"""Observability layer (serving/obs.py): flight recorder, request
timelines, debug endpoints, per-priority metrics, exposition format.

The load-bearing properties (ISSUE 12 acceptance):
- observability NEVER changes output: obs on/off is bit-token-identical
  (the serving_bench --obs-ab pin covers throughput);
- a killed replica's flight-recorder dump contains the final steps
  before the death;
- a migrated request's merged timeline spans both replicas under ONE
  request id;
- `prometheus_render` emits valid exposition: cumulative `le` buckets
  monotone non-decreasing, `+Inf` == `_count`, label values escaped;
- no RecordEvent span leaks on any terminal path (quarantine, abort,
  replica death included).
"""
import json
import os
import re
import sys
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import (EngineObs, FlightRecorder, Histogram,
                                RequestTracer, SamplingParams,
                                ServingEngine, ServingMetrics,
                                prometheus_render, resolve_debug_flag,
                                resolve_flight_steps, resolve_obs_flag,
                                timeline_to_chrome)
from paddle_tpu.serving.http import EngineDriver, Router, serve

_MODELS = {}


def tiny_gpt():
    m = _MODELS.get("gpt")
    if m is None:
        paddle.seed(7)
        cfg = GPTConfig(vocab_size=97, hidden_size=32,
                        num_hidden_layers=2, num_attention_heads=4,
                        intermediate_size=64,
                        max_position_embeddings=128,
                        hidden_dropout_prob=0.0,
                        attention_probs_dropout_prob=0.0)
        m = _MODELS["gpt"] = GPTForCausalLM(cfg)
        m.eval()
    return m


# -- exposition-format validation helpers -----------------------------------
_SERIES_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{([^{}]*)\})? (\S+)$")
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse_exposition(text):
    """Strict-enough parser: every non-comment line must match the
    exposition shape; returns [(name, {label: value}, float)]."""
    out = []
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _SERIES_RE.match(line)
        assert m, f"invalid exposition line: {line!r}"
        labels = {}
        body = m.group(3) or ""
        consumed = ",".join(f'{k}="{v}"'
                            for k, v in _LABEL_RE.findall(body))
        # every byte of the label body must be consumed by valid
        # name="escaped-value" pairs — unescaped quotes/newlines fail
        assert consumed == body, f"bad label body: {body!r}"
        for k, v in _LABEL_RE.findall(body):
            labels[k] = v
        out.append((m.group(1), labels, float(m.group(4))))
    return out


def check_histograms(series):
    """Every `<name>_bucket` family: cumulative counts monotone
    non-decreasing in le order and the +Inf bucket == _count."""
    hists = {}
    for name, labels, val in series:
        if name.endswith("_bucket"):
            key = (name[:-len("_bucket")],
                   tuple(sorted((k, v) for k, v in labels.items()
                                if k != "le")))
            hists.setdefault(key, []).append((labels["le"], val))
    assert hists, "no histogram series rendered"
    counts = {(n, tuple(sorted(la.items()))): v
              for n, la, v in series if n.endswith("_count")}
    for (base, lab_key), buckets in hists.items():
        def le_key(le):
            return float("inf") if le == "+Inf" else float(le)
        ordered = sorted(buckets, key=lambda b: le_key(b[0]))
        vals = [v for _, v in ordered]
        assert vals == sorted(vals), (base, ordered)
        assert ordered[-1][0] == "+Inf", (base, ordered)
        cnt = counts.get((base + "_count", lab_key))
        assert cnt is not None, (base, lab_key)
        assert ordered[-1][1] == cnt, (base, ordered, cnt)


class TestExpositionFormat:
    def test_histogram_cumulative_buckets_monotone_inf_equals_count(self):
        h = Histogram(buckets=(0.1, 1.0, 10.0))
        rng = np.random.RandomState(0)
        for v in rng.exponential(1.0, size=500):
            h.record(float(v))
        cum = h.cumulative_buckets()
        vals = [n for _, n in cum]
        assert vals == sorted(vals)
        assert cum[-1] == (float("inf"), 500)
        assert h.count == 500

    def test_prometheus_render_is_valid_exposition(self):
        """End-to-end: a populated ServingMetrics renders into lines
        the strict parser accepts, with monotone cumulative buckets
        and +Inf == _count for EVERY histogram family."""
        m = ServingMetrics()

        class _R:
            pass

        rng = np.random.RandomState(1)
        for i in range(40):
            r = _R()
            r.sampling = SamplingParams(max_new_tokens=4,
                                        priority=i % 3,
                                        deadline_s=1.0)
            r.output_tokens = [1]
            r.arrival_t = 0.0
            r.finish_reason = "stop" if i % 4 else "deadline"
            m.on_token(r, float(rng.exponential(0.1)))
            m.on_inter_token(float(rng.exponential(0.01)),
                             priority=i % 3)
            m.on_finish(r, float(rng.exponential(0.5)))
        text = prometheus_render({"replica-0": m.snapshot()})
        series = parse_exposition(text)
        check_histograms(series)

    def test_label_values_escaped(self):
        """Backslash, quote and newline in a replica label must not
        break the exposition line."""
        m = ServingMetrics()
        evil = 'rep"li\\ca\nzero'
        text = prometheus_render({evil: m.snapshot()})
        series = parse_exposition(text)     # parser rejects raw bytes
        rendered = {la["replica"] for _, la, _ in series
                    if "replica" in la}
        assert 'rep\\"li\\\\ca\\nzero' in rendered

    def test_per_priority_series_and_deadline_goodput(self):
        m = ServingMetrics()

        class _R:
            pass

        for prio, reason in ((0, "stop"), (5, "deadline")):
            r = _R()
            r.sampling = SamplingParams(max_new_tokens=4,
                                        priority=prio, deadline_s=1.0)
            r.output_tokens = [1]
            r.arrival_t = 0.0
            r.finish_reason = reason
            m.on_token(r, 0.01)
            m.on_finish(r, 0.5)
        m.on_inter_token(0.005, priority=5)
        snap = m.snapshot()
        assert snap["deadline_goodput"] == {"met": 1, "missed": 1}
        assert set(snap["by_priority"]) == {"0", "5"}
        text = prometheus_render({"r0": snap})
        series = parse_exposition(text)
        prio_ttft = [(la, v) for n, la, v in series
                     if n.endswith("ttft_seconds_count")
                     and "priority" in la]
        assert {la["priority"] for la, _ in prio_ttft} == {"0", "5"}
        dg = {la["outcome"]: v for n, la, v in series
              if n.endswith("deadline_goodput_total")}
        assert dg == {"met": 1.0, "missed": 1.0}

    def test_priority_class_cardinality_capped(self):
        m = ServingMetrics()
        for p in range(50):
            m.on_inter_token(0.001, priority=p)
        snap = m.snapshot()
        assert len(snap["by_priority"]) <= 9      # 8 classes + other
        assert "other" in snap["by_priority"]


class TestObsUnits:
    def test_tracer_bounded_evicts_finished_first(self):
        tr = RequestTracer(max_requests=2)
        tr.record("a", "submit")
        tr.record("a", "finish")
        tr.record("b", "submit")         # live
        tr.record("c", "submit")         # evicts finished "a", not "b"
        assert tr.timeline("a") is None
        assert tr.timeline("b") is not None
        assert tr.timeline("c") is not None
        assert tr.stats()["timelines_evicted"] == 1

    def test_tracer_per_timeline_event_cap(self):
        tr = RequestTracer(max_events=3)
        for i in range(10):
            tr.record("a", "prefill_chunk", tokens=i)
        tl = tr.timeline("a")
        assert len(tl) == 3
        assert tl[-1]["dropped"] == 7

    def test_flight_ring_bounded_and_env_knob(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_FLIGHT_STEPS", "4")
        assert resolve_flight_steps() == 4
        fr = FlightRecorder()
        for i in range(10):
            fr.on_step({"step": i})
        snap = fr.snapshot()
        assert snap["capacity"] == 4
        assert [r["step"] for r in snap["steps"]] == [6, 7, 8, 9]
        assert snap["steps_recorded"] == 10
        with pytest.raises(ValueError):
            resolve_flight_steps("zero")
        with pytest.raises(ValueError):
            resolve_flight_steps(0)

    def test_incident_freezes_ring(self):
        fr = FlightRecorder(steps=8)
        for i in range(3):
            fr.on_step({"step": i})
        dump = fr.incident("replica_death", detail="boom", step=3)
        fr.on_step({"step": 99})         # later steps don't mutate it
        assert [r["step"] for r in dump["steps"]] == [0, 1, 2]
        snap = fr.snapshot()
        assert len(snap["incidents"]) == 1
        assert [r["step"] for r in snap["incidents"][0]["steps"]] \
            == [0, 1, 2]
        assert snap["incidents"][0]["kind"] == "replica_death"

    def test_resolve_flags(self, monkeypatch):
        assert resolve_obs_flag() is True              # default on
        assert resolve_obs_flag(False) is False
        assert resolve_debug_flag() is False           # default OFF
        assert resolve_debug_flag(True) is True
        monkeypatch.setenv("PADDLE_TPU_OBS", "off")
        assert resolve_obs_flag() is False
        monkeypatch.setenv("PADDLE_TPU_DEBUG", "on")
        assert resolve_debug_flag() is True
        monkeypatch.setenv("PADDLE_TPU_OBS", "banana")
        with pytest.raises(ValueError):
            resolve_obs_flag()

    def test_flight_dump_renderer(self):
        sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                        os.pardir, "scripts"))
        from flight_dump import render
        fr = FlightRecorder(steps=8)
        for i in range(3):
            fr.on_step({"step": i, "queue_depth": i, "residents": 1,
                        "prefill_tokens": 0, "decode_tokens": 1,
                        "step_wall_ms": 1.5})
        fr.note("fault:kill", "pump raises at step 3")
        fr.incident("replica_death", detail="boom", step=3)
        text = render({"replica-0": fr.snapshot(), "replica-1": None})
        assert "replica-0" in text and "observability off" in text
        assert "incident 0: replica_death" in text
        assert "fault:kill" in text
        rows = [ln for ln in text.splitlines()
                if ln and ln.lstrip()[:1].isdigit()]
        assert len(rows) >= 6        # 3 ring rows + 3 incident rows

    def test_timeline_to_chrome_spans_phases(self):
        tl = [{"t": 0.0, "kind": "submit", "replica": "r0"},
              {"t": 1.0, "kind": "admit", "replica": "r0"},
              {"t": 2.0, "kind": "decode", "replica": "r0"},
              {"t": 3.0, "kind": "replica_death", "replica": "r0"},
              {"t": 3.5, "kind": "migrate", "replica": "r1"},
              {"t": 4.0, "kind": "finish", "replica": "r1"}]
        trace = timeline_to_chrome(tl, "cmpl-9")
        names = [e["name"] for e in trace["traceEvents"]]
        assert "cmpl-9:queued" in names
        assert "cmpl-9:prefill" in names
        assert "cmpl-9:decode" in names
        assert trace["otherData"]["replicas"] == ["r0", "r1"]
        spans = {e["name"]: e for e in trace["traceEvents"]}
        assert spans["cmpl-9:queued"]["dur"] == pytest.approx(1e6)
        # two replicas -> two tid lanes
        assert len({e["tid"] for e in trace["traceEvents"]}) == 2


class TestEngineObs:
    def test_timeline_lifecycle_and_token_identity(self):
        model = tiny_gpt()
        prompt = np.array([3, 14, 15, 9, 2, 6], np.int64)
        outs = {}
        for flag in (True, False):
            eng = ServingEngine(model, num_slots=2, max_len=64,
                                chunk_len=8, obs=flag)
            r = eng.add_request(prompt,
                                SamplingParams(max_new_tokens=8))
            eng.run()
            outs[flag] = list(r.output_tokens)
            if flag:
                tl = eng.obs.tracer.timeline(r.request_id)
                kinds = [e["kind"] for e in tl]
                assert kinds[0] == "submit"
                assert kinds[-1] == "finish"
                assert kinds.index("submit") < kinds.index("admit") \
                    < kinds.index("decode") < kinds.index("first_token")
                assert "prefill_chunk" in kinds
                steps = [e["step"] for e in tl]
                assert steps == sorted(steps)
                admit = tl[kinds.index("admit")]
                assert admit["slot"] == r.slot or admit["slot"] in (0, 1)
                assert tl[-1]["cause"] == "length"
                assert tl[-1]["tokens"] == 8
            else:
                assert eng.obs is None
        assert outs[True] == outs[False]

    def test_flight_records_match_metrics(self):
        model = tiny_gpt()
        eng = ServingEngine(model, num_slots=2, max_len=64,
                            chunk_len=8)
        for i in range(3):
            eng.add_request(np.arange(1, 5 + i, dtype=np.int64),
                            SamplingParams(max_new_tokens=4))
        eng.run()
        snap = eng.obs.flight.snapshot()
        assert snap["steps_recorded"] == eng._step_idx
        decode_total = sum(r["decode_tokens"] for r in snap["steps"])
        prefill_total = sum(r["prefill_tokens"] for r in snap["steps"])
        ms = eng.metrics.snapshot()
        assert decode_total == ms["packed_decode_tokens"]
        assert prefill_total == ms["prefill_chunk_tokens"]
        # composition rides per record
        busy = [r for r in snap["steps"] if r["residents"]]
        assert busy and all(len(r["slots"]) == r["residents"]
                            for r in busy)

    def test_quarantine_records_incident_and_closes_span(self):
        """A poisoned round leaves an incident dump and no leaked
        span for the quarantined request."""
        model = tiny_gpt()
        eng = ServingEngine(model, num_slots=2, max_len=64,
                            chunk_len=8)
        good = eng.add_request(np.array([3, 14, 15, 9], np.int64),
                               SamplingParams(max_new_tokens=4))
        bad = eng.add_request(np.array([5, 6, 7], np.int64),
                              SamplingParams(max_new_tokens=4))

        def hook(ids, _bad=bad.request_id):
            if _bad in ids:
                raise RuntimeError("poisoned step")

        eng.step_fault_hook = hook
        eng.run()
        assert bad.finish_reason == "poisoned"
        assert good.finish_reason in ("stop", "length")
        snap = eng.obs.flight.snapshot()
        kinds = [i["kind"] for i in snap["incidents"]]
        assert "step_fault" in kinds and "poison_quarantine" in kinds
        tl = eng.obs.tracer.timeline(bad.request_id)
        assert tl[-1]["kind"] == "poison"

    def test_abort_all_closes_spans_even_when_teardown_raises(self):
        """A teardown that raises midway (the replica-death path)
        leaves no span open: every span is a `with` block inside one
        round, and a request's residency is the tracer's timeline, not
        a span held across rounds."""
        model = tiny_gpt()
        eng = ServingEngine(model, num_slots=2, max_len=64,
                            chunk_len=8)
        r = eng.add_request(np.array([3, 14, 15, 9], np.int64),
                            SamplingParams(max_new_tokens=16))
        with profiler.Profiler(
                targets=[profiler.ProfilerTarget.CPU]) as p:
            eng.step()
            eng.pool.free = lambda pages: (_ for _ in ()).throw(
                RuntimeError("torn pool"))
            with pytest.raises(RuntimeError):
                eng.abort_all("replica_failure")
        assert eng.closed
        spans = p.aggregate()
        assert spans["serving::admit"]["calls"] == 1
        assert not any(n.startswith("serving::request") for n in spans)
        assert not hasattr(eng, "_spans")
        kinds = [e["kind"] for e in eng.obs.tracer.timeline(r.request_id)]
        assert kinds[:2] == ["submit", "admit"]

    def test_cancelled_queued_request_fully_retired(self):
        """cancel() of a queued request now runs the shared terminal
        path: the id leaves _requests (reusable) and obs records the
        terminal event."""
        model = tiny_gpt()
        eng = ServingEngine(model, num_slots=1, max_len=64,
                            chunk_len=8)
        r0 = eng.add_request(np.array([3, 14, 15], np.int64),
                             SamplingParams(max_new_tokens=4))
        eng.step()                                  # r0 takes the slot
        rq = eng.add_request(np.array([4, 5, 6], np.int64),
                             SamplingParams(max_new_tokens=4),
                             request_id="victim")
        assert eng.cancel("victim")
        assert "victim" not in eng._requests
        tl = eng.obs.tracer.timeline("victim")
        assert [e["kind"] for e in tl] == ["submit", "cancelled"]
        # the id is reusable immediately
        eng.add_request(np.array([4, 5], np.int64),
                        SamplingParams(max_new_tokens=2),
                        request_id="victim")
        eng.run()
        assert r0.finish_reason in ("stop", "length")
        assert rq.finish_reason == "cancelled"

    def test_debug_state_snapshot(self):
        model = tiny_gpt()
        eng = ServingEngine(model, num_slots=2, max_len=64,
                            chunk_len=8)
        eng.add_request(np.array([3, 14, 15, 9], np.int64),
                        SamplingParams(max_new_tokens=16))
        eng.add_request(np.array([4, 5, 6], np.int64),
                        SamplingParams(max_new_tokens=4, priority=2))
        eng.step()
        st = eng.debug_state()
        assert st["num_slots"] == 2
        assert len(st["residents"]) >= 1
        res = st["residents"][0]
        assert {"slot", "request_id", "state", "pages",
                "priority"} <= set(res)
        assert st["pool"]["pages_total"] == eng.num_pages - 1
        assert st["config"]["grouped"] is True
        assert st["obs"]["flight"]["steps_recorded"] == 1
        json.dumps(st)                   # endpoint-serializable
        eng.run()


def oracle_greedy(model, prompt, n_new):
    out = model.generate(paddle.to_tensor(np.asarray(prompt)[None]),
                         max_new_tokens=n_new).numpy()
    return out[0, len(prompt):].tolist()


class TestChaosObservability:
    def test_killed_replica_dump_and_merged_timeline(self):
        """ISSUE acceptance: kill the serving replica mid-stream —
        the dead replica's flight recorder holds an incident dump
        whose steps reach its final recorded step, and the migrated
        request's merged timeline spans BOTH replicas under the one
        ticket id."""
        model = tiny_gpt()
        engines = [ServingEngine(model, num_slots=2, max_len=64)
                   for _ in range(2)]
        for e in engines:
            e.generate([np.array([1, 2, 3])],
                       SamplingParams(max_new_tokens=2))
        drivers = [EngineDriver(e, name=f"replica-{i}")
                   for i, e in enumerate(engines)]
        router = Router(drivers).start()
        prompt = [3, 14, 15, 9]
        want = oracle_greedy(model, prompt, 24)
        t = router.submit(np.array(prompt, np.int64),
                          SamplingParams(max_new_tokens=24))
        victim = t.driver
        tokens = []
        for kind, val in t.events(poll_s=0.01):
            if kind == "token":
                tokens.append(val)
                if len(tokens) == 3 and not victim.dead:
                    victim.kill()
            elif kind == "done":
                break
        assert tokens == want and t.migrations == 1
        # half 1: the dead replica's black box survived the death
        dead_obs = victim.engine.obs
        snap = dead_obs.flight.snapshot()
        deaths = [i for i in snap["incidents"]
                  if i["kind"] == "replica_death"]
        assert deaths, snap["incidents"]
        dump = deaths[-1]
        assert dump["steps"], "dump lost the pre-death steps"
        last_steps = [r["step"] for r in dump["steps"]
                      if "step" in r]
        assert last_steps[-1] == victim.engine._step_idx
        # the victim's final resident set includes our request
        busy = [r for r in dump["steps"] if r["residents"]]
        assert any(t.id in [s[1] for s in r["slots"]] for r in busy)
        # half 2: ONE merged timeline across both replicas
        tl = router.request_timeline(t.id)
        replicas = {e["replica"] for e in tl}
        assert replicas == {"replica-0", "replica-1"}
        kinds = [e["kind"] for e in tl]
        assert "migrate" in kinds
        assert kinds.count("submit") == 2        # one per attempt
        assert "replica_death" in kinds          # terminal on victim
        assert kinds[-1] == "finish"             # survivor delivered
        mig = tl[kinds.index("migrate")]
        assert mig["cause"] == f"replica_death:{victim.name}"
        # chrome export spans both lanes
        trace = timeline_to_chrome(tl, t.id)
        assert len({e["tid"] for e in trace["traceEvents"]}) == 2
        router.drain()


class TestDebugEndpoints:
    def _post(self, host, port, body):
        import http.client
        conn = http.client.HTTPConnection(host, port, timeout=60)
        conn.request("POST", "/v1/completions", json.dumps(body),
                     {"Content-Type": "application/json"})
        return conn, conn.getresponse()

    def _get(self, host, port, path):
        import http.client
        conn = http.client.HTTPConnection(host, port, timeout=60)
        conn.request("GET", path)
        resp = conn.getresponse()
        body = resp.read()
        conn.close()
        return resp.status, body

    def test_debug_gate_off_by_default(self):
        model = tiny_gpt()
        server = serve([ServingEngine(model, num_slots=2, max_len=64)],
                       poll_interval_s=0.01)
        try:
            host, port = server.server_address[:2]
            status, body = self._get(host, port, "/debug/state")
            assert status == 403
            assert json.loads(body)["error"]["type"] == "forbidden"
        finally:
            server.drain()

    def test_debug_endpoints_end_to_end(self):
        """POST a client-named request, then pull its timeline (JSON
        + chrome), the engine state, and the flight ring over HTTP."""
        model = tiny_gpt()
        server = serve([ServingEngine(model, num_slots=2, max_len=64)],
                       poll_interval_s=0.01, debug_endpoints=True)
        try:
            host, port = server.server_address[:2]
            conn, resp = self._post(host, port,
                                    {"prompt": [3, 14, 15, 9],
                                     "max_tokens": 6,
                                     "request_id": "my-request.1"})
            body = json.loads(resp.read())
            conn.close()
            assert resp.status == 200
            assert body["id"] == "my-request.1"
            assert len(body["choices"][0]["token_ids"]) == 6

            status, raw = self._get(host, port, "/debug/state")
            assert status == 200
            st = json.loads(raw)
            assert "replica-0" in st["replicas"]
            assert st["replicas"]["replica-0"]["num_slots"] == 2

            status, raw = self._get(host, port,
                                    "/debug/requests/my-request.1")
            assert status == 200
            tl = json.loads(raw)
            kinds = [e["kind"] for e in tl["events"]]
            assert kinds[0] == "submit" and kinds[-1] == "finish"
            assert all(e["replica"] == "replica-0"
                       for e in tl["events"])

            status, raw = self._get(
                host, port,
                "/debug/requests/my-request.1?format=chrome")
            assert status == 200
            trace = json.loads(raw)
            assert any(e["name"] == "my-request.1:decode"
                       for e in trace["traceEvents"])

            status, raw = self._get(host, port,
                                    "/debug/requests/nope")
            assert status == 404

            status, raw = self._get(host, port, "/debug/flight")
            assert status == 200
            flight = json.loads(raw)
            assert flight["replica-0"]["steps_recorded"] >= 6
            assert flight["replica-0"]["steps"]

            status, raw = self._get(host, port, "/debug/bogus")
            assert status == 404
        finally:
            server.drain()

    def test_duplicate_live_request_id_conflicts(self):
        """A client-named id colliding with a LIVE request maps to
        409, not a 500 traceback."""
        model = tiny_gpt()
        server = serve([ServingEngine(model, num_slots=2, max_len=64)],
                       poll_interval_s=0.01)
        try:
            host, port = server.server_address[:2]
            conn, resp = self._post(host, port,
                                    {"prompt": [3, 14, 15, 9],
                                     "max_tokens": 48, "stream": True,
                                     "request_id": "dup"})
            line = resp.readline()          # stream started
            assert line.startswith(b"data:")
            conn2, resp2 = self._post(host, port,
                                      {"prompt": [5], "max_tokens": 2,
                                       "request_id": "dup"})
            body = json.loads(resp2.read())
            conn2.close()
            assert resp2.status == 409, body
            while resp.readline().strip() != b"data: [DONE]":
                pass
            conn.close()
        finally:
            server.drain()

    def test_bad_request_id_rejected(self):
        model = tiny_gpt()
        server = serve([ServingEngine(model, num_slots=2, max_len=64)],
                       poll_interval_s=0.01)
        try:
            host, port = server.server_address[:2]
            conn, resp = self._post(host, port,
                                    {"prompt": [3], "max_tokens": 2,
                                     "request_id": "spaces not ok"})
            body = json.loads(resp.read())
            conn.close()
            assert resp.status == 400
            assert "request_id" in body["error"]["message"]
        finally:
            server.drain()


@pytest.mark.slow
def test_serving_bench_obs_ab_smoke(tmp_path, monkeypatch):
    """`serving_bench.py --smoke --obs-ab` (ISSUE acceptance): the
    deterministic burst replay with the obs layer off vs on lands in
    the schema-v11 report's "obs" section — token-identical, same
    step count in both arms, tokens/s inside the 3% pin, the flight
    ring populated, and flight_dump.py rendering a row per step."""
    import importlib.util
    script = os.path.join(os.path.dirname(__file__), os.pardir,
                          "scripts", "serving_bench.py")
    spec = importlib.util.spec_from_file_location(
        "serving_bench_obs", script)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = str(tmp_path / "BENCH_serving.json")
    monkeypatch.setattr(sys, "argv",
                        ["serving_bench.py", "--smoke", "--requests",
                         "4", "--obs-ab", "--out", out])
    mod.main()
    with open(out) as f:
        report = json.load(f)
    assert report["schema_version"] == 19
    ob = report["obs"]
    assert ob["token_identical"]
    assert ob["on"]["decode_steps"] == ob["off"]["decode_steps"]
    assert ob["tokens_per_sec_ratio"] >= 1.0 - ob["noise_pin"]
    assert ob["flight_steps_recorded"] >= ob["on"]["decode_steps"]
    assert ob["flight_dump_rows"] >= ob["on"]["decode_steps"]
    assert ob["timelines_recorded"] >= ob["requests"]
