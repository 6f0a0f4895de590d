"""Fleet SLO observability (serving/slo.py + PR 15 wiring): burn-rate
windows, cost census, achieved utilization, per-tenant labels, fleet
view.

The load-bearing properties (ISSUE 15 acceptance):
- SLO + census on vs off is bit-token-identical (the serving_bench
  --obs-ab pin covers throughput);
- the cost census is captured EXACTLY once per compiled step and the
  retrace probe still sees cache_size 1 (AOT lowering never touches
  the jit dispatch cache);
- burn-rate states follow the multi-window rule with an injectable
  clock: both windows must burn to escalate, the fast window alone
  de-escalates; per-class series are isolated; label cardinality is
  capped;
- `Router.fleet_snapshot()` (GET /debug/fleet) merges both replicas'
  SLO + census state, and a killed replica's final SLO state
  survives in its incident dump;
- every new Prometheus series passes the strict PR-12 exposition
  parser.
"""
import json
import os
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import (SamplingParams, ServingEngine,
                                ServingMetrics, SLOConfig, SLOTracker,
                                model_cost_census, prometheus_render,
                                resolve_cost_census,
                                resolve_slo_config)
from paddle_tpu.serving.http import EngineDriver, Router, serve

from test_serving_obs import check_histograms, parse_exposition

sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                os.pardir, "scripts"))

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


def tracker(clock, **kw):
    """A tight test config: 10s fast / 100s slow windows, alert on a
    single event, burn thresholds warn 2 / page 10."""
    fields = dict(ttft_p99_s=1.0, itl_p99_s=0.1, goodput=0.99,
                  fast_window_s=10.0, slow_window_s=100.0,
                  warn_burn=2.0, page_burn=10.0, min_events=1)
    fields.update(kw.pop("cfg", {}))
    return SLOTracker(SLOConfig(**fields), clock=clock, **kw)


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


class TestSLOConfig:
    def test_spec_parsing(self):
        cfg = resolve_slo_config(
            "ttft_p99=0.25,itl_p99=0.05,goodput=0.995,fast=30,"
            "slow=300,warn=3,page=14.4,min_events=5")
        assert cfg.ttft_p99_s == 0.25
        assert cfg.itl_p99_s == 0.05
        assert cfg.goodput == 0.995
        assert cfg.fast_window_s == 30 and cfg.slow_window_s == 300
        assert cfg.warn_burn == 3 and cfg.page_burn == 14.4
        assert cfg.min_events == 5
        # goodput budget = 1 - target; latency budgets are p99
        assert cfg.budget("goodput") == pytest.approx(0.005)
        assert cfg.budget("ttft_p99") == 0.01

    def test_spec_off_on_and_env(self, monkeypatch):
        assert resolve_slo_config(False) is None
        assert resolve_slo_config("off") is None
        assert resolve_slo_config() == SLOConfig()
        monkeypatch.setenv("PADDLE_TPU_SLO", "off")
        assert resolve_slo_config() is None
        monkeypatch.setenv("PADDLE_TPU_SLO", "ttft_p99=0.5")
        assert resolve_slo_config().ttft_p99_s == 0.5
        # explicit override beats the env
        assert resolve_slo_config("on") == SLOConfig()

    def test_spec_errors(self):
        with pytest.raises(ValueError):
            resolve_slo_config("bogus_key=1")
        with pytest.raises(ValueError):
            resolve_slo_config("ttft_p99")          # not k=v
        with pytest.raises(ValueError):
            resolve_slo_config("goodput=1.5")       # out of (0,1)
        with pytest.raises(ValueError):
            resolve_cost_census("banana")


class TestBurnRate:
    def test_all_good_stays_ok(self):
        clk = FakeClock()
        tr = tracker(clk)
        for _ in range(50):
            tr.on_ttft(0.01)
            clk.t += 0.1
        assert tr.worst_state() == "ok"
        snap = tr.snapshot()
        s = snap["series"]["ttft_p99"]["all"]
        assert s["state"] == "ok" and s["fast_burn"] == 0.0

    def test_bad_burst_pages_then_fast_window_recovers(self):
        """The multi-window property: a bad burst pages (both windows
        burn), then good traffic — the fast window rotates the burst
        out and the state de-escalates long before the SLOW window
        forgets it."""
        clk = FakeClock()
        tr = tracker(clk)
        for _ in range(20):                 # all-bad burst at t~0
            tr.on_ttft(5.0)                 # > 1.0s target
            clk.t += 0.1
        assert tr.worst_state() == "page"
        # good traffic for a little over one FAST window
        for _ in range(120):
            tr.on_ttft(0.01)
            clk.t += 0.1
        # fast window (10s) no longer holds the burst -> recovered,
        # even though the slow window (100s) still remembers it
        snap = tr.snapshot()
        s = snap["series"]["ttft_p99"]["all"]
        assert s["state"] == "ok", s
        assert s["slow_burn"] > tr.config.warn_burn, s
        # the page -> ok journey landed in the transition log
        kinds = [(t["from"], t["to"]) for t in snap["transitions"]
                 if t["scope"] == "all" and t["slo"] == "ttft_p99"]
        assert ("ok", "page") in kinds
        assert kinds[-1][1] == "ok"

    def test_states_reevaluate_without_new_events(self):
        """A scrape after the bad traffic STOPPED must still see the
        fast window drain (states are re-evaluated at read time)."""
        clk = FakeClock()
        tr = tracker(clk)
        for _ in range(10):
            tr.on_inter_token(3.0)
            clk.t += 0.1
        assert tr.worst_state() == "page"
        clk.t += 300.0                      # silence > both windows
        assert tr.worst_state() == "ok"

    def test_min_events_gate(self):
        clk = FakeClock()
        tr = tracker(clk, cfg={"min_events": 10})
        for _ in range(9):
            tr.on_ttft(5.0)
        assert tr.worst_state() == "ok"     # not enough evidence
        tr.on_ttft(5.0)
        assert tr.worst_state() == "page"

    def test_goodput_slo(self):
        clk = FakeClock()
        tr = tracker(clk)
        for i in range(100):
            tr.on_goodput(i % 5 != 0)       # 20% missed >> 1% budget
            clk.t += 0.05
        assert tr.snapshot()["series"]["goodput"]["all"]["state"] \
            == "page"

    def test_per_class_isolation(self):
        """Only priority 1 burns; priority 0 stays ok (the aggregate
        burns too — half its traffic is bad)."""
        clk = FakeClock()
        tr = tracker(clk)
        for _ in range(30):
            tr.on_ttft(0.01, priority=0)
            tr.on_ttft(9.0, priority=1)
            clk.t += 0.1
        st = tr.states()["ttft_p99"]
        assert st["priority:0"] == "ok"
        assert st["priority:1"] == "page"
        assert st["all"] == "page"

    def test_adapter_scope_and_label_cap(self):
        clk = FakeClock()
        tr = tracker(clk, track_adapters=True, max_label_classes=4)
        for aid in range(20):
            tr.on_ttft(0.01, adapter_id=aid, priority=aid)
            clk.t += 0.01
        st = tr.states()["ttft_p99"]
        adapters = [k for k in st if k.startswith("adapter:")]
        prios = [k for k in st if k.startswith("priority:")]
        assert len(adapters) == 5 and "adapter:other" in adapters
        assert len(prios) == 5 and "priority:other" in prios
        # without adapter tracking the scope does not exist
        tr2 = tracker(clk)
        tr2.on_ttft(0.01, adapter_id=3)
        assert not any(k.startswith("adapter:")
                       for k in tr2.states()["ttft_p99"])

    def test_transition_callback_and_reset(self):
        clk = FakeClock()
        fired = []
        tr = tracker(clk, on_transition=fired.append)
        for _ in range(5):
            tr.on_ttft(9.0)
            clk.t += 0.1
        assert fired and fired[0]["to"] in ("warn", "page")
        assert fired[0]["slo"] == "ttft_p99"
        tr.reset()
        assert tr.events_total == 0
        assert tr.snapshot()["series"] == {}


class TestCostCensus:
    def test_model_census_captured_once_by_default(self):
        eng = ServingEngine(tiny_gpt(), num_slots=2, max_len=64,
                            chunk_len=8)
        assert eng.census_mode == "model"
        eng.add_request(np.array([3, 14, 15, 9], np.int64),
                        SamplingParams(max_new_tokens=4))
        eng.run()
        c = eng.cost_census()
        assert c["source"] == "model"
        assert c["flops"] > 0 and c["bytes_accessed"] > 0
        assert c["capacity_tokens"] == 2 * 8
        assert c["flops_per_token"] == pytest.approx(
            c["flops"] / 16)
        # exactly once per compile, and reads return the same record
        assert eng._census_captures == 1
        assert eng.cost_census() is c
        assert eng._census_captures == 1
        # the record rides the metrics snapshot + debug state
        assert eng.metrics.snapshot()["cost_census"] == c
        assert eng.debug_state()["cost_census"] == c

    def test_lowered_census_and_no_retrace(self):
        """The XLA-backed source: real HLO cost-analysis numbers, one
        capture, and the AOT lowering leaves the jit dispatch cache
        at exactly 1 entry (the retrace-probe contract)."""
        eng = ServingEngine(tiny_gpt(), num_slots=2, max_len=64,
                            chunk_len=8, cost_census="lowered")
        eng.add_request(np.array([3, 14, 15, 9], np.int64),
                        SamplingParams(max_new_tokens=4))
        eng.run()
        c = eng.cost_census()
        assert c["source"] == "lowered"
        assert c["flops"] > 0 and c["bytes_accessed"] > 0
        assert eng._census_captures == 1
        assert eng._unified_fn._cache_size() == 1

    def test_census_off_and_env(self, monkeypatch):
        eng = ServingEngine(tiny_gpt(), num_slots=2, max_len=64,
                            chunk_len=8, cost_census=False)
        assert eng.census_mode == "off"
        assert eng.cost_census() is None
        monkeypatch.setenv("PADDLE_TPU_COST_CENSUS", "lowered")
        assert resolve_cost_census() == "lowered"
        assert resolve_cost_census(False) == "off"

    def test_model_census_scales_with_geometry(self):
        base = dict(n_params=1000, param_bytes=4000, num_slots=4,
                    chunk_len=8, max_pages=4, page_bytes=1024,
                    n_heads=4, head_dim=8, page_size=16)
        a = model_cost_census(**base)
        b = model_cost_census(**{**base, "num_slots": 8})
        assert b["flops"] > a["flops"]
        assert b["bytes_accessed"] > a["bytes_accessed"]
        # mp shards the page walk per chip
        c = model_cost_census(**{**base, "mp": 2})
        assert c["bytes_accessed"] < a["bytes_accessed"]

    def test_achieved_util_in_flight_and_dump(self):
        eng = ServingEngine(tiny_gpt(), num_slots=2, max_len=64,
                            chunk_len=8)
        for i in range(3):
            eng.add_request(np.arange(1, 5 + i, dtype=np.int64),
                            SamplingParams(max_new_tokens=4))
        eng.run()
        steps = [r for r in eng.obs.flight.snapshot()["steps"]
                 if "step" in r]
        assert steps
        for rec in steps:
            assert 0.0 <= rec["achieved_util"] <= 1.0
            assert rec["slo"] == "ok"
        packed = [rec["prefill_tokens"] + rec["decode_tokens"]
                  + rec["draft_tokens"] for rec in steps]
        assert any(p > 0 for p in packed)
        busy = next(r for r, p in zip(steps, packed) if p > 0)
        assert busy["achieved_util"] == pytest.approx(
            (busy["prefill_tokens"] + busy["decode_tokens"]
             + busy["draft_tokens"]) / 16, abs=1e-4)
        # metrics histogram agrees step-for-step: a round records the
        # step it committed, the one the round before launched (the
        # first round commits none)
        au = eng.metrics.snapshot()["achieved_util"]
        assert packed[0] == 0
        assert au["count"] == sum(p > 0 for p in packed) == len(steps) - 1
        # flight_dump renders the new columns
        from flight_dump import render_flight
        text = render_flight(eng.obs.flight.snapshot())
        header = text.splitlines()[1]
        assert "util" in header and "slo" in header
        rows = [ln for ln in text.splitlines()
                if ln and ln.lstrip()[:1].isdigit()]
        assert len(rows) == len(steps)


class TestEngineSLO:
    def test_slo_on_off_token_identical(self):
        prompt = np.array([3, 14, 15, 9, 2, 6], np.int64)
        outs = {}
        for flag in (True, False):
            eng = ServingEngine(tiny_gpt(), num_slots=2, max_len=64,
                                chunk_len=8, slo=flag,
                                cost_census=("model" if flag
                                             else False))
            r = eng.add_request(prompt,
                                SamplingParams(max_new_tokens=8))
            eng.run()
            outs[flag] = list(r.output_tokens)
            assert (eng.slo is not None) is flag
        assert outs[True] == outs[False]

    def test_burning_engine_notes_flight_and_renders(self):
        """Impossible targets: every event is bad -> the tracker
        pages, the transition lands as a flight-recorder note (the
        "SLO was already burning" context), and the new series pass
        the strict exposition parser."""
        eng = ServingEngine(
            tiny_gpt(), num_slots=2, max_len=64, chunk_len=8,
            slo=SLOConfig(ttft_p99_s=1e-9, itl_p99_s=1e-9,
                          min_events=1))
        eng.add_request(np.array([3, 14, 15, 9], np.int64),
                        SamplingParams(max_new_tokens=8,
                                       deadline_s=60.0))
        eng.run()
        assert eng.slo.worst_state() == "page"
        notes = [r for r in eng.obs.flight.snapshot()["steps"]
                 if "note" in r]
        assert any(n["note"] == "slo:page" for n in notes)
        # step records carry the worst state of their moment
        assert any(r.get("slo") == "page"
                   for r in eng.obs.flight.snapshot()["steps"]
                   if "step" in r)
        snap = eng.metrics.snapshot()
        assert snap["slo"]["worst"] == "page"
        text = prometheus_render({"r0": snap})
        series = parse_exposition(text)
        check_histograms(series)
        states = {(la["slo"], la["scope"], la["label"]): v
                  for n, la, v in series
                  if n.endswith("slo_state")}
        assert states[("ttft_p99", "all", "")] == 2.0
        burns = [v for n, la, v in series
                 if n.endswith("slo_burn_rate")
                 and la["slo"] == "ttft_p99"
                 and la["scope"] == "all"]
        assert burns and all(b > 0 for b in burns)
        assert any(n.endswith("cost_census_flops")
                   for n, _, _ in series)
        assert any(n.endswith("achieved_util_bucket")
                   for n, _, _ in series)

    def test_engine_spec_string_gate(self):
        eng = ServingEngine(tiny_gpt(), num_slots=2, max_len=64,
                            chunk_len=8, slo="ttft_p99=0.25")
        assert eng.slo.config.ttft_p99_s == 0.25
        eng2 = ServingEngine(tiny_gpt(), num_slots=2, max_len=64,
                             chunk_len=8, slo="off")
        assert eng2.slo is None


class TestPerAdapterLabels:
    def _req(self, aid, prio=0, reason="stop", deadline=None):
        class _R:
            pass
        r = _R()
        r.sampling = SamplingParams(max_new_tokens=4, priority=prio,
                                    adapter_id=aid,
                                    deadline_s=deadline)
        r.output_tokens = [1]
        r.arrival_t = 0.0
        r.finish_reason = reason
        return r

    def test_by_adapter_series_and_goodput(self):
        m = ServingMetrics()
        m.adapters_enabled = True
        for aid, reason in ((0, "stop"), (3, "stop"),
                            (3, "deadline")):
            r = self._req(aid, reason=reason, deadline=1.0)
            m.on_token(r, 0.01)
            m.on_inter_token(0.005, adapter_id=aid)
            m.on_finish(r, 0.5)
        snap = m.snapshot()
        assert set(snap["by_adapter"]) == {"0", "3"}
        assert snap["by_adapter"]["3"]["deadline_goodput"] == \
            {"met": 1, "missed": 1}
        assert snap["by_adapter"]["0"]["ttft_s"]["count"] == 1
        text = prometheus_render({"r0": snap})
        series = parse_exposition(text)
        check_histograms(series)
        per_ad = {la["adapter"] for n, la, v in series
                  if n.endswith("ttft_seconds_count")
                  and "adapter" in la}
        assert per_ad == {"0", "3"}
        dg = {(la.get("adapter"), la["outcome"]): v
              for n, la, v in series
              if n.endswith("deadline_goodput_total")
              and "adapter" in la}
        assert dg[("3", "met")] == 1.0 and dg[("3", "missed")] == 1.0

    def test_adapter_label_cap_shared_with_counters(self):
        m = ServingMetrics()
        m.adapters_enabled = True
        for aid in range(20):
            m.on_adapter_request(aid)
            m.on_inter_token(0.005, adapter_id=aid)
        snap = m.snapshot()
        assert len(snap["by_adapter"]) <= 9
        assert "other" in snap["by_adapter"]
        # ONE label space: the ids the counters kept are exactly the
        # ids the latency series kept
        assert set(snap["by_adapter"]) == \
            set(snap["adapters"]["requests_by_adapter"]
                if snap["adapters"] else
                snap["by_adapter"])

    def test_no_adapter_series_on_base_engines(self):
        m = ServingMetrics()          # adapters_enabled stays None
        r = self._req(0)
        m.on_token(r, 0.01)
        m.on_inter_token(0.005)
        m.on_finish(r, 0.5)
        assert m.snapshot()["by_adapter"] == {}


def oracle_greedy(model, prompt, n_new):
    out = model.generate(paddle.to_tensor(np.asarray(prompt)[None]),
                         max_new_tokens=n_new).numpy()
    return out[0, len(prompt):].tolist()


class TestFleetView:
    def test_fleet_snapshot_merges_and_dead_slo_survives(self):
        """ISSUE acceptance: a 2-replica router's fleet snapshot
        carries both replicas' SLO + census state; killing one
        mid-stream leaves its final SLO state in BOTH the fleet view
        (dead replicas stay listed) and its incident dump."""
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
            elif kind in ("done", "error"):
                break
        assert tokens == want
        fleet = router.fleet_snapshot()
        json.dumps(fleet)                    # endpoint-serializable
        assert set(fleet["replicas"]) == {"replica-0", "replica-1"}
        assert fleet["slo_worst"] in ("ok", "warn", "page")
        for name, e in fleet["replicas"].items():
            assert e["slo"] is not None and "worst" in e["slo"]
            assert e["cost_census"]["flops"] > 0
            assert e["pool"]["pages_total"] > 0
            assert "achieved_util" in e
        assert fleet["replicas"][victim.name]["dead"] is True
        survivor = next(d for d in drivers if d is not victim)
        assert fleet["replicas"][survivor.name]["healthy"] is True
        assert fleet["replicas"][survivor.name][
            "tokens_generated"] > 0
        # the killed replica's incident dump froze its SLO state
        snap = victim.engine.obs.flight.snapshot()
        deaths = [i for i in snap["incidents"]
                  if i["kind"] == "replica_death"]
        assert deaths, snap["incidents"]
        assert deaths[-1].get("slo") is not None
        assert deaths[-1]["slo"]["worst"] in ("ok", "warn", "page")
        # driver stats surface the per-replica worst state
        assert survivor.stats()["slo_state"] in ("ok", "warn",
                                                 "page")
        # fleet_top renders one row per replica + the census footer
        from fleet_top import render_fleet
        text = render_fleet(fleet)
        assert "replica-0" in text and "replica-1" in text
        assert "DEAD" in text and "census[" in text
        # flight_dump auto-detects a fleet document
        from flight_dump import render
        assert "replica-0" in render(fleet)
        router.drain()

    def test_debug_fleet_endpoint(self):
        model = tiny_gpt()
        server = serve([ServingEngine(model, num_slots=2, max_len=64)
                        for _ in range(2)],
                       poll_interval_s=0.01, debug_endpoints=True)
        try:
            import http.client
            host, port = server.server_address[:2]
            conn = http.client.HTTPConnection(host, port, timeout=60)
            conn.request("POST", "/v1/completions",
                         json.dumps({"prompt": [3, 14, 15, 9],
                                     "max_tokens": 4}),
                         {"Content-Type": "application/json"})
            assert conn.getresponse().read()
            conn.close()
            conn = http.client.HTTPConnection(host, port, timeout=60)
            conn.request("GET", "/debug/fleet")
            resp = conn.getresponse()
            body = json.loads(resp.read())
            conn.close()
            assert resp.status == 200
            assert set(body["replicas"]) == {"replica-0",
                                             "replica-1"}
            assert body["router"]["ready"] is True
            assert body["slo_worst"] in ("ok", "warn", "page")
            for e in body["replicas"].values():
                assert e["cost_census"] is not None
                assert e["slo"] is not None
        finally:
            server.drain()


class TestBenchHistory:
    def _mod(self):
        import importlib.util
        script = os.path.join(os.path.dirname(__file__), os.pardir,
                              "scripts", "serving_bench.py")
        spec = importlib.util.spec_from_file_location(
            "serving_bench_hist", script)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def _report(self, tps, obs_tps=None):
        r = {"schema_version": 19, "platform": "cpu", "requests": 4,
             "tokens_per_sec": tps}
        if obs_tps is not None:
            r["obs"] = {"on": {"tokens_per_sec": obs_tps}}
        return r

    def test_entry_append_and_regression_sentinel(self, tmp_path):
        mod = self._mod()
        path = str(tmp_path / "BENCH_history.jsonl")
        e1 = mod.bench_history_entry(self._report(100.0, 200.0),
                                     t=1000.0)
        assert e1["sections"] == {"serving": 100.0, "obs": 200.0}
        assert e1["schema_version"] == 19 and e1["git_rev"]
        assert mod.append_bench_history(path, e1) == []
        # a small dip stays quiet...
        e2 = mod.bench_history_entry(self._report(95.0, 195.0),
                                     t=2000.0)
        assert mod.append_bench_history(path, e2) == []
        # ...a > 10% drop warns, naming the section
        e3 = mod.bench_history_entry(self._report(50.0, 194.0),
                                     t=3000.0)
        warnings = mod.append_bench_history(path, e3)
        assert len(warnings) == 1 and "'serving'" in warnings[0]
        # the file holds one JSON line per run, newest last
        lines = [json.loads(ln) for ln in
                 open(path).read().splitlines()]
        assert [ln["t"] for ln in lines] == [1000.0, 2000.0, 3000.0]

    def test_history_survives_corrupt_lines(self, tmp_path):
        mod = self._mod()
        path = str(tmp_path / "BENCH_history.jsonl")
        with open(path, "w") as f:
            f.write("not json\n")
            f.write(json.dumps({"t": 1, "sections":
                                {"serving": 100.0}}) + "\n")
            f.write("{truncated\n")
        e = mod.bench_history_entry(self._report(10.0), t=2.0)
        # last VALID entry is the baseline -> 90% drop warns
        assert len(mod.append_bench_history(path, e)) == 1

    def test_missing_sections_never_warn(self, tmp_path):
        mod = self._mod()
        path = str(tmp_path / "BENCH_history.jsonl")
        mod.append_bench_history(
            path, mod.bench_history_entry(self._report(100.0, 50.0),
                                          t=1.0))
        # the next run did not produce the obs section at all
        assert mod.append_bench_history(
            path, mod.bench_history_entry(self._report(99.0),
                                          t=2.0)) == []
