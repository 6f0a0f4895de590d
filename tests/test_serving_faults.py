"""serving.faults + recovery layers: no request dies because a replica did.

The chaos oracle (ISSUE acceptance): with a FaultInjector killing or
hanging a replica after >= 1 token has streamed, every client receives
the EXACT greedy token sequence the solo CompiledGenerator produces —
zero truncated or duplicated tokens (mid-stream migration re-prefills
prompt + emitted history on a survivor); a poisoned request 422s alone
while its co-residents complete token-identically on the same replica.

Pure units (no threads, fake clocks): CircuitBreaker state machine,
ReplicaWatchdog staleness scan, FaultInjector determinism, the
Ticket retry-backoff and cancel-vs-retry lock fixes.
"""
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import (FaultInjector, InjectedFault,
                                PoisonedRequest, SamplingParams,
                                ServingEngine, prometheus_render,
                                resolve_faults)
from paddle_tpu.serving.http import (CircuitBreaker, EngineDriver,
                                     ReplicaHung, ReplicaWatchdog,
                                     Router)

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


def oracle_greedy(model, prompt, n_new):
    out = model.generate(paddle.to_tensor(np.asarray(prompt)[None]),
                         max_new_tokens=n_new).numpy()
    return out[0, len(prompt):].tolist()


def wait_until(pred, timeout=30.0, interval=0.005):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return False


def make_cluster(n_replicas=2, *, faults=None, warm=True,
                 router_kw=None, **engine_kw):
    """N warmed engines behind started drivers + router (no HTTP —
    Ticket.events() is the consumption point under test). Warming
    compiles every program BEFORE any fault can fire, so an injected
    hang is the only thing that ever stalls a heartbeat."""
    model = tiny_gpt()
    kw = dict(num_slots=2, max_len=64)
    kw.update(engine_kw)
    engines = [ServingEngine(model, **kw) for _ in range(n_replicas)]
    if warm:
        for e in engines:
            e.generate([np.array([1, 2, 3])],
                       SamplingParams(max_new_tokens=2))
    drivers = [EngineDriver(e, name=f"replica-{i}", faults=faults)
               for i, e in enumerate(engines)]
    router = Router(drivers, **(router_kw or {})).start()
    return model, engines, drivers, router


def consume(ticket, on_token=None, poll_s=0.01):
    """Drain a ticket; returns (tokens, done_reason_or_None, error)."""
    tokens = []
    for kind, val in ticket.events(poll_s=poll_s):
        if kind == "token":
            tokens.append(val)
            if on_token is not None:
                on_token(tokens)
        elif kind == "done":
            return tokens, val, None
        elif kind == "error":
            return tokens, None, val
    return tokens, None, None


# -- FaultInjector units ----------------------------------------------------
class TestFaultInjector:
    def test_kill_fires_once_at_threshold_step(self):
        inj = FaultInjector()
        inj.kill_at_step("r0", 3)
        for s in range(3):
            inj.on_step("r0", s)          # below threshold: no-op
            inj.on_step("r1", 99)         # other replica: never
        with pytest.raises(InjectedFault) as ei:
            inj.on_step("r0", 3)
        assert ei.value.kind == "kill"
        inj.on_step("r0", 4)              # one-shot: consumed
        assert inj.kills_fired == 1

    def test_fail_kth_add_request_scoped_and_global(self):
        inj = FaultInjector()
        inj.fail_add_request(2)                    # global ordinal 2
        inj.fail_add_request(1, replica="r1")      # r1's first
        inj.on_add_request("r0", "a")              # global #1: ok
        with pytest.raises(InjectedFault):
            inj.on_add_request("r1", "b")          # r1 #1 AND global #2
        inj.on_add_request("r0", "c")
        inj.on_add_request("r1", "d")
        assert inj.add_fails_fired == 1

    def test_poison_hits_only_that_request(self):
        inj = FaultInjector()
        inj.poison("req-7")
        inj.on_engine_step("r0", ["req-1", "req-2"])
        with pytest.raises(InjectedFault) as ei:
            inj.on_engine_step("r0", ["req-1", "req-7"])
        assert ei.value.kind == "poison"
        assert ei.value.request_id == "req-7"
        inj.clear_poison("req-7")
        inj.on_engine_step("r0", ["req-7"])
        assert inj.poison_hits == 1

    def test_env_spec_parsing(self, monkeypatch):
        monkeypatch.setenv(
            "PADDLE_TPU_FAULTS",
            "kill:replica-0@40; hang:replica-1@10x5.0;"
            "fail_add:3;fail_add:replica-0@7;poison:req-9")
        inj = resolve_faults()
        assert inj._kills == {"replica-0": [40]}
        assert inj._hangs == {"replica-1": [(10, 5.0)]}
        assert inj._fail_adds == {"*": {3}, "replica-0": {7}}
        assert inj._poisoned == {"req-9"}
        monkeypatch.setenv("PADDLE_TPU_FAULTS", "")
        assert resolve_faults() is None
        with pytest.raises(ValueError):
            FaultInjector.parse("explode:everything")

    def test_chaos_schedule_reproducible_and_leaves_survivor(self):
        replicas = [f"replica-{i}" for i in range(3)]
        a = FaultInjector(seed=11).chaos_schedule(replicas, kills=1,
                                                  hangs=1)
        b = FaultInjector(seed=11).chaos_schedule(replicas, kills=1,
                                                  hangs=1)
        assert a == b and len(a) == 2          # seeded: identical
        victims = {e.split(":")[1].split("@")[0] for e in a}
        assert len(victims) == 2               # >= 1 replica untouched
        c = FaultInjector(seed=12).chaos_schedule(replicas, kills=1,
                                                  hangs=1)
        assert a != c                          # seed actually matters


# -- circuit breaker + watchdog units (fake clock, no threads) --------------
class TestCircuitBreaker:
    def test_opens_after_threshold_consecutive_failures(self):
        b = CircuitBreaker(failure_threshold=3, open_s=10.0)
        assert b.allow(0.0)
        b.record_failure(1.0)
        b.record_failure(2.0)
        assert b.state(2.0) == "closed" and b.allow(2.0)
        b.record_failure(3.0)
        assert b.state(3.0) == "open" and not b.allow(3.0)
        assert b.opens_total == 1

    def test_success_resets_consecutive_count(self):
        b = CircuitBreaker(failure_threshold=2, open_s=10.0)
        b.record_failure(1.0)
        b.record_success(2.0)
        b.record_failure(3.0)
        assert b.state(3.0) == "closed"       # never 2 consecutive

    def test_half_open_probe_success_closes_failure_reopens(self):
        b = CircuitBreaker(failure_threshold=1, open_s=5.0)
        b.record_failure(0.0)
        assert not b.allow(4.9)               # still cooling off
        assert b.allow(5.0)                   # half-open: one probe
        assert b.state(5.0) == "half_open"
        b.record_failure(6.0)                 # probe failed: reopen
        assert b.state(6.0) == "open" and not b.allow(10.9)
        assert b.allow(11.0)                  # cooled off again
        b.record_success(11.5)                # probe succeeded
        assert b.state(12.0) == "closed"
        b.trip(13.0)                          # death: immediate open
        assert b.state(13.0) == "open"

    def test_watchdog_condemns_only_stale_started_replicas(self):
        class FakeDriver:
            def __init__(self, name, beat, started=True, dead=False,
                         draining=False):
                self.name, self.last_beat = name, beat
                self.started, self.dead = started, dead
                self.draining = draining
                self.condemned_with = None

            def condemn(self, exc=None):
                self.condemned_with = exc
                self.dead = True    # mirrors EngineDriver.condemn

        t = [100.0]
        fresh = FakeDriver("fresh", beat=99.8)
        stale = FakeDriver("stale", beat=90.0)
        unborn = FakeDriver("unborn", beat=None)
        unstarted = FakeDriver("unstarted", beat=1.0, started=False)
        dead = FakeDriver("dead", beat=1.0, dead=True)
        draining = FakeDriver("draining", beat=1.0, draining=True)
        kills = []
        wd = ReplicaWatchdog(
            [fresh, stale, unborn, unstarted, dead, draining],
            timeout_s=1.0, clock=lambda: t[0],
            on_kill=lambda d: kills.append(d.name))
        assert wd.poll() == [stale]
        assert isinstance(stale.condemned_with, ReplicaHung)
        assert kills == ["stale"] and wd.kills_total == 1
        for d in (fresh, unborn, unstarted, dead, draining):
            assert d.condemned_with is None
        t[0] = 102.0                           # now fresh went stale too
        assert wd.poll() == [fresh]
        assert wd.kills_total == 2


# -- Ticket retry semantics (satellite fixes) -------------------------------
class TestTicketRetry:
    def test_first_failover_attempt_has_no_backoff_sleep(self):
        """Attempt 0 re-places IMMEDIATELY; backoff paces attempts
        1..N-1 starting at backoff_base_s (satellite fix — previously
        every failover slept before even trying). The router's jitter
        hook fires exactly once per backoff sleep, so counting its
        invocations counts the sleeps without patching time.sleep."""
        jitter_calls = []

        def jitter():
            jitter_calls.append(1)
            return 1.0

        model, engines, drivers, router = make_cluster(
            2, router_kw=dict(backoff_base_s=0.05, jitter=jitter))
        t = router.submit(np.array([3, 14, 15], np.int64),
                          SamplingParams(max_new_tokens=30))
        victim = t.driver
        assert wait_until(lambda: len(t.request.output_tokens) > 0)
        victim.kill()
        toks, done, err = consume(t)
        assert done == "length" and err is None
        # the failover needed zero backoff sleeps: a survivor was free
        assert jitter_calls == []
        assert t.attempts == 2 and t.migrations == 1
        router.drain()

    def test_cancel_racing_retry_never_cancels_stale_pair(self):
        """cancel() during a mid-failover re-place must cancel the NEW
        attempt, not the dead one: _retry re-checks the flag under the
        router lock after swapping the pair in."""
        model, engines, drivers, router = make_cluster(2)
        t = router.submit(np.array([3, 14, 15, 9], np.int64),
                          SamplingParams(max_new_tokens=60))
        first = t.request
        assert wait_until(lambda: len(first.output_tokens) > 2)
        # freeze the race deterministically: cancel flag flips while
        # the retry is between _place and the lock re-check
        t._cancelled = True
        t._failover(first)
        new_req = t.request
        assert new_req is not first
        assert wait_until(lambda: new_req.finished, timeout=30)
        assert new_req.finish_reason == "cancelled"
        router.drain()
        for e in engines:
            e.pool.assert_quiesced()


# -- mid-stream migration vs the solo oracle --------------------------------
class TestMigration:
    def test_midstream_kill_migrates_token_identical(self):
        """THE chaos oracle: kill the serving replica after >= 3 tokens
        have streamed; the client's full sequence equals solo
        CompiledGenerator greedy decode — no truncation, no dupes —
        and usage reports the migration."""
        model, engines, drivers, router = make_cluster(2)
        prompt = [3, 14, 15, 9]
        want = oracle_greedy(model, prompt, 24)
        t = router.submit(np.array(prompt, np.int64),
                          SamplingParams(max_new_tokens=24))
        victim = t.driver

        def kill_at_3(tokens):
            if len(tokens) == 3 and not victim.dead:
                victim.kill()

        toks, done, err = consume(t, on_token=kill_at_3)
        assert err is None and done == "length"
        assert toks == want
        out = t.output()
        assert out.token_ids == want
        assert out.prompt_token_ids == prompt
        assert out.migrations == 1 and t.attempts == 2
        assert router.migrations_total == 1
        assert router.retries_total == 1
        router.drain()
        for e in engines:
            e.pool.assert_quiesced()

    def test_migration_under_page_pressure_and_eviction(self):
        """Migration onto a survivor whose pool is tight: the re-placed
        prompt (original + emitted history) must evict prefix-cache
        leaves to fit, and the continuation stays token-identical
        through the eviction."""
        model, engines, drivers, router = make_cluster(
            2, num_slots=2, max_len=64, page_size=8, num_pages=17)
        # dirty the survivor's pool with finished requests so its
        # radix cache holds parked pages the migration must evict
        for p in ([5, 6, 7, 8], [9, 10, 11], [12, 13]):
            drivers[1].submit(np.array(p, np.int64),
                              SamplingParams(max_new_tokens=8))
        assert wait_until(
            lambda: engines[1].pool.cached_pages > 0, timeout=30)
        prompt = [3, 14, 15, 9, 26, 5]
        want = oracle_greedy(model, prompt, 40)
        t = router.submit(np.array(prompt, np.int64),
                          SamplingParams(max_new_tokens=40))
        assert t.driver is drivers[0]          # survivor is loaded
        def kill_at_4(tokens):
            if len(tokens) == 4 and not drivers[0].dead:
                drivers[0].kill()
        toks, done, err = consume(t, on_token=kill_at_4)
        assert err is None and done == "length"
        assert toks == want and t.migrations == 1
        router.drain()
        engines[1].pool.assert_quiesced()

    def test_migration_with_prefix_cache_off(self):
        """The oracle holds with the radix cache disabled — migration
        re-prefills the full prompt + history the slow way."""
        model, engines, drivers, router = make_cluster(
            2, prefix_cache=False)
        prompt = [26, 5, 35]
        want = oracle_greedy(model, prompt, 20)
        t = router.submit(np.array(prompt, np.int64),
                          SamplingParams(max_new_tokens=20))
        victim = t.driver
        def kill_at_2(tokens):
            if len(tokens) == 2 and not victim.dead:
                victim.kill()
        toks, done, err = consume(t, on_token=kill_at_2)
        assert err is None and done == "length" and toks == want
        assert t.output().migrations == 1
        router.drain()
        for e in engines:
            e.pool.assert_quiesced()

    def test_double_kill_migrates_twice(self):
        """Two migrations of one stream (3 replicas, kill two in
        sequence): still token-identical, migrations == 2."""
        model, engines, drivers, router = make_cluster(3)
        prompt = [7, 8, 9, 10]
        want = oracle_greedy(model, prompt, 30)
        t = router.submit(np.array(prompt, np.int64),
                          SamplingParams(max_new_tokens=30))
        killed = []

        def killer(tokens):
            n = len(tokens)
            if n in (3, 12) and n not in killed:
                killed.append(n)
                t.driver.kill()

        toks, done, err = consume(t, on_token=killer)
        assert err is None and done == "length"
        assert toks == want
        assert t.migrations == 2 and t.attempts == 3
        assert t.output().migrations == 2
        router.drain()

    def test_failed_migration_ends_stream_as_replica_failure(self):
        """When no survivor exists, the stream closes with the partial
        tokens and reason replica_failure (the pre-migration
        semantics are the documented fallback)."""
        model, engines, drivers, router = make_cluster(
            1, router_kw=dict(max_retries=2, backoff_base_s=0.0))
        t = router.submit(np.array([3, 14, 15], np.int64),
                          SamplingParams(max_new_tokens=40))
        assert wait_until(lambda: len(t.request.output_tokens) > 1)
        drivers[0].kill()
        toks, done, err = consume(t)
        assert done == "replica_failure" and len(toks) >= 1
        assert t.error is not None and t.migrations == 0


# -- watchdog end to end ----------------------------------------------------
class TestWatchdogEndToEnd:
    def test_hung_replica_condemned_and_stream_migrates(self):
        """An injected hang (no raise, heartbeat goes stale) is caught
        by the watchdog, the replica is condemned, its breaker trips
        open, and the resident stream migrates token-identically."""
        inj = FaultInjector()
        model, engines, drivers, router = make_cluster(
            2, faults=inj,
            router_kw=dict(watchdog_timeout_s=0.4,
                           watchdog_interval_s=0.1))
        prompt = [3, 14, 15, 9]
        want = oracle_greedy(model, prompt, 25)
        t = router.submit(np.array(prompt, np.int64),
                          SamplingParams(max_new_tokens=25))
        victim = t.driver
        hung = []

        def hang_at_3(tokens):
            if len(tokens) == 3 and not hung:
                hung.append(1)
                inj.hang_at_step(victim.name, 0, 60.0)

        toks, done, err = consume(t, on_token=hang_at_3)
        assert err is None and done == "length"
        assert toks == want and t.migrations == 1
        assert router.watchdog_kills_total == 1
        assert victim.dead and not victim.healthy
        assert isinstance(victim.death_exc, ReplicaHung)
        assert router.breakers[victim.name].state(
            time.monotonic()) == "open"
        inj.release_hangs()                 # let the wedged pump exit
        router.drain()

    def test_breaker_takes_flapping_replica_out_of_rotation(self):
        """Injected add_request failures on one replica open its
        breaker after `breaker_failures` consecutive placement
        failures; traffic then lands on the healthy replica WITHOUT
        paying the failed submit, and a half-open probe readmits the
        flapper once the injected fault schedule is exhausted."""
        inj = FaultInjector()
        for k in range(1, 4):
            inj.fail_add_request(k, replica="replica-0")
        model, engines, drivers, router = make_cluster(
            2, faults=inj,
            router_kw=dict(breaker_failures=3, breaker_open_s=0.2))
        outs = []
        for i in range(5):
            t = router.submit(np.array([3 + i, 14, 15], np.int64),
                              SamplingParams(max_new_tokens=2))
            toks, done, err = consume(t)
            assert done == "length" and err is None
            outs.append(t.driver.name)
        # every request SERVED despite the flapper (placement absorbed
        # the injected failures), breaker opened after 3 in a row
        assert inj.add_fails_fired == 3
        assert router.breakers["replica-0"].opens_total >= 1
        assert all(n == "replica-1" for n in outs)
        time.sleep(0.25)                    # past breaker_open_s
        t = router.submit(np.array([40, 41, 42], np.int64),
                          SamplingParams(max_new_tokens=2))
        toks, done, err = consume(t)
        assert done == "length"
        # the half-open probe's success closed the breaker again
        assert wait_until(lambda: router.breakers["replica-0"].state(
            time.monotonic()) == "closed", timeout=5)
        router.drain()


# -- poison quarantine ------------------------------------------------------
class TestPoisonQuarantine:
    def test_bisect_isolates_poison_neighbors_token_identical(self):
        """A poisoned resident deterministically kills the step; the
        engine bisects the batch, 422s it ALONE (typed
        PoisonedRequest) and every innocent co-resident completes
        bit-identical to solo decode on the SAME replica."""
        model = tiny_gpt()
        eng = ServingEngine(model, num_slots=4, max_len=64)
        inj = FaultInjector()
        eng.step_fault_hook = \
            lambda ids: inj.on_engine_step("r0", ids)
        prompts = [[3, 14, 15, 9], [26, 5, 35], [1, 2, 3, 4, 5, 6],
                   [7, 8, 9]]
        reqs = [eng.add_request(np.array(p),
                                SamplingParams(max_new_tokens=10))
                for p in prompts]
        inj.poison(reqs[1].request_id)
        eng.run()
        assert reqs[1].finish_reason == "poisoned"
        assert isinstance(reqs[1].error, PoisonedRequest)
        for i in (0, 2, 3):
            assert reqs[i].finish_reason == "length"
            assert reqs[i].output_tokens == oracle_greedy(
                model, prompts[i], 10), i
        assert eng.metrics.requests_poisoned == 1
        assert eng.metrics.snapshot()["requests"]["poisoned"] == 1
        eng.drain()
        eng.pool.assert_quiesced()

    def test_poison_arriving_mid_decode_is_still_isolated(self):
        """Poison injected after tokens already streamed (a decode-time
        poison, not an admission-time one): the victim keeps its
        emitted prefix, the neighbor is unharmed."""
        model = tiny_gpt()
        eng = ServingEngine(model, num_slots=2, max_len=64)
        inj = FaultInjector()
        eng.step_fault_hook = \
            lambda ids: inj.on_engine_step("r0", ids)
        a = eng.add_request(np.array([3, 14, 15, 9]),
                            SamplingParams(max_new_tokens=12))
        b = eng.add_request(np.array([26, 5, 35]),
                            SamplingParams(max_new_tokens=12))
        for _ in range(5):
            eng.step()
        assert len(a.output_tokens) > 0
        inj.poison(a.request_id)
        eng.run()
        assert a.finish_reason == "poisoned"
        assert b.finish_reason == "length"
        assert b.output_tokens == oracle_greedy(model, [26, 5, 35], 12)
        eng.drain()
        eng.pool.assert_quiesced()

    def test_global_fault_is_not_blamed_on_a_request(self):
        """A fault that does NOT track one request (every probe
        raises) fails the verdict check and propagates as replica
        death instead of poisoning an innocent."""
        model = tiny_gpt()
        eng = ServingEngine(model, num_slots=2, max_len=64)
        boom = RuntimeError("global device fault")

        def hook(ids):
            raise boom

        eng.step_fault_hook = hook
        eng.add_request(np.array([3, 14, 15]),
                        SamplingParams(max_new_tokens=4))
        eng.add_request(np.array([5, 6, 7]),
                        SamplingParams(max_new_tokens=4))
        with pytest.raises(RuntimeError) as ei:
            eng.run()
        assert ei.value is boom
        # nothing was spuriously quarantined
        assert eng.metrics.requests_poisoned == 0

    def test_poisoned_request_is_422_over_http_and_rendered(self):
        """Full vertical: HTTP client sends the poisoned request, gets
        a typed 422 with finish_reason "poisoned"; the co-resident
        stream completes; /metrics renders poisoned_total,
        migrations_total and per-replica breaker_state."""
        import http.client
        import json as json_mod

        from paddle_tpu.serving.http import serve

        model = tiny_gpt()
        inj = FaultInjector()
        engines = [ServingEngine(model, num_slots=2, max_len=64)]
        for e in engines:
            e.generate([np.array([1, 2, 3])],
                       SamplingParams(max_new_tokens=2))
        server = serve(engines, poll_interval_s=0.01, faults=inj)
        addr = server.server_address[:2]
        try:
            inj.poison("req-poison")
            # pin the engine-level id of the poisoned request via the
            # driver (the HTTP layer auto-generates ids otherwise)
            results = {}

            def victim():
                conn = http.client.HTTPConnection(*addr, timeout=60)
                conn.request("POST", "/v1/completions",
                             json_mod.dumps({"prompt": [26, 5, 35],
                                             "max_tokens": 8}),
                             {"Content-Type": "application/json"})
                resp = conn.getresponse()
                results["victim"] = (resp.status,
                                     json_mod.loads(resp.read()))
                conn.close()

            # identify the auto-generated id: submit through the
            # driver directly with a pinned id instead
            drv = server.router.drivers[0]
            neighbor = drv.submit(np.array([3, 14, 15, 9], np.int64),
                                  SamplingParams(max_new_tokens=20))
            poisoned = drv.submit(np.array([26, 5, 35], np.int64),
                                  SamplingParams(max_new_tokens=8),
                                  request_id="req-poison")
            assert wait_until(lambda: poisoned.finished, timeout=30)
            assert poisoned.finish_reason == "poisoned"
            assert wait_until(lambda: neighbor.finished, timeout=30)
            assert neighbor.finish_reason == "length"
            assert neighbor.output_tokens == oracle_greedy(
                model, [3, 14, 15, 9], 20)
            # protocol mapping: poisoned output -> 422
            from paddle_tpu.serving.http.protocol import \
                status_for_output
            assert status_for_output(poisoned.output()) == 422
            # /metrics renders the resilience series
            conn = http.client.HTTPConnection(*addr, timeout=30)
            conn.request("GET", "/metrics")
            text = conn.getresponse().read().decode()
            conn.close()
            assert 'paddle_serving_poisoned_total' \
                '{replica="replica-0"} 1' in text
            assert 'paddle_serving_requests_total{outcome="poisoned",' \
                'replica="replica-0"} 1' in text
            assert "paddle_serving_migrations_total 0" in text
            assert "paddle_serving_watchdog_kills_total 0" in text
            assert 'paddle_serving_breaker_state{replica="replica-0",' \
                'state="closed"} 0' in text
            assert "paddle_serving_retries_total 0" in text
        finally:
            server.drain()
        engines[0].pool.assert_quiesced()


# -- HTTP chaos oracle ------------------------------------------------------
class TestHTTPMigration:
    def test_sse_stream_survives_replica_kill_usage_counts_it(self):
        """SSE client vs a 2-replica server: its replica dies after
        tokens streamed; the client reads the EXACT oracle sequence to
        [DONE] with finish_reason length and usage.migrations == 1."""
        import http.client
        import json as json_mod

        from paddle_tpu.serving.http import serve

        model = tiny_gpt()
        engines = [ServingEngine(model, num_slots=2, max_len=64)
                   for _ in range(2)]
        for e in engines:
            e.generate([np.array([1, 2, 3])],
                       SamplingParams(max_new_tokens=2))
        server = serve(engines, poll_interval_s=0.01)
        addr = server.server_address[:2]
        try:
            prompt = [3, 14, 15, 9]
            want = oracle_greedy(model, prompt, 30)
            conn = http.client.HTTPConnection(*addr, timeout=120)
            conn.request("POST", "/v1/completions",
                         json_mod.dumps({"prompt": prompt,
                                         "stream": True,
                                         "max_tokens": 30}),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            tokens, fin, usage = [], None, None
            while True:
                line = resp.readline()
                if not line or line.strip() == b"data: [DONE]":
                    break
                if not line.startswith(b"data: "):
                    continue
                frame = json_mod.loads(line[6:])
                choice = frame["choices"][0]
                if choice["token"] is not None:
                    tokens.append(choice["token"])
                    if len(tokens) == 3:
                        victim = next(
                            d for d in server.router.drivers
                            if d.engine.scheduler.running)
                        victim.kill()
                if choice["finish_reason"]:
                    fin = choice["finish_reason"]
                    usage = frame.get("usage")
            conn.close()
            assert fin == "length"
            assert tokens == want          # zero truncated/duplicated
            assert usage["migrations"] == 1
            assert usage["completion_tokens"] == 30
            assert server.router.migrations_total == 1
        finally:
            server.drain()


# -- chaos soak (slow) ------------------------------------------------------
@pytest.mark.slow
def test_chaos_soak_random_schedule_token_identity():
    """~30s soak: 3 replicas under continuous traffic while a SEEDED
    random schedule kills one replica, hangs another past the watchdog
    timeout, and poisons every 7th request. Every non-poisoned request
    must finish token-identical to the solo oracle (migrated or not);
    every poisoned request must 422 alone; the survivor's pool must
    quiesce."""
    inj = FaultInjector(seed=1234)
    model, engines, drivers, router = make_cluster(
        3, faults=inj, num_slots=2, max_len=64,
        router_kw=dict(watchdog_timeout_s=1.0,
                       watchdog_interval_s=0.25))
    events = inj.chaos_schedule(
        [d.name for d in drivers], kills=1, hangs=1, hang_s=120.0,
        max_step=60, keep_alive=1)
    assert len(events) == 2
    deadline = time.monotonic() + 25.0
    results = []
    lock = threading.Lock()
    oracle_cache = {}

    def want(prompt, n):
        key = (tuple(prompt), n)
        if key not in oracle_cache:
            oracle_cache[key] = oracle_greedy(model, list(prompt), n)
        return oracle_cache[key]

    def client(i):
        rng = np.random.RandomState(i)
        prompt = (1 + rng.randint(0, 90, size=3 + (i % 5))).tolist()
        n = 6 + (i % 9)
        try:
            t = router.submit(np.array(prompt, np.int64),
                              SamplingParams(max_new_tokens=n))
        except Exception as exc:
            with lock:
                results.append((i, "submit_error", repr(exc)))
            return
        if i % 7 == 0:
            inj.poison(t.request.request_id)
        toks, done, err = consume(t)
        with lock:
            if i % 7 == 0:
                results.append((i, "poisoned_ok"
                                if done == "poisoned" else "BAD",
                                done or repr(err)))
                inj.clear_poison(t.request.request_id)
            elif done == "length" and toks == want(prompt, n):
                results.append((i, "ok", t.migrations))
            else:
                results.append((i, "BAD", (done, repr(err), toks,
                                           want(prompt, n))))

    i = 0
    threads = []
    while time.monotonic() < deadline:
        threads = [th for th in threads if th.is_alive()]
        while len(threads) < 6:
            th = threading.Thread(target=client, args=(i,))
            th.start()
            threads.append(th)
            i += 1
        time.sleep(0.02)
    for th in threads:
        th.join(60)
    inj.release_hangs()
    bad = [r for r in results if r[1] == "BAD"]
    assert not bad, bad[:5]
    oks = [r for r in results if r[1] == "ok"]
    assert len(oks) > 20
    # at least one fault actually fired against live traffic
    assert inj.kills_fired + inj.hangs_fired + inj.poison_hits >= 1
    router.drain()
    for d, e in zip(drivers, engines):
        if not d.dead:
            e.pool.assert_quiesced()


@pytest.mark.slow
def test_serving_bench_chaos_smoke(tmp_path, monkeypatch):
    """`serving_bench.py --smoke --chaos` in-process: the schema-v6
    report gains the chaos section and its own assertions hold
    (truncated_streams == 0 with a replica killed mid-load)."""
    import importlib.util
    import json as json_mod
    import os
    import sys
    script = os.path.join(os.path.dirname(__file__), os.pardir,
                          "scripts", "serving_bench.py")
    spec = importlib.util.spec_from_file_location(
        "serving_bench_chaos", script)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = str(tmp_path / "BENCH_serving.json")
    monkeypatch.setattr(sys, "argv",
                        ["serving_bench.py", "--smoke", "--chaos",
                         "--requests", "4", "--out", out])
    mod.main()
    with open(out) as f:
        report = json_mod.load(f)
    assert report["schema_version"] == 19
    chaos = report["chaos"]
    assert chaos["replicas"] == 2
    assert chaos["truncated_streams"] == 0
    assert chaos["completed"] == 4
    assert chaos["kills_fired"] >= 1
    assert chaos["fault_free"]["truncated_streams"] == 0
    assert chaos["goodput_tokens_per_sec"] > 0
