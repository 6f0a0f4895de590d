"""Graceful degradation under overload (PR 9).

The overload oracle (ISSUE acceptance): a resident preempted under
priority pressure — tokens banked, KV pages swapped to the host-RAM
tier, slot freed — and later resumed via swap-in emits a stream
bit-token-identical to the never-preempted solo CompiledGenerator
oracle, with the prefix cache on or off, with speculative decoding on,
and across a chaos-schedule replica kill mid-preemption. Queued
requests whose placement deadline expires fail fast as typed
`DeadlineExceeded` -> 504. The compiled surface is unchanged: the
unified step stays ONE trace and the two swap programs trace once
each (page ids are traced scalars).

Pure units (no model): PagePool SWAPPED-state invariants, HostPagePool
slot invariants, priority/deadline queue ordering, watchdog grace
(fake clock), Ticket migration cap, FaultInjector overload spikes.
"""
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import (DeadlineExceeded, FaultInjector,
                                HostPagePool, PagePool, Request,
                                RequestState, SamplingParams,
                                Scheduler, ServingEngine,
                                prometheus_render,
                                resolve_preempt_flag)
from paddle_tpu.serving.engine import SPILL_WIDTHS
from paddle_tpu.serving.http import (EngineDriver, ReplicaDead,
                                     ReplicaWatchdog, Router, serve)
from paddle_tpu.serving.http.protocol import (status_for_error,
                                              status_for_output)

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


def consume(ticket, poll_s=0.01):
    tokens = []
    for kind, val in ticket.events(poll_s=poll_s):
        if kind == "token":
            tokens.append(val)
        elif kind == "done":
            return tokens, val, None
        elif kind == "error":
            return tokens, None, val
    return tokens, None, None


# -- PagePool SWAPPED state + HostPagePool invariants ------------------------
class TestHostTierInvariants:
    def test_double_swap_out_raises(self):
        pool = PagePool(5)
        pages = pool.alloc(2)
        pool.swap_out(pages)
        assert pool.swapped_pages == 2
        with pytest.raises(ValueError, match="swap_out of free"):
            pool.swap_out(pages)          # already on the free list

    def test_swap_out_shared_or_unowned_raises(self):
        pool = PagePool(5)
        pages = pool.alloc(1)
        pool.retain(pages)                # refcount 2: shared
        with pytest.raises(ValueError, match="still shared"):
            pool.swap_out(pages)
        pool.release(pages)
        pool.release(pages)               # refcount 0, NOT cached
        with pytest.raises(ValueError, match="unowned"):
            pool.swap_out(pages)

    def test_swap_in_of_freed_host_page_raises(self):
        host = HostPagePool(2)
        slot = host.store(b"payload")
        assert host.load(slot) == b"payload"
        host.free(slot)
        with pytest.raises(ValueError, match="swap-in of a freed"):
            host.load(slot)
        with pytest.raises(ValueError, match="double free"):
            host.free(slot)

    def test_host_pool_capacity_bounds_store(self):
        host = HostPagePool(1)
        a = host.store(b"a")
        assert a is not None and host.free_pages == 0
        assert host.store(b"b") is None   # full: refused, no effects
        host.free(a)
        assert host.store(b"b") is not None

    def test_park_then_spill_refcounts(self):
        """The prefix-spill lifecycle: USED -> released -> CACHED
        (parked) -> SWAPPED-out to host (spill kind) -> restored ->
        parked again; counters and states close at every hop."""
        pool = PagePool(5)
        pages = pool.alloc(1)
        pool.release(pages)
        pool.park(pages)
        assert pool.cached_pages == 1
        pool.swap_out(pages, spill=True)  # parked page may spill
        assert pool.cached_pages == 0 and pool.swapped_pages == 1
        assert pool.free_pages == 4       # device page reclaimed
        fresh = pool.alloc(1)             # restore destination
        pool.swapped_restored(1, spill=True)
        pool.release(fresh)
        pool.park(fresh)
        assert pool.swapped_pages == 0 and pool.cached_pages == 1
        pool.assert_quiesced()            # spill drained: clean

    def test_assert_quiesced_counts_swapped(self):
        """A preempted REQUEST's host-resident KV is a shutdown leak;
        a prefix-cache SPILL is legitimate long-lived cache state."""
        pool = PagePool(5)
        pages = pool.alloc(2)
        pool.swap_out(pages)              # request kind
        with pytest.raises(RuntimeError, match="host-tier leak"):
            pool.assert_quiesced()
        pool.drop_swapped(2)
        pool.assert_quiesced()
        spill = pool.alloc(1)
        pool.release(spill)
        pool.park(spill)
        pool.swap_out(spill, spill=True)  # cache kind: allowed
        pool.assert_quiesced()

    def test_swapped_drain_overdraw_raises(self):
        pool = PagePool(5)
        pages = pool.alloc(1)
        pool.swap_out(pages)
        with pytest.raises(ValueError, match="only 1 are outstanding"):
            pool.swapped_restored(2)
        with pytest.raises(ValueError, match="only 0 are outstanding"):
            pool.drop_swapped(1, spill=True)   # wrong kind
        pool.swapped_restored(1)


# -- priority/deadline queue ordering (pure scheduler units) -----------------
def _req(rid, *, priority=0, deadline_s=None, arrival=0.0):
    return Request(rid, np.array([1, 2, 3], np.int64),
                   SamplingParams(max_new_tokens=4, priority=priority,
                                  deadline_s=deadline_s),
                   arrival_t=arrival)


class TestPriorityScheduling:
    def test_queue_orders_priority_then_deadline_then_arrival(self):
        s = Scheduler(num_slots=4)
        late_hi = _req("late-hi", priority=0, arrival=3.0)
        early_lo = _req("early-lo", priority=5, arrival=0.0)
        dl = _req("dl", priority=0, deadline_s=1.0, arrival=2.0)
        no_dl = _req("no-dl", priority=0, arrival=1.0)
        for r in (early_lo, no_dl, late_hi, dl):
            s.submit(r)
        grants = s.assign()
        assert [r.request_id for _, r in grants] == \
            ["dl", "no-dl", "late-hi", "early-lo"]

    def test_requeue_bypasses_max_queue(self):
        s = Scheduler(num_slots=1, max_queue=1)
        s.submit(_req("a"))
        from paddle_tpu.serving import QueueFull
        with pytest.raises(QueueFull):
            s.submit(_req("b"))
        preempted = _req("preempted", priority=9)
        s.requeue(preempted)              # never shed
        assert s.queue_depth == 2

    def test_deadline_expired_excludes_admitted(self):
        s = Scheduler(num_slots=2)
        fresh = _req("fresh", deadline_s=1.0, arrival=0.0)
        resumed = _req("resumed", deadline_s=1.0, arrival=0.0)
        resumed.admitted_t = 0.5          # met its placement deadline
        resumed.state = RequestState.PREEMPTED
        s.submit(fresh)
        s.requeue(resumed)
        assert s.deadline_expired(2.0) == [fresh]

    def test_preemption_victim_strict_priority(self):
        s = Scheduler(num_slots=3)
        a, b, c = (_req("a", priority=5, arrival=0.0),
                   _req("b", priority=9, arrival=1.0),
                   _req("c", priority=9, arrival=0.5))
        for slot, r in enumerate((a, b, c)):
            r.state = RequestState.DECODE
            s.running[slot] = r
        # head at priority 5: only the 9s qualify; latest arrival loses
        head = _req("head", priority=5)
        assert s.preemption_victim(head)[1] is b
        # head at priority 9: nobody is STRICTLY less important
        assert s.preemption_victim(_req("h9", priority=9)) is None
        # head at priority 0 outranks everyone; 9s still evict first
        assert s.preemption_victim(_req("h0", priority=0))[1] is b


# -- preemption oracle (engine level) ----------------------------------------
class TestPreemptionOracle:
    def _preempt_cycle(self, **engine_kw):
        """Low-priority resident + blocked high-priority arrival on a
        pool sized so preemption is the only way in; returns
        (engine, lo_request, hi_request)."""
        model = tiny_gpt()
        eng = ServingEngine(model, num_slots=2, max_len=64,
                            page_size=8, num_pages=6, chunk_len=16,
                            **engine_kw)
        lo = eng.add_request(np.arange(1, 9),
                             SamplingParams(max_new_tokens=24,
                                            priority=5))
        for _ in range(6):
            eng.step()
        assert len(lo.output_tokens) >= 3      # mid-stream victim
        hi = eng.add_request(np.arange(30, 38),
                             SamplingParams(max_new_tokens=24,
                                            priority=0))
        eng.run()
        return eng, lo, hi

    @pytest.mark.parametrize("prefix_cache", [True, False])
    def test_preempt_resume_token_identical(self, prefix_cache,
                                            only_the_unified_step):
        """The core oracle, plus (on the same engine, no extra
        cycles): the retrace probe — swap-out/swap-in are ONE program
        each and the unified step keeps its single trace across
        preempt/resume (ISSUE acceptance) — and the Prometheus
        overload series render."""
        model = tiny_gpt()
        eng, lo, hi = self._preempt_cycle(prefix_cache=prefix_cache)
        assert eng.metrics.preemptions >= 1
        assert eng.metrics.swapped_out_pages >= 1
        assert lo.preemptions >= 1 and hi.preemptions == 0
        assert lo.output_tokens == oracle_greedy(model,
                                                 np.arange(1, 9), 24)
        assert hi.output_tokens == oracle_greedy(model,
                                                 np.arange(30, 38), 24)
        assert lo.output().preemptions >= 1     # usage surface
        assert eng._swap_out_fn._cache_size() == len(SPILL_WIDTHS)
        assert eng._swap_in_fn._cache_size() == 1
        only_the_unified_step(eng)
        text = prometheus_render({"replica-0":
                                  eng.metrics.snapshot()})
        assert ('paddle_serving_preemptions_total'
                '{replica="replica-0"}') in text
        assert "paddle_serving_swapped_out_pages_total" in text
        assert "paddle_serving_swap_in_seconds_count" in text
        assert "paddle_serving_host_pages_total" in text
        assert 'outcome="deadline"' in text
        eng.drain()
        assert eng.pool.swapped_pages == eng.host_pool.used_pages

    @pytest.mark.parametrize("host_pages", [None, 2])
    def test_preempt_swaps_a_long_resident_out_in_one_batch(self,
                                                            host_pages):
        """A victim with several private KV pages goes to the host tier
        through the spill's batched call (fewer gathers than pages, its
        slots handed out while the copy is in flight) and resumes to the
        oracle's tokens; a host tier with room for two pages takes two
        and the tail recomputes."""
        model = tiny_gpt()
        prompt = np.arange(1, 41) % 97
        kw = {} if host_pages is None else {"host_pages": host_pages}
        eng = ServingEngine(model, num_slots=2, max_len=64, page_size=8,
                            num_pages=9, chunk_len=16,
                            prefix_cache=False, **kw)
        lo = eng.add_request(prompt, SamplingParams(max_new_tokens=12,
                                                    priority=5))
        for _ in range(6):
            eng.step()
        assert 1 <= len(lo.output_tokens) < 12
        hi = eng.add_request(np.arange(30, 62) % 97,
                             SamplingParams(max_new_tokens=12,
                                            priority=0))
        eng.step()
        assert lo.preemptions == 1
        pages = eng.metrics.swapped_out_pages
        assert pages == (6 if host_pages is None else 2)
        # the copies cross beside the step launched after them and are
        # collected after its fetch, in the next round
        assert eng.host_pool.pending_pages == pages
        eng.step()
        assert eng.host_pool.pending_pages == 0     # the step took it in
        eng.run()
        snap = eng.metrics.snapshot()
        assert snap["kv_spill_pages_total"] == pages
        assert 0 < snap["kv_spill_batches_total"] <= 2   # 6 = 4 + 2
        assert lo.output_tokens == oracle_greedy(model, prompt, 12)
        assert hi.output_tokens == oracle_greedy(
            model, np.arange(30, 62) % 97, 12)
        eng.drain()

    def test_preempt_resume_with_spec_decode(self):
        """The drafter is dropped at preemption and re-seeded from the
        banked history at resume — the verified stream stays exact."""
        model = tiny_gpt()
        eng, lo, hi = self._preempt_cycle(spec="ngram:4")
        assert eng.metrics.preemptions >= 1
        assert lo.output_tokens == oracle_greedy(model,
                                                 np.arange(1, 9), 24)
        assert hi.output_tokens == oracle_greedy(model,
                                                 np.arange(30, 38), 24)
        eng.drain()

    @pytest.mark.slow
    def test_multiple_preemptions_same_request(self):
        """A request can be displaced repeatedly by successively more
        important arrivals and still stream exactly. (Slow marker:
        the single-displacement oracle runs in three variants above;
        this depth check rides the soak lane.)"""
        model = tiny_gpt()
        eng = ServingEngine(model, num_slots=2, max_len=64,
                            page_size=8, num_pages=6, chunk_len=16)
        lo = eng.add_request(np.arange(1, 9),
                             SamplingParams(max_new_tokens=30,
                                            priority=9))
        for _ in range(5):
            eng.step()
        mid = eng.add_request(np.arange(20, 28),
                              SamplingParams(max_new_tokens=8,
                                             priority=5))
        while not mid.finished:
            eng.step()
        # lo resumed; displace it again with an even higher priority
        assert wait_until(lambda: (eng.step() is not None
                                   and len(lo.output_tokens) > 0),
                          timeout=10)
        hi = eng.add_request(np.arange(40, 48),
                             SamplingParams(max_new_tokens=8,
                                            priority=0))
        eng.run()
        assert lo.preemptions >= 2
        assert lo.output_tokens == oracle_greedy(model,
                                                 np.arange(1, 9), 30)
        assert mid.output_tokens == oracle_greedy(model,
                                                  np.arange(20, 28), 8)
        assert hi.output_tokens == oracle_greedy(model,
                                                 np.arange(40, 48), 8)
        eng.drain()

    def test_preempted_then_cancelled_releases_host_tier(self):
        eng, lo, hi = None, None, None
        model = tiny_gpt()
        eng = ServingEngine(model, num_slots=1, max_len=64,
                            page_size=8, num_pages=9, chunk_len=16)
        lo = eng.add_request(np.arange(1, 9),
                             SamplingParams(max_new_tokens=24,
                                            priority=5))
        for _ in range(4):
            eng.step()
        hi = eng.add_request(np.arange(30, 38),
                             SamplingParams(max_new_tokens=4,
                                            priority=0))
        eng.step()                        # preempts lo (slot pressure)
        assert lo.state is RequestState.PREEMPTED
        assert eng.host_pool.used_pages >= 1
        assert eng.cancel(lo.request_id)
        assert lo.finish_reason == "cancelled"
        eng.run()
        eng.drain()                       # quiesce: host tier drained
        assert eng.host_pool.used_pages == 0

    def test_drain_resumes_preempted_requests(self):
        """Graceful drain delivers a preempted stream instead of
        aborting it — it already streamed tokens."""
        model = tiny_gpt()
        eng = ServingEngine(model, num_slots=1, max_len=64,
                            page_size=8, num_pages=9, chunk_len=16)
        lo = eng.add_request(np.arange(1, 9),
                             SamplingParams(max_new_tokens=20,
                                            priority=5))
        for _ in range(4):
            eng.step()
        hi = eng.add_request(np.arange(30, 38),
                             SamplingParams(max_new_tokens=4,
                                            priority=0))
        eng.step()
        assert lo.state is RequestState.PREEMPTED
        eng.drain()
        assert lo.finish_reason == "length"
        assert lo.output_tokens == oracle_greedy(model,
                                                 np.arange(1, 9), 20)

    def test_preempt_flag_gating_env_and_ctor(self, monkeypatch):
        assert resolve_preempt_flag(True) is True
        assert resolve_preempt_flag(False) is False
        monkeypatch.setenv("PADDLE_TPU_PREEMPT", "off")
        assert resolve_preempt_flag() is False
        monkeypatch.setenv("PADDLE_TPU_PREEMPT", "on")
        assert resolve_preempt_flag() is True
        monkeypatch.setenv("PADDLE_TPU_PREEMPT", "sideways")
        with pytest.raises(ValueError):
            resolve_preempt_flag()
        # gate off: the blocked head backpressures instead
        model = tiny_gpt()
        eng = ServingEngine(model, num_slots=1, max_len=64,
                            preempt=False)
        lo = eng.add_request(np.arange(1, 9),
                             SamplingParams(max_new_tokens=10,
                                            priority=5))
        for _ in range(3):
            eng.step()
        hi = eng.add_request(np.arange(30, 38),
                             SamplingParams(max_new_tokens=4,
                                            priority=0))
        eng.run()
        assert eng.metrics.preemptions == 0
        assert lo.finish_reason == "length"
        assert hi.finish_reason == "length"   # admitted after lo

    def test_deadline_fail_fast_typed_504(self):
        """A queued request whose placement deadline expires fails as
        "deadline" with a typed DeadlineExceeded -> HTTP 504; a
        request that already STARTED is never deadline-failed."""
        model = tiny_gpt()
        t = [0.0]
        eng = ServingEngine(model, num_slots=1, max_len=64,
                            preempt=False, clock=lambda: t[0])
        running = eng.add_request(
            np.arange(1, 9), SamplingParams(max_new_tokens=30,
                                            deadline_s=5.0))
        eng.step()                        # admitted: deadline met
        queued = eng.add_request(
            np.arange(30, 38), SamplingParams(max_new_tokens=4,
                                              deadline_s=0.5))
        t[0] = 1.0                        # past queued's deadline
        finished = eng.step()
        assert queued.finish_reason == "deadline"
        assert isinstance(queued.error, DeadlineExceeded)
        assert queued.output_tokens == []
        assert status_for_output(queued.output()) == 504
        assert status_for_error(queued.error) == 504
        assert eng.metrics.requests_deadline == 1
        assert [o.request_id for o in finished] == [queued.request_id]
        t[0] = 2.0
        eng.run()
        assert running.finish_reason == "length"   # never 504'd
        eng.drain()

    def test_full_pool_request_forfeits_cow_claim(self):
        """Regression (found driving the live HTTP server): a request
        whose page budget spans the WHOLE pool used to deadlock at the
        queue head when its prompt had a partial-page (COW) match —
        the retained COW source was the one page spill/evict could not
        free. The claim is now forfeited and the request admits
        cache-cold instead of waiting forever."""
        model = tiny_gpt()
        eng = ServingEngine(model, num_slots=1, max_len=64,
                            page_size=8, num_pages=8, chunk_len=16)
        prompt = np.array([3, 14, 15, 9], np.int64)
        r1 = eng.add_request(prompt, SamplingParams(max_new_tokens=4))
        eng.run()              # inserts a partial page: COW candidate
        assert eng.pool.cached_pages >= 1
        # whole-pool budget: 4 + 52 = 56 tokens -> all 7 pages
        r2 = eng.add_request(prompt, SamplingParams(max_new_tokens=52))
        eng.run(max_steps=200)
        assert r2.finish_reason == "length"      # admitted, not stuck
        assert r2.output_tokens == oracle_greedy(model, prompt, 52)
        eng.drain()

    def test_prefix_spill_restores_on_match(self):
        """Parked prefix pages spill to the host tier under page
        pressure and a later match swap-ins instead of re-prefilling —
        token-identical, with restore traffic visible in stats."""
        model = tiny_gpt()
        eng = ServingEngine(model, num_slots=1, max_len=32,
                            page_size=8, num_pages=5, chunk_len=8)
        base = np.arange(1, 10, dtype=np.int64)
        want = oracle_greedy(model, base, 4)
        r1 = eng.add_request(base, SamplingParams(max_new_tokens=4))
        eng.run()
        assert r1.output_tokens == want
        assert eng.pool.cached_pages > 0          # inserted + parked
        # disjoint request too big for the free pages alone: pressure
        # spills the parked pages instead of dropping them
        r2 = eng.add_request(np.arange(40, 57),
                             SamplingParams(max_new_tokens=4))
        eng.run()
        assert eng.prefix_cache.spilled_pages_total >= 1
        # the base prompt again: spilled span restores and still hits
        r3 = eng.add_request(base, SamplingParams(max_new_tokens=4))
        eng.run()
        assert r3.output_tokens == want
        assert eng.prefix_cache.restored_pages_total >= 1
        assert r3.cached_tokens > 0
        eng.drain()


# -- watchdog false-positive hardening ---------------------------------------
class TestWatchdogGrace:
    class FakeDriver:
        def __init__(self, name, beat, grace=0.0):
            self.name, self.last_beat = name, beat
            self.started, self.dead, self.draining = True, False, False
            self.watchdog_grace_s = grace
            self.condemned = False

        def condemn(self, exc=None):
            self.condemned = True
            self.dead = True

    def test_grace_scales_tolerated_staleness(self):
        """Fake-clock regression (ISSUE satellite): a slow-but-alive
        replica mid-way through a legitimately huge packed step is NOT
        condemned while its token-scaled grace covers the staleness;
        past timeout + grace it is."""
        t = [100.0]
        slow = self.FakeDriver("slow", beat=95.0, grace=5.0)
        hung = self.FakeDriver("hung", beat=95.0, grace=0.0)
        wd = ReplicaWatchdog([slow, hung], timeout_s=1.0,
                             clock=lambda: t[0])
        assert wd.poll() == [hung]        # 5s stale > 1s, no grace
        assert not slow.condemned         # 5s stale <= 1s + 5s grace
        t[0] = 101.5                      # now 6.5s stale > 6s
        assert wd.poll() == [slow]
        assert slow.condemned

    def test_engine_beats_heartbeat_around_rounds(self):
        """The driver's heartbeat is stamped by the ENGINE around each
        compiled launch — a pump grinding through a long round beats
        continuously instead of once per iteration."""
        model = tiny_gpt()
        eng = ServingEngine(model, num_slots=1, max_len=32)
        driver = EngineDriver(eng, name="r0")   # installs the hook
        assert driver.last_beat is None
        eng.add_request(np.array([3, 14, 15], np.int64),
                        SamplingParams(max_new_tokens=2))
        eng.step()                        # pump never started...
        assert driver.last_beat is not None   # ...yet the beat landed
        eng.abort_all()

    def test_driver_grace_tracks_inflight_tokens(self):
        model = tiny_gpt()
        eng = ServingEngine(model, num_slots=1, max_len=32)
        driver = EngineDriver(eng, name="r0",
                              watchdog_grace_per_token_s=0.01)
        assert driver.watchdog_grace_s == 0.0
        eng.step_tokens_inflight = 200
        assert driver.watchdog_grace_s == pytest.approx(2.0)
        eng.step_tokens_inflight = 0
        assert driver.watchdog_grace_s == 0.0


# -- Ticket migration cap ----------------------------------------------------
def make_cluster(n_replicas=2, *, faults=None, router_kw=None,
                 **engine_kw):
    model = tiny_gpt()
    kw = dict(num_slots=2, max_len=64)
    kw.update(engine_kw)
    engines = [ServingEngine(model, **kw) for _ in range(n_replicas)]
    for e in engines:
        e.generate([np.array([1, 2, 3])],
                   SamplingParams(max_new_tokens=2))
    drivers = [EngineDriver(e, name=f"replica-{i}", faults=faults)
               for i, e in enumerate(engines)]
    router = Router(drivers, **(router_kw or {})).start()
    return model, engines, drivers, router


class TestMigrationCap:
    def test_cap_zero_fails_fast_with_typed_error(self):
        """max_migrations=0: a started stream whose replica dies is
        NOT re-placed — it closes as replica_failure with the typed
        error recorded and usage.migrations surfaced as-is."""
        model, engines, drivers, router = make_cluster(
            2, router_kw=dict(max_migrations=0))
        t = router.submit(np.array([3, 14, 15], np.int64),
                          SamplingParams(max_new_tokens=30))
        assert wait_until(lambda: len(t.request.output_tokens) > 0)
        t.driver.kill()
        tokens, done, err = consume(t)
        assert done == "replica_failure" and err is None
        assert isinstance(t.error, ReplicaDead)
        assert t.migrations == 0
        out = t.output()
        assert out.migrations == 0
        # the delivered partial stream EXACTLY — a terminal failover
        # must not double-count the banked dead attempt's tokens
        assert out.token_ids == tokens and 0 < len(tokens) < 30
        router.drain()

    @pytest.mark.slow
    def test_chaos_killing_every_survivor_terminates(self):
        """The every-replica-dying loop ends in bounded attempts: each
        migration costs one replica; when none is left the stream
        closes as replica_failure instead of retrying forever. (Soak
        lane; the cap semantics themselves are pinned non-slow by
        test_cap_zero_fails_fast_with_typed_error.)"""
        model, engines, drivers, router = make_cluster(
            2, router_kw=dict(max_migrations=8, backoff_base_s=0.01))
        t = router.submit(np.array([3, 14, 15], np.int64),
                          SamplingParams(max_new_tokens=60))
        got = []

        def killer():
            # kill whichever replica currently hosts the stream, as
            # soon as it has streamed on that replica — every survivor
            # dies, one after the other
            for _ in range(2):
                cur = t.driver
                if not wait_until(
                        lambda: len(t.request.output_tokens) > 0
                        or cur.dead, timeout=20):
                    return
                cur.kill()
                wait_until(lambda: t.driver is not cur or cur.dead,
                           timeout=20)

        kt = threading.Thread(target=killer)
        kt.start()
        tokens, done, err = consume(t)
        kt.join()
        assert done == "replica_failure" or err is not None
        assert t.migrations <= router.max_migrations
        assert t.attempts <= 2 + router.max_retries


# -- overload spikes (fault injection) ---------------------------------------
class TestOverloadSpikes:
    def test_spike_unit_fires_once(self):
        inj = FaultInjector()
        inj.spike_at_step("r0", 3, 5)
        assert inj.take_spike("r0", 2) == 0
        assert inj.take_spike("r1", 99) == 0
        assert inj.take_spike("r0", 3) == 5
        assert inj.take_spike("r0", 4) == 0     # one-shot
        assert inj.spikes_fired == 1

    def test_env_spec_parses_spike(self):
        inj = FaultInjector.parse("spike:replica-0@20x8")
        assert inj._spikes == {"replica-0": [(20, 8)]}

    @pytest.mark.slow
    def test_spike_floods_real_admission_path(self):
        """An injected spike submits junk at rock-bottom priority
        through engine.add_request: real requests outrank it. (Slow
        marker: the spike units above pin the mechanics; this is the
        cluster e2e.)"""
        inj = FaultInjector().spike_at_step("replica-0", 0, 3)
        model, engines, drivers, router = make_cluster(1, faults=inj)
        t = router.submit(np.array([3, 14, 15], np.int64),
                          SamplingParams(max_new_tokens=8))
        tokens, done, err = consume(t)
        assert done == "length" and err is None
        assert tokens == oracle_greedy(model, [3, 14, 15], 8)
        assert inj.spikes_fired == 1
        assert engines[0].metrics.requests_received >= 4  # 1 real + 3
        router.drain()


# -- chaos: replica kill mid-preemption --------------------------------------
class TestKillMidPreemption:
    def test_preempted_stream_migrates_token_identical(self):
        """ISSUE acceptance: a replica dies while a preempted request
        sits swapped-out in its queue. The banked history migrates to
        the survivor and the stream completes exactly; the dead
        engine's abort leaves no host-tier leak (abort_all runs
        assert_quiesced internally)."""
        model, engines, drivers, router = make_cluster(
            2, num_slots=1, max_len=64, page_size=8, chunk_len=16)
        prompt = np.array([3, 14, 15, 9], np.int64)
        want = oracle_greedy(model, prompt, 30)
        lo = router.submit(prompt, SamplingParams(max_new_tokens=30,
                                                  priority=5))
        victim_driver = lo.driver
        victim_engine = victim_driver.engine
        assert wait_until(lambda: len(lo.request.output_tokens) > 2)
        # a high-priority arrival on the same replica forces the
        # preemption (1 slot); route it directly through the driver
        hi = victim_driver.submit(np.arange(30, 38),
                                  SamplingParams(max_new_tokens=24,
                                                 priority=0))
        assert wait_until(
            lambda: victim_engine.metrics.preemptions >= 1)
        victim_driver.kill()              # dies mid-preemption
        tokens, done, err = consume(lo)
        assert done == "length" and err is None
        out = lo.output()
        assert out.token_ids == want      # banked + migrated, exact
        assert out.migrations == 1
        assert out.preemptions >= 1       # banked across the death
        router.drain()
        for e in engines:
            assert e.host_pool.used_pages == 0


# -- HTTP surface ------------------------------------------------------------
class TestOverloadHTTP:
    def test_priority_deadline_parse_and_validation(self):
        from paddle_tpu.serving.http.protocol import (
            ProtocolError, parse_completion_request)
        creq = parse_completion_request(json.dumps({
            "prompt": [1, 2, 3], "priority": 7,
            "deadline": 1.5}).encode())
        assert creq.sampling.priority == 7
        assert creq.sampling.deadline_s == 1.5
        with pytest.raises(ProtocolError):
            parse_completion_request(json.dumps({
                "prompt": [1], "deadline": -1}).encode())
        with pytest.raises(ProtocolError):
            parse_completion_request(json.dumps({
                "prompt": [1], "priority": "high"}).encode())

    def test_deadline_504_and_preemption_usage_over_http(self):
        """End-to-end taxonomy: a queued request whose deadline
        expires gets 504 (preemption off would strand it; here the
        equal priority blocks preemption), and a preempted-and-
        resumed stream reports usage.preemptions with exact tokens."""
        import http.client
        model = tiny_gpt()
        eng = ServingEngine(model, num_slots=1, max_len=128,
                            page_size=8, chunk_len=16)
        eng.generate([np.array([1, 2, 3])],
                     SamplingParams(max_new_tokens=2))
        server = serve([eng], poll_interval_s=0.01)
        host, port = server.server_address[:2]

        def post(body):
            conn = http.client.HTTPConnection(host, port, timeout=60)
            conn.request("POST", "/v1/completions", json.dumps(body),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            out = (resp.status, json.loads(resp.read()))
            conn.close()
            return out

        lo_prompt = [3, 14, 15, 9]
        want = oracle_greedy(model, lo_prompt, 110)
        lo_result = {}

        def lo_client():
            # long enough that the resident outlives the queued
            # request's deadline by a wide margin on any machine
            lo_result["resp"] = post({"prompt": lo_prompt,
                                      "max_tokens": 110,
                                      "priority": 5})

        base_tokens = eng.metrics.tokens_generated   # warm-up noise
        lt = threading.Thread(target=lo_client)
        lt.start()
        assert wait_until(
            lambda: eng.metrics.tokens_generated > base_tokens)
        # equal-priority arrival cannot preempt: it queues, its tight
        # deadline expires -> 504 with the typed error body
        status, body = post({"prompt": [5, 6, 7], "max_tokens": 4,
                             "priority": 5, "deadline": 0.05})
        assert status == 504
        assert body["error"]["code"] == 504
        # higher-priority arrival preempts the resident
        status, body = post({"prompt": [8, 9, 10], "max_tokens": 4,
                             "priority": 0})
        assert status == 200
        lt.join()
        status, body = lo_result["resp"]
        assert status == 200
        assert body["choices"][0]["token_ids"] == want
        assert body["usage"]["preemptions"] >= 1
        server.drain()

# -- bench -------------------------------------------------------------------
def _run_bench(tmp_path, monkeypatch, extra):
    import importlib.util
    script = os.path.join(os.path.dirname(__file__), os.pardir,
                          "scripts", "serving_bench.py")
    spec = importlib.util.spec_from_file_location(
        "serving_bench_overload", script)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = str(tmp_path / "BENCH_serving.json")
    monkeypatch.setattr(sys, "argv",
                        ["serving_bench.py"] + extra + ["--out", out])
    mod.main()
    with open(out) as f:
        return json.load(f)


@pytest.mark.slow
def test_serving_bench_overload_smoke():
    """The bench's deterministic virtual-time 3x-overload A/B (ISSUE
    acceptance), driven directly through `overload_trace` (the slow
    soak exercises the full `main()` + schema path): zero
    high-priority deadline misses and strictly better high-priority
    goodput with preemption on, preemption/swap traffic recorded, and
    the priority-flat fault-free replay bit-identical on vs off."""
    import importlib.util
    script = os.path.join(os.path.dirname(__file__), os.pardir,
                          "scripts", "serving_bench.py")
    spec = importlib.util.spec_from_file_location(
        "serving_bench_overload_direct", script)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    model, cfg = mod.build_model(False)
    ov = mod.overload_trace(model, cfg, slots=2, seed=3, scale=1)
    assert set(ov) >= {"on", "off", "fault_free", "deadline_s",
                      "high_goodput_tokens_per_virtual_s"}
    on, off = ov["on"], ov["off"]
    assert on["high_priority"]["deadline_misses"] == 0
    assert on["high_priority"]["completed"] == ov["requests_high"]
    assert off["high_priority"]["deadline_misses"] >= 1
    assert on["preemptions"] >= 1 and off["preemptions"] == 0
    assert on["swapped_in_pages"] == on["swapped_out_pages"] >= 1
    assert on["swap_in_p99_s"] is not None
    gp = ov["high_goodput_tokens_per_virtual_s"]
    assert gp["on"] > gp["off"]
    # degradation, not starvation: the low class still finishes
    assert on["low_priority"]["completed"] == ov["requests_low"]
    assert ov["fault_free"]["identical"] is True


@pytest.mark.slow
def test_overload_soak(tmp_path, monkeypatch):
    """The overload soak (slow marker): a 3x-scaled trace through the
    same deterministic harness — the zero-miss / strictly-better
    goodput / fault-free-identity contract must hold at load."""
    report = _run_bench(tmp_path, monkeypatch,
                        ["--smoke", "--requests", "3", "--slots", "4",
                         "--overload", "--overload-scale", "3"])
    assert report["schema_version"] == 19
    ov = report["overload"]
    assert ov["on"]["high_priority"]["deadline_misses"] == 0
    assert ov["on"]["high_priority"]["completed"] == \
        ov["requests_high"]
    assert ov["off"]["high_priority"]["deadline_misses"] >= 1
    assert ov["fault_free"]["identical"] is True
    assert ov["on"]["low_priority"]["completed"] == ov["requests_low"]
