"""Prefix-sharing-aware grouped attention (the grouped page walk).

Contracts:
- `ragged_paged_attention_grouped` (interpret-mode kernel) matches the
  ragged reference on shared-prefix batches AND is BIT-identical to
  the ungrouped kernel (same page order per row, same online-softmax
  recurrence — the two-phase walk changes HBM traffic, not math);
  a group of 1 (group_cnt 0) degenerates to exactly the ungrouped
  walk; the q8 lane moves code+scale pages through the same walk;
- phase 1's (group x page) sweep runs only on a step where some
  group_cnt is non-zero (a dynamic grid bound, decided from operand
  data): the one-step sweep of a step with nothing shared leaves the
  virgin partials the full sweep would have, and one trace serves
  steps with and without a group, on both lanes;
- `shared_prefix_groups` partitions rows by physical-page-prefix
  equality: trash entries never match, a COW'd page splits its row
  out exactly at the divergence point, deeper subgroup sharing beats
  a shallow umbrella group when it saves more reads, idle rows stay
  singletons;
- `count_page_block_reads` (the CPU-reference DMA model) prices the
  flat walk at one read per live page per row and the grouped walk at
  one read per shared page per GROUP;
- a ServingEngine that compiles the grouped walk (it has a prefix
  cache) emits bit-identical greedy tokens to one that compiles the
  flat walk (it has none) — through prefix-cache COW landing mid-span,
  eviction pressure, member retirement shrinking a group, and the
  int8 lane — while `shared_page_reads_saved_total` actually grows
  and the ONE unified trace never retraces;
- the new metrics render to Prometheus (saved-reads counter,
  group-size histogram, `grouped` tag in engine_info), and
  `grouped_walk_steps_total` counts exactly the steps whose group_cnt
  had a non-zero entry.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.serving import (SamplingParams, ServingEngine,
                                prometheus_render, shared_prefix_groups)

_MODELS = {}


def tiny_gpt():
    m = _MODELS.get("gpt")
    if m is None:
        paddle.seed(11)
        cfg = GPTConfig(vocab_size=89, hidden_size=32,
                        num_hidden_layers=2, num_attention_heads=4,
                        intermediate_size=64,
                        max_position_embeddings=128,
                        hidden_dropout_prob=0.0,
                        attention_probs_dropout_prob=0.0)
        m = _MODELS["gpt"] = GPTForCausalLM(cfg)
        m.eval()
    return m


def build_shared(rng, ps, mp, hkv, d, n_shared, members, extra):
    """Pools + page tables where the first `members` rows share an
    `n_shared`-page physical prefix and every row carries its own
    private tail; `extra` rows are fully private. Returns
    (kp, vp, pt, pos, q_len, gid, gld, gcnt) with pos covering the
    shared span for every member (the engine-side operand
    contract)."""
    b = members + extra
    pt = np.zeros((b, mp), np.int32)
    nxt = 1 + n_shared
    for r in range(b):
        start = 0
        if r < members:
            pt[r, :n_shared] = np.arange(1, 1 + n_shared)
            start = n_shared
        for i in range(start, mp - 1):
            pt[r, i] = nxt
            nxt += 1
    kp = rng.randn(nxt, ps, hkv, d).astype(np.float32)
    vp = rng.randn(nxt, ps, hkv, d).astype(np.float32)
    pos = np.array([n_shared * ps + rng.randint(0, 2 * ps)
                    if r < members else rng.randint(0, 2 * ps)
                    for r in range(b)], np.int32)
    q_len = np.array([1 + (r % 3) * 3 for r in range(b)], np.int32)
    gid = np.array([0] * members
                   + list(range(1, 1 + extra)), np.int32)
    gld = np.zeros(b, np.int32)
    gcnt = np.zeros(b, np.int32)
    gcnt[0] = n_shared
    return kp, vp, pt, pos, q_len, gid, gld, gcnt


def q8_pools(rng, shape):
    """Random int8 code pools of `shape` [P, ps, H_kv, D] with their
    rowwise scale pools: (kp, vp, ks, vs)."""
    codes = [rng.randint(-127, 128, size=shape).astype(np.int8)
             for _ in range(2)]
    scales = [(np.abs(rng.randn(*shape[:3])) / 127).astype(np.float32)
              for _ in range(2)]
    return (*codes, *scales)


# lane -> (ungrouped kernel, grouped op)
LANE_OPS = {
    "fp": (pa.ragged_paged_attention,
           pa.ragged_paged_attention_grouped),
    "q8": (pa.ragged_paged_attention_q8,
           pa.ragged_paged_attention_grouped_q8),
}


class TestGroupedKernel:
    """Interpret-mode grouped kernel vs the ragged reference and the
    ungrouped kernel."""

    @pytest.fixture(autouse=True)
    def _interpret(self, monkeypatch):
        monkeypatch.setattr(pa, "_INTERPRET", True)
        # key blocks of two pages of 8: the shared spans here (two
        # pages) end on a block's edge, where the grouped walk folds
        # the same blocks in the same order as the ungrouped one
        monkeypatch.setattr(pa, "K_BLOCK", 16)

    @pytest.mark.parametrize("rep", [1, 2])
    def test_matches_reference_and_ungrouped_bit_identical(self, rep):
        rng = np.random.RandomState(rep)
        ps, mp, hkv, d = 8, 6, 2, 128    # heads of 128: pools read in place
        kp, vp, pt, pos, q_len, gid, gld, gcnt = build_shared(
            rng, ps, mp, hkv, d, n_shared=2, members=3, extra=2)
        h = hkv * rep
        lq = int(q_len.max())
        q = rng.randn(len(q_len), lq, h, d).astype(np.float32)
        args = (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                jnp.asarray(pt), jnp.asarray(pos), jnp.asarray(q_len))
        ref = np.asarray(pa.ragged_attention_reference(*args))
        ung = np.asarray(pa.ragged_paged_attention(*args))
        grp = np.asarray(pa.ragged_paged_attention_grouped(
            *args, jnp.asarray(gid), jnp.asarray(gld),
            jnp.asarray(gcnt)))
        for r in range(len(q_len)):
            ql = int(q_len[r])
            np.testing.assert_allclose(grp[r, :ql], ref[r, :ql],
                                       rtol=2e-5, atol=2e-6)
            # same page order, same recurrence -> same bits
            np.testing.assert_array_equal(grp[r, :ql], ung[r, :ql])

    @pytest.mark.parametrize("lane", ["fp", "q8"])
    def test_group_of_one_bit_identical_to_ungrouped(self, lane):
        """All-singleton operands (group_cnt 0 everywhere) ARE the
        ungrouped walk: phase 1 touches nothing, phase 2 starts from
        the virgin partials at page 0."""
        rng = np.random.RandomState(3)
        ps, mp, hkv, d = 8, 5, 2, 128    # heads of 128: pools read in place
        kp, vp, pt, pos, q_len, *_ = build_shared(
            rng, ps, mp, hkv, d, n_shared=0, members=0, extra=4)
        lq = int(q_len.max())
        q = rng.randn(4, lq, hkv, d).astype(np.float32)
        pools = (kp, vp) if lane == "fp" else q8_pools(rng, kp.shape)
        ung_op, grp_op = LANE_OPS[lane]
        args = tuple(jnp.asarray(a)
                     for a in (q, *pools, pt, pos, q_len))
        ung = np.asarray(ung_op(*args))
        grp = np.asarray(grp_op(
            *args, jnp.arange(4, dtype=jnp.int32),
            jnp.zeros(4, jnp.int32), jnp.zeros(4, jnp.int32)))
        for r in range(4):
            ql = int(q_len[r])
            np.testing.assert_array_equal(grp[r, :ql], ung[r, :ql])

    @pytest.mark.parametrize("lane", ["fp", "q8"])
    def test_no_group_phase1_is_one_idle_grid_step(self, lane,
                                                   monkeypatch):
        """With no group to serve, phase 1 is ONE grid step that moves
        nothing (its work items are the groups that share), and phase
        2 starts every row from the virgin partials the ungrouped walk
        starts from: the step equals the ungrouped one bit for bit.
        With a group it has a work item a sharing group and q-block,
        over the key blocks of the longest shared span."""
        rng = np.random.RandomState(7)
        ps, mp, hkv, d = 8, 5, 2, 128    # heads of 128: pools read in place
        kp, vp, pt, pos, q_len, gid, gld, gcnt = build_shared(
            rng, ps, mp, hkv, d, n_shared=3, members=2, extra=2)
        b = len(q_len)
        pools = (kp, vp) if lane == "fp" else q8_pools(rng, kp.shape)
        q = rng.randn(b, int(q_len.max()), hkv, d).astype(np.float32)
        ung_op, grp_op = LANE_OPS[lane]
        args = tuple(jnp.asarray(a) for a in (q, *pools, pt, pos, q_len))
        grids = []
        real = pa.pl.pallas_call

        def spy(kernel, **kw):
            if kw["name"] == "grouped_phase1":
                grids.append(tuple(int(g) for g in kw["grid_spec"].grid))
            return real(kernel, **kw)

        monkeypatch.setattr(pa.pl, "pallas_call", spy)
        with jax.disable_jit():           # the bounds as numbers
            alone = np.asarray(grp_op(
                *args, jnp.arange(b, dtype=jnp.int32),
                jnp.zeros(b, jnp.int32), jnp.zeros(b, jnp.int32)))
            grp_op(*args, *(jnp.asarray(g) for g in (gid, gld, gcnt)))
        # one sharing group, one q-block; three shared pages are two
        # key blocks of two
        assert grids == [(1, 1), (1, 2)]
        ung = np.asarray(ung_op(*args))
        for r in range(b):
            ql = int(q_len[r])
            np.testing.assert_array_equal(alone[r, :ql], ung[r, :ql])

    @pytest.mark.parametrize("leader_group", ["first", "last"])
    @pytest.mark.parametrize("lane", ["fp", "q8"])
    def test_one_trace_serves_steps_with_and_without_a_group(
            self, lane, leader_group):
        """Sharing comes and goes as operand DATA: one jitted call
        runs phase 1 over the sharing group on a step with one (also
        when it carries the LAST group id) and one idle grid step on a
        step without, with one trace, and every step equals the
        ungrouped kernel bit for bit."""
        rng = np.random.RandomState(8)
        ps, mp, hkv, d = 8, 6, 2, 128    # heads of 128: pools read in place
        members, extra = 3, 2
        kp, vp, pt, pos, q_len, gid, gld, gcnt = build_shared(
            rng, ps, mp, hkv, d, n_shared=2, members=members,
            extra=extra)
        b = members + extra
        if leader_group == "last":
            gid = np.array([b - 1] * members + list(range(extra)),
                           np.int32)
            gcnt = gcnt[::-1].copy()          # leader row 0 either way
        q = rng.randn(b, int(q_len.max()), hkv * 2, d) \
            .astype(np.float32)
        pools = (kp, vp) if lane == "fp" else q8_pools(rng, kp.shape)
        ung_op, grp_op = LANE_OPS[lane]
        args = tuple(jnp.asarray(a)
                     for a in (q, *pools, pt, pos, q_len))
        traces = []

        @jax.jit
        def step(*a):
            traces.append(1)
            return grp_op(*a)

        ung = np.asarray(ung_op(*args))
        shared = (jnp.asarray(gid), jnp.asarray(gld),
                  jnp.asarray(gcnt))
        alone = (jnp.arange(b, dtype=jnp.int32),
                 jnp.zeros(b, jnp.int32), jnp.zeros(b, jnp.int32))
        for group in (shared, alone, shared):
            out = np.asarray(step(*args, *group))
            for r in range(b):
                ql = int(q_len[r])
                np.testing.assert_array_equal(out[r, :ql],
                                              ung[r, :ql])
        assert len(traces) == 1 and step._cache_size() == 1
        # both phases in the one program, no branch around either:
        # phase 1's q-blocks and sweep, the walk's q-blocks and pages
        # are dynamic grid bounds
        # (the walk is a program of its own inside the caller's)
        (walk,) = [e for e in
                   jax.make_jaxpr(grp_op)(*args, *shared).jaxpr.eqns
                   if e.primitive.name in ("pjit", "jit")]
        top = walk.params["jaxpr"].jaxpr.eqns
        assert "cond" not in [e.primitive.name for e in top]
        grids = {e.params["name"]:
                 e.params["grid_mapping"].num_dynamic_grid_bounds
                 for e in top if e.primitive.name == "pallas_call"}
        assert grids == {"grouped_phase1": 2, "ragged_walk": 2}

    def test_grouped_q8_lane_matches_q8_reference(self):
        """Code AND scale pages chase the same grouped walk; results
        match the q8 reference and the ungrouped q8 kernel."""
        rng = np.random.RandomState(4)
        ps, mp, hkv, d = 8, 5, 2, 128    # heads of 128: pools read in place
        _, _, pt, pos, q_len, gid, gld, gcnt = build_shared(
            rng, ps, mp, hkv, d, n_shared=2, members=3, extra=1)
        pools = q8_pools(rng, (int(pt.max()) + 1, ps, hkv, d))
        lq = int(q_len.max())
        q = rng.randn(len(q_len), lq, hkv * 2, d).astype(np.float32)
        args = tuple(jnp.asarray(a)
                     for a in (q, *pools, pt, pos, q_len))
        ref = np.asarray(pa.ragged_attention_reference_q8(*args))
        ung = np.asarray(pa.ragged_paged_attention_q8(*args))
        grp = np.asarray(pa.ragged_paged_attention_grouped_q8(
            *args, jnp.asarray(gid), jnp.asarray(gld),
            jnp.asarray(gcnt)))
        for r in range(len(q_len)):
            ql = int(q_len[r])
            np.testing.assert_allclose(grp[r, :ql], ref[r, :ql],
                                       rtol=2e-5, atol=2e-6)
            np.testing.assert_array_equal(grp[r, :ql], ung[r, :ql])


class TestSharedPrefixGroups:
    def test_basic_grouping_and_trash_exclusion(self):
        pt = np.array([[3, 2, 5, 4, 0],
                       [3, 2, 8, 7, 6],
                       [3, 2, 11, 10, 9],
                       [13, 12, 0, 0, 0],
                       [0, 0, 0, 0, 0]], np.int32)
        gid, gld, gcnt = shared_prefix_groups(pt, np.ones(5, np.int32))
        # rows 0-2 one group over the 2 shared pages; 3 and the
        # trash-rooted 4 are singletons
        assert gid[0] == gid[1] == gid[2]
        assert gcnt[gid[0]] == 2
        assert gld[gid[0]] in (0, 1, 2)
        assert gid[3] != gid[0] and gid[4] != gid[0]
        assert gcnt[gid[3]] == 0 and gcnt[gid[4]] == 0

    def test_deeper_subgroup_wins_when_it_saves_more(self):
        # rows 0,1 share 4 pages; row 2 shares only page 0 with them:
        # {0,1} at span 4 saves 4 reads, the umbrella {0,1,2} at span
        # 1 saves 2 — the split wins and row 2 closes alone
        pt = np.array([[3, 2, 5, 4, 0],
                       [3, 2, 5, 4, 9],
                       [3, 7, 0, 0, 0]], np.int32)
        gid, gld, gcnt = shared_prefix_groups(pt, np.ones(3, np.int32))
        assert gid[0] == gid[1] != gid[2]
        assert gcnt[gid[0]] == 4
        assert gcnt[gid[2]] == 0

    def test_cow_divergence_splits_exactly_at_the_cow_page(self):
        # three rows shared 3 pages; row 2's middle page went COW
        # (private copy id 9): it falls out at index 1, the others
        # keep the full span
        pt = np.array([[3, 2, 6, 30, 0],
                       [3, 2, 6, 31, 0],
                       [3, 9, 32, 33, 0]], np.int32)
        gid, gld, gcnt = shared_prefix_groups(pt, np.ones(3, np.int32))
        assert gid[0] == gid[1] != gid[2]
        assert gcnt[gid[0]] == 3
        assert gcnt[gid[2]] == 0

    def test_idle_rows_never_group(self):
        pt = np.array([[3, 2, 0, 0],
                       [3, 2, 0, 0],
                       [3, 2, 0, 0]], np.int32)
        gid, _, gcnt = shared_prefix_groups(
            pt, np.array([1, 0, 1], np.int32))
        assert gid[0] == gid[2] != gid[1]
        assert gcnt[gid[0]] == 2
        assert gcnt[gid[1]] == 0

    def test_count_page_block_reads_model(self):
        # rows 0,1 share 2 pages; row 0 lives on 4 pages, row 1 on 3,
        # row 2 (private) on 2, row 3 idle
        pt = np.zeros((4, 8), np.int32)
        pos = np.array([25, 20, 10, 5], np.int32)
        q_len = np.array([1, 4, 1, 0], np.int32)
        ps = 8
        gid = np.array([0, 0, 1, 2], np.int32)
        gcnt = np.array([2, 0, 0, 0], np.int32)
        flat, grouped, sizes = pa.count_page_block_reads(
            pt, pos, q_len, gid, gcnt, page_size=ps)
        # live pages: row0 (25+1-1)//8+1 = 4, row1 (20+4-1)//8+1 = 3,
        # row2 (10+1-1)//8+1 = 2, row3 idle 0
        assert flat == 4 + 3 + 2
        # grouped: shared 2 once + tails (4-2) + (3-2) + row2's 2
        assert grouped == 2 + 2 + 1 + 2
        assert sizes == [2]
        # without group operands the model is the flat walk
        f2, g2, s2 = pa.count_page_block_reads(pt, pos, q_len,
                                               page_size=ps)
        assert f2 == g2 == flat and s2 == []


def run_ab(model, prompts, max_new, *, warm=(), **kw):
    """The same batch through an engine that compiles the grouped walk
    and one that compiles the flat walk (no prefix cache: nothing can
    put a page into two rows' tables); returns (tokens_grouped,
    tokens_flat, engine_grouped)."""
    outs = {}
    engines = {}
    for flag in (True, False):
        eng = ServingEngine(model, prefix_cache=flag, **kw)
        assert eng.grouped is flag
        if warm:
            eng.generate(list(warm), SamplingParams(max_new_tokens=2))
        res = eng.generate(prompts, SamplingParams(
            max_new_tokens=max_new))
        outs[flag] = [list(o.token_ids) for o in res]
        engines[flag] = eng
    return outs[True], outs[False], engines[True]


class TestGroupedEngine:
    def _prompts(self, rng, sys_p, tails):
        return [np.concatenate(
            [sys_p, rng.randint(0, 89, size=n).astype(np.int64)])
            for n in tails]

    def test_tokens_identical_and_reads_saved(self):
        model = tiny_gpt()
        rng = np.random.RandomState(0)
        sys_p = rng.randint(0, 89, size=20).astype(np.int64)
        prompts = self._prompts(rng, sys_p, (3, 5, 7)) \
            + [rng.randint(0, 89, size=6).astype(np.int64)]
        on, off, eng = run_ab(model, prompts, 8, warm=[sys_p],
                              num_slots=4, max_len=64, page_size=8,
                              chunk_len=16)
        assert on == off
        snap = eng.metrics.snapshot()
        assert snap["grouped"] is True
        assert snap["shared_page_reads_saved_total"] > 0
        assert snap["group_size_per_step"]["max"] >= 3
        # the ONE unified program never retraced across group changes
        assert eng._unified_fn._cache_size() == 1

    def test_cow_mid_span_and_eviction_pressure(self):
        """Prompts whose shared prefix ends mid-page COW their partial
        page (the COW'd row's group span stops at the divergence), and
        a small pool forces eviction between steps — tokens stay
        bit-identical to the flat walk's through both."""
        model = tiny_gpt()
        rng = np.random.RandomState(1)
        sys_p = rng.randint(0, 89, size=20).astype(np.int64)  # 2.5 pgs
        prompts = self._prompts(rng, sys_p, (2, 3, 9, 11))
        on, off, eng = run_ab(model, prompts, 6, warm=[sys_p],
                              num_slots=3, max_len=64, page_size=8,
                              num_pages=13, chunk_len=16,
                              host_pages=0)   # no spill tier: EVICT
        assert on == off
        snap = eng.metrics.snapshot()
        assert snap["prefix"]["cow_copies"] > 0
        assert snap["prefix"]["evicted_pages"] > 0
        assert snap["shared_page_reads_saved_total"] > 0

    def test_group_shrinks_when_a_member_retires(self):
        """Three sharers with different budgets: after the shortest
        finishes, the LIVE page tables regroup to a smaller group —
        groups are per-step data, never trace state."""
        model = tiny_gpt()
        rng = np.random.RandomState(2)
        sys_p = rng.randint(0, 89, size=16).astype(np.int64)
        eng = ServingEngine(model, num_slots=3, max_len=64,
                            page_size=8, chunk_len=16)
        eng.generate([sys_p], SamplingParams(max_new_tokens=2))
        prompts = self._prompts(rng, sys_p, (3, 4, 5))
        reqs = [eng.add_request(p, SamplingParams(
            max_new_tokens=n)) for p, n in zip(prompts, (2, 8, 8))]
        sizes = []
        while eng.has_work:
            eng.step()
            q_len = np.array([1 if s in eng.scheduler.running else 0
                              for s in range(3)], np.int32)
            gid, _, gcnt = shared_prefix_groups(eng._pt_host, q_len)
            live_groups = [int((gid[q_len > 0] == g).sum())
                           for g in set(gid[q_len > 0])]
            if live_groups:
                sizes.append(max(live_groups))
        assert reqs[0].finish_reason == "length"
        assert 3 in sizes and 2 in sizes     # shrank, never retraced
        assert eng._unified_fn._cache_size() == 1

    def test_grouped_int8_lane_token_identity(self):
        model = tiny_gpt()
        rng = np.random.RandomState(5)
        sys_p = rng.randint(0, 89, size=16).astype(np.int64)
        prompts = self._prompts(rng, sys_p, (3, 6))
        on, off, eng = run_ab(model, prompts, 6, warm=[sys_p],
                              num_slots=2, max_len=64, page_size=8,
                              chunk_len=16, kv_dtype="int8")
        assert on == off
        assert eng.kv_dtype == "int8" and eng.grouped
        assert eng.metrics.snapshot()[
            "shared_page_reads_saved_total"] > 0

    def test_the_engine_derives_the_grouped_walk(self):
        """No option: the group operands ride the step iff the Pallas
        walk serves it and the engine has a prefix cache."""
        kw = dict(num_slots=2, max_len=32, page_size=8, chunk_len=8)
        model = tiny_gpt()
        assert ServingEngine(model, **kw).grouped is True
        assert ServingEngine(model, prefix_cache=False,
                             **kw).grouped is False
        assert ServingEngine(model, attn_impl="gather",
                             **kw).grouped is False
        with pytest.raises(TypeError):
            ServingEngine(model, grouped=False, **kw)

    @pytest.mark.parametrize("share", [True, False])
    def test_grouped_walk_steps_counts_steps_with_a_group(
            self, monkeypatch, share):
        """`grouped_walk_steps_total` counts exactly the unified steps
        whose group_cnt operand had a non-zero entry (the datum the
        compiled step sizes phase 1 from): some of the steps of
        prompts that share a cached prefix, none for independent
        prompts."""
        from paddle_tpu.serving import engine as engine_mod
        seen = []

        def spy(pt, q_len):
            out = shared_prefix_groups(pt, q_len)
            seen.append(bool(out[2].any()))
            return out

        monkeypatch.setattr(engine_mod, "shared_prefix_groups", spy)
        model = tiny_gpt()
        rng = np.random.RandomState(9)
        sys_p = rng.randint(0, 89, size=16).astype(np.int64)
        eng = ServingEngine(model, num_slots=3, max_len=64,
                            page_size=8, chunk_len=16)
        if share:
            eng.generate([sys_p], SamplingParams(max_new_tokens=2))
            prompts = self._prompts(rng, sys_p, (3, 4, 5))
        else:
            prompts = [rng.randint(0, 89, size=n).astype(np.int64)
                       for n in (19, 20, 21)]
        eng.generate(prompts, SamplingParams(max_new_tokens=6))
        snap = eng.metrics.snapshot()
        assert len(seen) == snap["unified_steps"] > 0
        assert snap["grouped_walk_steps_total"] == sum(seen)
        assert (0 < sum(seen) < len(seen)) if share else not any(seen)
        assert eng._unified_fn._cache_size() == 1
        text = prometheus_render({"r0": snap})
        assert ("paddle_serving_grouped_walk_steps_total"
                f'{{replica="r0"}} {sum(seen)}') in text

    def test_no_prefix_cache_never_counts_a_phase1_step(self):
        model = tiny_gpt()
        rng = np.random.RandomState(10)
        sys_p = rng.randint(0, 89, size=16).astype(np.int64)
        eng = ServingEngine(model, num_slots=3, max_len=64,
                            page_size=8, chunk_len=16,
                            prefix_cache=False)
        eng.generate([sys_p], SamplingParams(max_new_tokens=2))
        eng.generate(self._prompts(rng, sys_p, (3, 4, 5)),
                     SamplingParams(max_new_tokens=4))
        snap = eng.metrics.snapshot()
        assert snap["unified_steps"] > 0
        assert snap["grouped_walk_steps_total"] == 0

    def test_prometheus_renders_grouped_series(self):
        model = tiny_gpt()
        rng = np.random.RandomState(6)
        sys_p = rng.randint(0, 89, size=16).astype(np.int64)
        prompts = self._prompts(rng, sys_p, (3, 5))
        _, _, eng = run_ab(model, prompts, 4, warm=[sys_p],
                           num_slots=2, max_len=64, page_size=8,
                           chunk_len=16)
        text = prometheus_render({"r0": eng.metrics.snapshot()})
        assert 'grouped="on"' in text
        assert "paddle_serving_shared_page_reads_saved_total" in text
        assert "paddle_serving_page_block_reads_total" in text
        assert "paddle_serving_group_size_per_step_bucket" in text
        assert ("# TYPE paddle_serving_grouped_walk_steps_total counter"
                in text)
