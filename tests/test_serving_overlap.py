"""One step ahead: a round plans and launches step n + 1 while step n
runs, and only then fetches and commits n (`ServingEngine._unified_step`).

What must hold whatever overlaps: every greedy request's tokens are the
solo `CompiledGenerator` oracle's; a row whose budget ends at step n is
never planned into n + 1, and one that ends on EOS or a cancel rides
one step more, whose token its commit drops; a round that runs another
program on the pools waits for the step in flight, and one that
preempts, quarantines or needs the tokens on the host (speculation,
grammar) commits it first; the pools quiesce after a drain or an abort;
and the engine still compiles ONE step program.
`overlapped_steps_total` / `serial_fallback_steps_total` say how often
each kind of round ran.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import (FaultInjector, GrammarSpec, SamplingParams,
                                ServingEngine)

_MODELS = {}


def tiny_gpt():
    m = _MODELS.get("gpt")
    if m is None:
        paddle.seed(7)
        m = _MODELS["gpt"] = GPTForCausalLM(GPTConfig(
            vocab_size=97, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=64,
            max_position_embeddings=128, hidden_dropout_prob=0.0,
            attention_probs_dropout_prob=0.0))
        m.eval()
    return m


def oracle_greedy(prompt, n_new):
    """The request alone through CompiledGenerator greedy decode."""
    out = tiny_gpt().generate(paddle.to_tensor(np.asarray(prompt)[None]),
                              max_new_tokens=n_new).numpy()
    return out[0, len(prompt):].tolist()


def engine(**kw):
    kw = {"num_slots": 3, "max_len": 64, "page_size": 8, "chunk_len": 8,
          **kw}
    return ServingEngine(tiny_gpt(), **kw)


def greedy(n_new, **kw):
    return SamplingParams(max_new_tokens=n_new, **kw)


def prompt(seed, n):
    return np.random.RandomState(seed).randint(0, 97, size=n) \
        .astype(np.int64)


def steps_counted(eng):
    """(overlapped, serial fallback) steps, the round's unflushed share
    included."""
    snap = eng.metrics.snapshot()
    return tuple(snap[k] + eng._host_phases.get(k, 0)
                 for k in ("overlapped_steps_total",
                           "serial_fallback_steps_total"))


def step_until_in_flight(eng, n=1):
    for _ in range(n):
        eng.step()
    assert eng._inflight is not None and not eng._inflight.fetched


# -- the cases: each returns (engine, [(request, oracle tokens)]) ----------
def case_decode_only():
    eng = engine()
    ps = [prompt(i, 5 + i) for i in range(3)]
    return eng, [(eng.add_request(p, greedy(12)), oracle_greedy(p, 12))
                 for p in ps]


def case_chunked_prefill_with_arrivals():
    """Prompts of 2-4 chunks; each arrival lands while a step runs."""
    eng = engine()
    out = []
    for i, n in enumerate((27, 19, 30, 11)):
        p = prompt(10 + i, n)
        out.append((eng.add_request(p, greedy(9)), oracle_greedy(p, 9)))
        step_until_in_flight(eng, 2)
    return eng, out


def case_finish_at_max_new_tokens():
    """Budgets of 1, 2, 3 and 7: no row is planned past its last token,
    so the decode rows the steps packed are the tokens asked for."""
    eng = engine(num_slots=4)
    budgets = (1, 2, 3, 7)
    out = [(eng.add_request(prompt(20 + i, 6), greedy(m)),
            oracle_greedy(prompt(20 + i, 6), m))
           for i, m in enumerate(budgets)]
    eng.run()
    assert eng.metrics.packed_decode_tokens == sum(budgets)
    return eng, out


def case_finish_on_eos():
    """The EOS row rides one step more: its token is dropped and its
    slot freed a round later; the neighbour is untouched."""
    p = prompt(47, 7)
    raw = oracle_greedy(p, 20)
    k = next(i for i in range(3, 20) if raw[i] not in raw[:i])
    eng = engine()
    r = eng.add_request(p, greedy(20, eos_token_id=raw[k]))
    q = prompt(31, 5)
    other = eng.add_request(q, greedy(k + 8))
    eng.run()
    assert r.finish_reason == "stop"
    # k + 1 decode rows for the EOS request, k + 8 for its neighbour,
    # and the one step the EOS row rode past its end
    assert eng.metrics.packed_decode_tokens == (k + 1) + (k + 8) + 1
    return eng, [(r, raw[:k + 1]), (other, oracle_greedy(q, k + 8))]


def case_cancel_in_flight():
    eng = engine(num_slots=2)
    pa, pb = prompt(40, 6), prompt(41, 9)
    a = eng.add_request(pa, greedy(16))
    b = eng.add_request(pb, greedy(16))
    step_until_in_flight(eng, 5)
    assert a.slot in eng._inflight.plan.decode_slots
    eng.cancel(a.request_id)
    eng.run()
    assert a.finish_reason == "cancelled"
    assert 0 < len(a.output_tokens) < 16
    return eng, [(a, oracle_greedy(pa, 16)[:len(a.output_tokens)]),
                 (b, oracle_greedy(pb, 16))]


def case_prefix_hit_with_cow():
    """A mid-page hit admitted beside a decoding neighbour: the COW copy
    runs on a quiet chip."""
    eng = engine(num_slots=2)
    p = prompt(50, 20)                      # 2 full pages + 4 tokens
    first = eng.add_request(p, greedy(6))
    eng.run()
    q = prompt(51, 5)
    neighbour = eng.add_request(q, greedy(14))
    step_until_in_flight(eng, 3)
    hit = eng.add_request(p, greedy(6))
    eng.run()
    assert hit.cached_tokens == 19
    assert eng.prefix_cache.cow_copies_total == 1
    want = oracle_greedy(p, 6)
    return eng, [(first, want), (neighbour, oracle_greedy(q, 14)),
                 (hit, want)]


def case_host_tier_spills_under_page_pressure():
    """`docs_backlog`'s pattern at a toy's size: a closed loop of long
    prompts and short answers over a pool the prefix cache keeps full,
    so admissions spill parked pages to the host tier and repeated
    prompts restore them."""
    eng = engine(num_slots=2, num_pages=12, host_pages=24)
    ps = [prompt(60 + i % 5, 22 + 3 * (i % 5)) for i in range(12)]
    waiting = list(ps)
    out = []

    def submit():
        p = waiting.pop(0)
        out.append((eng.add_request(p, greedy(8)), oracle_greedy(p, 8)))
    for _ in range(4):                      # four clients
        submit()
    while eng.has_work:
        for _ in eng.step():
            if waiting:
                submit()
    assert eng.prefix_cache.spilled_pages_total > 0
    assert eng.prefix_cache.restored_pages_total > 0
    return eng, out


def case_preemption_with_swap_in():
    eng = engine(num_slots=2, num_pages=9, chunk_len=16,
                 prefix_cache=False)
    lo_p, hi_p = np.arange(1, 41) % 97, np.arange(30, 62) % 97
    lo = eng.add_request(lo_p, greedy(12, priority=5))
    step_until_in_flight(eng, 6)
    hi = eng.add_request(hi_p, greedy(12, priority=0))
    eng.run()
    assert lo.preemptions == 1
    assert eng.metrics.swapped_in_pages > 0
    return eng, [(lo, oracle_greedy(lo_p, 12)),
                 (hi, oracle_greedy(hi_p, 12))]


def case_poison_quarantine_in_flight():
    """The poisoned round raises while the step before it is in flight:
    quarantine commits that step first and bisects on a quiet engine."""
    eng = engine(num_slots=2)
    inj = FaultInjector()
    eng.step_fault_hook = lambda ids: inj.on_engine_step("r0", ids)
    pa, pb = prompt(70, 4), prompt(71, 3)
    a = eng.add_request(pa, greedy(12))
    b = eng.add_request(pb, greedy(12))
    step_until_in_flight(eng, 5)
    inj.poison(a.request_id)
    eng.run()
    assert a.finish_reason == "poisoned"
    assert eng.metrics.requests_poisoned == 1
    return eng, [(a, oracle_greedy(pa, 12)[:len(a.output_tokens)]),
                 (b, oracle_greedy(pb, 12))]


def case_abort_all_in_flight():
    """A replica's death retires every request at once: the step in
    flight is committed first, so each keeps one more oracle token."""
    eng = engine(num_slots=2)
    ps = [prompt(110, 5), prompt(111, 7)]
    reqs = [eng.add_request(p, greedy(16)) for p in ps]
    step_until_in_flight(eng, 6)
    emitted = [len(r.output_tokens) for r in reqs]
    eng.abort_all("replica_failure")
    assert [r.finish_reason for r in reqs] == ["replica_failure"] * 2
    assert [len(r.output_tokens) for r in reqs] == [n + 1 for n in emitted]
    return eng, [(r, oracle_greedy(p, 16)[:len(r.output_tokens)])
                 for r, p in zip(reqs, ps)]


def case_speculative_rows():
    eng = engine(spec="ngram")
    base = prompt(80, 4)
    ps = [np.tile(base, 3), prompt(81, 6)]
    return eng, [(eng.add_request(p, greedy(12)), oracle_greedy(p, 12))
                 for p in ps]


def case_grammar_rows():
    """A choice the unconstrained greedy trace already spells: the
    constrained stream is the oracle's."""
    p = prompt(47, 7)
    raw = oracle_greedy(p, 20)
    k = next(i for i in range(3, 20) if raw[i] not in raw[:i])
    choice = "".join(chr(t) for t in raw[:k])
    eng = engine(grammar=True)
    r = eng.add_request(p, greedy(20, eos_token_id=raw[k],
                                  grammar=GrammarSpec(kind="choice",
                                                      choices=(choice,))))
    q = prompt(91, 5)
    return eng, [(r, raw[:k + 1]),
                 (eng.add_request(q, greedy(10)), oracle_greedy(q, 10))]


CASES = {name[len("case_"):]: fn for name, fn in globals().items()
         if name.startswith("case_")}
# the rounds of these read the tokens on the host: none overlaps
SERIAL = {"speculative_rows", "grammar_rows"}


@pytest.mark.parametrize("case", sorted(CASES))
def test_overlapped_engine_matches_solo_oracle(case):
    eng, pairs = CASES[case]()
    eng.run()
    for req, want in pairs:
        assert list(req.output_tokens) == list(want), req.request_id
    overlapped, serial = steps_counted(eng)
    if case in SERIAL:
        assert overlapped == 0 and serial > 0
    else:
        assert overlapped > 0
    assert eng._inflight is None
    eng.drain()
    eng.pool.assert_quiesced()
    assert eng._unified_fn._cache_size() == 1


@pytest.mark.parametrize("spec,least,most", [(None, 0.8, 1.0),
                                             ("ngram", 0.0, 0.0)])
def test_overlap_share_of_the_steps(spec, least, most):
    """On a decode-only trace nearly every step is launched behind its
    predecessor (the first is not); with speculation on, none is."""
    eng = engine(spec=spec)
    for i in range(3):
        eng.add_request(prompt(100 + i, 5), greedy(24))
    eng.run()
    overlapped, serial = steps_counted(eng)
    share = overlapped / eng.metrics.unified_steps
    assert least <= share <= most
    assert overlapped + serial < eng.metrics.unified_steps + 1
    text = eng.metrics.snapshot()
    assert "overlapped_steps_total" in text
    assert "serial_fallback_steps_total" in text
