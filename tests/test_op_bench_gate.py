"""Op-bench regression gate semantics (scripts/op_bench_check.py).

Reference: tools/check_op_benchmark_result.py — the gate itself must be
tested or a silently-green gate hides regressions. Exercises the
primary wall_us gate, the advisory host_us path, --fail-on-host, and
the new/removed-op reporting.
"""
import io
import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
SPEC = importlib.util.spec_from_file_location(
    "op_bench_check",
    os.path.join(HERE, os.pardir, "scripts", "op_bench_check.py"))
obc = importlib.util.module_from_spec(SPEC)
SPEC.loader.exec_module(obc)


def _report(**ops):
    return {"platform": "tpu",
            "ops": {k: {"host_us": h, "wall_us": w}
                    for k, (h, w) in ops.items()}}


def test_gate_passes_within_threshold():
    base = _report(add=(30.0, 10.0), matmul=(40.0, 20.0))
    new = _report(add=(35.0, 12.0), matmul=(45.0, 24.0))
    out, err = io.StringIO(), io.StringIO()
    assert obc.run_gate(base, new, out=out, err=err) == 0
    assert "gate OK" in out.getvalue()


def test_gate_fails_on_wall_us_regression():
    base = _report(add=(30.0, 10.0), matmul=(40.0, 20.0))
    new = _report(add=(30.0, 14.0), matmul=(40.0, 20.0))  # 1.4x wall
    out, err = io.StringIO(), io.StringIO()
    assert obc.run_gate(base, new, out=out, err=err) == 1
    assert "add" in out.getvalue()


def test_host_us_is_advisory_by_default():
    # 4x host regression, wall flat: warns but passes (host timing noise)
    base = _report(add=(30.0, 10.0))
    new = _report(add=(120.0, 10.5))
    out, err = io.StringIO(), io.StringIO()
    assert obc.run_gate(base, new, out=out, err=err) == 0
    assert "advisory" in err.getvalue()


def test_fail_on_host_enforces_advisory():
    base = _report(add=(30.0, 10.0))
    new = _report(add=(120.0, 10.5))
    out, err = io.StringIO(), io.StringIO()
    assert obc.run_gate(base, new, fail_on_host=True,
                        out=out, err=err) == 1


def test_new_and_removed_ops_do_not_fail():
    base = _report(add=(30.0, 10.0), old_op=(10.0, 5.0))
    new = _report(add=(30.0, 10.0), new_op=(10.0, 5.0))
    out, err = io.StringIO(), io.StringIO()
    assert obc.run_gate(base, new, out=out, err=err) == 0
    assert "removed: old_op" in err.getvalue()
    assert "new op (no baseline): new_op" in err.getvalue()


def test_zero_baseline_is_infinite_regression():
    base = _report(add=(30.0, 0.0))
    new = _report(add=(30.0, 1.0))
    out, err = io.StringIO(), io.StringIO()
    assert obc.run_gate(base, new, out=out, err=err) == 1


def _op_bench_cases():
    spec = importlib.util.spec_from_file_location(
        "op_bench", os.path.join(HERE, os.pardir, "scripts",
                                 "op_bench.py"))
    ob = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ob)
    return ob._cases()


def test_paged_decode_attention_is_benched():
    """The ragged paged-attention decode op must keep a tracked perf
    number: its case stays in op_bench's table so every report (and
    therefore the wall_us gate) carries it."""
    cases = _op_bench_cases()
    assert "paged_decode_attention" in cases
    fn, args = cases["paged_decode_attention"]()
    out = fn(*args)
    assert tuple(out.shape) == (8, 1, 8, 64)


def test_ragged_q8_lane_is_benched():
    """The quantized-serving hot path — the ragged op's int8 lane over
    code + rowwise-scale pools — must keep its own tracked perf
    number next to the fp ragged entry: with PADDLE_TPU_KV_DTYPE=int8
    every serving step runs this shape, and the whole point of the
    lane (half the KV bytes per step) dies silently without a
    number."""
    import numpy as np
    cases = _op_bench_cases()
    assert "ragged_paged_attention_q8" in cases
    fn, args = cases["ragged_paged_attention_q8"]()
    # pools really are int8 codes + f32 rowwise scales
    assert args[1].numpy().dtype == np.int8
    assert args[3].numpy().dtype == np.float32
    assert args[3].numpy().shape == args[1].numpy().shape[:3]
    out = fn(*args)
    assert tuple(out.shape) == (8, 16, 8, 64)


def test_ragged_verify_shape_is_benched():
    """Speculative decoding's VERIFY pass — mixed per-row q_len with
    1 + k draft rows next to plain q_len-1 decode rows through
    `ragged_paged_attention` — must keep its own tracked perf number
    next to the uniform ragged entry: the spec subsystem's step cost
    IS this shape, and a silent regression here taxes every
    speculative token."""
    cases = _op_bench_cases()
    assert "ragged_paged_attention" in cases
    assert "ragged_paged_attention_verify" in cases
    fn, args = cases["ragged_paged_attention_verify"]()
    # the q_len operand really is the verify mix: some rows 1 + k,
    # some plain decode rows at 1
    ql = args[-1].numpy().tolist()
    assert 1 in ql and max(ql) > 1
    out = fn(*args)
    assert tuple(out.shape) == (8, 16, 8, 64)


def test_grouped_walk_is_benched():
    """The prefix-sharing-aware grouped walk (+ its q8 lane) must
    keep tracked perf numbers next to the flat ragged entries: under
    high prefix share every serving step runs this shape, and the
    once-per-group HBM claim dies silently without a number."""
    import numpy as np
    cases = _op_bench_cases()
    for name in ("ragged_paged_attention_grouped",
                 "ragged_paged_attention_grouped_q8"):
        assert name in cases, name
        fn, args = cases[name]()
        # the page tables really share a physical prefix (one group
        # of 4 rows over 4 pages — the operand contract)
        pt = args[5 if name.endswith("q8") else 3].numpy()
        assert (pt[:4, :4] == pt[0, :4]).all()
        assert len(set(pt[:, 4:].ravel().tolist())) > 8  # private tails
        gcnt = args[-1].numpy()
        assert gcnt[0] == 4 and (gcnt[1:] == 0).all()
        out = fn(*args)
        assert tuple(out.shape) == (8, 16, 8, 64)
