"""Share (%) of its roofline that the latent walk reached while the
trace was taken: the least time the chip could take for the work, the
larger of bytes over HBM bandwidth and operations over the bf16 peak
(peaks.json), over the device time of the operations whose text holds
one of `kernels`. The work is what the program's counters counted
between the trace's start and its stop (`obs["engine_traced"]`, taken
by kinds/serve_http_dsv2.py): `pairs`, the (query, key) pairs the
arithmetic is proportional to, and `distinct`, the keys that have to be
read at least once however a slot's queries share their reads; priced
by ref_deepseek_v2.py's `mla_step_flops` and `mla_step_bytes` at the
configuration's widths. It counts the work at what ANY form of the
attention must do, not what the implementation does, so the absorbed
walk, which pays 3.4 x the expanded form's operations a pair, reads
low on compute-bound chunks, honestly. None where there is no trace, no
such operation or no such counter (a program without the counters)."""
from benchmark import ref_deepseek_v2
from benchmark.stats import stat


def read(obs, ctx, kernels, pairs, distinct):
    red = obs.get("trace")
    n_pairs = stat(obs, "engine_traced", pairs, "value")
    n_distinct = stat(obs, "engine_traced", distinct, "value")
    if not red or not n_pairs or n_distinct is None:
        return None
    busy = sum(sec for name, sec in red["ops"].items()
               if any(k in red["text"][name] for k in kernels))
    bw, peak = ctx.peak("hbm_bytes_per_s"), ctx.peak("bf16_flops")
    if not busy or bw is None or peak is None:
        return None
    least = max(ref_deepseek_v2.mla_step_bytes(n_distinct, ctx.config) / bw,
                ref_deepseek_v2.mla_step_flops(n_pairs, ctx.config) / peak)
    return 100.0 * least / busy
