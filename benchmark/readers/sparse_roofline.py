"""Share (%) of its roofline that one of the sparse attention's kernels
reached while the trace was taken: the least time the chip could take
for the work, the larger of bytes over HBM bandwidth and operations over
the bf16 peak (peaks.json), over the device time of the operations whose
text holds one of `kernels`. The work is what the program's counters
counted between the trace's start and its stop (`obs["engine_traced"]`,
taken by kinds/serve_http_keye.py), priced by ref_keye_vl2.py's
`sparse_step_flops` and `sparse_step_bytes` at the configuration's
widths: `flops` and `bytes` name, for each keyword of those two
functions, the counter that fills it (the indexer: the VISIBLE pairs it
must score and a slot's context read once; the attention: the SELECTED
pairs it must weigh and the distinct keys its live queries can select
between them). It counts the work at what ANY form must do, not what
the implementation does, so a walk that reads and multiplies every
visible key and masks reads low, honestly, and no form can pass 100.
None where there is no trace, no such operation or no such counter (a
program without the counters)."""
from benchmark import ref_keye_vl2
from benchmark.stats import stat


def read(obs, ctx, kernels, flops, bytes):
    red = obs.get("trace")
    counted = {kw: stat(obs, "engine_traced", name, "value")
               for kw, name in {**flops, **bytes}.items()}
    if not red or None in counted.values() or not any(counted.values()):
        return None
    busy = sum(sec for name, sec in red["ops"].items()
               if any(k in red["text"][name] for k in kernels))
    bw, peak = ctx.peak("hbm_bytes_per_s"), ctx.peak("bf16_flops")
    if not busy or bw is None or peak is None:
        return None
    least = max(
        ref_keye_vl2.sparse_step_bytes(
            ctx.config, **{kw: counted[kw] for kw in bytes}) / bw,
        ref_keye_vl2.sparse_step_flops(
            ctx.config, **{kw: counted[kw] for kw in flops}) / peak)
    return 100.0 * least / busy
