"""Share (%) of its roofline that one of MiMo-V2-Flash's two walks (the
window layers' `ptk:sink_walk`, the full layers' `ptk:split_walk`)
reached while the trace was taken: the least time the chip could take
for the work, the larger of bytes over HBM bandwidth and operations over
the bf16 peak (peaks.json), over the device time of the operations whose
text holds one of `kernels`. The work is what the program's counters
counted between the trace's start and its stop (`obs["engine_traced"]`,
taken by kinds/serve_http_mimo.py), priced by ref_mimo_v2.py's
`walk_step_flops` (the scored (query, key) pairs, every query head a
score over the key width and a weighted sum over the value width) and
`walk_step_bytes` (the distinct keys a row's queries see, their K and V
rows of every kv head read once a row and layer) at the configuration's
widths of the layer kind `window` names. None where there is no trace,
no such operation or no such counter (a program without the counters)."""
from benchmark import ref_mimo_v2
from benchmark.stats import stat


def read(obs, ctx, kernels, pairs, keys, window):
    red = obs.get("trace")
    counted = [stat(obs, "engine_traced", name, "value")
               for name in (pairs, keys)]
    if not red or None in counted or not all(counted):
        return None
    busy = sum(sec for name, sec in red["ops"].items()
               if any(k in red["text"][name] for k in kernels))
    bw, peak = ctx.peak("hbm_bytes_per_s"), ctx.peak("bf16_flops")
    if not busy or bw is None or peak is None:
        return None
    least = max(
        ref_mimo_v2.walk_step_bytes(ctx.config, counted[1], window) / bw,
        ref_mimo_v2.walk_step_flops(ctx.config, counted[0], window) / peak)
    return 100.0 * least / busy
