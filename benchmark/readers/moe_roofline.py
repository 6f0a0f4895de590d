"""Share (%) of its roofline that the routed experts' product reached
while the trace was taken: the least time the chip could take for the
work, the larger of bytes over HBM bandwidth and operations over the
bf16 peak (peaks.json), over the device time of the operations whose
text holds one of `kernels`. The work is what the program's counters
`hit` and `here` counted between the trace's start and its stop
(`obs["engine_traced"]`, taken by kinds/serve_http_laguna.py), priced by
ref_laguna.py's `expert_step_bytes` and `expert_step_flops` at the
configuration's widths: it counts the work, not the implementation.
None where there is no trace, no such operation or no such counter."""
from benchmark import ref_laguna
from benchmark.stats import stat


def read(obs, ctx, kernels, hit, here):
    red = obs.get("trace")
    n_hit = stat(obs, "engine_traced", hit, "value")
    n_here = stat(obs, "engine_traced", here, "value")
    if not red or not n_hit or n_here is None:
        return None
    busy = sum(sec for name, sec in red["ops"].items()
               if any(k in red["text"][name] for k in kernels))
    bw, peak = ctx.peak("hbm_bytes_per_s"), ctx.peak("bf16_flops")
    if not busy or bw is None or peak is None:
        return None
    h, f = ctx.config["hidden_size"], ctx.config["moe_intermediate_size"]
    least = max(ref_laguna.expert_step_bytes(n_hit, n_here, h, f) / bw,
                ref_laguna.expert_step_flops(n_here, h, f) / peak)
    return 100.0 * least / busy
