"""Model FLOP/s utilization (%): tokens of one step over the median step
time x the operations the forward and backward passes require per token
(ref.train_flops_per_token) over the chips' bf16 peak (peaks.json, by
device_kind; an unknown kind is an error). From the median step and not
from the window's rate: it is read in the traced run, whose window also
holds the seconds the profiler takes to start and to write its trace."""
from benchmark.stats import stat


def read(obs, ctx):
    t, peak = obs.get("train"), ctx.peak("bf16_flops")
    if not t or not peak:
        return None
    rate = t["tokens_per_step"] / stat(obs, "train", "step_s", "median")
    return 100.0 * rate * t["flops_per_token"] / (peak * ctx.cell["chips"])
