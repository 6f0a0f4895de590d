"""Share (%) of the device's busy time spent in operations whose trace
name or description contains one of `kernels` (a Pallas kernel's function
name, as chip_smoke.py finds it in the compiled HLO text)."""
from benchmark import trace


def read(obs, ctx, kernels):
    if not obs.get("trace"):
        return None
    return trace.share_of_busy(obs["trace"], kernels)
