"""sweep.py for any serving kind: finds an open-loop cell's knee, once,
when the cell is defined, with the kind taken from the cell's
configuration (`kinds/<kind>.py` must offer `Served` with `engine`,
`drive` and `close`, as serve_http and serve_http_laguna do):

    python benchmark/sweep_kind.py --workload <cell> --rates 0.4,0.6,0.8 --seconds 40

One process and one set-up; the cell's traffic is replayed at each rate
in turn with a pause between rates for the queue to empty. The rule is
sweep.py's: the knee is the highest rate at which the queue depth at
the window's end is no more than the slots and at least 90% of the
requests due in the window finish inside it. A request due in the
window's last seconds cannot finish inside it however idle the server,
so where a request takes many seconds give `--seconds` enough of them
that those are a few per cent of the window's requests (90 s where a
request takes 2-15 s). Prints a table and the device's memory peak;
writes nothing.
"""
import argparse
import importlib
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from run import context     # noqa: E402


def step_window(engine):
    """serve_http.EngineWindow over the one histogram the table reads:
    a long window at a high rate holds more token gaps than the
    metrics' ring of 8192 samples, which EngineWindow refuses."""
    from benchmark.kinds import serve_http

    class StepWindow(serve_http.EngineWindow):
        HISTS = ("decode_step_s",)
    return StepWindow(engine)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    args.trace = 0
    ctx = context(args)
    from benchmark.kinds import serve_http
    from benchmark.stats import percentile
    kind = importlib.import_module("benchmark.kinds." + ctx.config["kind"])
    sv = kind.Served(ctx)
    mix, slots = ctx.mix, ctx.config["engine"]["num_slots"]
    rows = []
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            win = step_window(sv.engine)
            marks = {}
            good, recs, late = sv.drive(
                ctx, dict(mix, rate_rps=rate), args.seconds,
                lambda: (marks.update(t0=time.perf_counter()), win.start()),
                lambda: (marks.update(t1=time.perf_counter()), win.stop()))
            inside = [r for r in good if r["t_done"] < marks["t1"]]
            ttft, gaps = serve_http.client_times(good)
            # a rate far above the knee may complete nothing
            ttft, gaps = ttft or [float("nan")], gaps or [float("nan")]
            row = {"rate_rps": rate, "due": len(recs), "good": len(good),
                   "done_in_window_share": len(inside) / max(1, len(recs)),
                   "queue_depth_end": win.counters["queue_depth_end"],
                   "ttft_p50_ms": 1e3 * percentile(ttft, 50),
                   "ttft_p90_ms": 1e3 * percentile(ttft, 90),
                   "itl_p95_ms": 1e3 * percentile(gaps, 95),
                   "step_p50_ms": 1e3 * percentile(
                       win.samples["decode_step_s"], 50),
                   "late_p95_ms": 1e3 * percentile(late, 95)}
            row["sustained"] = (row["queue_depth_end"] <= slots
                                and row["done_in_window_share"] >= 0.9)
            rows.append(row)
            ctx.log(" ".join(f"{k}={v:.4g}" if isinstance(v, float)
                             else f"{k}={v}" for k, v in row.items()))
    finally:
        sv.close()
    ctx.log(f"memory: peak {ctx.memory_peak()} bytes")
    ok = [r["rate_rps"] for r in rows if r["sustained"]]
    ctx.log(f"knee: {max(ok) if ok else None} requests/s; four fifths of it: "
            f"{0.8 * max(ok) if ok else None}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
