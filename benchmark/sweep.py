"""Finds an open-loop cell's knee, once, when the cell is defined:

    python benchmark/sweep.py --workload <cell> --rates 1.0,1.4,1.8 --seconds 30

One process and one set-up; the cell's traffic is replayed at each rate
in turn (same sizes in the same order, only faster) with a pause between
rates for the queue to empty. The knee is the highest rate at which the
queue depth at the window's end is no more than the slots and at least
90% of the requests due in the window finish inside it. The cell's file
then fixes `rate_rps` at four fifths of it; no run searches for a rate.
Prints a table; writes nothing.
"""
import argparse
import sys
import time

from run import context


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    args.trace = 0
    ctx = context(args)
    from benchmark.kinds import serve_http
    from benchmark.stats import percentile
    sv = serve_http.Served(ctx)
    mix, slots = ctx.mix, ctx.config["engine"]["num_slots"]
    rows = []
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            win = serve_http.EngineWindow(sv.engine)
            marks = {}
            good, recs, late = sv.drive(
                ctx, dict(mix, rate_rps=rate), args.seconds,
                lambda: (marks.update(t0=time.perf_counter()), win.start()),
                lambda: (marks.update(t1=time.perf_counter()), win.stop()))
            inside = [r for r in good if r["t_done"] < marks["t1"]]
            ttft, gaps = serve_http.client_times(good)
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
    ok = [r["rate_rps"] for r in rows if r["sustained"]]
    ctx.log(f"knee: {max(ok) if ok else None} requests/s; four fifths of it: "
            f"{0.8 * max(ok) if ok else None}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
