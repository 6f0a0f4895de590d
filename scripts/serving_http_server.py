"""Launch the streaming HTTP serving front-end from the command line.

Builds N ServingEngine replicas over a GPT config (tiny on CPU,
GPT-124M-ish on the chip), fronts them with the least-loaded router,
and serves OpenAI-style completions until SIGTERM/SIGINT triggers a
graceful drain (stop admitting -> finish residents -> exit 0):

    python scripts/serving_http_server.py --port 8000 --replicas 2
    curl -s localhost:8000/v1/completions \
         -d '{"prompt": [3, 14, 15, 9], "max_tokens": 8}'
    # with --adapters K: pick a tenant fine-tune by model name
    curl -s localhost:8000/v1/completions \
         -d '{"prompt": [3, 14, 15, 9], "max_tokens": 8,
              "model": "lora-0"}'
    curl -sN localhost:8000/v1/completions \
         -d '{"prompt": [3, 14, 15, 9], "max_tokens": 8, "stream": true}'
    curl -s localhost:8000/metrics | head
    # with --debug (or PADDLE_TPU_DEBUG=on):
    curl -s localhost:8000/debug/state | python -m json.tool | head
    curl -s localhost:8000/debug/requests/cmpl-0   # one timeline
    python scripts/flight_dump.py http://localhost:8000  # ring table
    python scripts/fleet_top.py http://localhost:8000 --watch 2
        # one-row-per-replica fleet view (SLO burn state, cost
        # census, achieved utilization; GET /debug/fleet)
    kill -TERM <pid>       # graceful drain
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
os.environ.setdefault("JAX_DEFAULT_MATMUL_PRECISION", "default")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8000)
    ap.add_argument("--replicas", type=int, default=1)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=None)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--chunk", type=int, default=None)
    ap.add_argument("--max-queue", type=int, default=64,
                    help="per-replica admission queue bound "
                    "(full -> HTTP 429)")
    ap.add_argument("--timeout", type=float, default=None,
                    help="default per-request deadline in seconds")
    ap.add_argument("--watchdog-timeout", type=float, default=None,
                    help="condemn a replica whose pump heartbeat is "
                    "stale this long (hung-step detector); size it "
                    "ABOVE the worst-case step time incl. first-use "
                    "compilation (a huge packed step additionally "
                    "earns token-scaled grace). Residents of a "
                    "condemned replica migrate to survivors")
    ap.add_argument("--no-preempt", action="store_true",
                    help="disable overload preemption: a blocked "
                    "higher-priority request backpressures instead "
                    "of displacing the lowest-priority resident")
    ap.add_argument("--host-pages", type=int, default=None,
                    help="host-RAM KV tier capacity in pages "
                    "(default mirrors the device pool; 0 disables "
                    "swap — preemption then recomputes on resume)")
    ap.add_argument("--max-migrations", type=int, default=8,
                    help="per-request bound on mid-stream "
                    "migrations before the typed replica error "
                    "surfaces")
    ap.add_argument("--adapters", type=int, default=0,
                    help="register K random LoRA adapters (rank "
                    "--adapter-rank) named lora-0..lora-K-1 on every "
                    "replica — multi-tenant serving: clients pick a "
                    "tenant with the completions 'model' field "
                    "(unknown names 404)")
    ap.add_argument("--adapter-rank", type=int, default=4)
    ap.add_argument("--adapter-pages", type=int, default=8,
                    help="device adapter-pool capacity in adapters; "
                    "cold tenants load on demand, idle ones park, "
                    "pressure spills to host RAM / evicts LRU")
    ap.add_argument("--slo", default=None, metavar="SPEC",
                    help="SLO targets for the burn-rate tracker "
                    "(serving/slo.py), e.g. "
                    "'ttft_p99=0.5,itl_p99=0.1,goodput=0.99' — "
                    "'off' disables; default = the generous "
                    "defaults / PADDLE_TPU_SLO")
    ap.add_argument("--debug", action="store_true",
                    help="expose the /debug/state, "
                    "/debug/requests/<id> and /debug/flight "
                    "introspection endpoints (serving/obs.py) — off "
                    "by default, they carry prompt metadata; "
                    "equivalent to PADDLE_TPU_DEBUG=on")
    args = ap.parse_args()

    import jax
    from serving_bench import build_model   # same model zoo as the bench
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.serving.http import serve

    # sized by what the caller ASKED for, never by what JAX finds
    import bench
    from paddle_tpu.utils.compile_cache import use_compile_cache
    use_compile_cache()
    on_tpu = bench.tpu_expected()
    model, cfg = build_model(on_tpu)
    max_len = args.max_len or (1024 if on_tpu else 128)
    chunk = args.chunk or (128 if on_tpu else 32)

    engines = [ServingEngine(model, num_slots=args.slots,
                             max_len=max_len, page_size=args.page_size,
                             chunk_len=chunk, max_queue=args.max_queue,
                             preempt=not args.no_preempt,
                             host_pages=args.host_pages,
                             adapters=args.adapters > 0 or None,
                             adapter_pages=args.adapter_pages,
                             adapter_ranks=(args.adapter_rank,),
                             slo=args.slo)
               for _ in range(args.replicas)]
    if args.adapters:
        # identical registration order on every replica -> identical
        # adapter ids fleet-wide (the router's model-name registry)
        import numpy as np
        from paddle_tpu.serving import make_random_lora
        h = cfg.hidden_size
        hd = h // cfg.num_attention_heads
        rng = np.random.RandomState(0)
        weights = [make_random_lora(
            cfg.num_hidden_layers, h,
            cfg.num_attention_heads * hd,
            cfg.num_attention_heads * hd, rank=args.adapter_rank,
            rng=rng, amp=0.1) for _ in range(args.adapters)]
        for e in engines:
            for i, w in enumerate(weights):
                e.adapters.register(f"lora-{i}", w)
    # PADDLE_TPU_FAULTS (chaos spec, serving/faults.py) is parsed by
    # serve() itself — export it to rehearse kills/hangs/poisons/spikes
    server = serve(engines, args.host, args.port,
                   default_timeout_s=args.timeout,
                   watchdog_timeout_s=args.watchdog_timeout,
                   max_migrations=args.max_migrations,
                   debug_endpoints=args.debug or None)
    server.install_signal_handlers()
    print(f"serving {args.replicas} replica(s) of "
          f"{type(model).__name__} (vocab={cfg.vocab_size}) on "
          f"{server.url} — SIGTERM drains gracefully", flush=True)
    try:
        while server.router.healthy:
            time.sleep(0.25)
    except KeyboardInterrupt:
        server.drain()
    print("drained; exiting", flush=True)


if __name__ == "__main__":
    main()
