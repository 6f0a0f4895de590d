"""One run of one cell of the benchmark:

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Resolves cell -> configuration -> kind, traffic mix -> generator, and the
cell's metrics -> readers, all by file name (README.md); holds no cell's,
configuration's or metric's name itself. The last line of standard
output is the result; everything before it is a log.
"""
import time
T_START = time.perf_counter()       # as close to process start as code gets

import argparse     # noqa: E402
import glob         # noqa: E402
import importlib    # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# a rehearsal's files live apart and are found second
DIRS = (HERE, os.path.join(HERE, "rehearsal"))
CPU_REQUESTS = ("PADDLE_TPU_PALLAS_INTERPRET", "PADDLE_TPU_FORCE_CPU_DEVICES")


def find(sub, name):
    """(path, is_rehearsal) of <sub>/<name>.json."""
    for i, d in enumerate(DIRS):
        path = os.path.join(d, sub, name + ".json")
        if os.path.exists(path):
            return path, bool(i)
    raise SystemExit(f"no {sub}/{name}.json under benchmark/")


def load(sub, name):
    path, rehearsal = find(sub, name)
    with open(path) as f:
        return json.load(f), rehearsal


def metrics_of(cell_name, cell, group):
    """The metric files of `group` that name this cell (or "all"), or
    that the cell names: either side can be the file a later PR adds."""
    out = {}
    for d in DIRS:
        for path in sorted(glob.glob(os.path.join(d, "metrics", "*.json"))):
            with open(path) as f:
                m = json.load(f)
            name = os.path.basename(path)[:-len(".json")]
            cells = m.get("cells", ())
            if m["group"] == group and (cells == "all" or cell_name in cells
                                        or name in cell.get("metrics", ())):
                out[name] = m
    return out


class Ctx:
    """What a kind and a reader get from the harness."""

    def __init__(self, args, cell, config, mix, rehearsal):
        self.root, self.cell_name, self.cell = ROOT, args.workload, cell
        self.config, self.mix, self.rehearsal = config, mix, rehearsal
        self.seed, self.seconds, self.trace = args.seed, args.seconds, \
            bool(args.trace)
        self.traffic = importlib.import_module(
            "benchmark.traffic." + mix["generator"])
        self.setup_s = None
        self.compiles, self._counting = 0, False

    def log(self, msg):
        print(f"[{time.perf_counter() - T_START:8.2f}s] {msg}", flush=True)

    def check_device(self):
        """A real cell measures a TPU with the chips it asks for, and
        nothing may ask for the CPU or for interpret-mode kernels. A
        rehearsal cell runs only where the CPU was asked for."""
        asked_cpu = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
        if self.rehearsal:
            if not asked_cpu:
                raise SystemExit(f"{self.cell_name} is a rehearsal cell: it "
                                 f"runs only under JAX_PLATFORMS=cpu")
            importlib.import_module("benchmark.rehearsal.interpret").apply()
        else:
            for var in CPU_REQUESTS:
                if os.environ.get(var):
                    raise SystemExit(f"{var} is set; the benchmark runs the "
                                     f"real kernels on the real chip")
        import jax
        devs = jax.devices()
        if not self.rehearsal and devs[0].platform != "tpu":
            raise SystemExit(f"JAX found no TPU (platform "
                             f"{devs[0].platform!r}); nothing is measured")
        if len(devs) < self.cell["chips"]:
            raise SystemExit(f"{self.cell_name} needs {self.cell['chips']} "
                             f"chips, JAX found {len(devs)}")
        self.devices = devs[:self.cell["chips"]]
        self.device = {"platform": devs[0].platform,
                       "kind": devs[0].device_kind, "count": len(devs)}

    def use_compile_cache(self):
        """The program's own rule (a fixed path in the checkout, or
        JAX_COMPILATION_CACHE_DIR), and every program cached however
        quickly it compiled, so that a second run compiles nothing."""
        import jax
        from paddle_tpu.utils.compile_cache import use_compile_cache
        use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kw):
        if self._counting and event == \
                "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.log(f"compiled inside the window ({duration:.2f}s): {kw}")

    def window_opened(self):
        self.setup_s = time.perf_counter() - T_START
        self._counting = True

    def window_closed(self):
        self._counting = False

    def memory_peak(self):
        """Peak bytes on the fullest chip: the allocator's peak of live
        buffers plus its largest reservation for a running program's
        temporaries, which the first figure leaves out (in training the
        reservation is 11.4 GB, the buffers 1.9 GB). 0 where the backend
        reports nothing (the CPU of a rehearsal)."""
        return max(s.get("peak_bytes_in_use", 0)
                   + s.get("peak_bytes_reserved", 0)
                   for s in ((d.memory_stats() or {}) for d in self.devices))

    def peak(self, what):
        with open(os.path.join(HERE, "peaks.json")) as f:
            table = json.load(f)["by_device_kind"]
        kind = self.device["kind"]
        if self.rehearsal:          # a CPU has no peak here: nothing is
            return None             # reported against one
        if kind not in table or what not in table[kind]:
            raise SystemExit(f"peaks.json has no {what} for device_kind "
                             f"{kind!r}")
        return table[kind][what]


def context(args):
    """Resolves args.workload to its files and checks the device."""
    sys.path.insert(0, ROOT)
    cell, rehearsal = load("workloads", args.workload)
    config, _ = load("configs", cell["config"])
    mix, _ = load("traffic", cell["traffic"])
    mix.update(cell.get("traffic_params", {}))
    ctx = Ctx(args, cell, config, mix, rehearsal)
    ctx.check_device()
    ctx.use_compile_cache()
    return ctx


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    ctx = context(args)
    cell, config, rehearsal = ctx.cell, ctx.config, ctx.rehearsal
    ctx.log(f"cell {args.workload} seed {args.seed} seconds {args.seconds} "
            f"trace {args.trace}; device {ctx.device}")
    kind = importlib.import_module("benchmark.kinds." + config["kind"])
    res = kind.run(ctx)
    ctx.log(f"compilations inside the window: {ctx.compiles}")
    obs = dict(res["obs"], run={"setup_s": ctx.setup_s})
    metrics = {}
    group = "per_layer" if ctx.trace else "end_to_end"
    for name, m in metrics_of(args.workload, cell, group).items():
        reader = importlib.import_module("benchmark.readers." + m["reader"])
        value = reader.read(obs, ctx, **m["args"])
        if value is not None:       # nothing to read: left out of the line
            metrics[name] = {"value": value, "unit": m["unit"]}
    device = dict(ctx.device, memory_peak_bytes=res["memory_peak_bytes"])
    line = {"correct": bool(res["correct"]), "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics, "device": device}
    red = obs.get("trace")
    if ctx.trace and red:
        from benchmark import trace
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        line["breakdown"] = trace.breakdown(red)
    elif ctx.trace and not rehearsal:
        raise SystemExit("traced run: no operation ran on the device")
    elif ctx.trace:
        ctx.log("the CPU's trace has no device plane: busy and idle time are "
                "not rehearsed here (trace.py is checked on a synthetic "
                "trace in rehearsal/test_rehearsal.py)")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
