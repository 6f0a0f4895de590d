"""paddle.profiler parity: host spans + device trace capture.

Reference: python/paddle/profiler/profiler.py:344 (Profiler, scheduler
states at :79), RecordEvent (profiler/utils.py over C++ event_tracing.h),
ChromeTracingLogger (paddle/fluid/platform/profiler/chrometracing_logger.cc),
profiler_statistic.py summaries.

TPU mapping: host spans are recorded in-process (RecordEvent around user
code and every eager op dispatch); the device side is XLA's own profiler
(jax.profiler traces, viewable in TensorBoard/XProf) captured alongside
when a TPU/accelerator target is enabled. Chrome-trace export keeps the
reference's contract: one JSON openable in Perfetto / chrome://tracing.
"""
from __future__ import annotations

import json
import os
import threading
import time
from enum import Enum
from typing import Callable, Optional

from jax.profiler import TraceAnnotation as _trace_annotation

__all__ = ["Profiler", "RecordEvent", "ProfilerState", "ProfilerTarget",
           "make_scheduler", "export_chrome_tracing", "chrome_trace",
           "SortedKeys", "SummaryView"]


def chrome_trace(events, pid: int = None) -> dict:
    """THE chrome-tracing writer: `(name, tid, t0_ns, t1_ns[, args])`
    span tuples -> the Chrome-trace JSON dict (openable in Perfetto /
    chrome://tracing; reference: chrometracing_logger.cc). Shared by
    `Profiler.export` (host op/RecordEvent spans) and the serving
    observability layer (request-lifecycle timelines,
    serving/obs.py), so both render into the same trace format and
    one Perfetto window can show them side by side. Timestamps are
    ns; the earliest t0 becomes the trace origin."""
    base = min((e[2] for e in events), default=0)
    return {
        "traceEvents": [
            {"name": name, "ph": "X", "cat": "host",
             "ts": (t0 - base) / 1e3, "dur": (t1 - t0) / 1e3,
             "pid": os.getpid() if pid is None else pid, "tid": tid,
             **({"args": rest[0]} if rest and rest[0] else {})}
            for name, tid, t0, t1, *rest in events
        ],
        "displayTimeUnit": "ms",
    }


class ProfilerState(Enum):
    """reference: profiler.py:79."""
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1      # accepted for reference compat; maps to the accelerator
    TPU = 2
    CUSTOM_DEVICE = 3


class SortedKeys(Enum):
    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3
    GPUTotal = 4
    GPUAvg = 5
    GPUMax = 6
    GPUMin = 7


class SummaryView(Enum):
    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6
    MemoryManipulationView = 7
    UDFView = 8


# process-global: ops and RecordEvent spans on ANY thread (dataloader
# prefetch workers, etc.) record into the live profiler — events carry
# their tid, and list.append is GIL-atomic
_active: dict = {"profiler": None}


def _active_profiler():
    return _active["profiler"]


def _now_ns():
    return time.perf_counter_ns()


class RecordEvent:
    """Host span (reference: profiler/utils.py RecordEvent over
    platform/profiler/event_tracing.h). Usable as context manager or via
    begin()/end(), both on one thread.

    One span, three readers. While a `Profiler` records, the span lands
    in its Python list (`Profiler.export`). While a JAX profiler session
    runs (`jax.profiler.start_trace`, XProf against a live server, the
    benchmark's `--trace 1`), it is also a `jax.profiler.TraceAnnotation`
    of the same name: an event on its thread's line of the `/host:`
    plane of the same `.xplane.pb` that holds the device's `XLA Ops`, on
    the profiler's clock; with neither, the annotation is inert. And
    `elapsed_s` holds the span's own two clock reads after `end()`, so a
    counter fed from it agrees with the span. Keyword arguments (a page,
    a step index) become the annotation's arguments and the chrome
    event's `args`: ids belong there, never in `name`, which is a
    constant of the calling module."""

    __slots__ = ("name", "event_type", "args", "elapsed_s", "_t0",
                 "_annotation")

    def __init__(self, name: str, event_type=None, **args):
        self.name = name
        self.event_type = event_type
        self.args = args
        self.elapsed_s = 0.0
        self._t0 = None
        self._annotation = None

    def begin(self):
        self._annotation = _trace_annotation(self.name, **self.args)
        self._annotation.__enter__()
        self._t0 = _now_ns()

    def end(self):
        if self._t0 is None:
            return
        t0, t1 = self._t0, _now_ns()
        self._t0 = None
        self._annotation.__exit__(None, None, None)
        self._annotation = None
        self.elapsed_s = (t1 - t0) / 1e9
        prof = _active_profiler()
        if prof is not None and prof._recording and not prof.timer_only:
            prof._events.append(
                (self.name, threading.get_ident(), t0, t1, self.args))

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


def make_scheduler(*, closed: int, ready: int, record: int,
                   repeat: int = 0, skip_first: int = 0):
    """reference: profiler.py make_scheduler — cycle through
    CLOSED*closed -> READY*ready -> RECORD*record, repeating `repeat`
    times (0 = forever), after skipping `skip_first` steps."""
    period = closed + ready + record

    def scheduler(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat > 0 and s >= repeat * period:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


def export_chrome_tracing(dir_name: str, worker_name: str = None):
    """on_trace_ready factory (reference: profiler.py
    export_chrome_tracing)."""
    def handler(prof: "Profiler"):
        os.makedirs(dir_name, exist_ok=True)
        name = worker_name or f"host_{os.getpid()}"
        prof.export(os.path.join(
            dir_name, f"{name}_time_{time.time_ns()}"
                      f".paddle_trace.json"))
    return handler


def _default_targets():
    import jax
    targets = [ProfilerTarget.CPU]
    if any(d.platform != "cpu" for d in jax.local_devices()):
        targets.append(ProfilerTarget.TPU)
    return targets


class Profiler:
    """reference: profiler.py:344. Usage:

        with profiler.Profiler(targets=[...], scheduler=(2, 5)) as p:
            for batch in loader:
                train_step(batch)
                p.step()
        p.summary()
    """

    def __init__(self, *, targets=None, scheduler=None,
                 on_trace_ready: Optional[Callable] = None,
                 record_shapes=False, profile_memory=False,
                 timer_only=False, emit_nvtx=False, custom_device_types=None):
        self.targets = list(targets) if targets else _default_targets()
        if isinstance(scheduler, (tuple, list)) and len(scheduler) == 2:
            start, end = scheduler
            self._scheduler = make_scheduler(
                closed=max(start, 0), ready=0, record=end - start,
                repeat=1)
        elif callable(scheduler):
            self._scheduler = scheduler
        else:
            self._scheduler = None  # record everything between start/stop
        self.on_trace_ready = on_trace_ready
        self.timer_only = timer_only
        self._events: list = []      # current recording window
        self._all_events: list = []  # flushed windows (for post-hoc use)
        self._step = 0
        self._recording = False
        self._device_trace_dir = None
        self._xla_tracing = False
        self.current_state = ProfilerState.CLOSED
        self._step_times: list = []
        self._last_step_t = None

    # -- lifecycle -----------------------------------------------------------
    def start(self):
        # fresh run: a restarted profiler must not re-export the previous
        # run's spans or resume its scheduler mid-cycle
        self._events = []
        self._all_events = []
        self._step = 0
        self._step_times = []
        self._device_trace_dir = None  # stale dir from a previous run
        _active["profiler"] = self
        # the dispatch hook is installed only while a profiler is live so
        # un-profiled programs pay nothing on the op hot path
        from ..core import tensor as tensor_mod
        tensor_mod._profile_hook = _op_profile_hook
        self._last_step_t = time.perf_counter()
        self._update_state()
        return self

    def stop(self):
        try:
            if self._xla_tracing:
                self._stop_xla_trace()
        finally:
            self._recording = False
            self.current_state = ProfilerState.CLOSED
            if _active_profiler() is self:
                _active["profiler"] = None
                from ..core import tensor as tensor_mod
                tensor_mod._profile_hook = None
            self._flush_window()

    def step(self, num_samples=None):
        t = time.perf_counter()
        if self._last_step_t is not None:
            self._step_times.append((t - self._last_step_t, num_samples))
        self._last_step_t = t
        was_returning = (self.current_state
                         == ProfilerState.RECORD_AND_RETURN)
        self._step += 1
        self._update_state()
        if was_returning:
            # window boundary: hand the collected window to the handler
            # and clear the buffer (reference: one trace per window)
            self._flush_window()

    def _flush_window(self):
        if self.timer_only:
            self._events = []
            return
        if self._events:
            if self.on_trace_ready is not None:
                self.on_trace_ready(self)
            self._all_events.extend(self._events)
            self._events = []

    def step_info(self, unit=None):
        if not self._step_times:
            return ""
        dt, ns = self._step_times[-1]
        ips = f" ips: {ns / dt:.2f}" if ns else ""
        return f"batch_cost: {dt:.5f} s{ips}"

    def _update_state(self):
        if self._scheduler is None:
            new = ProfilerState.RECORD
        else:
            new = self._scheduler(self._step)
        prev_rec = self._recording
        self.current_state = new
        self._recording = new in (ProfilerState.RECORD,
                                  ProfilerState.RECORD_AND_RETURN)
        if not self.timer_only:
            want_xla = (self._recording
                        and ProfilerTarget.TPU in self.targets)
            if want_xla and not self._xla_tracing:
                self._start_xla_trace()
            elif not want_xla and self._xla_tracing:
                self._stop_xla_trace()

    def _start_xla_trace(self):
        """A device trace that cannot start (another profiler session is
        running, the directory cannot be made) raises: a host-only trace
        that looks like a whole one is how spans that never reached the
        device trace came to be thought sufficient."""
        import tempfile
        import jax
        trace_dir = tempfile.mkdtemp(prefix="paddle_xla_trace_")
        jax.profiler.start_trace(trace_dir)
        self._device_trace_dir = trace_dir
        self._xla_tracing = True

    def _stop_xla_trace(self):
        import jax
        self._xla_tracing = False
        jax.profiler.stop_trace()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- output --------------------------------------------------------------
    def export(self, path: str, format: str = "json"):
        """Chrome-trace JSON of the host spans (openable in Perfetto /
        chrome://tracing; reference: chrometracing_logger.cc)."""
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        # inside on_trace_ready: the current window; after stop(): all
        # flushed windows
        events = self._events or self._all_events
        trace = chrome_trace(events)
        if self._device_trace_dir:
            trace["otherData"] = {
                "xla_device_trace_dir": self._device_trace_dir}
        with open(path, "w") as f:
            json.dump(trace, f)
        return path

    def aggregate(self):
        """name -> dict(calls, total_ns, avg_ns, max_ns, min_ns)."""
        agg: dict = {}
        for name, _tid, t0, t1, *_ in (self._events
                                       or self._all_events):
            d = t1 - t0
            a = agg.setdefault(name, {"calls": 0, "total": 0,
                                      "max": 0, "min": None})
            a["calls"] += 1
            a["total"] += d
            a["max"] = max(a["max"], d)
            a["min"] = d if a["min"] is None else min(a["min"], d)
        for a in agg.values():
            a["avg"] = a["total"] / a["calls"]
        return agg

    def summary(self, sorted_by=SortedKeys.CPUTotal, op_detail=True,
                thread_sep=False, time_unit="ms", views=None):
        """Print the operator-view table (reference:
        profiler_statistic.py)."""
        unit = {"s": 1e9, "ms": 1e6, "us": 1e3, "ns": 1.0}[time_unit]
        agg = self.aggregate()
        sort_field = {
            SortedKeys.CPUTotal: "total", SortedKeys.CPUAvg: "avg",
            SortedKeys.CPUMax: "max", SortedKeys.CPUMin: "min",
        }.get(sorted_by, "total")
        rows = sorted(agg.items(), key=lambda kv: -kv[1][sort_field])
        lines = [f"{'Name':45s} {'Calls':>7s} {'Total(' + time_unit + ')':>12s}"
                 f" {'Avg(' + time_unit + ')':>12s} {'Max(' + time_unit + ')':>12s}"]
        lines.append("-" * 92)
        for name, a in rows:
            lines.append(
                f"{name[:45]:45s} {a['calls']:7d} {a['total'] / unit:12.4f}"
                f" {a['avg'] / unit:12.4f} {a['max'] / unit:12.4f}")
        text = "\n".join(lines)
        print(text)
        return text


def _op_profile_hook(op_name):
    """Dispatch-boundary hook: a RecordEvent span around each eager op
    when a profiler is actively recording (None otherwise — zero
    overhead)."""
    prof = _active_profiler()
    if prof is None or not prof._recording or prof.timer_only:
        return None
    return RecordEvent(f"op::{op_name}")


def load_profiler_result(filename: str):
    """Load an exported chrome-trace JSON (reference:
    profiler.load_profiler_result)."""
    with open(filename) as f:
        return json.load(f)
