"""What every cell's runner shares: the compile counter, the tracer, metric
discovery, and the result object the command prints."""

from __future__ import annotations

import glob
import importlib.util
import os
import shutil
import time
from typing import Any, Callable, Dict, List, Optional

from . import xplane
from .record import RunRecord
from .spec import CellSpec

# seconds of the window's end that a traced run puts under the profiler: a
# few steps of every cell, small enough to come back from the chip
TRACE_SECONDS = 3.0
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Instants at which XLA compiled (or loaded from the persistent cache) a
    program in this process, from jax's own monitoring events.  The window
    must hold none."""

    def __init__(self) -> None:
        self.times: List[float] = []
        self._installed = False

    def _on_event(self, name: str, _seconds: float, **_kw) -> None:
        if name == BACKEND_COMPILE_EVENT:
            self.times.append(time.perf_counter())

    def install(self) -> "CompileCounter":
        from jax import monitoring

        if not self._installed:
            monitoring.register_event_duration_secs_listener(self._on_event)
            self._installed = True
        return self

    def close(self) -> None:
        if self._installed:
            from jax._src import monitoring as _m

            unregister = getattr(_m, "_unregister_event_duration_listener_by_callback", None)
            if unregister is not None:
                unregister(self._on_event)
            self._installed = False


class Tracer:
    """jax.profiler around the end of the window of a traced run; the
    ``.xplane.pb`` goes under the cell's scratch and is reduced at once."""

    def __init__(self, spec: CellSpec, enabled: bool, seconds: float):
        self.enabled = enabled
        self.length = min(TRACE_SECONDS, seconds / 2.0)
        self.dir = os.path.join(spec.out_dir(), "trace", spec.name)
        self.started: Optional[float] = None
        self.stopped: Optional[float] = None

    def maybe_start(self, now: float, window_end: float) -> None:
        if self.enabled and self.started is None and now >= window_end - self.length:
            import jax

            shutil.rmtree(self.dir, ignore_errors=True)
            os.makedirs(self.dir, exist_ok=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0     # the benchmark's own annotations are enough, and cost less
            jax.profiler.start_trace(self.dir, profiler_options=options)
            self.started = time.perf_counter()

    def stop(self) -> None:
        if self.started is not None and self.stopped is None:
            import jax

            self.stopped = time.perf_counter()
            jax.profiler.stop_trace()

    def maybe_stop(self, now: float, window_end: float) -> None:
        if now >= window_end:
            self.stop()

    def summary(self) -> Optional[Dict[str, Any]]:
        if self.started is None:
            return None
        self.stop()
        files = glob.glob(os.path.join(self.dir, "plugins", "profile", "*", "*.xplane.pb"))
        if not files:
            return None
        return xplane.summarize(xplane.load(max(files, key=os.path.getmtime)))


class SessionTracer:
    """``--trace 2``: once the measured window has closed and its numbers are
    taken, one start and stop of the profiler that is thrown away (the first
    start costs most, and falls into no number), then the program's own trace
    session (``vescale_tpu.ndtimeline.api.start_trace_session``: profiler,
    ``vs.*`` spans, counters) over ``TRACE_SECONDS`` of the same traffic.
    Nothing here is imported, started or allocated before ``begin``."""

    def __init__(self, spec: CellSpec):
        self.dir = os.path.join(spec.out_dir(), "trace", spec.name)
        self.started: Optional[float] = None
        self.stopped: Optional[float] = None
        self.result: Any = None
        self.cost_s: Dict[str, float] = {}     # what starting and stopping took, for the [bm] line

    def begin(self) -> None:
        import jax

        from vescale_tpu.ndtimeline import api as nd

        t0 = time.perf_counter()
        shutil.rmtree(self.dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # as the session starts it
        jax.profiler.start_trace(os.path.join(self.dir, "first"), profiler_options=options)
        jax.profiler.stop_trace()
        shutil.rmtree(self.dir, ignore_errors=True)
        t1 = time.perf_counter()
        nd.start_trace_session(self.dir)
        self.started = time.perf_counter()
        self.cost_s.update(first_start_and_stop=t1 - t0, session_start=self.started - t1)

    def due(self, now: float) -> bool:
        return self.started is not None and now >= self.started + TRACE_SECONDS

    def end(self):
        """Stop the session; the trace is read (the session does it, for its
        clock offset) and deleted at once.  Returns what the session returned."""
        from vescale_tpu.ndtimeline import api as nd

        t0 = time.perf_counter()
        self.result = nd.stop_trace_session()
        self.stopped = time.perf_counter()
        shutil.rmtree(self.dir, ignore_errors=True)
        self.cost_s["session_stop_and_load"] = self.stopped - t0
        return self.result

    def summary(self) -> Optional[Dict[str, Any]]:
        """The same reduction a ``--trace 1`` run gets, of the session's trace."""
        if self.result is None or self.result.profile is None:
            return None
        return xplane.summarize(self.result.profile)


def annotate(name: str):
    """The benchmark's own span around a call into a layer (shows in the
    profiler's host lines; free while no trace is running)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


# ------------------------------------------------------------ metric readers
def discover(directory: str) -> List[Any]:
    """Every reader module in a directory of metric readers, by listing it.
    A reader declares ``METRICS`` (name -> unit, and for a per-layer metric
    layer and moves) and ``read(run) -> {name: value}``; what it cannot read
    it leaves out."""
    modules = []
    for path in sorted(glob.glob(os.path.join(directory, "*.py"))):
        stem = os.path.basename(path)[:-3]
        if stem.startswith("_"):
            continue
        spec = importlib.util.spec_from_file_location(f"benchmark_metric_{os.path.basename(directory)}_{stem}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        modules.append(module)
    return modules


def read_metrics(directory: str, wanted: List[Dict[str, Any]], run: RunRecord) -> Dict[str, Dict[str, Any]]:
    """``{name: {"value", "unit"}}`` for the BENCHMARK.json entries ``wanted``
    that some reader in ``directory`` can read from this run."""
    values: Dict[str, float] = {}
    for module in discover(directory):
        for name, value in module.read(run).items():
            if value is not None:
                values[name] = float(value)
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]} for m in wanted if m["name"] in values}


def device_block(devices, run: RunRecord) -> Dict[str, Any]:
    d = devices[0]
    out = {"platform": d.platform, "kind": d.device_kind, "count": len(devices),
           "memory_peak_bytes": int(run.memory_peak_bytes)}
    if run.trace is not None:
        out["busy_s"] = run.trace["busy_s"]
        out["window_s"] = run.trace["window_s"]
    return out


def memory_peak_bytes(devices) -> int:
    """Peak on the fullest chip, as the runtime counts it (it does not seem to
    count a program's temporaries: PERF.md, Open questions)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


def memory_in_use_bytes(devices) -> int:
    """What the fullest chip holds now (the peak may date from set-up)."""
    return max(int((d.memory_stats() or {}).get("bytes_in_use", 0)) for d in devices)


def result_object(spec: CellSpec, run: RunRecord, devices, *, correct: bool, attempted: int, failed: int,
                  traced: int) -> Dict[str, Any]:
    """The contract's result: end-to-end metrics untraced (``traced`` 0),
    per-layer traced (1), both side by side where the run measured first and
    traced afterwards (2).  The readers are those of the cell's own checkout
    (``spec.root``)."""
    metrics: Dict[str, Dict[str, Any]] = {}
    if traced != 1:
        metrics.update(read_metrics(os.path.join(spec.root, "benchmark", "e2e_metrics"), spec.end_to_end, run))
    if traced:
        metrics.update(read_metrics(os.path.join(spec.root, "benchmark", "layer_metrics"), spec.per_layer, run))
    out = {"correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
           "metrics": metrics, "device": device_block(devices, run)}
    if traced and run.trace is not None:
        out["breakdown"] = {"device_ops": run.trace["device_ops"][:10], "idle_gaps": run.trace["idle_gaps"][:10]}
    if traced == 2:
        from benchmark.layer_metrics import _session

        # memory_peak_bytes stays what mode 0 reports (read as the window closed); the reference check and the
        # session allocate after that, and their peak goes under a key of its own
        out["device"]["memory_peak_bytes_run"] = int(max(run.memory_peak_bytes, run.memory_peak_bytes_run))
        session = _session.reduced(run)
        if session is not None:      # gaps named by the program's own span first, and the decode gap's split
            out.setdefault("breakdown", {}).update(_session.breakdown(session))
    return out


def wait_until(deadline: float, stop_wait: Callable[[float], bool]) -> bool:
    """Sleep to ``deadline`` on ``perf_counter``; True if told to stop first."""
    while True:
        left = deadline - time.perf_counter()
        if left <= 0:
            return False
        if stop_wait(left):
            return True
