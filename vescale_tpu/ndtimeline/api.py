"""ndtimeline public API (reference legacy/vescale/ndtimeline/api.py:72
init_ndtimers, :318 flush, :293 wait, :309 inc_step)."""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import glob
import os
import time
import weakref
from typing import Any, Dict, List, Optional

from .predefined import HOST_GC, SESSION_MARK
from .timer import NDTimerManager, Span
from .world_info import WorldInfo

__all__ = [
    "init_ndtimers",
    "deinit_ndtimers",
    "flush",
    "wait",
    "inc_step",
    "ndtimeit",
    "ndtimer",
    "get_manager",
    "is_active",
    "start_trace_session",
    "stop_trace_session",
    "session_active",
    "TraceSession",
    "register_counter_source",
    "read_counters",
]

_MANAGER: Optional[NDTimerManager] = None
_ACTIVE = False  # set ONLY by init_ndtimers: the runtime auto-
# instrumentation gate.  A stray flush()/inc_step() on an un-profiled run
# auto-creates a manager (API compat) but must NOT flip instrumentation on.


def get_manager() -> NDTimerManager:
    global _MANAGER
    if _MANAGER is None:
        _MANAGER = NDTimerManager()
    return _MANAGER


def init_ndtimers(rank: int = 0, mesh=None, handlers=(), max_spans: int = 100_000) -> NDTimerManager:
    """(api.py:72) — create the global manager, register handlers."""
    global _MANAGER, _ACTIVE
    _ACTIVE = True
    _MANAGER = NDTimerManager(rank=rank, max_spans=max_spans)
    if mesh is not None:
        _MANAGER.world = WorldInfo.from_mesh(mesh, rank)
    for h in handlers:
        _MANAGER.register_handler(h)
    return _MANAGER


def deinit_ndtimers() -> None:
    """Deactivate the profiler and drop the global manager — the inverse
    of :func:`init_ndtimers`, for an A/B of a traced leg against the
    dormant no-op state, and for test teardown.
    Buffered spans that were never flushed are discarded."""
    global _MANAGER, _ACTIVE
    _ACTIVE = False
    _MANAGER = None


def flush(step_range=None, next_iteration: bool = False):
    """(api.py:318) Drain buffered spans to the registered handlers.

    ``step_range``: a ``range`` or ``(lo, hi)`` pair — only spans with
    ``lo <= span.step < hi`` are flushed (handlers see them, they are
    returned); spans OUTSIDE the window stay buffered for a later flush.
    ``next_iteration=True`` advances the global step counter after the
    flush (the reference's end-of-iteration flush shape)."""
    if step_range is not None:
        if isinstance(step_range, range):
            if step_range.step != 1:
                raise ValueError(
                    f"flush: strided step_range unsupported ({step_range})"
                )
            step_range = (step_range.start, step_range.stop)
        lo, hi = step_range
        if hi < lo:
            raise ValueError(f"flush: empty/inverted step_range ({lo}, {hi})")
    mgr = get_manager()
    spans = mgr.flush(step_range=step_range)
    if next_iteration:
        mgr.inc_step()
    return spans


def wait() -> None:
    """(api.py:293)"""
    get_manager().wait()


def inc_step(n: int = 1) -> None:
    """(api.py:309)"""
    get_manager().inc_step(n)


def is_active() -> bool:
    """True only after an EXPLICIT ``init_ndtimers`` — the gate the
    runtime's auto-instrumentation checks so un-profiled production runs
    pay nothing (a stray ``flush()``/``inc_step()`` must not activate it)."""
    return _ACTIVE and _MANAGER is not None


_DORMANT = contextlib.nullcontext()   # stateless, so one serves every dormant site


def ndtimeit(metric: str, tags=None, **ids):
    """Context manager: with ndtimeit("forward-compute"): ...

    A no-op (``nullcontext``) until the profiler is explicitly
    initialized: the runtime wiring (pipe engine, train step, checkpoint)
    calls this on every operation, and dormant instrumentation must not
    build TraceAnnotations, take locks, or grow a ring buffer nobody
    flushes.

    A span's identifiers are given as keyword arguments
    (``ndtimeit(SERVE_DECODE_LAUNCH, launch=n)``): a site on a hot path
    builds no dictionary, formats no string and reads no clock of its own
    before the one test here.  Armed, they are the span's tags (with
    ``tags``, where a caller has a dictionary already), in the ring and on
    the ``TraceAnnotation`` alike."""
    if not is_active():
        return _DORMANT
    if ids:
        tags = {**tags, **ids} if tags else ids
    return _MANAGER.timeit(metric, tags)


def ndtimer(metric: str):
    """Decorator form.  Resolves the manager at CALL time through
    ``ndtimeit``: dormant runs pay nothing, and an ``init_ndtimers`` after
    decoration is picked up (a decoration-time manager binding would both
    defeat the _ACTIVE gate and orphan the spans when the global manager is
    replaced)."""

    def deco(fn):
        @functools.wraps(fn)  # keep __name__/__doc__ for introspection
        # (jit cache keys in debug dumps, functools caches, help())
        def wrapped(*args, **kwargs):
            with ndtimeit(metric):
                return fn(*args, **kwargs)

        return wrapped

    return deco


# ------------------------------------------------------------ trace session
# One start/stop pair that a running process calls to trace itself for a
# while: the XLA profiler, the ndtimeit spans (whose TraceAnnotations land
# in the profiler's own trace under their ``vs.`` names) and the counters the
# working objects keep.  It arms the spans only: ``telemetry.init()`` keeps
# its own switch (registry, exporters, the ``record_step`` feed that reads
# the loss on the host), so a session never changes what a step does.

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# objects that count their own work in plain integers (``ServeEngine``):
# each has ``trace_counters() -> {name: int}``; held weakly, read at a
# session's two ends, never on a hot path
_COUNTER_SOURCES: "weakref.WeakSet[Any]" = weakref.WeakSet()


def register_counter_source(source: Any) -> None:
    _COUNTER_SOURCES.add(source)


def read_counters() -> Dict[str, int]:
    """The live counter sources' values, summed by name."""
    total: Dict[str, int] = {}
    for values in _counters_by_source().values():
        for name, value in values.items():
            total[name] = total.get(name, 0) + value
    return total


def _counters_by_source() -> Dict[Any, Dict[str, int]]:
    # keyed by weak reference: a dead source's key equals no later one's, even at the same address
    return {weakref.ref(source): {k: int(v) for k, v in source.trace_counters().items()}
            for source in list(_COUNTER_SOURCES)}


@dataclasses.dataclass
class TraceSession:
    """What :func:`stop_trace_session` returns."""

    log_dir: str
    xplane_path: Optional[str]        # None: no profiler, or it wrote no trace
    spans: List[Span]                 # the ring, drained (epoch-clock starts)
    counters: Dict[str, int]          # what was counted while the session ran
    started: float                    # epoch seconds
    stopped: float
    mark_epoch_s: float               # the ``vs.session-mark`` annotation's instant on the spans' clock ...
    mark_trace_ns: Optional[float]    # ... and on the trace's; None without a trace
    profile: Any = None               # the loaded ``jax.profiler.ProfileData`` (None without a trace)

    @property
    def clock_offset_ns(self) -> Optional[float]:
        """Trace nanoseconds minus epoch nanoseconds (to the float's 256 ns)."""
        return None if self.mark_trace_ns is None else self.mark_trace_ns - self.mark_epoch_s * 1e9

    def to_trace_ns(self, epoch_s: float) -> Optional[float]:
        """An instant of the spans' clock on the device trace's clock: lays
        spans recorded after the fact (``serve-queue-wait``,
        ``serve-decode-token``) over the profiler's events."""
        if self.mark_trace_ns is None:
            return None
        return (epoch_s - self.mark_epoch_s) * 1e9 + self.mark_trace_ns


@dataclasses.dataclass
class _LiveSession:
    log_dir: str
    profiler: bool
    own_timers: bool
    started: float
    counters_at_start: Dict[Any, Dict[str, int]]    # by source: one that dies while the session runs drops out
    compiles: List[float]
    on_compile: Any
    mark_epoch_s: float
    gc_spans: "_GcSpans"
    gc_at_start: tuple                              # ``hoststat.gc_witness_counts()`` as the session armed


GC_SPAN_MIN_S = 1e-3    # a generation-0 collection is a span only if it took longer


class _GcSpans:
    """What the collector's witness (``telemetry/hoststat.py``) hands its
    collections to while a session is armed.  A collection of generation 1
    or 2 is a ``vs.host-gc`` annotation on the trace's clock, opened before the
    pause and closed after it on the collecting thread; with any generation-0
    one over a millisecond (tens of microseconds is its rule, hundreds of
    times a second: an annotation each would be the armed cost) it is kept
    here and poured into the ring as the session stops.  Not before: the
    collector can run inside the ring's own lock, so nothing here takes it."""

    def __init__(self, annotation):
        self._annotation = annotation       # ``jax.profiler.TraceAnnotation``, bound once: no import at a collection
        self._ann = None
        self._t0 = 0.0
        self.kept: List[tuple] = []         # (epoch start, seconds, generation, collected)

    def open(self, gen: int) -> None:
        if gen:
            self._ann = self._annotation(HOST_GC, gen=gen)
            self._ann.__enter__()
        self._t0 = time.time()

    def close(self, gen: int, collected: int) -> None:
        duration = time.time() - self._t0
        ann, self._ann = self._ann, None
        if ann is not None:
            ann.set_metadata(collected=collected)
            ann.__exit__(None, None, None)
        if gen or duration > GC_SPAN_MIN_S:
            self.kept.append((self._t0, duration, gen, collected))


_SESSION: Optional[_LiveSession] = None


def session_active() -> bool:
    return _SESSION is not None


def _mark() -> float:
    """Emit the marker annotation; its instant on the spans' clock."""
    import jax

    ann = jax.profiler.TraceAnnotation(SESSION_MARK)
    t0 = time.time()
    ann.__enter__()
    t1 = time.time()
    ann.__exit__(None, None, None)
    return (t0 + t1) / 2.0


def _mark_trace_ns(profile) -> Optional[float]:
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == SESSION_MARK:
                    return float(e.start_ns)
    return None


def start_trace_session(log_dir: str, *, profiler: bool = True, rank: int = 0) -> None:
    """Start tracing this process: the XLA profiler writing under
    ``log_dir`` (the host's Python tracer off: the program's own
    annotations are enough, and cost less), the ndtimeit spans, and the
    counters.  One session at a time; :func:`stop_trace_session` ends it.
    ``profiler=False`` arms spans and counters alone (no device trace, no
    clock offset)."""
    global _SESSION
    if _SESSION is not None:
        raise RuntimeError("a trace session is already running")
    import jax
    from jax import monitoring

    if profiler:
        os.makedirs(log_dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=options)
    compiles: List[float] = []

    def on_compile(name: str, _seconds: float, **_kw) -> None:
        if name == BACKEND_COMPILE_EVENT:
            compiles.append(time.time())

    monitoring.register_event_duration_secs_listener(on_compile)
    own_timers = not is_active()   # an operator's own init_ndtimers, and its handlers, stay as they are
    if own_timers:
        init_ndtimers(rank=rank)
    from ..telemetry import hoststat

    gc_spans = _GcSpans(jax.profiler.TraceAnnotation)
    hoststat.arm_gc_spans(gc_spans)
    _SESSION = _LiveSession(log_dir=log_dir, profiler=profiler, own_timers=own_timers, started=time.time(),
                            counters_at_start=_counters_by_source(), compiles=compiles, on_compile=on_compile,
                            mark_epoch_s=_mark(), gc_spans=gc_spans, gc_at_start=hoststat.gc_witness_counts())


def stop_trace_session() -> TraceSession:
    """Stop the session.  The spans go dormant first and the ring is
    drained into the result; then the profiler stops and writes its
    ``.xplane.pb``, which is read once to find the marker, and with it the
    offset between the two clocks."""
    global _SESSION
    live = _SESSION
    if live is None:
        raise RuntimeError("no trace session is running")
    import jax
    from jax._src import monitoring as _monitoring

    from ..telemetry import hoststat

    _SESSION = None
    hoststat.arm_gc_spans(None)
    stopped = time.time()
    counters: Dict[str, int] = {}
    for source, values in _counters_by_source().items():     # a source born in the session counts from zero
        before = live.counters_at_start.get(source, {})
        for name, value in values.items():
            counters[name] = counters.get(name, 0) + value - before.get(name, 0)
    counters["backend_compiles"] = len(live.compiles)
    collections, full, pause_ns, _ = (b - a for a, b in zip(live.gc_at_start, hoststat.gc_witness_counts()))
    counters.update(gc_pauses=collections, gc_gen2_pauses=full, gc_pause_us=pause_ns // 1000)
    for start, duration, gen, collected in live.gc_spans.kept:
        get_manager().record(HOST_GC, start, duration, {"gen": gen, "collected": collected})
    if live.own_timers:
        spans = get_manager().flush()
        deinit_ndtimers()
    else:
        spans = [s for s in get_manager().tail(1 << 30) if s.start >= live.started]
    _monitoring.unregister_event_duration_listener(live.on_compile)
    path = profile = mark_ns = None
    if live.profiler:
        jax.profiler.stop_trace()
        files = glob.glob(os.path.join(live.log_dir, "plugins", "profile", "*", "*.xplane.pb"))
        if files:
            path = max(files, key=os.path.getmtime)
            profile = jax.profiler.ProfileData.from_file(path)
            mark_ns = _mark_trace_ns(profile)
    return TraceSession(log_dir=live.log_dir, xplane_path=path, spans=spans, counters=counters,
                        started=live.started, stopped=stopped, mark_epoch_s=live.mark_epoch_s,
                        mark_trace_ns=mark_ns, profile=profile)
