"""Host-stall counters: what the kernel already counts about the thread that
drives the chip, and about the machine it runs on.

A step that takes 11 s where its neighbours take 209 ms has either waited for
the device runtime (ours to find) or not been running at all (a neighbour on
the host's cores; nothing the program can cure).  The kernel tells the two
apart for free: per thread, the time spent runnable but not running and the
count of involuntary context switches; per machine, the time stolen by the
hypervisor and the CPU pressure stall total.  :func:`host_sched_stats` is one
plain read of those files (``memory_report.device_memory_stats`` is its
model): call it at both ends of a window and subtract
(:func:`host_sched_delta`).  A field whose file or line is missing reads
``None``, never an error; nothing runs between the two reads.

Some machines count nothing: under gVisor (the sealed one-chip machine this
repo is measured on) ``/proc/thread-self/schedstat`` and ``/proc/pressure``
do not exist, ``/proc/stat`` reads all zeros and ``getrusage`` reports no
context switch.  There every field reads ``None`` (or a delta of 0 for the
files that exist and count nothing), and a caller has only its own per-step
records to tell a stalled window by.

The interpreter's own counts stand beside the kernel's because both answer
the same question, "was the loop's thread running?": a full (generation 2)
collection of CPython walks every tracked container of the process on
whichever thread allocated last and holds the interpreter's lock while it
does, so every other Python thread stands still for its length.  One
function on ``gc.callbacks`` (the witness; installed once, by the first
:func:`host_sched_stats` or by a trace session) times each collection where
it runs and adds to plain integers: the ``gc_*`` fields.  They are the
interpreter's and read a number on every machine, gVisor too.  The witness
starts no thread, takes no lock and touches none of the collector's
settings; while a trace session is armed it also hands each collection to
the session (``ndtimeline.api``), which makes the ``vs.host-gc`` span of it.
"""

from __future__ import annotations

import gc
import os
import time
from typing import Any, Dict, List, Optional, Tuple

__all__ = ["host_sched_stats", "host_sched_delta", "gc_witness_counts", "arm_gc_spans"]

_FIELDS = (
    "thread_run_ns",                # /proc/thread-self/schedstat, 1st: on a core
    "thread_runq_wait_ns",          # ... 2nd: runnable, waiting for a core
    "thread_timeslices",            # ... 3rd
    "voluntary_ctxt_switches",      # /proc/thread-self/status
    "nonvoluntary_ctxt_switches",
    "cpu_steal_s",                  # /proc/stat, "cpu" line, 8th value, in seconds
    "psi_cpu_some_us",              # /proc/pressure/cpu, "some ... total="
)
_GC_FIELDS = (
    "gc_collections",               # the witness: collections ended, every generation
    "gc_gen2_collections",          # ... the full ones
    "gc_pause_ns",                  # ... nanoseconds inside them, callback to callback
    "gc_gen2_pause_ns",
)
GC_LONGEST_KEPT = 8                 # of the pauses since the last read
GC_LONGEST_SHOWN = 3                # of a delta

# ---- the witness.  Plain module state, written by ``_on_gc`` alone: the
# collector never runs inside itself (a collection triggered while one runs is
# skipped), so a "start" is always followed by its own "stop" on the same
# thread, and nothing here needs a lock.
_gc_counts = [0, 0, 0, 0]           # in _GC_FIELDS' order
_gc_longest: List[Tuple[int, int, int]] = []   # (pause ns, perf_counter_ns at its start, generation)
_gc_t0_ns = 0
_gc_spans: Any = None               # a trace session's sink while one is armed, see arm_gc_spans
_gc_open: Any = None                # the sink that opened the collection now running


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    global _gc_t0_ns, _gc_open
    if phase == "start":
        _gc_open = _gc_spans
        if _gc_open is not None:
            _gc_open.open(info["generation"])
        _gc_t0_ns = time.perf_counter_ns()
        return
    pause = time.perf_counter_ns() - _gc_t0_ns
    gen = info["generation"]
    _gc_counts[0] += 1
    _gc_counts[2] += pause
    if gen == 2:
        _gc_counts[1] += 1
        _gc_counts[3] += pause
    longest = _gc_longest       # one list throughout: a read on another thread may swap the global meanwhile
    longest.append((pause, _gc_t0_ns, gen))
    if len(longest) > GC_LONGEST_KEPT:
        longest.remove(min(longest))
    if _gc_open is not None:
        _gc_open.close(gen, info["collected"])
        _gc_open = None


def _install_gc_witness() -> None:
    """Put the witness on ``gc.callbacks``, once.  Nothing of the collector is
    set: its thresholds, ``gc.freeze`` and ``gc.disable`` are left alone."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def gc_witness_counts() -> Tuple[int, int, int, int]:
    """``_GC_FIELDS``' values since the installation."""
    return tuple(_gc_counts)


def arm_gc_spans(sink: Any) -> None:
    """While ``sink`` is not None each collection is handed to it:
    ``sink.open(generation)`` before the pause is timed and
    ``sink.close(generation, collected)`` after, on the collecting thread.
    A trace session arms and disarms it; nobody else.  Arming installs the
    witness where no read has."""
    global _gc_spans
    _install_gc_witness()
    _gc_spans = sink


def _read(path: str) -> Optional[str]:
    try:
        with open(path) as f:
            return f.read()
    except OSError:
        return None


def host_sched_stats(proc_root: str = "/proc") -> Dict[str, Any]:
    """One read, for the *calling* thread, the machine and the interpreter.
    ``at`` is ``time.perf_counter()`` at the read.  The first read installs
    the collector's witness; ``gc_longest_pauses`` is the longest few pauses
    since the read before this one (the read takes the list and leaves an
    empty one, so a window's pauses are never crowded out by set-up's)."""
    global _gc_longest
    _install_gc_witness()
    out: Dict[str, Any] = dict.fromkeys(_FIELDS)
    out["at"] = time.perf_counter()
    out.update(zip(_GC_FIELDS, _gc_counts))
    out["gc_longest_pauses"], _gc_longest = _gc_longest, []
    text = _read(os.path.join(proc_root, "thread-self", "schedstat"))
    parts = text.split() if text else []
    if len(parts) >= 3 and all(p.isdigit() for p in parts[:3]):
        out["thread_run_ns"], out["thread_runq_wait_ns"], out["thread_timeslices"] = (float(p) for p in parts[:3])
    text = _read(os.path.join(proc_root, "thread-self", "status"))
    for line in (text or "").splitlines():
        key, _, value = line.partition(":")
        if key in ("voluntary_ctxt_switches", "nonvoluntary_ctxt_switches") and value.strip().isdigit():
            out[key] = float(value)
    text = _read(os.path.join(proc_root, "stat"))
    for line in (text or "").splitlines():
        parts = line.split()
        if parts and parts[0] == "cpu" and len(parts) > 8 and parts[8].isdigit():
            out["cpu_steal_s"] = float(parts[8]) / os.sysconf("SC_CLK_TCK")
            break
    text = _read(os.path.join(proc_root, "pressure", "cpu"))
    for line in (text or "").splitlines():
        if line.startswith("some"):
            total = [p[6:] for p in line.split() if p.startswith("total=")]
            if total and total[0].isdigit():
                out["psi_cpu_some_us"] = float(total[0])
    return out


def host_sched_delta(opened: Dict[str, Any], closed: Dict[str, Any]) -> Dict[str, Any]:
    """``closed - opened`` field by field (``at`` becomes ``seconds``);
    ``None`` where either read lacks the field.  Both reads must come from
    the same thread for the ``thread_*`` and context-switch fields to mean
    anything.  ``gc_longest_pauses_ms_at_s`` is the collector's longest pauses
    that began between the two reads, ``[ms, seconds after the first read,
    generation]``, longest first."""
    out: Dict[str, Any] = {}
    for key in _FIELDS + _GC_FIELDS:
        a, b = opened.get(key), closed.get(key)
        out[key] = None if a is None or b is None else b - a
    began = [p for p in closed.get("gc_longest_pauses") or () if p[1] >= opened["at"] * 1e9]
    out["gc_longest_pauses_ms_at_s"] = [[round(ns / 1e6, 3), round(t0 / 1e9 - opened["at"], 3), gen]
                                        for ns, t0, gen in sorted(began, reverse=True)[:GC_LONGEST_SHOWN]]
    out["seconds"] = closed["at"] - opened["at"]
    return out
