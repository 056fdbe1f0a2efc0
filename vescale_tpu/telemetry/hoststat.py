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
"""

from __future__ import annotations

import os
import time
from typing import Dict, Optional

__all__ = ["host_sched_stats", "host_sched_delta"]

_FIELDS = (
    "thread_run_ns",                # /proc/thread-self/schedstat, 1st: on a core
    "thread_runq_wait_ns",          # ... 2nd: runnable, waiting for a core
    "thread_timeslices",            # ... 3rd
    "voluntary_ctxt_switches",      # /proc/thread-self/status
    "nonvoluntary_ctxt_switches",
    "cpu_steal_s",                  # /proc/stat, "cpu" line, 8th value, in seconds
    "psi_cpu_some_us",              # /proc/pressure/cpu, "some ... total="
)


def _read(path: str) -> Optional[str]:
    try:
        with open(path) as f:
            return f.read()
    except OSError:
        return None


def host_sched_stats(proc_root: str = "/proc") -> Dict[str, Optional[float]]:
    """One read, for the *calling* thread and the machine.  ``at`` is
    ``time.perf_counter()`` at the read."""
    out: Dict[str, Optional[float]] = dict.fromkeys(_FIELDS)
    out["at"] = time.perf_counter()
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


def host_sched_delta(opened: Dict[str, Optional[float]],
                     closed: Dict[str, Optional[float]]) -> Dict[str, Optional[float]]:
    """``closed - opened`` field by field (``at`` becomes ``seconds``);
    ``None`` where either read lacks the field.  Both reads must come from
    the same thread for the ``thread_*`` and context-switch fields to mean
    anything."""
    out: Dict[str, Optional[float]] = {}
    for key in _FIELDS:
        a, b = opened.get(key), closed.get(key)
        out[key] = None if a is None or b is None else b - a
    out["seconds"] = closed["at"] - opened["at"]
    return out
