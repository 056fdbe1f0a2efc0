"""The launches of a ``--trace 2`` run's traced seconds, each joined to the
device programs it started (not a reader: the leading underscore keeps it out
of discovery; ``session_programs.py`` reads from it).

Since the decode pipeline (PR 35) a ``vs.serve-decode`` span no longer
brackets the program it launched, so a program's own time cannot be read off a
host span.  The engines (PR 38) number their launches: the ENQUEUE alone is a
span, ``vs.serve-decode.launch`` / ``vs.serve-prefill.launch``, whose
``TraceAnnotation`` carries ``launch=<n>`` (a prefill's also ``rung`` and
``slot``) as the event's stats, and the programs' names tell their kind on the
device's ``XLA Modules`` line: every program of a decode step begins
``jit_decode`` (``jit_decode_merge``, then ``jit_decode`` itself), every program
of a prefill ``jit_prefill`` (``jit_prefill_embed`` / ``_stage`` / ``_head`` /
``_commit`` of ``ServeEngine``; the one ``jit_prefill`` of ``HybridServeEngine``).

**The join.**  The v5e's trace (jax 0.9.0, libtpu 0.0.34) puts ``run_id`` on
every module event and on the runtime's ``DoEnqueueProgram`` /
``CompleteCallbacks`` host events, but those run on the runtime's worker
threads after the launching call has returned, and the launching thread's own
events (``PjitFunction``, ``PJRT_LoadedExecutable_Execute``) carry no
identifier (PERF.md §6, PR 38, says what is there).  So the join is by kind
and order, which the device keeps (``run_id`` rises with it): one stream
runs the programs in the order they were enqueued.  A launch takes the first
module events of its kind, not yet taken, that start at or after its
``.launch`` span's start: of a decode launch every ``jit_decode*`` event up to
and with the decode program itself; of a prefill launch the run of
``jit_prefill*`` events until a name comes round again (the next prefill's).
A step in flight when the session started was launched under no span and
began before the first span did, so nothing takes it; a launch whose program
never shows before the session stops is dropped, and counted:
``launches_joined`` of ``launches_seen`` goes on a ``[bm]`` line, and under
``MIN_JOINED_SHARE`` the readers leave their metrics out.

A trace without ``.launch`` spans (a program before PR 38, a train cell, a CPU
run) gives ``None``: the readers then report nothing, and do not guess from
the old names.
"""

from __future__ import annotations

import bisect
import dataclasses
import json
from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmark import xplane
from benchmark.layer_metrics import _session

LAUNCH_SPANS = {"vs.serve-decode.launch": "decode", "vs.serve-prefill.launch": "prefill"}
MODULE_PREFIX = {"decode": "jit_decode", "prefill": "jit_prefill"}
DECODE_PROGRAM = "jit_decode"       # of a decode launch's module events, the step itself
MIN_JOINED_SHARE = 0.9

Event = Tuple[float, float, str]    # start_ns, end_ns, name: the trace's clock


@dataclasses.dataclass
class Launch:
    kind: str                       # "decode" | "prefill"
    number: Optional[int]           # the engine's count of launches (``launch=<n>``), one sequence for both kinds
    rung: Optional[int]             # a prefill's padded length
    slot: Optional[int]
    span: Tuple[float, float]       # its ``.launch`` span: the enqueue alone
    modules: List[Event] = dataclasses.field(default_factory=list)   # the ``XLA Modules`` events it started, in order
    ops: List[Event] = dataclasses.field(default_factory=list)       # the ``XLA Ops`` events inside those, an op that
                                                                     # lies inside another (a ``cond``'s branch) left out
    ops_inner: List[Event] = dataclasses.field(default_factory=list)  # ... and those left out

    @property
    def joined(self) -> bool:
        if self.kind == "decode":
            return any(module_name(n) == DECODE_PROGRAM for _, _, n in self.modules)
        return bool(self.modules)

    @property
    def program_ns(self) -> Optional[float]:
        """Device time of the launch's own programs: the decode program's
        event, or the sum of a prefill's."""
        if not self.joined:
            return None
        if self.kind == "decode":
            return sum(b - a for a, b, n in self.modules if module_name(n) == DECODE_PROGRAM)
        return sum(b - a for a, b, _ in self.modules)

    @property
    def start_wait_ns(self) -> Optional[float]:
        """From the enqueue's start to its first program's start: what the
        launch waited behind the work in flight (and the enqueue itself)."""
        return self.modules[0][0] - self.span[0] if self.joined else None


def module_name(event_name: str) -> str:
    """``jit_prefill_stage`` of ``jit_prefill_stage(14440638914244791340)``."""
    return event_name.split("(", 1)[0]


def kind_of(event_name: str) -> Optional[str]:
    name = module_name(event_name)
    for kind, prefix in MODULE_PREFIX.items():
        if name == prefix or name.startswith(prefix + "_"):
            return kind
    return None


def launch_spans(pd) -> List[Launch]:
    """The ``.launch`` annotations of the host's lines, with their stats."""
    out = []
    for plane in pd.planes:
        if plane.name != xplane.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                kind = LAUNCH_SPANS.get(e.name)
                if kind is None:
                    continue
                stats = {k: v for k, v in e.stats}
                tag = lambda key: int(stats[key]) if isinstance(stats.get(key), (int, float)) else None
                start = float(e.start_ns)
                out.append(Launch(kind, tag("launch"), tag("rung"), tag("slot"), (start, start + float(e.duration_ns))))
    return sorted(out, key=lambda launch: launch.span[0])


def join(launches: Sequence[Launch], modules: Sequence[Event]) -> None:
    """Give each launch its module events, by kind and order (the module's
    docstring has the rule)."""
    for kind in MODULE_PREFIX:
        events = [m for m in sorted(modules) if kind_of(m[2]) == kind]
        own = [launch for launch in launches if launch.kind == kind]
        i = 0
        for launch in own:
            while i < len(events) and events[i][0] < launch.span[0]:
                i += 1          # began before this enqueue did: a launch the session did not see
            names = set()
            while i < len(events) and module_name(events[i][2]) not in names:   # a name come round again: the next launch's
                launch.modules.append(events[i])
                names.add(module_name(events[i][2]))
                i += 1
                if kind == "decode" and DECODE_PROGRAM in names:
                    break


def attach_ops(launches: Sequence[Launch], ops: Sequence[Event]) -> None:
    """Each launch's device operations: those that start inside one of its
    module events; one that lies inside another (``cond`` and its branch's
    operations) goes to ``ops_inner``, so that ``ops`` sums to time once."""
    ops = sorted(ops, key=lambda e: (e[0], -e[1]))
    starts = [a for a, _, _ in ops]
    for launch in launches:
        for a, b, _ in launch.modules:
            outer_end = float("-inf")
            for op in ops[bisect.bisect_left(starts, a):bisect.bisect_left(starts, b)]:
                if op[1] <= outer_end:
                    launch.ops_inner.append(op)
                else:
                    launch.ops.append(op)
                    outer_end = op[1]


def reduce(pd) -> Optional[Dict[str, Any]]:
    """``{"launches", "seen", "joined"}`` of a loaded trace; None where it
    holds no ``.launch`` span or no device operation."""
    per_device = {k: v for k, v in xplane.device_events(pd).items() if v}
    launches = launch_spans(pd)
    if not per_device or not launches:
        return None
    join(launches, _session._first_device_modules(pd))
    attach_ops(launches, per_device[sorted(per_device)[0]])
    return {"launches": launches, "seen": len(launches), "joined": sum(launch.joined for launch in launches)}


def reduced(run) -> Optional[Dict[str, Any]]:
    """The launches of ``run``'s session (cached on the record, and said once
    on a ``[bm]`` line); None without a session, a trace or a ``.launch`` span."""
    if hasattr(run, "_programs_reduced"):
        return run._programs_reduced
    session = getattr(run, "session", None)
    if session is None or getattr(session, "profile", None) is None:
        return None
    out = run._programs_reduced = reduce(session.profile)
    if out is not None:
        by_kind = {kind: [sum(launch.kind == kind for launch in out["launches"]),
                          sum(launch.kind == kind and launch.joined for launch in out["launches"])]
                   for kind in MODULE_PREFIX}
        print("[bm] " + json.dumps({"launches_seen": out["seen"], "launches_joined": out["joined"],
                                    "seen_and_joined_by_kind": by_kind}), flush=True)
    return out


def trusted(programs: Optional[Dict[str, Any]]) -> bool:
    return programs is not None and programs["joined"] >= MIN_JOINED_SHARE * programs["seen"]


def of_kind(programs: Dict[str, Any], kind: str) -> List[Launch]:
    return [launch for launch in programs["launches"] if launch.kind == kind and launch.joined]
