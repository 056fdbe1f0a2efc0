"""The reduction from the profiler's ``.xplane.pb`` to what the metrics need:
device busy and idle time, the table of device operations, collective time,
and the longest idle gaps named by what the host was doing.  Kept with the
benchmark and checked on a recorded trace (``testdata/``), so that every PR
computes these the same way.

Layout of a TPU trace (jax 0.9.0, libtpu 0.0.34, read off this PR's traces):
one plane ``/device:TPU:<n>`` per chip, whose line ``XLA Ops`` holds one event
per executed HLO instruction (``XLA Modules`` holds one per program); host
threads are lines of the plane ``/host:CPU``, where ``TraceAnnotation`` spans
appear under their own names.  All planes share one clock, in nanoseconds.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bm."
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute")
# a device gap shorter than this is the space between two kernels, not the host's doing
MIN_GAP_NS = 20_000

Interval = Tuple[float, float]


def load(path: str):
    import jax

    return jax.profiler.ProfileData.from_file(path)


def union_length(intervals: Iterable[Interval]) -> float:
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def merged(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def op_family(name: str) -> str:
    """The instruction's name without its number.  A TPU event is named by the
    whole HLO instruction, ``%fusion.123 = bf16[32,14336]{...} fusion(...)``:
    this gives ``fusion``; ``%all-reduce-start.4 = ...`` gives
    ``all-reduce-start``."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"(\.\d+)+$", "", head) or head


def is_collective(name: str) -> bool:
    return any(c in op_family(name) for c in COLLECTIVES)


def shape_tag(name: str) -> str:
    """``bf16_32_14336`` from the instruction's (first) output shape."""
    m = re.search(r" = \(?(pred|[suf]\d+|bf16)\[([\d,]*)\]", name)
    if not m:
        return ""
    dims = m.group(2).replace(",", "_")
    return m.group(1) + ("_" + dims if dims else "")


def device_events(pd) -> Dict[str, List[Tuple[float, float, str]]]:
    """``{plane name: [(start_ns, end_ns, name)]}`` of the XLA ops of each TPU
    plane."""
    out = {}
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                out[plane.name] = [
                    (float(e.start_ns), float(e.start_ns) + float(e.duration_ns), e.name)
                    for e in line.events]
    return out


def host_spans(pd, prefix: str = SPAN_PREFIX) -> List[Tuple[float, float, str]]:
    spans = []
    for plane in pd.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    spans.append((float(e.start_ns), float(e.start_ns) + float(e.duration_ns), e.name))
    return spans


def name_gap(gap: Interval, spans: Sequence[Tuple[float, float, str]]) -> str:
    """The benchmark's annotation the host was inside at the middle of the
    gap (the innermost, if several), or ``outside bm spans``."""
    mid = (gap[0] + gap[1]) / 2.0
    inside = [(b - a, n) for a, b, n in spans if a <= mid < b]
    return min(inside)[1] if inside else "outside bm spans"


def summarize(pd) -> Optional[Dict[str, Any]]:
    """Busy seconds (union of op intervals, averaged over the chips), the
    traced window (first op start to last op end over all chips), collective
    seconds, the op table and the longest idle gaps.  None for a trace with no
    device operation."""
    per_device = device_events(pd)
    per_device = {k: v for k, v in per_device.items() if v}
    if not per_device:
        return None
    t0 = min(e[0] for evs in per_device.values() for e in evs)
    t1 = max(e[1] for evs in per_device.values() for e in evs)
    n = len(per_device)
    busy = sum(union_length((a, b) for a, b, _ in evs) for evs in per_device.values()) / n
    coll = sum(union_length((a, b) for a, b, name in evs if is_collective(name))
               for evs in per_device.values()) / n

    # op table over the chips: seconds per chip, by family and shape
    table: Dict[str, List[Any]] = {}
    for evs in per_device.values():
        for a, b, name in evs:
            key = "_".join(x for x in (op_family(name), shape_tag(name)) if x)
            row = table.setdefault(key, [0.0, set()])
            row[0] += (b - a) / n
            row[1].add(name)
    device_ops = sorted(([f"{key}__x{len(names)}", ns / 1e9] for key, (ns, names) in table.items()),
                        key=lambda r: -r[1])

    # idle gaps of the first chip, named by the host's spans
    first = per_device[sorted(per_device)[0]]
    spans = host_spans(pd)
    blocks = merged((a, b) for a, b, _ in first)
    gaps = [(a2 - b1, (b1, a2)) for (_, b1), (a2, _) in zip(blocks, blocks[1:]) if a2 - b1 >= MIN_GAP_NS]
    gaps.sort(reverse=True)
    idle_gaps = [[name_gap(g, spans), length / 1e9] for length, g in gaps[:10]]
    return {"busy_s": busy / 1e9, "window_s": (t1 - t0) / 1e9, "collective_s": coll / 1e9,
            "devices": n, "device_ops": device_ops[:20], "idle_gaps": idle_gaps}
