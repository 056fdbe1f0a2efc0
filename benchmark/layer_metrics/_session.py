"""The reduction of a ``--trace 2`` run's trace session (not a reader: the
leading underscore keeps it out of discovery).  A session
(``vescale_tpu.ndtimeline.api``) leaves the loaded ``.xplane.pb``, the ring's
spans, the counters and the offset between the spans' clock and the trace's;
this module lays them over one another, once a run (``reduced`` caches on the
record), and the ``session_*`` readers pick their metrics from it.

What is read where (one clock, the trace's nanoseconds):

- device: the first chip's ``XLA Ops`` line (busy blocks, idle gaps) and its
  ``XLA Modules`` line (one event per executed program);
- the program's live spans: host events named ``vs.*`` (``ndtimeit`` writes
  them as ``TraceAnnotation``s), and the benchmark's own, ``bm.*``;
- spans recorded after the fact (``serve-queue-wait``): the ring's, whose
  epoch instants ``session.to_trace_ns`` maps onto the trace's clock.

A run without a session, or a session whose trace holds no device operation
(a CPU run), gives ``None``: every reader then leaves its metrics out.
"""

from __future__ import annotations

import bisect
from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmark import stats, xplane

MODULES_LINE = "XLA Modules"
PROGRAM_PREFIX, BENCHMARK_PREFIX = "vs.", "bm."
SESSION_MARK = "vs.session-mark"
UNATTRIBUTED = "unattributed"

Span = Tuple[float, float, str]


def _first_device_modules(pd) -> List[Span]:
    planes = sorted((p for p in pd.planes if xplane.DEVICE_PLANE.match(p.name)), key=lambda p: p.name)
    for plane in planes[:1]:
        for line in plane.lines:
            if line.name == MODULES_LINE:
                return sorted((float(e.start_ns), float(e.start_ns) + float(e.duration_ns), e.name)
                              for e in line.events)
    return []


def _innermost(instant: float, spans: Sequence[Span]) -> Optional[Span]:
    inside = [(b - a, a, b, n) for a, b, n in spans if a <= instant < b]
    if not inside:
        return None
    _, a, b, n = min(inside)
    return (a, b, n)


def name_gap(gap: Tuple[float, float], program: Sequence[Span], benchmark: Sequence[Span]) -> str:
    """The program's span the host was inside at the gap's middle (the
    innermost; ``vs.serve-decode (enqueue)`` for the call itself outside its
    ``.fetch``), else the benchmark's, else ``unattributed``."""
    mid = (gap[0] + gap[1]) / 2.0
    own = _innermost(mid, program)
    if own is not None:
        return own[2] + " (enqueue)" if own[2] in ("vs.serve-decode", "vs.serve-prefill") else own[2]
    outer = _innermost(mid, benchmark)
    return outer[2] if outer is not None else UNATTRIBUTED


def _overlap(a: float, b: float, spans: Sequence[Span]) -> float:
    return sum(max(0.0, min(b, y) - max(a, x)) for x, y, _ in spans)


class _Busy:
    """Busy time of the device inside an interval, from its merged blocks."""

    def __init__(self, blocks: Sequence[Tuple[float, float]]):
        self.blocks = list(blocks)
        self.starts = [a for a, _ in self.blocks]
        self.cum = [0.0]
        for a, b in self.blocks:
            self.cum.append(self.cum[-1] + (b - a))

    def _before(self, t: float) -> float:
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0.0
        a, b = self.blocks[i - 1]
        return self.cum[i - 1] + (min(t, b) - a)

    def inside(self, a: float, b: float) -> float:
        return self._before(b) - self._before(a)


def reduced(run) -> Optional[Dict[str, Any]]:
    """The session of ``run`` reduced (cached on the record); None without one."""
    cached = getattr(run, "_session_reduced", None)
    if cached is not None:
        return cached
    session = getattr(run, "session", None)
    if session is None or session.profile is None:
        return None
    out = reduce(session.profile, session.spans, session.to_trace_ns, session.counters)
    if out is not None:
        run._session_reduced = out
    return out


def reduce(pd, ring, to_trace_ns, counters) -> Optional[Dict[str, Any]]:
    per_device = {k: v for k, v in xplane.device_events(pd).items() if v}
    if not per_device:
        return None
    ops = per_device[sorted(per_device)[0]]
    blocks = xplane.merged((a, b) for a, b, _ in ops)
    busy = _Busy(blocks)
    modules = _first_device_modules(pd)
    program = [s for s in xplane.host_spans(pd, PROGRAM_PREFIX) if s[2] != SESSION_MARK]
    benchmark = xplane.host_spans(pd, BENCHMARK_PREFIX)
    named = lambda name: sorted(s for s in program if s[2] == name)
    ms = lambda ns: [x / 1e6 for x in ns]

    gaps = [(b1, a2) for (_, b1), (a2, _) in zip(blocks, blocks[1:]) if a2 - b1 >= xplane.MIN_GAP_NS]
    names = [name_gap(g, program, benchmark) for g in gaps]
    idle_ns = sum(b - a for a, b in gaps)
    # the share is by time, not by gap: a decode gap's middle lies within a tenth of a millisecond of where
    # ``.fetch`` ends, so whole gaps counted by their middle swing between nothing and everything from run to run
    covered = _Busy(xplane.merged((a, b) for a, b, _ in program + benchmark))
    unattributed_ns = sum((b - a) - covered.inside(a, b) for a, b in gaps)
    longest = sorted(zip(gaps, names), key=lambda gn: gn[0][0] - gn[0][1])[:10]
    out: Dict[str, Any] = {
        "idle_gaps": [[n, (b - a) / 1e9] for (a, b), n in longest],
        "idle_unattributed_share": 100.0 * unattributed_ns / idle_ns if idle_ns else None,
        "counters": dict(counters),
        "ring_ms": {},        # metric -> durations of the ring's spans, in ms (the program's own record)
    }
    for s in ring:
        out["ring_ms"].setdefault(s.metric, []).append(s.duration * 1e3)

    # ---- train: the dominant program of the modules line is the step
    if modules:
        total: Dict[str, float] = {}
        for a, b, n in modules:
            total[n] = total.get(n, 0.0) + (b - a)
        main = max(total, key=total.get)
        steps = [(a, b) for a, b, n in modules if n == main]
        out["main_module"] = main
        out["main_module_ms"] = ms([b - a for a, b in steps])
        out["main_module_gap_ms"] = ms([a2 - b1 for (_, b1), (a2, _) in zip(steps, steps[1:])])

    # ---- serve: device time inside the engine's calls, and the gap between two decode programs
    decodes, prefills = named("vs.serve-decode"), named("vs.serve-prefill")
    out["decode_device_ms"] = ms([busy.inside(a, b) for a, b, _ in decodes])
    out["prefill_device_ms"] = ms([busy.inside(a, b) for a, b, _ in prefills])
    if decodes and modules:
        starts = [a for a, _, _ in decodes]

        def in_decode(t: float) -> bool:
            i = bisect.bisect_right(starts, t) - 1
            return i >= 0 and t < decodes[i][1]

        fetches, samples = named("vs.serve-decode.fetch"), named("vs.serve-sample")
        gap_ms, split = [], {"fetch": 0.0, "sample": 0.0, "enqueue": 0.0, "other": 0.0}
        for (_, b1, _), (a2, _, _) in zip(modules, modules[1:]):
            # two decode programs with no other program between them
            if not (in_decode(b1 - 1.0) and in_decode(a2)) or a2 <= b1:
                continue
            gap_ms.append((a2 - b1) / 1e6)
            fetch, sample = _overlap(b1, a2, fetches), _overlap(b1, a2, samples)
            enqueue = _overlap(b1, a2, decodes) - fetch
            split["fetch"] += fetch
            split["sample"] += sample
            split["enqueue"] += enqueue
            split["other"] += (a2 - b1) - fetch - sample - enqueue
        out["decode_gap_ms"] = gap_ms
        if gap_ms:   # mean milliseconds of one gap spent in each
            out["decode_gap_split_ms"] = {k: v / 1e6 / len(gap_ms) for k, v in split.items()}

    # ---- after the fact, through the offset: is each inside the live span that should hold it?
    waits = [(to_trace_ns(s.start), to_trace_ns(s.start + s.duration)) for s in ring if s.metric == "serve-queue-wait"]
    out["queue_waits"] = len(waits)
    out["queue_waits_ending_at_a_prefill"] = sum(
        1 for _, end in waits if end is not None and any(a - 5e5 <= end <= b for a, b, _ in prefills))
    return out


def breakdown(session: Dict[str, Any]) -> Dict[str, Any]:
    """What of the reduction goes into the result's ``breakdown``."""
    out = {"idle_gaps": session["idle_gaps"]}
    if "decode_gap_split_ms" in session:
        out["decode_gap_split_ms"] = session["decode_gap_split_ms"]
    return out


def p50(samples: Optional[Sequence[float]]) -> Optional[float]:
    return stats.percentile(samples or [], 50)


def suffix(run) -> Optional[str]:
    return {"train_steps": "train", "open_loop": "chat", "closed_loop": "batch"}.get(run.traffic_kind)


def serve_metrics(run, sfx: str) -> Dict[str, Optional[float]]:
    """The session's serve metrics under the family's suffix (``chat`` / ``batch``)."""
    session = reduced(run)
    if session is None:
        return {}
    c = session["counters"]
    steps = c.get("decode_steps") or 0
    return {
        f"decode_device_ms_p50.{sfx}": p50(session["decode_device_ms"]),
        f"prefill_device_ms_p50.{sfx}": p50(session["prefill_device_ms"]),
        f"decode_fetch_ms_p50.{sfx}": p50(session["ring_ms"].get("vs.serve-decode.fetch")),
        f"decode_host_gap_ms_p50.{sfx}": p50(session.get("decode_gap_ms")),
        f"logits_mb_to_host_per_step.{sfx}": c.get("logits_bytes_to_host", 0) / steps / 1e6 if steps else None,
        f"idle_unattributed_share.{sfx}": session["idle_unattributed_share"],
    }


def serve_declarations(sfx: str, moves: str) -> Dict[str, Dict[str, str]]:
    engine, device = "Serve engine", "Device"
    return {
        f"decode_device_ms_p50.{sfx}": {"unit": "ms", "layer": device, "moves": moves},
        f"prefill_device_ms_p50.{sfx}": {"unit": "ms", "layer": device, "moves": moves},
        f"decode_fetch_ms_p50.{sfx}": {"unit": "ms", "layer": engine, "moves": moves},
        f"decode_host_gap_ms_p50.{sfx}": {"unit": "ms", "layer": device, "moves": moves},
        f"logits_mb_to_host_per_step.{sfx}": {"unit": "MB", "layer": engine, "moves": moves},
        f"idle_unattributed_share.{sfx}": {"unit": "%", "layer": device, "moves": moves},
    }
