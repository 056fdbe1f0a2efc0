"""The serve loop's own pieces and the idle they explain, from the program's
trace session (``--trace 2``).  PR 38 tiled an iteration of
``run_serve_resilient`` with live spans (``vs.serve-boundary``,
``vs.serve-admit``, ``vs.serve-books``, ``vs.serve-hook``, ``vs.serve-idle``
beside ``vs.serve-sample`` and the engine's) and anchored a request's first
wait at the inbox (``serve-inbox-wait``, recorded after the fact):

- ``loop_books_ms_p50.chat`` / ``.batch``: the ``vs.serve-books`` span, by
  the ring's durations: ``_close_step`` whole, the host's books for one read
  step (step-time estimate, the request spans, completions, the step's line);
- ``inbox_wait_ms_p50.chat``: ``serve-inbox-wait``: from ``RequestInbox.push``
  to the loop's drain that submitted the request (a boundary's length; the
  wait for the decode step in flight where one is read first), which
  ``sched_queue_wait_ms_p50.chat`` cannot hold: its span begins at the submit;
- ``idle_no_work_share.chat``: of the device's idle time in the traced seconds
  (the gaps between its busy blocks, as ``idle_unattributed_share.*`` takes
  them), the share under ``vs.serve-idle``: the loop slept a slice because
  nothing was active and nothing queued.  A server with no request to serve,
  not a host that holds the chip back.  0 where the traced seconds always
  had a request to serve (the loop's other spans are there, that one is not).

A program without the spans leaves each metric out."""

from benchmark import xplane
from benchmark.layer_metrics import _session as s

IDLE_SPAN = "vs.serve-idle"
TILED = "vs.serve-boundary"     # opens every iteration of a loop that has the spans at all
MOVES = {"chat": "itl_p95_ms", "batch": "serve_tokens_per_s"}
LOOP, CONTROL, DEVICE = "Serve control (host loop)", "Serve control", "Device"
METRICS = {f"loop_books_ms_p50.{sfx}": {"unit": "ms", "layer": LOOP, "moves": moves} for sfx, moves in MOVES.items()}
METRICS["inbox_wait_ms_p50.chat"] = {"unit": "ms", "layer": CONTROL, "moves": MOVES["chat"]}
METRICS["idle_no_work_share.chat"] = {"unit": "%", "layer": DEVICE, "moves": MOVES["chat"]}


def idle_share_under(pd, name: str):
    """Of the first chip's idle time, the per cent under the host's spans
    called ``name``; None where the loop's iteration is not tiled (a program
    before PR 38), or the chip was never idle."""
    per_device = {k: v for k, v in xplane.device_events(pd).items() if v}
    if not per_device or not xplane.host_spans(pd, TILED):
        return None
    under = xplane.merged((a, b) for a, b, _ in xplane.host_spans(pd, name))
    blocks = xplane.merged((a, b) for a, b, _ in per_device[sorted(per_device)[0]])
    gaps = [(b1, a2) for (_, b1), (a2, _) in zip(blocks, blocks[1:]) if a2 - b1 >= xplane.MIN_GAP_NS]
    idle_ns = sum(b - a for a, b in gaps)
    covered = s._Busy(under)
    return 100.0 * sum(covered.inside(a, b) for a, b in gaps) / idle_ns if idle_ns else None


def read(run):
    sfx, session = s.suffix(run), s.reduced(run)
    if sfx not in MOVES or session is None:
        return {}
    out = {f"loop_books_ms_p50.{sfx}": s.p50(session["ring_ms"].get("vs.serve-books"))}
    if sfx == "chat":
        out["inbox_wait_ms_p50.chat"] = s.p50(session["ring_ms"].get("serve-inbox-wait"))
        profile = getattr(getattr(run, "session", None), "profile", None)
        out["idle_no_work_share.chat"] = None if profile is None else idle_share_under(profile, IDLE_SPAN)
    return out
