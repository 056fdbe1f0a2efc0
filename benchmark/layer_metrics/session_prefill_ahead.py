"""How far a prefill was left unread, from the program's trace session
(``--trace 2``): ``prefill_ahead_share.chat`` / ``.batch`` = the engine's
counter ``prefill_reads_ahead`` over ``prefill_launches``, of the prefills
launched in the traced seconds the share whose first token was still on the
device when the decode step that takes it from there was enqueued (the device
went from the prefill into that step; the rest were read first: no step in
flight to feed from, or a request of one token).  A block engine's loop never
reads a prefill: every one counts.  A program without the counter (before
PR 52), or a session that launched no prefill, leaves the metric out."""

from benchmark.layer_metrics import _session as s

MOVES = {"chat": "itl_p95_ms", "batch": "serve_tokens_per_s"}
METRICS = {f"prefill_ahead_share.{sfx}": {"unit": "%", "layer": "Serve engine", "moves": moves}
           for sfx, moves in MOVES.items()}


def read(run):
    sfx, session = s.suffix(run), s.reduced(run)
    if sfx not in MOVES or session is None:
        return {}
    counters = session["counters"]
    launches = counters.get("prefill_launches") or 0
    if not launches or "prefill_reads_ahead" not in counters:
        return {}
    return {f"prefill_ahead_share.{sfx}": 100.0 * counters["prefill_reads_ahead"] / launches}
