"""How far the decode pipeline engaged, from the program's trace session
(``--trace 2``): ``decode_ahead_share.chat`` / ``.batch`` = the engine's
counter ``decode_steps_ahead`` over ``decode_steps``, of the decode steps read
in the traced seconds the share that was launched while the step before it was
still unread (the device went from one into the next; the rest started cold:
no step in flight, or a boundary that read it first).  A program without the
counter (before PR 35), or a session that read no step, leaves the metric out."""

from benchmark.layer_metrics import _session as s

MOVES = {"chat": "itl_p95_ms", "batch": "serve_tokens_per_s"}
METRICS = {f"decode_ahead_share.{sfx}": {"unit": "%", "layer": "Serve engine", "moves": moves}
           for sfx, moves in MOVES.items()}


def read(run):
    sfx, session = s.suffix(run), s.reduced(run)
    if sfx not in MOVES or session is None:
        return {}
    counters = session["counters"]
    steps = counters.get("decode_steps") or 0
    if not steps or "decode_steps_ahead" not in counters:
        return {}
    return {f"decode_ahead_share.{sfx}": 100.0 * counters["decode_steps_ahead"] / steps}
