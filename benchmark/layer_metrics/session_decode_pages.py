"""How far the ``paged_decode`` kernel engaged, from the program's trace
session (``--trace 2``): ``decode_pages_read_share.chat`` / ``.batch`` = the
engine's counter ``decode_pages_read`` over ``decode_pages_capacity``, the
pages of K (and of V) the decode kernel fetched a layer in the traced seconds
against the ``slots x pages_per_slot`` the XLA leg gathers every step.  About
``kv_live_share`` plus half a page a slot.  A program without the counters
(before PR 27), or an engine built with the XLA leg (capacity 0), leaves the
metric out."""

from benchmark.layer_metrics import _session as s

MOVES = {"chat": "itl_p95_ms", "batch": "serve_tokens_per_s"}
METRICS = {f"decode_pages_read_share.{sfx}": {"unit": "%", "layer": "Serve engine", "moves": moves}
           for sfx, moves in MOVES.items()}


def read(run):
    sfx, session = s.suffix(run), s.reduced(run)
    if sfx not in MOVES or session is None:
        return {}
    capacity = session["counters"].get("decode_pages_capacity") or 0
    if not capacity:
        return {}
    return {f"decode_pages_read_share.{sfx}": 100.0 * session["counters"].get("decode_pages_read", 0) / capacity}
