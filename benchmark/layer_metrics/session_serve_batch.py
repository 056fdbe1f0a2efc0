"""Per-layer metrics of the closed-loop batch-decode cell that read the
program's trace session (``--trace 2``); ``session_serve_chat.py`` says what
each reads."""

from benchmark.layer_metrics import _session as s

METRICS = s.serve_declarations("batch", "serve_tokens_per_s")


def read(run):
    if run.traffic_kind != "closed_loop":
        return {}
    return s.serve_metrics(run, "batch")
