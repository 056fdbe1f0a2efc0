"""Which form the expert layer took, from the program's trace session
(``--trace 2``): ``moe_padded_share.batch`` = the engine's counter
``moe_padded_layer_steps`` over ``moe_layer_steps``, of the expert layers of
the decode calls read in the traced seconds the share that ran as the padded
batched product (``vescale_tpu/moe/dropless.py``: the call's shape made it a
candidate and its busiest expert fit the pad; the rest took the sorted, grouped
product, or all experts on all tokens where a step has few tokens).  A program
without the counter (before PR 37), or a session that read no expert layer,
leaves the metric out."""

from benchmark.layer_metrics import _session as s

METRICS = {"moe_padded_share.batch": {"unit": "%", "layer": "Expert layer", "moves": "serve_tokens_per_s"}}


def read(run):
    session = s.reduced(run)
    if s.suffix(run) != "batch" or session is None:
        return {}
    counters = session["counters"]
    layer_steps = counters.get("moe_layer_steps") or 0
    if not layer_steps or "moe_padded_layer_steps" not in counters:
        return {}
    return {"moe_padded_share.batch": 100.0 * counters["moe_padded_layer_steps"] / layer_steps}
