"""Per-layer metrics of the closed-loop batch-decode cell (suffix ``.batch``)."""

from benchmark.layer_metrics import _serve as s

HOST, ENGINE = "Serve control (host loop)", "Serve engine"
METRICS = {
    "loop_self_ms_p50.batch": {"unit": "ms", "layer": HOST, "moves": "serve_tokens_per_s"},
    "decode_step_ms_p50.batch": {"unit": "ms", "layer": ENGINE, "moves": "serve_tokens_per_s"},
    "prefill_ms_p50.batch": {"unit": "ms", "layer": ENGINE, "moves": "serve_tokens_per_s"},
    "batch_occupancy.batch": {"unit": "%", "layer": "Serve control", "moves": "serve_tokens_per_s"},
    "compiles_in_window.batch": {"unit": "count", "layer": ENGINE, "moves": "serve_tokens_per_s"},
    "kv_live_share.batch": {"unit": "%", "layer": ENGINE, "moves": "serve_tokens_per_s"},
}


def read(run):
    if run.traffic_kind != "closed_loop":
        return {}
    return {
        "loop_self_ms_p50.batch": s.loop_self_ms_p50(run),
        "decode_step_ms_p50.batch": s.decode_step_ms_p50(run),
        "prefill_ms_p50.batch": s.prefill_ms_p50(run),
        "batch_occupancy.batch": s.batch_occupancy(run),
        "compiles_in_window.batch": run.compiles_in_window(),
        "kv_live_share.batch": s.kv_live_share(run),
    }
