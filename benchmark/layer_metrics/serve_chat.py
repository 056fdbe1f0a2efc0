"""Per-layer metrics of the open-loop chat cell (suffix ``.chat``).

``ttft_p50_ms.chat`` is the median time to first token, which PR 24 could not
hold under the largest bound an end-to-end metric may have (PERF.md): it and
the metrics that explain it are declared as moving ``itl_p95_ms``, the cell's
one judged metric.  That arrow is nominal."""

from benchmark.layer_metrics import _serve as s

CONTROL, HOST, ENGINE, TAILS, GEN = (
    "Serve control", "Serve control (host loop)", "Serve engine", "Serve tails (recorded, not judged)",
    "Load generator")
METRICS = {
    "queue_wait_ms_p50.chat": {"unit": "ms", "layer": CONTROL, "moves": "itl_p95_ms"},
    "batch_occupancy.chat": {"unit": "%", "layer": CONTROL, "moves": "itl_p95_ms"},
    "loop_self_ms_p50.chat": {"unit": "ms", "layer": HOST, "moves": "itl_p95_ms"},
    "prefill_ms_p50.chat": {"unit": "ms", "layer": ENGINE, "moves": "itl_p95_ms"},
    "prefill_pad_share.chat": {"unit": "%", "layer": ENGINE, "moves": "itl_p95_ms"},
    "decode_step_ms_p50.chat": {"unit": "ms", "layer": ENGINE, "moves": "itl_p95_ms"},
    "ttft_p50_ms.chat": {"unit": "ms", "layer": CONTROL, "moves": "itl_p95_ms"},
    "ttft_p90_ms.chat": {"unit": "ms", "layer": TAILS, "moves": "itl_p95_ms"},
    "itl_p99_ms.chat": {"unit": "ms", "layer": TAILS, "moves": "itl_p95_ms"},
    "gen_lag_p99_ms.chat": {"unit": "ms", "layer": GEN, "moves": "itl_p95_ms"},
    "compiles_in_window.chat": {"unit": "count", "layer": ENGINE, "moves": "itl_p95_ms"},
    "kv_live_share.chat": {"unit": "%", "layer": ENGINE, "moves": "itl_p95_ms"},
}


def read(run):
    if run.traffic_kind != "open_loop":
        return {}
    return {
        "queue_wait_ms_p50.chat": s.queue_wait_ms_p50(run),
        "batch_occupancy.chat": s.batch_occupancy(run),
        "loop_self_ms_p50.chat": s.loop_self_ms_p50(run),
        "prefill_ms_p50.chat": s.prefill_ms_p50(run),
        "prefill_pad_share.chat": s.prefill_pad_share(run),
        "decode_step_ms_p50.chat": s.decode_step_ms_p50(run),
        "ttft_p50_ms.chat": s.ttft_ms(run, 50),
        "ttft_p90_ms.chat": s.ttft_ms(run, 90),
        "itl_p99_ms.chat": s.itl_ms(run, 99),
        "gen_lag_p99_ms.chat": s.gen_lag_ms_p99(run),
        "compiles_in_window.chat": run.compiles_in_window(),
        "kv_live_share.chat": s.kv_live_share(run),
    }
