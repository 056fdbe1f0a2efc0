"""Per-layer metrics of the train cells that read the program's trace session
(``--trace 2``): the step's program on the device (``XLA Modules`` line), the
idle between two of them, and the program's own spans ``vs.train-step`` (the
call of the jitted step: the enqueue only) and ``vs.data-load`` (the native
fetch)."""

from benchmark.layer_metrics import _session as s

MOVES = "train_tokens_per_s_per_chip"
METRICS = {
    "step_device_ms_p50.train": {"unit": "ms", "layer": "Device", "moves": MOVES},
    "step_host_gap_ms_p50.train": {"unit": "ms", "layer": "Device", "moves": MOVES},
    "step_dispatch_ms_p50.train": {"unit": "ms", "layer": "Train step assembly", "moves": MOVES},
    "data_load_ms_p50.train": {"unit": "ms", "layer": "Data input", "moves": MOVES},
    "idle_unattributed_share.train": {"unit": "%", "layer": "Device", "moves": MOVES},
}


def read(run):
    session = s.reduced(run) if run.kind == "train" else None
    if session is None:
        return {}
    return {
        "step_device_ms_p50.train": s.p50(session.get("main_module_ms")),
        "step_host_gap_ms_p50.train": s.p50(session.get("main_module_gap_ms")),
        "step_dispatch_ms_p50.train": s.p50(session["ring_ms"].get("vs.train-step")),
        "data_load_ms_p50.train": s.p50(session["ring_ms"].get("vs.data-load")),
        "idle_unattributed_share.train": session["idle_unattributed_share"],
    }
