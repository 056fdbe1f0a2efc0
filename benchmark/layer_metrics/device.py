"""Layer "Device": idle share from the reduced profiler trace, and the
runtime's peak bytes (which do not seem to count a program's temporaries:
PERF.md, Open questions), by the suffix of the cell's family.  A traffic kind
this file does not know gets nothing from it: its family brings a reader of
its own."""

from benchmark.layer_metrics import _serve as s

LAYER = "Device"
MOVES = {"train": "train_tokens_per_s_per_chip", "chat": "itl_p95_ms", "batch": "serve_tokens_per_s"}
METRICS = {f"{name}.{suffix}": {"unit": unit, "layer": LAYER, "moves": moves}
           for suffix, moves in MOVES.items() for name, unit in (("device_idle_share", "%"), ("peak_hbm_gb", "GB"))}


def read(run):
    suffix = {"train_steps": "train", "open_loop": "chat", "closed_loop": "batch"}.get(run.traffic_kind)
    if suffix is None:
        return {}
    return {f"device_idle_share.{suffix}": s.device_idle_share(run), f"peak_hbm_gb.{suffix}": s.peak_hbm_gb(run)}
