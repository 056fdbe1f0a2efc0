"""Layer "Train step assembly": host clock around block_until_ready, and
jax's own compile events."""

from benchmark import stats

LAYER = "Train step assembly"
METRICS = {
    "step_ms_p50.train": {"unit": "ms", "layer": LAYER, "moves": "train_tokens_per_s_per_chip"},
    "compiles_in_window.train": {"unit": "count", "layer": LAYER, "moves": "train_tokens_per_s_per_chip"},
}


def read(run):
    if run.kind != "train":
        return {}
    steps = [s for s, end in zip(run.step_s, run.step_end) if stats.in_window(end, run.window)]
    return {"step_ms_p50.train": stats.ms(stats.percentile(steps, 50)),
            "compiles_in_window.train": run.compiles_in_window()}
