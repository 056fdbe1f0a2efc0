"""Layer "Data input": host clock around the loader's next and the copy in."""

from benchmark import stats

LAYER = "Data input"
METRICS = {"data_wait_ms_p50.train": {"unit": "ms", "layer": LAYER, "moves": "train_tokens_per_s_per_chip"}}


def read(run):
    if run.kind != "train":
        return {}
    waits = [w for w, end in zip(run.data_wait_s, run.step_end) if stats.in_window(end, run.window)]
    return {"data_wait_ms_p50.train": stats.ms(stats.percentile(waits, 50))}
