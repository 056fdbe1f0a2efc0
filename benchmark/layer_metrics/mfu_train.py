"""Layer "Model blocks + kernels": an end-to-end utilisation (tokens/s x
operations per token from shapes, over chips x peak), not a roofline share."""

LAYER = "Model blocks + kernels"
METRICS = {"mfu.train": {"unit": "%", "layer": LAYER, "moves": "train_tokens_per_s_per_chip"}}


def read(run):
    if run.kind != "train" or not run.peak_flops_per_chip or run.train_tokens_per_s() is None:
        return {}
    return {"mfu.train": 100.0 * run.train_tokens_per_s() * run.flops_per_token
            / (run.chips * run.peak_flops_per_chip)}
