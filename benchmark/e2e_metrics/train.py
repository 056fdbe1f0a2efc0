"""End-to-end metric of the train cells."""

METRICS = {"train_tokens_per_s_per_chip": {"unit": "tokens/s/chip"}}


def read(run):
    if run.kind != "train":
        return {}
    rate = run.train_tokens_per_s()
    return {"train_tokens_per_s_per_chip": None if rate is None else rate / run.chips}
