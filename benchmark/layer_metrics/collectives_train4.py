"""Layer "Collectives / sharding": share of device-busy time in collective
operations of the profiler trace (HLO op names)."""

LAYER = "Collectives / sharding"
METRICS = {"collective_share.train4": {"unit": "%", "layer": LAYER, "moves": "train_tokens_per_s_per_chip"}}


def read(run):
    if run.kind != "train" or run.chips < 2 or run.trace is None or not run.trace["busy_s"]:
        return {}
    return {"collective_share.train4": 100.0 * run.trace["collective_s"] / run.trace["busy_s"]}
