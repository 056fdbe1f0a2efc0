"""Set-up: process start to the opening of the window (imports, weights,
cache, compilation or compile-cache load, warm-up, the traffic's lead-in)."""

METRICS = {"setup_s": {"unit": "s"}}


def read(run):
    return {"setup_s": run.setup_s}
