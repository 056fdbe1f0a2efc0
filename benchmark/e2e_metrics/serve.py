"""End-to-end metrics of the serve cells, from the benchmark's own
per-request and per-token samples."""

from benchmark import stats

METRICS = {
    "itl_p95_ms": {"unit": "ms"},
    "serve_tokens_per_s": {"unit": "tokens/s"},
}


def read(run):
    if run.kind != "serve":
        return {}
    return {
        "itl_p95_ms": stats.ms(stats.percentile(stats.token_gaps(run.token_times(), run.window), 95)),
        # tokens emitted inside the window, whether or not their request completes inside it
        "serve_tokens_per_s": stats.emitted_tokens(run.token_times(), run.window) / run.window_s,
    }
