"""Shared arithmetic of the serve readers (not a reader: the leading
underscore keeps it out of discovery).  A later mix gets its own suffix by a
reader file of a few lines that calls these."""

from benchmark import stats


def queue_wait_ms_p50(run):
    waits = [r.admitted - r.due for r in run.requests.values()
             if r.admitted is not None and stats.in_window(r.due, run.window)]
    return stats.ms(stats.percentile(waits, 50))


def batch_occupancy(run):
    active = [d[2] for d in run.in_window(run.decodes)]
    return 100.0 * sum(active) / (len(active) * run.slots) if active else None


def loop_self_ms_p50(run):
    """Step wall minus the time inside engine.prefill / engine.decode, over
    the loop iterations that decoded."""
    calls = sorted([(p[0], p[1]) for p in run.prefills] + [(d[0], d[1]) for d in run.decodes])
    decode_ends = sorted(d[1] for d in run.decodes)
    selfs, i, k = [], 0, 0
    for (prev_end, _, _), (end, _, _) in zip(run.loop_steps, run.loop_steps[1:]):
        inside = 0.0
        while i < len(calls) and calls[i][1] <= end:
            if calls[i][0] >= prev_end:
                inside += calls[i][1] - calls[i][0]
            i += 1
        decoded = False
        while k < len(decode_ends) and decode_ends[k] <= end:
            decoded = decoded or decode_ends[k] >= prev_end
            k += 1
        if decoded and stats.in_window(end, run.window):
            selfs.append(end - prev_end - inside)
    return stats.ms(stats.percentile(selfs, 50))


def prefill_ms_p50(run):
    return stats.ms(stats.percentile([p[1] - p[0] for p in run.in_window(run.prefills)], 50))


def prefill_pad_share(run):
    spans = run.in_window(run.prefills)
    if not spans:
        return None
    return 100.0 * (1.0 - sum(p[3] for p in spans) / (len(spans) * run.padded_prompt_len))


def kv_live_share(run):
    """Of the cache's positions (slots x positions a slot), the share that
    held a live token, averaged over the window's decode steps: the step that
    emits a request's k-th token reads its prompt and the k tokens before."""
    steps = run.in_window(run.decodes)
    if not steps or not run.slots or not run.padded_prompt_len:
        return None
    live = sum(r.prompt_len + k for r in run.requests.values()
               for k, t in enumerate(r.token_times) if k and stats.in_window(t, run.window))
    return 100.0 * live / (len(steps) * run.slots * run.padded_prompt_len)


def decode_step_ms_p50(run):
    return stats.ms(stats.percentile([d[1] - d[0] for d in run.in_window(run.decodes)], 50))


def ttft_ms(run, q):
    delays, _ = stats.first_token_delays(run.request_rows(), run.window)
    return stats.ms(stats.percentile(delays, q))


def itl_ms(run, q):
    return stats.ms(stats.percentile(stats.token_gaps(run.token_times(), run.window), q))


def gen_lag_ms_p99(run):
    lags = [r.sent - r.due for r in run.requests.values() if stats.in_window(r.due, run.window)]
    return stats.ms(stats.percentile(lags, 99))


def device_idle_share(run):
    if run.trace is None or not run.trace["window_s"]:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])


def peak_hbm_gb(run):
    return run.memory_peak_bytes / 1e9 if run.memory_peak_bytes else None
