"""Per-layer metrics of the open-loop chat cell that read the program's trace
session (``--trace 2``): device time inside the engine's own spans
(``vs.serve-decode``, ``vs.serve-prefill``), the wait for the device and the
logits' copy (``vs.serve-decode.fetch``), the idle between two decode
programs, the engine's counters, and the scheduler's own queue wait
(``serve-queue-wait``, recorded after the fact: from the loop's ``submit`` to
the request's own prefill, so without the inbox and the decode step in flight
that ``queue_wait_ms_p50.chat`` holds; PERF.md lists it for retirement or for
re-anchoring at the inbox push)."""

from benchmark.layer_metrics import _session as s

METRICS = dict(s.serve_declarations("chat", "itl_p95_ms"),
               **{"sched_queue_wait_ms_p50.chat": {"unit": "ms", "layer": "Serve control", "moves": "itl_p95_ms"}})


def read(run):
    if run.traffic_kind != "open_loop":
        return {}
    out = s.serve_metrics(run, "chat")
    if out:
        out["sched_queue_wait_ms_p50.chat"] = s.p50(s.reduced(run)["ring_ms"].get("serve-queue-wait"))
    return out
