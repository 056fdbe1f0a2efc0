"""Layer "Host machine": was the process held up inside the measured window?
``host_stall_ms_max`` is the largest excess of one step (train) or one decode
call (serve) over the window's median; read in every mode, it needs no trace.
Whether such an excess was a neighbour on the host's cores or a wait on the
device runtime is for the kernel's run-queue wait of the loop's thread to say
(``RunRecord.host_sched``, two reads of ``vescale_tpu.telemetry.host_sched_stats``
at the window's ends; ``run.py`` prints it as ``host_runq_wait_ms`` on the
``[bm]`` line).  It is no metric of ``BENCHMARK.json``: the sealed machines run
gVisor, whose kernel counts nothing, so no run there could report it."""

from benchmark import stats
from benchmark.layer_metrics import _session as s

LAYER = "Host machine"
MOVES = {"train": "train_tokens_per_s_per_chip", "chat": "itl_p95_ms", "batch": "serve_tokens_per_s"}
METRICS = {f"host_stall_ms_max.{suffix}": {"unit": "ms", "layer": LAYER, "moves": moves} for suffix, moves in MOVES.items()}


def read(run):
    suffix = s.suffix(run)
    if suffix is None:
        return {}
    if run.kind == "train":
        calls = [d for d, end in zip(run.step_s, run.step_end) if stats.in_window(end, run.window)]
    else:
        calls = [d[1] - d[0] for d in run.in_window(run.decodes)]
    return {f"host_stall_ms_max.{suffix}": stats.ms(max(calls) - stats.percentile(calls, 50)) if calls else None}
