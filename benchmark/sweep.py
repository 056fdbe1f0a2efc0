"""Find the knee of an open-loop serve cell: one process builds the system
once and runs the cell's traffic at several fixed rates, one window each.

    python3 benchmark/sweep.py --workload mistral7b_serve_chat --rates 1.4,1.6,1.8,2.0,2.2,2.4 --seconds 45 --seed 11

Prints one JSON row per rate.  The knee is the highest rate at which no
request is shed or left unanswered and the queue does not build: its mean
length stays under one request both in the middle fifth of the window and in
its last tenth (a queue that is long in the middle and happens to have drained
by the end is past the knee too).  The cell's traffic file then gets 0.8 x that
rate as a number.  Needs a TPU, like the benchmark's command.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse   # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import sys        # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def sweep_row(rec, rate: float) -> dict:
    """What one window at one rate says about whether the system kept up."""
    from benchmark import stats
    from benchmark.layer_metrics import _serve as s

    delays, failed = stats.first_token_delays(rec.request_rows(), rec.window)
    w0, w1 = rec.window
    mid = [q for t, _a, q in rec.loop_steps if w0 + 0.4 * (w1 - w0) <= t < w0 + 0.6 * (w1 - w0)]
    end = [q for t, _a, q in rec.loop_steps if w0 + 0.9 * (w1 - w0) <= t < w1]
    mean = lambda xs: sum(xs) / len(xs) if xs else None
    return {
        "rate_per_s": rate, "due_in_window": len(delays) + failed, "failed": failed,
        "shed": sum(1 for r in rec.requests.values() if r.status == "shed"),
        "queue_mid_mean": mean(mid), "queue_end_mean": mean(end),
        "ttft_p50_ms": stats.ms(stats.percentile(delays, 50)), "ttft_p90_ms": stats.ms(stats.percentile(delays, 90)),
        "itl_p95_ms": s.itl_ms(rec, 95), "queue_wait_ms_p50": s.queue_wait_ms_p50(rec),
        "tokens_per_s": stats.emitted_tokens(rec.token_times(), rec.window) / rec.window_s,
        "batch_occupancy": s.batch_occupancy(rec), "gen_lag_p99_ms": s.gen_lag_ms_p99(rec),
        "compiles_in_window": rec.compiles_in_window(),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests per second, in the order to run them")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    from benchmark.spec import load_cell
    from vescale_tpu.compile_cache import use_compile_cache

    spec = load_cell(args.workload, ROOT)
    if spec.traffic["kind"] != "open_loop":
        ap.error("a knee is a property of an open loop")
    use_compile_cache()
    import jax

    jax.config.update("jax_threefry_partitionable", True)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"sweep: no TPU (jax reports {devices[0].platform})", file=sys.stderr)
        return 2

    from benchmark.harness import CompileCounter
    from benchmark.serve_cell import ServeCell

    compiles = CompileCounter().install()
    try:
        cell = ServeCell(spec, devices)
        cell.build(args.seed)
        for rate in (float(r) for r in args.rates.split(",")):
            rec = cell.run(dict(spec.traffic, rate_per_s=rate), args.seed, args.seconds, traced=False,
                           compiles=compiles, setup_from=PROCESS_START)
            ok, ledger = cell.ledger(rec)
            print(json.dumps(dict(sweep_row(rec, rate), ledger_ok=ok)), flush=True)
    finally:
        compiles.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
