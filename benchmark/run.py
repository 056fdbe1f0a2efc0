"""The benchmark's one command:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1|2>

``--trace 0`` measures; ``--trace 1`` puts the window's last seconds under the
profiler and prints the per-layer metrics; ``--trace 2`` does what 0 does until
the window has closed, then traces a few seconds of the same traffic through
the program's own trace session and prints both kinds of metric.

One process, no child.  It needs the chips the cell asks for: without a TPU,
or with fewer chips, it exits non-zero and prints no result.  The last line of
its standard output is the result object of the contract; everything else
worth reading goes to the lines before it.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()   # set-up is counted from here: before any heavy import

import argparse   # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import sys        # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def log(**fields) -> None:
    print("[bm] " + json.dumps(fields, default=str), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="run one cell of BENCHMARK.json")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1, 2), default=0)
    args = ap.parse_args(argv)
    if args.seconds <= 0:
        ap.error("--seconds must be positive")

    from benchmark.spec import SpecError, load_cell

    try:
        spec = load_cell(args.workload, ROOT)
    except SpecError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    try:
        from vescale_tpu.compile_cache import use_compile_cache
    except ImportError as e:
        print(f"benchmark: the system under test is not in this checkout: {e}", file=sys.stderr)
        return 2

    cache_dir = use_compile_cache()   # JAX_COMPILATION_CACHE_DIR where set, else <checkout>/.jax_cache
    import jax

    jax.config.update("jax_threefry_partitionable", True)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"benchmark: no TPU (jax reports {devices[0].platform}); nothing is measured on another device",
              file=sys.stderr)
        return 2
    if len(devices) < spec.chips:
        print(f"benchmark: {spec.name} needs {spec.chips} chips, jax reports {len(devices)}", file=sys.stderr)
        return 2
    devices = devices[: spec.chips]

    from benchmark import serve_cell, train_cell
    from benchmark.harness import memory_peak_bytes, read_metrics, result_object

    runner = {"train": train_cell.run_cell, "serve": serve_cell.run_cell}[spec.kind]
    log(workload=spec.name, config=spec.config_name, traffic=spec.traffic_name, seed=args.seed,
        seconds=args.seconds, trace=args.trace, device=devices[0].device_kind, chips=len(devices),
        compile_cache=cache_dir or os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    rec, correct, attempted, failed, notes = runner(
        spec, devices, args.seed, args.seconds, args.trace, PROCESS_START)
    if args.trace == 2:
        rec.memory_peak_bytes_run = memory_peak_bytes(devices)
    log(setup_s=rec.setup_s, total_s=time.perf_counter() - PROCESS_START, **notes)
    # what the readers can read without a trace, in every run (the result line holds one family only)
    runq_ns = (rec.host_sched or {}).get("thread_runq_wait_ns")
    layer_dir, e2e_dir = (os.path.join(spec.root, "benchmark", d) for d in ("layer_metrics", "e2e_metrics"))
    log(end_to_end={k: v["value"] for k, v in read_metrics(e2e_dir, spec.end_to_end, rec).items()},
        per_layer={k: v["value"] for k, v in read_metrics(layer_dir, spec.per_layer, rec).items()},
        # the kernel's count for the window; null where it counts nothing (gVisor), so BENCHMARK.json lists no such metric
        host_runq_wait_ms=None if runq_ns is None else runq_ns / 1e6)
    result = result_object(spec, rec, devices, correct=correct, attempted=attempted, failed=failed,
                           traced=args.trace)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
