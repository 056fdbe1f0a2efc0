#!/usr/bin/env python
"""Redistribute microbenchmark — the multi-hop planner's acceptance gauge.

Runs a battery of representative placement transitions (single-hop kernel
baselines, the axis-swap cycle, Partial x cross-dim Shard, multi-mesh-dim
interleave changes, a cross-mesh bridge, and one genuinely out-of-scope
fallback pair) and reports, per pair:

  path                 trivial | kernel | planned | fallback
  hops / bytes_moved   plan length and cost-model wire bytes (planned)
  first_ms / repeat_ms wall time of the first (plan + trace + run) and a
                       repeated (cached) execution
  retraces_on_repeat   jit cache growth across the repeat — MUST be 0:
                       repeated boundary transitions pay zero re-plan and
                       zero retrace (ISSUE 2 acceptance)
  ok                   value-exactness vs the logical input

Emits ONE JSON metric line (``"metric": "redistribute_bench"``) on stdout.
Wired into tier-1 via tests/test_redistribute_plan.py (like
scripts/telemetry_smoke.py).
"""

from __future__ import annotations

import json
import os
import sys
import time
import warnings

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "cpu" in os.environ.get("JAX_PLATFORMS", ""):
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _jit_cache_sizes(plan):
    return [h.fn._cache_size() for h in plan.hops if hasattr(h.fn, "_cache_size")]


def _classify(src, dst):
    """Which redistribute() tier serves src -> dst — redistribute.py's own
    classify_transition (kept next to the dispatch), plus the plan object
    for planned pairs."""
    from vescale_tpu.redistribute import classify_transition
    from vescale_tpu.redistribute_plan import plan_redistribute

    path = classify_transition(src, dst)
    return path, plan_redistribute(src, dst) if path == "planned" else None


def run_bench() -> dict:
    import jax
    import numpy as np

    import vescale_tpu as vt
    from vescale_tpu.placements import (
        InterleavedShard,
        Partial,
        RaggedShard,
        Replicate,
        Shard,
    )
    from vescale_tpu.redistribute_plan import clear_plan_cache, plan_comm_summary

    n = len(jax.devices())
    if n < 8:  # the battery assumes an 8-way mesh
        raise SystemExit(f"redistribute_bench needs >= 8 devices, have {n}")
    mesh2d = vt.DeviceMesh(("dp", "tp"), (2, 4))
    mesh1d = vt.DeviceMesh(("tp",), (8,))

    xu = np.arange(7 * 12, dtype=np.float32).reshape(7, 12)  # uneven: no trivial respec
    x8 = np.arange(8 * 8, dtype=np.float32).reshape(8, 8)
    x64 = np.arange(64, dtype=np.float32)
    battery = [
        # name, mesh, src placements, dst placements, data, dst_mesh
        ("kernel:all_to_all", mesh2d, [Shard(0), Replicate()], [Shard(1), Replicate()], xu, None),
        ("kernel:interleave_1dim", mesh1d, [InterleavedShard(0, 3)], [Shard(0)],
         np.arange(96 * 3, dtype=np.float32).reshape(96, 3), None),
        ("planned:axis_swap", mesh2d, [Shard(0), Shard(1)], [Shard(1), Shard(0)], xu, None),
        ("planned:partial_cross_shard", mesh2d, [Partial(), Shard(0)], [Shard(0), Partial()], x8, None),
        ("planned:shard_to_partial", mesh2d, [Shard(0), Replicate()], [Partial(), Shard(0)], x8, None),
        ("planned:interleave_2dim", mesh2d, [InterleavedShard(0, 2), InterleavedShard(1, 2)],
         [Replicate(), Shard(1)], x8, None),
        ("planned:cross_mesh", mesh2d, [Partial(), InterleavedShard(0, 2)], [Shard(0)],
         np.arange(64 * 4, dtype=np.float32).reshape(64, 4), mesh1d),
        ("fallback:ragged_to_dense", mesh1d, [RaggedShard((0,), (1, 2, 1, 2, 1, 3, 3, 3))],
         [Shard(0)], x64, None),
    ]

    clear_plan_cache()
    pairs = []
    for name, mesh, src_pl, dst_pl, data, dst_mesh in battery:
        d = vt.distribute_tensor(data, mesh, src_pl)
        golden = np.asarray(d.full_tensor())
        src = d.spec
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            t0 = time.perf_counter()
            out = d.redistribute(dst_mesh, dst_pl)
            jax.block_until_ready(out.data)
            first_ms = (time.perf_counter() - t0) * 1e3
            dst = out.spec
            path, plan = _classify(src, dst)
            before = _jit_cache_sizes(plan) if plan is not None else []
            t0 = time.perf_counter()
            out2 = d.redistribute(dst_mesh, dst_pl)
            jax.block_until_ready(out2.data)
            repeat_ms = (time.perf_counter() - t0) * 1e3
            after = _jit_cache_sizes(plan) if plan is not None else []
        rec = {
            "name": name,
            "path": path,
            "first_ms": round(first_ms, 3),
            "repeat_ms": round(repeat_ms, 3),
            "retraces_on_repeat": sum(after) - sum(before),
            "ok": bool(np.allclose(np.asarray(out.full_tensor()), golden))
            and path == name.split(":")[0],
        }
        if plan is not None:
            summary = plan_comm_summary(plan)
            rec.update(
                hops=summary["n_hops"],
                bytes_moved=summary["bytes_moved"],
                collectives=summary["collectives"],
            )
        pairs.append(rec)

    backend = jax.devices()[0].platform
    return {
        "metric": "redistribute_bench",
        "backend": backend,
        "on_tpu": backend == "tpu",
        "n_devices": n,
        "pairs": pairs,
        "planned_resolved": sum(1 for p in pairs if p["path"] == "planned"),
        "fallbacks": sum(1 for p in pairs if p["path"] == "fallback"),
    }


def main() -> int:
    line = run_bench()
    for p in line["pairs"]:
        extra = f" hops={p.get('hops')} bytes={p.get('bytes_moved')}" if "hops" in p else ""
        print(
            f"[redistribute_bench] {p['name']:<28} path={p['path']:<8} "
            f"first={p['first_ms']:.1f}ms repeat={p['repeat_ms']:.2f}ms "
            f"retraces={p['retraces_on_repeat']}{extra} ok={p['ok']}",
            file=sys.stderr,
        )
    print(json.dumps(line))
    return 0 if all(p["ok"] for p in line["pairs"]) else 1


if __name__ == "__main__":
    sys.exit(main())
