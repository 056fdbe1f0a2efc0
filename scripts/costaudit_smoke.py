#!/usr/bin/env python
"""Cost-audit smoke — the acceptance run of ISSUE 18.

Every priced decision joined to its measured outcome, end to end:

  1. TRAIN leg: a deliberately skewed calibration table makes the
     redistribution planner pick a cheap-by-lie gather route; the audited
     execution measures the real wall time, the divergence gauge blows
     past the threshold (``cost-model-drift`` fires), the harvest folds
     the honest numbers back into the table, the digest rotates, and the
     next plan lookup self-heals onto the direct route.  steps.jsonl
     carries the ``cost_audit`` join and the dashboard renders the
     ``cost-model:`` block.
  2. SERVE leg: a tiny CPU serve loop under ``run_serve_resilient`` — the
     per-step scheduler estimate joins the ledger against measured decode
     wall times (nonzero matched on serve steps.jsonl lines), and the
     tagged prefill/decode spans harvest into the active table
     (``serve_decode`` buckets appear, feeding the calibrated step
     estimate).
  3. WHAT-IF: the scorer ranks >= 3 (dp, tp, pp) layouts by predicted
     step time with audit-backed confidence.
  4. DORMANT leg: with the auditor off, the module hooks are the named
     no-ops, plans carry no ledger id, and steps.jsonl lines are
     bit-identical to an un-audited run (no ``cost_audit`` key).

Exit 0 on success, 1 with a FAIL line per broken check.  Wired into
scripts/run_test.sh and tier-1 via tests/test_costaudit.py.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
# pin the audit cadences so a 4-step smoke samples + evaluates every step
os.environ.setdefault("VESCALE_TIMESERIES_CADENCE_S", "0")
os.environ.setdefault("VESCALE_ALERTS_EVAL_INTERVAL_S", "0")
os.environ.setdefault("VESCALE_COSTAUDIT_DECAY", "0.9")
os.environ.setdefault("VESCALE_REDISTRIBUTE_MEM_FACTOR", "16")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def check(failures, ok: bool, label: str) -> None:
    print(("PASS" if ok else "FAIL") + f"  {label}")
    if not ok:
        failures.append(label)


def train_leg(failures, out_dir: str) -> None:
    """Skewed table -> mis-ranked plan -> drift fires -> self-heal."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import vescale_tpu as vt
    from vescale_tpu import telemetry
    from vescale_tpu.mesh import DeviceMesh
    from vescale_tpu.ndtimeline import api as nd
    from vescale_tpu.placements import Shard
    from vescale_tpu.redistribute_plan import clear_plan_cache, plan_redistribute
    from vescale_tpu.spec import DArraySpec, TensorMeta
    from vescale_tpu.telemetry import calibrate as cal
    from vescale_tpu.telemetry import costaudit

    mesh = DeviceMesh(("x",), (8,))
    shape = (2048, 2048)  # per-shard 2 MiB: an exact power-of-2 bucket

    table = cal.CalibrationTable()
    table.add_sample("all_gather", 8, 2 * 1024 * 1024, 1e-9)  # the lie
    table.meta = {"platform": "cpu", "mesh": {"dim_names": ["x"], "shape": [8]}}
    cal.set_active(table)
    digest0 = cal.active_digest()

    nd.init_ndtimers(rank=0)
    telemetry.init(out_dir=out_dir, memtrack=False)
    eng = telemetry.get_state().alerts
    clear_plan_cache()

    meta = TensorMeta(shape, jnp.dtype(jnp.float32))
    src = DArraySpec(mesh, vt.normalize_placements([Shard(0)], 1, 2), meta)
    dst = DArraySpec(mesh, vt.normalize_placements([Shard(1)], 1, 2), meta)
    plan1 = plan_redistribute(src, dst)
    check(failures, plan1 is not None and plan1.plan_id is not None,
          "train: plan priced into the ledger")
    check(failures, any("all_gather" in h.collectives for h in plan1.hops),
          "train: skewed table mis-ranks onto the gather route")

    xnp = np.arange(shape[0] * shape[1], dtype=np.float32).reshape(shape)
    out = plan1.execute(vt.distribute_tensor(xnp, mesh, [Shard(0)]).data)
    check(failures, np.array_equal(np.asarray(out), xnp),
          "train: audited execution is value-exact")
    telemetry.record_step({"loss": 1.0, "step_time_s": 0.1})

    summ = costaudit.audit_summary()
    check(failures, summ["matched"] >= 1, "train: prediction joined to outcome")
    check(failures, (summ["divergence"] or 0) > 3.0,
          "train: divergence detected (measured >> predicted)")
    check(failures, "cost-model-drift" in (eng.firing() if eng else []),
          "train: cost-model-drift alert fired")
    check(failures, summ["digest_rotations"] >= 1 and cal.active_digest() != digest0,
          "train: harvest rotated the table digest")
    dash = telemetry.dashboard() or ""
    check(failures, "cost-model" in dash, "train: dashboard cost-model block")

    plan2 = plan_redistribute(src, dst)
    check(failures,
          plan2 is not None and plan2 is not plan1
          and not any("all_gather" in h.collectives for h in plan2.hops),
          "train: re-plan self-heals onto the direct route")
    telemetry.shutdown()
    cal.reset_active()
    clear_plan_cache()

    lines = [json.loads(line) for line in open(os.path.join(out_dir, "steps.jsonl"))]
    check(failures, any(
        (line.get("cost_audit") or {}).get("matched", 0) >= 1 for line in lines
    ), "train: steps.jsonl carries the cost_audit join")


def serve_leg(failures, out_dir: str) -> None:
    """The serve loop's predictions join the ledger; its tagged spans
    harvest into the active table."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from vescale_tpu import telemetry
    from vescale_tpu.mesh import DeviceMesh
    from vescale_tpu.models.llama import Llama, LlamaConfig
    from vescale_tpu.ndtimeline import api as nd
    from vescale_tpu.serve import (
        ContinuousBatchingScheduler,
        KVCacheConfig,
        PagedKVCache,
        Request,
        ServeEngine,
        run_serve_resilient,
    )
    from vescale_tpu.serve import obs as serve_obs
    from vescale_tpu.telemetry import calibrate as cal

    cfg = LlamaConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=8, num_key_value_heads=8,
        max_position_embeddings=64, dtype=jnp.float32,
    )
    model = Llama(cfg)
    params = model.init(jax.random.key(0), jnp.ones((1, 8), jnp.int32))["params"]
    mesh = DeviceMesh(("tp",), (len(jax.devices()),))
    kc = KVCacheConfig(
        layers=cfg.num_hidden_layers, kv_heads=cfg.num_key_value_heads,
        head_dim=cfg.head_dim, num_slots=2, page_size=4, pages_per_slot=4,
    )
    cache = PagedKVCache(kc, mesh)
    eng = ServeEngine(cfg, mesh, params, cache)
    sched = ContinuousBatchingScheduler(cache, max_queue=8)

    cal.set_active(cal.CalibrationTable())  # the harvest sink
    nd.init_ndtimers(rank=0)
    telemetry.init(out_dir=out_dir, memtrack=False)

    rng = np.random.default_rng(7)
    arrivals = [
        (2 * i, Request(rid=i, prompt=tuple(int(x) for x in rng.integers(1, 120, 3)),
                        max_new_tokens=4, deadline_steps=60))
        for i in range(4)
    ]
    run_serve_resilient(
        engine=eng, scheduler=sched, arrivals=arrivals,
        install_signal_handlers=False, coordinate=False,
    )
    table = cal.active_table()
    check(failures, table is not None and table.op_estimate_us("serve_decode") is not None,
          "serve: decode spans harvested into the table")
    est = serve_obs.ServeObservability(sched).calibrated_step_estimate()
    check(failures, est is not None and est > 0,
          "serve: calibrated step estimate reads the audited table")
    telemetry.shutdown()
    cal.reset_active()

    serve_lines = [
        json.loads(line) for line in open(os.path.join(out_dir, "steps.jsonl"))
        if '"kind": "serve"' in line
    ]
    check(failures, bool(serve_lines), "serve: steps.jsonl has serve lines")
    joined = [line for line in serve_lines
              if (line.get("cost_audit") or {}).get("by_kind", {})
              .get("serve_step", {}).get("matched", 0) >= 1]
    check(failures, bool(joined),
          "serve: per-step predictions joined to measured wall times")


def whatif_leg(failures) -> None:
    from vescale_tpu.telemetry import costaudit

    import types

    ranked = costaudit.score_candidates(
        costaudit.mesh_candidates(8),
        params_bytes=1e9, activation_bytes=1e8, flops_per_step=1e12,
        device=types.SimpleNamespace(device_kind="TPU v5 lite"),  # a chip the peak table lists
    )
    check(failures, len(ranked) >= 3, "whatif: >= 3 candidate layouts scored")
    costs = [r["predicted_step_us"] for r in ranked]
    check(failures, costs == sorted(costs), "whatif: ranked by predicted step time")
    check(failures, all(0.0 <= r["confidence"] <= 1.0 for r in ranked),
          "whatif: confidence bounded to [0, 1]")


def dormant_leg(failures, out_dir: str) -> None:
    import jax.numpy as jnp
    import numpy as np

    import vescale_tpu as vt
    from vescale_tpu import telemetry
    from vescale_tpu.mesh import DeviceMesh
    from vescale_tpu.placements import Shard
    from vescale_tpu.redistribute_plan import clear_plan_cache, plan_redistribute
    from vescale_tpu.spec import DArraySpec, TensorMeta
    from vescale_tpu.telemetry import costaudit

    check(failures, costaudit.record_prediction is costaudit._noop_record_prediction
          and costaudit.audit_step is costaudit._noop_audit_step,
          "dormant: hot hooks are the module-level no-ops")

    telemetry.init(out_dir=out_dir, memtrack=False, costaudit=False)
    clear_plan_cache()
    mesh = DeviceMesh(("x",), (8,))
    meta = TensorMeta((2048, 2048), jnp.dtype(jnp.float32))
    src = DArraySpec(mesh, vt.normalize_placements([Shard(0)], 1, 2), meta)
    dst = DArraySpec(mesh, vt.normalize_placements([Shard(1)], 1, 2), meta)
    plan = plan_redistribute(src, dst)
    check(failures, plan is not None and plan.plan_id is None,
          "dormant: plans carry no ledger id")
    xnp = np.arange(2048 * 2048, dtype=np.float32).reshape(2048, 2048)
    out = plan.execute(vt.distribute_tensor(xnp, mesh, [Shard(0)]).data)
    check(failures, np.array_equal(np.asarray(out), xnp),
          "dormant: un-audited execution is value-exact")
    telemetry.record_step({"loss": 1.0, "step_time_s": 0.1})
    telemetry.shutdown()
    clear_plan_cache()
    lines = [json.loads(line) for line in open(os.path.join(out_dir, "steps.jsonl"))]
    check(failures, all("cost_audit" not in line for line in lines),
          "dormant: steps.jsonl bit-identical (no cost_audit key)")


def main() -> int:
    failures: list = []
    root = tempfile.mkdtemp(prefix="costaudit_smoke_")

    train_leg(failures, os.path.join(root, "train"))
    serve_leg(failures, os.path.join(root, "serve"))
    whatif_leg(failures)
    dormant_leg(failures, os.path.join(root, "dormant"))

    if failures:
        print(f"\ncost-audit smoke: {len(failures)} FAILED")
        return 1
    print(f"\ncost-audit smoke: all checks passed (artifacts in {root})")
    print("COSTAUDIT SMOKE OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
