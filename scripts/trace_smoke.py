#!/usr/bin/env python
"""Trace + calibration smoke — the acceptance run of ISSUE 9.

Two legs, one driver (self-spawning, the elastic_smoke.py shape):

  2-proc gloo rig (2 processes x 4 virtual CPU devices = one 8-way dp
  mesh): each rank estimates cross-rank clock offsets over the
  ``allgather_ints`` control plane (max residual skew printed), records a
  few steps of ndtimeline spans — including tagged send/recv pairs — into
  per-rank raw dumps, and runs the ``calibrate()`` collective sweep over
  the PROCESS-SPANNING mesh; rank 0 then merges both ranks' spans with the
  offsets into ONE Perfetto trace and validates it end to end (metadata
  events, monotonic aligned timestamps, flow pair, span round-trip), and
  persists ``collective_calibration.json``.

  driver leg (single process, same 8-device mesh shape): a 2-stage
  PipeEngine run must yield a NONZERO bubble fraction from its spans and a
  non-empty per-step critical path; the children's calibration table
  reloads into the redistribution planner (plan costs re-rank by measured
  wall-times; an EMPTY table prices bit-identically to the analytic
  model) and into ``estimate_stage_costs`` (measured-us stage costs with a
  nonzero p2p comm term for ``simulate_schedule``); the merged child trace
  feeds the telemetry registry and the ``trace:`` / ``critical-path:``
  dashboard blocks render.

Exit 0 on success, 1 with FAIL lines.  Wired into tier-1 via
tests/test_trace.py and into scripts/run_test.sh.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

WORLD = 2
STEPS = 4
TABLE = "collective_calibration.json"


# --------------------------------------------------------------------- child
def child(root: str) -> None:
    import jax

    jax.config.update("jax_platforms", "cpu")

    import numpy as np

    import vescale_tpu.distributed as vdist

    vdist.initialize()
    me = jax.process_index()
    assert jax.process_count() == WORLD

    import jax.numpy as jnp  # noqa: E402

    from vescale_tpu.mesh import DeviceMesh  # noqa: E402
    from vescale_tpu.ndtimeline import LocalRawHandler  # noqa: E402
    from vescale_tpu.ndtimeline.api import flush, init_ndtimers, ndtimeit  # noqa: E402
    from vescale_tpu.ndtimeline.predefined import TRAIN_STEP  # noqa: E402
    from vescale_tpu.telemetry import calibrate, trace  # noqa: E402

    ndev = len(jax.devices())
    mesh = DeviceMesh(("dp",), (ndev,))

    raw_path = os.path.join(root, f"spans_r{me}.jsonl")
    init_ndtimers(rank=me, mesh=mesh, handlers=[LocalRawHandler(raw_path)])

    # ---- clock sync over the control plane (every rank gets the vector).
    # 32 rounds: the offset is a median of entry skews, and on a loaded
    # host 8 of them leave it most of a millisecond from the truth
    cs = trace.estimate_clock_offsets(rounds=32)
    print(f"residual_us={cs.residual_us:.1f}")
    if me == 0:
        with open(os.path.join(root, "clock.json"), "w") as f:
            json.dump(cs.as_dict(), f)

    # ---- a few traced steps with a tagged send/recv pair per step
    from vescale_tpu.ndtimeline.api import get_manager

    for step in range(STEPS):
        with ndtimeit(TRAIN_STEP):
            # the barrier is INSIDE the span: neither rank leaves it before
            # both have entered, so the two spans share an instant by
            # construction and only a wrong offset can pull them apart (a
            # barrier before the span leaves that to the scheduler)
            vdist.barrier(f"trace_smoke_step{step}")
            x = jnp.sum(jnp.ones((128, 128)) * (step + 1))
            jax.block_until_ready(x)
            role = "send" if me == 0 else "recv"
            with ndtimeit(
                f"p2p-{role}",
                tags={"flow_id": f"f{step}", "flow_role": role, "peer": 1 - me},
            ):
                time.sleep(0.002)
        get_manager().inc_step()
    flush()

    # ---- measured-cost sweep over the process-spanning mesh
    table = calibrate.calibrate(mesh, byte_buckets=(1 << 12, 1 << 15), reps=2)
    if me == 0:
        path = table.save(os.path.join(root, TABLE))
        print(f"calibration_digest={table.digest()} entries={len(table)} path={path}")
    vdist.barrier("trace_smoke_calibrated")

    # ---- rank 0 merges both ranks' dumps into one aligned Perfetto trace
    if me == 0:
        from vescale_tpu.ndtimeline.parser_handler import parse_raw_spans
        from vescale_tpu.ndtimeline.world_info import WorldInfo

        streams = {
            r: parse_raw_spans(os.path.join(root, f"spans_r{r}.jsonl"))
            for r in range(WORLD)
        }
        assert all(streams.values()), "a rank produced no spans"
        merged = trace.merge_traces(streams, clock=cs)
        starts = [s.start for s in merged]
        assert starts == sorted(starts), "merged spans not monotonic"
        world_infos = {r: WorldInfo(rank=r, world_size=WORLD) for r in range(WORLD)}
        trace_path = trace.write_perfetto(
            merged, os.path.join(root, "trace.json"), world_infos=world_infos
        )
        doc = trace.load_perfetto(trace_path)
        meta = [e for e in doc["traceEvents"] if e.get("ph") == "M"]
        assert {e["pid"] for e in meta if e["name"] == "process_name"} == set(
            range(WORLD)
        ), "missing process_name metadata"
        flows = [e for e in doc["traceEvents"] if e.get("ph") in ("s", "f")]
        sids = {e["id"] for e in flows if e["ph"] == "s"}
        fids = {e["id"] for e in flows if e["ph"] == "f"}
        assert sids and sids == fids, f"unpaired flow events: s={sids} f={fids}"
        back = trace.spans_from_perfetto(trace_path)
        assert len(back) == len(merged), "span round-trip lost events"
        # both ranks' TRAIN_STEP spans for one step overlap after alignment
        # (each holds the step's barrier, so they overlap in truth; raw
        # clocks could legally disagree by more)
        by_step = {}
        for s in merged:
            if s.metric == TRAIN_STEP:
                by_step.setdefault(s.step, {})[s.rank] = s
        for step, cell in by_step.items():
            if len(cell) == WORLD:
                a, b = cell[0], cell[1]
                assert a.start < b.start + b.duration and b.start < a.start + a.duration, (
                    f"step {step} TRAIN_STEP spans do not overlap after alignment"
                )
        print(f"merged_trace_ok spans={len(merged)}")
    print(f"OK proc {me}")


# -------------------------------------------------------------------- driver
def run_rig(root: str, timeout=420):
    """2-proc gloo rig via the shared session-unique-port spawner with one
    bounded transport-setup retry (the PR-9 flake class); a retry restarts
    from an empty trace root."""
    import shutil

    from vescale_tpu.testing import make_child_env, run_gloo_world

    def spawn(port):
        procs = []
        for pid in range(WORLD):
            env = make_child_env(port, pid, WORLD, scrub=("VESCALE_COST_CALIBRATION",))
            procs.append(subprocess.Popen(
                [sys.executable, os.path.abspath(__file__), "--child", root],
                env=env, cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True,
            ))
        return procs

    def reset():
        shutil.rmtree(root, ignore_errors=True)
        os.makedirs(root, exist_ok=True)

    return run_gloo_world(spawn, timeout=timeout, on_retry=reset)


def check(failures, ok, label):
    print(("PASS" if ok else "FAIL") + f"  {label}")
    if not ok:
        failures.append(label)


def driver_leg(failures, root: str) -> None:
    """Single-process leg: pipe bubble fraction, planner/table reload,
    calibrated stage costs, dashboard blocks."""
    import jax
    import jax.numpy as jnp

    import vescale_tpu as vt
    from vescale_tpu import telemetry
    from vescale_tpu.mesh import DeviceMesh
    from vescale_tpu.models.nanogpt import (
        GPTConfig,
        cross_entropy_loss,
        gpt_pipeline_units,
    )
    from vescale_tpu.ndtimeline.api import flush, init_ndtimers
    from vescale_tpu.ndtimeline.parser_handler import parse_raw_spans
    from vescale_tpu.pipe import (
        PipeEngine,
        construct_pipeline_stage,
        estimate_stage_costs,
        one_f_one_b_schedule,
        simulate_schedule,
    )
    from vescale_tpu.placements import Replicate, Shard
    from vescale_tpu.plan import PipelineParallelPlan, PipelineScheduleType
    from vescale_tpu.redistribute_plan import clear_plan_cache, plan_redistribute
    from vescale_tpu.spec import DArraySpec, TensorMeta
    from vescale_tpu.telemetry import calibrate, trace

    # ---- 2-stage pipe: spans -> nonzero bubble fraction + critical path
    init_ndtimers(rank=0)
    cfg = GPTConfig(block_size=16, vocab_size=64, n_layer=4, n_head=2, n_embd=32,
                    dropout=0.0)
    plan = PipelineParallelPlan(num_stages=2,
                                schedule_type=PipelineScheduleType.SIMPLE_1F1B)
    pm = construct_pipeline_stage(gpt_pipeline_units(cfg), plan)
    params = pm.init_all(jax.random.key(0), jnp.ones((2, cfg.block_size), jnp.int32))
    engine = PipeEngine(pm, plan, cross_entropy_loss)
    engine.on_instruction = lambda ins, dt: None  # blocked mode: honest spans
    toks = jax.random.randint(jax.random.key(1), (8, cfg.block_size + 1), 0,
                              cfg.vocab_size)
    engine.forward_backward(params, {"input": toks[:, :-1], "target": toks[:, 1:]},
                            num_microbatches=4)
    pipe_spans = flush()
    bf = trace.bubble_fraction(pipe_spans)
    check(failures, bf is not None and 0.0 < bf < 1.0,
          f"2-stage pipe bubble fraction nonzero ({None if bf is None else round(bf, 3)})")
    cp = trace.critical_path(pipe_spans)
    check(failures, cp["n_spans"] > 1 and cp["total_ms"] > 0,
          f"critical path extracted ({cp['n_spans']} spans, {cp['total_ms']:.2f} ms)")

    # ---- calibration table -> planner (measured ranking, empty-table parity)
    mesh = DeviceMesh(("dp",), (8,))

    def spec(pl, shape=(64, 32)):
        p = vt.normalize_placements(pl, mesh.ndim, len(shape))
        return DArraySpec(mesh, p, TensorMeta(tuple(shape), jnp.dtype(jnp.float32)))

    src = spec([Shard(0)])
    dsts = {"all_to_all": spec([Shard(1)]), "all_gather": spec([Replicate()])}
    clear_plan_cache()
    analytic = {k: plan_redistribute(src, d).total_cost for k, d in dsts.items()}

    empty_path = calibrate.CalibrationTable(
        meta={"mesh": {"dim_names": ["dp"], "shape": [8]}}
    ).save(os.path.join(root, "empty_calibration.json"))
    os.environ["VESCALE_COST_CALIBRATION"] = empty_path
    clear_plan_cache()
    empty = {k: plan_redistribute(src, d).total_cost for k, d in dsts.items()}
    check(failures, empty == analytic,
          "EMPTY calibration table prices bit-identically to the analytic model")

    table_path = os.path.join(root, TABLE)
    os.environ["VESCALE_COST_CALIBRATION"] = table_path
    table = calibrate.load_table(table_path)
    clear_plan_cache()
    measured = {k: plan_redistribute(src, d).total_cost for k, d in dsts.items()}
    check(failures, all(measured[k] != analytic[k] for k in dsts),
          "calibrated planner costs differ from analytic")
    # ranking by MEASURED costs: the plan ordering must match the table's
    # own ordering of the two wire patterns at the per-rank operand
    # payload each actually moves (both ops contribute the source shard)
    shard_b = 64 * 32 * 4 // 8
    t_costs = {
        "all_to_all": table.lookup_us("all_to_all", 8, shard_b),
        "all_gather": table.lookup_us("all_gather", 8, shard_b),
    }
    same_order = (measured["all_to_all"] < measured["all_gather"]) == (
        t_costs["all_to_all"] < t_costs["all_gather"]
    )
    check(failures, same_order,
          f"planner ranks candidates by measured costs ({ {k: round(v, 1) for k, v in measured.items()} })")

    # ---- calibrated stage costs -> simulate_schedule
    os.environ.pop("VESCALE_COST_CALIBRATION", None)
    calibrate.reset_active()
    x = jnp.ones((2, cfg.block_size), jnp.int32)
    legacy = estimate_stage_costs(pm, params, x, comm=None)
    check(failures, legacy.comm == 0.0, "no table: comm=None degrades to legacy 0.0")
    calibrate.set_active(table)
    cal = estimate_stage_costs(pm, params, x, comm=None)
    mk = simulate_schedule(one_f_one_b_schedule(2, 4), cal)
    check(failures, cal.comm > 0 and mk > 0,
          f"calibrated stage costs: comm={cal.comm:.3f} us, 1F1B makespan={mk:.1f} us")
    calibrate.reset_active()
    os.environ.pop("VESCALE_COST_CALIBRATION", None)

    # ---- merged child trace -> registry -> dashboard blocks
    telemetry.init(out_dir=None, memtrack=False)
    with open(os.path.join(root, "clock.json")) as f:
        cs = trace.ClockSync.from_dict(json.load(f))
    streams = {r: parse_raw_spans(os.path.join(root, f"spans_r{r}.jsonl"))
               for r in range(WORLD)}
    merged = trace.merge_traces(streams, clock=cs)
    trace.record_trace_metrics(merged, clock=cs, bubble=bf, cp=cp)
    dash = telemetry.dashboard()
    telemetry.shutdown()
    check(failures, "trace:" in dash and "critical-path:" in dash,
          "dashboard renders trace: and critical-path: blocks")


def main() -> int:
    failures: list = []
    root = tempfile.mkdtemp(prefix="trace_smoke_")

    results = run_rig(root)
    for pid, (rc, out) in enumerate(results):
        check(failures, rc == 0 and f"OK proc {pid}" in out,
              f"rig proc {pid} completed")
        if rc != 0:
            print(out[-4000:])
    out0 = results[0][1]
    check(failures, "merged_trace_ok" in out0, "rig produced one merged perfetto trace")
    residuals = [l for l in out0.splitlines() if l.startswith("residual_us=")]
    check(failures, bool(residuals), "max residual skew reported")
    if residuals:
        print(f"  (clock {residuals[0]})")
    check(failures, os.path.exists(os.path.join(root, TABLE)),
          "calibration table written by the rig")

    if not failures:  # the driver leg needs the rig's artifacts
        if "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8"
            )
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        driver_leg(failures, root)

    if failures:
        print(f"\ntrace smoke: {len(failures)} FAILED")
        return 1
    print(f"\ntrace smoke: all checks passed (artifacts in {root})")
    return 0


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        child(sys.argv[2])
    else:
        sys.exit(main())
