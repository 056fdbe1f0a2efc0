"""Unified trace timeline + measured-cost calibration tests (ISSUE 9).

Covers telemetry/trace.py (clock sync, merging, perfetto round-trip,
critical path, bubble fraction), telemetry/calibrate.py (byte-bucket
interpolation, analytic fallback with a one-time warning, stale-table
detection, digest), the calibrated planner/cost-function/stage-cost wiring
(empty-table bit-parity, digest-keyed plan caches), the skew-corrected
StragglerDetector lag report, the steps.jsonl span summaries, and the
tier-1 wiring of scripts/trace_smoke.py.
"""

import json
import os
import subprocess
import sys
import warnings

import pytest
import jax
import jax.numpy as jnp

import vescale_tpu as vt
from vescale_tpu import telemetry
from vescale_tpu.mesh import DeviceMesh
from vescale_tpu.ndtimeline.timer import Span
from vescale_tpu.placements import Replicate, Shard
from vescale_tpu.redistribute_plan import clear_plan_cache, plan_redistribute
from vescale_tpu.spec import DArraySpec, TensorMeta
from vescale_tpu.telemetry import calibrate, trace
from vescale_tpu.telemetry.straggler import StragglerDetector

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_calibration(monkeypatch):
    """Every test starts in analytic mode with empty plan caches."""
    monkeypatch.delenv("VESCALE_COST_CALIBRATION", raising=False)
    calibrate.reset_active()
    clear_plan_cache()
    yield
    calibrate.reset_active()
    clear_plan_cache()


def _table(entries=(), mesh_shape=(8,), dim_names=("dp",), **meta):
    t = calibrate.CalibrationTable(
        meta={"mesh": {"dim_names": list(dim_names), "shape": list(mesh_shape)}, **meta}
    )
    for op, n, nbytes, seconds in entries:
        t.add_sample(op, n, nbytes, seconds)
    return t


# ===================================================== calibration table
def test_bucket_interpolation_log_log():
    t = _table([("all_gather", 8, 4096, 100e-6), ("all_gather", 8, 16384, 400e-6)])
    # log-log midpoint of (4096->100us, 16384->400us) at 8192 is 200us
    assert t.lookup_us("all_gather", 8, 8192) == pytest.approx(200.0, rel=1e-6)
    # endpoints answer exactly
    assert t.lookup_us("all_gather", 8, 4096) == pytest.approx(100.0)
    # outside the measured range: per-byte-rate extrapolation from the edge
    assert t.lookup_us("all_gather", 8, 2048) == pytest.approx(50.0)
    assert t.lookup_us("all_gather", 8, 32768) == pytest.approx(800.0)
    # missing (op, axis) has no answer at all
    assert t.lookup_us("all_reduce", 8, 4096) is None
    assert t.lookup_us("all_gather", 4, 4096) is None


def test_samples_running_mean_and_span_harvest():
    t = _table()
    t.add_sample("all_reduce", 2, 4096, 100e-6)
    t.add_sample("all_reduce", 2, 4096, 300e-6)
    assert t.lookup_us("all_reduce", 2, 4096) == pytest.approx(200.0)
    # harvest from a span stream honoring the tag contract; untagged
    # spans are ignored
    spans = [
        Span("calibrate-collective", 0.0, 50e-6, 0, 0,
             tags={"collective_op": "all_reduce", "axis_size": 2, "bytes": 4096}),
        Span("forward-compute", 0.0, 1.0, 0, 0, tags={"stage": 0}),
    ]
    assert t.ingest_spans(spans) == 1
    assert t.lookup_us("all_reduce", 2, 4096) == pytest.approx(150.0)


def test_save_load_digest(tmp_path):
    t = _table([("all_to_all", 8, 4096, 80e-6)])
    p = t.save(str(tmp_path / "cal.json"))
    t2 = calibrate.load_table(p)
    assert t2.digest() == t.digest()
    assert t2.lookup_us("all_to_all", 8, 4096) == pytest.approx(
        t.lookup_us("all_to_all", 8, 4096)
    )
    t2.add_sample("all_to_all", 8, 16384, 200e-6)
    assert t2.digest() != t.digest()  # content-addressed


def test_missing_bucket_falls_back_analytic_with_one_warning():
    from vescale_tpu import collectives as C

    analytic = C.allreduce_cost(4096 / 1e9, 8)
    calibrate.set_active(_table([("all_gather", 8, 4096, 100e-6)]))
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        v1 = C.allreduce_cost(4096 / 1e9, 8)  # no all_reduce bucket
        v2 = C.allreduce_cost(4096 / 1e9, 8)
    assert v1 == analytic and v2 == analytic  # bit-identical fallback
    assert len([x for x in w if "no measured bucket" in str(x.message)]) == 1
    # the measured op still answers from the table
    assert C.allgather_cost(4096 / 1e9, 8) == pytest.approx(100.0)


def test_stale_table_mesh_mismatch_warns_and_falls_back():
    mesh = DeviceMesh(("dp",), (8,))
    stale = _table([("all_gather", 8, 4096, 100e-6)], mesh_shape=(2, 4),
                   dim_names=("dp", "tp"))
    calibrate.set_active(stale)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert calibrate.table_for(mesh) is None
        assert calibrate.table_for(mesh) is None
    assert len([x for x in w if "stale table" in str(x.message)]) == 1
    # a mesh-less consumer (no staleness evidence) still gets measured data
    assert calibrate.collective_cost_us("all_gather", 8, 4096) == pytest.approx(100.0)


# ============================================= planner calibrated wiring
def _spec(mesh, placements, shape=(64, 32)):
    pl = vt.normalize_placements(placements, mesh.ndim, len(shape))
    return DArraySpec(mesh, pl, TensorMeta(tuple(shape), jnp.dtype(jnp.float32)))


def _mesh8():
    return DeviceMesh(("dp",), (min(8, len(jax.devices())),))


def test_planner_empty_table_bit_identical(tmp_path, monkeypatch):
    mesh = _mesh8()
    src, dst = _spec(mesh, [Shard(0)]), _spec(mesh, [Replicate()])
    analytic = plan_redistribute(src, dst).total_cost
    empty = calibrate.CalibrationTable(
        meta={"mesh": {"dim_names": list(mesh.mesh_dim_names),
                       "shape": list(mesh.shape)}}
    )
    monkeypatch.setenv("VESCALE_COST_CALIBRATION",
                       empty.save(str(tmp_path / "empty.json")))
    clear_plan_cache()
    assert plan_redistribute(src, dst).total_cost == analytic


def test_planner_recosts_by_measured_table_and_keys_cache(tmp_path, monkeypatch):
    mesh = _mesh8()
    n = mesh.shape[0]
    src, dst = _spec(mesh, [Shard(0)]), _spec(mesh, [Replicate()])
    analytic = plan_redistribute(src, dst).total_cost
    t = _table(
        [("all_gather", n, 1 << 10, 120e-6), ("all_gather", n, 1 << 14, 500e-6)],
        mesh_shape=mesh.shape, dim_names=mesh.mesh_dim_names,
    )
    monkeypatch.setenv("VESCALE_COST_CALIBRATION", t.save(str(tmp_path / "cal.json")))
    # NO clear_plan_cache: the calibration digest is part of the plan-cache
    # key, so arming the table must re-plan on its own
    measured = plan_redistribute(src, dst).total_cost
    assert measured != analytic
    # the hop price is the interpolated table point at the op's PER-RANK
    # operand payload (the table's key — a gather's contribution is the
    # source shard, not ring-scaled wire bytes or the gathered output) +
    # measured hop latency
    payload = src.meta.shape[0] * src.meta.shape[1] * 4 // n
    expect = t.lookup_us("all_gather", n, payload) + calibrate.hop_latency_us()
    assert measured == pytest.approx(expect, rel=1e-9)
    # disarming (env removal) returns the ANALYTIC plan bit-identically,
    # again without any cache clearing
    monkeypatch.delenv("VESCALE_COST_CALIBRATION")
    assert plan_redistribute(src, dst).total_cost == analytic


def test_quant_edge_competition_follows_measurements(monkeypatch):
    """The VSC127/128 quant-vs-dense competition re-ranks under measured
    costs: a table where the quant wire pattern (all_gather) measures slow
    flips a taken quant hop into a VSC127 decline, and vice versa."""
    from vescale_tpu.placements import Partial
    from vescale_tpu.redistribute_plan import quant_outcome

    monkeypatch.setenv("VESCALE_REDISTRIBUTE_QUANT", "1")
    mesh = DeviceMesh(("dp",), (2,))
    src = _spec(mesh, [Partial()], shape=(4096, 64))
    dst = _spec(mesh, [Replicate()], shape=(4096, 64))
    assert quant_outcome(src, dst)[0] == "taken"  # analytic verdict

    fast_gather = _table(
        [("all_gather", 2, 1 << 18, 10e-6), ("all_reduce", 2, 1 << 20, 0.1)],
        mesh_shape=(2,),
    )
    calibrate.set_active(fast_gather)
    clear_plan_cache()
    assert quant_outcome(src, dst)[0] == "taken"

    slow_gather = _table(
        [("all_gather", 2, 1 << 18, 0.1), ("all_reduce", 2, 1 << 20, 10e-6)],
        mesh_shape=(2,),
    )
    calibrate.set_active(slow_gather)
    clear_plan_cache()
    verdict, decline = quant_outcome(src, dst)
    assert verdict == "declined" and decline.code == "VSC127"


def test_redistribute_cost_consumes_table():
    mesh = _mesh8()
    n = mesh.shape[0]
    from vescale_tpu.collectives import redistribute_cost

    src, dst = _spec(mesh, [Shard(0)]), _spec(mesh, [Replicate()])
    analytic = redistribute_cost(src, dst)
    calibrate.set_active(_table(
        [("all_gather", n, 1 << 10, 5000e-6), ("all_gather", n, 1 << 20, 5.0)],
        mesh_shape=mesh.shape, dim_names=mesh.mesh_dim_names,
    ))
    assert redistribute_cost(src, dst) != analytic


def test_estimate_stage_costs_calibrated_and_legacy():
    from vescale_tpu.models.nanogpt import GPTConfig, gpt_pipeline_units
    from vescale_tpu.pipe import (
        construct_pipeline_stage,
        estimate_stage_costs,
        one_f_one_b_schedule,
        simulate_schedule,
    )
    from vescale_tpu.plan import PipelineParallelPlan

    cfg = GPTConfig(block_size=16, vocab_size=64, n_layer=2, n_head=2, n_embd=32,
                    dropout=0.0)
    pm = construct_pipeline_stage(gpt_pipeline_units(cfg), PipelineParallelPlan(num_stages=2))
    params = pm.init_all(jax.random.key(0), jnp.ones((2, 16), jnp.int32))
    x = jnp.ones((2, 16), jnp.int32)
    flops = estimate_stage_costs(pm, params, x)  # legacy default comm=0.0
    assert estimate_stage_costs(pm, params, x, comm=None) == flops  # no table
    calibrate.set_active(_table(
        [("ppermute", 2, 1 << 10, 30e-6)], matmul_gflops=100.0,
    ))
    cal = estimate_stage_costs(pm, params, x, comm=None)
    assert cal.comm > 0 and cal.f[0] == pytest.approx(flops.f[0] / (100.0 * 1e3))
    assert simulate_schedule(one_f_one_b_schedule(2, 4), cal) > 0
    # explicit comm= keeps full manual control even with a table armed
    assert estimate_stage_costs(pm, params, x, comm=0.0) == flops


# ======================================================== trace timeline
def test_clock_sync_single_process():
    cs = trace.estimate_clock_offsets(rounds=3)
    assert cs.offsets_us == [0.0] and cs.residual_us == 0.0
    cs2 = trace.ClockSync.from_dict(cs.as_dict())
    assert cs2.offsets_us == cs.offsets_us


def test_merge_traces_aligns_skewed_ranks():
    # rank 1's clock runs 5 s ahead; logically its span starts 12 ms after
    # rank 0's
    s0 = Span("a", 100.0, 0.010, 0, 0)
    s1 = Span("b", 105.012, 0.010, 0, 1)
    merged = trace.merge_traces([s0, s1], clock={1: 5.0})
    assert [s.metric for s in merged] == ["a", "b"]
    assert merged[1].start - merged[0].start == pytest.approx(0.012)
    # mapping form: the mapping's rank key wins over the span's own
    merged2 = trace.merge_traces({0: [s0], 1: [s1]},
                                 clock=trace.ClockSync([0.0, 5e6], 10.0, 4))
    assert merged2[1].start == pytest.approx(100.012)
    # inputs are not mutated
    assert s1.start == 105.012


def test_perfetto_round_trip_with_flows(tmp_path):
    path = str(tmp_path / "trace.json")
    spans = [
        Span("train-step", 10.0, 0.020, 0, 0),
        Span("p2p-send", 10.001, 0.002, 0, 0,
             tags={"flow_id": "f0", "flow_role": "send", "peer": 1}),
        Span("p2p-recv", 10.004, 0.002, 0, 1,
             tags={"flow_id": "f0", "flow_role": "recv", "peer": 0}),
        Span("forward-compute", 10.010, 0.004, 0, 1, tags={"stage": 1}),
    ]
    out = trace.write_perfetto(spans, path, process_names={0: "rank 0 [dp=0]"})
    doc = trace.load_perfetto(out)
    evs = doc["traceEvents"]
    # metadata: both pids named, stage lane named on rank 1
    pn = {e["pid"]: e["args"]["name"] for e in evs
          if e["ph"] == "M" and e["name"] == "process_name"}
    assert pn == {0: "rank 0 [dp=0]", 1: "rank 1"}
    tn = [e for e in evs if e["ph"] == "M" and e["name"] == "thread_name"]
    assert any(e["pid"] == 1 and e["args"]["name"] == "stage 1" for e in tn)
    # flow pair: s anchored at the send span's end, f at the recv start
    flow_s = next(e for e in evs if e["ph"] == "s")
    flow_f = next(e for e in evs if e["ph"] == "f")
    assert flow_s["id"] == flow_f["id"] == "f0" and flow_f.get("bp") == "e"
    assert flow_s["ts"] == pytest.approx(10.003 * 1e6)
    assert flow_f["ts"] == pytest.approx(10.004 * 1e6)
    # X events sorted and round-trippable back into spans
    xs = [e for e in evs if e["ph"] == "X"]
    assert [e["ts"] for e in xs] == sorted(e["ts"] for e in xs)
    back = trace.spans_from_perfetto(out)
    assert len(back) == len(spans)
    assert {(s.metric, s.rank) for s in back} == {(s.metric, s.rank) for s in spans}
    assert back[0].duration == pytest.approx(0.020)


def test_critical_path_terminates_on_zero_duration_spans():
    """Regression: a zero-duration span 'ends at or before' its own start
    and must not become its own predecessor (infinite chain)."""
    spans = [Span("a", 1.0, 0.5, 0, 0), Span("b", 2.0, 0.0, 0, 0)]
    cp = trace.critical_path(spans)
    assert [s.metric for s in cp["spans"]] == ["a", "b"]
    # two zero-duration spans at the same instant must not ping-pong
    cp2 = trace.critical_path([Span("x", 1.0, 0.0, 0, 0), Span("y", 1.0, 0.0, 0, 1)])
    assert cp2["n_spans"] <= 2


def test_critical_path_chain():
    # rank0: [0,10ms] -> gap -> rank1: [12,20ms] -> rank0: [20,30ms];
    # an overlapped short span must not enter the chain
    spans = [
        Span("a", 0.000, 0.010, 0, 0),
        Span("noise", 0.013, 0.002, 0, 0),
        Span("b", 0.012, 0.008, 0, 1),
        Span("c", 0.020, 0.010, 0, 0),
    ]
    cp = trace.critical_path(spans)
    assert [s.metric for s in cp["spans"]] == ["a", "b", "c"]
    assert cp["total_ms"] == pytest.approx(28.0)
    assert cp["window_ms"] == pytest.approx(30.0)
    assert cp["coverage"] == pytest.approx(28.0 / 30.0)
    by_step = trace.critical_paths_by_step(spans + [Span("d", 1.0, 0.001, 1, 0)])
    assert set(by_step) == {0, 1} and by_step[1]["n_spans"] == 1
    assert trace.critical_path([])["n_spans"] == 0


def test_bubble_fraction_from_stage_spans():
    # window 4 ms; stage 0 busy 4 ms, stage 1 busy 2 ms -> bubble 0.25
    spans = [
        Span("forward-compute", 0.000, 0.004, 0, 0, tags={"stage": 0}),
        Span("forward-compute", 0.001, 0.001, 0, 0, tags={"stage": 1}),
        Span("backward-compute", 0.003, 0.001, 0, 0, tags={"stage": 1}),
    ]
    assert trace.bubble_fraction(spans) == pytest.approx(0.25)
    # non-pipe spans alone yield no verdict
    assert trace.bubble_fraction([Span("train-step", 0, 1.0, 0, 0)]) is None
    # step filter
    assert trace.bubble_fraction(spans, step=3) is None


# ==================================================== straggler skew (sat)
def test_straggler_lag_report_skew_corrected():
    det = StragglerDetector(min_ranks=2, lag_threshold_ms=1.0)
    det.set_clock_offsets(trace.ClockSync([0.0, 5e6], residual_us=100.0, rounds=4))
    # rank 1's RAW starts are ~5 s ahead (clock skew), logically in step
    for step in range(6):
        t0 = step * 1.0
        det([
            Span("train-step", t0, 0.010, step, 0),
            Span("train-step", t0 + 5.0 + 0.0001, 0.010, step, 1),
        ])
    assert det.lag_report() == []  # skew corrected: no lag to flag

    # an ACTUAL 20 ms lag on rank 1 survives the correction and is flagged
    det2 = StragglerDetector(min_ranks=2, lag_threshold_ms=1.0)
    det2.set_clock_offsets({1: 5.0})
    for step in range(6):
        t0 = step * 1.0
        det2([
            Span("train-step", t0, 0.010, step, 0),
            Span("train-step", t0 + 5.0 + 0.020, 0.010, step, 1),
        ])
    flagged = det2.lag_report()
    assert [e["rank"] for e in flagged] == [1]
    assert flagged[0]["mean_lag_ms"] == pytest.approx(10.0, rel=0.2)  # vs median
    assert "starts" in det2.summary()
    # duration-based report is unaffected by start skew
    assert det2.report() == []


def test_straggler_lag_floor_is_clock_residual():
    det = StragglerDetector(min_ranks=2, lag_threshold_ms=1.0)
    det.set_clock_offsets(trace.ClockSync([0.0, 0.0], residual_us=50_000.0, rounds=2))
    for step in range(4):
        det([
            Span("train-step", step * 1.0, 0.010, step, 0),
            Span("train-step", step * 1.0 + 0.004, 0.010, step, 1),
        ])
    # 4 ms lag is real but BELOW the 50 ms clock residual: not a claim we
    # can honestly make
    assert det.lag_report() == []


# ============================================== telemetry surfaces (sat)
def test_record_step_embeds_span_summary(tmp_path):
    from vescale_tpu.ndtimeline.api import init_ndtimers, ndtimeit

    telemetry.init(out_dir=str(tmp_path), memtrack=False)
    init_ndtimers(rank=0)
    try:
        with ndtimeit("data-load"):
            pass
        with ndtimeit("data-load"):
            pass
        telemetry.record_step({"loss": 1.0, "step_time_s": 0.01})
        rec = json.loads(open(tmp_path / "steps.jsonl").read().splitlines()[0])
        assert rec["spans"]["data-load"]["count"] == 2
        assert rec["spans"]["data-load"]["total_ms"] >= 0
    finally:
        telemetry.shutdown()


def test_record_step_spans_survive_auto_inc_ordering(tmp_path):
    """Regression: make_train_step's auto_inc_step advances the ndtimeline
    counter BEFORE telemetry.record_step runs — the span rollup must
    summarize the step that just finished, not the (empty) next one."""
    from vescale_tpu.ndtimeline.api import get_manager, init_ndtimers, ndtimeit

    telemetry.init(out_dir=str(tmp_path), memtrack=False)
    init_ndtimers(rank=0)
    try:
        with ndtimeit("train-step"):
            pass
        get_manager().inc_step()  # auto_inc fires before record_step
        telemetry.record_step({"loss": 1.0})
        rec = json.loads(open(tmp_path / "steps.jsonl").read().splitlines()[0])
        assert rec["spans"]["train-step"]["count"] == 1
    finally:
        telemetry.shutdown()


def test_platform_mismatch_is_stale():
    """A table measured on another backend (gloo-CPU wall times consulted
    on TPU) must warn once and behave as absent — including for the
    mesh-less collectives.py cost functions."""
    from vescale_tpu import collectives as C

    analytic = C.allgather_cost(4096 / 1e9, 8)
    t = _table([("all_gather", 8, 4096, 100e-6)], platform="tpu")
    calibrate.set_active(t)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert C.allgather_cost(4096 / 1e9, 8) == analytic
        assert C.allgather_cost(4096 / 1e9, 8) == analytic
    assert len([x for x in w if "platform" in str(x.message)]) == 1


def test_record_trace_metrics_feeds_dashboard_blocks():
    telemetry.init(out_dir=None, memtrack=False)
    try:
        spans = [
            Span("forward-compute", 0.000, 0.004, 0, 0, tags={"stage": 0}),
            Span("forward-compute", 0.002, 0.001, 0, 1, tags={"stage": 1}),
        ]
        trace.record_trace_metrics(spans, clock=trace.ClockSync([0.0, 10.0], 25.0, 4))
        dash = telemetry.dashboard()
        assert "trace:" in dash and "critical-path:" in dash
        reg = telemetry.get_registry()
        assert reg.gauge("trace_clock_residual_us").value == 25.0
        assert reg.counter("trace_spans_merged_total").value == 2
        assert 0.0 < reg.gauge("trace_pipe_bubble_fraction").value < 1.0
    finally:
        telemetry.shutdown()


def test_active_digest_follows_the_armed_table():
    assert calibrate.active_digest() is None
    t = _table([("all_reduce", 8, 4096, 100e-6)])
    calibrate.set_active(t)
    assert calibrate.active_digest() == t.digest()


# ------------------------------------------------------------ smoke (CI)
def test_trace_smoke_script():
    """tier-1 wiring of scripts/trace_smoke.py (the ISSUE 9 acceptance
    run: merged aligned perfetto trace, calibration sweep -> planner)."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "trace_smoke.py")],
        capture_output=True,
        text=True,
        timeout=600,
        env={**{k: v for k, v in os.environ.items()
               if k != "VESCALE_COST_CALIBRATION"}, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, (
        f"stdout:\n{out.stdout[-3000:]}\nstderr:\n{out.stderr[-3000:]}"
    )
    assert "trace smoke: all checks passed" in out.stdout
