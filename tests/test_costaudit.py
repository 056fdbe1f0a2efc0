"""Plan-vs-reality cost auditing (telemetry/costaudit.py — ISSUE 18).

The tentpole contract end to end: the bounded prediction ledger and its
divergence folds, the dormant-path identity no-ops, the online calibration
harvest (explicit spans + high-water mark + digest rotation), atomic table
persistence, the per-layer roofline attribution over HLO text, the what-if
(dp, tp, pp) scorer with audit-backed confidence, the ``cost-model-drift``
rule pack, the VSC208 lint rule, the steps.jsonl/dashboard surfaces, and —
on the 2-process gloo rig — the full divergence-driven replan loop (skewed
table mis-ranks a redistribution, the auditor detects it, recalibration
rotates the digest, and the planner self-heals onto the honest route).
"""

import json
import pathlib
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from vescale_tpu import telemetry
from vescale_tpu.redistribute_plan import clear_plan_cache
from vescale_tpu.telemetry import calibrate as cal
from vescale_tpu.telemetry import costaudit
from vescale_tpu.telemetry.calibrate import CalibrationTable, load_table
from vescale_tpu.testing import make_child_env, run_gloo_world


@pytest.fixture(autouse=True)
def _reset():
    yield
    telemetry.shutdown()
    cal.reset_active()
    clear_plan_cache()


def _span(op, axis, nbytes, dur_s, start):
    return types.SimpleNamespace(
        tags={"collective_op": op, "axis_size": axis, "bytes": nbytes},
        start=start, duration=dur_s,
    )


# ================================================================ dormant
def test_dormant_hooks_are_module_noops(tmp_path):
    assert not costaudit.is_active()
    assert costaudit.record_prediction is costaudit._noop_record_prediction
    assert costaudit.record_measurement is costaudit._noop_record_measurement
    assert costaudit.audit_step is costaudit._noop_audit_step
    assert costaudit.harvest is costaudit._noop_harvest
    assert costaudit.record_prediction("x", predicted_us=1.0) is None
    assert costaudit.record_measurement(7, measured_us=1.0) is None
    assert costaudit.audit_step("train") is None
    assert costaudit.harvest() == 0
    assert costaudit.audit_summary() is None
    assert costaudit.get_auditor() is None


def test_empty_ledger_step_record_is_bit_identical(tmp_path):
    """An armed auditor that never saw a prediction or a tagged span must
    leave the steps.jsonl line byte-compatible with an un-audited run."""
    telemetry.init(out_dir=str(tmp_path / "run"), memtrack=False)
    telemetry.record_step({"loss": 1.0, "step_time_s": 0.1})
    telemetry.shutdown()
    line = json.loads(
        (tmp_path / "run" / "steps.jsonl").read_text().splitlines()[0]
    )
    assert "cost_audit" not in line


# ================================================================= ledger
def test_ledger_join_and_decayed_divergence():
    telemetry.init(out_dir=None, memtrack=False)
    a = costaudit.get_auditor()
    assert a is not None and costaudit.is_active()

    pid = costaudit.record_prediction("redistribute", predicted_us=100.0)
    assert isinstance(pid, int)
    assert costaudit.record_measurement(pid, measured_us=200.0) == pytest.approx(2.0)
    s = a.summary()
    assert s["predictions"] == 1 and s["matched"] == 1
    assert s["divergence"] == pytest.approx(2.0)  # first fold seeds the mean

    pid2 = costaudit.record_prediction("redistribute", predicted_us=100.0)
    costaudit.record_measurement(pid2, measured_us=400.0)
    s = a.summary()
    # decayed mean: strictly between the old mean and the new ratio
    assert 2.0 < s["divergence"] < 4.0
    assert s["by_kind"]["redistribute"]["matched"] == 2

    # unknown / expired / None ids are ignored, not errors
    assert costaudit.record_measurement(None, measured_us=1.0) is None
    assert costaudit.record_measurement(10**9, measured_us=1.0) is None


def test_bytes_unit_divergence_for_aot_predictions():
    telemetry.init(out_dir=None, memtrack=False)
    pid = costaudit.record_prediction(
        "aot_memory", predicted_bytes=100.0, unit="bytes")
    assert costaudit.record_measurement(pid, measured_bytes=150.0) == pytest.approx(1.5)
    # weighted_bytes plans (analytic mode) are matched but never ratioed
    pid2 = costaudit.record_prediction(
        "redistribute", predicted_bytes=10.0, unit="weighted_bytes")
    assert costaudit.record_measurement(pid2, measured_us=5.0) is None
    s = costaudit.audit_summary()
    assert s["matched"] == 2
    assert s["by_kind"]["redistribute"]["divergence"] is None


def test_ledger_ring_is_bounded(monkeypatch):
    monkeypatch.setenv("VESCALE_COSTAUDIT_DEPTH", "4")
    telemetry.init(out_dir=None, memtrack=False)
    pids = [costaudit.record_prediction("k", predicted_us=1.0) for _ in range(6)]
    s = costaudit.audit_summary()
    assert s["predictions"] == 6 and s["ledger_depth"] == 4
    # the two oldest fell off the ring: their measurements are dropped
    assert costaudit.record_measurement(pids[0], measured_us=2.0) is None
    assert costaudit.record_measurement(pids[-1], measured_us=2.0) == pytest.approx(2.0)


def test_audit_step_publishes_gauges_and_jsonl(tmp_path):
    telemetry.init(out_dir=str(tmp_path / "run"), memtrack=False)
    pid = costaudit.record_prediction("pipe_schedule", predicted_us=10.0)
    costaudit.record_measurement(pid, measured_us=30.0)
    telemetry.record_step({"loss": 1.0})
    reg = telemetry.get_registry()
    assert reg.gauge("cost_model_divergence").value == pytest.approx(3.0)
    assert reg.gauge("cost_model_unmatched").value == 0.0
    dash = telemetry.dashboard()
    assert "cost-model" in dash
    telemetry.shutdown()
    line = json.loads(
        (tmp_path / "run" / "steps.jsonl").read_text().splitlines()[0]
    )
    assert line["cost_audit"]["matched"] == 1
    assert line["cost_audit"]["divergence"] == pytest.approx(3.0)


# ==================================================== calibration harvest
def test_harvest_explicit_spans_hwm_and_digest_rotation():
    telemetry.init(out_dir=None, memtrack=False)
    a = costaudit.get_auditor()
    t = CalibrationTable()
    t.add_sample("all_gather", 8, 1 << 20, 100e-6)
    cal.set_active(t)
    d0 = t.digest()

    spans = [_span("all_gather", 8, 1 << 20, 300e-6, start=10.0),
             _span("unrelated", 8, 1 << 20, 1.0, start=11.0)]
    spans[1].tags = {"note": "no harvest contract"}
    assert a.harvest(spans) == 1
    assert t.digest() != d0
    assert a.summary()["digest_rotations"] == 1
    # per-bucket divergence noted against the table's prior estimate
    div = a.bucket_divergence()
    assert div[("all_gather", 8, 1 << 20)]["ratio"] == pytest.approx(3.0)

    # the high-water mark: re-offering the same spans ingests nothing
    assert a.harvest(spans) == 0
    assert a.harvest([_span("all_gather", 8, 1 << 20, 300e-6, start=12.0)]) == 1


def test_persist_roundtrip_and_op_estimate(tmp_path):
    t = CalibrationTable()
    t.add_sample("all_gather", 8, 1 << 20, 100e-6)
    t.add_sample("all_gather", 8, 1 << 22, 400e-6)
    t.meta = {"platform": "cpu"}
    path = tmp_path / "tab" / "cal.json"
    path.parent.mkdir()
    t.save(str(path))
    # atomic write: no tmp residue next to the target
    assert [p.name for p in path.parent.iterdir()] == ["cal.json"]
    t2 = load_table(str(path))
    assert t2.digest() == t.digest()
    assert t2.lookup_us("all_gather", 8, 1 << 20) == pytest.approx(
        t.lookup_us("all_gather", 8, 1 << 20))
    # op_estimate_us: sample-weighted mean over the op's buckets
    est = t2.op_estimate_us("all_gather")
    assert est == pytest.approx((100.0 + 400.0) / 2)
    assert t2.op_estimate_us("ppermute") is None


def test_harvest_persists_on_cadence(tmp_path, monkeypatch):
    out = tmp_path / "cal.json"
    monkeypatch.setenv("VESCALE_COST_CALIBRATION", str(out))
    monkeypatch.setenv("VESCALE_COSTAUDIT_CADENCE_S", "0")
    telemetry.init(out_dir=None, memtrack=False)
    a = costaudit.get_auditor()
    t = CalibrationTable()
    cal.set_active(t)
    assert a.harvest([_span("all_reduce", 4, 1 << 16, 50e-6, start=1.0)]) == 1
    assert out.exists()
    assert load_table(str(out)).lookup_us("all_reduce", 4, 1 << 16) == pytest.approx(50.0)


# ============================================================== rule pack
def test_drift_rule_pack_shape():
    rules = costaudit.costaudit_rule_pack(5.0)
    assert len(rules) == 1
    r = rules[0]
    assert r.name == "cost-model-drift"
    assert r.metric == "cost_model_divergence"
    assert r.threshold == 5.0 and r.severity == "warning"


# ======================================================= roofline layers
_HLO = """\
HloModule step
ENTRY %main {
  %p0 = f32[1024,1024]{1,0} parameter(0)
  %p1 = f32[1024,1024]{1,0} parameter(1)
  %dot.1 = f32[1024,1024]{1,0} dot(%p0, %p1), metadata={op_name="jit(step)/model/attn/q_proj/dot_general"}
  %add.2 = f32[1024,1024]{1,0} add(%dot.1, %p0), metadata={op_name="jit(step)/model/mlp/residual/add"}
  ROOT %tanh.3 = f32[1024,1024]{1,0} tanh(%add.2), metadata={op_name="jit(step)/model/mlp/act/tanh"}
}
"""


def test_layer_attribution_classifies_against_roofline():
    att = costaudit.layer_attribution(_HLO, peak_flops=1e12, mem_gbps=100.0)
    by = {l["layer"]: l for l in att["layers"]}
    assert set(by) == {"model/attn", "model/mlp"}
    # the matmul: 2 * 1024^2 * 1024 flops, intensity far above ridge=10
    assert by["model/attn"]["flops"] == pytest.approx(2.0 * 1024**3)
    assert by["model/attn"]["bound"] == "compute"
    # elementwise ops: zero modeled flops -> memory-bound
    assert by["model/mlp"]["flops"] == 0.0
    assert by["model/mlp"]["bound"] == "memory"
    assert by["model/mlp"]["ops"] == 2
    assert att["total_flops"] == pytest.approx(2.0 * 1024**3)
    # est_us-descending ordering
    est = [l["est_us"] for l in att["layers"]]
    assert est == sorted(est, reverse=True)


def test_roofline_counter_tracks_attach_to_perfetto(tmp_path):
    att = costaudit.layer_attribution(_HLO, peak_flops=1e12, mem_gbps=100.0)
    evs = costaudit.roofline_counter_events(att)
    assert {e["ph"] for e in evs} == {"C"}
    assert any(e["name"] == "roofline:model/attn" for e in evs)
    trace = tmp_path / "trace.json"
    trace.write_text(json.dumps({"traceEvents": [{"ph": "M", "pid": 0}]}))
    costaudit.attach_roofline_tracks(str(trace), att)
    merged = json.loads(trace.read_text())
    assert len(merged["traceEvents"]) == 1 + len(evs)


# ========================================================= what-if scorer
# compute is priced from a published peak, so the scorer needs a device the
# peak table lists (the CPU the tests run on is not one)
_V5E = types.SimpleNamespace(device_kind="TPU v5 lite")


def test_device_peaks_of_the_benchmark_chip():
    from vescale_tpu.telemetry import calibrate

    assert calibrate.device_peak_flops(_V5E) == 197e12
    assert costaudit.device_mem_gbps(_V5E) == 819.0
    assert calibrate.device_peaks(_V5E)["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v5", "tpu v5 lite", None])
def test_device_peaks_raise_on_unknown_device_kind(kind):
    """No default peak: a device the table does not list is an error for
    every reader of it (the local CPU included)."""
    from vescale_tpu.telemetry import calibrate

    dev = types.SimpleNamespace(device_kind=kind, platform="tpu")
    with pytest.raises(ValueError, match="no published peak"):
        calibrate.device_peak_flops(dev)
    with pytest.raises(ValueError, match="no published peak"):
        costaudit.device_mem_gbps(dev)
    with pytest.raises(ValueError, match="no published peak"):
        costaudit.score_candidates([(8, 1, 1)], params_bytes=1e9, activation_bytes=1e8,
                                   flops_per_step=1e12, device=dev)


def test_mesh_candidates_enumerate_factorizations():
    cands = costaudit.mesh_candidates(8)
    assert (1, 8, 1) in cands and (8, 1, 1) in cands and (2, 2, 2) in cands
    assert all(dp * tp * pp == 8 for dp, tp, pp in cands)


def test_score_candidates_ranks_and_confidence_tiers():
    ranked = costaudit.score_candidates(
        costaudit.mesh_candidates(8),
        params_bytes=1e9, activation_bytes=1e8, flops_per_step=1e12, device=_V5E,
    )
    assert len(ranked) >= 3
    costs = [r["predicted_step_us"] for r in ranked]
    assert costs == sorted(costs)
    # no table: every comm term prices analytically at baseline confidence
    scored = [r for r in ranked if r["terms"]]
    assert scored and all(
        t["source"] == "analytic" for r in scored for t in r["terms"])
    assert all(r["confidence"] == pytest.approx(0.25) for r in scored)

    # a measured (un-audited) table lifts matching terms to 0.5
    t = CalibrationTable()
    for nb in (1 << 20, 1 << 24, 1 << 27, 1 << 28):
        t.add_sample("all_reduce", 8, nb, 1e-3)
    dp8 = next(r for r in costaudit.score_candidates(
        [(8, 1, 1)], params_bytes=1e9, activation_bytes=1e8,
        flops_per_step=1e12, table=t, device=_V5E) if r["terms"])
    assert dp8["terms"][0]["source"] == "measured"
    assert dp8["confidence"] == pytest.approx(0.5)


def test_whatif_cli_ranks_meshes(tmp_path):
    t = CalibrationTable()
    t.add_sample("all_reduce", 8, 1 << 27, 2e-3)
    tab = tmp_path / "cal.json"
    t.save(str(tab))
    out = subprocess.run(
        [sys.executable, "-m", "vescale_tpu.analysis", "--json", "whatif",
         "--devices", "8", "--table", str(tab), "--device", _V5E.device_kind],
        capture_output=True, text=True, timeout=300,
        cwd=pathlib.Path(__file__).resolve().parent.parent,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rep = json.loads(out.stdout)
    assert rep["num_devices"] == 8
    assert len(rep["candidates"]) >= 3
    costs = [c["predicted_step_us"] for c in rep["candidates"]]
    assert costs == sorted(costs)


# ==================================================== serve-side hinting
def test_scheduler_step_time_estimate_seed_then_p50():
    from vescale_tpu.mesh import DeviceMesh
    from vescale_tpu.serve import (
        ContinuousBatchingScheduler,
        KVCacheConfig,
        PagedKVCache,
    )

    kc = KVCacheConfig(layers=1, kv_heads=2, head_dim=4, num_slots=1,
                       page_size=4, pages_per_slot=2)
    sched = ContinuousBatchingScheduler(PagedKVCache(kc, DeviceMesh(("tp",), (2,))))
    assert sched.step_time_estimate() is None  # cold: nothing to predict
    sched.seed_step_time(0.5)
    assert sched.step_time_estimate() == pytest.approx(0.5)
    for _ in range(32):
        sched.observe_step_time(0.25)
    assert sched.step_time_estimate() == pytest.approx(0.25, rel=0.2)


def test_suggested_drafter_depth_from_audited_table():
    from vescale_tpu.serve.speculative import suggested_k

    assert suggested_k(CalibrationTable()) is None  # no serve measurements
    t = CalibrationTable()
    t.add_sample("serve_decode", 4, 4, 1000e-6)
    t.add_sample("serve_draft", 2, 1, 20e-6)  # 10us per launch at depth 1
    assert suggested_k(t) == 8  # deep drafts pay off: clamp at 8
    t2 = CalibrationTable()
    t2.add_sample("serve_decode", 4, 4, 30e-6)
    t2.add_sample("serve_draft", 2, 1, 20e-6)
    assert suggested_k(t2) == 1  # barely worth one draft


# ================================================================== lint
def test_vsc208_priced_decision_without_audit(tmp_path):
    from vescale_tpu.analysis.lint import lint_paths

    pkg = tmp_path / "vescale_tpu"
    pkg.mkdir()
    bad = pkg / "chooser.py"
    bad.write_text(
        "def choose(stages):\n"
        "    costs = estimate_stage_costs(stages)\n"
        "    return min(costs)\n"
    )
    rep = lint_paths([str(bad)])
    assert "VSC208" in rep.codes()

    good = pkg / "audited.py"
    good.write_text(
        "def choose(stages, ca):\n"
        "    costs = estimate_stage_costs(stages)\n"
        "    ca.record_prediction('pipe', predicted_us=min(costs))\n"
        "    return min(costs)\n"
    )
    assert "VSC208" not in lint_paths([str(good)]).codes()

    # out-of-package inspectors (tests, scripts) are exempt
    outside = tmp_path / "test_chooser.py"
    outside.write_text(bad.read_text())
    assert "VSC208" not in lint_paths([str(outside)]).codes()


# ========================================================== gloo rig e2e
def _spawn_two_process_worker(worker_name, tmp_path, extra_env=None):
    repo = pathlib.Path(__file__).resolve().parent.parent
    worker = repo / "tests" / "multiproc" / worker_name
    ckpt_root = tmp_path / "ckpt"

    def spawn(port):
        return [
            subprocess.Popen(
                [sys.executable, str(worker), str(ckpt_root)],
                env=make_child_env(port, pid, 2, extra=dict(extra_env or {})),
                cwd=str(repo),
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            )
            for pid in range(2)
        ]

    return run_gloo_world(
        spawn, timeout=420,
        on_retry=lambda: shutil.rmtree(ckpt_root, ignore_errors=True),
        transport_retries=1,
    )


@pytest.mark.slow
def test_two_process_divergence_driven_replan(tmp_path):
    """ISSUE 18 acceptance: a skewed calibration table mis-ranks a
    redistribution, the audited execution detects the divergence across a
    real process boundary (``cost-model-drift`` fires on both ranks), the
    harvest rotates the table digest, and the next plan lookup re-plans
    onto the honest direct route — with bit-exact values throughout."""
    results = _spawn_two_process_worker(
        "worker_costaudit.py", tmp_path,
        extra_env={
            "VESCALE_COSTAUDIT_DECAY": "0.9",
            "VESCALE_TIMESERIES_CADENCE_S": "0",
            "VESCALE_ALERTS_EVAL_INTERVAL_S": "0",
            "VESCALE_REDISTRIBUTE_MEM_FACTOR": "16",
        },
    )
    for pid, (rc, out) in enumerate(results):
        assert rc == 0, f"proc {pid} failed:\n{out[-4000:]}"
        assert f"OK proc {pid}" in out


# ============================================================ smoke wiring
def test_costaudit_smoke_script():
    """tier-1 wiring of scripts/costaudit_smoke.py: train + serve runs with
    joined predicted-vs-measured reports, the skewed-table drift + self-heal
    loop, the what-if ranking, and the dormant bit-identity check."""
    repo = pathlib.Path(__file__).resolve().parent.parent
    out = subprocess.run(
        [sys.executable, str(repo / "scripts" / "costaudit_smoke.py")],
        capture_output=True, text=True, timeout=600, cwd=str(repo),
    )
    assert out.returncode == 0, (out.stdout + out.stderr)[-4000:]
    assert "COSTAUDIT SMOKE OK" in out.stdout
