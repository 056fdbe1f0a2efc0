"""Memory observability (telemetry/memtrack.py + memory_report.py): the
tag-registry gate, live-array census buckets, leak detection, the OOM
flight recorder, AOT drift — plus the ndtimeline satellites (OPTIMIZER_STEP
/ DATA_LOAD call sites, no dead predefined names, host-dispatch span
tags)."""

import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from vescale_tpu import telemetry
from vescale_tpu.telemetry import memtrack
from vescale_tpu.telemetry.memory_report import (
    aot_memory_budget,
    compare_with_aot,
    device_memory_stats,
    live_array_census,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _telemetry_reset():
    yield
    telemetry.shutdown()


# ------------------------------------------------------------------- gate
def test_gate_dormant_hooks_are_noop_references():
    """The zero-overhead contract: while dormant the module hooks ARE the
    no-op functions (identity, not equivalence) and no tracker exists."""
    assert not memtrack.is_active()
    assert memtrack.get_tracker() is None
    assert memtrack.tag_array is memtrack._noop_tag_array
    assert memtrack.tag_tree is memtrack._noop_tag_tree
    x = jnp.ones((4,))
    assert memtrack.tag_array(x, "params") is x  # returns input untouched
    assert memtrack.dump_now() is None
    with memtrack.tagged("params"):
        assert memtrack.tag_array(x) is x
    assert not memtrack._TAG_STACK  # scope unwound


def test_gate_dormant_darray_factory_registers_nothing(mesh1d):
    from vescale_tpu import zeros

    assert memtrack.tag_array is memtrack._noop_tag_array
    with memtrack.tagged("params"):
        zeros((8, 8), device_mesh=mesh1d)
    assert memtrack.get_tracker() is None
    assert memtrack.tag_array is memtrack._noop_tag_array


def test_gate_dormant_optimizer_init_registers_nothing():
    from vescale_tpu.parallel.optimizer import DistributedOptimizer

    dopt = DistributedOptimizer(optax.sgd(0.1))
    dopt.init({"w": jnp.ones((4, 4))})
    assert memtrack.get_tracker() is None


def test_init_binds_and_shutdown_restores_hooks():
    st = telemetry.init(out_dir=None)
    assert st.memtrack is memtrack.get_tracker() is not None
    assert memtrack.tag_array is not memtrack._noop_tag_array
    telemetry.shutdown()
    assert memtrack.get_tracker() is None
    assert memtrack.tag_array is memtrack._noop_tag_array


def test_init_memtrack_false_keeps_dormant():
    telemetry.init(out_dir=None, memtrack=False)
    assert telemetry.is_active()
    assert memtrack.get_tracker() is None
    assert memtrack.tag_array is memtrack._noop_tag_array


# ----------------------------------------------------------------- census
def test_census_buckets_by_owner_tag(mesh1d):
    from vescale_tpu import zeros

    telemetry.init(out_dir=None)
    with memtrack.tagged("params"):
        w = zeros((16, 16), device_mesh=mesh1d)
    g = memtrack.tag_array(jnp.ones((8, 8)), "grads")
    tracker = memtrack.get_tracker()
    assert tracker.tag_of(w.data) == "params"
    assert tracker.tag_of(g) == "grads"
    census = tracker.census()
    assert census["tags"]["params"]["bytes"] >= 16 * 16 * 4
    assert census["tags"]["grads"]["bytes"] >= 8 * 8 * 4
    assert census["live_arrays"] >= 2
    top = census["top_arrays"][0]
    assert top["bytes"] >= 16 * 16 * 4 and top["tag"] in ("params", "untagged")


def test_tagging_never_extends_array_lifetime():
    telemetry.init(out_dir=None)
    tracker = memtrack.get_tracker()
    a = jnp.ones((32,)) * 3  # computed: unique buffer, not a cached constant
    memtrack.tag_array(a, "grads")
    assert tracker.num_tagged == 1
    del a
    import gc

    gc.collect()
    assert tracker.num_tagged == 0  # weakref callback evicted the entry


def test_optimizer_init_tags_state():
    from vescale_tpu.parallel.optimizer import DistributedOptimizer

    telemetry.init(out_dir=None)
    dopt = DistributedOptimizer(optax.adamw(1e-3))
    state = dopt.init({"w": jnp.ones((8, 8))})
    tracker = memtrack.get_tracker()
    leaves = jax.tree_util.tree_leaves(state)
    assert any(tracker.tag_of(l) == "optimizer_state" for l in leaves)
    census = tracker.census()
    assert census["tags"]["optimizer_state"]["bytes"] > 0


def test_checkpoint_load_tags_buffers(tmp_path):
    import vescale_tpu.checkpoint as ckpt

    state = {"model": {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}}
    ckpt.save(str(tmp_path / "ck"), state)
    telemetry.init(out_dir=None)
    loaded = ckpt.load(str(tmp_path / "ck"), state)
    tracker = memtrack.get_tracker()
    leaves = [l for l in jax.tree_util.tree_leaves(loaded) if hasattr(l, "nbytes")]
    assert any(tracker.tag_of(l) == "checkpoint_buffers" for l in leaves)


# ----------------------------------------------------------- device stats
def test_device_memory_stats_degrades_to_host_rss():
    stats = device_memory_stats()
    assert stats  # never empty
    # CPU backend has no memory_stats() -> exactly the host fallback entry
    if all(s["source"] == "host_rss" for s in stats):
        assert stats[0]["bytes_in_use"] is None or stats[0]["bytes_in_use"] > 0


def test_on_step_sets_gauges_and_history():
    telemetry.init(out_dir=None)
    keep = memtrack.tag_array(jnp.ones((64,)), "params")  # noqa: F841
    for i in range(3):
        telemetry.record_step({"step": i, "step_time_s": 0.01, "loss": 1.0})
    reg = telemetry.get_registry()
    names = reg.names()
    assert "mem_tag_params_bytes" in names
    assert "mem_live_arrays" in names
    assert any(n.startswith("mem_device") or n == "mem_host_rss_bytes" for n in names)
    tracker = memtrack.get_tracker()
    assert len(tracker.history) == 3
    assert tracker.history[-1]["tags"]["params"] >= 64 * 4


def test_census_interval_skips_steps():
    telemetry.init(out_dir=None, memtrack_interval=2)
    for i in range(4):
        telemetry.record_step({"step": i, "step_time_s": 0.01})
    # steps 0 and 2 sampled; 1 and 3 skipped
    assert len(memtrack.get_tracker().history) == 2


# ------------------------------------------------------------------ leaks
def test_leak_warning_after_monotonic_untagged_growth():
    # alerts=False: the engine-dormant legacy path — the leak surfaces as
    # the warn-once [alert:mem-leak] fallback line
    from vescale_tpu.telemetry import alerts as _alerts

    _alerts.clear_fallback_warned()
    telemetry.init(out_dir=None, memtrack_leak_steps=3, alerts=False)
    hoard = []
    with pytest.warns(UserWarning, match="possible leak"):
        for i in range(1, 6):
            # strictly growing untagged bytes each step (the leak shape)
            hoard.append(jnp.ones((256 * i,)) + i)
            telemetry.record_step({"step": i, "step_time_s": 0.01})
    reg = telemetry.get_registry()
    assert reg.counter("mem_leak_warnings_total").value == 1  # warn once per run
    assert reg.gauge("mem_untagged_growth_steps").value >= 3


def test_leak_routes_through_alert_engine_when_live(recwarn):
    # with the engine live (the default) the SAME leak raises the
    # mem-leak alert instead of a warning — one lifecycle for watchers
    from vescale_tpu.telemetry import alerts as _alerts

    telemetry.init(out_dir=None, memtrack_leak_steps=3)
    hoard = []
    for i in range(1, 6):
        hoard.append(jnp.ones((256 * i,)) + i)
        telemetry.record_step({"step": i, "step_time_s": 0.01})
    assert not any("possible leak" in str(w.message) for w in recwarn.list)
    eng = _alerts.get_engine()
    st = eng.state_of("mem-leak")
    assert st is not None and st["state"] == "firing"
    assert "possible leak" in st["message"]
    # still counted in the registry (the dashboard's mem block)
    assert telemetry.get_registry().counter("mem_leak_warnings_total").value == 1


def test_no_leak_warning_on_stable_memory(recwarn):
    telemetry.init(out_dir=None, memtrack_leak_steps=3)
    for i in range(6):
        telemetry.record_step({"step": i, "step_time_s": 0.01})
    assert not any("possible leak" in str(w.message) for w in recwarn.list)
    assert telemetry.get_registry().get("mem_leak_warnings_total") is None


# -------------------------------------------------------- flight recorder
def test_dump_now_bundle_and_file(tmp_path):
    telemetry.init(out_dir=str(tmp_path))
    keep = memtrack.tag_array(jnp.ones((32,)), "params")  # noqa: F841
    telemetry.record_step({"step": 1, "step_time_s": 0.01})
    bundle = telemetry.dump_now(reason="test")
    assert bundle["reason"] == "test"
    assert bundle["census"]["tags"]["params"]["bytes"] > 0
    assert bundle["device_memory"] and bundle["history"]
    assert bundle["registry"]["counters"]["train_steps_total"] == 1
    on_disk = json.load(open(bundle["path"]))
    assert on_disk["reason"] == "test"
    assert telemetry.get_registry().counter("mem_flight_records_total").value == 1


def test_flight_recorder_dumps_on_resource_exhausted(tmp_path):
    telemetry.init(out_dir=str(tmp_path))

    @telemetry.flight_recorder
    def step():
        raise RuntimeError("RESOURCE_EXHAUSTED: Out of memory allocating 1 bytes.")

    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        step()
    dumps = [f for f in os.listdir(tmp_path) if f.startswith("flight_record_")]
    assert len(dumps) == 1
    doc = json.load(open(tmp_path / dumps[0]))
    assert doc["reason"].startswith("oom:") and "RESOURCE_EXHAUSTED" in doc["exception"]


def test_flight_recorder_ignores_non_oom_and_dormant(tmp_path):
    @telemetry.flight_recorder
    def bad():
        raise ValueError("not an oom")

    with pytest.raises(ValueError):
        bad()  # dormant: nothing dumped, exception untouched
    telemetry.init(out_dir=str(tmp_path))
    with pytest.raises(ValueError):
        bad()  # active but not OOM-shaped: still no dump
    assert not [f for f in os.listdir(tmp_path) if f.startswith("flight_record_")]


def test_bundle_includes_ndtimeline_tail(tmp_path):
    from vescale_tpu.ndtimeline import api as nd_api

    old_mgr, old_active = nd_api._MANAGER, nd_api._ACTIVE
    try:
        mgr = nd_api.init_ndtimers(rank=0)
        with mgr.timeit("forward-compute"):
            pass
        telemetry.init(out_dir=None)
        bundle = telemetry.dump_now(reason="tail-test")
        assert bundle["ndtimeline_tail"], "buffered spans must appear in the bundle"
        assert bundle["ndtimeline_tail"][-1]["metric"] == "forward-compute"
        # the peek must NOT drain the buffer (a later flush still sees it)
        assert [s.metric for s in mgr.flush()] == ["forward-compute"]
    finally:
        nd_api._MANAGER, nd_api._ACTIVE = old_mgr, old_active


# -------------------------------------------------------------- AOT drift
def _fake_aot(budget):
    return {"measured": {"per_device_bytes_fp32_compile": budget}}


def test_compare_with_aot_flags_drift():
    report = {"peak_bytes": 1200.0, "argument_bytes": 1000, "output_bytes": 100,
              "temp_bytes": 100, "alias_bytes": 0, "generated_code_bytes": 0}
    d = compare_with_aot(report, _fake_aot(1000.0))
    assert d["exceeds_tolerance"] and abs(d["drift_frac"] - 0.2) < 1e-9
    d = compare_with_aot(report, _fake_aot(1150.0))
    assert not d["exceeds_tolerance"]
    # degrade, never raise
    assert compare_with_aot({}, _fake_aot(1000.0)) is None
    assert compare_with_aot(report, {"config": {}}) is None
    assert compare_with_aot(report, "/nonexistent/aot.json") is None


def test_aot_budget_sources():
    assert aot_memory_budget(_fake_aot(5.0))["bytes"] == 5.0
    b = aot_memory_budget({"bf16_basis_memory": {"total_bytes": 7.0}})
    assert b["bytes"] == 7.0 and b["source"] == "bf16_basis_memory.total_bytes"
    assert aot_memory_budget({}) is None


def test_step_report_attaches_aot_drift_and_gauge(tmp_path):
    # alerts=False: the engine-dormant legacy path still warns one-shot
    from vescale_tpu.telemetry import alerts as _alerts

    _alerts.clear_fallback_warned()
    telemetry.init(out_dir=str(tmp_path), alerts=False)

    def fn(x):
        return x @ x.T

    x = jnp.ones((16, 16))
    with pytest.warns(UserWarning, match="AOT budget"):
        report = telemetry.write_step_report(
            "prog", fn, x, aot_report=_fake_aot(1.0)  # tiny budget -> huge drift
        )
    assert report["aot_drift"]["exceeds_tolerance"]
    assert telemetry.get_state().last_step_report is report
    assert "step_report_prog_aot_drift_frac" in telemetry.get_registry().names()


def test_aot_drift_routes_through_alert_engine_when_live(tmp_path, recwarn):
    from vescale_tpu.telemetry import alerts as _alerts

    telemetry.init(out_dir=str(tmp_path))

    def fn(x):
        return x @ x.T

    x = jnp.ones((16, 16))
    report = telemetry.write_step_report("prog", fn, x, aot_report=_fake_aot(1.0))
    assert report["aot_drift"]["exceeds_tolerance"]
    assert not any("AOT budget" in str(w.message) for w in recwarn.list)
    st = _alerts.get_engine().state_of("aot-drift-prog")
    assert st is not None and st["state"] == "firing"
    # a non-exceeding report (budget == measured, zero drift) resolves it
    measured = report["aot_drift"]["measured_bytes"]
    telemetry.write_step_report("prog", fn, x, aot_report=_fake_aot(measured))
    assert _alerts.get_engine().state_of("aot-drift-prog")["state"] == "ok"


# ------------------------------------------------ ndtimeline satellites
def test_optimizer_step_span_emitted_eagerly():
    from vescale_tpu.ndtimeline import api as nd_api
    from vescale_tpu.parallel.optimizer import BasicOptimizer, DistributedOptimizer

    old_mgr, old_active = nd_api._MANAGER, nd_api._ACTIVE
    try:
        mgr = nd_api.init_ndtimers(rank=0)
        params = {"w": jnp.ones((4, 4))}
        for opt in (BasicOptimizer(optax.sgd(0.1)), DistributedOptimizer(optax.sgd(0.1))):
            state = opt.init(params)
            grads = {"w": jnp.ones((4, 4))}
            opt.step(params, state, grads)
        spans = [s.metric for s in mgr.flush()]
        assert spans.count("optimizer-step") == 2
        # inside jit the span must NOT fire (host spans cannot bracket
        # device work; tracing would record a bogus trace-time span)
        dopt = DistributedOptimizer(optax.sgd(0.1))
        state = dopt.init(params)
        jax.jit(dopt.step)(params, state, {"w": jnp.ones((4, 4))})
        assert "optimizer-step" not in [s.metric for s in mgr.flush()]
    finally:
        nd_api._MANAGER, nd_api._ACTIVE = old_mgr, old_active


def test_data_load_span_and_histogram(tmp_path):
    from vescale_tpu.data.loader import TokenDataLoader
    from vescale_tpu.ndtimeline import api as nd_api

    bin_path = str(tmp_path / "toks.bin")
    np.arange(4096, dtype=np.uint16).tofile(bin_path)
    old_mgr, old_active = nd_api._MANAGER, nd_api._ACTIVE
    try:
        mgr = nd_api.init_ndtimers(rank=0)
        telemetry.init(out_dir=None)
        loader = TokenDataLoader(bin_path, batch=2, seq_len=16, seed=1)
        batch = next(iter(loader))
        assert batch["input"].shape == (2, 16)
        loader.close()
        assert "vs.data-load" in [s.metric for s in mgr.flush()]
        assert telemetry.get_registry().histogram("data_load_seconds").count == 1
    finally:
        nd_api._MANAGER, nd_api._ACTIVE = old_mgr, old_active


def test_predefined_names_all_have_call_sites():
    """VERDICT item 7 contract: no declared-but-never-emitted metric names.
    Every NAME in predefined.py must be referenced somewhere else in the
    package source."""
    pkg = os.path.join(REPO, "vescale_tpu")
    pre = open(os.path.join(pkg, "ndtimeline", "predefined.py")).read()
    names = re.findall(r"^([A-Z][A-Z_]+) = ", pre, re.M)
    assert names, "predefined.py lost its names?"
    sources = []
    for root, _dirs, files in os.walk(pkg):
        for f in files:
            if f.endswith(".py") and f != "predefined.py":
                sources.append(open(os.path.join(root, f)).read())
    blob = "\n".join(sources)
    dead = [n for n in names if n not in blob]
    assert not dead, f"predefined names with zero call sites: {dead}"
    # and the deleted p2p/collective names stay deleted
    for gone in ("RECV_FORWARD", "SEND_BACKWARD", "UNSHARD_AG", "GRAD_RS", "GRAD_AR"):
        assert gone not in pre


def _tiny_engine():
    from vescale_tpu.models.nanogpt import GPTConfig, cross_entropy_loss, gpt_pipeline_units
    from vescale_tpu.pipe import PipeEngine, construct_pipeline_stage
    from vescale_tpu.plan import PipelineParallelPlan

    cfg = GPTConfig(block_size=8, vocab_size=32, n_layer=2, n_head=2, n_embd=16, dropout=0.0)
    plan = PipelineParallelPlan(num_stages=2)
    pm = construct_pipeline_stage(gpt_pipeline_units(cfg), plan)
    params = pm.init_all(jax.random.key(0), jnp.ones((2, cfg.block_size), jnp.int32))
    engine = PipeEngine(pm, plan, cross_entropy_loss)
    toks = jax.random.randint(jax.random.key(1), (4, cfg.block_size + 1), 0, cfg.vocab_size)
    return engine, params, {"input": toks[:, :-1], "target": toks[:, 1:]}


def test_engine_spans_tagged_host_dispatch_vs_blocked():
    from vescale_tpu.ndtimeline import api as nd_api

    engine, params, batch = _tiny_engine()
    old_mgr, old_active = nd_api._MANAGER, nd_api._ACTIVE
    try:
        mgr = nd_api.init_ndtimers(rank=0)
        engine.forward_backward(params, batch, num_microbatches=2)
        spans = mgr.flush()
        compute = [s for s in spans if s.metric == "forward-compute"]
        assert compute and all(s.tags["timing"] == "host-dispatch" for s in compute)
        engine.on_instruction = lambda ins, dt: None  # profiling mode blocks
        engine.forward_backward(params, batch, num_microbatches=2)
        spans = mgr.flush()
        compute = [s for s in spans if s.metric == "forward-compute"]
        assert compute and all(s.tags["timing"] == "blocked" for s in compute)
    finally:
        nd_api._MANAGER, nd_api._ACTIVE = old_mgr, old_active


def test_engine_tags_grads_and_stash():
    telemetry.init(out_dir=None)
    engine, params, batch = _tiny_engine()
    _loss, grads = engine.forward_backward(params, batch, num_microbatches=2)
    tracker = memtrack.get_tracker()
    leaves = [l for l in jax.tree_util.tree_leaves(grads) if hasattr(l, "nbytes")]
    assert leaves and any(tracker.tag_of(l) == "grads" for l in leaves)
    assert tracker.census()["tags"].get("grads", {}).get("bytes", 0) > 0


def test_train_step_retags_outputs():
    from vescale_tpu.dmodule import parallelize_module
    from vescale_tpu.mesh import DeviceMesh
    from vescale_tpu.models.nanogpt import GPT, GPTConfig, cross_entropy_loss, nanogpt_plan

    telemetry.init(out_dir=None)
    cfg = GPTConfig(block_size=8, vocab_size=32, n_layer=1, n_head=2, n_embd=16, dropout=0.0)
    mesh = DeviceMesh(("dp", "tp"), (1, 1), devices=jax.devices()[:1])
    dm = parallelize_module(GPT(cfg), mesh, nanogpt_plan(mesh))
    params = dm.init(jax.random.key(0), jnp.ones((2, 8), jnp.int32))["params"]
    from vescale_tpu.train import make_train_step

    tx = optax.sgd(0.1, momentum=0.9)  # momentum: nonempty optimizer state
    step = make_train_step(dm, tx, lambda lg, b: cross_entropy_loss(lg, b["target"]),
                           donate=False)
    opt_state = tx.init(params)
    toks = jax.random.randint(jax.random.key(1), (2, 9), 0, 32)
    batch = {"input": toks[:, :-1], "target": toks[:, 1:]}
    params, opt_state, _loss = step(params, opt_state, batch)
    tracker = memtrack.get_tracker()
    leaves = jax.tree_util.tree_leaves(params)
    assert any(tracker.tag_of(l) == "params" for l in leaves)
    census = tracker.census()
    assert census["tags"]["params"]["bytes"] > 0
    assert census["tags"]["optimizer_state"]["bytes"] > 0


# ------------------------------------------------------------- smoke (CI)
def test_memtrack_smoke_script():
    """tier-1 wiring of scripts/memtrack_smoke.py (the acceptance run)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "memtrack_smoke.py")],
        capture_output=True,
        text=True,
        timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        cwd=REPO,
    )
    assert proc.returncode == 0, f"smoke failed:\n{proc.stdout}\n{proc.stderr}"
    assert "all checks passed" in proc.stdout
