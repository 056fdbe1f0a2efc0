"""Request-lifecycle observability for serving (ISSUE 12): per-request
span chains + the taxonomy<->ledger lockstep verifier, goodput/MFU
accounting, serve step-counter attribution in steps.jsonl, the live ops
endpoints (/metrics, /healthz, /router) with their frozen router schema
and identity-asserted off mode, the retry_after_s cold-start seed, and
the tier-1 wiring of scripts/serve_obs_smoke.py."""

import json
import os
import pathlib
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from vescale_tpu import telemetry
from vescale_tpu.mesh import DeviceMesh
from vescale_tpu.models.llama import Llama, LlamaConfig
from vescale_tpu.ndtimeline import api as nd_api
from vescale_tpu.ndtimeline import predefined as P
from vescale_tpu.ndtimeline.timer import Span
from vescale_tpu.resilience import faultsim
from vescale_tpu.resilience.watchdog import Watchdog
from vescale_tpu.serve import (
    ContinuousBatchingScheduler,
    KVCacheConfig,
    PagedKVCache,
    Request,
    ServeEngine,
    ServeObservability,
    reqtrace,
    run_serve_resilient,
)
from vescale_tpu.serve.obs import ROUTER_FIELDS, ROUTER_SCHEMA_VERSION
from vescale_tpu.telemetry import ops_server
from vescale_tpu.telemetry.exporters import parse_prometheus_text
from vescale_tpu.testing import reserve_port

REPO = str(pathlib.Path(__file__).resolve().parent.parent)

CFG = LlamaConfig(
    vocab_size=64,
    hidden_size=16,
    intermediate_size=32,
    num_hidden_layers=2,
    num_attention_heads=2,
    num_key_value_heads=2,
    max_position_embeddings=64,
    dtype=jnp.float32,
)


@pytest.fixture(scope="module")
def serve_rig():
    mesh = DeviceMesh(("tp",), (2,))
    model = Llama(CFG)
    params = model.init(jax.random.key(0), jnp.ones((1, 8), jnp.int32))["params"]
    kc = KVCacheConfig(
        layers=CFG.num_hidden_layers, kv_heads=CFG.num_key_value_heads,
        head_dim=CFG.head_dim, num_slots=2, page_size=4, pages_per_slot=4,
    )
    cache = PagedKVCache(kc, mesh)
    eng = ServeEngine(CFG, mesh, params, cache)
    return eng, cache


@pytest.fixture
def live_ndtimeline():
    """A fresh ndtimeline manager for the test, restored afterwards (the
    module-global gate must not leak into other test files)."""
    old_mgr, old_active = nd_api._MANAGER, nd_api._ACTIVE
    mgr = nd_api.init_ndtimers(rank=0)
    try:
        yield mgr
    finally:
        nd_api._MANAGER, nd_api._ACTIVE = old_mgr, old_active


def _arrivals(n=5, **kw):
    rng = np.random.default_rng(3)
    out = []
    for i in range(n):
        kw.setdefault("deadline_steps", 50)
        out.append((2 * i, Request(
            rid=i, prompt=tuple(int(x) for x in rng.integers(1, 60, 3 + i % 2)),
            max_new_tokens=4, **kw,
        )))
    return out


def _run(eng, cache, arrivals, max_queue=8, **kw):
    cache.reset()
    sched = ContinuousBatchingScheduler(cache, max_queue=max_queue)
    res = run_serve_resilient(
        engine=eng, scheduler=sched, arrivals=arrivals,
        install_signal_handlers=False, coordinate=False, **kw,
    )
    return res, sched


def _get(url, timeout=5.0):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:  # non-2xx still carries a body
        return e.code, e.read().decode()


def _ops_threads():
    return [t for t in threading.enumerate() if t.name == "vescale-ops-server"]


# ========================================================== ops server unit
def test_ops_server_reserved_port_and_routes():
    port = reserve_port()  # the tier-1 no-collision registry
    srv = ops_server.OpsServer(port=port).start()
    try:
        assert srv.port == port
        status, body = _get(f"{srv.url}/healthz")
        assert status == 503 and "no provider" in body
        srv.register("healthz", lambda: {"ok": True, "n": 3})
        status, body = _get(f"{srv.url}/healthz")
        assert status == 200 and json.loads(body) == {"ok": True, "n": 3}
        status, body = _get(f"{srv.url}/nope")
        assert status == 404
    finally:
        srv.stop()
    assert not _ops_threads()


def test_ops_server_metrics_dormant_vs_active(tmp_path):
    srv = ops_server.OpsServer(port=0).start()
    try:
        assert not telemetry.is_active()
        status, body = _get(f"{srv.url}/metrics")
        assert status == 503 and "dormant" in body
        telemetry.init(out_dir=str(tmp_path), memtrack=False)
        try:
            telemetry.count("serve_requests_admitted_total", 2)
            status, body = _get(f"{srv.url}/metrics")
            assert status == 200
            series = parse_prometheus_text(body)
            assert series["serve_requests_admitted_total"] == 2
        finally:
            telemetry.shutdown()
    finally:
        srv.stop()


def test_ops_server_provider_error_is_500_not_hang():
    srv = ops_server.OpsServer(port=0).start()
    try:
        srv.register("router", lambda: (_ for _ in ()).throw(RuntimeError("boom")))
        status, body = _get(f"{srv.url}/router")
        assert status == 500 and "boom" in body
    finally:
        srv.stop()


def test_maybe_start_off_is_noop(monkeypatch):
    """Endpoint-off mode (knob unset) creates NOTHING: no thread, no
    socket, no active server — the telemetry-gate convention."""
    monkeypatch.delenv("VESCALE_SERVE_OPS_PORT", raising=False)
    before = threading.active_count()
    assert ops_server.maybe_start(health=lambda: {}) is None
    assert threading.active_count() == before
    assert ops_server.active_server() is None
    assert not _ops_threads()


def test_maybe_start_auto_port_and_active_registry(monkeypatch):
    monkeypatch.setenv("VESCALE_SERVE_OPS_PORT", "0")
    srv = ops_server.maybe_start(health=lambda: {"ok": True})
    try:
        assert srv is not None and srv.port > 0
        assert ops_server.active_server() is srv
        assert json.loads(_get(f"{srv.url}/healthz")[1]) == {"ok": True}
    finally:
        srv.stop()
    assert ops_server.active_server() is None


# ===================================================== providers / schema
def test_router_schema_frozen_and_json_roundtrip(serve_rig):
    from vescale_tpu.serve.obs import ROUTER_FIELDS_V1, ROUTER_FIELDS_V2

    eng, cache = serve_rig
    cache.reset()
    sched = ContinuousBatchingScheduler(cache, max_queue=8)
    obs = ServeObservability(sched, engine=eng, rank=0, replica_id="robs")
    from vescale_tpu.serve.obs import ROUTER_FIELDS_V3, ROUTER_FIELDS_V4

    feed = json.loads(json.dumps(obs.router()))
    assert set(feed) == set(ROUTER_FIELDS)
    # the freeze contract across versions: fields are only ever ADDED —
    # every prior version stays a strict subset, so a router written
    # against v1..v4 still runs against a v5 feed
    assert (
        ROUTER_FIELDS_V1 < ROUTER_FIELDS_V2 < ROUTER_FIELDS_V3
        < ROUTER_FIELDS_V4 < ROUTER_FIELDS
    )
    assert set(ROUTER_FIELDS_V2) - set(ROUTER_FIELDS_V1) == {"replica_id", "accepting"}
    assert set(ROUTER_FIELDS_V3) - set(ROUTER_FIELDS_V2) == {
        "prefix_hit_rate", "spec_accept_rate",
    }
    assert set(ROUTER_FIELDS_V4) - set(ROUTER_FIELDS_V3) == {"alerts"}
    assert set(ROUTER_FIELDS) - set(ROUTER_FIELDS_V4) == {"tenants", "rollout"}
    assert feed["schema_version"] == ROUTER_SCHEMA_VERSION == 5
    # v4 addition: the alert digest, dormant-safe shape
    assert set(feed["alerts"]) == {"active", "firing", "pending"}
    # v5 additions: tenant stats empty until a non-default tenant
    # submits; rollout null outside a weight rollout
    assert feed["tenants"] == {}
    assert feed["rollout"] is None
    assert feed["slots"] == 2 and feed["free_slots"] == 2
    assert set(feed["ttft_s"]) == {"p50", "p95", "p99"}
    assert set(feed["itl_s"]) == {"p50", "p95", "p99"}
    # v2 additions: identity + the pre-dispatch exclusion signal
    assert feed["replica_id"] == "robs"
    assert feed["accepting"] is True
    # v3 additions are null (not 0.0) while the multipliers are off —
    # "cold" and "disabled" must stay distinguishable
    assert feed["prefix_hit_rate"] is None
    assert feed["spec_accept_rate"] is None
    obs.draining = True
    assert obs.router()["accepting"] is False


def test_healthz_reports_watchdog_beat_age(serve_rig):
    eng, cache = serve_rig
    cache.reset()
    sched = ContinuousBatchingScheduler(cache, max_queue=8)
    wd = Watchdog(timeout_s=3600.0, abort=False)
    wd.beat(7)
    time.sleep(0.05)
    h = ServeObservability(sched, watchdog=wd).health()
    assert h["watchdog_last_beat_age_s"] >= 0.05
    assert h["last_decode_step_age_s"] is None  # no decode step yet
    assert h["ok"] and not h["draining"]
    assert h["free_slots"] == 2 and h["queue_depth"] == 0


# ================================================= retry_after_s cold start
def test_retry_after_cold_start_seed(serve_rig):
    _, cache = serve_rig
    cache.reset()
    sched = ContinuousBatchingScheduler(cache, max_queue=8)
    # unmeasured + unseeded: the old 10ms floor
    assert sched.retry_after_s() == pytest.approx(0.01)
    sched.seed_step_time(0.5)
    assert sched.retry_after_s() == pytest.approx(0.5)
    # a second seed never overwrites the first
    sched.seed_step_time(9.0)
    assert sched.retry_after_s() == pytest.approx(0.5)
    # a REAL decode sample supersedes the seed entirely (10ms floor holds)
    sched.observe_step_time(0.02)
    assert sched.retry_after_s() == pytest.approx(0.02)
    # and seeding after real samples is ignored
    sched2 = ContinuousBatchingScheduler(cache, max_queue=8)
    sched2.observe_step_time(0.03)
    sched2.seed_step_time(0.5)
    assert sched2.retry_after_s() == pytest.approx(0.03)


def test_loop_seeds_step_time_from_first_prefill(serve_rig):
    eng, cache = serve_rig
    res, sched = _run(eng, cache, _arrivals(n=2))
    assert res.status == "completed"
    assert sched._step_time_seed is not None and sched._step_time_seed > 0


# ======================================================= loop + endpoints
def test_loop_ops_endpoints_live_and_drain_visible(serve_rig, monkeypatch):
    eng, cache = serve_rig
    monkeypatch.setenv("VESCALE_SERVE_OPS_PORT", "0")
    faultsim.arm(faultsim.parse_schedule("preempt:step=5"))
    snapshots = []

    def on_step(step, active):
        srv = ops_server.active_server()
        assert srv is not None, "ops server not up during the loop"
        snapshots.append(json.loads(_get(f"{srv.url}/healthz")[1]))

    try:
        res, sched = _run(eng, cache, _arrivals(), on_step=on_step)
    finally:
        faultsim.disarm()
    assert res.status == "preempted"
    assert any(h["draining"] for h in snapshots), snapshots
    assert any(not h["draining"] for h in snapshots)
    assert all(h["free_slots"] <= 2 and h["queue_depth"] >= 0 for h in snapshots)
    # the loop tears its server down on exit
    assert ops_server.active_server() is None
    assert not _ops_threads()


def test_loop_endpoints_off_leaves_zero_threads(serve_rig, monkeypatch):
    eng, cache = serve_rig
    monkeypatch.delenv("VESCALE_SERVE_OPS_PORT", raising=False)
    seen = []

    def on_step(step, active):
        seen.append((ops_server.active_server(), len(_ops_threads())))

    res, _ = _run(eng, cache, _arrivals(n=2), on_step=on_step)
    assert res.status == "completed"
    assert seen and all(srv is None and n == 0 for srv, n in seen)


# ==================================================== goodput / MFU gauges
def test_goodput_vs_raw_accounting(serve_rig):
    eng, cache = serve_rig
    # force a mid-flight timeout: its sampled tokens are raw, not goodput
    faultsim.arm(faultsim.parse_schedule("request_timeout:step=3"))
    try:
        res, sched = _run(eng, cache, _arrivals())
    finally:
        faultsim.disarm()
    assert res.counts["timed_out"] >= 1
    completed_tokens = sum(
        len(o["tokens"]) for o in res.outcomes.values() if o["status"] == "completed"
    )
    assert sched.goodput_tokens == completed_tokens
    assert sched.raw_tokens > sched.goodput_tokens


def test_rate_gauges_published(serve_rig, tmp_path):
    eng, cache = serve_rig
    telemetry.init(out_dir=str(tmp_path), memtrack=False)
    try:
        res, sched = _run(eng, cache, _arrivals(n=3))
        snap = telemetry.get_registry().snapshot()
    finally:
        telemetry.shutdown()
    assert res.status == "completed"
    g = snap["gauges"]
    assert g["serve_goodput_tokens_per_s"] > 0
    assert g["serve_throughput_tokens_per_s"] >= g["serve_goodput_tokens_per_s"]
    assert snap["counters"]["serve_tokens_generated_total"] > 0
    assert snap["counters"]["serve_goodput_tokens_total"] == sched.goodput_tokens
    h = snap["histograms"]
    assert h["serve_itl_seconds"]["count"] > 0
    assert h["serve_ttft_queue_wait_seconds"]["count"] >= 3
    assert h["serve_ttft_prefill_seconds"]["count"] >= 3


# ============================================= step-counter attribution
def test_serve_decode_steps_attributed_in_jsonl(serve_rig, tmp_path, live_ndtimeline):
    """ISSUE 12 satellite 1 regression: the decode loop advances the
    profiler step counter itself, so each steps.jsonl serve line's spans
    rollup names its OWN decode step (span rollup step == decode step)."""
    eng, cache = serve_rig
    mgr = live_ndtimeline
    mgr.step = 37  # simulate a stale counter left by a prior training run
    telemetry.init(out_dir=str(tmp_path), memtrack=False)
    try:
        res, _ = _run(eng, cache, _arrivals(n=3))
    finally:
        telemetry.shutdown()
    assert res.status == "completed"
    lines = [json.loads(x) for x in open(os.path.join(tmp_path, "steps.jsonl"))]
    serve_lines = [x for x in lines if x.get("kind") == "serve"]
    assert serve_lines, lines[:3]
    # one line per decode step, each claiming exactly one decode-step span
    steps = [x["step"] for x in serve_lines]
    assert steps[0] == 37 and steps == list(range(37, 37 + len(steps)))
    for x in serve_lines:
        spans = x.get("spans") or {}
        assert spans.get(P.SERVE_DECODE_STEP, {}).get("count") == 1, (x["step"], spans)
    # the counter advanced once per decode step
    assert mgr.step == 37 + len(serve_lines)


def test_record_step_serve_kind_skips_train_conventions(tmp_path):
    telemetry.init(out_dir=str(tmp_path), memtrack=False)
    try:
        telemetry.record_step({"step": 5, "step_time_s": 0.1}, kind="serve")
        snap = telemetry.get_registry().snapshot()
        assert "train_steps_total" not in snap["counters"]
        assert "train_step_time_seconds" not in snap["histograms"]
        telemetry.record_step({"step": 6, "step_time_s": 0.1})
        snap = telemetry.get_registry().snapshot()
        assert snap["counters"]["train_steps_total"] == 1
    finally:
        telemetry.shutdown()
    lines = [json.loads(x) for x in open(os.path.join(tmp_path, "steps.jsonl"))]
    assert lines[0]["kind"] == "serve" and "kind" not in lines[1]


# ======================================================== request chains
def test_request_chains_golden(serve_rig, live_ndtimeline):
    eng, cache = serve_rig
    res, _ = _run(eng, cache, _arrivals())
    spans = live_ndtimeline.flush()
    assert not reqtrace.verify_request_chains(spans, res.outcomes)
    metrics = {s.metric for s in spans}
    assert {P.SERVE_SUBMIT, P.SERVE_QUEUE_WAIT, P.SERVE_PREFILL,
            P.SERVE_DECODE_TOKEN, P.SERVE_TERMINAL} <= metrics
    # per-slot lanes: admitted-phase spans carry stage == slot
    staged = [s for s in spans if s.tags and "stage" in s.tags]
    assert staged and all(s.tags["stage"] == s.tags["slot"] for s in staged)
    # flow arrows: submit=send, terminal=recv on the same per-rid id
    for rid in res.outcomes:
        roles = {s.tags["flow_role"] for s in spans
                 if s.tags and s.tags.get("flow_id") == f"req{rid}"}
        assert roles == {"send", "recv"}, (rid, roles)


def test_request_chains_fault_battery_forks(serve_rig, live_ndtimeline):
    eng, cache = serve_rig
    faultsim.arm(faultsim.parse_schedule(
        "request_timeout:step=6;oom:step=4;preempt:step=9"
    ))
    try:
        res, sched = _run(eng, cache, _arrivals(n=6))
    finally:
        faultsim.disarm()
    sched.ledger_check()
    assert res.status == "preempted"
    assert res.counts["evicted"] >= 1 and res.counts["timed_out"] >= 1
    spans = live_ndtimeline.flush()
    assert not reqtrace.verify_request_chains(spans, res.outcomes)
    chains = reqtrace.request_spans(spans)
    # the eviction fork is visible: the replayed rid has an evict span and
    # one prefill per attempt
    forked = [rid for rid, o in res.outcomes.items() if o.get("replays")]
    assert forked
    for rid in forked:
        c = chains[rid]
        assert len(c[P.SERVE_EVICT]) == res.outcomes[rid]["replays"]
        if res.outcomes[rid]["status"] == "completed":
            assert len(c[P.SERVE_PREFILL]) == res.outcomes[rid]["replays"] + 1


def test_chain_verifier_catches_breaks():
    def span(metric, rid, **tags):
        return Span(metric=metric, start=1.0, duration=0.0, step=0, rank=0,
                    tags={"rid": rid, **tags})

    ok = [
        span(P.SERVE_SUBMIT, 1),
        span(P.SERVE_TERMINAL, 1, outcome="shed"),
    ]
    outcomes = {1: {"status": "shed", "tokens": [], "replays": 0}}
    assert not reqtrace.verify_request_chains(ok, outcomes)
    # missing terminal
    assert reqtrace.verify_request_chains(ok[:1], outcomes)
    # outcome mismatch between span and ledger
    bad = [ok[0], span(P.SERVE_TERMINAL, 1, outcome="completed")]
    assert reqtrace.verify_request_chains(bad, outcomes)
    # orphan chain: spans for a rid the ledger never saw
    orphan = ok + [span(P.SERVE_SUBMIT, 9), span(P.SERVE_TERMINAL, 9, outcome="shed")]
    problems = reqtrace.verify_request_chains(orphan, outcomes)
    assert any("orphan" in p for p in problems)
    # completed chains need the full admitted arc
    outcomes2 = {1: {"status": "completed", "tokens": [4, 5], "replays": 0}}
    thin = [ok[0], span(P.SERVE_TERMINAL, 1, outcome="completed", tokens=2)]
    problems = reqtrace.verify_request_chains(thin, outcomes2)
    assert any("queue-wait" in p for p in problems)
    assert any("prefill" in p for p in problems)
    assert any("decode-token" in p for p in problems)


def test_chain_verifier_resubmitted_rid_counts_last_lifetime_only():
    """The retry_after contract: a rid evicted then drain-rejected may be
    RESUBMITTED; its earlier lifetime's evict/prefill spans must not be
    counted against the fresh lifetime's ledger row (replays=0)."""
    def span(metric, t, **tags):
        return Span(metric=metric, start=t, duration=0.0, step=0, rank=0,
                    tags={"rid": 7, **tags})

    spans = [
        # lifetime 1: admitted, evicted, then rejected on drain
        span(P.SERVE_SUBMIT, 1.0),
        span(P.SERVE_QUEUE_WAIT, 2.0, slot=0),
        span(P.SERVE_PREFILL, 3.0, slot=0),
        span(P.SERVE_EVICT, 4.0, slot=0, outcome="evict_replay"),
        span(P.SERVE_TERMINAL, 5.0, outcome="preempted_requeue"),
        # lifetime 2 (resubmitted): clean completion, replays=0
        span(P.SERVE_SUBMIT, 6.0),
        span(P.SERVE_QUEUE_WAIT, 7.0, slot=1),
        span(P.SERVE_PREFILL, 8.0, slot=1),
        span(P.SERVE_DECODE_TOKEN, 9.0, slot=1, i=1),
        span(P.SERVE_TERMINAL, 10.0, outcome="completed", tokens=2),
    ]
    outcomes = {7: {"status": "completed", "tokens": [4, 5], "replays": 0}}
    assert not reqtrace.verify_request_chains(spans, outcomes)
    # and the check still bites inside one lifetime: claim a replay the
    # latest lifetime's spans don't show
    outcomes[7]["replays"] = 1
    assert reqtrace.verify_request_chains(spans, outcomes)


def test_reqtrace_dormant_is_free(serve_rig):
    """With the profiler dormant no serve span is ever recorded (the
    manager ring stays empty) — the ndtimeit gating convention."""
    assert not nd_api.is_active()
    eng, cache = serve_rig
    res, _ = _run(eng, cache, _arrivals(n=2))
    assert res.status == "completed"
    assert not [s for s in nd_api.get_manager().tail(10_000)
                if s.metric in reqtrace.SERVE_SPAN_METRICS]


# ============================================================ smoke wiring
def test_serve_obs_smoke_script():
    """tier-1 wiring of scripts/serve_obs_smoke.py: the 2-proc fault-battery
    run with tracing + endpoints, merged Perfetto chains ledger-matched."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "serve_obs_smoke.py")],
        capture_output=True,
        text=True,
        timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout[-3000:]}\nstderr:\n{out.stderr[-3000:]}"
    assert "SERVE OBS SMOKE OK" in out.stdout
