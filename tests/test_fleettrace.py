"""Fleet-wide tracing (ISSUE 14): router journey spans (submit ->
dispatch-attempt[i] -> terminal, breaker transitions, backoff forks),
replica-qualified merge lanes, the fleet timeline assembler's
cross-process flow stitching, HTTP clock-offset estimation, fleet-scope
journey verification with superseded-by-failover classification, the
frozen `/fleet` schema + fleet-timeline dashboard block, and the tier-1
wiring of scripts/fleet_trace_smoke.py."""

import json
import os
import pathlib
import subprocess
import sys
import time
import urllib.request

import pytest

from vescale_tpu.analysis import envreg
from vescale_tpu.ndtimeline import api as nd_api
from vescale_tpu.ndtimeline import predefined as P
from vescale_tpu.ndtimeline.handlers import ChromeTraceHandler
from vescale_tpu.ndtimeline.timer import Span
from vescale_tpu.serve import (
    CircuitBreaker,
    FleetObservability,
    FleetRouter,
    Request,
    fleettrace,
)
from vescale_tpu.serve.obs import FLEET_FIELDS, FLEET_REPLICA_FIELDS, FLEET_SCHEMA_VERSION
from vescale_tpu.serve.reqtrace import classify_chains, verify_request_chains
from vescale_tpu.serve.router import ReplicaUnreachable
from vescale_tpu.telemetry import ops_server
from vescale_tpu.telemetry.trace import (
    load_perfetto,
    merge_traces,
    spans_from_perfetto,
    stream_process_names,
    write_perfetto,
)
from vescale_tpu.testing import reserve_port

REPO = str(pathlib.Path(__file__).resolve().parent.parent)


# ============================================================== fakes
# (the no-sockets substrate of test_fleet.py, trimmed to what the
# tracing tests drive)
def _feed(replica_id, *, queue=0, inflight=0, slots=4, p99=None, accepting=True,
          serve_step=1, retry_after=0.01):
    return {
        "schema_version": 2, "rank": 0, "replica_id": replica_id,
        "accepting": accepting, "draining": False, "queue_depth": queue,
        "inflight": inflight, "slots": slots,
        "free_slots": max(0, slots - inflight), "pages": 16, "free_pages": 16,
        "ttft_s": {"p50": None, "p95": None, "p99": p99},
        "itl_s": {"p50": None, "p95": None, "p99": None},
        "shed_rate": 0.0, "retry_after_s": retry_after,
        "goodput_tokens_per_s": 0.0, "throughput_tokens_per_s": 0.0,
        "mfu": None, "decode_steps": serve_step, "serve_step": serve_step,
        "uptime_s": 1.0,
    }


class FakeReplica:
    def __init__(self, rid, **feed_kw):
        self.id = rid
        self.alive = True
        self.feed_kw = dict(feed_kw)
        self.step = 0
        self.inflight = {}
        self.done = {}

    def poll_router(self):
        if not self.alive:
            raise ReplicaUnreachable("dead")
        self.step += 1
        return _feed(self.id, serve_step=self.step,
                     inflight=len(self.inflight), **self.feed_kw)

    def submit(self, payload):
        if not self.alive:
            raise ReplicaUnreachable("dead")
        self.inflight[payload["rid"]] = payload
        return {"accepted": True, "queue_depth": 0, "retry_after_s": 0.01}

    def outcomes(self):
        if not self.alive:
            raise ReplicaUnreachable("dead")
        return {"outcomes": dict(self.done)}

    def finish(self, rid, status="completed", **extra):
        p = self.inflight.pop(rid, {"max_new_tokens": 1})
        self.done[str(rid)] = {
            "status": status,
            "tokens": [5] * p.get("max_new_tokens", 1) if status == "completed" else [],
            "replays": 0, "tag": p.get("tag"), **extra,
        }

    def finish_all(self):
        for rid in list(self.inflight):
            self.finish(rid)


def make_router(replicas, **kw):
    t = [0.0]
    defaults = dict(
        poll_interval_s=0.0, breaker_failures=2, breaker_cooldown_s=1.0,
        health_stale_s=0.0, dispatch_retries=3, backoff_s=0.01,
        backoff_max_s=0.1, hedge_s=0.0,
        now_fn=lambda: t[0], sleep_fn=lambda s: t.__setitem__(0, t[0] + s),
    )
    defaults.update(kw)
    fr = FleetRouter(**defaults)
    for r in replicas:
        fr.add_replica(r.id, r)
    return fr, t


def _req(rid, max_new=2):
    return Request(rid=rid, prompt=(1, 2), max_new_tokens=max_new)


@pytest.fixture
def profiler():
    """A fresh ndtimeline manager per test, dormant again afterwards."""
    mgr = nd_api.init_ndtimers(rank=0)
    try:
        yield mgr
    finally:
        nd_api.deinit_ndtimers()


# ================================================= replica-qualified merge
def test_merge_traces_replica_qualified_streams_do_not_collide():
    # two replicas, BOTH rank 0 — the collision the satellite fixes
    a = [Span("serve-prefill", 10.0, 0.5, 0, 0, {"rid": 1})]
    b = [Span("serve-prefill", 10.2, 0.5, 0, 0, {"rid": 2})]
    merged = merge_traces({"r0": a, "r1": b})
    assert {s.rank for s in merged} == {0, 1}  # distinct pid lanes
    assert {s.tags["stream"] for s in merged} == {"r0", "r1"}
    assert stream_process_names({"r0": a, "r1": b}) == {0: "r0", 1: "r1"}
    # per-stream clock offsets by the SAME key
    merged = merge_traces({"r0": a, "r1": b}, clock={"r1": 0.2})
    starts = {s.tags["stream"]: s.start for s in merged}
    assert starts["r0"] == 10.0 and starts["r1"] == pytest.approx(10.0)
    # int-keyed mapping keeps the historic rank==pid behavior
    old = merge_traces({0: a, 1: b})
    assert {s.rank for s in old} == {0, 1}
    assert all("stream" not in (s.tags or {}) for s in old)


def test_chrome_trace_handler_renders_flow_lists(tmp_path):
    s = Span("serve-submit", 1.0, 0.0, 0, 1,
             {"rid": 3, "flow_id": ["req3", "disp9"], "flow_role": ["send", "recv"]})
    h = ChromeTraceHandler(str(tmp_path / "t.json"))
    h([s])
    h.write()
    events = load_perfetto(str(tmp_path / "t.json"))["traceEvents"]
    flows = {(e["ph"], e["id"]) for e in events if e.get("ph") in ("s", "f")}
    assert flows == {("s", "req3"), ("f", "disp9")}
    # the duration event round-trips its tags (lists intact)
    [back] = spans_from_perfetto(str(tmp_path / "t.json"))
    assert back.tags["flow_id"] == ["req3", "disp9"]


# ==================================================== router journey spans
def test_router_emits_journey_chain(profiler):
    a = FakeReplica("a")
    fr, _ = make_router([a])
    rec = fr.submit(_req(1))
    a.finish_all()
    fr.pump()
    assert rec.status == "completed"
    spans = profiler.flush()
    by_metric = {}
    for s in spans:
        by_metric.setdefault(s.metric, []).append(s)
    assert len(by_metric[P.FLEET_SUBMIT]) == 1
    [d] = by_metric[P.FLEET_DISPATCH]
    assert d.tags["replica"] == "a" and d.tags["kind"] == "dispatch"
    assert d.tags["ok"] is True and "score" in d.tags
    assert d.tags["tag"] == rec.tag_by_replica["a"]
    [t] = by_metric[P.FLEET_TERMINAL]
    assert t.tags["outcome"] == "completed" and t.tags["failovers"] == 0
    assert t.tags["flow_id"] == "fleet1" and t.tags["flow_role"] == "recv"
    assert not fleettrace.verify_fleet_journeys(spans, fr.ledger)


def test_failover_journey_has_failovers_plus_one_subchains(profiler):
    a, b = FakeReplica("a"), FakeReplica("b")
    fr, t = make_router([a, b])
    recs = [fr.submit(_req(i)) for i in range(4)]
    on_a = [r for r in recs if r.live_on == ["a"]]
    assert on_a
    a.alive = False
    t[0] += 0.01
    fr.pump()
    fr.pump()  # breaker opens -> failover
    b.finish_all()
    assert fr.pump() == 0
    fr.fleet_ledger_check()
    spans = profiler.flush()
    assert not fleettrace.verify_fleet_journeys(spans, fr.ledger)
    # the failed-over rids carry exactly failovers+1 = 2 dispatch
    # sub-chains, one tagged kind=failover
    for rec in on_a:
        assert rec.failovers == 1
        placed = [s for s in spans if s.metric == P.FLEET_DISPATCH
                  and s.tags["rid"] == rec.req.rid and s.tags.get("ok", True)]
        assert len(placed) == 2
        assert [s.tags["kind"] for s in placed].count("failover") == 1
    # dropping one dispatch span breaks verification loudly
    victim = on_a[0].req.rid
    pruned = [s for s in spans
              if not (s.metric == P.FLEET_DISPATCH and s.tags["rid"] == victim
                      and s.tags["kind"] == "failover")]
    problems = fleettrace.verify_fleet_journeys(pruned, fr.ledger)
    assert any(f"rid {victim}" in p and "dispatch sub-chains" in p for p in problems)
    # the superseded classification: rids re-driven off a resolve elsewhere
    for rec in on_a:
        assert rec.req.rid in fleettrace.superseded_rids(fr.ledger, "a")
        assert rec.req.rid not in fleettrace.superseded_rids(fr.ledger, "b")


def test_breaker_transition_spans_ordered(profiler):
    a, b = FakeReplica("a"), FakeReplica("b")
    fr, t = make_router([a, b], breaker_cooldown_s=1.0)
    fr.poll(force=True)
    a.alive = False
    fr.poll(force=True)
    fr.poll(force=True)  # 2 failures -> OPEN
    t[0] += 1.1
    fr.poll(force=True)  # OPEN -> HALF_OPEN probe, still dead -> re-OPEN
    a.alive = True
    t[0] += 1.1
    fr.poll(force=True)  # probe succeeds -> CLOSED
    assert fr.replicas["a"].breaker.state == CircuitBreaker.CLOSED
    walks = [(s.tags["from"], s.tags["to"]) for s in profiler.flush()
             if s.metric == P.FLEET_BREAKER and s.tags["replica"] == "a"]
    assert walks == [
        ("closed", "open"),
        ("open", "half_open"),
        ("half_open", "open"),
        ("open", "half_open"),
        ("half_open", "closed"),
    ]
    # the same walk is served as /fleet's breaker_transitions history
    hist = [(h["from"], h["to"]) for h in fr.breaker_transitions
            if h["replica"] == "a"]
    assert hist == walks


def test_hedge_first_terminal_wins_loser_superseded(profiler):
    slow, fast = FakeReplica("slow"), FakeReplica("fast", queue=1)
    fr, t = make_router([slow, fast], hedge_s=2.0)
    rec = fr.submit(_req(1))
    assert rec.live_on == ["slow"]
    t[0] += 3.0
    fr.pump()  # hedge placed on fast
    fast.finish(1)
    fr.pump()
    assert rec.status == "completed" and rec.replica == "fast"
    slow.finish(1)  # the loser completing later changes nothing
    fr.pump()
    fr.fleet_ledger_check()
    spans = profiler.flush()
    assert not fleettrace.verify_fleet_journeys(spans, fr.ledger)
    kinds = [s.tags["kind"] for s in spans if s.metric == P.FLEET_DISPATCH
             and s.tags["rid"] == 1]
    assert kinds == ["dispatch", "hedge"]
    # the loser attempt's chain is marked superseded, the winner's is not
    assert fleettrace.superseded_rids(fr.ledger, "slow") == {1}
    assert fleettrace.superseded_rids(fr.ledger, "fast") == set()


# ============================================ superseded chain verification
def _chain(rid, tag=None, terminal=None, t0=100.0):
    tags = {"rid": rid, "flow_id": f"req{rid}", "flow_role": "send"}
    if tag is not None:
        tags["tag"] = tag
    spans = [
        Span(P.SERVE_SUBMIT, t0, 0.0, 0, 0, tags),
        Span(P.SERVE_QUEUE_WAIT, t0 + 0.1, 0.1, 0, 0, {"rid": rid, "slot": 0, "stage": 0}),
        Span(P.SERVE_PREFILL, t0 + 0.2, 0.1, 0, 0, {"rid": rid, "slot": 0, "stage": 0}),
    ]
    if terminal is not None:
        spans.append(Span(P.SERVE_TERMINAL, t0 + 0.5, 0.0, 0, 0,
                          {"rid": rid, "outcome": terminal, "tokens": 1,
                           "flow_id": f"req{rid}", "flow_role": "recv"}))
    return spans


def test_stranded_chain_classifies_superseded_instead_of_orphan():
    stranded = _chain(7)  # no terminal: the replica died mid-request
    # without the failover context this is an orphan — a real failure
    assert any("orphan" in p for p in verify_request_chains(stranded, {}))
    assert classify_chains(stranded, {}) == {7: "orphan"}
    # with it, the chain classifies superseded-by-failover and verifies
    assert verify_request_chains(stranded, {}, superseded={7}) == []
    assert classify_chains(stranded, {}, superseded={7}) == {7: "superseded-by-failover"}
    # a partitioned replica may even hold a LATE terminal row + chain for
    # a rid the fleet resolved elsewhere: still exempt
    late = _chain(8, terminal="completed")
    ledger_row = {8: {"status": "timed_out", "tokens": [], "replays": 0}}
    assert any("terminal" in p for p in verify_request_chains(late, ledger_row))
    assert verify_request_chains(late, ledger_row, superseded={8}) == []
    # normal chains still verify strictly alongside superseded ones
    good = _chain(9, terminal="completed", t0=200.0)
    outcomes = {9: {"status": "completed", "tokens": [1], "replays": 0}}
    assert verify_request_chains(good + stranded, outcomes, superseded={7}) == []


# ================================================== the timeline assembler
def test_assemble_fleet_timeline_stitches_cross_process_flows(tmp_path):
    router_spans = [
        Span(P.FLEET_SUBMIT, 10.0, 0.0, 0, 0,
             {"rid": 1, "flow_id": "fleet1", "flow_role": "send"}),
        Span(P.FLEET_DISPATCH, 10.1, 0.01, 0, 0,
             {"rid": 1, "replica": "r0", "tag": 7, "kind": "dispatch", "ok": True}),
        Span(P.FLEET_TERMINAL, 11.0, 0.0, 0, 0,
             {"rid": 1, "outcome": "completed", "tokens": 1, "failovers": 0,
              "flow_id": "fleet1", "flow_role": "recv"}),
    ]
    replica_spans = _chain(1, tag=7, terminal="completed", t0=10.3)
    streams = {"router": router_spans, "r0": replica_spans}
    merged = fleettrace.assemble_fleet_timeline(streams)
    sub = next(s for s in merged if s.metric == P.SERVE_SUBMIT)
    disp = next(s for s in merged if s.metric == P.FLEET_DISPATCH)
    assert sub.tags["flow_id"] == ["req1", "disp7"]
    assert sub.tags["flow_role"] == ["send", "recv"]
    assert disp.tags["flow_id"] == "disp7" and disp.tags["flow_role"] == "send"
    path = str(tmp_path / "fleet.json")
    write_perfetto(merged, path,
                   process_names=fleettrace.fleet_process_names(streams))
    events = load_perfetto(path)["traceEvents"]
    disp_flow_pids = {e["pid"] for e in events
                     if e.get("ph") in ("s", "f") and e.get("id") == "disp7"}
    assert len(disp_flow_pids) == 2  # the arrow CROSSES process lanes
    lanes = {e["args"]["name"] for e in events
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    assert lanes == {"router", "r0"}


def test_fleet_clock_sync_offsets_and_merge_alignment():
    class _ClockClient:
        def __init__(self, skew_us):
            self.skew = skew_us

        def poll_health(self):
            return {"wall_time_us": int(time.time() * 1e6 + self.skew)}

    class _Legacy:  # pre-wall_time_us replica: no estimate, no crash
        def poll_health(self):
            return {"ok": True}

    cs = fleettrace.estimate_fleet_clock_offsets(
        {"r0": _ClockClient(2_500_000), "r1": _ClockClient(-1_000_000),
         "old": _Legacy()},
        rounds=5,
    )
    assert cs.offsets_us["r0"] == pytest.approx(2_500_000, abs=50_000)
    assert cs.offsets_us["r1"] == pytest.approx(-1_000_000, abs=50_000)
    assert "old" not in cs.offsets_us and cs.residual_us["old"] == -1.0
    assert cs.offset_s("router") == 0.0  # unknown streams align at 0
    # merge applies the offsets per stream key
    now = time.time()
    streams = {"router": [Span("x", now, 0.1, 0, 0, None)],
               "r0": [Span("y", now + 2.5, 0.1, 0, 0, None)]}
    merged = merge_traces(streams, clock=cs)
    starts = {s.tags["stream"]: s.start for s in merged}
    assert abs(starts["r0"] - starts["router"]) < 0.1
    # round trip
    back = fleettrace.FleetClockSync.from_dict(
        json.loads(json.dumps(cs.as_dict()))
    )
    assert back.offsets_us == cs.offsets_us


def test_estimate_fleet_clock_offsets_over_http():
    srv = ops_server.OpsServer(port=reserve_port()).start()
    try:
        srv.register("healthz",
                     lambda: {"ok": True, "wall_time_us": int(time.time() * 1e6)})
        from vescale_tpu.serve import HttpReplicaClient

        cs = fleettrace.estimate_fleet_clock_offsets(
            {"r0": HttpReplicaClient(srv.url, timeout_s=2.0)}, rounds=4
        )
        # same host, same clock: offset ~0, bounded by the reported residual
        assert abs(cs.offsets_us["r0"]) <= max(cs.residual_us["r0"], 2_000.0)
    finally:
        srv.stop()


# ======================================================== /fleet endpoint
def test_fleet_feed_schema_frozen_and_roundtrips(monkeypatch):
    a, b = FakeReplica("a"), FakeReplica("b", queue=2)
    fr, t = make_router([a, b])
    fr.poll(force=True)
    rec = fr.submit(_req(1))
    # breaker churn so the history tail is non-empty
    b.alive = False
    t[0] += 0.01
    fr.poll(force=True)
    fr.poll(force=True)
    feed = fr.obs.fleet()
    assert set(feed) == FLEET_FIELDS
    assert feed["schema_version"] == FLEET_SCHEMA_VERSION
    for row in feed["replicas"].values():
        assert set(row) == FLEET_REPLICA_FIELDS
    assert feed["replicas"]["b"]["breaker"] == "open"
    assert feed["breaker_transitions"][-1]["to"] == "open"
    assert feed["pending_requests"] == 1

    # ---- served over the router's own ops endpoint, schema intact
    monkeypatch.delenv("VESCALE_FLEET_OPS_PORT", raising=False)
    assert fr.start_ops() is None  # unset knob = literal no-op
    srv = fr.start_ops(port=reserve_port())
    try:
        with urllib.request.urlopen(f"{srv.url}/fleet", timeout=5) as resp:
            wire = json.loads(resp.read())
        assert set(wire) == FLEET_FIELDS
        for row in wire["replicas"].values():
            assert set(row) == FLEET_REPLICA_FIELDS
        with urllib.request.urlopen(f"{srv.url}/healthz", timeout=5) as resp:
            health = json.loads(resp.read())
        assert health["role"] == "router" and "wall_time_us" in health
    finally:
        fr.stop_ops()
    a.finish_all()
    fr.pump()
    assert rec.status == "completed"


def test_fleet_timeline_gauges_and_dashboard_block():
    from vescale_tpu import telemetry

    a = FakeReplica("a")
    fr, _ = make_router([a])
    telemetry.init(out_dir=None, memtrack=False)
    try:
        fr.submit(_req(1))
        a.finish_all()
        fr.pump()
        reg = telemetry.get_registry()
        snap = reg.snapshot()
        assert "fleet_timeline_goodput_tokens_per_s" in snap["gauges"]
        assert "fleet_timeline_shed_rate" in snap["gauges"]
        dash = telemetry.dashboard()
        assert "fleet-timeline:" in dash and "fleet:" in dash
        # the fleet-timeline gauges render in THEIR block, not fleet:
        fleet_block = dash.split("fleet-timeline:")[1]
        assert "fleet_timeline_goodput_tokens_per_s" in fleet_block
    finally:
        telemetry.shutdown()


def test_fleet_observability_slo_burn_rate():
    a = FakeReplica("a", p99=0.5)
    fr, _ = make_router([a])
    fr.poll(force=True)
    fr.obs.slo_ttft_s = 0.25
    feed = fr.obs.fleet()
    assert feed["ttft_p99_s"] == 0.5
    assert feed["slo_burn_rate"] == pytest.approx(2.0)  # burning 2x budget
    fr.obs.slo_ttft_s = 0.0
    assert fr.obs.fleet()["slo_burn_rate"] is None  # no SLO, no burn claim


# ===================================================== replica persistence
def test_loop_trace_persistence_scoped_no_handler_leak(tmp_path, monkeypatch):
    """Two sequential traced serve runs in one process: each run's spans
    land exactly once (no duplicated handler), and the loop restores the
    dormant profiler state it found (regression: the LocalRawHandler and
    the self-initialized manager used to leak across runs)."""
    import jax
    import numpy as np

    from vescale_tpu.mesh import DeviceMesh
    from vescale_tpu.ndtimeline.parser_handler import parse_raw_spans
    from vescale_tpu.serve import (
        ContinuousBatchingScheduler,
        DecodeStep,
        KVCacheConfig,
        PagedKVCache,
        ServeEngine,
        run_serve_resilient,
    )

    class _NopEngine:
        greedy = staticmethod(ServeEngine.greedy)

        def __init__(self, slots, vocab=8):
            self._p = np.zeros((vocab,), np.float32)
            self._d = DecodeStep(np.zeros((slots,), np.int32), np.zeros((slots, vocab), np.float32))

        def prefill(self, prompt, slot):
            return self._p

        def decode(self, tokens):
            return self._d

    monkeypatch.setenv("VESCALE_FLEET_TRACE_DIR", str(tmp_path))
    monkeypatch.setenv("VESCALE_SERVE_REPLICA_ID", "tr0")
    assert not nd_api.is_active()
    for rid in (1, 2):
        mesh = DeviceMesh(("tp",), (1,), devices=jax.devices()[:1])
        kc = KVCacheConfig(layers=1, kv_heads=1, head_dim=1, num_slots=2,
                           page_size=8, pages_per_slot=8)
        cache = PagedKVCache(kc, mesh)
        sched = ContinuousBatchingScheduler(cache, max_queue=8)
        res = run_serve_resilient(
            engine=_NopEngine(2), scheduler=sched,
            arrivals=[(0, _req(rid, max_new=3))],
            install_signal_handlers=False, coordinate=False,
        )
        assert res.status == "completed"
        # the loop owned the profiler: dormant again after every run
        assert not nd_api.is_active()
    spans = parse_raw_spans(str(tmp_path / "tr0.spans.jsonl"))
    subs = [s for s in spans if s.metric == P.SERVE_SUBMIT]
    # one submit per rid across BOTH runs — a leaked handler would
    # double-write run 2's spans
    assert sorted(s.tags["rid"] for s in subs) == [1, 2]
    terms = [s.tags["rid"] for s in spans if s.metric == P.SERVE_TERMINAL]
    assert sorted(terms) == [1, 2]


# ============================================================== knobs/wiring
def test_fleet_trace_knobs_registered():
    assert envreg.lookup("VESCALE_FLEET_TRACE_DIR").type == "str"
    assert envreg.lookup("VESCALE_FLEET_TRACE_FLUSH_EVERY").default == 1
    assert envreg.lookup("VESCALE_FLEET_OPS_PORT").default is None


def test_fleet_trace_smoke_script():
    """tier-1 wiring of scripts/fleet_trace_smoke.py: the kill+rejoin
    battery rendered as ONE stitched fleet timeline, round-tripped and
    journey-verified against the balanced fleet ledger — the ISSUE 14
    acceptance run."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "fleet_trace_smoke.py")],
        capture_output=True,
        text=True,
        timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, (
        f"stdout:\n{out.stdout[-3000:]}\nstderr:\n{out.stderr[-3000:]}"
    )
    assert "FLEET TRACE SMOKE OK" in out.stdout
