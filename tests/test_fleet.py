"""Fleet-level resilient serving (ISSUE 13): the multi-replica router's
circuit-breaker state machine, consistent-hash session affinity under
churn, the zero-loss fleet ledger with resubmissions, faked-feed dispatch
and failover (no sockets), the new faultsim kinds (replica_kill /
poll_blackhole), ops-server hardening (Retry-After, atomic bodies, the
fleet endpoints), the inbox-fed serve loop, and the tier-1 wiring of
scripts/fleet_smoke.py."""

import json
import os
import pathlib
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

import jax
import jax.numpy as jnp

from vescale_tpu.mesh import DeviceMesh
from vescale_tpu.models.llama import Llama, LlamaConfig
from vescale_tpu.resilience import faultsim
from vescale_tpu.serve import (
    CircuitBreaker,
    ConsistentHashRing,
    ContinuousBatchingScheduler,
    DecodeStep,
    FleetLedger,
    FleetRouter,
    HttpReplicaClient,
    KVCacheConfig,
    PagedKVCache,
    Request,
    RequestInbox,
    ServeEngine,
    run_serve_resilient,
    serve_replica,
)
from vescale_tpu.serve.router import (
    FleetRecord,
    ReplicaUnreachable,
    request_from_payload,
    request_payload,
)
from vescale_tpu.telemetry import ops_server
from vescale_tpu.testing import reserve_port

REPO = str(pathlib.Path(__file__).resolve().parent.parent)


# ============================================================== fakes
def _feed(replica_id, *, queue=0, inflight=0, slots=4, p99=None, accepting=True,
          draining=False, serve_step=1, retry_after=0.01, schema=2):
    out = {
        "schema_version": schema,
        "rank": 0,
        "draining": draining,
        "queue_depth": queue,
        "inflight": inflight,
        "slots": slots,
        "free_slots": max(0, slots - inflight),
        "pages": 16,
        "free_pages": 16,
        "ttft_s": {"p50": None, "p95": None, "p99": p99},
        "itl_s": {"p50": None, "p95": None, "p99": None},
        "shed_rate": 0.0,
        "retry_after_s": retry_after,
        "goodput_tokens_per_s": 0.0,
        "throughput_tokens_per_s": 0.0,
        "mfu": None,
        "decode_steps": serve_step,
        "serve_step": serve_step,
        "uptime_s": 1.0,
    }
    if schema >= 2:
        out["replica_id"] = replica_id
        out["accepting"] = accepting
    return out


class FakeReplica:
    """In-memory replica: a /router feed plus scripted submit/outcome
    behavior — the no-sockets substrate of every router unit test."""

    def __init__(self, rid, **feed_kw):
        self.id = rid
        self.alive = True
        self.feed_kw = dict(feed_kw)
        self.step = 0
        self.advance = True
        self.inflight = {}
        self.done = {}
        self.submit_response = None  # override: dict returned by submit

    def poll_router(self):
        if not self.alive:
            raise ReplicaUnreachable("dead")
        if self.advance:
            self.step += 1
        return _feed(self.id, serve_step=self.step,
                     inflight=len(self.inflight), **self.feed_kw)

    def submit(self, payload):
        if not self.alive:
            raise ReplicaUnreachable("dead")
        if self.submit_response is not None:
            return dict(self.submit_response)
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
            "replays": 0,
            **extra,
        }

    def finish_all(self):
        for rid in list(self.inflight):
            self.finish(rid)


def make_router(replicas, **kw):
    """A FleetRouter on a fake clock (time never passes unless the test
    advances it) — every decision becomes deterministic."""
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


# ==================================================== circuit breaker
def test_breaker_state_machine_closed_open_halfopen_closed():
    t = [0.0]
    b = CircuitBreaker(failures=3, cooldown_s=2.0, now_fn=lambda: t[0])
    assert b.state == CircuitBreaker.CLOSED and b.dispatchable
    b.record_failure()
    b.record_failure()
    assert b.state == CircuitBreaker.CLOSED  # under threshold
    b.record_failure()
    assert b.state == CircuitBreaker.OPEN and not b.dispatchable
    assert b.opens == 1
    # cooling: polls are skipped
    assert b.poll_disposition() == "skip"
    t[0] = 1.9
    assert b.poll_disposition() == "skip"
    # cooldown elapsed: the next poll is the half-open probe
    t[0] = 2.0
    assert b.poll_disposition() == "probe"
    assert b.state == CircuitBreaker.HALF_OPEN and not b.dispatchable
    b.record_success()
    assert b.state == CircuitBreaker.CLOSED and b.closes == 1
    # success resets the consecutive counter
    b.record_failure()
    b.record_failure()
    assert b.state == CircuitBreaker.CLOSED


def test_breaker_probe_failure_reopens_with_fresh_cooldown():
    t = [0.0]
    b = CircuitBreaker(failures=1, cooldown_s=1.0, now_fn=lambda: t[0])
    b.record_failure()
    assert b.state == CircuitBreaker.OPEN
    t[0] = 1.0
    assert b.poll_disposition() == "probe"
    b.record_failure()  # the probe fails
    assert b.state == CircuitBreaker.OPEN and b.reopens == 1
    # the cooldown restarted at the probe failure, not the first open
    t[0] = 1.5
    assert b.poll_disposition() == "skip"
    t[0] = 2.0
    assert b.poll_disposition() == "probe"
    b.record_success()
    assert b.state == CircuitBreaker.CLOSED


# ================================================= consistent hashing
def test_ring_affinity_stable_under_churn():
    r = ConsistentHashRing()
    for n in ("a", "b", "c"):
        r.add(n)
    keys = [f"sess{i}" for i in range(200)]
    all3 = ("a", "b", "c")
    before = {k: r.lookup(k, all3) for k in keys}
    assert set(before.values()) == {"a", "b", "c"}  # all nodes used
    # b leaves (outage): ONLY b's keys remap
    during = {k: r.lookup(k, ("a", "c")) for k in keys}
    for k in keys:
        if before[k] != "b":
            assert during[k] == before[k], k
    # b heals: its sessions come home exactly
    after = {k: r.lookup(k, all3) for k in keys}
    assert after == before


def test_ring_lookup_edge_cases():
    r = ConsistentHashRing()
    assert r.lookup("x", ("a",)) is None  # empty ring
    r.add("a")
    assert r.lookup("x", ()) is None  # nothing eligible
    assert r.lookup("x", ("a",)) == "a"
    r.remove("a")
    assert r.nodes() == ()


# ========================================================= fleet ledger
def test_fleet_ledger_check_balances_with_resubmissions():
    led = FleetLedger()
    r1 = FleetRecord(req=_req(1))
    led.submitted(r1)
    led.resolve(r1, "shed", {"status": "shed", "tokens": []}, None, 0.0)
    # same rid comes back after its terminal shed: a RESUBMISSION
    r1b = FleetRecord(req=_req(1))
    led.submitted(r1b)
    led.resolve(r1b, "completed", {"status": "completed", "tokens": [1]}, "A", 1.0)
    r2 = FleetRecord(req=_req(2))
    led.submitted(r2)
    led.resolve(r2, "completed", {"status": "completed", "tokens": [2]}, "B", 1.0)
    led.check()
    assert led.counts["submitted"] == 3 and led.counts["resubmitted"] == 1
    assert led.counts["completed"] == 2 and led.counts["shed"] == 1


def test_fleet_ledger_rejects_duplicate_pending_and_unresolved():
    led = FleetLedger()
    rec = FleetRecord(req=_req(7))
    led.submitted(rec)
    with pytest.raises(ValueError, match="duplicate fleet request id 7"):
        led.submitted(FleetRecord(req=_req(7)))
    with pytest.raises(AssertionError, match="unresolved"):
        led.check()
    # first terminal wins; a late second outcome is a no-op
    assert led.resolve(rec, "completed", {"status": "completed", "tokens": []}, "A", 0.0)
    assert not led.resolve(rec, "timed_out", None, "B", 1.0)
    assert rec.status == "completed"
    led.check()


# ==================================================== faked-feed router
def test_least_loaded_scoring_prefers_empty_low_latency_replica():
    empty = FakeReplica("empty")
    busy = FakeReplica("busy", queue=6)
    slow = FakeReplica("slow", p99=5.0)
    fr, _ = make_router([busy, empty, slow])
    fr.poll(force=True)
    assert fr.pick().id == "empty"
    # scoring is inspectable: backlog/slots + p99 seconds
    assert FleetRouter.score(_feed("x", queue=6)) > FleetRouter.score(_feed("x"))
    assert FleetRouter.score(_feed("x", p99=5.0)) > FleetRouter.score(_feed("x"))


def test_draining_replica_excluded_v1_and_v2_feeds():
    v2 = FakeReplica("v2", accepting=False)
    v1 = FakeReplica("v1", draining=True, schema=1)
    ok = FakeReplica("ok")
    fr, _ = make_router([v2, v1, ok])
    fr.poll(force=True)
    # both exclusion signals honored: v2 `accepting`, v1 fallback `draining`
    assert [h.id for h in fr._eligible()] == ["ok"]
    rec = fr.submit(_req(1))
    assert rec.live_on == ["ok"]


def test_dispatch_retries_next_replica_on_submit_failure():
    flaky = FakeReplica("flaky")
    flaky.submit_response = None
    good = FakeReplica("good", queue=1)  # worse score: picked second
    fr, _ = make_router([flaky, good], breaker_failures=5)

    def dead_submit(payload):
        raise ReplicaUnreachable("connection refused")

    flaky.submit = dead_submit
    rec = fr.submit(_req(1))
    assert rec.pending and rec.live_on == ["good"]
    # the failed submit counted, then the healthy re-poll reset the
    # streak — a flaky submit path alone must not open the breaker
    assert fr.replicas["flaky"].breaker.state == CircuitBreaker.CLOSED
    good.finish_all()
    fr.pump()
    fr.fleet_ledger_check()
    assert rec.status == "completed"


def test_replica_death_fails_over_inflight_requests():
    a, b = FakeReplica("a"), FakeReplica("b")
    fr, t = make_router([a, b])
    recs = [fr.submit(_req(i)) for i in range(4)]
    on_a = [r for r in recs if r.live_on == ["a"]]
    assert on_a, "least-loaded should have used both replicas"
    a.alive = False
    t[0] += 0.01
    fr.pump()
    fr.pump()  # second failure crosses the threshold -> open -> failover
    assert fr.replicas["a"].breaker.state == CircuitBreaker.OPEN
    for r in recs:
        assert r.pending and r.live_on == ["b"], (r.req.rid, r.live_on)
    for r in on_a:
        assert r.failovers == 1 and r.resubmissions == 1
    b.finish_all()
    assert fr.pump() == 0
    fr.fleet_ledger_check()
    c = fr.ledger.counts
    assert c["completed"] == 4 and c["failovers"] == len(on_a)
    assert c["redispatched"] == len(on_a) and c["resubmitted"] == 0


def test_dead_replica_readmitted_via_half_open_probe():
    a, b = FakeReplica("a"), FakeReplica("b")
    fr, t = make_router([a, b], breaker_cooldown_s=1.0)
    fr.poll(force=True)
    a.alive = False
    fr.poll(force=True)
    fr.poll(force=True)
    assert fr.replicas["a"].breaker.state == CircuitBreaker.OPEN
    # probe while still dead: re-opens
    t[0] += 1.1
    fr.poll(force=True)
    assert fr.replicas["a"].breaker.state == CircuitBreaker.OPEN
    assert fr.replicas["a"].breaker.reopens == 1
    # heals: the next probe readmits
    a.alive = True
    t[0] += 1.1
    fr.poll(force=True)
    assert fr.replicas["a"].breaker.state == CircuitBreaker.CLOSED
    rec = fr.submit(_req(9), session="s")  # dispatchable again
    assert rec.live_on in (["a"], ["b"])


def test_stale_serve_step_trips_breaker():
    wedged = FakeReplica("wedged")
    wedged.advance = False  # reachable, but serve_step frozen
    ok = FakeReplica("ok")
    fr, t = make_router([wedged, ok], health_stale_s=5.0, breaker_failures=1)
    fr.poll(force=True)  # baseline observation
    t[0] += 6.0
    fr.poll(force=True)
    assert fr.replicas["wedged"].breaker.state == CircuitBreaker.OPEN
    assert fr.replicas["ok"].breaker.state == CircuitBreaker.CLOSED


def test_replica_shed_outcome_spills_to_peer_and_backs_off():
    a, b = FakeReplica("a"), FakeReplica("b", queue=1)
    fr, t = make_router([a, b])
    rec = fr.submit(_req(1))
    assert rec.live_on == ["a"]
    a.done["1"] = {"status": "shed", "tokens": [], "retry_after_s": 3.0}
    fr.pump()
    # spilled to b, and a is backed off for its own hint
    assert rec.pending and rec.live_on == ["b"]
    assert fr.replicas["a"].backoff_until == pytest.approx(t[0] + 3.0)
    assert rec.resubmissions == 1
    b.finish(1)
    fr.pump()
    fr.fleet_ledger_check()
    assert rec.status == "completed"


def test_fleet_sheds_only_when_every_healthy_replica_sheds():
    a = FakeReplica("a", accepting=False)
    b = FakeReplica("b", accepting=False)
    fr, _ = make_router([a, b])
    rec = fr.submit(_req(1))
    assert rec.status == "shed"
    assert "every healthy replica shedding" in rec.outcome["reason"]
    fr.fleet_ledger_check()
    # one replica accepting again -> no fleet shed
    b.feed_kw["accepting"] = True
    rec2 = fr.submit(_req(2))
    assert rec2.pending and rec2.live_on == ["b"]


def test_drain_outcome_redispatches_to_peer():
    a, b = FakeReplica("a"), FakeReplica("b", queue=1)
    fr, _ = make_router([a, b])
    rec = fr.submit(_req(1))
    assert rec.live_on == ["a"]
    # a drains: the queued request comes back re-queueable
    a.done["1"] = {"status": "preempted_requeue", "tokens": [], "replays": 0}
    a.feed_kw["accepting"] = False
    a.feed_kw["draining"] = True
    fr.pump()
    assert rec.pending and rec.live_on == ["b"]
    b.finish(1)
    fr.pump()
    fr.fleet_ledger_check()


def test_stale_outcome_from_prior_dispatch_is_ignored():
    """Regression: when a rid bounces A -> B -> back to A, A's ledger
    still holds the terminal row of the FIRST dispatch until the new
    submission drains; the router's tag gate must ignore that stale row
    instead of shedding/redispatching a request A is about to serve."""
    a, b = FakeReplica("a"), FakeReplica("b", queue=1)
    fr, t = make_router([a, b])
    rec = fr.submit(_req(1))
    assert rec.live_on == ["a"]
    tag1 = rec.tag_by_replica["a"]
    # A sheds attempt 1 (row persists in A's outcomes), router spills to B
    a.done["1"] = {"status": "shed", "tokens": [], "retry_after_s": 0.2,
                   "tag": tag1}
    fr.pump()
    assert rec.pending and rec.live_on == ["b"]
    # B sheds too; A's backoff elapsed -> redispatch lands back on A
    b.done["1"] = {"status": "shed", "tokens": [], "retry_after_s": 0.2,
                   "tag": rec.tag_by_replica["b"]}
    t[0] += 1.0
    fr.pump()
    assert rec.pending and rec.live_on == ["a"]
    tag3 = rec.tag_by_replica["a"]
    assert tag3 != tag1
    # A's /outcomes STILL shows the stale attempt-1 shed row (the new
    # submission sits in its inbox): the tag gate must skip it
    fr.pump()
    assert rec.pending and rec.live_on == ["a"], (rec.status, rec.live_on)
    # the new attempt completes with its own tag: resolved normally
    a.done["1"] = {"status": "completed", "tokens": [9, 9], "replays": 0,
                   "tag": tag3}
    fr.pump()
    assert rec.status == "completed" and rec.outcome["tokens"] == [9, 9]
    fr.fleet_ledger_check()


def test_replica_timed_out_outcome_is_final():
    a, b = FakeReplica("a"), FakeReplica("b", queue=1)
    fr, _ = make_router([a, b])
    rec = fr.submit(_req(1))
    a.done["1"] = {"status": "timed_out", "tokens": [7], "replays": 0}
    fr.pump()
    # the request's own deadline expired: never re-driven elsewhere
    assert rec.status == "timed_out" and rec.replica == "a"
    fr.fleet_ledger_check()


def test_fleet_deadline_times_out_and_supersedes_late_outcome():
    a = FakeReplica("a")
    fr, t = make_router([a])
    rec = fr.submit(_req(1), deadline_s=5.0)
    t[0] = 6.0
    fr.pump()
    assert rec.status == "timed_out"
    assert rec.outcome["reason"] == "fleet deadline"
    # the replica finishes late: first-terminal-wins ignores it
    a.finish(1)
    fr.pump()
    assert rec.status == "timed_out"
    fr.fleet_ledger_check()


def test_hedge_places_second_copy_first_outcome_wins():
    slow, fast = FakeReplica("slow"), FakeReplica("fast", queue=1)
    fr, t = make_router([slow, fast], hedge_s=2.0)
    rec = fr.submit(_req(1))
    assert rec.live_on == ["slow"]
    t[0] += 3.0
    fr.pump()
    assert sorted(rec.live_on) == ["fast", "slow"] and rec.hedged
    fast.finish(1)
    fr.pump()
    assert rec.status == "completed" and rec.replica == "fast"
    # the slow copy completing later changes nothing
    slow.finish(1)
    fr.pump()
    assert rec.replica == "fast"
    fr.fleet_ledger_check()
    assert fr.ledger.counts["hedges"] == 1


def test_session_affinity_routes_consistently():
    a, b, c = FakeReplica("a"), FakeReplica("b"), FakeReplica("c")
    fr, _ = make_router([a, b, c])
    fr.poll(force=True)
    first = fr.pick(session="user-42").id
    for _ in range(5):
        assert fr.pick(session="user-42").id == first
    # a different session may land elsewhere, deterministically
    assert fr.pick(session="user-42").id == first


# ===================================================== faultsim kinds
def test_new_fault_kinds_parse_and_fire():
    faults = faultsim.parse_schedule("replica_kill:call=2;poll_blackhole:step=3,count=4")
    assert [f.kind for f in faults] == ["replica_kill", "poll_blackhole"]
    inj = faultsim.arm(faults)
    try:
        assert not inj.fires("replica_kill")  # call 0
        assert not inj.fires("replica_kill")  # call 1
        assert inj.fires("replica_kill")  # call 2
        assert not inj.fires("replica_kill")  # count=1 exhausted
        inj.set_step(3)
        fired = sum(1 for _ in range(10) if inj.fires("poll_blackhole"))
        assert fired == 4  # at-most-`count` firings, even inside the window
        inj.set_step(8)
        assert not inj.fires("poll_blackhole")
    finally:
        faultsim.disarm()


def test_new_fault_kinds_disarmed_hooks_are_noop_refs():
    assert faultsim.fires is faultsim._noop_fires
    assert faultsim.fires("replica_kill") is False
    assert faultsim.fires("poll_blackhole") is False
    assert "replica_kill" in faultsim.KINDS and "poll_blackhole" in faultsim.KINDS


# ================================================= ops server hardening
def _get_raw(url, timeout=5.0):
    resp = urllib.request.urlopen(url, timeout=timeout)
    return resp, resp.read().decode()


def test_retry_after_header_on_draining_and_shedding():
    srv = ops_server.OpsServer(port=reserve_port()).start()
    state = {"draining": False, "shedding": None, "retry_after_s": 2.4}
    try:
        srv.register("healthz", lambda: dict(state))
        srv.register("router", lambda: {"accepting": True, "queue_depth": 0,
                                        "retry_after_s": 2.4})
        resp, _ = _get_raw(f"{srv.url}/healthz")
        assert resp.headers.get("Retry-After") is None
        state["draining"] = True
        resp, body = _get_raw(f"{srv.url}/healthz")
        assert resp.headers.get("Retry-After") == "3"  # ceil(2.4)
        assert json.loads(body)["draining"] is True
        state["draining"] = False
        state["shedding"] = "queue full (8/8)"
        resp, _ = _get_raw(f"{srv.url}/healthz")
        assert resp.headers.get("Retry-After") == "3"
        # /router: accepting=False drives the header
        srv.register("router", lambda: {"accepting": False, "queue_depth": 9,
                                        "retry_after_s": 0.2})
        resp, _ = _get_raw(f"{srv.url}/router")
        assert resp.headers.get("Retry-After") == "1"  # floor at 1s
    finally:
        srv.stop()


def test_submit_and_outcomes_endpoints():
    srv = ops_server.OpsServer(port=reserve_port()).start()
    seen = []
    try:
        srv.register("submit", lambda payload: (seen.append(payload) or
                                                {"accepted": True, "rid": payload["rid"]}))
        srv.register("outcomes", lambda: {"outcomes": {"3": {"status": "completed"}}})
        body = json.dumps(request_payload(_req(3), session="s1")).encode()
        req = urllib.request.Request(f"{srv.url}/submit", data=body, method="POST")
        with urllib.request.urlopen(req, timeout=5) as resp:
            out = json.loads(resp.read())
        assert out == {"accepted": True, "rid": 3}
        assert seen and request_from_payload(seen[0]) == _req(3)
        _, body = _get_raw(f"{srv.url}/outcomes")
        assert json.loads(body)["outcomes"]["3"]["status"] == "completed"
        # malformed body is a 400, not a handler crash
        req = urllib.request.Request(f"{srv.url}/submit", data=b"{nope", method="POST")
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(req, timeout=5)
        assert e.value.code == 400
    finally:
        srv.stop()


def test_poll_blackhole_swallows_polls_then_recovers():
    srv = ops_server.OpsServer(port=reserve_port()).start()
    try:
        srv.register("router", lambda: {"queue_depth": 0})
        _get_raw(f"{srv.url}/router")  # healthy before
        faultsim.arm(faultsim.parse_schedule("poll_blackhole:call=0,count=2"))
        try:
            for _ in range(2):
                with pytest.raises(Exception):
                    _get_raw(f"{srv.url}/router", timeout=2.0)
            # count exhausted: the partition heals
            _, body = _get_raw(f"{srv.url}/router")
            assert json.loads(body) == {"queue_depth": 0}
        finally:
            faultsim.disarm()
        client = HttpReplicaClient(srv.url, timeout_s=2.0)
        assert client.poll_router() == {"queue_depth": 0}
    finally:
        srv.stop()


def test_concurrent_poller_never_sees_half_written_body():
    """Regression (ISSUE 13 satellite): responses are written atomically,
    so a poller racing server shutdown sees complete JSON or a connection
    error — never a truncated body."""
    payload = {"queue_depth": 3, "ttft_s": {"p99": 0.5}, "filler": "x" * 2048}
    stop = threading.Event()
    bad: list = []
    url_box: dict = {}

    def poller():
        import http.client

        while not stop.is_set():
            u = url_box.get("url")
            if u is None:
                time.sleep(0.001)
                continue
            try:
                with urllib.request.urlopen(f"{u}/router", timeout=2.0) as resp:
                    body = resp.read()
                    if resp.status == 200:
                        json.loads(body)  # complete or json raises
            except json.JSONDecodeError as e:
                bad.append(f"truncated json: {e}")
                return
            except http.client.IncompleteRead as e:
                bad.append(f"incomplete read: {e}")
                return
            except Exception:
                pass  # refused/reset mid-restart is fine; truncation is not

    th = threading.Thread(target=poller, daemon=True)
    th.start()
    try:
        for _ in range(8):
            srv = ops_server.OpsServer(port=reserve_port()).start()
            srv.register("router", lambda: dict(payload))
            url_box["url"] = srv.url
            time.sleep(0.05)
            srv.stop()
            url_box.pop("url", None)
    finally:
        stop.set()
        th.join(timeout=10)
    assert not bad, f"poller saw truncated bodies: {bad}"


# ======================================================= inbox + loop
def test_request_inbox_push_drain_close():
    box = RequestInbox()
    assert box.push(_req(1)) and box.push(_req(2))
    assert [r.rid for r in box.drain()] == [1, 2]
    assert box.drain() == []
    box.close()
    assert box.closed and not box.push(_req(3))
    assert box.drain() == []


class _NopEngine:
    greedy = staticmethod(ServeEngine.greedy)

    def __init__(self, slots, vocab=8):
        import numpy as np

        self._p = np.zeros((vocab,), np.float32)
        self._d = DecodeStep(np.zeros((slots,), np.int32), np.zeros((slots, vocab), np.float32))

    def prefill(self, prompt, slot):
        return self._p

    def decode(self, tokens):
        return self._d


def _nop_rig(slots=2):
    mesh = DeviceMesh(("tp",), (1,), devices=jax.devices()[:1])
    kc = KVCacheConfig(layers=1, kv_heads=1, head_dim=1, num_slots=slots,
                       page_size=8, pages_per_slot=8)
    cache = PagedKVCache(kc, mesh)
    return _NopEngine(slots), cache


def test_inbox_fed_loop_serves_and_exits_on_close():
    eng, cache = _nop_rig()
    sched = ContinuousBatchingScheduler(cache, max_queue=8)
    box = RequestInbox()
    box.push(_req(0, max_new=3))
    box.push(_req(1, max_new=2))
    done = []

    def on_step(step, active):
        # close once everything pushed so far is terminal: the loop must
        # then exit "completed" on its own
        if not done and len(sched.outcomes) == 2 and sched.all_terminal():
            box.close()
            done.append(step)

    res = run_serve_resilient(
        engine=eng, scheduler=sched, arrivals=(), inbox=box,
        install_signal_handlers=False, coordinate=False, on_step=on_step,
        max_steps=10_000,
    )
    assert res.status == "completed"
    assert {o["status"] for o in res.outcomes.values()} == {"completed"}
    sched.ledger_check()


def test_inbox_duplicate_rid_rejected_without_killing_loop():
    eng, cache = _nop_rig()
    sched = ContinuousBatchingScheduler(cache, max_queue=8)
    box = RequestInbox()
    box.push(_req(5, max_new=40))  # long enough to still be pending
    box.push(_req(5, max_new=40))  # duplicate while pending: rejected
    seen = []

    def on_step(step, active):
        seen.append(active)
        if len(sched.outcomes) == 1 and sched.all_terminal():
            box.close()

    res = run_serve_resilient(
        engine=eng, scheduler=sched, arrivals=(), inbox=box,
        install_signal_handlers=False, coordinate=False, on_step=on_step,
        max_steps=10_000,
    )
    assert res.status == "completed"
    assert len(res.outcomes) == 1 and res.outcomes[5]["status"] == "completed"


def test_inbox_closed_with_pending_items_still_served():
    """Regression: close() racing the boundary drain must not lose the
    requests pushed before it — the loop re-drains before declaring
    completion (push-after-close is refused, so the final drain is
    exhaustive)."""
    eng, cache = _nop_rig()
    sched = ContinuousBatchingScheduler(cache, max_queue=8)
    box = RequestInbox()
    assert box.push(_req(0, max_new=2)) and box.push(_req(1, max_new=2))
    box.close()  # closed while items still pending: worst-case interleave
    res = run_serve_resilient(
        engine=eng, scheduler=sched, arrivals=(), inbox=box,
        install_signal_handlers=False, coordinate=False, max_steps=10_000,
    )
    assert res.status == "completed"
    assert sorted(res.outcomes) == [0, 1]
    assert {o["status"] for o in res.outcomes.values()} == {"completed"}


def test_supervisor_stop_cancels_scheduled_restart(tmp_path):
    """Regression: a crash schedules a respawn; a stop() that lands
    before the restart fires must cancel it — a stopped replica can
    never be resurrected by a later poll()."""
    from vescale_tpu.serve import FleetSupervisor, ReplicaSpec

    spec = ReplicaSpec(
        "s0", [sys.executable, "-c", "import time; time.sleep(120)"],
        reserve_port(), log_path=str(tmp_path / "s0.log"),
    )
    sup = FleetSupervisor([spec], max_restarts=2, restart_backoff_s=0.05).start()
    try:
        assert sup.alive("s0")
        sup.kill("s0")
        deadline = time.monotonic() + 10
        while sup.managed["s0"].proc is not None and time.monotonic() < deadline:
            sup.poll()  # reaps the crash, schedules the restart
            time.sleep(0.01)
        assert sup.managed["s0"].proc is None
        assert sup._restart_at  # restart pending
        sup.stop("s0")  # scale-down wins over the pending respawn
        time.sleep(0.1)  # past the restart backoff
        sup.poll()
        assert sup.managed["s0"].proc is None and not sup.alive("s0")
        assert sup.managed["s0"].restarts == 0
        assert not sup._restart_at
    finally:
        sup.stop_all(grace_s=5.0)


def test_spawn_like_clones_spec_with_fresh_port_and_env_drop(tmp_path):
    """ISSUE 19 satellite: scale-up clones the template spec onto a fresh
    reserved port + unique auto id, drops restart_env_drop vars (a fault
    schedule aimed at the original fleet must not arm in the clone), and
    suffixes the log path."""
    from vescale_tpu.serve import FleetSupervisor, ReplicaSpec

    spec = ReplicaSpec(
        "s0", [sys.executable, "-c", "import time; time.sleep(120)"],
        reserve_port(), env={"VESCALE_FAULTSIM": "die:count=1", "KEEP": "1"},
        log_path=str(tmp_path / "s0.log"),
        restart_env_drop=("VESCALE_FAULTSIM",),
    )
    sup = FleetSupervisor([spec], max_restarts=2, restart_backoff_s=0.05).start()
    try:
        c0 = sup.spawn_like("s0")
        c1 = sup.spawn_like("s0")
        assert (c0.replica_id, c1.replica_id) == ("s0-s0", "s0-s1")
        ports = {spec.port, c0.port, c1.port}
        assert len(ports) == 3  # reserve_port never reuses in-process
        assert "VESCALE_FAULTSIM" not in c0.env and c0.env["KEEP"] == "1"
        assert c0.log_path == str(tmp_path / "s0.log") + ".s0-s0"
        assert sup.alive("s0-s0") and sup.alive("s0-s1")
        assert c0.url.endswith(f":{c0.port}")
        with pytest.raises(ValueError):
            sup.spawn_like("s0", replica_id="s0-s1")  # already managed
    finally:
        sup.stop_all(grace_s=5.0)


def test_supervisor_drain_is_nonblocking_and_never_respawns(tmp_path):
    """ISSUE 19 satellite: drain() sends SIGTERM and returns immediately
    (the autoscaler keeps pumping the router through the linger window);
    a later poll() reaps the exit WITHOUT scheduling a respawn."""
    from vescale_tpu.serve import FleetSupervisor, ReplicaSpec

    spec = ReplicaSpec(
        "s0", [sys.executable, "-c", "import time; time.sleep(120)"],
        reserve_port(), log_path=str(tmp_path / "s0.log"),
    )
    sup = FleetSupervisor([spec], max_restarts=2, restart_backoff_s=0.01).start()
    try:
        t0 = time.monotonic()
        sup.drain("s0")
        assert time.monotonic() - t0 < 1.0  # never waits for the exit
        deadline = time.monotonic() + 10
        while sup.managed["s0"].proc is not None and time.monotonic() < deadline:
            sup.poll()
            time.sleep(0.01)
        assert sup.managed["s0"].proc is None and not sup.alive("s0")
        assert not sup._restart_at  # stopped-on-purpose: no resurrection
        time.sleep(0.05)
        sup.poll()
        assert sup.managed["s0"].proc is None
        assert sup.managed["s0"].restarts == 0
    finally:
        sup.stop_all(grace_s=5.0)


def test_scale_down_drain_rehomes_sessions_with_zero_lost_rids():
    """ISSUE 19 satellite: the scale-down choreography at router level.
    While the victim drains (accepting=False) the router still HARVESTS
    its in-flight outcomes through the linger window; new traffic for its
    sessions spills to survivors; after removal the affinity ring
    re-homes deterministically.  Net: zero lost, zero duplicated rids."""
    a, b, c = FakeReplica("a"), FakeReplica("b"), FakeReplica("c")
    fr, t = make_router([a, b, c])
    fr.poll(force=True)
    # find a session homed on each replica
    home_to_session = {}
    i = 0
    while len(home_to_session) < 3 and i < 64:
        sid = f"user-{i}"
        home_to_session.setdefault(fr.pick(session=sid).id, sid)
        i += 1
    assert set(home_to_session) == {"a", "b", "c"}
    sid_a = home_to_session["a"]
    recs = [fr.submit(_req(i), session=sid_a) for i in range(3)]
    assert all(r.live_on == ["a"] for r in recs)

    # drain begins: the victim stops accepting but keeps its in-flight
    a.feed_kw.update(draining=True, accepting=False)
    fr.poll(force=True)
    # new work for the SAME session spills to a survivor immediately
    spill = fr.submit(_req(100), session=sid_a)
    assert spill.live_on and spill.live_on[0] in ("b", "c")

    # linger harvest: the draining replica finishes; the router, still
    # polling it, collects the outcomes BEFORE the replica is removed
    a.finish_all()
    fr.pump()
    assert all(not r.pending and r.status == "completed" for r in recs)
    assert all(r.replica == "a" for r in recs)

    # process exits -> autoscaler removes it; ring re-homes the session
    fr.remove_replica("a")
    assert "a" not in fr.replicas
    new_home = fr.pick(session=sid_a).id
    assert new_home in ("b", "c")
    for _ in range(5):
        assert fr.pick(session=sid_a).id == new_home  # stable re-home

    (b if spill.live_on[0] == "b" else c).finish_all()
    assert fr.pump() == 0
    fr.fleet_ledger_check()  # EXACTLY one terminal outcome per rid
    counts = fr.ledger.counts
    assert counts["completed"] == 4
    assert counts["submitted"] == 4 and counts["resubmitted"] == 0
    assert fr.ledger.pending_count() == 0


# ============================================== live replica end-to-end
CFG = LlamaConfig(
    vocab_size=64, hidden_size=16, intermediate_size=32,
    num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=2,
    max_position_embeddings=64, dtype=jnp.float32,
)


def test_serve_replica_over_http_with_router():
    """One REAL replica (tiny llama) behind serve_replica + HttpReplicaClient:
    dispatch, outcome harvest, v2 feed fields, ledger balance — the
    in-process version of the fleet smoke's transport path."""
    mesh = DeviceMesh(("tp",), (1,), devices=jax.devices()[:1])
    model = Llama(CFG)
    params = model.init(jax.random.key(0), jnp.ones((1, 8), jnp.int32))["params"]
    kc = KVCacheConfig(layers=CFG.num_hidden_layers, kv_heads=CFG.num_key_value_heads,
                       head_dim=CFG.head_dim, num_slots=2, page_size=4, pages_per_slot=4)
    cache = PagedKVCache(kc, mesh)
    eng = ServeEngine(CFG, mesh, params, cache)
    sched = ContinuousBatchingScheduler(cache, max_queue=8)
    port = reserve_port()
    box = RequestInbox()
    result = {}

    def run():
        result["res"] = serve_replica(
            engine=eng, scheduler=sched, replica_id="t0", port=port, inbox=box,
            linger_s=0.1, install_signal_handlers=False, coordinate=False,
        )

    th = threading.Thread(target=run, daemon=True)
    th.start()
    try:
        fr = FleetRouter(poll_interval_s=0.02, breaker_failures=5,
                         breaker_cooldown_s=0.2, dispatch_retries=8,
                         backoff_s=0.05, backoff_max_s=0.5, hedge_s=0.0)
        fr.add_replica("t0", HttpReplicaClient(f"http://127.0.0.1:{port}"))
        for i in range(3):
            fr.submit(Request(rid=i, prompt=(3 + i, 5), max_new_tokens=2),
                      session="s0")
        fr.drain(timeout_s=60.0)
        fr.fleet_ledger_check()
        assert fr.ledger.counts["completed"] == 3
        feed = fr.replicas["t0"].feed
        from vescale_tpu.serve.obs import ROUTER_SCHEMA_VERSION

        assert feed["replica_id"] == "t0"
        assert feed["schema_version"] == ROUTER_SCHEMA_VERSION
        assert feed["accepting"] is True
    finally:
        box.close()
        th.join(timeout=60)
    assert not th.is_alive() and result["res"].status == "completed"


# ============================================================ smoke wiring
def test_fleet_smoke_script():
    """tier-1 wiring of scripts/fleet_smoke.py: golden fleet vs
    kill+rejoin fleet — zero lost/duplicated requests, failovers counted,
    bit-identical tokens, rejoined replica serves — the ISSUE 13
    acceptance run."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "fleet_smoke.py")],
        capture_output=True,
        text=True,
        timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout[-3000:]}\nstderr:\n{out.stderr[-3000:]}"
    assert "FLEET SMOKE OK" in out.stdout
