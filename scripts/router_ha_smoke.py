"""Router high-availability smoke: kill -9 the LIVE ROUTER mid-load and
prove nothing is lost.

The fleet smoke (scripts/fleet_smoke.py) kills a *replica*; this one
kills the *router* — the component that, pre-ISSUE-20, held the fleet
ledger only in memory.  The battery:

1. golden leg: an in-process router (no journal) drives a wave over 2
   real replica children — the reference token streams.
2. HA leg: a ROUTER CHILD process acquires the leader lease, journals
   every transition to a shared directory, submits the same wave (rids
   offset by 100, prompts identical), and is killed by the armed
   ``router_kill`` fault via ``os._exit`` at a pump boundary — no drain,
   no lease release, exactly a crash.
3. a warm ``StandbyRouter`` in THIS process tails the journal, waits out
   the lease TTL, takes over (epoch bump fences the dead leader), then
   harvests finished outcomes, re-drives truly in-flight rids, and
   finishes the battery: ledger balanced, ZERO lost/duplicated rids, and
   every completed token stream BIT-IDENTICAL to the golden leg.
4. the promoted router re-announces on ``/fleet`` v5: the ``ha`` block
   reports role=leader at the bumped epoch over live HTTP.

Run directly: ``python scripts/router_ha_smoke.py`` (wired into
scripts/run_test.sh and tests/test_routerha.py).
"""

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WAVE = 12           # rids 0..11 golden, 100..111 HA leg (same prompts)
HA_BASE_RID = 100
LEASE_TTL_S = 1.0   # short lease so the standby promotes quickly
# fire at the FIRST pump: the wave is fully submitted (placement-barrier
# flushed) but nothing harvested yet, so the crash strands ALL of it —
# warm replicas drain these tiny prompts in a handful of pumps, so a
# later slot risks the fault never firing at all
ROUTER_KILL_SCHEDULE = "router_kill:call=0"


def _scripts_on_path():
    sys.path.insert(0, REPO)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------ router child
def router_child() -> None:
    """The doomed leader.  Runs in its own process so the armed
    ``router_kill`` fault's ``os._exit`` kills a real OS process — the
    journal on disk (flushed at every placement barrier) is all that
    survives, exactly the crash the recovery path promises to cover."""
    _scripts_on_path()
    import fleet_smoke

    from vescale_tpu.resilience import faultsim
    from vescale_tpu.serve import FleetJournal, LeaderLease

    faultsim.arm_from_env()  # VESCALE_FAULTSIM=router_kill:... from parent
    replicas = json.loads(os.environ["ROUTER_HA_REPLICAS"])
    lease = LeaderLease(os.environ["ROUTER_HA_LEASE_PATH"], holder="leader",
                        ttl_s=LEASE_TTL_S)
    journal = FleetJournal(os.environ["ROUTER_HA_JOURNAL_DIR"])
    fr, Client = fleet_smoke._router(journal=journal, lease=lease)
    for rid, url in replicas.items():
        fr.add_replica(rid, Client(url))
    # replicas are parent-supervised and already warm — just wait for feeds
    deadline = time.monotonic() + 60.0
    while time.monotonic() < deadline:
        fr.poll(force=True)
        if all(h.feed is not None and h.breaker.state == "closed"
               for h in fr.replicas.values()):
            break
        time.sleep(0.2)
    fleet_smoke._submit_wave(fr, fleet_smoke._prompts(WAVE, base_rid=HA_BASE_RID))
    while fr.pump() > 0:  # dies HERE at the armed pump boundary
        time.sleep(0.05)
    # unreachable under the armed schedule; exiting 0 fails the parent's
    # exit-code assert loudly rather than silently skipping the crash
    sys.exit(0)


# ------------------------------------------------------------------- smoke
def main() -> None:
    import shutil
    import tempfile
    import urllib.request

    _scripts_on_path()
    import fleet_smoke

    from vescale_tpu.analysis import envreg
    from vescale_tpu.serve import FleetSupervisor, Request, StandbyRouter

    work = tempfile.mkdtemp(prefix="router_ha_smoke_")
    journal_dir = os.path.join(work, "journal")
    lease_path = os.path.join(journal_dir, "LEASE")  # StandbyRouter default
    t0 = time.monotonic()
    specs = fleet_smoke._specs(work, 2)
    sup = FleetSupervisor(specs, max_restarts=2, restart_backoff_s=0.3)
    sup.start()
    try:
        # ---- golden leg: in-process router, no journal, no faults
        fr, Client = fleet_smoke._router()
        for s in specs:
            fr.add_replica(s.replica_id, Client(s.url))
        fleet_smoke._wait_fleet_up(fr, sup, specs)
        fleet_smoke._submit_wave(fr, fleet_smoke._prompts(WAVE))
        fleet_smoke._drain(fr, sup)
        fr.fleet_ledger_check()
        golden = {rec.req.rid: list(rec.outcome["tokens"])
                  for rec in fr.ledger.records.values()}
        assert len(golden) == WAVE and all(
            rec.status == "completed" for rec in fr.ledger.records.values()
        ), fr.summary()

        # ---- HA leg: the leader is a CHILD process that journals the
        # same wave (rids +100) and is crashed by router_kill mid-load
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "VESCALE_FAULTSIM": ROUTER_KILL_SCHEDULE,
            "ROUTER_HA_REPLICAS": json.dumps({s.replica_id: s.url for s in specs}),
            "ROUTER_HA_JOURNAL_DIR": journal_dir,
            "ROUTER_HA_LEASE_PATH": lease_path,
        })
        env.pop("VESCALE_FLEET_OPS_PORT", None)
        leader_log = os.path.join(work, "leader.log")
        with open(leader_log, "wb") as lf:
            leader = subprocess.Popen(
                [sys.executable, os.path.abspath(__file__), "--router"],
                env=env, stdout=lf, stderr=subprocess.STDOUT,
            )
            rc = leader.wait(timeout=180)
        kill_code = envreg.lookup("VESCALE_FAULTSIM_KILL_EXIT_CODE").default
        if rc != kill_code:
            sys.stderr.write(open(leader_log).read())
        assert rc == kill_code, f"leader exited {rc}, wanted {kill_code}"

        # ---- warm standby: tail the journal, wait out the lease, promote
        standby = StandbyRouter(
            journal_dir,
            {s.replica_id: Client(s.url) for s in specs},
            holder="standby",
            router_kwargs=dict(poll_interval_s=0.05, breaker_failures=2,
                               breaker_cooldown_s=0.5, dispatch_retries=4,
                               backoff_s=0.05, backoff_max_s=0.5, hedge_s=0.0),
        )
        tail = standby.tail()  # read-only view while the lease runs out
        assert tail["epoch"] == 1 and tail["pending"] >= 1, tail
        fr2 = None
        deadline = time.monotonic() + 60.0
        while fr2 is None and time.monotonic() < deadline:
            sup.poll()  # replicas keep decoding the dead leader's work
            fr2 = standby.poll()
            if fr2 is None:
                time.sleep(0.2)
        assert fr2 is not None, "standby never took over"
        rec = fr2.recovery
        assert rec["takeover"] and rec["epoch"] == 2, rec
        assert rec["quarantined"] == 0 and rec["torn"] == 0, rec
        assert rec["pending_at_recovery"] >= 1, rec

        # every wave rid must already be journaled (the placement barrier
        # flushes submit+dispatch before any pump); resubmit is the
        # belt-and-braces path and is expected to count zero
        wave = fleet_smoke._prompts(WAVE, base_rid=HA_BASE_RID)
        resubmitted = 0
        for rid, prompt, max_new in wave:
            if rid not in fr2.ledger.records:
                fr2.submit(Request(rid=rid, prompt=prompt, max_new_tokens=max_new),
                           session=f"sess{rid % 5}" if rid % 2 == 0 else None)
                resubmitted += 1
        fleet_smoke._drain(fr2, sup)
        fr2.fleet_ledger_check()
        c = fr2.summary()["counts"]
        assert c["completed"] == WAVE, c  # zero lost, zero duplicated

        # bit-identical completed streams: HA rid 100+i vs golden rid i
        for rid, prompt, max_new in wave:
            toks = list(fr2.ledger.records[rid].outcome["tokens"])
            assert toks == golden[rid - HA_BASE_RID], (
                rid, toks, golden[rid - HA_BASE_RID]
            )

        # ---- the promoted router re-announces on /fleet v5
        fr2.start_ops(0)
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{fr2._ops.port}/fleet", timeout=10
            ) as resp:
                fleet = json.loads(resp.read())
        finally:
            fr2._ops.stop()
        assert fleet["schema_version"] == 5, fleet["schema_version"]
        ha = fleet["ha"]
        assert ha["role"] == "leader" and ha["epoch"] == 2, ha
        assert ha["recovery"]["takeover"] is True, ha

        print(
            "ROUTER HA SMOKE OK: leader killed -9 mid-load at epoch 1, "
            f"standby took over at epoch 2 ({rec['pending_at_recovery']} "
            f"pending recovered: {rec['harvested']} harvested, "
            f"{rec['redriven']} re-driven, {resubmitted} resubmitted), "
            "ledger balanced, token streams bit-identical to golden "
            f"({time.monotonic() - t0:.1f}s)"
        )
    finally:
        sup.stop_all(grace_s=30.0)
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--router":
        router_child()
    else:
        main()
