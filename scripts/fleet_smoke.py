"""Fleet-level resilient-serving smoke — the acceptance run of ISSUE 13.

Two fleet legs, each on 3 single-process replica children (tiny llama,
seed-identical params, so any replica generates the same tokens for the
same prompt — decode determinism at fleet scope):

  golden    3 replicas behind a FleetRouter, an open-loop load dispatched
            by least-loaded scoring with session affinity.  Every request
            completes, the fleet ledger balances (zero lost, zero
            duplicated, zero failovers), and the per-rid token streams
            become the cross-leg truth.

  kill      the SAME load against a fresh fleet where replica r1 is armed
            with the faultsim ``replica_kill`` kind (env-armed — the
            process dies ABRUPTLY via os._exit mid-decode, with requests
            in flight, no drain, no cleanup).  The FleetSupervisor
            respawns it on the same port (the PR-4/5 restart story at
            replica granularity); the router's breaker opens on poll
            failures, every stranded request FAILS OVER to a healthy
            replica from the prompt, and the half-open probe readmits the
            restarted replica.  Assertions: the fleet-wide ledger
            balances with the failover resubmissions counted, every
            completed request's tokens are BIT-IDENTICAL to golden, the
            killed replica's exit code is the replica_kill code, the
            breaker walked closed -> open -> half-open -> closed, and the
            REJOINED replica resolves fresh traffic.

Exit 0 on success.  Wired into scripts/run_test.sh and tier-1 via
tests/test_fleet.py.
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N_REPLICAS = 3
SLOTS = 2
MAX_QUEUE = 16
# fires on the victim's THIRD loaded decode step: even a replica holding a
# single max_new=4 request reaches it, and the kill lands BEFORE the step's
# completions are ledgered — requests are guaranteed in flight at death
KILL_SCHEDULE = "replica_kill:call=2"
WAVE1 = 12  # rids 0..11, both legs
WAVE2 = 6   # rids 100..105, kill leg only (post-rejoin traffic)


def _prompts(n, base_rid=0):
    import numpy as np

    rng = np.random.default_rng(23)
    out = []
    for i in range(n):
        prompt = tuple(int(x) for x in rng.integers(1, 60, 3 + (i % 3)))
        out.append((base_rid + i, prompt, 4 + (i % 3)))
    return out


# --------------------------------------------------------------------- child
def replica_child() -> None:
    """One fleet replica: llama from a FIXED seed (every replica serves
    identical params — the fleet's determinism contract), fed over the
    ops endpoints, drained by SIGTERM."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from vescale_tpu.mesh import DeviceMesh
    from vescale_tpu.models.llama import Llama, LlamaConfig
    from vescale_tpu.serve import (
        ContinuousBatchingScheduler,
        KVCacheConfig,
        PagedKVCache,
        ServeEngine,
        serve_replica,
    )

    cfg = LlamaConfig(
        vocab_size=64, hidden_size=16, intermediate_size=32,
        num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=2,
        max_position_embeddings=64, dtype=jnp.float32,
    )
    mesh = DeviceMesh(("tp",), (1,), devices=jax.devices()[:1])
    model = Llama(cfg)
    params = model.init(jax.random.key(0), jnp.ones((1, 8), jnp.int32))["params"]
    kc = KVCacheConfig(
        layers=cfg.num_hidden_layers, kv_heads=cfg.num_key_value_heads,
        head_dim=cfg.head_dim, num_slots=SLOTS, page_size=4,
        pages_per_slot=4,
    )
    cache = PagedKVCache(kc, mesh)
    engine = ServeEngine(cfg, mesh, params, cache)
    # queue bound comes from the env (the driver's ReplicaSpec sets
    # VESCALE_SERVE_MAX_QUEUE)
    scheduler = ContinuousBatchingScheduler(cache)
    res = serve_replica(
        engine=engine, scheduler=scheduler, linger_s=1.0, coordinate=False,
    )
    print(f"replica done status={res.status} counts={json.dumps(res.counts)}")


# -------------------------------------------------------------------- driver
def _specs(workdir, n, kill_replica=None, extra_env=None):
    from vescale_tpu.serve import ReplicaSpec
    from vescale_tpu.testing import make_child_env, reserve_port

    specs = []
    for i in range(n):
        rid = f"r{i}"
        env = make_child_env(0, 0, 1, device_count=1,
                             scrub=("VESCALE_FAULTSIM", "VESCALE_SERVE_OPS_PORT",
                                    "VESCALE_SERVE_REPLICA_ID", "VESCALE_KERNELS"),
                             extra={"VESCALE_SERVE_MAX_QUEUE": MAX_QUEUE,
                                    **(extra_env or {})})
        if kill_replica == rid:
            env["VESCALE_FAULTSIM"] = KILL_SCHEDULE
        specs.append(ReplicaSpec(
            rid,
            [sys.executable, os.path.abspath(__file__), "--child"],
            reserve_port(),
            env=env,
            log_path=os.path.join(workdir, f"{rid}.log"),
            # a respawned replica must not re-arm the transient kill
            restart_env_drop=("VESCALE_FAULTSIM",),
        ))
    return specs


def _router(**kw):
    from vescale_tpu.serve import FleetRouter, HttpReplicaClient

    defaults = dict(
        poll_interval_s=0.05, breaker_failures=2, breaker_cooldown_s=0.5,
        dispatch_retries=4, backoff_s=0.05, backoff_max_s=0.5, hedge_s=0.0,
    )
    defaults.update(kw)
    return FleetRouter(**defaults), HttpReplicaClient


def _wait_fleet_up(fr, sup, specs, timeout=120.0):
    """Replica children pay a cold jax import; wait until every feed
    answers before calling the fleet 'up'."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        sup.poll()
        fr.poll(force=True)
        if all(h.feed is not None and h.breaker.state == "closed"
               for h in fr.replicas.values()):
            return
        time.sleep(0.2)
    raise TimeoutError(
        "fleet never came up: "
        + str({rid: (h.breaker.state, h.feed is not None)
               for rid, h in fr.replicas.items()})
    )


def _submit_wave(fr, wave, use_session=True):
    from vescale_tpu.serve import Request

    recs = []
    for rid, prompt, max_new in wave:
        # half the load pins a session (affinity coverage), half routes
        # least-loaded — which guarantees EVERY replica sees in-flight
        # work (the kill leg's victim must be loaded when it dies)
        session = f"sess{rid % 5}" if (use_session and rid % 2 == 0) else None
        recs.append(fr.submit(
            Request(rid=rid, prompt=prompt, max_new_tokens=max_new),
            session=session,
        ))
    return recs


def _drain(fr, sup, timeout=180.0):
    """Like FleetRouter.drain but interleaves supervisor turns so a dead
    replica's restart actually happens while the router pumps."""
    deadline = time.monotonic() + timeout
    while True:
        sup.poll()
        if fr.pump() == 0:
            return
        if time.monotonic() > deadline:
            raise TimeoutError(
                f"fleet drain stuck: pending="
                f"{[r.req.rid for r in fr.ledger.pending()]}"
            )
        time.sleep(0.05)


def _run_fleet_leg(workdir, label, kill_replica=None, extra_env=None):
    from vescale_tpu.serve import FleetSupervisor

    specs = _specs(workdir, N_REPLICAS, kill_replica=kill_replica,
                   extra_env=extra_env)
    fr, Client = _router()
    sup = FleetSupervisor(specs, max_restarts=2, restart_backoff_s=0.3)
    sup.start()
    try:
        for s in specs:
            fr.add_replica(s.replica_id, Client(s.url))
        _wait_fleet_up(fr, sup, specs)
        t0 = time.monotonic()
        _submit_wave(fr, _prompts(WAVE1))
        _drain(fr, sup)
        wave1_wall = time.monotonic() - t0

        wave2_resolved_by = {}
        if kill_replica is not None:
            # the kill has already happened mid-wave-1 (replica_kill fires
            # on the victim's THIRD loaded decode step — KILL_SCHEDULE's
            # call=2 is 0-based); now prove the REJOIN: wait for the
            # breaker to close again, then serve fresh traffic through
            # the restarted replica
            deadline = time.monotonic() + 120.0
            while time.monotonic() < deadline:
                sup.poll()
                fr.poll(force=True)
                if fr.replicas[kill_replica].breaker.state == "closed":
                    break
                time.sleep(0.2)
            assert fr.replicas[kill_replica].breaker.state == "closed", (
                f"{kill_replica} never readmitted: "
                f"{fr.replicas[kill_replica].breaker.state}"
            )
            # sessionless: least-loaded routing, and the freshly rejoined
            # (empty) replica is by construction the least loaded
            _submit_wave(fr, _prompts(WAVE2, base_rid=100), use_session=False)
            _drain(fr, sup)
            wave2_resolved_by = {
                rid: rec.replica
                for rid, rec in fr.ledger.records.items()
                if rid >= 100
            }
        fr.fleet_ledger_check()
        summary = fr.summary()
        tokens = {
            rid: rec.outcome["tokens"]
            for rid, rec in fr.ledger.records.items()
            if rec.status == "completed"
        }
        statuses = {rid: rec.status for rid, rec in fr.ledger.records.items()}
        print(f"{label}: wall={wave1_wall:.1f}s "
              f"counts={json.dumps(summary['counts'], sort_keys=True)}")
        return {
            "summary": summary,
            "tokens": tokens,
            "statuses": statuses,
            "wave2_resolved_by": wave2_resolved_by,
            "supervisor_exits": {
                rid: list(m.exit_history) for rid, m in sup.managed.items()
            },
        }
    finally:
        rcs = sup.stop_all(grace_s=30.0)
        print(f"{label}: replica exits {rcs}")


def main() -> None:
    import shutil
    import tempfile

    sys.path.insert(0, REPO)
    from vescale_tpu.analysis import envreg

    work = tempfile.mkdtemp(prefix="fleet_smoke_")
    t0 = time.monotonic()
    try:
        # ---- golden fleet: no faults, everything completes
        golden = _run_fleet_leg(work, "golden")
        g = golden["summary"]["counts"]
        assert g["completed"] == WAVE1 and g["failovers"] == 0, g
        assert set(golden["statuses"].values()) == {"completed"}, golden["statuses"]

        # ---- kill leg: r1 dies abruptly mid-load, restarts, rejoins
        kill = _run_fleet_leg(work, "kill", kill_replica="r1")
        k = kill["summary"]["counts"]

        # the fleet-wide ledger balances: every request terminal exactly
        # once, with the failover resubmissions explicitly counted
        assert k["completed"] == WAVE1 + WAVE2, k
        assert k["failovers"] >= 1, f"kill leg saw no failover: {k}"
        assert k["redispatched"] >= k["failovers"], k

        # the killed replica really died with the replica_kill exit code,
        # and the supervisor respawned it (the auto-restart path)
        kill_code = envreg.lookup("VESCALE_FAULTSIM_KILL_EXIT_CODE").default
        r1_exits = kill["supervisor_exits"]["r1"]
        assert -9 not in r1_exits[:1] and r1_exits[0] == kill_code, r1_exits
        assert kill["summary"]["replicas"]["r1"]["opens"] >= 1, kill["summary"]
        assert kill["summary"]["replicas"]["r1"]["closes"] >= 1, (
            "r1 was never readmitted through the half-open probe"
        )

        # zero lost, zero duplicated, and failover replays are
        # BIT-IDENTICAL: every completed rid's tokens equal golden's
        for rid, toks in golden["tokens"].items():
            assert kill["tokens"][rid] == toks, (
                rid, kill["tokens"][rid], toks
            )

        # the rejoined replica serves fresh traffic
        assert any(rep == "r1" for rep in kill["wave2_resolved_by"].values()), (
            f"rejoined r1 resolved nothing: {kill['wave2_resolved_by']}"
        )

        print(
            "FLEET SMOKE OK: replica killed mid-load and rejoined, "
            f"{k['failovers']} failovers re-drove stranded requests with "
            "bit-identical tokens, fleet ledger balanced "
            f"(zero lost/duplicated) ({time.monotonic() - t0:.1f}s)"
        )
    finally:
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        replica_child()
    else:
        main()
