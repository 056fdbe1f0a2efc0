"""Autoscaler + rolling-rollout smoke — the acceptance run of ISSUE 19.

One closed-loop fleet leg on real replica children (tiny llama,
seed-identical params — fleet_smoke's child, reused verbatim), walked
through the whole "fleet that operates itself" story:

  golden      one replica, the SAME load throttled to capacity (no
              overload, no autoscaler, no rollout).  Every rid completes;
              the per-rid token streams become the cross-leg truth.

  autoscale   a 5x-capacity traffic spike lands open-loop on a 1-replica
              fleet.  The queue-depth signal (sampled into the PR-16
              time-series store off the router's own /fleet publishes)
              crosses the up-threshold, holds, and the Autoscaler spawns
              a clone via ``FleetSupervisor.spawn_like`` — fresh reserved
              port, faultsim env dropped — and the router readmits it
              through the existing half-open breaker probe (the clone's
              cold jax import means its breaker OPENS first, then closes
              on the probe: the readmission path is exercised by
              construction).  p99 TTFT at spike vs after recovery is
              recorded.  Shed rids are client-resubmitted until complete:
              at the end the fleet ledger balances with ZERO lost / ZERO
              duplicated rids and every token stream is BIT-IDENTICAL to
              golden.

  rollout     a rolling weight rollout of a checkpoint holding the SAME
              params (the fixed-seed trick again), replica at a time:
              drain -> baseline -> swap -> canary -> commit.  First
              attempt: the template replica is env-armed with
              ``canary_diverge:count=1`` — one logit sign flips during
              the canary replay, the twin replays disagree, the replica
              self-rolls-back and the controller auto-rolls-back the
              whole fleet (nothing stays committed).  Second attempt
              (the fault is consumed): clean sweep, both replicas
              committed + finalized.  Post-rollout traffic is
              BIT-IDENTICAL to golden — the swapped-in weights really
              are the checkpoint's.

  scale-down  the spike is over: the under-threshold signal holds and
              the Autoscaler drains the clone (SIGTERM, non-blocking),
              harvests its linger window, and removes it from the router
              once the process is gone — sessions re-home to the
              survivor via the affinity ring.

The driver runs ndtimeline live: the run must leave ``fleet-scale``
spans (directions up AND down) and ``fleet-rollout-stage`` spans on the
router's ring — the stitched-timeline vocabulary of ISSUE 14.

Exit 0 on success.  Wired into scripts/run_test.sh and tier-1 via
tests/test_autoscale.py.
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "scripts"))

SLOTS = 2
MAX_QUEUE = 6
CAPACITY = SLOTS + MAX_QUEUE        # one replica's admission bound
SPIKE = 5 * CAPACITY                # the 5x overload (rids 0..39)
WAVE2 = 6                           # rids 200..205, post-rollout traffic
DIVERGE_SCHEDULE = "canary_diverge:call=0,count=1"
CANARY_PROMPTS = [[1, 2, 3], [4, 5, 6, 7]]


def _prompts(n, base_rid=0):
    import numpy as np

    rng = np.random.default_rng(31)
    out = []
    for i in range(n):
        prompt = tuple(int(x) for x in rng.integers(1, 60, 3 + (i % 3)))
        out.append((base_rid + i, prompt, 4 + (i % 3)))
    return out


def _specs(workdir, arm_template=False):
    import fleet_smoke

    from vescale_tpu.serve import ReplicaSpec
    from vescale_tpu.testing import make_child_env, reserve_port

    env = make_child_env(
        0, 0, 1, device_count=1,
        scrub=("VESCALE_FAULTSIM", "VESCALE_SERVE_OPS_PORT",
               "VESCALE_SERVE_REPLICA_ID", "VESCALE_KERNELS"),
        extra={"VESCALE_SERVE_MAX_QUEUE": MAX_QUEUE},
    )
    if arm_template:
        env["VESCALE_FAULTSIM"] = DIVERGE_SCHEDULE
    return [ReplicaSpec(
        "r0",
        [sys.executable, os.path.abspath(fleet_smoke.__file__), "--child"],
        reserve_port(),
        env=env,
        log_path=os.path.join(workdir, "r0.log"),
        # spawn_like drops this from the clone: the canary fault stays
        # aimed at the template replica only
        restart_env_drop=("VESCALE_FAULTSIM",),
    )]


def _router():
    from vescale_tpu.serve import FleetRouter, HttpReplicaClient

    return FleetRouter(
        poll_interval_s=0.05, breaker_failures=2, breaker_cooldown_s=0.5,
        dispatch_retries=4, backoff_s=0.05, backoff_max_s=0.5, hedge_s=0.0,
    ), HttpReplicaClient


def _wait_up(fr, sup, timeout=120.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        sup.poll()
        fr.poll(force=True)
        if fr.replicas and all(
            h.feed is not None and h.breaker.state == "closed"
            for h in fr.replicas.values()
        ):
            return
        time.sleep(0.2)
    raise TimeoutError("fleet never came up")


def _ttft_p99(fr):
    vals = [h.feed["ttft_s"]["p99"] for h in fr.replicas.values()
            if h.feed and h.feed["ttft_s"]["p99"] is not None]
    return max(vals) if vals else None


def _drain(fr, sup, autoscaler=None, timeout=240.0):
    deadline = time.monotonic() + timeout
    while True:
        sup.poll()
        if autoscaler is not None:
            autoscaler.tick()
        if fr.pump() == 0:
            return
        if time.monotonic() > deadline:
            raise TimeoutError(
                f"drain stuck: pending={[r.req.rid for r in fr.ledger.pending()]}"
            )
        time.sleep(0.05)


def _complete_all(fr, sup, waves, autoscaler=None, rounds=60):
    """Drain, then client-resubmit any terminal-shed rid (the
    retry_after_s contract) until EVERY rid completed — zero lost."""
    from vescale_tpu.serve import Request

    by_rid = {rid: (prompt, max_new) for rid, prompt, max_new in waves}
    for _ in range(rounds):
        _drain(fr, sup, autoscaler=autoscaler)
        shed = [rid for rid in by_rid
                if fr.ledger.records[rid].status != "completed"]
        if not shed:
            return
        time.sleep(0.2)  # honor the backpressure hint before retrying
        # resubmit at most two queue-fulls per round: hammering the full
        # backlog back in just sheds it again
        for rid in shed[:2 * MAX_QUEUE]:
            prompt, max_new = by_rid[rid]
            fr.submit(Request(rid=rid, prompt=prompt, max_new_tokens=max_new))
    raise AssertionError(f"rids never completed after {rounds} rounds: {shed}")


def _completed_tokens(fr, rids):
    return {rid: fr.ledger.records[rid].outcome["tokens"] for rid in rids}


# ------------------------------------------------------------------ golden
def _golden_leg(workdir):
    """One replica, throttled submission: the bit-identity reference."""
    from vescale_tpu.serve import FleetSupervisor

    specs = _specs(os.path.join(workdir, "golden"))
    os.makedirs(os.path.join(workdir, "golden"), exist_ok=True)
    fr, Client = _router()
    sup = FleetSupervisor(specs, max_restarts=2, restart_backoff_s=0.3).start()
    try:
        fr.add_replica("r0", Client(specs[0].url))
        _wait_up(fr, sup)
        waves = _prompts(SPIKE) + _prompts(WAVE2, base_rid=200)
        from vescale_tpu.serve import Request

        for i in range(0, len(waves), MAX_QUEUE):
            for rid, prompt, max_new in waves[i:i + MAX_QUEUE]:
                fr.submit(Request(rid=rid, prompt=prompt,
                                  max_new_tokens=max_new))
            _drain(fr, sup)
        # instantaneous-queue races can still shed a few: resubmit until
        # every rid completed (the same client contract the spike leg uses)
        _complete_all(fr, sup, waves)
        fr.fleet_ledger_check()
        return _completed_tokens(fr, [w[0] for w in waves])
    finally:
        sup.stop_all(grace_s=30.0)


# -------------------------------------------------------------- closed loop
def _save_rollout_checkpoint(workdir):
    """The rollout target: a checkpoint of the SAME fixed-seed params the
    children serve — post-rollout decode must stay bit-identical."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    import vescale_tpu.checkpoint as ckpt
    from vescale_tpu.models.llama import Llama, LlamaConfig

    cfg = LlamaConfig(
        vocab_size=64, hidden_size=16, intermediate_size=32,
        num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=2,
        max_position_embeddings=64, dtype=jnp.float32,
    )
    params = Llama(cfg).init(jax.random.key(0),
                             jnp.ones((1, 8), jnp.int32))["params"]
    root = os.path.join(workdir, "rollout_ckpt")
    ckpt.save(root, {"model": params})
    return root


def _autoscale_leg(workdir, golden_tokens):
    import vescale_tpu.telemetry as telemetry
    from vescale_tpu.ndtimeline import api as nd_api
    from vescale_tpu.serve import (
        Autoscaler,
        FleetSupervisor,
        Request,
        RolloutController,
    )
    from vescale_tpu.telemetry import timeseries as _ts

    telemetry.init(out_dir=None, memtrack=False, jsonl=False,
                   timeseries=True, alerts=True, timeseries_cadence_s=0.0)
    mgr = nd_api.init_ndtimers(rank=0)
    legdir = os.path.join(workdir, "autoscale")
    os.makedirs(legdir, exist_ok=True)
    specs = _specs(legdir, arm_template=True)
    fr, Client = _router()
    sup = FleetSupervisor(specs, max_restarts=2, restart_backoff_s=0.3).start()
    try:
        fr.add_replica("r0", Client(specs[0].url))
        _wait_up(fr, sup)
        autoscaler = Autoscaler(
            fr, sup, "r0",
            client_factory=lambda spec: Client(spec.url),
            min_replicas=1, max_replicas=2,
            up_burn=1.0, down_burn=0.5, up_queue=4,
            up_hold_s=0.3, down_hold_s=1.5, cooldown_s=2.0, window_s=3.0,
        )

        # ---- the 5x spike, open loop: queue depth blows past up_queue
        spike = _prompts(SPIKE)
        for rid, prompt, max_new in spike:
            fr.submit(Request(rid=rid, prompt=prompt, max_new_tokens=max_new),
                      session=f"sess{rid % 4}" if rid % 2 == 0 else None)
        scale_at = ttft_spike = None
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            sup.poll()
            fr.pump()
            d = autoscaler.tick()
            if d.startswith("scale_up"):
                scale_at = time.monotonic()
                ttft_spike = _ttft_p99(fr)
                break
            time.sleep(0.05)
        assert scale_at is not None, (
            f"spike never tripped scale-up: {autoscaler.last_signals}"
        )
        sig = autoscaler.last_signals
        assert sig["queue_depth"] is not None and sig["queue_depth"] >= 4, sig
        # the signal came through the PR-16 store, sampled off the
        # router's own /fleet publishes
        store = _ts.get_store()
        assert store is not None
        assert store.reduce("fleet_timeline_queue_depth", 60.0, "last") is not None
        assert len(fr.replicas) == 2 and autoscaler.scale_ups == 1
        clone = next(rid for rid in fr.replicas if rid != "r0")
        assert clone in sup.managed and sup.alive(clone)

        # ---- readmission: the clone's breaker opens during its cold
        # import, then the half-open probe lets it back in.  A fresh
        # breaker is "closed" before its first failed poll, so the state
        # waited for is the half-open close itself, not the word.  The
        # autoscaler is not ticked meanwhile: r0 may finish the spike
        # alone before a slow clone is up, and a tick would then count
        # the quiet fleet's hold and drain the clone it is waiting for
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            sup.poll()
            fr.pump()
            breaker = fr.replicas[clone].breaker
            if breaker.closes >= 1 and breaker.state == "closed":
                break
            time.sleep(0.1)
        assert fr.replicas[clone].breaker.state == "closed", "clone never readmitted"

        # ---- everything completes bit-identically (sheds resubmitted)
        _complete_all(fr, sup, spike, autoscaler=autoscaler)
        fr.fleet_ledger_check()
        spike_tokens = _completed_tokens(fr, [w[0] for w in spike])
        for rid, toks in spike_tokens.items():
            assert toks == golden_tokens[rid], (rid, toks, golden_tokens[rid])
        # TTFT recovery, attributed by construction: r0's histogram holds
        # the overloaded spike tail (it served alone pre-scale-up), the
        # clone's holds only post-scale-up service
        fr.poll(force=True)
        ttft_spike = ttft_spike or (
            (fr.replicas["r0"].feed or {}).get("ttft_s", {}).get("p99"))
        ttft_rec = (fr.replicas[clone].feed or {}).get("ttft_s", {}).get("p99")
        clone_stats = fr.summary()["replicas"][clone]
        assert clone_stats["closes"] >= 1, (
            "clone joined without a half-open readmission"
        )
        print(f"autoscale: scale-up fired (signals={sig}), clone {clone} "
              f"readmitted, {SPIKE} rids bit-identical; "
              f"ttft_p99 spike={ttft_spike} recovered={ttft_rec}")

        # ---- rolling rollout #1: canary_diverge armed on r0 -> fleet
        # auto-rollback (nothing stays committed)
        ckpt_root = _save_rollout_checkpoint(workdir)
        diverge = RolloutController(
            fr, ckpt_root, CANARY_PROMPTS, max_new_tokens=4,
            canary=True, baseline=True, stage_timeout_s=180.0,
        ).run()
        assert diverge["ok"] is False, diverge
        assert diverge["diverged"] == "r0", diverge
        assert diverge["committed"] == [], diverge
        assert "deterministic" in (diverge["reason"] or ""), diverge

        # ---- rolling rollout #2: the fault is consumed -> clean sweep
        clean = RolloutController(
            fr, ckpt_root, CANARY_PROMPTS, max_new_tokens=4,
            canary=True, baseline=True, stage_timeout_s=180.0,
        ).run()
        assert clean["ok"] is True, clean
        assert sorted(clean["committed"]) == sorted(fr.replicas), clean
        print(f"rollout: diverge auto-rolled-back {diverge['rolled_back']}, "
              f"clean sweep committed {clean['committed']}")

        # ---- post-rollout traffic: the swapped weights ARE the ckpt's
        wave2 = _prompts(WAVE2, base_rid=200)
        for rid, prompt, max_new in wave2:
            fr.submit(Request(rid=rid, prompt=prompt, max_new_tokens=max_new))
        _complete_all(fr, sup, wave2, autoscaler=None)
        for rid, toks in _completed_tokens(fr, [w[0] for w in wave2]).items():
            assert toks == golden_tokens[rid], (rid, toks)

        # ---- quiet fleet: the under-threshold hold drains the clone
        deadline = time.monotonic() + 120.0
        scale_down_seen = False
        while time.monotonic() < deadline:
            sup.poll()
            fr.pump()
            d = autoscaler.tick()
            scale_down_seen = scale_down_seen or d.startswith("scale_down")
            if scale_down_seen and len(fr.replicas) == 1:
                break
            time.sleep(0.1)
        assert scale_down_seen and len(fr.replicas) == 1, (
            f"clone never drained: {autoscaler.last_decision}"
        )
        assert autoscaler.scale_downs == 1
        assert not sup.alive(clone)
        assert fr.pick(session="sess0").id == "r0"  # ring re-homed
        fr.fleet_ledger_check()

        # ---- the run left its span vocabulary on the router's ring
        spans = mgr.flush()
        scale_dirs = {s.tags.get("direction") for s in spans
                      if s.metric == "fleet-scale"}
        assert scale_dirs == {"up", "down"}, scale_dirs
        stages = {s.tags.get("stage") for s in spans
                  if s.metric == "fleet-rollout-stage"}
        assert "fleet-leg" in stages, stages
        counts = fr.summary()["counts"]
        return {"ttft_spike": ttft_spike, "ttft_recovered": ttft_rec,
                "counts": counts}
    finally:
        sup.stop_all(grace_s=30.0)
        nd_api.deinit_ndtimers()
        telemetry.shutdown()


def main() -> None:
    import shutil
    import tempfile

    work = tempfile.mkdtemp(prefix="autoscale_smoke_")
    t0 = time.monotonic()
    try:
        golden = _golden_leg(work)
        res = _autoscale_leg(work, golden)
        print(
            "AUTOSCALE SMOKE OK: 5x spike -> scale-up -> half-open readmit "
            "-> bit-identical completion (zero lost/dup rids); rolling "
            "rollout auto-rolled-back on canary_diverge then committed "
            "clean; quiet fleet scaled back down "
            f"(counts={json.dumps(res['counts'], sort_keys=True)}, "
            f"{time.monotonic() - t0:.1f}s)"
        )
    finally:
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    main()
