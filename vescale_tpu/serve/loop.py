"""run_serve_resilient — the serve loop born inside the fault envelope.

The serving analog of ``resilience.loop.run_resilient``: the same
watchdog heartbeat, faultsim schedule, preemption choreography and PR-5
control plane wrap a continuous-batching decode loop instead of a train
step.  Failure playbook (docs/serving.md has the full matrix):

  hung decode            ``beat()`` lands once per decode step; a step that
                         stops progressing trips the watchdog exactly like
                         a hung train step — stack dump, flight record,
                         (optional) abort so the supervisor restarts and
                         queued clients retry.
  request deadline       timeout cancellation at the step boundary (the
                         decode step in flight is read first): the
                         request is EXPLICITLY rejected (``timed_out``),
                         its slot and pages freed, the batch marches on.
  slow decode            injected via faultsim ``slow_decode``; a p99-TTFT
                         SLO budget turns sustained slowness into load
                         shedding at admission instead of unbounded queue
                         growth.
  OOM mid-batch          the NEWEST admitted request is evicted and
                         replayed (decode is deterministic: it regenerates
                         the same tokens later); the batch never crashes.
  SIGTERM / preemption   stop admitting, DRAIN: in-flight requests decode
                         to completion (or their deadlines), queued ones
                         are rejected re-queueable with a retry-after, then
                         a clean ``status="preempted"`` return.
  multi-host desync      every rank exchanges [step, flags, scheduler
                         fingerprint] per step boundary; fault flags
                         (preempt / oom / request_timeout) are OR-agreed so
                         one rank's injection drives every rank's eviction
                         identically, and any divergence in slot
                         assignment/queue/token counts raises DesyncError
                         on EVERY rank before the divergent batch decodes.

Accounting contract (asserted by scripts/serve_smoke.py under injected
faults): every submitted request reaches EXACTLY one terminal outcome —
``completed`` (with deterministic tokens), ``shed``, ``timed_out`` or
``preempted_requeue`` — none lost, none duplicated.

Observability (ISSUE 12; docs/serving.md): with the ndtimeline profiler
live every request emits its lifecycle span chain (reqtrace.py) and each
decode step advances the telemetry step counter + writes its own
``kind="serve"`` steps.jsonl line; goodput/MFU gauges ride the registry
(obs.py); ``VESCALE_SERVE_OPS_PORT`` starts the live
``/metrics``+``/healthz``+``/router`` endpoints for probes and the
multi-replica router.
"""

from __future__ import annotations

import os
import threading
import time
import zlib
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..resilience import consistency as _cons
from ..resilience import faultsim as _fs
from ..resilience.preempt import PreemptionHandler
from ..resilience.watchdog import Watchdog
from . import reqtrace
from .engine import DecodeFeed, DecodeStep, PrefillStep, ServeEngine
from .obs import ServeObservability
from .scheduler import ContinuousBatchingScheduler, Request

__all__ = ["ControlChannel", "ServeResult", "run_serve_resilient"]

# control-plane vector (fixed width): [magic, step, preempt, oom, rtimeout,
# wall_mask, draining, then the scheduler fingerprint fields + the
# sampled-token crc].  preempt/oom/rtimeout/wall_mask are ORs (any rank's
# fault or clock-local deadline verdict drives every rank identically);
# everything else must agree or the batch must not decode again.
_COORD_MAGIC = 0x5E47E
_OR_FIELDS = ("preempt", "oom", "rtimeout", "wall_mask")
_COORD_FIELDS = ("coord_magic", "step", "preempt", "oom", "rtimeout", "wall_mask", "draining")
# scheduler.fingerprint() field names, in order: 3 scheduler fields + the
# cache fingerprint (which grew ``page_refs`` with prefix sharing — the
# live page-reference total, so shared-page refcount divergence trips the
# same DesyncError as slot-assignment divergence)
_FP_FIELDS = (
    "sched_hash", "queue_len", "active", "cache_hash", "free_slots",
    "free_pages", "tokens_held", "page_refs",
)


@dataclass
class ServeResult:
    status: str  # "completed" | "preempted"
    steps: int = 0
    outcomes: Dict[int, Dict[str, Any]] = field(default_factory=dict)
    counts: Dict[str, int] = field(default_factory=dict)
    drained: int = 0  # in-flight requests finished during the drain
    rejected_on_drain: int = 0


class ControlChannel:
    """Thread-safe replica control mailbox — the ``/control`` POST
    endpoint's provider (runs on the ops HTTP thread) posts one job at a
    time into it; the serve loop consumes at step boundaries, so weight
    swaps only ever happen between decode steps, never mid-batch.

    Ops (the rolling-rollout wire protocol; serve/autoscale.py's
    ``RolloutController`` is the caller):

      ``reload``   ``{"op": "reload", "checkpoint": path,
                   "prompts": [[tok, ...], ...], "max_new_tokens": N,
                   "canary": bool, "baseline": bool,
                   "expected": [[tok, ...], ...] | null}`` — drain
                   in-flight work, hot-swap weights from ``checkpoint``
                   (elastic params-only restore, no process restart),
                   then the canary stage: each pinned golden prompt is
                   replayed TWICE through the fresh weights (the two
                   streams must be bit-identical — the determinism
                   check that catches ``canary_diverge``) and, when
                   ``expected`` is given, both must equal it (the
                   cross-replica consistency check).  ``baseline``
                   computes ``expected`` from the OLD weights pre-swap
                   (the checkpoint-equivalence rollout).  Divergence
                   swaps the old weights straight back
                   (``rolled_back``); a pass parks them in-process
                   (``committed``, two-phase) until ``commit``/``revert``.
      ``commit``   drop the retained old tree — the fleet-wide rollout
                   succeeded, this replica's rollback leg is closed.
      ``revert``   drain, swap the retained old tree back in —
                   another replica's canary diverged, roll back.
      ``status``   read the live rollout state (also on /router v5).

    Posting while a job is pending returns ``{"ok": false, "error":
    "busy"}`` — the controller retries after the in-flight stage lands.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._job: Optional[Dict[str, Any]] = None
        self.state: Optional[Dict[str, Any]] = None  # mirror of obs.rollout

    def provider(self, payload: Dict[str, Any]) -> Dict[str, Any]:
        op = payload.get("op")
        if op == "status":
            return {"ok": True, "rollout": self.state}
        if op in ("reload", "commit", "revert"):
            if op == "reload" and not payload.get("checkpoint"):
                return {"ok": False, "error": "reload needs a checkpoint path"}
            with self._lock:
                if self._job is not None:
                    return {"ok": False, "error": "busy", "rollout": self.state}
                self._job = dict(payload)
            return {"ok": True, "accepted": op}
        return {"ok": False, "error": f"unknown op {op!r}"}

    def take(self) -> Optional[Dict[str, Any]]:
        with self._lock:
            job, self._job = self._job, None
            return job


def run_serve_resilient(
    *,
    engine: ServeEngine,
    scheduler: ContinuousBatchingScheduler,
    arrivals: Sequence[Tuple[int, Request]],
    max_steps: int = 100_000,
    wall_deadline_s: Optional[float] = None,
    preemption: Optional[PreemptionHandler] = None,
    install_signal_handlers: bool = True,
    watchdog: Optional[Watchdog] = None,
    watchdog_timeout_s: Optional[float] = None,
    coordinate: Optional[bool] = None,
    barrier_timeout_s: Optional[float] = None,
    on_step: Optional[Callable[[int, int], None]] = None,
    inbox: Optional[Any] = None,
    ops: Optional[Any] = None,
    idle_sleep_s: Optional[float] = None,
    replica_id: Optional[str] = None,
    speculative: Optional[Any] = None,
    control: Optional[ControlChannel] = None,
) -> ServeResult:
    """Serve ``arrivals`` (a deterministic open-loop schedule of
    ``(arrival_step, Request)`` pairs, ascending) to completion under the
    resilience envelope; returns when every request is terminal
    ("completed") or a preemption drain finishes ("preempted").

    ``wall_deadline_s`` (default env ``VESCALE_SERVE_DEADLINE_S``, 0=off)
    cancels any in-flight request that has been decoding longer than the
    budget; per-request ``deadline_steps`` ride on top deterministically.
    ``coordinate`` defaults to ``jax.process_count() > 1`` — the PR-5
    control plane then agrees on every admission/eviction/drain decision.

    The loop never loses a request: a mid-batch fault evicts and REPLAYS
    the newest request; a drain rejects queued requests re-queueable; a
    deadline rejects explicitly.  ``ServeResult.outcomes`` is the ledger.

    One decode step is kept in flight (docs/serving.md, "One decode step
    in flight"): an iteration launches step k, fed from step k-1's ids as
    they lie on the device (``engine.decode(DecodeFeed(...))``; the host's
    first token for a slot prefilled since), and only then reads step
    k-1, records its ids and keeps its books while the device works on k.
    A slot's length advances at the launch by the positions the step
    settles in the cache: one, whatever the token; or, where a step moves a
    block (below), none for a denoising pass and the block for the call
    that commits it.  A request that ends by its token count gets no further launch;
    an EOS is learned one step late (the extra step's ids are dropped); ids
    are recorded only for the slot that still holds the request they were
    launched for.  Every boundary that may
    evict, cancel, begin a drain, run a ``/control`` job or exit reads the
    step in flight first, so its outcome is that of a loop that read each
    step at once, and every stream is ``engine.replay_greedy``'s.  With
    ``speculative`` every step is read in the iteration that launched it.
    ``on_step`` runs with that one step possibly unread.

    A prefill is one step deep too: ``engine.prefill`` launches and returns
    its ``PrefillStep`` unread, and with a step in flight the iteration
    launches every admitted request's prefill, then its decode step with
    the fresh slots fed from the device (``DecodeFeed.fresh`` names the
    ``PrefillStep``), and only then reads the first tokens
    (``_first_tokens``: the TTFT is the instant the host has the token), so
    the device goes from the prefill into the step.  A request whose
    budget that unread token fills gets no step; an EOS as first token is
    learned one step late, as any EOS.  With no step in flight, with
    ``speculative``, after a prefix hit and for an engine whose ``prefill``
    returns a row, the first token is read at once.  Every prefill is read
    in the iteration that launched it, but the one that rides:

    A prompt RIDES the decode step where the engine OFFERS that
    (``engine.rides``: a single-stage ``ServeEngine``, and a
    ``HybridServeEngine`` whose model's module gives the body of such a step,
    whose ``prefill`` then launches nothing and returns a ``PrefillStep`` that
    waits; a block engine offers none, and
    nothing here names a model).  THIS LOOP decides, on what it observes:
    with a step in flight, no ``speculative`` and no prefix hit, an
    admitted request's prompt WAITS (``waiting``), and the oldest prompt
    that waits goes into the step about to be launched, if that step moves
    a slot (``DecodeFeed.rider``): one program, each weight read once for the
    decode rows and the prompt's together.  One rider a step: a second
    admission of the same iteration rides the step after.  A slot whose
    prompt waits or rides is not stepped (no ``cache.advance``, no token
    owed from it; the engine runs its decode row idle): the step that
    carries the prompt MAKES its first token, the step after it takes that
    from the device as it takes any unread prefill's, and the host reads it
    once that one is enqueued, a step late (``pending`` holds the rider
    beside its step), so no gap opens on the device.  Nothing waits across
    a boundary that settles: ``_settle`` reads the step in flight, its
    rider, and every prompt that still waits (whose read launches it, alone),
    as does an iteration that finds no slot to step.  A request cancelled
    from ``on_step`` while its prompt waits is dropped here and its prompt
    left to the engine, which launches it alone before any prompt that came
    after it.  ``engine.trace_counters()["prefill_rides"]`` of
    ``["prefill_launches"]`` says how often a prompt rode.

    A step yields a COUNT of tokens a slot, which the loop learns from the
    engine (``engine.block``, a ``BlockSchedule``, where generation is by
    diffusion over blocks; one token a slot without it).  Such an engine's
    prefill yields no token and is never read: the request's first tokens,
    and its TTFT, come with its first block's commit, and every block's
    tokens are recorded together, in order, when that call is read; a pass
    in between yields none.  A commit rides in the call that runs the first pass of the block
    after it (``BlockSchedule.fuses``: the request is owed more), so a block
    of ``B`` tokens is ``T`` calls and a request of ``n`` blocks ``n T + 1``;
    the program has ``commit_places`` places for such commits a call, and a
    slot that finds them taken is held for that call.  The schedule is
    static, so the host knows at the launch what the call in flight will
    yield (``_InFlight.block`` mirrors the slot's open block) and the
    pipeline stays one step deep; a request gets exactly
    ``max_new_tokens``, its last block cut where the budget ends.
    ``speculative`` and a prefix cache are refused for such an engine.

    Fleet mode (serve/fleet.py): ``inbox`` (a ``RequestInbox``) feeds the
    loop NETWORK submissions — drained into ``scheduler.submit`` at every
    step boundary, with an ``VESCALE_SERVE_IDLE_S`` sleep when the
    replica is fully idle so an empty replica does not spin; the loop
    then runs until the inbox is closed (or a preemption drain).  ``ops``
    injects a pre-started ``OpsServer`` (the caller owns its lifecycle —
    it can keep serving final outcomes after the loop returns); without
    it the loop starts/stops its own via ``VESCALE_SERVE_OPS_PORT``.

    Throughput multipliers (ISSUE 15): a scheduler built with a
    ``PrefixCache`` (or ``VESCALE_SERVE_PREFIX_CACHE=1``) maps cached
    prompt-prefix pages at admission and the loop prefills ONLY the
    suffix (``engine.prefill_suffix``), folding every freshly-prefilled
    prompt back into the radix tree; ``speculative`` (a
    ``SpeculativeDecoder``) replaces each single-token decode step with
    draft-k-then-verify-in-one-batched-step — greedy acceptance keeps the
    emitted stream BITWISE identical to plain decode, so both multipliers
    compose with every fault above (an evicted request's replay re-hits
    the tree; rejected draft tokens roll back uncommitted).

    Rolling weight rollout (``control``, a :class:`ControlChannel` —
    serve/fleet.py wires it to the ``/control`` endpoint): ``reload``
    jobs run the drain -> [baseline] -> swap -> canary ->
    committed | rolled_back machine at step boundaries — admission pauses
    (the /router feed drops ``accepting``) while in-flight requests
    decode out through the OLD weights, the fresh checkpoint is restored
    params-only in-process (``serve.load_params`` +
    ``ServeEngine.swap_params`` — the compiled programs take params as an
    argument, so no recompile), pinned golden prompts replay through the
    new weights, and any divergence swaps the old tree straight back.
    Single-process replicas only (fleet mode): nothing coordinates a
    reload across ranks, so a ``coordinate=True`` loop must not be given
    a control channel.
    """
    import jax

    from .. import telemetry as _tel
    from ..analysis import envreg
    from ..ndtimeline import api as _nd
    from ..ndtimeline import predefined as _p
    from ..telemetry import costaudit as _ca
    from ..telemetry import ops_server as _ops

    if not _fs.is_armed():
        _fs.arm_from_env()
    handler = preemption or PreemptionHandler()
    own_handler = preemption is None
    if own_handler and install_signal_handlers:
        handler.install()
    coord = (jax.process_count() > 1) if coordinate is None else bool(coordinate)
    if wall_deadline_s is None:
        wall_deadline_s = envreg.get_float("VESCALE_SERVE_DEADLINE_S") or 0.0
    if coord and wall_deadline_s and scheduler.cache.num_slots > 63:
        raise ValueError(
            "coordinated wall deadlines ride an int64 slot bitmask on the "
            f"control plane: num_slots={scheduler.cache.num_slots} > 63 — "
            "use per-request deadline_steps instead"
        )

    own_wd = False
    wd = watchdog
    if wd is None:
        wd = Watchdog.from_env(timeout_s=watchdog_timeout_s)
        own_wd = wd is not None
    if own_wd:
        wd.start()

    def _beat(step: int, phase: str = "decode") -> None:
        if wd is not None:
            wd.beat(step, phase=phase)

    if coord and control is not None:
        raise ValueError(
            "the /control reload machine is single-process (fleet mode): "
            "nothing coordinates a weight swap across ranks"
        )

    arrivals = sorted(arrivals, key=lambda p: (p[0], p[1].rid))
    next_arrival = 0
    token_crc = 0  # running digest of every sampled token (desync tripwire)
    draining = False
    reload_job: Optional[Dict[str, Any]] = None  # the in-flight /control job
    reload_t0 = 0.0  # when its drain began (the drain span's start)
    retained_params = None  # old tree parked by a committed swap (two-phase)
    result = ServeResult(status="completed")
    cache = scheduler.cache
    # what a step yields: one token a slot, or (``block``, the engine's
    # ``BlockSchedule``) what its schedule says of the slot's open block
    block = getattr(engine, "block", None)
    commit_places = block.commit_places(cache.num_slots) if block is not None else 0
    if block is not None and (speculative is not None or scheduler.prefix is not None):
        raise NotImplementedError(
            "speculative= and a prefix cache need a step of one token a position and a cache without slot "
            f"state; {type(engine).__name__} generates by blocks of {block.B}, whose open block is slot state"
        )
    # does a prompt ride a decode step?  The engine's OFFER (either engine's; one
    # without the attribute, or with it false, launches every
    # prompt alone); this loop takes it up where a step is about to be launched
    rides = getattr(engine, "rides", False)
    # the decode step in flight: launched, its ids not yet read, with what
    # each stepped slot held at the launch and will be given of the step's
    # ids: ``{slot: (request, skip, count)}``, and the prompt it carries:
    # ``[(request, its PrefillStep)]``, one or none.  At most one.
    pending: Optional[Tuple[DecodeStep, Dict[int, Tuple[Any, int, int]], List[Tuple[Any, PrefillStep]]]] = None
    # the requests admitted whose prompt still WAITS for a step to ride, by
    # slot and in the order they came: ``{slot: (request, its PrefillStep)}``.
    # A step carries one; a slot that waits is stepped by none
    waiting: Dict[int, Tuple[Any, PrefillStep]] = {}

    # ------------------------------------------- observability wiring
    # goodput/MFU accounting + the /healthz + /router providers; the ops
    # HTTP thread starts ONLY when VESCALE_SERVE_OPS_PORT is set (off by
    # default — maybe_start returns None without creating a thread)
    obs = ServeObservability(
        scheduler, engine=engine, watchdog=wd, rank=jax.process_index(),
        replica_id=replica_id, speculative=speculative,
    )
    from ..telemetry import alerts as _alerts

    if ops is not None:
        # a pre-started server (serve/fleet.py): register the live
        # providers on it; the CALLER owns start/stop — it may keep the
        # port serving final outcomes after this loop returns
        ops.register("healthz", obs.health).register("router", obs.router)
        ops.register("alerts", _alerts.payload)
        own_ops = False
    else:
        ops = _ops.maybe_start(health=obs.health, router=obs.router,
                               extra={"alerts": _alerts.payload})
        own_ops = ops is not None
    # arm the default serve rule pack on the live alert engine (idempotent
    # by pack name — a respawned loop in the same process re-arms cleanly);
    # the TTFT burn rule arms only when an SLO is configured
    if _alerts.is_active():
        _alerts.get_engine().arm_pack(
            "serve",
            _alerts.serve_rule_pack(
                slo_ttft_s=envreg.get_float("VESCALE_SERVE_SLO_TTFT_S") or 0.0
            ),
        )
    # ---- fleet trace persistence (VESCALE_FLEET_TRACE_DIR): this
    # replica's span stream lands on disk AS THE RUN GOES — flushed every
    # VESCALE_FLEET_TRACE_FLUSH_EVERY boundaries, so even an abrupt
    # replica_kill leaves every prior boundary's spans harvestable for
    # the fleet timeline assembler (fleettrace.assemble_fleet_timeline).
    # The stream file is keyed by replica_id (rank-qualified on
    # multi-process replicas so two ranks never interleave one file); a
    # respawned replica appends to the same file (its stranded prior-life
    # chains classify as superseded-by-failover at verification).  The
    # handler is scoped to THIS run (unregistered in the finally), and
    # flush cadence belongs to whoever owns the profiler: when the loop
    # initialized it, it drains per boundary for crash durability and
    # deactivates it on exit; an externally-initialized profiler keeps
    # its owner's flush discipline (the stream receives whatever the
    # owner flushes while the loop runs).
    fleet_trace_every = 0
    fleet_trace_handler = None
    own_nd_trace = False
    fleet_trace_dir = envreg.get_str("VESCALE_FLEET_TRACE_DIR")
    if fleet_trace_dir:
        from ..ndtimeline.handlers import LocalRawHandler

        own_nd_trace = not _nd.is_active()
        if own_nd_trace:
            _nd.init_ndtimers(rank=jax.process_index())
        stream = (
            obs.replica_id
            if jax.process_count() == 1
            else f"{obs.replica_id}.rank{jax.process_index()}"
        )
        fleet_trace_handler = LocalRawHandler(
            os.path.join(fleet_trace_dir, f"{stream}.spans.jsonl")
        )
        _nd.get_manager().register_handler(fleet_trace_handler)
        if own_nd_trace:
            fleet_trace_every = max(
                1, envreg.get_int("VESCALE_FLEET_TRACE_FLUSH_EVERY") or 1
            )
    # cold-start retry_after_s seed: with a calibration table armed the
    # decode step is priceable before anything has run; the first prefill
    # wall time (below) covers the un-calibrated case
    cal_seed = obs.calibrated_step_estimate()
    if cal_seed is not None:
        scheduler.seed_step_time(cal_seed)

    def _event(kind: str, **fields) -> None:
        _tel.record_event(f"serve_{kind}", **fields)

    # ------------------------------------------------- rollout machine
    from . import fleettrace as _ftrace

    def _rollout_state(state: str, step: int, **detail) -> None:
        """Publish the live rollout stage everywhere at once: the /router
        v5 ``rollout`` field, the /control ``status`` reply, and a
        ``serve_rollout_<state>`` event."""
        snap = {
            "state": state,
            "checkpoint": (reload_job or {}).get("checkpoint"),
            "detail": detail,
        }
        obs.rollout = snap
        if control is not None:
            control.state = snap
        _event(
            f"rollout_{state}", at_step=step,
            **{k: v for k, v in detail.items() if not isinstance(v, (list, dict))},
        )

    def _perform_reload(step: int) -> None:
        """The post-drain half of a /control job, run AT a step boundary
        with zero in-flight requests: [baseline ->] swap -> canary ->
        committed | rolled_back for ``reload``; instant park-drop for
        ``commit``; swap-back for ``revert``.  Queued requests stay
        queued throughout and decode through whichever tree survives."""
        nonlocal retained_params
        job = reload_job
        rep = obs.replica_id
        op = job.get("op", "reload")
        if op == "commit":
            finalized = retained_params is not None
            retained_params = None  # the fleet-wide rollout stuck: drop
            _rollout_state("committed", step, finalized=finalized)
            return
        if op == "revert":
            if retained_params is None:
                _rollout_state("rolled_back", step, reverted=False,
                               reason="nothing retained")
                return
            t0 = time.perf_counter()
            engine.swap_params(retained_params)
            retained_params = None
            _tel.count("serve_rollbacks_total")
            _ftrace.rollout_stage(rep, "reverted", time.perf_counter() - t0)
            _rollout_state("rolled_back", step, reverted=True)
            return
        # ------------------------------------------------- op == reload
        from . import load_params as _load_params

        ckpt = job["checkpoint"]
        prompts = [[int(t) for t in p] for p in (job.get("prompts") or [])]
        mnt = max(1, int(job.get("max_new_tokens") or 8))
        canary = bool(job.get("canary", True)) and bool(prompts)
        expected = job.get("expected")
        _tel.count("serve_rollouts_total")
        if canary and expected is None and job.get("baseline"):
            # checkpoint-equivalence rollout: the OLD weights' streams
            # are the reference the new weights must reproduce bitwise
            _rollout_state("baseline", step, prompts=len(prompts))
            b0 = time.perf_counter()
            expected = [engine.replay_greedy(p, mnt) for p in prompts]
            _ftrace.rollout_stage(rep, "baseline", time.perf_counter() - b0,
                                  checkpoint=ckpt)
        _rollout_state("swapping", step)
        s0 = time.perf_counter()
        try:
            old = engine.swap_params(_load_params(ckpt, engine.params))
        except Exception as e:  # unreadable/mismatched checkpoint: no swap
            why = f"restore failed: {e}"
            _ftrace.rollout_stage(rep, "swap", time.perf_counter() - s0,
                                  ok=False, reason=why, checkpoint=ckpt)
            _tel.count("serve_rollbacks_total")
            _rollout_state("rolled_back", step, reason=why)
            return
        _ftrace.rollout_stage(rep, "swap", time.perf_counter() - s0,
                              checkpoint=ckpt)
        ok, why, streams = True, "", []
        if canary:
            _rollout_state("canary", step, prompts=len(prompts))
            c0 = time.perf_counter()
            for p in prompts:
                s1 = engine.replay_greedy(p, mnt, canary=True)
                s2 = engine.replay_greedy(p, mnt, canary=True)
                if ok and s1 != s2:
                    # the determinism check: one replay's flipped logit
                    # (faultsim canary_diverge, or real nondeterminism)
                    # cannot reproduce, so the twin replays disagree
                    ok, why = False, "canary replay not deterministic"
                streams.append(s1)
            if ok and expected is not None:
                exp = [[int(t) for t in s] for s in expected]
                if exp != streams:
                    ok, why = False, "canary streams diverged from expected"
            _ftrace.rollout_stage(rep, "canary", time.perf_counter() - c0,
                                  ok=ok, reason=why or None, checkpoint=ckpt)
        if ok:
            # two-phase: park the old tree until the controller's fleet-
            # wide commit (or revert, if a LATER replica's canary fails)
            retained_params = old
            _ftrace.rollout_stage(rep, "committed", 0.0, checkpoint=ckpt)
            _rollout_state("committed", step, finalized=False,
                           streams=streams, canary=canary)
        else:
            engine.swap_params(old)
            _tel.count("serve_rollbacks_total")
            _ftrace.rollout_stage(rep, "rolled_back", 0.0, ok=False,
                                  reason=why, checkpoint=ckpt)
            _rollout_state("rolled_back", step, reason=why, streams=streams)

    def _coordinate(step: int, oom_fired: bool, rt_fired: bool,
                    wall_mask: int) -> Tuple[bool, bool, bool, int]:
        """One control-plane allgather: OR the fault/preempt flags and the
        (rank-local, clock-dependent) wall-deadline slot mask, verify
        scheduler+cache fingerprints agree.  Raises DesyncError (on every
        rank — the gathered matrix is identical everywhere) on divergence
        in slot assignment, queue, page tables or sampled tokens."""
        from ..distributed import allgather_ints

        fp = scheduler.fingerprint()
        vec = [
            _COORD_MAGIC,
            step,
            1 if handler.requested() else 0,
            1 if oom_fired else 0,
            1 if rt_fired else 0,
            wall_mask,
            1 if draining else 0,
            *[int(v) & 0x7FFFFFFF for v in fp],
            token_crc & 0x7FFFFFFF,
        ]
        rows = allgather_ints(vec, tag="serve_coord", timeout_s=barrier_timeout_s)
        if rows.shape[0] == 1:
            return bool(vec[2]), oom_fired, rt_fired, wall_mask
        preempt_any = bool(rows[:, 2].any())
        oom_any = bool(rows[:, 3].any())
        rt_any = bool(rows[:, 4].any())
        wall_any = int(np.bitwise_or.reduce(rows[:, 5]))
        fields = _COORD_FIELDS + _FP_FIELDS[: len(fp)] + ("token_crc",)
        mismatched = _cons.compare_rows(rows[:, : len(fields)], fields)
        for f in _OR_FIELDS:
            mismatched.pop(f, None)
        if mismatched:
            _tel.count("consistency_mismatches_total")
            _event("desync", at_step=step, fields=sorted(mismatched))
            raise _cons.DesyncError(mismatched, rows)
        if preempt_any and not handler.requested():
            handler.request()  # a PEER is being preempted; drain together
        return preempt_any, oom_any, rt_any, wall_any

    def _first_token(inf, now: float) -> float:
        """The request's first token is out: its TTFT, anchored at
        SUBMISSION (under load the queue wait is the dominant term, and the
        SLO shed path must see it).  The queue-wait component was observed
        at admission (scheduler); per-tenant TTFT rides along once tenants
        are in play (a non-default class, or weights configured); the
        zero-config single-tenant path observes exactly what it always did."""
        ttft = now - inf.submit_wall
        tenant = inf.req.tenant
        scheduler.observe_ttft(
            ttft,
            tenant=(
                tenant
                if (tenant != "default" or scheduler.tenant_weights)
                else None
            ),
        )
        return ttft

    def _prefill_admitted(step: int) -> List[Tuple[Any, Any]]:
        """Admit queued requests into free slots and LAUNCH their prefills,
        reading none: ``[(request, what its prefill returned)]``, for
        ``_first_tokens``.  Where a step moves a block the prefill yields no
        token: it opens the slot's first block, and the TTFT comes with that
        block."""
        with _nd.ndtimeit(_p.SERVE_ADMIT) as span:      # the scheduler's and the allocator's work
            admitted = scheduler.admit(step)
            if span is not None:
                span.tag(admitted=len(admitted))
        launched = []
        for inf in admitted:
            _beat(step, "prefill")
            inf.admit_wall = time.perf_counter()
            # queue-wait is measured to THIS request's own prefill start
            # (not the admit() pop): with several same-batch admissions the
            # later ones "wait" through the earlier prefills too, so the
            # queue_wait + prefill components tile the TTFT exactly
            wait_s = max(0.0, inf.admit_wall - inf.submit_wall)
            reqtrace.queue_wait(inf.req.rid, inf.slot, wait_s, replays=inf.replays)
            _tel.observe("serve_ttft_queue_wait_seconds", wait_s)
            if inf.prefix_hit:
                # prefix-cache hit: the slot's leading table entries map
                # cached pages (alloc_shared) — commit them and run only
                # the suffix (``decode_multi``'s road: its row comes read).
                # The TTFT decomposition still tiles: this request's
                # prefill component is just smaller.
                cache.commit_prefill(inf.slot, inf.prefix_hit)
                first = engine.prefill_suffix(
                    inf.req.prompt, inf.slot, inf.prefix_hit
                )
            else:
                first = engine.prefill(inf.req.prompt, inf.slot)
                cache.commit_prefill(inf.slot, len(inf.req.prompt))
            if scheduler.prefix is not None:
                # adopt the freshly-written full pages into the radix tree
                # (shared-prefix blocks dedupe against what it holds);
                # pure function of the admission stream — both ranks grow
                # bit-identical trees and the retain events fold into the
                # cache digest the control plane compares
                scheduler.prefix.insert(
                    inf.req.prompt, cache.page_table[inf.slot]
                )
                hit_rate = scheduler.prefix.stats.hit_rate()
                if hit_rate is not None:
                    _tel.set_gauge("serve_prefix_hit_rate", hit_rate)
            if speculative is not None:
                # mirror the admission in the drafter cache + its own full
                # prefill; a drafter pool too full to mirror degrades the
                # slot to undrafted (plain-speed, still bit-correct)
                speculative.admit(
                    inf.slot, inf.req.prompt, inf.req.max_new_tokens
                )
            if block is not None:
                inf.block = block.open(len(inf.req.prompt))
            launched.append((inf, first))
        return launched

    def _first_tokens(step: int, launched: List[Tuple[Any, Any]]) -> None:
        """Read each launched prefill's first token (the wait for the device;
        a row that came read, a stub engine's or a prefix hit's, is sampled on
        the host) and record it: its latency IS the TTFT.  A block engine's
        prefill yields no token and is never read: its books alone."""
        for inf, first in launched:
            if scheduler.active.get(inf.slot) is not inf:
                continue    # (a rider read a step late: its request was cancelled or evicted since)
            if block is None:
                _sample(inf.slot, first.token if isinstance(first, PrefillStep) else engine.greedy(first))
            now = time.perf_counter()
            prefill_s = now - inf.admit_wall
            reqtrace.prefill(inf.req.rid, inf.slot, prefill_s,
                             tokens=len(inf.req.prompt))
            _tel.observe("serve_ttft_prefill_seconds", prefill_s)
            if block is None:
                # cold-start retry seed: the first prefill wall time is the
                # first measured bound on a step of this model (conservative —
                # a decode step is cheaper than a full prefill)
                scheduler.seed_step_time(prefill_s)
                # the prefill's half of the TTFT decomposition closes it
                _event("admit", rid=inf.req.rid, slot=inf.slot, at_step=step,
                       replays=inf.replays, ttft_s=round(_first_token(inf, now), 6))
            else:
                _event("admit", rid=inf.req.rid, slot=inf.slot, at_step=step,
                       replays=inf.replays)

    def _sample(slot: int, token: int) -> None:
        nonlocal token_crc
        scheduler.record_token(slot, token)
        # EVERY sampled token is raw throughput — the prefill-sampled
        # first token included, so raw >= goodput always holds
        _tel.count("serve_tokens_generated_total")
        token_crc = zlib.crc32(int(token).to_bytes(4, "little", signed=False), token_crc)

    def _finish_done(step: int) -> None:
        """Complete slots that hit EOS or their token budget, by the tokens
        the host has read."""
        for slot in sorted(list(scheduler.active)):
            inf = scheduler.active[slot]
            done = len(inf.tokens) >= inf.req.max_new_tokens or (
                inf.req.eos_id is not None and inf.tokens and inf.tokens[-1] == inf.req.eos_id
            )
            if done:
                scheduler.complete(slot)
                if draining:
                    result.drained += 1
                _event("complete", rid=inf.req.rid, slot=slot, at_step=step,
                       tokens=len(inf.tokens))

    def _record(flight: Tuple[DecodeStep, Dict[int, Tuple[Any, int, int]], Any]) -> Dict[int, Tuple[Any, int]]:
        """Read a launched step's ids (the wait for the device, unless the
        ``decode`` call that it fed has waited already) and record, in
        order, those the launch meant for each slot (its one; of a block,
        ``[skip: skip + count]``, up to an EOS) for the slot that STILL
        holds the request it was launched for: a slot cancelled, evicted or
        completed since, or taken by another request, drops its ids.
        Returns ``{slot: (request, tokens recorded)}`` of those kept."""
        dstep, slots, _rider = flight
        # plain ints, a row a slot (of one id, or of a block's): read once
        next_ids = dstep.tokens.reshape(cache.num_slots, -1).tolist()
        kept: Dict[int, Tuple[Any, int]] = {}
        with _nd.ndtimeit(_p.SERVE_SAMPLE):
            for slot in sorted(slots):
                inf, skip, count = slots[slot]
                if scheduler.active.get(slot) is not inf:
                    continue
                if count and not inf.tokens and block is not None:
                    _event("first_tokens", rid=inf.req.rid, slot=slot,
                           ttft_s=round(_first_token(inf, time.perf_counter()), 6))
                eos, taken = inf.req.eos_id, 0
                for tok in next_ids[slot][skip:skip + count]:
                    _sample(slot, tok)
                    taken += 1
                    if eos is not None and tok == eos:
                        break
                kept[slot] = (inf, taken)
        return kept

    def _close_step(step: int, dt: float, width: int,
                    emitted: Dict[int, Tuple[Any, int]],
                    predicted_s: Optional[float] = None) -> None:
        """The host's books for one decode step that was READ, ``width`` slots
        wide, and the tokens it gave (``emitted``: ``{slot: (request,
        count)}``); then the requests those tokens end, and the step's line.
        ``dt`` is the wall time the host spent on it: with a step in flight
        the wait for it, so a step's period and each slot's inter-token
        latency.  The device works on the next step meanwhile."""
        with _nd.ndtimeit(_p.SERVE_BOOKS):
            if predicted_s is not None:
                pid = _ca.record_prediction(
                    "serve_step", predicted_us=predicted_s * 1e6,
                    detail={"active": width},
                )
                _ca.record_measurement(pid, measured_us=dt * 1e6)
            scheduler.observe_step_time(dt)
            reqtrace.decode_step(step, dt, width)
            for slot, (inf, m) in emitted.items():
                # a step that gave a slot several tokens (a speculative verify,
                # a block's commit) amortizes over them its wall and that of
                # the steps since the slot's last token that gave it none (a
                # block's denoising passes): a token's latency is its share of
                # the time its request waited for it
                inf.unyielded_s += dt
                if not m:
                    continue
                per_tok, inf.unyielded_s = inf.unyielded_s / m, 0.0
                for j in range(m):
                    scheduler.observe_itl(per_tok)
                    reqtrace.decode_token(
                        inf.req.rid, slot, len(inf.tokens) - m + j, per_tok
                    )
            _tel.count("serve_decode_steps_total")
            obs.on_decode_step(step, dt, width)
            _finish_done(step)
            # serve's auto_inc_step: every span emitted since the last line
            # (prefill, decode, terminals) carries the CURRENT profiler step —
            # advance the counter and record the per-step line NOW so the
            # steps.jsonl spans rollup attributes them to this decode step, not
            # a stale training step
            if _nd.is_active():
                mgr = _nd.get_manager()
                span_step = mgr.step
                mgr.inc_step()
            else:
                span_step = step
            _tel.record_step(
                {
                    "step": span_step,
                    "serve_step": step,
                    "step_time_s": dt,
                    "active": width,
                    "queue_depth": len(scheduler.queue),
                },
                kind="serve",
            )

    def _settle(step: int) -> None:
        """Read the step in flight, if there is one, and complete what it
        ended: after this the scheduler holds every token the device has
        made, as it did at every boundary before steps were launched ahead.
        Every decision that reads a token or changes the slot set calls
        this first."""
        nonlocal pending
        if pending is None:
            return
        flight, pending = pending, None
        t0 = time.perf_counter()
        kept = _record(flight)
        # ... and the first token of the prompt that step carried, and of those that still waited
        # for a step to ride (their read launches them, alone: nothing waits across this)
        _first_tokens(step, flight[2] + list(waiting.values()))
        waiting.clear()
        _close_step(step, time.perf_counter() - t0, len(flight[1]), kept)

    step = 0
    try:
        while True:
            if step >= max_steps:
                raise RuntimeError(
                    f"serve loop exceeded max_steps={max_steps} with "
                    f"{len(scheduler.queue)} queued / {len(scheduler.active)} active"
                )
            # what the top of an iteration does before admission; a step that a boundary reads first
            # (``_settle``) nests its ``.fetch``, ``vs.serve-sample`` and ``vs.serve-books`` inside
            with _nd.ndtimeit(_p.SERVE_BOUNDARY):
                _fs.set_step(step)
                _beat(step, "boundary")
                # liveness, not just decode progress: the /router feed's
                # serve_step advances every boundary, so a fleet router can
                # tell "idle" from "wedged" (stale-feed breaker trip)
                obs.serve_step = step
                if _fs.fires("hang", ctx=f"serve_step{step}"):
                    # wedged decode: stall past every deadline — the watchdog's
                    # detect/dump/abort path is the only way out, as in training
                    time.sleep(envreg.get_float("VESCALE_FAULTSIM_HANG_S"))
                if _fs.fires("preempt", ctx=f"serve_step{step}"):
                    handler.request()
                oom_fired = _fs.fires("oom", ctx=f"serve_step{step}")
                rt_fired = _fs.fires("request_timeout", ctx=f"serve_step{step}")

                # ------------------------------------------------ arrivals
                while (
                    not draining
                    and next_arrival < len(arrivals)
                    and arrivals[next_arrival][0] <= step
                ):
                    _, req = arrivals[next_arrival]
                    next_arrival += 1
                    scheduler.submit(req, step)
                if inbox is not None:
                    # network submissions (fleet mode): drained at the step
                    # boundary so scheduler state stays single-threaded; a
                    # malformed/duplicate wire submission is rejected and
                    # counted, never allowed to kill the serving loop.
                    # Mid-drain arrivals still enter the ledger — the exit
                    # flush below terminates them preempted_requeue.
                    for req, pushed_at in inbox.drain_stamped():
                        try:
                            scheduler.submit(req, step)
                        except ValueError as e:
                            _tel.count("serve_inbox_rejected_total")
                            _event("inbox_reject", rid=getattr(req, "rid", -1),
                                   at_step=step, error=str(e))
                        else:
                            reqtrace.inbox_wait(req.rid, pushed_at)

                # -------------------------------------------- weight rollout
                if control is not None:
                    if reload_job is None:
                        reload_job = control.take()
                        if reload_job is not None:
                            reload_t0 = time.perf_counter()
                            if reload_job.get("op", "reload") != "commit":
                                # admission pauses from here (the /router feed
                                # drops `accepting`); in-flight decodes out
                                _rollout_state("draining", step,
                                               inflight=len(scheduler.active))
                    if reload_job is not None:
                        # the drain is judged by what the device has made
                        _settle(step)
                        op = reload_job.get("op", "reload")
                        if op == "commit" or not scheduler.active:
                            if op != "commit":
                                _ftrace.rollout_stage(
                                    obs.replica_id, "drain",
                                    time.perf_counter() - reload_t0,
                                )
                            _perform_reload(step)
                            reload_job = None

                # ------------------------------------------- control plane
                # wall-deadline verdicts are rank-LOCAL clock reads: compute
                # before the exchange so every rank applies the OR-agreed set
                # (one rank's clock crossing the budget must not desync peers)
                wall_mask = 0
                for slot in scheduler.wall_expired_slots(time.perf_counter(), wall_deadline_s):
                    wall_mask |= 1 << slot
                if coord:
                    preempt_now, oom_fired, rt_fired, wall_mask = _coordinate(
                        step, oom_fired, rt_fired, wall_mask
                    )
                else:
                    preempt_now = handler.requested()

                # a boundary that may evict, cancel, begin the drain or find
                # nothing left to step first reads the step in flight, so its
                # verdict is the one the tokens give (flags are the agreed ones:
                # every rank settles at the same boundaries)
                if pending is not None and (
                    oom_fired or rt_fired or wall_mask
                    or (preempt_now and not draining)
                    or scheduler.step_deadline_due(step)
                    or not scheduler.active
                ):
                    _settle(step)

                # ------------------------------------------------- faults
                if oom_fired and scheduler.active:
                    # mid-batch OOM: evict the newest request, replay it later
                    # — the batch survives, nothing is lost
                    victim = scheduler.requeue_newest(reason="injected oom")
                    _event("oom_evict", rid=victim, at_step=step)
                force_slots: List[int] = []
                if rt_fired and scheduler.active:
                    # the OLDEST in-flight request's deadline is forced expired
                    force_slots = [min(scheduler.active,
                                       key=lambda s: (scheduler.active[s].admit_step, s))]

                # ------------------------------------- timeout cancellation
                scheduler.timeout_queued(step)
                wall_slots = [s for s in range(cache.num_slots) if wall_mask & (1 << s)]
                expired = scheduler.expire_active(
                    step, force_slots=force_slots, wall_slots=wall_slots,
                )
                for rid in expired:
                    _event("request_timeout", rid=rid, at_step=step)

                # ------------------------------------------------ drain / done
                if preempt_now and not draining:
                    draining = True
                    obs.draining = True  # /healthz reports the drain live
                    _tel.count("resilience_preemptions_total")
                    _event("drain_begin", at_step=step,
                           inflight=len(scheduler.active), queued=len(scheduler.queue))
                    result.rejected_on_drain = len(scheduler.reject_queued("preempted"))
                if draining and not scheduler.active:
                    # a mid-drain eviction may have requeued its victim: flush
                    # it as re-queueable too — the ledger must end all-terminal
                    result.rejected_on_drain += len(scheduler.reject_queued("preempted"))
                    result.status = "preempted"
                    break
                if (
                    not draining
                    and next_arrival >= len(arrivals)
                    and (inbox is None or inbox.closed)
                    and scheduler.all_terminal()
                ):
                    # close() may have raced this iteration's drain: anything
                    # push()ed before the close is still owed service — drain
                    # once more and only exit when the inbox is truly empty
                    # (push-after-close is refused at push(), so this final
                    # drain is exhaustive)
                    late = inbox.drain_stamped() if inbox is not None else ()
                    if not late:
                        result.status = "completed"
                        break
                    for req, pushed_at in late:
                        try:
                            scheduler.submit(req, step)
                        except ValueError as e:
                            _tel.count("serve_inbox_rejected_total")
                            _event("inbox_reject", rid=getattr(req, "rid", -1),
                                   at_step=step, error=str(e))
                        else:
                            reqtrace.inbox_wait(req.rid, pushed_at)

            # ---------------------------------------------- admit + decode
            if speculative is not None:
                # free drafter slots whose target terminated since the
                # last boundary BEFORE admission can reuse the slot ids
                speculative.sync_slots(scheduler.active)
            # the prefills whose first token stays on the device until the
            # decode step that takes it from there is enqueued, by slot:
            # ``{slot: (request, its PrefillStep)}``: the prompt that the step in
            # flight CARRIES (its first token is that step's to make), and those
            # launched in this iteration
            unread: Dict[int, Tuple[Any, PrefillStep]] = {}
            if pending is not None:
                unread = {inf.slot: (inf, first) for inf, first in pending[2] if scheduler.active.get(inf.slot) is inf}
            for slot in [slot for slot, (inf, _) in waiting.items() if scheduler.active.get(slot) is not inf]:
                # cancelled (a hook's ``timeout``) while its prompt waited: the engine still launches it, alone
                # and before any prompt that came after it, so the pages are written in the order of admission
                del waiting[slot]
            launched: List[Tuple[Any, Any]] = []
            if not draining and reload_job is None:
                launched = _prefill_admitted(step)
                # what needs the host's token before it can go on reads at
                # once: no step in flight to feed from (the next starts cold,
                # from the host's tokens), a drafter, a row that came read;
                # a block engine's prefill yields none and only keeps its books
                if block is None and pending is not None and speculative is None:
                    for inf, first in launched:
                        if isinstance(first, PrefillStep):
                            # (a prompt the engine launched is read behind this iteration's step; one that waits for
                            # its launch, where prompts ride, behind the step after the one that carries it)
                            (waiting if rides and not first.launched else unread)[inf.slot] = (inf, first)
                _first_tokens(step, [(inf, first) for inf, first in launched if inf.slot not in unread and inf.slot not in waiting])
                # the prefill-sampled token may already satisfy the request
                # (max_new_tokens=1, or EOS on the first token): complete it
                # here or the decode below would overrun its token budget
                # (one still unread ends by its count, below; its EOS is
                # learned a step late, as a decode step's is)
                _finish_done(step)
            if scheduler.active:
                if _fs.fires("slow_decode", ctx=f"serve_step{step}"):
                    time.sleep(envreg.get_float("VESCALE_FAULTSIM_SLOW_DECODE_S"))
                _beat(step, "decode")
                # cost-audit prediction BEFORE the step runs (and before
                # observe_step_time folds the measurement into the very
                # estimator the prediction came from)
                predicted_step_s = (
                    scheduler.step_time_estimate() if _ca.is_active() else None
                )
                t0 = time.perf_counter()
                # the slots this step moves, what each will be given of its
                # ids (one token; of a block, what the schedule says: none
                # from a denoising pass), and what feeds each: the id the
                # step in flight is making for it, as it lies on the device,
                # or (prefilled since) the host's last token; a block lies
                # on the device as the pass before left it; a first token still
                # unread is fed as its ``PrefillStep``, from the device too.  A
                # request whose budget the ids in flight, or its unread first
                # token, fill ends by its count: nothing more is launched for it
                flight = pending[1] if pending is not None else {}
                stepped: Dict[int, Tuple[Any, int, int]] = {}
                fresh: Dict[int, Any] = {}
                settles: Dict[int, int] = {}
                # a block engine's: the slots whose commit rides with their next
                # block's first pass, and those that found every place for commit
                # rows taken and are held this call
                fused: List[int] = []
                deferred: List[int] = []
                for slot, inf in scheduler.active.items():
                    if slot in waiting:
                        continue    # its prompt has not been through the stack yet
                    in_flight = flight.get(slot, (None,))[0] is inf
                    owed = (inf.req.max_new_tokens - len(inf.tokens) - (flight[slot][2] if in_flight else 0)
                            - (slot in unread))
                    if owed <= 0:
                        continue
                    if block is None:
                        stepped[slot], settles[slot] = (inf, 0, 1), 1
                        if slot in unread:
                            fresh[slot] = unread[slot][1]
                        elif not in_flight:
                            fresh[slot] = inf.tokens[-1]
                        continue
                    fuse = block.fuses(inf.block, owed)
                    if fuse and len(fused) == commit_places:
                        stepped[slot], settles[slot] = (inf, 0, 0), 0
                        deferred.append(slot)
                        continue
                    skip, count, settles[slot] = block.plan(inf.block, owed, fuse)
                    stepped[slot] = (inf, skip, count)
                    if fuse:
                        fused.append(slot)
                # a prompt RIDES the step about to be launched where the engine
                # offers that and the step moves a slot: the oldest of the prompts
                # that wait (one a step: a second admission of one iteration rides the
                # step after).  Its slot is not stepped by the step that carries it:
                # that step makes its FIRST token, which the step after it is fed from
                # the device and the host reads once that one is enqueued.  With no slot
                # to step there is no step to ride: the prompts that wait go alone, now
                rider: List[Tuple[Any, PrefillStep]] = []
                if waiting and stepped:
                    rider = [waiting.pop(next(iter(waiting)))]
                elif waiting:
                    unread.update(waiting)
                    waiting.clear()
                active_slots = sorted(stepped)
                drafted_rows = (speculative.drafted_slots(active_slots)
                                if speculative is not None else [])
                if speculative is None or not drafted_rows:
                    # plain decode, one step deep — also the speculative
                    # path's fallback when EVERY active slot degraded to
                    # undrafted (the drafter pool couldn't mirror them): the
                    # stream is the target's argmaxes either way, and k+1
                    # drafter launches plus a (k+1)-wide verify that drafts
                    # nothing would only add cost.  The step's greedy ids are
                    # taken in the decode program and its logits stay on the
                    # device (nothing here reads them)
                    before, pending = pending, None
                    if stepped:
                        if block is not None:
                            # (the prefills this call goes in behind, for the engine's count: nobody reads them)
                            feed = DecodeFeed(before[0] if before is not None else None,
                                              {inf.slot: first for inf, first in launched},
                                              slots={slot: stepped[slot][2] for slot in active_slots
                                                     if slot not in deferred},
                                              fused=fused, deferred=len(deferred))
                        elif before is None:
                            feed = np.zeros((cache.num_slots,), np.int32)
                            for slot, tok in fresh.items():
                                feed[slot] = tok
                        else:
                            feed = DecodeFeed(before[0], fresh, rider=rider[0][1] if rider else None)
                        # launch; with a step in flight the call then waits
                        # for THAT step's ids, the device already in this one
                        pending = (engine.decode(feed), stepped, rider)
                        for slot in active_slots:
                            # a step appends one position to every slot it
                            # stepped, whatever the token; the call that commits
                            # a block the block, a denoising pass nothing
                            if settles[slot]:
                                cache.advance(slot, settles[slot])
                    if speculative is not None:
                        # a drafter needs every token on the host before it
                        # drafts again: its loop reads each step at once
                        before, pending = pending, None
                    width = len(before[1]) if before is not None else 0
                    emitted = _record(before) if before is not None else {}
                else:
                    # draft-then-verify (speculative.py): the drafter
                    # proposes k tokens per mirrored slot, the target
                    # scores all of them in ONE batched multi-token paged
                    # step, and greedy acceptance emits the longest prefix
                    # the target itself would have produced — the stream
                    # stays BITWISE plain decode, only the number of
                    # target launches per token changes
                    spec = speculative
                    tokens = [0] * cache.num_slots
                    for slot, tok in fresh.items():
                        tokens[slot] = tok
                    d0 = time.perf_counter()
                    drafts = spec.draft(tokens, drafted_rows)
                    reqtrace.draft(step, spec.k,
                                   time.perf_counter() - d0, len(drafted_rows))
                    toks = np.zeros((cache.num_slots, spec.k + 1), np.int32)
                    for slot in active_slots:
                        toks[slot, 0] = tokens[slot]
                        toks[slot, 1:] = drafts[slot]
                    v0 = time.perf_counter()
                    vlogits = engine.decode_multi(toks)
                    verify_s = time.perf_counter() - v0
                    drafted_now = accepted_now = 0
                    width, emitted = len(active_slots), {}
                    for slot in active_slots:
                        inf = stepped[slot][0]
                        budget = inf.req.max_new_tokens - len(inf.tokens)
                        out, accepted = spec.accept(
                            drafts[slot], vlogits[slot], budget, inf.req.eos_id
                        )
                        for tok in out:
                            cache.advance(slot)
                            _sample(slot, tok)
                        emitted[slot] = (inf, len(out))
                        if slot not in spec.undrafted:
                            drafted_now += min(spec.k, budget)
                            accepted_now += accepted
                    spec.drafted += drafted_now
                    spec.accepted += accepted_now
                    spec.verify_steps += 1
                    # rejected draft positions: roll the drafter back to
                    # the target's committed lengths — their pages stay
                    # reserved, the bytes become uncommitted garbage
                    spec.rewind(cache.lengths, drafted_rows)
                    rate = spec.accept_rate()
                    reqtrace.verify(step, verify_s, drafted_now,
                                    accepted_now, rate)
                    _tel.count("serve_spec_drafted_tokens_total", drafted_now)
                    _tel.count("serve_spec_accepted_tokens_total", accepted_now)
                    _tel.count("serve_spec_verify_steps_total")
                    if rate is not None:
                        _tel.set_gauge("serve_spec_accept_rate", rate)
                dt = time.perf_counter() - t0
                # the first tokens left on the device, now that the step that
                # takes them from there is enqueued behind their prefills: the
                # wait for a prefill is no part of the step's period
                _first_tokens(step, list(unread.values()))
                if _fs.fires("replica_kill", ctx=f"serve_step{step}"):
                    # an abrupt replica crash MID-LOAD (consulted only on
                    # decode steps with in-flight work, so the kill always
                    # strands requests for the fleet router to fail over):
                    # no drain, no cleanup, no ledger flush — os._exit is
                    # the point.  The supervisor restart + elastic restore
                    # path brings the replica back.
                    _event("replica_kill", at_step=step,
                           inflight=len(scheduler.active))
                    os._exit(envreg.get_int("VESCALE_FAULTSIM_KILL_EXIT_CODE"))
                if width:
                    # a step was read in this iteration (with one in flight,
                    # the one launched before)
                    _close_step(step, dt, width, emitted, predicted_step_s)
            if on_step is not None:
                with _nd.ndtimeit(_p.SERVE_HOOK):
                    on_step(step, len(scheduler.active))
            if (
                inbox is not None
                and not draining
                and not scheduler.active
                and not scheduler.queue
                and next_arrival >= len(arrivals)
            ):
                # fully idle inbox-fed replica: don't spin a core at the
                # boundary rate — sleep one idle slice (the loop keeps
                # iterating, so watchdog beats and /router liveness
                # (serve_step) keep advancing while idle)
                if idle_sleep_s is None:
                    idle_sleep_s = envreg.get_float("VESCALE_SERVE_IDLE_S")
                if idle_sleep_s:
                    with _nd.ndtimeit(_p.SERVE_IDLE):      # no request to serve: the chip's idle here has a name
                        time.sleep(idle_sleep_s)
            if fleet_trace_every and step % fleet_trace_every == 0:
                # crash-durable tracing: this boundary's spans reach the
                # raw stream before the next decode step can kill us
                _nd.flush()
            step += 1
    finally:
        result.steps = step
        result.outcomes = dict(scheduler.outcomes)
        result.counts = dict(scheduler.counts)
        if fleet_trace_handler is not None:
            if fleet_trace_every:
                _nd.flush()  # the drain's final spans must be harvestable
            _nd.get_manager().unregister_handler(fleet_trace_handler)
            if own_nd_trace:
                # restore the dormant state this loop found: a second run
                # in the same process must not double-register or inherit
                # a live profiler it never asked for
                _nd.deinit_ndtimers()
        if own_ops and ops is not None:
            ops.stop()
        if own_wd:
            wd.stop()
        if own_handler and install_signal_handlers:
            handler.uninstall()
    _event("serve_done", status=result.status, steps=step, **result.counts)
    return result
