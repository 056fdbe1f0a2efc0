"""Continuous-batching scheduler — admission control, slots, load shedding.

Orca/vLLM-style iteration-level scheduling on top of the paged cache: new
requests are admitted into FREE decode slots at step boundaries (never
mid-step — the compiled decode program runs whole batches of static
shape), finished/expired requests are evicted the same way, and the batch
is re-packed purely by rewriting page-table rows.

Robustness is the design center, not an afterthought:

  * **Bounded queue** — ``submit`` beyond ``max_queue`` is rejected
    immediately with a ``retry_after_s`` hint (queue depth x observed
    decode-step time), not buffered until memory or the SLO dies.
  * **SLO shedding** — while the rolling p99 time-to-first-token exceeds
    ``slo_ttft_s``, new submissions are shed: an overloaded server that
    answers some requests inside the SLO beats one that answers all of
    them late (every shed increments ``resilience_shed_total`` /
    ``serve_requests_shed_total``).  TTFT anchors at SUBMISSION, so queue
    wait counts.  NOTE: the p99 is a rank-local wall statistic — on
    coordinated multi-host replicas leave the SLO at 0 (shed at the
    frontend); a divergent shed decision raises ``DesyncError`` loudly
    rather than silently forking the batch (docs/serving.md).
  * **Total accounting** — every submitted request ends in EXACTLY one
    terminal outcome (``completed`` / ``shed`` / ``timed_out`` /
    ``preempted_requeue``); the invariant the serve smoke asserts under
    fault injection ("none lost, none duplicated").

All decisions are deterministic functions of (request stream, step
index, capacity): ``fingerprint()`` digests queue + slot assignment so the
serve loop's cross-rank agreement check catches any divergence before a
divergent batch can decode.
"""

from __future__ import annotations

import dataclasses
import time
import zlib
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Sequence, Tuple

from . import reqtrace
from .kv_cache import PagedKVCache

__all__ = ["Request", "ShedError", "ContinuousBatchingScheduler"]

TERMINAL = ("completed", "shed", "timed_out", "preempted_requeue")


def _safe(name: str) -> str:
    """Metric-name-safe tenant slug (the alerts module's convention)."""
    return "".join(c if (c.isalnum() or c == "_") else "_" for c in name)


def _tenant_weights_from_env() -> Dict[str, float]:
    """Parse ``VESCALE_SERVE_TENANT_WEIGHTS`` — ``"tenant:weight"`` pairs,
    comma-separated (``"paid:3,free:1"``).  Empty/unset means the
    weight-aware admission gate is OFF.  Malformed values raise: a
    silently-dropped SLO class is worse than a crash at construction."""
    from ..analysis import envreg

    raw = envreg.get_str("VESCALE_SERVE_TENANT_WEIGHTS")
    if not raw:
        return {}
    out: Dict[str, float] = {}
    for part in raw.split(","):
        name, sep, w = part.strip().partition(":")
        if not sep or not name:
            raise ValueError(
                f"VESCALE_SERVE_TENANT_WEIGHTS: expected tenant:weight, got {part!r}"
            )
        out[name] = float(w)
    return out


@dataclasses.dataclass(frozen=True)
class Request:
    """One generation request.  ``deadline_steps`` is relative to the
    submission step (deterministic — the multi-host rig's unit); a wall
    deadline can ride on top via the loop's ``VESCALE_SERVE_DEADLINE_S``.
    ``eos_id`` stops generation early; ``max_new_tokens`` always bounds
    it.  ``tag`` is an OPAQUE client token echoed verbatim into this
    request's terminal outcome row — the fleet router stamps each
    dispatch attempt with one so a stale ledger row from a prior
    dispatch of the same rid can never be mistaken for the current
    attempt's result (serve/router.py)."""

    rid: int
    prompt: Tuple[int, ...]
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    deadline_steps: Optional[int] = None
    tag: Optional[int] = None
    # SLO class (per-tenant accounting + weight-aware shedding): requests
    # without one land in the "default" class, so single-tenant callers
    # never see the field
    tenant: str = "default"

    def __post_init__(self):
        if not self.prompt:
            raise ValueError(f"request {self.rid}: empty prompt")
        if self.max_new_tokens < 1:
            raise ValueError(f"request {self.rid}: max_new_tokens must be >= 1")
        if not self.tenant:
            raise ValueError(f"request {self.rid}: tenant must be a non-empty string")


class ShedError(RuntimeError):
    """Raised to a *direct* ``submit(..., raise_on_shed=True)`` caller when
    admission control rejects the request; carries the retry hint."""

    def __init__(self, rid: int, reason: str, retry_after_s: float):
        self.rid = rid
        self.reason = reason
        self.retry_after_s = retry_after_s
        super().__init__(
            f"request {rid} shed ({reason}); retry after ~{retry_after_s:.2f}s"
        )


@dataclasses.dataclass
class _InFlight:
    req: Request
    slot: int
    submit_step: int
    admit_step: int
    submit_wall: float = 0.0  # perf_counter at SUBMISSION (TTFT anchor —
    # queue wait is the dominant TTFT term under load; kept across replays)
    admit_wall: float = 0.0  # perf_counter at admission (wall deadlines)
    tokens: List[int] = dataclasses.field(default_factory=list)
    replays: int = 0
    # prompt tokens served from cached prefix pages (prefix_cache.py): the
    # loop prefills only prompt[prefix_hit:]
    prefix_hit: int = 0
    # where a decode step moves a block and yields a count of tokens (an
    # engine with a ``BlockSchedule``): the host's mirror of the slot's open
    # block, which the serve loop opens at the prefill and advances at every
    # launch; None where a step yields one token
    block: Optional[List[int]] = None
    # wall of the decode steps since the request's last token that gave it
    # none (a block's denoising passes): the next token's latency holds it
    unyielded_s: float = 0.0


class ContinuousBatchingScheduler:
    """Queue + slots + outcome ledger.  The serve loop drives it:
    ``submit`` on arrivals, ``expire`` then ``admit`` at each step
    boundary, ``record_token`` per decoded token, ``complete`` / ``evict``
    / ``requeue_newest`` as decode results come back."""

    def __init__(
        self,
        cache: PagedKVCache,
        *,
        max_queue: Optional[int] = None,
        slo_ttft_s: Optional[float] = None,
        ttft_window: int = 256,
        prefix_cache: Optional["PrefixCache"] = None,
        tenant_weights: Optional[Dict[str, float]] = None,
    ):
        from ..analysis import envreg
        from ..telemetry.registry import Histogram

        self.cache = cache
        # radix-tree prefix cache (prefix_cache.py): admission consults it
        # for page-granular prompt-prefix hits.  Explicit instance wins;
        # otherwise VESCALE_SERVE_PREFIX_CACHE=1 builds one from env so
        # every driver (loop, fleet replica) gets it with zero
        # call-site changes
        if prefix_cache is None and envreg.get_bool("VESCALE_SERVE_PREFIX_CACHE"):
            from .prefix_cache import PrefixCache

            prefix_cache = PrefixCache.from_env(cache)
        self.prefix = prefix_cache
        if max_queue is None:
            max_queue = envreg.get_int("VESCALE_SERVE_MAX_QUEUE")
            if envreg.get_raw("VESCALE_SERVE_MAX_QUEUE") is None:
                # nobody set a bound: the default may not be smaller than what the
                # cache admits at once plus as many waiting, or a burst that fits
                # the slots is shed before admit() has run
                max_queue = max(max_queue, 2 * cache.num_slots)
        self.max_queue = max_queue
        if slo_ttft_s is None:
            slo_ttft_s = envreg.get_float("VESCALE_SERVE_SLO_TTFT_S")
        self.slo_ttft_s = float(slo_ttft_s) if slo_ttft_s else 0.0
        # (request, submit_step, submit_wall) — the wall stamp anchors TTFT
        self.queue: Deque[Tuple[Request, int, float]] = deque()
        self.active: Dict[int, _InFlight] = {}  # slot -> in-flight
        self.outcomes: Dict[int, Dict[str, Any]] = {}  # rid -> terminal record
        # own rolling histograms: admission control must work with telemetry
        # dormant (the registry classes are plain objects, not the gate)
        self._ttft = Histogram("serve_ttft_seconds", window=ttft_window)
        self._step_time = Histogram("serve_decode_step_seconds", window=ttft_window)
        self._itl = Histogram("serve_itl_seconds", window=ttft_window)
        # cold-start seed for retry_after_s: before any decode step has
        # been observed the 10ms floor wildly underestimates real models —
        # the loop seeds this from the first prefill wall time (see
        # seed_step_time) so the first shed wave's retry hint is honest
        self._step_time_seed: Optional[float] = None
        # goodput vs raw throughput (docs/serving.md): raw counts every
        # sampled token; goodput only tokens of COMPLETED requests
        self.raw_tokens = 0
        self.goodput_tokens = 0
        self.counts = {
            "submitted": 0,
            "admitted": 0,
            "completed": 0,
            "shed": 0,
            "timed_out": 0,
            "evicted": 0,
            "requeued": 0,
            "resubmitted": 0,
        }
        # ---- per-tenant SLO classes.  With weights configured (arg or
        # VESCALE_SERVE_TENANT_WEIGHTS "tenant:weight,..."), admission
        # becomes weight-aware: a tenant whose queued share exceeds its
        # weighted slice of max_queue sheds FIRST, before the global
        # limits touch anyone else.  Unconfigured (None/empty) the gate
        # is entirely off — single-tenant behavior is bit-identical.
        if tenant_weights is None:
            tenant_weights = _tenant_weights_from_env()
        self.tenant_weights: Dict[str, float] = dict(tenant_weights or {})
        for t, w in self.tenant_weights.items():
            if w <= 0:
                raise ValueError(f"tenant {t!r}: weight must be > 0, got {w}")
        # per-tenant accounting exists regardless of weights: counters and
        # a TTFT histogram per observed class (lazily created; the rollup
        # rides the /router v5 feed)
        self.tenant_counts: Dict[str, Dict[str, int]] = {}
        self._tenant_ttft: Dict[str, Any] = {}
        # queue depth per tenant, maintained INCREMENTALLY at every queue
        # mutation: the weight-aware shed check runs per submit and must
        # cost O(1), never a queue scan
        self._tenant_qdepth: Dict[str, int] = {}
        self._tenant_cap_cache: Dict[str, Optional[int]] = {}
        self._ttft_window = ttft_window
        # event-sourced digest: every scheduling decision folds into a
        # running crc so fingerprint() is O(1) per step boundary (the
        # control-plane exchange must cost << a decode step)
        self._digest = 0

    def _fold(self, *ints: int) -> None:
        self._digest = zlib.crc32(
            b"".join((v & 0xFFFFFFFF).to_bytes(4, "little") for v in ints), self._digest
        )

    # ------------------------------------------------------------- tenants
    def _tenant_counts(self, tenant: str) -> Dict[str, int]:
        counts = self.tenant_counts.get(tenant)
        if counts is None:
            counts = self.tenant_counts[tenant] = {
                "submitted": 0, "shed": 0, "completed": 0,
            }
        return counts

    def _tenant_observe_ttft(self, tenant: str, seconds: float) -> None:
        from .. import telemetry as _tel
        from ..telemetry.registry import Histogram

        hist = self._tenant_ttft.get(tenant)
        if hist is None:
            hist = self._tenant_ttft[tenant] = Histogram(
                f"serve_ttft_seconds_tenant_{_safe(tenant)}",
                window=self._ttft_window,
            )
        hist.observe(seconds)
        _tel.observe(f"serve_ttft_seconds_tenant_{_safe(tenant)}", seconds)

    def tenant_queue_depth(self, tenant: str) -> int:
        return self._tenant_qdepth.get(tenant, 0)

    def _tq(self, tenant: str, delta: int) -> None:
        d = self._tenant_qdepth.get(tenant, 0) + delta
        if d:
            self._tenant_qdepth[tenant] = d
        else:
            self._tenant_qdepth.pop(tenant, None)

    def tenant_cap(self, tenant: str) -> Optional[int]:
        """The weighted queue slice a tenant may hold before it sheds
        (None = no weights configured, gate off).  An UNLISTED tenant
        weighs 1.0 against the configured classes — naming only the paid
        class still deprioritizes everyone else deterministically."""
        if not self.tenant_weights or not self.max_queue:
            return None
        if tenant in self._tenant_cap_cache:  # weights are ctor-frozen
            return self._tenant_cap_cache[tenant]
        w = float(self.tenant_weights.get(tenant, 1.0))
        total = sum(self.tenant_weights.values())
        if tenant not in self.tenant_weights:
            total += 1.0
        cap = max(1, int(self.max_queue * w / total))
        self._tenant_cap_cache[tenant] = cap
        return cap

    def _tenant_shed_reason(self, req: Request) -> Optional[str]:
        cap = self.tenant_cap(req.tenant)
        if cap is not None and self.tenant_queue_depth(req.tenant) >= cap:
            return (
                f"tenant {req.tenant} over weighted queue share "
                f"({self.tenant_queue_depth(req.tenant)}/{cap})"
            )
        return None

    def tenant_stats(self) -> Dict[str, Dict[str, Any]]:
        """The per-tenant rollup the `/router` v5 feed carries: counters,
        live queue depth, weighted cap, and the class's own p99 TTFT (the
        burn-rate rules' per-class denominator)."""
        tenants = set(self.tenant_counts) | set(self._tenant_qdepth)
        out: Dict[str, Dict[str, Any]] = {}
        for t in sorted(tenants):
            counts = self._tenant_counts(t)
            hist = self._tenant_ttft.get(t)
            out[t] = {
                "submitted": counts["submitted"],
                "shed": counts["shed"],
                "completed": counts["completed"],
                "queue_depth": self.tenant_queue_depth(t),
                "weight": float(self.tenant_weights.get(t, 1.0)),
                "cap": self.tenant_cap(t),
                "ttft_p99_s": hist.percentile(0.99) if hist is not None else None,
            }
        return out

    # ------------------------------------------------------------- metrics
    def observe_ttft(self, seconds: float, tenant: Optional[str] = None) -> None:
        from .. import telemetry as _tel

        self._ttft.observe(seconds)
        _tel.observe("serve_ttft_seconds", seconds)
        if tenant is not None:
            self._tenant_observe_ttft(tenant, seconds)

    def observe_step_time(self, seconds: float) -> None:
        from .. import telemetry as _tel

        self._step_time.observe(seconds)
        _tel.observe("serve_decode_step_seconds", seconds)

    def observe_itl(self, seconds: float) -> None:
        from .. import telemetry as _tel

        self._itl.observe(seconds)
        _tel.observe("serve_itl_seconds", seconds)

    def ttft_p99(self) -> Optional[float]:
        return self._ttft.percentile(0.99)

    def seed_step_time(self, seconds: float) -> None:
        """Seed the decode-step estimator before any real sample exists
        (the loop passes the first PREFILL wall time — an overestimate of a
        decode step, so the cold retry hint errs conservative instead of
        telling shed clients to hammer a server that has never decoded).
        Ignored once set or once real samples landed."""
        if self._step_time_seed is None and self._step_time.count == 0:
            self._step_time_seed = max(float(seconds), 1e-4)

    def step_time_estimate(self) -> Optional[float]:
        """The scheduler's current best guess at the next decode step's wall
        time (seconds): observed p50, else the cold-start seed, else None.
        The serve loop records it as the per-step cost-audit prediction the
        measured wall time is joined against."""
        p50 = self._step_time.percentile(0.5)
        return p50 if p50 is not None else self._step_time_seed

    def retry_after_s(self) -> float:
        """Backpressure hint: how long until a shed client plausibly finds
        room — queue depth x observed decode-step p50.  Cold start (no
        decode step observed yet) falls back to the seeded estimate
        (seed_step_time: first prefill wall, or the loop's calibration-
        derived guess), then a 10ms floor so an unmeasured server still
        says *something* positive."""
        p50 = self._step_time.percentile(0.5)
        if p50 is None:
            p50 = self._step_time_seed or 0.01
        return max(0.01, (len(self.queue) + 1) * max(p50, 1e-4))

    def currently_shedding(self) -> Optional[str]:
        """The admission-control reason a new submission would be shed
        RIGHT NOW (bounded queue / p99-TTFT SLO breach), or None.  The
        ops endpoints publish it — ``accepting`` in the `/router` v2 feed
        and the ``Retry-After`` header — so a fleet router can spill load
        to a peer replica without paying a rejected round trip."""
        if len(self.queue) >= self.max_queue:
            return f"queue full ({len(self.queue)}/{self.max_queue})"
        if self.slo_ttft_s > 0:
            p99 = self.ttft_p99()
            if p99 is not None and p99 > self.slo_ttft_s:
                return f"p99 TTFT {p99:.3f}s over SLO {self.slo_ttft_s:g}s"
        return None

    # ----------------------------------------------------------- admission
    def submit(self, req: Request, step: int, raise_on_shed: bool = False) -> bool:
        """Enqueue a request at ``step``; returns False (and records the
        terminal ``shed`` outcome) when admission control rejects it."""
        from .. import telemetry as _tel

        if any(r.rid == req.rid for r, _, _ in self.queue) or any(
            f.req.rid == req.rid for f in self.active.values()
        ):
            raise ValueError(f"duplicate request id {req.rid} (still pending)")
        prior = self.outcomes.get(req.rid)
        if prior is not None:
            if prior.get("status") not in TERMINAL:
                raise ValueError(f"duplicate request id {req.rid} (replay pending)")
            # the retry_after_s contract: a shed/timed-out/preempted request
            # MAY come back with the same rid — the new attempt supersedes
            # the prior terminal outcome (ledger_check nets resubmissions)
            self.outcomes.pop(req.rid)
            self.counts["resubmitted"] += 1
            self._fold(17, req.rid, step)
        self.counts["submitted"] += 1
        tcounts = self._tenant_counts(req.tenant)
        tcounts["submitted"] += 1
        _tel.count(f"serve_tenant_{_safe(req.tenant)}_submitted_total")
        reqtrace.submit(req.rid, step, tag=req.tag)
        reason = self.currently_shedding()
        tenant_shed = False
        if reason is None:
            reason = self._tenant_shed_reason(req)
            tenant_shed = reason is not None
        total = len(req.prompt) + req.max_new_tokens
        if reason is None and total > self.cache.max_seq_len:
            reason = (
                f"request needs {total} tokens, "
                f"cache max_seq_len is {self.cache.max_seq_len}"
            )
        if reason is None and self.cache.pages_needed(total) > self.cache.num_pages - 1:
            # could NEVER be admitted even into an empty pool: shedding now
            # beats blocking the FIFO head forever
            reason = (
                f"request needs {self.cache.pages_needed(total)} pages, "
                f"pool holds {self.cache.num_pages - 1}"
            )
        if reason is not None:
            retry = self.retry_after_s()
            self.counts["shed"] += 1
            tcounts["shed"] += 1
            self.outcomes[req.rid] = {
                "status": "shed",
                "reason": reason,
                "retry_after_s": retry,
                "tokens": [],
                "tag": req.tag,
            }
            _tel.count("serve_requests_shed_total")
            _tel.count("resilience_shed_total")
            _tel.count(f"serve_tenant_{_safe(req.tenant)}_shed_total")
            _tel.record_event("serve_shed", rid=req.rid, reason=reason, retry_after_s=retry)
            reqtrace.terminal(req.rid, "shed", 0, reason=reason)
            self._fold(10, req.rid, step)
            if tenant_shed:
                # the weight-aware decision depends on the tenant-weights
                # config: fold it separately so a rank armed with a
                # different weight table desyncs BEFORE batches fork
                self._fold(20, req.rid, step)
            if raise_on_shed:
                raise ShedError(req.rid, reason, retry)
            return False
        self._fold(11, req.rid, step)
        self.queue.append((req, step, time.perf_counter()))
        self._tq(req.tenant, +1)
        _tel.set_gauge("serve_queue_depth", len(self.queue))
        return True

    def admit(self, step: int) -> List[_InFlight]:
        """Fill free slots from the queue head (FIFO — deterministic) at a
        step boundary; returns the newly admitted in-flight records (the
        loop prefills them).  A head request the cache cannot hold yet
        BLOCKS the queue (FIFO fairness: skipping it would starve long
        requests under a stream of short ones)."""
        from .. import telemetry as _tel

        admitted: List[_InFlight] = []
        while self.queue:
            req, submit_step, submit_wall = self.queue[0]
            matched = 0
            if self.prefix is not None:
                # the radix tree decides: matched pages map for free and
                # LRU-unreferenced cached leaves may be evicted to cover
                # the fresh remainder (prefix_cache.try_admit mutates
                # nothing but LRU clocks/evictions on failure)
                got = self.prefix.try_admit(req.prompt, req.max_new_tokens)
                if got is None:
                    break
                slot, matched = got
            else:
                if not self.cache.can_admit(len(req.prompt), req.max_new_tokens):
                    break
                slot = self.cache.alloc(len(req.prompt), req.max_new_tokens)
            self.queue.popleft()
            self._tq(req.tenant, -1)
            inf = _InFlight(req=req, slot=slot, submit_step=submit_step,
                            admit_step=step, submit_wall=submit_wall,
                            prefix_hit=matched)
            prev = self.outcomes.pop(req.rid, None)  # a replayed eviction
            if prev is not None and prev.get("status") not in ("evicted_replay",):
                raise RuntimeError(f"request {req.rid} readmitted after terminal {prev}")
            if prev is not None:
                inf.replays = int(prev.get("replays", 0)) + 1
            self.active[slot] = inf
            self.counts["admitted"] += 1
            admitted.append(inf)
            self._fold(12, req.rid, slot, step)
            if matched:
                # the hit is a scheduling decision: fold it so a rank
                # whose tree diverged desyncs BEFORE the batch decodes
                self._fold(19, req.rid, matched)
                _tel.count("serve_prefix_hits_total")
                _tel.count("serve_prefix_hit_tokens_total", matched)
            _tel.count("serve_requests_admitted_total")
        _tel.set_gauge("serve_queue_depth", len(self.queue))
        _tel.set_gauge("serve_inflight", len(self.active))
        return admitted

    # ------------------------------------------------------------ outcomes
    def _terminal(self, inf: _InFlight, status: str, **extra) -> None:
        self.outcomes[inf.req.rid] = {
            "status": status,
            "tokens": list(inf.tokens),
            "replays": inf.replays,
            "tag": inf.req.tag,  # the request's opaque token, echoed
            **extra,
        }

    def record_token(self, slot: int, token: int) -> None:
        self.active[slot].tokens.append(int(token))
        self.raw_tokens += 1

    def complete(self, slot: int) -> Dict[str, Any]:
        """EOS / token budget reached: the request is done."""
        from .. import telemetry as _tel

        inf = self.active.pop(slot)
        self.cache.free(slot)
        self.counts["completed"] += 1
        self._tenant_counts(inf.req.tenant)["completed"] += 1
        # goodput: only tokens that reached a COMPLETED terminal count
        self.goodput_tokens += len(inf.tokens)
        self._fold(13, inf.req.rid, slot, len(inf.tokens))
        self._terminal(inf, "completed")
        reqtrace.terminal(inf.req.rid, "completed", len(inf.tokens), slot=slot)
        _tel.count("serve_requests_completed_total")
        _tel.count(f"serve_tenant_{_safe(inf.req.tenant)}_completed_total")
        _tel.count("serve_goodput_tokens_total", len(inf.tokens))
        _tel.set_gauge("serve_inflight", len(self.active))
        return self.outcomes[inf.req.rid]

    def timeout(self, slot: int, reason: str = "deadline") -> Dict[str, Any]:
        """Deadline expired mid-flight: cancel, free the slot, record the
        EXPLICIT rejection (partial tokens kept for diagnosis)."""
        from .. import telemetry as _tel

        inf = self.active.pop(slot)
        self.cache.free(slot)
        self.counts["timed_out"] += 1
        self._fold(14, inf.req.rid, slot)
        self._terminal(inf, "timed_out", reason=reason)
        reqtrace.terminal(inf.req.rid, "timed_out", len(inf.tokens),
                          reason=reason, slot=slot)
        _tel.count("serve_requests_timed_out_total")
        _tel.record_event("serve_timeout", rid=inf.req.rid, slot=slot, reason=reason)
        _tel.set_gauge("serve_inflight", len(self.active))
        return self.outcomes[inf.req.rid]

    def timeout_queued(self, step: int) -> List[int]:
        """Expire queued (never admitted) requests whose step deadline
        passed while they waited."""
        from .. import telemetry as _tel

        expired: List[int] = []
        keep: Deque[Tuple[Request, int, float]] = deque()
        for req, submit_step, submit_wall in self.queue:
            d = req.deadline_steps
            if d is not None and step - submit_step > d:
                self.counts["timed_out"] += 1
                self._fold(18, req.rid, step)
                self.outcomes[req.rid] = {
                    "status": "timed_out",
                    "tokens": [],
                    "replays": self._queued_replays(req.rid),
                    "reason": "queued past deadline",
                    "tag": req.tag,
                }
                reqtrace.terminal(req.rid, "timed_out", 0,
                                  reason="queued past deadline")
                _tel.count("serve_requests_timed_out_total")
                _tel.record_event("serve_timeout", rid=req.rid,
                                  reason="queued past deadline")
                expired.append(req.rid)
                self._tq(req.tenant, -1)
            else:
                keep.append((req, submit_step, submit_wall))
        self.queue = keep
        if expired:
            _tel.set_gauge("serve_queue_depth", len(self.queue))
        return expired

    def _queued_replays(self, rid: int) -> int:
        """How many times a still-QUEUED rid has already been evicted and
        requeued — its ``evicted_replay`` transient marker records the
        pre-eviction count (the ledger and the span chain's evict-span
        count must agree even when the replay never gets readmitted)."""
        prev = self.outcomes.get(rid)
        if prev is not None and prev.get("status") == "evicted_replay":
            return int(prev.get("replays", 0)) + 1
        return 0

    def requeue_newest(self, reason: str = "oom") -> Optional[int]:
        """Evict the NEWEST admitted request and replay it from the queue
        head — the mid-batch OOM protocol: the batch survives, the victim
        re-prefills later and (decode being deterministic) regenerates the
        same tokens.  Returns the victim rid, or None with nothing
        in-flight."""
        from .. import telemetry as _tel

        if not self.active:
            return None
        slot = max(self.active, key=lambda s: (self.active[s].admit_step, s))
        inf = self.active.pop(slot)
        self.cache.free(slot)
        self.counts["evicted"] += 1
        self.counts["requeued"] += 1
        self._fold(15, inf.req.rid, slot)
        # transient marker (NOT terminal): admit() consumes it to count
        # replays; generation restarts from the prompt
        self.outcomes[inf.req.rid] = {
            "status": "evicted_replay",
            "tokens": [],
            "replays": inf.replays,
            "reason": reason,
        }
        # the ORIGINAL submit stamps ride along: the replayed request's
        # TTFT honestly includes everything since the client submitted
        self.queue.appendleft((inf.req, inf.submit_step, inf.submit_wall))
        self._tq(inf.req.tenant, +1)
        # the fork marker: this rid's chain re-runs queue-wait -> prefill
        reqtrace.evict(inf.req.rid, slot, reason, replays=inf.replays + 1)
        _tel.count("serve_requests_evicted_total")
        _tel.record_event("serve_evict", rid=inf.req.rid, slot=slot, reason=reason)
        _tel.set_gauge("serve_inflight", len(self.active))
        return inf.req.rid

    def reject_queued(self, reason: str = "preempted") -> List[int]:
        """Drain protocol: every still-queued request is explicitly
        rejected as re-queueable (the client may resubmit verbatim after
        the restart) — never silently dropped."""
        from .. import telemetry as _tel

        rejected = []
        while self.queue:
            req, _, _ = self.queue.popleft()
            self._tq(req.tenant, -1)
            self._fold(16, req.rid)
            self.outcomes[req.rid] = {
                "status": "preempted_requeue",
                "tokens": [],
                "replays": self._queued_replays(req.rid),
                "reason": reason,
                "retry_after_s": self.retry_after_s(),
                "tag": req.tag,
            }
            reqtrace.terminal(req.rid, "preempted_requeue", 0, reason=reason)
            self.counts["shed"] += 1
            _tel.count("serve_requests_shed_total")
            _tel.count("resilience_shed_total")
            rejected.append(req.rid)
        _tel.set_gauge("serve_queue_depth", 0)
        return rejected

    # ------------------------------------------------------------ expiry
    def wall_expired_slots(self, now_s: float, wall_deadline_s: float) -> List[int]:
        """Slots whose request has been in flight longer than the wall
        budget — computed but NOT applied, so the serve loop can OR-agree
        the (rank-local, clock-dependent) verdict across ranks before any
        rank acts on it."""
        if not wall_deadline_s:
            return []
        return [
            slot for slot in sorted(self.active)
            if (now_s - self.active[slot].admit_wall) > wall_deadline_s
        ]

    @staticmethod
    def _step_over(inf: _InFlight, step: int) -> bool:
        d = inf.req.deadline_steps
        return d is not None and step - inf.submit_step > d

    def step_deadline_due(self, step: int) -> bool:
        """Whether :meth:`expire_active` would cancel an in-flight request at
        ``step`` for its step deadline (computed, not applied: the serve loop
        reads the decode step in flight before such a boundary)."""
        return any(self._step_over(inf, step) for inf in self.active.values())

    def expire_active(self, step: int, force_slots: Sequence[int] = (),
                      wall_slots: Sequence[int] = ()) -> List[int]:
        """Timeout cancellation at a step boundary: step-deadline expiry,
        ``wall_slots`` (agreed wall-budget expiries from
        :meth:`wall_expired_slots`) and ``force_slots`` (the faultsim
        ``request_timeout`` kind).  Returns the cancelled rids."""
        out: List[int] = []
        for slot in sorted(self.active):
            inf = self.active[slot]
            forced = slot in force_slots
            step_over = self._step_over(inf, step)
            if forced or step_over or slot in wall_slots:
                reason = "injected request_timeout" if forced else (
                    "step deadline" if step_over else "wall deadline"
                )
                self.timeout(slot, reason=reason)
                out.append(inf.req.rid)
        return out

    # ----------------------------------------------------------- agreement
    def fingerprint(self) -> Tuple[int, ...]:
        """Deterministic digest of the full scheduling-decision history
        (every submit/shed/admit/complete/timeout/evict folds into a
        running crc as it happens — O(1) at exchange time) + the cache's
        allocation digest: the serve loop exchanges it so slot-assignment
        divergence raises as a DesyncError BEFORE a divergent batch
        decodes."""
        return (self._digest, len(self.queue), len(self.active)) + self.cache.fingerprint()

    def all_terminal(self) -> bool:
        return not self.queue and not self.active

    def ledger_check(self) -> None:
        """Assert total accounting: every accepted submission ended exactly
        one way (a resubmission supersedes its prior terminal outcome, so
        distinct outcomes == submissions minus resubmissions)."""
        terminal = [r for r in self.outcomes.values() if r.get("status") in TERMINAL]
        if self.queue or self.active:
            raise AssertionError("ledger_check before drain")
        expected = self.counts["submitted"] - self.counts["resubmitted"]
        if len(terminal) != expected:
            raise AssertionError(
                f"{self.counts['submitted']} submitted "
                f"({self.counts['resubmitted']} resubmissions) but "
                f"{len(terminal)} terminal outcomes"
            )
