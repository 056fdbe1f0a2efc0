"""Serve-replica observability state — goodput accounting + the ops
endpoint providers.

One ``ServeObservability`` per ``run_serve_resilient`` call.  It owns the
derived numbers the scheduler's raw ledger cannot answer alone:

  * **goodput vs raw throughput** — ``serve_goodput_tokens_per_s`` counts
    only tokens of COMPLETED requests (scheduler.goodput_tokens);
    ``serve_throughput_tokens_per_s`` counts every sampled token.  The gap
    IS the work wasted on evicted/timed-out/drained requests.
  * **the `/healthz` and `/router` payloads** — the callables
    ``telemetry.ops_server.maybe_start`` binds to the endpoints.  The
    `/router` schema is FROZEN at ``ROUTER_SCHEMA_VERSION`` (docs/
    serving.md): the future multi-replica dispatcher polls it, so fields
    are only ever added, never renamed or removed.

Everything here is host-side floats; telemetry gauges are published only
while the registry gate is up (``_tel.set_gauge`` no-ops when dormant),
and the providers work with telemetry fully dormant — a liveness probe
must not require a metrics pipeline.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional

__all__ = [
    "ServeObservability",
    "FleetObservability",
    "ROUTER_SCHEMA_VERSION",
    "ROUTER_FIELDS",
    "ROUTER_FIELDS_V1",
    "ROUTER_FIELDS_V2",
    "ROUTER_FIELDS_V3",
    "ROUTER_FIELDS_V4",
    "FLEET_SCHEMA_VERSION",
    "FLEET_FIELDS",
    "FLEET_FIELDS_V2",
    "FLEET_FIELDS_V3",
    "FLEET_FIELDS_V4",
    "FLEET_REPLICA_FIELDS",
    "FLEET_REPLICA_FIELDS_V1",
    "FLEET_REPLICA_FIELDS_V2",
]

ROUTER_SCHEMA_VERSION = 5
# the frozen /router v1 field set: the freeze contract says fields are
# only ever ADDED — v1 must remain a strict subset of every later version
# (tests assert it), so a router written against v1 keeps working
ROUTER_FIELDS_V1 = frozenset(
    (
        "schema_version",
        "rank",
        "draining",
        "queue_depth",
        "inflight",
        "slots",
        "free_slots",
        "pages",
        "free_pages",
        "ttft_s",
        "itl_s",
        "shed_rate",
        "retry_after_s",
        "goodput_tokens_per_s",
        "throughput_tokens_per_s",
        "mfu",
        "decode_steps",
        "serve_step",
        "uptime_s",
    )
)
# schema v2 (additive only, per the freeze contract): `replica_id` (the
# fleet router's stable dispatch/affinity identity) and `accepting`
# (False while draining or actively shedding — the pre-dispatch
# exclusion signal).  docs/serving.md documents the v1 -> v2 delta.
ROUTER_FIELDS_V2 = ROUTER_FIELDS_V1 | frozenset(("replica_id", "accepting"))
# schema v3 (ISSUE 15, additive again): `prefix_hit_rate` (fraction of
# admitted prompt tokens served from radix-tree cached pages; null while
# the prefix cache is off or cold) and `spec_accept_rate` (fraction of
# drafted tokens the target accepted; null while speculation is off or
# before the first verify step) — the cache-warmth signals a fleet
# router can use to prefer replicas whose session affinity has already
# earned the prefix pages.  docs/serving.md documents the v2 -> v3 delta.
ROUTER_FIELDS_V3 = ROUTER_FIELDS_V2 | frozenset(("prefix_hit_rate", "spec_accept_rate"))
# schema v4 (additive again): `alerts` — the replica's alert-engine
# digest ({"active", "firing", "pending"}; firing/pending are sorted rule
# names).  A fleet router can treat a replica with critical rules firing
# as degraded BEFORE its breaker trips, and the digest rides the feed the
# router already polls — no second probe.  The full lifecycle snapshot
# (frozen schema v1) lives on `/alerts`; this is the inline summary.
# docs/serving.md documents the v3 -> v4 delta.
ROUTER_FIELDS_V4 = ROUTER_FIELDS_V3 | frozenset(("alerts",))
# schema v5 (additive again): `tenants` — per-tenant SLO-class stats
# (submitted/shed/completed/queue_depth/weight/cap/ttft_p99_s per tenant;
# {} until a non-default tenant submits) — and `rollout` — the replica's
# live weight-rollout state (null outside a rollout; during one, the
# {"state", "checkpoint", "detail"} dict the loop's reload machine
# maintains: draining -> baseline -> swapping -> canary ->
# committed | rolled_back).  The fleet rollout controller polls this
# instead of guessing from /healthz.  docs/serving.md has the delta.
ROUTER_FIELDS = ROUTER_FIELDS_V4 | frozenset(("tenants", "rollout"))

# the router-side `/fleet` rollup schema, frozen under the same contract
# as ROUTER_FIELDS (fields only ever added, asserted at the source and by
# tests): the live view an operator — or ROADMAP item 2's auto-plan
# search — reads to decide a replica is degrading before its breaker
# trips.  docs/serving.md documents every field.
FLEET_SCHEMA_VERSION = 5
FLEET_FIELDS_V2 = frozenset(
    (
        "schema_version",
        "healthy_replicas",
        "pending_requests",
        "counts",
        "replicas",
        "breaker_transitions",
        "goodput_tokens_per_s",
        "throughput_tokens_per_s",
        "mfu",
        "ttft_p99_s",
        "shed_rate",
        "slo_ttft_s",
        "slo_burn_rate",
        "uptime_s",
    )
)
# fleet schema v3 (additive): `alerts` — the ROUTER process's own
# alert-engine digest (fleet-scope rules: fleet-shed-rate,
# fleet-no-healthy-replicas, fleet-ttft-slo-burn), same
# {"active", "firing", "pending"} shape as /router v4.
FLEET_FIELDS_V3 = FLEET_FIELDS_V2 | frozenset(("alerts",))
# fleet schema v4 (additive): `queue_depth` — router-pending plus the sum
# of replica queue depths, the autoscaler's load-trend input published as
# the `fleet_timeline_queue_depth` gauge — `tenants` — the per-tenant
# stats summed across replica feeds — and `autoscale` — the attached
# Autoscaler's state snapshot (null until serve.autoscale attaches one).
FLEET_FIELDS_V4 = FLEET_FIELDS_V3 | frozenset(("queue_depth", "tenants", "autoscale"))
# fleet schema v5 (additive): `ha` — the router's high-availability block
# (null while journaling is off; else {"role", "epoch", "journal",
# "lease", "recovery"} — the fenced leader epoch, journal append/segment
# stats, and, after a crash recovery or standby takeover, the recovery
# audit: pending rids reconstructed, outcomes harvested from the
# replicas' /outcomes linger, rids re-driven from the prompt).
FLEET_FIELDS = FLEET_FIELDS_V4 | frozenset(("ha",))
# per-replica row of the `/fleet` feed (frozen with the outer schema)
FLEET_REPLICA_FIELDS_V1 = frozenset(
    (
        "breaker",
        "accepting",
        "queue_depth",
        "inflight",
        "shed_rate",
        "goodput_tokens_per_s",
        "throughput_tokens_per_s",
        "mfu",
        "serve_step",
        "dispatches",
        "opens",
        "reopens",
        "closes",
    )
)
# fleet schema v2 (additive, rides the /router v3 fields straight
# through): the per-replica cache-warmth columns of the aggregate view
FLEET_REPLICA_FIELDS_V2 = FLEET_REPLICA_FIELDS_V1 | frozenset(
    ("prefix_hit_rate", "spec_accept_rate")
)
# per-replica v3 (rides /router v5 through): the replica's live rollout
# state, so one /fleet poll shows which stage every replica is in
FLEET_REPLICA_FIELDS = FLEET_REPLICA_FIELDS_V2 | frozenset(("rollout",))


def _alerts_digest() -> Dict:
    """The inline alert summary every feed carries (schema'd by the
    endpoint that embeds it: /router v4, /fleet v3, /healthz).  Import is
    local so the providers keep working with telemetry fully dormant."""
    from ..telemetry import alerts as _alerts

    return _alerts.digest()


def _pcts(hist) -> Dict[str, Optional[float]]:
    return {
        "p50": hist.percentile(0.5),
        "p95": hist.percentile(0.95),
        "p99": hist.percentile(0.99),
    }


class ServeObservability:
    """Derived-rate bookkeeping + endpoint providers for one serve loop."""

    def __init__(self, scheduler, engine=None, watchdog=None, rank: int = 0,
                 replica_id: Optional[str] = None, speculative=None):
        from ..analysis import envreg

        self.scheduler = scheduler
        self.engine = engine
        self.watchdog = watchdog
        self.speculative = speculative  # the /router v3 spec_accept_rate source
        self.rank = int(rank)
        # stable fleet identity (schema v2): explicit arg, else the env
        # knob (one replica process = one id), else the rank
        self.replica_id = (
            replica_id
            or envreg.get_str("VESCALE_SERVE_REPLICA_ID")
            or f"rank{self.rank}"
        )
        self.draining = False  # the loop flips it; /healthz reports it
        # the loop's reload machine owns this: None outside a rollout,
        # else {"state", "checkpoint", "detail"} (/router v5 passes it
        # through; the fleet rollout controller polls it)
        self.rollout: Optional[Dict] = None
        self.serve_step = 0
        self.decode_steps = 0
        self._start = time.perf_counter()
        self._last_decode: Optional[float] = None

    def calibrated_step_estimate(self) -> Optional[float]:
        """Decode-step seconds estimated from the calibration table — the
        scheduler's cold-start ``retry_after_s`` seed when a table is armed
        (before even the first prefill has run).  Prefers MEASURED
        ``serve_decode`` buckets (harvested from a prior run's tagged decode
        spans by the cost auditor — audited, not modeled); None without one."""
        from ..telemetry.calibrate import active_table

        t = active_table()
        if t is None:
            return None
        us = t.op_estimate_us("serve_decode")
        return None if us is None else float(us) / 1e6

    def on_decode_step(self, step: int, dt_s: float, active: int) -> None:
        """Per decode step: advance the rate clocks and publish the
        goodput/throughput/MFU gauges (no-ops while telemetry is dormant)."""
        from .. import telemetry as _tel

        self.decode_steps += 1
        self.serve_step = int(step)
        self._last_decode = time.perf_counter()
        sched = self.scheduler
        up = max(1e-9, self._last_decode - self._start)
        goodput = sched.goodput_tokens / up
        raw = sched.raw_tokens / up
        if _tel.is_active():
            _tel.set_gauge("serve_goodput_tokens_per_s", goodput)
            _tel.set_gauge("serve_throughput_tokens_per_s", raw)
            # the serve rule pack's inputs (telemetry/alerts.py): shed
            # fraction, goodput as a fraction of raw throughput (1.0 when
            # nothing is wasted; collapses toward 0 under eviction churn),
            # and page-pool headroom for the drain-trend rule
            _tel.set_gauge(
                "serve_shed_rate",
                sched.counts["shed"] / max(1, sched.counts["submitted"]),
            )
            _tel.set_gauge("serve_goodput_fraction", goodput / raw if raw > 0 else 1.0)
            _tel.set_gauge("serve_free_pages", sched.cache.free_page_count())

    # --------------------------------------------------------- providers
    def health(self) -> Dict:
        """`/healthz`: is this replica alive and making progress — the
        watchdog's view (last-beat age), the decode loop's (last-step age),
        and the capacity headroom a probe alerts on."""
        sched = self.scheduler
        cache = sched.cache
        now = time.perf_counter()
        wd = self.watchdog
        shedding = sched.currently_shedding()
        return {
            "ok": not self.draining,
            "draining": self.draining,
            "replica_id": self.replica_id,
            # admission-control state + the same hint a shed client gets:
            # the ops server turns these into a Retry-After header
            "shedding": shedding,
            "retry_after_s": sched.retry_after_s(),
            "serve_step": self.serve_step,
            "decode_steps": self.decode_steps,
            "queue_depth": len(sched.queue),
            "inflight": len(sched.active),
            "free_slots": cache.free_slot_count(),
            "free_pages": cache.free_page_count(),
            "watchdog_last_beat_age_s": (
                round(wd.stalled_s, 6) if wd is not None else None
            ),
            "last_decode_step_age_s": (
                round(now - self._last_decode, 6)
                if self._last_decode is not None
                else None
            ),
            "uptime_s": round(now - self._start, 6),
            # this replica's wall clock at reply-build time: the fleet
            # clock-sync rounds (fleettrace.estimate_fleet_clock_offsets)
            # sample it NTP-style against the poller's own clock
            "wall_time_us": int(time.time() * 1e6),
            # /healthz is NOT frozen, so the alert digest rides it too —
            # a probe that only hits /healthz still sees firing rules
            "alerts": _alerts_digest(),
        }

    def router(self) -> Dict:
        """`/router`: the dispatch feed a multi-replica router polls —
        FROZEN schema, v4 (ROUTER_FIELDS; docs/serving.md has the
        v1 -> v2 -> v3 -> v4 deltas — fields are only ever added)."""
        sched = self.scheduler
        cache = sched.cache
        up = max(1e-9, time.perf_counter() - self._start)
        submitted = max(1, sched.counts["submitted"])
        prefix = getattr(sched, "prefix", None)
        spec = self.speculative
        ro = self.rollout
        rollout_busy = ro is not None and ro.get("state") in (
            "draining", "baseline", "swapping", "canary"
        )
        out = {
            "schema_version": ROUTER_SCHEMA_VERSION,
            "rank": self.rank,
            "replica_id": self.replica_id,
            "draining": self.draining,
            # the pre-dispatch exclusion signal: False while draining,
            # while admission control would shed a submission right now,
            # OR while the reload machine holds admission for a rollout
            "accepting": (
                not self.draining
                and not rollout_busy
                and sched.currently_shedding() is None
            ),
            "queue_depth": len(sched.queue),
            "inflight": len(sched.active),
            "slots": cache.num_slots,
            "free_slots": cache.free_slot_count(),
            "pages": cache.num_pages - 1,  # page 0 is the reserved null page
            "free_pages": cache.free_page_count(),
            "ttft_s": _pcts(sched._ttft),
            "itl_s": _pcts(sched._itl),
            "shed_rate": sched.counts["shed"] / submitted,
            "retry_after_s": sched.retry_after_s(),
            "goodput_tokens_per_s": sched.goodput_tokens / up,
            "throughput_tokens_per_s": sched.raw_tokens / up,
            "mfu": None,  # frozen v1 field; nothing publishes a utilisation from the host's step wall any more
            "decode_steps": self.decode_steps,
            "serve_step": self.serve_step,
            "uptime_s": round(up, 6),
            # v3: cache warmth — null (never 0.0) while the multiplier is
            # off or has no samples, so a router can tell "cold" from
            # "disabled" without a second probe
            "prefix_hit_rate": prefix.stats.hit_rate() if prefix is not None else None,
            "spec_accept_rate": spec.accept_rate() if spec is not None else None,
            # v4: the alert-engine digest ({"active": false, ...} while
            # dormant) — degradation signal ahead of the breaker
            "alerts": _alerts_digest(),
            # v5: per-tenant SLO-class stats + live rollout state
            "tenants": sched.tenant_stats(),
            "rollout": self.rollout,
        }
        assert set(out) == ROUTER_FIELDS  # the freeze, enforced at source
        return out


class FleetObservability:
    """Fleet-scope health rollups over a :class:`~.router.FleetRouter`'s
    cached replica feeds, breaker states and ledger — the router-side
    twin of :class:`ServeObservability`.

    Owns the numbers no single replica can answer: aggregate goodput and
    throughput (sums over feeds), fleet MFU (throughput-weighted mean),
    the fleet p99 TTFT (worst replica — the tail a client actually
    sees), per-replica shed rates, the breaker state-transition history,
    and the p99-TTFT **SLO burn rate** (fleet p99 / SLO budget: > 1
    means the fleet is currently burning error budget; sustained > 1 is
    the page).  Served three ways: the ``/fleet`` ops endpoint (frozen
    schema ``FLEET_FIELDS``), the ``fleet_timeline_*`` registry gauges
    (the ``fleet-timeline:`` dashboard block), and the router process's
    own ``/metrics``.  Everything works with telemetry dormant — gauges
    are simply skipped (the ServeObservability contract)."""

    def __init__(self, router, slo_ttft_s: Optional[float] = None):
        from ..analysis import envreg

        self.router = router
        if slo_ttft_s is None:
            slo_ttft_s = envreg.get_float("VESCALE_SERVE_SLO_TTFT_S") or 0.0
        self.slo_ttft_s = float(slo_ttft_s)
        # serve.autoscale.Autoscaler attaches its state callable here so
        # /fleet v4 carries the control loop's view (null until attached)
        self.autoscale_provider = None
        # FleetRouter wires its _ha_state here when a journal/lease is
        # attached so /fleet v5 carries leadership + journal health
        self.ha_provider = None
        self._start = time.perf_counter()

    # ------------------------------------------------------------ rollups
    def _rollup(self) -> Dict:
        feeds = {
            h.id: h.feed for h in self.router.replicas.values() if h.feed is not None
        }
        goodput = sum(float(f.get("goodput_tokens_per_s") or 0.0) for f in feeds.values())
        raw = sum(float(f.get("throughput_tokens_per_s") or 0.0) for f in feeds.values())
        # fleet MFU: throughput-weighted mean over replicas reporting one
        # (equal weights when nothing has throughput yet)
        num = den = 0.0
        for f in feeds.values():
            mfu = f.get("mfu")
            if mfu is None:
                continue
            w = float(f.get("throughput_tokens_per_s") or 0.0) or 1.0
            num += float(mfu) * w
            den += w
        fleet_mfu = (num / den) if den else None
        p99s = [
            (f.get("ttft_s") or {}).get("p99")
            for f in feeds.values()
            if isinstance(f.get("ttft_s"), dict)
        ]
        p99s = [p for p in p99s if p is not None]
        ttft_p99 = max(p99s) if p99s else None
        burn = (
            ttft_p99 / self.slo_ttft_s
            if (self.slo_ttft_s > 0 and ttft_p99 is not None)
            else None
        )
        counts = self.router.ledger.counts
        shed_rate = counts["shed"] / max(1, counts["submitted"])
        # the autoscaler's load-trend input: work waiting ANYWHERE in the
        # fleet — router-pending plus every replica's local queue
        queue_depth = self.router.ledger.pending_count() + sum(
            int(f.get("queue_depth") or 0) for f in feeds.values()
        )
        # per-tenant stats summed across feeds (absent pre-v5 feeds -> {})
        tenants: Dict[str, Dict] = {}
        for f in feeds.values():
            for t, row in (f.get("tenants") or {}).items():
                agg = tenants.setdefault(
                    t, {"submitted": 0, "shed": 0, "completed": 0, "queue_depth": 0}
                )
                for k in agg:
                    agg[k] += int(row.get(k) or 0)
        return {
            "feeds": feeds,
            "goodput": goodput,
            "raw": raw,
            "mfu": fleet_mfu,
            "ttft_p99": ttft_p99,
            "burn": burn,
            "shed_rate": shed_rate,
            "queue_depth": queue_depth,
            "tenants": tenants,
        }

    def fleet(self) -> Dict:
        """`/fleet`: the aggregated fleet feed — FROZEN schema
        (``FLEET_FIELDS`` outer, ``FLEET_REPLICA_FIELDS`` per replica;
        fields only ever added, the ROUTER_FIELDS contract)."""
        r = self._rollup()
        replicas = {}
        for h in self.router.replicas.values():
            f = h.feed or {}
            row = {
                "breaker": h.breaker.state,
                "accepting": bool(f.get("accepting", not f.get("draining", False)))
                if f
                else False,
                "queue_depth": f.get("queue_depth"),
                "inflight": f.get("inflight"),
                "shed_rate": f.get("shed_rate"),
                "goodput_tokens_per_s": f.get("goodput_tokens_per_s"),
                "throughput_tokens_per_s": f.get("throughput_tokens_per_s"),
                "mfu": f.get("mfu"),
                "serve_step": f.get("serve_step"),
                "dispatches": h.dispatches,
                "opens": h.breaker.opens,
                "reopens": h.breaker.reopens,
                "closes": h.breaker.closes,
                # v2: the /router v3 cache-warmth columns, passed through
                # (absent from an old replica's v2 feed -> null)
                "prefix_hit_rate": f.get("prefix_hit_rate"),
                "spec_accept_rate": f.get("spec_accept_rate"),
                # v3: the replica's live rollout stage (/router v5)
                "rollout": f.get("rollout"),
            }
            assert set(row) == FLEET_REPLICA_FIELDS  # frozen at source
            replicas[h.id] = row
        out = {
            "schema_version": FLEET_SCHEMA_VERSION,
            "healthy_replicas": sum(
                1 for h in self.router.replicas.values() if h.breaker.dispatchable
            ),
            "pending_requests": self.router.ledger.pending_count(),
            "counts": dict(self.router.ledger.counts),
            "replicas": replicas,
            "breaker_transitions": list(self.router.breaker_transitions)[-64:],
            "goodput_tokens_per_s": r["goodput"],
            "throughput_tokens_per_s": r["raw"],
            "mfu": r["mfu"],
            "ttft_p99_s": r["ttft_p99"],
            "shed_rate": r["shed_rate"],
            "slo_ttft_s": self.slo_ttft_s,
            "slo_burn_rate": r["burn"],
            "uptime_s": round(time.perf_counter() - self._start, 6),
            # v3: the router process's own alert digest (fleet-scope rules)
            "alerts": _alerts_digest(),
            # v4: aggregate load, per-tenant rollup, autoscaler state
            "queue_depth": r["queue_depth"],
            "tenants": r["tenants"],
            "autoscale": (
                self.autoscale_provider() if self.autoscale_provider else None
            ),
            # v5: the router HA block (null while journaling is off)
            "ha": self.ha_provider() if self.ha_provider else None,
        }
        assert set(out) == FLEET_FIELDS  # the freeze, enforced at source
        return out

    def health(self) -> Dict:
        """Router-process `/healthz`: liveness + the wall clock the fleet
        clock sync samples (not frozen — the /fleet feed is the API)."""
        return {
            "ok": True,
            "role": "router",
            "replicas": len(self.router.replicas),
            "healthy_replicas": sum(
                1 for h in self.router.replicas.values() if h.breaker.dispatchable
            ),
            "pending_requests": self.router.ledger.pending_count(),
            "uptime_s": round(time.perf_counter() - self._start, 6),
            "wall_time_us": int(time.time() * 1e6),
            "alerts": _alerts_digest(),
        }

    def publish(self) -> None:
        """Push the rollups into the gated registry as ``fleet_timeline_*``
        gauges — the ``fleet-timeline:`` dashboard block.  No-op while
        telemetry is dormant."""
        from .. import telemetry as _tel

        if not _tel.is_active():
            return
        r = self._rollup()
        # the fleet rule pack's no-healthy-replicas input
        _tel.set_gauge(
            "fleet_timeline_healthy_replicas",
            sum(1 for h in self.router.replicas.values() if h.breaker.dispatchable),
        )
        _tel.set_gauge("fleet_timeline_goodput_tokens_per_s", r["goodput"])
        _tel.set_gauge("fleet_timeline_throughput_tokens_per_s", r["raw"])
        if r["mfu"] is not None:
            _tel.set_gauge("fleet_timeline_mfu", r["mfu"])
        if r["ttft_p99"] is not None:
            _tel.set_gauge("fleet_timeline_ttft_p99_s", r["ttft_p99"])
        if r["burn"] is not None:
            _tel.set_gauge("fleet_timeline_slo_burn_rate", r["burn"])
        _tel.set_gauge("fleet_timeline_shed_rate", r["shed_rate"])
        # the autoscaler's two control inputs, published every poll so
        # the time-series store can trend them: total queued work and the
        # dispatchable replica count it scales against
        _tel.set_gauge("fleet_timeline_queue_depth", r["queue_depth"])
        _tel.set_gauge(
            "fleet_timeline_replica_count", len(self.router.replicas)
        )
        for rid, f in r["feeds"].items():
            if f.get("shed_rate") is not None:
                _tel.set_gauge(f"fleet_timeline_shed_rate_{rid}", f["shed_rate"])
