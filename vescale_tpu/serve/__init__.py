"""vescale_tpu.serve — continuous-batching inference inside the fault envelope.

ROADMAP item 1: the one-substrate thesis (PAPER.md) applied to serving.
The KV cache is a DArray with ordinary placements (kv_cache.py), the
scheduler admits into static decode slots at step boundaries with bounded
admission + load shedding (scheduler.py), prefill/decode are compiled
steps over the training param tree reusing the flash-attention path and
the pipe stage split (engine.py), and ``run_serve_resilient`` (loop.py)
wraps it all in the SAME watchdog/faultsim/preemption/control-plane
envelope ``run_resilient`` gives training.

Checkpoint handoff: :func:`load_params` restores a TRAINING checkpoint's
params (and nothing else — optimizer chunks are never read) onto the
serving mesh through the elastic preflight, so a 2-rank training run
serves on 1 rank (or any other shape) with bit-identical logits.
"""

from __future__ import annotations

from typing import Any, Dict

from . import autoscale, fleet, fleettrace, journal, obs, prefix_cache, reqtrace, router, speculative
from .autoscale import Autoscaler, RolloutController
from .engine import DecodeFeed, DecodeStep, PrefillStep, ServeEngine
from .fleet import FleetSupervisor, ReplicaSpec, RequestInbox, serve_replica
from .journal import FencedEpochError, FleetJournal, LeaderLease
from .fleettrace import (
    FleetClockSync,
    assemble_fleet_timeline,
    estimate_fleet_clock_offsets,
    superseded_rids,
    verify_fleet_journeys,
)
from .hybrid_engine import HybridServeEngine
from .kv_cache import KVCacheConfig, KVCacheOutOfPages, PagedKVCache, SlotStateUnsupported
from .loop import ControlChannel, ServeResult, run_serve_resilient
from .obs import FleetObservability, ServeObservability
from .prefix_cache import PrefixCache
from .speculative import SpeculativeDecoder, load_drafter_params, slice_drafter_params
from .router import (
    CircuitBreaker,
    ConsistentHashRing,
    FleetLedger,
    FleetRouter,
    HttpReplicaClient,
    StandbyRouter,
)
from .scheduler import ContinuousBatchingScheduler, Request, ShedError

__all__ = [
    "KVCacheConfig",
    "KVCacheOutOfPages",
    "PagedKVCache",
    "ContinuousBatchingScheduler",
    "Request",
    "ShedError",
    "ServeEngine",
    "DecodeFeed",
    "DecodeStep",
    "PrefillStep",
    "HybridServeEngine",
    "SlotStateUnsupported",
    "ServeResult",
    "ServeObservability",
    "FleetObservability",
    "FleetClockSync",
    "assemble_fleet_timeline",
    "estimate_fleet_clock_offsets",
    "superseded_rids",
    "verify_fleet_journeys",
    "run_serve_resilient",
    "load_params",
    "PrefixCache",
    "SpeculativeDecoder",
    "load_drafter_params",
    "slice_drafter_params",
    "CircuitBreaker",
    "ConsistentHashRing",
    "FleetLedger",
    "FleetRouter",
    "HttpReplicaClient",
    "StandbyRouter",
    "FleetJournal",
    "LeaderLease",
    "FencedEpochError",
    "journal",
    "RequestInbox",
    "ReplicaSpec",
    "FleetSupervisor",
    "serve_replica",
    "Autoscaler",
    "RolloutController",
    "ControlChannel",
    "autoscale",
    "obs",
    "prefix_cache",
    "reqtrace",
    "router",
    "fleet",
    "fleettrace",
    "speculative",
]


def load_params(path: str, template: Any) -> Dict[str, Any]:
    """Restore ONLY the params tree of a training checkpoint into the
    serving layout described by ``template`` (DArray / sharded jax.Array /
    np leaves — shardings are the contract, as in ``checkpoint.load``).

    The params-only template is the whole trick: ``checkpoint.load`` reads
    exactly the chunks the template names, so the optimizer state —
    typically 2x the params in bytes — never touches the wire, and the
    elastic preflight (VSC130) reshards a differently-shaped writer mesh
    transparently.  ``checkpoint.LAST_LOAD_STATS['elastic']`` says whether
    the restore crossed worlds."""
    from .. import checkpoint as ckpt

    return ckpt.load(path, {"model": template})["model"]
