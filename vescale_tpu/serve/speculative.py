"""Speculative decoding — draft-then-verify with a reduced-depth drafter.

Leviathan et al. (arXiv:2211.17192): a cheap DRAFTER proposes ``k`` tokens
autoregressively, the target model scores all of them in ONE batched
multi-token paged-attention step (``ServeEngine.decode_multi`` — width
``k + 1`` is a compile-time constant, no retrace), and greedy acceptance
keeps every draft token that equals the target's own argmax.  Under
greedy acceptance the emitted stream is BITWISE the stream plain decode
would have produced — the drafter only decides how many target-forward
launches it takes to produce it — so the repo's standing contracts
(golden replay, cross-rank digest agreement, the PR-10 fault battery)
hold with speculation on.

The drafter here is the SAME checkpoint restored at reduced depth: the
first ``drafter_layers`` decoder blocks plus the shared embedding / final
norm / head, loaded params-only through the elastic preflight
(:func:`load_drafter_params` names exactly those chunks, so the deeper
layers and the optimizer state never touch the wire).  A truncated model
is a weak LM, but acceptance makes its quality a THROUGHPUT knob, never a
correctness one.

Cache discipline: the drafter owns a private :class:`PagedKVCache` with
the same slot/page geometry (fewer layers) and mirrors the target cache's
slot lifecycle — the loop calls :meth:`on_admit` after target admission
and :meth:`sync_slots` each boundary.  During drafting the drafter
appends K/V for its own proposals; after verification :meth:`rewind`
rolls its lengths back to the target's committed length, so rejected
draft positions become uncommitted garbage that the next write overwrites
(the same stale-bytes-past-length contract the null page established).
The target's verify step writes K/V for all ``k + 1`` proposed positions
too; only the accepted ones are committed via ``cache.advance`` —
"rejected tokens roll their pages back uncommitted".
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from .engine import ServeEngine
from .kv_cache import KVCacheConfig, PagedKVCache

__all__ = [
    "SpeculativeDecoder",
    "drafter_config",
    "drafter_template",
    "load_drafter_params",
    "slice_drafter_params",
    "suggested_k",
]


def suggested_k(table=None) -> Optional[int]:
    """Drafter-depth hint from the AUDITED calibration table.

    Serve runs harvest their tagged spans into the active table
    (telemetry/costaudit.py): ``serve_decode`` buckets hold measured decode
    step wall times and ``serve_draft`` buckets hold measured draft-phase
    times keyed by depth (``bytes`` = k, so each sample prices k+1 drafter
    launches).  The hint is the deepest k whose draft phase — at the
    measured per-launch cost — stays under HALF a measured decode step,
    clamped to [1, 8].  Returns None when the table lacks serve
    measurements; callers then still require an explicit ``VESCALE_SPEC_K``.
    """
    from ..telemetry.calibrate import active_table

    t = table if table is not None else active_table()
    if t is None:
        return None
    decode_us = t.op_estimate_us("serve_decode")
    if not decode_us:
        return None
    total = weight = 0.0
    for (op, _axis, bucket), cell in t.entries.items():
        if op == "serve_draft" and bucket >= 1:
            total += cell["us"] / (bucket + 1) * cell["samples"]
            weight += cell["samples"]
    if not weight:
        return None
    per_launch = total / weight
    if per_launch <= 0:
        return None
    return max(1, min(8, int(decode_us / (2.0 * per_launch)) - 1))


def drafter_config(config, layers: int):
    """The target's ``LlamaConfig`` truncated to its first ``layers``
    decoder blocks (embedding/norm/head shared)."""
    if not (1 <= layers <= config.num_hidden_layers):
        raise ValueError(
            f"drafter_layers={layers} not in [1, {config.num_hidden_layers}]"
        )
    return dataclasses.replace(config, num_hidden_layers=layers)


def slice_drafter_params(params: Dict[str, Any], layers: int) -> Dict[str, Any]:
    """In-memory drafter tree: the first ``layers`` blocks + shared
    embed/norm/head picked out of a full target tree (the zero-IO path for
    tests and benches; checkpoints go through :func:`load_drafter_params`)."""
    if isinstance(params, dict) and "params" in params and "embed_tokens" not in params:
        params = params["params"]
    out = {k: v for k, v in params.items() if not k.startswith("layers_")}
    for l in range(layers):
        key = f"layers_{l}"
        if key not in params:
            raise ValueError(f"params missing {key} (drafter_layers={layers})")
        out[key] = params[key]
    return out


def drafter_template(config, mesh_jax, layers: int):
    """Abstract params-only restore template for the REDUCED-depth drafter:
    ShapeDtypeStruct + replicated sharding per leaf, naming ONLY the
    drafter's subtree — ``checkpoint.load`` reads exactly the chunks a
    template names, so the deeper layers (and the optimizer) are never
    read."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..models.llama import Llama

    dcfg = drafter_config(config, layers)
    shapes = jax.eval_shape(
        lambda r: Llama(dcfg).init(r, jnp.ones((1, 8), jnp.int32))["params"],
        jax.random.key(0),
    )
    rep = NamedSharding(mesh_jax, P())
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep), shapes
    )


def load_drafter_params(path: str, config, mesh_jax, layers: int) -> Dict[str, Any]:
    """Restore the drafter subtree from a TRAINING checkpoint through the
    elastic preflight (params-only, first ``layers`` blocks only)."""
    from .. import checkpoint as ckpt

    return ckpt.load(path, {"model": drafter_template(config, mesh_jax, layers)})["model"]


class SpeculativeDecoder:
    """Drafter engine + cache mirror + the greedy accept bookkeeping.

    Built by the serve driver next to the target engine and handed to
    ``run_serve_resilient(speculative=...)``; the loop drives
    :meth:`sync_slots` / :meth:`on_admit` / :meth:`draft` / :meth:`rewind`
    around the target's ``decode_multi`` verify step."""

    def __init__(
        self,
        engine: ServeEngine,
        drafter_params: Dict[str, Any],
        *,
        drafter_layers: Optional[int] = None,
        k: Optional[int] = None,
    ):
        from ..analysis import envreg

        if k is None:
            k = envreg.get_int("VESCALE_SPEC_K")
        if not k or k < 1:
            # audited-table drafter-depth hint: measured serve_draft /
            # serve_decode buckets (from a prior run's harvest) pick k when
            # neither the caller nor the env did; absent serve measurements
            # the explicit-k requirement stands
            k = suggested_k()
        if not k or k < 1:
            raise ValueError(f"speculative k must be >= 1, got {k}")
        if drafter_layers is None:
            drafter_layers = envreg.get_int("VESCALE_SPEC_DRAFTER_LAYERS")
        engine.cache.refuse_slot_state("speculative decoding")    # rejected drafts rewind the cache: kv_cache.py
        self.k = int(k)
        self.target = engine
        tc = engine.cache.config
        dcfg = drafter_config(engine.config, int(drafter_layers))
        self.cache = PagedKVCache(
            KVCacheConfig(
                layers=dcfg.num_hidden_layers,
                kv_heads=tc.kv_heads,
                head_dim=tc.head_dim,
                num_slots=tc.num_slots,
                page_size=tc.page_size,
                pages_per_slot=tc.pages_per_slot,
                num_pages=tc.num_pages,
                dtype=tc.dtype,
            ),
            engine.mesh,
        )
        self.engine = ServeEngine(
            dcfg, engine.mesh, drafter_params, self.cache,
            interpret=engine.interpret,
        )
        # acceptance accounting: drafted counts every proposed token that
        # HAD a chance to be accepted (budget-clamped proposals excluded
        # by the loop's take), accepted only those the target confirmed
        self.drafted = 0
        self.accepted = 0
        self.verify_steps = 0
        # slots the drafter could NOT mirror (its pool allocates every
        # slot's full page need, so target-side prefix sharing can admit
        # more than the drafter pool holds): those slots decode through
        # the verify step with zero drafts — one correct token per step,
        # plain-decode speed, never wrong output (greedy acceptance is
        # self-correcting) — and are excluded from acceptance accounting
        self.undrafted: set = set()

    def accept_rate(self) -> Optional[float]:
        """Fraction of drafted tokens the target accepted — the `/router`
        v3 ``spec_accept_rate`` field; None before the first verify."""
        if not self.drafted:
            return None
        return self.accepted / self.drafted

    # ------------------------------------------------------ slot lifecycle
    def on_admit(self, slot: int, prompt: Sequence[int], max_new_tokens: int) -> None:
        """Mirror a target admission: reserve the SAME slot id in the
        drafter cache and run the drafter's own full prefill (the drafter
        never consults the prefix tree — it is the cheap model)."""
        self.cache.alloc(len(prompt), max_new_tokens, slot=slot)
        self.engine.prefill(prompt, slot)
        self.cache.commit_prefill(slot, len(prompt))

    def admit(self, slot: int, prompt: Sequence[int], max_new_tokens: int) -> bool:
        """The loop's admission hook: :meth:`on_admit`, degrading to an
        UNDRAFTED slot when the drafter pool is out of pages (prefix
        sharing lets the target pool over-commit relative to the drafter's
        full-allocation mirror).  Deterministic: both ranks see the same
        admission stream, so both mark the same slots."""
        from .kv_cache import KVCacheOutOfPages

        self.undrafted.discard(slot)
        try:
            self.on_admit(slot, prompt, max_new_tokens)
            return True
        except KVCacheOutOfPages:
            self.undrafted.add(slot)
            return False

    def sync_slots(self, live_slots: Iterable[int]) -> None:
        """Free drafter slots whose target slot terminated (completion,
        timeout, eviction, drain) since the last boundary."""
        live = set(live_slots)
        for slot in self.cache.active_slots():
            if slot not in live:
                self.cache.free(slot)
        self.undrafted &= live

    def drafted_slots(self, active_slots: Sequence[int]) -> List[int]:
        """The subset of active slots the drafter actually mirrors."""
        return [s for s in active_slots if s not in self.undrafted]

    # ------------------------------------------------------------ drafting
    def draft(self, last_tokens: Sequence[int], active_slots: Sequence[int]) -> np.ndarray:
        """Propose ``k`` tokens per active slot: sequential drafter decode
        steps from each slot's last sampled token.  Runs ``k + 1`` steps —
        the last one writes the FINAL draft's K/V (its sampled token is
        discarded) so that on full acceptance the drafter cache covers
        every position the target committed, with no catch-up gap.
        Drafter lengths advance as it goes (rewound after verification); a
        drafter that runs past its reserved pages keeps proposing (writes
        land in the null page) — those proposals are garbage the verify
        step rejects."""
        S = self.cache.num_slots
        cur = [int(t) for t in last_tokens]
        drafts = np.zeros((S, self.k), np.int32)
        for i in range(self.k + 1):
            next_ids = self.engine.decode(cur).tokens
            for slot in active_slots:
                if self.cache.can_advance(slot):
                    self.cache.advance(slot)
                if i < self.k:
                    t = int(next_ids[slot])
                    drafts[slot, i] = t
                    cur[slot] = t
        return drafts

    def rewind(self, target_lengths: np.ndarray, active_slots: Sequence[int]) -> None:
        """Post-verify: roll every active drafter slot back to the
        target's committed length, discarding rejected draft positions."""
        for slot in active_slots:
            want = int(target_lengths[slot])
            have = int(self.cache.lengths[slot])
            if want <= have:
                self.cache.rollback(slot, want)
            else:
                # defensive (mirrored geometry makes want <= have hold
                # today): if the drafter ever stopped short of the
                # target's commit, catch the length up — the caught-up
                # positions hold STALE K/V the drafter will attend to,
                # which can only cost acceptance rate, never correctness
                # (every emitted token is the target's own argmax)
                while int(self.cache.lengths[slot]) < want and self.cache.can_advance(slot):
                    self.cache.advance(slot)

    # ------------------------------------------------------------ accepting
    def accept(
        self,
        drafts_row: np.ndarray,
        verify_logits_row: np.ndarray,
        budget: int,
        eos_id: Optional[int],
    ) -> Tuple[List[int], int]:
        """Greedy acceptance for one slot: compare the ``k`` drafts with
        the target's argmax at each position and emit the accepted prefix
        plus the target's own next token (the correction/bonus), clamped
        by the remaining token ``budget`` and cut at ``eos_id``.  Every
        emitted token is the target's OWN argmax — the drafts only decide
        how many of them one verify step yields — which is the greedy-
        acceptance bitwise-equality guarantee.

        Returns (emitted tokens, accepted draft count); the caller folds
        the counts into the acceptance-rate accounting."""
        k = self.k
        greedy = [int(np.argmax(verify_logits_row[i])) for i in range(k + 1)]
        matched = 0
        while matched < k and int(drafts_row[matched]) == greedy[matched]:
            matched += 1
        emitted: List[int] = []
        for i in range(matched + 1):  # accepted drafts + the bonus token
            if len(emitted) >= budget:
                break
            emitted.append(greedy[i])
            if eos_id is not None and greedy[i] == eos_id:
                break
        return emitted, min(matched, len(emitted))
