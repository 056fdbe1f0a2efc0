"""Paged KV cache — the serving working set as a DArray on the mesh.

Decode is memory-bound: the KV cache of every in-flight request IS the
working set, and continuous batching lives or dies on how it is carved up.
This module keeps the cache in two stacked DArrays (K and V) of physical
shape ``(layers, num_pages, page_size, kv_heads, head_dim)`` sharded with
the EXISTING placement vocabulary (``plan_axes``: kv-heads on "tp",
replicated elsewhere) — the same substrate training params live on, so the
redistribute/checkpoint/telemetry machinery applies unchanged
(arXiv:2211.05322's argument for one placement algebra over a
serving-specific sharding path).

Paging (vLLM-style): a global pool of fixed-size pages, a host-side free
list, and a per-slot page table.  Every device-facing shape is STATIC —
``num_slots`` decode rows, ``pages_per_slot`` table columns — so the
compiled prefill/decode programs never retrace as requests come and go;
admission and eviction only rewrite the (data, not shape) page-table and
length vectors.  Page 0 is reserved as the NULL page: unused table entries
point at it, keeping gathers in-bounds, and everything read through it is
masked by the length vector, so its contents never reach a logit.

Host-side state (free lists, page tables, lengths) is plain numpy and
fully deterministic: allocation pops the lowest free slot and the highest
free page, so two ranks driving the same request stream hold bit-identical
tables — the property ``fingerprint()`` exposes to the serve loop's
control-plane agreement check.

Page sharing (prefix_cache.py rides this): every page carries a refcount.
Exclusive pages (plain :meth:`alloc`) hold exactly one reference — their
owning slot.  :meth:`alloc_shared` maps already-written pages into a new
slot's table (one more reference each), and the radix tree pins cached
pages with its own reference (:meth:`retain_page`/:meth:`release_page`).
:meth:`free` only returns a page to the pool when its LAST reference
drops — a page with refcount > 0 can never be reallocated out from under
a reader.  Every inc/dec folds into the same event-sourced crc digest as
alloc/commit/free, and ``fingerprint()`` carries the live reference
total, so the PR-5/PR-10 cross-rank consistency check catches refcount
divergence exactly like slot-assignment divergence.

The latent form (``KVCacheConfig.latent``; multi-head latent attention rides
this): a position leaves ONE row a layer, shared by every head, from which
keys and values are both read, so the cache is the ``k`` pool alone,
``(layers, num_pages, page_size, 1, row)``, and ``v`` is None.  Nothing of the
host side changes: a latent page is allocated, shared (:meth:`alloc_shared`),
rolled back and fingerprinted like any other.  A latent pool may have slot
state beside it (``models/ling_hybrid.py``: one latent layer in six, the others'
delta-rule states a slot): ``arrays()`` is then ``k`` and the state's arrays, and
what slot state refuses (below) is refused.

Slot state (a hybrid of state-space and attention layers rides this): beside
the paged K and V, which then cover only the attention layers, the cache may
hold per-slot arrays of CONSTANT size (``KVCacheConfig.slot_state``: a
recurrent state, a convolution tail), each ``(layers, num_slots, ...)``.  A
slot owns its row of each with its pages: one ``alloc`` / ``free`` / ``reset``
covers both, and because a slot's row is wholly rewritten by its prefill,
``free`` zeroes nothing and a preempted request re-prefills as ever.  What
pages allow and a state does not is to be read at an earlier position: a state
holds the whole prefix folded together, so sharing a prefix
(:meth:`alloc_shared`) or rewinding a slot (:meth:`rollback`) would need a
SNAPSHOT of the state at that position, which nothing here takes.  Both raise
:class:`SlotStateUnsupported` on such a cache, and so do ``PrefixCache`` and
``SpeculativeDecoder`` when they are built over one.

A ring (a model that mixes window and full attention rides the slot state too:
``models/laguna.py``): a window layer needs the newest ``window`` positions of a
sequence and nothing else, so it keeps no pages: its K and V live in per-slot
arrays ``(window layers, num_slots, window, kv_heads, head_dim)``, position ``p``
at row ``p mod window``, 6.3 MB a slot whatever the sequence's length, while
``layers`` counts the full-attention layers alone and admission (``can_admit``,
``alloc``) counts their pages alone: the pool may then be smaller than
``num_slots * pages_per_slot`` on purpose (``num_pages``).  A ring keeps no
history (a row is overwritten ``window`` positions later), so what reads a slot
at an earlier position is refused exactly as for a recurrent state.

An open block (a model that generates by diffusion over blocks rides the slot
state too: ``models/sdar_moe.py``): while a slot's block of ``B`` positions is
being denoised, its ids, which of them are still masked and the pass it is at
are slot state, and every pass writes the block's K and V at the block's own
positions, PAST ``lengths``: provisional bytes, which no other slot can read
and which the next pass overwrites.  ``lengths`` counts the settled positions
only; the call that runs the block's final tokens (its commit, which rides
with the first pass of the block after it, written one block further past
``lengths``) leaves their K and V, and the host then moves the length by the
block (``advance(slot, positions)``).  Pages are reserved for prompt + budget, and a
page holds whole blocks (the engine checks that ``B`` divides the page), so the
last block, which may run past the budget, never leaves the reserved pages.
"""

from __future__ import annotations

import dataclasses
import math
import zlib
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["KVCacheConfig", "KVCacheOutOfPages", "PagedKVCache", "SlotStateUnsupported"]


class KVCacheOutOfPages(RuntimeError):
    """The page pool cannot cover the requested tokens — an admission-time
    capacity verdict (the scheduler sheds or waits), never a mid-decode
    crash: ``reserve`` is called before any cache byte moves."""


class SlotStateUnsupported(NotImplementedError):
    """The cache holds per-slot recurrent state, and the operation needs the
    state as it was at an earlier position: a snapshot nobody took."""


@dataclasses.dataclass(frozen=True)
class KVCacheConfig:
    """Static geometry of the paged cache.  ``max_seq_len`` (=
    ``page_size * pages_per_slot``) bounds prompt + generated tokens per
    request; ``num_pages`` defaults to one full allotment per slot plus the
    reserved null page (an intentionally tight pool — set it higher to
    overcommit slots against typical-shorter-than-max sequences).

    ``slot_state`` adds per-slot arrays beside the pages: entries ``(name,
    layers, shape, dtype)`` give ``PagedKVCache.state[name]`` of shape
    ``(layers, num_slots) + shape``; ``layers`` here then counts only the
    layers that keep K and V.  SEVERAL layers of a model may read one pool
    layer (``models/phi4flash.py``: ``layers`` = 1, written by one layer and
    read by eight, each with its own queries): the cache knows a pool layer by
    who writes it, and takes no notice of who reads.

    ``latent`` says the pool's rows are latents that serve as keys and values
    both: ``kv_heads`` is 1, ``head_dim`` the row's width, and there is no
    value pool.

    ``v_head_dim`` (None: ``head_dim``) is the width of a value where it is not
    a key's; the two pools then differ in their last axis.  ``folded`` lays a
    position's row out as every key head's entries side by side, ``(layers,
    num_pages, page_size, 1, kv_heads x width)``: the form for widths that are
    no whole number of 128-lane tiles a head (192: the chip would pad each head
    to 256 lanes, or turn the pool round so that a page is no longer one
    copy), read by ``kernels.paged_decode_folded``."""

    layers: int
    kv_heads: int
    head_dim: int
    num_slots: int = 8
    page_size: int = 16
    pages_per_slot: int = 4
    num_pages: Optional[int] = None
    dtype: Any = None  # default jnp.float32
    slot_state: Tuple[Tuple[str, int, Tuple[int, ...], Any], ...] = ()
    latent: bool = False
    v_head_dim: Optional[int] = None
    folded: bool = False

    def __post_init__(self):
        if self.latent and self.kv_heads != 1:
            raise ValueError("a latent cache keeps one row a position for all heads: kv_heads must be 1")
        if self.latent and (self.v_head_dim is not None or self.folded):
            raise ValueError("a latent cache has no value pool and one row a position: neither v_head_dim nor folded")
        if self.v_head_dim is not None and self.v_head_dim <= 0:
            raise ValueError("v_head_dim must be positive")
        if min(self.layers, self.kv_heads, self.head_dim) <= 0:
            raise ValueError("layers/kv_heads/head_dim must be positive")
        if min(self.num_slots, self.page_size, self.pages_per_slot) <= 0:
            raise ValueError("num_slots/page_size/pages_per_slot must be positive")
        if self.num_pages is not None and self.num_pages < 2:
            raise ValueError("num_pages must be >= 2 (page 0 is the reserved null page)")

    @property
    def max_seq_len(self) -> int:
        return self.page_size * self.pages_per_slot

    @property
    def pool_pages(self) -> int:
        # +1: page 0 is reserved (never allocated, masked everywhere)
        return self.num_pages if self.num_pages is not None else self.num_slots * self.pages_per_slot + 1

    def pool_row(self, values: bool = False) -> Tuple[int, int]:
        """The last two axes of the key pool (of the value pool): ``(kv_heads,
        width)``, or folded ``(1, kv_heads x width)``."""
        width = self.v_head_dim if values and self.v_head_dim is not None else self.head_dim
        return (1, self.kv_heads * width) if self.folded else (self.kv_heads, width)

    @classmethod
    def from_env(cls, layers: int, kv_heads: int, head_dim: int, dtype=None) -> "KVCacheConfig":
        from ..analysis import envreg

        return cls(
            layers=layers,
            kv_heads=kv_heads,
            head_dim=head_dim,
            num_slots=envreg.get_int("VESCALE_SERVE_SLOTS"),
            page_size=envreg.get_int("VESCALE_SERVE_PAGE_SIZE"),
            pages_per_slot=envreg.get_int("VESCALE_SERVE_PAGES_PER_SLOT"),
            dtype=dtype,
        )


def _zeros_global(spec):
    """A zero-filled global jax.Array for ``spec`` built shard-by-shard
    (``make_array_from_callback``) — multi-process safe, unlike an eager
    ``device_put`` of the logical value onto a process-spanning mesh."""
    import jax

    sharding = spec.named_sharding()
    shape = spec.layout().physical_shape
    dt = np.dtype(spec.dtype)
    return jax.make_array_from_callback(
        shape, sharding, lambda idx: np.zeros(_idx_shape(idx, shape), dt)
    )


def _zeros_replicated(shape, dtype, mesh):
    """A zero-filled array replicated over ``mesh``, made on the devices (a
    slot state is gigabytes: no host copy)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.jit(lambda: jnp.zeros(shape, dtype), out_shardings=NamedSharding(mesh.jax_mesh, P()))()


def _idx_shape(idx, shape) -> Tuple[int, ...]:
    return tuple(len(range(*s.indices(n))) for s, n in zip(idx, shape))


def write_pages(pool, rows, page_row, page: int, layer=0):
    """``rows`` (L, T, KV, hd), every layer's K or V of a rung's positions (a
    folded pool's: (L, T, 1, KV x hd)), into
    the pages ``page_row`` (T / page,) of ``pool`` (L, pages, page, KV, hd): one
    slab of all layers a page, updated in place.  Rows of fewer layers (one:
    a step that carries a prompt writes a layer at a time) go to the layers
    from ``layer`` on, an int or a traced int32.  (One scatter over the page
    axis makes the compiler re-lay out the WHOLE pool and back around it where
    a row of the pool is 4 heads wide, 3.7 ms a copy at a pool of 1.2 GB:
    PERF.md section 6, PR 43.)  The prefill programs of ``models/falcon_h1.py``,
    ``models/sdar_moe.py`` and ``models/laguna.py`` write through it; a family whose compiled rung
    holds no such copy (pools with rows of 8 or 32 heads, or the latent form's
    one row) keeps its scatter, which has no loop to run (PERF.md section 6,
    PR 44).  Entries of ``page_row`` past a slot's reserved pages name page 0,
    the null page: they land there one after another, and nobody reads it."""
    import jax

    L, T = rows.shape[:2]
    slabs = rows.reshape(L, T // page, page, *rows.shape[2:]).astype(pool.dtype)

    def one_page(p, pool):
        slab = jax.lax.dynamic_slice_in_dim(slabs, p, 1, axis=1)
        return jax.lax.dynamic_update_slice(pool, slab, (layer, page_row[p], 0, 0, 0))

    return jax.lax.fori_loop(0, T // page, one_page, pool)


class PagedKVCache:
    """Slot-allocated paged K/V storage + deterministic host bookkeeping.

    Device side: ``k``/``v`` are DArrays of shape
    ``(L, num_pages, page_size, KV, hd)``; the engine's compiled steps take
    ``k.data``/``v.data`` (donated) and the loop re-wraps the outputs via
    :meth:`update`.  Host side: ``page_table`` (num_slots, pages_per_slot)
    int32 and ``lengths`` (num_slots,) int32 are the only mutable state —
    both travel into the compiled steps as DATA, never as shapes.
    """

    def __init__(self, config: KVCacheConfig, mesh, placements=None):
        import jax.numpy as jnp

        from ..darray import DArray
        from ..placements import Shard, plan_axes
        from ..spec import DArraySpec, TensorMeta
        from ..telemetry import memtrack as _memtrack

        self.config = config
        self.mesh = mesh
        dtype = config.dtype if config.dtype is not None else jnp.float32
        shape = (config.layers, self.num_pages, config.page_size) + config.pool_row()
        if placements is None:
            # kv-heads (axis 3) split over the mesh dim NAMED "tp" when it
            # exists; any other axis name stays replicated — the same
            # mesh-shape-agnostic convention as llama_plan
            placements = plan_axes(mesh, tp=Shard(3))
        tp = next(
            (mesh.shape[i] for i, p in enumerate(placements) if p.is_shard(3)), 1
        )
        if config.kv_heads % max(tp, 1) or (config.folded and tp > 1):
            raise ValueError(
                f"kv_heads={config.kv_heads} not divisible by the head-sharded "
                f"mesh extent {tp}" + (" (a folded row holds every head: it is not split over heads)" if config.folded else "")
            )
        self.spec = DArraySpec(
            mesh,
            tuple(placements),
            TensorMeta(shape, jnp.dtype(dtype)),
        )
        # the value pool's own spec where a value is not as wide as a key
        self.v_spec = self.spec if config.v_head_dim is None else DArraySpec(
            mesh, tuple(placements), TensorMeta(shape[:3] + config.pool_row(values=True), jnp.dtype(dtype)))
        with _memtrack.tagged("kv_cache"):
            self.k = _memtrack.tag_array(DArray(_zeros_global(self.spec), self.spec))
            self.v = None if config.latent else _memtrack.tag_array(DArray(_zeros_global(self.v_spec), self.v_spec))
            # per-slot state beside the pages, replicated over the mesh (see the module docstring)
            self.state = {
                name: _memtrack.tag_array(_zeros_replicated((layers, config.num_slots) + tuple(shape), dt, mesh))
                for name, layers, shape, dt in config.slot_state
            }
        # ---------------------------------------------- host bookkeeping
        self.page_table = np.zeros((config.num_slots, config.pages_per_slot), np.int32)
        self.lengths = np.zeros((config.num_slots,), np.int32)
        self._pages_held = np.zeros((config.num_slots,), np.int32)
        # per-page reference counts: slots + the prefix tree; a page leaves
        # the free list at refs 0->1 and returns only at refs 1->0
        self._page_refs = np.zeros((self.num_pages,), np.int32)
        # pop() takes the HIGHEST page / lowest slot — deterministic across
        # ranks by construction (the agreement check hashes the result)
        self._free_pages: List[int] = list(range(1, self.num_pages))
        self._free_slots: List[int] = sorted(range(config.num_slots), reverse=True)
        # event-sourced digest: every mutation folds into a running crc, so
        # fingerprint() is O(1) per step (recomputing over the whole table
        # grew the per-step control exchange with the table)
        self._digest = 0
        self._tokens_held = 0

    # ------------------------------------------------------------ geometry
    @property
    def num_pages(self) -> int:
        return self.config.pool_pages

    @property
    def num_slots(self) -> int:
        return self.config.num_slots

    @property
    def max_seq_len(self) -> int:
        return self.config.max_seq_len

    @property
    def has_slot_state(self) -> bool:
        return bool(self.state)

    def state_bytes_per_slot(self) -> int:
        """Bytes of slot state one slot owns, all layers together."""
        return sum(int(a.nbytes) for a in self.state.values()) // self.num_slots

    def refuse_slot_state(self, what: str) -> None:
        """Raise where ``what`` needs a slot's state as it was at an earlier position."""
        if self.state:
            raise SlotStateUnsupported(
                f"{what} needs a slot's state (a recurrence's, an open block's, a ring's) as it was at an earlier position, and this cache "
                f"({', '.join(sorted(self.state))} beside the pages) keeps only the newest: the missing "
                "mechanism is a snapshot of the state at page boundaries")

    def pages_needed(self, tokens: int) -> int:
        return max(1, math.ceil(tokens / self.config.page_size))

    def free_slot_count(self) -> int:
        return len(self._free_slots)

    def free_page_count(self) -> int:
        return len(self._free_pages)

    def active_slots(self) -> List[int]:
        return sorted(set(range(self.num_slots)) - set(self._free_slots))

    def can_admit(self, prompt_tokens: int, max_new_tokens: int) -> bool:
        """Admission-time capacity check against the WHOLE request (prompt +
        generation budget): admitting on prompt pages alone would turn pool
        exhaustion into a mid-decode fault for a request we promised to
        serve."""
        total = prompt_tokens + max_new_tokens
        if total > self.max_seq_len:
            return False
        return (
            len(self._free_slots) > 0
            and self.pages_needed(total) <= len(self._free_pages)
        )

    def _fold(self, *ints: int) -> None:
        b = b"".join((v & 0xFFFFFFFF).to_bytes(4, "little") for v in ints)
        self._digest = zlib.crc32(b, self._digest)

    # ---------------------------------------------------------- allocation
    def _take_slot(self, slot: Optional[int]) -> int:
        """Pop the deterministic next free slot, or claim an EXPLICIT one
        (the speculative drafter mirrors the target cache's slot ids)."""
        if slot is None:
            return self._free_slots.pop()
        self._free_slots.remove(slot)  # ValueError when not free — loud
        return slot

    def alloc(self, prompt_tokens: int, max_new_tokens: int = 0,
              slot: Optional[int] = None) -> int:
        """Reserve a slot + every page the request can ever touch; returns
        the slot id.  Raises :class:`KVCacheOutOfPages` when the pool
        cannot cover it (callers gate on :meth:`can_admit`)."""
        total = prompt_tokens + max_new_tokens
        if total > self.max_seq_len:
            raise KVCacheOutOfPages(
                f"request of {total} tokens exceeds max_seq_len={self.max_seq_len}"
            )
        need = self.pages_needed(total)
        if not self._free_slots or need > len(self._free_pages):
            raise KVCacheOutOfPages(
                f"need slot+{need} pages, have {len(self._free_slots)} slots / "
                f"{len(self._free_pages)} pages free"
            )
        slot = self._take_slot(slot)
        row = self.page_table[slot]
        row[:] = 0
        for i in range(need):
            row[i] = self._free_pages.pop()
            self._page_refs[row[i]] = 1
        self._pages_held[slot] = need
        self.lengths[slot] = 0
        self._fold(1, slot, need, int(row[0]))
        return slot

    def alloc_shared(self, shared_pages: Sequence[int], prompt_tokens: int,
                     max_new_tokens: int = 0, slot: Optional[int] = None) -> int:
        """Prefix-cache admission: map ``shared_pages`` (already written,
        already referenced — typically by the radix tree) into the new
        slot's leading table entries and allocate FRESH pages only for the
        rest of the request.  The shared pages gain one reference each;
        the slot's prefill then starts at the shared boundary."""
        self.refuse_slot_state("alloc_shared (prefix sharing)")
        total = prompt_tokens + max_new_tokens
        if total > self.max_seq_len:
            raise KVCacheOutOfPages(
                f"request of {total} tokens exceeds max_seq_len={self.max_seq_len}"
            )
        shared = [int(p) for p in shared_pages]
        need = self.pages_needed(total)
        if len(shared) > need:
            raise ValueError(
                f"{len(shared)} shared pages exceed the {need} the request needs"
            )
        fresh = need - len(shared)
        if not self._free_slots or fresh > len(self._free_pages):
            raise KVCacheOutOfPages(
                f"need slot+{fresh} fresh pages, have {len(self._free_slots)} "
                f"slots / {len(self._free_pages)} pages free"
            )
        slot = self._take_slot(slot)
        row = self.page_table[slot]
        row[:] = 0
        for i, p in enumerate(shared):
            if self._page_refs[p] <= 0:
                raise ValueError(f"shared page {p} is unreferenced (freed?)")
            row[i] = p
            self._page_refs[p] += 1
            self._fold(4, slot, p, int(self._page_refs[p]))
        for i in range(len(shared), need):
            row[i] = self._free_pages.pop()
            self._page_refs[row[i]] = 1
        self._pages_held[slot] = need
        self.lengths[slot] = 0
        self._fold(1, slot, need, int(row[0]))
        return slot

    def commit_prefill(self, slot: int, prompt_tokens: int) -> None:
        """The prompt's K/V pages were written by the engine: the slot now
        holds ``prompt_tokens`` positions."""
        if prompt_tokens > int(self._pages_held[slot]) * self.config.page_size:
            raise ValueError(f"slot {slot}: prefill {prompt_tokens} exceeds reserved pages")
        self.lengths[slot] = prompt_tokens
        self._tokens_held += prompt_tokens
        self._fold(2, slot, prompt_tokens)

    def advance(self, slot: int, positions: int = 1) -> None:
        """``positions`` more positions of the slot are settled in the cache
        (from ``lengths`` on): the one of a decoded token, or the block an
        engine that generates by blocks has just committed."""
        if self.lengths[slot] + positions > int(self._pages_held[slot]) * self.config.page_size:
            raise KVCacheOutOfPages(f"slot {slot} is full ({int(self.lengths[slot])} tokens)")
        self.lengths[slot] += positions
        self._tokens_held += positions

    def can_advance(self, slot: int) -> bool:
        return self.lengths[slot] < int(self._pages_held[slot]) * self.config.page_size

    def rollback(self, slot: int, length: int) -> None:
        """Rewind the slot to ``length`` committed positions — the
        speculative drafter's post-verify rewind (rejected draft positions
        become uncommitted garbage again, overwritten by the next write).
        Pages stay reserved; only the length bookkeeping moves."""
        self.refuse_slot_state("rollback (speculation)")
        cur = int(self.lengths[slot])
        if not (0 <= length <= cur):
            raise ValueError(f"slot {slot}: rollback to {length} from {cur}")
        self._tokens_held -= cur - length
        self.lengths[slot] = length
        self._fold(7, slot, length)

    def free(self, slot: int) -> None:
        """Release the slot; each of its pages drops one reference and
        returns to the pool only when that was the LAST one (eviction,
        completion, timeout — all the same host-side operation).  Pages a
        prefix tree still retains — or another slot still maps — survive
        with their bytes intact."""
        if slot in self._free_slots:
            return
        held = int(self._pages_held[slot])
        # LIFO return keeps the free list a deterministic function of the
        # alloc/free history (not of dict/set iteration order)
        for i in range(held - 1, -1, -1):
            p = int(self.page_table[slot, i])
            self._page_refs[p] -= 1
            if self._page_refs[p] < 0:
                raise AssertionError(f"page {p} refcount went negative")
            if self._page_refs[p] == 0:
                self._free_pages.append(p)
        self._tokens_held -= int(self.lengths[slot])
        self._fold(3, slot, held, int(self.lengths[slot]))
        self.page_table[slot] = 0
        self.lengths[slot] = 0
        self._pages_held[slot] = 0
        self._free_slots.append(slot)
        self._free_slots.sort(reverse=True)

    # ----------------------------------------------------- page references
    def retain_page(self, page: int) -> None:
        """One more holder for an ALREADY-REFERENCED page (the radix tree
        pinning a slot's prefill output).  Folds into the digest like every
        other allocation event."""
        if not (0 < page < self.num_pages):
            raise ValueError(f"page {page} out of range (page 0 is reserved)")
        if self._page_refs[page] <= 0:
            raise ValueError(f"page {page} is unreferenced — nothing to retain")
        self._page_refs[page] += 1
        self._fold(5, page, int(self._page_refs[page]))

    def release_page(self, page: int) -> None:
        """Drop one reference (prefix-tree eviction); the page returns to
        the free pool only when this was the last holder."""
        if self._page_refs[page] <= 0:
            raise ValueError(f"page {page} is already unreferenced")
        self._page_refs[page] -= 1
        self._fold(6, page, int(self._page_refs[page]))
        if self._page_refs[page] == 0:
            self._free_pages.append(page)

    def page_ref(self, page: int) -> int:
        return int(self._page_refs[page])

    def reset(self) -> None:
        """Return every slot and page to the pool (device bytes stay —
        stale pages are legal: nothing reads past a slot's length).  Lets a
        driver reuse one COMPILED engine across runs instead of
        rebuilding (and recompiling) per run.  EVERY reference is dropped,
        the prefix tree's included — a PrefixCache built over this cache
        must be discarded (or ``reset``) with it, never carried across."""
        for slot in list(self.active_slots()):
            self.free(slot)
        # drop non-slot holders (a discarded radix tree's retained pages
        # would otherwise leak out of the pool permanently)
        self._page_refs[:] = 0
        self._free_pages = list(range(1, self.num_pages))

    # ------------------------------------------------------- device plumbing
    def update(self, k_data, v_data=None) -> None:
        """Re-wrap the engine step's donated outputs (same spec: the
        compiled program preserves the sharding).  A latent cache has no ``v``."""
        from ..darray import DArray

        if (v_data is None) != self.config.latent:
            raise ValueError("a latent cache takes back its one pool, any other its two")
        self.k = DArray(k_data, self.spec)
        self.v = None if v_data is None else DArray(v_data, self.v_spec)

    def arrays(self) -> Dict[str, Any]:
        """Everything the cache keeps on the device, by name, as an engine's
        programs take it (and give it back: :meth:`update_arrays`): the pools
        ``k`` (and ``v``), then the slot state's arrays in their order."""
        pools = {"k": self.k.data} if self.v is None else {"k": self.k.data, "v": self.v.data}
        return {**pools, **self.state}

    def update_arrays(self, arrays: Dict[str, Any]) -> None:
        self.update(arrays["k"], arrays.get("v"))
        if self.state:
            self.update_state(**{name: arrays[name] for name in self.state})

    def update_state(self, **arrays) -> None:
        """Take back the slot state an engine step was given (donated)."""
        if set(arrays) != set(self.state):
            raise ValueError(f"slot state is {sorted(self.state)}, got {sorted(arrays)}")
        self.state = dict(arrays)

    def table_array(self) -> np.ndarray:
        """A snapshot: a decode step is launched and not waited for, and the
        runtime may read a host argument after the call returns, while
        ``free`` and ``advance`` write the live arrays in place."""
        return self.page_table.copy()

    def lengths_array(self) -> np.ndarray:
        return self.lengths.copy()

    # ------------------------------------------------------------ agreement
    def fingerprint(self) -> Tuple[int, ...]:
        """Host-bookkeeping digest for the serve loop's control-plane
        agreement: ranks whose slot assignment, page allocation history or
        lengths diverge must raise before the next decode step can act on
        the disagreement.  Event-sourced (every alloc/commit/free folds
        into a running crc; advances keep a token total) so the per-step
        exchange is O(1), and deliberately EXCLUDES device bytes (the null
        page legally holds scatter garbage).  The live page-reference
        total rides along so shared-prefix refcount divergence trips the
        same DesyncError as slot-assignment divergence.  A slot's state has
        no bookkeeping of its own (it goes with the slot, whose assignment the
        digest holds); a cache that keeps one says so, and how much."""
        base = (
            self._digest,
            len(self._free_slots),
            len(self._free_pages),
            self._tokens_held,
            int(self._page_refs.sum()),
        )
        return base + (self.state_bytes_per_slot(),) if self.state else base

    def utilization(self) -> float:
        usable = self.num_pages - 1
        return 1.0 - (len(self._free_pages) / usable) if usable else 0.0
