"""Paged-attention decode kernel — a slot's live pages, straight from the pool.

The XLA decode leg (:func:`paged_attention_xla`) moves the WHOLE page pool
every step, whatever it holds: per layer it slices the layer out of the 5-D
pool, gathers every slot's ``Pmax`` pages into a dense ``(S, Tmax, KV,
hd)`` view, upcasts it and runs a masked softmax over all ``Tmax``
positions.  The kernel does work in proportion to the tokens the cache
holds:

  * **operands** — the whole ``(L, N, page, KV, hd)`` K and V pools stay
    in HBM (``pl.ANY``); the layer index, the ``(S,)`` lengths and the
    ``(S, Pmax)`` page table ride in as scalar-prefetch operands (the
    layer as an operand, not a constant, so every layer of a decode
    program is the same Mosaic kernel).  No per-layer slice, no gather.
  * **grid** — one step a slot.  Inside it a loop over the slot's *live*
    blocks, ``ceil(len / block)`` of them, a block being ``block_pages``
    pages (``_block_pages``: about 1 MiB of K, at most 512 positions).
    Pages are scattered in the pool, so a block is fetched by one DMA a
    page, K and V each into one of two VMEM buffers: while block ``b`` is
    computed block ``b + 1`` (or the next slot's first) is in flight.
    Pages past ``ceil(len / page)`` cost neither a DMA nor compute; an
    inactive slot (the engine passes length 1) costs one block.
  * **per block** — for each kv head its ``(block, hd)`` K and V rows are
    read out of the ``(page, KV, hd)`` page layout by a sublane-strided
    load (16-bit pools through a 32-bit view of two heads at once, as
    jax's ragged paged attention does), so nothing is transposed.  All
    ``H`` query rows go through the MXU against that head's K (the rows of
    other groups are dropped by a select, which also keeps a NaN of one kv
    head out of the others), which makes MHA (group 1) and GQA one code
    path with full-height matmul operands.  Scores, the online softmax
    (running max, sum and accumulator in VMEM scratch, carried over the
    slot's blocks) and the accumulation are fp32.

Numerics: K and V enter the matmuls as the pool holds them — widened to
fp32 exactly; on the chip the MXU multiplies fp32 operands in one bf16
pass at default precision, which is what the XLA leg's fp32 einsum does
there too — and nothing else is rounded below fp32.  Against the XLA
reference only the accumulation ORDER differs (online per block against
one full-row softmax), so interpreted parity is ulp-bounded, not bitwise:
the bound is asserted in tests/test_kernels.py and documented in
docs/kernels.md.  V rows past a slot's length are zeroed and their scores
masked, so stale bytes (a NaN in a page's tail, or VMEM a skipped DMA
never wrote) reach nothing.  A slot of length 0 fetches nothing and its
output is zeros.

Each op takes the kernel's ``interpret`` flag or None for its XLA leg, and :func:`leg` /
:func:`leg_latent` / :func:`leg_folded` resolve that for a pool: a caller names its pool and chooses nothing.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import kernels

__all__ = ["paged_decode", "paged_attention_xla", "supports", "leg", "paged_decode_latent", "latent_attention_xla",
           "supports_latent", "leg_latent", "paged_decode_folded", "folded_attention_xla", "supports_folded", "leg_folded"]

_NEG_INF = -1e30
_BLOCK_BYTES = 1 << 20       # of K in one block (V the same; two buffers each)
_BLOCK_POSITIONS = 512       # at most: a block's compute is not bounded by the length inside it


def _block_pages(pages_per_slot: int, page: int, kv_heads: int, head_dim: int, itemsize: int) -> int:
    """Pages a block holds, from the shapes alone: as many as ``_BLOCK_BYTES``
    of K and ``_BLOCK_POSITIONS`` allow (GQA at 8 x 128 bf16: 32 pages of 16,
    512 positions; MHA at 32 x 128: 8 pages, 128 positions), never more than
    a slot has."""
    by_bytes = _BLOCK_BYTES // (page * kv_heads * head_dim * itemsize)
    return max(1, min(pages_per_slot, by_bytes, max(1, _BLOCK_POSITIONS // page)))


def supports(pool_dtype, kv_heads: int, head_dim: int, *, interpret: bool) -> bool:
    """Whether the kernel takes a pool of this dtype with ``kv_heads`` heads
    (per shard) of ``head_dim``: 32-bit pools, or bfloat16 with an even
    number of heads (a 32-bit row holds two); compiled, the strided loads
    want whole 128-lane rows, and a bfloat16 page's copy whole sublane tiles
    of its heads: 2, 4 or a multiple of 8 of them (6, 10, 12 or 20 Mosaic
    refuses: "slice shape must be aligned to tiling (8)"; such rows go FOLDED,
    :func:`paged_decode_folded`).  The engine takes its XLA leg otherwise."""
    dt = jnp.dtype(pool_dtype)
    if not (dt.itemsize == 4 or (dt == jnp.bfloat16 and kv_heads % 2 == 0)):
        return False
    return interpret or (head_dim % 128 == 0 and (dt.itemsize == 4 or kv_heads in (2, 4) or kv_heads % 8 == 0))


def leg(pool_dtype, kv_heads: int, head_dim: int) -> Optional[bool]:
    """The leg :func:`paged_decode` takes over such a pool: the kernel's ``interpret`` flag, or None for the XLA leg."""
    return kernels.resolve("paged_decode",
                           supported=lambda interpret: supports(pool_dtype, kv_heads, head_dim, interpret=interpret))


def paged_attention_xla(q, k_pool, v_pool, table, valid_len, *, layer: int, scale: float):
    """:func:`paged_decode` without the kernel: gather every slot's pages, mask by length, float32 softmax."""
    S, H, hd = q.shape
    KV = k_pool.shape[3]
    ks = jnp.take(k_pool[layer], table, axis=0).reshape(S, -1, KV, hd)
    vs = jnp.take(v_pool[layer], table, axis=0).reshape(S, -1, KV, hd)
    qg = (q.astype(jnp.float32) * scale).reshape(S, KV, H // KV, hd)
    s = jnp.einsum("skgd,stkd->skgt", qg, ks.astype(jnp.float32))
    mask = jnp.arange(ks.shape[1], dtype=jnp.int32)[None, :] < valid_len[:, None]
    p = jax.nn.softmax(jnp.where(mask[:, None, None, :], s, -1e30), axis=-1)
    return jnp.einsum("skgt,stkd->skgd", p, vs.astype(jnp.float32)).reshape(S, H, hd)


def _decode_kernel(layer_ref, len_ref, table_ref, q_ref, k_hbm, v_hbm, o_ref,
                   k_buf, v_buf, sems, cur_ref, m_scr, l_scr, acc_scr,
                   *, scale, page, block_pages, kv_heads, group):
    """Grid (S,), sequential: the buffer a block lands in alternates over the
    whole call (``cur_ref``, SMEM scratch, survives from one slot to the
    next), because the last block of a slot prefetches the next slot's first."""
    s = pl.program_id(0)
    n_slots = pl.num_programs(0)
    H = kv_heads * group
    T = block_pages * page
    layer = layer_ref[0]
    packed = k_buf.dtype.itemsize == 2      # two heads a 32-bit row

    def block_dma(slot, block, buf, wait):
        """Start (or wait for) the copies of the live pages of ``block`` of
        ``slot`` into buffer ``buf``: one DMA a page for K and one for V."""
        first = block * block_pages
        live = jnp.clip(pl.cdiv(len_ref[slot], page) - first, 0, block_pages)

        def one_page(i, carry):
            phys = table_ref[slot, first + i]
            for hbm, vmem, sem in ((k_hbm, k_buf, 0), (v_hbm, v_buf, 1)):
                copy = pltpu.make_async_copy(hbm.at[layer, phys], vmem.at[buf, i], sems.at[buf, sem])
                copy.wait() if wait else copy.start()
            return carry

        jax.lax.fori_loop(0, live, one_page, 0)

    length = len_ref[s]
    # at least one block a slot (a length of 0 fetches nothing and masks it
    # all): the last block of a slot is where the next slot's first is started
    n_blocks = jnp.maximum(pl.cdiv(length, T), 1)

    @pl.when(s == 0)
    def _first():
        cur_ref[0] = 0
        block_dma(0, 0, 0, wait=False)

    m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    q = q_ref[0].astype(jnp.float32) * scale                           # (H, hd)
    row_head = jax.lax.broadcasted_iota(jnp.int32, (H, 1), 0) // group   # kv head of each q row

    def head_rows(buf_ref, buf, k, keep):
        """kv head ``k``'s (T, hd) rows of the block in buffer ``buf``, fp32
        (exact).  ``keep`` (T, hd) zeroes rows past the length, or is None."""
        rows = buf_ref.at[buf].reshape(T * kv_heads, buf_ref.shape[-1])
        if not packed:
            x = rows[pl.ds(k, T, stride=kv_heads), :].astype(jnp.float32)
            return x if keep is None else jnp.where(keep, x, 0.0)
        # a 32-bit row holds heads 2j (low half) and 2j+1 (high half) of one position
        w = rows.bitcast(jnp.uint32)[pl.ds(k // 2, T, stride=kv_heads // 2), :]
        w = (w << 16) if k % 2 == 0 else (w & jnp.uint32(0xFFFF0000))
        if keep is not None:
            w = jnp.where(keep, w, jnp.uint32(0))
        return pltpu.bitcast(w, jnp.float32)

    def block_body(b, cur):
        nxt = 1 - cur

        @pl.when(b + 1 < n_blocks)
        def _():
            block_dma(s, b + 1, nxt, wait=False)

        @pl.when(jnp.logical_and(b + 1 == n_blocks, s + 1 < n_slots))
        def _():
            block_dma(s + 1, 0, nxt, wait=False)

        block_dma(s, b, cur, wait=True)
        pos = b * T + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
        valid = pos < length                                          # (1, T)
        keep = (b * T + jax.lax.broadcasted_iota(jnp.int32, (T, k_buf.shape[-1]), 0)) < length

        sc = jnp.zeros((H, T), jnp.float32)
        for k in range(kv_heads):
            kk = head_rows(k_buf, cur, k, None)
            s_k = jax.lax.dot_general(q, kk, (((1,), (1,)), ((), ())),
                                      preferred_element_type=jnp.float32)   # (H, T)
            sc = jnp.where(row_head == k, s_k, sc)
        sc = jnp.where(valid, sc, _NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        pexp = jnp.exp(sc - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(pexp, axis=-1, keepdims=True)
        m_scr[...] = m_new
        pv = jnp.zeros(acc_scr.shape, jnp.float32)
        for k in range(kv_heads):
            vk = head_rows(v_buf, cur, k, keep)
            o_k = jax.lax.dot_general(pexp, vk, (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)   # (H, hd)
            pv = jnp.where(row_head == k, o_k, pv)
        acc_scr[...] = acc_scr[...] * alpha + pv
        return nxt

    cur_ref[0] = jax.lax.fori_loop(0, n_blocks, block_body, cur_ref[0])

    l = l_scr[...]
    o_ref[0] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@kernels.with_xla_leg(paged_attention_xla, static_argnames=("scale", "interpret"))
def paged_decode(q, k_pool, v_pool, table, lengths, *, layer, scale, interpret):
    """One decode-step attention of one layer over the paged KV pool.

    ``q``: (S, H, hd) new-token queries; ``k_pool``/``v_pool``:
    (L, N, page, KV, hd), the WHOLE pool, every layer (the kernel leaves it
    in HBM and reads only ``layer``'s live pages); ``layer``: int or int32
    scalar; ``table``: (S, Pmax) int32 physical page ids per slot (0 = the
    reserved null page); ``lengths``: (S,) int32 valid positions per slot (the
    new token included); ``interpret``: the kernel's flag, or None for the XLA
    leg (what :func:`leg` resolved, latched by the caller's program).  Returns
    fp32 (S, H, hd) attention output — callers reshape and cast.

    The caller (serve/engine.py) owns any shard_map wrapping of the kernel
    for a kv-head-sharded pool.
    """
    S, H, hd = q.shape
    L, N, page, KV, hd2 = k_pool.shape
    if hd != hd2 or H % KV or v_pool.shape != k_pool.shape or v_pool.dtype != k_pool.dtype:
        raise ValueError(f"paged_decode: q {q.shape} against pools {k_pool.shape} / {v_pool.shape}")
    if not supports(k_pool.dtype, KV, hd, interpret=bool(interpret)):
        raise ValueError(f"paged_decode takes no {k_pool.dtype} pool of {KV} kv heads x {hd} (see supports())")
    itemsize = jnp.dtype(k_pool.dtype).itemsize
    Pmax = table.shape[1]
    G = H // KV
    bp = _block_pages(Pmax, page, KV, hd, itemsize)
    buf = pltpu.VMEM((2, bp, page, KV, hd), k_pool.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S,),
        in_specs=[
            pl.BlockSpec((1, H, hd), lambda s, *_: (s, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, hd), lambda s, *_: (s, 0, 0)),
        scratch_shapes=[
            buf, buf,
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=float(scale), page=page, block_pages=bp,
                          kv_heads=KV, group=G),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode",
    )(jnp.asarray(layer, jnp.int32).reshape(1), jnp.clip(lengths.astype(jnp.int32), 0, Pmax * page),
      table.astype(jnp.int32), q, k_pool, v_pool)


# ------------------------------------------------------------- the latent form
# Multi-head latent attention's decode (the absorbed form): the pool holds ONE
# row a position, shared by every head, and the values are the row's first
# ``latent`` columns.  So a block of pages is fetched once and serves both
# products: all ``H`` absorbed queries against the block's rows through the MXU
# for the scores, the probabilities against the same rows' leading columns for
# the mix.  With 128 heads on one row that is about 240 operations a byte read:
# the v5e's ridge, where the kernel above (one or four heads a row) sits far on
# the memory side.
#
# How a block is fetched (PR 57; PERF.md section 6 has the split it rests on).  A
# page of 16 rows is one copy of 20 KB, and what such a page costs is not its
# bytes (32 copies in flight move at 766 GB/s) but the scalar unit's work on its
# descriptor, which runs under nothing: a DMA start holds the instruction
# stream whether it stands before the products or among them.  So the kernel
# makes a descriptor as cheap as it gets and issues as few branches as it can:
#   * a block's pages are started in GROUPS (128 positions: 8 pages of 16), the
#     copies of a group unrolled under one branch (is the group live?), and a
#     block is waited for ONCE, by a descriptor over its live groups (the
#     semaphore counts bytes).  A group is fetched whole: its pages past the
#     slot's length are whatever the table names (the null page), masked like
#     every row past the length;
#   * the buffer's index is STATIC in every descriptor: the buffers alternate
#     over the whole call (a slot's last block starts the next slot's first), so
#     the slot's loop walks that ring two positions a trip, from the position
#     its first block stands in.
# One dynamic loop over the pages with one wait a page cost 1.19 us a block of
# 512 positions beside 0.84 (64 heads) or 1.33 (128) of compute, and they ADD;
# this form costs 0.42.  The masks are free (they hide under the products), and
# products bounded by the live groups of a slot's last block were SLOWER than
# whole ones (four bodies and their branches), so every block's products are whole.
_GROUP_POSITIONS = 128       # of a group: its pages' copies are started under one branch


def _latent_blocks(pages_per_slot: int, page: int, row: int, itemsize: int):
    """``(pages a group, groups a block)`` of the latent kernel, from the shapes
    alone: groups of ``_GROUP_POSITIONS`` positions (at least a page), and as
    many of them a block as ``_BLOCK_BYTES`` and ``_BLOCK_POSITIONS`` allow
    (rows of 640 bfloat16 in pages of 16: 4 groups of 8 pages, 512 positions),
    never more than a slot has."""
    group = max(1, _GROUP_POSITIONS // page)
    return group, max(1, _block_pages(pages_per_slot + group - 1, page, 1, row, itemsize) // group)


def supports_latent(pool_dtype, row: int, latent: int, page: int, *, interpret: bool) -> bool:
    """Whether :func:`paged_decode_latent` takes a pool of this dtype with
    rows ``row`` wide of which the first ``latent`` are the values: compiled,
    both are whole 128-lane tiles and a page is a whole sublane tile of the
    dtype (16 rows of bfloat16, 8 of float32)."""
    dt = jnp.dtype(pool_dtype)
    if dt not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)) or not 0 < latent <= row:
        return False
    return interpret or (row % 128 == 0 and latent % 128 == 0 and page % (32 // dt.itemsize) == 0)


def leg_latent(pool_dtype, row: int, latent: int, page: int) -> Optional[bool]:
    """The leg :func:`paged_decode_latent` takes over such a pool: the kernel's ``interpret`` flag, or None."""
    return kernels.resolve("paged_decode_latent",
                           supported=lambda interpret: supports_latent(pool_dtype, row, latent, page, interpret=interpret))


def latent_attention_xla(q, pool, table, valid_len, *, layer: int, scale: float, latent: int):
    """Decode attention of one layer in the absorbed form without the kernel:
    gather every slot's pages, mask by length, float32 softmax.  ``q`` (S, H,
    row) in the pool's type, ``pool`` (L, N, page, 1, row); the values are the
    rows' first ``latent`` columns.  Returns (S, H, latent) float32."""
    S, H, row = q.shape
    rows = jnp.take(pool[layer], table, axis=0).reshape(S, -1, row)                      # (S, Tmax, row)
    mask = jnp.arange(rows.shape[1], dtype=jnp.int32)[None, :] < valid_len[:, None]
    rows = jnp.where(mask[:, :, None], rows, jnp.zeros_like(rows))     # stale bytes past the length reach nothing
    # operands widened exactly (the kernel multiplies them as they are, with float32 accumulation: the same numbers)
    s = scale * jnp.einsum("shr,str->sht", q.astype(jnp.float32), rows.astype(jnp.float32))
    p = jax.nn.softmax(jnp.where(mask[:, None, :], s, -1e30), axis=-1)
    return jnp.einsum("sht,stc->shc", p.astype(rows.dtype).astype(jnp.float32), rows[..., :latent].astype(jnp.float32))


def _latent_kernel(layer_ref, len_ref, table_ref, q_ref, pool_hbm, o_ref,
                   buf, sems, cur_ref, m_scr, l_scr, acc_scr, *, scale, page, group, groups, latent):
    """Grid (S,), sequential; the two buffers alternate over the whole call as in ``_decode_kernel``
    (``cur_ref``: the buffer the slot's first block is in).  Its trace is a serve cell's set-up time, twice a cell:
    two bodies and a group's copies traced once keep it at 0.07 s (a Python loop over the copies: 0.26 s, and 5 s of
    ``setup_s`` on the chip's host)."""
    s = pl.program_id(0)
    n_slots = pl.num_programs(0)
    block_pages = group * groups
    GT = group * page                  # positions a group
    T = groups * GT                    # positions a block
    row = buf.shape[-1]
    layer = layer_ref[0]

    def live_groups(slot, block):
        """The groups of ``block`` of ``slot`` that hold a live position."""
        return jnp.clip(pl.cdiv(len_ref[slot] - block * T, GT), 0, groups)

    def start(slot, block, into, n):
        """Start the copies of the first ``n`` groups of ``block`` of ``slot`` into buffer ``into`` (static)."""
        def one_page(i, carry):
            pltpu.make_async_copy(pool_hbm.at[layer, table_ref[slot, block * block_pages + i]],
                                  buf.at[into, i], sems.at[into]).start()
            return carry

        for g in range(groups):
            @pl.when(g < n)
            def _(g=g):       # unrolled where it is lowered (a page's index is a constant there), traced once
                jax.lax.fori_loop(g * group, (g + 1) * group, one_page, 0, unroll=True)

    length = len_ref[s]
    # at least one block a slot (a length of 0 fetches nothing and computes nothing):
    # the last block of a slot is where the next slot's first is started
    n_blocks = jnp.maximum(pl.cdiv(length, T), 1)

    @pl.when(s == 0)
    def _first():
        cur_ref[0] = 0
        start(0, 0, 0, live_groups(0, 0))

    m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)
    q = q_ref[0]                                                       # (H, row), the pool's type

    def attend(b, cur):
        rows = buf[cur].reshape(T, row)
        # rows past the length are stale pool bytes, or VMEM no copy ever wrote: zeroed, and their scores masked
        keep = (b * T + jax.lax.broadcasted_iota(jnp.int32, (T, row), 0)) < length
        rows = jnp.where(keep, rows, jnp.zeros_like(rows))
        sc = scale * jax.lax.dot_general(q, rows, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)  # (H, T)
        valid = (b * T + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)) < length
        sc = jnp.where(valid, sc, _NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        pexp = jnp.where(valid, jnp.exp(sc - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(pexp, axis=-1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            pexp.astype(rows.dtype), rows[:, :latent], (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    def one_block(b, cur):
        """Block ``b`` of this slot, in buffer ``cur`` (static): start what follows it, wait for it, attend to it."""
        more = b + 1 < n_blocks
        nslot = jnp.minimum(jnp.where(more, s, s + 1), n_slots - 1)
        nblock = jnp.where(more, b + 1, 0)
        start(nslot, nblock, 1 - cur, jnp.where(jnp.logical_or(more, s + 1 < n_slots), live_groups(nslot, nblock), 0))
        live = live_groups(s, b)
        for k in range(1, groups + 1):
            @pl.when(live == k)
            def _(k=k):       # one wait for what was started: k groups' bytes
                pltpu.make_async_copy(pool_hbm.at[layer, pl.ds(0, k * group)], buf.at[cur, pl.ds(0, k * group)],
                                      sems.at[cur]).wait()

        @pl.when(live > 0)
        def _():
            attend(b, cur)

    # the call's blocks stand in a ring of two positions: this slot's block b at position first + b, in buffer
    # (first + b) % 2; the loop walks the positions in pairs, so that a buffer's index is the place in the pair
    first = cur_ref[0]

    def two_positions(j, carry):
        for k in range(2):
            b = 2 * j + k - first

            @pl.when(jnp.logical_and(b >= 0, b < n_blocks))
            def _(b=b, k=k):
                one_block(b, k)
        return carry

    jax.lax.fori_loop(0, pl.cdiv(first + n_blocks, 2), two_positions, 0)
    cur_ref[0] = (first + n_blocks) % 2
    l = l_scr[...]
    o_ref[0] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@kernels.with_xla_leg(latent_attention_xla, static_argnames=("scale", "latent", "interpret"))
def paged_decode_latent(q, pool, table, lengths, *, layer, scale, latent, interpret):
    """One decode-step attention of one layer over a LATENT paged pool.

    ``q``: (S, H, row) absorbed queries in the pool's type (the products run
    on operands of that type with float32 accumulation, on both legs);
    ``pool``: (L, N, page, 1, row), every layer; ``table``, ``lengths``,
    ``layer``, ``interpret`` as :func:`paged_decode` takes them (None: the
    XLA leg; :func:`leg_latent` resolves it).  Returns float32 (S, H, latent):
    ``sum_t softmax_t(scale q . row_t) row_t[:latent]`` over the slot's first
    ``lengths`` positions (zeros for a length of 0).  The kernel reads only the
    slot's live pages, once, for scores and values both."""
    S, H, row = q.shape
    L, N, page, one, row2 = pool.shape
    if one != 1 or row != row2 or q.dtype != pool.dtype:
        raise ValueError(f"paged_decode_latent: q {q.shape} {q.dtype} against a pool {pool.shape} {pool.dtype}")
    if not supports_latent(pool.dtype, row, latent, page, interpret=bool(interpret)):
        raise ValueError(f"paged_decode_latent takes no {pool.dtype} pool of rows {row} / {latent} in pages of {page} "
                         "(see supports_latent())")
    Pmax = table.shape[1]
    group, groups = _latent_blocks(Pmax, page, row, jnp.dtype(pool.dtype).itemsize)
    bp = group * groups
    table = table.astype(jnp.int32)
    if Pmax % bp:       # a group is started whole: the table ends on a block's edge, its new entries the null page
        table = jnp.pad(table, ((0, 0), (0, bp - Pmax % bp)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S,),
        in_specs=[pl.BlockSpec((1, H, row), lambda s, *_: (s, 0, 0)), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, H, latent), lambda s, *_: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, bp, page, row), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, latent), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_latent_kernel, scale=float(scale), page=page, group=group, groups=groups, latent=latent),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, latent), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_latent",
    )(jnp.asarray(layer, jnp.int32).reshape(1), jnp.clip(lengths.astype(jnp.int32), 0, Pmax * page),
      table, q, pool.reshape(L, N, page, row))


# ------------------------------------------------------------- the folded form
# Keys of one width beside values of another, neither a whole number of 128-lane
# tiles a head (192 | 128 on 4 or 8 key heads): a pool row ``(KV, 192)`` would be
# laid out by the chip with 192 padded to 256 lanes, or transposed so that the
# PAGES are the lanes (read on a described v5e: ``bf16[L, N, 32, 4, 192]`` gets
# the layout ``{1,4,3,2,0}``), and either way a page is no longer one contiguous
# copy.  So the pools are FOLDED: a position's row holds every key head's
# entries side by side, ``(L, N, page, 1, KV x Dk)`` and ``(L, N, page, 1, KV x
# Dv)`` (768 | 512 and 1,536 | 1,024 lanes: whole tiles, no padding, a page one
# DMA).  Nothing is sliced at a half tile: the query rows are SPREAD over the
# folded width instead (row ``h`` keeps its entries in its key head's lanes and
# zeros elsewhere; the spreading is one small product with a 0 / 1 matrix, once
# a slot), so one product of ``(H, KV x Dk)`` against the block's rows gives
# every head's scores, and the mix of the block's folded values is cut back a
# key head at a time at whole-tile offsets.  The MXU does ``KV`` times the
# products a head needs, as :func:`paged_decode` does; the step is the pages'
# read.  A ``sink`` (one learned logit a query head: a column of the softmax
# that takes mass and gives no value) joins the running maximum and the
# denominator once, after the slot's last block.
def supports_folded(pool_dtype, kv_heads: int, dk: int, dv: int, page: int, *, interpret: bool) -> bool:
    """Whether :func:`paged_decode_folded` takes pools of this dtype whose rows
    fold ``kv_heads`` heads of ``dk`` (keys) and ``dv`` (values): compiled, both
    folded widths and ``dv`` are whole 128-lane tiles and a page a whole sublane
    tile of the dtype."""
    dt = jnp.dtype(pool_dtype)
    if dt not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)) or min(kv_heads, dk, dv) < 1:
        return False
    return interpret or ((kv_heads * dk) % 128 == 0 and dv % 128 == 0 and page % (32 // dt.itemsize) == 0)


def leg_folded(pool_dtype, kv_heads: int, dk: int, dv: int, page: int) -> Optional[bool]:
    """The leg :func:`paged_decode_folded` takes over such pools: the kernel's ``interpret`` flag, or None."""
    return kernels.resolve("paged_decode", supported=lambda interpret: supports_folded(pool_dtype, kv_heads, dk, dv, page,
                                                                                      interpret=interpret))


def _folded_heads(q, k_pool, v_pool):
    S, H, dk = q.shape
    L, N, page, one, wk = k_pool.shape
    kv = wk // dk
    if (one != 1 or wk != kv * dk or H % max(kv, 1) or v_pool.shape[:4] != k_pool.shape[:4] or v_pool.shape[4] % kv
            or v_pool.dtype != k_pool.dtype or q.dtype != k_pool.dtype):
        raise ValueError(f"paged_decode_folded: q {q.shape} {q.dtype} against folded pools {k_pool.shape} / {v_pool.shape} "
                         f"{k_pool.dtype}")
    return kv, v_pool.shape[4] // kv


def folded_attention_xla(q, k_pool, v_pool, table, valid_len, *, layer: int, scale: float, sink=None):
    """:func:`paged_decode_folded` without the kernel: gather every slot's pages,
    mask by length, float32 softmax with the sink as one more column.  Products
    on operands of the pools' type with float32 accumulation, as the kernel."""
    S, H, dk = q.shape
    kv, dv = _folded_heads(q, k_pool, v_pool)
    ks = jnp.take(k_pool[layer], table, axis=0).reshape(S, -1, kv, dk)
    vs = jnp.take(v_pool[layer], table, axis=0).reshape(S, -1, kv, dv)
    mask = jnp.arange(ks.shape[1], dtype=jnp.int32)[None, :] < valid_len[:, None]
    vs = jnp.where(mask[:, :, None, None], vs, jnp.zeros_like(vs))     # stale bytes past the length reach nothing
    s = scale * jnp.einsum("skgd,stkd->skgt", q.reshape(S, kv, H // kv, dk), ks, preferred_element_type=jnp.float32)
    s = jnp.where(mask[:, None, None, :], s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    column = None if sink is None else sink.astype(jnp.float32).reshape(1, kv, H // kv, 1)
    if column is not None:
        m = jnp.maximum(m, column)
    p = jnp.where(mask[:, None, None, :], jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True) + (0.0 if column is None else jnp.exp(column - m))
    o = jnp.einsum("skgt,stkd->skgd", p.astype(vs.dtype), vs, preferred_element_type=jnp.float32)
    return (o / jnp.where(l == 0.0, 1.0, l)).reshape(S, H, dv)


def _folded_kernel(layer_ref, len_ref, table_ref, q_ref, spread_ref, *rest, scale, page, block_pages, kv_heads, group, dk, dv,
                   has_sink):
    """Grid (S,), sequential; the buffers alternate over the whole call as in ``_decode_kernel``."""
    sink_ref, (k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, cur_ref, m_scr, l_scr, acc_scr) = (
        (rest[0], rest[1:]) if has_sink else (None, rest))
    s = pl.program_id(0)
    n_slots = pl.num_programs(0)
    H = kv_heads * group
    T = block_pages * page
    layer = layer_ref[0]

    def block_dma(slot, block, buf, wait):
        first = block * block_pages
        live = jnp.clip(pl.cdiv(len_ref[slot], page) - first, 0, block_pages)

        def one_page(i, carry):
            phys = table_ref[slot, first + i]
            for hbm, vmem, sem in ((k_hbm, k_buf, 0), (v_hbm, v_buf, 1)):
                copy = pltpu.make_async_copy(hbm.at[layer, phys], vmem.at[buf, i], sems.at[buf, sem])
                copy.wait() if wait else copy.start()
            return carry

        jax.lax.fori_loop(0, live, one_page, 0)

    length = len_ref[s]
    n_blocks = jnp.maximum(pl.cdiv(length, T), 1)

    @pl.when(s == 0)
    def _first():
        cur_ref[0] = 0
        block_dma(0, 0, 0, wait=False)

    m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)
    row_head = jax.lax.broadcasted_iota(jnp.int32, (H, 1), 0) // group          # the key head of each query row
    # the query rows over the folded width: a copy of the row in every key head's lanes (exact: ones and zeros), kept
    # in its own head's alone
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (H, kv_heads * dk), 1) // dk
    q = jax.lax.dot_general(q_ref[0], spread_ref[...], (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    q = jnp.where(lane_head == row_head, q, 0.0).astype(k_buf.dtype)           # (H, KV x Dk)

    def block_body(b, cur):
        nxt = 1 - cur

        @pl.when(b + 1 < n_blocks)
        def _():
            block_dma(s, b + 1, nxt, wait=False)

        @pl.when(jnp.logical_and(b + 1 == n_blocks, s + 1 < n_slots))
        def _():
            block_dma(s + 1, 0, nxt, wait=False)

        block_dma(s, b, cur, wait=True)
        keys = k_buf[cur].reshape(T, kv_heads * dk)
        sc = scale * jax.lax.dot_general(q, keys, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)   # (H, T)
        valid = (b * T + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)) < length
        sc = jnp.where(valid, sc, _NEG_INF)             # (a stale key's NaN goes with it)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        pexp = jnp.where(valid, jnp.exp(sc - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(pexp, axis=-1, keepdims=True)
        m_scr[...] = m_new
        # values past the length are stale pool bytes, or VMEM a skipped DMA never wrote: zeroed
        values = v_buf[cur].reshape(T, kv_heads * dv)
        keep = (b * T + jax.lax.broadcasted_iota(jnp.int32, (T, kv_heads * dv), 0)) < length
        mixed = jax.lax.dot_general(pexp.astype(values.dtype), jnp.where(keep, values, jnp.zeros_like(values)),
                                    (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)   # (H, KV x Dv)
        pv = jnp.zeros(acc_scr.shape, jnp.float32)
        for k in range(kv_heads):                       # a row's own key head's lanes: whole tiles
            pv = jnp.where(row_head == k, mixed[:, k * dv:(k + 1) * dv], pv)
        acc_scr[...] = acc_scr[...] * alpha + pv
        return nxt

    cur_ref[0] = jax.lax.fori_loop(0, n_blocks, block_body, cur_ref[0])
    m, l, acc = m_scr[...], l_scr[...], acc_scr[...]
    if has_sink:
        # one more column, of no value: it joins the maximum and the denominator (a slot of length 0: all of the mass)
        m_all = jnp.maximum(m, sink_ref[...])
        shrink = jnp.exp(m - m_all)
        l, acc = l * shrink + jnp.exp(sink_ref[...] - m_all), acc * shrink
    o_ref[0] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@kernels.with_xla_leg(folded_attention_xla, static_argnames=("scale", "interpret"))
def paged_decode_folded(q, k_pool, v_pool, table, lengths, *, layer, scale, sink=None, interpret):
    """One decode-step attention of one layer over FOLDED pools: keys of one
    width, values of another, an optional sink.

    ``q``: (S, H, Dk) in the pools' type; ``k_pool``: (L, N, page, 1, KV x Dk)
    and ``v_pool``: (L, N, page, 1, KV x Dv), every layer, a position's row
    every key head's entries side by side (query head ``h`` reads key head ``h
    // (H / KV)``); ``sink``: None or (H,) float32, one logit a query head that
    enters the softmax's maximum and denominator and mixes no value;
    ``table``, ``lengths``, ``layer``, ``interpret`` as :func:`paged_decode`
    takes them (None: the XLA leg; :func:`leg_folded` resolves it).  Returns
    float32 (S, H, Dv): ``sum_t p_t v_t`` with ``p_t = exp(s_t - m) / (sum_t
    exp(s_t - m) + exp(sink - m))`` over the slot's first ``lengths`` positions
    (zeros for a length of 0, with a sink or without).  The kernel is named by
    its key heads in a device trace (``paged_decode_kv4``)."""
    S, H, dk = q.shape
    kv, dv = _folded_heads(q, k_pool, v_pool)
    L, N, page = k_pool.shape[:3]
    if not supports_folded(k_pool.dtype, kv, dk, dv, page, interpret=bool(interpret)):
        raise ValueError(f"paged_decode_folded takes no {k_pool.dtype} pools of {kv} heads x {dk} | {dv} in pages of {page} "
                         "(see supports_folded())")
    Pmax = table.shape[1]
    bp = _block_pages(Pmax, page, kv, dk, jnp.dtype(k_pool.dtype).itemsize)
    spread = jnp.tile(jnp.eye(dk, dtype=k_pool.dtype), (1, kv))                # (Dk, KV x Dk): a row into every head's lanes
    whole = lambda *shape: pl.BlockSpec(shape, lambda s, *_: (0,) * len(shape))
    sinks = () if sink is None else (sink.astype(jnp.float32).reshape(H, 1),)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S,),
        in_specs=[pl.BlockSpec((1, H, dk), lambda s, *_: (s, 0, 0)), whole(dk, kv * dk), *(whole(H, 1) for _ in sinks),
                  pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, H, dv), lambda s, *_: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, bp, page, kv * dk), k_pool.dtype),
            pltpu.VMEM((2, bp, page, kv * dv), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, dv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_folded_kernel, scale=float(scale), page=page, block_pages=bp, kv_heads=kv, group=H // kv, dk=dk,
                          dv=dv, has_sink=sink is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=f"paged_decode_kv{kv}",
    )(jnp.asarray(layer, jnp.int32).reshape(1), jnp.clip(lengths.astype(jnp.int32), 0, Pmax * page),
      table.astype(jnp.int32), q, spread, *sinks, k_pool.reshape(L, N, page, kv * dk), v_pool.reshape(L, N, page, kv * dv))
