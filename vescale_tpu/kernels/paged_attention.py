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
:func:`leg_latent` resolve that for a pool: a caller names its pool and chooses nothing.
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
           "supports_latent", "leg_latent"]

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
    want whole 128-lane rows.  The engine takes its XLA leg otherwise."""
    dt = jnp.dtype(pool_dtype)
    if not (dt.itemsize == 4 or (dt == jnp.bfloat16 and kv_heads % 2 == 0)):
        return False
    return interpret or head_dim % 128 == 0


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
# ``latent`` columns.  So a block of pages is fetched once (one DMA a page, as
# above) and serves both products: all ``H`` absorbed queries against the
# block's rows through the MXU for the scores, the probabilities against the
# same rows' leading columns for the mix.  With 128 heads on one row that is
# about 240 operations a byte read: the v5e's ridge, where the kernel above
# (one or four heads a row) sits far on the memory side.
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
                   buf, sems, cur_ref, m_scr, l_scr, acc_scr, *, scale, page, block_pages, latent):
    """Grid (S,), sequential; the buffers alternate over the whole call as in ``_decode_kernel``."""
    s = pl.program_id(0)
    n_slots = pl.num_programs(0)
    T = block_pages * page
    row = buf.shape[-1]
    layer = layer_ref[0]

    def block_dma(slot, block, b, wait):
        first = block * block_pages
        live = jnp.clip(pl.cdiv(len_ref[slot], page) - first, 0, block_pages)

        def one_page(i, carry):
            copy = pltpu.make_async_copy(pool_hbm.at[layer, table_ref[slot, first + i]], buf.at[b, i], sems.at[b])
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
    q = q_ref[0]                                                       # (H, row), the pool's type

    def block_body(b, cur):
        nxt = 1 - cur

        @pl.when(b + 1 < n_blocks)
        def _():
            block_dma(s, b + 1, nxt, wait=False)

        @pl.when(jnp.logical_and(b + 1 == n_blocks, s + 1 < n_slots))
        def _():
            block_dma(s + 1, 0, nxt, wait=False)

        block_dma(s, b, cur, wait=True)
        rows = buf[cur].reshape(T, row)
        # rows past the length are stale pool bytes, or VMEM a skipped DMA never wrote: zeroed, and their scores masked
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
        return nxt

    cur_ref[0] = jax.lax.fori_loop(0, n_blocks, block_body, cur_ref[0])
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
    bp = _block_pages(Pmax, page, 1, row, jnp.dtype(pool.dtype).itemsize)
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
        functools.partial(_latent_kernel, scale=float(scale), page=page, block_pages=bp, latent=latent),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, latent), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_latent",
    )(jnp.asarray(layer, jnp.int32).reshape(1), jnp.clip(lengths.astype(jnp.int32), 0, Pmax * page),
      table.astype(jnp.int32), q, pool.reshape(L, N, page, row))
