"""Paged-attention decode kernel — K/V read straight from the page pool.

The PR-10 serve decode step ran, per layer, as four separate XLA ops over
the WHOLE page pool: scatter the new token's K/V into its page, gather
every slot's pages into a dense (S, Tmax, KV, hd) view, masked fp32
softmax over Tmax, then the value matmul.  The gather alone materializes
``S * Tmax`` K/V rows in HBM per layer per token — the single biggest
serving-throughput lever named by ROADMAP item 1.

This kernel (PagedAttention-style, vLLM lineage) replaces the
gather → softmax → matmul chain with ONE kernel: the per-slot page table
and length vector ride in as scalar-prefetch operands, so the BlockSpec
index map addresses the K/V **page pool directly** — grid step ``(s, p)``
DMAs physical page ``table[s, p]`` into VMEM (the null page 0 for unused
entries), and an online fp32 softmax accumulates across the slot's pages
in VMEM scratch.  Nothing dense is ever materialized: HBM traffic is one
read of the pages the slot actually references plus the (S, H, hd) q/out
rows.  The cache write of the new token's K/V stays the single scatter it
always was — it IS the persistence op, not part of attention.

GQA runs natively: q heads are grouped per kv head inside the kernel
(``H = KV * G``) and scores are computed as a (KV,)-batched matmul, so
repeated K/V heads are never materialized.

Numerics: fp32 scores/softmax/accumulation exactly like the XLA
reference; the accumulation ORDER differs (online per-page vs one full-row
softmax), so parity is ulp-bounded rather than bitwise — the bound is
asserted in tests/test_kernels.py and documented in docs/kernels.md.
Fully-masked rows (inactive slots never have them: length >= 1) divide by
a guarded 1.0 like the flash kernels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_decode"]

_NEG_INF = -1e30


def _decode_kernel(table_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale, page, kv_heads, group):
    """Grid (S, Pmax): slot-major, pages fastest (TPU grids run
    sequentially, so the online-softmax state in scratch carries across a
    slot's pages).  ``table_ref``/``len_ref`` are the scalar-prefetch
    operands — the same arrays whose values the k/v index maps read."""
    s = pl.program_id(0)
    p = pl.program_id(1)
    n_pages = pl.num_programs(1)

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    # (H, hd) -> (KV, G, hd): q heads of kv group g are rows [g*G, (g+1)*G)
    qg = (q_ref[0].astype(jnp.float32) * scale).reshape(kv_heads, group, -1)
    k = jnp.transpose(k_ref[0].astype(jnp.float32), (1, 0, 2))  # (KV, page, hd)
    v = jnp.transpose(v_ref[0].astype(jnp.float32), (1, 0, 2))
    # (KV, G, page) scores: batched over kv heads, contracted over hd
    sc = jax.lax.dot_general(
        qg, k, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
    )
    pos = p * page + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 2)
    sc = jnp.where(pos < len_ref[s], sc, _NEG_INF)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1))
    pexp = jnp.exp(sc - m_new[..., None])
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(pexp, axis=-1)
    acc_scr[...] = acc_scr[...] * alpha[..., None] + jax.lax.dot_general(
        pexp, v, (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new

    @pl.when(p == n_pages - 1)
    def _final():
        l = l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        out = acc_scr[...] / l_safe[..., None]  # (KV, G, hd)
        o_ref[0] = out.reshape(kv_heads * group, -1).astype(o_ref.dtype)


def paged_decode(q, k_pool, v_pool, table, lengths, *, scale, interpret):
    """One decode-step attention over the paged KV pool.

    ``q``: (S, H, hd) new-token queries; ``k_pool``/``v_pool``:
    (N, page, KV, hd) ONE layer's physical page pool; ``table``:
    (S, Pmax) int32 physical page ids per slot (0 = the reserved null
    page); ``lengths``: (S,) int32 valid positions per slot (the new token
    included).  Returns fp32 (S, H, hd) attention output — callers reshape
    and cast (the XLA reference's ``.astype(dtype)`` boundary).

    Implementation-only: the caller (serve/engine.py) owns the dispatch
    decision and any shard_map wrapping for a kv-head-sharded pool.
    """
    S, H, hd = q.shape
    N, page, KV, hd2 = k_pool.shape
    assert hd == hd2 and H % KV == 0, (q.shape, k_pool.shape)
    Pmax = table.shape[1]
    G = H // KV
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, Pmax),
        in_specs=[
            pl.BlockSpec((1, H, hd), lambda s, p, t, L: (s, 0, 0)),
            pl.BlockSpec((1, page, KV, hd), lambda s, p, t, L: (t[s, p], 0, 0, 0)),
            pl.BlockSpec((1, page, KV, hd), lambda s, p, t, L: (t[s, p], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, hd), lambda s, p, t, L: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, G), jnp.float32),
            pltpu.VMEM((KV, G), jnp.float32),
            pltpu.VMEM((KV, G, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _decode_kernel, scale=float(scale), page=page, kv_heads=KV, group=G
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, hd), jnp.float32),
        interpret=interpret,
    )(table.astype(jnp.int32), lengths.astype(jnp.int32), q, k_pool, v_pool)
