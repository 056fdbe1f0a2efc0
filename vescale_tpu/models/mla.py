"""Multi-head LATENT attention (MLA), the block two families behind
``serve.HybridServeEngine`` share (``models/deepseek_v2.py``,
``models/longcat_flash.py``): written once, as pure functions over one
attention's parameter tree and a :class:`LatentAttention` that holds the
block's numbers.  What differs between the families is in that object and
nowhere else: the rotary frequencies (YaRN's, or the plain ones of a theta) and
the multiplier on their cos / sin, the score scale, the head count, and the two
multipliers some sources put on the normed low-rank activations.  No family is
named here and none is imported; a family's file builds its
:class:`LatentAttention` from its own config.

Equations (HF ``modeling_deepseek.py``; ISSUE 34 and ISSUE 54 write them out)::

    c_q = s_q norm(W_qa u);  q_h = W_qb,h c_q = q_nope,h | q_pe,h       (``q_lora_rank`` None: q_h = W_q,h u, no norm)
    W_kva u = c_kv | k_pe (one for all heads);  c = s_kv norm(c_kv)
    rotary on q_pe,h and k_pe, over interleaved pairs, at ``inv_freq``

  * **expanded** (prefill; what the sources compute): ``k_h = W_uk,h c | k_pe``,
    ``v_h = W_uv,h c``, causal softmax at ``softmax_scale``, ``o = W_o
    concat_h(P_h v_h)``: scores ``qk_head_dim`` wide, values ``v_head_dim`` wide,
    through the blocked flash forward;
  * **absorbed** (decode; the same numbers): ``q'_h = W_uk,h^T q_nope,h | q_pe,h``,
    ``s_h,t = q'_h . row_t``, ``u_h = sum_t p_h,t c_t``, ``o = W_o concat_h(W_uv,h
    u_h)``: every head reads ONE row a position.

The cache row of a position is ``c_t | k_pe,t`` (after the norm and its
multiplier, after the rotary): ``latent_row`` numbers in ``dtype``, padded with
zeros to ``cache_row`` (whole 128-lane tiles: what the chip's layout of such a
row occupies anyway), in a paged cache of the latent form
(``serve/kv_cache.py``): one pool, no value pool.  ``kv_b`` is kept as its two
halves in the layouts the absorbed products read (``kv_b_k`` (H, nope, C),
``kv_b_v`` (H, C, v)); the expanded form multiplies by the same two arrays, so
there is no second copy.  ``head_gate`` (rows, H), where a family gives one, weighs
each head's value output before ``W_o`` (a source's head-wise output gate: the
family computes it, from whatever it is a function of).

Precision: weights and matmul operands ``dtype`` (bfloat16) with float32
accumulation; norms, multipliers, rotary and softmax float32; a cached row is
rounded to the pool's type once.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .blocks import F32, _mm, rmsnorm

__all__ = ["LatentAttention", "LANES", "FLASH_NAME", "attention_params", "rotary", "mla_prefill", "mla_step", "cache_config",
           "decode_kernel", "latent_bytes_read", "prefill_attn_flops"]

LANES = 128
FLASH_NAME = "mla_flash_fwd"        # the prefill attention's kernel, as the device trace names it


@dataclasses.dataclass(frozen=True, eq=False)
class LatentAttention:
    """One latent-attention block's numbers, as a family's config gives them."""

    hidden_size: int
    num_attention_heads: int
    q_lora_rank: Optional[int]          # None: no query LoRA, ``q = W_q u`` (the tree has ``q`` where it had ``q_a``, ``q_a_norm``, ``q_b``)
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    inv_freq: np.ndarray                # the rotary frequencies (qk_rope_head_dim / 2,), float32
    softmax_scale: float
    rms_norm_eps: float
    dtype: Any                          # weights, matmul operands and the cache's rows
    cos_scale: float = 1.0              # on the rotary term's cos and sin (YaRN: mscale / mscale_all_dim)
    q_scale: float = 1.0                # on the normed c_q (a source's ``mla_scale_q_lora``)
    kv_scale: float = 1.0               # on the normed latent, not on k_pe (``mla_scale_kv_lora``)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_row(self) -> int:
        """What a position leaves in the cache: the latent and the one rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_row(self) -> int:
        """... as the pool keeps it: padded with zeros to whole lane tiles."""
        return -(-self.latent_row // LANES) * LANES


def attention_params(a: LatentAttention, key, gains: Optional[Mapping[str, float]] = None) -> Dict[str, Any]:
    """One attention's seeded weights in ``a.dtype``, normal with variance 1 /
    fan-in; ``gains`` widens or narrows a matrix by name (a family's init rule)."""
    E, H, dt, gains = a.hidden_size, a.num_attention_heads, a.dtype, gains or {}

    def normal(k, shape, fan_in, name=None):
        return (jax.random.normal(k, shape, F32) * (gains.get(name, 1.0) / math.sqrt(fan_in))).astype(dt)

    ks = jax.random.split(key, 6)
    if a.q_lora_rank is None:
        queries = {"q": normal(ks[1], (E, H * a.qk_head_dim), E, "q")}
    else:
        queries = {"q_a": normal(ks[0], (E, a.q_lora_rank), E),
                   "q_a_norm": jnp.ones((a.q_lora_rank,), dt),
                   "q_b": normal(ks[1], (a.q_lora_rank, H * a.qk_head_dim), a.q_lora_rank, "q_b")}
    return {**queries,
            "kv_a": normal(ks[2], (E, a.latent_row), E),
            "kv_a_norm": jnp.ones((a.kv_lora_rank,), dt),
            # kv_b's two halves, a head at a time: W_uk (H, nope, C) and W_uv (H, C, v)
            "kv_b_k": normal(ks[3], (H, a.qk_nope_head_dim, a.kv_lora_rank), a.kv_lora_rank, "kv_b_k"),
            "kv_b_v": normal(ks[4], (H, a.kv_lora_rank, a.v_head_dim), a.kv_lora_rank, "kv_b_v"),
            "o": normal(ks[5], (H * a.v_head_dim, E), H * a.v_head_dim)}


def rotary(a: LatentAttention, x, positions):
    """Rotate the interleaved pairs ``(2i, 2i+1)`` of ``x`` (..., dim) by
    ``positions`` times ``a.inv_freq``, in place: ``positions`` broadcasts
    against ``x``'s leading axes with one axis of size 1 added for ``dim`` (a
    (T,) vector for ``x`` (T, dim); ``positions[:, None]`` for (T, H, dim);
    ``positions[None, :]`` for (H, T, dim)).  The sources first bring the pairs
    to halves and their result stays so; queries and keys get the same order, so
    scores do not see it, and keeping the pairs where they are needs no strided
    access: ``x cos + (x R) sin`` with ``R`` the (dim, dim) matrix that takes
    each pair ``(a, b)`` to ``(-b, a)`` (entries 0 and +-1: the product is
    exact).  Float32.  The cos/sin multiplier ``a.cos_scale`` is applied as it
    stands."""
    dim = x.shape[-1]
    angle = positions.astype(F32)[..., None] * jnp.asarray(np.repeat(a.inv_freq, 2))      # (..., dim)
    m = a.cos_scale
    turn = np.zeros((dim, dim), np.float32)
    turn[np.arange(1, dim, 2), np.arange(0, dim, 2)] = -1.0
    turn[np.arange(0, dim, 2), np.arange(1, dim, 2)] = 1.0
    x = x.astype(F32)
    turned = jnp.dot(x, jnp.asarray(turn), precision=jax.lax.Precision.HIGHEST)
    return x * (jnp.cos(angle) * m) + turned * (jnp.sin(angle) * m)


def _scaled(x, scale: float):
    """``x`` times a LoRA multiplier, in float32; a multiplier of 1 is no operation."""
    return x if scale == 1.0 else x * scale


def _queries(a: LatentAttention, ap, u, positions, *, head_major: bool = False):
    """``q_nope`` (T, H, nope) and the rotated ``q_pe`` (T, H, rope) in the
    operands' type; ``head_major``: (H, T, .), as the flash forward reads
    them, straight from the product."""
    H = a.num_attention_heads
    if a.q_lora_rank is None:
        cq, w = u.astype(a.dtype), ap["q"].astype(a.dtype).reshape(a.hidden_size, H, a.qk_head_dim)
    else:
        cq = _scaled(rmsnorm(_mm(u, ap["q_a"], a.dtype), ap["q_a_norm"], a.rms_norm_eps), a.q_scale).astype(a.dtype)
        w = ap["q_b"].astype(a.dtype).reshape(a.q_lora_rank, H, a.qk_head_dim)
    q = jnp.einsum("tr,rhd->htd" if head_major else "tr,rhd->thd", cq, w, preferred_element_type=F32).astype(a.dtype)
    where = positions[None, :] if head_major else positions[:, None]
    return q[..., : a.qk_nope_head_dim], rotary(a, q[..., a.qk_nope_head_dim:], where).astype(a.dtype)


def _latent_rows(a: LatentAttention, ap, u, positions):
    """The cache rows of ``u``'s positions (T, cache_row) in ``a.dtype``:
    the normed latent under its multiplier, the rotated key, the zero pad."""
    kv = _mm(u, ap["kv_a"], a.dtype)
    latent = _scaled(rmsnorm(kv[:, : a.kv_lora_rank], ap["kv_a_norm"], a.rms_norm_eps), a.kv_scale)
    k_pe = rotary(a, kv[:, a.kv_lora_rank:], positions)
    pad = jnp.zeros((u.shape[0], a.cache_row - a.latent_row), F32)
    return jnp.concatenate([latent, k_pe, pad], axis=-1).astype(a.dtype)


def _gated(y, head_gate, heads_axis: int):
    """``y`` with its heads on ``heads_axis`` and rows on the other leading axis,
    each head's output times its gate (rows, H) in float32; None: ``y`` as it is."""
    if head_gate is None:
        return y
    gate = head_gate.astype(F32) if heads_axis == 1 else head_gate.astype(F32).T
    return y.astype(F32) * gate[..., None]


def mla_prefill(a: LatentAttention, ap, u, *, interpret: Optional[bool] = None, head_gate=None):
    """The EXPANDED form over one sequence ``u`` (T, E) from position 0:
    per-head keys and values from the latent, causal attention with scores
    ``qk_head_dim`` wide and values ``v_head_dim`` wide through the blocked
    flash forward (no (T, T) tensor).  ``head_gate`` (T, H), where given, weighs
    each head's output before ``W_o``.  Returns the output (T, E) and the
    positions' cache rows (T, cache_row).  Pad positions follow the real ones,
    so causality keeps them out."""
    from ..ops.flash_attention import flash_attention_forward

    T, H = u.shape[0], a.num_attention_heads
    positions = jnp.arange(T, dtype=jnp.int32)
    q_nope, q_pe = _queries(a, ap, u, positions, head_major=True)
    rows = _latent_rows(a, ap, u, positions)
    latent, k_pe = rows[:, : a.kv_lora_rank], rows[:, a.kv_lora_rank: a.latent_row]
    # head-major (H, T, .) as the kernel reads them, in the operands' type straight from the products
    k_nope = jnp.einsum("tc,hdc->htd", latent, ap["kv_b_k"].astype(a.dtype), preferred_element_type=F32).astype(a.dtype)
    v = jnp.einsum("tc,hcd->htd", latent, ap["kv_b_v"].astype(a.dtype), preferred_element_type=F32).astype(a.dtype)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe[None], (H, T, a.qk_rope_head_dim))], axis=-1)
    y = flash_attention_forward(q, k, v, scale=a.softmax_scale, interpret=interpret, name=FLASH_NAME)
    y = _gated(y, head_gate, 0).astype(y.dtype)
    return jnp.einsum("htd,hde->te", y, ap["o"].astype(a.dtype).reshape(H, a.v_head_dim, a.hidden_size),
                      preferred_element_type=F32), rows


def mla_step(a: LatentAttention, ap, u, pool, *, layer: int, table, page, offset, positions, valid_len,
             interpret: Optional[bool], head_gate=None):
    """The ABSORBED form, one new position a slot: ``u`` (S, E); the
    position's row goes to ``(page, offset)`` of the pool's ``layer`` (the
    null page for a slot that may not write), then
    ``kernels.paged_decode_latent`` reads the slot's pages with the
    ``latent_row``-wide absorbed queries (``interpret``: the kernel's flag, or
    None for its XLA leg); ``head_gate`` (S, H) as :func:`mla_prefill`'s.
    Returns the output (S, E) and the pool."""
    from ..kernels.paged_attention import paged_decode_latent

    S, H = u.shape[0], a.num_attention_heads
    q_nope, q_pe = _queries(a, ap, u, positions)
    pool = pool.at[layer, page, offset, 0].set(_latent_rows(a, ap, u, positions).astype(pool.dtype))
    # the heads lead both operands of the absorbed products (a batch axis elsewhere the CPU's runtime refuses in bfloat16)
    q_abs = jnp.einsum("hsd,hdc->hsc", q_nope.transpose(1, 0, 2), ap["kv_b_k"].astype(a.dtype),
                       preferred_element_type=F32).transpose(1, 0, 2)
    pad = jnp.zeros((S, H, a.cache_row - a.latent_row), pool.dtype)
    q = jnp.concatenate([q_abs.astype(pool.dtype), q_pe.astype(pool.dtype), pad], axis=-1)
    mixed = paged_decode_latent(q, pool, table, valid_len, layer=layer, scale=a.softmax_scale, latent=a.kv_lora_rank,
                                interpret=interpret)
    y = jnp.einsum("hsc,hcd->hsd", mixed.astype(a.dtype).transpose(1, 0, 2), ap["kv_b_v"].astype(a.dtype),
                   preferred_element_type=F32).transpose(1, 0, 2)
    return _mm(_gated(y, head_gate, 1).reshape(S, H * a.v_head_dim), ap["o"], a.dtype), pool


# ------------------------------------- the block's side of the serve engine's seam
def cache_config(a: LatentAttention, *, layers: int, num_slots: int, page_size: int, pages_per_slot: int,
                 num_pages: Optional[int] = None):
    """A latent pool of ``layers`` attentions' rows (a model whose layer holds
    two attentions gives twice its depth), no value pool, no slot state."""
    from ..serve.kv_cache import KVCacheConfig

    return KVCacheConfig(layers=layers, kv_heads=1, head_dim=a.cache_row, num_slots=num_slots, page_size=page_size,
                         pages_per_slot=pages_per_slot, num_pages=num_pages, dtype=a.dtype, latent=True)


def decode_kernel(a: LatentAttention, cache) -> Optional[bool]:
    """The ``interpret`` flag of ``paged_decode_latent`` over ``cache``, or None for its XLA leg."""
    from ..kernels import paged_attention

    return paged_attention.leg_latent(cache.k.data.dtype, a.cache_row, a.kv_lora_rank, cache.config.page_size)


def latent_bytes_read(a: LatentAttention, cache, lengths: np.ndarray, layers: int) -> int:
    """The latent pages one decode step's attention had to read: live pages x page bytes x ``layers`` attentions."""
    kc = cache.config
    live = int(np.minimum(-(-(lengths + 1) // kc.page_size), kc.pages_per_slot)[lengths > 0].sum())
    return live * kc.page_size * kc.head_dim * jnp.dtype(a.dtype).itemsize * layers


def prefill_attn_flops(a: LatentAttention, bucket: int, layers: int) -> int:
    """Causal attention's useful operations over ``bucket`` positions at the real
    widths (scores and values, half the square), ``layers`` attentions."""
    per_pair = 2 * (a.qk_head_dim + a.v_head_dim)
    return a.num_attention_heads * per_pair * bucket * bucket // 2 * layers
