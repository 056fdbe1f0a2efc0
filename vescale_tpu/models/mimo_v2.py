"""MiMo-V2 (``mimo_v2``): window and full attention mixed, with KEYS WIDER THAN
VALUES and a learned SINK in every window layer's softmax, over many routed
experts chosen under a selection bias.

``hybrid_layer_pattern`` says which a layer is (published: 0, a full layer, then
five of 1, sliding-window layers).  Both kinds have ``num_attention_heads``
query heads with keys of ``head_dim`` (192) and values of ``v_head_dim`` (128);
a full layer has ``num_key_value_heads`` (4) key heads and rotates at
``rope_theta`` (1e7), a window layer ``swa_num_key_value_heads`` (8), rotates at
``swa_rope_theta`` (1e4), sees the ``sliding_window`` (128) newest positions,
itself among them, and carries one learned sink logit a query head: a column of
the softmax that takes mass and gives no value.  Only the first ``int(head_dim x
partial_rotary_factor)`` (64) entries of a head are rotated; values are scaled
by ``attention_value_scale`` (0.707).  ``moe_layer_freq`` says which layers are
dense SwiGLUs (layer 0) and which score all ``num_experts`` by a float32
sigmoid, keep the ``num_experts_per_tok`` largest of ``sigmoid + c`` (``c`` the
selection bias, ``e_score_correction_bias``: it chooses, it does not weigh) and
renormalise the kept sigmoids (``moe.dropless.route_sigmoid_topk``); no shared
expert, no scaling factor.  Pre-norm residual layers, a final norm, an untied
head.

    u = rmsnorm(x);  q = u Wq (T, H, Dk);  k = u Wk (T, KV_l, Dk);  v = scale_v (u Wv) (T, KV_l, Dv)
    q[..., :rot], k[..., :rot] rotated at theta_l (half-split pairs); the rest passes
    s_ij = q_i . k_j / sqrt(Dk), kept where j <= i, on a window layer also i - j < window
    full:    p_ij = exp(s_ij - m_i) / sum_j exp(s_ij - m_i)
    window:  m_i = max(max_j s_ij, b_h);  p_ij = exp(s_ij - m_i) / (sum_j exp(s_ij - m_i) + exp(b_h - m_i))
    a_i = sum_j p_ij v_j (T, H, Dv);  x += a Wo
    h = rmsnorm(x);  dense: x += SwiGLU(h)
    sparse:  P = sigmoid(h Wr);  I = top-k of P + c;  w = P_I / sum(P_I);  x += sum_{e in I, held} w_e E_e(h)

What the published config does not settle is read as ISSUE 50 wrote it down (the
configuration file lists each under ``assumed``): the rotated part is a head's
FIRST entries in the half-split pairing (``blocks.rotary``'s), the score scale
is ``head_dim ** -0.5``, queries and keys carry no per-head norm and the output
no gate.

**The cache** (``serve/kv_cache.py``).  Neither 192 nor 4 x 192 lays out on the
chip as ``(key heads, head_dim)`` rows without padding, so both kinds of store
are FOLDED (``KVCacheConfig.folded``): a position's row is every key head's
entries side by side.  The full layers keep PAGES, ``cache.k`` ``(full layers,
pages, page, 1, 4 x 192)`` beside ``cache.v`` ``(..., 1, 4 x 128)``: 2,560 B a
position and layer.  A window layer keeps a RING a slot (``slot_state``
``ring_k`` ``(window layers, slots, window, 1, 8 x 192)``, ``ring_v`` ``(...,
1, 8 x 128)``): position ``p`` at ring row ``p mod window`` (:func:`ring_row`);
keys are cached after the rotary term and values after the scale, so a row's
order in the ring does not matter to a softmax.  A decode step writes the new
position's row and reads the ring THROUGH ``kernels.paged_decode_folded``, the
ring viewed as pages under the arithmetic table ``table[s, j] = s window / page
+ j`` with lengths ``min(length + 1, window)`` and the layer's sink; the pages
go through the same op without one.  A prefill runs the flash forward (windowed
with the sink, or causal; keys 192 against values 128 in both) and rewrites the
slot's ring from the last ``min(n, window)`` REAL positions
(:func:`ring_source`).  Admission counts the full layers' pages alone; a ring
keeps no history, so ``cache.refuse_slot_state`` refuses prefix sharing,
speculation and rollback.

Layers are NOT stacked under one scan (``Wk`` and ``Wv`` differ in shape by
kind).  Precision: weights and matmul operands ``config.dtype`` (bfloat16) with
float32 accumulation; residual stream, norms, rotary, sinks, router, ``c`` and
softmax float32; K and V are rounded to the cache's type once, V after its
scale.  A chip's share: ``num_experts`` is what the router scores,
``experts_held`` / ``first_expert_held`` which of them this tree holds; a row
whose experts all live elsewhere gets zero from the layer.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..moe.dropless import route_sigmoid_topk, routed_experts
from .blocks import F32, ROUTED_DOWN_GAIN, _mm, ring_row, ring_source, rmsnorm, rotary, swiglu, window_pairs, write_position

__all__ = [
    "MimoV2Config", "init_params", "embed", "head", "attention_prefill", "attention_step", "ring_row", "ring_source",
    "mlp", "expert_layer", "layer_prefill", "layer_step", "cache_config", "prefill_chunk", "decode_kernels",
    "serve_prefill", "serve_decode", "STEP_COUNTERS", "step_counters", "prefill_counters", "window_pairs", "FULL", "SWA",
    "SCORE_DEVIATION", "SINK_BELOW", "SINK_SPREAD", "BIAS_DEVIATION", "HELD_DOWN_GAIN",
]

FULL, SWA = 0, 1        # ``hybrid_layer_pattern``'s two values
# The init rule (the configuration's ``assumed.init``).  ``Wq`` and ``Wk`` are drawn wider than variance 1 / fan-in
# by as much as gives the scores ``q . k / sqrt(head_dim)`` THIS deviation (the rotary term turns pairs and changes
# no length, so both kinds take the same gain, ``SCORE_DEVIATION ** 0.5`` each): with unit scores the softmax over
# a hundred positions is flat, and a window one position short, or no sink, read the same to rounding
# (``models/laguna.py`` has the readings the rule was set by).
SCORE_DEVIATION = 2.0
# A sink is drawn uniformly between ``SINK_BELOW + SINK_SPREAD`` and ``SINK_BELOW - SINK_SPREAD`` UNDER the logarithm
# of a full window's expected softmax denominator, ``log(window) + SCORE_DEVIATION ** 2 / 2`` (the mean of ``window``
# log-normal terms): it then holds between a tenth and a half of a full window's mass (0.14 to 0.40 on the mean at 128
# positions), as a trained sink does, and "no sink" is no rounding error.
SINK_BELOW, SINK_SPREAD = 1.25, 0.75
# The selection bias ``c``: within every ``experts_held`` contiguous experts (one chip's share) the SAME multiset, the
# quantiles of a normal of this deviation, in an order the seed draws.  The kept sigmoids lie within 0.02 of 1, so a
# bias of this size changes the kept set of nearly every token (2.8 of 8 on the mean) while every share, and so
# every seed, holds the same biases: which experts a bias starves does not depend on the seed.
BIAS_DEVIATION = 0.02
# The routed experts' down projections: ``blocks.ROUTED_DOWN_GAIN`` is reckoned for a tree that holds every expert.
# Here a sixteenth of them is held and a token reaches half an expert on the mean, so at that gain the whole routed
# part is a thousandth of the stream and no fault of the router could show; twelve times wider, one (token, expert)
# pair moves a logit row by about the size of rounding and a kept set chosen without the bias by several times that
# (read on the chip at the published widths, PERF.md section 6, PR 50: at eight times the sound program read 8.0e-3
# of the largest logit, one kept expert fewer 9.8e-3 and no selection bias 1.7e-2, too close over a limit of 1.5e-2).
HELD_DOWN_GAIN = 12.0 * ROUTED_DOWN_GAIN


@dataclasses.dataclass(frozen=True)
class MimoV2Config:
    vocab_size: int = 152576            # rows of the embedding and of the (untied) head
    hidden_size: int = 4096
    num_hidden_layers: int = 48
    hybrid_layer_pattern: Tuple[int, ...] = ((FULL,) + (SWA,) * 4) + ((FULL,) + (SWA,) * 5) * 7 + (FULL,)
    num_attention_heads: int = 64       # both kinds
    head_dim: int = 192                 # keys and queries, both kinds
    v_head_dim: int = 128               # values, both kinds
    num_key_value_heads: int = 4        # a full layer's
    swa_num_key_value_heads: int = 8    # a window layer's
    sliding_window: int = 128
    partial_rotary_factor: float = 0.334
    rope_theta: float = 1e7
    swa_rope_theta: float = 1e4
    attention_value_scale: float = 0.707
    add_swa_attention_sink_bias: bool = True
    add_full_attention_sink_bias: bool = False
    moe_layer_freq: Tuple[int, ...] = (0,) + (1,) * 47
    intermediate_size: int = 16384      # the dense layers' SwiGLU
    moe_intermediate_size: int = 2048   # width of one routed expert
    num_experts: int = 256              # the router's outputs (``n_routed_experts``): every expert the model has
    num_experts_per_tok: int = 8
    experts_held: int = 256             # ... and the contiguous ids this tree holds
    first_expert_held: int = 0
    rms_norm_eps: float = 1e-5          # ``layernorm_epsilon``
    prefill_chunk: int = 128            # the prefill ladder's first rung: the flash forward's smallest whole tile
    dtype: Any = jnp.bfloat16           # weights, matmul operands, K and V

    def __post_init__(self):
        L = self.num_hidden_layers
        if not (len(self.hybrid_layer_pattern) == len(self.moe_layer_freq) == L):
            raise ValueError(f"hybrid_layer_pattern and moe_layer_freq name each of the {L} layers")
        if set(self.hybrid_layer_pattern) - {FULL, SWA} or set(self.moe_layer_freq) - {0, 1}:
            raise ValueError("a layer's attention is 0 (full) or 1 (sliding window) and its feed-forward 0 (dense) or 1 (experts)")
        if FULL not in self.hybrid_layer_pattern:
            raise ValueError("the paged pools belong to the full-attention layers: a model of window layers alone has "
                             "no page to admit by")
        if self.num_attention_heads % self.num_key_value_heads or self.num_attention_heads % self.swa_num_key_value_heads:
            raise ValueError("query heads come in whole groups a key head, on both kinds of layer")
        if self.rotated % 2 or not 0 < self.rotated <= self.head_dim:
            raise ValueError("the rotated part of a head is made of pairs, and is at most the head")
        if not (0 <= self.first_expert_held and self.first_expert_held + self.experts_held <= self.num_experts
                and self.experts_held > 0):
            raise ValueError(f"experts {self.first_expert_held}..{self.first_expert_held + self.experts_held} "
                             f"are not among the router's {self.num_experts}")
        if self.sliding_window < 1:
            raise ValueError("a window holds at least the position itself")

    @property
    def rotated(self) -> int:
        """The entries of a head that the rotary term turns: its first ``int(head_dim x partial_rotary_factor)``."""
        return int(self.head_dim * self.partial_rotary_factor)

    def layers_of(self, kind: int) -> Tuple[int, ...]:
        """The layers whose attention is ``kind``, in order: a full layer's place here is its layer of the pools,
        a window layer's its layer of the rings."""
        return tuple(l for l, t in enumerate(self.hybrid_layer_pattern) if t == kind)

    def kv_heads(self, kind: int) -> int:
        return self.swa_num_key_value_heads if kind == SWA else self.num_key_value_heads

    def theta(self, kind: int) -> float:
        return self.swa_rope_theta if kind == SWA else self.rope_theta

    def has_sink(self, kind: int) -> bool:
        return self.add_swa_attention_sink_bias if kind == SWA else self.add_full_attention_sink_bias


# ------------------------------------------------------------------ parameters
def selection_bias(config: MimoV2Config, key):
    """``c`` (num_experts,) float32 by the init rule (``BIAS_DEVIATION``): every share's quantiles, in a seeded order."""
    c = config
    held = c.experts_held
    if c.num_experts % held:
        raise ValueError(f"the init rule draws the selection bias a share at a time: {held} held does not divide {c.num_experts}")
    quantiles = jax.scipy.stats.norm.ppf((jnp.arange(held, dtype=F32) + 0.5) / held) * BIAS_DEVIATION
    order = jax.vmap(lambda k: jax.random.permutation(k, held))(jax.random.split(key, c.num_experts // held))
    return jnp.take(quantiles, order).reshape(c.num_experts).astype(F32)


def init_params(config: MimoV2Config, key) -> Dict[str, Any]:
    """Seeded random weights in the types they are served in (jit the call).
    Matrices are normal with variance 1 / fan-in, but for: the embedding (unit
    variance: the stream starts at the size the branches add to it); the router
    (float32) twice as wide and the routed experts' down projections
    ``HELD_DOWN_GAIN`` times as wide; ``Wq`` and ``Wk`` ``SCORE_DEVIATION **
    0.5`` times as wide each; the sinks and the selection bias by their rules
    (``SINK_BELOW``, ``BIAS_DEVIATION``)."""
    c, dt = config, config.dtype
    E, H, Dk, Dv = c.hidden_size, c.num_attention_heads, c.head_dim, c.v_head_dim

    def normal(k, shape, fan_in, dtype=dt, gain=1.0):
        return (jax.random.normal(k, shape, F32) * (gain / math.sqrt(fan_in))).astype(dtype)

    def attention(k, kind):
        ks = jax.random.split(k, 5)
        KV, gain = c.kv_heads(kind), SCORE_DEVIATION ** 0.5
        out = {"q_proj": normal(ks[0], (E, H * Dk), E, gain=gain), "k_proj": normal(ks[1], (E, KV * Dk), E, gain=gain),
               "v_proj": normal(ks[2], (E, KV * Dv), E), "o_proj": normal(ks[3], (H * Dv, E), H * Dv)}
        if c.has_sink(kind):
            middle = math.log(c.sliding_window) + SCORE_DEVIATION ** 2 / 2 - SINK_BELOW
            out["sink"] = middle + SINK_SPREAD * jax.random.uniform(ks[4], (H,), F32, -1.0, 1.0)
        return out

    def swiglu_params(k, width):
        ks = jax.random.split(k, 3)
        return {"gate": normal(ks[0], (E, width), E), "up": normal(ks[1], (E, width), E),
                "down": normal(ks[2], (width, E), width)}

    def moe(k):
        ks = jax.random.split(k, 5)
        F, held = c.moe_intermediate_size, c.experts_held
        return {"router": normal(ks[0], (E, c.num_experts), E, F32, gain=2.0), "router_bias": selection_bias(c, ks[4]),
                "w_gate": normal(ks[1], (held, E, F), E), "w_up": normal(ks[2], (held, E, F), E),
                "w_down": normal(ks[3], (held, F, E), F, gain=HELD_DOWN_GAIN)}

    params: Dict[str, Any] = {
        "embed_tokens": {"embedding": normal(jax.random.fold_in(key, 1 << 20), (c.vocab_size, E), 1.0)},
        "lm_head": {"kernel": normal(jax.random.fold_in(key, 1 << 21), (E, c.vocab_size), E)},
        "norm": {"weight": jnp.ones((E,), dt)},
    }
    for l in range(c.num_hidden_layers):
        k_attn, k_mlp = jax.random.split(jax.random.fold_in(key, l))
        params[f"layers_{l}"] = {
            "input_layernorm": {"weight": jnp.ones((E,), dt)},
            "post_attention_layernorm": {"weight": jnp.ones((E,), dt)},
            "self_attn": attention(k_attn, c.hybrid_layer_pattern[l]),
            "mlp": moe(k_mlp) if c.moe_layer_freq[l] else swiglu_params(k_mlp, c.intermediate_size),
        }
    return params


# ------------------------------------------------------------ embedding, head
def embed(config: MimoV2Config, params, tokens):
    return jnp.take(params["embed_tokens"]["embedding"], tokens, axis=0).astype(F32)


def head(config: MimoV2Config, params, x):
    """Next-token logits (float32)."""
    return _mm(rmsnorm(x, params["norm"]["weight"], config.rms_norm_eps), params["lm_head"]["kernel"], config.dtype)


# ------------------------------------------------------------------ attention
def _turned(c: MimoV2Config, x, positions, kind: int):
    """``x`` (N, heads, Dk) with its first ``c.rotated`` entries rotated at the kind's theta; the rest passes."""
    return jnp.concatenate([rotary(x[..., :c.rotated], positions, c.theta(kind)), x[..., c.rotated:].astype(F32)], axis=-1)


def _qkv(c: MimoV2Config, ap, u, positions, kind: int):
    """Queries (N, H, Dk) and keys (N, KV, Dk) after the rotary term, values (N,
    KV, Dv) after their scale, all in ``c.dtype`` (each rounded once)."""
    N, KV = u.shape[0], c.kv_heads(kind)
    q = _turned(c, _mm(u, ap["q_proj"], c.dtype).reshape(N, -1, c.head_dim), positions, kind)
    k = _turned(c, _mm(u, ap["k_proj"], c.dtype).reshape(N, KV, c.head_dim), positions, kind)
    v = c.attention_value_scale * _mm(u, ap["v_proj"], c.dtype).reshape(N, KV, c.v_head_dim)
    return q.astype(c.dtype), k.astype(c.dtype), v.astype(c.dtype)


def _out(c: MimoV2Config, ap, y):
    return _mm(y.reshape(y.shape[0], -1), ap["o_proj"], c.dtype)


def attention_prefill(c: MimoV2Config, ap, u, kind: int, *, interpret: Optional[bool] = None):
    """Attention of a layer of ``kind`` over one sequence ``u`` (T, E) from
    position 0, through the flash forward (keys ``head_dim`` wide against
    values ``v_head_dim``): causal on a full layer; on a window layer under the
    window, with the layer's sink.  Returns the output (T, E) and this layer's K
    (T, KV, Dk) and V (T, KV, Dv).  Pad positions follow the real ones, so
    causality keeps them out."""
    from ..ops.flash_attention import flash_attention

    q, k, v = _qkv(c, ap, u, jnp.arange(u.shape[0], dtype=jnp.int32), kind)
    y = flash_attention(q[None], k[None], v[None], causal=True, scale=c.head_dim ** -0.5, interpret=interpret,
                        window=c.sliding_window if kind == SWA else None, sink=ap.get("sink"))[0]
    return _out(c, ap, y), k, v


def _folded(a):
    """(N, KV, D) rows as a folded store holds them: (N, 1, KV x D)."""
    return a.reshape(a.shape[0], 1, -1)


def attention_step(c: MimoV2Config, ap, u, kind: int, k_store, v_store, *, layer: int, table, write, positions,
                   valid_len, interpret: Optional[bool]):
    """One new position a slot, at ``positions`` (S,): its K and V go, folded,
    to ``write`` of the stores' ``layer`` (a full layer: ``(page, offset)`` of
    the pools, the null page for a slot that may not write; a window layer: the
    ring's row, as a page of the rings read as pages), then
    ``kernels.paged_decode_folded`` reads through ``table`` with the layer's
    sink, on the leg ``interpret`` names.  Returns the output (S, E) and both
    stores."""
    from ..kernels.paged_attention import paged_decode_folded

    q, k, v = _qkv(c, ap, u, positions, kind)
    k_store, v_store = write_position(k_store, v_store, _folded(k), _folded(v), (layer,) + write)
    y = paged_decode_folded(q, k_store, v_store, table, valid_len, layer=layer, scale=c.head_dim ** -0.5,
                            sink=ap.get("sink"), interpret=interpret)
    return _out(c, ap, y), k_store, v_store


# -------------------------------------------------------------- feed-forward
def mlp(c: MimoV2Config, mp, h):
    """The dense layers' SwiGLU."""
    return swiglu(h, mp["gate"], mp["up"], mp["down"], c.dtype)


def expert_layer(c: MimoV2Config, ep, h, token_mask=None):
    """``sum over kept and held e of w_e E_e(h)`` for tokens ``h`` (N, E): the
    router's scores in float32, sigmoid routing under the selection bias
    (``route_sigmoid_topk``), the dropless layer over the held experts; no
    shared expert.  Returns the sum (N, E) float32 (zero for a token none of
    whose experts is held), how many tokens each held expert got (held,), and
    how many of the tokens that route (``token_mask``) kept no held expert."""
    def route(scores):
        idx, gates = route_sigmoid_topk(scores, c.num_experts_per_tok, bias=ep["router_bias"])
        local = idx - c.first_expert_held
        return idx, gates, jnp.any((local >= 0) & (local < c.experts_held), axis=-1)

    routed, counts, reached = routed_experts(h, ep["router"], route, ep["w_gate"], ep["w_up"], ep["w_down"],
                                             first_held=c.first_expert_held, token_mask=token_mask, dtype=c.dtype)
    routes = jnp.ones(reached.shape, bool) if token_mask is None else token_mask
    return routed, counts, jnp.sum(routes & ~reached, dtype=jnp.int32)


def _after_attention(c: MimoV2Config, lp, l: int, x, y, token_mask):
    """The layer's second half: the dense SwiGLU, or the expert layer (whose
    counts, the held experts' rows and the rows routed nowhere, come back; a
    dense layer's are None)."""
    x = x + y
    h = rmsnorm(x, lp["post_attention_layernorm"]["weight"], c.rms_norm_eps)
    if not c.moe_layer_freq[l]:
        with jax.named_scope("vs.mlp"):
            return x + mlp(c, lp["mlp"], h), None
    with jax.named_scope("vs.moe"):
        y, *counts = expert_layer(c, lp["mlp"], h, token_mask=token_mask)
    return x + y, counts


def layer_prefill(c: MimoV2Config, lp, l: int, x, live, *, interpret: Optional[bool] = None):
    """Layer ``l`` over one padded sequence ``x`` (T, E) float32; ``live`` (T,)
    the positions that route to experts (the real ones).  Returns the residual
    stream and the layer's K and V."""
    with jax.named_scope("vs.attn"):
        y, k, v = attention_prefill(c, lp["self_attn"], rmsnorm(x, lp["input_layernorm"]["weight"], c.rms_norm_eps),
                                    c.hybrid_layer_pattern[l], interpret=interpret)
    x, _ = _after_attention(c, lp, l, x, y, live)
    return x, k, v


def layer_step(c: MimoV2Config, lp, l: int, x, live, attention):
    """Layer ``l`` over one step, ``x`` (S, E) float32; ``attention(u)`` is
    :func:`attention_step` over this layer's stores.  Returns the residual
    stream, both stores and the held experts' counts (None of a dense layer)."""
    with jax.named_scope("vs.attn"):
        y, k_store, v_store = attention(rmsnorm(x, lp["input_layernorm"]["weight"], c.rms_norm_eps))
    x, counts = _after_attention(c, lp, l, x, y, live)
    return x, k_store, v_store, counts


# ------------------------------------------- what the serve engine asks of a model
# (``serve/hybrid_engine.py``, "The seam")
def cache_config(config: MimoV2Config, *, num_slots: int, page_size: int, pages_per_slot: int,
                 num_pages: Optional[int] = None):
    """Folded pages for the full layers (keys ``head_dim``, values
    ``v_head_dim`` wide, on their key heads), a folded ring of
    ``sliding_window`` positions a slot for the window ones, on theirs (whole
    pages of the pool's size, so that the ring reads as pages)."""
    from ..serve.kv_cache import KVCacheConfig

    c = config
    if c.sliding_window % page_size:
        raise ValueError(f"a ring of {c.sliding_window} positions is read as whole pages of {page_size}")
    swa, KV = len(c.layers_of(SWA)), c.swa_num_key_value_heads
    rings = (("ring_k", swa, (c.sliding_window, 1, KV * c.head_dim), c.dtype),
             ("ring_v", swa, (c.sliding_window, 1, KV * c.v_head_dim), c.dtype))
    return KVCacheConfig(
        layers=len(c.layers_of(FULL)), kv_heads=c.num_key_value_heads, head_dim=c.head_dim, v_head_dim=c.v_head_dim,
        folded=True, num_slots=num_slots, page_size=page_size, pages_per_slot=pages_per_slot, num_pages=num_pages,
        dtype=c.dtype, slot_state=rings if swa else ())


def prefill_chunk(config: MimoV2Config) -> int:
    """The prefill ladder's first rung."""
    return config.prefill_chunk


def decode_kernels(config: MimoV2Config, cache) -> Dict[str, Any]:
    """``{"decode": the ``interpret`` flag of ``paged_decode_folded``, or None for
    the XLA leg}``: one answer for pools and rings, which holds only if the
    kernel takes both rows (4 and 8 key heads)."""
    from ..kernels import paged_attention

    c, dt, page = config, cache.k.data.dtype, cache.config.page_size
    legs = {paged_attention.leg_folded(dt, c.kv_heads(kind), c.head_dim, c.v_head_dim, page)
            for kind in (FULL, SWA) if c.layers_of(kind)}
    return {"decode": legs.pop() if len(legs) == 1 else None}


def serve_prefill(c: MimoV2Config, params, arrays, tokens, length, page_row, slot, *, page: int,
                  interpret: Optional[bool] = None):
    """The prefill program's body: ``tokens`` (rung,) through the stack.  The
    full layers' K and V of the rung's positions go to the slot's pages; the
    slot's rows of the rings are wholly rewritten from the last ``min(length,
    window)`` real positions of the window layers' K and V.  Returns the last
    real position's logits row and the cache's arrays."""
    from ..serve.kv_cache import write_pages

    T = tokens.shape[0]
    live = jnp.arange(T, dtype=jnp.int32) < length
    x = embed(c, params, tokens)
    kept = {FULL: ([], []), SWA: ([], [])}
    for l in range(c.num_hidden_layers):
        x, k, v = layer_prefill(c, params[f"layers_{l}"], l, x, live, interpret=interpret)
        kept[c.hybrid_layer_pattern[l]][0].append(_folded(k))
        kept[c.hybrid_layer_pattern[l]][1].append(_folded(v))
    logits = head(c, params, jax.lax.dynamic_index_in_dim(x, length - 1, axis=0, keepdims=True))[0]
    out = dict(arrays)
    out["k"] = write_pages(arrays["k"], jnp.stack(kept[FULL][0]), page_row, page)
    out["v"] = write_pages(arrays["v"], jnp.stack(kept[FULL][1]), page_row, page)
    if kept[SWA][0]:
        source = ring_source(length, T, c.sliding_window)
        for name, rows in (("ring_k", kept[SWA][0]), ("ring_v", kept[SWA][1])):
            ring = jnp.take(jnp.stack(rows), source, axis=1)[:, None].astype(arrays[name].dtype)    # (layers, 1, window, 1, KV x D)
            out[name] = jax.lax.dynamic_update_slice_in_dim(arrays[name], ring, slot, axis=1)
    return logits, out


def serve_decode(c: MimoV2Config, params, arrays, table, lengths, tokens, *, active, write_page, write_offset,
                 kernels: Dict[str, Any]):
    """The decode program's body, one token a slot.  A full layer writes the
    new position to the slot's page and reads its pages up to it; a window
    layer writes ring row ``lengths mod window`` and reads the ring, viewed as
    pages under an arithmetic table, up to ``min(lengths + 1, window)``, with
    its sink: both through ``paged_decode_folded`` (the kernel on TPU).
    Returns the logits (S, vocab), ``{"experts": (expert layers, held) tokens an
    expert got, "nowhere": (expert layers,) active rows none of whose experts is
    held}`` and the cache's arrays."""
    S, W, page = lengths.shape[0], c.sliding_window, arrays["k"].shape[2]
    stores = {FULL: (arrays["k"], arrays["v"])}
    place = {FULL: dict(table=table, write=(write_page, write_offset), valid_len=lengths + 1)}
    if "ring_k" in arrays:
        # the rings as pools of ``window / page`` pages a slot (a split of a major axis: no bytes move), slot ``s``'s
        # pages ``s window / page ...``; every slot writes its own ring (one that holds nothing: row 0, which its
        # prefill rewrites)
        as_pages = lambda ring: ring.reshape(ring.shape[0], S * (W // page), page, *ring.shape[3:])
        stores[SWA] = (as_pages(arrays["ring_k"]), as_pages(arrays["ring_v"]))
        row = ring_row(lengths, W)
        ring_table = (jnp.arange(S, dtype=jnp.int32) * (W // page))[:, None] + jnp.arange(W // page, dtype=jnp.int32)[None, :]
        place[SWA] = dict(table=ring_table, write=(ring_table[:, 0] + row // page, row % page),
                          valid_len=jnp.minimum(lengths + 1, W))
    index = {l: i for kind in (FULL, SWA) for i, l in enumerate(c.layers_of(kind))}
    x = embed(c, params, tokens)
    experts, nowhere = [], []
    for l in range(c.num_hidden_layers):
        lp, kind = params[f"layers_{l}"], c.hybrid_layer_pattern[l]
        step = lambda u, lp=lp, kind=kind, l=l: attention_step(
            c, lp["self_attn"], u, kind, *stores[kind], layer=index[l], positions=lengths, interpret=kernels["decode"],
            **place[kind])
        x, k_store, v_store, counts = layer_step(c, lp, l, x, active, step)
        stores[kind] = (k_store, v_store)
        if counts is not None:
            experts.append(counts[0])
            nowhere.append(counts[1])
    out = dict(arrays, k=stores[FULL][0], v=stores[FULL][1])
    if SWA in stores:
        out["ring_k"], out["ring_v"] = (a.reshape(arrays[name].shape) for name, a in zip(("ring_k", "ring_v"), stores[SWA]))
    return head(c, params, x), ({"experts": jnp.stack(experts), "nowhere": jnp.stack(nowhere)} if experts else {}), out


# this model's own counters beside those every model's engine keeps (``HybridServeEngine.trace_counters``;
# ``decode_pages_*`` there are ONE full layer's pages).  Of the decode steps read: the positions the full layers'
# attention read from the pages (every slot's ``length + 1``, a slot that holds nothing its one, times the full
# layers) and their bytes (K and V of those positions: 2,560 B a position and layer at the published widths);
# the ring positions the window layers read (``min(length + 1, window)`` a slot, times the window layers) and the
# rings' bytes read and written (those positions' K and V, and the one row a slot and layer writes); the active
# rows of the expert layers none of whose kept experts is held here (``rows_routed_nowhere``, of
# ``moe_assignments / num_experts_per_tok`` rows: the step's program counts them).  Of the prefills:
# the useful operations of the window layers' attention under the window and of the full layers' under the causal
# mask (scores over ``head_dim`` and values over ``v_head_dim``, over the (query, key) pairs the mask keeps, from
# the rung).
STEP_COUNTERS = ("page_positions_read", "page_bytes_read", "ring_positions_read", "ring_bytes_rw",
                 "prefill_window_attn_flops", "prefill_full_attn_flops", "rows_routed_nowhere")


def _position_bytes(c: MimoV2Config, kind: int) -> int:
    """K and V of one position in one layer of ``kind``."""
    return c.kv_heads(kind) * (c.head_dim + c.v_head_dim) * jnp.dtype(c.dtype).itemsize


def step_counters(config: MimoV2Config, cache, lengths: np.ndarray, counts) -> Dict[str, int]:
    c = config
    full, swa = len(c.layers_of(FULL)), len(c.layers_of(SWA))
    reach = lengths.astype(np.int64) + 1
    pages = int(reach.sum()) * full
    rings = int(np.minimum(reach, c.sliding_window).sum()) * swa
    nowhere = counts.get("nowhere")
    return {"page_positions_read": pages, "page_bytes_read": pages * _position_bytes(c, FULL),
            "ring_positions_read": rings, "ring_bytes_rw": (rings + len(lengths) * swa) * _position_bytes(c, SWA),
            "rows_routed_nowhere": 0 if nowhere is None else int(np.asarray(nowhere).sum())}


def prefill_counters(config: MimoV2Config, bucket: int) -> Dict[str, int]:
    c = config
    flops = lambda kind, pairs: 2 * (c.head_dim + c.v_head_dim) * c.num_attention_heads * pairs * len(c.layers_of(kind))
    return {"prefill_window_attn_flops": flops(SWA, window_pairs(bucket, c.sliding_window)),
            "prefill_full_attn_flops": flops(FULL, window_pairs(bucket))}
