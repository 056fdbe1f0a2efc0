"""Laguna (``laguna``): a decoder whose layers MIX window and full attention,
over many small routed experts beside a shared one.

``layer_types`` says which a layer is (published: full, then three sliding, and
so on).  Both kinds are grouped-query attention on the same 8 key heads of 128,
but they differ in more than the mask: a sliding layer has MORE query heads (64
against 48 at XS.2, so the attention weights differ in shape by layer type),
rotates the whole head at theta 1e4, and sees the ``sliding_window`` newest
positions, itself among them; a full layer rotates HALF of each head (the first
``partial_rotary_factor x head_dim``) by YaRN's frequencies, cos and sin times
``attention_factor``, and sees everything before it.  Every layer gates its
attention: one scalar a head and position, from the layer's normed input,
multiplies the head's output before the output projection.  Layer 0 is a dense
SwiGLU; every later layer scores all ``num_experts`` by a float32 SIGMOID,
keeps the ``num_experts_per_tok`` largest, renormalises over the kept, times
``moe_routed_scaling_factor`` (``moe.dropless.route_sigmoid_topk``), beside one
shared SwiGLU.  Pre-norm residual layers, a final norm, an untied head.

    u = rmsnorm(x);  q = u Wq (T, H_l, hd);  k = u Wk, v = u Wv (T, KV, hd)
    full:    q, k[..., :rot] rotated by the YaRN frequencies, cos and sin x attention_factor; the rest passes
    sliding: q, k rotated over all of hd at the sliding theta
    s_ij = q_i . k_j / sqrt(hd), kept where j <= i, on a sliding layer also i - j < window
    a = softmax(s) v;  g = attention_gate(u Wg) (T, H_l);  x += (a * g) Wo
    h = rmsnorm(x);  layer 0: x += SwiGLU_dense(h)
    later:   p = sigmoid(h Wr);  I = top-k;  w = scale p_I / sum(p_I);  x += sum_I w_e E_e(h) + E_shared(h)

What the published config does not settle is read as ISSUE 45 wrote it down
(the configuration file lists each under ``assumed``): the gate is
:func:`attention_gate` (ONE function: a correction from the release's modeling
code is one line), the router is the sigmoid convention under which a scaling
factor of 2.5 is published, and queries and keys carry no per-head norm.

**The cache** (``serve/kv_cache.py``): two kinds of attention state side by
side.  The full layers keep PAGES under the slot's table, which grow with the
sequence; a sliding layer needs the newest ``window`` positions and nothing
else, so it keeps a RING a slot (``slot_state`` ``ring_k`` / ``ring_v``,
``(sliding layers, slots, window, KV, hd)``): position ``p`` lives at ring row
``p mod window`` (:func:`ring_row`).  Keys are cached AFTER the rotary term, so
their order in the ring does not matter to a softmax.  A decode step writes the
new position's row and then reads the ring THROUGH ``paged_decode``, unchanged:
the ring viewed as ``(layers, slots x window / page, page, KV, hd)`` (a free
reshape) under the arithmetic table ``table[s, j] = s window / page + j`` and
lengths ``min(length + 1, window)``: a ring of exactly the window's size holds
exactly the positions ``p - window + 1 .. p`` once ``p`` is written, so no mask
beyond the length is needed.  A prefill runs the windowed flash forward over its
rung and rewrites the slot's ring from the last ``min(n, window)`` REAL
positions (:func:`ring_source`; ``n`` the prompt's length, not the rung's: a pad
position must not land on a live row).  Admission counts pages for the full
layers alone.  A ring keeps no history: ``cache.refuse_slot_state`` refuses
prefix sharing, speculation and rollback on this cache.

Layers are NOT stacked under one scan (their attention weights differ in
shape); the expert layer is jitted inside its caller (``moe.dropless``), so a
program traces it once.  Precision: weights and matmul operands
``config.dtype`` (bfloat16) with float32 accumulation; residual stream, norms,
rotary, gate, router and softmax float32; K and V are rounded to the cache's
type once.  A chip's share: as ``models/granite_hybrid.py`` (``num_experts`` is
what the router scores, ``experts_held`` / ``first_expert_held`` which of them
this tree holds).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..moe.dropless import route_sigmoid_topk, routed_experts
from .blocks import (F32, ROUTED_DOWN_GAIN, _mm, ring_row, ring_source, rmsnorm, swiglu, window_pairs, write_position,
                     yarn_inv_freq)

__all__ = [
    "LagunaConfig", "init_params", "embed", "head", "inv_freq", "rotary", "attention_gate", "attention_prefill",
    "attention_step", "ring_row", "ring_source", "mlp", "expert_layer", "layer_prefill", "layer_step", "cache_config",
    "prefill_chunk", "decode_kernels", "serve_prefill", "serve_decode", "STEP_COUNTERS", "step_counters",
    "prefill_counters", "window_pairs", "qk_gain", "FULL", "SLIDING", "DENSE", "SPARSE", "SCORE_DEVIATION",
]

FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"
# With ``Wq`` and ``Wk`` at variance 1 / fan-in the scores have unit deviation, the softmax over some hundred
# positions is flat, "random attention averages its values away" (sdar-30b's ``router_init``), and a window of
# 512 and no window at all give the same logits to rounding: no comparison could tell a wrong mask.  So
# ``init_params`` draws both wider, by as much as gives a layer's scores THIS deviation (``qk_gain``: a full
# layer's rotated half carries ``attention_factor`` twice, so its weights are drawn narrower than a sliding
# layer's): a row puts most of its mass on a handful of keys, as a trained head does, and ONE key gained or lost
# at the window's edge moves a logit by more than rounding.  (Read on the chip at the published widths, PERF.md
# section 6, PR 45: at a deviation of 2 the sound program reads 6e-3 of the largest logit and a window one
# position short 4.5e-2, seven times that; at 3 rounding grows faster than the fault, 1.7e-2 and 7.7e-2.)
SCORE_DEVIATION = 2.0


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    vocab_size: int = 100352            # rows of the embedding and of the (untied) head
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    layer_types: Tuple[str, ...] = (FULL, SLIDING, SLIDING, SLIDING) * 10
    num_attention_heads_per_layer: Tuple[int, ...] = (48, 64, 64, 64) * 10
    num_key_value_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 512
    mlp_layer_types: Tuple[str, ...] = (DENSE,) + (SPARSE,) * 39
    intermediate_size: int = 8192       # the dense layers' SwiGLU
    moe_intermediate_size: int = 512    # width of one routed expert
    shared_expert_intermediate_size: int = 512
    num_experts: int = 256              # the router's outputs: every expert the model has
    num_experts_per_tok: int = 8
    experts_held: int = 256             # ... and the contiguous ids this tree holds
    first_expert_held: int = 0
    moe_routed_scaling_factor: float = 2.5
    # rope_parameters.full_attention (rope_type "yarn") and .sliding_attention ("default")
    full_rope_theta: float = 500000.0
    full_rope_factor: float = 64.0
    full_rope_original_max_position_embeddings: int = 4096
    full_rope_beta_fast: float = 64.0
    full_rope_beta_slow: float = 1.0
    full_rope_attention_factor: float = 1.4158883083359672
    full_partial_rotary_factor: float = 0.5
    sliding_rope_theta: float = 10000.0
    sliding_partial_rotary_factor: float = 1.0
    rms_norm_eps: float = 1e-6
    prefill_chunk: int = 128            # the prefill ladder's first rung: the flash forward's smallest whole tile
    dtype: Any = jnp.bfloat16           # weights, matmul operands, K and V

    def __post_init__(self):
        L = self.num_hidden_layers
        if not (len(self.layer_types) == len(self.num_attention_heads_per_layer) == len(self.mlp_layer_types) == L):
            raise ValueError(f"layer_types, num_attention_heads_per_layer and mlp_layer_types name each of the {L} layers")
        if set(self.layer_types) - {FULL, SLIDING} or set(self.mlp_layer_types) - {DENSE, SPARSE}:
            raise ValueError(f"a layer's attention is {FULL} or {SLIDING} and its feed-forward {DENSE} or {SPARSE}")
        if FULL not in self.layer_types:
            raise ValueError("the paged pools belong to the full-attention layers: a model of sliding layers alone has "
                             "no page to admit by")
        if any(h % self.num_key_value_heads for h in self.num_attention_heads_per_layer):
            raise ValueError("query heads come in whole groups a key head")
        for factor in (self.full_partial_rotary_factor, self.sliding_partial_rotary_factor):
            if int(self.head_dim * factor) % 2 or not 0 < factor <= 1:
                raise ValueError("the rotated part of a head is made of pairs, and is at most the head")
        if not (0 <= self.first_expert_held and self.first_expert_held + self.experts_held <= self.num_experts
                and self.experts_held > 0):
            raise ValueError(f"experts {self.first_expert_held}..{self.first_expert_held + self.experts_held} "
                             f"are not among the router's {self.num_experts}")
        if self.sliding_window < 1:
            raise ValueError("a window holds at least the position itself")

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        """The layers whose attention is ``kind``, in order: a full layer's place here is its layer of the pools,
        a sliding layer's its layer of the rings."""
        return tuple(l for l, t in enumerate(self.layer_types) if t == kind)


# ------------------------------------------------------------------ parameters
def qk_gain(config: LagunaConfig, kind: str) -> float:
    """How much wider than variance 1 / fan-in ``Wq`` and ``Wk`` of a layer of
    ``kind`` are drawn, each, so that its scores ``q . k / sqrt(head_dim)`` have
    the deviation ``SCORE_DEVIATION``: at gain ``g`` each a score's variance is
    ``g^4`` times the mean square of the rotary term's multiplier over the head
    (1 on a sliding layer; on a full layer ``attention_factor^4`` over the
    rotated part and 1 past it)."""
    c = config
    rotated, factor = (c.full_partial_rotary_factor, c.full_rope_attention_factor) if kind == FULL else (1.0, 1.0)
    return (SCORE_DEVIATION ** 2 / (rotated * factor ** 4 + (1.0 - rotated))) ** 0.25


def init_params(config: LagunaConfig, key) -> Dict[str, Any]:
    """Seeded random weights in the types they are served in (jit the call).
    Matrices are normal with variance 1 / fan-in, but for: the embedding (unit
    variance: the stream starts at the size the branches add to it); the router
    (float32) twice as wide and the routed experts' down projections
    ``ROUTED_DOWN_GAIN`` times as wide (the constant says why);
    and ``Wq``, ``Wk`` :func:`qk_gain` times as wide each (``SCORE_DEVIATION`` says why)."""
    c, dt = config, config.dtype
    E, KV, hd = c.hidden_size, c.num_key_value_heads, c.head_dim

    def normal(k, shape, fan_in, dtype=dt, gain=1.0):
        return (jax.random.normal(k, shape, F32) * (gain / math.sqrt(fan_in))).astype(dtype)

    def attention(k, H, kind):
        ks = jax.random.split(k, 5)
        gain = qk_gain(c, kind)
        return {"q_proj": normal(ks[0], (E, H * hd), E, gain=gain), "k_proj": normal(ks[1], (E, KV * hd), E, gain=gain),
                "v_proj": normal(ks[2], (E, KV * hd), E), "o_proj": normal(ks[3], (H * hd, E), H * hd),
                "g_proj": normal(ks[4], (E, H), E)}

    def swiglu_params(k, width):
        ks = jax.random.split(k, 3)
        return {"gate": normal(ks[0], (E, width), E), "up": normal(ks[1], (E, width), E),
                "down": normal(ks[2], (width, E), width)}

    def moe(k):
        ks = jax.random.split(k, 5)
        F, held = c.moe_intermediate_size, c.experts_held
        return {"router": normal(ks[0], (E, c.num_experts), E, F32, gain=2.0),
                "w_gate": normal(ks[1], (held, E, F), E), "w_up": normal(ks[2], (held, E, F), E),
                "w_down": normal(ks[3], (held, F, E), F, gain=ROUTED_DOWN_GAIN),
                "shared": swiglu_params(ks[4], c.shared_expert_intermediate_size)}

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
            "self_attn": attention(k_attn, c.num_attention_heads_per_layer[l], c.layer_types[l]),
            "mlp": moe(k_mlp) if c.mlp_layer_types[l] == SPARSE else swiglu_params(k_mlp, c.intermediate_size),
        }
    return params


# ------------------------------------------------------------ embedding, head
def embed(config: LagunaConfig, params, tokens):
    return jnp.take(params["embed_tokens"]["embedding"], tokens, axis=0).astype(F32)


def head(config: LagunaConfig, params, x):
    """Next-token logits (float32)."""
    return _mm(rmsnorm(x, params["norm"]["weight"], config.rms_norm_eps), params["lm_head"]["kernel"], config.dtype)


# --------------------------------------------------------------------- rotary
def inv_freq(config: LagunaConfig, kind: str) -> Tuple[np.ndarray, float]:
    """The rotary frequencies of a layer of ``kind`` (rotated width / 2,) and
    what multiplies its cos and sin: a full layer's are YaRN's over the rotated
    part under ``attention_factor``; a sliding layer's plain."""
    c = config
    if kind == FULL:
        return yarn_inv_freq(int(c.head_dim * c.full_partial_rotary_factor), c.full_rope_theta, c.full_rope_factor,
                             c.full_rope_original_max_position_embeddings, c.full_rope_beta_fast,
                             c.full_rope_beta_slow), float(c.full_rope_attention_factor)
    dim = int(c.head_dim * c.sliding_partial_rotary_factor)
    return (c.sliding_rope_theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)).astype(np.float32), 1.0


def rotary(x, positions, frequencies, factor: float = 1.0):
    """Rotate the first ``2 len(frequencies)`` of ``x`` (N, heads, dim) by
    ``positions`` (N,) over the pairs ``(i, i + rotated / 2)`` (the source's
    ``rotate_half`` on the rotated part), cos and sin times ``factor``; what
    lies past the rotated part passes.  Float32."""
    half = len(frequencies)
    angle = positions.astype(F32)[:, None, None] * jnp.asarray(frequencies)
    cos, sin = jnp.cos(angle) * factor, jnp.sin(angle) * factor
    x = x.astype(F32)
    a, b = x[..., :half], x[..., half: 2 * half]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., 2 * half:]], axis=-1)


# ------------------------------------------------------------------ attention
def attention_gate(z):
    """``gating: true`` as this file reads it (the configuration's ``assumed``):
    a softplus of the gate's projection, float32, one scalar a head and position."""
    return jax.nn.softplus(z.astype(F32))


def _qkvg(c: LagunaConfig, ap, u, positions, kind: str):
    """Queries (N, H, hd) and keys (N, KV, hd) after the layer kind's rotary
    term, values (N, KV, hd), all in ``c.dtype``, and the gate (N, H) float32."""
    N, KV, hd = u.shape[0], c.num_key_value_heads, c.head_dim
    frequencies, factor = inv_freq(c, kind)
    q = rotary(_mm(u, ap["q_proj"], c.dtype).reshape(N, -1, hd), positions, frequencies, factor)
    k = rotary(_mm(u, ap["k_proj"], c.dtype).reshape(N, KV, hd), positions, frequencies, factor)
    v = _mm(u, ap["v_proj"], c.dtype).reshape(N, KV, hd)
    return q.astype(c.dtype), k.astype(c.dtype), v.astype(c.dtype), attention_gate(_mm(u, ap["g_proj"], c.dtype))


def _out(c: LagunaConfig, ap, y, gate):
    """Every head's output times its gate, through the output projection."""
    return _mm((y.astype(F32) * gate[..., None]).reshape(y.shape[0], -1), ap["o_proj"], c.dtype)


def attention_prefill(c: LagunaConfig, ap, u, kind: str, *, interpret: Optional[bool] = None):
    """Attention of a layer of ``kind`` over one sequence ``u`` (T, E) from
    position 0, through the flash forward: causal on a full layer, under the
    window on a sliding one (``window_flash_fwd``, whose key loop starts at the
    window's first block).  Returns the output (T, E) and this layer's K and V
    (T, KV, hd).  Pad positions follow the real ones, so causality keeps them
    out."""
    from ..ops.flash_attention import flash_attention

    q, k, v, gate = _qkvg(c, ap, u, jnp.arange(u.shape[0], dtype=jnp.int32), kind)
    y = flash_attention(q[None], k[None], v[None], causal=True, scale=c.head_dim ** -0.5, interpret=interpret,
                        window=c.sliding_window if kind == SLIDING else None)[0]
    return _out(c, ap, y, gate), k, v


def attention_step(c: LagunaConfig, ap, u, kind: str, k_store, v_store, *, layer: int, table, write, positions,
                   valid_len, interpret: Optional[bool]):
    """One new position a slot, at ``positions`` (S,): its K and V go to
    ``write`` of the stores' ``layer`` (a full layer: ``(page, offset)`` of the
    pools, the null page for a slot that may not write; a sliding layer:
    ``(slot, ring row)`` of the rings), then ``kernels.paged_decode`` reads
    through ``table``, on the leg ``interpret`` names (the kernel's flag, or
    None for the XLA leg).  Returns the output (S, E) and both stores."""
    from ..kernels.paged_attention import paged_decode

    q, k, v, gate = _qkvg(c, ap, u, positions, kind)
    k_store, v_store = write_position(k_store, v_store, k, v, (layer,) + write)
    y = paged_decode(q, k_store, v_store, table, valid_len, layer=layer, scale=c.head_dim ** -0.5, interpret=interpret)
    return _out(c, ap, y, gate), k_store, v_store


# -------------------------------------------------------------- feed-forward
def mlp(c: LagunaConfig, mp, h):
    """The SwiGLU over a tree of ``gate`` / ``up`` / ``down``: a dense layer's MLP, the shared expert."""
    return swiglu(h, mp["gate"], mp["up"], mp["down"], c.dtype)


def expert_layer(c: LagunaConfig, ep, h, token_mask=None):
    """``sum over kept and held e of w_e E_e(h) + E_shared(h)`` for tokens ``h``
    (N, E): the router's scores in float32, sigmoid routing
    (``route_sigmoid_topk``), the dropless layer over the held experts, and the
    shared expert on every token.  Returns the sum (N, E) float32 and how many
    tokens each held expert got (held,)."""
    route = lambda scores: route_sigmoid_topk(scores, c.num_experts_per_tok, scale=c.moe_routed_scaling_factor)
    routed, counts = routed_experts(h, ep["router"], route, ep["w_gate"], ep["w_up"], ep["w_down"],
                                    first_held=c.first_expert_held, token_mask=token_mask, dtype=c.dtype)
    return routed + mlp(c, ep["shared"], h), counts


def _after_attention(c: LagunaConfig, lp, l: int, x, y, token_mask):
    """The layer's second half: the dense SwiGLU, or the expert layer (whose
    counts come back; a dense layer's are None)."""
    x = x + y
    h = rmsnorm(x, lp["post_attention_layernorm"]["weight"], c.rms_norm_eps)
    if c.mlp_layer_types[l] == DENSE:
        with jax.named_scope("vs.mlp"):
            return x + mlp(c, lp["mlp"], h), None
    with jax.named_scope("vs.moe"):
        y, counts = expert_layer(c, lp["mlp"], h, token_mask=token_mask)
    return x + y, counts


def layer_prefill(c: LagunaConfig, lp, l: int, x, live, *, interpret: Optional[bool] = None):
    """Layer ``l`` over one padded sequence ``x`` (T, E) float32; ``live`` (T,)
    the positions that route to experts (the real ones).  Returns the residual
    stream and the layer's K and V."""
    with jax.named_scope("vs.attn"):
        y, k, v = attention_prefill(c, lp["self_attn"], rmsnorm(x, lp["input_layernorm"]["weight"], c.rms_norm_eps),
                                    c.layer_types[l], interpret=interpret)
    x, _ = _after_attention(c, lp, l, x, y, live)
    return x, k, v


def layer_step(c: LagunaConfig, lp, l: int, x, live, attention):
    """Layer ``l`` over one step, ``x`` (S, E) float32; ``attention(u)`` is
    :func:`attention_step` over this layer's stores.  Returns the residual
    stream, both stores and the held experts' counts (None of a dense layer)."""
    with jax.named_scope("vs.attn"):
        y, k_store, v_store = attention(rmsnorm(x, lp["input_layernorm"]["weight"], c.rms_norm_eps))
    x, counts = _after_attention(c, lp, l, x, y, live)
    return x, k_store, v_store, counts


# ------------------------------------------- what the serve engine asks of a model
# (``serve/hybrid_engine.py``, "The seam")
def cache_config(config: LagunaConfig, *, num_slots: int, page_size: int, pages_per_slot: int,
                 num_pages: Optional[int] = None):
    """Pages for the full layers, a ring of ``sliding_window`` positions a slot
    for the sliding ones (whole pages of the pool's size, so that the ring reads
    as pages)."""
    from ..serve.kv_cache import KVCacheConfig

    c = config
    if c.sliding_window % page_size:
        raise ValueError(f"a ring of {c.sliding_window} positions is read as whole pages of {page_size}")
    sliding = len(c.layers_of(SLIDING))
    ring = (c.sliding_window, c.num_key_value_heads, c.head_dim)
    return KVCacheConfig(
        layers=len(c.layers_of(FULL)), kv_heads=c.num_key_value_heads, head_dim=c.head_dim, num_slots=num_slots,
        page_size=page_size, pages_per_slot=pages_per_slot, num_pages=num_pages, dtype=c.dtype,
        slot_state=(("ring_k", sliding, ring, c.dtype), ("ring_v", sliding, ring, c.dtype)) if sliding else ())


def prefill_chunk(config: LagunaConfig) -> int:
    """The prefill ladder's first rung."""
    return config.prefill_chunk


def decode_kernels(config: LagunaConfig, cache) -> Dict[str, Any]:
    """``{"decode": the ``interpret`` flag of ``paged_decode``, or None for the
    XLA leg}``: pools and rings have one row (``KV`` heads of ``hd``) and one
    type, so one answer holds for both."""
    from ..kernels import paged_attention

    return {"decode": paged_attention.leg(cache.k.data.dtype, config.num_key_value_heads, config.head_dim)}


def serve_prefill(c: LagunaConfig, params, arrays, tokens, length, page_row, slot, *, page: int,
                  interpret: Optional[bool] = None):
    """The prefill program's body: ``tokens`` (rung,) through the stack.  The
    full layers' K and V of the rung's positions go to the slot's pages; the
    slot's rows of the rings are wholly rewritten from the last ``min(length,
    window)`` real positions of the sliding layers' K and V.  Returns the last
    real position's logits row and the cache's arrays."""
    from ..serve.kv_cache import write_pages

    T = tokens.shape[0]
    live = jnp.arange(T, dtype=jnp.int32) < length
    x = embed(c, params, tokens)
    kept = {FULL: ([], []), SLIDING: ([], [])}
    for l in range(c.num_hidden_layers):
        x, k, v = layer_prefill(c, params[f"layers_{l}"], l, x, live, interpret=interpret)
        kept[c.layer_types[l]][0].append(k)
        kept[c.layer_types[l]][1].append(v)
    logits = head(c, params, jax.lax.dynamic_index_in_dim(x, length - 1, axis=0, keepdims=True))[0]
    out = dict(arrays)
    out["k"] = write_pages(arrays["k"], jnp.stack(kept[FULL][0]), page_row, page)
    out["v"] = write_pages(arrays["v"], jnp.stack(kept[FULL][1]), page_row, page)
    if kept[SLIDING][0]:
        source = ring_source(length, T, c.sliding_window)
        for name, rows in (("ring_k", kept[SLIDING][0]), ("ring_v", kept[SLIDING][1])):
            ring = jnp.take(jnp.stack(rows), source, axis=1)[:, None].astype(arrays[name].dtype)    # (layers, 1, window, KV, hd)
            out[name] = jax.lax.dynamic_update_slice_in_dim(arrays[name], ring, slot, axis=1)
    return logits, out


def serve_decode(c: LagunaConfig, params, arrays, table, lengths, tokens, *, active, write_page, write_offset,
                 kernels: Dict[str, Any]):
    """The decode program's body, one token a slot.  A full layer writes the
    new position to the slot's page and reads its pages up to it; a sliding
    layer writes ring row ``lengths mod window`` and reads the ring, viewed as
    pages under an arithmetic table, up to ``min(lengths + 1, window)``: both
    through the same ``paged_decode`` (the kernel on TPU), at the layer's own
    count of query heads.  Returns the logits (S, vocab), ``{"experts":
    (expert layers, held) tokens an expert got}`` and the cache's arrays."""
    S, W, page = lengths.shape[0], c.sliding_window, arrays["k"].shape[2]
    stores = {FULL: (arrays["k"], arrays["v"])}
    place = {FULL: dict(table=table, write=(write_page, write_offset), valid_len=lengths + 1)}
    if "ring_k" in arrays:
        # the rings as pools of ``window / page`` pages a slot (a split of a major axis: no bytes move), slot ``s``'s
        # pages ``s window / page ...``; every slot writes its own ring (one that holds nothing: row 0, which its
        # prefill rewrites)
        as_pages = lambda ring: ring.reshape(ring.shape[0], S * (W // page), page, *ring.shape[3:])
        stores[SLIDING] = (as_pages(arrays["ring_k"]), as_pages(arrays["ring_v"]))
        row = ring_row(lengths, W)
        ring_table = (jnp.arange(S, dtype=jnp.int32) * (W // page))[:, None] + jnp.arange(W // page, dtype=jnp.int32)[None, :]
        place[SLIDING] = dict(table=ring_table, write=(ring_table[:, 0] + row // page, row % page),
                              valid_len=jnp.minimum(lengths + 1, W))
    index = {l: i for kind in (FULL, SLIDING) for i, l in enumerate(c.layers_of(kind))}
    x = embed(c, params, tokens)
    experts = []
    for l in range(c.num_hidden_layers):
        lp, kind = params[f"layers_{l}"], c.layer_types[l]
        step = lambda u, lp=lp, kind=kind, l=l: attention_step(
            c, lp["self_attn"], u, kind, *stores[kind], layer=index[l], positions=lengths, interpret=kernels["decode"],
            **place[kind])
        x, k_store, v_store, counts = layer_step(c, lp, l, x, active, step)
        stores[kind] = (k_store, v_store)
        if counts is not None:
            experts.append(counts)
    out = dict(arrays, k=stores[FULL][0], v=stores[FULL][1])
    if SLIDING in stores:
        out["ring_k"], out["ring_v"] = (a.reshape(arrays["ring_k"].shape) for a in stores[SLIDING])
    return head(c, params, x), ({"experts": jnp.stack(experts)} if experts else {}), out


# this model's own counters beside those every model's engine keeps (``HybridServeEngine.trace_counters``;
# ``decode_pages_*`` there are ONE full layer's pages).  Of the decode steps read: the ring positions the sliding
# layers' attention read (every slot's ``min(length + 1, window)``, a slot that holds nothing its one, times the
# sliding layers), what they would have read without a window (``length + 1``: what pages would have cost), and
# the rings' bytes read and written (K and V of those positions, and of the one a slot and layer writes).  Of
# the prefills: the useful operations of the sliding layers' attention under the window and of the full layers'
# under the causal mask (scores and values over the (query, key) pairs the mask keeps, from the rung).
STEP_COUNTERS = ("ring_positions_read", "ring_positions_unwindowed", "ring_bytes_rw", "prefill_window_attn_flops",
                 "prefill_full_attn_flops")


def step_counters(config: LagunaConfig, cache, lengths: np.ndarray, counts) -> Dict[str, int]:
    c = config
    sliding = len(c.layers_of(SLIDING))
    reach = lengths.astype(np.int64) + 1
    read = int(np.minimum(reach, c.sliding_window).sum()) * sliding
    position_bytes = 2 * c.num_key_value_heads * c.head_dim * jnp.dtype(c.dtype).itemsize
    return {"ring_positions_read": read, "ring_positions_unwindowed": int(reach.sum()) * sliding,
            "ring_bytes_rw": (read + len(lengths) * sliding) * position_bytes}


def prefill_counters(config: LagunaConfig, bucket: int) -> Dict[str, int]:
    c = config
    flops = lambda kind, pairs: 4 * c.head_dim * pairs * sum(c.num_attention_heads_per_layer[l] for l in c.layers_of(kind))
    return {"prefill_window_attn_flops": flops(SLIDING, window_pairs(bucket, c.sliding_window)),
            "prefill_full_attn_flops": flops(FULL, window_pairs(bucket))}
