"""Granite-4.0-H (``granitemoehybrid``): a decoder whose mixers are Mamba-2
state-space layers with an attention layer every few, and whose feed-forward
is a routed expert layer beside one shared expert, in every layer.

The block is written ONCE, as pure functions over a plain parameter tree
(``init_params``), and both serve programs call them: prefill
(``mamba2.mamba2_prefill``, ``attention_prefill``) and decode
(``mamba2.mamba2_step``, ``attention_step``) share the projections, the
convolution, the gate, the norms, the expert layer (``moe.dropless``) and the
head (the norm, the product and the SwiGLU are ``models/blocks.py``'s, each
decode kernel and its XLA leg ``kernels/``'s).  No flax module, no third copy
for training yet (ROADMAP D3).

Equations (HF ``modeling_granitemoehybrid.py``; ISSUE 29 writes them out):

    x0 = embedding_multiplier * E[tokens]
    x += residual_multiplier * mixer_l(rmsnorm(x))
    h  = rmsnorm(x);  x += residual_multiplier * (moe(h) + shared(h))
    logits = rmsnorm(x_L) @ E^T / logits_scaling          (tied head)

``attention``: q, k, v, o without bias, grouped-query, NO positional term,
causal softmax of ``attention_multiplier * q.k``.  ``mamba``:
``models/mamba2.py``'s mixer, one group of B and C shared by all heads.

Precision: weights and matmul operands are ``config.dtype`` (bfloat16) with
float32 accumulation; the residual stream, the norms, the gate, the router and
everything of the state-space recurrence (decay, cumulative sums, the scan's
own products, the state) are float32.  The embedding is scaled by 12 and each
layer adds 0.22 of its output, so a bfloat16 residual would round the layers'
contributions away first.

A chip's share (the ``model-configs`` guide, section 4): ``num_experts`` is
what the router scores, ``experts_held`` / ``first_expert_held`` say which of
them this parameter tree holds; ``vocab_size`` is the rows of the embedding
held here.  The expert layer adds up the part its own experts give; nothing
stands in for the absent chips.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..moe.dropless import route_topk, routed_experts
from .blocks import F32, _mm, rmsnorm, swiglu, write_position
from .mamba2 import mamba2_prefill, mamba2_step

__all__ = [
    "GraniteHybridConfig", "init_params", "embed", "head", "attention_prefill", "attention_step", "expert_layer",
    "layer_prefill", "layer_step", "cache_config", "prefill_chunk", "decode_kernels", "serve_prefill", "serve_decode",
    "STEP_COUNTERS", "step_counters", "prefill_counters",
]


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int = 100352            # rows of the (tied) embedding held here
    hidden_size: int = 4096
    layer_types: Tuple[str, ...] = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: int = 128
    intermediate_size: int = 768        # width of one routed expert
    shared_intermediate_size: int = 1536
    num_experts: int = 72               # the router's outputs: every expert the model has
    num_experts_per_tok: int = 10
    experts_held: int = 72              # ... and the contiguous ids this tree holds
    first_expert_held: int = 0
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 256
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.0078125
    logits_scaling: float = 16.0
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16           # weights and matmul operands
    state_dtype: Any = jnp.float32      # the recurrent state: rewritten every step, so its rounding accumulates

    def __post_init__(self):
        if not self.layer_types or set(self.layer_types) - {"mamba", "attention"}:
            raise ValueError(f"layer_types must name 'mamba' or 'attention', got {self.layer_types!r}")
        if self.mamba_n_groups != 1:
            raise ValueError("this model shares one group of B and C between all heads (mamba_n_groups = 1)")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of num_key_value_heads")
        if not (0 <= self.first_expert_held and self.first_expert_held + self.experts_held <= self.num_experts
                and self.experts_held > 0):
            raise ValueError(f"experts {self.first_expert_held}..{self.first_expert_held + self.experts_held} "
                             f"are not among the router's {self.num_experts}")
        if self.num_experts_per_tok > self.num_experts:
            raise ValueError("num_experts_per_tok exceeds num_experts")

    @property
    def num_hidden_layers(self) -> int:
        return len(self.layer_types)

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def in_proj_dim(self) -> int:
        return self.d_inner + self.conv_dim + self.mamba_n_heads

    @property
    def mamba_layers(self) -> Tuple[int, ...]:
        return tuple(l for l, t in enumerate(self.layer_types) if t == "mamba")

    @property
    def attention_layers(self) -> Tuple[int, ...]:
        return tuple(l for l, t in enumerate(self.layer_types) if t == "attention")

    @property
    def ssm_state_shape(self) -> Tuple[int, int]:
        """One slot's state in one state-space layer as the cache keeps it:
        (state, heads x head width), the state dim on sublanes and every
        head's row side by side on lanes (``kernels/ssm_step.py`` says why)."""
        return (self.mamba_d_state, self.d_inner)

    @property
    def conv_tail_shape(self) -> Tuple[int, int]:
        """One slot's convolution tail in one layer: the last ``d_conv - 1`` inputs."""
        return (self.mamba_d_conv - 1, self.conv_dim)


# ------------------------------------------------------------------ parameters
def init_params(config: GraniteHybridConfig, key) -> Dict[str, Any]:
    """Seeded random weights in the types they are served in (jit the call:
    the float32 draws are then temporaries).  Matrices are normal with
    variance 1 / fan-in; ``A_log``, ``dt_bias`` and ``D`` are as Mamba-2
    initialises them (``A`` uniform in [1, 16], ``dt`` log-uniform in
    [1e-3, 1e-1] through the inverse softplus, ``D`` = 1): with normal draws
    the decay ``exp(dt A)`` is 0 or 1 and the recurrence does nothing."""
    c, dt = config, config.dtype

    def normal(k, shape, fan_in, dtype=dt):
        return (jax.random.normal(k, shape, F32) / math.sqrt(fan_in)).astype(dtype)

    def mamba(k):
        ks = jax.random.split(k, 6)
        step = jnp.exp(jax.random.uniform(ks[3], (c.mamba_n_heads,), F32) * (math.log(1e-1) - math.log(1e-3))
                       + math.log(1e-3))
        return {
            "in_proj": normal(ks[0], (c.hidden_size, c.in_proj_dim), c.hidden_size),
            "conv_weight": jax.random.uniform(ks[1], (c.mamba_d_conv, c.conv_dim), F32, -0.5, 0.5).astype(dt),
            "conv_bias": jax.random.uniform(ks[2], (c.conv_dim,), F32, -0.5, 0.5).astype(dt),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "A_log": jnp.log(jax.random.uniform(ks[4], (c.mamba_n_heads,), F32, 1.0, 16.0)),
            "D": jnp.ones((c.mamba_n_heads,), F32),
            "norm_weight": jnp.ones((c.d_inner,), dt),
            "out_proj": normal(ks[5], (c.d_inner, c.hidden_size), c.d_inner),
        }

    def attention(k):
        ks = jax.random.split(k, 4)
        q, kv = c.num_attention_heads * c.head_dim, c.num_key_value_heads * c.head_dim
        return {"q_proj": normal(ks[0], (c.hidden_size, q), c.hidden_size),
                "k_proj": normal(ks[1], (c.hidden_size, kv), c.hidden_size),
                "v_proj": normal(ks[2], (c.hidden_size, kv), c.hidden_size),
                "o_proj": normal(ks[3], (q, c.hidden_size), q)}

    def moe(k):
        ks = jax.random.split(k, 7)
        E, F, S, held = c.hidden_size, c.intermediate_size, c.shared_intermediate_size, c.experts_held
        return {"router": normal(ks[0], (E, c.num_experts), E, F32),
                "w_gate": normal(ks[1], (held, E, F), E), "w_up": normal(ks[2], (held, E, F), E),
                "w_down": normal(ks[3], (held, F, E), F),
                "shared_gate": normal(ks[4], (E, S), E), "shared_up": normal(ks[5], (E, S), E),
                "shared_down": normal(ks[6], (S, E), S)}

    params: Dict[str, Any] = {
        "embed_tokens": {"embedding": normal(jax.random.fold_in(key, 1 << 20), (c.vocab_size, c.hidden_size),
                                             c.hidden_size)},
        "norm": {"weight": jnp.ones((c.hidden_size,), dt)},
    }
    for l, kind in enumerate(c.layer_types):
        k_mixer, k_moe = jax.random.split(jax.random.fold_in(key, l))
        params[f"layers_{l}"] = {
            "input_layernorm": {"weight": jnp.ones((c.hidden_size,), dt)},
            "post_attention_layernorm": {"weight": jnp.ones((c.hidden_size,), dt)},
            "mixer": mamba(k_mixer) if kind == "mamba" else attention(k_mixer),
            "moe": moe(k_moe),
        }
    return params


# ------------------------------------------------------------ embedding, head
def embed(config: GraniteHybridConfig, params, tokens):
    return config.embedding_multiplier * jnp.take(params["embed_tokens"]["embedding"], tokens, axis=0).astype(F32)


def head(config: GraniteHybridConfig, params, x):
    """Logits (float32) over the rows of the embedding held here."""
    xn = rmsnorm(x, params["norm"]["weight"], config.rms_norm_eps)
    return _mm(xn, params["embed_tokens"]["embedding"].T, config.dtype) / config.logits_scaling


# ---------------------------------------------------------- attention mixer
def _qkv(c: GraniteHybridConfig, ap, u):
    lead = u.shape[:-1]
    q = _mm(u, ap["q_proj"], c.dtype).astype(c.dtype).reshape(lead + (c.num_attention_heads, c.head_dim))
    k = _mm(u, ap["k_proj"], c.dtype).astype(c.dtype).reshape(lead + (c.num_key_value_heads, c.head_dim))
    v = _mm(u, ap["v_proj"], c.dtype).astype(c.dtype).reshape(lead + (c.num_key_value_heads, c.head_dim))
    return q, k, v


def attention_prefill(c: GraniteHybridConfig, ap, u, *, interpret: Optional[bool] = None):
    """Causal attention over one sequence ``u`` (T, E) with no positional
    term; returns the output (T, E) and this layer's K and V (T, KV, hd).
    Pad positions follow the real ones, so causality keeps them out."""
    from ..ops.flash_attention import flash_attention

    q, k, v = _qkv(c, ap, u)
    y = flash_attention(q[None], k[None], v[None], causal=True, scale=c.attention_multiplier, interpret=interpret)[0]
    return _mm(y.reshape(u.shape[0], -1), ap["o_proj"], c.dtype), k, v


def attention_step(c: GraniteHybridConfig, ap, u, k_pool, v_pool, *, layer: int, table, page, offset, valid_len,
                   interpret: Optional[bool]):
    """One new position a slot: its K and V go to ``(page, offset)`` of the
    pool's ``layer`` (the null page for a slot that may not write), then
    ``kernels.paged_decode`` reads the slot's pages (``interpret``: the
    kernel's flag, or None for its XLA leg).  Returns the output (S, E) and
    both pools."""
    from ..kernels.paged_attention import paged_decode

    q, k, v = _qkv(c, ap, u)
    k_pool, v_pool = write_position(k_pool, v_pool, k, v, (layer, page, offset))
    y = paged_decode(q, k_pool, v_pool, table, valid_len, layer=layer, scale=c.attention_multiplier, interpret=interpret)
    return _mm(y.reshape(u.shape[0], -1), ap["o_proj"], c.dtype), k_pool, v_pool


# -------------------------------------------------------------- expert layer
def expert_layer(c: GraniteHybridConfig, ep, h, token_mask=None):
    """``moe(h) + shared(h)`` for tokens ``h`` (N, E): the router scores all
    ``num_experts``, the ten largest are kept and their gates are a softmax
    over those ten; the held experts' part is computed without capacity
    (``moe.dropless``), the shared expert on every token.  Returns the sum
    (N, E) float32 and how many tokens each held expert got (held,)."""
    routed, counts = routed_experts(h, ep["router"], lambda scores: route_topk(scores, c.num_experts_per_tok),
                                    ep["w_gate"], ep["w_up"], ep["w_down"], first_held=c.first_expert_held,
                                    token_mask=token_mask, dtype=c.dtype)
    return routed + swiglu(h, ep["shared_gate"], ep["shared_up"], ep["shared_down"], c.dtype), counts


# ------------------------------------------------------------ whole layers
def _mixer_input(c: GraniteHybridConfig, lp, x):
    return rmsnorm(x, lp["input_layernorm"]["weight"], c.rms_norm_eps)


def _after_mixer(c: GraniteHybridConfig, lp, x, y, token_mask):
    """The mixer's output into the residual stream, then the expert layer's."""
    x = x + c.residual_multiplier * y
    with jax.named_scope("vs.moe"):
        h = rmsnorm(x, lp["post_attention_layernorm"]["weight"], c.rms_norm_eps)
        y, counts = expert_layer(c, lp["moe"], h, token_mask=token_mask)
    return x + c.residual_multiplier * y, counts


def layer_prefill(c: GraniteHybridConfig, lp, kind: str, x, length, *, interpret: Optional[bool] = None):
    """One layer over one padded sequence ``x`` (T, E) float32.  Returns the
    residual stream and what the layer leaves in the cache: ``(state, tail)``
    of a state-space layer, ``(k, v)`` of an attention layer.  Pad positions
    route to no expert."""
    u = _mixer_input(c, lp, x)
    if kind == "mamba":
        with jax.named_scope("vs.mamba"):
            y, *kept = mamba2_prefill(c, lp["mixer"], u, length)
    else:
        with jax.named_scope("vs.attn"):
            y, *kept = attention_prefill(c, lp["mixer"], u, interpret=interpret)
    x, _ = _after_mixer(c, lp, x, y, jnp.arange(x.shape[0]) < length)
    return x, tuple(kept)


def layer_step(c: GraniteHybridConfig, lp, kind: str, x, active, mixer_step):
    """One layer over one new position a slot, ``x`` (S, E) float32;
    ``mixer_step(u)`` is the mixer over this layer's share of the cache and
    returns ``(y, *cache)``.  Slots that are not ``active`` route nowhere.
    Returns the residual stream, the cache's parts and the held experts' counts."""
    with jax.named_scope("vs.mamba" if kind == "mamba" else "vs.attn"):
        y, *kept = mixer_step(_mixer_input(c, lp, x))
    x, counts = _after_mixer(c, lp, x, y, active)
    return x, tuple(kept), counts


# ------------------------------------------- what the serve engine asks of a model
# (``serve/hybrid_engine.py``, "The seam")
def cache_config(config: GraniteHybridConfig, *, num_slots: int, page_size: int, pages_per_slot: int,
                 num_pages: Optional[int] = None):
    """The cache geometry of a hybrid model: pages for its attention layers
    only, and a recurrent state and a convolution tail a slot for each
    state-space layer."""
    from ..serve.kv_cache import KVCacheConfig

    if not config.attention_layers or not config.mamba_layers:
        raise ValueError("a hybrid has layers of both kinds")
    m = len(config.mamba_layers)
    return KVCacheConfig(
        layers=len(config.attention_layers), kv_heads=config.num_key_value_heads, head_dim=config.head_dim,
        num_slots=num_slots, page_size=page_size, pages_per_slot=pages_per_slot, num_pages=num_pages,
        dtype=config.dtype,
        slot_state=(("ssm", m, config.ssm_state_shape, config.state_dtype),
                    ("conv", m, config.conv_tail_shape, config.dtype)))


def prefill_chunk(config: GraniteHybridConfig) -> int:
    """The prefill ladder's first rung: the chunked scan wants whole chunks."""
    return config.mamba_chunk_size


def decode_kernels(config: GraniteHybridConfig, cache) -> Dict[str, Any]:
    """The decode step's kernels, latched at build: ``{"decode":, "ssm_step":}``
    each kernel's ``interpret`` flag, or None for its XLA leg (the kernels on
    TPU, the XLA legs elsewhere, as ``ServeEngine`` decides)."""
    from ..kernels import paged_attention, ssm_step

    return {"decode": paged_attention.leg(cache.k.data.dtype, config.num_key_value_heads, config.head_dim),
            "ssm_step": ssm_step.leg(config.state_dtype, *config.ssm_state_shape)}


def _cache_rows(c: GraniteHybridConfig) -> Dict[int, int]:
    """Where each layer's share of the cache lies: its row of the state arrays, or its layer of the pools."""
    row = {l: i for i, l in enumerate(c.mamba_layers)}
    row.update({l: i for i, l in enumerate(c.attention_layers)})
    return row


def serve_prefill(c: GraniteHybridConfig, params, arrays, tokens, length, page_row, slot, *, page: int,
                  interpret: Optional[bool] = None):
    """The prefill program's body: ``tokens`` (bucket,) through the stack; K
    and V of the bucket's positions go to the slot's pages, the state and the
    tail to the slot's rows.  Returns the last real position's logits row and
    the cache's arrays."""
    kd, vd, ssm, conv = arrays["k"], arrays["v"], arrays["ssm"], arrays["conv"]
    x = embed(c, params, tokens)
    states, tails, ks, vs = [], [], [], []
    for l, kind in enumerate(c.layer_types):
        x, kept = layer_prefill(c, params[f"layers_{l}"], kind, x, length, interpret=interpret)
        if kind == "mamba":
            states.append(kept[0])
            tails.append(kept[1])
        else:
            ks.append(kept[0])
            vs.append(kept[1])
    last = jax.lax.dynamic_index_in_dim(x, length - 1, axis=0, keepdims=True)
    logits = head(c, params, last)[0]
    pages = lambda stack: jnp.stack(stack).reshape(len(stack), -1, page, *stack[0].shape[1:])
    kd = kd.at[:, page_row].set(pages(ks).astype(kd.dtype))
    vd = vd.at[:, page_row].set(pages(vs).astype(vd.dtype))
    ssm = jax.lax.dynamic_update_slice_in_dim(ssm, jnp.stack(states)[:, None].astype(ssm.dtype), slot, axis=1)
    conv = jax.lax.dynamic_update_slice_in_dim(conv, jnp.stack(tails)[:, None].astype(conv.dtype), slot, axis=1)
    return logits, {"k": kd, "v": vd, "ssm": ssm, "conv": conv}


def serve_decode(c: GraniteHybridConfig, params, arrays, table, lengths, tokens, *, active, write_page, write_offset,
                 kernels: Dict[str, Any]):
    """The decode program's body, one token a slot: the recurrence's one step
    over every slot's state (read and written whole: the step's largest
    traffic beside the expert weights; on TPU the ``ssm_step`` kernel, which
    passes over it once), paged attention over a one-layer-a-period pool (the
    ``paged_decode`` kernel on TPU, the XLA leg elsewhere), the expert layer
    over the active slots.  Returns the logits (S, vocab), ``{"experts":
    (layers, held) tokens an expert got}`` and the cache's arrays."""
    kd, vd, ssm, conv = arrays["k"], arrays["v"], arrays["ssm"], arrays["conv"]
    row = _cache_rows(c)
    x = embed(c, params, tokens)                    # (S, E)
    counts = []
    for l, kind in enumerate(c.layer_types):
        lp, i = params[f"layers_{l}"], row[l]
        if kind == "mamba":
            step = lambda u, lp=lp, i=i: mamba2_step(c, lp["mixer"], u, ssm, conv[i], layer=i,
                                                     interpret=kernels["ssm_step"])
        else:
            step = lambda u, lp=lp, i=i: attention_step(
                c, lp["mixer"], u, kd, vd, layer=i, table=table, page=write_page, offset=write_offset,
                valid_len=lengths + 1, interpret=kernels["decode"])
        x, kept, n = layer_step(c, lp, kind, x, active, step)
        if kind == "mamba":
            ssm, conv = kept[0], conv.at[i].set(kept[1])
        else:
            kd, vd = kept
        counts.append(n)
    return head(c, params, x), {"experts": jnp.stack(counts)}, {"k": kd, "v": vd, "ssm": ssm, "conv": conv}


# this model's own counter beside those every model's engine keeps: the slot state read and written (every
# slot's, every step)
STEP_COUNTERS = ("ssm_state_bytes_rw",)


def step_counters(config: GraniteHybridConfig, cache, lengths, counts) -> Dict[str, int]:
    return {"ssm_state_bytes_rw": 2 * cache.state_bytes_per_slot() * cache.num_slots}


def prefill_counters(config: GraniteHybridConfig, bucket: int) -> Dict[str, int]:
    return {}
