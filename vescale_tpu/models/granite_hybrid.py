"""Granite-4.0-H (``granitemoehybrid``): a decoder whose mixers are Mamba-2
state-space layers with an attention layer every few, and whose feed-forward
is a routed expert layer beside one shared expert, in every layer.

The block is written ONCE, as pure functions over a plain parameter tree
(``init_params``), and both serve programs call them: prefill
(``mamba2.mamba2_prefill``, ``attention_prefill``), decode
(``mamba2.mamba2_step``, ``attention_step``) and the decode step that CARRIES a
prompt (``mamba2.mamba2_ride``, ``attention_ride``: :func:`serve_ride`, what the
engine launches for every prompt) share the projections, the convolution, the
gate, the norms, the expert layer (``moe.dropless``) and the head (the norm, the
product and the SwiGLU are ``models/blocks.py``'s, each decode kernel and its
XLA leg ``kernels/``'s).  No flax module, no third copy for training yet
(ROADMAP D3).

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

from ..moe.dropless import fits_pad, padded_candidate, route_topk, routed_experts
from .blocks import F32, _mm, rmsnorm, swiglu, write_position
from .mamba2 import mamba2_prefill, mamba2_ride, mamba2_step

__all__ = [
    "GraniteHybridConfig", "init_params", "embed", "head", "attention_prefill", "attention_step", "attention_ride",
    "expert_layer", "layer_prefill", "layer_step", "cache_config", "prefill_chunk", "decode_kernels", "serve_prefill",
    "serve_decode", "serve_ride", "STEP_COUNTERS", "step_counters", "prefill_counters",
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


def attention_ride(c: GraniteHybridConfig, ap, u, k_pool, v_pool, *, layer, table, page, offset, valid_len, page_row,
                   page_size: int, interpret: Optional[bool], flash_interpret: Optional[bool]):
    """A decode step's rows AND a prompt's through one attention mixer: ``u``
    (S + T, E), the ``S`` slots' rows first, then a prompt padded to ``T``
    positions.  ``W_q``, ``W_k``, ``W_v`` and ``W_o`` over all the rows at once;
    between them the slots' rows as :func:`attention_step` runs them (their K
    and V to ``(page, offset)``, ``paged_decode`` over their pages) and the
    prompt's as :func:`attention_prefill` does (causal over themselves: a whole
    prompt rides one step, so none of its rows reads a page), its K and V into
    the pages ``page_row`` (T / page_size,) of this ``layer`` (an int or a
    traced int32).  Returns the output (S + T, E) and both pools."""
    from ..kernels.paged_attention import paged_decode
    from ..ops.flash_attention import flash_attention
    from ..serve.kv_cache import write_pages

    S, scale = table.shape[0], c.attention_multiplier
    q, k, v = _qkv(c, ap, u)
    k_pool, v_pool = write_position(k_pool, v_pool, k[:S], v[:S], (layer, page, offset))
    k_pool = write_pages(k_pool, k[None, S:], page_row, page_size, layer)
    v_pool = write_pages(v_pool, v[None, S:], page_row, page_size, layer)
    y_step = paged_decode(q[:S], k_pool, v_pool, table, valid_len, layer=layer, scale=scale, interpret=interpret)
    y_prompt = flash_attention(q[None, S:], k[None, S:], v[None, S:], causal=True, scale=scale, interpret=flash_interpret)[0]
    y = jnp.concatenate([y_step.reshape(S, -1), y_prompt.reshape(u.shape[0] - S, -1)])
    return _mm(y, ap["o_proj"], c.dtype), k_pool, v_pool


# -------------------------------------------------------------- expert layer
def _expert_layer(c: GraniteHybridConfig, ep, h, token_mask):
    """:func:`expert_layer`, and beside it each token's ten kept ids (N, k)."""
    def route(scores):
        idx, gates = route_topk(scores, c.num_experts_per_tok)
        return idx, gates, idx

    routed, counts, idx = routed_experts(h, ep["router"], route, ep["w_gate"], ep["w_up"], ep["w_down"],
                                         first_held=c.first_expert_held, token_mask=token_mask, dtype=c.dtype)
    return routed + swiglu(h, ep["shared_gate"], ep["shared_up"], ep["shared_down"], c.dtype), counts, idx


def expert_layer(c: GraniteHybridConfig, ep, h, token_mask=None):
    """``moe(h) + shared(h)`` for tokens ``h`` (N, E): the router scores all
    ``num_experts``, the ten largest are kept and their gates are a softmax
    over those ten; the held experts' part is computed without capacity
    (``moe.dropless``), the shared expert on every token.  Returns the sum
    (N, E) float32 and how many tokens each held expert got (held,)."""
    return _expert_layer(c, ep, h, token_mask)[:2]


def _held_counts(c: GraniteHybridConfig, idx, token_mask):
    """How many of the tokens ``token_mask`` (N,) names each held expert got, from their kept ids ``idx`` (N, k): what
    the expert layer counts (``moe.dropless``), of some of a call's rows alone."""
    mine = (idx[..., None] - c.first_expert_held == jnp.arange(c.experts_held)) & token_mask[:, None, None]
    return jnp.sum(mine, axis=(0, 1), dtype=jnp.int32)      # (a compare and a sum of N k held booleans: no scatter)


# ------------------------------------------------------------ whole layers
def _mixer_input(c: GraniteHybridConfig, lp, x):
    return rmsnorm(x, lp["input_layernorm"]["weight"], c.rms_norm_eps)


def _after_mixer(c: GraniteHybridConfig, lp, x, y, token_mask):
    """The mixer's output into the residual stream, then the expert layer's.  Returns the stream, the held
    experts' counts and every token's kept ids."""
    x = x + c.residual_multiplier * y
    with jax.named_scope("vs.moe"):
        h = rmsnorm(x, lp["post_attention_layernorm"]["weight"], c.rms_norm_eps)
        y, counts, idx = _expert_layer(c, lp["moe"], h, token_mask)
    return x + c.residual_multiplier * y, counts, idx


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
    x, _, _ = _after_mixer(c, lp, x, y, jnp.arange(x.shape[0]) < length)
    return x, tuple(kept)


def layer_step(c: GraniteHybridConfig, lp, kind: str, x, active, mixer_step):
    """One layer over one new position a slot, ``x`` (S, E) float32;
    ``mixer_step(u)`` is the mixer over this layer's share of the cache and
    returns ``(y, *cache)``.  Slots that are not ``active`` route nowhere.
    Returns the residual stream, the cache's parts and the held experts' counts."""
    with jax.named_scope("vs.mamba" if kind == "mamba" else "vs.attn"):
        y, *kept = mixer_step(_mixer_input(c, lp, x))
    x, counts, _ = _after_mixer(c, lp, x, y, active)
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
    the cache's arrays.  (Since this module gives :func:`serve_ride` the engine
    runs no prefill program of its own: a prompt alone is the riding program
    with every decode row idle.  What still lowers this body is the benchmark's
    rehearsal, ``benchmark/families/granite_hybrid.py:rehearse_serve``, and the
    tests that compare a riding step with it.)"""
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


def serve_ride(c: GraniteHybridConfig, params, arrays, table, lengths, tokens, prompt, length, page_row, slot, *, active,
               write_page, write_offset, kernels: Dict[str, Any], page: int, interpret: Optional[bool] = None):
    """The body of the decode step that CARRIES a prompt: :func:`serve_decode`
    with ``prompt`` (rung,) rows more, of which ``length`` are real.  The ``S``
    decode rows and the prompt's are ONE array before every weight's product
    (the mixers' projections; the router, the routed experts and the shared
    expert, :func:`expert_layer` once over ``S + rung`` rows with the idle slots
    and the pad masked out; the head over the ``S`` rows and the prompt's row
    ``length - 1``), so a weight crosses the HBM once for both, the held
    experts' 72% of them too.  What is no weight's product runs for each kind of
    row as it does alone (``mamba2_ride``, :func:`attention_ride`); the prompt's
    K and V go to the pages ``page_row``, its state and tail over ``slot``'s rows
    after the step's pass over them, a layer at a time.  A slot that ``active``
    (S,) does not name (it holds no request, or its prompt waits, or it is
    ``slot`` itself) keeps its state and tail BIT FOR BIT and writes the null
    page: with no slot active this is a prompt launched alone, beside slots in
    the middle of their outputs.  Which form the expert layer takes follows
    from ``S + rung`` (``moe.dropless.expert_form``), as at every other call.
    Returns the logits (S, vocab), the prompt's logits row, the counts and the
    cache's arrays.  ``counts["experts"]`` (layers, held) is of the DECODE rows
    alone, what :func:`serve_decode` counts of the same step (the engine's
    ``moe_*`` counters are of decode positions, and a prompt's rows are none);
    where the call may take the padded form, ``counts["ride_fits_pad"]``
    (layers,) says of each layer whether ALL its rows' counts did."""
    S, rung = tokens.shape[0], prompt.shape[0]
    mask = jnp.concatenate([active, jnp.arange(rung) < length])
    row = _cache_rows(c)

    def ride_layer(kind):
        def one(lp, x, kd, vd, ssm, conv, i, table, lengths, active, mask, write_page, write_offset, length, page_row, slot):
            """One layer of ``kind`` over all the rows; ``i`` is its row of the state arrays, or its layer of the pools,
            AS A VALUE, so that a kind of layer, its kernels and its expert layer and all, is traced and lowered once a
            rung whatever the depth (as ``falcon_h1.serve_ride``'s)."""
            u = _mixer_input(c, lp, x)
            if kind == "mamba":
                with jax.named_scope("vs.mamba"):
                    y, ssm, tail, state, prompt_tail = mamba2_ride(
                        c, lp["mixer"], u, ssm, jax.lax.dynamic_index_in_dim(conv, i, keepdims=False), length,
                        active=active, layer=i, interpret=kernels["ssm_step"])
                # every slot's tail as the step leaves it, then the prompt's state and tail over its slot's rows
                conv = jax.lax.dynamic_update_slice(conv, tail[None].astype(conv.dtype), (i, 0, 0, 0))
                conv = jax.lax.dynamic_update_slice(conv, prompt_tail[None, None].astype(conv.dtype), (i, slot, 0, 0))
                ssm = jax.lax.dynamic_update_slice(ssm, state[None, None].astype(ssm.dtype), (i, slot, 0, 0))
            else:
                with jax.named_scope("vs.attn"):
                    y, kd, vd = attention_ride(
                        c, lp["mixer"], u, kd, vd, layer=i, table=table, page=write_page, offset=write_offset,
                        valid_len=lengths + 1, page_row=page_row, page_size=page, interpret=kernels["decode"],
                        flash_interpret=interpret)
            x, counts, idx = _after_mixer(c, lp, x, y, mask)
            return x, kd, vd, ssm, conv, _held_counts(c, idx[:S], active), fits_pad(counts)

        return jax.jit(one)     # (inlined where it is called: the cache's arrays are the outer program's to donate)

    ride = {kind: ride_layer(kind) for kind in set(c.layer_types)}
    kd, vd, ssm, conv = arrays["k"], arrays["v"], arrays["ssm"], arrays["conv"]
    x = embed(c, params, jnp.concatenate([tokens, prompt]))         # (S + rung, E)
    of_the_step, fits = [], []
    for l, kind in enumerate(c.layer_types):
        x, kd, vd, ssm, conv, n, fit = ride[kind](
            params[f"layers_{l}"], x, kd, vd, ssm, conv, jnp.int32(row[l]), table, lengths, active, mask, write_page,
            write_offset, length, page_row, slot)
        of_the_step.append(n)
        fits.append(fit)
    last = jax.lax.dynamic_index_in_dim(x, S + length - 1, axis=0, keepdims=True)
    logits = head(c, params, jnp.concatenate([x[:S], last]))
    counts = {"experts": jnp.stack(of_the_step)}
    if padded_candidate(S + rung, c.num_experts_per_tok, c.experts_held, c.num_experts):
        counts["ride_fits_pad"] = jnp.stack(fits)
    return logits[:S], logits[S], counts, {"k": kd, "v": vd, "ssm": ssm, "conv": conv}


# this model's own counters beside those every model's engine keeps: the slot state read and written (every
# slot's, every step), and of the steps that carried a prompt at a rung whose expert layer may take its padded form
# (``moe.dropless.padded_candidate`` of the step's rows and the rung's together), the expert layers they ran and
# those of them that did take it (the busiest held expert fit the pad; the rest fell to the sorted form's XLA leg)
STEP_COUNTERS = ("ssm_state_bytes_rw", "ride_candidate_layer_steps", "ride_padded_layer_steps")


def step_counters(config: GraniteHybridConfig, cache, lengths, counts) -> Dict[str, int]:
    out = {"ssm_state_bytes_rw": 2 * cache.state_bytes_per_slot() * cache.num_slots}
    fits = counts.get("ride_fits_pad")
    if fits is not None:
        out.update(ride_candidate_layer_steps=int(fits.size), ride_padded_layer_steps=int(fits.sum()))
    return out


def prefill_counters(config: GraniteHybridConfig, bucket: int) -> Dict[str, int]:
    return {}
