"""Falcon-H1 (``falcon_h1``): a decoder whose every layer runs a Mamba-2
state-space mixer AND a grouped-query attention mixer side by side on the same
normed input and adds both to the residual stream, over a dense SwiGLU, with
twelve fixed multipliers (muP) on the way.

The block is written ONCE, as pure functions over a plain parameter tree
(``init_params``), and every serve program calls them.  The state-space mixer
is ``models/mamba2.py``'s (``mamba2_prefill`` over ``ssd_chunked``,
``mamba2_step``, and ``mamba2_ride``, which is both over one array of rows),
which is written for ``G`` groups of B and C; the norm, the
product and the rotary term are ``models/blocks.py``'s.  No flax module, no
training copy.

Equations (HF ``modeling_falcon_h1.py``; ISSUE 43 writes them out):

    x0 = embedding_multiplier * E[tokens]
    u  = rmsnorm(x; input_layernorm)
    x += ssm_out_multiplier * mamba(ssm_in_multiplier * u)
       + attention_out_multiplier * attn(attention_in_multiplier * u)
    h  = rmsnorm(x; pre_ff_layernorm)
    x += mlp_multipliers[1] * W_down(W_up h * silu(mlp_multipliers[0] * W_gate h))
    logits = lm_head_multiplier * W_head rmsnorm(x_L; final_layernorm)      (head untied)

``attn``: q, k, v, o without bias, grouped-query (query head ``h`` reads key
head ``h // (H / KV)``; 20 on 4 at 34B, five a key head), ``k = key_multiplier *
W_k u``, rotary over the whole head (``rotate_half`` pairs) on q and k, causal
softmax of ``q.k / sqrt(head_dim)``.  ``mamba``: ``models/mamba2.py``'s mixer
with ``G`` = 2 groups of B and C at 34B, its in-projection ``p = W_in u`` times
``ssm_multipliers`` segment by segment (z, x, B, C, dt) before ``[z | xBC | dt]
= p`` (the gate first, then each group of ``d_ssm / G`` channels normed by its
own mean square).

Precision: weights and matmul operands are
``config.dtype`` (bfloat16) with float32 accumulation; the residual stream, the
norms, the gate, the rotary term and everything of the recurrence are float32,
the state float32.

The cache: EVERY layer owns a row of the state arrays (``ssm``, ``conv``) AND a
layer of the K/V pools; a prefill writes both for every layer and a decode
step reads and writes both.

A prompt RIDES the decode step (``serve_ride``, which ``HybridServeEngine``
takes as this model's offer): the step's rows and the prompt's are one array
before every weight's product, so the weights cross the HBM once for both, and
a slot that holds no request in that step keeps its state and tail bit for bit.

A chip's share: ``vocab_size`` is the rows of the embedding and of the head
held here; nothing stands in for the absent chips.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .blocks import F32, _mm, rmsnorm, rotary, write_position
from .mamba2 import mamba2_prefill, mamba2_ride, mamba2_step

__all__ = [
    "FalconH1Config", "init_params", "embed", "head", "in_scale", "attention_prefill", "attention_step",
    "attention_ride", "mlp", "layer", "cache_config", "prefill_chunk", "decode_kernels", "serve_prefill",
    "serve_decode", "serve_ride",
    "STEP_COUNTERS", "step_counters", "prefill_counters", "BRANCH_GAIN",
]

# Random weights at variance 1 / fan-in under this model's multipliers would make every branch a rounding
# error of the stream (the state-space branch enters at 0.25 x ... x 0.088, the head scales by 0.0078), and a
# comparison with the reference would then hold whatever a branch computed.  So ``init_params`` draws each
# matrix that a multiplier follows (or whose input one scales) wider by that multiplier's inverse: every
# pre-activation the block's nonlinearities see (the convolution's, the gates', the softmax's scores, the
# logits) then has unit variance, as the trained model's multipliers are there to arrange; and the three
# branches' last projections (``out_proj``, ``o_proj``, ``down_proj``) this much of that, so that each branch
# adds about a quarter of the stream's size a layer, as Granite's residual multiplier of 0.22 does.
BRANCH_GAIN = 0.25


@dataclasses.dataclass(frozen=True)
class FalconH1Config:
    vocab_size: int = 261120            # rows of the embedding and of the (untied) head held here
    hidden_size: int = 5120
    num_hidden_layers: int = 72
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    intermediate_size: int = 21504
    mamba_n_heads: int = 32
    mamba_d_head: int = 128             # heads x head width is the source's ``mamba_d_ssm``
    mamba_d_state: int = 256
    mamba_d_conv: int = 4
    mamba_n_groups: int = 2
    mamba_chunk_size: int = 128
    rope_theta: float = 1e11
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    ssm_multipliers: Tuple[float, ...] = (0.3535533905932738, 0.25, 0.1767766952966369, 0.5, 0.3535533905932738)
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    mlp_multipliers: Tuple[float, float] = (0.1767766952966369, 0.011160714285714284)
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16           # weights and matmul operands
    state_dtype: Any = jnp.float32      # the recurrent state: rewritten every step, so its rounding accumulates

    def __post_init__(self):
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of num_key_value_heads")
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError("the state-space heads come in whole groups")
        if self.head_dim % 2:
            raise ValueError("a head is made of rotary pairs")
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError("ssm_multipliers are five (z, x, B, C, dt) and mlp_multipliers two (gate, down)")

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
    def in_segments(self) -> Tuple[int, ...]:
        """Widths of the in-projection's segments, in ``ssm_multipliers``' order: z, x, B, C, dt."""
        GN = self.mamba_n_groups * self.mamba_d_state
        return (self.d_inner, self.d_inner, GN, GN, self.mamba_n_heads)

    @property
    def ssm_state_shape(self) -> Tuple[int, int]:
        """One slot's state in one layer as the cache keeps it: (state, heads x
        head width); group ``g``'s heads are the lanes ``[g, g + 1) x d_inner / G``."""
        return (self.mamba_d_state, self.d_inner)

    @property
    def conv_tail_shape(self) -> Tuple[int, int]:
        return (self.mamba_d_conv - 1, self.conv_dim)


# ------------------------------------------------------------------ parameters
def init_params(config: FalconH1Config, key) -> Dict[str, Any]:
    """Seeded random weights in the types they are served in (jit the call).
    Matrices are normal with variance ``(gain / multiplier)^2 / fan-in``
    (``BRANCH_GAIN`` says which gain and why); ``A_log``, ``dt_bias`` and ``D``
    as Mamba-2 initialises them (``granite_hybrid.init_params`` says why)."""
    c, dt = config, config.dtype
    E, F = c.hidden_size, c.intermediate_size
    in_gain = 1.0 / (math.sqrt(E) * c.ssm_in_multiplier * in_scale(c))      # (in_proj_dim,): a segment's own

    def normal(k, shape, fan_in, gain=1.0):
        return (jax.random.normal(k, shape, F32) * (gain / math.sqrt(fan_in))).astype(dt)

    def mamba(k):
        ks = jax.random.split(k, 6)
        step = jnp.exp(jax.random.uniform(ks[3], (c.mamba_n_heads,), F32) * (math.log(1e-1) - math.log(1e-3))
                       + math.log(1e-3))
        return {
            "in_proj": (jax.random.normal(ks[0], (E, c.in_proj_dim), F32) * in_gain).astype(dt),
            "conv_weight": jax.random.uniform(ks[1], (c.mamba_d_conv, c.conv_dim), F32, -0.5, 0.5).astype(dt),
            "conv_bias": jax.random.uniform(ks[2], (c.conv_dim,), F32, -0.5, 0.5).astype(dt),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "A_log": jnp.log(jax.random.uniform(ks[4], (c.mamba_n_heads,), F32, 1.0, 16.0)),
            "D": jnp.ones((c.mamba_n_heads,), F32),
            "norm_weight": jnp.ones((c.d_inner,), dt),
            "out_proj": normal(ks[5], (c.d_inner, E), c.d_inner, BRANCH_GAIN / c.ssm_out_multiplier),
        }

    def attention(k):
        ks = jax.random.split(k, 4)
        q, kv = c.num_attention_heads * c.head_dim, c.num_key_value_heads * c.head_dim
        return {"q_proj": normal(ks[0], (E, q), E, 1.0 / c.attention_in_multiplier),
                "k_proj": normal(ks[1], (E, kv), E, 1.0 / (c.attention_in_multiplier * c.key_multiplier)),
                "v_proj": normal(ks[2], (E, kv), E, 1.0 / c.attention_in_multiplier),
                "o_proj": normal(ks[3], (q, E), q, BRANCH_GAIN / c.attention_out_multiplier)}

    def feed_forward(k):
        ks = jax.random.split(k, 3)
        return {"gate_proj": normal(ks[0], (E, F), E, 1.0 / c.mlp_multipliers[0]),
                "up_proj": normal(ks[1], (E, F), E),
                "down_proj": normal(ks[2], (F, E), F, BRANCH_GAIN / c.mlp_multipliers[1])}

    params: Dict[str, Any] = {
        "embed_tokens": {"embedding": normal(jax.random.fold_in(key, 1 << 20), (c.vocab_size, E), 1.0,
                                             1.0 / c.embedding_multiplier)},
        "lm_head": {"kernel": normal(jax.random.fold_in(key, 1 << 21), (E, c.vocab_size), E,
                                     1.0 / c.lm_head_multiplier)},
        "final_layernorm": {"weight": jnp.ones((E,), dt)},
    }
    for l in range(c.num_hidden_layers):
        k_mamba, k_attn, k_ff = jax.random.split(jax.random.fold_in(key, l), 3)
        params[f"layers_{l}"] = {
            "input_layernorm": {"weight": jnp.ones((E,), dt)},
            "pre_ff_layernorm": {"weight": jnp.ones((E,), dt)},
            "mamba": mamba(k_mamba),
            "self_attn": attention(k_attn),
            "feed_forward": feed_forward(k_ff),
        }
    return params


# ------------------------------------------------------------- shared pieces
def embed(config: FalconH1Config, params, tokens):
    return config.embedding_multiplier * jnp.take(params["embed_tokens"]["embedding"], tokens, axis=0).astype(F32)


def head(config: FalconH1Config, params, x):
    """Logits (float32) over the rows of the head held here."""
    xn = rmsnorm(x, params["final_layernorm"]["weight"], config.rms_norm_eps)
    return config.lm_head_multiplier * _mm(xn, params["lm_head"]["kernel"], config.dtype)


def in_scale(c: FalconH1Config):
    """``ssm_multipliers`` laid over the in-projection's outputs, (in_proj_dim,) float32."""
    return jnp.concatenate([jnp.full((n,), m, F32) for n, m in zip(c.in_segments, c.ssm_multipliers)])


# ---------------------------------------------------------- attention mixer
def _qkv(c: FalconH1Config, ap, u, positions):
    """Queries (N, H, hd) and keys (N, KV, hd), rotated by ``positions`` (N,),
    the keys scaled by ``key_multiplier`` first; values (N, KV, hd); all in
    ``c.dtype``."""
    N, H, KV, hd = u.shape[0], c.num_attention_heads, c.num_key_value_heads, c.head_dim
    q = _mm(u, ap["q_proj"], c.dtype).reshape(N, H, hd)
    k = c.key_multiplier * _mm(u, ap["k_proj"], c.dtype).reshape(N, KV, hd)
    v = _mm(u, ap["v_proj"], c.dtype).reshape(N, KV, hd)
    return rotary(q, positions, c.rope_theta).astype(c.dtype), rotary(k, positions, c.rope_theta).astype(c.dtype), \
        v.astype(c.dtype)


def attention_prefill(c: FalconH1Config, ap, u, *, interpret: Optional[bool] = None):
    """Causal attention over one sequence ``u`` (T, E) from position 0, through
    the flash forward (its grouped-query kernel on TPU, which routes a query
    head to its key head by the block's index: five a key head is an index like
    any other); returns the output (T, E) and this layer's K and V (T, KV, hd).
    Pad positions follow the real ones, so causality keeps them out."""
    from ..ops.flash_attention import flash_attention

    q, k, v = _qkv(c, ap, u, jnp.arange(u.shape[0], dtype=jnp.int32))
    y = flash_attention(q[None], k[None], v[None], causal=True, scale=c.head_dim ** -0.5, interpret=interpret)[0]
    return _mm(y.reshape(u.shape[0], -1), ap["o_proj"], c.dtype), k, v


def attention_step(c: FalconH1Config, ap, u, k_pool, v_pool, *, layer: int, table, page, offset, positions,
                   valid_len, interpret: Optional[bool]):
    """One new position a slot, at ``positions`` (S,): its K and V go to
    ``(page, offset)`` of the pool's ``layer`` (the null page for a slot that
    may not write), then ``kernels.paged_decode`` reads the slot's pages
    (``interpret``: the kernel's flag, or None for its XLA leg).  Returns the
    output (S, E) and both pools."""
    from ..kernels.paged_attention import paged_decode

    q, k, v = _qkv(c, ap, u, positions)
    k_pool, v_pool = write_position(k_pool, v_pool, k, v, (layer, page, offset))
    y = paged_decode(q, k_pool, v_pool, table, valid_len, layer=layer, scale=c.head_dim ** -0.5, interpret=interpret)
    return _mm(y.reshape(u.shape[0], -1), ap["o_proj"], c.dtype), k_pool, v_pool


def attention_ride(c: FalconH1Config, ap, u, k_pool, v_pool, *, layer, table, page, offset, positions, valid_len,
                   page_row, page_size: int, interpret: Optional[bool], flash_interpret: Optional[bool]):
    """A decode step's rows AND a prompt's through one attention mixer: ``u``
    (S + T, E), the ``S`` slots' rows first (at ``positions[:S]``), then a
    prompt padded to ``T`` positions from 0.  ``W_q``, ``W_k``, ``W_v`` and
    ``W_o`` over all the rows at once; between them the slots' rows as
    :func:`attention_step` runs them (their K and V to ``(page, offset)``,
    ``paged_decode`` over their pages) and the prompt's as
    :func:`attention_prefill` does (causal over themselves: a whole prompt
    rides one step, so none of its rows reads a page), its K and V into the
    pages ``page_row`` (T / page_size,) of this ``layer`` (an int or a traced
    int32).  Returns the output (S + T, E) and both pools."""
    from ..kernels.paged_attention import paged_decode
    from ..ops.flash_attention import flash_attention
    from ..serve.kv_cache import write_pages

    S, scale = table.shape[0], c.head_dim ** -0.5
    q, k, v = _qkv(c, ap, u, positions)
    k_pool, v_pool = write_position(k_pool, v_pool, k[:S], v[:S], (layer, page, offset))
    k_pool = write_pages(k_pool, k[None, S:], page_row, page_size, layer)
    v_pool = write_pages(v_pool, v[None, S:], page_row, page_size, layer)
    y_step = paged_decode(q[:S], k_pool, v_pool, table, valid_len, layer=layer, scale=scale, interpret=interpret)
    y_prompt = flash_attention(q[None, S:], k[None, S:], v[None, S:], causal=True, scale=scale, interpret=flash_interpret)[0]
    y = jnp.concatenate([y_step.reshape(S, -1), y_prompt.reshape(u.shape[0] - S, -1)])
    return _mm(y, ap["o_proj"], c.dtype), k_pool, v_pool


# ------------------------------------------------------------ the dense MLP
def mlp(c: FalconH1Config, fp, h):
    gate = c.mlp_multipliers[0] * _mm(h, fp["gate_proj"], c.dtype)
    return c.mlp_multipliers[1] * _mm(_mm(h, fp["up_proj"], c.dtype) * jax.nn.silu(gate), fp["down_proj"], c.dtype)


# ------------------------------------------------------------ whole layers
def layer(c: FalconH1Config, lp, x, mamba_mixer, attention_mixer):
    """One layer over ``x`` (T, E) or (S, E) float32: both mixers on the same
    normed input (each under its own multiplier), their sum into the stream,
    then the MLP's.  ``mamba_mixer(u)`` and ``attention_mixer(u)`` are the
    mixers over this layer's share of the cache, a prefill's or a step's, and
    return ``(y, *what the layer leaves in the cache)``; those come back beside
    the stream."""
    u = rmsnorm(x, lp["input_layernorm"]["weight"], c.rms_norm_eps)
    with jax.named_scope("vs.mamba"):
        ym, *kept_m = mamba_mixer(c.ssm_in_multiplier * u)
    with jax.named_scope("vs.attn"):
        ya, *kept_a = attention_mixer(c.attention_in_multiplier * u)
    x = x + c.ssm_out_multiplier * ym + c.attention_out_multiplier * ya
    with jax.named_scope("vs.mlp"):
        x = x + mlp(c, lp["feed_forward"], rmsnorm(x, lp["pre_ff_layernorm"]["weight"], c.rms_norm_eps))
    return x, tuple(kept_m), tuple(kept_a)


# ------------------------------------------- what the serve engine asks of a model
# (``serve/hybrid_engine.py``, "The seam")
def cache_config(config: FalconH1Config, *, num_slots: int, page_size: int, pages_per_slot: int,
                 num_pages: Optional[int] = None):
    """Pages AND a recurrent state and a convolution tail a slot, for every layer."""
    from ..serve.kv_cache import KVCacheConfig

    L = config.num_hidden_layers
    return KVCacheConfig(
        layers=L, kv_heads=config.num_key_value_heads, head_dim=config.head_dim, num_slots=num_slots,
        page_size=page_size, pages_per_slot=pages_per_slot, num_pages=num_pages, dtype=config.dtype,
        slot_state=(("ssm", L, config.ssm_state_shape, config.state_dtype),
                    ("conv", L, config.conv_tail_shape, config.dtype)))


def prefill_chunk(config: FalconH1Config) -> int:
    """The prefill ladder's first rung: the chunked scan wants whole chunks."""
    return config.mamba_chunk_size


def decode_kernels(config: FalconH1Config, cache) -> Dict[str, Any]:
    """``{"decode":, "ssm_step":}``, each kernel's ``interpret`` flag, or None
    for its XLA leg (the kernels on TPU, the XLA legs elsewhere)."""
    from ..kernels import paged_attention, ssm_step

    return {"decode": paged_attention.leg(cache.k.data.dtype, config.num_key_value_heads, config.head_dim),
            "ssm_step": ssm_step.leg(config.state_dtype, *config.ssm_state_shape, groups=config.mamba_n_groups)}


def serve_prefill(c: FalconH1Config, params, arrays, tokens, length, page_row, slot, *, page: int,
                  interpret: Optional[bool] = None):
    """The prefill program's body: ``tokens`` (bucket,) through the stack; every
    layer's K and V of the bucket's positions go to the slot's pages, its state
    and tail to the slot's rows.  Returns the last real position's logits row
    and the cache's arrays.  (Since this module gives :func:`serve_ride` the
    engine runs no prefill program of its own: a prompt alone is the riding
    program with every decode row idle.  What still lowers this body is the
    benchmark's rehearsal, ``benchmark/families/falcon_h1.py:rehearse_serve``,
    and the tests that compare a riding step with it.)"""
    from ..serve.kv_cache import write_pages

    kd, vd, ssm, conv = arrays["k"], arrays["v"], arrays["ssm"], arrays["conv"]
    scale = in_scale(c)
    x = embed(c, params, tokens)
    states, tails, ks, vs = [], [], [], []
    for l in range(c.num_hidden_layers):
        lp = params[f"layers_{l}"]
        x, (state, tail), (k, v) = layer(
            c, lp, x,
            lambda u, lp=lp: mamba2_prefill(c, lp["mamba"], u, length, in_scale=scale),
            lambda u, lp=lp: attention_prefill(c, lp["self_attn"], u, interpret=interpret))
        states.append(state)
        tails.append(tail)
        ks.append(k)
        vs.append(v)
    last = jax.lax.dynamic_index_in_dim(x, length - 1, axis=0, keepdims=True)
    logits = head(c, params, last)[0]
    kd, vd = write_pages(kd, jnp.stack(ks), page_row, page), write_pages(vd, jnp.stack(vs), page_row, page)
    ssm = jax.lax.dynamic_update_slice_in_dim(ssm, jnp.stack(states)[:, None].astype(ssm.dtype), slot, axis=1)
    conv = jax.lax.dynamic_update_slice_in_dim(conv, jnp.stack(tails)[:, None].astype(conv.dtype), slot, axis=1)
    return logits, {"k": kd, "v": vd, "ssm": ssm, "conv": conv}


def serve_decode(c: FalconH1Config, params, arrays, table, lengths, tokens, *, active, write_page, write_offset,
                 kernels: Dict[str, Any]):
    """The decode program's body, one token a slot: in every layer the
    recurrence's one step over every slot's state (read and written whole: with
    the weights the step's largest traffic; the ``ssm_step`` kernel on TPU) and
    paged attention over that layer's pool (the ``paged_decode`` kernel on TPU),
    then the MLP.  Returns the logits (S, vocab), no counts of its own (a dense
    model: nothing is routed) and the cache's arrays."""
    del active          # every slot goes through the dense layers; a slot that holds nothing writes the null page
    kd, vd, ssm, conv = arrays["k"], arrays["v"], arrays["ssm"], arrays["conv"]

    scale = in_scale(c)
    x = embed(c, params, tokens)                    # (S, E)
    for l in range(c.num_hidden_layers):
        lp = params[f"layers_{l}"]
        x, (ssm, tail), (kd, vd) = layer(
            c, lp, x,
            lambda u, lp=lp, l=l: mamba2_step(c, lp["mamba"], u, ssm, conv[l], layer=l, interpret=kernels["ssm_step"],
                                              in_scale=scale),
            lambda u, lp=lp, l=l: attention_step(
                c, lp["self_attn"], u, kd, vd, layer=l, table=table, page=write_page, offset=write_offset,
                positions=lengths, valid_len=lengths + 1, interpret=kernels["decode"]))
        conv = conv.at[l].set(tail)
    return head(c, params, x), {}, {"k": kd, "v": vd, "ssm": ssm, "conv": conv}


def serve_ride(c: FalconH1Config, params, arrays, table, lengths, tokens, prompt, length, page_row, slot, *, active,
               write_page, write_offset, kernels: Dict[str, Any], page: int, interpret: Optional[bool] = None):
    """The body of the decode step that CARRIES a prompt: :func:`serve_decode`
    with ``prompt`` (rung,) rows more, of which ``length`` are real.  The ``S``
    decode rows and the prompt's are ONE array before every weight's product
    (the mixers' projections, the MLP; the head over the ``S`` rows and the
    prompt's row ``length - 1``), so a weight crosses the HBM once for both.
    What is no weight's product runs for each kind of row as it does alone
    (``mamba2_ride``, :func:`attention_ride`); the prompt's K and V go to the
    pages ``page_row``, its state and tail over ``slot``'s rows after the
    step's pass over them, a layer at a time.  A slot that ``active`` (S,) does
    not name (it holds no request, or its prompt waits, or it is ``slot``
    itself) keeps its state and tail BIT FOR BIT and writes the null page: with
    no slot active this is a prompt launched alone, beside slots in the middle
    of their outputs.  Returns the logits (S, vocab), the prompt's logits row,
    no counts of its own and the cache's arrays."""
    S, rung = tokens.shape[0], prompt.shape[0]
    scale = in_scale(c)
    positions = jnp.concatenate([lengths, jnp.arange(rung, dtype=lengths.dtype)])

    def ride_layer(lp, x, kd, vd, ssm, conv, l, table, lengths, positions, active, write_page, write_offset, length,
                   page_row, slot):
        """One layer over all the rows; ``l`` is the layer's number AS A VALUE, so that the layer, its three kernels
        and all, is traced and lowered once a rung whatever the depth (both decode kernels take their layer as an
        operand; a rung written out layer by layer cost the dense engine seconds of set-up a rung)."""
        x, (ssm, tail, state, prompt_tail), (kd, vd) = layer(
            c, lp, x,
            lambda u: mamba2_ride(c, lp["mamba"], u, ssm, jax.lax.dynamic_index_in_dim(conv, l, keepdims=False), length,
                                  active=active, layer=l, interpret=kernels["ssm_step"], in_scale=scale),
            lambda u: attention_ride(
                c, lp["self_attn"], u, kd, vd, layer=l, table=table, page=write_page, offset=write_offset,
                positions=positions, valid_len=lengths + 1, page_row=page_row, page_size=page,
                interpret=kernels["decode"], flash_interpret=interpret))
        # every slot's tail as the step leaves it, then the prompt's state and tail over its slot's rows
        conv = jax.lax.dynamic_update_slice(conv, tail[None].astype(conv.dtype), (l, 0, 0, 0))
        conv = jax.lax.dynamic_update_slice(conv, prompt_tail[None, None].astype(conv.dtype), (l, slot, 0, 0))
        ssm = jax.lax.dynamic_update_slice(ssm, state[None, None].astype(ssm.dtype), (l, slot, 0, 0))
        return x, kd, vd, ssm, conv

    ride_layer = jax.jit(ride_layer)    # (inlined where it is called: the cache's arrays are the outer program's to donate)
    kd, vd, ssm, conv = arrays["k"], arrays["v"], arrays["ssm"], arrays["conv"]
    x = embed(c, params, jnp.concatenate([tokens, prompt]))         # (S + rung, E)
    for l in range(c.num_hidden_layers):
        x, kd, vd, ssm, conv = ride_layer(params[f"layers_{l}"], x, kd, vd, ssm, conv, jnp.int32(l), table, lengths,
                                          positions, active, write_page, write_offset, length, page_row, slot)
    last = jax.lax.dynamic_index_in_dim(x, S + length - 1, axis=0, keepdims=True)
    logits = head(c, params, jnp.concatenate([x[:S], last]))
    return logits[:S], logits[S], {}, {"k": kd, "v": vd, "ssm": ssm, "conv": conv}


# this model's own counters beside those every model's engine keeps: the slot state read and written (every
# slot's, every layer's, every step), and the chunks of ``mamba_chunk_size`` positions that prefills put
# through the chunked scan (a rung's, pad and all: the scan runs over the whole rung)
STEP_COUNTERS = ("ssm_state_bytes_rw", "prefill_scan_chunks")


def step_counters(config: FalconH1Config, cache, lengths, counts) -> Dict[str, int]:
    return {"ssm_state_bytes_rw": 2 * cache.state_bytes_per_slot() * cache.num_slots}


def prefill_counters(config: FalconH1Config, bucket: int) -> Dict[str, int]:
    return {"prefill_scan_chunks": bucket // config.mamba_chunk_size}
