"""Ling hybrid (``bailing_hybrid``; Ling-3.0-flash): a decoder whose mixers are
delta-rule LINEAR attention (Kimi Delta Attention) in five layers of six and
multi-head LATENT attention in the sixth, and whose feed-forward, after the
leading dense layers, is a sigmoid-routed, group-limited expert layer beside
one shared expert.

Both blocks are another file's: ``models/kda.py`` (the delta rule: a float32
matrix state a head and slot, a convolution tail) and ``models/mla.py`` (the
latent block DeepSeek-V2 and LongCat-Flash have, here without a query LoRA and
with a head-wise gate on the value output); this file gives them the model's
numbers (``LingHybridConfig.kda``, ``.mla``), walks the stack, and is what
``serve.HybridServeEngine`` asks of a model's module (the last section).  The
cache is the first that is a LATENT pool AND slot state: ``cache.k`` holds the
latent layers' rows (one layer in six), ``cache.state`` the delta-rule layers'
states and tails.

Layer ``i`` (0-based, of the SOURCE's stack: ``first_layer`` is the source's
index of this tree's layer 0, for a cut that starts past the source's first
layer) is latent iff ``(i + 1) % layer_group_size == 0``, else delta-rule; the
tree's layers before ``first_k_dense_replace`` have a dense SwiGLU of
``intermediate_size``, every other an expert layer.  Pre-norm residual, an
untied head, a final norm::

    h += mixer(rms(h));  h += ffn(rms(h))

**The latent mixer**: ``mla.mla_prefill`` / ``mla.mla_step`` with ``q = W_q u``
(no LoRA, no norm), plain rotary frequencies of ``rope_theta`` over interleaved
pairs, score scale ``(nope + rope)^-1/2``, and ``head_gate = sigmoid(W_g u)``
(E -> H) on each head's value output before ``W_o``.

**The expert layer** (the source's ``noaux_tc``): ``s = sigmoid(W_r u)`` in
float32 over ALL ``num_experts``; the choice on ``s + bias``; ``n_group`` groups
scored by the sum of their two best, ``topk_group`` kept, the ``k`` best of
those, gates ``s`` renormalised times ``routed_scaling_factor``
(``moe.dropless.route_sigmoid_group_limited``); plus the shared SwiGLU whole.
A chip's share: ``num_experts`` is what the router scores, ``experts_held`` /
``first_expert_held`` which of them this tree holds (whole routing groups: the
group-limited router was designed to be placed so); ``vocab_size`` the rows of
embedding and head held here.  A long prompt goes through the routed part in
``dropless.in_row_pieces`` (at most 4,096 rows at the published widths: the
dropless layer sizes its sorted form for every kept pair landing here, eight a
row, where a share's mean is one).

Precision: weights and matmul operands ``dtype`` (bfloat16) with float32
accumulation; residual stream, norms, rotary, gates, router, softmax and the
delta rule float32.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..moe.dropless import in_row_pieces, route_sigmoid_group_limited, routed_experts
from . import kda, mla
from .blocks import F32, ROUTED_DOWN_GAIN, _mm, rmsnorm, swiglu

__all__ = [
    "LingHybridConfig", "init_params", "selection_bias", "embed", "head", "expert_layer", "cache_config", "prefill_chunk",
    "decode_kernels", "serve_prefill", "serve_decode", "STEP_COUNTERS", "step_counters", "prefill_counters",
    "SCORE_DEVIATION", "BIAS_DEVIATION", "HELD_DOWN_GAIN",
]

# The init rule (a trained model's are not random; ``benchmark/configs/*.json`` state it under ``assumed``).  The latent
# mixer's ``W_q`` and ``W_uk`` each ``SCORE_DEVIATION ** 0.5`` times as wide, so that the scores ``q . k / sqrt(192)`` have
# this deviation (MiMo-V2's, Laguna's and LongCat's rule and number: at unit scores a softmax over a thousand positions is
# flat and no fault of the attention shows).
SCORE_DEVIATION = 2.0
# The selection bias: every share's quantiles of a normal of this deviation, in a seeded order (MiMo-V2's rule): it changes
# the kept groups and experts of most tokens while every share, and so every seed, holds the same biases.
BIAS_DEVIATION = 0.02
# The routed experts' down projections: ``blocks.ROUTED_DOWN_GAIN`` is reckoned for a tree that holds every expert.  Here
# an eighth is held (one routing group) and a token reaches ONE held expert on the mean, under gates that sum to 2.5: eight
# times wider, the routed part is a few per cent of the stream, so a routing group swapped shows and one (token, expert)
# pair that rounding moves across the cut stays near the size of rounding.
HELD_DOWN_GAIN = 8.0 * ROUTED_DOWN_GAIN


@dataclasses.dataclass(frozen=True)
class LingHybridConfig:
    vocab_size: int = 157184            # rows of the embedding and of the (untied) head held here
    hidden_size: int = 2560
    num_hidden_layers: int = 42
    first_layer: int = 0                # the source's index of this tree's layer 0 (a cut may start past it)
    layer_group_size: int = 6           # one latent layer closes every group of this many
    first_k_dense_replace: int = 2      # this tree's layers before the first expert layer
    intermediate_size: int = 6144       # the dense layers' MLP
    moe_intermediate_size: int = 768    # width of one routed expert
    moe_shared_expert_intermediate_size: int = 768
    num_shared_experts: int = 1
    num_experts: int = 512              # the router's outputs: every expert the model has ...
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    experts_held: int = 512             # ... and the contiguous ids this tree holds
    first_expert_held: int = 0
    num_attention_heads: int = 32
    head_dim: int = 128                 # a delta-rule head's d_k = d_v
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 6e6
    rms_norm_eps: float = 1e-6
    prefill_chunk: int = 128            # the prefill ladder's first rung: a chunk of the delta rule, a flash block
    dtype: Any = jnp.bfloat16           # weights, matmul operands, the latent rows and the convolution tails
    state_dtype: Any = jnp.float32      # the delta-rule states

    def __post_init__(self):
        per = self.num_experts // self.n_group
        if self.num_experts % self.n_group or not 0 < self.topk_group <= self.n_group:
            raise ValueError(f"{self.num_experts} experts do not lie in {self.n_group} groups of which {self.topk_group} are kept")
        if not (0 <= self.first_expert_held and self.first_expert_held + self.experts_held <= self.num_experts
                and self.experts_held > 0) or self.first_expert_held % per or self.experts_held % per:
            raise ValueError(f"experts {self.first_expert_held}..{self.first_expert_held + self.experts_held} are not whole "
                             f"routing groups of {per} among the router's {self.num_experts}")
        if self.qk_rope_head_dim % 2:
            raise ValueError("the rotary part of a head is made of pairs")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace counts this tree's leading dense layers")
        if self.kda_lower_bound >= 0:
            raise ValueError("the gate's lower bound is a negative log-decay")
        if not self.latent_layers:
            raise ValueError("a stack without a latent layer has no pool: the cache's pages would belong to nobody")

    def is_latent(self, layer: int) -> bool:
        return (self.first_layer + layer + 1) % self.layer_group_size == 0

    @property
    def latent_layers(self) -> Tuple[int, ...]:
        return tuple(l for l in range(self.num_hidden_layers) if self.is_latent(l))

    @property
    def delta_layers(self) -> Tuple[int, ...]:
        return tuple(l for l in range(self.num_hidden_layers) if not self.is_latent(l))

    @property
    def expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def groups_held(self) -> Tuple[int, ...]:
        per = self.num_experts // self.n_group
        return tuple(range(self.first_expert_held // per, (self.first_expert_held + self.experts_held) // per))

    @property
    def kda(self) -> kda.DeltaAttention:
        return kda.DeltaAttention(
            hidden_size=self.hidden_size, num_heads=self.num_attention_heads, head_dim=self.head_dim,
            conv_kernel=self.short_conv_kernel_size, lower_bound=self.kda_lower_bound, rms_norm_eps=self.rms_norm_eps,
            dtype=self.dtype, state_dtype=self.state_dtype)

    @property
    def mla(self) -> mla.LatentAttention:
        """The latent block on this model's numbers: no query LoRA, the plain frequencies of ``rope_theta``."""
        rope = self.qk_rope_head_dim
        return mla.LatentAttention(
            hidden_size=self.hidden_size, num_attention_heads=self.num_attention_heads, q_lora_rank=None,
            kv_lora_rank=self.kv_lora_rank, qk_nope_head_dim=self.qk_nope_head_dim, qk_rope_head_dim=rope,
            v_head_dim=self.v_head_dim, inv_freq=(self.rope_theta ** (-np.arange(0, rope, 2, dtype=np.float64) / rope)).astype(np.float32),
            softmax_scale=(self.qk_nope_head_dim + rope) ** -0.5, rms_norm_eps=self.rms_norm_eps, dtype=self.dtype)


# ------------------------------------------------------------------ parameters
def selection_bias(config: LingHybridConfig, key):
    """The router's selection bias (num_experts,) float32 by the init rule (``BIAS_DEVIATION``): every share's quantiles,
    in a seeded order."""
    c = config
    held = c.experts_held
    quantiles = jax.scipy.stats.norm.ppf((jnp.arange(held, dtype=F32) + 0.5) / held) * BIAS_DEVIATION
    order = jax.vmap(lambda k: jax.random.permutation(k, held))(jax.random.split(key, c.num_experts // held))
    return jnp.take(quantiles, order).reshape(c.num_experts).astype(F32)


def init_params(config: LingHybridConfig, key) -> Dict[str, Any]:
    """Seeded random weights in the types they are served in (jit the call).
    Matrices are normal with variance 1 / fan-in, but for: the embedding (unit
    variance: the stream starts at the size the branches add to it); the router
    (float32) twice as wide; the routed experts' down projections
    ``HELD_DOWN_GAIN`` times as wide; the latent mixer's ``W_q`` and ``W_uk``
    ``SCORE_DEVIATION ** 0.5`` times as wide each; the delta rule's gate by
    ``models/kda.py``'s rule; the selection bias by ``BIAS_DEVIATION``."""
    c, dt = config, config.dtype
    E = c.hidden_size

    def normal(k, shape, fan_in, dtype=dt, gain=1.0):
        return (jax.random.normal(k, shape, F32) * (gain / math.sqrt(fan_in))).astype(dtype)

    def swiglu_params(k, width):
        ks = jax.random.split(k, 3)
        return {"gate": normal(ks[0], (E, width), E), "up": normal(ks[1], (E, width), E), "down": normal(ks[2], (width, E), width)}

    def moe(k):
        ks = jax.random.split(k, 6)
        F, held = c.moe_intermediate_size, c.experts_held
        return {"router": normal(ks[0], (E, c.num_experts), E, F32, gain=2.0), "router_bias": selection_bias(c, ks[4]),
                "w_gate": normal(ks[1], (held, E, F), E), "w_up": normal(ks[2], (held, E, F), E),
                "w_down": normal(ks[3], (held, F, E), F, gain=HELD_DOWN_GAIN),
                "shared": swiglu_params(ks[5], c.num_shared_experts * c.moe_shared_expert_intermediate_size)}

    def latent(k):
        k_attn, k_gate = jax.random.split(k)
        widen = SCORE_DEVIATION ** 0.5
        return {**mla.attention_params(c.mla, k_attn, {"q": widen, "kv_b_k": widen}),
                "gate": normal(k_gate, (E, c.num_attention_heads), E)}

    params: Dict[str, Any] = {
        "embed_tokens": {"embedding": normal(jax.random.fold_in(key, 1 << 20), (c.vocab_size, E), 1.0)},
        "lm_head": {"kernel": normal(jax.random.fold_in(key, 1 << 21), (E, c.vocab_size), E)},
        "norm": {"weight": jnp.ones((E,), dt)},
    }
    for l in range(c.num_hidden_layers):
        k_mixer, k_mlp = jax.random.split(jax.random.fold_in(key, l))
        params[f"layers_{l}"] = {
            "input_layernorm": {"weight": jnp.ones((E,), dt)},
            "post_attention_layernorm": {"weight": jnp.ones((E,), dt)},
            "mixer": latent(k_mixer) if c.is_latent(l) else kda.mixer_params(c.kda, k_mixer),
            "mlp": moe(k_mlp) if l >= c.first_k_dense_replace else swiglu_params(k_mlp, c.intermediate_size),
        }
    return params


# ------------------------------------------------------------ embedding, head
def embed(config: LingHybridConfig, params, tokens):
    return jnp.take(params["embed_tokens"]["embedding"], tokens, axis=0).astype(F32)


def head(config: LingHybridConfig, params, x):
    """Logits (float32) over the rows of the vocabulary held here."""
    return _mm(rmsnorm(x, params["norm"]["weight"], config.rms_norm_eps), params["lm_head"]["kernel"], config.dtype)


# ---------------------------------------------------------------- feed-forward
def _routed_rows(c: LingHybridConfig, ep, h, token_mask):
    route = lambda scores: route_sigmoid_group_limited(
        scores, c.num_experts_per_tok, n_group=c.n_group, topk_group=c.topk_group, scale=c.routed_scaling_factor,
        bias=ep["router_bias"])
    routed, counts, kept = routed_experts(h, ep["router"], route, ep["w_gate"], ep["w_up"], ep["w_down"],
                                          first_held=c.first_expert_held, token_mask=token_mask, dtype=c.dtype)
    here = jnp.any(kept[:, np.asarray(c.groups_held, np.int32)], axis=-1)
    if token_mask is not None:
        here = here & token_mask
    return routed, counts, jnp.sum(here.astype(jnp.int32))


def expert_layer(c: LingHybridConfig, ep, h, token_mask=None):
    """``sum over kept and held e of g_e E_e(h) + S(h)`` for tokens ``h`` (N,
    E), the routed part in ``dropless.in_row_pieces``.  Returns the sum (N, E)
    float32, how many tokens each held expert got (held,), and how many of the
    tokens that route kept a group held here (a scalar)."""
    with jax.named_scope("vs.routed"):
        routed, counts, here = in_row_pieces(lambda rows, mask: _routed_rows(c, ep, rows, mask), h, token_mask,
                                             k=c.num_experts_per_tok)
    shared = ep["shared"]
    return routed + swiglu(h, shared["gate"], shared["up"], shared["down"], c.dtype), counts, here


def _feed_forward(c: LingHybridConfig, lp, l: int, x, token_mask):
    """The layer's second half on the stream ``x``; of an expert layer also ``(counts, rows that kept a held group)``."""
    h = rmsnorm(x, lp["post_attention_layernorm"]["weight"], c.rms_norm_eps)
    if l < c.first_k_dense_replace:
        mp = lp["mlp"]
        with jax.named_scope("vs.mlp"):
            return x + swiglu(h, mp["gate"], mp["up"], mp["down"], c.dtype), None
    with jax.named_scope("vs.moe"):
        y, counts, here = expert_layer(c, lp["mlp"], h, token_mask=token_mask)
    return x + y, (counts, here)


def _head_gate(c: LingHybridConfig, mp, u):
    """The latent mixer's head-wise output gate (rows, H) float32."""
    return jax.nn.sigmoid(_mm(u, mp["gate"], c.dtype))


# ------------------------------------------- what the serve engine asks of a model
# (``serve/hybrid_engine.py``, "The seam")
def cache_config(config: LingHybridConfig, *, num_slots: int, page_size: int, pages_per_slot: int,
                 num_pages: Optional[int] = None):
    """A latent pool of the latent layers' rows (no value pool) AND slot state:
    every delta-rule layer's matrix state and convolution tail."""
    c = config
    base = mla.cache_config(c.mla, layers=len(c.latent_layers), num_slots=num_slots, page_size=page_size,
                            pages_per_slot=pages_per_slot, num_pages=num_pages)
    return dataclasses.replace(base, slot_state=kda.slot_state(c.kda, len(c.delta_layers)))


def prefill_chunk(config: LingHybridConfig) -> int:
    """The prefill ladder's first rung."""
    return config.prefill_chunk


def decode_kernels(config: LingHybridConfig, cache) -> Dict[str, Any]:
    """``{"decode": paged_decode_latent's flag over the pool, "kda": kda_step's over the states}``, or None for an XLA leg."""
    return {"decode": mla.decode_kernel(config.mla, cache), "kda": kda.step_kernel(config.kda, cache)}


def serve_prefill(c: LingHybridConfig, params, arrays, tokens, length, page_row, slot, *, page: int,
                  interpret: Optional[bool] = None):
    """The prefill program's body: ``tokens`` (rung,) through the stack, a
    delta-rule layer by ``kda_chunk`` from a zero state and a latent layer in
    the expanded form; the latent rows go to the slot's pages (``page_row``: what
    lies past its reserved pages is the null page), the slot's rows of the
    states and tails are rewritten from where the prompt ends.  Pad positions
    follow the real ones, leave the states as they were and route to no expert.
    Returns the last real position's logits row and the cache's arrays."""
    T = tokens.shape[0]
    chunk_leg = kda.chunk_kernel(c.kda, T) if interpret is None else interpret
    live = jnp.arange(T) < length
    x = embed(c, params, tokens)
    rows, states, tails = [], [], []
    for l in range(c.num_hidden_layers):
        lp = params[f"layers_{l}"]
        u = rmsnorm(x, lp["input_layernorm"]["weight"], c.rms_norm_eps)
        if c.is_latent(l):
            with jax.named_scope("vs.mla"):
                y, kept = mla.mla_prefill(c.mla, lp["mixer"], u, interpret=interpret, head_gate=_head_gate(c, lp["mixer"], u))
            rows.append(kept)
        else:
            with jax.named_scope("vs.kda"):
                y, state, tail = kda.kda_prefill(c.kda, lp["mixer"], u, length, interpret=chunk_leg)
            states.append(state)
            tails.append(tail)
        x, _ = _feed_forward(c, lp, l, x + y, live)
    last = jax.lax.dynamic_index_in_dim(x, length - 1, axis=0, keepdims=True)
    pool = arrays["k"]
    pages = jnp.stack(rows).reshape(len(rows), -1, page, 1, pool.shape[-1])
    out = dict(arrays, k=pool.at[:, page_row].set(pages.astype(pool.dtype)))
    for name, own in ((kda.STATE, states), (kda.TAIL, tails)):      # (layers, ...) -> the slot's rows of (layers, slots, ...)
        out[name] = jax.lax.dynamic_update_slice_in_dim(arrays[name], jnp.stack(own)[:, None].astype(arrays[name].dtype),
                                                        slot, axis=1)
    return head(c, params, last)[0], out


def serve_decode(c: LingHybridConfig, params, arrays, table, lengths, tokens, *, active, write_page, write_offset,
                 kernels: Dict[str, Any]):
    """The decode program's body, one token a slot: a delta-rule layer moves
    its state one position (``kda_step``; an idle slot's state and tail stand
    bit for bit), a latent layer writes the position's row to the slot's page
    and reads its pages in the absorbed form.  Returns the logits (S, vocab),
    the step's counts ``{"experts": (expert layers, held) tokens an expert got,
    "held_group": (expert layers,) active rows that kept a group held here}``
    and the cache's arrays."""
    x = embed(c, params, tokens)                    # (S, E)
    pool, state, conv = arrays["k"], arrays[kda.STATE], arrays[kda.TAIL]
    experts, here = [], []
    latent = delta = 0
    for l in range(c.num_hidden_layers):
        lp = params[f"layers_{l}"]
        u = rmsnorm(x, lp["input_layernorm"]["weight"], c.rms_norm_eps)
        if c.is_latent(l):
            with jax.named_scope("vs.mla"):
                y, pool = mla.mla_step(c.mla, lp["mixer"], u, pool, layer=latent, table=table, page=write_page,
                                       offset=write_offset, positions=lengths, valid_len=lengths + 1,
                                       interpret=kernels["decode"], head_gate=_head_gate(c, lp["mixer"], u))
            latent += 1
        else:
            with jax.named_scope("vs.kda"):
                y, state, conv = kda.kda_step(c.kda, lp["mixer"], u, state, conv, layer=delta, active=active,
                                              positions=lengths, interpret=kernels["kda"])
            delta += 1
        x, routed = _feed_forward(c, lp, l, x + y, active)
        if routed is not None:
            experts.append(routed[0])
            here.append(routed[1])
    counts = {"experts": jnp.stack(experts), "held_group": jnp.stack(here)} if experts else {}
    return head(c, params, x), counts, dict(arrays, **{"k": pool, kda.STATE: state, kda.TAIL: conv})


# this model's own counters beside those every model's engine keeps (``HybridServeEngine.trace_counters``).  Of the decode
# steps read: the matrix states' bytes read and written (every slot's, idle or not: the kernel moves them all), the latent
# pages the latent layers had to read (the name the other latent families' cells use), the active rows that kept a routing
# group held here, summed over the expert layers.  A prefill adds nothing of its own: the real rows that went through
# ``kda_chunk`` are the engine's ``prefill_tokens_real`` (every row goes through every layer), and what a reader needs of a
# rung it takes from the launch.
STEP_COUNTERS = ("kda_state_bytes_rw", "latent_bytes_read", "route_rows_held_group")


def step_counters(config: LingHybridConfig, cache, lengths: np.ndarray, counts: Dict[str, np.ndarray]) -> Dict[str, int]:
    c = config
    out = {"kda_state_bytes_rw": kda.state_bytes_rw(c.kda, len(lengths), len(c.delta_layers)),
           "latent_bytes_read": mla.latent_bytes_read(c.mla, cache, lengths, len(c.latent_layers))}
    if "held_group" in counts:
        out["route_rows_held_group"] = int(np.asarray(counts["held_group"]).sum())
    return out


def prefill_counters(config: LingHybridConfig, bucket: int) -> Dict[str, int]:
    return {}
