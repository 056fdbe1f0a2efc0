"""LongCat-Flash (``longcat_flash``): a layer of TWO latent-attention sublayers
whose routed branch leaves after the first and returns at the layer's end, over
a softmax router some of whose outputs are ZERO-COMPUTE identity experts.

A layer is not attention -> feed-forward.  With ``x`` the float32 residual
stream (pre-norm, RMSNorm, an untied head, a final norm), layer ``l``::

    for i in (0, 1):                                  # two sublayers; attention index a = 2 l + i
        x   = x + MLA_a(norm(x; w_in[i]))
        h_i = norm(x; w_post[i])
        if i == 0:  m = Routed(h_0)                   # the shortcut branch leaves here ...
        x   = x + SwiGLU_i(h_i)                       # dense, ``ffn_hidden_size`` wide
    x = x + m                                         # ... and returns at the layer's end

(the source computes ``m`` beside the second sublayer to hide the experts'
exchange behind it; one chip has no exchange and the order of the sums is the
source's).  ``MLA_a`` is ``models/mla.py``'s block on this model's numbers
(``LongcatFlashConfig.mla``): plain rotary frequencies of ``rope_theta`` over
interleaved pairs, the score scale ``qk_head_dim ** -0.5``, and the source's two
multipliers, ``(hidden_size / q_lora_rank) ** 0.5`` on the normed ``c_q`` and
``(hidden_size / kv_lora_rank) ** 0.5`` on the normed latent (``mla_scale_q_lora``
/ ``mla_scale_kv_lora``; ``k_pe`` is not scaled): expanded in prefill, absorbed
in decode, the cache row ``c | k_pe`` after the multiplier and the rotary.

``Routed(h)``: ``p = softmax(h W_r)`` in float32 over ALL ``num_experts +
zero_expert_num`` outputs (no router bias term); the ``num_experts_per_tok``
largest of ``p + b`` (``b`` the selection bias, ``e_score_correction_bias``: it
chooses, it does not weigh); gates ``g_j = routed_scaling_factor x p_j``, not
renormalised (``moe.dropless.route_softmax_biased``); then::

    Routed(h) = sum over kept j with id_j < num_experts of g_j E_id_j(h)
              + (sum over kept j with id_j >= num_experts of g_j) h

``E_e`` a SwiGLU of ``expert_ffn_hidden_size``; the second term is the
zero-compute experts (``zero_expert_type`` "identity",
``moe.dropless.identity_experts``); no shared expert.  So the SwiGLU pairs a
token costs are not ``k`` but vary from 0 to ``k``.

**The cache.**  A model layer owns TWO layers of the latent pool: sublayer ``i``
of layer ``l`` keeps its rows in pool layer ``2 l + i`` (``cache_config``:
``layers = 2 x num_layers``), so admission, ``latent_bytes_read`` and every
"x layers" of a reader count sublayers.

**A chip's share.**  ``num_experts`` is what the model has (the router scores
``num_experts + zero_expert_num`` outputs), ``experts_held`` /
``first_expert_held`` which of the real ones this tree holds; a pair on an expert
held elsewhere adds nothing here.  The identity part needs no weight and no
exchange, so it is computed HERE, whole, for every token of this chip: in the sum
over shares it counts once, as a shared expert does.  ``vocab_size`` is the rows
of embedding and head held here.

Precision: weights and matmul operands ``config.dtype`` (bfloat16) with float32
accumulation; residual stream, norms, multipliers, rotary, router, selection
bias, gates, the identity part and softmax float32.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..moe.dropless import identity_experts, in_row_pieces, route_softmax_biased, routed_experts
from . import mla
from .blocks import F32, ROUTED_DOWN_GAIN, _mm, rmsnorm, swiglu

__all__ = [
    "LongcatFlashConfig", "init_params", "selection_bias", "embed", "head", "routed_branch", "layer", "cache_config",
    "prefill_chunk", "decode_kernels", "serve_prefill", "serve_decode", "STEP_COUNTERS", "step_counters",
    "prefill_counters", "SUBLAYERS", "BIAS_OVER_UNIFORM", "SCORE_DEVIATION",
]

SUBLAYERS = 2       # attention sublayers (and dense SwiGLUs, and pool layers) a model layer
# The selection bias ``b``, by the init rule (the source's buffer is trained): a normal's quantiles of deviation
# ``BIAS_OVER_UNIFORM / outputs`` (a flat router's probability is ``1 / outputs``, and the kept ones lie some ten times
# over it), the SAME multiset within every ``experts_held`` contiguous real experts (one chip's share) and one more over
# the identity outputs, in orders the seed draws: it changes the kept set of most tokens while every share, and so
# every seed, holds the same biases.
BIAS_OVER_UNIFORM = 2.0
# The attention's matrices behind the two LoRA multipliers.  With every matrix at variance 1 / fan-in the multipliers (2 on
# ``c_q``, 3.46 on the latent) give scores of deviation 5.8 and values 3.46 times the stream's size: a softmax that peaked
# turns the rounding of a bfloat16 operand into several per cent of a probability, and the sound program read 0.26 of the
# largest logit from its float32 reference on the chip (0.10 at a quarter of the widths on the CPU; PERF.md section 6, PR
# 54).  A trained model's ``W_qb`` and ``W_kvb`` have grown up under the multipliers; the init rule draws them as if they
# had: ``W_qb`` ``SCORE_DEVIATION ** 0.5 / s_q`` and ``W_uk`` ``SCORE_DEVIATION ** 0.5 / s_kv`` times as wide, so that the
# scores ``q . k / sqrt(192)`` have THIS deviation (MiMo-V2's and Laguna's rule and number: at unit scores a softmax over a
# thousand positions is flat and no fault of the attention shows), and ``W_uv`` ``1 / s_kv`` times as wide (values of the
# stream's size).  The multipliers stay where the source has them: dropping either still halves, or thirds, the scores.
SCORE_DEVIATION = 2.0


@dataclasses.dataclass(frozen=True)
class LongcatFlashConfig:
    vocab_size: int = 131072            # rows of the embedding and of the (untied) head held here
    hidden_size: int = 6144
    num_layers: int = 28                # model layers: two attention sublayers each
    ffn_hidden_size: int = 12288        # each of a layer's two dense SwiGLUs
    expert_ffn_hidden_size: int = 2048  # width of one routed expert
    num_experts: int = 512              # ``n_routed_experts``: the experts with weights the model has ...
    zero_expert_num: int = 256          # ... and the router's further outputs, the identity experts
    num_experts_per_tok: int = 12       # ``moe_topk``, over both kinds
    routed_scaling_factor: float = 6.0
    experts_held: int = 512             # the contiguous real experts this tree holds
    first_expert_held: int = 0
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    rope_theta: float = 1e7
    rms_norm_eps: float = 1e-5
    prefill_chunk: int = 128            # the prefill ladder's first rung: the flash forward's smallest whole block
    dtype: Any = jnp.bfloat16           # weights, matmul operands and the cache's rows

    def __post_init__(self):
        if not (0 <= self.first_expert_held and self.first_expert_held + self.experts_held <= self.num_experts
                and self.experts_held > 0):
            raise ValueError(f"experts {self.first_expert_held}..{self.first_expert_held + self.experts_held} "
                             f"are not among the model's {self.num_experts}")
        if self.zero_expert_num < 0 or not 0 < self.num_experts_per_tok <= self.router_outputs:
            raise ValueError(f"{self.num_experts_per_tok} of {self.num_experts} + {self.zero_expert_num} outputs a token")
        if self.qk_rope_head_dim % 2:
            raise ValueError("the rotary part of a head is made of pairs")

    @property
    def router_outputs(self) -> int:
        """What the router scores: the real experts, then the identity experts."""
        return self.num_experts + self.zero_expert_num

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def attention_layers(self) -> int:
        """The latent pool's layers: one an attention sublayer."""
        return SUBLAYERS * self.num_layers

    @property
    def mla(self) -> mla.LatentAttention:
        """The latent-attention block on this model's numbers: plain frequencies of ``rope_theta``, the score scale
        ``qk_head_dim ** -0.5``, the two LoRA multipliers where the config switches them on."""
        dim = self.qk_rope_head_dim
        return mla.LatentAttention(
            hidden_size=self.hidden_size, num_attention_heads=self.num_attention_heads, q_lora_rank=self.q_lora_rank,
            kv_lora_rank=self.kv_lora_rank, qk_nope_head_dim=self.qk_nope_head_dim, qk_rope_head_dim=dim,
            v_head_dim=self.v_head_dim, softmax_scale=self.qk_head_dim ** -0.5, rms_norm_eps=self.rms_norm_eps, dtype=self.dtype,
            inv_freq=(self.rope_theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)).astype(np.float32),
            q_scale=(self.hidden_size / self.q_lora_rank) ** 0.5 if self.mla_scale_q_lora else 1.0,
            kv_scale=(self.hidden_size / self.kv_lora_rank) ** 0.5 if self.mla_scale_kv_lora else 1.0)


# ------------------------------------------------------------------ parameters
def selection_bias(config: LongcatFlashConfig, key):
    """``b`` (router_outputs,) float32 by the init rule (``BIAS_OVER_UNIFORM``): every share's quantiles, and the
    identity outputs', each in a seeded order."""
    c = config
    if c.num_experts % c.experts_held:
        raise ValueError(f"the init rule draws the selection bias a share at a time: {c.experts_held} held does not divide "
                         f"{c.num_experts}")

    def blocks(k, count, size):
        quantiles = jax.scipy.stats.norm.ppf((jnp.arange(size, dtype=F32) + 0.5) / size) * (BIAS_OVER_UNIFORM / c.router_outputs)
        order = jax.vmap(lambda kk: jax.random.permutation(kk, size))(jax.random.split(k, count))
        return jnp.take(quantiles, order).reshape(count * size)

    k_real, k_zero = jax.random.split(key)
    parts = [blocks(k_real, c.num_experts // c.experts_held, c.experts_held)]
    if c.zero_expert_num:
        parts.append(blocks(k_zero, 1, c.zero_expert_num))
    return jnp.concatenate(parts).astype(F32)


def init_params(config: LongcatFlashConfig, key) -> Dict[str, Any]:
    """Seeded random weights in the types they are served in (jit the call: the
    float32 draws are then temporaries).  Matrices are normal with variance 1 /
    fan-in, but for: the embedding (unit variance: the stream starts at the size
    the branches add to it); the router (float32) twice as wide, so that its
    softmax over all outputs is not flat; the routed experts' down projections
    ``ROUTED_DOWN_GAIN`` times as wide (the constant says why); the selection
    bias by its rule (``BIAS_OVER_UNIFORM``); ``W_qb``, ``W_uk`` and ``W_uv`` as
    narrow as their LoRA multiplier is large (``SCORE_DEVIATION``)."""
    c, dt = config, config.dtype
    E, a = c.hidden_size, c.mla
    attention_gains = {"q_b": SCORE_DEVIATION ** 0.5 / a.q_scale, "kv_b_k": SCORE_DEVIATION ** 0.5 / a.kv_scale,
                       "kv_b_v": 1.0 / a.kv_scale}

    def normal(k, shape, fan_in, dtype=dt, gain=1.0):
        return (jax.random.normal(k, shape, F32) * (gain / math.sqrt(fan_in))).astype(dtype)

    def swiglu_params(k, width):
        ks = jax.random.split(k, 3)
        return {"gate": normal(ks[0], (E, width), E), "up": normal(ks[1], (E, width), E),
                "down": normal(ks[2], (width, E), width)}

    def moe(k):
        ks = jax.random.split(k, 5)
        F, held = c.expert_ffn_hidden_size, c.experts_held
        return {"router": normal(ks[0], (E, c.router_outputs), E, F32, gain=2.0), "router_bias": selection_bias(c, ks[4]),
                "w_gate": normal(ks[1], (held, E, F), E), "w_up": normal(ks[2], (held, E, F), E),
                "w_down": normal(ks[3], (held, F, E), F, gain=ROUTED_DOWN_GAIN)}

    params: Dict[str, Any] = {
        "embed_tokens": {"embedding": normal(jax.random.fold_in(key, 1 << 20), (c.vocab_size, E), 1.0)},
        "lm_head": {"kernel": normal(jax.random.fold_in(key, 1 << 21), (E, c.vocab_size), E)},
        "norm": {"weight": jnp.ones((E,), dt)},
    }
    for l in range(c.num_layers):
        k_moe, *ks = jax.random.split(jax.random.fold_in(key, l), 1 + 2 * SUBLAYERS)
        lp: Dict[str, Any] = {"mlp": moe(k_moe)}
        for i in range(SUBLAYERS):
            lp[f"input_layernorm_{i}"] = {"weight": jnp.ones((E,), dt)}
            lp[f"post_attention_layernorm_{i}"] = {"weight": jnp.ones((E,), dt)}
            lp[f"self_attn_{i}"] = mla.attention_params(a, ks[2 * i], attention_gains)
            lp[f"mlps_{i}"] = swiglu_params(ks[2 * i + 1], c.ffn_hidden_size)
        params[f"layers_{l}"] = lp
    return params


# ------------------------------------------------------------ embedding, head
def embed(config: LongcatFlashConfig, params, tokens):
    return jnp.take(params["embed_tokens"]["embedding"], tokens, axis=0).astype(F32)


def head(config: LongcatFlashConfig, params, x):
    """Logits (float32) over the rows of the vocabulary held here."""
    return _mm(rmsnorm(x, params["norm"]["weight"], config.rms_norm_eps), params["lm_head"]["kernel"], config.dtype)


# ------------------------------------------------------------ the routed branch
def routed_branch(c: LongcatFlashConfig, ep, h, token_mask=None):
    """``Routed(h)`` for tokens ``h`` (N, E): the held real experts' part through
    the dropless layer, and the identity experts' part, whole; a long rung in
    ``dropless.in_row_pieces`` (at most 1,024 rows at the published widths: the
    sorted form is sized for every kept pair landing here, 12 a row, where a
    share's mean is a few in a hundred).  Returns the sum (N, E) float32, how
    many tokens each held expert got (held,), and how many kept pairs of the
    tokens that route (``token_mask``) fell on identity experts."""
    return in_row_pieces(lambda rows, mask: _routed_rows(c, ep, rows, mask), h, token_mask, k=c.num_experts_per_tok)


def _routed_rows(c: LongcatFlashConfig, ep, h, token_mask):
    def route(scores):
        idx, gates = route_softmax_biased(scores, c.num_experts_per_tok, scale=c.routed_scaling_factor, bias=ep["router_bias"])
        return idx, gates, idx, gates

    routed, counts, idx, gates = routed_experts(h, ep["router"], route, ep["w_gate"], ep["w_up"], ep["w_down"],
                                                first_held=c.first_expert_held, token_mask=token_mask, dtype=c.dtype)
    same, zero_pairs = identity_experts(h, idx, gates, first_identity=c.num_experts, token_mask=token_mask)
    return routed + same, counts, zero_pairs


# ------------------------------------------------------------------ a whole layer
def layer(c: LongcatFlashConfig, lp, x, live, attend: Callable):
    """One model layer over the stream ``x`` (N, E) float32: ``attend(i, u)`` is
    sublayer ``i``'s attention over the normed stream ``u`` (the expanded form
    over a sequence, or the absorbed step over the pool: the caller's closure
    keeps what it leaves in the cache); ``live`` (N,) the rows that route to
    experts.  Returns the stream and the routed branch's two counts."""
    for i in range(SUBLAYERS):
        with jax.named_scope("vs.attn"):
            x = x + attend(i, rmsnorm(x, lp[f"input_layernorm_{i}"]["weight"], c.rms_norm_eps))
        h = rmsnorm(x, lp[f"post_attention_layernorm_{i}"]["weight"], c.rms_norm_eps)
        if i == 0:
            with jax.named_scope("vs.moe"):
                shortcut, *counts = routed_branch(c, lp["mlp"], h, token_mask=live)
        with jax.named_scope("vs.mlp"):
            mp = lp[f"mlps_{i}"]
            x = x + swiglu(h, mp["gate"], mp["up"], mp["down"], c.dtype)
    return x + shortcut, counts


# ------------------------------------------- what the serve engine asks of a model
# (``serve/hybrid_engine.py``, "The seam")
def cache_config(config: LongcatFlashConfig, *, num_slots: int, page_size: int, pages_per_slot: int,
                 num_pages: Optional[int] = None):
    """A latent pool of every SUBLAYER's rows (two pool layers a model layer), no value pool, no slot state."""
    return mla.cache_config(config.mla, layers=config.attention_layers, num_slots=num_slots, page_size=page_size,
                            pages_per_slot=pages_per_slot, num_pages=num_pages)


def prefill_chunk(config: LongcatFlashConfig) -> int:
    """The prefill ladder's first rung."""
    return config.prefill_chunk


def decode_kernels(config: LongcatFlashConfig, cache) -> Dict[str, Any]:
    """``{"decode": the ``interpret`` flag of ``paged_decode_latent``, or None for the XLA leg}``."""
    return {"decode": mla.decode_kernel(config.mla, cache)}


def serve_prefill(c: LongcatFlashConfig, params, arrays, tokens, length, page_row, slot, *, page: int,
                  interpret: Optional[bool] = None):
    """The prefill program's body: ``tokens`` (rung,) through the stack in the
    expanded form; every sublayer's rows go to the slot's pages of its pool
    layer (``page_row``: what lies past its reserved pages is the null page).
    Pad positions route to no expert and get no identity part.  Returns the last
    real position's logits row and the cache's arrays."""
    a = c.mla
    live = jnp.arange(tokens.shape[0], dtype=jnp.int32) < length
    x = embed(c, params, tokens)
    kept = []

    def attend(i, u, ap):
        y, rows = mla.mla_prefill(a, ap, u, interpret=interpret)
        kept.append(rows)
        return y

    for l in range(c.num_layers):
        lp = params[f"layers_{l}"]
        x, _ = layer(c, lp, x, live, lambda i, u, lp=lp: attend(i, u, lp[f"self_attn_{i}"]))
    last = jax.lax.dynamic_index_in_dim(x, length - 1, axis=0, keepdims=True)
    pool = arrays["k"]
    pages = jnp.stack(kept).reshape(len(kept), -1, page, 1, a.cache_row)
    return head(c, params, last)[0], {"k": pool.at[:, page_row].set(pages.astype(pool.dtype))}


def serve_decode(c: LongcatFlashConfig, params, arrays, table, lengths, tokens, *, active, write_page, write_offset,
                 kernels: Dict[str, Any]):
    """The decode program's body, one token a slot in the absorbed form, two
    pool layers a model layer.  Returns the logits (S, vocab), the step's counts
    ``{"experts": (layers, held) tokens an expert got, "zero": (layers,) kept
    pairs on identity experts}`` and the cache's arrays."""
    a = c.mla
    x = embed(c, params, tokens)                    # (S, E)
    pool, experts, zero = arrays["k"], [], []

    def attend(i, u, ap, l):
        nonlocal pool
        y, pool = mla.mla_step(a, ap, u, pool, layer=SUBLAYERS * l + i, table=table, page=write_page, offset=write_offset,
                               positions=lengths, valid_len=lengths + 1, interpret=kernels["decode"])
        return y

    for l in range(c.num_layers):
        lp = params[f"layers_{l}"]
        x, counts = layer(c, lp, x, active, lambda i, u, lp=lp, l=l: attend(i, u, lp[f"self_attn_{i}"], l))
        experts.append(counts[0])
        zero.append(counts[1])
    return head(c, params, x), {"experts": jnp.stack(experts), "zero": jnp.stack(zero)}, {"k": pool}


# this model's own counters beside those every model's engine keeps (``HybridServeEngine.trace_counters``, whose
# ``moe_assignments`` counts all ``num_experts_per_tok`` pairs a token, identity ones among them).  Of the decode steps
# read: the latent pages their attention had to read (live pages x page bytes x 2 x ``num_layers``: every SUBLAYER reads
# its own pool layer) and the kept pairs of active rows that fell on identity experts.  Of the prefills: causal
# attention's useful operations at the real widths over the rung, both sublayers.
STEP_COUNTERS = ("latent_bytes_read", "prefill_attn_flops", "zero_expert_assignments")


def step_counters(config: LongcatFlashConfig, cache, lengths: np.ndarray, counts: Dict[str, np.ndarray]) -> Dict[str, int]:
    return {"latent_bytes_read": mla.latent_bytes_read(config.mla, cache, lengths, config.attention_layers),
            "zero_expert_assignments": int(np.asarray(counts["zero"]).sum())}


def prefill_counters(config: LongcatFlashConfig, bucket: int) -> Dict[str, int]:
    return {"prefill_attn_flops": mla.prefill_attn_flops(config.mla, bucket, config.attention_layers)}
