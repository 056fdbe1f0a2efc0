"""DeepSeek-V2 (``deepseek_v2``): a decoder whose attention is multi-head
LATENT attention (MLA) and whose feed-forward, after a leading dense layer, is
a group-limited routed expert layer beside shared experts.

The block is written ONCE, as pure functions over a plain parameter tree
(``init_params``), and both serve programs call them; what other families
share (the norm, the product, the SwiGLU, YaRN's frequencies) is
``models/blocks.py``'s, and the latent-attention block itself, which another
family has too, is ``models/mla.py``'s: this file gives it the model's numbers
(``DeepseekV2Config.mla``: YaRN's frequencies, the score scale with its
``mscale``, 128 heads).  The last section of this file is what
``serve.HybridServeEngine`` asks of a model's module (the cache's geometry,
the bodies of its two programs, the counters of a decode step).

Equations (HF ``modeling_deepseek.py``; ISSUE 34 writes them out).  Pre-norm
residual blocks, an untied head, a final norm.  Attention, every layer::

    c_q = norm(W_qa x);  q_h = W_qb,h c_q = q_nope,h (128) | q_pe,h (64)
    W_kva x = c_kv (512) | k_pe (64, one for all heads);  c = norm(c_kv)
    rotary (YaRN frequencies) on q_pe,h and k_pe, over interleaved pairs
    scale = 192^-0.5 * mscale^2

  * **expanded** (prefill; what the source computes): ``k_h = W_uk,h c | k_pe``,
    ``v_h = W_uv,h c``, causal softmax, ``o = W_o concat_h(P_h v_h)``: scores
    192 wide, values 128 wide, through the blocked flash forward;
  * **absorbed** (decode; the same numbers): ``q'_h = W_uk,h^T q_nope,h |
    q_pe,h`` (576), ``s_h,t = q'_h . row_t``, ``u_h = sum_t p_h,t c_t`` (512),
    ``o = W_o concat_h(W_uv,h u_h)``: all 128 heads read ONE row a position.

The cache row of a position is ``c_t | k_pe,t`` (after the norm, after the
rotary): 576 numbers in ``config.dtype``, padded with zeros to ``cache_row``
(640, whole 128-lane tiles: what the chip's layout of a 576-wide row occupies
anyway), in a paged cache of the latent form (``serve/kv_cache.py``): one pool,
no value pool.  ``kv_b`` is kept as its two halves in the layouts the absorbed
products read (``kv_b_k`` (H, 128, 512), ``kv_b_v`` (H, 512, 128)); the
expanded form multiplies by the same two arrays, so there is no second copy.

Layer 0 is a SwiGLU MLP of ``intermediate_size``; every later layer scores all
``num_experts`` by a float32 softmax, keeps ``topk_group`` of ``n_group``
groups, then ``num_experts_per_tok`` experts, gates not renormalised, times
``routed_scaling_factor`` (``moe.dropless.route_group_limited``), beside one
SwiGLU of ``n_shared_experts x moe_intermediate_size``.

Precision: weights and matmul operands ``config.dtype`` (bfloat16) with
float32 accumulation; residual stream, norms, rotary, router and softmax
float32.  A chip's share: as in ``models/granite_hybrid.py`` (``num_experts``
is what the router scores; ``experts_held`` / ``first_expert_held`` which of
them this tree holds; ``vocab_size`` the rows of embedding and head held here).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..moe.dropless import route_group_limited, routed_experts
from . import mla
from .blocks import F32, ROUTED_DOWN_GAIN, _mm, rmsnorm, swiglu, yarn_inv_freq, yarn_mscale
from .mla import LANES

__all__ = [
    "DeepseekV2Config", "init_params", "embed", "head", "inv_freq", "rotary", "mla_prefill", "mla_step", "dense_mlp",
    "expert_layer", "layer_prefill", "layer_step", "cache_config", "prefill_chunk", "decode_kernels", "serve_prefill",
    "serve_decode", "STEP_COUNTERS", "step_counters", "prefill_counters",
]


@dataclasses.dataclass(frozen=True)
class DeepseekV2Config:
    vocab_size: int = 102400            # rows of the embedding and of the head held here
    hidden_size: int = 5120
    num_hidden_layers: int = 60
    first_k_dense_replace: int = 1      # layers before the first expert layer
    intermediate_size: int = 12288      # the dense layers' MLP
    moe_intermediate_size: int = 1536   # width of one routed expert
    n_shared_experts: int = 2
    num_experts: int = 160              # the router's outputs: every expert the model has
    num_experts_per_tok: int = 6
    n_group: int = 8
    topk_group: int = 3
    routed_scaling_factor: float = 16.0
    experts_held: int = 160             # ... and the contiguous ids this tree holds
    first_expert_held: int = 0
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    rope_factor: float = 40.0           # rope_scaling (type "yarn")
    rope_original_max_position_embeddings: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 0.707
    rope_mscale_all_dim: float = 0.707
    rms_norm_eps: float = 1e-6
    prefill_chunk: int = 128            # the prefill ladder's first rung: the flash forward's smallest whole block
    dtype: Any = jnp.bfloat16           # weights, matmul operands and the cache's rows

    def __post_init__(self):
        if not (0 <= self.first_expert_held and self.first_expert_held + self.experts_held <= self.num_experts
                and self.experts_held > 0):
            raise ValueError(f"experts {self.first_expert_held}..{self.first_expert_held + self.experts_held} "
                             f"are not among the router's {self.num_experts}")
        if self.num_experts % self.n_group or not 0 < self.topk_group <= self.n_group:
            raise ValueError(f"{self.num_experts} experts do not lie in {self.n_group} groups of which "
                             f"{self.topk_group} are kept")
        if self.qk_rope_head_dim % 2:
            raise ValueError("the rotary part of a head is made of pairs")
        if not 0 < self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace counts the leading dense layers, at least one")

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

    @property
    def mla(self) -> mla.LatentAttention:
        """The latent-attention block on this model's numbers: YaRN's frequencies with the cos / sin multiplier
        ``mscale / mscale_all_dim``, the score scale with ``mscale^2``, no multiplier on the low-rank activations."""
        return mla.LatentAttention(
            hidden_size=self.hidden_size, num_attention_heads=self.num_attention_heads, q_lora_rank=self.q_lora_rank,
            kv_lora_rank=self.kv_lora_rank, qk_nope_head_dim=self.qk_nope_head_dim, qk_rope_head_dim=self.qk_rope_head_dim,
            v_head_dim=self.v_head_dim, inv_freq=inv_freq(self), softmax_scale=self.softmax_scale,
            rms_norm_eps=self.rms_norm_eps, dtype=self.dtype,
            cos_scale=yarn_mscale(self.rope_factor, self.rope_mscale) / yarn_mscale(self.rope_factor, self.rope_mscale_all_dim))

    @property
    def softmax_scale(self) -> float:
        return self.qk_head_dim ** -0.5 * yarn_mscale(self.rope_factor, self.rope_mscale_all_dim) ** 2

    @property
    def groups_held(self) -> Tuple[int, ...]:
        """The routing groups whose experts this tree holds (whole groups, or the count is not whole)."""
        per = self.num_experts // self.n_group
        return tuple(g for g in range(self.n_group)
                     if self.first_expert_held <= g * per and (g + 1) * per <= self.first_expert_held + self.experts_held)


# ------------------------------------------------------------------ parameters
def init_params(config: DeepseekV2Config, key) -> Dict[str, Any]:
    """Seeded random weights in the types they are served in (jit the call:
    the float32 draws are then temporaries).  Matrices are normal with
    variance 1 / fan-in, but for two: the router (float32) is drawn twice as
    wide, so that its softmax over all experts is not flat and the groups
    differ; and the routed experts' down projections ``ROUTED_DOWN_GAIN`` times
    as wide (the constant says why)."""
    c, dt = config, config.dtype
    E = c.hidden_size

    def normal(k, shape, fan_in, dtype=dt, gain=1.0):
        return (jax.random.normal(k, shape, F32) * (gain / math.sqrt(fan_in))).astype(dtype)

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
                "shared": swiglu_params(ks[4], c.n_shared_experts * F)}

    params: Dict[str, Any] = {
        "embed_tokens": {"embedding": normal(jax.random.fold_in(key, 1 << 20), (c.vocab_size, E), E)},
        "lm_head": {"kernel": normal(jax.random.fold_in(key, 1 << 21), (E, c.vocab_size), E)},
        "norm": {"weight": jnp.ones((E,), dt)},
    }
    for l in range(c.num_hidden_layers):
        k_attn, k_mlp = jax.random.split(jax.random.fold_in(key, l))
        params[f"layers_{l}"] = {
            "input_layernorm": {"weight": jnp.ones((E,), dt)},
            "post_attention_layernorm": {"weight": jnp.ones((E,), dt)},
            "self_attn": mla.attention_params(c.mla, k_attn),
            "mlp": moe(k_mlp) if l >= c.first_k_dense_replace else swiglu_params(k_mlp, c.intermediate_size),
        }
    return params


# ------------------------------------------------------------ embedding, head
def embed(config: DeepseekV2Config, params, tokens):
    return jnp.take(params["embed_tokens"]["embedding"], tokens, axis=0).astype(F32)


def head(config: DeepseekV2Config, params, x):
    """Logits (float32) over the rows of the vocabulary held here."""
    return _mm(rmsnorm(x, params["norm"]["weight"], config.rms_norm_eps), params["lm_head"]["kernel"], config.dtype)


# --------------------------------------------------------------------- rotary
def inv_freq(config: DeepseekV2Config) -> np.ndarray:
    """The rotary frequencies (qk_rope_head_dim / 2,): YaRN's, on this config's numbers."""
    c = config
    return yarn_inv_freq(c.qk_rope_head_dim, c.rope_theta, c.rope_factor, c.rope_original_max_position_embeddings,
                         c.rope_beta_fast, c.rope_beta_slow)


def rotary(config: DeepseekV2Config, x, positions):
    """``mla.rotary`` at this model's frequencies and multiplier (``mscale / mscale_all_dim``)."""
    return mla.rotary(config.mla, x, positions)


# ------------------------------------------------------------------ attention
def mla_prefill(c: DeepseekV2Config, ap, u, *, interpret: Optional[bool] = None):
    """``mla.mla_prefill`` (the EXPANDED form over one sequence) on this model's numbers."""
    return mla.mla_prefill(c.mla, ap, u, interpret=interpret)


def mla_step(c: DeepseekV2Config, ap, u, pool, **where):
    """``mla.mla_step`` (the ABSORBED form, one new position a slot) on this model's numbers."""
    return mla.mla_step(c.mla, ap, u, pool, **where)


# ---------------------------------------------------------------- feed-forward
def dense_mlp(c: DeepseekV2Config, mp, h):
    """The SwiGLU over a tree of ``gate`` / ``up`` / ``down``: a dense layer's MLP, the shared experts."""
    return swiglu(h, mp["gate"], mp["up"], mp["down"], c.dtype)


def expert_layer(c: DeepseekV2Config, ep, h, token_mask=None):
    """``sum over kept and held e of g_e E_e(h) + S(h)`` for tokens ``h`` (N,
    E).  Returns the sum (N, E) float32, how many tokens each held expert got
    (held,), and how many of the tokens' kept groups lie on this chip (a scalar)."""
    route = lambda scores: route_group_limited(scores, c.num_experts_per_tok, n_group=c.n_group, topk_group=c.topk_group,
                                               scale=c.routed_scaling_factor)
    routed, counts, kept = routed_experts(h, ep["router"], route, ep["w_gate"], ep["w_up"], ep["w_down"],
                                          first_held=c.first_expert_held, token_mask=token_mask, dtype=c.dtype)
    here = kept[:, np.asarray(c.groups_held, np.int32)]
    if token_mask is not None:
        here = here & token_mask[:, None]
    return routed + dense_mlp(c, ep["shared"], h), counts, jnp.sum(here.astype(jnp.int32))


# ------------------------------------------------------------ whole layers
def _after_attention(c: DeepseekV2Config, lp, l: int, x, y, token_mask):
    x = x + y
    h = rmsnorm(x, lp["post_attention_layernorm"]["weight"], c.rms_norm_eps)
    if l < c.first_k_dense_replace:
        with jax.named_scope("vs.mlp"):
            return x + dense_mlp(c, lp["mlp"], h), None
    with jax.named_scope("vs.moe"):
        y, counts, groups = expert_layer(c, lp["mlp"], h, token_mask=token_mask)
    return x + y, (counts, groups)


def layer_prefill(c: DeepseekV2Config, lp, l: int, x, length, *, interpret: Optional[bool] = None):
    """Layer ``l`` over one padded sequence ``x`` (T, E) float32.  Returns the
    residual stream and the positions' cache rows.  Pad positions route to no expert."""
    with jax.named_scope("vs.attn"):
        y, rows = mla_prefill(c, lp["self_attn"], rmsnorm(x, lp["input_layernorm"]["weight"], c.rms_norm_eps),
                              interpret=interpret)
    x, _ = _after_attention(c, lp, l, x, y, jnp.arange(x.shape[0]) < length)
    return x, rows


def layer_step(c: DeepseekV2Config, lp, l: int, x, active, attention_step):
    """Layer ``l`` over one new position a slot, ``x`` (S, E) float32;
    ``attention_step(u)`` is :func:`mla_step` over this layer of the pool.
    Returns the residual stream, the pool, and of an expert layer ``(counts,
    groups kept here)`` (None of a dense one)."""
    with jax.named_scope("vs.attn"):
        y, pool = attention_step(rmsnorm(x, lp["input_layernorm"]["weight"], c.rms_norm_eps))
    x, routed = _after_attention(c, lp, l, x, y, active)
    return x, pool, routed


# ------------------------------------------- what the serve engine asks of a model
def cache_config(config: DeepseekV2Config, *, num_slots: int, page_size: int, pages_per_slot: int,
                 num_pages: Optional[int] = None):
    """The cache's geometry: a latent pool of every layer's rows, no value
    pool, no slot state."""
    return mla.cache_config(config.mla, layers=config.num_hidden_layers, num_slots=num_slots, page_size=page_size,
                            pages_per_slot=pages_per_slot, num_pages=num_pages)


def prefill_chunk(config: DeepseekV2Config) -> int:
    """The prefill ladder's first rung."""
    return config.prefill_chunk


def decode_kernels(config: DeepseekV2Config, cache) -> Dict[str, Any]:
    """The decode step's kernels, latched at build: ``{"decode": the
    ``interpret`` flag of ``paged_decode_latent``, or None for the XLA leg}``."""
    return {"decode": mla.decode_kernel(config.mla, cache)}


def serve_prefill(c: DeepseekV2Config, params, arrays, tokens, length, page_row, slot, *, page: int,
                  interpret: Optional[bool] = None):
    """The prefill program's body: ``tokens`` (bucket,) through the stack in
    the expanded form; every layer's rows go to the slot's pages (``page_row``:
    what lies past its reserved pages is the null page).  Returns the last real
    position's logits row and the cache's arrays."""
    x = embed(c, params, tokens)
    kept = []
    for l in range(c.num_hidden_layers):
        x, rows = layer_prefill(c, params[f"layers_{l}"], l, x, length, interpret=interpret)
        kept.append(rows)
    last = jax.lax.dynamic_index_in_dim(x, length - 1, axis=0, keepdims=True)
    pool = arrays["k"]
    pages = jnp.stack(kept).reshape(len(kept), -1, page, 1, c.cache_row)
    return head(c, params, last)[0], {"k": pool.at[:, page_row].set(pages.astype(pool.dtype))}


def serve_decode(c: DeepseekV2Config, params, arrays, table, lengths, tokens, *, active, write_page, write_offset,
                 kernels: Dict[str, Any]):
    """The decode program's body, one token a slot in the absorbed form.
    Returns the logits (S, vocab), the step's counts ``{"experts": (expert
    layers, held) tokens an expert got, "groups": (expert layers,) kept groups
    that lie here}`` and the cache's arrays."""
    x = embed(c, params, tokens)                    # (S, E)
    pool, experts, groups = arrays["k"], [], []
    for l in range(c.num_hidden_layers):
        lp = params[f"layers_{l}"]
        step = lambda u, lp=lp, l=l: mla_step(c, lp["self_attn"], u, pool, layer=l, table=table, page=write_page,
                                               offset=write_offset, positions=lengths, valid_len=lengths + 1,
                                               interpret=kernels["decode"])
        x, pool, routed = layer_step(c, lp, l, x, active, step)
        if routed is not None:
            experts.append(routed[0])
            groups.append(routed[1])
    return head(c, params, x), {"experts": jnp.stack(experts), "groups": jnp.stack(groups)}, {"k": pool}


# counters of this model beside those every model's engine keeps (``HybridServeEngine.trace_counters``)
STEP_COUNTERS = ("latent_bytes_read", "prefill_attn_flops", "moe_groups_kept_here")


def step_counters(config: DeepseekV2Config, cache, lengths: np.ndarray, counts: Dict[str, np.ndarray]) -> Dict[str, int]:
    """What one decode step adds: the latent pages its attention had to read
    (live pages x page bytes x layers) and the kept groups that lay here."""
    return {"latent_bytes_read": mla.latent_bytes_read(config.mla, cache, lengths, config.num_hidden_layers),
            "moe_groups_kept_here": int(counts["groups"].sum())}


def prefill_counters(config: DeepseekV2Config, bucket: int) -> Dict[str, int]:
    """What one prefill of ``bucket`` positions adds: causal attention's
    useful operations at the real widths (scores and values, half the square)."""
    return {"prefill_attn_flops": mla.prefill_attn_flops(config.mla, bucket, config.num_hidden_layers)}
