"""LLaMA family — Llama-2 / Llama-3 / OpenLlama.

The reference's examples train HF llama checkpoints
(legacy/examples/llama2_4D_finetune/llama_train.py,
open_llama_4D_benchmark/) with a 4D sharding plan
(open_llama_4D_benchmark/sharding_plan.py).  This is an idiomatic flax
re-implementation: RMSNorm, rotary embeddings, grouped-query attention,
SwiGLU MLP, tied-or-untied head — bf16-first for the MXU.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import flax.linen as nn

from ..placements import Shard, plan_axes

__all__ = [
    "LlamaConfig",
    "Llama",
    "LlamaBlock",
    "LlamaEmbed",
    "LlamaHead",
    "llama_plan",
    "LLAMA2_7B",
    "LLAMA3_8B",
    "LLAMA3_70B",
    "LLAMA3_405B",
    "OPEN_LLAMA_3B",
]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32   # < heads -> GQA
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    # pallas fused kernel; GSPMD-partitionable over batch/head dims via
    # custom_partitioning (ops/flash_attention.py), so it composes with plain
    # jit + dp/tp meshes.  Seq-sharded long-context uses ring/ulysses
    # (parallel/context.py) instead.  Off-TPU it falls back to dense math.
    use_flash_attention: bool = True
    remat: bool = False  # jax.checkpoint each block (HBM for FLOPs)
    # jax.checkpoint_policies name (e.g. "dots_saveable",
    # "dots_with_no_batch_dims_saveable") — with a policy, only activations
    # the policy excludes are recomputed, so the MFU cost of remat shrinks
    # from ~25% (full recompute) to ~0 while still dropping the elementwise
    # intermediates that dominate activation HBM.  None = full remat.
    remat_policy: Optional[str] = None
    # what to rematerialize when remat=True:
    #   "block" — jax.checkpoint the whole block (max HBM savings, pays a
    #             full forward recompute incl. the flash-attention kernel);
    #   "mlp"   — checkpoint only the MLP: attention residuals (q/k/v/o/lse,
    #             the flash kernel's saved state) stay live, so backward
    #             reuses the fused kernel's forward instead of re-running it
    #             — ~O(5*B*T*E) more HBM per layer for less recompute.
    remat_scope: str = "block"
    # lax.scan over layers: XLA compiles ONE block instead of L copies
    # (minutes -> seconds at 24+ layers; same step math).  Params gain a
    # leading (L,) axis — shard them with pipe.spmd.shard_stacked_params or
    # tp-shifted plans (llama_plan(scanned=True)).
    scan_layers: bool = False
    # fp8 quantized training (SURVEY.md:17 new-gen scope): every projection
    # matmul runs through flax's Fp8DotGeneralOp — e4m3 fwd / e5m2 grads
    # with delayed (amax-history) scaling.  Adds an
    # ``_overwrite_with_gradient`` variable collection (scales + histories)
    # that make_train_step threads and overwrite-updates automatically; the
    # functional equivalent for custom training loops is quant/fp8.py.
    use_fp8: bool = False
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.remat_policy and not self.remat:
            raise ValueError(
                "remat_policy is set but remat=False — the policy would be "
                "silently ignored; set remat=True (or drop the policy)"
            )
        if self.remat_scope not in ("block", "mlp"):
            raise ValueError(f"remat_scope must be 'block' or 'mlp', got {self.remat_scope!r}")
        if self.remat_scope != "block" and not self.remat:
            raise ValueError(
                "remat_scope is set but remat=False — the scope would be "
                "silently ignored; set remat=True (or drop the scope)"
            )
        if self.remat_policy and self.remat_scope != "block":
            raise ValueError("remat_policy applies to remat_scope='block' only")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


LLAMA2_7B = LlamaConfig()
OPEN_LLAMA_3B = LlamaConfig(hidden_size=3200, intermediate_size=8640, num_hidden_layers=26, num_attention_heads=32)
LLAMA3_8B = LlamaConfig(
    vocab_size=128256,
    hidden_size=4096,
    intermediate_size=14336,
    num_hidden_layers=32,
    num_attention_heads=32,
    num_key_value_heads=8,
    max_position_embeddings=8192,
    rope_theta=500000.0,
)
LLAMA3_70B = LlamaConfig(
    vocab_size=128256,
    hidden_size=8192,
    intermediate_size=28672,
    num_hidden_layers=80,
    num_attention_heads=64,
    num_key_value_heads=8,
    rope_theta=500000.0,
)
LLAMA3_405B = LlamaConfig(
    vocab_size=128256,
    hidden_size=16384,
    intermediate_size=53248,
    num_hidden_layers=126,
    num_attention_heads=128,
    num_key_value_heads=8,
    rope_theta=500000.0,
)


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param("weight", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        x32 = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.eps)
        return (x32 * scale).astype(self.dtype)


def rotary(q, k, positions, theta: float):
    """Apply rotary position embeddings (fp32 phase math)."""
    hd = q.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B,T,hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)

    def rot(x):
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        c = cos[:, :, None, :]
        s = sin[:, :, None, :]
        return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)

    return rot(q), rot(k)


def _proj_kwargs(c: "LlamaConfig") -> dict:
    """Extra nn.Dense kwargs for the block projections: fp8 routes the
    matmul through the delayed-scaling fp8 dot op (embed/lm_head stay
    high-precision — standard fp8 recipe keeps the ends of the network
    out of fp8).  Fp8DirectDotGeneralOp is the non-deprecated flax op;
    the older Fp8DotGeneralOp is the fallback — both keep their state in
    the _overwrite_with_gradient collection make_train_step understands."""
    if not c.use_fp8:
        return {}
    op = getattr(nn, "Fp8DirectDotGeneralOp", None) or nn.Fp8DotGeneralOp
    return {"dot_general_cls": op}


class LlamaAttention(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, positions):
        c = self.config
        B, T, E = x.shape
        H, KV, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        q = nn.Dense(H * hd, use_bias=False, dtype=c.dtype, name="q_proj", **_proj_kwargs(c))(x)
        k = nn.Dense(KV * hd, use_bias=False, dtype=c.dtype, name="k_proj", **_proj_kwargs(c))(x)
        v = nn.Dense(KV * hd, use_bias=False, dtype=c.dtype, name="v_proj", **_proj_kwargs(c))(x)
        q = q.reshape(B, T, H, hd)
        k = k.reshape(B, T, KV, hd)
        v = v.reshape(B, T, KV, hd)
        q, k = rotary(q, k, positions, c.rope_theta)
        if c.use_flash_attention:
            from ..ops.flash_attention import flash_attention

            # GQA runs natively in the kernel: no repeated K/V in HBM
            y = flash_attention(q, k, v, causal=True).reshape(B, T, H * hd)
        else:
            if KV != H:  # GQA: repeat kv heads for the dense einsum
                rep = H // KV
                k = jnp.repeat(k, rep, axis=2)
                v = jnp.repeat(v, rep, axis=2)
            att = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / math.sqrt(hd)
            mask = jnp.tril(jnp.ones((T, T), dtype=bool))
            att = jnp.where(mask[None, None], att, jnp.finfo(jnp.float32).min)
            att = jax.nn.softmax(att, axis=-1).astype(c.dtype)
            y = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(B, T, H * hd)
        return nn.Dense(E, use_bias=False, dtype=c.dtype, name="o_proj", **_proj_kwargs(c))(y)


class LlamaMLP(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x):
        c = self.config
        g = nn.Dense(c.intermediate_size, use_bias=False, dtype=c.dtype, name="gate_proj", **_proj_kwargs(c))(x)
        u = nn.Dense(c.intermediate_size, use_bias=False, dtype=c.dtype, name="up_proj", **_proj_kwargs(c))(x)
        return nn.Dense(c.hidden_size, use_bias=False, dtype=c.dtype, name="down_proj", **_proj_kwargs(c))(
            nn.silu(g) * u
        )


class LlamaBlock(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, positions):
        c = self.config
        # remat_scope="mlp": checkpoint applied here (Llama skips the
        # block-level wrap); nn.remat preserves the submodule name, so
        # param FQNs — and every plan/checkpoint keyed on them — are
        # unchanged across scopes
        mlp_cls = (
            nn.remat(LlamaMLP, prevent_cse=not c.scan_layers)
            if (c.remat and c.remat_scope == "mlp")
            else LlamaMLP
        )
        x = x + LlamaAttention(c, name="self_attn")(
            RMSNorm(c.rms_norm_eps, c.dtype, name="input_layernorm")(x), positions
        )
        x = x + mlp_cls(c, name="mlp")(
            RMSNorm(c.rms_norm_eps, c.dtype, name="post_attention_layernorm")(x)
        )
        return x


def _scan_body(block_cls):
    """(carry, broadcast) scan signature around a block class."""

    class ScanBody(nn.Module):
        config: LlamaConfig

        @nn.compact
        def __call__(self, x, positions):
            return block_cls(self.config, name="block")(x, positions), None

    return ScanBody


class Llama(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, idx, deterministic: bool = True):
        c = self.config
        B, T = idx.shape
        emb = nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype, name="embed_tokens")
        x = emb(idx)
        positions = jnp.arange(T)[None, :].repeat(B, axis=0)
        if c.remat and c.remat_scope == "block":
            policy = getattr(jax.checkpoint_policies, c.remat_policy) if c.remat_policy else None
            # inside scan the loop structure already blocks CSE; prevent_cse
            # there would only pessimize the compiled body
            block_cls = nn.remat(LlamaBlock, policy=policy, prevent_cse=not c.scan_layers)
        else:
            block_cls = LlamaBlock  # scope="mlp" remat happens inside the block
        if c.scan_layers:
            scan = nn.scan(
                _scan_body(block_cls),
                # fp8 delayed-scaling state is per-layer too: stack it on the
                # same leading (L,) axis as the params
                variable_axes={"params": 0, "_overwrite_with_gradient": 0},
                split_rngs={"params": True},
                in_axes=nn.broadcast,
                length=c.num_hidden_layers,
            )
            x, _ = scan(c, name="layers")(x, positions)
        else:
            for i in range(c.num_hidden_layers):
                x = block_cls(c, name=f"layers_{i}")(x, positions)
        x = RMSNorm(c.rms_norm_eps, c.dtype, name="norm")(x)
        if c.tie_word_embeddings:
            return emb.attend(x)
        return nn.Dense(c.vocab_size, use_bias=False, dtype=c.dtype, name="lm_head")(x)


class LlamaEmbed(nn.Module):
    """Token-embedding pipeline unit (first-stage granularity; mirrors the
    reference's smallest_unsplittable_units for HF llama, pipe_parser.py)."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, idx):
        c = self.config
        return nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype, name="embed_tokens")(idx)


class LlamaHead(nn.Module):
    """Final-norm + LM-head pipeline unit (last-stage granularity)."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, x):
        c = self.config
        x = RMSNorm(c.rms_norm_eps, c.dtype, name="norm")(x)
        return nn.Dense(c.vocab_size, use_bias=False, dtype=c.dtype, name="lm_head")(x)


def llama_plan(mesh, sequence_parallel: bool = True, scanned: bool = False):
    """TP/SP plan (reference legacy/examples/open_llama_4D_benchmark/
    sharding_plan.py): column-parallel q/k/v + gate/up, row-parallel o/down,
    hidden-sharded embedding, vocab-sharded head; RMSNorms replicated with SP
    activations.

    Mesh-shape-agnostic: shardings bind to the mesh dims *named* "dp"/"tp"
    (``plan_axes``), so the same plan works on ("dp","tp"), ("pp","dp","tp")
    or 5-D meshes.  The fwd-plan FQN regexes tolerate a missing
    ``layers_N.`` prefix so they also match a standalone ``LlamaBlock``
    parallelized per pipeline stage.

    ``scanned=True`` targets the ``scan_layers`` param layout: block leaves
    live under ``layers.block.*`` with a leading (L,) stack axis, so their
    tp Shard dims shift by one (embed/head are unstacked and keep theirs).
    """
    S = Shard
    off = 1 if scanned else 0
    col = plan_axes(mesh, tp=S(1))      # column-parallel kernel (in, out/tp)
    row = plan_axes(mesh, tp=S(0))      # row-parallel kernel (in/tp, out)
    bcol = plan_axes(mesh, tp=S(1 + off))  # block kernels (maybe stacked)
    brow = plan_axes(mesh, tp=S(0 + off))
    rep = plan_axes(mesh)
    dp_only = plan_axes(mesh, dp=S(0))
    seq_par = plan_axes(mesh, dp=S(0), tp=S(1)) if sequence_parallel else dp_only
    blk = r"(layers\.block\.)" if scanned else r"(layers_\d+\.)?"
    param_plan = {
        r"embed_tokens\.embedding": col,
        blk + r"self_attn\.(q_proj|k_proj|v_proj)\.kernel": bcol,
        blk + r"self_attn\.o_proj\.kernel": brow,
        blk + r"mlp\.(gate_proj|up_proj)\.kernel": bcol,
        blk + r"mlp\.down_proj\.kernel": brow,
        r"lm_head\.kernel": col,
        r".*layernorm\.weight": rep,
        r"norm\.weight": rep,
        r".*": rep,
    }
    fwd_plan = {
        r"": {"input": [dp_only], "output": [dp_only]},
        blk + r"(input_layernorm|post_attention_layernorm)": {
            "input": [seq_par],
            "output": [seq_par],
        },
        blk + r"self_attn": {"input": [dp_only], "output": [dp_only]},
        blk + r"mlp": {"input": [dp_only], "output": [dp_only]},
        r"norm": {"input": [seq_par], "output": [dp_only]},
    }
    return {"parameter": param_plan, "forward": fwd_plan}
