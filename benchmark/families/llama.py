"""The Llama family (``"model": "llama"``: Mistral-7B, DeepSeek-LLM-7B):
RMSNorm, rotary embeddings (rotate-half), grouped-query or multi-head causal
attention, SwiGLU, untied (or tied) head; ``vescale_tpu/models/llama.py`` in
the program.  The names are those ``benchmark/README.md`` ("Adding a family") fixes.

The reference is straightforward ``jax.numpy`` in float32 at ``highest``
matmul precision; no kernels, no cache, no batching.  It follows the published
description (HF ``modeling_llama.py`` / ``modeling_mistral.py``); no sliding
window, as Mistral-7B-v0.3 has none.  Weights come layer by layer from the
system's own flax tree and are cast inside each jitted call, so a whole
float32 copy of a 16-layer model never exists.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Sequence
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference
from benchmark.families import ServeSystem, TrainSystem
from benchmark.spec import SpecError

# ------------------------------------------------------------------ tolerances
# Serve: prefill-then-decode through the paged cache against the reference's
# full float32 forward, as a share of the largest reference logit.
# The system computes in bf16 (8 bits of mantissa: 2^-9 = 2e-3 per rounding)
# through L blocks of about ten roundings each, whose errors add like a random
# walk: 2e-3 * sqrt(10 L) = 2.5e-2 at L 16.  PR 22 measured 1.2e-2 between two
# bf16 serve legs at depth 4; PR 24's chip runs read 0.9e-2 to 1.1e-2 (PERF.md).
# A wrong mask, position, page or head mapping moves logits by their own size
# (order 1); computing in fp8 (2^-4 per rounding) would read about 0.5.
SERVE_LOGITS_TOLERANCE = 4e-2
# Train: the step's own loss (bf16 compute, the step's kernels) against the
# reference's float32 loss on the same parameters and batch.  The loss is a
# mean over 4096 positions, so per-logit errors mostly cancel: on the initial
# parameters PR 24's chip runs read 3e-5 to 6e-4, on one chip and on four;
# on the parameters a window of some 220 steps leaves, where the check now is,
# 8e-5 to 1.0e-3 (PERF.md).  5e-3 is five times the worst reading; chip_smoke.py
# allows 2e-2 between two bf16 layouts.  A wrong shard, mask or missing
# all-reduce moves the loss by order 1.
TRAIN_LOSS_TOLERANCE = 5e-3
# Because errors cancel in that mean, lower-precision compute could pass it.
# So the system's forward (the module the step differentiates) is also compared
# logit by logit at a few seeded positions, as a share of the reference's
# largest logit: SERVE_LOGITS_TOLERANCE has the arithmetic (bf16 through L
# blocks reads about 1e-2; fp8 would read about 0.5).
TRAIN_LOGITS_TOLERANCE = 4e-2


# --------------------------------------------------------------- the program
def program_config(config: Dict[str, Any], *, max_positions: int, use_flash_attention: bool = True):
    """The program's ``LlamaConfig`` from a configuration file's object: the
    published keys go through unchanged.  ``max_positions`` is the longest
    sequence this cell runs (the program sizes nothing else by it: rotary
    phases are computed from positions)."""
    from vescale_tpu.models.llama import LlamaConfig

    if config.get("sliding_window") is not None:
        raise SpecError("models/llama.py has no sliding-window attention")
    if config["hidden_size"] != config["num_attention_heads"] * config["head_dim"]:
        raise SpecError("LlamaConfig derives head_dim as hidden_size / num_attention_heads")
    return LlamaConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"], num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"], num_key_value_heads=config["num_key_value_heads"],
        max_position_embeddings=max_positions, rms_norm_eps=config["rms_norm_eps"],
        rope_theta=config["rope_theta"], tie_word_embeddings=config["tie_word_embeddings"],
        use_flash_attention=use_flash_attention, dtype=jnp.bfloat16,
    )


def _kv_cache_config(cfg, serve: Dict[str, Any]):
    from vescale_tpu.serve import KVCacheConfig

    positions = int(serve["positions_per_slot"])
    return KVCacheConfig(
        layers=cfg.num_hidden_layers, kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        num_slots=int(serve["slots"]), page_size=int(serve["page_size"]),
        pages_per_slot=positions // int(serve["page_size"]), dtype=cfg.dtype)


def build_serve(config: Dict[str, Any], serve: Dict[str, Any], devices, seed: int) -> ServeSystem:
    """Weights made on the device in one jitted call from the seed, in the type
    they are served in; a paged cache of K and V pages; ``ServeEngine``."""
    from vescale_tpu.mesh import DeviceMesh
    from vescale_tpu.models.llama import Llama
    from vescale_tpu.serve import PagedKVCache, ServeEngine

    if serve["weight_dtype"] != "bfloat16":
        raise ValueError("serve cells hold their weights in bfloat16")
    cfg = program_config(config, max_positions=int(serve["positions_per_slot"]))
    mesh = DeviceMesh(("tp",), (1,), devices=list(devices))
    params = jax.jit(
        lambda key: jax.tree_util.tree_map(
            lambda x: x.astype(cfg.dtype), Llama(cfg).init(key, jnp.ones((1, 8), jnp.int32))["params"])
    )(jax.random.key(seed))
    cache = PagedKVCache(_kv_cache_config(cfg, serve), mesh)
    return ServeSystem(params, cache, ServeEngine(cfg, mesh, params, cache), cfg.vocab_size)


def build_train(config: Dict[str, Any], train: Dict[str, Any], mesh, seq_len: int) -> TrainSystem:
    from vescale_tpu.models.llama import Llama, llama_plan

    cfg = program_config(config, max_positions=seq_len, use_flash_attention=bool(train["use_flash_attention"]))
    return TrainSystem(Llama(cfg), llama_plan(mesh, sequence_parallel=bool(train["sequence_parallel"])),
                       cfg.vocab_size)


def rehearse_serve(name: str, config: Dict[str, Any], serve: Dict[str, Any], devices):
    """The cell's prefill stage and decode step, lowered for described devices:
    shapes where the cache and the engine would allocate (two functions patched
    for the duration, here, not in the program)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from vescale_tpu.mesh import DeviceMesh
    from vescale_tpu.models.llama import Llama
    from vescale_tpu.serve import PagedKVCache, ServeEngine
    from vescale_tpu.serve import kv_cache as kv_cache_module

    cfg = program_config(config, max_positions=int(serve["positions_per_slot"]))
    mesh = DeviceMesh(("tp",), (1,), devices=list(devices[:1]))
    replicated = NamedSharding(mesh.jax_mesh, P())
    abstract = jax.eval_shape(lambda r: Llama(cfg).init(r, jnp.ones((1, 8), jnp.int32)), jax.random.key(0))["params"]
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, cfg.dtype, sharding=replicated), abstract)
    kc = _kv_cache_config(cfg, serve)

    def shapes_for_zeros(cache_spec):
        return jax.ShapeDtypeStruct(cache_spec.layout().physical_shape, cache_spec.dtype,
                                    sharding=cache_spec.named_sharding())

    with mock.patch.object(kv_cache_module, "_zeros_global", shapes_for_zeros), \
            mock.patch.object(ServeEngine, "_replicate", lambda self, leaf: leaf):
        cache = PagedKVCache(kc, mesh)
        engine = ServeEngine(cfg, mesh, params, cache)
    S, Tmax = cache.num_slots, cache.max_seq_len
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=replicated)
    sizes = {"weights_bytes_bf16": sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(params)) * 2,
             "cache_bytes": 2 * int(np.prod(cache.k.data.shape)) * 2}
    x = jax.ShapeDtypeStruct((1, Tmax, cfg.hidden_size), cfg.dtype, sharding=replicated)
    return sizes, [
        (f"{name}: prefill stage, {Tmax} padded positions, depth {cfg.num_hidden_layers}",
         engine._stage_fns[0].lower(params, x, i32(1, Tmax))),
        (f"{name}: decode step, {S} slots x {Tmax} positions",
         engine._decode_fn.lower(params, cache.k.data, cache.v.data, i32(S, kc.pages_per_slot), i32(S), i32(S))),
    ]


# ------------------------------------------------------------- the reference
F32 = jnp.float32
# attention is computed this many query heads at a time: at T 4096 all 32
# heads of scores would be 2 GB in float32
HEAD_BLOCK = 8


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w.astype(F32)


def _rotate(x, positions, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = positions.astype(F32)[:, None] * inv[None, :]          # (T, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps", "theta"))
def block(lp: Dict[str, Any], x, *, heads: int, kv_heads: int, eps: float, theta: float):
    """One decoder block over one sequence ``x`` (T, E), float32."""
    with jax.default_matmul_precision("highest"):
        T, E = x.shape
        hd = E // heads
        f = lambda a: a.astype(F32)
        xn = _rmsnorm(x, lp["input_layernorm"]["weight"], eps)
        pos = jnp.arange(T)
        q = _rotate((xn @ f(lp["self_attn"]["q_proj"]["kernel"])).reshape(T, heads, hd), pos, theta)
        k = _rotate((xn @ f(lp["self_attn"]["k_proj"]["kernel"])).reshape(T, kv_heads, hd), pos, theta)
        v = (xn @ f(lp["self_attn"]["v_proj"]["kernel"])).reshape(T, kv_heads, hd)
        rep = heads // kv_heads
        mask = jnp.tril(jnp.ones((T, T), dtype=bool))
        outs = []
        for h0 in range(0, heads, HEAD_BLOCK):
            hs = slice(h0, min(h0 + HEAD_BLOCK, heads))
            kk = jnp.repeat(k, rep, axis=1)[:, hs]
            vv = jnp.repeat(v, rep, axis=1)[:, hs]
            s = jnp.einsum("qhd,khd->hqk", q[:, hs], kk) / math.sqrt(hd)
            p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
            outs.append(jnp.einsum("hqk,khd->qhd", p, vv))
        y = jnp.concatenate(outs, axis=1).reshape(T, heads * hd)
        x = x + y @ f(lp["self_attn"]["o_proj"]["kernel"])
        xn = _rmsnorm(x, lp["post_attention_layernorm"]["weight"], eps)
        g = xn @ f(lp["mlp"]["gate_proj"]["kernel"])
        u = xn @ f(lp["mlp"]["up_proj"]["kernel"])
        return x + (jax.nn.silu(g) * u) @ f(lp["mlp"]["down_proj"]["kernel"])


@jax.jit
def _embed(embedding, tokens):
    return jnp.take(embedding, tokens, axis=0).astype(F32)


@functools.partial(jax.jit, static_argnames=("eps", "transpose"))
def _head(norm_w, kernel, x, *, eps: float, transpose: bool):
    with jax.default_matmul_precision("highest"):
        w = kernel.astype(F32)
        return _rmsnorm(x, norm_w, eps) @ (w.T if transpose else w)


def hidden_states(params: Dict[str, Any], config: Dict[str, Any], tokens: Sequence[int]):
    """The residual stream after the last block, (T, E) float32."""
    x = _embed(params["embed_tokens"]["embedding"], jnp.asarray(np.asarray(tokens, np.int32)))
    for l in range(config["num_hidden_layers"]):
        x = block(params[f"layers_{l}"], x, heads=config["num_attention_heads"],
                  kv_heads=config["num_key_value_heads"], eps=float(config["rms_norm_eps"]),
                  theta=float(config["rope_theta"]))
    return x


def logits(params: Dict[str, Any], config: Dict[str, Any], tokens: Sequence[int], rows: Sequence[int]):
    """Next-token logits (float32) at the positions ``rows`` of ``tokens``."""
    x = hidden_states(params, config, tokens)[jnp.asarray(np.asarray(rows, np.int32))]
    if config["tie_word_embeddings"]:
        return _head(params["norm"]["weight"], params["embed_tokens"]["embedding"], x,
                     eps=float(config["rms_norm_eps"]), transpose=True)
    return _head(params["norm"]["weight"], params["lm_head"]["kernel"], x,
                 eps=float(config["rms_norm_eps"]), transpose=False)


loss_and_logits = functools.partial(reference.loss_and_logits, logits)


# -------------------------------------------- operations and bytes from shapes
# The benchmark's own arithmetic, so that no later PR moves a utilisation by
# recounting.
def matmul_params_per_layer(c: Dict[str, Any]) -> int:
    """Parameters of one block that a token multiplies: q, k, v, o and the
    three SwiGLU matrices (norm weights do no matmul)."""
    h, kv = c["hidden_size"], c["num_key_value_heads"] * c["head_dim"]
    q = c["num_attention_heads"] * c["head_dim"]
    return h * q + 2 * h * kv + q * h + 3 * h * c["intermediate_size"]


def param_count(c: Dict[str, Any]) -> int:
    h = c["hidden_size"]
    per_layer = matmul_params_per_layer(c) + 2 * h
    head = 0 if c["tie_word_embeddings"] else c["vocab_size"] * h
    return c["num_hidden_layers"] * per_layer + c["vocab_size"] * h + head + h


def train_flops_per_token(c: Dict[str, Any], seq_len: int) -> float:
    """Forward + backward operations one trained token needs: 6 per matmul
    parameter (2 forward, 4 backward), causal attention at half the square
    (forward 2*T*d for QK^T and PV together, times 3 with the backward), and
    the untied head.  The embedding lookup multiplies nothing; recomputation
    is not counted."""
    d = c["num_attention_heads"] * c["head_dim"]
    per_layer = 6.0 * matmul_params_per_layer(c) + 6.0 * seq_len * d
    head = 6.0 * c["hidden_size"] * c["vocab_size"]
    return c["num_hidden_layers"] * per_layer + head


def kv_bytes_per_position(c: Dict[str, Any], itemsize: int = 2) -> int:
    return 2 * c["num_hidden_layers"] * c["num_key_value_heads"] * c["head_dim"] * itemsize
