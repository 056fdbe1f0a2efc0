"""The plain reference of the Llama-family architectures the cells run
(Mistral-7B, DeepSeek-LLM-7B): RMSNorm, rotary embeddings (rotate-half),
grouped-query or multi-head causal attention, SwiGLU, untied (or tied) head.
Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision; no
kernels, no cache, no batching.  It follows the published description (HF
``modeling_llama.py`` / ``modeling_mistral.py``); no sliding window, as
Mistral-7B-v0.3 has none.

Weights come layer by layer from the system's own flax tree and are cast
inside each jitted call, so a whole float32 copy of a 16-layer model never
exists.  ``correct`` compares logits (serve) or the loss (train), never
sampled tokens: with random weights the largest logit changes on rounding.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

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


@jax.jit
def _xent(lg, targets):
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, targets[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - picked)


def loss_and_logits(params: Dict[str, Any], config: Dict[str, Any], inputs: np.ndarray, targets: np.ndarray,
                    rows: Sequence[int]):
    """Mean next-token cross-entropy of a (B, T) batch, a sequence at a time,
    and the first sequence's logits at the positions ``rows``: one forward a
    sequence serves both."""
    per_seq, picked = [], None
    for row_in, row_tg in zip(np.asarray(inputs), np.asarray(targets)):
        lg = logits(params, config, row_in, range(len(row_in)))
        if picked is None:
            picked = np.asarray(lg[jnp.asarray(np.asarray(rows, np.int32))])
        per_seq.append(float(_xent(lg, jnp.asarray(row_tg.astype(np.int32)))))
    return float(np.mean(per_seq)), picked


def loss(params: Dict[str, Any], config: Dict[str, Any], inputs: np.ndarray, targets: np.ndarray) -> float:
    return loss_and_logits(params, config, inputs, targets, [0])[0]


def rel_at_scale(got, want) -> float:
    """Largest difference as a share of the reference's largest magnitude."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) or 1.0))
