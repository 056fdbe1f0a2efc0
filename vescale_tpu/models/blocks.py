"""What more than one model family behind ``serve.HybridServeEngine`` is built
of: the norms, the product, the SwiGLU, the plain rotary term, YaRN's
frequencies, a new position's write into its pools, where a ring of the newest
``window`` positions keeps a position (two families mix window and full
attention), and one rule of the random weights they are served with.  Plain functions of arrays and numbers: none
reads a config, so none knows its caller.  A family's file holds what is its
own (its mixers' projections, its routing rule, its cache, ``embed``, ``head``)
and imports from here, ``models/mamba2.py``, ``kernels/`` and ``moe/``; no
family imports another.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["F32", "ROUTED_DOWN_GAIN", "rmsnorm", "layernorm", "swiglu", "rotary", "yarn_mscale", "yarn_inv_freq", "write_position", "ring_row",
           "ring_source", "window_pairs"]

F32 = jnp.float32
# Of a family's ``init_params`` with routed experts.  Random weights of variance 1 / fan-in make every expert's
# output as large as the residual stream, and the gates (DeepSeek-V2: a peaked softmax's probabilities times 16)
# reach 3.5: a token whose sixth and seventh expert a rounding difference swaps then moves by a tenth of its own
# size, the next layer's router sees that and swaps more, and two computations of the same model in different
# precisions part by their whole range (PERF.md, section 6, PR 34: 0.47 to 1.3 of the largest logit on the chip).
# In a trained model one expert's marginal contribution is small beside the stream.  So the routed experts' down
# projections are drawn this much narrower: the routed part stays a few per cent of the stream, a swapped expert
# moves a logit row by 5e-3 of the largest, and a wrong gate scale still shows.
ROUTED_DOWN_GAIN = 1.0 / 64.0


def rmsnorm(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w.astype(F32)


def layernorm(x, weight, bias, eps):
    """LayerNorm over the last axis (mean and variance of it, then ``weight`` and ``bias``), float32."""
    x = x.astype(F32)
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    return (centred * jax.lax.rsqrt(jnp.mean(jnp.square(centred), axis=-1, keepdims=True) + eps) * weight.astype(F32)
            + bias.astype(F32))


def _mm(x, w, dtype):
    """``x @ w`` with operands in ``dtype`` and a float32 result."""
    return jnp.dot(x.astype(dtype), w.astype(dtype), preferred_element_type=F32)


def swiglu(h, gate, up, down, dtype):
    """``W_down (silu(W_gate h) * W_up h)``, no biases: a dense MLP, a shared expert."""
    return _mm(jax.nn.silu(_mm(h, gate, dtype)) * _mm(h, up, dtype), down, dtype)


def rotary(x, positions, theta: float):
    """Rotate ``x`` (N, heads, dim) by ``positions`` (N,) over the pairs ``(i, i
    + dim / 2)`` (the sources' ``rotate_half``) at the plain frequencies of
    ``theta``, float32."""
    half = x.shape[-1] // 2
    angle = positions.astype(F32)[:, None, None] * (1.0 / theta ** (jnp.arange(half, dtype=F32) / half))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :half].astype(F32), x[..., half:].astype(F32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float, original_positions: int, beta_fast: float,
                  beta_slow: float) -> np.ndarray:
    """The rotary frequencies (dim / 2,) of a rotated width ``dim`` under YaRN:
    a pair that turns more than ``beta_fast`` times over the
    ``original_positions`` keeps its frequency, one that turns fewer than
    ``beta_slow`` times has it divided by ``factor``, a linear ramp between."""
    plain = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(rotations):
        return dim * math.log(original_positions / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / ((high if high != low else high + 0.001) - low), 0, 1)
    keep = 1.0 - ramp
    return (plain / factor * (1.0 - keep) + plain * keep).astype(np.float32)


def write_position(k_store, v_store, k, v, where):
    """K and V of the new positions into ``where`` of their stores (``(layer,
    page, offset)`` of the pools, a slot's row of a ring), rounded to the
    stores' type.  A layer writes before it attends.  Returns both stores."""
    return k_store.at[where].set(k.astype(k_store.dtype)), v_store.at[where].set(v.astype(v_store.dtype))


def ring_row(positions, window: int):
    """The ring row that holds position ``p``: ``p mod window``."""
    return positions % window


def ring_source(length, rung: int, window: int):
    """For each ring row ``r`` (window,), the position of a prefilled prompt of
    ``length`` tokens (on a rung of ``rung`` positions) that it holds: the
    NEWEST real position ``p < length`` with ``ring_row(p) = r``.  A row that no
    real position falls on (a prompt shorter than the window) names position 0:
    no decode step reads it before it is written."""
    r = jnp.arange(window, dtype=jnp.int32)
    newest = r + window * ((length - 1 - r) // window)
    return jnp.clip(jnp.where(r < length, newest, 0), 0, rung - 1)


def window_pairs(T: int, window: Optional[int] = None) -> int:
    """The (query, key) pairs of ``T`` positions that the causal mask keeps, under a window of ``window`` or none."""
    if window is None or T <= window:
        return T * (T + 1) // 2
    return window * (window + 1) // 2 + (T - window) * window
