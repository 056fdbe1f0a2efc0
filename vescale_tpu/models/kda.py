"""Kimi Delta Attention (KDA; Kimi Linear, arXiv:2510.26692), the
linear-attention block of a hybrid decoder behind ``serve.HybridServeEngine``:
written once, as pure functions over one mixer's parameter tree and a
:class:`DeltaAttention` that holds the block's numbers.  No family is named here
and none is imported; a family's file builds its :class:`DeltaAttention` from
its own config (``models/ling_hybrid.py``), as ``models/mla.py`` is used.

Equations (the public ``fla`` KDA layer's, whose arguments the sources' config
keys are; ``u`` the normed stream, ``H`` heads of ``d_k`` = ``d_v`` = ``head_dim``)::

    q~ | k~ | v~ = W_qkv u                                 one product, no bias
    q, k, v      = SiLU(conv(q~)), SiLU(conv(k~)), SiLU(conv(v~))     causal depthwise convolution, ``conv_kernel`` taps
    q_h, k_h     = l2norm(q_h) d_k^-1/2, l2norm(k_h)       per head
    g_t,h        = lower_bound sigmoid(exp(A_log_h) (W_f u + dt_bias)_h)      (lower_bound, 0) a CHANNEL of d_k: the safe gate
    beta_t,h     = sigmoid(w_beta,h . u)
    S'           = Diag(exp g_t,h) S_t-1,h ;  S_t,h = S' + beta_t,h k_t,h (v_t,h - S'^T k_t,h)^T ;  o_t,h = S_t,h^T q_t,h
    y            = W_o concat_h(sigmoid(w_gate,h . u) rms_h(o_t,h))           head-wise gate, per-head RMSNorm with a (d_v,) gain

No position term.  The recurrence is ``kernels/kda.py``'s: :func:`kda_prefill`
runs a prompt through ``kda_chunk`` (a pad row gets ``g = 0`` and ``beta = 0``
and leaves the state where the last real row left it), :func:`kda_step` one
position a slot through ``kda_step`` (a slot that holds no request likewise, bit
for bit).  A slot keeps, a layer: the state ``(H, d_k, d_v)`` in
``state_dtype`` (float32: 2.1 MB at 32 heads of 128) and the convolution's tail,
the last ``conv_kernel - 1`` rows of ``q~ | k~ | v~`` in ``dtype``, kept as a RING:
row ``j`` holds the newest input whose position is ``j`` modulo ``conv_kernel - 1``,
and a step overwrites the ONE row its position names.  (A tail shifted in place,
``new[j] = old[j + 1]``, reads the rows it writes: at 256 slots the chip's compiler
built that update, for the first layer of six, so that rows 0 and 1 came out wrong
while a state-space family's same lines at 64 to 128 slots come out right; PERF.md
section 6, PR 63.  A ring's write depends on no other row of the array.)

Precision: weights and matmul operands ``dtype`` (bfloat16) with float32
accumulation; the convolution, SiLU, norms, gates, ``beta`` and the recurrence
float32; a tail row is rounded to ``dtype`` once (the prefill's convolution reads
the same rounded rows, so both programs see one input).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .blocks import F32, _mm, rmsnorm

__all__ = ["DeltaAttention", "mixer_params", "kda_prefill", "kda_step", "slot_state", "step_kernel", "chunk_kernel",
           "state_bytes_rw", "STATE", "TAIL", "L2_EPS", "GATE_BIAS_RANGE", "GATE_RATE_RANGE"]

STATE, TAIL = "kda_state", "kda_conv"       # the slot state's two arrays, as ``cache.state`` names them
L2_EPS = 1e-6                               # under the root of a head's l2 norm (``fla``'s)
# The init rule of the gate (a trained ``A_log`` and ``dt_bias`` are not random): ``dt_bias`` uniform over this range a
# channel and ``exp(A_log)`` uniform over the other a head, so that ``lower_bound sigmoid(.)`` is spread over the whole of
# (lower_bound, 0) in its LOGARITHM: channels that forget in a position (sigmoid near 1) beside channels that keep a
# thousand (sigmoid(-6) = 2.5e-3: a log-decay of -0.012), pinned at neither end.  The slow channels are what makes the
# state's precision visible: rounding it to bfloat16 a position is a random walk over as many positions as a channel keeps.
GATE_BIAS_RANGE = (-6.0, 2.0)
GATE_RATE_RANGE = (1.0, 2.0)


@dataclasses.dataclass(frozen=True, eq=False)
class DeltaAttention:
    """One delta-rule mixer's numbers, as a family's config gives them."""

    hidden_size: int
    num_heads: int
    head_dim: int                       # d_k = d_v
    conv_kernel: int
    lower_bound: float                  # the gate's: a log-decay no lower than this a position (negative)
    rms_norm_eps: float
    dtype: Any                          # weights, matmul operands and the convolution's tail
    state_dtype: Any = jnp.float32

    @property
    def inner(self) -> int:
        """Channels of ``q~``, of ``k~`` and of ``v~``: heads x head width."""
        return self.num_heads * self.head_dim


def mixer_params(a: DeltaAttention, key, gains: Optional[Mapping[str, float]] = None) -> Dict[str, Any]:
    """One mixer's seeded weights: matrices normal with variance 1 / fan-in in
    ``a.dtype`` (``gains`` widens one by name), the convolution's taps normal
    with variance 1 / taps, ``A_log`` and ``dt_bias`` float32 by the gate's init
    rule (``GATE_RATE_RANGE``, ``GATE_BIAS_RANGE``), the per-head norm's gain ones."""
    E, H, D, K, dt, gains = a.hidden_size, a.num_heads, a.head_dim, a.conv_kernel, a.dtype, gains or {}

    def normal(k, shape, fan_in, name=None):
        return (jax.random.normal(k, shape, F32) * (gains.get(name, 1.0) / math.sqrt(fan_in))).astype(dt)

    ks = jax.random.split(key, 8)
    return {"qkv": normal(ks[0], (E, 3 * a.inner), E), "conv": normal(ks[1], (K, 3 * a.inner), K),
            "f": normal(ks[2], (E, a.inner), E, "f"),
            "dt_bias": jax.random.uniform(ks[3], (a.inner,), F32, *GATE_BIAS_RANGE),
            "A_log": jnp.log(jax.random.uniform(ks[4], (H,), F32, *GATE_RATE_RANGE)),
            "beta": normal(ks[5], (E, H), E), "gate": normal(ks[6], (E, H), E),
            "o_norm": jnp.ones((D,), dt), "o": normal(ks[7], (a.inner, E), a.inner)}


# ------------------------------------------------------------ the block's pieces
def _inputs(a: DeltaAttention, kp, u):
    """``q~ | k~ | v~`` of the rows ``u`` in the weights' type, as the convolution's tail is kept."""
    return _mm(u, kp["qkv"], a.dtype).astype(a.dtype)


def _gates(a: DeltaAttention, kp, u, live):
    """The log-decays (rows, H, d_k), ``beta`` (rows, H) and the output gate
    (rows, H), float32; a row ``live`` (rows,) does not name gets ``g = 0`` and
    ``beta = 0``: it leaves the state as it was."""
    H, D = a.num_heads, a.head_dim
    rate = jnp.exp(kp["A_log"].astype(F32))[None, :, None]
    g = a.lower_bound * jax.nn.sigmoid(rate * (_mm(u, kp["f"], a.dtype) + kp["dt_bias"].astype(F32)).reshape(-1, H, D))
    beta = jax.nn.sigmoid(_mm(u, kp["beta"], a.dtype))
    return (jnp.where(live[:, None, None], g, 0.0), jnp.where(live[:, None], beta, 0.0),
            jax.nn.sigmoid(_mm(u, kp["gate"], a.dtype)))


def _heads(a: DeltaAttention, conved):
    """From the convolution's output (rows, 3 H d) float32: ``q`` (l2-normed,
    times ``d^-1/2``), ``k`` (l2-normed) and ``v``, (rows, H, d) each."""
    H, D = a.num_heads, a.head_dim
    q, k, v = (x.reshape(-1, H, D) for x in jnp.split(jax.nn.silu(conved), 3, axis=-1))
    unit = lambda x: x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPS)
    return unit(q) * D ** -0.5, unit(k), v


def _out(a: DeltaAttention, kp, o, gate):
    """``W_o concat_h(gate_h rms_h(o_h))``: ``o`` (rows, H, d_v), ``gate`` (rows, H)."""
    normed = rmsnorm(o, kp["o_norm"], a.rms_norm_eps) * gate[..., None]
    return _mm(normed.reshape(o.shape[0], a.inner), kp["o"], a.dtype)


def kda_prefill(a: DeltaAttention, kp, u, length, *, interpret: Optional[bool]):
    """One sequence ``u`` (T, E) of which the first ``length`` positions are
    real: the convolution from zeros before the start, the recurrence from a
    zero state through ``kernels.kda_chunk`` on the leg ``interpret`` names.
    Returns the mixer's output (T, E), the state (H, d_k, d_v) where the prompt
    ends, in the cache's type, and the tail (conv_kernel - 1, 3 H d) of its last
    real inputs, as the ring keeps them."""
    from ..kernels.kda import kda_chunk         # (Pallas comes with it: imported late)

    x = _inputs(a, kp, u)
    T, K = x.shape[0], a.conv_kernel
    padded = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x], axis=0)
    newest = jax.lax.dynamic_slice_in_dim(padded, length, K - 1, axis=0)          # the inputs of positions length - (K - 1) .. length - 1
    tail = jnp.take(newest, (jnp.arange(K - 1) - length) % (K - 1), axis=0)        # ... each in the ring's row its position names
    w = kp["conv"].astype(F32)
    q, k, v = _heads(a, sum(w[i] * padded[i: i + T].astype(F32) for i in range(K)))
    g, beta, gate = _gates(a, kp, u, jnp.arange(T) < length)
    with jax.named_scope("vs.kda-chunk"):
        o, state = kda_chunk(q, k, v, g, beta, interpret=interpret)
    return _out(a, kp, o, gate), state.astype(a.state_dtype), tail


def kda_step(a: DeltaAttention, kp, u, state, conv, *, layer, active, positions, interpret: Optional[bool]):
    """The recurrence's one step for every slot: ``u`` (S, E) at ``positions``
    (S,); ``state`` (delta-rule layers, S, H, d_k, d_v) and ``conv`` (delta-rule
    layers, S, conv_kernel - 1, 3 H d), of which this mixer's are the
    ``layer``-th.  A slot ``active`` (S,) does not name takes ``g = 0`` and
    ``beta = 0`` and keeps its tail: its state stands bit for bit though the
    kernel reads and writes it.  Returns the output (S, E), ``state`` and
    ``conv`` advanced."""
    from ..kernels import kda as _kda

    x = _inputs(a, kp, u)
    K, R = a.conv_kernel, a.conv_kernel - 1
    ring = jax.lax.dynamic_index_in_dim(conv, layer, axis=0, keepdims=False)              # (S, K - 1, 3 H d)
    rows = jnp.arange(R)[None, :]
    # row j holds the input ``back + 1`` positions ago, so it meets tap ``K - 2 - back``: the taps are chosen a slot and row
    # (each slot is somewhere else on its ring), the rows stay where they lie
    back = (positions[:, None] - 1 - rows) % R                                              # (S, K - 1): 0 the newest
    w = kp["conv"].astype(F32)
    taps = sum(jnp.where((back == b)[:, :, None], w[K - 2 - b][None, None, :], 0.0) for b in range(R))
    q, k, v = _heads(a, w[K - 1][None, :] * x.astype(F32) + jnp.sum(taps * ring.astype(F32), axis=1))
    g, beta, gate = _gates(a, kp, u, active)
    with jax.named_scope("vs.kda-step"):
        state, o = _kda.kda_step(state, q, k, v, g, beta, layer=layer, interpret=interpret)
    # the oldest input's row takes the new one: every element of the slab from the element it replaces and ``x``, nothing shifted
    takes = (rows == (positions % R)[:, None]) & active[:, None]
    conv = jax.lax.dynamic_update_index_in_dim(conv, jnp.where(takes[:, :, None], x[:, None, :], ring), layer, axis=0)
    return _out(a, kp, o, gate), state, conv


# ------------------------------------- the block's side of the serve engine's seam
def slot_state(a: DeltaAttention, layers: int):
    """The block's share of ``KVCacheConfig.slot_state``: ``layers`` mixers' states and convolution tails."""
    H, D = a.num_heads, a.head_dim
    return ((STATE, layers, (H, D, D), a.state_dtype), (TAIL, layers, (a.conv_kernel - 1, 3 * a.inner), a.dtype))


def step_kernel(a: DeltaAttention, cache) -> Optional[bool]:
    """The ``interpret`` flag of ``kernels.kda_step`` over ``cache``'s states, or None for its XLA leg."""
    from ..kernels import kda as _kda

    return _kda.leg_step(cache.state[STATE].dtype, a.num_heads, a.head_dim, a.head_dim)


def chunk_kernel(a: DeltaAttention, positions: int) -> Optional[bool]:
    """... and of ``kernels.kda_chunk`` over a rung of ``positions``."""
    from ..kernels import kda as _kda

    return _kda.leg_chunk(a.num_heads, a.head_dim, a.head_dim, positions)


def state_bytes_rw(a: DeltaAttention, slots: int, layers: int) -> int:
    """What one decode step reads and writes of the matrix states: every slot's,
    idle or not (the kernel moves them all), ``layers`` mixers', once each way."""
    return 2 * slots * layers * a.num_heads * a.head_dim * a.head_dim * np.dtype(a.state_dtype).itemsize
