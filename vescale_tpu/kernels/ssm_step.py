"""State-space decode step kernel — a slot's recurrent state, read once and
written once.

One decode step of a Mamba-2 layer updates every slot's state and reads the
new state out against ``C``:

    h' = decay * h + B (x) (dt x)          y = sum over the state dim of h' * C

In XLA that is two passes over the state (the chip's trace, PERF.md PR 29: a
fusion that reduces ``h'`` to ``y`` and recomputes ``h'`` from ``h`` for it,
then the fusion that writes ``h'`` in place: two reads and a write), and the
state is the largest thing a decode step moves beside the expert weights.
This kernel reads a block of ``h``, forms ``h'``, writes it back to the same
place and reduces it to ``y`` while it is in VMEM: one read, one write.

  * **layout** — the state of all state-space layers is one array
    ``(layers, slots, N, J)``, ``N`` the state dim (on sublanes) and ``J =
    heads x head width`` flattened (on lanes).  Then everything a head
    contributes is a ROW over ``J`` (its decay repeated over its head width,
    ``dt x``), ``B`` and ``C`` are COLUMNS over ``N`` shared by all heads, and
    the kernel is two broadcasts, a multiply-add and a sublane reduction:
    nothing is transposed, and no lane is idle (a head width of 64 alone
    would fill half of each vector register).
  * **groups** — where the heads read ``G`` groups of ``B`` and ``C`` (``(S,
    G, N)``; Falcon-H1 has two), group ``g`` owns the lanes ``[g J / G, (g +
    1) J / G)``, a block never straddles two groups, and its columns are those
    of the group its lanes lie in: the block's index picks them, the kernel's
    body is the same.  One group, ``(S, N)``, is the program it was before.
  * **operands** — the whole state array stays where it is and is aliased to
    the output: the grid visits the blocks of one layer (the layer index rides
    in as a scalar-prefetch operand, so every layer of a decode program is the
    same Mosaic kernel) and every other byte of it is untouched.
  * **grid** — ``(slots, J / block)``, both parallel; a block is ``(N,
    block)`` float32 of at most ``_BLOCK_BYTES`` (1 MiB: 2048 lanes at N =
    128, 1024 at N = 256; in and out double-buffered, 4 MiB of VMEM).

**The selective form** (:func:`ssm_step_selective`, Mamba-1): there the decay
is ``exp(dt[j] * A[n, j])``, one value a (state row, lane) and not one a
lane.  Materialised in XLA it is an array as large as the state, a third pass
over the largest thing the step moves a slot; so that form takes ``dt`` (a
row) and ``A`` (an ``(N, J)`` block, the same for every slot: the lane blocks
are the grid's outer axis, so a block of it is fetched once for all slots)
and forms the decay in VMEM.  Same layout, same aliasing, same layer operand.

Numerics: float32 throughout, the same operations in the same order as the
XLA leg (:func:`ssm_advance_xla`, :func:`ssm_selective_xla`), except the order of the sum over ``N``;
interpreted parity is asserted in tests/test_granite_hybrid.py (1e-6 of the
tensor's scale).  :func:`ssm_step` takes the kernel's ``interpret`` flag or
None for the XLA leg, and :func:`leg` resolves that for a state's shape.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import kernels

__all__ = ["ssm_step", "ssm_advance_xla", "ssm_step_selective", "ssm_selective_xla", "supports", "leg"]

_BLOCK_BYTES = 1 << 20   # of the state in one block, float32: in and out double-buffered


def _block(state_dim: int, lanes: int) -> int:
    """Lanes of one block: as many as ``_BLOCK_BYTES`` hold of a float32 state
    ``state_dim`` deep, halved until they divide ``lanes`` (a group's lanes)."""
    block = min(max(_BLOCK_BYTES // (4 * state_dim), 1), lanes)
    while lanes % block:
        block //= 2
    return block


def supports(state_dtype, state_dim: int, lanes: int, *, interpret: bool, groups: int = 1) -> bool:
    """Whether the kernel takes a state of this type and shape, its lanes in
    ``groups`` groups: float32 (a 16-bit state would want 16 rows a tile and
    its own rounding), whole groups, and, compiled, whole (8, 128) tiles in a
    group's block."""
    if jnp.dtype(state_dtype) != jnp.float32 or lanes % groups:
        return False
    return interpret or (state_dim % 8 == 0 and _block(state_dim, lanes // groups) % 128 == 0)


def leg(state_dtype, state_dim: int, lanes: int, *, groups: int = 1) -> Optional[bool]:
    """The leg :func:`ssm_step` (and :func:`ssm_step_selective`: one group) takes over such a state: the kernel's
    ``interpret`` flag, or None for the XLA leg."""
    return kernels.resolve(
        "ssm_step", supported=lambda interpret: supports(state_dtype, state_dim, lanes, interpret=interpret, groups=groups))


def _over_lanes(a, lanes: int):
    """``B`` or ``C`` laid against the state's lanes: (S, N) -> (S, N, 1), one
    column for all; (S, G, N) -> (S, N, J), each group's column over the ``J /
    G`` lanes of its heads."""
    if a.ndim == 2:
        return a[:, :, None]
    return jnp.repeat(a.transpose(0, 2, 1), lanes // a.shape[1], axis=2)


def ssm_advance_xla(ssm, decay, dtx, B, C, *, layer: int):
    """:func:`ssm_step` without the kernel, which reads and writes the state
    once; this reads it twice."""
    J = ssm.shape[-1]
    h = decay[:, None, :] * ssm[layer].astype(jnp.float32) + _over_lanes(B, J) * dtx[:, None, :]
    return ssm.at[layer].set(h.astype(ssm.dtype)), jnp.sum(h * _over_lanes(C, J), axis=1)


def _step_kernel(layer_ref, decay_ref, dtx_ref, b_ref, c_ref, h_ref, h_out_ref, y_ref):
    del layer_ref                                        # it placed the blocks
    new = decay_ref[0] * h_ref[0, 0] + b_ref[0] * dtx_ref[0]          # (1, T) * (N, T) + (N, 1) * (1, T)
    h_out_ref[0, 0] = new
    y_ref[0] = jnp.sum(new * c_ref[0], axis=0, keepdims=True)


@kernels.with_xla_leg(ssm_advance_xla, static_argnames=("interpret",), donate_argnames=("state",))
def ssm_step(state, decay, dtx, B, C, *, layer, interpret):
    """One step of one layer for every slot: ``h' = decay * h + B (x) dtx`` and
    ``y = sum_n h' C`` on the ``layer``-th state (an int32 scalar or array of
    one) of ``state`` (layers, S, N, J), updated in place; ``decay`` and
    ``dtx`` (S, J): each head's ``exp(dt A)`` repeated over its head width, and
    ``dt x``; ``B`` and ``C`` (S, N), or (S, G, N) where the lanes lie in ``G``
    groups; ``interpret`` the kernel's flag (a float32 state: :func:`supports`),
    or None for the XLA leg (what :func:`leg` resolved).  Returns the state
    array and ``y`` (S, J) float32."""
    _layers, S, N, J = state.shape
    G = 1 if B.ndim == 2 else B.shape[1]
    if C.shape != B.shape or J % G:
        raise ValueError(f"ssm_step: B {B.shape} and C {C.shape} against a state of {J} lanes")
    T = _block(N, J // G)
    f32 = jnp.float32
    row = lambda a: a.astype(f32).reshape(S, 1, J)
    col = lambda a: a.astype(f32).reshape(S * G, N, 1)
    rows = pl.BlockSpec((1, 1, T), lambda s, j, layer: (s, 0, j))
    if G == 1:
        cols = pl.BlockSpec((1, N, 1), lambda s, j, layer: (s, 0, 0))
    else:       # the column of the group the block's lanes lie in
        per_group = J // G // T
        cols = pl.BlockSpec((1, N, 1), lambda s, j, layer: (s * G + j // per_group, 0, 0))
    block = pl.BlockSpec((1, 1, N, T), lambda s, j, layer: (layer[0], s, 0, j))
    new_state, y = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S, J // T),
            in_specs=[rows, rows, cols, cols, block], out_specs=[block, rows]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype), jax.ShapeDtypeStruct((S, 1, J), f32)],
        input_output_aliases={5: 0},          # the state (operand 5, the scalar first) is the first output
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="ssm_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), row(decay), row(dtx), col(B), col(C), state)
    return new_state, y.reshape(S, J)


# ---------------------------------------------------------- the selective form
def ssm_selective_xla(ssm, dt, A, dtx, B, C, *, layer):
    """:func:`ssm_step_selective` without the kernel: the decay, as large as
    the state, is written out, and the state read twice."""
    h = jnp.exp(dt[:, None, :] * A[None]) * ssm[layer].astype(jnp.float32) + B[:, :, None] * dtx[:, None, :]
    return ssm.at[layer].set(h.astype(ssm.dtype)), jnp.sum(h * C[:, :, None], axis=1)


def _selective_kernel(layer_ref, dt_ref, a_ref, dtx_ref, b_ref, c_ref, h_ref, h_out_ref, y_ref):
    del layer_ref                                        # it placed the blocks
    new = jnp.exp(dt_ref[0] * a_ref[...]) * h_ref[0, 0] + b_ref[0] * dtx_ref[0]    # exp((1, T) * (N, T)) * (N, T) + (N, 1) * (1, T)
    h_out_ref[0, 0] = new
    y_ref[0] = jnp.sum(new * c_ref[0], axis=0, keepdims=True)


@kernels.with_xla_leg(ssm_selective_xla, static_argnames=("interpret",), donate_argnames=("state",))
def ssm_step_selective(state, dt, A, dtx, B, C, *, layer, interpret):
    """One step of one Mamba-1 layer for every slot: ``h' = exp(dt (x) A) * h +
    B (x) dtx`` and ``y = sum_n h' C`` on the ``layer``-th state (an int32
    scalar or array of one) of ``state`` (layers, S, N, J), updated in place;
    ``dt`` and ``dtx`` (S, J): each lane's step size and ``dt x``; ``A`` (N, J),
    negative, every slot's; ``B`` and ``C`` (S, N).  A slot whose ``dt`` and
    ``dtx`` are 0 keeps its state bit for bit (``exp(0) h + 0``).  ``interpret``
    the kernel's flag, or None for the XLA leg (what :func:`leg` resolved).
    Returns the state array and ``y`` (S, J) float32."""
    _layers, S, N, J = state.shape
    if A.shape != (N, J) or B.shape != (S, N) or C.shape != B.shape:
        raise ValueError(f"ssm_step_selective: A {A.shape}, B {B.shape} and C {C.shape} against a state of {(N, J)} a slot")
    T = _block(N, J)
    f32 = jnp.float32
    row = lambda a: a.astype(f32).reshape(S, 1, J)
    col = lambda a: a.astype(f32).reshape(S, N, 1)
    # (lane blocks outermost: a block of ``A`` stays in VMEM while the slots go by)
    rows = pl.BlockSpec((1, 1, T), lambda j, s, layer: (s, 0, j))
    cols = pl.BlockSpec((1, N, 1), lambda j, s, layer: (s, 0, 0))
    block = pl.BlockSpec((1, 1, N, T), lambda j, s, layer: (layer[0], s, 0, j))
    new_state, y = pl.pallas_call(
        _selective_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(J // T, S),
            in_specs=[rows, pl.BlockSpec((N, T), lambda j, s, layer: (0, j)), rows, cols, cols, block],
            out_specs=[block, rows]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype), jax.ShapeDtypeStruct((S, 1, J), f32)],
        input_output_aliases={6: 0},          # the state (operand 6, the scalar first) is the first output
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="ssm_step_selective",
    )(jnp.asarray(layer, jnp.int32).reshape(1), row(dt), A.astype(f32), row(dtx), col(B), col(C), state)
    return new_state, y.reshape(S, J)
