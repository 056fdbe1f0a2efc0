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

Numerics: float32 throughout, the same operations in the same order as the
XLA leg (``models/granite_hybrid.py``), except the order of the sum over
``N``; interpreted parity is asserted in tests/test_granite_hybrid.py (1e-6 of the
tensor's scale).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssm_step", "supports"]

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


def _step_kernel(layer_ref, decay_ref, dtx_ref, b_ref, c_ref, h_ref, h_out_ref, y_ref):
    del layer_ref                                        # it placed the blocks
    new = decay_ref[0] * h_ref[0, 0] + b_ref[0] * dtx_ref[0]          # (1, T) * (N, T) + (N, 1) * (1, T)
    h_out_ref[0, 0] = new
    y_ref[0] = jnp.sum(new * c_ref[0], axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnames=("state",))
def ssm_step(state, decay, dtx, B, C, *, layer, interpret: bool):
    """One step of one layer for every slot.  ``state`` (layers, S, N, J)
    float32, updated in place at ``layer`` (an int32 scalar or array of one);
    ``decay`` and ``dtx`` (S, J): each head's ``exp(dt A)`` repeated over its
    head width, and ``dt x``; ``B`` and ``C`` (S, N), or (S, G, N) where the
    lanes lie in ``G`` groups.  Returns the state array and ``y`` (S, J) float32."""
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
