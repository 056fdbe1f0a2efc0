"""Selective scan kernel — a Mamba-1 layer's recurrence over a prompt, the
state in VMEM from the first position to the last.

A prefill of a Mamba-1 layer runs, over one sequence from a zero state,

    h_t = exp(dt_t (x) A) * h_{t-1} + B_t (x) (dt_t u_t)        y_t = sum over the state dim of h_t * C_t

with a decay that is one value a (state row, lane): ``dt_t`` is a row over the
``J`` lanes and ``A`` an ``(N, J)`` block, so there is no scalar a head to
factor out and the matmul form of ``models/mamba2.py:ssd_chunked`` (Mamba-2's
state-space duality) cannot compute it.  Written in XLA the recurrence is a
loop whose carry, the state, goes to HBM and back every position (or, as an
associative scan, ``T`` copies of the state).  This kernel keeps the state
``(N, block)`` float32 in VMEM (in vector registers, at the shapes it is run
at) while it walks the positions, reads ``u`` and ``dt`` once, and writes ``y``
and the last state once.

  * **layout** — as ``kernels/ssm_step.py``: ``N`` on sublanes, the ``J``
    channels on lanes, so ``dt_t`` and ``dt_t u_t`` are ROWS over the lanes,
    ``B_t`` and ``C_t`` COLUMNS over ``N`` (they come in as ``(T / 8, N, 8)``:
    eight positions' columns side by side, the eight on the leading axis, which
    a dynamic index may pick, and a position's column a static lane of them),
    and a position is two broadcasts, an exponential, two multiply-adds and a
    sublane reduction.  The state the kernel returns, ``(N, J)``, is a layer's
    slot of the cache's array as ``ssm_step`` reads it.
  * **grid** — ``(J / block, T / rows)``: lane blocks outermost (independent),
    the positions innermost and sequential; the state is scratch that lives
    from a lane block's first rows to its last.  ``block`` is ``_LANES`` (512:
    a state of 16 rows is 8 vector registers) or all of ``J`` where that is
    smaller; ``rows`` at most ``_ROWS`` positions a grid step.
  * **per grid step** — the positions go by eights: one aligned ``(8,
    block)`` load of ``dt`` and of ``u``, eight positions unrolled, one aligned
    ``(8, block)`` store of ``y``.

A position whose ``dt`` is 0 decays nothing and adds nothing (``exp(0) h +
0``): the caller forces ``dt`` to 0 past a prompt's length, and the state then
stands where the prompt ends.  Numerics: float32 throughout, the same
operations in the same order as the XLA leg (:func:`selective_scan_xla`: a
``lax.scan`` over the positions), except the order of the sum over ``N``;
interpreted parity is asserted in tests/test_phi4flash.py (1e-6 of the tensor's
scale).  :func:`selective_scan` takes the kernel's ``interpret`` flag or None
for the XLA leg, and :func:`leg` resolves that for a shape.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import kernels

__all__ = ["selective_scan", "selective_scan_xla", "supports", "leg"]

_LANES = 512     # of a state block: N = 16 rows of it are 8 vector registers, so the state never leaves them
_ROWS = 128      # positions a grid step, at most
_UNROLL = 8      # positions a loop trip: one (8, 128) tile's sublanes


def _fit(most: int, n: int) -> int:
    """The largest halving of ``most`` that divides ``n``."""
    block = min(most, n)
    while n % block:
        block //= 2
    return block


def _lanes(J: int) -> int:
    return _fit(_LANES, J)


def _rows(T: int) -> int:
    return _fit(_ROWS, T)


def supports(state_dim: int, lanes: int, positions: int, *, interpret: bool) -> bool:
    """Whether the kernel takes a sequence of ``positions`` positions over a
    state of ``state_dim`` rows by ``lanes`` lanes: the positions go by eights,
    and, compiled, the blocks are whole (8, 128) tiles."""
    if positions % _UNROLL or _rows(positions) % _UNROLL:
        return False
    return interpret or (state_dim % 8 == 0 and _lanes(lanes) % 128 == 0)


def leg(state_dim: int, lanes: int, positions: int) -> Optional[bool]:
    """The leg :func:`selective_scan` takes over such a sequence: the kernel's ``interpret`` flag, or None for the XLA leg."""
    return kernels.resolve(
        "selective_scan", supported=lambda interpret: supports(state_dim, lanes, positions, interpret=interpret))


def selective_scan_xla(u, dt, A, B, C):
    """:func:`selective_scan` without the kernel: a ``lax.scan`` over the
    positions (eight a trip), the state its carry."""
    f32 = jnp.float32
    A = A.astype(f32)

    def position(h, inp):
        u_t, dt_t, b_t, c_t = inp
        h = jnp.exp(dt_t[None, :] * A) * h + b_t[:, None] * (dt_t * u_t)[None, :]
        return h, jnp.sum(h * c_t[:, None], axis=0)

    last, y = jax.lax.scan(position, jnp.zeros(A.shape, f32), tuple(a.astype(f32) for a in (u, dt, B, C)),
                           unroll=min(_UNROLL, u.shape[0]))
    return y, last


def _scan_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, last_ref, h_scr, *, rows: int):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _():
        h_scr[...] = jnp.zeros_like(h_scr)

    A = a_ref[...]
    sublane = jax.lax.broadcasted_iota(jnp.int32, (_UNROLL, u_ref.shape[1]), 0)

    def eight(g, h):
        first = pl.multiple_of(g * _UNROLL, _UNROLL)
        dt8 = dt_ref[pl.ds(first, _UNROLL), :]
        dtu8 = dt8 * u_ref[pl.ds(first, _UNROLL), :]
        b8, c8 = b_ref[g], c_ref[g]                                                       # (N, 8): eight columns
        y8 = jnp.zeros(dt8.shape, jnp.float32)
        for i in range(_UNROLL):
            h = jnp.exp(dt8[i: i + 1, :] * A) * h + b8[:, i: i + 1] * dtu8[i: i + 1, :]      # (N, T)
            y8 = jnp.where(sublane == i, jnp.sum(h * c8[:, i: i + 1], axis=0, keepdims=True), y8)
        y_ref[pl.ds(first, _UNROLL), :] = y8
        return h

    h = jax.lax.fori_loop(0, rows // _UNROLL, eight, h_scr[...])
    h_scr[...] = h

    @pl.when(t == pl.num_programs(1) - 1)
    def _():
        last_ref[...] = h


@kernels.with_xla_leg(selective_scan_xla, static_argnames=("interpret",))
def selective_scan(u, dt, A, B, C, *, interpret):
    """The recurrence over one sequence from a zero state: ``u`` and ``dt`` (T,
    J), the layer's input after its convolution and its step sizes (0 where a
    position must leave the state as it was); ``A`` (N, J), negative; ``B`` and
    ``C`` (T, N).  ``interpret`` the kernel's flag (:func:`supports`), or None
    for the XLA leg (what :func:`leg` resolved).  Returns ``y`` (T, J) and the
    state after the last position (N, J), float32."""
    T, J = u.shape
    N = A.shape[0]
    if dt.shape != (T, J) or A.shape != (N, J) or B.shape != (T, N) or C.shape != (T, N):
        raise ValueError(f"selective_scan: u {u.shape}, dt {dt.shape}, A {A.shape}, B {B.shape}, C {C.shape}")
    if not supports(N, J, T, interpret=bool(interpret)):
        raise ValueError(f"selective_scan takes no sequence of {T} positions over a state of {(N, J)} (see supports())")
    f32 = jnp.float32
    lanes, rows = _lanes(J), _rows(T)
    wide = pl.BlockSpec((rows, lanes), lambda j, t: (t, j))
    cols = pl.BlockSpec((rows // _UNROLL, N, _UNROLL), lambda j, t: (t, 0, 0))
    by_eights = lambda a: a.astype(f32).reshape(T // _UNROLL, _UNROLL, N).transpose(0, 2, 1)
    state = pl.BlockSpec((N, lanes), lambda j, t: (0, j))
    y, last = pl.pallas_call(
        functools.partial(_scan_kernel, rows=rows),
        grid=(J // lanes, T // rows),
        in_specs=[wide, wide, state, cols, cols],
        out_specs=[wide, state],
        out_shape=[jax.ShapeDtypeStruct((T, J), f32), jax.ShapeDtypeStruct((N, J), f32)],
        scratch_shapes=[pltpu.VMEM((N, lanes), f32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="selective_scan",
    )(u.astype(f32), dt.astype(f32), A.astype(f32), by_eights(B), by_eights(C))
    return y, last
