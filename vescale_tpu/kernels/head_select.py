"""Head-to-selection kernel — the largest logit of a row, its id and the softmax
denominator, straight off the head's product: no logits are written.

A pass of generation by diffusion over blocks (``models/sdar_moe.py``) asks of
each open row's logits three numbers: ``top`` (the largest), ``best`` (its id)
and ``denominator = sum(exp(logit - top))`` (the row's confidence is its
inverse: the softmax probability of ``best`` over the whole vocabulary).  XLA
gives them as the head's product written out in float32 (512 rows x 151,936
columns: 311 MB) and two more reads of that (PERF.md section 6, PR 47).  Here
the product never leaves VMEM:

  * **layout** — the rows ``x`` (R, d), normalised and in the operand type, are
    ONE row block that stays in VMEM (512 x 2048 bfloat16: 2 MB); the head's
    weights ``w`` (d, V) stream past ONCE in tiles of ``tile`` columns of the
    vocabulary (the grid; 1,024 at these widths, 4 MB: the whole kernel stays
    inside the 16 MiB of VMEM a kernel has without asking for more), and a
    tile's product is taken ``chunk`` columns at a time, so that the reduction
    of one chunk's scores and the product of the next are independent work in
    one basic block for the scheduler to overlap: the product is compute-bound
    (at 512 rows 1.62 ms of MXU against 0.76 ms of weights), the reduction is
    VPU work under it.
  * **the running triple is kept A LANE**: three ``(R, 128)`` scratch arrays
    hold, for each of a row's 128 lanes, the largest score that lane has seen
    (columns ``lane, lane + 128, ...``), the id it was first seen at and the
    lane's own online denominator, and a slab of 128 columns of scores folds in
    with elementwise work alone (a larger score rescales the lane's denominator
    by ``exp(old - new)``); the one reduction ACROSS lanes is the last grid
    step's, which writes the three ``(R, 1)`` outputs.  (On the chip this reads
    1.71 ms where a cross-lane reduction a chunk reads 1.79 and XLA's product
    alone 1.72: PERF.md section 6, PR 47.  The outputs are ``(R, 1)``, not
    padded to whole lanes: nothing of ``R x 128`` is among the call's shapes,
    which an op table could take for an expert layer's.)
  * **a vocabulary that is not whole tiles** (151,936 = 128 x 1,187, and 1,187
    is prime): the last tile's columns past ``V`` hold whatever the buffer
    held; the slabs wholly past ``V`` are not computed (static), the one slab
    that straddles it is masked to ``-inf``.  No padded copy of the head is made.

Numerics: the product on the operands as they are with float32 accumulation,
statistics float32: the precision of the XLA leg.  ``top`` and ``best`` are
exactly the XLA leg's where the scores are (a tie goes to the LOWEST id; a NaN
counts as the largest, as ``jnp.argmax`` has it: ``best`` is the first NaN's id,
or a ``+inf``'s before it, the denominator NaN, and ``top`` reads ``+inf`` where
``jnp.max`` reads NaN); the online denominator differs from the two-pass one in
its last bits (20 steps of float32 at the sum's scale over 151,936 columns on
the chip, as two orders of summation differ).  Interpreted parity with the XLA
leg is asserted in tests/test_head_select.py.  :func:`head_select` takes the
kernel's ``interpret`` flag or None
for the XLA leg (the logits ``(R, V)`` are made, and :func:`logit_stats` reads
them three times), and :func:`leg` resolves that for the rows' shape.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import kernels

__all__ = ["head_select", "logit_stats", "supports", "leg"]

_WEIGHT_TILE_BYTES = 4 << 20        # of the head a grid step, held twice (the next tile arrives under this one's products)
_CHUNK = 512                        # columns a product inside a step: a (512, 512) float32 block of scores is 1 MB; whole lanes
_LANES = 128
# what the kernel may hold in VMEM: under the 16 MiB a kernel gets WITHOUT ASKING FOR MORE.  A call that raises its limit
# (``vmem_limit_bytes``) changes how the compiler builds every fusion of the program around it: with 100 MiB asked for here,
# each of the pass's six expert layers read 0.15 ms slower and ate a third of what this kernel saves (PERF.md section 6, PR 47)
_VMEM_BYTES = 14 << 20


def _tile(d: int, dtype) -> int:
    """Columns of the head a grid step: whole chunks, ``_WEIGHT_TILE_BYTES`` of weights."""
    return max(_CHUNK, _WEIGHT_TILE_BYTES // (d * jnp.dtype(dtype).itemsize) // _CHUNK * _CHUNK)


def _vmem_bytes(rows: int, d: int, dtype) -> int:
    """The rows and a tile of the head, double-buffered; a chunk's scores; the three running arrays."""
    item = jnp.dtype(dtype).itemsize
    return 2 * rows * d * item + 2 * d * _tile(d, dtype) * item + rows * _CHUNK * 4 + 3 * rows * _LANES * 4


def supports(dtype, rows: int, d: int, *, interpret: bool) -> bool:
    """Whether the kernel takes ``rows`` rows of ``d`` in ``dtype``: any under
    the interpreter; compiled, float32 or bfloat16 operands in whole lanes
    whose rows, as ONE block, fit the VMEM a kernel has by default."""
    return interpret or (jnp.dtype(dtype) in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)) and d % 128 == 0
                         and _vmem_bytes(rows, d, dtype) <= _VMEM_BYTES)


def leg(dtype, rows: int, d: int) -> Optional[bool]:
    """The leg :func:`head_select` takes for such rows: the kernel's ``interpret`` flag, or None for the XLA leg."""
    return kernels.resolve("head_select", supported=lambda interpret: supports(dtype, rows, d, interpret=interpret))


def logit_stats(logits):
    """Of rows of logits ``(N, vocab)``, what a selection asks of each: ``top``
    (the largest logit), ``best`` (its id, int32: a tie goes to the lowest id)
    and ``denominator = sum(exp(logit - top))`` over the whole vocabulary, the
    softmax's: the probability of ``best`` is its inverse."""
    top = jnp.max(logits, axis=-1)
    return top, jnp.argmax(logits, axis=-1).astype(jnp.int32), jnp.sum(jnp.exp(logits - top[:, None]), axis=-1)


def _kernel(x_ref, w_ref, top_ref, best_ref, den_ref, peak_ref, sum_ref, at_ref, *, vocab: int, tile: int, chunk: int):
    j = pl.program_id(0)
    last = pl.num_programs(0) - 1

    @pl.when(j == 0)
    def _():
        peak_ref[...] = jnp.full(peak_ref.shape, -jnp.inf, jnp.float32)
        sum_ref[...] = jnp.zeros(sum_ref.shape, jnp.float32)
        at_ref[...] = jnp.zeros(at_ref.shape, jnp.int32)

    def fold(valid: int):
        """The tile's first ``valid`` columns (static) into every lane's running triple."""
        x = x_ref[...]
        lane = jax.lax.broadcasted_iota(jnp.int32, peak_ref.shape, 1)
        for start in range(0, valid, chunk):
            scores = jnp.dot(x, w_ref[:, start:start + chunk], preferred_element_type=jnp.float32)
            peak, total, at = peak_ref[...], sum_ref[...], at_ref[...]
            for first in range(0, min(chunk, valid - start), _LANES):
                slab = scores[:, first:first + _LANES]
                straddles = start + first + _LANES > valid                      # (static) the vocabulary ends in this slab
                if straddles:
                    slab = jnp.where(lane < valid - start - first, slab, -jnp.inf)
                key = jnp.where(slab != slab, jnp.inf, slab)                    # a NaN is the largest
                new = jnp.maximum(peak, key)
                # (a lane that has seen no column yet keeps a sum of 0: exp(-inf - 0), where -inf + inf is a NaN)
                scale = jnp.where(new == -jnp.inf, 0.0, new) if straddles else new
                total = total * jnp.exp(peak - scale) + jnp.exp(slab - scale)
                at = jnp.where(key > peak, lane + (j * tile + start + first), at)   # (an equal one later: the lower id stays)
                peak = new
            peak_ref[...], sum_ref[...], at_ref[...] = peak, total, at

    ragged = vocab % tile
    if not ragged:
        fold(tile)
    else:
        pl.when(j < last)(lambda: fold(tile))
        pl.when(j == last)(lambda: fold(ragged))

    @pl.when(j == last)
    def _():
        peak, total, at = peak_ref[...], sum_ref[...], at_ref[...]
        top = jnp.max(peak, axis=-1, keepdims=True)
        top_ref[...] = top
        den_ref[...] = jnp.sum(total * jnp.exp(peak - top), axis=-1, keepdims=True)     # (a lane that saw no column: 0 x exp(-inf))
        best_ref[...] = jnp.min(jnp.where(peak == top, at, jnp.iinfo(jnp.int32).max), axis=-1, keepdims=True)


@kernels.with_xla_leg(lambda x, w, **_tiles: logit_stats(jnp.dot(x, w, preferred_element_type=jnp.float32)),
                      static_argnames=("interpret", "tile", "chunk"))
def head_select(x, w, *, interpret, tile: Optional[int] = None, chunk: int = _CHUNK):
    """``(top, best, denominator)`` of the rows of ``x @ w`` (:func:`logit_stats`
    of them), each ``(R,)``: float32, int32, float32.  ``x`` (R, d) and ``w``
    (d, V) of one type (the product's operands, float32 accumulation);
    ``interpret`` the kernel's flag, or None for the XLA leg (what :func:`leg`
    resolved); of the kernel, ``tile`` columns of ``w`` a grid step (by default
    what ``_WEIGHT_TILE_BYTES`` hold), ``chunk`` (a divisor of it) a product."""
    R, d = x.shape
    V = w.shape[1]
    tile = min(tile or _tile(d, x.dtype), -(-V // _LANES) * _LANES)
    chunk = min(chunk, tile)
    if w.shape[0] != d or x.dtype != w.dtype or tile % chunk or chunk % _LANES:
        raise ValueError(f"head_select: rows {x.shape} {x.dtype} on a head {w.shape} {w.dtype}, tiles of {tile} in products of {chunk}")
    column = lambda dtype: jax.ShapeDtypeStruct((R, 1), dtype)
    triple = pl.BlockSpec((R, 1), lambda j: (0, 0))
    top, best, den = pl.pallas_call(
        functools.partial(_kernel, vocab=V, tile=tile, chunk=chunk),
        grid=(-(-V // tile),),
        in_specs=[pl.BlockSpec((R, d), lambda j: (0, 0)), pl.BlockSpec((d, tile), lambda j: (0, j))],
        out_specs=(triple, triple, triple),
        out_shape=(column(jnp.float32), column(jnp.int32), column(jnp.float32)),
        scratch_shapes=[pltpu.VMEM((R, _LANES), jnp.float32), pltpu.VMEM((R, _LANES), jnp.float32), pltpu.VMEM((R, _LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="head_select",
    )(x, w)
    return top[:, 0], best[:, 0], den[:, 0]
