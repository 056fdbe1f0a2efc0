"""Grouped SwiGLU kernel — each held expert's three matrices, brought into VMEM
once, over that expert's own rows.

The sorted form of ``moe/dropless.py`` has the (token, expert) pairs that fell
on held experts in expert order and wants, for expert ``e``'s rows ``x``,

    y = (silu(x W_gate,e) * (x W_up,e)) W_down,e

XLA gives that as three ``ragged_dot`` kernels with the hidden between them in
HBM; none of them knows that an expert's three matrices are used together on the
same few rows, and at 16 to 128 rows an expert they stream the weights at 94-340
GB/s of the chip's 819 (PERF.md section 6, PR 37 and PR 46).  This kernel is the
three products as ONE grid over ROW TILES:

  * **layout** — ``xs`` (R, d) holds the held pairs' token rows in expert order,
    each expert's segment starting at a multiple of the row tile (the cumulative
    sum of the counts rounded up to the tile; ``moe/dropless.py`` lays it out
    with one sort), so a tile belongs to one expert.  ``R`` is static: ``tiles x
    tile`` with ``tiles = ceil(pairs / tile) + held`` (:func:`row_tiles`: every
    expert may end in a tile that is not full).  The tiles that hold rows come
    first; an expert that got no row has no tile, so it costs no read and no
    grid step.
  * **operands** — the tile's expert rides in as a scalar-prefetch operand and
    picks the weight blocks; consecutive tiles of one expert name the same
    blocks, which the pipeline then does not fetch again, and the next expert's
    weights arrive while this one's last tile is in the MXU.  The tiles behind
    the last real one name the last real tile's blocks and do nothing: no read,
    no product, no write.  Rows of ``ys`` behind an expert's count, and behind
    the last real tile, are undefined, as the rows past the groups of a
    ``ragged_dot`` are.
  * **tiles** (:func:`tiles`, from ``d``, ``f``, the operand type and the static
    mean rows an expert, never from a model's name) — a row tile is the power of
    two at or above one and a half times the mean rows (a uniform router's
    busiest experts still take one tile; the chip's sweep, PERF.md section 6, PR
    46), from the type's sublane packing up to ``_ROW_TILE_MAX``, the MXU's own
    128 rows: more rows an expert are more tiles on the same resident weights,
    and a larger tile would multiply its zeros.  The expert's matrices are whole
    where, double-buffered, they fit ``_WEIGHT_BYTES`` of VMEM, else in tiles over
    ``f`` (the second grid axis) with ``ys``' block as the float32 accumulator;
    the weights then pass once a ROW TILE, so the row tile goes up to
    ``_ROW_TILE_MAX_STREAMED``, where a tile's products take as long as its
    weights' read (256 rows x 6 d f operations at the chip's 197 TFLOP/s against
    6 d f bytes at 819 GB/s).

Numerics: the products on the operands as they are with float32 accumulation,
the hidden rounded to the operand type, exactly where the XLA leg rounds it; with
tiles over ``f`` the down product's partial sums are added tile by tile in
float32.  Interpreted parity with the loop over tokens and with the XLA leg is
asserted in tests/test_dropless.py.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["grouped_swiglu", "tiles", "row_tiles", "supports"]

_WEIGHT_BYTES = 40 << 20            # an expert's three weight blocks, double-buffered
_ROW_TILE_MAX = 128                 # rows a tile where an expert's matrices stay whole in VMEM across its tiles
_ROW_TILE_MAX_STREAMED = 256        # ... and where they pass once a row tile: a tile's products then take as long as its weights' read
_VMEM_LIMIT_BYTES = 100 << 20       # of the chip's 128 MiB


def _sublanes(dtype) -> int:
    return 32 // jnp.dtype(dtype).itemsize          # rows of one (sublane, lane) tile: 8 float32, 16 bfloat16


def tiles(d: int, f: int, dtype, mean_rows: float) -> Tuple[int, int]:
    """``(row tile, f tile)`` for experts of ``d`` x ``f`` in ``dtype`` that get
    ``mean_rows`` rows each on average (static: pairs / held)."""
    fits = lambda t: 2 * 3 * d * t * jnp.dtype(dtype).itemsize <= _WEIGHT_BYTES
    # whole, or the largest whole-lane divisor of f that fits (1536: 512)
    tf = f if fits(f) else max((t for t in range(128, f, 128) if f % t == 0 and fits(t)), default=f)
    cap = _ROW_TILE_MAX if tf == f else _ROW_TILE_MAX_STREAMED
    tm = _sublanes(dtype)
    while tm < min(1.5 * mean_rows, cap):
        tm *= 2
    return tm, tf


def row_tiles(pairs: int, held: int, tm: int) -> int:
    """The static number of row tiles: every held expert may end in a tile that is not full."""
    return -(-pairs // tm) + held


def supports(dtype, d: int, f: int, *, interpret: bool) -> bool:
    """Whether the kernel takes experts of this type and these widths: any under
    the interpreter; compiled, float32 or bfloat16 operands in whole lanes."""
    return interpret or (jnp.dtype(dtype) in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)) and d % 128 == 0 and f % 128 == 0)


def _kernel(expert_ref, real_ref, x_ref, gate_ref, up_ref, down_ref, y_ref, *, f_tiles: int):
    del expert_ref                                       # it placed the weight blocks
    j = pl.program_id(1)

    @pl.when(pl.program_id(0) < real_ref[0])
    def _():
        x = x_ref[...]
        product = lambda a, w: jnp.dot(a, w, preferred_element_type=jnp.float32)
        hidden = (jax.nn.silu(product(x, gate_ref[0])) * product(x, up_ref[0])).astype(x.dtype)
        y = product(hidden, down_ref[0])
        if f_tiles == 1:
            y_ref[...] = y
        else:
            @pl.when(j == 0)
            def _():
                y_ref[...] = y

            @pl.when(j > 0)
            def _():
                y_ref[...] += y


@functools.partial(jax.jit, static_argnames=("tm", "tf", "interpret"))
def grouped_swiglu(xs, counts, w_gate, w_up, w_down, *, tm: int, tf: int, interpret: bool):
    """``ys[r] = (silu(xs[r] W_gate,e) * (xs[r] W_up,e)) W_down,e`` for the rows
    ``r`` of expert ``e``'s segment.  ``xs`` (R, d), ``R = row_tiles(pairs, held,
    tm) x tm``, expert ``e``'s ``counts[e]`` rows (held, int32) from the cumulative
    sum of the counts before it, each rounded up to ``tm``;
    ``w_gate`` / ``w_up`` (held, d, f) and ``w_down`` (held, f, d) of ``xs``' type;
    ``tm`` / ``tf`` from :func:`tiles`.  Returns ``ys`` (R, d) float32."""
    R, d = xs.shape
    held, _, f = w_gate.shape
    T, f_tiles = R // tm, f // tf
    if R % tm or f % tf or w_up.shape != (held, d, f) or w_down.shape != (held, f, d):
        raise ValueError(f"grouped_swiglu: rows {xs.shape} in tiles of {tm}, experts {w_gate.shape} / {w_up.shape} / "
                         f"{w_down.shape} in tiles of {tf}")
    ends = jnp.cumsum((counts + tm - 1) // tm)          # the tiles up to and with each expert
    real = ends[-1]
    # a tile behind the last real one names that one's blocks: nothing moves for it
    block = jnp.minimum(jnp.arange(T, dtype=jnp.int32), jnp.maximum(real - 1, 0))
    expert = jnp.minimum(jnp.sum(block[:, None] >= ends[None, :], axis=1), held - 1).astype(jnp.int32)

    def rows(t, j, expert, real):
        return jnp.minimum(t, jnp.maximum(real[0] - 1, 0)), 0

    def f_block(t, j, real):
        return jnp.where(t < real[0], j, f_tiles - 1)

    return pl.pallas_call(
        functools.partial(_kernel, f_tiles=f_tiles),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(T, f_tiles),
            in_specs=[pl.BlockSpec((tm, d), rows),
                      pl.BlockSpec((1, d, tf), lambda t, j, expert, real: (expert[t], 0, f_block(t, j, real))),
                      pl.BlockSpec((1, d, tf), lambda t, j, expert, real: (expert[t], 0, f_block(t, j, real))),
                      pl.BlockSpec((1, tf, d), lambda t, j, expert, real: (expert[t], f_block(t, j, real), 0))],
            out_specs=pl.BlockSpec((tm, d), rows)),
        out_shape=jax.ShapeDtypeStruct((R, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary"),
                                             vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="grouped_swiglu",
    )(expert, real.reshape(1).astype(jnp.int32), xs, w_gate, w_up, w_down)
