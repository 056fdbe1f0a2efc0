"""Fused vocab-parallel cross entropy — sumexp + gold pick in ONE pass.

``loss.py``'s vocab-parallel path computes, per rank, three separate
passes over the local logits shard: ``sum(exp(lg - gmax))``, the gold
logit pick (``take_along_axis``), and (under label smoothing) ``sum(lg)``.
This kernel walks the vocab dim once per row block and accumulates all
three in VMEM scratch — each logit is read from HBM exactly once — while
keeping the no-full-logits property: everything here is per-shard; the
cross-shard ``pmax``/``psum`` stay with the caller, unchanged.

Shapes: ``lg`` (N, Vs) fp32 local shard rows, ``idx`` (N,) int32 LOCAL
column ids (already clipped in-range by the caller — out-of-range rows are
masked by the caller's ``in_range`` exactly like the XLA path), ``gmax``
(N,) fp32 global row max (stop-gradient, nondiff).  Returns
``(sumexp, picked, sumlg)`` fp32 (N,) each.

Differentiable via custom_vjp (the loss sits under ``value_and_grad`` in
every train step): the backward is its own one-pass kernel computing
``dlg = g_se * exp(lg - gmax) + onehot(idx) * g_pick + g_sl`` — the exact
cotangent jax AD derives for the XLA path's three ops.

Parity: fp32, same elementwise math; the vocab-dim SUM is blocked, so
accumulation order differs from XLA's row reduction — parity is
ulp-bounded (asserted in tests/test_kernels.py; docs/kernels.md)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_xent_parts", "xent_blocks"]


def _fit_pow2(n: int, cap: int) -> int:
    """Largest power-of-two divisor of ``n``, at most ``cap`` (>= 1)."""
    b = 1
    while b * 2 <= cap and n % (b * 2) == 0:
        b *= 2
    return b


def xent_blocks(n_rows: int, vs: int):
    """(row_block, col_block) for the kernel grid, or None when the shard
    is not worth a kernel launch (callers fall back to the XLA path and
    count it)."""
    if n_rows <= 0 or vs < 8:
        return None
    return _fit_pow2(n_rows, 8), _fit_pow2(vs, 512)


def _xent_fwd_kernel(lg_ref, idx_ref, gmax_ref, se_ref, pk_ref, sl_ref,
                     se_s, pk_s, sl_s, *, block_c):
    j = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        se_s[...] = jnp.zeros(se_s.shape, jnp.float32)
        pk_s[...] = jnp.zeros(pk_s.shape, jnp.float32)
        sl_s[...] = jnp.zeros(sl_s.shape, jnp.float32)

    lg = lg_ref[...].astype(jnp.float32)            # (R, C)
    gmax = gmax_ref[...]                            # (R, 1)
    cols = j * block_c + jax.lax.broadcasted_iota(jnp.int32, lg.shape, 1)
    se_s[...] = se_s[...] + jnp.sum(jnp.exp(lg - gmax), axis=1, keepdims=True)
    hit = cols == idx_ref[...]                      # (R, C) one-hot row pick
    pk_s[...] = pk_s[...] + jnp.sum(jnp.where(hit, lg, 0.0), axis=1, keepdims=True)
    sl_s[...] = sl_s[...] + jnp.sum(lg, axis=1, keepdims=True)

    @pl.when(j == nv - 1)
    def _final():
        se_ref[...] = se_s[...]
        pk_ref[...] = pk_s[...]
        sl_ref[...] = sl_s[...]


def _xent_bwd_kernel(lg_ref, idx_ref, gmax_ref, gse_ref, gpk_ref, gsl_ref, dlg_ref,
                     *, block_c):
    j = pl.program_id(1)
    lg = lg_ref[...].astype(jnp.float32)
    gmax = gmax_ref[...]
    cols = j * block_c + jax.lax.broadcasted_iota(jnp.int32, lg.shape, 1)
    dlg = gse_ref[...] * jnp.exp(lg - gmax)
    dlg = dlg + jnp.where(cols == idx_ref[...], gpk_ref[...], 0.0)
    dlg = dlg + gsl_ref[...]
    dlg_ref[...] = dlg.astype(dlg_ref.dtype)


def _row_spec(R):
    return pl.BlockSpec((R, 1), lambda i, j: (i, 0))


def _fwd_call(lg, idx, gmax, interpret):
    N, Vs = lg.shape
    R, C = xent_blocks(N, Vs)
    grid = (N // R, Vs // C)
    col2 = lambda i, j: (i, j)
    outs = pl.pallas_call(
        functools.partial(_xent_fwd_kernel, block_c=C),
        grid=grid,
        in_specs=[
            pl.BlockSpec((R, C), col2),
            _row_spec(R),
            _row_spec(R),
        ],
        out_specs=(_row_spec(R), _row_spec(R), _row_spec(R)),
        out_shape=(
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, 1), jnp.float32),
        ],
        interpret=interpret,
    )(lg, idx[:, None].astype(jnp.int32), gmax[:, None].astype(jnp.float32))
    return tuple(o[:, 0] for o in outs)


def _bwd_call(lg, idx, gmax, gse, gpk, gsl, interpret):
    N, Vs = lg.shape
    R, C = xent_blocks(N, Vs)
    grid = (N // R, Vs // C)
    col2 = lambda i, j: (i, j)
    return pl.pallas_call(
        functools.partial(_xent_bwd_kernel, block_c=C),
        grid=grid,
        in_specs=[
            pl.BlockSpec((R, C), col2),
            _row_spec(R),
            _row_spec(R),
            _row_spec(R),
            _row_spec(R),
            _row_spec(R),
        ],
        out_specs=pl.BlockSpec((R, C), col2),
        out_shape=jax.ShapeDtypeStruct(lg.shape, lg.dtype),
        interpret=interpret,
    )(
        lg,
        idx[:, None].astype(jnp.int32),
        gmax[:, None].astype(jnp.float32),
        gse[:, None].astype(jnp.float32),
        gpk[:, None].astype(jnp.float32),
        gsl[:, None].astype(jnp.float32),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_xent_parts(lg, idx, gmax, interpret):
    """(sumexp, picked, sumlg) over the vocab dim of ``lg`` in one pass.
    ``idx`` int32 local gold columns (clipped), ``gmax`` fp32 row max
    (treated nondiff — the caller stop-gradients it, and the max shift
    cancels in the gradient exactly as in the XLA path)."""
    return _fwd_call(lg, idx, gmax, interpret)


def _fused_fwd(lg, idx, gmax, interpret):
    return _fwd_call(lg, idx, gmax, interpret), (lg, idx, gmax)


def _fused_bwd(interpret, res, cts):
    lg, idx, gmax = res
    gse, gpk, gsl = cts
    dlg = _bwd_call(lg, idx, gmax, gse, gpk, gsl, interpret)
    # int cotangent is float0; gmax is stop-gradient upstream
    return dlg, np.zeros(idx.shape, jax.dtypes.float0), jnp.zeros_like(gmax)


fused_xent_parts.defvjp(_fused_fwd, _fused_bwd)
