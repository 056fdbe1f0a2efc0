"""Fused adamw_lowmem update — one kernel over (g, m, v) per leaf.

``parallel/optimizer.py``'s ``scale_by_adam_lowmem`` runs its moment
update as a chain of ~10 elementwise XLA ops per leaf (two casts in, two
muls + adds for each moment, square, sqrt, two divides, three casts out).
This kernel performs the WHOLE chain in one pass per block — each element
of g/m/v is read once from HBM and each output written once, instead of
XLA's fusion boundaries deciding how many intermediate materializations
the chain costs.

The math is the reference chain verbatim, in the same order, in fp32 —
purely elementwise, so kernel output is BIT-IDENTICAL to the XLA path
(asserted in tests/test_kernels.py, not ulp-bounded).  The bias-correction
scalars c1/c2 are computed once per step by the caller (exactly where the
reference computes them) and ride in as a scalar-prefetch operand.

Sharded leaves: the public entry is wrapped in ``custom_partitioning``
with the STATE leaf's sharding as the rule (g is resharded to match m/v),
which is precisely ZeRO's weight-update sharding — the update runs on
each rank's 1/dp state shard, same as the XLA chain under GSPMD — so
kernel dispatch does not change the program's collective structure.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_adamw_update", "update_ulps_vs_float64"]

# Flattened leaves are viewed as (rows, _LANES) and each grid step works a
# (_SUB, _LANES) block: lane dim matches the TPU tile (…, 128) so nothing
# is padded inside a tile, and 64K elements per step keeps the sequential
# grid short (a 16M-element weight is 256 steps, not tens of thousands)
# while staying ~0.5 MB of VMEM across the six operands.  Any leaf size
# works — the launch pads the tail block once, outside the kernel.
_LANES = 128
_SUB = 512
_BLOCK = _SUB * _LANES  # elements per grid step


def _adamw_kernel(coef_ref, g_ref, m_ref, v_ref, u_ref, mo_ref, vo_ref, *, b1, b2, eps):
    # the reference chain (optimizer.scale_by_adam_lowmem.one), same order
    g32 = g_ref[...].astype(jnp.float32)
    m32 = b1 * m_ref[...].astype(jnp.float32) + (1.0 - b1) * g32
    v32 = b2 * v_ref[...].astype(jnp.float32) + (1.0 - b2) * jnp.square(g32)
    c1 = coef_ref[0]
    c2 = coef_ref[1]
    u_ref[...] = ((m32 / c1) / (jnp.sqrt(v32 / c2) + eps)).astype(u_ref.dtype)
    mo_ref[...] = m32.astype(mo_ref.dtype)
    vo_ref[...] = v32.astype(vo_ref.dtype)


def _fused_local(g, m, v, coef, *, b1, b2, eps, state_dtype, interpret):
    """The per-shard kernel launch: flatten, pad to the block size, run the
    1-D grid, slice back.  Zero padding is harmless through the chain
    (0 -> u = 0 / (0 + eps) = 0) and sliced off anyway."""
    shape = g.shape
    n = g.size
    nb = max(1, -(-n // _BLOCK))
    pad = nb * _BLOCK - n

    def flat(x):
        x = x.reshape(-1)
        if pad:
            x = jnp.pad(x, (0, pad))
        return x.reshape(nb * _SUB, _LANES)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((_SUB, _LANES), lambda i, c: (i, 0)),
            pl.BlockSpec((_SUB, _LANES), lambda i, c: (i, 0)),
            pl.BlockSpec((_SUB, _LANES), lambda i, c: (i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((_SUB, _LANES), lambda i, c: (i, 0)),
            pl.BlockSpec((_SUB, _LANES), lambda i, c: (i, 0)),
            pl.BlockSpec((_SUB, _LANES), lambda i, c: (i, 0)),
        ),
    )
    u, mo, vo = pl.pallas_call(
        functools.partial(_adamw_kernel, b1=b1, b2=b2, eps=eps),
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((nb * _SUB, _LANES), g.dtype),
            jax.ShapeDtypeStruct((nb * _SUB, _LANES), state_dtype),
            jax.ShapeDtypeStruct((nb * _SUB, _LANES), state_dtype),
        ),
        interpret=interpret,
    )(coef.astype(jnp.float32), flat(g), flat(m), flat(v))

    def unflat(x):
        return x.reshape(-1)[:n].reshape(shape)

    return unflat(u), unflat(mo), unflat(vo)


@functools.lru_cache(maxsize=64)
def _partitioned_fused(ndim, b1, b2, eps, state_dtype_name, interpret):
    """One custom_partitioning rule per (rank, hyperparams): elementwise,
    so every output follows the STATE leaf's sharding (m — the ZeRO
    weight-update shard) and g/v are co-sharded to it."""
    from jax.experimental.custom_partitioning import custom_partitioning
    from jax.sharding import NamedSharding, PartitionSpec as P

    state_dtype = jnp.dtype(state_dtype_name)

    @custom_partitioning
    def fused(g, m, v, coef):
        return _fused_local(
            g, m, v, coef, b1=b1, b2=b2, eps=eps, state_dtype=state_dtype,
            interpret=interpret,
        )

    def _state_sharding(mesh, arg_shapes):
        spec = getattr(arg_shapes[1].sharding, "spec", None) or P()
        return NamedSharding(mesh, P(*spec))

    def infer(mesh, arg_shapes, result_shape):
        sh = _state_sharding(mesh, arg_shapes)
        return (sh, sh, sh)

    def partition(mesh, arg_shapes, result_shape):
        sh = _state_sharding(mesh, arg_shapes)
        rep = NamedSharding(mesh, P())

        def lower(g, m, v, coef):
            return _fused_local(
                g, m, v, coef, b1=b1, b2=b2, eps=eps, state_dtype=state_dtype,
                interpret=interpret,
            )

        return mesh, lower, (sh, sh, sh), (sh, sh, sh, rep)

    dims = " ".join(f"a{i}" for i in range(ndim)) or "..."
    leaf = dims
    fused.def_partition(
        partition=partition,
        infer_sharding_from_operands=infer,
        sharding_rule=f"{leaf}, {leaf}, {leaf}, c -> {leaf}, {leaf}, {leaf}",
    )
    return fused


def fused_adamw_update(g, m, v, c1, c2, *, b1, b2, eps, state_dtype, interpret):
    """(updates, m_new, v_new) for one leaf — bit-identical to the XLA
    chain in ``scale_by_adam_lowmem`` (same elementwise ops, same order).
    ``c1``/``c2`` are the caller-computed bias corrections (traced f32
    scalars)."""
    coef = jnp.stack([jnp.asarray(c1, jnp.float32), jnp.asarray(c2, jnp.float32)])
    fn = _partitioned_fused(
        g.ndim, float(b1), float(b2), float(eps), jnp.dtype(state_dtype).name,
        bool(interpret),
    )
    return fn(g, m, v, coef)


def update_ulps_vs_float64(u, g, m, v, c1, c2, *, b1, b2, eps) -> float:
    """The parity measure of the update ``u`` (tests/test_kernels.py,
    chip_smoke.py): its worst distance, in fp32 ulps of each element, from
    a float64 evaluation of ``(m' / c1) / (sqrt(v' / c2) + eps)``.

    ``m' = b1*m + (1-b1)*g`` is two products into one add, so fp32 has
    three roundings of it: both products rounded, or either one fused into
    the add.  Where the products cancel these differ by tens of ulps of
    the result, and a compiler picks per fusion (XLA:CPU copies the moment
    into the fusion of every output and contracts each copy on its own).
    So the reference is the interval the formula spans over those
    roundings of ``m'`` and ``v'``, and the measure is how far ``u`` lies
    outside it (0 inside).  ``g``/``m``/``v`` are the step's inputs and
    ``c1``/``c2`` the fp32 bias corrections the step used."""
    g, m, v = (np.asarray(x, np.float32).astype(np.float64) for x in (g, m, v))
    c1, c2 = (float(np.asarray(c, np.float32)) for c in (c1, c2))

    def r32(x):
        return x.astype(np.float32).astype(np.float64)

    def roundings(a, x, b, y):
        # products of two fp32 values are exact in float64
        p, q = float(np.float32(a)) * x, float(np.float32(b)) * y
        return r32(r32(p) + r32(q)), r32(p + r32(q)), r32(r32(p) + q)

    us = np.stack([
        (mm / c1) / (np.sqrt(vv / c2) + eps)
        for mm in roundings(b1, m, 1.0 - b1, g)
        for vv in roundings(b2, v, 1.0 - b2, r32(g * g))
    ])
    u = np.asarray(u, np.float32)
    if not np.array_equal(np.isfinite(u), np.isfinite(us[0])):
        return float("inf")
    fin = np.isfinite(u)
    if not fin.any():
        return 0.0
    u64 = u[fin].astype(np.float64)
    outside = np.maximum(np.maximum(us.min(0)[fin] - u64, u64 - us.max(0)[fin]), 0.0)
    return float(np.max(outside / np.spacing(np.abs(u[fin]))))
