"""Mesh collectives + cost model.

Reference: legacy/vescale/dtensor/_collective_utils.py:50-357 (mesh_scatter /
all_to_all / broadcast / reduce_scatter / all_gather / all_reduce over NCCL
process groups) and the bandwidth-factor cost model (:406-475) used by
sharding-strategy selection.

TPU-native: each collective is an XLA op over a named mesh axis, executed via
``shard_map`` so it works both eagerly and under jit, riding ICI.  There are
no process groups and no async handles — overlap comes from XLA's
latency-hiding scheduler (SURVEY §5 "Distributed communication backend").

Functions take and return *global* jax.Arrays whose leading mesh-axis layout
matches the reference's per-rank calling convention: the input's dim
``stack_dim`` (default 0) of size ``mesh.size(dim)`` carries "each rank's
operand" and collectives combine along it.
"""

from __future__ import annotations

import functools
import itertools
from typing import Optional, Union

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from .mesh import DeviceMesh

__all__ = [
    "mesh_all_reduce",
    "mesh_all_gather",
    "mesh_reduce_scatter",
    "mesh_all_to_all",
    "mesh_broadcast",
    "mesh_scatter",
    "mesh_ppermute",
    "all_reduce_q",
    "reduce_scatter_q",
    "next_sr_key",
    "q_psum",
    "q_all_gather",
    "q_psum_scatter",
    "q_all_to_all",
    "allgather_cost",
    "analytic_cost_us",
    "allreduce_cost",
    "reduce_scatter_cost",
    "all_to_all_cost",
    "redistribute_cost",
]

_REDUCE = {
    "sum": jax.lax.psum,
    "avg": lambda x, axis_name: jax.lax.pmean(x, axis_name),
    "max": jax.lax.pmax,
    "min": jax.lax.pmin,
}


def _axis(mesh: DeviceMesh, mesh_dim) -> str:
    return mesh.dim_name(mesh_dim)


@functools.lru_cache(maxsize=None)
def _program(jax_mesh, body, in_specs, out_specs, static):
    return jax.jit(shard_map(functools.partial(body, **dict(static)), mesh=jax_mesh,
                             in_specs=in_specs, out_specs=out_specs, check_vma=False))


def _smap(mesh: DeviceMesh, body, in_specs, out_specs, **static):
    """The ONE compiled program of an eager collective: ``body`` (a function
    of this module, never a closure) with its ``static`` parameters bound,
    under ``shard_map`` and ``jit``, kept by what defines it (the jax mesh,
    the body, the specs, the static parameters; shape and dtype are ``jit``'s
    own key).  A bare ``shard_map`` called eagerly runs its body a primitive
    at a time, each a program of its own, on EVERY call; this traces and
    compiles once a definition.  What changes from call to call (the operand,
    a stochastic-rounding key) is an argument of the program.  Under an outer
    trace the inner ``jit`` is inlined."""
    return _program(mesh.jax_mesh, body, in_specs, out_specs, tuple(sorted(static.items())))


def _all_reduce_body(x, *, ax, reduce_op, stacked):
    return _REDUCE[reduce_op](jnp.squeeze(x, 0) if stacked else x, ax)


def mesh_all_reduce(tensor, mesh: DeviceMesh, reduce_op: str = "sum", mesh_dim=0, stacked: bool = True):
    """If ``stacked``: input dim0 (= mesh dim size) holds per-rank operands,
    output is the reduced value (dim0 removed).  Mirrors
    _collective_utils.py:344."""
    ax = _axis(mesh, mesh_dim)
    f = _smap(mesh, _all_reduce_body, P(ax) if stacked else P(), P(), ax=ax, reduce_op=reduce_op, stacked=stacked)
    return f(tensor)


def _all_gather_body(x, *, ax, gather_dim, stacked):  # stacked x: (1, *local)
    return jax.lax.all_gather(jnp.squeeze(x, 0) if stacked else x, ax, axis=gather_dim, tiled=True)


def mesh_all_gather(tensor, mesh: DeviceMesh, mesh_dim=0, gather_dim: int = 0, stacked: bool = True):
    """All-gather per-rank operands along ``gather_dim``
    (_collective_utils.py:315).  With ``stacked`` the input dim0 carries the
    per-rank shards."""
    ax = _axis(mesh, mesh_dim)
    f = _smap(mesh, _all_gather_body, P(ax) if stacked else P(), P(), ax=ax, gather_dim=gather_dim, stacked=stacked)
    return f(tensor)


def _reduce_scatter_body(x, *, ax, n, reduce_op, scatter_dim):  # (1, *full)
    x = jnp.squeeze(x, 0)
    if reduce_op == "avg":
        out = jax.lax.psum_scatter(x, ax, scatter_dimension=scatter_dim, tiled=True) / n
    elif reduce_op == "sum":
        out = jax.lax.psum_scatter(x, ax, scatter_dimension=scatter_dim, tiled=True)
    else:
        full = _REDUCE[reduce_op](x, ax)
        idx = jax.lax.axis_index(ax)
        chunk = full.shape[scatter_dim] // n
        out = jax.lax.dynamic_slice_in_dim(full, idx * chunk, chunk, axis=scatter_dim)
    return out[None]


def mesh_reduce_scatter(tensor, mesh: DeviceMesh, reduce_op: str = "sum", scatter_dim: int = 0, mesh_dim=0):
    """Each rank contributes a full tensor (stacked on dim0); output stacks
    each rank's reduced scatter chunk on dim0 (_collective_utils.py:288)."""
    ax = _axis(mesh, mesh_dim)
    f = _smap(mesh, _reduce_scatter_body, P(ax), P(ax), ax=ax, n=mesh.size(mesh_dim), reduce_op=reduce_op,
              scatter_dim=scatter_dim)
    return f(tensor)


def _all_to_all_body(x, *, ax, split_dim, concat_dim):
    out = jax.lax.all_to_all(jnp.squeeze(x, 0), ax, split_axis=split_dim, concat_axis=concat_dim, tiled=True)
    return out[None]


def mesh_all_to_all(tensor, mesh: DeviceMesh, mesh_dim=0, split_dim: int = 0, concat_dim: int = 0):
    """Stacked all-to-all (_collective_utils.py:119): input dim0 = per-rank
    operands; each rank splits its operand along ``split_dim`` and exchanges
    chunk j with rank j, concatenating received chunks along ``concat_dim``.
    Dims are in the *operand* (post-squeeze) coordinate system."""
    ax = _axis(mesh, mesh_dim)
    return _smap(mesh, _all_to_all_body, P(ax), P(ax), ax=ax, split_dim=split_dim, concat_dim=concat_dim)(tensor)


def _broadcast_body(x, *, ax, src_rank):
    x = jnp.squeeze(x, 0)
    masked = jnp.where(jax.lax.axis_index(ax) == src_rank, x, jnp.zeros_like(x))
    return jax.lax.psum(masked, ax)


def mesh_broadcast(tensor, mesh: DeviceMesh, mesh_dim=0, src_rank: int = 0):
    """Broadcast rank ``src_rank``'s operand (from the stacked dim0) to all
    (_collective_utils.py:237): output has no stack dim."""
    ax = _axis(mesh, mesh_dim)
    return _smap(mesh, _broadcast_body, P(ax), P(), ax=ax, src_rank=src_rank)(tensor)


def mesh_scatter(tensor, mesh: DeviceMesh, mesh_dim=0, scatter_dim: int = 0, src_rank: int = 0):
    """Scatter chunks of the full tensor along ``scatter_dim`` from
    ``src_rank`` (_collective_utils.py:50).  Output stacks each rank's chunk
    on dim0.  On TPU this is a resharding (slice) — data is already global."""
    n = mesh.size(mesh_dim)
    chunks = jnp.stack(jnp.array_split(tensor, n, axis=scatter_dim), axis=0)
    ax = _axis(mesh, mesh_dim)
    return jax.device_put(chunks, NamedSharding(mesh.jax_mesh, P(ax)))


def _ppermute_body(x, *, ax, perm):
    return jax.lax.ppermute(jnp.squeeze(x, 0), ax, perm)[None]


def mesh_ppermute(tensor, mesh: DeviceMesh, mesh_dim=0, shift: int = 1):
    """Ring permute along a mesh dim (the PP p2p primitive; reference uses
    dist.send/recv — pipe/p2p_communication.py)."""
    ax = _axis(mesh, mesh_dim)
    n = mesh.size(mesh_dim)
    perm = tuple((i, (i + shift) % n) for i in range(n))
    return _smap(mesh, _ppermute_body, P(ax), P(ax), ax=ax, perm=perm)(tensor)


# ------------------------------------------------- quantized collectives
# Block-scaled int8 gradient collectives (ROADMAP item 2; EQuARX,
# arXiv:2506.17615): quantize each rank's contribution ONCE (per-block fp32
# scales, quant/blockscale.py), move a single packed int8 buffer on the
# wire, and accumulate the dequantized contributions in a wide master dtype
# in FIXED rank order — so the reduction can never overflow int8 and the
# result is deterministic + bitwise replayable by the emulator's quantized
# mode (emulator/quantized.py).  The ``q_*`` helpers run INSIDE a shard_map
# body (an axis name in scope); ``all_reduce_q``/``reduce_scatter_q`` are
# the eager stacked-convention wrappers mirroring ``mesh_all_reduce`` /
# ``mesh_reduce_scatter``.
#
# Wire-dtype convention (debug/comm_mode.py keys on it): REDUCTION payloads
# travel as signed int8 (HLO ``s8``) and pure data-MOVEMENT payloads as
# unsigned int8 (``u8``), so compiled-HLO comm accounting can attribute an
# s8 all-gather to a logical quantized all-reduce and a u8 collective to
# its own logical op.

def _rank_key(key, axis_name, rounding: str):
    """Per-rank stochastic-rounding key: fold the mesh position into the
    seed so ranks draw independent (but replayable) noise."""
    if rounding != "stochastic":
        return None
    return jax.random.fold_in(key, jax.lax.axis_index(axis_name))


_SR_CALLS = itertools.count()


def next_sr_key():
    """A fresh stochastic-rounding key for ONE eager quantized reduction:
    ``fold_in(key(VESCALE_GRAD_COMPRESS_SEED), call_index)``.  Successive
    calls (steps, tree leaves) draw independent noise — reusing one key
    across steps would correlate rounding errors into systematic drift,
    the bias SR exists to remove — while the sequence stays a pure
    function of (seed, call order), so a run is replayable end to end.
    Jit-embedded callers can't use a host counter: they thread a key (or
    ``step``) explicitly — see ``dp_grad_reduce``."""
    from .analysis import envreg

    seed = envreg.get_int("VESCALE_GRAD_COMPRESS_SEED") or 0
    return jax.random.fold_in(jax.random.key(seed), next(_SR_CALLS))


def _compress_settings(block, rounding):
    """Resolve the static compression knobs: explicit args win, else the
    registered VESCALE_GRAD_COMPRESS_* env defaults.  The ONE place the
    block-size and rounding-mode precedence lives (the eager wrappers and
    the DDP/ZeRO reduction path both call it)."""
    from .analysis import envreg
    from .quant import blockscale

    if block is None:
        block = envreg.get_int("VESCALE_GRAD_COMPRESS_BLOCK") or blockscale.DEFAULT_BLOCK
    if rounding is None:
        rounding = (
            "stochastic" if envreg.get_bool("VESCALE_GRAD_COMPRESS_SR") else "nearest"
        )
    return int(block), rounding


def _compress_defaults(block, rounding, key):
    """``_compress_settings`` plus the key draw: an SR call without an
    explicit key gets a FRESH counter-derived one (``next_sr_key``) — note
    this is resolved at TRACE time under jit, where the caller should
    thread a per-step key instead."""
    block, rounding = _compress_settings(block, rounding)
    if rounding == "stochastic" and key is None:
        key = next_sr_key()
    return block, rounding, key


def q_psum(x, axis_name, n: int, *, block, rounding="nearest", key=None,
           acc_dtype=jnp.float32, reduce_op: str = "sum"):
    """Quantized all-reduce over ``axis_name`` (shard_map body helper):
    quantize → all-gather one packed s8 buffer → dequantize-accumulate all
    ``n`` contributions in ``acc_dtype`` in rank order."""
    from .quant import blockscale

    if reduce_op not in ("sum", "avg"):
        raise ValueError(f"quantized reduction supports sum/avg, got {reduce_op!r}")
    qb = blockscale.quantize_int8_blocks(x, block, rounding, _rank_key(key, axis_name, rounding))
    payload = blockscale.pack_int8_payload(qb)
    allp = jax.lax.all_gather(payload, axis_name, axis=0, tiled=False)  # (n, P)
    nb = qb.q.shape[0]
    acc = None
    for r in range(n):  # fixed rank order: deterministic, emulator-replayable
        qr = blockscale.unpack_int8_payload(allp[r], nb, block)
        # the dequantize multiply is EXACT (power-of-two scales,
        # blockscale.py), so backend FMA contraction of this mul into the
        # accumulate add cannot change a bit — the emulator's
        # mul-then-add replay stays bit-for-bit without fighting fusion
        d = qr.q.astype(acc_dtype) * qr.scales.astype(acc_dtype)[:, None]
        acc = d if acc is None else acc + d
    if reduce_op == "avg":
        acc = acc / n
    return acc.reshape(-1)[: x.size].reshape(x.shape).astype(x.dtype)


def _as_move_payload(payload):
    # movement convention: u8 on the wire (see module comment)
    return jax.lax.bitcast_convert_type(payload, jnp.uint8)


def _from_move_payload(payload_u8):
    return jax.lax.bitcast_convert_type(payload_u8, jnp.int8)


def q_all_gather(x, axis_name, n: int, *, axis: int, extent: int, block,
                 rounding="nearest", key=None, acc_dtype=jnp.float32):
    """Quantized all-gather along tensor ``axis`` (shard_map body helper):
    each rank's chunk moves as a packed u8 buffer; chunks are dequantized
    and concatenated in rank order, trimmed to the logical ``extent``.
    Lossy — every rank's data (including the caller's own chunk) round
    trips through int8, so the result is REPLICATED consistently."""
    from .quant import blockscale

    qb = blockscale.quantize_int8_blocks(x, block, rounding, _rank_key(key, axis_name, rounding))
    payload = _as_move_payload(blockscale.pack_int8_payload(qb))
    allp = jax.lax.all_gather(payload, axis_name, axis=0, tiled=False)
    nb = qb.q.shape[0]
    parts = []
    for r in range(n):
        qr = blockscale.unpack_int8_payload(_from_move_payload(allp[r]), nb, block)
        parts.append(blockscale.dequantize_int8_blocks(qr, x.shape, x.dtype, acc_dtype))
    out = jnp.concatenate(parts, axis=axis)
    if out.shape[axis] != extent:
        out = jax.lax.slice_in_dim(out, 0, extent, axis=axis)
    return out


def q_psum_scatter(x, axis_name, n: int, *, scatter_dim: int, block,
                   rounding="nearest", key=None, acc_dtype=jnp.float32,
                   reduce_op: str = "sum"):
    """Quantized reduce-scatter (shard_map body helper): the operand is
    split into ``n`` chunks along ``scatter_dim`` (must divide evenly —
    callers pad first), each chunk quantized separately so its blocks and
    scales travel together through one packed s8 all-to-all; each rank
    dequantize-accumulates its received chunks in rank order."""
    from .quant import blockscale

    if reduce_op not in ("sum", "avg"):
        raise ValueError(f"quantized reduction supports sum/avg, got {reduce_op!r}")
    if x.shape[scatter_dim] % n:
        raise ValueError(
            f"q_psum_scatter: dim {scatter_dim} extent {x.shape[scatter_dim]} "
            f"not divisible by {n} (pad first)"
        )
    chunks = jnp.split(x, n, axis=scatter_dim)
    key0 = _rank_key(key, axis_name, rounding)
    payloads = []
    nb = None
    for c, chunk in enumerate(chunks):
        kc = None if key0 is None else jax.random.fold_in(key0, c)
        qb = blockscale.quantize_int8_blocks(chunk, block, rounding, kc)
        nb = qb.q.shape[0]
        payloads.append(blockscale.pack_int8_payload(qb))
    stackp = jnp.stack(payloads)  # (n, P) s8
    recv = jax.lax.all_to_all(stackp, axis_name, split_axis=0, concat_axis=0, tiled=True)
    acc = None
    for r in range(n):
        qr = blockscale.unpack_int8_payload(recv[r], nb, block)
        # exact dequantize multiply: FMA-contraction-proof (see q_psum)
        d = qr.q.astype(acc_dtype) * qr.scales.astype(acc_dtype)[:, None]
        acc = d if acc is None else acc + d
    if reduce_op == "avg":
        acc = acc / n
    cshape = chunks[0].shape
    csize = 1
    for s in cshape:
        csize *= int(s)
    return acc.reshape(-1)[:csize].reshape(cshape).astype(x.dtype)


def q_all_to_all(x, axis_name, n: int, *, split_axis: int, concat_axis: int,
                 block, rounding="nearest", key=None, acc_dtype=jnp.float32):
    """Quantized all-to-all (shard_map body helper): split along
    ``split_axis`` (must divide evenly), move packed u8 chunk payloads,
    reassemble the received chunks along ``concat_axis`` in rank order.
    Pure movement — lossy only through one quantize round trip."""
    from .quant import blockscale

    if x.shape[split_axis] % n:
        raise ValueError(
            f"q_all_to_all: dim {split_axis} extent {x.shape[split_axis]} "
            f"not divisible by {n} (pad first)"
        )
    chunks = jnp.split(x, n, axis=split_axis)
    key0 = _rank_key(key, axis_name, rounding)
    payloads = []
    nb = None
    for c, chunk in enumerate(chunks):
        kc = None if key0 is None else jax.random.fold_in(key0, c)
        qb = blockscale.quantize_int8_blocks(chunk, block, rounding, kc)
        nb = qb.q.shape[0]
        payloads.append(_as_move_payload(blockscale.pack_int8_payload(qb)))
    stackp = jnp.stack(payloads)  # (n, P) u8
    recv = jax.lax.all_to_all(stackp, axis_name, split_axis=0, concat_axis=0, tiled=True)
    parts = []
    for r in range(n):
        qr = blockscale.unpack_int8_payload(_from_move_payload(recv[r]), nb, block)
        parts.append(
            blockscale.dequantize_int8_blocks(qr, chunks[0].shape, x.dtype, acc_dtype)
        )
    return jnp.concatenate(parts, axis=concat_axis)


_WARNED_COUNTERPRODUCTIVE = set()


def _compress_wire_bytes(n_elements: int, itemsize: int, block: int, op: str, n: int):
    """WIRE-accurate per-device byte accounting for one quantized
    collective vs its uncompressed form: the quantized all-reduce is
    gather-based (moves (n-1) packed contributions vs the ring's
    2(n-1)/n raw), so at large mesh dims it moves MORE — the telemetry
    must say so rather than report payload-packing 'savings'."""
    from .quant import blockscale

    raw = n_elements * itemsize
    packed = blockscale.packed_nbytes(n_elements, block)
    f = (n - 1) / max(1, n)
    if op == "all_reduce":
        return 2.0 * f * raw, float((n - 1) * packed)
    # reduce_scatter: all-to-all of packed chunks vs psum_scatter's ring
    return f * raw, f * packed


def _compress_telemetry(n_elements: int, itemsize: int, block: int, op: str, n: int):
    """Byte-savings accounting per quantized collective call (eager
    wrappers + DDP wiring), using the wire formulas above.  A
    counterproductive configuration (quantized bytes >= raw bytes on the
    wire — e.g. int8 all-reduce on a large dp dim) warns once per
    (op, n) instead of crediting phantom savings."""
    if n <= 1:
        # size-1 mesh dim: no bytes move either way — count the call but
        # record no savings/ratio and never warn about a no-op
        from . import telemetry as _tel

        if _tel.is_active():
            _tel.count("grad_compress_collectives_total")
            _tel.count(f"grad_compress_{op}_total")
        return
    raw_wire, q_wire = _compress_wire_bytes(n_elements, itemsize, block, op, n)
    if q_wire >= raw_wire and (op, n) not in _WARNED_COUNTERPRODUCTIVE:
        _WARNED_COUNTERPRODUCTIVE.add((op, n))
        import warnings

        # a config-review notice latched per (op, n) — the fix is editing
        # VESCALE_GRAD_COMPRESS, not paging anyone; stays a warning
        warnings.warn(  # vescale-lint: disable=VSC207
            f"grad_compress='int8' {op} over a mesh dim of {n} moves "
            f"~{int(q_wire)} bytes on the wire vs ~{int(raw_wire)} uncompressed "
            "(the gather-based quantized all-reduce is O(n) in wire bytes) — "
            "compression is counterproductive here; prefer the ZeRO "
            "reduce-scatter path or disable VESCALE_GRAD_COMPRESS",
            stacklevel=3,
        )
    from . import telemetry as _tel

    if not _tel.is_active():
        return
    _tel.count("grad_compress_collectives_total")
    _tel.count("grad_compress_bytes_saved_total", max(0.0, raw_wire - q_wire))
    _tel.set_gauge("grad_compress_ratio", raw_wire / q_wire if q_wire else 0.0)
    _tel.count(f"grad_compress_{op}_total")


def _all_reduce_q_body(x, key, *, ax, n, stacked, **kw):
    return q_psum(jnp.squeeze(x, 0) if stacked else x, ax, n, key=key, **kw)


def all_reduce_q(tensor, mesh: DeviceMesh, reduce_op: str = "sum", mesh_dim=0,
                 stacked: bool = True, *, block=None, rounding=None, key=None,
                 acc_dtype=jnp.float32):
    """Block-scaled int8 all-reduce — the quantized ``mesh_all_reduce``.
    Same stacked calling convention; knobs default from the registered
    ``VESCALE_GRAD_COMPRESS_*`` env vars, read on the host each call.  The
    stochastic-rounding key is an ARGUMENT of the compiled program."""
    block, rounding, key = _compress_defaults(block, rounding, key)
    ax = _axis(mesh, mesh_dim)
    n = mesh.size(mesh_dim)
    f = _smap(mesh, _all_reduce_q_body, (P(ax) if stacked else P(), P()), P(), ax=ax, n=n, stacked=stacked,
              block=block, rounding=rounding, acc_dtype=acc_dtype, reduce_op=reduce_op)
    out = f(tensor, key)
    elems = int(np.prod(tensor.shape[1:] if stacked else tensor.shape))
    _compress_telemetry(elems, jnp.dtype(tensor.dtype).itemsize, block, "all_reduce", n)
    return out


def _reduce_scatter_q_body(x, key, *, ax, n, **kw):  # (1, *full)
    return q_psum_scatter(jnp.squeeze(x, 0), ax, n, key=key, **kw)[None]


def reduce_scatter_q(tensor, mesh: DeviceMesh, reduce_op: str = "sum",
                     scatter_dim: int = 0, mesh_dim=0, *, block=None,
                     rounding=None, key=None, acc_dtype=jnp.float32):
    """Block-scaled int8 reduce-scatter — the quantized
    ``mesh_reduce_scatter`` (same stacked convention: input dim0 carries
    per-rank full operands, output dim0 the per-rank reduced chunks)."""
    block, rounding, key = _compress_defaults(block, rounding, key)
    ax = _axis(mesh, mesh_dim)
    n = mesh.size(mesh_dim)
    f = _smap(mesh, _reduce_scatter_q_body, (P(ax), P()), P(ax), ax=ax, n=n, scatter_dim=scatter_dim,
              block=block, rounding=rounding, acc_dtype=acc_dtype, reduce_op=reduce_op)
    out = f(tensor, key)
    elems = int(np.prod(tensor.shape[1:]))
    _compress_telemetry(elems, jnp.dtype(tensor.dtype).itemsize, block, "reduce_scatter", n)
    return out


# ------------------------------------------------------------- cost model
# Bandwidth-factor model mirroring _collective_utils.py:406-475: cost in
# microseconds for `bytes_gb` gigabytes over a mesh dim of size n.  The
# factors are tuned for TPU ICI (~100 GB/s per link v5p) instead of NCCL.
#
# Calibrated mode (telemetry/calibrate.py): when VESCALE_COST_CALIBRATION
# arms a measured table, each cost function answers from the table's
# (op, mesh-dim size, byte bucket) wall-times — interpolated between
# buckets — and falls back to the analytic formula below (with a one-time
# warning per missing op/axis pair) otherwise.  Without a table, or with an
# EMPTY one, the numbers are bit-identical to the analytic model.
_ICI_GBPS = 100.0
_LAUNCH_US = 1.0  # per-op overhead (vs reference's kernel-launch constant)


def _ring_cost(bytes_gb: float, n: int, steps_factor: float) -> float:
    if n <= 1:
        return 0.0
    return _LAUNCH_US + (bytes_gb * steps_factor * (n - 1) / n) / _ICI_GBPS * 1e6


def _measured_us(op: str, num_devices: int, bytes_gb: float):
    from .telemetry import calibrate as _cal

    return _cal.collective_cost_us(op, num_devices, bytes_gb * 1e9)


def analytic_cost_us(op: str, bytes_gb: float, num_devices: int) -> float:
    """The pure bandwidth-factor cost (never consults the calibration
    table) — the planner's in-calibrated-mode fallback for ops whose
    bucket is missing, so one Dijkstra never mixes denominations."""
    factors = {"all_gather": 1.0, "reduce_scatter": 1.0, "all_to_all": 1.0,
               "all_reduce": 2.0, "ppermute": 1.0}
    return _ring_cost(bytes_gb, num_devices, factors[op])


def allgather_cost(bytes_gb: float, num_devices: int) -> float:
    us = _measured_us("all_gather", num_devices, bytes_gb)
    return us if us is not None else _ring_cost(bytes_gb, num_devices, 1.0)


def reduce_scatter_cost(bytes_gb: float, num_devices: int) -> float:
    us = _measured_us("reduce_scatter", num_devices, bytes_gb)
    return us if us is not None else _ring_cost(bytes_gb, num_devices, 1.0)


def allreduce_cost(bytes_gb: float, num_devices: int) -> float:
    us = _measured_us("all_reduce", num_devices, bytes_gb)
    return us if us is not None else _ring_cost(bytes_gb, num_devices, 2.0)


def all_to_all_cost(bytes_gb: float, num_devices: int) -> float:
    us = _measured_us("all_to_all", num_devices, bytes_gb)
    return us if us is not None else _ring_cost(bytes_gb, num_devices, 1.0)


def redistribute_cost(src_spec, dst_spec) -> float:
    """Estimated cost of ``redistribute(src -> dst)`` (reference
    redistribute_cost, _collective_utils.py:453) — used by auto-plan."""
    import math

    if src_spec.mesh != dst_spec.mesh:
        return float("inf")
    nbytes = float(np.prod(src_spec.shape)) * jnp.dtype(src_spec.dtype).itemsize
    gb = nbytes / 1e9
    cost = 0.0
    for i, (s, d) in enumerate(zip(src_spec.placements, dst_spec.placements)):
        n = src_spec.mesh.shape[i]
        if s == d:
            continue
        if s.is_partial() and d.is_replicate():
            cost += allreduce_cost(gb, n)
        elif s.is_partial() and d.is_shard():
            cost += reduce_scatter_cost(gb, n)
        elif (s.is_shard() or s.is_ragged_shard()) and d.is_replicate():
            cost += allgather_cost(gb / n, n)
        elif s.is_shard() and d.is_shard():
            cost += all_to_all_cost(gb / n, n)
        elif s.is_replicate() and (d.is_shard() or d.is_ragged_shard()):
            cost += 0.0  # local slice
        else:
            cost += allreduce_cost(gb, n)
    return cost
