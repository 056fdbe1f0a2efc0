"""Flash attention — dispatch front-end over the Pallas kernels.

The hot op of every model family (SURVEY §6 ladder).  This module owns the
DISPATCH (which implementation runs), the custom_vjp, and the GSPMD
partition rule; the fused Pallas kernels themselves live in
``vescale_tpu.kernels.flash_attention`` behind the framework-wide kernel
contract (``VESCALE_KERNELS``, docs/kernels.md).

Two implementations, one op:

  * **pallas** — on TPU (or under ``VESCALE_KERNELS=interpret`` /
    ``interpret=True`` anywhere): forward streams K/V blocks through the
    MXU with online-softmax accumulation in fp32 and saves the per-row
    logsumexp; backward runs the standard flash decomposition as two
    kernels recomputing probabilities from the saved LSE — the T x T
    score matrix never touches HBM, activation memory is O(T * D).
  * **xla** — everywhere else: a plain jnp reference with numerically
    matching math.  It materializes the O(T^2) score matrix and has none
    of the kernel's MXU blocking or memory behavior — it is a fallback,
    not a slow kernel.  With ``VESCALE_KERNELS=off`` (the default) this is
    the bare ``_dense_ref``, byte-identical to the pre-kernel-layer
    framework; with a kernel mode enabled the fallback routes through the
    same custom_vjp + partition rule as the kernel (one rule per op, both
    implementations — the ``impl`` leg of ``_partitioned_fwd``/``_bwd``)
    and counts into ``kernel_fallback_flash_attention_total``.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..kernels.flash_attention import (  # noqa: F401  (re-exported for tests)
    BLOCK_MASK_NAME,
    CAUSAL_NAME,
    WINDOW_NAME,
    _NEG_INF,
    _flash_bwd_pallas,
    _flash_fwd_pallas,
    _flash_fwd_serve,
    _use_streaming,
)

__all__ = ["flash_attention", "flash_attention_forward", "flash_attention_sharded"]


# ---------------------------------------------------------------- reference
def _dense_ref(q, k, v, scale, causal):
    if k.shape[2] != q.shape[2]:  # GQA: repeat kv heads for the dense math
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        T = q.shape[1]
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v)


def _fit_block(block: int, T: int) -> int:
    """Largest halving of the requested block that divides T (not under 8), so
    e.g. T=768 stays on the flash path with 256-blocks instead of silently
    falling back to dense O(T^2)."""
    b = min(block, T)
    while b > 8 and T % b:
        b //= 2
    return b


# ------------------------------------------------------------- custom vjp
def _to3(x):
    B, T, H, D = x.shape
    return jnp.transpose(x, (0, 2, 1, 3)).reshape(B * H, T, D)


def _from3(x, B, H):
    BH, T, D = x.shape
    return jnp.transpose(x.reshape(B, H, T, D), (0, 2, 1, 3))


def _xla_fwd_4d(q, k, v, scale, causal, mask_block=1, window=None, sink=None):
    """Dense (o, lse) with the kernel's GQA layout and lse convention —
    the fallback leg of the shared partition rule (mode != off only; the
    off-mode fallback is the bare ``_dense_ref``).  ``mask_block`` > 1: causal
    over blocks of that many positions (:func:`_masked_forward`);
    ``window``: a row sees the ``window`` newest positions
    (:func:`_masked_forward`); ``v`` may be narrower than ``k``, and ``sink``
    (H,) is one more column of the softmax a head, of no value
    (:func:`_masked_forward`)."""
    B, T, H, D = q.shape
    G = k.shape[2]
    rep = H // G
    qg = q.astype(jnp.float32).reshape(B, T, G, rep, D)
    s = scale * jnp.einsum("bqgrd,bkgd->bgrqk", qg, k.astype(jnp.float32))
    if causal:
        mask = jnp.tril(jnp.ones((T, T), bool))
        if mask_block != 1:
            block = jnp.arange(T, dtype=jnp.int32) // mask_block
            mask = block[None, :] <= block[:, None]
        if window is not None:
            position = jnp.arange(T, dtype=jnp.int32)
            mask = mask & (position[:, None] - position[None, :] < window)
        s = jnp.where(mask[None, None, None], s, _NEG_INF)
    m = jnp.max(s, axis=-1)
    if sink is not None:
        column = sink.astype(jnp.float32).reshape(1, G, rep, 1)
        m = jnp.maximum(m, column)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    if sink is not None:
        l = l + jnp.exp(column - m)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o = jnp.einsum("bgrqk,bkgd->bqgrd", p, v.astype(jnp.float32))
    o = o / jnp.transpose(l_safe, (0, 3, 1, 2))[..., None]
    lse = (m + jnp.log(l_safe)).reshape(B, H, T)
    return o.reshape(B, T, H, v.shape[-1]).astype(q.dtype), lse


def _xla_bwd_4d(q, k, v, o, do, lse, scale, causal):
    """Dense flash-decomposition backward (probabilities recomputed from
    the saved LSE — the same math the dq/dkv kernels run, unblocked)."""
    B, T, H, D = q.shape
    G = k.shape[2]
    rep = H // G
    q32 = q.astype(jnp.float32)
    k32 = k.astype(jnp.float32)
    v32 = v.astype(jnp.float32)
    do32 = do.astype(jnp.float32)
    qg = q32.reshape(B, T, G, rep, D)
    dog = do32.reshape(B, T, G, rep, D)
    s = scale * jnp.einsum("bqgrd,bkgd->bgrqk", qg, k32)
    if causal:
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask[None, None, None], s, _NEG_INF)
    p = jnp.exp(s - lse.reshape(B, G, rep, T)[..., None])
    delta = jnp.sum(do32 * o.astype(jnp.float32), axis=-1)  # (B, T, H)
    delta_r = jnp.transpose(delta, (0, 2, 1)).reshape(B, G, rep, T)
    dp = jnp.einsum("bqgrd,bkgd->bgrqk", dog, v32)
    ds = p * (dp - delta_r[..., None]) * scale
    dq = jnp.einsum("bgrqk,bkgd->bqgrd", ds, k32).reshape(B, T, H, D).astype(q.dtype)
    dk = jnp.einsum("bgrqk,bqgrd->bkgd", ds, qg).astype(k.dtype)
    dv = jnp.einsum("bgrqk,bqgrd->bkgd", p, dog).astype(v.dtype)
    return dq, dk, dv


def _fwd_4d(q, k, v, scale, causal, block_q, block_k, interpret, impl):
    """(B,T,H,D) q + (B,T,G,D) k/v (G | H; GQA stays un-repeated) ->
    (o (B,T,H,D), lse (B,H,T)) via the selected implementation."""
    if impl == "xla":
        return _xla_fwd_4d(q, k, v, scale, causal)
    B, T, H, D = q.shape
    G = k.shape[2]
    o3, lse3 = _flash_fwd_pallas(
        _to3(q), _to3(k), _to3(v), scale, causal, block_q, block_k, interpret, H, G
    )
    return _from3(o3, B, H), lse3.reshape(B, H, T)


def _bwd_4d(q, k, v, o, do, lse, scale, causal, block_q, block_k, interpret, impl):
    if impl == "xla":
        return _xla_bwd_4d(q, k, v, o, do, lse, scale, causal)
    B, T, H, D = q.shape
    G = k.shape[2]
    dq3, dk3, dv3 = _flash_bwd_pallas(
        _to3(q), _to3(k), _to3(v), _to3(o), _to3(do), lse.reshape(B * H, T, 1),
        scale, causal, block_q, block_k, interpret, H, G,
    )
    return _from3(dq3, B, H), _from3(dk3, B, G), _from3(dv3, B, G)


# ---------------------------------------------------- GSPMD partitionability
# A pallas_call is an opaque custom call to XLA: GSPMD cannot derive a
# partitioning rule for it, so without help every sharded caller would gather
# q/k/v to replicated (VERDICT round-1 weak #4: "flash attention dies under
# GSPMD").  Attention is independent per (batch, head), so the kernel admits
# a trivial rule — shard b and h, replicate t and d, zero communication —
# registered here via jax.experimental.custom_partitioning so *plain
# jit+mesh model code* keeps the fused kernel (the shard_map wrapper below
# remains for explicit use).  The rule is defined ONCE per op and carries
# both implementations via the ``impl`` leg — the XLA fallback of an enabled
# kernel mode partitions exactly like the kernel.  Seq-sharded inputs are
# all-gathered by the need_replication factors; long-context seq sharding
# belongs to ring/ulysses (parallel/context.py) instead.


def _batch_head_axes(mesh, arg_shapes):
    """(batch_axes, head_axes) of the q operand's (suggested) sharding.

    The head axes are kept only if their total mesh extent divides the
    kv-head count G (k operand, dim 2): GQA/MQA route q heads to kv groups
    inside the kernel, which is only shard-local-consistent when the head
    partitioning splits kv groups evenly.  Otherwise heads are replicated
    (batch-only partitioning) — e.g. MQA (G=1) under tp."""
    from jax.sharding import PartitionSpec as P

    spec = getattr(arg_shapes[0].sharding, "spec", None) or P()
    spec = tuple(spec) + (None,) * (4 - len(tuple(spec)))
    b, h = spec[0], spec[2]
    if h is not None:
        G = arg_shapes[1].shape[2]
        h_extent = 1
        for name in h if isinstance(h, tuple) else (h,):
            h_extent *= mesh.shape[name]
        if G % h_extent:
            h = None
    return b, h


@functools.lru_cache(maxsize=64)
def _partitioned_fwd(scale, causal, block_q, block_k, interpret, impl):
    from jax.experimental.custom_partitioning import custom_partitioning
    from jax.sharding import NamedSharding, PartitionSpec as P

    @custom_partitioning
    def fwd(q, k, v):
        return _fwd_4d(q, k, v, scale, causal, block_q, block_k, interpret, impl)

    def infer(mesh, arg_shapes, shape):
        b, h = _batch_head_axes(mesh, arg_shapes)
        return (
            NamedSharding(mesh, P(b, None, h, None)),
            NamedSharding(mesh, P(b, h, None)),
        )

    def partition(mesh, arg_shapes, result_shape):
        b, h = _batch_head_axes(mesh, arg_shapes)
        qsh = NamedSharding(mesh, P(b, None, h, None))
        lsh = NamedSharding(mesh, P(b, h, None))

        def lower(q, k, v):
            return _fwd_4d(q, k, v, scale, causal, block_q, block_k, interpret, impl)

        # k/v share the head axis on their (smaller) group dim: GQA under tp
        # needs tp | KV, which every llama/mixtral plan in-tree satisfies
        return mesh, lower, (qsh, lsh), (qsh, qsh, qsh)

    fwd.def_partition(
        partition=partition,
        infer_sharding_from_operands=infer,
        sharding_rule="b t h d, b t g d, b t g d -> b t h d, b h t",
        need_replication_factors=("t", "d"),
    )
    return fwd


@functools.lru_cache(maxsize=64)
def _partitioned_bwd(scale, causal, block_q, block_k, interpret, impl):
    from jax.experimental.custom_partitioning import custom_partitioning
    from jax.sharding import NamedSharding, PartitionSpec as P

    @custom_partitioning
    def bwd(q, k, v, o, do, lse):
        return _bwd_4d(q, k, v, o, do, lse, scale, causal, block_q, block_k, interpret, impl)

    def infer(mesh, arg_shapes, shape):
        b, h = _batch_head_axes(mesh, arg_shapes)
        qsh = NamedSharding(mesh, P(b, None, h, None))
        return (qsh, qsh, qsh)

    def partition(mesh, arg_shapes, result_shape):
        b, h = _batch_head_axes(mesh, arg_shapes)
        qsh = NamedSharding(mesh, P(b, None, h, None))
        lsh = NamedSharding(mesh, P(b, h, None))

        def lower(q, k, v, o, do, lse):
            return _bwd_4d(q, k, v, o, do, lse, scale, causal, block_q, block_k, interpret, impl)

        return mesh, lower, (qsh, qsh, qsh), (qsh, qsh, qsh, qsh, qsh, lsh)

    bwd.def_partition(
        partition=partition,
        infer_sharding_from_operands=infer,
        sharding_rule=(
            "b t h d, b t g d, b t g d, b t h d, b t h d, b h t"
            " -> b t h d, b t g d, b t g d"
        ),
        need_replication_factors=("t", "d"),
    )
    return bwd


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, scale, causal, block_q, block_k, interpret, impl):
    out, _ = _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret, impl)
    return out


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret, impl):
    o, lse = _partitioned_fwd(scale, causal, block_q, block_k, interpret, impl)(q, k, v)
    return o, (q, k, v, o, lse)


def _flash_bwd(scale, causal, block_q, block_k, interpret, impl, res, g):
    q, k, v, o, lse = res
    return _partitioned_bwd(scale, causal, block_q, block_k, interpret, impl)(q, k, v, o, g, lse)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _masked_forward(q, k, v, scale, block_q, block_k, interpret, mask_block=1, window=None, sink=None):
    """The forward alone under one of the two masks a serve prefill brings:
    causal over BLOCKS of ``mask_block`` positions (position i sees j iff ``j //
    mask_block <= i // mask_block``: what a model that generates by diffusion
    over blocks prefills under), or a causal sliding ``window`` (i sees j iff
    ``0 <= i - j < window``: the window layers of a model that mixes window and
    full attention; the kernel's key loop starts at the window's first block),
    or under the plain causal mask with what the differentiated road does not
    take: values ``v`` narrower than the keys, a ``sink`` (H,), one logit a head
    in every row's softmax that mixes no value (with a window or without).
    No partition rule (one device, never differentiated); the GQA kernel on TPU
    or interpreted, the dense product elsewhere.  Under a window the tiles are
    no larger than the window rounded up to whole 128s: a query block then
    visits two key blocks whatever the rung (a window of 512: the tiles of 512
    it had)."""
    from .. import kernels as _kernels

    B, T, H, D = q.shape
    G = k.shape[2]
    if interpret is None:
        interpret = _kernels.mode() == "interpret"
    if window is not None:
        cap = -(-window // 128) * 128
        block_q, block_k = min(block_q, cap), min(block_k, cap)
    block_q, block_k = _fit_block(block_q, T), _fit_block(block_k, T)
    tiles = not (T % block_q or T % block_k or block_q % mask_block or block_k % mask_block)
    if (_kernels.on_tpu() or interpret) and tiles:
        masks = {"mask_block": mask_block} if window is None else {"window": window}
        if sink is not None:
            masks["sink"] = sink
        o3, _lse = _flash_fwd_pallas(_to3(q), _to3(k), _to3(v), scale, True, block_q, block_k, interpret, H, G, **masks)
        return _from3(o3, B, H)
    return _xla_fwd_4d(q, k, v, scale, True, mask_block, window, sink)[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _windowed(q, k, v, scale, window, block_q, block_k, interpret):
    return _masked_forward(q, k, v, scale, block_q, block_k, interpret, window=window)


def _windowed_refuses_grad(*_args):
    raise NotImplementedError("flash_attention(window=...) is forward only: the backward kernels know no window, so "
                              "a model that trains through window attention has no path here yet")


_windowed.defvjp(_windowed_refuses_grad, _windowed_refuses_grad)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _narrow_or_sunk(q, k, v, sink, scale, window, block_q, block_k, interpret):
    return _masked_forward(q, k, v, scale, block_q, block_k, interpret, window=window, sink=sink)


def _narrow_or_sunk_refuses_grad(*_args):
    raise NotImplementedError("flash_attention(sink=...) and values narrower than the keys (v.shape[-1] != q.shape[-1]) are "
                              "forward only: the backward kernels know neither, so a model that trains through them has no "
                              "path here yet")


_narrow_or_sunk.defvjp(_narrow_or_sunk_refuses_grad, _narrow_or_sunk_refuses_grad)


def flash_attention(
    q,
    k,
    v,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: Optional[bool] = None,
    mask_block: int = 1,
    window: Optional[int] = None,
    sink=None,
):
    """Fused attention over (B, T, H, D) q with (B, T, G, D) k/v, G | H —
    GQA/MQA run natively: the kernels route each q head to its kv group via
    BlockSpec index maps, so the repeated K/V heads are never materialized
    in HBM (vs the torch-reference pattern of repeat_kv before SDPA).
    Divisibility: T % block sizes == 0 (pad upstream).

    Dispatch: the Pallas kernel runs on TPU, under ``interpret=True``, or
    under ``VESCALE_KERNELS=interpret`` (which resolves an unset
    ``interpret`` to True — CPU tier-1 then exercises the kernel path);
    anywhere else the jnp dense reference runs.  ``VESCALE_KERNELS=off``
    reproduces the pre-kernel-layer dispatch byte-for-byte.

    ``mask_block`` > 1 (static; ``causal`` must hold) makes the mask causal
    over blocks of that many positions, full inside a block, FORWARD ONLY
    (:func:`_masked_forward`: a serve prefill's); at its default of 1
    nothing of the path below changes.

    ``window`` (static; ``causal`` must hold, no ``mask_block``) is a sliding
    window: position i sees j iff ``0 <= i - j < window``, FORWARD ONLY
    (:func:`_masked_forward`, the kernel named ``window_flash_fwd`` in a device
    trace; differentiating through it raises ``NotImplementedError``); at its
    default of None nothing of the path below changes.

    ``sink`` ((H,) float32; ``causal`` must hold, no ``mask_block``; with a
    ``window`` or without) adds one column to every row's softmax: query head
    ``h``'s logit ``sink[h]`` enters the maximum and the denominator and mixes
    no value (a row of a head whose sink is large gives most of its mass away).
    ``v`` may be (B, T, G, Dv) with ``Dv != D``: the output is then (B, T, H,
    Dv).  Both FORWARD ONLY, on the road ``window`` takes (differentiating
    raises ``NotImplementedError`` and names them); without a window the kernel
    is named ``causal_flash_fwd`` in a device trace.  At ``sink=None`` and ``Dv
    == D`` nothing of the paths above and below changes."""
    B, T, H, D = q.shape
    G = k.shape[2]
    if H % max(G, 1):
        raise ValueError(f"q heads {H} not a multiple of kv heads {G}")
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    if sink is not None or v.shape[-1] != D:
        if not causal or mask_block != 1 or (window is not None and window < 1):
            raise ValueError("a sink, and values narrower than the keys, go with the causal mask (under a window of 1 or more "
                             "positions, or none) and take no block mask")
        if sink is not None and sink.shape != (H,):
            raise ValueError(f"sink {sink.shape} is one logit a query head, ({H},)")
        return _narrow_or_sunk(q, k, v, sink, scale, None if window is None else int(window), block_q, block_k, interpret)
    if window is not None:
        if not causal or mask_block != 1 or window < 1:
            raise ValueError(f"window={window} is a causal window of 1 or more positions, without a block mask")
        return _windowed(q, k, v, scale, int(window), block_q, block_k, interpret)
    if mask_block != 1:
        if not causal or mask_block < 1:
            raise ValueError(f"mask_block={mask_block} is a causal mask over blocks of 1 or more positions")
        return _masked_forward(q, k, v, scale, block_q, block_k, interpret, mask_block=int(mask_block))
    from .. import kernels as _kernels

    kmode = _kernels.mode()
    on_tpu = jax.devices()[0].platform == "tpu"
    if interpret is None:
        # off-TPU default = dense fallback, NOT the interpreter — unless the
        # kernel contract asks for the interpreter explicitly
        interpret = kmode == "interpret"

    def _xla_fallback():
        if kmode == "off":
            return _dense_ref(q, k, v, scale, causal)
        # an enabled kernel mode takes the SHARED partition rule's xla leg
        # (same custom_vjp, same GSPMD behavior as the kernel) and counts
        _kernels.record_fallback("flash_attention")
        return _flash(q, k, v, scale, causal, 0, 0, False, "xla")

    if not on_tpu and not interpret:
        return _xla_fallback()

    block_q, block_k = _fit_block(block_q, T), _fit_block(block_k, T)
    if T % block_q or T % block_k:
        return _xla_fallback()
    if kmode != "off":
        _kernels.record_dispatch("flash_attention")
    return _flash(q, k, v, scale, causal, block_q, block_k, interpret, "pallas")


def flash_attention_forward(q, k, v, *, scale: Optional[float] = None, block_q: int = 512, block_k: int = 512,
                            interpret: Optional[bool] = None, name: Optional[str] = None):
    """The CAUSAL forward alone, head-major, for a program that never
    differentiates it (a serve prefill): ``q`` and ``k`` (H, T, D), ``v`` (H, T,
    Dv) where ``Dv`` may differ from ``D`` (latent attention's expanded form
    scores 192 wide and mixes values 128 wide), one sequence, no grouped heads.
    Returns (H, T, Dv) in ``q``'s type.  Head-major so that a caller whose
    projections can write that layout pays no transpose on the way in.

    Dispatch as :func:`flash_attention`: on TPU, under ``interpret=True`` or
    ``VESCALE_KERNELS=interpret``, the serve forward's kernel
    (``kernels.flash_attention._flash_fwd_serve``: products on the operands as
    they are, float32 softmax; ``name`` is the kernel's in the device trace);
    elsewhere the dense product, which forms the (T, T) scores and is for the
    CPU at small sizes.  No custom_vjp and no partition rule: one device,
    forward only."""
    H, T, D = q.shape
    if k.shape != q.shape or v.shape[:2] != (H, T):
        raise ValueError(f"flash_attention_forward: q {q.shape}, k {k.shape}, v {v.shape}")
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    from .. import kernels as _kernels

    if interpret is None:
        interpret = _kernels.mode() == "interpret"
    block_q, block_k = _fit_block(block_q, T), _fit_block(block_k, T)
    if (not _kernels.on_tpu() and not interpret) or T % block_q or T % block_k:
        s = jnp.einsum("hqd,hkd->hqk", q, k, preferred_element_type=jnp.float32) * scale
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, _NEG_INF)
        return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, axis=-1).astype(q.dtype), v,
                          preferred_element_type=jnp.float32).astype(q.dtype)
    return _flash_fwd_serve(q, k, v, scale, block_q, block_k, interpret, name=name)


def flash_attention_sharded(
    q,
    k,
    v,
    mesh,
    *,
    batch_dims=("dp",),
    head_dim: Optional[str] = "tp",
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: Optional[bool] = None,
):
    """Multi-chip flash attention: batch and/or head dims sharded over the
    mesh.  Attention is independent per (batch, head), so the kernel runs on
    local shards inside a shard_map with ZERO communication — this is the
    partitioning rule GSPMD cannot derive for a pallas custom call.

    ``q/k/v``: (B, T, H, D) with B shardable over ``batch_dims`` and H over
    ``head_dim``.  Seq-sharded inputs belong to ring/ulysses instead
    (parallel/context.py).  Dispatch inside the shard_map body follows the
    same ``VESCALE_KERNELS`` contract as :func:`flash_attention`."""
    from jax.sharding import PartitionSpec as P

    from ..collectives import shard_map

    names = tuple(d for d in batch_dims if d in mesh.mesh_dim_names)
    hd = head_dim if head_dim in mesh.mesh_dim_names else None
    if not names and hd is None:
        return flash_attention(q, k, v, causal=causal, scale=scale, block_q=block_q, block_k=block_k, interpret=interpret)
    from .. import kernels as _kernels

    D = q.shape[-1]
    scale_ = scale if scale is not None else 1.0 / math.sqrt(D)
    # the kernel mode is part of the cache key: the body's dispatch is
    # latched at trace time, so a mode flip must build (and compile) a
    # fresh program instead of silently reusing the other path's
    fn = _sharded_flash_fn(mesh, names, hd, causal, float(scale_), block_q, block_k,
                           bool(interpret) if interpret is not None else None,
                           _kernels.mode())
    return fn(q, k, v)


@functools.lru_cache(maxsize=64)
def _sharded_flash_fn(mesh, batch_names, head_name, causal, scale, block_q, block_k,
                      interpret, kmode):
    """Cached compiled program (jit cache is keyed on fn identity; a fresh
    closure per call would recompile every step).  ``kmode`` is unused in
    the body (the dispatch inside re-reads it at trace time) but keys the
    cache so each VESCALE_KERNELS mode gets its own compilation."""
    from jax.sharding import PartitionSpec as P

    from ..collectives import shard_map

    manual = frozenset(batch_names + ((head_name,) if head_name else ()))
    bspec = tuple(batch_names) if len(batch_names) > 1 else (batch_names[0] if batch_names else None)
    spec = P(bspec, None, head_name, None)

    def body(q_l, k_l, v_l):
        return flash_attention(
            q_l, k_l, v_l, causal=causal, scale=scale, block_q=block_q, block_k=block_k, interpret=interpret
        )

    return jax.jit(
        shard_map(
            body,
            mesh=mesh.jax_mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
            axis_names=manual,
        )
    )
