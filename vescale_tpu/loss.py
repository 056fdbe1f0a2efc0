"""Loss parallel — vocab-sharded cross entropy without materializing logits.

Capability parity with the reference loss_parallel
(legacy/vescale/dtensor/loss.py:39,151,262): log-softmax + NLL over a
vocab-dim-sharded logits tensor, never gathering the full vocab dim.

TPU-native: two paths.
  * Inside jit, `vocab_parallel_cross_entropy` is written so GSPMD keeps the
    vocab dim sharded end-to-end (max/logsumexp are reductions XLA
    partitions; the gold-logit pick is a one-hot contraction).
  * The eager/explicit path runs the same math under shard_map with psum —
    bit-exact control over the reduction, mirroring the reference handlers.
The `loss_parallel()` context manager is kept for migration parity.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .collectives import shard_map
from .mesh import DeviceMesh

__all__ = ["loss_parallel", "vocab_parallel_cross_entropy"]


@contextlib.contextmanager
def loss_parallel():
    """Reference ctx manager (loss.py:39).  On TPU the efficient sharded
    loss needs no dispatch interception — under jit, GSPMD partitions the
    softmax/NLL reductions over whatever sharding the logits carry, so this
    scopes intent only (and keeps migrated code importable).  It warns once
    so users expecting the reference's op-interception semantics know to
    call ``vocab_parallel_cross_entropy`` for the explicit shard_map path."""
    import warnings

    if not getattr(loss_parallel, "_warned", False):
        loss_parallel._warned = True
        # an API-semantics notice to the calling developer, not a runtime
        # health signal — stays a process-wide warn-once, not an alert
        warnings.warn(  # vescale-lint: disable=VSC207
            "loss_parallel() performs no dispatch interception on TPU: inside "
            "jit the sharded loss is already efficient via GSPMD; for the "
            "explicit no-full-logits path use vocab_parallel_cross_entropy("
            "..., mesh=, vocab_dim_name=)",
            stacklevel=3,
        )
    yield


def vocab_parallel_cross_entropy(
    logits,
    targets,
    *,
    mesh: Optional[DeviceMesh] = None,
    vocab_dim_name: Optional[str] = None,
    label_smoothing: float = 0.0,
):
    """Token-mean cross entropy over vocab-sharded logits.

    ``logits``: (..., V) — under jit, pass the GSPMD-sharded array (any
    layout); XLA partitions the reductions.  With ``mesh`` +
    ``vocab_dim_name`` the explicit shard_map path runs: logits' last dim
    sharded over that mesh dim, full logits never materialized (reference
    _log_softmax_handler/_nll_loss_forward_handler, loss.py:151,262).

    With ``VESCALE_KERNELS`` enabled the per-shard heavy pass (sumexp +
    gold pick + Σlogits) runs as ONE fused Pallas kernel
    (``kernels.cross_entropy``) — one read of each logit — while the
    cross-shard pmax/psum (and so the collective count) stay exactly as
    they are.  ``off`` keeps this function byte-identical to the
    pre-kernel path.
    """
    V = logits.shape[-1]
    use = _xent_kernel_mode(V if mesh is None or vocab_dim_name is None
                            else V // mesh.size(mesh.dim_name(vocab_dim_name)),
                            logits)
    if mesh is None or vocab_dim_name is None:
        lg = logits.astype(jnp.float32)
        if use is not None:
            gmax = jax.lax.stop_gradient(jnp.max(lg, axis=-1))
            sumexp, picked, sumlg = _xent_parts_nd(lg, targets, gmax, use)
            logz = gmax + jnp.log(sumexp)
            if label_smoothing > 0.0:
                return jnp.mean(logz - (1 - label_smoothing) * picked - label_smoothing * (sumlg / V))
            return jnp.mean(logz - picked)
        logz = jax.scipy.special.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
        if label_smoothing > 0.0:
            # uniform smoothing: loss = logz - (1-ls)*gold - ls*mean_v(logit)
            return jnp.mean(logz - (1 - label_smoothing) * gold - label_smoothing * jnp.mean(lg, axis=-1))
        return jnp.mean(logz - gold)

    # the builder returns a jit-wrapped fn cached per (mesh, axis, vocab,
    # smoothing, rank, kernel-dispatch): eager calls reuse one compilation,
    # traced calls inline it into the enclosing jit
    fn = _vocab_parallel_fn(
        mesh, mesh.dim_name(vocab_dim_name), V, float(label_smoothing), logits.ndim, use
    )
    return fn(logits, targets)


def _xent_kernel_mode(shard_v: int, logits) -> Optional[bool]:
    """Kernel-dispatch decision for the fused cross entropy: None = XLA
    path, else the interpret flag.  Counted here (the call site), since
    the shape gate below is a late fallback."""
    from . import kernels as _kernels
    from .kernels.cross_entropy import xent_blocks

    kmode = _kernels.mode()
    if kmode == "off":
        return None
    n_rows = 1
    for d in logits.shape[:-1]:
        n_rows *= int(d)
    ok = kmode == "interpret" or _kernels.on_tpu()
    if not ok or xent_blocks(n_rows, shard_v) is None:
        _kernels.record_fallback("fused_xent")
        return None
    _kernels.record_dispatch("fused_xent")
    return kmode == "interpret"


def _xent_parts_nd(lg32, idx, gmax, interpret):
    """Run the one-pass kernel over (..., Vs) rows: flatten the leading
    dims, launch, restore.  ``idx`` are already-local column ids."""
    from .kernels.cross_entropy import fused_xent_parts

    lead = lg32.shape[:-1]
    flat = fused_xent_parts(
        lg32.reshape(-1, lg32.shape[-1]),
        idx.reshape(-1),
        gmax.reshape(-1),
        interpret,
    )
    return tuple(x.reshape(lead) for x in flat)


@functools.lru_cache(maxsize=64)
def _vocab_parallel_fn(mesh: DeviceMesh, ax: str, V: int, label_smoothing: float,
                       ndim: int, kernel: Optional[bool] = None):
    n = mesh.size(ax)
    shard_v = V // n

    def body(lg_local, tgt):
        # lg_local: (..., V/n) this rank's vocab slice; tgt: (...) global ids
        lg_local = lg_local.astype(jnp.float32)
        r = jax.lax.axis_index(ax)
        lo = r * shard_v
        # numerically-stable logsumexp across shards: global max first.
        # stop_gradient: the max-shift cancels exactly in the gradient, and
        # pmax has no differentiation rule
        local_max = jnp.max(lg_local, axis=-1)
        gmax = jax.lax.stop_gradient(jax.lax.pmax(jax.lax.stop_gradient(local_max), ax))
        in_range = (tgt >= lo) & (tgt < lo + shard_v)
        local_idx = jnp.clip(tgt - lo, 0, shard_v - 1)
        if kernel is not None:
            # fused one-pass kernel for the per-shard heavy lifting; the
            # cross-shard reductions below are IDENTICAL to the XLA path
            sumexp, picked, sumlg = _xent_parts_nd(lg_local, local_idx, gmax, kernel)
        else:
            sumexp = jnp.sum(jnp.exp(lg_local - gmax[..., None]), axis=-1)
            picked = jnp.take_along_axis(lg_local, local_idx[..., None], axis=-1)[..., 0]
            sumlg = None
        gsum = jax.lax.psum(sumexp, ax)
        logz = gmax + jnp.log(gsum)
        # gold logit: owned by exactly one shard; psum the masked pick
        gold = jax.lax.psum(jnp.where(in_range, picked, 0.0), ax)
        if label_smoothing > 0.0:
            local_sum = sumlg if sumlg is not None else jnp.sum(lg_local, axis=-1)
            mean_v = jax.lax.psum(local_sum, ax) / V
            return jnp.mean(logz - (1 - label_smoothing) * gold - label_smoothing * mean_v)
        return jnp.mean(logz - gold)

    return jax.jit(
        shard_map(
            body,
            mesh=mesh.jax_mesh,
            in_specs=(P(*([None] * (ndim - 1) + [ax])), P()),
            out_specs=P(),
            check_vma=False,
            axis_names=frozenset({ax}),
        )
    )
