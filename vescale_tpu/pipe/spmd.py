"""Compiled SPMD pipeline — the TPU perf path.

Where the reference drives PP with per-rank executors + NCCL send/recv
(legacy/vescale/pipe/p2p_communication.py), the TPU-native path compiles the
WHOLE pipeline into one XLA program: stage params are stacked on a ``pp``
mesh axis, microbatches stream through a ``lax.scan`` whose steady state
rotates activations with ``lax.ppermute`` over ICI.  Reverse-mode AD
transposes the ppermute (reverse rotation), so ``jax.grad`` of this function
IS the backward pipeline — 1F1B emerges from XLA's scheduler rather than an
instruction VM.  (Pattern from public JAX pipelining recipes; see the
scaling-book's pipelining chapter.)

Schedules on the compiled path (reference _schedules/):
  - 1F1B-equivalent     ``pipeline_blocks``            <- pipedream_flush.py
  - Interleaved/VPP     ``pipeline_blocks(virtual_chunks=V)`` <- looping_bfs.py:699,873
    (each physical stage hosts V model chunks; microbatches re-enter stage 0
    after stage S-1, Megatron wave ordering, waves of S microbatches)
  - Zero-bubble         ``pipeline_blocks_zb``         <- zero_bubble_v.py:132,198,602
    (custom backward: phase 1 propagates ONLY input cotangents — the
    critical path; phase 2 computes every deferred weight grad afterwards,
    so wgrad work sits behind all dgrads in program order and XLA's
    scheduler is free to slot it into bubbles — the role of the reference's
    CostGraph, done by the compiler)

Requirements: homogeneous stages (same block params structure per stage) —
the canonical transformer middle.  Embedding/head run outside, replicated or
dp/tp-sharded.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..mesh import DeviceMesh
from ..collectives import shard_map

__all__ = [
    "pipeline_blocks",
    "pipeline_blocks_zb",
    "stack_stage_params",
    "stack_interleaved_params",
    "shard_stacked_params",
]


def stack_stage_params(params_list):
    """Stack per-stage param trees (same structure) along a new leading axis
    -> leaves (S, ...)."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, axis=0), *params_list)


def stack_interleaved_params(groups_params, num_stages: int):
    """Stack ``S*V`` per-group param trees (PipeModule group order: group
    ``g`` = chunk ``g // S`` on stage ``g % S``) into leaves (S*V, ...)
    ordered *stage-major* (index = stage*V + chunk) so that ``Shard`` on the
    pp mesh dim gives each stage its V contiguous chunks."""
    n = len(groups_params)
    if n % num_stages:
        raise ValueError(f"{n} groups not divisible by {num_stages} stages")
    V = n // num_stages
    reordered = [groups_params[v * num_stages + s] for s in range(num_stages) for v in range(V)]
    return stack_stage_params(reordered)


def shard_stacked_params(
    stacked,
    mesh: DeviceMesh,
    param_plan,
    pp_dim: str = "pp",
    fqn_prefix: str = "",
):
    """Place pp-stacked per-stage block params by a DModule param plan.

    Each leaf is (S, *block_shape) — or the flat stage-major (S*V,
    *block_shape) from ``stack_interleaved_params`` — the leading axis is
    Shard-placed on
    ``pp_dim`` and the block dims follow the plan's placements for
    ``fqn_prefix + leaf_path`` (the same FQN-regex plans
    ``parallelize_module`` consumes — reference dmodule/_dmodule.py:217
    _distribute_parameter, applied to the compiled-pipeline layout).
    Returns the tree with leaves ``jax.device_put`` onto the mesh.
    """
    from jax.sharding import NamedSharding

    from ..dmodule.api import DModule, keypath_fqn, pspec_of
    from ..placements import Replicate

    dm = DModule(None, mesh, {"parameter": param_plan})
    pp_index = mesh._dim_index(pp_dim)

    def one(keypath, leaf):
        path = keypath_fqn(keypath)
        placements = list(dm.param_placements(fqn_prefix + path, leaf.ndim - 1))
        placements[pp_index] = Replicate()  # pp is the stage axis, not a block dim
        block_spec = pspec_of(placements, leaf.ndim - 1, mesh)
        spec = P(pp_dim, *block_spec)
        return jax.device_put(leaf, NamedSharding(mesh.jax_mesh, spec))

    return jax.tree_util.tree_map_with_path(one, stacked)


# ------------------------------------------------------------ schedule math
def _vpp_slot(t, idx, S: int, V: int, M: int):
    """Decode the (microbatch, chunk) occupying stage ``idx`` at step ``t``.

    Megatron wave ordering (looping_bfs.py): microbatch ``m`` enters stage 0
    chunk 0 at ``t = (m // S) * S*V + m % S``; each step the activation
    rotates one stage forward, re-entering stage 0 for the next chunk after
    stage S-1.  Position ``p = v*S + idx`` gives the unique decomposition
    below.  Returns (m, v, active, inject, collect) — all traced scalars.
    """
    u = t - idx
    w = u // (S * V)
    q = u - w * (S * V)
    v = q // S
    j = q - v * S
    m = w * S + j
    active = (u >= 0) & (m < M)
    inject = active & (v == 0) & (idx == 0)
    collect = active & (v == V - 1) & (idx == S - 1)
    return m, v, active, inject, collect


def _vpp_total_steps(S: int, V: int, M: int) -> int:
    return ((M - 1) // S) * S * V + ((M - 1) % S) + S * V


def _index_chunk(params, v, V: int):
    """Select chunk ``v``'s param slice from local (V, ...) leaves."""
    if V == 1:
        return jax.tree_util.tree_map(lambda p: jnp.squeeze(p, 0), params)
    vc = jnp.clip(v, 0, V - 1)
    return jax.tree_util.tree_map(
        lambda p: jax.lax.dynamic_index_in_dim(p, vc, 0, keepdims=False), params
    )


def _prepare(x, mesh, pp_dim, num_microbatches, virtual_chunks, extra_specs, stacked_params):
    S = mesh.size(pp_dim)
    M = num_microbatches or S
    B = x.shape[0]
    if B % M != 0:
        raise ValueError(f"batch {B} not divisible by {M} microbatches")
    leaves = jax.tree_util.tree_leaves(stacked_params)
    if leaves and leaves[0].shape[0] != S * virtual_chunks:
        raise ValueError(
            f"stacked_params leading axis {leaves[0].shape[0]} != num_stages {S} "
            f"* virtual_chunks {virtual_chunks} (use stack_stage_params / "
            "stack_interleaved_params)"
        )
    xm = x.reshape(M, B // M, *x.shape[1:])
    act_spec = extra_specs if extra_specs is not None else P()
    manual = frozenset({pp_dim}) if mesh.ndim > 1 else frozenset(mesh.mesh_dim_names)
    return S, M, B, xm, act_spec, manual


def _constrain_auto(z, auto_act_spec: Optional[P], lead: int = 0):
    """Pin an activation buffer to ``auto_act_spec`` on the AUTO axes
    (legal inside the pp-manual shard_map: dp/tp/... stay GSPMD-managed).
    A bare PartitionSpec resolves against the CONTEXT mesh, whose axis
    types are (Manual, Auto, ...) here — a NamedSharding built from the
    concrete mesh would carry all-Auto types and trip the context-mesh
    check when sharding propagates (zeros_like etc.)."""
    if auto_act_spec is None:
        return z
    spec = P(*((None,) * lead + tuple(auto_act_spec)))
    return jax.lax.with_sharding_constraint(z, spec)


# ------------------------------------------------------------- 1F1B / VPP
def pipeline_blocks(
    block_fn: Callable,
    stacked_params,
    x,
    mesh: DeviceMesh,
    pp_dim: str = "pp",
    num_microbatches: Optional[int] = None,
    extra_specs: Optional[P] = None,
    virtual_chunks: int = 1,
    auto_act_spec: Optional[P] = None,
):
    """Apply ``S * virtual_chunks`` sequential model chunks (V per pp-mesh
    rank, Megatron interleaved assignment) to ``x``, pipelined over
    microbatches.

    ``block_fn(chunk_params, x_micro) -> y_micro`` must preserve the
    activation shape.  ``stacked_params`` leaves are (S, ...) for V=1
    (``stack_stage_params``) or (S*V, ...) stage-major
    (``stack_interleaved_params``), sharded on ``pp``.  ``x``: (B, ...) with
    B divisible by num_microbatches.  Returns (B, ...) outputs (as if the
    chunks were applied sequentially).

    ``auto_act_spec``: PartitionSpec over the AUTO (non-pp) mesh axes for
    one microbatch activation ``(b, *features)`` — e.g. ``P("dp", "tp")``
    for the Megatron-SP layout (batch over dp, sequence over tp).  Without
    it GSPMD chooses; with it the microbatch stash, the rotating carry, the
    outs buffer, and every scan-saved boundary (the backward stash) are
    pinned to that sharding — at 405B scale the difference between a 68 GB
    and a 1 GB per-device activation footprint.
    """
    S, M, B, xm, act_spec, manual = _prepare(
        x, mesh, pp_dim, num_microbatches, virtual_chunks, extra_specs, stacked_params
    )
    V = virtual_chunks
    T = _vpp_total_steps(S, V, M)

    def constrain(z, lead: int = 0):
        return _constrain_auto(z, auto_act_spec, lead)

    def worker(params, xm_local):
        # leaves (V, ...): the local stage's chunks
        idx = jax.lax.axis_index(pp_dim)
        perm = [(i, (i + 1) % S) for i in range(S)]
        micro = constrain(xm_local, lead=1)  # (M, b, ...)
        outs0 = jnp.zeros_like(micro)
        act0 = constrain(jnp.zeros_like(micro[0]))

        def body(carry, t):
            act, outs = carry
            m, v, active, inject, collect = _vpp_slot(t, idx, S, V, M)
            mc = jnp.clip(m, 0, M - 1)
            x_in = jnp.where(
                inject, jax.lax.dynamic_index_in_dim(micro, mc, 0, keepdims=False), act
            )
            y = constrain(block_fn(_index_chunk(params, v, V), x_in))
            outs = jax.lax.dynamic_update_index_in_dim(
                outs,
                jnp.where(
                    collect,
                    y,
                    jax.lax.dynamic_index_in_dim(outs, mc, 0, keepdims=False),
                ),
                mc,
                0,
            )
            act_next = jax.lax.ppermute(y, pp_dim, perm)
            return (act_next, outs), None

        (_, outs), _ = jax.lax.scan(body, (act0, outs0), jnp.arange(T))
        # only the LAST stage's buffer holds real outputs; return it as a
        # pp-sharded stage axis — downstream slicing moves one copy instead
        # of the old zeros+psum all-reduce of the full activation
        return outs[None]

    out = shard_map(
        worker,
        mesh=mesh.jax_mesh,
        in_specs=(P(pp_dim), act_spec),
        out_specs=P(pp_dim, *tuple(act_spec)),
        check_vma=False,
        # only pp is manual — dp/tp/sp remain auto so GSPMD shards the
        # per-stage compute (4D composition: PP x DP x TP x SP)
        axis_names=manual,
    )(stacked_params, xm)
    return out[S - 1].reshape(B, *x.shape[1:])


# ------------------------------------------------------------- zero bubble
def pipeline_blocks_zb(
    block_fn: Callable,
    stacked_params,
    x,
    mesh: DeviceMesh,
    pp_dim: str = "pp",
    num_microbatches: Optional[int] = None,
    extra_specs: Optional[P] = None,
    virtual_chunks: int = 1,
    auto_act_spec: Optional[P] = None,
):
    """``pipeline_blocks`` with a REAL zero-bubble backward
    (reference zero_bubble_v.py: B/W split).

    ``auto_act_spec`` pins the microbatch stash AND the per-step
    input/cotangent stashes (``xins``/``dys`` — ZB's dominant activation
    memory, T steps x microbatch each) to the given auto-axis layout, the
    same 405B-scale memory knob as ``pipeline_blocks``.

    Forward is the same rotating scan (inputs stashed per step).  The custom
    backward runs two phases:

      1. **dgrad scan** (reverse): re-linearizes each step's block
         (rematerialization) and transposes w.r.t. the *input only* —
         cotangents rotate backwards over ICI with no weight-grad matmuls on
         the critical path.  The per-step output cotangents are stashed.
      2. **wgrad scan**: computes every deferred weight grad from the
         stashed (input, cotangent) pairs and accumulates into the param
         grads.  In program order all W work follows all B work, giving
         XLA's latency-hiding scheduler the whole bubble budget to fill —
         the compiled analog of the reference's CostGraph scheduling.

    Cost: one extra block forward per phase (remat), the standard TPU
    trade of HBM for FLOPs.
    """
    S, M, B, xm, act_spec, manual = _prepare(
        x, mesh, pp_dim, num_microbatches, virtual_chunks, extra_specs, stacked_params
    )
    V = virtual_chunks
    T = _vpp_total_steps(S, V, M)

    def worker(params, xm_local):
        perm = [(i, (i + 1) % S) for i in range(S)]
        perm_rev = [(i, (i - 1) % S) for i in range(S)]
        micro = _constrain_auto(xm_local, auto_act_spec, lead=1)

        @jax.custom_vjp
        def pipe(params, micro):
            outs, _ = _fwd(params, micro)
            return outs

        def _fwd(params, micro):
            # axis_index is taken inside each phase: a value captured from
            # the enclosing worker trace would leak into the custom_vjp
            idx = jax.lax.axis_index(pp_dim)
            outs0 = jnp.zeros_like(micro)
            act0 = _constrain_auto(jnp.zeros_like(micro[0]), auto_act_spec)
            xin0 = _constrain_auto(
                jnp.zeros((T, *micro.shape[1:]), micro.dtype), auto_act_spec, lead=1
            )

            def body(carry, t):
                act, outs, xins = carry
                m, v, active, inject, collect = _vpp_slot(t, idx, S, V, M)
                mc = jnp.clip(m, 0, M - 1)
                x_in = jnp.where(
                    inject, jax.lax.dynamic_index_in_dim(micro, mc, 0, keepdims=False), act
                )
                xins = jax.lax.dynamic_update_index_in_dim(xins, x_in, t, 0)
                y = _constrain_auto(
                    block_fn(_index_chunk(params, v, V), x_in), auto_act_spec
                )
                outs = jax.lax.dynamic_update_index_in_dim(
                    outs,
                    jnp.where(
                        collect,
                        y,
                        jax.lax.dynamic_index_in_dim(outs, mc, 0, keepdims=False),
                    ),
                    mc,
                    0,
                )
                act_next = jax.lax.ppermute(y, pp_dim, perm)
                return (act_next, outs, xins), None

            (_, outs, xins), _ = jax.lax.scan(
                body, (act0, outs0, xin0), jnp.arange(T)
            )
            return outs, xins

        def pipe_fwd(params, micro):
            outs, xins = _fwd(params, micro)
            return outs, (params, micro, xins)

        def pipe_bwd(res, d_outs):
            params, micro, xins = res
            idx = jax.lax.axis_index(pp_dim)

            # ---- phase 1: dgrad-only reverse scan (the critical path) ----
            def bwd_body(carry, t):
                dact, dmicro, dys = carry
                m, v, active, inject, collect = _vpp_slot(t, idx, S, V, M)
                mc = jnp.clip(m, 0, M - 1)
                x_in = jax.lax.dynamic_index_in_dim(xins, t, 0, keepdims=False)
                p_v = _index_chunk(params, v, V)
                # cotangent of this step's output: what flowed back from the
                # next stage, plus the direct output cotangent if collected
                dy = dact + jnp.where(
                    collect,
                    jax.lax.dynamic_index_in_dim(d_outs, mc, 0, keepdims=False),
                    jnp.zeros_like(dact),
                )
                dy = jnp.where(active, dy, jnp.zeros_like(dy))
                dys = jax.lax.dynamic_update_index_in_dim(dys, dy, t, 0)
                _, f_lin = jax.linearize(lambda xx: block_fn(p_v, xx), x_in)
                (dx,) = jax.linear_transpose(f_lin, x_in)(dy)
                # injected steps terminate at the microbatch input
                dmicro = jax.lax.dynamic_update_index_in_dim(
                    dmicro,
                    jnp.where(
                        inject,
                        dx,
                        jax.lax.dynamic_index_in_dim(dmicro, mc, 0, keepdims=False),
                    ),
                    mc,
                    0,
                )
                dx = jnp.where(inject, jnp.zeros_like(dx), dx)
                dact_next = jax.lax.ppermute(dx, pp_dim, perm_rev)
                return (dact_next, dmicro, dys), None

            dact0 = _constrain_auto(jnp.zeros_like(micro[0]), auto_act_spec)
            dmicro0 = jnp.zeros_like(micro)
            dys0 = _constrain_auto(
                jnp.zeros((T, *micro.shape[1:]), micro.dtype), auto_act_spec, lead=1
            )
            (_, dmicro, dys), _ = jax.lax.scan(
                bwd_body, (dact0, dmicro0, dys0), jnp.arange(T - 1, -1, -1)
            )

            # ---- phase 2: deferred wgrads (fill the bubbles) ----
            def w_body(dparams, t):
                m, v, active, _, _ = _vpp_slot(t, idx, S, V, M)
                x_in = jax.lax.dynamic_index_in_dim(xins, t, 0, keepdims=False)
                dy = jax.lax.dynamic_index_in_dim(dys, t, 0, keepdims=False)
                p_v = _index_chunk(params, v, V)
                _, f_lin = jax.linearize(lambda pp: block_fn(pp, x_in), p_v)
                (dp,) = jax.linear_transpose(f_lin, p_v)(dy)
                vc = jnp.clip(v, 0, V - 1)

                def add_chunk(acc, d):
                    if V == 1:
                        return acc + d[None]
                    cur = jax.lax.dynamic_index_in_dim(acc, vc, 0, keepdims=False)
                    return jax.lax.dynamic_update_index_in_dim(acc, cur + d, vc, 0)

                return jax.tree_util.tree_map(add_chunk, dparams, dp), None

            dparams0 = jax.tree_util.tree_map(jnp.zeros_like, params)
            dparams, _ = jax.lax.scan(w_body, dparams0, jnp.arange(T))
            return dparams, dmicro

        pipe.defvjp(pipe_fwd, pipe_bwd)
        outs = pipe(params, micro)
        return outs[None]

    out = shard_map(
        worker,
        mesh=mesh.jax_mesh,
        in_specs=(P(pp_dim), act_spec),
        out_specs=P(pp_dim, *tuple(act_spec)),
        check_vma=False,
        axis_names=manual,
    )(stacked_params, xm)
    return out[S - 1].reshape(B, *x.shape[1:])
