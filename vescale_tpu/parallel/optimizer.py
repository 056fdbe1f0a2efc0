"""Optimizers: BasicOptimizer, DistributedOptimizer (ZeRO-2+), Muon.

Capability parity:
  - ``BasicOptimizer``        <- legacy/vescale/optim/base_optimizer.py:116
  - ``DistributedOptimizer``  <- legacy/vescale/optim/distributed_optimizer.py:131
  - ``clip_grad_norm_fp32``   <- legacy/vescale/optim/clip_grads.py:21
  - Muon-style optimizer      <- new-gen veScale (README.md:19, raggedshard.md
                                 §Structure-Aware gather-compute-scatter)

TPU-native ZeRO design: the reference maintains explicit gbuf range maps
(distributed_optimizer.py:383-601) to give each DP rank a contiguous shard of
grads + optimizer state, reduce-scattering grads in and all-gathering params
out.  Under GSPMD the same state machine is expressed as *sharding
constraints*: optimizer-state leaves (and the fp32 master params) carry a
Shard(dp) annotation, so XLA compiles the grad reduction as reduce-scatter,
runs the param update on 1/dp of the elements per chip, and all-gathers the
updated params — the weight-update-sharding transform of
arXiv:2004.13336, with overlap from the latency-hiding scheduler.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec

from ..mesh import DeviceMesh

__all__ = [
    "BasicOptimizer",
    "DistributedOptimizer",
    "zero_sharded",
    "clip_grad_norm_fp32",
    "found_inf",
    "muon",
    "adamw_lowmem",
]


def found_inf(grads) -> jax.Array:
    """Scalar bool: any non-finite value in any grad leaf (reference
    found_inf_reduce_handler, vescale/dtensor/_dispatch.py:60 — there an
    explicit cross-rank all-reduce of per-shard flags; under GSPMD the
    ``jnp.any`` over sharded leaves compiles to the same reduce +
    all-reduce)."""
    leaves = jax.tree_util.tree_leaves(grads)
    flags = [jnp.any(~jnp.isfinite(g)) for g in leaves if hasattr(g, "dtype")]
    if not flags:
        return jnp.asarray(False)
    out = flags[0]
    for f in flags[1:]:
        out = jnp.logical_or(out, f)
    return out


# --------------------------------------------------------------------- util
def _zero_pspec_for(shape: Tuple[int, ...], param_pspec: PartitionSpec, mesh: DeviceMesh, dp_dims: Sequence[str]) -> PartitionSpec:
    """Add the dp axes to the first free, divisible dim of a state leaf
    (weight-update sharding).  Leaves too small / indivisible — or already
    sharded on a dp axis — stay as-is."""
    entries = list(param_pspec) + [None] * (len(shape) - len(param_pspec))

    def uses_dp(e) -> bool:
        names = e if isinstance(e, tuple) else (e,)
        return any(n in dp_dims for n in names if n is not None)

    if any(uses_dp(e) for e in entries):
        return param_pspec  # param itself is dp-sharded (FSDP-style) already
    dp_total = 1
    for d in dp_dims:
        dp_total *= mesh.size(d)
    for i, (s, e) in enumerate(zip(shape, entries)):
        if e is None and s % dp_total == 0 and s >= dp_total:
            entries[i] = tuple(dp_dims) if len(dp_dims) > 1 else dp_dims[0]
            return PartitionSpec(*entries)
    return param_pspec


def _state_pspec(state_kp, shape, param_paths, pspec_by_path, mesh, dp_dims) -> Optional[PartitionSpec]:
    """ZeRO pspec for one state leaf, or None if it matches no param.

    Optimizer-state trees (adam mu/nu, momentum, master params) embed the
    params tree: a state leaf's keypath *ends with* some param's keypath.
    Matching by keypath suffix (+ shape check) is exact where a shape-dict
    heuristic would confuse same-shaped params with different layouts."""
    kp = tuple(str(k) for k in state_kp)
    for plen in range(len(kp), 0, -1):
        suffix = kp[-plen:]
        if suffix in param_paths and param_paths[suffix] == shape:
            base = pspec_by_path.get(suffix, PartitionSpec())
            return _zero_pspec_for(shape, base, mesh, dp_dims)
    return None


def _param_path_maps(params, param_pspecs):
    param_paths = {}
    for kp, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        param_paths[tuple(str(k) for k in kp)] = tuple(leaf.shape)
    pspec_by_path = {}
    for kp, ps in jax.tree_util.tree_flatten_with_path(
        param_pspecs, is_leaf=lambda x: isinstance(x, PartitionSpec)
    )[0]:
        pspec_by_path[tuple(str(k) for k in kp)] = ps
    return param_paths, pspec_by_path


def _constrain_state(state, params, param_pspecs, mesh: DeviceMesh, dp_dims):
    """Attach ZeRO shardings to every state leaf that corresponds to a param."""
    param_paths, pspec_by_path = _param_path_maps(params, param_pspecs)

    def one(state_kp, leaf):
        if not hasattr(leaf, "shape") or len(leaf.shape) == 0:
            return leaf
        ps = _state_pspec(state_kp, tuple(leaf.shape), param_paths, pspec_by_path, mesh, dp_dims)
        if ps is None:
            return leaf
        return jax.lax.with_sharding_constraint(leaf, NamedSharding(mesh.jax_mesh, ps))

    return jax.tree_util.tree_map_with_path(one, state)


def zero_sharded(
    tx: optax.GradientTransformation,
    mesh: DeviceMesh,
    param_pspecs,
    dp_dims: Sequence[str] = ("dp",),
) -> optax.GradientTransformation:
    """Wrap an optax transform so its state is ZeRO-sharded over ``dp_dims``.

    ``param_pspecs``: pytree of PartitionSpec matching the params tree (from
    DModule.variables_shardings / pspec_of)."""

    def init(params):
        return _constrain_state(tx.init(params), params, param_pspecs, mesh, dp_dims)

    def update(grads, state, params=None, **kw):
        updates, new_state = tx.update(grads, state, params, **kw)
        return updates, _constrain_state(new_state, params, param_pspecs, mesh, dp_dims)

    return optax.GradientTransformation(init, update)


def clip_grad_norm_fp32(grads, max_norm: float, norm_type: int = 2):
    """Global-norm clip in fp32 (reference clip_grads.py:21).  The norm
    reduction over sharded grads compiles to the cross-mesh all-reduce the
    reference issues explicitly.  Returns (clipped_grads, total_norm)."""
    leaves = jax.tree_util.tree_leaves(grads)
    # pre-scale by the global max |g| so the squared sum cannot overflow fp32
    # (1e20-magnitude grads would otherwise clip to zero silently)
    gmax = jnp.maximum(
        jnp.asarray(1e-30, jnp.float32),
        jnp.max(jnp.stack([jnp.max(jnp.abs(g.astype(jnp.float32))) for g in leaves])),
    )
    if norm_type == 2:
        total = gmax * jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32) / gmax)) for g in leaves))
    else:
        total = gmax * sum(jnp.sum(jnp.abs(g.astype(jnp.float32) / gmax) ** norm_type) for g in leaves) ** (
            1.0 / norm_type
        )
    scale = jnp.minimum(1.0, max_norm / (total + 1e-6))
    return jax.tree_util.tree_map(lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype), grads), total


def _optimizer_step_span(grads):
    """ndtimeline OPTIMIZER_STEP span for EAGER optimizer steps only.

    ``step`` is usually traced inside the jitted train step, where a host
    span would bracket trace time once and then never fire — the in-jit
    device work belongs to the XLA profiler.  Eager call sites (the pipe
    engine's update loop, examples, debugging) get a real span.  A step is
    being traced exactly when its ``grads`` are tracers."""
    import contextlib

    from ..ndtimeline.api import is_active, ndtimeit
    from ..ndtimeline.predefined import OPTIMIZER_STEP

    if is_active() and not any(
        isinstance(g, jax.core.Tracer) for g in jax.tree_util.tree_leaves(grads)
    ):
        return ndtimeit(OPTIMIZER_STEP)
    return contextlib.nullcontext()


# ---------------------------------------------------------------- wrappers
class BasicOptimizer:
    """DP-replicated optimizer wrapper (reference base_optimizer.py:116):
    plain optax step + grad-sync contract (automatic under jit)."""

    def __init__(self, optimizer: optax.GradientTransformation, models=None, grad_clip: Optional[float] = None):
        self.tx = optimizer
        self.grad_clip = grad_clip

    def init(self, params):
        from ..telemetry import memtrack as _memtrack

        return _memtrack.tag_tree(self.tx.init(params), "optimizer_state")

    def step(self, params, opt_state, grads):
        with _optimizer_step_span(grads):
            if self.grad_clip is not None:
                grads, _ = clip_grad_norm_fp32(grads, self.grad_clip)
            updates, opt_state = self.tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state


class DistributedOptimizer:
    """ZeRO-2+ optimizer (reference distributed_optimizer.py:131).

    fp32 master params + optimizer states sharded over the DP mesh dims;
    params may be any dtype (bf16 training).  ``step`` is jit-friendly:

        dopt = DistributedOptimizer(optax.adamw(...), mesh, param_pspecs)
        state = dopt.init(params)
        params, state = jax.jit(dopt.step)(params, state, grads)

    Grad reduce-scatter / param all-gather / overlap are emitted by XLA from
    the sharding constraints (see module docstring).

    Overflow protection (reference found_inf_reduce_handler,
    vescale/dtensor/_dispatch.py:60, + the overflow tracking of
    legacy/vescale/optim/distributed_optimizer.py): with
    ``loss_scale="dynamic"`` (or a static float) the step unscales grads,
    all-reduces a found-inf flag, and on overflow SKIPS the step — params
    and optimizer state come back bitwise unchanged — backing off the
    dynamic scale; after ``growth_interval`` clean steps the scale doubles.
    Scale the loss with ``dopt.scale_loss(loss, state)`` before ``grad``.
    """

    def __init__(
        self,
        optimizer: optax.GradientTransformation,
        mesh: DeviceMesh = None,
        param_pspecs=None,
        models=None,
        dp_dims: Sequence[str] = ("dp",),
        grad_clip: Optional[float] = None,
        main_param_dtype=jnp.float32,
        overlap_param_gather: bool = True,  # parity flag; XLA handles overlap
        loss_scale=None,  # None | float | "dynamic"
        init_scale: float = 2.0**15,
        growth_interval: int = 2000,
        growth_factor: float = 2.0,
        backoff_factor: float = 0.5,
        min_scale: float = 1.0,
        skip_nonfinite: Optional[bool] = None,
        grad_compress: Optional[str] = None,
        compress_block: Optional[int] = None,
        **_: Any,
    ):
        self.mesh = mesh
        self.dp_dims = tuple(dp_dims)
        self.param_pspecs = param_pspecs
        # gradient compression for the explicit ZeRO grad reduction
        # (reduce_grads): "int8" = block-scaled quantized reduce-scatter /
        # all-reduce; None defers to VESCALE_GRAD_COMPRESS
        from .ddp import resolve_grad_compress

        self.grad_compress = resolve_grad_compress(grad_compress)
        self.compress_block = compress_block
        self.grad_clip = grad_clip
        self.main_param_dtype = main_param_dtype
        self.loss_scale = loss_scale
        self.init_scale = float(init_scale)
        self.growth_interval = int(growth_interval)
        self.growth_factor = float(growth_factor)
        self.backoff_factor = float(backoff_factor)
        # floor under persistent overflows: without it the scale decays to 0,
        # scale_loss zeroes the loss, inv becomes inf, grads32 = 0*inf = NaN,
        # and training silently skips every step forever (r4 advisor finding).
        # Clamped to init_scale so a sub-unity init_scale cannot make an
        # overflow RAISE the scale to the floor; must stay > 0 to be a floor.
        if float(min_scale) <= 0.0:
            raise ValueError(f"min_scale must be > 0, got {min_scale}")
        self.min_scale = min(float(min_scale), float(init_scale))
        # skip-step on non-finite grads is implied by loss scaling; it can
        # also be enabled standalone (bf16-without-scaling runs)
        self.skip_nonfinite = bool(loss_scale is not None) if skip_nonfinite is None else skip_nonfinite
        if loss_scale == "dynamic" and not self.skip_nonfinite:
            raise ValueError(
                "loss_scale='dynamic' requires skip_nonfinite: the scale "
                "backoff/growth is driven by the overflow flag — without it "
                "the scale would freeze and overflows would corrupt params"
            )
        self.tx = (
            zero_sharded(optimizer, mesh, param_pspecs, dp_dims)
            if mesh is not None and param_pspecs is not None
            else optimizer
        )

    # ------------------------------------------------------------- state
    def init(self, params):
        from ..telemetry import memtrack as _memtrack

        main = jax.tree_util.tree_map(lambda p: p.astype(self.main_param_dtype), params)
        if self.mesh is not None and self.param_pspecs is not None:
            main = _constrain_state(main, params, self.param_pspecs, self.mesh, self.dp_dims)
        state = {"inner": self.tx.init(main), "main_params": main}
        if self.loss_scale == "dynamic":
            state["loss_scale"] = {
                "scale": jnp.asarray(self.init_scale, jnp.float32),
                "growth_count": jnp.asarray(0, jnp.int32),
                # consecutive skipped steps — a stalled run (every step
                # overflowing at the floor) is observable instead of silent
                "skip_count": jnp.asarray(0, jnp.int32),
            }
        # memory attribution: fp32 masters + moments are usually the single
        # largest resident HBM bucket — the census must name them
        return _memtrack.tag_tree(state, "optimizer_state")

    # ------------------------------------------------------- loss scaling
    def current_scale(self, opt_state):
        if self.loss_scale == "dynamic":
            return opt_state["loss_scale"]["scale"]
        if self.loss_scale is not None:
            return jnp.asarray(self.loss_scale, jnp.float32)
        return jnp.asarray(1.0, jnp.float32)

    def scale_loss(self, loss, opt_state):
        """Multiply the loss by the current scale (call before ``grad``)."""
        return loss * self.current_scale(opt_state).astype(loss.dtype)

    # ----------------------------------------------------- grad reduction
    def reduce_grads(self, grads, dp_dim: Optional[str] = None):
        """Explicit DP gradient reduction into the ZeRO layout (reference
        distributed_optimizer.py's grad reduce-scatter) for eager /
        explicit flows — under pure GSPMD the reduction is structural and
        this is not needed.

        DArray leaves with a Partial placement on the dp dim reduce to
        ``Shard(0)`` when ZeRO state sharding is active and dim0 divides
        the dp world (each rank keeps exactly the grad shard its optimizer
        partition consumes), else to ``Replicate``.  With
        ``grad_compress="int8"`` the wire payload is block-scaled int8
        (quantized reduce-scatter / all-reduce); other leaves are returned
        unchanged."""
        from ..darray import DArray
        from .ddp import _reduce_partial_leaf

        dp_dim = dp_dim or self.dp_dims[0]
        if self.mesh is None:
            return grads
        dp_index = self.mesh._dim_index(dp_dim)
        zero_active = self.param_pspecs is not None
        dp_world = self.mesh.size(dp_dim)

        def one(g):
            if not (isinstance(g, DArray) and g.placements[dp_index].is_partial()):
                return g
            from ..placements import Replicate as R, Shard as S

            target = (
                S(0)
                if zero_active and g.shape and g.shape[0] % dp_world == 0
                else R()
            )
            return _reduce_partial_leaf(
                g, dp_index, target, self.grad_compress, self.compress_block
            )

        return jax.tree_util.tree_map(
            one, grads, is_leaf=lambda x: isinstance(x, DArray)
        )

    # -------------------------------------------------------------- step
    def step(self, params, opt_state, grads):
        """copy grads -> fp32, unscale, clip, inner step on fp32 master
        shards, copy master -> model params (reference step/:1142-1223
        pipeline); overflow -> skip + scale backoff."""
        with _optimizer_step_span(grads):
            return self._step_impl(params, opt_state, grads)

    def _step_impl(self, params, opt_state, grads):
        inv = 1.0 / self.current_scale(opt_state)
        grads32 = jax.tree_util.tree_map(
            lambda g: g.astype(self.main_param_dtype) * inv.astype(self.main_param_dtype), grads
        )
        # the overflow flag is computed on the raw unscaled grads, BEFORE
        # clipping turns inf into nan-laden scale factors
        overflow = found_inf(grads32) if self.skip_nonfinite else None
        if self.grad_clip is not None:
            grads32, _ = clip_grad_norm_fp32(grads32, self.grad_clip)
        main = opt_state["main_params"]
        updates, inner = self.tx.update(grads32, opt_state["inner"], main)
        main_new = optax.apply_updates(main, updates)
        if overflow is None:
            new_params = jax.tree_util.tree_map(lambda m, p: m.astype(p.dtype), main_new, params)
            out_state = {"inner": inner, "main_params": main_new}
            if "loss_scale" in opt_state:
                out_state["loss_scale"] = opt_state["loss_scale"]
            return new_params, out_state

        def keep_old(new, old):
            return jax.tree_util.tree_map(lambda n, o: jnp.where(overflow, o, n), new, old)

        main_out = keep_old(main_new, main)
        inner_out = keep_old(inner, opt_state["inner"])
        new_params = keep_old(
            jax.tree_util.tree_map(lambda m, p: m.astype(p.dtype), main_new, params), params
        )
        out_state = {"inner": inner_out, "main_params": main_out}
        if self.loss_scale == "dynamic":
            ls = opt_state["loss_scale"]
            growth = jnp.where(overflow, 0, ls["growth_count"] + 1)
            grown = growth >= self.growth_interval
            scale = jnp.where(
                overflow,
                jnp.maximum(ls["scale"] * self.backoff_factor, self.min_scale),
                jnp.where(grown, ls["scale"] * self.growth_factor, ls["scale"]),
            )
            out_state["loss_scale"] = {
                "scale": scale,
                "growth_count": jnp.where(grown, 0, growth).astype(jnp.int32),
                "skip_count": jnp.where(
                    overflow, ls.get("skip_count", jnp.asarray(0, jnp.int32)) + 1, 0
                ).astype(jnp.int32),
            }
        elif "loss_scale" in opt_state:
            out_state["loss_scale"] = opt_state["loss_scale"]
        return new_params, out_state

    def state_pspecs(self, params):
        """PartitionSpecs of the optimizer state (metadata only — used by
        checkpoint planners; no state is materialized)."""
        state = jax.eval_shape(self.init, params)
        param_paths, pspec_by_path = _param_path_maps(params, self.param_pspecs)

        def one(kp, leaf):
            if not hasattr(leaf, "shape") or len(leaf.shape) == 0:
                return PartitionSpec()
            ps = _state_pspec(kp, tuple(leaf.shape), param_paths, pspec_by_path, self.mesh, self.dp_dims)
            return ps if ps is not None else PartitionSpec()

        return jax.tree_util.tree_map_with_path(one, state)

    def state_template(self, params):
        """Abstract optimizer-state tree for checkpoint restore: every leaf
        is a ``jax.ShapeDtypeStruct`` carrying THIS optimizer's ZeRO
        sharding (``state_pspecs`` recomputed for the current mesh/world).

        This is the elastic-restore entry point (docs/resilience.md):
        after a world-size change, build the optimizer for the NEW mesh,
        pass ``state_template(params)`` as the ``"optimizer"`` template to
        ``checkpoint.load`` and each new rank's ranges — the reference's
        gbuf range maps, here the pspec-derived chunk boxes — are filled
        from the old ranks' saved chunks by box intersection, without ever
        materializing a throwaway zero state."""
        state = jax.eval_shape(self.init, params)
        if self.mesh is None or self.param_pspecs is None:
            return jax.tree_util.tree_map(
                lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype) if hasattr(l, "shape") else l,
                state,
            )
        param_paths, pspec_by_path = _param_path_maps(params, self.param_pspecs)
        jm = self.mesh.jax_mesh

        def one(kp, leaf):
            if not hasattr(leaf, "shape"):
                return leaf
            if len(leaf.shape) == 0:
                # scalars (step counters) stay uncommitted so jit may
                # co-locate them — the same policy as the load path
                return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)
            ps = _state_pspec(
                kp, tuple(leaf.shape), param_paths, pspec_by_path, self.mesh, self.dp_dims
            )
            return jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype, sharding=NamedSharding(jm, ps or PartitionSpec())
            )

        return jax.tree_util.tree_map_with_path(one, state)


# ----------------------------------------------------------- low-mem adamw
class ScaleByAdamLowmemState(NamedTuple):
    count: jax.Array
    mu: Any
    nu: Any


def scale_by_adam_lowmem(
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    state_dtype=jnp.bfloat16,
) -> optax.GradientTransformation:
    """Adam moment estimation with both moments stored in ``state_dtype``.

    Halves (bf16) optimizer-state HBM vs fp32 mu/nu — the difference between
    fitting a 1-2B model on one 16 GB chip and not.  All arithmetic runs in
    fp32; only the carried state is rounded, so the second moment keeps its
    fp32 *dynamic range* (bf16 shares the fp32 exponent) and loses only
    mantissa — the same trade the reference's bf16 mixed-precision training
    makes for params (legacy/examples/llama2_4D_finetune/llama_train.py dtype
    flags).  fp32 ``state_dtype`` reproduces optax.scale_by_adam exactly.

    With ``VESCALE_KERNELS`` enabled the per-leaf elementwise chain runs as
    ONE fused Pallas kernel (``kernels.fused_adamw``) — same ops, same
    order, bit-identical under jit (asserted in tests/test_kernels.py);
    the decision is latched per trace (docs/kernels.md).
    """

    def init(params):
        zeros = lambda p: jnp.zeros(p.shape, state_dtype)
        return ScaleByAdamLowmemState(
            count=jnp.zeros([], jnp.int32),
            mu=jax.tree_util.tree_map(zeros, params),
            nu=jax.tree_util.tree_map(zeros, params),
        )

    def update(grads, state, params=None, **_kw):
        count = state.count + 1
        c1 = 1.0 - b1 ** count.astype(jnp.float32)
        c2 = 1.0 - b2 ** count.astype(jnp.float32)

        from .. import kernels as _kernels

        interp = _kernels.resolve("fused_adamw")  # None -> the XLA chain

        def one(g, m, v):
            if interp is not None and g.ndim > 0:
                from ..kernels.fused_adamw import fused_adamw_update

                return fused_adamw_update(
                    g, m, v, c1, c2, b1=b1, b2=b2, eps=eps,
                    state_dtype=state_dtype, interpret=interp,
                )
            g32 = g.astype(jnp.float32)
            m32 = b1 * m.astype(jnp.float32) + (1.0 - b1) * g32
            v32 = b2 * v.astype(jnp.float32) + (1.0 - b2) * jnp.square(g32)
            u = ((m32 / c1) / (jnp.sqrt(v32 / c2) + eps)).astype(g.dtype)
            return u, m32.astype(state_dtype), v32.astype(state_dtype)

        triples = jax.tree_util.tree_map(one, grads, state.mu, state.nu)
        updates, mu, nu = jax.tree_util.tree_transpose(
            jax.tree_util.tree_structure(grads),
            jax.tree_util.tree_structure((0, 0, 0)),
            triples,
        )
        return updates, ScaleByAdamLowmemState(count=count, mu=mu, nu=nu)

    return optax.GradientTransformation(init, update)


def adamw_lowmem(
    learning_rate,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 1e-4,  # optax.adamw default, for drop-in parity
    state_dtype=jnp.bfloat16,
) -> optax.GradientTransformation:
    """AdamW with ``state_dtype`` moments (see ``scale_by_adam_lowmem``)."""
    return optax.chain(
        scale_by_adam_lowmem(b1, b2, eps, state_dtype),
        optax.add_decayed_weights(weight_decay),
        optax.scale_by_learning_rate(learning_rate),
    )


# -------------------------------------------------------------------- muon
def _newton_schulz(G, steps: int = 5, eps: float = 1e-7):
    """Quintic Newton-Schulz orthogonalization (Muon).  Runs in bf16 on the
    MXU; operates on the full 2-D gradient."""
    a, b, c = 3.4445, -4.7750, 2.0315
    X = G.astype(jnp.bfloat16)
    X = X / (jnp.linalg.norm(X.astype(jnp.float32)) + eps)
    transpose = G.shape[0] > G.shape[1]
    if transpose:
        X = X.T

    def body(X, _):
        A = X @ X.T
        B = b * A + c * (A @ A)
        return a * X + B @ X, None

    X, _ = jax.lax.scan(body, X, None, length=steps)
    if transpose:
        X = X.T
    return X.astype(G.dtype)


def muon(
    learning_rate: float = 0.02,
    momentum: float = 0.95,
    nesterov: bool = True,
    ns_steps: int = 5,
    fallback: Optional[optax.GradientTransformation] = None,
    state_dtype=None,
) -> optax.GradientTransformation:
    """Muon optimizer: momentum + Newton-Schulz orthogonalized updates for
    2-D params; ``fallback`` (default adamw 3e-4) for others.  The
    reference's gather-compute-scatter over RaggedShard params
    (raggedshard.md) is GSPMD-implicit: the NS matmuls force an all-gather
    of the 2-D param's gradient, and the result re-shards on write.
    ``state_dtype`` (e.g. bf16) stores the momentum low-precision, the
    ``adamw_lowmem`` trade."""
    fallback = fallback or optax.adamw(3e-4)

    def mom_init(params):
        return jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, state_dtype or p.dtype), params
        )

    def mom_update(grads, mom, params=None, **_kw):
        new_mom = jax.tree_util.tree_map(
            lambda m, g: (momentum * m.astype(g.dtype) + g).astype(m.dtype), mom, grads
        )

        def one(g, m):
            eff = momentum * m.astype(g.dtype) + g if nesterov else m.astype(g.dtype)
            o = _newton_schulz(eff, ns_steps)
            # flax kernels are (fan_in, fan_out): the Muon per-matrix LR
            # scale is sqrt(max(1, fan_out / fan_in)) = shape[1]/shape[0]
            # (the torch recipe's rows/cols, transposed for this layout)
            scale = jnp.sqrt(jnp.maximum(1.0, g.shape[1] / g.shape[0]))
            return (-learning_rate * scale * o).astype(g.dtype)

        return jax.tree_util.tree_map(one, grads, new_mom), new_mom

    muon_core = optax.GradientTransformation(mom_init, mom_update)

    _EXCLUDE = ("embed", "embedding", "wte", "wpe", "lm_head", "head")

    def labels(params):
        # the Muon recipe orthogonalizes hidden 2-D weights only; embeddings
        # and output heads go to the fallback optimizer
        def one(kp, p):
            path = ".".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in kp).lower()
            if p.ndim != 2 or any(tok in path for tok in _EXCLUDE):
                return "fallback"
            return "muon"

        return jax.tree_util.tree_map_with_path(one, params)

    return optax.multi_transform({"muon": muon_core, "fallback": fallback}, labels)
