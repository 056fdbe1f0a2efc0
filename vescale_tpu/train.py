"""Jitted train-step assembly.

The reference's training loop composes DModule forward + DDP backward +
DistributedOptimizer step as three separately-hooked eager phases (SURVEY
§3.3).  TPU-native, the whole step is ONE jit-compiled program: GSPMD
inserts the DP grad all-reduce, TP boundary collectives and ZeRO
reduce-scatter/all-gather, and XLA's latency-hiding scheduler overlaps them
with compute (the role of the reference's async bucket machinery).
"""

from __future__ import annotations

import functools
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import optax

from .dmodule.api import DModule

__all__ = ["make_train_step", "make_eval_step"]

# double-increment guard: with auto_inc_step (default), a loop that
# ALSO advances the ndtimeline counter manually (inc_step() /
# flush(next_iteration=True)) per step silently double-counts the global
# step.  SHARED across every make_train_step fn: any auto-inc step records
# the counter value it produced here, so a second auto-inc fn (train + eval
# loops sharing one manager) is recognized as legitimate — only a counter
# move no auto-inc step made triggers the one-time warning.
_AUTO_STEP_GUARD: Dict[str, Any] = {"mgr": None, "step": None, "warned": False}


def make_train_step(
    dmodel: DModule,
    tx,
    loss_fn: Callable,
    *,
    has_aux: bool = False,
    donate: bool = True,
    rng_streams: tuple = ("dropout",),
    grad_accum_steps: int = 1,
    auto_inc_step: bool = True,
    with_metrics: Optional[bool] = None,
):
    """Build ``train_step(params, opt_state, batch, step_key) ->
    (params, opt_state, loss)``.

    ``tx`` may be an ``optax.GradientTransformation`` OR a
    ``DistributedOptimizer``/``BasicOptimizer`` — with a DistributedOptimizer
    the step scales the loss by the live loss scale before ``grad``,
    unscales/clips/skips inside ``dopt.step``, and reports the UNSCALED
    loss, so mixed-precision overflow protection needs no hand wiring
    (examples/resilient_train shows the manual equivalent).

    ``loss_fn(logits_or_outputs, batch)`` computes the scalar loss from the
    model output.  Dropout etc. draw from ``step_key`` folded per stream —
    deterministic and bitwise-identical under any sharding.

    ``grad_accum_steps`` > 1 splits the batch into micro-batches accumulated
    in fp32 via ``lax.scan`` (the reference DDP's main_grad accumulation,
    ddp/grad_buffer.py, expressed functionally) before one optimizer update.
    The accumulated grads/loss are averaged over micro-batches, so
    ``loss_fn`` must be MEAN-reduced for step-1 equivalence (a sum-reduced
    loss would be scaled by 1/grad_accum_steps).

    ``has_aux=True``: ``loss_fn`` returns ``(loss, aux)`` — a metrics pytree
    carried through every path (r5, VERDICT r4 next #8): with a
    DistributedOptimizer only the LOSS is scaled (aux stays raw), and under
    grad accumulation float aux leaves are MEAN-reduced across micro-batches
    while integer leaves (counts) are SUMMED.

    fp8 models (``LlamaConfig.use_fp8`` — flax ``Fp8DotGeneralOp``) carry an
    ``_overwrite_with_gradient`` variable collection (delayed-scaling amax
    histories + scales).  Pass ``params`` as the TWO-collection bundle
    ``{"params": ..., "_overwrite_with_gradient": ...}`` (and init the
    optimizer on the ``params`` subtree only): the step threads the
    collection through apply, keeps it away from the optimizer, and
    OVERWRITES it with its gradient (the fp8 delayed-scaling update) under
    a finite guard so skipped overflow steps cannot poison the histories.

    ``with_metrics``: the telemetry feed (telemetry/).  When True the
    compiled step additionally computes per-step scalars — grad-norm, and
    with a DistributedOptimizer the live loss-scale value and skipped-step
    count — returned OUT-OF-BAND: the wrapper strips them from the public
    return and forwards them (plus wall-clock step time, loss, tokens/sec)
    to ``telemetry.record_step``.  ``None`` (default) resolves to
    ``telemetry.is_active()`` at BUILD time, so a run that calls
    ``telemetry.init()`` before ``make_train_step`` gets the full feed and
    an un-instrumented run compiles the exact unchanged program — the
    zero-overhead gating contract.
    """
    from . import telemetry as _tel
    from .parallel.optimizer import BasicOptimizer, DistributedOptimizer

    if with_metrics is None:
        with_metrics = _tel.is_active()
    dopt = tx if isinstance(tx, (BasicOptimizer, DistributedOptimizer)) else None
    OWG = "_overwrite_with_gradient"

    def micro_loss(p, micro_batch, step_key, opt_state=None):
        rngs = (
            {name: jax.random.fold_in(step_key, i) for i, name in enumerate(rng_streams)}
            if step_key is not None
            else None
        )
        variables = (
            {"params": p["params"], OWG: p[OWG]}
            if isinstance(p, dict) and OWG in p
            else {"params": p}
        )
        out = dmodel.apply(
            variables, micro_batch["input"], deterministic=step_key is None, rngs=rngs
        )
        res = loss_fn(out, micro_batch)
        loss, aux = res if has_aux else (res, None)
        if isinstance(dopt, DistributedOptimizer) and opt_state is not None:
            loss = dopt.scale_loss(loss, opt_state)
        return (loss, aux) if has_aux else loss

    def _reduce_aux_leaf(a):
        # a: (grad_accum_steps, ...) stacked metric — means for measures,
        # sums for integer counts
        if jnp.issubdtype(a.dtype, jnp.inexact):
            return jnp.mean(a, axis=0).astype(a.dtype)
        return jnp.sum(a, axis=0)

    def step(params, opt_state, batch, step_key=None):
        fp8_bundle = isinstance(params, dict) and OWG in params
        if grad_accum_steps <= 1:
            if has_aux:
                (loss, aux), grads = jax.value_and_grad(
                    lambda p: micro_loss(p, batch, step_key, opt_state), has_aux=True
                )(params)
            else:
                loss, grads = jax.value_and_grad(
                    lambda p: micro_loss(p, batch, step_key, opt_state)
                )(params)
                aux = None
        else:
            b0 = jax.tree_util.tree_leaves(batch)[0].shape[0]
            if b0 % grad_accum_steps != 0:
                raise ValueError(
                    f"batch dim {b0} not divisible by grad_accum_steps={grad_accum_steps}"
                )
            micros = jax.tree_util.tree_map(
                lambda x: x.reshape(grad_accum_steps, x.shape[0] // grad_accum_steps, *x.shape[1:]),
                batch,
            )

            def accum(carry, inputs):
                g_acc, l_acc = carry
                mb, i = inputs
                key_i = jax.random.fold_in(step_key, 1000 + i) if step_key is not None else None
                if has_aux:
                    (l, aux_i), g = jax.value_and_grad(
                        lambda p: micro_loss(p, mb, key_i, opt_state), has_aux=True
                    )(params)
                else:
                    l, g = jax.value_and_grad(lambda p: micro_loss(p, mb, key_i, opt_state))(params)
                    aux_i = None
                if fp8_bundle:
                    # OWG "grads" are next-values, not gradients.  amax
                    # histories combine by elementwise MAX across the
                    # micro-batches (every micro-batch rolled the SAME
                    # pre-step history, so max captures the true per-step
                    # amax — a spike in micro-batch 1 must not be dropped
                    # because micro-batch N was calm); derived scale leaves
                    # take the latest (identical across micro-batches: all
                    # computed from the pre-step history).
                    def owg_one(kp, a, b):
                        leaf = str(getattr(kp[-1], "key", kp[-1]))
                        return jnp.maximum(a, b) if "amax_history" in leaf else b

                    g_acc = {
                        "params": jax.tree_util.tree_map(
                            lambda a, b: a + b.astype(a.dtype), g_acc["params"], g["params"]
                        ),
                        OWG: jax.tree_util.tree_map_with_path(owg_one, g_acc[OWG], g[OWG]),
                    }
                else:
                    g_acc = jax.tree_util.tree_map(lambda a, b: a + b.astype(a.dtype), g_acc, g)
                return (g_acc, l_acc + l), aux_i

            g0 = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (g_sum, l_sum), aux_stack = jax.lax.scan(
                accum, (g0, 0.0), (micros, jnp.arange(grad_accum_steps))
            )
            if fp8_bundle:
                grads = {
                    "params": jax.tree_util.tree_map(
                        lambda g, p: (g / grad_accum_steps).astype(p.dtype),
                        g_sum["params"],
                        params["params"],
                    ),
                    OWG: g_sum[OWG],
                }
            else:
                grads = jax.tree_util.tree_map(
                    lambda g, p: (g / grad_accum_steps).astype(p.dtype), g_sum, params
                )
            loss = l_sum / grad_accum_steps
            aux = (
                jax.tree_util.tree_map(_reduce_aux_leaf, aux_stack) if has_aux else None
            )
        if fp8_bundle:
            # the OWG collection never meets the optimizer: its "gradient"
            # IS its next value (delayed-scaling histories/scales), applied
            # under a finite guard — an overflow step's inf amax must not
            # poison the rolling history
            owg_new = jax.tree_util.tree_map(
                lambda new, old: jnp.where(jnp.isfinite(new), new, old),
                grads[OWG],
                params[OWG],
            )
            params_p, grads_p = params["params"], grads["params"]
        else:
            params_p, grads_p = params, grads
        if dopt is not None:
            new_params_p, new_opt_state = dopt.step(params_p, opt_state, grads_p)
            if isinstance(dopt, DistributedOptimizer):
                # report the UNSCALED loss (pre-step scale — the one
                # micro_loss multiplied by; the post-step scale differs on
                # backoff/growth steps)
                loss = loss / dopt.current_scale(opt_state)
        else:
            updates, new_opt_state = tx.update(grads_p, opt_state, params_p)
            new_params_p = optax.apply_updates(params_p, updates)
        new_params = {"params": new_params_p, OWG: owg_new} if fp8_bundle else new_params_p
        if with_metrics:
            # out-of-band telemetry scalars (stripped by the wrapper below).
            # grad-norm is reported UNSCALED — grads under loss scaling carry
            # the scale factor, which is an implementation detail, not signal.
            gnorm = optax.global_norm(
                jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads_p)
            )
            if isinstance(dopt, DistributedOptimizer):
                gnorm = gnorm / dopt.current_scale(opt_state)
            tmetrics = {"grad_norm": gnorm}
            if isinstance(new_opt_state, dict) and "loss_scale" in new_opt_state:
                ls = new_opt_state["loss_scale"]
                tmetrics["loss_scale"] = ls["scale"]
                if "skip_count" in ls:
                    tmetrics["skip_count"] = ls["skip_count"]
            if has_aux:
                return new_params, new_opt_state, loss, aux, tmetrics
            return new_params, new_opt_state, loss, tmetrics
        if has_aux:
            return new_params, new_opt_state, loss, aux
        return new_params, new_opt_state, loss

    jitted = jax.jit(step, donate_argnums=(0, 1) if donate else ())

    # runtime profiler wiring (VERDICT r4 next #5): when ndtimeline is
    # initialized (``init_ndtimers`` or a trace session), every call emits a
    # TRAIN_STEP span (host region —
    # brackets dispatch; XLA's profiler owns on-device timing, and the
    # TraceAnnotation threads the span into its captures) and — with
    # ``auto_inc_step`` (default) — advances the global step counter, so a
    # loop using make_train_step must NOT also call inc_step() (or pass
    # auto_inc_step=False to keep manual control).  Un-initialized
    # profiler: ndtimeit is a nullcontext and nothing is recorded.
    from .ndtimeline import api as _nd
    from .ndtimeline.predefined import TRAIN_STEP

    from .telemetry import memtrack as _memtrack

    @functools.wraps(jitted)
    def timed_step(*args, **kwargs):
        t0 = time.perf_counter()
        try:
            with _nd.ndtimeit(TRAIN_STEP):
                out = jitted(*args, **kwargs)
        except BaseException as e:
            # OOM flight recorder (telemetry/memtrack.py): a
            # RESOURCE_EXHAUSTED at step 40k leaves a forensic bundle
            # (tagged census, device stats, last reports) instead of a bare
            # stack trace.  Gated — dormant runs pay this try frame only.
            _memtrack.maybe_dump_oom(e)
            raise
        # re-tag the donated/updated outputs: each jitted call returns FRESH
        # arrays, and without this the whole model would age into the
        # untagged bucket after one step (and trip the leak detector)
        _memtrack.tag_tree(out[0], "params")
        if len(out) > 1:
            _memtrack.tag_tree(out[1], "optimizer_state")
        if auto_inc_step and _nd.is_active():
            mgr = _nd.get_manager()
            g = _AUTO_STEP_GUARD
            if g["mgr"] is not mgr:  # manager re-init: restart tracking
                g["mgr"], g["step"] = mgr, None
            if not g["warned"] and g["step"] is not None and mgr.step > g["step"]:
                import warnings

                g["warned"] = True
                # a caller-contract misuse notice (fix the call site), not
                # a runtime health signal — stays a warn-once
                warnings.warn(  # vescale-lint: disable=VSC207
                    "make_train_step(auto_inc_step=True) advances the "
                    "ndtimeline step counter itself, but it was ALSO advanced "
                    "externally (manual inc_step() or flush(next_iteration="
                    "True)) within one training step — steps are being "
                    "double-counted.  Pass auto_inc_step=False to keep manual "
                    "control, or drop the manual increment.",
                    stacklevel=2,
                )
            mgr.inc_step()
            g["step"] = mgr.step
        if with_metrics:
            # the telemetry scalars ride as a trailing pytree; strip them
            # unconditionally so the public return shape never depends on
            # whether telemetry is live at CALL time
            tmetrics = out[-1]
            out = out[:-1]
        else:
            tmetrics = None
        if _tel.is_active():
            # host-fetching the loss forces this step's completion, so the
            # recorded time is true wall clock, not async dispatch time —
            # the observability trade a telemetry-on run opts into
            loss_val = float(out[2])
            dt = time.perf_counter() - t0
            rec: Dict[str, Any] = {"step_time_s": dt, "loss": loss_val}
            batch = args[2] if len(args) > 2 else kwargs.get("batch")
            leaf = batch.get("input") if isinstance(batch, dict) else None
            if leaf is not None and hasattr(leaf, "shape"):
                tokens = 1
                for s in leaf.shape:
                    tokens *= int(s)
                rec["tokens"] = tokens
                if dt > 0:
                    rec["tokens_per_sec"] = tokens / dt
            if tmetrics:
                rec.update({k: float(v) for k, v in tmetrics.items()})
            # default train rule pack (loss anomaly, grad-norm spike,
            # step-time regression, memory growth): armed lazily at the
            # first live step so late telemetry.init() still gets it;
            # arm_pack dedups by name (a set probe) on every later step
            from .telemetry import alerts as _alerts

            if _alerts.is_active():
                _alerts.get_engine().arm_pack("train", _alerts.train_rule_pack())
            _tel.record_step(rec)
        return out

    # keep the jit surface (lower/trace inspection) reachable
    timed_step.lower = jitted.lower
    if getattr(jitted, "trace", None) is not None:
        timed_step.trace = jitted.trace
    timed_step._jitted = jitted
    return timed_step


def make_eval_step(dmodel: DModule, loss_fn: Callable):
    def step(params, batch):
        out = dmodel.apply({"params": params}, batch["input"], deterministic=True)
        return loss_fn(out, batch)

    return jax.jit(step)
