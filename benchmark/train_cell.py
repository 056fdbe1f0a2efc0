"""A train cell: the README's assembly (``DeviceMesh`` ->
``parallelize_module(module, plan)`` of the configuration's model family ->
``dm.init`` -> ``adamw_lowmem`` [-> ``zero_sharded``] ->
``make_train_step(donate=True)``) stepping over batches that
``data/loader.py`` reads from a token file written from the seed.  The family's
own file (``benchmark/families/<model>.py``) gives the module, its plan, the
operations a token needs, the reference and the check's tolerances.
"""

from __future__ import annotations

import math
import os
import time
from typing import List

import numpy as np

from . import reference, stats, trafficgen
from .harness import CompileCounter, SessionTracer, Tracer, annotate, memory_in_use_bytes, memory_peak_bytes
from .record import RunRecord
from .spec import CellSpec, device_peaks

# the reference check's procedure; its two tolerances, with their reasons, are the family's
CHECK_POSITIONS = 8
# steps on fresh loader batches between the repeated-batch lead-in and the
# window's opening, so that nothing of the lead-in is still pending inside it
SETTLE_STEPS = 5


def _check_losses(losses: List[float], vocab: int) -> List[str]:
    """chip_smoke.check_losses: finite, first near ln(vocab), falling on a
    repeated batch."""
    problems = []
    if not all(math.isfinite(x) for x in losses):
        problems.append(f"non-finite loss in the lead-in: {losses}")
    elif abs(losses[0] - math.log(vocab)) > 1.0:
        problems.append(f"first loss {losses[0]:.3f} is not near ln({vocab}) = {math.log(vocab):.3f}")
    elif not losses[-1] < losses[0] - 0.5:
        problems.append(f"loss did not fall on the repeated batch: {losses}")
    return problems


def run_cell(spec: CellSpec, devices, seed: int, seconds: float, traced: int, setup_from: float):
    """The whole of a train cell's run: (record, correct, attempted, failed, notes).
    ``traced`` is the command's ``--trace``: 0, 1 (the window's last seconds
    under the profiler) or 2 (the window as 0 has it, then the loop runs on
    under the program's trace session)."""
    import jax
    import jax.numpy as jnp

    from vescale_tpu import telemetry
    from vescale_tpu.data import TokenDataLoader
    from vescale_tpu.dmodule import parallelize_module
    from vescale_tpu.mesh import DeviceMesh
    from vescale_tpu.models.nanogpt import cross_entropy_loss
    from vescale_tpu.ndtimeline import api as ndtimeline
    from vescale_tpu.parallel.optimizer import adamw_lowmem, zero_sharded
    from vescale_tpu.train import make_train_step

    c, t, traffic = spec.config, spec.config["train"], spec.traffic
    family = spec.family()
    if traffic["kind"] != "train_steps":
        raise trafficgen.TrafficError(f"a train cell takes train_steps traffic, not {traffic['kind']!r}")
    if (t["param_dtype"], t["compute_dtype"], t["optimizer"], t["moment_dtype"]) != (
            "float32", "bfloat16", "adamw_lowmem", "bfloat16"):
        raise ValueError("train cells run fp32 parameters, bf16 compute and adamw_lowmem with bf16 moments")
    dp, tp = int(t["mesh"]["dp"]), int(t["mesh"]["tp"])
    if dp * tp != spec.chips or len(devices) < spec.chips:
        raise ValueError(f"mesh dp {dp} x tp {tp} needs {dp * tp} chips; the cell has {spec.chips}, "
                         f"jax reports {len(devices)}")
    devices = list(devices[: spec.chips])
    T, B = int(traffic["seq_len"]), int(traffic["global_batch"])
    compiles = CompileCounter().install()
    loader = None
    try:
        # ---- set-up: corpus, model, optimizer, step
        mesh = DeviceMesh(("dp", "tp"), (dp, tp), devices=devices)
        system = family.build_train(c, t, mesh, T)
        os.makedirs(spec.out_dir(), exist_ok=True)
        tok_path = os.path.join(spec.out_dir(), f"tokens.{spec.name}.bin")
        trafficgen.write_token_file(tok_path, system.vocab, T, int(traffic["token_file_sequences"]), seed)
        loader = TokenDataLoader(tok_path, batch=B, seq_len=T, seed=seed % (1 << 31))
        dm = parallelize_module(system.module, mesh, system.plan)
        params = dm.init(jax.random.key(seed), jnp.ones((1, T), jnp.int32))["params"]
        tx = adamw_lowmem(float(traffic["learning_rate"]))
        if t["zero"]:
            pspecs = jax.tree_util.tree_map(lambda p: p.sharding.spec, params)
            tx = zero_sharded(tx, mesh, pspecs, dp_dims=("dp",))
        opt_state = tx.init(params)
        step = make_train_step(dm, tx, lambda lg, b: cross_entropy_loss(lg, b["target"]),
                               donate=True, with_metrics=False)

        rec = RunRecord(kind="train", chips=spec.chips, traffic_kind=traffic["kind"], device_kind=devices[0].device_kind,
                        tokens_per_step=B * T, flops_per_token=family.train_flops_per_token(c, T))
        if devices[0].platform == "tpu":
            rec.peak_flops_per_chip = device_peaks(rec.device_kind, spec.root)["bf16_flops_per_s"]

        # ---- lead-in on one repeated batch (the step's two compilations, and
        # a loss that must fall), then a few steps on fresh batches
        host = loader.next()
        batch = {k: jnp.asarray(v) for k, v in host.items()}
        lead = []
        for _ in range(max(3, int(traffic["lead_in_steps"]))):
            params, opt_state, loss = step(params, opt_state, batch)
            lead.append(float(jax.block_until_ready(loss)))
        problems = _check_losses(lead, system.vocab)
        for _ in range(SETTLE_STEPS):
            params, opt_state, loss = step(params, opt_state, {k: jnp.asarray(v) for k, v in loader.next().items()})
        jax.block_until_ready(loss)

        # ---- the window: it opens here and closes at the end of the last
        # step that began before ``seconds`` were over
        tracer = Tracer(spec, traced == 1, seconds)
        after = SessionTracer(spec) if traced == 2 else None
        sched_open = telemetry.host_sched_stats()    # two plain reads, one at each end; nothing runs between them
        w0 = time.perf_counter()
        closes = w0 + float(seconds)
        rec.setup_s = w0 - setup_from
        in_window = True
        while True:
            now = time.perf_counter()
            if in_window and now >= closes:
                # the window has closed: its numbers are taken here, before anything is traced
                in_window = False
                rec.host_sched = telemetry.host_sched_delta(sched_open, telemetry.host_sched_stats())
                tracer.maybe_stop(now, closes)
                rec.window = (w0, max(closes, rec.step_end[-1]))
                rec.memory_peak_bytes = memory_peak_bytes(devices)    # before the reference runs; a peak over the whole process
                in_use_at_close = memory_in_use_bytes(devices)
                rec.compile_times = list(compiles.times)
                program_tracing = {"ndtimeline": ndtimeline.is_active(), "telemetry": telemetry.is_active()}
                if after is None:
                    break
                after.begin()       # --trace 2: the loop runs on under the program's trace session
            elif not in_window and after.due(now):
                break
            tracer.maybe_start(now, closes)   # its start-up is not billed to the data wait
            t0 = time.perf_counter()
            with annotate("bm.data"):
                host = loader.next()
                batch = {k: jnp.asarray(v) for k, v in host.items()}
            t1 = time.perf_counter()
            with annotate("bm.step"):
                params, opt_state, loss = step(params, opt_state, batch)
                t_dispatched = time.perf_counter()
                loss = jax.block_until_ready(loss)
            t2 = time.perf_counter()
            with annotate("bm.host"):
                if in_window:
                    rec.dispatch_s.append(t_dispatched - t1)
                    rec.data_wait_s.append(t1 - t0)
                    rec.step_s.append(t2 - t0)
                    rec.step_end.append(t2)
                    rec.losses.append(float(loss))
                else:
                    rec.traced_steps.append((t0, t1, t_dispatched, t2))
                    float(loss)
        if after is not None:
            rec.session = after.end()
            rec.traced_window = (after.started, after.stopped)
            rec.trace = after.summary()
        else:
            rec.trace = tracer.summary()

        # ---- after the window, on the parameters the window left: the
        # reference's float32 loss and logits of one fresh batch against the
        # step's own loss (it is taken before the update) and the system's
        # forward
        host = loader.next()
        batch = {k: jnp.asarray(v) for k, v in host.items()}
        rows = sorted(int(r) for r in np.random.default_rng([int(seed), 6]).choice(T, CHECK_POSITIONS, replace=False))
        ref_loss, want = family.loss_and_logits(params, c, host["input"], host["target"], rows)
        forward = jax.jit(lambda p, x: dm.apply({"params": p}, x, deterministic=True, rngs=None)[0, np.asarray(rows)])
        got = np.asarray(forward(params, batch["input"]), np.float32)
        logits_err = reference.rel_at_scale(got, want)
        params, opt_state, loss = step(params, opt_state, batch)
        sys_loss = float(jax.block_until_ready(loss))
        loss_err = abs(sys_loss - ref_loss)
        if not loss_err <= family.TRAIN_LOSS_TOLERANCE:
            problems.append(f"loss {sys_loss:.5f} differs from the reference's {ref_loss:.5f} by {loss_err:.2e}")
        if not (np.isfinite(got).all() and logits_err <= family.TRAIN_LOGITS_TOLERANCE):
            problems.append(f"logits differ from the reference's by {logits_err:.2e} of its largest")
    finally:
        compiles.close()
        if loader is not None:
            loader.close()
    failed = sum(1 for l in rec.losses if not math.isfinite(l))
    correct = not problems and failed == 0 and rec.compiles_in_window() == 0
    slowest = sorted(((s_, i) for i, s_ in enumerate(rec.step_s)), reverse=True)[:3]
    # [index, whole step, of it waiting for data, of it inside the step's call before it returned]
    notes = {"slowest_steps_ms": [[i, round(s_ * 1e3, 2), round(rec.data_wait_s[i] * 1e3, 2),
                                   round(rec.dispatch_s[i] * 1e3, 2)] for s_, i in slowest],
             "lead_in_losses": lead, "reference_loss": ref_loss, "loss_abs_diff": loss_err,
             "loss_tolerance": family.TRAIN_LOSS_TOLERANCE, "logits_max_abs_diff_over_max": logits_err,
             "logits_tolerance": family.TRAIN_LOGITS_TOLERANCE, "logits_rows": rows, "problems": problems,
             "window_s": rec.window_s, "memory_peak_bytes": rec.memory_peak_bytes,
             "memory_in_use_bytes_at_close": in_use_at_close,
             "compiles_in_window": rec.compiles_in_window(),
             "window_losses_first_last": rec.losses[:1] + rec.losses[-1:],
             "host_sched_in_window": rec.host_sched, "program_tracing_in_window": program_tracing}
    if rec.traced_steps:    # what the session costs while it is on: the step under it against the window's
        notes["traced_step_ms_p50"] = stats.ms(stats.percentile([s_[3] - s_[0] for s_ in rec.traced_steps], 50))
        notes["window_step_ms_p50"] = stats.ms(stats.percentile(rec.step_s, 50))
        notes["session_counters"] = rec.session.counters
        notes["session_cost_s"] = after.cost_s
        notes["session_clock_offset_ns"] = rec.session.clock_offset_ns
    return rec, correct, len(rec.losses), failed, notes
