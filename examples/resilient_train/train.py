"""Resilient bf16 training example: crash-resume + dynamic loss scaling.

Demonstrates the round-4 recovery/mixed-precision surfaces together (the
MegaScale-style recovery recipe the reference's checkpoint README
describes, legacy/vescale/checkpoint/README.md:37-49):

  * ``CheckpointManager`` — step-named saves, keep-K rotation, resume from
    the newest COMMITTED checkpoint (torn saves are invisible);
  * fire-and-forget async saves (training never blocks on io; chunk writes
    ride the native C++ pool when available);
  * ``DistributedOptimizer(loss_scale="dynamic")`` — found-inf detection
    with bitwise skip-step and scale backoff for bf16 training.

Kill it mid-run and start it again: it continues from the last committed
step.

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  python examples/resilient_train/train.py --steps 40 --save-every 10
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dp", type=int, default=2)
    ap.add_argument("--tp", type=int, default=4)
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--save-every", type=int, default=10)
    ap.add_argument("--keep", type=int, default=3)
    ap.add_argument("--ckpt-dir", type=str, default="/tmp/vescale_tpu_resilient_ckpts")
    ap.add_argument("--crash-at", type=int, default=None,
                    help="simulate a crash (os._exit) after this step")
    args = ap.parse_args()

    import jax

    from vescale_tpu.compile_cache import use_compile_cache

    use_compile_cache()
    if not jax.config.jax_threefry_partitionable:
        jax.config.update("jax_threefry_partitionable", True)
    import jax.numpy as jnp
    import optax

    import vescale_tpu as vt
    from vescale_tpu.checkpoint import CheckpointManager
    from vescale_tpu.dmodule import parallelize_module
    from vescale_tpu.models.nanogpt import GPT, GPTConfig, cross_entropy_loss, nanogpt_plan
    from vescale_tpu.parallel import DistributedOptimizer

    mesh = vt.DeviceMesh(("dp", "tp"), (args.dp, args.tp))
    cfg = GPTConfig(block_size=128, vocab_size=512, n_layer=4, n_head=8,
                    n_embd=256, dropout=0.0, dtype=jnp.bfloat16)
    dm = parallelize_module(GPT(cfg), mesh, nanogpt_plan(mesh))
    idx0 = jnp.ones((2, cfg.block_size), jnp.int32)
    variables = dm.init(jax.random.key(0), idx0)
    params = variables["params"]
    pspecs = jax.tree_util.tree_map(lambda p: p.sharding.spec, params)

    dopt = DistributedOptimizer(
        optax.adamw(3e-4), mesh, pspecs, grad_clip=1.0, loss_scale="dynamic"
    )
    opt_state = dopt.init(params)

    mgr = CheckpointManager(args.ckpt_dir, keep=args.keep)
    start = 0
    latest = mgr.latest_step()
    if latest is not None:
        restored = mgr.restore({"model": params, "optimizer": opt_state})
        params, opt_state = restored["model"], restored["optimizer"]
        start = latest + 1
        print(f"[resume] continuing from committed step {latest}")

    @jax.jit
    def step_fn(params, opt_state, batch):
        def lf(p):
            logits = dm.apply({"params": p}, batch["input"])
            return dopt.scale_loss(cross_entropy_loss(logits, batch["target"]), opt_state)

        loss, grads = jax.value_and_grad(lf)(params)
        # unscale with the PRE-step scale (the one lf multiplied by) — the
        # post-step scale differs on backoff/growth steps
        unscaled = loss / dopt.current_scale(opt_state)
        params, opt_state = dopt.step(params, opt_state, grads)
        return params, opt_state, unscaled

    # optional hang watchdog (VESCALE_WATCHDOG_TIMEOUT=30 arms it): a step
    # that stops making progress dumps all-thread stacks and aborts so a
    # supervisor restart resumes from the last committed step — see
    # docs/resilience.md "Multi-host: coordinated recovery"
    from vescale_tpu.resilience import Watchdog

    wd = Watchdog.from_env()
    if wd is not None:
        wd.start()

    rng = np.random.default_rng(0)
    handle = None
    for i in range(start, args.steps):
        if wd is not None:
            wd.beat(i)
        toks = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (args.dp * 4, cfg.block_size + 1)), jnp.int32
        )
        batch = {"input": toks[:, :-1], "target": toks[:, 1:]}
        params, opt_state, loss = step_fn(params, opt_state, batch)
        scale = float(dopt.current_scale(opt_state))
        print(f"step {i:4d}  loss {float(loss):.4f}  loss_scale {scale:.0f}")
        if i % args.save_every == 0 or i == args.steps - 1:
            # fire-and-forget: training continues while chunks write
            handle = mgr.save(i, {"model": params, "optimizer": opt_state}, async_checkpoint=True)
        if args.crash_at is not None and i == args.crash_at:
            print(f"[crash] simulating SIGKILL at step {i}")
            os._exit(137)
    if handle is not None:
        handle.wait()  # only the LAST save is worth blocking the exit for
    if wd is not None:
        wd.stop()
    print(f"done; latest committed checkpoint: step {mgr.latest_step()}")


if __name__ == "__main__":
    main()
