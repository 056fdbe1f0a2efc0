"""nanoGPT 4D training example.

Mirrors the reference recipe (legacy/examples/nanogpt_4D_finetune/
finetune_4D.py): "zero model change" — the single-device model + a sharding
plan + the framework wrappers.  Runs on any device count (virtual CPU mesh
included):

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  python examples/nanogpt_4d_finetune/train.py --dp 2 --tp 4 --steps 20

With --data pointing at a nanoGPT-style .bin token file the native C++
loader feeds batches; otherwise a synthetic stream is used.
"""

from __future__ import annotations

import argparse
import os
import sys

# allow running straight from a repo checkout
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))
import time

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dp", type=int, default=2)
    ap.add_argument("--tp", type=int, default=4)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--n-layer", type=int, default=4)
    ap.add_argument("--n-embd", type=int, default=256)
    ap.add_argument("--n-head", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--grad-clip", type=float, default=1.0)
    ap.add_argument("--data", type=str, default=None, help="token .bin file")
    ap.add_argument("--zero2", action="store_true", help="use DistributedOptimizer")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    from vescale_tpu.compile_cache import use_compile_cache

    use_compile_cache()
    import jax.numpy as jnp
    import optax

    import vescale_tpu as vt
    from vescale_tpu.dmodule import parallelize_module
    from vescale_tpu.models.nanogpt import GPT, GPTConfig, cross_entropy_loss, nanogpt_plan
    from vescale_tpu.parallel import DistributedOptimizer
    from vescale_tpu.train import make_train_step
    from vescale_tpu.ndtimeline import init_ndtimers, ndtimeit, flush, LoggingHandler

    mesh = vt.DeviceMesh(("dp", "tp"), (args.dp, args.tp))
    cfg = GPTConfig(
        block_size=args.seq,
        vocab_size=50304,
        n_layer=args.n_layer,
        n_head=args.n_head,
        n_embd=args.n_embd,
        dropout=0.0,
    )
    model = GPT(cfg)
    dm = parallelize_module(model, mesh, nanogpt_plan(mesh))
    variables = dm.init(jax.random.key(0), jnp.ones((2, args.seq), jnp.int32))
    params = variables["params"]
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))
    print(f"mesh {dict(zip(mesh.mesh_dim_names, mesh.shape))}, params {n_params/1e6:.1f}M")

    if args.zero2:
        pspecs = jax.tree_util.tree_map(lambda p: p.sharding.spec, params)
        dopt = DistributedOptimizer(optax.adamw(args.lr), mesh, pspecs, grad_clip=args.grad_clip)
        opt_state = dopt.init(params)

        @jax.jit
        def step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(
                lambda p: cross_entropy_loss(dm.apply({"params": p}, batch["input"]), batch["target"])
            )(params)
            params, opt_state = dopt.step(params, opt_state, grads)
            return params, opt_state, loss

    else:
        tx = optax.chain(optax.clip_by_global_norm(args.grad_clip), optax.adamw(args.lr))
        opt_state = tx.init(params)
        step = make_train_step(
            dm, tx, lambda lg, b: cross_entropy_loss(lg, b["target"]), donate=False
        )

    if args.data:
        from vescale_tpu.data import TokenDataLoader

        loader = TokenDataLoader(args.data, batch=args.batch, seq_len=args.seq, seed=0)
        get_batch = lambda i: loader.next()
    else:
        def get_batch(i):
            toks = jax.random.randint(jax.random.key(100 + i), (args.batch, args.seq + 1), 0, cfg.vocab_size)
            return {"input": toks[:, :-1], "target": toks[:, 1:]}

    init_ndtimers(handlers=[LoggingHandler(lambda m: None)])
    t0 = time.time()
    for i in range(args.steps):
        with ndtimeit("train-step"):
            batch = get_batch(i)
            params, opt_state, loss = step(params, opt_state, batch)
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i}: loss {float(loss):.4f}")
    flush()
    dt = time.time() - t0
    print(f"{args.steps} steps in {dt:.1f}s ({args.steps * args.batch * args.seq / dt:.0f} tok/s)")


if __name__ == "__main__":
    main()
