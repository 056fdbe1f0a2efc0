"""Llama-2 4D finetune example (reference legacy/examples/llama2_4D_finetune/
llama_train.py): TP+SP+DP llama with ZeRO-2 optimizer and checkpointing.

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  python examples/llama2_4d_finetune/train.py --dp 2 --tp 4 --tiny --cpu
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
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-5)
    ap.add_argument("--tiny", action="store_true", help="tiny config (tests/CPU)")
    ap.add_argument("--save", type=str, default=None, help="checkpoint path")
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
    import vescale_tpu.checkpoint as ckpt
    from vescale_tpu.dmodule import parallelize_module
    from vescale_tpu.models.llama import LLAMA2_7B, Llama, LlamaConfig, llama_plan
    from vescale_tpu.models.nanogpt import cross_entropy_loss
    from vescale_tpu.parallel import DistributedOptimizer

    if args.tiny:
        cfg = LlamaConfig(
            vocab_size=32000,
            hidden_size=256,
            intermediate_size=512,
            num_hidden_layers=4,
            num_attention_heads=8,
            num_key_value_heads=4,
            max_position_embeddings=args.seq,
            dtype=jnp.float32 if args.cpu else jnp.bfloat16,
        )
    else:
        cfg = LLAMA2_7B

    mesh = vt.DeviceMesh(("dp", "tp"), (args.dp, args.tp))
    model = Llama(cfg)
    dm = parallelize_module(model, mesh, llama_plan(mesh))
    v = dm.init(jax.random.key(0), jnp.ones((2, args.seq), jnp.int32))
    params = v["params"]
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))
    print(f"mesh {dict(zip(mesh.mesh_dim_names, mesh.shape))}, params {n_params/1e6:.1f}M")

    pspecs = jax.tree_util.tree_map(lambda p: p.sharding.spec, params)
    dopt = DistributedOptimizer(optax.adamw(args.lr), mesh, pspecs, grad_clip=1.0)
    opt_state = dopt.init(params)

    @jax.jit
    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: cross_entropy_loss(dm.apply({"params": p}, batch["input"]), batch["target"])
        )(params)
        params, opt_state = dopt.step(params, opt_state, grads)
        return params, opt_state, loss

    for i in range(args.steps):
        toks = jax.random.randint(jax.random.key(100 + i), (args.batch, args.seq + 1), 0, cfg.vocab_size)
        batch = {"input": toks[:, :-1], "target": toks[:, 1:]}
        params, opt_state, loss = step(params, opt_state, batch)
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i}: loss {float(loss):.4f}")

    if args.save:
        ckpt.save(args.save, {"model": params, "optimizer": opt_state})
        print(f"checkpoint saved to {args.save}")


if __name__ == "__main__":
    main()
