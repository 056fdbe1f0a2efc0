"""fp8 (delayed-scaling) Llama training example (round 5; SURVEY.md:17
new-gen quantized-training scope).

Demonstrates the MODULE path: ``LlamaConfig(use_fp8=True)`` adds an
``_overwrite_with_gradient`` variable collection (per-matmul amax
histories + scales); pass the two-collection bundle to ``make_train_step``
and everything else — DistributedOptimizer dynamic loss scaling, grad
accumulation, checkpointing the bundle — just composes.  The functional
path for custom loops is ``vescale_tpu.quant.fp8_dot`` (see
docs/parallel_overview.md).

Run (CPU demo):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/fp8_train/train_fp8.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax

# demo-safe default: run on CPU unless explicitly asked for the real chip
from vescale_tpu.analysis import envreg  # noqa: E402

if not envreg.get_bool("VESCALE_FP8_ON_TPU"):
    jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp
import numpy as np
import optax

import vescale_tpu as vt
from vescale_tpu.dmodule import parallelize_module
from vescale_tpu.models.llama import Llama, LlamaConfig, llama_plan
from vescale_tpu.models.nanogpt import cross_entropy_loss
from vescale_tpu.parallel.optimizer import DistributedOptimizer
from vescale_tpu.train import make_train_step

OWG = "_overwrite_with_gradient"


def main():
    n = len(jax.devices())
    tp = 2 if n % 2 == 0 else 1
    mesh = vt.DeviceMesh(("dp", "tp"), (n // tp, tp))
    on_tpu = jax.devices()[0].platform == "tpu"

    cfg = LlamaConfig(
        vocab_size=256,
        hidden_size=128,
        intermediate_size=256,
        num_hidden_layers=4,
        num_attention_heads=8,
        num_key_value_heads=4,
        max_position_embeddings=128,
        dtype=jnp.bfloat16 if on_tpu else jnp.float32,
        use_flash_attention=on_tpu,
        use_fp8=True,
    )
    dm = parallelize_module(Llama(cfg), mesh, llama_plan(mesh))
    variables = dm.init(jax.random.key(0), jnp.ones((2, 64), jnp.int32))
    bundle = {"params": variables["params"], OWG: variables[OWG]}

    pspecs = jax.tree_util.tree_map(lambda p: p.sharding.spec, variables["params"])
    dopt = DistributedOptimizer(
        optax.adamw(3e-4), mesh, pspecs, loss_scale="dynamic", init_scale=2.0**10
    )
    opt_state = dopt.init(variables["params"])  # optimizer sees params ONLY

    step = make_train_step(
        dm, dopt, lambda lg, b: cross_entropy_loss(lg, b["target"]), donate=False
    )

    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 65)), jnp.int32)
    batch = {"input": toks[:, :-1], "target": toks[:, 1:]}

    for i in range(10):
        bundle, opt_state, loss = step(bundle, opt_state, batch)
        if i % 2 == 0:
            scale = float(dopt.current_scale(opt_state))
            print(f"step {i}: loss {float(loss):.4f}  loss_scale {scale:g}")

    # the delayed-scaling state advanced with training
    amax0 = jax.tree_util.tree_leaves(bundle[OWG])[0]
    print("fp8 amax history head:", np.asarray(amax0)[:3])
    print("done")


if __name__ == "__main__":
    main()
