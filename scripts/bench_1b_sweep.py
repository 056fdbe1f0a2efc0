"""Sweep 1B-class llama bench configs on the real chip (scratch tool, not
the driver bench).  Usage: python scripts/bench_1b_sweep.py <variant>."""

import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

sys.path.insert(0, ".")


def run(variant: str):
    import optax

    from vescale_tpu.compile_cache import use_compile_cache
    from vescale_tpu.mesh import DeviceMesh
    from vescale_tpu.dmodule import parallelize_module
    from vescale_tpu.models.llama import Llama, LlamaConfig, llama_plan
    from vescale_tpu.models.nanogpt import cross_entropy_loss
    from vescale_tpu.parallel.optimizer import adamw_lowmem
    from vescale_tpu.telemetry.calibrate import device_peak_flops
    from vescale_tpu.train import make_train_step

    use_compile_cache()
    T = 4096
    base = dict(
        vocab_size=32000,
        hidden_size=2048,
        intermediate_size=5632,
        num_hidden_layers=24,
        num_attention_heads=16,
        num_key_value_heads=8,
        max_position_embeddings=T,
        dtype=jnp.bfloat16,
        use_flash_attention=True,
    )
    variants = {
        # (B, cfg extras)
        "full_remat_b2": (2, dict(remat=True)),
        "full_remat_b4": (4, dict(remat=True)),
        "dots_b1": (1, dict(remat=True, remat_policy="dots_saveable")),
        "dots_nobatch_b2": (2, dict(remat=True, remat_policy="dots_with_no_batch_dims_saveable")),
        "noremat_b1": (1, dict()),
        "mlpremat_b1": (1, dict(remat=True, remat_scope="mlp")),
        "mlpremat_b2": (2, dict(remat=True, remat_scope="mlp")),
        # 2B-class rung: muon's single bf16 momentum + bf16-moment adam
        # fallback halves optimizer state vs fp32 adam (params stay fp32
        # flax default, so ~2B is the ceiling on a 16 GB chip)
        "muon2b_b1": (1, dict(
            hidden_size=2304, intermediate_size=6144, num_hidden_layers=30,
            num_attention_heads=18, num_key_value_heads=9, remat=True,
        )),
    }
    B, extra = variants[variant]
    cfg = LlamaConfig(**{**base, **extra})

    devices = jax.devices()
    mesh = DeviceMesh(("dp", "tp"), (1, 1), devices=devices[:1])
    dm = parallelize_module(Llama(cfg), mesh, llama_plan(mesh, sequence_parallel=False))
    params = dm.init(jax.random.key(0), jnp.ones((1, T), jnp.int32))["params"]
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))
    print(f"{variant}: params={n_params/1e9:.3f}B  B={B}", flush=True)
    if variant.startswith("muon"):
        from vescale_tpu.parallel.optimizer import muon

        tx = muon(0.02, fallback=adamw_lowmem(3e-4), state_dtype=jnp.bfloat16)
    else:
        tx = adamw_lowmem(3e-4)
    opt_state = tx.init(params)
    step = make_train_step(dm, tx, lambda lg, b: cross_entropy_loss(lg, b["target"]), donate=True)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab_size, (B, T + 1)), jnp.int32)
    batch = {"input": toks[:, :-1], "target": toks[:, 1:]}

    for _ in range(3):
        params, opt_state, loss = step(params, opt_state, batch)
    jax.block_until_ready(loss)
    iters = 10
    t0 = time.perf_counter()
    for _ in range(iters):
        params, opt_state, loss = step(params, opt_state, batch)
    jax.block_until_ready(loss)
    dt = (time.perf_counter() - t0) / iters
    flops_per_token = 6.0 * n_params + 12.0 * cfg.num_hidden_layers * T * cfg.hidden_size
    mfu = flops_per_token * B * T / dt / device_peak_flops(devices[0])
    print(
        f"{variant}: step={dt*1e3:.1f}ms  tok/s={B*T/dt:.0f}  MFU={mfu:.4f}",
        flush=True,
    )


if __name__ == "__main__":
    run(sys.argv[1])
