"""OpenLlama 4D benchmark runner (reference legacy/examples/
open_llama_4D_benchmark/run_open_llama_w_vescale.py): dp x tp (+SP) llama
with optional HF checkpoint load, timed train steps, MFU report via
llama_mfu_calculator.

  # tiny smoke on a virtual 8-device CPU mesh
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  python examples/open_llama_4d_benchmark/run_open_llama.py --dp 2 --tp 4 --tiny --cpu

  # open_llama-3b on real chips (random init unless --hf-ckpt points at
  # a local HF pytorch/safetensors checkpoint — this image has no egress,
  # so there is no downloader; the reference's download_open_llama_ckpt.py
  # role is served by pointing --hf-ckpt at a pre-fetched dir)
  python examples/open_llama_4d_benchmark/run_open_llama.py --dp 1 --tp 1 --bf16 --remat
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

from llama_mfu_calculator import llama_flops_per_token, mfu


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=2, help="per-dp-rank microbatch")
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--lr", type=float, default=3e-5)
    ap.add_argument("--tiny", action="store_true", help="tiny config (tests/CPU)")
    ap.add_argument("--bf16", action="store_true")
    ap.add_argument("--remat", action="store_true", help="checkpoint each block")
    ap.add_argument("--no-sp", action="store_true", help="disable sequence parallel")
    ap.add_argument("--hf-ckpt", type=str, default=None, help="local HF checkpoint dir")
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--peak-flops", type=float, default=None,
                    help="chip peak bf16 FLOP/s for MFU (default: the device's "
                         "row of telemetry.calibrate.DEVICE_PEAKS)")
    args = ap.parse_args()

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    from vescale_tpu.compile_cache import use_compile_cache

    use_compile_cache()
    peak = args.peak_flops
    if peak is None and not args.cpu:
        from vescale_tpu.telemetry.calibrate import device_peak_flops

        peak = device_peak_flops(jax.devices()[0])  # an unknown device fails here, before the run
    import jax.numpy as jnp
    import optax

    import vescale_tpu as vt
    from vescale_tpu.dmodule import parallelize_module
    from vescale_tpu.models.llama import OPEN_LLAMA_3B, Llama, LlamaConfig, llama_plan
    from vescale_tpu.models.nanogpt import cross_entropy_loss
    from vescale_tpu.parallel.optimizer import adamw_lowmem, zero_sharded
    from vescale_tpu.train import make_train_step

    dtype = jnp.bfloat16 if args.bf16 else jnp.float32
    if args.tiny:
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=256, intermediate_size=512,
            num_hidden_layers=4, num_attention_heads=8, num_key_value_heads=4,
            max_position_embeddings=args.seq, dtype=dtype, remat=args.remat,
        )
    else:
        import dataclasses

        cfg = dataclasses.replace(
            OPEN_LLAMA_3B,
            max_position_embeddings=args.seq,
            dtype=dtype,
            remat=args.remat,
            use_flash_attention=True,
        )

    mesh = vt.DeviceMesh(("dp", "tp"), (args.dp, args.tp))
    dm = parallelize_module(Llama(cfg), mesh, llama_plan(mesh, sequence_parallel=not args.no_sp))
    params = dm.init(jax.random.key(0), jnp.ones((2, args.seq), jnp.int32))["params"]
    if args.hf_ckpt:
        from vescale_tpu.models.convert import load_hf_llama

        loaded = load_hf_llama(args.hf_ckpt, cfg)
        params = jax.tree_util.tree_map(
            lambda init, new: jax.device_put(jnp.asarray(new, init.dtype), init.sharding),
            params, loaded,
        )
        print(f"loaded HF checkpoint from {args.hf_ckpt}")
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))
    print(f"mesh {dict(zip(mesh.mesh_dim_names, mesh.shape))}, params {n_params/1e6:.1f}M")

    pspecs = jax.tree_util.tree_map(lambda p: p.sharding.spec, params)
    tx = zero_sharded(adamw_lowmem(args.lr), mesh, pspecs, dp_dims=("dp",))
    opt_state = tx.init(params)
    step = make_train_step(dm, tx, lambda lg, b: cross_entropy_loss(lg, b["target"]), donate=True)

    B = args.batch * args.dp
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, args.seq + 1)), jnp.int32)
    batch = {"input": toks[:, :-1], "target": toks[:, 1:]}

    key = jax.random.key(1)
    for _ in range(2):  # warmup/compile
        params, opt_state, loss = step(params, opt_state, batch, key)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for i in range(args.steps):
        params, opt_state, loss = step(params, opt_state, batch, key)
    jax.block_until_ready(loss)
    dt = (time.perf_counter() - t0) / args.steps

    n_chips = args.dp * args.tp
    tok_s_chip = B * args.seq / dt / n_chips
    fpt = llama_flops_per_token(
        cfg.hidden_size, cfg.intermediate_size, cfg.num_hidden_layers,
        cfg.vocab_size, args.seq, cfg.num_key_value_heads / cfg.num_attention_heads,
    )
    mfu_txt = f"{mfu(tok_s_chip, fpt, peak):.4f}" if peak else "n/a (the CPU has no device peak)"
    print(
        f"step {dt*1e3:.1f} ms  tokens/sec/chip {tok_s_chip:.0f}  "
        f"MFU {mfu_txt}  (loss {float(loss):.4f})"
    )


if __name__ == "__main__":
    main()
