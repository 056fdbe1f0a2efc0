"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once on a TPU, through the entry points a user calls,
at the full widths of ``LLAMA2_7B`` (hidden 4096, FFN 11008, 32 heads of 128,
vocab 32000; depth cut to fit one 16 GB chip, weights random from ``--seed``):

  train    DeviceMesh -> parallelize_module(Llama, llama_plan) -> dm.init ->
           adamw_lowmem -> make_train_step(donate=True), batches read by
           TokenDataLoader from a token file written here; then the same
           step under VESCALE_KERNELS=on (fused_adamw compiled) against the
           default program's parameters and moments.
  kernels  the four Pallas kernels against their XLA legs, outside any timing.
  serve    ServeEngine + PagedKVCache + ContinuousBatchingScheduler +
           run_serve_resilient answering a dozen requests, once per kernel
           mode, and the two engines' decode logits compared.

``--chips 4`` runs only the sharded path and what it is compared with: the
same model on a ("dp","tp") = (2,2) mesh (sequence-parallel plan, ZeRO over
dp) against a one-device mesh in the same process.

One process; it starts no child that needs the chip.  It needs a TPU: without
one it exits non-zero and prints no result.  The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``;
everything worth reading (compile seconds, step and request times, peak
bytes, kernel-vs-XLA differences, the request ledger) is on earlier lines.
These are smoke output, not a benchmark.

``--rehearse`` is for the sandbox, which has no chip: tiny widths, kernels in
the Pallas interpreter, any backend.  It checks paths and control flow only,
prints no result line and never exits 0.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import math
import os
import sys
import tempfile
import time
import traceback

import numpy as np


def log(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


@contextlib.contextmanager
def kernels_mode(mode: str):
    """VESCALE_KERNELS for everything traced or built inside the block (the
    mode is latched per trace / per engine build)."""
    from vescale_tpu.analysis import envreg

    prev = envreg.get_raw("VESCALE_KERNELS")
    os.environ["VESCALE_KERNELS"] = mode
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop("VESCALE_KERNELS", None)
        else:
            os.environ["VESCALE_KERNELS"] = prev


def kernel_counters() -> dict:
    """The kernel dispatch/fallback counters.  They count only while the
    telemetry registry is up, so the first call brings it up (in memory) —
    after the train phase's timed steps, which run with telemetry dormant."""
    from vescale_tpu import telemetry

    if not telemetry.is_active():
        telemetry.init(out_dir=None, memtrack=False, timeseries=False, alerts=False, costaudit=False)
    counters = telemetry.get_registry().snapshot()["counters"]
    return {k: int(v) for k, v in counters.items() if k.startswith("kernel_")}


def check_no_fallback(phase: str, before: dict, must_dispatch: str) -> None:
    """On the chip a counted kernel fallback is a failure, and the kernel
    that was asked for must have been dispatched."""
    now = kernel_counters()
    delta = {k: v - before.get(k, 0) for k, v in now.items() if v != before.get(k, 0)}
    log(phase, kernel_counters=delta)
    fallbacks = {k: v for k, v in delta.items() if k.startswith("kernel_fallback")}
    if fallbacks:
        raise RuntimeError(f"{phase}: kernel fallback counted: {fallbacks}")
    if delta.get(f"kernel_dispatch_{must_dispatch}_total", 0) < 1:
        raise RuntimeError(f"{phase}: {must_dispatch} was asked for and not dispatched")


def model_config(sizes, layers):
    import jax.numpy as jnp

    from vescale_tpu.models.llama import LLAMA2_7B, LlamaConfig

    if sizes.tiny:
        return LlamaConfig(
            vocab_size=512, hidden_size=128, intermediate_size=256,
            num_hidden_layers=layers, num_attention_heads=4, num_key_value_heads=4,
            max_position_embeddings=sizes.seq, dtype=jnp.float32,
        )
    # published widths; only depth is cut, and the context is the run's seq
    return dataclasses.replace(
        LLAMA2_7B, num_hidden_layers=layers, max_position_embeddings=sizes.seq
    )


@dataclasses.dataclass(frozen=True)
class Sizes:
    tiny: bool
    seq: int             # train sequence length (one chip)
    layers: int
    warmup: int
    steps: int
    serve_slots: int
    serve_page: int
    serve_pages_per_slot: int
    prompt_lens: tuple   # (lo, hi) of the served prompts
    new_tokens: int
    requests: int


# layers=4: what fits one 16 GB chip at the published widths with room for the
# VESCALE_KERNELS=on program (5 compile, at 10.19 + 4.21 GB, but leave it none)
REAL = Sizes(tiny=False, seq=4096, layers=4, warmup=3, steps=6,
             serve_slots=16, serve_page=16, serve_pages_per_slot=128,
             prompt_lens=(128, 1024), new_tokens=32, requests=12)
TINY = Sizes(tiny=True, seq=128, layers=2, warmup=1, steps=5,
             serve_slots=4, serve_page=8, serve_pages_per_slot=8,
             prompt_lens=(4, 24), new_tokens=4, requests=6)


# ===================================================================== train
def write_token_file(path: str, vocab: int, n_tokens: int, seed: int) -> None:
    rng = np.random.default_rng(seed)
    rng.integers(0, vocab, n_tokens, dtype=np.uint16).tofile(path)


def build_trainer(cfg, mesh, *, seed, lr, sequence_parallel, zero):
    """The README quick-start assembly (run_open_llama)."""
    import jax
    import jax.numpy as jnp

    from vescale_tpu.dmodule import parallelize_module
    from vescale_tpu.models.llama import Llama, llama_plan
    from vescale_tpu.models.nanogpt import cross_entropy_loss
    from vescale_tpu.parallel.optimizer import adamw_lowmem, zero_sharded
    from vescale_tpu.train import make_train_step

    dm = parallelize_module(Llama(cfg), mesh, llama_plan(mesh, sequence_parallel=sequence_parallel))
    params = dm.init(jax.random.key(seed), jnp.ones((1, cfg.max_position_embeddings), jnp.int32))["params"]
    tx = adamw_lowmem(lr)
    if zero:
        pspecs = jax.tree_util.tree_map(lambda p: p.sharding.spec, params)
        tx = zero_sharded(tx, mesh, pspecs, dp_dims=("dp",))

    def make_step():
        # with_metrics=False: the step stays the plain program even while the
        # telemetry registry is up to count kernel dispatches
        return make_train_step(
            dm, tx, lambda lg, b: cross_entropy_loss(lg, b["target"]),
            donate=True, with_metrics=False,
        )

    return params, tx, make_step


def run_steps(step, params, opt_state, batch, n):
    """n steps, each timed around block_until_ready; returns the new state,
    the losses and the step seconds."""
    import jax

    losses, secs = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, batch)
        jax.block_until_ready(loss)
        secs.append(time.perf_counter() - t0)
        losses.append(float(loss))
    return params, opt_state, losses, secs


def check_losses(phase: str, losses, vocab: int) -> None:
    if not all(math.isfinite(x) for x in losses):
        raise RuntimeError(f"{phase}: non-finite loss {losses}")
    # random weights predict near-uniformly: the first loss is ln(vocab) plus
    # half the variance of the logits, which the init keeps below 1
    if abs(losses[0] - math.log(vocab)) > 1.0:
        raise RuntimeError(f"{phase}: first loss {losses[0]:.3f} is not near ln({vocab}) = {math.log(vocab):.3f}")
    # the batch repeats, so Adam memorises it: the loss must fall
    if not losses[-1] < losses[0] - 0.5:
        raise RuntimeError(f"{phase}: loss did not fall on a repeated batch: {losses}")


def phase_train(cfg, devices, sizes, args, on_tpu):
    import jax
    import jax.numpy as jnp

    from vescale_tpu.data import TokenDataLoader
    from vescale_tpu.mesh import DeviceMesh

    T, lr = sizes.seq, 3e-4
    dev = devices[0]

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        tok_path = os.path.join(tmp, "tokens.bin")
        write_token_file(tok_path, cfg.vocab_size, 64 * (T + 1), args.seed)
        t0 = time.perf_counter()
        loader = TokenDataLoader(tok_path, batch=1, seq_len=T, seed=args.seed)  # builds the native .so
        host_batch = loader.next()
        log("train", loader_build_and_first_batch_s=round(time.perf_counter() - t0, 2),
            tokens_in_file=loader.num_tokens)
        loader.close()
    if not (host_batch["input"][:, 1:] == host_batch["target"][:, :-1]).all():
        raise RuntimeError("train: loader targets are not the inputs shifted by one")
    if not (0 <= host_batch["input"].min() and host_batch["input"].max() < cfg.vocab_size):
        raise RuntimeError("train: loader returned a token outside the vocabulary")
    batch = {k: jnp.asarray(v) for k, v in host_batch.items()}

    mesh = DeviceMesh(("dp", "tp"), (1, 1), devices=devices[:1])
    params, tx, make_step = build_trainer(
        cfg, mesh, seed=args.seed, lr=lr, sequence_parallel=False, zero=False)
    opt_state = tx.init(params)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))
    log("train", model="LLAMA2_7B widths", hidden=cfg.hidden_size, ffn=cfg.intermediate_size,
        heads=cfg.num_attention_heads, kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        vocab=cfg.vocab_size, layers=cfg.num_hidden_layers, seq=T, batch=1,
        dtype=jnp.dtype(cfg.dtype).name, params=n_params)

    step = make_step()
    t0 = time.perf_counter()
    compiled = step.lower(params, opt_state, batch).compile()
    compile_s = time.perf_counter() - t0
    text = compiled.as_text()
    n_kernel_calls = text.count('custom_call_target="tpu_custom_call"')
    mem = compiled.memory_analysis()
    log("train", compile_s=round(compile_s, 2), tpu_custom_calls=n_kernel_calls,
        argument_bytes=getattr(mem, "argument_size_in_bytes", None),
        temp_bytes=getattr(mem, "temp_size_in_bytes", None))
    if on_tpu and n_kernel_calls < 3 * cfg.num_hidden_layers:
        # flash fwd, dq and dk/dv per layer
        raise RuntimeError(
            f"train: {n_kernel_calls} tpu_custom_call in the compiled step, expected at least "
            f"{3 * cfg.num_hidden_layers}: the flash kernel is not in it")

    params, opt_state, warm_losses, warm_s = run_steps(step, params, opt_state, batch, sizes.warmup)
    log("train", warmup_step_s=[round(x, 2) for x in warm_s],
        note="call 1 is jit's own compile (served by the cache); call 2 compiles again: "
             "the state comes back with other shardings than tx.init gave it")
    params, opt_state, losses, secs = run_steps(step, params, opt_state, batch, sizes.steps)
    all_losses = warm_losses + losses
    log("train", losses=[round(x, 4) for x in all_losses])
    log("train", step_ms=[round(s * 1e3, 2) for s in secs],
        median_step_ms=round(float(np.median(secs)) * 1e3, 2),
        tokens_per_s=round(T / float(np.median(secs)), 1))
    check_losses("train", all_losses, cfg.vocab_size)
    stats = dev.memory_stats() or {}
    log("train", peak_bytes_in_use=stats.get("peak_bytes_in_use"), bytes_limit=stats.get("bytes_limit"))

    # ---- the same step with fused_adamw compiled, against the default program
    before = kernel_counters()
    shardings = jax.tree_util.tree_map(lambda x: x.sharding, (params, opt_state))
    host_state = jax.device_get((params, opt_state))
    p_off, s_off, loss_off = step(params, opt_state, batch)
    host_off = jax.device_get((p_off, s_off))
    loss_off = float(loss_off)
    del p_off, s_off, params, opt_state
    with kernels_mode("on" if on_tpu else "interpret"):
        step_on = make_step()
        params, opt_state = jax.device_put(host_state, shardings)
        del host_state
        t0 = time.perf_counter()
        p_on, s_on, loss_on = step_on(params, opt_state, batch)
        jax.block_until_ready(loss_on)
        log("train", kernels_on_first_call_s=round(time.perf_counter() - t0, 2))
    check_no_fallback("train kernels=on", before, "fused_adamw")
    host_on = jax.device_get((p_on, s_on))
    loss_on = float(loss_on)
    del p_on, s_on, params, opt_state

    # parameters, in units of lr (an update is O(1) in them): the largest and
    # the mean difference
    p_max = p_sum = 0.0
    p_n = 0
    for a, b in zip(jax.tree_util.tree_leaves(host_on[0]), jax.tree_util.tree_leaves(host_off[0])):
        d = np.abs(a.astype(np.float64) - b.astype(np.float64))
        p_max = max(p_max, float(d.max()))
        p_sum += float(d.sum())
        p_n += d.size
    # moments (bf16): elements that differ at all, and by how much at the leaf's scale
    m_differing = m_total = 0
    m_at_scale = 0.0
    for a, b in zip(jax.tree_util.tree_leaves(host_on[1]), jax.tree_util.tree_leaves(host_off[1])):
        if a.ndim == 0:  # the step count
            continue
        a32, b32 = a.astype(np.float32), b.astype(np.float32)
        m_differing += int((a32 != b32).sum())
        m_total += a.size
        m_at_scale = max(m_at_scale, bf16_ulps_at_scale(a32, b32))
    log("train kernels=on", loss_off=loss_off, loss_on=loss_on,
        param_max_abs_diff_over_lr=p_max / lr, param_mean_abs_diff_over_lr=p_sum / p_n / lr,
        moment_fraction_differing=m_differing / max(m_total, 1),
        moment_max_diff_bf16_steps_at_scale=m_at_scale)
    # Tolerances.  The two steps are two compilations, and XLA fuses the bf16
    # forward and backward differently around a different optimizer tail
    # (first chip run: the losses already differ, 1.68614 against 1.68617),
    # so the optimizer legs do not see the same gradients: they agree to bf16
    # rounding.  The kernels phase compares the kernel with the XLA chain on
    # equal inputs; this comparison is for what only the whole step shows — a
    # wrong sharding rule, padding or scalar operand inside the program, each
    # of which moves moments by many steps and every parameter by O(lr).
    # Bounds, with the first chip run's values: loss 1e-3 (3.5e-5); a moment
    # within 2 bf16 steps at its leaf's scale (1.0: the neighbouring value);
    # parameters by at most lr/4 anywhere (0.066 lr, where a small gradient
    # met its noise) and lr/100 on average.
    if abs(loss_on - loss_off) > 1e-3:
        raise RuntimeError("train kernels=on: loss differs from the default program's")
    if p_max > 0.25 * lr or p_sum / p_n > 1e-2 * lr:
        raise RuntimeError(f"train kernels=on: parameters differ by {p_max / lr} lr at most, "
                           f"{p_sum / p_n / lr} lr on average")
    if m_at_scale > 2.0:
        raise RuntimeError(f"train kernels=on: moments differ by {m_at_scale} bf16 steps at scale")


# =================================================================== kernels
def bf16_ulps_at_scale(a, b) -> float:
    """max |a - b| in bf16 steps at the reference's largest magnitude."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    step = float(np.spacing(np.float32(np.max(np.abs(b)) or 1.0))) * 2.0 ** 16
    return float(np.max(np.abs(a - b)) / step)


def rel_at_scale(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) or 1.0))


def kernels_paged_decode(cfg, sizes, args, on_tpu, interp, key):
    """``paged_decode`` against the engine's XLA leg, over a 5-D pool of two
    layers (the second is read; the first is NaN, so a read of the wrong
    layer fails): the serve phase's own geometry, full, and the two serve
    cells' of the benchmark with ragged lengths, a third and a fifth of the
    capacity live, as those cells hold.  On the chip both legs are timed and
    the kernel's reading rate over the live pages' bytes is logged: smoke
    numbers for PERF.md, not benchmark metrics."""
    import jax
    import jax.numpy as jnp

    from vescale_tpu.kernels.paged_attention import paged_decode

    H, hd, dtype = cfg.num_attention_heads, cfg.head_dim, cfg.dtype
    S, page, Pmax = sizes.serve_slots, sizes.serve_page, sizes.serve_pages_per_slot
    # (name, slots, pages per slot, kv heads, mean share of the capacity live)
    cases = [("serve-phase mha", S, Pmax, H, 0.5), ("serve-phase gqa", S, Pmax, max(1, H // 4), 0.5)]
    if not sizes.tiny:
        cases += [("mistral7b_serve_chat", 32, 128, 8, 1 / 3), ("deepseek7b_serve_batch", 32, 96, 32, 1 / 5)]
    rng = np.random.default_rng(args.seed + 2)
    for name, S, Pmax, KV, share in cases:
        N, Tmax, layer = S * Pmax + 1, page * Pmax, 1
        kp_, kv_, kq, key = jax.random.split(key, 4)
        pool = lambda k: jnp.stack([jnp.full((N, page, KV, hd), jnp.nan, dtype),
                                    jax.random.normal(k, (N, page, KV, hd), jnp.float32).astype(dtype)])
        kp, vp = pool(kp_), pool(kv_)
        q = jax.random.normal(kq, (S, H, hd), jnp.float32).astype(dtype)
        table = jnp.asarray(rng.permutation(np.arange(1, N))[: S * Pmax].reshape(S, Pmax), jnp.int32)
        lengths = jnp.asarray(rng.integers(1, int(min(1.0, 2 * share) * Tmax) + 1, S), jnp.int32)
        scale = hd ** -0.5

        def xla_chain(q, kp, vp, table, lengths):   # serve/engine.py's XLA leg, line for line
            ks = jnp.take(kp[layer], table, axis=0).reshape(S, Tmax, KV, hd)
            vs = jnp.take(vp[layer], table, axis=0).reshape(S, Tmax, KV, hd)
            qg = (q.astype(jnp.float32) * scale).reshape(S, KV, H // KV, hd)
            s = jnp.einsum("skgd,stkd->skgt", qg, ks.astype(jnp.float32))
            mask = jnp.arange(Tmax, dtype=jnp.int32)[None, :] < lengths[:, None]
            s = jnp.where(mask[:, None, None, :], s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("skgt,stkd->skgd", p, vs.astype(jnp.float32)).reshape(S, H, hd)

        @jax.jit
        def reference(*a):
            with jax.default_matmul_precision("highest"):
                return xla_chain(*a)

        xla_leg = jax.jit(xla_chain)
        kernel = jax.jit(lambda q, kp, vp, table, lengths: paged_decode(
            q, kp, vp, table, lengths, layer=layer, scale=scale, interpret=interp))
        operands = (q, kp, vp, table, lengths)
        o_r, o_k = reference(*operands), kernel(*operands)
        err, err_xla = rel_at_scale(o_k, o_r), rel_at_scale(xla_leg(*operands), o_r)
        live_pages = int(np.sum(-(-np.asarray(lengths) // page)))
        fields = dict(case=name, slots=S, pages_per_slot=Pmax, page=page, H=H, KV=KV, hd=hd,
                      dtype=jnp.dtype(dtype).name, live_pages=live_pages, of=S * Pmax,
                      max_abs_diff_over_max=f"{err:.3e}", xla_leg_max_abs_diff_over_max=f"{err_xla:.3e}")
        if on_tpu:
            def median_ms(fn, n=20):
                jax.block_until_ready(fn(*operands))
                times = []
                for _ in range(n):
                    t0 = time.perf_counter()
                    jax.block_until_ready(fn(*operands))
                    times.append(time.perf_counter() - t0)
                return float(np.median(times)) * 1e3

            t_k, t_x = median_ms(kernel), median_ms(xla_leg)
            live_bytes = 2 * live_pages * page * KV * hd * jnp.dtype(dtype).itemsize
            fields.update(kernel_ms=round(t_k, 3), xla_leg_ms=round(t_x, 3),
                          live_page_bytes=live_bytes, kernel_live_GBps=round(live_bytes / t_k / 1e6, 1),
                          note="host clock around one call of one layer, dispatch included")
        log("kernels paged_decode", **fields)
        # Tolerance.  Both legs read the same bf16 pool and keep fp32 after it;
        # the kernel's MXU passes round its fp32 operands (q * scale, the
        # probabilities) to bf16, 2^-9 each, while the reference multiplies in
        # full fp32: 1e-2 of the largest output bounds a softmax-weighted
        # mean of such products with room (measured on the v5e: 2.6e-3).
        # Interpreted in fp32: 1e-5.
        if not err <= (1e-2 if on_tpu else 1e-5):
            raise RuntimeError(f"kernels paged_decode {name}: differs by {err}")
        del kp, vp, q, o_r, o_k


def phase_kernels(cfg, sizes, args, on_tpu):
    import jax
    import jax.numpy as jnp

    from vescale_tpu.kernels import ulps_at_scale
    from vescale_tpu.kernels.cross_entropy import fused_xent_parts
    from vescale_tpu.kernels.fused_adamw import fused_adamw_update, update_ulps_vs_float64
    from vescale_tpu.ops.flash_attention import _dense_ref, flash_attention

    interp = not on_tpu  # the sandbox rehearsal runs the kernels interpreted
    key = jax.random.key(args.seed + 1)
    T, H, D = sizes.seq, cfg.num_attention_heads, cfg.head_dim
    dtype = cfg.dtype

    # ---- flash: forward and grads against the dense reference, MHA and GQA.
    # The reference runs in fp32 at "highest" matmul precision on the same
    # (bf16-valued) inputs, a kv group at a time (heads are independent; all
    # 32 at once would hold 2 GB of scores several times over).
    for KV in (H, max(1, H // 4)):
        rep = H // KV
        kq, kk, kv_, kw, key = jax.random.split(key, 5)
        q = jax.random.normal(kq, (1, T, H, D), jnp.float32).astype(dtype)
        k = jax.random.normal(kk, (1, T, KV, D), jnp.float32).astype(dtype)
        v = jax.random.normal(kv_, (1, T, KV, D), jnp.float32).astype(dtype)
        w = jax.random.normal(kw, (1, T, H, D), jnp.float32).astype(dtype)  # cotangent

        def kernel_loss(q, k, v, w):
            o = flash_attention(q, k, v, causal=True, interpret=True if interp else None)
            return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32)), o

        (_, o_k), g_k = jax.jit(jax.value_and_grad(kernel_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v, w)

        @jax.jit
        def ref_group(qg, kg, vg, wg):
            def loss(qg, kg, vg):
                o = _dense_ref(qg, kg, vg, D ** -0.5, True)
                return jnp.sum(o * wg), o

            with jax.default_matmul_precision("highest"):
                (_, o), g = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(qg, kg, vg)
            return o, g

        o_r, dq_r, dk_r, dv_r = [], [], [], []
        step_g = max(1, min(KV, 8 // rep))  # at most 8 q heads of scores at once
        f = lambda x: x.astype(jnp.float32)
        for g0 in range(0, KV, step_g):
            hs = slice(g0 * rep, (g0 + step_g) * rep)
            gs = slice(g0, g0 + step_g)
            o, (dq, dk, dv) = ref_group(f(q[:, :, hs]), f(k[:, :, gs]), f(v[:, :, gs]), f(w[:, :, hs]))
            o_r.append(np.asarray(o)); dq_r.append(np.asarray(dq))
            dk_r.append(np.asarray(dk)); dv_r.append(np.asarray(dv))
        refs = [np.concatenate(x, axis=2) for x in (o_r, dq_r, dk_r, dv_r)]
        got = [np.asarray(x, np.float32) for x in (o_k,) + tuple(g_k)]
        errs = {n: round(bf16_ulps_at_scale(a, b), 3) if dtype == jnp.bfloat16 else round(ulps_at_scale(a, b), 1)
                for n, a, b in zip(("o", "dq", "dk", "dv"), got, refs)}
        log("kernels flash", T=T, H=H, KV=KV, D=D, dtype=jnp.dtype(dtype).name,
            unit="bf16 ulps at scale" if dtype == jnp.bfloat16 else "fp32 ulps at scale", **errs)
        # Tolerance.  The kernel returns bf16: rounding alone is half a bf16
        # step at the element's size, at most half a step at the tensor's
        # scale.  Inside, scores and probabilities are fp32 but the MXU takes
        # fp32 operands in bf16 passes, and dk/dv sum T such products: the
        # gradients are allowed 4 steps at scale (1.6% of the largest
        # element), the output 2 (measured on the v5e: at most 1.06 and 0.62).
        # Interpreted on the CPU in fp32 (rehearsal) the documented bound of
        # docs/kernels.md applies: 8 fp32 ulps at scale.
        bound = {"o": 2.0, "dq": 4.0, "dk": 4.0, "dv": 4.0} if dtype == jnp.bfloat16 else dict.fromkeys(errs, 8.0)
        for n, e in errs.items():
            if not e <= bound[n]:
                raise RuntimeError(f"kernels flash KV={KV}: {n} differs by {e} > {bound[n]}")
        del q, k, v, w, o_k, g_k

    # ---- paged decode against slice -> gather -> masked softmax -> matmul
    kernels_paged_decode(cfg, sizes, args, on_tpu, interp, key)

    # ---- fused adamw on one FFN leaf: compiled kernel against the jitted XLA chain
    b1, b2, eps = 0.9, 0.999, 1e-8
    shape = (cfg.hidden_size, cfg.intermediate_size)
    kg, km, kv_, key = jax.random.split(key, 4)
    g = jax.random.normal(kg, shape, jnp.float32)
    m = jax.random.normal(km, shape, jnp.float32).astype(jnp.bfloat16)
    v = jnp.abs(jax.random.normal(kv_, shape, jnp.float32)).astype(jnp.bfloat16)
    c1 = jnp.asarray(1.0 - b1 ** 7, jnp.float32)
    c2 = jnp.asarray(1.0 - b2 ** 7, jnp.float32)

    @jax.jit
    def xla_chain(g, m, v, c1, c2):
        g32 = g.astype(jnp.float32)
        m32 = b1 * m.astype(jnp.float32) + (1.0 - b1) * g32
        v32 = b2 * v.astype(jnp.float32) + (1.0 - b2) * jnp.square(g32)
        u = ((m32 / c1) / (jnp.sqrt(v32 / c2) + eps)).astype(g.dtype)
        return u, m32.astype(jnp.bfloat16), v32.astype(jnp.bfloat16)

    ker = jax.jit(lambda g, m, v, c1, c2: fused_adamw_update(
        g, m, v, c1, c2, b1=b1, b2=b2, eps=eps, state_dtype=jnp.bfloat16, interpret=interp))
    u_x, m_x, v_x = (np.asarray(x) for x in xla_chain(g, m, v, c1, c2))
    u_k, m_k, v_k = (np.asarray(x) for x in ker(g, m, v, c1, c2))
    n64 = 1 << 20  # the float64 reference is evaluated on the first 2^20 elements
    flat = lambda x: np.asarray(x).reshape(-1)[:n64]
    kw = dict(b1=b1, b2=b2, eps=eps)
    ulps_k = update_ulps_vs_float64(flat(u_k), flat(g), flat(m), flat(v), c1, c2, **kw)
    ulps_x = update_ulps_vs_float64(flat(u_x), flat(g), flat(m), flat(v), c1, c2, **kw)
    m_diff = int((m_k.astype(np.float32) != m_x.astype(np.float32)).sum())
    v_diff = int((v_k.astype(np.float32) != v_x.astype(np.float32)).sum())
    log("kernels fused_adamw", leaf=shape, kernel_update_ulps_vs_float64=round(ulps_k, 3),
        xla_update_ulps_vs_float64=round(ulps_x, 3), moments_m_differing=m_diff,
        moments_v_differing=v_diff, of=m_k.size,
        update_max_abs_diff_kernel_vs_xla=float(np.max(np.abs(u_k - u_x))))
    # Tolerance.  The CPU contract (tests/test_kernels.py) is 4 elementwise
    # fp32 ulps from the float64 evaluation, on correctly rounded divides and
    # square roots.  The chip's are not: the formula chains three divides and a
    # square root, and the XLA chain itself measured 4.73 ulps from float64 on
    # the v5e (the kernel 4.88, first chip run).  So on the chip either leg
    # gets 8, and the kernel at most 1 more than the XLA leg.  On equal inputs
    # the moments came out bitwise equal (0 of 45,088,768 differ): required.
    bound = 8.0 if on_tpu else 4.0
    if not (ulps_k <= bound and ulps_k <= ulps_x + 1.0):
        raise RuntimeError(f"kernels fused_adamw: update is {ulps_k} ulps from float64 "
                           f"(bound {bound}, XLA chain {ulps_x})")
    if m_diff or v_diff:
        raise RuntimeError(f"kernels fused_adamw: {m_diff} + {v_diff} moments differ from the XLA chain's")
    del g, m, v

    # ---- fused xent at rows x vocab of the train step: loss and gradient
    rows, vocab = sizes.seq, cfg.vocab_size
    kl, ki, key = jax.random.split(key, 3)
    lg = jax.random.normal(kl, (rows, vocab), jnp.float32) * 2.0
    idx = jax.random.randint(ki, (rows,), 0, vocab, jnp.int32)

    def xla_loss(lg, idx):
        gmax = jax.lax.stop_gradient(jnp.max(lg, axis=-1))
        se = jnp.sum(jnp.exp(lg - gmax[:, None]), axis=-1)
        pk = jnp.take_along_axis(lg, idx[:, None], axis=-1)[:, 0]
        return jnp.mean(gmax + jnp.log(se) - pk)

    def ker_loss(lg, idx):
        gmax = jax.lax.stop_gradient(jnp.max(lg, axis=-1))
        se, pk, _ = fused_xent_parts(lg, idx, gmax, interp)
        return jnp.mean(gmax + jnp.log(se) - pk)

    l_x, g_x = jax.jit(jax.value_and_grad(xla_loss))(lg, idx)
    l_k, g_k = jax.jit(jax.value_and_grad(ker_loss))(lg, idx)
    dl = abs(float(l_k) - float(l_x))
    dg = ulps_at_scale(g_k, g_x)
    log("kernels fused_xent", rows=rows, vocab=vocab, loss_xla=float(l_x), loss_kernel=float(l_k),
        loss_abs_diff=f"{dl:.3e}", grad_fp32_ulps_at_scale=round(dg, 2))
    # Tolerance: everything is fp32 elementwise plus a sum of 32000 positive
    # terms in another order (blocks of 512 against XLA's tree), good to a few
    # tens of ulps of the sum in the worst row; the loss is a mean of 4096 rows
    # near 11, where 5e-5 is about 50 ulps.  The gradient is measured at the
    # scale of its largest elements (the one-hot picks), where docs/kernels.md
    # bounds the reordering at 8 ulps; the two legs may also take exp() from
    # different implementations on the chip, hence 16.
    if not (dl <= 5e-5 and dg <= 16.0):
        raise RuntimeError(f"kernels fused_xent: loss differs by {dl}, gradient by {dg} ulps")


# ===================================================================== serve
def phase_serve(cfg, sizes, args, on_tpu, devices):
    import jax
    import jax.numpy as jnp

    from vescale_tpu.mesh import DeviceMesh
    from vescale_tpu.models.llama import Llama
    from vescale_tpu.serve import (
        ContinuousBatchingScheduler,
        KVCacheConfig,
        PagedKVCache,
        Request,
        ServeEngine,
        run_serve_resilient,
    )
    from vescale_tpu.serve.scheduler import TERMINAL

    mesh = DeviceMesh(("tp",), (1,), devices=devices[:1])
    # a deployment holds the weights in the compute dtype (flax inits fp32)
    params = jax.jit(
        lambda key: jax.tree_util.tree_map(
            lambda x: x.astype(cfg.dtype),
            Llama(cfg).init(key, jnp.ones((1, 8), jnp.int32))["params"])
    )(jax.random.key(args.seed))
    kc = KVCacheConfig(
        layers=cfg.num_hidden_layers, kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        num_slots=sizes.serve_slots, page_size=sizes.serve_page,
        pages_per_slot=sizes.serve_pages_per_slot, dtype=cfg.dtype,
    )
    cache_bytes = (2 * kc.layers * kc.pool_pages * kc.page_size * kc.kv_heads * kc.head_dim
                   * jnp.dtype(cfg.dtype).itemsize)
    log("serve", slots=kc.num_slots, positions_per_slot=kc.max_seq_len, page=kc.page_size,
        cache_bytes=cache_bytes, weights_dtype=jnp.dtype(cfg.dtype).name)

    rng = np.random.default_rng(args.seed + 3)
    lo, hi = sizes.prompt_lens
    lens = np.linspace(lo, hi, sizes.requests).astype(int)
    prompts = [tuple(int(t) for t in rng.integers(1, cfg.vocab_size - 1, n)) for n in lens]
    arrivals = [(i // 4, Request(rid=i, prompt=p, max_new_tokens=sizes.new_tokens))
                for i, p in enumerate(prompts)]
    # teacher-forced decode for the logits comparison: fixed prompts and fixed
    # next tokens, so both engines see the same cache whatever they would sample
    forced_prompts = prompts[:: max(1, len(prompts) // 4)][:4]
    forced_steps = 8 if not sizes.tiny else 3
    forced_tokens = rng.integers(1, cfg.vocab_size - 1, (forced_steps, kc.num_slots)).astype(np.int32)

    logits_by_mode = {}
    for mode in ("off", "on" if on_tpu else "interpret"):
        before = kernel_counters()
        with kernels_mode(mode):
            cache = PagedKVCache(kc, mesh)
            t0 = time.perf_counter()
            engine = ServeEngine(cfg, mesh, params, cache)
            sched = ContinuousBatchingScheduler(cache, max_queue=len(arrivals))
            step_t = []

            def on_step(step, active, _last=[None]):
                now = time.perf_counter()
                if _last[0] is not None:
                    step_t.append(now - _last[0])
                _last[0] = now

            res = run_serve_resilient(
                engine=engine, scheduler=sched, arrivals=arrivals,
                install_signal_handlers=False, coordinate=False, on_step=on_step,
            )
            wall = time.perf_counter() - t0
        sched.ledger_check()  # every submission ended exactly one way
        ledger = {rid: (o["status"], len(o["tokens"])) for rid, o in sorted(res.outcomes.items())}
        log(f"serve kernels={mode}", status=res.status, steps=res.steps, wall_s=round(wall, 2),
            counts=dict(sched.counts))
        log(f"serve kernels={mode}", ledger=ledger)
        if set(res.outcomes) != {r.rid for _, r in arrivals}:
            raise RuntimeError(f"serve kernels={mode}: outcomes for {sorted(res.outcomes)}, not every request")
        bad = {rid: o["status"] for rid, o in res.outcomes.items() if o["status"] not in TERMINAL}
        if bad:
            raise RuntimeError(f"serve kernels={mode}: non-terminal outcomes {bad}")
        if sched.counts["completed"] != len(arrivals):
            # the queue holds every request and nothing sets a deadline
            raise RuntimeError(f"serve kernels={mode}: {sched.counts}")
        if any(len(o["tokens"]) != sizes.new_tokens for o in res.outcomes.values()):
            raise RuntimeError(f"serve kernels={mode}: a request did not get {sizes.new_tokens} tokens")
        ttft, itl = sched._ttft.snapshot(), sched._itl.snapshot()
        fmt = lambda h: {k: round(h[k] * 1e3, 2) for k in ("p50", "max") if k in h}
        steady = sorted(step_t)[: max(1, len(step_t) // 2)]  # the half without prefills and compiles
        log(f"serve kernels={mode}", ttft_ms=fmt(ttft), itl_ms=fmt(itl),
            decode_step_ms_median_of_fast_half=round(float(np.median(steady)) * 1e3, 2),
            note="first requests include compilation")
        if mode != "off":
            check_no_fallback(f"serve kernels={mode}", before, "paged_decode")

        # teacher-forced logits
        cache.reset()
        slots = []
        rows = []
        for p in forced_prompts:
            slot = cache.alloc(len(p), forced_steps + 1)
            rows.append(engine.prefill(list(p), slot))
            cache.commit_prefill(slot, len(p))
            slots.append(slot)
        dec = []
        for i in range(forced_steps):
            toks = np.zeros((kc.num_slots,), np.int32)
            toks[slots] = forced_tokens[i, slots]
            logits = engine.decode(toks)
            for s in slots:
                cache.advance(s)
            dec.append(logits[slots])
            # the ids the decode program took are the host's argmax of its logits, every slot
            if not np.array_equal(logits.tokens, np.argmax(np.asarray(logits), -1)):
                raise RuntimeError(f"serve kernels={mode}: decode's tokens are not the argmax of its logits")
        logits_by_mode[mode] = (np.stack(rows), np.stack(dec))
        stats = devices[0].memory_stats() or {}
        log(f"serve kernels={mode}", peak_bytes_in_use=stats.get("peak_bytes_in_use"))
        del engine, cache, sched

    (pre_a, dec_a), (pre_b, dec_b) = logits_by_mode.values()
    if not (np.isfinite(dec_a).all() and np.isfinite(dec_b).all()):
        raise RuntimeError("serve: non-finite decode logits")
    d_pre, d_dec = rel_at_scale(pre_b, pre_a), rel_at_scale(dec_b, dec_a)
    agree = float(np.mean(np.argmax(dec_a, -1) == np.argmax(dec_b, -1)))
    log("serve logits", modes=list(logits_by_mode), shape=dec_a.shape,
        prefill_max_abs_diff_over_max=f"{d_pre:.3e}", decode_max_abs_diff_over_max=f"{d_dec:.3e}",
        decode_argmax_agreement=agree)
    # Tolerance.  Prefill is the same program in both modes (flash either
    # way): identical.  Decode differs only in the attention leg, by the
    # amount the kernels phase measures for paged_decode (bf16 MXU passes
    # against the XLA leg's own default-precision einsum), carried through
    # `layers` bf16 blocks and the bf16 lm_head: 3e-2 of the largest logit.
    if not d_pre <= (0.0 if on_tpu else 1e-4):  # off the chip the default prefill is the dense leg
        raise RuntimeError(f"serve: prefill logits differ between kernel modes by {d_pre}")
    if not d_dec <= (3e-2 if on_tpu else 1e-4):
        raise RuntimeError(f"serve: decode logits differ between kernel modes by {d_dec}")


# ================================================================= four chips
def four_chip_phases(devices, sizes, args, on_tpu):
    """The phases of ``--chips 4``: the one-device leg, then dp 2 x tp 2 with
    flash on (the default on TPU; under a mesh it is a ``custom_partitioning``
    op), then dp 2 x tp 2 with the model's dense attention — so that a fault
    of the partition rule does not hide whether the rest of the sharded path
    (mesh, plan, sequence parallelism, ZeRO, collectives) runs."""
    import jax
    import jax.numpy as jnp

    from vescale_tpu.debug.comm_mode import count_collectives
    from vescale_tpu.mesh import DeviceMesh

    # lr below the train phase's 3e-4, whose first losses jump about (10.9,
    # 9.4, 7.4, 9.6, ...): two legs are only comparable where the steps are smooth
    lr, steps = 1e-4, 4
    T = sizes.seq // 2            # global batch 2 x seq/2: what one chip holds at 1 x seq
    cfg = model_config(dataclasses.replace(sizes, seq=T), sizes.layers)
    rng = np.random.default_rng(args.seed)
    toks = rng.integers(0, cfg.vocab_size, (2, T + 1)).astype(np.int32)
    host_batch = {"input": toks[:, :-1], "target": toks[:, 1:]}
    one_device_losses = []

    def leg(name, cfg, mesh_devices, shape, sequence_parallel, zero):
        log(f"chips4 {name}", model="LLAMA2_7B widths", layers=cfg.num_hidden_layers, global_batch=2,
            seq=T, dtype=jnp.dtype(cfg.dtype).name, flash=cfg.use_flash_attention, mesh=shape)
        mesh = DeviceMesh(("dp", "tp"), shape, devices=mesh_devices)
        params, tx, make_step = build_trainer(
            cfg, mesh, seed=args.seed, lr=lr, sequence_parallel=sequence_parallel, zero=zero)
        jax.block_until_ready(params)
        in_use = [int((d.memory_stats() or {}).get("bytes_in_use", 0)) for d in devices]
        log(f"chips4 {name}", bytes_in_use_after_init=in_use)
        if len(mesh_devices) > 1 and on_tpu:
            used = [in_use[devices.index(d)] for d in mesh_devices]
            # tp splits every matrix in two and dp replicates: each device is
            # born with the same share; one holding the whole model (or
            # nothing) means arrays were not placed by the mesh
            if min(used) < 0.5 * max(used):
                raise RuntimeError(f"chips4 {name}: model not spread over the devices: {used}")
        opt_state = tx.init(params)
        batch = {k: jnp.asarray(v) for k, v in host_batch.items()}
        step = make_step()
        t0 = time.perf_counter()
        compiled = step.lower(params, opt_state, batch).compile()
        text = compiled.as_text()
        census = {k: v for k, v in count_collectives(text).items() if v}
        n_kernel_calls = text.count('custom_call_target="tpu_custom_call"')
        mem = compiled.memory_analysis()
        log(f"chips4 {name}", compile_s=round(time.perf_counter() - t0, 2), collectives=census,
            tpu_custom_calls=n_kernel_calls,
            argument_bytes_per_device=getattr(mem, "argument_size_in_bytes", None),
            temp_bytes_per_device=getattr(mem, "temp_size_in_bytes", None))
        if on_tpu and cfg.use_flash_attention and n_kernel_calls < 3 * cfg.num_hidden_layers:
            raise RuntimeError(f"chips4 {name}: the flash kernel is not in the compiled step")
        params, opt_state, losses, secs = run_steps(step, params, opt_state, batch, steps)
        log(f"chips4 {name}", losses=[round(x, 5) for x in losses],
            step_ms=[round(s * 1e3, 1) for s in secs],
            peak_bytes_in_use=[(d.memory_stats() or {}).get("peak_bytes_in_use") for d in mesh_devices])
        if not all(math.isfinite(x) for x in losses):
            raise RuntimeError(f"chips4 {name}: non-finite loss")
        return losses

    def one_device():
        one_device_losses[:] = leg("1x1", cfg, devices[:1], (1, 1), sequence_parallel=False, zero=False)

    def sharded(name, cfg):
        four = leg(name, cfg, devices[:4], (2, 2), sequence_parallel=True, zero=True)
        if not one_device_losses:
            raise RuntimeError(f"chips4 {name}: no one-device losses to compare with")
        diffs = [abs(a - b) for a, b in zip(one_device_losses, four)]
        log(f"chips4 {name}", loss_abs_diff_per_step_vs_1x1=[round(d, 5) for d in diffs])
        # Tolerance.  Same seed, parameters (threefry is partitionable: an init
        # is the same bits under any sharding) and global batch, so the legs
        # compute the same step up to the order of bf16 reductions: row-
        # parallel matmuls sum two half-length products, the gradient is the
        # mean of two dp halves, sequence parallelism reshards activations in
        # bf16, and dense attention differs from the flash kernel by a bf16
        # step (kernels phase).  The first loss sees only the forward: bf16
        # logits err by about 1e-2 each and the loss averages 4096 of them,
        # so 2e-2 has room.  Later losses also see Adam, whose first steps move
        # every weight by lr whatever its gradient's size, so bf16 noise in
        # small gradients grows with each step: 0.1 (1% of the first loss).  A
        # sharding bug (a missing all-reduce, a wrong shard) moves the first
        # loss by O(1).
        if diffs[0] > 2e-2 or max(diffs) > 0.1:
            raise RuntimeError(f"chips4 {name}: losses differ from the one-device leg's by {diffs}")
        if not four[-1] < four[0]:
            raise RuntimeError(f"chips4 {name}: loss did not fall on the repeated batch: {four}")

    dense = dataclasses.replace(cfg, use_flash_attention=False)
    return [
        ("chips4 1x1", one_device),
        ("chips4 dp2xtp2 flash", lambda: sharded("dp2xtp2 flash", cfg)),
        ("chips4 dp2xtp2 dense-attention", lambda: sharded("dp2xtp2 dense-attention", dense)),
    ]


# ====================================================================== main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the dp2 x tp2 path and its one-device comparison")
    ap.add_argument("--rehearse", action="store_true",
                    help="sandbox rehearsal: tiny widths, interpreted kernels, any backend; never exits 0")
    args = ap.parse_args(argv)

    from vescale_tpu.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    import jax

    jax.config.update("jax_threefry_partitionable", True)
    devices = jax.devices()
    dev = devices[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.rehearse:
        print(f"chip_smoke: no TPU (jax reports {dev.platform}); nothing is printed for another device",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, jax reports {len(devices)}",
              file=sys.stderr)
        return 2
    if on_tpu:
        from vescale_tpu.telemetry.calibrate import device_peaks

        device_peaks(dev)  # a chip the peak table does not know is an error here too
    sizes = TINY if args.rehearse else REAL
    log("device", platform=dev.platform, kind=repr(dev.device_kind), count=len(devices),
        jax=jax.__version__, compile_cache=cache_dir or os.environ.get("JAX_COMPILATION_CACHE_DIR"),
        seed=args.seed)

    cfg = model_config(sizes, sizes.layers)
    if args.chips == 4:
        phases = four_chip_phases(devices, sizes, args, on_tpu)
    else:
        phases = [
            ("train", lambda: phase_train(cfg, devices, sizes, args, on_tpu)),
            ("kernels", lambda: phase_kernels(cfg, sizes, args, on_tpu)),
            ("serve", lambda: phase_serve(cfg, sizes, args, on_tpu, devices)),
        ]
    # every phase runs, so that one chip call shows every fault; any failure
    # fails the run
    failed = []
    t0 = time.perf_counter()
    for name, phase in phases:
        t1 = time.perf_counter()
        try:
            phase()
        except Exception:
            traceback.print_exc()
            failed.append(name)
        gc.collect()  # a failed phase's arrays must not crowd the next one
        log("phase", name=name, seconds=round(time.perf_counter() - t1, 1),
            result="FAILED" if name in failed else "passed")
    log("phase", total_s=round(time.perf_counter() - t0, 1))
    if failed:
        print(f"chip_smoke: FAILED phases: {failed}", file=sys.stderr)
        return 1

    if args.rehearse:
        print("rehearsal passed: paths and control flow only, not a chip run", flush=True)
        return 3
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
