"""Benchmark — prints ONE JSON line for the driver.

Headline: Llama-1.3B pretrain step at seq 4096 (BASELINE.md ladder rung 2-3
scaled to the single available 16 GB chip), bf16, pallas flash attention,
bf16 optimizer moments (adamw_lowmem), donated buffers, no remat (B=1
activations fit, so no recompute tax).  Reported MFU counts ideal model
FLOPs (6P + attention) only.  The reference publishes no absolute numbers
(BASELINE.md); the ladder target is MFU >= 45%, so ``vs_baseline`` reports
MFU / 0.45.

The rung selected by ``VESCALE_BENCH`` runs in the process that was started
(one process holds the chip).  The default rung needs a TPU and exits
non-zero without one.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def _tpu_devices():
    """The devices of an MFU rung.  These rungs measure the chip: without a
    TPU they stop with an error and print no line."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"bench: this rung measures a TPU and found {devices[0].platform} "
            f"({devices[0].device_kind}); no line is printed for another device"
        )
    return devices


def _step_report_line(step, params, opt_state, batch):
    """Compile-time step report (telemetry/step_report.py) trimmed for the
    bench line: XLA FLOPs / peak HBM / collective counts of the exact step
    program.  AOT lower+compile is a SECOND compile of the step, so it is
    opt-in (VESCALE_BENCH_STEP_REPORT=1).  Never fails the bench — errors
    degrade to None."""
    from vescale_tpu.analysis import envreg

    if not envreg.get_bool("VESCALE_BENCH_STEP_REPORT"):
        return None
    try:
        from vescale_tpu.telemetry.step_report import build_step_report

        r = build_step_report(step, params, opt_state, batch, name="bench_step")
        return {
            "flops": r.get("flops"),
            "peak_bytes": r.get("peak_bytes"),
            "temp_bytes": r.get("temp_bytes"),
            "collectives": {k: v for k, v in (r.get("collectives") or {}).items() if v},
        }
    except Exception as e:
        print(f"[bench] step report failed (non-fatal): {e!r}", file=sys.stderr)
        return None


def _cost_model_line():
    """Which cost model is pricing planner/scheduler decisions during this
    bench: the active calibration table's digest (so a future reader of
    BENCH_*.json knows WHICH measured table stood behind a perf line), or
    'analytic'.  Never fails the bench."""
    try:
        from vescale_tpu.telemetry import calibrate

        digest = calibrate.active_digest()
        if digest is not None:
            return {"kind": "calibrated", "calibration_digest": digest}
        return {"kind": "analytic"}
    except Exception as e:
        print(f"[bench] cost-model probe failed (non-fatal): {e!r}", file=sys.stderr)
        return {"kind": "analytic"}


def time_and_report(step, params, opt_state, batch, *, n, tokens_per_step,
                    flops_per_token, metric, extra=None):
    """Warmup + timed loop + one JSON line (shared by the MFU rungs)."""
    import jax

    from vescale_tpu.telemetry.calibrate import device_peak_flops

    peak = device_peak_flops(jax.devices()[0])
    step_report = _step_report_line(step, params, opt_state, batch)
    for _ in range(3):
        params, opt_state, loss = step(params, opt_state, batch)
    jax.block_until_ready(loss)
    iters = 10
    t0 = time.perf_counter()
    for _ in range(iters):
        params, opt_state, loss = step(params, opt_state, batch)
    jax.block_until_ready(loss)
    dt = (time.perf_counter() - t0) / iters
    mfu = flops_per_token * tokens_per_step / dt / (peak * n)
    line = {
        "metric": metric,
        "value": round(mfu, 4),
        "unit": "MFU",
        "vs_baseline": round(mfu / 0.45, 4),
        "tokens_per_sec_per_chip": round(tokens_per_step / dt / n, 1),
        "step_time_ms": round(dt * 1e3, 2),
    }
    if step_report is not None:
        line["step_report"] = step_report
    line["cost_model"] = _cost_model_line()
    line.update(extra or {})
    print(json.dumps(line))
    return mfu


def bench_moe():
    """Mixtral-style MoE/EP rung (BASELINE.md ladder: "Mixtral 8x7B EP"),
    scaled to the available chips.  Run with VESCALE_BENCH=moe; the default
    headline stays the llama rung the driver records."""
    import jax
    import jax.numpy as jnp
    import optax

    from vescale_tpu.mesh import DeviceMesh
    from vescale_tpu.dmodule import parallelize_module
    from vescale_tpu.models.mixtral import Mixtral, MixtralConfig, mixtral_plan
    from vescale_tpu.models.nanogpt import cross_entropy_loss
    from vescale_tpu.train import make_train_step

    devices = _tpu_devices()
    n = len(devices)
    B, T = 2, 2048
    cfg = MixtralConfig(
        vocab_size=32000,
        hidden_size=768,
        intermediate_size=1536,
        num_hidden_layers=8,
        num_attention_heads=12,
        num_key_value_heads=4,
        num_local_experts=8,
        num_experts_per_tok=2,
        capacity_factor=2.0,
        max_position_embeddings=T,
        dtype=jnp.bfloat16,
    )
    metric = "mixtral_moe_train_MFU_seq2048"

    # keep dp >= 2 on multi-chip: mixtral_plan shards only the batch over dp,
    # so maximizing ep would replicate all dense compute across ep ranks
    ep = 1
    max_ep = max(1, n // 2) if n > 1 else 1
    for cand in range(min(max_ep, cfg.num_local_experts), 0, -1):
        if n % cand == 0 and cfg.num_local_experts % cand == 0:
            ep = cand
            break
    mesh = DeviceMesh(("dp", "ep"), (n // ep, ep), devices=devices)
    dm = parallelize_module(Mixtral(cfg), mesh, mixtral_plan(mesh))
    params = dm.init(jax.random.key(0), jnp.ones((2, T), jnp.int32))["params"]
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))
    tx = optax.adamw(3e-4)
    opt_state = tx.init(params)
    # router aux (load-balancing) loss intentionally excluded: it's sown into
    # the "losses" collection and does not affect the compute profile
    step = make_train_step(
        dm, tx, lambda lg, b: cross_entropy_loss(lg, b["target"]), donate=True
    )
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B * n, T + 1)), jnp.int32)
    batch = {"input": toks[:, :-1], "target": toks[:, 1:]}
    # active params per token: dense share + top_k/E of expert params
    expert_params = 3 * cfg.num_local_experts * cfg.hidden_size * cfg.intermediate_size * cfg.num_hidden_layers
    active = n_params - expert_params + expert_params * cfg.num_experts_per_tok / cfg.num_local_experts
    time_and_report(
        step, params, opt_state, batch,
        n=n,
        tokens_per_step=B * n * T,
        flops_per_token=6.0 * active + 12.0 * cfg.num_hidden_layers * T * cfg.hidden_size,
        metric=metric,
        extra={"params": n_params, "active_params": int(active), "seq_len": T, "ep": ep},
    )


def bench_longctx():
    """Long-context rung (VESCALE_BENCH=longctx): llama-350M-class at seq
    32768 on one chip — the flash kernels keep activation memory O(T*D) so
    a 16 GB chip trains 32k sequences that dense attention (O(T^2) scores)
    cannot hold.  Multi-chip seq sharding uses ring/ulysses
    (parallel/context.py), exercised in tests/test_context_parallel.py."""
    import jax
    import jax.numpy as jnp

    from vescale_tpu.mesh import DeviceMesh
    from vescale_tpu.dmodule import parallelize_module
    from vescale_tpu.models.llama import Llama, LlamaConfig, llama_plan
    from vescale_tpu.models.nanogpt import cross_entropy_loss
    from vescale_tpu.parallel.optimizer import adamw_lowmem
    from vescale_tpu.train import make_train_step

    devices = _tpu_devices()
    n = len(devices)
    B, T = 1, 32768
    cfg = LlamaConfig(
        vocab_size=32000,
        hidden_size=1024,
        intermediate_size=2816,
        num_hidden_layers=24,
        num_attention_heads=16,
        num_key_value_heads=8,
        max_position_embeddings=T,
        dtype=jnp.bfloat16,
        use_flash_attention=True,
        remat=True,
        remat_scope="mlp",  # attention residuals fit at 350M; skip kernel recompute
        scan_layers=True,   # ONE compiled block: 24-layer unrolled XLA at
                            # seq 32k takes tens of minutes to optimize
    )
    metric = "llama350m_longctx_MFU_1chip_seq32768"

    mesh = DeviceMesh(("dp", "tp"), (n, 1), devices=devices)
    dm = parallelize_module(Llama(cfg), mesh, llama_plan(mesh, sequence_parallel=False, scanned=cfg.scan_layers))
    params = dm.init(jax.random.key(0), jnp.ones((1, T), jnp.int32))["params"]
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))
    tx = adamw_lowmem(3e-4)
    opt_state = tx.init(params)
    step = make_train_step(
        dm, tx, lambda lg, b: cross_entropy_loss(lg, b["target"]), donate=True
    )
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B * n, T + 1)), jnp.int32)
    batch = {"input": toks[:, :-1], "target": toks[:, 1:]}
    time_and_report(
        step, params, opt_state, batch,
        n=n,
        tokens_per_step=B * n * T,
        flops_per_token=6.0 * n_params + 12.0 * cfg.num_hidden_layers * T * cfg.hidden_size,
        metric=metric,
        extra={"params": n_params, "seq_len": T},
    )


def bench_memtrack():
    """Memory-tracking overhead rung (VESCALE_BENCH=memtrack): the SAME
    compiled step timed under telemetry without and with memtrack, so the
    reported delta is the per-step cost of the memory layer alone (census +
    device gauges + history ring), not the grad-norm scalars or the JSONL
    stream.  The number production runs consult before leaving memtrack on."""
    import tempfile

    import jax
    import jax.numpy as jnp
    import optax

    from vescale_tpu import telemetry
    from vescale_tpu.dmodule import parallelize_module
    from vescale_tpu.mesh import DeviceMesh
    from vescale_tpu.models.llama import Llama, LlamaConfig, llama_plan
    from vescale_tpu.models.nanogpt import cross_entropy_loss
    from vescale_tpu.parallel.optimizer import DistributedOptimizer
    from vescale_tpu.telemetry import memtrack

    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    B, T = (4, 1024) if on_tpu else (2, 64)
    cfg = LlamaConfig(
        vocab_size=2048 if on_tpu else 128,
        hidden_size=256 if on_tpu else 32,
        intermediate_size=512 if on_tpu else 64,
        num_hidden_layers=4 if on_tpu else 2,
        num_attention_heads=4 if on_tpu else 2,
        num_key_value_heads=4 if on_tpu else 2,
        max_position_embeddings=T,
        dtype=jnp.bfloat16 if on_tpu else jnp.float32,
    )
    mesh = DeviceMesh(("dp", "tp"), (1, 1), devices=devices[:1])
    dm = parallelize_module(Llama(cfg), mesh, llama_plan(mesh, sequence_parallel=False))
    params = dm.init(jax.random.key(0), jnp.ones((2, T), jnp.int32))["params"]
    dopt = DistributedOptimizer(optax.adamw(1e-3))

    from vescale_tpu.train import make_train_step

    out_dir = tempfile.mkdtemp(prefix="bench_memtrack_")
    # build ONCE under telemetry so both loops run the identical program
    telemetry.init(out_dir=out_dir, memtrack=False)
    opt_state = dopt.init(params)
    step = make_train_step(
        dm, dopt, lambda lg, b: cross_entropy_loss(lg, b["target"]), donate=False
    )
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T + 1)), jnp.int32)
    batch = {"input": toks[:, :-1], "target": toks[:, 1:]}

    def timed_loop(iters):
        p, s = params, opt_state
        for _ in range(3):  # warmup/compile
            p, s, loss = step(p, s, batch)
        float(loss)
        t0 = time.perf_counter()
        for _ in range(iters):
            p, s, loss = step(p, s, batch)
        float(loss)
        return (time.perf_counter() - t0) / iters

    iters = 20 if on_tpu else 5
    base = timed_loop(iters)  # telemetry on, memtrack off
    telemetry.shutdown()
    telemetry.init(out_dir=out_dir)  # memtrack on (default)
    memtrack.tag_tree(params, "params")
    tracked = timed_loop(iters)
    tracker = memtrack.get_tracker()
    live = tracker.history[-1]["live_arrays"] if tracker.history else 0
    from vescale_tpu.telemetry import costaudit

    audit = costaudit.audit_summary()  # plan-vs-reality ledger state
    telemetry.shutdown()
    overhead = tracked - base
    print(json.dumps({
        "metric": "memtrack_overhead_ms_per_step",
        "value": round(overhead * 1e3, 4),
        "unit": "ms",
        "overhead_frac": round(overhead / base, 4) if base > 0 else None,
        "step_ms_base": round(base * 1e3, 3),
        "step_ms_memtrack": round(tracked * 1e3, 3),
        "live_arrays": live,
        "audit": audit,
    }))


def bench_trace():
    """Trace-overhead rung (VESCALE_BENCH=trace): the SAME compiled step
    timed bare vs with the ndtimeline profiler live — a TRAIN_STEP span per
    step into the ring buffer, drained to a LocalRawHandler at a 50-step
    flush cadence (the production tracing configuration; a PER-STEP file
    flush costs ~80 us of pure IO and belongs to interactive debugging, not
    an always-on profile).  The reported delta is the per-step cost of
    leaving tracing on.  Acceptance bar from ISSUE 9: < 1%/step."""
    import tempfile

    import jax
    import jax.numpy as jnp
    import optax

    from vescale_tpu.dmodule import parallelize_module
    from vescale_tpu.mesh import DeviceMesh
    from vescale_tpu.models.llama import Llama, LlamaConfig, llama_plan
    from vescale_tpu.models.nanogpt import cross_entropy_loss
    from vescale_tpu.ndtimeline import LocalRawHandler
    from vescale_tpu.ndtimeline.api import flush, init_ndtimers
    from vescale_tpu.parallel.optimizer import DistributedOptimizer
    from vescale_tpu.train import make_train_step

    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    B, T = (4, 1024) if on_tpu else (2, 64)
    cfg = LlamaConfig(
        vocab_size=2048 if on_tpu else 128,
        hidden_size=256 if on_tpu else 32,
        intermediate_size=512 if on_tpu else 64,
        num_hidden_layers=4 if on_tpu else 2,
        num_attention_heads=4 if on_tpu else 2,
        num_key_value_heads=4 if on_tpu else 2,
        max_position_embeddings=T,
        dtype=jnp.bfloat16 if on_tpu else jnp.float32,
    )
    mesh = DeviceMesh(("dp", "tp"), (1, 1), devices=devices[:1])
    dm = parallelize_module(Llama(cfg), mesh, llama_plan(mesh, sequence_parallel=False))
    params = dm.init(jax.random.key(0), jnp.ones((2, T), jnp.int32))["params"]
    dopt = DistributedOptimizer(optax.adamw(1e-3))
    opt_state = dopt.init(params)
    step = make_train_step(
        dm, dopt, lambda lg, b: cross_entropy_loss(lg, b["target"]), donate=False
    )
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T + 1)), jnp.int32)
    batch = {"input": toks[:, :-1], "target": toks[:, 1:]}
    # CPU steps are ~1 ms: deep median to resolve a <1% delta (resilience
    # rung rationale); TPU steps are long enough for a short loop
    iters = 30 if on_tpu else 100

    p, s = params, opt_state
    for _ in range(3):  # warmup/compile; both loops run the identical program
        p, s, loss = step(p, s, batch)
    float(loss)

    def _median(xs):
        xs = sorted(xs)
        return xs[len(xs) // 2]

    def timed_loop(traced: bool):
        if traced:
            out = tempfile.mkdtemp(prefix="bench_trace_")
            init_ndtimers(rank=0, handlers=[LocalRawHandler(os.path.join(out, "spans.jsonl"))])
        p, s = params, opt_state
        ts = [time.perf_counter()]
        for i in range(iters):
            p, s, loss = step(p, s, batch)
            float(loss)
            # cadenced drain (the step counter advances via the train
            # step's own auto_inc_step — a manual next_iteration here
            # would double-count)
            if traced and (i + 1) % 50 == 0:
                flush()
            ts.append(time.perf_counter())
        if traced:
            flush()
        return _median([b - a for a, b in zip(ts, ts[1:])])

    bare = timed_loop(traced=False)
    traced = timed_loop(traced=True)
    overhead = traced - bare
    print(json.dumps({
        "metric": "trace_overhead_ms_per_step",
        "value": round(overhead * 1e3, 4),
        "unit": "ms",
        "overhead_frac": round(overhead / bare, 4) if bare > 0 else None,
        "step_ms_bare": round(bare * 1e3, 3),
        "step_ms_traced": round(traced * 1e3, 3),
        "target_frac": 0.01,
        "cost_model": _cost_model_line(),
    }))


def bench_resilience():
    """Resilience-overhead rung (VESCALE_BENCH=resilience): the SAME
    compiled step timed in a bare python loop vs inside ``run_resilient``
    with the whole layer ARMED — faultsim schedule installed (but far in
    the future, so quiescent), retry-wrapped storage/loader I/O, anomaly
    guard live, preemption flag checked — and no faults firing.  The
    reported ``overhead_frac`` is the steady-state price of leaving
    recovery on; the acceptance bar is < 1%.  Both loops host-fetch the
    loss each step (the anomaly guard needs it; an uninstrumented loop
    that never syncs would make the comparison dispatch-vs-compute)."""
    import tempfile

    import jax
    import jax.numpy as jnp
    import optax

    from vescale_tpu.dmodule import parallelize_module
    from vescale_tpu.mesh import DeviceMesh
    from vescale_tpu.models.llama import Llama, LlamaConfig, llama_plan
    from vescale_tpu.models.nanogpt import cross_entropy_loss
    from vescale_tpu.parallel.optimizer import DistributedOptimizer
    from vescale_tpu.checkpoint import CheckpointManager
    from vescale_tpu.resilience import AnomalyPolicy, Fault, faultsim, run_resilient
    from vescale_tpu.train import make_train_step

    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    B, T = (4, 1024) if on_tpu else (2, 64)
    cfg = LlamaConfig(
        vocab_size=2048 if on_tpu else 128,
        hidden_size=256 if on_tpu else 32,
        intermediate_size=512 if on_tpu else 64,
        num_hidden_layers=4 if on_tpu else 2,
        num_attention_heads=4 if on_tpu else 2,
        num_key_value_heads=4 if on_tpu else 2,
        max_position_embeddings=T,
        dtype=jnp.bfloat16 if on_tpu else jnp.float32,
    )
    mesh = DeviceMesh(("dp", "tp"), (1, 1), devices=devices[:1])
    dm = parallelize_module(Llama(cfg), mesh, llama_plan(mesh, sequence_parallel=False))
    params = dm.init(jax.random.key(0), jnp.ones((2, T), jnp.int32))["params"]
    dopt = DistributedOptimizer(optax.adamw(1e-3))
    opt_state = dopt.init(params)
    step = make_train_step(
        dm, dopt, lambda lg, b: cross_entropy_loss(lg, b["target"]), donate=False
    )
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T + 1)), jnp.int32)
    batch = {"input": toks[:, :-1], "target": toks[:, 1:]}
    # CPU steps are ~1 ms: the median needs a deep sample to resolve a <1%
    # delta on a shared host; TPU steps are long enough for a short loop
    iters = 30 if on_tpu else 100

    # warmup/compile once; both loops then run the identical program
    p, s = params, opt_state
    for _ in range(3):
        p, s, loss = step(p, s, batch)
    float(loss)

    def _median(xs):
        xs = sorted(xs)
        return xs[len(xs) // 2]

    def bare_loop():
        p, s = params, opt_state
        ts = [time.perf_counter()]
        for _ in range(iters):
            p, s, loss = step(p, s, batch)
            float(loss)  # the sync the anomaly guard also pays
            ts.append(time.perf_counter())
        # median, not mean: a single GC pause / scheduler hiccup on a
        # millisecond-scale CPU step would otherwise dominate the delta
        return _median([b - a for a, b in zip(ts, ts[1:])])

    def resilient_loop():
        root = tempfile.mkdtemp(prefix="bench_resilience_")
        # armed but quiescent: schedule installed, nothing ever fires
        faultsim.arm([Fault("preempt", at_step=10**9)])
        ts = []
        try:
            run_resilient(
                step_fn=step,
                params=params,
                opt_state=opt_state,
                manager=CheckpointManager(root, keep=1),
                batch_fn=lambda i: batch,
                total_steps=iters + 1,  # the final step always saves;
                save_every=10**9,       # keep it out of the timed window
                async_save=False,
                anomaly=AnomalyPolicy(threshold=3),
                install_signal_handlers=True,
                on_step=lambda i, l: ts.append(time.perf_counter()),
            )
        finally:
            faultsim.disarm()
        return _median([b - a for a, b in zip(ts, ts[1:])][: iters - 1])

    def layer_host_cost():
        """Pure host cost per step of the armed loop machinery, isolated
        from XLA/scheduler noise by a no-op step_fn: the resilience layer
        adds ONLY host-side bookkeeping (it runs the same compiled
        program), so its true per-step price is (armed - bare) around a
        step that costs ~nothing."""
        nul_iters = 2000
        nop_out = ({"w": np.float32(0)}, {"m": np.float32(0)}, 1.0)

        def nop_step(p, o, b, k=None):
            return nop_out

        t0 = time.perf_counter()
        for _ in range(nul_iters):
            out = nop_step(None, None, batch)
            float(out[2])
        bare_nop = (time.perf_counter() - t0) / nul_iters
        root = tempfile.mkdtemp(prefix="bench_resilience_nop_")
        faultsim.arm([Fault("preempt", at_step=10**9)])
        ts = []
        try:
            run_resilient(
                step_fn=nop_step,
                params=nop_out[0],
                opt_state=nop_out[1],
                manager=CheckpointManager(root, keep=1),
                batch_fn=lambda i: batch,
                total_steps=nul_iters + 1,
                save_every=10**9,
                async_save=False,
                anomaly=AnomalyPolicy(threshold=3),
                install_signal_handlers=True,
                on_step=lambda i, l: ts.append(time.perf_counter()),
            )
        finally:
            faultsim.disarm()
        deltas = sorted(b - a for a, b in zip(ts, ts[1:]))[: nul_iters - 1]
        armed_nop = sum(deltas) / len(deltas)
        return max(0.0, armed_nop - bare_nop)

    # interleave and take best-of-two each: bounds drift on shared hosts
    base = bare_loop()
    armed = resilient_loop()
    base = min(base, bare_loop())
    armed = min(armed, resilient_loop())
    layer = layer_host_cost()
    print(json.dumps({
        # "_cpu" suffix off-TPU: the name says the step was a CPU step.
        # Headline value = deterministic layer host cost / real step time;
        # wall_delta_frac is the raw (noisier) wall-clock cross-check.
        "metric": "resilience_overhead_frac" if on_tpu else "resilience_overhead_frac_cpu",
        "value": round(layer / base, 5) if base > 0 else None,
        "unit": "fraction",
        "layer_host_us_per_step": round(layer * 1e6, 2),
        "step_ms_bare": round(base * 1e3, 3),
        "step_ms_armed": round(armed * 1e3, 3),
        "wall_delta_frac": round((armed - base) / base, 4) if base > 0 else None,
        "iters": iters,
        "acceptance_lt": 0.01,
    }))


def bench_watchdog():
    """Watchdog+consistency overhead rung (VESCALE_BENCH=watchdog): the
    multi-host resilience layer's armed-but-quiescent per-step price — a
    live watchdog (heartbeat per step boundary, deadline never reached),
    coordinated-mode control exchange (trivial on one process, exactly the
    host path multi-host runs pay minus the wire), and consistency
    fingerprints at the default cadence (every 32 steps).  Isolated from
    XLA noise the same way bench_resilience's layer_host_cost is: the
    delta between two no-op-step run_resilient loops that differ ONLY in
    watchdog+coordination arming, expressed as a fraction of a real
    (small-llama) step.  Acceptance: < 1%."""
    import tempfile

    import jax
    import jax.numpy as jnp
    import optax

    from vescale_tpu.checkpoint import CheckpointManager
    from vescale_tpu.dmodule import parallelize_module
    from vescale_tpu.mesh import DeviceMesh
    from vescale_tpu.models.llama import Llama, LlamaConfig, llama_plan
    from vescale_tpu.models.nanogpt import cross_entropy_loss
    from vescale_tpu.parallel.optimizer import DistributedOptimizer
    from vescale_tpu.resilience import Watchdog, run_resilient
    from vescale_tpu.train import make_train_step

    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    B, T = (4, 1024) if on_tpu else (2, 64)
    cfg = LlamaConfig(
        vocab_size=2048 if on_tpu else 128,
        hidden_size=256 if on_tpu else 32,
        intermediate_size=512 if on_tpu else 64,
        num_hidden_layers=4 if on_tpu else 2,
        num_attention_heads=4 if on_tpu else 2,
        num_key_value_heads=4 if on_tpu else 2,
        max_position_embeddings=T,
        dtype=jnp.bfloat16 if on_tpu else jnp.float32,
    )
    mesh = DeviceMesh(("dp", "tp"), (1, 1), devices=devices[:1])
    dm = parallelize_module(Llama(cfg), mesh, llama_plan(mesh, sequence_parallel=False))
    params = dm.init(jax.random.key(0), jnp.ones((2, T), jnp.int32))["params"]
    dopt = DistributedOptimizer(optax.adamw(1e-3))
    opt_state = dopt.init(params)
    step = make_train_step(
        dm, dopt, lambda lg, b: cross_entropy_loss(lg, b["target"]), donate=False
    )
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T + 1)), jnp.int32)
    batch = {"input": toks[:, :-1], "target": toks[:, 1:]}
    iters = 30 if on_tpu else 100

    p, s = params, opt_state
    for _ in range(3):  # compile outside every timed window
        p, s, loss = step(p, s, batch)
    float(loss)

    def _median(xs):
        xs = sorted(xs)
        return xs[len(xs) // 2]

    def real_step_time():
        p, s = params, opt_state
        ts = [time.perf_counter()]
        for _ in range(iters):
            p, s, loss = step(p, s, batch)
            float(loss)
            ts.append(time.perf_counter())
        return _median([b - a for a, b in zip(ts, ts[1:])])

    nop_out = ({"w": np.float32(0)}, {"m": np.float32(0)}, 1.0)

    def _nop_loop(nul_iters, **kw):
        root = tempfile.mkdtemp(prefix="bench_watchdog_")
        ts = []
        run_resilient(
            step_fn=lambda p, o, b, k=None: nop_out,
            params=nop_out[0],
            opt_state=nop_out[1],
            manager=CheckpointManager(root, keep=1),
            batch_fn=lambda i: batch,
            total_steps=nul_iters + 1,
            save_every=10**9,  # the forced final save stays untimed
            async_save=False,
            install_signal_handlers=False,
            on_step=lambda i, l: ts.append(time.perf_counter()),
            **kw,
        )
        deltas = sorted(b - a for a, b in zip(ts, ts[1:]))[: nul_iters - 1]
        return sum(deltas) / len(deltas)

    nul_iters = 2000
    wd = Watchdog(timeout_s=3600.0, abort=False)  # armed, never due
    wd.start()
    try:
        armed = _nop_loop(nul_iters, watchdog=wd)
        coord = _nop_loop(nul_iters, watchdog=wd, coordinate=True, consistency_every=32)
        plain = _nop_loop(nul_iters)
        armed = min(armed, _nop_loop(nul_iters, watchdog=wd))
        coord = min(coord, _nop_loop(
            nul_iters, watchdog=wd, coordinate=True, consistency_every=32
        ))
        plain = min(plain, _nop_loop(nul_iters))
    finally:
        wd.stop()
    wd_layer = max(0.0, armed - plain)  # the watchdog heartbeat alone
    coord_layer = max(0.0, coord - plain)  # + control exchange + fingerprints
    base = real_step_time()
    assert wd.fired == 0, "watchdog fired during a quiescent bench"
    print(json.dumps({
        "metric": "watchdog_overhead_frac" if on_tpu else "watchdog_overhead_frac_cpu",
        "value": round(wd_layer / base, 6) if base > 0 else None,
        "unit": "fraction",
        "watchdog_us_per_step": round(wd_layer * 1e6, 2),
        "coord_us_per_step": round(coord_layer * 1e6, 2),
        "coord_overhead_frac": round(coord_layer / base, 5) if base > 0 else None,
        "step_ms_real": round(base * 1e3, 3),
        "nop_us_plain": round(plain * 1e6, 2),
        "iters": nul_iters,
        "acceptance_lt": 0.01,
    }))


def bench_serve():
    """Serving rung (VESCALE_BENCH=serve): continuous-batching throughput
    and latency under a synthetic open-loop load — tokens/s, p50/p99
    time-to-first-token, shed rate — plus the armed-but-quiescent
    resilience overhead of the serve loop measured the watchdog-rung way:
    the SAME load runs bare and with the full envelope armed (live
    watchdog, single-proc coordinated control exchange, faultsim schedule
    that never fires), and the per-loop-iteration delta is reported as a
    fraction of a real decode step.  Acceptance: < 1%."""
    import jax
    import jax.numpy as jnp

    from vescale_tpu.mesh import DeviceMesh
    from vescale_tpu.models.llama import Llama, LlamaConfig
    from vescale_tpu.resilience import Watchdog, faultsim
    from vescale_tpu.serve import (
        ContinuousBatchingScheduler,
        DecodeStep,
        KVCacheConfig,
        PagedKVCache,
        Request,
        ServeEngine,
        run_serve_resilient,
    )

    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    cfg = LlamaConfig(
        vocab_size=2048 if on_tpu else 512,
        hidden_size=256 if on_tpu else 64,
        intermediate_size=512 if on_tpu else 128,
        num_hidden_layers=4,
        num_attention_heads=8,
        num_key_value_heads=8,
        max_position_embeddings=128,
        dtype=jnp.bfloat16 if on_tpu else jnp.float32,
    )
    mesh = DeviceMesh(("tp",), (1,), devices=devices[:1])
    model = Llama(cfg)
    params = model.init(jax.random.key(0), jnp.ones((1, 8), jnp.int32))["params"]

    kc = KVCacheConfig(
        layers=cfg.num_hidden_layers, kv_heads=cfg.num_key_value_heads,
        head_dim=cfg.head_dim, num_slots=8, page_size=8, pages_per_slot=8,
    )
    cache = PagedKVCache(kc, mesh)
    engine = ServeEngine(cfg, mesh, params, cache)

    def build(eng=engine, c=cache, max_queue=8):
        # ONE compiled engine for every run: reset returns slots/pages to
        # the pool, so timed windows never include a recompile
        c.reset()
        sched = ContinuousBatchingScheduler(c, max_queue=max_queue)
        return eng, sched

    rng = np.random.default_rng(0)
    n_requests = 64 if not on_tpu else 96
    arrivals = []
    for i in range(n_requests):
        prompt = tuple(int(x) for x in rng.integers(1, cfg.vocab_size - 1, 8))
        # ~2 arrivals/step against 8 slots: a real overload, so the
        # bounded queue sheds and the shed-rate number is non-vacuous
        arrivals.append((i // 2, Request(rid=i, prompt=prompt, max_new_tokens=8)))

    def _median(xs):
        xs = sorted(xs)
        return xs[len(xs) // 2]

    def run_once(eng, c, arr, max_queue=8, **kw):
        eng, sched = build(eng, c, max_queue)
        iters = []
        last = [None]

        def on_step(step, active):
            now = time.perf_counter()
            if last[0] is not None:
                iters.append(now - last[0])
            last[0] = now

        t0 = time.perf_counter()
        res = run_serve_resilient(
            engine=eng, scheduler=sched, arrivals=arr,
            install_signal_handlers=False, on_step=on_step, **kw,
        )
        wall = time.perf_counter() - t0
        return res, sched, wall, iters

    # ------------------------------------------------ throughput/latency
    run_once(engine, cache, arrivals, coordinate=False)  # compile warmup
    res, sched, wall, bare_iters = run_once(engine, cache, arrivals, coordinate=False)
    gen_tokens = sum(len(o["tokens"]) for o in res.outcomes.values())
    # goodput vs raw: only COMPLETED requests' tokens are goodput — the
    # gap is work burned on shed/evicted/timed-out requests (ISSUE 12)
    goodput_tokens = sum(
        len(o["tokens"]) for o in res.outcomes.values() if o["status"] == "completed"
    )
    ttft_p50 = sched._ttft.percentile(0.5)
    ttft_p99 = sched._ttft.percentile(0.99)
    itl_p50 = sched._itl.percentile(0.5)
    itl_p99 = sched._itl.percentile(0.99)
    shed_rate = sched.counts["shed"] / max(1, sched.counts["submitted"])
    step_real = _median(bare_iters)

    # ---------------------------- throughput multipliers (ISSUE 15)
    # (a) shared-prefix workload leg: the SAME load with the radix-tree
    # prefix cache off vs on — prefill-token savings is the headline
    # (acceptance: > 50% on the shared-prefix workload); (b) speculative
    # on/off leg: reduced-depth drafter + batched verify vs plain decode —
    # the acceptance rate must be NONZERO even on CPU (the tokens/s delta
    # is honest either way: a tiny CPU model rarely wins from drafting)
    from vescale_tpu.serve import PrefixCache, SpeculativeDecoder, slice_drafter_params

    mrng = np.random.default_rng(7)
    shared_sys = tuple(int(x) for x in mrng.integers(1, cfg.vocab_size - 1, 48))
    mult_arrivals = []
    for i in range(24):
        tail = tuple(int(x) for x in mrng.integers(1, cfg.vocab_size - 1, 2 + i % 3))
        mult_arrivals.append((i // 2, Request(
            rid=i, prompt=shared_sys + tail, max_new_tokens=8,
        )))

    def run_mult(prefix=False, spec=None):
        cache.reset()
        pc = PrefixCache(cache) if prefix else None
        sched = ContinuousBatchingScheduler(cache, max_queue=len(mult_arrivals),
                                            prefix_cache=pc)
        t0 = time.perf_counter()
        res = run_serve_resilient(
            engine=engine, scheduler=sched, arrivals=mult_arrivals,
            install_signal_handlers=False, coordinate=False, speculative=spec,
        )
        wall = time.perf_counter() - t0
        assert sched.counts["shed"] == 0, sched.counts  # savings math needs all admitted
        toks = sum(len(o["tokens"]) for o in res.outcomes.values())
        return res, sched, pc, wall, toks

    run_mult()  # warmup (the shared-prefix prompt length compiles nothing new)
    _, _, _, base_wall, base_toks = run_mult()
    _, _, _, _, _ = run_mult(prefix=True)  # warmup the suffix-chunk program
    _, sched_px, pc, px_wall, px_toks = run_mult(prefix=True)
    assert px_toks == base_toks  # bit-identical streams -> same token count
    prefix_savings = pc.stats.hit_tokens / max(1, pc.stats.prompt_tokens)

    spec = SpeculativeDecoder(engine, slice_drafter_params(params, 2),
                              drafter_layers=2, k=4)
    run_mult(spec=spec)  # warmup compiles drafter + verify programs
    spec.drafted = spec.accepted = spec.verify_steps = 0
    _, _, _, spec_wall, spec_toks = run_mult(spec=spec)
    assert spec_toks == base_toks
    spec_accept = spec.accept_rate() or 0.0

    # -------------------------------------- quiescent envelope overhead
    # the watchdog-rung method: a NOP engine isolates the loop's per-step
    # HOST path (beat + faultsim consults + control exchange + scheduler
    # bookkeeping) from XLA noise over thousands of steps; the delta
    # between armed and bare nop loops is the envelope's price, expressed
    # as a fraction of the real decode step above
    class _NopEngine:
        greedy = staticmethod(ServeEngine.greedy)

        def __init__(self, slots, vocab):
            self._p = np.zeros((vocab,), np.float32)
            self._d = DecodeStep(np.zeros((slots,), np.int32), np.zeros((slots, vocab), np.float32))

        def prefill(self, prompt, slot):
            return self._p

        def decode(self, tokens):
            return self._d

    nul_iters = 2000
    nop_slots, nop_vocab = 4, 8
    nop_kc = KVCacheConfig(layers=1, kv_heads=1, head_dim=1, num_slots=nop_slots,
                           page_size=32, pages_per_slot=32)
    nop_cache = PagedKVCache(nop_kc, mesh)
    nop_eng = _NopEngine(nop_slots, nop_vocab)
    # each request's FIRST token comes from prefill, so it contributes
    # max_new-1 decode steps: +1 makes 16 requests over nop_slots slots
    # cover >= nul_iters decode iterations
    per_req = nul_iters * nop_slots // 16 + 1
    nop_arr = [
        (0, Request(rid=i, prompt=(1, 2), max_new_tokens=per_req))
        for i in range(16)
    ]

    def nop_median(**kw):
        # queue bound >= request count: every request admits (shedding here
        # would halve the iteration count the sizing math assumes)
        res, sched, _, iters = run_once(nop_eng, nop_cache, nop_arr,
                                        max_queue=len(nop_arr), **kw)
        assert sched.counts["shed"] == 0 and res.steps >= nul_iters, (
            sched.counts, res.steps)
        trimmed = sorted(iters)[: max(1, len(iters) - 10)]
        return sum(trimmed) / len(trimmed)

    wd = Watchdog(timeout_s=3600.0, abort=False).start()
    faultsim.arm(faultsim.parse_schedule("slow_decode:step=10000000"))  # armed, never due
    try:
        armed = nop_median(coordinate=True, watchdog=wd)
        plain = nop_median(coordinate=False)
        armed = min(armed, nop_median(coordinate=True, watchdog=wd))
        plain = min(plain, nop_median(coordinate=False))
    finally:
        faultsim.disarm()
        wd.stop()
    assert wd.fired == 0, "watchdog fired during a quiescent serve bench"
    overhead = max(0.0, armed - plain)

    # -------------------- request tracing + ops endpoints overhead
    # the ISSUE-12 acceptance bar: the SAME nop load with per-request
    # lifecycle spans recording (live ndtimeline) AND the ops HTTP thread
    # up, vs the plain loop above — per-iteration delta as a fraction of a
    # real decode step must stay under the <1% envelope bar
    from vescale_tpu.ndtimeline import api as nd_api

    from vescale_tpu.analysis import envreg

    old_mgr, old_active = nd_api._MANAGER, nd_api._ACTIVE
    old_ops_port = envreg.get_raw("VESCALE_SERVE_OPS_PORT")
    os.environ["VESCALE_SERVE_OPS_PORT"] = "0"
    try:
        nd_api.init_ndtimers(rank=0, max_spans=200_000)
        traced = nop_median(coordinate=False)
        nd_api.get_manager().flush()  # drop the spans between runs
        traced = min(traced, nop_median(coordinate=False))
    finally:
        if old_ops_port is None:
            os.environ.pop("VESCALE_SERVE_OPS_PORT", None)
        else:
            os.environ["VESCALE_SERVE_OPS_PORT"] = old_ops_port
        nd_api._MANAGER, nd_api._ACTIVE = old_mgr, old_active
    obs_overhead = max(0.0, traced - plain)
    print(json.dumps({
        "metric": "serve_tokens_per_s" if on_tpu else "serve_tokens_per_s_cpu",
        "value": round(gen_tokens / wall, 2),
        "unit": "tokens/s",
        "requests": n_requests,
        "completed": sched.counts["completed"],
        "shed_rate": round(shed_rate, 4),
        "ttft_p50_ms": round(ttft_p50 * 1e3, 3) if ttft_p50 else None,
        "ttft_p99_ms": round(ttft_p99 * 1e3, 3) if ttft_p99 else None,
        "decode_steps": res.steps,
        "decode_step_ms": round(step_real * 1e3, 3),
        "goodput_tokens_per_s": round(goodput_tokens / wall, 2),
        "goodput_fraction": round(goodput_tokens / max(1, gen_tokens), 4),
        "itl_p50_ms": round(itl_p50 * 1e3, 3) if itl_p50 else None,
        "itl_p99_ms": round(itl_p99 * 1e3, 3) if itl_p99 else None,
        # throughput multipliers (ISSUE 15): shared-prefix + spec-decode legs
        "prefix_savings_frac": round(prefix_savings, 4),
        "prefix_hit_tokens": pc.stats.hit_tokens,
        "prefix_tokens_per_s": round(px_toks / px_wall, 2),
        "baseline_tokens_per_s": round(base_toks / base_wall, 2),
        "spec_accept_rate": round(spec_accept, 4),
        "spec_drafted": spec.drafted,
        "spec_tokens_per_s": round(spec_toks / spec_wall, 2),
        "prefix_savings_acceptance_gt": 0.5,
        "resilience_overhead_frac": round(overhead / step_real, 5) if step_real > 0 else None,
        "resilience_overhead_us_per_step": round(overhead * 1e6, 2),
        "obs_overhead_frac": round(obs_overhead / step_real, 5) if step_real > 0 else None,
        "obs_overhead_us_per_step": round(obs_overhead * 1e6, 2),
        "nop_iters": nul_iters,
        "acceptance_lt": 0.01,
    }))


def bench_alerts():
    """Alert-engine overhead rung (VESCALE_BENCH=alerts): the sensing
    layer's per-decode-step price — the history-store append plus
    rule-pack evaluation that ``telemetry.record_step(kind="serve")``
    runs at every step boundary — priced the quiescent-envelope way and
    expressed as a fraction of a real decode step.

    The layer has two cost regimes, so the envelope has two legs, each
    the delta between tight ``record_step`` loops differing ONLY in
    timeseries+alerts arming (the default serve pack, armed over
    representative HEALTHY series):

      * guard leg (default cadence/eval-interval): almost every step is
        rate-limited to two clock-read guards — the price every decode
        step pays;
      * fire leg (cadence 0, eval interval 0): EVERY step snapshots the
        registry into the rings and evaluates every rule — the price a
        step pays when the limiters come due.

    A real decode step of duration T amortizes to
    ``guard + fire * T / eval_interval`` (conservative: it bills the
    store snapshot at the 0.25 s rule cadence though it actually fires
    at the 1 s sample cadence).  Acceptance: that amortized cost < 1% of
    the real decode step, with nothing fired while quiescent."""
    import jax
    import jax.numpy as jnp

    from vescale_tpu import telemetry
    from vescale_tpu.analysis import envreg
    from vescale_tpu.mesh import DeviceMesh
    from vescale_tpu.models.llama import Llama, LlamaConfig
    from vescale_tpu.serve import (
        ContinuousBatchingScheduler,
        KVCacheConfig,
        PagedKVCache,
        Request,
        ServeEngine,
        run_serve_resilient,
    )
    from vescale_tpu.telemetry import alerts as _alerts

    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"

    # ------------------------- denominator: a real decode step (the
    # serve-rung model class), measured with telemetry DORMANT so the
    # layer under test is absent from its own denominator
    assert not telemetry.is_active()
    cfg = LlamaConfig(
        vocab_size=2048 if on_tpu else 512,
        hidden_size=256 if on_tpu else 64,
        intermediate_size=512 if on_tpu else 128,
        num_hidden_layers=4,
        num_attention_heads=8,
        num_key_value_heads=8,
        max_position_embeddings=128,
        dtype=jnp.bfloat16 if on_tpu else jnp.float32,
    )
    mesh = DeviceMesh(("tp",), (1,), devices=devices[:1])
    model = Llama(cfg)
    params = model.init(jax.random.key(0), jnp.ones((1, 8), jnp.int32))["params"]
    kc = KVCacheConfig(
        layers=cfg.num_hidden_layers, kv_heads=cfg.num_key_value_heads,
        head_dim=cfg.head_dim, num_slots=8, page_size=8, pages_per_slot=8,
    )
    cache = PagedKVCache(kc, mesh)
    engine = ServeEngine(cfg, mesh, params, cache)
    rng = np.random.default_rng(0)
    arrivals = [
        (i // 2, Request(
            rid=i,
            prompt=tuple(int(x) for x in rng.integers(1, cfg.vocab_size - 1, 8)),
            max_new_tokens=8,
        ))
        for i in range(32)
    ]

    def decode_iters():
        cache.reset()
        sched = ContinuousBatchingScheduler(cache, max_queue=len(arrivals))
        iters, last = [], [None]

        def on_step(step, active):
            now = time.perf_counter()
            if last[0] is not None:
                iters.append(now - last[0])
            last[0] = now

        run_serve_resilient(
            engine=engine, scheduler=sched, arrivals=arrivals,
            install_signal_handlers=False, coordinate=False, on_step=on_step,
        )
        assert sched.counts["shed"] == 0, sched.counts
        return iters

    def _median(xs):
        xs = sorted(xs)
        return xs[len(xs) // 2]

    decode_iters()  # compile warmup
    step_real = _median(decode_iters())

    # ------------------------- the layer, isolated from XLA: tight
    # record_step loops (nothing else in the body), min of two runs
    def _quiescent_metrics(reg):
        # representative HEALTHY series: every pack rule has real data to
        # reduce over, none of it anywhere near a threshold
        reg.gauge("serve_shed_rate").set(0.0)
        reg.gauge("serve_queue_depth").set(2.0)
        reg.gauge("serve_goodput_fraction").set(0.95)
        reg.gauge("serve_free_pages").set(100.0)
        h = reg.histogram("serve_ttft_seconds")
        for _ in range(64):
            h.observe(0.005)

    def layer_loop(n, armed, cadence=None, eval_s=None):
        old = os.environ.get("VESCALE_ALERTS_EVAL_INTERVAL_S")  # vescale-lint: disable=VSC201 (save/restore around init)
        if eval_s is not None:
            os.environ["VESCALE_ALERTS_EVAL_INTERVAL_S"] = str(eval_s)
        try:
            telemetry.init(out_dir=None, memtrack=False, timeseries=armed,
                           alerts=armed, timeseries_cadence_s=cadence)
            _quiescent_metrics(telemetry.get_registry())
            if armed:
                assert _alerts.get_engine().arm_pack(
                    "serve", _alerts.serve_rule_pack(slo_ttft_s=1.0))
            for _ in range(100):  # steady state: rings warm, rules evaluated
                telemetry.record_step({"q": 2}, kind="serve")
            t0 = time.perf_counter()
            for _ in range(n):
                telemetry.record_step({"q": 2}, kind="serve")
            per = (time.perf_counter() - t0) / n
            if armed:
                p = _alerts.payload()
                assert p["counts"]["fired"] == 0 and not p["firing"], (
                    "alert fired during a quiescent bench", p)
            return per
        finally:
            telemetry.shutdown()
            if eval_s is not None:
                if old is None:
                    os.environ.pop("VESCALE_ALERTS_EVAL_INTERVAL_S", None)
                else:
                    os.environ["VESCALE_ALERTS_EVAL_INTERVAL_S"] = old

    guard_iters, fire_iters = 20_000, 2_000
    plain = min(layer_loop(guard_iters, armed=False) for _ in range(2))
    guard = min(layer_loop(guard_iters, armed=True) for _ in range(2))
    fire = min(layer_loop(fire_iters, armed=True, cadence=0.0, eval_s=0.0)
               for _ in range(2))
    guard_cost = max(0.0, guard - plain)
    fire_cost = max(0.0, fire - plain)

    eval_interval = envreg.get_float("VESCALE_ALERTS_EVAL_INTERVAL_S")
    cadence = envreg.get_float("VESCALE_TIMESERIES_CADENCE_S")
    amortized = guard_cost + fire_cost * step_real / eval_interval
    frac = amortized / step_real if step_real > 0 else None
    print(json.dumps({
        "metric": "alerts_overhead_frac" if on_tpu else "alerts_overhead_frac_cpu",
        "value": round(frac, 6) if frac is not None else None,
        "unit": "fraction",
        "guard_us_per_step": round(guard_cost * 1e6, 3),
        "fire_us_per_eval": round(fire_cost * 1e6, 2),
        "amortized_us_per_step": round(amortized * 1e6, 2),
        "eval_interval_s": eval_interval,
        "cadence_s": cadence,
        "step_ms_real": round(step_real * 1e3, 3),
        "rules_armed": len(_alerts.serve_rule_pack(slo_ttft_s=1.0)),
        "guard_iters": guard_iters,
        "fire_iters": fire_iters,
        "acceptance_lt": 0.01,
    }))
    assert frac is not None and frac < 0.01, (frac, guard_cost, fire_cost)


def bench_costaudit():
    """Cost-audit overhead rung (VESCALE_BENCH=costaudit): the plan-vs-
    reality layer's per-step price — a prediction/measurement ledger join
    plus the ``audit_step`` harvest-and-publish that rides every
    ``telemetry.record_step`` — expressed as a fraction of a real compiled
    train step.

    Both legs run the IDENTICAL body (record_prediction + joined
    record_measurement + record_step): with costaudit dormant the first
    two are the module-level no-op hooks, so the delta is exactly the
    armed layer.  Acceptance: < 1% of the real step."""
    import jax
    import jax.numpy as jnp
    import optax

    from vescale_tpu import telemetry
    from vescale_tpu.dmodule import parallelize_module
    from vescale_tpu.mesh import DeviceMesh
    from vescale_tpu.models.llama import Llama, LlamaConfig, llama_plan
    from vescale_tpu.models.nanogpt import cross_entropy_loss
    from vescale_tpu.parallel.optimizer import DistributedOptimizer
    from vescale_tpu.telemetry import costaudit
    from vescale_tpu.train import make_train_step

    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    B, T = (4, 1024) if on_tpu else (2, 64)
    cfg = LlamaConfig(
        vocab_size=2048 if on_tpu else 128,
        hidden_size=256 if on_tpu else 32,
        intermediate_size=512 if on_tpu else 64,
        num_hidden_layers=4 if on_tpu else 2,
        num_attention_heads=4 if on_tpu else 2,
        num_key_value_heads=4 if on_tpu else 2,
        max_position_embeddings=T,
        dtype=jnp.bfloat16 if on_tpu else jnp.float32,
    )
    mesh = DeviceMesh(("dp", "tp"), (1, 1), devices=devices[:1])
    dm = parallelize_module(Llama(cfg), mesh, llama_plan(mesh, sequence_parallel=False))
    params = dm.init(jax.random.key(0), jnp.ones((2, T), jnp.int32))["params"]
    dopt = DistributedOptimizer(optax.adamw(1e-3))
    opt_state = dopt.init(params)
    step = make_train_step(
        dm, dopt, lambda lg, b: cross_entropy_loss(lg, b["target"]), donate=False
    )
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T + 1)), jnp.int32)
    batch = {"input": toks[:, :-1], "target": toks[:, 1:]}

    # denominator: the real step, telemetry DORMANT
    assert not telemetry.is_active()
    p, s = params, opt_state
    for _ in range(3):
        p, s, loss = step(p, s, batch)
    float(loss)
    iters = 20 if on_tpu else 5
    t0 = time.perf_counter()
    for _ in range(iters):
        p, s, loss = step(p, s, batch)
    float(loss)
    step_real = (time.perf_counter() - t0) / iters

    def layer_loop(n, armed):
        telemetry.init(out_dir=None, memtrack=False, timeseries=False,
                       alerts=False, costaudit=armed)
        try:
            for _ in range(100):  # steady state: ledger warm, ring bounded
                pid = costaudit.record_prediction("bench", predicted_us=100.0)
                costaudit.record_measurement(pid, measured_us=110.0)
                telemetry.record_step({"q": 2}, kind="train")
            t0 = time.perf_counter()
            for _ in range(n):
                pid = costaudit.record_prediction("bench", predicted_us=100.0)
                costaudit.record_measurement(pid, measured_us=110.0)
                telemetry.record_step({"q": 2}, kind="train")
            per = (time.perf_counter() - t0) / n
            return per, costaudit.audit_summary()
        finally:
            telemetry.shutdown()

    loop_iters = 20_000
    plain = min(layer_loop(loop_iters, armed=False)[0] for _ in range(2))
    armed_runs = [layer_loop(loop_iters, armed=True) for _ in range(2)]
    armed = min(per for per, _ in armed_runs)
    audit = armed_runs[-1][1]
    cost = max(0.0, armed - plain)
    frac = cost / step_real if step_real > 0 else None
    assert audit is not None and audit["matched"] >= loop_iters, audit
    print(json.dumps({
        "metric": "costaudit_overhead_frac" if on_tpu else "costaudit_overhead_frac_cpu",
        "value": round(frac, 6) if frac is not None else None,
        "unit": "fraction",
        "audit_us_per_step": round(cost * 1e6, 3),
        "step_ms_real": round(step_real * 1e3, 3),
        "loop_iters": loop_iters,
        "audit": audit,
        "acceptance_lt": 0.01,
    }))
    assert frac is not None and frac < 0.01, (frac, cost, step_real)


def bench_kernels():
    """Kernel rung (VESCALE_BENCH=kernels): per-kernel kernel-vs-XLA wall
    time at 2-3 shapes plus an interpret-mode parity assertion, one JSON
    line.  On TPU the kernel leg runs COMPILED (VESCALE_KERNELS=on) and
    the speedup column is the headline; on CPU the kernel leg runs the
    pallas INTERPRETER — wall times are recorded for the record (the
    interpreter is expected to lose) and the parity numbers are the
    point.  Every sub-line carries the kernel mode it ran —
    which is SET for the rung's duration (the kernel legs go through the
    public dispatching call sites), then restored."""
    import jax

    from vescale_tpu.analysis import envreg

    on_tpu = jax.devices()[0].platform == "tpu"
    kmode = "on" if on_tpu else "interpret"
    prev_mode = envreg.get_raw("VESCALE_KERNELS")
    os.environ["VESCALE_KERNELS"] = kmode
    try:
        _bench_kernels_impl(on_tpu, kmode)
    finally:
        if prev_mode is None:
            os.environ.pop("VESCALE_KERNELS", None)
        else:
            os.environ["VESCALE_KERNELS"] = prev_mode


def _bench_kernels_impl(on_tpu, kmode):
    import jax
    import jax.numpy as jnp

    interp = not on_tpu
    iters = 20 if on_tpu else 3

    def timed(fn, *args):
        out = jax.block_until_ready(fn(*args))  # compile + warmup
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters, out

    # the one documented parity metric (docs/kernels.md)
    from vescale_tpu.kernels import ulps_at_scale as ulps

    rng = np.random.default_rng(0)
    per_kernel = {}

    # ------------------------------------------------------------- flash
    from vescale_tpu.ops.flash_attention import _dense_ref, flash_attention

    rows = []
    for (B, T, H, D) in ((1, 512, 8, 64), (1, 1024, 8, 64)) if on_tpu else ((1, 128, 4, 32), (1, 256, 4, 32)):
        q, k, v = (jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32) for _ in range(3))
        scale = 1.0 / (D ** 0.5)
        xla = jax.jit(lambda q, k, v: _dense_ref(q, k, v, scale, True))
        ker = jax.jit(lambda q, k, v: flash_attention(q, k, v, interpret=interp))
        t_x, o_x = timed(xla, q, k, v)
        t_k, o_k = timed(ker, q, k, v)
        rows.append({"shape": [B, T, H, D], "xla_ms": round(t_x * 1e3, 3),
                     "kernel_ms": round(t_k * 1e3, 3),
                     "speedup": round(t_x / t_k, 3), "max_ulp": ulps(o_k, o_x)})
        assert np.allclose(np.asarray(o_k), np.asarray(o_x), rtol=2e-5, atol=2e-5)
    per_kernel["flash_attention"] = rows

    # ------------------------------------------------------ paged decode
    from vescale_tpu.kernels.paged_attention import paged_decode

    rows = []
    for (S, Pmax, page, KV, hd, H) in ((8, 8, 16, 8, 128, 8), (16, 16, 16, 8, 128, 16)) if on_tpu else ((4, 4, 8, 4, 32, 8), (8, 8, 8, 4, 32, 8)):
        N = S * Pmax + 1
        Tmax = page * Pmax
        kp = jnp.asarray(rng.normal(size=(N, page, KV, hd)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(N, page, KV, hd)), jnp.float32)
        q = jnp.asarray(rng.normal(size=(S, H, hd)), jnp.float32)
        table = jnp.asarray(
            rng.permutation(np.arange(1, N))[: S * Pmax].reshape(S, Pmax), jnp.int32)
        lengths = jnp.asarray(rng.integers(1, Tmax + 1, S), jnp.int32)
        scale = 1.0 / (hd ** 0.5)

        def xla_chain(q, kp, vp, table, lengths):
            ks = jnp.take(kp, table, axis=0).reshape(S, Tmax, KV, hd)
            vs = jnp.take(vp, table, axis=0).reshape(S, Tmax, KV, hd)
            qg = (q * scale).reshape(S, KV, H // KV, hd)
            s = jnp.einsum("skgd,stkd->skgt", qg, ks)
            mask = jnp.arange(Tmax, dtype=jnp.int32)[None, :] < lengths[:, None]
            s = jnp.where(mask[:, None, None, :], s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("skgt,stkd->skgd", p, vs).reshape(S, H, hd)

        xla = jax.jit(xla_chain)
        # the kernel takes the whole 5-D pool and a layer index: one layer here
        ker = jax.jit(lambda q, kp, vp, table, lengths: paged_decode(
            q, kp[None], vp[None], table, lengths, layer=0, scale=scale, interpret=interp))
        t_x, o_x = timed(xla, q, kp, vp, table, lengths)
        t_k, o_k = timed(ker, q, kp, vp, table, lengths)
        rows.append({"shape": {"slots": S, "pages_per_slot": Pmax, "page": page,
                               "kv_heads": KV, "head_dim": hd, "q_heads": H},
                     "xla_ms": round(t_x * 1e3, 3), "kernel_ms": round(t_k * 1e3, 3),
                     "speedup": round(t_x / t_k, 3), "max_ulp": ulps(o_k, o_x)})
        assert np.allclose(np.asarray(o_k), np.asarray(o_x), rtol=2e-5, atol=2e-5)
    per_kernel["paged_decode"] = rows

    # ------------------------------------------------------- fused adamw
    from vescale_tpu.kernels.fused_adamw import fused_adamw_update

    rows = []
    b1, b2, eps = 0.9, 0.999, 1e-8
    for n in ((1 << 22, 1 << 20) if on_tpu else (1 << 16, 1 << 14)):
        g = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
        m = jnp.asarray(rng.normal(size=(n,)), jnp.float32).astype(jnp.bfloat16)
        v = jnp.abs(jnp.asarray(rng.normal(size=(n,)), jnp.float32)).astype(jnp.bfloat16)
        c1 = jnp.asarray(1.0 - b1 ** 7, jnp.float32)
        c2 = jnp.asarray(1.0 - b2 ** 7, jnp.float32)

        def xla_chain(g, m, v, c1, c2):
            g32 = g.astype(jnp.float32)
            m32 = b1 * m.astype(jnp.float32) + (1.0 - b1) * g32
            v32 = b2 * v.astype(jnp.float32) + (1.0 - b2) * jnp.square(g32)
            u = ((m32 / c1) / (jnp.sqrt(v32 / c2) + eps)).astype(g.dtype)
            return u, m32.astype(jnp.bfloat16), v32.astype(jnp.bfloat16)

        xla = jax.jit(xla_chain)
        ker = jax.jit(lambda g, m, v, c1, c2: fused_adamw_update(
            g, m, v, c1, c2, b1=b1, b2=b2, eps=eps, state_dtype=jnp.bfloat16,
            interpret=interp))
        t_x, o_x = timed(xla, g, m, v, c1, c2)
        t_k, o_k = timed(ker, g, m, v, c1, c2)
        bitwise = all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(o_k, o_x))
        rows.append({"numel": n, "xla_ms": round(t_x * 1e3, 3),
                     "kernel_ms": round(t_k * 1e3, 3),
                     "speedup": round(t_x / t_k, 3), "bitwise": bitwise})
        # moments must be bitwise; the update tolerates 4 elementwise ulps
        # (XLA's context-dependent divide-chain rewrite; docs/kernels.md)
        assert np.array_equal(np.asarray(o_k[1]), np.asarray(o_x[1])), n
        assert np.array_equal(np.asarray(o_k[2]), np.asarray(o_x[2])), n
        du = np.abs(np.asarray(o_k[0], np.float64) - np.asarray(o_x[0], np.float64))
        assert np.all(du <= 4 * np.spacing(np.abs(np.asarray(o_x[0])))), n
    per_kernel["fused_adamw"] = rows

    # --------------------------------------------------------- fused xent
    from vescale_tpu.kernels.cross_entropy import fused_xent_parts

    rows = []
    for (Nr, Vs) in ((2048, 8192), (4096, 4096)) if on_tpu else ((128, 1024), (256, 512)):
        lg = jnp.asarray(rng.normal(size=(Nr, Vs)), jnp.float32)
        idx = jnp.asarray(rng.integers(0, Vs, Nr), jnp.int32)

        def xla_chain(lg, idx):
            gmax = jax.lax.stop_gradient(jnp.max(lg, axis=-1))
            se = jnp.sum(jnp.exp(lg - gmax[:, None]), axis=-1)
            pk = jnp.take_along_axis(lg, idx[:, None], axis=-1)[:, 0]
            return jnp.mean(gmax + jnp.log(se) - pk)

        def ker_chain(lg, idx):
            gmax = jax.lax.stop_gradient(jnp.max(lg, axis=-1))
            se, pk, _ = fused_xent_parts(lg, idx, gmax, interp)
            return jnp.mean(gmax + jnp.log(se) - pk)

        xla = jax.jit(xla_chain)
        ker = jax.jit(ker_chain)
        t_x, o_x = timed(xla, lg, idx)
        t_k, o_k = timed(ker, lg, idx)
        rows.append({"rows": Nr, "vocab_shard": Vs, "xla_ms": round(t_x * 1e3, 3),
                     "kernel_ms": round(t_k * 1e3, 3),
                     "speedup": round(t_x / t_k, 3), "max_ulp": ulps(o_k, o_x)})
        assert abs(float(o_k) - float(o_x)) < 1e-5
    per_kernel["fused_xent"] = rows

    for rows in per_kernel.values():
        for r in rows:
            r["vescale_kernels_mode"] = kmode
    speedups = [r["speedup"] for rows in per_kernel.values() for r in rows]
    geomean = float(np.exp(np.mean(np.log(np.maximum(speedups, 1e-9)))))
    print(json.dumps({
        "metric": "kernels_speedup" if on_tpu else "kernels_parity_cpu",
        "value": round(geomean, 4),
        "unit": "x_xla_geomean",
        "vescale_kernels_mode": kmode,
        "parity": "asserted (adamw bitwise; attention/xent ulp-bounded)",
        "kernels": per_kernel,
    }))


def bench_elastic():
    """Elastic-restore rung (VESCALE_BENCH=elastic): restore-and-reshard
    wall time onto a DIFFERENT mesh vs a same-shape restore of the same
    checkpoint — the price of resuming after a capacity change relative to
    an ordinary resume.  One checkpoint (sharded params + ZeRO optimizer
    state) is written from an N-device dp mesh, then loaded back (a)
    same-shape and (b) onto an N/2-device mesh via recomputed
    ``state_template`` shardings — (b) is the chunk-box reshard path the
    writer-mesh meta routes a world change to (VSC130)."""
    import tempfile

    import jax
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from vescale_tpu import checkpoint as ckpt
    from vescale_tpu.mesh import DeviceMesh
    from vescale_tpu.parallel.optimizer import DistributedOptimizer

    devices = jax.devices()
    n = len(devices)
    half = max(1, n // 2)
    on_tpu = devices[0].platform == "tpu"
    rows = 1024 if not on_tpu else 8192
    cols = 256

    def world(ndev):
        mesh = DeviceMesh(("dp",), (ndev,), devices=devices[:ndev])
        sh = NamedSharding(mesh.jax_mesh, P("dp", None))
        params = {
            f"w{i}": jax.device_put(
                np.random.default_rng(i).normal(size=(rows, cols)).astype(np.float32), sh
            )
            for i in range(4)
        }
        pspecs = {f"w{i}": P("dp", None) for i in range(4)}
        dopt = DistributedOptimizer(optax.adamw(1e-3), mesh, pspecs)
        return params, dopt

    params, dopt = world(n)
    state = dopt.init(params)
    root = tempfile.mkdtemp(prefix="bench_elastic_")
    path = f"{root}/ck"
    ckpt.save(path, {"model": params, "optimizer": state})

    def timed_load(template):
        t0 = time.perf_counter()
        ckpt.load(path, template)
        return time.perf_counter() - t0

    # same-shape template (the ordinary resume)
    same_tmpl = {"model": params, "optimizer": dopt.state_template(params)}
    # cross-shape template: half the devices, recomputed ZeRO shardings
    params_h, dopt_h = world(half)
    cross_tmpl = {"model": params_h, "optimizer": dopt_h.state_template(params_h)}

    same = min(timed_load(same_tmpl) for _ in range(3))
    cross = min(timed_load(cross_tmpl) for _ in range(3))
    degenerate = half == n  # 1-device host: no smaller world to reshard onto
    if not degenerate:
        assert ckpt.LAST_LOAD_STATS["elastic"] == 1  # the cross load resharded
    bytes_state = sum(
        int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
        for l in jax.tree_util.tree_leaves(state)
        if hasattr(l, "shape")
    ) + sum(int(np.prod(l.shape)) * 4 for l in jax.tree_util.tree_leaves(params))
    print(json.dumps({
        "metric": "elastic_reshard_ratio" if on_tpu else "elastic_reshard_ratio_cpu",
        # null on a 1-device host: both loads are the same dp=1 mesh, so a
        # "ratio" would record pure timing noise as a reshard cost
        "value": None if degenerate else (round(cross / same, 4) if same > 0 else None),
        "unit": "x_same_shape_restore",
        "same_shape_s": round(same, 4),
        "reshard_s": None if degenerate else round(cross, 4),
        "mesh": f"dp={n}->dp={half}" + (" (degenerate: no reshard ran)" if degenerate else ""),
        "state_mb": round(bytes_state / 2**20, 2),
    }))


def main():
    import jax
    import jax.numpy as jnp

    from vescale_tpu.mesh import DeviceMesh
    from vescale_tpu.dmodule import parallelize_module
    from vescale_tpu.models.llama import Llama, LlamaConfig, llama_plan
    from vescale_tpu.models.nanogpt import cross_entropy_loss
    from vescale_tpu.parallel.optimizer import adamw_lowmem
    from vescale_tpu.train import make_train_step

    devices = _tpu_devices()
    n = len(devices)
    # B=1 WITHOUT remat beats B=2 with full remat: 1.26B params + bf16 adam
    # moments + one batch of activations fit in 15.75 GB, so no forward is
    # recomputed.  B=2 needs remat (or OOMs by ~0.5 GB even with mlp-scope
    # remat).
    B, T = 1, 4096
    cfg = LlamaConfig(
        vocab_size=32000,
        hidden_size=2048,
        intermediate_size=5632,
        num_hidden_layers=24,
        num_attention_heads=16,
        num_key_value_heads=8,   # GQA, llama-3 style
        max_position_embeddings=T,
        dtype=jnp.bfloat16,
        use_flash_attention=True,  # GSPMD-partitionable (custom_partitioning)
    )
    metric = "llama1.3b_train_MFU_1chip_seq4096"

    mesh = DeviceMesh(("dp", "tp"), (n, 1), devices=devices)
    model = Llama(cfg)
    dm = parallelize_module(model, mesh, llama_plan(mesh, sequence_parallel=False))
    variables = dm.init(jax.random.key(0), jnp.ones((2, T), jnp.int32))
    params = variables["params"]
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))
    tx = adamw_lowmem(3e-4)  # bf16 moments: 5 GB of adam state, not 10
    opt_state = tx.init(params)

    def loss_fn(logits, batch):
        return cross_entropy_loss(logits, batch["target"])

    step = make_train_step(dm, tx, loss_fn, donate=True)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B * n, T + 1)), jnp.int32)
    batch = {"input": toks[:, :-1], "target": toks[:, 1:]}

    # PaLM-style MFU: 6*P per token + attention 12*L*T*E per token (fwd+bwd)
    time_and_report(
        step, params, opt_state, batch,
        n=n,
        tokens_per_step=B * n * T,
        flops_per_token=6.0 * n_params + 12.0 * cfg.num_hidden_layers * T * cfg.hidden_size,
        metric=metric,
        extra={"params": n_params, "seq_len": T, "flash_attention": cfg.use_flash_attention},
    )


def _dispatch():
    from vescale_tpu.analysis import envreg
    from vescale_tpu.compile_cache import use_compile_cache

    use_compile_cache()
    which = envreg.get_str("VESCALE_BENCH")
    if which == "moe":
        bench_moe()
    elif which == "longctx":
        bench_longctx()
    elif which == "memtrack":
        bench_memtrack()
    elif which == "trace":
        bench_trace()
    elif which == "resilience":
        bench_resilience()
    elif which == "watchdog":
        bench_watchdog()
    elif which == "serve":
        bench_serve()
    elif which == "alerts":
        bench_alerts()
    elif which == "costaudit":
        bench_costaudit()
    elif which == "elastic":
        bench_elastic()
    elif which == "kernels":
        bench_kernels()
    elif which == "redistribute":
        # multi-hop planner battery (VESCALE_BENCH=redistribute): plan
        # length, bytes moved and retrace count per representative
        # transition pair — scripts/redistribute_bench.py emits the line
        sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "scripts"))
        import redistribute_bench

        print(json.dumps(redistribute_bench.run_bench()))
    elif which == "fleet":
        # multi-replica fleet rung (VESCALE_BENCH=fleet): aggregate
        # tokens/s, fleet p99 TTFT and shed rate under a 5x-capacity
        # overload with a mid-run replica kill + rejoin, plus the
        # router-hop-vs-direct-submit overhead line AND the tracing-on
        # vs tracing-off hop line (fleet_trace_overhead_frac, both <1%
        # bar) — scripts/fleet_smoke.py emits the line
        sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "scripts"))
        import fleet_smoke

        print(json.dumps(fleet_smoke.run_bench()))
    elif which == "autoscale":
        # fleet autoscaling rung (VESCALE_BENCH=autoscale): 5x-capacity
        # spike on real children -> scale-up latency + p99 TTFT recovery
        # (zero lost rids), plus the quiescent overhead lines — throttled
        # autoscaler tick and per-tenant submit accounting, both amortized
        # against a MEASURED decode step (<1% bar) —
        # scripts/autoscale_smoke.py emits the line
        sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "scripts"))
        import autoscale_smoke

        print(json.dumps(autoscale_smoke.run_bench()))
    elif which == "routerha":
        # router high availability rung (VESCALE_BENCH=routerha): the
        # fleet journal's append cost per dispatch hop — plain router vs
        # journaled router over the no-socket instant client, amortized
        # against a measured request decode service time (<1% bar) —
        # scripts/router_ha_smoke.py emits the line
        sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "scripts"))
        import router_ha_smoke

        print(json.dumps(router_ha_smoke.run_bench()))
    elif which == "quantcomm":
        # quantized gradient collectives (VESCALE_BENCH=quantcomm): the
        # 2-proc gloo rig's grad-reduce bytes-on-the-wire + step time,
        # fp32 psum vs block-scaled int8, plus the emulator bit-for-bit
        # verdict — scripts/quantcomm_smoke.py emits the line
        sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "scripts"))
        import quantcomm_smoke

        print(json.dumps(quantcomm_smoke.run_bench()))
    else:
        main()


if __name__ == "__main__":
    _dispatch()
