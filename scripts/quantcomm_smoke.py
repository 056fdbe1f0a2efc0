"""Quantized gradient-collective smoke — the three-part proof of the int8
grad-compression stack (ROADMAP item 2; EQuARX, arXiv:2506.17615):

  rig      2 spawned processes (1 CPU device each, gloo collectives — the
           same rig as tests/test_multiprocess.py, so inter-process bytes
           are REAL network bytes): a gradient pytree is reduced with the
           uncompressed fp32 all-reduce (DDP's fp32 main-grad default) and
           with ``q_psum`` (block-scaled int8).  Wire bytes are read from
           the COMPILED programs via ``debug.comm_mode.collective_wire_bytes``
           — the payload dtype comes from the HLO, not from a hand-claim —
           and the smoke asserts >= 3.5x fewer bytes for int8 (measured:
           ~3.94x vs the fp32 payload — int8 codes + one E8M0 scale byte
           per 64-element block).  A bf16-grad psum is compiled and
           measured alongside; on XLA CPU it upcasts to f32 on the wire,
           so its ratio matches fp32's — the number reported is what the
           compiled program actually moves.

  replay   the emulator's quantized mode (emulator/quantized.py) replays
           the rig's reduction on the driver host: quantize once with the
           SAME jax quantizer, accumulate fp32 in rank order.  The smoke
           asserts the replay's result digest equals BOTH ranks' digests
           BIT-FOR-BIT (deterministic nearest rounding) — the acceptance
           contract of the emulator quantized-ring mode.

  e2e      the 350M-class CPU training smoke (the scaled-down llama config
           of the CPU rounds — same code path as the real 350M,
           sized for tier-1): 8-virtual-device dp training via a shard_map
           step whose ONLY difference between runs is the grad reduction
           (``dp_grad_reduce``: exact pmean vs int8 quantized).  Asserts
           the int8 run trains (loss falls), is bitwise replayable, and
           its final loss is within LOSS_TOL (5% relative, documented in
           docs/observability.md) of the exact baseline.

Exit 0 on success.  Wired into scripts/run_test.sh and tier-1 via
tests/test_quantcomm.py.
"""

import hashlib
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BLOCK = 64
WORLD = 2
RIG_ITERS = 10
E2E_STEPS = 20
LOSS_TOL = 0.05  # relative final-loss gap, int8 vs exact baseline

# ~2.2M gradient elements (~8.6 MiB fp32) across transformer-shaped leaves
SHAPES = {"wqkv": (768, 768), "mlp_in": (768, 1536), "emb": (4096, 96)}


def rig_grads(rank: int):
    """Deterministic per-rank gradient contributions (shared by the rig
    children and the driver's emulator replay)."""
    import numpy as np

    out = {}
    for i, (k, shp) in enumerate(sorted(SHAPES.items())):
        rng = np.random.default_rng(1000 * rank + i)
        out[k] = (rng.normal(scale=1.0 + i, size=shp)).astype(np.float32)
    return out


def _digest(tree) -> str:
    import numpy as np

    h = hashlib.sha256()
    for k in sorted(tree):
        h.update(np.asarray(tree[k]).tobytes())
    return h.hexdigest()


# --------------------------------------------------------------- rig child
def child_rig() -> None:
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import vescale_tpu.distributed as vdist

    vdist.initialize()
    me = jax.process_index()
    assert jax.process_count() == WORLD and len(jax.devices()) == WORLD

    from vescale_tpu.collectives import q_psum, shard_map
    from vescale_tpu.debug.comm_mode import collective_wire_bytes
    from vescale_tpu.mesh import DeviceMesh

    mesh = DeviceMesh(("dp",), (WORLD,))
    sh = NamedSharding(mesh.jax_mesh, P("dp"))

    def stacked(k, shp, dtype):
        def cb(idx):
            r = idx[0].start or 0
            return rig_grads(r)[k][None].astype(dtype)

        return jax.make_array_from_callback((WORLD,) + shp, sh, cb)

    grads32 = {k: stacked(k, s, np.float32) for k, s in SHAPES.items()}
    grads16 = {k: stacked(k, s, jnp.bfloat16) for k, s in SHAPES.items()}

    def tmap(f, t):
        return jax.tree_util.tree_map(f, t)

    def base_body(g):
        return tmap(lambda x: jax.lax.psum(jnp.squeeze(x, 0), "dp"), g)

    def quant_body(g):
        return tmap(
            lambda x: q_psum(jnp.squeeze(x, 0), "dp", WORLD, block=BLOCK), g
        )

    def build(body):
        return jax.jit(
            shard_map(
                body, mesh=mesh.jax_mesh, in_specs=(P("dp"),), out_specs=P(),
                check_vma=False,
            )
        )

    f_base, f_quant = build(base_body), build(quant_body)
    wb = collective_wire_bytes(f_base.lower(grads32).compile().as_text())
    wq = collective_wire_bytes(f_quant.lower(grads32).compile().as_text())
    wbf = collective_wire_bytes(f_base.lower(grads16).compile().as_text())

    out_q = f_quant(grads32)
    out_b = f_base(grads32)
    # lossy but bounded: per element the error is at most the sum of each
    # rank's block quantization step (amax_block / 254)
    err = max(
        float(jnp.max(jnp.abs(out_q[k] - out_b[k]))) for k in SHAPES
    )
    assert 0.0 < err < 0.2, f"quantization error implausible: {err}"

    local = {k: np.asarray(out_q[k].addressable_shards[0].data) for k in SHAPES}
    print(f"QDIGEST={_digest(local)}")

    def timed(f, g):
        leaf = f(g)["wqkv"]
        leaf.block_until_ready()  # warmup (compiled above already)
        t0 = time.perf_counter()
        for _ in range(RIG_ITERS):
            leaf = f(g)["wqkv"]
        leaf.block_until_ready()
        return (time.perf_counter() - t0) / RIG_ITERS * 1e3

    ms_base, ms_quant = timed(f_base, grads32), timed(f_quant, grads32)
    if me == 0:
        print("RIG " + json.dumps({
            "bytes_f32": wb["total"],
            # NOTE: XLA CPU upcasts the bf16 all-reduce to f32 on the wire
            # (convert + f32 all-reduce in the compiled program), so this
            # measures what a bf16 grad psum ACTUALLY moves on this
            # backend, not 2 bytes/element
            "bytes_bf16_as_compiled": wbf["total"],
            "bytes_int8": wq["total"],
            "int8_tagged": wq.get("all_reduce:int8", 0.0),
            "ratio_vs_f32": wb["total"] / wq["total"],
            "ratio_vs_bf16": wbf["total"] / wq["total"],
            "allreduce_ms_f32": round(ms_base, 3),
            "allreduce_ms_int8": round(ms_quant, 3),
            "grad_elements": int(sum(
                int(np.prod(s)) for s in SHAPES.values()
            )),
        }))
    print(f"OK proc {me}")


# --------------------------------------------------------------- e2e child
def child_e2e() -> None:
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    from vescale_tpu.collectives import shard_map
    from vescale_tpu.mesh import DeviceMesh
    from vescale_tpu.models.llama import Llama, LlamaConfig
    from vescale_tpu.models.nanogpt import cross_entropy_loss
    from vescale_tpu.parallel.ddp import dp_grad_reduce

    ndev = len(jax.devices())
    assert ndev >= 8, ndev
    ndev = 8
    mesh = DeviceMesh(("dp",), (ndev,), devices=jax.devices()[:ndev])
    T = 64
    cfg = LlamaConfig(
        vocab_size=512, hidden_size=128, intermediate_size=256,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        max_position_embeddings=T, dtype=jnp.float32,
    )
    model = Llama(cfg)
    tx = optax.adamw(3e-3)

    def local_loss(p, batch):
        logits = model.apply({"params": p}, batch["input"])
        return cross_entropy_loss(logits, batch["target"])

    def run(mode):
        params = model.init(jax.random.key(0), jnp.ones((2, T), jnp.int32))["params"]
        opt = tx.init(params)
        pspec = jax.tree_util.tree_map(lambda _: P(), params)
        ospec = jax.tree_util.tree_map(lambda _: P(), opt)

        def body(p, o, batch):
            loss, grads = jax.value_and_grad(local_loss)(p, batch)
            grads = dp_grad_reduce(grads, "dp", ndev, compress=mode, reduce_op="avg")
            updates, o2 = tx.update(grads, o, p)
            return optax.apply_updates(p, updates), o2, jax.lax.pmean(loss, "dp")

        step = jax.jit(shard_map(
            body, mesh=mesh.jax_mesh, in_specs=(pspec, ospec, P("dp")),
            out_specs=(pspec, ospec, P()), check_vma=False,
        ))
        rng = np.random.default_rng(42)
        losses = []
        for _ in range(E2E_STEPS):
            # learnable data: strided arithmetic token sequences (the next
            # token is a deterministic function of the previous one), so
            # the loss trajectory actually FALLS and a grad-quality
            # regression would show up as a trajectory gap
            starts = rng.integers(0, cfg.vocab_size, (ndev, 1))
            strides = rng.integers(1, 7, (ndev, 1))
            toks = jnp.asarray(
                (starts + strides * np.arange(T + 1)) % cfg.vocab_size, jnp.int32
            )
            batch = {"input": toks[:, :-1], "target": toks[:, 1:]}
            params, opt, loss = step(params, opt, batch)
            losses.append(float(loss))
        return losses

    base = run(None)
    q1 = run("int8")
    q2 = run("int8")
    assert q1 == q2, "int8 run is not bitwise replayable"
    gap = abs(q1[-1] - base[-1]) / abs(base[-1])
    assert gap < LOSS_TOL, (
        f"int8 final loss {q1[-1]:.6f} vs baseline {base[-1]:.6f}: "
        f"relative gap {gap:.4f} exceeds {LOSS_TOL}"
    )
    assert q1[-1] < base[0] * 0.9, "int8 run did not train"
    print("E2E " + json.dumps({
        "loss_first": base[0], "loss_final_base": base[-1],
        "loss_final_int8": q1[-1], "rel_gap": gap, "steps": E2E_STEPS,
        "tol": LOSS_TOL,
    }))
    print("OK e2e")


# ------------------------------------------------------------------ driver
_SCRUB = ("VESCALE_GRAD_COMPRESS", "VESCALE_GRAD_COMPRESS_SR",
          "VESCALE_GRAD_COMPRESS_BLOCK", "VESCALE_GRAD_COMPRESS_SEED",
          "VESCALE_REDISTRIBUTE_QUANT")


def _env(device_count: int, extra=None, port: int = 0, pid: int = 0, world: int = 1):
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from vescale_tpu.testing import make_child_env

    return make_child_env(port, pid, world, device_count=device_count,
                          scrub=_SCRUB, extra=extra)


def run_rig(timeout=240):
    """Spawn the 2-process x 1-device gloo rig; returns (rank0 stats dict,
    [per-rank digests]).  Ports from the session-unique registry, one
    bounded transport-setup retry (vescale_tpu.testing)."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from vescale_tpu.testing import run_gloo_world

    def spawn(port):
        procs = []
        for pid in range(WORLD):
            procs.append(subprocess.Popen(
                [sys.executable, os.path.abspath(__file__), "--child-rig"],
                env=_env(1, port=port, pid=pid, world=WORLD), cwd=REPO,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            ))
        return procs

    results = run_gloo_world(spawn, timeout=timeout)
    stats, digests = None, []
    for pid, (rc, out) in enumerate(results):
        assert rc == 0, f"rig proc {pid} rc={rc}\n{out[-4000:]}"
        assert f"OK proc {pid}" in out, out[-2000:]
        for line in out.splitlines():
            if line.startswith("RIG "):
                stats = json.loads(line[4:])
            elif line.startswith("QDIGEST="):
                digests.append(line.split("=", 1)[1].strip())
    assert stats is not None and len(digests) == WORLD, (stats, digests)
    return stats, digests


def run_e2e(timeout=420) -> dict:
    p = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child-e2e"],
        env=_env(8), cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, timeout=timeout,
    )
    assert p.returncode == 0, f"e2e rc={p.returncode}\n{p.stdout[-4000:]}"
    assert "OK e2e" in p.stdout, p.stdout[-2000:]
    for line in p.stdout.splitlines():
        if line.startswith("E2E "):
            return json.loads(line[4:])
    raise AssertionError(p.stdout[-2000:])


def emulator_digest() -> str:
    """The driver-side quantized replay of the rig reduction."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from vescale_tpu.emulator import quantized_all_reduce

    per_rank = [rig_grads(r) for r in range(WORLD)]
    out = {
        k: quantized_all_reduce([pr[k] for pr in per_rank], block=BLOCK)[0]
        for k in SHAPES
    }
    return _digest(out)


def main() -> None:
    t0 = time.monotonic()
    stats, digests = run_rig()
    assert stats["ratio_vs_f32"] >= 3.5, (
        f"int8 grad reduce moves only {stats['ratio_vs_f32']:.2f}x fewer "
        f"bytes than the fp32 payload (need >= 3.5x): {stats}"
    )
    assert stats["int8_tagged"] > 0, (
        "compiled quant program shows no s8 payload — the wire convention broke"
    )
    assert digests[0] == digests[1], "ranks disagree on the quantized reduction"
    edig = emulator_digest()
    assert edig == digests[0], (
        f"emulator quantized replay diverges from the gloo rig: "
        f"{edig} vs {digests[0]}"
    )
    e2e = run_e2e()
    print(
        "QUANTCOMM SMOKE OK: "
        f"{stats['ratio_vs_f32']:.2f}x fewer grad bytes (int8 vs fp32 payload; "
        f"{stats['ratio_vs_bf16']:.2f}x vs bf16), emulator replay bit-identical "
        f"on both ranks, e2e loss gap {e2e['rel_gap']:.4f} < {LOSS_TOL} "
        f"in {time.monotonic() - t0:.1f}s"
    )


if __name__ == "__main__":
    if "--child-rig" in sys.argv:
        child_rig()
    elif "--child-e2e" in sys.argv:
        child_e2e()
    else:
        main()
