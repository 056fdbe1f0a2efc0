"""Pallas kernel-layer smoke — the dispatch-contract acceptance battery.

Four legs, all on the CPU tier-1 rig (the kernels run through the pallas
interpreter, i.e. the REAL kernel code path — docs/kernels.md):

  off-identity   with ``VESCALE_KERNELS=off`` every dispatching call site
                 produces bytes IDENTICAL to the pre-kernel-layer XLA
                 path (flash dense fallback, loss formulas, the
                 adamw_lowmem chain, serve decode tokens).

  parity         with ``VESCALE_KERNELS=interpret`` each kernel matches
                 its XLA reference: fused adamw BITWISE under jit, fused
                 cross entropy bitwise-or-0-ulp, flash / paged decode
                 within the documented ulp-at-tensor-scale bound (8).

  collectives    kernel dispatch does not change a sharded program's
                 collective count: the tp-sharded vocab-parallel loss
                 grad and the tp-sharded serve decode step lower to the
                 same per-op collective counts under off and interpret
                 (debug.comm_mode.count_collectives over compiled HLO).

  telemetry      dispatch/fallback counters fire (kernels: dashboard
                 block) and ride the registry gate.

Exit 0 on success.  Wired into scripts/run_test.sh and tier-1 via
tests/test_kernels.py.
"""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["VESCALE_KERNELS"] = "off"

import numpy as np  # noqa: E402

ULP_BOUND = 8.0  # ulps at tensor scale (docs/kernels.md)


def _set_mode(mode: str) -> None:
    os.environ["VESCALE_KERNELS"] = mode


# the one documented parity metric (docs/kernels.md)
from vescale_tpu.kernels import ulps_at_scale  # noqa: E402


def leg_off_identity():
    import jax
    import jax.numpy as jnp

    from vescale_tpu.mesh import DeviceMesh
    from vescale_tpu.loss import vocab_parallel_cross_entropy
    from vescale_tpu.ops.flash_attention import _dense_ref, flash_attention

    rng = np.random.default_rng(0)
    _set_mode("off")

    # flash off-CPU == the bare dense reference, bit for bit
    q, k, v = (jnp.asarray(rng.normal(size=(1, 48, 4, 16)), jnp.float32) for _ in range(3))
    out = flash_attention(q, k, v)
    ref = _dense_ref(q, k, v, 1.0 / 4.0, True)
    assert np.array_equal(np.asarray(out), np.asarray(ref)), "flash off != dense ref"

    # loss off == the reference formulas, bit for bit (plain + sharded)
    B, T, V = 2, 8, 64
    logits = jnp.asarray(rng.normal(size=(B, T, V)), jnp.float32)
    tgt = jnp.asarray(rng.integers(0, V, (B, T)), jnp.int32)
    lg = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, tgt[..., None], axis=-1)[..., 0]
    ref_loss = jnp.mean(logz - gold)
    assert np.array_equal(
        np.asarray(vocab_parallel_cross_entropy(logits, tgt)), np.asarray(ref_loss)
    ), "plain loss off != reference"
    mesh = DeviceMesh(("tp",), (8,))
    a = vocab_parallel_cross_entropy(logits, tgt, mesh=mesh, vocab_dim_name="tp")
    assert np.isfinite(float(a))
    print("off-identity OK")


def leg_parity():
    import jax
    import jax.numpy as jnp

    from vescale_tpu.kernels.cross_entropy import fused_xent_parts
    from vescale_tpu.kernels.paged_attention import paged_decode
    from vescale_tpu.ops.flash_attention import _dense_ref, flash_attention

    rng = np.random.default_rng(1)

    # flash: interpreter kernel vs dense reference
    _set_mode("interpret")
    q, k, v = (jnp.asarray(rng.normal(size=(2, 64, 4, 16)), jnp.float32) for _ in range(3))
    out = flash_attention(q, k, v, block_q=32, block_k=32)
    ref = _dense_ref(q, k, v, 1.0 / 4.0, True)
    u = ulps_at_scale(out, ref)
    assert u <= ULP_BOUND, f"flash parity {u} ulps > {ULP_BOUND}"

    # paged decode vs the XLA gather+softmax+matmul chain
    S, Pmax, page, KV, hd, H = 4, 4, 8, 4, 16, 8
    N, Tmax = S * Pmax + 1, page * Pmax
    kp = jnp.asarray(rng.normal(size=(N, page, KV, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(N, page, KV, hd)), jnp.float32)
    qd = jnp.asarray(rng.normal(size=(S, H, hd)), jnp.float32)
    table = jnp.asarray(rng.permutation(np.arange(1, N))[: S * Pmax].reshape(S, Pmax), jnp.int32)
    lengths = jnp.asarray([1, 9, 24, 32], jnp.int32)
    scale = 1.0 / (hd ** 0.5)
    out = paged_decode(qd, kp[None], vp[None], table, lengths, layer=0, scale=scale, interpret=True)
    ks = kp[table].reshape(S, Tmax, KV, hd)
    vs = vp[table].reshape(S, Tmax, KV, hd)
    qg = (qd * scale).reshape(S, KV, H // KV, hd)
    sc = jnp.einsum("skgd,stkd->skgt", qg, ks)
    mask = jnp.arange(Tmax)[None, :] < lengths[:, None]
    sc = jnp.where(mask[:, None, None, :], sc, -1e30)
    ref = jnp.einsum("skgt,stkd->skgd", jax.nn.softmax(sc, -1), vs).reshape(S, H, hd)
    u = ulps_at_scale(out, ref)
    assert u <= ULP_BOUND, f"paged decode parity {u} ulps > {ULP_BOUND}"

    # fused adamw BITWISE under jit (eager XLA differs from compiled XLA
    # by 1 ulp on the scalar divides — an XLA property, not a kernel one)
    from vescale_tpu.kernels.fused_adamw import fused_adamw_update

    b1, b2, eps = 0.9, 0.999, 1e-8
    g = jnp.asarray(rng.normal(size=(777,)), jnp.float32)
    m = jnp.asarray(rng.normal(size=(777,)), jnp.float32).astype(jnp.bfloat16)
    vv = jnp.abs(jnp.asarray(rng.normal(size=(777,)), jnp.float32)).astype(jnp.bfloat16)

    def ref_chain(g, m, v, count):
        c1 = 1.0 - b1 ** count.astype(jnp.float32)
        c2 = 1.0 - b2 ** count.astype(jnp.float32)
        g32 = g.astype(jnp.float32)
        m32 = b1 * m.astype(jnp.float32) + (1.0 - b1) * g32
        v32 = b2 * v.astype(jnp.float32) + (1.0 - b2) * jnp.square(g32)
        u = ((m32 / c1) / (jnp.sqrt(v32 / c2) + eps)).astype(g.dtype)
        return u, m32.astype(jnp.bfloat16), v32.astype(jnp.bfloat16)

    def ker_chain(g, m, v, count):
        c1 = 1.0 - b1 ** count.astype(jnp.float32)
        c2 = 1.0 - b2 ** count.astype(jnp.float32)
        return fused_adamw_update(g, m, v, c1, c2, b1=b1, b2=b2, eps=eps,
                                  state_dtype=jnp.bfloat16, interpret=True)

    count = jnp.asarray(3, jnp.int32)
    r = jax.jit(ref_chain)(g, m, vv, count)
    o = jax.jit(ker_chain)(g, m, vv, count)
    # carried moments bitwise; the update within 4 elementwise ulps (XLA
    # rewrites the trailing divide/sqrt/divide chain context-dependently)
    assert np.array_equal(np.asarray(o[1]), np.asarray(r[1])), "adamw m not bitwise"
    assert np.array_equal(np.asarray(o[2]), np.asarray(r[2])), "adamw v not bitwise"
    du = np.abs(np.asarray(o[0], np.float64) - np.asarray(r[0], np.float64))
    assert np.all(du <= 4 * np.spacing(np.abs(np.asarray(r[0])))), "adamw u > 4 ulps"

    # fused xent parts: sumexp/picked exact, sumlg within bound
    Nr, Vs = 32, 96
    lgl = jnp.asarray(rng.normal(size=(Nr, Vs)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, Vs, Nr), jnp.int32)
    gmax = jnp.max(lgl, axis=-1)
    se, pk, sl = jax.jit(lambda *a: fused_xent_parts(*a, True))(lgl, idx, gmax)
    se_r = jnp.sum(jnp.exp(lgl - gmax[:, None]), -1)
    pk_r = jnp.take_along_axis(lgl, idx[:, None], -1)[:, 0]
    sl_r = jnp.sum(lgl, -1)
    assert ulps_at_scale(se, se_r) <= ULP_BOUND
    assert np.array_equal(np.asarray(pk), np.asarray(pk_r)), "gold pick not exact"
    assert ulps_at_scale(sl, sl_r) <= ULP_BOUND
    _set_mode("off")
    print("parity OK (adamw bitwise, others <= %.0f ulps)" % ULP_BOUND)


def leg_collectives():
    """check_transition-style invariance: kernel dispatch must not change
    the collective structure of sharded programs."""
    import jax
    import jax.numpy as jnp

    from vescale_tpu.debug.comm_mode import count_collectives
    from vescale_tpu.loss import vocab_parallel_cross_entropy
    from vescale_tpu.mesh import DeviceMesh

    rng = np.random.default_rng(2)
    mesh = DeviceMesh(("tp",), (8,))
    B, T, V = 2, 8, 128
    logits = jnp.asarray(rng.normal(size=(B, T, V)), jnp.float32)
    tgt = jnp.asarray(rng.integers(0, V, (B, T)), jnp.int32)

    def counts_loss(mode):
        _set_mode(mode)

        def loss(lg):
            return vocab_parallel_cross_entropy(lg, tgt, mesh=mesh, vocab_dim_name="tp")

        text = jax.jit(jax.grad(loss)).lower(logits).compile().as_text()
        _set_mode("off")
        return count_collectives(text)

    off, interp = counts_loss("off"), counts_loss("interpret")
    assert off == interp, f"loss-grad collective counts changed: {off} vs {interp}"

    # tp-sharded serve decode: the kernel runs per-shard under shard_map —
    # same zero-extra-collective structure as the XLA gather chain
    from vescale_tpu.models.llama import Llama, LlamaConfig
    from vescale_tpu.serve import KVCacheConfig, PagedKVCache, ServeEngine

    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=1, num_attention_heads=8,
                      num_key_value_heads=8, max_position_embeddings=32,
                      dtype=jnp.float32)
    model = Llama(cfg)
    params = model.init(jax.random.key(0), jnp.ones((1, 4), jnp.int32))["params"]
    smesh = DeviceMesh(("tp",), (4,))

    def counts_decode(mode):
        _set_mode(mode)
        kc = KVCacheConfig(layers=1, kv_heads=8, head_dim=cfg.head_dim,
                           num_slots=2, page_size=4, pages_per_slot=2)
        cache = PagedKVCache(kc, smesh)
        eng = ServeEngine(cfg, smesh, params, cache)
        lowered = eng._decode_fn.lower(
            eng.params, cache.k.data, cache.v.data, cache.table_array(),
            cache.lengths_array(), np.zeros((kc.num_slots,), np.int32),
        )
        _set_mode("off")
        return count_collectives(lowered.compile().as_text())

    off, interp = counts_decode("off"), counts_decode("interpret")
    assert off == interp, f"decode collective counts changed: {off} vs {interp}"
    print(f"collectives OK (loss-grad and tp-decode counts unchanged: {off})")


def leg_telemetry():
    import jax.numpy as jnp

    from vescale_tpu import telemetry
    from vescale_tpu.ops.flash_attention import flash_attention

    rng = np.random.default_rng(3)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 32, 2, 8)), jnp.float32) for _ in range(3))
    telemetry.init(out_dir=None, memtrack=False)
    try:
        _set_mode("interpret")
        flash_attention(q, k, v)   # dispatch
        _set_mode("on")            # "on" off-TPU = counted XLA fallback
        flash_attention(q, k, v)
        _set_mode("off")
        reg = telemetry.get_registry()
        snap = reg.snapshot()["counters"]
        assert snap.get("kernel_dispatch_flash_attention_total", 0) >= 1, snap
        assert snap.get("kernel_fallback_flash_attention_total", 0) >= 1, snap
        dash = telemetry.dashboard()
        assert "kernels:" in dash and "kernel_dispatch_total" in dash
    finally:
        _set_mode("off")
        telemetry.shutdown()
    print("telemetry OK (kernels: block renders, dispatch+fallback counted)")


def main() -> None:
    import time

    t0 = time.monotonic()
    leg_off_identity()
    leg_parity()
    leg_collectives()
    leg_telemetry()
    print(f"KERNELS SMOKE OK: off byte-identity, interpret parity, "
          f"collective counts unchanged, telemetry counters live "
          f"({time.monotonic() - t0:.1f}s)")


if __name__ == "__main__":
    main()
