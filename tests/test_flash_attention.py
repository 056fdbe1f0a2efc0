"""Flash-attention kernel tests (interpret mode on CPU; the real-chip run
happens in chip_smoke.py and the benchmark's train cells)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from vescale_tpu.ops.flash_attention import _dense_ref, flash_attention


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_dense(causal):
    B, T, H, D = 2, 128, 4, 32
    ks = jax.random.split(jax.random.key(0), 3)
    q, k, v = (jax.random.normal(kk, (B, T, H, D)) for kk in ks)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64, interpret=True)
    golden = _dense_ref(q, k, v, 1.0 / np.sqrt(D), causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(golden), rtol=2e-5, atol=2e-5)


def test_flash_grads_match_dense():
    B, T, H, D = 1, 64, 2, 16
    ks = jax.random.split(jax.random.key(1), 3)
    q, k, v = (jax.random.normal(kk, (B, T, H, D)) for kk in ks)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, block_q=32, block_k=32, interpret=True) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(_dense_ref(q, k, v, 1.0 / np.sqrt(D), True) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


def test_flash_indivisible_falls_back():
    B, T, H, D = 1, 50, 2, 16
    ks = jax.random.split(jax.random.key(2), 3)
    q, k, v = (jax.random.normal(kk, (B, T, H, D)) for kk in ks)
    out = flash_attention(q, k, v, block_q=32, block_k=32, interpret=True)
    golden = _dense_ref(q, k, v, 1.0 / np.sqrt(D), True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(golden), rtol=2e-5, atol=2e-5)


def test_flash_sharded_multichip():
    """shard_map-wrapped kernel over dp x tp (batch + heads sharded)."""
    import vescale_tpu as vt
    from vescale_tpu.ops import flash_attention_sharded

    mesh = vt.DeviceMesh(("dp", "tp"), (2, 4))
    B, T, H, D = 4, 64, 8, 16
    ks = jax.random.split(jax.random.key(3), 3)
    q, k, v = (jax.random.normal(kk, (B, T, H, D)) for kk in ks)
    out = flash_attention_sharded(q, k, v, mesh, block_q=32, block_k=32, interpret=True)
    golden = _dense_ref(q, k, v, 1.0 / np.sqrt(D), True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(golden), rtol=2e-5, atol=2e-5)
    # grads flow through the shard_map + custom_vjp composition
    g = jax.grad(lambda q: jnp.sum(flash_attention_sharded(q, k, v, mesh, block_q=32, block_k=32, interpret=True) ** 2))(q)
    g2 = jax.grad(lambda q: jnp.sum(_dense_ref(q, k, v, 1.0 / np.sqrt(D), True) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g2), rtol=5e-4, atol=5e-4)


def test_block_fit_keeps_flash_path():
    """regression: T=768 (divides 256, not 512) stays fused via block fit."""
    B, T, H, D = 1, 768, 2, 16
    ks = jax.random.split(jax.random.key(4), 3)
    q, k, v = (jax.random.normal(kk, (B, T, H, D)) for kk in ks)
    out = flash_attention(q, k, v, interpret=True)  # defaults 512 -> fit 256
    golden = _dense_ref(q, k, v, 1.0 / np.sqrt(D), True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(golden), rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_flash_gspmd_partitionable_no_shard_map():
    """VERDICT r1 #2: flash == dense under a dp x tp mesh with PLAIN jit —
    no shard_map in user code — via custom_partitioning, fwd and bwd, with
    zero resharding of q/k/v (b/h sharded, t/d replicated)."""
    import vescale_tpu as vt
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = vt.DeviceMesh(("dp", "tp"), (2, 4))
    B, T, H, D = 4, 128, 4, 16
    ks = jax.random.split(jax.random.key(3), 3)
    q, k, v = (jax.random.normal(kk, (B, T, H, D)) for kk in ks)
    sh = NamedSharding(mesh.jax_mesh, P("dp", None, "tp", None))
    qs, ks_, vs = (jax.device_put(x, sh) for x in (q, k, v))

    f = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=True))
    out = f(qs, ks_, vs)
    golden = _dense_ref(q, k, v, 1.0 / np.sqrt(D), True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(golden), rtol=2e-5, atol=2e-5)
    # b/h sharding propagated (normalize trailing Nones: jax versions differ
    # on whether specs are padded to rank)
    got = tuple(out.sharding.spec)
    assert got + (None,) * (4 - len(got)) == ("dp", None, "tp", None)

    g = jax.jit(
        jax.grad(
            lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=True).sum(),
            argnums=(0, 1, 2),
        )
    )(qs, ks_, vs)
    gref = jax.grad(
        lambda q, k, v: _dense_ref(q, k, v, 1.0 / np.sqrt(D), True).sum(), argnums=(0, 1, 2)
    )(q, k, v)
    for a, b in zip(g, gref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)

    # the partitioning rule means no all-gather of the seq dim is inserted
    hlo = f.lower(qs, ks_, vs).compile().as_text()
    assert "all-gather" not in hlo


def test_flash_partitioned_seq_sharded_input_gathers():
    """Seq-sharded q/k/v still computes correctly (t is a need-replication
    factor: XLA gathers seq before the kernel rather than mis-partitioning)."""
    import vescale_tpu as vt
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = vt.DeviceMesh(("dp", "tp"), (2, 4))
    B, T, H, D = 2, 128, 4, 16
    ks = jax.random.split(jax.random.key(4), 3)
    q, k, v = (jax.random.normal(kk, (B, T, H, D)) for kk in ks)
    sh = NamedSharding(mesh.jax_mesh, P("dp", "tp", None, None))  # seq-sharded
    qs, ks_, vs = (jax.device_put(x, sh) for x in (q, k, v))
    out = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=True))(qs, ks_, vs)
    golden = _dense_ref(q, k, v, 1.0 / np.sqrt(D), True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(golden), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("rep", [2, 4])
def test_flash_gqa_matches_dense(causal, rep):
    """GQA: kv heads stay un-repeated in HBM; kernel output must equal the
    dense reference computed on repeated heads."""
    B, T, H, D = 2, 128, 8, 32
    G = H // rep
    ks = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(ks[0], (B, T, H, D))
    k = jax.random.normal(ks[1], (B, T, G, D))
    v = jax.random.normal(ks[2], (B, T, G, D))
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64, interpret=True)
    golden = _dense_ref(q, k, v, 1.0 / np.sqrt(D), causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(golden), rtol=2e-5, atol=2e-5)


def test_flash_gqa_grads_match_dense():
    """dk/dv must sum over the group's q heads (the accumulation grid dim)."""
    B, T, H, D = 1, 64, 4, 16
    G = 2
    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (B, T, H, D))
    k = jax.random.normal(ks[1], (B, T, G, D))
    v = jax.random.normal(ks[2], (B, T, G, D))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, block_q=32, block_k=32, interpret=True) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(_dense_ref(q, k, v, 1.0 / np.sqrt(D), True) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


def test_flash_gqa_bad_heads_raises():
    q = jnp.ones((1, 64, 6, 16))
    kv = jnp.ones((1, 64, 4, 16))
    with pytest.raises(ValueError, match="not a multiple"):
        flash_attention(q, kv, kv, interpret=True)


@pytest.mark.slow
def test_flash_gqa_gspmd_partitionable():
    """GQA under a dp x tp mesh with plain jit: tp shards q heads AND the
    smaller kv-head dim (tp | KV); fwd + bwd match dense with no shard_map."""
    import vescale_tpu as vt
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = vt.DeviceMesh(("dp", "tp"), (2, 2))
    B, T, H, D = 4, 128, 8, 16
    G = 4
    ks = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(ks[0], (B, T, H, D))
    k = jax.random.normal(ks[1], (B, T, G, D))
    v = jax.random.normal(ks[2], (B, T, G, D))
    sh = NamedSharding(mesh.jax_mesh, P("dp", None, "tp", None))
    qs, ks_, vs = (jax.device_put(x, sh) for x in (q, k, v))

    f = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=True))
    out = f(qs, ks_, vs)
    golden = _dense_ref(q, k, v, 1.0 / np.sqrt(D), True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(golden), rtol=2e-5, atol=2e-5)

    g = jax.jit(
        jax.grad(
            lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=True).sum(),
            argnums=(0, 1, 2),
        )
    )(qs, ks_, vs)
    gref = jax.grad(
        lambda q, k, v: _dense_ref(q, k, v, 1.0 / np.sqrt(D), True).sum(), argnums=(0, 1, 2)
    )(q, k, v)
    for a, b in zip(g, gref):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_flash_mqa_tp_falls_back_to_batch_partitioning():
    """MQA (G=1) with q heads tp-sharded: tp does not divide G, so the
    partition rule must drop the head axis (replicate) instead of splitting
    the size-1 kv-head dim — output still matches dense."""
    import vescale_tpu as vt
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = vt.DeviceMesh(("dp", "tp"), (2, 4))
    B, T, H, D = 2, 128, 8, 16
    ks = jax.random.split(jax.random.key(6), 3)
    q = jax.random.normal(ks[0], (B, T, H, D))
    k = jax.random.normal(ks[1], (B, T, 1, D))  # MQA
    v = jax.random.normal(ks[2], (B, T, 1, D))
    qs = jax.device_put(q, NamedSharding(mesh.jax_mesh, P("dp", None, "tp", None)))
    out = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=True))(qs, k, v)
    golden = _dense_ref(q, k, v, 1.0 / np.sqrt(D), True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(golden), rtol=2e-5, atol=2e-5)

    g = jax.jit(
        jax.grad(
            lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=True).sum(),
            argnums=(1, 2),
        )
    )(qs, k, v)
    gref = jax.grad(
        lambda q, k, v: _dense_ref(q, k, v, 1.0 / np.sqrt(D), True).sum(), argnums=(1, 2)
    )(q, k, v)
    for a, b in zip(g, gref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)


# ----------------------------------------------------- streaming kernels
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("rep", [1, 2])
def test_flash_streaming_matches_dense(causal, rep):
    """The large-T streaming kernels (grid-streamed K/V with scratch
    accumulators, VMEM O(block)) compute the same math as the resident
    kernels and the dense reference — fwd and grads, MHA and GQA."""
    from vescale_tpu.ops.flash_attention import (
        _flash_fwd_pallas,
        _from3,
        _to3,
    )

    B, T, H, D = 1, 128, 4, 16
    G = H // rep
    ks = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(ks[0], (B, T, H, D))
    k = jax.random.normal(ks[1], (B, T, G, D))
    v = jax.random.normal(ks[2], (B, T, G, D))
    scale = 1.0 / np.sqrt(D)

    o3, lse3 = _flash_fwd_pallas(
        _to3(q), _to3(k), _to3(v), scale, causal, 32, 32, True, H, G, streaming=True
    )
    o = _from3(o3, B, H)
    golden = _dense_ref(q, k, v, scale, causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(golden), rtol=2e-5, atol=2e-5)

    # grads: compare streaming bwd against the dense reference's autodiff
    def loss_dense(q, k, v):
        return jnp.sum(_dense_ref(q, k, v, scale, causal) ** 2)

    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    do = 2.0 * golden
    from vescale_tpu.ops.flash_attention import _flash_bwd_pallas

    dq3, dk3, dv3 = _flash_bwd_pallas(
        _to3(q), _to3(k), _to3(v), _to3(o), _to3(do),
        lse3, scale, causal, 32, 32, True, H, G, streaming=True,
    )
    for got3, want, nh in ((dq3, gd[0], H), (dk3, gd[1], G), (dv3, gd[2], G)):
        np.testing.assert_allclose(
            np.asarray(_from3(got3, B, nh)), np.asarray(want), rtol=1e-4, atol=1e-4
        )


@pytest.mark.parametrize(
    "kernel,T,D,dtype,rep,streams",
    [
        # the bf16 main path keeps every kernel resident, MHA and GQA
        ("fwd", 4096, 128, jnp.bfloat16, 1, False),
        ("dq", 4096, 128, jnp.bfloat16, 1, False),
        ("dkv", 4096, 128, jnp.bfloat16, 1, False),
        ("dkv", 4096, 128, jnp.bfloat16, 4, False),
        # dk/dv holds q, dO and the lane-padded lse/delta columns whole: it
        # leaves the resident form long before fwd and dq do
        ("dkv", 4096, 128, jnp.float32, 1, True),
        ("dkv", 8192, 128, jnp.bfloat16, 1, True),
        ("dq", 8192, 128, jnp.bfloat16, 1, False),
        ("fwd", 8192, 128, jnp.bfloat16, 1, False),
        # D 64 is padded to 128 lanes: no longer T than D 128 stays resident
        ("fwd", 16384, 64, jnp.bfloat16, 1, True),
        ("fwd", 16384, 128, jnp.bfloat16, 1, True),
        # the long-context rung streams everything
        ("fwd", 32768, 64, jnp.bfloat16, 2, True),
        ("dq", 32768, 64, jnp.bfloat16, 2, True),
        ("dkv", 32768, 64, jnp.bfloat16, 2, True),
    ],
)
def test_streaming_choice_per_kernel(kernel, T, D, dtype, rep, streams):
    """The resident/streaming choice counts what each resident kernel holds
    in VMEM (tests/test_tpu_compile.py compiles both sides of it)."""
    from vescale_tpu.ops.flash_attention import _use_streaming

    assert _use_streaming(kernel, T, D, dtype, 512, 512, rep) == streams
