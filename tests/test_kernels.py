"""Pallas kernel layer tests — the ISSUE 11 dispatch contract.

Every kernel runs through the pallas INTERPRETER here (the real kernel
code path, CPU-executable) and is compared against its XLA reference:
fused adamw bitwise under jit, fused cross entropy exact-or-ulp-bounded,
flash / paged decode within the documented ulp-at-tensor-scale bound.
``VESCALE_KERNELS=off`` byte-identity, dispatch telemetry, the VSC206
lint rule and collective-count invariance are asserted alongside.
"""

import os
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from vescale_tpu import kernels
from vescale_tpu.mesh import DeviceMesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# documented parity bound: ulps at the tensor's scale (fp32 spacing of the
# reference's max |value|) — fp32 accumulation ORDER is the only difference
ULP_BOUND = 8.0


# the one documented parity metric (docs/kernels.md; kernels.ulps_at_scale)
from vescale_tpu.kernels import ulps_at_scale  # noqa: E402


@pytest.fixture
def kmode(monkeypatch):
    def set_mode(mode):
        monkeypatch.setenv("VESCALE_KERNELS", mode)

    monkeypatch.setenv("VESCALE_KERNELS", "off")
    return set_mode


# ============================================================= dispatch
def test_mode_parses_and_validates(kmode):
    assert kernels.mode() == "off"
    for m in ("off", "interpret", "on"):
        kmode(m)
        assert kernels.mode() == m
    kmode("bogus")
    with pytest.raises(ValueError, match="VESCALE_KERNELS"):
        kernels.mode()


def test_resolve_contract_on_cpu(kmode):
    kmode("off")
    assert kernels.resolve("x") is None
    kmode("interpret")
    assert kernels.resolve("x") is True
    kmode("on")  # compiled kernels need a TPU: XLA fallback off-TPU
    assert kernels.resolve("x") is None


# (VESCALE_KERNELS or None for unset, a TPU present, kernel) -> resolve()
@pytest.mark.parametrize("value,tpu,name,want", [
    (None, False, "paged_decode", None),      # unset off-TPU: the XLA leg, as before PR 27
    (None, True, "paged_decode", False),      # unset on TPU: the compiled kernel is the decode path
    (None, True, "fused_adamw", None),        # the other kernels keep their default
    (None, True, "flash_attention", None),
    ("off", True, "paged_decode", None),      # an explicit mode means what it meant, for every kernel
    ("on", True, "paged_decode", False),
    ("on", True, "fused_adamw", False),
    ("on", False, "paged_decode", None),
    ("interpret", False, "paged_decode", True),
    ("interpret", True, "fused_xent", True),
])
def test_resolve_default_by_platform(monkeypatch, value, tpu, name, want):
    monkeypatch.delenv("VESCALE_KERNELS", raising=False)
    if value is not None:
        monkeypatch.setenv("VESCALE_KERNELS", value)
    monkeypatch.setattr(kernels, "on_tpu", lambda: tpu)
    assert kernels.resolve(name) is want
    assert kernels.mode() == (value or "off")
    # a shape the kernel does not take is the XLA path in every mode, asked with the flag it would get
    asked = []
    assert kernels.resolve(name, supported=lambda interpret: asked.append(interpret)) is None
    assert asked == ([] if want is None else [want])


def test_dispatch_counters_ride_registry_gate(kmode):
    from vescale_tpu import telemetry

    kmode("interpret")
    kernels.record_dispatch("t")  # dormant: must be a no-op, not an error
    telemetry.init(out_dir=None, memtrack=False)
    try:
        kernels.record_dispatch("t")
        kernels.record_fallback("t")
        snap = telemetry.get_registry().snapshot()["counters"]
        assert snap["kernel_dispatch_t_total"] == 1
        assert snap["kernel_fallback_t_total"] == 1
        assert snap["kernel_dispatch_total"] == 1
        dash = telemetry.dashboard()
        assert "kernels:" in dash
    finally:
        telemetry.shutdown()


def test_vsc206_lint_rule():
    from vescale_tpu.analysis.lint import lint_source

    bad = "from jax.experimental import pallas as pl\npl.pallas_call(f, out_shape=o)(x)\n"
    codes = [f.code.code for f in lint_source(bad, "vescale_tpu/serve/engine.py")]
    assert "VSC206" in codes
    codes = [f.code.code for f in lint_source(bad, "vescale_tpu/kernels/foo.py")]
    assert "VSC206" not in codes
    suppressed = bad.splitlines()
    suppressed[1] += "  # vescale-lint: disable=VSC206"
    codes = [f.code.code for f in lint_source("\n".join(suppressed), "x/y.py")]
    assert "VSC206" not in codes


def test_kernels_env_registered():
    from vescale_tpu.analysis import envreg

    assert envreg.is_registered("VESCALE_KERNELS")
    assert envreg.lookup("VESCALE_KERNELS").default is None   # unset: each kernel's own default (docs/kernels.md)


# ================================================================ flash
def test_flash_off_is_byte_identical_to_dense(kmode):
    from vescale_tpu.ops.flash_attention import _dense_ref, flash_attention

    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 40, 2, 16)), jnp.float32) for _ in range(3))
    kmode("off")
    out = flash_attention(q, k, v)
    ref = _dense_ref(q, k, v, 0.25, True)
    assert np.array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_interpret_mode_dispatches_kernel(kmode, dtype, causal):
    """Under VESCALE_KERNELS=interpret an unset interpret= resolves to the
    pallas interpreter on CPU — parity against the dense reference."""
    from vescale_tpu.ops.flash_attention import _dense_ref, flash_attention

    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 64, 4, 16)), np.float32).astype(dtype)
               for _ in range(3))
    kmode("interpret")
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    kmode("off")
    ref = _dense_ref(q, k, v, 0.25, causal)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=tol, atol=tol
    )


def test_flash_enabled_fallback_shares_partition_rule(kmode):
    """A non-divisible T under an enabled mode routes through the SHARED
    custom_vjp/partition rule (impl='xla'), counts the fallback, and still
    matches the dense math — forward and grad."""
    from vescale_tpu import telemetry
    from vescale_tpu.ops.flash_attention import _dense_ref, flash_attention

    rng = np.random.default_rng(2)
    # T=50: no power-of-two block divides it -> XLA fallback either mode
    q, k, v = (jnp.asarray(rng.normal(size=(1, 50, 2, 16)), jnp.float32) for _ in range(3))
    telemetry.init(out_dir=None, memtrack=False)
    try:
        kmode("interpret")
        out = flash_attention(q, k, v, block_q=32, block_k=32)
        g = jax.grad(lambda q: jnp.sum(flash_attention(q, k, v, block_q=32, block_k=32) ** 2))(q)
        snap = telemetry.get_registry().snapshot()["counters"]
        assert snap.get("kernel_fallback_flash_attention_total", 0) >= 1
    finally:
        kmode("off")
        telemetry.shutdown()
    ref = _dense_ref(q, k, v, 0.25, True)
    g_ref = jax.grad(lambda q: jnp.sum(_dense_ref(q, k, v, 0.25, True) ** 2))(q)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=1e-4, atol=1e-4)


def test_flash_xla_impl_gqa_grads_match_dense(kmode):
    """The shared-rule XLA leg handles GQA (G < H) fwd+bwd like the dense
    reference — the path a sharded caller takes when the kernel can't."""
    from vescale_tpu.ops.flash_attention import _dense_ref, _flash

    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(1, 24, 4, 8)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(1, 24, 2, 8)), jnp.float32) for _ in range(2))
    scale = 1.0 / np.sqrt(8)
    out = _flash(q, k, v, scale, True, 0, 0, False, "xla")
    ref = _dense_ref(q, k, v, scale, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
    g1 = jax.grad(lambda q, k, v: jnp.sum(_flash(q, k, v, scale, True, 0, 0, False, "xla") ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda q, k, v: jnp.sum(_dense_ref(q, k, v, scale, True) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


# ========================================================== paged decode
def _paged_ref(q, kp, vp, table, lengths, scale):
    """The op's XLA leg (``interpret=None``) over ONE layer's (N, page, KV, hd) pool."""
    from vescale_tpu.kernels.paged_attention import paged_decode

    return paged_decode(q, kp[None], vp[None], table, lengths, layer=0, scale=scale, interpret=None)


# (S, Pmax, page, KV, hd, H): a toy, and the two serve cells' head layouts at
# their widths (Mistral's GQA 8 x 4 and DeepSeek's MHA 32 x 1; page 16, hd 128)
# with slots of more than one block of pages (blocks of 32 and of 8 pages)
PAGED_TOY = (3, 2, 4, 2, 8, 4)
PAGED_GQA = (4, 40, 16, 8, 128, 32)
PAGED_MHA = (4, 12, 16, 32, 128, 32)
PAGED_LAYOUTS = {"toy": PAGED_TOY, "gqa8x4": PAGED_GQA, "mha32x1": PAGED_MHA}


def _paged_case(rng, S, Pmax, page, KV, hd, H, dtype, layers=1):
    """A 5-D pool of ``layers`` layers, a shuffled page table (no slot's
    pages are in order or adjacent) and ragged lengths."""
    N = S * Pmax + 1
    kp = jnp.asarray(rng.normal(size=(layers, N, page, KV, hd)), np.float32).astype(dtype)
    vp = jnp.asarray(rng.normal(size=(layers, N, page, KV, hd)), np.float32).astype(dtype)
    q = jnp.asarray(rng.normal(size=(S, H, hd)), jnp.float32)
    table = jnp.asarray(
        rng.permutation(np.arange(1, N))[: S * Pmax].reshape(S, Pmax), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, page * Pmax + 1, S), jnp.int32)
    return q, kp, vp, table, lengths


def _paged_bound(dtype):
    return ULP_BOUND if dtype == jnp.float32 else 64.0  # bf16 K/V: coarser inputs


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "page,Pmax,layout",
    [(4, 4, None), (8, 2, None), (6, 3, None), (16, 1, None),
     (16, 40, "gqa8x4"), (16, 12, "mha32x1")],
)
def test_paged_decode_matches_gather_reference(dtype, page, Pmax, layout):
    """Property sweep: page sizes (including non-power-of-two 6),
    pages-per-slot, dtypes, ragged lengths, both serve cells' head layouts —
    all within the ulp bound, reading layer 1 of a three-layer pool."""
    from vescale_tpu.kernels.paged_attention import paged_decode

    rng = np.random.default_rng(page * 10 + Pmax)
    S, KV, hd, H = (3, 2, 16, 4) if layout is None else (
        PAGED_LAYOUTS[layout][0], *PAGED_LAYOUTS[layout][3:])
    q, kp, vp, table, lengths = _paged_case(rng, S, Pmax, page, KV, hd, H, dtype, layers=3)
    scale = 1.0 / np.sqrt(hd)
    out = paged_decode(q, kp, vp, table, lengths, layer=1, scale=scale, interpret=True)
    ref = _paged_ref(q, kp[1], vp[1], table, lengths, scale)
    assert ulps_at_scale(out, ref) <= _paged_bound(dtype)


@pytest.mark.parametrize("layout", list(PAGED_LAYOUTS))
def test_paged_decode_edge_lengths(layout):
    """length=1 (only the fresh token), one position either side of a page
    and of a block of pages, a full slot, and slots sharing no pages — the
    masking edges the serve loop exercises."""
    from vescale_tpu.kernels.paged_attention import _block_pages, paged_decode

    rng = np.random.default_rng(7)
    _, Pmax, page, KV, hd, H = PAGED_LAYOUTS[layout]
    block = page * _block_pages(Pmax, page, KV, hd, 4)
    edges = sorted({1, page - 1, page, page + 1, block - 1, block, min(block + 1, page * Pmax), page * Pmax})
    q, kp, vp, table, _ = _paged_case(rng, len(edges), Pmax, page, KV, hd, H, jnp.float32)
    lengths = jnp.asarray(edges, jnp.int32)
    scale = 1.0 / np.sqrt(hd)
    out = paged_decode(q, kp, vp, table, lengths, layer=0, scale=scale, interpret=True)
    ref = _paged_ref(q, kp[0], vp[0], table, lengths, scale)
    assert ulps_at_scale(out, ref) <= ULP_BOUND
    assert np.isfinite(np.asarray(out)).all()


def test_paged_decode_length_zero_and_past_capacity():
    """Lengths the engine never passes must still end: 0 fetches nothing and
    gives zeros (and the slot after it is still served), one past the
    capacity reads the full slot and no table entry beyond it."""
    from vescale_tpu.kernels.paged_attention import paged_decode

    rng = np.random.default_rng(5)
    S, Pmax, page, KV, hd, H = PAGED_TOY
    q, kp, vp, table, _ = _paged_case(rng, S, Pmax, page, KV, hd, H, jnp.float32)
    scale, full = 1.0 / np.sqrt(hd), page * Pmax
    out = paged_decode(q, kp, vp, table, jnp.asarray([0, full + 1, 3], jnp.int32),
                       layer=0, scale=scale, interpret=True)
    ref = _paged_ref(q, kp[0], vp[0], table, jnp.asarray([1, full, 3], jnp.int32), scale)
    assert not np.asarray(out[0]).any()
    assert ulps_at_scale(out[1:], ref[1:]) <= ULP_BOUND


def test_paged_decode_nan_poison_matches_reference():
    """NaN in a VALID position poisons exactly that slot in BOTH paths;
    NaN in a masked position (stale page tail) leaks into NEITHER."""
    from vescale_tpu.kernels.paged_attention import paged_decode

    rng = np.random.default_rng(11)
    S, Pmax, page, KV, hd, H = 3, 2, 4, 2, 8, 4
    q, kp, vp, table, _ = _paged_case(rng, S, Pmax, page, KV, hd, H, jnp.float32)
    lengths = jnp.asarray([5, 2, 7], jnp.int32)
    scale = 1.0 / np.sqrt(hd)
    # valid poison: slot 0, position 2 (< 5) of its first page
    kp1 = kp.at[0, table[0, 0], 2, 0, 3].set(jnp.nan)
    # masked poison: slot 1, position 3 of page 0 (>= length 2): stale bytes
    kp1 = kp1.at[0, table[1, 0], 3, 1, 0].set(jnp.nan)
    out = paged_decode(q, kp1, vp, table, lengths, layer=0, scale=scale, interpret=True)
    ref = _paged_ref(q, kp1[0], vp[0], table, lengths, scale)
    nan_rows = np.unique(np.argwhere(np.isnan(np.asarray(out)))[:, 0])
    nan_rows_ref = np.unique(np.argwhere(np.isnan(np.asarray(ref)))[:, 0])
    assert list(nan_rows) == [0] and list(nan_rows_ref) == [0]
    fin = ~np.isnan(np.asarray(ref))
    assert ulps_at_scale(np.asarray(out)[fin], np.asarray(ref)[fin]) <= ULP_BOUND


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("layout", list(PAGED_LAYOUTS))
def test_paged_decode_reads_only_live_positions_of_its_layer(layout, dtype):
    """Everything the call has no business reading is NaN — K and V of every
    position past a slot's length (the tail of its last page, its pages
    beyond, the null page, unmapped pages) and the whole of the layers the
    call does not name — and the output is, bit for bit, the clean pool's."""
    from vescale_tpu.kernels.paged_attention import paged_decode

    rng = np.random.default_rng(13)
    S, Pmax, page, KV, hd, H = PAGED_LAYOUTS[layout]
    layer = 2
    q, kp, vp, table, lengths = _paged_case(rng, S, Pmax, page, KV, hd, H, dtype, layers=3)
    live = np.zeros(kp.shape[:3], bool)      # (layer, physical page, position in it)
    for s, n in enumerate(np.asarray(lengths)):
        pos = np.arange(int(n))
        live[layer, np.asarray(table)[s, pos // page], pos % page] = True
    poison = lambda pool: jnp.where(jnp.asarray(live)[..., None, None], pool, jnp.nan)
    scale = 1.0 / np.sqrt(hd)
    clean = paged_decode(q, kp, vp, table, lengths, layer=layer, scale=scale, interpret=True)
    out = paged_decode(q, poison(kp), poison(vp), table, lengths, layer=layer, scale=scale, interpret=True)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_array_equal(np.asarray(out), np.asarray(clean))
    ref = _paged_ref(q, kp[layer], vp[layer], table, lengths, scale)
    assert ulps_at_scale(out, ref) <= _paged_bound(dtype)


def test_serve_engine_decode_tokens_identical_off_vs_interpret(kmode):
    """End-to-end engine proof: greedy token streams equal between the XLA
    decode and the fused kernel, on a tp-sharded cache (shard_map leg)."""
    from vescale_tpu.models.llama import Llama, LlamaConfig
    from vescale_tpu.serve import KVCacheConfig, PagedKVCache, ServeEngine

    cfg = LlamaConfig(vocab_size=96, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=8,
                      num_key_value_heads=8, max_position_embeddings=32,
                      dtype=jnp.float32)
    model = Llama(cfg)
    params = model.init(jax.random.key(0), jnp.ones((1, 4), jnp.int32))["params"]

    def run(mode):
        kmode(mode)
        mesh = DeviceMesh(("tp",), (4,))
        kc = KVCacheConfig(layers=2, kv_heads=8, head_dim=cfg.head_dim,
                           num_slots=2, page_size=4, pages_per_slot=4)
        cache = PagedKVCache(kc, mesh)
        eng = ServeEngine(cfg, mesh, params, cache)
        slot = cache.alloc(3, 5)
        logits = eng.prefill((5, 9, 17), slot)
        cache.commit_prefill(slot, 3)
        toks = [int(np.argmax(logits))]
        for _ in range(4):
            t = [0] * kc.num_slots
            t[slot] = toks[-1]
            lg = eng.decode(t)
            cache.advance(slot)
            toks.append(int(np.argmax(lg[slot])))
        kmode("off")
        return toks

    assert run("off") == run("interpret")


# ========================================================== fused adamw
@pytest.mark.parametrize("n", [1, 255, 256, 257])
@pytest.mark.parametrize("state_dtype", [jnp.bfloat16, jnp.float32])
def test_fused_adamw_bitwise_under_jit(n, state_dtype):
    """Non-divisible block edges (1, 255, 257) and both state dtypes: the
    carried moments are BIT-IDENTICAL to the jitted XLA chain; the update
    of each leg is within 4 elementwise ulps of the float64 evaluation of
    its formula (``update_ulps_vs_float64`` — the two jitted legs are not
    compared with each other: XLA:CPU contracts the first moment
    differently in each fusion it copies it into, docs/kernels.md)."""
    from vescale_tpu.kernels.fused_adamw import fused_adamw_update, update_ulps_vs_float64

    rng = np.random.default_rng(n)
    b1, b2, eps = 0.9, 0.999, 1e-8
    g = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    m = jnp.asarray(rng.normal(size=(n,)), jnp.float32).astype(state_dtype)
    v = jnp.abs(jnp.asarray(rng.normal(size=(n,)), jnp.float32)).astype(state_dtype)
    c1 = jnp.asarray(1.0 - b1 ** 5, jnp.float32)
    c2 = jnp.asarray(1.0 - b2 ** 5, jnp.float32)

    def ref(g, m, v, c1, c2):
        g32 = g.astype(jnp.float32)
        m32 = b1 * m.astype(jnp.float32) + (1.0 - b1) * g32
        v32 = b2 * v.astype(jnp.float32) + (1.0 - b2) * jnp.square(g32)
        u = ((m32 / c1) / (jnp.sqrt(v32 / c2) + eps)).astype(g.dtype)
        return u, m32.astype(state_dtype), v32.astype(state_dtype)

    def ker(g, m, v, c1, c2):
        return fused_adamw_update(g, m, v, c1, c2, b1=b1, b2=b2, eps=eps,
                                  state_dtype=state_dtype, interpret=True)

    (uk, mk, vk), (ur, mr, vr) = jax.jit(ker)(g, m, v, c1, c2), jax.jit(ref)(g, m, v, c1, c2)
    assert np.array_equal(np.asarray(mk), np.asarray(mr))
    assert np.array_equal(np.asarray(vk), np.asarray(vr))
    for u in (uk, ur):
        assert update_ulps_vs_float64(u, g, m, v, c1, c2, b1=b1, b2=b2, eps=eps) <= 4.0


def test_fused_adamw_nan_poison():
    """A NaN grad element must poison u/m/v at exactly that element in
    both paths (skip-step overflow protection upstream depends on it)."""
    from vescale_tpu.kernels.fused_adamw import fused_adamw_update

    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(37,)), jnp.float32).at[5].set(jnp.nan)
    m = jnp.asarray(rng.normal(size=(37,)), jnp.float32).astype(jnp.bfloat16)
    v = jnp.abs(jnp.asarray(rng.normal(size=(37,)), jnp.float32)).astype(jnp.bfloat16)
    c1 = jnp.asarray(0.5, jnp.float32)
    c2 = jnp.asarray(0.1, jnp.float32)
    u, mo, vo = fused_adamw_update(g, m, v, c1, c2, b1=0.9, b2=0.999, eps=1e-8,
                                   state_dtype=jnp.bfloat16, interpret=True)
    for out in (u, mo, vo):
        nan_at = np.argwhere(np.isnan(np.asarray(out, np.float32))).ravel()
        assert list(nan_at) == [5]


def test_adamw_lowmem_step_bitwise_and_zero_collectives(kmode):
    """adamw_lowmem inside a ZeRO DistributedOptimizer on a dp mesh:
    kernel dispatch keeps the step bitwise-identical AND the compiled
    step's collective counts unchanged (the custom_partitioning rule
    follows the state's ZeRO sharding instead of forcing gathers)."""
    import optax

    from jax.sharding import NamedSharding, PartitionSpec as P

    from vescale_tpu.debug.comm_mode import count_collectives
    from vescale_tpu.parallel.optimizer import DistributedOptimizer, adamw_lowmem

    mesh = DeviceMesh(("dp",), (8,))
    rng = np.random.default_rng(0)
    rep = NamedSharding(mesh.jax_mesh, P())
    params = {"w": jax.device_put(
        jnp.asarray(rng.normal(size=(64, 16)), jnp.float32), rep)}
    grads = {"w": jax.device_put(
        jnp.asarray(rng.normal(size=(64, 16)), jnp.float32), rep)}
    pspecs = {"w": P()}

    def run(mode):
        kmode(mode)
        dopt = DistributedOptimizer(adamw_lowmem(1e-3), mesh, pspecs)
        state = jax.jit(dopt.init)(params)
        step = jax.jit(dopt.step)
        text = step.lower(params, state, grads).compile().as_text()
        p, s = step(params, state, grads)
        kmode("off")
        return count_collectives(text), p, s

    c_off, p_off, s_off = run("off")
    c_int, p_int, s_int = run("interpret")
    assert c_off == c_int, f"collectives changed: {c_off} vs {c_int}"
    assert np.array_equal(np.asarray(p_off["w"]), np.asarray(p_int["w"]))
    for a, b in zip(jax.tree_util.tree_leaves(s_off), jax.tree_util.tree_leaves(s_int)):
        if hasattr(a, "shape"):
            assert np.array_equal(np.asarray(a), np.asarray(b))


# =========================================================== fused xent
@pytest.mark.parametrize("shape", [(2, 8, 128), (3, 7, 96)])
@pytest.mark.parametrize("smoothing", [0.0, 0.1])
def test_loss_kernel_matches_xla_sharded(kmode, shape, smoothing):
    """Vocab-parallel loss on a tp mesh: value and grad parity between the
    XLA path and the fused kernel, even rows odd rows, with smoothing."""
    from vescale_tpu.loss import vocab_parallel_cross_entropy

    rng = np.random.default_rng(int(np.prod(shape)))
    B, T, V = shape
    logits = jnp.asarray(rng.normal(size=shape), jnp.float32)
    tgt = jnp.asarray(rng.integers(0, V, (B, T)), jnp.int32)
    mesh = DeviceMesh(("tp",), (8,))

    def value_and_grad(mode):
        kmode(mode)
        fn = lambda lg: vocab_parallel_cross_entropy(
            lg, tgt, mesh=mesh, vocab_dim_name="tp", label_smoothing=smoothing)
        out = jax.value_and_grad(fn)(logits)
        kmode("off")
        return out

    (l0, g0), (l1, g1) = value_and_grad("off"), value_and_grad("interpret")
    assert ulps_at_scale(l1, l0) <= ULP_BOUND
    assert ulps_at_scale(g1, g0) <= ULP_BOUND


def test_loss_kernel_plain_path_and_nan(kmode):
    from vescale_tpu.loss import vocab_parallel_cross_entropy

    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(4, 64)), jnp.float32)
    tgt = jnp.asarray(rng.integers(0, 64, (4,)), jnp.int32)
    kmode("off")
    a = vocab_parallel_cross_entropy(logits, tgt)
    kmode("interpret")
    b = vocab_parallel_cross_entropy(logits, tgt)
    assert ulps_at_scale(b, a) <= ULP_BOUND
    # NaN-poisoned logits: both paths must yield NaN loss
    poisoned = logits.at[1, 3].set(jnp.nan)
    nb = vocab_parallel_cross_entropy(poisoned, tgt)
    kmode("off")
    na = vocab_parallel_cross_entropy(poisoned, tgt)
    assert np.isnan(float(na)) and np.isnan(float(nb))


def test_loss_kernel_indivisible_vocab_falls_back(kmode):
    """A vocab shard too small for the kernel grid falls back to the XLA
    path (counted) and stays correct."""
    from vescale_tpu import telemetry
    from vescale_tpu.loss import vocab_parallel_cross_entropy

    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(2, 4, 40)), jnp.float32)  # 40/8 = 5 < 8
    tgt = jnp.asarray(rng.integers(0, 40, (2, 4)), jnp.int32)
    mesh = DeviceMesh(("tp",), (8,))
    kmode("off")
    ref = vocab_parallel_cross_entropy(logits, tgt, mesh=mesh, vocab_dim_name="tp")
    telemetry.init(out_dir=None, memtrack=False)
    try:
        kmode("interpret")
        out = vocab_parallel_cross_entropy(logits, tgt, mesh=mesh, vocab_dim_name="tp")
        snap = telemetry.get_registry().snapshot()["counters"]
        assert snap.get("kernel_fallback_fused_xent_total", 0) >= 1
    finally:
        kmode("off")
        telemetry.shutdown()
    assert np.array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_loss_kernel_dtypes(kmode, dtype):
    """bf16 logits cast to fp32 at the loss boundary in both paths."""
    from vescale_tpu.loss import vocab_parallel_cross_entropy

    rng = np.random.default_rng(4)
    logits = jnp.asarray(rng.normal(size=(2, 8, 64)), np.float32).astype(dtype)
    tgt = jnp.asarray(rng.integers(0, 64, (2, 8)), jnp.int32)
    mesh = DeviceMesh(("tp",), (8,))
    kmode("off")
    a = vocab_parallel_cross_entropy(logits, tgt, mesh=mesh, vocab_dim_name="tp")
    kmode("interpret")
    b = vocab_parallel_cross_entropy(logits, tgt, mesh=mesh, vocab_dim_name="tp")
    assert ulps_at_scale(b, a) <= ULP_BOUND


# ============================================================ smoke wiring
def test_kernels_smoke_script():
    """tier-1 wiring of scripts/kernels_smoke.py — the ISSUE 11 acceptance
    battery (off byte-identity, interpret parity, collective counts)."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "kernels_smoke.py")],
        capture_output=True,
        text=True,
        timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout[-3000:]}\nstderr:\n{out.stderr[-3000:]}"
    assert "KERNELS SMOKE OK" in out.stdout
