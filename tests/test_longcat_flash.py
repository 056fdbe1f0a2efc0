"""The LongCat-Flash layer on the serve path (``models/longcat_flash.py`` over
``models/mla.py``, the softmax router under a selection bias and the identity
experts of ``moe/dropless.py``, the latent form of ``serve/kv_cache.py`` with
TWO pool layers a model layer, ``serve/hybrid_engine.py`` with the model's
module plugged in, ``kernels/paged_attention.py:paged_decode_latent``) at a small
size on the CPU, against the plain float32 reference of
``benchmark/families/longcat_flash.py`` (the expanded form only; it imports
nothing of the program) and against loops written here."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.spec import SpecError, load_family
from vescale_tpu.mesh import DeviceMesh
from vescale_tpu.models import longcat_flash as lc
from vescale_tpu.models import mla
from vescale_tpu.moe import dropless
from vescale_tpu.moe.dropless import dropless_experts, identity_experts, route_softmax_biased
from vescale_tpu.serve import ContinuousBatchingScheduler, HybridServeEngine, PagedKVCache, PrefixCache, SlotStateUnsupported
from vescale_tpu.serve.hybrid_engine import hybrid_cache_config

FAMILY = load_family("longcat_flash")
ASSUMED = dict(FAMILY.ASSUMED)
# hidden 64, two model layers (four sublayers), 4 heads of 16 + 8 | 16 over a latent of 32; 16 real experts and 8
# identity ones, 4 of the 24 outputs kept a token, of which this chip holds real experts 0-3
TOY = {"model": "longcat_flash", "vocab_size": 96, "hidden_size": 64, "num_layers": 2, "ffn_hidden_size": 96,
       "expert_ffn_hidden_size": 32, "n_routed_experts": 4, "zero_expert_num": 8, "zero_expert_type": "identity", "moe_topk": 4,
       "routed_scaling_factor": 6, "num_attention_heads": 4, "attention_bias": False, "attention_method": "MLA",
       "q_lora_rank": 32, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
       "mla_scale_q_lora": True, "mla_scale_kv_lora": True, "rope_theta": 10000000, "rms_norm_eps": 1e-5,
       "max_position_embeddings": 131072, "reduced": ["n_routed_experts", "vocab_size"],
       "published": {"n_routed_experts": 16, "vocab_size": 192}, "share": {"chips": 4, "of": ["n_routed_experts", "vocab_size"]},
       "assumed": ASSUMED}
SLOTS, PAGE, PAGES = 3, 4, 8          # 32 positions a slot: rungs 8, 16, 32
TIGHT = 2e-5                          # float32 program against float32 reference


def toy_config(**changes):
    """The program's config of TOY, computing in float32 so that it can be held tightly to the reference."""
    return dataclasses.replace(FAMILY.program_config(TOY, prefill_chunk=8), dtype=jnp.float32, **changes)


@pytest.fixture(scope="module", params=["xla_legs", "experts_sorted", "kernels_interpreted"])
def system(request):
    """The toy engine, three times: as a CPU builds it (the XLA decode leg, the
    dense prefill attention, all held experts on all tokens); with both of the
    expert layer's limits turned to 0 while the programs are traced, so that
    every program takes the sorted form a real prefill takes; and with the Pallas
    kernels a TPU would compile (``paged_decode_latent``, the flash forward with
    two head widths, the grouped SwiGLU kernel) run through the interpreter."""
    cfg = toy_config()
    mesh = DeviceMesh(("tp",), (1,), devices=jax.devices()[:1])
    params = jax.jit(lambda k: lc.init_params(cfg, k))(jax.random.key(7))
    cache = PagedKVCache(hybrid_cache_config(cfg, num_slots=SLOTS, page_size=PAGE, pages_per_slot=PAGES), mesh)
    with pytest.MonkeyPatch.context() as patch:
        if request.param in ("experts_sorted", "kernels_interpreted"):
            patch.setattr(dropless, "DENSE_MAX_TOKENS", 0)
            patch.setattr(dropless, "PADDED_MAX_MEAN_ROWS", 0)
        if request.param == "kernels_interpreted":
            patch.setenv("VESCALE_KERNELS", "interpret")
        engine = HybridServeEngine(cfg, mesh, params, cache).warm()     # every program is traced here
    assert engine.kernel_decode == (request.param == "kernels_interpreted")
    return cfg, mesh, params, cache, engine


def tokens(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(1, TOY["vocab_size"] - 1, n)]


def rel(got, want):
    return float(np.max(np.abs(np.asarray(got) - np.asarray(want))) / np.max(np.abs(np.asarray(want))))


def through_the_cache(engine, cache, prompt, forced):
    """Prefill ``prompt``, then feed ``forced`` one decode step at a time; the logits rows."""
    cache.reset()
    slot = cache.alloc(len(prompt), len(forced) + 1)
    rows = [engine.prefill(prompt, slot)]
    cache.commit_prefill(slot, len(prompt))
    for tok in forced:
        toks = np.zeros((cache.num_slots,), np.int32)
        toks[slot] = tok
        rows.append(engine.decode(toks)[slot])
        cache.advance(slot)
    return np.stack(rows), slot


# ------------------------------------------------- program against reference
@pytest.mark.parametrize("n", [5, 13], ids=["rung_8", "rung_16"])
def test_prefill_then_decode_through_the_latent_cache_is_the_references_expanded_forward(system, n):
    """The pad rule is under the check (5 of 8, 13 of 16 positions real), the
    decode steps are the ABSORBED form against the reference's expanded one, and
    every sublayer reads the pool layer it wrote."""
    cfg, _mesh, params, cache, engine = system
    prompt, forced = tokens(n, n), tokens(100 + n, 4)
    got, _slot = through_the_cache(engine, cache, prompt, forced)
    want = FAMILY.logits(params, TOY, prompt + forced, range(n - 1, n + 4))
    assert rel(got, want) < 5 * TIGHT
    cache.reset()


# what each fault is worth at the toy's size against float32 rounding (2e-5): the attention's and the identity part's
# move everything; a fault of the few pairs on held experts what the held experts' part is of the stream
LEAST = {"fp8_weights": 1e-2, "no_q_multiplier": 1e-2, "no_kv_multiplier": 1e-2, "renormalised_gates": 1e-2,
         "no_scaling_factor": 1e-2, "no_identity": 1e-2, "identity_sign": 1e-2, "bias_weighs": 1e-3, "no_selection_bias": 1e-3,
         "shortcut_returns_at_once": 1e-3, "shortcut_from_second": 1e-2, "rotary_halves": 1e-2, "top11": 1e-3}


@pytest.mark.parametrize("wrong", FAMILY.FAULTS)
def test_a_wrong_computation_on_the_same_weights_reads_far_from_the_program(system, wrong):
    """Either multiplier dropped, the gates renormalised or unscaled, the identity
    part dropped or turned, the bias weighing or not choosing, the shortcut
    returning where it leaves or leaving after the second sublayer: each reads
    fifty times float32 rounding or more away."""
    cfg, _mesh, params, cache, engine = system
    prompt, forced = tokens(13, 13), tokens(113, 4)
    got, _slot = through_the_cache(engine, cache, prompt, forced)
    bad = FAMILY.logits(params, TOY, prompt + forced, range(12, 17), wrong=wrong)
    assert rel(got, bad) > LEAST[wrong], wrong
    cache.reset()


def test_the_layer_is_two_sublayers_whose_routed_branch_leaves_after_the_first_and_returns_at_the_end():
    """The layer's order of operations, written out here against ``lc.layer``."""
    cfg = toy_config()
    lp = lc.init_params(cfg, jax.random.key(3))["layers_0"]
    x = jax.random.normal(jax.random.key(4), (6, cfg.hidden_size), jnp.float32)
    attend = lambda i, u: mla.mla_prefill(cfg.mla, lp[f"self_attn_{i}"], u)[0]
    got, counts = lc.layer(cfg, lp, x, jnp.ones((6,), bool), attend)
    norm = lambda v, w: lc.rmsnorm(v, w["weight"], cfg.rms_norm_eps)
    mlp = lambda i, h: lc.swiglu(h, lp[f"mlps_{i}"]["gate"], lp[f"mlps_{i}"]["up"], lp[f"mlps_{i}"]["down"], cfg.dtype)
    a = x + attend(0, norm(x, lp["input_layernorm_0"]))
    h0 = norm(a, lp["post_attention_layernorm_0"])
    shortcut = lc.routed_branch(cfg, lp["mlp"], h0)[0]
    b = a + mlp(0, h0)
    c = b + attend(1, norm(b, lp["input_layernorm_1"]))
    want = c + mlp(1, norm(c, lp["post_attention_layernorm_1"])) + shortcut
    assert rel(got, want) < 1e-6
    assert rel(got, want - shortcut) > 1e-2, "the branch is a visible part of the layer"
    assert len(counts) == 2 and counts[0].shape == (cfg.experts_held,)


def test_both_lora_multipliers_are_in_the_rows_the_cache_keeps_and_in_the_queries():
    cfg = toy_config()
    a = cfg.mla
    assert a.q_scale == pytest.approx((64 / 32) ** 0.5) and a.kv_scale == pytest.approx((64 / 32) ** 0.5)
    real = FAMILY.program_config({**TOY, "hidden_size": 6144, "q_lora_rank": 1536, "kv_lora_rank": 512}).mla
    assert real.q_scale == 2.0 and real.kv_scale == pytest.approx(3.4641, abs=1e-4) and real.softmax_scale == 24 ** -0.5
    assert a.cos_scale == 1.0 and a.softmax_scale == pytest.approx(24 ** -0.5)
    np.testing.assert_allclose(a.inv_freq, 1e7 ** (-np.arange(0, 8, 2) / 8), rtol=1e-6)
    ap = lc.init_params(cfg, jax.random.key(3))["layers_0"]["self_attn_0"]
    u = jax.random.normal(jax.random.key(5), (6, cfg.hidden_size), jnp.float32)
    plain = dataclasses.replace(a, q_scale=1.0, kv_scale=1.0)
    y, rows = mla.mla_prefill(a, ap, u)
    _y, rows_plain = mla.mla_prefill(plain, ap, u)
    # the latent is scaled, the rotary key beside it is not
    np.testing.assert_allclose(rows[:, :32], a.kv_scale * rows_plain[:, :32], rtol=1e-6)
    np.testing.assert_allclose(rows[:, 32:40], rows_plain[:, 32:40], rtol=1e-6)
    for dropped in (dataclasses.replace(a, q_scale=1.0), dataclasses.replace(a, kv_scale=1.0)):
        assert rel(mla.mla_prefill(dropped, ap, u)[0], y) > 1e-2
    off = dataclasses.replace(cfg, mla_scale_q_lora=False, mla_scale_kv_lora=False).mla
    assert (off.q_scale, off.kv_scale) == (1.0, 1.0)


def test_the_absorbed_form_is_the_expanded_form_on_the_same_weights_under_the_multipliers():
    cfg = toy_config()
    a = cfg.mla
    ap = lc.init_params(cfg, jax.random.key(3))["layers_1"]["self_attn_1"]
    T = 16
    u = jax.random.normal(jax.random.key(4), (T, cfg.hidden_size), jnp.float32)
    y, rows = jax.jit(lambda u: mla.mla_prefill(a, ap, u))(u)
    pool = jnp.zeros((4, T // PAGE + 1, PAGE, 1, a.cache_row), jnp.float32)
    pool = pool.at[3, 1:, :, 0].set(rows[: T].reshape(T // PAGE, PAGE, a.cache_row))
    table = jnp.arange(1, T // PAGE + 1, dtype=jnp.int32)[None]
    y1, _pool = jax.jit(lambda u1, pool: mla.mla_step(
        a, ap, u1, pool, layer=3, table=table, page=jnp.asarray([T // PAGE]), offset=jnp.asarray([PAGE - 1]),
        positions=jnp.asarray([T - 1]), valid_len=jnp.asarray([T]), interpret=None))(u[-1:], pool)
    assert rel(y1[0], y[-1]) < 1e-5


# -------------------------------------------------------------------- router
def route_loop(scores, k, scale, bias):
    """The softmax router under a selection bias, a token at a time in numpy."""
    ids, gates = [], []
    for row in np.asarray(scores, np.float64):
        p = np.exp(row - row.max())
        p /= p.sum()
        top = sorted(range(len(p)), key=lambda e: (-(p[e] + bias[e]), e))[:k]
        ids.append(top)
        gates.append([p[e] * scale for e in top])
    return np.asarray(ids), np.asarray(gates)


@pytest.mark.parametrize("case", ["random", "biased", "flat"])
def test_the_softmax_router_keeps_the_largest_of_p_plus_b_and_weighs_with_p_times_six(case):
    rng = np.random.default_rng(0)
    scores = rng.normal(size=(50, 24)) * 2
    bias = np.zeros(24)
    if case == "biased":
        bias = rng.normal(size=24) * 0.05
    if case == "flat":
        scores, bias = np.zeros((5, 24)), np.linspace(0.01, -0.01, 24)
    idx, gates = route_softmax_biased(jnp.asarray(scores, jnp.float32), 4, scale=6.0, bias=jnp.asarray(bias, jnp.float32))
    want_idx, want_gates = route_loop(scores, 4, 6.0, bias)
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    np.testing.assert_allclose(np.asarray(gates), want_gates, rtol=1e-5)
    assert not np.allclose(np.asarray(gates).sum(axis=1), 6.0), "the gates are not renormalised over the kept"
    plain_idx, plain_gates = route_softmax_biased(jnp.asarray(scores, jnp.float32), 4, scale=6.0)
    if case == "biased":
        # the bias chooses: other outputs are kept; it does not weigh: a kept output's gate is 6 p whatever its bias
        assert (np.asarray(idx) != np.asarray(plain_idx)).any()
        p = np.asarray(jax.nn.softmax(jnp.asarray(scores, jnp.float32), axis=-1))
        np.testing.assert_allclose(np.asarray(gates), 6.0 * np.take_along_axis(p, np.asarray(idx), axis=1), rtol=1e-6)
    if case == "random":
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(plain_idx))
        np.testing.assert_allclose(np.asarray(gates), np.asarray(plain_gates), rtol=1e-6)


def test_the_selection_bias_gives_every_share_and_the_identity_outputs_the_same_quantiles():
    cfg = toy_config()
    b = np.asarray(lc.selection_bias(cfg, jax.random.key(1)))
    assert b.shape == (24,) and b.dtype == np.float32
    shares = np.sort(b[:16].reshape(4, 4), axis=1)
    assert (shares == shares[0]).all() and abs(float(b[:16].sum())) < 1e-6 and abs(float(b[16:].sum())) < 1e-6
    assert float(np.abs(b).max()) < 2 * lc.BIAS_OVER_UNIFORM / 24
    assert not np.array_equal(b, np.asarray(lc.selection_bias(cfg, jax.random.key(2)))), "the seed draws the order"
    with pytest.raises(ValueError):
        lc.selection_bias(toy_config(experts_held=3), jax.random.key(1))


# ---------------------------------------------------- identity experts, the share
def _expert_loop(ep, h, idx, gates, first, held):
    out = np.zeros(h.shape, np.float64)
    for n in range(h.shape[0]):
        for j in range(idx.shape[1]):
            e = int(idx[n, j]) - first
            if 0 <= e < held:
                out[n] += float(gates[n, j]) * np.asarray(FAMILY._swiglu(h[n:n + 1], ep["w_gate"][e], ep["w_up"][e], ep["w_down"][e]))[0]
    return out


@pytest.mark.parametrize("case", ["identity_alone", "real_alone", "mixed"])
def test_identity_experts_alone_real_experts_alone_and_both(case):
    cfg = toy_config(experts_held=16)
    ep = lc.init_params(cfg, jax.random.key(5))["layers_0"]["mlp"]
    ep = dict(ep, w_down=ep["w_down"] * 64.0)                   # (the init rule's narrow down projections, widened to be seen)
    h = jax.random.normal(jax.random.key(6), (9, cfg.hidden_size), jnp.float32)
    idx = {"identity_alone": [16, 19, 23, 17], "real_alone": [0, 7, 15, 3], "mixed": [2, 18, 9, 23]}[case]
    idx = jnp.asarray(np.tile(idx, (9, 1)), jnp.int32)
    gates = jax.random.uniform(jax.random.key(8), (9, 4), jnp.float32, 0.05, 0.5)
    routed, counts = dropless_experts(h, idx, gates, ep["w_gate"], ep["w_up"], ep["w_down"])
    same, zero_pairs = identity_experts(h, idx, gates, first_identity=16)
    kept_here = np.asarray(idx) < 16
    assert int(zero_pairs) == int((~kept_here).sum()) and int(counts.sum()) == int(kept_here.sum())
    np.testing.assert_allclose(same, np.where(kept_here, 0.0, gates).sum(axis=1, keepdims=True) * h, rtol=1e-6)
    assert rel(np.asarray(routed) + 1e-9, _expert_loop(ep, h, np.asarray(idx), np.asarray(gates), 0, 16) + 1e-9) < 1e-5
    if case == "identity_alone":
        assert not np.asarray(routed).any() and int(counts.sum()) == 0, "twelve identity picks cost no product"
    if case == "real_alone":
        assert not np.asarray(same).any()
    masked = identity_experts(h, idx, gates, first_identity=16, token_mask=jnp.arange(9) < 4)
    assert not np.asarray(masked[0][4:]).any() and int(masked[1]) == int((~kept_here[:4]).sum())


def test_a_token_with_only_identity_picks_costs_no_product_and_gets_itself_back_under_its_gates():
    cfg = toy_config()
    ep = lc.init_params(cfg, jax.random.key(5))["layers_1"]["mlp"]
    # a router that sends every token to the identity outputs alone (ids 16-23): no held expert gets a row
    ep = dict(ep, router=jnp.zeros_like(ep["router"]).at[:, 16:].set(1.0), router_bias=jnp.zeros_like(ep["router_bias"]))
    h = jnp.abs(jax.random.normal(jax.random.key(6), (7, cfg.hidden_size), jnp.float32))    # every score positive
    out, counts, zero_pairs = lc.routed_branch(cfg, ep, h)
    assert int(counts.sum()) == 0 and int(zero_pairs) == 7 * 4
    scores = np.asarray(h @ ep["router"], np.float64)
    p = np.exp(scores - scores.max(1, keepdims=True))
    p /= p.sum(1, keepdims=True)
    weight = 6.0 * np.sort(p, axis=1)[:, -4:].sum(axis=1)
    assert rel(out, weight[:, None] * np.asarray(h)) < 1e-5
    # ... and one that sends them to experts 8-15, held by another share: nothing at all comes back here
    ep = dict(ep, router=jnp.zeros_like(ep["router"]).at[:, 8:16].set(1.0))
    out, counts, zero_pairs = lc.routed_branch(cfg, ep, h)
    assert not np.asarray(out).any() and int(counts.sum()) == 0 and int(zero_pairs) == 0
    out, counts, zero_pairs = lc.routed_branch(dataclasses.replace(cfg, first_expert_held=8), ep, h)
    assert int(counts.sum()) == 7 * 4 and int(zero_pairs) == 0


@pytest.mark.parametrize("N", [40, 160], ids=["batched", "sorted"])
def test_the_four_shares_add_up_to_the_uncut_layer_with_the_identity_part_counted_once(N):
    whole = toy_config(experts_held=16, first_expert_held=0)
    ep = lc.init_params(whole, jax.random.key(9))["layers_1"]["mlp"]
    ep = dict(ep, w_down=ep["w_down"] * 64.0)                   # (the real experts' part as large as the identity part)
    h = jax.random.normal(jax.random.key(10), (N, whole.hidden_size), jnp.float32)
    uncut = dict(TOY, n_routed_experts=16, reduced=["vocab_size"], share={"chips": 2, "of": ["vocab_size"]})
    want = FAMILY.routed(ep, h, uncut, first_held=0, total=16)
    full, counts, zero_pairs = lc.routed_branch(whole, ep, h)
    assert rel(full, want) < TIGHT and int(counts.sum()) + int(zero_pairs) == 4 * N and 0 < int(zero_pairs) < 4 * N
    same = FAMILY.routed(dict(ep, **{k: ep[k][:0] for k in ("w_gate", "w_up", "w_down")}), h, uncut, first_held=0, total=16)
    assert rel(same, want) > 0.1 and rel(want - same, want) > 0.1, "both parts are a visible share of the layer"
    total, pairs = same, 0
    for index in range(4):
        quarter = toy_config(experts_held=4, first_expert_held=4 * index)
        mine = dict(ep, **{k: ep[k][4 * index: 4 * index + 4] for k in ("w_gate", "w_up", "w_down")})
        part, counts, zero_here = lc.routed_branch(quarter, mine, h)
        assert int(zero_here) == int(zero_pairs), "every share computes the identity part whole"
        total = total + (part - same)               # ... so it is counted once
        pairs += int(counts.sum())
    assert rel(total, want) < TIGHT and pairs + int(zero_pairs) == 4 * N


def test_a_long_rung_goes_through_the_routed_branch_in_pieces_and_gives_the_same(monkeypatch):
    cfg = toy_config()
    ep = lc.init_params(cfg, jax.random.key(9))["layers_0"]["mlp"]
    h = jax.random.normal(jax.random.key(10), (48, cfg.hidden_size), jnp.float32)
    live = jnp.arange(48) < 41
    whole = lc.routed_branch(cfg, ep, h, live)
    form_bytes = lambda rows: 6 * cfg.num_experts_per_tok * cfg.hidden_size * rows      # (dropless.row_pieces' arithmetic)
    monkeypatch.setattr(dropless, "SORTED_FORM_BYTES", form_bytes(16))                  # three pieces of 16 rows
    assert dropless.row_pieces(48, cfg.num_experts_per_tok, cfg.hidden_size) == 3
    pieces = jax.jit(lambda h: lc.routed_branch(cfg, ep, h, live))(h)
    assert rel(pieces[0], whole[0]) < 1e-6 and not np.asarray(pieces[0][41:]).any()
    np.testing.assert_array_equal(np.asarray(pieces[1]), np.asarray(whole[1]))
    assert int(pieces[2]) == int(whole[2])
    monkeypatch.setattr(dropless, "SORTED_FORM_BYTES", form_bytes(20))                  # 48 rows are three pieces of 16 again
    assert rel(lc.routed_branch(cfg, ep, h)[0], lc._routed_rows(cfg, ep, h, None)[0]) < 1e-6
    monkeypatch.undo()
    # the real ladder: every rung over 1,024 rows divides into equal pieces of at most 1,024
    assert [dropless.row_pieces(n, 12, 6144) for n in (128, 1024, 1536, 2048, 3072, 4096)] == [1, 1, 2, 2, 3, 4]


# --------------------------------------------------------------------- cache
def test_a_model_layer_owns_two_layers_of_the_latent_pool(system):
    cfg, mesh, params, cache, engine = system
    assert cfg.attention_layers == 4 and cache.config.layers == 4 and cache.config.latent and cache.v is None
    assert cache.k.data.shape == (4, SLOTS * PAGES + 1, PAGE, 1, cfg.mla.cache_row) and not cache.has_slot_state
    assert list(cache.arrays()) == ["k"]
    cut = hybrid_cache_config(cfg, num_slots=SLOTS, page_size=PAGE, pages_per_slot=PAGES, num_pages=17)
    assert cut.pool_pages == 17 and cut.layers == 4
    PrefixCache(cache)                              # pages alone: nothing to refuse
    ContinuousBatchingScheduler(cache, prefix_cache=PrefixCache(cache))
    # every pool layer holds its own sublayer's rows: after a prefill no two layers of the slot's first page agree
    cache.reset()
    slot = cache.alloc(8, 4)
    engine.prefill(tokens(2, 8), slot)
    cache.commit_prefill(slot, 8)
    page = np.asarray(cache.k.data[:, int(cache.page_table[slot, 0])], np.float32)
    assert all(np.abs(page[a]).max() > 0 for a in range(4))
    assert all(not np.allclose(page[a], page[b]) for a in range(4) for b in range(a))
    cache.reset()


def test_what_this_engine_has_no_program_for_says_which_and_not_that_the_cache_forbids_it(system):
    cfg, _mesh, params, cache, engine = system
    for call, args in ((engine.decode_multi, (np.zeros((SLOTS, 2), np.int32),)), (engine.prefill_suffix, ([1] * 8, 0, 4))):
        with pytest.raises(NotImplementedError) as e:
            call(*args)
        assert not isinstance(e.value, SlotStateUnsupported) and "program" in str(e.value)


def test_every_rung_is_compiled_before_the_engine_is_handed_over_and_the_counters_count(system):
    cfg, _mesh, params, cache, engine = system
    assert engine.buckets == [8, 16, 32] and engine.model is lc
    before = (engine._prefill_fn._cache_size(), engine._decode_fn._cache_size())
    start = engine.trace_counters()
    got, slot = through_the_cache(engine, cache, tokens(3, 20), tokens(4, 3))
    assert (engine._prefill_fn._cache_size(), engine._decode_fn._cache_size()) == before and before[0] >= 3
    c = {k: v - start[k] for k, v in engine.trace_counters().items()}
    assert c["decode_steps"] == 3 and c["prefill_tokens_real"] == 20 and c["prefill_tokens_padded"] == 32
    # one slot of 20, 21, 22 positions + the new one: 6 pages each step, FOUR pool layers, pages of 4 rows of 128 float32
    assert c["latent_bytes_read"] == 3 * 6 * PAGE * cfg.mla.cache_row * 4 * 4
    assert c["prefill_attn_flops"] == 4 * 2 * (24 + 16) * 32 * 32 // 2 * 4
    # two expert layers (one a MODEL layer), four pairs a token, identity ones among them
    assert c["moe_assignments"] == 3 * 4 * 2 and c["moe_layer_steps"] == 3 * 2
    assert 0 <= c["moe_assignments_held"] and 0 < c["zero_expert_assignments"]
    assert c["moe_assignments_held"] + c["zero_expert_assignments"] <= c["moe_assignments"]
    # (ONE pool layer's pages; the two idle slots' one page each is counted too)
    assert (c["decode_pages_read"], c["decode_pages_capacity"]) == ((6 * 3 + 2 * 3, 3 * SLOTS * PAGES)
                                                                    if engine.kernel_decode else (0, 0))
    cache.reset()


def test_the_normal_path_serves_it_and_a_replay_through_the_cache_gives_the_same_tokens(system):
    """``ContinuousBatchingScheduler`` + ``run_serve_resilient`` over more
    requests than slots: every request completes, and its tokens are those of a
    greedy replay alone on the cache."""
    from vescale_tpu.serve import Request, run_serve_resilient

    cfg, _mesh, params, cache, engine = system
    cache.reset()
    sched = ContinuousBatchingScheduler(cache)
    prompts = {rid: tokens(40 + rid, n) for rid, n in enumerate((5, 19, 9, 17, 6))}
    arrivals = [Request(rid=rid, prompt=tuple(p), max_new_tokens=8) for rid, p in prompts.items()]
    run_serve_resilient(engine=engine, scheduler=sched, arrivals=[(0, r) for r in arrivals],
                        install_signal_handlers=False, coordinate=False)
    sched.ledger_check()
    assert sched.counts["completed"] == len(prompts)
    for rid, p in prompts.items():
        assert list(sched.outcomes[rid]["tokens"]) == engine.replay_greedy(p, 8)
    cache.reset()


# -------------------------------------------------------------------- family
@pytest.mark.parametrize("broken,says", [
    ({"zero_expert_type": "copy"}, "zero_expert_type"),
    ({"mla_scale_q_lora": False}, "mla_scale_q_lora"),
    ({"attention_method": "MHA"}, "attention_method"),
    ({"assumed": dict(ASSUMED, norm_topk_prob=True)}, "norm_topk_prob"),
    ({"assumed": {}}, "assumed"),
    ({"share": {"chips": 8, "of": ["n_routed_experts", "vocab_size"]}}, "do not hold"),
    ({"share": {"chips": 2, "of": ["vocab_size"]}}, "share"),
])
def test_the_family_refuses_what_it_cannot_run(broken, says):
    with pytest.raises(SpecError) as e:
        FAMILY.program_config(dict(TOY, **broken))
    assert says in str(e.value)


def test_the_family_gives_this_chip_its_place_and_counts_what_the_program_allocates():
    cfg = FAMILY.program_config(dict(TOY, share={"chips": 4, "of": ["n_routed_experts", "vocab_size"], "index": 2}))
    assert (cfg.num_experts, cfg.zero_expert_num, cfg.router_outputs, cfg.experts_held, cfg.first_expert_held) == (16, 8, 24, 4, 8)
    assert cfg.num_experts_per_tok == 4 and cfg.routed_scaling_factor == 6.0 and cfg.rope_theta == 1e7
    params = jax.eval_shape(lambda k: lc.init_params(FAMILY.program_config(TOY), k), jax.random.key(0))
    nbytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in jax.tree_util.tree_leaves(params))
    assert FAMILY.weight_bytes(TOY) == nbytes
    assert FAMILY.param_count(TOY) == sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(params))
    assert params["layers_0"]["mlp"]["router"].shape == (64, 24) and params["layers_0"]["mlp"]["router"].dtype == jnp.float32
    assert FAMILY.sublayers(TOY) == 4 and FAMILY.latent_bytes_per_position(TOY) == 40 * 2
    assert FAMILY.pool_bytes_per_position(TOY) == 4 * 128 * 2
    assert FAMILY.prefill_rungs({"positions_per_slot": 4096}) == [128, 256, 512, 1024, 1536, 2048, 3072, 4096]
    with pytest.raises(ValueError):
        lc.LongcatFlashConfig(experts_held=16, first_expert_held=500)
    with pytest.raises(ValueError):
        lc.LongcatFlashConfig(num_experts_per_tok=800)
