"""The DeepSeek-V2 block on the serve path (``models/deepseek_v2.py``, the
group-limited router of ``moe/dropless.py``, the latent form of
``serve/kv_cache.py``, ``serve/hybrid_engine.py`` with the model's module
plugged in, ``kernels/paged_attention.py:paged_decode_latent``) at a small
size on the CPU, against the plain float32 reference of
``benchmark/families/deepseek_v2.py`` (the expanded form only; it imports
nothing of the program) and against loops written here."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.spec import SpecError, load_family
from vescale_tpu.kernels.paged_attention import _latent_blocks, paged_decode_latent, supports_latent
from vescale_tpu.mesh import DeviceMesh
from vescale_tpu.models import blocks
from vescale_tpu.models import deepseek_v2 as ds
from vescale_tpu.moe import route_group_limited
from vescale_tpu.serve import (ContinuousBatchingScheduler, HybridServeEngine, KVCacheConfig, PagedKVCache, PrefixCache,
                               SlotStateUnsupported)
from vescale_tpu.serve.hybrid_engine import hybrid_cache_config

FAMILY = load_family("deepseek_v2")
ROPE = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707, "mscale_all_dim": 0.707,
        "original_max_position_embeddings": 4096, "type": "yarn"}
# hidden 64, a dense layer and two expert layers, 4 heads of 16 + 8 | 16 over a latent of 32; 16 experts in 4
# groups, 2 groups and 3 experts kept a token, of which this chip holds 8 (groups 0 and 1)
TOY = {"model": "deepseek_v2", "vocab_size": 96, "hidden_size": 64, "num_hidden_layers": 3, "first_k_dense_replace": 1,
       "intermediate_size": 96, "moe_intermediate_size": 32, "moe_layer_freq": 1, "n_shared_experts": 2,
       "n_routed_experts": 8, "num_experts_per_tok": 3, "n_group": 4, "topk_group": 2, "routed_scaling_factor": 16,
       "norm_topk_prob": False, "scoring_func": "softmax", "topk_method": "group_limited_greedy",
       "num_attention_heads": 4, "num_key_value_heads": 4, "attention_bias": False, "q_lora_rank": 32,
       "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_theta": 10000,
       "rope_scaling": ROPE, "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
       "reduced": ["n_routed_experts", "vocab_size"], "published": {"n_routed_experts": 16, "vocab_size": 192},
       "share": {"chips": 2, "of": ["n_routed_experts", "vocab_size"]}}
SLOTS, PAGE, PAGES = 3, 4, 8          # 32 positions a slot: rungs 8, 16, 32
TIGHT = 2e-5                          # float32 program against float32 reference


def toy_config(**changes):
    """The program's config of TOY, computing in float32 so that it can be held tightly to the reference."""
    return dataclasses.replace(FAMILY.program_config(TOY, prefill_chunk=8), dtype=jnp.float32, **changes)


@pytest.fixture(scope="module", params=["xla_legs", "experts_sorted", "kernels_interpreted", "experts_padded"])
def system(request):
    """The toy engine, four times: as a CPU builds it (the XLA decode leg, the
    dense prefill attention, all experts on all tokens); with both of the
    expert layer's limits turned to 0 while the programs are traced, so that
    both take the sorted, grouped product a real prefill takes; with the Pallas
    kernels a TPU would compile (``paged_decode_latent``, the flash forward
    with two head widths, and, both limits at 0 here too, the grouped SwiGLU
    kernel that is the sorted form's leg there) run through the interpreter; and with the first limit
    alone turned to 0, so that both are candidates for the padded batched
    product that a 256- or 512-rung prefill takes at the real size."""
    from vescale_tpu.moe import dropless

    cfg = toy_config()
    mesh = DeviceMesh(("tp",), (1,), devices=jax.devices()[:1])
    params = jax.jit(lambda k: ds.init_params(cfg, k))(jax.random.key(7))
    cache = PagedKVCache(hybrid_cache_config(cfg, num_slots=SLOTS, page_size=PAGE, pages_per_slot=PAGES), mesh)
    with pytest.MonkeyPatch.context() as patch:
        if request.param in ("experts_sorted", "kernels_interpreted", "experts_padded"):
            patch.setattr(dropless, "DENSE_MAX_TOKENS", 0)
        if request.param in ("experts_sorted", "kernels_interpreted"):
            patch.setattr(dropless, "PADDED_MAX_MEAN_ROWS", 0)
        if request.param == "experts_padded":
            patch.setattr(dropless, "PADDED_MIN_MEAN_ROWS", 0)          # (a toy program is a few rows an expert)
        if request.param == "kernels_interpreted":
            patch.setenv("VESCALE_KERNELS", "interpret")
        engine = HybridServeEngine(cfg, mesh, params, cache).warm()     # every program is traced here
    assert engine.kernel_decode == (request.param == "kernels_interpreted")
    assert engine._decode_padded_candidate == (request.param == "experts_padded")
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
    """The pad rule is under the check (5 of 8, 13 of 16 positions real), and
    the decode steps are the ABSORBED form against the reference's expanded one."""
    cfg, _mesh, params, cache, engine = system
    prompt, forced = tokens(n, n), tokens(100 + n, 4)
    got, _slot = through_the_cache(engine, cache, prompt, forced)
    want = FAMILY.logits(params, TOY, prompt + forced, range(n - 1, n + 4))
    assert rel(got, want) < 5 * TIGHT
    cache.reset()


@pytest.mark.parametrize("wrong", FAMILY.FAULTS)
def test_a_wrong_algebra_on_the_same_weights_reads_far_from_the_program(system, wrong):
    cfg, _mesh, params, cache, engine = system
    prompt, forced = tokens(5, 5), tokens(105, 4)
    got, _slot = through_the_cache(engine, cache, prompt, forced)
    bad = FAMILY.logits(params, TOY, prompt + forced, range(4, 9), wrong=wrong)
    # a fault of the attention moves everything; one of the routed part what the routed part is of the stream
    # (``ROUTED_DOWN_GAIN``): a thousand times what float32 rounding reads here (on the chip, at the real
    # widths: 1.3 to 2.2 times the limit, PERF.md section 6)
    assert rel(got, bad) > (5 * FAMILY.SERVE_LOGITS_TOLERANCE if wrong in ("no_mscale", "rotary_halves") else 1e-2), wrong
    cache.reset()


def test_the_absorbed_form_is_the_expanded_form_on_the_same_weights():
    """One layer's attention alone: the last position's output of the expanded
    prefill over T positions is the absorbed step's over the first T - 1 rows."""
    cfg = toy_config()
    ap = ds.init_params(cfg, jax.random.key(3))["layers_1"]["self_attn"]
    T = 16
    u = jax.random.normal(jax.random.key(4), (T, cfg.hidden_size), jnp.float32)
    y, rows = jax.jit(lambda u: ds.mla_prefill(cfg, ap, u))(u)
    assert rows.shape == (T, cfg.cache_row) and not np.asarray(rows[:, cfg.latent_row:]).any()
    pool = jnp.zeros((1, 6, PAGE, 1, cfg.cache_row), jnp.float32)
    pool = pool.at[0, jnp.asarray([3, 1, 5, 2])].set(rows.reshape(4, PAGE, 1, cfg.cache_row))   # pages out of order
    pool = pool.at[0, 2, PAGE - 1].set(37.0)        # the last position is the step's to write
    table = jnp.asarray([[3, 1, 5, 2]], jnp.int32)
    y1, pool = jax.jit(lambda u1, pool: ds.mla_step(
        cfg, ap, u1, pool, layer=0, table=table, page=jnp.asarray([2]), offset=jnp.asarray([PAGE - 1]),
        positions=jnp.asarray([T - 1]), valid_len=jnp.asarray([T]), interpret=None))(u[T - 1:], pool)
    assert rel(y1[0], y[T - 1]) < TIGHT
    assert rel(pool[0, 2, PAGE - 1, 0], rows[T - 1]) < TIGHT


# -------------------------------------------------------------------- rotary
def test_yarn_frequencies_and_mscale_are_the_hand_computed_ones():
    cfg = FAMILY.program_config(dict(TOY, qk_rope_head_dim=64))
    inv = ds.inv_freq(cfg)
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    # correction range: 64 ln(4096 / (2 pi r)) / (2 ln 10000) at r = 32 and 1: 10.46 -> 10, 22.50 -> 23
    low = math.floor(64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(10000)))
    high = math.ceil(64 * math.log(4096 / (1 * 2 * math.pi)) / (2 * math.log(10000)))
    assert (low, high) == (10, 23)
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)                  # fast pairs keep their frequency
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-6)             # slow pairs are stretched 40-fold
    np.testing.assert_allclose(inv[15], plain[15] * (1 - 5 / 13) + plain[15] / 40 * (5 / 13), rtol=1e-6)
    np.testing.assert_allclose(inv, FAMILY.yarn_inv_freq(dict(TOY, qk_rope_head_dim=64)), rtol=1e-6)
    assert blocks.yarn_mscale(40, 0.707) == pytest.approx(0.1 * 0.707 * math.log(40) + 1) == pytest.approx(1.2608, abs=1e-4)
    assert blocks.yarn_mscale(1, 0.707) == 1.0
    real = FAMILY.program_config(dict(TOY, qk_nope_head_dim=128, qk_rope_head_dim=64))
    assert real.softmax_scale == pytest.approx(192 ** -0.5 * 1.5896, rel=1e-4)


def test_rotary_turns_interleaved_pairs_and_scores_depend_on_the_distance_alone():
    cfg = toy_config()
    x = jax.random.normal(jax.random.key(0), (6, cfg.qk_rope_head_dim), jnp.float32)
    inv = ds.inv_freq(cfg)
    got = np.asarray(ds.rotary(cfg, x, jnp.arange(6)))
    for t in range(6):
        for i in range(cfg.qk_rope_head_dim // 2):
            a, b, ang = float(x[t, 2 * i]), float(x[t, 2 * i + 1]), t * float(inv[i])
            assert got[t, 2 * i] == pytest.approx(a * math.cos(ang) - b * math.sin(ang), abs=1e-5)
            assert got[t, 2 * i + 1] == pytest.approx(b * math.cos(ang) + a * math.sin(ang), abs=1e-5)
    # with a head axis, either side of the positions' axis
    heads = jnp.stack([x, 2 * x], axis=1)                                    # (T, H, dim)
    np.testing.assert_allclose(ds.rotary(cfg, heads, jnp.arange(6)[:, None])[:, 1], 2 * got, rtol=1e-6)
    np.testing.assert_allclose(ds.rotary(cfg, heads.transpose(1, 0, 2), jnp.arange(6)[None, :])[1], 2 * got, rtol=1e-6)
    q = np.asarray(ds.rotary(cfg, jnp.broadcast_to(x[0], (6, 8)), jnp.arange(6)))
    k = np.asarray(ds.rotary(cfg, jnp.broadcast_to(x[1], (6, 8)), jnp.arange(6)))
    assert q[3] @ k[1] == pytest.approx(q[5] @ k[3], rel=1e-4)


# -------------------------------------------------------------------- router
def route_loop(scores, k, n_group, topk_group, scale):
    """Group-limited greedy routing, a token at a time in numpy."""
    ids, gates = [], []
    for row in np.asarray(scores, np.float64):
        p = np.exp(row - row.max())
        p /= p.sum()
        per = len(p) // n_group
        best = [p[g * per:(g + 1) * per].max() for g in range(n_group)]
        kept = sorted(range(n_group), key=lambda g: (-best[g], g))[:topk_group]
        masked = np.array([p[e] if e // per in kept else 0.0 for e in range(len(p))])
        top = sorted(range(len(p)), key=lambda e: (-masked[e], e))[:k]
        ids.append(top)
        gates.append([masked[e] * scale for e in top])
    return np.asarray(ids), np.asarray(gates)


@pytest.mark.parametrize("case", ["random", "ties", "flat"])
def test_the_group_limited_router_is_a_loop_over_tokens(case):
    rng = np.random.default_rng(0)
    scores = rng.normal(size=(50, 16)) * 2
    if case == "ties":                      # whole groups tie, and experts inside a group: the lowest id wins
        scores = np.round(scores)
    if case == "flat":
        scores = np.zeros((5, 16))
    idx, gates, kept = route_group_limited(jnp.asarray(scores, jnp.float32), 3, n_group=4, topk_group=2, scale=16.0)
    want_idx, want_gates = route_loop(scores, 3, 4, 2, 16.0)
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    np.testing.assert_allclose(np.asarray(gates), want_gates, rtol=1e-5)
    assert (np.asarray(kept).sum(axis=1) == 2).all()
    assert all(np.asarray(kept)[n, e // 4] for n in range(len(scores)) for e in np.asarray(idx)[n])
    assert not np.allclose(np.asarray(gates).sum(axis=1), 16.0), "the gates are not renormalised over the kept"


def test_a_token_whose_kept_experts_all_lie_elsewhere_gets_the_shared_experts_alone():
    cfg = toy_config()
    ep = ds.init_params(cfg, jax.random.key(5))["layers_1"]["mlp"]
    # a router that sends every token to groups 2 and 3 (experts 8-15): none is held by share 0
    ep = dict(ep, router=jnp.zeros_like(ep["router"]).at[:, 8:].set(1.0))
    h = jnp.abs(jax.random.normal(jax.random.key(6), (7, cfg.hidden_size), jnp.float32))    # every score positive
    out, counts, groups = ds.expert_layer(cfg, ep, h)
    assert int(counts.sum()) == 0 and int(groups) == 0
    shared = FAMILY._swiglu(h, ep["shared"]["gate"], ep["shared"]["up"], ep["shared"]["down"])
    assert rel(out, shared) < TIGHT
    other = dataclasses.replace(cfg, first_expert_held=8)
    _out, counts, groups = ds.expert_layer(other, ep, h)
    assert int(counts.sum()) == 7 * 3 and int(groups) == 7 * 2


@pytest.mark.parametrize("N", [40, 160], ids=["batched", "sorted"])
def test_the_four_shares_add_up_to_the_uncut_layer_with_the_shared_experts_counted_once(N):
    whole = toy_config(experts_held=16, first_expert_held=0)
    ep = ds.init_params(whole, jax.random.key(9))["layers_1"]["mlp"]
    h = jax.random.normal(jax.random.key(10), (N, whole.hidden_size), jnp.float32)
    uncut = dict(TOY, n_routed_experts=16, reduced=["vocab_size"], share={"chips": 2, "of": ["vocab_size"]})
    want = FAMILY.expert_layer(ep, h, uncut, first_held=0)
    full, counts, groups = ds.expert_layer(whole, ep, h)
    assert rel(full, want) < TIGHT and int(counts.sum()) == 3 * N and int(groups) == 2 * N
    shared = FAMILY._swiglu(h, ep["shared"]["gate"], ep["shared"]["up"], ep["shared"]["down"])
    total, kept_groups = shared, 0
    for index in range(4):
        quarter = toy_config(experts_held=4, first_expert_held=4 * index)
        mine = dict(ep, **{k: ep[k][4 * index: 4 * index + 4] for k in ("w_gate", "w_up", "w_down")})
        part, counts, groups = ds.expert_layer(quarter, mine, h)
        assert quarter.groups_held == (index,)
        total = total + (part - shared)             # each share computes the shared experts whole: counted once
        kept_groups += int(groups)
    assert rel(total, want) < TIGHT and kept_groups == 2 * N


# --------------------------------------------------------------------- cache
def test_the_latent_cache_is_one_pool_and_shares_rolls_back_and_resets_like_any_other(system):
    cfg, mesh, params, cache, engine = system
    assert cache.config.latent and cache.v is None and not cache.has_slot_state
    assert cache.k.data.shape == (cfg.num_hidden_layers, SLOTS * PAGES + 1, PAGE, 1, cfg.cache_row)
    assert list(cache.arrays()) == ["k"] and len(cache.fingerprint()) == 5
    cache.reset()
    first = cache.alloc(8, 4)
    cache.commit_prefill(first, 8)
    shared = [int(p) for p in cache.page_table[first, :2]]
    for p in shared:
        cache.retain_page(p)                        # as the radix tree pins a prefill's pages
    second = cache.alloc_shared(shared, 8, 4)
    assert [int(p) for p in cache.page_table[second, :2]] == shared and cache.page_ref(shared[0]) == 3
    cache.commit_prefill(second, 8)
    cache.advance(second)
    cache.rollback(second, 8)
    assert int(cache.lengths[second]) == 8
    before = cache.fingerprint()
    cache.free(second)
    assert cache.page_ref(shared[0]) == 2 and cache.fingerprint() != before
    cache.reset()
    assert cache.free_page_count() == SLOTS * PAGES and cache.free_slot_count() == SLOTS
    PrefixCache(cache)                              # pages alone: nothing to refuse
    ContinuousBatchingScheduler(cache, prefix_cache=PrefixCache(cache))
    with pytest.raises(ValueError):
        KVCacheConfig(layers=1, kv_heads=2, head_dim=8, latent=True)
    with pytest.raises(ValueError):
        cache.update(cache.k.data, cache.k.data)


def test_a_shared_latent_page_gives_the_second_slot_the_first_slots_logits(system):
    """Two slots whose tables map the same two pages of one prefill decode the same next token's logits."""
    cfg, _mesh, params, cache, engine = system
    prompt = tokens(21, 8)
    cache.reset()
    first = cache.alloc(8, 4)
    engine.prefill(prompt, first)
    cache.commit_prefill(first, 8)
    second = cache.alloc_shared([int(p) for p in cache.page_table[first, :2]], 8, 4)
    cache.commit_prefill(second, 8)
    toks = np.zeros((SLOTS,), np.int32)
    toks[[first, second]] = 17
    step = engine.decode(toks)
    assert rel(step[second], step[first]) < 1e-6
    cache.reset()


def test_what_this_engine_has_no_program_for_says_which_and_not_that_the_cache_forbids_it(system):
    cfg, _mesh, params, cache, engine = system
    for call, args in ((engine.decode_multi, (np.zeros((SLOTS, 2), np.int32),)), (engine.prefill_suffix, ([1] * 8, 0, 4))):
        with pytest.raises(NotImplementedError) as e:
            call(*args)
        assert not isinstance(e.value, SlotStateUnsupported) and "program" in str(e.value)


def test_every_rung_is_compiled_before_the_engine_is_handed_over_and_the_counters_count(system):
    cfg, _mesh, params, cache, engine = system
    assert engine.buckets == [8, 16, 32]
    before = (engine._prefill_fn._cache_size(), engine._decode_fn._cache_size())
    start = engine.trace_counters()
    got, slot = through_the_cache(engine, cache, tokens(3, 20), tokens(4, 3))
    assert (engine._prefill_fn._cache_size(), engine._decode_fn._cache_size()) == before and before[0] >= 3
    c = {k: v - start[k] for k, v in engine.trace_counters().items()}
    assert c["decode_steps"] == 3 and c["prefill_tokens_real"] == 20 and c["prefill_tokens_padded"] == 32
    # one slot of 20, 21, 22 positions + the new one: 6 pages each step, 3 layers, pages of 4 rows of 128 float32
    assert c["latent_bytes_read"] == 3 * 6 * PAGE * cfg.cache_row * 4 * cfg.num_hidden_layers
    assert c["prefill_attn_flops"] == 4 * 2 * (24 + 16) * 32 * 32 // 2 * 3
    assert c["moe_assignments"] == 3 * 3 * 2 and 0 <= c["moe_assignments_held"] <= c["moe_assignments"]
    assert 0 <= c["moe_groups_kept_here"] <= 3 * 2 * 2
    assert (c["decode_pages_read"], c["decode_pages_capacity"]) == ((6 * 3 + 2 * 3, 3 * SLOTS * PAGES)
                                                                    if engine.kernel_decode else (0, 0))
    cache.reset()


# -------------------------------------------------------------------- kernel
def ragged_pool(rng, dtype, lengths, *, layers=2, page=16, row=256, pages_per_slot=9, scattered=False):
    """A latent pool whose null page holds NaN and a table of each slot's live pages, in order or ``scattered`` over
    the pool; every unused entry of the table points at the null page."""
    live = sum(-(-n // page) for n in lengths)
    pool = jnp.asarray(rng.normal(size=(layers, 1 + live, page, 1, row)), dtype)
    pool = pool.at[:, 0].set(jnp.nan)               # the null page holds garbage that must reach nothing
    ids = 1 + (rng.permutation(live) if scattered else np.arange(live))
    table, nxt = np.zeros((len(lengths), pages_per_slot), np.int32), 0
    for s, n in enumerate(lengths):
        for i in range(-(-n // page)):
            table[s, i], nxt = ids[nxt], nxt + 1
    return pool, jnp.asarray(table)


# a block of the kernel is 512 positions, fetched in groups of 128 (the cases' pages of 16 and float32 rows of 256)
_G = 16 * _latent_blocks(64, 16, 256, 4)[0]
_T = _G * _latent_blocks(64, 16, 256, 4)[1]
# name: (lengths, heads, pages a slot, pool layers, the table scattered)
LATENT_CASES = {
    # nothing, part of a page, a full slot, ragged, one whole page: one block a slot
    "one-block": ([0, 5, 16 * 9, 37, 16], 8, 9, 2, False),
    # a block's edges: the double buffer across blocks and the next slot's first block started from a slot's last
    "block-edges-64-heads": ([_T - 1, _T, _T + 1, 2 * _T], 64, 2 * _T // 16, 1, True),
    # a group's edges, a slot of nothing and one of a page BETWEEN long ones, a table no whole number of blocks wide
    "group-edges-128-heads": ([2 * _T + _G + 1, 0, _T + _G, 16, 3 * _T - 5, _G - 1, 1, 2 * _T + 2 * _G], 128, 100, 1, True),
    # three blocks and a tail a slot, each slot's last block another count of live groups, the pages in order
    "long-slots-64-heads": ([3 * _T + 7, 3 * _T + _G + 16, 4 * _T - 1, 3 * _T + 3 * _G], 64, 4 * _T // 16, 2, False),
}


@pytest.mark.parametrize("case", list(LATENT_CASES))
@pytest.mark.parametrize("dtype,bound", [(jnp.float32, 2e-6), (jnp.bfloat16, 2e-2)], ids=["float32", "bfloat16"])
def test_the_latent_decode_kernel_is_the_xla_leg_on_ragged_lengths_a_null_page_and_a_full_slot(dtype, bound, case):
    lengths, heads, pages_per_slot, layers, scattered = LATENT_CASES[case]
    rng = np.random.default_rng(0)
    pool, table = ragged_pool(rng, dtype, lengths, layers=layers, pages_per_slot=pages_per_slot, scattered=scattered)
    q = jnp.asarray(rng.normal(size=(len(lengths), heads, 256)), dtype)
    valid = jnp.asarray(lengths, jnp.int32)
    for layer in range(layers):
        got = paged_decode_latent(q, pool, table, valid, layer=layer, scale=0.3, latent=128, interpret=True)
        want = paged_decode_latent(q, pool, table, valid, layer=layer, scale=0.3, latent=128, interpret=None)
        assert got.shape == (len(lengths), heads, 128) and got.dtype == jnp.float32
        assert np.isfinite(np.asarray(got)).all() and not np.asarray(got)[np.asarray(lengths) == 0].any()
        assert float(jnp.max(jnp.abs(got - want))) < bound * float(jnp.max(jnp.abs(want)))


def test_the_latent_kernel_says_what_it_takes():
    assert supports_latent(jnp.bfloat16, 640, 512, 16, interpret=False)
    assert not supports_latent(jnp.bfloat16, 576, 512, 16, interpret=False)     # not whole lane tiles
    assert not supports_latent(jnp.bfloat16, 640, 512, 8, interpret=False)      # half a sublane tile of bfloat16
    assert supports_latent(jnp.float32, 640, 512, 8, interpret=False)
    assert supports_latent(jnp.float32, 40, 32, 4, interpret=True)
    assert not supports_latent(jnp.int8, 640, 512, 32, interpret=True)
    with pytest.raises(ValueError):
        paged_decode_latent(jnp.zeros((1, 2, 40), jnp.float32), jnp.zeros((1, 2, 4, 1, 48), jnp.float32),
                            jnp.zeros((1, 1), jnp.int32), jnp.ones((1,), jnp.int32), layer=0, scale=1.0, latent=32,
                            interpret=True)


def test_the_flash_forward_takes_values_of_another_width_than_the_scores():
    from vescale_tpu.ops.flash_attention import flash_attention_forward

    rng = np.random.default_rng(1)
    q, k = (jnp.asarray(rng.normal(size=(3, 64, 24)), jnp.float32) for _ in range(2))
    v = jnp.asarray(rng.normal(size=(3, 64, 16)), jnp.float32)
    dense = flash_attention_forward(q, k, v, scale=0.2)                        # the CPU's dense product
    blocked = flash_attention_forward(q, k, v, scale=0.2, block_q=16, block_k=16, interpret=True)
    assert blocked.shape == (3, 64, 16) and rel(blocked, dense) < 1e-5
    s = 0.2 * np.einsum("hqd,hkd->hqk", q, k) + np.where(np.tril(np.ones((64, 64), bool)), 0, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    assert rel(dense, np.einsum("hqk,hkd->hqd", p / p.sum(-1, keepdims=True), v)) < 1e-5


# -------------------------------------------------------------------- family
@pytest.mark.parametrize("broken,says", [
    ({"rope_scaling": dict(ROPE, type="linear")}, "YaRN"),
    ({"topk_method": "greedy"}, "group_limited_greedy"),
    ({"norm_topk_prob": True}, "norm_topk_prob"),
    ({"n_routed_experts": 2, "share": {"chips": 8, "of": ["n_routed_experts", "vocab_size"]}}, "whole routing groups"),
    ({"share": {"chips": 4, "of": ["n_routed_experts", "vocab_size"]}}, "do not hold"),
    ({"share": {"chips": 2, "of": ["vocab_size"]}}, "share"),
])
def test_the_family_refuses_what_it_cannot_run(broken, says):
    with pytest.raises(SpecError) as e:
        FAMILY.program_config(dict(TOY, **broken))
    assert says in str(e.value)


def test_the_family_gives_this_chip_its_place_and_counts_what_the_program_allocates():
    cfg = FAMILY.program_config(dict(TOY, share={"chips": 2, "of": ["n_routed_experts", "vocab_size"], "index": 1}))
    assert (cfg.num_experts, cfg.experts_held, cfg.first_expert_held, cfg.groups_held) == (16, 8, 8, (2, 3))
    params = jax.eval_shape(lambda k: ds.init_params(FAMILY.program_config(TOY), k), jax.random.key(0))
    nbytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in jax.tree_util.tree_leaves(params))
    assert FAMILY.weight_bytes(TOY) == nbytes
    assert FAMILY.latent_bytes_per_position(TOY) == 3 * 40 * 2
    assert FAMILY.prefill_rungs({"positions_per_slot": 8192}) == [128, 256, 512, 1024, 1536, 2048, 3072, 4096, 5120, 6144, 7168, 8192]
