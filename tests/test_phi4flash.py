"""Phi-4-flash's layers on the serve path (``models/phi4flash.py``: a stack in two
halves, Mamba-1 mixers beside rings under differential attention, ONE pool layer
that the second half reads, gated memory units; ``kernels/selective_scan.py`` and
``kernels/ssm_step.py:ssm_step_selective``; ``serve/hybrid_engine.py`` over a
cache of folded pages, rings, states and tails) at a small size on the CPU,
against the plain float32 reference of ``benchmark/families/phi4flash.py`` (which
imports nothing of the program)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.spec import SpecError, load_family
from vescale_tpu.mesh import DeviceMesh
from vescale_tpu.models import phi4flash as pf
from vescale_tpu.serve import HybridServeEngine, PagedKVCache, PrefixCache, SlotStateUnsupported
from vescale_tpu.serve.hybrid_engine import hybrid_cache_config

FAMILY = load_family("phi4flash")
# the published ratios at small widths: hidden 64, 4 query pairs on 2 key pairs of heads of 8 (rows of 16), N 16, window
# 8, ten layers: two self-decoder periods, the two middle layers, two cross periods
TOY = {"model": "phi4flash", "vocab_size": 512, "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 10,
       "num_attention_heads": 8, "num_key_value_heads": 4, "sliding_window": 8, "mb_per_layer": 2, "layer_norm_eps": 1e-5,
       "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False, "hidden_act": "silu",
       "assumed": {"mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2, "mamba_dt_rank": 4,
                   "attention": "differential_adjacent_pairs", "attention_bias": True, "position_embedding": "none",
                   "self_decoder_layers": 4, "memory_from": "scan_output_before_gate", "window_includes_self": True}}
SLOTS, PAGE, PAGES = 3, 4, 16       # 64 positions a slot (rungs 8, 16, 32, 64)
W = TOY["sliding_window"]
# float32 program against float32 reference: both round at 6e-8 an operation and sum in other orders.  The sound
# program reads 8e-7 to 1.5e-6 here, with the XLA legs and with the kernels interpreted; the faults 0.13 to 0.8.
TIGHT = 1e-5


def toy_config(**changes):
    """The program's config of TOY, computing in float32 so that it can be held tightly to the reference."""
    return dataclasses.replace(FAMILY.program_config(TOY, prefill_chunk=8), dtype=jnp.float32, **changes)


def build(cfg, params=None):
    mesh = DeviceMesh(("tp",), (1,), devices=jax.devices()[:1])
    if params is None:
        params = jax.jit(lambda k: pf.init_params(cfg, k))(jax.random.key(7))
    cache = PagedKVCache(hybrid_cache_config(cfg, num_slots=SLOTS, page_size=PAGE, pages_per_slot=PAGES), mesh)
    return params, cache, HybridServeEngine(cfg, mesh, params, cache)


@pytest.fixture(scope="module", params=["xla_legs", "kernels_interpreted"])
def system(request):
    """The toy engine, twice: with the XLA legs the CPU takes, and with the
    Pallas kernels a TPU would compile (``paged_decode_folded`` over pool and
    rings, ``ssm_step_selective``, ``selective_scan``, the windowed flash
    forward) run through the interpreter."""
    with pytest.MonkeyPatch.context() as patch:
        if request.param == "kernels_interpreted":
            patch.setenv("VESCALE_KERNELS", "interpret")
        cfg = toy_config()
        params, cache, engine = build(cfg)
        engine.warm()                               # every program is traced here
    assert engine.kernel_decode == engine.kernel_ssm == (request.param == "kernels_interpreted")
    return cfg, params, cache, engine


def tokens(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(1, TOY["vocab_size"] - 1, n)]


def rel(got, want):
    return float(np.max(np.abs(np.asarray(got) - np.asarray(want))) / np.max(np.abs(np.asarray(want))))


def decode_one(engine, cache, feed):
    toks = np.zeros((cache.num_slots,), np.int32)
    for slot, tok in feed.items():
        toks[slot] = tok
    out = engine.decode(toks)
    for slot in feed:
        cache.advance(slot)
    return out


def served_rows(engine, cache, prompt, forced):
    """The prefill's row and each teacher-forced decode step's, through pool, rings and states."""
    cache.reset()
    slot = cache.alloc(len(prompt), len(forced) + 1)
    rows = [np.asarray(engine.prefill(prompt, slot))]
    cache.commit_prefill(slot, len(prompt))
    for tok in forced:
        rows.append(np.asarray(decode_one(engine, cache, {slot: tok})[slot]))
    cache.reset()
    return np.stack(rows)


# ----------------------------------------------------------------- the kernels
def _scan_inputs(T, J, N, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    dt = jnp.asarray(rng.uniform(1e-3, 1e-1, size=(T, J)), jnp.float32).at[T - 3:].set(0.0)      # a pad: the state stands
    A = -jnp.asarray(np.tile(np.arange(1, N + 1, dtype=np.float32)[:, None], (1, J)) * rng.uniform(0.5, 1.5, size=(N, J)), jnp.float32)
    return f(T, J), dt, A, f(T, N), f(T, N)


def _plain_scan(u, dt, A, B, C):
    """The recurrence as a plain loop over positions, float64."""
    u, dt, A, B, C = (np.asarray(a, np.float64) for a in (u, dt, A, B, C))
    h, ys = np.zeros(A.shape), []
    for t in range(u.shape[0]):
        h = np.exp(dt[t][None, :] * A) * h + B[t][:, None] * (dt[t] * u[t])[None, :]
        ys.append((h * C[t][:, None]).sum(0))
    return np.stack(ys), h


@pytest.mark.parametrize("T,J,N", [(8, 128, 16), (64, 256, 16), (256, 640, 16), (136, 128, 8)],
                         ids=["one-eight", "two-lane-blocks", "two-row-blocks-J-no-power-of-two", "rows-of-8"])
def test_the_selective_scan_kernel_is_its_xla_leg_and_the_plain_loop(T, J, N):
    from vescale_tpu.kernels.selective_scan import selective_scan, supports

    args = _scan_inputs(T, J, N)
    assert supports(N, J, T, interpret=True)
    y_xla, last_xla = selective_scan(*args, interpret=None)
    y_ker, last_ker = selective_scan(*args, interpret=True)
    y_plain, last_plain = _plain_scan(*args)
    for got, leg, plain in ((y_ker, y_xla, y_plain), (last_ker, last_xla, last_plain)):
        assert rel(got, leg) < 1e-6 and rel(leg, plain) < 1e-5
    # the pad (dt = 0) left the state where the last real position put it
    np.testing.assert_allclose(np.asarray(last_ker), _plain_scan(*(a[: T - 3] for a in args[:2]), args[2], *(a[: T - 3] for a in args[3:]))[1],
                               rtol=1e-4, atol=1e-5)


def test_the_selective_scan_refuses_what_it_does_not_take():
    from vescale_tpu.kernels.selective_scan import selective_scan, supports

    assert not supports(16, 128, 12, interpret=True) and supports(16, 128, 16, interpret=True)
    assert not supports(16, 64, 16, interpret=False) and supports(16, 5120, 2048, interpret=False)
    with pytest.raises(ValueError, match="takes no sequence"):
        selective_scan(*_scan_inputs(12, 128, 16), interpret=True)


@pytest.mark.parametrize("layer", [0, 2])
def test_the_selective_step_kernel_is_its_xla_leg_and_leaves_an_idle_slot_and_other_layers_bit_for_bit(layer):
    from vescale_tpu.kernels.ssm_step import ssm_selective_xla, ssm_step_selective

    S, L, N, J = 4, 3, 16, 256
    u, dt, A, B, C = _scan_inputs(S, J, N, seed=3)
    dt = dt.at[1].set(0.0)                                                   # slot 1 is idle: dt = 0 and dt x = 0
    state = jnp.asarray(np.random.default_rng(5).normal(size=(L, S, N, J)), jnp.float32)
    new_xla, y_xla = ssm_selective_xla(state, dt, A, dt * u, B, C, layer=layer)
    new_ker, y_ker = ssm_step_selective(jnp.array(state), dt, A, dt * u, B, C, layer=jnp.int32(layer), interpret=True)
    assert rel(y_ker, y_xla) < 1e-6 and rel(new_ker[layer], new_xla[layer]) < 1e-6
    for leg in (new_xla, new_ker):
        assert np.array_equal(np.asarray(leg[layer, 1]), np.asarray(state[layer, 1])), "an idle slot's state stands"
        assert all(np.array_equal(np.asarray(leg[l]), np.asarray(state[l])) for l in range(L) if l != layer)
    h = np.exp(np.asarray(dt)[:, None, :] * np.asarray(A)[None]) * np.asarray(state[layer]) + np.asarray(B)[:, :, None] * np.asarray(dt * u)[:, None, :]
    assert rel(new_ker[layer], h) < 1e-6 and rel(y_ker, (h * np.asarray(C)[:, :, None]).sum(1)) < 1e-6


# ------------------------------------------------------ differential attention
def test_the_paired_heads_are_the_four_softmaxes_written_out():
    """``pair_queries`` on rows of ``[k_1, k_2]`` and ``[v_1, v_2]`` through ONE
    grouped softmax a head, against the formula: for query pair ``i`` on key
    pair ``g``, ``softmax(q_{i,s} k_{g,s}^T / sqrt(hd))`` for ``s`` in (1, 2),
    each against ``v_{g,1}`` and against ``v_{g,2}``."""
    cfg = toy_config()
    T, H, KV, hd = 12, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.normal(size=(T, n, hd)), jnp.float32) for n in (H, KV, KV))
    paired = pf.pair_queries(q)
    assert paired.shape == (T, H, 2 * hd)
    k2, v2 = k.reshape(T, KV // 2, 2 * hd), v.reshape(T, KV // 2, 2 * hd)
    got = np.stack([np.asarray(pf.attend_row(cfg, paired[t], k2.reshape(T, 1, -1), v2.reshape(T, 1, -1), t + 1)) for t in range(T)])
    want = np.zeros((T, H, 2 * hd))
    mask = np.tril(np.ones((T, T), bool))
    for i in range(H // 2):
        g = i // ((H // 2) // (KV // 2))
        for s in (0, 1):
            scores = np.asarray(q[:, 2 * i + s]) @ np.asarray(k[:, 2 * g + s]).T / np.sqrt(hd)
            S = np.asarray(jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1))
            for half in (0, 1):
                want[:, 2 * i + s, half * hd: (half + 1) * hd] = S @ np.asarray(v[:, 2 * g + half])
    assert rel(got, want) < 1e-6


def test_lambda_init_is_the_papers_and_the_combination_is_the_formulas():
    assert float(pf.lambda_init(0)) == pytest.approx(0.2) and float(pf.lambda_init(17)) == pytest.approx(0.8 - 0.6 * np.exp(-5.1))
    assert [FAMILY.lambda_init(l) for l in (1, 17, 31)] == pytest.approx([float(pf.lambda_init(l)) for l in (1, 17, 31)])
    cfg = toy_config()
    ap = jax.tree_util.tree_map(lambda a: a[0], pf.init_params(cfg, jax.random.key(2))["self"]["second"]["attn"])
    y = jnp.asarray(np.random.default_rng(2).normal(size=(5, cfg.num_attention_heads, 2 * cfg.head_dim)), jnp.float32)
    lam0 = 0.356
    lq1, lk1, lq2, lk2 = np.asarray(ap["lambda"], np.float64)
    lam = np.exp(lq1 @ lk1) - np.exp(lq2 @ lk2) + lam0
    o = np.asarray(y[:, 0::2], np.float64) - lam * np.asarray(y[:, 1::2], np.float64)
    o = o / np.sqrt((o ** 2).mean(-1, keepdims=True) + cfg.layer_norm_eps) * np.asarray(ap["subln"], np.float64) * (1 - lam0)
    want = o.reshape(5, -1) @ np.asarray(ap["o_proj"], np.float64) + np.asarray(ap["o_bias"], np.float64)
    assert rel(pf.differential(cfg, ap, y, lam0), want) < 1e-5


# ------------------------------------------------ the engine against the reference
# under the window, at it, over it, and a length at which the rings have wrapped twice
@pytest.mark.parametrize("n", [5, 8, 11, 20], ids=["under-the-window", "at-the-window", "over-the-window", "wrapped-twice"])
def test_prefill_and_teacher_forced_decode_against_the_reference(system, n):
    cfg, params, cache, engine = system
    prompt, forced = tokens(n, n), tokens(100 + n, 12)
    got = served_rows(engine, cache, prompt, forced)
    want = np.asarray(FAMILY.logits(params, TOY, prompt + forced, range(n - 1, n + len(forced))))
    assert got.shape == want.shape == (13, TOY["vocab_size"]) and rel(got, want) < TIGHT


@pytest.mark.parametrize("wrong", ["window_minus_1", "pair_swapped", "m_after_gate", "fp8_weights"])
def test_the_reference_with_a_fault_reads_not_correct_at_the_familys_tolerance(system, wrong):
    """What the tolerance sees: the sound program against the reference with a
    window one short, the first query pair's two softmaxes exchanged, ``m``
    taken after the gate, or its weights in e4m3, at the lengths that reach the
    window: every one reads several times the family's tolerance (the sound
    program: 1e-6)."""
    cfg, params, cache, engine = system
    prompt, forced = tokens(20, 20), tokens(120, 12)
    got = served_rows(engine, cache, prompt, forced)
    faulty = np.asarray(FAMILY.logits(params, TOY, prompt + forced, range(19, 32), wrong))
    assert rel(got, faulty) > 3 * FAMILY.SERVE_LOGITS_TOLERANCE, wrong
    with pytest.raises(ValueError, match="wrong is one of"):
        FAMILY.logits(params, TOY, prompt, [0], "no_such_fault")


def test_check_window_reads_every_row_and_each_fault(system):
    cfg, params, cache, engine = system
    sound = FAMILY.check_window(engine, TOY, 3, prompt_tokens=19, steps=9)
    assert sound["ok"] and sound["logits_max_abs_diff_over_max"] < TIGHT and sound["argmax_agreement"] == 1.0
    assert (sound["prompt_tokens"], sound["decode_steps"], sound["tolerance"]) == (19, 9, FAMILY.SERVE_LOGITS_TOLERANCE)
    for wrong in ("window_minus_1", "pair_swapped", "m_after_gate"):
        out = FAMILY.check_window(engine, TOY, 3, prompt_tokens=19, steps=9, wrong=wrong)
        assert not out["ok"] and out["wrong"] == wrong
    assert cache.alloc(4, 1) is not None        # the check left the cache free
    cache.reset()


# --------------------------------------------- the prefill that stops half way
def _whole_stack(cfg, params, toks, length):
    """Every layer over EVERY row, in the program's own functions and precision
    (what a prefill would cost if the second half ran the prompt): the last real
    row's logits."""
    c, T = cfg, len(toks)
    x = pf.embed(c, params, jnp.asarray(toks, jnp.int32))
    norm = lambda np_, x: pf.layernorm(x, np_["weight"], np_["bias"], c.layer_norm_eps)
    take = lambda tree, i: jax.tree_util.tree_map(lambda a: a[i], tree)
    causal = jnp.tril(jnp.ones((T, T), bool))

    def dense(ap, u, k, v, lam0):
        q = pf._queries(c, ap, u)                                             # (T, H, 2 hd), paired
        k, v = pf._pair_heads(c, k), pf._pair_heads(c, v)
        group = q.shape[1] // k.shape[1]
        s = jnp.einsum("qhd,khd->hqk", q, jnp.repeat(k, group, axis=1)) * c.head_dim ** -0.5
        y = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1), jnp.repeat(v, group, axis=1))
        return pf.differential(c, ap, y, lam0)

    for i in range(c.self_periods):
        mp, wp = take(params["self"]["first"], i), take(params["self"]["second"], i)
        out, _m, _state, _tail = pf.mamba_prefill(c, mp["mamba"], norm(mp["input_layernorm"], x), length, interpret=None)
        x = pf.mlp(c, mp, x + out)
        y, _k, _v = pf.window_prefill(c, wp["attn"], norm(wp["input_layernorm"], x), float(pf.lambda_init(2 * i + 1)))
        x = pf.mlp(c, wp, x + y)
    mp, fp = params["mid_mamba"], params["mid_full"]
    out, m, _state, _tail = pf.mamba_prefill(c, mp["mamba"], norm(mp["input_layernorm"], x), length, interpret=None)
    x = pf.mlp(c, mp, x + out)
    u = norm(fp["input_layernorm"], x)
    k, v = pf._keys_values(c, fp["attn"], u)
    x = pf.mlp(c, fp, x + dense(fp["attn"], u, k, v, float(pf.lambda_init(c.split + 1))))
    for i in range(c.cross_periods):
        gp, cp = take(params["cross"]["first"], i), take(params["cross"]["second"], i)
        x = pf.mlp(c, gp, x + pf.gmu(c, gp["gmu"], norm(gp["input_layernorm"], x), m))
        x = pf.mlp(c, cp, x + dense(cp["attn"], norm(cp["input_layernorm"], x), k, v, float(pf.lambda_init(c.split + 3 + 2 * i))))
    return pf.head(c, params, x)[length - 1]


@pytest.mark.parametrize("n,rung", [(5, 8), (16, 16), (19, 32)], ids=["a-rung-not-filled", "a-rung-filled", "over-two-windows"])
def test_the_half_way_prefill_gives_the_whole_stacks_last_row(system, n, rung):
    """The prefill program runs the second half of the stack on the last real
    row alone; every layer over every row of the rung gives that row the same
    logits, and the counters say ``n`` real rows of ``rung`` for the first half
    (the engine's) and ONE for the second (the model's)."""
    cfg, params, cache, engine = system
    prompt = tokens(40 + n, n)
    before = engine.trace_counters()
    cache.reset()
    slot = cache.alloc(n, 1)
    got = np.asarray(engine.prefill(prompt, slot))
    cache.reset()
    after = engine.trace_counters()
    ran = {k: after[k] - before[k] for k in ("prefill_tokens_real", "prefill_tokens_padded", "prefill_rows_cross")}
    assert ran == {"prefill_tokens_real": n, "prefill_tokens_padded": rung, "prefill_rows_cross": 1}
    want = _whole_stack(cfg, params, prompt + [0] * (rung - n), n)
    assert rel(got, want) < TIGHT


# ------------------------------------------------------------------ the cache
def test_an_idle_row_leaves_its_slots_rings_and_states_bit_for_bit(system):
    """Two slots decode for three steps beside a third that holds no request:
    the idle slot's rows of the rings, the states and the tails are the same
    bytes after the steps as before them, though the kernels read and write
    every slot's."""
    cfg, params, cache, engine = system
    cache.reset()
    a, b = cache.alloc(11, 8), cache.alloc(6, 8)
    engine.prefill(tokens(1, 11), a)
    cache.commit_prefill(a, 11)
    engine.prefill(tokens(2, 6), b)
    cache.commit_prefill(b, 6)
    free = [s for s in range(SLOTS) if s not in (a, b)][0]
    held = lambda: {name: np.asarray(arr[:, free]) for name, arr in cache.arrays().items() if name not in ("k", "v")}
    before = held()
    for tok in tokens(3, 3):
        decode_one(engine, cache, {a: tok, b: tok + 1})
    after = held()
    assert set(before) == {"ring_k", "ring_v", "ssm", "conv"}
    for name in before:
        assert np.array_equal(before[name], after[name]), name
    cache.reset()


def test_the_cache_is_one_pool_layer_with_rings_states_and_tails(system):
    cfg, params, cache, engine = system
    kc = cache.config
    hd = cfg.head_dim
    assert (kc.layers, kc.kv_heads, kc.head_dim, kc.folded) == (1, cfg.num_key_value_heads // 2, 2 * hd, True)
    row = cfg.num_key_value_heads * hd
    assert [(name, layers, tuple(shape)) for name, layers, shape, _dt in kc.slot_state] == [
        ("ring_k", 2, (W, 1, row)), ("ring_v", 2, (W, 1, row)), ("ssm", 3, (16, 2 * cfg.hidden_size)),
        ("conv", 3, (3, 2 * cfg.hidden_size))]
    assert cache.k.data.shape == (1, SLOTS * PAGES + 1, PAGE, 1, row) and cache.state["ssm"].dtype == jnp.float32
    assert (cfg.self_periods, cfg.split, cfg.cross_periods, cfg.pool_readers) == (2, 4, 2, 3)
    published = pf.Phi4FlashConfig()
    assert (published.self_periods, published.split, published.cross_periods, published.pool_readers) == (8, 16, 7, 8)
    assert (published.head_dim, published.d_inner, published.pair_heads) == (64, 5120, 10)
    with pytest.raises(SlotStateUnsupported):
        PrefixCache(cache)
    with pytest.raises(SlotStateUnsupported):
        engine.decode_multi(np.zeros((SLOTS, 2), np.int32))


def test_the_counters_count_every_reader_of_the_pool(system):
    cfg, params, cache, engine = system
    cache.reset()
    slot = cache.alloc(10, 4)
    engine.prefill(tokens(9, 10), slot)
    cache.commit_prefill(slot, 10)
    before = engine.trace_counters()
    np.asarray(decode_one(engine, cache, {slot: 5}))
    after = engine.trace_counters()
    cache.reset()
    d = {k: after[k] - before[k] for k in after}
    position = 2 * cfg.num_key_value_heads * cfg.head_dim * 4                 # K and V of a position, float32 here
    reach = 11 + (SLOTS - 1)                                                  # the slot's 11, an idle slot's one
    assert cfg.pool_readers == 3 and d["shared_pool_bytes_read"] == 3 * reach * position
    windowed = min(11, W) + (SLOTS - 1)
    assert d["ring_positions_read"] == 2 * windowed and d["ring_positions_unwindowed"] == 2 * reach
    assert d["ring_bytes_rw"] == (2 * windowed + 2 * SLOTS) * position
    assert d["ssm_state_bytes_rw"] == 2 * SLOTS * 3 * (16 * 128 * 4 + 3 * 128 * 4)
    if engine.kernel_decode:     # the engine counts ONE reading where the kernel reads the pool; the model the other two
        pages = -(-11 // PAGE) + (SLOTS - 1)
        assert d["decode_pages_read"] == 3 * pages and d["decode_pages_capacity"] == 3 * SLOTS * PAGES
    else:
        assert d["decode_pages_read"] == d["decode_pages_capacity"] == 0


def test_the_config_refuses_what_the_block_is_not():
    with pytest.raises(SpecError, match="mb_per_layer"):
        FAMILY.program_config(dict(TOY, mb_per_layer=1))
    with pytest.raises(SpecError, match="assumed"):
        FAMILY.program_config(dict(TOY, assumed=dict(TOY["assumed"], memory_from="mixer_output")))
    with pytest.raises(SpecError, match="self_decoder_layers"):
        FAMILY.program_config(dict(TOY, assumed=dict(TOY["assumed"], self_decoder_layers=6)))
    with pytest.raises(ValueError, match="twos"):
        toy_config(num_attention_heads=7)
    with pytest.raises(ValueError, match="even depth"):
        toy_config(num_hidden_layers=9)
    with pytest.raises(ValueError, match="whole pages"):
        hybrid_cache_config(toy_config(), num_slots=2, page_size=3, pages_per_slot=4)


def test_layernorm_is_the_plain_formula():
    from vescale_tpu.models.blocks import layernorm

    rng = np.random.default_rng(0)
    x, w, b = rng.normal(size=(5, 32)) * 3 + 1, rng.normal(size=32), rng.normal(size=32)
    want = (x - x.mean(-1, keepdims=True)) / np.sqrt(x.var(-1, keepdims=True) + 1e-5) * w + b
    assert rel(layernorm(jnp.asarray(x, jnp.float32), jnp.asarray(w, jnp.float32), jnp.asarray(b, jnp.float32), 1e-5), want) < 1e-5
