"""MiMo-V2's layers on the serve path (``models/mimo_v2.py``: keys of 192 beside
values of 128, a sink in every window layer's softmax, folded pages on 4 key
heads beside folded rings on 8, sigmoid-routed experts under a selection bias;
``kernels.paged_decode_folded``; the flash forward with narrower values and a
sink; ``moe.dropless.route_sigmoid_topk(bias=)``; ``serve/hybrid_engine.py`` over
a cache of folded pages and rings) at a small size on the CPU, against the plain
float32 reference of ``benchmark/families/mimo_v2.py`` (which imports nothing of
the program)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.spec import SpecError, load_family
from vescale_tpu.mesh import DeviceMesh
from vescale_tpu.models import blocks
from vescale_tpu.models import mimo_v2 as mm
from vescale_tpu.serve import (ContinuousBatchingScheduler, HybridServeEngine, PagedKVCache, PrefixCache, Request,
                               SlotStateUnsupported, run_serve_resilient)
from vescale_tpu.serve.hybrid_engine import hybrid_cache_config
from vescale_tpu.serve.kv_cache import KVCacheConfig

FAMILY = load_family("mimo_v2")
# hidden 64, 8 query heads with keys of 24 (8 of them rotated: int(24 x 0.334)) and values of 16, on 2 key heads (full)
# and 4 (window), window 8, 16 experts top-4 of width 32 of which this tree holds 4 (one of four shares), the dense
# layer and one whole period after it
TOY = {"model": "mimo_v2", "model_type": "mimo_v2", "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 7,
       "num_attention_heads": 8, "swa_num_attention_heads": 8, "num_key_value_heads": 2, "swa_num_key_value_heads": 4,
       "head_dim": 24, "swa_head_dim": 24, "v_head_dim": 16, "swa_v_head_dim": 16, "intermediate_size": 96,
       "moe_intermediate_size": 32, "n_routed_experts": 4, "num_experts_per_tok": 4, "sliding_window": 8,
       "sliding_window_size": 8, "partial_rotary_factor": 0.334, "rope_theta": 10000000, "swa_rope_theta": 10000,
       "attention_value_scale": 0.707, "layernorm_epsilon": 1e-5, "hybrid_layer_pattern": [0, 1, 1, 1, 1, 0, 1],
       "moe_layer_freq": [0, 1, 1, 1, 1, 1, 1], "attention_bias": False, "tie_word_embeddings": False, "hidden_act": "silu",
       "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
       "n_shared_experts": None, "routed_scaling_factor": None, "add_swa_attention_sink_bias": True,
       "add_full_attention_sink_bias": False, "rope_scaling": {"rope_type": "default", "type": "default"},
       "attention_chunk_size": 8, "attention_projection_layout": "fused_qkv", "published": {"n_routed_experts": 16},
       "assumed": {"rotated_entries": "first_half_split", "score_scale": "head_dim**-0.5", "qk_norm": False,
                   "output_gate": False, "attention_chunk_size": "no_term", "routed_scaling_factor_null": 1.0}}
SLOTS, PAGE, PAGES, POOL = 3, 4, 16, 30     # 64 positions a slot (rungs 8, 16, 32, 64); a pool of 29 pages where 48 would be whole
W = TOY["sliding_window"]
# float32 program against float32 reference: both round at 6e-8 an operation and sum in other orders.  The sound
# program reads 4.5e-7 to 7.6e-7 here, with the XLA legs and with the kernels interpreted; the faults read 2e-2 (one
# kept expert fewer, no selection bias) to 0.63 (the whole head rotated).
TIGHT = 1e-5


def toy_config(**changes):
    """The program's config of TOY, computing in float32 so that it can be held tightly to the reference."""
    return dataclasses.replace(FAMILY.program_config(TOY, prefill_chunk=8), dtype=jnp.float32, **changes)


def build(cfg, params=None, cache=None):
    mesh = DeviceMesh(("tp",), (1,), devices=jax.devices()[:1])
    if params is None:
        params = jax.jit(lambda k: mm.init_params(cfg, k))(jax.random.key(7))
    if cache is None:
        cache = PagedKVCache(hybrid_cache_config(cfg, num_slots=SLOTS, page_size=PAGE, pages_per_slot=PAGES, num_pages=POOL), mesh)
    return params, cache, HybridServeEngine(cfg, mesh, params, cache)


@pytest.fixture(scope="module", params=["xla_legs", "kernels_interpreted"])
def system(request):
    """The toy engine, twice: with the XLA legs the CPU takes, and with the
    Pallas kernels a TPU would compile (``paged_decode_folded`` over the pages at
    2 key heads and over the rings at 4 with the sinks, the windowed forward with
    a sink and the causal one at 24 | 16, and, with both of the expert layer's
    limits turned to 0 while the programs are traced, the grouped SwiGLU kernel)
    run through the interpreter."""
    from vescale_tpu.moe import dropless

    with pytest.MonkeyPatch.context() as patch:
        if request.param == "kernels_interpreted":
            patch.setattr(dropless, "DENSE_MAX_TOKENS", 0)
            patch.setattr(dropless, "PADDED_MAX_MEAN_ROWS", 0)
            patch.setenv("VESCALE_KERNELS", "interpret")
        cfg = toy_config()
        params, cache, engine = build(cfg)
        engine.warm()                               # every program is traced here
    assert engine.kernel_decode == (request.param == "kernels_interpreted")
    return cfg, params, cache, engine


@pytest.fixture(scope="module")
def xla_system():
    cfg = toy_config()
    params, cache, engine = build(cfg)
    return cfg, params, cache, engine.warm()


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


# ------------------------------------------------- the folded decode kernel
def _dense_decode(q, k_pool, v_pool, table, lengths, layer, scale, sink):
    """A loop over slots and heads in float64: the sink as one more column that is dropped after the softmax."""
    S, H, dk = q.shape
    kv = k_pool.shape[-1] // dk
    dv = v_pool.shape[-1] // kv
    keys = np.asarray(k_pool[layer].astype(jnp.float32), np.float64)[np.asarray(table)].reshape(S, -1, kv, dk)
    values = np.asarray(v_pool[layer].astype(jnp.float32), np.float64)[np.asarray(table)].reshape(S, -1, kv, dv)
    out = np.zeros((S, H, dv))
    for s in range(S):
        n = int(lengths[s])
        for h in range(H):
            g = h // (H // kv)
            scores = (np.asarray(q[s, h].astype(jnp.float32), np.float64) @ keys[s, :n, g].T) * scale
            if sink is not None:
                scores = np.concatenate([scores, [float(sink[h])]])
            if scores.size:
                p = np.exp(scores - scores.max())
                out[s, h] = (p / p.sum())[:n] @ values[s, :n, g]
    return out


@pytest.mark.parametrize("sink", [True, False], ids=["sink", "no-sink"])
@pytest.mark.parametrize("dtype,kv,page,pages", [(jnp.float32, 2, 4, 6), (jnp.float32, 4, 2, 12), (jnp.bfloat16, 2, 4, 6)],
                         ids=["f32-kv2", "f32-kv4-three-blocks", "bf16-kv2"])
def test_the_folded_decode_kernel_is_its_xla_leg_and_the_dense_softmax(dtype, kv, page, pages, sink):
    """``paged_decode_folded`` interpreted against its XLA leg and a float64 loop,
    keys of 24 beside values of 16, slots of length 0 (zeros, with a sink or
    without), 1, a part of a page, and every page."""
    from vescale_tpu.kernels.paged_attention import _block_pages, paged_decode_folded

    S, H, dk, dv, L, N = 5, 8, 24, 16, 2, 80
    ks = jax.random.split(jax.random.key(kv + page), 5)
    q = jax.random.normal(ks[0], (S, H, dk), jnp.float32).astype(dtype)
    k_pool = jax.random.normal(ks[1], (L, N, page, 1, kv * dk), jnp.float32).astype(dtype)
    v_pool = jax.random.normal(ks[2], (L, N, page, 1, kv * dv), jnp.float32).astype(dtype)
    v_pool = v_pool.at[:, 0].set(jnp.nan)                       # the null page's bytes reach nothing
    table = 1 + jax.random.permutation(ks[3], N - 1)[:S * pages].reshape(S, pages).astype(jnp.int32)
    lengths = jnp.asarray([0, 1, 7, page * pages, 13], jnp.int32)
    table = table.at[2, -(-7 // page):].set(0)                  # pages past a slot's length name the null page
    logit = 2.0 * jax.random.normal(ks[4], (H,), jnp.float32) if sink else None
    kw = dict(layer=1, scale=dk ** -0.5, sink=logit)
    kernel = paged_decode_folded(q, k_pool, v_pool, table, lengths, interpret=True, **kw)
    xla = paged_decode_folded(q, k_pool, v_pool, table, lengths, interpret=None, **kw)
    want = _dense_decode(q, k_pool, v_pool, table, lengths, 1, dk ** -0.5, logit)
    assert kernel.shape == xla.shape == (S, H, dv) and kernel.dtype == jnp.float32
    loose = 2e-2 if dtype == jnp.bfloat16 else 2e-6              # (bf16: the probabilities are rounded for the second product)
    assert rel(kernel, want) < loose and rel(xla, want) < loose and rel(kernel, xla) < loose
    assert not np.asarray(kernel[0]).any() and not np.asarray(xla[0]).any(), "a slot that holds nothing reads zeros, not NaN"
    if page == 2:
        assert _block_pages(pages, page, kv, dk, 4) == 12, "(one block here; the slot of every page still spans all of it)"


def test_the_folded_kernel_refuses_what_it_cannot_read_and_says_what_it_supports():
    from vescale_tpu.kernels.paged_attention import paged_decode_folded, supports_folded

    q = jnp.zeros((2, 8, 24), jnp.float32)
    pools = jnp.zeros((1, 4, 4, 1, 48), jnp.float32), jnp.zeros((1, 4, 4, 1, 32), jnp.float32)
    args = (jnp.zeros((2, 2), jnp.int32), jnp.ones((2,), jnp.int32))
    with pytest.raises(ValueError, match="folded pools"):
        paged_decode_folded(q, pools[0][..., :40], pools[1], *args, layer=0, scale=1.0, interpret=True)
    with pytest.raises(ValueError, match="folded pools"):
        paged_decode_folded(q.astype(jnp.bfloat16), *pools, *args, layer=0, scale=1.0, interpret=True)
    # compiled: whole lane tiles of folded keys and of a head's values, whole sublane tiles a page
    assert supports_folded(jnp.bfloat16, 4, 192, 128, 32, interpret=False) and supports_folded(jnp.bfloat16, 8, 192, 128, 32, interpret=False)
    assert not supports_folded(jnp.bfloat16, 4, 192, 128, 8, interpret=False) and not supports_folded(jnp.bfloat16, 1, 192, 128, 32, interpret=False)
    assert not supports_folded(jnp.bfloat16, 4, 192, 96, 32, interpret=False) and supports_folded(jnp.float32, 2, 24, 16, 4, interpret=True)
    assert not supports_folded(jnp.float16, 4, 192, 128, 32, interpret=True)


# ---------------------------------------- the forward with narrower values and a sink
def _dense_forward(q, k, v, window, sink):
    B, T, H, D = q.shape
    k, v = (jnp.repeat(a, H // k.shape[2], axis=2) for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    i = jnp.arange(T)
    keep = i[:, None] >= i[None, :]
    if window is not None:
        keep = keep & (i[:, None] - i[None, :] < window)
    s = jnp.where(keep, s, -jnp.inf)
    if sink is not None:
        s = jnp.concatenate([s, jnp.broadcast_to(sink[None, :, None, None], (B, H, T, 1))], axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1)[..., :T], v)


@pytest.mark.parametrize("sink", [True, False], ids=["sink", "no-sink"])
@pytest.mark.parametrize("T,window,block", [(64, 16, 16), (64, None, 16), (128, 33, 32), (32, 8, 8), (256, 128, 512)],
                         ids=["T=4W", "causal", "W-no-multiple-of-a-block", "T=4W-small", "window-128-caps-the-tiles"])
def test_the_forward_with_values_narrower_than_keys_and_a_sink_is_the_dense_softmax(T, window, block, sink):
    """The interpreted kernel (resident and streaming form) and the XLA leg at 8
    query heads on 2 with keys of 24 and values of 16, against a dense softmax
    with the sink as a dropped column."""
    from vescale_tpu.ops.flash_attention import _flash_fwd_pallas, _from3, _to3, flash_attention

    ks = jax.random.split(jax.random.key(T), 4)
    q, k = (jax.random.normal(kk, (1, T, h, 24), jnp.float32) for kk, h in zip(ks, (8, 2)))
    v = jax.random.normal(ks[2], (1, T, 2, 16), jnp.float32)
    logit = 2.0 * jax.random.normal(ks[3], (8,), jnp.float32) if sink else None
    want = _dense_forward(q, k, v, window, logit)
    got = flash_attention(q, k, v, window=window, sink=logit, block_q=block, block_k=block, interpret=True)
    assert got.shape == (1, T, 8, 16) and rel(got, want) < 2e-6
    assert rel(flash_attention(q, k, v, window=window, sink=logit), want) < 2e-6                  # the XLA leg
    tile = min(block, T, 128)
    masks = {} if window is None else {"window": window}
    streamed = _flash_fwd_pallas(_to3(q), _to3(k), _to3(v), 24 ** -0.5, True, tile, tile, True, 8, 2, streaming=True, sink=logit,
                                 **masks)[0]
    assert rel(_from3(streamed, 1, 8), want) < 2e-6


def test_a_sink_and_narrower_values_are_forward_only_causal_and_named():
    from vescale_tpu.kernels.flash_attention import CAUSAL_NAME, WINDOW_NAME
    from vescale_tpu.ops.flash_attention import flash_attention

    q, v = jnp.ones((1, 16, 2, 24), jnp.float32), jnp.ones((1, 16, 2, 16), jnp.float32)
    sink = jnp.zeros((2,), jnp.float32)
    with pytest.raises(NotImplementedError, match=r"sink=\.\.\.\) and values narrower than the keys"):
        jax.grad(lambda q: flash_attention(q, q, q, sink=sink).sum())(q)
    with pytest.raises(NotImplementedError, match="forward only"):
        jax.grad(lambda q: flash_attention(q, q, v, window=4).sum())(q)
    with pytest.raises(ValueError, match="causal mask"):
        flash_attention(q, q, v, causal=False)
    with pytest.raises(ValueError, match="causal mask"):
        flash_attention(q, q, q, sink=sink, mask_block=4)
    with pytest.raises(ValueError, match="one logit a query head"):
        flash_attention(q, q, q, sink=jnp.zeros((3,), jnp.float32))
    text = lambda **kw: str(jax.make_jaxpr(lambda q, v: flash_attention(q, q, v, interpret=True, **kw))(q, v))
    assert CAUSAL_NAME in text() and WINDOW_NAME in text(window=4) and CAUSAL_NAME not in text(window=4)
    # the windowed forward's tiles are no larger than the window rounded up to whole 128s; a window of 512 keeps its 512
    big = jnp.ones((1, 1024, 2, 24), jnp.float32)
    grid = lambda window: str(jax.make_jaxpr(lambda q: flash_attention(q, q, q, window=window, interpret=True))(big))
    assert "grid=(2, 8)" in grid(128) and "grid=(2, 2)" in grid(512)


# ------------------------------------------------------------------ the router
def test_a_selection_bias_chooses_and_does_not_weigh():
    from vescale_tpu.moe.dropless import route_sigmoid_topk

    scores = np.asarray(jax.random.normal(jax.random.key(0), (300, 16), jnp.float32)) * 2.0
    bias = np.asarray(jax.random.normal(jax.random.key(1), (16,), jnp.float32)) * 0.05
    idx, gates = route_sigmoid_topk(jnp.asarray(scores), 4, bias=jnp.asarray(bias))
    p = 1.0 / (1.0 + np.exp(-scores.astype(np.float64)))
    order = np.argsort(-(p + bias), axis=-1)[:, :4]
    assert np.array_equal(np.sort(np.asarray(idx), -1), np.sort(order, -1)) and idx.dtype == jnp.int32
    kept = np.take_along_axis(p, np.asarray(idx), axis=-1)
    assert np.allclose(np.asarray(gates), kept / kept.sum(-1, keepdims=True), rtol=1e-5), "the sigmoids, not the biased scores"
    plain, plain_gates = route_sigmoid_topk(jnp.asarray(scores), 4)
    assert (np.sort(np.asarray(plain), -1) != np.sort(np.asarray(idx), -1)).any(axis=-1).mean() > 0.2
    # a bias of zeros is the program without one
    same, same_gates = route_sigmoid_topk(jnp.asarray(scores), 4, bias=jnp.zeros((16,), jnp.float32))
    assert np.array_equal(np.sort(np.asarray(same), -1), np.sort(np.asarray(plain), -1))
    assert np.allclose(np.sort(np.asarray(same_gates), -1), np.sort(np.asarray(plain_gates), -1), rtol=1e-6)


# ------------------------------------------------------------ the ring's rows
@pytest.mark.parametrize("length,rung", [(3, 8), (8, 8), (9, 16), (16, 16), (21, 32), (27, 32)])
def test_a_prefill_leaves_the_newest_real_positions_on_their_rows(length, rung):
    source = np.asarray(mm.ring_source(length, rung, W))
    for r in range(W):
        live = [p for p in range(length) if p % W == r]
        if live:
            assert source[r] == max(live) and max(live) >= length - W, "the newest real position of that row"
        assert 0 <= source[r] < length, "never a pad position"
    assert np.array_equal(np.asarray(mm.ring_row(jnp.arange(20), W)), np.arange(20) % W)


# ------------------------------------------------------- through the cache
@pytest.mark.parametrize("length,steps", [(5, 20), (8, 20), (13, 20), (27, 6)],
                         ids=["shorter-than-the-window", "the-window", "longer", "three-windows"])
def test_prefill_then_decode_through_rings_and_pages_is_the_references_full_forward(system, length, steps):
    """``check_window`` at the toy's lengths: prompts shorter than, equal to and
    longer than the window, on rungs they do not fill (8 fills its own), and
    enough steps that the ring wraps twice (20 steps over 8 rows)."""
    _cfg, _params, _cache, engine = system
    got = FAMILY.check_window(engine, TOY, 3, length, steps)
    assert got["logits_max_abs_diff_over_max"] < TIGHT and got["argmax_agreement"] == 1.0
    assert got["ok"] and got["tolerance"] == FAMILY.SERVE_LOGITS_TOLERANCE and got["decode_steps"] == steps


def test_two_slots_of_different_lengths_interleaved(system):
    _cfg, params, cache, engine = system
    cache.reset()
    a, b = tokens(1, 13), tokens(2, 27)              # rungs 16 and 32, neither filled
    more_a, more_b = tokens(3, 12), tokens(4, 10)
    sa = cache.alloc(len(a), 13)
    rows_a = [engine.prefill(a, sa)]
    cache.commit_prefill(sa, len(a))
    rows_a += [decode_one(engine, cache, {sa: t})[sa] for t in more_a[:2]]
    sb = cache.alloc(len(b), 11)                     # b arrives while a decodes
    rows_b = [engine.prefill(b, sb)]
    cache.commit_prefill(sb, len(b))
    for i in range(10):
        out = decode_one(engine, cache, {sa: more_a[2 + i], sb: more_b[i]})
        rows_a.append(out[sa])
        rows_b.append(out[sb])
    assert rel(np.stack(rows_a), FAMILY.logits(params, TOY, a + more_a, range(len(a) - 1, len(a) + 12))) < TIGHT
    assert rel(np.stack(rows_b), FAMILY.logits(params, TOY, b + more_b, range(len(b) - 1, len(b) + 10))) < TIGHT
    cache.reset()


def test_the_cache_keeps_folded_pages_for_the_full_layers_and_a_folded_ring_a_slot_for_the_window_ones(system):
    cfg, _params, cache, engine = system
    assert cache.k.data.shape == (2, POOL, PAGE, 1, 2 * 24) and cache.v.data.shape == (2, POOL, PAGE, 1, 2 * 16)
    assert cache.state["ring_k"].shape == (5, SLOTS, W, 1, 4 * 24) and cache.state["ring_v"].shape == (5, SLOTS, W, 1, 4 * 16)
    assert cache.state_bytes_per_slot() == 5 * W * 4 * (24 + 16) * 4
    kc = cache.config
    assert (kc.layers, kc.kv_heads, kc.head_dim, kc.v_head_dim, kc.folded) == (2, 2, 24, 16, True)
    assert kc.pool_row() == (1, 48) and kc.pool_row(values=True) == (1, 32)
    assert engine.buckets == [8, 16, 32, 64] and cfg.layers_of(mm.FULL) == (0, 5) and cfg.layers_of(mm.SWA) == (1, 2, 3, 4, 6)
    # admission counts pages (the full layers') alone: 29 usable pages hold one request of 64 positions and one of 52
    cache.reset()
    assert cache.can_admit(50, 14) and cache.alloc(50, 14) == 0
    assert not cache.can_admit(50, 14) and cache.can_admit(40, 12)
    cache.reset()
    with pytest.raises(ValueError, match="whole pages"):
        mm.cache_config(cfg, num_slots=2, page_size=3, pages_per_slot=4)


def test_a_cache_config_of_two_widths_or_folded_rows_says_its_pools_rows():
    plain = KVCacheConfig(layers=1, kv_heads=4, head_dim=192)
    assert plain.pool_row() == plain.pool_row(values=True) == (4, 192) and plain.v_head_dim is None and not plain.folded
    two = KVCacheConfig(layers=1, kv_heads=4, head_dim=192, v_head_dim=128)
    assert two.pool_row() == (4, 192) and two.pool_row(values=True) == (4, 128)
    folded = KVCacheConfig(layers=1, kv_heads=4, head_dim=192, v_head_dim=128, folded=True)
    assert folded.pool_row() == (1, 768) and folded.pool_row(values=True) == (1, 512)
    with pytest.raises(ValueError, match="latent cache has no value pool"):
        KVCacheConfig(layers=1, kv_heads=1, head_dim=640, latent=True, v_head_dim=128)
    with pytest.raises(ValueError, match="v_head_dim must be positive"):
        KVCacheConfig(layers=1, kv_heads=4, head_dim=192, v_head_dim=0)
    mesh = DeviceMesh(("tp",), (1,), devices=jax.devices()[:1])
    cache = PagedKVCache(dataclasses.replace(two, num_slots=2, page_size=4, pages_per_slot=2), mesh)
    assert cache.k.data.shape == (1, 5, 4, 4, 192) and cache.v.data.shape == (1, 5, 4, 4, 128)
    cache.update(cache.k.data, cache.v.data)
    assert cache.v.data.shape == (1, 5, 4, 4, 128)


def test_the_counters_count_pages_rings_windows_and_rows_routed_nowhere(system):
    cfg, _params, cache, engine = system
    cache.reset()
    before = engine.trace_counters()
    slots = {}
    for n in (5, 13):
        s = cache.alloc(n, 4)
        engine.prefill(tokens(20 + n, n), s)
        cache.commit_prefill(s, n)
        slots[s] = n
    np.asarray(decode_one(engine, cache, {s: 1 for s in slots}))
    d = {k: v - before[k] for k, v in engine.trace_counters().items()}
    assert d["decode_steps"] == 1 and d["prefill_bucket_tokens"] == 8 + 16
    # two full layers and five window layers; slots of 5 and 13 positions and one that holds nothing (its one)
    assert d["page_positions_read"] == 2 * (6 + 14 + 1) and d["page_bytes_read"] == d["page_positions_read"] * 2 * (24 + 16) * 4
    assert d["ring_positions_read"] == 5 * (6 + 8 + 1)
    assert d["ring_bytes_rw"] == (d["ring_positions_read"] + 5 * SLOTS) * 4 * (24 + 16) * 4
    pairs = lambda T: sum(min(i + 1, W) for i in range(T))
    assert mm.window_pairs(8, W) == pairs(8) and mm.window_pairs(16, W) == pairs(16) and mm.window_pairs(5, W) == 15
    assert d["prefill_window_attn_flops"] == 2 * (24 + 16) * 8 * 5 * (pairs(8) + pairs(16))
    assert d["prefill_full_attn_flops"] == 2 * (24 + 16) * 8 * 2 * (8 * 9 // 2 + 16 * 17 // 2)
    assert d["moe_layer_steps"] == 6 and d["moe_expert_slots"] == 6 * 4 and d["moe_assignments"] == 2 * 4 * 6
    assert d["moe_assignments_held"] < d["moe_assignments"], "a quarter of the experts is held"
    assert 0 <= d["rows_routed_nowhere"] <= 2 * 6, "of the two active rows of six expert layers; the idle slot routes nowhere and is not one"
    assert d["moe_expert_layer_calls"] == 3 * 6 and d["moe_grouped_layer_calls"] == (3 * 6 if engine.kernel_decode else 0)
    assert d["decode_pages_read"] == (2 + 4 + 1 if engine.kernel_decode else 0)     # ONE full layer's pages
    for name in mm.STEP_COUNTERS:
        assert name in d
    assert mm.prefill_counters(cfg, 16)["prefill_window_attn_flops"] == FAMILY.prefill_attention_flops(TOY, 16, FAMILY.SWA)
    assert mm.prefill_counters(cfg, 16)["prefill_full_attn_flops"] == FAMILY.prefill_attention_flops(TOY, 16, FAMILY.FULL)
    cache.reset()


def test_the_normal_path_serves_it_and_a_replay_through_the_cache_gives_the_same_tokens(system):
    """``ContinuousBatchingScheduler`` + ``run_serve_resilient`` over more
    requests than slots, short and long prompts in one queue: every request
    completes, and its tokens are those of a greedy replay alone on the cache."""
    _cfg, _params, cache, engine = system
    cache.reset()
    sched = ContinuousBatchingScheduler(cache)
    prompts = {rid: tokens(40 + rid, n) for rid, n in enumerate((5, 29, 9, 17, 6, 40))}
    arrivals = [Request(rid=rid, prompt=tuple(p), max_new_tokens=12) for rid, p in prompts.items()]
    run_serve_resilient(engine=engine, scheduler=sched, arrivals=[(0, r) for r in arrivals],
                        install_signal_handlers=False, coordinate=False)
    sched.ledger_check()
    assert sched.counts["completed"] == len(prompts)
    for rid, p in prompts.items():
        assert list(sched.outcomes[rid]["tokens"]) == engine.replay_greedy(p, 12)
    cache.reset()


def test_prefix_sharing_speculation_and_rollback_are_refused_on_a_cache_with_rings(system):
    """A ring keeps no history: what needs a slot's state at an earlier position is refused by name."""
    _cfg, _params, cache, engine = system
    with pytest.raises(SlotStateUnsupported, match="ring_k, ring_v"):
        engine.decode_multi(np.zeros((SLOTS, 2), np.int32))
    with pytest.raises(SlotStateUnsupported):
        engine.prefill_suffix(tokens(1, 9), 0, 4)
    with pytest.raises(SlotStateUnsupported):
        cache.rollback(0, 0)
    with pytest.raises(SlotStateUnsupported):
        cache.alloc_shared([1], 9, 2)
    with pytest.raises(SlotStateUnsupported):
        PrefixCache(cache)


# ---------------------------------------------------------------- the faults
@pytest.mark.parametrize("fault", list(FAMILY.FAULTS))
def test_each_fault_of_the_reference_fails_check_window_at_its_tolerance(xla_system, fault):
    """The program against the reference WITH the fault: what a program with
    that fault would read against the sound reference.  At the toy's widths one
    kept expert of four fewer shows too (at the published widths it is one of
    eight on a sixteenth of the experts, and cannot be told from rounding)."""
    _cfg, _params, _cache, engine = xla_system
    got = FAMILY.check_window(engine, TOY, 5, 13, 20, wrong=fault)
    assert not got["ok"] and got["logits_max_abs_diff_over_max"] > 1.3 * FAMILY.SERVE_LOGITS_TOLERANCE > 1000 * TIGHT


@pytest.mark.parametrize("fault", ["window_plus_1", "no_sink", "key_of_128", "no_selection_bias"])
def test_the_smallest_faults_fail_with_the_kernels_interpreted_too(system, fault):
    _cfg, _params, _cache, engine = system
    assert not FAMILY.check_window(engine, TOY, 5, 13, 20, wrong=fault)["ok"]


RING_FAULTS = {
    # row p for p < window, then stuck on the last row: the prefill keeps the OLDEST window positions
    "ring_not_wrapped": {"ring_row": lambda positions, window: jnp.minimum(positions, window - 1),
                         "ring_source": lambda length, rung, window: jnp.arange(window, dtype=jnp.int32)},
    # the rung's last window positions, pad and all, in place of the prompt's
    "pads_in_the_ring": {"ring_source": lambda length, rung, window, real=mm.ring_source: real(rung, rung, window)},
}


@pytest.mark.parametrize("fault", list(RING_FAULTS))
def test_each_fault_of_the_ring_fails_check_window_at_its_tolerance(fault):
    """The program WITH the fault (its ring's placement patched while its
    programs are traced) against the sound reference, over the sound engine's
    cache geometry; the same prompt reads sound before."""
    cfg = toy_config()
    params, cache, engine = build(cfg)
    assert FAMILY.check_window(engine, TOY, 5, 13, 20)["logits_max_abs_diff_over_max"] < TIGHT
    with pytest.MonkeyPatch.context() as patch:
        for name, wrong in RING_FAULTS[fault].items():
            patch.setattr(mm, name, wrong)
        _params, _cache, faulty = build(cfg, params, cache)          # the same cache: new programs, traced under the fault
        got = FAMILY.check_window(faulty, TOY, 5, 13, 20)
    assert not got["ok"] and got["logits_max_abs_diff_over_max"] > 2 * FAMILY.SERVE_LOGITS_TOLERANCE


# ----------------------------------------------------------------- the family
def test_the_init_rule_gives_a_sink_a_visible_share_of_a_windows_mass_and_a_bias_that_changes_the_kept_sets():
    """The two bands of the init rule, at the toy's widths: over the rows of a
    window layer that see a full window, a sink holds between a tenth and a half
    of the softmax's mass (on the mean over heads and rows; "no sink" is then
    no rounding error), and the selection bias changes the kept set of at least
    a fifth of the tokens (or "no selection bias" would be rounding too)."""
    from vescale_tpu.moe.dropless import route_sigmoid_topk

    cfg = toy_config()
    params = mm.init_params(cfg, jax.random.key(3))
    rms = lambda a: float(jnp.sqrt(jnp.mean(jnp.square(a))))
    T = 48
    x = mm.embed(cfg, params, jnp.asarray(tokens(9, T)))
    assert 0.7 < rms(x) < 1.4
    x, _k, _v = mm.layer_prefill(cfg, params["layers_0"], 0, x, jnp.ones((T,), bool))
    lp = params["layers_1"]
    u = blocks.rmsnorm(x, lp["input_layernorm"]["weight"], cfg.rms_norm_eps)
    q, k, v = mm._qkv(cfg, lp["self_attn"], u, jnp.arange(T), mm.SWA)
    scores = jnp.einsum("qhd,khd->hqk", q, jnp.repeat(k, 2, axis=1)) / 24 ** 0.5          # (8 query heads on 4 key heads)
    assert 1.5 < float(jnp.std(scores)) < 2.6, "a deviation of about 2, not the flat softmax of variance 1 / fan-in"
    i = jnp.arange(T)
    keep = (i[:, None] >= i[None, :]) & (i[:, None] - i[None, :] < W)
    sink = lp["self_attn"]["sink"]
    assert sink.shape == (8,) and sink.dtype == jnp.float32 and "sink" not in params["layers_0"]["self_attn"]
    with_sink = jnp.concatenate([jnp.where(keep[None], scores, -jnp.inf), jnp.broadcast_to(sink[:, None, None], (8, T, 1))], axis=-1)
    share = float(jnp.mean(jax.nn.softmax(with_sink, axis=-1)[:, W:, -1]))
    assert 0.1 < share < 0.5, share
    middle = np.log(W) + mm.SCORE_DEVIATION ** 2 / 2 - mm.SINK_BELOW
    assert float(sink.min()) >= middle - mm.SINK_SPREAD and float(sink.max()) <= middle + mm.SINK_SPREAD
    assert float(jnp.std(v)) < 0.9 * float(jnp.std(u)), "values carry attention_value_scale"
    # the selection bias: every share's quantiles, in a seeded order; it changes what at least a fifth of the tokens keep
    ep = params["layers_1"]["mlp"]
    c = np.asarray(ep["router_bias"])
    assert c.shape == (16,) and ep["router_bias"].dtype == jnp.float32 and ep["router"].shape == (64, 16)
    shares = np.sort(c.reshape(4, 4), axis=-1)
    assert np.allclose(shares, shares[0]) and abs(c.mean()) < 1e-6 and 0.5 * mm.BIAS_DEVIATION < c.std() < 1.2 * mm.BIAS_DEVIATION
    assert not np.array_equal(c.reshape(4, 4)[0], c.reshape(4, 4)[1]) or not np.array_equal(c.reshape(4, 4)[0], c.reshape(4, 4)[2])
    h = blocks.rmsnorm(jax.random.normal(jax.random.key(5), (400, 64)), jnp.ones((64,)), 1e-5)

    def changed(ep, k):
        router_scores = jnp.dot(h, ep["router"], precision=jax.lax.Precision.HIGHEST)
        biased, _ = route_sigmoid_topk(router_scores, k, bias=ep["router_bias"])
        plain, _ = route_sigmoid_topk(router_scores, k)
        return float((np.sort(np.asarray(biased), -1) != np.sort(np.asarray(plain), -1)).any(axis=-1).mean())

    # (4 of 16 sigmoids lie further apart than 8 of 256: the rule's deviation is reckoned for the published router)
    assert changed(ep, 4) > 0.1
    published = mm.init_params(toy_config(num_experts=256, experts_held=16, num_experts_per_tok=8), jax.random.key(3))["layers_1"]["mlp"]
    assert published["router"].shape == (64, 256) and changed(published, 8) > 0.6


def test_the_two_layer_kinds_differ_in_key_heads_theta_and_sink_and_rotate_a_third_of_a_head():
    cfg = toy_config()
    assert cfg.rotated == 8 and MM_PUBLISHED.rotated == 64, "int(head_dim x 0.334): 8 of 24 here, 64 of 192 published"
    assert (cfg.kv_heads(mm.FULL), cfg.kv_heads(mm.SWA)) == (2, 4) and (cfg.theta(mm.FULL), cfg.theta(mm.SWA)) == (1e7, 1e4)
    assert cfg.has_sink(mm.SWA) and not cfg.has_sink(mm.FULL)
    x = jnp.ones((3, 2, 24), jnp.float32)
    turned = mm._turned(cfg, x, jnp.asarray([0, 1, 7]), mm.SWA)
    assert np.allclose(turned[:, :, 8:], 1.0), "two thirds of each head pass"
    assert np.allclose(turned[0], 1.0) and not np.allclose(turned[1, :, :8], 1.0)
    assert not np.allclose(mm._turned(cfg, x, jnp.asarray([0, 1, 7]), mm.FULL)[2], turned[2]), "another base a kind"
    params = jax.eval_shape(lambda k: mm.init_params(cfg, k), jax.random.key(0))
    full, swa = params["layers_0"]["self_attn"], params["layers_1"]["self_attn"]
    assert full["q_proj"].shape == swa["q_proj"].shape == (64, 8 * 24) and full["o_proj"].shape == (8 * 16, 64)
    assert full["k_proj"].shape == (64, 2 * 24) and full["v_proj"].shape == (64, 2 * 16)
    assert swa["k_proj"].shape == (64, 4 * 24) and swa["v_proj"].shape == (64, 4 * 16)
    assert "router" not in params["layers_0"]["mlp"] and params["layers_5"]["mlp"]["w_gate"].shape == (4, 64, 32)
    assert MM_PUBLISHED.hybrid_layer_pattern.count(0) == 9 and len(MM_PUBLISHED.hybrid_layer_pattern) == 48
    assert MM_PUBLISHED.layers_of(mm.FULL) == (0, 5, 11, 17, 23, 29, 35, 41, 47)


MM_PUBLISHED = mm.MimoV2Config()


def test_the_shares_add_up_to_the_uncut_expert_layer():
    """The routed parts that the four shares give (experts 0-3, 4-7, 8-11, 12-15,
    each from its own tree's held experts), summed, are the uncut reference's
    expert layer over all 16: no shared expert is counted once, and a token
    whose experts all live elsewhere gets zero from a share."""
    whole = toy_config(experts_held=16)
    ep = mm.init_params(whole, jax.random.key(11))["layers_1"]["mlp"]
    h = blocks.rmsnorm(jax.random.normal(jax.random.key(12), (40, 64)), jnp.ones((64,)), 1e-5)
    want = FAMILY.expert_layer(ep, h, k=4)
    total, nowhere, rows = jnp.zeros_like(h), [], 0
    for share in range(4):
        cfg = toy_config(first_expert_held=4 * share)
        part = {"router": ep["router"], "router_bias": ep["router_bias"],
                **{name: ep[name][4 * share: 4 * share + 4] for name in ("w_gate", "w_up", "w_down")}}
        out, counts, missed = mm.expert_layer(cfg, part, h)
        assert rel(out, FAMILY.expert_layer(part, h, k=4, first_held=4 * share)) < TIGHT
        zero_rows = np.asarray(jnp.all(out == 0.0, axis=-1))
        assert int(missed) == int(zero_rows.sum()), "a row routed nowhere here gets exactly zero"
        total, rows = total + out, rows + int(counts.sum())
        nowhere.append(int(missed))
    assert rel(total, want) < TIGHT and rows == 40 * 4, "every (token, expert) pair falls on exactly one share"
    assert 0 < sum(nowhere) < 4 * 40


def test_pad_positions_route_to_no_expert_and_a_masked_row_is_not_routed_nowhere():
    cfg = toy_config()
    ep = mm.init_params(cfg, jax.random.key(11))["layers_1"]["mlp"]
    h = blocks.rmsnorm(jax.random.normal(jax.random.key(12), (16, 64)), jnp.ones((64,)), 1e-5)
    mask = jnp.arange(16) < 9
    out, counts, missed = mm.expert_layer(cfg, ep, h, token_mask=mask)
    full, full_counts, full_missed = mm.expert_layer(cfg, ep, h)
    assert not np.asarray(out[9:]).any() and rel(out[:9], full[:9]) < TIGHT
    assert int(counts.sum()) <= int(full_counts.sum()) and int(missed) <= int(full_missed) <= 16


def test_the_family_refuses_another_block_under_this_name():
    with pytest.raises(SpecError, match="add_full_attention_sink_bias"):
        FAMILY.program_config(dict(TOY, add_full_attention_sink_bias=True))
    with pytest.raises(SpecError, match="scoring_func"):
        FAMILY.program_config(dict(TOY, scoring_func="softmax"))
    with pytest.raises(SpecError, match="n_shared_experts"):
        FAMILY.program_config(dict(TOY, n_shared_experts=1))
    with pytest.raises(SpecError, match="score_scale"):
        FAMILY.program_config(dict(TOY, assumed=dict(TOY["assumed"], score_scale="v_head_dim**-0.5")))
    with pytest.raises(SpecError, match="swa_head_dim"):
        FAMILY.program_config(dict(TOY, swa_head_dim=16))
    with pytest.raises(ValueError, match="name each of the 7 layers"):
        toy_config(hybrid_layer_pattern=(0,) * 6)
    with pytest.raises(ValueError, match="no page to admit by"):
        toy_config(hybrid_layer_pattern=(1,) * 7)
    with pytest.raises(ValueError, match="whole groups a key head"):
        toy_config(swa_num_key_value_heads=3)
    with pytest.raises(ValueError, match="wrong is one of"):
        FAMILY.logits({}, TOY, [1, 2], [0], wrong="something_else")
    with pytest.raises(ValueError, match="a share at a time"):
        mm.selection_bias(toy_config(experts_held=3, first_expert_held=0), jax.random.key(0))
