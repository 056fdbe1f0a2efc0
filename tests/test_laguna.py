"""Laguna's layers on the serve path (``models/laguna.py``: window and full
attention mixed, a ring a slot beside the pages, sigmoid-routed small experts
beside a shared one; the windowed flash forward of ``kernels/`` and ``ops/``;
``moe.dropless.route_sigmoid_topk``; ``serve/hybrid_engine.py`` over a cache of
pages and rings) at a small size on the CPU, against the plain float32 reference
of ``benchmark/families/laguna.py`` (which imports nothing of the program)."""

import dataclasses
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.spec import SpecError, load_family
from vescale_tpu.mesh import DeviceMesh
from vescale_tpu.models import blocks
from vescale_tpu.models import laguna as lg
from vescale_tpu.serve import (ContinuousBatchingScheduler, HybridServeEngine, PagedKVCache, PrefixCache, Request,
                               SlotStateUnsupported, run_serve_resilient)
from vescale_tpu.serve.hybrid_engine import hybrid_cache_config

FAMILY = load_family("laguna")
# hidden 64, 2 key heads of 16, window 8, 8 experts top-2 + shared, five layers dense / s / s / s / full with 3 and 4
# query heads a key head (6 on the full layers, 8 on the sliding ones), the published rotary parameters but for the
# original length (16: the YaRN ramp then lies inside the toy's 32 rotated pairs)
TOY = {"model": "laguna", "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 5, "num_attention_heads": 6,
       "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 96, "num_experts": 8, "num_experts_per_tok": 2,
       "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32, "attention_bias": False, "rms_norm_eps": 1e-6,
       "tie_word_embeddings": False, "gating": True, "sliding_window": 8, "moe_apply_router_weight_on_input": False,
       "moe_routed_scaling_factor": 2.5, "partial_rotary_factor": 0.5, "max_position_embeddings": 4096,
       "rope_parameters": {"full_attention": {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
                                              "original_max_position_embeddings": 16, "beta_slow": 1, "beta_fast": 64,
                                              "attention_factor": 1.4158883083359672, "partial_rotary_factor": 0.5},
                           "sliding_attention": {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1}},
       "layer_types": ["full_attention", "sliding_attention", "sliding_attention", "sliding_attention", "full_attention"],
       "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
       "num_attention_heads_per_layer": [6, 8, 8, 8, 6],
       "assumed": {"attention_gate": "softplus", "router": "sigmoid_topk_renormalised", "qk_norm": False}}
SLOTS, PAGE, PAGES, POOL = 3, 4, 16, 30     # 64 positions a slot (rungs 8, 16, 32, 64); a pool of 29 pages where 48 would be whole
W = TOY["sliding_window"]
# float32 program against float32 reference: both round at 6e-8 an operation and sum in other orders (blocks of keys
# against rows, the ring's rows against positions).  The sound program reads 1.2e-6 to 1.9e-6 here, with the XLA legs and
# with the kernels interpreted; the faults read 3e-2 (one kept expert fewer) to 1.3 (the rotary terms swapped).
TIGHT = 1e-5


def toy_config(**changes):
    """The program's config of TOY, computing in float32 so that it can be held tightly to the reference."""
    return dataclasses.replace(FAMILY.program_config(TOY, prefill_chunk=8), dtype=jnp.float32, **changes)


def build(cfg, params=None, cache=None):
    mesh = DeviceMesh(("tp",), (1,), devices=jax.devices()[:1])
    if params is None:
        params = jax.jit(lambda k: lg.init_params(cfg, k))(jax.random.key(7))
    if cache is None:
        cache = PagedKVCache(hybrid_cache_config(cfg, num_slots=SLOTS, page_size=PAGE, pages_per_slot=PAGES, num_pages=POOL), mesh)
    return params, cache, HybridServeEngine(cfg, mesh, params, cache)


@pytest.fixture(scope="module", params=["xla_legs", "kernels_interpreted"])
def system(request):
    """The toy engine, twice: with the XLA legs the CPU takes, and with the
    Pallas kernels a TPU would compile (``paged_decode`` over pages and over the
    ring at 3 and 4 query rows a key head, the windowed and the causal flash
    forward, and, with both of the expert layer's limits turned to 0 while the
    programs are traced, the grouped SwiGLU kernel that is the sorted form's leg
    there) run through the interpreter."""
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


# ------------------------------------------------------- the windowed forward
def _dense_window(q, k, v, window):
    B, T, H, D = q.shape
    k, v = (jnp.repeat(a, H // k.shape[2], axis=2) for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    i = jnp.arange(T)
    keep = (i[:, None] >= i[None, :]) & (i[:, None] - i[None, :] < window)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1), v)


@pytest.mark.parametrize("T,window,block", [(64, 16, 16), (64, 16, 32), (128, 33, 32), (8, 16, 8), (32, 8, 8)],
                         ids=["T=4W", "T=4W-blocks-of-2W", "W-no-multiple-of-a-block", "T<W", "T=4W-small"])
def test_the_windowed_forward_is_the_dense_masked_softmax(T, window, block):
    """The interpreted kernel (resident and streaming form) and the XLA leg, at
    6 query heads on 2, against a dense softmax under ``0 <= i - j < window``."""
    from vescale_tpu.ops.flash_attention import _flash_fwd_pallas, _from3, _to3, flash_attention

    ks = jax.random.split(jax.random.key(T + window), 3)
    q, k, v = (jax.random.normal(kk, (1, T, h, 16), jnp.float32) for kk, h in zip(ks, (6, 2, 2)))
    want = _dense_window(q, k, v, window)
    assert rel(flash_attention(q, k, v, window=window, block_q=block, block_k=block, interpret=True), want) < 2e-6
    assert rel(flash_attention(q, k, v, window=window), want) < 2e-6                # the XLA leg
    streamed = _flash_fwd_pallas(_to3(q), _to3(k), _to3(v), 0.25, True, block, block, True, 6, 2, streaming=True, window=window)[0]
    assert rel(_from3(streamed, 1, 6), want) < 2e-6
    # a window as wide as the sequence is the causal mask
    assert rel(flash_attention(q, k, v, window=T, block_q=block, block_k=block, interpret=True),
               flash_attention(q, k, v, block_q=block, block_k=block, interpret=True)) < 2e-6


def test_a_window_is_forward_only_causal_and_takes_no_block_mask():
    from vescale_tpu.ops.flash_attention import flash_attention

    q = jnp.ones((1, 16, 2, 16), jnp.float32)
    with pytest.raises(NotImplementedError, match="forward only"):
        jax.grad(lambda q: flash_attention(q, q, q, window=4).sum())(q)
    with pytest.raises(ValueError, match="causal window"):
        flash_attention(q, q, q, causal=False, window=4)
    with pytest.raises(ValueError, match="causal window"):
        flash_attention(q, q, q, window=4, mask_block=4)
    with pytest.raises(ValueError, match="causal window"):
        flash_attention(q, q, q, window=0)


def _jaxpr_digest(fn, *args):
    text = re.sub(r" at 0x[0-9a-f]+", "", str(jax.make_jaxpr(fn)(*args)))      # (a closure prints its address)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


# The forwards that the accepted serve cells' prefills trace, as they stood at the parent of the PR that brought
# ``window`` (e61687d: ``python -c`` of the two lambdas below on that tree): a digest of the jaxpr, kernel bodies
# and all, which holds no source location.  A PR that changes these kernels on purpose takes the digests anew.
FORWARDS_BEFORE_WINDOW = {"sdar_block_mask": "969497efc2c60a20", "falcon_causal": "43df0afc11c76845"}


@pytest.mark.parametrize("which", list(FORWARDS_BEFORE_WINDOW))
def test_without_a_window_the_forwards_trace_to_the_text_they_had(which):
    """SDAR's rung (32 query heads on 4 under the block mask of 4) and
    Falcon-H1's (20 on 4, causal), interpreted, at 512 positions."""
    from vescale_tpu.ops.flash_attention import flash_attention

    heads, extra = {"sdar_block_mask": (32, {"mask_block": 4}), "falcon_causal": (20, {})}[which]
    q = jax.ShapeDtypeStruct((1, 512, heads, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 512, 4, 128), jnp.bfloat16)
    digest = _jaxpr_digest(lambda q, k, v: flash_attention(q, k, v, causal=True, scale=128 ** -0.5, interpret=True, **extra), q, kv, kv)
    assert digest == FORWARDS_BEFORE_WINDOW[which]
    with_window = _jaxpr_digest(lambda q, k, v: flash_attention(q, k, v, causal=True, scale=128 ** -0.5, interpret=True, window=512),
                                q, kv, kv)
    assert with_window != digest


# ------------------------------------------------------------------ the router
def test_route_sigmoid_topk_is_a_plain_top_k_of_sigmoids():
    from vescale_tpu.moe.dropless import route_sigmoid_topk

    scores = np.asarray(jax.random.normal(jax.random.key(0), (37, 16), jnp.float32)) * 3.0
    idx, gates = route_sigmoid_topk(jnp.asarray(scores), 4, scale=2.5)
    p = 1.0 / (1.0 + np.exp(-scores.astype(np.float64)))
    order = np.argsort(-p, axis=-1)[:, :4]
    assert np.array_equal(np.sort(np.asarray(idx), -1), np.sort(order, -1)) and idx.dtype == jnp.int32
    kept = np.take_along_axis(p, np.asarray(idx), axis=-1)
    assert np.allclose(np.asarray(gates), 2.5 * kept / kept.sum(-1, keepdims=True), rtol=1e-5)
    assert np.allclose(np.asarray(gates).sum(-1), 2.5, rtol=1e-5) and gates.dtype == jnp.float32
    # a sigmoid keeps the order of the scores, whatever the other experts score: no softmax over them
    idx2, _ = route_sigmoid_topk(jnp.asarray(scores + 5.0), 4)
    assert np.array_equal(np.asarray(idx2), np.asarray(idx))


# ------------------------------------------------------------ the ring's rows
@pytest.mark.parametrize("length,rung", [(3, 8), (8, 8), (9, 16), (16, 16), (21, 32), (27, 32)])
def test_a_prefill_leaves_the_newest_real_positions_on_their_rows(length, rung):
    source = np.asarray(lg.ring_source(length, rung, W))
    for r in range(W):
        live = [p for p in range(length) if p % W == r]
        if live:
            assert source[r] == max(live) and max(live) >= length - W, "the newest real position of that row"
        assert 0 <= source[r] < length, "never a pad position"
    assert sorted(source[r] for r in range(min(length, W))) == list(range(max(0, length - W), length)) or length < W
    assert np.array_equal(np.asarray(lg.ring_row(jnp.arange(20), W)), np.arange(20) % W)


# ------------------------------------------------------- through the cache
@pytest.mark.parametrize("length,steps", [(5, 20), (8, 20), (13, 20), (27, 6)],
                         ids=["shorter-than-the-window", "the-window", "longer", "three-windows"])
def test_prefill_then_decode_through_ring_and_pages_is_the_references_full_forward(system, length, steps):
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


def test_the_cache_keeps_pages_for_the_full_layers_and_a_ring_a_slot_for_the_sliding_ones(system):
    cfg, _params, cache, engine = system
    assert cache.k.data.shape == cache.v.data.shape == (2, POOL, PAGE, 2, 16), "the two full layers' pages, a pool cut short"
    assert cache.state["ring_k"].shape == cache.state["ring_v"].shape == (3, SLOTS, W, 2, 16)
    assert cache.state_bytes_per_slot() == 2 * 3 * W * 2 * 16 * 4
    assert engine.buckets == [8, 16, 32, 64] and cfg.layers_of(lg.FULL) == (0, 4) and cfg.layers_of(lg.SLIDING) == (1, 2, 3)
    # admission counts pages (the full layers') alone: 29 usable pages hold one request of 64 positions and one of 52
    cache.reset()
    assert cache.can_admit(50, 14) and cache.alloc(50, 14) == 0
    assert not cache.can_admit(50, 14) and cache.can_admit(40, 12)
    cache.reset()
    with pytest.raises(ValueError, match="whole pages"):
        lg.cache_config(cfg, num_slots=2, page_size=3, pages_per_slot=4)


def test_the_counters_count_the_ring_and_the_window(system):
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
    # three sliding layers; slots of 5 and 13 positions and one that holds nothing (its one)
    assert d["ring_positions_read"] == 3 * (6 + 8 + 1) and d["ring_positions_unwindowed"] == 3 * (6 + 14 + 1)
    assert d["ring_bytes_rw"] == (d["ring_positions_read"] + 3 * SLOTS) * 2 * 2 * 16 * 4
    pairs = lambda T: sum(min(i + 1, W) for i in range(T))
    assert lg.window_pairs(8, W) == pairs(8) and lg.window_pairs(16, W) == pairs(16) and lg.window_pairs(5, W) == 15
    assert d["prefill_window_attn_flops"] == 4 * 16 * (8 + 8 + 8) * (pairs(8) + pairs(16))
    assert d["prefill_full_attn_flops"] == 4 * 16 * (6 + 6) * (8 * 9 // 2 + 16 * 17 // 2)
    assert d["moe_layer_steps"] == 4 and d["moe_expert_slots"] == 4 * 8 and d["moe_assignments"] == 2 * 2 * 4
    assert d["moe_assignments_held"] == d["moe_assignments"], "every expert is held; the idle slot routes nowhere"
    # two prefills and a step, four expert layers each; the grouped kernel's where the fixture turned the layer's limits to 0
    assert d["moe_expert_layer_calls"] == 3 * 4 and d["moe_grouped_layer_calls"] == (3 * 4 if engine.kernel_decode else 0)
    assert d["decode_pages_read"] == (2 + 4 + 1 if engine.kernel_decode else 0)     # ONE full layer's pages
    for name in lg.STEP_COUNTERS:
        assert name in d
    assert lg.prefill_counters(cfg, 16)["prefill_window_attn_flops"] == FAMILY.prefill_attention_flops(TOY, 16, FAMILY.SLIDING)
    assert lg.prefill_counters(cfg, 16)["prefill_full_attn_flops"] == FAMILY.prefill_attention_flops(TOY, 16, FAMILY.FULL)
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
@pytest.mark.parametrize("fault", [f for f in FAMILY.FAULTS])
def test_each_fault_of_the_reference_fails_check_window_at_its_tolerance(system, fault):
    """The program against the reference WITH the fault: what a program with
    that fault would read against the sound reference.  At the toy's widths one
    kept expert of two fewer shows too (at the published widths it is one of
    eight, of experts drawn narrow, and cannot be told from rounding)."""
    _cfg, _params, _cache, engine = system
    got = FAMILY.check_window(engine, TOY, 5, 13, 20, wrong=fault)
    room = 1.0 if fault == "top7" else 2.0
    assert not got["ok"] and got["logits_max_abs_diff_over_max"] > room * FAMILY.SERVE_LOGITS_TOLERANCE > 1000 * TIGHT


RING_FAULTS = {
    # row p for p < window, then stuck on the last row: the prefill keeps the OLDEST window positions
    "ring_not_wrapped": {"ring_row": lambda positions, window: jnp.minimum(positions, window - 1),
                         "ring_source": lambda length, rung, window: jnp.arange(window, dtype=jnp.int32)},
    # the rung's last window positions, pad and all, in place of the prompt's
    "pads_in_the_ring": {"ring_source": lambda length, rung, window, real=lg.ring_source: real(rung, rung, window)},
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
            patch.setattr(lg, name, wrong)
        _params, _cache, faulty = build(cfg, params, cache)          # the same cache: new programs, traced under the fault
        got = FAMILY.check_window(faulty, TOY, 5, 13, 20)
    assert not got["ok"] and got["logits_max_abs_diff_over_max"] > 2 * FAMILY.SERVE_LOGITS_TOLERANCE
    if fault == "ring_not_wrapped":
        # a prompt that fits the window never wraps: the fault is silent until the ring turns over
        assert FAMILY.check_window(faulty, TOY, 5, 5, 2)["ok"]


def test_the_runners_lengths_would_not_reach_a_window(system):
    """A prompt and steps that stay inside the window read the same with no
    window at all: why ``serve_cell.py``'s check (320 + 4 against a window of 512)
    cannot vouch for the mask, and ``check_window`` exists."""
    _cfg, _params, _cache, engine = system
    assert FAMILY.check_window(engine, TOY, 5, 4, 3, wrong="no_window")["logits_max_abs_diff_over_max"] < TIGHT
    assert not FAMILY.check_window(engine, TOY, 5, 4, 8, wrong="no_window")["ok"]


# ----------------------------------------------------------------- the family
def test_the_init_rule_gives_a_sliding_layers_attention_a_visible_share_of_the_stream():
    """The attention branch of a sliding layer, as it enters the stream, is a
    visible share of the stream's own size (5 to 50% at the published widths,
    where it reads 33% over 1,152 positions; a window of 8 averages over fewer
    keys, so the toy's reads a little over that), and its scores are not flat."""
    cfg = toy_config()
    params = lg.init_params(cfg, jax.random.key(3))
    rms = lambda a: float(jnp.sqrt(jnp.mean(jnp.square(a))))
    x = lg.embed(cfg, params, jnp.asarray(tokens(9, 32)))
    assert 0.7 < rms(x) < 1.4
    live = jnp.ones((32,), bool)
    x, _k, _v = lg.layer_prefill(cfg, params["layers_0"], 0, x, live)
    lp = params["layers_1"]
    u = blocks.rmsnorm(x, lp["input_layernorm"]["weight"], cfg.rms_norm_eps)
    y, _k, _v = lg.attention_prefill(cfg, lp["self_attn"], u, lg.SLIDING)
    assert 0.05 < rms(y) / rms(x) < 0.65, rms(y) / rms(x)
    q, k, _v, gate = lg._qkvg(cfg, lp["self_attn"], u, jnp.arange(32), lg.SLIDING)
    scores = jnp.einsum("qhd,kd->hqk", q[:, :4], k[:, 0]) / 4.0
    assert 1.5 < float(jnp.std(scores)) < 2.6, "a deviation of about 2, not the flat softmax of variance 1 / fan-in"
    assert lg.qk_gain(cfg, lg.SLIDING) == pytest.approx(2.0 ** 0.5) and lg.qk_gain(cfg, lg.FULL) < lg.qk_gain(cfg, lg.SLIDING)
    assert gate.shape == (32, 8) and float(gate.min()) > 0.0


def test_the_two_layer_types_rotate_differently_and_differ_in_shape():
    cfg = toy_config()
    full, factor = lg.inv_freq(cfg, lg.FULL)
    sliding, one = lg.inv_freq(cfg, lg.SLIDING)
    want_full, want_factor = FAMILY.rotary_parameters(TOY["rope_parameters"]["full_attention"], 16)
    assert full.shape == (4,) and sliding.shape == (8,) and one == 1.0 and factor == pytest.approx(1.4158883083359672)
    assert np.allclose(full, want_full) and want_factor == factor
    assert np.allclose(sliding, FAMILY.rotary_parameters(TOY["rope_parameters"]["sliding_attention"], 16)[0])
    # the published numbers: 32 rotated pairs of a head of 128, the ramp between the 8th and the 25th
    real, _ = FAMILY.rotary_parameters({"rope_theta": 500000, "rope_type": "yarn", "factor": 64, "beta_slow": 1, "beta_fast": 64,
                                        "original_max_position_embeddings": 4096, "partial_rotary_factor": 0.5}, 128)
    plain = 500000.0 ** (-np.arange(0, 64, 2) / 64)
    assert real.shape == (32,) and np.allclose(real[:5], plain[:5]) and np.allclose(real[-4:], plain[-4:] / 64)
    x = jnp.ones((3, 2, 16), jnp.float32)
    turned = lg.rotary(x, jnp.asarray([0, 1, 7]), full, factor)
    assert np.allclose(turned[:, :, 8:], 1.0), "half of each head passes"
    assert np.allclose(turned[0, :, :8], factor), "cos and sin carry the attention factor, at position 0 too"
    params = jax.eval_shape(lambda k: lg.init_params(cfg, k), jax.random.key(0))
    assert params["layers_0"]["self_attn"]["q_proj"].shape == (64, 6 * 16) and params["layers_1"]["self_attn"]["q_proj"].shape == (64, 8 * 16)
    assert params["layers_0"]["self_attn"]["g_proj"].shape == (64, 6) and "router" not in params["layers_0"]["mlp"]
    assert params["layers_4"]["mlp"]["w_gate"].shape == (8, 64, 32) and params["layers_4"]["mlp"]["shared"]["gate"].shape == (64, 32)


def test_the_family_refuses_another_block_under_this_name():
    with pytest.raises(SpecError, match="gating"):
        FAMILY.program_config(dict(TOY, gating=False))
    with pytest.raises(SpecError, match="attention_gate"):
        FAMILY.program_config(dict(TOY, assumed=dict(TOY["assumed"], attention_gate="sigmoid")))
    with pytest.raises(SpecError, match="YaRN"):
        FAMILY.program_config(dict(TOY, rope_parameters=dict(TOY["rope_parameters"], full_attention=TOY["rope_parameters"]["sliding_attention"])))
    with pytest.raises(ValueError, match="name each of the 5 layers"):
        toy_config(layer_types=(lg.FULL,) * 4)
    with pytest.raises(ValueError, match="no page to admit by"):
        toy_config(layer_types=(lg.SLIDING,) * 5)
    with pytest.raises(ValueError, match="wrong is one of"):
        FAMILY.logits({}, TOY, [1, 2], [0], wrong="something_else")
