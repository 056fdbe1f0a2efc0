"""The Falcon-H1 block on the serve path (``models/falcon_h1.py``, the Mamba-2
functions of ``models/mamba2.py`` at G = 2, ``kernels/ssm_step.py``
with groups, ``serve/hybrid_engine.py`` over a cache whose every layer has
pages and slot state) at a small size on the CPU, against the plain float32
reference of ``benchmark/families/falcon_h1.py`` (which imports nothing of the
program) and against per-token loops written here."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.spec import SpecError, load_family
from vescale_tpu.mesh import DeviceMesh
from vescale_tpu.models import falcon_h1 as fh
from vescale_tpu.models import blocks, mamba2
from vescale_tpu.serve import (ContinuousBatchingScheduler, HybridServeEngine, PagedKVCache, Request,
                               SlotStateUnsupported, run_serve_resilient)
from vescale_tpu.serve.hybrid_engine import hybrid_cache_config

FAMILY = load_family("falcon_h1")
# hidden 64, two layers, 4 state-space heads of 16 in 2 groups with a state of 16 (d_ssm 64, the convolution over 128),
# 10 query heads on 2 key heads of 16 (five a key head, as 20 on 4), an MLP of 96, chunk 8; the published multipliers
TOY = {"model": "falcon_h1", "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 10,
       "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 96, "mamba_n_heads": 4, "mamba_d_head": 16,
       "mamba_d_ssm": 64, "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_n_groups": 2, "mamba_chunk_size": 8,
       "mamba_expand": 2, "mamba_rms_norm": True, "mamba_norm_before_gate": False, "mamba_conv_bias": True,
       "mamba_proj_bias": False, "attention_bias": False, "mlp_bias": False, "projectors_bias": False,
       "tie_word_embeddings": False, "rope_scaling": None, "attn_layer_indices": None, "hidden_act": "silu",
       "rope_theta": 100000000000, "rms_norm_eps": 1e-5, "embedding_multiplier": 5.656854249492381,
       "lm_head_multiplier": 0.0078125, "ssm_in_multiplier": 0.25, "ssm_out_multiplier": 0.08838834764831845,
       "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5, 0.3535533905932738],
       "attention_in_multiplier": 1, "attention_out_multiplier": 0.0375, "key_multiplier": 0.011048543456039804,
       "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
       "reduced": ["vocab_size"], "published": {"vocab_size": 1024}, "share": {"chips": 2, "of": ["vocab_size"]}}
SLOTS, PAGE, PAGES = 3, 4, 8          # 32 positions a slot: buckets 8, 16, 32
# float32 program against float32 reference: both round at 6e-8 an operation, and the orders of their sums differ (chunks
# against positions, blocks of keys against rows).  The sound program reads 3e-7 to 4e-7 here, with the XLA legs and with
# the kernels interpreted; ten times that is the tolerance.  The faults of a configuration or of the mathematics read
# 1.3e-2 (group 0's B and C for all heads) to 0.65 (the MLP's gate multiplier left out), a thousand times the tolerance
# and more; a state kept in bfloat16 reads 6e-5 to 8e-5 over sixteen decode steps (2^-9 an element of the state, read
# out against C and through a branch that is a quarter of the stream), ten times the tolerance and more.
TIGHT = 5e-6


def toy_config(**changes):
    """The program's config of TOY, computing in float32 so that it can be held tightly to the reference."""
    return dataclasses.replace(FAMILY.program_config(TOY), dtype=jnp.float32, **changes)


def build(cfg, params=None):
    mesh = DeviceMesh(("tp",), (1,), devices=jax.devices()[:1])
    if params is None:
        params = jax.jit(lambda k: fh.init_params(cfg, k))(jax.random.key(7))
    cache = PagedKVCache(hybrid_cache_config(cfg, num_slots=SLOTS, page_size=PAGE, pages_per_slot=PAGES), mesh)
    return params, cache, HybridServeEngine(cfg, mesh, params, cache).warm()


@pytest.fixture(scope="module", params=["xla_legs", "kernels_interpreted"])
def system(request):
    """The toy engine, twice: with the XLA legs the CPU takes, and with the
    Pallas kernels a TPU would compile (``ssm_step`` with two groups,
    ``paged_decode`` with five query rows a key head, the grouped-query flash
    forward) run through the interpreter."""
    with pytest.MonkeyPatch.context() as patch:
        if request.param == "kernels_interpreted":
            patch.setenv("VESCALE_KERNELS", "interpret")
        cfg = toy_config()
        params, cache, engine = build(cfg)        # every program is traced here
    assert engine.kernel_ssm_step == engine.kernel_decode == (request.param == "kernels_interpreted")
    return cfg, params, cache, engine


def tokens(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(1, TOY["vocab_size"] - 1, n)]


def rel(got, want):
    return float(np.max(np.abs(np.asarray(got) - np.asarray(want))) / np.max(np.abs(np.asarray(want))))


def decode_one(engine, cache, feed):
    """One decode step feeding ``{slot: token}``; returns the logits rows by slot."""
    toks = np.zeros((cache.num_slots,), np.int32)
    for slot, tok in feed.items():
        toks[slot] = tok
    out = engine.decode(toks)
    for slot in feed:
        cache.advance(slot)
    return out


def through_the_cache(engine, cache, prompt, forced):
    """Prefill of ``prompt`` and teacher-forced decode steps: the logits rows of positions ``len(prompt) - 1 ...``."""
    cache.reset()
    s = cache.alloc(len(prompt), len(forced) + 1)
    rows = [engine.prefill(prompt, s)]
    cache.commit_prefill(s, len(prompt))
    rows += [decode_one(engine, cache, {s: t})[s] for t in forced]
    cache.reset()
    return np.stack(rows)


# ------------------------------------------------------------ the mixer alone
@pytest.mark.parametrize("length,bucket", [(n, b) for n in (5, 8, 13, 16, 27) for b in (8, 16, 32) if b >= n])
def test_chunked_scan_with_two_groups_is_the_sequential_recurrence_under_every_buckets_padding(length, bucket):
    """A prompt that ends inside a chunk (5, 13, 27) and one that fills its chunks (8, 16)."""
    cfg = toy_config()
    mp = fh.init_params(cfg, jax.random.key(1))["layers_0"]["mamba"]
    scale = fh.in_scale(cfg)
    u = jax.random.normal(jax.random.key(length), (bucket, cfg.hidden_size), jnp.float32)
    u = u.at[length:].set(37.0)                       # a pad that would show if anything read it
    y, state, tail = jax.jit(lambda u: mamba2.mamba2_prefill(cfg, mp, u, length, in_scale=scale))(u)
    want = FAMILY.mamba_mixer(mp, u[:length], heads=cfg.mamba_n_heads, head_width=cfg.mamba_d_head,
                              state=cfg.mamba_d_state, groups=2, multipliers=cfg.ssm_multipliers, eps=cfg.rms_norm_eps)
    assert rel(y[:length], want) < TIGHT
    # the state and the tail: the program's own one-step recurrence fed the real positions one by one
    h = jnp.zeros((1, 1) + cfg.ssm_state_shape, jnp.float32)          # (layers, slots, N, H P)
    t = jnp.zeros((1,) + cfg.conv_tail_shape, jnp.float32)
    step = jax.jit(lambda u1, h, t: mamba2.mamba2_step(cfg, mp, u1, h, t, layer=0, in_scale=scale))
    for i in range(length):
        y1, h, t = step(u[i][None], h, t)
        assert rel(y1[0], want[i]) < 4 * TIGHT
    assert rel(state, h[0, 0]) < TIGHT
    assert rel(tail, t[0]) < TIGHT


def test_each_group_of_heads_reads_its_own_b_and_c():
    """A per-position loop in numpy, head ``h`` on group ``h // 2``; and the scan resumed from a state."""
    H, P, N, G, T = 4, 3, 5, 2, 16
    rng = np.random.default_rng(0)
    x, B, C = (jnp.asarray(rng.normal(size=s), jnp.float32) for s in ((T, H, P), (T, G, N), (T, G, N)))
    dt = jnp.asarray(rng.uniform(0.01, 0.5, size=(T, H)), jnp.float32)
    A = -jnp.asarray([1.0, 9.0, 3.0, 0.5], jnp.float32)
    y, last = mamba2.ssd_chunked(x, dt, A, B, C, 8)
    h, want = np.zeros((H, P, N)), np.zeros((T, H, P))
    for i in range(T):
        for head in range(H):
            g = head // (H // G)
            h[head] = np.exp(float(dt[i, head] * A[head])) * h[head] + np.outer(np.asarray(dt[i, head] * x[i, head]), B[i, g])
            want[i, head] = h[head] @ np.asarray(C[i, g])
    assert rel(y, want) < 1e-5 and rel(last, h) < 1e-5
    _, cut = mamba2.ssd_chunked(x[:8], dt[:8], A, B[:8], C[:8], 8)
    y2, rest = mamba2.ssd_chunked(x[8:], dt[8:], A, B[8:], C[8:], 8, initial_state=cut)
    assert rel(rest, last) < 1e-6 and rel(y2, y[8:]) < 1e-5


def test_with_one_group_the_generalised_scan_step_and_kernel_give_granites_results_to_the_bit():
    """``B`` and ``C`` with a group axis of one against the same without one: the arithmetic Granite's cell runs."""
    from vescale_tpu.kernels.ssm_step import ssm_step

    H, P, N, T, L, S = 4, 8, 16, 16, 2, 3
    rng = np.random.default_rng(1)
    x, B, C = (jnp.asarray(rng.normal(size=s), jnp.float32) for s in ((T, H, P), (T, N), (T, N)))
    dt = jnp.asarray(rng.uniform(0.01, 0.5, size=(T, H)), jnp.float32)
    A = -jnp.asarray(rng.uniform(1, 16, size=(H,)), jnp.float32)
    same = lambda a, b: all(bool(jnp.all(p == q)) for p, q in zip(a, b))
    assert same(mamba2.ssd_chunked(x, dt, A, B[:, None], C[:, None], 8), mamba2.ssd_chunked(x, dt, A, B, C, 8))
    J = H * P
    state = jnp.asarray(rng.normal(size=(L, S, N, J)), jnp.float32)
    decay = jnp.asarray(rng.uniform(0.1, 1.0, size=(S, J)), jnp.float32)
    dtx, Bs, Cs = (jnp.asarray(rng.normal(size=s), jnp.float32) for s in ((S, J), (S, N), (S, N)))
    assert same(ssm_step(state, decay, dtx, Bs[:, None], Cs[:, None], layer=1, interpret=None),
                ssm_step(state, decay, dtx, Bs, Cs, layer=1, interpret=None))
    assert same(ssm_step(jnp.array(state), decay, dtx, Bs[:, None], Cs[:, None], layer=1, interpret=True),
                ssm_step(jnp.array(state), decay, dtx, Bs, Cs, layer=1, interpret=True))


@pytest.mark.parametrize("G", [1, 2])
@pytest.mark.parametrize("N,J", [(128, 512), (256, 4096)], ids=["state128", "state256-falcon"])
def test_the_ssm_step_kernel_with_groups_is_the_xla_leg_and_leaves_the_other_layers_alone(G, N, J):
    from vescale_tpu.kernels.ssm_step import _block, ssm_step, supports

    L, S, layer = 2, 2, 1
    rng = np.random.default_rng(G + N)
    state = jnp.asarray(rng.normal(size=(L, S, N, J)), jnp.float32)
    decay = jnp.asarray(rng.uniform(0.1, 1.0, size=(S, J)), jnp.float32)
    dtx, B, C = (jnp.asarray(rng.normal(size=shape), jnp.float32) for shape in ((S, J), (S, G, N), (S, G, N)))
    want_state, want_y = ssm_step(state, decay, dtx, B, C, layer=layer, interpret=None)
    got_state, got_y = ssm_step(jnp.array(state), decay, dtx, B, C, layer=layer, interpret=True)
    assert rel(got_y, want_y) < 1e-6 and rel(got_state[layer], want_state[layer]) < 1e-6
    assert bool(jnp.all(got_state[0] == state[0]))
    # the XLA leg itself: every lane against its own group's column
    lane_group = np.arange(J) // (J // G)
    h = np.asarray(decay)[:, None, :] * np.asarray(state[layer]) + \
        np.asarray(B).transpose(0, 2, 1)[:, :, lane_group] * np.asarray(dtx)[:, None, :]
    assert rel(want_state[layer], h) < 1e-6
    assert rel(want_y, (h * np.asarray(C).transpose(0, 2, 1)[:, :, lane_group]).sum(1)) < 1e-5
    # a block is 1 MiB of the state and never straddles two groups
    assert supports(jnp.float32, N, J, interpret=False, groups=G) and (J // G) % _block(N, J // G) == 0
    assert _block(N, J // G) * N * 4 <= 1 << 20


def test_the_block_is_chosen_by_its_bytes_and_granites_is_what_it_was():
    from vescale_tpu.kernels.ssm_step import _block, supports

    assert _block(128, 8192) == 2048 and _block(64, 1024) == 1024            # Granite's cell, and the narrow test case
    assert _block(256, 2048) == 1024                                        # Falcon-H1: a group's 2048 lanes in two blocks
    assert not supports(jnp.float32, 256, 4096, interpret=True, groups=3)   # 4096 lanes are no three groups
    assert not supports(jnp.float32, 256, 2 * 200, interpret=False, groups=2) and supports(jnp.float32, 256, 400, interpret=True, groups=2)
    assert not supports(jnp.bfloat16, 256, 4096, interpret=True, groups=2)


# ------------------------------------------------------- through the cache
def test_prefill_then_decode_through_the_cache_is_the_references_full_forward(system):
    """Two slots of different lengths on two rungs, interleaved: logits, not tokens."""
    _cfg, params, cache, engine = system
    cache.reset()
    a, b = tokens(1, 13), tokens(2, 27)              # buckets 16 and 32; both end inside a chunk
    more_a, more_b = tokens(3, 6), tokens(4, 4)
    sa = cache.alloc(len(a), 8)
    rows_a = [engine.prefill(a, sa)]
    cache.commit_prefill(sa, len(a))
    rows_a.append(decode_one(engine, cache, {sa: more_a[0]})[sa])
    rows_a.append(decode_one(engine, cache, {sa: more_a[1]})[sa])
    sb = cache.alloc(len(b), 5)                      # b arrives while a decodes
    rows_b = [engine.prefill(b, sb)]
    cache.commit_prefill(sb, len(b))
    for i in range(4):
        out = decode_one(engine, cache, {sa: more_a[2 + i], sb: more_b[i]})
        rows_a.append(out[sa])
        rows_b.append(out[sb])
    want_a = FAMILY.logits(params, TOY, a + more_a, range(len(a) - 1, len(a) + 6))
    want_b = FAMILY.logits(params, TOY, b + more_b, range(len(b) - 1, len(b) + 4))
    assert rel(np.stack(rows_a), want_a) < TIGHT
    assert rel(np.stack(rows_b), want_b) < TIGHT
    cache.reset()


def test_every_layer_owns_state_and_pages_and_the_counters_count_them(system):
    cfg, _params, cache, engine = system
    L = cfg.num_hidden_layers
    assert cache.k.data.shape[0] == cache.v.data.shape[0] == L, "a layer of the pools for every layer"
    assert cache.state["ssm"].shape == (L, SLOTS, 16, 64) and cache.state["ssm"].dtype == jnp.float32
    assert cache.state["conv"].shape == (L, SLOTS, 3, 64 + 2 * 2 * 16)
    assert engine.buckets == [8, 16, 32]
    cache.reset()
    before = engine.trace_counters()
    slots = []
    for n in (5, 9):
        s = cache.alloc(n, 4)
        engine.prefill(tokens(20 + n, n), s)
        cache.commit_prefill(s, n)
        slots.append(s)
    np.asarray(decode_one(engine, cache, {s: 1 for s in slots}))
    d = {k: v - before[k] for k, v in engine.trace_counters().items()}
    assert d["decode_steps"] == 1 and d["ssm_state_bytes_rw"] == 2 * SLOTS * cache.state_bytes_per_slot()
    assert d["prefill_scan_chunks"] == 8 // 8 + 16 // 8 and d["prefill_bucket_tokens"] == 8 + 16
    assert all(v == 0 for k, v in d.items() if k.startswith("moe_")), "a dense model: nothing is routed"
    # a layer's pages, with the kernel leg: slots of 6 and 10 positions and one that holds nothing (a length of 1)
    assert d["decode_pages_read"] == (2 + 3 + 1 if engine.kernel_decode else 0)
    cache.reset()


def test_the_normal_path_serves_it_and_a_replay_through_the_cache_gives_the_same_tokens(system):
    """``ContinuousBatchingScheduler`` + ``run_serve_resilient`` over more
    requests than slots: every request completes, and its tokens are those of
    a greedy replay alone on the cache."""
    _cfg, _params, cache, engine = system
    cache.reset()
    sched = ContinuousBatchingScheduler(cache)
    prompts = {rid: tokens(40 + rid, n) for rid, n in enumerate((5, 13, 9, 17, 6))}
    arrivals = [Request(rid=rid, prompt=tuple(p), max_new_tokens=5) for rid, p in prompts.items()]
    run_serve_resilient(engine=engine, scheduler=sched, arrivals=[(0, r) for r in arrivals],
                        install_signal_handlers=False, coordinate=False)
    sched.ledger_check()
    assert sched.counts["completed"] == len(prompts)
    for rid, p in prompts.items():
        assert list(sched.outcomes[rid]["tokens"]) == engine.replay_greedy(p, 5)
    cache.reset()


def test_speculation_and_prefix_sharing_stay_refused_on_a_cache_with_slot_state(system):
    _cfg, _params, cache, engine = system
    with pytest.raises(SlotStateUnsupported):
        engine.decode_multi(np.zeros((SLOTS, 2), np.int32))
    with pytest.raises(SlotStateUnsupported):
        engine.prefill_suffix(tokens(1, 9), 0, 4)
    with pytest.raises(SlotStateUnsupported):
        cache.rollback(0, 0)


# ---------------------------------------------------------------- the faults
def _group_zero_for_all(params):
    """The tree with group 1's B and C made group 0's: their columns of the in-projection and of the convolution."""
    d, GN, N = 64, 32, 16
    out = dict(params)
    for l in range(TOY["num_hidden_layers"]):
        mp = dict(params[f"layers_{l}"]["mamba"])
        for first in (2 * d, 2 * d + GN):                 # B's columns of the in-projection's output, then C's
            mp["in_proj"] = mp["in_proj"].at[:, first + N: first + 2 * N].set(mp["in_proj"][:, first: first + N])
        for name in ("conv_weight", "conv_bias"):         # ... and of the convolution's channels (x | B | C)
            for first in (d, d + GN):
                mp[name] = mp[name].at[..., first + N: first + 2 * N].set(mp[name][..., first: first + N])
        out[f"layers_{l}"] = dict(params[f"layers_{l}"], mamba=mp)
    return out


def _without(key, index=None):
    """TOY with one multiplier left out (1 in its place), or a branch dropped (0)."""
    def change(value):
        config = dict(TOY)
        if index is None:
            config[key] = value
        else:
            config[key] = [value if i == index else m for i, m in enumerate(TOY[key])]
        return config
    return change


FAULTS = {
    "attention_branch_dropped": lambda params: (params, _without("attention_out_multiplier")(0.0), {}),
    "mamba_branch_dropped": lambda params: (params, _without("ssm_out_multiplier")(0.0), {}),
    "group_0s_B_and_C_for_all_heads": lambda params: (_group_zero_for_all(params), TOY, {}),
    "gated_norm_over_all_of_d_ssm": lambda params: (params, TOY, {"norm_groups": 1}),
    "key_multiplier_left_out": lambda params: (params, _without("key_multiplier")(1.0), {}),
    **{f"in_projection_multiplier_{name}_left_out": (lambda params, i=i: (params, _without("ssm_multipliers", i)(1.0), {}))
       for i, name in enumerate(("z", "x", "B", "C", "dt"))},
    "mlp_gate_multiplier_left_out": lambda params: (params, _without("mlp_multipliers", 0)(1.0), {}),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_each_fault_fails_the_comparison_at_its_tolerance(system, fault):
    """The program against the reference WITH the fault (a multiplier left out
    of its configuration, a branch dropped from it, one group's columns given
    to both, its norm taken over all channels): what a program with that fault
    would read against the sound reference."""
    _cfg, params, cache, engine = system
    prompt, forced = tokens(7, 13), tokens(8, 3)
    got = through_the_cache(engine, cache, prompt, forced)
    rows = range(len(prompt) - 1, len(prompt) + len(forced))
    assert rel(got, FAMILY.logits(params, TOY, prompt + forced, rows)) < TIGHT
    faulty_params, faulty_config, kw = FAULTS[fault](params)
    assert rel(got, FAMILY.logits(faulty_params, faulty_config, prompt + forced, rows, **kw)) > 1000 * TIGHT


def test_a_bfloat16_state_fails_the_comparison_at_its_tolerance():
    """The state alone in bfloat16 (weights and products float32): its rounding, 2^-9 an element, is read out every step."""
    cfg = toy_config(state_dtype=jnp.bfloat16)
    params, cache, engine = build(cfg)
    assert not engine.kernel_ssm_step
    prompt, forced = tokens(7, 13), tokens(8, 16)
    got = through_the_cache(engine, cache, prompt, forced)
    want = FAMILY.logits(params, TOY, prompt + forced, range(len(prompt) - 1, len(prompt) + len(forced)))
    assert rel(got[:1], want[:1]) < TIGHT, "the prefill's own row does not read the state back"
    assert rel(got, want) > 4 * TIGHT


# ----------------------------------------------------------------- the family
def test_the_init_rule_gives_every_branch_a_visible_share_of_the_stream():
    """Under the published multipliers each branch's output, as it enters the
    stream, is between a tenth and a half of the stream's own size."""
    cfg = toy_config()
    params = fh.init_params(cfg, jax.random.key(3))
    rms = lambda a: float(jnp.sqrt(jnp.mean(jnp.square(a))))
    x = fh.embed(cfg, params, jnp.asarray(tokens(9, 32)))
    assert 0.7 < rms(x) < 1.4
    lp = params["layers_0"]
    u = blocks.rmsnorm(x, lp["input_layernorm"]["weight"], cfg.rms_norm_eps)
    ym = mamba2.mamba2_prefill(cfg, lp["mamba"], cfg.ssm_in_multiplier * u, 32, in_scale=fh.in_scale(cfg))[0]
    ya = fh.attention_prefill(cfg, lp["self_attn"], cfg.attention_in_multiplier * u)[0]
    yf = fh.mlp(cfg, lp["feed_forward"], u)
    for branch in (cfg.ssm_out_multiplier * ym, cfg.attention_out_multiplier * ya, yf):
        assert 0.05 < rms(branch) / rms(x) < 0.5, rms(branch) / rms(x)


def test_the_family_refuses_another_block_under_this_name():
    with pytest.raises(SpecError, match="mamba_norm_before_gate"):
        FAMILY.program_config(dict(TOY, mamba_norm_before_gate=True))
    with pytest.raises(SpecError, match="mamba_d_ssm"):
        FAMILY.program_config(dict(TOY, mamba_d_ssm=128))
    with pytest.raises(SpecError, match="share"):
        FAMILY.program_config(dict(TOY, share=None))
    with pytest.raises(ValueError, match="whole groups"):
        toy_config(mamba_n_groups=3)
