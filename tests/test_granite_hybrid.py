"""The Granite-4.0-H block on the serve path (``models/granite_hybrid.py``,
``models/mamba2.py``, ``moe/dropless.py``, the slot state of ``serve/kv_cache.py``,
``serve/hybrid_engine.py``) at a small size on the CPU, against the plain
float32 reference of ``benchmark/families/granite_hybrid.py`` (which imports
nothing of the program) and against per-token loops written here."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.spec import SpecError, load_family
from vescale_tpu.mesh import DeviceMesh
from vescale_tpu.models import granite_hybrid as gh
from vescale_tpu.models import mamba2
from vescale_tpu.moe import TokenDispatcher, dropless_experts, route_topk
from vescale_tpu.serve import (ContinuousBatchingScheduler, HybridServeEngine, PagedKVCache, PrefixCache, Request,
                               SlotStateUnsupported, SpeculativeDecoder)
from vescale_tpu.serve.hybrid_engine import hybrid_cache_config, prefill_buckets

FAMILY = load_family("granite_hybrid")
# hidden 64, four layers with one attention layer, 8 experts top-3 of which 4 are held, chunk 8
TOY = {"model": "granite_hybrid", "position_embedding_type": "nope", "vocab_size": 96, "hidden_size": 64,
       "num_hidden_layers": 4, "layer_types": ["mamba", "attention", "mamba", "mamba"],
       "num_attention_heads": 4, "num_key_value_heads": 2, "intermediate_size": 32, "shared_intermediate_size": 48,
       "num_local_experts": 4, "num_experts_per_tok": 3, "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
       "mamba_d_conv": 4, "mamba_n_groups": 1, "mamba_chunk_size": 8, "mamba_expand": 2,
       "embedding_multiplier": 12, "residual_multiplier": 0.22, "attention_multiplier": 0.25, "logits_scaling": 16,
       "rms_norm_eps": 1e-5, "tie_word_embeddings": True,
       "reduced": ["num_local_experts", "vocab_size"], "published": {"num_local_experts": 8, "vocab_size": 192},
       "share": {"chips": 2, "of": ["num_local_experts", "vocab_size"]}}
SLOTS, PAGE, PAGES = 3, 4, 8          # 32 positions a slot: buckets 8, 16, 32
TIGHT = 2e-5                          # float32 program against float32 reference


def toy_config(**changes):
    """The program's config of TOY, computing in float32 so that it can be held tightly to the reference."""
    return dataclasses.replace(FAMILY.program_config(TOY), dtype=jnp.float32, **changes)


@pytest.fixture(scope="module", params=["experts_by_shape", "experts_sorted", "kernels_interpreted", "experts_padded"])
def system(request):
    """The toy engine, four times: as the expert layer chooses by its shapes
    (every toy shape is under its first limit: all experts on all tokens); with
    both its limits turned to 0 while the programs are traced, so that prefill
    and decode both take the sorted, grouped product a long prefill takes; with
    the Pallas kernels a TPU would compile (``ssm_step``, ``paged_decode``,
    flash attention, and, both limits at 0 here too, the grouped SwiGLU kernel
    that is the sorted form's leg there) run through the interpreter; and with the first limit
    alone turned to 0, so that both are candidates for the padded batched
    product that a 256-rung prefill takes at the real size."""
    from vescale_tpu.moe import dropless

    cfg = toy_config()
    mesh = DeviceMesh(("tp",), (1,), devices=jax.devices()[:1])
    params = jax.jit(lambda k: gh.init_params(cfg, k))(jax.random.key(7))
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
    assert engine.kernel_ssm_step == engine.kernel_decode == (request.param == "kernels_interpreted")
    assert engine._decode_padded_candidate == (request.param == "experts_padded")
    return cfg, mesh, params, cache, engine


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


# ------------------------------------------------------------ the mixer alone
@pytest.mark.parametrize("length,bucket", [(n, b) for n in (5, 8, 13, 16, 24, 29) for b in (8, 16, 32) if b >= n])
def test_chunked_scan_prefill_is_the_sequential_recurrence_under_every_buckets_padding(length, bucket):
    cfg = toy_config()
    mp = gh.init_params(cfg, jax.random.key(1))["layers_0"]["mixer"]
    u = jax.random.normal(jax.random.key(length), (bucket, cfg.hidden_size), jnp.float32)
    u = u.at[length:].set(37.0)                       # a pad that would show if anything read it
    y, state, tail = jax.jit(lambda u: mamba2.mamba2_prefill(cfg, mp, u, length))(u)
    # the output: the reference's position-at-a-time scan over the real positions alone
    want = FAMILY.mamba_mixer(mp, u[:length], heads=cfg.mamba_n_heads, head_width=cfg.mamba_d_head,
                              state=cfg.mamba_d_state, eps=cfg.rms_norm_eps)
    assert rel(y[:length], want) < TIGHT
    # the state and the tail: the program's own one-step recurrence fed the real positions one by one
    h = jnp.zeros((1, 1) + cfg.ssm_state_shape, jnp.float32)          # (layers, slots, N, H P)
    t = jnp.zeros((1,) + cfg.conv_tail_shape, jnp.float32)
    step = jax.jit(lambda u1, h, t: mamba2.mamba2_step(cfg, mp, u1, h, t, layer=0))
    for i in range(length):
        y1, h, t = step(u[i][None], h, t)
        assert rel(y1[0], want[i]) < 5 * TIGHT
    assert rel(state, h[0, 0]) < TIGHT
    assert rel(tail, t[0]) < TIGHT


def test_a_position_with_step_size_zero_leaves_the_state_alone():
    H, P, N, T = 2, 4, 8, 16
    rng = np.random.default_rng(0)
    x, B, C = (jnp.asarray(rng.normal(size=s), jnp.float32) for s in ((T, H, P), (T, N), (T, N)))
    dt = jnp.asarray(rng.uniform(0.01, 0.5, size=(T, H)), jnp.float32).at[11:].set(0.0)
    A = -jnp.asarray([1.0, 9.0], jnp.float32)
    _, whole = mamba2.ssd_chunked(x, dt, A, B, C, 8)
    _, cut = mamba2.ssd_chunked(x[:8], dt[:8], A, B[:8], C[:8], 8)
    _, rest = mamba2.ssd_chunked(x[8:], dt[8:], A, B[8:], C[8:], 8, initial_state=cut)
    assert rel(whole, rest) < 1e-6
    h = np.zeros((H, P, N))
    for i in range(11):
        h = np.exp(np.asarray(dt[i] * A))[:, None, None] * h + np.einsum("hp,n->hpn", np.asarray(dt[i])[:, None] * x[i], B[i])
    assert rel(whole, h) < 1e-5


# ------------------------------------------------------- through the cache
def test_prefill_then_decode_through_the_cache_is_the_references_full_forward(system):
    """Two slots of different lengths, interleaved: logits, not tokens."""
    _cfg, _mesh, params, cache, engine = system
    cache.reset()
    a, b = tokens(1, 13), tokens(2, 27)              # buckets 16 and 32
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
    counters = engine.trace_counters()
    assert counters["prefill_tokens_padded"] == counters["prefill_bucket_tokens"] >= 16 + 32
    cache.reset()


def test_a_freed_slot_reused_and_a_preempted_request_re_prefilled_give_the_fresh_slots_logits(system):
    _cfg, _mesh, params, cache, engine = system
    cache.reset()
    victim, other = tokens(5, 11), tokens(6, 21)
    want = np.asarray(FAMILY.logits(params, TOY, victim + [9, 4], range(len(victim) - 1, len(victim) + 2)))
    # a slot that another request used to the full, freed and taken again
    s = cache.alloc(len(other), 4)
    engine.prefill(other, s)
    cache.commit_prefill(s, len(other))
    decode_one(engine, cache, {s: 3})
    cache.free(s)
    assert cache.alloc(len(victim), 4) == s, "the lowest free slot is taken again"
    rows = [engine.prefill(victim, s)]
    cache.commit_prefill(s, len(victim))
    rows += [decode_one(engine, cache, {s: 9})[s], decode_one(engine, cache, {s: 4})[s]]
    assert rel(np.stack(rows), want) < TIGHT
    cache.reset()
    # preempted mid-decode by the scheduler, replayed from its prompt
    sched = ContinuousBatchingScheduler(cache)
    sched.submit(Request(rid=0, prompt=tuple(other), max_new_tokens=4), step=0)
    sched.submit(Request(rid=1, prompt=tuple(victim), max_new_tokens=4), step=0)
    for inf in sched.admit(0):
        engine.prefill(inf.req.prompt, inf.slot)
        cache.commit_prefill(inf.slot, len(inf.req.prompt))
    decode_one(engine, cache, {inf.slot: 9 for inf in sched.active.values()})
    assert sched.requeue_newest(reason="oom") == 1
    (again,) = sched.admit(1)
    rows = [engine.prefill(again.req.prompt, again.slot)]
    cache.commit_prefill(again.slot, len(victim))
    rows += [decode_one(engine, cache, {again.slot: 9})[again.slot], decode_one(engine, cache, {again.slot: 4})[again.slot]]
    assert rel(np.stack(rows), want) < TIGHT
    cache.reset()


def test_every_bucket_is_compiled_before_the_engine_is_handed_over(system):
    _cfg, _mesh, _params, cache, engine = system
    assert engine.buckets == [8, 16, 32] and prefill_buckets(256, 1536) == [256, 512, 1024, 1536]
    with pytest.raises(ValueError):
        prefill_buckets(256, 1500)
    cache.reset()
    # (a rung's program is the step that carries the prompt, ``serve_ride``'s: no program is a prefill's own)
    before = (engine._ride_fn._cache_size(), engine._decode_fn._cache_size())
    for n in (3, 8, 9, 16, 17, 32):
        s = cache.alloc(n, 0)
        engine.prefill(tokens(n, n), s)
        cache.commit_prefill(s, n)
        engine.decode(np.zeros((SLOTS,), np.int32))         # (the host's tokens: the prompt that waits goes first, alone)
        cache.free(s)
    # (a program may hold one more entry than buckets: the first call of all saw the arrays as allocated)
    assert (engine._ride_fn._cache_size(), engine._decode_fn._cache_size()) == before and before[0] >= 3
    assert engine.rides and engine._prefill_fn._cache_size() == 0 and not engine._waiting


def test_the_counters_count_what_the_decode_steps_routed(system):
    cfg, _mesh, _params, cache, engine = system
    cache.reset()
    before = engine.trace_counters()
    slots = []
    for n in (5, 9):
        s = cache.alloc(n, 4)
        engine.prefill(tokens(20 + n, n), s)
        cache.commit_prefill(s, n)
        slots.append(s)
    first = decode_one(engine, cache, {s: 1 for s in slots})
    second = decode_one(engine, cache, {s: 2 for s in slots})
    assert engine.trace_counters()["logits_bytes_to_host"] == before["logits_bytes_to_host"], "nobody read a row yet"
    assert first[slots[0]].shape == (cfg.vocab_size,) and np.asarray(second).shape == second.shape
    d = {k: v - before[k] for k, v in engine.trace_counters().items()}
    layers, k, held = cfg.num_hidden_layers, cfg.num_experts_per_tok, cfg.experts_held
    assert d["decode_steps"] == 2 and d["moe_assignments"] == 2 * 2 * k * layers
    assert 0 < d["moe_assignments_held"] <= d["moe_assignments"]
    assert d["moe_expert_slots"] == 2 * layers * held and 0 < d["moe_experts_touched"] <= d["moe_expert_slots"]
    assert d["moe_layer_steps"] == 2 * layers
    assert d["moe_padded_layer_steps"] == (2 * layers if engine._decode_padded_candidate else 0), "2 rows fit any pad"
    # of launched programs, the two prefills with the two steps: every one's expert layers, and those that are the grouped
    # kernel outside any choice on the device (the fixture that interprets the kernels turns both limits to 0)
    assert d["moe_expert_layer_calls"] == 4 * layers
    assert d["moe_grouped_layer_calls"] == (4 * layers if engine.kernel_decode else 0)
    assert d["moe_busiest_expert_tokens"] * held >= d["moe_assignments_held"], "the busiest is at least the mean"
    assert d["ssm_state_bytes_rw"] == 2 * 2 * SLOTS * cache.state_bytes_per_slot()
    assert d["prefill_tokens_real"] == 14 and d["prefill_bucket_tokens"] == 8 + 16
    assert d["logits_bytes_to_host"] == (1 + SLOTS) * cfg.vocab_size * 4, "one row of the first step, the second whole"
    cache.reset()


# ------------------------------------------------------------ the expert layer
def _per_token_loop(x, scores, k, w_gate, w_up, w_down, first, held):
    out = np.zeros((x.shape[0], w_down.shape[-1]))
    for n in range(x.shape[0]):
        order = np.argsort(-scores[n], kind="stable")[:k]
        gates = np.exp(scores[n][order] - scores[n][order].max())
        gates /= gates.sum()
        for e, g in zip(order, gates):
            if first <= e < first + held:
                a, b = x[n] @ w_gate[e - first], x[n] @ w_up[e - first]
                out[n] += g * ((a / (1.0 + np.exp(-a)) * b) @ w_down[e - first])
    return out


@pytest.mark.parametrize("N,k", [(40, 3), (144, 2), (160, 3)], ids=["all_on_all", "padded", "sorted"])
@pytest.mark.parametrize("first,held", [(0, 8), (0, 4), (4, 4), (2, 3)])
def test_the_dropless_layer_is_a_per_token_loop_under_routing_so_uneven_that_capacity_would_drop(first, held, N, k):
    from vescale_tpu.moe.dropless import DENSE_MAX_TOKENS, ROW_PAD, padded_candidate

    # one case for each of the layer's three forms: few tokens; a candidate whose busiest expert, kept by every one of the
    # 123 tokens that route, fits the pad; and 137 on one expert, which a call that is no candidate sorts and one that is
    # sends to the sorted form on the device
    d, f, E = 16, 12, 8
    # (told the router's eight outputs too: 36 pairs an expert land, and a share of 3 or 4 could get 96 or 72)
    assert 40 <= DENSE_MAX_TOKENS < 144 and padded_candidate(144, 2, held, E) and padded_candidate(144, 2, held)
    assert 144 - len(range(0, 144, 7)) <= ROW_PAD < 160 - len(range(0, 160, 7))
    rng = np.random.default_rng(3)
    x = rng.normal(size=(N, d))
    scores = rng.normal(size=(N, E))
    scores[:, 1] += 6.0            # every token keeps experts 1 and 5: N tokens each, capacity is 3 N / 4
    scores[:, 5] += 5.0 if k == 3 else 1.0     # (of two choices the second goes to 5 mostly: every share gets some)
    w_gate, w_up, w_down = rng.normal(size=(held, d, f)), rng.normal(size=(held, d, f)), rng.normal(size=(held, f, d))
    idx, gates = route_topk(jnp.asarray(scores, jnp.float32), k)
    capacity = TokenDispatcher.capacity_for(N, E, k, 2.0)
    dispatched, _ = TokenDispatcher(E, capacity).build_masks(idx, gates)
    assert float(dispatched.sum()) < N * k, "TokenDispatcher drops here"
    mask = np.ones((N,), bool)
    mask[::7] = False              # tokens that route nowhere
    got, counts = jax.jit(lambda *a: dropless_experts(*a, first_held=first, scored=E, token_mask=jnp.asarray(mask)))(
        jnp.asarray(x, jnp.float32), idx, gates, *(jnp.asarray(w, jnp.float32) for w in (w_gate, w_up, w_down)))
    want = _per_token_loop(x, scores, k, w_gate, w_up, w_down, first, held) * mask[:, None]
    assert rel(got, want) < 1e-5
    kept = np.asarray(idx)[mask]
    assert list(np.asarray(counts)) == [int((kept == first + e).sum()) for e in range(held)]


def test_the_shares_add_up_to_the_uncut_layer():
    """Both halves' routed parts, with the shared expert counted once, are the
    uncut reference's layer."""
    whole = toy_config(experts_held=8, first_expert_held=0)
    ep = gh.init_params(whole, jax.random.key(11))["layers_0"]["moe"]
    h = jax.random.normal(jax.random.key(12), (24, whole.hidden_size), jnp.float32)
    shared = FAMILY._swiglu(h, ep["shared_gate"], ep["shared_up"], ep["shared_down"])
    total = -shared                                 # each half adds the shared expert: count it once
    for index in (0, 1):
        half = toy_config(experts_held=4, first_expert_held=4 * index)
        mine = dict(ep, **{k: ep[k][4 * index: 4 * index + 4] for k in ("w_gate", "w_up", "w_down")})
        part, counts = gh.expert_layer(half, mine, h)
        assert int(counts.sum()) > 0
        # ... and each half alone is the reference given the same share
        assert rel(part, FAMILY.expert_layer(mine, h, k=3, first_held=4 * index)) < TIGHT
        total = total + part
    assert rel(total, FAMILY.expert_layer(ep, h, k=3, first_held=0)) < TIGHT


# ------------------------------------------------------------------ refusals
@pytest.mark.parametrize("what", ["alloc_shared", "rollback", "decode_multi", "prefill_suffix", "prefix_cache",
                                  "speculative", "num_stages", "mesh"])
def test_what_needs_a_snapshot_of_the_state_or_a_program_that_is_not_there_says_so(system, what):
    cfg, mesh, params, cache, engine = system
    cache.reset()
    if what in ("num_stages", "mesh"):
        with pytest.raises(NotImplementedError):
            if what == "num_stages":
                HybridServeEngine(cfg, mesh, params, cache, num_stages=2)
            else:
                HybridServeEngine(cfg, DeviceMesh(("tp",), (2,), devices=jax.devices()[:2]), params, cache)
        return
    with pytest.raises(SlotStateUnsupported) as e:
        if what == "alloc_shared":
            cache.alloc_shared([1], 8, 4)
        elif what == "rollback":
            cache.rollback(cache.alloc(4, 4), 0)
        elif what == "decode_multi":
            engine.decode_multi(np.zeros((SLOTS, 2), np.int32))
        elif what == "prefill_suffix":
            engine.prefill_suffix([1] * 8, 0, 4)
        elif what == "prefix_cache":
            ContinuousBatchingScheduler(cache, prefix_cache=PrefixCache(cache))
        else:
            SpeculativeDecoder(engine, params, drafter_layers=1, k=2)
    assert "snapshot" in str(e.value)
    cache.reset()


def test_a_cache_without_slot_state_is_as_it_was():
    from vescale_tpu.serve import KVCacheConfig

    mesh = DeviceMesh(("tp",), (1,), devices=jax.devices()[:1])
    plain = PagedKVCache(KVCacheConfig(layers=2, kv_heads=2, head_dim=8, num_slots=2, page_size=4, pages_per_slot=4), mesh)
    assert not plain.has_slot_state and plain.state == {} and len(plain.fingerprint()) == 5
    s = plain.alloc(4, 4)
    plain.commit_prefill(s, 4)
    plain.rollback(s, 2)
    PrefixCache(plain)


def test_the_hybrid_cache_covers_the_attention_layers_and_fingerprints_its_state(system):
    cfg, _mesh, _params, cache, _engine = system
    assert cache.config.layers == 1 and cache.k.data.shape[0] == 1
    assert cache.state["ssm"].shape == (3, SLOTS) + cfg.ssm_state_shape and cache.state["ssm"].dtype == jnp.float32
    assert cache.state["conv"].shape == (3, SLOTS) + cfg.conv_tail_shape
    assert cfg.ssm_state_shape == (16, 8 * 16), "state dim on sublanes, heads x head width on lanes"
    assert cache.fingerprint()[-1] == cache.state_bytes_per_slot() == 3 * (8 * 16 * 16 * 4 + 3 * (128 + 32) * 4)
    with pytest.raises(ValueError):
        cache.update_state(ssm=cache.state["ssm"])


# ------------------------------------------------------- the family's refusals
@pytest.mark.parametrize("broken,says", [
    ({"position_embedding_type": "rope"}, "positional"),
    ({"share": None}, "share"),
    ({"share": {"chips": 2, "of": ["num_local_experts", "vocab_size", "num_attention_heads"]}}, "divides"),
    ({"share": {"chips": 3, "of": ["num_local_experts", "vocab_size"]}}, "do not hold"),
    ({"published": {"num_local_experts": 8, "vocab_size": 192, "layer_types": ["mamba", "attention", "mamba"] * 4},
      "layer_types": ["mamba", "attention", "mamba", "mamba"]}, "periods"),
    ({"mamba_expand": 3}, "mamba_expand"),
])
def test_the_family_refuses_what_it_cannot_run(broken, says):
    config = {k: v for k, v in dict(TOY, **broken).items() if v is not None}
    with pytest.raises(SpecError) as e:
        FAMILY.program_config(config)
    assert says in str(e.value)


def test_the_family_takes_whole_periods_and_this_chips_place():
    two = dict(TOY, num_hidden_layers=8, layer_types=TOY["layer_types"] * 2,
               published=dict(TOY["published"], layer_types=TOY["layer_types"] * 4))
    assert FAMILY.program_config(two).num_hidden_layers == 8
    second = FAMILY.program_config(dict(TOY, share=dict(TOY["share"], index=1)))
    assert (second.first_expert_held, second.experts_held, second.num_experts) == (4, 4, 8)


def test_the_default_queue_bound_holds_a_burst_that_the_slots_could_admit(monkeypatch):
    """80 callers on 64 slots send at once: nobody set a bound, so none is shed."""
    from vescale_tpu.serve import KVCacheConfig

    mesh = DeviceMesh(("tp",), (1,), devices=jax.devices()[:1])
    big = PagedKVCache(KVCacheConfig(layers=1, kv_heads=2, head_dim=8, num_slots=64, page_size=4, pages_per_slot=2), mesh)
    small = PagedKVCache(KVCacheConfig(layers=1, kv_heads=2, head_dim=8, num_slots=8, page_size=4, pages_per_slot=2), mesh)
    monkeypatch.delenv("VESCALE_SERVE_MAX_QUEUE", raising=False)
    assert ContinuousBatchingScheduler(big).max_queue == 128 and ContinuousBatchingScheduler(small).max_queue == 64
    sched = ContinuousBatchingScheduler(big)
    assert all(sched.submit(Request(rid=i, prompt=(1, 2), max_new_tokens=2), step=0) for i in range(80))
    assert ContinuousBatchingScheduler(big, max_queue=64).max_queue == 64
    monkeypatch.setenv("VESCALE_SERVE_MAX_QUEUE", "64")
    assert ContinuousBatchingScheduler(big).max_queue == 64, "a bound that was set is kept"


@pytest.mark.parametrize("layer", [0, 2])
def test_the_ssm_step_kernel_is_the_xla_leg_and_leaves_the_other_layers_alone(layer):
    from vescale_tpu.kernels.ssm_step import ssm_step, supports

    L, S, N, J = 3, 4, 16, 256
    rng = np.random.default_rng(layer)
    state = jnp.asarray(rng.normal(size=(L, S, N, J)), jnp.float32)
    decay = jnp.asarray(rng.uniform(0.1, 1.0, size=(S, J)), jnp.float32)
    dtx, B, C = (jnp.asarray(rng.normal(size=shape), jnp.float32) for shape in ((S, J), (S, N), (S, N)))
    want_state, want_y = ssm_step(state, decay, dtx, B, C, layer=layer, interpret=None)
    got_state, got_y = ssm_step(jnp.array(state), decay, dtx, B, C, layer=layer, interpret=True)
    assert rel(got_y, want_y) < 1e-6 and rel(got_state[layer], want_state[layer]) < 1e-6
    for other in set(range(L)) - {layer}:
        assert bool(jnp.all(got_state[other] == state[other]))
    assert supports(jnp.float32, 128, 8192, interpret=False) and not supports(jnp.bfloat16, 128, 8192, interpret=True)
    assert not supports(jnp.float32, 128, 8000, interpret=False)
