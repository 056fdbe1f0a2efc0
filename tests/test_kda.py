"""Delta-rule linear attention on the serve path (``kernels/kda.py``: ``kda_step``
and ``kda_chunk``, both legs; ``models/kda.py``; ``models/ling_hybrid.py`` over it
and ``models/mla.py`` without a query LoRA; the sigmoid router under a group limit
of ``moe/dropless.py``; a cache that is a latent pool AND slot state,
``serve/kv_cache.py``; ``serve/hybrid_engine.py`` with the model's module plugged
in) at a small size on the CPU, against the plain float32 reference of
``benchmark/families/ling_hybrid.py`` (the recurrence position by position; it
imports nothing of the program) and against loops written here."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.spec import SpecError, load_family
from vescale_tpu.kernels import kda as kda_kernels
from vescale_tpu.mesh import DeviceMesh
from vescale_tpu.models import ling_hybrid as lh
from vescale_tpu.models import mla
from vescale_tpu.moe import dropless
from vescale_tpu.moe.dropless import route_sigmoid_group_limited
from vescale_tpu.serve import HybridServeEngine, PagedKVCache, PrefixCache, SlotStateUnsupported
from vescale_tpu.serve.hybrid_engine import hybrid_cache_config
from vescale_tpu.serve.kv_cache import KVCacheConfig

FAMILY = load_family("ling_hybrid")
# hidden 64, the cut's seven layers (a dense delta-rule layer, three delta-rule expert layers, the latent one, two more),
# 4 heads of 16; 32 experts in 4 groups of 8, 2 groups and 4 experts kept a token, of which this chip holds group 0
TOY = {"model": "ling_hybrid", **FAMILY.FIXED, "vocab_size": 96, "hidden_size": 64, "num_hidden_layers": 7, "layer_group_size": 6,
       "first_k_dense_replace": 1, "intermediate_size": 96, "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 32,
       "num_shared_experts": 1, "num_experts": 8, "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2,
       "routed_scaling_factor": 2.5, "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
       "short_conv_kernel_size": 4, "kda_lower_bound": -5, "kv_lora_rank": 32, "qk_head_dim": 24, "qk_nope_head_dim": 16,
       "qk_rope_head_dim": 8, "rotary_dim": 8, "v_head_dim": 16, "rope_theta": 6000000, "rms_norm_eps": 1e-6,
       "expert_swiglu_limit_list": [0] * 7, "share_expert_swiglu_limit_list": [0] * 7,
       "reduced": ["num_experts", "vocab_size"], "published": {"num_experts": 32, "vocab_size": 384},
       "share": {"chips": 4, "of": ["num_experts", "vocab_size"], "index": 0}, "assumed": {**FAMILY.ASSUMED, "first_layer": 1}}
SLOTS, PAGE, PAGES = 3, 4, 16         # 64 positions a slot: rungs 16, 32, 64
TIGHT = 2e-5                          # float32 program against float32 reference


def rel(got, want):
    return float(np.max(np.abs(np.asarray(got, np.float64) - np.asarray(want, np.float64))) / np.max(np.abs(np.asarray(want))))


# ------------------------------------------------------------------ the kernels
def recurrence(q, k, v, g, beta, state=None):
    """The delta rule one position at a time, in float64: outputs (T, H, d_v) and the last state (H, d_k, d_v)."""
    q, k, v, g, beta = (np.asarray(a, np.float64) for a in (q, k, v, g, beta))
    T, H, dk = q.shape
    S = np.zeros((H, dk, v.shape[-1])) if state is None else np.asarray(state, np.float64).copy()
    out = np.zeros(v.shape)
    for t in range(T):
        for h in range(H):
            decayed = np.exp(g[t, h])[:, None] * S[h]
            S[h] = decayed + np.outer(k[t, h], beta[t, h] * (v[t, h] - decayed.T @ k[t, h]))
            out[t, h] = S[h].T @ q[t, h]
    return out, S


def operands(seed, T, H, dk, dv, gate=None, alike=0.0):
    """Unit keys and queries as the block makes them; gates uniform over (-5, 0) or all ``gate``; ``alike`` adds one
    direction to every key of a head (correlated keys are what strains the chunk's inverse)."""
    rng = np.random.default_rng(seed)
    q, k = rng.normal(size=(T, H, dk)), rng.normal(size=(T, H, dk)) + alike * rng.normal(size=(1, H, dk))
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    g = -5.0 * rng.uniform(size=(T, H, dk)) if gate is None else np.full((T, H, dk), gate)
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, rng.normal(size=(T, H, dv)), g, rng.uniform(size=(T, H))))


LEGS = pytest.mark.parametrize("interpret", [None, True], ids=["xla_leg", "kernel_interpreted"])


@LEGS
def test_the_step_is_the_recurrences_one_position_and_an_idle_slot_stands_bit_for_bit(interpret):
    S, H, dk, dv = 5, 4, 16, 16
    state = np.random.default_rng(0).normal(size=(3, S, H, dk, dv)).astype(np.float32)
    q, k, v, g, beta = operands(1, S, H, dk, dv)
    idle = 2
    g, beta = g.at[idle].set(0.0), beta.at[idle].set(0.0)
    new, out = kda_kernels.kda_step(jnp.asarray(state), q, k, v, g, beta, layer=jnp.int32(1), interpret=interpret)
    new = np.asarray(new)
    for s in range(S):
        want_out, want_state = recurrence(q[s: s + 1], k[s: s + 1], v[s: s + 1], g[s: s + 1], beta[s: s + 1], state[1, s])
        assert np.abs(new[1, s] - want_state).max() < 1e-5 and np.abs(np.asarray(out)[s] - want_out[0]).max() < 1e-5
    assert (new[0] == state[0]).all() and (new[2] == state[2]).all(), "the other layers of the array are untouched"
    assert (new[1, idle] == state[1, idle]).all(), "g = 0 and beta = 0: exp(0) S + k * 0"


@LEGS
@pytest.mark.parametrize("T,H,dk,dv,gate,alike", [(32, 2, 16, 16, None, 0.0), (128, 2, 16, 8, None, 0.0), (256, 1, 32, 32, None, 3.0),
                                                    (256, 2, 16, 16, -5.0, 0.0), (256, 2, 16, 16, -1e-3, 3.0)],
                         ids=["two_subchunks", "one_chunk", "keys_alike", "every_gate_at_the_lower_bound", "slow_gates_keys_alike"])
def test_the_chunked_form_is_the_recurrence(interpret, T, H, dk, dv, gate, alike):
    """Against the float64 loop over positions.  At the lower bound the running
    sum of a chunk reaches -640 (``exp`` of it is 0 in float32, ``exp`` of its
    negative no float32 at all): finite, and equal."""
    args = operands(T, T, H, dk, dv, gate, alike)
    out, last = kda_kernels.kda_chunk(*args, interpret=interpret)
    want_out, want_last = recurrence(*args)
    assert np.isfinite(np.asarray(out)).all() and np.isfinite(np.asarray(last)).all()
    assert rel(out, want_out) < 3e-5 and rel(last, want_last) < 3e-5


@LEGS
def test_pad_rows_leave_the_state_where_the_last_real_row_left_it(interpret):
    """A prompt of 100 rows on a rung of 128 and on one of 256 (``g = 0``,
    ``beta = 0`` past the prompt): the same state bit for bit, and the
    recurrence's over the 100 rows."""
    q, k, v, g, beta = operands(7, 256, 2, 16, 16)
    live = (jnp.arange(256) < 100)
    g, beta = jnp.where(live[:, None, None], g, 0.0), jnp.where(live[:, None], beta, 0.0)
    _, short = kda_kernels.kda_chunk(q[:128], k[:128], v[:128], g[:128], beta[:128], interpret=interpret)
    _, long = kda_kernels.kda_chunk(q, k, v, g, beta, interpret=interpret)
    assert (np.asarray(short) == np.asarray(long)).all()
    assert rel(short, recurrence(q[:100], k[:100], v[:100], g[:100], beta[:100])[1]) < 3e-5


def test_which_shapes_the_kernels_take():
    f32 = jnp.float32
    assert kda_kernels.supports_step(f32, 32, 128, 128, interpret=False) and kda_kernels.supports_chunk(32, 128, 128, 8192, interpret=False)
    assert not kda_kernels.supports_step(jnp.bfloat16, 32, 128, 128, interpret=True), "a 16-bit state is another kernel"
    assert not kda_kernels.supports_step(f32, 4, 16, 16, interpret=False) and kda_kernels.supports_step(f32, 4, 16, 16, interpret=True)
    assert not kda_kernels.supports_chunk(32, 128, 128, 192, interpret=False), "compiled: whole chunks of 128"
    assert kda_kernels.supports_chunk(4, 16, 16, 48, interpret=True) and not kda_kernels.supports_chunk(4, 16, 16, 40, interpret=True)
    assert kda_kernels.leg_step(f32, 32, 128, 128) is None and kda_kernels.leg_chunk(32, 128, 128, 128) is None, "no TPU here"
    with pytest.raises(ValueError, match="kda_chunk takes no sequence"):
        kda_kernels.kda_chunk(*operands(0, 40, 1, 16, 16), interpret=True)


# ------------------------------------------------------------------- the router
def plain_route(scores, k, n_group, topk_group, scale, bias):
    """``noaux_tc`` in plain numpy, a token at a time; ties go to the lower id."""
    s = 1.0 / (1.0 + np.exp(-scores.astype(np.float64)))
    c = s + (0.0 if bias is None else bias.astype(np.float64))
    ids, gates, kept = [], [], []
    for row_s, row_c in zip(s, c):
        groups = row_c.reshape(n_group, -1)
        score = np.sort(groups, axis=1)[:, -2:].sum(axis=1)
        keep = np.argsort(-score, kind="stable")[:topk_group]
        masked = np.where(np.isin(np.arange(len(row_c)) // groups.shape[1], keep), row_c, -np.inf)
        top = np.argsort(-masked, kind="stable")[:k]
        ids.append(top)
        gates.append(row_s[top] / row_s[top].sum() * scale)
        kept.append(np.isin(np.arange(n_group), keep))
    return np.array(ids), np.array(gates), np.array(kept)


def test_the_sigmoid_router_under_a_group_limit_is_the_plain_one_ties_and_bias_and_all():
    rng = np.random.default_rng(3)
    scores = rng.normal(size=(40, 32)).astype(np.float32)
    scores[0] = 0.5                                       # every expert alike: the lowest groups, the lowest ids
    scores[1, 8:16] = scores[1, 16:24]                    # two groups alike
    bias = (0.3 * rng.normal(size=32)).astype(np.float32)
    for b in (None, bias):
        idx, gates, kept = route_sigmoid_group_limited(jnp.asarray(scores), 4, n_group=4, topk_group=2, scale=2.5,
                                                       bias=None if b is None else jnp.asarray(b))
        want_idx, want_gates, want_kept = plain_route(scores, 4, 4, 2, 2.5, b)
        assert (np.asarray(idx) == want_idx).all() and (np.asarray(kept) == want_kept).all()
        np.testing.assert_allclose(np.asarray(gates), want_gates, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(gates).sum(axis=1), 2.5, rtol=1e-5)
    assert list(np.asarray(idx)[0]) != [0, 1, 2, 3] and list(plain_route(scores, 4, 4, 2, 2.5, None)[0][0]) == [0, 1, 2, 3]
    without, with_bias = (plain_route(scores, 4, 4, 2, 2.5, b)[2] for b in (None, bias))
    assert (without != with_bias).any(), "the bias changes which groups are kept: it chooses"
    # ... and does not weigh: the gates are the kept experts' sigmoids
    s = 1 / (1 + np.exp(-scores[5].astype(np.float64)))
    np.testing.assert_allclose(np.asarray(gates)[5], s[np.asarray(idx)[5]] / s[np.asarray(idx)[5]].sum() * 2.5, rtol=1e-5)
    with pytest.raises(ValueError, match="cannot give"):
        route_sigmoid_group_limited(jnp.zeros((2, 32)), 17, n_group=4, topk_group=2)


def top_k_route(scores, k, *, n_group, topk_group, scale=1.0, bias=None):
    """The rule as it read before PR 67: every selection a ``jax.lax.top_k``, the kept groups marked by a scatter."""
    N, E = scores.shape
    per = E // n_group
    probs = jax.nn.sigmoid(scores.astype(jnp.float32))
    choice = probs if bias is None else probs + bias.astype(jnp.float32)
    best, _ = jax.lax.top_k(choice.reshape(N, n_group, per), 2)
    _, groups = jax.lax.top_k(jnp.sum(best, axis=-1), topk_group)
    kept = jnp.zeros((N, n_group), bool).at[jnp.arange(N)[:, None], groups].set(True)
    _, idx = jax.lax.top_k(jnp.where(jnp.repeat(kept, per, axis=1), choice, -jnp.inf), k)
    top = jnp.take_along_axis(probs, idx, axis=-1)
    return idx.astype(jnp.int32), top * (scale / jnp.sum(top, axis=-1, keepdims=True)), kept


def top_k_operands(jaxpr):
    """The operand shapes of every ``top_k`` of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "top_k":
            yield eqn.invars[0].aval.shape
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from top_k_operands(inner)


def router_case(name):
    """Scores (N, E) and a bias or None at Ling's shapes: (256, 512), 8 groups of 64 (of 2 in one case)."""
    rng = np.random.default_rng(67)
    scores, bias = rng.normal(size=(256, 512)).astype(np.float32), None
    if name == "bias":
        bias = (0.3 * rng.normal(size=512)).astype(np.float32)
        assert (bias < 0).any() and (bias > 0).any()
    elif name == "two_largest_of_a_group_equal":
        scores[:, 64 * 3 + 5] = scores[:, 64 * 3 + 40] = 2.0     # group 3's maximum twice: it scores 2 x sigmoid(2.0)
        scores[::2, 64 * 6 + 63] = scores[::2, 64 * 6] = 2.5     # the first and the last place of a group
        scores[5, 64:128] = 0.25                                 # a whole group alike
        scores[7] = -3.0                                         # group 3 holds 2.0 twice and nothing else, four groups 2.0 and 1.5:
        scores[7, [64 * 3 + 9, 64 * 3 + 10]] = 2.0               # twice its maximum puts group 3 first, its maximum and the next
        for g in (0, 1, 2, 4):                                   # below it would put it behind all four
            scores[7, [64 * g + 1, 64 * g + 33]] = 2.0, 1.5
    elif name == "two_groups_equal":
        scores[:, 64 * 5:64 * 6] = scores[:, 64 * 2:64 * 3]      # the lower group wins where the cut falls between them
        scores[0] = np.tile(scores[0, :64], 8)                   # every group alike: groups 0 to 3
    elif name == "two_experts_a_group":
        scores = scores[:, :16]
        scores[3] = 0.5
    elif name == "bfloat16":
        scores = jnp.asarray(scores, jnp.bfloat16)               # 8 bits of mantissa: equal scores abound
    elif name != "random":
        raise ValueError(name)
    return jnp.asarray(scores), None if bias is None else jnp.asarray(bias)


@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("name", ["random", "bias", "two_largest_of_a_group_equal", "two_groups_equal", "two_experts_a_group", "bfloat16"])
def test_the_rule_without_a_sort_routes_bit_for_bit_as_its_top_k_form_does(name, jitted):
    """8 of 4 of 8 groups a token, as Ling routes."""
    scores, bias = router_case(name)
    new, old = (lambda s, b, rule=rule: rule(s, 8, n_group=8, topk_group=4, scale=2.5, bias=b) for rule in (route_sigmoid_group_limited, top_k_route))
    if jitted:
        N, n_group, per = scores.shape[0], 8, scores.shape[1] // 8
        operands = list(top_k_operands(jax.make_jaxpr(jax.jit(new))(scores, bias).jaxpr))
        assert (N, n_group, per) in top_k_operands(jax.make_jaxpr(old)(scores, bias).jaxpr), "the walk finds the sort where there is one"
        assert operands == [(N, n_group)], f"one top_k is left, of the groups' scores: none over {(N, n_group, per)}, none over a row's experts"
        new, old = jax.jit(new), jax.jit(old)
    (idx, gates, kept), (want_idx, want_gates, want_kept) = new(scores, bias), old(scores, bias)
    assert idx.dtype == jnp.int32 and gates.dtype == jnp.float32 and kept.dtype == jnp.bool_
    kept = np.asarray(kept)
    assert np.array_equal(np.asarray(idx), np.asarray(want_idx)) and np.array_equal(kept, np.asarray(want_kept))
    assert np.array_equal(np.asarray(gates).view(np.uint32), np.asarray(want_gates).view(np.uint32)), "the gates, bit for bit"
    assert (kept.sum(axis=1) == 4).all()
    if name == "two_largest_of_a_group_equal":
        assert list(kept[7]) == [True] * 4 + [False] * 4, "twice the maximum, not the maximum and the next below it"
    if name == "two_groups_equal":
        assert not (kept[:, 5] & ~kept[:, 2]).any() and (kept[:, 2] & ~kept[:, 5]).any()
        assert list(kept[0]) == [True] * 4 + [False] * 4


# ------------------------------------------------- the latent block without a query LoRA
def test_the_latent_block_without_a_query_lora_has_one_query_matrix_and_takes_a_head_gate():
    cfg = toy_config()
    a = cfg.mla
    assert a.q_lora_rank is None and a.cache_row == 128 and a.latent_row == 40
    ap = mla.attention_params(a, jax.random.key(0))
    assert set(ap) == {"q", "kv_a", "kv_a_norm", "kv_b_k", "kv_b_v", "o"} and ap["q"].shape == (64, 4 * 24)
    u = jax.random.normal(jax.random.key(1), (16, 64))
    plain, rows = mla.mla_prefill(a, ap, u)
    ones, _ = mla.mla_prefill(a, ap, u, head_gate=jnp.ones((16, 4)))
    none, _ = mla.mla_prefill(a, ap, u, head_gate=jnp.zeros((16, 4)))
    assert rel(ones, plain) < 1e-6 and float(jnp.abs(none).max()) == 0.0 and rows.shape == (16, 128)
    # a gate on ONE head is that head's share of the output
    only = [mla.mla_prefill(a, ap, u, head_gate=jnp.zeros((16, 4)).at[:, h].set(1.0))[0] for h in range(4)]
    assert rel(sum(only), plain) < 1e-5
    with_lora = mla.attention_params(dataclasses.replace(a, q_lora_rank=8), jax.random.key(0))
    assert {"q_a", "q_a_norm", "q_b"} <= set(with_lora) and "q" not in with_lora, "a family with a query LoRA has the tree it had"


# ------------------------------------------------- a latent pool AND slot state
def test_a_cache_is_a_latent_pool_and_slot_state_at_once():
    kc = KVCacheConfig(layers=1, kv_heads=1, head_dim=128, num_slots=3, page_size=4, pages_per_slot=8, dtype=jnp.bfloat16, latent=True,
                       slot_state=(("kda_state", 6, (4, 16, 16), jnp.float32), ("kda_conv", 6, (3, 192), jnp.bfloat16)))
    mesh = DeviceMesh(("tp",), (1,), devices=jax.devices()[:1])
    cache = PagedKVCache(kc, mesh)
    arrays = cache.arrays()
    assert list(arrays) == ["k", "kda_state", "kda_conv"] and cache.v is None
    assert arrays["k"].shape == (1, 25, 4, 1, 128) and arrays["kda_state"].shape == (6, 3, 4, 16, 16)
    assert arrays["kda_conv"].shape == (6, 3, 3, 192) and arrays["kda_conv"].dtype == jnp.bfloat16
    cache.update_arrays({name: a + 1 for name, a in arrays.items()})
    assert float(cache.state["kda_state"][0, 0, 0, 0, 0]) == 1.0 and float(cache.k.data[0, 0, 0, 0, 0]) == 1.0
    slot = cache.alloc(5, 3)            # pages are the latent layer's; the slot's rows of the state come with it
    assert cache.page_table[slot, :2].all() and cache.free_page_count() == 24 - 2
    with pytest.raises(SlotStateUnsupported, match="kda_conv, kda_state"):
        cache.refuse_slot_state("a shared prefix")
    with pytest.raises(SlotStateUnsupported):
        PrefixCache(cache)
    cache.free(slot)
    assert cache.free_page_count() == 24
    assert hybrid_cache_config(FAMILY.program_config(TOY, prefill_chunk=16), num_slots=3, page_size=4, pages_per_slot=8) == kc


# ------------------------------------------------------------------- the program
def toy_config(**changes):
    """The program's config of TOY, computing in float32 so that it can be held tightly to the reference."""
    return dataclasses.replace(FAMILY.program_config(TOY, prefill_chunk=16), dtype=jnp.float32, **changes)


@pytest.fixture(scope="module", params=["xla_legs", "kernels_interpreted"])
def system(request):
    """The toy engine, twice: as a CPU builds it (the XLA legs, the dense prefill
    attention, all held experts on all tokens), and with the Pallas kernels a TPU
    would compile (``kda_step``, ``kda_chunk``, ``paged_decode_latent``, the flash
    forward, the grouped SwiGLU kernel: the expert layer's limits turned to 0
    while the programs are traced) run through the interpreter."""
    cfg = toy_config()
    mesh = DeviceMesh(("tp",), (1,), devices=jax.devices()[:1])
    params = jax.jit(lambda k: lh.init_params(cfg, k))(jax.random.key(7))
    cache = PagedKVCache(hybrid_cache_config(cfg, num_slots=SLOTS, page_size=PAGE, pages_per_slot=PAGES), mesh)
    with pytest.MonkeyPatch.context() as patch:
        if request.param == "kernels_interpreted":
            patch.setattr(dropless, "DENSE_MAX_TOKENS", 0)
            patch.setattr(dropless, "PADDED_MAX_MEAN_ROWS", 0)
            patch.setenv("VESCALE_KERNELS", "interpret")
        engine = HybridServeEngine(cfg, mesh, params, cache).warm()     # every program is traced here
    assert engine.kernel_decode == engine.kernel_kda == (request.param == "kernels_interpreted")
    assert engine.buckets == [16, 32, 64] and not engine.rides
    return cfg, mesh, params, cache, engine


def tokens(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(1, TOY["vocab_size"] - 1, n)]


def through_the_cache(engine, cache, prompt, forced):
    """Prefill ``prompt``, then feed ``forced`` one decode step at a time; the logits rows."""
    cache.reset()
    slot = cache.alloc(len(prompt), len(forced) + 1)
    rows = [np.asarray(engine.prefill(prompt, slot))]
    cache.commit_prefill(slot, len(prompt))
    for tok in forced:
        toks = np.zeros((cache.num_slots,), np.int32)
        toks[slot] = tok
        rows.append(np.asarray(engine.decode(toks)[slot]))
        cache.advance(slot)
    return np.stack(rows), slot


@pytest.mark.parametrize("n", [5, 13, 37], ids=["rung_16", "rung_16_nearly_full", "rung_64"])
def test_prefill_then_decode_through_the_latent_pool_and_the_states_is_the_references_forward(system, n):
    """The pad rule is under the check (5 and 13 of 16, 37 of 64 positions real:
    pad rows leave state and tail alone), the state and the tail go from the
    prefill to the decode steps, the decode steps are the ABSORBED latent form
    against the reference's expanded one, and the reference never chunks."""
    cfg, _mesh, params, cache, engine = system
    prompt, forced = tokens(n, n), tokens(100 + n, 6)
    got, _slot = through_the_cache(engine, cache, prompt, forced)
    want = FAMILY.logits(params, TOY, prompt + forced, range(n - 1, n + 6))
    assert rel(got, want) < 5 * TIGHT
    cache.reset()


# what each fault is worth at the toy's size against float32 rounding (2e-5); the router in bfloat16 moves a pair across the
# cut only where two scores lie within 2^-9 of each other, which a toy of 32 experts may not have: it is held to "not closer"
LEAST = {"fp8_weights": 1e-2, "state_bf16": 1e-3, "router_bf16": 0.0, "gate_unbounded": 1e-2, "no_beta": 1e-2,
         "decay_after": 1e-2, "group_swapped": 1e-2}


@pytest.mark.parametrize("wrong", FAMILY.FAULTS)
def test_a_wrong_computation_on_the_same_weights_reads_far_from_the_program(system, wrong):
    cfg, _mesh, params, cache, engine = system
    prompt, forced = tokens(37, 37), tokens(137, 6)
    got, _slot = through_the_cache(engine, cache, prompt, forced)
    sound = rel(got, FAMILY.logits(params, TOY, prompt + forced, range(36, 43)))
    far = rel(got, FAMILY.logits(params, TOY, prompt + forced, range(36, 43), wrong))
    assert far >= max(LEAST[wrong], sound), (wrong, far, sound)
    cache.reset()


@pytest.mark.parametrize("wrong, reads", [("", None), ("state_bf16", "state"), ("decay_after", "state"), ("group_swapped", "experts"),
                                          ("fp8_weights", "experts")])
def test_the_long_check_reads_the_states_and_the_expert_layers_where_no_moved_pair_reaches(system, wrong, reads):
    """``check_window``'s two readings beside the logits: the slot's matrix states of the layers no router comes before
    (two here, as at the published cut) against the reference scan's carry, and the program's expert layers on the
    reference's own streams, row by row.  (In float32, as this toy computes, a sound reading is rounding.)"""
    _cfg, _mesh, _params, cache, engine = system
    got = FAMILY.check_window(engine, TOY, 5, prompt_tokens=37, steps=6, wrong=wrong)
    assert got["state_layers"] == 2 and got["expert_rows"] == 6 * 43 and not cache.active_slots()
    state, off = got["state_max_abs_diff_over_max"], got["expert_rows_off"]
    if reads is None:
        assert got["ok"] and state < TIGHT and off == 0 and got["expert_row_worst"] < TIGHT
    elif reads == "state":
        # rounded to bfloat16 a position, 43 positions: two hundred times float32's rounding, and under the limit a
        # thousand positions at the published widths pass (the family's readings); the decay misplaced is far over it
        assert state > (1e-3 if wrong == "state_bf16" else FAMILY.STATE_TOLERANCE) and off == 0
        assert got["ok"] == (wrong == "state_bf16" and got["logits_max_abs_diff_over_max"] <= FAMILY.SERVE_LOGITS_TOLERANCE)
    else:
        assert off > 0.5 * got["expert_rows"] and not got["ok"] and (wrong == "fp8_weights" or state < TIGHT)


def test_a_decode_step_leaves_an_idle_slots_state_and_tail_bit_for_bit_and_counts_what_it_moved(system):
    cfg, _mesh, _params, cache, engine = system
    cache.reset()
    a = cache.alloc(13, 4)
    np.asarray(engine.prefill(tokens(1, 13), a))
    cache.commit_prefill(a, 13)
    b = cache.alloc(9, 4)
    np.asarray(engine.prefill(tokens(2, 9), b))          # prefilled, NOT committed: its length is 0, the step takes it as idle
    before = {name: np.asarray(x) for name, x in cache.state.items()}
    counters = engine.trace_counters()
    toks = np.zeros((cache.num_slots,), np.int32)
    toks[a] = 5
    np.asarray(engine.decode(toks)[a])
    after = {name: np.asarray(x) for name, x in cache.state.items()}
    for name in before:
        assert (after[name][:, b] == before[name][:, b]).all(), name
        assert (after[name][:, a] != before[name][:, a]).any(), name
    added = {k: v - counters[k] for k, v in engine.trace_counters().items()}
    assert added["kda_state_bytes_rw"] == 2 * SLOTS * 6 * 4 * 16 * 16 * 4, "every slot's, idle or not: the kernel moves them all"
    assert added["latent_bytes_read"] == 4 * PAGE * 128 * 4, "one latent layer: the active slot's 4 pages of 4 float32 rows of 128"
    assert added["moe_layer_steps"] == 6 and added["moe_assignments"] == 6 * 4
    assert 0 <= added["route_rows_held_group"] <= 6 and added["moe_assignments_held"] <= added["moe_assignments"]
    cache.reset()


def test_a_share_whose_decode_rows_are_few_an_expert_leaves_the_pad_for_the_grouped_kernel(monkeypatch):
    """The cell's decode step in small: 3 slots x 4 experts a token over 8 held of 32 are 12 pairs, 1.5 a HELD expert and
    0.4 a SCORED one.  With all-on-all out of the way and a pad worth taking from 1 row an expert on, the rule that read
    ``N k / held`` made the step a padded candidate; told the router's 32 outputs it is the sorted form alone, the grouped
    kernel's, and the engine's latches and counters, which repeat the rule on the host, say so."""
    monkeypatch.setattr(dropless, "DENSE_MAX_TOKENS", 0)
    monkeypatch.setattr(dropless, "PADDED_MIN_MEAN_ROWS", 1)
    monkeypatch.setenv("VESCALE_KERNELS", "interpret")
    cfg = toy_config()
    k, held, scored = cfg.num_experts_per_tok, cfg.experts_held, cfg.num_experts
    assert (k, held, scored) == (4, 8, 32)
    assert dropless.padded_candidate(SLOTS, k, held) and not dropless.padded_candidate(SLOTS, k, held, scored)
    mesh = DeviceMesh(("tp",), (1,), devices=jax.devices()[:1])
    params = jax.jit(lambda key: lh.init_params(cfg, key))(jax.random.key(7))
    cache = PagedKVCache(hybrid_cache_config(cfg, num_slots=SLOTS, page_size=PAGE, pages_per_slot=PAGES), mesh)
    engine = HybridServeEngine(cfg, mesh, params, cache)
    assert not engine._decode_padded_candidate and engine._expert_layers == 6
    assert engine._grouped_layers == {SLOTS: 6}, "a rung's rows are 2 to 8 a scored expert: candidates still, here"
    slot = cache.alloc(9, 4)
    np.asarray(engine.prefill(tokens(3, 9), slot))
    cache.commit_prefill(slot, 9)
    for tok in (5, 6, 7):
        toks = np.zeros((cache.num_slots,), np.int32)
        toks[slot] = tok
        np.asarray(engine.decode(toks)[slot])
        cache.advance(slot)
    c = engine.trace_counters()
    assert c["decode_steps"] == 3 and c["moe_layer_steps"] == 3 * 6 and c["moe_padded_layer_steps"] == 0
    assert c["moe_expert_layer_calls"] == (1 + 3) * 6 and c["moe_grouped_layer_calls"] == 3 * 6, "every decode launch's"


def test_the_modules_side_of_the_seam(system):
    cfg, _mesh, _params, cache, engine = system
    assert cfg.latent_layers == (4,) and cfg.delta_layers == (0, 1, 2, 3, 5, 6) and cfg.groups_held == (0,)
    assert [cfg.is_latent(l) for l in range(7)] == [(1 + l + 1) % 6 == 0 for l in range(7)], "the cut starts at the source's layer 1"
    kc = cache.config
    assert kc.latent and kc.layers == 1 and [s[:3] for s in kc.slot_state] == [("kda_state", 6, (4, 16, 16)), ("kda_conv", 6, (3, 192))]
    assert set(lh.STEP_COUNTERS) <= set(engine.trace_counters()) and lh.prefill_counters(cfg, 64) == {}
    with pytest.raises(SlotStateUnsupported):
        engine.decode_multi(np.zeros((SLOTS, 2), np.int32))
    with pytest.raises(ValueError, match="whole routing groups"):
        toy_config(experts_held=4)
    with pytest.raises(ValueError, match="without a latent layer"):
        toy_config(num_hidden_layers=3)
    for key, bad in (("q_lora_rank", 8), ("rope_scaling", {"type": "yarn"}), ("expert_swiglu_limit_list", [0, 0, 0, 0, 0, 0, 4]),
                     ("num_nextn_predict_layers", 1)):
        with pytest.raises(SpecError, match=key):
            FAMILY.program_config({**TOY, key: bad})


# ------------------------------------------------------------------- the share
def test_the_shares_routed_parts_and_the_shared_expert_once_are_the_uncut_expert_layer_and_the_head_is_sliced():
    """Four chips, a routing group each: their routed parts added up, plus the
    shared expert counted once, are the reference's expert layer of the uncut
    model; and a chip's logits are the whole head's first rows."""
    whole = dataclasses.replace(toy_config(), experts_held=32, first_expert_held=0, vocab_size=384)
    params = jax.jit(lambda k: lh.init_params(whole, k))(jax.random.key(11))
    ep = params["layers_1"]["mlp"]
    h = jax.random.normal(jax.random.key(12), (24, 64))
    uncut = {**TOY, "num_experts": 32, "vocab_size": 384, "reduced": [], "published": {}, "share": None}
    want = FAMILY.expert_layer(ep, h, uncut, first_held=0)
    shared = lh.swiglu(h, ep["shared"]["gate"], ep["shared"]["up"], ep["shared"]["down"], jnp.float32)
    total, rows_here = shared, 0
    for chip in range(4):
        share = dataclasses.replace(whole, experts_held=8, first_expert_held=8 * chip)
        held = {**ep, **{name: ep[name][8 * chip: 8 * chip + 8] for name in ("w_gate", "w_up", "w_down")}}
        routed, counts, here = lh._routed_rows(share, held, h, None)
        assert int(counts.sum()) <= 24 * 4 and share.groups_held == (chip,)
        total, rows_here = total + routed, rows_here + int(here)
    assert rel(total, want) < TIGHT
    assert rows_here == 24 * 2, "every token keeps two of the four groups"
    x = jax.random.normal(jax.random.key(13), (5, 64))
    sliced = {**params, "lm_head": {"kernel": params["lm_head"]["kernel"][:, :96]}}
    assert (np.asarray(lh.head(toy_config(), sliced, x)) == np.asarray(lh.head(whole, params, x))[:, :96]).all()
