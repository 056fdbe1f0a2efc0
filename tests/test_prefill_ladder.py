"""The dense engine's prefill ladder (``serve.engine.prefill_buckets``,
``ServeEngine.buckets`` / ``warm``): the rungs follow from the cache's
geometry; a prompt padded to its rung gives the logits row and the K/V that
the same prompt padded to ``max_seq_len`` gives, and touches nothing past the
rung; after ``warm()`` (or the first ``prefill``) no prompt compiles anything;
``warm()`` leaves every slot as it was."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from vescale_tpu.mesh import DeviceMesh
from vescale_tpu.models.llama import Llama, LlamaConfig
from vescale_tpu.ndtimeline import api as nd
from vescale_tpu.serve import KVCacheConfig, PagedKVCache, ServeEngine
from vescale_tpu.serve.engine import prefill_buckets

PAGE, PAGES, SLOTS = 16, 40, 3              # 640 positions a slot: rungs 128 / 256 / 512 / 640
LADDER = [128, 256, 512, 640]


# ------------------------------------------------------------------ the rule
@pytest.mark.parametrize("chunk, positions, smallest, want", [
    (256, 1536, 0, [256, 512, 1024, 1536]),             # the hybrid cell's buckets, to the letter
    (256, 2048, 0, [256, 512, 1024, 1536, 2048]),       # past 1024 no rung is over 1.5 x the one below
    (16, 2048, 128, [128, 256, 512, 1024, 1536, 2048]),  # the chat cell's cache: pages of 16, nothing under 128
    (16, 1536, 128, [128, 256, 512, 1024, 1536]),       # the DeepSeek cell's
    (16, 1536, 256, [256, 512, 1024, 1536]),
    (PAGE, PAGE * PAGES, 128, LADDER),                  # this file's
    (4, 16, 128, [16]),                                 # a tiny cache has the one rung
    (24, 2400, 128, [192, 384, 768, 1536, 2304, 2400]),  # pages that are no power of two: whole pages all the same
    (8, 32, 0, [8, 16, 32]),
    (256, 4096, 0, [256, 512, 1024, 1536, 2048, 3072, 4096]),
    (128, 8192, 0, [128, 256, 512, 1024, 1536, 2048, 3072, 4096, 5120, 6144, 7168, 8192]),   # from 4096 on, quarter steps
    (128, 6144, 128, [128, 256, 512, 1024, 1536, 2048, 3072, 4096, 5120, 6144]),
    (256, 1500, 0, ValueError),                         # no whole number of chunks: refused as before
    (16, 1000, 128, ValueError),
])
def test_the_ladder_follows_from_the_geometry(chunk, positions, smallest, want):
    if want is ValueError:
        with pytest.raises(ValueError, match="whole number of scan chunks"):
            prefill_buckets(chunk, positions, smallest)
        return
    got = prefill_buckets(chunk, positions, smallest)
    assert got == want and got[-1] == positions
    assert all(b % chunk == 0 for b in got) and got == sorted(set(got))
    assert all(b <= 1.5 * a for a, b in zip(got, got[1:]) if a >= 1024)
    assert all(b >= smallest for b in got[:-1])


def test_the_hybrid_engine_still_exports_the_one_rule():
    from vescale_tpu.serve import hybrid_engine

    assert hybrid_engine.prefill_buckets is prefill_buckets and "prefill_buckets" in hybrid_engine.__all__


# ------------------------------------------------------------------ engines
def _config(dtype):
    return LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64, num_hidden_layers=2,
                       num_attention_heads=2, num_key_value_heads=2, max_position_embeddings=1024, dtype=dtype)


def _engine(cfg, params, mesh, stages):
    kc = KVCacheConfig(layers=cfg.num_hidden_layers, kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
                       num_slots=SLOTS, page_size=PAGE, pages_per_slot=PAGES, dtype=cfg.dtype)
    cache = PagedKVCache(kc, mesh)
    return ServeEngine(cfg, mesh, params, cache, num_stages=stages), cache


def _prompt(n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(1, 60, n)]


def _pools(cache):
    return np.asarray(cache.k.data).astype(np.float32), np.asarray(cache.v.data).astype(np.float32)


@pytest.fixture(scope="module", params=[(jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)], ids=["float32", "bfloat16"])
def pair(request):
    """For each stage count a bucketed engine and one held to the single rung
    ``max_seq_len`` (what the engine did before it had a ladder), each over a
    cache of its own in which slot 0 already holds a request."""
    dtype, tol = request.param
    cfg = _config(dtype)
    mesh = DeviceMesh(("tp",), (1,), devices=jax.devices()[:1])
    params = jax.tree_util.tree_map(lambda x: x.astype(dtype),
                                    Llama(cfg).init(jax.random.key(0), jnp.ones((1, 8), jnp.int32))["params"])
    built = {}
    for stages in (1, 2):
        rigs = []
        for ladder in (None, [PAGE * PAGES]):
            eng, cache = _engine(cfg, params, mesh, stages)
            if ladder is not None:
                eng.buckets = ladder
            other = cache.alloc(300, 40)
            eng.prefill(_prompt(300, 99), other).token      # (read: a prompt that waited would be launched by the next call)
            cache.commit_prefill(other, 300)
            rigs.append((eng, cache, other))
        built[stages] = rigs
    return built, tol


@pytest.mark.parametrize("n", [100, 128, 129, 256, 300, 512, 600])   # each rung, three edges, one past an edge
@pytest.mark.parametrize("stages", [1, 2])
def test_a_bucketed_prefill_is_the_prefill_padded_to_max_seq_len(pair, stages, n):
    built, tol = pair
    (eng, cache, other), (full, full_cache, _) = built[stages]
    assert eng.buckets == LADDER and full.buckets == [PAGE * PAGES] and len(eng.stage_bounds) == stages
    rung = next(b for b in LADDER if b >= n)
    prompt = _prompt(n, n)
    slot, full_slot = cache.alloc(n, PAGE * PAGES - n), full_cache.alloc(n, PAGE * PAGES - n)
    row, held = cache.page_table[slot].copy(), PAGES          # the whole allotment is reserved: none of it is page 0
    assert slot == full_slot and (row > 0).all() and np.array_equal(row, full_cache.page_table[full_slot])
    k0, v0 = _pools(cache)
    before = eng.trace_counters()
    got, want = eng.prefill(prompt, slot), full.prefill(prompt, full_slot)
    # the logits row of the last real position (read where it is asked for: shape and dtype copy nothing; a
    # single-stage engine's prompt WAITS until then for a step to carry it, and is launched alone by this read)
    assert got.shape == want.shape == (64,) and got.dtype == np.float32 and not (got.read or want.read)
    assert got.launched == want.launched == (stages > 1)
    got, want = np.asarray(got), np.asarray(want)
    assert np.abs(got - want).max() <= tol * np.abs(want).max()
    k1, v1 = _pools(cache)
    kf, vf = _pools(full_cache)
    # K/V of the prompt's positions, page by page (the last page up to the prompt's end)
    for i in range(-(-n // PAGE)):
        upto = min(PAGE, n - i * PAGE)
        for new, ref in ((k1, kf), (v1, vf)):
            a, b = new[:, row[i], :upto], ref[:, row[i], :upto]
            assert np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1.0), f"page {i}"
    # the slot's entries past the rung, every other slot's pages and the null page's neighbours: not a bit moved
    written = set(int(p) for p in row[: rung // PAGE])
    untouched = [p for p in range(1, cache.num_pages) if p not in written]
    assert set(int(p) for p in row[rung // PAGE: held]) <= set(untouched)
    assert set(int(p) for p in cache.page_table[other][: cache.pages_needed(340)]) <= set(untouched)
    assert np.array_equal(k0[:, untouched], k1[:, untouched]) and np.array_equal(v0[:, untouched], v1[:, untouched])
    # the counters say which rung ran
    d = {k: v - before[k] for k, v in eng.trace_counters().items()}
    assert (d["prefill_calls"], d["prefill_tokens_real"], d["prefill_tokens_padded"]) == (1, n, rung)
    cache.free(slot)
    full_cache.free(full_slot)


def test_decode_after_a_bucketed_prefill_reads_what_the_full_pad_wrote(pair):
    """Pages past the rung hold whatever the slot's last tenant left there:
    decode masks by length and writes a position before it reads it."""
    built, tol = pair
    (eng, cache, _), (full, full_cache, _) = built[1]
    rows = []
    for e, c in ((eng, cache), (full, full_cache)):
        stale = c.alloc(600, 40)                       # a tenant that filled the allotment, then left
        e.prefill(_prompt(600, 5), stale)
        c.free(stale)
        slot = c.alloc(250, 390)
        assert slot == stale
        e.prefill(_prompt(250, 6), slot)
        c.commit_prefill(slot, 250)
        out = []
        for t in (7, 8, 9, 10, 11, 12, 13, 14):        # crosses from position 255 into the page past the 256 rung
            toks = np.zeros((SLOTS,), np.int32)
            toks[slot] = t
            out.append(e.decode(toks)[slot])
            c.advance(slot)
        rows.append(np.stack(out))
        c.free(slot)
    assert np.abs(rows[0] - rows[1]).max() <= tol * np.abs(rows[1]).max()


# --------------------------------------------------------------- the warm-up
def _program_counts(eng):
    """Executables a program: a prefill's programs (the ONE step that carries a prompt where prompts ride, the
    stages' four elsewhere: an executable a rung each), then the decode step's."""
    if eng.rides:
        return [eng._ride_fn._cache_size(), eng._decode_fn._cache_size()]
    return [f._cache_size() for f in (eng._embed_fn, *eng._stage_fns, eng._head_fn, eng._commit_fn, eng._decode_fn)]


@pytest.mark.parametrize("how", ["warm", "first_prefill"])
@pytest.mark.parametrize("stages", [1, 2])
def test_after_the_warm_up_no_prompt_of_any_rung_compiles(stages, how, tmp_path):
    cfg = _config(jnp.float32)
    mesh = DeviceMesh(("tp",), (1,), devices=jax.devices()[:1])
    params = Llama(cfg).init(jax.random.key(0), jnp.ones((1, 8), jnp.int32))["params"]
    eng, cache = _engine(cfg, params, mesh, stages)
    assert _program_counts(eng) == [0] * (2 if stages == 1 else stages + 4) and eng.rides == (stages == 1)
    if how == "warm":
        assert eng.warm() is eng
    else:                                              # the benchmark's own warm-up: one short prompt, one step
        slot = cache.alloc(8, 2)
        eng.prefill([1] * 8, slot)
        cache.commit_prefill(slot, 8)
        eng.decode(np.zeros((SLOTS,), np.int32))
        cache.reset()
    counts = _program_counts(eng)
    # (a program may hold one entry more than rungs: the first call of all saw the cache's arrays as allocated)
    assert all(c >= len(LADDER) for c in counts[:-1]) and counts[-1] >= 1
    nd.start_trace_session(str(tmp_path / "rungs"), profiler=False)   # counts jax's compile events and the engine's counters
    for n in (1, 8, 128, 129, 256, 257, 512, 513, 640):
        slot = cache.alloc(n, 0)
        eng.prefill(_prompt(n, n), slot)
        cache.commit_prefill(slot, n)
        eng.decode(np.zeros((SLOTS,), np.int32))
        cache.free(slot)
    c = nd.stop_trace_session().counters
    assert _program_counts(eng) == counts and c["backend_compiles"] == 0
    # a run states its mean rung and its pad share without a trace
    assert (c["prefill_calls"], c["prefill_tokens_padded"]) == (9, 3 * 128 + 2 * 256 + 2 * 512 + 2 * 640)
    assert c["prefill_tokens_real"] == 1 + 8 + 128 + 129 + 256 + 257 + 512 + 513 + 640


def test_warm_leaves_every_slots_pages_and_lengths_as_they_were():
    cfg = _config(jnp.float32)
    mesh = DeviceMesh(("tp",), (1,), devices=jax.devices()[:1])
    params = Llama(cfg).init(jax.random.key(0), jnp.ones((1, 8), jnp.int32))["params"]
    eng, cache = _engine(cfg, params, mesh, 1)
    a, b = cache.alloc(300, 100), cache.alloc(20, 20)
    eng.buckets, ladder = [PAGE * PAGES], eng.buckets   # fill two slots without the ladder's help ...
    eng._warmed = True
    for slot, n in ((a, 300), (b, 20)):
        eng.prefill(_prompt(n, slot), slot)
        cache.commit_prefill(slot, n)
    toks = np.zeros((SLOTS,), np.int32)
    toks[a], toks[b] = 5, 6
    eng.decode(toks)
    cache.advance(a)
    cache.advance(b)
    eng.buckets = ladder                                # ... then warm every rung beside them
    k0, v0 = _pools(cache)
    table, lengths, fingerprint = cache.page_table.copy(), cache.lengths.copy(), cache.fingerprint()
    counters = eng.trace_counters()
    eng.warm()
    k1, v1 = _pools(cache)
    live = slice(1, None)                               # page 0 is the null page: what warm() writes, nobody reads
    assert np.array_equal(k0[:, live], k1[:, live]) and np.array_equal(v0[:, live], v1[:, live])
    assert np.array_equal(table, cache.page_table) and np.array_equal(lengths, cache.lengths)
    assert cache.fingerprint() == fingerprint and eng.trace_counters() == counters
    # and the two requests go on as if nothing had happened: the same row as an engine that never warmed in between
    step = eng.decode(toks)
    ref, ref_cache = _engine(cfg, params, mesh, 1)
    ref.buckets, ref._warmed = [PAGE * PAGES], True
    for n in (300, 20):
        slot = ref_cache.alloc(n, 100 if n == 300 else 20)
        ref.prefill(_prompt(n, slot), slot)
        ref_cache.commit_prefill(slot, n)
    ref.decode(toks)
    ref_cache.advance(a)
    ref_cache.advance(b)
    want = ref.decode(toks)
    assert np.array_equal(step.tokens[[a, b]], want.tokens[[a, b]])
    assert np.abs(step[[a, b]] - want[[a, b]]).max() <= 1e-5 * np.abs(want[[a, b]]).max()
