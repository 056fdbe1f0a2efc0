"""Serving throughput multipliers (ISSUE 15): radix-tree prefix caching
over the PagedKVCache + speculative decoding.

Covers the radix-tree invariants (insert/match/split on non-page-aligned
prefixes, deterministic LRU eviction, refcount-digest fold ordering), the
page-refcount safety contract (an eviction/oom fault can never free a
page another holder still references, and the victim's replay re-hits the
cache), drafter/target greedy-acceptance bit-equality across k in
{1, 4, 8} and page sizes including non-pow2, the `/router` v3 feed, and
the tier-1 wiring of scripts/spec_prefix_smoke.py."""

import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from vescale_tpu.mesh import DeviceMesh
from vescale_tpu.models.llama import Llama, LlamaConfig
from vescale_tpu.resilience import faultsim
from vescale_tpu.serve import (
    ContinuousBatchingScheduler,
    KVCacheConfig,
    KVCacheOutOfPages,
    PagedKVCache,
    PrefixCache,
    Request,
    ServeEngine,
    SpeculativeDecoder,
    run_serve_resilient,
    slice_drafter_params,
)
from vescale_tpu.serve.speculative import drafter_config, drafter_template

REPO = str(pathlib.Path(__file__).resolve().parent.parent)

CFG = LlamaConfig(
    vocab_size=64,
    hidden_size=16,
    intermediate_size=32,
    num_hidden_layers=2,
    num_attention_heads=2,
    num_key_value_heads=2,
    max_position_embeddings=64,
    dtype=jnp.float32,
)


@pytest.fixture(scope="module")
def model_and_params():
    model = Llama(CFG)
    params = model.init(jax.random.key(0), jnp.ones((1, 8), jnp.int32))["params"]
    return model, params


@pytest.fixture(scope="module")
def tp2_mesh():
    return DeviceMesh(("tp",), (2,))


def _cache(num_slots=2, page_size=4, pages_per_slot=4, num_pages=None, mesh=None):
    kc = KVCacheConfig(
        layers=CFG.num_hidden_layers,
        kv_heads=CFG.num_key_value_heads,
        head_dim=CFG.head_dim,
        num_slots=num_slots,
        page_size=page_size,
        pages_per_slot=pages_per_slot,
        **({"num_pages": num_pages} if num_pages is not None else {}),
    )
    return PagedKVCache(kc, mesh if mesh is not None else DeviceMesh(("tp",), (2,)))


# ======================================================== refcounted pages
def test_shared_page_survives_slot_free():
    """The eviction-safety contract: freeing a slot drops ONE reference per
    page — a page the radix tree (or another slot) still holds keeps its
    bytes and never re-enters the free pool."""
    c = _cache(num_slots=2, page_size=4, pages_per_slot=4)
    s0 = c.alloc(8, 0)
    c.commit_prefill(s0, 8)
    pages = [int(p) for p in c.page_table[s0][:2]]
    for p in pages:
        c.retain_page(p)  # the tree pins both pages
    free_before = c.free_page_count()
    c.free(s0)  # oom eviction / completion / timeout — same host op
    assert all(c.page_ref(p) == 1 for p in pages)
    assert all(p not in c._free_pages for p in pages)
    # a second holder: map the shared pages into a new slot, free the tree
    s1 = c.alloc_shared(pages, 8, 0)
    assert [int(p) for p in c.page_table[s1][:2]] == pages
    assert all(c.page_ref(p) == 2 for p in pages)
    for p in pages:
        c.release_page(p)  # tree eviction while the slot still reads
    assert all(c.page_ref(p) == 1 for p in pages)
    assert all(p not in c._free_pages for p in pages)
    c.free(s1)  # the LAST reference: now they return
    assert all(c.page_ref(p) == 0 for p in pages)
    assert c.free_page_count() == free_before + 2


def test_release_page_refcount_errors():
    c = _cache()
    s = c.alloc(4, 0)
    p = int(c.page_table[s][0])
    with pytest.raises(ValueError):
        c.retain_page(0)  # the reserved null page
    with pytest.raises(ValueError):
        c.release_page(int(c._free_pages[0]))  # unreferenced
    c.retain_page(p)
    c.free(s)
    c.release_page(p)
    with pytest.raises(ValueError):
        c.release_page(p)  # already back in the pool


def test_alloc_shared_validations():
    c = _cache(num_slots=2, page_size=4, pages_per_slot=4)
    s = c.alloc(8, 0)
    pages = [int(p) for p in c.page_table[s][:2]]
    with pytest.raises(ValueError):
        c.alloc_shared(pages, 4, 0)  # 2 shared pages > the 1 page needed
    with pytest.raises(KVCacheOutOfPages):
        c.alloc_shared(pages, 8, 100)  # over max_seq_len
    for p in pages:
        c.retain_page(p)
    c.free(s)
    stale = pages[0]
    c.release_page(pages[0])
    c.release_page(pages[1])  # both unreferenced now
    with pytest.raises(ValueError):
        c.alloc_shared([stale], 8, 0)  # freed page may not be mapped


def test_fingerprint_carries_page_refs_and_fold_order():
    """The refcount-digest fold contract: identical event ORDER gives
    identical fingerprints; a different interleaving of the same events
    gives a different digest (the digest is the decision log); and the
    fingerprint's live-reference total catches a silent retain."""
    a, b = _cache(), _cache()
    for c in (a, b):
        s = c.alloc(8, 0)
        c.commit_prefill(s, 8)
        c.retain_page(int(c.page_table[s][0]))
        c.retain_page(int(c.page_table[s][1]))
        c.free(s)
    assert a.fingerprint() == b.fingerprint()
    # same events, different order -> different digest
    c2 = _cache()
    s = c2.alloc(8, 0)
    c2.commit_prefill(s, 8)
    c2.retain_page(int(c2.page_table[s][1]))  # swapped
    c2.retain_page(int(c2.page_table[s][0]))
    c2.free(s)
    assert c2.fingerprint()[0] != a.fingerprint()[0]
    # the live-reference total rides the fingerprint tuple
    assert a.fingerprint()[-1] == 2 == int(a._page_refs.sum())


# ============================================================= radix tree
def _fill(cache, tree, prompt, max_new=0):
    """Admit + fake-prefill + insert one prompt; returns the slot."""
    got = tree.try_admit(prompt, max_new)
    assert got is not None
    slot, _ = got
    cache.commit_prefill(slot, len(prompt))
    tree.insert(prompt, cache.page_table[slot])
    return slot


def test_tree_match_insert_roundtrip_and_cap():
    c = _cache(num_slots=2, page_size=4, pages_per_slot=4)
    t = PrefixCache(c)
    prompt = tuple(range(1, 11))  # 10 tokens, page 4 -> 2 full pages
    s = _fill(c, t, prompt)
    expect = [int(p) for p in c.page_table[s][:2]]
    matched, pages = t.match(prompt[:8])
    assert matched == 8 and pages == expect
    # non-page-aligned query: only whole blocks match
    matched, pages = t.match(prompt[:7])
    assert matched == 4 and pages == expect[:1]
    # the admission cap is STRICTLY below the prompt length: a request
    # whose prompt the tree fully covers still prefills >= 1 token
    assert t._match_cap(8) == 4 and t._match_cap(9) == 8
    c.free(s)
    got = t.try_admit(prompt, 0)
    assert got is not None and got[1] == 8  # both full pages re-hit


def test_tree_insert_split_on_divergence():
    """Two prompts sharing one page then diverging: insertion splits the
    existing 2-page edge at the page boundary inside it, and both leaves
    stay matchable.  Non-page-aligned tails are never cached."""
    c = _cache(num_slots=3, page_size=4, pages_per_slot=4)
    t = PrefixCache(c)
    pa = (1, 2, 3, 4, 5, 6, 7, 8, 9)  # 2 full pages + 1-token tail
    pb = (1, 2, 3, 4, 9, 9, 9, 9, 1)  # shares page 0, diverges in page 1
    sa = _fill(c, t, pa)
    assert t.node_count() == 1  # one 2-page edge
    sb = _fill(c, t, pb)
    # split: shared [1,2,3,4] node + two divergent leaves
    assert t.node_count() == 3
    ma, pga = t.match(pa[:8])
    mb, pgb = t.match(pb[:8])
    assert ma == 8 and mb == 8
    assert pga[0] == pgb[0]  # the shared first page IS shared
    assert pga[1] != pgb[1]
    # the 9th token of either prompt lives in the slot's private tail
    # page, never in the tree: a 9-token match still returns 2 pages
    assert t.match(pa)[0] == 8


def test_tree_dedup_insert_existing_page_wins():
    c = _cache(num_slots=2, page_size=4, pages_per_slot=4)
    t = PrefixCache(c)
    prompt = tuple(range(1, 9))
    s0 = _fill(c, t, prompt)
    first = [int(p) for p in c.page_table[s0][:2]]
    s1 = _fill(c, t, prompt)  # same prompt again: adopts NOTHING new
    assert t.retained_pages == 2
    assert t.match(prompt[:8])[1] == first
    c.free(s0), c.free(s1)
    assert t.evictable_pages() == 2


def test_tree_lru_eviction_deterministic():
    """Eviction order is (last_use, seq) over unreferenced leaves — a pure
    function of the admission history, identical on every rank."""
    def build():
        c = _cache(num_slots=3, page_size=4, pages_per_slot=2, num_pages=None)
        t = PrefixCache(c)
        slots = [
            _fill(c, t, (i + 1, i + 2, i + 3, i + 4)) for i in range(3)
        ]
        for s in slots:
            c.free(s)
        t.match((1, 2, 3, 4))  # bump prompt 0's leaf: now the LRU is prompt 1
        return c, t

    (c1, t1), (c2, t2) = build(), build()
    assert c1.fingerprint() == c2.fingerprint()
    freed1 = t1.evict(1)
    freed2 = t2.evict(1)
    assert freed1 == freed2 == 1
    assert c1.fingerprint() == c2.fingerprint()
    # the LRU victim was prompt 1 (never re-touched): 0 and 2 still match
    assert t1.match((1, 2, 3, 4))[0] == 4
    assert t1.match((2, 3, 4, 5))[0] == 0
    assert t1.match((3, 4, 5, 6))[0] == 4


def test_tree_evict_never_frees_referenced_page():
    c = _cache(num_slots=2, page_size=4, pages_per_slot=2)
    t = PrefixCache(c)
    s0 = _fill(c, t, (1, 2, 3, 4))
    # the slot still maps the page (refcount 2): not evictable at all
    assert t.evictable_pages() == 0
    assert t.evict(1) == 0
    assert t.match((1, 2, 3, 4))[0] == 4
    c.free(s0)
    assert t.evictable_pages() == 1
    assert t.evict(1) == 1


def test_tree_max_pages_cap_evicts_lru_to_fit():
    c = _cache(num_slots=3, page_size=4, pages_per_slot=2)
    t = PrefixCache(c, max_pages=1)
    s0 = _fill(c, t, (1, 2, 3, 4))
    c.free(s0)
    assert t.retained_pages == 1
    s1 = _fill(c, t, (5, 6, 7, 8))  # cap: must evict the first leaf
    c.free(s1)
    assert t.retained_pages == 1
    assert t.match((1, 2, 3, 4))[0] == 0
    assert t.match((5, 6, 7, 8))[0] == 4


def test_tree_insert_cap_eviction_protects_attach_path():
    """Regression: insert()'s cap-driven eviction must never detach the
    node the new leaf is about to attach to.  A PRIVATE admission (plain
    alloc, no alloc_shared) does not pin the walked path with slot
    references, so once the path's leaf is evicted the attach node itself
    becomes a childless evictable leaf — without protection the new edge
    would hang off a DETACHED node: unmatchable, unevictable, its
    retained pages leaked from the tree forever."""
    c = _cache(num_slots=2, page_size=4, pages_per_slot=3)
    t = PrefixCache(c, max_pages=2)
    s0 = _fill(c, t, (1, 2, 3, 4, 5, 6, 7, 8))
    c.free(s0)  # the whole cached path is tree-only (unpinned)
    pb = (1, 2, 3, 4, 9, 9, 9, 9, 8, 8, 8, 8)
    s1 = c.alloc(len(pb), 0)  # private pages: the slot pins nothing cached
    c.commit_prefill(s1, len(pb))
    t.insert(pb, c.page_table[s1])  # splits, then must evict 2 under cap
    c.free(s1)
    # the attach node survived: the shared first page still matches and
    # the newly adopted block chains off it
    assert t.match((1, 2, 3, 4))[0] == 4
    assert t.match(pb[:8])[0] == 8
    assert t.retained_pages <= t.max_pages
    # every retained page is reachable from the root (nothing leaked)
    reach, stack = 0, [t.root]
    while stack:
        n = stack.pop()
        reach += len(n.pages)
        stack.extend(n.children.values())
    assert reach == t.retained_pages == 2


def test_cache_reset_drops_tree_references_too():
    """Regression: a driver that resets the cache while DISCARDING its
    PrefixCache must get the whole pool back — the dead
    tree's retained pages may not leak out of the pool permanently."""
    c = _cache(num_slots=2, page_size=4, pages_per_slot=4)
    t = PrefixCache(c)
    _fill(c, t, tuple(range(1, 9)))
    c.reset()  # tree discarded with it
    assert c.free_page_count() == c.num_pages - 1
    assert int(c._page_refs.sum()) == 0


def test_tree_reset_releases_every_retained_page():
    c = _cache(num_slots=2, page_size=4, pages_per_slot=4)
    t = PrefixCache(c)
    s = _fill(c, t, tuple(range(1, 9)))
    c.free(s)
    assert c.free_page_count() < c.num_pages - 1
    t.reset()
    assert t.retained_pages == 0 and t.node_count() == 0
    assert c.free_page_count() == c.num_pages - 1


def test_try_admit_evicts_to_cover_fresh_remainder():
    """A full pool with unreferenced cached leaves still admits: the tree
    evicts its own LRU leaves (matched pages protected) to free pages."""
    c = _cache(num_slots=2, page_size=4, pages_per_slot=2, num_pages=5)
    # pool: pages 1..4 usable (page 0 reserved)
    t = PrefixCache(c)
    s0 = _fill(c, t, (1, 2, 3, 4, 5, 6, 7, 8))  # 2 pages, both cached
    c.free(s0)
    # a DIFFERENT 8-token prompt needs 2 pages; only 2 free + 2 cached.
    # It matches nothing, so both cached leaves may be evicted if needed.
    got = t.try_admit((9, 9, 9, 9, 8, 8, 8, 8), 0)
    assert got is not None and got[1] == 0
    c.free(got[0])
    # and a prompt sharing the ORIGINAL prefix must not evict what it
    # matched (protect=) — when the tree still holds it
    t.reset()
    s0 = _fill(c, t, (1, 2, 3, 4, 5, 6, 7, 8))
    c.free(s0)
    got = t.try_admit((1, 2, 3, 4, 9, 9, 9, 9), 0)
    assert got is not None and got[1] == 4
    slot = got[0]
    assert int(c.page_table[slot][0]) == t.match((1, 2, 3, 4))[1][0]


# ============================================== engine + loop bit-equality
def _build_rig(params, mesh, page_size=4, num_slots=2, pages_per_slot=4,
               prefix=False, max_pages=None):
    kc = KVCacheConfig(
        layers=CFG.num_hidden_layers, kv_heads=CFG.num_key_value_heads,
        head_dim=CFG.head_dim, num_slots=num_slots, page_size=page_size,
        pages_per_slot=pages_per_slot,
    )
    cache = PagedKVCache(kc, mesh)
    eng = ServeEngine(CFG, mesh, params, cache)
    pc = PrefixCache(cache, max_pages=max_pages) if prefix else None
    sched = ContinuousBatchingScheduler(cache, max_queue=16, prefix_cache=pc)
    return eng, cache, sched, pc


def _shared_arrivals(n=5, plen_shared=6, max_new=4):
    rng = np.random.default_rng(7)
    shared = tuple(int(x) for x in rng.integers(1, 60, plen_shared))
    out = []
    for i in range(n):
        tail = tuple(int(x) for x in rng.integers(1, 60, 1 + i % 3))
        out.append((2 * i, Request(rid=i, prompt=shared + tail,
                                   max_new_tokens=max_new)))
    return out


def _run(eng, sched, arrivals, **kw):
    res = run_serve_resilient(
        engine=eng, scheduler=sched, arrivals=arrivals,
        install_signal_handlers=False, coordinate=False, **kw,
    )
    sched.ledger_check()
    return res


@pytest.mark.parametrize("page_size", [4, 5])  # incl. non-pow2
def test_loop_prefix_cache_tokens_bit_identical(model_and_params, tp2_mesh, page_size):
    _, params = model_and_params
    arrivals = _shared_arrivals()
    eng, _, sched, _ = _build_rig(params, tp2_mesh, page_size=page_size)
    golden = _run(eng, sched, arrivals)
    assert all(o["status"] == "completed" for o in golden.outcomes.values())
    eng2, _, sched2, pc = _build_rig(params, tp2_mesh, page_size=page_size,
                                     prefix=True)
    res = _run(eng2, sched2, arrivals)
    for rid, o in res.outcomes.items():
        assert o["tokens"] == golden.outcomes[rid]["tokens"], rid
    # the shared system prompt actually hit (admissions after the first)
    assert pc.stats.hit_tokens > 0
    assert pc.stats.hits >= 1
    # the scheduler counted the hits as in-flight records
    assert sched2.counts["completed"] == len(arrivals)


def test_loop_same_boundary_hit_admissions_never_corrupt_shared_pages(
        model_and_params, tp2_mesh):
    """Regression: two prefix-HIT requests admitted in the SAME boundary.
    While the first one's suffix prefill runs (a multi-token step over all
    slots — static shapes), the second slot is allocated with SHARED pages
    already mapped but length still 0: its lane of the batched write must
    land in the null page, not scatter garbage into the shared prefix
    everyone else reads."""
    _, params = model_and_params
    rng = np.random.default_rng(13)
    shared = tuple(int(x) for x in rng.integers(1, 60, 8))
    arrivals = [(0, Request(rid=0, prompt=shared + (7,), max_new_tokens=2))]
    # rid 1 and 2 arrive TOGETHER after rid 0 freed both slots: both hit,
    # both admitted at one boundary
    arrivals += [
        (6, Request(rid=i, prompt=shared + (10 + i, 20 + i), max_new_tokens=3))
        for i in (1, 2)
    ]
    eng, _, sched, _ = _build_rig(params, tp2_mesh)
    golden = _run(eng, sched, arrivals)
    eng2, _, sched2, pc = _build_rig(params, tp2_mesh, prefix=True)
    res = _run(eng2, sched2, arrivals)
    assert pc.stats.hits >= 2  # both simultaneous admissions actually hit
    for rid, o in res.outcomes.items():
        assert o["tokens"] == golden.outcomes[rid]["tokens"], rid


def test_loop_prefix_replay_rehits_after_oom(model_and_params, tp2_mesh):
    """Satellite: an oom eviction of a slot whose prefix pages are SHARED
    must not free them (the tree + peer slots still hold references), the
    victim's replay must RE-HIT the cache, and the whole faulted history
    stays deterministic (two identical faulted runs agree on every digest
    — the rank-identical surface the 2-proc smoke exchanges)."""
    _, params = model_and_params
    arrivals = _shared_arrivals(n=4, max_new=4)
    eng, _, sched, _ = _build_rig(params, tp2_mesh)
    golden = _run(eng, sched, arrivals)

    def faulted():
        faultsim.arm(faultsim.parse_schedule("oom:step=5"))
        try:
            eng2, cache2, sched2, pc = _build_rig(params, tp2_mesh, prefix=True)
            res = _run(eng2, sched2, arrivals)
        finally:
            faultsim.disarm()
        return res, cache2, sched2, pc

    res_a, cache_a, sched_a, pc_a = faulted()
    res_b, cache_b, sched_b, pc_b = faulted()
    assert res_a.counts["evicted"] >= 1
    # no page was lost or double-freed: every page's refcount is exactly
    # its holder count (all slots freed at exit -> only tree refs remain)
    refs = cache_a._page_refs
    assert (refs >= 0).all()
    assert int(refs.sum()) == pc_a.retained_pages
    # the replay re-hit the tree: at least one hit beyond the golden
    # admission count's worth
    assert pc_a.stats.hits >= 2
    assert any(o["replays"] == 1 for o in res_a.outcomes.values())
    # completed tokens bit-identical to plain golden, through the replay
    for rid, o in res_a.outcomes.items():
        if o["status"] == "completed":
            assert o["tokens"] == golden.outcomes[rid]["tokens"], rid
    # determinism: the two faulted histories agree on EVERY digest
    assert cache_a.fingerprint() == cache_b.fingerprint()
    assert sched_a.fingerprint() == sched_b.fingerprint()
    assert pc_a.stats.hit_tokens == pc_b.stats.hit_tokens


@pytest.mark.parametrize("k", [1, 4, 8])
def test_loop_speculative_bit_identical(model_and_params, tp2_mesh, k):
    """Greedy acceptance: the emitted stream with a (weak) reduced-depth
    drafter is BITWISE the plain-decode stream for every k — the drafter
    only changes how many verify launches it takes."""
    _, params = model_and_params
    arrivals = _shared_arrivals(max_new=5)
    eng, _, sched, _ = _build_rig(params, tp2_mesh)
    golden = _run(eng, sched, arrivals)
    eng2, _, sched2, _ = _build_rig(params, tp2_mesh)
    spec = SpeculativeDecoder(eng2, slice_drafter_params(params, 1),
                              drafter_layers=1, k=k)
    res = _run(eng2, sched2, arrivals, speculative=spec)
    for rid, o in res.outcomes.items():
        assert o["tokens"] == golden.outcomes[rid]["tokens"], (k, rid)
    assert spec.verify_steps > 0
    assert spec.drafted > 0
    assert 0 <= (spec.accept_rate() or 0.0) <= 1.0


@pytest.mark.parametrize("path", ["speculative", "prefix_hit"])
def test_loop_paths_that_need_the_hosts_token_read_their_prefill_at_once(model_and_params, tp2_mesh, path):
    """A drafter drafts from tokens on the host, and a prefix hit's suffix comes off ``decode_multi`` as a
    row: neither leaves a first token on the device for the decode step behind it (a plain prefill under
    a prefix cache, with a step in flight, does), and the streams stay the plain loop's."""
    from vescale_tpu.serve import DecodeFeed, PrefillStep

    _, params = model_and_params
    arrivals = _shared_arrivals(max_new=5)
    eng, _, sched, _ = _build_rig(params, tp2_mesh)
    golden = _run(eng, sched, arrivals)
    eng2, _, sched2, pc = _build_rig(params, tp2_mesh, prefix=path == "prefix_hit")
    spec = SpeculativeDecoder(eng2, slice_drafter_params(params, 1), drafter_layers=1, k=4) if path == "speculative" else None
    decode, suffix, fed, hits = eng2.decode, eng2.prefill_suffix, [], []

    def noted_decode(tokens):
        fed.append(dict(tokens.fresh) if isinstance(tokens, DecodeFeed) else None)
        return decode(tokens)

    def noted_suffix(prompt, slot, matched):
        hits.append(slot)
        return suffix(prompt, slot, matched)

    eng2.decode, eng2.prefill_suffix = noted_decode, noted_suffix
    res = _run(eng2, sched2, arrivals, speculative=spec)
    assert {rid: o["tokens"] for rid, o in res.outcomes.items()} == {rid: o["tokens"] for rid, o in golden.outcomes.items()}
    unread = [f for fresh in fed if fresh for f in fresh.values() if isinstance(f, PrefillStep)]
    assert eng2.prefill_reads_ahead == len(unread)
    if path == "speculative":
        assert not unread and all(fresh is None for fresh in fed), "every step of a drafter's loop starts from the host's tokens"
    else:
        assert hits and eng2.prefill_launches + len(hits) == len(arrivals)
        assert len(unread) <= eng2.prefill_launches - 1 and eng.prefill_reads_ahead == len(arrivals) - 1


def test_loop_spec_plus_prefix_under_fault_battery(model_and_params, tp2_mesh):
    """The acceptance criterion: BOTH multipliers on, full fault battery —
    completed token streams bit-identical to the plain golden run, ledger
    balanced, eviction during shared-page life safe."""
    _, params = model_and_params
    arrivals = _shared_arrivals(n=5, max_new=4)
    eng, _, sched, _ = _build_rig(params, tp2_mesh)
    golden = _run(eng, sched, arrivals)
    faultsim.arm(faultsim.parse_schedule(
        "oom:step=5;request_timeout:step=6;slow_decode:step=3"
    ))
    try:
        eng2, _, sched2, pc = _build_rig(params, tp2_mesh, prefix=True)
        spec = SpeculativeDecoder(eng2, slice_drafter_params(params, 1),
                                  drafter_layers=1, k=4)
        res = _run(eng2, sched2, arrivals, speculative=spec)
    finally:
        faultsim.disarm()
    assert res.counts["evicted"] >= 1 and res.counts["timed_out"] >= 1
    for rid, o in res.outcomes.items():
        if o["status"] == "completed":
            assert o["tokens"] == golden.outcomes[rid]["tokens"], rid
    assert pc.stats.hit_tokens > 0 and spec.drafted > 0


def test_engine_decode_multi_matches_sequential(model_and_params, tp2_mesh):
    """The batched multi-token verify step scores a window exactly like
    sequential single-token decode steps would (argmax surface)."""
    _, params = model_and_params
    kc_kw = dict(page_size=4, num_slots=2, pages_per_slot=4)
    prompt = (5, 9, 17, 3, 44)
    window = (7, 11, 2)

    # sequential: feed window tokens one at a time
    eng, cache, _, _ = _build_rig(params, tp2_mesh, **kc_kw)
    slot = cache.alloc(len(prompt), 8)
    eng.prefill(prompt, slot)
    cache.commit_prefill(slot, len(prompt))
    seq_argmax = []
    for tok in window:
        t = [0] * cache.num_slots
        t[slot] = tok
        lg = eng.decode(t)
        cache.advance(slot)
        seq_argmax.append(int(np.argmax(lg[slot])))

    # batched: the same window in ONE decode_multi call
    eng2, cache2, _, _ = _build_rig(params, tp2_mesh, **kc_kw)
    slot2 = cache2.alloc(len(prompt), 8)
    eng2.prefill(prompt, slot2)
    cache2.commit_prefill(slot2, len(prompt))
    toks = np.zeros((cache2.num_slots, len(window)), np.int32)
    toks[slot2] = window
    lg = eng2.decode_multi(toks)
    multi_argmax = [int(np.argmax(lg[slot2, i])) for i in range(len(window))]
    assert multi_argmax == seq_argmax


# ==================================================== speculative plumbing
def test_spec_accept_budget_eos_and_self_correction():
    class _Eng:  # accept() only reads k
        pass

    spec = SpeculativeDecoder.__new__(SpeculativeDecoder)
    spec.k = 4
    V = 8
    greedy = [3, 5, 1, 2, 7]  # target argmax at the 5 window positions

    def logits_for(seq):
        out = np.full((len(seq), V), -1.0, np.float32)
        for i, t in enumerate(seq):
            out[i, t] = 1.0
        return out

    lg = logits_for(greedy)
    # full acceptance: drafts == greedy -> k accepted + the bonus token
    emitted, acc = spec.accept(np.array(greedy[:4]), lg, budget=10, eos_id=None)
    assert emitted == greedy and acc == 4
    # first divergence cuts: 2 accepted + the target's own correction
    emitted, acc = spec.accept(np.array([3, 5, 9, 9]), lg, budget=10, eos_id=None)
    assert emitted == [3, 5, 1] and acc == 2
    # garbage drafts (an undrafted slot) still emit the target's token
    emitted, acc = spec.accept(np.array([0, 0, 0, 0]), lg, budget=10, eos_id=None)
    assert emitted == [3] and acc == 0
    # budget clamps the emission (and the accepted count with it)
    emitted, acc = spec.accept(np.array(greedy[:4]), lg, budget=2, eos_id=None)
    assert emitted == greedy[:2] and acc == 2
    # EOS cuts mid-window
    emitted, acc = spec.accept(np.array(greedy[:4]), lg, budget=10, eos_id=5)
    assert emitted == [3, 5]


def test_drafter_config_and_slice_validation():
    dc = drafter_config(CFG, 1)
    assert dc.num_hidden_layers == 1
    with pytest.raises(ValueError):
        drafter_config(CFG, 0)
    with pytest.raises(ValueError):
        drafter_config(CFG, CFG.num_hidden_layers + 1)


def test_slice_drafter_params_keeps_shared_and_first_layers(model_and_params):
    _, params = model_and_params
    sliced = slice_drafter_params(params, 1)
    assert "layers_0" in sliced and "layers_1" not in sliced
    assert "embed_tokens" in sliced and "norm" in sliced
    with pytest.raises(ValueError):
        slice_drafter_params({"embed_tokens": {}}, 1)


def test_drafter_template_names_only_drafter_chunks(tp2_mesh):
    """The params-only restore contract: the template names exactly the
    reduced-depth subtree, so checkpoint.load never reads deeper layers
    (or the optimizer)."""
    tpl = drafter_template(CFG, tp2_mesh.jax_mesh, 1)
    assert "layers_0" in tpl and "layers_1" not in tpl
    assert "embed_tokens" in tpl and "lm_head" in tpl


def test_spec_bad_k_and_layers_raise(model_and_params, tp2_mesh):
    _, params = model_and_params
    eng, _, _, _ = _build_rig(params, tp2_mesh)
    with pytest.raises(ValueError):
        SpeculativeDecoder(eng, slice_drafter_params(params, 1),
                           drafter_layers=1, k=0)


# ======================================================= obs / env / wiring
def test_router_v3_rates_live(model_and_params, tp2_mesh):
    from vescale_tpu.serve import ServeObservability
    from vescale_tpu.serve.obs import ROUTER_FIELDS

    _, params = model_and_params
    arrivals = _shared_arrivals()
    eng, _, sched, pc = _build_rig(params, tp2_mesh, prefix=True)
    spec = SpeculativeDecoder(eng, slice_drafter_params(params, 1),
                              drafter_layers=1, k=2)
    _run(eng, sched, arrivals, speculative=spec)
    obs = ServeObservability(sched, engine=eng, rank=0, speculative=spec)
    feed = json.loads(json.dumps(obs.router()))
    assert set(feed) == set(ROUTER_FIELDS)
    assert feed["prefix_hit_rate"] == pytest.approx(pc.stats.hit_rate())
    assert feed["prefix_hit_rate"] > 0
    assert feed["spec_accept_rate"] == pytest.approx(spec.accept_rate() or 0.0)


def test_fleet_replica_row_carries_warmth_fields():
    from vescale_tpu.serve.obs import (
        FLEET_REPLICA_FIELDS,
        FLEET_REPLICA_FIELDS_V1,
        FLEET_REPLICA_FIELDS_V2,
    )

    assert FLEET_REPLICA_FIELDS_V1 < FLEET_REPLICA_FIELDS_V2 < FLEET_REPLICA_FIELDS
    assert set(FLEET_REPLICA_FIELDS_V2) - set(FLEET_REPLICA_FIELDS_V1) == {
        "prefix_hit_rate", "spec_accept_rate",
    }


def test_env_knobs_registered():
    from vescale_tpu.analysis import envreg

    for name in (
        "VESCALE_SERVE_PREFIX_CACHE",
        "VESCALE_SERVE_PREFIX_CACHE_PAGES",
        "VESCALE_SPEC_K",
        "VESCALE_SPEC_DRAFTER_LAYERS",
    ):
        assert envreg.lookup(name) is not None
    assert envreg.get_int("VESCALE_SPEC_K") >= 1


def test_scheduler_builds_prefix_cache_from_env(monkeypatch, tp2_mesh):
    monkeypatch.setenv("VESCALE_SERVE_PREFIX_CACHE", "1")
    cache = _cache(mesh=tp2_mesh)
    sched = ContinuousBatchingScheduler(cache, max_queue=4)
    assert sched.prefix is not None and sched.prefix.cache is cache
    monkeypatch.delenv("VESCALE_SERVE_PREFIX_CACHE")
    sched2 = ContinuousBatchingScheduler(cache, max_queue=4)
    assert sched2.prefix is None


def test_telemetry_counts_prefix_and_spec(model_and_params, tp2_mesh):
    from vescale_tpu import telemetry

    _, params = model_and_params
    arrivals = _shared_arrivals()
    telemetry.init(out_dir=None, memtrack=False)
    try:
        eng, _, sched, pc = _build_rig(params, tp2_mesh, prefix=True)
        spec = SpeculativeDecoder(eng, slice_drafter_params(params, 1),
                                  drafter_layers=1, k=2)
        _run(eng, sched, arrivals, speculative=spec)
        snap = telemetry.get_registry().snapshot()
        counters, gauges = snap["counters"], snap["gauges"]
        assert counters["serve_prefix_hit_tokens_total"] == pc.stats.hit_tokens
        assert counters["serve_prefix_hits_total"] == pc.stats.hits
        assert counters["serve_spec_drafted_tokens_total"] == spec.drafted
        assert counters["serve_spec_accepted_tokens_total"] == spec.accepted
        assert counters["serve_spec_verify_steps_total"] == spec.verify_steps
        assert gauges["serve_prefix_hit_rate"] == pytest.approx(pc.stats.hit_rate())
        # goodput still counts only completed requests' (accepted) tokens
        assert counters["serve_goodput_tokens_total"] == sched.goodput_tokens
    finally:
        telemetry.shutdown()


def test_spec_draft_verify_spans_emitted(model_and_params, tp2_mesh):
    from vescale_tpu.ndtimeline import api as nd_api
    from vescale_tpu.ndtimeline import predefined as _p
    from vescale_tpu.serve import reqtrace

    _, params = model_and_params
    arrivals = _shared_arrivals(n=2)
    old_mgr, old_active = nd_api._MANAGER, nd_api._ACTIVE
    nd_api.init_ndtimers(rank=0)
    try:
        eng, _, sched, _ = _build_rig(params, tp2_mesh, prefix=True)
        spec = SpeculativeDecoder(eng, slice_drafter_params(params, 1),
                                  drafter_layers=1, k=2)
        res = _run(eng, sched, arrivals, speculative=spec)
        spans = nd_api.get_manager().tail(100_000)
        drafts = [s for s in spans if s.metric == _p.SERVE_DRAFT]
        verifies = [s for s in spans if s.metric == _p.SERVE_VERIFY]
        assert len(drafts) == len(verifies) == spec.verify_steps
        assert all("accept_rate" in s.tags or s.tags["drafted"] == 0
                   for s in verifies)
        # the request chains stay ledger-matched with speculation on
        assert reqtrace.verify_request_chains(spans, res.outcomes) == []
    finally:
        nd_api._MANAGER, nd_api._ACTIVE = old_mgr, old_active


# ============================================================ smoke wiring
def test_spec_prefix_smoke_script():
    """tier-1 wiring of scripts/spec_prefix_smoke.py: the 2-proc gloo
    serve battery with caching+speculation ON vs the plain-decode golden
    run — completed tokens bit-identical, ledgers balanced, prefill-token
    savings and acceptance rate measured."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "spec_prefix_smoke.py")],
        capture_output=True,
        text=True,
        timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout[-3000:]}\nstderr:\n{out.stderr[-3000:]}"
    assert "SPEC PREFIX SMOKE OK" in out.stdout
