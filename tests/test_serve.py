"""Serving subsystem tests — paged KV cache, continuous batching, the
resilient serve loop, and the train->serve checkpoint handoff (ISSUE 10),
plus the tier-1 wiring of scripts/serve_smoke.py (2-proc gloo proof) and
of the shared gloo-rig port registry (the PR-9 flake fix)."""

import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import vescale_tpu.checkpoint as ckpt
from vescale_tpu.mesh import DeviceMesh
from vescale_tpu.models.llama import Llama, LlamaConfig
from vescale_tpu.placements import Replicate
from vescale_tpu.resilience import faultsim
from vescale_tpu.serve import (
    ContinuousBatchingScheduler,
    KVCacheConfig,
    KVCacheOutOfPages,
    PagedKVCache,
    Request,
    ServeEngine,
    load_params,
    run_serve_resilient,
)

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


def _cache(num_slots=2, page_size=4, pages_per_slot=4, mesh=None, **kw):
    kc = KVCacheConfig(
        layers=CFG.num_hidden_layers,
        kv_heads=CFG.num_key_value_heads,
        head_dim=CFG.head_dim,
        num_slots=num_slots,
        page_size=page_size,
        pages_per_slot=pages_per_slot,
    )
    return PagedKVCache(kc, mesh if mesh is not None else DeviceMesh(("tp",), (2,)), **kw)


# ================================================================= kv cache
def test_kv_cache_geometry_and_null_page():
    c = _cache(num_slots=3, page_size=4, pages_per_slot=2)
    assert c.max_seq_len == 8
    # page 0 is reserved: never in the free pool, never allocated
    assert 0 not in c._free_pages
    assert c.free_page_count() == c.num_pages - 1
    s = c.alloc(3, 2)  # 5 tokens -> 2 pages
    assert 0 not in set(c.page_table[s][: int(c._pages_held[s])])
    assert c.free_page_count() == c.num_pages - 3


def test_kv_cache_alloc_free_roundtrip_deterministic():
    a, b = _cache(num_slots=3), _cache(num_slots=3)
    for c in (a, b):
        s0 = c.alloc(4, 4)
        s1 = c.alloc(4, 4)
        c.commit_prefill(s0, 4)
        c.advance(s0)
        c.free(s1)
        c.alloc(2, 2)
    assert a.fingerprint() == b.fingerprint()
    assert np.array_equal(a.page_table, b.page_table)
    assert np.array_equal(a.lengths, b.lengths)


def test_kv_cache_fingerprint_tracks_history():
    a, b = _cache(), _cache()
    assert a.fingerprint() == b.fingerprint()
    a.alloc(4, 0)
    assert a.fingerprint() != b.fingerprint()
    # same END state via a different history must still differ (the digest
    # is the decision log, not the table bytes)
    s = b.alloc(4, 0)
    b.free(s)
    b.alloc(4, 0)
    assert a.fingerprint() != b.fingerprint()


def test_kv_cache_capacity_errors():
    c = _cache(num_slots=1, page_size=4, pages_per_slot=2)
    assert not c.can_admit(4, 8)  # 12 tokens > max_seq_len 8
    with pytest.raises(KVCacheOutOfPages):
        c.alloc(4, 8)
    s = c.alloc(4, 4)
    assert not c.can_admit(1, 0)  # no slot left
    c.commit_prefill(s, 4)
    for _ in range(4):
        c.advance(s)
    with pytest.raises(KVCacheOutOfPages):
        c.advance(s)  # slot full
    c.free(s)
    assert c.can_admit(4, 4)


def test_kv_cache_reset_returns_everything():
    c = _cache(num_slots=2)
    c.alloc(4, 0)
    c.alloc(4, 0)
    c.reset()
    assert c.free_slot_count() == 2
    assert c.free_page_count() == c.num_pages - 1
    assert int(c.lengths.sum()) == 0


def test_kv_cache_kv_head_divisibility():
    kc = KVCacheConfig(layers=1, kv_heads=3, head_dim=4)
    with pytest.raises(ValueError, match="divisible"):
        PagedKVCache(kc, DeviceMesh(("tp",), (2,)))


# ================================================================ scheduler
def _req(rid, plen=3, **kw):
    kw.setdefault("max_new_tokens", 4)
    return Request(rid=rid, prompt=tuple(range(1, plen + 1)), **kw)


def test_scheduler_fifo_admit_and_bounded_queue():
    sched = ContinuousBatchingScheduler(_cache(num_slots=2), max_queue=2)
    accepted = [sched.submit(_req(rid), step=0) for rid in range(5)]
    # queue bound is 2: the first two queue, the rest shed immediately
    assert accepted == [True, True, False, False, False]
    for rid in (2, 3, 4):
        out = sched.outcomes[rid]
        assert out["status"] == "shed" and out["retry_after_s"] > 0
    admitted = sched.admit(step=0)
    assert [i.req.rid for i in admitted] == [0, 1]  # FIFO
    assert not sched.queue
    # queue drained by admission -> new submissions are accepted again
    assert sched.submit(_req(9), step=1)


def test_scheduler_shed_is_terminal_and_counted():
    sched = ContinuousBatchingScheduler(_cache(num_slots=1), max_queue=1)
    assert sched.submit(_req(0), 0)
    assert not sched.submit(_req(1), 0)  # queue full (slot fill happens at admit)
    assert sched.outcomes[1]["status"] == "shed"
    assert sched.counts["shed"] == 1
    with pytest.raises(ValueError, match="duplicate"):
        sched.submit(_req(0), 0)


def test_scheduler_shed_request_can_resubmit():
    """The retry_after_s contract: a shed (or timed-out) request MAY come
    back with the same rid; the new attempt supersedes the prior terminal
    outcome and the ledger still balances."""
    sched = ContinuousBatchingScheduler(_cache(num_slots=1), max_queue=1)
    assert sched.submit(_req(0), 0)
    assert not sched.submit(_req(1), 0)  # shed: queue full
    assert sched.outcomes[1]["status"] == "shed"
    sched.admit(0)  # drain the queue so the retry has room
    assert sched.submit(_req(1), 3)  # same rid, accepted now
    assert 1 not in sched.outcomes  # prior terminal outcome superseded
    assert sched.counts["resubmitted"] == 1
    # still-pending duplicates stay rejected
    with pytest.raises(ValueError, match="pending"):
        sched.submit(_req(1), 4)


def test_scheduler_slo_shedding():
    sched = ContinuousBatchingScheduler(_cache(), max_queue=8, slo_ttft_s=0.01)
    for _ in range(64):
        sched.observe_ttft(0.5)  # sustained p99 far over the 10ms SLO
    assert not sched.submit(_req(7), 0)
    assert "SLO" in sched.outcomes[7]["reason"]


def test_scheduler_requeue_newest_replays():
    sched = ContinuousBatchingScheduler(_cache(num_slots=2), max_queue=4)
    sched.submit(_req(0), 0)
    sched.submit(_req(1), 1)
    sched.admit(0)
    first = sched.admit(1)  # rid 1 admitted later
    victim = sched.requeue_newest(reason="oom")
    assert victim == 1
    assert sched.outcomes[1]["status"] == "evicted_replay"
    re = sched.admit(2)
    assert [i.req.rid for i in re] == [1]
    assert re[0].replays == 1
    assert 1 not in {rid for rid, o in sched.outcomes.items()}  # marker consumed


def test_scheduler_queue_deadline_and_reject():
    sched = ContinuousBatchingScheduler(_cache(num_slots=1), max_queue=8)
    sched.submit(_req(0), 0)
    sched.submit(_req(1, deadline_steps=2), 0)
    sched.admit(0)
    assert sched.timeout_queued(step=5) == [1]
    assert sched.outcomes[1]["status"] == "timed_out"
    sched.submit(_req(2), 5)
    assert sched.reject_queued("preempted") == [2]
    assert sched.outcomes[2]["status"] == "preempted_requeue"
    assert sched.outcomes[2]["retry_after_s"] > 0


def test_scheduler_fingerprint_diverges_with_decisions():
    a = ContinuousBatchingScheduler(_cache(), max_queue=4)
    b = ContinuousBatchingScheduler(_cache(), max_queue=4)
    for s in (a, b):
        s.submit(_req(0), 0)
    assert a.fingerprint() == b.fingerprint()
    b.submit(_req(1), 0)
    assert a.fingerprint() != b.fingerprint()


# ================================================================== engine
def _gen_tokens(engine, cache, prompt, n):
    slot = cache.alloc(len(prompt), n)
    logits = engine.prefill(prompt, slot)
    cache.commit_prefill(slot, len(prompt))
    toks = [engine.greedy(logits)]
    for _ in range(n - 1):
        t = [0] * cache.num_slots
        t[slot] = toks[-1]
        lg = engine.decode(t)
        cache.advance(slot)
        toks.append(engine.greedy(lg[slot]))
    cache.free(slot)
    return toks


def _reference_tokens(model, params, prompt, n):
    seq = list(prompt)
    out = []
    for _ in range(n):
        lg = model.apply({"params": params}, jnp.asarray([seq], jnp.int32))
        t = int(np.argmax(np.asarray(lg)[0, -1]))
        out.append(t)
        seq.append(t)
    return out


def test_engine_paged_decode_matches_full_recompute(model_and_params, tp2_mesh):
    """The serving correctness keystone: prefill-once + paged decode must
    reproduce the exact greedy tokens of recomputing the full prefix with
    the training forward every step."""
    model, params = model_and_params
    cache = _cache(mesh=tp2_mesh)
    eng = ServeEngine(CFG, tp2_mesh, params, cache)
    prompt = (5, 9, 17, 3, 44)
    got = _gen_tokens(eng, cache, prompt, 6)
    assert got == _reference_tokens(model, params, prompt, 6)


def _decode_logits(eng, cache, prompt, steps):
    """Logits of ``steps`` teacher-forced decode calls after one prefill."""
    slot = cache.alloc(len(prompt), steps + 1)
    eng.prefill(prompt, slot)
    cache.commit_prefill(slot, len(prompt))
    rows = []
    for i in range(steps):
        t = [0] * cache.num_slots
        t[slot] = 3 + i
        rows.append(eng.decode(t))
        cache.advance(slot)
    return np.stack(rows)


def test_engine_default_decode_leg_off_tpu_is_the_xla_program(model_and_params, tp2_mesh, monkeypatch):
    """With VESCALE_KERNELS unset on a backend that is no TPU the engine
    builds the XLA leg: the decode program and its logits are, byte for
    byte, those of VESCALE_KERNELS=off, and the page counters stay 0."""
    _, params = model_and_params
    prompt, built = (5, 9, 17, 3, 44), {}
    for mode in (None, "off"):
        monkeypatch.delenv("VESCALE_KERNELS", raising=False)
        if mode is not None:
            monkeypatch.setenv("VESCALE_KERNELS", mode)
        cache = _cache(mesh=tp2_mesh)
        eng = ServeEngine(CFG, tp2_mesh, params, cache)
        assert eng.kernel_decode is False
        program = eng._decode_fn.lower(
            eng.params, cache.k.data, cache.v.data, cache.table_array(), cache.lengths_array(),
            np.zeros((cache.num_slots,), np.int32)).as_text()
        built[mode] = (program, _decode_logits(eng, cache, prompt, 4), eng.trace_counters())
    assert built[None][0] == built["off"][0]
    assert "custom_call" not in built[None][0]
    np.testing.assert_array_equal(built[None][1], built["off"][1])
    assert built[None][2]["decode_pages_read"] == built[None][2]["decode_pages_capacity"] == 0
    assert built[None][2]["decode_steps"] == 4


def test_engine_interpreted_kernel_streams_and_page_counters(model_and_params, tp2_mesh, monkeypatch):
    """VESCALE_KERNELS=interpret: the kernel leg (per shard of the
    kv-head-sharded 5-D pool, under the shard_map shim) emits the XLA leg's
    greedy stream, and ``trace_counters`` says how many pages it fetched:
    each slot's pages up to its new token, an inactive slot's one."""
    model, params = model_and_params
    prompt, n = (5, 9, 17, 3, 44), 6
    monkeypatch.setenv("VESCALE_KERNELS", "interpret")
    cache = _cache(num_slots=2, page_size=4, pages_per_slot=4, mesh=tp2_mesh)
    eng = ServeEngine(CFG, tp2_mesh, params, cache)
    assert eng.kernel_decode is True
    assert _gen_tokens(eng, cache, prompt, n) == _reference_tokens(model, params, prompt, n)
    c = eng.trace_counters()
    # decode calls at lengths 5..9 of the one active slot: ceil((len + 1) / 4) pages, + 1 for the idle slot
    assert c["decode_steps"] == n - 1
    assert c["decode_pages_read"] == sum(-(-(length + 1) // 4) + 1 for length in range(5, 5 + n - 1))
    assert c["decode_pages_capacity"] == (n - 1) * 2 * 4
    assert c["decode_pages_read"] <= c["decode_pages_capacity"]
    # a bfloat16 pool whose shard holds one kv head is a layout the kernel does not take: the XLA leg runs
    kc = KVCacheConfig(layers=CFG.num_hidden_layers, kv_heads=2, head_dim=CFG.head_dim, num_slots=2,
                       page_size=4, pages_per_slot=4, dtype=jnp.bfloat16)
    assert ServeEngine(CFG, tp2_mesh, params, PagedKVCache(kc, tp2_mesh)).kernel_decode is False


def test_engine_tokens_invariant_to_page_size_and_slot(model_and_params, tp2_mesh):
    model, params = model_and_params
    prompt = (7, 3, 29)
    baseline = None
    for page_size, pages in ((2, 8), (8, 2)):
        cache = _cache(num_slots=2, page_size=page_size, pages_per_slot=pages, mesh=tp2_mesh)
        eng = ServeEngine(CFG, tp2_mesh, params, cache)
        # churn the pool first so the request lands in a different slot and
        # different physical pages
        s = cache.alloc(4, 4)
        cache.commit_prefill(s, 4)
        cache.free(s)
        toks = _gen_tokens(eng, cache, prompt, 5)
        if baseline is None:
            baseline = toks
        assert toks == baseline, (page_size, toks, baseline)


def test_engine_continuous_batching_interleaved(model_and_params, tp2_mesh):
    """Two requests sharing the decode batch — admitted at different times,
    finishing independently — must each produce their single-request
    reference tokens (slot interference would break both)."""
    model, params = model_and_params
    cache = _cache(num_slots=2, mesh=tp2_mesh)
    eng = ServeEngine(CFG, tp2_mesh, params, cache)
    pa, pb = (5, 9, 17), (40, 2, 33, 8)

    sa = cache.alloc(len(pa), 6)
    la = eng.prefill(pa, sa)
    cache.commit_prefill(sa, len(pa))
    ta = [eng.greedy(la)]
    # one solo decode for A, then B joins the batch
    t = [0, 0]
    t[sa] = ta[-1]
    lg = eng.decode(t)
    cache.advance(sa)
    ta.append(eng.greedy(lg[sa]))

    sb = cache.alloc(len(pb), 6)
    lb = eng.prefill(pb, sb)
    cache.commit_prefill(sb, len(pb))
    tb = [eng.greedy(lb)]
    for _ in range(3):
        t = [0, 0]
        t[sa], t[sb] = ta[-1], tb[-1]
        lg = eng.decode(t)
        cache.advance(sa)
        cache.advance(sb)
        ta.append(eng.greedy(lg[sa]))
        tb.append(eng.greedy(lg[sb]))
    assert ta == _reference_tokens(model, params, pa, 5)
    assert tb == _reference_tokens(model, params, pb, 4)


def test_engine_a_joining_slot_fed_from_its_unread_prefill_decodes_what_the_hosts_token_gives(model_and_params, tp2_mesh):
    """B joins A's batch with a step in flight, on the tp mesh: B's prefill stays on the device
    (``PrefillStep``), the step behind it takes B's first id from there, and both streams are the full
    recompute's; the row and the id read afterwards are the ones a caller that reads at once gets."""
    from vescale_tpu.serve import DecodeFeed, PrefillStep

    model, params = model_and_params
    cache = _cache(num_slots=2, mesh=tp2_mesh)
    eng = ServeEngine(CFG, tp2_mesh, params, cache)
    pa, pb = (5, 9, 17), (40, 2, 33, 8)
    sa = cache.alloc(len(pa), 6)
    first_a = eng.prefill(pa, sa)
    cache.commit_prefill(sa, len(pa))
    assert isinstance(first_a, PrefillStep) and not first_a.read
    ta = [first_a.token]
    t = [0, 0]
    t[sa] = ta[-1]
    step = eng.decode(t)                        # A alone, left in flight
    cache.advance(sa)
    sb = cache.alloc(len(pb), 6)
    first_b = eng.prefill(pb, sb)               # launched behind it, unread
    cache.commit_prefill(sb, len(pb))
    nxt = eng.decode(DecodeFeed(step, {sb: first_b}))
    cache.advance(sa)
    cache.advance(sb)
    assert step.read and not first_b.read and not nxt.read and eng.prefill_reads_ahead == 1
    ta.append(int(step.tokens[sa]))
    tb = [first_b.token]
    assert first_b.token == int(np.argmax(np.asarray(first_b))) and np.asarray(first_b).dtype == np.float32
    for _ in range(2):
        step, nxt = nxt, eng.decode(DecodeFeed(nxt))
        cache.advance(sa)
        cache.advance(sb)
        ta.append(int(step.tokens[sa]))
        tb.append(int(step.tokens[sb]))
    assert ta == _reference_tokens(model, params, pa, 4)
    assert tb == _reference_tokens(model, params, pb, 3)


def test_engine_stage_split_matches_single_stage(model_and_params, tp2_mesh):
    """num_stages=2 splits the layer loop with the pipe engine's cut math;
    the math is unchanged.  (Bitwise until a single-stage engine's prompt went
    through the step that carries it: that program's products run over the
    decode rows and the prompt's together, so the last bits may differ.)"""
    model, params = model_and_params
    prompt = (11, 4, 9)
    outs = []
    for stages in (1, 2):
        cache = _cache(mesh=tp2_mesh)
        eng = ServeEngine(CFG, tp2_mesh, params, cache, num_stages=stages)
        assert len(eng.stage_bounds) == stages
        slot = cache.alloc(len(prompt), 1)
        outs.append(np.asarray(eng.prefill(prompt, slot)))
    assert np.abs(outs[0] - outs[1]).max() <= 1e-6 * np.abs(outs[1]).max()


def test_engine_rejects_scanned_params(tp2_mesh):
    cache = _cache(mesh=tp2_mesh)
    with pytest.raises(ValueError, match="scan_layers"):
        ServeEngine(CFG, tp2_mesh, {"layers": {}, "embed_tokens": {}}, cache)


# ================================================================ faultsim
def test_faultsim_serve_kinds_parse_and_fire():
    faults = faultsim.parse_schedule("request_timeout:step=3;slow_decode:call=1,count=2")
    assert [f.kind for f in faults] == ["request_timeout", "slow_decode"]
    inj = faultsim.arm(faults)
    try:
        inj.set_step(3)
        assert inj.fires("request_timeout")
        assert not inj.fires("request_timeout")  # count=1 consumed
        assert not inj.fires("slow_decode")  # call 0
        assert inj.fires("slow_decode")  # call 1
        assert inj.fires("slow_decode")  # call 2 (count=2)
        assert not inj.fires("slow_decode")
    finally:
        faultsim.disarm()


def test_faultsim_serve_kinds_disarmed_are_noop_refs():
    assert faultsim.fires is faultsim._noop_fires
    assert faultsim.fires("request_timeout") is False
    assert faultsim.fires("slow_decode") is False


# ==================================================================== loop
@pytest.fixture(scope="module")
def serve_rig(model_and_params, tp2_mesh):
    """One compiled engine shared by every loop test (cache.reset between
    runs keeps the jit cache warm)."""
    _, params = model_and_params
    cache = _cache(num_slots=2, page_size=4, pages_per_slot=4, mesh=tp2_mesh)
    eng = ServeEngine(CFG, tp2_mesh, params, cache)
    return eng, cache


def _arrivals(n=5, **kw):
    rng = np.random.default_rng(3)
    out = []
    for i in range(n):
        kw.setdefault("deadline_steps", 50)
        out.append((2 * i, Request(
            rid=i, prompt=tuple(int(x) for x in rng.integers(1, 60, 3 + i % 2)),
            max_new_tokens=4, **kw,
        )))
    return out


def _run(eng, cache, arrivals, max_queue=8, **kw):
    cache.reset()
    sched = ContinuousBatchingScheduler(cache, max_queue=max_queue)
    res = run_serve_resilient(
        engine=eng, scheduler=sched, arrivals=arrivals,
        install_signal_handlers=False, coordinate=False, **kw,
    )
    return res, sched


def test_loop_completes_all_and_ledger_balances(serve_rig):
    eng, cache = serve_rig
    res, sched = _run(eng, cache, _arrivals())
    assert res.status == "completed"
    sched.ledger_check()
    assert all(o["status"] == "completed" for o in res.outcomes.values())
    assert all(len(o["tokens"]) == 4 for o in res.outcomes.values())


def test_loop_oom_evicts_newest_and_replays_identically(serve_rig):
    eng, cache = serve_rig
    golden, _ = _run(eng, cache, _arrivals())
    faultsim.arm(faultsim.parse_schedule("oom:step=3"))
    try:
        res, sched = _run(eng, cache, _arrivals())
    finally:
        faultsim.disarm()
    sched.ledger_check()
    assert res.status == "completed"
    assert res.counts["evicted"] == 1 and res.counts["requeued"] == 1
    # the evicted request replayed from its prompt and regenerated the
    # SAME tokens — decode is deterministic in any slot/page assignment
    for rid, o in res.outcomes.items():
        assert o["status"] == "completed"
        assert o["tokens"] == golden.outcomes[rid]["tokens"], rid
    assert any(o["replays"] == 1 for o in res.outcomes.values())


def test_loop_request_timeout_kind_rejects_explicitly(serve_rig):
    eng, cache = serve_rig
    faultsim.arm(faultsim.parse_schedule("request_timeout:step=2"))
    try:
        res, sched = _run(eng, cache, _arrivals())
    finally:
        faultsim.disarm()
    sched.ledger_check()
    statuses = [o["status"] for o in res.outcomes.values()]
    assert statuses.count("timed_out") == 1
    assert res.counts["timed_out"] == 1
    timed = next(o for o in res.outcomes.values() if o["status"] == "timed_out")
    assert "request_timeout" in timed["reason"]


def test_loop_slow_decode_kind_sleeps_and_completes(serve_rig, monkeypatch):
    eng, cache = serve_rig
    monkeypatch.setenv("VESCALE_FAULTSIM_SLOW_DECODE_S", "0.01")
    faultsim.arm(faultsim.parse_schedule("slow_decode:step=1,count=2"))
    try:
        res, sched = _run(eng, cache, _arrivals(n=2))
        fired = faultsim.get_injector().fired_total["slow_decode"]
    finally:
        faultsim.disarm()
    assert fired == 2
    assert res.status == "completed"
    sched.ledger_check()


def test_loop_single_token_and_eos_budgets(serve_rig):
    """max_new_tokens=1 completes on the prefill-sampled token (no decode
    overrun), and an eos_id matching the first token stops generation at
    exactly one token."""
    eng, cache = serve_rig
    arr = [(0, Request(rid=0, prompt=(5, 9, 17), max_new_tokens=1))]
    res, sched = _run(eng, cache, arr)
    sched.ledger_check()
    assert res.outcomes[0]["status"] == "completed"
    assert len(res.outcomes[0]["tokens"]) == 1
    first = res.outcomes[0]["tokens"][0]
    arr = [(0, Request(rid=1, prompt=(5, 9, 17), max_new_tokens=8, eos_id=first))]
    res, sched = _run(eng, cache, arr)
    assert res.outcomes[1]["status"] == "completed"
    assert res.outcomes[1]["tokens"] == [first]


def test_loop_wall_deadline_or_agreed(serve_rig, monkeypatch):
    """Wall-clock deadlines in coordinated mode: one rank's clock-local
    expiry verdict (the slot bitmask) is OR-agreed, so a PEER's verdict
    cancels the request here too — no desync, explicit timed_out."""
    import vescale_tpu.distributed as vdist

    def fake_allgather(values, tag="", timeout_s=None):
        row = np.asarray(list(values), np.int64)
        peer = row.copy()
        if row[1] >= 2:  # from step 2 the peer's clock says slot 0 expired
            peer[5] |= 1
        return np.stack([row, peer])

    monkeypatch.setattr(vdist, "allgather_ints", fake_allgather)
    eng, cache = serve_rig
    cache.reset()
    sched = ContinuousBatchingScheduler(cache, max_queue=8)
    arr = [(0, Request(rid=0, prompt=(5, 9), max_new_tokens=8))]
    res = run_serve_resilient(
        engine=eng, scheduler=sched, arrivals=arr,
        install_signal_handlers=False, coordinate=True, wall_deadline_s=3600.0,
    )
    sched.ledger_check()
    assert res.outcomes[0]["status"] == "timed_out"
    assert "wall deadline" in res.outcomes[0]["reason"]


def test_loop_step_deadline_times_out(serve_rig):
    eng, cache = serve_rig
    # max_new 4 needs ~4 steps; a 1-step deadline must cancel mid-flight
    arr = [(0, Request(rid=0, prompt=(5, 9), max_new_tokens=4, deadline_steps=1))]
    res, sched = _run(eng, cache, arr)
    sched.ledger_check()
    assert res.outcomes[0]["status"] == "timed_out"
    assert 0 < len(res.outcomes[0]["tokens"]) < 4  # partial kept for diagnosis


def test_loop_preemption_drains_cleanly(serve_rig):
    eng, cache = serve_rig
    faultsim.arm(faultsim.parse_schedule("preempt:step=3"))
    try:
        res, sched = _run(eng, cache, _arrivals(n=6))
    finally:
        faultsim.disarm()
    sched.ledger_check()
    assert res.status == "preempted"
    statuses = {o["status"] for o in res.outcomes.values()}
    assert statuses <= {"completed", "preempted_requeue"}
    # in-flight requests were drained to completion, queued ones rejected
    assert res.counts["completed"] >= 1
    done = [o for o in res.outcomes.values() if o["status"] == "completed"]
    assert all(len(o["tokens"]) == 4 for o in done)


def test_loop_hung_decode_trips_watchdog(serve_rig, monkeypatch):
    """A wedged decode step (faultsim `hang`) must trip the SAME watchdog
    machinery as a hung train step: no beat within the deadline -> stack
    dump fired (abort disabled here so the test survives to assert)."""
    from vescale_tpu.resilience import Watchdog

    monkeypatch.setenv("VESCALE_FAULTSIM_HANG_S", "0.8")
    eng, cache = serve_rig
    cache.reset()
    sched = ContinuousBatchingScheduler(cache, max_queue=8)
    wd = Watchdog(timeout_s=0.2, poll_s=0.05, abort=False)
    wd.start()
    faultsim.arm(faultsim.parse_schedule("hang:step=2"))
    try:
        res = run_serve_resilient(
            engine=eng, scheduler=sched, arrivals=_arrivals(n=2),
            install_signal_handlers=False, coordinate=False, watchdog=wd,
        )
    finally:
        faultsim.disarm()
        wd.stop()
    assert wd.fired >= 1
    assert wd.last_bundle["reason"] == "hang"
    assert res.status == "completed"  # the stall ended; the run finished


def test_loop_coordination_desync_raises(serve_rig, monkeypatch):
    """A rank whose scheduler digest disagrees must get a DesyncError at
    the step boundary — BEFORE the divergent batch decodes."""
    import vescale_tpu.distributed as vdist
    from vescale_tpu.resilience.consistency import DesyncError

    def fake_allgather(values, tag="", timeout_s=None):
        row = np.asarray(list(values), np.int64)
        other = row.copy()
        other[7] += 1  # the peer's scheduler decision digest diverged
        return np.stack([row, other])

    monkeypatch.setattr(vdist, "allgather_ints", fake_allgather)
    eng, cache = serve_rig
    cache.reset()
    sched = ContinuousBatchingScheduler(cache, max_queue=8)
    with pytest.raises(DesyncError, match="sched_hash"):
        run_serve_resilient(
            engine=eng, scheduler=sched, arrivals=_arrivals(n=2),
            install_signal_handlers=False, coordinate=True,
        )


def test_loop_shed_under_overload(serve_rig):
    eng, cache = serve_rig
    arr = [(0, r[1]) for r in _arrivals(n=6)]  # all at once, 2 slots, queue 2
    res, sched = _run(eng, cache, arr, max_queue=2)
    sched.ledger_check()
    assert res.counts["shed"] >= 1
    shed = [o for o in res.outcomes.values() if o["status"] == "shed"]
    assert all(o["retry_after_s"] > 0 for o in shed)
    done = [o for o in res.outcomes.values() if o["status"] == "completed"]
    assert len(done) == len(res.outcomes) - len(shed)


def test_loop_serving_dashboard_block(serve_rig, tmp_path):
    from vescale_tpu import telemetry

    eng, cache = serve_rig
    telemetry.init(out_dir=str(tmp_path), memtrack=False)
    try:
        _run(eng, cache, _arrivals(n=3))
        dash = telemetry.dashboard()
        reg = telemetry.get_registry()
        snap = reg.snapshot()
    finally:
        telemetry.shutdown()
    assert "serving:" in dash
    assert snap["counters"]["serve_requests_admitted_total"] >= 3
    assert snap["counters"]["serve_requests_completed_total"] >= 3
    assert "serve_decode_step_seconds" in snap["histograms"]
    assert "serve_ttft_seconds" in snap["histograms"]


# =========================================== what a decode step returns
TIED = ((5, 9), (7, 11))    # two pairs of ids with one row of the head each, the second pair's negated


@pytest.fixture(scope="module", params=["dense_tp2", "hybrid"])
def step_rig(request):
    """Both engines behind one surface: (engine, cache, vocab, weights whose
    head gives TIED's ids equal logits, a call that serves other weights and
    returns the ones before).  The dense one is the loop tests' engine on the
    tp-2 mesh; the hybrid one is ``test_granite_hybrid``'s toy."""
    if request.param == "dense_tp2":
        eng, cache = request.getfixturevalue("serve_rig")
        vocab, head = CFG.vocab_size, np.array(eng.params["lm_head"]["kernel"]).T
        tied = lambda rows: {**eng.params, "lm_head": {"kernel": np.ascontiguousarray(rows.T)}}
        swap = eng.swap_params
    else:
        from tests.test_granite_hybrid import PAGE, PAGES, SLOTS, gh, hybrid_cache_config, toy_config
        from vescale_tpu.serve import HybridServeEngine

        cfg = toy_config()
        mesh = DeviceMesh(("tp",), (1,), devices=jax.devices()[:1])
        params = jax.jit(lambda k: gh.init_params(cfg, k))(jax.random.key(7))
        cache = PagedKVCache(hybrid_cache_config(cfg, num_slots=SLOTS, page_size=PAGE, pages_per_slot=PAGES), mesh)
        eng = HybridServeEngine(cfg, mesh, params, cache)
        vocab, head = cfg.vocab_size, np.array(params["embed_tokens"]["embedding"])
        tied = lambda rows: {**eng.params, "embed_tokens": {"embedding": jnp.asarray(rows)}}

        def swap(new):      # every program takes the tree as an argument
            prior, eng.params = eng.params, new
            return prior
    # one direction, a thousand times a row's length: one pair or the other holds every row's largest logit
    u = 1e3 * np.random.default_rng(5).standard_normal(head.shape[1]).astype(head.dtype) / np.sqrt(head.shape[1])
    for (a, b), sign in zip(TIED, (1, -1)):
        head[a] = head[b] = sign * u
    return eng, cache, vocab, tied(head), swap


def _decode_forced(eng, cache, slots, tok):
    toks = np.zeros((cache.num_slots,), np.int32)
    toks[slots] = tok
    step = eng.decode(toks)
    for s in slots:
        cache.advance(s)
    return step


def test_decode_step_tokens_are_the_argmax_of_its_logits_and_a_tie_goes_to_the_lowest_id(step_rig):
    eng, cache, vocab, tied_params, swap = step_rig
    cache.reset()
    prior = swap(tied_params)
    try:
        slots = []
        for prompt in ((2, 3, 4), (13, 21, 34, 55)):
            slots.append(cache.alloc(len(prompt), 4))
            eng.prefill(prompt, slots[-1])
            cache.commit_prefill(slots[-1], len(prompt))
        for tok in (17, 23, 42):
            step = _decode_forced(eng, cache, slots, tok)
            logits = np.asarray(step)
            assert isinstance(step.tokens, np.ndarray) and step.tokens.dtype == np.int32
            assert step.tokens.shape == (cache.num_slots,) and step.shape == logits.shape == (cache.num_slots, vocab)
            np.testing.assert_array_equal(step.tokens, np.argmax(logits, -1))
            for s in slots:
                top = np.flatnonzero(logits[s] == logits[s].max())
                assert tuple(top) in TIED and step.tokens[s] == top[0], (top, step.tokens[s])
    finally:
        swap(prior)
        cache.reset()


def test_logits_cross_to_the_host_only_when_a_caller_reads_them(step_rig):
    eng, cache, vocab, _, _ = step_rig
    cache.reset()
    slot = cache.alloc(3, 8)
    eng.prefill((5, 9, 17), slot)
    cache.commit_prefill(slot, 3)

    def copied(read):
        before = eng.trace_counters()["logits_bytes_to_host"]
        read(_decode_forced(eng, cache, [slot], 3))
        return eng.trace_counters()["logits_bytes_to_host"] - before

    assert copied(lambda step: (step.tokens[slot], step.shape)) == 0
    assert copied(lambda step: step[slot]) == vocab * 4
    assert copied(lambda step: np.asarray(step)) == cache.num_slots * vocab * 4
    assert copied(lambda step: (step[slot], step[slot], np.stack([step]))) == (2 + cache.num_slots) * vocab * 4
    # a list of rows indexes as it does an ndarray (chip_smoke.py reads ``logits[slots]``)
    assert copied(lambda step: np.testing.assert_array_equal(step[[slot, 0]], np.asarray(step)[[slot, 0]])) == (
        2 + cache.num_slots) * vocab * 4
    assert copied(lambda step: (len(step), step.dtype)) == 0
    step = _decode_forced(eng, cache, [slot], 3)
    assert len(step) == cache.num_slots and step.dtype == np.float32
    cache.reset()


def test_the_loops_streams_are_those_of_a_host_argmax_over_every_row(step_rig):
    """The ids the decode program takes are, token for token, what
    ``np.argmax`` of the copied rows gave (``_gen_tokens``); and the loop
    pays for no logits."""
    eng, cache, vocab, _, _ = step_rig
    arrivals = _arrivals(n=4)
    before = eng.trace_counters()
    res, sched = _run(eng, cache, arrivals)
    loop = {k: v - before[k] for k, v in eng.trace_counters().items()}
    assert res.status == "completed" and loop["decode_steps"] >= 3 and loop["logits_bytes_to_host"] == 0
    sched.ledger_check()
    cache.reset()
    for _, req in arrivals:
        before = eng.trace_counters()["logits_bytes_to_host"]
        assert res.outcomes[req.rid]["tokens"] == _gen_tokens(eng, cache, list(req.prompt), req.max_new_tokens)
        assert eng.trace_counters()["logits_bytes_to_host"] - before == (req.max_new_tokens - 1) * vocab * 4


# ==================================================== train->serve handoff
def test_train_to_serve_handoff_elastic_params_only(tmp_path, model_and_params):
    """Satellite 3: a training checkpoint (params + optimizer, written on a
    ("dp","tp") mesh) restores params-ONLY onto a different serve mesh via
    the elastic preflight: VSC130 emitted, optimizer chunks never read,
    and the serve logits are bit-identical to a same-mesh restore."""
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from vescale_tpu.checkpoint import storage as _storage
    from vescale_tpu.checkpoint.elastic import preflight

    model, params = model_and_params
    train_mesh = DeviceMesh(("dp", "tp"), (2, 4))
    rep = NamedSharding(train_mesh.jax_mesh, P())
    placed = jax.tree_util.tree_map(
        lambda x: jax.device_put(np.asarray(x), rep), params
    )
    opt_state = optax.adam(1e-3).init(placed)
    root = str(tmp_path / "ckpt")
    ckpt.save(root, {"model": placed, "optimizer": opt_state})

    def template_on(jmesh):
        sh = NamedSharding(jmesh, P())
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype, sharding=sh),
            params,
        )

    # --- the preflight's own verdict: VSC130 (info), not an error
    serve_mesh = DeviceMesh(("tp",), (4,), devices=jax.devices()[:4])
    meta = json.loads(_storage.FileSystemStorage(root).read_bytes("meta.json").decode())
    report, elastic = preflight(meta, {"model": template_on(serve_mesh.jax_mesh)}, root)
    assert elastic
    assert [f.code.code for f in report.findings] == ["VSC130"]

    # --- params-only load: optimizer chunks must never be read
    reads = []
    orig = _storage.FileSystemStorage.read_bytes

    def recording(self, name):
        reads.append(name)
        return orig(self, name)

    _storage.FileSystemStorage.read_bytes = recording
    try:
        restored = load_params(root, template_on(serve_mesh.jax_mesh))
    finally:
        _storage.FileSystemStorage.read_bytes = orig
    stats = dict(ckpt.LAST_LOAD_STATS)
    assert stats["elastic"] == 1
    chunk_reads = [n for n in reads if n.startswith("data/")]
    assert chunk_reads and all(n.startswith("data/model/") for n in chunk_reads), chunk_reads
    assert not any("optimizer" in n for n in reads), reads

    # --- logits parity: cross-mesh restore == same-mesh restore, bitwise
    same_mesh = load_params(root, template_on(train_mesh.jax_mesh))

    def probe(mesh, p):
        kc = KVCacheConfig(layers=CFG.num_hidden_layers, kv_heads=CFG.num_key_value_heads,
                           head_dim=CFG.head_dim, num_slots=1, page_size=4, pages_per_slot=4)
        cache = PagedKVCache(kc, mesh, placements=[Replicate()] * mesh.ndim)
        eng = ServeEngine(CFG, mesh, p, cache)
        slot = cache.alloc(3, 1)
        return np.asarray(eng.prefill((9, 4, 31), slot))

    a = probe(serve_mesh, restored)
    b = probe(train_mesh, same_mesh)
    assert a.tobytes() == b.tobytes()


# ====================================================== gloo rig (satellite)
def test_rig_ports_never_reuse():
    from vescale_tpu.testing import reserve_port, reserved_ports

    before = len(reserved_ports())
    ports = [reserve_port() for _ in range(16)]
    assert len(set(ports)) == 16
    allp = reserved_ports()
    assert len(allp) == before + 16
    # the registry's global invariant — across every spawned harness test
    # in this session, no port was ever handed out twice
    assert len(set(allp)) == len(allp)


def test_rig_transport_retry_bounded(tmp_path):
    from vescale_tpu.testing import run_gloo_world

    marker = tmp_path / "tried"
    code = (
        "import os,sys\n"
        f"m={str(marker)!r}\n"
        "first=not os.path.exists(m)\n"
        "open(m,'a').write('x')\n"
        "if first:\n"
        "    print('Gloo connect: Connection refused'); sys.exit(1)\n"
        "print('fine')\n"
    )
    seen_ports = []

    def spawn(port):
        seen_ports.append(port)
        return [subprocess.Popen(
            [sys.executable, "-c", code],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )]

    results = run_gloo_world(spawn, timeout=60, transport_retries=1)
    assert [rc for rc, _ in results] == [0]
    assert len(seen_ports) == 2 and seen_ports[0] != seen_ports[1]

    # a NON-transport failure must surface unretried
    calls = []

    def spawn_fail(port):
        calls.append(port)
        return [subprocess.Popen(
            [sys.executable, "-c", "print('AssertionError: real bug'); raise SystemExit(1)"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )]

    results = run_gloo_world(spawn_fail, timeout=60, transport_retries=1)
    assert results[0][0] == 1 and len(calls) == 1


# ============================================================ smoke wiring
def test_serve_smoke_script():
    """tier-1 wiring of scripts/serve_smoke.py: train on 2 procs, serve on
    2 (coordinated faults) and on 1 (elastic restore + fault battery),
    logits bit-identical across worlds — the ISSUE 10 acceptance run."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "serve_smoke.py")],
        capture_output=True,
        text=True,
        timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout[-3000:]}\nstderr:\n{out.stderr[-3000:]}"
    assert "SERVE SMOKE OK" in out.stdout
