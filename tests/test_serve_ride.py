"""A prompt rides the decode step (PR 53): a single-stage ``ServeEngine``'s
``prefill`` launches nothing, and the step whose ``DecodeFeed`` names the
``PrefillStep`` as ``rider`` carries the prompt's rows through its own program,
beside the decode rows under one product a weight.  Held here, on both legs
(``VESCALE_KERNELS`` unset: the XLA legs a CPU takes; ``interpret``: the
``paged_decode`` and flash kernels a TPU compiles, through the interpreter):

(a) a riding step gives the decode rows' logits, the rider's row, its greedy id
    and its slot's pages that ``prefill`` followed by ``decode`` gives, with the
    rider's slot idle in the step that carries it;
(b) through ``run_serve_resilient`` every request's stream is the same riding
    and not (the engine behind a face without the offer), whatever ends a
    request or joins an admission, and the roads that need the host's token (a
    prefix hit, ``speculative=``) carry nothing;
(c) a ``PrefillStep`` nobody carried is launched when it is read and when a
    ``decode`` is called without it: the benchmark's reference check, written out;
(d) a riding step is ONE ``vs.serve-decode.launch`` that says ``launch``, ``rung``
    and ``slot``, no launch of the prefill kind, and its module is ``jit_decode``;
    nothing compiles after ``warm()``."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from vescale_tpu.mesh import DeviceMesh
from vescale_tpu.models.llama import Llama, LlamaConfig
from vescale_tpu.ndtimeline import api as nd
from vescale_tpu.ndtimeline import predefined as P
from vescale_tpu.serve import (ContinuousBatchingScheduler, DecodeFeed, KVCacheConfig, PagedKVCache, PrefillStep, PrefixCache,
                               Request, ServeEngine, SpeculativeDecoder, run_serve_resilient, slice_drafter_params)

PAGE, PAGES, SLOTS = 16, 24, 4              # 384 positions a slot: rungs 128 / 256 / 384
CFG = LlamaConfig(vocab_size=96, hidden_size=32, intermediate_size=64, num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=2, max_position_embeddings=512, dtype=jnp.float32)
TOL = 2e-5      # float32 products over other row counts: the family's tolerance for its own forward twice over


def _prompt(seed, n):
    return tuple(int(t) for t in np.random.default_rng(seed).integers(1, 90, n))


def _engine(params, stages=1):
    mesh = DeviceMesh(("tp",), (1,), devices=jax.devices()[:1])
    cache = PagedKVCache(KVCacheConfig(layers=CFG.num_hidden_layers, kv_heads=CFG.num_key_value_heads, head_dim=CFG.head_dim,
                                       num_slots=SLOTS, page_size=PAGE, pages_per_slot=PAGES, dtype=CFG.dtype), mesh)
    return ServeEngine(CFG, mesh, params, cache, num_stages=stages), cache


@pytest.fixture(scope="module")
def params():
    return Llama(CFG).init(jax.random.key(0), jnp.ones((1, 8), jnp.int32))["params"]


@pytest.fixture(scope="module", params=[None, "interpret"], ids=["xla_legs", "kernels_interpreted"])
def leg(request):
    """The leg is latched when an engine is BUILT: every engine of a test is built under it."""
    with pytest.MonkeyPatch.context() as patch:
        if request.param is None:
            patch.delenv("VESCALE_KERNELS", raising=False)
        else:
            patch.setenv("VESCALE_KERNELS", request.param)
        yield request.param


class NoOffer:
    """An engine behind a face that offers no ride: the serve loop names no
    rider, so every prompt that waits goes alone, before the step that follows."""

    def __init__(self, engine):
        self._engine = engine

    def __getattr__(self, name):
        if name == "rides":
            raise AttributeError(name)
        return getattr(self._engine, name)


# ------------------------------------------------------------ (a) the program
@pytest.mark.parametrize("n", [128, 200], ids=["rung128", "rung256"])
def test_a_riding_step_is_a_prefill_and_a_decode_step(params, leg, n):
    rung = 128 if n <= 128 else 256
    got = {}
    for ride in (True, False):
        eng, cache = _engine(params)
        assert eng.rides and eng.kernel_decode == (leg == "interpret")
        a, b = cache.alloc(60, 40), cache.alloc(37, 40)
        firsts = []
        for slot, (seed, length) in ((a, (1, 60)), (b, (2, 37))):
            firsts.append(eng.prefill(_prompt(seed, length), slot).token)
            cache.commit_prefill(slot, length)
        toks = np.zeros((SLOTS,), np.int32)
        toks[a], toks[b] = firsts
        s0 = eng.decode(toks)
        cache.advance(a), cache.advance(b)
        c = cache.alloc(n, 100)
        pages = cache.page_table[c, : rung // PAGE].copy()
        waiting = eng.prefill(_prompt(3, n), c)
        cache.commit_prefill(c, n)
        assert isinstance(waiting, PrefillStep) and not waiting.launched and (waiting.rung, waiting.slot) == (rung, c)
        k0 = np.asarray(cache.k.data)       # (a read of the pools launches nothing: the prompt still waits)
        assert not waiting.launched
        if ride:
            s1 = eng.decode(DecodeFeed(s0, rider=waiting))      # the step carries it: slot c is not stepped
            cache.advance(a), cache.advance(b)
            assert waiting.launched and not waiting.read and int(cache.lengths[c]) == n
            # the rider's own decode row wrote no page of its slot (past the rung nothing moved; up to it lie the prompt's rows)
            k1, past = np.asarray(cache.k.data), cache.page_table[c, rung // PAGE: cache.pages_needed(n + 100)]
            assert (past > 0).all() and np.array_equal(k0[:, past], k1[:, past])
            s2 = eng.decode(DecodeFeed(s1, {c: waiting}))       # ... and the step after takes its first id from the device
            assert not waiting.read
            cache.advance(a), cache.advance(b), cache.advance(c)
            got[ride] = dict(step=np.asarray(s1)[[a, b]], ids=s1.tokens[[a, b]], row=np.asarray(waiting), first=waiting.token,
                             after=np.asarray(s2)[[a, b, c]], after_ids=s2.tokens[[a, b, c]])
            c1 = eng.trace_counters()
            assert (c1["prefill_rides"], c1["prefill_launches"], c1["prefill_reads_ahead"]) == (1, 3, 1)
        else:
            first = waiting.token                                # read: launched alone
            s1 = eng.decode(DecodeFeed(s0, {c: first}))         # the step that follows it steps slot c as well
            cache.advance(a), cache.advance(b), cache.advance(c)
            s2 = eng.decode(DecodeFeed(s1))
            got[ride] = dict(step=np.asarray(s1)[[a, b]], ids=s1.tokens[[a, b]], row=np.asarray(waiting), first=first,
                             after=np.concatenate([np.asarray(s2)[[a, b]], np.asarray(s1)[[c]]]),
                             after_ids=np.concatenate([s2.tokens[[a, b]], s1.tokens[[c]]]))
            assert eng.trace_counters()["prefill_rides"] == 0
        k, v = np.asarray(cache.k.data), np.asarray(cache.v.data)
        got[ride]["pages"] = np.stack([k[:, pages].reshape(2, rung, -1)[:, :n], v[:, pages].reshape(2, rung, -1)[:, :n]])
    rode, apart = got[True], got[False]
    for key in ("step", "row", "after", "pages"):
        assert np.abs(rode[key] - apart[key]).max() <= TOL * np.abs(apart[key]).max(), key
    assert rode["first"] == apart["first"] == int(np.argmax(rode["row"]))
    assert np.array_equal(rode["ids"], apart["ids"]) and np.array_equal(rode["after_ids"], apart["after_ids"])


# --------------------------------------------------------------- (b) the loop
@pytest.fixture(scope="module")
def served(params, leg):
    eng, cache = _engine(params)
    return eng.warm(), cache


def _serve(engine, cache, arrivals, **kw):
    cache.reset()
    sched = ContinuousBatchingScheduler(cache, max_queue=32, **{k: kw.pop(k) for k in ("prefix_cache",) if k in kw})
    res = run_serve_resilient(engine=engine, scheduler=sched, arrivals=arrivals, install_signal_handlers=False, coordinate=False, **kw)
    sched.ledger_check()
    cache.reset()
    assert all(o["status"] == "completed" for o in res.outcomes.values())
    return {rid: o["tokens"] for rid, o in res.outcomes.items()}


def _golden(served, req):
    eng, cache = served
    cache.reset()
    return eng.replay_greedy(req.prompt, req.max_new_tokens, eos_id=req.eos_id)


def _delta(eng, start):
    return {k: v - start[k] for k, v in eng.trace_counters().items()}


def test_every_stream_is_the_same_riding_and_not(served, tmp_path):
    """One long request keeps a step in flight; behind it arrive two requests in ONE iteration, a request
    of one token, and one whose first token is its EOS.  Riding, apart, and ``replay_greedy`` agree."""
    eng, cache = served
    eos_prompt = _prompt(24, 140)
    eos = _golden(served, Request(rid=0, prompt=eos_prompt, max_new_tokens=1))[0]
    reqs = [(0, Request(rid=0, prompt=_prompt(20, 30), max_new_tokens=16)),
            (2, Request(rid=1, prompt=_prompt(21, 100), max_new_tokens=4)), (2, Request(rid=2, prompt=_prompt(22, 200), max_new_tokens=4)),
            (7, Request(rid=3, prompt=_prompt(23, 129), max_new_tokens=1)),
            (9, Request(rid=4, prompt=eos_prompt, max_new_tokens=5, eos_id=eos))]
    want = {req.rid: _golden(served, req) for _, req in reqs}
    assert want[4] == [eos] and len(want[3]) == 1
    start = eng.trace_counters()
    nd.start_trace_session(str(tmp_path / "riding"), profiler=False)
    try:
        rode = _serve(eng, cache, reqs)
    finally:
        compiles = nd.stop_trace_session().counters["backend_compiles"]
    c = _delta(eng, start)
    # the first request finds no step in flight and goes alone; of the two admitted together one rides the step
    # about to be launched and the other the step after it
    assert (c["prefill_launches"], c["prefill_rides"], c["prefill_reads_ahead"]) == (5, 4, 4) and compiles == 0
    assert c["decode_steps_ahead"] == c["decode_steps"] - 1, "one cold start: no prompt, riding or alone, broke the pipeline"
    start = eng.trace_counters()
    apart = _serve(NoOffer(eng), cache, reqs)
    c = _delta(eng, start)
    assert (c["prefill_launches"], c["prefill_rides"], c["prefill_reads_ahead"]) == (5, 0, 3)
    assert rode == apart == want


def test_a_request_cancelled_while_its_prompt_rides_drops_the_first_token(served):
    """The rider's first token is read a step late: a slot that timed out in between records nothing."""
    eng, cache = served
    reqs = [(0, Request(rid=0, prompt=_prompt(30, 20), max_new_tokens=12)),
            (3, Request(rid=1, prompt=_prompt(31, 50), max_new_tokens=6))]
    cache.reset()
    sched = ContinuousBatchingScheduler(cache, max_queue=8)

    def on_step(step, active):
        if step == 3 and 1 in {inf.req.rid for inf in sched.active.values()}:      # the iteration whose step carries rid 1
            (slot,) = [s for s, inf in sched.active.items() if inf.req.rid == 1]
            sched.timeout(slot, reason="cancelled under its rider")

    start = eng.trace_counters()
    res = run_serve_resilient(engine=eng, scheduler=sched, arrivals=reqs, install_signal_handlers=False, coordinate=False,
                              on_step=on_step)
    sched.ledger_check()
    cache.reset()
    assert _delta(eng, start)["prefill_rides"] == 1
    assert res.outcomes[1]["status"] == "timed_out" and res.outcomes[1]["tokens"] == []
    assert res.outcomes[0]["tokens"] == _golden(served, reqs[0][1])


def test_a_request_cancelled_while_its_prompt_still_waits_leaves_the_next_tenants_pages_right(served):
    """Two admitted in one iteration: the second waits for the step after.  Cancelled before that step, its
    prompt is still the engine's to launch (alone, BEFORE any prompt that came after it), so the request that
    takes over its slot and pages finds them written in the order of admission."""
    eng, cache = served
    reqs = [(0, Request(rid=0, prompt=_prompt(32, 20), max_new_tokens=14)),
            (2, Request(rid=1, prompt=_prompt(33, 40), max_new_tokens=5)), (2, Request(rid=2, prompt=_prompt(34, 150), max_new_tokens=5)),
            (3, Request(rid=3, prompt=_prompt(35, 90), max_new_tokens=5))]
    cache.reset()
    sched = ContinuousBatchingScheduler(cache, max_queue=8)
    seen = {}

    def on_step(step, active):
        if step == 2:       # rid 1 rides this iteration's step, rid 2 waits for the next
            (slot,) = [s for s, inf in sched.active.items() if inf.req.rid == 2]
            seen["waited"] = [w.slot for w in eng._waiting] == [slot]
            seen["pages"] = cache.page_table[slot].copy()
            sched.timeout(slot, reason="cancelled while its prompt waited")
        if step == 3 and "reused" not in seen:
            (slot,) = [s for s, inf in sched.active.items() if inf.req.rid == 3]
            seen["reused"] = bool(set(cache.page_table[slot][cache.page_table[slot] > 0]) & set(seen["pages"][seen["pages"] > 0]))

    start = eng.trace_counters()
    res = run_serve_resilient(engine=eng, scheduler=sched, arrivals=reqs, install_signal_handlers=False, coordinate=False,
                              on_step=on_step)
    sched.ledger_check()
    cache.reset()
    c = _delta(eng, start)
    assert seen == {"waited": True, "pages": seen["pages"], "reused": True} and not eng._waiting
    assert (c["prefill_launches"], c["prefill_rides"]) == (4, 2), "the cancelled prompt went alone, before the step that carried rid 3"
    assert res.outcomes[2]["status"] == "timed_out" and res.outcomes[2]["tokens"] == []
    assert all(res.outcomes[rid]["tokens"] == _golden(served, req) for _, req in reqs for rid in [req.rid] if rid != 2)


def test_a_boundary_that_settles_reads_the_prompts_that_wait(served):
    """Three admitted in one iteration behind a step in flight: one rides, two wait.  An eviction at the
    next boundary reads the step in flight first, and with it every first token: the prompts that still
    waited go alone then, the newest request is evicted and replayed, and every stream is ``replay_greedy``'s."""
    from vescale_tpu.resilience import faultsim

    eng, cache = served
    reqs = [(0, Request(rid=0, prompt=_prompt(36, 20), max_new_tokens=12))] + [
        (2, Request(rid=k, prompt=_prompt(36 + k, 30 * k), max_new_tokens=4)) for k in (1, 2, 3)]
    want = {req.rid: _golden(served, req) for _, req in reqs}
    start = eng.trace_counters()
    faultsim.arm(faultsim.parse_schedule("oom:step=3"))
    try:
        cache.reset()
        sched = ContinuousBatchingScheduler(cache, max_queue=8)
        res = run_serve_resilient(engine=eng, scheduler=sched, arrivals=reqs, install_signal_handlers=False, coordinate=False)
    finally:
        faultsim.disarm()
    sched.ledger_check()
    cache.reset()
    c = _delta(eng, start)
    assert res.counts["evicted"] == 1 and {rid: o["tokens"] for rid, o in res.outcomes.items()} == want
    # rid 0 alone (cold), rid 1 rode, rids 2 and 3 went alone at the boundary, and the evicted one came again
    assert c["prefill_launches"] == 5 and 1 <= c["prefill_rides"] <= 2 and not eng._waiting


def test_a_step_launches_only_the_prompts_it_needs_and_idles_the_slots_of_those_that_wait(params, leg):
    """The engine's half of 'the step after': a feed that names a rider leaves a YOUNGER prompt waiting, its slot
    idle; a feed that is fed from a prompt that waits launches it alone, and every prompt older than it first."""
    eng, cache = _engine(params)
    a = cache.alloc(20, 10)
    toks = np.zeros((SLOTS,), np.int32)
    toks[a] = eng.prefill(_prompt(80, 20), a).token
    cache.commit_prefill(a, 20)
    s0 = eng.decode(toks)
    cache.advance(a)
    b, c, d = (cache.alloc(n, 10) for n in (40, 130, 60))
    steps = {}
    for slot, n in ((b, 40), (c, 130), (d, 60)):
        steps[slot] = eng.prefill(_prompt(81 + slot, n), slot)
        cache.commit_prefill(slot, n)
    k0 = np.asarray(cache.k.data)
    s1 = eng.decode(DecodeFeed(s0, rider=steps[b]))         # b rides; c and d still wait, their slots idle
    cache.advance(a)
    assert steps[b].launched and not steps[c].launched and not steps[d].launched and eng._waiting == [steps[c], steps[d]]
    k1 = np.asarray(cache.k.data)
    for slot in (c, d):
        pages = cache.page_table[slot][cache.page_table[slot] > 0]
        assert np.array_equal(k0[:, pages], k1[:, pages]), "a slot whose prompt waits wrote nothing"
    s2 = eng.decode(DecodeFeed(s1, {b: steps[b], d: steps[d]}))    # fed from d: c, older, goes first, then d, both alone
    cache.advance(a), cache.advance(b), cache.advance(d)
    assert steps[c].launched and steps[d].launched and steps[c]._launch + 1 == steps[d]._launch and not eng._waiting
    s3 = eng.decode(DecodeFeed(s2, {c: steps[c]}))
    for slot in (a, b, c, d):
        cache.advance(slot)
    got = {slot: [steps[slot].token if slot != a else None, int(s3.tokens[slot])] for slot in (a, b, c, d)}
    c1 = eng.trace_counters()
    assert (c1["prefill_launches"], c1["prefill_rides"], c1["prefill_reads_ahead"]) == (4, 1, 3)
    # ... and every slot's stream is that of an engine that launched each prompt alone, at once
    ref, ref_cache = _engine(params)
    for slot, n in ((a, 20), (b, 40), (c, 130), (d, 60)):
        assert ref_cache.alloc(n, 10) == slot
    first = {a: ref.prefill(_prompt(80, 20), a).token}
    ref_cache.commit_prefill(a, 20)
    feed = np.zeros((SLOTS,), np.int32)
    feed[a] = first[a]
    last = {a: [int(ref.decode(feed).tokens[a])]}
    ref_cache.advance(a)
    for slot, n in ((b, 40), (c, 130), (d, 60)):
        first[slot] = ref.prefill(_prompt(81 + slot, n), slot).token
        ref_cache.commit_prefill(slot, n)
    # a steps three times more; b and d step twice from their first tokens, c once
    for active in ((a,), (a, b, d), (a, b, c, d)):
        feed = np.zeros((SLOTS,), np.int32)
        for slot in active:
            feed[slot] = last[slot][-1] if slot in last else first[slot]
        out = ref.decode(feed).tokens
        for slot in active:
            last.setdefault(slot, []).append(int(out[slot]))
            ref_cache.advance(slot)
    assert got == {slot: [first[slot] if slot != a else None, last[slot][-1]] for slot in (a, b, c, d)}


@pytest.mark.parametrize("path", ["prefix_hit", "speculative"])
def test_the_roads_that_need_the_hosts_token_carry_nothing_they_did_not_before(params, served, leg, path):
    """A prefix hit's suffix comes off ``decode_multi`` read, and a drafter drafts from tokens on the host:
    under ``speculative=`` no prompt rides; beside a prefix cache the prompts that MISS ride, the hits do not."""
    eng, cache = served
    shared = _prompt(40, 4 * PAGE)
    reqs = [(k, Request(rid=k, prompt=(shared if k % 2 == 0 else ()) + _prompt(41 + k, 9 + k), max_new_tokens=7)) for k in range(5)]
    want = {req.rid: _golden(served, req) for _, req in reqs}
    start = eng.trace_counters()
    if path == "prefix_hit":
        pc = PrefixCache(cache)
        got = _serve(eng, cache, reqs, prefix_cache=pc)
        c = _delta(eng, start)
        # (the first finds no step in flight and goes alone; of those behind it the two that share its prefix hit)
        assert pc.stats.hit_tokens == 2 * len(shared) and (c["prefill_rides"], c["prefill_launches"]) == (2, 3)
    else:
        spec = SpeculativeDecoder(eng, slice_drafter_params(params, 1), drafter_layers=1, k=3)
        got = _serve(eng, cache, reqs, speculative=spec)
        c = _delta(eng, start)
        assert spec.drafted > 0 and (c["prefill_rides"], c["prefill_launches"]) == (0, len(reqs))
        assert spec.engine.trace_counters()["prefill_rides"] == 0 and not spec.engine._waiting, "the drafter's prompts went alone"
    assert got == want


# ------------------------------------------------- (c) nobody carried the step
def test_a_step_nobody_carried_is_launched_by_its_reader_and_by_a_cold_decode(params, leg):
    """The benchmark's reference check, written out (``benchmark/serve_cell.py``: ``check_reference``): prefill
    a prompt, then teacher-forced decode steps from the HOST's tokens with nothing in flight, the rows
    stacked afterwards.  Against the engine of two stages, whose prefill is four programs of its own."""
    prompt, forced = _prompt(50, 150), [7, 8, 9, 10]
    rows = {}
    for stages in (1, 2):
        eng, cache = _engine(params, stages)
        assert eng.rides == (stages == 1)
        slot = cache.alloc(len(prompt), len(forced) + 1)
        out = [eng.prefill(prompt, slot)]
        cache.commit_prefill(slot, len(prompt))
        assert out[0].launched == (stages == 2) and out[0].shape == (CFG.vocab_size,) and out[0].dtype == np.float32
        for tok in forced:
            toks = np.zeros((SLOTS,), np.int32)
            toks[slot] = tok
            out.append(eng.decode(toks)[slot])      # the first of these launches the prompt that waits, alone, before its step
            assert out[0].launched and not out[0].read
            cache.advance(slot)
        rows[stages] = np.stack(out)
        c = eng.trace_counters()
        assert (c["prefill_launches"], c["prefill_rides"], c["decode_launches"], c["decode_steps"]) == (1, 0, 4, 4)
        assert eng.launches == 5, "a prompt that went alone is a launch of its own"
    assert np.abs(rows[1] - rows[2]).max() <= TOL * np.abs(rows[2]).max()
    # ... and by whoever reads it: the token, or the row
    eng, cache = _engine(params)
    for read in (lambda step: step.token, np.asarray):
        slot = cache.alloc(len(prompt), 4)
        step = eng.prefill(prompt, slot)
        assert not step.launched and step in eng._waiting
        read(step)
        assert step.launched and step.read and not eng._waiting and int(np.argmax(np.asarray(step))) == step.token
        assert np.abs(np.asarray(step) - rows[2][0]).max() <= TOL * np.abs(rows[2][0]).max()
        cache.free(slot)


def test_prompts_that_wait_go_in_the_order_they_came_before_anything_else_runs(params, leg):
    """A slot's stale tenant, then its new one, both waiting: every later call of the engine that runs a
    program launches them first, in order, so the pages hold what eager launches would have left."""
    eng, cache = _engine(params)
    ref, ref_cache = _engine(params, 2)
    for e, c in ((eng, cache), (ref, ref_cache)):
        stale = c.alloc(300, 10)
        e.prefill(_prompt(60, 300), stale)
        c.free(stale)
        slot = c.alloc(70, 10)
        assert slot == stale
        step = e.prefill(_prompt(61, 70), slot)
        c.commit_prefill(slot, 70)
    assert len(eng._waiting) == 2 and not ref._waiting
    old = eng.swap_params(eng.params)       # (any call that runs, or re-homes, the programs: here the one that swaps the tree)
    assert not eng._waiting and old is not None and eng.trace_counters()["prefill_launches"] == 2
    toks = np.zeros((SLOTS,), np.int32)
    toks[slot] = 5
    got, want = eng.decode(toks)[slot], ref.decode(toks)[slot]
    assert np.abs(got - want).max() <= TOL * np.abs(want).max()


# ------------------------------------------------------------------ (d) spans
def test_a_riding_step_is_one_launch_of_the_decode_kind_and_its_module_is_jit_decode(served, tmp_path):
    eng, cache = served
    cache.reset()
    a = cache.alloc(20, 8)
    first = eng.prefill(_prompt(70, 20), a).token
    cache.commit_prefill(a, 20)
    toks = np.zeros((SLOTS,), np.int32)
    toks[a] = first
    s0 = eng.decode(toks)
    cache.advance(a)
    nd.start_trace_session(str(tmp_path / "session"), profiler=False)
    try:
        b = cache.alloc(130, 8)
        rider = eng.prefill(_prompt(71, 130), b)
        cache.commit_prefill(b, 130)
        number = eng.launches
        s1 = eng.decode(DecodeFeed(s0, rider=rider))
        cache.advance(a)
        s2 = eng.decode(DecodeFeed(s1, {b: rider}))
        rider.token
        s2.tokens
    finally:
        session = nd.stop_trace_session()
    cache.reset()
    named = lambda metric: [s for s in session.spans if s.metric == metric]
    carrying, after = named(P.SERVE_DECODE_LAUNCH)
    assert carrying.tags == {"launch": number, "rung": 256, "slot": b} and after.tags == {"launch": number + 1}
    assert not named(P.SERVE_PREFILL_LAUNCH) and len(named(P.SERVE_PREFILL_CALL)) == 1
    (fetch,) = named(P.SERVE_PREFILL_FETCH)
    assert fetch.tags == {"launch": number}, "the rider's read names the launch that carried it"
    assert sorted(s.tags["launch"] for s in named(P.SERVE_DECODE_FETCH)) == [number - 1, number, number + 1]
    c = session.counters
    assert (c["decode_launches"], c["prefill_launches"], c["prefill_rides"], c["prefill_reads_ahead"]) == (2, 1, 1, 1)
    assert c["backend_compiles"] == 0 and eng.launches == number + 2
    # the program's name on the device's ``XLA Modules`` line is its function's: the benchmark joins launches by it
    lowered = eng._ride_fn.lower(eng.params, cache.k.data, cache.v.data, cache.table_array(), cache.lengths_array(), eng._host_tokens(toks),
                       eng._first_ids(), np.zeros((256,), np.int32), np.int32(1), np.zeros((256 // PAGE,), np.int32), np.int32(0))
    assert lowered.as_text().lstrip().startswith("module @jit_decode ")
    assert eng._decode_fn.lower(eng.params, cache.k.data, cache.v.data, cache.table_array(), cache.lengths_array(),
                                eng._host_tokens(toks)).as_text().lstrip().startswith("module @jit_decode ")
    assert eng.buckets == [128, 256, 384] and eng._ride_fn._cache_size() >= 3, "one program a rung, each warmed"
