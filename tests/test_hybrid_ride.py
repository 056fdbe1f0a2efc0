"""A prompt rides the decode step behind ``HybridServeEngine`` (PR 55): the
engine OFFERS a ride (``engine.rides``, set on the instance) where the model's
module gives a ``serve_ride`` body and a step moves one position a slot, its
``prefill`` then launches nothing, and the step whose ``DecodeFeed`` names the
``PrefillStep`` as ``rider`` carries the prompt's rows through its own program,
beside the decode rows under one product a weight.  Falcon-H1 gave the first
body (``models/falcon_h1.py``, over ``models/mamba2.py:mamba2_ride``), Granite-4.0-H
the second (PR 62: ``models/granite_hybrid.py``, whose layer ends in an expert
layer that runs once over both kinds of row).  Every case below is held for
BOTH families, each at the toy size of its own test file
(``tests/test_falcon_h1.py``, ``tests/test_granite_hybrid.py``) in float32, on
both legs (``VESCALE_KERNELS`` unset: the XLA legs a CPU takes; ``interpret``:
the ``ssm_step``, ``paged_decode`` and flash kernels a TPU compiles, through the
interpreter, and with them, both of the expert layer's limits at 0 while
Granite's programs are traced, the grouped SwiGLU kernel that a riding 512 rung
takes on the chip):

(a) a riding step IS a prefill and a decode step: against the parent's two
    programs (``serve_prefill``'s, which the engine still builds for the
    benchmark's rehearsal and never runs, and the decode step) the decode rows'
    logits and ids, the prompt's row, every slot's pages, state and tail agree
    at the family's tolerance, at each rung and with the prompt shorter than it;
(b) an IDLE row leaves its slot's state and tail as they were, BIT FOR BIT: a
    slot whose prompt still waits while a step carries another, and every slot
    in the middle of its output while a prompt is launched alone (the program
    then runs with every decode row idle: a body that stepped every slot, as the
    plain decode step's does, would move them).  The plain step's idle rows hold
    no request, or one whose prompt will rewrite them: their state is nobody's;
(c) through ``run_serve_resilient`` every stream is the same with the offer
    and with it hidden, cancellations while a prompt rides and while it waits
    included;
(d) an engine of a module without ``serve_ride`` (the other five families), and
    a block engine whatever its module gives, offer nothing and report no
    ``prefill_rides``;
(e) a riding step is ONE launch of the decode kind, tagged ``rung`` and
    ``slot``, and its module is ``jit_decode``; nothing compiles after ``warm()``;
(f) ``prefill_rides``, ``prefill_launches``, ``prefill_scan_chunks`` and
    ``prefill_tokens_padded`` count a prompt once, rode or alone, and
    ``moe_expert_layer_calls`` a launched program once;
(g) the engine's ``moe_*`` counters are of DECODE positions with riders as
    without: a riding step returns the counts of its decode rows alone, so
    ``moe_assignments_held`` and ``moe_assignments`` of a run with riders are
    those of the same requests with the offer hidden, to the integer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_falcon_h1
import test_granite_hybrid
import test_program_identity as identity
from test_serve_ride import NoOffer
from vescale_tpu.mesh import DeviceMesh
from vescale_tpu.models import falcon_h1 as fh
from vescale_tpu.models import granite_hybrid as gh
from vescale_tpu.models import sdar_moe
from vescale_tpu.moe import dropless
from vescale_tpu.ndtimeline import api as nd
from vescale_tpu.ndtimeline import predefined as P
from vescale_tpu.serve import (ContinuousBatchingScheduler, DecodeFeed, HybridServeEngine, PagedKVCache, PrefillStep, Request,
                               run_serve_resilient)
from vescale_tpu.serve import hybrid_engine
from vescale_tpu.serve.hybrid_engine import hybrid_cache_config

SLOTS, PAGE, PAGES = 4, 4, 8        # 32 positions a slot: rungs 8 / 16 / 32 (the chunk is 8)
# the families whose modules give the body: each one's toy (its test file: ``toy_config``, ``tokens``, ``rel``, and
# ``TIGHT``, float32 against float32 with the sums in another order, the family's own tolerance) and its module
FAMILIES = {"falcon_h1": (test_falcon_h1, fh), "granite_hybrid": (test_granite_hybrid, gh)}


def _engine(cfg, params):
    mesh = DeviceMesh(("tp",), (1,), devices=jax.devices()[:1])
    cache = PagedKVCache(hybrid_cache_config(cfg, num_slots=SLOTS, page_size=PAGE, pages_per_slot=PAGES), mesh)
    return HybridServeEngine(cfg, mesh, params, cache).warm(), cache


@pytest.fixture(scope="module", params=[(family, leg) for family in FAMILIES for leg in (None, "interpret")],
                ids=lambda param: f"{param[0]}-{'kernels_interpreted' if param[1] else 'xla_legs'}")
def pair(request):
    """Two engines of one family over the same weights, built (and so latched)
    under one leg: the one that rides, and one that is only ever driven through
    the parent's two programs (:func:`prefill_apart`, then ``decode``).  Each
    engine bears its family's toy (``engine.toy``)."""
    family, leg = request.param
    toy, model = FAMILIES[family]
    with pytest.MonkeyPatch.context() as patch:
        if leg is None:
            patch.delenv("VESCALE_KERNELS", raising=False)
        else:
            # ... and, as ``tests/test_granite_hybrid.py``'s own fixture does, both of the expert layer's limits at 0
            # while the programs are traced: every one then holds the sorted form on the leg a TPU takes, the grouped kernel
            patch.setenv("VESCALE_KERNELS", leg)
            patch.setattr(dropless, "DENSE_MAX_TOKENS", 0)
            patch.setattr(dropless, "PADDED_MAX_MEAN_ROWS", 0)
        cfg = toy.toy_config()
        params = jax.jit(lambda k: model.init_params(cfg, k))(jax.random.key(7))
        rides, apart = _engine(cfg, params), _engine(cfg, params)
    assert rides[0].rides and rides[0].kernel_ssm_step == rides[0].kernel_decode == (leg == "interpret")
    rides[0].warmed = _programs(rides[0])
    rides[0].toy = apart[0].toy = toy
    return rides, apart


def _programs(eng):
    """How many signatures each program has been called with: one more after ``warm()`` is a program lowered (and,
    with an empty compile cache, compiled) under a request; a counter of compiles alone does not see a cache's hit."""
    return eng._ride_fn._cache_size(), eng._decode_fn._cache_size(), eng._merge_fn._cache_size()


def prefill_apart(eng, cache, prompt, slot):
    """The parent's prefill: ``serve_prefill``'s program, launched alone at once."""
    n = len(prompt)
    bucket = next(b for b in eng.buckets if b >= n)
    toks = np.zeros((bucket,), np.int32)
    toks[:n] = prompt
    return eng._launched_prefill(*eng._run_prefill(toks, n, cache.page_table[slot, : bucket // PAGE].copy(), slot), slot)


def _host(cache, feed):
    toks = np.zeros((cache.num_slots,), np.int32)
    for slot, tok in feed.items():
        toks[slot] = tok
    return toks


def _held(cache, slot):
    """What ``slot`` holds on the device: its live positions of both pools, its state and its tail, every layer."""
    n = int(cache.lengths[slot])
    pages = cache.page_table[slot, : cache.pages_needed(n)]
    k, v = np.asarray(cache.k.data), np.asarray(cache.v.data)
    flat = lambda pool: pool[:, pages].reshape(pool.shape[0], -1, *pool.shape[3:])[:, :n]
    return {"k": flat(k), "v": flat(v), "ssm": np.asarray(cache.state["ssm"])[:, slot], "conv": np.asarray(cache.state["conv"])[:, slot]}


def _bits(cache, slot):
    return tuple(np.asarray(cache.state[name])[:, slot].tobytes() for name in ("ssm", "conv"))


def _delta(eng, start):
    return {k: v - start[k] for k, v in eng.trace_counters().items()}


def _routed(d):
    """What a run's decode positions gave the experts (a dense family: nothing)."""
    return d["moe_assignments"], d["moe_assignments_held"]


# ------------------------------------------------------------ (a) the program
@pytest.mark.parametrize("n", [8, 13, 16, 27], ids=["rung8_full", "rung16_short", "rung16_full", "rung32_short"])
def test_a_riding_step_is_a_prefill_and_a_decode_step(pair, n):
    rung = next(b for b in (8, 16, 32) if b >= n)
    toy = pair[0][0].toy
    tokens, rel = toy.tokens, toy.rel
    got = {}
    for ride, (eng, cache) in zip((True, False), pair):
        cache.reset()
        start = eng.trace_counters()
        launch = (lambda prompt, slot: eng.prefill(prompt, slot)) if ride else (lambda prompt, slot: prefill_apart(eng, cache, prompt, slot))
        a, b = cache.alloc(13, 10), cache.alloc(6, 10)
        firsts = {}
        for slot, (seed, length) in ((a, (1, 13)), (b, (2, 6))):
            firsts[slot] = launch(tokens(seed, length), slot).token      # (riding: read, so launched ALONE, every decode row idle)
            cache.commit_prefill(slot, length)
        s0 = eng.decode(_host(cache, firsts))
        cache.advance(a), cache.advance(b)
        c = cache.alloc(n, 4)
        w = launch(tokens(3, n), c)
        cache.commit_prefill(c, n)
        if ride:
            assert isinstance(w, PrefillStep) and not w.launched and (w.rung, w.slot) == (rung, c) and w.shape == (toy.TOY["vocab_size"],)
            s1 = eng.decode(DecodeFeed(s0, rider=w))            # the step carries it: slot c is not stepped
            cache.advance(a), cache.advance(b)
            assert w.launched and not w.read and int(cache.lengths[c]) == n
            s2 = eng.decode(DecodeFeed(s1, {c: w}))             # ... and the step after takes its first id from the device
            assert not w.read
            cache.advance(a), cache.advance(b), cache.advance(c)
            after, after_ids = np.asarray(s2)[[a, b, c]], s2.tokens[[a, b, c]]
            held_c = _held(cache, c)
            d = _delta(eng, start)
            assert (d["prefill_rides"], d["prefill_launches"], d["prefill_reads_ahead"]) == (1, 3, 1)
        else:
            assert w.launched
            s1 = eng.decode(DecodeFeed(s0, {c: w.token}))       # the step that follows it steps slot c as well
            cache.advance(a), cache.advance(b), cache.advance(c)
            held_c = _held(cache, c)                            # (the step after would move c's state once more: it holds a request)
            s2 = eng.decode(DecodeFeed(s1))
            cache.advance(a), cache.advance(b)
            after = np.concatenate([np.asarray(s2)[[a, b]], np.asarray(s1)[[c]]])
            after_ids = np.concatenate([s2.tokens[[a, b]], s1.tokens[[c]]])
            assert "prefill_rides" in eng.trace_counters() and _delta(eng, start)["prefill_rides"] == 0
        got[ride] = dict(step=np.asarray(s1)[[a, b]], ids=s1.tokens[[a, b]], row=np.asarray(w), first=w.token, after=after,
                         after_ids=after_ids, routed=_routed(_delta(eng, start)), **{f"{name}[{slot}]": value for slot in (a, b, c)
                                                 for name, value in (held_c if slot == c else _held(cache, slot)).items()})
        assert not eng._waiting
    rode, apart = got[True], got[False]
    for key in rode:
        if key not in ("ids", "first", "after_ids", "routed"):
            assert rel(rode[key], apart[key]) < toy.TIGHT, key
    # the expert counts are of decode positions: seven where the prompt rode (2 + 2 + 3: the riding step's counts are of
    # its two decode rows alone, the rung's rows are none), eight apart (2 + 3 + 3: the step after steps slot c once more)
    eng = pair[0][0]
    pairs_a_position = getattr(eng.config, "num_experts_per_tok", 0) * eng._expert_layers
    assert (rode["routed"][0], apart["routed"][0]) == (7 * pairs_a_position, 8 * pairs_a_position)
    assert all(0 <= held <= assignments and (held > 0) == bool(eng._expert_layers) for assignments, held in (rode["routed"], apart["routed"]))
    assert rode["first"] == apart["first"] == int(np.argmax(rode["row"]))
    assert np.array_equal(rode["ids"], apart["ids"]) and np.array_equal(rode["after_ids"], apart["after_ids"])


# ------------------------------------------------------ (b) an idle row's state
def test_an_idle_rows_state_and_tail_are_the_same_bits_after_a_riding_step_and_a_prompt_launched_alone(pair):
    (eng, cache), (ref, ref_cache) = pair
    tokens, rel, TIGHT = eng.toy.tokens, eng.toy.rel, eng.toy.TIGHT
    olds, news = ((11, 13), (12, 6), (13, 9), (16, 11)), ((14, 5), (15, 20))       # (seed, length) of each prompt
    cache.reset()
    # slots a and b in the middle of their outputs; y and z held tenants, which left their state behind
    slots = [cache.alloc(length, 10) for _seed, length in olds]
    a, b, y, z = slots
    firsts = {}
    for slot, (seed, length) in zip(slots, olds):
        firsts[slot] = eng.prefill(tokens(seed, length), slot).token
        cache.commit_prefill(slot, length)
    s0 = eng.decode(_host(cache, firsts))
    for slot in slots:
        cache.advance(slot)
    s0.tokens
    cache.free(y), cache.free(z)
    c, x = (cache.alloc(length, 4) for _seed, length in news)
    assert {c, x} == {y, z}, "the two new requests take the slots the old tenants left"
    rider, younger = (eng.prefill(tokens(seed, length), slot) for slot, (seed, length) in zip((c, x), news))
    cache.commit_prefill(c, 5), cache.commit_prefill(x, 20)
    seen = {slot: _bits(cache, slot) for slot in (a, b, x)}
    assert np.frombuffer(seen[x][0], np.float32).any(), "a state of zeros would stand under any body"
    s1 = eng.decode(DecodeFeed(s0, rider=rider))                # a and b step; c rides; x's prompt still waits: an idle row
    cache.advance(a), cache.advance(b)
    assert rider.launched and not younger.launched and eng._waiting == [younger]
    assert _bits(cache, x) == seen[x], "a slot whose prompt waits kept its state and tail through the riding step"
    assert _bits(cache, a) != seen[a] and _bits(cache, b) != seen[b], "the active rows stepped"
    seen = {slot: _bits(cache, slot) for slot in (a, b, c, x)}
    younger.token                                               # nobody carried it: launched ALONE, every decode row idle
    assert younger.launched and not eng._waiting
    for slot in (a, b, c):
        assert _bits(cache, slot) == seen[slot], "a slot in the middle of its output did not move under a prompt launched alone"
    assert _bits(cache, x) != seen[x], "... and the prompt's own state and tail are over its slot's rows"
    # the streams go on as if nothing had happened beside them: the next step against the engine that never rode
    got = np.asarray(eng.decode(DecodeFeed(s1, {c: rider, x: younger})))
    ref_cache.reset()
    for slot, (seed, length) in zip(slots, olds):
        assert ref_cache.alloc(length, 10) == slot
        prefill_apart(ref, ref_cache, tokens(seed, length), slot)
        ref_cache.commit_prefill(slot, length)
    r0 = ref.decode(_host(ref_cache, firsts))
    for slot in slots:
        ref_cache.advance(slot)
    ref_cache.free(y), ref_cache.free(z)
    r1 = ref.decode(DecodeFeed(r0))                             # (a plain step moves every slot that holds a request: a and b)
    ref_cache.advance(a), ref_cache.advance(b)
    assert tuple(ref_cache.alloc(length, 4) for _seed, length in news) == (c, x)
    fresh = {slot: prefill_apart(ref, ref_cache, tokens(seed, length), slot).token for slot, (seed, length) in zip((c, x), news)}
    ref_cache.commit_prefill(c, 5), ref_cache.commit_prefill(x, 20)
    want = np.asarray(ref.decode(DecodeFeed(r1, fresh)))
    assert (rider.token, younger.token) == (fresh[c], fresh[x]) and rel(got[[a, b, c, x]], want[[a, b, c, x]]) < TIGHT


# --------------------------------------------------------------- (c) the loop
def _serve(engine, cache, arrivals, **kw):
    cache.reset()
    sched = ContinuousBatchingScheduler(cache, max_queue=32)
    res = run_serve_resilient(engine=engine, scheduler=sched, arrivals=arrivals, install_signal_handlers=False, coordinate=False, **kw)
    sched.ledger_check()
    cache.reset()
    return sched, res


def _golden(eng, cache, req):
    cache.reset()
    return eng.replay_greedy(req.prompt, req.max_new_tokens, eos_id=req.eos_id)


def test_every_stream_is_the_same_with_the_offer_and_with_it_hidden(pair, tmp_path):
    """One long request keeps a step in flight; behind it arrive two requests in ONE iteration, a request of one
    token, and one whose first token is its EOS.  Riding, behind ``NoOffer`` and ``replay_greedy`` agree."""
    (eng, cache), _ = pair
    tokens = eng.toy.tokens
    eos_prompt = tuple(tokens(24, 14))
    eos = _golden(eng, cache, Request(rid=0, prompt=eos_prompt, max_new_tokens=1))[0]
    reqs = [(0, Request(rid=0, prompt=tuple(tokens(20, 7)), max_new_tokens=16)),
            (2, Request(rid=1, prompt=tuple(tokens(21, 12)), max_new_tokens=4)), (2, Request(rid=2, prompt=tuple(tokens(22, 22)), max_new_tokens=4)),
            (7, Request(rid=3, prompt=tuple(tokens(23, 9)), max_new_tokens=1)),
            (9, Request(rid=4, prompt=eos_prompt, max_new_tokens=5, eos_id=eos))]
    want = {req.rid: _golden(eng, cache, req) for _, req in reqs}
    assert want[4] == [eos] and len(want[3]) == 1
    start = eng.trace_counters()
    nd.start_trace_session(str(tmp_path / "riding"), profiler=False)
    try:
        _, res = _serve(eng, cache, reqs)
    finally:
        compiles = nd.stop_trace_session().counters["backend_compiles"]
    rode = {rid: o["tokens"] for rid, o in res.outcomes.items()}
    d = _delta(eng, start)
    # the first request finds no step in flight and goes alone; of the two admitted together one rides the step
    # about to be launched and the other the step after it
    assert (d["prefill_launches"], d["prefill_rides"], d["prefill_reads_ahead"]) == (5, 4, 4) and compiles == 0
    assert d["decode_steps_ahead"] == d["decode_steps"] - 1, "one cold start: no prompt, riding or alone, broke the pipeline"
    start = eng.trace_counters()
    _, res = _serve(NoOffer(eng), cache, reqs)
    d = _delta(eng, start)
    assert (d["prefill_launches"], d["prefill_rides"]) == (5, 0)
    assert _programs(eng) == eng.warmed, "riding or alone, fed by the host, a step or a prefill: every call found its program warmed"
    assert rode == {rid: o["tokens"] for rid, o in res.outcomes.items()} == want
    assert all(o["status"] == "completed" for o in res.outcomes.values())


@pytest.mark.parametrize("when", ["while_it_rides", "while_it_waits"])
def test_a_request_cancelled_under_its_prompt_leaves_every_other_stream_as_it_was(pair, when):
    """Two admitted in one iteration behind a step in flight: rid 1 rides this iteration's step, rid 2 waits for the
    next.  Cancelled from ``on_step`` (the rider: its first token, read a step late, is dropped; the one that
    waits: its prompt is still the engine's to launch, alone, BEFORE the prompt of the request that takes its
    slot), with the offer and with it hidden every other stream is ``replay_greedy``'s."""
    (eng, cache), _ = pair
    tokens = eng.toy.tokens
    reqs = [(0, Request(rid=0, prompt=tuple(tokens(32, 7)), max_new_tokens=14)),
            (2, Request(rid=1, prompt=tuple(tokens(33, 12)), max_new_tokens=5)), (2, Request(rid=2, prompt=tuple(tokens(34, 20)), max_new_tokens=5)),
            (3, Request(rid=3, prompt=tuple(tokens(35, 10)), max_new_tokens=5))]
    victim = 1 if when == "while_it_rides" else 2
    want = {req.rid: _golden(eng, cache, req) for _, req in reqs}
    for face in (eng, NoOffer(eng)):
        cache.reset()
        sched = ContinuousBatchingScheduler(cache, max_queue=8)

        def on_step(step, active):
            if step == 2:
                (slot,) = [s for s, inf in sched.active.items() if inf.req.rid == victim]
                sched.timeout(slot, reason=f"cancelled {when}")

        start = eng.trace_counters()
        res = run_serve_resilient(engine=face, scheduler=sched, arrivals=reqs, install_signal_handlers=False, coordinate=False,
                                  on_step=on_step)
        sched.ledger_check()
        cache.reset()
        d = _delta(eng, start)
        assert not eng._waiting and d["prefill_launches"] == 4, "the cancelled prompt went through the stack all the same"
        if face is eng:
            assert d["prefill_rides"] == (3 if victim == 1 else 2), "a prompt that waited when its request went is launched alone"
        # (riding, its first token is read a step late: dropped; behind the other face it may have been recorded)
        assert res.outcomes[victim]["status"] == "timed_out" and res.outcomes[victim]["tokens"] == ([] if face is eng else want[victim][:1])
        assert all(res.outcomes[rid]["tokens"] == want[rid] for rid in want if rid != victim)


# ----------------------------------------------------------------- (d) the offer
@pytest.mark.parametrize("family", [f for f in identity.FAMILIES if f not in FAMILIES])
def test_an_engine_of_a_module_without_the_body_offers_nothing(family):
    with pytest.MonkeyPatch.context() as patch:
        engine, _params = identity._engine(family, "xla_legs", patch)
    assert not hasattr(engine.model, "serve_ride") and engine.rides is False and engine._ride_fn is None
    assert "prefill_rides" not in engine.trace_counters() and "prefill_launches" in engine.trace_counters()
    assert not engine._waiting


def test_a_block_engine_offers_nothing_whatever_its_module_gives_and_the_two_that_give_it_offer():
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(sdar_moe, "serve_ride", fh.serve_ride, raising=False)
        engine, _params = identity._engine("sdar_moe", "xla_legs", patch)
        assert engine.block is not None and hasattr(engine.model, "serve_ride") and engine.rides is False
        assert "prefill_rides" not in engine.trace_counters()
        for family in FAMILIES:
            engine, _params = identity._engine(family, "xla_legs", patch)
            assert engine.rides is True and "prefill_rides" in engine.trace_counters() and engine._ride_fn is not None
    # the offer is the instance's, by its model: the class has none, and what every model's engine counts does not name it
    assert not hasattr(HybridServeEngine, "rides") and "prefill_rides" not in hybrid_engine.COUNTERS


# ------------------------------------------------------------------ (e) spans
def test_a_riding_step_is_one_launch_of_the_decode_kind_and_its_module_is_jit_decode(pair, tmp_path):
    (eng, cache), _ = pair
    tokens = eng.toy.tokens
    cache.reset()
    a = cache.alloc(6, 8)
    first = eng.prefill(tokens(70, 6), a).token
    cache.commit_prefill(a, 6)
    s0 = eng.decode(_host(cache, {a: first}))
    cache.advance(a)
    nd.start_trace_session(str(tmp_path / "session"), profiler=False)
    try:
        b = cache.alloc(13, 8)
        rider = eng.prefill(tokens(71, 13), b)
        cache.commit_prefill(b, 13)
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
    assert carrying.tags == {"launch": number, "rung": 16, "slot": b} and after.tags == {"launch": number + 1}
    assert not named(P.SERVE_PREFILL_LAUNCH) and len(named(P.SERVE_PREFILL_CALL)) == 1
    (fetch,) = named(P.SERVE_PREFILL_FETCH)
    assert fetch.tags == {"launch": number}, "the rider's read names the launch that carried it"
    assert sorted(s.tags["launch"] for s in named(P.SERVE_DECODE_FETCH)) == [number - 1, number, number + 1]
    c = session.counters
    assert (c["decode_launches"], c["prefill_launches"], c["prefill_rides"], c["prefill_reads_ahead"]) == (2, 1, 1, 1)
    assert c["backend_compiles"] == 0 and eng.launches == number + 2
    # the program's name on the device's ``XLA Modules`` line is its function's: the benchmark joins launches by it
    i32 = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.int32)
    held = tuple(jax.ShapeDtypeStruct(x.shape, x.dtype) for x in eng._held())
    lowered = eng._ride_fn.lower(eng.params, *held, i32(SLOTS, PAGES), i32(SLOTS), i32(SLOTS), i32(SLOTS), i32(16), i32(), i32(16 // PAGE), i32())
    assert lowered.as_text().lstrip().startswith("module @jit_decode ")
    assert eng._decode_fn.lower(eng.params, *held, i32(SLOTS, PAGES), i32(SLOTS), i32(SLOTS)).as_text().lstrip().startswith("module @jit_decode ")
    # (the first rung twice: once over the cache's arrays as they were allocated)
    assert eng.buckets == [8, 16, 32] and _programs(eng) == eng.warmed and eng.warmed[0] == 4, "one program a rung, each warmed"
    assert eng._prefill_fn._cache_size() == 0, "no program is a prefill's alone"


# --------------------------------------------------------------- (f) counters
def test_a_prompt_is_counted_once_whether_it_rode_or_went_alone(pair):
    (eng, cache), _ = pair
    tokens = eng.toy.tokens
    cache.reset()
    chunk = eng.toy.TOY["mamba_chunk_size"]
    start = eng.trace_counters()
    a = cache.alloc(6, 8)
    first = eng.prefill(tokens(80, 6), a)                   # rung 8
    at_the_call = _delta(eng, start)
    assert (at_the_call["prefill_tokens_real"], at_the_call["prefill_tokens_padded"], at_the_call["prefill_bucket_tokens"],
            at_the_call["prefill_launches"]) == (6, 8, 8, 0)
    # (a family's own counter of the scan's chunks, where it keeps one, and the program the prompt will go in, its
    # expert layers counted now: the rung's rows beside every slot's)
    assert at_the_call.get("prefill_scan_chunks", 8 // chunk) == 8 // chunk
    assert at_the_call["moe_expert_layer_calls"] == eng._expert_layers and eng._prompt_rows[8] == 8 + SLOTS
    cache.commit_prefill(a, 6)
    s0 = eng.decode(_host(cache, {a: first.token}))         # read: it went alone
    cache.advance(a)
    b = cache.alloc(13, 8)
    rider = eng.prefill(tokens(81, 13), b)                  # rung 16
    cache.commit_prefill(b, 13)
    s1 = eng.decode(DecodeFeed(s0, rider=rider))            # ... and this one rode
    cache.advance(a)
    np.asarray(eng.decode(DecodeFeed(s1, {b: rider})))
    d = _delta(eng, start)
    cache.reset()
    assert (d["prefill_launches"], d["prefill_rides"], d["prefill_reads_ahead"]) == (2, 1, 1)
    assert (d["prefill_tokens_real"], d["prefill_tokens_padded"], d["prefill_bucket_tokens"]) == (19, 24, 24)
    if "prefill_scan_chunks" in d:
        assert d["prefill_scan_chunks"] * chunk == d["prefill_bucket_tokens"], "the benchmark's own identity, at any edge of a session"
    # four programs were launched (a prompt alone, a step, the step that carried the other prompt, a step): each one's
    # expert layers once, and under the interpreted kernels (the sorted form whatever the rows) all the grouped kernel
    assert d["moe_expert_layer_calls"] == 4 * eng._expert_layers
    assert d["moe_grouped_layer_calls"] == (4 * eng._expert_layers if eng.kernel_decode else 0)
    assert (d["decode_launches"], d["decode_steps"]) == (3, 3) and eng.launches - (start["decode_launches"] + start["prefill_launches"]
                                                                                  - start["prefill_rides"]) == 4
    assert d["ssm_state_bytes_rw"] == 3 * 2 * SLOTS * cache.state_bytes_per_slot(), "a step's state traffic, carrying or not"


# ------------------------------------------------- (g) the expert counters' meaning
def test_the_expert_counters_are_of_decode_positions_with_riders_as_with_the_offer_hidden(pair):
    """One long request keeps a step in flight and three more arrive behind it, two of them in one iteration, so
    prompts ride steps of one, two and three decode rows.  A riding step's expert layer runs over its decode rows and
    the prompt's together, but what it returns under ``counts["experts"]`` is the decode rows' alone: the engine's
    ``moe_assignments`` (decode positions x experts a token x layers) and ``moe_assignments_held`` (those that fell on
    an expert held here: Granite's toy holds 4 of 8) are, to the integer, what the same requests give with the offer
    hidden, when every prompt goes alone and no step ever holds a prompt's rows."""
    (eng, cache), _ = pair
    tokens = eng.toy.tokens
    reqs = [(0, Request(rid=0, prompt=tuple(tokens(90, 9)), max_new_tokens=14)),
            (2, Request(rid=1, prompt=tuple(tokens(91, 12)), max_new_tokens=6)), (2, Request(rid=2, prompt=tuple(tokens(92, 21)), max_new_tokens=5)),
            (5, Request(rid=3, prompt=tuple(tokens(93, 5)), max_new_tokens=4))]
    seen = {}
    for face in (eng, NoOffer(eng)):
        start = eng.trace_counters()
        _, res = _serve(face, cache, reqs)
        d = _delta(eng, start)
        seen[face is eng] = (_routed(d), d["moe_layer_steps"], {rid: o["tokens"] for rid, o in res.outcomes.items()})
        assert d.get("ride_candidate_layer_steps", 0) == 0, "no toy rung is a candidate for the pad under these limits"
        assert d["prefill_rides"] == (3 if face is eng else 0) and d["prefill_launches"] == 4
        assert d["moe_expert_layer_calls"] == (d["decode_launches"] + d["prefill_launches"] - d["prefill_rides"]) * eng._expert_layers
    assert seen[True] == seen[False]
    (assignments, held), _steps, _streams = seen[True]
    if eng._expert_layers:      # (Falcon-H1 is dense: nothing is routed, and the counters stay 0 on both sides)
        c = eng.config
        assert 0 < held < assignments and assignments % (c.num_experts_per_tok * c.num_hidden_layers) == 0
    else:
        assert (assignments, held) == (0, 0)
