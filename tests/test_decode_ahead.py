"""The decode pipeline one step deep (``run_serve_resilient`` launches step k
before it has read step k-1's ids; ``DecodeAhead.decode`` of both engines takes
the ids from the device): every request's stream stays what
``engine.replay_greedy`` gives, on a tiny Llama, Granite and DeepSeek-V2 engine,
under staggered arrivals, EOS at every position, budgets of 1 and 2, injected
faults and a cancellation from ``on_step`` with a step in flight; a prefill one
step deep as well (``prefill`` launches and returns its ``PrefillStep`` unread;
the loop reads it after the decode step that takes its id from the device is
enqueued); and the pins the benchmark's harness holds the loop and the engines to."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from test_deepseek_v2 import toy_config as deepseek_toy
from test_granite_hybrid import toy_config as granite_toy
from vescale_tpu.mesh import DeviceMesh
from vescale_tpu.models import deepseek_v2 as ds
from vescale_tpu.models import granite_hybrid as gh
from vescale_tpu.models.llama import Llama, LlamaConfig
from vescale_tpu.ndtimeline import api as nd
from vescale_tpu.resilience import faultsim
from vescale_tpu.serve import (ContinuousBatchingScheduler, DecodeFeed, DecodeStep, HybridServeEngine, KVCacheConfig,
                               PagedKVCache, PrefillStep, Request, ServeEngine, run_serve_resilient)
from vescale_tpu.serve.hybrid_engine import hybrid_cache_config

SLOTS, PAGE, PAGES = 3, 4, 8          # 32 positions a slot
LLAMA = LlamaConfig(vocab_size=96, hidden_size=16, intermediate_size=32, num_hidden_layers=2, num_attention_heads=2,
                    num_key_value_heads=2, max_position_embeddings=64, dtype=jnp.float32)


@pytest.fixture(scope="module", params=["llama", "granite", "deepseek_v2"])
def rig(request):
    """(engine, cache): a warmed toy engine of each of the three models that serve."""
    mesh = DeviceMesh(("tp",), (1,), devices=jax.devices()[:1])
    if request.param == "llama":
        params = Llama(LLAMA).init(jax.random.key(0), jnp.ones((1, 8), jnp.int32))["params"]
        cache = PagedKVCache(KVCacheConfig(layers=2, kv_heads=2, head_dim=LLAMA.head_dim, num_slots=SLOTS,
                                           page_size=PAGE, pages_per_slot=PAGES), mesh)
        return ServeEngine(LLAMA, mesh, params, cache).warm(), cache
    cfg, model = (granite_toy(), gh) if request.param == "granite" else (deepseek_toy(), ds)
    params = jax.jit(lambda k: model.init_params(cfg, k))(jax.random.key(7))
    cache = PagedKVCache(hybrid_cache_config(cfg, num_slots=SLOTS, page_size=PAGE, pages_per_slot=PAGES), mesh)
    return HybridServeEngine(cfg, mesh, params, cache).warm(), cache


def _prompt(seed, n):
    return tuple(int(t) for t in np.random.default_rng(seed).integers(1, 90, n))


def _run(rig, arrivals, **kw):
    eng, cache = rig
    cache.reset()
    sched = ContinuousBatchingScheduler(cache, max_queue=32)
    res = run_serve_resilient(engine=eng, scheduler=sched, arrivals=arrivals, install_signal_handlers=False,
                              coordinate=False, **kw)
    sched.ledger_check()
    cache.reset()
    return res, sched


def _golden(rig, req):
    eng, cache = rig
    cache.reset()
    return eng.replay_greedy(req.prompt, req.max_new_tokens, eos_id=req.eos_id)


def _eos_at(rig, prompt, budget, position):
    """A request whose greedy stream meets its EOS first at ``position`` (None where the stream repeats a token
    before it: the test then skips that case, it cannot be built)."""
    stream = _golden(rig, Request(rid=0, prompt=prompt, max_new_tokens=budget))
    if stream[position] in stream[:position]:
        return None
    return stream[position]


# ----------------------------------------------------------------- streams
def test_every_stream_is_replay_greedys_under_staggered_arrivals_eos_anywhere_and_budgets_of_one_and_two(rig):
    """Nine requests over three slots (so freed slots are taken again while a step is in flight), arriving
    between steps; EOS at the first, a middle and the last position; ``max_new_tokens`` 1 and 2."""
    reqs, budget = [], 6
    for rid, (arrive, n) in enumerate([(0, 5), (0, 3), (1, 7), (2, 4), (2, 6), (4, 3), (5, 8), (9, 5), (9, 4)]):
        reqs.append((arrive, Request(rid=rid, prompt=_prompt(100 + rid, n), max_new_tokens=budget)))
    for rid, position in ((1, 0), (3, 2), (4, budget - 1)):
        eos = _eos_at(rig, reqs[rid][1].prompt, budget, position)
        if eos is not None:
            reqs[rid] = (reqs[rid][0], Request(rid=rid, prompt=reqs[rid][1].prompt, max_new_tokens=budget, eos_id=eos))
    reqs[5] = (reqs[5][0], Request(rid=5, prompt=reqs[5][1].prompt, max_new_tokens=1))
    reqs[6] = (reqs[6][0], Request(rid=6, prompt=reqs[6][1].prompt, max_new_tokens=2))
    want = {req.rid: _golden(rig, req) for _, req in reqs}
    res, _ = _run(rig, reqs)
    assert res.status == "completed" and set(res.outcomes) == set(want)
    for rid, out in res.outcomes.items():
        assert out["status"] == "completed" and out["tokens"] == want[rid], rid
    assert len(want[5]) == 1 and len(want[6]) <= 2
    assert any(len(want[rid]) < budget for rid in (1, 3, 4)), "no EOS case could be built: choose other prompts"


@pytest.mark.parametrize("fault, step", [("oom", 3), ("oom", 4), ("request_timeout", 3), ("request_timeout", 5)])
def test_an_injected_fault_with_a_step_in_flight_ends_all_terminal_with_the_outcomes_of_a_loop_that_reads_every_step(
        rig, fault, step):
    """Three requests on three slots from step 0 (nothing queues, so every boundary finds the slots a loop
    that read each step at once would find): the boundary that evicts or cancels reads the step in flight
    first, so the victim holds one token of its prefill and one of each decode step before that boundary."""
    reqs = [(0, Request(rid=rid, prompt=_prompt(40 + rid, 4 + rid), max_new_tokens=8)) for rid in range(3)]
    want = {req.rid: _golden(rig, req) for _, req in reqs}
    faultsim.arm(faultsim.parse_schedule(f"{fault}:step={step}"))
    try:
        res, sched = _run(rig, reqs)
    finally:
        faultsim.disarm()
    assert res.status == "completed" and sched.all_terminal()
    if fault == "oom":
        assert res.counts["evicted"] == res.counts["requeued"] == 1
        assert sorted(o["replays"] for o in res.outcomes.values()) == [0, 0, 1]
        assert all(o["status"] == "completed" and o["tokens"] == want[rid] for rid, o in res.outcomes.items())
    else:
        (timed,) = [rid for rid, o in res.outcomes.items() if o["status"] == "timed_out"]
        assert timed == 0 and "request_timeout" in res.outcomes[0]["reason"]
        assert res.outcomes[0]["tokens"] == want[0][: 1 + step]       # the prefill's and one a step before the boundary
        assert all(res.outcomes[rid]["tokens"] == want[rid] for rid in (1, 2))


def test_a_step_deadline_cancels_with_the_tokens_the_device_had_made_and_completes_what_had_finished(rig):
    """A request whose budget its last step before the deadline fills completes; its neighbour, one token
    short, is cancelled holding every token of the steps launched: the boundary read the step in flight."""
    reqs = [(0, Request(rid=0, prompt=_prompt(1, 4), max_new_tokens=4, deadline_steps=2)),
            (0, Request(rid=1, prompt=_prompt(2, 5), max_new_tokens=5, deadline_steps=2))]
    want = {req.rid: _golden(rig, req) for _, req in reqs}
    res, _ = _run(rig, reqs)
    assert res.outcomes[0]["status"] == "completed" and res.outcomes[0]["tokens"] == want[0]
    assert res.outcomes[1]["status"] == "timed_out" and res.outcomes[1]["tokens"] == want[1][:4]


def test_a_cancellation_from_on_step_mid_flight_loses_and_doubles_no_token_and_the_slots_next_request_gets_none_of_it(rig):
    """The benchmark's close: ``scheduler.timeout(slot)`` from ``on_step`` while a step is in flight.  The
    cancelled requests keep what was recorded, the ids in flight for them are dropped, and the requests
    admitted into the freed slots at the next boundary (the third slot decodes on, so that step is still
    unread then) stream exactly their own tokens."""
    eng, cache = rig
    first = [(0, Request(rid=rid, prompt=_prompt(60 + rid, 5), max_new_tokens=12)) for rid in range(SLOTS)]
    late = [(0, Request(rid=SLOTS + rid, prompt=_prompt(80 + rid, 4), max_new_tokens=5)) for rid in range(2)]
    want = {req.rid: _golden(rig, req) for _, req in first + late}
    cache.reset()
    sched = ContinuousBatchingScheduler(cache, max_queue=32)
    seen = {}

    def on_step(step, active):
        if step == 3:
            for slot in sorted(sched.active):
                inf = sched.active[slot]
                if inf.req.rid in (0, 1):
                    seen[inf.req.rid] = (slot, list(inf.tokens))
                    sched.timeout(slot, reason="cancelled by the test")
        if step == 4:       # the freed slots are taken again, with the step launched at 3 read only now
            assert {slot: inf.req.rid for slot, inf in sched.active.items() if inf.req.rid >= SLOTS} == {
                seen[0][0]: SLOTS, seen[1][0]: SLOTS + 1}

    res = run_serve_resilient(engine=eng, scheduler=sched, arrivals=first + late, install_signal_handlers=False,
                              coordinate=False, on_step=on_step)
    sched.ledger_check()
    assert sorted(seen) == [0, 1]
    for rid, (_, tokens) in seen.items():
        out = res.outcomes[rid]
        assert out["status"] == "timed_out" and out["tokens"] == tokens == want[rid][: len(tokens)]
        assert 2 <= len(tokens) < 12
    for rid in (2, SLOTS, SLOTS + 1):
        assert res.outcomes[rid]["status"] == "completed" and res.outcomes[rid]["tokens"] == want[rid], rid
    cache.reset()


# ------------------------------------------------------- a prefill, one step deep
def test_a_prefill_returns_unread_and_gives_its_row_and_its_rows_argmax_when_asked(rig):
    eng, cache = rig
    cache.reset()
    slot = cache.alloc(5, 4)
    start = eng.trace_counters()
    step = eng.prefill(_prompt(3, 5), slot)
    cache.commit_prefill(slot, 5)
    vocab = eng.config.vocab_size
    assert isinstance(step, PrefillStep)
    assert (step.shape, step.dtype) == ((vocab,), np.float32) and not step.read, "neither waits"
    # counted as launched at once; where prompts ride it WAITS for a step to carry it, and this read launches it alone
    rides = getattr(eng, "rides", False)
    assert step.launched != rides and eng.trace_counters()["prefill_launches"] == start["prefill_launches"] + (not rides)
    row = np.asarray(step)
    assert step.launched and eng.trace_counters()["prefill_launches"] == start["prefill_launches"] + 1
    assert step.read and (row.shape, row.dtype) == ((vocab,), np.float32) and np.isfinite(row).all()
    assert step.token == int(np.argmax(row)) == eng.greedy(step)
    assert np.array_equal(np.stack([step, row]), np.stack([row, row])), "stacks with rows, as the reference check does"
    # the decode step it feeds from the device is the step its token feeds from the host
    again = eng.prefill(_prompt(3, 5), slot)
    fed = eng.decode(DecodeFeed(eng.decode(np.zeros((SLOTS,), np.int32)), {slot: again}))
    assert not again.read and eng.trace_counters()["prefill_reads_ahead"] == start["prefill_reads_ahead"] + 1
    toks = np.zeros((SLOTS,), np.int32)
    toks[slot] = again.token
    assert int(eng.decode(toks).tokens[slot]) == int(fed.tokens[slot])
    cache.reset()


def test_a_tie_goes_to_the_lowest_id_in_the_prefills_program_as_on_the_host(rig):
    """Parameters of zeros: every logit of the row is the same (or not a number, which counts as the largest)."""
    eng, cache = rig
    cache.reset()
    slot = cache.alloc(4, 2)
    params, eng.params = eng.params, jax.tree_util.tree_map(jnp.zeros_like, eng.params)
    try:
        step = eng.prefill(_prompt(5, 4), slot)
        row = np.asarray(step)
    finally:
        eng.params = params
    assert len(set(row.tolist())) == 1 or np.isnan(row).all()
    assert step.token == int(np.argmax(row)) == 0
    cache.reset()


def test_a_prefill_that_is_not_its_slots_newest_is_read_before_the_step_and_fed_from_the_host(rig):
    eng, cache = rig
    cache.reset()
    slot = cache.alloc(5, 4)
    stale = eng.prefill(_prompt(3, 5), slot)
    newest = eng.prefill(_prompt(4, 5), slot)       # the slot's place among the firsts now holds this one's id
    cache.commit_prefill(slot, 5)
    start = eng.trace_counters()["prefill_reads_ahead"]
    before = eng.decode(np.zeros((SLOTS,), np.int32))
    a = eng.decode(DecodeFeed(before, {slot: stale}))
    assert stale.read and not newest.read and eng.trace_counters()["prefill_reads_ahead"] == start
    toks = np.zeros((SLOTS,), np.int32)
    toks[slot] = stale.token
    cache.commit_prefill(slot, 5)
    assert int(eng.decode(toks).tokens[slot]) == int(a.tokens[slot])
    cache.reset()


def test_prefills_admitted_behind_a_step_in_flight_are_read_after_the_step_that_takes_their_ids_is_enqueued(rig, tmp_path):
    """One long request keeps a step in flight; two requests admitted in ONE iteration, one of a budget of
    one token and one whose first token is its EOS arrive behind it.  Every stream is ``replay_greedy``'s,
    each of those prefills is read after the launch of the step behind it, the request of one token gets
    no step, the EOS is learned a step late and nothing is recorded after it, each slot is freed once a
    request, the counter says how many ids a step took from the device, and nothing compiles."""
    eng, cache = rig
    long_one = Request(rid=0, prompt=_prompt(20, 5), max_new_tokens=14)
    pair = [Request(rid=1, prompt=_prompt(21, 4), max_new_tokens=3), Request(rid=2, prompt=_prompt(22, 6), max_new_tokens=3)]
    one = Request(rid=3, prompt=_prompt(23, 3), max_new_tokens=1)
    eos_prompt = _prompt(24, 5)
    eos_first = Request(rid=4, prompt=eos_prompt, max_new_tokens=4, eos_id=_eos_at(rig, eos_prompt, 4, 0))
    reqs = [(0, long_one), (2, pair[0]), (2, pair[1]), (7, one), (9, eos_first)]
    want = {req.rid: _golden(rig, req) for _, req in reqs}
    assert want[4] == [eos_first.eos_id] and len(want[3]) == 1

    log, freed = [], []
    prefill, decode, read, free = eng.prefill, eng.decode, eng._read_prefill, cache.free

    def logged_prefill(prompt, slot):
        out = prefill(prompt, slot)
        log.append(("prefill", slot, out))
        return out

    def logged_decode(tokens):
        fresh = dict(tokens.fresh) if isinstance(tokens, DecodeFeed) else None
        assert fresh is None or not any(isinstance(f, PrefillStep) and f.read for f in fresh.values())
        out = decode(tokens)
        log.append(("launch", fresh, tokens.rider if fresh is not None else None))
        return out

    def logged_read(step):
        log.append(("read", None, step))
        return read(step)

    def counted_free(slot):
        freed.append(slot)
        return free(slot)

    eng.prefill, eng.decode, eng._read_prefill, cache.free = logged_prefill, logged_decode, logged_read, counted_free
    start = eng.trace_counters()
    nd.start_trace_session(str(tmp_path / "session"), profiler=False)
    try:
        res, sched = _run(rig, reqs)
    finally:
        counters = nd.stop_trace_session().counters
        del eng.prefill, eng.decode, eng._read_prefill, cache.free
    assert {rid: o["tokens"] for rid, o in res.outcomes.items()} == want
    assert all(o["status"] == "completed" for o in res.outcomes.values()) and len(freed) == len(reqs)

    steps = {rid: out for (what, _, out), (_, req) in zip([e for e in log if e[0] == "prefill"], reqs) for rid in [req.rid]}
    at = {id(e[2]): i for i, e in enumerate(log) if e[0] == "read"}
    fed = {id(f): i for i, e in enumerate(log) if e[0] == "launch" and e[1] for f in e[1].values() if isinstance(f, PrefillStep)}
    launched = {id(e[2]): i for i, e in enumerate(log) if e[0] == "prefill"}
    # the first request finds no step in flight: read at once, and the step after it starts from the host's token
    first_launch = next(i for i, e in enumerate(log) if e[0] == "launch")
    assert at[id(steps[0])] < first_launch and log[first_launch][1] is None and id(steps[0]) not in fed
    a, b = steps[1], steps[2]
    carried = {id(e[2]): i for i, e in enumerate(log) if e[0] == "launch" and e[2] is not None}
    if not getattr(eng, "rides", False):
        # the two of one iteration: both launched, then ONE step that takes both ids from the device, then both read
        assert launched[id(b)] == launched[id(a)] + 1 and fed[id(a)] == fed[id(b)] == launched[id(b)] + 1
        assert (at[id(a)], at[id(b)]) == (fed[id(a)] + 1, fed[id(a)] + 2)
        # a budget of one token: known by count, so no step takes its id; it is read in its own iteration all the same
        assert id(steps[3]) not in fed and id(steps[3]) in at and not carried
        ahead = 3
    else:
        # where prompts ride, the first of the two RIDES the step about to be launched (which steps neither slot) and
        # the second the step after it; a rider's first token is its step's to make: the NEXT step takes it from
        # the device, and the host reads it once that one is enqueued
        assert launched[id(b)] == launched[id(a)] + 1 and carried[id(a)] == launched[id(b)] + 1
        assert not {a.slot, b.slot} & set(log[carried[id(a)]][1])
        assert carried[id(b)] == fed[id(a)] == carried[id(a)] + 1 and at[id(a)] == fed[id(a)] + 1
        assert fed[id(b)] == at[id(a)] + 1 and at[id(b)] == fed[id(b)] + 1 and log[fed[id(b)]][2] is None
        # a budget of one token rides too; known by count, no step takes its id, and it is read behind the step after
        assert id(steps[3]) in carried and id(steps[3]) not in fed and at[id(steps[3])] > carried[id(steps[3])] + 1
        assert set(carried) == {id(a), id(b), id(steps[3]), id(steps[4])}
        assert counters["prefill_rides"] == 4
        ahead = 4       # ... and a rider is unread when the step that carries it is enqueued, whatever its budget
    # an EOS as first token: the step behind it was launched before the host knew, and its id for the slot dropped
    assert fed[id(steps[4])] < at[id(steps[4])] and res.outcomes[4]["tokens"] == [eos_first.eos_id]
    assert all(step.read for step in steps.values())
    delta = {k: counters[k] for k in ("prefill_launches", "prefill_reads_ahead", "backend_compiles")}
    assert delta == {"prefill_launches": 5, "prefill_reads_ahead": ahead, "backend_compiles": 0}
    assert eng.trace_counters()["prefill_reads_ahead"] == start["prefill_reads_ahead"] + ahead


def test_no_program_compiles_after_warm_in_any_form_of_the_feed(rig, tmp_path):
    """The host's tokens; the step before's ids as they are; a fresh slot's first token from the host, from
    its unread prefill on the device, and one of each in one call: one merge program, one executable each."""
    eng, cache = rig
    cache.reset()
    slots = [cache.alloc(4, 8) for _ in range(SLOTS)]
    programs = lambda: [f._cache_size() for f in (eng._merge_fn, eng._first_fn, eng._decode_fn)]
    before = programs()

    def prefilled(slot, seed):
        step = eng.prefill(_prompt(seed, 4), slot)
        cache.commit_prefill(slot, 4)
        return step

    nd.start_trace_session(str(tmp_path / "session"), profiler=False)
    try:
        toks = np.zeros((SLOTS,), np.int32)
        toks[slots[0]] = prefilled(slots[0], 1).token
        step = eng.decode(toks)                                                         # cold: the host's tokens
        step = eng.decode(DecodeFeed(step, {slots[1]: prefilled(slots[1], 2)}))         # an unread prefill's id
        step = eng.decode(DecodeFeed(step, {slots[2]: prefilled(slots[2], 3).token}))   # the host's first token
        step = eng.decode(DecodeFeed(step, {slots[0]: prefilled(slots[0], 4), slots[1]: prefilled(slots[1], 5).token}))
        step = eng.decode(DecodeFeed(step))                                             # nothing fresh: no merge at all
        step.tokens
    finally:
        counters = nd.stop_trace_session().counters
    assert programs() == before and counters["backend_compiles"] == 0
    assert (counters["prefill_launches"], counters["prefill_reads_ahead"], counters["decode_steps_ahead"]) == (5, 2, 4)
    cache.reset()


# -------------------------------------------------------------------- pins
class _RecordingEngine:
    """A stand-in that computes nothing and writes down the order of launches and reads.  Step k's id for
    every slot is k, so a stream says which steps fed it."""

    greedy = staticmethod(ServeEngine.greedy)

    class _Step:
        def __init__(self, k, slots, log):
            self.k, self.slots, self.log = k, slots, log

        @property
        def tokens(self):
            self.log.append(("read", self.k))
            return np.full((self.slots,), self.k, np.int32)

    def __init__(self, slots, vocab=64):
        self.slots, self.vocab, self.log, self.fed = slots, vocab, [], []

    def prefill(self, prompt, slot):
        self.log.append(("prefill", slot))
        row = np.zeros((self.vocab,), np.float32)
        row[50 + slot] = 1.0
        return row

    def decode(self, tokens):
        k = sum(1 for what, _ in self.log if what == "launch")
        self.log.append(("launch", k))
        self.fed.append(tokens)
        return self._Step(k, self.slots, self.log)


def test_the_loop_launches_step_k_before_it_reads_step_k_minus_1_and_feeds_it_from_the_device():
    mesh = DeviceMesh(("tp",), (1,), devices=jax.devices()[:1])
    cache = PagedKVCache(KVCacheConfig(layers=1, kv_heads=1, head_dim=1, num_slots=2, page_size=8, pages_per_slot=4), mesh)
    eng = _RecordingEngine(2)
    sched = ContinuousBatchingScheduler(cache, max_queue=8)
    arrivals = [(0, Request(rid=0, prompt=(1, 2, 3), max_new_tokens=6)), (2, Request(rid=1, prompt=(4, 5), max_new_tokens=3))]
    res = run_serve_resilient(engine=eng, scheduler=sched, arrivals=arrivals, install_signal_handlers=False,
                              coordinate=False)
    at = {event: i for i, event in enumerate(eng.log)}
    launches = [k for what, k in eng.log if what == "launch"]
    assert launches == list(range(len(launches))) and len(launches) == 5
    for k in launches[1:]:
        assert at[("launch", k)] < at[("read", k - 1)] < at.get(("launch", k + 1), len(eng.log)), eng.log
    assert sum(1 for what, _ in eng.log if what == "read") == len(launches), "every step launched is read, once"
    # the first step starts cold, from the host's token; every later one names the step before it, with the
    # host's first token for the slot prefilled since and nothing else
    assert isinstance(eng.fed[0], np.ndarray) and list(eng.fed[0]) == [50, 0]
    assert all(isinstance(x, DecodeFeed) for x in eng.fed[1:])
    assert [x.step.k for x in eng.fed[1:]] == launches[:-1]
    assert [x.fresh for x in eng.fed[1:]] == [{}, {1: 51}, {}, {}]
    # rid 0: its prefill's token, then steps 0..4; rid 1 (prefilled before step 2): steps 2 and 3
    assert res.outcomes[0]["tokens"] == [50, 0, 1, 2, 3, 4] and res.outcomes[1]["tokens"] == [51, 2, 3]


def test_decode_takes_one_positional_argument_and_steps_ahead_are_the_steps_less_the_cold_starts(rig, tmp_path):
    """``engine.decode`` wrapped as ``benchmark/serve_cell.py`` wraps it (an instance attribute of one
    positional argument); a scripted run with one idle stretch (two cold starts) and an injected ``oom``
    (the boundary reads the step in flight, the next step starts cold); the counters of a trace session
    say the same, and that nothing compiled after ``warm()``."""
    eng, _ = rig
    decode, fed = eng.decode, []

    def timed_decode(tokens):
        fed.append(tokens)
        return decode(tokens)

    eng.decode = timed_decode
    reqs = [(0, Request(rid=0, prompt=_prompt(7, 9), max_new_tokens=6)), (1, Request(rid=1, prompt=_prompt(8, 17), max_new_tokens=7)),
            (12, Request(rid=2, prompt=_prompt(9, 3), max_new_tokens=5)), (13, Request(rid=3, prompt=_prompt(10, 30), max_new_tokens=2))]
    want = {req.rid: _golden(rig, req) for _, req in reqs}
    fed.clear()
    faultsim.arm(faultsim.parse_schedule("oom:step=3"))
    nd.start_trace_session(str(tmp_path / "session"), profiler=False)
    try:
        res, _ = _run(rig, reqs)
    finally:
        counters = nd.stop_trace_session().counters
        faultsim.disarm()
        del eng.decode
    assert all(o["tokens"] == want[rid] for rid, o in res.outcomes.items()) and res.counts["evicted"] == 1
    cold = sum(isinstance(x, np.ndarray) for x in fed)
    assert cold == 3 and all(isinstance(x, (np.ndarray, DecodeFeed)) for x in fed)
    assert counters["decode_steps"] == len(fed) and counters["decode_steps_ahead"] == len(fed) - cold
    assert counters["backend_compiles"] == 0 and counters["logits_bytes_to_host"] == 0


def test_a_step_is_read_once_by_whoever_reads_first_and_counted_then(rig):
    eng, cache = rig
    cache.reset()
    slot = cache.alloc(5, 8)
    eng.prefill(_prompt(3, 5), slot).token      # (read: a prompt that waited would be launched, and counted, by the step)
    cache.commit_prefill(slot, 5)
    start = eng.trace_counters()
    toks = np.zeros((SLOTS,), np.int32)
    toks[slot] = 9
    first = eng.decode(toks)
    cache.advance(slot)
    launched = dict(start, decode_launches=start["decode_launches"] + 1)     # counted as launched at once, as read later
    if "moe_expert_layer_calls" in start:           # ... with the expert layers its program holds (none the grouped kernel here)
        launched["moe_expert_layer_calls"] += eng._expert_layers
    assert isinstance(first, DecodeStep) and not first.read and eng.trace_counters() == launched, "launched, not read"
    second = eng.decode(DecodeFeed(first))          # waits for the first step's ids once its own program is enqueued
    cache.advance(slot)
    assert first.read and not second.read
    third = eng.decode(DecodeFeed(second, {slot: int(second.tokens[slot])}))     # read by the host first, then fed
    cache.advance(slot)
    row = third[slot]                               # a read of the logits reads the ids too
    assert third.read and int(np.argmax(row)) == int(third.tokens[slot])
    c = {k: v - start[k] for k, v in eng.trace_counters().items()}
    assert (c["decode_steps"], c["decode_steps_ahead"]) == (3, 1) and c["logits_bytes_to_host"] == row.nbytes
    # the same three steps, each read before the next is launched from the host's tokens
    cache.reset()
    slot = cache.alloc(5, 8)
    eng.prefill(_prompt(3, 5), slot)
    cache.commit_prefill(slot, 5)
    tok, ids = 9, []
    for _ in range(3):
        toks[slot] = tok
        step = eng.decode(toks)
        cache.advance(slot)
        ids.append(tok := int(step.tokens[slot]))
    assert ids == [int(first.tokens[slot]), int(second.tokens[slot]), int(third.tokens[slot])]
    cache.reset()


def test_the_decode_program_keeps_the_argument_list_the_family_files_lower_it_with(rig):
    eng, cache = rig
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    held = (cache.k.data, cache.v.data) if isinstance(eng, ServeEngine) else tuple(cache.arrays().values())
    lowered = eng._decode_fn.lower(eng.params, *held, i32(SLOTS, PAGES), i32(SLOTS), i32(SLOTS))
    logits, ids = lowered.out_info[0], lowered.out_info[1]
    assert logits.shape[0] == SLOTS and (ids.shape, ids.dtype) == ((SLOTS,), jnp.int32)
