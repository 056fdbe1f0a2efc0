"""A launch carries its number to what it causes, and the serve loop's
iteration is tiled by spans (PR 38): under a trace session without the
profiler, a loop of each engine class (``ServeEngine``; ``HybridServeEngine``
stepping a token and stepping a block) leaves ``vs.serve-decode.launch`` /
``vs.serve-prefill.launch`` spans numbered without a hole, each ``.fetch``
names the launch it read, the counters agree with the ring; the loop's five
spans cover an iteration but for its own lines; ``serve-inbox-wait`` +
``serve-queue-wait`` + ``serve-prefill`` tile a request's time to its first
token; and dormant every new site is the one ``nullcontext``: no dictionary,
no string, no clock."""

import ast
import contextlib
import inspect
import statistics
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_granite_hybrid import toy_config as granite_toy
from test_sdar_moe import toy_config as sdar_toy
from vescale_tpu.mesh import DeviceMesh
from vescale_tpu.models import granite_hybrid as gh
from vescale_tpu.models import sdar_moe as sd
from vescale_tpu.models.llama import Llama, LlamaConfig
from vescale_tpu.ndtimeline import api as nd
from vescale_tpu.ndtimeline import predefined as P
from vescale_tpu.ndtimeline import timer as nd_timer
from vescale_tpu.serve import (ContinuousBatchingScheduler, DecodeFeed, HybridServeEngine, KVCacheConfig, PagedKVCache, Request,
                               ServeEngine, reqtrace, run_serve_resilient)
from vescale_tpu.serve import engine as engine_module
from vescale_tpu.serve import hybrid_engine as hybrid_module
from vescale_tpu.serve import loop as loop_module
from vescale_tpu.serve.fleet import RequestInbox
from vescale_tpu.serve.hybrid_engine import hybrid_cache_config

SLOTS, PAGE, PAGES = 4, 8, 8          # 64 positions a slot
LLAMA = LlamaConfig(vocab_size=96, hidden_size=16, intermediate_size=32, num_hidden_layers=2, num_attention_heads=2,
                    num_key_value_heads=2, max_position_embeddings=64, dtype=jnp.float32)
LAUNCHES = (P.SERVE_DECODE_LAUNCH, P.SERVE_PREFILL_LAUNCH)
LOOP_SPANS = (P.SERVE_BOUNDARY, P.SERVE_ADMIT, P.SERVE_BOOKS, P.SERVE_HOOK, P.SERVE_IDLE)
# what tiles an iteration at its top level (``.launch``, a decode step's ``.fetch`` and a settled step's spans nest inside
# these; a prefill's ``.fetch`` stands beside them: the loop reads a prefill after the decode call that went in behind it)
TOP = LOOP_SPANS + (P.SERVE_SAMPLE, P.SERVE_DECODE_CALL, P.SERVE_PREFILL_CALL, P.SERVE_PREFILL_FETCH)
NEW_LIVE = LAUNCHES + LOOP_SPANS


@pytest.fixture(scope="module", params=["llama", "granite", "sdar_blocks"])
def rig(request):
    """(engine, cache): a warmed toy engine of each class, the hybrid one stepping a token and stepping a block."""
    mesh = DeviceMesh(("tp",), (1,), devices=jax.devices()[:1])
    if request.param == "llama":
        params = Llama(LLAMA).init(jax.random.key(0), jnp.ones((1, 8), jnp.int32))["params"]
        cache = PagedKVCache(KVCacheConfig(layers=2, kv_heads=2, head_dim=LLAMA.head_dim, num_slots=SLOTS,
                                           page_size=PAGE, pages_per_slot=PAGES), mesh)
        return ServeEngine(LLAMA, mesh, params, cache).warm(), cache
    cfg, model = (granite_toy(), gh) if request.param == "granite" else (sdar_toy(), sd)
    params = jax.jit(lambda k: model.init_params(cfg, k))(jax.random.key(7))
    cache = PagedKVCache(hybrid_cache_config(cfg, num_slots=SLOTS, page_size=PAGE, pages_per_slot=PAGES), mesh)
    return HybridServeEngine(cfg, mesh, params, cache).warm(), cache


def _requests(n=7, budget=8):
    rng = np.random.default_rng(38)
    return [Request(rid=rid, prompt=tuple(int(t) for t in rng.integers(1, 89, 3 + rid)), max_new_tokens=budget)
            for rid in range(n)]


def _serve(rig, requests, *, pace_s=0.0, on_step=None):
    """An inbox-fed loop, as a fleet replica and the benchmark run it: a
    feeder thread pushes the requests ``pace_s`` apart and closes the inbox.
    ``(result, scheduler, {rid: push instant on time.time()})``."""
    eng, cache = rig
    cache.reset()
    sched = ContinuousBatchingScheduler(cache, max_queue=32)
    inbox, pushed = RequestInbox(), {}

    def feed():
        for req in requests:
            pushed[req.rid] = time.time()
            inbox.push(req)
            time.sleep(pace_s)
        inbox.close()

    feeder = threading.Thread(target=feed, name="feeder")
    feeder.start()
    try:
        res = run_serve_resilient(engine=eng, scheduler=sched, arrivals=[], inbox=inbox, install_signal_handlers=False,
                                  coordinate=False, on_step=on_step, idle_sleep_s=0.001)
    finally:
        feeder.join(timeout=30.0)
    assert not feeder.is_alive()
    sched.ledger_check()
    cache.reset()
    return res, sched, pushed


@pytest.fixture
def traced(rig, tmp_path):
    """One traced loop of seven requests over four slots, pushed a few
    milliseconds apart: ``(session, result, push instants, first-token instants)``."""
    eng, _ = rig
    first = {}
    record = ContinuousBatchingScheduler.record_token

    def stamped(self, slot, token):
        first.setdefault(self.active[slot].req.rid, time.time())
        return record(self, slot, token)

    hook_calls = []
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(ContinuousBatchingScheduler, "record_token", stamped)
        nd.start_trace_session(str(tmp_path / "session"), profiler=False)
        try:
            res, _, pushed = _serve(rig, _requests(), pace_s=0.004, on_step=lambda step, active: hook_calls.append(step))
        finally:
            session = nd.stop_trace_session()
    assert res.status == "completed" and all(o["status"] == "completed" for o in res.outcomes.values())
    assert len(hook_calls) == res.steps or len(hook_calls) == res.steps - 1     # the iteration that breaks calls no hook
    return session, res, pushed, first


def _named(session, metric):
    return sorted((s for s in session.spans if s.metric == metric), key=lambda s: s.start)


# ------------------------------------------------------------- A. launches
def test_launches_are_numbered_without_a_hole_and_each_fetch_names_the_launch_it_read(traced, rig):
    session, res, _, _ = traced
    eng, _ = rig
    decodes, prefills = (_named(session, m) for m in LAUNCHES)
    numbers = sorted(s.tags["launch"] for s in decodes + prefills)
    assert numbers == list(range(numbers[0], numbers[0] + len(numbers))), "one sequence for both kinds, no hole"
    assert numbers[-1] + 1 == eng.launches == eng.decode_launches + eng.prefill_launches - eng.prefill_rides
    # in time order the numbers rise: a launch's number is its place in the order of enqueues
    in_time = [s.tags["launch"] for s in sorted(decodes + prefills, key=lambda s: s.start)]
    assert in_time == sorted(in_time)
    if getattr(eng, "rides", False):
        # where prompts ride, a prompt's program IS a decode step's (the step that carries it; alone, that step with
        # every decode row idle): ONE launch span of the decode kind that says where and how wide, and no span of the
        # prefill kind; the steps nobody's ``decode`` call launched (the prompts that went alone) are read by no fetch
        assert not prefills and session.counters["prefill_rides"] > 0
        prefills = [s for s in decodes if "rung" in s.tags]
        alone = session.counters["prefill_launches"] - session.counters["prefill_rides"]
        read_steps = {s.tags["launch"] for s in _named(session, P.SERVE_DECODE_FETCH)}
        went_alone = [s for s in decodes if s.tags["launch"] not in read_steps]
        assert len(went_alone) == alone > 0 and all(s in prefills for s in went_alone)
        decodes = [s for s in decodes if s not in went_alone]
    # a prefill says where and how wide; it is read once, in the order of the launches, by a fetch that names
    # it and comes after its enqueue (a block engine's loop never reads one: its prefill yields no token)
    assert len(prefills) == len(res.outcomes) and all(set(s.tags) == {"launch", "rung", "slot"} for s in prefills)
    assert all(s.tags["rung"] in eng.buckets and 0 <= s.tags["slot"] < SLOTS for s in prefills)
    read = _named(session, P.SERVE_PREFILL_FETCH)
    assert [s.tags["launch"] for s in read] == ([s.tags["launch"] for s in prefills] if eng.block is None else [])
    for fetch, launch in zip(read, prefills):
        assert set(fetch.tags) == {"launch"} and launch.start + launch.duration <= fetch.start + 1e-6
    # every decode step launched in the session was read in it, once, by a fetch that names it; the pipeline
    # is one step deep, so a fetch reads the launch before the one whose span it sits in
    fetched = [s.tags["launch"] for s in _named(session, P.SERVE_DECODE_FETCH)]
    assert sorted(fetched) == sorted(s.tags["launch"] for s in decodes) and len(set(fetched)) == len(fetched)
    for fetch in _named(session, P.SERVE_DECODE_FETCH):
        (launch,) = [s for s in decodes if s.tags["launch"] == fetch.tags["launch"]]
        assert launch.start + launch.duration <= fetch.start + 1e-6, "a step is read after it was launched"
    # each .launch lies inside the call's span that was there before (but a prompt's that its READER launched:
    # nobody carried it, and the read of its first token is where it went, alone)
    calls = _named(session, P.SERVE_DECODE_CALL) + _named(session, P.SERVE_PREFILL_CALL)
    for s in decodes + [p for p in prefills if not getattr(eng, "rides", False)]:
        assert any(c.start - 1e-6 <= s.start and s.start + s.duration <= c.start + c.duration + 1e-6 for c in calls)


def test_the_counters_agree_with_the_ring(traced):
    session, _, _, _ = traced
    c = session.counters
    # (a prompt that rode is its step's launch, one that went alone a launch of the decode kind of its own: both say ``rung``)
    wide = [s for s in _named(session, P.SERVE_DECODE_LAUNCH) if "rung" in s.tags]
    alone = len(wide) - c.get("prefill_rides", 0)
    assert c["decode_launches"] == len(_named(session, P.SERVE_DECODE_LAUNCH)) - alone > 0
    assert c["prefill_launches"] == len(_named(session, P.SERVE_PREFILL_LAUNCH)) + len(wide) > 0
    assert c["decode_steps"] == len(_named(session, P.SERVE_DECODE_FETCH)) == c["decode_launches"]


def test_a_step_launched_in_a_session_and_read_after_it_is_in_one_counter_and_not_the_other(rig, tmp_path):
    eng, cache = rig
    cache.reset()
    slot = cache.alloc(5, 4)
    eng.prefill((3, 4, 5, 6, 7), slot)
    cache.commit_prefill(slot, 5)
    feed = np.zeros((SLOTS,), np.int32) if eng.block is None else DecodeFeed(None, slots={slot: 0})
    nd.start_trace_session(str(tmp_path / "session"), profiler=False)
    step = eng.decode(feed)
    session = nd.stop_trace_session()
    assert (session.counters["decode_launches"], session.counters["decode_steps"]) == (1, 0) and not step.read
    step.tokens
    cache.reset()


def test_every_stream_is_replay_greedys_with_prefills_left_unread_behind_the_step_in_flight(rig):
    """Each engine kind under requests that arrive while a step is in flight: the streams are
    ``replay_greedy``'s, and ``prefill_reads_ahead`` counts the prefills a decode step went in behind
    unread: all but the first (no step in flight: read at once) where a step takes a token a slot, and
    every one of a block engine, whose loop never reads a prefill."""
    eng, cache = rig
    reqs = [(arrive, req) for arrive, req in zip((0, 2, 2, 5, 6, 9, 11), _requests(budget=9))]
    cache.reset()
    want = {req.rid: eng.replay_greedy(req.prompt, req.max_new_tokens) for _, req in reqs}
    cache.reset()
    sched = ContinuousBatchingScheduler(cache, max_queue=32)
    start = eng.trace_counters()
    res = run_serve_resilient(engine=eng, scheduler=sched, arrivals=reqs, install_signal_handlers=False, coordinate=False)
    sched.ledger_check()
    cache.reset()
    assert {rid: o["tokens"] for rid, o in res.outcomes.items()} == want
    c = {k: v - start[k] for k, v in eng.trace_counters().items()}
    assert c["prefill_launches"] == len(reqs)
    assert c["prefill_reads_ahead"] == len(reqs) - (eng.block is None)
    assert c["decode_steps_ahead"] == c["decode_steps"] - 1, "one cold start, and no prefill broke the pipeline"


# ------------------------------------------------------------ B. the tiling
def test_the_loops_spans_tile_an_iteration_to_within_its_own_lines(traced):
    """Every span of the top level is disjoint from the next, an iteration
    runs boundary, admit, prefills, decode, sample, books, hook, idle in that
    order, and what no span covers is the loop's own lines: a small share of
    an iteration at the median (one preempted sliver does not move a median)."""
    session, res, _, _ = traced
    top = sorted((s for s in session.spans if s.metric in TOP), key=lambda s: s.start)
    # nested ones out: a settled step's fetch, sample and books lie inside the boundary that read it
    outer, end = [], float("-inf")
    for s in top:
        if s.start + s.duration <= end + 1e-7:
            continue
        assert s.start >= end - 1e-6, f"{s.metric} straddles the span before it"
        outer.append(s)
        end = s.start + s.duration
    assert {s.metric for s in outer} >= set(LOOP_SPANS) | {P.SERVE_DECODE_CALL, P.SERVE_PREFILL_CALL}
    starts = [k for k, s in enumerate(outer) if s.metric == P.SERVE_BOUNDARY]
    assert starts[0] == 0 and len(starts) in (res.steps, res.steps + 1)
    order = {m: k for k, m in enumerate((P.SERVE_BOUNDARY, P.SERVE_ADMIT, P.SERVE_PREFILL_CALL, P.SERVE_DECODE_CALL,
                                         P.SERVE_SAMPLE, P.SERVE_BOOKS, P.SERVE_HOOK, P.SERVE_IDLE))}
    uncovered = []
    for a, b in zip(starts, starts[1:]):
        spans = outer[a:b]
        # (a prefill is read at once where no step is in flight, else after the step in flight is recorded)
        ranks = [order[s.metric] for s in spans if s.metric != P.SERVE_PREFILL_FETCH]
        assert ranks == sorted(ranks) and ranks.count(order[P.SERVE_ADMIT]) <= 1, [s.metric for s in spans]
        wall = outer[b].start - spans[0].start
        uncovered.append(1.0 - sum(s.duration for s in spans) / wall)
    assert statistics.median(uncovered) < 0.2, uncovered
    # an iteration that read a step kept its books once; one with nothing to serve slept under its own name
    assert len(_named(session, P.SERVE_BOOKS)) == session.counters["decode_steps"]
    assert _named(session, P.SERVE_IDLE), "the feeder paces its pushes: some iteration found nothing to serve"
    admitted = [s.tags["admitted"] for s in _named(session, P.SERVE_ADMIT)]
    assert sum(admitted) == len(res.outcomes) and all(s.tags is None for s in _named(session, P.SERVE_BOOKS))


def test_the_heavy_pieces_run_inside_the_span_that_names_them(rig, tmp_path):
    """The scheduler's admission, the caller's hook and the idle sleep, each
    stamped by a wrapper on ``time.time()``, fall inside the span of their name."""
    stamps = {P.SERVE_ADMIT: [], P.SERVE_HOOK: [], P.SERVE_IDLE: []}
    admit, sleep = ContinuousBatchingScheduler.admit, time.sleep

    def stamped_admit(self, step):
        t0 = time.time()
        out = admit(self, step)
        stamps[P.SERVE_ADMIT].append((t0, time.time()))
        return out

    def stamped_sleep(seconds):
        t0 = time.time()
        sleep(seconds)
        if threading.current_thread().name != "feeder":
            stamps[P.SERVE_IDLE].append((t0, time.time()))

    def hook(step, active):
        t0 = time.time()
        stamps[P.SERVE_HOOK].append((t0, time.time()))

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(ContinuousBatchingScheduler, "admit", stamped_admit)
        patch.setattr(loop_module.time, "sleep", stamped_sleep)
        nd.start_trace_session(str(tmp_path / "session"), profiler=False)
        try:
            _serve(rig, _requests(4, budget=4), pace_s=0.004, on_step=hook)
        finally:
            session = nd.stop_trace_session()
    for metric, calls in stamps.items():
        spans = _named(session, metric)
        assert calls and len(spans) == len(calls), metric
        for (t0, t1), s in zip(calls, spans):
            assert s.start - 1e-6 <= t0 and t1 <= s.start + s.duration + 1e-6, metric


# --------------------------------------------------- the request's own chain
def test_inbox_wait_queue_wait_and_prefill_tile_a_requests_time_to_its_first_token(traced, rig):
    session, res, pushed, first = traced
    eng, _ = rig
    chains = reqtrace.request_spans(session.spans)
    assert not reqtrace.verify_request_chains(session.spans, res.outcomes)
    seams, residuals = [], []
    for rid in res.outcomes:
        (inbox,), (queue,), (prefill,) = (chains[rid][m] for m in (P.SERVE_INBOX_WAIT, P.SERVE_QUEUE_WAIT, P.SERVE_PREFILL))
        assert inbox.tags == {"rid": rid}
        end = lambda s: s.start + s.duration
        seams += [abs(inbox.start - pushed[rid]), abs(queue.start - end(inbox)), abs(prefill.start - end(queue))]
        if eng.block is None:       # a block engine's first token comes with its first block's commit, not its prefill
            residuals.append(abs((first[rid] - pushed[rid]) - (inbox.duration + queue.duration + prefill.duration)))
    # each seam is the loop's own lines between two stamps (medians: one preempted sliver does not move them)
    assert statistics.median(seams) < 2e-3, seams
    if residuals:
        assert statistics.median(residuals) < 3e-3, residuals


def test_the_chain_check_takes_the_new_link():
    span = lambda metric, start, duration=0.0, **tags: nd_timer.Span(metric, start, duration, 0, 0, tags)
    spans = [span(P.SERVE_INBOX_WAIT, 9.0, 1.0, rid=7), span(P.SERVE_SUBMIT, 10.0, rid=7),
             span(P.SERVE_QUEUE_WAIT, 10.0, 1.0, rid=7, slot=0), span(P.SERVE_PREFILL, 11.0, 1.0, rid=7, slot=0),
             span(P.SERVE_TERMINAL, 13.0, rid=7, outcome="completed")]
    outcomes = {7: {"status": "completed", "tokens": [1], "replays": 0}}
    assert P.SERVE_INBOX_WAIT in reqtrace.SERVE_SPAN_METRICS
    assert not reqtrace.verify_request_chains(spans, outcomes)
    assert not reqtrace.verify_request_chains(spans[1:], outcomes), "an arrivals-fed request has no inbox wait"
    stray = spans + [span(P.SERVE_INBOX_WAIT, 20.0, 1.0, rid=7)]
    assert any("inbox-wait" in p for p in reqtrace.verify_request_chains(stray, outcomes))


def test_push_stamps_the_request_and_drain_hands_the_stamp_on():
    box = RequestInbox()
    t0 = time.perf_counter()
    reqs = _requests(2)
    assert all(box.push(r) for r in reqs)
    t1 = time.perf_counter()
    got = box.drain_stamped()
    assert [r for r, _ in got] == reqs and all(t0 <= at <= t1 for _, at in got) and got[0][1] <= got[1][1]
    assert box.drain_stamped() == [] and box.drain() == []
    box.push(reqs[0])
    assert box.drain() == [reqs[0]]


# ---------------------------------------------------------------- dormant
def test_dormant_every_new_site_is_the_one_nullcontext_and_builds_nothing(rig):
    """``test_reqtrace_dormant_is_free``'s way (the ring stays empty), and
    more: every site's call of ``ndtimeit`` is seen, gets the shared
    ``nullcontext`` back and hands over no dictionary; ``inbox_wait`` returns
    before it reads a clock."""
    assert not nd.is_active()
    seen = []
    real = nd.ndtimeit

    def spy(metric, tags=None, **ids):
        out = real(metric, tags, **ids)
        seen.append((metric, tags, out))
        return out

    class NoClock:
        def __getattr__(self, name):
            raise AssertionError(f"a dormant emitter read time.{name}")

    with pytest.MonkeyPatch.context() as patch:
        for module in (nd, engine_module, hybrid_module):
            patch.setattr(module, "ndtimeit", spy)
        res, _, _ = _serve(rig, _requests(4, budget=4), pace_s=0.004, on_step=lambda step, active: None)
        patch.setattr(reqtrace, "time", NoClock())
        reqtrace.inbox_wait(1, 0.0)
    assert res.status == "completed"
    # (where prompts ride no launch is of the prefill kind: a prompt's program is a decode step's)
    assert {m for m, _, _ in seen} >= set(NEW_LIVE) - ({P.SERVE_PREFILL_LAUNCH} if getattr(rig[0], "rides", False) else set()), \
        "every new site ran"
    dormant = real("anything")
    assert isinstance(dormant, contextlib.nullcontext)
    assert all(out is dormant and tags is None for _, tags, out in seen)
    with dormant as span:
        assert span is None      # what a site that tags late tests for
    ring = nd.get_manager().tail(10_000)
    assert not [s for s in ring if s.metric in NEW_LIVE + (P.SERVE_INBOX_WAIT,) or s.metric in reqtrace.SERVE_SPAN_METRICS]


@pytest.mark.parametrize("module", [engine_module, hybrid_module, loop_module], ids=lambda m: m.__name__.rsplit(".", 1)[-1])
def test_no_site_builds_a_dictionary_formats_a_string_or_reads_a_clock_before_the_gate(module):
    """By the source: every argument of every ``ndtimeit`` call in the serve
    path is a name, an attribute or a constant (``launch=n``), so nothing is
    built, formatted or called before ``ndtimeit``'s own test of the gate."""
    calls = [node for node in ast.walk(ast.parse(inspect.getsource(module)))
             if isinstance(node, ast.Call) and getattr(node.func, "attr", getattr(node.func, "id", None)) == "ndtimeit"]
    assert calls
    for call in calls:
        for arg in list(call.args) + [kw.value for kw in call.keywords]:
            assert isinstance(arg, (ast.Name, ast.Attribute, ast.Constant)), ast.dump(arg)
        assert all(kw.arg is not None for kw in call.keywords), "no ** of a dictionary built at the site"


def test_armed_the_tags_reach_the_ring_and_the_annotation_alike(tmp_path):
    """``ndtimeit``'s keyword arguments are the span's tags in the ring and
    the ``TraceAnnotation``'s stats in the profiler's trace; ``tag`` adds what
    a site knows only later to both; a ring span lies inside its annotation."""
    nd.start_trace_session(str(tmp_path / "session"))
    with nd.ndtimeit(P.SERVE_PREFILL_LAUNCH, launch=41, rung=128, slot=2):
        pass
    with nd.ndtimeit(P.SERVE_ADMIT) as span:
        span.tag(admitted=3)
    with nd.ndtimeit(P.CHECKPOINT_SAVE, tags={"path": "/x"}, launch=1):
        time.sleep(0.002)
    session = nd.stop_trace_session()
    ring = {s.metric: s for s in session.spans}
    assert ring[P.SERVE_PREFILL_LAUNCH].tags == {"launch": 41, "rung": 128, "slot": 2}
    assert ring[P.SERVE_ADMIT].tags == {"admitted": 3}
    assert ring[P.CHECKPOINT_SAVE].tags == {"path": "/x", "launch": 1}
    events = {e.name: e for plane in session.profile.planes for line in plane.lines for e in line.events
              if e.name in ring}
    assert dict(events[P.SERVE_PREFILL_LAUNCH].stats) == {"launch": 41, "rung": 128, "slot": 2}
    assert dict(events[P.SERVE_ADMIT].stats) == {"admitted": 3}
    assert dict(events[P.CHECKPOINT_SAVE].stats) == {"path": "/x", "launch": 1}
    saved, event = ring[P.CHECKPOINT_SAVE], events[P.CHECKPOINT_SAVE]
    assert saved.duration * 1e9 <= event.duration_ns + 1e3        # the ring's span is the inner one (timer.py says why)
    start_ns = session.to_trace_ns(saved.start)
    assert event.start_ns - 2e5 <= start_ns <= event.start_ns + event.duration_ns     # to the clock offset's few us
