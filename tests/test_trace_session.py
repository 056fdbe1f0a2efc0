"""The program's trace session (``ndtimeline.api.start_trace_session`` /
``stop_trace_session``): it arms and disarms in a running process, twice; its
``vs.*`` spans appear once a call while it runs and never while it does not;
the engine's counters say what a tiny serve run implies; the clock offset lays
a span recorded after the fact inside the live span that contains it; the
host-stall reads give ``None`` where ``/proc`` lacks a file; and the
collector's witness counts every collection, names the long ones of a window
and is a ``vs.host-gc`` span only while a session is armed."""

import gc
import os
import time

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp

from vescale_tpu import telemetry
from vescale_tpu.mesh import DeviceMesh
from vescale_tpu.models.llama import Llama, LlamaConfig
from vescale_tpu.ndtimeline import api as nd
from vescale_tpu.ndtimeline import predefined as P
from vescale_tpu.serve import (ContinuousBatchingScheduler, KVCacheConfig, PagedKVCache, Request, ServeEngine,
                               run_serve_resilient)
from vescale_tpu.telemetry import hoststat

CFG = LlamaConfig(vocab_size=64, hidden_size=16, intermediate_size=32, num_hidden_layers=2, num_attention_heads=2,
                  num_key_value_heads=2, max_position_embeddings=64, dtype=jnp.float32)
SLOTS, POSITIONS = 2, 16
LIVE_SERVE_SPANS = (P.SERVE_PREFILL_CALL, P.SERVE_PREFILL_FETCH, P.SERVE_DECODE_CALL, P.SERVE_DECODE_FETCH,
                    P.SERVE_SAMPLE)


@pytest.fixture(scope="module")
def serve_rig():
    mesh = DeviceMesh(("tp",), (1,), devices=jax.devices()[:1])
    params = Llama(CFG).init(jax.random.key(0), jnp.ones((1, 8), jnp.int32))["params"]
    kc = KVCacheConfig(layers=CFG.num_hidden_layers, kv_heads=CFG.num_key_value_heads, head_dim=CFG.head_dim,
                       num_slots=SLOTS, page_size=4, pages_per_slot=POSITIONS // 4)
    cache = PagedKVCache(kc, mesh)
    return ServeEngine(CFG, mesh, params, cache), cache


def _serve(eng, cache, n=3, new_tokens=4):
    cache.reset()
    rng = np.random.default_rng(3)
    arrivals = [(2 * i, Request(rid=i, prompt=tuple(int(x) for x in rng.integers(1, 60, 3 + i % 2)),
                                max_new_tokens=new_tokens)) for i in range(n)]
    sched = ContinuousBatchingScheduler(cache, max_queue=8)
    res = run_serve_resilient(engine=eng, scheduler=sched, arrivals=arrivals, install_signal_handlers=False,
                              coordinate=False)
    assert res.status == "completed"
    return arrivals


def _host_events(profile, prefix="vs."):
    return [(e.name, float(e.start_ns), float(e.start_ns) + float(e.duration_ns))
            for plane in profile.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events if e.name.startswith(prefix)]


def _metrics(out):
    """The ring's spans by name, in order, but for the collector's: a
    collection may fall in any session and is not what these tests place."""
    return [s.metric for s in out.spans if s.metric != P.HOST_GC]


def _profiler_is_running() -> bool:
    """A second start raises while one runs; a start that works is stopped again."""
    try:
        jax.profiler.start_trace(os.path.join(os.environ.get("TMPDIR", "/tmp"), "vs_probe_trace"))
    except RuntimeError:
        return True
    jax.profiler.stop_trace()
    return False


# ------------------------------------------------------------ start and stop
def test_session_starts_and_stops_twice_in_one_process(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((32, 32))
    f(x).block_until_ready()      # compiled before any session
    for i in range(2):
        assert not nd.is_active() and not nd.session_active()
        nd.start_trace_session(str(tmp_path / f"s{i}"))
        assert nd.is_active() and nd.session_active() and not telemetry.is_active()
        with pytest.raises(RuntimeError):
            nd.start_trace_session(str(tmp_path / "second"))
        with nd.ndtimeit("vs.work"):
            f(x).block_until_ready()
        out = nd.stop_trace_session()
        # dormant again: gate down, ring gone, no profiler
        assert not nd.is_active() and not nd.session_active()
        assert nd.get_manager().tail(10) == []
        assert not _profiler_is_running()
        assert _metrics(out) == ["vs.work"]
        assert out.xplane_path and os.path.exists(out.xplane_path) and out.profile is not None
        assert out.counters["backend_compiles"] == 0 and out.stopped > out.started
        names = [n for n, _, _ in _host_events(out.profile)]
        assert names.count("vs.work") == 1 and names.count(P.SESSION_MARK) == 1
    with pytest.raises(RuntimeError):
        nd.stop_trace_session()


def test_session_without_the_profiler_arms_spans_and_counters_only(tmp_path):
    nd.start_trace_session(str(tmp_path / "none"), profiler=False)
    with nd.ndtimeit("vs.work"):
        pass
    out = nd.stop_trace_session()
    assert _metrics(out) == ["vs.work"]
    assert out.xplane_path is None and out.profile is None and out.clock_offset_ns is None
    assert out.to_trace_ns(out.started) is None
    assert not os.path.exists(tmp_path / "none")


def test_session_counts_compiles_while_it_runs(tmp_path):
    nd.start_trace_session(str(tmp_path / "c"), profiler=False)
    jax.jit(lambda x: x * 3 + 1)(jnp.ones((7,))).block_until_ready()
    assert nd.stop_trace_session().counters["backend_compiles"] >= 1


def test_session_leaves_an_operators_own_timers_as_they_are(tmp_path):
    seen = []
    mgr = nd.init_ndtimers(rank=0, handlers=[seen.extend])
    with nd.ndtimeit("before"):
        pass
    nd.start_trace_session(str(tmp_path / "own"), profiler=False)
    with nd.ndtimeit("vs.during"):
        pass
    out = nd.stop_trace_session()
    assert _metrics(out) == ["vs.during"]
    assert nd.is_active() and nd.get_manager() is mgr           # still the operator's
    assert [s.metric for s in nd.flush()] == ["before", "vs.during"] and len(seen) == 2


# ----------------------------------------------------------------- the spans
@pytest.mark.parametrize("armed", [False, True])
def test_serve_spans_once_a_call_when_armed_and_never_when_not(serve_rig, tmp_path, armed):
    eng, cache = serve_rig
    before = eng.trace_counters()
    if armed:
        nd.start_trace_session(str(tmp_path / "serve"), profiler=False)
    _serve(eng, cache)
    calls = {k: v - before[k] for k, v in eng.trace_counters().items()}
    if not armed:
        assert not nd.is_active() and nd.get_manager().tail(100) == []
        return
    out = nd.stop_trace_session()
    count = lambda name: sum(1 for s in out.spans if s.metric == name)
    prefills = count("serve-prefill")            # the loop's after-the-fact span, one a request admitted
    assert prefills == 3
    assert count(P.SERVE_PREFILL_CALL) == count(P.SERVE_PREFILL_FETCH) == prefills
    assert count(P.SERVE_DECODE_CALL) == count(P.SERVE_DECODE_FETCH) == count(P.SERVE_SAMPLE) == calls["decode_steps"] > 0
    assert count("serve-decode-step") == calls["decode_steps"]


def test_train_and_loader_spans_and_the_step_reads_no_loss_on_the_host(tmp_path, monkeypatch):
    from vescale_tpu.data.loader import TokenDataLoader
    from vescale_tpu.dmodule import parallelize_module
    from vescale_tpu.models.nanogpt import GPT, GPTConfig, cross_entropy_loss, nanogpt_plan
    from vescale_tpu.train import make_train_step

    cfg = GPTConfig(block_size=8, vocab_size=32, n_layer=1, n_head=2, n_embd=16, dropout=0.0)
    mesh = DeviceMesh(("dp", "tp"), (1, 1), devices=jax.devices()[:1])
    dm = parallelize_module(GPT(cfg), mesh, nanogpt_plan(mesh))
    params = dm.init(jax.random.key(0), jnp.ones((2, 8), jnp.int32))["params"]
    tx = optax.sgd(0.1)
    step = make_train_step(dm, tx, lambda lg, b: cross_entropy_loss(lg, b["target"]), donate=False)
    opt_state = tx.init(params)
    np.random.default_rng(0).integers(0, 32, 4096, dtype=np.uint16).tofile(tmp_path / "toks.bin")
    loader = TokenDataLoader(str(tmp_path / "toks.bin"), batch=2, seq_len=8, seed=1)
    recorded = []
    monkeypatch.setattr(telemetry, "record_step", lambda *a, **k: recorded.append(a))
    try:
        def one():
            batch = {k: jnp.asarray(v) for k, v in loader.next().items()}
            return step(params, opt_state, batch)[2]

        one().block_until_ready()                     # dormant: nothing recorded
        assert nd.get_manager().tail(10) == []
        nd.start_trace_session(str(tmp_path / "train"), profiler=False)
        for _ in range(3):
            loss = one()
        assert isinstance(loss, jax.Array)            # still the device's: the wrapper read nothing
        out = nd.stop_trace_session()
    finally:
        loader.close()
    assert _metrics(out) == [P.DATA_LOAD, P.TRAIN_STEP] * 3
    assert [s.step for s in out.spans if s.metric == P.TRAIN_STEP] == [0, 1, 2]
    assert recorded == []                             # the record_step feed stays with telemetry.init()


# -------------------------------------------------------------- the counters
def test_engine_counters_say_what_a_tiny_serve_run_implies(serve_rig, tmp_path):
    eng, cache = serve_rig
    nd.start_trace_session(str(tmp_path / "counters"), profiler=False)
    arrivals = _serve(eng, cache, n=3, new_tokens=4)
    c = nd.stop_trace_session().counters
    # a request's first token comes from its prefill, so decode steps >= the longest answer - 1
    assert c["decode_steps"] >= 3
    # the loop takes each step's ids and reads no logits row: nothing crossed to the host
    assert c["logits_bytes_to_host"] == 0
    # each prefill adds its prompt and the rung it was padded to (this cache is shorter than the smallest rung of
    # the ladder, so it has the one rung): a run states its mean rung and its pad share from the counters alone
    real = sum(len(r.prompt) for _, r in arrivals)
    rungs = [next(b for b in eng.buckets if b >= len(r.prompt)) for _, r in arrivals]
    assert eng.buckets == [POSITIONS] and c["prefill_calls"] == len(arrivals)
    assert c["prefill_tokens_real"] == real and c["prefill_tokens_padded"] == sum(rungs)
    # outside a session the engine counts on, and the next session reports its own share only
    _serve(eng, cache, n=1)
    nd.start_trace_session(str(tmp_path / "again"), profiler=False)
    assert nd.stop_trace_session().counters["decode_steps"] == 0


def test_a_counter_source_that_dies_in_a_session_takes_nothing_from_the_others():
    """The session subtracts source by source: an engine collected while it
    runs drops out, and one made while it runs counts from zero."""
    class Source:
        def __init__(self, n):
            self.n = n

        def trace_counters(self):
            return {"decode_steps": self.n}

    stays, dies = Source(5), Source(1000)
    nd.register_counter_source(stays)
    nd.register_counter_source(dies)
    nd.start_trace_session(os.path.join(os.environ.get("TMPDIR", "/tmp"), "vs_sources"), profiler=False)
    stays.n += 3
    del dies
    born = Source(2)
    nd.register_counter_source(born)
    assert nd.stop_trace_session().counters["decode_steps"] == 3 + 2


# ------------------------------------------------------------ the two clocks
def test_offset_lays_an_after_the_fact_span_inside_the_live_span_that_holds_it(tmp_path):
    nd.start_trace_session(str(tmp_path / "clock"))
    with nd.ndtimeit("vs.outer"):
        time.sleep(0.005)
        t0 = time.time()
        time.sleep(0.005)
        nd.get_manager().record("after-the-fact", t0, time.time() - t0)     # as reqtrace records: epoch start, duration
        time.sleep(0.005)
    out = nd.stop_trace_session()
    (name, a, b), = [e for e in _host_events(out.profile) if e[0] == "vs.outer"]
    late, = [s for s in out.spans if s.metric == "after-the-fact"]
    start, end = out.to_trace_ns(late.start), out.to_trace_ns(late.start + late.duration)
    assert a < start < end < b
    assert 3e6 < start - a < 5e7 and 3e6 < b - end < 5e7      # 5 ms of sleep on either side, mapped to 0.05 ms
    # the live span's own ring record maps onto its annotation
    ring, = [s for s in out.spans if s.metric == "vs.outer"]
    assert abs(out.to_trace_ns(ring.start) - a) < 2e5 and abs(out.clock_offset_ns) > 0


# ------------------------------------------------------------ the host reads
def test_host_sched_stats_reads_this_thread_and_the_machine():
    opened = hoststat.host_sched_stats()
    sum(range(200_000))
    delta = hoststat.host_sched_delta(opened, hoststat.host_sched_stats())
    assert delta["seconds"] > 0
    for key in ("thread_run_ns", "thread_runq_wait_ns", "nonvoluntary_ctxt_switches"):
        assert delta[key] is None or delta[key] >= 0
    if os.path.exists("/proc/thread-self/schedstat"):
        assert delta["thread_run_ns"] is not None and delta["thread_runq_wait_ns"] is not None


@pytest.mark.parametrize("present", [(), ("stat",), ("thread-self/schedstat", "pressure/cpu"), ("thread-self/status",)])
def test_host_sched_stats_gives_none_where_proc_lacks_a_file(tmp_path, present):
    files = {"stat": "cpu  10 0 5 100 1 0 2 300 0 0\ncpu0 1 2 3\n", "thread-self/schedstat": "5000 700 3\n",
             "pressure/cpu": "some avg10=0.00 avg60=0.00 avg300=0.00 total=4200\nfull avg10=0.00 total=0\n",
             "thread-self/status": "Name:\tx\nvoluntary_ctxt_switches:\t12\nnonvoluntary_ctxt_switches:\t4\n"}
    for name in present:
        path = tmp_path / name
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(files[name])
    got = hoststat.host_sched_stats(str(tmp_path))
    want = {"thread_run_ns": None, "thread_runq_wait_ns": None, "thread_timeslices": None,
            "voluntary_ctxt_switches": None, "nonvoluntary_ctxt_switches": None, "cpu_steal_s": None,
            "psi_cpu_some_us": None}
    if "stat" in present:
        want["cpu_steal_s"] = 300 / os.sysconf("SC_CLK_TCK")
    if "thread-self/schedstat" in present:
        want.update(thread_run_ns=5000.0, thread_runq_wait_ns=700.0, thread_timeslices=3.0, psi_cpu_some_us=4200.0)
    if "thread-self/status" in present:
        want.update(voluntary_ctxt_switches=12.0, nonvoluntary_ctxt_switches=4.0)
    assert {k: got[k] for k in want} == want and got["at"] > 0
    delta = hoststat.host_sched_delta(got, hoststat.host_sched_stats(str(tmp_path)))
    assert all(delta[k] == (None if v is None else 0.0) for k, v in want.items())
    # the interpreter's fields are no file's: a number wherever the kernel counts nothing
    assert all(isinstance(got[k], int) and isinstance(delta[k], int) and delta[k] >= 0 for k in hoststat._GC_FIELDS)
    assert isinstance(delta["gc_longest_pauses_ms_at_s"], list)


def test_host_sched_delta_subtracts_field_by_field_and_keeps_none():
    """Two reads from one thread: each counter's growth, ``None`` where either
    read lacked the field, and the seconds between them.  Nothing else: no
    thread, no state between the reads."""
    import threading

    opened = dict.fromkeys(hoststat._FIELDS) | {"at": 10.0, "thread_run_ns": 5e9, "thread_runq_wait_ns": 1e6,
                                                "nonvoluntary_ctxt_switches": 3.0, "cpu_steal_s": 0.5}
    closed = dict.fromkeys(hoststat._FIELDS) | {"at": 55.0, "thread_run_ns": 9e9, "thread_runq_wait_ns": 2.5e8,
                                                "nonvoluntary_ctxt_switches": 7.0, "psi_cpu_some_us": 40.0}
    delta = hoststat.host_sched_delta(opened, closed)
    assert delta == {"thread_run_ns": 4e9, "thread_runq_wait_ns": 2.49e8, "thread_timeslices": None,
                     "voluntary_ctxt_switches": None, "nonvoluntary_ctxt_switches": 4.0, "cpu_steal_s": None,
                     "psi_cpu_some_us": None, "seconds": 45.0,
                     # two reads that lack the interpreter's fields, as a record of before PR 56 does
                     "gc_collections": None, "gc_gen2_collections": None, "gc_pause_ns": None,
                     "gc_gen2_pause_ns": None, "gc_longest_pauses_ms_at_s": []}
    before = threading.active_count()
    hoststat.host_sched_delta(hoststat.host_sched_stats(), hoststat.host_sched_stats())
    assert threading.active_count() == before and set(hoststat.__all__) == {
        "host_sched_stats", "host_sched_delta", "gc_witness_counts", "arm_gc_spans"}


# ------------------------------------------------------------ the collector's witness
# ``quiet_collector`` (tests/conftest.py): no collection but the test's own; the witness itself never
# touches the collector's switch, which a test below holds
def test_witness_counts_a_full_collection_between_two_reads(quiet_collector):
    gc.collect(2)                                   # before the first read: in no delta
    opened = hoststat.host_sched_stats()
    gc.collect(2)
    closed = hoststat.host_sched_stats()
    delta = hoststat.host_sched_delta(opened, closed)
    assert delta["gc_collections"] == 1 and delta["gc_gen2_collections"] == 1
    assert delta["gc_pause_ns"] == delta["gc_gen2_pause_ns"] > 0
    (ms, at_s, gen), = delta["gc_longest_pauses_ms_at_s"]
    assert gen == 2 and 0 <= at_s <= delta["seconds"] and ms == pytest.approx(delta["gc_gen2_pause_ns"] / 1e6, abs=1e-3)
    # the next window holds none of it
    quiet = hoststat.host_sched_delta(closed, hoststat.host_sched_stats())
    assert quiet["gc_collections"] == 0 and quiet["gc_pause_ns"] == 0 and quiet["gc_longest_pauses_ms_at_s"] == []


def test_witness_tells_generations_apart_and_shows_the_longest_three(quiet_collector):
    opened = hoststat.host_sched_stats()
    for g in (0, 1, 2, 0, 2):
        gc.collect(g)
    delta = hoststat.host_sched_delta(opened, hoststat.host_sched_stats())
    assert delta["gc_collections"] == 5 and delta["gc_gen2_collections"] == 2
    assert 0 < delta["gc_gen2_pause_ns"] < delta["gc_pause_ns"]
    shown = delta["gc_longest_pauses_ms_at_s"]
    assert len(shown) == hoststat.GC_LONGEST_SHOWN and [p[0] for p in shown] == sorted((p[0] for p in shown), reverse=True)
    assert all(0 <= p[1] <= delta["seconds"] and p[2] in (0, 1, 2) for p in shown)


def test_witness_keeps_a_bounded_list_of_the_longest(quiet_collector):
    hoststat.host_sched_stats()
    for _ in range(3 * hoststat.GC_LONGEST_KEPT):
        gc.collect(0)
    gc.collect(2)                                    # the long one comes last, with the list full
    kept = hoststat.host_sched_stats()["gc_longest_pauses"]
    assert len(kept) == hoststat.GC_LONGEST_KEPT and max(kept)[2] == 2
    assert hoststat.host_sched_stats()["gc_longest_pauses"] == []      # a read leaves an empty list behind


def test_witness_is_installed_once_and_sets_nothing_of_the_collector(tmp_path):
    thresholds, enabled = gc.get_threshold(), gc.isenabled()
    frozen = gc.get_freeze_count()
    for _ in range(2):
        hoststat.host_sched_stats()
        nd.start_trace_session(str(tmp_path / "w"), profiler=False)
        nd.stop_trace_session()
    assert gc.callbacks.count(hoststat._on_gc) == 1
    assert gc.get_threshold() == thresholds and gc.isenabled() == enabled and gc.get_freeze_count() == frozen


def test_a_full_collection_under_a_session_is_one_host_gc_span_inside_the_span_it_fell_in(tmp_path, quiet_collector):
    nd.start_trace_session(str(tmp_path / "g"), profiler=False)
    with nd.ndtimeit(P.SERVE_BOOKS):
        gc.collect(0)                               # tens of microseconds: counted, no span
        gc.collect(2)
    out = nd.stop_trace_session()
    (books,), (pause,) = ([s for s in out.spans if s.metric == m] for m in (P.SERVE_BOOKS, P.HOST_GC))
    assert books.start <= pause.start and pause.start + pause.duration <= books.start + books.duration
    assert pause.tags["gen"] == 2 and pause.tags["collected"] >= 0 and pause.duration > 0
    assert out.counters["gc_pauses"] == 2 and out.counters["gc_gen2_pauses"] == 1
    assert out.counters["gc_pause_us"] >= int(pause.duration * 1e6 * 0.5)
    # disarmed with the session: a further collection is counted and is no span
    counted = hoststat.gc_witness_counts()
    gc.collect(2)
    assert hoststat.gc_witness_counts()[1] == counted[1] + 1
    assert hoststat._gc_spans is None and nd.get_manager().tail(10) == []
    nd.start_trace_session(str(tmp_path / "g2"), profiler=False)
    again = nd.stop_trace_session()
    assert again.spans == [] and again.counters["gc_pauses"] == 0


def test_generation_zero_is_a_span_only_when_it_is_long(tmp_path, quiet_collector, monkeypatch):
    nd.start_trace_session(str(tmp_path / "z"), profiler=False)
    for _ in range(5):
        gc.collect(0)
    monkeypatch.setattr(nd, "GC_SPAN_MIN_S", 0.0)   # every one is "long" now
    gc.collect(0)
    gc.collect(1)
    out = nd.stop_trace_session()
    assert [s.tags["gen"] for s in out.spans if s.metric == P.HOST_GC] == [0, 1]
    assert out.counters["gc_pauses"] == 7 and out.counters["gc_gen2_pauses"] == 0


def test_host_gc_is_an_annotation_on_the_trace_nested_in_the_span_it_fell_in(tmp_path, quiet_collector):
    """Under the profiler the collection is on the host plane, on the
    trace's clock, inside the ``vs.*`` span the host was in, and the ring's
    record of it maps onto the annotation through the session's offset."""
    nd.start_trace_session(str(tmp_path / "t"))
    with nd.ndtimeit(P.SERVE_BOOKS):
        gc.collect(0)
        gc.collect(2)
    out = nd.stop_trace_session()
    events = _host_events(out.profile)
    (_, a, b), = [e for e in events if e[0] == P.SERVE_BOOKS]
    (_, ga, gb), = [e for e in events if e[0] == P.HOST_GC]
    assert a <= ga < gb <= b
    ring, = [s for s in out.spans if s.metric == P.HOST_GC]
    assert abs(out.to_trace_ns(ring.start) - ga) < 2e5


def test_a_collection_inside_the_rings_own_lock_does_not_wait_for_it(tmp_path, quiet_collector):
    """``NDTimerManager.record`` allocates a span under its lock, and the
    collector runs wherever something allocates: the witness keeps the
    collection aside and the session records it once it stops."""
    nd.start_trace_session(str(tmp_path / "l"), profiler=False)
    with nd.get_manager()._lock:
        gc.collect(1)
    out = nd.stop_trace_session()
    assert [s.tags["gen"] for s in out.spans if s.metric == P.HOST_GC] == [1]
