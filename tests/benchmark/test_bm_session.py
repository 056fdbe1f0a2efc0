"""``--trace 2`` and the readers of the program's trace session: the reduction
on a hand-made trace whose numbers can be reckoned on paper, and on the CPU
rig modes 0 and 2 of one seed side by side (the window's plan, and the
end-to-end samples' keys and counts)."""

import json
import os
import time
import types

import jax
import pytest

from bm_fixtures import REPO, make_tiny_root

from benchmark import harness, serve_cell, stats, train_cell
from benchmark.harness import discover, read_metrics, result_object
from benchmark.layer_metrics import _session
from benchmark.spec import load_benchmark, load_cell

TINY_OF = {"mistral7b_train_seq4096": "tiny_train", "mistral7b_train_dp2tp2": "tiny_train4",
           "mistral7b_serve_chat": "tiny_chat", "deepseek7b_serve_batch": "tiny_batch"}


# ------------------------------------------------------- a hand-made trace
def _trace(device_ops, modules, host):
    """An XSpace of one TPU plane and one host plane from (start_us, end_us[, name]) tuples."""
    def line(lid, name, events, ids):
        evs = "".join(f"events {{ metadata_id: {ids[n]} offset_ps: {int(a * 1e6)} duration_ps: {int((b - a) * 1e6)} }} "
                      for a, b, n in events)
        return f'lines {{ id: {lid} name: "{name}" timestamp_ns: 0 {evs} }}'

    def plane(pid, name, lines):
        names = sorted({n for _, evs in lines for _, _, n in evs})
        ids = {n: i + 1 for i, n in enumerate(names)}
        meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }} ' for n, i in ids.items())
        body = " ".join(line(k + 1, ln, evs, ids) for k, (ln, evs) in enumerate(lines))
        return f'planes {{ id: {pid} name: "{name}" {meta} {body} }}'

    ops = [(a, b, "%fusion.1 = bf16[8,8]{1,0} fusion(x)") for a, b in device_ops]
    text = plane(1, "/device:TPU:0", [("XLA Ops", ops), ("XLA Modules", modules)]) + plane(
        2, "/host:CPU", [("python3", host)])
    return jax.profiler.ProfileData.from_text_proto(text)


def _ring(*spans):
    return [types.SimpleNamespace(metric=m, start=a / 1e6, duration=(b - a) / 1e6) for m, a, b in spans]


@pytest.fixture(scope="module")
def serve_session():
    """Two decode calls, a prefill of two programs, a third decode call, and
    one lone operation much later (times in microseconds)."""
    device_ops = [(100, 800), (1400, 2100), (2600, 2900), (3000, 3300), (3700, 4400), (6000, 6100)]
    modules = [(100, 800, "jit_decode(1)"), (1400, 2100, "jit_decode(1)"), (2600, 2900, "jit_stage(2)"),
               (3000, 3300, "jit_head(3)"), (3700, 4400, "jit_decode(1)")]
    host = [(0, 1000, "vs.serve-decode"), (50, 990, "vs.serve-decode.fetch"), (1010, 1200, "vs.serve-sample"),
            (1300, 2300, "vs.serve-decode"), (1350, 2290, "vs.serve-decode.fetch"), (2310, 2400, "vs.serve-sample"),
            (2500, 3500, "vs.serve-prefill"), (3310, 3490, "vs.serve-prefill.fetch"),
            (3600, 4600, "vs.serve-decode"), (3650, 4590, "vs.serve-decode.fetch"),
            (-5, 1005, "bm.decode"), (1295, 2305, "bm.decode"), (2495, 3505, "bm.prefill"), (3595, 4605, "bm.decode"),
            (-50, -49, "vs.session-mark")]
    ring = _ring(("vs.serve-decode.fetch", 50, 990), ("vs.serve-decode.fetch", 1350, 2290),
                 ("vs.serve-decode.fetch", 3650, 4590), ("serve-queue-wait", 2000, 2498), ("serve-queue-wait", 100, 200))
    counters = {"decode_steps": 3, "logits_bytes_to_host": 3 * 2 * 64 * 4, "backend_compiles": 0}
    return _session.reduce(_trace(device_ops, modules, host), ring, lambda s: s * 1e9, counters)


def test_device_time_inside_the_engines_spans(serve_session):
    assert serve_session["decode_device_ms"] == pytest.approx([0.7, 0.7, 0.7])
    assert serve_session["prefill_device_ms"] == pytest.approx([0.6])
    assert serve_session["main_module"] == "jit_decode(1)"
    assert serve_session["main_module_ms"] == pytest.approx([0.7, 0.7, 0.7])
    assert serve_session["main_module_gap_ms"] == pytest.approx([0.6, 1.6])


def test_the_gap_between_two_decode_programs_and_its_split(serve_session):
    # only the first pair has no other program between its two decode programs
    assert serve_session["decode_gap_ms"] == pytest.approx([0.6])
    # 800..1400: fetch tail 800..990 and the next call's 1350..1400; sample 1010..1200; the calls
    # themselves 800..1000 and 1300..1400 less their fetches; the rest is the loop's own
    assert serve_session["decode_gap_split_ms"] == pytest.approx(
        {"fetch": 0.24, "sample": 0.19, "enqueue": 0.06, "other": 0.11})
    assert sum(serve_session["decode_gap_split_ms"].values()) == pytest.approx(0.6)


def test_idle_gaps_name_the_programs_span_first(serve_session):
    assert serve_session["idle_gaps"] == [
        ["unattributed", pytest.approx(1.6e-3)], ["vs.serve-sample", pytest.approx(0.6e-3)],
        ["vs.serve-sample", pytest.approx(0.5e-3)], ["bm.prefill", pytest.approx(0.4e-3)],
        ["vs.serve-prefill (enqueue)", pytest.approx(0.1e-3)]]
    # by time, not by gap: of 3,200 us of idle no vs.* or bm.* span covers 100 + 100 + 0 + 90 + 1,395
    assert serve_session["idle_unattributed_share"] == pytest.approx(100 * 1685 / 3200)
    assert _session.breakdown(serve_session).keys() == {"idle_gaps", "decode_gap_split_ms"}


def test_ring_spans_and_the_clock_mapping(serve_session):
    assert serve_session["ring_ms"]["vs.serve-decode.fetch"] == pytest.approx([0.94, 0.94, 0.94])
    # of the two queue waits, the one that ends where the prefill's span begins is mapped onto it
    assert (serve_session["queue_waits"], serve_session["queue_waits_ending_at_a_prefill"]) == (2, 1)


def test_serve_readers_pick_from_the_reduction(serve_session):
    run = types.SimpleNamespace(traffic_kind="open_loop", kind="serve", _session_reduced=serve_session)
    chat = next(m for m in discover(os.path.join(REPO, "benchmark", "layer_metrics"))
                if "sched_queue_wait_ms_p50.chat" in m.METRICS)
    got = chat.read(run)
    assert set(got) == set(chat.METRICS)
    assert got["decode_device_ms_p50.chat"] == pytest.approx(0.7)
    assert got["decode_fetch_ms_p50.chat"] == pytest.approx(0.94)
    assert got["decode_host_gap_ms_p50.chat"] == pytest.approx(0.6)
    assert got["logits_mb_to_host_per_step.chat"] == pytest.approx(2 * 64 * 4 / 1e6)
    assert got["sched_queue_wait_ms_p50.chat"] == pytest.approx((0.498 + 0.1) / 2)
    assert got["idle_unattributed_share.chat"] == pytest.approx(100 * 1685 / 3200)
    assert chat.read(types.SimpleNamespace(traffic_kind="closed_loop", kind="serve")) == {}


def test_train_readers_and_a_trace_without_a_device():
    steps = [(0, 2000, "jit_step(9)"), (2030, 4030, "jit_step(9)"), (4070, 6070, "jit_step(9)"), (6080, 6090, "jit_convert(1)")]
    host = [(1990, 2025, "bm.data"), (2000, 2020, "vs.data-load"), (2025, 4035, "bm.step"), (2026, 2029, "vs.train-step")]
    pd = _trace([(a, b) for a, b, _ in steps], steps, host)
    ring = _ring(("vs.train-step", 2026, 2029), ("vs.data-load", 2000, 2020), ("vs.train-step", 4060, 4069))
    run = types.SimpleNamespace(kind="train", traffic_kind="train_steps", _session_reduced=None,
                                session=types.SimpleNamespace(profile=pd, spans=ring, to_trace_ns=lambda s: s * 1e9,
                                                              counters={}))
    train = next(m for m in discover(os.path.join(REPO, "benchmark", "layer_metrics"))
                 if "step_device_ms_p50.train" in m.METRICS)
    got = train.read(run)
    assert got["step_device_ms_p50.train"] == pytest.approx(2.0)
    assert got["step_host_gap_ms_p50.train"] == pytest.approx(0.035)
    assert got["step_dispatch_ms_p50.train"] == pytest.approx(0.006)
    assert got["data_load_ms_p50.train"] == pytest.approx(0.02)
    # 30 us inside bm.data, 40 us of which bm.step covers the first 5; the 10 us gap is under MIN_GAP_NS
    assert got["idle_unattributed_share.train"] == pytest.approx(100 * 35 / 70)
    assert run._session_reduced is not None                     # reduced once, kept on the record
    no_device = types.SimpleNamespace(kind="train", traffic_kind="train_steps", session=types.SimpleNamespace(
        profile=jax.profiler.ProfileData.from_text_proto('planes { id: 2 name: "/host:CPU" }'), spans=[],
        to_trace_ns=lambda s: None, counters={}))
    assert train.read(no_device) == {} and _session.reduced(types.SimpleNamespace(session=None)) is None


def test_host_stall_readers_need_no_trace():
    host = next(m for m in discover(os.path.join(REPO, "benchmark", "layer_metrics"))
                if "host_stall_ms_max.train" in m.METRICS)
    from benchmark.record import RunRecord

    run = RunRecord(kind="train", chips=1, traffic_kind="train_steps", window=(0.0, 10.0),
                    step_s=[0.2, 0.2, 0.5, 0.2, 9.0], step_end=[1, 2, 3, 4, 11],
                    host_sched={"thread_runq_wait_ns": 2.5e6})
    assert host.read(run) == {"host_stall_ms_max.train": pytest.approx(300.0)}
    serve = RunRecord(kind="serve", chips=1, traffic_kind="closed_loop", window=(0.0, 10.0),
                      decodes=[(0.0, 0.07, 2), (1.0, 1.07, 2), (2.0, 2.21, 2)], host_sched={"thread_runq_wait_ns": None})
    assert host.read(serve) == {"host_stall_ms_max.batch": pytest.approx(140.0)}
    serve.decodes = []      # nothing in the window: left out, not zero
    assert host.read(serve) == {"host_stall_ms_max.batch": None}


# --------------------------------------------------- a trace from the chip
def test_reduction_of_a_session_recorded_on_the_v5e():
    """``testdata/session_chat_decode_prefill``: 0.39 s of this PR's own
    ``--trace 2`` run of ``mistral7b_serve_chat`` on the TPU v5e (two decode
    calls, one prefill, two more; cut to the device's op and module lines and
    the ``vs.*`` / ``bm.*`` annotations), with the session's ring and marker."""
    data = os.path.join(REPO, "benchmark", "testdata", "session_chat_decode_prefill")
    with open(data + ".session.json") as f:
        side = json.load(f)
    ring = [types.SimpleNamespace(**s) for s in side["spans"]]
    to_ns = lambda epoch_s: (epoch_s - side["mark_epoch_s"]) * 1e9 + side["mark_trace_ns"]
    from benchmark import xplane

    got = _session.reduce(xplane.load(data + ".xplane.pb"), ring, to_ns, side["counters"])
    assert got["main_module"].startswith("jit_decode(")
    assert got["decode_device_ms"] == pytest.approx([65.909495, 65.906919, 66.068891, 65.94262])
    assert got["prefill_device_ms"] == pytest.approx([86.882887])
    assert got["decode_gap_ms"] == pytest.approx([3.484373, 3.846682])       # the pair around the prefill is left out
    split = got["decode_gap_split_ms"]
    assert split == pytest.approx({"fetch": 1.7915075, "sample": 0.38372, "enqueue": 1.197775, "other": 0.292525})
    assert sum(split.values()) == pytest.approx((3.484373 + 3.846682) / 2)
    assert {n for n, _ in got["idle_gaps"]} >= {"vs.serve-sample", "vs.serve-decode.fetch", "vs.serve-decode (enqueue)"}
    # by time: the five whole gaps have 0.17-0.29 ms of 2.0-3.8 outside every span (the loop's bookkeeping);
    # the clip's first and last gap lost the spans around them in the cutting, 2.05 and 1.48 ms
    assert got["idle_unattributed_share"] == pytest.approx(21.072, abs=1e-3)
    assert _session.p50(got["ring_ms"]["vs.serve-decode.fetch"]) == pytest.approx(67.8029, abs=1e-3)
    # the scheduler's queue wait was recorded after the fact on the epoch clock: the session's
    # offset lays its end on the start of the engine's live prefill span, to half a millisecond
    assert (got["queue_waits"], got["queue_waits_ending_at_a_prefill"]) == (1, 1)
    assert side["counters"]["logits_bytes_to_host"] == 32 * 32768 * 4 * side["counters"]["decode_steps"]


# ------------------------------------------------- modes 0 and 2 on the CPU rig
@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """The toy checkout with this repo's session metrics declared for the toy cells."""
    root = make_tiny_root(str(tmp_path_factory.mktemp("bm_session_root")))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    have = {m["name"] for m in bench["per_layer"]}
    for m in load_benchmark(REPO)["per_layer"]:
        if m["name"] not in have:
            bench["per_layer"].append(dict(m, workloads=[TINY_OF[w] for w in m["workloads"]]))
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


def _run(root, workload, mode, monkeypatch):
    monkeypatch.setattr(harness, "TRACE_SECONDS", 0.4)
    monkeypatch.setattr(serve_cell, "TRACE_SECONDS", 0.4)
    monkeypatch.setattr(serve_cell, "EXTENSION_S", 3.0)
    spec = load_cell(workload, root)
    monkeypatch.setattr(spec.family(), "TRAIN_LOSS_TOLERANCE", 2e-2)   # the toy's loss is a mean over 64 positions
    runner = {"train": train_cell.run_cell, "serve": serve_cell.run_cell}[spec.kind]
    devices = jax.devices()[: spec.chips]
    rec, correct, attempted, failed, notes = runner(spec, devices, 2**31 + 11, 1.0, mode, time.perf_counter())
    assert correct, notes
    result = result_object(spec, rec, devices, correct=correct, attempted=attempted, failed=failed, traced=mode)
    return spec, rec, notes, result


@pytest.mark.parametrize("workload", ["tiny_train", "tiny_chat", "tiny_batch"])
def test_mode_2_measures_what_mode_0_measures_and_then_traces(tiny_root, workload, monkeypatch):
    spec, rec0, notes0, plain = _run(tiny_root, workload, 0, monkeypatch)
    _, rec2, notes2, both = _run(tiny_root, workload, 2, monkeypatch)
    e2e = {m["name"] for m in spec.end_to_end}
    assert set(plain["metrics"]) == e2e and rec0.session is None and rec0.traced_window is None
    # the last line of mode 2 holds the end-to-end metrics of mode 0 and the per-layer metrics side by side
    assert e2e < set(both["metrics"]) and set(both["metrics"]) - e2e <= {m["name"] for m in spec.per_layer}
    assert {n for n in both["metrics"] if n.startswith("host_stall_ms_max")}
    assert set(both) >= {"correct", "attempted", "failed", "metrics", "device"}
    json.dumps(both)
    # the program's tracing was off while the window was open, in both modes, and off again afterwards
    for notes in (notes0, notes2):
        assert notes["program_tracing_in_window"] == {"ndtimeline": False, "telemetry": False}
        assert notes["compiles_in_window"] == 0 and notes["host_sched_in_window"]["seconds"] > 0
        assert "canary_late_ms_at_s" not in notes and "canary_ticks" not in notes["host_sched_in_window"]
    from vescale_tpu.ndtimeline import api as nd

    assert not nd.is_active() and not nd.session_active()
    # the session came after the window and left its spans, counters and trace on the record
    assert rec2.session is not None and rec2.traced_window[0] >= rec2.window[1]
    assert rec2.session.profile is not None and not os.path.exists(rec2.session.xplane_path)   # read, then deleted
    assert notes2["session_cost_s"].keys() == {"first_start_and_stop", "session_start", "session_stop_and_load"}
    metrics = {s.metric for s in rec2.session.spans}
    if spec.kind == "train":
        assert metrics == {"vs.train-step", "vs.data-load"} and rec2.traced_steps and not rec0.traced_steps
        assert all(s[0] >= rec2.window[1] for s in rec2.traced_steps)
        assert rec0.tokens_per_step == rec2.tokens_per_step
        assert all(stats.in_window(end, rec.window) or end == rec.step_end[-1] for rec in (rec0, rec2) for end in rec.step_end)
    else:
        assert {"vs.serve-decode", "vs.serve-decode.fetch", "vs.serve-sample", "serve-decode-token"} <= metrics
        assert rec2.session.counters["decode_steps"] > 0
        # the window's plan is that of mode 0 to the letter
        plan = lambda rec: {rid: (round(r.due - rec.window[0], 9) if rec.traffic_kind == "open_loop" else 0.0,
                                  r.prompt_len, r.max_new_tokens)
                            for rid, r in rec.requests.items()
                            if (stats.in_window(r.due, rec.window) if rec.traffic_kind == "open_loop" else rid < 8)}
        assert plan(rec0) == plan(rec2) and len(plan(rec0)) >= 8
        if rec2.traffic_kind == "open_loop":
            assert plain["attempted"] == both["attempted"]
            late = [r for r in rec2.requests.values() if r.due >= rec2.window[1]]
            assert late and not [r for r in rec0.requests.values() if r.due >= rec0.window[1]]
            assert min(r.rid for r in late) == len(rec0.requests)        # new rids, past the window's
    # every sample behind an end-to-end metric lies in the window: what came after counts for nothing
    for rec in (rec0, rec2):
        assert read_metrics(os.path.join(spec.root, "benchmark", "e2e_metrics"), spec.end_to_end, rec).keys() == e2e


def test_mode_2_result_takes_its_gaps_from_the_session(tiny_root, serve_session):
    spec = load_cell("tiny_chat", tiny_root)
    from benchmark.record import RunRecord

    rec = RunRecord(kind="serve", chips=1, traffic_kind="open_loop", window=(0.0, 1.0), memory_peak_bytes=5,
                    memory_peak_bytes_run=7, trace={"busy_s": 1.0, "window_s": 2.0, "device_ops": [["x", 1.0]],
                                                    "idle_gaps": [["bm.decode", 0.1]]})
    rec._session_reduced = serve_session
    out = result_object(spec, rec, jax.devices()[:1], correct=True, attempted=1, failed=0, traced=2)
    # the peak is what mode 0 reports, read as the window closed; the run's later peak has a key of its own
    assert out["device"]["memory_peak_bytes"] == 5 and out["device"]["memory_peak_bytes_run"] == 7
    assert out["device"]["busy_s"] == 1.0
    assert out["breakdown"]["device_ops"] == [["x", 1.0]]
    assert out["breakdown"]["idle_gaps"][1][0] == "vs.serve-sample" and "decode_gap_split_ms" in out["breakdown"]
    assert out["metrics"]["decode_device_ms_p50.chat"] == {"value": pytest.approx(0.7), "unit": "ms"}
    assert "setup_s" in out["metrics"]
