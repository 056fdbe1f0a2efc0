"""The join of launches to device programs (``benchmark/layer_metrics/_programs.py``)
and the two readers over it (``session_programs.py``, ``session_loop.py``) on a
hand-made trace whose numbers can be reckoned on paper, on the chip's recorded
session by the OLD names (nothing to join: nothing reported), and their twelve
entries of ``BENCHMARK.json``.

``test_bm_moe_padded``'s last test holds that PR 37's entry is the LAST of
``BENCHMARK.json`` (true when it was written).  As that file did for
``test_bm_blockdiff``, this one tells it AT IMPORT to read the benchmark as it
stood before this PR's entries were appended; the older links read through its
view (the chain of ROADMAP D14 grew a link)."""

import os
import types

import jax
import pytest

import test_bm_moe_padded
from bm_fixtures import REPO

from benchmark import xplane
from benchmark.harness import discover
from benchmark.layer_metrics import _programs, _session
from benchmark.spec import load_benchmark

CHAT = ["mistral7b_serve_chat"]
BATCH = ["deepseek7b_serve_batch", "granite4hsmall_serve_batch", "deepseekv2_serve_longctx", "sdar30b_serve_blockgen"]
# name -> (source, layer), in the order of BENCHMARK.json; each but the last two in a .chat and a .batch form
NEW = {"decode_program_ms_p50": ("device_trace", "Device"), "prefill_program_ms_p50": ("device_trace", "Device"),
       "prefill_start_wait_ms_p50": ("device_trace", "Serve engine"), "decode_launch_ms_p50": ("program_span", "Serve engine"),
       "loop_books_ms_p50": ("program_span", "Serve control (host loop)"),
       "inbox_wait_ms_p50": ("program_span", "Serve control"), "idle_no_work_share": ("device_trace", "Device")}
CHAT_ONLY = ("inbox_wait_ms_p50", "idle_no_work_share")
NEW_NAMES = [f"{name}.{sfx}" for name in NEW for sfx in (("chat",) if name in CHAT_ONLY else ("chat", "batch"))]


def _before_this_pr(root):
    """``BENCHMARK.json`` without the per-layer entries PR 38 appended."""
    bench = load_benchmark(root)
    bench["per_layer"] = [m for m in bench["per_layer"] if m["name"] not in NEW_NAMES]
    return bench


test_bm_moe_padded.load_benchmark = _before_this_pr      # the newest link of the chain: each reads through the next


# ------------------------------------------------------- a hand-made trace
def _trace(ops, modules, host):
    """An XSpace of one TPU plane and one host plane.  ``ops`` and ``modules``
    are ``(start_us, end_us, name)``; ``host`` is ``(start_us, end_us, name,
    {stat: int})``: a ``TraceAnnotation``'s keyword arguments are its event's stats."""
    def plane(pid, name, lines):
        names = sorted({e[2] for _, evs in lines for e in evs})
        stat_names = sorted({k for _, evs in lines for e in evs for k in (e[3] if len(e) > 3 else {})})
        ids, stat_ids = ({n: i + 1 for i, n in enumerate(ns)} for ns in (names, stat_names))
        meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }} ' for n, i in ids.items())
        meta += "".join(f'stat_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }} ' for n, i in stat_ids.items())
        body = ""
        for k, (line_name, evs) in enumerate(lines):
            events = ""
            for e in evs:
                stats = "".join(f"stats {{ metadata_id: {stat_ids[key]} int64_value: {value} }} "
                                for key, value in (e[3] if len(e) > 3 else {}).items())
                events += (f"events {{ metadata_id: {ids[e[2]]} offset_ps: {int(e[0] * 1e6)} "
                           f"duration_ps: {int((e[1] - e[0]) * 1e6)} {stats}}} ")
            body += f'lines {{ id: {k + 1} name: "{line_name}" timestamp_ns: 0 {events}}} '
        return f'planes {{ id: {pid} name: "{name}" {meta} {body}}}'

    text = plane(1, "/device:TPU:0", [("XLA Ops", ops), ("XLA Modules", modules)]) + plane(2, "/host:CPU", [("python3", host)])
    return jax.profiler.ProfileData.from_text_proto(text)


FUSION = "%fusion.1 = bf16[8,8]{1,0} fusion(x)"
COND = "%cond.5 = (f32[8,8]{1,0}) conditional(p, a, b)"


def _steady_decodes(first_number, first_start, count):
    """``count`` decode launches 800 us apart, each launched 650 us before its
    program starts (the step before is still running): ``(modules, host)``."""
    modules, host = [], []
    for k in range(count):
        start = first_start + 800 * k
        host.append((start - 650, start - 600, "vs.serve-decode.launch", {"launch": first_number + k}))
        modules.append((start, start + 700, "jit_decode(1)"))
    return modules, host


@pytest.fixture(scope="module")
def made():
    """Microseconds.  A decode step in flight when the session starts (its
    program began at 0, under no span); launch 10, a decode step fed from the
    device (a merge program, then the step); launch 11, a prefill of four
    programs that queues behind it; launch 12, a decode step whose expert layer
    is a ``cond`` that holds its branch's two operations; launches 13 and 14,
    two prefills back to back; eight plain decode steps; and launch 23, whose
    program the session never saw."""
    modules = [(0, 700, "jit_decode(1)"),
               (700, 701, "jit_decode_merge(7)"), (701, 1401, "jit_decode(1)"),
               (1401, 1411, "jit_prefill_embed(2)"), (1411, 1701, "jit_prefill_stage(3)"),
               (1701, 1721, "jit_prefill_head(4)"), (1721, 1741, "jit_prefill_commit(5)"),
               (1860, 2560, "jit_decode(1)"),
               (2560, 2570, "jit_prefill_embed(2)"), (2570, 2860, "jit_prefill_stage(3)"),
               (2860, 2880, "jit_prefill_head(4)"), (2880, 2900, "jit_prefill_commit(5)"),
               (3060, 3070, "jit_prefill_embed(2)"), (3070, 3360, "jit_prefill_stage(3)"),
               (3360, 3380, "jit_prefill_head(4)"), (3380, 3400, "jit_prefill_commit(5)")]
    host = [(100, 160, "vs.serve-decode.launch", {"launch": 10}),
            (900, 1000, "vs.serve-prefill.launch", {"launch": 11, "rung": 256, "slot": 3}),
            (1800, 1850, "vs.serve-decode.launch", {"launch": 12}),
            (2000, 2100, "vs.serve-prefill.launch", {"launch": 13, "rung": 256, "slot": 4}),
            (2950, 3050, "vs.serve-prefill.launch", {"launch": 14, "rung": 512, "slot": 5}),
            (90, 1450, "vs.serve-decode"), (890, 1745, "vs.serve-prefill")]
    steady_modules, steady_host = _steady_decodes(15, 4000, 8)
    host.append((10400, 10450, "vs.serve-decode.launch", {"launch": 23}))
    ops = [(a, b, FUSION) for a, b, _ in modules + steady_modules if b - a > 1]
    ops.remove((1860, 2560, FUSION))
    ops += [(1860, 1900, FUSION), (1900, 2100, COND), (1910, 2000, FUSION), (2000, 2090, FUSION), (2100, 2560, FUSION)]
    return _trace(ops, modules + steady_modules, host + steady_host)


def _run(pd, kind="closed_loop", ring=()):
    spans = [types.SimpleNamespace(metric=m, start=a / 1e6, duration=(b - a) / 1e6) for m, a, b in ring]
    return types.SimpleNamespace(traffic_kind=kind, kind="serve", session=types.SimpleNamespace(
        profile=pd, spans=spans, to_trace_ns=lambda s: s * 1e9, counters={}))


def _reader(metric):
    (found,) = [m for m in discover(os.path.join(REPO, "benchmark", "layer_metrics")) if metric in m.METRICS]
    return found


def test_a_launch_takes_the_programs_it_started_by_kind_and_order(made):
    got = _programs.reduce(made)
    assert (got["seen"], got["joined"]) == (14, 13) and _programs.trusted(got)
    by_number = {launch.number: launch for launch in got["launches"]}
    assert sorted(by_number) == list(range(10, 24))
    us = lambda events: [(round(a / 1e3), round(b / 1e3)) for a, b, _ in events]
    # the step in flight at the session's start began before any span did: nobody takes it
    assert us(by_number[10].modules) == [(700, 701), (701, 1401)] and by_number[10].program_ns == pytest.approx(700e3)
    # a prefill of four programs, queued behind the decode step: they start 501 us after its enqueue did
    first = by_number[11]
    assert (first.kind, first.rung, first.slot) == ("prefill", 256, 3) and len(first.modules) == 4
    assert first.program_ns == pytest.approx((10 + 290 + 20 + 20) * 1e3) and first.start_wait_ns == pytest.approx(501e3)
    # two prefills back to back: a name come round again opens the next one's
    assert us(by_number[13].modules)[0] == (2560, 2570) and us(by_number[14].modules)[0] == (3060, 3070)
    assert len(by_number[13].modules) == len(by_number[14].modules) == 4 and by_number[14].rung == 512
    assert us(by_number[12].modules) == [(1860, 2560)]
    # a launch whose program never arrives is seen, not joined, and guesses nothing
    assert not by_number[23].joined and by_number[23].program_ns is None and by_number[23].start_wait_ns is None


def test_an_op_inside_another_is_counted_once(made):
    launch = next(x for x in _programs.reduce(made)["launches"] if x.number == 12)
    assert [xplane.op_family(n) for _, _, n in launch.ops] == ["fusion", "cond", "fusion"]
    assert [xplane.op_family(n) for _, _, n in launch.ops_inner] == ["fusion", "fusion"]
    assert sum(b - a for a, b, _ in launch.ops) == pytest.approx(launch.program_ns)


@pytest.mark.parametrize("kind, sfx", [("open_loop", "chat"), ("closed_loop", "batch")])
def test_the_program_readers_pick_from_the_join(made, kind, sfx):
    reader = _reader("decode_program_ms_p50.batch")
    ring = [("vs.serve-decode.launch", 100, 160), ("vs.serve-decode.launch", 1800, 1850), ("vs.serve-decode.launch", 3350, 3390)]
    got = reader.read(_run(made, kind, ring))
    assert set(got) == {name for name in reader.METRICS if name.endswith("." + sfx)}
    assert got[f"decode_program_ms_p50.{sfx}"] == pytest.approx(0.7)
    assert got[f"prefill_program_ms_p50.{sfx}"] == pytest.approx(0.34)
    assert got[f"prefill_start_wait_ms_p50.{sfx}"] == pytest.approx(0.501)      # 501, 560 and 110 us
    assert got[f"decode_launch_ms_p50.{sfx}"] == pytest.approx(0.05)


def test_under_nine_launches_in_ten_joined_the_metrics_are_left_out():
    modules, host = _steady_decodes(0, 1000, 5)
    host += [(5000 + 100 * k, 5050 + 100 * k, "vs.serve-decode.launch", {"launch": 5 + k}) for k in range(2)]
    pd = _trace([(a, b, FUSION) for a, b, _ in modules], modules, host)
    got = _programs.reduce(pd)
    assert (got["seen"], got["joined"]) == (7, 5) and not _programs.trusted(got)
    assert _reader("decode_program_ms_p50.batch").read(_run(pd)) == {}


def test_the_old_names_leave_the_metrics_out_instead_of_guessing():
    """``testdata/session_chat_decode_prefill``: a chip's session from before
    the launches had spans and the programs their names (``jit_stage``,
    ``jit_head_last``, ``jit__lambda``): the old readers still read it; the new ones report nothing."""
    pd = xplane.load(os.path.join(REPO, "benchmark", "testdata", "session_chat_decode_prefill.xplane.pb"))
    assert _session.reduce(pd, [], lambda s: s * 1e9, {}) is not None
    assert _programs.reduce(pd) is None
    run = _run(pd, "open_loop")
    assert _reader("decode_program_ms_p50.chat").read(run) == {}
    loop = _reader("loop_books_ms_p50.chat").read(run)
    assert all(value is None for value in loop.values())


def test_a_train_run_a_run_without_a_session_and_a_trace_without_a_device_report_nothing(made):
    for reader in (_reader("decode_program_ms_p50.chat"), _reader("loop_books_ms_p50.chat")):
        assert reader.read(_run(made, "train_steps")) == {}
        assert reader.read(types.SimpleNamespace(traffic_kind="closed_loop", session=None)) == {}
        assert reader.read(types.SimpleNamespace(traffic_kind="open_loop")) == {}
        no_device = jax.profiler.ProfileData.from_text_proto('planes { id: 2 name: "/host:CPU" }')
        assert reader.read(_run(no_device, "open_loop")) == {}


def test_the_loop_readers_and_the_idle_under_the_idle_span():
    """Two busy blocks 0-1000 and 5000-6000 us and a third at 9000: of 7000 us
    of idle, the loop slept 2 x 1000 + 500 under ``vs.serve-idle`` (a slice
    that begins while the chip is still busy counts for its idle part)."""
    modules = [(0, 1000, "jit_decode(1)"), (5000, 6000, "jit_decode(1)"), (9000, 9100, "jit_decode(1)")]
    host = [(500, 1500, "vs.serve-idle"), (2000, 3000, "vs.serve-idle"), (3500, 4500, "vs.serve-idle"),
            (6100, 6200, "vs.serve-boundary"), (-10, 100, "vs.serve-decode.launch", {"launch": 0})]
    pd = _trace([(a, b, FUSION) for a, b, _ in modules], modules, host)
    ring = [("vs.serve-books", 0, 300), ("vs.serve-books", 1000, 1100), ("vs.serve-books", 2000, 2200),
            ("serve-inbox-wait", 0, 2000), ("serve-inbox-wait", 0, 4000)]
    reader = _reader("idle_no_work_share.chat")
    chat = reader.read(_run(pd, "open_loop", ring))
    assert chat == {"loop_books_ms_p50.chat": pytest.approx(0.2), "inbox_wait_ms_p50.chat": pytest.approx(3.0),
                    "idle_no_work_share.chat": pytest.approx(100 * 2500 / 7000)}
    assert reader.read(_run(pd, "closed_loop", ring)) == {"loop_books_ms_p50.batch": pytest.approx(0.2)}
    # traced seconds that always had a request to serve: the loop is tiled and never slept, so the share is 0
    busy = _trace([(a, b, FUSION) for a, b, _ in modules], modules, [(6100, 6200, "vs.serve-boundary")])
    assert reader.read(_run(busy, "open_loop", ring))["idle_no_work_share.chat"] == 0.0
    # a program without the spans: each metric is left out (None is dropped by read_metrics), none is zero
    bare = _trace([(a, b, FUSION) for a, b, _ in modules], modules, [(6100, 6200, "bm.decode")])
    assert set(reader.read(_run(bare, "open_loop")).values()) == {None}


def test_the_span_and_counter_names_are_the_programs_own():
    """A span renamed in the program would leave a metric out in silence."""
    from vescale_tpu.ndtimeline import predefined as P
    from vescale_tpu.serve import hybrid_engine

    loop = _reader("loop_books_ms_p50.chat")
    assert set(_programs.LAUNCH_SPANS) == {P.SERVE_DECODE_LAUNCH, P.SERVE_PREFILL_LAUNCH}
    assert (loop.IDLE_SPAN, loop.TILED) == (P.SERVE_IDLE, P.SERVE_BOUNDARY)
    source = open(loop.__file__).read() + open(_reader("decode_program_ms_p50.chat").__file__).read()
    assert all(f'"{name}"' in source for name in (P.SERVE_BOOKS, P.SERVE_INBOX_WAIT, P.SERVE_DECODE_LAUNCH))
    assert {"decode_launches", "prefill_launches"} <= set(hybrid_engine.COUNTERS)


def test_the_twelve_entries_are_the_last_of_benchmark_json_and_nothing_else_moved():
    bench = load_benchmark(REPO)
    entries = bench["per_layer"][-len(NEW_NAMES):]
    assert [m["name"] for m in entries] == NEW_NAMES and len(NEW_NAMES) == 12
    declared = {**_reader("decode_program_ms_p50.chat").METRICS, **_reader("loop_books_ms_p50.chat").METRICS}
    assert set(declared) == set(NEW_NAMES)
    for entry in entries:
        base, sfx = entry["name"].rsplit(".", 1)
        assert (entry["source"], entry["layer"]) == NEW[base]
        assert (entry["workloads"], entry["moves"]) == ((CHAT, "itl_p95_ms") if sfx == "chat" else (BATCH, "serve_tokens_per_s"))
        assert (entry["unit"], entry["layer"], entry["moves"]) == tuple(declared[entry["name"]][k] for k in ("unit", "layer", "moves"))
        assert entry["better"] == ("higher" if base == "idle_no_work_share" else "lower")
    # the cells of the .batch entries are those decode_device_ms_p50.batch lists
    assert BATCH == next(m for m in bench["per_layer"] if m["name"] == "decode_device_ms_p50.batch")["workloads"]
    before = _before_this_pr(REPO)
    assert before["per_layer"] == bench["per_layer"][:-len(NEW_NAMES)]
    assert all(before[key] == bench[key] for key in bench if key != "per_layer")
