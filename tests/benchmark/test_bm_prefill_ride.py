"""The reader of ``prefill_ride_share.chat`` / ``.batch`` and
``ride_step_program_ms_p50.batch``
(``benchmark/layer_metrics/session_prefill_ride.py``) on made-up sessions, the
name of the counter it reads against the engine's own, and its three entries
of ``BENCHMARK.json``.

``test_bm_prefill_ahead``'s last test holds that PR 52's two entries are the
LAST of ``BENCHMARK.json``.  As that file does for ``test_bm_mimo``, this one
tells it AT IMPORT (every worker imports every test module before it runs one)
to read the benchmark as it stood before this PR's three entries were
appended; the older links read through its view, so none of them sees them
(the chain of ROADMAP D14 grew a link)."""

import os
import types

import pytest

import test_bm_prefill_ahead
from bm_fixtures import REPO

from benchmark.harness import discover
from benchmark.layer_metrics import _programs
from benchmark.spec import load_benchmark

NEW_METRICS = ["prefill_ride_share.batch", "prefill_ride_share.chat", "ride_step_program_ms_p50.batch"]


def _before_this_pr(root):
    """``BENCHMARK.json`` without the three per-layer entries PR 53 appended."""
    bench = load_benchmark(root)
    bench["per_layer"] = [m for m in bench["per_layer"] if m["name"] not in NEW_METRICS]
    return bench


test_bm_prefill_ahead.load_benchmark = _before_this_pr      # the newest link of the chain: each reads through the next


@pytest.fixture(scope="module")
def reader():
    (found,) = [m for m in discover(os.path.join(REPO, "benchmark", "layer_metrics"))
                if "prefill_ride_share.batch" in m.METRICS]
    return found


def _launch(kind, number, rung, program_ms, joined=True):
    """A launch of ``_programs.py`` whose own program ran ``program_ms`` (a decode step's after its merge)."""
    name = {"decode": "jit_decode", "prefill": "jit_prefill_stage"}[kind]
    start = 1e6 * number
    modules = [(start, start + 2e3, "jit_decode_merge(7)")] if kind == "decode" else []
    if joined:
        modules.append((start + 3e3, start + 3e3 + program_ms * 1e6, f"{name}(11)"))
    return _programs.Launch(kind, number, rung, 0 if rung else None, (start - 5e4, start), modules)


def _run(kind, counters, launches=None):
    programs = None
    if launches is not None:
        programs = {"launches": launches, "seen": len(launches), "joined": sum(x.joined for x in launches)}
    return types.SimpleNamespace(traffic_kind=kind, _session_reduced={"counters": counters}, _programs_reduced=programs)


@pytest.mark.parametrize("kind, sfx", [("open_loop", "chat"), ("closed_loop", "batch")])
def test_the_share_is_the_prompts_a_step_carried_over_the_prompts_launched(reader, kind, sfx):
    got = reader.read(_run(kind, {"prefill_launches": 40, "prefill_rides": 39, "prefill_reads_ahead": 40}))
    assert got == {f"prefill_ride_share.{sfx}": pytest.approx(97.5)}
    assert reader.read(_run(kind, {"prefill_launches": 12, "prefill_rides": 0})) == {f"prefill_ride_share.{sfx}": 0.0}


def test_a_riding_steps_time_is_the_median_of_the_decode_programs_whose_launch_says_rung(reader):
    steps = [_launch("decode", n, None, 7.8) for n in range(20)]
    rides = [_launch("decode", 20 + n, rung, ms) for n, (rung, ms) in enumerate([(128, 9.0), (128, 9.2), (512, 16.0)])]
    got = reader.read(_run("closed_loop", {"prefill_launches": 3, "prefill_rides": 3}, steps + rides))
    assert got == {"prefill_ride_share.batch": 100.0, "ride_step_program_ms_p50.batch": pytest.approx(9.2)}
    # the chat cell declares no such metric; the share is read there all the same
    assert reader.read(_run("open_loop", {"prefill_launches": 3, "prefill_rides": 3}, steps + rides)) == {
        "prefill_ride_share.chat": 100.0}
    # the merge program before the step is no part of it, and a launch that joined nothing is no sample
    unjoined = [_launch("decode", 30, 256, 50.0, joined=False)]
    got = reader.read(_run("closed_loop", {}, steps + rides[:1] + unjoined))
    assert got == {"ride_step_program_ms_p50.batch": pytest.approx(9.0)}


@pytest.mark.parametrize("launches", [
    [_launch("decode", n, None, 7.8) for n in range(9)] + [_launch("prefill", 9, 128, 6.6)],   # the parent: a prefill says rung
    [_launch("decode", n, 128, 9.0, joined=n > 1) for n in range(9)],                          # under nine in ten joined
    None], ids=["the_parents_launches", "a_join_not_trusted", "no_launch_spans"])
def test_a_program_whose_steps_carry_nothing_reports_no_time_and_does_not_raise(reader, launches):
    assert reader.read(_run("closed_loop", {"prefill_launches": 5, "prefill_reads_ahead": 5}, launches)) == {}


@pytest.mark.parametrize("counters", [{"prefill_launches": 40, "prefill_reads_ahead": 40},
                                      {"prefill_launches": 0, "prefill_rides": 0}, {}],
                         ids=["a_program_without_the_counter", "no_prompt_launched", "no_counters"])
def test_nothing_to_read_leaves_the_share_out_and_does_not_raise(reader, counters):
    assert reader.read(_run("closed_loop", counters)) == {} and reader.read(_run("open_loop", counters)) == {}


def test_a_train_run_and_a_run_without_a_session_report_nothing(reader):
    assert reader.read(_run("train_steps", {"prefill_launches": 5, "prefill_rides": 5})) == {}
    assert reader.read(types.SimpleNamespace(traffic_kind="closed_loop", session=None)) == {}
    assert reader.read(types.SimpleNamespace(traffic_kind="open_loop")) == {}


def test_the_counter_it_reads_is_the_one_the_dense_engine_reports_and_the_hybrid_one_does_not():
    """The name in the reader is the engine's: a counter renamed in the program would leave the metric out of
    every line in silence.  ``HybridServeEngine`` carries no prompt and reports no such counter: its cells' lines
    leave the share out, as the parent's do."""
    import inspect

    from vescale_tpu.serve import ServeEngine, hybrid_engine

    assert '"prefill_rides"' in inspect.getsource(ServeEngine.trace_counters)
    assert "prefill_rides" not in hybrid_engine.COUNTERS and not hasattr(hybrid_engine.HybridServeEngine, "rides")


def test_the_three_entries_are_the_last_of_benchmark_json_and_nothing_else_moved(reader):
    bench = load_benchmark(REPO)
    entries = bench["per_layer"][-3:]
    assert [m["name"] for m in entries] == NEW_METRICS and sorted(reader.METRICS) == sorted(NEW_METRICS)
    batch, chat, step = entries
    for entry in entries:
        declared = reader.METRICS[entry["name"]]
        assert (entry["unit"], entry["layer"], entry["moves"]) == (declared["unit"], declared["layer"], declared["moves"])
        assert entry["layer"] == "Serve engine"
    assert all((m["source"], m["better"], m["unit"]) == ("program_counter", "higher", "%") for m in (batch, chat))
    assert (step["source"], step["better"], step["unit"]) == ("device_trace", "lower", "ms")
    # the two cells whose engine carries a prompt; the time in the claimed cell alone
    assert batch["workloads"] == step["workloads"] == ["deepseek7b_serve_batch"] and chat["workloads"] == ["mistral7b_serve_chat"]
    assert (batch["moves"], chat["moves"], step["moves"]) == ("serve_tokens_per_s", "itl_p95_ms", "serve_tokens_per_s")
    before = _before_this_pr(REPO)
    assert before["per_layer"] == bench["per_layer"][:-3]
    assert all(before[key] == bench[key] for key in bench if key != "per_layer")
