"""The reader of ``prefill_ahead_share.chat`` / ``.batch``
(``benchmark/layer_metrics/session_prefill_ahead.py``) on made-up sessions, the
names of the counters it reads against the engines' own, and its two entries of
``BENCHMARK.json``.

``test_bm_mimo``'s last test holds that PR 50's entries are the LAST of
``BENCHMARK.json`` and counts the metrics that list its cell (1 + 22: true when
it was written).  As ``test_bm_moe_padded`` does for ``test_bm_blockdiff``, this
file tells it AT IMPORT (every worker imports every test module before it runs
one) to read the benchmark as it stood before this PR's two entries were
appended; the older links read through its view, so none of them sees them
(the chain of ROADMAP D14 grew a link)."""

import os
import types

import pytest

import test_bm_mimo
from bm_fixtures import REPO

from benchmark.harness import discover
from benchmark.spec import load_benchmark

NEW_METRICS = ["prefill_ahead_share.chat", "prefill_ahead_share.batch"]


def _before_this_pr(root):
    """``BENCHMARK.json`` without the two per-layer entries PR 52 appended."""
    bench = load_benchmark(root)
    bench["per_layer"] = [m for m in bench["per_layer"] if m["name"] not in NEW_METRICS]
    return bench


test_bm_mimo.load_benchmark = _before_this_pr      # the newest link of the chain: each reads through the next


@pytest.fixture(scope="module")
def reader():
    (found,) = [m for m in discover(os.path.join(REPO, "benchmark", "layer_metrics"))
                if "prefill_ahead_share.batch" in m.METRICS]
    return found


def _run(kind, counters):
    return types.SimpleNamespace(traffic_kind=kind, _session_reduced={"counters": counters})


@pytest.mark.parametrize("kind, sfx", [("open_loop", "chat"), ("closed_loop", "batch")])
def test_the_share_is_the_prefills_left_unread_over_the_prefills_launched(reader, kind, sfx):
    got = reader.read(_run(kind, {"prefill_launches": 40, "prefill_reads_ahead": 39, "decode_steps": 7}))
    assert got == {f"prefill_ahead_share.{sfx}": pytest.approx(97.5)}
    assert reader.read(_run(kind, {"prefill_launches": 12, "prefill_reads_ahead": 0})) == {f"prefill_ahead_share.{sfx}": 0.0}


@pytest.mark.parametrize("counters", [{"prefill_launches": 40}, {"prefill_launches": 0, "prefill_reads_ahead": 0},
                                      {"decode_steps": 73, "decode_steps_ahead": 73}, {}],
                         ids=["a_program_without_the_counter", "no_prefill_launched", "the_parents_counters", "no_counters"])
def test_nothing_to_read_leaves_the_metric_out_and_does_not_raise(reader, counters):
    assert reader.read(_run("closed_loop", counters)) == {} and reader.read(_run("open_loop", counters)) == {}


def test_a_train_run_and_a_run_without_a_session_report_nothing(reader):
    assert reader.read(_run("train_steps", {"prefill_launches": 5, "prefill_reads_ahead": 5})) == {}
    assert reader.read(types.SimpleNamespace(traffic_kind="closed_loop", session=None)) == {}
    assert reader.read(types.SimpleNamespace(traffic_kind="open_loop")) == {}


def test_the_counters_it_reads_are_the_ones_both_engines_report():
    """The names in the reader are the engines': a counter renamed in the program would leave the metric out
    of every line in silence."""
    import inspect

    from vescale_tpu.serve import ServeEngine, hybrid_engine

    for name in ("prefill_launches", "prefill_reads_ahead"):
        assert name in hybrid_engine.COUNTERS and f'"{name}"' in inspect.getsource(ServeEngine.trace_counters)


def test_the_two_entries_are_the_last_of_benchmark_json_and_nothing_else_moved(reader):
    bench = load_benchmark(REPO)
    chat, batch = bench["per_layer"][-2:]
    assert [chat["name"], batch["name"]] == NEW_METRICS == sorted(reader.METRICS, reverse=True)
    # layer, moves and cells as ``decode_ahead_share.*`` has them
    ahead = {m["name"]: m for m in bench["per_layer"] if m["name"].startswith("decode_ahead_share.")}
    for entry in (chat, batch):
        twin = ahead[entry["name"].replace("prefill_", "decode_")]
        assert {k: v for k, v in entry.items() if k != "name"} == {k: v for k, v in twin.items() if k != "name"}
        declared = reader.METRICS[entry["name"]]
        assert (entry["unit"], entry["layer"], entry["moves"]) == (declared["unit"], declared["layer"], declared["moves"])
        assert entry["source"] == "program_counter" and entry["better"] == "higher"
    assert chat["workloads"] == ["mistral7b_serve_chat"] and len(batch["workloads"]) == 7
    before = _before_this_pr(REPO)
    assert before["per_layer"] == bench["per_layer"][:-2]
    assert all(before[key] == bench[key] for key in bench if key != "per_layer")
