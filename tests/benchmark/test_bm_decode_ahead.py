"""The reader of ``decode_ahead_share.chat`` / ``.batch``
(``benchmark/layer_metrics/session_decode_ahead.py``) on made-up sessions, and
its two entries of ``BENCHMARK.json``.

``test_bm_mla``'s last test holds that PR 34's entries are the LAST of
``BENCHMARK.json`` (true when it was written).  As that file does for
``test_bm_hybrid``, this one tells it AT IMPORT (every worker imports every test
module before it runs one) to read the benchmark as it stood before this PR's
two entries were appended; ``test_bm_hybrid`` reads through ``test_bm_mla``'s
view, so it sees neither PR's."""

import os
import types

import pytest

import test_bm_mla
from bm_fixtures import REPO

from benchmark.harness import discover
from benchmark.spec import load_benchmark

NEW_METRICS = ["decode_ahead_share.chat", "decode_ahead_share.batch"]
BATCH_CELLS = ["deepseek7b_serve_batch", "granite4hsmall_serve_batch", "deepseekv2_serve_longctx"]


def _before_this_pr(root):
    """``BENCHMARK.json`` without the two per-layer entries PR 35 appended."""
    bench = load_benchmark(root)
    bench["per_layer"] = [m for m in bench["per_layer"] if m["name"] not in NEW_METRICS]
    return bench


test_bm_mla.load_benchmark = _before_this_pr


@pytest.fixture(scope="module")
def reader():
    (found,) = [m for m in discover(os.path.join(REPO, "benchmark", "layer_metrics"))
                if "decode_ahead_share.batch" in m.METRICS]
    return found


def _run(kind, counters):
    return types.SimpleNamespace(traffic_kind=kind, _session_reduced={"counters": counters})


@pytest.mark.parametrize("kind, sfx", [("open_loop", "chat"), ("closed_loop", "batch")])
def test_the_share_is_the_steps_launched_ahead_over_the_steps_read(reader, kind, sfx):
    got = reader.read(_run(kind, {"decode_steps": 400, "decode_steps_ahead": 390, "decode_pages_read": 7}))
    assert got == {f"decode_ahead_share.{sfx}": pytest.approx(97.5)}
    assert reader.read(_run(kind, {"decode_steps": 12, "decode_steps_ahead": 0})) == {f"decode_ahead_share.{sfx}": 0.0}


@pytest.mark.parametrize("counters", [{"decode_steps": 400}, {"decode_steps": 0, "decode_steps_ahead": 0}, {}],
                         ids=["a_program_without_the_counter", "no_step_read", "no_counters"])
def test_nothing_to_read_leaves_the_metric_out_and_does_not_raise(reader, counters):
    assert reader.read(_run("closed_loop", counters)) == {}


def test_a_train_run_and_a_run_without_a_session_report_nothing(reader):
    assert reader.read(_run("train_steps", {"decode_steps": 5, "decode_steps_ahead": 5})) == {}
    assert reader.read(types.SimpleNamespace(traffic_kind="closed_loop", session=None)) == {}
    assert reader.read(types.SimpleNamespace(traffic_kind="open_loop")) == {}


def test_the_two_entries_are_the_last_of_benchmark_json_and_nothing_else_moved(reader):
    bench = load_benchmark(REPO)
    chat, batch = bench["per_layer"][-2:]
    assert [chat["name"], batch["name"]] == NEW_METRICS == sorted(reader.METRICS, reverse=True)
    assert (chat["workloads"], chat["moves"]) == (["mistral7b_serve_chat"], "itl_p95_ms")
    assert (batch["workloads"], batch["moves"]) == (BATCH_CELLS, "serve_tokens_per_s")
    for entry in (chat, batch):
        declared = reader.METRICS[entry["name"]]
        assert (entry["unit"], entry["layer"], entry["moves"]) == (declared["unit"], declared["layer"], declared["moves"])
        assert entry["source"] == "program_counter" and entry["better"] == "higher"
    before = _before_this_pr(REPO)
    assert before["per_layer"] == bench["per_layer"][:-2]
    assert all(before[key] == bench[key] for key in bench if key != "per_layer")
