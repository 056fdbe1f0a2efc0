"""The reader of ``blockdiff_fused_commit_share.batch``
(``benchmark/layer_metrics/session_block_fused.py``) on made-up sessions, the names of
the counters it reads against the engine's own, and its entry of ``BENCHMARK.json``.

``test_bm_programs``' last test holds that PR 38's entries are the LAST of
``BENCHMARK.json`` (true when it was written).  As that file did for
``test_bm_moe_padded``, this one tells it AT IMPORT (every worker imports every
test module before it runs one) to read the benchmark as it stood before this
PR's entry was appended; the older links read through its view, so none of
them sees it (the chain of ROADMAP D14 grew a link)."""

import os
import types

import pytest

import test_bm_programs
from bm_fixtures import REPO

from benchmark.harness import discover
from benchmark.spec import load_benchmark

NEW_METRIC = "blockdiff_fused_commit_share.batch"
CELL = "sdar30b_serve_blockgen"


def _before_this_pr(root):
    """``BENCHMARK.json`` without the per-layer entry PR 39 appended."""
    bench = load_benchmark(root)
    bench["per_layer"] = [m for m in bench["per_layer"] if m["name"] != NEW_METRIC]
    return bench


test_bm_programs.load_benchmark = _before_this_pr      # the newest link of the chain: each reads through the next


@pytest.fixture(scope="module")
def reader():
    (found,) = [m for m in discover(os.path.join(REPO, "benchmark", "layer_metrics")) if NEW_METRIC in m.METRICS]
    return found


def _run(kind, counters):
    return types.SimpleNamespace(traffic_kind=kind, _session_reduced={"counters": counters})


@pytest.mark.parametrize("fused, commits, share", [(185, 186, 100.0 * 185 / 186), (96, 96, 100.0), (0, 32, 0.0)],
                         ids=["all_but_a_requests_last_block", "every_commit_rode", "every_commit_was_a_call_of_its_own"])
def test_the_share_is_the_commits_that_rode_over_the_commits(reader, fused, commits, share):
    got = reader.read(_run("closed_loop", {"block_commit_passes": commits, "block_commits_fused": fused,
                                           "block_commits_deferred": 3, "block_passes": 5 * commits, "decode_steps": 9}))
    assert got == {NEW_METRIC: pytest.approx(share)}


@pytest.mark.parametrize("counters", [{"block_commit_passes": 32, "block_passes": 160},
                                      {"block_commit_passes": 0, "block_commits_fused": 0}, {"decode_steps": 73}, {}],
                         ids=["a_program_without_the_counter", "no_commit_read", "no_block_engine", "no_counters"])
def test_nothing_to_read_leaves_the_metric_out_and_does_not_raise(reader, counters):
    assert reader.read(_run("closed_loop", counters)) == {}


def test_a_chat_run_a_train_run_and_a_run_without_a_session_report_nothing(reader):
    full = {"block_commit_passes": 6, "block_commits_fused": 5}
    assert reader.read(_run("open_loop", full)) == {} and reader.read(_run("train_steps", full)) == {}
    assert reader.read(types.SimpleNamespace(traffic_kind="closed_loop", session=None)) == {}
    assert reader.read(types.SimpleNamespace(traffic_kind="closed_loop")) == {}


def test_the_counters_it_reads_are_the_ones_the_engine_reports():
    """The names in the reader are the engine's: a counter renamed in the
    program would leave the metric out in silence."""
    from vescale_tpu.serve import hybrid_engine

    assert {"block_commit_passes", "block_commits_fused", "block_commits_deferred"} <= set(hybrid_engine.BLOCK_COUNTERS)


def test_the_entry_is_the_last_of_benchmark_json_and_nothing_else_moved(reader):
    bench = load_benchmark(REPO)
    entry = bench["per_layer"][-1]
    declared = reader.METRICS[NEW_METRIC]
    assert entry == {"name": NEW_METRIC, "unit": declared["unit"], "better": "higher", "source": "program_counter",
                     "layer": declared["layer"], "moves": declared["moves"], "workloads": [CELL]}
    assert (declared["unit"], declared["layer"], declared["moves"]) == ("%", "Block diffusion", "serve_tokens_per_s")
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"][:-1]}, "the layer's name as the benchmark already has it"
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == entry["moves"])["workloads"]
    before = _before_this_pr(REPO)
    assert before["per_layer"] == bench["per_layer"][:-1]
    assert all(before[key] == bench[key] for key in bench if key != "per_layer")
