"""The harness's own functions carry a train run and a serve run of each
traffic kind through to a well-formed result object, on the CPU, at toy sizes,
steered from here (the harness has no switch for it)."""

import json
import os
import subprocess
import sys
import time

import jax
import pytest

from bm_fixtures import REPO, make_tiny_root

from benchmark import serve_cell, train_cell
from benchmark.harness import result_object
from benchmark.spec import load_cell


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_tiny_root(str(tmp_path_factory.mktemp("bm_root")))


def _well_formed(result, spec, traced):
    assert set(result) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert result["attempted"] > 0 and result["failed"] == 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(result["device"])
    wanted = spec.per_layer if traced else spec.end_to_end
    units = {m["name"]: m["unit"] for m in wanted}
    assert result["metrics"], "a cell reports at least one metric"
    for name, m in result["metrics"].items():
        assert m["unit"] == units[name] and isinstance(m["value"], float)
    json.dumps(result)


@pytest.mark.parametrize("workload,runner", [
    ("tiny_train", train_cell.run_cell), ("tiny_train4", train_cell.run_cell),
    ("tiny_chat", serve_cell.run_cell), ("tiny_batch", serve_cell.run_cell)])
@pytest.mark.parametrize("traced", [False, True])
def test_cell_runs_to_a_result(tiny_root, workload, runner, traced, monkeypatch):
    # the toy's loss is a mean over 64 positions, not 4096: its bf16 errors cancel eight times less
    spec = load_cell(workload, tiny_root)
    monkeypatch.setattr(spec.family(), "TRAIN_LOSS_TOLERANCE", 2e-2)
    devices = jax.devices()[: spec.chips]
    rec, correct, attempted, failed, notes = runner(spec, devices, 2**31 + 11, 1.0, traced, time.perf_counter())
    assert correct, str(notes.get("problems") or notes.get("ledger") or notes)
    assert notes["compiles_in_window"] == 0
    result = result_object(spec, rec, devices, correct=correct, attempted=attempted, failed=failed, traced=traced)
    _well_formed(result, spec, traced)
    names = set(result["metrics"])
    if not traced:
        assert "setup_s" in names and len(names) >= 2
        assert names == {m["name"] for m in spec.end_to_end}
    else:
        # the CPU has no device plane: metrics read from the trace are left out, the rest are there
        absent = {m["name"] for m in spec.per_layer} - names
        assert all(n.split(".")[0] in ("device_idle_share", "collective_share", "mfu", "peak_hbm_gb") for n in absent), absent


def test_command_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "benchmark/run.py", "--workload", "mistral7b_train_seq4096",
                          "--seed", "1", "--seconds", "1", "--trace", "0"],
                         cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert not any(line.startswith("{") for line in out.stdout.splitlines()), out.stdout
    assert "no TPU" in out.stderr
