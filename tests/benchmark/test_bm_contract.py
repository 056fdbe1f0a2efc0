"""BENCHMARK.json against the contract's rules that a test can check, and a
later PR's way of adding a configuration, a mix, a cell and a per-layer metric
by files and entries alone."""

import json
import os
import re
import time

import jax
import pytest

from bm_fixtures import REPO, make_tiny_root

from benchmark import serve_cell
from benchmark.harness import discover, result_object
from benchmark.spec import SpecError, device_peaks, load_benchmark, load_cell

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = load_benchmark(REPO)
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def _reports(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer",
                          "trace_in_run"}
    assert BENCH["trace_in_run"] is True
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(BENCH["workloads"]) <= 24 and 1 <= len(BENCH["per_layer"]) <= 128
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, len(BENCH["workloads"]) // 4)


@pytest.mark.parametrize("entry", METRICS + BENCH["workloads"] + BENCH["configs"], ids=lambda e: e["name"])
def test_names_units_and_keys(entry):
    assert NAME.match(entry["name"])
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        allowed = {"name", "unit", "better", "source", "workloads"} | (
            {"layer", "moves"} if "layer" in entry else {"bound"})
        assert set(entry) <= allowed
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key] and "\t" not in entry[key]
    for key in ("config", "traffic"):
        if key in entry:
            assert NAME.match(entry[key])


def test_no_name_twice():
    for group in (METRICS, BENCH["workloads"], BENCH["configs"]):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_end_to_end_bounds_and_sources():
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_moves_names_an_end_to_end_metric_every_reporting_cell_reports(metric):
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert metric["moves"] in e2e
    for cell in (w["name"] for w in BENCH["workloads"]):
        if _reports(metric, cell):
            assert _reports(e2e[metric["moves"]], cell), (metric["name"], cell)


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_reports_enough_and_its_files_are_there(cell):
    spec = load_cell(cell, REPO)
    e2e = {m["name"] for m in spec.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and spec.per_layer
    assert spec.chips in (1, 4)
    assert spec.config["kind"] in ("train", "serve") and spec.traffic["kind"]
    declared = next(c for c in BENCH["configs"] if c["name"] == spec.config_name)
    assert declared["reduced"] == spec.config["reduced"] and declared["source"] == spec.config["source"]
    for key in declared["reduced"]:
        assert not re.search(r"(_dim|_rank|hidden_size|intermediate_size|head_dim|experts_per_tok)$", key)
        assert spec.config["published"][key] != spec.config[key]


def test_every_configuration_is_used_and_every_file_is_its_own():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    assert all(f.startswith(tuple(p + "/" for p in BENCH["paths"])) for f in files)


def test_layers_are_spelt_one_way_and_readers_match_the_declaration():
    declared = {m["name"]: m for m in BENCH["per_layer"]}
    read = {}
    for module in discover(os.path.join(REPO, "benchmark", "layer_metrics")):
        read.update(module.METRICS)
    assert set(read) == set(declared)
    for name, m in read.items():
        assert (m["unit"], m["layer"], m["moves"]) == (
            declared[name]["unit"], declared[name]["layer"], declared[name]["moves"]), name
    e2e_read = {}
    for module in discover(os.path.join(REPO, "benchmark", "e2e_metrics")):
        e2e_read.update(module.METRICS)
    assert {n: m["unit"] for n, m in e2e_read.items()} == {m["name"]: m["unit"] for m in BENCH["end_to_end"]}


def test_peaks_table_knows_the_v5e_and_refuses_the_rest():
    assert device_peaks("TPU v5 lite", REPO)["bf16_flops_per_s"] == 197e12
    with pytest.raises(SpecError):
        device_peaks("TPU v9", REPO)
    with pytest.raises(SpecError):
        load_cell("no_such_cell", REPO)


def test_a_later_pr_adds_config_traffic_cell_and_metric_by_files_alone(tmp_path):
    """One new file each under configs/, traffic/ and layer_metrics/, and new
    entries in BENCHMARK.json: nothing that was there is edited."""
    root = make_tiny_root(str(tmp_path / "root"))
    before = {}
    for d, _, files in os.walk(root):
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                before[os.path.join(d, f)] = fh.read()

    bench_dir = os.path.join(root, "benchmark")
    with open(os.path.join(bench_dir, "configs", "tiny-mha.serve.json")) as f:
        config = json.load(f)
    config["serve"]["slots"] = 2                                           # a new configuration ...
    with open(os.path.join(bench_dir, "configs", "tiny-mha.serve-2slots.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(bench_dir, "traffic", "tiny_open.json")) as f:
        traffic = json.load(f)
    traffic["rate_per_s"] = 30.0                                           # ... a new mix of an existing kind ...
    with open(os.path.join(bench_dir, "traffic", "tiny_open_fast.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(bench_dir, "layer_metrics", "serve_fast.py"), "w") as f:   # ... a new reader ...
        f.write(
            "from benchmark.layer_metrics import _serve as s\n"
            "METRICS = {'decode_step_ms_p50.fast': {'unit': 'ms', 'layer': 'Serve engine', 'moves': 'itl_p95_ms'}}\n"
            "def read(run):\n"
            "    return {'decode_step_ms_p50.fast': s.decode_step_ms_p50(run)} if run.traffic_kind == 'open_loop' else {}\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    old_entries = json.loads(json.dumps(bench))
    bench["configs"].append({"name": "tiny-mha.serve-2slots", "source": "tests only",
                             "file": "benchmark/configs/tiny-mha.serve-2slots.json", "reduced": [], "why": "toy"})
    bench["workloads"].append({"name": "tiny_fast", "config": "tiny-mha.serve-2slots", "traffic": "tiny_open_fast",
                               "chips": 1, "why": "toy"})                  # ... and a new cell with its metrics
    bench["end_to_end"].append({"name": "itl_p50_ms.fast", "unit": "ms", "better": "lower", "bound": 0.05,
                                "source": "host_clock", "workloads": ["tiny_fast"]})
    bench["per_layer"].append({"name": "decode_step_ms_p50.fast", "unit": "ms", "better": "lower",
                               "source": "host_clock", "layer": "Serve engine", "moves": "itl_p95_ms",
                               "workloads": ["tiny_fast"]})
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        assert bench[group][: len(old_entries[group])] == old_entries[group]
    with open(os.path.join(bench_dir, "e2e_metrics", "serve_fast.py"), "w") as f:
        f.write(
            "from benchmark import stats\n"
            "METRICS = {'itl_p50_ms.fast': {'unit': 'ms'}}\n"
            "def read(run):\n"
            "    if run.kind != 'serve':\n"
            "        return {}\n"
            "    return {'itl_p50_ms.fast': stats.ms(stats.percentile(stats.token_gaps(run.token_times(), run.window), 50))}\n")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    spec = load_cell("tiny_fast", root)
    devices = jax.devices()[:1]
    rec, correct, attempted, failed, notes = serve_cell.run_cell(spec, devices, 3, 1.0, True, time.perf_counter())
    assert correct, notes
    assert rec.slots == 2 and len(rec.requests) == round(30.0 * 1.5)
    traced = result_object(spec, rec, devices, correct=correct, attempted=attempted, failed=failed, traced=True)
    assert set(traced["metrics"]) == {"decode_step_ms_p50.fast"}
    plain = result_object(spec, rec, devices, correct=correct, attempted=attempted, failed=failed, traced=False)
    assert set(plain["metrics"]) == {"itl_p50_ms.fast", "setup_s"}
    # no file that was there changed, save BENCHMARK.json, which only gained entries
    for path, content in before.items():
        if not path.endswith("BENCHMARK.json"):
            with open(path, "rb") as fh:
                assert fh.read() == content, path
