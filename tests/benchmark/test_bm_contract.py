"""BENCHMARK.json against the contract's rules that a test can check, and a
later PR's way of adding a configuration, a mix, a cell, a per-layer metric and
a model family by files and entries alone."""

import json
import os
import re
import time

import jax
import pytest

from bm_fixtures import REPO, make_tiny_root

from benchmark import serve_cell
from benchmark.harness import discover, result_object
from benchmark.spec import (SpecError, check_config, device_peaks, family_names, family_path, load_benchmark, load_cell,
                            load_family)

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
    assert spec.config["deployment"] and os.path.isfile(family_path(spec.config["model"], REPO))
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
    before = _snapshot(root)

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
    after = _snapshot(root)
    for path, content in before.items():
        if not path.endswith("BENCHMARK.json"):
            assert after[path] == content, path


# ------------------------------------------------------------ model families
FAMILIES = family_names(REPO)
FAMILY_NAMES = {"serve": ("build_serve", "rehearse_serve", "SERVE_LOGITS_TOLERANCE"),
                "train": ("build_train", "train_flops_per_token", "TRAIN_LOSS_TOLERANCE", "TRAIN_LOGITS_TOLERANCE")}


@pytest.mark.parametrize("model", FAMILIES)
def test_a_family_file_gives_the_names_the_readme_fixes(model):
    family = load_family(model, REPO)
    assert load_family(model, REPO) is family, "one module a file and process: its jitted reference compiles once"
    for name in ("program_config", "logits", "loss_and_logits"):
        assert callable(getattr(family, name)), name
    kinds = {c["kind"] for c in (load_cell(w["name"], REPO).config for w in BENCH["workloads"]) if c["model"] == model}
    assert kinds, f"no cell runs family {model!r}"
    for kind in kinds:
        for name in FAMILY_NAMES[kind]:
            assert hasattr(family, name), (kind, name)
            if name.endswith("TOLERANCE"):
                assert 0 < getattr(family, name) < 1
    with open(os.path.join(REPO, "benchmark", "README.md")) as f:
        readme = f.read()
    for name in ("program_config", "logits", "loss_and_logits") + FAMILY_NAMES["serve"] + FAMILY_NAMES["train"]:
        assert f"`{name}" in readme, f"benchmark/README.md does not state {name}"


def test_a_model_without_a_family_file_is_a_spec_error(tmp_path):
    root = make_tiny_root(str(tmp_path / "root"))
    path = os.path.join(root, "benchmark", "configs", "tiny-gqa.serve.json")
    with open(path) as f:
        config = json.load(f)
    with open(path, "w") as f:
        json.dump(dict(config, model="no_such_family"), f)
    with pytest.raises(SpecError) as e:
        load_cell("tiny_chat", root)
    assert os.path.join("benchmark", "families") in str(e.value) and "'llama'" in str(e.value)
    with pytest.raises(SpecError):
        load_family("_private", root)


DECLARED = {"name": "c", "source": "s", "reduced": ["n_experts", "vocab"]}
SOUND = {"source": "s", "model": "llama", "kind": "serve", "serve": {}, "deployment": "2 chips share each layer",
         "n_experts": 36, "vocab": 50176, "reduced": ["n_experts", "vocab"],
         "published": {"n_experts": 72, "vocab": 100352}, "share": {"chips": 2, "of": ["n_experts", "vocab"]}}


@pytest.mark.parametrize("broken,says", [
    ({}, None),
    ({"share": None}, None),
    ({"kind": "both"}, "kind"),
    ({"serve": None}, "block"),
    ({"source": "another"}, "source"),
    ({"reduced": ["vocab"]}, "reduced"),
    ({"deployment": ""}, "deployment"),
    ({"published": {"vocab": 100352}}, "n_experts"),
    ({"share": {"chips": 1, "of": ["vocab"]}}, "share"),
    ({"share": {"chips": 2, "of": ["hidden"]}}, "hidden"),
], ids=lambda v: "-".join(v) if isinstance(v, dict) else str(v))
def test_a_configuration_file_states_its_cut_whatever_its_family(broken, says):
    config = {k: v for k, v in dict(SOUND, **broken).items() if v is not None}
    if says is None:
        check_config(config, DECLARED, REPO)
        return
    with pytest.raises(SpecError) as e:
        check_config(config, DECLARED, REPO)
    assert says in str(e.value)


TOY_FAMILY = '''"""A family under a ``model`` the repo does not know.  It wraps the tiny Llama
preset, but its configuration has keys of its own, and it brings its own
builder, its own cache object, its own reference function and its own
tolerance."""
import functools

from benchmark import reference
from benchmark.families import llama

BUILT = []     # every system this file built, for the test to look at
SERVE_LOGITS_TOLERANCE = 3e-2     # bf16 through two toy blocks reads under 1e-2 on the CPU


def _as_llama(c):
    return {"model": "llama", "vocab_size": c["rows"], "hidden_size": c["width"], "intermediate_size": c["ffn"],
            "num_hidden_layers": c["depth"], "num_attention_heads": c["heads"], "num_key_value_heads": c["kv"],
            "head_dim": c["width"] // c["heads"], "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
            "sliding_window": None, "tie_word_embeddings": False}


def program_config(config, *, max_positions, **kw):
    return llama.program_config(_as_llama(config), max_positions=max_positions, **kw)


class CountingCache:
    """Not a ``PagedKVCache``: the runner and the scheduler get this object."""

    def __init__(self, inner):
        self._inner, self.touched = inner, set()

    def __getattr__(self, name):
        self.touched.add(name)
        return getattr(self._inner, name)


class ToySystem:
    def __init__(self, params, cache, engine, vocab):
        self.params, self.cache, self.engine, self.vocab = params, cache, engine, vocab
        self.largest_token, prefill = 0, engine.prefill

        def prefill_and_look(prompt, slot):
            self.largest_token = max(self.largest_token, max(prompt))
            return prefill(prompt, slot)

        engine.prefill = prefill_and_look


def build_serve(config, serve, devices, seed):
    inner = llama.build_serve(_as_llama(config), dict(serve, weight_dtype="bfloat16"), devices, seed)
    BUILT.append(ToySystem(inner.params, CountingCache(inner.cache), inner.engine, config["share_of_rows"]))
    return BUILT[-1]


def logits(params, config, tokens, rows):
    return SIGN * llama.logits(params, _as_llama(config), tokens, rows)


loss_and_logits = functools.partial(reference.loss_and_logits, logits)


def rehearse_serve(name, config, serve, devices):
    return llama.rehearse_serve(name, _as_llama(config), serve, devices)
'''


def _snapshot(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                out[os.path.join(d, f)] = fh.read()
    return out


def _add_toy_family(root, model, sign):
    """What a ``model_config`` PR does: one file under families/, one under
    configs/, and entries and list members in BENCHMARK.json."""
    bench_dir = os.path.join(root, "benchmark")
    with open(os.path.join(bench_dir, "families", model + ".py"), "w") as f:
        f.write(TOY_FAMILY.replace("SIGN", sign))
    config = {"source": "tests only", "model": model, "rows": 256, "width": 64, "ffn": 128, "depth": 2, "heads": 4,
              "kv": 2, "share_of_rows": 128, "reduced": ["rows"], "published": {"rows": 512},
              "share": {"chips": 2, "of": ["rows"]},
              "assumed": {}, "deployment": "none: a toy", "kind": "serve",
              "serve": {"slots": 4, "positions_per_slot": 64, "page_size": 8}}
    with open(os.path.join(bench_dir, "configs", model + ".serve.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = model + "_chat"
    bench["configs"].append({"name": model + ".serve", "source": "tests only",
                             "file": f"benchmark/configs/{model}.serve.json", "reduced": ["rows"], "why": "toy"})
    bench["workloads"].append({"name": cell, "config": model + ".serve", "traffic": "tiny_open", "chips": 1,
                               "why": "toy"})
    for group, name in (("end_to_end", "itl_p95_ms"), ("per_layer", "decode_step_ms_p50.chat")):
        next(m for m in bench[group] if m["name"] == name)["workloads"].append(cell)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return cell


def _run_serve(root, cell):
    spec = load_cell(cell, root)
    devices = jax.devices()[:1]
    rec, correct, attempted, failed, notes = serve_cell.run_cell(spec, devices, 2**31 + 5, 1.0, 0, time.perf_counter())
    return spec, rec, correct, attempted, failed, notes


def test_a_later_pr_adds_a_model_family_by_files_alone(tmp_path):
    root = make_tiny_root(str(tmp_path / "root"))
    before = _snapshot(root)
    old_bench = load_benchmark(root)
    old_cells = {w["name"]: load_cell(w["name"], root) for w in old_bench["workloads"]}
    with pytest.raises(SpecError):
        family_path("toy", root)

    cell = _add_toy_family(root, "toy", "+1.0")
    spec, rec, correct, attempted, failed, notes = _run_serve(root, cell)
    assert correct and attempted > 0, notes    # (a request shed on a loaded test machine is not this test's matter)
    assert notes["reference"]["tolerance"] == 3e-2 and 0 < notes["reference"]["logits_max_abs_diff_over_max"] < 3e-2
    # its own cache object went through the runner's surface and into the program's scheduler
    (system,) = spec.family().BUILT
    assert {"alloc", "commit_prefill", "advance", "reset", "num_slots", "max_seq_len"} <= system.cache.touched
    # the traffic and the check drew their ids from the vocabulary the family gave, a slice
    assert 64 < system.largest_token < 128
    devices = jax.devices()[:1]
    plain = result_object(spec, rec, devices, correct=correct, attempted=attempted, failed=failed, traced=0)
    assert set(plain["metrics"]) == {"itl_p95_ms", "setup_s"}
    traced = result_object(spec, rec, devices, correct=correct, attempted=attempted, failed=failed, traced=1)
    assert "decode_step_ms_p50.chat" in traced["metrics"]

    # every file that was there is byte-equal; BENCHMARK.json only gained entries and list members
    after = _snapshot(root)
    for path, content in before.items():
        if not path.endswith("BENCHMARK.json"):
            assert after[path] == content, path
    assert sorted(set(after) - set(before)) == sorted(
        os.path.join(root, "benchmark", sub, name) for sub, name in
        (("families", "toy.py"), ("configs", "toy.serve.json"))), "two new files, no more (scratch aside)"
    new_bench = load_benchmark(root)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len(new_bench[group]) >= len(old_bench[group])
        for old, new in zip(old_bench[group], new_bench[group]):
            lists = {k for k in old if isinstance(old[k], list)}
            assert {k: v for k, v in old.items() if k not in lists} == {k: v for k, v in new.items() if k not in lists}
            assert all(new[k][: len(old[k])] == old[k] for k in lists), (group, old["name"])
    # the cells that were there report the same metric sets as before
    for name, old in old_cells.items():
        new = load_cell(name, root)
        assert [m["name"] for m in new.end_to_end] == [m["name"] for m in old.end_to_end]
        assert [m["name"] for m in new.per_layer] == [m["name"] for m in old.per_layer]
        assert new.config == old.config and new.traffic == old.traffic


def test_a_family_whose_reference_is_wrong_reads_not_correct(tmp_path):
    """The same family with one sign turned in its reference: the run itself is
    sound (ledger, no compile in the window), and ``correct`` is false."""
    root = make_tiny_root(str(tmp_path / "root"))
    cell = _add_toy_family(root, "toy_wrong", "-1.0")
    _spec, rec, correct, attempted, failed, notes = _run_serve(root, cell)
    assert not correct
    assert notes["ledger"]["problems"] == [] and notes["compiles_in_window"] == 0
    assert notes["reference"]["logits_max_abs_diff_over_max"] > 1.0
