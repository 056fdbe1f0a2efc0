"""What a cell is made of, read from data: ``BENCHMARK.json`` names the cell,
its configuration file and its traffic mix; the files hold the sizes.  The
harness finds everything by name, so a later PR adds a configuration, a mix,
a cell, a metric or a model family by adding files and entries, never by
editing one.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from typing import Any, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class SpecError(ValueError):
    """``BENCHMARK.json`` or a file it names is missing or malformed."""


def _load_json(path: str) -> Any:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise SpecError(f"cannot read {path}: {e}") from e


@dataclasses.dataclass(frozen=True)
class CellSpec:
    """One entry of ``workloads`` with the files it names, loaded."""

    root: str                  # the checkout: where BENCHMARK.json lies
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]     # the configuration file's object
    traffic_name: str
    traffic: Dict[str, Any]    # the traffic file's object
    end_to_end: List[Dict[str, Any]]   # BENCHMARK.json entries this cell reports
    per_layer: List[Dict[str, Any]]

    @property
    def kind(self) -> str:
        return self.config["kind"]

    def family(self):
        """The module of this configuration's ``model`` (``load_family``)."""
        return load_family(self.config["model"], self.root)

    def out_dir(self) -> str:
        """Scratch inside the checkout (token file, traces); in .gitignore."""
        return os.path.join(self.root, "benchmark_out")


def _reports(metric: Dict[str, Any], workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def load_cell(workload: str, root: str = ROOT) -> CellSpec:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json (has: {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if cell["config"] not in configs:
        raise SpecError(f"workload {workload!r} names configuration {cell['config']!r}, which configs lacks")
    config = _load_json(os.path.join(root, configs[cell["config"]]["file"]))
    check_config(config, configs[cell["config"]], root)
    traffic = _load_json(os.path.join(root, "benchmark", "traffic", cell["traffic"] + ".json"))
    return CellSpec(
        root=root, name=workload, chips=int(cell["chips"]),
        config_name=cell["config"], config=config,
        traffic_name=cell["traffic"], traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, workload)],
    )


def check_config(config: Dict[str, Any], declared: Dict[str, Any], root: str = ROOT) -> None:
    """What a configuration file must hold whatever its family: the keys the
    harness itself reads, a family file for its ``model``, and the cut from
    its source written down (README, "A configuration")."""
    name = declared["name"]
    if config.get("kind") not in ("train", "serve"):
        raise SpecError(f"configuration {name!r}: kind must be 'train' or 'serve'")
    if not isinstance(config.get(config["kind"]), dict):
        raise SpecError(f"configuration {name!r}: kind is {config['kind']!r}, so the file needs a {config['kind']!r} block")
    family_path(config.get("model"), root)
    for key in ("source", "reduced"):
        if config.get(key) != declared[key]:
            raise SpecError(f"configuration {name!r}: {key} is {config.get(key)!r} in its file and {declared[key]!r} "
                            "in BENCHMARK.json")
    if not config.get("deployment"):
        raise SpecError(f"configuration {name!r}: the file states no deployment")
    for key in config["reduced"]:
        if key not in config or key not in config.get("published", {}):
            raise SpecError(f"configuration {name!r}: {key!r} is reduced, so the file holds its value here and its "
                            "source's value under published")
    share = config.get("share")
    if share is not None:
        # a chip's share of a layer (README, "A chip's share"): over how many chips, and which counts are this chip's
        if not (isinstance(share.get("chips"), int) and share["chips"] >= 2 and share.get("of")):
            raise SpecError(f"configuration {name!r}: share needs chips (2 or more) and of (the keys that are a share)")
        for key in share["of"]:
            if key not in config["reduced"]:
                raise SpecError(f"configuration {name!r}: {key!r} is this chip's share, so reduced lists it")


# ------------------------------------------------------------ model families
def family_names(root: str = ROOT) -> List[str]:
    """The families of a checkout: the files of ``benchmark/families/``, by
    listing the directory, as metric readers are found."""
    directory = os.path.join(root, "benchmark", "families")
    return sorted(f[:-3] for f in (os.listdir(directory) if os.path.isdir(directory) else ())
                  if f.endswith(".py") and not f.startswith("_"))


def family_path(model: Any, root: str = ROOT) -> str:
    directory = os.path.join(root, "benchmark", "families")
    if not isinstance(model, str) or model not in family_names(root):
        raise SpecError(f"model {model!r} has no file under {directory} (has: {family_names(root)}): a family is "
                        "benchmark/families/<model>.py (README, \"Adding a family\")")
    return os.path.join(directory, model + ".py")


def load_family(model: str, root: str = ROOT):
    """The family's module.  One module object a file and process, so that its
    jitted reference compiles once."""
    path = family_path(model, root)
    name = f"benchmark_family_{model}"
    module = sys.modules.get(name)
    if module is None or os.path.abspath(getattr(module, "__file__", "")) != os.path.abspath(path):
        spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        sys.modules[name] = module
    return module


def device_peaks(device_kind: str, root: str = ROOT) -> Dict[str, float]:
    """The benchmark's own table of peaks; a device it lacks is an error."""
    table = _load_json(os.path.join(root, "benchmark", "peaks.json"))
    if device_kind not in table:
        raise SpecError(f"no peaks for device kind {device_kind!r} in benchmark/peaks.json (has: {sorted(table)})")
    return table[device_kind]
