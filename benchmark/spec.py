"""What a cell is made of, read from data: ``BENCHMARK.json`` names the cell,
its configuration file and its traffic mix; the files hold the sizes.  The
harness finds everything by name, so a later PR adds a configuration, a mix,
a cell or a metric by adding files and entries, never by editing one.
"""

from __future__ import annotations

import dataclasses
import json
import os
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
    if config.get("kind") not in ("train", "serve"):
        raise SpecError(f"configuration {cell['config']!r}: kind must be 'train' or 'serve'")
    traffic = _load_json(os.path.join(root, "benchmark", "traffic", cell["traffic"] + ".json"))
    return CellSpec(
        root=root, name=workload, chips=int(cell["chips"]),
        config_name=cell["config"], config=config,
        traffic_name=cell["traffic"], traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, workload)],
    )


def llama_config(config: Dict[str, Any], *, max_positions: int, use_flash_attention: bool = True):
    """The program's ``LlamaConfig`` from a configuration file whose ``model``
    is ``llama``: the published keys go through unchanged.  ``max_positions``
    is the longest sequence this cell runs (the program sizes nothing else by
    it: rotary phases are computed from positions)."""
    import jax.numpy as jnp

    from vescale_tpu.models.llama import LlamaConfig

    if config.get("model") != "llama":
        raise SpecError(f"model {config.get('model')!r}: this harness builds 'llama' configurations")
    if config.get("sliding_window") is not None:
        raise SpecError("models/llama.py has no sliding-window attention")
    if config["hidden_size"] != config["num_attention_heads"] * config["head_dim"]:
        raise SpecError("LlamaConfig derives head_dim as hidden_size / num_attention_heads")
    return LlamaConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"], num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"], num_key_value_heads=config["num_key_value_heads"],
        max_position_embeddings=max_positions, rms_norm_eps=config["rms_norm_eps"],
        rope_theta=config["rope_theta"], tie_word_embeddings=config["tie_word_embeddings"],
        use_flash_attention=use_flash_attention, dtype=jnp.bfloat16,
    )


def device_peaks(device_kind: str, root: str = ROOT) -> Dict[str, float]:
    """The benchmark's own table of peaks; a device it lacks is an error."""
    table = _load_json(os.path.join(root, "benchmark", "peaks.json"))
    if device_kind not in table:
        raise SpecError(f"no peaks for device kind {device_kind!r} in benchmark/peaks.json (has: {sorted(table)})")
    return table[device_kind]
