"""Helpers of the benchmark's tests: a checkout in a temporary directory that
holds the harness's data directories and the toy cells that live with the
tests (never among the benchmark's own cells)."""

import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny")


def make_tiny_root(tmp: str) -> str:
    """tmp/BENCHMARK.json + tmp/benchmark/{configs,traffic} from the toy files,
    with the real readers, families and peaks beside them."""
    bench = os.path.join(tmp, "benchmark")
    os.makedirs(bench)
    shutil.copy(os.path.join(TINY, "BENCHMARK.json"), os.path.join(tmp, "BENCHMARK.json"))
    for sub in ("configs", "traffic"):
        shutil.copytree(os.path.join(TINY, sub), os.path.join(bench, sub))
    for sub in ("layer_metrics", "e2e_metrics", "families"):
        shutil.copytree(os.path.join(REPO, "benchmark", sub), os.path.join(bench, sub),
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "benchmark", "peaks.json"), os.path.join(bench, "peaks.json"))
    return tmp
