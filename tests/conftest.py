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
LATER_CELLS = {"phi4miniflash_serve_reasoning": "tiny_batch"}
LATER_TRAFFIC_FILES = {"reasoning2k_closed120"}


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
