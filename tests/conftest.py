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
def traces_and_compiles():
    """``with traces_and_compiles() as seen:`` counts, by jax's own monitoring events, the jaxprs traced and the
    programs compiled inside the block: ``seen == {"traced": 0, "compiled": 0}`` is "nothing was built anew"."""
    import contextlib

    from jax._src import monitoring

    events = {"/jax/core/compile/jaxpr_trace_duration": "traced", "/jax/core/compile/backend_compile_duration": "compiled"}

    @contextlib.contextmanager
    def counting():
        seen = {"traced": 0, "compiled": 0}

        def on_event(name, _seconds, **_kw):
            if name in events:
                seen[events[name]] += 1

        monitoring.register_event_duration_secs_listener(on_event)
        try:
            yield seen
        finally:
            monitoring.unregister_event_duration_listener(on_event)

    return counting


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


# ------------------------------------------------- compiling for the chip, without one (tests/test_tpu_compile*.py)
@pytest.fixture(scope="module")
def chip():
    """Sharding on one described v5e device, with the persistent compile
    cache off around the module (an entry written for a described chip
    cannot be read back without one, and warns)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or another process holds it
        pytest.skip(f"cannot describe a v5e topology: {str(e)[:200]}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


class _JaxOnATpu:
    """``jax`` as ``ops/flash_attention.py`` sees it, but for the platform of ``jax.devices()[0]``."""

    def __getattr__(self, name):
        return getattr(jax, name)

    def devices(self, *_args):
        import types

        return [types.SimpleNamespace(platform="tpu")]


class _CellsPrograms:
    """``cells_programs(cell)``: a serve cell's programs from shapes alone, as ``benchmark/rehearse.py`` lowers them:
    ``(family, config, sizes, [(title, lowered)], engine)``, the engine being the one the cell's family built for them.
    Built ONCE a module for a cell and a value of ``VESCALE_KERNELS``: a build lowers EVERY rung of the cell's ladder
    (4-13 s the first time in a process, less once jax has traced the kernels), and a family's cases each compile one
    program of the same build."""

    def __init__(self, chip):
        self.chip, self.built = chip, {}

    def on_a_tpu(self):
        """The program asks ``jax.devices()`` for its platform and would take its CPU legs here, so this answers for
        it while a program is traced."""
        import contextlib
        import importlib
        from unittest import mock

        from vescale_tpu import kernels

        flash_ops = importlib.import_module("vescale_tpu.ops.flash_attention")    # (``ops`` exports the function under this name)
        stack = contextlib.ExitStack()
        stack.enter_context(mock.patch.object(kernels, "on_tpu", lambda: True))
        stack.enter_context(mock.patch.object(flash_ops, "jax", _JaxOnATpu()))
        return stack

    def __call__(self, cell):
        from vescale_tpu.analysis import envreg

        key = (cell, envreg.get_raw("VESCALE_KERNELS"))
        if key not in self.built:
            from unittest import mock

            from benchmark.spec import load_cell
            from vescale_tpu.serve import HybridServeEngine, ServeEngine

            engines, stack = [], self.on_a_tpu()
            for cls in (ServeEngine, HybridServeEngine):
                def noted(self, *args, _init=cls.__init__, **kwargs):
                    _init(self, *args, **kwargs)
                    engines.append(self)

                stack.enter_context(mock.patch.object(cls, "__init__", noted))
            spec = load_cell(cell)
            family, config = spec.family(), spec.config
            (device,) = self.chip.device_set
            with stack:
                sizes, programs = family.rehearse_serve(spec.name, config, config["serve"], [device])
            (engine,) = engines
            self.built[key] = (family, config, sizes, programs, engine)
        return self.built[key]

    @staticmethod
    def assert_in_place_and_fits(compiled, sizes, pool):
        """The pools are written in place: no copy of one (``pool``, its shape as
        the compiled text writes it) to another layout and back around a scatter
        over the page axis, the cache's bytes aliased, and 16 GB of HBM hold the
        arguments (weights, pools, state) and the program's temporaries, with room
        for the logits."""
        assert not [line for line in compiled.as_text().splitlines() if " copy(" in line and f"= {pool}" in line]
        memory = compiled.memory_analysis()
        cache_bytes = sizes["kv_pool_bytes"] + sizes["slot_state_bytes"]
        assert memory.argument_size_in_bytes >= sum(sizes.values()) and memory.alias_size_in_bytes >= cache_bytes, memory
        assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.0e9 and memory.temp_size_in_bytes < 0.5e9, memory


@pytest.fixture(scope="module")
def cells_programs(chip):
    return _CellsPrograms(chip)


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
