"""The static-analysis layer (vescale_tpu/analysis/): findings model,
env registry + generated configuration doc, the shardcheck jaxpr engine,
vescale-lint rules, the structured redistribute decline codes (VSC12x),
the dmodule / step-report / pipeline integration points, and the tier-1
smoke wiring of scripts/shardcheck_smoke.py."""

import os
import re
import subprocess
import sys
import textwrap
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import vescale_tpu as vt
from vescale_tpu import analysis
from vescale_tpu.analysis import (
    CODES,
    Finding,
    FindingReport,
    Severity,
    ShardcheckError,
    check_param_plan,
    check_stage_boundaries,
    check_transition,
    envreg,
    lint_source,
    shardcheck,
)
from vescale_tpu.placements import Partial, RaggedShard, Replicate, Shard
from vescale_tpu.redistribute_plan import (
    Decline,
    clear_plan_cache,
    decline_finding,
    decline_reason,
    plan_redistribute,
)
from vescale_tpu.spec import DArraySpec, TensorMeta

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
AX = {"dp": 2, "tp": 4}


def _spec(mesh, placements, shape, dtype=jnp.float32):
    return DArraySpec(mesh, placements, TensorMeta(tuple(shape), jnp.dtype(dtype)))


@pytest.fixture
def mesh2d():
    return vt.DeviceMesh(("dp", "tp"), (2, 4))


@pytest.fixture
def mesh8():
    return vt.DeviceMesh(("x",), (8,))


@pytest.fixture(autouse=True)
def _fresh_plan_cache():
    clear_plan_cache()
    yield
    clear_plan_cache()


# ================================================================ findings
def test_codes_are_a_closed_stable_vocabulary():
    assert set(CODES) >= {
        "VSC101", "VSC102", "VSC103", "VSC104", "VSC105", "VSC106", "VSC107",
        "VSC108", "VSC120", "VSC121", "VSC122", "VSC123", "VSC124", "VSC125",
        "VSC126", "VSC201", "VSC202", "VSC203", "VSC204", "VSC205",
    }
    for name, c in CODES.items():
        assert c.code == name and c.title
    with pytest.raises(KeyError):
        analysis.code("VSC999")


def test_report_gating_and_serialization():
    rep = FindingReport("t")
    assert rep.ok() and rep.ok(strict=True) and rep.max_severity is None
    rep.add(Finding(CODES["VSC108"], "info only"))
    assert rep.ok(strict=True)  # INFO never fails
    rep.add(Finding(CODES["VSC105"], "warn"))
    assert rep.ok() and not rep.ok(strict=True)
    rep.add(Finding(CODES["VSC101"], "err", mesh_dim="tp", bytes_est=123))
    assert not rep.ok()
    d = rep.to_dict()
    assert d["codes"] == ["VSC101", "VSC105", "VSC108"]
    assert d["max_severity"] == "error"
    assert "VSC101" in rep.format() and rep.by_code("VSC101")[0].bytes_est == 123


def test_finding_severity_override_defaults_to_code():
    f = Finding("VSC101", "x")  # str code accepted
    assert f.code is CODES["VSC101"] and f.severity == Severity.ERROR
    g = Finding(CODES["VSC101"], "x", severity=Severity.WARNING)
    assert g.severity == Severity.WARNING


# ================================================================== envreg
def test_envreg_typed_accessors_are_live(monkeypatch):
    monkeypatch.delenv("VESCALE_REDISTRIBUTE_MAX_HOPS", raising=False)
    assert envreg.get_int("VESCALE_REDISTRIBUTE_MAX_HOPS") == 3  # default
    monkeypatch.setenv("VESCALE_REDISTRIBUTE_MAX_HOPS", "5")
    assert envreg.get_int("VESCALE_REDISTRIBUTE_MAX_HOPS") == 5  # live read
    # malformed values fail LOUDLY: a typo'd knob must not silently revert
    # to the default (e.g. a watchdog deadline of "5s" never arming)
    monkeypatch.setenv("VESCALE_REDISTRIBUTE_MAX_HOPS", "junk")
    with pytest.raises(ValueError, match="VESCALE_REDISTRIBUTE_MAX_HOPS"):
        envreg.get_int("VESCALE_REDISTRIBUTE_MAX_HOPS")
    monkeypatch.setenv("VESCALE_BARRIER_TIMEOUT", "5s")
    with pytest.raises(ValueError, match="expected a float"):
        envreg.get_float("VESCALE_BARRIER_TIMEOUT")


@pytest.mark.parametrize("raw,expected", [
    ("", False), ("0", False), ("false", False), ("OFF", False), ("no", False),
    ("1", True), ("true", True), ("2", True), ("yes", True),
])
def test_envreg_bool_parse_table(monkeypatch, raw, expected):
    monkeypatch.setenv("VESCALE_STRICT_REDISTRIBUTE", raw)
    assert envreg.get_bool("VESCALE_STRICT_REDISTRIBUTE") is expected


def test_envreg_none_defaults_and_unregistered(monkeypatch):
    monkeypatch.delenv("VESCALE_BARRIER_TIMEOUT", raising=False)
    assert envreg.get_float("VESCALE_BARRIER_TIMEOUT") is None
    assert envreg.get_int("VESCALE_NUM_PROCESSES") is None
    with pytest.raises(KeyError, match="not registered"):
        envreg.get_raw("VESCALE_" + "NOT_A_REAL_KNOB")
    with pytest.raises(ValueError, match="conflicting"):
        envreg.register("VESCALE_STRICT_REDISTRIBUTE", "int", 7, "clash")
    # idempotent identical re-registration is fine
    prev = envreg.lookup("VESCALE_STRICT_REDISTRIBUTE")
    envreg.register(prev.name, prev.type, prev.default, prev.doc)


def test_configuration_doc_is_in_sync_with_registry():
    with open(os.path.join(REPO, "docs", "configuration.md"), encoding="utf-8") as f:
        committed = f.read()
    assert committed == envreg.configuration_markdown(), (
        "docs/configuration.md is stale; regenerate with "
        "python -m vescale_tpu.analysis envdoc --write docs/configuration.md"
    )
    for v in envreg.all_vars():
        assert f"`{v.name}`" in committed


def test_no_unregistered_vescale_string_in_package():
    """Every VESCALE_* token appearing in a package STRING LITERAL (the
    form that can reach os.environ — docstrings included) is a registered
    var or a documented prefix of one: the doc table is complete.
    Identifiers (the devicemesh_api singleton, plan-compat enum members)
    are Python symbols, not env knobs, and are out of scope — the same
    semantics vescale-lint's VSC202 enforces."""
    import ast

    pat = re.compile(r"VESCALE_[A-Z0-9_]+")
    offenders = []
    for root, dirs, files in os.walk(os.path.join(REPO, "vescale_tpu")):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for fn in files:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(root, fn)
            with open(path, encoding="utf-8") as f:
                tree = ast.parse(f.read())
            for node in ast.walk(tree):
                if not (isinstance(node, ast.Constant) and isinstance(node.value, str)):
                    continue
                for tok in set(pat.findall(node.value)):
                    if envreg.is_registered(tok):
                        continue
                    if any(v.name.startswith(tok) for v in envreg.all_vars()):
                        continue  # docstring family prefix (VESCALE_IO_BACKOFF_...)
                    if tok == "VESCALE_DEVICE" + "_MESH":  # vescale-lint: disable=VSC202 (API singleton's __all__ entry)
                        continue
                    offenders.append((fn, tok))
    assert not offenders, f"unregistered VESCALE_* tokens: {offenders}"


# ============================================================== shardcheck
def test_shardcheck_flags_materializing_reshape():
    x = jax.ShapeDtypeStruct((64, 512), jnp.float32)
    rep = shardcheck(lambda a: jnp.reshape(a, (64 * 512,)), x,
                     in_specs=[P(None, "tp")], mesh=AX, min_bytes=0,
                     check_source=False)
    f = rep.by_code("VSC101")
    assert f and f[0].mesh_dim == "tp" and f[0].bytes_est == 64 * 512 * 4
    assert f[0].cost_us and f[0].cost_us > 0  # priced by collectives.py
    assert not rep.ok()


def test_shardcheck_clean_program_is_clean():
    x = jax.ShapeDtypeStruct((64, 512), jnp.float32)

    def clean(a):
        return jnp.mean(jnp.tanh(a) * 2.0, axis=1)

    rep = shardcheck(clean, x, in_specs=[P("dp", None)], mesh=AX,
                     min_bytes=0, check_source=False)
    assert rep.ok(strict=True), rep.format()


def test_shardcheck_sharding_preserving_reshape_is_clean():
    # splitting an UNSHARDED dim / keeping the sharded dim leading is free
    x = jax.ShapeDtypeStruct((64, 512), jnp.float32)
    rep = shardcheck(lambda a: jnp.reshape(a, (64, 8, 64)), x,
                     in_specs=[P("dp", None)], mesh=AX, min_bytes=0,
                     check_source=False)
    assert rep.ok(strict=True), rep.format()


def test_shardcheck_flags_concat_along_sharded_dim():
    x = jax.ShapeDtypeStruct((64, 512), jnp.float32)
    rep = shardcheck(lambda a: jnp.concatenate([a, a], axis=1), x,
                     in_specs=[P(None, "tp")], mesh=AX, min_bytes=0,
                     check_source=False)
    assert rep.by_code("VSC101")


def test_shardcheck_flags_elementwise_sharding_conflict():
    x = jax.ShapeDtypeStruct((64, 512), jnp.float32)
    rep = shardcheck(lambda a, b: a + b, x, x,
                     in_specs=[P("dp", None), P("tp", None)], mesh=AX,
                     min_bytes=0, check_source=False)
    assert rep.by_code("VSC102")


def test_shardcheck_partial_consumed_by_nonlinear_op():
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    mesh = vt.DeviceMesh(("dp", "tp"), (2, 4))
    spec = _spec(mesh, [Replicate(), Partial()], (64, 64))
    rep = shardcheck(lambda a: jnp.exp(a), x, in_specs=[spec], mesh=AX,
                     min_bytes=0, check_source=False)
    f = rep.by_code("VSC103")
    assert f and f[0].mesh_dim == "tp"
    # linear consumption of the same Partial is clean
    rep2 = shardcheck(lambda a: (a * 2.0) + a, x, in_specs=[spec], mesh=AX,
                      min_bytes=0, check_source=False)
    assert not rep2.by_code("VSC103"), rep2.format()


def test_shardcheck_dot_general_derived_partial_is_gspmd_business():
    # (B, H) x (H, O): contracting over tp-sharded H DERIVES a partial —
    # inside a jit program GSPMD all-reduces it at the point of use (the
    # expected TP boundary collective), so tanh(x @ y) is NOT a bug.  The
    # whole row-parallel nanogpt/llama forward hinges on this distinction.
    a = jax.ShapeDtypeStruct((8, 512), jnp.float32)
    w = jax.ShapeDtypeStruct((512, 128), jnp.float32)

    def row_parallel(x, y):
        return jnp.tanh(x @ y)

    rep = shardcheck(row_parallel, a, w, in_specs=[P(None, "tp"), P("tp", None)],
                     mesh=AX, min_bytes=0, check_source=False)
    assert rep.ok(strict=True), rep.format()

    # a DECLARED Partial input flowing through the same dot is still the
    # caller's reduction to perform: nonlinear consumption is VSC103
    mesh = vt.DeviceMesh(("dp", "tp"), (2, 4))
    pspec = _spec(mesh, [Replicate(), Partial()], (8, 512))

    def bad(x, y):
        return jnp.tanh(x @ y)

    rep2 = shardcheck(bad, a, w, in_specs=[pspec, P()], mesh=AX,
                      min_bytes=0, check_source=False)
    assert rep2.by_code("VSC103"), rep2.format()


def test_shardcheck_donation_miss():
    params = jnp.zeros((1024, 512), jnp.float32)  # 2 MiB > threshold
    grads = jnp.zeros((1024, 512), jnp.bfloat16)  # dtype-distinct from output

    def step(p, g):
        return p - 0.1 * g.astype(p.dtype), jnp.sum(g)

    rep = shardcheck(step, params, grads, check_source=False)
    f = rep.by_code("VSC105")
    assert f and f[0].severity == Severity.WARNING
    rep2 = shardcheck(step, params, grads, donate_argnums=(0,),
                      check_source=False)
    assert not rep2.by_code("VSC105")


def test_shardcheck_recurses_into_scan():
    x = jax.ShapeDtypeStruct((64, 512), jnp.float32)

    def loop(a):
        def body(carry, _):
            return jnp.reshape(jnp.reshape(carry, (64 * 512,)), (64, 512)), ()

        out, _ = jax.lax.scan(body, a, jnp.arange(3))
        return out

    rep = shardcheck(loop, x, in_specs=[P(None, "tp")], mesh=AX,
                     min_bytes=0, check_source=False)
    assert rep.by_code("VSC101")


def test_shardcheck_reads_sharding_constraints():
    # a mid-program with_sharding_constraint introduces the sharding; the
    # downstream flatten then materializes it
    mesh = vt.DeviceMesh(("dp", "tp"), (2, 4))
    from jax.sharding import NamedSharding

    def f(a):
        a = jax.lax.with_sharding_constraint(
            a, NamedSharding(mesh.jax_mesh, P(None, "tp"))
        )
        return jnp.reshape(a, (64 * 512,))

    x = jax.ShapeDtypeStruct((64, 512), jnp.float32)
    rep = shardcheck(f, x, mesh=AX, min_bytes=0, check_source=False)
    assert rep.by_code("VSC101")


def test_shardcheck_rank_divergent_collective_in_source(tmp_path):
    # the divergent program lives in a throwaway module (NOT this file —
    # the repo-wide lint gate must stay green) so inspect.getsource works
    mod_path = tmp_path / "divergent_mod.py"
    mod_path.write_text(textwrap.dedent("""
        rank = 0

        def barrier():
            pass

        def program(a):
            if rank == 0:
                barrier()
            return a + 1
    """))
    import importlib.util

    spec_ = importlib.util.spec_from_file_location("divergent_mod", mod_path)
    m = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(m)
    rep = shardcheck(m.program, jnp.ones((4,)), check_source=True)
    assert rep.by_code("VSC104")


def test_shardcheck_untraceable_degrades_to_info():
    rep = shardcheck(lambda a: a.no_such_attr, jnp.ones((4,)),
                     check_source=False)
    assert rep.codes() == ["VSC109"] and rep.ok()


def test_shardcheck_static_argnums_are_honored():
    # a flag branch that would crash tracing as a tracer; and the sharded
    # reshape behind it is still analyzed when the flag is static
    x = jax.ShapeDtypeStruct((64, 512), jnp.float32)

    def f(a, flatten):
        if flatten:
            return jnp.reshape(a, (64 * 512,))
        return a

    rep = shardcheck(f, x, True, static_argnums=(1,),
                     in_specs=[P(None, "tp")], mesh=AX, min_bytes=0,
                     check_source=False)
    assert rep.by_code("VSC101"), rep.format()
    assert not rep.by_code("VSC109")


# ================================================== decline codes (VSC12x)
def test_decline_budget_emits_vsc120(mesh8):
    src = _spec(mesh8, [RaggedShard((0,), (1, 2, 1, 2, 1, 3, 3, 3))], (64,))
    dst = _spec(mesh8, [Shard(0)], (64,))
    assert plan_redistribute(src, dst) is None
    d = decline_finding(src, dst)
    assert isinstance(d, Decline) and d.code == "VSC120"
    assert "[VSC120]" in decline_reason(src, dst)
    assert "memory budget" in d.message


def test_decline_hop_bound_emits_vsc121(mesh2d, monkeypatch):
    monkeypatch.setenv("VESCALE_REDISTRIBUTE_MAX_HOPS", "0")
    src = _spec(mesh2d, [Shard(0), Shard(1)], (8, 8))
    dst = _spec(mesh2d, [Shard(1), Shard(0)], (8, 8))
    assert plan_redistribute(src, dst) is None
    assert decline_finding(src, dst).code == "VSC121"
    assert "0 hops" in decline_finding(src, dst).message


def test_decline_cross_mesh_no_bridge_emits_vsc122(mesh8):
    other = vt.DeviceMesh(("y",), (8,))
    src = _spec(mesh8, [RaggedShard((0,), (1, 1, 1, 1, 1, 1, 1, 1))], (64,))
    dst = _spec(other, [Shard(0)], (64,))
    assert plan_redistribute(src, dst) is None
    assert decline_finding(src, dst).code == "VSC122"


def test_decline_cross_mesh_budget_emits_vsc123(mesh8):
    # padded Shard on both sides: the only unpadded bridge is Replicate,
    # logical-size vs a 1/8 shard — over the 4x budget
    other = vt.DeviceMesh(("y",), (8,))
    src = _spec(mesh8, [Shard(0)], (10,))
    dst = _spec(other, [Shard(0)], (10,))
    assert plan_redistribute(src, dst) is None
    assert decline_finding(src, dst).code == "VSC123"


def test_decline_cross_mesh_strip_and_dress_emit_vsc124_125(mesh8, monkeypatch):
    import vescale_tpu.redistribute_plan as rp

    other = vt.DeviceMesh(("y",), (8,))
    monkeypatch.setattr(
        rp, "_search_same_mesh",
        lambda s, d: (None, Decline("VSC121", "synthetic decline")),
    )
    # src needs stripping (Partial -> Replicate bridge): source side fails
    src = _spec(mesh8, [Partial()], (64,))
    dst = _spec(other, [Replicate()], (64,))
    plan, reason = rp._plan_cross_mesh(src, dst)
    assert plan is None and reason.code == "VSC124"
    # src already plain; dst needs dressing: destination side fails
    src2 = _spec(mesh8, [Replicate()], (64,))
    dst2 = _spec(other, [Partial()], (64,))
    plan, reason = rp._plan_cross_mesh(src2, dst2)
    assert plan is None and reason.code == "VSC125"


def test_decline_not_consulted_emits_vsc126(mesh8):
    src = _spec(mesh8, [Shard(0)], (1024,))
    assert decline_finding(src, _spec(mesh8, [Replicate()], (1024,))).code == "VSC126"


def test_warn_fallback_message_carries_the_code(mesh8):
    src = _spec(mesh8, [RaggedShard((0,), (1, 2, 1, 2, 1, 3, 3, 3))], (64,))
    dst = _spec(mesh8, [Shard(0)], (64,))
    x = np.arange(64, dtype=np.float32)
    d = vt.from_local(
        [x[o:o + s] for s, o in zip(*src.placements[0].local_sizes_and_offsets(64))],
        mesh8, src.placements, shape=(64,),
    )
    import importlib

    rd = importlib.import_module("vescale_tpu.redistribute")
    rd._warned_pairs.clear()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        d.redistribute(placements=[Shard(0)])
    msgs = [str(ww.message) for ww in w if "materialize the LOGICAL" in str(ww.message)]
    assert msgs and "[VSC120]" in msgs[0]


# ===================================================== transition findings
def test_check_transition_fallback_yields_vsc106_with_decline(mesh8):
    src = _spec(mesh8, [RaggedShard((0,), (1, 2, 1, 2, 1, 3, 3, 3))], (64,))
    dst = _spec(mesh8, [Shard(0)], (64,))
    findings = check_transition(src, dst)
    codes = {f.code.code for f in findings}
    assert codes == {"VSC106", "VSC120"}
    assert "[VSC120]" in findings[0].message


def test_check_transition_planned_yields_costed_info(mesh2d):
    from vescale_tpu.placements import InterleavedShard

    src = _spec(mesh2d, [InterleavedShard(0, 2), InterleavedShard(1, 2)], (8, 8))
    dst = _spec(mesh2d, [Replicate(), Shard(1)], (8, 8))
    findings = check_transition(src, dst)
    assert [f.code.code for f in findings] == ["VSC108"]
    assert findings[0].severity == Severity.INFO and findings[0].bytes_est >= 0
    assert check_transition(src, src) == []


def test_check_stage_boundaries(mesh8):
    good = _spec(mesh8, [Shard(0)], (64,))
    bad_out = _spec(mesh8, [RaggedShard((0,), (1, 2, 1, 2, 1, 3, 3, 3))], (64,))
    rep = check_stage_boundaries([good, bad_out], [good, good],
                                 labels=["b0", "b1"])
    assert not rep.by_code("VSC106") or all(
        f.where != "b0" for f in rep.by_code("VSC106")
    )
    assert any(f.where == "b1" for f in rep.by_code("VSC106"))


# ============================================================ lint rules
def _lint(src):
    return lint_source(textwrap.dedent(src), "snippet.py")


def test_lint_flags_direct_env_reads_not_writes():
    f = _lint("""
        import os
        a = os.environ.get("VESCALE_KERNELS")
        b = os.getenv("VESCALE_KERNELS")
        c = os.environ["VESCALE_KERNELS"]
        d = "VESCALE_KERNELS" in os.environ
        os.environ["VESCALE_KERNELS"] = "1"          # write: fine
        os.environ.setdefault("VESCALE_KERNELS", "") # write: fine
        del os.environ["VESCALE_KERNELS"]            # write: fine
    """)
    assert len([x for x in f if x.code.code == "VSC201"]) == 4


def test_lint_flags_unregistered_names_and_suppression():
    bogus = "VESCALE_" + "TOTALLY_BOGUS"
    f = _lint(f'x = "{bogus}"\n')
    assert [x.code.code for x in f] == ["VSC202"]
    f2 = _lint(f'x = "{bogus}"  # vescale-lint: disable=VSC202\n')
    assert f2 == []
    f3 = _lint(f'x = "{bogus}"  # vescale-lint: disable=all\n')
    assert f3 == []
    assert _lint('x = "VESCALE_KERNELS"\n') == []  # registered
    assert _lint('y = "VESCALE_IO_BACKOFF_"\n') == []  # family prefix


def test_lint_hook_slots_must_not_be_lambdas():
    bad = _lint("""
        def _noop(x):
            return x
        tag_array = _noop
        def activate():
            global tag_array
            tag_array = lambda x: x
    """)
    assert [x.code.code for x in bad] == ["VSC203"]
    assert _lint("my_hook = lambda: None\n")[0].code.code == "VSC203"
    assert _lint("not_a_slot = lambda: None\n") == []


def test_lint_signal_handler_safety():
    bad = _lint("""
        import signal, threading
        lock = threading.Lock()
        def _on_signal(signum, frame):
            lock.acquire()
        signal.signal(signal.SIGTERM, _on_signal)
    """)
    assert [x.code.code for x in bad] == ["VSC204"]
    good = _lint("""
        import signal
        def _on_signal(signum, frame):
            flag.set()
        signal.signal(signal.SIGTERM, _on_signal)
    """)
    assert good == []


def test_lint_bare_except_in_retry_loop():
    bad = _lint("""
        while True:
            try:
                step()
            except:
                pass
    """)
    assert [x.code.code for x in bad] == ["VSC205"]
    reraises = _lint("""
        while True:
            try:
                step()
            except:
                raise
    """)
    assert reraises == []
    transports = _lint("""
        while True:
            try:
                step()
            except BaseException as e:
                box = e
    """)
    assert transports == []
    outside_loop = _lint("""
        try:
            step()
        except:
            pass
    """)
    assert outside_loop == []


def test_lint_rank_divergent_collective():
    bad = _lint("""
        def f(rank):
            if rank == 0:
                barrier()
    """)
    assert [x.code.code for x in bad] == ["VSC104"]
    good = _lint("""
        def f(rank, loss):
            if loss > 0:
                barrier()
            if rank == 0:
                print("hello")
    """)
    assert good == []


def test_lint_repo_is_green():
    from vescale_tpu.analysis.lint import lint_paths

    rep = lint_paths([
        os.path.join(REPO, "vescale_tpu"),
        os.path.join(REPO, "scripts"),
        os.path.join(REPO, "chip_smoke.py"),
        os.path.join(REPO, "__graft_entry__.py"),
        os.path.join(REPO, "examples"),
    ])
    assert rep.ok(strict=True), rep.format()


# ====================================================== integration points
def test_dmodule_rejects_partial_param_plan_in_strict(mesh2d, monkeypatch):
    import flax.linen as nn

    from vescale_tpu.dmodule import parallelize_module

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(4)(x)

    monkeypatch.setenv("VESCALE_SHARDCHECK", "strict")
    with pytest.raises(ShardcheckError, match="VSC107"):
        parallelize_module(Tiny(), mesh2d, {"parameter": {r".*": [Partial()]}})

    monkeypatch.setenv("VESCALE_SHARDCHECK", "warn")
    with pytest.warns(UserWarning, match="VSC107"):
        parallelize_module(Tiny(), mesh2d, {"parameter": {r".*": [Partial()]}})

    monkeypatch.setenv("VESCALE_SHARDCHECK", "off")
    parallelize_module(Tiny(), mesh2d, {"parameter": {r".*": [Partial()]}})

    # a clean plan stays silent in every mode
    monkeypatch.setenv("VESCALE_SHARDCHECK", "strict")
    parallelize_module(Tiny(), mesh2d, {"parameter": {r".*": [Replicate()]}})


def test_step_report_carries_shardcheck_section(monkeypatch):
    from vescale_tpu.telemetry.step_report import build_step_report

    def f(a):
        return (a * 2).sum()

    monkeypatch.setenv("VESCALE_SHARDCHECK", "warn")
    rep = build_step_report(f, jnp.ones((8, 8)), name="t")
    assert rep["shardcheck"]["name"] == "t"
    assert rep["shardcheck"]["n_findings"] == 0

    monkeypatch.setenv("VESCALE_SHARDCHECK", "off")
    rep2 = build_step_report(f, jnp.ones((8, 8)), name="t")
    assert "shardcheck" not in rep2

    # donation forwarding: unknown (default None) never flags VSC105; an
    # explicit donate_argnums=() on a buffer-rebuilding step does
    monkeypatch.setenv("VESCALE_SHARDCHECK", "warn")
    big = jnp.zeros((1024, 512), jnp.float32)
    step = jax.jit(lambda p: p * 0.5, donate_argnums=(0,))
    repd = build_step_report(step, big, name="donated")
    assert "VSC105" not in repd["shardcheck"]["codes"]
    repn = build_step_report(step, big, name="undonated", donate_argnums=())
    assert "VSC105" in repn["shardcheck"]["codes"]


def test_pipeline_plan_boundary_report(mesh8):
    from vescale_tpu.plan import PipelineParallelPlan

    plan = PipelineParallelPlan(
        num_stages=2,
        stage_out_placements=[[RaggedShard((0,), (1, 2, 1, 2, 1, 3, 3, 3))]],
        stage_in_placements=[[Shard(0)]],
    )
    rep = plan.boundary_report(mesh8, (64,))
    assert rep.by_code("VSC106")
    good = PipelineParallelPlan(
        num_stages=2,
        stage_out_placements=[[Shard(0)]],
        stage_in_placements=[[Shard(0)]],
    )
    assert good.boundary_report(mesh8, (64,)).ok(strict=True)
    with pytest.raises(ValueError, match="declared together"):
        PipelineParallelPlan(num_stages=2, stage_out_placements=[[Shard(0)]])


def test_param_plan_check(mesh2d):
    rep = check_param_plan({r"dense.*": [Shard(0)]}, mesh2d)
    assert rep.ok(strict=True)
    rep2 = check_param_plan({r"dense.*": [Partial()]}, mesh2d)
    f = rep2.by_code("VSC107")
    assert f and f[0].mesh_dim == "dp"


def test_analysis_mode_helpers(monkeypatch):
    monkeypatch.delenv("VESCALE_SHARDCHECK", raising=False)
    assert analysis.mode() == "warn" and analysis.enabled()
    monkeypatch.setenv("VESCALE_SHARDCHECK", "strict")
    assert analysis.is_strict()
    monkeypatch.setenv("VESCALE_SHARDCHECK", "off")
    assert not analysis.enabled()
    monkeypatch.setenv("VESCALE_SHARDCHECK", "bogus")
    assert analysis.mode() == "warn"


# ------------------------------------------------------------- smoke (CI)
def test_shardcheck_smoke_script():
    """tier-1 wiring of scripts/shardcheck_smoke.py (the acceptance run)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "shardcheck_smoke.py")],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, f"smoke failed:\n{proc.stdout}\n{proc.stderr}"
    assert "[smoke] PASS" in proc.stdout
