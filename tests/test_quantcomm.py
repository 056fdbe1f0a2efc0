"""Quantized gradient collectives (ROADMAP item 2): the block-scaled int8
quantizer (quant/blockscale.py property tests), the quantized collectives
(collectives.all_reduce_q / reduce_scatter_q / q_psum), the emulator's
bit-for-bit quantized replay, the redistribution planner's gated
quantize->move->dequantize hop (VSC127/VSC128), the DDP / DistributedOptimizer
grad_compress knobs, CommDebugMode's int8 attribution, and the tier-1
wiring of scripts/quantcomm_smoke.py."""

import os
import subprocess
import sys
import warnings

import numpy as np
import pytest
import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

import vescale_tpu as vt
from vescale_tpu.collectives import (
    all_reduce_q,
    mesh_all_reduce,
    mesh_reduce_scatter,
    q_psum,
    reduce_scatter_q,
    shard_map,
)
from vescale_tpu.mesh import DeviceMesh
from vescale_tpu.placements import Partial, Replicate, Shard
from vescale_tpu.quant import blockscale
from vescale_tpu.spec import DArraySpec, TensorMeta

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ===================================================== quantizer properties
class TestBlockQuantizer:
    def _roundtrip_err(self, x, block=64, **kw):
        qb = blockscale.quantize_int8_blocks(jnp.asarray(x), block, **kw)
        deq = blockscale.dequantize_int8_blocks(qb, x.shape, x.dtype)
        return np.asarray(deq) - np.asarray(x), qb

    def test_roundtrip_bound_normal(self):
        x = (np.random.default_rng(0).normal(size=4096) * 10).astype(np.float32)
        err, _ = self._roundtrip_err(x)
        amax = np.abs(x.reshape(-1, 64)).max(1)
        bound = np.repeat(amax / 127.0, 64)  # pow2 scale <= 2 * amax/254
        assert (np.abs(err) <= bound + 1e-12).all()

    def test_all_zero_blocks_exact(self):
        x = np.zeros(256, np.float32)
        err, qb = self._roundtrip_err(x)
        assert np.array_equal(err, np.zeros_like(err))
        assert np.array_equal(np.asarray(qb.q), np.zeros_like(np.asarray(qb.q)))

    def test_denormal_blocks(self):
        """Subnormal inputs: the scale clamps at the smallest normal power
        of two; round-trip stays within the per-block bound and finite."""
        x = (np.random.default_rng(1).normal(size=256) * 1e-41).astype(np.float32)
        err, qb = self._roundtrip_err(x)
        assert np.isfinite(np.asarray(qb.scales)).all()
        amax = np.abs(x.reshape(-1, 64)).max(1)
        scales = np.asarray(qb.scales)
        assert (np.abs(err) <= np.repeat(scales, 64) / 2 + 1e-45).all()
        assert (scales >= amax / 127.0 - 1e-45).all()

    def test_mixed_sign_outliers(self):
        """One huge outlier only costs ITS block's precision."""
        x = np.random.default_rng(2).normal(size=512).astype(np.float32)
        x[5] = 1e4
        x[300] = -3.0
        err, _ = self._roundtrip_err(x)
        # outlier block: bound scales with the outlier
        assert np.abs(err[:64]).max() <= 1e4 / 127.0
        # other blocks unaffected by the distant outlier
        clean_amax = np.abs(x[64:].reshape(-1, 64)).max(1)
        assert (np.abs(err[64:]) <= np.repeat(clean_amax / 127.0, 64) + 1e-12).all()

    def test_nonfinite_contract_pass_through(self):
        """Documented contract: a non-finite element poisons its WHOLE
        block to non-finite on dequantize (so found_inf still fires);
        other blocks are untouched."""
        x = np.ones(192, np.float32)
        x[10] = np.nan
        x[70] = np.inf
        qb = blockscale.quantize_int8_blocks(jnp.asarray(x), 64)
        deq = np.asarray(blockscale.dequantize_int8_blocks(qb, x.shape, x.dtype))
        assert not np.isfinite(deq[:64]).any()
        assert not np.isfinite(deq[64:128]).any()
        assert np.isfinite(deq[128:]).all()

    def test_nonfinite_validate_raises(self):
        x = jnp.asarray([1.0, np.nan, 2.0], jnp.float32)
        with pytest.raises(ValueError, match="non-finite"):
            blockscale.quantize_int8_blocks(x, 64, validate=True)
        # finite input passes with validate on
        blockscale.quantize_int8_blocks(jnp.ones(8), 64, validate=True)

    def test_stochastic_rounding_unbiased_and_replayable(self):
        """E[deq] ~= x over many seeded draws, and the same key reproduces
        the same codes exactly."""
        val = 0.3  # deliberately between two code points for most scales
        x = jnp.full((4096,), val, jnp.float32)
        k = jax.random.key(7)
        qb1 = blockscale.quantize_int8_blocks(x, 64, "stochastic", k)
        qb2 = blockscale.quantize_int8_blocks(x, 64, "stochastic", k)
        assert np.array_equal(np.asarray(qb1.q), np.asarray(qb2.q))
        deq = np.asarray(blockscale.dequantize_int8_blocks(qb1, x.shape, x.dtype))
        scale = float(np.asarray(qb1.scales)[0])
        # mean within 4 standard errors of the rounding noise
        se = scale / np.sqrt(12 * x.size)
        assert abs(float(deq.mean()) - val) < 4 * se, (deq.mean(), val, se)

    def test_stochastic_requires_key(self):
        with pytest.raises(ValueError, match="key"):
            blockscale.quantize_int8_blocks(jnp.ones(8), 64, "stochastic")
        with pytest.raises(ValueError, match="rounding"):
            blockscale.quantize_int8_blocks(jnp.ones(8), 64, "floor")

    def test_pack_unpack_roundtrip_e8m0(self):
        x = (np.random.default_rng(3).normal(size=300) * 5).astype(np.float32)
        qb = blockscale.quantize_int8_blocks(jnp.asarray(x), 64)
        buf = blockscale.pack_int8_payload(qb)
        assert buf.dtype == jnp.int8
        nb = qb.q.shape[0]
        assert buf.size == blockscale.packed_nbytes(300, 64) == nb * 64 + nb
        qb2 = blockscale.unpack_int8_payload(buf, nb, 64)
        assert np.array_equal(np.asarray(qb.q), np.asarray(qb2.q))
        assert np.array_equal(np.asarray(qb.scales), np.asarray(qb2.scales))

    def test_scales_are_powers_of_two(self):
        x = (np.random.default_rng(4).normal(size=1024) * 100).astype(np.float32)
        qb = blockscale.quantize_int8_blocks(jnp.asarray(x), 64)
        s = np.asarray(qb.scales)
        assert (np.log2(s) == np.round(np.log2(s))).all()

    def test_fp8_consumes_shared_helpers(self):
        """Satellite: fp8 and int8 share ONE scaling implementation."""
        from vescale_tpu.quant import fp8

        assert fp8._quantize is blockscale.quantize_clip
        amax = jnp.asarray(3.0)
        assert float(blockscale.scale_from_amax(amax, fp8.E4M3_MAX)) == float(
            np.float32(fp8.E4M3_MAX) / np.float32(3.0)
        )
        assert float(blockscale.scale_from_amax(jnp.asarray(0.0), 448.0)) == 1.0


# ===================================================== quantized collectives
class TestQuantizedCollectives:
    def test_all_reduce_q_matches_exact_within_bound(self, mesh1d):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(8, 256, 33)).astype(np.float32))
        exact = np.asarray(mesh_all_reduce(x, mesh1d))
        quant = np.asarray(all_reduce_q(x, mesh1d))
        # per element: at most world * per-rank block step
        bound = 8 * float(np.abs(np.asarray(x)).max()) / 127.0
        err = np.abs(quant - exact).max()
        assert 0 < err <= bound
        # deterministic: bitwise identical on repeat
        assert np.array_equal(quant, np.asarray(all_reduce_q(x, mesh1d)))

    def test_all_reduce_q_avg(self, mesh1d):
        x = jnp.asarray(np.random.default_rng(1).normal(size=(8, 128)).astype(np.float32))
        s = np.asarray(all_reduce_q(x, mesh1d, reduce_op="sum"))
        a = np.asarray(all_reduce_q(x, mesh1d, reduce_op="avg"))
        np.testing.assert_allclose(a, s / 8, rtol=1e-6)

    def test_reduce_scatter_q(self, mesh1d):
        x = jnp.asarray(np.random.default_rng(2).normal(size=(8, 64, 16)).astype(np.float32))
        exact = np.asarray(mesh_reduce_scatter(x, mesh1d, scatter_dim=0))
        quant = np.asarray(reduce_scatter_q(x, mesh1d, scatter_dim=0))
        assert quant.shape == exact.shape
        bound = 8 * float(np.abs(np.asarray(x)).max()) / 127.0
        assert np.abs(quant - exact).max() <= bound

    def test_stochastic_default_key_fresh_per_call(self, mesh1d, monkeypatch):
        """Without an explicit key, successive SR reductions draw FRESH
        counter-derived noise — a constant mask would correlate rounding
        errors across training steps into systematic drift."""
        monkeypatch.setenv("VESCALE_GRAD_COMPRESS_SR", "1")
        x = jnp.asarray(
            np.random.default_rng(4).normal(size=(8, 2048)).astype(np.float32)
        )
        a = np.asarray(all_reduce_q(x, mesh1d))
        b = np.asarray(all_reduce_q(x, mesh1d))
        assert not np.array_equal(a, b)

    def test_dp_grad_reduce_leaf_and_step_keys(self, mesh2d):
        """SR noise differs per tree leaf and per step value."""
        from vescale_tpu.parallel.ddp import dp_grad_reduce

        x = jnp.asarray(
            np.random.default_rng(5).normal(size=(2, 33, 64)).astype(np.float32)
        )

        def body(v, step):
            v = jnp.squeeze(v, 0)
            out = dp_grad_reduce(
                {"a": v, "b": v}, "dp", 2, compress="int8",
                rounding="stochastic", key=jax.random.key(0), step=step,
            )
            return out["a"], out["b"]

        f = jax.jit(shard_map(
            body, mesh=mesh2d.jax_mesh, in_specs=(P("dp"), P()),
            out_specs=(P(), P()), check_vma=False,
        ))
        a0, b0 = f(x, jnp.asarray(0))
        assert not np.array_equal(np.asarray(a0), np.asarray(b0)), "leaves share noise"
        a1, _ = f(x, jnp.asarray(1))
        assert not np.array_equal(np.asarray(a0), np.asarray(a1)), "steps share noise"
        with pytest.raises(ValueError, match="sum/avg"):
            dp_grad_reduce({"a": x}, "dp", 2, compress=None, reduce_op="max")

    def test_stochastic_seeded_replayable(self, mesh1d):
        x = jnp.asarray(np.random.default_rng(3).normal(size=(8, 512)).astype(np.float32))
        k = jax.random.key(11)
        a = np.asarray(all_reduce_q(x, mesh1d, rounding="stochastic", key=k))
        b = np.asarray(all_reduce_q(x, mesh1d, rounding="stochastic", key=k))
        assert np.array_equal(a, b)
        c = np.asarray(all_reduce_q(x, mesh1d, rounding="stochastic", key=jax.random.key(12)))
        assert not np.array_equal(a, c)

    def test_telemetry_counters_wire_accurate(self):
        from vescale_tpu import telemetry

        mesh = DeviceMesh(("dp",), (2,))
        telemetry.init(out_dir=None, memtrack=False)
        try:
            x = jnp.ones((2, 4096), jnp.float32)
            all_reduce_q(x, mesh)
            snap = telemetry.get_registry().snapshot()
            assert snap["counters"]["grad_compress_collectives_total"] == 1
            saved = snap["counters"]["grad_compress_bytes_saved_total"]
            # WIRE accounting at n=2: ring all-reduce 2*(1/2)*raw vs one
            # packed contribution received
            raw_wire = 4096 * 4
            q_wire = blockscale.packed_nbytes(4096, 64)
            assert saved == raw_wire - q_wire
            assert abs(snap["gauges"]["grad_compress_ratio"] - raw_wire / q_wire) < 1e-9
            # dashboard folds them into a grad-compression block
            dash = telemetry.dashboard()
            assert "grad-compression:" in dash
            assert "grad_compress_bytes_saved_total" in dash
            prom = telemetry.prometheus_dump()
            assert "grad_compress_bytes_saved_total" in prom
        finally:
            telemetry.shutdown()

    def test_counterproductive_config_warns_not_credits(self, mesh1d):
        """The gather-based quantized all-reduce moves MORE wire bytes than
        the ring at n=8: telemetry must record zero savings (ratio < 1)
        and warn once, never credit phantom compression."""
        from vescale_tpu import telemetry
        from vescale_tpu.collectives import _WARNED_COUNTERPRODUCTIVE

        _WARNED_COUNTERPRODUCTIVE.clear()
        telemetry.init(out_dir=None, memtrack=False)
        try:
            x = jnp.ones((8, 4096), jnp.float32)
            with pytest.warns(UserWarning, match="counterproductive"):
                all_reduce_q(x, mesh1d)
            snap = telemetry.get_registry().snapshot()
            assert snap["counters"]["grad_compress_bytes_saved_total"] == 0
            assert snap["gauges"]["grad_compress_ratio"] < 1.0
        finally:
            telemetry.shutdown()
            _WARNED_COUNTERPRODUCTIVE.clear()


    @pytest.mark.parametrize("wrapper", ["all_reduce_q", "reduce_scatter_q"])
    def test_a_second_call_with_the_same_definition_builds_nothing(self, mesh1d, traces_and_compiles, wrapper):
        """An eager quantized collective is ONE compiled program, kept by what defines it (mesh, axis, op, ``block``,
        ``rounding``, ``acc_dtype``; shape and dtype are ``jit``'s own): the second call traces and compiles nothing
        (before, a bare ``shard_map`` ran its body a primitive at a time, each a program of its own, on every call),
        and a call with another ``block`` or ``rounding`` compiles exactly once more.  The stochastic-rounding key is
        an ARGUMENT of that program: another key is another result and no other program."""
        call = {"all_reduce_q": all_reduce_q, "reduce_scatter_q": lambda *a, **k: reduce_scatter_q(*a, scatter_dim=0, **k)}[wrapper]
        x = jnp.asarray(np.random.default_rng(8).normal(size=(8, 64, 16)).astype(np.float32))
        keys = [jax.random.key(21), jax.random.key(22)]
        first = np.asarray(call(x, mesh1d, block=64))
        with traces_and_compiles() as seen:
            again = np.asarray(call(x, mesh1d, block=64))
        assert seen == {"traced": 0, "compiled": 0} and np.array_equal(first, again)
        for other in (dict(block=32), dict(block=64, rounding="stochastic", key=keys[0])):
            with traces_and_compiles() as seen:
                once = np.asarray(call(x, mesh1d, **other))
            assert seen["traced"] >= 1 and seen["compiled"] == 1, (other, seen)
            with traces_and_compiles() as seen:
                twice = np.asarray(call(x, mesh1d, **other))
            assert seen == {"traced": 0, "compiled": 0} and np.array_equal(once, twice), other
        with traces_and_compiles() as seen:
            rekeyed = np.asarray(call(x, mesh1d, block=64, rounding="stochastic", key=keys[1]))
        assert seen == {"traced": 0, "compiled": 0} and not np.array_equal(rekeyed, twice)

    def test_the_knobs_are_read_on_the_host_each_call_and_key_the_program(self, mesh1d, traces_and_compiles, monkeypatch):
        """``VESCALE_GRAD_COMPRESS_BLOCK`` set between two calls takes effect in the second (one more program) and,
        unset again, the first program is found, not rebuilt."""
        x = jnp.asarray(np.random.default_rng(9).normal(size=(8, 512)).astype(np.float32))
        default = np.asarray(all_reduce_q(x, mesh1d))
        monkeypatch.setenv("VESCALE_GRAD_COMPRESS_BLOCK", "16")
        with traces_and_compiles() as seen:
            finer = np.asarray(all_reduce_q(x, mesh1d))
        assert seen["compiled"] == 1 and np.array_equal(finer, np.asarray(all_reduce_q(x, mesh1d, block=16)))
        assert not np.array_equal(finer, default)
        monkeypatch.delenv("VESCALE_GRAD_COMPRESS_BLOCK")
        with traces_and_compiles() as seen:
            assert np.array_equal(np.asarray(all_reduce_q(x, mesh1d)), default)
        assert seen == {"traced": 0, "compiled": 0}


# ============================================================ emulator mode
class TestEmulatorQuantized:
    def test_bit_for_bit_vs_shard_map(self, mesh1d):
        from vescale_tpu.emulator import quantized_all_reduce

        rng = np.random.default_rng(5)
        locals_ = [rng.normal(size=(128, 17)).astype(np.float32) for _ in range(8)]
        rig = np.asarray(all_reduce_q(jnp.stack([jnp.asarray(t) for t in locals_]), mesh1d))
        emu = quantized_all_reduce(locals_, block=64)[0]
        assert np.array_equal(rig, emu), "emulator replay must be bit-for-bit"

    def test_bit_for_bit_stochastic(self, mesh1d):
        from vescale_tpu.emulator import quantized_all_reduce

        rng = np.random.default_rng(6)
        locals_ = [rng.normal(size=(256,)).astype(np.float32) for _ in range(8)]
        rig = np.asarray(all_reduce_q(
            jnp.stack([jnp.asarray(t) for t in locals_]), mesh1d,
            rounding="stochastic", key=jax.random.key(9),
        ))
        emu = quantized_all_reduce(locals_, block=64, rounding="stochastic", seed=9)[0]
        assert np.array_equal(rig, emu)

    def test_reduce_scatter_replay(self, mesh1d):
        from vescale_tpu.emulator import quantized_reduce_scatter

        rng = np.random.default_rng(7)
        locals_ = [rng.normal(size=(64, 8)).astype(np.float32) for _ in range(8)]
        rig = np.asarray(reduce_scatter_q(
            jnp.stack([jnp.asarray(t) for t in locals_]), mesh1d, scatter_dim=0
        ))
        emu = quantized_reduce_scatter(locals_, block=64)
        for r in range(8):
            assert np.array_equal(rig[r], emu[r]), r

    def test_ring_report(self):
        from vescale_tpu.emulator import quantized_ring_report

        rng = np.random.default_rng(8)
        locals_ = [rng.normal(size=(512,)).astype(np.float32) for _ in range(4)]
        rep = quantized_ring_report(locals_, block=64)
        assert rep["world_size"] == 4 and len(rep["buckets"]) == 4
        assert rep["compress_ratio"] > 3.5
        assert rep["max_abs_err"] > 0  # lossy
        for b in rep["buckets"]:
            assert 0 <= b["bitwise_equal_elements"] <= b["n_elements"]
            assert b["max_abs_err"] <= 4 * 10 / 127.0  # loose sanity bound

    def test_process_group_quantized_mode(self):
        from vescale_tpu.emulator import EmulatorProcessGroup, quantized_all_reduce

        locals_ = [np.full((64,), float(r + 1), np.float32) for r in range(4)]
        pg = EmulatorProcessGroup(4, quantized="int8")
        out = pg.all_reduce(locals_)
        assert np.array_equal(out[0], quantized_all_reduce(locals_, block=64)[0])
        with pytest.raises(ValueError, match="quantized"):
            EmulatorProcessGroup(4, quantized="fp4")


# ========================================================= planner quant hop
@pytest.fixture
def quant_gate(monkeypatch):
    from vescale_tpu.redistribute_plan import clear_plan_cache

    monkeypatch.setenv("VESCALE_REDISTRIBUTE_QUANT", "1")
    clear_plan_cache()
    yield
    clear_plan_cache()


class TestPlannerQuantHop:
    def _specs(self, mesh, dtype=jnp.float32, shape=(4096, 64)):
        meta = TensorMeta(shape, jnp.dtype(dtype))
        return (
            DArraySpec(mesh, (Partial(),), meta),
            DArraySpec(mesh, (Replicate(),), meta),
        )

    def test_hop_taken_where_cost_model_wins(self, quant_gate):
        from vescale_tpu.redistribute_plan import quant_outcome, quant_single_hop_plan

        mesh = DeviceMesh(("dp",), (2,))
        src, dst = self._specs(mesh)
        verdict, hop = quant_outcome(src, dst)
        assert verdict == "taken"
        assert hop.collectives == {"all_reduce:int8": 1}
        assert hop.bytes_moved < hop.bytes_raw / 3.5
        plan = quant_single_hop_plan(src, dst)
        assert plan is not None and plan.hops[0].kind == "quant"
        # executing the plan through redistribute() is lossy-but-bounded
        loc = np.random.default_rng(0).normal(size=(4096, 64)).astype(np.float32)
        d = vt.from_local([loc, loc], mesh, [Partial()])
        out = d.redistribute(placements=[Replicate()])
        err = np.abs(np.asarray(out.data) - 2 * loc).max()
        assert 0 < err <= 2 * np.abs(loc).max() / 127.0

    def test_structured_decline_where_it_loses(self, quant_gate):
        from vescale_tpu.redistribute_plan import quant_decline_finding, quant_outcome

        # the gather-based quantized all-reduce is O(n) in both wire bytes
        # and dequantize compute: at a mesh dim of 8 the ring psum wins
        mesh = DeviceMesh(("dp",), (8,))
        src, dst = self._specs(mesh)
        verdict, decline = quant_outcome(src, dst)
        assert verdict == "declined"
        assert decline.code == "VSC127" and "cost model" in decline.message
        assert quant_decline_finding(src, dst).code == "VSC127"

    def test_decline_on_unquantizable_dtype(self, quant_gate):
        from vescale_tpu.redistribute_plan import quant_outcome

        mesh = DeviceMesh(("dp",), (2,))
        src, dst = self._specs(mesh, jnp.int32)
        verdict, decline = quant_outcome(src, dst)
        assert verdict == "declined" and decline.code == "VSC127"
        assert "no quantizable" in decline.message

    def test_gate_off_is_inert(self):
        from vescale_tpu.redistribute_plan import (
            clear_plan_cache,
            quant_outcome,
            quant_single_hop_plan,
        )

        clear_plan_cache()
        mesh = DeviceMesh(("dp",), (2,))
        src, dst = self._specs(mesh)
        assert quant_outcome(src, dst) is None
        assert quant_single_hop_plan(src, dst) is None
        # redistribute stays exact
        loc = np.random.default_rng(0).normal(size=(4096, 64)).astype(np.float32)
        d = vt.from_local([loc, loc], mesh, [Partial()])
        out = d.redistribute(placements=[Replicate()])
        np.testing.assert_array_equal(np.asarray(out.data), 2 * loc)

    def test_shardcheck_surfaces_taken_and_declined(self, quant_gate):
        from vescale_tpu.analysis.shardcheck import check_transition

        mesh = DeviceMesh(("dp",), (2,))
        src, dst = self._specs(mesh)
        codes = [f.code.code for f in check_transition(src, dst)]
        assert "VSC128" in codes
        mesh8 = DeviceMesh(("dp",), (8,))
        src8, dst8 = self._specs(mesh8)
        codes = [f.code.code for f in check_transition(src8, dst8)]
        assert "VSC127" in codes

    def test_cache_stats_track_quant_declines(self, quant_gate):
        from vescale_tpu.redistribute_plan import plan_cache_stats, quant_outcome

        mesh = DeviceMesh(("dp",), (2,))
        src, dst = self._specs(mesh, jnp.int32)
        quant_outcome(src, dst)
        assert plan_cache_stats()["quant_declines"] >= 1

    def test_multi_hop_plan_can_carry_quant_edge(self, quant_gate):
        """A composite transition (Partial x cross-dim Shard) that only the
        planner serves: with the gate on, its wire-heavy edge may quantize;
        the plan still verifies against the exact result within bound."""
        mesh = DeviceMesh(("dp", "tp"), (2, 4))
        meta = TensorMeta((512, 64), jnp.dtype(jnp.float32))
        src = DArraySpec(mesh, (Partial(), Shard(1)), meta)
        dst = DArraySpec(mesh, (Shard(0), Replicate()), meta)
        from vescale_tpu.redistribute_plan import plan_redistribute

        plan = plan_redistribute(src, dst)
        assert plan is not None


# ================================================== comm_mode attribution
class TestCommModeInt8:
    def test_count_collectives_synthetic(self):
        from vescale_tpu.debug.comm_mode import count_collectives

        text = "\n".join([
            "%ar = f32[128]{0} all-reduce(f32[128]{0} %p), replica_groups={{0,1}}",
            "%ag = s8[2,4224]{1,0} all-gather(s8[1,4224]{1,0} %q), replica_groups={{0,1}}",
            "%mv = u8[2,4224]{1,0} all-to-all(u8[2,4224]{1,0} %r), replica_groups={{0,1}}",
            "%aa = s8[2,64]{1,0} all-to-all(s8[2,64]{1,0} %s), replica_groups={{0,1}}",
        ])
        c = count_collectives(text)
        # s8 all-gather attributes to logical all_reduce with the int8 tag
        assert c["all_reduce"] == 2 and c["all_reduce:int8"] == 1
        assert c["all_gather"] == 0
        # u8 all-to-all keeps its own logical op; s8 all-to-all -> reduce_scatter
        assert c["all_to_all"] == 1 and c["all_to_all:int8"] == 1
        assert c["reduce_scatter"] == 1 and c["reduce_scatter:int8"] == 1
        # tags are detail, not double counts
        assert c["total"] == 4

    def test_compiled_quant_program_attribution(self):
        from vescale_tpu.debug.comm_mode import collective_wire_bytes, count_collectives

        mesh = DeviceMesh(("dp",), (8,))
        x = jnp.zeros((8, 8192), jnp.float32)

        def quant(v):
            return q_psum(jnp.squeeze(v, 0), "dp", 8, block=64)

        f = jax.jit(shard_map(
            quant, mesh=mesh.jax_mesh, in_specs=P("dp"), out_specs=P(),
            check_vma=False,
        ))
        text = f.lower(x).compile().as_text()
        c = count_collectives(text)
        assert c["all_reduce"] == 1 and c.get("all_reduce:int8") == 1
        assert c["all_gather"] == 0, "quantized reduce must not read as gather traffic"
        w = collective_wire_bytes(text)
        assert w["all_reduce:int8"] == w["total"] > 0
        # unoptimized stableHLO spelling parses to the SAME wire bytes
        ws = collective_wire_bytes(f.lower(x).as_text())
        assert ws["total"] == w["total"] and ws.get("all_reduce:int8") == w["all_reduce:int8"]

    def test_wire_bytes_ratio_two_ranks(self):
        """The acceptance measurement: >= 3.5x fewer grad bytes for int8 vs
        the fp32 payload at world 2 (the gloo rig's configuration)."""
        from vescale_tpu.debug.comm_mode import collective_wire_bytes

        mesh = DeviceMesh(("dp",), (2,))
        x = jnp.zeros((2, 1 << 16), jnp.float32)
        fb = jax.jit(shard_map(
            lambda v: jax.lax.psum(jnp.squeeze(v, 0), "dp"),
            mesh=mesh.jax_mesh, in_specs=P("dp"), out_specs=P(), check_vma=False,
        ))
        fq = jax.jit(shard_map(
            lambda v: q_psum(jnp.squeeze(v, 0), "dp", 2, block=64),
            mesh=mesh.jax_mesh, in_specs=P("dp"), out_specs=P(), check_vma=False,
        ))
        wb = collective_wire_bytes(fb.lower(x).compile().as_text())
        wq = collective_wire_bytes(fq.lower(x).compile().as_text())
        assert wb["total"] / wq["total"] >= 3.5


# ====================================================== DDP / optimizer knob
class _FakeModule:
    def __init__(self, mesh):
        self.mesh = mesh

    def apply(self, *a, **k):  # pragma: no cover - unused
        raise NotImplementedError


class TestGradCompressKnob:
    def test_ddp_finish_grad_sync_int8(self, mesh2d):
        from vescale_tpu.parallel import DistributedDataParallel

        loc = np.random.default_rng(0).normal(size=(256, 64)).astype(np.float32)
        g = vt.from_local([loc] * 8, mesh2d, [Partial(), Replicate()])
        ddp = DistributedDataParallel(_FakeModule(mesh2d), mesh2d, grad_compress="int8")
        out = ddp.finish_grad_sync({"w": g})["w"]
        assert out.placements[0].is_replicate()
        err = np.abs(np.asarray(out.data) - 2 * loc).max()
        assert 0 < err <= 2 * np.abs(loc).max() / 127.0

    def test_ddp_zero_reduce_scatter_int8(self, mesh2d):
        from vescale_tpu.parallel import DistributedDataParallel

        loc = np.random.default_rng(1).normal(size=(256, 64)).astype(np.float32)
        g = vt.from_local([loc] * 8, mesh2d, [Partial(), Replicate()])
        ddp = DistributedDataParallel(
            _FakeModule(mesh2d), mesh2d, grad_compress="int8",
            use_distributed_optimizer=True,
        )
        out = ddp.finish_grad_sync({"w": g})["w"]
        assert out.placements[0] == Shard(0)
        exact = np.asarray(
            g.redistribute(placements=[Shard(0), Replicate()]).data
        )
        err = np.abs(np.asarray(out.data) - exact).max()
        assert 0 < err <= 2 * np.abs(loc).max() / 127.0

    def test_knob_env_default_and_validation(self, mesh2d, monkeypatch):
        from vescale_tpu.parallel import DistributedDataParallel
        from vescale_tpu.parallel.ddp import resolve_grad_compress

        assert DistributedDataParallel(_FakeModule(mesh2d), mesh2d).grad_compress is None
        monkeypatch.setenv("VESCALE_GRAD_COMPRESS", "int8")
        assert (
            DistributedDataParallel(_FakeModule(mesh2d), mesh2d).grad_compress == "int8"
        )
        with pytest.raises(ValueError, match="int8"):
            resolve_grad_compress("fp4")

    def test_distributed_optimizer_reduce_grads(self, mesh2d):
        from vescale_tpu.parallel.optimizer import DistributedOptimizer

        loc = np.random.default_rng(2).normal(size=(256, 64)).astype(np.float32)
        g = vt.from_local([loc] * 8, mesh2d, [Partial(), Replicate()])
        dopt = DistributedOptimizer(
            optax.adamw(1e-3), mesh2d, {"w": P(None, "tp")}, grad_compress="int8"
        )
        out = dopt.reduce_grads({"w": g})["w"]
        # ZeRO active + dim0 divisible -> reduce-scattered into Shard(0)
        assert out.placements[0] == Shard(0)
        err = np.abs(np.asarray(out.data) - 2 * loc).max()
        assert 0 < err <= 2 * np.abs(loc).max() / 127.0
        # non-DArray leaves ride through untouched
        plain = jnp.ones((4,))
        assert dopt.reduce_grads({"w": plain})["w"] is plain

    def test_dp_grad_reduce_in_shard_map(self, mesh2d):
        from vescale_tpu.parallel.ddp import dp_grad_reduce

        loc = np.random.default_rng(3).normal(size=(32, 16)).astype(np.float32)

        def body(x):
            x = jnp.squeeze(x, 0)
            return dp_grad_reduce({"g": x}, "dp", 2, compress="int8")["g"]

        f = jax.jit(shard_map(
            body, mesh=mesh2d.jax_mesh, in_specs=P("dp"), out_specs=P(),
            check_vma=False,
        ))
        out = np.asarray(f(jnp.stack([jnp.asarray(loc)] * 2)))
        err = np.abs(out - 2 * loc).max()
        assert 0 < err <= 2 * np.abs(loc).max() / 127.0

    def test_uncompressed_paths_unchanged(self, mesh2d):
        """Default (knob off): finish_grad_sync stays exact."""
        from vescale_tpu.parallel import DistributedDataParallel

        loc = np.ones((16, 4), np.float32)
        g = vt.from_local([loc] * 8, mesh2d, [Partial(), Replicate()])
        ddp = DistributedDataParallel(_FakeModule(mesh2d), mesh2d)
        out = ddp.finish_grad_sync({"w": g})["w"]
        np.testing.assert_array_equal(np.asarray(out.data), 2 * loc)


# ============================================================== env registry
def test_knobs_registered():
    from vescale_tpu.analysis import envreg

    for name in (
        "VESCALE_GRAD_COMPRESS",
        "VESCALE_GRAD_COMPRESS_BLOCK",
        "VESCALE_GRAD_COMPRESS_SR",
        "VESCALE_GRAD_COMPRESS_SEED",
        "VESCALE_REDISTRIBUTE_QUANT",
    ):
        assert envreg.is_registered(name), name
    assert envreg.get_int("VESCALE_GRAD_COMPRESS_BLOCK") == 64
    assert envreg.get_bool("VESCALE_REDISTRIBUTE_QUANT") is False


# ============================================================ smoke wiring
def test_quantcomm_smoke_script():
    """tier-1 wiring of scripts/quantcomm_smoke.py: the 2-proc gloo rig's
    >=3.5x byte savings, the emulator bit-for-bit replay, and the e2e CPU
    loss-trajectory tolerance."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "quantcomm_smoke.py")],
        capture_output=True, text=True, timeout=420, cwd=REPO,
    )
    assert out.returncode == 0, out.stdout[-4000:] + out.stderr[-4000:]
    assert "QUANTCOMM SMOKE OK" in out.stdout
