"""The head-to-selection kernel (``kernels/head_select.py``), interpreted on the
CPU, against the op's XLA leg (``logit_stats`` over the head's product, made
whole): the largest logit of a row and its id exactly, the softmax
denominator to a few float32 steps at its scale; and where the benchmark's op
table files the kernel's device event."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vescale_tpu import kernels
from vescale_tpu.kernels import ulps_at_scale
from vescale_tpu.kernels.head_select import head_select, supports

f32, bf16 = jnp.float32, jnp.bfloat16
DENOMINATOR_ULPS = 8        # an online sum against a two-pass one, at the sum's scale


def _operands(rows, d, vocab, dtype, seed=0):
    """Rows and a head whose products and partial sums are EXACT in float32
    (eighths, a few units wide), so that the largest logit and its id do not
    depend on the order a backend adds a row's terms in."""
    rng = np.random.default_rng(seed)
    x = rng.integers(-16, 17, (rows, d)).astype(np.float32) / 8
    w = rng.integers(-16, 17, (d, vocab)).astype(np.float32) / 8
    return x, w, dtype


def _both(x, w, dtype, **tiles):
    x, w = jnp.asarray(x, dtype), jnp.asarray(w, dtype)
    got = head_select(x, w, interpret=True, **tiles)
    want = head_select(x, w, interpret=None)
    return [np.asarray(a) for a in got], [np.asarray(a) for a in want]


def _hold(got, want):
    assert got[0].dtype == np.float32 and got[1].dtype == np.int32 and got[2].dtype == np.float32
    assert np.array_equal(got[0], want[0]) and np.array_equal(got[1], want[1])
    assert ulps_at_scale(got[2], want[2]) <= DENOMINATOR_ULPS


def _a_vocabulary_that_is_not_whole_tiles():
    """128 x 11 columns under a tile of 512 in products of 256: two whole tiles and 384 columns of a third."""
    _hold(*_both(*_operands(16, 64, 1408, f32), tile=512, chunk=256))


def _rows_that_are_not_whole_sublanes():
    _hold(*_both(*_operands(12, 64, 1408, f32, seed=1), tile=512, chunk=128))


def _bfloat16_operands_as_served():
    _hold(*_both(*_operands(24, 128, 1408, bf16, seed=2), tile=512, chunk=256))


def _a_vocabulary_under_one_lane_tile():
    """The toy engines' 96 columns: 32 lanes never see a column, and keep a denominator of nothing."""
    _hold(*_both(*_operands(16, 64, 96, f32, seed=3)))


def _a_vocabulary_that_ends_inside_a_slab():
    """1,400 columns: the last tile's third slab holds 120 of them, the fourth none."""
    _hold(*_both(*_operands(16, 64, 1400, f32, seed=4), tile=512, chunk=128))


def _whole_tiles_alone():
    _hold(*_both(*_operands(8, 64, 1024, f32, seed=5), tile=512, chunk=512))


def _one_hot_rows(seed):
    """Row r is twice the r-th unit vector: its logits are twice the head's row r."""
    _x, w, dtype = _operands(8, 64, 1408, f32, seed=seed)
    return 2.0 * np.eye(8, 64, dtype=np.float32), w, dtype


def _a_tie_in_two_tiles_goes_to_the_lower_id():
    """Row r's two largest logits are EQUAL, one in the first tile and one in
    the ragged last one (column 1,300 + r, lane 20 + r); the earlier one in a
    lower lane than that (rows 0-3) and in a higher one (rows 4-7): the lower id."""
    x, w, dtype = _one_hot_rows(6)
    first = np.asarray([7, 8, 9, 10, 104, 105, 106, 107])
    for r in range(8):
        w[r, first[r]] = w[r, 1300 + r] = 8.0
    got, want = _both(x, w, dtype, tile=512, chunk=128)
    assert np.array_equal(got[1], first.astype(np.int32)) and np.array_equal(got[0], np.full(8, 16.0, np.float32))
    _hold(got, want)


def _the_maximum_in_the_ragged_last_tile():
    x, w, dtype = _one_hot_rows(7)
    w[:, 1407] = 8.0                                     # the last valid column of all
    got, want = _both(x, w, dtype, tile=512, chunk=256)
    assert np.array_equal(got[1], np.full(8, 1407, np.int32))
    _hold(got, want)


def _a_nan_counts_as_the_largest():
    """As ``jnp.argmax`` has it: the first NaN's id; the denominator is a NaN on both legs."""
    x, w, dtype = _operands(8, 64, 1408, f32, seed=8)
    w[:, 700], w[:, 900] = np.nan, np.nan
    got, want = _both(x, w, dtype, tile=512, chunk=128)
    assert np.array_equal(got[1], want[1]) and np.array_equal(got[1], np.full(8, 700, np.int32))
    assert np.isnan(got[2]).all() and np.isnan(want[2]).all() and np.isposinf(got[0]).all()


def _what_the_compiled_kernel_takes_and_how_it_is_dispatched(monkeypatch):
    assert supports(bf16, 512, 2048, interpret=False) and supports(f32, 256, 2048, interpret=False)
    # (whole lanes; and the rows as one block inside the VMEM a kernel has without asking for more)
    assert not supports(bf16, 512, 2000, interpret=False) and not supports(bf16, 1024, 2048, interpret=False)
    assert not supports(f32, 512, 2048, interpret=False)
    assert supports(jnp.float16, 5, 7, interpret=True) and "head_select" in kernels.DEFAULT_ON_TPU
    with pytest.raises(ValueError, match="head_select"):
        head_select(jnp.zeros((8, 64), f32), jnp.zeros((64, 1408), bf16), interpret=True)
    for mode, leg in (("off", None), ("interpret", True), ("on", None)):     # (no TPU here: "on" falls back)
        monkeypatch.setenv("VESCALE_KERNELS", mode)
        assert kernels.resolve("head_select") is leg
    monkeypatch.delenv("VESCALE_KERNELS")
    assert kernels.resolve("head_select") is None                           # unset, off the chip: the XLA leg


def _the_op_table_files_the_kernels_event_under_unmask():
    """The family's own table looks at the experts' signatures first, and
    ``[512,128]`` is one of them: the kernel's outputs are ``(rows, 1)`` and its
    scratch is no operand, so its event answers to the head's ``,151936]`` alone."""
    from benchmark.families.sdar_moe import mechanism_of, mechanism_signatures
    from benchmark.spec import load_cell

    spec = load_cell("sdar30b_serve_blockgen")
    signatures = mechanism_signatures(spec.config, spec.config["serve"])
    assert mechanism_of("%fusion = f32[512,128]{1,0} fusion(f32[512,2048] %x)", signatures) == "experts"
    rows, E, V = 512, spec.config["hidden_size"], spec.config["vocab_size"]
    x, w = jax.ShapeDtypeStruct((rows, E), bf16), jax.ShapeDtypeStruct((E, V), bf16)
    hlo = jax.jit(lambda x, w: head_select(x, w, interpret=False)).trace(x, w).lower(lowering_platforms=("tpu",)).as_text(dialect="hlo")
    (call,) = [line for line in hlo.splitlines() if "custom-call(" in line and "tpu_custom_call" in line]
    assert f"bf16[{E},{V}]" in call and "[512,128]" not in call
    assert mechanism_of(call, signatures) == "unmask"
    assert mechanism_of("%head_select = (f32[512,1]{1,0}, s32[512,1]{1,0}, f32[512,1]{1,0}) custom-call(bf16[512,2048]{1,0} %x, "
                        "bf16[2048,151936]{1,0} %lm_head), custom_call_target=\"tpu_custom_call\"", signatures) == "unmask"


CASES = {"ragged_vocabulary": _a_vocabulary_that_is_not_whole_tiles,
         "rows_not_whole_sublanes": _rows_that_are_not_whole_sublanes,
         "bfloat16": _bfloat16_operands_as_served,
         "vocabulary_under_a_lane_tile": _a_vocabulary_under_one_lane_tile,
         "vocabulary_ends_inside_a_slab": _a_vocabulary_that_ends_inside_a_slab,
         "whole_tiles": _whole_tiles_alone,
         "tie_across_tiles": _a_tie_in_two_tiles_goes_to_the_lower_id,
         "maximum_in_the_ragged_tile": _the_maximum_in_the_ragged_last_tile,
         "nan_is_the_largest": _a_nan_counts_as_the_largest,
         "dispatch": _what_the_compiled_kernel_takes_and_how_it_is_dispatched,
         "op_table_files_it_under_unmask": _the_op_table_files_the_kernels_event_under_unmask}


@pytest.mark.parametrize("what", list(CASES))
def test_head_select_gives_the_xla_legs_top_and_argmax_and_its_denominator(what, monkeypatch):
    case = CASES[what]
    case(monkeypatch) if what == "dispatch" else case()
