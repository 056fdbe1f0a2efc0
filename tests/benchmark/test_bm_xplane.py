"""The reduction from a profiler trace to busy/idle time, the op table and
named gaps, on two small traces recorded in this PR's own traced runs on the
TPU v5e (cut to the XLA-op line and the benchmark's annotations): two steps of
``mistral7b_train_seq4096`` and a prefill between decode steps of
``mistral7b_serve_chat``."""

import os

import pytest

from bm_fixtures import REPO

from benchmark import xplane

DATA = os.path.join(REPO, "benchmark", "testdata")


@pytest.fixture(scope="module")
def train():
    return xplane.summarize(xplane.load(os.path.join(DATA, "train_2steps.xplane.pb")))


@pytest.fixture(scope="module")
def chat():
    return xplane.summarize(xplane.load(os.path.join(DATA, "chat_prefill_decode.xplane.pb")))


def test_train_busy_and_idle(train):
    assert train["devices"] == 1
    assert train["busy_s"] == pytest.approx(0.464173281, rel=1e-9)
    assert train["window_s"] == pytest.approx(0.470737706, rel=1e-9)
    assert 100 * (1 - train["busy_s"] / train["window_s"]) == pytest.approx(1.3945, abs=1e-3)
    assert train["collective_s"] == 0.0


def test_train_op_table(train):
    assert train["device_ops"][:3] == [
        ["fusion_bf16_4096_14336__x20", pytest.approx(0.148828559)],
        ["fusion_f32_4096__x29", pytest.approx(0.109729609)],
        ["fusion_bf16_4096_4096__x18", pytest.approx(0.039129744)]]
    # the flash kernels carry the name of the module that calls them
    flash = dict(train["device_ops"])["self_attn_bf16_32_4096_128__x8"]
    assert flash == pytest.approx(0.028223321)
    seconds = [s for _, s in train["device_ops"]]
    assert seconds == sorted(seconds, reverse=True) and sum(seconds) <= train["busy_s"]


def test_train_gaps_are_named_by_the_benchmarks_span(train):
    assert train["idle_gaps"] == [["bm.step", pytest.approx(0.003280711)], ["bm.step", pytest.approx(0.003278397)]]


def test_chat_busy_ops_and_gaps(chat):
    assert chat["busy_s"] == pytest.approx(0.296771768, rel=1e-9)
    assert chat["window_s"] == pytest.approx(0.309856707, rel=1e-9)
    assert [name for name, _ in chat["device_ops"][:4]] == [
        "copy_bf16_32_128_16_8_128__x32", "broadcast_select_fusion_bf16_32_128_16_8_128__x16",
        "slice_bitcast_fusion_bf16_4097_16_8_128__x32", "fusion_bf16_4096_16_8_128__x32"]
    assert chat["idle_gaps"][:4] == [
        ["bm.decode", pytest.approx(0.003577645)], ["outside bm spans", pytest.approx(0.003543292)],
        ["bm.decode", pytest.approx(0.003459793)], ["bm.prefill", pytest.approx(0.001684038)]]


def test_interval_arithmetic():
    assert xplane.union_length([(0, 2), (1, 3), (5, 6), (5.5, 5.8)]) == 4
    assert xplane.merged([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]
    spans = [(0.0, 10.0, "bm.outer"), (2.0, 4.0, "bm.inner")]
    assert xplane.name_gap((2.5, 3.5), spans) == "bm.inner"
    assert xplane.name_gap((5.0, 6.0), spans) == "bm.outer"
    assert xplane.name_gap((11.0, 12.0), spans) == "outside bm spans"


@pytest.mark.parametrize("name,family,tag,collective", [
    ("%fusion.123 = bf16[32,14336]{1,0:T(8,128)(2,1)} fusion(bf16[32,4096]{1,0} %x), kind=kOutput",
     "fusion", "bf16_32_14336", False),
    ("%all-reduce-start.4 = f32[4096]{0} all-reduce-start(f32[4096]{0} %g), replica_groups={{0,1}}",
     "all-reduce-start", "f32_4096", True),
    ("%copy-start = (bf16[4096,14336]{1,0}, bf16[4096,14336]{1,0}, u32[]) copy-start(bf16[4096,14336]{1,0} %p)",
     "copy-start", "bf16_4096_14336", False),
    ("%collective-permute-done.2.1 = bf16[8,128]{1,0} collective-permute-done(%s)", "collective-permute-done",
     "bf16_8_128", True),
    ("%all_gather_fusion = u32[] fusion()", "all_gather_fusion", "u32", False),
])
def test_instruction_names(name, family, tag, collective):
    assert xplane.op_family(name) == family
    assert xplane.shape_tag(name) == tag
    assert xplane.is_collective(name) is collective


def test_a_trace_without_device_operations_gives_nothing(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    files = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path) for f in fs if f.endswith(".xplane.pb")]
    assert files and xplane.summarize(xplane.load(files[0])) is None
