"""The collector's span under the benchmark's reduction, as the reduction
stands (``benchmark/layer_metrics/_session.py`` is read, not edited): an idle
gap whose middle lies in a ``vs.host-gc`` span is named by it, whatever span it
is nested in, and counts as covered; a gap the collector only touched keeps the
name it had."""

import pytest

from test_bm_session import _ring, _trace

from benchmark.layer_metrics import _session

HOST_GC = "vs.host-gc"      # vescale_tpu.ndtimeline.predefined.HOST_GC; a test below holds the two together

# (the host's spans around an idle gap of 1,000..151,000 us, the gap's name, microseconds of it no span covers)
CASES = {
    "nested in the loop's books": (
        [(900, 161000, "vs.serve-books"), (5000, 145000, HOST_GC)], HOST_GC, 0),
    "nested in a fetch inside a decode call": (
        [(500, 152000, "vs.serve-decode"), (800, 151500, "vs.serve-decode.fetch"), (20000, 140000, HOST_GC)], HOST_GC, 0),
    "between the loop's spans, where the gap read unattributed": (
        [(30000, 130000, HOST_GC)], HOST_GC, 50000),
    "under the benchmark's span alone": (
        [(0, 160000, "bm.decode"), (40000, 120000, HOST_GC)], HOST_GC, 0),
    "a short collection early in the books: acquitted": (
        [(900, 161000, "vs.serve-books"), (2000, 9000, HOST_GC)], "vs.serve-books", 0),
    "no collection at all": (
        [(900, 161000, "vs.serve-books")], "vs.serve-books", 0),
}


def test_the_name_is_the_programs():
    from vescale_tpu.ndtimeline import predefined

    assert predefined.HOST_GC == HOST_GC and HOST_GC.startswith(_session.PROGRAM_PREFIX)


@pytest.mark.parametrize("case", list(CASES))
def test_name_gap_takes_the_innermost_span_at_the_middle(case):
    host, name, _ = CASES[case]
    program = [(a * 1e3, b * 1e3, n) for a, b, n in host if n.startswith("vs.")]
    benchmark = [(a * 1e3, b * 1e3, n) for a, b, n in host if n.startswith("bm.")]
    assert _session.name_gap((1000e3, 151000e3), program, benchmark) == name


@pytest.mark.parametrize("case", list(CASES))
def test_the_reduction_names_the_long_gap_and_counts_it_covered(case):
    host, name, uncovered_us = CASES[case]
    device_ops = [(100, 1000), (151000, 152000), (152700, 153400)]
    modules = [(a, b, "jit_decode(1)") for a, b in device_ops]
    ring = _ring(*[(n, a, b) for a, b, n in host if n.startswith("vs.")])
    counters = {"decode_steps": 3, "gc_pauses": 7, "gc_gen2_pauses": 1, "gc_pause_us": 140250}
    out = _session.reduce(_trace(device_ops, modules, host + [(-50, -49, "vs.session-mark")]), ring,
                          lambda s: s * 1e9, counters)
    assert out["idle_gaps"][0] == [name, pytest.approx(0.15)]
    # the second gap, 152,000..152,700, lies under no span in any case but the books' and the decode call's
    second_uncovered = 0 if any(a <= 152000 and b >= 152700 for a, b, _ in host) else 700
    assert out["idle_unattributed_share"] == pytest.approx(100 * (uncovered_us + second_uncovered) / 150700)
    assert _session.breakdown(out)["idle_gaps"][0][0] == name
    # the session's counters reach the result whole, and the ring's record of the pause its place
    assert {k: out["counters"][k] for k in ("gc_pauses", "gc_gen2_pauses", "gc_pause_us")} == {
        "gc_pauses": 7, "gc_gen2_pauses": 1, "gc_pause_us": 140250}
    assert (HOST_GC in out["ring_ms"]) == any(n == HOST_GC for _, _, n in host)
