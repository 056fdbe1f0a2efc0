"""Traffic generators carry the same work under every seed, and the metric
arithmetic gives what hand-made samples say it must."""

import collections
import json
import os

import numpy as np
import pytest

from bm_fixtures import REPO

from benchmark import stats, trafficgen


def _traffic(name):
    with open(os.path.join(REPO, "benchmark", "traffic", name + ".json")) as f:
        return json.load(f)


def _multiset(reqs):
    return collections.Counter((len(r.prompt), r.max_new_tokens) for r in reqs)


@pytest.mark.parametrize("seeds", [(1, 2), (7, 2**31 + 12345)])
def test_open_loop_same_work_other_order(seeds):
    t = _traffic("chat_poisson_0.8knee")
    a, b = (trafficgen.open_loop_requests(t, s, 45.0, 32768) for s in seeds)
    assert len(a) == len(b) == round(t["rate_per_s"] * (t["lead_in_s"] + 45.0))
    assert _multiset(a) == _multiset(b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert [r.due_s for r in a] != [r.due_s for r in b]
    horizon = t["lead_in_s"] + 45.0
    spacings = [np.sort(np.diff([0.0] + [r.due_s for r in reqs] + [horizon])) for reqs in (a, b)]
    np.testing.assert_allclose(spacings[0], spacings[1], rtol=0, atol=1e-9)    # the same gaps, in another order
    for reqs in (a, b):
        due = [r.due_s for r in reqs]
        assert due == sorted(due) and 0.0 <= due[0] and due[-1] <= horizon
        assert all(len(r.prompt) + r.max_new_tokens <= t["max_total"] for r in reqs)
        assert all(0 < tok < 32768 for r in reqs for tok in r.prompt)


@pytest.mark.parametrize("n,horizon", [(79, 55.0), (10, 3.0)])
def test_arrival_gaps_are_exponential_quantiles_that_fill_the_horizon(n, horizon):
    gaps = trafficgen.arrival_gaps(n, horizon)
    assert len(gaps) == n + 1 and (gaps > 0).all() and abs(gaps.sum() - horizon) < 1e-9
    assert (np.diff(gaps) > 0).all()
    mean = horizon / (n + 1)
    # an exponential's median is ln 2 of its mean, and 1 - 1/e of it lies under the mean
    assert abs(np.median(gaps) / mean - np.log(2)) < 0.05
    assert abs(np.mean(gaps < mean) - (1 - np.exp(-1))) < 0.06


def test_open_loop_same_seed_same_requests():
    t = _traffic("chat_poisson_0.8knee")
    assert trafficgen.open_loop_requests(t, 5, 45.0, 32768) == trafficgen.open_loop_requests(t, 5, 45.0, 32768)


def test_open_loop_lengths_follow_the_file():
    t = _traffic("chat_poisson_0.8knee")
    reqs = trafficgen.open_loop_requests(t, 3, 45.0, 32768)
    prompts = np.array([len(r.prompt) for r in reqs])
    outs = np.array([r.max_new_tokens for r in reqs])
    assert t["prompt_len"]["min"] <= prompts.min() and prompts.max() <= t["prompt_len"]["max"]
    assert abs(np.median(prompts) - t["prompt_len"]["median"]) <= 8
    assert abs(np.median(outs) - t["output_len"]["median"]) <= 4      # the medians the trace publishes
    assert 950 <= prompts.mean() <= 1030 and 165 <= outs.mean() <= 195


@pytest.mark.parametrize("seeds", [(1, 2), (7, 2**31 + 12345)])
def test_closed_loop_same_pool_other_order(seeds):
    t = _traffic("batch_decode_closed40")
    a, b = (trafficgen.closed_loop_requests(t, s, 102400) for s in seeds)
    wave = t["first_wave"]
    assert len(a) == len(b) == t["pool"]
    assert _multiset(a) == _multiset(b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert all(len(r.prompt) + r.max_new_tokens <= t["max_total"] for r in a)
    # the first requests *sent* count seeded shares of their outputs as done: the same
    # shares in another order, by request index, so a pool that wraps is cut once only
    sa, sb = (trafficgen.first_wave_done_shares(t, s) for s in seeds)
    assert len(sa) == wave and sorted(sa) == sorted(sb) and sa != sb and 0.0 < min(sa) and max(sa) < 1.0
    cut = [trafficgen.cut_first_wave(r, share).max_new_tokens for r, share in zip(a, sa)]
    assert all(1 <= c <= r.max_new_tokens for c, r in zip(cut, a)) and len(set(cut)) > wave // 2
    # at ShareGPT's means, less what the cache's positions clip
    assert 135 <= np.mean([len(r.prompt) for r in a]) <= 165 and 285 <= np.mean([r.max_new_tokens for r in a]) <= 340


def test_quantile_lengths_are_fixed_and_clipped():
    d = {"dist": "lognormal", "median": 256, "sigma": 0.9, "min": 32, "max": 1536}
    a, b = trafficgen.quantile_lengths(d, 121), trafficgen.quantile_lengths(d, 121)
    assert (a == b).all() and a.min() >= 32 and a.max() <= 1536 and (np.diff(a) >= 0).all()
    assert trafficgen.quantile_lengths({"dist": "uniform", "min": 10, "max": 20}, 2).tolist() == [12, 18]
    with pytest.raises(trafficgen.TrafficError):
        trafficgen.quantile_lengths({"dist": "zipf", "min": 1, "max": 2}, 4)


def test_requests_longer_than_the_cache_are_refused():
    t = dict(_traffic("chat_poisson_0.8knee"), max_total=512)
    with pytest.raises(trafficgen.TrafficError):
        trafficgen.open_loop_requests(t, 1, 45.0, 32768)


def test_token_file_from_seed(tmp_path):
    p1, p2, p3 = (str(tmp_path / n) for n in "abc")
    trafficgen.write_token_file(p1, 32768, 64, 4, 9)
    trafficgen.write_token_file(p2, 32768, 64, 4, 9)
    trafficgen.write_token_file(p3, 32768, 64, 4, 10)
    a, b, c = (np.fromfile(p, np.uint16) for p in (p1, p2, p3))
    assert (a == b).all() and (a != c).any() and a.size == 4 * 65 and a.max() < 32768
    with pytest.raises(trafficgen.TrafficError):
        trafficgen.write_token_file(p1, 102400, 64, 4, 9)


# ------------------------------------------------------------ metric arithmetic
@pytest.mark.parametrize("samples,q,want", [
    ([1.0, 2.0, 3.0, 4.0, 5.0], 50, 3.0),
    ([1.0, 2.0, 3.0, 4.0], 50, 2.5),
    (list(range(1, 101)), 95, 95.05),
    ([7.0], 95, 7.0),
    ([], 50, None),
])
def test_percentile(samples, q, want):
    got = stats.percentile(samples, q)
    assert got == want if want is None else got == pytest.approx(want)
    if samples:
        assert got == pytest.approx(float(np.percentile(samples, q)))


def test_emitted_tokens_count_inside_the_window_whatever_the_request_does():
    window = (10.0, 20.0)
    times = [[9.0, 9.9, 10.0, 15.0], [19.99, 20.0, 25.0], []]   # a request that ends after the window still counts
    assert stats.emitted_tokens(times, window) == 3


def test_token_gaps_belong_to_the_window_of_the_closing_token():
    window = (10.0, 20.0)
    times = [[9.5, 10.5, 12.0], [19.0, 21.0], [5.0]]
    assert stats.token_gaps(times, window) == pytest.approx([1.0, 1.5])


def test_first_token_delay_is_timed_from_the_due_instant():
    window = (10.0, 20.0)
    reqs = [
        {"due": 11.0, "token_times": [11.25, 11.5], "status": "completed"},
        {"due": 19.9, "token_times": [20.4], "status": "timed_out"},    # due inside, answered after: a sample
        {"due": 12.0, "token_times": [], "status": "shed"},             # failed, not a sample
        {"due": 13.0, "token_times": [], "status": None},               # never answered: failed
        {"due": 9.0, "token_times": [9.1], "status": "completed"},      # due in the lead-in: not this window's
    ]
    samples, failed = stats.first_token_delays(reqs, window)
    assert samples == pytest.approx([0.25, 0.5]) and failed == 2


def test_step_rate_is_continuous_and_counts_all_the_time():
    # four steps from the opening at 10.0 to the end of the last: a stall lengthens the time
    assert stats.steps_per_s([10.5, 11.0, 11.5, 12.0], 10.0) == pytest.approx(2.0)
    assert stats.steps_per_s([10.5, 11.0, 11.5, 12.1], 10.0) == pytest.approx(4 / 2.1)
    assert stats.steps_per_s([], 10.0) is None
