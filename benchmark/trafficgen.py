"""The one general traffic generator: a traffic mix is a data file of
parameters, and this module turns it and a seed into requests.

Every seed carries the same work.  Lengths are the quantiles of the mix's
distributions on a fixed grid, one per request, paired by a permutation the
*file* fixes; ``--seed`` only permutes the order of the requests and of the
gaps between their arrivals, and draws the token ids.  An open loop holds a
fixed number of requests, rate x horizon; the gaps between their arrivals are
the quantiles of an exponential distribution on a fixed grid, scaled to fill
the horizon: the spacings of a Poisson process conditioned on its count, with
the same share of close arrivals under every seed.

Kinds and their parameters (``benchmark/README.md`` has the long form):

``train_steps``  seq_len, global_batch, lead_in_steps, learning_rate,
                 token_file_sequences
``open_loop``    rate_per_s, lead_in_s, grace_s, prompt_len, output_len,
                 max_total, pairing_seed
``closed_loop``  clients, lead_in_s, pool, first_wave, prompt_len,
                 output_len, max_total, pairing_seed
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

KINDS = ("train_steps", "open_loop", "closed_loop")


class TrafficError(ValueError):
    """A traffic file asks for something the generator cannot make."""


def quantile_lengths(dist: Dict[str, Any], n: int) -> np.ndarray:
    """n whole lengths: the distribution's quantiles at (i + 1/2) / n, clipped
    to [min, max].  No randomness: the same n gives the same lengths."""
    if n < 1:
        raise TrafficError("a length grid needs at least one request")
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(dist["min"]), int(dist["max"])
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        x = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    elif kind == "loguniform":
        x = np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    elif kind == "uniform":
        x = lo + u * (hi - lo)
    else:
        raise TrafficError(f"unknown length distribution {kind!r}")
    return np.clip(np.round(x), lo, hi).astype(np.int64)


@dataclasses.dataclass(frozen=True)
class PlannedRequest:
    rid: int
    due_s: float              # seconds after the traffic starts (open loop); 0 for a closed loop
    prompt: Tuple[int, ...]
    max_new_tokens: int


def _paired_lengths(traffic: Dict[str, Any], n: int) -> List[Tuple[int, int]]:
    """The multiset of (prompt, output) lengths, the same for every seed."""
    prompts = quantile_lengths(traffic["prompt_len"], n)
    outputs = quantile_lengths(traffic["output_len"], n)
    pairing = np.random.default_rng(int(traffic.get("pairing_seed", 0))).permutation(n)
    pairs = [(int(p), int(outputs[j])) for p, j in zip(prompts, pairing)]
    limit = int(traffic["max_total"])
    too_long = [pr for pr in pairs if pr[0] + pr[1] > limit]
    if too_long:
        raise TrafficError(f"{len(too_long)} requests exceed max_total {limit}, e.g. {too_long[0]}: "
                           "the cache would shed them; choose lengths on which no operation fails")
    return pairs


def _prompts(rng: np.random.Generator, lengths: Sequence[int], vocab: int) -> List[Tuple[int, ...]]:
    return [tuple(int(t) for t in rng.integers(1, vocab - 1, n)) for n in lengths]


def arrival_gaps(n: int, horizon: float) -> np.ndarray:
    """The n + 1 spacings of n arrivals over ``horizon`` (the last one runs
    from the last arrival to the horizon's end): quantiles of the exponential
    distribution at (i + 1/2) / (n + 1), scaled to sum to the horizon.  No
    randomness: a seed only permutes them, so every seed has as many arrivals
    close upon one another as every other."""
    gaps = -np.log1p(-(np.arange(n + 1) + 0.5) / (n + 1))
    return gaps * (horizon / gaps.sum())


def open_loop_requests(traffic: Dict[str, Any], seed: int, seconds: float, vocab: int) -> List[PlannedRequest]:
    """The schedule of an ``open_loop`` mix over lead-in + window."""
    horizon = float(traffic["lead_in_s"]) + float(seconds)
    n = int(round(float(traffic["rate_per_s"]) * horizon))
    pairs = _paired_lengths(traffic, n)
    rng = np.random.default_rng([int(seed), 1])
    order = rng.permutation(n)
    due = np.cumsum(rng.permutation(arrival_gaps(n, horizon)))[:n]
    prompts = _prompts(rng, [pairs[j][0] for j in order], vocab)
    return [PlannedRequest(rid=i, due_s=float(due[i]), prompt=prompts[i], max_new_tokens=pairs[j][1])
            for i, j in enumerate(order)]


def closed_loop_requests(traffic: Dict[str, Any], seed: int, vocab: int) -> List[PlannedRequest]:
    """The pool of a ``closed_loop`` mix, in the order the clients take from
    it: the same multiset of requests for every seed.  A run that sends more
    than the pool holds takes it again from the start."""
    n = int(traffic["pool"])
    pairs = _paired_lengths(traffic, n)
    rng = np.random.default_rng([int(seed), 2])
    order = rng.permutation(n)
    prompts = _prompts(rng, [pairs[j][0] for j in order], vocab)
    return [PlannedRequest(rid=i, due_s=0.0, prompt=prompts[i], max_new_tokens=pairs[j][1])
            for i, j in enumerate(order)]


def first_wave_done_shares(traffic: Dict[str, Any], seed: int) -> List[float]:
    """For the first ``first_wave`` requests a closed loop *sends* (by request
    index, not by place in the pool): the share of its output that counts as
    already done (quantiles of uniform(0, 1), permuted by the seed), so that
    the slots do not all complete in one wave."""
    wave = int(traffic.get("first_wave", 0))
    rng = np.random.default_rng([int(seed), 5])
    return [float(x) for x in rng.permutation((np.arange(wave) + 0.5) / max(wave, 1))]


def cut_first_wave(p: PlannedRequest, done_share: float) -> PlannedRequest:
    return dataclasses.replace(p, max_new_tokens=max(1, int(round(p.max_new_tokens * (1.0 - done_share)))))


def write_token_file(path: str, vocab: int, seq_len: int, sequences: int, seed: int) -> None:
    """The training corpus of a ``train_steps`` mix: uint16 tokens from the
    seed (the nanoGPT .bin convention ``data/loader.py`` reads)."""
    if vocab > 1 << 16:
        raise TrafficError(f"vocab {vocab} does not fit the loader's uint16 token file")
    rng = np.random.default_rng([int(seed), 3])
    rng.integers(0, vocab, sequences * (seq_len + 1), dtype=np.uint16).tofile(path)
