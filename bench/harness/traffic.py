"""The one traffic generator: every mix is a data file of parameters.

Sizes and gaps are drawn by stratified inverse-CDF sampling in blocks:
each block of ``block`` requests holds the same set of prompt lengths,
output lengths and arrival gaps for every seed, in a seed-drawn order.
So two seeds offer the same work, and a window covering whole blocks sees
the same mix; only the order and the token ids change.  A mix that names
``order_seed`` draws that order from it instead of from the run's seed:
every seed then serves the same schedule, and only token ids (and weights)
change.

Parameters (all in the traffic file):

``kind``           "open_loop" (arrivals at ``rate_per_s``), "closed_loop"
                   (the queue is kept at least ``queue_min`` deep) or
                   "batch" (fixed-size calls; see ``harness.kan``).
``prompt_tokens``, ``output_tokens``
                   {"dist": "lognormal", "median", "sigma", "min", "max"}
                   or {"dist": "uniform", "min", "max"}.
``rate_per_s``     mean arrival rate (open loop).
``burst``          optional {"period_s", "on_s", "factor"}: within each
                   period the first ``on_s`` seconds arrive ``factor`` times
                   faster than the rest, at the same mean rate.
``shared_prefix``  optional {"tokens", "groups"}: requests of one group
                   (rid modulo groups) share their first ``tokens`` tokens.
``block``          requests per stratified block.
``order_seed``     optional: the number the blocks' order is drawn from,
                   in place of the run's seed.
"""

from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np

from .common import np_rng

_TOKENS_TAG, _SIZES_TAG, _GAPS_TAG, _PREFIX_TAG = 7, 11, 13, 17


@dataclasses.dataclass
class Planned:
    rid: int
    prompt_len: int
    output_len: int
    arrival_s: float   # due time, seconds after the load starts


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def draw_lengths(spec: dict, n: int, rng) -> np.ndarray:
    """n stratified lengths of the distribution ``spec``, in seeded order."""
    u = _quantiles(n)
    if spec["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(x) for x in u])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        v = spec["min"] + (spec["max"] - spec["min"]) * u
    elif spec["dist"] == "fixed":
        v = np.full(n, spec["value"], np.float64)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    lo, hi = spec.get("min", 1), spec.get("max", None)
    v = np.clip(np.rint(v), lo, hi if hi is not None else np.inf)
    return rng.permutation(v.astype(np.int64))


def _warp(t: np.ndarray, burst: dict | None) -> np.ndarray:
    """Map steady arrival times (seconds) onto a periodic burst profile
    with the same mean rate (inverse of the cumulative intensity)."""
    if not burst:
        return t
    period, on, factor = burst["period_s"], burst["on_s"], burst["factor"]
    hi = factor * period / (factor * on + (period - on))   # on-phase intensity
    lo = hi / factor
    per_cycle = hi * on + lo * (period - on)               # == period
    cycles, rest = np.divmod(t, per_cycle)
    inside = np.where(rest < hi * on, rest / hi, on + (rest - hi * on) / lo)
    return cycles * period + inside


def plan(traffic: dict, seed: int, count: int) -> list:
    """The first ``count`` requests of the mix for ``seed``.  Open-loop due
    times start at ``first_arrival_s``; closed-loop requests are all due
    at once."""
    block = int(traffic.get("block", 32))
    rate = traffic.get("rate_per_s")
    order = traffic.get("order_seed", seed)
    out, unit, t = [], [], 0.0
    for b in range(math.ceil(count / block)):
        rng = np_rng(order, _SIZES_TAG, b)
        plens = draw_lengths(traffic["prompt_tokens"], block, rng)
        olens = draw_lengths(traffic["output_tokens"], block, rng)
        gaps = -np.log1p(-_quantiles(block))
        # stratified quantiles of the exponential fall short of its mean
        # (by 4% in a block of 8): scale them to mean 1, so the mix keeps
        # its stated rate at any block size
        gaps = rng.permutation(gaps * (block / gaps.sum()))
        for i in range(min(block, count - b * block)):
            out.append(Planned(b * block + i, int(plens[i]), int(olens[i]),
                               0.0))
            unit.append(t)
            t += float(gaps[i])
    first = float(traffic.get("first_arrival_s", 0.05))
    if rate:
        due = _warp(np.array(unit) / float(rate), traffic.get("burst"))
        for p, d in zip(out, due):
            p.arrival_s = first + float(d)
    return out


def prompt_tokens(traffic: dict, seed: int, p: Planned, vocab: int) -> list:
    """Token ids of a planned request's prompt (uniform over [3, vocab)),
    with its group's shared prefix in front where the mix shares one."""
    shared = traffic.get("shared_prefix")
    n_shared = 0
    toks = []
    if shared:
        n_shared = min(int(shared["tokens"]), p.prompt_len - 1)
        g = p.rid % int(shared["groups"])
        toks = np_rng(seed, _PREFIX_TAG, g).integers(
            3, vocab, n_shared).tolist()
    own = np_rng(seed, _TOKENS_TAG, p.rid).integers(
        3, vocab, p.prompt_len - n_shared).tolist()
    return toks + own


def requests_needed(traffic: dict, seconds: float) -> int:
    """Open loop: every request due before the window closes, plus one
    block, so the load never runs dry inside the window."""
    horizon = float(traffic.get("lead_s", 0)) + float(seconds)
    block = int(traffic.get("block", 32))
    return int(math.ceil(traffic["rate_per_s"] * horizon)) + block
