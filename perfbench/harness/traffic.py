"""The one general traffic generator: a mix is a data file of parameters.

Every run of a cell offers the SAME multiset of requests; `--seed` only
permutes which request arrives when (and draws the token ids). Lengths are
a fixed grid of quantiles of the distribution the file names, so no run
draws a heavier tail than another.

Kinds (the `kind` key of a traffic file):
  open_loop    arrivals on a schedule at the cell's fixed `rate_rps`:
               a fixed grid of exponential gaps, ordered by the seed and
               scaled so that exactly round(rate * seconds) requests fall
               inside the window; a
               ramp before it and a cool-down after it at the same rate
               (neither counted).
  closed_loop  `clients` callers, each sending its next request when the
               previous one completed; requests come from the grid in a
               seeded order, cycled.
  train_job    a fixed token batch per step, tokens from the seed.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import statistics

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
TRAFFIC_DIR = os.path.join(os.path.dirname(_HERE), "traffic")


def load_traffic(name: str, directory: str = TRAFFIC_DIR) -> dict:
    path = os.path.join(directory, name + ".json")
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Req:
    index: int           # position in the offered order
    due_s: float         # seconds from window open (negative: ramp)
    prompt_tokens: int
    output_tokens: int
    counted: bool        # arrived inside the window


def length_grid(spec: dict, n: int) -> list[int]:
    """n lengths at the mid-quantiles (i + 0.5) / n of the distribution,
    clipped to [min, max]: the same list for every seed."""
    lo, hi = int(spec["min"]), int(spec["max"])
    qs = [(i + 0.5) / n for i in range(n)]
    if spec["dist"] == "lognormal_grid":
        mu, sigma = math.log(spec["median"]), float(spec["sigma"])
        nd = statistics.NormalDist()
        vals = [math.exp(mu + sigma * nd.inv_cdf(q)) for q in qs]
    elif spec["dist"] == "uniform_grid":
        vals = [lo + q * (hi - lo) for q in qs]
    elif spec["dist"] == "fixed":
        vals = [spec["value"]] * n
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return [int(min(hi, max(lo, round(v)))) for v in vals]


def request_pairs(traffic: dict, n: int) -> list[tuple[int, int]]:
    """The cell's multiset of (prompt, output) lengths. Pairing is fixed by
    the traffic file's own `pairing_seed`, never by --seed."""
    prompts = length_grid(traffic["prompt_tokens"], n)
    outputs = length_grid(traffic["output_tokens"], n)
    order = np.random.default_rng(
        int(traffic.get("pairing_seed", 0))).permutation(n)
    pairs = [(prompts[i], outputs[int(j)]) for i, j in enumerate(order)]
    cap = traffic.get("max_total_tokens")
    if cap:
        bad = [p for p in pairs if p[0] + p[1] > cap]
        if bad:
            raise ValueError(f"pairs over max_total_tokens={cap}: {bad[:3]}")
    return pairs


def _arrivals(rng, n: int, start_s: float, length_s: float) -> list[float]:
    """n arrival times inside (start, start + length). The n + 1 gaps are
    the mid-quantiles of an exponential distribution, scaled to fill the
    span: every seed offers the SAME multiset of gaps, so the same number
    of near-coincident arrivals (which are what two requests admitted in
    one step, and a queue, are made of); the seed only orders them."""
    if n <= 0:
        return []
    grid = np.array([-math.log(1.0 - (i + 0.5) / (n + 1))
                     for i in range(n + 1)])
    gaps = grid[rng.permutation(n + 1)]
    t = np.cumsum(gaps)
    return [start_s + float(x) * length_s / float(t[-1]) for x in t[:-1]]


def open_loop_schedule(traffic: dict, rate_rps: float, seconds: float,
                       ramp_s: float, cooldown_s: float,
                       seed: int) -> list[Req]:
    """Ramp, window and cool-down arrivals in due order. Exactly
    round(rate * seconds) of them are `counted`, and their (prompt,
    output) pairs are the same multiset for every seed."""
    n = int(round(rate_rps * seconds))
    pairs = request_pairs(traffic, n)
    rng = np.random.default_rng([int(seed), 0x7AFF1C])
    window = [pairs[int(i)] for i in rng.permutation(n)]
    n_ramp = int(round(rate_rps * ramp_s))
    n_cool = int(round(rate_rps * cooldown_s))
    ramp = [pairs[int(i)] for i in rng.integers(0, n, n_ramp)]
    cool = [pairs[int(i)] for i in rng.integers(0, n, n_cool)]
    out: list[Req] = []
    for seg, start, length, counted in (
            (ramp, -ramp_s, ramp_s, False), (window, 0.0, seconds, True),
            (cool, seconds, cooldown_s, False)):
        for due, (p, o) in zip(_arrivals(rng, len(seg), start, length), seg):
            out.append(Req(len(out), due, p, o, counted))
    return out


def closed_loop_sequence(traffic: dict, seed: int, count: int) -> list[Req]:
    """`count` requests for the clients to pull in order: the grid of
    `grid_size` pairs, permuted anew by the seed each time it is used up."""
    g = int(traffic["grid_size"])
    pairs = request_pairs(traffic, g)
    rng = np.random.default_rng([int(seed), 0xC105ED])
    out: list[Req] = []
    while len(out) < count:
        for i in rng.permutation(g):
            p, o = pairs[int(i)]
            out.append(Req(len(out), 0.0, p, o, False))
    return out[:count]


def prompt_ids(seed: int, index: int, n_tokens: int, vocab: int) -> list:
    """Token ids of request `index`: uniform over the vocabulary, so no two
    requests share a 128-token page by accident."""
    rng = np.random.default_rng([abs(int(seed)), 0x1D5, abs(int(index))])
    return rng.integers(0, vocab, n_tokens).tolist()
