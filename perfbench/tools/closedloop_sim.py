#!/usr/bin/env python3
"""What `serve_cell.run_closed_loop` COUNTS in a closed-loop cell, on the
host and in milliseconds: the harness's own request order for a seed
(`traffic.closed_loop_sequence`), its callers, ramp and window, its count
(a prompt's tokens in one lump at the first token delivered, a generated
token when it is handed over), over a model of the engine that has two
numbers from a traced chip run: the decode step (`--step-ms` at an empty
cache plus `--step-us-per-token` a token in flight) and what an admission
stalls the batch for, by prompt bucket (`--admit-ms`). No device number
comes out of it: it says how a cell's `tok_per_s_chip` SPREADS over seeds
before the chip is asked, and which part of the spread the order of the
requests makes (all of it here: two runs of one seed read alike).

Held against the chip (PR 45, `ouro_2_6b-serve-solver`, calls 11 and 12,
seeds 4501100001-8 and 4501200001-6, the defaults below): 10 of 14 seeds
within 1.1 tokens/s of the chip's reading, the other four 3.1-8.6 off (one
request's first token on the other side of a window edge), correlation
0.90, standard deviation 2.35 % of the mean beside the chip's 2.47 %.
Nine further seeds were written down BEFORE their chip runs (calls 13 and
14): the chip read 0.6-1.0 under every one, the requests counted equal.

    python3 perfbench/tools/closedloop_sim.py \
        --workload ouro_2_6b-serve-solver --seeds 4501100001,4501100002
    python3 perfbench/tools/closedloop_sim.py \
        --workload ouro_2_6b-serve-solver --sets 200

`--sets n` draws n sets of six seeds and prints how the driver's spread of
a set (quartiles of the six as a share of the median, the run farthest
from the median left out where that narrows it) is distributed, and the
share of sets at or under half the metric's bound.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.harness import cells, traffic as traffic_mod  # noqa: E402


def simulate(traffic: dict, cellp: dict, buckets, slots: int, seed: int,
             seconds: float, step_ms: float, step_us_per_token: float,
             admit_ms: dict) -> dict:
    """One window: {"tok_per_s": the harness's count over `seconds`,
    "first_tokens": requests whose prompt was counted, "steps": decode
    steps inside the window}."""
    seq = traffic_mod.closed_loop_sequence(
        traffic, seed, int(cellp.get("requests_upper_bound", 4000)))
    stagger = float(cellp.get("client_stagger_s", 0.25))
    t0 = float(cellp["ramp_s"])
    t1 = t0 + seconds
    ready = [i * stagger for i in range(int(cellp["clients"]))]
    waiting: list = []
    active: list[dict] = []
    t, nxt, tokens, firsts, steps = 0.0, 0, 0, 0, 0
    while t < t1:
        ready.sort()
        while ready and ready[0] <= t:      # a caller sends its next request
            ready.pop(0)
            waiting.append(seq[nxt])
            nxt += 1
        admitted = []
        while waiting and len(active) + len(admitted) < slots:
            admitted.append(waiting.pop(0))
        by_bucket: dict = {}
        for r in admitted:
            b = min(x for x in buckets if x >= r.prompt_tokens)
            by_bucket[b] = by_bucket.get(b, 0) + 1
        for b, n in by_bucket.items():      # the batch waits for a prefill
            t += admit_ms[b] * 1e-3 * (1 + 0.7 * (n - 1))
        # the stream swallows the token the prefill sampled: a request's
        # first DELIVERED token is its first decode step's
        active += [{"r": r, "made": 1, "len": r.prompt_tokens + 1,
                    "first": True} for r in admitted]
        if not active:
            t = min(ready) if ready else t + 0.01
            continue
        t += (step_ms + 1e-3 * step_us_per_token
              * sum(a["len"] for a in active)) * 1e-3
        inside = t0 <= t < t1
        steps += inside
        for a in active:
            a["made"] += 1
            a["len"] += 1
            if inside:
                tokens += 1
                if a["first"]:
                    tokens += a["r"].prompt_tokens + 1
                    firsts += 1
            a["first"] = False
        for a in [a for a in active if a["made"] >= a["r"].output_tokens]:
            active.remove(a)
            ready.append(t)                 # its caller sends the next
    return {"tok_per_s": tokens / seconds, "first_tokens": firsts,
            "steps": steps}


def set_spread(values: list) -> float:
    """The driver's spread of one set of runs: first to third quartile
    (statistics.quantiles, n=4) as a share of the median, of all the runs
    or of all but the one farthest from the median, whichever is less."""
    med = statistics.median(values)

    def iqr(v):
        q = statistics.quantiles(v, n=4)
        return q[2] - q[0]
    rest = list(values)
    rest.remove(max(values, key=lambda v: abs(v - med)))
    return min(iqr(values), iqr(rest)) / med


def _pairs(text: str) -> dict:
    return {int(k): float(v) for k, v in
            (p.split(":") for p in text.split(","))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--sets", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--step-ms", type=float, default=38.0)
    ap.add_argument("--step-us-per-token", type=float, default=2.0)
    ap.add_argument("--admit-ms", type=_pairs,
                    default={128: 30.0, 256: 56.0, 512: 105.0})
    ap.add_argument("--benchmark-root", default=ROOT)
    args = ap.parse_args(argv)
    found = cells.load_cell(args.benchmark_root, args.workload)
    traffic, cellp = found["traffic"], found["cellp"]
    if traffic["kind"] != "closed_loop":
        print(f"{args.workload}: traffic kind {traffic['kind']!r}; this "
              f"models closed_loop alone", file=sys.stderr)
        return 2
    engine = {**found["cfg"]["engine"], **cellp.get("engine", {})}
    seconds = args.seconds or float(found["bench"]["run_seconds"])

    def run(seed):
        return simulate(traffic, cellp, sorted(engine["prompt_buckets"]),
                        int(engine["max_slots"]), seed, seconds,
                        args.step_ms, args.step_us_per_token, args.admit_ms)

    for seed in (int(s) for s in args.seeds.split(",") if s):
        print(json.dumps({"seed": seed, **run(seed)}))
    if args.sets:
        bound = next(m["bound"] for m in found["bench"]["end_to_end"]
                     if m["name"] == "tok_per_s_chip")
        spreads = sorted(
            set_spread([run(104729 * (6 * i + j) + 4501300001)["tok_per_s"]
                        for j in range(6)]) for i in range(args.sets))
        print(json.dumps({
            "sets": args.sets,
            "spread_quantiles": {str(q): spreads[min(
                len(spreads) - 1, int(q * len(spreads)))]
                for q in (0.1, 0.25, 0.5, 0.75, 0.9)},
            "half_bound": bound / 2,
            "share_at_or_under_half_bound": sum(
                s <= bound / 2 for s in spreads) / len(spreads)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
