#!/usr/bin/env python3
"""Find the highest rate a chat cell sustains: one replica, one warm-up,
then a window at each rate in turn. Run once when a cell is defined (or
re-defined by a later benchmark PR); the cell then carries 0.8 of the
answer as a number.

    python3 perfbench/tools/sweep.py --workload <cell> --seed <n> \
        --seconds <window> --rates 1.5,2,2.5,3

A rate is SUSTAINED when (a) every request offered inside the window
completed before the bounded drain ended, and (b) requests that arrived
in the last third of the window waited no longer for their first token
than those of the first third: median TTFT of the last third at most 1.5
times that of the first third (a growing queue shows there first), and (c)
no queue stands all through the window: the window's median TTFT is at
most twice that of the lowest rate swept.
Prints one JSON line per rate.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--benchmark-root", default=ROOT)
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    from perfbench.harness import cells
    cells.prepare_env(ROOT)
    import jax

    from perfbench.harness import modelcfg, readers, serve_cell
    from perfbench.harness.record import Record
    found = cells.load_cell(args.benchmark_root, args.workload,
                            args.rehearsal)
    cfg, traffic, cellp = found["cfg"], found["traffic"], found["cellp"]
    if not args.rehearsal and jax.devices()[0].platform != "tpu":
        print("sweep: no TPU here", file=sys.stderr)
        return 3
    model_cfg = modelcfg.model_config(cfg, "open_loop", args.rehearsal)
    engine_cfg = modelcfg.engine_config(cfg, cellp, args.rehearsal)
    jseed = args.seed % (2**31 - 5)
    rec0 = Record(tracing=False)
    rep = serve_cell.Replica(model_cfg, engine_cfg, jseed, rec0)
    try:
        serve_cell.run_waves(
            rep, serve_cell.warm_waves(
                traffic, engine_cfg,
                int(cellp.get("warm_admit_together", 4))),
            jseed, model_cfg.vocab)
        unloaded = None
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            rec = Record(tracing=False)
            rep.rec = rec
            out = serve_cell.run_open_loop(
                rep, rec, traffic, {**cellp, "rate_rps": rate},
                args.seed + i, args.seconds, model_cfg.vocab, None,
                keep_sinks=True)
            sinks = [s for s in out["sinks"] if s.req.counted and s.times]
            third = args.seconds / 3

            def med_ttft(lo, hi):
                return readers.percentile(
                    [(s.times[0] - s.due) * 1e3 for s in sinks
                     if lo <= s.req.due_s < hi], 50)

            first, last = med_ttft(0, third), med_ttft(2 * third,
                                                       args.seconds)
            p = lambda series, q: readers.percentile(  # noqa: E731
                rec.samples.get(series, []), q)
            unloaded = unloaded or p("ttft_ms", 50)
            line = {
                "rate_rps": rate, "offered": out["attempted"],
                "failed_or_undrained": out["failed"],
                "drain_s": rec.values["drain_s"],
                "ttft_p50_first_third_ms": first,
                "ttft_p50_last_third_ms": last,
                "sustained": bool(
                    out["failed"] == 0 and first and last
                    and last <= 1.5 * first
                    and p("ttft_ms", 50) <= 2 * (unloaded or 1e9)),
                "ttft_p50_ms": p("ttft_ms", 50), "ttft_p90_ms": p(
                    "ttft_ms", 90),
                "itl_p50_ms": p("itl_ms", 50), "itl_p99_ms": p("itl_ms", 99),
                "itl_samples": len(rec.samples.get("itl_ms", [])),
                "compiles_in_window": rec.counters.get("new_programs"),
                "hbm_peak_gib": (jax.devices()[0].memory_stats() or {}).get(
                    "peak_bytes_in_use", 0) / 2**30,
            }
            print(json.dumps(line), flush=True)
            rep.sinks.clear()
    finally:
        rep.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
