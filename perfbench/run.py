#!/usr/bin/env python3
"""The benchmark's command.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data the harness finds by the names in
BENCHMARK.json: configs/<config>.json, traffic/<traffic>.json,
cells/<cell>.json, metrics/<metric>.json, kernels/<kernel>.py. The last
line of stdout is the result object. No chip, too few chips or an unknown
device kind is a failure (exit code != 0, no result), never a CPU run;
`--rehearsal` (tests only) runs the same flow at the configuration's tiny
rehearsal sizes and prints its numbers under a key of their own, never as
metrics.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT_DIR = os.path.join(ROOT, ".perfbench_out")


def _proc_start_wall() -> float:
    try:
        import psutil
        return psutil.Process().create_time()
    except Exception:  # noqa: BLE001 — psutil is optional
        return time.time()


def metrics_for(bench: dict, cell: str, group: str) -> list[dict]:
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def main(argv=None) -> int:
    t_start = _proc_start_wall()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--debug-dir", default=None)
    ap.add_argument("--benchmark-root", default=ROOT,
                    help="where BENCHMARK.json and the data files live "
                    "(tests point this at a copy with a cell added)")
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    from perfbench.harness import cells
    cells.prepare_env(ROOT)
    try:
        found = cells.load_cell(args.benchmark_root, args.workload,
                                args.rehearsal)
    except KeyError:
        print(f"perfbench: no workload {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    bench, cell, cfg = found["bench"], found["cell"], found["cfg"]
    traffic, cellp = found["traffic"], found["cellp"]

    from perfbench.harness import readers
    from perfbench.harness.record import Record
    rec = Record(tracing=bool(args.trace))
    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(OUT_DIR, "trace", cell["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)

    if traffic["kind"] == "train_job":
        from perfbench.harness import train_cell
        out = train_cell.run(cell, cfg, traffic, cellp, args, rec, t_start,
                             trace_dir, OUT_DIR)
        device = dict(out["device"])
    else:
        import jax

        from perfbench.harness import serve_cell, tracered
        from perfbench.harness.peaks import peaks_for
        dev = jax.devices()[0]
        n = jax.device_count()
        if not args.rehearsal and (dev.platform != "tpu"
                                   or n < cell["chips"]):
            print(f"perfbench: found {n} x {dev.platform}; the cell needs "
                  f"{cell['chips']} TPU chip(s)", file=sys.stderr)
            return 3
        if dev.platform == "tpu":
            rec.context["peaks"] = peaks_for(dev.device_kind)
        out = serve_cell.run(cell, cfg, traffic, cellp, args, rec, t_start,
                             trace_dir)
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": n}
        if trace_dir:
            raw = tracered.read_xplane(trace_dir)
            rec.trace = tracered.reduce_trace(raw)
            if args.debug_dir:
                os.makedirs(args.debug_dir, exist_ok=True)
                with open(os.path.join(args.debug_dir,
                                       "trace_summary.json"), "w") as f:
                    json.dump(tracered.summarize(
                        tracered.read_xplane(trace_dir, full_names=True),
                        40, "custom|kernel|paged|flash"), f, indent=1)
                small = tracered.cut(raw, 1.0, 0.15)
                with open(os.path.join(args.debug_dir,
                                       "trace_small.json"), "w") as f:
                    json.dump({"trace": small, "expect": {
                        k: v for k, v in tracered.reduce_trace(
                            small).items()
                        if k in ("window_s", "busy_s", "collective_s")}}, f)
    device["memory_peak_bytes"] = out["memory_peak_bytes"]

    group = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    mdir = found["metrics_dir"]
    for m in metrics_for(bench, cell["name"], group):
        v = readers.read_metric(m["name"], rec, mdir)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        elif group == "end_to_end":
            print(f"perfbench: end-to-end metric {m['name']} has no value",
                  file=sys.stderr)
            return 4
    result = {
        "correct": out["correct"], "attempted": out["attempted"],
        "failed": out["failed"], "metrics": metrics, "device": device,
        "check": out["check"],
        "compiles_in_window": rec.counters.get("new_programs"),
        "samples": {k: len(v) for k, v in rec.samples.items()},
        "extra": {k: rec.values[k] for k in sorted(rec.values)
                  if k not in metrics},
        # where the percentiles sit in their distributions (for PERF.md)
        "quantiles": {
            series: {str(q): readers.percentile(rec.samples[series], q)
                     for q in qs}
            for series, qs in (("itl_ms", (90, 94, 96, 98, 99, 99.5)),
                               ("ttft_ms", (25, 50, 75, 90)))
            if rec.samples.get(series)},
    }
    if args.trace and rec.trace is None and not args.rehearsal:
        print("perfbench: the trace shows no operation on a device",
              file=sys.stderr)
        return 5
    if args.trace and rec.trace is not None:
        device["busy_s"] = rec.trace["busy_s"]
        device["window_s"] = rec.trace["window_s"]
        result["breakdown"] = {"device_ops": rec.trace["device_ops"][:10],
                               "idle_gaps": rec.trace["idle_gaps"][:10]}
    if args.rehearsal:
        # Not device numbers: nothing of a rehearsal goes under `metrics`.
        result["rehearsal_only_not_device_numbers"] = result.pop("metrics")
        result["metrics"] = {}
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
