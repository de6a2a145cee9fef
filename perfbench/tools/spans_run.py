#!/usr/bin/env python3
"""The benchmark's command with the program's own spans read as well:

    python3 perfbench/tools/spans_run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

`perfbench/run.py` unchanged, and round it the two statements the harness
lacks (no file can bring a reader into `harness/serve_cell.py`, and a PR
that is no `benchmark` PR edits none): `diagnostics.spans_on()` when a
traced run's replica is built, and `program_spans.collect(rep, rec,
trace_dir)` when the cell has run. The result line is run.py's; the
readings lie under `extra` as `prog.*` (PERF.md section 3 says what each
is), the series' medians as `prog.<series>_p50`. The `benchmark` issue
that puts the two statements into serve_cell.py deletes this file.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None) -> int:
    sys.path.insert(0, ROOT)
    from perfbench import run
    from perfbench.harness import program_spans, readers, serve_cell
    from ray_tpu import diagnostics
    made = []
    init0, run0 = serve_cell.Replica.__init__, serve_cell.run

    def init(self, model_cfg, engine_cfg, seed, rec):
        init0(self, model_cfg, engine_cfg, seed, rec)
        if rec.tracing:
            diagnostics.spans_on()
        made.append(self)

    def run_cell(cell, cfg, traffic, cellp, args, rec, t_start, trace_dir):
        out = run0(cell, cfg, traffic, cellp, args, rec, t_start, trace_dir)
        program_spans.collect(made[-1], rec, trace_dir)
        for series, values in rec.samples.items():
            if series.startswith("prog."):
                rec.values[series + "_p50"] = readers.percentile(values, 50)
        return out

    serve_cell.Replica.__init__, serve_cell.run = init, run_cell
    try:
        return run.main(argv)
    finally:
        serve_cell.Replica.__init__, serve_cell.run = init0, run0
        diagnostics.spans_off()


if __name__ == "__main__":
    sys.exit(main())
