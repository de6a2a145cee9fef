#!/usr/bin/env python3
"""How far the program's log-probabilities lie from the plain reference's,
over many seeded sequences on one replica: the data the correctness rule
of a configuration is set from (PERF.md section 2). Run when a
configuration is added whose function is not continuous (hard top-k
routing), or when a later benchmark PR revisits the rule.

    python3 perfbench/tools/checkdist.py --workload <cell> --seed <n> \
        --sequences 30 --new-tokens 64

Prints one JSON line per sequence: every generated token's absolute
difference, the reference's router margin at its position, and the verdict
of the configuration's rule (harness/serve_cell.judge).

`--fault <name>` hands the REFERENCE something else than the program had,
which is what a wrong program looks like from the comparison's side: the
rule has to refuse every such sequence.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _replace(c, **kw):
    import dataclasses
    return dataclasses.replace(c, **kw)


FAULTS = {
    # one expert per token where the program takes two
    "top1": lambda c, ids: (_replace(c, moe_top_k=1), ids),
    # another rotary base
    "rope_base": lambda c, ids: (_replace(c, rope_theta=1e4), ids),
    # the last prompt token is another token (a wrong token)
    "last_token": lambda c, ids: (c, ids[:-1] + [(ids[-1] + 1) % c.vocab]),
    # the first page of the context is lost (128 of 200 tokens)
    "lost_page": lambda c, ids: (c, ids[128:]),
    # one token in the middle of the context is another token
    "mid_token": lambda c, ids: (
        c, ids[:100] + [(ids[100] + 1) % c.vocab] + ids[101:]),
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--sequences", type=int, default=30)
    ap.add_argument("--prompt-tokens", type=int, default=200)
    ap.add_argument("--new-tokens", type=int, default=64)
    ap.add_argument("--fault", default="none",
                    help="comma-separated, of: none, " + ", ".join(
                        sorted(FAULTS)))
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--benchmark-root", default=ROOT)
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    from perfbench.harness import cells
    cells.prepare_env(ROOT)
    import jax

    from perfbench.harness import modelcfg, serve_cell
    from perfbench.harness.record import Record
    found = cells.load_cell(args.benchmark_root, args.workload,
                            args.rehearsal)
    cfg, traffic, cellp = found["cfg"], found["traffic"], found["cellp"]
    if not args.rehearsal and jax.devices()[0].platform != "tpu":
        print("checkdist: no TPU here", file=sys.stderr)
        return 3
    model_cfg = modelcfg.model_config(cfg, traffic["kind"], args.rehearsal)
    engine_cfg = modelcfg.engine_config(cfg, cellp, args.rehearsal)
    reference = cells.load_reference(args.benchmark_root, cfg)
    jseed = args.seed % (2**31 - 5)
    tol = modelcfg.LOGPROB_TOL[model_cfg.dtype]
    rep = serve_cell.Replica(model_cfg, engine_cfg, jseed,
                             Record(tracing=False))
    try:
        for fault in args.fault.split(","):
            for k in range(args.sequences):
                d = serve_cell.reference_diffs(
                    rep, reference, model_cfg, jseed, 10**6 + k,
                    args.prompt_tokens, args.new_tokens,
                    None if fault == "none" else FAULTS[fault])
                verdict = serve_cell.judge(d, tol, cfg.get("check"))
                print(json.dumps({"seed": args.seed, "sequence": k,
                                  "fault": fault, "verdict": verdict,
                                  **d}), flush=True)
    finally:
        rep.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
