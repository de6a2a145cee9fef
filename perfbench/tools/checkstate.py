#!/usr/bin/env python3
"""The recurrent state itself against the plain recurrence: what the
log-probability check of a cell cannot tell apart (PERF.md section 2).

A sequence's log-probabilities see the state only through 27 blocks and a
hard top-k, where bf16 rounding of the activations already moves them by
more than a state kept in bfloat16 does; and they see a snapshot only as
far as the state remembers. The state can be read where it lies. One
seeded request goes through the serving engine on its normal path (a
chunked prefill that leaves a snapshot at each chunk's end, then decode);
then, for every Mamba layer,

  the slot's row           against the state after prompt + generated[:-1]
  each snapshot's row      against the state after the chunk boundary

by the configuration's plain reference (`states_of`: float32, a position
at a time) on the engine's own parameters. The number compared is, a
layer, the median over its SLOWEST EIGHTH OF HEADS (longest memory 1 /
(softplus(dt_bias) exp(A_log)), read from the parameters) of |S_engine -
S_reference| / |S_reference| (Frobenius, a head). Rounding of the inputs
x, B, dt to bfloat16 moves a head's state by the same share however long
it remembers (the sum and its error both add up incoherently); rounding
of the STATE at every position adds up over the positions a head
remembers, so the slow heads are where a state kept in lower precision
shows. The first Mamba layer decides (the configuration's `check.state`:
its `layer`, and a `limit` a dtype): nothing routes before it, so no
expert flip reaches it. `--fault` gives the REFERENCE what a wrong program would
hold:

  state_bf16      the state kept in bfloat16 between positions
  wrong_snapshot  a snapshot taken one page before its boundary

    python3 perfbench/tools/checkstate.py --workload <cell> --seed <n> \
        --sequences 4 --prompt-tokens 1500 --new-tokens 32 \
        --fault none,state_bf16,wrong_snapshot
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import threading
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FAULTS = ("none", "state_bf16", "wrong_snapshot")


def head_errors(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """got, want [LM, H, P, N] -> [LM, H]: |got - want| / |want| a head."""
    num = np.sqrt(((got.astype(np.float64) - want) ** 2).sum((-1, -2)))
    return num / np.maximum(np.sqrt((want.astype(np.float64) ** 2)
                                    .sum((-1, -2))), 1e-30)


def slow_heads(params, model_cfg) -> list:
    """A Mamba layer -> the indices of its slowest eighth of heads (at
    least one), by the memory its own parameters give a head."""
    out = []
    for kind, lp in zip(model_cfg.layer_pattern, params["layers"]):
        if kind == "M":
            step = np.log1p(np.exp(np.asarray(lp["dt_bias"], np.float64)))
            rate = step * np.exp(np.asarray(lp["a_log"], np.float64))
            out.append(np.argsort(rate)[:max(1, len(rate) // 8)])
    return out


def state_diffs(engine, reference, model_cfg, prompt: list, req,
                fault: str = "none", memo: dict | None = None) -> dict:
    """`req` finished on `engine` (nothing admitted since): a layer, the
    slow heads' median error of its slot's row and of each snapshot its
    chunked prefill left, against `reference.states_of`. `memo` keeps the
    reference's states from one fault of a request to the next."""
    page, chunk = engine.e.page_size, max(engine.e.prompt_buckets)
    fed = list(prompt) + list(req.generated[:-1])
    bounds = list(range(chunk, len(prompt), chunk))
    rows = [req.slot] + [engine.snapshot_row(prompt[:b]) for b in bounds]
    if None in rows:
        raise RuntimeError(f"no row for one of slot, snapshots at {bounds}: "
                           f"{rows}")
    got, _ = engine.state_rows(rows)
    dtype = "bfloat16" if fault == "state_bf16" else model_cfg.ssm_state_dtype
    memo = {} if memo is None else memo
    if dtype not in memo:    # one pass gives the right and the wrong boundary
        at = [len(fed)] + bounds + [b - page for b in bounds]
        memo[dtype] = dict(zip(at, reference.states_of(
            engine.params, dataclasses.replace(model_cfg,
                                               ssm_state_dtype=dtype),
            fed, at)))
    back = page if fault == "wrong_snapshot" else 0
    want = [memo[dtype][n] for n in [len(fed)] + [b - back for b in bounds]]
    names = ["slot"] + [f"snapshot_{b}" for b in bounds]
    slow = slow_heads(engine.params, model_cfg)
    return {"rows": {name: [float(np.median(e[h])) for e, h
                            in zip(head_errors(g, w), slow)]
                     for name, g, w in zip(names, got, want)},
            "tokens_fed": len(fed), "finite": bool(np.isfinite(got).all())}


def judge(d: dict, rule: dict, dtype: str) -> dict:
    """Every compared row's error at the deciding layer within the limit
    of the configuration's `check.state` for the model's dtype."""
    layer, limit = int(rule["layer"]), float(rule["limit"][dtype])
    worst = max(errs[layer] for errs in d["rows"].values())
    return {"ok": bool(d["finite"] and worst <= limit), "worst": worst,
            "limit": limit, "layer": layer}


def run_one(rep, ids: list, n_new: int):
    """One request through the replica's pump, as serve_cell's check."""
    from perfbench.harness import serve_cell, traffic as traffic_mod
    done = threading.Event()
    sink = rep.submit(traffic_mod.Req(-1, 0.0, len(ids), n_new, False), ids,
                      time.perf_counter(), on_done=lambda s: done.set())
    if not done.wait(serve_cell.WAIT_LIMIT_S):
        raise RuntimeError("the request never finished")
    rep.sinks.clear()
    return sink.engine_req


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--sequences", type=int, default=4)
    ap.add_argument("--prompt-tokens", type=int, default=1500)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--fault", default="none",
                    help="comma-separated, of: " + ", ".join(FAULTS))
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--benchmark-root", default=ROOT)
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    from perfbench.harness import cells
    cells.prepare_env(ROOT)
    import jax

    from perfbench.harness import modelcfg, serve_cell
    from perfbench.harness import traffic as traffic_mod
    from perfbench.harness.record import Record
    found = cells.load_cell(args.benchmark_root, args.workload,
                            args.rehearsal)
    cfg, traffic, cellp = found["cfg"], found["traffic"], found["cellp"]
    if not args.rehearsal and jax.devices()[0].platform != "tpu":
        print("checkstate: no TPU here", file=sys.stderr)
        return 3
    model_cfg = modelcfg.model_config(cfg, traffic["kind"], args.rehearsal)
    engine_cfg = modelcfg.engine_config(cfg, cellp, args.rehearsal)
    reference = cells.load_reference(args.benchmark_root, cfg)
    jseed = args.seed % (2**31 - 5)
    rep = serve_cell.Replica(model_cfg, engine_cfg, jseed,
                             Record(tracing=False))
    try:
        for k in range(args.sequences):
            ids = traffic_mod.prompt_ids(jseed, 10**6 + k,
                                         args.prompt_tokens, model_cfg.vocab)
            req = run_one(rep, ids, args.new_tokens)
            memo = {}
            for fault in args.fault.split(","):
                d = state_diffs(rep.engine, reference, model_cfg, ids, req,
                                fault, memo)
                print(json.dumps({
                    "seed": args.seed, "sequence": k, "fault": fault,
                    "verdict": judge(d, cfg["check"]["state"],
                                     model_cfg.dtype), **d}), flush=True)
    finally:
        rep.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
