"""Finding a cell's data files by the names in BENCHMARK.json, and the
process environment every entry point of the benchmark sets first."""

from __future__ import annotations

import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def prepare_env(root: str = ROOT) -> None:
    """JAX's persistent compilation cache: where the environment says, else
    ONE fixed path in the checkout (the program's ensure_compile_cache
    takes the same variable). Every program is kept, small ones too: a run
    after the first compiles nothing."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(root, ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def load_cell(root: str, workload: str, rehearsal: bool = False) -> dict:
    """{bench, cell, cfg, traffic, cellp, metrics_dir} of one workload, or
    KeyError where BENCHMARK.json has no such cell."""
    def data(*parts):
        with open(os.path.join(root, *parts)) as f:
            return json.load(f)

    bench = data("BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    cell = cells[workload]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    bdir = bench["paths"][0]
    traffic = data(bdir, "traffic", cell["traffic"] + ".json")
    cellp = data(bdir, "cells", cell["name"] + ".json")
    if rehearsal:
        traffic = {**traffic, **traffic.get("rehearsal_overrides", {})}
        cellp = {**cellp, **cellp.get("rehearsal", {})}
    return {"bench": bench, "cell": cell, "cfg": data(conf["file"]),
            "traffic": traffic, "cellp": cellp,
            "metrics_dir": os.path.join(root, bdir, "metrics")}
