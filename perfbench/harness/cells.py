"""Finding a cell's data files, and the modules a configuration's file names
(its plain reference, its flop count, a kernel's cost function), by the
names in BENCHMARK.json; and the process environment every entry point of
the benchmark sets first."""

from __future__ import annotations

import importlib.util
import json
import os
import re
import sys

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
    KeyError where BENCHMARK.json has no such cell. `cfg` is the
    configuration's file as read plus one key, `_file`: the path it was
    read from, for the errors that name it."""
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
    return {"bench": bench, "cell": cell,
            "cfg": {**data(conf["file"]), "_file": conf["file"]},
            "traffic": traffic, "cellp": cellp,
            "metrics_dir": os.path.join(root, bdir, "metrics")}


def load_module(path: str):
    """The Python file at `path`, imported under a name made from the path:
    one file is imported once a process, and two files of one name under
    two benchmark roots are two modules."""
    path = os.path.abspath(path)
    name = "perfbench_file_" + re.sub(r"\W", "_", path)
    if name in sys.modules:
        return sys.modules[name]
    if not os.path.isfile(path):
        raise FileNotFoundError(f"perfbench: no module at {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod     # a dataclass defined there looks itself up
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


def load_reference(root: str, cfg: dict):
    """The configuration's plain reference: the module its file names under
    `reference`, a path from the benchmark root `load_cell` was given (a
    copy under --benchmark-root brings its own). perfbench/README.md has
    the interface: logprobs_of, mean_loss."""
    if not cfg.get("reference"):
        raise KeyError(
            f"{cfg.get('_file', 'the configuration')}: no \"reference\" key; "
            f"a configuration names the file that decides `correct`")
    return load_module(os.path.join(root, cfg["reference"]))


def load_flops(root: str, cfg: dict):
    """train_flops_per_token(model, seq) of the module the configuration's
    file names under `flops`, else the decoders' count in modelcfg."""
    if cfg.get("flops"):
        return load_module(
            os.path.join(root, cfg["flops"])).train_flops_per_token
    from perfbench.harness import modelcfg
    return modelcfg.train_flops_per_token
