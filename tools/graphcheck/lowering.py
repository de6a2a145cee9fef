"""AOT lowering of registered graphs under simulated meshes.

No execution, no TPU: `jax.jit(fn, ...).lower(*avals)` traces and lowers
on CPU (`.lower()` on the jit wrapper, StableHLO via `.as_text()`),
`.compile()` runs the XLA pipeline far enough to
expose the partitioned module (collectives, input shardings, memory and
cost analyses) without ever dispatching. Meshes are carved out of the
virtual CPU device set (`--xla_force_host_platform_device_count`), the
same simulation dryrun_multichip uses.
"""

from __future__ import annotations

import dataclasses
import re
import warnings
from typing import Any

import numpy as np

from tools.graphcheck import GraphSpec, mesh_key

_DONATION_REJECT = re.compile(
    r"donated buffers (?:were|was) not usable|buffer donation", re.I)


@dataclasses.dataclass
class FlatArg:
    label: str          # e.g. "state.params['layers']['wq']"
    aval: Any           # shape/dtype carrier
    arg_idx: int        # which top-level argument it flattened out of
    donated: bool


@dataclasses.dataclass
class LoweredGraph:
    spec: GraphSpec
    graph_id: str
    jaxpr: Any
    stablehlo: str
    compiled: Any            # None when compile itself failed
    hlo: str
    flat_in: list            # [FlatArg]
    flat_out_avals: list
    input_shardings: list | None
    donation_warnings: list
    error: str | None = None


def make_mesh(axes: dict | None):
    import jax
    from jax.sharding import Mesh
    if not axes:
        return None
    n = int(np.prod(list(axes.values())))
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"mesh {axes} needs {n} devices, have {len(devs)} — run under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    return Mesh(np.array(devs[:n]).reshape(*axes.values()),
                tuple(axes.keys()))


def _label_args(spec: GraphSpec) -> list:
    import jax
    names = spec.arg_names or tuple(
        f"arg{i}" for i in range(len(spec.args)))
    flat: list[FlatArg] = []
    for i, arg in enumerate(spec.args):
        leaves = jax.tree_util.tree_flatten_with_path(arg)[0]
        for path, leaf in leaves:
            label = names[i] + jax.tree_util.keystr(path)
            flat.append(FlatArg(label, leaf, i,
                                i in spec.donate_argnums))
    return flat


def lower_graph(spec: GraphSpec) -> LoweredGraph:
    import jax
    graph_id = f"{spec.name}@{mesh_key(spec.mesh_axes)}"
    jit_kwargs: dict = {}
    if spec.donate_argnums:
        jit_kwargs["donate_argnums"] = spec.donate_argnums
    if spec.in_shardings is not None:
        jit_kwargs["in_shardings"] = spec.in_shardings
    if spec.out_shardings is not None:
        jit_kwargs["out_shardings"] = spec.out_shardings

    jaxpr = jax.make_jaxpr(spec.fn)(*spec.args)
    donation_warnings: list[str] = []
    compiled = None
    hlo = ""
    input_shardings = None
    error = None
    jit_fn = spec.jit_fn if spec.jit_fn is not None else jax.jit(
        spec.fn, **jit_kwargs)
    # Fingerprints measure a FRESH compile: executables loaded from the
    # persistent compilation cache report different memory/cost estimates
    # than a cold XLA run, which would drift `bytes`/`flops` depending on
    # cache warmth (and graphcheck's own compiles would pollute the cache
    # the test suite shares). Hermetic: cache off for the compile, restored
    # after.
    from jax.experimental.compilation_cache import compilation_cache as _cc
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    # The flag alone is not enough: whether the cache is used is latched
    # on the FIRST jitted computation in the process, so if anything jax
    # ran before graphcheck in this process with the cache on, compiles
    # here still read warm entries and report cache-loaded memory
    # estimates. reset_cache() drops the latch so the disable takes
    # effect; a second reset in the finally re-latches with the restored
    # flag.
    _cc.reset_cache()
    try:
        with warnings.catch_warnings(record=True) as wlog:
            warnings.simplefilter("always")
            lowered = jit_fn.lower(*spec.args)
            stablehlo = lowered.as_text()
            try:
                compiled = lowered.compile()
                hlo = compiled.as_text()
                try:
                    # one sharding a flattened leaf, in flat_in's order
                    # (an argument that is a pytree, as a TrainState,
                    # comes back as a pytree of shardings)
                    input_shardings = jax.tree.leaves(
                        compiled.input_shardings[0])
                except Exception:  # noqa: BLE001 — backend-optional surface
                    input_shardings = None
            except Exception as e:  # noqa: BLE001 — surfaced as a finding
                error = f"{type(e).__name__}: {e}"
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        _cc.reset_cache()
    for w in wlog:
        msg = str(w.message)
        if _DONATION_REJECT.search(msg):
            donation_warnings.append(msg.splitlines()[0])

    flat_out = [v.aval for v in jaxpr.jaxpr.outvars]
    return LoweredGraph(
        spec=spec, graph_id=graph_id, jaxpr=jaxpr, stablehlo=stablehlo,
        compiled=compiled, hlo=hlo, flat_in=_label_args(spec),
        flat_out_avals=flat_out, input_shardings=input_shardings,
        donation_warnings=donation_warnings, error=error)


def lower_all(registry: dict) -> list:
    """Expand every registration across its meshes and lower each."""
    corpus: list[LoweredGraph] = []
    for reg in registry.values():
        for axes in reg.meshes:
            mesh = make_mesh(axes)
            spec = reg.build(mesh)
            spec.mesh = mesh
            spec.mesh_axes = axes
            spec.source = reg.source
            corpus.append(lower_graph(spec))
    return corpus
