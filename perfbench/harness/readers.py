"""The fixed set of reducers a metric file (metrics/<name>.json) may name.
A reader that finds nothing to read returns None and the harness leaves
the metric out of the line.

  {"reducer": "percentile", "series": "itl_ms", "q": 50}
  {"reducer": "counter", "counter": "preemptions"}
  {"reducer": "value", "value": "tok_per_s_chip"}
  {"reducer": "device_share", "op_pattern": "..."}      % of device busy time
  {"reducer": "roofline", "op_pattern": "...", "kernel": "paged_attn"}
  {"reducer": "device_idle"}                             % of traced window
  {"reducer": "collective_exposed"}                      % of traced window
"""

from __future__ import annotations

import json
import math
import os

from perfbench.harness import cells, tracered

_HERE = os.path.dirname(os.path.abspath(__file__))
METRICS_DIR = os.path.join(os.path.dirname(_HERE), "metrics")


def percentile(values: list, q: float) -> float | None:
    """Linear-interpolated percentile (numpy's default), no numpy needed."""
    if not values:
        return None
    v = sorted(values)
    pos = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def _percentile(spec, rec):
    return percentile(rec.samples.get(spec["series"], []), spec["q"])


def _counter(spec, rec):
    return rec.counters.get(spec["counter"])


def _value(spec, rec):
    return rec.values.get(spec["value"])


def _device_share(spec, rec):
    t = rec.trace
    if not t or t["busy_s"] <= 0:
        return None
    secs, _n = tracered.seconds_matching(t, spec["op_pattern"])
    return 100.0 * secs / (t["busy_s"] * t["n_devices"])


def _device_idle(spec, rec):
    t = rec.trace
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def _collective_exposed(spec, rec):
    t = rec.trace
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * t["collective_s"] / t["window_s"]


def _roofline(spec, rec):
    """Least time the chip could take for what the kernel's events did in
    the traced window (operations and bytes from shapes, by the cost
    function kernels/<kernel>.py of the benchmark root the cell was loaded
    from) over the time they took."""
    t = rec.trace
    if not t:
        return None
    secs, n = tracered.seconds_matching(t, spec["op_pattern"])
    if secs <= 0 or n == 0:
        return None
    mod = cells.load_module(os.path.join(spec["kernels_dir"],
                                         spec["kernel"] + ".py"))
    cost = mod.cost({**rec.context, "op_count": t["op_count"],
                     "n_events": n, "op_pattern": spec["op_pattern"]})
    if cost is None:
        return None
    flops, nbytes = cost
    peaks = rec.context["peaks"]
    least = max(flops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / secs


REDUCERS = {
    "percentile": _percentile, "counter": _counter, "value": _value,
    "device_share": _device_share, "device_idle": _device_idle,
    "collective_exposed": _collective_exposed, "roofline": _roofline,
}


def load_metric(name: str, directory: str = METRICS_DIR) -> dict:
    with open(os.path.join(directory, name + ".json")) as f:
        return json.load(f)


def read_metric(name: str, rec, directory: str = METRICS_DIR):
    # a kernel's cost function lies beside the metrics, under the same root
    spec = {"kernels_dir": os.path.join(os.path.dirname(directory),
                                        "kernels"),
            **load_metric(name, directory)}
    out = REDUCERS[spec["reducer"]](spec, rec)
    if out is None or (isinstance(out, float) and not math.isfinite(out)):
        return None
    return float(out)
