"""The chip's published peaks, keyed by JAX's `device_kind`.

Copied in spirit from bench_tpu.PEAK_FLOPS (unknown kind = error); the
table itself is data (peaks.json) so a later PR adds a chip by adding to
nothing that measures."""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind: str) -> dict:
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["by_device_kind"]
    if device_kind not in table:
        raise KeyError(
            f"device_kind {device_kind!r} is not in perfbench/harness/"
            f"peaks.json ({sorted(table)}): add its published peaks with "
            f"their source; there is no default")
    return table[device_kind]
