"""The trace -> metrics reduction, on a hand-made trace whose answers are
known and on a small trace recorded on the chip."""

import json
import os

import pytest

from perfbench.harness import readers, tracered
from perfbench.harness.record import Record

HERE = os.path.dirname(os.path.abspath(__file__))


def _trace():
    ms = 1e6
    ops = [["fusion.1", 0 * ms, 2 * ms], ["while.2", 3 * ms, 4 * ms],
           ["fusion.1", 3 * ms, 1 * ms], ["all-gather.5", 5 * ms, 1 * ms],
           ["paged_kernel", 8 * ms, 1 * ms]]
    host = [["bench.window", 0, 10 * ms], ["bench.step_decode", 1 * ms, 2.5 * ms],
            ["bench.step_admit", 7 * ms, 0.9 * ms], ["other", 0, 1]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": [["jit_x", 0, 10 * ms]]}]},
        {"name": "/host:CPU", "lines": [{"name": "pump", "events": host}]}]}


def test_busy_idle_self_times_and_gap_owners():
    r = tracered.reduce_trace(_trace())
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.007)          # 2 + 4 + 1 ms
    assert r["n_devices"] == 1
    # while.2 keeps what its children (1 + 1 ms) do not cover
    assert r["op_self_s"]["while.2"] == pytest.approx(0.002)
    assert r["op_self_s"]["fusion.1"] == pytest.approx(0.003)
    assert r["op_count"]["fusion.1"] == 2
    assert r["collective_s"] == pytest.approx(0.001)
    assert sum(r["op_self_s"].values()) == pytest.approx(r["busy_s"])
    gaps = dict(r["idle_gaps"])
    assert gaps["bench.step_decode"] == pytest.approx(0.001)   # 2..3 ms
    assert gaps["bench.step_admit"] == pytest.approx(0.001)    # 7..8 ms
    assert gaps["between_spans"] == pytest.approx(0.001)       # 9..10 ms
    assert r["device_ops"][0][0] == "fusion.1"


def test_readers_on_the_reduced_trace():
    rec = Record(tracing=True)
    rec.trace = tracered.reduce_trace(_trace())
    assert readers.REDUCERS["device_idle"]({}, rec) == pytest.approx(30.0)
    assert readers.REDUCERS["collective_exposed"]({}, rec) == \
        pytest.approx(10.0)
    share = readers.REDUCERS["device_share"]({"op_pattern": "paged"}, rec)
    assert share == pytest.approx(100 / 7)
    rec.samples["x"] = [1.0, 2.0, 3.0, 4.0]
    assert readers.percentile(rec.samples["x"], 50) == pytest.approx(2.5)
    assert readers.REDUCERS["percentile"]({"series": "y", "q": 50},
                                          rec) is None


def test_no_device_operation_reduces_to_nothing():
    t = _trace()
    t["planes"] = t["planes"][1:]
    assert tracered.reduce_trace(t) is None


def test_recorded_chip_trace():
    path = os.path.join(HERE, "data", "trace_small.json")
    if not os.path.exists(path):
        pytest.skip("no recorded trace in this checkout")
    with open(path) as f:
        blob = json.load(f)
    r = tracered.reduce_trace(blob["trace"])
    for key, want in blob["expect"].items():
        assert r[key] == pytest.approx(want, rel=1e-6), key
    secs, n = tracered.seconds_matching(r, "^_paged_decode")
    assert n == blob["expect_paged"]["events"] > 0     # 12 layers a step
    assert secs == pytest.approx(blob["expect_paged"]["seconds"], rel=1e-6)
    assert r["device_ops"][0][0] == blob["expect_top_op"]
    assert sorted(g[0] for g in r["idle_gaps"]) == blob["expect_gap_owners"]
    assert 0 < r["busy_s"] <= r["window_s"]
    assert sum(r["op_self_s"].values()) == pytest.approx(
        r["busy_s"] * r["n_devices"], rel=1e-6)
