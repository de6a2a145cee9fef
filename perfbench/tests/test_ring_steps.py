"""The wrapper's series rebuilt from the ring (harness/ring_steps.py), on
pump turns written by hand, whose answers are worked in the comments.
tests/test_spans.py holds the same functions to the wrapper's own lists on
a run of a replica; `data/ring_small.json` is a slice recorded on the chip
(the module runs of the first device and the program's spans beside them,
`qwen2_7b-serve-chat`, PR 58's traced run)."""

import json
import os

import pytest

from perfbench.tests.test_program_spans import _span_records

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000
P = "ray_tpu."


def _records():
    """A busy stretch of four turns and a turn of the next (times in ms).
    Turn 1 admits two requests and dispatches step 1, nothing to return;
    turn 2 dispatches step 2 ahead and returns step 1's two tokens, both
    the first their streams carry; turn 3 admits a third request behind
    step 2, dispatches step 3 over three slots and returns two tokens;
    turn 4 returns step 3's three tokens (one a first) and dispatches
    nothing: the requests are over. Turn 5, later, admits a chunk of a
    long prompt (no slot, no decode step)."""
    rows = [
        (1, 0, "pump.step", 0, 10, {}),
        (2, 1, "engine.admit", 1, 6, {"rows": 48, "fenced": 0}),
        (3, 2, "engine.admit.prefill", 2, 3,
         {"bucket": 16, "tokens": (10,), "prefix": (0,)}),
        (4, 2, "engine.admit.prefill", 3, 4,
         {"bucket": 32, "tokens": (20,), "prefix": (16,)}),
        (5, 1, "engine.decode", 7, 9,
         {"step": 1, "ahead": 0, "lengths": (10, 36)}),
        (6, 0, "pump.fanout", 10.1, 10.2, {"tokens": 0, "firsts": 0}),
        (7, 0, "pump.step", 11, 20, {}),
        (8, 7, "engine.decode", 11.5, 12.5,
         {"step": 2, "ahead": 1, "lengths": (11, 37)}),
        (9, 7, "engine.land", 12.5, 19.5, {}),
        (10, 9, "engine.land.fence", 12.6, 19, {}),
        (11, 0, "pump.fanout", 20.1, 20.2, {"tokens": 2, "firsts": 2}),
        (12, 0, "pump.step", 21, 40, {}),
        (13, 12, "engine.admit", 21.5, 30, {"rows": 64, "fenced": 0}),
        (14, 13, "engine.admit.prefill", 22, 24,
         {"bucket": 16, "tokens": (9, 12, 16), "prefix": (0, 0, 0)}),
        (15, 12, "engine.decode", 31, 32,
         {"step": 3, "ahead": 1, "lengths": (12, 38, 9)}),
        (16, 12, "engine.land", 32, 39.5, {}),
        (17, 16, "engine.land.fence", 32.1, 39, {}),
        (18, 0, "pump.fanout", 40.1, 40.2, {"tokens": 2, "firsts": 0}),
        (19, 0, "pump.step", 41, 50, {}),
        (20, 19, "engine.land", 41.5, 49, {}),
        (21, 20, "engine.land.fence", 41.6, 48.5, {}),
        (22, 0, "pump.fanout", 50.1, 50.2, {"tokens": 3, "firsts": 1}),
        (23, 0, "pump.idle", 50.3, 70, {}),
        (24, 0, "pump.step", 70.5, 80, {}),
        (25, 24, "engine.admit", 71, 79, {"rows": 32, "fenced": 0}),
        (26, 25, "engine.admit.prefill", 72, 78,
         {"bucket": 32, "tokens": (32,), "prefix": (0,)}),
        (27, 0, "pump.fanout", 80.1, 80.2, {"tokens": 0, "firsts": 0}),
    ]
    return [(i, p, P + n, a * MS, b * MS, "pump", at)
            for i, p, n, a, b, at in rows]


def test_steps_by_hand():
    from perfbench.harness import ring_steps as rs
    got = rs.steps(_records())
    # one a turn that DISPATCHED a decode step: turns 1, 2 and 3
    assert [(s["t0"], s["t1"]) for s in got] == [
        (0.0, 0.010), (0.011, 0.020), (0.021, 0.040)]
    assert [s["lengths"] for s in got] == [[10, 36], [11, 37], [12, 38, 9]]
    assert [s["active"] for s in got] == [2, 2, 3]
    assert [s["step"] for s in got] == [1, 2, 3]
    # the gaps a turn closed: tokens less the first ones of a stream
    assert [(s["tokens"], s["firsts"], s["gaps"]) for s in got] == [
        (0, 0, 0), (2, 2, 0), (2, 0, 2)]
    assert [s["admit_worked"] for s in got] == [True, False, True]
    # 7 slots over 3 steps of 4
    assert rs.batch_occupancy_pct(got, 4) == pytest.approx(100 * 7 / 12)
    # both gaps were closed by the turn that held the second admission
    assert rs.admit_gap_share_pct(got) == 100.0
    assert rs.admit_gap_share_pct(got[:2]) is None      # no gap at all
    assert rs.batch_occupancy_pct([], 4) is None


def test_the_wrappers_two_series_by_hand():
    from perfbench.harness import ring_steps as rs
    recs = _records()
    # the turns that returned a token: 2 (its start -> its step's end),
    # 3 (its admission's end -> its step's end) and 4
    assert rs.decode_step_ms(recs) == pytest.approx([9.0, 10.0, 9.0])
    # every admission that prefilled, no threshold: turns 1, 3 and 5;
    # an `engine.admit` span that found no slot free (`rows` 0) is none
    assert rs.admit_work_ms(recs) == pytest.approx([5.0, 8.5, 8.0])
    full = (99, 7, P + "engine.admit", 11.1 * MS, 11.13 * MS, "pump",
            {"rows": 0, "fenced": 0})
    assert len(rs.admit_work_ms(recs + [full])) == 3
    # nor does it make its turn one that held an admission
    assert [s["admit_worked"] for s in rs.steps(recs + [full])] == [
        True, False, True]


def test_prefills_by_hand():
    from perfbench.harness import ring_steps as rs
    pre = rs.prefills(_records())
    assert pre == [
        {"bucket": 16, "tokens": [10], "prefix": [0]},
        {"bucket": 32, "tokens": [20], "prefix": [16]},
        {"bucket": 16, "tokens": [9, 12, 16], "prefix": [0, 0, 0]},
        {"bucket": 32, "tokens": [32], "prefix": [0]}]
    # requests x bucket is `engine.admit`'s `rows`, admission by admission
    assert [rs.prefill_rows(pre[a:b])["request_rows"]
            for a, b in ((0, 2), (2, 3), (3, 4))] == [48, 48, 32]
    # three requests run as a batch of four: 16 more rows on the device
    assert rs.prefill_rows(pre) == {
        "tokens": 99, "request_rows": 128, "padded_rows": 144}
    assert rs.prefill_real_rows_pct(pre) == pytest.approx(100 * 99 / 128)
    assert rs.prefill_real_rows_pct([]) is None


def test_a_ring_without_the_attributes_yields_nothing():
    """A parent commit's spans (test_program_spans' records: `step` and
    `ahead` on a decode span, nothing on a fan-out or a prefill)."""
    from perfbench.harness import ring_steps as rs
    old = _span_records()
    assert rs.steps(old) == [] and rs.decode_step_ms(old) == []
    assert rs.prefills(old) == []
    assert len(rs.admit_work_ms(old)) == 2      # the spans were there
    assert rs.steps([]) == [] and rs.host_lead_ns({"planes": []}, []) is None


def _device(runs):
    return {"planes": [
        {"name": "/host:CPU", "lines": []},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [["fusion.1", 0, 5]]},
            {"name": "XLA Modules", "events": [
                [n, a * MS, (b - a) * MS] for n, a, b in runs]}]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_decode_paged(9)", 0, 1]]}]}]}


def test_host_lead_by_hand():
    """Five steps on the device (a decode run, the key's split, then its
    sampler), an admission's programs between the second and the third,
    the fifth cut by the trace's end; the fences of steps 2, 3 and 4 end
    1.0, 2.0 and 1.5 ms after their samplers on the trace's clock. The
    fence at 3 ms fetched a step from before the trace, and step 1's own
    is the first step's: neither is matched."""
    from perfbench.harness import ring_steps as rs
    trace = _device([
        ("jit_decode_paged(1)", 0, 9), ("jit__threefry_split(7)", 9, 9.1),
        ("jit_sample(2)", 9.1, 9.5),
        ("jit_decode_paged(1)", 9.5, 18.5), ("jit_sample(2)", 18.5, 19),
        ("jit_prefill_batch(3)", 19, 26), ("jit_sample(4)", 26, 26.2),
        ("jit_merge_tokens(5)", 26.2, 26.3),
        ("jit_decode_paged(1)", 26.3, 35.3), ("jit_sample(2)", 35.3, 36),
        ("jit_decode_paged(1)", 36, 45), ("jit_sample(2)", 45, 45.5),
        ("jit_decode_paged(1)", 45.5, 50)])
    spans = [(P + "engine.land.fence", a * MS, b * MS)
             for a, b in ((1, 3), (4, 11), (12, 20), (30, 38), (39, 47))]
    spans.append((P + "pump.step", 0, 50 * MS))
    got = rs.host_lead_ns(trace, spans)
    assert got == {"steps": 3, "least_ns": 1.0 * MS, "median_ns": 1.5 * MS}
    # a host plane that LAGS reads under zero
    late = [(n, a - 3 * MS, b - 3 * MS) for n, a, b in spans]
    assert rs.host_lead_ns(trace, late) == {
        "steps": 3, "least_ns": -2.0 * MS, "median_ns": -1.5 * MS}
    # a fence the machine froze under ends nearer the NEXT step's end (44
    # against 36 and 45.5) and still is step 3's: the others outvote it
    frozen = [sp if sp[2] != 38 * MS else (sp[0], sp[1], 44 * MS)
              for sp in spans]
    assert rs.host_lead_ns(trace, frozen) == {
        "steps": 3, "least_ns": 1.0 * MS, "median_ns": 1.5 * MS}
    # two fences that agree on nothing: no reading
    assert rs.host_lead_ns(trace, [spans[0], (spans[3][0], 0, 60 * MS)]
                           ) is None
    # no module line, or no decode run on it: nothing to read
    assert rs.host_lead_ns(_device([("jit_other(1)", 0, 9)]), spans) is None
    assert rs.host_lead_ns(trace, spans[-1:]) is None


def test_host_lead_on_a_recorded_slice():
    from perfbench.harness import ring_steps as rs
    path = os.path.join(HERE, "data", "ring_small.json")
    with open(path) as f:
        data = json.load(f)
    got = rs.host_lead_ns(data["trace"], [tuple(s) for s in data["spans"]])
    assert got["steps"] == data["expect"]["steps"]
    assert got["least_ns"] == pytest.approx(data["expect"]["least_ns"])
    assert got["median_ns"] == pytest.approx(data["expect"]["median_ns"])
    # the ring's records of the same slice give the steps it dispatched
    steps = rs.steps([tuple(r) for r in data["records"]])
    assert len(steps) == data["expect"]["ring_steps"]
    assert all(s["active"] == len(s["lengths"]) > 0 for s in steps)
    # one admission in the slice: its turn dispatched a step of four slots
    # and returned the three tokens of the step before; the turn after it
    # returned four, one of them the first its stream carries
    at = [s["admit_worked"] for s in steps].index(True)
    assert [(s["active"], s["tokens"], s["firsts"], s["gaps"])
            for s in steps[at:at + 2]] == [(4, 3, 0, 3), (4, 4, 1, 3)]
    assert sum(s["admit_worked"] for s in steps) == 1
