"""The reductions of the program's own spans (harness/program_spans.py):
on pump turns written by hand, whose answers are worked in the comments,
and on a 35 ms slice recorded on the chip (`data/spans_small.json`: the op
line of a `qwen2_7b-serve-chat` slice with the program's annotations
beside it, the ring's records of the same 35 ms, and where the slice's
`bench.window` lay on the ring's clock; PR 37's traced run, seed
3700100001, cut where the engine ran out of requests)."""

import json
import os

import pytest

from perfbench.harness import tracered

HERE = os.path.dirname(os.path.abspath(__file__))

MS = 1_000_000


def _span_records():
    """Four pump turns written by hand (times in ms): one that admits with
    nothing in flight, one that decodes ahead, one that fetches first and
    prefills a chunk, one whose fan-out the records no longer hold."""
    P = "ray_tpu."
    rows = [
        (1, 0, "pump.step", 0, 30, {}),
        (2, 1, "engine.admit", 1, 25, {"fenced": 1, "rows": 1536}),
        (3, 2, "engine.admit.plan", 1, 2, {}),
        (4, 2, "engine.admit.prefill", 2, 4, {}),
        (5, 2, "engine.admit.prefill", 4, 5, {}),
        (6, 2, "engine.admit.register", 5, 5.5, {}),
        (7, 2, "engine.admit.sample", 6, 24, {}),
        (8, 1, "engine.decode", 26, 27.5, {"step": 1, "ahead": 0}),
        (9, 0, "pump.fanout", 30.2, 30.7, {}),
        (10, 0, "pump.step", 31, 42, {}),
        (11, 10, "engine.decode", 31.5, 32.5, {"step": 2, "ahead": 1}),
        (12, 10, "engine.land", 32.5, 41.5, {}),
        (13, 12, "engine.land.fence", 32.6, 40.6, {}),
        (14, 0, "pump.fanout", 42.1, 42.4, {}),
        (15, 0, "pump.step", 43, 80, {}),
        (16, 15, "engine.land", 43.1, 50.1, {}),
        (17, 16, "engine.land.fence", 43.2, 50.0, {}),
        (18, 15, "engine.admit", 50.5, 70, {"fenced": 0, "rows": 512}),
        (19, 18, "engine.admit.prefill", 51, 53, {}),
        (20, 15, "engine.decode", 71, 72, {"step": 3, "ahead": 0}),
        (21, 0, "pump.fanout", 80.1, 80.2, {}),
        (22, 0, "pump.step", 81, 90, {}),
        (23, 22, "engine.decode", 81.5, 82, {"step": 4, "ahead": 1}),
        (24, 0, "request", 0.5, 85, {"queue_ms": 1.5}),
        (25, 0, "request", 60, 61, {"queue_ms": None}),
    ]
    return [(i, p, P + n, a * MS, b * MS, "pump", at)
            for i, p, n, a, b, at in rows]


def test_program_spans_reductions_by_hand():
    from perfbench.harness import program_spans as ps
    recs = _span_records()
    turns = ps.turns(recs)
    assert [t["step"][0] for t in turns] == [1, 10, 15, 22]
    assert [t["fanout"] and t["fanout"][0] for t in turns] == [
        9, 14, 21, None]
    # the host's own work a token: 11 + 0.3 less the 8 ms fence
    assert ps.step_host_ms(turns) == pytest.approx([3.3])
    # turn 1: 0 -> 4 (first prefill back) + 24 -> 27.5 (sample -> decode);
    # turn 3: the fence's end, 50.0 -> 53, and no sample
    assert ps.admit_unfed_ms(turns) == pytest.approx([7.5, 3.0])
    assert ps.steps_ahead_pct(recs) == 50.0
    # the one fenced admission: 24 ms over 1536 rows
    assert ps.prefill_fenced_ms_per_krow(recs) == pytest.approx(
        [24.0 / 1.536])
    assert ps.req_queue_ms(recs) == [1.5]
    assert [r[0] for r in ps.in_window(recs, 31 * MS, 43 * MS)] == [
        10, 11, 12, 13, 14]
    assert ps.steps_ahead_pct([]) is None and ps.turns([]) == []


def test_idle_time_is_split_among_the_innermost_program_spans():
    from perfbench.harness import program_spans as ps
    P = "ray_tpu."
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["fusion.1", 10.0, 10.0], ["fusion.2", 30.0, 30.0]]}]},
        {"name": "/host:CPU", "lines": [{"name": "t", "events": [
            ["bench.window", 0.0, 100.0]]}]}]}
    spans = [(P + "pump.idle", 0, 8), (P + "pump.step", 5, 70),
             (P + "engine.admit", 22, 28)]
    assert ps.innermost_timeline(spans) == [
        (0, 8, P + "pump.idle"), (8, 22, P + "pump.step"),
        (22, 28, P + "engine.admit"), (28, 70, P + "pump.step")]
    got = ps.idle_by_span(trace, spans)
    assert got["window_s"] == pytest.approx(100e-9)
    assert got["idle_s"] == pytest.approx(60e-9)
    assert {k: round(v * 1e9, 6) for k, v in got["by_span"].items()} == {
        P + "pump.idle": 8, P + "pump.step": 16, P + "engine.admit": 6,
        ps.NO_SPAN: 30}
    assert ps.idle_by_span({"planes": []}, spans) is None
    # a span the trace could not hold is taken from the ring, on its clock
    recs = [(1, 0, P + "pump.idle", 990, 1010, "pump", {}),     # crosses
            (2, 0, P + "pump.step", 1010, 1020, "pump", {}),    # inside
            (3, 2, "xla.compile", 1012, 1015, "pump", {}),
            (4, 0, P + "pump.idle", 900, 950, "pump", {})]      # before
    assert ps.spans_for_trace(trace, recs, (1000e-9, 1100e-9)) == [
        (P + "pump.idle", -10.0, 10.0), ("xla.compile", 12.0, 15.0)]


def test_a_recorded_slice_splits_its_idle_time_by_the_programs_spans():
    from perfbench.harness import program_spans as ps
    with open(os.path.join(HERE, "data", "spans_small.json")) as f:
        small = json.load(f)
    trace = small["trace"]
    records = [tuple(r) for r in small["records"]]
    names = {n for n, _s, _e in tracered.host_spans(trace, ps.PREFIX)}
    assert names >= {ps.PUMP_STEP, ps.PUMP_FANOUT, ps.FENCE}
    # the engine ran out of requests 16 ms into the slice and the quiet
    # stretch outlasted it: the profiler kept no annotation of it (it ended
    # after the trace), the ring did
    assert ps.PUMP_IDLE not in names
    spans = ps.spans_for_trace(trace, records, small["host_interval"])
    (idle,) = [s for s in spans if s[0] == ps.PUMP_IDLE]
    win = tracered.window_of(trace)
    assert win[0] < idle[1] < win[1] < idle[2]
    got = ps.idle_by_span(trace, spans)
    red = tracered.reduce_trace(trace)
    # the same idle time as device_idle_pct.* reads, split another way
    assert got["window_s"] == pytest.approx(red["window_s"]) == 0.035
    assert got["idle_s"] == pytest.approx(red["window_s"] - red["busy_s"])
    by = {k: v * 1e3 for k, v in got["by_span"].items()}       # ms
    assert by[ps.PUMP_IDLE] == pytest.approx(19.6049, abs=1e-3)
    assert by[ps.FENCE] == pytest.approx(2.1847, abs=1e-3)
    assert sum(by.values()) == pytest.approx(21.9339, abs=1e-3)
    with_work = got["idle_s"] - got["by_span"][ps.PUMP_IDLE]
    assert 100 * with_work / got["window_s"] == pytest.approx(6.654, abs=1e-2)
    # tracered's own attribution of the same gaps, by the benchmark's spans
    assert dict(red["idle_gaps"])["between_spans"] == pytest.approx(
        21.9212e-3, abs=1e-6)
