"""Reductions from the program's own spans (ray_tpu/diagnostics.py: the
replica's pump, the engine's admission, decode and fetch, a request's
life) to numbers. Pure functions over span records `(id, parent, name,
t0_ns, t1_ns, thread, attrs)` and over tracered's neutral trace structure,
so the arithmetic is tested on records written by hand and on a small
recorded trace (tests/test_program_spans.py) without a chip.

`collect` is the one function that touches the program: it reads the ring,
cuts it to the measured window and leaves its readings in the record, as
series `prog.<name>_ms` and values `prog.<name>_pct`. A program without the
recorder (a parent commit) yields nothing, and nothing is raised.

What each reading is, by the spans' names (PERF.md section 3 has them):

  step_host_ms     a pump turn that dispatched a decode step and no
                   prefill: `pump.step` + the `pump.fanout` after it, less
                   the `land.fence` inside it (the wait for the device):
                   the host's own work a token
  admit_unfed_ms   a turn that dispatched a prefill: host time in which
                   the host KNOWS the device holds nothing: from the end of
                   the turn's `land.fence` (the turn's start where nothing
                   was in flight) to the end of the first `admit.prefill`,
                   plus from the end of `admit.sample` to the end of
                   `engine.decode`
  steps_ahead_pct  100 x `engine.decode` spans with ahead = 1 / all of them
  req_queue_ms     `queue_ms` of the `ray_tpu.request` records that arrived
                   in the window
  prefill_fenced_ms_per_krow   duration of the `engine.admit` spans with
                   fenced = 1, over the span's `rows` / 1000 (the
                   prompt-bucket rows the step prefilled): the duration
                   has a mode a bucket, a row's cost has one
  idle_with_work_pct, idle_in.<span>_pct   the idle time of chip 0 in the
                   traced slice, split at the spans' boundaries among the
                   innermost program span over each instant: under
                   `pump.idle` the engine had no request, everywhere else
                   it had one and the chip waited
"""

from __future__ import annotations

import bisect
import math

from perfbench.harness import tracered

PREFIX = "ray_tpu."
PUMP_STEP = PREFIX + "pump.step"
PUMP_FANOUT = PREFIX + "pump.fanout"
PUMP_IDLE = PREFIX + "pump.idle"
FENCE = PREFIX + "engine.land.fence"
ADMIT = PREFIX + "engine.admit"
PREFILL = PREFIX + "engine.admit.prefill"
SAMPLE = PREFIX + "engine.admit.sample"
DECODE = PREFIX + "engine.decode"
REQUEST = PREFIX + "request"
NO_SPAN = "no_span"        # idle time that no program span lies over


def in_window(records: list, lo_ns: float, hi_ns: float) -> list:
    """The records that began in [lo_ns, hi_ns)."""
    return [r for r in records if lo_ns <= r[3] < hi_ns]


def turns(records: list) -> list[dict]:
    """One entry a `pump.step` span, in order: {"step": its record,
    "fanout": the `pump.fanout` that followed it on its thread (None if the
    records end first), "inside": {name: [records]} of the spans under it,
    at any depth}."""
    by_id = {r[0]: r for r in records}
    steps = sorted((r for r in records if r[2] == PUMP_STEP),
                   key=lambda r: r[3])
    out = {r[0]: {"step": r, "fanout": None, "inside": {}} for r in steps}
    for r in records:
        top = r
        while top[1] in by_id:
            top = by_id[top[1]]
        if top is not r and top[0] in out:
            out[top[0]]["inside"].setdefault(r[2], []).append(r)
    fanouts = sorted((r for r in records if r[2] == PUMP_FANOUT),
                     key=lambda r: r[3])
    starts = [f[3] for f in fanouts]
    for k, s in enumerate(steps):
        i = bisect.bisect_left(starts, s[4])
        nxt = steps[k + 1][3] if k + 1 < len(steps) else math.inf
        if i < len(fanouts) and fanouts[i][3] < nxt and (
                fanouts[i][5] == s[5]):
            out[s[0]]["fanout"] = fanouts[i]
    for t in out.values():
        for spans in t["inside"].values():
            spans.sort(key=lambda r: r[3])
    return [out[s[0]] for s in steps]


def _ms(ns: float) -> float:
    return ns / 1e6


def step_host_ms(turn_list: list) -> list[float]:
    out = []
    for t in turn_list:
        inside = t["inside"]
        if PREFILL in inside or DECODE not in inside or t["fanout"] is None:
            continue
        s, f = t["step"], t["fanout"]
        fence = sum(r[4] - r[3] for r in inside.get(FENCE, ()))
        out.append(_ms((s[4] - s[3]) + (f[4] - f[3]) - fence))
    return out


def admit_unfed_ms(turn_list: list) -> list[float]:
    out = []
    for t in turn_list:
        inside = t["inside"]
        if PREFILL not in inside:
            continue
        first = inside[PREFILL][0]
        fences = [r[4] for r in inside.get(FENCE, ()) if r[4] <= first[3]]
        unfed = first[4] - (max(fences) if fences else t["step"][3])
        if SAMPLE in inside and DECODE in inside:
            unfed += max(0, inside[DECODE][-1][4] - inside[SAMPLE][-1][4])
        out.append(_ms(unfed))
    return out


def steps_ahead_pct(records: list) -> float | None:
    dec = [r for r in records if r[2] == DECODE]
    if not dec:
        return None
    return 100.0 * sum(1 for r in dec if r[6].get("ahead")) / len(dec)


def req_queue_ms(records: list) -> list[float]:
    return [r[6]["queue_ms"] for r in records
            if r[2] == REQUEST and r[6].get("queue_ms") is not None]


def prefill_fenced_ms_per_krow(records: list) -> list[float]:
    return [_ms(r[4] - r[3]) * 1000.0 / r[6]["rows"]
            for r in records if r[2] == ADMIT and r[6].get("fenced")]


def innermost_timeline(spans: list) -> list:
    """[(start, end, name)], sorted and disjoint: over each instant the
    SHORTEST of the `(name, start, end)` spans that cover it (a child is
    shorter than its parent; of two threads' spans the shorter one is the
    closer description)."""
    marks = sorted({t for _n, a, b in spans for t in (a, b)})
    by_start = sorted(spans, key=lambda x: x[1])
    out, live, k = [], [], 0
    for lo, hi in zip(marks, marks[1:]):
        while k < len(by_start) and by_start[k][1] <= lo:
            live.append(by_start[k])
            k += 1
        live = [s for s in live if s[2] > lo]
        if live:
            name = min(live, key=lambda x: x[2] - x[1])[0]
            if out and out[-1][2] == name and out[-1][1] == lo:
                out[-1][1] = hi
            else:
                out.append([lo, hi, name])
    return [tuple(x) for x in out]


def idle_gaps(trace: dict) -> tuple | None:
    """((window start, end), [(gap start, end)]) of the first device in the
    trace's window (tracered.window_of), in ns; None where the trace has
    no device line."""
    lines = tracered.device_lines(trace)
    win = tracered.window_of(trace)
    if not lines or win is None:
        return None
    lo, hi = win
    busy = tracered._clip(tracered._union(
        [[e[1], e[1] + e[2]] for e in lines[min(lines)]]), lo, hi)
    gaps, prev = [], lo
    for s, e in busy + [[hi, hi]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    return win, gaps


def idle_by_span(trace: dict, spans: list) -> dict | None:
    """{"window_s", "idle_s", "by_span": {name or NO_SPAN: idle seconds}}
    of the first device in the trace's window, or None where the trace
    has no device line. `spans` are `(name, start_ns, end_ns)` on the
    trace's clock."""
    found = idle_gaps(trace)
    if found is None:
        return None
    (lo, hi), gaps = found
    line = innermost_timeline(spans)
    starts = [seg[0] for seg in line]
    by: dict[str, float] = {}
    for g0, g1 in gaps:
        covered = 0.0
        i = max(0, bisect.bisect_right(starts, g0) - 1)
        while i < len(line) and line[i][0] < g1:
            a, b, name = line[i]
            d = min(b, g1) - max(a, g0)
            if d > 0:
                by[name] = by.get(name, 0.0) + d / 1e9
                covered += d
            i += 1
        if g1 - g0 > covered:
            by[NO_SPAN] = by.get(NO_SPAN, 0.0) + (g1 - g0 - covered) / 1e9
    return {"window_s": (hi - lo) / 1e9, "idle_s": sum(by.values()),
            "by_span": by}


def spans_for_trace(trace: dict, records: list, host_interval) -> list:
    """`(name, start_ns, end_ns)` on the trace's clock: the program's
    annotations in the trace, and, of the ring's records, those the trace
    cannot hold (the profiler keeps an annotation only if it began AND
    ended while it ran, so a `pump.idle` across an edge of the slice is
    missing; an `xla.compile` was never one; a request's life is no work
    of the host's and stays out). The ring's clock is laid on
    the trace's by the `bench.window` span, which both saw: `host_interval`
    is its (start, end) in perf_counter seconds."""
    spans = list(tracered.host_spans(trace, PREFIX))
    win = tracered.window_of(trace)
    if win is None or not host_interval:
        return spans
    a_ns, b_ns = (t * 1e9 for t in host_interval)
    shift = win[0] - a_ns
    for r in records:
        crosses = r[3] < a_ns < r[4] or r[3] < b_ns < r[4]
        if r[2] != REQUEST and r[4] > a_ns and r[3] < b_ns and (
                crosses or not r[2].startswith(PREFIX)):
            spans.append((r[2], r[3] + shift, r[4] + shift))
    return spans


def collect(rep, rec, trace_dir: str | None) -> None:
    """Fill `rec.samples["prog.*"]` and `rec.values["prog.*"]` from what the
    program recorded in the window `rep.t_open .. rep.t_close` and, with a
    trace, in the traced slice."""
    from ray_tpu import diagnostics
    read = getattr(diagnostics, "spans", None)
    if read is None:
        return
    records, dropped = read()
    if not records:
        return
    rec.values["prog.spans_dropped"] = dropped
    mine = in_window(records, rep.t_open * 1e9, rep.t_close * 1e9)
    turn_list = turns(mine)
    for name, series in (("step_host_ms", step_host_ms(turn_list)),
                         ("admit_unfed_ms", admit_unfed_ms(turn_list)),
                         ("req_queue_ms", req_queue_ms(mine)),
                         ("prefill_fenced_ms_per_krow",
                          prefill_fenced_ms_per_krow(mine))):
        if series:
            rec.samples["prog." + name] = series
    ahead = steps_ahead_pct(mine)
    if ahead is not None:
        rec.values["prog.steps_ahead_pct"] = ahead
    if not trace_dir:
        return
    trace = tracered.read_xplane(
        trace_dir, host_prefix=(tracered.HOST_SPAN_PREFIX, PREFIX))
    spans = spans_for_trace(trace, records,
                            rec.context.get("trace_host_interval"))
    idle = idle_by_span(trace, spans)
    if idle is None or idle["window_s"] <= 0:
        return
    pct = 100.0 / idle["window_s"]
    for name, secs in idle["by_span"].items():
        rec.values[f"prog.idle_in.{name}_pct"] = secs * pct
    rec.values["prog.idle_with_work_pct"] = pct * (
        idle["idle_s"] - idle["by_span"].get(PUMP_IDLE, 0.0))
