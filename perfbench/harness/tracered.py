"""Reduction from a profiler trace to numbers. Pure functions over a
neutral structure, so the arithmetic is tested on a small recorded trace
(tests/data/trace_small.json) without a chip:

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, dur_ns], ...]}]}]}

`read_xplane` is the only function that touches JAX's ProfileData.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_SPAN_PREFIX = "bench."
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast|send|recv)", re.I)


KERNEL_TAG = re.compile(r"\b_\w+_kernel\b")


def short_name(full: str) -> str:
    """The profiler names a device op by its whole HLO line
    ('%fusion.7 = bf16[...] fusion(...)'): keep the instruction's name,
    and for a Pallas custom call add the kernel it carries
    ('custom-call.3[_dma_kernel]'), which is what a reader matches on."""
    name = full.split(" = ", 1)[0].lstrip("%").strip()
    tags = sorted(set(KERNEL_TAG.findall(full)))
    return name + ("[" + ",".join(tags) + "]" if tags else "")


def read_xplane(trace_dir: str, host_prefix: str = HOST_SPAN_PREFIX,
                full_names: bool = False) -> dict:
    """The newest .xplane.pb under trace_dir as the neutral structure.
    Device planes whole; of host planes only the benchmark's own spans."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not paths:
        return {"planes": []}
    planes = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        is_dev = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            evs = [[e.name if (full_names or not is_dev)
                    else short_name(e.name),
                    float(e.start_ns), float(e.duration_ns)]
                   for e in line.events
                   if is_dev or e.name.startswith(host_prefix)]
            if evs:
                lines.append({"name": line.name, "events": evs})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def summarize(trace: dict, top: int = 12, grep: str | None = None) -> dict:
    """Plane / line / top event names: what a builder reads once by hand.
    With `grep`, also the distinct names that match it (cut to 700)."""
    out = {}
    for plane in trace["planes"]:
        for li, line in enumerate(plane["lines"]):
            tot: dict[str, float] = {}
            for name, _s, d in line["events"]:
                tot[name] = tot.get(name, 0.0) + d
            best = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
            out[f"{plane['name']} | {li} {line['name']}"] = {
                "events": len(line["events"]),
                "top": [[n[:300], d / 1e9] for n, d in best],
                "grep": sorted({n[:700] for n in tot
                                if grep and re.search(grep, n)})[:6]}
    return out


def _union(intervals: list) -> list:
    """Sorted, merged [start, end] intervals."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _self_times(events: list) -> list:
    """[(name, self_ns)] for events of ONE line: a parent (while, call,
    conditional) loses what its direct children cover, so a sum over
    names never counts a nanosecond twice."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    selfs = [e[2] for e in evs]
    stack: list[int] = []
    for i, (_n, s, d) in enumerate(evs):
        while stack and evs[stack[-1]][1] + evs[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            selfs[stack[-1]] -= d
        stack.append(i)
    return [(e[0], max(0.0, t)) for e, t in zip(evs, selfs)]


def device_lines(trace: dict) -> dict:
    """{device ordinal: events of its op line}."""
    out = {}
    for plane in trace["planes"]:
        m = DEVICE_PLANE.match(plane["name"])
        if not m:
            continue
        for line in plane["lines"]:
            if line["name"] == OPS_LINE:
                out[int(m.group(1))] = line["events"]
    return out


def host_spans(trace: dict, prefix: str = HOST_SPAN_PREFIX) -> list:
    """[(name, start_ns, end_ns)] of the benchmark's TraceAnnotations."""
    out = []
    for plane in trace["planes"]:
        if plane["name"].startswith("/device:"):
            continue
        for line in plane["lines"]:
            out += [(n, s, s + d) for n, s, d in line["events"]
                    if n.startswith(prefix)]
    return out


def window_of(trace: dict) -> tuple | None:
    """The traced window in ns: the benchmark's `bench.window` span where
    it is in the trace, else first device op start to last device op end."""
    for n, s, e in host_spans(trace):
        if n == HOST_SPAN_PREFIX + "window":
            return (s, e)
    evs = [e for line in device_lines(trace).values() for e in line]
    if not evs:
        return None
    return (min(e[1] for e in evs), max(e[1] + e[2] for e in evs))


def _clip(intervals: list, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def reduce_trace(trace: dict, top: int = 10) -> dict | None:
    """Everything the per-layer readers take from a trace, or None where
    no operation ran on a device:
      window_s, busy_s (mean over devices), n_devices,
      op_self_s   {op name: self seconds, summed over devices}
      op_count    {op name: events, summed over devices}
      collective_s  mean over devices of the self time of collective ops on
                    the op line (the core runs nothing else meanwhile)
      device_ops  top ops by self time [[name, seconds]]
      idle_gaps   [[host span or 'between_spans', idle seconds]] device 0
    """
    lines = device_lines(trace)
    win = window_of(trace)
    if not lines or win is None:
        return None
    lo, hi = win
    busy, coll = [], []
    op_self: dict[str, float] = {}
    op_count: dict[str, int] = {}
    for _dev, events in sorted(lines.items()):
        inside = [e for e in events if e[1] + e[2] > lo and e[1] < hi]
        merged = _clip(_union([[e[1], e[1] + e[2]] for e in inside]), lo, hi)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        c = 0.0
        for name, t in _self_times(inside):
            op_self[name] = op_self.get(name, 0.0) + t / 1e9
            op_count[name] = op_count.get(name, 0) + 1
            if COLLECTIVE.match(name):
                c += t / 1e9
        coll.append(c)
    if not any(b > 0 for b in busy):
        return None
    # Idle gaps of the first device, by the innermost benchmark span that
    # covers the middle of the gap.
    first = lines[min(lines)]
    merged = _clip(_union([[e[1], e[1] + e[2]] for e in first]), lo, hi)
    spans = sorted(host_spans(trace), key=lambda x: x[2] - x[1])
    gaps: dict[str, float] = {}
    prev = lo
    for s, e in merged + [[hi, hi]]:
        if s > prev:
            mid = (prev + s) / 2
            owner = next((n for n, a, b in spans
                          if a <= mid <= b and n != HOST_SPAN_PREFIX
                          + "window"), "between_spans")
            gaps[owner] = gaps.get(owner, 0.0) + (s - prev) / 1e9
        prev = max(prev, e)
    rank = sorted(op_self.items(), key=lambda kv: -kv[1])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / len(busy),
        "n_devices": len(busy),
        "op_self_s": op_self, "op_count": op_count,
        "collective_s": sum(coll) / len(coll),
        "device_ops": [[n, t] for n, t in rank[:top]],
        "idle_gaps": [[n, t] for n, t in sorted(
            gaps.items(), key=lambda kv: -kv[1])[:top]],
    }


def seconds_matching(reduced: dict, pattern: str) -> tuple[float, int]:
    """(self seconds, events) of device ops whose name matches `pattern`,
    summed over devices."""
    rx = re.compile(pattern)
    names = [n for n in reduced["op_self_s"] if rx.search(n)]
    return (sum(reduced["op_self_s"][n] for n in names),
            sum(reduced["op_count"][n] for n in names))


def cut(trace: dict, start_s: float, length_s: float) -> dict:
    """A slice of a trace, `length_s` long from `start_s` into its window:
    how tests/data/trace_small.json was recorded. The slice carries a
    bench.window span of its own."""
    lo = window_of(trace)[0] + start_s * 1e9
    hi = lo + length_s * 1e9
    planes = []
    for plane in trace["planes"]:
        lines = []
        for line in plane["lines"]:
            evs = [e for e in line["events"]
                   if e[1] >= lo and e[1] + e[2] <= hi
                   and e[0] != HOST_SPAN_PREFIX + "window"]
            if evs:
                lines.append({"name": line["name"], "events": evs})
        if lines:
            planes.append({"name": plane["name"], "lines": lines})
    planes.append({"name": "/host:cut", "lines": [{"name": "cut", "events": [
        [HOST_SPAN_PREFIX + "window", lo, hi - lo]]}]})
    return {"planes": planes}
