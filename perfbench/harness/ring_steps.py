"""The series `serve_cell._wrap_engine` takes by reaching into the engine,
rebuilt from the program's own ring (ray_tpu/diagnostics.py) alone. Pure
functions over span records `(id, parent, name, t0_ns, t1_ns, thread,
attrs)` and `program_spans.turns`; no import of `serve_cell`, no touch of
the engine. A ring whose spans lack an attribute (a parent commit's) yields
empty lists and None, and nothing is raised.

What the spans say of the work (since PR 58; PERF.md section 3):

  engine.decode    `lengths`: the `lengths` operand of the active slots, in
                   slot order, as the step was DISPATCHED with them
  pump.fanout      `tokens`: the tokens the turn's `step()` returned, one
                   a request; `firsts`: those of them that are the first
                   their request's subscriber sees
  engine.admit.prefill   `bucket` (the group's S), `tokens` (the new tokens
                   of each real request) and `prefix` (each one's cached
                   tokens); the dispatch pads the batch to a power of two

The wrapper keeps one entry a CALL of `step()` that returned a token;
`steps` has one a TURN that dispatched a decode step. A call fetches the
step the call before dispatched, so inside a busy stretch the two lists
pair turn for turn, and at its edges each has an entry the other lacks: the
stretch's first turn dispatches and returns nothing (no entry of the
wrapper's, though the kernels ran), its last returns the last tokens and
dispatches nothing (an entry of the wrapper's with `lengths` []). On a
paired turn, each of the wrapper's fields against this module's:

  lengths       EQUAL. The wrapper reads `eng.lengths[eng.active]` after
                `step()` returns, when the step before has landed (`_land`
                added one a slot): that IS the operand of the step the call
                dispatched (`_past`: the host's lengths plus one where the
                step in flight moves the slot). One entry fewer a slot that
                the dispatched step led past an end on `eos_token` (none
                where `eos_token` is off, as in every cell). What the
                kernel attended is one key MORE a slot: the operand counts
                the keys the cache holds, and the step writes its own token
                first and attends it (`limits = lengths + 1`). The cost
                functions count the operand from either list.
  active        the wrapper's is `len(out)`, the tokens the call returned:
                this turn's `tokens`, and the `active` (`len(lengths)`) of
                the entry BEFORE, the step those tokens came from; `active`
                here is the count the dispatched step ran
  gaps          the wrapper's is `eng.active.sum()` before the call, which
                since the loop runs ahead (PR 34, 51) counts every token
                the call returns, a request's first streamed token too:
                this turn's `gaps + firsts` (less a first token that the
                admitting call returns itself, a request of one token,
                which neither counts). `gaps` here is `tokens - firsts`:
                the gaps between two tokens of one stream that the turn
                closed
  admit_worked  the wrapper's is "`_admit()` took 2 ms or more"; here it is
                "the turn dispatched a prefill" (an `engine.admit.prefill`
                span inside its `pump.step`): equal wherever the wrapper's
                is true. An `engine.admit` span alone says only that the
                queue held a request: a closed-loop cell's queue always
                does, and with no slot free the span is 0.03 ms of nothing
  t0, t1        the `pump.step` span's, in perf_counter seconds; the
                wrapper's pair lies inside it
"""

from __future__ import annotations

import bisect
import collections

from perfbench.harness import program_spans as ps

MODULES_LINE = "XLA Modules"
DECODE_RUN = "decode_paged"     # in the name of the decode program's runs
SAMPLER_RUN = "sample"          # and of the sampler's, on that line


def _delivered(turn: dict) -> tuple:
    """(tokens, firsts) of the turn's fan-out; (0, 0) where it says none."""
    f = turn["fanout"]
    if f is None or "tokens" not in f[6]:
        return 0, 0
    return f[6]["tokens"], f[6]["firsts"]


def steps(records: list) -> list[dict]:
    """One entry a turn that dispatched a decode step, in order: the shape
    of `Replica.steps` (the module's docstring has each field against the
    wrapper's), with the turn's `tokens` and `firsts` and the span's `step`
    (`kv_stats()["decode_steps"]` after it; the trace's annotation carries
    it too) beside them."""
    out = []
    for t in ps.turns(records):
        dec = t["inside"].get(ps.DECODE)
        if not dec or "lengths" not in dec[-1][6]:
            continue
        tokens, firsts = _delivered(t)
        lengths = list(dec[-1][6]["lengths"])
        out.append({"t0": t["step"][3] / 1e9, "t1": t["step"][4] / 1e9,
                    "active": len(lengths), "gaps": tokens - firsts,
                    "admit_worked": ps.PREFILL in t["inside"],
                    "lengths": lengths, "tokens": tokens, "firsts": firsts,
                    "step": dec[-1][6].get("step")})
    return out


def decode_step_ms(records: list) -> list[float]:
    """The wrapper's `decode_step_ms`: of every turn that returned a token,
    from the end of its `engine.admit` (the turn's start where the queue
    was empty) to the end of its `pump.step`: the dispatch of step N + 1
    and the fence on step N. Where a turn without an admission fetched
    BEFORE it dispatched (`_may_lead` said no), that fence lies inside
    this reading and outside the wrapper's, which starts at the return of
    the empty `_admit()`."""
    out = []
    for t in ps.turns(records):
        if not _delivered(t)[0]:
            continue
        admits = t["inside"].get(ps.ADMIT)
        start = admits[-1][4] if admits else t["step"][3]
        out.append((t["step"][4] - start) / 1e6)
    return out


def admit_work_ms(records: list) -> list[float]:
    """The wrapper's `admit_work_ms` without its 2 ms threshold: the length
    of every `engine.admit` span that prefilled something (`rows` > 0; one
    that found the queue full and no slot free admitted nothing)."""
    return [(r[4] - r[3]) / 1e6 for r in records
            if r[2] == ps.ADMIT and r[6].get("rows")]


def batch_occupancy_pct(step_list: list, max_slots: int) -> float | None:
    if not step_list:
        return None
    return 100.0 * sum(s["active"] for s in step_list) / (
        max_slots * len(step_list))


def admit_gap_share_pct(step_list: list) -> float | None:
    gaps = sum(s["gaps"] for s in step_list)
    if not gaps:
        return None
    return 100.0 * sum(s["gaps"] for s in step_list
                       if s["admit_worked"]) / gaps


def prefills(records: list) -> list[dict]:
    """One entry a prefill dispatch (`engine.admit.prefill`), in order."""
    out = sorted((r for r in records
                  if r[2] == ps.PREFILL and "bucket" in r[6]),
                 key=lambda r: r[3])
    return [{"bucket": r[6]["bucket"], "tokens": list(r[6]["tokens"]),
             "prefix": list(r[6]["prefix"])} for r in out]


def prefill_rows(prefill_list: list) -> dict:
    """{"tokens": new tokens prefilled, "request_rows": requests x bucket
    (`engine.admit`'s `rows`), "padded_rows": the rows the dispatches ran,
    their batches padded to a power of two (`kv_stats()`'s
    `prefill_rows_bucketed`)}."""
    out = {"tokens": 0, "request_rows": 0, "padded_rows": 0}
    for p in prefill_list:
        n = len(p["tokens"])
        out["tokens"] += sum(p["tokens"])
        out["request_rows"] += n * p["bucket"]
        out["padded_rows"] += (1 << (n - 1).bit_length()) * p["bucket"]
    return out


def prefill_real_rows_pct(prefill_list: list) -> float | None:
    """Of the rows the requests' buckets hold, the share that is a token."""
    rows = prefill_rows(prefill_list)
    if not rows["request_rows"]:
        return None
    return 100.0 * rows["tokens"] / rows["request_rows"]


def host_lead_ns(trace: dict, spans: list) -> dict | None:
    """How far the trace's host plane leads its device plane, from what
    cannot happen on one clock: a `land.fence` returns only after the step
    it fetched has ended on the device. `trace` is tracered's neutral
    structure with the first device's `XLA Modules` line (a program run an
    event), `spans` `(name, start_ns, end_ns)` on the trace's clock
    (`program_spans.spans_for_trace`).

    A step on the device is a run of the decode program (`DECODE_RUN`) up
    to the end of the first run of the sampler after it (`SAMPLER_RUN`,
    whose tokens the fence fetches). Every step is fetched once and in
    order, so fence i fetched step i + c for ONE c over the slice: the c
    that most fences' nearest step ends agree on (a step is several ms,
    the lead under three; a fence the machine froze under, M8, ends nearer
    a later step and is outvoted). The first and the last step of the
    trace are matched to nothing: the trace cut them. -> {"steps": fences
    matched, "least_ns", "median_ns": fence's end less that step's end};
    None where the trace has no such line, no fence lies among the steps,
    or no c has most of the fences (a slice of a few steps behind seconds
    of queued prefill: `solar_open2_250b-serve-agentic`'s). A least value under 0 says the host plane lags by as much at
    least; over 0 it bounds the lead from above (the fetch itself takes
    time)."""
    runs = []
    for plane in trace["planes"]:
        if plane["name"].startswith("/device:"):
            runs = sorted((s, s + d, n) for line in plane["lines"]
                          if line["name"] == MODULES_LINE
                          for n, s, d in line["events"])
            break
    ends = []       # the end of each step on the device, in order
    for j, (_start, end, name) in enumerate(runs):
        if DECODE_RUN not in name:
            continue
        for _s, e, n in runs[j + 1:]:
            if DECODE_RUN in n:
                break
            if SAMPLER_RUN in n:
                end = e
                break
        ends.append(end)
    fences = sorted(e for n, _s, e in spans if n == ps.FENCE)
    if not ends or not fences:
        return None
    votes = collections.Counter()
    for i, t in enumerate(fences):
        k = bisect.bisect_left(ends, t)
        votes[min(range(max(k - 1, 0), min(k + 1, len(ends))),
                  key=lambda j: abs(t - ends[j])) - i] += 1
    c, agreed = votes.most_common(1)[0]
    if 2 * agreed <= len(fences):
        return None     # the device's queue is deeper than a step or two
    leads = sorted(t - ends[i + c] for i, t in enumerate(fences)
                   if 0 < i + c < len(ends) - 1)
    if not leads:
        return None
    return {"steps": len(leads), "least_ns": leads[0],
            "median_ns": leads[len(leads) // 2]}
