"""The hot paths' own spans (ray_tpu/diagnostics.py `span`, `spans_on`; the
pump of llm/serve.py; admission, decode and fetch of llm/engine.py; a
request's life): off they are one shared object and nothing is kept; on,
they say what the engine did, count what its own counters count, and lie
in the profiler's trace. Tiny models on the CPU: no number here is a time.
"""

import glob
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu import diagnostics
from ray_tpu.llm import EngineConfig, InferenceEngine
from ray_tpu.models import configs

pytestmark = pytest.mark.heavy

MODELS = {
    "per_head": configs.tiny(),
    "hybrid": configs.tiny_hybrid(moe_experts=2, moe_held_group=1),
}
KINDS = sorted(MODELS)
P = "ray_tpu."


@pytest.fixture(autouse=True)
def recording_off_after():
    yield
    diagnostics.spans_off()


def _engine(kind="per_head", **kw):
    e = dict(max_slots=3, max_len=160, page_size=16, prompt_buckets=(16, 32),
             eos_token=-1)
    return InferenceEngine(MODELS[kind], EngineConfig(**{**e, **kw}), seed=3)


def _ids(n, seed):
    return [int(t) for t in np.random.RandomState(seed).randint(0, 256, n)]


def _run(eng, prompts_and_new, seed=1):
    reqs = [eng.request(eng.add_request(_ids(n, k + seed), new, 0.0))
            for k, (n, new) in enumerate(prompts_and_new)]
    calls = 0
    while eng.has_work():
        eng.step()
        calls += 1
        assert calls < 2000
    return reqs


def _named(records, name):
    return [r for r in records if r.name == P + name]


# ------------------------------------------------------------------- off


def test_off_span_is_the_one_shared_object_and_reads_no_clock(monkeypatch):
    def no_clock():
        raise AssertionError("a clock was read with recording off")
    monkeypatch.setattr(diagnostics.time, "perf_counter_ns", no_clock)
    a = diagnostics.span("ray_tpu.engine.decode", step=1, ahead=0)
    b = diagnostics.span("ray_tpu.pump.step")
    assert a is b is diagnostics.NO_SPAN and not a.on
    with a as sp:
        sp.set(rows=3)
    diagnostics.record("ray_tpu.request", 1, 2, queue_ms=1.0)
    assert diagnostics.spans() == ([], 0) and not diagnostics.recording()


def test_off_a_run_of_steps_keeps_nothing():
    eng = _engine()
    req = eng.request(eng.add_request(_ids(10, 1), 9, 0.0))
    for _ in range(8):
        eng.step()
    assert diagnostics.spans() == ([], 0)
    # a request's arrival and its slot are stamped all the same
    assert 0 < req.t_arrive_ns <= req.t_slot_ns
    diagnostics.spans_on()
    diagnostics.spans_off()
    assert diagnostics.spans() == ([], 0)


def _watch_off(monkeypatch, eng):
    """-> (the attributes handed to the no-op span's `set()`, the reads of
    the ns clock, the calls of `eng._admit_queued`), each a list that
    grows from here on."""
    sets, clock, queued = [], [], []
    real = time.perf_counter_ns
    admit_queued = eng._admit_queued
    monkeypatch.setattr(diagnostics._NoSpan, "set",
                        lambda self, **attrs: sets.append(attrs))
    monkeypatch.setattr(diagnostics.time, "perf_counter_ns",
                        lambda: clock.append(1) or real())
    monkeypatch.setattr(eng, "_admit_queued", lambda sp: (
        queued.append(1), admit_queued(sp))[1])
    return sets, clock, queued


def test_off_the_hot_paths_build_no_attribute_and_read_no_new_clock(
        monkeypatch):
    """With recording off `lengths`, `tokens`, `prefix` (and `rows`) are not
    built: every `set()` stands under `if sp.on:`; and the engine reads the
    clock where it did, once an admission for `t_slot_ns` (a request's
    arrival is stamped by its dataclass)."""
    eng = _engine()
    _run(eng, [(10, 3)])
    sets, clock, queued = _watch_off(monkeypatch, eng)
    _run(eng, [(10, 6), (12, 4), (50, 5)])
    assert sets == [] and len(clock) == len(queued) >= 2
    assert diagnostics.spans() == ([], 0)


# -------------------------------------------------------------- recorder


def test_parents_attributes_and_after_the_fact_records():
    diagnostics.spans_on()
    with diagnostics.span("a", k=1) as a:
        with diagnostics.span("b") as b:
            b.set(z=2)
        t = time.perf_counter_ns()
        diagnostics.record("r", t, t + 5, q=3)
        diagnostics.record("older", 10, 20)
    records, dropped = diagnostics.spans()
    by = {r.name: r for r in records}
    assert dropped == 0
    assert [r.name for r in records] == ["b", "r", "older", "a"]
    assert by["older"].parent == 0       # it began before every open span
    assert a.on and by["a"].parent == 0 and by["a"].attrs == {"k": 1}
    assert by["b"].parent == by["r"].parent == by["a"].id
    assert by["b"].attrs == {"z": 2} and by["r"].attrs == {"q": 3}
    assert (by["r"].t0_ns, by["r"].t1_ns) == (t, t + 5)
    assert by["a"].t0_ns <= by["b"].t0_ns <= by["b"].t1_ns <= by["a"].t1_ns
    assert by["a"].thread == threading.current_thread().name


def test_the_ring_drops_the_oldest_and_counts_them():
    diagnostics.spans_on(capacity=4)
    for k in range(7):
        with diagnostics.span("s", k=k):
            pass
    records, dropped = diagnostics.spans()
    assert dropped == 3 and [r.attrs["k"] for r in records] == [3, 4, 5, 6]


def test_a_span_closed_out_of_order_is_refused():
    """Spans nest as `with` blocks do; one closed under an open child would
    pass for the parent of everything after it."""
    diagnostics.spans_on()
    a = diagnostics.span("a").__enter__()
    b = diagnostics.span("b").__enter__()
    with pytest.raises(AssertionError):
        a.__exit__(None, None, None)
    b.__exit__(None, None, None)
    a.__exit__(None, None, None)
    assert [r.name for r in diagnostics.spans()[0]] == ["b", "a"]


def test_threads_keep_their_own_parents_and_no_record_is_lost():
    diagnostics.spans_on()
    n_threads, n_each = 16, 200
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)

    def work(k):
        for i in range(n_each):
            with diagnostics.span("outer", k=k):
                with diagnostics.span("inner", k=k):
                    pass

    try:
        ts = [threading.Thread(target=work, args=(k,))
              for k in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(old)
    records, dropped = diagnostics.spans()
    assert dropped == 0 and len(records) == 2 * n_threads * n_each
    assert len({r.id for r in records}) == len(records)
    by_id = {r.id: r for r in records}
    for r in records:
        if r.name == "inner":
            parent = by_id[r.parent]
            assert parent.name == "outer" and parent.attrs == r.attrs
            assert parent.thread == r.thread
        else:
            assert r.parent == 0


# ---------------------------------------------------------------- engine


@pytest.mark.parametrize("kind", KINDS)
def test_one_decode_span_a_step_and_the_counters_agree(kind):
    """`steps_ahead_pct` is the kv_stats() deltas' ratio, exactly."""
    from perfbench.harness import program_spans
    eng = _engine(kind)
    _run(eng, [(10, 3)])                      # compiled before recording
    base = eng.kv_stats()
    diagnostics.spans_on()
    _run(eng, [(10, 12), (20, 5), (27, 9), (12, 20)])
    records, dropped = diagnostics.spans()
    end = eng.kv_stats()
    dec = _named(records, "engine.decode")
    steps = end["decode_steps"] - base["decode_steps"]
    ahead = end["decode_steps_ahead"] - base["decode_steps_ahead"]
    assert dropped == 0 and steps > 0 and 0 < ahead < steps
    assert len(dec) == steps
    assert sum(r.attrs["ahead"] for r in dec) == ahead
    assert [r.attrs["step"] for r in dec] == list(range(
        base["decode_steps"] + 1, end["decode_steps"] + 1))
    assert program_spans.steps_ahead_pct(records) == 100.0 * ahead / steps
    # every step that was dispatched was fetched, by a land span of its own
    assert len(_named(records, "engine.land")) == steps


def test_children_lie_inside_parents_and_self_time_is_what_is_left():
    eng = _engine()
    _run(eng, [(10, 3)])
    diagnostics.spans_on()
    _run(eng, [(10, 6), (40, 4)])
    records, _ = diagnostics.spans()
    by_id = {r.id: r for r in records}
    children = {}
    for r in records:
        if r.parent:
            p = by_id[r.parent]
            assert p.t0_ns <= r.t0_ns <= r.t1_ns <= p.t1_ns
            assert p.thread == r.thread
            children.setdefault(p.id, []).append(r)
    names = {r.name: {c.name for c in children.get(r.id, ())}
             for r in _named(records, "engine.admit")}
    assert names[P + "engine.admit"] >= {
        P + "engine.admit.plan", P + "engine.admit.prefill",
        P + "engine.admit.register"}
    for r in _named(records, "engine.land"):
        assert [c.name for c in children[r.id]
                if c.name.startswith(P + "engine")] == [
                    P + "engine.land.fence"]
    for pid, kids in children.items():
        p = by_id[pid]
        self_ns = (p.t1_ns - p.t0_ns) - sum(
            c.t1_ns - c.t0_ns for c in kids)
        assert self_ns >= 0
    assert all(r.parent == 0 for r in _named(records, "request"))


def test_an_admission_says_what_it_admitted():
    eng = _engine()
    _run(eng, [(10, 3)])
    base = eng.kv_stats()
    diagnostics.spans_on()
    _run(eng, [(10, 4), (12, 4), (30, 4)])
    records, _ = diagnostics.spans()
    admits = _named(records, "engine.admit")
    assert admits[0].attrs == {"rows": 16 + 16 + 32, "fenced": 0}
    # the rows its dispatches ran and whether a step was in the air are
    # kv_stats()'s to count (no reduction read them off the span)
    st = eng.kv_stats()
    assert st["prefill_rows_run"] - base["prefill_rows_run"] == 16 + 16 + 32
    assert st["admissions_under_flight"] == base["admissions_under_flight"]
    # ONE prefill span a group: the two prompts of bucket 16, the one of 32
    pre = [r for r in _named(records, "engine.admit.prefill")
           if r.parent == admits[0].id]
    assert len(pre) == 2
    # the burst's sampler is dispatched there and nothing is fetched: the
    # three first tokens come back under the `land.fence` of the decode
    # step that read them
    (sample,) = [r for r in _named(records, "engine.admit.sample")
                 if r.parent == admits[0].id]
    assert max(r.t1_ns for r in pre) <= sample.t0_ns
    st = eng.kv_stats()
    assert (st["admissions"], st["admissions_unfenced"]) == (2, 2)
    fence = min(_named(records, "engine.land.fence"), key=lambda r: r.t0_ns)
    assert admits[0].t1_ns <= fence.t0_ns


def test_no_admit_span_round_the_empty_call_of_a_decode_turn():
    eng = _engine()
    _run(eng, [(10, 3)])
    diagnostics.spans_on()
    _run(eng, [(10, 12)])
    records, _ = diagnostics.spans()
    assert len(_named(records, "engine.admit")) == 1
    assert len(_named(records, "engine.decode")) == 11


@pytest.mark.parametrize("kind", KINDS)
def test_a_chunked_prompts_request_span(kind):
    """A prompt of two chunks: two admissions, neither fenced (the first
    draws no token, the second's stays on the device); the request's span
    runs from its arrival, and `queue_ms` to its slot."""
    eng = _engine(kind)
    _run(eng, [(50, 3)], seed=100)    # other tokens: no page to hit
    base = eng.kv_stats()
    diagnostics.spans_on()
    (req,) = _run(eng, [(50, 6)])
    records, _ = diagnostics.spans()
    (life,) = _named(records, "request")
    assert 0 < req.t_arrive_ns == life.t0_ns <= req.t_slot_ns <= life.t1_ns
    assert life.attrs == {
        "queue_ms": (req.t_slot_ns - req.t_arrive_ns) / 1e6}
    assert len(_named(records, "engine.decode")) == 5   # the first token
    #                                              is the admission's
    admits = _named(records, "engine.admit")
    assert [r.attrs["fenced"] for r in admits] == [0, 0]
    st = eng.kv_stats()
    assert st["admissions"] - base["admissions"] == 2
    assert st["admissions_under_flight"] == base["admissions_under_flight"]
    # what each chunk's dispatch worked on: the first over no cached
    # prefix, the second over the first chunk's tokens
    pre = _named(records, "engine.admit.prefill")
    assert [r.attrs for r in pre] == [
        {"bucket": 32, "tokens": (32,), "prefix": (0,)},
        {"bucket": 32, "tokens": (18,), "prefix": (32,)}]
    # the slot came with the SECOND admission: the wait spans the first
    assert admits[0].t1_ns <= admits[1].t0_ns <= req.t_slot_ns
    assert req.t_slot_ns <= admits[1].t1_ns


def test_a_preempted_request_keeps_its_first_slots_stamp():
    eng = _engine(max_slots=2, num_pages=4)
    _run(eng, [(10, 3)])
    diagnostics.spans_on()
    reqs = [eng.request(eng.add_request(_ids(10, k + 1), 20, 0.0))
            for k in range(2)]
    eng.step()
    first = [r.t_slot_ns for r in reqs]
    assert all(first)
    while eng.has_work():
        eng.step()
    records, _ = diagnostics.spans()
    assert [len(r.generated) for r in reqs] == [20, 20]
    assert eng.kv_stats()["preemptions"] >= 1
    assert len(_named(records, "engine.admit")) >= 2  # one came back
    assert [r.t_slot_ns for r in reqs] == first
    assert sorted(r.attrs["queue_ms"] for r in _named(records, "request")
                  ) == sorted((r.t_slot_ns - r.t_arrive_ns) / 1e6
                              for r in reqs)


def test_a_compile_while_recording_is_a_span_under_its_caller():
    diagnostics.spans_on()
    eng = _engine(max_slots=5, max_len=96)    # shapes no other test has
    _run(eng, [(10, 3)])
    records, _ = diagnostics.spans()
    by_id = {r.id: r for r in records}
    xla = [r for r in records if r.name in ("xla.compile", "xla.trace")]
    assert xla and all(r.t1_ns - r.t0_ns >= 1e6 for r in xla)
    assert {by_id[r.parent].name for r in xla if r.parent} >= {
        P + "engine.decode"}


def test_the_programs_have_names_and_recording_changes_no_text():
    eng = _engine()
    _run(eng, [(10, 3)])
    fn = eng._decode_paged[next(iter(eng._decode_paged))]
    B = eng.e.max_slots
    args = (eng.params, *eng._pools(), *eng.rows,
            jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32),
            jnp.zeros((B,), bool), jnp.zeros((B, 1), jnp.int32))
    off = fn.lower(*args).as_text()
    diagnostics.spans_on()
    with diagnostics.span("ray_tpu.engine.decode", step=1, ahead=0):
        on = fn.lower(*args).as_text()
    assert on == off and "module @jit_decode_paged " in off
    pre = eng._prefill_batches[next(iter(eng._prefill_batches))]
    assert pre.__wrapped__.__name__ == "prefill_batch"


def test_the_spans_lie_in_a_profilers_trace_with_their_attributes(tmp_path):
    from jax.profiler import ProfileData
    eng = _engine()
    _run(eng, [(10, 3)])
    diagnostics.spans_on()
    jax.profiler.start_trace(str(tmp_path))
    try:
        _run(eng, [(10, 6)])
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    events = [e for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events
              if e.name.startswith(P)]
    names = {e.name for e in events}
    assert names >= {P + "engine.admit", P + "engine.admit.prefill",
                     P + "engine.decode", P + "engine.land",
                     P + "engine.land.fence"}
    dec = [dict(e.stats) for e in events if e.name == P + "engine.decode"]
    ring = _named(diagnostics.spans()[0], "engine.decode")
    assert sorted((d["step"], d["ahead"]) for d in dec) == [
        (r.attrs["step"], r.attrs["ahead"]) for r in ring]


def test_the_spans_say_what_each_step_worked_on():
    """`engine.decode` carries the `lengths` operand of its active slots,
    `engine.admit.prefill` its bucket and each real request's new and
    cached tokens; `prefill_real_rows_pct` and the padded rows agree with
    what the test prefilled and with `kv_stats()`'s deltas."""
    from perfbench.harness import ring_steps
    eng = _engine()
    _run(eng, [(10, 3)])
    base = eng.kv_stats()
    diagnostics.spans_on()
    _run(eng, [(10, 4), (12, 4), (30, 4)])
    _run(eng, [(9, 2), (11, 2), (13, 2)], seed=50)   # a batch padded to 4
    records, _ = diagnostics.spans()
    end = eng.kv_stats()
    dec = _named(records, "engine.decode")
    # 3 steps of the first burst (the first token is the admission's),
    # 1 of the second
    assert [r.attrs["lengths"] for r in dec] == [
        (10, 12, 30), (11, 13, 31), (12, 14, 32), (9, 11, 13)]
    pre = ring_steps.prefills([tuple(r) for r in records])
    assert pre == [{"bucket": 16, "tokens": [10, 12], "prefix": [0, 0]},
                   {"bucket": 32, "tokens": [30], "prefix": [0]},
                   {"bucket": 16, "tokens": [9, 11, 13], "prefix": [0, 0, 0]}]
    rows = ring_steps.prefill_rows(pre)
    assert rows["tokens"] == 10 + 12 + 30 + 9 + 11 + 13
    assert rows["request_rows"] == sum(
        r.attrs["rows"] for r in _named(records, "engine.admit"))
    assert rows["padded_rows"] == (end["prefill_rows_bucketed"]
                                   - base["prefill_rows_bucketed"]) == 128
    assert ring_steps.prefill_real_rows_pct(pre) == 100.0 * 85 / 112
    # plain ints, not numpy's: a reader writes the records out as JSON
    assert all(type(x) is int for r in dec for x in r.attrs["lengths"])


# ------------------------------------------------------------------ pump


def _replica(kind):
    from perfbench.harness import serve_cell
    from perfbench.harness.record import Record
    rec = Record(tracing=True)
    rep = serve_cell.Replica(
        MODELS[kind], EngineConfig(
            max_slots=3, max_len=160, page_size=16, prompt_buckets=(16, 32),
            eos_token=-1), 5, rec)
    return rep, rec


@pytest.fixture(scope="module")
def replica():
    rep, rec = _replica("per_head")
    yield rep, rec
    rep.stop()


def _serve(rep, lengths, new=6):
    from perfbench.harness import traffic
    done = threading.Semaphore(0)
    sinks = [rep.submit(traffic.Req(k, 0.0, n, new, True), _ids(n, k + 1),
                        time.perf_counter(), lambda s: done.release())
             for k, n in enumerate(lengths)]
    for _ in sinks:
        assert done.acquire(timeout=120)
    return sinks


def test_pump_idle_is_one_span_a_quiet_stretch(replica):
    rep, _rec = replica
    _serve(rep, [10])
    diagnostics.spans_on()
    _serve(rep, [10, 12])
    time.sleep(0.08)              # dozens of the pump's 2 ms sleeps
    _serve(rep, [14])
    time.sleep(0.02)
    records, _ = diagnostics.spans()
    pump = sorted((r for r in records if r.name.startswith(P + "pump.")),
                  key=lambda r: r.t0_ns)
    kinds = [r.name[len(P + "pump."):] for r in pump]
    assert kinds.count("idle") == 1          # the last stretch is still open
    i = kinds.index("idle")
    assert kinds[i - 1] == "fanout" and kinds[i + 1] == "step"
    assert pump[i].t1_ns - pump[i].t0_ns >= 0.06e9
    assert pump[i - 1].t1_ns <= pump[i].t0_ns <= pump[i].t1_ns <= (
        pump[i + 1].t0_ns)
    # a turn is a step and the fan-out after it
    steps = [r for r in pump if r.name == P + "pump.step"]
    assert kinds.count("fanout") == len(steps)
    assert all(a == "step" and b == "fanout" for a, b in zip(
        *[iter(k for k in kinds if k != "idle")] * 2))
    # every engine span of the pump's thread lies under a pump.step
    by_id = {r.id: r for r in records}
    for r in records:
        if r.name.startswith(P + "engine.") and r.thread == steps[0].thread:
            top = r
            while top.parent:
                top = by_id[top.parent]
            assert top.name == P + "pump.step"


def test_off_the_pump_builds_no_attribute_and_reads_no_new_clock(
        replica, monkeypatch):
    """`_loop` with recording off: no `set()` is reached (`tokens` and
    `firsts` are counted under `if sp.on:`), and the pump's thread reads
    the ns clock once an admission, as the engine alone does."""
    rep, _rec = replica
    _serve(rep, [10])
    assert not diagnostics.recording()
    sets, clock, queued = _watch_off(monkeypatch, rep.engine)
    sinks = _serve(rep, [10, 12, 40], new=6)
    assert all(len(s.times) == 5 for s in sinks)    # the stream swallows one
    assert sets == [] and len(clock) == len(queued) >= 1
    assert diagnostics.spans() == ([], 0)


def test_the_harness_outside_timings_still_read_with_recording_on(replica):
    """The benchmark's instance-level wrap of `_admit` and `step` (its
    seven `program_span` metrics) sees what it saw."""
    from perfbench.harness import program_spans, serve_cell
    rep, rec = replica
    _serve(rep, [10])
    diagnostics.spans_on()
    rec.samples.clear()
    rep.steps.clear()
    rep.t_open, rep.t_close = time.perf_counter(), float("inf")
    sinks = _serve(rep, [10, 12, 40], new=8)
    rep.t_close = time.perf_counter()
    assert all(serve_cell.request_ok(s) and s.admit_t > 0 for s in sinks)
    # the wrapper's `admit_t` (queue_wait_ms) is the start of the `_admit()`
    # that gave the request its slot: the request's own `t_slot_ns`, read
    # a few statements later inside that call
    assert all(0 <= s.engine_req.t_slot_ns / 1e9 - s.admit_t < 0.025
               for s in sinks)
    assert len(rec.samples["decode_step_ms"]) == len(rep.steps) > 0
    assert {"active", "gaps", "admit_worked", "lengths"} <= set(rep.steps[0])
    # and the program's own spans reduce beside them
    program_spans.collect(rep, rec, None)
    assert rec.values["prog.spans_dropped"] == 0
    assert len(rec.samples["prog.req_queue_ms"]) == 3
    # no admission of this traffic waits for its first tokens any more
    assert "prog.prefill_fenced_ms_per_krow" not in rec.samples
    assert len(rec.samples["prog.step_host_ms"]) >= 1
    assert len(rec.samples["prog.admit_unfed_ms"]) >= 1
    assert 0 <= rec.values["prog.steps_ahead_pct"] <= 100


# ------------------------------- the wrapper's series, from the ring alone


@pytest.fixture(scope="module", params=KINDS)
def both(request):
    """One run of a replica with the wrapper AND recording on: the
    wrapper's lists, the ring's records and the turns they lie in. Two
    busy stretches; admissions behind a step in flight; a chunked prompt
    (50 tokens over buckets of 32)."""
    from perfbench.harness import program_spans
    kind = request.param
    rep, rec = (request.getfixturevalue("replica") if kind == "per_head"
                else _replica(kind))
    try:
        _serve(rep, [10, 50], new=3)      # compiled before recording
        diagnostics.spans_on()
        rec.samples.clear()
        rep.steps.clear()
        rep.t_open, rep.t_close = time.perf_counter(), float("inf")
        _serve(rep, [10, 12, 40], new=8)
        time.sleep(0.03)
        _serve(rep, [14, 50, 9, 21], new=5)
        time.sleep(0.03)                  # the last fan-out is in the ring
        rep.t_close = time.perf_counter()
        records = [tuple(r) for r in diagnostics.spans()[0]]
        yield {"engine": rep.engine.e,
               "wrapper": list(rep.steps), "records": records,
               "decode_step_ms": list(rec.samples["decode_step_ms"]),
               "admit_work_ms": list(rec.samples.get("admit_work_ms", ())),
               "turns": program_spans.turns(records)}
    finally:
        diagnostics.spans_off()
        if kind != "per_head":
            rep.stop()


def _pairs(both):
    """(the wrapper's entry or None, the ring's entry or None, the turn) a
    turn that has either: the wrapper's `t0..t1` lies inside its turn's
    `pump.step`."""
    from perfbench.harness import program_spans, ring_steps
    ring = {s["t0"]: s for s in ring_steps.steps(both["records"])}
    out = []
    for t in both["turns"]:
        a, b = t["step"][3] / 1e9, t["step"][4] / 1e9
        mine = [w for w in both["wrapper"] if a <= w["t0"] and w["t1"] <= b]
        assert len(mine) <= 1
        r = ring.get(a)
        assert (r is not None) == (program_spans.DECODE in t["inside"])
        if mine or r is not None:
            out.append((mine[0] if mine else None, r, t))
    assert sum(1 for w, _r, _t in out if w) == len(both["wrapper"])
    return out


def test_the_ring_rebuilds_the_wrappers_steps_turn_for_turn(both):
    """ring_steps' docstring, field by field, for EVERY step."""
    from perfbench.harness import ring_steps
    pairs = _pairs(both)
    assert sum(1 for w, r, _t in pairs if w and r) >= 10
    for w, r, _t in pairs:
        if r is None:
            # the last turn of a stretch: tokens came back, nothing went out
            assert w["lengths"] == [] and w["active"] > 0
        elif w is None:
            # the first: a step went out, nothing came back
            assert r["tokens"] == 0 and r["lengths"]
        else:
            assert w["lengths"] == r["lengths"]
            assert w["active"] == r["tokens"] > 0
            assert w["gaps"] == r["gaps"] + r["firsts"]
            assert r["gaps"] >= 0 and w["admit_worked"] <= r["admit_worked"]
    # the tokens a call returns are those of the step the call before
    # dispatched: the wrapper's `active` is one step behind the ring's
    ring = [r for _w, r, _t in pairs if r]
    for before, r in zip(ring, ring[1:]):
        if r["tokens"]:
            assert r["tokens"] == before["active"]
    # two stretches, so two entries that only one list has, on either side
    assert sum(1 for w, r, _t in pairs if r is None) >= 2
    assert sum(1 for w, r, _t in pairs if w is None) >= 2
    # every request's first streamed token was counted once
    assert sum(f[6]["firsts"] for t in both["turns"]
               if (f := t["fanout"]) is not None) == 7
    # an admission the wrapper saw at work is one the ring has
    assert len(both["admit_work_ms"]) <= len(ring_steps.admit_work_ms(
        both["records"]))


def test_decode_step_ms_from_the_ring_has_the_wrappers_samples(both):
    """The same count, and each sample the wrapper's within 25 ms above it
    (the ring's starts no later and ends no earlier: the pump's span lies
    round the wrapper's call; on a shared CPU a thread may lose its core
    in between), the median within 1 ms. A fence BEFORE the dispatch (the
    host could not lead) lies in the ring's reading alone."""
    from perfbench.harness import program_spans, ring_steps
    got = ring_steps.decode_step_ms(both["records"])
    want = both["decode_step_ms"]
    assert len(got) == len(want) > 10
    turns = [t for t in both["turns"]
             if t["fanout"] is not None and t["fanout"][6]["tokens"]]
    diffs = []
    for g, w, t in zip(got, want, turns):
        inside = t["inside"]
        early = 0.0
        if program_spans.ADMIT not in inside:
            # a fence that ended before the turn's dispatch began (a turn
            # that dispatches nothing fetches last, after its `_admit()`)
            first = min((r[3] for r in inside.get(program_spans.DECODE, ())),
                        default=0)
            early = sum((r[4] - r[3]) / 1e6 for r in inside.get(
                program_spans.FENCE, ()) if r[4] <= first)
        assert -1e-6 <= g - w - early <= 25.0
        diffs.append(g - w - early)
    assert sorted(diffs)[len(diffs) // 2] < 1.0


COST_MODELS = {
    "paged_attn": configs.tiny, "latent_attn": configs.tiny_mla,
    "looped_attn": configs.tiny_ouro, "swa_decode": configs.tiny_laguna,
    "ssm_update": configs.tiny_hybrid, "selective_update": configs.tiny_jamba,
    "kda_update": configs.tiny_solar_open2,
}


@pytest.mark.parametrize("kernel", sorted(COST_MODELS))
def test_a_cost_function_counts_the_same_from_either_list(both, kernel):
    """Each of the seven cost functions that read `rec.context["steps"]`:
    the same (operations, bytes) from the wrapper's entries and from the
    ring's of the same turns. The wrapper's whole list has its entries of
    a stretch's LAST turn besides, which dispatched nothing: no slot, so
    nothing but what a cost function counts once a call whatever the slots
    (`selective_update.py`: A's bytes, for a kernel that never ran). The
    ring's whole list has the steps the wrapper never saw, a stretch's
    first, whose kernels ran all the same."""
    from perfbench.harness import cells, ring_steps
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    mod = cells.load_module(os.path.join(root, "perfbench", "kernels",
                                         kernel + ".py"))
    pairs = _pairs(both)
    ctx = {"model": COST_MODELS[kernel](), "engine": both["engine"]}

    def cost(steps):
        return mod.cost({**ctx, "steps": steps})

    def plus(a, b):
        return tuple(x + y for x, y in zip(a, b))

    same = cost([w for w, r, _t in pairs if w and r])
    assert same is not None and min(same) > 0
    assert cost([r for w, r, _t in pairs if w and r]) == same
    empty = cost([w for w, r, _t in pairs if r is None])
    assert empty[0] == 0 and (empty[1] == 0) == (kernel != "selective_update")
    assert cost(both["wrapper"]) == plus(same, empty)
    alone = cost([r for w, r, _t in pairs if w is None])
    assert min(alone) > 0
    assert cost(ring_steps.steps(both["records"])) == plus(same, alone)
