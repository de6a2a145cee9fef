"""A serving cell: the replica (`_LLMServerImpl`) in this process, its own
pump thread doing every engine step; the benchmark adds requests as
`completions_stream` does (engine.add_request under the replica's lock plus
a token subscription) and reads the clock where the pump hands each token
to the subscriber. It never calls engine.step() itself.
"""

from __future__ import annotations

import math
import queue
import sys
import threading
import time

from perfbench.harness import traffic as traffic_mod

ADMIT_WORK_MS = 2.0   # an _admit that launched a prefill streams the
#                       weights once (>= 5 ms at these sizes); one that
#                       found nothing to admit returns in microseconds
WAIT_LIMIT_S = 240.0  # no single wait of the harness may outlast this


class Sink:
    """Stands where completions_stream puts a queue.Queue: the pump calls
    put(token) for every token and put(None) at the end of the stream."""

    __slots__ = ("req", "due", "sent", "times", "done", "engine_req",
                 "on_done", "admit_t")

    def __init__(self, req, due: float, on_done=None):
        self.req = req
        self.due = due              # perf_counter time it was due
        self.sent = 0.0
        self.times: list[float] = []
        self.done = 0.0
        self.engine_req = None
        self.on_done = on_done
        self.admit_t = 0.0

    def put(self, tok):
        t = time.perf_counter()
        if tok is None:
            self.done = t
            if self.on_done is not None:
                self.on_done(self)
        else:
            self.times.append(t)


class Replica:
    """The system under test plus the benchmark's handle on it."""

    def __init__(self, model_cfg, engine_cfg, seed: int, rec):
        from ray_tpu.llm import LLMConfig
        from ray_tpu.llm.serve import _LLMServerImpl
        self.rec = rec
        self.server = _LLMServerImpl(LLMConfig(
            model_id="perfbench", model=model_cfg, engine=engine_cfg,
            seed=seed))
        self.engine = self.server.engine
        self.sinks: dict[int, Sink] = {}
        self.t_open = math.inf      # spans are kept only inside the window
        self.t_close = math.inf
        self.steps: list[dict] = []  # decode steps (traced runs only)
        if rec.tracing:
            self._wrap_engine()

    # ---- the benchmark's spans round the engine's calls (traced runs) ----

    def _wrap_engine(self):
        from jax.profiler import TraceAnnotation
        eng, rec = self.engine, self.rec
        admit0, step0 = eng._admit, eng.step
        state = {"admit_end": 0.0, "admit_ms": 0.0, "ann": None}

        def admit():
            t0 = time.perf_counter()
            with TraceAnnotation("bench.step_admit"):
                out = admit0()
            t1 = time.perf_counter()
            state["admit_end"], state["admit_ms"] = t1, (t1 - t0) * 1e3
            for rid in out:
                s = self.sinks.get(rid)
                if s is not None:
                    s.admit_t = t0
            # the rest of step() is the decode dispatch, sampling and the
            # host fence that fetches the tokens
            state["ann"] = TraceAnnotation("bench.step_decode")
            state["ann"].__enter__()
            return out

        def step():
            state["ann"] = None
            t0 = time.perf_counter()
            active_before = eng.active.copy()
            out = step0()
            t1 = time.perf_counter()
            if state["ann"] is not None:
                state["ann"].__exit__(None, None, None)
            if not (self.t_open <= t0 < self.t_close):
                return out
            worked = state["admit_ms"] >= ADMIT_WORK_MS
            if worked:
                rec.sample("admit_work_ms", state["admit_ms"])
            # slots that decoded this step: active after admission
            n_dec = len(out) if out else 0
            if n_dec:
                rec.sample("decode_step_ms", (t1 - state["admit_end"]) * 1e3)
                # gaps this step closed: tokens of slots that were already
                # streaming before it
                n_gaps = int(active_before.sum())
                self.steps.append({
                    "t0": t0, "t1": t1, "active": n_dec,
                    "gaps": n_gaps, "admit_worked": worked,
                    "lengths": eng.lengths[eng.active].tolist()})
            return out

        eng._admit, eng.step = admit, step

    # ---- requests ----

    def submit(self, req, ids: list, due: float, on_done=None,
               logprobs: bool = False) -> Sink:
        """As completions_stream does it, with token ids instead of text
        (random-weight ids do not survive the byte tokenizer)."""
        sink = Sink(req, due, on_done)
        with self.rec.span("add_request"):
            with self.server._lock:
                rid = self.engine.add_request(
                    ids, req.output_tokens, 0.0, logprobs=logprobs)
                self.server._token_subs[rid] = sink
                sink.engine_req = self.engine.request(rid)
                self.sinks[rid] = sink
        sink.sent = time.perf_counter()
        return sink

    def submit_in_pump(self, req, ids: list, on_done) -> Sink:
        """From a sink callback: the pump thread already holds the
        replica's lock and is between two steps, so what is added here is
        admitted TOGETHER by the next step (warm-up needs that)."""
        sink = Sink(req, time.perf_counter(), on_done)
        rid = self.engine.add_request(ids, req.output_tokens, 0.0)
        self.server._token_subs[rid] = sink
        sink.engine_req = self.engine.request(rid)
        self.sinks[rid] = sink
        return sink

    def stop(self):
        self.server._stop = True
        self.server._pump.join(timeout=30)


def request_ok(sink: Sink) -> bool:
    r = sink.engine_req
    return (sink.done > 0 and r is not None
            and len(r.generated) == sink.req.output_tokens)


# ---------------------------------------------------------------- warm-up


def warm_waves(traffic: dict, engine_cfg, together: int) -> list[list]:
    """Waves of (prompt, output) lengths; a wave is admitted in ONE engine
    step. Covers every prefill program the cell's prompt lengths can
    reach — (requests admitted together padded to a power of two) x
    (prompt bucket), the chunked path's continuation over a cached prefix —
    the sampler's batch of 1..together first tokens, and every decode
    page bucket up to the cell's longest sequence."""
    lo = int(traffic["prompt_tokens"]["min"])
    hi = int(traffic["prompt_tokens"]["max"])
    out_hi = int(traffic["output_tokens"]["max"])
    page = engine_cfg.page_size
    buckets = sorted(b for b in engine_cfg.prompt_buckets
                     if b <= engine_cfg.max_len)
    chunk = (max(buckets) // page) * page
    reps: list[int] = []          # one representative prompt per program
    prev = 0
    for b in buckets:
        a, z = max(lo, prev + 1), min(hi, b)
        if a <= z:
            reps.append(z)
        prev = b
    if hi > max(buckets):         # chunked prefill: chunk, then the rest
        prev = 0
        for b in buckets:
            a = max(lo, max(buckets) + 1, chunk + prev + 1)
            z = min(hi, chunk + b)
            if a <= z:
                reps.append(z)
            prev = b
    waves = [[(n, 2)] * k for n in reps for k in range(1, together + 1)]
    # decode page buckets: a sequence of n tokens decodes against
    # ceil((n + 1) / page) pages, bucketed to a power of two
    longest = min(hi + out_hi, engine_cfg.max_len - 1)
    p, seen = 1, set()
    while True:
        n = min(p * page - 8, hi, longest - 4)
        if n >= lo and n not in seen:
            seen.add(n)
            waves.append([(n, 3)])
        if p * page >= longest:
            break
        p *= 2
    # long outputs grow INTO a page bucket no prompt starts in
    if hi + out_hi > hi + 3:
        waves.append([(hi, min(out_hi, page + 8))])
    return waves


def run_waves(rep: Replica, waves: list, seed: int, vocab: int) -> int:
    """Each wave enters from the pump thread when the last request of the
    wave before it ended. Returns the requests run."""
    finished = threading.Event()
    state = {"i": 0, "left": 0, "n": 0, "bad": 0}

    def start_wave(submit):
        wave = waves[state["i"]]
        state["left"] = len(wave)
        for p, o in wave:
            state["n"] += 1
            req = traffic_mod.Req(-state["n"], 0.0, p, o, False)
            submit(req, traffic_mod.prompt_ids(seed, 2 * 10**6 + state["n"], p,
                                            vocab))

    def on_done(sink):
        if not request_ok(sink):
            state["bad"] += 1
        state["left"] -= 1
        if state["left"]:
            return
        state["i"] += 1
        if state["i"] >= len(waves):
            finished.set()
        else:
            start_wave(lambda r, ids: rep.submit_in_pump(r, ids, on_done))

    if waves:
        start_wave(lambda r, ids: rep.submit(
            r, ids, time.perf_counter(), on_done))
        if not finished.wait(WAIT_LIMIT_S * 4):
            raise RuntimeError(
                f"warm-up stalled in wave {state['i']} of {len(waves)}")
    if state["bad"]:
        raise RuntimeError(f"{state['bad']} warm-up requests returned other "
                           f"than the tokens asked for")
    rep.sinks.clear()
    return state["n"]


# ------------------------------------------------------------ correctness


def reference_diffs(rep: Replica, reference, model_cfg, seed: int,
                    index: int = 10**6, n_prompt: int = 200, n_new: int = 16,
                    fault=None) -> dict:
    """One seeded sequence: prefill, then `n_new` greedy tokens through the
    paged cache; |log-probability - the plain reference's| for every
    generated token, with the reference's router margin at its position.
    `reference` is the configuration's own (cells.load_reference).
    `fault(model_cfg, ids) -> (model_cfg, ids)` changes what the REFERENCE
    is given (tools/checkdist.py: what a wrong program would read)."""
    ids = traffic_mod.prompt_ids(seed, index, n_prompt, model_cfg.vocab)
    req = traffic_mod.Req(-1, 0.0, n_prompt, n_new, False)
    done = threading.Event()
    sink = rep.submit(req, ids, time.perf_counter(),
                      on_done=lambda s: done.set(), logprobs=True)
    if not done.wait(WAIT_LIMIT_S):
        raise RuntimeError("the correctness request never finished")
    r = sink.engine_req
    rep.sinks.clear()
    gen = list(r.generated)
    got = [float(x) for x in r.token_logprobs]
    ref_cfg, ref_ids = fault(model_cfg, ids) if fault else (model_cfg, ids)
    want, margin = reference.logprobs_of(rep.engine.params, ref_cfg, ref_ids,
                                         gen)
    return {"diffs": [abs(a - b) for a, b in zip(got, want)],
            "margins": [m if math.isfinite(m) else None for m in margin],
            "finite": all(math.isfinite(x) for x in got + want),
            "complete": len(gen) == n_new == len(got) == len(want)}


def judge(d: dict, tol: float, rule: dict | None = None) -> dict:
    """The verdict on reference_diffs' output.

    A dense model: every token within `tol`.

    A configuration with a `check` rule (hard top-k routing, so its
    function is not continuous): where bf16 rounding upstream flips an
    expert AT a token, that token's log-probability moves by up to whole
    units in either program (2.7 seen on the chip), and only where the
    reference's own router margin is small (every flip seen: under 0.065).
    So a token is held to a tolerance only where the reference's margin
    is at least `clear_router_margin`, the reference's doing and none of
    the program's. Flips at tokens of the context reach such a token
    thinned by attention (0.115 seen at most): it holds `clear_tol_x` times
    `tol`, still under what one wrong token in a context of 200 shows
    (0.34 at least). The median over ALL tokens holds `tol`. PERF.md
    section 2 has the measurements."""
    diffs, margins = d["diffs"], d["margins"]
    over = [[x, m] for x, m in zip(diffs, margins) if x > tol]
    median = sorted(diffs)[len(diffs) // 2] if diffs else None
    sound = d["complete"] and d["finite"] and bool(diffs)
    out = {"max_abs_diff": max(diffs, default=None),
           "median_abs_diff": median, "tol": tol, "tokens": len(diffs),
           "over_tol_diff_and_router_margin": over}
    if rule:
        clear = [x for x, m in zip(diffs, margins)
                 if m is None or m >= rule["clear_router_margin"]]
        clear_tol = rule["clear_tol_x"] * tol
        ok = (sound and median <= tol
              and all(x <= clear_tol for x in clear))
        out.update(clear_tokens=len(clear),
                   max_abs_diff_clear=max(clear, default=None),
                   clear_tol=clear_tol)
    else:
        ok = sound and not over
    return {"ok": bool(ok), **out}


def check_against_reference(rep: Replica, reference, model_cfg, seed: int,
                            tol: float, rule: dict | None = None) -> dict:
    n_new = int(rule["new_tokens"]) if rule else 16
    return judge(reference_diffs(rep, reference, model_cfg, seed,
                                 n_new=n_new), tol, rule)


# ------------------------------------------------------------------ loops


def _engine_counters(rep: Replica) -> dict:
    from ray_tpu import diagnostics
    kv = rep.engine.kv_stats()
    # A shape that was not warmed up shows as a backend compile in a
    # checkout's first run and only as a trace once the persistent cache
    # holds it: the larger of the two deltas counts both.
    return {"jit_misses": diagnostics.jit_misses(),
            "jit_traces": diagnostics.jit_traces(),
            "preemptions": kv.get("preemptions", 0),
            "prefix_hits": kv.get("prefix_hits", 0)}


def _counter_deltas(rec, base: dict, end: dict):
    for k in base:
        rec.counters[k] = end[k] - base[k]
    rec.counters["new_programs"] = max(rec.counters["jit_misses"],
                                       rec.counters["jit_traces"])


def _trace_slice(rec, t0: float, at_s: float, length_s: float,
                 trace_dir: str):
    """Profile `length_s` seconds starting `at_s` into the window, in the
    calling thread; the pump goes on. Keeps the decode steps of that slice
    for the kernels' cost functions."""
    import jax
    from jax.profiler import TraceAnnotation
    time.sleep(max(0.0, t0 + at_s - time.perf_counter()))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    a = time.perf_counter()
    with TraceAnnotation("bench.window"):
        time.sleep(length_s)
    b = time.perf_counter()
    jax.profiler.stop_trace()
    rec.context["trace_host_interval"] = (a, b)


def run_open_loop(rep: Replica, rec, traffic: dict, cellp: dict, seed: int,
                  seconds: float, vocab: int, trace_dir: str | None,
                  keep_sinks: bool = False) -> dict:
    rate = float(cellp["rate_rps"])
    ramp_s = float(cellp["ramp_s"])
    drain_s = float(cellp.get("drain_limit_s", 60.0))
    sched = traffic_mod.open_loop_schedule(
        traffic, rate, seconds, ramp_s, drain_s, seed)
    ids = [traffic_mod.prompt_ids(seed, r.index, r.prompt_tokens, vocab)
           for r in sched]
    counted = [r for r in sched if r.counted]
    left = threading.Semaphore(0)
    sinks: list[Sink] = []
    stop = threading.Event()

    def on_done(sink):
        if sink.req.counted:
            left.release()

    t0 = time.perf_counter() + ramp_s + 0.05   # window opens here

    def generate():
        for r, tok in zip(sched, ids):
            due = t0 + r.due_s
            while not stop.is_set():
                wait = due - time.perf_counter()
                if wait <= 0:
                    break
                time.sleep(min(wait, 0.05))
            if stop.is_set():
                return
            sinks.append(rep.submit(r, tok, due, on_done))

    gen = threading.Thread(target=generate, name="perfbench-gen", daemon=True)
    gen.start()
    time.sleep(max(0.0, t0 - time.perf_counter()))
    base = _engine_counters(rep)
    rep.t_open, rep.t_close = t0, t0 + seconds
    opened_wall = time.time()
    print("perfbench: window open", file=sys.stderr, flush=True)
    if trace_dir:
        _trace_slice(rec, t0, float(cellp.get("trace_at_s", 4.0)),
                     float(cellp.get("trace_s", 3.0)), trace_dir)
    time.sleep(max(0.0, t0 + seconds - time.perf_counter()))
    end = _engine_counters(rep)
    # drain: the cool-down keeps arriving at the same rate meanwhile
    deadline = time.perf_counter() + drain_s
    drained = 0
    while drained < len(counted):
        if not left.acquire(timeout=max(0.0, deadline - time.perf_counter())):
            break
        drained += 1
    stop.set()
    gen.join(timeout=5)
    t_end = time.perf_counter()

    mine = [s for s in sinks if s.req.counted]
    failed = len(counted) - sum(1 for s in mine if request_ok(s))
    for s in mine:
        if not s.times:
            continue
        rec.sample("ttft_ms", (s.times[0] - s.due) * 1e3)
        rec.sample("gen_lag_ms", (s.sent - s.due) * 1e3)
        if s.admit_t:
            rec.sample("queue_wait_ms", (s.admit_t - s.due) * 1e3)
        for a, b in zip(s.times, s.times[1:]):
            rec.sample("itl_ms", (b - a) * 1e3)
    _counter_deltas(rec, base, end)
    rec.values["drain_s"] = t_end - (t0 + seconds)
    _step_values(rep, rec)
    return {"attempted": len(counted), "failed": failed,
            "opened_wall": opened_wall,
            "sinks": sinks if keep_sinks else None}


def run_closed_loop(rep: Replica, rec, traffic: dict, cellp: dict, seed: int,
                    seconds: float, vocab: int, trace_dir: str | None,
                    chips: int) -> dict:
    clients = int(cellp["clients"])
    ramp_s = float(cellp["ramp_s"])
    stagger = float(cellp.get("client_stagger_s", 0.25))
    est = int(cellp.get("requests_upper_bound", 4000))
    seq = traffic_mod.closed_loop_sequence(traffic, seed, est)
    n_ids = min(est, int(cellp.get("pregenerated", 1500)))
    ids = [traffic_mod.prompt_ids(seed, r.index, r.prompt_tokens, vocab)
           for r in seq[:n_ids]]
    todo: "queue.Queue" = queue.Queue()
    finished: list[Sink] = []
    offered: list[Sink] = []
    stop = threading.Event()

    def on_done(sink):
        finished.append(sink)
        todo.put(1)

    t_begin = time.perf_counter()
    t0 = t_begin + ramp_s

    def dispatch():
        for i in range(clients):       # callers join one by one
            while not stop.is_set() and (
                    time.perf_counter() < t_begin + i * stagger):
                time.sleep(0.01)
            todo.put(1)             # one caller ready to send

    def sender():
        nxt = 0
        while not stop.is_set():
            try:
                todo.get(timeout=0.05)
            except queue.Empty:
                continue
            if nxt >= len(ids):
                return
            offered.append(rep.submit(seq[nxt], ids[nxt],
                                      time.perf_counter(), on_done))
            nxt += 1

    threads = [threading.Thread(target=f, daemon=True, name=n)
               for f, n in ((dispatch, "perfbench-join"),
                            (sender, "perfbench-send"))]
    for t in threads:
        t.start()
    time.sleep(max(0.0, t0 - time.perf_counter()))
    base = _engine_counters(rep)
    rep.t_open, rep.t_close = t0, t0 + seconds
    opened_wall = time.time()
    print("perfbench: window open", file=sys.stderr, flush=True)
    if trace_dir:
        _trace_slice(rec, t0, float(cellp.get("trace_at_s", 4.0)),
                     float(cellp.get("trace_s", 3.0)), trace_dir)
    time.sleep(max(0.0, t0 + seconds - time.perf_counter()))
    end = _engine_counters(rep)
    t1 = t0 + seconds
    stop.set()
    for t in threads:
        t.join(timeout=5)
    inside = [s for s in list(finished) if t0 <= s.done < t1]
    ok = [s for s in inside if request_ok(s)]
    # All the work of the window and nothing else: a prompt's tokens count
    # when its prefill delivered the first token, a generated token when
    # the pump handed it over. (Counting whole requests at completion
    # leaves the 16 in flight at either edge to chance: 2.7 % of spread on
    # the chip against 0.5 % this way.) The stream swallows the first
    # token of a request (PERF.md section 7): it is counted with the first
    # one delivered.
    tokens = 0
    for s in list(offered):
        times = list(s.times)
        if times and t0 <= times[0] < t1:
            tokens += s.req.prompt_tokens + 1
        tokens += sum(1 for t in times if t0 <= t < t1)
    rec.values["tok_per_s_chip"] = tokens / seconds / chips
    rec.values["requests_per_s"] = len(ok) / seconds
    _counter_deltas(rec, base, end)
    _step_values(rep, rec)
    return {"attempted": len(inside), "failed": len(inside) - len(ok),
            "opened_wall": opened_wall}


def _step_values(rep: Replica, rec):
    """Occupancy and the share of gaps that held an admit, from the decode
    steps the spans saw inside the window (traced runs)."""
    steps = rep.steps
    if not steps:
        return
    slots = rep.engine.e.max_slots
    rec.values["batch_occupancy_pct"] = 100.0 * sum(
        s["active"] for s in steps) / (slots * len(steps))
    gaps = sum(s["gaps"] for s in steps)
    if gaps:
        rec.values["admit_gap_share_pct"] = 100.0 * sum(
            s["gaps"] for s in steps if s["admit_worked"]) / gaps
    iv = rec.context.get("trace_host_interval")
    if iv:
        rec.context["steps"] = [s for s in steps if iv[0] <= s["t0"] < iv[1]]


# ------------------------------------------------------------------- cell


def run(cell: dict, cfg: dict, traffic: dict, cellp: dict, args, rec,
        proc_start_wall: float, trace_dir: str | None) -> dict:
    import jax

    from perfbench.harness import cells, modelcfg
    model_cfg = modelcfg.model_config(cfg, traffic["kind"], args.rehearsal)
    engine_cfg = modelcfg.engine_config(cfg, cellp, args.rehearsal)
    reference = cells.load_reference(args.benchmark_root, cfg)
    jseed = int(args.seed) % (2**31 - 5)
    marks = [("start_to_replica", time.time())]   # where set-up goes
    rep = Replica(model_cfg, engine_cfg, jseed, rec)
    jax.block_until_ready(rep.engine.params)
    marks.append(("weights", time.time()))
    # what a kernel's cost function may read: the program's two configs,
    # and the configuration's file (the chip's share it states)
    rec.context.update(model=model_cfg, engine=engine_cfg, cfg=cfg)
    try:
        waves = warm_waves(traffic, engine_cfg,
                           int(cellp.get("warm_admit_together", 4)))
        n_warm = run_waves(rep, waves, jseed, model_cfg.vocab)
        marks.append(("warm_up", time.time()))
        tol = modelcfg.LOGPROB_TOL[model_cfg.dtype]
        check = check_against_reference(rep, reference, model_cfg, jseed,
                                        tol, cfg.get("check"))
        marks.append(("check", time.time()))
        if traffic["kind"] == "open_loop":
            out = run_open_loop(rep, rec, traffic, cellp, args.seed,
                                args.seconds, model_cfg.vocab, trace_dir)
        else:
            out = run_closed_loop(rep, rec, traffic, cellp, args.seed,
                                  args.seconds, model_cfg.vocab, trace_dir,
                                  cell["chips"])
    finally:
        rep.stop()
    rec.values["setup_s"] = out["opened_wall"] - proc_start_wall
    marks.append(("traffic_and_ramp", out["opened_wall"]))
    prev = proc_start_wall
    for name, t in marks:
        rec.values["setup." + name + "_s"] = t - prev
        prev = t
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    if peak:
        rec.values["hbm_peak_gib"] = peak / 2**30
    return {"correct": bool(check["ok"]) and out["failed"] == 0,
            "attempted": out["attempted"], "failed": out["failed"],
            "check": check, "warm_requests": n_warm,
            "memory_peak_bytes": peak}
