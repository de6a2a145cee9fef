"""Process-global diagnostics of the serving and training hot paths: the
jit compile counters, and the span recorder (further down).

The dynamic half of graphcheck's recompile gate (finding class 3): the
static pass can prove a *hazard* (weak types, per-call jit wrappers,
unstable static args), but whether a hot loop actually recompiles in
steady state is a runtime fact. `jit_misses()` is a monotonic counter of
backend compiles in this process — tests snapshot it, run N steady-state
steps, and assert the delta is zero:

    base = diagnostics.jit_misses()
    for _ in range(8):
        engine.step()
    assert diagnostics.jit_misses() == base

Implementation: jax.monitoring duration events. Every executable build
records '/jax/core/compile/backend_compile_duration' exactly once (the
jaxpr trace and jaxpr->MLIR stages record their own keys, counted
separately as `jit_traces()` — a tracing-cache miss that HITS the
persistent compilation cache still costs the trace). The listener is
registered at import, appends nothing per event but two int increments,
and is process-global — counters cover every engine/trainer/actor in
the process, which is exactly what a steady-state assertion wants.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import NamedTuple

_lock = threading.Lock()
_counts = {"compiles": 0, "traces": 0}
_installed = False

_COMPILE_KEY = "/jax/core/compile/backend_compile_duration"
_TRACE_KEY = "/jax/core/compile/jaxpr_trace_duration"
# Every jit inside a jit reports a trace of its own, hundreds a program and
# microseconds each: only an event long enough to keep a device waiting
# becomes a span.
_XLA_SPAN_MIN_S = 1e-3


def _listener(name: str, duration_secs: float = 0.0, **_kw) -> None:
    if name == _COMPILE_KEY:
        span_name = "xla.compile"
        with _lock:
            _counts["compiles"] += 1
    elif name == _TRACE_KEY:
        span_name = "xla.trace"
        with _lock:
            _counts["traces"] += 1
    else:
        return
    if _recorder is not None and duration_secs >= _XLA_SPAN_MIN_S:
        # the event comes with its duration, at its end: a compile inside
        # a measured window is a span with a name, under the span that
        # called the program
        t1 = time.perf_counter_ns()
        record(span_name, t1 - int(duration_secs * 1e9), t1)


def _install() -> None:
    global _installed
    if _installed:
        return
    import jax
    jax.monitoring.register_event_duration_secs_listener(_listener)
    _installed = True


_install()


def jit_misses() -> int:
    """Monotonic count of backend compiles in this process. A steady-state
    hot loop must hold this flat; every increment is a fresh executable
    (new shape bucket, weak-type fork, unstable static, dropped cache)."""
    with _lock:
        return _counts["compiles"]


def jit_traces() -> int:
    """Monotonic count of jaxpr traces (>= jit_misses: retraces that hit
    the executable cache still pay python tracing)."""
    with _lock:
        return _counts["traces"]


# ---- spans ------------------------------------------------------------
# The hot paths' own spans: the replica's pump (llm/serve.py) and the
# engine's scheduling (llm/engine.py) say where a turn's time goes, at the
# place where the work happens. One call site, two sinks: while recording
# is on a span is a jax.profiler.TraceAnnotation (so it lies in the
# profiler's trace, on the device trace's clock, whenever a profile is
# being taken) AND one record in a bounded ring (time.perf_counter_ns). Off,
# the default, `span()` is one read of a module global and a shared object
# that does nothing. PERF.md section 3 lists the names; they are API.


class Span(NamedTuple):
    """One record of the ring. `parent` is the id of the innermost span
    that was open on the same thread when this one began (0 = none);
    `attrs` holds what the call site gave at entry and through `set()`."""
    id: int
    parent: int
    name: str
    t0_ns: int
    t1_ns: int
    thread: str
    attrs: dict


class _NoSpan:
    """What `span()` returns while recording is off: ONE shared object, no
    clock read, nothing kept."""
    __slots__ = ()
    on = False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        pass


NO_SPAN = _NoSpan()


class _OpenSpans(threading.local):
    """A thread's open spans, innermost last."""

    def __init__(self):
        self.stack: list = []


class _Recorder:
    def __init__(self, capacity: int):
        self.ring: collections.deque = collections.deque(maxlen=capacity)
        self.dropped = 0
        self.ids = itertools.count(1)
        self.open = _OpenSpans()
        self.lock = threading.Lock()
        from jax.profiler import TraceAnnotation
        self.annotation = TraceAnnotation

    def append(self, rec: Span):
        with self.lock:
            if len(self.ring) == self.ring.maxlen:
                self.dropped += 1
            self.ring.append(rec)


class _LiveSpan:
    __slots__ = ("rec", "name", "attrs", "ann", "id", "parent", "t0")
    on = True

    def __init__(self, rec: _Recorder, name: str, attrs: dict):
        self.rec, self.name, self.attrs = rec, name, attrs

    def __enter__(self):
        self.ann = self.rec.annotation(self.name, **self.attrs)
        self.ann.__enter__()
        stack = self.rec.open.stack
        self.parent = stack[-1].id if stack else 0
        self.id = next(self.rec.ids)
        stack.append(self)
        self.t0 = time.perf_counter_ns()
        return self

    def set(self, **attrs):
        """What is known only at the span's end (`rows`, `fenced`):
        it goes to the ring's record; the trace's copy has what was given
        at entry."""
        self.attrs.update(attrs)

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        # innermost, as `with` blocks nest (a span left open under this
        # one would otherwise pass for the parent of all that follows)
        stack = self.rec.open.stack
        assert stack and stack[-1] is self, (self.name, len(stack))
        stack.pop()
        self.rec.append(Span(self.id, self.parent, self.name, self.t0, t1,
                             threading.current_thread().name, self.attrs))
        self.ann.__exit__(*exc)
        return False


_recorder: _Recorder | None = None   # None = recording is off


def span(name: str, **attrs):
    """A context manager round a piece of the hot path's host work. Pass
    as attributes only what the caller already holds (numbers, a name);
    build what costs (a list of ids) under `if sp.on:` and hand it to
    `sp.set()`."""
    rec = _recorder
    if rec is None:
        return NO_SPAN
    return _LiveSpan(rec, name, attrs)


def record(name: str, t0_ns: int, t1_ns: int, **attrs) -> None:
    """A span that is known only once it is over (a request's life, a
    compile the listener is told of): into the ring alone, under the
    innermost span open on this thread that began before it did (none: a
    request is older than the step that ends it). Nothing while recording
    is off."""
    rec = _recorder
    if rec is None:
        return
    parent = next((s.id for s in reversed(rec.open.stack) if s.t0 <= t0_ns), 0)
    rec.append(Span(next(rec.ids), parent, name, int(t0_ns), int(t1_ns),
                    threading.current_thread().name, attrs))


def recording() -> bool:
    return _recorder is not None


def spans_on(capacity: int = 262144) -> None:
    """Start recording into a new ring of `capacity` records (the oldest
    are dropped, and counted, beyond it). This and `spans_off()` are the
    only switch."""
    global _recorder
    _recorder = _Recorder(capacity)


def spans_off() -> None:
    """Stop recording and let the ring go. A span that is open keeps its
    recorder and ends into it, unseen."""
    global _recorder
    _recorder = None


def spans() -> tuple[list[Span], int]:
    """(the ring's records, oldest END first; how many the ring dropped).
    ([], 0) while recording is off."""
    rec = _recorder
    if rec is None:
        return [], 0
    with rec.lock:
        return list(rec.ring), rec.dropped
