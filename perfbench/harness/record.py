"""What one run hands to the metric readers: sample series, counters,
named values, the reduced trace. Kept in memory, read once at the end."""

from __future__ import annotations

import contextlib
import time


class Record:
    def __init__(self, tracing: bool):
        self.tracing = tracing
        self.samples: dict[str, list] = {}
        self.counters: dict[str, float] = {}
        self.values: dict[str, float] = {}
        self.trace: dict | None = None      # tracered.reduce_trace's result
        self.context: dict = {}             # model, engine, peaks, steps ...

    def sample(self, series: str, value: float):
        self.samples.setdefault(series, []).append(value)

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span: its duration in ms joins series `name`; in a traced
        run it is also written into the profiler's trace as bench.<name>,
        on the device's clock, so idle gaps can be laid at its door."""
        ann = None
        if self.tracing:
            from jax.profiler import TraceAnnotation
            ann = TraceAnnotation("bench." + name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = (time.perf_counter() - t0) * 1e3
            if ann is not None:
                ann.__exit__(None, None, None)
            self.sample(name, dt)
