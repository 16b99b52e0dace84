"""The harness's own spans and counts, kept in memory until the run ends.

Spans are recorded from the benchmark's files, around the calls into each
layer of the program (choosing-metrics §4); spans inside the program are a
later `tracing` change. In a traced run each span is also written into the
profiler's trace (`jax.profiler.TraceAnnotation`, name `bench.<span>`), which
puts it on the device trace's clock so that idle gaps can be attributed to
what the host was doing.
"""
from __future__ import annotations

import contextlib
import time


class HostLog:
    def __init__(self, clock=time.perf_counter, annotate=False):
        self.clock = clock
        self.annotate = annotate
        self.spans = []      # (name, start, end) on `clock`
        self.samples = {}    # name -> list of numbers
        self.counts = {}     # name -> number

    @contextlib.contextmanager
    def span(self, name):
        if self.annotate:
            import jax

            annotation = jax.profiler.TraceAnnotation("bench." + name)
        else:
            annotation = contextlib.nullcontext()
        with annotation:
            t0 = self.clock()
            try:
                yield
            finally:
                self.spans.append((name, t0, self.clock()))

    def sample(self, name, value):
        self.samples.setdefault(name, []).append(value)

    def count(self, name, by=1):
        self.counts[name] = self.counts.get(name, 0) + by

    def between(self, t0, t1):
        """A view holding only what fell inside [t0, t1) on `clock`:
        spans that started there, and samples recorded as (time, value)."""
        view = HostLog(self.clock)
        view.spans = [s for s in self.spans if t0 <= s[1] < t1]
        view.samples = {
            k: [v for v in vals if not isinstance(v, tuple)
                or t0 <= v[0] < t1] for k, vals in self.samples.items()}
        view.counts = dict(self.counts)
        return view


def percentile(values, q):
    """The q-th percentile (0-100) by linear interpolation; None if empty."""
    if not values:
        return None
    s = sorted(values)
    pos = (len(s) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)
