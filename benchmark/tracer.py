"""A short profiler trace inside the measured window (`--trace 1` runs).

Traces are large and tracing slows the host, so only `length` seconds are
traced, starting `start_at` seconds into the window; the per-layer metrics
are taken over that traced part. The traced region is bracketed by a
`bench.traced_window` annotation, which gives its bounds on the profile's
own clock.
"""
from __future__ import annotations

import glob
import os
import shutil
import time


class Tracer:
    def __init__(self, directory, start_at, length, sync=None):
        self.directory = directory
        self.start_at = start_at
        self.length = length
        self.sync = sync            # callable: wait for the device
        self.t0 = self.t1 = None    # perf_counter bounds of the traced part
        self._span = None

    def tick(self, now):
        if self.t0 is None and now >= self.start_at:
            self.start()
        elif self.t0 is not None and self.t1 is None \
                and now >= self.start_at + self.length:
            self.stop()

    def start(self):
        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        if self.sync:
            self.sync()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self._span = jax.profiler.TraceAnnotation("bench.traced_window")
        self._span.__enter__()
        self.t0 = time.perf_counter()

    def stop(self):
        import jax

        if self.t0 is None or self.t1 is not None:
            return
        if self.sync:
            self.sync()
        self.t1 = time.perf_counter()
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def xplane_path(self):
        found = sorted(glob.glob(os.path.join(
            self.directory, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None
