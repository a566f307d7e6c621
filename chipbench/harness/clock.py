"""The benchmark's own clock and its host spans."""

from __future__ import annotations

import contextlib
import time

now = time.perf_counter


class Spans:
    """Host spans of the benchmark's own calls into each layer, kept in
    memory; with ``annotate`` they are also written into the profiler's
    trace (jax.profiler.TraceAnnotation) so that idle gaps on the device can
    be named by what the host was doing."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.totals = {}      # name -> [count, seconds]

    @contextlib.contextmanager
    def span(self, name: str):
        ctx = contextlib.nullcontext()
        if self.annotate:
            import jax

            ctx = jax.profiler.TraceAnnotation("bench." + name)
        t0 = now()
        with ctx:
            yield
        dt = now() - t0
        tot = self.totals.setdefault(name, [0, 0.0])
        tot[0] += 1
        tot[1] += dt


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of a non-empty list (q in 0..100)."""
    v = sorted(values)
    k = max(0, min(len(v) - 1, int(-(-q * len(v) // 100)) - 1))
    return float(v[k])
