"""Host spans, recorded from the benchmark's own files around
the calls into each layer of the program.  Kept in memory, read by the
per-layer metrics after the window.  With ``annotate`` (the traced run)
each span is also written into the profiler's trace as ``pb:<name>``, on
the device events' clock, so that idle gaps can be attributed to what the
host was doing."""
import contextlib
import time


class Spans:
    def __init__(self, annotate=False):
        self.annotate = annotate
        self.records = []          # (name, t0, t1) on time.perf_counter

    @contextlib.contextmanager
    def span(self, name):
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation("pb:" + name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.records.append((name, t0, time.perf_counter()))
            if self.annotate:
                ann.__exit__(None, None, None)

    def wrap(self, name, fn):
        """``fn`` with a span around every call (for a bound method of a
        program object the benchmark may not edit)."""
        def spanned(*args, **kwargs):
            with self.span(name):
                return fn(*args, **kwargs)
        return spanned

    def durations(self, name, t_open=None, t_close=None):
        return [t1 - t0 for n, t0, t1 in self.records
                if n == name and (t_open is None or t0 >= t_open)
                and (t_close is None or t1 <= t_close)]
