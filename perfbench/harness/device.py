"""What a run says about its device and its compilations."""


class CompileWatch:
    """Counts what JAX's own monitoring events report: persistent-cache
    hits and misses, and programs compiled or loaded.  The listeners only
    add one to a counter, and fire only when something compiles, which
    inside the window must be never."""
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.hits = self.misses = self.compiles = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == self.HIT:
            self.hits += 1
        elif event == self.MISS:
            self.misses += 1

    def _duration(self, event, _secs, **_kw):
        if event == self.COMPILE:
            self.compiles += 1


def device_info(devices):
    """The ``device`` object of the last line.  ``memory_peak_bytes`` is the
    allocator's peak reservation on the fullest chip: on this runtime
    ``peak_bytes_in_use`` omits a compiled program's temporaries."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_reserved",
                                       stats.get("peak_bytes_in_use", 0))))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}
