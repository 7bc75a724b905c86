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


def memory_peak(stats):
    """Bytes one device holds, from its ``memory_stats()`` read when the
    window closes: live arrays (``bytes_in_use``: weights, optimizer state,
    cache, inputs) and the region the runtime keeps reserved for its loaded
    programs' temporaries (``bytes_reserved``: as large as the largest
    program's, kept between calls).  What a deployment holds while it
    runs, and nothing that set-up held for a moment and freed:
    ``peak_bytes_in_use`` is left out, because in LM training it is two
    copies of the state during init (12.96 GB where 11.50 are held).  What
    the keys mean was found out on the chip with
    perfbench/tools/memory_probe.py (PERF.md section 7).  A platform that
    reports no key (the CPU) reads 0."""
    return (int(stats.get("bytes_in_use", 0))
            + int(stats.get("bytes_reserved", 0)))


def fullest(devices):
    """``memory_stats()`` of the device that holds most (``{}`` where the
    platform reports none)."""
    return max((d.memory_stats() or {} for d in devices), key=memory_peak)


def device_info(devices):
    """The ``device`` object of the last line.  ``memory_peak_bytes`` is
    resident and temporary bytes together on the fullest chip;
    ``memory_reserved_peak_bytes`` the old reading under its own name
    (``peak_bytes_reserved``: the largest program's temporaries alone)."""
    stats = fullest(devices)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": memory_peak(stats),
            "memory_reserved_peak_bytes":
                int(stats.get("peak_bytes_reserved", 0))}
