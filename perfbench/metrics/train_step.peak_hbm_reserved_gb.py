"""Peak allocator reservation on the fullest chip after the window, GB
(10^9 bytes): parameters, optimizer state and the step's temporaries."""


def read(run):
    if "items_per_step" not in run["facts"]:
        return None
    peak = run["device"].get("memory_peak_bytes")
    return peak / 1e9 if peak else None
