"""Peak of the allocator's RESERVATION on the fullest chip after the window,
GB (10^9 bytes): on this runtime the largest compiled program's temporaries
(``memory_stats()["peak_bytes_reserved"]``, kept by harness/device.py as
``device.memory_reserved_peak_bytes``).  What the chip holds besides
(parameters, optimizer state) is in ``device.memory_peak_bytes``."""


def read(run):
    if "items_per_step" not in run["facts"]:
        return None
    peak = run["device"].get("memory_reserved_peak_bytes")
    return peak / 1e9 if peak else None
