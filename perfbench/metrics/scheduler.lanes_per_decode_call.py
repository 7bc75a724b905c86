"""Requests in flight per scheduler step in the window (the lanes its
decode call carried), mean, from Scheduler.in_flight."""


def read(run):
    lanes = run["facts"].get("lanes_per_decode_call")
    return sum(lanes) / len(lanes) if lanes else None
