"""Time per step the device's op line spends inside collective operations
and nothing else (worst chip): the gossip that compute does not hide."""


def read(run):
    steps = run["facts"].get("traced_steps")
    if not run.get("trace") or not steps:
        return None
    return run["trace"]["comm_exposed_s_worst"] / steps
