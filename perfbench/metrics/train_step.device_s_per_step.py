"""Device busy time (union of the operations' intervals, mean over the
chips) per optimizer step traced."""


def read(run):
    steps = run["facts"].get("traced_steps")
    if not run.get("trace") or not steps:
        return None
    return run["trace"]["busy_s"] / steps
