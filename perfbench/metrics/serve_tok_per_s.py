"""Completed output tokens per second: every token the clients saw in the
window over the window's length (its opening to the return of the step that
closed it).  All the work over all the time: a stall of the host inside the
window shows here, and is not voted down by a median of slices, which stands
beside this as ``scheduler.tok_per_s_slice_p50`` (PERF.md, PR 32)."""


def read(run):
    tokens = run["facts"].get("tokens_in_window")
    if not tokens:
        return None
    t_open, t_close = run["facts"]["window"]
    return tokens / (t_close - t_open)
