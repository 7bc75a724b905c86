"""From a profiler trace to device busy time, idle gaps, exposed collective
time and the operations that took most time.

The reduction works on a plain structure, so that it can be checked on a
small recorded chip trace kept under perfbench/fixtures:

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, duration_ns], ...]}]}]}

:func:`load_xplane` makes it from the ``.xplane.pb`` that
``jax.profiler.trace`` writes, with nothing but JAX.  ``merge`` and the
collective classifier are copies of tools/trace_analyze.py's (the program's
file, listed in PERF.md for a later PR to delete); its ``subtract`` of a
compute cover from the collectives' intervals is replaced by ownership:

events on a device's op line nest (a ``while`` holds its body's ops), so
time is given to the INNERMOST event running: an op's self time.  Busy time
is the union of all events; a collective's exposed time is the time the
device's op line spends inside collective operations (the wait in a
``-done``, a synchronous permute) and in nothing else.
"""
import glob
import os
import re

COMM_RE = re.compile(
    r"ragged[-_]?all[-_]?to[-_]?all"
    r"|all[-_]?reduce|all[-_]?gather|reduce[-_]?scatter"
    r"|collective[-_]?permute|all[-_]?to[-_]?all|collective[-_]?broadcast"
    r"|\bsend(?:[-_]done)?\b|\brecv(?:[-_]done)?\b"
    r"|ppermute|collective", re.I)
DEVICE_PLANE_RE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
SPAN_PREFIX = "pb:"
WINDOW_SPAN = "pb:window"


def find_xplane(trace_dir):
    hits = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return hits[-1]


def load_xplane(path, keep_host_prefix=SPAN_PREFIX):
    """.xplane.pb -> the plain structure.  Device planes keep every line;
    host planes keep only the benchmark's own spans (``pb:*``)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        is_dev = bool(DEVICE_PLANE_RE.match(plane.name))
        lines = []
        for line in plane.lines:
            events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                      for ev in line.events
                      if is_dev or ev.name.startswith(keep_host_prefix)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def merge(intervals):
    """Union of [start, end) intervals; returns merged list + total."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out, sum(e - s for s, e in out)


_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def short_name(name):
    """A device event is named by its whole HLO instruction
    (``%fusion.12 = (f32[256]{...}, ...) fusion(...operands...)``).  Keep the
    instruction's own name and its largest result shape:
    ``fusion.12_f32_256_56_56_256``.  Names that are already short pass
    unchanged, so this is safe to apply twice."""
    head, sep, rest = (name or "").partition(" = ")
    if not sep:
        return name or ""
    result = rest.split(") ", 1)[0] if rest.startswith("(") else \
        rest.split(" ", 1)[0]
    best, best_n = "", -1
    for dt, dims in _SHAPE_RE.findall(result):
        n = 1
        for d in dims.split(","):
            n *= int(d) if d else 1
        if n > best_n:
            best, best_n = dt + "_" + dims.replace(",", "_"), n
    return head.lstrip("%") + ("_" + best if best else "")


def owned_segments(events):
    """Give every instant to the innermost event running then.
    ``events``: [[name, start, dur], ...] of ONE line.  Returns
    [(name, start, end), ...] in time order, not overlapping."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    segs, stack, cur = [], [], None

    def close_until(t):
        nonlocal cur
        while stack and stack[-1][1] <= t:
            name, end = stack.pop()
            if end > cur:
                segs.append((name, cur, end))
                cur = end

    for name, start, dur in evs:
        if dur <= 0:
            continue
        if cur is None:
            cur = start
        close_until(start)
        if stack:
            if start > cur:
                segs.append((stack[-1][0], cur, start))
        cur = max(cur, start) if stack else start
        stack.append((name, start + dur))
    close_until(float("inf"))
    return segs


def host_spans(trace):
    """The benchmark's own spans from the host planes: [(name, s, e)]."""
    out = []
    for plane in trace["planes"]:
        if DEVICE_PLANE_RE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            out.extend((n, s, s + d) for n, s, d in line["events"]
                       if n.startswith(SPAN_PREFIX))
    return sorted(out, key=lambda x: x[1])


def idle_share(reduced):
    """1 - device busy time over the traced window, between 0 and 1 (None
    without a reduced trace)."""
    if not reduced:
        return None
    return 1.0 - reduced["busy_s"] / reduced["window_s"]


def reduce(trace, top=10, device_shift_ns=0):
    """The plain structure -> the numbers the per-layer metrics read.

    Returns None when no operation ran on a device.  Times in seconds.
    ``window_s`` is the benchmark's ``pb:window`` span where the trace
    holds one that contains device work, else first to last device event.
    The profiler stamps device events ahead of host events (1.04 ms in the
    recorded fixture, 1.3-1.7 ms in the cells' traces).  Busy time and the
    window are taken as stamped; an idle gap is NAMED by the host span that
    covers it after the gap is moved by ``device_shift_ns`` onto the host's
    clock (harness/program_spans.py's ``clock_offset`` estimates it per
    trace; 0 where the trace cannot say, which is safe while spans last
    far longer than the offset).
    """
    devices = []
    for plane in trace["planes"]:
        if not DEVICE_PLANE_RE.match(plane["name"]):
            continue
        ops = [[short_name(n), s, d] for line in plane["lines"]
               if line["name"] == OP_LINE for n, s, d in line["events"]]
        if ops:
            devices.append((plane["name"], ops))
    if not devices:
        return None
    spans = host_spans(trace)
    lo = min(ev[1] for _, ops in devices for ev in ops)
    hi = max(ev[1] + ev[2] for _, ops in devices for ev in ops)
    window_from = "device_events"
    for name, s, e in spans:
        if name == WINDOW_SPAN and s < hi and e > lo:
            lo, hi, window_from = s, e, "pb:window"
            break
    window = hi - lo
    per_device, self_time = [], {}
    for plane_name, ops in devices:
        segs = [(n, max(s, lo), min(e, hi)) for n, s, e in owned_segments(ops)
                if min(e, hi) > max(s, lo)]
        busy_iv, busy = merge([[s, e] for _, s, e in segs])
        comm = sum(e - s for n, s, e in segs if COMM_RE.search(n))
        gaps, pos = [], lo
        for s, e in busy_iv:
            if s > pos:
                gaps.append((pos, s))
            pos = e
        if hi > pos:
            gaps.append((pos, hi))
        for n, s, e in segs:
            self_time[n] = self_time.get(n, 0) + (e - s)
        per_device.append({"plane": plane_name, "busy_ns": busy,
                           "comm_exposed_ns": comm, "gaps": gaps})
    n_dev = len(per_device)
    # idle gaps of the busiest-waiting device, named by the host span that
    # overlaps each most
    worst = max(per_device, key=lambda d: window - d["busy_ns"])
    named = [s for s in spans if s[0] != WINDOW_SPAN]
    by_name, singles = {}, []
    for g0, g1 in worst["gaps"]:
        h0, h1 = g0 + device_shift_ns, g1 + device_shift_ns
        best, best_ov = "none", 0
        for n, s, e in named:
            if s >= h1:
                break
            ov = min(e, h1) - max(s, h0)
            if ov > best_ov:
                best, best_ov = n[len(SPAN_PREFIX):], ov
        by_name[best] = by_name.get(best, 0) + (g1 - g0)
        singles.append((best, g1 - g0))
    singles.sort(key=lambda x: -x[1])
    idle_gaps = [[n, t / 1e9] for n, t in
                 sorted(by_name.items(), key=lambda x: -x[1])[:top - 4]]
    idle_gaps += [["longest:" + n, t / 1e9] for n, t in singles[:4]]
    device_ops = [[n, t / n_dev / 1e9] for n, t in
                  sorted(self_time.items(), key=lambda x: -x[1])[:top]]
    return {
        "n_devices": n_dev,
        "window_s": window / 1e9,
        "window_from": window_from,
        "device_shift_s": device_shift_ns / 1e9,
        "busy_s": sum(d["busy_ns"] for d in per_device) / n_dev / 1e9,
        "comm_exposed_s_worst": max(d["comm_exposed_ns"]
                                    for d in per_device) / 1e9,
        "self_time_s": {n: t / n_dev / 1e9 for n, t in self_time.items()},
        "breakdown": {"device_ops": device_ops, "idle_gaps": idle_gaps[:top]},
    }
