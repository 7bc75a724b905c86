"""The program's own stage spans (``bf:<cat>.<name>``, written by
``bluefog_tpu.utils.tracing.stage`` into any profiler trace) read back
from the traced tail: nesting, durations, self time, entry attributes, and
the device's idle time given to the innermost span that covers it.

Like harness/trace.py the reduction works on a plain structure, so it can
be checked on a hand-made one; host events carry a fourth field, their
attributes (``ProfileEvent.stats``):

    {"planes": [{"name": "/host:CPU", "lines": [{"name": "python",
        "events": [["bf:engine.decode_call", start_ns, dur_ns, {"S": 32}],
                   ...]},
                   {"name": "tfrt-non-blocking-queue", "events": [
                       ["DoEnqueueProgram", start_ns, dur_ns, {}], ...]}]},
                {"name": "/device:TPU:0", "lines": [
                    {"name": "XLA Ops", "events": [["", start_ns, dur_ns], ...]},
                    {"name": "XLA Modules", "events": [
                        [name, start_ns, dur_ns, {"run_id": 7}], ...]}]}]}

The profiler stamps device events ahead of host events (by 1.4-1.5 ms in
PR 25's chip traces), more than the stages being told apart last.  So the
offset is estimated from each trace (:func:`clock_offset`) and the device's
events are shifted by it before any idle time is given to a span; where
it cannot be estimated, idle time is not split by span at all.

A trace of a program that has no such spans (the parent of the PR that
added them) gives an analysis whose every reading is None; so does, for
the idle readings, a trace with no device plane (the CPU rehearsal).

    python -m perfbench.harness.program_spans <trace dir or .xplane.pb>
    python -m perfbench.harness.program_spans --dump-op-stats <...>

print the idle attribution, or the stats of a few ``XLA Ops`` events.
"""
import os

from perfbench.harness import estimators, manifest, trace

PREFIX = "bf:"
MODULE_LINE = "XLA Modules"
# the TPU runtime's own host events: a program handed to the device's
# queue, and the host told that the program with this ``run_id`` has ended
ENQUEUE, COMPLETE = "DoEnqueueProgram", "CompleteCallbacks"


def load(path):
    """.xplane.pb -> the plain structure: host lines keep the ``bf:`` events,
    ``pb:window`` and the runtime's enqueue / complete events with their
    stats, device planes their op line (names left out: only when the
    device was busy is read) and their module line."""
    from jax.profiler import ProfileData
    keep = (trace.WINDOW_SPAN, ENQUEUE, COMPLETE)
    planes = []
    for plane in ProfileData.from_file(path).planes:
        is_dev = bool(trace.DEVICE_PLANE_RE.match(plane.name))
        lines = []
        for line in plane.lines:
            if not is_dev:
                events = [[ev.name, int(ev.start_ns), int(ev.duration_ns),
                           dict(ev.stats)] for ev in line.events
                          if ev.name.startswith(PREFIX) or ev.name in keep]
            elif line.name == trace.OP_LINE:
                events = [["", int(ev.start_ns), int(ev.duration_ns)]
                          for ev in line.events]
            elif line.name == MODULE_LINE:
                events = [[ev.name, int(ev.start_ns), int(ev.duration_ns),
                           {"run_id": dict(ev.stats).get("run_id")}]
                          for ev in line.events]
            else:
                events = []
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


class Span:
    __slots__ = ("name", "start", "end", "attrs", "whole", "parent",
                 "children")

    def __init__(self, name, start, end, attrs, whole):
        self.name, self.start, self.end = name, start, end
        self.attrs, self.whole = attrs, whole
        self.parent, self.children = None, []

    @property
    def dur(self):
        return self.end - self.start

    @property
    def self_time(self):
        return self.dur - sum(c.dur for c in self.children)

    @property
    def path(self):
        return (self.parent.path + "/" if self.parent else "") \
            + self.name[len(PREFIX):]


def _device_planes(trace_doc):
    return [p for p in trace_doc["planes"]
            if trace.DEVICE_PLANE_RE.match(p["name"])]


def _host_events(trace_doc, name):
    return [e for p in trace_doc["planes"]
            if not trace.DEVICE_PLANE_RE.match(p["name"])
            for l in p["lines"] for e in l["events"] if e[0] == name]


def clock_offset(trace_doc, calls):
    """How far the device's events are stamped ahead of the host's, in ns:
    ``(lowest, highest)`` it can be, or None where the trace cannot say.

    No program starts before the runtime put it on the device's queue: an
    engine call (``calls``: [(start, end)] of the ``bf:engine.*_call``
    spans) that holds exactly one ``DoEnqueueProgram`` launched the
    program that overlaps it most, and the offset is at least that enqueue
    minus the program's stamped start.  No program ends after the host was
    told so: the offset is at most each ``CompleteCallbacks`` minus the
    stamped end of the program with its ``run_id``.  The largest of the
    former and the smallest of the latter hold the offset between them."""
    modules = {}       # device ordinal -> [(start, end, run_id)]
    for plane in _device_planes(trace_doc):
        modules[int(plane["name"].rsplit(":", 1)[1])] = [
            (e[1], e[1] + e[2], e[3].get("run_id")) for l in plane["lines"]
            if l["name"] == MODULE_LINE for e in l["events"]]
    every = [m for ms in modules.values() for m in ms]
    enqueued = sorted(e[1] for e in _host_events(trace_doc, ENQUEUE))
    lows = []
    for s, e in calls:
        inside = [t for t in enqueued if s <= t < e]
        if len(inside) == 1 and every:
            m = max(every, key=lambda m: min(m[1], e) - max(m[0], s))
            lows.append(inside[0] - m[0])
    ended = {(device, run_id): end for device, ms in modules.items()
             for _start, end, run_id in ms}
    highs = []
    for name, start, _dur, stats in _host_events(trace_doc, COMPLETE):
        key = (stats.get("device_ordinal", 0), stats.get("run_id"))
        if key in ended:
            highs.append(start - ended[key])
    if not lows or not highs:
        return None
    return max(lows), min(highs)


def _busy(trace_doc, lo, hi, shift):
    """Busy intervals on the host's clock (device stamps + ``shift``),
    clipped to the window, of the device that idles most (as
    harness/trace.py names gaps); None without a device."""
    per_device = []
    for plane in _device_planes(trace_doc):
        iv = [[max(e[1] + shift, lo), min(e[1] + shift + e[2], hi)]
              for l in plane["lines"] if l["name"] == trace.OP_LINE
              for e in l["events"]
              if e[1] + shift < hi and e[1] + shift + e[2] > lo]
        if iv:
            per_device.append(trace.merge(iv))
    if not per_device:
        return None
    return min(per_device, key=lambda d: d[1])[0]


class Analysis:
    """What one traced tail says through the program's spans.  Times in
    seconds.  Durations are taken from spans that lie wholly inside the
    window; idle time is given to spans clipped to it."""

    def __init__(self, trace_doc):
        host = [l for p in trace_doc["planes"]
                if not trace.DEVICE_PLANE_RE.match(p["name"])
                for l in p["lines"]]
        self.spans, self.idle_by_path, self.idle_s = [], {}, None
        self.idle_named_s, self.window_s, self.offset_ns = None, None, None
        # the thread that drives the device: the one holding the window
        # span, else the one with most stage spans
        line = max(host, default={"events": []}, key=lambda l: (
            any(e[0] == trace.WINDOW_SPAN for e in l["events"]),
            sum(e[0].startswith(PREFIX) for e in l["events"])))
        window = [e for e in line["events"] if e[0] == trace.WINDOW_SPAN]
        events = [e for e in line["events"] if e[0].startswith(PREFIX)]
        if not events:
            return
        if window:
            lo, hi = window[0][1], window[0][1] + window[0][2]
        else:
            lo = min(e[1] for e in events)
            hi = max(e[1] + e[2] for e in events)
        self.window_s = (hi - lo) / 1e9
        stack = []
        for name, start, dur, *rest in sorted(
                events, key=lambda e: (e[1], -e[2])):
            end = start + dur
            if end <= lo or start >= hi or dur <= 0:
                continue
            sp = Span(name, max(start, lo), min(end, hi),
                      rest[0] if rest else {}, start >= lo and end <= hi)
            while stack and stack[-1].end <= sp.start:
                stack.pop()
            if stack:
                sp.parent = stack[-1]
                stack[-1].children.append(sp)
            stack.append(sp)
            self.spans.append(sp)
        # device stamps onto the host's clock: the middle of what the
        # offset can be (as stamped where the trace cannot say)
        self.offset_ns = clock_offset(trace_doc, [
            (e[1], e[1] + e[2]) for e in events
            if e[0].startswith(PREFIX + "engine.") and e[0].endswith("_call")])
        busy = _busy(trace_doc, lo, hi, self.shift_ns)
        if busy is None:
            return
        gaps, pos = [], lo
        for s, e in busy:
            if s > pos:
                gaps.append((pos, s))
            pos = max(pos, e)
        if hi > pos:
            gaps.append((pos, hi))
        self.idle_s = sum(e - s for s, e in gaps) / 1e9
        # every instant to the innermost span running then
        owned = trace.owned_segments(
            [[i, sp.start, sp.dur] for i, sp in enumerate(self.spans)])
        named, gi = 0, 0
        for i, s, e in owned:
            while gi < len(gaps) and gaps[gi][1] <= s:
                gi += 1
            gj = gi
            while gj < len(gaps) and gaps[gj][0] < e:
                ov = min(e, gaps[gj][1]) - max(s, gaps[gj][0])
                if ov > 0:
                    sp = self.spans[i]
                    self.idle_by_path[sp.path] = \
                        self.idle_by_path.get(sp.path, 0.0) + ov / 1e9
                    if not sp.children:
                        named += ov
                gj += 1
        self.idle_named_s = named / 1e9

    @property
    def shift_ns(self):
        """What is added to a device stamp to put it on the host's clock:
        the middle of what the offset can be, 0 where it is unknown."""
        return sum(self.offset_ns or (0, 0)) // 2

    # -- readings (None where the trace holds nothing to read) -------------

    def named(self, name, under=None):
        """Whole spans called ``name`` (directly under a span ``under``)."""
        return [s for s in self.spans if s.name == name and s.whole
                and (under is None
                     or (s.parent is not None and s.parent.name == under))]

    def median_s(self, name, under=None):
        d = [s.dur / 1e9 for s in self.named(name, under)]
        return estimators.median(d) if d else None

    def uncovered_s(self, name):
        """Per span ``name``, the time its children do not cover: median."""
        d = [s.self_time / 1e9 for s in self.named(name)]
        return estimators.median(d) if d else None

    def attr_sum(self, name, key):
        vals = [s.attrs[key] for s in self.named(name) if key in s.attrs]
        return sum(vals) if vals else None

    def attr_ratio(self, name, num, den):
        n, d = self.attr_sum(name, num), self.attr_sum(name, den)
        return n / d if n is not None and d else None

    def attr_median(self, name, key):
        vals = [s.attrs[key] for s in self.named(name) if key in s.attrs]
        return estimators.median(vals) if vals else None

    def outside_s(self, name, inner_prefix, reduce="median"):
        """Per span ``name``: its time outside its outermost descendants
        whose names start with ``inner_prefix``."""
        def inner(sp):
            return sum(c.dur if c.name.startswith(inner_prefix) else inner(c)
                       for c in sp.children)
        d = [(s.dur - inner(s)) / 1e9 for s in self.named(name)]
        if not d:
            return None
        return estimators.median(d) if reduce == "median" else sum(d) / len(d)

    def idle_named_share(self):
        """Share of the device's idle time inside some leaf stage span."""
        if not self.idle_s:
            return None
        return self.idle_named_s / self.idle_s

    def report(self):
        """The idle attribution, as lines of text."""
        if self.idle_s is None:
            return ["program spans: %d, no device in the trace"
                    % len(self.spans)]
        steps = len(self.named(PREFIX + "serve.step")) or None
        out = ["device idle %.6f s of a %.6f s window; inside a leaf stage "
               "span %.6f s" % (self.idle_s, self.window_s,
                                self.idle_named_s)]
        if self.offset_ns is None:
            return out + ["how far device events are stamped ahead of host "
                          "events cannot be told from this trace: idle time "
                          "is not split by span"]
        low, high = self.offset_ns
        out.append("device events are stamped %.6f to %.6f s ahead of host "
                   "events and were shifted by %.6f s: a span's edge is good "
                   "to %.6f s; idle time by innermost span%s:"
                   % (low / 1e9, high / 1e9, (low + high) // 2 / 1e9,
                      (high - low) / 2e9,
                      " (and ms per scheduler step, %d steps)" % steps
                      if steps else ""))
        rows = sorted(self.idle_by_path.items(), key=lambda x: -x[1])
        rows.append(("(outside every bf: span)",
                     self.idle_s - sum(self.idle_by_path.values())))
        for path, t in rows:
            out.append("  %-58s %.6f s%s" % (
                path, t, "  %.3f ms/step" % (t / steps * 1e3)
                if steps else ""))
        for call in ("decode_call", "prefill_call"):
            name = PREFIX + "engine." + call
            if self.named(name):
                out.append("bf:engine.%s: median %.6f s over %d calls; its "
                           "stages leave %.6f s of a call uncovered (median)"
                           % (call, self.median_s(name),
                              len(self.named(name)), self.uncovered_s(name)))
        return out


def of(run):
    """The analysis of this run's traced tail: the one the runner made
    when it reduced the trace (runners/_common.py), else made here once
    from the run's trace directory and printed before the last line."""
    if "program_spans" not in run:
        trace_dir = os.path.join(
            run.get("out_dir", os.path.join(manifest.ROOT, "perfbench_out")),
            "trace", run["workload"])
        try:
            ana = Analysis(load(trace.find_xplane(trace_dir)))
        except FileNotFoundError:
            ana = Analysis({"planes": []})
        run["program_spans"] = ana
        if ana.spans:
            for text in ana.report():
                print("[perfbench] " + text, flush=True)
    return run["program_spans"]


def dump_op_stats(path, n=3):
    """Stat names and values of the ``n`` longest ``XLA Ops`` events of the
    first device plane: does an op event carry its HLO op_name, FLOPs,
    bytes?"""
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(path).planes:
        if not trace.DEVICE_PLANE_RE.match(plane.name):
            continue
        print(f"plane {plane.name}: lines "
              f"{[line.name for line in plane.lines]}")
        for line in plane.lines:
            if line.name != trace.OP_LINE:
                continue
            events = sorted(line.events, key=lambda ev: -ev.duration_ns)
            seen = set()
            for ev in events:
                head = trace.short_name(ev.name).split("_")[0].split(".")[0]
                if head in seen:
                    continue
                seen.add(head)
                print(f"event {trace.short_name(ev.name)!r} "
                      f"{ev.duration_ns / 1e3:.1f} us")
                for key, value in ev.stats:
                    print(f"    {key} = {str(value)[:300]!r}")
                if len(seen) >= n:
                    break
        return
    print("no device plane in", path)


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="a trace directory or an .xplane.pb")
    ap.add_argument("--dump-op-stats", action="store_true")
    args = ap.parse_args(argv)
    path = args.trace if os.path.isfile(args.trace) \
        else trace.find_xplane(args.trace)
    if args.dump_op_stats:
        dump_op_stats(path)
    else:
        print("\n".join(Analysis(load(path)).report()))
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
