"""Device time by the program's own scopes.

A device trace names an operation by its HLO instruction (``fusion.165``)
and nothing else, and every compile renumbers those.  The program says
which instruction belongs to which named part of it
(``bluefog_tpu.utils.tracing.device_scopes()``: per compiled program the
HLO module's name and ``{instruction: (scope, direction)}``); this reader
sums the traced tail's op self time through that table, by
``(program key, scope, direction)``.

Like harness/trace.py it works on a plain structure, so it can be checked
on a hand-made one and on a small recorded chip trace
(``perfbench/fixtures/scopes_small.json.gz``):

    {"planes": [{"name": "/device:TPU:0", "lines": [
                    {"name": "XLA Ops", "events": [[instruction, start_ns,
                                                    dur_ns], ...]},
                    {"name": "XLA Modules", "events": [
                        ["jit__decode_body(123)", start_ns, dur_ns,
                         {"run_id": 7}], ...]}]},
                {"name": "/host:CPU", "lines": [{"name": "python", "events": [
                    ["bf:engine.prefill_call", start_ns, dur_ns,
                     {"Tpad": 256, "tokens": 200}],
                    ["DoEnqueueProgram", start_ns, dur_ns, {"run_id": 7}],
                    ["pb:window", start_ns, dur_ns, {}], ...]}]}]}

Two programs of one trace reuse instruction names, so an op event is looked
up in the table of the ``XLA Modules`` event that contains it.  The buckets
of one jit share a module name, so such a module event is tied to its
program key by the engine call it ran in: the call span that holds the
runtime's ``DoEnqueueProgram`` with the module event's ``run_id``, else the
one that holds the event's start once it is moved onto the host's clock
(``program_spans``' per-trace shift); the span's ``Tpad`` / ``S`` name the
bucket.  Time is self time (the innermost op event running, as
``trace.owned_segments`` gives it), clipped to the ``pb:window`` span like
``trace.reduce``'s busy time, and a mean over the chips, so a program's
scopes and its unscoped rest sum to the busy time ``trace.reduce`` reports.

A program without the registry (the parent of the PR that added it), a
trace without a device plane (the CPU rehearsal) or without a table give
an analysis whose every reading is None.

    python -m perfbench.harness.scopes <trace dir or .xplane.pb> \\
        --tables <device_scopes_rank0.json>

prints the split of a trace by the tables a process flushed
(``BLUEFOG_TRACE=<dir>``, docs/OBSERVABILITY.md).
"""
import bisect
import json
import os
import time

from perfbench.harness import manifest, program_spans, trace

MODULE_LINE = program_spans.MODULE_LINE
ENQUEUE = program_spans.ENQUEUE
# an engine call span names its bucket's program key
CALL_KEYS = {
    "bf:engine.prefill_call": lambda a: "prefill Tpad=%d" % a["Tpad"],
    "bf:engine.decode_call": lambda a: "decode S=%d" % a["S"],
    "bf:engine.chunk_call": lambda a: "chunk S=%d T=%d" % (a["S"], a["T"]),
}
ATTENTION = ("cache.read", "mla.attend", "attn.window", "attn.full", "attn")
PROJECTION = ("attn.project", "mla.project")
FFN = ("ffn", "moe.route", "moe.experts", "moe.shared")


def instruction(name):
    """An op event is named by its whole HLO instruction text
    (``%fusion.12 = (f32[256]{...}) fusion(...)``): the instruction's own
    name.  A name that is already bare passes unchanged."""
    return (name or "").partition(" = ")[0].lstrip("%")


def load(path):
    """.xplane.pb -> the plain structure: device planes keep their op
    line (instruction names) and their module line, host lines what
    ``program_spans.load`` keeps (the ``bf:`` spans, the window, the
    runtime's enqueue and complete events), so one structure serves both
    analyses."""
    from jax.profiler import ProfileData
    keep = (trace.WINDOW_SPAN, ENQUEUE, program_spans.COMPLETE)
    planes = []
    for plane in ProfileData.from_file(path).planes:
        is_dev = bool(trace.DEVICE_PLANE_RE.match(plane.name))
        lines = []
        for line in plane.lines:
            if not is_dev:
                events = [[ev.name, int(ev.start_ns), int(ev.duration_ns),
                           dict(ev.stats)] for ev in line.events
                          if ev.name.startswith(program_spans.PREFIX)
                          or ev.name in keep]
            elif line.name == trace.OP_LINE:
                events = [[instruction(ev.name), int(ev.start_ns),
                           int(ev.duration_ns)] for ev in line.events]
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


def _calls(doc):
    """The engine call spans of the host planes, ``[(start, end, key,
    attrs)]`` by start, and ``{run_id: index}`` for the calls that hold
    exactly the enqueue events of those run ids."""
    calls, enqueued = [], []
    for plane in doc["planes"]:
        if trace.DEVICE_PLANE_RE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, start, dur, *rest in line["events"]:
                attrs = rest[0] if rest else {}
                if name in CALL_KEYS:
                    try:
                        calls.append((start, start + dur,
                                      CALL_KEYS[name](attrs), attrs))
                    except KeyError:
                        pass
                elif name == ENQUEUE and attrs.get("run_id") is not None:
                    enqueued.append((start, attrs["run_id"]))
    calls.sort(key=lambda c: c[0])
    starts = [c[0] for c in calls]
    by_run = {}
    for at, run_id in enqueued:
        i = bisect.bisect_right(starts, at) - 1
        if i >= 0 and at < calls[i][1]:
            by_run[run_id] = i
    return calls, by_run


class Analysis:
    """What one traced tail says through the programs' scope tables.
    Seconds, mean over the chips.  ``by`` ``{(key, scope, direction): s}``
    (``scope`` ``""``: in a table, under no scope; key ``None``: a module
    event no table could be tied to); ``calls`` ``{key: module events}``;
    ``tokens`` ``{key: real prompt tokens of its calls}``."""

    def __init__(self, doc, tables, shift_ns=0):
        self.by, self.calls, self.tokens = {}, {}, {}
        self.mixed_s, self.inherited_s, self.no_row = {}, {}, {}
        self.unscoped_ops, self.n_devices = {}, 0
        by_module = {}
        for key, tab in (tables or {}).items():
            by_module.setdefault(tab["module"], []).append(key)
        calls, by_run = _calls(doc)
        starts = [c[0] for c in calls]
        devices = [p for p in doc["planes"]
                   if trace.DEVICE_PLANE_RE.match(p["name"])]
        ops = {p["name"]: [e for l in p["lines"] if l["name"] == trace.OP_LINE
                           for e in l["events"]] for p in devices}
        ops = {n: e for n, e in ops.items() if e}
        if not ops or not tables:
            return
        self.n_devices = n = len(ops)
        lo = min(e[1] for evs in ops.values() for e in evs)
        hi = max(e[1] + e[2] for evs in ops.values() for e in evs)
        for plane in doc["planes"]:
            for line in plane["lines"]:
                for e in line["events"]:
                    if e[0] == trace.WINDOW_SPAN and e[1] < hi \
                            and e[1] + e[2] > lo:
                        lo, hi = e[1], e[1] + e[2]
        add = lambda d, k, v: d.__setitem__(k, d.get(k, 0.0) + v / n / 1e9)
        for plane in devices:
            if plane["name"] not in ops:
                continue
            modules = sorted(
                (e for l in plane["lines"] if l["name"] == MODULE_LINE
                 for e in l["events"]), key=lambda e: e[1])
            keyed = []
            for name, start, dur, *rest in modules:
                if start >= hi or start + dur <= lo:
                    keyed.append(None)
                    continue
                cands = by_module.get(name.partition("(")[0], ())
                # the call it ran in: by the enqueue's run id, else by time
                i = by_run.get((rest[0] if rest else {}).get("run_id"), -1)
                if i < 0:
                    i = bisect.bisect_right(starts, start + shift_ns) - 1
                    if i >= 0 and start + shift_ns >= calls[i][1]:
                        i = -1
                key = None
                if len(cands) == 1:
                    key = cands[0]
                elif i >= 0 and calls[i][2] in cands:
                    key = calls[i][2]
                keyed.append(key)
                self.calls[key] = self.calls.get(key, 0) + 1 / n
                if key is not None and i >= 0 and calls[i][2] == key \
                        and "tokens" in calls[i][3]:
                    self.tokens[key] = self.tokens.get(key, 0) \
                        + calls[i][3]["tokens"] / n
            m_starts = [e[1] for e in modules]
            for name, s, e in trace.owned_segments(ops[plane["name"]]):
                s, e = max(s, lo), min(e, hi)
                if e <= s:
                    continue
                j = bisect.bisect_right(m_starts, s) - 1
                key = keyed[j] if j >= 0 and s < modules[j][1] \
                    + modules[j][2] else None
                tab = tables.get(key)
                if tab is None:
                    add(self.by, (None, "", ""), e - s)
                    continue
                row = tab["ops"].get(name)
                if row is None:
                    add(self.no_row, key, e - s)
                    row = ("", "")
                scope, direction = row
                add(self.by, (key, scope, direction), e - s)
                if name in tab.get("mixed", ()):
                    add(self.mixed_s, key, e - s)
                if name in tab.get("inherited", ()):
                    add(self.inherited_s, key, e - s)
                if not scope:
                    add(self.unscoped_ops, (key, name), e - s)

    # -- readings (None where the trace holds nothing to read) -------------

    def seconds(self, kind=None, scopes=None, direction=None):
        """Op self time in programs whose key starts with ``kind``, under
        one of ``scopes`` (None: any, the unscoped rest too), in
        ``direction`` (None: any)."""
        rows = [t for (key, scope, d), t in self.by.items()
                if key is not None and (kind is None or key.startswith(kind))
                and (scopes is None or scope in scopes)
                and (direction is None or d == direction)]
        return sum(rows) if rows else None

    def events(self, kind):
        n = sum(c for key, c in self.calls.items()
                if key is not None and key.startswith(kind))
        return n or None

    def per_call(self, kind, scopes):
        """Seconds under ``scopes`` a module event of the ``kind``
        programs (0 where they ran and none of their time is there)."""
        n = self.events(kind)
        return (self.seconds(kind, scopes) or 0.0) / n if n else None

    def per_ktok(self, kind, scopes):
        """Seconds under ``scopes`` a thousand real prompt tokens of the
        ``kind`` programs' calls."""
        tokens = sum(t for key, t in self.tokens.items()
                     if key.startswith(kind))
        if not tokens or not self.events(kind):
            return None
        return (self.seconds(kind, scopes) or 0.0) / (tokens / 1e3)

    def scoped_share(self):
        """Op self time the tables give a scope, over all op self time."""
        total = sum(self.by.values())
        if not total:
            return None
        return sum(t for (key, scope, _), t in self.by.items()
                   if scope) / total

    def report(self, top=6):
        """The whole table by program and scope, as lines of text."""
        total = sum(self.by.values())
        if not total:
            return ["device scopes: no device operation met a scope table"]
        out = ["device time by the programs' own scopes: %.6f s of op self "
               "time (mean of %d chip(s)), %.4f of it under a scope"
               % (total, self.n_devices, self.scoped_share())]
        keys = sorted({k for k, _, _ in self.by},
                      key=lambda k: -sum(t for (kk, _, _), t
                                         in self.by.items() if kk == k))
        for key in keys:
            rows = sorted(((s, d, t) for (k, s, d), t in self.by.items()
                           if k == key), key=lambda r: -r[2])
            own = sum(t for _, _, t in rows)
            n = self.calls.get(key, 0)
            out.append("  %s: %.6f s in %.4g module event(s)%s; fusions "
                       "across scopes %.6f s, named by operands or users "
                       "%.6f s, instructions with no row %.6f s"
                       % (key or "(no table: module events tied to no "
                          "registered program)", own, n,
                          ", %.4g prompt tokens" % self.tokens[key]
                          if key in self.tokens else "",
                          self.mixed_s.get(key, 0.0),
                          self.inherited_s.get(key, 0.0),
                          self.no_row.get(key, 0.0)))
            for scope, direction, t in rows:
                out.append("    %-14s %-3s %.6f s  %5.1f %%%s" % (
                    scope or "(no scope)", direction, t, 100 * t / own,
                    "  %.6f s/event" % (t / n) if n else ""))
            left = sorted(((t, name) for (k, name), t
                           in self.unscoped_ops.items() if k == key),
                          reverse=True)[:top]
            if left:
                out.append("    under no scope, longest: " + ", ".join(
                    "%s %.6f s" % (name, t) for t, name in left))
        return out


def _tables():
    """The running program's tables, or None where it has no registry."""
    try:
        from bluefog_tpu.utils import tracing
    except ImportError:
        return None
    get = getattr(tracing, "device_scopes", None)
    return get() if get else None


def of(run):
    """The analysis of this run's traced tail, made once from the run's
    trace directory and the program's tables and printed before the last
    line."""
    if "device_scopes" not in run:
        t0 = time.perf_counter()
        ana, tables = None, _tables()
        t1 = time.perf_counter()
        if tables:
            trace_dir = os.path.join(
                run.get("out_dir",
                        os.path.join(manifest.ROOT, "perfbench_out")),
                "trace", run["workload"])
            try:
                ana = Analysis(load(trace.find_xplane(trace_dir)), tables,
                               program_spans.of(run).shift_ns)
            except FileNotFoundError:
                pass
        run["device_scopes"] = ana = ana or Analysis({"planes": []}, None)
        if ana.by:
            for text in ana.report() + [
                    "device scopes: the programs' tables took %.2f s (%d "
                    "programs), reading the trace through them %.2f s"
                    % (t1 - t0, len(tables), time.perf_counter() - t1)]:
                print("[perfbench] " + text, flush=True)
    return run["device_scopes"]


def on_chip(run):
    """The analysis where it may give a device number (a run on the TPU
    whose trace met a table), else None."""
    if run["device"]["platform"] != "tpu":
        return None
    ana = of(run)
    return ana if ana.by else None


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="a trace directory or an .xplane.pb")
    ap.add_argument("--tables", required=True,
                    help="device_scopes_rank<r>.json of the traced process")
    args = ap.parse_args(argv)
    path = args.trace if os.path.isfile(args.trace) \
        else trace.find_xplane(args.trace)
    with open(args.tables) as f:
        tables = json.load(f)["programs"]
    shift = program_spans.Analysis(program_spans.load(path)).shift_ns
    print("\n".join(Analysis(load(path), tables, shift).report()))
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
