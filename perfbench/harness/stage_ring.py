"""The program's stage ring (``bluefog_tpu.utils.tracing.stage_records``:
every ``bf:<cat>.<name>`` stage's entry and exit, kept with nothing armed
and no profiler) read after the run, for the UNTRACED window the
end-to-end metrics are taken from: where a stall sat, and whether the
device idled through it.

The grouping rule (``tools/trace_report.py`` states the same one and keeps
its own copy):

* records that lie wholly inside the window are nested by time on one
  thread, as harness/program_spans.py nests a trace's spans; a
  ``bf:host.pause`` record (the program's second observer, armed only) is
  no stage and is kept apart;
* an INSTANCE is a stage with none beneath it, or the time a stage with
  some beneath it spent outside them (``.../(self)``: the retrace check in
  ``collect``, the scheduler's own Python in ``serve.step``);
* instances are grouped by path and bucket: the path names the stages from
  the outermost down (``serve.step/engine.decode_call/collect/wait``: a
  stage's category is written where it differs from its parent's), so it
  holds the enclosing call's kind, and the bucket is the nearest one up
  the path (``S`` of a decode or chunk call or a ``pack``, ``Tpad`` of a
  prefill call);
* an instance's EXCESS is its seconds over its group's median; a group of
  fewer than ``MIN_GROUP`` instances is not judged, and counted as such;
* a STALL is an instance whose excess passes ``STALL_S`` seconds AND
  ``STALL_RATIO`` times its group's median.

For a stall under a ``decode_call`` the next ``wait`` of a decode call that
begins after it bears witness: the scheduler runs one call ahead, so a wait
near zero says the device had finished the program that was queued while
the host stood (the device idled, the HOST heard late), and a wait as long
as ever says the device, or its launch, was late by itself.  Where the
window's median decode wait is itself next to nothing (under ``NO_WAIT_S``:
most steps hold a prefill, which waits the call in flight out) the next
wait says nothing and the line says so.  A prefill is synchronous and has
no such witness.

A program without a stage ring (the parent of the PR that added it) gives
an analysis whose every reading is None.
"""
import sys

from perfbench.harness import estimators

PREFIX = "bf:"
PAUSE = "bf:host.pause"
MIN_GROUP = 5
STALL_S = 0.1
STALL_RATIO = 3.0
NO_WAIT_S = 0.0002      # a median decode wait under this bears no witness
SELF = "(self)"


class Node:
    __slots__ = ("name", "bucket", "t0", "t1", "cpu_s", "parent", "children",
                 "path", "call_bucket")

    def __init__(self, name, bucket, t0, t1, cpu_s, parent):
        self.name, self.bucket, self.t0, self.t1 = name, bucket, t0, t1
        self.cpu_s, self.parent, self.children = cpu_s, parent, []
        cat, _, short = name[len(PREFIX):].partition(".")
        if parent is None:
            self.path, self.call_bucket = cat + "." + short, bucket
            return
        parent.children.append(self)
        same = parent.name.startswith(PREFIX + cat + ".")
        self.path = parent.path + "/" + (short if same else cat + "." + short)
        # the nearest bucket up the path
        self.call_bucket = parent.call_bucket if bucket is None else bucket

    @property
    def dur(self):
        return self.t1 - self.t0

    @property
    def root(self):
        return self if self.parent is None else self.parent.root

    def under(self, name):
        """The nearest stage called ``name`` around this one, or None."""
        p = self.parent
        while p is not None and p.name != name:
            p = p.parent
        return p


class Instance:
    __slots__ = ("node", "path", "bucket", "seconds", "excess", "median", "n")

    def __init__(self, node, path, seconds):
        self.node, self.path, self.seconds = node, path, seconds
        self.bucket = node.call_bucket
        self.excess = self.median = self.n = None


def nest(records, lo, hi):
    """``records`` ((name, bucket, t0, t1, depth, cpu_s), times in seconds)
    that lie wholly in ``[lo, hi]`` -> (stages in time order, each with its
    parent and children; the pause records)."""
    nodes, pauses, stack = [], [], []
    inside = [r for r in records if r[2] >= lo and r[3] <= hi]
    for name, bucket, t0, t1, _depth, cpu_s in sorted(
            inside, key=lambda r: (r[2], -r[3])):
        if name == PAUSE:
            pauses.append((t0, t1))
            continue
        while stack and stack[-1].t1 <= t0:
            stack.pop()
        node = Node(name, bucket, t0, t1, cpu_s, stack[-1] if stack else None)
        stack.append(node)
        nodes.append(node)
    return nodes, pauses


def instances(nodes):
    """Every leaf, and every other stage's time outside its children,
    with its excess over its group's median where the group is judged.
    Returns (instances, {(path, bucket): its instances})."""
    out, groups = [], {}
    for node in nodes:
        if node.children:
            inst = Instance(node, node.path + "/" + SELF,
                            node.dur - sum(c.dur for c in node.children))
        else:
            inst = Instance(node, node.path, node.dur)
        out.append(inst)
        groups.setdefault((inst.path, inst.bucket), []).append(inst)
    for members in groups.values():
        if len(members) < MIN_GROUP:
            continue
        median = estimators.median([m.seconds for m in members])
        for m in members:
            m.median, m.n, m.excess = median, len(members), m.seconds - median
    return out, groups


class Analysis:
    """What the stage ring says of one window.  ``records`` None: the
    program keeps no stage ring.  ``dropped``: what the ring had overwritten
    when it was read.  ``observed``: whether the pause observer ran."""

    def __init__(self, records, window, dropped=0, observed=False):
        self.decode_wait_s_p50 = self.decode_read_back_s_p50 = None
        self.excess_s_max = self.stall_s = None
        self.stalls, self.lines, self.nodes, self.unjudged = [], [], [], 0
        self.pauses, self.groups, self.waits = [], {}, []
        if records is None:
            return
        lo, hi = window
        if dropped and (not records or records[0][3] >= lo):
            # the oldest record kept ended inside the window: what came
            # before it in the window is gone
            self.lines.append(
                "stage ring: %d records were overwritten, some of them this "
                "window's: no reading is made of it" % dropped)
            return
        self.nodes, self.pauses = nest(records, lo, hi)
        every, self.groups = instances(self.nodes)
        self.unjudged = sum(len(g) < MIN_GROUP for g in self.groups.values())
        under = "engine.decode_call/collect/"
        self.waits = [n for n in self.nodes if n.path.endswith(under + "wait")]
        backs = [n for n in self.nodes if n.path.endswith(under + "read_back")]
        if self.waits:
            self.decode_wait_s_p50 = estimators.median(
                [n.dur for n in self.waits])
        if backs:
            self.decode_read_back_s_p50 = estimators.median(
                [n.dur for n in backs])
        judged = [i for i in every if i.excess is not None]
        if not judged:
            return
        self.excess_s_max = max(i.excess for i in judged)
        self.stalls = sorted(
            (i for i in judged if i.excess > STALL_S
             and i.excess > STALL_RATIO * i.median),
            key=lambda i: i.node.t0)
        self.stall_s = sum(i.excess for i in self.stalls)
        self.lines += [self.describe(i, lo, observed) for i in self.stalls]
        if self.unjudged:
            self.lines.append(
                "stage ring: %d groups of fewer than %d instances were not "
                "judged" % (self.unjudged, MIN_GROUP))

    def describe(self, inst, lo, observed):
        """One stall as one line of text."""
        node = inst.node
        text = ("stall at %.6f s: %s bucket %s excess %.6f s over a median "
                "of %.6f s (%d instances)" % (
                    node.t0 - lo, inst.path, inst.bucket, inst.excess,
                    inst.median, inst.n))
        root = node.root
        if root.cpu_s is not None:
            text += "; thread CPU %.6f s across that %s of %.6f s" % (
                root.cpu_s, root.path, root.dur)
        paused = [min(b, node.t1) - max(a, node.t0)
                  for a, b in self.pauses if a < node.t1 and b > node.t0]
        if paused:
            text += ("; the observer's wakes were late by %.6f s inside it: "
                     "the process or the machine stood still"
                     % sum(paused))
        elif observed:
            text += ("; the observer's wakes came on time: the rest of the "
                     "process ran")
        if node.name == PREFIX + "engine.decode_call" \
                or node.under(PREFIX + "engine.decode_call"):
            after = [w for w in self.waits if w.t0 >= node.t1]
            if after:
                w = after[0]
                median = estimators.median(
                    [i.seconds for i in self.groups[
                        (w.path, w.call_bucket)]])
                text += ("; the next decode wait took %.6f s over a median "
                         "of %.6f s: " % (w.dur, median))
                if median < NO_WAIT_S:
                    # (most steps hold a prefill, which waits the call in
                    # flight out: the decode wait finds it done)
                    text += ("a decode wait is next to nothing in this "
                             "window whatever the device did: no witness")
                elif w.dur < 0.25 * median:
                    text += "the device idled through the stall"
                else:
                    text += "the device was busy when the stall ended"
            else:
                text += "; no decode wait follows it in the window"
        elif node.under(PREFIX + "engine.prefill_call") \
                or node.name == PREFIX + "engine.prefill_call":
            text += ("; a prefill is synchronous: no later wait bears "
                     "witness to what the device did")
        return text


def of(run):
    """The analysis of this run's untraced window, made once from the
    program's ring and printed (``perfbench: stall ...`` a stall) on
    standard error."""
    if "stage_ring" not in run:
        from bluefog_tpu.utils import tracing
        read = getattr(tracing, "stage_records", None)
        window = run.get("facts", {}).get("window")
        if read is None or window is None:
            ana = Analysis(None, (0.0, 0.0))
        else:
            ana = Analysis([tuple(r) for r in read()], window,
                           tracing.stage_dropped(), tracing.enabled())
        run["stage_ring"] = ana
        for text in ana.lines:
            print("perfbench: " + text, file=sys.stderr, flush=True)
    return run["stage_ring"]
