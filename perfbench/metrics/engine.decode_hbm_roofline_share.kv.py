"""``engine.decode_hbm_roofline_share`` for a family whose cache has two
kinds of layer: the bytes the traced tail's decode calls cannot avoid (the
family's ``decode_floor_bytes``: every weight byte of the layers and the
head a call, a held expert only where a token fell on it, the lanes' LIVE
positions in every full layer and at most a window of them in every window
layer) over the device time of the decode program's events in the trace
times the published HBM bandwidth of this device kind.  A lower bound on
bytes, so at most 1.  (That metric's reader names another family's
program and its floor knows one kind of cache.)"""
import os

from perfbench.harness import manifest, peaks, program_spans, trace

PROGRAM = "jit__hybrid_decode_body"


def program_events(run, ana, prefix):
    """(start ns on the host's clock, duration ns) of the device's module
    events whose name starts with ``prefix``, sorted."""
    doc = program_spans.load(trace.find_xplane(os.path.join(
        run["out_dir"], "trace", run["workload"])))
    return sorted(
        (start + ana.shift_ns, dur) for plane in doc["planes"]
        if trace.DEVICE_PLANE_RE.match(plane["name"])
        for line in plane["lines"] if line["name"] == program_spans.MODULE_LINE
        for name, start, dur, _ in line["events"] if name.startswith(prefix))


def read(run):
    ana = program_spans.of(run)
    marks = [m for m in ana.named("bf:engine.held_work")
             if "positions_window" in m.attrs]
    family = manifest.load_module("families", run["config"]["family"])
    if not marks or run["device"]["platform"] != "tpu" \
            or not hasattr(family, "position_bytes"):
        return None
    # a mark closes its call, so the last program event that started
    # before a mark is that call's
    events = program_events(run, ana, PROGRAM)
    hits = positions = ring = calls = busy_ns = 0
    i, last = 0, None
    for mark in sorted(marks, key=lambda s: s.start):
        while i < len(events) and events[i][0] <= mark.start:
            i += 1
        if i == 0 or i - 1 == last:
            continue                 # no program event of its own in the trace
        last = i - 1
        calls += 1
        busy_ns += events[last][1]
        hits += mark.attrs["experts_hit"]
        positions += mark.attrs["positions"]
        ring += mark.attrs["positions_window"]
    if not calls:
        return None
    floor = family.decode_floor_bytes(run["config"], calls, hits, positions,
                                      ring)
    return floor / (busy_ns / 1e9 * peaks.peak(run["device"]["kind"],
                                               "hbm_bytes_per_s"))
