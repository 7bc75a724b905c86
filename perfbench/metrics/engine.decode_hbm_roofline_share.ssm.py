"""``engine.decode_hbm_roofline_share`` for a family whose slots hold
recurrent states: the bytes the traced tail's decode calls cannot avoid
(the family's ``decode_floor_bytes``: every weight byte of the layers and
the head a call, a held expert only where a token fell on it, the live
lanes' states and convolution inputs read AND written in every state-space
layer, the lanes' LIVE positions in the attention layers) over the device
time of the decode program's events in the trace times the published HBM
bandwidth of this device kind.  A lower bound on bytes, so at most 1.
(That metric's reader names another family's program and its floor knows
no state.)"""
from perfbench.harness import manifest, peaks, program_spans

PROGRAM = "jit__ssm_decode_body"


def read(run):
    ana = program_spans.of(run)
    marks = [m for m in ana.named("bf:engine.held_work")
             if "state_lanes" in m.attrs]
    family = manifest.load_module("families", run["config"]["family"])
    if not marks or run["device"]["platform"] != "tpu" \
            or not hasattr(family, "ssm_state_bytes"):
        return None
    # a mark closes its call, so the last program event that started
    # before a mark is that call's
    events = manifest.load_module(
        "metrics", "engine.decode_hbm_roofline_share.kv").program_events(
            run, ana, PROGRAM)
    hits = positions = lanes = calls = busy_ns = 0
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
        lanes += mark.attrs["state_lanes"]
    if not calls:
        return None
    floor = family.decode_floor_bytes(run["config"], calls, hits, positions,
                                      lanes)
    return floor / (busy_ns / 1e9 * peaks.peak(run["device"]["kind"],
                                               "hbm_bytes_per_s"))
