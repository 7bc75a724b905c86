"""Share of the memory roofline the recurrent states' update reaches in
decode: the bytes the states cost the traced tail's decode calls (the
family's ``ssm_state_bytes`` of the ``state_lanes`` of the program's
bf:engine.held_work marks: in every state-space layer each live lane's
state and its convolution's kept inputs, read and written) over the op
self time of the decode programs under ``ssm.scan`` and ``ssm.conv`` times
the published HBM bandwidth of this device kind.  The marks and the module
events are the same calls only up to the traced window's edges, so the
bytes are the marks' mean a call times the module events.  A lower bound
on bytes, so at most 1: a reading over 1 says that a fusion which moves
the states is named under another scope."""
from perfbench.harness import manifest, peaks, program_spans, scopes

STATE = ("ssm.scan", "ssm.conv")


def read(run):
    family = manifest.load_module("families", run["config"]["family"])
    ana = scopes.on_chip(run)
    if not ana or not hasattr(family, "ssm_state_bytes"):
        return None
    marks = [m for m in program_spans.of(run).named("bf:engine.held_work")
             if "state_lanes" in m.attrs]
    seconds, events = ana.seconds("decode ", STATE), ana.events("decode ")
    if not marks or not seconds or not events:
        return None
    lanes = sum(m.attrs["state_lanes"] for m in marks) / len(marks) * events
    return family.ssm_state_bytes(run["config"], lanes) / (
        seconds * peaks.peak(run["device"]["kind"], "hbm_bytes_per_s"))
