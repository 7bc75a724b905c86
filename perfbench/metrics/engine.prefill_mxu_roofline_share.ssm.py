"""``engine.prefill_mxu_roofline_share`` for the state-space programs: the
operations the traced tail's prompts need (the family's ``prefill_flops``
of each call's REAL tokens: every matmul of the layers held, of the routed
experts the expected share that falls on the held ones, the recurrence,
causal attention on the attention layers, the head for one position;
padding, a chunk's scores and masked products count for nothing) over the
device time of the state-space prefill program's events in the trace times
the published bfloat16 peak of this device kind.  Operations a program
cannot avoid, so at most 1; a prompt served alone reads every held
expert's weights for itself, so expect a small share at short prompts.
(That metric's reader and its ``.latent`` twin name other families'
programs.)"""
from perfbench.harness import manifest, peaks, program_spans

PROGRAM = "jit__ssm_prefill_body"


def read(run):
    ana = program_spans.of(run)
    calls = ana.named("bf:engine.prefill_call")
    family = manifest.load_module("families", run["config"]["family"])
    if not calls or run["device"]["platform"] != "tpu" \
            or not hasattr(family, "prefill_flops"):
        return None
    events = manifest.load_module(
        "metrics", "engine.decode_hbm_roofline_share.kv").program_events(
            run, ana, PROGRAM)
    # a call's program event starts inside its span (the span ends only
    # once the device's answer is back)
    flops = busy_ns = i = 0
    for call in sorted(calls, key=lambda s: s.start):
        while i < len(events) and events[i][0] < call.start:
            i += 1
        if i < len(events) and events[i][0] <= call.start + call.dur:
            flops += family.prefill_flops(run["config"], call.attrs["tokens"])
            busy_ns += events[i][1]
            i += 1
    if not busy_ns:
        return None
    return flops / (busy_ns / 1e9 * peaks.peak(run["device"]["kind"],
                                               "bf16_flops_per_s"))
