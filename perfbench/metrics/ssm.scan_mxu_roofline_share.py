"""Share of the matrix unit's peak the chunked scan reaches in prefill:
the operations the recurrence itself needs for the traced tail's prompts
(the family's ``ssm_scan_flops`` of the prefill calls' REAL tokens: the
state's update and its read-out, one multiply-add a state element each;
padding, a chunk's scores and masked products, the cumulative sums and the
exponentials count for nothing) over the op self time of the prefill
programs under ``ssm.scan`` times the published bfloat16 peak of this
device kind.  Operations a program cannot avoid, so at most 1; and small by
nature: most of a scan is not matrix work."""
from perfbench.harness import manifest, peaks, scopes


def read(run):
    family = manifest.load_module("families", run["config"]["family"])
    ana = scopes.on_chip(run)
    if not ana or not hasattr(family, "ssm_scan_flops"):
        return None
    seconds = ana.seconds("prefill ", ("ssm.scan",))
    tokens = sum(t for key, t in ana.tokens.items()
                 if key.startswith("prefill "))
    if not seconds or not tokens:
        return None
    return family.ssm_scan_flops(run["config"], tokens) / (
        seconds * peaks.peak(run["device"]["kind"], "bf16_flops_per_s"))
