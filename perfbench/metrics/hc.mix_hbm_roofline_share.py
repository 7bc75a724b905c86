"""Share of the memory roofline the residual streams' maps reach in
prefill: the bytes they cannot avoid for the traced tail's prompts (the
family's ``hc_floor_bytes`` of the prefill calls' REAL tokens: per token
and sublayer ``3 n + 2`` vectors of the hidden size read or written, and
each sublayer's phi once a call; padding counts for nothing) over the op
self time of the prefill programs under ``hc.coef`` and ``hc.mix`` times
the published HBM bandwidth of this device kind.  A lower bound on bytes,
so at most 1: a reading over 1 says that a fusion which carries stream
traffic is named under another scope."""
from perfbench.harness import manifest, peaks, scopes

STREAMS = ("hc.coef", "hc.mix")


def read(run):
    family = manifest.load_module("families", run["config"]["family"])
    ana = scopes.on_chip(run)
    if not ana or not hasattr(family, "hc_floor_bytes"):
        return None
    seconds = ana.seconds("prefill ", STREAMS)
    tokens = sum(t for key, t in ana.tokens.items()
                 if key.startswith("prefill "))
    if not seconds or not tokens:
        return None
    floor = family.hc_floor_bytes(run["config"], tokens,
                                  ana.events("prefill "))
    return floor / (seconds * peaks.peak(run["device"]["kind"],
                                         "hbm_bytes_per_s"))
