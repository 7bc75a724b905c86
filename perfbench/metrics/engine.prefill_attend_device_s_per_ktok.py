"""Device time of attention per thousand real prompt tokens: op self time
of the prefill programs under the attention scopes (``attn``,
``attn.window``, ``attn.full``, ``mla.attend``, ``cache.read``) and the
projections that feed them (``attn.project``, ``mla.project``), over the
``tokens`` of the traced ``bf:engine.prefill_call`` spans / 1,000."""
from perfbench.harness import scopes


def read(run):
    ana = scopes.on_chip(run)
    return ana and ana.per_ktok("prefill ", scopes.ATTENTION + scopes.PROJECTION)
