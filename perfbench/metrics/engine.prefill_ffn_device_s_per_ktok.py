"""Device time of the feed-forward halves per thousand real prompt tokens:
op self time of the prefill programs under ``ffn`` and ``moe.*``, over the
``tokens`` of the traced ``bf:engine.prefill_call`` spans / 1,000."""
from perfbench.harness import scopes


def read(run):
    ana = scopes.on_chip(run)
    return ana and ana.per_ktok("prefill ", scopes.FFN)
