"""Device time of the residual streams' maps per thousand real prompt
tokens: op self time of the prefill programs under ``hc.coef`` and
``hc.mix``, over the ``tokens`` of the traced ``bf:engine.prefill_call``
spans / 1,000."""
from perfbench.harness import scopes

STREAMS = ("hc.coef", "hc.mix")


def read(run):
    ana = scopes.on_chip(run)
    if not ana or not ana.seconds("prefill ", STREAMS):
        return None             # a program without streams has no such time
    return ana.per_ktok("prefill ", STREAMS)
