"""Device time a decode call spends on the residual streams' maps: op self
time of the decode programs under ``hc.coef`` (the norm of the flattened
streams, the 24 coefficients, the sigmoids, the Sinkhorn rounds) and
``hc.mix`` (every read and write of the streams), over their module events
in the traced tail."""
from perfbench.harness import scopes

STREAMS = ("hc.coef", "hc.mix")


def read(run):
    ana = scopes.on_chip(run)
    if not ana or not ana.seconds("decode ", STREAMS):
        return None             # a program without streams has no such time
    return ana.per_call("decode ", STREAMS)
