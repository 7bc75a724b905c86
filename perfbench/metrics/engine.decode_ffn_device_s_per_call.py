"""Device time a decode call spends in the feed-forward halves: op self
time of the decode programs under ``ffn``, ``moe.route``, ``moe.experts``,
``moe.shared``, over their module events in the traced tail."""
from perfbench.harness import scopes


def read(run):
    ana = scopes.on_chip(run)
    return ana and ana.per_call("decode ", scopes.FFN)
