"""Device time a decode call spends landing its tokens in the cache: op
self time of the decode programs under ``cache.write``, over their module
events in the traced tail."""
from perfbench.harness import scopes


def read(run):
    ana = scopes.on_chip(run)
    return ana and ana.per_call("decode ", ("cache.write",))
