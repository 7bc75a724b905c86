"""``device.scoped_share`` of a serving cell: share of the traced tail's op
self time that the engine programs' scope tables put under a scope."""
from perfbench.harness import scopes


def read(run):
    ana = scopes.on_chip(run)
    return ana and ana.scoped_share()
