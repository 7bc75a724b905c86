"""Share of the traced tail's op self time that the programs' scope tables
put under a scope of the vocabulary (docs/OBSERVABILITY.md): how much of
the device's time has a name that survives a recompile.  (The serving
cells report it as ``device.serve_scoped_share``: a per-layer metric names
one end-to-end metric.)"""
from perfbench.harness import scopes


def read(run):
    ana = scopes.on_chip(run)
    return ana and ana.scoped_share()
