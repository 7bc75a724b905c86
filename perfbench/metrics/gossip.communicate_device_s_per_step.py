"""Device time of the neighbour exchange per optimizer step traced: op
self time of EVERY instruction under the step program's ``COMMUNICATE``
scope (packing the flat buffer, the permutes' exposed waits, the combine,
unpacking), mean over the chips.  ``gossip.exposed_s_per_step`` stays
beside it: the collective operations alone, worst chip."""
from perfbench.harness import scopes


def read(run):
    ana, steps = scopes.on_chip(run), run["facts"].get("traced_steps")
    if ana is None or not steps or ana.events("train_step") is None:
        return None
    return (ana.seconds("train_step", ("COMMUNICATE",)) or 0.0) / steps
