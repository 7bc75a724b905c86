"""Device time of the optimizer update per optimizer step traced: op self
time under the step program's ``ADAPT`` scope, mean over the chips."""
from perfbench.harness import scopes


def read(run):
    ana, steps = scopes.on_chip(run), run["facts"].get("traced_steps")
    if ana is None or not steps or ana.events("train_step") is None:
        return None
    return (ana.seconds("train_step", ("ADAPT",)) or 0.0) / steps
