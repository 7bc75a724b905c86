"""Device time of the train step's backward pass per optimizer step traced:
op self time of the instructions the step program's scope table puts
under a ``transpose(`` (direction ``bwd``: the transposed pass and what it
recomputes of the forward one), mean over the chips."""
from perfbench.harness import scopes


def read(run):
    ana, steps = scopes.on_chip(run), run["facts"].get("traced_steps")
    if ana is None or not steps or ana.events("train_step") is None:
        return None
    return (ana.seconds("train_step", None, "bwd") or 0.0) / steps
