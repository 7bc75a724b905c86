"""Device time of the train step's forward pass per optimizer step traced:
op self time of the instructions the step program's scope table puts
under a ``jvp(`` and no ``transpose(`` (direction ``fwd``; differentiation
happens under the ``GRADIENT`` scope alone), mean over the chips like
``train_step.device_s_per_step``, of which it is a part."""
from perfbench.harness import scopes


def read(run):
    ana, steps = scopes.on_chip(run), run["facts"].get("traced_steps")
    if ana is None or not steps or ana.events("train_step") is None:
        return None
    return (ana.seconds("train_step", None, "fwd") or 0.0) / steps
