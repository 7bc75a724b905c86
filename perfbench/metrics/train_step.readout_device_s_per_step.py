"""Device time of the LM train step's read-out per optimizer step traced:
op self time of the instructions the step program's scope table puts under
``readout`` (the embedding lookup, the final norm, the head's products and
the loss, forward and backward alike), mean over the chips like
``train_step.device_s_per_step``, of which it is a part.  The scope is the
program's own and older than this reader: a parent reads it too.

Where the compiler fuses the head's Adam update into the product that makes
the head's gradient (the parent of PR 51 and PR 51's form both: 2.8 ms a
step at ``pythia-410m``'s sizes), that update counts HERE and not under
``ADAPT``; a form that hands the head's gradient to HBM moves it to
``train_step.optimizer_device_s_per_step``.  Judge a change of the read-out
by the two together."""
from perfbench.harness import scopes


def read(run):
    ana, steps = scopes.on_chip(run), run["facts"].get("traced_steps")
    if ana is None or not steps or ana.events("train_step") is None:
        return None
    return (ana.seconds("train_step", ("readout",), None) or 0.0) / steps
