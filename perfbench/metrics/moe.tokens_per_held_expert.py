"""Tokens a held expert sees per decode call and layer: the token-expert
pairs of live lanes that fell on experts this chip holds (`pairs`, an
attribute of the program's bf:engine.held_work marks in the traced tail)
over the held experts and the expert layers (the family's word for both:
``held_experts(cfg)``, ``expert_layers(cfg)``) and the calls marked."""
from perfbench.harness import manifest, program_spans


def read(run):
    ana = program_spans.of(run)
    pairs = ana.attr_sum("bf:engine.held_work", "pairs")
    family = manifest.load_module("families", run["config"]["family"])
    if pairs is None or not hasattr(family, "held_experts"):
        return None
    cfg = run["config"]
    calls = len(ana.named("bf:engine.held_work"))
    return pairs / (family.held_experts(cfg) * family.expert_layers(cfg)
                    * calls)
