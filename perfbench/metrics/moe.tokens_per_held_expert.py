"""Tokens a held expert sees per decode call and layer: the token-expert
pairs of live lanes that fell on experts this chip holds (`pairs`, an
attribute of the program's bf:engine.held_work marks in the traced tail)
over the held experts, the expert layers and the calls marked."""
from perfbench.harness import program_spans


def read(run):
    ana = program_spans.of(run)
    pairs = ana.attr_sum("bf:engine.held_work", "pairs")
    if pairs is None:
        return None
    cfg = run["config"]
    calls = len(ana.named("bf:engine.held_work"))
    return pairs / (cfg["n_routed_experts"]
                    * (cfg["num_hidden_layers"] - 1) * calls)
