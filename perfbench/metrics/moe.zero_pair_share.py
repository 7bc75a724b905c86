"""Share of the token-expert pairs of live decode lanes that fell on
identity experts, which compute nothing: `zero_pairs` over the configuration's
``moe_topk`` times `token_layers` (the live tokens times the expert layers
that routed them), both attributes of the program's bf:engine.held_work
marks in the traced tail.  A router that spread its selections evenly over
its outputs would read ``zero_expert_num`` over all of them; what the seed's
weights and selection bias make of it is this reading.  A program without
the attributes (one with no identity experts) gives nothing to read."""
from perfbench.harness import program_spans


def read(run):
    ana = program_spans.of(run)
    zero = ana.attr_sum("bf:engine.held_work", "zero_pairs")
    tokens = ana.attr_sum("bf:engine.held_work", "token_layers")
    top_k = run["config"].get("moe_topk")
    if zero is None or not tokens or not top_k:
        return None
    return zero / (top_k * tokens)
