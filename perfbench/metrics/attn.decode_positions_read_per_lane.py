"""Cache positions the decode program reads per lane and call, over all
the layers held: `positions_read_full` (what its attention met of ONE
full layer in a call, counted by the program where it contracts over the
pages and handed back behind its routing carrier: rows x their length,
whatever the lanes' own lengths) times the full layers plus
`positions_read_window` (of one window layer: rings) times the window
layers, attributes of the program's bf:engine.held_work marks in the
traced tail, over the lanes of the calls marked (`rows` counts lanes x
held experts x expert layers).  Reading only the lanes' live
positions would read `positions` x full layers + `positions_window` x
window layers instead."""
from perfbench.harness import manifest, program_spans


def read(run):
    ana = program_spans.of(run)
    full = ana.attr_sum("bf:engine.held_work", "positions_read_full")
    ring = ana.attr_sum("bf:engine.held_work", "positions_read_window")
    rows = ana.attr_sum("bf:engine.held_work", "rows")
    family = manifest.load_module("families", run["config"]["family"])
    if full is None or ring is None or not rows \
            or not hasattr(family, "layers_of"):
        return None
    cfg = run["config"]
    lanes = rows / (family.held_experts(cfg) * family.expert_layers(cfg))
    return (full * family.layers_of(cfg, "full_attention")
            + ring * family.layers_of(cfg, "sliding_attention")) / lanes
