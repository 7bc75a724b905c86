"""Key blocks the full layers' attention of a prefill call took, the mean
over the traced tail's calls: ``key_blocks``, an attribute of the program's
``bf:engine.prefill_call`` spans (the flash forward kernel holds one
block of a head's keys in VMEM: 1 up to a block, one more for every block
a padded prompt passes; a query's partials over its blocks are merged).
A program whose spans do not carry the attribute gives nothing to read."""
from perfbench.harness import program_spans


def read(run):
    blocks = [c.attrs["key_blocks"]
              for c in program_spans.of(run).named("bf:engine.prefill_call")
              if "key_blocks" in c.attrs]
    return sum(blocks) / len(blocks) if blocks else None
