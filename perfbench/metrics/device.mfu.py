"""Model FLOP/s utilization, per cent: FLOPs the forward and backward
passes require per item (the family's function, recomputation not counted)
times the measured items per second per chip, over the published bf16 peak
of this device kind.  A device that is not in the table is an error."""
from perfbench.harness import estimators, peaks


def read(run):
    readings = run["readings"].get("train_items_per_s_per_chip")
    if not readings or run["device"]["platform"] != "tpu":
        return None
    rate = estimators.median(readings)
    return 100.0 * run["facts"]["flops_per_item"] * rate / peaks.peak(
        run["device"]["kind"])
