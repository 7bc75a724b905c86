"""Published peaks of one chip, keyed by ``device_kind`` (peaks.json)."""
import json
import os

_TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peak(device_kind, what="bf16_flops_per_s"):
    with open(_TABLE) as f:
        table = json.load(f)
    kind = device_kind.lower()
    for row in table["peaks"]:
        if row["key"] in kind:
            return float(row[what])
    raise KeyError(f"no published peak for device_kind {device_kind!r}: add "
                   "it to perfbench/harness/peaks.json with its source")
