"""Bytes each chip sends per optimizer step in cross-chip collectives, from
the compiled step's HLO (exact; 0 on one chip)."""


def read(run):
    st = run["facts"].get("structure") or {}
    if "wire_bytes_per_call" not in st:
        return None
    return st["wire_bytes_per_call"] / run["facts"]["steps_per_call"]
