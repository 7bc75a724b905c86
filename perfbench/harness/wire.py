"""Cross-chip collectives of a compiled (SPMD, per-partition) HLO module:
instruction counts and per-chip wire bytes.  A copy of
bluefog_tpu/utils/hlo_bytes.wire_stats, kept here so that no later PR can
change what "wire bytes" means; exact, repeats from run to run."""
import re

_DT_BYTES = {"f64": 8, "u64": 8, "s64": 8, "c64": 8,
             "f32": 4, "u32": 4, "s32": 4,
             "bf16": 2, "f16": 2, "u16": 2, "s16": 2,
             "u8": 1, "s8": 1, "pred": 1}
_COLLECTIVES = ("all-reduce", "collective-permute", "all-gather",
                "reduce-scatter", "all-to-all")
_PAT = re.compile(r"= (.*?) (" + "|".join(_COLLECTIVES) + r")(-start)?\(")


def _shape_bytes(token):
    m = re.match(r"(\w+)\[([\d,]*)\]", token)
    if not m or m.group(1) not in _DT_BYTES:
        return 0
    n = 1
    for d in [int(d) for d in m.group(2).split(",") if d] or [1]:
        n *= d
    return n * _DT_BYTES[m.group(1)]


def _group_size(line):
    m = re.search(r"replica_groups=\{\{([\d,]+)\}", line)
    if m:
        return len(m.group(1).split(","))
    m = re.search(r"replica_groups=\[\d+,(\d+)\]<=", line)
    return int(m.group(1)) if m else None


def wire_stats(hlo_txt):
    """(counts, bytes) keyed by collective kind; result shapes, with the
    accounting per kind that the original documents: a permute's buffer
    once (half the data bytes of the async ``-start`` tuple), an
    all-gather's ``out*(n-1)/n``, a reduce-scatter's ``out*(n-1)``, an
    all-reduce's payload once, an all-to-all's buffer in full."""
    counts, bytes_ = {}, {}
    for line in hlo_txt.splitlines():
        m = _PAT.search(line)
        if not m:
            continue
        op, is_start = m.group(2), bool(m.group(3))
        toks = [t for t in (_shape_bytes(t) for t in
                            re.findall(r"\w+\[[\d,]*\]", m.group(1))) if t]
        result_b = sum(toks)
        n = _group_size(line)
        if n == 1:
            continue      # groups of one chip (a psum over an axis of size
                          # 1) move nothing: the one change from the original
        if op == "collective-permute":
            data = [t for t in toks if t > 4]
            payload = sum(data) // 2 if is_start else sum(data)
        elif op in ("all-gather", "reduce-scatter") and is_start:
            k = len(toks) // 2
            payload = abs(sum(toks[k:]) - sum(toks[:k]))
        elif op == "all-gather":
            payload = result_b * (n - 1) // n if n else result_b
        elif op == "reduce-scatter":
            payload = result_b * (n - 1) if n else result_b
        else:
            payload = result_b
        counts[op] = counts.get(op, 0) + 1
        bytes_[op] = bytes_.get(op, 0) + payload
    return counts, bytes_
