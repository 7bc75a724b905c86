"""Per-chip wire-byte accounting from compiled (SPMD) HLO text.

Shared by :mod:`bluefog_tpu.autotune` (the cost model's tier-1 evidence)
and the byte proofs among the tests: one accounting, so that a plan's
predicted bytes and a test's measured bytes can never disagree about what
"wire bytes" means.

The counter parses *result* shapes (operand shapes are not always printed
by ``Compiled.as_text()``) and applies per-collective-kind accounting —
each kind moves a different fraction of its printed shapes over the wire.
The async ``-start`` forms are counted once; their ``-done``/``-update``
variants reuse the same buffer and never match (the pattern requires the
opening paren directly after the op name, which ``-done(`` breaks).
"""
import math
import re

_DT_BYTES = {"f64": 8, "u64": 8, "s64": 8, "c64": 8,
             "f32": 4, "u32": 4, "s32": 4,
             "bf16": 2, "f16": 2, "u16": 2, "s16": 2,
             "u8": 1, "s8": 1, "pred": 1,
             "f8e4m3fn": 1, "f8e5m2": 1}

# ops that move bytes across chips; -done/-update variants reuse the same
# buffer and must not be double counted
_COLLECTIVES = ("all-reduce", "collective-permute", "all-gather",
                "reduce-scatter", "all-to-all")


def _shape_bytes(token: str) -> int:
    m = re.match(r"(\w+)\[([\d,]*)\]", token)
    if not m or m.group(1) not in _DT_BYTES:
        return 0
    dims = [int(d) for d in m.group(2).split(",") if d] or [1]
    n = 1
    for d in dims:
        n *= d
    return n * _DT_BYTES[m.group(1)]


def _group_size(line: str):
    """Participant count from replica_groups: ``{{0,1,...}, ...}`` (explicit
    first group) or the iota form ``[groups,size]<=[...]``."""
    m = re.search(r"replica_groups=\{\{([\d,]+)\}", line)
    if m:
        return len(m.group(1).split(","))
    m = re.search(r"replica_groups=\[\d+,(\d+)\]<=", line)
    return int(m.group(1)) if m else None


def wire_stats(hlo_txt: str):
    """Per-chip wire bytes and instruction counts of cross-chip collectives
    in a compiled (SPMD, per-partition) HLO module.

    Parsed from *result* shapes (operand shapes are not always printed),
    with accounting per collective kind — each moves a different fraction
    of its shapes over the wire:

    * ``collective-permute``: the transferred buffer(s) once — XLA's
      combiner can merge several buffers into one permute (tuple result);
      the ``-start`` form's result tuple is ``(in…, out…, sync flags)``,
      so after dropping the scalar sync tokens, half the data bytes.
    * ``all-gather``: each chip sends its 1/n shard to ``n-1`` peers, i.e.
      ``out*(n-1)/n`` bytes (``-start`` result tuple ``(in…, out…)``:
      second half minus first half).
    * ``reduce-scatter``: ``in - out = out*(n-1)`` bytes leave each chip.
    * ``all-reduce``: the reduced payload counted once (the ``-start``
      result is the payload shape itself, not an (in, out) pair — never
      halved; a ring implementation moves ~2x this, this column is
      payload as the published tables state).
    * ``all-to-all``: the buffer counted in full (each chip keeps 1/n —
      a slight upper bound).

    Returns ``(counts, bytes_)``: two dicts keyed by collective kind.
    """
    counts, bytes_ = {}, {}
    # lazy shape span: TPU layouts carry tile annotations with parens
    # (`f32[1024]{1,0:T(8,128)}`), so the span can't be a strict char class
    pat = re.compile(
        r"= (.*?) (" + "|".join(_COLLECTIVES) + r")(-start)?\(")
    for line in hlo_txt.splitlines():
        m = pat.search(line)
        if not m:
            continue
        op, is_start = m.group(2), bool(m.group(3))
        toks = [_shape_bytes(t)
                for t in re.findall(r"\w+\[[\d,]*\]", m.group(1))]
        toks = [t for t in toks if t]       # drop non-data (token[], etc.)
        result_b = sum(toks)
        n = _group_size(line)
        if op == "collective-permute":
            # drop the u32[] sync-flag scalars of the async form; a real
            # payload buffer is never 4 bytes
            data = [t for t in toks if t > 4]
            payload = sum(data) // 2 if is_start else sum(data)
        elif op in ("all-gather", "reduce-scatter") and is_start:
            # result tuple (in…, out…): the difference is what hits the wire
            k = len(toks) // 2
            payload = abs(sum(toks[k:]) - sum(toks[:k]))
        elif op == "all-gather":
            payload = result_b * (n - 1) // n if n else result_b
        elif op == "reduce-scatter":
            payload = result_b * (n - 1) if n else result_b
        else:                               # all-reduce, all-to-all
            payload = result_b
        counts[op] = counts.get(op, 0) + 1
        bytes_[op] = bytes_.get(op, 0) + payload
    return counts, bytes_


def total_wire_bytes(hlo_txt: str) -> int:
    """Sum of :func:`wire_stats` bytes across all collective kinds."""
    _, bytes_ = wire_stats(hlo_txt)
    return int(sum(bytes_.values()))


# ---------------------------------------------------------------------------
# Buffers a compiled program makes: the serving engine's proof that its KV
# cache is updated in place (no copy of it, no layer loop's stacked output)
# ---------------------------------------------------------------------------

_COMPUTATION_RE = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$")
_INSTRUCTION_RE = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([a-z][\w\-]*)\(")
_SHAPES_RE = re.compile(r"\b\w+\[[\d,]*\]")
# results that are another instruction's buffer, not one of their own
_FORWARDING = frozenset((
    "parameter", "get-tuple-element", "tuple", "bitcast", "while",
    "conditional", "call", "optimization-barrier", "copy-done",
    "dynamic-update-slice"))


def _dims(shape_txt: str) -> tuple:
    """``bf16[1,33,16]`` -> ``(33, 16)``: the dimensions, leading ones
    dropped."""
    d = [int(x) for x in shape_txt[shape_txt.index("[") + 1:-1].split(",")
         if x]
    while d[:1] == [1]:
        d.pop(0)
    return tuple(d)


def _result_bytes(type_txt: str) -> int:
    return sum(_shape_bytes(m.group(0))
               for m in _SHAPES_RE.finditer(type_txt))


def _computations(hlo_txt: str) -> dict:
    """``{computation: [(name, opcode, result type text, line), ...]}`` of
    a compiled module's text."""
    comps, cur = {}, None
    for line in hlo_txt.splitlines():
        m = _COMPUTATION_RE.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif cur is not None:
            m = _INSTRUCTION_RE.match(line)
            if m:
                cur.append((m.group(1), m.group(3), m.group(2), line))
    return comps


def loop_writes(hlo_txt: str, elements: int) -> tuple:
    """``(inside, outside)``: how many ``dynamic-update-slice``
    instructions of a compiled module update a buffer of ``elements``
    elements (a KV-cache tensor, under whatever view of it) inside a
    ``while`` loop's body — in what it calls and fuses too — and how
    many outside every loop.  The serving engine's proof that a decode
    call writes its KV cache once per lane and tensor after the layer
    loop, not once per layer in it."""
    comps = _computations(hlo_txt)
    callees = {c: {n for *_, line in rows for n in re.findall(
        r"(?:calls|body|condition|to_apply)=%?([\w.\-]+)", line)}
        for c, rows in comps.items()}
    todo = [n for rows in comps.values() for *_, line in rows
            for n in re.findall(r"body=%?([\w.\-]+)", line)]
    looped = set()
    while todo:
        c = todo.pop()
        if c not in looped:
            looped.add(c)
            todo.extend(callees.get(c, ()))
    count = [0, 0]
    for c, rows in comps.items():
        for _, op, type_txt, _ in rows:
            m = re.search(r"\[([\d,]*)\]", type_txt)
            if op == "dynamic-update-slice" and m and elements == \
                    math.prod(int(d) for d in m.group(1).split(",") if d):
                count[c not in looped] += 1
    return tuple(count)


def materialized(hlo_txt: str, min_bytes: int, dims: tuple = None):
    """``[(name, opcode, bytes), ...]`` of the instructions of a compiled
    module whose result is a buffer of their own of at least ``min_bytes``
    (a tuple result counts the sum of its elements); with ``dims`` only
    those whose result holds an array of exactly these dimensions, leading
    ones apart and whatever its dtype (a staged copy of the rows ``[lanes,
    heads, max_len, head_dim]``, a layer of the cache).

    Left out: instructions inside fused computations (a fusion's interior
    lives in registers), results that forward an operand's buffer
    (``bitcast``, ``get-tuple-element``, ``while``, ...) and updates in
    place (``dynamic-update-slice``, and a fusion that holds one of its
    own result's size).  What is left at cache size in a serving program
    is a copy of the cache."""
    comps = {c: [(name, op, _result_bytes(type_txt), line, type_txt)
                 for name, op, type_txt, line in rows]
             for c, rows in _computations(hlo_txt).items()}
    fused = {name for rows in comps.values() for _, op, _, line, _ in rows
             if op == "fusion"
             for name in re.findall(r"calls=%?([\w.\-]+)", line)}
    out = []
    for comp, rows in comps.items():
        if comp in fused:
            continue
        for name, op, size, line, type_txt in rows:
            if size < min_bytes or op in _FORWARDING:
                continue
            if dims is not None and tuple(dims) not in (
                    _dims(m.group(0))
                    for m in _SHAPES_RE.finditer(type_txt)):
                continue
            if op == "fusion":
                inner = re.search(r"calls=%?([\w.\-]+)", line)
                if inner and any(
                        o == "dynamic-update-slice" and b == size
                        for _, o, b, *_ in comps.get(inner.group(1), ())):
                    continue
            out.append((name, op, size))
    return out


# ---------------------------------------------------------------------------
# Which named part of the program an instruction belongs to: the
# ``jax.named_scope``s a program was traced under come through compilation
# as each instruction's ``metadata={op_name="jit(f)/GRADIENT/jvp(ffn)/dot"}``
# ---------------------------------------------------------------------------

_MODULE_RE = re.compile(r"^HloModule ([\w.\-]+)", re.M)
_OP_NAME_RE = re.compile(r'\bop_name="([^"]*)"')
_CALLS_RE = re.compile(r"\bcalls=%?([\w.\-]+)")
_NAME_RE = re.compile(r"%([\w.\-]+)")


def op_names(hlo_txt: str) -> tuple:
    """``(module name, {computation: {instruction: (opcode, op_name, fused,
    operands)}})`` of a compiled module's text, for every computation the
    device runs instruction by instruction (a fused computation's
    interior is left out: a device trace shows the fusion alone).
    ``op_name`` is the instruction's own metadata (a fusion's is its
    root's; ``""`` where the compiler made the instruction itself: a copy,
    a tuple, a loop), ``fused`` the distinct ``op_name``s of the
    instructions a fusion holds, so that a fusion the compiler put
    together across named parts of the program can be told from one that
    lies inside a single part (``()`` for anything but a fusion), and
    ``operands`` the instructions of the same computation it reads."""
    module = _MODULE_RE.search(hlo_txt)
    comps = _computations(hlo_txt)
    own, roots = {}, {}
    for c, rows in comps.items():
        own[c] = {name: (m.group(1) if (m := _OP_NAME_RE.search(line))
                         else "") for name, _, _, line in rows}
        roots[c] = next((own[c][name] for name, _, _, line in rows
                         if line.lstrip().startswith("ROOT ")), "")
    inner = {name: m.group(1) for rows in comps.values()
             for name, op, _, line in rows
             if op == "fusion" and (m := _CALLS_RE.search(line))}
    fused = set(inner.values())
    out = {}
    for c, rows in comps.items():
        if c in fused:
            continue
        out[c] = {}
        for name, op, _, line in rows:
            held = own.get(inner.get(name), {})
            reads = tuple(dict.fromkeys(
                n for n in _NAME_RE.findall(line.split(" = ", 1)[1])
                if n in own[c] and n != name))
            out[c][name] = (op, own[c][name] or roots.get(inner.get(name), ""),
                            tuple(sorted(set(held.values()) - {""})), reads)
    return (module.group(1) if module else ""), out


# ---------------------------------------------------------------------------
# ICI-vs-DCN attribution from PRE-optimization StableHLO (jax `.lower()`
# text).  Pre-opt is the honest layer for codec pins: the CPU backend
# constant-folds bf16/fp8 casts in *compiled* HLO, but the traced program
# states exactly what dtype each collective moves and between which devices.
# ---------------------------------------------------------------------------

_SHLO_DT_BYTES = {
    "f64": 8, "i64": 8, "ui64": 8,
    "f32": 4, "i32": 4, "ui32": 4,
    "bf16": 2, "f16": 2, "i16": 2, "ui16": 2,
    "f8E4M3FN": 1, "f8E5M2": 1, "f8E4M3B11FNUZ": 1,
    "i8": 1, "ui8": 1, "i1": 1,
}

_SHLO_COLLECTIVES = ("collective_permute", "all_reduce", "all_to_all",
                     "all_gather", "reduce_scatter")

_SHLO_OP_RE = re.compile(
    r'"stablehlo\.(' + "|".join(_SHLO_COLLECTIVES) + r')"')
_SHLO_PAIRS_RE = re.compile(
    r"source_target_pairs\s*=\s*dense<\[(.*?)\]>", re.S)
_SHLO_GROUPS_RE = re.compile(
    r"replica_groups\s*=\s*dense<\[(.*?)\]>", re.S)
_SHLO_RESULT_RE = re.compile(r"->\s*\(?\s*(tensor<[^>]+>(?:,\s*tensor<[^>]+>)*)")
_SHLO_TENSOR_RE = re.compile(r"tensor<((?:\d+x)*)([A-Za-z0-9]+)>")


def _shlo_tensor_bytes(sig: str) -> int:
    total = 0
    for dims, dt in _SHLO_TENSOR_RE.findall(sig):
        if dt not in _SHLO_DT_BYTES:
            continue
        n = 1
        for d in dims.strip("x").split("x"):
            if d:
                n *= int(d)
        total += n * _SHLO_DT_BYTES[dt]
    return total


def _shlo_groups(attr_payload: str):
    """``[[0, 1], [2, 3]]`` inner text -> list of int lists."""
    groups = []
    for chunk in attr_payload.replace("[", "").split("]"):
        nums = [int(x) for x in re.findall(r"-?\d+", chunk)]
        if nums:
            groups.append(nums)
    return groups


def stablehlo_wire_stats(stablehlo_txt: str, slice_size: int):
    """Per-chip collective bytes split into cross-slice (DCN) vs
    intra-slice (ICI) traffic, from pre-optimization StableHLO.

    With the gossip-DP axis outermost (``parallel/compose`` orders devices
    slice-major), devices ``[k*slice_size, (k+1)*slice_size)`` share slice
    ``k``.  A collective is **cross-slice** iff any of its participant
    pairs/groups spans two slice blocks (``device // slice_size`` differs)
    — gossip permutes over the DP axis qualify; PP ppermutes, TP psums,
    and SP all_to_alls never do.  Bytes are the op's result-tensor payload
    counted once per static occurrence (per-chip, SPMD), the same
    convention as the pod-scale AOT proofs.

    Returns a dict: ``{"ici"|"dcn": {kind: {"count", "bytes"}},
    "ici_bytes", "dcn_bytes", "ici_dtypes", "dcn_dtypes"}``.  Collectives
    whose participant attribute cannot be parsed (e.g. hex-packed dense
    literals at very large rank counts) are tallied under ``"unknown"``.
    """
    L = int(slice_size)
    out = {"ici": {}, "dcn": {}, "unknown": {},
           "ici_dtypes": set(), "dcn_dtypes": set()}
    lines = stablehlo_txt.splitlines()
    for i, line in enumerate(lines):
        m = _SHLO_OP_RE.search(line)
        if not m:
            continue
        kind = m.group(1)
        pm = _SHLO_PAIRS_RE.search(line) or _SHLO_GROUPS_RE.search(line)
        groups = _shlo_groups(pm.group(1)) if pm else None
        if kind == "collective_permute" and groups:
            # pairs parse as flat [src, dst] rows under either regex shape
            flat = [x for g in groups for x in g]
            groups = [flat[j:j + 2] for j in range(0, len(flat), 2)]
        # result type: same line for single-line ops, else the region's
        # closing `}) : (...) -> ...` line
        sig_m = _SHLO_RESULT_RE.search(line)
        j = i
        while sig_m is None and j + 1 < len(lines):
            j += 1
            sig_m = _SHLO_RESULT_RE.search(lines[j])
            if lines[j].lstrip().startswith('"stablehlo') and sig_m is None:
                break
        payload = _shlo_tensor_bytes(sig_m.group(1)) if sig_m else 0
        dtypes = {dt for _, dt in
                  _SHLO_TENSOR_RE.findall(sig_m.group(1))} if sig_m else set()
        if groups is None:
            side = "unknown"
        elif any(len({d // L for d in g}) > 1 for g in groups):
            side = "dcn"
        else:
            side = "ici"
        slot = out[side].setdefault(kind, {"count": 0, "bytes": 0})
        slot["count"] += 1
        slot["bytes"] += payload
        if side in ("ici", "dcn"):
            out[side + "_dtypes"] |= dtypes
    out["ici_bytes"] = sum(v["bytes"] for v in out["ici"].values())
    out["dcn_bytes"] = sum(v["bytes"] for v in out["dcn"].values())
    out["ici_dtypes"] = sorted(out["ici_dtypes"])
    out["dcn_dtypes"] = sorted(out["dcn_dtypes"])
    return out


# ---------------------------------------------------------------------------
# dot FLOP accounting from PRE-optimization StableHLO.  Pre-opt is again
# the honest layer: it counts the matmul work the *program* states (the
# grouped-vs-capacity MoE comparison of tests/test_moe_dropless.py), before
# the CPU backend's algebraic simplifications can hide padding waste.
# ---------------------------------------------------------------------------

# pretty form: `stablehlo.dot_general %a, %b, [batching_dims = [..] x
# [..],] contracting_dims = [..] x [..], ... : (tensor<A>, tensor<B>) ->
# tensor<R>`; generic form carries `#stablehlo.dot<...
# lhs_contracting_dimensions = [..] ...>` instead.
_SHLO_DOT_RE = re.compile(r"stablehlo\.dot_general")
_SHLO_DOT_CONTRACT_RE = re.compile(
    r"(?:(?<!lhs_)(?<!rhs_)contracting_dims\s*=\s*\[([\d,\s]*)\]\s*x"
    r"|lhs_contracting_dimensions\s*=\s*\[([\d,\s]*)\])")
_SHLO_DOT_SIG_RE = re.compile(
    r":\s*\(tensor<([^>]+)>,\s*tensor<[^>]+>\)\s*->\s*tensor<([^>]+)>")


def _shlo_dims(spec: str):
    """``"5x2x16xf32"`` -> ``[5, 2, 16]`` (scalar ``"f32"`` -> ``[]``)."""
    return [int(d) for d in spec.split("x") if d.isdigit()]


def stablehlo_dot_flops(stablehlo_txt: str) -> int:
    """Total FLOPs of every ``stablehlo.dot_general`` in the module:
    ``2 * prod(result dims) * prod(lhs contracting dims)`` per op, the
    standard multiply-add convention.  Counts static occurrences once
    (per-chip under SPMD shard_map) — loop trip counts (``lax.scan``
    bodies lower to a single region) are NOT multiplied in, so compare
    programs of identical structure, which is exactly the dropless-vs-
    capacity head-to-head.  Raises on a dot whose contracting dims or
    type signature cannot be parsed — silent undercounting would make
    the graded ratio a lie."""
    total = 0
    for line in stablehlo_txt.splitlines():
        if not _SHLO_DOT_RE.search(line):
            continue
        cm = _SHLO_DOT_CONTRACT_RE.search(line)
        sm = _SHLO_DOT_SIG_RE.search(line)
        if cm is None or sm is None:
            raise ValueError(
                "stablehlo_dot_flops: unparseable dot_general line "
                f"(contracting dims or type signature missing): {line!r}")
        contract = [int(d) for d in
                    re.findall(r"\d+", cm.group(1) or cm.group(2))]
        lhs, res = _shlo_dims(sm.group(1)), _shlo_dims(sm.group(2))
        k = 1
        for d in contract:
            k *= lhs[d]
        n = 1
        for d in res:
            n *= d
        total += 2 * n * k
    return int(total)
