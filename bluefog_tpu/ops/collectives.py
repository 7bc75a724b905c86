"""Neighbor/global collectives over a mesh axis.

TPU-native re-design of the reference's op execution layer.  Where the
reference negotiates per-tensor requests on a background thread and then calls
``MPI_Neighbor_allgather`` / ``ncclSend``/``ncclRecv`` groups
(``operations.cc:567-764``, ``mpi_controller.cc:419-745``,
``nccl_controller.cc:710-948``), here every op is a pure function traced once
under ``jit``: the topology arrives pre-compiled as a
:class:`~bluefog_tpu.schedule.CommSchedule` and each round lowers to one
``lax.ppermute`` (XLA collective-permute on the ICI torus).  Negotiation,
handle tables and fusion buffers have no equivalent — XLA programs are
deterministic and the compiler fuses the weighted combines into the permute
epilogues.

All functions take ``axis``: the mesh axis name the op runs over.  They must
be called inside ``shard_map`` (or ``pjit`` with manual axes) with one block
per device.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..schedule import CommSchedule

Axis = str


def my_rank(axis: Axis = "rank") -> jax.Array:
    """This device's index along ``axis`` (reference: ``bf.rank()``)."""
    return lax.axis_index(axis)


def _table(row: np.ndarray, idx: jax.Array, dtype=None) -> jax.Array:
    """Look up this device's entry of a per-device table (baked-in constant)."""
    t = jnp.asarray(row)[idx]
    return t.astype(dtype) if dtype is not None else t


def corrupt_payload(x: jax.Array, rank: int, *, axis: Axis = "rank") -> jax.Array:
    """Fault-injection support: NaN this block iff the device IS ``rank``.

    The traced primitive behind :mod:`bluefog_tpu.utils.chaos`'s payload
    corruption — the sick-rank emulation whose detection/rollback the
    resilience layer owes the user.  Non-target ranks pass their block
    through untouched; integer payloads are left alone (NaN has no integer
    encoding, and corrupting lengths/counters would break shape plumbing
    rather than emulate a numerics fault)."""
    if not jnp.issubdtype(x.dtype, jnp.inexact):
        return x
    bad = jnp.full(x.shape, jnp.nan, x.dtype)
    return jnp.where(lax.axis_index(axis) == rank, bad, x)


WIRE_CODECS = ("bf16", "int8", "fp8")


def _parse_wire(wire: str) -> Tuple[str, Optional[int]]:
    """``"int8"`` -> (int8, None); ``"int8@256"`` -> (int8, 256).

    The ``@B`` suffix switches the quantizers from one amax scale per
    buffer to one per B-element block: a single outlier then costs only
    its own block's resolution instead of the whole payload's, for
    4/B extra bytes per block (~1.6 % at B=256).  bf16 is a plain cast
    and takes no block size."""
    if not isinstance(wire, str):
        raise ValueError(
            f"unknown wire codec {wire!r}: pass one of {WIRE_CODECS} "
            "(optionally with an @B block-size suffix for int8/fp8)")
    base, sep, blk = wire.partition("@")
    if not sep:
        return base, None
    if base == "bf16":
        raise ValueError("bf16 is a plain cast; block size applies only "
                         "to the quantizing codecs (int8/fp8)")
    try:
        b = int(blk)                  # "" raises too: "int8@" is malformed
    except ValueError:
        b = 0
    if b <= 0:
        raise ValueError(f"bad wire block size in {wire!r}")
    return base, b


def _block(xf: jax.Array, blk: int) -> jax.Array:
    """Flatten + zero-pad to a multiple of ``blk``, reshape [nb, blk]
    (decode recovers the original size from the caller's ``shape``)."""
    flat = xf.reshape(-1)
    pad = (-flat.size) % blk
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(-1, blk)


def _amax_scale(xf: jax.Array, qmax: float, blk: Optional[int]):
    """(scaled values ready to cast, riding scale(s)).  Per-buffer when
    ``blk`` is None, else one scale per block row.  The scale is floored
    at the smallest NORMAL f32: for subnormal amax the division would
    underflow to 0 and ``xf/scale`` become inf (which e4m3fn, having no
    inf, would turn into payload-poisoning NaN — int8 survives the same
    corner only via its clip).  With the floor, tiny payloads quantize
    to 0: graceful."""
    tiny = float(np.finfo(np.float32).tiny)
    amax = (jnp.max(jnp.abs(xf)) if blk is None
            else jnp.max(jnp.abs(xf), axis=1, keepdims=True))
    scale = jnp.where(amax > 0, jnp.maximum(amax / qmax, tiny), 1.0)
    return xf / scale, scale.astype(jnp.float32)


def _wire_encode(wire: str, x: jax.Array) -> Tuple[jax.Array, ...]:
    """Compress ``x`` for the permute wire.  ``bf16`` halves the bytes by a
    plain cast (the TPU counterpart of the reference's fp16 wire support,
    ``common/half.{h,cc}``); ``int8`` quarters them with symmetric
    quantization whose f32 scale rides beside the payload; ``fp8`` also
    quarters them but keeps a floating representation (e4m3fn,
    amax-scaled) — same wire bytes as int8 with better relative precision
    for the heavy-tailed values gossip payloads actually carry.  An
    ``@B`` suffix (e.g. ``"int8@256"``) scales per B-element block
    instead of per buffer (:func:`_parse_wire`)."""
    base, blk = _parse_wire(wire)
    if base == "bf16":
        return (x.astype(jnp.bfloat16),)
    if base in ("int8", "fp8"):
        xf = x.astype(jnp.float32)
        if blk is not None:
            xf = _block(xf, blk)
        if base == "int8":
            scaled, scale = _amax_scale(xf, 127.0, blk)
            q = jnp.clip(jnp.round(scaled), -127, 127).astype(jnp.int8)
        else:
            f8max = float(jnp.finfo(jnp.float8_e4m3fn).max)    # 448
            scaled, scale = _amax_scale(xf, f8max, blk)
            q = scaled.astype(jnp.float8_e4m3fn)
        return (q, scale)
    raise ValueError(f"unknown wire codec {wire!r}; choose from "
                     f"{WIRE_CODECS} (quantizers accept an '@B' block "
                     "suffix, e.g. 'int8@256')")


def _wire_decode(wire: str, parts: Tuple[jax.Array, ...], dtype,
                 shape=None) -> jax.Array:
    base, blk = _parse_wire(wire)
    if base == "bf16":
        return parts[0].astype(dtype)
    q, scale = parts
    out = q.astype(jnp.float32) * scale          # broadcasts per-block too
    if blk is not None:
        out = out.reshape(-1)[:int(np.prod(shape))].reshape(shape)
    return out.astype(dtype)


def _wire_ppermute(wire: Optional[str], send: jax.Array, axis: Axis,
                   perm) -> jax.Array:
    """One ppermute round, optionally wire-compressed.

    The barriers pin the codec around the permute: XLA's collective
    reorderer happily commutes a bare convert across a collective-permute
    and fuses encode+decode into a no-op, which silently puts FULL-WIDTH
    bytes back on the wire (caught by the v5e AOT payload tests).  Shared
    by the gossip collectives and the window ops so the pinning subtlety
    lives in exactly one place."""
    if wire is None:
        return lax.ppermute(send, axis, perm=perm)
    if not jnp.issubdtype(send.dtype, jnp.floating):
        # complex would silently lose its imaginary part in the codecs
        raise ValueError(
            f"wire compression needs a real float input, got {send.dtype}")
    parts = lax.optimization_barrier(_wire_encode(wire, send))
    moved = lax.optimization_barrier(tuple(
        lax.ppermute(p, axis, perm=perm) for p in parts))
    return _wire_decode(wire, moved, send.dtype, shape=send.shape)


def _default_concurrent() -> bool:
    """Round-parallel default: live-context knob, else BLUEFOG_ROUND_PARALLEL.

    Lazy imports keep ops importable without the context/config layers
    (the AOT tests build schedules with no live mesh).
    """
    try:
        from ..parallel import context as _ctx
        c = _ctx._context
        if c is not None and c.round_parallel is not None:
            return bool(c.round_parallel)
    except Exception:
        pass
    try:
        from ..utils.config import env_flag
        return env_flag("BLUEFOG_ROUND_PARALLEL", False)
    except Exception:
        return False


def _default_dcn_wire() -> Optional[str]:
    """Process default for the DCN-hop wire codec of hierarchical gossip:
    live-context knob (``bf.set_dcn_wire``), else ``BLUEFOG_DCN_WIRE``.

    Only the *machine-axis* permutes of ``hierarchical_neighbor_allreduce``
    consult this — flat gossip keeps its explicit ``wire=`` contract — so
    setting it compresses exactly the cross-slice edges, never the
    intra-slice reduce.  Lazy imports for the same reason as
    :func:`_default_concurrent`.
    """
    try:
        from ..parallel import context as _ctx
        c = _ctx._context
        if c is not None and c.dcn_wire is not None:
            return c.dcn_wire if c.dcn_wire != "off" else None
    except Exception:
        pass
    try:
        import os
        w = os.environ.get("BLUEFOG_DCN_WIRE", "").strip()
        if w and w.lower() not in ("off", "none", "0"):
            _check_wire(w)      # validate eagerly: a typo'd codec must not
            return w            # silently fall back to full-width DCN bytes
    except ValueError:
        raise
    except Exception:
        pass
    return None


def _check_wire(wire: str) -> str:
    """Validate a wire-codec spec eagerly (base + optional @B block size).

    ``_parse_wire`` alone defers base validation to encode time (deep inside
    a trace); the knob/env entry points call this instead so a typo fails at
    the line that sets it."""
    base, _ = _parse_wire(wire)
    if base not in WIRE_CODECS:
        raise ValueError(
            f"unknown wire codec {wire!r}: pass one of {WIRE_CODECS} "
            "(optionally with an @B block-size suffix for int8/fp8)")
    return wire


def _round_sends(x: jax.Array, sched: CommSchedule, idx) -> list:
    """Per-round send values (dst-weighting applies the sender-side scale)."""
    sends = []
    for r in range(sched.num_rounds):
        send = x
        if sched.uses_dst_weighting:
            # dst-weighting: the *sender* scales per-edge before the permute
            # (reference fusion-buffer trick, mpi_controller.cc:1394-1454).
            send = x * _table(sched.send_scale[r], idx, x.dtype)
        sends.append(send)
    return sends


def _concurrent_ppermutes(wire: Optional[str], sends, axis: Axis,
                          rounds) -> list:
    """Issue every round's permute as one concurrent group.

    The sequential path interleaves ``acc = acc + recv * w`` between
    permutes, handing the scheduler a chain it tends to respect; here all
    sends are materialized first, every permute is issued back-to-back with
    no arithmetic between them, and only then are the results combined —
    the permute group's depth is the chromatic index of the topology, not
    ``num_rounds`` sequential hops.  The barriers serve double duty: they
    pin the wire codecs exactly like :func:`_wire_ppermute` (encode/decode
    must not commute across the permutes) and they fence the group so the
    combine arithmetic cannot be threaded between rounds.
    """
    if wire is None:
        sends = lax.optimization_barrier(tuple(sends))
        recvs = lax.optimization_barrier(tuple(
            lax.ppermute(s, axis, perm=perm)
            for s, perm in zip(sends, rounds)))
        return list(recvs)
    for s in sends:
        if not jnp.issubdtype(s.dtype, jnp.floating):
            raise ValueError(
                f"wire compression needs a real float input, got {s.dtype}")
    encoded = [_wire_encode(wire, s) for s in sends]
    widths = [len(parts) for parts in encoded]
    flat = lax.optimization_barrier(
        tuple(p for parts in encoded for p in parts))
    moved, pos = [], 0
    for w, perm in zip(widths, rounds):
        moved.extend(lax.ppermute(flat[pos + i], axis, perm=perm)
                     for i in range(w))
        pos += w
    moved = lax.optimization_barrier(tuple(moved))
    recvs, pos = [], 0
    for w, s in zip(widths, sends):
        recvs.append(_wire_decode(wire, tuple(moved[pos:pos + w]),
                                  s.dtype, shape=s.shape))
        pos += w
    return recvs


def neighbor_exchange(
    x: jax.Array,
    sched: CommSchedule,
    *,
    axis: Axis = "rank",
    wire: Optional[str] = None,
    concurrent: Optional[bool] = None,
) -> List[Tuple[jax.Array, jax.Array]]:
    """The permute rounds of :func:`neighbor_allreduce` without its combine:
    per round, what this device received and the weight it enters the
    combine with (:func:`neighbor_combine`).  ``wire`` and ``concurrent``
    as there."""
    if concurrent is None:
        concurrent = _default_concurrent()
    idx = lax.axis_index(axis)
    sends = _round_sends(x, sched, idx)
    if concurrent and sched.num_rounds > 1:
        recvs = _concurrent_ppermutes(wire, sends, axis, sched.rounds)
    else:
        recvs = [_wire_ppermute(wire, send, axis, perm)
                 for send, perm in zip(sends, sched.rounds)]
    return [(recv, _table(sched.recv_weight[r], idx, x.dtype))
            for r, recv in enumerate(recvs)]


def neighbor_combine(
    x: jax.Array,
    sched: CommSchedule,
    received: Sequence[Tuple[jax.Array, jax.Array]],
    *,
    axis: Axis = "rank",
) -> jax.Array:
    """``self_weight * x + sum_r w_r * recv_r`` in round order, over the
    ``(recv_r, w_r)`` of :func:`neighbor_exchange` or equal slices of
    ``x`` and of every ``recv_r``."""
    acc = x * _table(sched.self_weight, lax.axis_index(axis), x.dtype)
    for recv, w in received:
        acc = acc + recv * w
    return acc


def neighbor_allreduce(
    x: jax.Array,
    sched: CommSchedule,
    *,
    axis: Axis = "rank",
    wire: Optional[str] = None,
    concurrent: Optional[bool] = None,
) -> jax.Array:
    """Weighted average of ``x`` with in-neighbor values under ``sched``.

    Computes ``self_weight * x + sum_r recv_weight[r] * ppermute_r(x)``:
    the combine the reference performs in ``PerformNeighborAllreduceCallback``
    (``torch/mpi_ops.cc:99-164``), fused here into the permute rounds.
    ``ppermute`` zero-fills devices that receive nothing in a round and their
    table weight is 0, so irregular topologies need no masking.

    ``wire`` compresses the permuted bytes (``"bf16"`` 2x; ``"int8"`` and
    ``"fp8"`` 4x with a riding scale — per buffer, or per B-element block
    with an ``"@B"`` suffix like ``"int8@256"``) — a lever for comm-bound
    regimes (small batch, DCN cross-machine edges).  The self term always combines at full precision;
    gossip averaging tolerates the bounded quantization error the way
    consensus tolerates stale neighbor values.

    ``concurrent=True`` emits the edge-colored rounds as one concurrent
    permute group instead of a sequential permute chain — every
    round's input is ``x`` (rounds are edge-disjoint by construction,
    :func:`bluefog_tpu.schedule.rounds_edge_disjoint`), so the chain depth
    was never semantically required.  The weighted combine happens after
    the exchange, in round order, either way.  ``None`` (default) resolves
    to the context's ``round_parallel`` knob, then
    ``BLUEFOG_ROUND_PARALLEL``, then False.

    The exchange (:func:`neighbor_exchange`) and the combine
    (:func:`neighbor_combine`) are callable apart: the fused communicator
    exchanges one buffer and combines leaf by leaf.
    """
    received = neighbor_exchange(x, sched, axis=axis, wire=wire,
                                 concurrent=concurrent)
    return neighbor_combine(x, sched, received, axis=axis)


def neighbor_allgather(
    x: jax.Array,
    sched: CommSchedule,
    *,
    axis: Axis = "rank",
) -> jax.Array:
    """Concatenate in-neighbor tensors along dim 0, sorted by source rank.

    Reference: ``MPI_Neighbor_allgatherv`` (``mpi_controller.cc:282``).  XLA
    needs a uniform output shape, so the result has ``max_in_degree`` slots on
    every device; devices with smaller in-degree leave trailing slots zero
    (their ``in_degree`` is available statically from the schedule).  For
    regular topologies this is exactly the reference output.
    """
    idx = lax.axis_index(axis)
    slots = max(sched.max_in_degree, 1)
    d0 = x.shape[0]
    out = jnp.zeros((slots * d0,) + x.shape[1:], x.dtype)
    for r in range(sched.num_rounds):
        recv = lax.ppermute(x, axis, perm=sched.rounds[r])
        received = _table(sched.recv_src[r] >= 0, idx)
        start = jnp.where(received, _table(sched.recv_slot[r], idx) * d0, 0)
        cur = lax.dynamic_slice_in_dim(out, start, d0, axis=0)
        new = jnp.where(received, recv, cur)
        out = lax.dynamic_update_slice_in_dim(out, new, start, axis=0)
    return out


def ragged_neighbor_allgather(
    x: jax.Array,
    length: jax.Array,
    sched: CommSchedule,
    *,
    axis: Axis = "rank",
) -> Tuple[jax.Array, jax.Array]:
    """Neighbor allgather of padded ragged slices — ONE collective chain.

    ``x`` is ``[max_d0, ...]`` with this rank's valid rows ``x[:length]``.
    The 4-byte length channel rides inside the same permuted buffer as the
    data (everything is bitcast to bytes, the length appended as one extra
    row), instead of paying a second full permute chain for 4 bytes the way
    two separate allgathers would.  The reference pre-negotiates sizes over
    its control channel (``mpi_context.cc:504-630``); under SPMD the length
    is just payload.

    Returns ``(gathered [max_in_degree * max_d0, ...], lengths
    [max_in_degree])`` sorted by source rank, zero-padded on ranks with
    smaller in-degree.
    """
    orig_dtype = x.dtype
    if x.dtype == jnp.bool_:
        # bitcast rejects bool; a 0/1 byte round-trips exactly
        x = x.astype(jnp.uint8)
    elif jnp.issubdtype(x.dtype, jnp.complexfloating):
        f = jnp.float64 if x.dtype == jnp.complex128 else jnp.float32
        x = jnp.stack([x.real.astype(f), x.imag.astype(f)], axis=-1)

    d0 = x.shape[0]
    row = int(np.prod(x.shape[1:], dtype=np.int64)) if x.ndim > 1 else 1
    itemsize = jnp.dtype(x.dtype).itemsize
    row_b = max(row * itemsize, 1)
    W = max(row_b, 4)

    xb = lax.bitcast_convert_type(x.reshape(d0, -1), jnp.uint8)
    xb = xb.reshape(d0, row_b)
    if W > row_b:
        xb = jnp.pad(xb, ((0, 0), (0, W - row_b)))
    lb = lax.bitcast_convert_type(
        jnp.asarray(length, jnp.int32).reshape(1), jnp.uint8).reshape(1, 4)
    if W > 4:
        lb = jnp.pad(lb, ((0, 0), (0, W - 4)))
    buf = jnp.concatenate([xb, lb], axis=0)              # [d0 + 1, W]

    gathered = neighbor_allgather(buf, sched, axis=axis)
    slots = max(sched.max_in_degree, 1)
    g = gathered.reshape(slots, d0 + 1, W)

    data = g[:, :d0, :row_b].reshape(slots * d0, row, itemsize)
    if itemsize == 1:
        data = data[..., 0]
    data = lax.bitcast_convert_type(data, x.dtype)
    data = data.reshape((slots * d0,) + x.shape[1:])
    if orig_dtype == jnp.bool_:
        data = data.astype(jnp.bool_)
    elif jnp.issubdtype(orig_dtype, jnp.complexfloating):
        data = lax.complex(data[..., 0], data[..., 1]).astype(orig_dtype)
    lens = lax.bitcast_convert_type(g[:, d0, :4], jnp.int32)   # [slots]
    return data, lens


def allreduce(x: jax.Array, *, average: bool = True, axis: Axis = "rank") -> jax.Array:
    """Global allreduce (reference: ``MPIController::Allreduce``)."""
    return lax.pmean(x, axis) if average else lax.psum(x, axis)


def allgather(x: jax.Array, *, axis: Axis = "rank") -> jax.Array:
    """Concatenate all devices' blocks along dim 0 (reference: Allgather)."""
    return lax.all_gather(x, axis, tiled=True)


def broadcast(x: jax.Array, root_rank: int, *, axis: Axis = "rank") -> jax.Array:
    """Every device receives root's block (reference: Broadcast).

    Binomial-tree fan-out in ``ceil(log2 n)`` ``ppermute`` rounds: at round k
    the devices within distance ``2**k`` of the root forward to distance
    ``2**k`` further.  Compared to the masked-``psum`` formulation (a full
    allreduce: ~2x bytes in a 2(n-1)-hop latency chain plus a pointless
    reduction), the tree moves ``log2(n)``x bytes in ``log2(n)`` hops and
    never reduces — the right shape for ``broadcast_parameters`` restarts,
    which are latency-bound.
    """
    n = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    pos = (idx - root_rank) % n          # distance downstream of the root
    y = x
    shift = 1
    while shift < n:
        # only the devices that already hold the value send (n-1 block-sends
        # total across all rounds, the binomial-tree optimum)
        perm = tuple(((root_rank + j) % n, (root_rank + j + shift) % n)
                     for j in range(min(shift, n - shift)))
        recv = lax.ppermute(y, axis, perm=perm)
        # devices at distance [shift, 2*shift) receive from a device that
        # already holds the value; everyone else keeps theirs
        y = jnp.where((pos >= shift) & (pos < 2 * shift), recv, y)
        shift *= 2
    return y


def pair_gossip(
    x: jax.Array,
    partners: Sequence[int],
    *,
    self_weight: float = 0.5,
    pair_weight: float = 0.5,
    axis: Axis = "rank",
) -> jax.Array:
    """Exchange with a paired partner and weighted-average (reference:
    ``MPI_Sendrecv`` pair gossip, ``mpi_controller.cc:747-773``).

    ``partners[i]`` is device i's partner; the pairing must be an involution
    (``partners[partners[i]] == i``).  Self-paired devices keep their value.
    """
    partners = list(int(p) for p in partners)
    n = len(partners)
    for i, p in enumerate(partners):
        if partners[p] != i:
            raise ValueError("partners must be a pairing (involution)")
    perm = tuple((i, partners[i]) for i in range(n) if partners[i] != i)
    if not perm:
        return x
    recv = lax.ppermute(x, axis, perm=perm)
    idx = lax.axis_index(axis)
    paired = _table(np.array([partners[i] != i for i in range(n)]), idx)
    sw = jnp.asarray(self_weight, x.dtype)
    pw = jnp.asarray(pair_weight, x.dtype)
    return jnp.where(paired, sw * x + pw * recv, x)


def hierarchical_neighbor_allreduce(
    x: jax.Array,
    machine_sched: CommSchedule,
    *,
    machine_axis: Axis = "machine",
    local_axis: Axis = "local",
    wire: Optional[str] = None,
    concurrent: Optional[bool] = None,
) -> jax.Array:
    """Machine-level neighbor averaging on a 2-D (machine x local) mesh.

    Reference algorithm (``mpi_controller.cc:452-507``): intra-machine
    allreduce-average -> machine-level neighbor exchange among local rank 0 ->
    intra-machine broadcast.  Under SPMD the pmean over the local (ICI) axis
    already leaves the machine average replicated, the machine-level gossip
    rides the cross-machine axis (DCN on multi-slice), and the trailing
    broadcast is implicit.

    ``wire`` compresses the *machine-axis* permutes only — exactly the bytes
    that cross the thin DCN links on a multi-slice pod — while the
    intra-slice ``pmean`` (ICI, wire-speed) always reduces at full
    precision.  ``None`` resolves to the process default
    (``bf.set_dcn_wire`` / ``BLUEFOG_DCN_WIRE``); pass ``"off"`` to force
    full-width DCN bytes.  ``concurrent`` emits the machine rounds as one
    concurrent permute group (same resolution chain as the flat op:
    ``bf.set_round_parallel`` / ``BLUEFOG_ROUND_PARALLEL``).
    """
    if wire is None:
        wire = _default_dcn_wire()
    elif wire == "off":
        wire = None
    machine_avg = lax.pmean(x, local_axis)
    return neighbor_allreduce(machine_avg, machine_sched, axis=machine_axis,
                              wire=wire, concurrent=concurrent)
