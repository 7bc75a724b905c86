"""Blocking op API over distributed tensors.

User-facing equivalent of ``bluefog/torch/mpi_ops.py``.  A *distributed
tensor* is a global array whose leading axis is the rank axis: ``x[i]`` is
rank i's value, sharded over the mesh (``PartitionSpec('rank')``).  Every op
wraps the SPMD primitives from :mod:`bluefog_tpu.ops` in ``shard_map`` over
the context mesh, jit-compiles once per (op, schedule, shape, dtype) and
caches the executable — the compiled-program analogue of the reference's
fusion/negotiation machinery (there is nothing to negotiate: the program *is*
the agreement).

Nonblocking variants are deliberately absent: JAX dispatch is asynchronous
already, so ``neighbor_allreduce`` returns immediately with a future-backed
array; ``synchronize(x)`` (= ``block_until_ready``) and ``poll(x)`` give the
reference's handle semantics (``mpi_ops.py:962-1005``) without a handle table.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import ops
from .parallel import context as _mesh
from .schedule import CommSchedule, compile_from_weights
from .utils import chaos as _chaos
from .utils import flight as _flight
from .utils import metrics as _metrics
from .utils import timeline as _tl

__all__ = [
    "allreduce", "allgather", "ragged_allgather", "broadcast",
    "neighbor_allreduce", "neighbor_allgather", "ragged_neighbor_allgather",
    "pair_gossip",
    "hierarchical_neighbor_allreduce",
    "barrier", "synchronize", "poll", "resolve_schedule", "shard_distributed",
]

def _dispatch(op_name, fn, *args):
    """Dispatch one eager op under a host timeline span (no-op when the
    timeline is off) — the per-op activities the reference's negotiation
    loop records (``test/timeline_test.py:54-117``) — and count the call +
    payload bytes in the metrics registry."""
    _metrics.record_op(op_name, args)
    _flight.record_op(op_name)
    with _tl.op_span(op_name):
        out = fn(*args)
    # fault injection (zero-cost gate: one attribute load when no plan is
    # installed) — chaos may kill this rank, stall it, or NaN its payload
    if _chaos._plan is not None:
        out = _chaos.on_eager_op(op_name, out)
    return out


def _cached(key, build):
    # The executable cache lives on the parallel context (one process-level
    # cache shared with the window ops), so repeated CommSchedule->jaxpr
    # lowering never retraces regardless of which layer dispatches it.
    return _mesh.cached_program(key, build)


def _per_rank(inner):
    """Lift a per-rank-value op to a [1, ...] mesh block."""
    def f(block, *args, **kwargs):
        return inner(block[0], *args, **kwargs)[None]
    return f


def _shard_map_1d(inner, mesh: Mesh, donate: bool = False):
    return jax.jit(jax.shard_map(
        inner, mesh=mesh, in_specs=P("rank"), out_specs=P("rank")),
        donate_argnums=(0,) if donate else ())


def _shard_map_2d(inner, mesh: Mesh, donate: bool = False):
    return jax.jit(jax.shard_map(
        inner, mesh=mesh,
        in_specs=P(("machine", "local")), out_specs=P(("machine", "local"))),
        donate_argnums=(0,) if donate else ())


def _check_distributed(x, n: int):
    if x.shape[0] != n:
        raise ValueError(
            f"distributed tensor must have leading rank axis of size {n}, "
            f"got shape {x.shape}")


def shard_distributed(x: jax.Array) -> jax.Array:
    """Place a distributed tensor on the mesh, sharded along the rank axis."""
    ctx = _mesh.get_context()
    _check_distributed(x, ctx.size)
    sharding = NamedSharding(ctx.mesh, P("rank"))
    if jax.process_count() > 1:
        # device_put of a host-local array onto a cross-process sharding
        # routes through multihost_utils.assert_equal — a *computation* on
        # the global mesh, which some backends (CPU tests; heterogeneous
        # bring-up) cannot run outside shard_map.  Assembling from per-shard
        # callbacks places each addressable shard directly, no collective.
        arr = np.asarray(x)
        return jax.make_array_from_callback(
            arr.shape, sharding, lambda idx: arr[idx])
    return jax.device_put(x, sharding)


# ---------------------------------------------------------------------------
# Weight-policy resolution (reference: mpi_ops.py:482-535)
# ---------------------------------------------------------------------------

def resolve_schedule(
    self_weight: Optional[Union[float, Sequence[float]]] = None,
    src_weights: Optional[Sequence[Dict[int, float]]] = None,
    dst_weights: Optional[Sequence[Union[Dict[int, float], List[int]]]] = None,
    schedule: Optional[CommSchedule] = None,
    *,
    size: Optional[int] = None,
    default_schedule=None,
) -> CommSchedule:
    """Resolve neighbor-op weights to a compiled schedule.

    Policy (mirroring the reference):
      * nothing given -> the static topology schedule (topology weights when
        the topology was set ``is_weighted``, else uniform 1/(in_degree+1));
      * ``schedule`` given -> used as-is (the idiomatic dynamic-topology path:
        precompile with :func:`bluefog_tpu.schedule.compile_dynamic_schedules`);
      * explicit weights -> ``self_weight`` (scalar or per-rank), per-rank
        ``src_weights`` dicts, optional per-rank ``dst_weights`` (lists mean
        scale 1).  Both of ``self_weight``/``src_weights`` must be present
        together, and ``dst_weights`` requires both — same contract as the
        reference.
    """
    if schedule is not None:
        if self_weight is not None or src_weights is not None or dst_weights is not None:
            raise ValueError("pass either a schedule or explicit weights, not both")
        return schedule
    if self_weight is None and src_weights is None:
        if dst_weights is not None:
            raise ValueError(
                "self_weight and src_weights must be given when dst_weights is used")
        return (default_schedule or _mesh.static_schedule)()
    if self_weight is None or src_weights is None:
        raise ValueError(
            "self_weight and src_weights must be presented at the same time")

    n = _mesh.size() if size is None else size
    if np.isscalar(self_weight):
        self_weights = [float(self_weight)] * n
    else:
        self_weights = [float(w) for w in self_weight]
    if isinstance(src_weights, dict):
        raise ValueError(
            "src_weights must be a per-rank sequence of {src_rank: weight} "
            "dicts (the SPMD program needs every rank's weights)")
    src_list = [dict(d) for d in src_weights]

    dst_list = None
    if dst_weights is not None:
        dst_list = []
        for d in dst_weights:
            if isinstance(d, dict):
                dst_list.append({int(k): float(v) for k, v in d.items()})
            else:
                dst_list.append({int(k): 1.0 for k in d})
    return compile_from_weights(n, self_weights, src_list, dst_list)


# ---------------------------------------------------------------------------
# Ops
# ---------------------------------------------------------------------------

def neighbor_allreduce(
    x: jax.Array,
    *,
    self_weight=None,
    src_weights=None,
    dst_weights=None,
    schedule: Optional[CommSchedule] = None,
    step: Optional[int] = None,
    wire: Optional[str] = None,
    donate: bool = False,
    concurrent: Optional[bool] = None,
) -> jax.Array:
    """Weighted neighbor averaging of each rank's slice (the flagship op).

    Reference: ``bf.neighbor_allreduce`` (``mpi_ops.py:540-592``).  When a
    dynamic topology is installed (``bf.set_dynamic_topology``), pass the
    iteration counter as ``step`` and the matching schedule of the period is
    used automatically.  ``wire`` compresses the gossiped bytes
    (``"bf16"``/``"int8"``/``"fp8"``, see :func:`bluefog_tpu.ops.neighbor_allreduce`).

    ``donate=True`` donates ``x``'s buffer to the computation (output and
    input have identical shape/sharding, so XLA averages in place instead
    of allocating a fresh result).  Opt-in because it invalidates the
    caller's ``x`` — the right mode on step paths that rebind, e.g.
    ``x = bf.neighbor_allreduce(x, donate=True)``.

    ``concurrent=True`` emits the edge-colored gossip rounds as one
    concurrent permute group instead of a sequential chain (default: the
    context knob ``bf.set_round_parallel`` / ``BLUEFOG_ROUND_PARALLEL``,
    see :func:`bluefog_tpu.ops.neighbor_allreduce`).
    """
    ctx = _mesh.get_context()
    _check_distributed(x, ctx.size)
    dyn = ctx.dynamic_schedules
    if (dyn and schedule is None and self_weight is None
            and src_weights is None and dst_weights is None):
        if step is None:
            raise ValueError(
                "a dynamic topology is installed; pass step= (the iteration "
                "counter) so the period's schedule can be selected")
        schedule = dyn[int(step) % len(dyn)]
    sched = resolve_schedule(self_weight, src_weights, dst_weights, schedule)
    # resolve the round-parallel default NOW so it is part of the cache key
    # — otherwise a program traced under one knob setting would be served
    # after the knob flips
    if concurrent is None:
        concurrent = ops.collectives._default_concurrent()
    fn = _cached(
        ("nar", sched, ctx.mesh, x.shape, x.dtype.name, wire, donate,
         concurrent),
        lambda: _shard_map_1d(
            _per_rank(partial(ops.neighbor_allreduce, sched=sched,
                              axis="rank", wire=wire, concurrent=concurrent)),
            ctx.mesh, donate=donate))
    return _dispatch("neighbor_allreduce", fn, x)


def neighbor_allgather(
    x: jax.Array,
    *,
    self_weight=None,
    src_weights=None,
    dst_weights=None,
    schedule: Optional[CommSchedule] = None,
) -> jax.Array:
    """Concatenate in-neighbor slices along each rank's first value dim.

    Output shape ``[n, max_in_degree * d0, ...]``; slots beyond a rank's
    in-degree are zero (regular topologies fill every slot).  Reference:
    ``bf.neighbor_allgather`` (``mpi_ops.py:396-476``).
    """
    ctx = _mesh.get_context()
    _check_distributed(x, ctx.size)
    if x.ndim < 2:
        raise ValueError("neighbor_allgather needs a per-rank first dimension")
    sched = resolve_schedule(self_weight, src_weights, dst_weights, schedule)
    fn = _cached(
        ("nag", sched, ctx.mesh, x.shape, x.dtype.name),
        lambda: _shard_map_1d(
            _per_rank(partial(ops.neighbor_allgather, sched=sched, axis="rank")),
            ctx.mesh))
    return _dispatch("neighbor_allgather", fn, x)


def ragged_neighbor_allgather(
    x: jax.Array,
    lengths,
    *,
    self_weight=None,
    src_weights=None,
    dst_weights=None,
    schedule: Optional[CommSchedule] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Neighbor allgather of per-rank slices with different valid first dims.

    Same pad + length-channel contract as :func:`ragged_allgather` (the
    reference's neighbor_allgather handles varying first dimensions via size
    pre-negotiation, ``mpi_context.cc:504-630``): ``x`` is ``[n, max_d0,
    ...]`` with rank r's valid rows in ``x[r, :lengths[r]]``.  Returns
    ``(gathered [n, max_in_degree * max_d0, ...], lengths [n,
    max_in_degree])`` where slot k of rank r holds the padded slice and valid
    length of its k-th sorted in-neighbor.
    """
    ctx = _mesh.get_context()
    _check_distributed(x, ctx.size)
    if x.ndim < 2:
        raise ValueError("ragged_neighbor_allgather needs a per-rank first "
                         "dimension")
    lengths = jnp.asarray(lengths, jnp.int32).reshape(ctx.size)
    sched = resolve_schedule(self_weight, src_weights, dst_weights, schedule)

    def per_rank(xb, lb):
        # one collective chain: the length channel rides in the data buffer
        data, lens = ops.ragged_neighbor_allgather(
            xb[0], lb[0], sched, axis="rank")
        return data[None], lens[None]

    fn = _cached(
        ("rnag", sched, ctx.mesh, x.shape, x.dtype.name),
        lambda: jax.jit(jax.shard_map(
            per_rank, mesh=ctx.mesh, in_specs=(P("rank"), P("rank")),
            out_specs=(P("rank"), P("rank")))))
    return _dispatch("ragged_neighbor_allgather", fn, x, lengths)


def allreduce(x: jax.Array, average: bool = True,
              *, donate: bool = False) -> jax.Array:
    """Global (weighted-uniform) allreduce. Reference: ``bf.allreduce``.

    ``donate=True``: reduce in place (see :func:`neighbor_allreduce`)."""
    ctx = _mesh.get_context()
    _check_distributed(x, ctx.size)
    fn = _cached(
        ("ar", average, ctx.mesh, x.shape, x.dtype.name, donate),
        lambda: _shard_map_1d(
            _per_rank(partial(ops.allreduce, average=average, axis="rank")),
            ctx.mesh, donate=donate))
    return _dispatch("allreduce", fn, x)


def allgather(x: jax.Array) -> jax.Array:
    """All ranks receive the concatenation of all slices: ``[n, n*d0, ...]``."""
    ctx = _mesh.get_context()
    _check_distributed(x, ctx.size)
    if x.ndim < 2:
        raise ValueError("allgather needs a per-rank first dimension")
    fn = _cached(
        ("ag", ctx.mesh, x.shape, x.dtype.name),
        lambda: _shard_map_1d(
            _per_rank(partial(ops.allgather, axis="rank")), ctx.mesh))
    return _dispatch("allgather", fn, x)


def ragged_allgather(x: jax.Array, lengths) -> Tuple[jax.Array, jax.Array]:
    """Allgather of per-rank slices with *different* valid first dims.

    The reference's allgather accepts tensors whose first dimension differs
    per rank (it pre-negotiates sizes, ``mpi_context.cc:643-717``;
    ``torch_ops_test.py:322``).  XLA needs static shapes, so the TPU contract
    is pad + length channel: ``x`` is ``[n, max_d0, ...]`` with rank r's
    valid data in ``x[r, :lengths[r]]``.  Returns ``(gathered, lengths)``
    where ``gathered[r]`` is ``[n * max_d0, ...]`` (every rank's padded
    slice, in rank order) and ``lengths`` is replicated so each rank can
    slice out the valid prefixes.
    """
    ctx = _mesh.get_context()
    _check_distributed(x, ctx.size)
    lengths = jnp.asarray(lengths, jnp.int32).reshape(ctx.size, 1)
    return allgather(x), allgather(lengths)


def broadcast(x: jax.Array, root_rank: int,
              *, donate: bool = False) -> jax.Array:
    """Every rank's slice becomes root's slice. Reference: ``bf.broadcast``.

    ``donate=True``: overwrite in place (see :func:`neighbor_allreduce`)."""
    ctx = _mesh.get_context()
    _check_distributed(x, ctx.size)
    fn = _cached(
        ("bc", root_rank, ctx.mesh, x.shape, x.dtype.name, donate),
        lambda: _shard_map_1d(
            _per_rank(partial(ops.broadcast, root_rank=root_rank, axis="rank")),
            ctx.mesh, donate=donate))
    return _dispatch("broadcast", fn, x)


def pair_gossip(
    x: jax.Array,
    partners: Sequence[int],
    *,
    self_weight: float = 0.5,
    pair_weight: float = 0.5,
    donate: bool = False,
) -> jax.Array:
    """Paired exchange-and-average. Reference: ``bf.pair_gossip``.

    ``donate=True``: average in place (see :func:`neighbor_allreduce`)."""
    ctx = _mesh.get_context()
    _check_distributed(x, ctx.size)
    key = ("pg", tuple(int(p) for p in partners), float(self_weight),
           float(pair_weight), ctx.mesh, x.shape, x.dtype.name, donate)
    fn = _cached(
        key,
        lambda: _shard_map_1d(
            _per_rank(partial(
                ops.pair_gossip, partners=tuple(int(p) for p in partners),
                self_weight=self_weight, pair_weight=pair_weight, axis="rank")),
            ctx.mesh, donate=donate))
    return _dispatch("pair_gossip", fn, x)


def hierarchical_neighbor_allreduce(
    x: jax.Array,
    *,
    self_weight=None,
    src_machine_weights=None,
    dst_machine_weights=None,
    schedule: Optional[CommSchedule] = None,
    wire: Optional[str] = None,
    donate: bool = False,
    concurrent: Optional[bool] = None,
) -> jax.Array:
    """Machine-level neighbor averaging (reference: ``mpi_ops.py:848-864``).

    Intra-machine average over the ``local`` mesh axis, then machine-level
    gossip over the ``machine`` axis; the result is replicated within each
    machine.  ``donate=True``: average in place (see
    :func:`neighbor_allreduce`).

    ``wire`` compresses the machine-axis permutes only — the DCN hop on a
    multi-slice pod — while the intra-slice reduce stays full precision
    (default: ``bf.set_dcn_wire`` / ``BLUEFOG_DCN_WIRE``; ``"off"`` forces
    full width).  ``concurrent`` round-parallelizes the machine rounds
    (default: ``bf.set_round_parallel`` / ``BLUEFOG_ROUND_PARALLEL``).
    """
    ctx = _mesh.get_context()
    _check_distributed(x, ctx.size)
    # Machine-weight resolution reuses the rank policy at machine scope.
    sched = resolve_schedule(
        self_weight, src_machine_weights, dst_machine_weights, schedule,
        size=ctx.machine_size, default_schedule=_mesh.machine_schedule)
    # resolve the knob-backed defaults NOW so they are part of the cache key
    # — same rule as neighbor_allreduce's concurrent: a program traced under
    # one knob setting must not be served after the knob flips
    if wire is None:
        wire = ops.collectives._default_dcn_wire()
    elif wire == "off":
        wire = None
    if concurrent is None:
        concurrent = ops.collectives._default_concurrent()
    fn = _cached(
        ("hnar", sched, ctx.mesh_2d, x.shape, x.dtype.name, wire, donate,
         concurrent),
        lambda: _shard_map_2d(
            _per_rank(partial(
                ops.hierarchical_neighbor_allreduce, machine_sched=sched,
                machine_axis="machine", local_axis="local",
                wire=wire if wire is not None else "off",
                concurrent=concurrent)),
            ctx.mesh_2d, donate=donate))
    return _dispatch("hierarchical_neighbor_allreduce", fn, x)


# ---------------------------------------------------------------------------
# Synchronization (reference handle semantics without handles)
# ---------------------------------------------------------------------------

def synchronize(x):
    """Block until the async computation backing ``x`` is done; returns ``x``.

    Reference: ``bf.synchronize(handle)`` — JAX arrays *are* the handles.
    """
    return jax.block_until_ready(x)


def poll(x) -> bool:
    """True if ``x``'s computation has completed (reference: ``bf.poll``)."""
    leaves = jax.tree_util.tree_leaves(x)
    return all(leaf.is_ready() for leaf in leaves if hasattr(leaf, "is_ready"))


def hard_sync(x):
    """Device-to-host barrier: returns ``x`` only after every computation
    producing it has actually finished on the device.

    A host transfer cannot complete before the producing program has, so
    fetching one element of each leaf synchronizes on any backend.  On this
    installation (jax 0.9 + libtpu) ``jax.block_until_ready`` closes a
    timed section just as well: ``chip_smoke.py``'s ``timing_barrier`` phase
    times the same fused step under both on the chip and fails if they
    disagree.
    """
    for leaf in jax.tree_util.tree_leaves(x):
        if isinstance(leaf, jax.Array):
            # single-element index, not ravel(): a dynamic-slice costs O(1),
            # where ravel dispatches a full-buffer copy inside the timed
            # window this barrier is meant to close
            if not leaf.is_fully_addressable:
                # multi-process arrays can't be basic-indexed from one host;
                # fetching an element of the local shard is the same barrier
                leaf = leaf.addressable_shards[0].data
            jax.device_get(leaf if leaf.ndim == 0 else leaf[(0,) * leaf.ndim])
    return x


def barrier():
    """Synchronize all pending work (reference: ``bf.barrier``).

    Under SPMD every compiled program is already a global synchronization
    point; this only drains the host dispatch queue.
    """
    (jax.device_put(0) + 0).block_until_ready()
