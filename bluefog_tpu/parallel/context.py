"""Global context: device mesh + virtual topology state.

TPU-native replacement for the reference's init/global-state machinery
(``bluefog/common/basics.py`` + ``operations.cc:1189-1326``).  There is no
background communication thread and no ctypes boundary: ``init`` builds a
``jax.sharding.Mesh`` over the devices (and a 2-D machine x local mesh for
hierarchical ops), and topology state lives in one process-level context whose
schedules are compiled lazily and cached.

Rank semantics under SPMD: a device's rank is its index along the mesh's
``rank`` axis (``ops.my_rank()`` inside shard_map).  Host-side code sees the
*global* picture — per-rank values are arrays with a leading rank axis —
so accessors like ``in_neighbor_ranks`` take the rank as an argument instead
of reading an ambient "my rank" (reference: ``basics.py:200-265``).
"""
from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from typing import Callable, List, Optional

import jax
import numpy as np
import networkx as nx
from jax.sharding import Mesh

from .. import topology as topo_util
from ..schedule import CommSchedule, compile_topology

_lock = threading.Lock()
_context: Optional["BlueFogTpuContext"] = None


@dataclass
class BlueFogTpuContext:
    devices: np.ndarray                       # flat, rank-ordered
    nodes_per_machine: int
    mesh: Mesh                                # 1-D ('rank',)
    mesh_2d: Mesh                             # 2-D ('machine', 'local')
    topology: Optional[nx.DiGraph] = None
    topology_weighted: bool = False
    machine_topology: Optional[nx.DiGraph] = None
    machine_topology_weighted: bool = False
    dynamic_schedules: Optional[List[CommSchedule]] = None
    # process default for round-parallel gossip emission (None = defer to
    # BLUEFOG_ROUND_PARALLEL; per-call concurrent= overrides both)
    round_parallel: Optional[bool] = None
    # process default for the DCN-hop wire codec of hierarchical gossip
    # (None = defer to BLUEFOG_DCN_WIRE; "off" forces full width)
    dcn_wire: Optional[str] = None
    # process default staleness bound for async window gossip (None = defer
    # to BLUEFOG_ASYNC; 0 forces synchronous lockstep)
    async_staleness: Optional[int] = None
    # how the machine grouping was derived ("auto" = from the device mesh /
    # slice_index at init; None = manual nodes_per_machine / set_machine_topology)
    hierarchical: Optional[str] = None
    _sched: Optional[CommSchedule] = None
    _machine_sched: Optional[CommSchedule] = None

    @property
    def size(self) -> int:
        return len(self.devices)

    @property
    def machine_size(self) -> int:
        return self.size // self.nodes_per_machine

    def static_schedule(self) -> CommSchedule:
        if self.topology is None:
            raise RuntimeError("no topology set; call bf.init() / bf.set_topology()")
        if self._sched is None:
            self._sched = compile_topology(self.topology, weighted=self.topology_weighted)
        return self._sched

    def machine_schedule(self) -> CommSchedule:
        if self.machine_topology is None:
            raise RuntimeError("no machine topology set; call bf.set_machine_topology()")
        if self._machine_sched is None:
            self._machine_sched = compile_topology(
                self.machine_topology, weighted=self.machine_topology_weighted)
        return self._machine_sched


# ---------------------------------------------------------------------------
# Process-level program cache (the AOT/compile layer)
# ---------------------------------------------------------------------------
# One compiled program per (op, CommSchedule, mesh, shape, dtype, donation)
# key.  CommSchedule is a frozen, hashable dataclass, so schedule identity is
# part of the key and repeated schedule->jaxpr lowering never retraces: the
# second neighbor_allreduce over the same topology/shape reuses the first
# call's traced program, whether dispatched from api.py, a tool, or a fused
# train step.  Keys embed everything they depend on, so the cache never needs
# invalidation for correctness — clearing happens only at shutdown, to drop
# executables pinning device buffers.
_program_cache: dict = {}
_program_stats = {"hits": 0, "misses": 0}


def cached_program(key, build: Callable[[], Callable]) -> Callable:
    """Memoize ``build()`` (a traced/compiled program) under ``key``.

    The build itself runs outside the lock — tracing can take seconds and
    may re-enter this cache (an op built from other cached ops must not
    deadlock).  Two threads racing on one key both build; the first insert
    wins so every caller dispatches the same executable.
    """
    from ..utils import metrics as _metrics
    with _lock:
        fn = _program_cache.get(key)
        if fn is not None:
            _program_stats["hits"] += 1
            hit = True
        else:
            hit = False
    _metrics.note_cache_event(hit, key)
    if hit:
        return fn
    fn = build()
    with _lock:
        _program_stats["misses"] += 1
        return _program_cache.setdefault(key, fn)


def cached_lowering(key, fn: Callable, *args):
    """AOT variant: lower + compile ``fn`` for ``args`` once per ``key`` and
    return the executable.  Use when the call site owns concrete arguments
    and wants XLA's compiled program (cost analysis, HLO text) rather than
    a jit wrapper: a re-run within the process never pays tracing twice."""
    def build():
        return fn.lower(*args).compile()
    return cached_program(key, build)


def program_cache_stats() -> dict:
    """Copy of the cache counters ({"hits", "misses"})."""
    with _lock:
        return dict(_program_stats)


def program_cache_size() -> int:
    with _lock:
        return len(_program_cache)


def clear_program_cache() -> None:
    """Drop every cached program (counters survive: they describe the
    process, not the current cache generation)."""
    with _lock:
        _program_cache.clear()


def init(
    topology_fn: Optional[Callable[[], nx.DiGraph]] = None,
    is_weighted: bool = False,
    *,
    devices: Optional[List] = None,
    platform: Optional[str] = None,
    nodes_per_machine: Optional[int] = None,
    hierarchical: Optional[str] = None,
) -> BlueFogTpuContext:
    """Initialize the context (reference: ``bf.init``, ``basics.py:49-70``).

    Args:
      topology_fn: zero-arg callable returning the virtual topology; defaults
        to ``ExponentialGraph(size)`` like the reference.
      is_weighted: use the topology's mixing weights for neighbor averaging
        instead of the uniform ``1/(in_degree+1)`` default.
      devices: explicit device list (rank order).  Default: ``jax.devices()``.
      platform: select a backend explicitly (e.g. ``"cpu"`` for the 8-device
        virtual-mesh test fixture).
      nodes_per_machine: devices per "machine" for hierarchical ops.  Default:
        ``jax.local_device_count()`` when multi-process, else the device count
        (single host = one machine).  The reference's
        ``BLUEFOG_NODES_PER_MACHINE`` virtual-machine split maps here.
      hierarchical: ``"auto"`` derives the two-level structure from the real
        device mesh instead of requiring manual ``set_machine_topology``:
        devices are grouped by TPU ``slice_index`` when present (reordered so
        each slice's chips are contiguous on the rank axis, making the
        ``machine`` mesh axis coincide with the DCN boundary), else by
        process locality, else by ``nodes_per_machine``; the machine-level
        topology is then auto-installed as weighted ``ExponentialTwoGraph``
        over the slice leaders.  ``None`` defers to the ``BLUEFOG_HIERARCHICAL``
        env flag; ``"off"`` disables.  See
        ``docs/PERFORMANCE.md#pod-scale-hierarchical-gossip``.
    """
    global _context, _active_compose
    _active_compose = None    # a new context invalidates any prior carving
    from ..utils.config import (
        add_recommended_tpu_flags, enable_compilation_cache, env_flag,
        env_int, logger, looks_like_tpu_environment, setup_logging)
    from ..utils.timeline import maybe_start_from_env
    from ..utils import metrics as _metrics
    setup_logging()
    # a fresh init starts a fresh warmup: the retrace sentinel must not
    # carry a previous training run's steady-state declaration
    _metrics.mark_steady_state(False)
    if devices is None:
        from jax._src import xla_bridge as _xb
        from ..run.launcher import maybe_initialize_distributed
        fresh = not _xb.backends_are_initialized()
        if fresh and platform in (None, "tpu") and looks_like_tpu_environment():
            # libtpu parses LIBTPU_INIT_ARGS once, when the backend comes up
            add_recommended_tpu_flags()
        if platform is not None:
            # An explicit platform also *restricts* backend init:
            # jax.devices(platform) alone would still initialize every
            # backend JAX_PLATFORMS lists, claiming a chip the caller asked
            # to stay off.
            if fresh:
                jax.config.update("jax_platforms", platform)
                # An explicit platform still joins the process group when
                # launched by bfrun-tpu: pin the backend FIRST, then
                # bootstrap — otherwise every worker reports process_index 0
                # and multi-process sessions deadlock.  Only the explicit
                # BLUEFOG_COORDINATOR bootstrap, NOT pod auto-detect:
                # bf.init(platform="cpu") on one pod host is a local debug
                # session, and a no-arg jax.distributed.initialize() there
                # would block waiting for the other hosts.
                if os.environ.get("BLUEFOG_COORDINATOR"):
                    maybe_initialize_distributed()
            devices = jax.devices(platform)
        else:
            # multi-host bootstrap when launched by bfrun-tpu or on a TPU pod
            maybe_initialize_distributed()
            devices = jax.devices()
        if jax.process_count() == 1:
            # multi-process keeps jax's process-grouped order: the 2-D
            # (machine, local) mesh and machine_rank/local_rank require each
            # host's chip block to stay contiguous, which a 1-D torus snake
            # does not guarantee across hosts
            devices = _torus_order(devices)
    devs = np.asarray(devices, dtype=object)
    n = len(devs)
    logger.info("bf.init: platform %s, device_kind %s, %d device(s)",
                devs[0].platform, devs[0].device_kind, n)
    if devs[0].platform == "tpu":
        logger.info("bf.init: persistent compilation cache at %s",
                    enable_compilation_cache())
    if hierarchical is None:
        hierarchical = "auto" if env_flag("BLUEFOG_HIERARCHICAL", False) else None
    elif hierarchical in ("off", False):
        hierarchical = None
    elif hierarchical is True:
        hierarchical = "auto"
    if hierarchical not in (None, "auto"):
        raise ValueError(
            f"hierarchical must be 'auto' or 'off', got {hierarchical!r}")
    if nodes_per_machine is None:
        nodes_per_machine = env_int("BLUEFOG_NODES_PER_MACHINE")
    if hierarchical == "auto":
        ordered, nodes_per_machine = _auto_hierarchy(list(devs), nodes_per_machine)
        devs = np.asarray(ordered, dtype=object)
    if nodes_per_machine is None:
        nodes_per_machine = jax.local_device_count() if jax.process_count() > 1 else n
    maybe_start_from_env()
    _metrics.maybe_start_from_env()
    from ..utils import chaos as _chaos
    _chaos.maybe_install_from_env()
    from ..utils import flight as _flight
    _flight.maybe_enable_from_env()
    from ..utils import tracing as _tracing
    _tracing.maybe_enable_from_env()
    from ..utils import fleetview as _fleetview
    _fleetview.maybe_arm_from_env(n)
    _flight.record("lifecycle", name="init", devices=n)
    if n % nodes_per_machine != 0:
        raise ValueError(
            f"device count {n} not divisible by nodes_per_machine {nodes_per_machine}")

    mesh = Mesh(devs, ("rank",))
    mesh_2d = Mesh(devs.reshape(n // nodes_per_machine, nodes_per_machine),
                   ("machine", "local"))
    ctx = BlueFogTpuContext(
        devices=devs, nodes_per_machine=nodes_per_machine,
        mesh=mesh, mesh_2d=mesh_2d)

    topo = topology_fn() if topology_fn is not None else topo_util.ExponentialGraph(n)
    ctx.topology = _check_topology(topo, n)
    ctx.topology_weighted = is_weighted

    ctx.hierarchical = hierarchical
    if hierarchical == "auto" and ctx.machine_size > 1:
        # the two-level family's default cross-slice graph: log2(M) leader
        # out-edges, weighted — cross-slice bytes/step scale with this
        # degree, not the rank count (the pod-scale AOT tests pin it)
        ctx.machine_topology = topo_util.ExponentialTwoGraph(ctx.machine_size)
        ctx.machine_topology_weighted = True

    with _lock:
        _context = ctx
    return ctx


def reinit(world_size: int, *,
           topology_fn: Optional[Callable[[], nx.DiGraph]] = None,
           is_weighted: bool = False) -> BlueFogTpuContext:
    """Tear down and re-form the mesh at a new world size (mesh regrowth).

    The checkpoint-free re-bootstrap primitive behind
    :func:`bluefog_tpu.resilience.regrow_world`: the frozen-at-``init``
    SPMD world is replaced by a new one at ``world_size`` ranks.  Surviving
    ranks keep their devices (rank ``r < old_size`` stays on the device it
    already owned, so host-memory state carry re-shards onto the same
    physical buffers); joiners take unused devices from the backend pool.
    The compiled-program cache is dropped (every cached executable names
    the old mesh), the compose carving is rebuilt at the new data-parallel
    width when one is active, the resilience membership registry is
    re-baselined, and the steady-state flag resets — the recompiles that
    follow are the intended cost of a world change, not a retrace bug.

    In a multi-process job the ``jax.distributed`` client is torn down and
    re-formed at the new process count (the supervisor has already spawned
    the joiner processes); the single-process SPMD simulation skips that
    step — growth there means carving more of the virtual device pool.

    Returns the new context.  Raises if no context is initialized or the
    backend cannot supply ``world_size`` devices.
    """
    global _context, _active_compose
    ctx = get_context()
    world_size = int(world_size)
    if world_size < 1:
        raise ValueError(f"world_size must be >= 1, got {world_size}")
    old = list(ctx.devices)
    if world_size <= len(old):
        devs_list = old[:world_size]
    else:
        platform = getattr(old[0], "platform", None)
        pool = jax.devices(platform) if platform else jax.devices()
        have = {id(d) for d in old}
        spare = [d for d in pool if id(d) not in have]
        need = world_size - len(old)
        if len(spare) < need:
            raise ValueError(
                f"cannot regrow to {world_size} ranks: backend has only "
                f"{len(old) + len(spare)} device(s) "
                f"({len(old)} in use + {len(spare)} spare)")
        devs_list = old + spare[:need]
    # validate EVERY precondition before anything is torn down: raising
    # past this point would leave a half-torn world (distributed client
    # re-formed, new mesh installed, carving gone)
    old_compose = _active_compose
    if old_compose is not None and world_size % old_compose.slice_size:
        raise ValueError(
            f"world size {world_size} is not a multiple of the active "
            f"carving's slice size {old_compose.slice_size} "
            f"(pp={old_compose.pp} tp={old_compose.tp} "
            f"sp={old_compose.sp} ep={old_compose.ep})")
    _rebootstrap_distributed(world_size)

    from ..utils import metrics as _metrics
    from ..utils import flight as _flight
    from . import exec_cache as _exec
    # every cached executable names the old mesh — but a later regrow back
    # to this shape should not pay recompilation: park, then clear
    _exec.stash(ctx, old_compose)
    clear_program_cache()
    _metrics.mark_steady_state(False)

    devs = np.asarray(devs_list, dtype=object)
    npm = ctx.nodes_per_machine
    if npm == ctx.size or world_size % npm != 0:
        npm = world_size        # single machine (or no longer divisible)
    mesh = Mesh(devs, ("rank",))
    mesh_2d = Mesh(devs.reshape(world_size // npm, npm),
                   ("machine", "local"))
    topo = (topology_fn() if topology_fn is not None
            else topo_util.ExponentialGraph(world_size))
    new_ctx = BlueFogTpuContext(
        devices=devs, nodes_per_machine=npm, mesh=mesh, mesh_2d=mesh_2d,
        topology=_check_topology(topo, world_size),
        topology_weighted=is_weighted,
        round_parallel=ctx.round_parallel, dcn_wire=ctx.dcn_wire,
        async_staleness=ctx.async_staleness)

    with _lock:
        _context = new_ctx
        _active_compose = None
    if old_compose is not None:
        from . import compose as _compose
        _compose.compose_parallelism(
            world_size // old_compose.slice_size, old_compose.pp,
            old_compose.tp, old_compose.sp, old_compose.ep,
            num_experts=old_compose.num_experts,
            capacity_factor=old_compose.capacity_factor,
            devices=devs_list, wire=old_compose.wire)

    # warm re-entry: a previously-seen world shape restores its parked
    # programs — the regrow recompiles nothing (preempt_bench pins this)
    _exec.restore(new_ctx, _active_compose)

    # the old world's membership registry (and its pristine baseline) is
    # meaningless against the new mesh — re-baseline from scratch
    from .. import resilience as _rz
    _rz.reset()
    _flight.record("lifecycle", name="reinit", devices=world_size,
                   old_devices=len(old))
    return new_ctx


def _rebootstrap_distributed(world_size: int) -> bool:
    """Tear down and re-form the ``jax.distributed`` client for a regrown
    world.  Only in a real multi-process job (``BLUEFOG_COORDINATOR`` set
    AND more than one process): the single-process simulation has no
    client to re-form and must not dial a coordinator."""
    if not os.environ.get("BLUEFOG_COORDINATOR"):
        return False
    if int(os.environ.get("BLUEFOG_NUM_PROCESSES", "1")) <= 1:
        return False
    try:
        jax.distributed.shutdown()
    except Exception:            # pragma: no cover - never formed / torn
        pass
    os.environ["BLUEFOG_NUM_PROCESSES"] = str(int(world_size))
    from ..run.launcher import maybe_initialize_distributed
    return maybe_initialize_distributed()


def _install(ctx: BlueFogTpuContext, compose=None) -> None:
    """Reinstall a previously captured context (the regrow rollback path:
    a failed :func:`reinit` must leave the process on the old world)."""
    global _context, _active_compose
    from . import exec_cache as _exec
    if _context is not None:
        # park the aborted world's programs too: its shape may come back
        _exec.stash(_context, _active_compose)
    clear_program_cache()
    with _lock:
        _context = ctx
        _active_compose = compose
    _exec.restore(ctx, compose)
    # in a real multi-process job _rebootstrap_distributed mutated this
    # to the aborted target; a later launch/reinit must see the world
    # actually installed (the single-process sim never mutates it)
    if (os.environ.get("BLUEFOG_COORDINATOR")
            and int(os.environ.get("BLUEFOG_NUM_PROCESSES", "1")) > 1):
        os.environ["BLUEFOG_NUM_PROCESSES"] = str(ctx.size)
    from ..utils import metrics as _metrics
    _metrics.mark_steady_state(False)


def _auto_hierarchy(devices: List, nodes_per_machine: Optional[int]):
    """Derive the (ordered devices, nodes_per_machine) two-level grouping.

    Preference order: TPU ``slice_index`` (the real ICI/DCN boundary on a
    multi-slice pod — devices are stably reordered so each slice's chips are
    contiguous on the rank axis, which is what makes the 2-D mesh's
    ``machine`` axis the DCN axis), then process locality (one machine per
    host), then an explicit ``nodes_per_machine``.  With no detectable
    structure every rank is its own machine: hierarchical gossip degenerates
    to flat gossip instead of a silent wrong grouping.
    """
    slice_ids = [getattr(d, "slice_index", None) for d in devices]
    distinct = {s for s in slice_ids if s is not None}
    if len(distinct) > 1 and all(s is not None for s in slice_ids):
        order = sorted(range(len(devices)), key=lambda i: (slice_ids[i], i))
        ordered = [devices[i] for i in order]
        counts = {s: slice_ids.count(s) for s in distinct}
        sizes = set(counts.values())
        if len(sizes) != 1:
            raise ValueError(
                f"hierarchical='auto' needs equal-sized slices, got {counts}")
        derived = sizes.pop()
        if nodes_per_machine is not None and nodes_per_machine != derived:
            raise ValueError(
                f"nodes_per_machine={nodes_per_machine} contradicts the "
                f"device mesh ({derived} chips per slice)")
        return ordered, derived
    if nodes_per_machine is not None:
        return devices, nodes_per_machine
    if jax.process_count() > 1:
        return devices, jax.local_device_count()
    return devices, 1


def _torus_order(devices):
    """Order the rank axis along the physical ICI torus so ring/neighbor
    ppermutes ride single-hop links (a raw ``jax.devices()`` enumeration can
    zig-zag across the torus).  Applied only to auto-discovered devices —
    explicit lists are the caller's ordering.  Only TPUs have a torus; there
    a failure to read it propagates rather than scrambling silently."""
    if len(devices) <= 1 or devices[0].platform != "tpu":
        return devices
    from jax.experimental import mesh_utils
    return list(
        mesh_utils.create_device_mesh((len(devices),), devices=devices).flat)


def _check_topology(topo: nx.DiGraph, size: int) -> nx.DiGraph:
    if topo.number_of_nodes() != size:
        raise ValueError(
            f"topology has {topo.number_of_nodes()} nodes but the mesh has {size} devices")
    return topo


def get_context() -> BlueFogTpuContext:
    if _context is None:
        raise RuntimeError("bluefog_tpu is not initialized; call bf.init() first")
    return _context


def shutdown() -> None:
    """Drop the context (reference: ``bf.shutdown``) — flushing any active
    timeline first, as the reference's shutdown drains its writer thread
    (``operations.cc:464-473``)."""
    global _context, _active_compose
    _active_compose = None
    from ..utils.timeline import stop_timeline
    from ..utils import metrics as _metrics
    from ..utils import chaos as _chaos
    from ..utils import flight as _flight
    _flight.record("lifecycle", name="shutdown")
    stop_timeline()
    _metrics.stop_metrics()   # final JSONL sample + close
    _metrics.mark_steady_state(False)
    _chaos.uninstall()
    _chaos._corrupt_programs.clear()  # jitted corruptors pin device buffers
    clear_program_cache()     # executables pin device buffers past shutdown
    from . import exec_cache as _exec
    _exec.clear()             # ... and so does the warm pool
    with _lock:
        _context = None


def is_initialized() -> bool:
    return _context is not None


def size() -> int:
    return get_context().size


def local_size() -> int:
    return get_context().nodes_per_machine


def machine_size() -> int:
    return get_context().machine_size


def devices() -> np.ndarray:
    return get_context().devices


# The active composed-parallelism carving (a parallel.compose.Mesh3D), set
# by compose_parallelism() so tools (autotune, flight postmortems) can read
# the axis split without threading it through every call.  Cleared on
# init/shutdown: a carving is only meaningful against the mesh it divided.
_active_compose = None


def set_compose(m) -> None:
    global _active_compose
    _active_compose = m


def get_compose():
    return _active_compose


def mesh() -> Mesh:
    return get_context().mesh


def mesh_2d() -> Mesh:
    return get_context().mesh_2d


def load_topology() -> nx.DiGraph:
    return get_context().topology


def is_topology_weighted() -> bool:
    return get_context().topology_weighted


def set_topology(topology: Optional[nx.DiGraph] = None,
                 is_weighted: bool = False) -> bool:
    """Replace the virtual topology (reference: ``basics.py:311-419``).

    Unlike the reference there is no open-window restriction: window state is
    explicit and schedules are compiled per topology, so changing topology
    simply invalidates the cached schedule.
    """
    ctx = get_context()
    if topology is None:
        topology = topo_util.ExponentialGraph(ctx.size)
    ctx.topology = _check_topology(topology, ctx.size)
    ctx.topology_weighted = is_weighted
    ctx._sched = None
    ctx.dynamic_schedules = None
    return True


def load_machine_topology() -> Optional[nx.DiGraph]:
    return get_context().machine_topology


def is_machine_topology_weighted() -> bool:
    return get_context().machine_topology_weighted


def set_machine_topology(topology: nx.DiGraph, is_weighted: bool = False) -> bool:
    """Set the machine-level topology for hierarchical ops (reference:
    ``basics.py:267-309``)."""
    ctx = get_context()
    ctx.machine_topology = _check_topology(topology, ctx.machine_size)
    ctx.machine_topology_weighted = is_weighted
    ctx._machine_sched = None
    return True


def machine_rank(rank: int) -> int:
    """Machine id of ``rank`` (reference: ``bf.machine_rank()`` — ambient
    there; takes the rank here since SPMD host code sees all ranks)."""
    return int(rank) // get_context().nodes_per_machine


def local_rank(rank: int) -> int:
    """Rank within its machine (reference: ``bf.local_rank()``)."""
    return int(rank) % get_context().nodes_per_machine


def suspend() -> None:
    """No-op (reference: ``bf.suspend``, ``basics.py:548-568`` — parks the
    MPI background thread for Jupyter cell boundaries; there is no
    background thread here)."""


def resume() -> None:
    """No-op counterpart of :func:`suspend`."""


def in_neighbor_ranks(rank: int) -> List[int]:
    """Sorted in-neighbors of ``rank`` in the current topology."""
    return topo_util.GetInNeighbors(get_context().topology, rank)


def out_neighbor_ranks(rank: int) -> List[int]:
    return topo_util.GetOutNeighbors(get_context().topology, rank)


def in_neighbor_machine_ranks(machine_rank: int) -> List[int]:
    topo = get_context().machine_topology
    if topo is None:
        raise RuntimeError("no machine topology set")
    return topo_util.GetInNeighbors(topo, machine_rank)


def out_neighbor_machine_ranks(machine_rank: int) -> List[int]:
    topo = get_context().machine_topology
    if topo is None:
        raise RuntimeError("no machine topology set")
    return topo_util.GetOutNeighbors(topo, machine_rank)


def set_dynamic_topology(generator_factory, num_steps: Optional[int] = None,
                         uniform: bool = True) -> List[CommSchedule]:
    """Install an iteration-varying topology from a one-peer generator family.

    The reference's pattern is per-iteration mutation of the optimizer's
    ``dst_weights/src_weights/self_weight`` from a generator
    (``examples/pytorch_benchmark.py:182-208``); here the generator's period
    compiles once into a schedule list stored on the context —
    ``neighbor_allreduce(x, step=t)`` and the ``communication_type``
    optimizer factories then pick it up automatically.

    ``generator_factory(rank)`` returns the reference-style iterator yielding
    ``([send_ranks], [recv_ranks])`` per iteration.  Returns the schedules.
    """
    from ..schedule import compile_dynamic_schedules
    ctx = get_context()
    scheds = compile_dynamic_schedules(
        generator_factory, ctx.size, num_steps, uniform)
    ctx.dynamic_schedules = scheds
    return scheds


def clear_dynamic_topology() -> None:
    get_context().dynamic_schedules = None


def dynamic_schedules() -> Optional[List[CommSchedule]]:
    return get_context().dynamic_schedules


def set_round_parallel(value: Optional[bool]) -> None:
    """Set the process default for round-parallel gossip emission.

    ``True`` makes ``neighbor_allreduce`` issue its edge-colored rounds as
    one concurrent permute group, ``False`` forces the sequential chain,
    ``None`` defers to the ``BLUEFOG_ROUND_PARALLEL`` env flag.  A per-call
    ``concurrent=`` argument always wins.  Flipping the knob changes the
    traced program, so do it before warmup (the retrace sentinel counts a
    steady-state flip as the recompile it is).
    """
    get_context().round_parallel = value


def round_parallel() -> Optional[bool]:
    """The context's round-parallel default (see :func:`set_round_parallel`)."""
    return get_context().round_parallel


def set_dcn_wire(value: Optional[str]) -> None:
    """Set the process default wire codec for the DCN hop of hierarchical
    gossip (``"bf16"``/``"int8"``/``"fp8"``, optionally ``"@B"``-blocked).

    Applies only to the machine-axis permutes of
    ``hierarchical_neighbor_allreduce`` / ``hierarchical_communicator`` —
    the cross-slice edges — never the intra-slice reduce, which stays full
    precision.  ``"off"`` forces full-width DCN bytes, ``None`` defers to
    the ``BLUEFOG_DCN_WIRE`` env var.  A per-call ``wire=`` always wins.
    Like ``set_round_parallel``, flip it before warmup: it is part of the
    traced program (and of the program-cache key).
    """
    if value is not None and value != "off":
        from ..ops.collectives import _check_wire
        _check_wire(value)
    get_context().dcn_wire = value


def dcn_wire() -> Optional[str]:
    """The context's DCN-wire default (see :func:`set_dcn_wire`)."""
    return get_context().dcn_wire


#: Default staleness bound when neither the knob nor BLUEFOG_ASYNC is set:
#: deep enough to absorb a ~5x pace spread on the fleet's slowest rank
#: before the first forced sync-up, shallow enough that a stuck rank is
#: dragged back within a handful of ticks.
_DEFAULT_ASYNC_BOUND = 4


def set_async_gossip(bound: Optional[int]) -> None:
    """Set the process default staleness bound K for
    :func:`bluefog_tpu.optimizers.async_window_gossip`.

    ``K=0`` forces synchronous lockstep (every tick active — the oracle
    mode); ``K>0`` lets ranks free-run until some neighbor contribution is
    more than K ticks stale, at which point the whole fleet syncs up on the
    next tick.  ``None`` defers to the ``BLUEFOG_ASYNC`` env var (and its
    default).  A per-strategy ``staleness_bound=`` argument always wins.
    Like ``set_round_parallel``, the bound is resolved at trace time and is
    part of the compiled program: flip it before warmup, or the retrace
    sentinel will count the recompile it causes.
    """
    if bound is not None and int(bound) < 0:
        raise ValueError(f"staleness bound must be >= 0, got {bound}")
    get_context().async_staleness = None if bound is None else int(bound)


def async_gossip_bound() -> int:
    """The resolved async staleness bound: context knob, else the
    ``BLUEFOG_ASYNC`` env var, else ``_DEFAULT_ASYNC_BOUND``
    (see :func:`set_async_gossip`)."""
    ctx = get_context()
    if ctx.async_staleness is not None:
        return ctx.async_staleness
    env = os.environ.get("BLUEFOG_ASYNC", "").strip()
    if env:
        bound = int(env)
        if bound < 0:
            raise ValueError(
                f"BLUEFOG_ASYNC must be >= 0, got {env!r}")
        return bound
    return _DEFAULT_ASYNC_BOUND


def apply_plan(plan) -> bool:
    """Apply an autotune plan's context knobs to the live process.

    Accepts a :class:`bluefog_tpu.autotune.Plan` or its raw ``doc`` dict.
    Sets the virtual topology from the plan's JSON spec (so a plan applied
    on a different host reconstructs the identical graph and schedule key)
    and the round-parallel emission default; per-strategy knobs (wire,
    fused-k, delayed) live in the strategy/train-step the plan builds, not
    in context state.  Like every topology/emission flip, apply before
    warmup — the knobs are part of the traced program.
    """
    doc = plan.doc if hasattr(plan, "doc") else plan
    cfg = doc["config"]
    ctx = get_context()
    if cfg.get("topology") is not None:
        topo = topo_util.topology_from_spec(cfg["topology"])
        if topo.number_of_nodes() != ctx.size:
            raise ValueError(
                f"plan was tuned for {topo.number_of_nodes()} ranks but "
                f"this context has {ctx.size}; re-tune on this mesh")
        set_topology(topo, is_weighted=True)
    set_round_parallel(cfg.get("concurrent"))
    return True


def static_schedule() -> CommSchedule:
    return get_context().static_schedule()


def machine_schedule() -> CommSchedule:
    return get_context().machine_schedule()
