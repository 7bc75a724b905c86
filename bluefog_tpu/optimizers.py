"""Decentralized optimizer strategies (functional, optax-composable).

TPU-native re-design of the reference's optimizer wrappers
(``bluefog/torch/optimizers.py``, SURVEY.md §2.4).  The reference hooks
forward/backward passes to overlap nonblocking communication with compute;
under XLA that overlap is the compiler's job (async collectives +
latency-hiding scheduling), so each strategy is a *pure function* from
``(grads, state, params)`` to ``(new_params, new_state)`` with the
communication placed according to the algorithm:

=======================================  =====================================
reference wrapper                        strategy here
=======================================  =====================================
DistributedGradientAllreduceOptimizer    ``gradient_allreduce``:
                                         x_{t+1} = A(x_t, pmean(g_t))
DistributedAdaptWithCombineOptimizer     ``adapt_with_combine`` (CTA):
(+ NeighborAllreduce / Hierarchical      x_{t+1} = A(Comb(x_t), g_t)
 aliases)
DistributedAdaptThenCombineOptimizer     ``adapt_then_combine`` (ATC):
                                         x_{t+1} = Comb(A(x_t, g_t))
DistributedWinPutOptimizer               ``win_put``: mailbox gossip of
                                         params, combine, then adapt
DistributedPullGetOptimizer              ``pull_get``: mailbox fetch of
                                         neighbor params, combine, adapt
DistributedPushSumOptimizer              ``push_sum``: biased gossip with
                                         associated-P weight correction
=======================================  =====================================

``A`` is any ``optax.GradientTransformation``; ``Comb`` is a communicator
built by :func:`neighbor_communicator` (static, dynamic via ``lax.switch``,
hierarchical, global, or none).  All updates must run inside ``shard_map``
over the context mesh — :func:`make_train_step` builds that program.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from . import fusion, ops
from .ops import windows as wops
from .parallel import context as _mesh
from .schedule import CommSchedule
from .utils import chaos as _chaos
from .utils import flight as _flight
from .utils import metrics as _metrics
from .utils import tracing as _tracing
from .utils.timeline import named_span

Axis = str
Communicator = Callable[[Any, jax.Array], Any]   # (params_pytree, step) -> pytree


# ---------------------------------------------------------------------------
# Communicators
# ---------------------------------------------------------------------------

def neighbor_communicator(
    schedule: Optional[CommSchedule] = None,
    schedules: Optional[Sequence[CommSchedule]] = None,
    *,
    axis: Axis = "rank",
    fuse: bool = True,
    wire: Optional[str] = None,
    concurrent: Optional[bool] = None,
) -> Communicator:
    """Neighbor averaging of a params pytree; dynamic when ``schedules``.

    Dynamic topologies compile to a ``lax.switch`` over the period's branches
    (the reference instead re-negotiates per-iteration send/recv lists,
    ``optimizers.py`` + ``examples/pytorch_benchmark.py:182-208``).
    ``fuse`` gossips one buffer per dtype instead of one permute chain per
    leaf (reference fusion buffers, SURVEY.md §2.4): the buffer is in the
    leaves' own tile order (:func:`bluefog_tpu.fusion.tile_tree`), it is
    permuted once a round, and each leaf's span of it combines with the
    same span of what arrived.  ``wire`` compresses the gossiped bytes on
    the wire (``"bf16"``/``"int8"``/``"fp8"``, see
    :func:`bluefog_tpu.ops.neighbor_allreduce`); with ``fuse`` the int8/fp8
    riding scale is per buffer, amortizing the side channel across the
    whole model.  ``concurrent`` forwards to
    :func:`bluefog_tpu.ops.neighbor_allreduce` (round-parallel emission of
    the edge-colored permute rounds; None = context/env default).
    """
    if (schedule is None) == (schedules is None):
        raise ValueError("pass exactly one of schedule / schedules")
    if schedule is not None and schedule.num_rounds == 0:
        fuse = False     # degenerate topology (e.g. 1 chip): the op is
                         # elementwise, fusion's concat/split is pure cost

    def wire_of(dtype):
        # non-real-float leaves (int counters, complex) always travel
        # uncompressed — quantizing them is meaningless or lossy
        return wire if jnp.issubdtype(dtype, jnp.floating) else None

    def by_step(fn, step, x):
        """``fn(sched, x)`` under the schedule of this step."""
        if schedule is not None:
            return fn(schedule, x)
        return lax.switch(step % len(schedules),
                          [partial(fn, s) for s in schedules], x)

    def leaf(sched, x):
        return ops.neighbor_allreduce(x, sched, axis=axis,
                                      wire=wire_of(x.dtype),
                                      concurrent=concurrent)

    def mix(sched, buf, spans):
        """One dtype's leaves mixed in tile order: the buffer is exchanged
        once, and each leaf's span of it combines with the same span of
        what arrived."""
        received = ops.neighbor_exchange(buf, sched, axis=axis,
                                         wire=wire_of(buf.dtype),
                                         concurrent=concurrent)
        return [ops.neighbor_combine(
            buf[a:b], sched, [(recv[a:b], w) for recv, w in received],
            axis=axis) for a, b in spans]

    def comm(params, step):
        with named_span("COMMUNICATE"):
            if not fuse:
                return jax.tree.map(partial(by_step, leaf, step), params)
            tiled = fusion.tile_tree(params)
            # The combine reads the packed buffer (the permutes hold it
            # anyway), not the leaf it was packed from: behind the barrier
            # XLA cannot forward the span to the leaf, so a step that
            # donates its state may write the new parameters over the old
            # before the permutes are done.  Without it the mixed leaves
            # and the new parameters each want the other's input buffer,
            # and the step pays a copy of every mixed leaf.
            return tiled.untile([
                by_step(partial(mix, spans=spans), step,
                        lax.optimization_barrier(buf))
                for buf, spans in zip(tiled.buffers, tiled.spans)])

    return comm


def hierarchical_communicator(
    machine_schedule: Optional[CommSchedule] = None,
    machine_schedules: Optional[Sequence[CommSchedule]] = None,
    *,
    machine_axis: Axis = "machine",
    local_axis: Axis = "local",
    fuse: bool = True,
    wire: Optional[str] = None,
    concurrent: Optional[bool] = None,
) -> Communicator:
    """Machine-level neighbor averaging on the 2-D mesh (reference:
    ``DistributedHierarchicalNeighborAllreduceOptimizer``).

    ``wire`` compresses the machine-level gossip — exactly the edges that
    ride DCN on a multi-slice deployment, where compression pays most; the
    intra-machine pmean (ICI) stays full precision.  ``None`` resolves to
    the process DCN-wire default (``bf.set_dcn_wire`` / ``BLUEFOG_DCN_WIRE``)
    once, here at factory time — the traced program is pinned to the knob
    value the communicator was built under, so a later knob flip cannot
    silently change an already-compiled step (retrace sentinel stays 0).
    ``"off"`` forces full width.  ``concurrent`` round-parallelizes the
    machine rounds (forwarded to :func:`bluefog_tpu.ops.neighbor_allreduce`;
    None = context/env default).
    """
    if (machine_schedule is None) == (machine_schedules is None):
        raise ValueError("pass exactly one of machine_schedule / machine_schedules")
    if wire is None:
        wire = ops.collectives._default_dcn_wire()
    elif wire == "off":
        wire = None

    def comm(params, step):
        def leaf(x):
            w = wire if jnp.issubdtype(x.dtype, jnp.floating) else None
            xm = lax.pmean(x, local_axis)
            if machine_schedule is not None:
                return ops.neighbor_allreduce(xm, machine_schedule,
                                              axis=machine_axis, wire=w,
                                              concurrent=concurrent)
            branches = [
                partial(ops.neighbor_allreduce, sched=s, axis=machine_axis,
                        wire=w, concurrent=concurrent)
                for s in machine_schedules
            ]
            return lax.switch(step % len(machine_schedules), branches, xm)
        with named_span("COMMUNICATE"):
            if fuse:
                return fusion.fused_leaf_op(leaf)(params)
            return jax.tree.map(leaf, params)

    return comm


def allreduce_communicator(*, axis: Axis = "rank") -> Communicator:
    """Global parameter averaging (reference ``communication_type=allreduce``)."""
    def comm(params, step):
        with named_span("COMMUNICATE"):
            return jax.tree.map(lambda x: lax.pmean(x, axis), params)
    return comm


def empty_communicator() -> Communicator:
    """No communication (reference ``CommunicationType.empty``)."""
    return lambda params, step: params


def _every_k(comm: Communicator, k: int) -> Communicator:
    """Communicate every k-th step (reference: num_steps_per_communication)."""
    if k <= 1:
        return comm
    def wrapped(params, step):
        return lax.cond((step + 1) % k == 0,
                        lambda p: comm(p, step), lambda p: p, params)
    return wrapped


# ---------------------------------------------------------------------------
# Strategy container
# ---------------------------------------------------------------------------

class DecentralizedState(NamedTuple):
    step: jax.Array
    opt_state: Any
    comm_state: Any = None        # window pytrees / push-sum p, if any


class DecentralizedOptimizer(NamedTuple):
    """init(params) -> state;  update(grads, state, params) -> (params, state).

    Unlike a plain ``optax.GradientTransformation``, update returns the *new
    parameters*: gossip averaging is multiplicative in the parameters, not an
    additive update.  ``axes`` names the mesh axes the update must run under
    (``make_train_step`` picks the matching mesh).
    """
    init: Callable[[Any], DecentralizedState]
    update: Callable[[Any, DecentralizedState, Any], Tuple[Any, DecentralizedState]]
    axes: Tuple[str, ...] = ("rank",)
    # True for strategies whose comm_state carries in-flight (one-step-
    # delayed) mixed parameters: the gossip issued at step t is consumed by
    # the adapt of step t+1, so XLA's latency-hiding scheduler can run the
    # permute chain concurrently with the step's matmuls.  ``make_train_step
    # (overlap=True)`` requires it, and ``init_distributed`` seeds the carry
    # from each rank's OWN params instead of the broadcast template.
    pipelined: bool = False


def _apply(opt, grads, opt_state, params):
    # named scopes thread into HLO op metadata, so device traces show the
    # reference's activity names (COMMUNICATE/ADAPT) without user effort
    # (reference auto-annotation: torch/optimizers.py:112-163)
    with named_span("ADAPT"):
        updates, new_opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_opt_state


def _map_windows(fn, windows, *rest):
    """tree.map over per-parameter Window leaves (Windows are pytree nodes,
    so a plain tree.map would descend into them)."""
    return jax.tree.map(
        fn, windows, *rest, is_leaf=lambda t: isinstance(t, wops.Window))


# ---------------------------------------------------------------------------
# Strategies
# ---------------------------------------------------------------------------

def gradient_allreduce(
    opt: optax.GradientTransformation, *, axis: Axis = "rank",
    fuse: bool = True,
) -> DecentralizedOptimizer:
    """Horovod-style synchronous data parallelism (reference:
    ``DistributedGradientAllreduceOptimizer``, ``optimizers.py:166-294``)."""
    def init(params):
        return DecentralizedState(jnp.zeros((), jnp.int32), opt.init(params))

    def update(grads, state, params):
        reduce_ = lambda g: lax.pmean(g, axis)
        with named_span("COMMUNICATE"):
            if fuse:
                grads = fusion.fused_leaf_op(reduce_)(grads)
            else:
                grads = jax.tree.map(reduce_, grads)
        new_params, opt_state = _apply(opt, grads, state.opt_state, params)
        return new_params, DecentralizedState(state.step + 1, opt_state)

    return DecentralizedOptimizer(init, update, (axis,))


def adapt_with_combine(
    opt: optax.GradientTransformation,
    comm: Communicator,
    *,
    num_steps_per_communication: int = 1,
    delayed: bool = False,
    axes: Tuple[str, ...] = ("rank",),
) -> DecentralizedOptimizer:
    """Combine-then-adapt (CTA): x_{t+1} = A(Comb(x_t), g_t).

    Reference: ``DistributedAdaptWithCombineOptimizer``
    (``optimizers.py:311-482``) — the forward hook communicates the *current*
    parameters while the backward pass runs; ``step()`` applies the optimizer
    to the combined parameters using gradients evaluated at x_t.  The gradient
    is intentionally "stale" w.r.t. the combined point; that is the CTA
    algorithm, and XLA overlaps the gossip with the backward compute here for
    the same latency hiding.

    ``delayed=True`` is the pipelined (one-step-stale) variant:

        x_{t+1} = A(Comb(x_{t-1}), g(x_t))

    The gossip issued at step t rides in ``comm_state`` and is consumed by
    step t+1's adapt, so the adapt never waits on the permute chain — inside
    a fused ``lax.scan`` the in-flight mixed params live in the scan carry
    and the permutes of step t overlap the matmuls of step t (AD-PSGD /
    D-PSGD staleness analysis: 1-step-stale mixing preserves the convergence
    rate).  The first step adapts on the rank's own params (carry seeded by
    ``init``/``init_distributed``); staleness begins at step 2.  Pair with
    ``make_train_step(..., overlap=True)``.
    """
    if delayed and num_steps_per_communication != 1:
        raise ValueError(
            "delayed=True requires num_steps_per_communication == 1: the "
            "carried mixed params would be poisoned by raw params on "
            "non-communicating steps")
    comm = _every_k(comm, num_steps_per_communication)

    def init(params):
        carry = jax.tree.map(jnp.copy, params) if delayed else None
        return DecentralizedState(
            jnp.zeros((), jnp.int32), opt.init(params), carry)

    def update(grads, state, params):
        if delayed:
            # issue gossip on the CURRENT params; adapt on LAST step's
            # result — the permutes' inputs never pass through this step's
            # update dot-generals, which is what lets the latency-hiding
            # scheduler bury them under compute.
            mixed_next = comm(params, state.step)
            new_params, opt_state = _apply(
                opt, grads, state.opt_state, state.comm_state)
            return new_params, DecentralizedState(
                state.step + 1, opt_state, mixed_next)
        combined = comm(params, state.step)
        new_params, opt_state = _apply(opt, grads, state.opt_state, combined)
        return new_params, DecentralizedState(state.step + 1, opt_state)

    return DecentralizedOptimizer(init, update, axes, pipelined=delayed)


def adapt_then_combine(
    opt: optax.GradientTransformation,
    comm: Communicator,
    *,
    num_steps_per_communication: int = 1,
    delayed: bool = False,
    axes: Tuple[str, ...] = ("rank",),
) -> DecentralizedOptimizer:
    """Adapt-then-combine (ATC): x_{t+1} = Comb(A(x_t, g_t)).

    Reference: ``DistributedAdaptThenCombineOptimizer``
    (``optimizers.py:484-760``) — backward hooks run the optimizer step inline
    per parameter, then immediately fire communication of the adapted value.
    The permute chain here is data-dependent on the update by construction
    (it mixes the adapted value), which is why the pipelined mode lives on
    CTA: delaying ATC's gossip by one step turns it into delayed CTA anyway
    (the gossip always sees pre-update params), so ``delayed=True`` is
    rejected with a pointer instead of silently changing algorithms.
    """
    if delayed:
        raise ValueError(
            "adapt_then_combine cannot be pipelined: its gossip input IS "
            "the update output. Use adapt_with_combine(..., delayed=True) "
            "for one-step-delayed mixing")
    comm = _every_k(comm, num_steps_per_communication)

    def init(params):
        return DecentralizedState(jnp.zeros((), jnp.int32), opt.init(params))

    def update(grads, state, params):
        adapted, opt_state = _apply(opt, grads, state.opt_state, params)
        new_params = comm(adapted, state.step)
        return new_params, DecentralizedState(state.step + 1, opt_state)

    return DecentralizedOptimizer(init, update, axes)


def _mailbox_optimizer(
    opt: optax.GradientTransformation,
    sched: Optional[CommSchedule],
    leaf_comm,
    *,
    axis: Axis,
    num_steps_per_communication: int,
    fuse: bool,
    carry_windows: bool,
) -> DecentralizedOptimizer:
    """Shared scaffold for window (mailbox) gossip strategies.

    ``leaf_comm(s, window, x) -> new Window`` is the per-buffer gossip round;
    ``carry_windows`` keeps the mailboxes in ``comm_state`` across steps
    (push pipelines read last step's deliveries) or rebuilds them locally
    each communication (pull pipelines overwrite them anyway — carrying
    them would just pin ``max_in_degree`` dead parameter copies in HBM).
    """
    k = num_steps_per_communication

    def _sched():
        return sched if sched is not None else _mesh.static_schedule()

    def _fused(params):
        return fusion.fuse_tree(params).buffers if fuse else params

    def init(params):
        windows = jax.tree.map(
            lambda x: wops.win_create(x, _sched(), zero_init=False),
            _fused(params)) if carry_windows else None
        return DecentralizedState(
            jnp.zeros((), jnp.int32), opt.init(params), windows)

    def update(grads, state, params):
        s = _sched()
        ft = fusion.fuse_tree(params) if fuse else None
        comm_input = ft.buffers if fuse else params

        def communicate(operand):
            values, windows = operand
            with named_span("COMMUNICATE"):
                if carry_windows:
                    new_windows = _map_windows(
                        lambda w, x: leaf_comm(s, w, x, axis), windows, values)
                else:
                    new_windows = jax.tree.map(
                        lambda x: leaf_comm(s, wops.win_create(x, s), x, axis),
                        values)
            combined = _map_windows(lambda w: w.value, new_windows)
            return combined, (new_windows if carry_windows else None)

        if k > 1:
            combined, windows = lax.cond(
                (state.step + 1) % k == 0, communicate,
                lambda o: o, (comm_input, state.comm_state))
        else:
            combined, windows = communicate((comm_input, state.comm_state))
        if fuse:
            ft.buffers = combined
            combined = ft.unfuse()
        new_params, opt_state = _apply(opt, grads, state.opt_state, combined)
        return new_params, DecentralizedState(state.step + 1, opt_state, windows)

    return DecentralizedOptimizer(init, update, (axis,))


def win_put_optimizer(
    opt: optax.GradientTransformation,
    sched: Optional[CommSchedule] = None,
    *,
    axis: Axis = "rank",
    num_steps_per_communication: int = 1,
    fuse: bool = True,
    wire: Optional[str] = None,
) -> DecentralizedOptimizer:
    """Mailbox gossip: put params to out-neighbors, combine mailboxes, adapt.

    Reference: ``DistributedWinPutOptimizer`` (``optimizers.py:850-1005``).
    The window state (one mailbox per in-neighbor) is carried in
    ``comm_state``; staleness is exactly one step — a rank combines the values
    its neighbors put *last* step, matching the reference's nonblocking-put
    pipeline.  ``fuse`` keeps one window per dtype buffer instead of one per
    parameter (the reference creates a window per parameter and pays one RMA
    epoch each; here fusing makes the put one permute chain total).
    """
    def leaf(s, w, x, ax):
        # combine last step's mailboxes with the current value, then put
        # the combined value to out-neighbors (wire= compresses the put
        # bytes; the local combine stays full precision)
        w = wops.Window(value=x, recv=w.recv)
        value, w = wops.win_update(w, s, axis=ax)
        return wops.win_put(w, value, s, axis=ax, wire=wire)

    return _mailbox_optimizer(
        opt, sched, leaf, axis=axis,
        num_steps_per_communication=num_steps_per_communication,
        fuse=fuse, carry_windows=True)


def pull_get_optimizer(
    opt: optax.GradientTransformation,
    sched: Optional[CommSchedule] = None,
    *,
    axis: Axis = "rank",
    num_steps_per_communication: int = 1,
    fuse: bool = True,
    wire: Optional[str] = None,
) -> DecentralizedOptimizer:
    """Pull-based gossip: fetch neighbors' CURRENT params, combine, adapt.

    Reference: ``DistributedPullGetOptimizer`` (``optimizers.py:911-931``).
    The staleness profile is what distinguishes pull from push: a ``win_get``
    fetches the value the neighbor holds *now* (zero steps stale under
    lockstep SPMD), whereas :func:`win_put_optimizer` combines what neighbors
    pushed *last* step (one step stale).  The two trajectories genuinely
    differ (``tests/test_optimizers.py::test_pull_get_differs_from_win_put``);
    pull-with-fresh-values coincides with combine-then-adapt on the current
    params, which the tests pin as its oracle.  The mailboxes are rebuilt
    inside each communication (``carry_windows=False``): a pull overwrites
    them before reading, so persisting them would only waste HBM.
    """
    def leaf(s, w, x, ax):
        # publish the current value, pull in-neighbors' current values
        # into the mailboxes, combine fresh
        w = wops.win_get(w, s, axis=ax, wire=wire)
        _, w = wops.win_update(w, s, axis=ax)
        return w

    return _mailbox_optimizer(
        opt, sched, leaf, axis=axis,
        num_steps_per_communication=num_steps_per_communication,
        fuse=fuse, carry_windows=False)


def push_sum(
    opt: optax.GradientTransformation,
    sched: Optional[CommSchedule] = None,
    *,
    axis: Axis = "rank",
    self_weight: Optional[float] = None,
    dst_weight: Optional[float] = None,
    fuse: bool = True,
) -> DecentralizedOptimizer:
    """Stochastic gradient push (push-sum gossip with weight correction).

    Reference: ``DistributedPushSumOptimizer`` (``optimizers.py:1007-1160``):
    each parameter carries an associated scalar p (starting at 1); every step
    rank r keeps fraction ``1/(outdeg+1)`` of ``(x, p)`` and accumulates the
    same fraction into each out-neighbor's mailbox; the de-biased parameter is
    ``x / p``.  Works on topologies that are only *column*-substochastic
    (directed, unbalanced) where plain gossip would drift.
    """
    def _sched():
        s = sched if sched is not None else _mesh.static_schedule()
        if s.uses_dst_weighting:
            # push_sum scales outgoing mass itself (x * dw below); a schedule
            # with baked-in send scales would make win_accumulate scale again,
            # double-weighting sends and breaking mass conservation.
            raise ValueError(
                "push_sum requires a schedule without dst-weighting "
                "(uses_dst_weighting=False); pass dst_weight= instead")
        return s

    def _vals(params):
        return fusion.fuse_tree(params).buffers if fuse else params

    def init(params):
        s = _sched()
        windows = jax.tree.map(
            lambda x: wops.win_create(x, s, zero_init=True), _vals(params))
        p_windows = jax.tree.map(
            lambda x: wops.win_create(jnp.ones((), x.dtype), s, zero_init=True),
            _vals(params))
        return DecentralizedState(
            jnp.zeros((), jnp.int32), opt.init(params), (windows, p_windows))

    def update(grads, state, params):
        s = _sched()
        idx = lax.axis_index(axis)
        out_deg = jnp.asarray(s.out_degree)[idx]
        sw = (1.0 / (out_deg + 1.0)) if self_weight is None else self_weight
        dw = sw if dst_weight is None else dst_weight
        windows, p_windows = state.comm_state
        recipe = fusion.fuse_tree(params) if fuse else None

        def gossip(w):
            # accumulate dw*x into out-neighbors; then x' = sw*x + mailboxes
            # (x is the window's value channel: the BIASED iterate x = p * z)
            x = w.value
            w = wops.win_accumulate(w, x * jnp.asarray(dw, x.dtype), s, axis=axis)
            w = wops.Window(value=x * jnp.asarray(sw, x.dtype), recv=w.recv)
            _, w = wops.win_update_then_collect(w, s, axis=axis)
            return w                      # w.value is the mixed iterate

        with named_span("COMMUNICATE"):
            windows = _map_windows(gossip, windows)
            mixed = _map_windows(lambda w: w.value, windows)
            p_windows = _map_windows(gossip, p_windows)
            p_new = _map_windows(lambda w: w.value, p_windows)

        # de-bias, adapt the de-biased iterate, re-bias into the gossip
        # channel so the mass-preserving invariant sum_r x_r = sum_r p_r*z_r
        # continues to hold (reference: optimizers.py:1140-1158)
        debiased = jax.tree.map(lambda x, p: x / p, mixed, p_new)
        if fuse:
            recipe.buffers = debiased
            debiased = recipe.unfuse()
        new_params, opt_state = _apply(opt, grads, state.opt_state, debiased)
        adapted = (fusion.fuse_tree(new_params).buffers if fuse
                   else new_params)
        rebiased = jax.tree.map(lambda x, p: x * p, adapted, p_new)
        windows = _map_windows(
            lambda w, x: wops.Window(value=x, recv=w.recv), windows, rebiased)
        return new_params, DecentralizedState(
            state.step + 1, opt_state, (windows, p_windows))

    return DecentralizedOptimizer(init, update, (axis,))


def choco_gossip(
    opt: optax.GradientTransformation,
    sched: Optional[CommSchedule] = None,
    *,
    wire: str = "int8",
    gamma: float = 1.0,
    axis: Axis = "rank",
    axes: Tuple[str, ...] = ("rank",),
) -> DecentralizedOptimizer:
    """CHOCO-SGD: error-compensated *compressed* gossip.

    Plain ``wire=`` compression on CTA (:func:`neighbor_communicator`)
    re-quantizes the full parameters every step, so the error floor is set
    by the quantizer.  CHOCO (Koloskova et al., "Decentralized stochastic
    optimization and gossip algorithms with compressed communication",
     2019) instead gossips compressed *differences* against a shared public
    copy, so quantization error is fed back and decays:

        x_half = A(x_t, g_t)                       (adapt)
        q_i    = Q(x_half_i - xhat_i)              (compress the diff)
        xhat_i += deq(q_i);  s_i += w_ii deq(q_i) + sum_j w_ij deq(q_j)
        x_{t+1} = x_half + gamma (s_i - xhat_i)    (consensus on public copies)

    ``s_i`` tracks ``sum_j w_ij xhat_j`` exactly: every rank applies the
    same deterministic ``deq(Q(.))`` to what it sends and what it updates
    locally, so only the compressed bytes ever cross the wire.  Assumes
    identical initial params across ``axis`` (the ``replicate`` flow);
    ``comm_state`` holds ``(xhat, s)`` in fused per-dtype buffers.
    Reference anchor: goes beyond the reference's fp16 wire
    (``common/half.{h,cc}``) the way its own lineage of gossip papers does.
    """
    import dataclasses as _dc

    from .ops.collectives import _parse_wire, _wire_decode, _wire_encode

    def _scheds():
        s = sched if sched is not None else _mesh.static_schedule()
        if s.uses_dst_weighting and _parse_wire(wire)[0] not in ("int8",
                                                                 "fp8"):
            # the s-tracking invariant s_i == sum_j w_ij xhat_j needs
            # deq(Q(.)) to commute with the sender-side dst scaling; the
            # amax-scaled per-buffer quantizers (int8, fp8) are
            # scale-invariant — scaling the input scales only the riding
            # wire scale, the codes are identical — but a bf16 cast is
            # not: the public copies would silently drift from what
            # crossed the wire.
            raise ValueError(
                "choco_gossip with a dst-weighted schedule "
                "(uses_dst_weighting=True) requires wire='int8' or "
                f"'fp8'; wire={wire!r} does not commute with send scaling")
        # zero-self variant: the permute rounds carry neighbors' diffs only;
        # the self term is applied locally (full knowledge of own q)
        s0 = _dc.replace(s, self_weight=np.zeros_like(s.self_weight), key="")
        return s, s0

    def init(params):
        _scheds()                     # fail fast on wire/schedule mismatch
        bufs = fusion.fuse_tree(jax.tree.map(jnp.copy, params)).buffers
        # identical starts => xhat_j == x_0 for all j and row-stochastic
        # weights make s = sum_j w_ij xhat_j = x_0 as well
        return DecentralizedState(
            jnp.zeros((), jnp.int32), opt.init(params),
            (bufs, [jnp.copy(b) for b in bufs]))

    def update(grads, state, params):
        s_full, s_zero = _scheds()
        idx = lax.axis_index(axis)
        xhat, s = state.comm_state
        half_tree, opt_state = _apply(opt, grads, state.opt_state, params)
        fp = fusion.fuse_tree(half_tree)
        sw = jnp.asarray(s_full.self_weight)

        new_bufs, new_xhat, new_s = [], [], []
        for buf, xh, sb in zip(fp.buffers, xhat, s):
            diff = buf - xh
            qd = _wire_decode(wire, _wire_encode(wire, diff), buf.dtype,
                              shape=diff.shape)
            with named_span("COMMUNICATE"):
                recv = ops.neighbor_allreduce(diff, s_zero, axis=axis,
                                              wire=wire)
            xh2 = xh + qd
            sb2 = sb + qd * sw[idx].astype(buf.dtype) + recv
            new_bufs.append(buf + jnp.asarray(gamma, buf.dtype) * (sb2 - xh2))
            new_xhat.append(xh2)
            new_s.append(sb2)

        fp.buffers = new_bufs
        return fp.unfuse(), DecentralizedState(
            state.step + 1, opt_state, (new_xhat, new_s))

    return DecentralizedOptimizer(init, update, axes)


def push_schedule(topo=None, size: Optional[int] = None) -> CommSchedule:
    """Column-stochastic push schedule: sender j keeps and sends
    ``1/(outdeg_j + 1)`` of its mass on every out-edge.  The receive weight
    of edge (j -> i) therefore depends on the *sender's* out-degree — the
    weight family push-sum/push-DIGing need on directed, unbalanced graphs
    (reference usage: ``examples/pytorch_optimization.py:371-433``).
    """
    from . import topology as _topo
    if topo is None:
        topo = _mesh.load_topology()
    n = size if size is not None else topo.number_of_nodes()
    keep = [1.0 / (len(_topo.GetOutNeighbors(topo, r)) + 1.0)
            for r in range(n)]
    src = [{s: keep[s] for s in _topo.GetInNeighbors(topo, r)}
           for r in range(n)]
    from .schedule import compile_from_weights
    return compile_from_weights(n, keep, src)


class AsyncGossipState(NamedTuple):
    """Carry for :func:`async_window_gossip` (rides the fused-scan carry).

    ``recv`` mirrors the params' fused buffers with one ``[K, ...]`` mailbox
    block each; ``p``/``p_recv`` are the push-sum mass lane (a single scalar
    for the whole model — every buffer gossips with the same activity
    pattern, so one mass suffices); ``stamps`` are the per-slot step stamps
    the bounded-staleness gate reads; ``local_steps`` counts the ticks this
    rank actually worked; ``force`` is the fleet-wide sync-up flag for the
    *next* tick; ``depth`` is last tick's staleness depth (the probe
    surface :func:`bluefog_tpu.diagnostics.observe_async_staleness` reads).
    """
    recv: Any
    p: jax.Array
    p_recv: jax.Array
    stamps: jax.Array
    local_steps: jax.Array
    force: jax.Array
    depth: jax.Array


def async_window_gossip(
    opt: optax.GradientTransformation,
    sched: Optional[CommSchedule] = None,
    *,
    axis: Axis = "rank",
    staleness_bound: Optional[int] = None,
    pace: Optional[Sequence[int]] = None,
    fuse: bool = True,
    wire: Optional[str] = None,
) -> DecentralizedOptimizer:
    """Bounded-staleness asynchronous window gossip (the paper's second half).

    Reference: the WinPut/PushSum optimizer family over true one-sided RMA
    (``optimizers.py:763-1160`` + the passive-recv thread): every rank runs
    its local step loop at its own pace, pushes ``1/(outdeg+1)`` of its mass
    into neighbor mailboxes via ``win_accumulate`` and proceeds *without
    waiting*; receivers fold in whatever has arrived.  XLA programs are
    bulk-synchronous, so pace heterogeneity is modeled inside the compiled
    step: a static per-rank ``pace`` table marks rank r *active* on ticks
    where ``tick % pace[r] == 0`` — an inactive tick is a rank still busy
    with local compute, so it neither pushes, collects, nor adapts (its
    mailboxes keep accumulating).  The harness (``tools/gossip_bench.py``)
    turns that model into real wall clock: a lockstep fleet pays the
    straggler's delay every tick, the async fleet only on forced sync-ups.

    Correctness under partial activity is push-sum's: the mass scalar ``p``
    travels through the *same* mailboxes with the same activity pattern, so
    every tick's effective mixing over the extended (value ⊕ mailbox) state
    is column-stochastic for ANY activity vector
    (:func:`bluefog_tpu.ops.windows.async_mixing_matrices` is the host-side
    model, property-tested) and the de-biased iterate ``z = x / p`` stays a
    convex combination of the fleet's parameters — the staleness-aware
    mixing correction.

    The staleness bound K (``staleness_bound``, default from
    :func:`bluefog_tpu.parallel.context.async_gossip_bound` /
    ``BLUEFOG_ASYNC``): per-slot step stamps track each in-neighbor's most
    recent delivery; when any rank's staleness depth exceeds K the whole
    fleet is forced active on the next tick (a sync-up), bounding how far a
    straggler's contribution can lag.  ``K=0`` statically forces every tick
    active — exact synchronous lockstep, trajectory-identical to
    combine-then-adapt on the same push schedule (the float64 oracle in
    ``tests/test_async_gossip.py``).

    Params carry the DE-BIASED iterate ``z`` (re-biased to ``x = z·p`` at
    update entry), so rank-0 template broadcast in ``init_distributed``
    and checkpoint surgery both see the quantity the model actually uses.
    """
    def _sched():
        s = sched if sched is not None else _mesh.static_schedule()
        if s.uses_dst_weighting:
            raise ValueError(
                "async_window_gossip requires column-stochastic push "
                "weights (push_schedule), not a dst-weighted schedule")
        return s

    def _bound() -> int:
        if staleness_bound is not None:
            b = int(staleness_bound)
        else:
            b = _mesh.async_gossip_bound()
        if b < 0:
            raise ValueError(f"staleness_bound must be >= 0, got {b}")
        return b

    def _pace(n: int) -> np.ndarray:
        if pace is None:
            return np.ones(n, np.int32)
        tab = np.asarray(pace, np.int32)
        if tab.shape != (n,) or (tab < 1).any():
            raise ValueError(
                f"pace must be {n} ints >= 1, got {np.asarray(pace)!r}")
        return tab

    def _vals(params):
        return fusion.fuse_tree(params).buffers if fuse else params

    def init(params):
        s = _sched()
        _bound()                         # fail fast on a bad knob
        K = max(s.max_in_degree, 1)
        recv = jax.tree.map(
            lambda x: jnp.zeros((K,) + x.shape, x.dtype), _vals(params))
        return DecentralizedState(
            jnp.zeros((), jnp.int32), opt.init(params),
            AsyncGossipState(
                recv=recv,
                p=jnp.ones((), jnp.float32),
                p_recv=jnp.zeros((K,), jnp.float32),
                stamps=wops.stamp_create(s),
                local_steps=jnp.zeros((), jnp.int32),
                force=jnp.zeros((), jnp.bool_),
                depth=jnp.zeros((), jnp.int32)))

    def update(grads, state, params):
        s = _sched()
        bound = _bound()
        cs: AsyncGossipState = state.comm_state
        tick = state.step
        idx = lax.axis_index(axis)
        out_deg = jnp.asarray(s.out_degree)[idx]
        sw = 1.0 / (out_deg.astype(jnp.float32) + 1.0)

        if bound == 0:
            # statically lockstep: the whole activity machinery folds away
            # and the trajectory is exactly synchronous CTA on push weights
            active = jnp.ones((), jnp.bool_)
        else:
            scheduled = (tick % jnp.asarray(_pace(s.size))[idx]) == 0
            active = jnp.logical_or(scheduled, cs.force)

        recipe = fusion.fuse_tree(params) if fuse else None
        z_vals = recipe.buffers if fuse else params
        p = cs.p

        def gossip(w: wops.Window) -> wops.Window:
            # rebias z -> x = z*p, push 1/(outdeg+1) of x along out-edges
            # (wire-codec'd), then — if active — collect: keep the same
            # fraction of x and fold in every mailbox.  Inactive ticks
            # deliver nothing, collect nothing: mailboxes keep accumulating.
            z = w.value
            dt = z.dtype
            x = z * p.astype(dt)
            send = x * jnp.where(active, sw, 0.0).astype(dt)
            w = wops.win_accumulate(
                wops.Window(value=x, recv=w.recv), send, s, axis=axis,
                wire=wire)
            # unreal slots (beyond in_degree) never receive and start at
            # zero, so the plain sum over K equals the real-slot sum
            mailbox = jnp.sum(w.recv.astype(dt), axis=0)
            mixed = (jnp.where(active, sw, 1.0).astype(dt) * x
                     + jnp.where(active, mailbox, jnp.zeros_like(mailbox)))
            new_recv = jnp.where(active, jnp.zeros_like(w.recv), w.recv)
            return wops.Window(value=mixed, recv=new_recv)

        with named_span("COMMUNICATE"):
            wins = jax.tree.map(wops.Window, z_vals, cs.recv)
            wins = _map_windows(gossip, wins)
            mixed_vals = _map_windows(lambda w: w.value, wins)
            new_recv = _map_windows(lambda w: w.recv, wins)
            # mass lane: same mailboxes, same activity, no wire codec
            # (a quantized p would bias the correction it exists to apply)
            pwin = wops.win_accumulate(
                wops.Window(value=p, recv=cs.p_recv),
                p * jnp.where(active, sw, 0.0), s, axis=axis)
            p_mixed = jnp.where(
                active, sw * p + jnp.sum(pwin.recv), p)
            new_p_recv = jnp.where(
                active, jnp.zeros_like(pwin.recv), pwin.recv)
            stamps = wops.stamp_push(cs.stamps, tick, active, s, axis=axis)

        depth = wops.staleness_depth(stamps, tick, s, axis=axis)
        if bound == 0:
            force_next = jnp.zeros((), jnp.bool_)
        else:
            force_next = lax.pmax(depth, axis) > bound

        # de-bias; inactive ranks see p_mixed == p and mixed == x, so their
        # z is algebraically unchanged (masked below to keep it bit-exact)
        z_mixed = jax.tree.map(
            lambda m: m / p_mixed.astype(m.dtype), mixed_vals)
        if fuse:
            recipe.buffers = z_mixed
            z_tree = recipe.unfuse()
        else:
            z_tree = z_mixed
        adapted, new_opt_state = _apply(opt, grads, state.opt_state, z_tree)
        # an inactive rank is mid-local-compute: no adapt lands, params and
        # optimizer state freeze until its next active tick
        new_params = jax.tree.map(
            lambda a, orig: jnp.where(active, a, orig), adapted, params)
        opt_state = jax.tree.map(
            lambda nw, od: jnp.where(active, nw, od),
            new_opt_state, state.opt_state)
        return new_params, DecentralizedState(
            state.step + 1, opt_state,
            AsyncGossipState(
                recv=new_recv, p=p_mixed, p_recv=new_p_recv, stamps=stamps,
                local_steps=cs.local_steps + active.astype(jnp.int32),
                force=force_next, depth=depth))

    return DecentralizedOptimizer(init, update, (axis,))


class AdaptiveStalenessController:
    """Learn the async staleness bound K online from fleet pace signals.

    The bound is a trace-time constant of :func:`async_window_gossip` —
    ``K=0`` even compiles a different (statically lockstep) program — so
    "online" here is host-side: the controller watches the same per-rank
    step-time table the AutoScaler and straggler detector read
    (:func:`bluefog_tpu.diagnostics.observe_step_time` /
    ``last_step_times``), recommends the bound that absorbs the current
    pace spread, and after ``patience`` consecutive agreeing observations
    applies it via :func:`bluefog_tpu.parallel.context.set_async_gossip` +
    ``mark_steady_state(False)`` (the retrace that follows is intended, not
    a bug).  The caller rebuilds its step on a non-``None`` return; with
    the warm executable pool a return to a previously-seen K costs no
    fresh compile.

    The recommendation: a rank running at ``r×`` the alive-median pace
    needs its neighbors to tolerate ``ceil(r) - 1`` missed ticks before a
    forced sync-up, so ``K = clamp(ceil(max_alive / median) - 1, k_min,
    k_max)``.  A throttled spot rank therefore deepens the window and
    degrades gracefully; when its pace recovers K shrinks back toward
    lockstep.  Hysteresis: a change is applied only after the same
    recommendation holds ``patience`` observations in a row, so a single
    noisy step cannot thrash the compiled program.
    """

    def __init__(self, *, k_min: int = 0, k_max: int = 16,
                 patience: int = 3, dead_ranks: Sequence[int] = ()):
        if not (0 <= k_min <= k_max):
            raise ValueError(
                f"need 0 <= k_min <= k_max, got {k_min}..{k_max}")
        if patience < 1:
            raise ValueError(f"patience must be >= 1, got {patience}")
        self.k_min = int(k_min)
        self.k_max = int(k_max)
        self.patience = int(patience)
        self.dead_ranks = frozenset(int(r) for r in dead_ranks)
        self._candidate: Optional[int] = None
        self._streak = 0
        self.applied: Optional[int] = None

    @property
    def current_bound(self) -> int:
        return _mesh.async_gossip_bound()

    def recommend(self, step_times: Optional[Sequence[float]] = None
                  ) -> Optional[int]:
        """The bound the current pace spread calls for (no side effects).
        ``None`` when no step-time table has been observed yet."""
        from . import diagnostics as _diag
        t = (np.asarray(step_times, np.float64).reshape(-1)
             if step_times is not None else _diag.last_step_times())
        if t is None or np.size(t) == 0:
            return None
        t = np.asarray(t, np.float64).reshape(-1)
        alive = [r for r in range(t.size)
                 if r not in self.dead_ranks and np.isfinite(t[r])]
        if not alive:
            return None
        med = float(np.median(t[alive]))
        if med <= 0:
            return None
        spread = float(np.max(t[alive])) / med
        k = int(np.ceil(spread)) - 1
        return max(self.k_min, min(self.k_max, k))

    def observe(self, step_times: Optional[Sequence[float]] = None
                ) -> Optional[int]:
        """Fold one pace observation in; returns the newly-applied bound
        when the hysteresis window agrees on a change, else ``None`` (the
        caller rebuilds its optimizer/step only on a non-``None`` return).
        """
        rec = self.recommend(step_times)
        if rec is None or rec == self.current_bound:
            self._candidate, self._streak = None, 0
            return None
        if rec == self._candidate:
            self._streak += 1
        else:
            self._candidate, self._streak = rec, 1
        if self._streak < self.patience:
            return None
        old = self.current_bound
        self._candidate, self._streak = None, 0
        _mesh.set_async_gossip(rec)
        _metrics.mark_steady_state(False)   # the K-change retrace is intended
        self.applied = rec
        _metrics.gauge(
            "bluefog_async_staleness_bound",
            "async gossip staleness bound K (pace-adaptive)").set(rec)
        _flight.record("async_bound", old=old, new=rec,
                       reason="pace_adaptive")
        return rec


def push_diging(
    opt: optax.GradientTransformation,
    sched: Optional[CommSchedule] = None,
    *,
    axis: Axis = "rank",
    axes: Tuple[str, ...] = ("rank",),
    fuse: bool = True,
) -> DecentralizedOptimizer:
    """Push-DIGing: gradient tracking on directed graphs via push-sum.

    Reference algorithm library: ``examples/pytorch_optimization.py:371``
    (Nedic et al., "Achieving geometric convergence for distributed
    optimization over time-varying graphs").  Gradient tracking
    (:func:`gradient_tracking`) needs doubly-stochastic mixing; on a
    directed graph only *column*-stochastic push weights ``C`` are
    available, so the iterate rides a biased channel ``u`` with a mass
    lane ``p`` de-biasing it:

        y_t     = C(y_{t-1}) + g(z_t) - g(z_{t-1})     (tracker)
        u_{t+1} = C(u_t + A(y_t))                      (push mixing)
        p_{t+1} = C(p_t)
        z_{t+1} = u_{t+1} / p_{t+1}                    (de-biased = params)

    The params the train step carries are always the de-biased ``z``, so
    the user's grad_fn never sees the mass bias.  ``comm_state`` holds
    ``(u, p, y, g_prev)`` with ``u, p`` in fused per-dtype buffers.
    """
    def _sched():
        return sched if sched is not None else push_schedule()

    def _bufs(tree):
        return fusion.fuse_tree(tree).buffers if fuse else tree

    def init(params):
        u0 = _bufs(jax.tree.map(jnp.copy, params))
        p0 = jax.tree.map(lambda x: jnp.ones((), x.dtype), u0)
        zeros = jax.tree.map(jnp.zeros_like, params)
        return DecentralizedState(
            jnp.zeros((), jnp.int32), opt.init(params),
            (u0, p0, zeros, zeros))

    def update(grads, state, params):
        s = _sched()
        u, p, y, g_prev = state.comm_state
        nar = lambda t: jax.tree.map(
            lambda x: ops.neighbor_allreduce(x, s, axis=axis), t)
        with named_span("COMMUNICATE"):
            y = nar(y)
        y = jax.tree.map(lambda a, g, gp: a + g - gp, y, grads, g_prev)
        with named_span("ADAPT"):
            updates, opt_state = opt.update(y, state.opt_state, params)
        step_tree = _bufs(updates)
        with named_span("COMMUNICATE"):
            u = nar(jax.tree.map(jnp.add, u, step_tree))
            p = nar(p)
        recipe = fusion.fuse_tree(params) if fuse else None
        z = jax.tree.map(lambda a, b: a / b, u, p)
        if fuse:
            recipe.buffers = z
            z = recipe.unfuse()
        return z, DecentralizedState(
            state.step + 1, opt_state, (u, p, y, grads))

    return DecentralizedOptimizer(init, update, axes)


def exact_diffusion(
    opt: optax.GradientTransformation,
    comm: Communicator,
    *,
    axes: Tuple[str, ...] = ("rank",),
) -> DecentralizedOptimizer:
    """Exact diffusion: bias-corrected CTA gossip.

    Reference algorithm library: ``examples/pytorch_optimization.py:237``
    (Yuan et al., "Exact diffusion for distributed optimization").  Plain
    CTA/diffusion converges to a neighborhood of the optimum whose radius
    scales with data heterogeneity; the psi-correction removes that bias:

        psi_t   = A(x_t, g_t)
        x_{t+1} = Comb(psi_t + x_t - psi_{t-1})

    ``comm_state`` carries psi_{t-1}.
    """
    def init(params):
        return DecentralizedState(
            jnp.zeros((), jnp.int32), opt.init(params),
            jax.tree.map(jnp.copy, params))          # psi_prev := x_0

    def update(grads, state, params):
        psi_prev = state.comm_state
        psi, opt_state = _apply(opt, grads, state.opt_state, params)
        phi = jax.tree.map(lambda a, b, c: a + b - c, psi, params, psi_prev)
        new_params = comm(phi, state.step)
        return new_params, DecentralizedState(state.step + 1, opt_state, psi)

    return DecentralizedOptimizer(init, update, axes)


def gradient_tracking(
    opt: optax.GradientTransformation,
    comm: Communicator,
    *,
    axes: Tuple[str, ...] = ("rank",),
) -> DecentralizedOptimizer:
    """Gradient tracking: every rank tracks the GLOBAL average gradient.

    Reference algorithm library: ``examples/pytorch_optimization.py:313``.
    The tracker y obeys the dynamic-average-consensus recursion

        y_{t+1} = Comb(y_t) + g_{t+1} - g_t
        x_{t+1} = Comb(A(x_t, y_t))

    so sum_r y_r == sum_r g_r at every step and each rank's optimizer steps
    on an estimate of the average gradient — exact convergence under
    heterogeneous data.  ``comm_state`` carries ``(y, g_prev)``.
    """
    def init(params):
        zeros = jax.tree.map(jnp.zeros_like, params)
        # y_0 = g_0 is established on the first update (g_prev = 0, y = 0)
        return DecentralizedState(
            jnp.zeros((), jnp.int32), opt.init(params), (zeros, zeros))

    def update(grads, state, params):
        y, g_prev = state.comm_state
        y = comm(y, state.step)
        y = jax.tree.map(lambda a, g, gp: a + g - gp, y, grads, g_prev)
        adapted, opt_state = _apply(opt, y, state.opt_state, params)
        new_params = comm(adapted, state.step)
        return new_params, DecentralizedState(
            state.step + 1, opt_state, (y, grads))

    return DecentralizedOptimizer(init, update, axes)


def _zero_axis_size(axis: Axis) -> int:
    """Static size of a mesh axis by name from the live context."""
    if axis == "rank":
        return _mesh.size()
    if axis == "local":
        return _mesh.local_size()
    if axis == "machine":
        return _mesh.machine_size()
    raise ValueError(f"unknown mesh axis {axis!r}")


def _zero_shard_templates(params, n: int):
    """Zero-filled shard templates (one per dtype bucket) for ``opt.init``.

    Shapes only depend on the template, so ``init_distributed`` can build the
    state outside ``shard_map``; actual shard *content* is rank-dependent and
    materializes on the first update.  Caveat: optax transforms whose init
    inspects parameter values (not just shapes) see zeros here.
    """
    fused = fusion.fuse_tree(params)
    return [jnp.zeros(((buf.size + (-buf.size) % n) // n,), buf.dtype)
            for buf in fused.buffers]


def _zero_apply(opt, grads, opt_state, params, axis: Axis, n: int):
    """ZeRO-1 sharded adapt: reduce-scatter grads over ``axis``, step the
    local 1/n shard of params with the local 1/n optimizer state, all-gather
    the updated params.  Per-chip optimizer-state memory is 1/n of the
    replicated strategies'; the two collectives move the same bytes as one
    allreduce (reduce_scatter + all_gather), so the bandwidth cost matches
    :func:`gradient_allreduce` with ``fuse=True``.
    """
    idx = lax.axis_index(axis)
    # align grad dtypes to the params so both trees land in the SAME per-
    # dtype buckets (f32 grads over bf16 params would otherwise bucket
    # differently and the zip below would pair mismatched buffers)
    grads = jax.tree.map(lambda g, p: g.astype(p.dtype), grads, params)
    fg = fusion.fuse_tree(grads)
    fp = fusion.fuse_tree(params)
    g_shards, p_shards, pads = [], [], []
    with named_span("COMMUNICATE"):       # reduce-scatter phase
        for gbuf, pbuf in zip(fg.buffers, fp.buffers):
            pad = (-gbuf.size) % n
            gp = jnp.pad(gbuf, (0, pad))
            shard = lax.psum_scatter(gp, axis, scatter_dimension=0, tiled=True)
            if jnp.issubdtype(shard.dtype, jnp.floating):
                shard = shard / n              # mean, matching pmean semantics
            pp = jnp.pad(pbuf, (0, pad))
            g_shards.append(shard)
            p_shards.append(lax.dynamic_slice_in_dim(
                pp, idx * shard.size, shard.size))
            pads.append(pad)
    with named_span("ADAPT"):
        updates, new_opt_state = opt.update(g_shards, opt_state, p_shards)
        new_shards = optax.apply_updates(p_shards, updates)
    new_bufs = []
    with named_span("COMMUNICATE"):       # all-gather phase
        for shard, pad in zip(new_shards, pads):
            full = lax.all_gather(shard, axis, tiled=True)
            new_bufs.append(full[:full.size - pad] if pad else full)
    fp.buffers = new_bufs
    return fp.unfuse(), new_opt_state


def _check_elementwise_chain(opt: optax.GradientTransformation,
                             n_probe: int = 2) -> None:
    """Best-effort tripwire for the ZeRO elementwise requirement (see
    :func:`zero_gradient_allreduce`): run ``opt.update`` once on a small
    structured dummy tree (reference semantics) and once on emulated ZeRO
    shard buffers (pad + split each fused dtype bucket across ``n_probe``
    virtual ranks, one state shard each — exactly ``_zero_apply``'s
    dataflow), and raise if the resulting parameters differ.

    The probe runs at three gradient magnitudes (x1, x100, x0.01) so
    threshold-dependent couplings fire on at least one of them — e.g.
    ``clip_by_global_norm`` with a max_norm above the base probe's ~2.31
    global norm takes its no-op branch at x1 but clips (per-shard vs
    global norm, divergent) at x100.  Also catches ``masked``/
    ``multi_transform`` (flat buffers instead of the labeled tree, usually
    a structure error) and per-leaf scalers (trust ratios see shard
    norms).  Plain sgd/momentum/adam/adamw chains are elementwise and pass
    bit-for-bit.  Best-effort by construction: a coupling whose threshold
    sits outside all three probe magnitudes (or that only engages on
    shapes/dtypes unlike the probe tree) can still slip through — the
    probe is a cheap guard, not a proof of elementwiseness.
    """
    tree_p = {"a": jnp.asarray([0.3, -0.4, 0.5], jnp.float32),
              "b": jnp.asarray([[2.0, -1.0], [0.5, 3.0]], jnp.float32)}
    base_g = {"a": jnp.asarray([0.1, 0.2, -0.3], jnp.float32),
              "b": jnp.asarray([[-1.0, 0.4], [0.2, 2.0]], jnp.float32)}
    why = None
    try:
        for scale in (1.0, 100.0, 0.01):
            tree_g = jax.tree.map(lambda g: g * scale, base_g)
            ref_upd, _ = opt.update(tree_g, opt.init(tree_p), tree_p)
            ref_new = optax.apply_updates(tree_p, ref_upd)

            fp, fg = fusion.fuse_tree(tree_p), fusion.fuse_tree(tree_g)
            pads = [(-buf.size) % n_probe for buf in fp.buffers]
            p_pad = [jnp.pad(b, (0, p)) for b, p in zip(fp.buffers, pads)]
            g_pad = [jnp.pad(b, (0, p)) for b, p in zip(fg.buffers, pads)]
            shards_new = []
            for i in range(n_probe):
                sl = lambda b: lax.dynamic_slice_in_dim(
                    b, i * (b.size // n_probe), b.size // n_probe)
                p_sh = [sl(b) for b in p_pad]
                g_sh = [sl(b) for b in g_pad]
                st = opt.init([jnp.zeros_like(b) for b in p_sh])
                upd, _ = opt.update(g_sh, st, p_sh)
                shards_new.append(optax.apply_updates(p_sh, upd))
            new_bufs = [
                jnp.concatenate([shards_new[i][k] for i in range(n_probe)])
                for k in range(len(p_pad))]
            fp.buffers = [b[:b.size - p] if p else b
                          for b, p in zip(new_bufs, pads)]
            zero_new = fp.unfuse()
            agree = all(
                np.allclose(np.asarray(a), np.asarray(b),
                            rtol=2e-5, atol=1e-6)
                for a, b in zip(jax.tree.leaves(ref_new),
                                jax.tree.leaves(zero_new)))
            if not agree:
                why = ("probe trajectories differ between the structured "
                       "tree and ZeRO shard buffers "
                       f"(at gradient scale x{scale:g})")
                break
    except Exception as exc:                    # structure errors etc.
        why = f"probe failed on ZeRO shard buffers: {exc!r}"
    if why:
        raise ValueError(
            "this optax chain is not elementwise, so ZeRO-1 sharding would "
            f"silently diverge from gradient_allreduce ({why}). Transforms "
            "that couple elements across the tree (clip_by_global_norm, "
            "masked, multi_transform, per-leaf trust ratios) see per-shard "
            "buffers under ZeRO, not the full tree. Use gradient_allreduce, "
            "move the coupling into grad_fn, or pass "
            "check_elementwise=False if you know the chain is exact.")


def zero_gradient_allreduce(
    opt: optax.GradientTransformation, *, axis: Axis = "rank",
    axis_size: Optional[int] = None, check_elementwise: bool = True,
) -> DecentralizedOptimizer:
    """Synchronous data parallelism with ZeRO-1 sharded optimizer state.

    Same trajectory as :func:`gradient_allreduce` **provided the optax chain
    is elementwise** — this is a hard requirement, not an optimization note.
    The adapt runs on flat per-dtype shard buffers, not the user's param
    pytree, so transforms that depend on tree structure or couple elements
    across the tree (``optax.masked`` weight decay, ``multi_transform``,
    ``clip_by_global_norm``) see a different tree/norm than they would
    unsharded and silently diverge from ``gradient_allreduce``.  Plain
    sgd/momentum/adam/adamw chains are elementwise and exact.  Each chip
    stores only ``1/n`` of the optimizer state: grads are
    ``reduce_scatter``'d, the local shard is stepped, and updated params are
    ``all_gather``'d — the classic ZeRO stage-1 dataflow mapped onto ICI
    collectives.  Beyond-reference: the reference is replicated-state-only
    (``optimizers.py:166-294``); this is what makes billion-parameter models
    fit the strategy on TPU.

    Requires params to be identical across ``axis`` (true for this strategy:
    identical init + identical updates), which is why ZeRO composes with the
    *synchronous* strategies but not with gossip over the same axis — under
    gossip each rank's params differ, and gathering shards would splice
    different trajectories.  For gossip + ZeRO use
    :func:`zero_adapt_with_combine` with orthogonal axes.

    ``axis_size`` overrides the context lookup (for AOT compilation against
    an abstract topology where no context is initialized).
    ``check_elementwise=False`` skips the construction-time probe
    (:func:`_check_elementwise_chain`) that rejects tree-coupled chains.
    """
    if check_elementwise:
        _check_elementwise_chain(opt)
    n = axis_size or _zero_axis_size(axis)
    axes = ("rank",) if axis == "rank" else ("machine", "local")

    def init(params):
        return DecentralizedState(jnp.zeros((), jnp.int32),
                                  opt.init(_zero_shard_templates(params, n)))

    def update(grads, state, params):
        new_params, opt_state = _zero_apply(
            opt, grads, state.opt_state, params, axis, n)
        return new_params, DecentralizedState(state.step + 1, opt_state)

    return DecentralizedOptimizer(init, update, axes)


def zero_adapt_with_combine(
    opt: optax.GradientTransformation,
    comm: Communicator,
    *,
    shard_axis: Axis = "local",
    axes: Tuple[str, ...] = ("machine", "local"),
    shard_axis_size: Optional[int] = None,
    check_elementwise: bool = True,
) -> DecentralizedOptimizer:
    """Hierarchical gossip with ZeRO sharding on the orthogonal axis.

    The 2-D-mesh composition: ``comm`` gossips parameters machine-to-machine
    (DCN-friendly neighbor averaging, e.g.
    ``hierarchical_communicator(...)``), while the adapt is ZeRO-sharded
    across the chips *within* each machine (ICI reduce-scatter/all-gather):

        x_{t+1} = ZeROAdapt_local(Comb_machine(x_t), pmean_local(g_t))

    Every chip in a machine ends each step with identical parameters (the
    all-gather re-assembles one shared update), so the cross-machine gossip
    sees one logical model per machine — the same layout the reference's
    hierarchical mode maintains via local allreduce + bcast
    (``mpi_controller.cc:452-507``), but with 1/local_size optimizer-state
    memory and grads averaged in the same collective that shards them.

    Shares :func:`zero_gradient_allreduce`'s hard requirement: the optax
    chain must be elementwise (the adapt sees flat shard buffers, not the
    param pytree — tree-structured or global-norm transforms diverge), and
    the same construction-time tripwire enforces it
    (``check_elementwise=False`` to skip).
    """
    if check_elementwise:
        _check_elementwise_chain(opt)
    n = shard_axis_size or _zero_axis_size(shard_axis)

    def init(params):
        return DecentralizedState(jnp.zeros((), jnp.int32),
                                  opt.init(_zero_shard_templates(params, n)))

    def update(grads, state, params):
        combined = comm(params, state.step)
        new_params, opt_state = _zero_apply(
            opt, grads, state.opt_state, combined, shard_axis, n)
        return new_params, DecentralizedState(state.step + 1, opt_state)

    return DecentralizedOptimizer(init, update, axes)


def powersgd_allreduce(
    opt: optax.GradientTransformation,
    *,
    compression_rank: int = 2,
    min_compress_size: int = 2048,
    axis: Axis = "rank",
) -> DecentralizedOptimizer:
    """Synchronous DP with PowerSGD rank-r gradient compression.

    Beyond-reference bandwidth lever (Vogels et al., "PowerSGD: practical
    low-rank gradient compression for distributed optimization", 2019 —
    public technique): each matrix-shaped gradient ``M [m, k]`` is
    allreduced as two rank-r factors, ``(m + k) * r`` values on the wire
    instead of ``m * k`` (an ~85x cut for a 1024x512 layer at r=4), with the
    approximation error fed back into the next step so it decays instead
    of accumulating.  One power-iteration per step, warm-started from last
    step's factor:

        M  = grad + error                  (error feedback)
        P  = pmean(M @ Q);  P = qr(P).Q    (left factor, orthonormalized)
        Q' = pmean(M.T @ P)                (right factor)
        M^ = P @ Q'.T;  error = M - M^

    All compute is two skinny matmuls + a tiny [m, r] QR — exactly the MXU
    shape, unlike coordinate-wise quantizers.  The TPU fit is the point:
    the wire savings pay on DCN-linked multi-slice DP, while the compress/
    decompress cost is a rounding error next to the model matmuls.

    Leaves below ``min_compress_size`` elements or with fewer than 2 dims
    (biases, norms, scalars) are allreduced exactly.  ``Q`` is initialized
    identically on every rank (deterministic per-leaf key) and stays
    identical by construction (it only ever updates from pmean'd values),
    which is what makes the factor allreduces well-defined.  Compression
    runs in f32 regardless of the gradient dtype for a stable power
    iteration.  Like :func:`gradient_allreduce`, the trajectory keeps all
    ranks bitwise in lock-step.
    """
    if compression_rank < 1:
        raise ValueError(f"compression_rank must be >= 1, got "
                         f"{compression_rank}")
    r = compression_rank

    def _compressible(x):
        return x.ndim >= 2 and x.size >= min_compress_size

    def _mk(x):
        return int(np.prod(x.shape[:-1])), int(x.shape[-1])

    def init(params):
        leaves = jax.tree.leaves(params)
        errs, qs = [], []
        for i, p in enumerate(leaves):
            if not _compressible(p):
                continue
            m, k = _mk(p)
            key = jax.random.fold_in(jax.random.key(17), i)
            qs.append(jax.random.normal(key, (k, min(r, m, k)),
                                        jnp.float32))
            errs.append(jnp.zeros((m, k), jnp.float32))
        return DecentralizedState(
            jnp.zeros((), jnp.int32), opt.init(params),
            (tuple(errs), tuple(qs)))

    def update(grads, state, params):
        errs, qs = state.comm_state
        leaves, treedef = jax.tree.flatten(grads)
        new_errs, new_qs = [], []
        out: list = [None] * len(leaves)
        ci = 0
        for i, g in enumerate(leaves):
            if not _compressible(g):
                continue
            m, k = _mk(g)
            M = g.reshape(m, k).astype(jnp.float32) + errs[ci]
            # COMMUNICATE scopes the collectives only — the compress/
            # decompress matmuls and the QR are compute, and mislabeling
            # them would skew the trace-derived comm/compute split
            with named_span("COMMUNICATE"):
                P = lax.pmean(M @ qs[ci], axis)          # [m, r]
            P = jnp.linalg.qr(P, mode="reduced")[0]
            with named_span("COMMUNICATE"):
                Qn = lax.pmean(M.T @ P, axis)            # [k, r]
            Mhat = P @ Qn.T
            new_errs.append(M - Mhat)
            # pmean outputs are VMA-unvarying, but the carried state
            # entered varying (replicate/shard flow) — recast so scan
            # carries type-match under VMA checking
            new_qs.append(lax.pcast(Qn, axis, to="varying")
                          if axis in getattr(jax.typeof(qs[ci]), "vma",
                                             ()) else Qn)
            out[i] = Mhat.reshape(g.shape).astype(g.dtype)
            ci += 1
        # exact-path leaves (biases, norms, scalars) reduce in ONE fused
        # allreduce per dtype — not dozens of latency-bound tiny
        # collectives on exactly the links PowerSGD targets
        exact_idx = [i for i, o in enumerate(out) if o is None]
        if exact_idx:
            with named_span("COMMUNICATE"):
                reduced = fusion.fused_leaf_op(
                    lambda x: lax.pmean(x, axis))(
                    [leaves[i] for i in exact_idx])
            for i, rg in zip(exact_idx, reduced):
                out[i] = rg
        ghat = jax.tree.unflatten(treedef, out)
        new_params, opt_state = _apply(opt, ghat, state.opt_state, params)
        return new_params, DecentralizedState(
            state.step + 1, opt_state, (tuple(new_errs), tuple(new_qs)))

    return DecentralizedOptimizer(init, update, (axis,))


# ---------------------------------------------------------------------------
# Strategy registry (the autotune surface)
# ---------------------------------------------------------------------------

class StrategySpec(NamedTuple):
    """Constructor + contract metadata for one named strategy.

    ``build`` takes the normalized knob set the autotuner enumerates —
    ``(opt, *, schedule, wire, concurrent, delayed,
    num_steps_per_communication)`` — and returns the configured
    :class:`DecentralizedOptimizer`.  The flags describe which knobs the
    algorithm actually responds to (so the search space can collapse the
    indifferent axes) and ``weights`` lists the schedule weightings its
    contract admits:

    * ``"recv"`` — recv-side combine weights (``compile_topology``),
      the standard gossip schedule.
    * ``"push"`` — column-stochastic push weights (:func:`push_schedule`),
      NOT dst-weighted; what push-sum-family algorithms require.
    * ``"dst"`` — sender-side dst-weighting
      (``compile_from_weights(..., dst_weights_per_rank=...)``); only
      algorithms whose wire codec commutes with send scaling admit it.
    """
    build: Callable[..., DecentralizedOptimizer]
    uses_schedule: bool       # gossip: wire bytes depend on the topology
    wire_aware: bool          # accepts a wire= codec on its gossip rounds
    concurrent_aware: bool    # accepts concurrent= round-parallel emission
    pipelined_ok: bool        # supports delayed=True (hence overlap=True)
    weights: Tuple[str, ...]


def _reg_allreduce(opt, *, schedule=None, wire=None, concurrent=None,
                   delayed=False, num_steps_per_communication=1):
    return gradient_allreduce(opt)


def _reg_neighbor_cta(opt, *, schedule=None, wire=None, concurrent=None,
                      delayed=False, num_steps_per_communication=1):
    comm = neighbor_communicator(
        schedule if schedule is not None else _mesh.static_schedule(),
        wire=wire, concurrent=concurrent)
    return adapt_with_combine(
        opt, comm, delayed=delayed,
        num_steps_per_communication=num_steps_per_communication)


def _reg_neighbor_atc(opt, *, schedule=None, wire=None, concurrent=None,
                      delayed=False, num_steps_per_communication=1):
    comm = neighbor_communicator(
        schedule if schedule is not None else _mesh.static_schedule(),
        wire=wire, concurrent=concurrent)
    return adapt_then_combine(
        opt, comm, delayed=delayed,
        num_steps_per_communication=num_steps_per_communication)


def _reg_exact_diffusion(opt, *, schedule=None, wire=None, concurrent=None,
                         delayed=False, num_steps_per_communication=1):
    comm = neighbor_communicator(
        schedule if schedule is not None else _mesh.static_schedule(),
        wire=wire, concurrent=concurrent)
    return exact_diffusion(opt, comm)


def _reg_gradient_tracking(opt, *, schedule=None, wire=None, concurrent=None,
                           delayed=False, num_steps_per_communication=1):
    comm = neighbor_communicator(
        schedule if schedule is not None else _mesh.static_schedule(),
        wire=wire, concurrent=concurrent)
    return gradient_tracking(opt, comm)


def _reg_push_sum(opt, *, schedule=None, wire=None, concurrent=None,
                  delayed=False, num_steps_per_communication=1):
    return push_sum(opt, schedule)


def _reg_push_diging(opt, *, schedule=None, wire=None, concurrent=None,
                     delayed=False, num_steps_per_communication=1):
    return push_diging(opt, schedule)


def _reg_choco(opt, *, schedule=None, wire=None, concurrent=None,
               delayed=False, num_steps_per_communication=1):
    return choco_gossip(opt, schedule, wire=wire if wire else "int8")


def _reg_async_window_gossip(opt, *, schedule=None, wire=None,
                             concurrent=None, delayed=False,
                             num_steps_per_communication=1):
    # pace/staleness_bound come from the context knob (BLUEFOG_ASYNC /
    # set_async_gossip), not the autotune axes: the tuner picks sync-vs-
    # async as an *algorithm*, the operator tunes the bound per fleet
    return async_window_gossip(opt, schedule, wire=wire)


#: Name -> :class:`StrategySpec` for every strategy the autotuner can pick.
STRATEGIES = {
    "allreduce": StrategySpec(
        _reg_allreduce, uses_schedule=False, wire_aware=False,
        concurrent_aware=False, pipelined_ok=False, weights=()),
    "neighbor_cta": StrategySpec(
        _reg_neighbor_cta, uses_schedule=True, wire_aware=True,
        concurrent_aware=True, pipelined_ok=True, weights=("recv",)),
    "neighbor_atc": StrategySpec(
        _reg_neighbor_atc, uses_schedule=True, wire_aware=True,
        concurrent_aware=True, pipelined_ok=False, weights=("recv",)),
    "exact_diffusion": StrategySpec(
        _reg_exact_diffusion, uses_schedule=True, wire_aware=True,
        concurrent_aware=True, pipelined_ok=False, weights=("recv",)),
    "gradient_tracking": StrategySpec(
        _reg_gradient_tracking, uses_schedule=True, wire_aware=True,
        concurrent_aware=True, pipelined_ok=False, weights=("recv",)),
    "push_sum": StrategySpec(
        _reg_push_sum, uses_schedule=True, wire_aware=False,
        concurrent_aware=False, pipelined_ok=False, weights=("push",)),
    "push_diging": StrategySpec(
        _reg_push_diging, uses_schedule=True, wire_aware=False,
        concurrent_aware=False, pipelined_ok=False, weights=("push",)),
    "choco": StrategySpec(
        _reg_choco, uses_schedule=True, wire_aware=True,
        concurrent_aware=False, pipelined_ok=False,
        weights=("recv", "dst")),
    "async_window_gossip": StrategySpec(
        _reg_async_window_gossip, uses_schedule=True, wire_aware=True,
        concurrent_aware=False, pipelined_ok=False, weights=("push",)),
}


def strategy_constraint_violation(
    name: str,
    *,
    schedule: Optional[CommSchedule] = None,
    wire: Optional[str] = None,
    delayed: bool = False,
    num_steps_per_communication: int = 1,
    overlap: bool = False,
) -> Optional[str]:
    """The reason a knob combination violates ``name``'s contract, or None.

    Mirrors the raises the constructors / :func:`make_train_step` would hit
    so the autotuner can reject candidates *before* paying for a compile and
    record why.  Messages match the runtime errors (pinned by tests).
    """
    spec = STRATEGIES[name]
    if delayed and not spec.pipelined_ok:
        if name == "neighbor_atc":
            return ("adapt_then_combine cannot be pipelined: its gossip "
                    "input IS the update output. Use adapt_with_combine"
                    "(..., delayed=True) for one-step-delayed mixing")
        return (f"{name} has no pipelined variant: delayed=True only "
                "applies to adapt_with_combine")
    if delayed and num_steps_per_communication != 1:
        return ("delayed=True requires num_steps_per_communication == 1: "
                "the carried mixed params would be poisoned by raw params "
                "on non-communicating steps")
    if overlap and not (spec.pipelined_ok and delayed):
        return ("overlap=True requires a pipelined strategy whose "
                "comm_state carries one-step-delayed mixed params — build "
                "one with adapt_with_combine(..., delayed=True)")
    dst = schedule is not None and schedule.uses_dst_weighting
    if name in ("push_sum", "push_diging") and dst:
        return ("push_sum requires a schedule without dst-weighting "
                "(uses_dst_weighting=False); pass dst_weight= instead"
                if name == "push_sum" else
                "push_diging requires column-stochastic push weights "
                "(push_schedule), not a dst-weighted schedule")
    if name == "async_window_gossip" and dst:
        return ("async_window_gossip requires column-stochastic push "
                "weights (push_schedule), not a dst-weighted schedule")
    if name == "choco" and dst:
        from .ops.collectives import _parse_wire
        w = wire if wire else "int8"
        if _parse_wire(w)[0] not in ("int8", "fp8"):
            return ("choco_gossip with a dst-weighted schedule "
                    "(uses_dst_weighting=True) requires wire='int8' or "
                    f"'fp8'; wire={w!r} does not commute with send scaling")
    return None


# ---------------------------------------------------------------------------
# Reference-named factories (the familiar surface)
# ---------------------------------------------------------------------------

def DistributedGradientAllreduceOptimizer(opt, **kw):
    return gradient_allreduce(opt, **kw)


def DistributedAdaptWithCombineOptimizer(opt, communication_type="neighbor_allreduce",
                                         **kw):
    comm, kw = _comm_from_type(communication_type, kw)
    return adapt_with_combine(opt, comm, **kw)


def DistributedAdaptThenCombineOptimizer(opt, communication_type="neighbor_allreduce",
                                         **kw):
    comm, kw = _comm_from_type(communication_type, kw)
    return adapt_then_combine(opt, comm, **kw)


def DistributedNeighborAllreduceOptimizer(opt, **kw):
    comm, kw = _comm_from_type("neighbor_allreduce", kw)
    return adapt_with_combine(opt, comm, **kw)


def DistributedHierarchicalNeighborAllreduceOptimizer(opt, **kw):
    comm, kw = _comm_from_type("hierarchical_neighbor_allreduce", kw)
    return adapt_with_combine(opt, comm, **kw)


def DistributedWinPutOptimizer(opt, **kw):
    return win_put_optimizer(opt, **kw)


def DistributedPullGetOptimizer(opt, **kw):
    return pull_get_optimizer(opt, **kw)


def DistributedPushSumOptimizer(opt, **kw):
    return push_sum(opt, **kw)


def _comm_from_type(communication_type: str, kw):
    """Resolve a reference communication_type to (communicator, strategy kw).

    The hierarchical type also forces ``axes=("machine", "local")`` so the
    train step runs on the 2-D mesh its communicator needs.
    """
    kw = dict(kw)
    sched = kw.pop("schedule", None)
    scheds = kw.pop("schedules", None)
    wire = kw.pop("wire", None)
    concurrent = kw.pop("concurrent", None)
    if communication_type == "neighbor_allreduce":
        if sched is None and scheds is None:
            # an installed dynamic topology (bf.set_dynamic_topology) takes
            # precedence over the static schedule — the reference's
            # per-iteration weight-mutation pattern, compiled
            scheds = _mesh.get_context().dynamic_schedules
            if scheds is None:
                sched = _mesh.static_schedule()
        comm = neighbor_communicator(sched, scheds, wire=wire,
                                     concurrent=concurrent)
    elif communication_type == "hierarchical_neighbor_allreduce":
        if sched is None and scheds is None:
            sched = _mesh.machine_schedule()
        comm = hierarchical_communicator(sched, scheds, wire=wire,
                                         concurrent=concurrent)
        kw.setdefault("axes", ("machine", "local"))
    elif communication_type in ("allreduce", "empty"):
        if sched is not None or scheds is not None:
            raise TypeError(
                f"communication_type {communication_type!r} does not take a "
                "schedule; dynamic topologies require neighbor_allreduce")
        if wire is not None or concurrent is not None:
            raise TypeError(
                f"wire compression / round-parallel emission apply to "
                f"gossip, not communication_type {communication_type!r}")
        comm = (allreduce_communicator() if communication_type == "allreduce"
                else empty_communicator())
    else:
        raise ValueError(f"unknown communication_type {communication_type!r}")
    allowed = ("num_steps_per_communication", "axes", "delayed")
    unknown = set(kw) - set(allowed)
    if unknown:
        raise TypeError(f"unexpected arguments: {sorted(unknown)}")
    return comm, kw


# ---------------------------------------------------------------------------
# Train-step builder
# ---------------------------------------------------------------------------

def _row_sharding(n: int, like=None):
    """Where an ``[n, ...]`` stack belongs: one row per device.  ``like`` (a
    placed stack) wins — a composed carving shards over its own mesh;
    otherwise the context's rank axis when it has ``n`` devices.  ``None``
    (leave it to the first step call) across processes and for widths
    that are not the mesh's."""
    if jax.process_count() > 1:
        return None
    sharding = getattr(like, "sharding", None)
    if isinstance(sharding, NamedSharding) and sharding.spec \
            and sharding.spec[0] is not None:
        return NamedSharding(sharding.mesh, P(sharding.spec[0]))
    if _mesh.is_initialized() and _mesh.size() == n:
        return NamedSharding(_mesh.mesh(), P("rank"))
    return None


def _stack_rows(tree, n: int, sharding):
    """``n`` copies of every leaf along a new leading axis, each device
    materializing only its own row: broadcasting on the default device
    first would put all ``n`` copies on device 0."""
    def stack(t):
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), t)
    if sharding is None:
        return stack(tree)
    return jax.jit(stack, out_shardings=sharding)(tree)


def replicate(tree, n: Optional[int] = None):
    """Stack n copies along a new leading rank axis (distributed tensor),
    sharded one row per device over the context mesh."""
    n = _mesh.size() if n is None else n
    return _stack_rows(tree, n, _row_sharding(n))


def init_distributed(strategy: DecentralizedOptimizer, dist_params):
    """Initialize strategy state for distributed (rank-stacked) params,
    placed like them."""
    first = jax.tree.leaves(dist_params)[0]
    n = first.shape[0]
    template = jax.tree.map(lambda x: x[0], dist_params)
    state = _stack_rows(strategy.init(template), n, _row_sharding(n, first))
    if strategy.pipelined:
        # the delayed-mixing carry must start from each rank's OWN params
        # (broadcasting the rank-0 template would silently teleport rank 0's
        # params into every rank's first adapt under rank-varying inits)
        state = state._replace(
            comm_state=jax.tree.map(jnp.copy, dist_params))
    return state


# Argument positions make_train_step donates (params, opt-state).  The AOT
# tests read this instead of hard-coding the tuple, so a future signature
# change cannot silently desynchronize the reported `donated` flag from what
# the executable actually aliases.
TRAIN_STEP_DONATE_ARGNUMS = (0, 1)
STATEFUL_TRAIN_STEP_DONATE_ARGNUMS = (0, 1, 2)


class _InstrumentedStep:
    """Telemetry shim around the jitted train step.

    Feeds the metrics registry from the host side of every call: per-call
    wall time (EWMA gauge + histogram), the fused-k/donation flags, and
    the retrace sentinel — the jit cache growing after warmup means the
    step recompiled in steady state.  With ``metrics_every_k`` set it also
    samples :func:`bluefog_tpu.diagnostics.diagnose_consensus` on the
    step's *output* params (never the donated inputs) on the first call —
    so the probe compiles inside the warmup window — and then on every
    k-th call.  Everything else (``.lower`` for AOT, ``._cache_size`` in
    tests) delegates to the wrapped jit function untouched.
    """

    def __init__(self, fn, *, steps_per_call: int, donated: bool,
                 overlap: bool = False,
                 metrics_every_k: Optional[int] = None, warmup: int = 2):
        self._fn = fn
        self._steps_per_call = steps_per_call
        self._donated = donated
        self._overlap = overlap
        self._metrics_every_k = metrics_every_k
        self._warmup = max(int(warmup), 1)
        self._calls = 0
        self._jit_cache_baseline: Optional[int] = None
        self._trace = _tracing.new_trace("train")
        # the first call alone goes through :meth:`_first_call`, which
        # registers the program and takes itself out of the way
        self._call = self._first_call

    def __getattr__(self, name):
        fn = self.__dict__.get("_fn")
        if fn is None:
            raise AttributeError(name)
        return getattr(fn, name)

    def _jit_cache_len(self) -> Optional[int]:
        try:
            return self._fn._cache_size()
        except Exception:
            return None

    def __call__(self, *args, **kwargs):
        # the gossip round rides inside the fused step program, so the
        # span covers compute + communication of this call and, around
        # its `dispatch`, everything this wrapper does on the host
        with _tracing.stage(self._trace, "train_step", cat="train",
                            step=self._calls + 1,
                            fused_k=self._steps_per_call) as st:
            st.attrs["overlap"] = self._overlap        # ring only
            return self._call(*args, **kwargs)

    def _first_call(self, *args, **kwargs):
        """The first call, then never again: keep the call's abstract
        shapes (the arrays themselves may be donated) and hand
        ``tracing.register_program`` a way to find the compiled step again
        when someone asks which of its instructions is which named part
        (``tracing.device_scopes``): lowering from the shapes goes through
        jit's own caches, and nothing is lowered or compiled before
        that."""
        shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype,
                sharding=a.sharding if a.committed else None)
            if isinstance(a, jax.Array) else a, (args, kwargs))
        _tracing.register_program(
            "train_step", lambda fn=self._fn, shapes=shapes:
            fn.lower(*shapes[0], **shapes[1]).compile())
        del self._call
        return self._call(*args, **kwargs)

    def _call(self, *args, **kwargs):
        import time as _time
        call = self._calls + 1
        _flight.record("step_begin", name="train_step", step=call)
        t0 = _time.perf_counter()
        try:
            # fault injection (zero-cost gate when no plan is installed): a
            # kill/hang/throttle fault fires BEFORE dispatch — the sleep
            # lands in the step-time metrics, which is how a straggler
            # looks for real
            if _chaos._plan is not None:
                _chaos.on_train_step(call)
            with _tracing.stage(self._trace, "dispatch", cat="train"):
                out = self._fn(*args, **kwargs)
        except BaseException as e:
            # flush the black box before the exception unwinds the train
            # loop (the launcher/supervisor may take the process down next)
            _flight.note_failure(
                "exception", detail=f"{type(e).__name__}: {e}", step=call)
            raise
        dt = _time.perf_counter() - t0
        self._calls += 1
        # payload corruption touches only the step OUTPUTS (donation-safe,
        # same contract as the consensus probe below)
        if _chaos._plan is not None:
            out = _chaos.corrupt_train_output(out, self._calls)
            # seeded membership churn (`join` faults) enacts the real
            # elastic-join path against the step outputs
            out = _chaos.apply_membership(out, self._calls)
        _metrics.record_step(dt, steps=self._steps_per_call,
                             donated=self._donated,
                             fused_k=self._steps_per_call,
                             overlap=self._overlap)
        _flight.record("step_end", name="train_step", step=self._calls,
                       dur_s=round(dt, 6), fused_k=self._steps_per_call,
                       overlap=self._overlap, donated=self._donated)
        from . import diagnostics as _diag
        # per-rank step-time table every call (a host-side numpy fill):
        # chaos-injected sleeps are attributed per step, not lumped into
        # whichever call the probe happens to sample
        step_times = _diag.observe_step_time(dt)
        k = self._metrics_every_k
        if k and (self._calls == 1 or self._calls % k == 0):
            with _tracing.stage(self._trace, "consensus_probe",
                                cat="train", step=self._calls):
                _diag.diagnose_consensus(out[0], step_times=step_times)
                # async-gossip states carry their staleness depth in the
                # step output — a pure host read, no extra collective or
                # compile
                if len(out) > 1:
                    _diag.observe_async_staleness(out[1])
        if self._calls >= self._warmup:
            size = self._jit_cache_len()
            if (_metrics.in_steady_state() and size is not None
                    and self._jit_cache_baseline is not None
                    and size > self._jit_cache_baseline):
                _metrics.note_retrace(
                    f"jit cache grew {self._jit_cache_baseline} -> {size}")
            self._jit_cache_baseline = size
            _metrics.mark_steady_state(True)
        _metrics.sample(step=self._calls)
        return out


def _default_metrics_every_k(metrics_every_k, strategy):
    """An armed fleet view (``BLUEFOG_FLEET_EVERY`` / ``fleetview.arm``)
    declares a probe cadence; a step built without an explicit
    ``metrics_every_k`` inherits it so the metric carrier actually
    gossips — only for rank-axis strategies, the ones the probe can run
    on."""
    if metrics_every_k is not None:
        return metrics_every_k
    from .utils import fleetview as _fleetview
    every = _fleetview.fleet_every()
    if every is not None and strategy.axes[:1] == ("rank",):
        return every
    return None


def _check_metrics_every_k(metrics_every_k, strategy):
    if metrics_every_k is None:
        return
    if metrics_every_k < 1:
        raise ValueError("metrics_every_k must be >= 1")
    if strategy.axes[:1] != ("rank",):
        raise ValueError(
            "metrics_every_k requires a strategy that gossips over the "
            "rank axis (axes[0] == 'rank'); the consensus probe runs over "
            "the 1-D mesh — call diagnose_consensus manually for "
            "hierarchical strategies")


def _check_overlap(overlap, strategy):
    if overlap and not strategy.pipelined:
        raise ValueError(
            "overlap=True requires a pipelined strategy whose comm_state "
            "carries one-step-delayed mixed params — build one with "
            "adapt_with_combine(..., delayed=True) (or "
            "DistributedAdaptWithCombineOptimizer(..., delayed=True)). "
            "With a bulk-synchronous strategy the adapt waits on the "
            "gossip, so there is nothing for the scheduler to overlap.")


def make_train_step(
    grad_fn: Callable[[Any, Any], Tuple[jax.Array, Any]],
    strategy: DecentralizedOptimizer,
    *,
    steps_per_call: int = 1,
    reuse_batch: bool = False,
    donate: bool = True,
    overlap: bool = False,
    metrics_every_k: Optional[int] = None,
    metrics_warmup: int = 2,
    mesh: Optional[Mesh] = None,
    in_spec: Optional[P] = None,
    check_vma: bool = True,
):
    """Build the jitted SPMD training step over the context mesh.

    ``grad_fn(params, batch) -> (loss, grads)`` is a per-rank pure function.
    The returned function maps distributed pytrees
    ``(params, state, batch) -> (new_params, new_state, loss)`` with every
    leaf carrying the leading rank axis.

    ``steps_per_call > 1`` runs that many optimizer steps inside ONE compiled
    program via ``lax.scan`` — batch leaves then carry an extra steps axis
    after the rank axis (``[n, steps, ...]``) and the returned loss is
    ``[n, steps]``.  This is the TPU-idiomatic training loop: one dispatch
    per scan amortizes host overhead and lets XLA overlap the gossip
    collectives of step t with the compute of step t+1 (the role the
    reference's background thread + nonblocking ops play,
    ``operations.cc:453-520``).  Dynamic topologies keep rotating inside
    the fused body: the communicator's ``lax.switch`` dispatches on the
    step counter carried in ``state``, which advances every scan iteration.

    ``reuse_batch=True`` (requires ``steps_per_call > 1``) feeds the SAME
    batch to every step of the fused loop instead of slicing a steps axis:
    batch leaves stay ``[n, ...]``, so a k-step call costs no k-fold batch
    replication in HBM or on the host->device path.  This is the right
    mode whenever the data loader is not the object under test.

    ``donate=False`` disables buffer donation for callers that must keep
    reading the pre-step params/state after the call; by default both are
    donated (:data:`TRAIN_STEP_DONATE_ARGNUMS`) so XLA updates them in
    place instead of round-tripping fresh HBM allocations.

    ``metrics_every_k=k`` samples the consensus-health probes
    (:mod:`bluefog_tpu.diagnostics`) every k-th call, on the step's output
    params — compatible with donation, and compiled during warmup so
    steady state sees zero extra compilations.  ``metrics_warmup`` is the
    call count after which the retrace sentinel arms (every builder call
    always feeds step-time/flag metrics; the registry is cheap).

    ``overlap=True`` declares the pipelined execution mode: it requires a
    strategy built with ``delayed=True`` (``strategy.pipelined``), whose
    in-flight mixed params ride the donated state carry — through the fused
    ``lax.scan`` as well — so each step's permute chain is data-independent
    of its update dot-generals and the latency-hiding scheduler can bury
    the gossip under compute.  The flag is surfaced in the metrics registry
    (``bluefog_step_overlap``) and validated here rather than inferred, so
    a bulk-synchronous strategy silently losing the overlap is impossible.

    ``mesh=``/``in_spec=`` override the context mesh for composed
    parallelism (:mod:`bluefog_tpu.parallel.compose` builds a 4-D
    gossip-DP x PP x TP x SP mesh and passes it here): every leaf still
    carries ONE leading device axis, collapsed over all mesh axes.
    ``check_vma=False`` opts the body out of replication checking — the
    composed LM gradient recipe relies on the legacy cotangent-sum psum
    transpose (see examples/llm_3d.py and tests/test_compose.py).
    """
    metrics_every_k = _default_metrics_every_k(metrics_every_k, strategy)
    _check_metrics_every_k(metrics_every_k, strategy)
    _check_overlap(overlap, strategy)
    if mesh is None:
        ctx = _mesh.get_context()
        mesh = ctx.mesh if strategy.axes == ("rank",) else ctx.mesh_2d
        spec = (P("rank") if strategy.axes == ("rank",)
                else P(("machine", "local")))
    else:
        spec = in_spec if in_spec is not None else P(tuple(mesh.axis_names))

    def grad3(p, ns, b):
        loss, grads = grad_fn(p, b)
        return loss, grads, ns

    inner = _stateful_per_rank(grad3, strategy, steps_per_call, lambda ns: ns,
                               reuse_batch=reuse_batch)

    def per_rank(params, state, batch):
        new_params, _, new_state, losses = inner(params, {}, state, batch)
        return new_params, new_state, losses

    # donate params/state: the update is functional but the caller always
    # rebinds both, so XLA can reuse their buffers in place (halves peak
    # parameter memory for large models)
    step = jax.jit(
        jax.shard_map(per_rank, mesh=mesh, in_specs=(spec, spec, spec),
                      out_specs=(spec, spec, spec), check_vma=check_vma),
        donate_argnums=TRAIN_STEP_DONATE_ARGNUMS if donate else ())
    return _InstrumentedStep(
        step, steps_per_call=steps_per_call, donated=donate, overlap=overlap,
        metrics_every_k=metrics_every_k, warmup=metrics_warmup)


def _stateful_per_rank(grad_fn, strategy, steps_per_call, sync,
                       reuse_batch=False):
    """Shared per-rank step body: slice off the rank axis, scan
    (grad -> state sync -> strategy update), re-stack.  ``grad_fn(p, ns, b)
    -> (loss, grads, new_ns)``; ``sync`` post-processes the net state.
    ``reuse_batch``: scan over nothing (``xs=None``) and close over one
    steps-axis-free batch instead of slicing ``batch[t]`` each step."""
    if reuse_batch and steps_per_call == 1:
        raise ValueError("reuse_batch requires steps_per_call > 1 (a single "
                         "step has no steps axis to elide)")

    def per_rank(params, net_state, dstate, batch):
        params, net_state, dstate, batch = jax.tree.map(
            lambda x: x[0], (params, net_state, dstate, batch))

        def one(p, ns, s, b):
            with named_span("GRADIENT"):
                loss, grads, ns = grad_fn(p, ns, b)
            ns = sync(ns)
            p, s = strategy.update(grads, s, p)
            return p, ns, s, loss

        if steps_per_call == 1:
            out = one(params, net_state, dstate, batch)
            return jax.tree.map(lambda x: x[None], out)

        def body(carry, b):
            p, ns, s = carry
            p, ns, s, loss = one(p, ns, s, batch if reuse_batch else b)
            return (p, ns, s), loss

        (params, net_state, dstate), losses = lax.scan(
            body, (params, net_state, dstate),
            None if reuse_batch else batch, length=steps_per_call)
        return jax.tree.map(
            lambda x: x[None], (params, net_state, dstate, losses))

    return per_rank


def make_stateful_train_step(
    grad_fn: Callable[[Any, Any, Any], Tuple[jax.Array, Any, Any]],
    strategy: DecentralizedOptimizer,
    *,
    steps_per_call: int = 1,
    reuse_batch: bool = False,
    donate: bool = True,
    overlap: bool = False,
    state_sync: Optional[str] = None,
    state_sync_schedule: Optional[CommSchedule] = None,
    metrics_every_k: Optional[int] = None,
    metrics_warmup: int = 2,
):
    """:func:`make_train_step` for networks with non-parameter state (BN
    running stats, EMA shadows — haiku's ``transform_with_state``, flax's
    ``batch_stats`` collection).

    ``grad_fn(params, net_state, batch) -> (loss, grads, new_net_state)``.
    The returned step maps ``(params, net_state, dstate, batch) ->
    (new_params, new_net_state, new_dstate, loss)``.

    ``state_sync`` keeps the per-rank state from drifting apart the way the
    reference's per-rank BN buffers do (its broadcast only syncs at restart):
    ``None`` leaves state rank-local (reference behavior), ``"neighbor"``
    gossips it over the topology each step (``state_sync_schedule``
    overrides the context schedule), ``"allreduce"`` globally averages it.
    Integer leaves (counters) are never averaged.  Syncing requires a
    rank-axis strategy (1-D mesh).

    ``steps_per_call``, ``reuse_batch``, ``donate``, ``overlap``,
    ``metrics_every_k``, and ``metrics_warmup`` behave exactly as in
    :func:`make_train_step` (donation here covers params, net state, and
    optimizer state — :data:`STATEFUL_TRAIN_STEP_DONATE_ARGNUMS`).
    """
    metrics_every_k = _default_metrics_every_k(metrics_every_k, strategy)
    _check_metrics_every_k(metrics_every_k, strategy)
    _check_overlap(overlap, strategy)
    ctx = _mesh.get_context()
    mesh = ctx.mesh if strategy.axes == ("rank",) else ctx.mesh_2d
    spec = P("rank") if strategy.axes == ("rank",) else P(("machine", "local"))

    if state_sync not in (None, "neighbor", "allreduce"):
        raise ValueError(f"unknown state_sync {state_sync!r}")
    if state_sync_schedule is not None and state_sync != "neighbor":
        raise ValueError(
            "state_sync_schedule only applies to state_sync='neighbor'")
    if state_sync is not None and strategy.axes != ("rank",):
        raise ValueError(
            "state_sync requires a rank-axis strategy; sync net state "
            "manually for hierarchical (2-D mesh) strategies")

    def sync(ns):
        if state_sync is None:
            return ns
        s = (state_sync_schedule if state_sync_schedule is not None
             else _mesh.static_schedule())

        def leaf(x):
            if not jnp.issubdtype(x.dtype, jnp.floating):
                return x
            if state_sync == "neighbor":
                return ops.neighbor_allreduce(x, s, axis="rank")
            return lax.pmean(x, "rank")

        with named_span("STATE_SYNC"):
            return jax.tree.map(leaf, ns)

    inner = _stateful_per_rank(grad_fn, strategy, steps_per_call, sync,
                               reuse_batch=reuse_batch)
    step = jax.jit(
        jax.shard_map(inner, mesh=mesh, in_specs=(spec,) * 4,
                      out_specs=(spec,) * 4),
        donate_argnums=STATEFUL_TRAIN_STEP_DONATE_ARGNUMS if donate else ())
    return _InstrumentedStep(
        step, steps_per_call=steps_per_call, donated=donate, overlap=overlap,
        metrics_every_k=metrics_every_k, warmup=metrics_warmup)
