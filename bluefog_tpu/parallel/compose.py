"""Composed parallelism: gossip-DP x pipeline x tensor x Ulysses on ONE mesh.

This is the production-shape carving ROADMAP item 4 names: the device mesh
is split into five axes

* ``rank``  — gossip data parallelism.  Each device neighbor-averages its
  full local parameter tree with its same-(stage, tp, sp) peers across DP
  replicas; the gossip graph lives over DP *leaders* only, so with the DP
  axis outermost (slice-major on multislice hardware) every gossip permute
  rides the DCN hop while the other three axes stay intra-slice.
* ``stage`` — GPipe pipeline parallelism (:func:`..pipeline.pipeline_apply`:
  activations ``ppermute`` stage to stage, ``jax.grad`` through the
  schedule IS the backward pipeline).
* ``tp``    — Megatron tensor parallelism inside every decoder block
  (column-split qkv/up, row-split out/down, one ``psum`` per sublayer).
* ``sp``    — Ulysses sequence parallelism (:func:`..ops.ulysses_attention`:
  two ``all_to_all``s re-shard heads <-> sequence around local attention).
* ``expert`` — expert parallelism for routed MoE (``ep``, 1 by default):
  capacity-based dispatch/combine ``all_to_all``s
  (:mod:`..parallel.expert`) shard the experts of the routed LM in
  :mod:`bluefog_tpu.moe`; like pp/tp/sp it is intra-slice by construction.

:func:`compose_parallelism` validates the carving eagerly (sizes must
multiply to the mesh size, the wire codec applies to gossip permutes only,
the DP topology must have exactly ``dp`` nodes) and returns a
:class:`Mesh3D`.  :func:`make_train_step` then wires the carving through
the full step machinery so buffer donation, ``adapt_with_combine(
delayed=True)`` pipelined gossip, fused ``steps_per_call``, and the
retrace sentinel all survive composition — the returned step is the same
:class:`~bluefog_tpu.optimizers._InstrumentedStep` a 1-D run gets.

The module also ships the reference composed LM (:class:`LMConfig`,
:func:`init_lm_params`, :func:`make_lm_grad_fn`) used by the benchmark's
composed-LM cells (perfbench/families/composed_lm.py), chip_smoke.py,
examples/llm_3d.py, and the compose test oracles.  Its gradient recipe is
the one tests/test_compose.py pins: NO loss-side collective inside AD —
the loss is masked to the last stage and seeded once (``1/TP``), the
structural row-parallel psums transpose as cotangent sums under the legacy
(``check_vma=False``) semantics, and shared-parameter grads are psum'd
over (stage, tp) outside AD.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import networkx as nx
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from .. import topology as topo_util
from ..models import decoder
from ..schedule import CommSchedule, compile_topology
from . import context as _ctx
from .pipeline import pipeline_apply

AXES: Tuple[str, str, str, str, str] = ("rank", "stage", "tp", "sp",
                                        "expert")

__all__ = [
    "AXES", "Mesh3D", "compose_parallelism", "make_train_step",
    "LMConfig", "init_lm_params", "make_lm_grad_fn", "make_lm_batch",
    "device_put",
]


@dataclasses.dataclass(frozen=True)
class Mesh3D:
    """A validated 5-axis carving of the device mesh.

    ``mesh`` has axes ``("rank", "stage", "tp", "sp", "expert")`` with the
    gossip-DP axis outermost; ``topology``/``schedule`` describe the gossip
    graph over the ``dp`` DP leaders (NOT over all ranks — that is the
    point); ``wire`` is the optional codec gossip bytes travel in on the
    wire.  The ``expert`` axis (``ep``, innermost, 1 by default) shards
    routed-MoE experts: its all_to_alls stay intra-slice by construction —
    see :mod:`bluefog_tpu.moe`.  ``num_experts``/``capacity_factor`` are
    carried as carving metadata so tools (autotune, flight bundles) see
    the MoE shape alongside the mesh shape.
    """
    mesh: Mesh
    dp: int
    pp: int
    tp: int
    sp: int
    topology: nx.DiGraph
    is_weighted: bool
    schedule: CommSchedule
    wire: Optional[str] = None
    ep: int = 1
    num_experts: Optional[int] = None
    capacity_factor: Optional[float] = None

    @property
    def size(self) -> int:
        return self.dp * self.pp * self.tp * self.sp * self.ep

    @property
    def slice_size(self) -> int:
        """Devices per DP replica — everything inside is intra-slice."""
        return self.pp * self.tp * self.sp * self.ep

    @property
    def spec(self) -> P:
        """One leading device axis collapsed over all five mesh axes."""
        return P(AXES)

    def leader_degree(self) -> int:
        """Max out-degree (self-loops excluded) of the DP gossip graph —
        the per-step cross-slice permute count per chip."""
        return max(
            sum(1 for v in self.topology.successors(u) if v != u)
            for u in self.topology.nodes)

    def effective_mixing(self) -> np.ndarray:
        """Mixing matrix over ALL ranks: ``W_dp (x) I_slice`` — every
        (stage, tp, sp) coordinate runs an independent consensus over the
        DP axis (contrast hierarchical gossip's ``W (x) J/L``)."""
        W = topo_util.to_weight_matrix(self.topology)
        return topo_util.compose_two_level(W, np.eye(self.slice_size))

    def spectral_gap(self) -> float:
        """Consensus contraction rate — equals the DP graph's own gap
        (kron with the identity only replicates the spectrum)."""
        return topo_util.spectral_gap(
            topo_util.to_weight_matrix(self.topology))

    def describe(self) -> dict:
        """JSON-ready summary for plans and flight bundles."""
        return {
            "dp": self.dp, "pp": self.pp, "tp": self.tp, "sp": self.sp,
            "ep": self.ep, "num_experts": self.num_experts,
            "capacity_factor": self.capacity_factor,
            "n_chips": self.size,
            "topology": self.topology.graph.get(
                "name", f"digraph<{self.topology.number_of_nodes()}>"),
            "leader_degree": self.leader_degree(),
            "gossip_rounds": self.schedule.num_rounds,
            "wire": self.wire,
            "spectral_gap": round(self.spectral_gap(), 6),
        }


def compose_parallelism(
    dp: int,
    pp: int = 1,
    tp: int = 1,
    sp: int = 1,
    ep: int = 1,
    *,
    num_experts: Optional[int] = None,
    capacity_factor: Optional[float] = None,
    devices: Optional[Any] = None,
    topology: Union[nx.DiGraph, Callable[[int], nx.DiGraph], None] = None,
    weighted: bool = True,
    wire: Optional[str] = None,
) -> Mesh3D:
    """Carve the device mesh into (gossip-DP, PP, TP, SP, EP), validated.

    Args:
      dp, pp, tp, sp, ep: axis sizes; their product must equal the device
        count exactly (pass ``devices=`` to carve a sub-mesh).  ``ep``
        shards routed-MoE experts (``bluefog_tpu.moe``) and stays
        intra-slice: the slice-major device sort keeps gossip-DP outermost.
      num_experts: total routed experts in the model this carving will run.
        Required when ``ep > 1`` (each expert-parallel peer owns
        ``num_experts // ep`` experts, so ``num_experts % ep == 0``);
        optional metadata otherwise.
      capacity_factor: expert capacity factor metadata, surfaced by
        ``describe()`` (the model config holds the
        operative value — see ``moe.MoELMConfig``).
      devices: explicit device list; defaults to the context's devices
        (``bf.init`` order) or ``jax.devices()``.  On multislice hardware
        devices are re-ordered slice-major so the DP axis — the only one
        gossip crosses — spans the DCN hop.
      topology: the gossip graph over the ``dp`` DP leaders: an
        ``nx.DiGraph`` with exactly ``dp`` nodes, or a callable
        ``f(dp) -> DiGraph`` (e.g. ``topology.ExponentialTwoGraph`` or a
        ``lambda d: TwoLevelGraph(...)`` when the DP axis itself spans a
        machine hierarchy).  Default: ``ExponentialTwoGraph(dp)``.
      weighted: compile the graph's own mixing weights (vs the reference's
        uniform ``1/(in_degree+1)``).
      wire: DCN wire codec for the gossip permutes ONLY (``"bf16"``,
        ``"fp8"``, ``"fp8@64"``, ... — see ``ops.collectives``).
        PP/TP/SP/EP collectives are intra-slice and never compressed.
        Requires ``dp > 1``: with a single replica there is no gossip edge
        to compress, so a codec would silently grade nothing.
    """
    for name, v in (("dp", dp), ("pp", pp), ("tp", tp), ("sp", sp),
                    ("ep", ep)):
        if not isinstance(v, (int, np.integer)) or v < 1:
            raise ValueError(f"axis size {name}={v!r} must be a positive int")
    n = dp * pp * tp * sp * ep
    if num_experts is not None and (
            not isinstance(num_experts, (int, np.integer))
            or num_experts < 1):
        raise ValueError(
            f"num_experts={num_experts!r} must be a positive int")
    if ep > 1:
        if num_experts is None:
            raise ValueError(
                f"ep={ep} carves an expert-parallel axis but num_experts "
                "was not given; each expert peer owns num_experts // ep "
                "experts, so the carving contract needs the total")
        if num_experts % ep:
            raise ValueError(
                f"num_experts ({num_experts}) % ep ({ep}) != 0: each "
                "expert-parallel peer owns a contiguous block of "
                "num_experts // ep experts")
    if capacity_factor is not None and not (
            isinstance(capacity_factor, (int, float, np.floating))
            and float(capacity_factor) > 0):
        raise ValueError(
            f"capacity_factor={capacity_factor!r} must be a positive number")

    if devices is None:
        devices = list(np.ravel(_ctx.devices())) if _ctx.is_initialized() \
            else jax.devices()
    devices = list(np.ravel(np.asarray(devices, dtype=object)))
    if len(devices) != n:
        raise ValueError(
            f"carving dp*pp*tp*sp*ep = {dp}*{pp}*{tp}*{sp}*{ep} = {n} does "
            f"not match the device count ({len(devices)}); every chip must "
            "belong to exactly one (replica, stage, tp, sp, expert) "
            "coordinate — pass devices= to carve a sub-mesh")
    # slice-major order: gossip (the only DCN-crossing axis) gets the
    # outermost position, so cross-slice traffic is exactly the DP permutes
    devices.sort(key=lambda d: (getattr(d, "slice_index", 0) or 0,
                                getattr(d, "id", 0)))

    if wire is not None:
        from ..ops import collectives as _coll
        _coll._check_wire(wire)       # eager: fail at carve, not at trace
        if dp == 1:
            raise ValueError(
                "wire codec applies to gossip permutes only; a dp=1 "
                "carving has no gossip edges to compress")

    if topology is None:
        topo = topo_util.ExponentialTwoGraph(dp) if dp > 1 \
            else topo_util.FullyConnectedGraph(1)
    elif callable(topology):
        topo = topology(dp)
    else:
        topo = topology
    if topo.number_of_nodes() != dp:
        raise ValueError(
            f"gossip topology has {topo.number_of_nodes()} nodes but the "
            f"DP axis has {dp} leaders; the gossip graph lives over DP "
            "replicas only (PP/TP/SP peers hold different shards and must "
            "not be mixed)")

    mesh = Mesh(
        np.asarray(devices, dtype=object).reshape(dp, pp, tp, sp, ep),
        AXES)
    m = Mesh3D(mesh=mesh, dp=dp, pp=pp, tp=tp, sp=sp, ep=ep, topology=topo,
               is_weighted=weighted,
               schedule=compile_topology(topo, weighted), wire=wire,
               num_experts=num_experts,
               capacity_factor=(None if capacity_factor is None
                                else float(capacity_factor)))
    if _ctx.is_initialized():
        _ctx.set_compose(m)
    return m


def make_train_step(
    m: Mesh3D,
    grad_fn: Callable[[Any, Any], Tuple[jax.Array, Any]],
    opt,
    *,
    delayed: bool = True,
    steps_per_call: int = 1,
    reuse_batch: bool = False,
    donate: bool = True,
    fuse: bool = True,
    concurrent: Optional[bool] = None,
    metrics_every_k: Optional[int] = None,
    metrics_warmup: int = 2,
    check_vma: bool = False,
):
    """Wire a composed carving through the full step machinery.

    Builds ``neighbor_communicator(schedule, axis="rank", wire=...)`` over
    the DP axis, wraps ``opt`` in ``adapt_with_combine(delayed=...)``
    (``delayed=True`` = pipelined gossip: the permute chain of step t is
    data-independent of its adapt, so the scheduler buries DCN latency
    under PP/TP/SP compute), and hands both to
    :func:`bluefog_tpu.optimizers.make_train_step` with the 4-D mesh —
    donation, fused ``steps_per_call``, chaos/flight instrumentation, and
    the retrace sentinel are inherited unchanged.

    ``grad_fn(params, batch) -> (loss, grads)`` runs per-device inside the
    4-axis shard_map body (see :func:`make_lm_grad_fn` for the reference
    LM).  ``check_vma`` defaults to False because the composed gradient
    recipe pins the legacy psum-transpose semantics.

    Returns ``(step, strategy)`` — the strategy is needed for
    ``init_distributed(strategy, params)``.
    """
    from .. import optimizers as bfopt
    comm = bfopt.neighbor_communicator(
        m.schedule, axis="rank", fuse=fuse, wire=m.wire,
        concurrent=concurrent)
    strategy = bfopt.adapt_with_combine(opt, comm, delayed=delayed,
                                        axes=AXES)
    step = bfopt.make_train_step(
        grad_fn, strategy, steps_per_call=steps_per_call,
        reuse_batch=reuse_batch, donate=donate, overlap=delayed,
        metrics_every_k=metrics_every_k, metrics_warmup=metrics_warmup,
        mesh=m.mesh, in_spec=m.spec, check_vma=check_vma)
    return step, strategy


def device_put(m: Mesh3D, tree: Any) -> Any:
    """Place a ``[n, ...]``-stacked pytree onto the carving's mesh."""
    sharding = NamedSharding(m.mesh, m.spec)
    return jax.tree.map(lambda x: jax.device_put(x, sharding), tree)


# ---------------------------------------------------------------------------
# The reference composed LM: decoder blocks with TP inside, pipelined over
# stages, Ulysses over sp, gossip-DP over replicas.  Shared by the
# benchmark's composed-LM cells, examples/llm_3d.py, and the compose oracles.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LMConfig:
    """Shape of the composed decoder-only LM (a copy-task trainer: predict
    the token ``lag`` positions back, the proof that gradients flow through
    every stage boundary, tp psum, sp all_to_all, and the gossip at once).
    """
    vocab: int = 64
    d_model: int = 32
    heads: int = 4
    layers: int = 4          # total decoder blocks, layers % pp == 0
    seq_len: int = 32        # GLOBAL sequence length, seq_len % sp == 0
    micro: int = 4           # microbatches per step (pipeline fill)
    batch: int = 2           # per-microbatch batch size
    lag: int = 2             # copy-task lag (within the local sp shard)
    ffn_mult: int = 4

    def validate(self, m: Mesh3D) -> None:
        D, H = self.d_model, self.heads
        if self.layers % m.pp:
            raise ValueError(f"layers ({self.layers}) % pp ({m.pp}) != 0")
        if D % H:
            raise ValueError(f"d_model ({D}) % heads ({H}) != 0")
        if (D // H) % 2:
            raise ValueError(f"head_dim ({D // H}) must be even for rope")
        if H % m.tp:
            raise ValueError(f"heads ({H}) % tp ({m.tp}) != 0")
        if (H // m.tp) % m.sp:
            raise ValueError(
                f"local heads ({H // m.tp}) % sp ({m.sp}) != 0: ulysses "
                "scatters this tp rank's heads across the sp axis")
        if self.seq_len % m.sp:
            raise ValueError(f"seq_len ({self.seq_len}) % sp ({m.sp}) != 0")
        if self.seq_len // m.sp <= self.lag:
            raise ValueError("local sequence shorter than the copy lag")

    @property
    def n_params(self) -> int:
        """Dense (un-sharded) parameter count."""
        return (self.layers * decoder.block_param_count(self)
                + 2 * self.vocab * self.d_model)


@dataclasses.dataclass(frozen=True)
class DraftCarve:
    """A truncated-stage draft sub-model carved from a served carving.

    Self-speculative decoding drafts with the FIRST ``stages`` pipeline
    stages of the very model being served (early-exit: run stages
    ``0 .. stages-1``, then the shared LN + head directly on that
    activation).  No extra weights exist anywhere — the draft is a
    prefix of the target's own pipeline cycle on the same mesh, which is
    what makes its early-layer KV writes bit-identical to the target's
    and lets the verify pass reuse them.  The carve is pure metadata:
    the engine uses it to size the truncated ``ppermute`` cycle, and
    ``cost_fraction`` prices a draft token against a target token.
    """
    stages: int            # pipeline stages the draft runs (1 .. pp)
    pp: int                # target pipeline depth it was carved from
    layers: int            # decoder blocks the draft runs
    total_layers: int      # decoder blocks in the target
    n_params: int          # dense draft params (blocks run + embed/head)
    target_params: int     # dense target params

    @property
    def logit_stage(self) -> int:
        """Mesh stage holding the draft's final activation: the truncated
        cycle still ``ppermute``\\ s after every stage, so after ``stages``
        hops the activation sits at stage ``stages % pp`` (``0`` for the
        full cycle — the same stage the target reads logits from)."""
        return self.stages % self.pp

    @property
    def cost_fraction(self) -> float:
        """Draft-token FLOPs as a fraction of a target token's — the
        ``c`` in the Leviathan et al. speedup model ``(1 - a^(k+1)) /
        ((1 - a) (ck + 1))``."""
        return self.n_params / self.target_params

    def describe(self) -> dict:
        return {"stages": self.stages, "pp": self.pp,
                "layers": self.layers, "total_layers": self.total_layers,
                "cost_fraction": round(self.cost_fraction, 4)}


def draft_carve(m: Mesh3D, cfg: LMConfig, stages: int) -> DraftCarve:
    """Carve the truncated-stage draft for self-speculative decoding.

    ``stages`` counts pipeline stages off the front of the carving
    (``1 <= stages <= m.pp``; ``stages == m.pp`` is the degenerate
    identity draft — valid, every token accepted, no speedup).  The same
    sub-mesh discipline as the PR 9 trajectory oracle: nothing is
    resharded, the draft is a prefix of the already-compiled stage loop.
    """
    if not isinstance(stages, int) or not 1 <= stages <= m.pp:
        raise ValueError(f"draft stages={stages!r} must be an int in "
                         f"[1, pp={m.pp}]")
    cfg.validate(m)
    Lps = cfg.layers // m.pp
    draft_layers = stages * Lps
    return DraftCarve(
        stages=stages, pp=m.pp, layers=draft_layers,
        total_layers=cfg.layers,
        n_params=(draft_layers * decoder.block_param_count(cfg)
                  + 2 * cfg.vocab * cfg.d_model),
        target_params=cfg.n_params)


def init_lm_params(cfg: LMConfig, m: Mesh3D, seed: int = 0) -> Any:
    """Distributed LM params: every leaf stacked ``[n, ...]`` along the one
    collapsed device axis.  Device ``(r, s, t, u)`` holds the blocks of its
    (stage s, tp t) owner — identical across dp and sp, which gossip and
    the sp-pmean'd grads preserve — plus a full replica of the shared
    embed/head."""
    cfg.validate(m)
    rng = np.random.default_rng(seed)
    D, Lps, TP = cfg.d_model, cfg.layers // m.pp, m.tp

    def w(*shape, scale=0.1):
        return (rng.normal(size=shape) * scale).astype(np.float32)

    blocks = {k: w(m.pp, TP, Lps, *shape)   # [pp, tp, Lps, ...] owners
              for k, shape in decoder.block_param_shapes(cfg, TP).items()}
    shared = {"embed": w(cfg.vocab, D), "head": w(D, cfg.vocab)}

    # flat device i = (((r*pp + s)*tp + t)*sp + u)*ep + e
    r, s, t, u, e = np.unravel_index(np.arange(m.size),
                                     (m.dp, m.pp, m.tp, m.sp, m.ep))
    del r, u, e
    return {
        "blocks": {k: jnp.asarray(v[s, t]) for k, v in blocks.items()},
        "shared": {k: jnp.asarray(np.broadcast_to(v, (m.size,) + v.shape))
                   for k, v in shared.items()},
    }


def make_lm_batch(cfg: LMConfig, m: Mesh3D, seed: int = 0,
                  steps: Optional[int] = None) -> jax.Array:
    """Copy-task tokens stacked per device: ``[n, (steps,) micro, batch,
    seq_len/sp]``.  Each DP replica draws its own data; stage/tp copies
    inside a replica see identical tokens; sp shards slice the global
    sequence."""
    rng = np.random.default_rng(seed)
    shape = (m.dp, cfg.micro, cfg.batch, cfg.seq_len) if steps is None \
        else (m.dp, steps, cfg.micro, cfg.batch, cfg.seq_len)
    data = rng.integers(0, cfg.vocab, size=shape).astype(np.int32)
    Tl = cfg.seq_len // m.sp
    r, _, _, u, _ = np.unravel_index(np.arange(m.size),
                                     (m.dp, m.pp, m.tp, m.sp, m.ep))
    per_dev = np.stack([data[ri][..., ui * Tl:(ui + 1) * Tl]
                        for ri, ui in zip(r, u)])
    return jnp.asarray(per_dev)


def make_lm_grad_fn(cfg: LMConfig, m: Mesh3D, *, remat: bool = False,
                    use_pallas: bool = False):
    """Per-device ``grad_fn(params, toks) -> (loss, grads)`` for the
    composed LM, exact under the legacy (``check_vma=False``) psum
    transpose — the recipe tests/test_compose.py pins:

    * the loss is computed on every stage but masked to the LAST stage and
      seeded once with ``1/TP``; each structural row-parallel ``psum``
      transposes into the cotangent sum that both restores scale and
      aggregates the per-tp-rank input cotangents for the layer below;
    * shared embed/head grads are per-role partial sums -> one
      ``psum(("stage", "tp"))`` OUTSIDE AD;
    * sp shards are data-parallel over the sequence: all grads (and the
      loss) are ``pmean``'d over ``sp`` outside AD.
    """
    cfg.validate(m)
    from ..ops.ulysses import ulysses_attention

    Tl = cfg.seq_len // m.sp
    S, TP = m.pp, m.tp

    def attend(q, k, v):                            # [B, Tl, H/TP, Dh]
        with jax.named_scope("attn"):
            return ulysses_attention(
                q, k, v, axis="sp", causal=True, use_pallas=use_pallas,
                pallas_block_q=min(512, cfg.seq_len)), None

    def stage_fn(bp, x):
        # global rope positions: each sp shard rotates by its own offset,
        # so ulysses' gathered sequence is position-consistent
        positions = lax.axis_index("sp") * Tl + jnp.arange(Tl)
        y, _ = lax.scan(lambda c, lp: (decoder.decoder_block(
            cfg, TP, lp, c, positions, attend)[0], None), x, bp)
        return y

    def grad_fn(params, toks):
        sid = lax.axis_index("stage")

        def loss_fn(q):
            with jax.named_scope("readout"):
                x = q["shared"]["embed"][toks]      # [M, B, Tl, D]
            out = pipeline_apply(stage_fn, q["blocks"], x, axis="stage",
                                 remat=remat)
            with jax.named_scope("readout"):
                logits = decoder.lm_logits(q["shared"], out)
                targets = jnp.roll(toks, cfg.lag, axis=-1)
                # the mean cross-entropy over [:, :, lag:] (per sp shard),
                # written so that the float32 logits are the one array of
                # their size in HBM: the first `lag` positions weigh 0 (a
                # slice is a copy of all the logits) and the label's logit
                # is picked by a compare (take_along_axis comes back as a
                # scatter-add into a zeroed array of the logits' size), so
                # (softmax - onehot) fuses into both backward products
                lse = jax.nn.logsumexp(logits, axis=-1)
                hit = targets[..., None] == jnp.arange(cfg.vocab)
                ce = lse - jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
                loss = jnp.sum(jnp.where(jnp.arange(Tl) >= cfg.lag, ce, 0.0)
                               ) / (ce.size // Tl * (Tl - cfg.lag))
                return jnp.where(sid == S - 1, loss, 0.0) / TP

        loss, g = jax.value_and_grad(loss_fn)(params)
        loss = lax.psum(loss, ("stage", "tp"))
        g["shared"] = jax.tree.map(
            lambda v: lax.psum(v, ("stage", "tp")), g["shared"])
        if m.sp > 1:
            loss = lax.pmean(loss, "sp")
            g = jax.tree.map(lambda v: lax.pmean(v, "sp"), g)
        return loss, g

    return grad_fn
