"""Tier-1 evidence: compiled-HLO wire bytes + spectral-gap consensus.

The cost model never guesses bytes from shapes: each compile group (one
per ``(algorithm, topology, wire, weights)`` — the knobs that change what
crosses the wire) is lowered through ``shard_map`` on the *current*
backend and the bytes are counted from the compiled program by
:func:`bluefog_tpu.utils.hlo_bytes.wire_stats`.  Scoring is pure
arithmetic on those
bytes: no wall clock, no RNG, so the same inputs always produce the same
plan (pinned by tests).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np

from ..parallel import context as _mesh
from ..utils.hlo_bytes import wire_stats
from .. import topology as topo_util
from .candidates import Candidate, CarvingCandidate, schedule_for

# Pseudo-cost constants (seconds).  These are NOT measurements — they are a
# fixed, documented preference order: bytes dominate, each sequential gossip
# round adds latency, each host dispatch adds overhead amortized by fused-k.
# Tier-2/3 measured seconds override the pseudo-seconds wholesale.
_BYTES_PER_SEC = 4.0e10          # ICI-class link, order-of-magnitude
_DCN_BYTES_PER_SEC = 2.5e9       # cross-slice (DCN-class) link — the ~16x
                                 # gap is why carvings are ranked DCN-first
_ROUND_LATENCY_S = 2.0e-6        # per sequential permute round
_DISPATCH_S = 50.0e-6            # per host->device call, / fused_k
_EXPOSED_WHEN_DELAYED = 0.25     # fraction of comm left exposed when the
                                 # one-step-delayed pipeline hides the rest


def probe_compiled(strategy, params, n: int):
    """Compile the strategy's update (zero grads) under ``shard_map`` on the
    context mesh and return the compiled executable.

    Cached through the context's AOT program cache keyed by the caller's
    group key + the param-tree structure, so re-tuning in one process never
    re-lowers a group.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..optimizers import init_distributed, replicate

    mesh = _mesh.get_context().mesh
    dist_params = replicate(params, n)
    dist_state = init_distributed(strategy, dist_params)

    def per_rank(p, s):
        p, s = jax.tree.map(lambda t: t[0], (p, s))
        grads = jax.tree.map(jnp.zeros_like, p)
        new_p, new_s = strategy.update(grads, s, p)
        return jax.tree.map(lambda t: t[None], (new_p, new_s))

    fn = jax.jit(jax.shard_map(
        per_rank, mesh=mesh, in_specs=(P("rank"),) * 2,
        out_specs=(P("rank"),) * 2))
    return fn.lower(dist_params, dist_state).compile()


def _params_struct_key(params) -> tuple:
    import jax
    return tuple((tuple(x.shape), str(x.dtype))
                 for x in jax.tree.leaves(params))


def group_wire_bytes(cand: Candidate, params, n: int,
                     opt_factory) -> Tuple[Dict[str, int], int]:
    """``(collective counts, per-step wire bytes per chip)`` for the
    candidate's compile group, from a real compile on the current backend.

    Probes at ``fused_k=1`` / ``delayed=False`` / default emission — the
    group members only rescale or rearrange that program, never change its
    payloads — with the schedule passed explicitly so probing never mutates
    the process context.  Raises whatever the compile raises; the tuner
    converts that into a rejection with reason.
    """
    from ..optimizers import STRATEGIES

    sched = schedule_for(cand.topology, cand.weights, n)
    strategy = STRATEGIES[cand.algorithm].build(
        opt_factory(), schedule=sched, wire=cand.wire, concurrent=None,
        delayed=False, num_steps_per_communication=1)

    def build():
        return probe_compiled(strategy, params, n)

    compiled = _mesh.cached_program(
        ("autotune-probe", cand.compile_group, n,
         _params_struct_key(params)), build)
    counts, bytes_ = wire_stats(compiled.as_text())
    return counts, int(sum(bytes_.values()))


def consensus_gap(cand: Candidate) -> float:
    """Consensus contraction rate of the candidate's mixing step.

    ``allreduce`` averages exactly (gap 1.0); gossip candidates take
    :func:`bluefog_tpu.topology.spectral_gap` of the topology's built-in
    (doubly-stochastic) weights — the graph governs the consensus rate for
    the push family too, since their de-biased iterate contracts on the
    same graph.
    """
    if cand.topology is None:
        return 1.0
    return topo_util.spectral_gap(
        topo_util.topology_from_spec(cand.topology))


def predicted_step_time_s(cand: Candidate, bytes_per_step: int,
                          num_rounds: int) -> float:
    """Analytic pseudo-seconds per optimizer step (tier-1 fallback)."""
    comm = bytes_per_step / _BYTES_PER_SEC
    rounds = 1 if cand.concurrent else max(num_rounds, 1)
    lat = rounds * _ROUND_LATENCY_S if bytes_per_step else 0.0
    if cand.delayed:
        comm, lat = (comm * _EXPOSED_WHEN_DELAYED,
                     lat * _EXPOSED_WHEN_DELAYED)
    return comm + lat + _DISPATCH_S / max(cand.fused_k, 1)


def objective_score(objective, step_time_s: float, gap: float,
                    bytes_per_step: int) -> float:
    """Lower-is-better score under the requested objective.

    ``"step_time"`` ranks by (predicted or measured) seconds;
    ``"consensus_per_byte"`` ranks by wire bytes paid per unit of
    consensus contraction (allreduce pays full payload for gap 1.0, a
    sparse gossip graph pays less for a smaller gap — the frontier
    ``tools/gossip_bench.py --frontier`` grades); a dict blends the two
    with the given weights, each term in its own units (documented, not
    normalized — the blend is a preference order, not a physical sum).
    """
    per_byte = (bytes_per_step + 1.0) / max(gap, 1e-9)
    if objective == "step_time":
        return step_time_s
    if objective == "consensus_per_byte":
        return per_byte
    if isinstance(objective, dict):
        unknown = set(objective) - {"step_time", "consensus_per_byte"}
        if unknown:
            raise ValueError(f"unknown objective terms {sorted(unknown)}")
        return (float(objective.get("step_time", 0.0)) * step_time_s
                + float(objective.get("consensus_per_byte", 0.0))
                * per_byte)
    raise ValueError(
        f"unknown objective {objective!r}: 'step_time', "
        "'consensus_per_byte', or a weight dict over those")


def carving_wire_bytes(carve: CarvingCandidate, cfg, *,
                       wire: Optional[str] = None,
                       remat: bool = False) -> dict:
    """ICI-vs-DCN byte attribution for one 5-axis carving, from a real
    AOT lowering of one full optimizer step (never a shape guess).

    Composes the carving, builds the LM step — the routed-MoE one when
    ``cfg`` is a :class:`~bluefog_tpu.moe.MoELMConfig`, the dense one
    otherwise — lowers it, and splits the pre-optimization StableHLO's
    collective bytes by slice with
    :func:`~bluefog_tpu.utils.hlo_bytes.stablehlo_wire_stats`.  The
    model contract
    (``cfg.validate``) and the carving contract both raise here; the
    carving tuner converts that into an audited rejection.  The process
    context's active carving is restored on exit."""
    import jax
    import optax

    from .. import optimizers as bfopt
    from ..parallel import compose
    from ..utils.hlo_bytes import stablehlo_wire_stats

    carve_kw = {}
    num_experts = getattr(cfg, "num_experts", None)
    is_moe = num_experts is not None
    if is_moe:
        carve_kw = {"num_experts": num_experts,
                    "capacity_factor": cfg.capacity_factor}
    prior = _mesh.get_compose()
    try:
        m = compose.compose_parallelism(
            carve.dp, carve.pp, carve.tp, carve.sp, carve.ep, wire=wire,
            **carve_kw)
        cfg.validate(m)
        if is_moe:
            from .. import moe as bfmoe
            grad_fn = bfmoe.make_moe_grad_fn(cfg, m, remat=remat)
            params = bfmoe.init_moe_params(cfg, m)
            toks = bfmoe.make_moe_batch(cfg, m)
        else:
            grad_fn = compose.make_lm_grad_fn(cfg, m, remat=remat)
            params = compose.init_lm_params(cfg, m)
            toks = compose.make_lm_batch(cfg, m)
        step, strategy = compose.make_train_step(
            m, grad_fn, optax.sgd(0.05), delayed=True)
        state = bfopt.init_distributed(strategy, params)
        shlo = step.lower(params, state, toks).as_text()
        stats = stablehlo_wire_stats(shlo, m.slice_size)
        stats["slice_size"] = m.slice_size
        return stats
    finally:
        _mesh.set_compose(prior)


def predicted_carving_step_time_s(stats: dict) -> float:
    """Analytic pseudo-seconds for a carving's per-step wire bill: DCN
    bytes at DCN speed + ICI bytes at ICI speed.  Same caveat as the
    strategy constants above — a documented preference order (DCN bytes
    dominate), not a measurement."""
    return (stats["dcn_bytes"] / _DCN_BYTES_PER_SEC
            + stats["ici_bytes"] / _BYTES_PER_SEC)


def num_schedule_rounds(cand: Candidate, n: int) -> int:
    """Sequential permute rounds the candidate's schedule executes."""
    if cand.topology is None or cand.weights is None:
        return 0
    sched = schedule_for(cand.topology, cand.weights, n)
    return int(np.asarray(len(sched.rounds)))
