"""Routed-MoE layer primitives: top-k router, expert FFN, dense oracle.

These are the per-device building blocks the reference MoE LM
(:mod:`.model`) runs inside the composed 5-axis shard_map.  They wrap the
capacity-based dispatch machinery of :mod:`..parallel.expert` with the
pieces a *trainable* MoE needs on top of raw dispatch:

* :func:`router_topk` — softmax router with top-k selection (k ∈ {1, 2});
  for k > 1 the kept gates are renormalized to sum to one (the classic
  mixture), for k = 1 the raw top probability is the gate (Switch).
* :func:`moe_ffn_routed` — one routed expert-FFN sublayer: router →
  choice-major fused dispatch (one all_to_all round trip for all k
  choices) → per-local-expert einsum with Megatron-TP row/column split →
  combine → gate-weighted sum, plus the auxiliary statistics the loss and
  the grading probe need (load-balance aux, router z, dropped fraction,
  token entropy, per-expert usage).
* :func:`moe_ffn_dense` — the dense-equivalent oracle: identical router
  and gating math, but every expert computed on every token and selected
  by mask — no expert axis, no all_to_all, no capacity.  With top-1
  routing and no dropped tokens the routed path must match this
  loss-for-loss to 1e-9 in float64 (tests/test_moe.py pins it).

Cross-device accounting (the part that makes ``ep=1`` and ``ep>1``
carvings bit-compatible): the load-balance loss is a *global* quantity —
``E * sum_e f_e * p_e`` over the whole batch — but under expert
parallelism each peer only sees its own batch shard.  The router stats
are therefore psum'd over the ``expert`` axis *inside* the layer
(``f_bar = psum(f_local / ep)``), and the model divides the aux term by
``ep`` in the per-device loss so the legacy psum-transpose (which
multiplies the replicated cotangent by the axis size) restores exactly
the global-batch router gradient.  See ``model.make_moe_grad_fn``.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..models.decoder import gated_ffn, relu2_ffn
from ..parallel.expert import moe_apply_dropless, moe_combine, moe_dispatch
from .dropless import grouped_ffn

__all__ = ["router_topk", "router_sigmoid_grouped", "router_softmax",
           "zero_expert_part", "held_expert_ffn",
           "held_expert_ffn_grouped", "held_moe_ffn", "EXPERT_FORMS",
           "router_expert_choice", "moe_ffn_routed",
           "moe_ffn_dropless", "moe_dropless_combine",
           "moe_ffn_expert_choice", "moe_ffn_dense", "moe_ffn_dense_ec"]


def router_topk(x: jax.Array, wr: jax.Array, *, top_k: int):
    """Softmax router: ``(logits, probs, topk_idx, topk_gate)``.

    ``x`` is ``[T, D]`` tokens, ``wr`` the ``[D, E]`` router weight
    (replicated over tp/sp/expert — every device routes its own tokens
    over ALL experts).  For ``top_k > 1`` the selected gates are
    renormalized to sum to one per token.
    """
    if top_k not in (1, 2):
        raise ValueError(f"top_k must be 1 or 2, got {top_k!r}")
    logits = x @ wr                                    # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = lax.top_k(probs, top_k)                # [T, k] each
    if top_k > 1:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    return logits, probs, idx, gate


def router_sigmoid_grouped(x: jax.Array, wr: jax.Array, *, top_k: int,
                           n_group: int, topk_group: int,
                           route_scale: float,
                           bias: jax.Array | None = None):
    """Sigmoid router with group-limited selection:
    ``(scores [T, E], idx [T, k], weight [T, k])``.

    Scores are ``sigmoid(x wr)`` in float32 at full matmul precision
    whatever ``x``'s dtype: the selection sits on near-ties that a
    rounded score flips.  Experts lie in ``n_group`` equal groups; a
    group's score is the sum of its two highest scores, the
    ``topk_group`` best groups stay, and among their experts the
    ``top_k`` highest scores are taken.  The kept scores are normalised
    to sum to one and multiplied by ``route_scale``.  With a correction
    ``bias`` ``[E]`` groups and experts are SELECTED by ``score + bias``
    and weighed by the raw scores: the bias moves selections alone.
    """
    with jax.named_scope("moe.route"):
        T, E = x.shape[0], wr.shape[1]
        s = jax.nn.sigmoid(jnp.matmul(
            x.astype(jnp.float32), wr.astype(jnp.float32),
            precision=lax.Precision.HIGHEST))
        by = s if bias is None else s + bias.astype(jnp.float32)
        group = jnp.sum(lax.top_k(by.reshape(T, n_group, E // n_group), 2)[0],
                        axis=-1)                               # [T, G]
        kept = jnp.sum(jax.nn.one_hot(lax.top_k(group, topk_group)[1],
                                      n_group, dtype=jnp.float32), axis=1)
        # raw scores lie in (0, 1); a biased one may lie anywhere
        masked = jnp.where(jnp.repeat(kept, E // n_group, axis=1) > 0,
                           by, -1.0 if bias is None else -jnp.inf)
        top, idx = lax.top_k(masked, top_k)
        if bias is not None:
            top = jnp.take_along_axis(s, idx, axis=-1)
        return s, idx, route_scale * top / jnp.sum(top, -1, keepdims=True)


def router_softmax(x: jax.Array, wr: jax.Array, *, top_k: int,
                   route_scale: float, bias: jax.Array | None = None):
    """Softmax router over ALL of ``wr``'s outputs, no groups:
    ``(scores [T, E], idx [T, k], weight [T, k])``.

    Scores are ``softmax(x wr)`` in float32 at full matmul precision
    whatever ``x``'s dtype.  The ``top_k`` outputs with the largest ``score
    + bias`` are taken (``bias`` ``[E]``: it moves selections alone) and
    weighed by their RAW scores times ``route_scale``, with no
    renormalisation: the weights of a token sum to whatever its kept
    scores sum to.  Which outputs are experts with weights, which are held
    here and which are identity experts is not the router's to know
    (:func:`held_moe_ffn`)."""
    with jax.named_scope("moe.route"):
        s = jax.nn.softmax(jnp.matmul(
            x.astype(jnp.float32), wr.astype(jnp.float32),
            precision=lax.Precision.HIGHEST), axis=-1)
        by = s if bias is None else s + bias.astype(jnp.float32)
        idx = lax.top_k(by, top_k)[1]
        return s, idx, route_scale * jnp.take_along_axis(s, idx, axis=-1)


def zero_expert_part(h: jax.Array, idx: jax.Array, weight: jax.Array,
                     first: int) -> jax.Array:
    """What the identity experts add for tokens ``h`` ``[T, D]`` routed as
    ``idx`` / ``weight`` ``[T, k]``: the router's outputs from ``first`` on
    compute nothing, and a selected one adds ``weight_e * h``.  No weights,
    no rows in a grouped buffer; in a deployment the chip that attends a
    token adds this for it, once, with no exchange."""
    w = jnp.sum(jnp.where(idx >= first, weight.astype(jnp.float32), 0.0),
                axis=-1, keepdims=True)
    return (w * h.astype(jnp.float32)).astype(h.dtype)


# what one expert computes: ``(silu(x wg) * (x wu)) wd``, or ``relu(x wg)^2
# wd`` with no gate (``wu`` is then None)
EXPERT_FORMS = ("gated_silu", "relu2")


def _expert_act(form: str, up: jax.Array, gate_of) -> jax.Array:
    """An expert's activation from its first product ``up``: times the
    gate's product (``gate_of()``, made only where the form has a gate)."""
    if form not in EXPERT_FORMS:
        raise ValueError(f"an expert's form is one of {EXPERT_FORMS}, got "
                         f"{form!r}")
    if form == "gated_silu":
        return jax.nn.silu(up) * gate_of()
    return jnp.square(jax.nn.relu(up))


def _held(idx: jax.Array, held_start: int, held_experts: int):
    """``idx`` ``[T, k]`` over all the router's experts as flat indices
    into this chip's experts, and which pairs fell on one of them."""
    local = idx.reshape(-1) - held_start
    return local, (local >= 0) & (local < held_experts)


def held_expert_ffn(x: jax.Array, idx: jax.Array, weight: jax.Array,
                    wg: jax.Array, wu: jax.Array | None, wd: jax.Array, *,
                    held_start: int, form: str = "gated_silu"):
    """What THIS chip's experts add for tokens ``x`` ``[T, D]`` routed as
    ``idx`` / ``weight`` ``[T, k]`` over all the router's experts: the
    chip holds the ``wg.shape[0]`` experts from ``held_start`` (gated
    SiLU FFNs, ``wg``/``wu`` ``[Eh, D, F]``, ``wd`` ``[Eh, F, D]``; or, in
    the ``"relu2"`` ``form``, ``relu(x wg)^2 wd`` with no ``wu``) and
    computes ``sum_{e selected and held} weight_e * expert_e(x)``.  What
    the absent experts would add is left out: on one chip of an
    expert-parallel deployment this is the layer without its exchange.

    The form of a decode step's lanes: every token through every held
    expert, masked by its weight, each expert's weights read once (the
    matmuls wait for their weights either way, and the grouped kernel's
    row tiles of 32 took 2.5 times the weights' time over groups of
    five, PERF.md §6, PR 33).  Returns ``(y [T, D], pairs)``: ``pairs``
    counts the pairs that fell on held experts (int32 scalar), the rows
    of the ``T * Eh`` computed that are real work."""
    with jax.named_scope("moe.experts"):
        T, k, Eh = x.shape[0], idx.shape[1], wg.shape[0]
        local, held = _held(idx, held_start, Eh)
        gate = jnp.sum(jnp.where(
            local[:, None] == jnp.arange(Eh), weight.reshape(T * k, 1),
            0).reshape(T, k, Eh), axis=1)                      # [T, Eh]
        act = _expert_act(form, jnp.einsum("td,edf->etf", x, wg),
                          lambda: jnp.einsum("td,edf->etf", x, wu))
        y = jnp.einsum("etf,efd,te->td", act, wd, gate.astype(x.dtype),
                       preferred_element_type=jnp.float32)
        return y.astype(x.dtype), jnp.sum(held.astype(jnp.int32))


def held_expert_ffn_grouped(x: jax.Array, idx: jax.Array, weight: jax.Array,
                            wg: jax.Array, wu: jax.Array | None,
                            wd: jax.Array, layer: jax.Array, *,
                            held_start: int, form: str = "gated_silu"):
    """:func:`held_expert_ffn`'s sum for a prompt's tokens, dropless: the
    token-expert pairs are sorted by expert into one buffer of ``T * k``
    rows, held pairs first in contiguous groups, and the three matmuls
    run as XLA's grouped (ragged) dot over the held groups alone; rows
    behind them are never computed.  The weights are the stacks of ALL
    expert layers (``[layers, Eh, ., .]``) and ``layer``'s experts are
    the only groups with rows: a layer loop that sliced its experts out
    of the stack would copy them every time (the grouped dot is a kernel
    call, no slice fuses into it).  Returns ``(y [T, D], pairs)``."""
    with jax.named_scope("moe.experts"):
        T, D = x.shape
        k, Eh = idx.shape[1], wg.shape[1]
        local, held = _held(idx, held_start, Eh)
        key = jnp.where(held, local, Eh)           # absent pairs sort last
        order = jnp.argsort(key)                   # stable in jax
        sizes = jnp.sum(jax.nn.one_hot(key, Eh + 1, dtype=jnp.int32),
                        axis=0)[:Eh]
        rows = x[order // k]                                   # [T*k, D]
        pairs = jnp.sum(sizes)
        sizes = lax.dynamic_update_slice(
            jnp.zeros((wg.shape[0] * Eh,), jnp.int32), sizes, (layer * Eh,))
        wg, wu, wd = (w if w is None else w.reshape((-1,) + w.shape[2:])
                      for w in (wg, wu, wd))
        act = _expert_act(form, lax.ragged_dot(rows, wg, sizes),
                          lambda: lax.ragged_dot(rows, wu, sizes))
        out = lax.ragged_dot(act, wd, sizes)
        w = jnp.where(held, weight.reshape(T * k), 0.0)[order]
        # rows past the held groups hold whatever the kernel left there
        out = jnp.where((jnp.arange(T * k) < pairs)[:, None],
                        out * w.astype(out.dtype)[:, None], 0)
        back = jnp.zeros((T * k,), jnp.int32).at[order].set(
            jnp.arange(T * k, dtype=jnp.int32))
        y = jnp.sum(out[back].reshape(T, k, D), axis=1, dtype=jnp.float32)
        return y.astype(x.dtype), pairs


def held_moe_ffn(cfg, lp: Dict[str, jax.Array], h: jax.Array,
                 live: jax.Array | None = None,
                 layer: jax.Array | None = None, *,
                 form: str = "gated_silu"):
    """One served expert layer's FFN on the normed tokens ``h`` ``[T, D]``
    as the chip that holds ``cfg.held_experts`` experts from
    ``cfg.held_start`` computes it: the full-width router
    (:func:`router_sigmoid_grouped`, under the layer's correction bias
    ``eb`` where it has one), the held experts' part (leaves
    ``weg``/``weu``/``wed``), and the shared expert
    (``wsg``/``wsu``/``wsd``) for every token.  Tokens not ``live``
    (trash lanes, a prompt's padding) are routed to no expert: their
    ``idx`` reads -1.  Without ``layer`` the leaves are the layer's own
    and every token goes through every held expert
    (:func:`held_expert_ffn`); with it ``weg``/``weu``/``wed`` are the
    stacks of all expert layers and the pairs go through the grouped
    kernel (:func:`held_expert_ffn_grouped`).  In the ``"relu2"`` ``form``
    every expert is ``relu(x w1)^2 w2`` with no gate (leaves
    ``we1``/``we2``, the shared expert ``ws1``/``ws2``), and where the
    layer has latent projections (``wdn`` ``[D, latent]``, ``wup``
    ``[latent, D]``) the ROUTED experts read ``h wdn`` and their weighted
    sum goes through ``wup``, with nothing between a projection and the
    experts; the router and the shared expert stay on ``h``.  Three fields
    that only a :class:`~bluefog_tpu.models.decoder.LatentConfig` has
    change the layer (the other configurations' are the defaults):
    ``router`` ``"softmax"`` takes :func:`router_softmax` in the grouped
    sigmoid router's place; the last ``zero_experts`` of the router's
    outputs are identity experts, whose part (:func:`zero_expert_part`) is
    added for every token whichever experts are held; ``shared_expert``
    False leaves the shared expert out.  Returns ``(y, idx [T, k], weight
    [T, k])``."""
    if getattr(cfg, "router", "sigmoid_grouped") == "softmax":
        _, idx, weight = router_softmax(
            h, lp["wr"], top_k=cfg.top_k, route_scale=cfg.route_scale,
            bias=lp.get("eb"))
    else:
        _, idx, weight = router_sigmoid_grouped(
            h, lp["wr"], top_k=cfg.top_k, n_group=cfg.n_group,
            topk_group=cfg.topk_group, route_scale=cfg.route_scale,
            bias=lp.get("eb"))
    if live is not None:
        idx = jnp.where(live[:, None], idx, -1)
    x = h
    if "wdn" in lp:
        with jax.named_scope("moe.latent"):
            x = h @ lp["wdn"]
    gated = form == "gated_silu"
    experts = (x, idx, weight.astype(h.dtype)) + (
        (lp["weg"], lp["weu"], lp["wed"]) if gated
        else (lp["we1"], None, lp["we2"]))
    if layer is None:
        y, _ = held_expert_ffn(*experts, held_start=cfg.held_start,
                               form=form)
    else:
        y, _ = held_expert_ffn_grouped(*experts, layer,
                                       held_start=cfg.held_start, form=form)
    if "wup" in lp:
        with jax.named_scope("moe.latent"):
            y = y @ lp["wup"]
    zero = getattr(cfg, "zero_experts", 0)
    if zero:
        with jax.named_scope("moe.zero"):
            y = y + zero_expert_part(h, idx, weight, cfg.num_experts - zero)
    if not getattr(cfg, "shared_expert", True):
        return y, idx, weight
    with jax.named_scope("moe.shared"):
        shared = gated_ffn(h, lp["wsg"], lp["wsu"], lp["wsd"]) if gated \
            else relu2_ffn(h, lp["ws1"], lp["ws2"])
        return y + shared, idx, weight


def _router_stats(logits, probs, idx, keep, *, num_experts: int,
                  axis: str) -> Dict[str, jax.Array]:
    """Aux/grading statistics for one routed sublayer.

    ``aux`` and ``usage`` are *globalized* over the expert-parallel axis
    (psum of the ``1/ep``-scaled shard means), so their values are
    replicated across ``ep`` peers and identical to the ``ep=1`` carving;
    ``z``/``dropped``/``entropy`` stay shard-local means (the model's
    ``/ep`` + outside-AD psum over ``expert`` turns them global — the
    same treatment as the CE term).
    """
    ep = lax.axis_size(axis)
    dt = probs.dtype
    f_part = jnp.mean(
        jax.nn.one_hot(idx[:, 0], num_experts, dtype=dt), axis=0) / ep
    p_part = jnp.mean(probs, axis=0) / ep
    f_bar = lax.psum(f_part, axis)                     # global dispatch frac
    p_bar = lax.psum(p_part, axis)                     # global mean prob
    aux = num_experts * jnp.sum(f_bar * p_bar)
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    dropped = 1.0 - jnp.mean(keep.astype(dt))
    entropy = -jnp.mean(jnp.sum(probs * jnp.log(probs + 1e-20), axis=-1))
    return {"aux": aux, "z": z, "dropped": dropped, "entropy": entropy,
            "usage": f_bar}


def _expert_einsum(h: jax.Array, w1: jax.Array, w2: jax.Array) -> jax.Array:
    """Per-expert FFN on ``[E?, T, D]`` token blocks: column-split w1,
    row-split w2, one psum over tp — the Megatron split *inside* every
    expert, so tp and ep compose."""
    u = jax.nn.gelu(jnp.einsum("etd,edf->etf", h, w1))
    return lax.psum(jnp.einsum("etf,efd->etd", u, w2), "tp")


def moe_ffn_routed(
    x: jax.Array,                 # [T, D] this device's (post-LN) tokens
    wr: jax.Array,                # [D, E] router
    w1: jax.Array,                # [E_local, D, F/TP]
    w2: jax.Array,                # [E_local, F/TP, D]
    *,
    num_experts: int,
    top_k: int,
    capacity: int,
    axis: str = "expert",
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One routed expert-FFN sublayer inside the composed shard_map.

    Dispatch is choice-major fused (the ``moe_apply_topk`` scheme: one
    all_to_all round trip carries all k choices, ``k * capacity`` pooled
    slots per (source, expert) pair filled first-choice-first).  Returns
    ``(y [T, D], stats)`` — ``y`` is the gate-weighted combined output
    (dropped tokens contribute zero), ``stats`` the per-layer scalars of
    :func:`_router_stats`.
    """
    T, D = x.shape
    E, k = num_experts, top_k
    n = lax.axis_size(axis)
    e_local = E // n
    logits, probs, idx, gate = router_topk(x, wr, top_k=k)
    x_rep = jnp.tile(x, (k, 1))                        # [k*T, D]
    flat_idx = idx.T.reshape(k * T)                    # choice-major
    cap = k * capacity
    expert_in, pos, keep = moe_dispatch(
        x_rep, flat_idx, capacity=cap, axis=axis, num_experts=E)
    h = expert_in.reshape(n, e_local, cap, D)
    h = h.transpose(1, 0, 2, 3).reshape(e_local, n * cap, D)
    o = _expert_einsum(h, w1, w2)                      # [E_local, n*cap, D]
    o = o.reshape(e_local, n, cap, D).transpose(1, 0, 2, 3)
    expert_out = o.reshape(n * e_local, cap, D)
    out = moe_combine(expert_out, flat_idx, pos, keep, capacity=cap,
                      axis=axis, num_experts=E)        # [k*T, D]
    gates = gate.T[..., None].astype(x.dtype)          # [k, T, 1]
    y = jnp.sum(out.reshape(k, T, D) * gates, axis=0)
    return y, _router_stats(logits, probs, idx, keep,
                            num_experts=E, axis=axis)


def moe_ffn_dense(
    x: jax.Array,                 # [T, D]
    wr: jax.Array,                # [D, E]
    w1: jax.Array,                # [E, D, F/TP] — ALL experts local
    w2: jax.Array,                # [E, F/TP, D]
    *,
    top_k: int,
    axis: str = "expert",
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Dense-equivalent oracle: every expert computed on every token,
    selection by gate mask — the no-drop reference the routed path must
    match.  Runs on an ``ep=1`` carving (the ``expert`` axis psums in the
    stats are size-1 no-ops, keeping the two code paths symmetric).

    **Oracle/tests only** — this path pays E× the active FLOPs by
    construction (every expert on every token) and is gated behind
    ``dense_equiv=True`` model builds.  Production ``ep=1`` runs route
    through the grouped dropless path (``dispatch="dropless"``), which
    computes only the routed tokens.
    """
    E = w1.shape[0]
    logits, probs, idx, gate = router_topk(x, wr, top_k=top_k)
    o = _expert_einsum(jnp.broadcast_to(x, (E,) + x.shape), w1, w2)
    sel = jax.nn.one_hot(idx, E, dtype=x.dtype)        # [T, k, E]
    y = jnp.einsum("tke,etd,tk->td", sel, o, gate.astype(x.dtype))
    keep = jnp.ones(idx.shape[0] * top_k, dtype=bool)  # dense never drops
    return y, _router_stats(logits, probs, idx, keep,
                            num_experts=E, axis=axis)


def moe_ffn_dropless(
    x: jax.Array,                 # [T, D] this device's (post-LN) tokens
    wr: jax.Array,                # [D, E] router
    w1: jax.Array,                # [E_local, D, F/TP]
    w2: jax.Array,                # [E_local, F/TP, D]
    *,
    num_experts: int,
    top_k: int,
    axis: str = "expert",
    tile: int = 8,
    impl: str | None = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One dropless routed expert-FFN sublayer: top-k router → sort-based
    grouped dispatch (:func:`..parallel.expert.moe_apply_dropless`) →
    grouped GEMM over ragged expert groups → inverse-permutation combine
    → gate-weighted sum.  No capacity hyperparameter, zero dropped tokens
    by construction (``stats["dropped"]`` is exactly 0), no zero-padded
    slots matmul'd beyond the ≤ ``tile - 1`` pad rows per group.
    """
    T = x.shape[0]
    logits, probs, idx, gate = router_topk(x, wr, top_k=top_k)
    y = moe_dropless_combine(x, idx, gate, w1, w2, num_experts=num_experts,
                             axis=axis, tile=tile, impl=impl)
    keep = jnp.ones((top_k * T,), dtype=bool)          # dropless by design
    return y, _router_stats(logits, probs, idx, keep,
                            num_experts=num_experts, axis=axis)


def moe_dropless_combine(
    x: jax.Array,                 # [T, D]
    idx: jax.Array,               # [T, k] routed expert ids
    gate: jax.Array,              # [T, k] renormalized gates
    w1: jax.Array,                # [E_local, D, F/TP]
    w2: jax.Array,                # [E_local, F/TP, D]
    *,
    num_experts: int,
    axis: str = "expert",
    tile: int = 8,
    impl: str | None = None,
) -> jax.Array:
    """The gate-weighted dropless grouped-FFN on *precomputed* routing —
    the math of :func:`moe_ffn_dropless` past the router.  Split out so
    the serving hot path can route once and reuse ``(idx, gate)`` for
    both the expert math and its hot-expert accounting without running
    the router twice."""
    T, D = x.shape
    E, k = num_experts, idx.shape[1]
    x_rep = jnp.tile(x, (k, 1))                        # [k*T, D]
    flat_idx = idx.T.reshape(k * T)                    # choice-major

    def grouped(params, xt, tile_eid):
        w1_, w2_ = params
        # tp psum mirrors _expert_einsum: reduce the row-split w2 partial
        # before the combine all_to_all.
        return lax.psum(grouped_ffn(xt, tile_eid, w1_, w2_, impl=impl),
                        "tp")

    out = moe_apply_dropless(x_rep, flat_idx, grouped, (w1, w2),
                             axis=axis, num_experts=E, tile=tile)
    gates = gate.T[..., None].astype(x.dtype)          # [k, T, 1]
    return jnp.sum(out.reshape(k, T, D) * gates, axis=0)


def router_expert_choice(x: jax.Array, wr: jax.Array, *, capacity: int):
    """Expert-choice router (Zhou et al. 2022): experts pick tokens.

    ``x`` is ``[B, T, D]`` (the sequence dim must be whole — EC selects
    over it, so ``sp == 1``), ``wr`` the ``[D, E]`` router.  Each expert
    takes its top-``capacity`` tokens *per batch row* by router
    probability: returns ``(logits [B, T, E], probs, sel [B, E, C],
    gate [B, E, C])``.  Load balance is perfect by construction (every
    expert processes exactly ``C`` tokens), so no aux loss is needed; a
    token may be picked by several experts or by none (coverage is
    reported in the stats).
    """
    if x.ndim != 3:
        raise ValueError(
            f"router_expert_choice expects [B, T, D] tokens (whole "
            f"sequences; sp must be 1), got shape {x.shape}")
    B, T, D = x.shape
    if not 1 <= capacity <= T:
        raise ValueError(
            f"moe_ec_invalid_capacity: expert-choice capacity must be in "
            f"[1, seq_len={T}], got {capacity!r}")
    logits = x @ wr                                    # [B, T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate, sel = lax.top_k(probs.transpose(0, 2, 1), capacity)  # [B, E, C]
    return logits, probs, sel, gate


def _router_stats_ec(logits, probs, sel, *, num_experts: int,
                     axis: str) -> Dict[str, jax.Array]:
    """EC-mode stats: balance is structural (``usage`` ≡ 1/E, ``aux`` ≡
    0, ``dropped`` ≡ 0); ``coverage`` — the fraction of tokens picked by
    at least one expert — is the EC-specific health signal, globalized
    over the ``ep`` axis like ``usage`` in the top-k path."""
    ep = lax.axis_size(axis)
    dt = probs.dtype
    B = logits.shape[0]
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    entropy = -jnp.mean(jnp.sum(probs * jnp.log(probs + 1e-20), axis=-1))
    hit = jnp.zeros(logits.shape[:2], dt).at[
        jnp.arange(B)[:, None, None], sel].set(1.0)
    coverage = lax.psum(jnp.mean(hit) / ep, axis)
    return {"aux": jnp.zeros((), dt), "z": z, "dropped": jnp.zeros((), dt),
            "entropy": entropy,
            "usage": jnp.full((num_experts,), 1.0 / num_experts, dt),
            "coverage": coverage}


def moe_ffn_expert_choice(
    x: jax.Array,                 # [B, T, D] this device's sequences
    wr: jax.Array,                # [D, E] router
    w1: jax.Array,                # [E_local, D, F/TP]
    w2: jax.Array,                # [E_local, F/TP, D]
    *,
    num_experts: int,
    capacity: int,
    axis: str = "expert",
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One expert-choice sublayer: every expert gathers its top-C tokens
    per batch row into a *statically balanced* ``[E, B*C, D]`` buffer —
    no capacity padding (every slot is a real token), no dropped-token
    failure mode, one tiled all_to_all round trip, zero wasted FLOPs.
    This is the dropless fast path the graded FLOP comparison uses: at
    ``C = ceil(k*T/E)`` it does the same active-token work as top-k
    routing with none of the ``capacity_factor`` padding.
    """
    B, T, D = x.shape
    E, C = num_experts, capacity
    n = lax.axis_size(axis)
    e_local = E // n
    logits, probs, sel, gate = router_expert_choice(x, wr, capacity=C)
    b_ix = jnp.arange(B)[:, None, None]
    xe = x[b_ix, sel]                                  # [B, E, C, D]
    buf = xe.transpose(1, 0, 2, 3).reshape(E, B * C, D)
    recv = lax.all_to_all(buf, axis, split_axis=0, concat_axis=0,
                          tiled=True)                  # [n*e_local, B*C, D]
    h = recv.reshape(n, e_local, B * C, D).transpose(1, 0, 2, 3)
    h = h.reshape(e_local, n * B * C, D)
    o = _expert_einsum(h, w1, w2)                      # [E_local, n*B*C, D]
    o = o.reshape(e_local, n, B * C, D).transpose(1, 0, 2, 3)
    back = lax.all_to_all(o.reshape(n * e_local, B * C, D), axis,
                          split_axis=0, concat_axis=0, tiled=True)
    oe = back.reshape(E, B, C, D).transpose(1, 0, 2, 3)  # [B, E, C, D]
    y = jnp.zeros_like(x).at[b_ix, sel].add(
        oe * gate[..., None].astype(x.dtype))
    return y, _router_stats_ec(logits, probs, sel, num_experts=E, axis=axis)


def moe_ffn_dense_ec(
    x: jax.Array,                 # [B, T, D]
    wr: jax.Array,                # [D, E]
    w1: jax.Array,                # [E, D, F/TP] — ALL experts local
    w2: jax.Array,                # [E, F/TP, D]
    *,
    capacity: int,
    axis: str = "expert",
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Dense-equivalent oracle for expert-choice routing: every expert
    computed on every token, then each expert's top-C token outputs
    selected by gather — the reference :func:`moe_ffn_expert_choice`
    must match float64-exactly.  Oracle/tests only (E× FLOPs)."""
    B, T, D = x.shape
    E = w1.shape[0]
    logits, probs, sel, gate = router_expert_choice(x, wr, capacity=capacity)
    h = x.reshape(B * T, D)
    o = _expert_einsum(jnp.broadcast_to(h, (E,) + h.shape), w1, w2)
    oe = o.reshape(E, B, T, D).transpose(1, 0, 2, 3)   # [B, E, T, D]
    b_ix = jnp.arange(B)[:, None, None]
    e_ix = jnp.arange(E)[None, :, None]
    sel_out = oe[b_ix, e_ix, sel]                      # [B, E, C, D]
    y = jnp.zeros_like(x).at[b_ix, sel].add(
        sel_out * gate[..., None].astype(x.dtype))
    return y, _router_stats_ec(logits, probs, sel, num_experts=E, axis=axis)
