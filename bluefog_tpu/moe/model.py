"""The routed-MoE reference LM on the composed 5-axis carving.

Grown from the PR 9 composed LM skeleton (``parallel.compose``): the same
copy-task decoder — pipelined over ``stage``, Megatron-TP attention,
Ulysses over ``sp``, gossip-DP over ``rank`` — with every block's dense
FFN replaced by a routed expert FFN sharded over the ``expert`` axis.

**Gradient recipe** (the part tests/test_moe.py pins with a float64
dense-equivalent oracle, exact under the legacy ``check_vma=False`` psum
transpose):

* the differentiated per-device scalar is ``(CE_local + alpha * aux_bar +
  beta * z_local) / ep``, masked to the LAST stage and seeded ``1/TP`` —
  the dense recipe with one extra normalization: ``ep`` shards the batch,
  so shard-local means carry a ``1/ep`` to make them global-batch partial
  sums;
* the aux load-balance term uses *globalized* router stats
  (``f_bar = psum(f_local/ep, "expert")``) computed inside the layer; its
  psum transposes (legacy semantics: cotangent x axis size) against the
  ``1/ep`` in the loss, so every shard's router gradient is exactly the
  global-batch gradient;
* per-layer aux/z/metric scalars RIDE THE PIPELINE: each stage adds its
  routers' contributions to a reserved carrier row appended to the
  activation batch (``[B_local + 1, Tl, D]``; layer math sees only the
  first ``B_local`` rows), so the scalars reach the last stage through the
  same ``ppermute`` chain as the activations and their cotangents flow
  back through the backward pipeline with the same seeding as the CE —
  no extra collective inside AD;
* outside AD: loss and shared grads ``psum(("stage", "tp"))`` (dense
  recipe), router grads ``psum("tp")`` (tp-replicated, no structural psum
  on their path), then loss + shared/blocks/router grads ``psum`` over
  ``expert`` (they are global-batch partials) while **expert grads stay
  sharded over ep** — each expert already saw every token routed to it via
  the all_to_all, so its gradient is complete and local; finally
  everything ``pmean``'d over ``sp`` as in the dense recipe.

``dense_equiv=True`` builds the float64-oracle twin: identical router,
gating, and loss code, but every expert computed densely on every token
(no expert axis, no capacity) — with top-1 routing and zero drops the
routed model must match it loss-for-loss.
"""
from __future__ import annotations

import dataclasses
import math
import os
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..models import decoder
from ..parallel.compose import AXES, LMConfig, Mesh3D
from ..parallel.pipeline import pipeline_apply
from .layers import (moe_ffn_dense, moe_ffn_dense_ec, moe_ffn_dropless,
                     moe_ffn_expert_choice, moe_ffn_routed)

__all__ = ["MoELMConfig", "init_moe_params", "make_moe_batch",
           "make_moe_grad_fn", "make_moe_probe"]

# carrier-row channel layout (written once per layer, summed over layers):
# 0 aux (load balance, globalized), 1 router-z, 2 dropped fraction,
# 3 mean token entropy, 4 expert-choice coverage (0 under top-k routing),
# 5 reserved, 6.. per-expert dispatch fraction
_CH_FIXED = 6


@dataclasses.dataclass(frozen=True)
class MoELMConfig(LMConfig):
    """Shape of the routed-MoE composed LM.

    Inherits the dense skeleton's fields (vocab/d_model/heads/layers/
    seq_len/micro/batch/lag/ffn_mult — ``ffn_mult`` now sizes each
    *expert's* hidden layer) and adds the MoE shape.  ``batch`` is the
    GLOBAL per-microbatch batch size; the expert axis shards it
    (``batch % ep == 0``), so an ``ep>1`` carving trains the same global
    batch as its ``ep=1`` twin.
    """
    num_experts: int = 8
    top_k: int = 1           # 1 (Switch) or 2 (classic mixture)
    capacity_factor: float = 1.25
    aux_alpha: float = 1e-2  # load-balance loss weight
    z_alpha: float = 1e-3    # router z-loss weight
    router_mode: str = "topk"      # "topk" | "expert_choice"
    dispatch: str = "capacity"     # "capacity" | "dropless"
    group_tile: int = 8            # dropless grouped-GEMM tile rows

    @classmethod
    def from_env(cls, **overrides) -> "MoELMConfig":
        """Defaults from ``BLUEFOG_MOE_*`` env knobs (explicit kwargs
        win): ``BLUEFOG_MOE_EXPERTS``, ``BLUEFOG_MOE_TOPK``,
        ``BLUEFOG_MOE_CAPACITY_FACTOR``, ``BLUEFOG_MOE_AUX_ALPHA``,
        ``BLUEFOG_MOE_Z_ALPHA``, ``BLUEFOG_MOE_ROUTER``,
        ``BLUEFOG_MOE_DISPATCH``, ``BLUEFOG_MOE_TILE``."""
        env = {}
        for key, name, cast in (
                ("num_experts", "BLUEFOG_MOE_EXPERTS", int),
                ("top_k", "BLUEFOG_MOE_TOPK", int),
                ("capacity_factor", "BLUEFOG_MOE_CAPACITY_FACTOR", float),
                ("aux_alpha", "BLUEFOG_MOE_AUX_ALPHA", float),
                ("z_alpha", "BLUEFOG_MOE_Z_ALPHA", float),
                ("router_mode", "BLUEFOG_MOE_ROUTER", str),
                ("dispatch", "BLUEFOG_MOE_DISPATCH", str),
                ("group_tile", "BLUEFOG_MOE_TILE", int)):
            raw = os.environ.get(name)
            if raw is not None:
                try:
                    env[key] = cast(raw)
                except ValueError as e:
                    raise ValueError(f"{name}={raw!r}: {e}") from None
        env.update(overrides)
        return cls(**env)

    def validate(self, m: Mesh3D) -> None:
        super().validate(m)
        E = self.num_experts
        if self.top_k not in (1, 2):
            raise ValueError(f"top_k ({self.top_k}) must be 1 or 2")
        if not isinstance(E, int) or E < 1:
            raise ValueError(f"num_experts ({E!r}) must be a positive int")
        if E % m.ep:
            raise ValueError(
                f"num_experts ({E}) % ep ({m.ep}) != 0: each expert peer "
                "owns a contiguous block of num_experts // ep experts")
        if m.num_experts is not None and m.num_experts != E:
            raise ValueError(
                f"carving was validated for num_experts={m.num_experts} "
                f"but the model has {E}")
        if self.batch % m.ep:
            raise ValueError(
                f"batch ({self.batch}) % ep ({m.ep}) != 0: the expert "
                "axis shards the global microbatch")
        if (self.ffn_mult * self.d_model) % m.tp:
            raise ValueError(
                f"expert hidden ({self.ffn_mult * self.d_model}) % tp "
                f"({m.tp}) != 0")
        if self.d_model < _CH_FIXED + E:
            raise ValueError(
                f"d_model ({self.d_model}) < {_CH_FIXED} + num_experts "
                f"({E}): the metrics carrier row stores per-expert usage "
                "in the channel dimension")
        if not (isinstance(self.capacity_factor, (int, float))
                and self.capacity_factor > 0):
            raise ValueError(
                f"capacity_factor ({self.capacity_factor!r}) must be > 0")
        if self.dispatch not in ("capacity", "dropless"):
            raise ValueError(
                f"dispatch ({self.dispatch!r}) must be 'capacity' or "
                "'dropless'")
        if self.router_mode not in ("topk", "expert_choice"):
            raise ValueError(
                f"router_mode ({self.router_mode!r}) must be 'topk' or "
                "'expert_choice'")
        if not isinstance(self.group_tile, int) or self.group_tile < 1:
            raise ValueError(
                f"group_tile ({self.group_tile!r}) must be a positive int")
        if self.router_mode == "expert_choice":
            if self.dispatch != "dropless":
                raise ValueError(
                    "router_mode='expert_choice' requires "
                    "dispatch='dropless': expert choice has no capacity "
                    "overflow to drop, so the padded-slot path does not "
                    "apply")
            if m.sp != 1:
                raise ValueError(
                    f"router_mode='expert_choice' requires sp=1 (got "
                    f"sp={m.sp}): experts select their top-C tokens over "
                    "the whole sequence dimension")
            if self.ec_capacity(m) > self.seq_len // m.sp:
                raise ValueError(
                    f"expert-choice capacity ({self.ec_capacity(m)}) > "
                    f"local seq_len ({self.seq_len // m.sp}): raise "
                    "num_experts or shrink top_k")

    def capacity(self, m: Mesh3D) -> int:
        """Static per-(source, expert, choice) slot count for one
        dispatch: ``ceil(capacity_factor * local_tokens / num_experts)``
        over the ``batch/ep * seq_len/sp`` tokens of one microbatch."""
        tokens = (self.batch // m.ep) * (self.seq_len // m.sp)
        return max(1, math.ceil(
            float(self.capacity_factor) * tokens / self.num_experts))

    def ec_capacity(self, m: Mesh3D) -> int:
        """Expert-choice top-C per (expert, batch row):
        ``ceil(top_k * seq_len / num_experts)`` — the token budget that
        matches top-k routing's ACTIVE work exactly, with zero padding
        (every one of the ``E * C`` slots is a real token)."""
        return max(1, math.ceil(
            self.top_k * (self.seq_len // m.sp) / self.num_experts))

    def _n_params(self, experts: int) -> int:
        """Dense count with ``experts`` expert FFNs a block: the dense
        block's attention leaves, the router, that many dense FFNs."""
        per_block = (
            decoder.block_param_count(self, decoder.ATTENTION_LEAVES)
            + self.d_model * self.num_experts
            + experts * decoder.block_param_count(self, decoder.FFN_LEAVES))
        return self.layers * per_block + 2 * self.vocab * self.d_model

    @property
    def n_params(self) -> int:
        """Dense (un-sharded) parameter count, ALL experts included."""
        return self._n_params(self.num_experts)

    @property
    def n_active_params(self) -> int:
        """Parameters a single token activates (top-k experts only) —
        the N in the MFU accounting."""
        return self._n_params(self.top_k)


def init_moe_params(cfg: MoELMConfig, m: Mesh3D, seed: int = 0,
                    dtype: Any = np.float32,
                    dense_equiv: bool = False) -> Any:
    """Distributed MoE LM params, every leaf stacked ``[n, ...]``.

    Expert weights are drawn at FULL ``[E, ...]`` shape and then sliced
    per (stage, tp, ep) owner, so carvings that differ only in ``ep`` (and
    the dense-equivalent twin, which keeps all E experts local) share
    bit-identical values — the property the trajectory oracle needs.
    Attention/router/shared draws are ep-independent by construction.
    """
    cfg.validate(m)
    if dense_equiv and m.ep != 1:
        raise ValueError("dense_equiv keeps every expert local — carve "
                         f"ep=1, not ep={m.ep}")
    rng = np.random.default_rng(seed)
    D, F, E = cfg.d_model, cfg.ffn_mult * cfg.d_model, cfg.num_experts
    Lps, TP = cfg.layers // m.pp, m.tp
    Fl, e_local = F // TP, E // m.ep

    def w(*shape, scale=0.1):
        return (rng.normal(size=shape) * scale).astype(dtype)

    shapes = decoder.block_param_shapes(cfg, TP)
    blocks = {k: w(m.pp, TP, Lps, *shapes[k])   # [pp, tp, Lps, ...] owners
              for k in decoder.ATTENTION_LEAVES}
    wr_full = w(m.pp, Lps, D, E)            # [pp, Lps, D, E]
    w1_full = w(m.pp, Lps, E, D, F)
    w2_full = w(m.pp, Lps, E, F, D)
    shared = {"embed": w(cfg.vocab, D), "head": w(D, cfg.vocab)}

    # flat device i = (((r*pp + s)*tp + t)*sp + u)*ep + e
    r, s, t, u, e = np.unravel_index(np.arange(m.size),
                                     (m.dp, m.pp, m.tp, m.sp, m.ep))
    del r, u

    def expert_slice(full, si, ti, ei):     # [Lps, E, ...] -> owner shard
        blk = full[si] if dense_equiv \
            else full[si][:, ei * e_local:(ei + 1) * e_local]
        if full is w1_full:
            return blk[..., ti * Fl:(ti + 1) * Fl]           # column split
        return blk[:, :, ti * Fl:(ti + 1) * Fl, :]           # row split

    return {
        "blocks": {k: jnp.asarray(v[s, t]) for k, v in blocks.items()},
        "router": {"wr": jnp.asarray(wr_full[s])},
        "experts": {
            "w1": jnp.asarray(np.stack(
                [expert_slice(w1_full, si, ti, ei)
                 for si, ti, ei in zip(s, t, e)])),
            "w2": jnp.asarray(np.stack(
                [expert_slice(w2_full, si, ti, ei)
                 for si, ti, ei in zip(s, t, e)])),
        },
        "shared": {k: jnp.asarray(np.broadcast_to(v, (m.size,) + v.shape))
                   for k, v in shared.items()},
    }


def make_moe_batch(cfg: MoELMConfig, m: Mesh3D, seed: int = 0,
                   steps: Optional[int] = None) -> jax.Array:
    """Copy-task tokens stacked per device: ``[n, (steps,) micro,
    batch/ep, seq_len/sp]``.  Each DP replica draws its own GLOBAL batch;
    stage/tp copies see identical tokens; sp shards slice the sequence and
    ep shards slice the batch rows — so the global data is identical
    across carvings that differ only in ep."""
    rng = np.random.default_rng(seed)
    shape = (m.dp, cfg.micro, cfg.batch, cfg.seq_len) if steps is None \
        else (m.dp, steps, cfg.micro, cfg.batch, cfg.seq_len)
    data = rng.integers(0, cfg.vocab, size=shape).astype(np.int32)
    Tl, Bl = cfg.seq_len // m.sp, cfg.batch // m.ep
    r, _, _, u, e = np.unravel_index(np.arange(m.size),
                                     (m.dp, m.pp, m.tp, m.sp, m.ep))
    per_dev = np.stack(
        [data[ri][..., ei * Bl:(ei + 1) * Bl, ui * Tl:(ui + 1) * Tl]
         for ri, ui, ei in zip(r, u, e)])
    return jnp.asarray(per_dev)


def _make_forward(cfg: MoELMConfig, m: Mesh3D, *, remat: bool,
                  dense_equiv: bool):
    """Shared per-device forward: ``(params, toks) -> (ce_local,
    channels)`` — shard-local means, nothing reduced over expert/sp yet.
    ``channels`` is the layer-summed carrier vector read off the last
    stage's pipeline output (zeros elsewhere; mask with the stage id as
    the dense recipe does)."""
    cfg.validate(m)
    import optax

    from ..ops.ulysses import ulysses_attention

    D, E = cfg.d_model, cfg.num_experts
    Tl, Bl = cfg.seq_len // m.sp, cfg.batch // m.ep
    cap, k = cfg.capacity(m), cfg.top_k
    n_ch = _CH_FIXED + E

    def attend(q, kk, v):                           # [Bl, Tl, H/TP, Dh]
        return ulysses_attention(q, kk, v, axis="sp", causal=True,
                                 pallas_block_q=min(512, cfg.seq_len)), None

    ec = cfg.router_mode == "expert_choice"
    ecC = cfg.ec_capacity(m) if ec else 0
    dropless = cfg.dispatch == "dropless"

    def moe_ffn(rp, xp, h3):
        """The block's ``ffn`` hook on the normed ``h3`` ``[Bl, Tl, D]``:
        ``(y3, this layer's carrier-channel vector)``."""
        h = h3.reshape(Bl * Tl, D)
        if ec and dense_equiv:
            y3, st = moe_ffn_dense_ec(h3, rp["wr"], xp["w1"], xp["w2"],
                                      capacity=ecC, axis="expert")
        elif ec:
            y3, st = moe_ffn_expert_choice(
                h3, rp["wr"], xp["w1"], xp["w2"],
                num_experts=E, capacity=ecC, axis="expert")
        elif dense_equiv:
            y, st = moe_ffn_dense(h, rp["wr"], xp["w1"], xp["w2"],
                                  top_k=k, axis="expert")
            y3 = y.reshape(Bl, Tl, D)
        elif dropless:
            y, st = moe_ffn_dropless(h, rp["wr"], xp["w1"], xp["w2"],
                                     num_experts=E, top_k=k, axis="expert",
                                     tile=cfg.group_tile)
            y3 = y.reshape(Bl, Tl, D)
        else:
            y, st = moe_ffn_routed(h, rp["wr"], xp["w1"], xp["w2"],
                                   num_experts=E, top_k=k, capacity=cap,
                                   axis="expert")
            y3 = y.reshape(Bl, Tl, D)
        vec = jnp.zeros((n_ch,), h3.dtype)
        vec = vec.at[0].set(st["aux"]).at[1].set(st["z"])
        vec = vec.at[2].set(lax.stop_gradient(st["dropped"]))
        vec = vec.at[3].set(lax.stop_gradient(st["entropy"]))
        if "coverage" in st:
            vec = vec.at[4].set(lax.stop_gradient(
                st["coverage"].astype(h3.dtype)))
        vec = vec.at[_CH_FIXED:].set(lax.stop_gradient(
            st["usage"].astype(h3.dtype)))
        return y3, vec

    def stage_fn(sp_params, x):                     # x [Bl+1, Tl, D]
        data, row = x[:Bl], x[Bl:]
        positions = lax.axis_index("sp") * Tl + jnp.arange(Tl)
        def body(c, layer_params):
            lp, rp, xp = layer_params
            c, _, vec = decoder.decoder_block(
                cfg, m.tp, lp, c, positions, attend,
                lambda _, h3: moe_ffn(rp, xp, h3))
            return c, vec
        data, vecs = lax.scan(body, data, sp_params)  # vecs [Lps, n_ch]
        row = row + jnp.zeros_like(row).at[0, 0, :n_ch].set(vecs.sum(0))
        return jnp.concatenate([data, row], axis=0)

    def forward(q, toks):                           # toks [M, Bl, Tl]
        x = q["shared"]["embed"][toks]              # [M, Bl, Tl, D]
        pad = jnp.zeros((cfg.micro, 1, Tl, D), x.dtype)
        x = jnp.concatenate([x, pad], axis=1)       # carrier row
        out = pipeline_apply(
            stage_fn, (q["blocks"], q["router"], q["experts"]), x,
            axis="stage", remat=remat)
        data = out[:, :Bl]
        channels = out[:, Bl, 0, :n_ch].mean(0)     # mean over microbatches
        logits = decoder.lm_logits(q["shared"], data)
        targets = jnp.roll(toks, cfg.lag, axis=-1)
        ce = optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :, cfg.lag:], targets[:, :, cfg.lag:]).mean()
        return ce, channels

    return forward


def make_moe_grad_fn(cfg: MoELMConfig, m: Mesh3D, *, remat: bool = False,
                     dense_equiv: bool = False):
    """Per-device ``grad_fn(params, toks) -> (loss, grads)`` for the
    routed-MoE LM (see the module docstring for the full recipe).  Drop it
    straight into :func:`bluefog_tpu.parallel.compose.make_train_step`.
    """
    forward = _make_forward(cfg, m, remat=remat, dense_equiv=dense_equiv)
    S, TP, EP, L = m.pp, m.tp, m.ep, cfg.layers

    def grad_fn(params, toks):
        sid = lax.axis_index("stage")

        def loss_fn(q):
            ce, ch = forward(q, toks)
            total = (ce + cfg.aux_alpha * ch[0] / L
                     + cfg.z_alpha * ch[1] / L) / EP
            return jnp.where(sid == S - 1, total, 0.0) / TP

        loss, g = jax.value_and_grad(loss_fn)(params)
        loss = lax.psum(loss, ("stage", "tp"))
        g["shared"] = jax.tree.map(
            lambda v: lax.psum(v, ("stage", "tp")), g["shared"])
        g["router"] = jax.tree.map(
            lambda v: lax.psum(v, "tp"), g["router"])
        if EP > 1:
            # loss and non-expert grads are global-batch partials (the
            # 1/ep in the loss); expert grads are complete and STAY
            # sharded — each expert saw all its tokens via the all_to_all
            loss = lax.psum(loss, "expert")
            for key in ("shared", "blocks", "router"):
                g[key] = jax.tree.map(
                    lambda v: lax.psum(v, "expert"), g[key])
        if m.sp > 1:
            loss = lax.pmean(loss, "sp")
            g = jax.tree.map(lambda v: lax.pmean(v, "sp"), g)
        return loss, g

    return grad_fn


def make_moe_probe(cfg: MoELMConfig, m: Mesh3D, *,
                   dense_equiv: bool = False):
    """Forward-only grading probe: ``probe(params, batch) -> dict``.

    Runs the same composed forward OUTSIDE the train step (donation and
    the retrace sentinel stay untouched) and returns the routing health
    scalars: load-balance aux, router z, dropped token fraction, mean
    token entropy, per-expert dispatch fractions and their usage entropy (nats; ``log(E)`` is perfectly balanced), plus the
    plain CE for cross-checking.  All values are global — aggregated over
    stage/tp/expert/sp exactly like the loss.
    """
    forward = _make_forward(cfg, m, remat=False, dense_equiv=dense_equiv)
    S, TP, EP, L = m.pp, m.tp, m.ep, cfg.layers
    E = cfg.num_experts

    def body(params, toks):
        p = jax.tree.map(lambda v: v[0], params)
        ce, ch = forward(p, toks[0])
        sid = lax.axis_index("stage")
        vec = jnp.concatenate([ch, ce[None]])
        vec = lax.psum(jnp.where(sid == S - 1, vec, 0.0),
                       ("stage", "tp")) / TP
        vec = lax.psum(vec, "expert") / EP
        vec = lax.pmean(vec, "sp")
        return vec[None]

    compiled = jax.jit(jax.shard_map(
        body, mesh=m.mesh, in_specs=P(AXES), out_specs=P(AXES),
        check_vma=False))

    def probe(params, batch):
        row = np.asarray(compiled(params, batch))[0]
        usage = row[_CH_FIXED:_CH_FIXED + E] / L
        u = np.clip(usage / max(usage.sum(), 1e-20), 1e-20, 1.0)
        return {
            "aux_loss": float(row[0] / L),
            "z_loss": float(row[1] / L),
            "dropped_fraction": float(row[2] / L),
            "token_entropy": float(row[3] / L),
            "ec_coverage": float(row[4] / L),
            "usage": [float(x) for x in usage],
            "usage_entropy": float(-(u * np.log(u)).sum()),
            "ce": float(row[-1]),
        }

    return probe
