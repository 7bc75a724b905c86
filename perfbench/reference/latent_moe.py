"""Plain reference of the ``latent_moe`` family: the forward pass of a
latent-attention decoder with a leading dense layer and sigmoid-routed
expert layers, as ONE chip of an expert-parallel deployment computes it, in
straightforward float32 ``jax.numpy`` with matmuls at ``highest`` precision:
the whole sequence at once, unabsorbed attention, every held expert applied
densely to every token and masked by its weight, no cache, no batching, no
kernels.  Written from the equations below; imports nothing from
``bluefog_tpu``.

``cfg`` is the configuration file's dict (the source's key names).  With
``RMS(x; g) = x / sqrt(mean(x^2) + eps) * g``, for one sequence x[T, D]:

    block    x += MLA(RMS(x; g1));  x += FFN(RMS(x; g2))
    logits   RMS(x_L; gf) @ head                 (over the vocabulary slice)

    MLA(h)   cq = RMS(h wqa; gq);  q = cq wqb  -> H heads of [q_nope | q_rope]
             [ckv | kr] = h wkva;  ckv = RMS(ckv; gkv)
             q_rope, k_rope = rot(q_rope), rot(kr)      k_rope: one per token
             [k_nope_i | v_i] = ckv wkvb                per head i
             score_i(t, s) = (q_nope_i(t).k_nope_i(s) + q_rope_i(t).k_rope(s)) c
             c = (nope + rope)^-0.5 m^2,  m = 0.1 mscale_all_dim ln(factor) + 1
             out = concat_i(causal_softmax(score_i) v_i) wo
    rot      halves pairing (channel i with i + d/2), YaRN ladder: f_i =
             theta^(-2i/d); lo = floor(d ln(L0 / (beta_fast 2 pi)) / (2 ln theta)),
             hi = ceil(d ln(L0 / (beta_slow 2 pi)) / (2 ln theta));
             r_i = clip((i - lo) / (hi - lo), 0, 1);
             angle_i(p) = p f_i (r_i / factor + 1 - r_i)
    layer 0  FFN(h) = (silu(h wg) * h wu) wd
    others   s = sigmoid(h wr)  [E];  groups of E / n_group;  a group's score
             = the sum of its two highest s;  the topk_group best groups stay;
             among their experts the num_experts_per_tok highest s are taken;
             w_e = routed_scaling_factor s_e / sum_selected s;
             FFN(h) = shared(h) + sum_{e selected and HELD} w_e expert_e(h)

The cut: the chip holds experts ``held_start .. held_start + held - 1``
(``held`` = the length of ``weg``), and what the absent experts would add
is left out; :func:`moe_ffn` takes any held range, so a test can add the
shares of all chips up to the uncut layer.

:func:`forward` runs a layer at a time: the caller hands it ``layer_leaves(i)``
which returns layer ``i``'s leaves, so that on the chip one layer at a time
is upcast to float32 and freed.  Besides the logits it returns, per expert
layer, the selection and how far each HELD expert's decision is from
flipping (:func:`held_margin`): the selection sits on near-ties that the
program's bfloat16 activations flip, and the function is compared only
where it is decided.
"""
import functools
import json
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def yarn_freqs(cfg):
    d, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    sc = cfg["rope_scaling"]
    i = jnp.arange(d // 2, dtype=jnp.float32)
    f = theta ** (-2.0 * i / d)

    def dim(turns):
        return d * math.log(sc["original_max_position_embeddings"]
                            / (turns * 2 * math.pi)) / (2 * math.log(theta))
    lo = max(math.floor(dim(sc["beta_fast"])), 0)
    hi = min(math.ceil(dim(sc["beta_slow"])), d - 1)
    r = jnp.clip((i - lo) / (hi - lo), 0.0, 1.0)
    return f * (r / sc["factor"] + 1.0 - r)


def rot(x, freqs):
    """x [T, ..., d] at positions 0..T-1."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs[None]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def softmax_scale(cfg):
    sc = cfg["rope_scaling"]
    m = 0.1 * sc["mscale_all_dim"] * math.log(sc["factor"]) + 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def mla(cfg, w, h):
    T = h.shape[0]
    H, nope = cfg["num_attention_heads"], cfg["qk_nope_head_dim"]
    rope, vd, C = cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"]
    eps, freqs = cfg["rms_norm_eps"], yarn_freqs(cfg)
    q = _mm(rms(_mm(h, w["wqa"]), w["gq"], eps), w["wqb"]).reshape(
        T, H, nope + rope)
    q_nope, q_rope = q[..., :nope], rot(q[..., nope:], freqs)
    kv = _mm(h, w["wkva"])
    ckv, k_rope = rms(kv[:, :C], w["gkv"], eps), rot(kv[:, C:], freqs)
    kvb = _mm(ckv, w["wkvb"]).reshape(T, H, nope + vd)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    s = (jnp.einsum("thd,shd->hts", q_nope, k_nope, precision=HIGHEST)
         + jnp.einsum("thd,sd->hts", q_rope, k_rope, precision=HIGHEST)
         ) * softmax_scale(cfg)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    out = jnp.einsum("hts,shd->thd", p, v, precision=HIGHEST)
    return _mm(out.reshape(T, H * vd), w["wo"])


def gated(h, wg, wu, wd):
    return _mm(jax.nn.silu(_mm(h, wg)) * _mm(h, wu), wd)


def route(cfg, h, wr):
    """(scores [T, E], selected [T, E] bool, weight [T, E])."""
    E, G = wr.shape[1], cfg["n_group"]
    k, kg = cfg["num_experts_per_tok"], cfg["topk_group"]
    s = jax.nn.sigmoid(_mm(h, wr))
    group = jnp.sort(s.reshape(-1, G, E // G), -1)[..., -2:].sum(-1)
    kept = group >= jnp.sort(group, -1)[:, G - kg, None]
    masked = jnp.where(jnp.repeat(kept, E // G, axis=1), s, -1.0)
    sel = masked >= jnp.sort(masked, -1)[:, E - k, None]
    w = cfg["routed_scaling_factor"] * jnp.where(sel, s, 0.0) / jnp.sum(
        jnp.where(sel, s, 0.0), -1, keepdims=True)
    return s, sel, w


LADDER = (0.001, 0.003, 0.006, 0.012, 0.03, 0.06)


def held_margin(cfg, h, wr, held_start, held):
    """How far every router logit may move, as a share of the logits'
    root mean square (an error of the router's input moves them by that
    share of their size), before the selection of a HELD expert can
    change: ``[T]``, the largest ``delta`` of :data:`LADDER` at which it
    is still decided (0: not even at the smallest).  Interval logic on scores ``sigmoid(logit +- delta)``: a
    group surely stays if fewer than ``topk_group`` others can beat its
    lowest score, surely goes if ``topk_group`` others surely beat its
    highest; a held expert is decided if its group surely goes, or if
    every group is sure and, among the experts of the groups that stay,
    fewer than ``num_experts_per_tok`` can beat its lowest score (in) or
    that many surely beat its highest (out)."""
    E, G = wr.shape[1], cfg["n_group"]
    k, kg = cfg["num_experts_per_tok"], cfg["topk_group"]
    logit = _mm(h, wr)
    size = jnp.sqrt(jnp.mean(logit * logit))
    mine = slice(held_start, held_start + held)

    def top2(s):
        return jnp.sort(s.reshape(-1, G, E // G), -1)[..., -2:].sum(-1)

    def decided(delta):
        lo = jax.nn.sigmoid(logit - delta * size)
        hi = jax.nn.sigmoid(logit + delta * size)
        glo, ghi = top2(lo), top2(hi)
        can = jnp.sum(ghi[:, None, :] > glo[:, :, None], -1) - 1
        surely = jnp.sum(glo[:, None, :] > ghi[:, :, None], -1)
        stays = jnp.repeat(can < kg, E // G, axis=1)
        goes = jnp.repeat(surely >= kg, E // G, axis=1)
        # who CAN beat a held expert: the experts of every group that may
        # stay; who SURELY does: those of the groups that surely stay
        may_hi = jnp.where(~goes, hi, -1.0)
        sure_lo = jnp.where(stays, lo, -1.0)
        can_e = jnp.sum(may_hi[:, None, :] > lo[:, mine, None], -1) \
            - ~goes[:, mine]
        sure_e = jnp.sum(sure_lo[:, None, :] > hi[:, mine, None], -1)
        ok = goes[:, mine] | (sure_e >= k) | (stays[:, mine] & (can_e < k))
        return jnp.all(ok, -1)

    out, ok = jnp.zeros(h.shape[0], jnp.float32), True
    for delta in LADDER:
        ok = ok & decided(delta)
        out = jnp.where(ok, delta, out)
    return out


def moe_ffn(cfg, w, h, held_start=0, shared=True):
    """One expert layer's FFN for the chip that holds ``w["weg"].shape[0]``
    experts from ``held_start``.  Returns (y, selected [T, E])."""
    _, sel, weight = route(cfg, h, w["wr"])
    y = gated(h, w["wsg"], w["wsu"], w["wsd"]) if shared \
        else jnp.zeros_like(h)
    for j in range(w["weg"].shape[0]):
        y = y + weight[:, held_start + j, None] * gated(
            h, w["weg"][j], w["weu"][j], w["wed"][j])
    return y, sel


def layer(cfg, w, x, held_start=0):
    """One block on x [T, D] with float32 leaves ``w``: the dense FFN where
    ``w`` has ``wg``, the expert FFN where it has ``wr``.  Returns (x,
    selected or None, margin or None)."""
    eps = cfg["rms_norm_eps"]
    x = x + mla(cfg, w, rms(x, w["g1"], eps))
    h = rms(x, w["g2"], eps)
    if "wr" not in w:
        return x + gated(h, w["wg"], w["wu"], w["wd"]), None, None
    y, sel = moe_ffn(cfg, w, h, held_start)
    return x + y, sel, held_margin(cfg, h, w["wr"], held_start,
                                   w["weg"].shape[0])


@functools.lru_cache(maxsize=None)
def _layer_step(cfg_json, held_start):
    """One block, jitted once per configuration: a second pass over another
    sequence of the same length compiles nothing."""
    cfg = json.loads(cfg_json)
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    return jax.jit(lambda w, x: layer(cfg, f32(w), x, held_start))


def forward(cfg, layer_leaves, shared, toks, held_start=0):
    """``layer_leaves(i)`` -> layer i's leaves (any float dtype: upcast
    here, one layer at a time); ``shared``: embed [V, D], head [D, V], gf.
    Returns (logits f32 [T, V], selected [L-1, T, E] bool, margin [L-1, T])."""
    x = shared["embed"][toks].astype(jnp.float32)
    step = _layer_step(json.dumps(cfg, sort_keys=True), held_start)
    sels, margins = [], []
    for i in range(cfg["num_hidden_layers"]):
        x, sel, margin = step(layer_leaves(i), x)
        if sel is not None:
            sels.append(sel)
            margins.append(margin)
    logits = _mm(rms(x, shared["gf"].astype(jnp.float32),
                     cfg["rms_norm_eps"]),
                 shared["head"].astype(jnp.float32))
    return logits, jnp.stack(sels), jnp.stack(margins)
