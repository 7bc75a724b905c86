"""Plain reference of the ``latent_scmoe`` family: the forward pass of a
decoder of shortcut-connected double layers (two latent attentions, two
dense gated FFNs, ONE expert layer that reads the first half's normed
activation and whose result joins after the second half's FFN) under a
softmax router whose last outputs are identity experts, as ONE chip of an
expert-parallel deployment computes it, in straightforward float32
``jax.numpy`` with matmuls at ``highest`` precision: the whole sequence at
once, unabsorbed attention, every held expert applied densely to every
token and masked by its weight, no cache, no batching, no kernels.  Written
from the equations below; imports nothing from ``bluefog_tpu``.

``cfg`` is the configuration file's dict (the source's key names).  With
``RMS(x; g) = x / sqrt(mean(x^2) + eps) * g`` and ``FFN(h; w) = (silu(h wg)
* (h wu)) wd``, one layer on the residual x [T, D], its halves' leaves
``w1`` and ``w2``:

    x1 = x  + MLA(RMS(x;  w1.g1); w1)
    h1 = RMS(x1; w1.g2)
    m  = MoE(h1; w1)                          computed here, used below
    x2 = x1 + FFN(h1; w1)
    x3 = x2 + MLA(RMS(x2; w2.g1); w2)
    x4 = x3 + FFN(RMS(x3; w2.g2); w2) + m     the layer's output
    logits = RMS(x_L; gf) @ head              (over the vocabulary slice)

    MLA(h)   q = s_q (RMS(h wqa; gq) wqb)     -> H heads of [q_nope | q_rope]
             [c | kr] = h wkva;  c = RMS(c; gkv)
             [k_nope_i | v_i] = (s_kv c) wkvb               per head i
             score_i(t, s) = (q_nope_i(t).k_nope_i(s)
                              + rot(q_rope_i)(t).rot(kr)(s)) (nope + rope)^-0.5
             out = concat_i(causal_softmax(score_i) v_i) wo
             s_q = sqrt(D / q_lora_rank) where ``mla_scale_q_lora``, s_kv =
             sqrt(D / kv_lora_rank) where ``mla_scale_kv_lora``: s_q on both
             parts of the query, s_kv on the normed compressed vector (keys'
             unturned part and values, not the rotary key)
    rot      halves pairing (channel i with i + d/2), the plain ladder
             angle_i(p) = p theta^(-2i/d): no YaRN, no attention factor
    MoE(h)   p = softmax(h wr) over all E + Z outputs (float32)
             S = the moe_topk outputs with the largest p + eb
             w_e = routed_scaling_factor p_e for e in S   (no renormalising)
             m = sum_{e in S, HELD} w_e FFN_e(h) + (sum_{e in S, e >= E} w_e) h
             E = the published ``n_routed_experts``: outputs from E on are
             identity experts; what the absent real experts would add is
             left out

Departures from the source, each noted in the configuration file too: the
rotary pairing is halves, not interleaved pairs (equal up to a fixed
permutation of the rotary columns of wqb and wkva); ``norm_topk_prob`` is
absent from the source's config and read as false; the router has no bias
of its own (``router_bias`` false) beside the selection-only
``e_score_correction_bias``.

The cut: the chip holds experts ``held_start .. held_start + held - 1``
(``held`` = the length of ``weg``); :func:`moe` takes any held range and
leaves the identity part out on request, so a test can add the shares of
all chips, with the identity part counted once, up to the uncut layer.

:func:`forward` runs a SUBLAYER at a time (a layer's leaves in float32 are
3.7 GB at the published widths): the caller hands it ``layer_leaves(i)``,
which returns layer ``i``'s two halves' leaves in any float dtype; an
attention, a dense FFN, the router and ONE expert are each upcast inside a
jitted step of their own and freed, and attention runs a group of heads at
a time.  The cut of 12 of 768 sits where neighbouring scores lie 5 % of
their size apart, and the program's bfloat16 activations move a score by
about as much: the function jumps at most positions.  So ``forward`` takes
the selections to evaluate under (``chosen``: the PROGRAM's own; weights
still from the reference's scores), and hands back its own selections and
scores so that the caller can hold every difference to a rounding tie
(:func:`tie_distance`).
"""
import functools
import json
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
# attention's float32 scores are kept for this many heads at a time
# (8 heads x 4,096^2 x 4 B = 0.5 GB, and as much again for the softmax)
HEAD_GROUP = 8


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def f32(tree):
    """The upcast every step makes of the leaves it is handed (a control
    replaces it: the leaves through int8 and back)."""
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rot(x, theta):
    """x [T, ..., d] at positions 0..T-1, the plain ladder."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs[None]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def scales(cfg):
    """(s_q, s_kv): ``sqrt(hidden / rank)`` where the config switches a
    scale on, else 1."""
    D = cfg["hidden_size"]
    return (math.sqrt(D / cfg["q_lora_rank"])
            if cfg["mla_scale_q_lora"] else 1.0,
            math.sqrt(D / cfg["kv_lora_rank"])
            if cfg["mla_scale_kv_lora"] else 1.0)


def mla(cfg, w, h):
    T = h.shape[0]
    H, nope = cfg["num_attention_heads"], cfg["qk_nope_head_dim"]
    rope, vd, C = cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    s_q, s_kv = scales(cfg)
    q = s_q * _mm(rms(_mm(h, w["wqa"]), w["gq"], eps), w["wqb"]).reshape(
        T, H, nope + rope)
    q_nope, q_rope = q[..., :nope], rot(q[..., nope:], theta)
    kv = _mm(h, w["wkva"])
    c, k_rope = rms(kv[:, :C], w["gkv"], eps), rot(kv[:, C:], theta)
    kvb = _mm(s_kv * c, w["wkvb"]).reshape(T, H, nope + vd)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]

    def heads(args):
        qn, qr, kn, vv = args                   # [T, g, .] each
        s = (jnp.einsum("thd,shd->hts", qn, kn, precision=HIGHEST)
             + jnp.einsum("thd,sd->hts", qr, k_rope, precision=HIGHEST)
             ) * (nope + rope) ** -0.5
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", p, vv, precision=HIGHEST)

    g = math.gcd(H, HEAD_GROUP)
    split = lambda a: jnp.moveaxis(a.reshape(T, H // g, g, -1), 1, 0)
    out = jax.lax.map(heads, (split(q_nope), split(q_rope), split(k_nope),
                              split(v)))        # [H / g, T, g, v]
    return _mm(jnp.moveaxis(out, 0, 1).reshape(T, H * vd), w["wo"])


def gated(h, wg, wu, wd):
    return _mm(jax.nn.silu(_mm(h, wg)) * _mm(h, wu), wd)


def route(cfg, h, wr, eb):
    """(p [T, E + Z] raw scores, by [T, E + Z] what selection goes by,
    picked [T, k] the reference's own selection, best first, size: the
    router logits' root mean square)."""
    logit = _mm(h, wr)
    p = jax.nn.softmax(logit, axis=-1)
    by = p + eb
    return p, by, jax.lax.top_k(by, cfg["moe_topk"])[1], \
        jnp.sqrt(jnp.mean(logit * logit))


def weights_of(cfg, p, chosen):
    """[T, E + Z]: ``routed_scaling_factor p_e`` at the outputs ``chosen``
    [T, k] names (-1 names none), 0 elsewhere."""
    E = p.shape[-1]
    sel = jnp.any(chosen[..., None] == jnp.arange(E), axis=-2)
    return jnp.where(sel, cfg["routed_scaling_factor"] * p, 0.0)


def first_zero(cfg, router_outputs):
    """The first identity output: the router's last ``zero_expert_num``."""
    return router_outputs - cfg["zero_expert_num"]


ATTN = ("wqa", "gq", "wqb", "wkva", "gkv", "wkvb", "wo")
FFN = ("wg", "wu", "wd")


@functools.lru_cache(maxsize=None)
def _steps(cfg_json):
    """The sublayer steps, jitted once per configuration: each upcasts the
    leaves it is handed (:func:`f32`, looked up when traced) and nothing
    else, so one sublayer's float32 leaves are alive at a time."""
    cfg = json.loads(cfg_json)
    eps = cfg["rms_norm_eps"]

    @jax.jit
    def attend(w, g1, x):
        w, g1 = f32((w, g1))
        return x + mla(cfg, w, rms(x, g1, eps))

    @jax.jit
    def normed(g2, x):
        return rms(x, f32(g2), eps)

    @jax.jit
    def dense(w, h):
        w = f32(w)
        return gated(h, w["wg"], w["wu"], w["wd"])

    @jax.jit
    def router(wr, eb, h, chosen):
        wr, eb = f32((wr, eb))
        p, by, picked, size = route(cfg, h, wr, eb)
        weight = weights_of(cfg, p, picked if chosen is None else chosen)
        zero = first_zero(cfg, wr.shape[1])
        return (jnp.sum(weight[:, zero:], -1, keepdims=True) * h, weight,
                (p, by, picked, size))

    @jax.jit
    def expert(wg, wu, wd, h, col):
        wg, wu, wd = f32((wg, wu, wd))
        return col[:, None] * gated(h, wg, wu, wd)

    return attend, normed, dense, router, expert


def moe(cfg, w, h, held_start=0, chosen=None, identity=True):
    """One expert layer on the normed tokens h [T, D] for the chip that
    holds ``w["weg"].shape[0]`` experts from ``held_start``, under the
    selections ``chosen`` [T, k] (-1 names no output; None: the
    reference's own).  ``identity`` False leaves the identity experts'
    part out (every chip computes it alike: a sum of shares counts it
    once).  The router and one expert at a time are upcast.  Returns (m,
    (p, by, picked, size))."""
    _, _, _, router, expert = _steps(json.dumps(cfg, sort_keys=True))
    zero, weight, routed = router(w["wr"], w["eb"], h, chosen)
    m = zero if identity else jnp.zeros_like(h)
    for j in range(w["weg"].shape[0]):
        m = m + expert(w["weg"][j], w["weu"][j], w["wed"][j], h,
                       weight[:, held_start + j])
    return m, routed


def double_layer(cfg, w1, w2, x, held_start=0, chosen=None, join="second"):
    """One double layer on x [T, D], a sublayer at a time; ``w1`` / ``w2``
    the halves' leaves in any float dtype, ``chosen`` as in :func:`moe`.
    ``join`` ``"first"`` adds the experts' result behind the FIRST half (a
    control: the layer without its shortcut).  Returns (x, (p, by, picked,
    size))."""
    attend, normed, dense, _, _ = _steps(json.dumps(cfg, sort_keys=True))
    pick = lambda w, names: {k: w[k] for k in names}
    x = attend(pick(w1, ATTN), w1["g1"], x)
    h = normed(w1["g2"], x)
    m, routed = moe(cfg, w1, h, held_start, chosen)
    x = x + dense(pick(w1, FFN), h)
    if join == "first":
        x = x + m
    x = attend(pick(w2, ATTN), w2["g1"], x)
    x = x + dense(pick(w2, FFN), normed(w2["g2"], x))
    return (x + m if join == "second" else x), routed


def forward(cfg, layer_leaves, shared, toks, held_start=0, chosen=None,
            join="second"):
    """``layer_leaves(i)`` -> layer i's ``(first half, second half)``
    leaves (any float dtype: upcast here, a sublayer at a time);
    ``shared``: embed [V, D], head [D, V], gf; ``chosen`` [L, T, k]: the
    selections to evaluate under (None: the reference's own).  Returns
    (logits f32 [T, V], p [L, T, E + Z], by [L, T, E + Z], picked [L, T,
    k], size [L])."""
    x = shared["embed"][toks].astype(jnp.float32)
    routed = []
    for i in range(cfg["num_layers"]):
        x, r = double_layer(cfg, *layer_leaves(i), x, held_start,
                            None if chosen is None else chosen[i], join)
        routed.append(r)
    logits = _mm(rms(x, shared["gf"].astype(jnp.float32),
                     cfg["rms_norm_eps"]),
                 shared["head"].astype(jnp.float32))
    return (logits,) + tuple(jnp.stack(part) for part in zip(*routed))


def tie_distance(p, by, picked, size):
    """[..., E + Z]: how far an output's router logit and the cut's would
    each have to move towards the other, as a share of the router logits'
    root mean square ``size`` [...], for the output's ``p + eb`` to meet
    the selection's cut (the ``k``-th largest ``by``, ``picked[..., -1]``):
    ``|by_e - cut| / (p_e (1 - p_e) + p_cut (1 - p_cut))``, the softmax's
    slopes in the two logits, over ``size``.  (An error of the router's
    input moves every logit by a like share of ``size``, and a score by
    that times its slope: an output of a small score near the cut on its
    bias is flipped by the cut's own move.)  An output by which a
    program's selection differs from the reference's lies within a rounding
    of the cut, or the route is at fault."""
    last = picked[..., -1:]
    cut, p_cut = (jnp.take_along_axis(a, last, -1) for a in (by, p))
    slope = p * (1.0 - p) + p_cut * (1.0 - p_cut)
    return jnp.abs(by - cut) / jnp.maximum(slope, 1e-30) \
        / size[..., None, None]
