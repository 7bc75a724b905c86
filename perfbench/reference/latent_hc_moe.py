"""Plain reference of the ``latent_hc_moe`` family: the ``latent_moe``
family's decoder (latent attention, YaRN rotary, gated FFNs, sigmoid-routed
experts beside a shared one: the same equations, imported from
``perfbench/reference/latent_moe.py``) with three things of its own, written
here from the equations below in straightforward float32 ``jax.numpy`` with
matmuls at ``highest`` precision; the whole sequence at once, no cache, no
batching, no kernels, nothing imported from ``bluefog_tpu``.

``cfg`` is the configuration file's dict (the source's key names).

1. **A residual of n = hc_mult streams** (manifold-constrained
   hyper-connections, arXiv:2512.24880 on arXiv:2409.19606).  Per token the
   residual is ``X [n, D]``; entry ``X_i = embed[token]`` for every i, exit
   ``x = sum_i X_i`` before the final RMSNorm and the head.  Each sublayer
   ``F`` (attention, then the FFN) of a layer has its own ``phi [n D, n^2 + 2
   n]``, three gains ``alpha`` and a bias ``b [n^2 + 2 n]``:

       xt     = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)    all n D values
       c      = xt phi
       H_pre  = sigmoid(alpha[0] c[0:n] + b[0:n])
       H_post = 2 sigmoid(alpha[1] c[n:2n] + b[n:2n])
       M0     = exp(clip(alpha[2] mat(c[2n:]) + mat(b[2n:]), clamp_min, clamp_max))
       Mt     = rows(cols(Mt-1)),  cols(M) = M / (sum_i M_ij + hc_eps),
                rows(M) = M / (sum_j M_ij + hc_eps),  t = 1 .. hc_sinkhorn_iters
       H_res  = M at the last round       (mat: row-major, [i, j] = 2n + i n + j)
       h      = sum_i H_pre[i] X_i
       y      = F(RMS(h; g))                the block's own g1 / g2
       X'_i   = sum_j H_res[i, j] X_j + H_post[i] y

2. **``first_k_dense_replace`` leading dense layers**, not one.

3. **A score-correction bias** (``topk_method: noaux_tc``): ``s = sigmoid(h
   wr)``; the ``num_experts_per_tok`` experts with the highest ``s + e_bias``
   are taken (``n_group`` 1: no group step), and their weights are the RAW
   ``s`` of the taken, normalised, times ``routed_scaling_factor``.

The chip holds experts ``held_start .. held_start + held - 1`` (``held`` =
the length of ``weg``; all of them in the configuration the benchmark runs),
and :func:`moe_ffn` takes any held range, so a test can add the shares of
several chips up to the uncut layer.

:func:`forward` runs a layer at a time (``layer_leaves(i)`` hands it layer
``i``'s leaves, upcast here) and attention a group of heads at a time (a
head's output goes through its own rows of ``wo``, so the groups' outputs
add), so that at the cell's lengths one layer in float32 and one group's
``[heads, T, T]`` scores fit beside the served weights.  Besides the logits
it returns, per expert layer, the selection and how far it is from
flipping (:func:`held_margin`, on the BIASED scores).
"""
import functools
import json

import jax
import jax.numpy as jnp

from perfbench.reference.latent_moe import LADDER, gated, mla, rms

HIGHEST = jax.lax.Precision.HIGHEST
# heads whose [heads, T, T] float32 scores one pass of the attention holds
HEAD_GROUP = 8


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def sinkhorn(m, iters, eps):
    """``iters`` rounds of column-then-row normalisation of positive
    matrices ``m`` ``[..., n, n]``."""
    for _ in range(iters):
        m = m / (jnp.sum(m, -2, keepdims=True) + eps)      # columns: over i
        m = m / (jnp.sum(m, -1, keepdims=True) + eps)      # rows: over j
    return m


def coefficients(cfg, phi, alpha, b, X):
    """(H_pre [T, n], H_post [T, n], H_res [T, n, n]) of the streams ``X``
    ``[T, n, D]`` under one sublayer's maps."""
    T, n, _ = X.shape
    v = X.reshape(T, -1)
    xt = v / jnp.sqrt(jnp.mean(v * v, -1, keepdims=True)
                      + cfg["rms_norm_eps"])
    c = _mm(xt, phi.reshape(v.shape[1], -1))
    pre = jax.nn.sigmoid(alpha[0] * c[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * c[:, n:2 * n] + b[n:2 * n])
    m0 = jnp.exp(jnp.clip(alpha[2] * c[:, 2 * n:] + b[2 * n:],
                          cfg["mhc_h_res_clamp_min"],
                          cfg["mhc_h_res_clamp_max"])).reshape(T, n, n)
    return pre, post, sinkhorn(m0, cfg["hc_sinkhorn_iters"], cfg["hc_eps"])


def sublayer(cfg, maps, g, X, F):
    """One sublayer on the streams: read their combination, apply ``F`` to
    its RMSNorm, leave the streams remixed with the output spread over
    them.  ``F(h) -> (y, aux)``; returns ``(X', aux)``."""
    pre, post, res = coefficients(cfg, *maps, X)
    h = jnp.einsum("ti,tid->td", pre, X, precision=HIGHEST)
    y, aux = F(rms(h, g, cfg["rms_norm_eps"]))
    return (jnp.einsum("tij,tjd->tid", res, X, precision=HIGHEST)
            + post[:, :, None] * y[:, None, :]), aux


def attention(cfg, w, h):
    """The family's latent attention, a group of heads at a time."""
    H, nope = cfg["num_attention_heads"], cfg["qk_nope_head_dim"]
    rope, vd = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    step = min(HEAD_GROUP, H)
    out = 0.0
    for lo in range(0, H, step):
        cols = lambda a, per: a[:, lo * per:(lo + step) * per]
        part = dict(w, wqb=cols(w["wqb"], nope + rope),
                    wkvb=cols(w["wkvb"], nope + vd),
                    wo=w["wo"][lo * vd:(lo + step) * vd])
        out = out + mla(dict(cfg, num_attention_heads=step), part, h)
    return out


def route(cfg, h, wr, e_bias):
    """(raw scores [T, E], selected [T, E] bool, weight [T, E])."""
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise ValueError("this family's router has one group")
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(_mm(h, wr))
    by = s + e_bias
    sel = by >= jnp.sort(by, -1)[:, -k, None]
    w = cfg["routed_scaling_factor"] * jnp.where(sel, s, 0.0) / jnp.sum(
        jnp.where(sel, s, 0.0), -1, keepdims=True)
    return s, sel, w


def held_margin(cfg, h, wr, e_bias, held_start, held):
    """How far every router logit may move, as a share of the logits' root
    mean square, before the selection of a HELD expert can change: ``[T]``,
    the largest ``delta`` of ``LADDER`` at which every held expert is still
    decided (0: not even at the smallest).  Interval logic on the biased
    scores ``sigmoid(logit +- delta) + e_bias``: an expert is surely taken
    if fewer than ``num_experts_per_tok`` others can beat its lowest score,
    surely left if that many surely beat its highest."""
    k = cfg["num_experts_per_tok"]
    logit = _mm(h, wr)
    size = jnp.sqrt(jnp.mean(logit * logit))
    mine = slice(held_start, held_start + held)

    def decided(delta):
        lo = jax.nn.sigmoid(logit - delta * size) + e_bias
        hi = jax.nn.sigmoid(logit + delta * size) + e_bias
        can = jnp.sum(hi[:, None, :] > lo[:, mine, None], -1) - 1
        sure = jnp.sum(lo[:, None, :] > hi[:, mine, None], -1)
        return jnp.all((can < k) | (sure >= k), -1)

    out, ok = jnp.zeros(h.shape[0], jnp.float32), True
    for delta in LADDER:
        ok = ok & decided(delta)
        out = jnp.where(ok, delta, out)
    return out


def moe_ffn(cfg, w, h, held_start=0, shared=True):
    """One expert layer's FFN for the chip that holds ``w["weg"].shape[0]``
    experts from ``held_start``: every held expert applied to every token
    and weighed by its routing weight (0 where it was not taken), one
    expert after the other (a loop the compiler sees once: 64 experts
    written out took four minutes to compile at the cell's size; an expert's
    matrices are upcast as its turn comes).  Returns (y, selected [T, E])."""
    _, sel, weight = route(cfg, h, w["wr"], w["eb"])
    y = gated(h, w["wsg"], w["wsu"], w["wsd"]) if shared \
        else jnp.zeros_like(h)
    held = w["weg"].shape[0]
    mine = weight[:, held_start:held_start + held].T          # [held, T]

    def one(y, e):
        wj, wg, wu, wd = e
        f32 = lambda a: a.astype(jnp.float32)
        return y + wj[:, None] * gated(h, f32(wg), f32(wu), f32(wd)), None
    y, _ = jax.lax.scan(one, y, (mine, w["weg"], w["weu"], w["wed"]))
    return y, sel


def layer(cfg, w, X, held_start=0):
    """One block on the streams ``X`` ``[T, n, D]`` with float32 leaves
    ``w``: the dense FFN where ``w`` has ``wg``, the expert FFN where it
    has ``wr``.  Returns (X, selected or None, margin or None)."""
    X, _ = sublayer(cfg, (w["h1p"], w["h1a"], w["h1b"]), w["g1"], X,
                    lambda h: (attention(cfg, w, h), None))

    def ffn(h):
        if "wr" not in w:
            return gated(h, w["wg"], w["wu"], w["wd"]), (None, None)
        y, sel = moe_ffn(cfg, w, h, held_start)
        return y, (sel, held_margin(cfg, h, w["wr"], w["eb"], held_start,
                                    w["weg"].shape[0]))
    X, (sel, margin) = sublayer(cfg, (w["h2p"], w["h2a"], w["h2b"]),
                                w["g2"], X, ffn)
    return X, sel, margin


@functools.lru_cache(maxsize=None)
def _layer_step(cfg_json, held_start):
    """One block, jitted once per configuration: a second pass over another
    sequence of the same length compiles nothing."""
    cfg = json.loads(cfg_json)
    # the held experts' stacks are upcast an expert at a time (moe_ffn)
    f32 = lambda w: {k: a if k in ("weg", "weu", "wed")
                     else a.astype(jnp.float32) for k, a in w.items()}
    return jax.jit(lambda w, X: layer(cfg, f32(w), X, held_start))


def forward(cfg, layer_leaves, shared, toks, held_start=0):
    """``layer_leaves(i)`` -> layer i's leaves (any float dtype: upcast
    here, one layer at a time); ``shared``: embed [V, D], head [D, V], gf.
    Returns (logits f32 [T, V], selected [Lx, T, E] bool, margin [Lx, T])
    over the ``Lx`` expert layers."""
    x = shared["embed"][toks].astype(jnp.float32)
    X = jnp.repeat(x[:, None, :], cfg["hc_mult"], axis=1)
    step = _layer_step(json.dumps(cfg, sort_keys=True), held_start)
    sels, margins = [], []
    for i in range(cfg["num_hidden_layers"]):
        X, sel, margin = step(layer_leaves(i), X)
        if sel is not None:
            sels.append(sel)
            margins.append(margin)
    logits = _mm(rms(jnp.sum(X, 1), shared["gf"].astype(jnp.float32),
                     cfg["rms_norm_eps"]),
                 shared["head"].astype(jnp.float32))
    return logits, jnp.stack(sels), jnp.stack(margins)
