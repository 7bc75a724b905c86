"""Plain reference of the ``hybrid_moe`` family: the forward pass of a
decoder whose layers attend in two ways (a sliding window or every earlier
position, by ``layer_types``), with grouped-query heads, RMSNorm over each
head of q and k, the norm on each sublayer's OUTPUT, a leading dense layer
and sigmoid-routed expert layers, as ONE chip of an expert-parallel
deployment computes it, in straightforward float32 ``jax.numpy`` with
matmuls at ``highest`` precision: the whole sequence at once under an
explicit ``[T, T]`` mask per kind (a block of heads at a time, so that the
scores fit), K and V repeated per group, every held expert applied densely
to every token and masked by its weight; no cache, no ring, no batching, no
kernels.  Written from the equations below; imports nothing from
``bluefog_tpu``.

``cfg`` is the configuration file's dict (the source's key names).  With
``RMS(z; g) = z / sqrt(mean(z^2) + eps) * g``, for one sequence x[T, D]:

    block l  x += RMS(Attn_l(x); g1);  x += RMS(FFN_l(x); g2)
             (the sublayer reads the residual stream itself; its OUTPUT is
             normed before it is added)
    logits   RMS(x_L; gf) @ head                 (over the vocabulary slice)

    Attn(x)  q = x wq -> H heads of Dh;  k = x wk, v = x wv -> Hkv heads
             q_i = RMS(q_i; gq), k_j = RMS(k_j; gk)   over the Dh of a head,
                                                      one scale for all heads
             sliding_attention: q, k = rot(q), rot(k); full_attention: not
             score_i(t, s) = q_i(t).k_[i / (H/Hkv)](s) / sqrt(Dh)  for s <= t,
                             and on a sliding layer only for t - s < window
             out = concat_i(softmax_s(score_i) v_[i / (H/Hkv)]) wo
    rot      halves pairing (channel i with i + Dh/2), the whole head:
             angle_i(p) = p theta^(-2i/Dh), no scaling
    dense    FFN(h) = (silu(h wg) * h wu) wd
    sparse   s = sigmoid(h wr)  [E];  the num_experts_per_tok highest;
             w_e = routed_scaling_factor s_e / sum_selected s;
             FFN(h) = shared(h) + sum_{e selected and HELD} w_e expert_e(h)

The cut: the chip holds experts ``held_start .. held_start + held - 1``
(``held`` = the length of ``weg``), and what the absent experts would add
is left out; :func:`moe_ffn` takes any held range, so a test can add the
shares of all chips up to the uncut layer.

:func:`forward` runs a layer at a time: the caller hands it
``layer_leaves(i)``, so that on the chip one layer at a time is upcast to
float32 and freed.  Besides the logits it returns, per expert layer, the
selection and how far each HELD expert's decision is from flipping
(:func:`held_margin`): the selection sits on near-ties that the program's
bfloat16 activations flip, and the function is compared only where it is
decided.
"""
import functools
import json

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
# float32 scores of one block of heads, [heads, T, T], stay inside this
SCORE_BYTES = 1 << 29


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rot(x, theta):
    """x [T, heads, d] at positions 0..T-1."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def mask_of(kind, T, window):
    """[T, T] bool: may the query at row t see the key at column s."""
    t, s = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    keep = s <= t
    if kind == "sliding_attention":
        keep = keep & (t - s < window)
    return keep


def attention(cfg, w, x, kind):
    T = x.shape[0]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    Dh, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    q = rms(_mm(x, w["wq"]).reshape(T, H, Dh), w["gq"], eps)
    k = rms(_mm(x, w["wk"]).reshape(T, Hkv, Dh), w["gk"], eps)
    v = _mm(x, w["wv"]).reshape(T, Hkv, Dh)
    if kind == "sliding_attention":
        theta = float(cfg["rope_parameters"]["rope_theta"])
        q, k = rot(q, theta), rot(k, theta)
    k, v = (jnp.repeat(a, H // Hkv, axis=1) for a in (k, v))  # head i: i // G
    keep = mask_of(kind, T, cfg["sliding_window"])

    def heads(args):                     # a block of heads, [b, T, Dh] each
        qb, kb, vb = args
        s = jnp.einsum("htd,hsd->hts", qb, kb, precision=HIGHEST) * Dh ** -0.5
        p = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hts,hsd->htd", p, vb, precision=HIGHEST)

    b = max(c for c in range(1, H + 1)
            if H % c == 0 and (c == 1 or c * T * T * 4 <= SCORE_BYTES))
    split = lambda a: a.transpose(1, 0, 2).reshape(H // b, b, T, Dh)
    out = jax.lax.map(heads, (split(q), split(k), split(v)))
    return _mm(out.reshape(H, T, Dh).transpose(1, 0, 2).reshape(T, H * Dh),
               w["wo"])


def gated(h, wg, wu, wd):
    return _mm(jax.nn.silu(_mm(h, wg)) * _mm(h, wu), wd)


def route(cfg, h, wr):
    """(scores [T, E], selected [T, E] bool, weight [T, E])."""
    E, k = wr.shape[1], cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(_mm(h, wr))
    sel = s >= jnp.sort(s, -1)[:, E - k, None]
    w = cfg["routed_scaling_factor"] * jnp.where(sel, s, 0.0) / jnp.sum(
        jnp.where(sel, s, 0.0), -1, keepdims=True)
    return s, sel, w


LADDER = (0.001, 0.003, 0.006, 0.012, 0.03, 0.06)


def held_margin(cfg, h, wr, held_start, held):
    """How far every router logit may move, as a share of the logits' root
    mean square (an error of the router's input moves them by that share
    of their size), before the selection of a HELD expert can change:
    ``[T]``, the largest ``delta`` of :data:`LADDER` at which every held
    expert is still decided (0: not even at the smallest).  Interval logic
    on scores ``sigmoid(logit +- delta)``: a held expert is surely in if
    fewer than ``num_experts_per_tok`` others can beat its lowest score,
    surely out if that many surely beat its highest."""
    k = cfg["num_experts_per_tok"]
    logit = _mm(h, wr)
    size = jnp.sqrt(jnp.mean(logit * logit))
    mine = slice(held_start, held_start + held)

    def decided(delta):
        lo = jax.nn.sigmoid(logit - delta * size)
        hi = jax.nn.sigmoid(logit + delta * size)
        # its own highest beats its own lowest: not another expert
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
    experts from ``held_start``.  Returns (y, selected [T, E])."""
    _, sel, weight = route(cfg, h, w["wr"])
    y = gated(h, w["wsg"], w["wsu"], w["wsd"]) if shared \
        else jnp.zeros_like(h)
    for j in range(w["weg"].shape[0]):
        y = y + weight[:, held_start + j, None] * gated(
            h, w["weg"][j], w["weu"][j], w["wed"][j])
    return y, sel


def layer(cfg, w, x, kind, held_start=0):
    """One block of a ``kind`` layer (the source's ``layer_types`` word) on
    x [T, D] with float32 leaves ``w``: the dense FFN where ``w`` has
    ``wg``, the expert FFN where it has ``wr``.  Returns (x, selected or
    None, margin or None)."""
    eps = cfg["rms_norm_eps"]
    x = x + rms(attention(cfg, w, x, kind), w["g1"], eps)
    if "wr" not in w:
        return x + rms(gated(x, w["wg"], w["wu"], w["wd"]), w["g2"],
                       eps), None, None
    y, sel = moe_ffn(cfg, w, x, held_start)
    return x + rms(y, w["g2"], eps), sel, held_margin(
        cfg, x, w["wr"], held_start, w["weg"].shape[0])


@functools.lru_cache(maxsize=None)
def _layer_step(cfg_json, kind, held_start):
    """One block, jitted once per configuration and kind: a second pass
    over another sequence of the same length compiles nothing."""
    cfg = json.loads(cfg_json)
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    return jax.jit(lambda w, x: layer(cfg, f32(w), x, kind, held_start))


def forward(cfg, layer_leaves, shared, toks, held_start=0):
    """``layer_leaves(i)`` -> layer i's leaves (any float dtype: upcast
    here, one layer at a time); ``shared``: embed [V, D], head [D, V], gf.
    Returns (logits f32 [T, V], selected [expert layers, T, E] bool, margin
    [expert layers, T])."""
    x = shared["embed"][toks].astype(jnp.float32)
    cfg_json = json.dumps(cfg, sort_keys=True)
    sels, margins = [], []
    for i in range(cfg["num_hidden_layers"]):
        step = _layer_step(cfg_json, cfg["layer_types"][i], held_start)
        x, sel, margin = step(layer_leaves(i), x)
        if sel is not None:
            sels.append(sel)
            margins.append(margin)
    logits = _mm(rms(x, shared["gf"].astype(jnp.float32),
                     cfg["rms_norm_eps"]),
                 shared["head"].astype(jnp.float32))
    return logits, jnp.stack(sels), jnp.stack(margins)
