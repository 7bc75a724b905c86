"""Plain reference of the ``ssm_latent_moe`` family: the forward pass of a
decoder whose every layer is ONE mixer behind one RMSNorm (a Mamba-2
state-space mixer, grouped-query attention with no position signal, or
``relu^2`` experts computed in a latent under a bias-corrected sigmoid
router, by the letters of ``hybrid_override_pattern``), as ONE chip of an
expert-parallel deployment computes it, in straightforward float32
``jax.numpy`` with matmuls at ``highest`` precision: the whole sequence at
once, the state-space recurrence TOKEN BY TOKEN and nothing else (no chunks),
attention under an explicit ``[T, T]`` mask a block of heads at a time, every
held expert applied densely to every token and masked by its weight, one
expert upcast at a time; no cache, no batching, no kernels.  Written from the
equations below; imports nothing from ``bluefog_tpu``.

``cfg`` is the configuration file's dict (the source's key names).  With
``RMS(z; g) = z / sqrt(mean(z^2) + eps) * g``, for one sequence x[T, D]:

    layer l  x += mixer_l(RMS(x; g, norm_eps))      one mixer a layer
    logits   RMS(x_L; gf, norm_eps) @ head          (over the vocabulary slice)

    M (Mamba-2)  d_in = mamba_num_heads * mamba_head_dim; G = n_groups;
                 N = ssm_state_size; C = d_in + 2 G N convolved channels
        z, xBC, dt = split(u w_in)                          [d_in], [C], [heads]
        xBC_t  = silu(b_conv + sum_{j<K} w_conv[:, j] xBC_raw_{t-K+1+j})
                 (causal, depthwise, K = conv_kernel; zeros before the prompt)
        x, B, C = split(xBC)            x [heads, head_dim]; B, C [G, N]
        d_t    = softplus(dt_t + dt_bias)                   no clamp
        a_t    = exp(d_t * -exp(A_log))                     in (0, 1)
        S_t    = a_t S_{t-1} + d_t x_t (x) B_t[group of the head];  S_{-1} = 0
        y_t    = S_t C_t[group of the head] + Dskip x_t
        y_t    = RMS per group of d_in / G channels (y_t * silu(z_t); g_y,
                 layer_norm_epsilon)                        the gate BEFORE the norm
        out    = y_t w_out
    * (attention)  q = u wq -> H heads of head_dim; k = u wk, v = u wv -> Hkv
        score_i(t, s) = q_i(t) . k_[i / (H/Hkv)](s) / sqrt(head_dim), s <= t
        out = concat_i(softmax_s(score_i) v_[i / (H/Hkv)]) wo
        nothing is turned and nothing is normed: no position signal
    E (experts)  s = sigmoid(u wr) [E]; chosen = the num_experts_per_tok
        highest of s + e_bias (n_group 1: no group step); w_e = s_e over the
        chosen's sum, times routed_scaling_factor
        l = u wdn                                           [moe_latent_size]
        r = sum_{e chosen and HELD} w_e relu(l w1_e)^2 w2_e
        out = r wup + relu(u ws1)^2 ws2                     (the shared expert)

The cut: the chip holds experts ``held_start .. held_start + held - 1``
(``held`` = the length of ``we1``), and what the absent experts would add is
left out; :func:`moe_ffn` takes any held range, so a test can add the shares
of all chips up to the uncut layer.

:func:`forward` runs a layer at a time (``layer_leaves(i)`` hands it layer
``i``'s leaves, upcast here, an expert layer's experts one at a time), so
that on the chip one layer in float32 fits beside the served weights.
Besides the logits it returns, per expert layer, the experts it chose and
the biased scores it chose them by, and per state-space layer the recurrent
state after the last token that is no padding (``true_len``): what a served
slot holds then, number by number.  With ``chosen`` it takes the
selections as given (the weights still from its own scores): where the
served program's rounding put an expert on the other side of the cut, the
function is compared on the program's side of it.
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


def relu2(h, w1, w2):
    return _mm(jnp.square(jax.nn.relu(_mm(h, w1))), w2)


def recurrence(x, B, C, delta, a, Dskip, true_len=None):
    """``S_t = a_t S_{t-1} + delta_t x_t (x) B_t``, ``y_t = S_t C_t + Dskip
    x_t`` from ``S_{-1} = 0``, token by token: x [T, H, P], B, C [T, H, N]
    (a head's group's, already spread over the heads), delta, a [T, H].
    Returns (y [T, H, P], the state [H, P, N] after token ``true_len - 1``:
    the last one where no ``true_len`` is given; what follows it is
    padding, and its y is nobody's)."""
    def step(S, t):
        x_t, B_t, C_t, d_t, a_t, real = t
        new = a_t[:, None, None] * S \
            + (d_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        y_t = jnp.sum(new * C_t[:, None, :], -1) + Dskip[:, None] * x_t
        return jnp.where(real, new, S), y_t
    T = x.shape[0]
    real = jnp.arange(T) < (T if true_len is None else true_len)
    S0 = jnp.zeros(x.shape[1:] + B.shape[-1:], jnp.float32)
    S, y = jax.lax.scan(step, S0, (x, B, C, delta, a, real))
    return y, S


def mamba(cfg, w, u, true_len=None):
    """The Mamba-2 mixer on the normed u [T, D].  Returns (out [T, D], the
    recurrent state [H, P, N] after token ``true_len - 1``)."""
    T = u.shape[0]
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N, K = cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"]
    d_in = H * P
    proj = _mm(u, w["w_in"])
    z, xbc, dt = (proj[:, :d_in], proj[:, d_in:d_in + d_in + 2 * G * N],
                  proj[:, 2 * d_in + 2 * G * N:])
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc])
    conv = w["b_conv"] + sum(w["w_conv"][:, j] * padded[j:j + T]
                             for j in range(K))
    xbc = jax.nn.silu(conv)
    x = xbc[:, :d_in].reshape(T, H, P)
    spread = lambda t: jnp.repeat(t.reshape(T, G, N), H // G, axis=1)
    B, C = spread(xbc[:, d_in:d_in + G * N]), spread(xbc[:, d_in + G * N:])
    delta = jax.nn.softplus(dt + w["dt_bias"])
    a = jnp.exp(delta * -jnp.exp(w["A_log"]))
    y, S = recurrence(x, B, C, delta, a, w["Dskip"], true_len)
    y = y.reshape(T, d_in) * jax.nn.silu(z)
    y = y.reshape(T, G, d_in // G)
    y = y / jnp.sqrt(jnp.mean(y * y, -1, keepdims=True)
                     + cfg["layer_norm_epsilon"])
    return _mm(y.reshape(T, d_in) * w["g_y"], w["w_out"]), S


def attention(cfg, w, u):
    T = u.shape[0]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    Dh = cfg["head_dim"]
    q = _mm(u, w["wq"]).reshape(T, H, Dh)
    k = jnp.repeat(_mm(u, w["wk"]).reshape(T, Hkv, Dh), H // Hkv, axis=1)
    v = jnp.repeat(_mm(u, w["wv"]).reshape(T, Hkv, Dh), H // Hkv, axis=1)
    keep = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]

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


def route(cfg, h, wr, eb, chosen=None):
    """(biased scores [T, E], picked [T, k] the experts of the k highest
    biased scores, weight [T, E]: the raw scores of the selected experts
    over their sum times the scaling factor, 0 elsewhere).  The selection
    is ``picked``, or ``chosen`` [T, k] where it is given (-1: none)."""
    E, k = wr.shape[1], cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(_mm(h, wr))
    by = s + eb
    picked = jax.lax.top_k(by, k)[1]
    use = picked if chosen is None else chosen
    sel = jnp.any(use[..., None] == jnp.arange(E), axis=1)
    kept = jnp.where(sel, s, 0.0)
    w = cfg["routed_scaling_factor"] * kept / jnp.maximum(
        jnp.sum(kept, -1, keepdims=True), 1e-30)
    return by, picked, w


def moe_ffn(cfg, w, h, held_start=0, shared=True, chosen=None):
    """One expert layer's mixer for the chip that holds ``w["we1"].shape[0]``
    experts from ``held_start``; leaves in any float dtype, one expert
    upcast at a time.  Returns (y, biased scores [T, E], picked [T, k])."""
    f32 = lambda a: a.astype(jnp.float32)
    by, picked, weight = route(cfg, h, f32(w["wr"]), f32(w["eb"]), chosen)
    lat = _mm(h, f32(w["wdn"]))
    held = w["we1"].shape[0]
    mine = jax.lax.dynamic_slice_in_dim(weight, held_start, held, axis=1)

    def one(r, e):
        w1, w2, we = e
        return r + we[:, None] * relu2(lat, f32(w1), f32(w2)), None
    r, _ = jax.lax.scan(one, jnp.zeros_like(lat),
                        (w["we1"], w["we2"], mine.T))
    y = _mm(r, f32(w["wup"]))
    if shared:
        y = y + relu2(h, f32(w["ws1"]), f32(w["ws2"]))
    return y, by, picked


def layer(cfg, w, x, kind, held_start=0, chosen=None, true_len=None):
    """One layer of ``kind`` (a letter of ``hybrid_override_pattern``) on
    x [T, D].  Returns (x, biased scores or None, picked or None, the
    recurrent state after token ``true_len - 1`` or None)."""
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    u = rms(x, w["g"].astype(jnp.float32), cfg["norm_eps"])
    if kind == "E":
        y, by, picked = moe_ffn(cfg, w, u, held_start, chosen=chosen)
        return x + y, by, picked, None
    if kind == "M":
        y, S = mamba(cfg, f32(w), u, true_len)
        return x + y, None, None, S
    return x + attention(cfg, f32(w), u), None, None, None


@functools.lru_cache(maxsize=None)
def _layer_step(cfg_json, kind, held_start, given):
    """One layer, jitted once per configuration and kind: a second pass
    over another sequence of the same length compiles nothing."""
    cfg = json.loads(cfg_json)
    if given:
        return jax.jit(lambda w, x, n, chosen: layer(
            cfg, w, x, kind, held_start, chosen, true_len=n))
    return jax.jit(lambda w, x, n: layer(cfg, w, x, kind, held_start,
                                         true_len=n))


def forward(cfg, layer_leaves, shared, toks, held_start=0, chosen=None,
            true_len=None):
    """``layer_leaves(i)`` -> layer i's leaves (any float dtype: upcast
    here, one layer at a time); ``shared``: embed [V, D], head [D, V], gf.
    ``chosen`` [expert layers, T, k]: the selections to evaluate under.
    ``true_len``: the tokens of ``toks`` that are no padding (all of them
    where it is not given).  Returns (logits f32 [T, V], biased scores
    [expert layers, T, E], picked [expert layers, T, k], the state-space
    layers' states [ssm layers, H, P, N] after token ``true_len - 1``)."""
    x = shared["embed"][toks].astype(jnp.float32)
    cfg_json = json.dumps(cfg, sort_keys=True)
    n = jnp.int32(toks.shape[0] if true_len is None else true_len)
    scores, picks, states = [], [], []
    for i in range(cfg["num_hidden_layers"]):
        kind = cfg["hybrid_override_pattern"][i]
        given = kind == "E" and chosen is not None
        step = _layer_step(cfg_json, kind, held_start, given)
        x, by, picked, S = step(layer_leaves(i), x, n, chosen[len(picks)]) \
            if given else step(layer_leaves(i), x, n)
        if by is not None:
            scores.append(by)
            picks.append(picked)
        if S is not None:
            states.append(S)
    logits = _mm(rms(x, shared["gf"].astype(jnp.float32), cfg["norm_eps"]),
                 shared["head"].astype(jnp.float32))
    return logits, jnp.stack(scores), jnp.stack(picks), jnp.stack(states)
