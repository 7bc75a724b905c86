"""Plain reference of the ``delta_gqa_moe`` family: the forward pass of a
pre-norm decoder whose layers are a mixer and an expert layer (the mixer a
gated delta-rule layer with a decay per channel, Kimi Delta Attention,
arXiv:2510.26692, or on the layers ``gqa_layers`` names gated grouped-query
attention with no position signal; the experts gated SiLU under a
bias-corrected sigmoid router beside a shared expert), as ONE chip of an
expert-parallel deployment computes it, in straightforward float32
``jax.numpy`` with matmuls at ``highest`` precision: the whole sequence at
once, the delta rule TOKEN BY TOKEN and nothing else (no chunks), attention
under an explicit causal mask a block of queries at a time, every held
expert applied densely to every token and masked by its weight, one expert
upcast at a time; no cache, no batching, no kernels.  Written from the
equations below; imports nothing from ``bluefog_tpu``.

``cfg`` is the configuration file's dict (the source's key names).  With
``RMS(z; g) = z / sqrt(mean(z^2) + eps) * g`` (``eps`` = ``rms_norm_eps``),
for one sequence x[T, D] and layer l:

    x += mix_l(RMS(x; g))        G where l is in gqa_layers, else L
    x += moe(RMS(x; g))
    logits = RMS(x_L; gf) @ head                (over the vocabulary slice)

Every sublayer is ONE entry of the program's plan with its own norm scale
``g``, so ``layer_leaves(2 l)`` is layer l's mixer and ``layer_leaves(2 l +
1)`` its experts.

    L (delta rule)  H = linear_attn_config.num_heads heads, keys, queries
        and values of linear_attn_config.head_dim channels (K = V);
        w_in = [wq | wk | wv], w_conv = [cq ; ck ; cv] side by side
        q~, k~, v~ = silu(conv(u w_in; w_conv))   causal, depthwise,
                     short_conv_kernel_size taps, zeros before the prompt,
                     the current input under the LAST tap, no bias
        q_t = q~_t / sqrt(|q~_t|^2 + 1e-6) * K^-0.5     per head
        k_t = k~_t / sqrt(|k~_t|^2 + 1e-6)
        g_t = -exp(A_log)[head] * softplus((u wfa) wfb + dt_bias)   [H, K]
        b_t = (2 if kda_allow_neg_eigval else 1) * sigmoid(u wb)    [H]
        S'  = exp(g_t)[:, None] * S_{t-1}         S [H, K, V]; S_{-1} = 0
        S_t = S' + b_t k_t (x) (v_t - S'^T k_t)
        o_t = S_t^T q_t
        y_t = RMS per head (o_t; g_o) * sigmoid((u wga) wgb)    norm FIRST
        out = y_t w_out
    G (attention)  q = u wq -> Hq heads of head_dim; k = u wk, v = u wv -> Hkv
        score_i(t, s) = q_i(t) . k_[i / (Hq/Hkv)](s) / sqrt(head_dim), s <= t
        out = (concat_i(softmax_s(score_i) v_[..]) * sigmoid(u wgate)) wo
        nothing is turned and nothing is normed: no position signal
    E (experts)  s = sigmoid(u wr) [E]; chosen = the num_experts_per_tok
        highest of s + e_bias; w_e = s_e over the chosen's sum, times
        routed_scaling_factor
        out = sum_{e chosen and HELD} w_e (silu(u wg_e) * u wu_e) wd_e
              + (silu(u wsg) * u wsu) wsd            (the shared expert)

The cut: the chip holds experts ``held_start .. held_start + held - 1``
(``held`` = the length of ``weg``), and what the absent experts would add is
left out; :func:`moe_ffn` takes any held range, so a test can add the shares
of all chips up to the uncut layer.

:func:`forward` runs an entry at a time (``layer_leaves(i)`` hands it entry
``i``'s leaves, upcast here, an expert layer's experts one at a time), so
that on the chip one sublayer in float32 fits beside the served weights.
Besides the logits it returns, per expert layer, the experts it chose and
the biased scores it chose them by, and per delta layer the state after the
last token that is no padding (``true_len``): what a served slot holds then,
number by number.  With ``chosen`` it takes the selections as given (the
weights still from its own scores): where the served program's rounding put
an expert on the other side of the cut, the function is compared on the
program's side of it.
"""
import functools
import json

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
# float32 scores of one block of queries, [heads, block, T], stay inside this
SCORE_BYTES = 1 << 29


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def gated(h, wg, wu, wd):
    return _mm(jax.nn.silu(_mm(h, wg)) * _mm(h, wu), wd)


def plan(cfg):
    """The entries' letters, two a layer: the mixer's, then ``E``."""
    return "".join(("G" if l in cfg["gqa_layers"] else "L") + "E"
                   for l in range(cfg["num_hidden_layers"]))


def recurrence(q, k, v, g, beta, true_len=None):
    """``S' = exp(g_t) S_{t-1}``, ``S_t = S' + beta_t k_t (x) (v_t - S'^T
    k_t)``, ``o_t = S_t^T q_t`` from ``S_{-1} = 0``, token by token: q, k, g
    [T, H, K], v [T, H, V], beta [T, H].  Returns (o [T, H, V], the state
    [H, K, V] after token ``true_len - 1``: the last one where no
    ``true_len`` is given; what follows it is padding, and its o is
    nobody's)."""
    def step(S, t):
        q_t, k_t, v_t, g_t, b_t, real = t
        S1 = jnp.exp(g_t)[:, :, None] * S
        u = v_t - jnp.sum(S1 * k_t[:, :, None], 1)              # [H, V]
        new = S1 + b_t[:, None, None] * k_t[:, :, None] * u[:, None, :]
        return jnp.where(real, new, S), jnp.sum(new * q_t[:, :, None], 1)
    T = q.shape[0]
    real = jnp.arange(T) < (T if true_len is None else true_len)
    S0 = jnp.zeros(k.shape[1:] + v.shape[-1:], jnp.float32)
    S, o = jax.lax.scan(step, S0, (q, k, v, g, beta, real))
    return o, S


def delta(cfg, w, u, true_len=None):
    """The delta-rule mixer on the normed u [T, D].  Returns (out [T, D],
    the state [H, K, V] after token ``true_len - 1``)."""
    T = u.shape[0]
    lin = cfg["linear_attn_config"]
    H, K, taps = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    raw = _mm(u, w["w_in"])                                     # q | k | v
    padded = jnp.concatenate([jnp.zeros((taps - 1, raw.shape[1])), raw])
    qkv = jax.nn.silu(sum(w["w_conv"][:, j] * padded[j:j + T]
                          for j in range(taps)))
    q, k, v = (qkv[:, i * H * K:(i + 1) * H * K].reshape(T, H, K)
               for i in range(3))
    unit = lambda t: t / jnp.sqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)
    q, k = unit(q) * K ** -0.5, unit(k)
    g = -jnp.exp(w["A_log"])[:, None] * jax.nn.softplus(
        _mm(_mm(u, w["wfa"]), w["wfb"]) + w["dt_bias"]).reshape(T, H, K)
    beta = (2.0 if cfg["kda_allow_neg_eigval"] else 1.0) \
        * jax.nn.sigmoid(_mm(u, w["wb"]))
    o, S = recurrence(q, k, v, g, beta, true_len)
    y = rms(o, w["g_o"], cfg["rms_norm_eps"]).reshape(T, H * K) \
        * jax.nn.sigmoid(_mm(_mm(u, w["wga"]), w["wgb"]))
    return _mm(y, w["w_out"]), S


def attention(cfg, w, u):
    T = u.shape[0]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    Dh = cfg["head_dim"]
    heads = lambda t, n: t.reshape(T, n, Dh).transpose(1, 0, 2)
    q = heads(_mm(u, w["wq"]), H)
    k = jnp.repeat(heads(_mm(u, w["wk"]), Hkv), H // Hkv, axis=0)
    v = jnp.repeat(heads(_mm(u, w["wv"]), Hkv), H // Hkv, axis=0)
    at = jnp.arange(T)

    def block(args):                    # a block of queries, [H, b, Dh]
        qb, tb = args
        s = jnp.einsum("htd,hsd->hts", qb, k, precision=HIGHEST) * Dh ** -0.5
        p = jax.nn.softmax(
            jnp.where(at[None, None, :] <= tb[None, :, None], s, -jnp.inf), -1)
        return jnp.einsum("hts,hsd->htd", p, v, precision=HIGHEST)

    b = max(c for c in range(1, T + 1)
            if T % c == 0 and (c == 1 or H * c * T * 4 <= SCORE_BYTES))
    out = jax.lax.map(block, (q.reshape(H, T // b, b, Dh).transpose(1, 0, 2, 3),
                              at.reshape(T // b, b)))           # [T/b, H, b, Dh]
    att = out.transpose(0, 2, 1, 3).reshape(T, H * Dh)
    if cfg["use_gqa_gate"]:
        att = att * jax.nn.sigmoid(_mm(u, w["wgate"]))
    return _mm(att, w["wo"])


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
    """One expert layer's mixer for the chip that holds ``w["weg"].shape[0]``
    experts from ``held_start``; leaves in any float dtype, one expert
    upcast at a time.  Returns (y, biased scores [T, E], picked [T, k])."""
    f32 = lambda a: a.astype(jnp.float32)
    by, picked, weight = route(cfg, h, f32(w["wr"]), f32(w["eb"]), chosen)
    held = w["weg"].shape[0]
    mine = jax.lax.dynamic_slice_in_dim(weight, held_start, held, axis=1)

    def one(y, e):
        wg, wu, wd, we = e
        return y + we[:, None] * gated(h, f32(wg), f32(wu), f32(wd)), None
    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (w["weg"], w["weu"], w["wed"], mine.T))
    if shared:
        y = y + gated(h, f32(w["wsg"]), f32(w["wsu"]), f32(w["wsd"]))
    return y, by, picked


def layer(cfg, w, x, kind, held_start=0, chosen=None, true_len=None):
    """One entry of ``kind`` (a letter of :func:`plan`) on x [T, D].
    Returns (x, biased scores or None, picked or None, the delta state after
    token ``true_len - 1`` or None)."""
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    u = rms(x, w["g"].astype(jnp.float32), cfg["rms_norm_eps"])
    if kind == "E":
        y, by, picked = moe_ffn(cfg, w, u, held_start, chosen=chosen)
        return x + y, by, picked, None
    if kind == "L":
        y, S = delta(cfg, f32(w), u, true_len)
        return x + y, None, None, S
    return x + attention(cfg, f32(w), u), None, None, None


@functools.lru_cache(maxsize=None)
def _layer_step(cfg_json, kind, held_start, given):
    """One entry, jitted once per configuration and kind: a second pass
    over another sequence of the same length compiles nothing."""
    cfg = json.loads(cfg_json)
    if given:
        return jax.jit(lambda w, x, n, chosen: layer(
            cfg, w, x, kind, held_start, chosen, true_len=n))
    return jax.jit(lambda w, x, n: layer(cfg, w, x, kind, held_start,
                                         true_len=n))


def forward(cfg, layer_leaves, shared, toks, held_start=0, chosen=None,
            true_len=None):
    """``layer_leaves(i)`` -> entry i's leaves (any float dtype: upcast
    here, one entry at a time); ``shared``: embed [V, D], head [D, V], gf.
    ``chosen`` [expert layers, T, k]: the selections to evaluate under.
    ``true_len``: the tokens of ``toks`` that are no padding (all of them
    where it is not given).  Returns (logits f32 [T, V], biased scores
    [expert layers, T, E], picked [expert layers, T, k], the delta layers'
    states [delta layers, H, K, V] after token ``true_len - 1``)."""
    x = shared["embed"][toks].astype(jnp.float32)
    cfg_json = json.dumps(cfg, sort_keys=True)
    n = jnp.int32(toks.shape[0] if true_len is None else true_len)
    scores, picks, states = [], [], []
    for i, kind in enumerate(plan(cfg)):
        given = kind == "E" and chosen is not None
        step = _layer_step(cfg_json, kind, held_start, given)
        x, by, picked, S = step(layer_leaves(i), x, n, chosen[len(picks)]) \
            if given else step(layer_leaves(i), x, n)
        if by is not None:
            scores.append(by)
            picks.append(picked)
        if S is not None:
            states.append(S)
    logits = _mm(rms(x, shared["gf"].astype(jnp.float32),
                     cfg["rms_norm_eps"]), shared["head"].astype(jnp.float32))
    return logits, jnp.stack(scores), jnp.stack(picks), jnp.stack(states)
