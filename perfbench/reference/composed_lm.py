"""Plain reference of the composed LM: forward pass and copy-task loss in
straightforward float32 ``jax.numpy``, matmuls at ``highest`` precision, no
kernels, no cache, no batching, no sharding.  Written from the block's
equations, independent of bluefog_tpu/parallel/compose.py and
bluefog_tpu/serve/engine.py, which it is compared with.

The block (the repo's, NOT Pythia's; see configs/pythia-410m.json
``departures``), for one sequence x[T, D]:

    h   = norm(x)                      norm: (z - mean) / sqrt(var + 1e-6), no parameters
    q,k,v = split(h @ wqkv, 3)         each [T, heads, D/heads]
    q,k = rope(q), rope(k)             whole head; channel i pairs with i + half
    x   = x + causal_softmax(q k^T / sqrt(D/heads)) v @ wo
    x   = x + gelu_tanh(norm(x) @ w1) @ w2
    logits = norm(x_L) @ head          embed and head untied

Loss (training): predict the token ``lag`` positions back, mean cross-entropy
over positions lag..T-1.
"""
import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _norm(z):
    mu = jnp.mean(z, axis=-1, keepdims=True)
    var = jnp.mean((z - mu) ** 2, axis=-1, keepdims=True)
    return (z - mu) / jnp.sqrt(var + 1e-6)


def _rope(x, base=10000.0):
    T, _, d = x.shape
    half = d // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _block(x, w, heads):
    T, D = x.shape
    hd = D // heads
    q, k, v = jnp.split(_mm(_norm(x), w["wqkv"]), 3, axis=-1)
    q = _rope(q.reshape(T, heads, hd))
    k = _rope(k.reshape(T, heads, hd))
    v = v.reshape(T, heads, hd)
    s = jnp.einsum("ihd,jhd->hij", q, k, precision=HIGHEST) / jnp.sqrt(
        jnp.float32(hd))
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    att = jnp.einsum("hij,jhd->ihd", p, v, precision=HIGHEST).reshape(T, D)
    x = x + _mm(att, w["wo"])
    return x + _mm(_gelu_tanh(_mm(_norm(x), w["w1"])), w["w2"])


@functools.partial(jax.jit, static_argnames=("heads",))
def logits(params, toks, heads):
    """params: {"wqkv","wo","w1","w2"} stacked [layers, ...], "embed" [V, D],
    "head" [D, V], any float dtype; toks: [T] int.  Returns f32 [T, V]."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    x = p["embed"][toks]
    blocks = {k: p[k] for k in ("wqkv", "wo", "w1", "w2")}
    x, _ = jax.lax.scan(lambda c, w: (_block(c, w, heads), None), x, blocks)
    return _mm(_norm(x), p["head"])


@functools.partial(jax.jit, static_argnames=("heads", "lag"))
def copy_task_loss(params, toks, heads, lag):
    """Mean cross-entropy of one sequence's positions lag..T-1 against the
    token ``lag`` positions back."""
    lg = logits(params, toks, heads)[lag:]
    targets = toks[:-lag]
    logz = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, targets[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - picked)
