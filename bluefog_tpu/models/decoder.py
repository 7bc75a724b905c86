"""The composed LM's decoder block, defined once.

Everything a model configuration changes is here: the norm, the rotary
embedding, the block's order of operations, the logit read-out and the
shapes of a block's parameters.  Training (:mod:`..parallel.compose`), MoE
training (:mod:`..moe.model`) and the serving programs
(:mod:`..serve.engine`: prefill, decode/draft, chunk) call
:func:`decoder_block` on the raw parameter tree and differ only in its
``attend`` closure (how q/k/v meet the sequence or the KV cache) and
``ffn`` hook: one edit here reaches all of them, which keeps a draft's
cache rows equal to the verify's and a prefill's to the decode's.
Imports jax only: the callers import this module, never the reverse.
"""
import math
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

ATTENTION_LEAVES = ("wqkv", "wo")
FFN_LEAVES = ("w1", "w2")


def norm(x: jax.Array) -> jax.Array:
    """Parameter-free layer norm over the channel axis."""
    mu = x.mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(x.var(-1, keepdims=True) + 1e-6)


def rope(x: jax.Array, positions: jax.Array,
         base: float = 10000.0) -> jax.Array:
    """Rotary position embedding on ``x`` ``[..., H, Dh]`` with integer
    ``positions`` shaped ``x.shape[:-2]`` or a suffix of it: ``[T]``
    against ``[B, T, H, Dh]`` (training, prefill), ``[S]`` against ``[S, H,
    Dh]`` (decode: each lane at its own offset), ``[S, T]`` against ``[S,
    T, H, Dh]`` (verify, chunked prefill).  Rotation is per token, so it
    commutes with any sequence sharding, and a token roped through one
    shape matches the same token roped through another bit for bit."""
    d = x.shape[-1]
    if d % 2:
        raise ValueError(f"rope needs an even head_dim, got {d}: the "
                         "rotation pairs channel i with channel i + d//2")
    half = d // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs   # [..., half]
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1).astype(x.dtype)


def dense_ffn(lp: Dict[str, jax.Array], h: jax.Array):
    """The default ``ffn`` hook: the two-matmul gelu FFN, column- then
    row-parallel over ``tp``, on the normed activation ``h``.

    The backward pass keeps the f32 pre-activation ``z = h @ w1`` alone
    and recomputes the gelu from it: under the layers' ``lax.scan`` every
    value AD keeps is stacked over the layers, and the plain expression
    keeps four intermediates of the tanh gelu and the second matmul's
    operand besides (at 24 layers of [2048, 4096]: 3.6 GB written in the
    forward loop and read back in the backward loop, PERF.md §6, PR 31).
    Same forward arithmetic, same AD rule; a program that is never
    differentiated lowers to the plain expression.  ``prevent_cse`` is
    off as ``jax.checkpoint`` advises under ``scan``: its barriers keep
    the recompute out of the backward matmuls' fusions."""
    down = jax.checkpoint(lambda z, w2: jax.nn.gelu(z) @ w2,
                          prevent_cse=False)
    return lax.psum(down(h @ lp["w1"], lp["w2"]), "tp"), None


def decoder_block(cfg: Any, tp: int, lp: Dict[str, jax.Array], x: jax.Array,
                  positions: jax.Array, attend: Callable,
                  ffn: Callable = dense_ffn) -> Tuple[jax.Array, Any, Any]:
    """One pre-norm decoder block on ``x`` ``[..., D]`` with this tp rank's
    leaves ``lp``: norm → ``wqkv`` → split → rope → attention → ``wo`` →
    norm → FFN, both halves residual.  ``attend(q, k, v) -> (att, aux)``
    takes the roped heads ``[..., heads // tp, head_dim]`` and returns the
    attention output in q's shape plus what the caller's cache hooks made
    (the updated cache, a token's pages still to be written, None).
    ``ffn(lp, h) -> (y, faux)`` takes the normed post-attention activation
    and returns the FFN output plus its by-product (routing, a metrics
    vector, None).  Returns ``(x, aux, faux)``."""
    lead = x.shape[:-1]
    heads = lead + (cfg.heads // tp, cfg.d_model // cfg.heads)
    q, k, v = jnp.split(norm(x) @ lp["wqkv"], 3, axis=-1)
    q = rope(q.reshape(heads), positions)
    k = rope(k.reshape(heads), positions)
    att, aux = attend(q, k, v.reshape(heads))
    x = x + lax.psum(att.reshape(lead + (-1,)) @ lp["wo"], "tp")
    y, faux = ffn(lp, norm(x))
    return x + y, aux, faux


def lm_logits(shared: Dict[str, jax.Array], x: jax.Array) -> jax.Array:
    """Final norm and read-out through the shared head (the stage select
    and its ``psum`` stay with the caller: they differ)."""
    return norm(x) @ shared["head"]


def block_param_shapes(cfg: Any, tp: int = 1) -> Dict[str, Tuple[int, int]]:
    """One tp rank's leaves of the dense block, in the order they are
    drawn: column-parallel ``wqkv``/``w1``, row-parallel ``wo``/``w2``."""
    D, F = cfg.d_model, cfg.ffn_mult * cfg.d_model
    return {"wqkv": (D, 3 * D // tp), "wo": (D // tp, D),
            "w1": (D, F // tp), "w2": (F // tp, D)}


def block_param_count(cfg: Any, leaves: Optional[Sequence[str]] = None) -> int:
    """Un-sharded parameter count of one dense block (``D*3*D + D*D + D*F
    + F*D``), or of the named ``leaves`` of it."""
    shapes = block_param_shapes(cfg)
    return sum(math.prod(shapes[name]) for name in leaves or shapes)
