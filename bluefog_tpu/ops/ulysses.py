"""All-to-all (Ulysses-style) sequence parallelism — the second SP mode.

Where :func:`~bluefog_tpu.ops.ring_attention` rotates K/V blocks around the
mesh in ``n-1`` steps, this mode re-shards the activations instead: one
``all_to_all`` scatters attention *heads* across the axis while gathering the
full *sequence*, each device then runs ordinary (flash) attention for its
head group over the whole sequence, and a second ``all_to_all`` restores the
sequence sharding.  Per step that is 2 collectives moving ``2x`` the
activation bytes versus the ring's ``n-1`` permutes of the K/V stream — the
better trade when heads are plentiful and the per-hop latency of a long ring
dominates (many chips, moderate sequence).  Requires ``num_heads %
axis_size == 0``; the ring mode has no such constraint.

Both modes are exact attention; `tests/test_ulysses.py` pins them to each
other and to the dense oracle.  (The reference predates sequence parallelism
entirely — SURVEY.md §5 — this file and ``ring.py`` are the long-context
surface the build plan adds.)
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .ring import online_softmax_merge

Axis = str


def _zero_offset(axis: Optional[Axis]) -> jax.Array:
    """An int32 zero whose varying-manual-axes match shard_map data.

    Inside ``shard_map`` with vma checking, the kernel's scalar offsets must
    carry the same varying axes as q/k/v or the interpreter rejects the
    mixed ``dynamic_slice``; an ``axis_index``-derived zero is varying."""
    if axis is None:
        return jnp.int32(0)
    return (lax.axis_index(axis) * 0).astype(jnp.int32)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def local_flash_attention(q, k, v, causal: bool, scale: float,
                          block_q: int = 512,
                          interpret: Optional[bool] = None,
                          axis: Optional[Axis] = None):
    """Non-collective flash attention over this device's arrays.

    Both directions keep K and V rows whole in VMEM and walk the key axis
    inside the kernel: a ``[block_q, block_k]`` score tile at a time (never
    HBM), only over the key blocks the causal mask leaves a query block.
    The forward folds them on-line into ``out`` and ``lse``; the backward
    recomputes the scores (FlashAttention-2 recurrence).  The whole rows
    bound the usable ``T``; for sequences past that, ring attention chunks
    K/V across devices instead.  ``axis``: the enclosing shard_map axis, if
    any (only used to stamp the backward kernel's scalar offsets as
    axis-varying).
    """
    out, _ = _local_fwd_impl(q, k, v, causal, scale, block_q, interpret)
    return out


def _publish_visited(direction, q, k, causal, block_q):
    """Trace-time gauge: the share of the kernel's [block_q, block_k] tiles
    its key-block loop runs over (both offsets are zero here)."""
    from . import pallas_attention as pa
    from ..utils import metrics

    Tq, Tk = q.shape[1], k.shape[1]
    qb, kb = pa._blocking(Tq, Tk, q.shape[-1], block_q,
                          direction == "backward")
    visited, total = pa.key_blocks_visited(Tq, Tk, qb, kb, causal=causal)
    metrics.gauge(
        "bluefog_flash_key_blocks_visited_share",
        "share of the local flash kernel's [block_q, block_k] score tiles "
        "its key-block loop computes, as last traced",
    ).set(visited / total, **{"pass": direction})


def _local_fwd_impl(q, k, v, causal, scale, block_q, interpret):
    from . import pallas_attention as pa

    _publish_visited("forward", q, k, causal, block_q)
    return pa.attention_local_forward(
        q, k, v, causal=causal, scale=scale, block_q=block_q,
        interpret=interpret)


def _local_bwd_impl(res, g, causal, scale, block_q, interpret, axis):
    """f32 ``(dq, dk, dv)`` of the local attention from its residuals."""
    from . import pallas_attention as pa

    q, k, v, out, lse = res
    do = g.astype(jnp.float32)
    delta = jnp.sum(do * out.astype(jnp.float32), axis=-1)
    zero = _zero_offset(axis)
    _publish_visited("backward", q, k, causal, block_q)
    return pa.attention_block_backward(
        q, k, v, do, lse, delta, zero, zero,
        causal=causal, scale=scale, block_q=block_q, interpret=interpret)


def _local_fwd(q, k, v, causal, scale, block_q, interpret, axis):
    out, lse = _local_fwd_impl(q, k, v, causal, scale, block_q, interpret)
    return out, (q, k, v, out, lse)


def _local_bwd(causal, scale, block_q, interpret, axis, res, g):
    q, k, v = res[:3]
    dq, dk, dv = _local_bwd_impl(
        res, g, causal, scale, block_q, interpret, axis)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


local_flash_attention.defvjp(_local_fwd, _local_bwd)


def dense_attention(q, k, v, causal: bool, scale: Optional[float] = None):
    """f32 dense attention ([Tq, Tk] scores in memory) — the oracle for
    tests and the single-device fallback in the transformer block."""
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    # f32 floor; float64 inputs (the x64 oracles) keep full precision so a
    # decode-vs-forward comparison can be pinned at 1e-9, not f32 rounding
    ct = jnp.promote_types(q.dtype, jnp.float32)
    s = jnp.einsum("bihd,bjhd->bihj", q.astype(ct) * scale,
                   k.astype(ct))
    if causal:
        T, Tk = q.shape[1], k.shape[1]
        mask = jnp.arange(T)[:, None] >= jnp.arange(Tk)[None, :]
        s = jnp.where(mask[:, None, :][None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bihj,bjhd->bihd", p,
                      v.astype(ct)).astype(q.dtype)


def _chunk_len(Tk: int, max_chunk: int) -> int:
    """Largest divisor of ``Tk`` that is <= max_chunk."""
    for c in range(min(max_chunk, Tk), 0, -1):
        if Tk % c == 0:
            return c
    return Tk


def _jnp_local_attention(q, k, v, causal: bool, scale: float,
                         max_chunk: int = 512,
                         axis: Optional[Axis] = None):
    """Online-softmax local attention, scanned over K/V chunks.

    The jnp path of the ulysses mode: same flash recurrence as
    ``_jnp_ring_attention`` but chunking locally instead of over devices, so
    memory stays O(Tq x chunk) — a 32k-token gathered sequence never
    materializes a [Tq, Tk] score tensor.  ``axis``: the enclosing shard_map
    axis, if any (stamps the scan carry as axis-varying to match q/k/v).
    """
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    chunk = _chunk_len(Tk, max_chunk)
    C = Tk // chunk
    # accumulate in at least f32; f64 inputs keep f64 (the float64 oracle
    # needs attention above the f32 noise floor)
    acc = jnp.promote_types(q.dtype, jnp.float32)
    qf = q.astype(acc) * scale
    kc = k.reshape(B, C, chunk, H, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, C, chunk, H, D).transpose(1, 0, 2, 3, 4)
    q_pos = jnp.arange(Tq)

    o0 = jnp.zeros(q.shape, acc)
    l0 = jnp.zeros(q.shape[:3], acc)
    m0 = jnp.full(q.shape[:3], -jnp.inf, acc)
    if axis is not None:
        o0, l0, m0 = (lax.pcast(t, axis, to='varying')
                      for t in (o0, l0, m0))

    def step(carry, inp):
        o, l, m = carry
        c, kt, vt = inp
        s = jnp.einsum("bihd,bjhd->bihj", qf, kt.astype(acc))
        if causal:
            k_pos = c * chunk + jnp.arange(chunk)
            mask = q_pos[:, None, None] >= k_pos[None, None, :]
            s = jnp.where(mask[None], s, -jnp.inf)
        return online_softmax_merge(o, l, m, s, vt), None

    (o, l, _), _ = lax.scan(step, (o0, l0, m0), (jnp.arange(C), kc, vc))
    l = jnp.where(l == 0.0, 1.0, l)
    return (o / l[..., None]).astype(q.dtype)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis: Axis = "rank",
    causal: bool = False,
    scale: Optional[float] = None,
    use_pallas: bool = False,
    pallas_block_q: int = 512,
    pallas_interpret: Optional[bool] = None,
) -> jax.Array:
    """Exact attention over a sequence sharded along ``axis`` via head
    re-sharding (2 ``all_to_all``s around a local attention).

    Blocks: ``q, k, v`` are ``[batch, block_len, heads, head_dim]`` — the
    same contract as :func:`ring_attention`, so the two modes are drop-in
    swaps.  Requires ``heads % axis_size == 0``.
    """
    if q.ndim != 4:
        raise ValueError("expected [batch, block_len, heads, head_dim]")
    if k.shape[2] != q.shape[2] or v.shape[2] != q.shape[2]:
        raise ValueError(
            "ulysses scatters heads across the axis and needs equal q/kv "
            "head counts; grouped-query (GQA) kv is a ring_attention "
            "feature")
    n = lax.axis_size(axis)
    H = q.shape[2]
    if H % n:
        raise ValueError(
            f"ulysses SP needs heads ({H}) divisible by axis size ({n}); "
            "use ring_attention for uneven head counts")
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])

    if use_pallas:
        # hand-written VJP END TO END (collectives included): the backward
        # runs its own all_to_alls instead of relying on automatic
        # collective transposition, mirroring the ring path's design
        return _pallas_ulysses(q, k, v, axis, causal, float(scale),
                               pallas_block_q, pallas_interpret)
    if n == 1:
        # degenerate axis (e.g. an sp=1 carving in parallel/compose): the
        # block already holds the full sequence and all heads — skip the
        # two size-1 all_to_alls so composed programs pay zero collectives
        # for the unused axis
        return _jnp_local_attention(q, k, v, causal, float(scale), axis=axis)
    qg, kg, vg = (_scatter_heads(t, axis) for t in (q, k, v))
    out = _jnp_local_attention(qg, kg, vg, causal, float(scale), axis=axis)
    return _gather_heads(out, axis)


def _scatter_heads(x, axis):
    """[B, T_local, H, D] -> [B, T, H/n, D]: heads scatter, sequence gathers."""
    return lax.all_to_all(x, axis, split_axis=2, concat_axis=1, tiled=True)


def _gather_heads(x, axis):
    """Inverse of :func:`_scatter_heads`."""
    return lax.all_to_all(x, axis, split_axis=1, concat_axis=2, tiled=True)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _pallas_ulysses(q, k, v, axis, causal, scale, block_q, interpret):
    out, _ = _ulysses_fwd_impl(q, k, v, axis, causal, scale, block_q,
                               interpret)
    return out


def _ulysses_fwd_impl(q, k, v, axis, causal, scale, block_q, interpret):
    qg, kg, vg = (_scatter_heads(t, axis) for t in (q, k, v))
    out_g, lse = _local_fwd_impl(qg, kg, vg, causal, scale, block_q,
                                 interpret)
    return _gather_heads(out_g, axis), (qg, kg, vg, out_g, lse)


def _ulysses_fwd(q, k, v, axis, causal, scale, block_q, interpret):
    out, res = _ulysses_fwd_impl(
        q, k, v, axis, causal, scale, block_q, interpret)
    return out, res


def _ulysses_bwd(axis, causal, scale, block_q, interpret, res, g):
    # the cotangent is sequence-sharded like the output; move it to the
    # head-sharded layout the kernel residuals live in
    grads = _local_bwd_impl(res, _scatter_heads(g, axis), causal, scale,
                            block_q, interpret, axis)
    return tuple(_gather_heads(d, axis).astype(t.dtype)
                 for d, t in zip(grads, res[:3]))


_pallas_ulysses.defvjp(_ulysses_fwd, _ulysses_bwd)
