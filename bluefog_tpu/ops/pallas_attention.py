"""Pallas TPU kernel: blockwise attention partials for ring attention.

The hot inner step of :func:`bluefog_tpu.ops.ring_attention` is, per K/V
block, ``s = q k^T; online-softmax fold; o += p v``.  Lowered naively the
``[Tq, Tk]`` score matrix round-trips through HBM between the einsums; this
kernel computes one block's *attention partial* entirely in VMEM — both
matmuls hit the MXU, the scores never leave the chip:

    m_blk = rowmax(s),  p = exp(s - m_blk),  l_blk = rowsum(p),  o_blk = p v

The ring scan then merges partials with the standard flash-attention
recurrence (merge_partials), which is exactly the fold ring_attention's pure
-jnp path performs.  On non-TPU backends the kernel runs in interpreter mode
(slow but correct), so the same code path is testable on the CPU virtual
mesh.

Reference anchor: the reference has no attention kernels (it predates
long-context training, SURVEY.md §5); this is the TPU-native capability its
ring p2p schedules point toward.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # large-negative stand-in: keeps exp() exact zeros without nan


# --- scaffolding shared by the forward and backward pallas_calls -----------

def _split_heads(x: jax.Array) -> jax.Array:
    """[B, T, H, D] -> [B*H, T, D]: one grid step per (batch, head)."""
    B, T, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, T, D)


def _merge_heads(x: jax.Array, B: int, H: int) -> jax.Array:
    """[B*H, T, D] -> [B, T, H, D]."""
    _, T, D = x.shape
    return x.reshape(B, H, T, D).transpose(0, 2, 1, 3)


# Mosaic gives a v5e kernel 16 MiB of scoped VMEM.  These kernels block the
# queries only: K and V (and dK, dV in the backward) stay whole, and the
# body holds [block_q, Tk] f32 score tiles.  The two bounds below are
# calibrated against Mosaic for v5e (tests/test_tpu_aot.py compiles every
# row): the resident rows against the limit itself, the score tiles against
# three quarters of it, which leaves room for the q/o/do tiles and Mosaic's
# own temporaries.  Past them the kernel needs K/V blocking, which it does
# not have yet.
_VMEM_LIMIT = 16 * 1024 * 1024
_SCORE_BUDGET = 12 * 1024 * 1024
_LANE = 128
_MIN_BLOCK_Q = 8


def _q_blocking(Tq: int, Tk: int, D: int, block_q: int, backward: bool):
    """Pick the q block: at most ``block_q`` rows, shrunk (to a power of
    two) until the score tiles fit VMEM — the tile is [QB, Tk] instead of
    [Tq, Tk] (a 4k-token local block would otherwise need a 64 MB tile).
    Non-divisible Tq is padded up to a block multiple — never fall back to
    one full [Tq, Tk] tile, which is the exact blow-up blocking prevents.
    Returns ``(qb, pad, Tp)`` with ``Tp = Tq + pad`` a multiple of ``qb``.

    Raises ``ValueError`` at trace time when the shape cannot fit whatever
    the block, so the caller never meets Mosaic's RESOURCE_EXHAUSTED."""
    which = "backward" if backward else "forward"
    lanes = -(-D // _LANE) * _LANE                    # minor dim pads to 128
    rows = (4 if backward else 2) * Tk * lanes * 4    # K, V (, dK, dV) in f32
    tiles = 3 if backward else 2                      # s, p (, dp) per q row
    io = 8 if backward else 4          # q, o (, do, dq) tiles, double-buffered
    fit = _SCORE_BUDGET // (tiles * Tk * 4 + io * lanes * 4)
    if rows > _VMEM_LIMIT or fit < _MIN_BLOCK_Q:
        raise ValueError(
            f"flash attention {which}: Tk={Tk} keys at head_dim {D} do not "
            f"fit the {_VMEM_LIMIT >> 20} MiB scoped VMEM limit (whole K/V "
            f"rows need {rows} bytes; a {_MIN_BLOCK_Q}-row score block "
            f"needs {tiles * _MIN_BLOCK_Q * Tk * 4} of "
            f"{_SCORE_BUDGET}): the kernel blocks queries only — shard the "
            "sequence further (ring attention) or use the XLA path")
    qb = min(block_q, Tq)
    if qb > fit:
        qb = 1 << (fit.bit_length() - 1)
    pad = (-Tq) % qb
    return qb, pad, Tq + pad


def _pad_rows(x: jax.Array, pad: int, value: float = 0.0) -> jax.Array:
    if not pad:
        return x
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0)), constant_values=value)


def _q_spec(t: int, d: int) -> pl.BlockSpec:
    return pl.BlockSpec((1, t, d), lambda i, j: (i, j, 0))


def _kv_spec(t: int, d: int, H: int = 0, Hkv: int = 0) -> pl.BlockSpec:
    """K/V block for grid step i over B*H (q-head-major) grid steps.

    With grouped-query attention (``Hkv < H``) the K/V array stays compact
    at ``[B*Hkv, T, D]`` and the index map routes q head ``h`` to kv head
    ``h // (H // Hkv)`` — GQA costs zero data expansion in the kernel."""
    if not H or H == Hkv:
        return pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0))
    group = H // Hkv
    return pl.BlockSpec(
        (1, t, d), lambda i, j: ((i // H) * Hkv + (i % H) // group, 0, 0))


def _smem_scalar(x: jax.Array) -> jax.Array:
    return jnp.reshape(x.astype(jnp.int32), (1,))


def _vma_of(x: jax.Array):
    # under shard_map the outputs vary over the same mesh axes as the inputs
    return getattr(jax.typeof(x), "vma", frozenset()) or frozenset()


def _apply_causal_mask(s, qoff_ref, koff_ref, block_q: int, window: int = 0):
    """In-kernel: mask scores above the diagonal given the global offsets of
    this grid step's q rows (``qoff + j*block_q``) and the K block.
    ``window > 0`` additionally masks keys more than ``window - 1`` tokens
    behind the query (sliding-window attention)."""
    tq, tk = s.shape
    base = qoff_ref[0] + pl.program_id(1) * block_q
    q_pos = base + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
    k_pos = koff_ref[0] + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
    keep = q_pos >= k_pos
    if window:
        keep = keep & (q_pos - k_pos < window)
    return jnp.where(keep, s, NEG_INF)


def _partial_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref,
                    o_ref, l_ref, m_ref, *, causal: bool, scale: float,
                    block_q: int, window: int = 0):
    q = q_ref[0].astype(jnp.float32) * scale          # [QB, D]
    k = k_ref[0].astype(jnp.float32)                  # [Tk, D]
    v = v_ref[0].astype(jnp.float32)                  # [Tk, D]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)           # [QB, Tk]
    if causal:
        s = _apply_causal_mask(s, qoff_ref, koff_ref, block_q, window)
    m = jnp.max(s, axis=-1, keepdims=True)            # [Tq, 1]
    safe_m = jnp.where(m <= NEG_INF / 2, 0.0, m)
    p = jnp.exp(s - safe_m)
    p = jnp.where(s <= NEG_INF / 2, 0.0, p)
    l = jnp.sum(p, axis=-1, keepdims=True)            # [Tq, 1]
    o = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)           # [Tq, D]
    o_ref[0] = o
    l_ref[0] = l
    m_ref[0] = jnp.where(m <= NEG_INF / 2, -jnp.inf, m)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "interpret", "block_q", "window"))
def attention_block_partial(
    q: jax.Array,                  # [B, Tq, H, D]
    k: jax.Array,                  # [B, Tk, Hkv, D] — Hkv may divide H (GQA)
    v: jax.Array,                  # [B, Tk, Hkv, D]
    q_offset: jax.Array,           # [] int32 — global position of q[0]
    k_offset: jax.Array,           # [] int32
    *,
    causal: bool = False,
    scale: float = 1.0,
    interpret: Optional[bool] = None,
    block_q: int = 512,
    window: int = 0,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One K/V block's flash-attention partial, fully in VMEM.
    ``window > 0`` (needs ``causal``): sliding-window masking — keys more
    than ``window - 1`` tokens behind the query are masked.
    ``block_q`` is an upper bound: the q block shrinks to what VMEM allows
    at this ``Tk`` (see :func:`_q_blocking`).

    Returns ``(o_blk [B,Tq,H,D] f32, l_blk [B,Tq,H] f32, m_blk [B,Tq,H] f32)``
    relative to the block max ``m_blk`` (rows with no valid key get
    ``m = -inf, l = 0, o = 0``).
    """
    B, Tq, H, D = q.shape
    Tk, Hkv = k.shape[1], k.shape[2]
    if H % Hkv:
        raise ValueError(f"q heads {H} not a multiple of kv heads {Hkv}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    qb, pad, Tp = _q_blocking(Tq, Tk, D, block_q, backward=False)
    qr = _pad_rows(_split_heads(q), pad)
    kr, vr = _split_heads(k), _split_heads(v)

    kernel = functools.partial(_partial_kernel, causal=causal, scale=scale,
                               block_q=qb, window=window)
    vma = _vma_of(qr)
    o, l, m = pl.pallas_call(
        kernel,
        grid=(B * H, Tp // qb),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # scalar offsets
            pl.BlockSpec(memory_space=pltpu.SMEM),
            _q_spec(qb, D),
            _kv_spec(Tk, D, H, Hkv),
            _kv_spec(Tk, D, H, Hkv),
        ],
        out_specs=[
            _q_spec(qb, D),
            _q_spec(qb, 1),
            _q_spec(qb, 1),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tp, D), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((B * H, Tp, 1), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((B * H, Tp, 1), jnp.float32, vma=vma),
        ],
        interpret=interpret,
    )(_smem_scalar(q_offset), _smem_scalar(k_offset), qr, kr, vr)

    o = _merge_heads(o[:, :Tq], B, H)
    l = _merge_heads(l[:, :Tq], B, H)[..., 0]
    m = _merge_heads(m[:, :Tq], B, H)[..., 0]
    return o, l, m


def _backward_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, do_ref,
                     lse_ref, delta_ref, dq_ref, dk_ref, dv_ref, *,
                     causal: bool, scale: float, block_q: int,
                     num_heads: int = 0, group: int = 1, window: int = 0):
    """Flash-attention backward for one K/V block, scores recomputed in VMEM.

    Standard FlashAttention-2 backward recurrence with the *global* softmax
    statistics (lse over the full ring) supplied per q row:

        p  = exp(s - lse)          # normalized probabilities, s = scale q k^T
        dv = p^T do
        dp = do v^T
        ds = p * (dp - delta)      # delta_i = do_i . o_i
        dq += scale ds k           # accumulated over K/V blocks by the caller
        dk  = scale ds^T q         # accumulated over q blocks by this grid
        dv, dk accumulate across the q-block grid dimension (sequential on TPU)
    """
    j = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)                  # [QB, D]
    k = k_ref[0].astype(jnp.float32)                  # [Tk, D]
    v = v_ref[0].astype(jnp.float32)                  # [Tk, D]
    do = do_ref[0].astype(jnp.float32)                # [QB, D]
    lse = lse_ref[0]                                  # [QB, 1] (-inf: no keys)
    delta = delta_ref[0]                              # [QB, 1]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # [QB, Tk]
    if causal:
        s = _apply_causal_mask(s, qoff_ref, koff_ref, block_q, window)
    safe_lse = jnp.where(jnp.isneginf(lse), 0.0, lse)
    p = jnp.exp(s - safe_lse)
    # masked scores and rows with no valid keys (padded rows carry lse=-inf).
    # Broadcast lse to the score shape as f32 BEFORE the -inf test: a bool
    # [QB, 1] -> [QB, Tk] lane-broadcast lowers to a tpu.dynamic_gather on
    # vector<8x128xi1> that Mosaic cannot legalize, while f32 lane-broadcasts
    # (already used by `s - safe_lse` above) compile fine.
    lse_full = jnp.broadcast_to(lse, s.shape)
    p = jnp.where((s <= NEG_INF / 2) | jnp.isneginf(lse_full), 0.0, p)

    dv = jax.lax.dot_general(
        p, do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)           # [Tk, D]
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)           # [QB, Tk]
    ds = p * (dp - delta)                             # [QB, Tk]
    dq = jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # [QB, D]
    dk = jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # [Tk, D]

    dq_ref[0] = dq

    # dk/dv accumulate across the (sequential) grid: over q blocks (j) and,
    # under GQA, over the q heads sharing this kv head — initialize only on
    # the FIRST (head-in-group, q-block) step touching the block
    i = pl.program_id(0)
    first = (j == 0) if group == 1 else (
        (j == 0) & (jax.lax.rem(jax.lax.rem(i, num_heads), group) == 0))

    @pl.when(first)
    def _():
        dk_ref[0] = dk
        dv_ref[0] = dv

    @pl.when(jnp.logical_not(first))
    def _():
        dk_ref[0] += dk
        dv_ref[0] += dv


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "interpret", "block_q", "window"))
def attention_block_backward(
    q: jax.Array,                  # [B, Tq, H, D]
    k: jax.Array,                  # [B, Tk, Hkv, D] — Hkv may divide H (GQA)
    v: jax.Array,                  # [B, Tk, Hkv, D]
    do: jax.Array,                 # [B, Tq, H, D] — cotangent of the output
    lse: jax.Array,                # [B, Tq, H] f32 — global log-sum-exp
    delta: jax.Array,              # [B, Tq, H] f32 — rowsum(do * o)
    q_offset: jax.Array,           # [] int32
    k_offset: jax.Array,           # [] int32
    *,
    causal: bool = False,
    scale: float = 1.0,
    interpret: Optional[bool] = None,
    block_q: int = 512,
    window: int = 0,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One K/V block's backward partial: ``(dq, dk_blk, dv_blk)``, all f32.

    ``dq`` is this block's *contribution* to the query gradient (sum over
    blocks in the ring caller); ``dk_blk/dv_blk`` are complete for this block
    w.r.t. this device's queries (sum over devices as the block rotates).
    """
    B, Tq, H, D = q.shape
    Tk, Hkv = k.shape[1], k.shape[2]
    if H % Hkv:
        raise ValueError(f"q heads {H} not a multiple of kv heads {Hkv}")
    group = H // Hkv
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    qb, pad, Tp = _q_blocking(Tq, Tk, D, block_q, backward=True)
    qr = _pad_rows(_split_heads(q), pad)
    kr, vr = _split_heads(k), _split_heads(v)
    dor = _pad_rows(_split_heads(do), pad)
    # -inf lse rows give p = 0: padded rows contribute nothing to dk/dv
    lser = _pad_rows(_split_heads(lse.astype(jnp.float32)[..., None]),
                     pad, value=-jnp.inf)
    deltar = _pad_rows(_split_heads(delta.astype(jnp.float32)[..., None]), pad)

    kernel = functools.partial(_backward_kernel, causal=causal, scale=scale,
                               block_q=qb, num_heads=H, group=group,
                               window=window)
    vma = _vma_of(qr)
    dq, dk, dv = pl.pallas_call(
        kernel,
        grid=(B * H, Tp // qb),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            _q_spec(qb, D),
            _kv_spec(Tk, D, H, Hkv),
            _kv_spec(Tk, D, H, Hkv),
            _q_spec(qb, D),
            _q_spec(qb, 1),
            _q_spec(qb, 1),
        ],
        out_specs=[
            _q_spec(qb, D),
            _kv_spec(Tk, D, H, Hkv),
            _kv_spec(Tk, D, H, Hkv),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tp, D), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((B * Hkv, Tk, D), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((B * Hkv, Tk, D), jnp.float32, vma=vma),
        ],
        interpret=interpret,
    )(_smem_scalar(q_offset), _smem_scalar(k_offset),
      qr, kr, vr, dor, lser, deltar)

    dq = _merge_heads(dq[:, :Tq], B, H)
    dk = _merge_heads(dk, B, Hkv)
    dv = _merge_heads(dv, B, Hkv)
    return dq, dk, dv


def merge_partials(carry, partial):
    """Fold one block partial into the running (o, l, m) flash state."""
    o, l, m = carry
    o_b, l_b, m_b = partial
    m_new = jnp.maximum(m, m_b)
    safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    c_old = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - safe))
    c_new = jnp.where(jnp.isneginf(m_b), 0.0, jnp.exp(m_b - safe))
    l = l * c_old + l_b * c_new
    o = o * c_old[..., None] + o_b * c_new[..., None]
    return o, l, m_new
