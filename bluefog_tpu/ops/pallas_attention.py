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

Beside the partial: the backward of one block (attention_block_backward,
the ring's and the local path's) and the whole-sequence forward of the
local path (attention_local_forward).  Both walk the key axis inside the
kernel and stop at the diagonal: only the key blocks the causal mask (and a
window) leaves a query block are read (key_blocks_visited counts them).

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


# Mosaic gives a v5e kernel 16 MiB of scoped VMEM.  K and V (and dK, dV in
# the backward) stay whole in it, one DMA a head.  The backward and the
# local forward walk the key axis INSIDE the body, a block of ``block_k``
# rows at a time, and hold [block_q, block_k] f32 score tiles; the ring's
# forward partial (`_partial_kernel`) still holds one [block_q, Tk] tile.
# The two bounds below are calibrated against Mosaic for v5e
# (tests/test_tpu_aot.py compiles every row): the resident rows against the
# limit itself, the score tiles against three quarters of it, which leaves
# room for the q/o/do tiles and Mosaic's own temporaries.
_VMEM_LIMIT = 16 * 1024 * 1024
_SCORE_BUDGET = 12 * 1024 * 1024
_LANE = 128
_MIN_BLOCK_Q = 8
_MAX_BLOCK_K = 512


def _k_blocking(Tk: int) -> int:
    """The key block of the kernels that loop over keys: the largest
    divisor of ``Tk`` that is a whole number of lane tiles and at most
    ``_MAX_BLOCK_K``; ``Tk`` whole (one tile, no loop) where none exists."""
    for kb in range(_MAX_BLOCK_K, 0, -_LANE):
        if kb <= Tk and Tk % kb == 0:
            return kb
    return Tk


def _q_blocking(Tq: int, Tk: int, D: int, block_q: int, backward: bool,
                block_k: int = 0):
    """Pick the q block: at most ``block_q`` rows, shrunk (to a power of
    two) until the score tiles fit VMEM — the tile is [QB, block_k]
    (``block_k`` 0: [QB, Tk], the forward partial's) instead of [Tq, Tk]
    (a 4k-token local block would otherwise need a 64 MB tile).
    Non-divisible Tq is padded up to a block multiple — never fall back to
    one full [Tq, Tk] tile, which is the exact blow-up blocking prevents.
    Returns ``(qb, pad, Tp)`` with ``Tp = Tq + pad`` a multiple of ``qb``.

    Raises ``ValueError`` at trace time when the shape cannot fit whatever
    the block, so the caller never meets Mosaic's RESOURCE_EXHAUSTED."""
    which = "backward" if backward else "forward"
    kb = block_k or Tk
    lanes = -(-D // _LANE) * _LANE                    # minor dim pads to 128
    rows = (4 if backward else 2) * Tk * lanes * 4    # K, V (, dK, dV) in f32
    tiles = 3 if backward else 2                      # s, p (, dp) per q row
    io = 8 if backward else 4          # q, o (, do, dq) tiles, double-buffered
    fit = _SCORE_BUDGET // (tiles * kb * 4 + io * lanes * 4)
    if rows > _VMEM_LIMIT or fit < _MIN_BLOCK_Q:
        raise ValueError(
            f"flash attention {which}: Tk={Tk} keys at head_dim {D} do not "
            f"fit the {_VMEM_LIMIT >> 20} MiB scoped VMEM limit (whole K/V "
            f"rows need {rows} bytes; a {_MIN_BLOCK_Q}-row score block "
            f"needs {tiles * _MIN_BLOCK_Q * kb * 4} of "
            f"{_SCORE_BUDGET}): the kernel keeps K/V rows whole — shard the "
            "sequence further (ring attention) or use the XLA path")
    qb = min(block_q, Tq)
    if qb > fit:
        qb = 1 << (fit.bit_length() - 1)
    pad = (-Tq) % qb
    return qb, pad, Tq + pad


def _blocking(Tq: int, Tk: int, D: int, block_q: int, backward: bool):
    """``(block_q, block_k)`` of the kernels that loop over key blocks."""
    kb = _k_blocking(Tk)
    return _q_blocking(Tq, Tk, D, block_q, backward, block_k=kb)[0], kb


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


def _check_heads(q: jax.Array, k: jax.Array) -> None:
    H, Hkv = q.shape[2], k.shape[2]
    if H % Hkv:
        raise ValueError(f"q heads {H} not a multiple of kv heads {Hkv}")


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


# --- the key axis, blocked inside the kernel --------------------------------
#
# A query block meets only the key blocks [lo, hi) that the causal mask
# (and a window, where one is given) leaves it; of those only the blocks the
# diagonal or the window's edge crosses take a mask.  The arithmetic is ONE
# function on plain ints and traced scalars alike: the kernels' loop bounds
# and :func:`key_blocks_visited` cannot drift apart.

def _clip(x, lo, hi):
    if all(isinstance(t, int) for t in (x, lo, hi)):
        return min(max(x, lo), hi)
    return jnp.minimum(jnp.maximum(x, lo), hi)


def _key_block_range(first, block_q: int, block_k: int, num_k: int,
                     causal: bool, window: int = 0):
    """Key blocks of ``block_k`` rows that a block of ``block_q`` query rows
    meets, ``first`` being its first row's position minus key 0's:
    ``(lo, a, b, hi)`` — blocks ``[lo, hi)`` hold a key some row may see,
    blocks ``[a, b)`` only keys every row sees (no mask needed)."""
    if not causal:
        return 0, 0, num_k, num_k
    last = first + block_q - 1
    hi = _clip((last + block_k) // block_k, 0, num_k)
    b = _clip((first + 1) // block_k, 0, hi)
    if not window:
        return 0, 0, b, hi
    lo = _clip((first - window + 1) // block_k, 0, hi)
    a = _clip((last - window + block_k) // block_k, lo, hi)
    return lo, a, _clip(b, a, hi), hi


def key_blocks_visited(Tq: int, Tk: int, block_q: int, block_k: int,
                       q_offset: int = 0, k_offset: int = 0,
                       causal: bool = True, window: int = 0):
    """``(visited, total)`` ``[block_q, block_k]`` tiles of the
    ``ceil(Tq / block_q) x ceil(Tk / block_k)`` grid: the key blocks the
    kernels' loops run over, by the kernels' own arithmetic."""
    num_q, num_k = -(-Tq // block_q), -(-Tk // block_k)
    visited = 0
    for j in range(num_q):
        lo, _, _, hi = _key_block_range(
            q_offset + j * block_q - k_offset, block_q, block_k, num_k,
            causal, window)
        visited += hi - lo
    return visited, num_q * num_k


def _block_mask(s, first, k_start, window: int):
    """In-kernel: mask the scores of query rows at ``first..`` against keys
    at ``k_start..`` (both relative to key 0) above the diagonal and, with
    ``window > 0``, more than ``window - 1`` tokens behind the query."""
    ahead = (first - k_start
             + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
             - jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
    keep = ahead >= 0
    if window:
        keep = keep & (ahead < window)
    return jnp.where(keep, s, NEG_INF)


def _over_key_blocks(step, carry, first, *, block_q: int, block_k: int,
                     num_k: int, causal: bool, window: int):
    """Run ``step(i, carry, masked)`` over the key blocks a query block
    meets: one loop over the blocks under the mask, one on either side over
    those its edges cross (the loops the static arguments rule out are not
    traced)."""
    lo, a, b, hi = _key_block_range(first, block_q, block_k, num_k,
                                    causal, window)
    for start, stop, masked in ((lo, a, True), (a, b, False), (b, hi, True)):
        if isinstance(start, int) and isinstance(stop, int) and start == stop:
            continue
        carry = jax.lax.fori_loop(
            start, stop, functools.partial(step, masked=masked), carry)
    return carry


def _key_rows(i, block_k: int):
    return pl.ds(pl.multiple_of(i * block_k, block_k), block_k)


def _fold_key_blocks(q, k_ref, v_ref, first, *, block_q: int, block_k: int,
                     causal: bool, window: int = 0):
    """The on-line softmax fold of one query block (``q`` [QB, D] f32,
    scaled) over the key blocks it meets: ``(o, l, m)`` relative to the
    running max ``m``; a row that met no key keeps ``m <= NEG_INF / 2`` and
    whatever its masked tiles left in ``o`` and ``l`` (the caller zeroes
    it)."""
    def step(i, carry, masked):
        o, l, m = carry
        rows = _key_rows(i, block_k)
        k = k_ref[0, rows, :].astype(jnp.float32)     # [KB, D]
        v = v_ref[0, rows, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # [QB, KB]
        if masked:
            s = _block_mask(s, first, i * block_k, window)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # a masked score under a real max is an exact 0; under no max yet
        # it is a 1 that the first real max's rescale (exp(-1e30)) wipes
        p = jnp.exp(s - m_new)
        c = jnp.exp(m - m_new)
        l = l * c + jnp.sum(p, axis=-1, keepdims=True)
        o = o * c + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [QB, D]
        return o, l, m_new

    tq = q.shape[0]
    carry = (jnp.zeros((tq, v_ref.shape[-1]), jnp.float32),
             jnp.zeros((tq, 1), jnp.float32),
             jnp.full((tq, 1), NEG_INF, jnp.float32))
    return _over_key_blocks(
        step, carry, first, block_q=block_q, block_k=block_k,
        num_k=k_ref.shape[1] // block_k, causal=causal, window=window)


def _local_forward_kernel(q_ref, k_ref, v_ref, out_ref, lse_ref, *,
                          causal: bool, scale: float, block_q: int,
                          block_k: int):
    q = q_ref[0].astype(jnp.float32) * scale          # [QB, D]
    o, l, m = _fold_key_blocks(
        q, k_ref, v_ref, pl.program_id(1) * block_q, block_q=block_q,
        block_k=block_k, causal=causal)
    # a row that met a key has l >= 1 (its max's own exp); one that met
    # none has garbage in o and l.  f32 selects on the [QB, 1] column only:
    # a bool [QB, 1] -> [QB, D] lane-broadcast is a vector<i1> gather
    # Mosaic cannot legalize (see `_backward_kernel`)
    none = m <= NEG_INF / 2
    out_ref[0] = (o / jnp.where(none, jnp.inf, l)).astype(out_ref.dtype)
    lse_ref[0] = jnp.where(none, -jnp.inf,
                           m + jnp.log(jnp.where(none, 1.0, l)))


def _local_forward(q, k, v, *, causal, scale, interpret, block_q, block_k):
    B, Tq, H, D = q.shape
    Tk, Hkv = k.shape[1], k.shape[2]
    qr = _pad_rows(_split_heads(q), (-Tq) % block_q)
    Tp = qr.shape[1]
    kernel = functools.partial(_local_forward_kernel, causal=causal,
                               scale=scale, block_q=block_q, block_k=block_k)
    vma = _vma_of(qr)
    out, lse = pl.pallas_call(
        kernel,
        grid=(B * H, Tp // block_q),
        in_specs=[
            _q_spec(block_q, D),
            _kv_spec(Tk, D, H, Hkv),
            _kv_spec(Tk, D, H, Hkv),
        ],
        out_specs=[
            _q_spec(block_q, D),
            _q_spec(block_q, 1),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tp, D), q.dtype, vma=vma),
            jax.ShapeDtypeStruct((B * H, Tp, 1), jnp.float32, vma=vma),
        ],
        interpret=interpret,
    )(qr, _split_heads(k), _split_heads(v))
    return (_merge_heads(out[:, :Tq], B, H),
            _merge_heads(lse[:, :Tq], B, H)[..., 0])


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "interpret", "block_q"))
def attention_local_forward(
    q: jax.Array,                  # [B, Tq, H, D]
    k: jax.Array,                  # [B, Tk, Hkv, D] — Hkv may divide H (GQA)
    v: jax.Array,                  # [B, Tk, Hkv, D]
    *,
    causal: bool = False,
    scale: float = 1.0,
    interpret: Optional[bool] = None,
    block_q: int = 512,
) -> Tuple[jax.Array, jax.Array]:
    """Whole-sequence flash attention forward, q and k both at position 0.

    Each query block folds the key blocks the causal mask leaves it into a
    running ``(o, l, m)`` inside the kernel; blocks above the diagonal are
    never read.  ``block_q`` is an upper bound (see :func:`_q_blocking`);
    the key block is the code's own choice (:func:`_k_blocking`).

    Returns ``(out [B,Tq,H,D] in q.dtype, lse [B,Tq,H] f32)``: the
    normalised output and the log-sum-exp of the scaled scores (a row with
    no valid key gets ``out = 0, lse = -inf``).
    """
    _check_heads(q, k)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    qb, kb = _blocking(q.shape[1], k.shape[1], q.shape[-1], block_q, False)
    return _local_forward(q, k, v, causal=causal, scale=scale,
                          interpret=interpret, block_q=qb, block_k=kb)


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
                     causal: bool, scale: float, block_q: int, block_k: int,
                     num_heads: int = 0, group: int = 1, window: int = 0):
    """Flash-attention backward for one K/V block, scores recomputed in VMEM
    a ``[block_q, block_k]`` tile at a time over the key blocks this grid
    step's query rows may see.

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
    i, j = pl.program_id(0), pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)                  # [QB, D]
    do = do_ref[0].astype(jnp.float32)                # [QB, D]
    delta = delta_ref[0]                              # [QB, 1]
    lse = lse_ref[0]                                  # [QB, 1] (-inf: no keys)
    # rows with no valid keys (padded rows carry lse = -inf) and masked
    # scores (NEG_INF) both come out of the exp as exact zeros: no
    # [QB, KB] select, and no bool lane-broadcast (a tpu.dynamic_gather on
    # vector<8x128xi1> that Mosaic cannot legalize)
    shift = jnp.where(jnp.isneginf(lse), -NEG_INF, lse)
    first = qoff_ref[0] + j * block_q - koff_ref[0]
    # dk/dv accumulate across the (sequential) grid: over q blocks (j) and,
    # under GQA, over the q heads sharing this kv head — zero them only on
    # the FIRST (head-in-group, q-block) step touching the block
    first_step = (j == 0) if group == 1 else (
        (j == 0) & (jax.lax.rem(jax.lax.rem(i, num_heads), group) == 0))

    @pl.when(first_step)
    def _():
        dk_ref[0] = jnp.zeros(dk_ref.shape[1:], dk_ref.dtype)
        dv_ref[0] = jnp.zeros(dv_ref.shape[1:], dv_ref.dtype)

    def step(n, dq, masked):
        rows = _key_rows(n, block_k)
        k = k_ref[0, rows, :].astype(jnp.float32)     # [KB, D]
        v = v_ref[0, rows, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [QB, KB]
        if masked:
            s = _block_mask(s, first, n * block_k, window)
        p = jnp.exp(s - shift)
        dv_ref[0, rows, :] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [KB, D]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # [QB, KB]
        ds = p * (dp - delta)                         # [QB, KB]
        dk_ref[0, rows, :] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [KB, D]
        return dq + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [QB, D]

    dq = _over_key_blocks(
        step, jnp.zeros(dq_ref.shape[1:], jnp.float32), first,
        block_q=block_q, block_k=block_k, num_k=k_ref.shape[1] // block_k,
        causal=causal, window=window)
    dq_ref[0] = dq * scale


def _block_backward(q, k, v, do, lse, delta, q_offset, k_offset, *, causal,
                    scale, interpret, block_q, block_k, window):
    B, Tq, H, D = q.shape
    Tk, Hkv = k.shape[1], k.shape[2]
    pad = (-Tq) % block_q
    qr = _pad_rows(_split_heads(q), pad)
    Tp = qr.shape[1]
    kr, vr = _split_heads(k), _split_heads(v)
    dor = _pad_rows(_split_heads(do), pad)
    # -inf lse rows give p = 0: padded rows contribute nothing to dk/dv
    lser = _pad_rows(_split_heads(lse.astype(jnp.float32)[..., None]),
                     pad, value=-jnp.inf)
    deltar = _pad_rows(_split_heads(delta.astype(jnp.float32)[..., None]), pad)

    kernel = functools.partial(_backward_kernel, causal=causal, scale=scale,
                               block_q=block_q, block_k=block_k, num_heads=H,
                               group=H // Hkv, window=window)
    vma = _vma_of(qr)
    dq, dk, dv = pl.pallas_call(
        kernel,
        grid=(B * H, Tp // block_q),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            _q_spec(block_q, D),
            _kv_spec(Tk, D, H, Hkv),
            _kv_spec(Tk, D, H, Hkv),
            _q_spec(block_q, D),
            _q_spec(block_q, 1),
            _q_spec(block_q, 1),
        ],
        out_specs=[
            _q_spec(block_q, D),
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
    Key blocks the causal mask or the window hides from a query block are
    never read (a K/V block wholly above the diagonal gives zeros).
    """
    _check_heads(q, k)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    qb, kb = _blocking(q.shape[1], k.shape[1], q.shape[-1], block_q, True)
    return _block_backward(
        q, k, v, do, lse, delta, q_offset, k_offset, causal=causal,
        scale=scale, interpret=interpret, block_q=qb, block_k=kb,
        window=window)


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
