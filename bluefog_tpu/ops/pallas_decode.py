"""Paged flash-decode attention: the serving hot path as a Pallas kernel.

The XLA decode path (:func:`bluefog_tpu.serve.kv_cache.attend_rows` /
:func:`attend_chunk`) gathers every lane's FULL ``[Hkv, max_len,
head_dim]`` pages and scores all ``max_len`` positions per step, so HBM
traffic scales with buffer *capacity* rather than with actual context.
This module streams K/V **blocks** straight from HBM through the page
indirection and stops at each lane's real length — the PagedAttention /
flash-decoding recipe:

* the KV-block grid dimension walks ``max_len`` in ``block_k`` steps with
  **online-softmax** accumulation (running ``m``/``l``/``acc`` in VMEM
  scratch), and a scalar-prefetched per-(lane, block) table clamps the
  BlockSpec index past ``lengths[i]`` — a repeated block index means the
  pipeline skips the DMA, and ``pl.when`` skips the compute, so cost
  follows the context, not the capacity;
* a second scalar-prefetched table routes blocks below ``prefix_lens[i]``
  to the lane's **shared prefix page** (same semantics as the XLA
  gather's indirection) — callers must keep prefix lengths block-aligned
  (the engine pins ``prefix_page_tokens % block_k == 0``);
* grouped-query attention blocks over **kv heads** with the q-group (and
  the chunk's T queries) folded into the q tile — no ``jnp.repeat``-ed
  keys, and each K/V block is fetched once for its whole q group;
* int8/fp8 pages are **dequantized in-kernel**: the per-(position, head)
  amax scales ride as ``[block_k]``-blocked lane vectors applied to the
  score rows / probability columns, so quantized pages never round-trip
  through HBM at f32.

The kv-head-major page layout (``[rows, kv_heads, max_len, head_dim]``:
what the engine hands the kernel whatever order the cache stores, a copy
of a layer where the store is token rows, ``kv_cache.logical_pages``)
makes every K/V block a natively-tiled ``[block_k, head_dim]`` VMEM tile
— the same scalar-prefetch BlockSpec trick :mod:`.pallas_moe` proved
through Mosaic for v5e.  Off-TPU the kernel runs in interpreter mode;
under ``JAX_ENABLE_X64`` it accumulates in f64, which is what the oracle
tests pin against the XLA path.

**The dense engine's own read is a second kernel here**
(:func:`attend_live_blocks`; :func:`bluefog_tpu.serve.kv_cache.attend_layer`
takes it from the shapes alone, no option): one new token a lane over a
layer of the STACKED token-row tensors ``[layers, rows, max_len, kv_heads
* head_dim]``, taken whole as operands in HBM (no layer of them is copied
and no logical view made), the token itself beside the pages (its write
stays deferred) and the lanes as they come, nothing laid out by row.
There is no grid: the live (lane, block) pairs of the layer are listed
once and walked in one loop, a pair's K and V blocks coming by async
copies started two pairs ahead, across the lanes' edges.  On the serving
cell (24 layers x 33 rows x 1,024 positions x 1,024 lanes, bfloat16, lanes
at 0.21 of a row on average) the decode program takes 2.80 ms with it
where the batch's bound took 4.87, the kernel at 86 % of the chip's 819
GB/s over what it fetches; a grid over rows with the loop inside a step
took 0.59 ms more (every row waits for its first copy), a grid over (rows
x blocks) 1.35 (6,336 steps a call, most of them skipped)
(``docs/PERF_PR53_RECORD.md``).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attend_rows", "flash_attend_chunk", "attend_live_blocks"]


def _vma_of(x: jax.Array):
    # under shard_map the output varies over the same mesh axes as the input
    return getattr(jax.typeof(x), "vma", frozenset()) or frozenset()


def _flash_kernel(lens_ref, blk_ref, row_ref, q_ref, k_ref, v_ref, *rest,
                  block_k: int, group: int, scale: float, acc_dt,
                  quantized: bool):
    """One (lane, kv-head, kv-block) grid step of the online softmax.

    ``q_ref``: ``[1, 1, T*group, Dh]`` — the lane's queries for this kv
    head, query t of group lane g at row ``t*group + g``; ``k/v_ref``:
    ``[1, 1, block_k, Dh]`` pages (already routed through the prefix
    indirection by the index map); ``m/l/acc`` scratch carries the flash
    state across the (sequential, innermost) block dimension.
    """
    if quantized:
        ksc_ref, vsc_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    s_id = pl.program_id(0)
    b = pl.program_id(2)
    nb = pl.num_programs(2)

    @pl.when(b == 0)
    def _():
        m_ref[:] = jnp.full(m_ref.shape, -jnp.inf, acc_dt)
        l_ref[:] = jnp.zeros(l_ref.shape, acc_dt)
        acc_ref[:] = jnp.zeros(acc_ref.shape, acc_dt)

    length = lens_ref[s_id]            # last valid key position for t=0
    be = blk_ref[s_id, b]              # effective (clamped) block index

    @pl.when(b == be)                  # past the lane's last block: skip
    def _():
        tg = q_ref.shape[2]
        q = q_ref[0, 0].astype(acc_dt) * scale              # [TG, Dh]
        # pages read at the f32 floor, exactly like the XLA path's
        # _gather_pages (under x64 the f64 oracle still sees f32 pages)
        k = k_ref[0, 0].astype(jnp.float32).astype(acc_dt)  # [Bk, Dh]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=acc_dt)                  # [TG, Bk]
        if quantized:
            # per-position amax scales ride as a [1, Bk] lane vector:
            # (q @ (k * sc)^T) == (q @ k^T) * sc, row-wise
            s = s * ksc_ref[0, 0, 0].astype(acc_dt)
        # query t*group+g sits at position length+t: keys 0..length+t valid
        kpos = be * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (tg, block_k), 1)
        tq = jax.lax.broadcasted_iota(jnp.int32, (tg, block_k), 0) // group
        s = jnp.where(kpos <= length + tq, s, -jnp.inf)
        # key 0 is always valid (length >= 0), so after block 0 every row's
        # running max is finite and no exp() below can see inf - inf
        m_prev = m_ref[:]                                   # [TG, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                              # [TG, Bk]
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[:] = m_new
        if quantized:
            p = p * vsc_ref[0, 0, 0].astype(acc_dt)
        v = v_ref[0, 0].astype(jnp.float32).astype(acc_dt)  # [Bk, Dh]
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=acc_dt)

    @pl.when(b == nb - 1)
    def _():
        # l > 0: every row keeps at least key 0, so no 0/0 lane exists
        o_ref[0, 0] = (acc_ref[:] / l_ref[:]).astype(o_ref.dtype)


def _block_k_for(L: int, block_k: int) -> int:
    """Clamp ``block_k`` to the page length and validate divisibility."""
    bk = min(int(block_k), L)
    if bk < 1 or L % bk:
        raise ValueError(
            f"flash decode block_k={block_k} does not tile max_len={L}: "
            f"need block_k >= 1 with max_len % min(block_k, max_len) == 0")
    if bk % 8 and bk != L:
        raise ValueError(
            f"flash decode block_k={block_k}: KV blocks are TPU sublane "
            f"tiles — use a multiple of 8 (or one covering max_len={L})")
    return bk


def _flash_attend(q4: jax.Array, cl: Dict[str, jax.Array],
                  slots: jax.Array, lengths: jax.Array, scale: float,
                  prefix_slots: Optional[jax.Array],
                  prefix_lens: Optional[jax.Array],
                  block_k: int, interpret: bool) -> jax.Array:
    S, T, H, Dh = q4.shape
    Hkv, L = cl["k"].shape[1], cl["k"].shape[2]
    G = H // Hkv
    bk = _block_k_for(L, block_k)
    nb = L // bk
    quantized = "k_scale" in cl
    acc_dt = jnp.promote_types(q4.dtype, jnp.float32)

    # -- scalar-prefetch tables (plain jnp, tiny [S, nb] int32) ----------
    lengths = lengths.astype(jnp.int32)
    # blocks 0..last are real; past that the table repeats `last`, which
    # makes the index map emit the previous block (no DMA) and the kernel
    # body skip (b != blk_tab[s, b])
    last = (lengths + (T - 1)) // bk                            # [S]
    bidx = jnp.arange(nb, dtype=jnp.int32)[None, :]
    blk_tab = jnp.minimum(bidx, last[:, None])                  # [S, nb]
    rows = slots.astype(jnp.int32)[:, None]
    if prefix_slots is not None:
        # a block is entirely inside the shared prefix iff it ends at or
        # below prefix_len — prefix lengths are block-aligned by contract,
        # so no block ever straddles the prefix/slot boundary
        in_prefix = (blk_tab + 1) * bk <= \
            prefix_lens.astype(jnp.int32)[:, None]
        row_tab = jnp.where(in_prefix,
                            prefix_slots.astype(jnp.int32)[:, None], rows)
    else:
        row_tab = jnp.broadcast_to(rows, (S, nb))
    row_tab = row_tab.astype(jnp.int32)

    # -- q: [S, T, H, Dh] -> [S, Hkv, T*G, Dh] (query t of group lane g
    #    at row t*G + g, so one q tile serves its kv head's whole group)
    TG = T * G
    qr = q4.reshape(S, T, Hkv, G, Dh).transpose(0, 2, 1, 3, 4)
    qr = qr.reshape(S, Hkv, TG, Dh)

    kv_spec = pl.BlockSpec(
        (1, 1, bk, Dh),
        lambda s, h, b, lens, blk, row: (row[s, b], h, blk[s, b], 0))
    in_specs = [
        pl.BlockSpec((1, 1, TG, Dh), lambda s, h, b, *refs: (s, h, 0, 0)),
        kv_spec, kv_spec,
    ]
    args = [qr, cl["k"], cl["v"]]
    if quantized:
        # scales viewed [rows, Hkv, nb, 1, bk] so the block's trailing
        # (sublane, lane) dims (1, bk) EQUAL the array dims — the only
        # Mosaic-legal tiling for a sub-8 sublane count at any bk; the
        # kernel reads the block as a [1, bk] lane vector
        sc_spec = pl.BlockSpec(
            (1, 1, 1, 1, bk),
            lambda s, h, b, lens, blk, row: (row[s, b], h, blk[s, b], 0, 0))
        in_specs += [sc_spec, sc_spec]
        args += [cl["k_scale"].reshape(-1, Hkv, nb, 1, bk),
                 cl["v_scale"].reshape(-1, Hkv, nb, 1, bk)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                 # lengths, blk_tab, row_tab
        grid=(S, Hkv, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, TG, Dh),
                               lambda s, h, b, *refs: (s, h, 0, 0)),
        scratch_shapes=[pltpu.VMEM((TG, 1), acc_dt),    # m
                        pltpu.VMEM((TG, 1), acc_dt),    # l
                        pltpu.VMEM((TG, Dh), acc_dt)],  # acc
    )
    kernel = functools.partial(
        _flash_kernel, block_k=bk, group=G, scale=scale, acc_dt=acc_dt,
        quantized=quantized)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, Hkv, TG, Dh), acc_dt,
                                       vma=_vma_of(q4)),
        interpret=interpret,
    )(lengths, blk_tab, row_tab, *args)
    out = out.reshape(S, Hkv, T, G, Dh).transpose(0, 2, 1, 3, 4)
    return out.reshape(S, T, H, Dh).astype(q4.dtype)


def _common_checks(H: int, Dh: int, cl: Dict[str, jax.Array],
                   slots: jax.Array, lengths: jax.Array,
                   prefix_slots, prefix_lens) -> None:
    if cl["k"].ndim != 4 or cl["v"].shape != cl["k"].shape:
        raise ValueError(
            f"flash decode wants one layer's pages [rows, kv_heads, "
            f"max_len, head_dim]; got k {cl['k'].shape} v {cl['v'].shape}")
    Hkv = cl["k"].shape[1]
    if H % Hkv:
        raise ValueError(f"{H} q heads not a multiple of {Hkv} kv heads")
    if cl["k"].shape[-1] != Dh:
        raise ValueError(f"q head_dim {Dh} != page head_dim "
                         f"{cl['k'].shape[-1]}")
    if slots.shape != lengths.shape or slots.ndim != 1:
        raise ValueError(f"slots/lengths must be [S] int32, got "
                         f"{slots.shape} / {lengths.shape}")
    if (prefix_slots is None) != (prefix_lens is None):
        raise ValueError("prefix_slots and prefix_lens come together")
    if ("k_scale" in cl) != ("v_scale" in cl):
        raise ValueError("k_scale and v_scale come together")


def flash_attend_rows(q: jax.Array, kl: jax.Array, vl: jax.Array,
                      slots: jax.Array, lengths: jax.Array,
                      scale: Optional[float] = None, *,
                      k_scale: Optional[jax.Array] = None,
                      v_scale: Optional[jax.Array] = None,
                      prefix_slots: Optional[jax.Array] = None,
                      prefix_lens: Optional[jax.Array] = None,
                      block_k: int = 128,
                      interpret: Optional[bool] = None) -> jax.Array:
    """Flash-decode drop-in for :func:`~bluefog_tpu.serve.kv_cache.
    attend_rows`: one new token per lane (``q``: ``[S, heads,
    head_dim]``) over its slot's valid keys ``0 .. lengths[i]``
    inclusive, reading K/V blocks through the prefix-page indirection
    and dequantizing int8/fp8 pages in-kernel."""
    S, H, Dh = q.shape
    cl = {"k": kl, "v": vl}
    if k_scale is not None:
        cl["k_scale"] = k_scale
    if v_scale is not None:
        cl["v_scale"] = v_scale
    _common_checks(H, Dh, cl, slots, lengths, prefix_slots, prefix_lens)
    if scale is None:
        scale = Dh ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    out = _flash_attend(q[:, None], cl, slots, lengths, float(scale),
                        prefix_slots, prefix_lens, block_k,
                        bool(interpret))
    return out[:, 0]


def flash_attend_chunk(q: jax.Array, cl: Dict[str, jax.Array],
                       slots: jax.Array, lengths: jax.Array,
                       scale: Optional[float] = None, *,
                       prefix_slots: Optional[jax.Array] = None,
                       prefix_lens: Optional[jax.Array] = None,
                       block_k: int = 128,
                       interpret: Optional[bool] = None) -> jax.Array:
    """Flash-decode drop-in for :func:`~bluefog_tpu.serve.kv_cache.
    attend_chunk`: the k-token verify / chunked-prefill forward — query
    t of lane i sits at position ``lengths[i] + t`` and attends keys
    ``0 .. lengths[i] + t`` inclusive.  The T queries fold into the q
    tile with the GQA group, so each K/V block is still fetched once."""
    S, T, H, Dh = q.shape
    _common_checks(H, Dh, cl, slots, lengths, prefix_slots, prefix_lens)
    if scale is None:
        scale = Dh ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _flash_attend(q, cl, slots, lengths, float(scale),
                         prefix_slots, prefix_lens, block_k,
                         bool(interpret))


# ---------------------------------------------------------------------------
# The dense in-place decode read, a bound per LANE
# ---------------------------------------------------------------------------

# blocks of K and of V in flight or in use at once: the third took 0.08 ms
# off the serving cell's decode program of 3.16 (docs/PERF_PR53_RECORD.md)
_LIVE_BLOCKS_DEPTH = 3


def _live_blocks_kernel(layer_ref, row_ref, nblk_ref, at_ref, q_ref, kn_ref,
                        vn_ref, own_ref, k_hbm, v_hbm, out_ref, kbuf, vbuf,
                        sem, lane_of, blk_of, q_s, sn_s, m_s, l_s, acc_s, *,
                        block: int, depth: int):
    """One layer's read: every live (lane, block) pair in ONE walk.

    Lane ``i`` holds row ``row_ref[i]`` of the cache, of which
    ``nblk_ref[i]`` blocks of ``block`` positions are live and
    ``at_ref[i]`` positions valid.  The pairs are listed first (scalars),
    then walked: a pair's blocks of ``k_hbm``/``v_hbm``, the stacked cache
    tensors where they lie, reach ``kbuf``/``vbuf`` by async copies started
    ``depth - 1`` pairs ahead of the one computed on, across the lanes'
    edges, so only a layer's first copy is waited for whole.  At a lane's
    first block its queries are spread over the lanes of a token row
    (``q_s``) and its token's own score taken (``sn_s``), where the running
    maximum ``m_s`` starts, so it is finite whatever the mask leaves;
    ``l_s``/``acc_s`` carry the sums to the lane's last block, where the
    token joins, one division follows and a head keeps the lanes that are
    its own."""
    layer, (G, H) = layer_ref[0], own_ref.shape[:2]
    f32 = dict(preferred_element_type=jnp.float32)
    exact = dict(f32, precision=jax.lax.Precision.HIGHEST)

    def list_lane(i, t):
        def list_block(b, t):
            lane_of[t] = i
            blk_of[t] = b
            return t + 1
        return jax.lax.fori_loop(0, nblk_ref[i], list_block, t)
    total = jax.lax.fori_loop(0, nblk_ref.shape[0], list_lane, 0)
    # a lane of no live block is its token alone
    out_ref[...] = jnp.broadcast_to(vn_ref[...], out_ref.shape)

    def copies(t):
        at = pl.ds(pl.multiple_of(blk_of[t] * block, block), block)
        return [pltpu.make_async_copy(
            hbm.at[layer, row_ref[lane_of[t]], at], buf.at[t % depth],
            sem.at[j, t % depth])
            for j, (hbm, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf)))]

    def start(t):
        @pl.when(t < total)
        def _():
            for c in copies(t):
                c.start()
    for t in range(depth - 1):
        start(t)

    def step(t, _):
        start(t + depth - 1)
        i, b = lane_of[t], blk_of[t]
        k_copy, v_copy = copies(t)

        @pl.when(b == 0)
        def _():
            q = q_ref[i].astype(jnp.float32)                    # [G, lanes]
            spread = own_ref[0] * q[0:1]
            for g in range(1, G):
                spread = spread + own_ref[g] * q[g:g + 1]
            sn = jnp.sum(spread * kn_ref[i], -1, keepdims=True)
            q_s[...] = spread.astype(q_s.dtype)
            sn_s[...] = sn
            m_s[...] = sn
            l_s[...] = jnp.zeros_like(l_s)
            acc_s[...] = jnp.zeros_like(acc_s)
        k_copy.wait()
        q, k = q_s[...], kbuf[t % depth]
        on_lanes = (((1,), (1,)), ((), ()))
        if q.dtype == k.dtype == jnp.bfloat16:
            s = jax.lax.dot_general(q, k, on_lanes, **f32)
        else:
            s = jax.lax.dot_general(q, k.astype(q.dtype), on_lanes, **exact)
        pos = b * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < at_ref[i], s, -jnp.inf)
        m_prev = m_s[...]
        m = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        carried = jnp.exp(m_prev - m)
        p = jnp.exp(s - m)
        m_s[...] = m
        l_s[...] = l_s[...] * carried + jnp.sum(p, -1, keepdims=True)
        v_copy.wait()
        v = vbuf[t % depth]
        if v.dtype == jnp.bfloat16:
            # the float32 probabilities as their three bfloat16 pieces
            # (their sum is the float32 exactly) in ONE matmul, summed in
            # float32
            hi = p.astype(v.dtype)
            rest = p - hi.astype(p.dtype)
            mid = rest.astype(v.dtype)
            lo = (rest - mid.astype(p.dtype)).astype(v.dtype)
            wide = jnp.dot(jnp.concatenate([hi, mid, lo], 0), v, **f32)
            pv = wide[:H] + wide[H:2 * H] + wide[2 * H:]
        else:
            pv = jnp.dot(p, v.astype(p.dtype), **exact)
        acc_s[...] = acc_s[...] * carried + pv

        @pl.when(b == nblk_ref[i] - 1)
        def _():
            pn = jnp.exp(sn_s[...] - m_s[...])
            x = (acc_s[...] + pn * vn_ref[i]) / (l_s[...] + pn)
            for g in range(G):
                out_ref[i, pl.ds(g, 1), :] = jnp.sum(
                    x * own_ref[g], 0, keepdims=True)
    jax.lax.fori_loop(0, total, step, None)


def attend_live_blocks(q: jax.Array, kn: jax.Array, vn: jax.Array,
                       own: jax.Array, kl: jax.Array, vl: jax.Array,
                       layer: jax.Array, rows: jax.Array, nblk: jax.Array,
                       at: jax.Array, *, block: int,
                       interpret: Optional[bool] = None) -> jax.Array:
    """One new token a lane over ``layer`` of the stacked token-row tensors
    ``kl``/``vl`` (``[layers, rows, max_len, lanes]``, ``lanes`` the kv
    heads times ``head_dim``; taken whole as operands in HBM: no layer of
    them is copied), lane ``i`` reading of its row ``rows[i]`` the blocks
    ``0 .. nblk[i] - 1`` of ``block`` positions, of them positions ``0 ..
    at[i] - 1``, and nothing else: a lane of no block reads nothing and is
    its token alone.  The arithmetic is
    :func:`bluefog_tpu.serve.kv_cache._attend_dense`'s: the scaled queries
    spread block-diagonally over a token row's lanes, exact products, mask
    by the lane's own length, float32 softmax, the float32 probabilities on
    bfloat16 pages as their three bfloat16 pieces in one matmul, the
    token's own score and value joined once at the end, one division; a
    lane's sum is carried from block to block under a running maximum.

    ``q`` ``[S, group, lanes]``: q head ``k * group + g`` in the lanes of
    kv head ``k`` of entry ``g``, scaled; ``kn``/``vn`` ``[S, 1, lanes]``
    float32: the token's key and value as a token row; ``own`` ``[group,
    heads, lanes]`` float32, 1.0 where a lane is a head's own and the head
    stands at place ``g`` of its group (``heads`` a multiple of 16: whole
    tiles of bfloat16 probabilities; the spare ones own nothing).  Returns
    ``[S, group, lanes]`` float32, laid out as ``q``."""
    S, G, C = q.shape
    H, depth = own.shape[1], _LIVE_BLOCKS_DEPTH
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    pairs = S * (kl.shape[2] // block)
    # every lane's operands and results are held whole beside the blocks in
    # flight: past the compiler's own allowance (16 MiB a kernel on a v5e)
    # the call asks for what it holds
    held = sum(a.size * a.dtype.itemsize for a in (q, kn, vn, own)) \
        + 4 * (S * G + 2 * H) * C \
        + depth * block * C * (kl.dtype.itemsize + vl.dtype.itemsize)
    params = pltpu.CompilerParams(vmem_limit_bytes=held + (4 << 20)) \
        if held > (12 << 20) else None
    i32 = lambda a: a.astype(jnp.int32)
    return pl.pallas_call(
        functools.partial(_live_blocks_kernel, block=block, depth=depth),
        compiler_params=params,
        in_specs=[smem] * 4 + [vmem] * 4 + [hbm] * 2,
        out_specs=vmem,
        out_shape=jax.ShapeDtypeStruct((S, G, C), jnp.float32,
                                       vma=_vma_of(q)),
        scratch_shapes=[pltpu.VMEM((depth, block, C), kl.dtype),
                        pltpu.VMEM((depth, block, C), vl.dtype),
                        pltpu.SemaphoreType.DMA((2, depth)),
                        pltpu.SMEM((pairs,), jnp.int32),
                        pltpu.SMEM((pairs,), jnp.int32),
                        pltpu.VMEM((H, C), q.dtype),
                        pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, C), jnp.float32)],
        interpret=interpret,
    )(i32(jnp.reshape(layer, (1,))), i32(rows), i32(nblk), i32(at), q, kn,
      vn, own, kl, vl)
