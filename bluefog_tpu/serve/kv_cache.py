"""Slotted paged KV cache for the decentralized serving engine.

Layout (per device, i.e. per (replica, stage, tp) coordinate of the
compose carving)::

    k, v: [layers, slots + prefix_slots + 1, kv_heads, max_len, head_dim]
    k, v: [layers, slots + prefix_slots + 1, max_len, kv_heads * head_dim]

(the second where a ``head_dim`` under 128 fills the 128 lanes only
beside the other heads: **token rows**, :func:`page_order`)

* ``layers``   — the decoder blocks THIS pipeline stage owns;
* ``slots``    — request slots: one resident sequence each, allocated at
  admission and recycled at retirement (continuous batching never reshapes
  the cache — shapes are static so the decode program never retraces);
* the next ``prefix_slots`` physical rows are **shared prefix pages**:
  content-addressed prompt prefixes sealed once by a prefill and then
  attached to by any number of requests (read-only after sealing — the
  divergent suffix copy-on-writes into the request's private slot, so
  sharers can never contaminate each other);
* the last physical row is the **trash slot**: padding rows of a bucketed
  decode batch append their garbage kv there, so an inactive lane can run
  the exact same program as a live one;
* ``max_len``  — per-slot token capacity (prompt + generated);
* ``kv_heads`` — the kv heads THIS tp rank holds: the cache is sharded
  over ``("tp",)`` by splitting heads, and the layout is grouped-query
  aware (``kv_heads`` may be ``num_heads // group`` compact heads, the
  same ``num_kv_heads`` contract as
  :class:`bluefog_tpu.models.transformer.RingTransformerBlock` — q heads
  attend their ``h // group`` kv head).

**The order a row's pages lie in follows the shapes**
(:func:`page_order`; :meth:`KVCacheConfig.shapes`), because a decode
token's write costs the tiles it touches: kept by head (``kv_heads``
BEFORE ``max_len``: one (row, head)'s key positions are contiguous, and
the flash-decode kernel, :mod:`bluefog_tpu.ops.pallas_decode`, streams
``[block_k, head_dim]`` K/V blocks straight from HBM) where ``head_dim``
fills the 128 lanes, or where nothing can be put beside it (the compiler
then holds the positions in the lanes and a token is one COLUMN of
tiles); as **token rows** ``[max_len, kv_heads * head_dim]`` where
``head_dim`` is under 128 and the heads side by side fill the lanes (16
heads of 64): the same bytes, and a token is one ROW, 8 tiles a layer
where the column was 64.  The functions below tell the two apart by a
payload tensor's rank.  Writes land in either order; the in-place decode
read contracts token rows as they lie (:func:`_attend_by_row`); every
other reader (the staged reads, prefix pages, quantized stores, the
k-token forms, the engine's view for the flash-decode kernel) meets
them through ONE logical view ``[..., kv_heads, max_len, head_dim]``
(:func:`logical_pages`), a copy whose speed nothing measures.

**Quantized storage** (``store="int8"`` / ``"fp8"``): pages hold the
quantized payload plus per-(position, head) f32 amax scales in sibling
``k_scale``/``v_scale`` arrays — the exact symmetric-quantization recipe
the gossip wire codec uses (:func:`bluefog_tpu.ops.collectives._amax_scale`
with a head_dim-sized block), dequantized inside :func:`attend_rows` /
:func:`attend_chunk` right before the score matmul.  ``store="raw"``
keeps the payload in ``dtype`` (f32 or bf16) with no scales.

**A decode token reaches the cache once, after the layers.**  On the
engine's XLA path each layer hands its new token's pages
(:func:`token_pages`) to the attention beside the cache, the layer loop
stacks them as its output, and :func:`append_tokens` writes ``t[:, slot,
:, length]`` (a token row: ``t[:, slot, length]``) with one
``dynamic_update_slice`` per lane and tensor.
:func:`layer_append`, the write per lane, tensor AND layer, stays for the
flash-decode kernel, which streams its pages from HBM and so needs the
token there before it runs; chunks (:func:`layer_append_chunk`) and
prompts (:func:`layer_prefill`) are written per layer as before.

**Decode attention meets the pages where they lie, in every family**
(:func:`_attend_by_row` under :func:`attend_layer`,
:func:`latent_attend_slots` and :func:`attend_slots`): the lanes' queries
are laid out by row, every row meets its own pages in one grouped einsum
that takes the layer's slice of the stacked tensor as its operand
(:func:`_layer_pages`), the token is attended beside the pages, and the
lanes' rows of the result are read back: one pass over K and one over V a
layer.  **The dense family's read stops where the live lanes end**
(:func:`attend_layer`, :func:`_attend_dense`; one decode program a bucket
as before, no option, and what is left out is what the mask gave a weight
of exactly 0.0).  *Token rows stop at each LANE's own last position*: a
Pallas kernel (:func:`bluefog_tpu.ops.pallas_decode.attend_live_blocks`)
takes the stacked tensors whole, where they lie, and the lanes as they
come (nothing is laid out by row), and walks ONE list of the layer's live
(lane, block) pairs, a lane the blocks ``0 .. ceil(length / 128) - 1`` of
its row (:func:`read_block`, :func:`live_blocks`) and a lane on the trash
row nothing, so that a row no lane holds is never touched; a block is
fetched by an async copy ahead of the one computed on and the lane's
softmax is carried across its blocks.  It engages from the shapes alone: token rows, the read in place,
a row of more than one whole block; a shorter row is read whole by XLA.
*Pages kept by head stop at the batch's longest live position*: the passes
take positions ``[0, bound)`` of every row, ``bound`` the least multiple
of a step that covers the longest lane that holds a request
(:func:`live_bound`; the step from ``max_len`` alone, an eighth of a row
in whole 128s, so at most eight bounds: :func:`read_bounds`), chosen ON
THE DEVICE from the call's lengths by a ``lax.switch`` in every layer
whose branches are the two passes over a shorter slice of the layer's
pages and nothing else.  The latent and the hybrid family read every row
whole.  The staged form
(:func:`attend_rows` through
:func:`_gather_pages`: each lane's row read into a buffer of its own,
2 MB a lane and tensor, and read again by the attention) stays where
"one query a row" does not hold or is not priced: shared prefix pages
(several lanes attend one row), a quantized store (the scales are not
folded into the by-row products), the k-token forms
(:func:`attend_chunk`), a token already written (the flash-decode
kernel's order) and a decode bucket under a third of the rows
(:func:`read_in_place`).  Three things hold the TPU's compiler to the
writes and to either read (each found by compiling the serving cell's
decode program for a described v5e, ``tests/test_serve_fast.py``): every
window written, every row staged and the layer's slice read in place is
pinned to the cache's own axis order (:func:`_pin_window`), or the
compiler copies both tensors whole into another; where rows are staged
V's are read only after the softmax, or both tensors' staged rows are
alive at once and one of them leaves the chip's on-chip memory; each
lane's window is laid out only after the write before it, or all of them
(one position padded to a tile's worth: 6 MiB each where positions are
minor, 0.79 MB of a token row) are held at once.

**Two kinds of layer in one model** (:class:`HybridCacheConfig`): full
layers keep every position of a slot, window layers a ring of ``window``
positions (position ``p`` at ``p mod window``); a slot owns its row of
both, a prompt lands whole in the one and by its last ``window`` positions
in the other (:func:`hybrid_prefill`), a decode token once per lane and
tensor after the layers (:func:`hybrid_append_tokens`), and decode reads
the pages in place (:func:`attend_slots`).  :class:`LatentCacheConfig`
is the cache of one compressed vector per token, which every head of a
layer shares (:func:`latent_attend_slots`).

**A third kind of tensor: a state with no positions**
(:class:`SsmCacheConfig`).  A recurrent layer (a state-space mixer, or a
delta-rule mixer whose state is a matrix of key by value channels a head)
keeps per slot a state ``[heads, head_dim, state]`` in float32 and the last
inputs of its convolution: no length, overwritten whole by a prompt
(:func:`ssm_prefill`), read AND written whole by every decode step
(:func:`ssm_conv_step`, :func:`ssm_state_step`: every row of a layer's
states in one pass, where they lie, a row no lane names passing
unchanged), beside the rows of the model's attention layers in one tree
under one :class:`SlotAllocator`.  Prefix pages do not apply to it (a
state has no positions to share); a slot that is admitted again is
overwritten whole, so nothing carries over.  Its reads and writes run
under the device scopes ``ssm.scan`` and ``ssm.conv``, not ``cache.*``.

Every read of the cache here runs under the device scope ``cache.read``
and every landing in it under ``cache.write`` (``jax.named_scope``;
:func:`bluefog_tpu.utils.tracing.device_scopes`), so a device trace says
what a program spends on either whatever the compiler numbers its
instructions.

The pure functions here (:func:`layer_append`, :func:`attend_layer`,
:func:`attend_rows`, :func:`attend_chunk`, ...) are the single-device
math the engine's
shard_map body calls per layer; they are also unit-tested directly (GQA
grouping, slot-reuse equivalence after evict, quantization drift bounds,
the deferred write against the per-layer one).
:class:`SlotAllocator` is the host-side free heap with occupancy gauges
(``bluefog_serve_kv_slots_in_use`` / ``bluefog_serve_kv_occupancy``);
:class:`PrefixCache` is the host-side content-addressed page directory
(``bluefog_serve_prefix_{hits,misses}_total``).
"""
from __future__ import annotations

import dataclasses
import hashlib
import heapq
import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental.layout import Layout, with_layout_constraint

from ..ops import pallas_decode
from ..ops.collectives import _amax_scale
from ..utils import metrics as _metrics

__all__ = ["KVCacheConfig", "LatentCacheConfig", "HybridCacheConfig",
           "SsmCacheConfig", "ssm_prefill", "ssm_conv_step", "ssm_state_step",
           "ssm_append_tokens",
           "hybrid_prefill", "hybrid_append_tokens", "attend_slots",
           "latent_prefill",
           "latent_append_tokens", "latent_attend_slots", "init_cache",
           "attend_rows", "attend_layer", "read_in_place", "read_bounds",
           "live_bound", "read_block", "live_blocks", "dense_positions_met",
           "page_order", "logical_pages",
           "attend_chunk", "token_pages", "append_tokens", "layer_append",
           "layer_append_chunk", "layer_prefill", "quantize_rows",
           "dequantize_rows", "store_dtype", "SlotAllocator", "PrefixCache"]

KV_STORES = ("raw", "int8", "fp8")


def store_dtype(store: str, raw_dtype: Any = jnp.float32):
    """Payload dtype of one cache page under ``store``."""
    if store == "raw":
        return raw_dtype
    if store == "int8":
        return jnp.int8
    if store == "fp8":
        if not hasattr(jnp, "float8_e4m3fn"):
            raise ValueError("fp8 KV needs jnp.float8_e4m3fn support in "
                             "this jax build — use kv store 'int8'")
        return jnp.float8_e4m3fn
    raise ValueError(f"unknown KV store {store!r}: choose from {KV_STORES}")


def quantize_rows(x: jax.Array, store: str):
    """Quantize kv rows ``[..., head_dim]`` for page storage.

    Returns ``(payload, scale)`` where ``scale`` is ``None`` for raw
    storage and ``[...]`` (head_dim folded away) f32 otherwise — one amax
    scale per (token position, kv head), i.e. the wire codec's ``@B``
    blockwise recipe at ``B = head_dim``, reusing its
    :func:`~bluefog_tpu.ops.collectives._amax_scale` kernel verbatim.
    """
    if store == "raw":
        return x, None
    shape = x.shape
    xf = x.astype(jnp.float32).reshape(-1, shape[-1])
    if store == "int8":
        scaled, scale = _amax_scale(xf, 127.0, shape[-1])
        q = jnp.clip(jnp.round(scaled), -127, 127).astype(jnp.int8)
    elif store == "fp8":
        f8max = float(jnp.finfo(store_dtype("fp8")).max)          # 448
        scaled, scale = _amax_scale(xf, f8max, shape[-1])
        q = scaled.astype(store_dtype("fp8"))
    else:
        raise ValueError(f"unknown KV store {store!r}: choose from "
                         f"{KV_STORES}")
    return q.reshape(shape), scale.reshape(shape[:-1])


def dequantize_rows(q: jax.Array, scale: Optional[jax.Array],
                    dtype: Any) -> jax.Array:
    """Inverse of :func:`quantize_rows` (identity cast for raw storage)."""
    if scale is None:
        return q.astype(dtype)
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


@dataclasses.dataclass(frozen=True)
class KVCacheConfig:
    """Static shape of one device's cache (all sharding already applied)."""
    layers: int            # decoder blocks on this pipeline stage
    slots: int             # request slots (excluding prefix pages + trash)
    max_len: int           # tokens per slot
    kv_heads: int          # kv heads on this tp rank (GQA-compact)
    head_dim: int
    dtype: Any = jnp.float32   # raw payload / dequantization target dtype
    store: str = "raw"         # page storage: "raw" | "int8" | "fp8"
    prefix_slots: int = 0      # shared prefix pages (rows after `slots`)

    def __post_init__(self):
        for name in ("layers", "slots", "max_len", "kv_heads", "head_dim"):
            v = getattr(self, name)
            if not isinstance(v, int) or v < 1:
                raise ValueError(f"KVCacheConfig.{name}={v!r} must be a "
                                 "positive int")
        if not isinstance(self.prefix_slots, int) or self.prefix_slots < 0:
            raise ValueError(f"KVCacheConfig.prefix_slots="
                             f"{self.prefix_slots!r} must be an int >= 0")
        store_dtype(self.store)        # validates the store name eagerly

    @property
    def rows(self) -> int:
        """Physical rows: request slots + prefix pages + the trash slot."""
        return self.slots + self.prefix_slots + 1

    @property
    def trash_slot(self) -> int:
        """Physical row index padding lanes write their garbage kv to."""
        return self.slots + self.prefix_slots

    def prefix_row(self, page: int) -> int:
        """Physical row of shared prefix page ``page``."""
        if not 0 <= page < self.prefix_slots:
            raise ValueError(f"prefix page {page} out of range "
                             f"[0, {self.prefix_slots})")
        return self.slots + page

    @property
    def quantized(self) -> bool:
        return self.store != "raw"

    @property
    def page_order(self) -> str:
        """How a layer's pages of a row lie (:func:`page_order`)."""
        return page_order(self.kv_heads, self.head_dim, self.max_len)

    @property
    def read_step(self) -> int:
        """The positions the in-place decode read's bound advances by: a
        lane's own block of token rows (:func:`read_block`), else the step
        of the batch's bound (:func:`read_bounds`)."""
        if self.page_order == "token_rows":
            return read_block(self.max_len)
        return read_bounds(self.max_len)[0]

    def shapes(self) -> Dict[str, Tuple[int, ...]]:
        """The stored shape of every cache tensor: the payload by its
        :attr:`page_order`, the scales of a quantized store ``[layers,
        rows, kv_heads, max_len]`` in every order."""
        lead = (self.layers, self.rows)
        by_head = lead + (self.kv_heads, self.max_len)
        pay = lead + (self.max_len, self.kv_heads * self.head_dim) \
            if self.page_order == "token_rows" else by_head + (self.head_dim,)
        out = {"k": pay, "v": pay}
        if self.quantized:
            out["k_scale"] = out["v_scale"] = by_head
        return out

    def page_orders(self) -> Dict[str, str]:
        """:func:`page_order` of every cache tensor by name; a scale has
        no ``head_dim``, its positions are minor."""
        return {name: self.page_order if name in ("k", "v")
                else "positions_minor" for name in self.shapes()}

    def bytes(self) -> int:
        """Device bytes of one cache (payload pages + riding scales)."""
        per = self.layers * self.rows * self.max_len * self.kv_heads
        payload = 2 * per * self.head_dim * \
            jnp.dtype(store_dtype(self.store, self.dtype)).itemsize
        scales = 2 * per * 4 if self.quantized else 0
        return payload + scales

    def bytes_per_token(self) -> int:
        """Device bytes one cached token costs (k + v + scales): what the
        ``bluefog_serve_cache_bytes_per_token`` gauge shows."""
        per_head = self.head_dim * \
            jnp.dtype(store_dtype(self.store, self.dtype)).itemsize
        if self.quantized:
            per_head += 4                       # the riding f32 amax scale
        return 2 * self.layers * self.kv_heads * per_head


def init_cache(cfg: KVCacheConfig) -> dict:
    """Zeroed cache dict: ``{"k", "v"}`` payload pages (plus
    ``{"k_scale", "v_scale"}`` when quantized), each in its stored shape
    (:meth:`KVCacheConfig.shapes`)."""
    dt = store_dtype(cfg.store, cfg.dtype)
    return {name: jnp.zeros(shape, dt if name in ("k", "v") else jnp.float32)
            for name, shape in cfg.shapes().items()}


# ---------------------------------------------------------------------------
# Device-side page math: on one layer's slice of the cache dict, or (given
# ``layer``) on the stacked cache dict at that layer
# ---------------------------------------------------------------------------

def _positions_minor(head_dim: int, max_len: int) -> bool:
    """Whether a TPU stores ``[..., max_len, head_dim]`` with the
    positions, not ``head_dim``, in the 128 lanes: it takes whichever of
    the two minor axes wastes less of them, so positions for a
    ``head_dim`` of 64 under a ``max_len`` of 1024."""
    waste = lambda n: -(-n // 128) * 128 / n
    return waste(head_dim) > waste(max_len)


def page_order(kv_heads: int, head_dim: int, max_len: int) -> str:
    """How a layer's pages of a row lie in a dense cache tensor, from the
    shapes alone; what a decode token's write costs on a TPU is the tiles
    it touches, each read and written back, so the order is the one in
    which a token touches fewest without a lane padded:

    * ``"head_dim_minor"``: ``[kv_heads, max_len, head_dim]`` with a
      ``head_dim`` that fills the 128 lanes.  A token is one row of each
      head's tiles.
    * ``"token_rows"``: ``[max_len, kv_heads * head_dim]``, positions down
      the sublanes and a token's heads and ``head_dim`` side by side in
      the lanes, where ``head_dim`` alone is under 128 but the product
      fills them (16 heads of 64): a token is one ROW, 8 tiles of 1,024
      lanes, and the same bytes as any other order.
    * ``"positions_minor"``: ``[kv_heads, max_len, head_dim]`` held with
      the positions in the lanes (:func:`_positions_minor`), where neither
      holds (one kv head of 64 on a tp rank): a token is one COLUMN, one
      element in every tile of ``head_dim`` sublanes, which is what 16
      heads of 64 cost before they were token rows (1,536 tiles of 4 KB a
      token's window over 24 layers, 60 us of a v5e, 64 windows a call)."""
    if head_dim % 128 == 0:
        return "head_dim_minor"
    if head_dim < 128 and (kv_heads * head_dim) % 128 == 0:
        return "token_rows"
    return "positions_minor" if _positions_minor(head_dim, max_len) \
        else "head_dim_minor"


def _token_rows(t: jax.Array, stacked: bool = True) -> bool:
    """Whether a PAYLOAD tensor of a dense cache (``k``/``v``: stacked
    over the layers, or one layer of it) holds token rows ``[..., rows,
    max_len, kv_heads * head_dim]``, one axis fewer than pages kept by
    head ``[..., rows, kv_heads, max_len, head_dim]``."""
    return t.ndim == 3 + stacked


def logical_pages(t: jax.Array, head_dim: int,
                  stacked: bool = False) -> jax.Array:
    """A payload tensor (or any leading slice of one) in the ONE order
    every reader but the in-place decode read takes: ``[..., kv_heads,
    max_len, head_dim]``.  Pages kept by head are that already; token rows
    are split and turned, a copy where it is not fused away (the staged
    reads, the flash-decode kernel's view: paths whose speed nothing
    measures)."""
    if not _token_rows(t, stacked):
        return t
    return jnp.swapaxes(
        t.reshape(t.shape[:-1] + (t.shape[-1] // head_dim, head_dim)),
        -3, -2)


def _pin(w: jax.Array, major_to_minor: Tuple[int, ...]) -> jax.Array:
    """``w`` held to an axis order in memory, when compiling for a TPU.
    Other backends keep everything row-major and need no pin."""
    lay = Layout(major_to_minor=major_to_minor)
    return lax.platform_dependent(
        w, tpu=lambda w: with_layout_constraint(w, lay),
        default=lambda w: w)


def _pin_window(upd: jax.Array, max_len: int) -> jax.Array:
    """Give a window of a cache tensor (``[layers, 1, kv_heads, T,
    head_dim]`` of pages kept by head, ``[layers, 1, T, kv_heads *
    head_dim]`` of token rows, ``[layers, 1, kv_heads, T]`` of scales),
    one about to be written or one just read, the axis order the tensor
    has in a TPU's memory: its own, but for pages kept by head with the
    positions minor (:func:`_positions_minor`).

    A ``dynamic_update_slice`` wants buffer and update in one order, and
    the compiler, left alone, has moved the 1.7 GB buffer to the order of
    the 2 KB update (``head_dim`` minor, as the projection leaves it): a
    copy of the whole cache at the head of the call and one back at its
    end, in some programs and not in others.  Pinned, it transposes the
    update."""
    order = tuple(range(upd.ndim))
    if upd.ndim == 5 and _positions_minor(upd.shape[4], max_len):
        order = (0, 1, 2, 4, 3)
    return _pin(upd, order)


def _at(t: jax.Array, layer, row, ax: int, pos) -> tuple:
    """The start of a window of ``t`` at ``[layer, row]`` whose position
    axis ``ax`` starts at ``pos``, every other axis at 0."""
    at = [layer, row] + [0] * (t.ndim - 2)
    at[ax] = pos
    return tuple(at)


def _write_lanes(t: jax.Array, layer: jax.Array, slots: jax.Array,
                 pos: jax.Array, upd: jax.Array, ax: int = 3) -> jax.Array:
    """``t[layer, slots[i], :, pos[i] + j] = upd[i, :, j]`` for every lane
    ``i`` in order (last write wins on the shared trash row) and every
    ``j < T``: ``t`` is a stacked cache tensor whose axis ``ax`` holds the
    positions (``[layers, rows, kv_heads, max_len(, head_dim)]``, or token
    rows ``[layers, rows, max_len, kv_heads * head_dim]`` with ``ax`` 2),
    ``upd`` is a lane's window of it per lane, ``T`` positions long
    (``[S, kv_heads, T(, head_dim)]``, ``[S, T, kv_heads * head_dim]``).

    One ``dynamic_update_slice`` per lane, unrolled, NOT one scatter: the
    TPU's scatter wants its indexed axes (layer, row, position) major and
    its window (head, head_dim) minor, and the compiler then keeps the
    whole loop-carried cache in that order, which also pads ``head_dim``
    up to the 128 lanes.  A ``dynamic_update_slice`` takes the buffer in
    the order it has (:func:`_pin_window`), so the cache stays as it is
    from call to call.  Positions at or past ``max_len`` are dropped as
    the scatter dropped them: a token past the end lands in the trash row
    (the last one, which nothing reads), and a chunk that straddles the
    end is clamped into range and keeps what its window held before it.
    """
    S, T, max_len = upd.shape[0], upd.shape[ax - 1], t.shape[ax]
    start = jnp.minimum(pos, max_len - T)
    over = pos - start               # > 0: the window runs past max_len
    rows = jnp.where(over < T, slots, t.shape[1] - 1)
    ats = [_at(t, layer, rows[i], ax, start[i]) for i in range(S)]
    new = upd.astype(t.dtype)
    if T > 1:
        # every window is read before any is written: live lanes hold
        # rows of their own, and what the shared trash row held matters
        # to no one
        along = [1] * upd.ndim
        along[ax - 1] = T
        j = jnp.arange(T).reshape(along)
        over = over.reshape((S,) + (1,) * (upd.ndim - 1))
        old = jnp.concatenate(
            [lax.dynamic_slice(t, at, (1, 1) + new.shape[1:])[0]
             for at in ats])
        new = jnp.where(j >= over,
                        jnp.take_along_axis(new, (j - over) % T, axis=ax - 1),
                        old)
    for i, at in enumerate(ats):
        t = lax.dynamic_update_slice(
            t, _pin_window(new[i][None, None], max_len), at)
    return t


def _write_in_turn(buf: jax.Array, new: jax.Array,
                   starts: Sequence[tuple], pin) -> jax.Array:
    """``buf`` with lane i's window ``new[:, i:i + 1]`` written at
    ``starts[i]``, in order, each ``pin``-ned to ``buf``'s axis order.
    On a TPU a window is laid out only once the write before it is done:
    a single position pads to a tile's worth of them (128 where positions
    are minor, 6 MiB for 49 KB at 24 layers x 16 heads x 64; 16 sublanes
    of a token row, 0.79 MB), and unordered the compiler lays out every
    lane's window first and holds them all.  So the window crosses a
    barrier with the write before it in the order it was computed in, and
    is given the buffer's only behind it.  (The CPU's compiler answers
    the same barrier with a copy of ``buf``.  Token rows need no turn
    and take none, :func:`_write_tokens`: at 24 layers x 32 lanes the
    decode program compiled for a v5e holds 2.9 MB of temporaries with it
    and without, a call's writes take 0.279 ms either way, and its three
    platform switches a lane and tensor were most of what tracing the
    dense decode program cost at set-up.  Positions-minor pages, the rings
    and the latent cache share this path, their programs as they were.)"""
    for i, at in enumerate(starts):
        w = _pin(new[:, i:i + 1], tuple(range(new.ndim)))
        buf, w = lax.platform_dependent(
            buf, w, tpu=lambda b, w: lax.optimization_barrier((b, w)),
            default=lambda b, w: (b, w))
        buf = lax.dynamic_update_slice(buf, pin(w), at)
    return buf


def _write_tokens(t: jax.Array, slots: jax.Array, pos: jax.Array,
                  upd: jax.Array, ax: int = 3) -> jax.Array:
    """``t[:, slots[i], :, pos[i]] = upd[:, i]`` for every lane ``i`` in
    order: :func:`_write_lanes` for one token per lane and ALL layers at
    once.  ``upd`` is ``[layers, S, kv_heads(, head_dim)]``; the window
    of one lane is ``[layers, 1, kv_heads, 1(, head_dim)]``, or of token
    rows (``ax`` 2: the positions' axis of ``t``) ``[layers, 1, 1,
    kv_heads * head_dim]``, pinned like every other.  Last write wins on
    the shared trash row, and a position at or past ``max_len`` goes
    there too.

    What a write costs on a TPU is neither its launch nor its bytes but
    the tiles it touches, each read and written back (39 ns a tile of
    4 KB on a v5e): a token row touches 8 a layer at 16 heads of 64,
    where the same token in a cache with the positions minor had one
    element in every tile of its column, 64 a layer
    (:func:`page_order`)."""
    S, max_len = upd.shape[1], t.shape[ax]
    rows = jnp.where(pos < max_len, slots, t.shape[1] - 1)
    at = jnp.minimum(pos, max_len - 1)
    new = upd.astype(t.dtype)
    new = new.reshape(new.shape[:2] + (1, -1)) if ax == 2 \
        else jnp.expand_dims(new, 3)
    starts = [_at(t, 0, rows[i], ax, at[i]) for i in range(S)]
    pin = lambda w: _pin_window(w, max_len)
    if ax != 2:
        return _write_in_turn(t, new, starts, pin)
    # a token row's window waits for no turn (:func:`_write_in_turn`)
    for i, start in enumerate(starts):
        t = lax.dynamic_update_slice(t, pin(new[:, i:i + 1]), start)
    return t


def _read_lanes(t: jax.Array, layer: jax.Array,
                rows: jax.Array) -> jax.Array:
    """``t[layer, rows[i]]`` for every lane: ``[S, kv_heads, max_len(,
    head_dim)]`` (token rows: ``[S, max_len, kv_heads * head_dim]``) out
    of a stacked cache tensor.  One ``dynamic_slice`` per
    lane, unrolled, NOT one gather: the TPU's gather of rows this long
    first cuts its whole operand — here all layers of the cache — into
    four pieces along ``max_len`` (``mini-gather-slice``), a copy of the
    tensor per layer; the slices are plain reads the compiler fuses.
    Each row is pinned to the cache's own axis order
    (:func:`_pin_window`): in a loop that only reads the cache nothing
    else holds the compiler to it, and it copies both tensors whole into
    the order the attention's matmuls would like."""
    size = (1, 1) + t.shape[2:]
    zeros = (0,) * (t.ndim - 2)
    return jnp.concatenate(
        [_pin_window(lax.dynamic_slice(t, (layer, rows[i]) + zeros, size),
                     t.shape[3])[0]
         for i in range(rows.shape[0])], axis=0)


def token_pages(k_new: jax.Array, v_new: jax.Array, store: str,
                dtype: Any) -> Dict[str, jax.Array]:
    """What a write of ``k_new/v_new`` (``[..., kv_heads, head_dim]``)
    puts into the cache, under the cache dict's own names: the payload in
    the pages' ``dtype``, quantized when the store calls for it, and then
    the ``k_scale``/``v_scale`` beside it."""
    qk, sk = quantize_rows(k_new, store)
    qv, sv = quantize_rows(v_new, store)
    new = {"k": qk.astype(dtype), "v": qv.astype(dtype)}
    if sk is not None:
        new["k_scale"], new["v_scale"] = sk, sv
    return new


def layer_append(cache: Dict[str, jax.Array], layer: jax.Array,
                 slots: jax.Array, lengths: jax.Array, k_new: jax.Array,
                 v_new: jax.Array, store: str = "raw"
                 ) -> Dict[str, jax.Array]:
    """One decode token per lane into ``layer`` of the stacked cache dict
    (``[layers, rows, kv_heads, max_len, head_dim]`` per tensor),
    quantizing on the way in when the store calls for it: ``k_new/v_new``
    are ``[S, kv_heads, head_dim]`` and lane i's token lands at position
    ``lengths[i]`` of row ``slots[i]``.  Duplicate (trash-slot) rows are
    allowed — last write wins, and nothing ever reads the trash row.

    One write per lane, tensor AND layer: the flash-decode kernel reads
    its pages from HBM itself, so its token has to be there before it
    runs.  The XLA attention takes the token beside the pages
    (:func:`attend_layer`, or :func:`attend_rows`'s ``new``) and the
    engine lands all layers' tokens at once after the layer loop
    (:func:`append_tokens`)."""
    return layer_append_chunk(cache, layer, slots, lengths, k_new[:, None],
                              v_new[:, None], store)


def _positions_axis(name: str, t: jax.Array) -> int:
    """The axis of the stacked cache tensor ``t`` called ``name`` that
    holds the positions: 2 of token rows, 3 of everything kept by head
    (pages and scales)."""
    return 2 if name in ("k", "v") and _token_rows(t) else 3


@jax.named_scope("cache.write")
def append_tokens(cache: Dict[str, jax.Array], slots: jax.Array,
                  lengths: jax.Array, new: Dict[str, jax.Array]
                  ) -> Dict[str, jax.Array]:
    """One decode token per lane into EVERY layer of the stacked cache
    dict at once: ``new`` holds, per cache tensor, the
    :func:`token_pages` of all layers stacked (``[layers, S, kv_heads(,
    head_dim)]``, a layer scan's ``ys``), and ``t[:, slots[i], :,
    lengths[i]] = new[:, i]`` (of token rows ``t[:, slots[i],
    lengths[i]]``, a token's heads side by side).  The cache ends up as
    ``layers`` calls of :func:`layer_append` leave it, with one
    ``dynamic_update_slice`` per lane and tensor instead of one per lane,
    tensor and layer (1,536 of 2 KB in a 24-layer decode call of 32
    lanes, 4.19 µs each on a v5e: 6.45 ms of a 16.85 ms program; the 64
    that replaced them took 3.84 while the positions were minor)."""
    return {name: _write_tokens(t, slots, lengths, new[name],
                                _positions_axis(name, t))
            for name, t in cache.items()}


@jax.named_scope("cache.write")
def layer_append_chunk(cache: Dict[str, jax.Array], layer: jax.Array,
                       slots: jax.Array, lengths: jax.Array,
                       k_new: jax.Array, v_new: jax.Array,
                       store: str = "raw") -> Dict[str, jax.Array]:
    """Write a T-token chunk per lane (the k-token verify / chunked
    prefill append) into ``layer`` of the stacked cache dict:
    ``k_new/v_new`` are ``[S, T, kv_heads, head_dim]`` and token t of
    lane i lands at position ``lengths[i] + t`` of row ``slots[i]``."""
    new = token_pages(k_new, v_new, store, cache["k"].dtype)
    S, T = k_new.shape[:2]

    def land(name, t):
        ax = _positions_axis(name, t)
        upd = new[name].reshape(S, T, -1) if ax == 2 \
            else jnp.swapaxes(new[name], 1, 2)
        return _write_lanes(t, layer, slots, lengths, upd, ax)
    return {name: land(name, t) for name, t in cache.items()}


@jax.named_scope("cache.write")
def layer_prefill(cache: Dict[str, jax.Array], layer: jax.Array,
                  slot_id: jax.Array, k: jax.Array, v: jax.Array,
                  store: str = "raw") -> Dict[str, jax.Array]:
    """Land a whole padded prompt's kv (``[Tpad, kv_heads, head_dim]``)
    at positions ``0..Tpad-1`` of row ``slot_id`` of ``layer`` — the
    prefill write, one ``dynamic_update_slice`` per tensor of the stacked
    cache dict.  Positions past the true length hold garbage that the
    length masks never read before an append overwrites them."""
    qk, sk = quantize_rows(k, store)
    qv, sv = quantize_rows(v, store)
    rows = _token_rows(cache["k"])
    max_len = cache["k"].shape[2 if rows else 3]
    out = dict(cache)
    for name, pay in (("k", qk), ("v", qv)):
        # token rows take the block as the projection leaves it; pages
        # kept by head take it turned
        pay = pay.reshape(pay.shape[0], -1) if rows else pay.transpose(1, 0, 2)
        out[name] = lax.dynamic_update_slice(
            cache[name], _pin_window(
                pay[None, None].astype(cache[name].dtype), max_len),
            _at(cache[name], layer, slot_id, 2, 0))
    if sk is not None:
        for name, sc in (("k_scale", sk), ("v_scale", sv)):
            out[name] = lax.dynamic_update_slice(
                cache[name], _pin_window(sc.T[None, None], max_len),
                (layer, slot_id, 0, 0))
    return out


def _gather_pages(cl: Dict[str, jax.Array], name: str, head_dim: int,
                  slots: jax.Array, prefix_slots: Optional[jax.Array],
                  prefix_lens: Optional[jax.Array],
                  layer: Optional[jax.Array] = None,
                  new: Optional[Dict[str, jax.Array]] = None,
                  lengths: Optional[jax.Array] = None) -> jax.Array:
    """Gather each lane's rows of tensor ``name`` (``"k"`` or ``"v"``),
    reading **through the page indirection**: key positions
    ``< prefix_lens[i]`` come from the lane's shared prefix page, the rest
    from its private slot.  With ``layer`` the rows are read straight out
    of the stacked cache at ``[layer, row]`` (the layer is never
    materialized).  With ``new`` (one token's :func:`token_pages` per
    lane, not yet written) position ``lengths[i]`` reads the token,
    dequantized as its page would be.  Returns the f32-dequantized rows
    in the logical order ``[S, Hkv, max_len, Dh]`` whatever order the
    pages are stored in (:func:`logical_pages`)."""
    def rows(tensor, r):
        got = cl[tensor][r] if layer is None else \
            _read_lanes(cl[tensor], layer, r)
        return logical_pages(got, head_dim) if tensor == name else got

    sname = name + "_scale"
    pay = rows(name, slots)
    sc = rows(sname, slots) if sname in cl else None
    L = cl[name].shape[-2]
    if prefix_slots is not None:
        shared = (jnp.arange(L)[None, :]
                  < prefix_lens[:, None])                       # [S, L]
        pay = jnp.where(shared[:, None, :, None],
                        rows(name, prefix_slots), pay)
        if sc is not None:
            sc = jnp.where(shared[:, None, :],
                           rows(sname, prefix_slots), sc)
    out = dequantize_rows(pay, sc, jnp.float32)
    if new is not None:
        here = (jnp.arange(L)[None, :]
                == lengths[:, None])[:, None, :, None]          # [S,1,L,1]
        out = jnp.where(here, dequantize_rows(
            new[name], new.get(sname), jnp.float32)[:, :, None], out)
    return out


def _heads_and_positions(t: jax.Array, head_dim: int,
                         stacked: bool) -> Tuple[int, int]:
    """``(kv_heads, max_len)`` of a payload tensor in either order."""
    if _token_rows(t, stacked):
        return t.shape[-1] // head_dim, t.shape[-2]
    return t.shape[-3], t.shape[-2]


def read_in_place(lanes: int, rows: int) -> bool:
    """Whether a decode step of ``lanes`` lanes meets a layer's ``rows``
    rows where they lie: one pass over all of them costs less than the
    three passes (read, write, read again) over the lanes' own, staged,
    unless the lanes are under a third of the rows."""
    return 3 * lanes >= rows


def read_bounds(max_len: int) -> Tuple[int, ...]:
    """The position counts a dense in-place decode read of pages kept by
    head may stop at (token rows stop by lane: :func:`read_block`), from
    ``max_len`` alone: multiples of a step of ``max_len / 8`` rounded up to
    whole 128s (a positions-minor page's lanes stay whole; at least 128),
    the last of them the whole row; so at most 8, and one (the whole row,
    no choice to make) where the row is no longer than a step."""
    step = -(-max_len // (8 * 128)) * 128
    return tuple(range(step, max_len, step)) + (max_len,)


def live_bound(lengths, live, max_len: int):
    """The least of :func:`read_bounds` that covers the longest LIVE
    lane's cached positions ``0 .. lengths[i] - 1``, whatever stale length
    a lane that holds no request carries (``live`` false: its slot is the
    trash row); the first where no lane is live.  Of the last axis, so a
    batch of calls gives a bound each.  The same integer arithmetic on
    numpy arrays (the host counting what a call reads) and on traced ones
    (the program choosing it)."""
    xp = jnp if isinstance(lengths, jax.Array) else np
    step = read_bounds(max_len)[0]
    longest = xp.max(xp.where(live, lengths, 0), -1)
    return xp.minimum(xp.maximum(-(-longest // step), 1) * step, max_len)


_READ_BLOCK = 128


def read_block(max_len: int) -> int:
    """The positions a lane's read of token rows advances by, from
    ``max_len`` alone: 128 (a tile of a bfloat16 page's sublanes eight
    times over, 256 KB of a row of 1,024 lanes) where a row holds more than
    one such block and only whole ones, else the whole row: no choice to
    make, and no kernel."""
    whole = max_len > _READ_BLOCK and max_len % _READ_BLOCK == 0
    return _READ_BLOCK if whole else max_len


def live_blocks(lengths, live, block: int):
    """The blocks of ``block`` positions each lane's read of token rows
    takes: those that hold its cached positions ``0 .. lengths[i] - 1``,
    none for a lane that holds no request (``live`` false: its slot is the
    trash row, whatever stale length it carries) and none for a length of
    0.  The same integer arithmetic on numpy arrays (the host counting
    what a call reads) and on traced ones (the kernel's trip counts)."""
    xp = jnp if isinstance(lengths, jax.Array) else np
    return xp.where(live, -(-lengths // block), 0)


def dense_positions_met(lengths, live, rows: int, max_len: int,
                        token_rows: bool):
    """The cache positions ONE layer's dense in-place decode read meets,
    by the read's own integer rule, on numpy arrays (the host's count) and
    on traced ones (the program's): of token rows that hold more than one
    block each live lane's own blocks (:func:`live_blocks` x
    :func:`read_block`), else every one of ``rows`` rows up to the bound of
    the longest live lane (:func:`live_bound`).  Summed over the last axis,
    so a batch of calls gives a count each."""
    block = read_block(max_len)
    if token_rows and block < max_len:
        xp = jnp if isinstance(lengths, jax.Array) else np
        held = xp.minimum(lengths, max_len)      # a row holds no more
        return live_blocks(held, live, block).sum(-1) * block
    bound = live_bound(lengths, live, max_len)
    if token_rows:          # a token row of one block is read whole
        bound = 0 * bound + max_len
    return rows * bound


def _layer_pages(t: jax.Array, layer: jax.Array, pin) -> jax.Array:
    """``t[layer]`` of a stacked cache tensor as an operand the compiler
    reads where it lies: the layer's slice ``[1, rows, ...]``, ``pin``-ned
    to the cache's own axis order and fused into the einsum that takes it,
    the way a scanned layer's weights reach their matmul.  Unpinned, in a
    loop that only reads the cache, the TPU's compiler copies the whole
    tensor into the order a matmul would like (at the serving cell's
    sizes two copies, ``head_dim`` padded from 64 to 128: 6.65 GB of
    temporaries)."""
    window = lax.dynamic_slice(t, (layer,) + (0,) * (t.ndim - 1),
                               (1,) + t.shape[1:])
    return pin(window)[0]


@jax.named_scope("cache.read")
def _attend_by_row(qs: Sequence[jax.Array], kts: Sequence[jax.Array],
                   vt: jax.Array, slots: jax.Array, lengths: jax.Array,
                   kns: Sequence[jax.Array], vn: jax.Array,
                   scale: Optional[float], *, ring: bool = False,
                   probs: Any = None, stage: Optional[bool] = None
                   ) -> Tuple[jax.Array, int]:
    """The decode read every family shares: one new token per lane
    attends over one layer's pages IN PLACE.  The lanes' queries are laid
    out by row (lane ``i`` to row ``slots[i]``), every row meets its own
    pages in one grouped einsum, and the lanes' rows of the result are
    read back; the trash row and the rows of no lane compute what nobody
    reads.

    The score is a sum of parts (one for K and V per head, two for the
    latent cache): ``qs[j]`` ``[S, heads, d_j]`` on ``kts[j]`` ``[rows,
    kv_heads, L, d_j]``, q head ``h`` on kv head ``h // group``, times
    ``scale`` (None: folded into the queries); ``vt`` ``[rows, kv_heads,
    L, dv]`` holds the values.  The token itself is not in the pages yet
    (``kns[j]`` ``[S, kv_heads, d_j]``, ``vn``) and is attended beside
    them, no select over whole rows: positions ``0 .. lengths[i] - 1`` of
    the pages are valid, or in a ``ring`` of ``L`` positions (position
    ``p`` at index ``p mod L``) the entries written so far but the one
    the token will replace.  Every operand keeps the dtype it comes in,
    products accumulate in float32, the softmax over pages and token is
    float32, and the probabilities enter the value product as ``probs``
    (None: float32 as they are).  (The dense family's read, which also
    meets token rows and stops at a bound, is :func:`_attend_dense`: the
    same sums.)

    ``stage`` (None: decided from the shapes, :func:`read_in_place`)
    gathers the lanes' rows first and meets them alone.  Returns the
    lanes' result ``[S, heads, dv]`` in float32 and the cache positions
    the einsums met."""
    S, H = qs[0].shape[:2]
    (Hkv, Dv), (R, L) = vn.shape[1:], (vt.shape[0], vt.shape[-2])
    if H % Hkv:
        raise ValueError(f"{H} q heads not a multiple of {Hkv} kv heads")
    if (not read_in_place(S, R)) if stage is None else stage:
        kts, vt = [kt[slots] for kt in kts], vt[slots]
        slots, R = jnp.arange(S), S
    by_row = lambda a: jnp.zeros((R,) + a.shape[1:], a.dtype).at[slots].set(a)
    qs = [by_row(q) for q in qs]
    kns, vn, at = [by_row(kn) for kn in kns], by_row(vn), by_row(lengths)
    j = jnp.arange(L)[None, :]
    valid = j < at[:, None]
    if ring:
        valid = valid & (j != (at % L)[:, None])
    f32 = dict(preferred_element_type=jnp.float32)
    qs = [q.reshape(R, Hkv, H // Hkv, -1) for q in qs]
    total = lambda parts: sum(parts[1:], parts[0])
    s = total([jnp.einsum("rkgd,rkld->rkgl", q, kt, **f32)
               for q, kt in zip(qs, kts)])
    sn = total([jnp.einsum("rkgd,rkd->rkg", q, kn, **f32)
                for q, kn in zip(qs, kns)])
    vn = vn[:, :, None, :]                                  # [R, Hkv, 1, dv]
    weigh = lambda p: jnp.einsum("rkgl,rkld->rkgd", p, vt, **f32)
    if scale is not None:
        s, sn = s * scale, sn * scale
    s = jnp.where(jnp.expand_dims(valid, tuple(range(1, s.ndim - 1))), s,
                  -jnp.inf)
    m = jnp.maximum(jnp.max(s, -1), sn)
    p, pn = jnp.exp(s - m[..., None]), jnp.exp(sn - m)
    out = weigh(p if probs is None else p.astype(probs)) \
        + pn[..., None] * vn.astype(jnp.float32)
    out = out / (jnp.sum(p, -1) + pn)[..., None]
    return out.reshape(R, H, Dv)[slots], R * L


@jax.named_scope("cache.read")
def attend_rows(q: jax.Array, kl: jax.Array, vl: jax.Array,
                slots: jax.Array, lengths: jax.Array,
                scale: Optional[float] = None, *,
                k_scale: Optional[jax.Array] = None,
                v_scale: Optional[jax.Array] = None,
                prefix_slots: Optional[jax.Array] = None,
                prefix_lens: Optional[jax.Array] = None,
                layer: Optional[jax.Array] = None,
                new: Optional[Dict[str, jax.Array]] = None) -> jax.Array:
    """Masked decode attention of one new token per request over its slot.

    ``q``: ``[S, heads, head_dim]`` (heads may be ``group * kv_heads`` —
    grouped-query attention: q head ``h`` attends compact kv head
    ``h // group``, via a reshape-grouped einsum that never materializes
    repeated K/V copies);
    ``kl/vl``: one layer's pages, or with ``layer`` the stacked cache
    read at that layer, in either stored order (by head, or token rows:
    every reader here meets them as :func:`logical_pages`); ``lengths``:
    the new token's position, so keys ``0 .. lengths[i]`` inclusive are
    valid.  The token is either in the pages already (post-append) or
    handed over as ``new``, its
    :func:`token_pages` (``[S, kv_heads(, head_dim)]`` per tensor): the
    attention then sees at ``lengths[i]`` exactly what reading the
    written page would give, and the write can wait
    (:func:`append_tokens`).  ``k_scale/v_scale`` dequantize int8/fp8
    pages on the fly; ``prefix_slots/prefix_lens`` route key positions
    below the prefix length through the lane's shared prefix page.  Same
    numerics as the dense oracle: f32-floor scores, scale folded into q,
    ``-inf`` masking.
    """
    S, H, Dh = q.shape
    Hkv, L = _heads_and_positions(kl, Dh, layer is not None)
    if H % Hkv:
        raise ValueError(f"{H} q heads not a multiple of {Hkv} kv heads")
    if scale is None:
        scale = Dh ** -0.5
    cl = {"k": kl, "v": vl}
    if k_scale is not None:
        cl["k_scale"], cl["v_scale"] = k_scale, v_scale
    pages = (Dh, slots, prefix_slots, prefix_lens, layer, new, lengths)
    ct = jnp.promote_types(q.dtype, jnp.float32)
    qg = (q.astype(ct) * scale).reshape(S, Hkv, H // Hkv, Dh)
    ks = _gather_pages(cl, "k", *pages)
    s = jnp.einsum("skgd,skld->skgl", qg, ks.astype(ct))
    valid = jnp.arange(L)[None, :] <= lengths[:, None]             # [S, L]
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    # V's rows are read only once the probabilities exist, so K's staged
    # rows are dead by then: left to its scheduler the TPU compiler may
    # stage both tensors' rows first (2 x 64 MiB at 32 lanes x 1024), and
    # only one of the two then fits in its on-chip memory
    p, cl = lax.optimization_barrier((p, cl))
    vs = _gather_pages(cl, "v", *pages)
    out = jnp.einsum("skgl,skld->skgd", p, vs.astype(ct))
    return out.reshape(S, H, Dh).astype(q.dtype)


def _attend_dense(q: jax.Array, kl: jax.Array, vl: jax.Array,
                  layer: jax.Array, slots: jax.Array, lengths: jax.Array,
                  kn: jax.Array, vn: jax.Array) -> Tuple[jax.Array, Any]:
    """The dense family's in-place decode read, :func:`_attend_by_row`'s
    sums in three steps so that the middle one can stop where the live
    lanes end: the scaled queries ``q`` ``[S, heads, head_dim]`` of one new
    token a lane over ``layer`` of the stacked tensors ``kl``/``vl``, the
    token (``kn``, ``vn`` ``[S, kv_heads, head_dim]``) attended beside the
    pages.

    *What no bound changes* is done once: the lanes' queries, tokens and
    lengths laid out by row, the token's own score.  *The passes over the
    pages* (scores and mask, the softmax's maximum with the token's score
    in it, the exponentials, the value product and their sum) and *the
    token's share and the division* follow, by the order the pages lie in.

    Pages of token rows (``[layers, rows, L, kv_heads * d]``:
    :func:`page_order`) are contracted AS THEY LIE, the lanes never split
    into heads: a row's queries are spread block-diagonally over them (q
    head ``h`` in the lanes of kv head ``h // group``, zeros elsewhere)
    for the scores, every head's probabilities weigh the whole row of
    values and the head keeps the lanes that are its own.  The zeros add
    an exact 0.0 to a float32 sum: the by-head result in another order of
    summation, at ``kv_heads`` times the multiply-adds, which a matrix
    unit has to spare beside the bytes.  Products are exact whatever the
    dtypes: an explicit precision where an operand is float32, but for
    float32 probabilities on bfloat16 pages, which go in as their three
    bfloat16 pieces stacked into one matmul (the same float32 sum in ONE
    pass over each tile of pages, where the explicit precision makes
    three: 16 query rows a tile leave the matrix unit no pass to spare).
    Where a row holds more than one block (:func:`read_block`) all three
    steps are ONE kernel a layer
    (:func:`~bluefog_tpu.ops.pallas_decode.attend_live_blocks`) that takes
    both stacked tensors whole and the lanes as they come, nothing laid
    out by row, and reads of each lane's row the blocks that hold the
    lane's own positions (:func:`live_blocks`; none for a lane on the
    trash row, so none of a row without a lane): the same arithmetic with
    a lane's sum carried from block to block under a running maximum.  A
    row of one block is read whole by the XLA passes below.

    Pages kept by head take the XLA passes over positions ``[0, bound)``
    of every row of the layer, sliced where the stacked tensor lies as
    :func:`_layer_pages` slices a layer.  Where a row holds more than one
    step (:func:`read_bounds`) the bound is :func:`live_bound` of the
    call's lanes, a lane live unless its slot is the trash row (the last),
    chosen on the device by a ``lax.switch`` over the bounds, the cache
    tensors its operands as they lie; a branch holds the passes and
    nothing else, because every branch is traced, lowered and loaded at
    set-up.  The positions left out are those whose score the mask sets to
    ``-inf``: the same float32 sum without its exact zeros.

    Returns the lanes' result ``[S, heads, head_dim]`` in float32 and the
    cache positions met (:func:`dense_positions_met`: traced where the
    lengths decide them)."""
    rows = _token_rows(kl)
    (S, H, Dh), Hkv = q.shape, kn.shape[1]
    R, L = kl.shape[1], kl.shape[-2]
    if H % Hkv:
        raise ValueError(f"{H} q heads not a multiple of {Hkv} kv heads")
    G = H // Hkv
    live = slots != R - 1
    met = dense_positions_met(lengths, live, R, L, rows)
    block = read_block(L) if rows else L
    if block < L:
        # a bound per lane: the kernel walks each lane's own live blocks
        # of its row, the lanes as they come (nothing is laid out by row)
        Hp = -(-H // 16) * 16          # whole bfloat16 tiles of heads
        h = np.arange(Hp)[None, :, None]
        own = ((np.arange(Hkv * Dh)[None, None, :] // Dh == h // G)
               & (h % G == np.arange(G)[:, None, None]) & (h < H))
        token_row = lambda a: a.reshape(S, 1, -1).astype(jnp.float32)
        held = jnp.minimum(lengths, L)           # a row holds no more
        out = pallas_decode.attend_live_blocks(
            jnp.swapaxes(q.reshape(S, Hkv, G, Dh), 1, 2).reshape(S, G, -1),
            token_row(kn), token_row(vn), own.astype(np.float32), kl, vl,
            layer, slots, live_blocks(held, live, block), held, block=block)
        out = jnp.swapaxes(out.reshape(S, G, Hkv, Dh), 1, 2)
        return out.reshape(S, H, Dh), met
    by_row = lambda a: jnp.zeros((R,) + a.shape[1:], a.dtype).at[slots].set(a)
    q, kn, vn, at = by_row(q), by_row(kn), by_row(vn), by_row(lengths)
    valid = jnp.arange(L)[None, :] < at[:, None]                  # [R, L]
    f32 = dict(preferred_element_type=jnp.float32)
    exact = dict(f32, precision=lax.Precision.HIGHEST)
    if rows:
        # which lanes are a head's own: a constant of the program (numpy),
        # not iotas compared again in every layer
        own = (np.arange(Hkv * Dh)[None, :] // Dh
               == np.arange(H)[:, None] // G)               # [H, lanes]
        q = jnp.where(own, jnp.tile(q, (1, 1, Hkv)), 0)
        sn = jnp.einsum("rhc,rc->rh", q, kn.reshape(R, -1), **exact)
        vn = jnp.repeat(vn, G, axis=1)                      # [R, H, d]
    else:
        q = q.reshape(R, Hkv, G, Dh)
        sn = jnp.einsum("rkgd,rkd->rkg", q, kn, **f32)
        vn = vn[:, :, None, :]                              # [R, Hkv, 1, d]
    # the stacked tensors pinned to their own axis order once, not each
    # branch's slice of them (:func:`_layer_pages`): the compiled program
    # is the same, and a pin is two traces a platform in every branch
    kl, vl = _pin_window(kl, L), _pin_window(vl, L)

    def weigh(p, vt):
        if not rows:
            return jnp.einsum("rkgl,rkld->rkgd", p, vt, **f32)
        # float32 probabilities on the pages in their own dtype, no pass of
        # either rounded.  On bfloat16 pages the float32 probabilities go
        # in as their three bfloat16 pieces (hi + mid + lo is the float32
        # exactly), stacked as 3 H rows of ONE matmul and summed in
        # float32: what an explicit precision computes in three passes
        # over the pages' tiles (2.50 ms a pass over V at the serving
        # cell's sizes on a v5e where the bytes take 2.34), in one (2.38)
        pages = (((2,), (1,)), ((0,), (0,)))
        if vt.dtype != jnp.bfloat16:
            return lax.dot_general(p, vt, pages, **exact)
        hi = p.astype(vt.dtype)
        rest = p - hi.astype(p.dtype)
        mid = rest.astype(vt.dtype)
        lo = (rest - mid.astype(p.dtype)).astype(vt.dtype)
        wide = lax.dot_general(jnp.concatenate([hi, mid, lo], 1), vt, pages,
                               **f32)
        return wide[:, :H] + wide[:, H:2 * H] + wide[:, 2 * H:]

    def passes(bound):
        kt, vt = (lax.dynamic_slice(
            t, (layer,) + (0,) * (t.ndim - 1),
            (1,) + t.shape[1:-2] + (bound, t.shape[-1]))[0] for t in (kl, vl))
        s = lax.dot_general(q, kt, (((2,), (2,)), ((0,), (0,))), **exact) \
            if rows else jnp.einsum("rkgd,rkld->rkgl", q, kt, **f32)
        s = jnp.where(jnp.expand_dims(valid[:, :bound],
                                      tuple(range(1, s.ndim - 1))),
                      s, -jnp.inf)
        m = jnp.maximum(jnp.max(s, -1), sn)
        p = jnp.exp(s - m[..., None])
        return m, weigh(p, vt), jnp.sum(p, -1)
    bounds = read_bounds(L)
    if rows or len(bounds) == 1:
        m, out, total = passes(L)
    else:
        bound = met // R        # pages kept by head: rows x the bound
        m, out, total = lax.switch((bound - 1) // bounds[0],
                                   [lambda b=b: passes(b) for b in bounds])
    if rows:        # a head keeps the lanes that are its own
        out = jnp.sum(jnp.where(own, out, 0.0).reshape(R, H, Hkv, Dh), 2)
    pn = jnp.exp(sn - m)
    out = (out + pn[..., None] * vn.astype(jnp.float32)) \
        / (total + pn)[..., None]
    return out.reshape(R, H, Dh)[slots], met


@jax.named_scope("cache.read")
def attend_layer(q: jax.Array, kl: jax.Array, vl: jax.Array,
                 layer: jax.Array, slots: jax.Array, lengths: jax.Array,
                 new: Dict[str, jax.Array], scale: Optional[float] = None
                 ) -> Tuple[jax.Array, Any]:
    """:func:`attend_rows` of raw pages without prefix rows, read IN
    PLACE: one new token per lane (``q`` ``[S, heads, head_dim]``, its
    ``new`` :func:`token_pages` not yet written) over ``layer`` of the
    stacked cache tensors ``kl``/``vl`` (``[layers, rows, kv_heads,
    max_len, head_dim]``, or token rows ``[layers, rows, max_len, kv_heads
    * head_dim]``); ``layer`` may be a scanned index.  The same
    arithmetic as the staged form (the scale folded into the queries,
    pages in their own dtype, exact products, float32 softmax and float32
    probabilities into the value product) in another order of summation,
    over the blocks that hold each lane's own positions where the pages
    are token rows of more than one block (a kernel, from the shapes
    alone), else over the positions the longest live lane reaches and no
    further (:func:`_attend_dense`).  The queries are float32, but over token rows
    they keep their own dtype where the scale is a power of two (0.125 for
    a ``head_dim`` of 64): the product with it is exact there, and a
    matrix unit then takes queries and pages in one dtype.  A bucket under
    a third of the rows (:func:`read_in_place`) takes the staged form
    itself.  Returns the lanes' result and the cache positions met."""
    rows = _token_rows(kl)
    S, R, L = q.shape[0], kl.shape[1], kl.shape[-2]
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if not read_in_place(S, R):
        return attend_rows(q, kl, vl, slots, lengths, scale, layer=layer,
                           new=new), S * L
    ct = jnp.promote_types(q.dtype, jnp.float32)
    if rows and math.frexp(scale)[0] == 0.5:
        ct = q.dtype
    out, met = _attend_dense(q.astype(ct) * scale, kl, vl, layer, slots,
                             lengths, new["k"], new["v"])
    return out.astype(q.dtype), met


@jax.named_scope("cache.read")
def attend_chunk(q: jax.Array, cl: Dict[str, jax.Array], slots: jax.Array,
                 lengths: jax.Array, scale: Optional[float] = None, *,
                 prefix_slots: Optional[jax.Array] = None,
                 prefix_lens: Optional[jax.Array] = None,
                 layer: Optional[jax.Array] = None) -> jax.Array:
    """Chunked causal attention for the k-token verify forward (and the
    chunked prefill of a prefix-hit request): ``q`` is ``[S, T, heads,
    head_dim]`` with query t of lane i sitting at position ``lengths[i] +
    t``, attending over its slot's rows ``0 .. lengths[i] + t`` inclusive
    (post :func:`layer_append_chunk`) — prefix pages, quantized storage
    and the stacked cache at ``layer`` read exactly as in
    :func:`attend_rows`."""
    S, T, H, Dh = q.shape
    Hkv, L = _heads_and_positions(cl["k"], Dh, layer is not None)
    if H % Hkv:
        raise ValueError(f"{H} q heads not a multiple of {Hkv} kv heads")
    if scale is None:
        scale = Dh ** -0.5
    ks, vs = (_gather_pages(cl, name, Dh, slots, prefix_slots, prefix_lens,
                            layer) for name in ("k", "v"))
    ct = jnp.promote_types(q.dtype, jnp.float32)
    qg = (q.astype(ct) * scale).reshape(S, T, Hkv, H // Hkv, Dh)
    s = jnp.einsum("stkgd,skld->stkgl", qg, ks.astype(ct))
    qpos = lengths[:, None] + jnp.arange(T)[None, :]            # [S, T]
    valid = jnp.arange(L)[None, None, :] <= qpos[:, :, None]    # [S, T, L]
    s = jnp.where(valid[:, :, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("stkgl,skld->stkgd", p, vs.astype(ct))
    return out.reshape(S, T, H, Dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# The latent cache: one compressed vector per token and layer
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LatentCacheConfig:
    """Shapes of the latent cache ``{"ckv": [layers, slots + 1, max_len,
    kv_rank], "kr": [layers, slots + 1, max_len, rope_dim]}``: per token
    and layer ONE compressed vector and ONE rotary key
    (:func:`..models.decoder.mla_project`) shared by every head, where
    :class:`KVCacheConfig` keeps K and V per head.  Two tensors, because
    a TPU lays ``[max_len, kv_rank + rope_dim]`` out with the positions in
    its lanes when the sum is no multiple of 128 and then copies the whole
    cache into the order the attention reads; apart, ``ckv`` keeps a
    token's values together (a token's write touches a few tiles: PR 29
    measured that a write costs the tiles it touches) and ``kr`` takes the
    order :func:`_positions_minor` says.  ``layers`` counts attention
    SUBLAYERS: a shortcut-connected double layer
    (:func:`..models.decoder.latent_double_block`) has two, each with a
    cached vector a token of its own, and :func:`latent_append_tokens`
    lands them all, ``LatentConfig.attn_layers`` a lane a step.  The last
    row is the trash slot; there are no prefix pages and no quantized
    store."""
    layers: int
    slots: int
    max_len: int
    kv_rank: int
    rope_dim: int
    dtype: Any = jnp.float32
    prefix_slots = 0                # what the engine's host code asks for

    @property
    def rows(self) -> int:
        return self.slots + 1

    @property
    def trash_slot(self) -> int:
        return self.slots

    def shapes(self) -> Dict[str, Tuple[int, ...]]:
        lead = (self.layers, self.rows, self.max_len)
        return {"ckv": lead + (self.kv_rank,), "kr": lead + (self.rope_dim,)}

    def page_orders(self) -> Dict[str, str]:
        """How each tensor's ``[max_len, dim]`` pages lie, in
        :func:`page_order`'s words (one vector a token: there are no
        heads to put side by side)."""
        return {name: page_order(1, shape[-1], self.max_len)
                for name, shape in self.shapes().items()}

    def bytes(self) -> int:
        return self.rows * self.max_len * self.bytes_per_token()

    def bytes_per_token(self) -> int:
        """Device bytes one cached token costs over all layers."""
        return (self.layers * (self.kv_rank + self.rope_dim)
                * jnp.dtype(self.dtype).itemsize)


def _pin_latent(w: jax.Array, max_len: int) -> jax.Array:
    """A window ``[layers, 1, T, dim]`` of a latent cache tensor in the
    axis order the tensor has in a TPU's memory (:func:`_pin_window`'s
    reason)."""
    order = (0, 1, 3, 2) if _positions_minor(w.shape[3], max_len) \
        else (0, 1, 2, 3)
    return _pin(w, order)


def _split_latent(cache: Dict[str, jax.Array], latent: jax.Array):
    """``latent [..., kv_rank + rope_dim]`` as the cache's two parts."""
    C = cache["ckv"].shape[-1]
    return {"ckv": latent[..., :C], "kr": latent[..., C:]}


@jax.named_scope("cache.write")
def latent_prefill(cache: Dict[str, jax.Array], layer: jax.Array,
                   slot_id: jax.Array, latent: jax.Array
                   ) -> Dict[str, jax.Array]:
    """Land a padded prompt's vectors ``[Tpad, kv_rank + rope_dim]`` at
    positions ``0..Tpad-1`` of row ``slot_id`` of ``layer``: one
    ``dynamic_update_slice`` per tensor."""
    max_len = cache["ckv"].shape[2]
    return {name: lax.dynamic_update_slice(
        cache[name], _pin_latent(part[None, None].astype(cache[name].dtype),
                                 max_len), (layer, slot_id, 0, 0))
        for name, part in _split_latent(cache, latent).items()}


@jax.named_scope("cache.write")
def latent_append_tokens(cache: Dict[str, jax.Array], slots: jax.Array,
                         lengths: jax.Array, new: jax.Array
                         ) -> Dict[str, jax.Array]:
    """One decode token per lane into EVERY layer at once, after the
    layer loop: ``new`` is ``[layers, S, kv_rank + rope_dim]`` (the
    layers' stacked vectors) and ``t[:, slots[i], lengths[i]] = new[:,
    i]``, one ``dynamic_update_slice`` per lane and tensor, each after the
    one before (:func:`_write_in_turn`).  A position at or past
    ``max_len`` goes to the trash row."""
    S, max_len = new.shape[1], cache["ckv"].shape[2]
    rows = jnp.where(lengths < max_len, slots, cache["ckv"].shape[1] - 1)
    at = jnp.minimum(lengths, max_len - 1)
    starts = [(0, rows[i], at[i], 0) for i in range(S)]
    return {name: _write_in_turn(
        cache[name], part.astype(cache[name].dtype)[:, :, None], starts,
        lambda w: _pin_latent(w, max_len))
        for name, part in _split_latent(cache, new).items()}


@jax.named_scope("cache.read")
def latent_attend_slots(q_abs: jax.Array, q_rope: jax.Array,
                        cache: Dict[str, jax.Array], layer: jax.Array,
                        slots: jax.Array, lengths: jax.Array,
                        new: jax.Array, scale: float, *,
                        stage: Optional[bool] = None
                        ) -> Tuple[jax.Array, int]:
    """Absorbed decode attention of one new token per lane over its row
    of the latent cache: ``q_abs`` ``[S, H, kv_rank]`` is the query moved
    into the compressed space, ``q_rope`` ``[S, H, rope]`` its rotary
    part, ``new`` ``[S, kv_rank + rope]`` the token's own vector (not yet
    written, attended beside the pages).  ``score = (q_abs . ckv + q_rope
    . k_rope) * scale`` in float32 over positions ``0 .. lengths[i] - 1``
    and the token; returns the attended compressed vectors ``[S, H,
    kv_rank]`` and the cache positions met.  The layer's pages (``layer``
    of the stacked cache, a scanned index or a static one) are read IN
    PLACE, every head on the one vector a position has
    (:func:`_attend_by_row` with one shared kv head): the compressed
    vectors come from HBM twice a layer, for the scores and for the
    weighted sum, where staging each lane's row (``[128, 2560, 512]``:
    336 MB a layer at 128 lanes of 2,560) wrote them once more and read
    them twice."""
    L = cache["ckv"].shape[2]
    dt = cache["ckv"].dtype
    pages = {name: _layer_pages(t, layer, lambda w: _pin_latent(w, L))
             [:, None] for name, t in cache.items()}        # [R, 1, L, dim]
    tok = {name: part.astype(dt)[:, None]                   # [S, 1, dim]
           for name, part in _split_latent(cache, new).items()}
    out, met = _attend_by_row(
        (q_abs.astype(dt), q_rope.astype(dt)), (pages["ckv"], pages["kr"]),
        pages["ckv"], slots, lengths, (tok["ckv"], tok["kr"]), tok["ckv"],
        scale, probs=dt, stage=stage)
    return out.astype(q_abs.dtype), met


# ---------------------------------------------------------------------------
# The two-kind cache: full layers keep every position, window layers a ring
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class HybridCacheConfig:
    """Shapes of a cache with two kinds of layer, K and V per compact kv
    head as in :class:`KVCacheConfig`::

        k,  v:  [full_layers,   slots + 1, kv_heads, max_len, head_dim]
        kw, vw: [window_layers, slots + 1, kv_heads, window,  head_dim]

    A full layer keeps every position of a slot.  A window layer keeps a
    **ring** of the last ``window`` positions, position ``p`` at index
    ``p mod window``: what a query at ``t`` may see there (``t - window +
    1 .. t``) is exactly what the ring holds once the token at ``t`` has
    replaced the one at ``t - window``.  A slot owns its row of both
    kinds; the last row is the trash slot.  No prefix pages (a ring
    cannot lend rows to a shared prefix: it holds a prompt's END) and no
    quantized store."""
    full_layers: int
    window_layers: int
    slots: int
    max_len: int
    window: int
    kv_heads: int
    head_dim: int
    dtype: Any = jnp.float32
    prefix_slots = 0                # what the engine's host code asks for

    @property
    def rows(self) -> int:
        return self.slots + 1

    @property
    def trash_slot(self) -> int:
        return self.slots

    def shapes(self) -> Dict[str, Tuple[int, ...]]:
        full = (self.full_layers, self.rows, self.kv_heads, self.max_len,
                self.head_dim)
        ring = (self.window_layers, self.rows, self.kv_heads, self.window,
                self.head_dim)
        return {"k": full, "v": full, "kw": ring, "vw": ring}

    def page_orders(self) -> Dict[str, str]:
        """How each tensor's pages lie; both kinds are kept by head, so
        never as token rows (:func:`page_order` of one head)."""
        return {name: page_order(1, self.head_dim, shape[3])
                for name, shape in self.shapes().items()}

    def _position_bytes(self) -> int:
        """K and V of one position in one layer."""
        return (2 * self.kv_heads * self.head_dim
                * jnp.dtype(self.dtype).itemsize)

    def bytes_per_token(self) -> int:
        """Device bytes one more cached token costs: the full layers'
        alone, a ring's size does not grow with the sequence."""
        return self.full_layers * self._position_bytes()

    def bytes_per_slot(self) -> Dict[str, int]:
        """Device bytes a slot owns, by kind of layer."""
        return {"full": self.full_layers * self.max_len
                * self._position_bytes(),
                "window": self.window_layers * self.window
                * self._position_bytes()}

    def bytes(self) -> int:
        return self.rows * sum(self.bytes_per_slot().values())


# the K and V tensor of each kind of layer
KIND_TENSORS = {"full": ("k", "v"), "window": ("kw", "vw")}


@jax.named_scope("cache.write")
def hybrid_prefill(cache: Dict[str, jax.Array], kind: str, layer: int,
                   slot_id: jax.Array, k: jax.Array, v: jax.Array,
                   true_len: jax.Array) -> Dict[str, jax.Array]:
    """Land a padded prompt's kv (``[Tpad, kv_heads, head_dim]``) in row
    ``slot_id`` of the ``layer``-th layer of its ``kind``.  A full layer
    takes positions ``0..Tpad-1`` as :func:`layer_prefill` writes them.  A
    window layer takes the prompt's LAST ``window`` real positions,
    position ``p`` at ring index ``p mod window`` (of a prompt shorter
    than the window every position, at its own index; what lies behind
    ``true_len`` is garbage the length mask never reads)."""
    kn, vn = KIND_TENSORS[kind]
    out = dict(cache)
    if kind == "window":
        W = cache[kn].shape[3]
        first = jnp.maximum(true_len - W, 0)
        at = first + (jnp.arange(W) - first) % W     # the p = j (mod W) kept
        k, v = k[at], v[at]
    max_len = cache[kn].shape[3]
    for name, pay in ((kn, k), (vn, v)):
        out[name] = lax.dynamic_update_slice(
            cache[name], _pin_window(
                pay.transpose(1, 0, 2)[None, None].astype(cache[name].dtype),
                max_len), (layer, slot_id, 0, 0, 0))
    return out


@jax.named_scope("cache.write")
def hybrid_append_tokens(cache: Dict[str, jax.Array], slots: jax.Array,
                         lengths: jax.Array, new: Dict[str, jax.Array]
                         ) -> Dict[str, jax.Array]:
    """One decode token per lane into EVERY layer of both kinds at once,
    after the layer loop (:func:`append_tokens`): ``new`` holds per cache
    tensor its layers' pages stacked ``[layers, S, kv_heads, head_dim]``;
    a full layer's land at ``lengths[i]``, a ring's at ``lengths[i] mod
    window``.  One ``dynamic_update_slice`` per lane and tensor."""
    W = cache["kw"].shape[3]
    ring = KIND_TENSORS["window"]
    return {name: _write_tokens(
        t, slots, lengths % W if name in ring else lengths, new[name])
        for name, t in cache.items()}


def attend_slots(q: jax.Array, kt: jax.Array, vt: jax.Array,
                 slots: jax.Array, lengths: jax.Array,
                 new: Dict[str, jax.Array], *,
                 ring: bool = False) -> Tuple[jax.Array, int]:
    """Decode attention of one new token per lane over one layer's pages
    ``kt``/``vt`` ``[rows, kv_heads, L, head_dim]`` (a static slice of the
    cache), q head ``h`` on compact kv head ``h // group`` in one
    grouped-head einsum.  Returns the lanes' result and the cache
    POSITIONS the einsum met (rows x ``L``: what the program reads of this
    layer, whatever the lanes' lengths).

    The pages are read IN PLACE (:func:`_attend_by_row`).  Staging each
    lane's row first (:func:`attend_rows`) reads it, writes it and reads
    it again: 0.86 GB a tensor and layer, 4.04 GB of temporaries a program
    against 0.11, at 48 lanes of 8,704 positions of 8 heads of 128.  So
    only where the lanes are under a third of the rows, where three passes
    over theirs cost less than one over all, are the lanes' rows staged
    and met alone.

    The token itself is not in the pages yet (``new``: its
    :func:`token_pages`, ``[S, kv_heads, head_dim]``) and is attended
    beside them; with ``ring`` the pages are a ring of ``L`` positions,
    whose order does not matter to a softmax: the keys were turned before
    they were stored.  The matmuls take the pages in their own dtype and
    accumulate in float32; softmax in float32."""
    out, met = _attend_by_row(
        (q.astype(kt.dtype),), (kt,), vt, slots, lengths, (new["k"],),
        new["v"], q.shape[-1] ** -0.5, ring=ring, probs=vt.dtype)
    return out.astype(q.dtype), met


# ---------------------------------------------------------------------------
# The state cache: rows of positions for the attention layers, beside a
# fixed-size recurrent state and the convolution's kept inputs per slot
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SsmCacheConfig:
    """Shapes of the cache of a model with recurrent layers (state-space
    or delta-rule: ``decoder.RECURRENT_KINDS``) beside attention layers::

        k, v: [full_layers, slots + 1, kv_heads, max_len, head_dim]
        ssm:  [ssm_layers,  slots + 1, ssm_heads, ssm_head_dim, ssm_state]
        conv: [ssm_layers,  slots + 1, conv_taps, conv_dim]

    ``k``/``v`` are :class:`HybridCacheConfig`'s full layers.  ``ssm`` is
    each slot's recurrent state, float32 whatever the served dtype (a
    rounding of it is fed back every step); ``conv`` the ``conv_taps`` raw
    inputs of the layer's convolution before the next token, in ``dtype``.
    Neither has a length.  The last row is the trash slot; no prefix pages
    and no quantized store.  The sizes are the recurrent mixer's own
    (:meth:`of`): a Mamba mixer's heads, channels and ``B``/``C`` width, a
    delta mixer's heads, key channels and value channels."""
    full_layers: int
    ssm_layers: int
    slots: int
    max_len: int
    kv_heads: int
    head_dim: int
    ssm_heads: int
    ssm_head_dim: int
    ssm_state: int
    conv_taps: int
    conv_dim: int
    dtype: Any = jnp.float32
    prefix_slots = 0                # what the engine's host code asks for

    @classmethod
    def of(cls, cfg: Any, slots: int, max_len: int,
           dtype: Any) -> "SsmCacheConfig":
        """The cache of the single-mixer model ``cfg``
        (:class:`~bluefog_tpu.models.decoder.SsmConfig`): a state and kept
        inputs for every layer of its plan's recurrent kind."""
        return cls(
            full_layers=cfg.layers_of("full"),
            ssm_layers=cfg.layers_of(cfg.recurrent),
            slots=slots, max_len=max_len, kv_heads=cfg.kv_heads,
            head_dim=cfg.head_dim, ssm_heads=cfg.ssm_heads,
            ssm_head_dim=cfg.ssm_head_dim, ssm_state=cfg.ssm_state,
            conv_taps=cfg.conv_kernel - 1, conv_dim=cfg.conv_dim,
            dtype=dtype)

    @property
    def rows(self) -> int:
        return self.slots + 1

    @property
    def trash_slot(self) -> int:
        return self.slots

    def shapes(self) -> Dict[str, Tuple[int, ...]]:
        full = (self.full_layers, self.rows, self.kv_heads, self.max_len,
                self.head_dim)
        return {"k": full, "v": full,
                "ssm": (self.ssm_layers, self.rows, self.ssm_heads,
                        self.ssm_head_dim, self.ssm_state),
                "conv": (self.ssm_layers, self.rows, self.conv_taps,
                         self.conv_dim)}

    def dtypes(self) -> Dict[str, Any]:
        return {"k": self.dtype, "v": self.dtype, "ssm": jnp.float32,
                "conv": self.dtype}

    def page_orders(self) -> Dict[str, str]:
        """How each tensor's pages lie: the attention layers' by head; a
        state has no positions (``"state"``)."""
        return {"k": page_order(1, self.head_dim, self.max_len),
                "v": page_order(1, self.head_dim, self.max_len),
                "ssm": "state", "conv": "state"}

    def bytes_per_token(self) -> int:
        """Device bytes one more cached token costs: the attention
        layers' alone, a state does not grow with the sequence."""
        return (self.full_layers * 2 * self.kv_heads * self.head_dim
                * jnp.dtype(self.dtype).itemsize)

    def bytes_per_slot(self) -> Dict[str, int]:
        """Device bytes a slot owns, by kind of layer."""
        return {"full": self.max_len * self.bytes_per_token(),
                "ssm": self.ssm_layers * (
                    self.ssm_heads * self.ssm_head_dim * self.ssm_state * 4
                    + self.conv_taps * self.conv_dim
                    * jnp.dtype(self.dtype).itemsize)}

    def bytes(self) -> int:
        return self.rows * sum(self.bytes_per_slot().values())


def ssm_prefill(cache: Dict[str, jax.Array], layer: int, slot_id: jax.Array,
                state: jax.Array, conv: jax.Array) -> Dict[str, jax.Array]:
    """Overwrite row ``slot_id`` of the ``layer``-th state-space layer
    whole with a prompt's ``state`` ``[heads, head_dim, state]`` and kept
    convolution inputs ``conv`` ``[taps, conv_dim]``: whatever the slot's
    last request left there is gone."""
    out = dict(cache)
    with jax.named_scope("ssm.scan"):
        out["ssm"] = lax.dynamic_update_slice(
            cache["ssm"], state[None, None].astype(cache["ssm"].dtype),
            (layer, slot_id, 0, 0, 0))
    with jax.named_scope("ssm.conv"):
        out["conv"] = lax.dynamic_update_slice(
            cache["conv"], conv[None, None].astype(cache["conv"].dtype),
            (layer, slot_id, 0, 0))
    return out


def _by_row(rows: int, slots: jax.Array, value: jax.Array,
            fill: float) -> jax.Array:
    """The lanes' ``value`` ``[S, ...]`` laid out by row ``[rows, ...]``,
    ``fill`` where no lane names the row (dead lanes meet in the trash
    row; which of them lands there does not matter)."""
    return jnp.full((rows,) + value.shape[1:], fill,
                    value.dtype).at[slots].set(value)


@jax.named_scope("ssm.conv")
def ssm_conv_step(cache: Dict[str, jax.Array], layer: int, slots: jax.Array,
                  xbc: jax.Array, step
                  ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One decode token per lane through the ``layer``-th state-space
    layer's convolution, its kept inputs updated where they lie: the
    lanes' raw inputs ``xbc`` ``[S, conv_dim]`` are laid out by row,
    ``step(xbc, prev [rows, taps, conv_dim]) -> (out, kept)`` runs over
    every row, the rows a lane names take their new kept inputs (the rest
    keep theirs) and the lanes' rows of ``out`` are read back.  (A gather
    of the lanes' kept inputs and a scatter behind it took 0.5 ms a layer
    at 160 lanes of 61 KB, twenty times what the bytes cost: PERF.md
    section 6, PR 43.)"""
    t = cache["conv"]
    rows = t.shape[1]
    named = _by_row(rows, slots, jnp.ones(slots.shape, bool), False)
    out, kept = step(_by_row(rows, slots, xbc, 0.0), t[layer])
    kept = jnp.where(named[:, None, None], kept.astype(t.dtype), t[layer])
    return out[slots], {**cache, "conv": lax.dynamic_update_slice(
        t, kept[None], (layer, 0, 0, 0))}


@jax.named_scope("ssm.scan")
def ssm_state_step(cache: Dict[str, jax.Array], layer: int, slots: jax.Array,
                   step, *inputs: jax.Array
                   ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One decode token per lane through the ``layer``-th recurrent
    layer's recurrence, the states updated WHERE THEY LIE: the lanes'
    ``inputs`` (``[S, ...]`` each: a Mamba mixer's ``log_a``, ``dx``, ``B``
    and ``C``, a delta mixer's ``g``, ``beta``, ``q``, ``k`` and ``v``) are
    laid out by row, every row's state takes ``step(states, *inputs) ->
    (y, states)`` in one pass over the layer's slice of the donated tensor
    (a row no lane names gets zeros, a step under which either recurrence
    passes its state unchanged), and the
    lanes' rows of ``y`` are read back.  A gather of the lanes' states
    and a scatter behind it would move each state four times where this
    moves it twice; like the in-place attention read it is the form for a
    bucket that covers most of the rows (:func:`read_in_place`)."""
    t = cache["ssm"]
    at = (0,) * (t.ndim - 5) + (layer,)     # a leading axis of one or none
    rows = t.shape[-4]
    y, new = step(t[at], *(_by_row(rows, slots, a, 0.0) for a in inputs))
    return y[slots], {**cache, "ssm": lax.dynamic_update_slice(
        t, new.reshape((1,) * len(at) + new.shape).astype(t.dtype),
        at + (0,) * 4)}


@jax.named_scope("cache.write")
def ssm_append_tokens(cache: Dict[str, jax.Array], slots: jax.Array,
                      lengths: jax.Array, new: Dict[str, jax.Array]
                      ) -> Dict[str, jax.Array]:
    """One decode token per lane into every attention layer at once, after
    the layer loop (:func:`hybrid_append_tokens` for the full layers
    alone): ``new["k"]``/``new["v"]`` ``[full_layers, S, kv_heads,
    head_dim]`` land at ``lengths[i]``.  The states were updated in the
    loop."""
    return {**cache, **{name: _write_tokens(cache[name], slots, lengths,
                                            new[name]) for name in ("k", "v")}}


# ---------------------------------------------------------------------------
# Host-side bookkeeping
# ---------------------------------------------------------------------------

class SlotAllocator:
    """Host-side free heap over one replica's request slots.

    Continuous batching allocates a slot at admission and frees it at
    retirement (or eviction); the device-side cache rows are never zeroed —
    a recycled slot is overwritten by the next prefill and masked by its
    new length, which the slot-reuse test pins as bit-equivalent to a
    fresh cache.  The free list is a binary heap so both :meth:`alloc`
    and :meth:`free` stay O(log slots) as slot counts grow with paged
    sharing (the old list kept itself sorted with an O(n log n) sort per
    free), while preserving the lowest-free-slot-first order the reuse
    tests pin.
    """

    def __init__(self, slots: int, *, replica: int = 0):
        if slots < 1:
            raise ValueError(f"need >= 1 slot, got {slots}")
        self.slots = int(slots)
        self.replica = int(replica)
        self._free = list(range(self.slots))     # already a valid min-heap
        self._in_use: set = set()

    def alloc(self) -> Optional[int]:
        """Lowest free slot id, or None when the replica is full."""
        if not self._free:
            return None
        slot = heapq.heappop(self._free)
        self._in_use.add(slot)
        self._export()
        return slot

    def free(self, slot: int) -> None:
        if slot not in self._in_use:
            raise ValueError(f"slot {slot} is not allocated")
        self._in_use.discard(slot)
        heapq.heappush(self._free, slot)
        self._export()

    @property
    def in_use(self) -> int:
        return len(self._in_use)

    @property
    def occupancy(self) -> float:
        return len(self._in_use) / self.slots

    def _export(self) -> None:
        _metrics.gauge(
            "bluefog_serve_kv_slots_in_use",
            "allocated KV-cache slots, by replica").set(
                float(self.in_use), replica=str(self.replica))
        _metrics.gauge(
            "bluefog_serve_kv_occupancy",
            "KV-cache slot occupancy fraction, by replica").set(
                self.occupancy, replica=str(self.replica))


@dataclasses.dataclass
class _Prefix:
    row: int               # physical cache row holding the sealed pages
    tokens: Tuple[int, ...]
    digest: str            # content hash (flight bundles / debugging)
    refs: int = 0
    sealed: bool = False
    tick: int = 0          # LRU clock


class PrefixCache:
    """Host-side content-addressed directory of shared prefix pages.

    One replica's reserved prefix rows (physical rows ``slots ..
    slots + pages - 1``) each hold ONE sealed prefix: a prompt prefix
    whose length is a multiple of ``page_tokens``, hashed by content.
    System-prompt-heavy traffic prefills the shared prefix once
    (:meth:`admit` hands out the row, the engine seals it with a plain
    prefill) and every later request with the same prefix attaches by
    reference (:meth:`acquire` / :meth:`release` refcount the row);
    the divergent suffix lands in the request's private slot, so the
    shared pages are immutable after sealing — copy-on-write where the
    "copy" is the suffix itself.  Refcount-0 entries are evicted LRU
    when the pool is full.
    """

    def __init__(self, pages: int, page_tokens: int, first_row: int, *,
                 replica: int = 0):
        if pages < 1:
            raise ValueError(f"need >= 1 prefix page, got {pages}")
        if page_tokens < 1:
            raise ValueError(f"page_tokens must be >= 1, got {page_tokens}")
        self.pages = int(pages)
        self.page_tokens = int(page_tokens)
        self.first_row = int(first_row)
        self.replica = int(replica)
        self._free = list(range(first_row, first_row + pages))  # min-heap
        self._by_key: Dict[Tuple[int, ...], _Prefix] = {}
        self._by_row: Dict[int, _Prefix] = {}
        self._tick = 0

    # -- lookup --------------------------------------------------------

    def _share_len(self, prompt: Sequence[int]) -> int:
        """Longest shareable prefix length: whole pages, and at least one
        prompt token left over to carry the request's own logits."""
        return ((len(prompt) - 1) // self.page_tokens) * self.page_tokens

    def match(self, prompt: Sequence[int]) -> Optional[Tuple[int, int]]:
        """Longest sealed prefix of ``prompt``: ``(row, plen)`` or None."""
        plen = self._share_len(prompt)
        while plen >= self.page_tokens:
            e = self._by_key.get(tuple(prompt[:plen]))
            if e is not None and e.sealed:
                return e.row, plen
            plen -= self.page_tokens
        return None

    def acquire(self, prompt: Sequence[int]) -> Optional[Tuple[int, int]]:
        """Attach to the longest sealed prefix (refcount + hit metrics)."""
        got = self.match(prompt)
        counter = _metrics.counter(
            "bluefog_serve_prefix_hits_total"
            if got else "bluefog_serve_prefix_misses_total",
            "shared-prefix page lookups, by outcome")
        counter.inc(replica=str(self.replica))
        if got is None:
            return None
        row, plen = got
        e = self._by_row[row]
        self._tick += 1
        e.refs, e.tick = e.refs + 1, self._tick
        self._export()
        return row, plen

    def attach(self, row: int) -> None:
        """Refcount a row WITHOUT the hit/miss metric — the seal-then-attach
        path of the request that missed and prefilled the page itself."""
        e = self._by_row[row]
        self._tick += 1
        e.refs, e.tick = e.refs + 1, self._tick
        self._export()

    def release(self, row: int) -> None:
        e = self._by_row.get(row)
        if e is None or e.refs < 1:
            raise ValueError(f"prefix row {row} is not acquired")
        e.refs -= 1
        self._export()

    # -- admission -----------------------------------------------------

    def admit(self, prompt: Sequence[int]) -> Optional[Tuple[int, int]]:
        """Reserve a page row for ``prompt``'s shareable prefix.

        Returns ``(row, plen)`` for the engine to seal (prefill
        ``prompt[:plen]`` into ``row``, then :meth:`seal`), or None when
        the prefix is shorter than one page or the pool is exhausted by
        in-use entries.  Evicts the LRU refcount-0 entry when full.
        """
        plen = self._share_len(prompt)
        if plen < self.page_tokens:
            return None
        key = tuple(prompt[:plen])
        if key in self._by_key:                  # racing admit: reuse it
            return self._by_key[key].row, plen
        if self._free:
            row = heapq.heappop(self._free)
        else:
            idle = [e for e in self._by_row.values() if e.refs == 0]
            if not idle:
                return None
            victim = min(idle, key=lambda e: e.tick)
            del self._by_key[victim.tokens]
            del self._by_row[victim.row]
            row = victim.row
        digest = hashlib.blake2s(
            b",".join(str(t).encode() for t in key), digest_size=8
        ).hexdigest()
        e = _Prefix(row=row, tokens=key, digest=digest)
        self._by_key[key] = e
        self._by_row[row] = e
        self._export()
        return row, plen

    def seal(self, row: int) -> None:
        """Mark a row's pages as prefilled — attachable from now on."""
        self._by_row[row].sealed = True

    @property
    def in_use(self) -> int:
        return len(self._by_row)

    def describe(self) -> dict:
        """Flight-bundle block: what is resident, with content digests."""
        return {
            "pages": self.pages, "page_tokens": self.page_tokens,
            "resident": [
                {"row": e.row, "tokens": len(e.tokens), "refs": e.refs,
                 "digest": e.digest, "sealed": e.sealed}
                for e in sorted(self._by_row.values(),
                                key=lambda e: e.row)],
        }

    def _export(self) -> None:
        _metrics.gauge(
            "bluefog_serve_prefix_pages_in_use",
            "resident shared-prefix pages, by replica").set(
                float(self.in_use), replica=str(self.replica))
