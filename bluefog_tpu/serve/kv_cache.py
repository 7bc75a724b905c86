"""Slotted paged KV cache for the decentralized serving engine.

Layout (per device, i.e. per (replica, stage, tp) coordinate of the
compose carving)::

    k, v: [layers, slots + prefix_slots + 1, kv_heads, max_len, head_dim]

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

The layout is **kv-head major** (``kv_heads`` BEFORE ``max_len``): one
(row, head)'s key positions are contiguous, so the flash-decode kernel
(:mod:`bluefog_tpu.ops.pallas_decode`) streams ``[block_k, head_dim]``
K/V blocks straight from HBM as natively-tiled VMEM tiles — no Mosaic
relayout, no strided DMA.  The XLA paths below index the same layout.

**Quantized storage** (``store="int8"`` / ``"fp8"``): pages hold the
quantized payload plus per-(position, head) f32 amax scales in sibling
``k_scale``/``v_scale`` arrays — the exact symmetric-quantization recipe
the gossip wire codec uses (:func:`bluefog_tpu.ops.collectives._amax_scale`
with a head_dim-sized block), dequantized inside :func:`attend_rows` /
:func:`attend_chunk` right before the score matmul.  ``store="raw"``
keeps the payload in ``dtype`` (f32 or bf16) with no scales.

The pure functions here (:func:`layer_append`, :func:`attend_rows`,
:func:`attend_chunk`, ...) are the single-device math the engine's
shard_map body calls per layer; they are also unit-tested directly (GQA
grouping, slot-reuse equivalence after evict, quantization drift bounds).
:class:`SlotAllocator` is the host-side free heap with occupancy gauges
(``bluefog_serve_kv_slots_in_use`` / ``bluefog_serve_kv_occupancy``);
:class:`PrefixCache` is the host-side content-addressed page directory
(``bluefog_serve_prefix_{hits,misses}_total``).
"""
from __future__ import annotations

import dataclasses
import hashlib
import heapq
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.layout import Layout, with_layout_constraint

from ..ops.collectives import _amax_scale
from ..utils import metrics as _metrics

__all__ = ["KVCacheConfig", "init_cache", "attend_rows",
           "attend_chunk", "layer_append", "layer_append_chunk",
           "layer_prefill", "quantize_rows", "dequantize_rows",
           "store_dtype", "SlotAllocator", "PrefixCache"]

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

    def bytes(self) -> int:
        """Device bytes of one cache (payload pages + riding scales)."""
        per = self.layers * self.rows * self.max_len * self.kv_heads
        payload = 2 * per * self.head_dim * \
            jnp.dtype(store_dtype(self.store, self.dtype)).itemsize
        scales = 2 * per * 4 if self.quantized else 0
        return payload + scales

    def bytes_per_token(self) -> int:
        """Device bytes one cached token costs (k + v + scales), the
        serve_bench ``kv_bytes_per_token`` row's per-device term."""
        per_head = self.head_dim * \
            jnp.dtype(store_dtype(self.store, self.dtype)).itemsize
        if self.quantized:
            per_head += 4                       # the riding f32 amax scale
        return 2 * self.layers * self.kv_heads * per_head


def init_cache(cfg: KVCacheConfig) -> dict:
    """Zeroed cache dict: ``{"k", "v"}`` payload pages (plus
    ``{"k_scale", "v_scale"}`` when quantized)."""
    shape = (cfg.layers, cfg.rows, cfg.kv_heads, cfg.max_len, cfg.head_dim)
    dt = store_dtype(cfg.store, cfg.dtype)
    cache = {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
    if cfg.quantized:
        cache["k_scale"] = jnp.zeros(shape[:-1], jnp.float32)
        cache["v_scale"] = jnp.zeros(shape[:-1], jnp.float32)
    return cache


# ---------------------------------------------------------------------------
# Device-side page math: on one layer's slice of the cache dict, or (given
# ``layer``) on the stacked cache dict at that layer
# ---------------------------------------------------------------------------

def _pin_window(upd: jax.Array, max_len: int) -> jax.Array:
    """Give an update window ``[1, 1, kv_heads, T(, head_dim)]`` the axis
    order its cache tensor has in a TPU's memory, when compiling for one.

    A TPU stores ``[..., max_len, head_dim]`` with whichever of the two
    minor axes wastes less of the 128 lanes in the lanes: positions, for a
    ``head_dim`` of 64 under a ``max_len`` of 1024.  A
    ``dynamic_update_slice`` wants buffer and update in one order, and the
    compiler, left alone, has moved the 1.7 GB buffer to the order of the
    2 KB update (``head_dim`` minor, as the projection leaves it): a copy
    of the whole cache at the head of the call and one back at its end,
    in some programs and not in others.  Pinned, it transposes the
    update.  Other backends keep everything row-major and need no pin."""
    order = tuple(range(upd.ndim))
    if upd.ndim == 5:
        waste = lambda n: -(-n // 128) * 128 / n
        if waste(upd.shape[4]) > waste(max_len):
            order = (0, 1, 2, 4, 3)
    lay = Layout(major_to_minor=order)
    return lax.platform_dependent(
        upd, tpu=lambda w: with_layout_constraint(w, lay),
        default=lambda w: w)


def _write_lanes(t: jax.Array, layer: jax.Array, slots: jax.Array,
                 pos: jax.Array, upd: jax.Array) -> jax.Array:
    """``t[layer, slots[i], :, pos[i] + j] = upd[i, :, j]`` for every lane
    ``i`` in order (last write wins on the shared trash row) and every
    ``j < T``: ``t`` is a stacked cache tensor ``[layers, rows, kv_heads,
    max_len(, head_dim)]``, ``upd`` is ``[S, kv_heads, T(, head_dim)]``.

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
    S, T, max_len = upd.shape[0], upd.shape[2], t.shape[3]
    tail = (0,) * (t.ndim - 4)                          # head_dim, if any
    start = jnp.minimum(pos, max_len - T)
    over = pos - start               # > 0: the window runs past max_len
    rows = jnp.where(over < T, slots, t.shape[1] - 1)
    ats = [(layer, rows[i], 0, start[i]) + tail for i in range(S)]
    new = upd.astype(t.dtype)
    if T > 1:
        # every window is read before any is written: live lanes hold
        # rows of their own, and what the shared trash row held matters
        # to no one
        j = jnp.arange(T).reshape((1, 1, T) + (1,) * len(tail))
        over = over.reshape((S, 1, 1) + (1,) * len(tail))
        old = jnp.concatenate(
            [lax.dynamic_slice(t, at, (1, 1) + new.shape[1:])[0]
             for at in ats])
        new = jnp.where(j >= over,
                        jnp.take_along_axis(new, (j - over) % T, axis=2),
                        old)
    for i, at in enumerate(ats):
        t = lax.dynamic_update_slice(
            t, _pin_window(new[i][None, None], max_len), at)
    return t


def _read_lanes(t: jax.Array, layer: jax.Array,
                rows: jax.Array) -> jax.Array:
    """``t[layer, rows[i]]`` for every lane: ``[S, kv_heads, max_len(,
    head_dim)]`` out of a stacked cache tensor.  One ``dynamic_slice`` per
    lane, unrolled, NOT one gather: the TPU's gather of rows this long
    first cuts its whole operand — here all layers of the cache — into
    four pieces along ``max_len`` (``mini-gather-slice``), a copy of the
    tensor per layer; the slices are plain reads the compiler fuses."""
    size = (1, 1) + t.shape[2:]
    zeros = (0,) * (t.ndim - 2)
    return jnp.concatenate(
        [lax.dynamic_slice(t, (layer, rows[i]) + zeros, size)[0]
         for i in range(rows.shape[0])], axis=0)


def layer_append(cache: Dict[str, jax.Array], layer: jax.Array,
                 slots: jax.Array, lengths: jax.Array, k_new: jax.Array,
                 v_new: jax.Array, store: str = "raw"
                 ) -> Dict[str, jax.Array]:
    """One decode token per lane into ``layer`` of the stacked cache dict
    (``[layers, rows, kv_heads, max_len, head_dim]`` per tensor),
    quantizing on the way in when the store calls for it: ``k_new/v_new``
    are ``[S, kv_heads, head_dim]`` and lane i's token lands at position
    ``lengths[i]`` of row ``slots[i]``.  Duplicate (trash-slot) rows are
    allowed — last write wins, and nothing ever reads the trash row."""
    return layer_append_chunk(cache, layer, slots, lengths, k_new[:, None],
                              v_new[:, None], store)


def layer_append_chunk(cache: Dict[str, jax.Array], layer: jax.Array,
                       slots: jax.Array, lengths: jax.Array,
                       k_new: jax.Array, v_new: jax.Array,
                       store: str = "raw") -> Dict[str, jax.Array]:
    """Write a T-token chunk per lane (the k-token verify / chunked
    prefill append) into ``layer`` of the stacked cache dict:
    ``k_new/v_new`` are ``[S, T, kv_heads, head_dim]`` and token t of
    lane i lands at position ``lengths[i] + t`` of row ``slots[i]``."""
    qk, sk = quantize_rows(k_new, store)
    qv, sv = quantize_rows(v_new, store)
    out = dict(cache)
    for name, upd in (("k", qk), ("v", qv), ("k_scale", sk),
                      ("v_scale", sv)):
        if upd is not None:
            out[name] = _write_lanes(cache[name], layer, slots, lengths,
                                     jnp.swapaxes(upd, 1, 2))
    return out


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
    max_len = cache["k"].shape[3]
    out = dict(cache)
    for name, pay in (("k", qk), ("v", qv)):
        out[name] = lax.dynamic_update_slice(
            cache[name], _pin_window(
                pay.transpose(1, 0, 2)[None, None].astype(cache[name].dtype),
                max_len), (layer, slot_id, 0, 0, 0))
    if sk is not None:
        for name, sc in (("k_scale", sk), ("v_scale", sv)):
            out[name] = lax.dynamic_update_slice(
                cache[name], _pin_window(sc.T[None, None], max_len),
                (layer, slot_id, 0, 0))
    return out


def _gather_pages(cl: Dict[str, jax.Array], slots: jax.Array,
                  prefix_slots: Optional[jax.Array],
                  prefix_lens: Optional[jax.Array],
                  layer: Optional[jax.Array] = None):
    """Gather each lane's kv rows, reading **through the page
    indirection**: key positions ``< prefix_lens[i]`` come from the
    lane's shared prefix page, the rest from its private slot.  With
    ``layer`` the rows are read straight out of the stacked cache at
    ``[layer, row]`` (the layer is never materialized).  Returns
    f32-dequantized ``(ks, vs)`` of shape ``[S, Hkv, max_len, Dh]``."""
    def rows(name, r):
        return cl[name][r] if layer is None else \
            _read_lanes(cl[name], layer, r)

    ks, vs = rows("k", slots), rows("v", slots)
    ksc = rows("k_scale", slots) if "k_scale" in cl else None
    vsc = rows("v_scale", slots) if "v_scale" in cl else None
    if prefix_slots is not None:
        L = cl["k"].shape[-2]
        shared = (jnp.arange(L)[None, :]
                  < prefix_lens[:, None])                       # [S, L]
        sel = shared[:, None, :, None]
        ks = jnp.where(sel, rows("k", prefix_slots), ks)
        vs = jnp.where(sel, rows("v", prefix_slots), vs)
        if ksc is not None:
            ksc = jnp.where(shared[:, None, :],
                            rows("k_scale", prefix_slots), ksc)
            vsc = jnp.where(shared[:, None, :],
                            rows("v_scale", prefix_slots), vsc)
    ct = jnp.float32
    return dequantize_rows(ks, ksc, ct), dequantize_rows(vs, vsc, ct)


def attend_rows(q: jax.Array, kl: jax.Array, vl: jax.Array,
                slots: jax.Array, lengths: jax.Array,
                scale: Optional[float] = None, *,
                k_scale: Optional[jax.Array] = None,
                v_scale: Optional[jax.Array] = None,
                prefix_slots: Optional[jax.Array] = None,
                prefix_lens: Optional[jax.Array] = None,
                layer: Optional[jax.Array] = None) -> jax.Array:
    """Masked decode attention of one new token per request over its slot.

    ``q``: ``[S, heads, head_dim]`` (heads may be ``group * kv_heads`` —
    grouped-query attention: q head ``h`` attends compact kv head
    ``h // group``, via a reshape-grouped einsum that never materializes
    repeated K/V copies);
    ``kl/vl``: one layer's pages (post-append), or with ``layer`` the
    stacked cache read at that layer; ``lengths``: the position
    the new token was appended at, so keys ``0 .. lengths[i]`` inclusive
    are valid.  ``k_scale/v_scale`` dequantize int8/fp8 pages on the fly;
    ``prefix_slots/prefix_lens`` route key positions below the prefix
    length through the lane's shared prefix page.  Same numerics as the
    dense oracle: f32-floor scores, scale folded into q, ``-inf``
    masking.
    """
    S, H, Dh = q.shape
    Hkv, L = kl.shape[-3], kl.shape[-2]
    if H % Hkv:
        raise ValueError(f"{H} q heads not a multiple of {Hkv} kv heads")
    if scale is None:
        scale = Dh ** -0.5
    cl = {"k": kl, "v": vl}
    if k_scale is not None:
        cl["k_scale"], cl["v_scale"] = k_scale, v_scale
    ks, vs = _gather_pages(cl, slots, prefix_slots, prefix_lens, layer)
    ct = jnp.promote_types(q.dtype, jnp.float32)
    qg = (q.astype(ct) * scale).reshape(S, Hkv, H // Hkv, Dh)
    s = jnp.einsum("skgd,skld->skgl", qg, ks.astype(ct))
    valid = jnp.arange(L)[None, :] <= lengths[:, None]             # [S, L]
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("skgl,skld->skgd", p, vs.astype(ct))
    return out.reshape(S, H, Dh).astype(q.dtype)


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
    Hkv, L = cl["k"].shape[-3], cl["k"].shape[-2]
    if H % Hkv:
        raise ValueError(f"{H} q heads not a multiple of {Hkv} kv heads")
    if scale is None:
        scale = Dh ** -0.5
    ks, vs = _gather_pages(cl, slots, prefix_slots, prefix_lens, layer)
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
