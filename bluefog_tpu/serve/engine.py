"""Prefill + decode engine over a ``compose_parallelism`` carving.

The carving's gossip-DP axis becomes the **replica** axis: each of the
``m.dp`` replicas holds the full model PP×TP-sharded intra-slice and
serves its own stream of requests — no collective ever crosses the
``rank`` axis at serve time (that axis is reserved for
:mod:`bluefog_tpu.serve.refresh`, which pulls fresh weights from the
training fleet through it).  One SPMD program spans all replicas: every
engine call runs everywhere, and replicas with nothing to do run the
identical program over their trash slot, which is what keeps the compile
cache finite and the retrace sentinel at 0.

Shapes are **bucketed**: decode batches only ever have the lane counts in
``ServeConfig.batch_buckets`` and prompts are padded to the lengths in
``prefill_buckets``.  :meth:`ServeEngine.warmup` compiles every declared
bucket up front; afterwards the engine snapshots all jit caches and any
growth fires :func:`bluefog_tpu.utils.metrics.note_retrace` — the same
sentinel a training step uses, so one gauge covers the whole fleet.

The KV cache is a donated argument that every engine program updates **in
place**: it rides the carry of the layer loop (and of the fused
``decode_steps_per_call`` loop around it) and is written and read at
``[layer, row, ...]`` — never sliced out per layer, never restacked, so a
program's output cache is its input buffer (:mod:`.kv_cache` owns the
layout, which follows the shapes: heads of 64 lie side by side in a
token's row, ``program_memory()[...]["pages"]``; the in-place writes and
reads, int8/fp8 page storage and shared prefix pages;
:meth:`ServeEngine.program_memory` and the
``bluefog_serve_cache_copy_bytes`` gauge say what the compiler built).
A decode token's K and V are not written in the loop at all: each layer
attends over the cached rows plus its own token, the tokens of all layers
leave the loop as the scan's stacked output and land in the cache once
per lane and tensor after it (``bluefog_serve_cache_writes_per_call``);
only the Pallas flash-decode kernel, which reads its pages from HBM
itself, has them written per layer.  That attention meets the layer's
pages where they lie, every row its own query, in all three families
(``program_memory()[...]["read"] == "in_place"``,
``bluefog_serve_cache_positions_read_total{kind}``), the dense family's
only where its live lanes end: of token rows the blocks that hold each
lane's own positions, fetched by a kernel that takes the stacked cache
whole, of pages kept by head as far as the longest live lane of the call
reaches, a bound the program chooses itself
(:func:`.kv_cache.attend_layer`); shared prefix pages,
a quantized store and a bucket under a third of the rows stage each
lane's row first, as the cache's own properties say.
Steady-state decode is a single cached program per (bucket,
steps_per_call): embed → pp-cycle of stage-local layer loops
(``ppermute`` moves the activation, a stage-id ``where`` keeps exactly
one stage's work where there is more than one stage) → stage-0 logits
``psum`` → greedy argmax or the fused temperature/top-p sampler, fused
over ``decode_steps_per_call`` tokens.

Fast paths on top of the correct-first PR 10 engine:

- **Self-speculative decoding** (``spec_decode=k``): a truncated-stage
  draft (:func:`~bluefog_tpu.parallel.compose.draft_carve` — the first
  ``spec_stages`` stages of the target's own pipeline, early-exited into
  the shared head) drafts ``k`` tokens in one fused scan, then ONE
  target chunk call verifies all ``k`` causally and the host keeps the
  longest agreeing prefix plus the target's bonus token.  Accepted
  tokens are bit-identical to plain greedy decode (the accept rule only
  ever emits target-argmax tokens), so speculation is pure throughput.
- **Shared prefix pages** (``prefix_pages=p``): content-hashed prompt
  prefixes are sealed once into reserved cache rows; a prefix-hit
  request prefills only its divergent remainder (:meth:`chunk_prefill`)
  and every attention reads through the page indirection.
- **Quantized KV** (``kv_dtype="int8"|"fp8"``): pages stored with the
  wire codec's per-(position, head) amax recipe, dequantized inside the
  attend kernels; prefill's own dense attention stays full-precision —
  drift only enters where a stored page is read back.

A **latent model** (:class:`~bluefog_tpu.models.decoder.LatentConfig`:
latent attention, a leading dense layer, expert layers of which this chip
holds a subset under the full-width router) is served by two programs of
its own over a latent cache (one compressed vector per token and layer,
:class:`.kv_cache.LatentCacheConfig`): prefill in the unabsorbed form,
decode in the absorbed form with the same deferred one-write-per-lane
landing; the leading layer runs apart from the scan over the expert
layers, one cache stacked over all.  Every fast path above and any
carving with pp, tp or ep above 1 is refused for it by name.  The SAME two
programs serve a latent model of shortcut-connected double layers
(``LatentConfig.shortcut``: two latent attentions with their own cached
vectors, two dense FFNs, and one expert layer whose result joins a
sublayer later; no leading layer, the double layer in the scan, the cache
stacked over attention sublayers), under a softmax router whose last
outputs are identity experts that compute nothing (``router``,
``zero_experts``) and with no shared expert; its programs also hand out
the experts each layer chose and the decode steps' logits
(:meth:`ServeEngine.decode_chosen`), which only a comparison reads.

A **hybrid model** (:class:`~bluefog_tpu.models.decoder.HybridConfig`:
window and full attention layers by a static plan, grouped-query heads
with QK-norm, the same held-experts layers) has two programs of its own
too, over a cache of two kinds (:class:`.kv_cache.HybridCacheConfig`: a
slot's rows in the full layers, its rings in the window layers): the plan
is unrolled, a prompt's attention is blocked, decode reads the cache in
place, and the same fast paths and carvings are refused by name.

A **single-mixer model** (:class:`~bluefog_tpu.models.decoder.SsmConfig`:
every layer ONE mixer, by a static plan a recurrent mixer (Mamba-2, or the
gated delta rule with a decay per channel), attention that turns nothing
(gated where the configuration says so), or experts under the same
full-width router, ``relu^2`` in a latent or gated SiLU on the hidden
state) is the fourth pair of programs, over a cache with a third kind of
tensor (:class:`.kv_cache.SsmCacheConfig`): a slot owns, beside its rows in
the attention layers, a fixed-size recurrent state and the convolution's
kept inputs in every recurrent layer.  A prompt is scanned in chunks and
overwrites the slot's state whole; a decode step updates every row's state
where it lies.  The scheduler needs nothing new: a state lives on the
device between calls, so a call staged one ahead finds it there.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..models import decoder
from ..moe.dropless import decode_tile
from ..moe.layers import held_moe_ffn, moe_dropless_combine, router_topk
from ..moe.model import MoELMConfig
from ..ops import pallas_attention as _pa
from ..ops import pallas_decode as _pd
from ..ops.ulysses import dense_attention
from ..parallel.compose import AXES, LMConfig, Mesh3D, draft_carve
from ..utils import flight as _flight
from ..utils import metrics as _metrics
from ..utils import tracing as _tracing
from . import kv_cache as _kv

__all__ = ["ServeConfig", "ServeEngine"]

_BUCKET_GRAMMAR = ("'<batch,...>@<prompt_len,...>' with positive ints "
                   "(e.g. '1,2,4@8,16')")


def _parse_buckets(spec: str) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """``"1,2,4@8,16"`` -> ``((1, 2, 4), (8, 16))`` (batch@prefill).

    Malformed specs are rejected naming the offending token and the
    expected grammar, so a typo'd env var fails loudly at config time
    instead of as a bare ``int()`` traceback.
    """
    if spec.count("@") > 1:
        raise ValueError(
            f"BLUEFOG_SERVE_BUCKETS={spec!r}: more than one '@' — expected "
            + _BUCKET_GRAMMAR)
    batch_s, _, prefill_s = spec.partition("@")

    def ints(part: str, side: str) -> Tuple[int, ...]:
        out = []
        for tok in part.split(","):
            tok = tok.strip()
            if not tok:
                continue
            try:
                v = int(tok)
            except ValueError:
                raise ValueError(
                    f"BLUEFOG_SERVE_BUCKETS={spec!r}: bad {side} bucket "
                    f"token {tok!r} — expected " + _BUCKET_GRAMMAR) from None
            if v < 1:
                raise ValueError(
                    f"BLUEFOG_SERVE_BUCKETS={spec!r}: {side} bucket "
                    f"{tok!r} must be >= 1 — expected " + _BUCKET_GRAMMAR)
            out.append(v)
        return tuple(out)

    return ints(batch_s, "batch"), ints(prefill_s, "prefill")


def _env_int(name: str, tok: str, grammar: str) -> int:
    try:
        v = int(tok.strip())
    except ValueError:
        raise ValueError(f"{name}={tok!r}: bad token {tok.strip()!r} — "
                         f"expected {grammar}") from None
    if v < 0:
        raise ValueError(f"{name}={tok!r}: {tok.strip()!r} must be >= 0 — "
                         f"expected {grammar}")
    return v


_MOE_GRAMMAR = ("'<experts>[x<top_k>][@<ep>][:<tile>]' with positive ints "
                "(e.g. '8', '8x2', '8x2@2:4'; tile in 1..8, omitted = "
                "auto decode tile)")


def _parse_serve_moe(spec: str) -> Tuple[int, int, int, int]:
    """``"8x2@2:4"`` -> ``(experts=8, top_k=2, ep=2, tile=4)``.

    ``top_k``/``ep``/``tile`` are optional (defaults 1/1/0, 0 meaning the
    engine picks the decode tile via
    :func:`~bluefog_tpu.moe.dropless.decode_tile`).  Malformed specs name
    the offending token and the grammar, same contract as
    :func:`_parse_buckets`.
    """
    body, _, tile_s = spec.partition(":")
    body, _, ep_s = body.partition("@")
    e_s, _, k_s = body.partition("x")

    def intval(tok: str, what: str, lo: int) -> int:
        tok = tok.strip()
        try:
            v = int(tok)
        except ValueError:
            raise ValueError(
                f"BLUEFOG_SERVE_MOE={spec!r}: bad {what} token {tok!r} — "
                f"expected " + _MOE_GRAMMAR) from None
        if v < lo:
            raise ValueError(
                f"BLUEFOG_SERVE_MOE={spec!r}: {what} {tok!r} must be >= "
                f"{lo} — expected " + _MOE_GRAMMAR)
        return v

    return (intval(e_s, "experts", 1),
            intval(k_s, "top_k", 1) if k_s else 1,
            intval(ep_s, "ep", 1) if ep_s else 1,
            intval(tile_s, "tile", 1) if tile_s else 0)


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Static serving shapes — everything that pins a compiled program.

    ``batch_buckets``: the only decode lane counts ever traced (ascending);
    the scheduler rounds its active-lane count up to the smallest bucket
    that fits and pads the rest with trash lanes.  ``prefill_buckets``:
    prompt pad lengths, same contract.  ``slots``/``max_len`` size each
    replica's KV cache; ``decode_steps_per_call`` fuses that many greedy
    tokens into one program call (admission only happens between calls).

    Fast-path knobs (all default-off, so the default config compiles the
    exact PR 10 programs):

    - ``spec_decode``: draft depth k for self-speculative decoding (0 =
      off); ``spec_stages`` is how many pipeline stages the draft runs.
    - ``prefix_pages`` / ``prefix_page_tokens``: shared prefix pool size
      and the page granularity prompts are content-hashed at.
    - ``kv_dtype``: KV page storage — ``"raw"`` (engine dtype), or
      ``"int8"`` / ``"fp8"`` via the wire-codec quantizer.
    - ``decode_kernel``: decode-attention backend — ``"xla"`` (the
      gather-then-attend reference in serve/kv_cache.py) or ``"pallas"``
      (ops/pallas_decode.py: flash decode reading KV pages in place
      through the slot indirection, dequant fused for int8/fp8 stores).
      ``decode_block_k`` is the KV-page tile (keys per kernel grid step;
      clamped to ``max_len`` for short caches).
    - ``temperature`` / ``top_p`` / ``seed``: the fused sampler.  0.0
      temperature is exact greedy (the default); speculative decoding
      requires greedy (its accept rule is argmax-prefix agreement).
    """
    batch_buckets: Tuple[int, ...] = (1, 2, 4)
    prefill_buckets: Tuple[int, ...] = (8, 16)
    slots: int = 8
    max_len: int = 64
    decode_steps_per_call: int = 1
    dtype: Any = jnp.float32
    kv_dtype: str = "raw"
    decode_kernel: str = "xla"
    decode_block_k: int = 128
    spec_decode: int = 0
    spec_stages: int = 1
    prefix_pages: int = 0
    prefix_page_tokens: int = 16
    temperature: float = 0.0
    top_p: float = 1.0
    seed: int = 0
    moe_experts: int = 0        # 0 = dense model; >0 declares the MoE shape
    moe_top_k: int = 1          # serving routes top-k only (k in {1, 2})
    moe_ep: int = 1             # expert-parallel peers carved per replica
    moe_tile: int = 0           # dropless decode tile rows (0 = auto)

    def __post_init__(self):
        if not self.batch_buckets or not self.prefill_buckets:
            raise ValueError("declare at least one batch and one prefill "
                             "bucket — undeclared shapes retrace")
        for name in ("batch_buckets", "prefill_buckets"):
            b = getattr(self, name)
            if tuple(sorted(set(b))) != tuple(b):
                raise ValueError(f"{name}={b} must be strictly ascending")
        if self.batch_buckets[-1] > self.slots:
            raise ValueError(
                f"largest batch bucket ({self.batch_buckets[-1]}) exceeds "
                f"slots ({self.slots}); a lane needs a resident slot")
        if self.prefill_buckets[-1] > self.max_len:
            raise ValueError(
                f"largest prefill bucket ({self.prefill_buckets[-1]}) "
                f"exceeds max_len ({self.max_len})")
        if self.decode_steps_per_call < 1:
            raise ValueError("decode_steps_per_call must be >= 1")
        if self.kv_dtype not in _kv.KV_STORES:
            raise ValueError(f"kv_dtype={self.kv_dtype!r}: expected one of "
                             f"{', '.join(_kv.KV_STORES)}")
        _kv.store_dtype(self.kv_dtype)      # fp8 needs dtype support
        if self.decode_kernel not in ("xla", "pallas"):
            raise ValueError(
                f"decode_kernel={self.decode_kernel!r}: expected 'xla' or "
                "'pallas'")
        if self.decode_block_k < 1:
            raise ValueError("decode_block_k must be >= 1")
        if self.decode_kernel == "pallas":
            # fail at config time, not inside the first traced decode step
            bk = _pd._block_k_for(self.max_len, self.decode_block_k)
            if self.prefix_pages and self.prefix_page_tokens % bk:
                raise ValueError(
                    f"prefix_page_tokens ({self.prefix_page_tokens}) must be "
                    f"a multiple of the flash-decode KV block "
                    f"({bk}): the kernel routes whole KV blocks through the "
                    "shared prefix page, so a prefix may not end mid-block")
        if self.spec_decode < 0:
            raise ValueError("spec_decode (draft depth k) must be >= 0")
        if self.spec_stages < 1:
            raise ValueError("spec_stages must be >= 1")
        if self.prefix_pages < 0:
            raise ValueError("prefix_pages must be >= 0")
        if self.prefix_page_tokens < 1:
            raise ValueError("prefix_page_tokens must be >= 1")
        if self.prefix_pages and \
                self.prefix_page_tokens > self.prefill_buckets[-1]:
            raise ValueError(
                f"prefix_page_tokens ({self.prefix_page_tokens}) exceeds "
                f"the largest prefill bucket ({self.prefill_buckets[-1]}): "
                "a prefix page is sealed by one prefill call")
        if self.temperature < 0.0:
            raise ValueError("temperature must be >= 0 (0 = greedy)")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError("top_p must be in (0, 1]")
        if self.spec_decode and self.temperature > 0.0:
            raise ValueError(
                "speculative decoding is greedy-only: its accept rule is "
                "argmax-prefix agreement; sampled speculation needs the "
                "full accept-reject rule (set temperature=0.0 or "
                "spec_decode=0)")
        if self.moe_experts < 0:
            raise ValueError("moe_experts must be >= 0 (0 = dense model)")
        if self.moe_experts:
            if self.moe_top_k not in (1, 2):
                raise ValueError(
                    f"moe_top_k ({self.moe_top_k}) must be 1 or 2: serving "
                    "routes top-k only")
            if self.moe_ep < 1:
                raise ValueError(f"moe_ep ({self.moe_ep}) must be >= 1")
            if self.moe_experts % self.moe_ep:
                raise ValueError(
                    f"moe_serving_ep_mismatch: moe_experts "
                    f"({self.moe_experts}) % moe_ep ({self.moe_ep}) != 0 — "
                    "each expert-parallel peer owns a contiguous block of "
                    f"experts; offender: moe_ep={self.moe_ep}")
            if not 0 <= self.moe_tile <= 8:
                raise ValueError(
                    f"moe_tile ({self.moe_tile}) must be in [0, 8] (0 = "
                    "auto): decode batches are tiny, so grouped tiles "
                    "above 8 rows pad every expert group with mostly-zero "
                    "tiles")

    @property
    def decode_window(self) -> int:
        """Most tokens one engine call can add to a slot (plain fused
        decode vs one speculative round's k drafts + bonus)."""
        return max(self.decode_steps_per_call,
                   self.spec_decode + 1 if self.spec_decode else 0)

    @classmethod
    def from_env(cls, **overrides) -> "ServeConfig":
        """Honour the serving fast-path env surface:

        - ``BLUEFOG_SERVE_BUCKETS='<batch,...>@<prompt_len,...>'``
        - ``BLUEFOG_SPEC_DECODE='<k>'`` or ``'<k>@<stages>'``
        - ``BLUEFOG_KV_DTYPE='raw'|'int8'|'fp8'``
        - ``BLUEFOG_PREFIX_PAGES='<pages>'`` or ``'<pages>x<page_tokens>'``
        - ``BLUEFOG_DECODE_KERNEL='xla'|'pallas'`` or ``'pallas@<block_k>'``
        - ``BLUEFOG_SERVE_MOE='<experts>[x<top_k>][@<ep>][:<tile>]'``
        """
        spec = os.environ.get("BLUEFOG_SERVE_BUCKETS", "")
        if spec:
            batch, prefill = _parse_buckets(spec)
            overrides.setdefault("batch_buckets", batch)
            if prefill:
                overrides.setdefault("prefill_buckets", prefill)
        sd = os.environ.get("BLUEFOG_SPEC_DECODE", "")
        if sd:
            grammar = "'<k>' or '<k>@<stages>' (e.g. '4' or '4@1')"
            k_s, _, st_s = sd.partition("@")
            overrides.setdefault(
                "spec_decode", _env_int("BLUEFOG_SPEC_DECODE", k_s, grammar))
            if st_s:
                overrides.setdefault(
                    "spec_stages",
                    _env_int("BLUEFOG_SPEC_DECODE", st_s, grammar))
        kd = os.environ.get("BLUEFOG_KV_DTYPE", "")
        if kd:
            if kd not in _kv.KV_STORES:
                raise ValueError(
                    f"BLUEFOG_KV_DTYPE={kd!r}: bad token {kd!r} — expected "
                    f"one of {', '.join(_kv.KV_STORES)}")
            overrides.setdefault("kv_dtype", kd)
        dk = os.environ.get("BLUEFOG_DECODE_KERNEL", "")
        if dk:
            grammar = ("'xla' or 'pallas' or 'pallas@<block_k>' "
                       "(e.g. 'pallas@128')")
            kern, _, bk_s = dk.partition("@")
            if kern not in ("xla", "pallas"):
                raise ValueError(
                    f"BLUEFOG_DECODE_KERNEL={dk!r}: bad token {kern!r} — "
                    f"expected {grammar}")
            overrides.setdefault("decode_kernel", kern)
            if bk_s:
                overrides.setdefault(
                    "decode_block_k",
                    _env_int("BLUEFOG_DECODE_KERNEL", bk_s, grammar))
        pp = os.environ.get("BLUEFOG_PREFIX_PAGES", "")
        if pp:
            grammar = ("'<pages>' or '<pages>x<page_tokens>' "
                       "(e.g. '4' or '4x16')")
            pages_s, _, ptok_s = pp.partition("x")
            overrides.setdefault(
                "prefix_pages",
                _env_int("BLUEFOG_PREFIX_PAGES", pages_s, grammar))
            if ptok_s:
                overrides.setdefault(
                    "prefix_page_tokens",
                    _env_int("BLUEFOG_PREFIX_PAGES", ptok_s, grammar))
        sm = os.environ.get("BLUEFOG_SERVE_MOE", "")
        if sm:
            experts, top_k, ep, tile = _parse_serve_moe(sm)
            overrides.setdefault("moe_experts", experts)
            overrides.setdefault("moe_top_k", top_k)
            overrides.setdefault("moe_ep", ep)
            overrides.setdefault("moe_tile", tile)
        return cls(**overrides)

    def batch_bucket_for(self, lanes: int) -> int:
        """Smallest declared decode bucket that fits ``lanes`` live lanes."""
        for b in self.batch_buckets:
            if b >= lanes:
                return b
        raise ValueError(f"{lanes} live lanes exceed the largest declared "
                         f"batch bucket {self.batch_buckets[-1]}")

    def prefill_bucket_for(self, length: int) -> int:
        """Smallest declared prompt pad length that fits ``length`` tokens."""
        for b in self.prefill_buckets:
            if b >= length:
                return b
        raise ValueError(f"prompt of {length} tokens exceeds the largest "
                         f"declared prefill bucket "
                         f"{self.prefill_buckets[-1]}")


class _DeviceRow:
    """Row ``index`` of a program's ``[n_devices, ...]`` output, left on the
    device: it crosses to the host when something converts it
    (``np.asarray``), which ``on_read`` is told."""

    def __init__(self, out: jax.Array, index: int, on_read):
        self._out, self._index, self._on_read = out, index, on_read
        self.shape, self.dtype = out.shape[1:], out.dtype

    def __array__(self, dtype=None, copy=None):
        self._on_read()
        row = np.asarray(self._out)[self._index]
        return row if dtype is None else row.astype(dtype)


class _DecodeCall(NamedTuple):
    """A decode call dispatched and not yet read back: its bucket, its
    lanes' slots and positions ``[replicas, S]``, the program's outputs
    before the state (tokens ``[n_devices, steps, S]`` first) and the cache
    positions its attention meets (None: the program counts them itself)."""
    S: int
    slots: np.ndarray
    lens: np.ndarray
    out: list
    read: Optional[int] = None


class ServeEngine:
    """SPMD prefill/decode over one carving; host-side shapes per replica.

    ``params`` is the ``[n, ...]``-stacked compose-LM tree
    (:func:`~bluefog_tpu.parallel.compose.init_lm_params` layout, or a
    training snapshot via :func:`bluefog_tpu.checkpoint.load_for_serving`).
    The engine never mutates it — :meth:`update_params` rebinds the whole
    tree, which is how the refresher swaps weights mid-traffic without a
    retrace (same shapes, same program).  With a
    :class:`~bluefog_tpu.models.decoder.LatentConfig` as ``cfg`` the tree
    is :func:`~bluefog_tpu.models.decoder.latent_param_shapes`'s
    (``first`` / ``blocks`` / ``shared``) and the latent programs run.
    """

    # a single-mixer model's programs (set per engine; the class's word is
    # what an engine built before the family, or around it, goes by)
    _ssm = False

    def __init__(self, m: Mesh3D,
                 cfg: "LMConfig | decoder.LatentConfig | decoder.HybridConfig"
                      " | decoder.SsmConfig",
                 params: Any,
                 scfg: Optional[ServeConfig] = None):
        if m.sp != 1:
            raise ValueError(
                "serving decodes one token at a time; an sp > 1 carving has "
                "no sequence to shard — fold sp into tp for inference")
        self._moe = isinstance(cfg, MoELMConfig)
        self._latent = isinstance(cfg, decoder.LatentConfig)
        self._hybrid = isinstance(cfg, decoder.HybridConfig)
        self._ssm = isinstance(cfg, decoder.SsmConfig)
        # one chip's share of an expert-parallel deployment: held experts
        # under the full-width router, programs of the family's own
        self._share = self._latent or self._hybrid or self._ssm
        # a call of these returns the routing carrier beside its tokens
        self._routed = self._moe or self._share
        if self._moe and cfg.router_mode == "expert_choice":
            raise ValueError(
                "moe_serving_requires_topk_router: expert-choice routing "
                "selects each expert's top-C tokens over the WHOLE "
                "sequence, but autoregressive decode sees one token at a "
                "time — an EC router at serve time would condition routing "
                "on future tokens (the causality caveat that keeps it "
                "training-only).  Serve with router_mode='topk'.")
        cfg.validate(m)
        scfg = scfg or ServeConfig.from_env()
        if scfg.max_len < scfg.prefill_buckets[-1] + scfg.decode_window:
            raise ValueError("max_len leaves no room to decode past the "
                             "longest prompt bucket")
        if self._share:
            self._refuse_share(
                "latent" if self._latent else
                "hybrid" if self._hybrid else "ssm", m, scfg)
        if scfg.moe_experts and not self._moe:
            raise ValueError(
                f"ServeConfig declares an MoE (moe_experts="
                f"{scfg.moe_experts}, via BLUEFOG_SERVE_MOE or --serve-moe) "
                "but the model config is dense — build an MoELMConfig or "
                "drop the knob")
        if self._moe and scfg.moe_experts:
            for knob, mine in (("moe_experts", cfg.num_experts),
                               ("moe_top_k", cfg.top_k),
                               ("moe_ep", m.ep)):
                declared = getattr(scfg, knob)
                if declared != mine:
                    raise ValueError(
                        f"ServeConfig.{knob}={declared} does not match the "
                        f"model/carving value {mine} — the serve-MoE knob "
                        "must agree with the MoELMConfig and the ep carve")
        if self._moe:
            e_local = cfg.num_experts // m.ep
            # decode tile: every ep peer contributes its (replicated) lane
            # rows, so the per-device grouped buffer sees ep * S * k rows
            # over e_local groups
            self._moe_tile = scfg.moe_tile or decode_tile(
                m.ep * scfg.batch_buckets[-1] * cfg.top_k, e_local)
            self._moe_chunk_tile = cfg.group_tile   # prefill/verify shapes
        self._route_stats: Optional[np.ndarray] = None
        self._decode_logits = None      # (slots, device array): hybrid, ssm
        self._decode_chosen = None      # (slots, device array): ssm, and
        self._prefill_chosen = None     # device array: a softmax router's
        self.m, self.cfg, self.scfg = m, cfg, scfg
        self.draft = draft_carve(m, cfg, scfg.spec_stages) \
            if scfg.spec_decode else None
        self._sharding = NamedSharding(m.mesh, P(AXES))
        # normalize through the SAME placement path update_params uses, so
        # a mid-traffic weight swap presents bit-identical shardings to the
        # jit cache and cannot retrace the warmed buckets
        self.update_params(params)
        self.cache_cfg = _kv.LatentCacheConfig(
            layers=cfg.attn_layers, slots=scfg.slots, max_len=scfg.max_len,
            kv_rank=cfg.kv_rank, rope_dim=cfg.rope_dim, dtype=scfg.dtype) \
            if self._latent else _kv.HybridCacheConfig(
            full_layers=cfg.layers_of("full"),
            window_layers=cfg.layers_of("window"), slots=scfg.slots,
            max_len=scfg.max_len, window=cfg.window, kv_heads=cfg.kv_heads,
            head_dim=cfg.head_dim, dtype=scfg.dtype) \
            if self._hybrid else _kv.SsmCacheConfig.of(
            cfg, scfg.slots, scfg.max_len, scfg.dtype) \
            if self._ssm else _kv.KVCacheConfig(
            layers=cfg.layers // m.pp, slots=scfg.slots,
            max_len=scfg.max_len, kv_heads=cfg.heads // m.tp,
            head_dim=cfg.d_model // cfg.heads, dtype=scfg.dtype,
            store=scfg.kv_dtype, prefix_slots=scfg.prefix_pages)
        # the zero state and every program's outputs carry the SAME stated
        # sharding (``out_shardings``): left to infer it, jit names an
        # output on a one-chip mesh ``P()`` or ``P(AXES)`` by its rank, and
        # a state array that changed spelling between calls would add a jit
        # cache entry to every bucket on its second visit
        cc = self.cache_cfg

        def _zeros():
            if self._share:
                # a recurrent layer's state is float32 whatever is served
                dtypes = cc.dtypes() if self._ssm else {}
                return {name: jnp.zeros((1,) + shape,
                                        dtypes.get(name, cc.dtype))
                        for name, shape in cc.shapes().items()}
            return {name: t[None] for name, t in _kv.init_cache(cc).items()}

        # the fused sampler's raw PRNG keys, one per physical row, live on
        # the device beside the cache: an argument and a donated output of
        # every program.  An admission's key is made INSIDE its prefill
        # from (seed, replica, slot, admission count), so a fixed seed
        # replays a fixed run; decode gathers its lanes' keys and scatters
        # the advanced ones back (idle lanes meet in the trash row)
        self.cache, self._keys = jax.jit(jax.shard_map(
            lambda: (_zeros(), jnp.zeros((1, cc.rows, 2), jnp.uint32)),
            mesh=m.mesh, in_specs=(), out_specs=P(AXES)),
            out_shardings=self._sharding)()
        self._decode_jit = self._build(
            self._latent_decode_body if self._latent else
            self._hybrid_decode_body if self._hybrid else
            self._ssm_decode_body if self._ssm else self._decode_body)
        self._prefill_jit = self._build(
            self._latent_prefill_body if self._latent else
            self._hybrid_prefill_body if self._hybrid else
            self._ssm_prefill_body if self._ssm else self._prefill_body)
        _metrics.gauge(
            "bluefog_serve_residual_streams",
            "residual streams the layer loops of this engine's programs "
            "carry (1: the plain residual)").set(
                float(getattr(cfg, "streams", 1)))
        if self._share:
            _metrics.gauge(
                "bluefog_serve_cache_bytes_per_token",
                "device bytes one cached token costs over all layers"
            ).set(float(cc.bytes_per_token()))
        if self._hybrid or self._ssm:
            for kind, size in cc.bytes_per_slot().items():
                _metrics.gauge(
                    "bluefog_serve_cache_bytes_per_slot",
                    "device bytes of the cache a slot owns, by kind of "
                    "layer (full: every position; window: a ring; ssm: a "
                    "recurrent layer's state and its convolution's kept "
                    "inputs)"
                ).set(float(size), kind=kind)
        self._chunk_jit = self._build(self._chunk_body) \
            if (scfg.spec_decode or scfg.prefix_pages) else None
        self._draft_jit = self._build(self._draft_body) \
            if scfg.spec_decode else None
        # a decode call staged before the one ahead of it has been read
        # back takes its lanes' tokens from that call's output, on the
        # device (:meth:`decode`): the decode programs themselves are the
        # ones that take every token from the host
        self._feed_jit = jax.jit(
            jax.shard_map(self._feed_body, mesh=m.mesh, in_specs=P(AXES),
                          out_specs=P(AXES), check_vma=False),
            out_shardings=self._sharding)
        self._flying: Optional[_DecodeCall] = None     # dispatched, unread
        self._seed_count = 0        # admissions so far: folded into keys
        self._warm_sizes: Optional[Tuple[int, ...]] = None
        self._program_bytes: dict = {}
        self._engine_trace = _tracing.new_trace("engine")

    # what a held-experts family's programs do not do yet, by family: the
    # reason each ServeConfig fast path is refused (a later PR each)
    _SHARE_REFUSALS = {
        "latent": dict(
            decode_kernel="the flash-decode kernel streams per-head K and V "
                          "pages; the latent cache holds one vector per token",
            kv_dtype="the latent cache has no quantized store",
            spec_decode="there is no truncated-stage draft of a latent "
                        "model",
            prefix_pages="the latent cache has no shared prefix rows"),
        "hybrid": dict(
            decode_kernel="the flash-decode kernel reads a slot's row up to "
                          "its length; it has no ring",
            kv_dtype="the two-kind cache has no quantized store",
            spec_decode="the model's own drafter layer is not served, and "
                        "there is no truncated-stage draft of one chip's "
                        "share",
            prefix_pages="a ring holds a prompt's END: it cannot lend rows "
                         "to a shared prefix"),
        "ssm": dict(
            decode_kernel="the flash-decode kernel reads rows of positions; "
                          "a state-space layer's state has none",
            kv_dtype="the state cache has no quantized store: a recurrent "
                     "state is fed back every step",
            spec_decode="the model's own drafter layer is not served, and a "
                        "rejected draft would have to roll a state back",
            prefix_pages="a prefix page holds positions; a recurrent state "
                         "has none to share"),
    }

    @classmethod
    def _refuse_share(cls, family: str, m: Mesh3D, scfg: ServeConfig) -> None:
        """Refuse by name (``<family>_serving_<what>``), before anything is
        built, what the programs of a held-experts ``family`` do not do."""
        why = cls._SHARE_REFUSALS[family]
        if (m.pp, m.tp, m.ep) != (1, 1, 1):
            raise ValueError(
                f"{family}_serving_carving: pp={m.pp} tp={m.tp} ep={m.ep} — "
                f"the {family} programs run one chip's share of each layer; "
                "the experts it holds are named in the model's config, "
                "replicas (dp) are the only carving")
        for bad, what in ((scfg.decode_kernel != "xla", "decode_kernel"),
                          (scfg.kv_dtype != "raw", "kv_dtype"),
                          (scfg.spec_decode, "spec_decode"),
                          (scfg.prefix_pages, "prefix_pages")):
            if bad:
                raise ValueError(f"{family}_serving_{what}: {why[what]}")

    def _stage(self, name: str, **attrs) -> _tracing.stage:
        """``bf:engine.<name>`` in the profiler's trace (and the ring when
        armed).  Every device call is staged the same way: ``stage_in``
        (the call's one host array onto the mesh), ``dispatch`` (the
        jitted call, until it returns to Python, and the request for what
        the host will read of it), ``collect`` (the retrace check and the
        bookkeeping, and beneath it ``wait``: until the device has the
        call's outputs, and ``read_back``: until they are host arrays)."""
        return _tracing.stage(self._engine_trace, name, cat="engine",
                              **attrs)

    # ------------------------------------------------------------------
    # device-side bodies (per-device shapes, leading [1, ...] sliced off)
    # ------------------------------------------------------------------

    def _build(self, body):
        """Every program is ``body(params, cache, keys, staged)``: the
        cache and the sampler-key table are its state (donated, its last
        two outputs), ``staged`` the one int32 array the host sends a call
        (:meth:`_pack`)."""
        return jax.jit(
            jax.shard_map(body, mesh=self.m.mesh,
                          in_specs=P(AXES), out_specs=P(AXES),
                          check_vma=False),
            donate_argnums=(1, 2),
            out_shardings=NamedSharding(self.m.mesh, P(AXES)))

    @staticmethod
    def _unpack(staged, n: int):
        """Inside a program, what :meth:`_pack` put together: the tokens
        ``[..., T]`` and the ``n`` integers after them, ``[...]`` each."""
        return (staged[..., :-n],) + tuple(staged[..., i - n]
                                           for i in range(n))

    def _unpack_lanes(self, lanes, n: int = 4):
        """A lane program's ``staged``: tokens ``[S, T]``, then slot,
        position, prefix row and prefix length per lane (the last two None
        for the program of an engine without prefix pages) and whatever
        else the program was sent."""
        toks, slot_ids, lens, prows, plens, *rest = self._unpack(lanes, n)
        if not self._use_prefix:
            prows = plens = None
        return (toks, slot_ids, lens, prows, plens, *rest)

    @jax.named_scope("readout")
    def _seed_rows(self, keys, rows, key_id, count):
        """An admission's sampler key into its row of the table, per lane:
        ``fold_in(fold_in(PRNGKey(seed), key_id), count)`` with ``key_id``
        the slot's ``replica * rows + slot`` and ``count`` the engine's
        admission count.  A count of 0 is no admission (another replica's
        copy of the program, a sealed prefix, a verify chunk): that key
        lands in the trash row."""
        base = jax.random.PRNGKey(self.scfg.seed)
        new = jax.vmap(lambda i, c: jax.random.key_data(
            jax.random.fold_in(jax.random.fold_in(base, i), c)))(key_id,
                                                                  count)
        trash = keys.shape[0] - 1
        return keys.at[jnp.where(count > 0, rows, trash)].set(new)

    @property
    def _use_prefix(self) -> bool:
        return self.scfg.prefix_pages > 0

    @jax.named_scope("readout")
    def _next_token(self, logits, keys):
        """Greedy argmax, or the fused temperature/top-p sampler.

        ``logits``: ``[S, V]``; ``keys``: ``[S, 2]`` raw per-lane PRNG
        keys, split once per sampled token so the stream is deterministic
        in (seed, lane history).  top-p keeps the smallest
        probability-sorted set covering ``top_p`` mass (always >= 1
        token) and renormalizes inside ``categorical``.
        """
        scfg = self.scfg
        if scfg.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1), keys

        def one(lg, key):
            k_use, k_next = jax.random.split(key)
            lg = lg / scfg.temperature
            if scfg.top_p < 1.0:
                srt = jnp.sort(lg)[::-1]
                probs = jax.nn.softmax(srt)
                keep = (jnp.cumsum(probs) - probs) < scfg.top_p
                thresh = jnp.min(jnp.where(keep, srt, jnp.inf))
                lg = jnp.where(lg >= thresh, lg, -jnp.inf)
            return jax.random.categorical(k_use, lg), k_next

        return jax.vmap(one)(logits, keys)

    def _ffn(self, *, chunk=False, draft=False):
        """The block's ``ffn`` hook (:func:`decoder.decoder_block`): normed
        post-attention activation ``[..., D]`` in, ``(y, routing)`` out.

        Dense models run the reference two-matmul gelu FFN.  MoE models
        route through the dropless grouped-GEMM path (top-k router →
        sort-based dispatch → grouped GEMM → combine) with the small
        decode tile on the hot path, the training tile for prefill/verify
        shapes (``chunk=True``).  ``draft=True`` is the spec-decode draft:
        the expert-MEAN dense FFN (one matmul pair at active-param cost,
        no dispatch) — causally safe because the verify chunk overwrites
        every drafted KV row and the accept rule only ever emits
        target-argmax tokens, so draft quality affects throughput, never
        the stream.  ``routing`` is the router's ``(probs, idx)`` on the
        routed path (for hot-expert accounting), else ``None``.
        """
        if not self._moe:
            return decoder.dense_ffn
        E = self.cfg.num_experts

        def mean_ffn(lp, h):
            hf = h.reshape(-1, h.shape[-1])
            w1d = lax.psum(jnp.sum(lp["w1e"], axis=0), "expert") / E
            w2d = lax.psum(jnp.sum(lp["w2e"], axis=0), "expert") / E
            y = lax.psum(jax.nn.gelu(hf @ w1d) @ w2d, "tp")
            return y.reshape(h.shape), None

        def routed_ffn(lp, h):
            hf = h.reshape(-1, h.shape[-1])
            logits, probs, idx, gate = router_topk(hf, lp["wr"],
                                                   top_k=self.cfg.top_k)
            y = moe_dropless_combine(
                hf, idx, gate, lp["w1e"], lp["w2e"], num_experts=E,
                axis="expert",
                tile=self._moe_chunk_tile if chunk else self._moe_tile)
            return y.reshape(h.shape), (probs, idx)

        return mean_ffn if draft else routed_ffn

    def _route_vec(self, routing, live):
        """Fold one layer's routing into the ``[E + 2]`` stats carrier:
        per-expert top-1 counts over live lanes, summed live-token router
        entropy, live-token count."""
        probs, idx = routing
        E = self.cfg.num_experts
        w = live.astype(jnp.float32)
        cnt = jnp.sum(jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32)
                      * w[:, None], axis=0)
        ent = jnp.sum(-jnp.sum(probs * jnp.log(probs + 1e-20), axis=-1) * w)
        return jnp.concatenate([cnt, ent[None], jnp.sum(w)[None]])

    def _layer_scan(self, one, blocks, x, cache):
        """Run a stage's layers with the cache as a **loop-carried**
        buffer: ``one(lp, x, cache, layer) -> (x, cache, new)`` gets the
        whole stacked cache dict plus its layer index and writes/reads it
        at ``[layer, ...]`` (:mod:`.kv_cache`); only the block weights
        are scanned over.  A scan that takes the cache as ``xs`` and
        returns it as ``ys`` slices every layer out and stacks the updated
        layers into a freshly allocated buffer — the carried form is what
        XLA updates in place, so with the donated argument the program's
        output cache IS its input cache.  ``new`` is what a layer leaves
        for after the loop (a decode token's pages, or None): returned
        stacked ``[layers, ...]`` as the scan's ``ys``."""
        def body(carry, xs):
            lp, layer = xs
            x, cache, new = one(lp, *carry, layer)
            return (x, cache), new
        layers = jax.tree.leaves(blocks)[0].shape[0]
        (x, cache), news = lax.scan(body, (x, cache),
                                    (blocks, jnp.arange(layers)))
        return x, cache, news

    def _layer_view(self, cache, layer):
        """One layer's pages as a tensor of their own — what the Pallas
        kernels take (their index maps address ``[row, head, block]``: the
        logical order, whatever order the pages are stored in)."""
        return {name: _kv.logical_pages(t[layer], self.cache_cfg.head_dim)
                if name in ("k", "v") else t[layer]
                for name, t in cache.items()}

    @property
    def _defer_appends(self) -> bool:
        """Whether a decode token's kv reaches the cache after the layer
        loop (one write per lane and tensor) instead of inside it (one
        per lane, tensor and layer).  The XLA attention takes the token
        beside the pages it reads (in place, or staged:
        :attr:`_read_in_place`); the flash-decode kernel streams its
        pages from HBM itself, so its token has to be written before it
        runs."""
        return self.scfg.decode_kernel != "pallas"

    @property
    def _read_in_place(self) -> bool:
        """Whether a decode step's attention meets a layer's pages where
        they lie, every row its own query (:func:`.kv_cache.attend_layer`,
        ``latent_attend_slots``, ``attend_slots``), instead of staging
        each lane's row first.  A property of the cache alone: shared
        prefix pages have several lanes attend one row, a quantized
        store's scales are not folded into the by-row products, and a
        token already written (the flash-decode kernel's order) is not
        beside the pages; those keep :func:`.kv_cache.attend_rows`."""
        return (self._defer_appends and not self._use_prefix
                and self.scfg.kv_dtype == "raw")

    def _layer_step(self, lp, x, cache, layer, slot_ids, lens, prows, plens,
                    draft=False):
        """One decoder block on one new token per lane: ``x`` is ``[S, D]``,
        ``cache`` the stage's stacked cache dict, read at ``layer``.
        Returns ``(x, cache, new, routing)``: ``new`` is the token's
        pages, still to be written (:meth:`_defer_appends`), or None once
        the cache holds them."""
        def attend(q, k, v):                            # [S, H/tp, Dh]
            if self._defer_appends:
                c, new = cache, _kv.token_pages(k, v, self.scfg.kv_dtype,
                                                cache["k"].dtype)
            else:
                new = None
                c = _kv.layer_append(cache, layer, slot_ids, lens, k, v,
                                     store=self.scfg.kv_dtype)
            if self.scfg.decode_kernel == "pallas":
                cl = self._layer_view(c, layer)
                att = _pd.flash_attend_rows(
                    q, cl["k"], cl["v"], slot_ids, lens,
                    k_scale=cl.get("k_scale"), v_scale=cl.get("v_scale"),
                    prefix_slots=prows, prefix_lens=plens,
                    block_k=self.scfg.decode_block_k)
            elif self._read_in_place:
                att, _ = _kv.attend_layer(q, c["k"], c["v"], layer, slot_ids,
                                          lens, new)
            else:
                att = _kv.attend_rows(q, c["k"], c["v"], slot_ids, lens,
                                      k_scale=c.get("k_scale"),
                                      v_scale=c.get("v_scale"),
                                      prefix_slots=prows, prefix_lens=plens,
                                      layer=layer, new=new)
            return att, (c, new)

        x, (cache, new), routing = decoder.decoder_block(
            self.cfg, self.m.tp, lp, x, lens, attend, self._ffn(draft=draft))
        return x, cache, new, routing

    def _pp_cycle(self, blocks, x, cache, one, n_stages=None, land=None):
        """Cycle ``x`` through ``n_stages`` pipeline stages (all of them by
        default; the draft truncates); each stage's layer loop
        (:meth:`_layer_scan` of ``one``, the cache carried through it and
        updated in place) runs everywhere but only the
        owning stage keeps its activation and cache writes, so the program
        is identical on every device.  What the layers left for after the
        loop goes into the cache there, through ``land(cache, news)``,
        before the keep-select.  The keep-select over the cache is
        what makes only the owning stage's writes stick at ``pp > 1``
        (there it costs a pass over the cache per hop); on a ``stage`` axis
        of one chip ``sid == s`` is the constant true and the compiler
        folds the select away, so the carried buffer goes straight
        through.  After n hops the valid activation sits at stage
        ``n % pp`` (0 for the full cycle) — the caller reads logits there
        and ``psum`` broadcasts them."""
        n = self.m.pp if n_stages is None else n_stages
        sid = lax.axis_index("stage")
        perm = [(i, (i + 1) % self.m.pp) for i in range(self.m.pp)]
        for s in range(n):
            y, nc, news = self._layer_scan(one, blocks, x, cache)
            if news is not None:
                nc = land(nc, news)
            keep = sid == s
            # x may be a pytree carrier (activation + stats accumulator on
            # the MoE decode path) — keep/permute leafwise
            x = jax.tree.map(lambda new, old: jnp.where(keep, new, old),
                             y, x)
            cache = jax.tree.map(
                lambda new, old: jnp.where(keep, new, old), nc, cache)
            x = jax.tree.map(lambda t: lax.ppermute(t, "stage", perm), x)
        return x, cache, sid

    def _blocks_tree(self, params):
        """Per-layer scanned leaves: the dense block weights, plus the
        router/expert tables merged in on the MoE path (all leading-[Lps],
        so one ``lax.scan`` pairs every layer's leaves — the weights are
        the scan's only ``xs``; the cache rides its carry)."""
        if not self._moe:
            return params["blocks"]
        bp = dict(params["blocks"])
        bp["wr"] = params["router"]["wr"]
        bp["w1e"] = params["experts"]["w1"]
        bp["w2e"] = params["experts"]["w2"]
        return bp

    def _decode_scan(self, params, cache, toks, slot_ids, lens, prows,
                     plens, keys, *, steps, n_stages=None):
        """The shared fused-decode scan: ``steps`` tokens, optionally on a
        truncated (draft) stage cycle.  Returns ``(gen [steps, S], keys,
        cache, stats)`` — ``stats`` is the accumulated ``[E + 2]``
        hot-expert carrier on the routed MoE path (it rides the same
        keep/ppermute carrier as the activation, so each stage's layers
        fold in exactly once), else ``None``."""
        shared = params["shared"]
        embed = shared["embed"]
        bp = self._blocks_tree(params)
        draft = n_stages is not None
        out_stage = (self.m.pp if n_stages is None else n_stages) % self.m.pp
        track = self._moe and not draft
        live = slot_ids < self.scfg.slots                 # [S] real lanes

        def step(carry, _):
            toks, lens, cache, keys, st = carry

            if track:
                def one(lp, xc, c, layer):
                    x, acc = xc
                    x, c, new, routing = self._layer_step(
                        lp, x, c, layer, slot_ids, lens, prows, plens)
                    return (x, acc + self._route_vec(routing, live)), c, new
                with jax.named_scope("readout"):
                    x0 = (embed[toks], st)                    # [S, D] + [E+2]
            else:
                def one(lp, x, c, layer):
                    x, c, new, _ = self._layer_step(
                        lp, x, c, layer, slot_ids, lens, prows, plens,
                        draft=draft)
                    return x, c, new
                with jax.named_scope("readout"):
                    x0 = embed[toks]                          # [S, D]

            x, cache, sid = self._pp_cycle(
                bp, x0, cache, one, n_stages=n_stages,
                land=lambda c, new: _kv.append_tokens(c, slot_ids, lens,
                                                      new))
            if track:
                x, acc = x
                st = lax.psum(jnp.where(sid == out_stage, acc, 0.0),
                              "stage")
            with jax.named_scope("readout"):
                logits = lax.psum(
                    jnp.where(sid == out_stage, decoder.lm_logits(shared, x),
                              0.0), "stage")
                if n_stages is None:
                    nxt, keys = self._next_token(logits, keys)
                else:
                    nxt = jnp.argmax(logits, axis=-1)  # draft: greedy only
                nxt = nxt.astype(toks.dtype)
            return (nxt, lens + 1, cache, keys, st), nxt

        st0 = jnp.zeros((self.cfg.num_experts + 2,), jnp.float32) \
            if track else jnp.zeros((), jnp.float32)
        (_, _, cache, keys, st), gen = lax.scan(
            step, (toks, lens, cache, keys, st0), None, length=steps)
        return gen, keys, cache, (st if track else None)

    def _split_args(self, args):
        return jax.tree.map(lambda t: t[0], args)

    @staticmethod
    def _feed_body(lanes, gen):
        """A decode call's ``staged`` out of what the host sent for a call
        that runs ahead: ``lanes`` ``[1, S, 1 + 4 + 1]`` is the staged
        array with one more integer a lane, the lane of the call in flight
        that chooses this lane's token (negative: the host's token
        stands), and ``gen`` ``[1, steps, S']`` that call's tokens, which
        the host has not read yet."""
        src = lanes[0, :, -1]
        toks = jnp.where(src >= 0, gen[0, -1, jnp.maximum(src, 0)],
                         lanes[0, :, 0])
        return jnp.concatenate([toks[:, None], lanes[0, :, 1:-1]], -1)[None]

    def _decode_body(self, params, cache, keys, lanes):
        params, cache, keys, lanes = self._split_args(
            (params, cache, keys, lanes))
        toks, slot_ids, lens, prows, plens = self._unpack_lanes(lanes)
        gen, lane_keys, cache, st = self._decode_scan(
            params, cache, toks[:, 0], slot_ids, lens, prows, plens,
            keys[slot_ids], steps=self.scfg.decode_steps_per_call)
        with jax.named_scope("readout"):
            keys = keys.at[slot_ids].set(lane_keys)
        out = (gen, st, keys, cache) if self._moe else (gen, keys, cache)
        return jax.tree.map(lambda t: t[None], out)

    def _draft_body(self, params, cache, keys, lanes):
        """k greedy draft tokens on the truncated stage cycle.  The draft
        IS the target's own first ``spec_stages`` stages, so its
        early-layer cache appends equal what the verify pass will write
        over them — shared rows stay consistent by construction."""
        params, cache, keys, lanes = self._split_args(
            (params, cache, keys, lanes))
        toks, slot_ids, lens, prows, plens = self._unpack_lanes(lanes)
        gen, _, cache, _ = self._decode_scan(
            params, cache, toks[:, 0], slot_ids, lens, prows, plens,
            jnp.zeros(slot_ids.shape + (2,), jnp.uint32),  # greedy: unused
            steps=self.scfg.spec_decode, n_stages=self.draft.stages)
        return jax.tree.map(lambda t: t[None], (gen, keys, cache))

    def _chunk_body(self, params, cache, keys, lanes):
        """The k-token verify forward / chunked prefill: ``toks`` is
        ``[S, T]`` with token t of lane i at position ``lens[i] + t``.
        Appends all T kv rows then attends causally over the slot (and
        through the prefix indirection); emits the argmax at EVERY
        position ``[S, T]`` — for the verify these are the target tokens
        g_1..g_T, for a chunked prefill position ``true_len - 1`` is the
        request's first generated token, and the lane carries what its
        slot's sampler key is made from (:meth:`_seed_rows`)."""
        params, cache, keys, lanes = self._split_args(
            (params, cache, keys, lanes))
        toks, slot_ids, lens, prows, plens, key_id, count = \
            self._unpack_lanes(lanes, 6)
        keys = self._seed_rows(keys, slot_ids, key_id, count)
        S, T = toks.shape
        pos = lens[:, None] + jnp.arange(T)[None, :]          # [S, T]
        # chunk rows of live lanes all count toward the hot-expert stats
        # (a spec-verify chunk is all real positions; chunked-prefill pad
        # positions add bounded noise to the gauges, never to the math)
        live = jnp.broadcast_to((slot_ids < self.scfg.slots)[:, None],
                                (S, T)).reshape(S * T)

        ffn = self._ffn(chunk=True)

        def one(lp, xc, c, layer):
            x, acc = xc

            def attend(q, k, v):                        # [S, T, H/tp, Dh]
                nc = _kv.layer_append_chunk(c, layer, slot_ids, lens, k, v,
                                            store=self.scfg.kv_dtype)
                if self.scfg.decode_kernel == "pallas":
                    return _pd.flash_attend_chunk(
                        q, self._layer_view(nc, layer), slot_ids, lens,
                        prefix_slots=prows, prefix_lens=plens,
                        block_k=self.scfg.decode_block_k), nc
                return _kv.attend_chunk(q, nc, slot_ids, lens,
                                        prefix_slots=prows,
                                        prefix_lens=plens, layer=layer), nc

            x, c, routing = decoder.decoder_block(
                self.cfg, self.m.tp, lp, x, pos, attend, ffn)
            if self._moe:
                acc = acc + self._route_vec(routing, live)
            return (x, acc), c, None

        st0 = jnp.zeros((self.cfg.num_experts + 2,) if self._moe else (),
                        jnp.float32)
        with jax.named_scope("readout"):
            x = params["shared"]["embed"][toks]               # [S, T, D]
        (x, st), cache, sid = self._pp_cycle(
            self._blocks_tree(params), (x, st0), cache, one)
        with jax.named_scope("readout"):
            logits = lax.psum(
                jnp.where(sid == 0, decoder.lm_logits(params["shared"], x),
                          0.0), "stage")                      # [S, T, V]
            gen = jnp.argmax(logits, axis=-1).astype(toks.dtype)
        if self._moe:
            st = lax.psum(jnp.where(sid == 0, st, 0.0), "stage")
            return jax.tree.map(lambda t: t[None], (gen, st, keys, cache))
        return jax.tree.map(lambda t: t[None], (gen, keys, cache))

    def _unpack_prompt(self, keys, staged):
        """A prefill program's ``staged``: the padded prompt ``[Tpad]``,
        its row and true length, and what the row's sampler key is made
        from, which goes into the table here (:meth:`_seed_rows`)."""
        toks, slot_id, true_len, key_id, count = self._unpack(staged, 4)
        keys = self._seed_rows(keys, slot_id[None], key_id[None],
                               count[None])
        return keys, toks, slot_id, true_len

    def _prefill_body(self, params, cache, keys, staged):
        params, cache, keys, staged = self._split_args(
            (params, cache, keys, staged))
        keys, toks, slot_id, true_len = self._unpack_prompt(keys, staged)
        positions = jnp.arange(toks.shape[0])
        with jax.named_scope("readout"):
            x = params["shared"]["embed"][toks][None]         # [1, Tpad, D]
        ffn = self._ffn(chunk=True)

        def one(lp, x, c, layer):
            def attend(q, k, v):                        # [1, Tpad, H/tp, Dh]
                # the whole padded prompt lands in the slot; positions past
                # true_len hold garbage that decode's length mask never
                # reads before the append overwrites it.  Attention over
                # the prompt itself is dense full-precision — quantization
                # drift only enters where a STORED page is read back
                nc = _kv.layer_prefill(c, layer, slot_id, k[0], v[0],
                                       store=self.scfg.kv_dtype)
                with jax.named_scope("attn"):
                    return dense_attention(q, k, v, causal=True), nc

            x, c, _ = decoder.decoder_block(
                self.cfg, self.m.tp, lp, x, positions, attend, ffn)
            return x, c, None

        x, cache, sid = self._pp_cycle(self._blocks_tree(params), x, cache,
                                       one)
        with jax.named_scope("readout"):
            logits = jnp.where(sid == 0,
                               decoder.lm_logits(params["shared"], x[0]),
                               0.0)                           # [Tpad, V]
            logits = lax.psum(logits, "stage")
            last = lax.dynamic_slice_in_dim(logits, true_len - 1, 1,
                                            axis=0)[0]
            nxt = jnp.argmax(last, axis=-1).astype(toks.dtype)
        return jax.tree.map(lambda t: t[None], (nxt, last, keys, cache))

    # ------------------------------------------------------------------
    # the latent model's programs (one chip's share of each layer)
    # ------------------------------------------------------------------

    # the grouped kernel's buffers hold ``top_k`` rows a token: a prompt
    # whose rows pass this many bytes goes through an expert layer
    # :attr:`_PROMPT_FFN_CHUNK` tokens at a time (12 rows of 6,144 a token
    # are 604 MB a buffer at 4,096 tokens)
    _PROMPT_ROWS_BYTES = 320 << 20

    def _latent_ffn(self, live, experts=None):
        """An expert layer's ``ffn`` hook: the full-width router (sigmoid
        with group-limited selection, or softmax, by the configuration),
        the held experts' part, the identity experts' where the router has
        such outputs, the shared expert where the model has one, for every
        token.  ``live`` ``[tokens]`` marks the tokens that
        count: the rest (trash lanes, a prompt's padding) are routed to no
        expert and stay out of the carrier.  With ``experts``, the held
        experts' weights of ALL layers stacked as the tree holds them, the
        pairs go through the grouped kernel and ``lp["layer"]`` says which
        layer's groups are meant (a prompt whose rows pass
        :attr:`_PROMPT_ROWS_BYTES` in chunks of tokens); without, ``lp``
        holds the layer's own
        and every token goes through every held expert
        (:func:`~bluefog_tpu.moe.layers.held_moe_ffn`).  ``faux`` is the
        layer's ``[E + 4]`` carrier (:meth:`_note_route_stats`), and where
        the programs hand their selections out (:attr:`_hands_chosen`)
        ``(carrier, idx [tokens, top_k])``."""
        cfg = self.cfg
        held = self._held_mask()

        def ffn(lp, h):
            if experts is None:
                y, idx, weight = held_moe_ffn(cfg, lp, h, live)
            else:
                lp = {**lp, **experts}
                part = lambda hl: held_moe_ffn(cfg, lp, hl[0], hl[1],
                                               layer=lp["layer"])
                T, C = h.shape[0], self._PROMPT_FFN_CHUNK
                if T <= C or T % C or T * cfg.top_k * h.shape[1] \
                        * h.dtype.itemsize <= self._PROMPT_ROWS_BYTES:
                    y, idx, weight = part((h, live))
                else:
                    y, idx, weight = jax.tree.map(
                        lambda t: t.reshape((T,) + t.shape[2:]),
                        lax.map(part, (h.reshape(T // C, C, -1),
                                       live.reshape(T // C, C))))
            vec = self._carrier(idx, weight, live, held)
            return y, (vec, idx) if self._hands_chosen else vec

        return ffn

    def _held_mask(self):
        """``[E]`` float32: 1 at the router outputs this chip holds."""
        cfg = self.cfg
        held = jnp.arange(cfg.num_experts) - cfg.held_start
        return ((held >= 0) & (held < cfg.held_experts)).astype(jnp.float32)

    def _carrier(self, idx, weight, live, held):
        """One expert layer's ``[E + 4]`` carrier
        (:meth:`_note_route_stats`) from its routing ``idx`` / ``weight``
        ``[tokens, top_k]`` (-1 where a token is not ``live``)."""
        cfg = self.cfg
        cnt = jnp.sum(jax.nn.one_hot(idx, cfg.num_experts,
                                     dtype=jnp.float32), axis=(0, 1))
        p = weight / cfg.route_scale
        ent = jnp.sum(-jnp.sum(p * jnp.log(p + 1e-20), -1) * live)
        return jnp.concatenate([cnt, jnp.stack([
            ent, jnp.sum(live.astype(jnp.float32)),
            jnp.sum(cnt * held), jnp.sum((cnt > 0) * held)])])

    def _latent_layers(self, params, x, cache, positions, attend_with, live,
                       grouped):
        """The leading dense layers, then the scan over the expert layers,
        over ONE cache stacked over all of them.  ``x`` is the residual as
        the layers carry it (:func:`~bluefog_tpu.models.decoder.hc_fan_out`:
        the streams, stream axis major, where the model has several).
        ``attend_with(lp, cache, layer)`` builds a layer's ``attend`` hook;
        its ``aux`` is ``(cache, new)``: the cache as the layer leaves it
        and what it hands on for after the loop.  ``grouped`` is the held
        experts' form: a prompt's pairs through the grouped kernel, or
        (decode) every lane through every held expert
        (:meth:`_latent_ffn`).  Returns ``(x, cache, news [layers, ...] or
        None, carrier)`` and, where the programs hand their selections out
        (:attr:`_hands_chosen`), ``chosen [expert layers, tokens, top_k]``
        behind them.  A model of double layers goes through
        :meth:`_double_layers`."""
        cfg = self.cfg
        names = ("weg", "weu", "wed") if grouped else ()
        ffn = self._latent_ffn(
            live, {k: params["blocks"][k] for k in names} or None)
        blocks = {k: v for k, v in params["blocks"].items()
                  if k not in names}
        blocks["layer"] = jnp.arange(cfg.expert_layers)
        if cfg.shortcut:
            return self._double_layers(params, blocks, x, cache, positions,
                                       attend_with, ffn)
        x, (cache, new0), _ = decoder.latent_block(
            cfg, params["first"], x, positions,
            attend_with(params["first"], cache, 0), decoder.dense_gated_ffn)
        news0 = [new0]                  # the leading layers' tokens
        for i in range(1, cfg.dense_layers):
            lp = jax.tree.map(lambda a: a[i - 1], params["dense"])
            x, (cache, new), _ = decoder.latent_block(
                cfg, lp, x, positions, attend_with(lp, cache, i),
                decoder.dense_gated_ffn)
            news0.append(new)

        def body(carry, lp):
            x, cache, acc = carry
            x, (cache, new), faux = decoder.latent_block(
                cfg, lp, x, positions,
                attend_with(lp, cache, lp["layer"] + cfg.dense_layers), ffn)
            vec, *idx = faux if self._hands_chosen else (faux,)
            return (x, cache, acc + vec), (new, *idx)

        (x, cache, acc), (news, *chosen) = lax.scan(
            body, (x, cache, jnp.zeros((cfg.num_experts + 4,), jnp.float32)),
            blocks)
        if new0 is not None:
            news = jnp.concatenate([new[None] for new in news0] + [news])
        return (x, cache, news, acc, *chosen)

    def _double_layers(self, params, blocks, x, cache, positions,
                       attend_with, ffn):
        """:meth:`_latent_layers` for a model of shortcut-connected double
        layers (:func:`~bluefog_tpu.models.decoder.latent_double_block`):
        no leading layer, the scan over the double layers, each half with
        a cache layer of its own (``2 l`` and ``2 l + 1``), the expert
        layer's result handed from the first half's ``ffn`` hook to the
        second's.  Returns ``(x, cache, news [attention sublayers, ...] or
        None, carrier)`` and, where the programs hand their selections
        out, ``chosen [layers, tokens, top_k]``."""
        cfg = self.cfg

        def body(carry, lps):
            x, cache, acc = carry
            lp, lp2 = lps
            at = 2 * lp["layer"]
            # the second half's hook meets the cache as the first left it
            x, ((_, new), (cache, new2)), faux = decoder.latent_double_block(
                cfg, lp, lp2, x, positions, attend_with(lp, cache, at),
                lambda aux: attend_with(lp2, aux[0], at + 1), ffn)
            vec, *idx = faux if self._hands_chosen else (faux,)
            news = None if new is None else jnp.stack([new, new2])
            return (x, cache, acc + vec), (news, *idx)

        (x, cache, acc), (news, *chosen) = lax.scan(
            body, (x, cache, jnp.zeros((cfg.num_experts + 4,), jnp.float32)),
            (blocks, params["blocks2"]))
        if news is not None:
            news = news.reshape((-1,) + news.shape[2:])
        return (x, cache, news, acc, *chosen)

    @property
    def _hands_chosen(self) -> bool:
        """Whether the programs hand out, beside their tokens, the experts
        every expert layer chose and the decode steps' logits
        (:meth:`decode_chosen`, :meth:`prefill_chosen`,
        :meth:`decode_logits`): the single-mixer family's, and a latent
        model's whose router cuts its softmax scores where they lie a few
        hundredths of their size apart, so that a comparison with a
        reference can be made under the program's own selections."""
        return self._ssm or (self._latent and self.cfg.router == "softmax")

    def _latent_decode_body(self, params, cache, keys, lanes):
        """Fused decode in the absorbed form: every layer attends over its
        lanes' cached vectors plus the token's own, and the tokens of all
        layers land in the cache once per lane after the loop.  Where the
        programs hand their selections out (:attr:`_hands_chosen`) the
        experts each layer chose and the steps' logits stand between the
        carrier and the key table, as in :meth:`_ssm_decode_body`."""
        params, cache, table, lanes = self._split_args(
            (params, cache, keys, lanes))
        toks, slot_ids, lens, _, _ = self._unpack_lanes(lanes)
        with jax.named_scope("readout"):
            toks, keys = toks[:, 0], table[slot_ids]
        cfg, shared = self.cfg, params["shared"]
        live = slot_ids < self.scfg.slots

        def step(carry, _):
            toks, lens, cache, keys, st = carry

            # the layers only read the cache: it stays outside their loop's
            # carry, and the loop hands on the tokens' vectors alone
            def attend_with(lp, _, layer):
                def attend(q_nope, q_rope, latent):         # [S, H, .]
                    with jax.named_scope("mla.attend"):
                        u, _ = _kv.latent_attend_slots(
                            decoder.mla_absorb_q(cfg, lp, q_nope), q_rope,
                            cache, layer, slot_ids, lens, latent,
                            cfg.softmax_scale,
                            stage=None if self._read_in_place else True)
                        return decoder.mla_unabsorb_out(cfg, lp, u), \
                            (None, latent)
                return attend

            with jax.named_scope("readout"):
                x = shared["embed"][toks]
            x, _, news, acc, *chosen = self._latent_layers(
                params, decoder.hc_fan_out(cfg, x), None, lens, attend_with,
                live, grouped=False)
            cache = _kv.latent_append_tokens(cache, slot_ids, lens, news)
            logits = decoder.latent_logits(
                cfg, shared, decoder.hc_collapse(cfg, x))
            nxt, keys = self._next_token(logits, keys)
            nxt = nxt.astype(toks.dtype)
            out = (nxt, chosen[0], logits.astype(jnp.float32)) if chosen \
                else nxt
            return (nxt, lens + 1, cache, keys, st + acc), out

        st0 = jnp.zeros((cfg.num_experts + 4,), jnp.float32)
        (_, _, cache, keys, st), gen = lax.scan(
            step, (toks, lens, cache, keys, st0), None,
            length=self.scfg.decode_steps_per_call)
        with jax.named_scope("readout"):
            table = table.at[slot_ids].set(keys)
        gen, *handed = gen if self._hands_chosen else (gen,)
        return jax.tree.map(lambda t: t[None],
                            (gen, st, *handed, table, cache))

    def _latent_prefill_body(self, params, cache, keys, staged):
        """One padded prompt in the unabsorbed form; every layer's vectors
        land in the slot as the layer runs.  Padding is routed to no
        expert, and only the last real position is read out.  Beside them
        the experts each layer chose, where the programs hand them out
        (:attr:`_hands_chosen`, :meth:`prefill_chosen`)."""
        params, cache, keys, staged = self._split_args(
            (params, cache, keys, staged))
        keys, toks, slot_id, true_len = self._unpack_prompt(keys, staged)
        cfg, shared = self.cfg, params["shared"]
        positions = jnp.arange(toks.shape[0])

        def attend_with(lp, cache, layer):
            def attend(q_nope, q_rope, latent):             # [Tpad, H, .]
                nc = _kv.latent_prefill(cache, layer, slot_id, latent)
                return decoder.mla_unabsorbed(cfg, lp, q_nope, q_rope,
                                              latent), (nc, None)
            return attend

        with jax.named_scope("readout"):
            x = shared["embed"][toks]
        x, cache, _, _, *chosen = self._latent_layers(
            params, decoder.hc_fan_out(cfg, x), cache, positions, attend_with,
            positions < true_len, grouped=True)
        with jax.named_scope("readout"):
            x = lax.dynamic_slice_in_dim(x, true_len - 1, 1, axis=-2)
        x = decoder.hc_collapse(cfg, x)
        with jax.named_scope("readout"):
            last = decoder.latent_logits(cfg, shared, x[0]).astype(
                jnp.float32)
            nxt = jnp.argmax(last, axis=-1).astype(toks.dtype)
        return jax.tree.map(lambda t: t[None],
                            (nxt, last, *chosen, keys, cache))

    # ------------------------------------------------------------------
    # the hybrid model's programs (window and full layers, one chip's share)
    # ------------------------------------------------------------------

    # a prompt's expert layers run over this many tokens at a time: the
    # grouped kernel's buffers hold top_k rows a token, 0.8 GB at 8,192
    _PROMPT_FFN_CHUNK = 2048

    def _hybrid_ffn(self, ffn_kind, live, grouped):
        """A hybrid layer's ``ffn`` hook by the plan's ``ffn_kind``: the
        dense gated FFN, or the held-experts layer with its carrier
        (:meth:`_latent_ffn`).  ``grouped`` (a prompt): the pairs through
        the grouped kernel, :attr:`_PROMPT_FFN_CHUNK` tokens at a time,
        and no carrier (nothing reads a prompt's)."""
        if ffn_kind == "dense":
            return lambda lp, h: (decoder.dense_gated_ffn(lp, h)[0], 0.0)
        if not grouped:
            return self._latent_ffn(live)
        cfg = self.cfg

        def ffn(lp, h):
            stack = {**lp, **{k: lp[k][None] for k in ("weg", "weu", "wed")}}
            part = lambda hl: held_moe_ffn(cfg, stack, hl[0], hl[1],
                                           layer=jnp.int32(0))[0]
            T, C = h.shape[0], self._PROMPT_FFN_CHUNK
            if T <= C or T % C:
                return part((h, live)), 0.0
            y = lax.map(part, (h.reshape(T // C, C, -1),
                               live.reshape(T // C, C)))
            return y.reshape(h.shape), 0.0
        return ffn

    def _hybrid_layers(self, params, x, cache, positions, attend_with, live,
                       grouped):
        """The plan's layers, unrolled (they differ in kind and
        feed-forward on no common period), one cache tree carried
        through: ``attend_with(kind, index, cache)`` builds the ``attend``
        hook of the ``index``-th layer of its kind, whose ``aux`` is
        ``(cache, new)`` as in :meth:`_latent_layers`.  Returns ``(x,
        cache, news, carrier)``, ``news`` each layer's ``new`` in plan
        order."""
        cfg = self.cfg
        news, acc = [], jnp.zeros((cfg.num_experts + 4,), jnp.float32)
        for i, (kind, ffn_kind) in enumerate(cfg.plan):
            x, (cache, new), vec = decoder.hybrid_block(
                cfg, params["layers"][i], x, positions, kind,
                attend_with(kind, cfg.index_in_kind(i), cache),
                self._hybrid_ffn(ffn_kind, live, grouped))
            news.append(new)
            acc = acc + vec
        return x, cache, news, acc

    def _hybrid_decode_body(self, params, cache, keys, lanes):
        """Fused decode over the two-kind cache: a full layer attends over
        its lanes' rows, a window layer over their rings, each plus the
        token's own K and V; the tokens of all layers land once per lane
        and tensor after the loop (a ring's at ``length mod window``).
        Beside the tokens it hands out every fused step's logits (left on
        the device: :meth:`decode_logits`) and, behind the routing
        carrier, the cache positions its attention met, by kind."""
        params, cache, table, lanes = self._split_args(
            (params, cache, keys, lanes))
        toks, slot_ids, lens, _, _ = self._unpack_lanes(lanes)
        with jax.named_scope("readout"):
            toks, keys = toks[:, 0], table[slot_ids]
        cfg, shared = self.cfg, params["shared"]
        live = slot_ids < self.scfg.slots

        def step(carry, _):
            toks, lens, cache, keys, st = carry
            met = dict.fromkeys(_kv.KIND_TENSORS, 0)

            # the layers only read the cache: it stays out of their carry
            def attend_with(kind, index, _):
                kn, vn = _kv.KIND_TENSORS[kind]

                def attend(q, k, v):            # [S, H, Dh], [S, Hkv, Dh]
                    new = _kv.token_pages(k, v, "raw", cache[kn].dtype)
                    out, read = _kv.attend_slots(
                        q, cache[kn][index], cache[vn][index], slot_ids,
                        lens, new, ring=kind == "window")
                    met[kind] += read
                    return out, (None, new)
                return attend

            with jax.named_scope("readout"):
                x = shared["embed"][toks]
            x, _, news, acc = self._hybrid_layers(
                params, x, None, lens, attend_with, live,
                grouped=False)
            stacked = {}
            for kind, names in _kv.KIND_TENSORS.items():
                mine = [n for n, (k, _) in zip(news, cfg.plan) if k == kind]
                for name, part in zip(names, ("k", "v")):
                    stacked[name] = jnp.stack([n[part] for n in mine])
            cache = _kv.hybrid_append_tokens(cache, slot_ids, lens, stacked)
            logits = decoder.latent_logits(cfg, shared, x)
            nxt, keys = self._next_token(logits, keys)
            nxt = nxt.astype(toks.dtype)
            acc = jnp.concatenate([acc, jnp.array(
                [met[kind] for kind in _kv.KIND_TENSORS], jnp.float32)])
            return (nxt, lens + 1, cache, keys, st + acc), (
                nxt, logits.astype(jnp.float32))

        st0 = jnp.zeros((cfg.num_experts + 4 + len(_kv.KIND_TENSORS),),
                        jnp.float32)
        (_, _, cache, keys, st), (gen, logits) = lax.scan(
            step, (toks, lens, cache, keys, st0), None,
            length=self.scfg.decode_steps_per_call)
        with jax.named_scope("readout"):
            table = table.at[slot_ids].set(keys)
        return jax.tree.map(lambda t: t[None],
                            (gen, st, logits, table, cache))

    # keys one call of the flash forward kernel takes: a head's K and V
    # lie whole in VMEM there (16,384 keys of 128 channels are the kernel's
    # whole scoped limit before a score is held)
    _FLASH_KEY_BLOCK = 8192

    @classmethod
    def _key_blocks(cls, Tpad: int) -> int:
        """Key blocks a full layer's attention over a prompt padded to
        ``Tpad`` takes (:meth:`_flash_causal`)."""
        return -(-Tpad // cls._FLASH_KEY_BLOCK)

    @classmethod
    def _flash_causal(cls, q, k, v):
        """Causal attention of one whole prompt on a full layer, ``q``
        ``[T, H, Dh]`` on compact ``k``/``v`` ``[T, Hkv, Dh]``: the flash
        forward kernel (K and V of a head whole in VMEM, queries in
        blocks, scores never in HBM).  At 8,192 positions of 64 heads on a
        v5e it takes 21.5 ms where XLA's blocked form took 58.7; under a
        window of 128 the kernel still meets every key (21.4 ms) and the
        band of :func:`decoder.window_attention` takes 2.9 (PERF.md §6,
        PR 35), so window layers do not come here.  A prompt past
        ``_FLASH_KEY_BLOCK`` positions meets its keys a block at a time:
        a block of queries meets every key block up to its own (causal
        inside that one, whole for the earlier ones), and its partials
        ``(o, l, m)`` over them are merged under their common maximum."""
        T, B = q.shape[0], cls._FLASH_KEY_BLOCK
        part = functools.partial(_pa.attention_block_partial, causal=True,
                                 scale=q.shape[-1] ** -0.5)
        if T <= B:
            o, l, _ = part(q[None], k[None], v[None], jnp.int32(0),
                           jnp.int32(0))
            return (o / l[..., None])[0].astype(q.dtype)
        outs = []
        for qa in range(0, T, B):           # a block of queries at a time
            o = l = m = None
            for ka in range(0, qa + 1, B):
                ob, lb, mb = part(q[None, qa:qa + B], k[None, ka:ka + B],
                                  v[None, ka:ka + B], jnp.int32(qa),
                                  jnp.int32(ka))
                if o is None:
                    o, l, m = ob, lb, mb
                    continue
                top = jnp.maximum(m, mb)    # finite: a query meets itself
                was, now = jnp.exp(m - top), jnp.exp(mb - top)
                o = o * was[..., None] + ob * now[..., None]
                l, m = l * was + lb * now, top
            outs.append((o / l[..., None])[0].astype(q.dtype))
        return jnp.concatenate(outs)

    def _hybrid_prefill_body(self, params, cache, keys, staged):
        """One padded prompt, its attention blocked (the flash forward
        kernel on a full layer, the band on a window layer); a full
        layer's K and V land in the slot's row, a window layer's last
        ``window`` real positions in its ring.  Padding is routed to no
        expert, and only the last real position is read out."""
        params, cache, keys, staged = self._split_args(
            (params, cache, keys, staged))
        keys, toks, slot_id, true_len = self._unpack_prompt(keys, staged)
        cfg, shared = self.cfg, params["shared"]
        positions = jnp.arange(toks.shape[0])

        def attend_with(kind, index, cache):
            def attend(q, k, v):            # [Tpad, H, Dh], [Tpad, Hkv, Dh]
                nc = _kv.hybrid_prefill(cache, kind, index, slot_id, k, v,
                                        true_len)
                att = decoder.window_attention(q, k, v, cfg.window) \
                    if kind == "window" else self._flash_causal(q, k, v)
                return att, (nc, None)
            return attend

        with jax.named_scope("readout"):
            x = shared["embed"][toks]
        x, cache, _, _ = self._hybrid_layers(
            params, x, cache, positions, attend_with,
            positions < true_len, grouped=True)
        with jax.named_scope("readout"):
            last = decoder.latent_logits(
                cfg, shared, lax.dynamic_slice_in_dim(x, true_len - 1, 1)[0]
            ).astype(jnp.float32)
            nxt = jnp.argmax(last, axis=-1).astype(toks.dtype)
        return jax.tree.map(lambda t: t[None], (nxt, last, keys, cache))

    # ------------------------------------------------------------------
    # the single-mixer model's programs (one mixer a layer, by the plan
    # recurrent, attention or experts; one chip's share)
    # ------------------------------------------------------------------

    def _ssm_ffn(self, live, grouped):
        """An expert layer's mixer: the full-width router under its
        selection bias, the held experts in the configuration's form
        (``relu^2`` or gated SiLU; in a latent where it has one), the
        shared expert (:func:`~bluefog_tpu.moe.layers.held_moe_ffn`).
        ``grouped``: the pairs through the grouped kernel (a prompt, past
        :attr:`_PROMPT_FFN_CHUNK` tokens that many at a time), else every
        token through every held expert (a decode step).  ``aux`` is
        ``(carrier [E + 4], chosen [tokens, top_k])``."""
        cfg = self.cfg
        held = self._held_mask()
        stacked = ("we1", "we2") if cfg.expert_form == "relu2" \
            else ("weg", "weu", "wed")

        def ffn(lp, h):
            if grouped:
                lp = {**lp, **{k: lp[k][None] for k in stacked}}
            part = lambda hl: held_moe_ffn(
                cfg, lp, hl[0], hl[1], jnp.int32(0) if grouped else None,
                form=cfg.expert_form)
            T, C = h.shape[0], self._PROMPT_FFN_CHUNK
            if not grouped or T <= C or T % C:
                y, idx, weight = part((h, live))
            else:
                y, idx, weight = jax.tree.map(
                    lambda t: t.reshape((T,) + t.shape[2:]),
                    lax.map(part, (h.reshape(T // C, C, -1),
                                   live.reshape(T // C, C))))
            return y, (self._carrier(idx, weight, live, held), idx)
        return ffn

    @staticmethod
    def _experts_mixer(ffn, lp, cache):
        """An expert layer's ``mix`` hook (:meth:`_ssm_layers`): it leaves
        the cache as it is and hands on its carrier and selections."""
        def mix(h):
            y, (vec, idx) = ffn(lp, h)
            return y, (cache, None, vec, idx)
        return mix

    def _ssm_layers(self, params, x, cache, mixer_of):
        """The plan's layers, unrolled, one cache tree carried through:
        ``mixer_of(kind, index, lp, cache)`` builds the ``mix`` hook of the
        ``index``-th layer of its kind (:func:`decoder.mixer_block`), whose
        ``aux`` is ``(cache, new, carrier, chosen)``: the cache as the
        layer leaves it, what an attention layer hands on for after the
        loop, and an expert layer's carrier and selections.  Returns ``(x,
        cache, news, carrier, chosen [expert layers, tokens, top_k])``."""
        cfg = self.cfg
        news, chosen = [], []
        acc = jnp.zeros((cfg.num_experts + 4,), jnp.float32)
        for i, kind in enumerate(cfg.plan):
            lp = params["layers"][i]
            x, (cache, new, vec, idx) = decoder.mixer_block(
                cfg, lp, x, kind,
                mixer_of(kind, cfg.index_in_kind(i), lp, cache))
            if kind == "full":
                news.append(new)
            elif kind == "experts":
                acc = acc + vec
                chosen.append(idx)
        return x, cache, news, acc, jnp.stack(chosen)

    def _ssm_decode_body(self, params, cache, keys, lanes):
        """Fused decode over the state cache: a recurrent layer (a Mamba
        mixer or a delta-rule mixer, by the plan) moves every row's state
        on by its lane's token where the state lies (its convolution's kept
        inputs too), an attention layer attends over its
        lanes' rows plus the token's own K and V, which land once per lane
        and tensor after the loop.  Beside the tokens it hands out every
        fused step's logits and the experts each expert layer chose (left
        on the device: :meth:`decode_logits`, :meth:`decode_chosen`) and,
        behind the routing carrier, the cache positions its attention
        met."""
        whole = cache["ssm"]
        params, cache, table, lanes = self._split_args(
            (params, cache, keys, lanes))
        # the states keep the argument's own leading axis: read through a
        # squeeze, the first layer's states come from the argument and
        # land in a copy of all of them (3.4 GB of temporaries)
        cache["ssm"] = whole
        toks, slot_ids, lens, _, _ = self._unpack_lanes(lanes)
        with jax.named_scope("readout"):
            toks, keys = toks[:, 0], table[slot_ids]
        cfg, shared = self.cfg, params["shared"]
        live = slot_ids < self.scfg.slots
        ffn = self._ssm_ffn(live, grouped=False)

        def step(carry, _):
            toks, lens, cache, keys, st = carry
            met = [0]

            def mixer_of(kind, index, lp, cache):
                if kind == "experts":
                    return self._experts_mixer(ffn, lp, cache)
                if kind == "full":
                    def attention(h):
                        q, k, v = decoder.gqa_project(cfg, lp, h)
                        new = _kv.token_pages(k, v, "raw", cache["k"].dtype)
                        out, read = _kv.attend_slots(
                            q, cache["k"][index], cache["v"][index],
                            slot_ids, lens, new)
                        met[0] += read
                        return decoder.gqa_out(cfg, lp, out, h), (
                            cache, new, None, None)
                    return attention
                conv = lambda x, prev: decoder.mamba_conv(cfg, lp, x, prev)

                def delta(h):
                    qkv, f, b, z = decoder.delta_project(cfg, lp, h)
                    qkv, nc = _kv.ssm_conv_step(cache, index, slot_ids, qkv,
                                                conv)
                    with jax.named_scope("ssm.scan"):
                        g, beta = decoder.delta_discretize(cfg, lp, f, b)
                    o, nc = _kv.ssm_state_step(
                        nc, index, slot_ids, decoder.delta_step, g, beta,
                        *decoder.delta_split(cfg, qkv))
                    return decoder.delta_gate_out(cfg, lp, o, z), (
                        nc, None, None, None)
                if kind == "delta":
                    return delta

                def mamba(h):
                    z, xbc, dt = decoder.mamba_project(cfg, lp, h)
                    xbc, nc = _kv.ssm_conv_step(
                        cache, index, slot_ids, xbc,
                        lambda xbc, prev: decoder.mamba_conv(cfg, lp, xbc,
                                                             prev))
                    x, B, C = decoder.mamba_split(cfg, xbc)
                    with jax.named_scope("ssm.scan"):
                        log_a, dx = decoder.mamba_discretize(lp, x, dt)
                    y, nc = _kv.ssm_state_step(
                        nc, index, slot_ids,
                        lambda *a: decoder.mamba_step(cfg, *a), log_a, dx, B,
                        C)
                    with jax.named_scope("ssm.scan"):
                        y = y + lp["Dskip"].astype(jnp.float32)[:, None] \
                            * x.astype(jnp.float32)
                    return decoder.mamba_gate_out(cfg, lp, y, z), (
                        nc, None, None, None)
                return mamba

            with jax.named_scope("readout"):
                x = shared["embed"][toks]
            x, cache, news, acc, chosen = self._ssm_layers(
                params, x, cache, mixer_of)
            cache = _kv.ssm_append_tokens(cache, slot_ids, lens, {
                part: jnp.stack([n[part] for n in news])
                for part in ("k", "v")})
            logits = decoder.latent_logits(cfg, shared, x)
            nxt, keys = self._next_token(logits, keys)
            nxt = nxt.astype(toks.dtype)
            acc = jnp.concatenate([acc, jnp.array(met, jnp.float32)])
            return (nxt, lens + 1, cache, keys, st + acc), (
                nxt, chosen, logits.astype(jnp.float32))

        st0 = jnp.zeros((cfg.num_experts + 5,), jnp.float32)
        (_, _, cache, keys, st), (gen, chosen, logits) = lax.scan(
            step, (toks, lens, cache, keys, st0), None,
            length=self.scfg.decode_steps_per_call)
        with jax.named_scope("readout"):
            table = table.at[slot_ids].set(keys)
        whole = cache.pop("ssm")
        out = jax.tree.map(lambda t: t[None],
                           (gen, st, chosen, logits, table, cache))
        out[-1]["ssm"] = whole
        return out

    def _ssm_prefill_body(self, params, cache, keys, staged):
        """One padded prompt: a recurrent layer (a Mamba mixer or a
        delta-rule mixer, by the plan) scans it in chunks and leaves in the
        slot the state after its last REAL token and the convolution inputs
        before ``true_len``, both overwritten whole; an attention layer's K
        and V land in the slot's row under the flash forward kernel (a
        prompt past one block of keys in several, :meth:`_flash_causal`).
        Padding is routed to no expert, and only the last
        real position is read out; beside its logits the program hands
        out the experts each expert layer chose (:meth:`prefill_chosen`)."""
        params, cache, keys, staged = self._split_args(
            (params, cache, keys, staged))
        keys, toks, slot_id, true_len = self._unpack_prompt(keys, staged)
        cfg, shared = self.cfg, params["shared"]
        ffn = self._ssm_ffn(jnp.arange(toks.shape[0]) < true_len,
                            grouped=True)

        def mixer_of(kind, index, lp, cache):
            if kind == "experts":
                return self._experts_mixer(ffn, lp, cache)
            if kind == "full":
                def attention(h):
                    q, k, v = decoder.gqa_project(cfg, lp, h)
                    nc = _kv.hybrid_prefill(cache, "full", index, slot_id,
                                            k, v, true_len)
                    att = self._flash_causal(q, k, v)
                    return decoder.gqa_out(cfg, lp, att, h), (
                        nc, None, None, None)
                return attention

            def delta(h):
                qkv, f, b, z = decoder.delta_project(cfg, lp, h)
                o, state, kept = decoder.delta_scan_chunked(
                    cfg, lp, qkv, f, b, true_len)
                nc = _kv.ssm_prefill(cache, index, slot_id, state, kept)
                return decoder.delta_gate_out(cfg, lp, o, z), (
                    nc, None, None, None)
            if kind == "delta":
                return delta

            def mamba(h):
                z, xbc, dt = decoder.mamba_project(cfg, lp, h)
                xbc, kept = decoder.mamba_conv(cfg, lp, xbc,
                                               true_len=true_len)
                y, state = decoder.mamba_scan_chunked(
                    cfg, lp, *decoder.mamba_split(cfg, xbc), dt, true_len)
                nc = _kv.ssm_prefill(cache, index, slot_id, state, kept)
                return decoder.mamba_gate_out(cfg, lp, y, z), (
                    nc, None, None, None)
            return mamba

        with jax.named_scope("readout"):
            x = shared["embed"][toks]
        x, cache, _, _, chosen = self._ssm_layers(params, x, cache, mixer_of)
        with jax.named_scope("readout"):
            last = decoder.latent_logits(
                cfg, shared, lax.dynamic_slice_in_dim(x, true_len - 1, 1)[0]
            ).astype(jnp.float32)
            nxt = jnp.argmax(last, axis=-1).astype(toks.dtype)
        return jax.tree.map(lambda t: t[None],
                            (nxt, last, chosen, keys, cache))

    def _count_held_work(self, lanes: int, lens, slots) -> None:
        """After a latent decode call: the routing carrier's held-expert
        counts into the fleet's counters, and a ``bf:engine.held_work``
        mark in the trace (directly under ``decode_call``, once ``collect``
        has closed) carrying them for this call beside the lanes'
        live cache ``positions`` (the benchmark's expert-layer and roofline
        metrics read its attributes); of a model with identity experts
        also ``zero_pairs`` and ``token_layers``."""
        cfg, scfg = self.cfg, self.scfg
        E = cfg.num_experts
        pairs = int(self._route_stats[:, E + 2].sum())
        hit = int(self._route_stats[:, E + 3].sum())
        rows = (self.m.dp * lanes * cfg.held_experts * cfg.expert_layers
                * scfg.decode_steps_per_call)
        _metrics.counter(
            "bluefog_serve_moe_held_pairs_total",
            "token-expert pairs of live decode lanes that fell on experts "
            "this chip holds").inc(pairs)
        _metrics.counter(
            "bluefog_serve_moe_rows_total",
            "rows the held experts' matmuls of decode calls compute (every "
            "lane through every held expert; x expert layers x fused "
            "steps)").inc(rows)
        _metrics.counter(
            "bluefog_serve_moe_decode_calls_total",
            "decode calls of a held-experts model").inc()
        live = slots < scfg.slots
        seen = np.asarray(lens)[live] + 1       # a lane's live positions
        attrs = dict(pairs=pairs, rows=rows, experts_hit=hit,
                     positions=int(seen.sum()))
        zero = getattr(cfg, "zero_experts", 0)
        if zero:
            # the live pairs that fell on identity outputs (the carrier's
            # counts of the router's last outputs, summed here) beside the
            # live (token, expert layer) pairs they are a share of
            attrs["zero_pairs"] = int(self._route_stats[:, E - zero:E].sum())
            attrs["token_layers"] = int(self._route_stats[:, E + 1].sum())
            _metrics.counter(
                "bluefog_serve_moe_zero_pairs_total",
                "token-expert pairs of live decode lanes that fell on "
                "identity experts, which compute nothing").inc(
                    attrs["zero_pairs"])
        if self._ssm:
            # the live lanes' states, which every recurrent layer reads
            # and writes whole in every fused step; and the positions the
            # attention layers' contraction met, as the hybrid family's
            attrs["state_lanes"] = int(live.sum())
            _metrics.counter(
                "bluefog_serve_state_updates_total",
                "recurrent states decode calls read and wrote (live lanes "
                "x recurrent layers x fused steps)").inc(
                    attrs["state_lanes"] * self.cache_cfg.ssm_layers
                    * scfg.decode_steps_per_call)
            met = int(self._route_stats[:, E + 4].sum())
            attrs["positions_read_full"] = met // (
                cfg.layers_of("full") * scfg.decode_steps_per_call)
            self._count_positions(met, "full")
        if self._hybrid:
            # what a window layer may see of the lanes' positions, and
            # what the program's attention met of either kind (the
            # carrier's last entries, summed over that kind's layers and
            # the fused steps: here per layer and step)
            attrs["positions_window"] = int(
                np.minimum(seen, cfg.window).sum())
            for i, kind in enumerate(_kv.KIND_TENSORS):
                met = int(self._route_stats[:, E + 4 + i].sum())
                attrs[f"positions_read_{kind}"] = met // (
                    cfg.layers_of(kind) * scfg.decode_steps_per_call)
                self._count_positions(met, kind)
        with self._stage("held_work", **attrs):
            pass

    def _read_form(self, lanes: int) -> str:
        """How a decode program of ``lanes`` lanes meets the cache:
        ``"in_place"`` or ``"staged"`` (:attr:`_read_in_place`, and a
        bucket under a third of the rows stages its lanes' rows)."""
        return "in_place" if self._read_in_place and _kv.read_in_place(
            lanes, self.cache_cfg.rows) else "staged"

    def _count_positions(self, met: int, kind: str) -> None:
        _metrics.counter(
            "bluefog_serve_cache_positions_read_total",
            "cache positions the decode programs' attention met, by kind "
            "of layer (full, window, latent; summed over that kind's "
            "layers and the fused steps)").inc(met, kind=kind)

    def _decode_positions(self, lanes: int, slots: np.ndarray,
                          lens: np.ndarray) -> Tuple[int, int]:
        """What a dense or latent decode call's attention meets of the
        cache, and what meeting every position of the same rows would be,
        from what the host staged (nothing is read back; a hybrid program
        sums its two kinds behind its carrier): the lanes' rows whole where
        the read is staged, every row of every layer where it is in place,
        and of a dense cache each fused step's rows only as far as the
        program's own rule takes them (:func:`.kv_cache.dense_positions_met`
        of the lanes' positions as they advance: of token rows each live
        lane's own blocks, else every row up to the longest live lane's
        bound, a replica at a time)."""
        cc, steps = self.cache_cfg, self.scfg.decode_steps_per_call
        in_place = self._read_form(lanes) == "in_place"
        rows = cc.rows if in_place else lanes
        layers = cc.layers if self._latent else self.cfg.layers
        reserved = self.m.dp * layers * steps * rows * cc.max_len
        if self._latent or not in_place:
            return reserved, reserved
        met = _kv.dense_positions_met(
            lens[:, None, :] + np.arange(steps)[None, :, None],
            (slots != cc.trash_slot)[:, None, :], rows, cc.max_len,
            cc.page_order == "token_rows")
        return int(self.cfg.layers * met.sum()), reserved

    # ------------------------------------------------------------------
    # host-side surface (per-REPLICA shapes; the engine broadcasts each
    # replica's row across its slice devices)
    # ------------------------------------------------------------------

    def _count_crossing(self, program: str, direction: str,
                        arrays: int = 1) -> None:
        _metrics.counter(
            "bluefog_serve_host_arrays_total",
            "arrays an engine call moved between the host and the mesh, by "
            "program (decode, prefill, chunk, draft) and direction (in: "
            "staged for the call; out: read back)").inc(
                arrays, program=program, direction=direction)

    @staticmethod
    def _pack(tokens, *fields) -> np.ndarray:
        """Everything the host sends one call, as ONE int32 array: per
        replica (and lane) the tokens ``[..., T]``, then one integer of
        each of ``fields`` ``[...]`` (:meth:`_unpack` inside the program)."""
        return np.concatenate(
            [np.asarray(tokens, np.int32)]
            + [np.asarray(f, np.int32)[..., None] for f in fields], axis=-1)

    def _expand(self, program: str, arr: np.ndarray) -> jax.Array:
        """``[replicas, ...]`` host array -> ``[n_devices, ...]`` on the
        mesh, in one transfer."""
        if arr.shape[0] != self.m.dp:
            raise ValueError(f"leading axis {arr.shape[0]} != replica count "
                             f"{self.m.dp}")
        self._count_crossing(program, "in")
        if self.m.slice_size > 1:
            arr = np.repeat(arr, self.m.slice_size, axis=0)
        return jax.device_put(arr, self._sharding)

    @staticmethod
    def _ask_back(outs: Sequence[jax.Array]) -> None:
        """With the dispatch, ask for the copy to the host of exactly what
        :meth:`_collect` will read of the call: the transfer follows the
        program on the device with no word from the host between them, so
        that telling ``wait`` from ``read_back`` adds no round trip."""
        for a in outs:
            a.copy_to_host_async()

    def _collect(self, program: str, *outs: jax.Array) -> list:
        """``[n_devices, ...]`` each -> ``[replicas, ...]`` host arrays
        (slice rows agree), read back together: ``wait`` until the device
        has them, ``read_back`` until the host has (:meth:`_ask_back` asked
        for the copy when the call was dispatched)."""
        self._count_crossing(program, "out", len(outs))
        with self._stage("wait"):
            jax.block_until_ready(outs)
        with self._stage("read_back"):
            return [a[::self.m.slice_size] for a in jax.device_get(outs)]

    def _args(self, staged: jax.Array) -> tuple:
        """A program's arguments (:meth:`_build`)."""
        return self.params, self.cache, self._keys, staged

    def _admission(self, replica: int, slot: int, shape=()):
        """What an admitted slot's sampler key is made from inside the
        program (:meth:`_seed_rows`), ``[replicas, *shape]`` each: the
        slot's id and the admission count, which advances here; 0 for every
        other replica."""
        self._seed_count += 1
        key_id = np.zeros((self.m.dp,) + shape, np.int32)
        count = np.zeros_like(key_id)
        key_id[replica] = replica * self.cache_cfg.rows + slot
        count[replica] = self._seed_count
        return key_id, count

    def _trash_vec(self, S: int) -> np.ndarray:
        return np.full((self.m.dp, S), self.cache_cfg.trash_slot, np.int32)

    def _stage_lanes(self, program: str, tokens, slots, lens, prefix_rows,
                     prefix_lens, *more) -> tuple:
        """The arguments of a lane program's call: ``tokens`` ``[replicas,
        S, T]`` and per lane its slot, position, prefix attachment (trash
        row at length 0 = no indirection for that lane) and ``more``."""
        if prefix_rows is None:
            S = tokens.shape[1]
            prefix_rows = self._trash_vec(S)
            prefix_lens = np.zeros((self.m.dp, S), np.int32)
        elif not self._use_prefix:
            raise ValueError("prefix attachments need prefix_pages > 0")
        return self._args(self._expand(program, self._pack(
            tokens, slots, lens, prefix_rows, prefix_lens, *more)))

    def prefill(self, replica: int, slot: int,
                tokens: Sequence[int]) -> Tuple[int, "_DeviceRow"]:
        """Prefill one request into ``slot`` of ``replica``; other replicas
        run the same program against their trash slot.  Returns the first
        greedy token and the last-position logits ``[vocab]``, which stay
        on the device until something converts them (``np.asarray``)."""
        if not 0 <= slot < self.scfg.slots:
            raise ValueError(f"slot {slot} out of range "
                             f"[0, {self.scfg.slots})")
        return self._prefill_into(replica, slot, tokens, admit=True)

    def seal_prefix(self, replica: int, row: int,
                    tokens: Sequence[int]) -> None:
        """Prefill a shared prefix into reserved page row ``row`` — the
        same compiled prefill program (the row id is data, not shape), so
        sealing never retraces.  The row must come from the replica's
        :class:`~bluefog_tpu.serve.kv_cache.PrefixCache` ``admit``."""
        cc = self.cache_cfg
        if not cc.slots <= row < cc.slots + cc.prefix_slots:
            raise ValueError(f"prefix row {row} out of range "
                             f"[{cc.slots}, {cc.slots + cc.prefix_slots})")
        if len(tokens) % self.scfg.prefix_page_tokens:
            raise ValueError(f"prefix of {len(tokens)} tokens is not whole "
                             f"pages of {self.scfg.prefix_page_tokens}")
        self._prefill_into(replica, row, tokens, admit=False)

    def _prefill_into(self, replica: int, row: int, tokens: Sequence[int],
                      admit: bool) -> Tuple[int, "_DeviceRow"]:
        if not tokens:
            raise ValueError("empty prompt")
        Tpad = self.scfg.prefill_bucket_for(len(tokens))
        R = self.m.dp
        toks = np.zeros((R, Tpad), np.int32)
        toks[replica, :len(tokens)] = np.asarray(tokens, np.int32)
        slot_id = self._trash_vec(1)[:, 0]
        slot_id[replica] = row
        true_len = np.ones((R,), np.int32)
        true_len[replica] = len(tokens)
        # a sealed prefix is no admission: no key, the count stays
        key_id, count = self._admission(replica, row) if admit \
            else (np.zeros((R,), np.int32),) * 2
        attrs = {}
        if (self._hybrid or self._ssm) and self.cfg.layers_of("full"):
            # what the full layers' attention took of the flash kernel
            attrs["key_blocks"] = self._key_blocks(Tpad)
            _metrics.counter(
                "bluefog_serve_prefill_key_blocks_total",
                "key blocks the full layers' attention of prefill calls "
                "took (one to a call up to the flash kernel's block of "
                "keys)").inc(attrs["key_blocks"])
        with self._stage("prefill_call", Tpad=Tpad, tokens=len(tokens),
                         replica=replica, **attrs):
            with self._stage("stage_in"):
                args = self._args(self._expand("prefill", self._pack(
                    toks, slot_id, true_len, key_id, count)))
            with self._stage("dispatch"):
                nxt, logits, *chosen, self._keys, self.cache = \
                    self._prefill_jit(*args)
                self._ask_back((nxt,))
                if chosen:
                    self._prefill_chosen, = chosen
            with self._stage("collect"):
                self._check_program(f"prefill Tpad={Tpad}",
                                    self._prefill_jit, args,
                                    self._cache_writes("prefill", 1))
                nxt, = self._collect("prefill", nxt)
                return int(nxt[replica]), _DeviceRow(
                    logits, replica * self.m.slice_size,
                    lambda: self._count_crossing("prefill", "out"))

    def chunk_prefill(self, replica: int, slot: int, tokens: Sequence[int],
                      start: int, prefix_row: int) -> int:
        """Prefill only the divergent remainder of a prefix-hit request.

        The request attached to a sealed prefix of ``start`` tokens at
        page row ``prefix_row``; ``tokens`` is the rest of its prompt
        (``>= 1`` — the page granularity guarantees a leftover token).
        The remainder chunk attends through the page indirection, writes
        its own kv into the private ``slot`` (positions ``start ..``),
        and returns the request's first greedy token.  Cost is one chunk
        of ``len(tokens)`` instead of the whole prompt.
        """
        if not 0 <= slot < self.scfg.slots:
            raise ValueError(f"slot {slot} out of range "
                             f"[0, {self.scfg.slots})")
        if not tokens:
            raise ValueError("empty remainder: a prefix hit always leaves "
                             ">= 1 prompt token")
        Tpad = self.scfg.prefill_bucket_for(len(tokens))
        R = self.m.dp
        toks = np.zeros((R, 1, Tpad), np.int32)
        toks[replica, 0, :len(tokens)] = np.asarray(tokens, np.int32)
        slots = self._trash_vec(1)
        slots[replica, 0] = slot
        lens = np.zeros((R, 1), np.int32)
        lens[replica, 0] = start
        prows = self._trash_vec(1)
        prows[replica, 0] = prefix_row
        # the lane starts where its prefix ends: position == prefix length
        gen = self._chunk_call(toks, slots, lens, prows, lens,
                               self._admission(replica, slot, (1,)))
        return int(gen[replica, 0, len(tokens) - 1])

    def _chunk_call(self, toks, slots, lens, prows, plens,
                    admission=None) -> np.ndarray:
        """One call of the chunk program; ``admission`` is the one lane's
        :meth:`_admission` where the chunk is a request's prefill."""
        toks = np.asarray(toks, np.int32)
        S, T = toks.shape[1:]
        zeros = np.zeros((self.m.dp, S), np.int32)
        with self._stage("chunk_call", S=S, T=T):
            with self._stage("stage_in"):
                args = self._stage_lanes("chunk", toks, slots, lens, prows,
                                         plens, *(admission or (zeros,) * 2))
            with self._stage("dispatch"):
                *out, self._keys, self.cache = self._chunk_jit(*args)
                self._ask_back(out)
            with self._stage("collect"):
                self._check_program(f"chunk S={S} T={T}", self._chunk_jit,
                                    args, self._cache_writes("chunk", S))
                gen, *st = self._collect("chunk", *out)
                if st:
                    self._note_route_stats(st[0])
                return gen

    def decode(self, tokens: np.ndarray, slots: np.ndarray,
               lens: np.ndarray, prefix_rows: Optional[np.ndarray] = None,
               prefix_lens: Optional[np.ndarray] = None, *,
               ahead: bool = False) -> np.ndarray:
        """One fused decode call for every replica at one batch bucket.

        ``tokens``/``slots``/``lens``: ``[replicas, S]`` with ``S`` in
        ``batch_buckets``; idle lanes use the trash slot with ``lens=0``.
        ``lens[r, i]`` is the position the lane's pending token occupies
        (prompt length + tokens already generated).  ``prefix_rows`` /
        ``prefix_lens`` attach lanes to sealed prefix pages (trash row at
        length 0 for unattached lanes).  Returns the decoded tokens
        ``[replicas, decode_steps_per_call, S]`` (greedy, or sampled when
        ``temperature > 0`` — each lane's PRNG stream was seeded at its
        prefill).

        ``ahead=True`` (the scheduler's) runs ONE CALL AHEAD of the host:
        this call is staged and dispatched, then the call dispatched one
        ``decode`` earlier is collected and ITS tokens are returned (they
        belong to that call's lanes; ``[replicas, 0, S]``, no step, where
        nothing was in flight), and this one stays in flight until the
        next ``decode`` or :meth:`decode_drain`.  A NEGATIVE token then
        stands for the slot's pending token, the last one the call in
        flight chooses for it, which the host has not read: it goes from
        that call's output into this call's staged array on the device
        (:meth:`_feed_body`, one small program between the two decode
        programs, which stay what they are).  The stages keep their names
        and order under one ``decode_call``; ``collect`` waits for the
        program ahead of the one just dispatched, so the device always has
        its next program queued.  What belongs to a call (the routing
        carrier behind :meth:`moe_load`, the ``held_work`` mark, the
        position counters, :meth:`decode_logits`) is published when THAT
        call is collected.  The call's ``ahead`` attribute and
        ``bluefog_serve_decode_calls_total{ahead}`` say whether a call was
        in flight when it was dispatched.
        """
        tokens = np.asarray(tokens, np.int32)
        slots = np.array(slots, np.int32)
        S = tokens.shape[1]
        if S not in self.scfg.batch_buckets:
            raise ValueError(f"batch lane count {S} is not a declared "
                             f"bucket {self.scfg.batch_buckets}")
        if self._flying is not None and not ahead:
            raise RuntimeError(
                "a decode call is in flight: its tokens would be lost — "
                "collect it first (decode_drain)")
        fed = self._fed_by(tokens, slots)
        writes = self._cache_writes("decode", S)
        behind = int(self._flying is not None)
        self._count_decode_call(behind)
        lens = np.array(lens, np.int32)
        attrs = dict(S=int(S), cache_writes=writes, ahead=behind)
        read = None
        if not (self._hybrid or self._ssm):
            # (their programs count what they met behind the carrier)
            read, reserved = self._decode_positions(S, slots, lens)
            if not self._share:
                attrs.update(positions_read=read, positions_reserved=reserved)
        with self._stage("decode_call", **attrs):
            with self._stage("stage_in"):
                args = self._stage_lanes(
                    "decode", tokens[..., None], slots, lens, prefix_rows,
                    prefix_lens, *(() if fed is None else (fed,)))
            with self._stage("dispatch"):
                if fed is not None:
                    args = args[:-1] + (
                        self._feed_jit(args[-1], self._flying.out[0]),)
                *out, self._keys, self.cache = self._decode_jit(*args)
                # (the logits and selections a program hands out stay on
                # the device: _collect_decode takes them off the list)
                self._ask_back(out[:len(out) - self._hands_logits
                                   - self._hands_chosen])
            call = _DecodeCall(S, slots, lens, out, read)
            if self._hands_logits and self._decode_logits is None:
                # nothing has been collected yet: the call in flight's
                self._decode_logits = (slots, out[-1])
                if self._hands_chosen:
                    self._decode_chosen = (slots, out[-2])
            due, self._flying = (self._flying, call) if ahead \
                else (call, None)
            gen = self._collect_decode(due, (
                f"decode S={S}", self._decode_jit, args, writes,
                self._read_form(S)))
            return np.empty((self.m.dp, 0, S), np.int32) if gen is None \
                else gen

    def _fed_by(self, tokens: np.ndarray, slots: np.ndarray
                ) -> Optional[np.ndarray]:
        """Per lane of a call about to be staged, the lane of the call in
        flight whose slot it rides (``[replicas, S]``, -1 where the host's
        token stands); None where every token is the host's."""
        wanted = tokens < 0
        if not wanted.any():
            return None
        if self._flying is None:
            raise ValueError("a negative token stands for what the decode "
                             "call in flight chooses, and none is in flight")
        ahead = self._flying.slots                          # [replicas, S']
        lane_of = np.full((self.m.dp, self.cache_cfg.rows), -1, np.int32)
        np.put_along_axis(lane_of, ahead, np.arange(
            ahead.shape[1], dtype=np.int32)[None], axis=1)
        lane_of[:, self.cache_cfg.trash_slot] = -1
        fed = np.where(wanted, np.take_along_axis(lane_of, slots, axis=1), -1)
        if (fed < 0)[wanted].any():
            raise ValueError("a negative token on a slot that the decode "
                             "call in flight does not carry")
        return fed

    def decode_drain(self) -> Optional[np.ndarray]:
        """Collect the decode call in flight (``decode(..., ahead=True)``)
        with nothing dispatched behind it: its tokens, or None where none
        is in flight.  One ``bf:engine.decode_drain`` span with a
        ``collect`` beneath."""
        due, self._flying = self._flying, None
        if due is None:
            return None
        with self._stage("decode_drain", S=due.S):
            return self._collect_decode(due)

    def _count_decode_call(self, ahead: int) -> None:
        _metrics.counter(
            "bluefog_serve_decode_calls_total",
            "decode calls (a speculative round is one) by whether another "
            "was in flight, dispatched and not yet read back, when the "
            "call was dispatched (ahead: 1 or 0)").inc(ahead=str(ahead))

    def _collect_decode(self, due: Optional[_DecodeCall], check=None
                        ) -> Optional[np.ndarray]:
        """The ``collect`` stage of a decode call, ``due`` the call whose
        tokens are read (None: nothing was in flight) and ``check`` the program just dispatched
        (:meth:`_check_program`), and what is published with a call."""
        with self._stage("collect"):
            if check is not None:
                self._check_program(*check)
            if due is None:
                return None
            S, slots, lens, out, read = due
            if self._hands_logits:
                self._decode_logits = (slots, out.pop())
            if self._hands_chosen:
                self._decode_chosen = (slots, out.pop())
            gen, *st = self._collect("decode", *out)
            if st:
                self._note_route_stats(st[0])
            if read is not None:
                self._count_positions(
                    read, "latent" if self._latent else "full")
        # a mark never goes inside a stage: beneath ``collect`` lie its
        # ``wait`` and ``read_back`` and nothing else, in every family
        if self._share:
            self._count_held_work(S, lens, slots)
        return gen

    @property
    def _hands_logits(self) -> bool:
        """Whether the decode program hands out every fused step's logits
        beside its tokens (:meth:`decode_logits`)."""
        return self._hybrid or self._hands_chosen

    def decode_logits(self, replica: int
                      ) -> Optional[Tuple[np.ndarray, "_DeviceRow"]]:
        """What the last :meth:`decode` call COLLECTED of the hybrid or the
        state-space family or a softmax-routed latent model (the call whose tokens were last returned; before any has been,
        the call in flight, and converting them waits for it) chose its
        tokens from: ``replica``'s lanes' slots ``[S]`` and their
        logits ``[decode_steps_per_call, S, vocab]`` (float32), which stay
        on the device until something converts them, as a prefill's do.
        ``Scheduler`` never reads them; the benchmark's comparison with
        the reference does, so that what decode READS of the cache is
        held to it number by number.  ``None`` before the first call and
        for the other families, whose programs hand out tokens alone."""
        return self._handed_out(self._decode_logits, replica)

    def _handed_out(self, kept, replica: int):
        if kept is None:
            return None
        slots, rows = kept
        return slots[replica], _DeviceRow(
            rows, replica * self.m.slice_size,
            lambda: self._count_crossing("decode", "out"))

    def decode_chosen(self, replica: int
                      ) -> Optional[Tuple[np.ndarray, "_DeviceRow"]]:
        """Beside :meth:`decode_logits`, of the state-space family and of
        a softmax-routed latent model (:attr:`_hands_chosen`): the
        experts every expert layer chose for every lane in every fused step
        of that call, ``[decode_steps_per_call, expert layers, S, top_k]``
        int32 over the router's outputs (-1: a dead lane).  Left on the
        device like the logits; only a comparison with a reference reads
        them, to evaluate the reference under the program's own selections
        where a rounding tie put the two apart.  ``None`` before the first
        call and for the other families."""
        return self._handed_out(self._decode_chosen, replica)

    def prefill_chosen(self, replica: int) -> Optional["_DeviceRow"]:
        """:meth:`decode_chosen` for the last :meth:`prefill`: ``[expert
        layers, Tpad, top_k]`` (-1 at the prompt's padding)."""
        if self._prefill_chosen is None:
            return None
        return _DeviceRow(self._prefill_chosen,
                          replica * self.m.slice_size,
                          lambda: self._count_crossing("prefill", "out"))

    def spec_decode(self, tokens: np.ndarray, slots: np.ndarray,
                    lens: np.ndarray,
                    prefix_rows: Optional[np.ndarray] = None,
                    prefix_lens: Optional[np.ndarray] = None
                    ) -> Tuple[np.ndarray, np.ndarray]:
        """One speculative round: draft k, verify in one chunk, accept.

        Same lane contract as :meth:`decode`.  Returns ``(emitted,
        counts)``: ``emitted`` is ``[replicas, S, k+1]`` int32 (positions
        ``>= counts`` hold -1), ``counts`` is ``[replicas, S]`` — each
        lane advances by ``counts[r, i]`` tokens (``1 <= counts <= k+1``:
        the accepted draft prefix plus the target's bonus token).  Every
        emitted token is a target-argmax token, so the stream is
        bit-identical to plain greedy decode; speculation only changes
        how many arrive per call.
        """
        k = self.scfg.spec_decode
        if not k:
            raise ValueError("spec_decode is not armed "
                             "(ServeConfig.spec_decode == 0)")
        tokens = np.asarray(tokens, np.int32)
        slots = np.asarray(slots, np.int32)
        lens = np.asarray(lens, np.int32)
        S = tokens.shape[1]
        if S not in self.scfg.batch_buckets:
            raise ValueError(f"batch lane count {S} is not a declared "
                             f"bucket {self.scfg.batch_buckets}")
        self._count_decode_call(0)
        with self._stage("spec_round", S=int(S), k=k) as round_:
            emitted, counts, drafted, accepted = self._spec_round(
                tokens, slots, lens, prefix_rows, prefix_lens)
            round_.attrs.update(drafted=drafted, accepted=accepted)
        return emitted, counts

    def _spec_round(self, tokens, slots, lens, prefix_rows, prefix_lens):
        k, S = self.scfg.spec_decode, tokens.shape[1]
        with self._stage("stage_in"):
            args = self._stage_lanes("draft", tokens[..., None], slots, lens,
                                     prefix_rows, prefix_lens)
        with self._stage("dispatch"):
            drafts, self._keys, self.cache = self._draft_jit(*args)
            self._ask_back((drafts,))
        with self._stage("collect"):
            self._check_program(f"draft S={S}", self._draft_jit, args,
                                self._cache_writes("draft", S),
                                self._read_form(S))
            drafts, = self._collect("draft", drafts)    # [R, k, S]
        d = np.transpose(drafts, (0, 2, 1))             # [R, S, k]
        # verify chunk: [t0, d_1 .. d_k] per lane — the draft rows it
        # appended are overwritten with the (identical) target values and
        # the later-stage layers get theirs written for the first time
        chunk = np.concatenate([tokens[:, :, None], d], axis=2)
        gen = self._chunk_call(chunk, slots, lens, prefix_rows,
                               prefix_lens)                 # [R, S, k+1]
        # accept: longest prefix where draft_i == target g_i, then the
        # bonus g_{j+1}; rejected rows above the new frontier are garbage
        # that the next round's appends overwrite before any read
        match = d == gen[:, :, :k]
        j = np.argmin(np.concatenate(
            [match, np.zeros_like(match[:, :, :1])], axis=2), axis=2)
        counts = (j + 1).astype(np.int32)
        t_idx = np.arange(k + 1)[None, None, :]
        d_pad = np.concatenate([d, np.zeros_like(d[:, :, :1])], axis=2)
        emitted = np.where(
            t_idx < j[:, :, None], d_pad,
            np.where(t_idx == j[:, :, None], gen, -1)).astype(np.int32)
        live = slots < self.scfg.slots                  # trash lanes don't count
        drafted = int(live.sum()) * k
        accepted = int(j[live].sum())
        if drafted:
            _metrics.counter(
                "bluefog_serve_spec_drafted_total",
                "draft tokens proposed by speculative decoding").inc(drafted)
            _metrics.counter(
                "bluefog_serve_spec_accepted_total",
                "draft tokens accepted by the verify pass").inc(accepted)
        return emitted, counts, drafted, accepted

    def idle_lane(self) -> Tuple[int, int, int]:
        """(token, slot, len) triple a padding lane should carry."""
        return 0, self.cache_cfg.trash_slot, 0

    def _note_route_stats(self, st: np.ndarray) -> None:
        """Fold one MoE call's ``[R, E + 2]`` hot-expert carrier into the
        last-call snapshot (per-expert top-1 counts over live lanes and
        layers, summed router entropy, live token-layer count).  A latent
        model's carrier counts every selection, not the first alone, and
        has two more entries: the pairs that fell on held experts, and the
        (layer, held expert) groups that got a token; a hybrid model's has
        two more behind those: the cache positions its attention met on
        full and on window layers; a state-space model's one: the
        positions its attention layers met."""
        self._route_stats = st.astype(np.float64)

    def moe_load(self) -> Optional[list]:
        """Per-replica routing load from the most recent MoE engine call
        (fused decode, or the spec-verify chunk): a list of ``m.dp``
        dicts with ``fractions`` (``[E]`` top-1 dispatch fractions),
        ``counts`` (raw live token-layer counts), ``entropy`` (mean
        live-token router entropy, nats) and ``tokens`` (live token-layer
        count).  ``None`` for dense engines or before the first call with
        a live lane — the expert-load-aware scheduler reads this."""
        if not self._routed or self._route_stats is None:
            return None
        E = self.cfg.num_experts
        out = []
        for r in range(self.m.dp):
            cnt = self._route_stats[r, :E]
            tot = float(cnt.sum())
            n = float(self._route_stats[r, E + 1])
            out.append({
                "counts": cnt.copy(),
                "fractions": cnt / tot if tot else np.zeros(E),
                "entropy": float(self._route_stats[r, E]) / n if n else 0.0,
                "tokens": n,
            })
        return out

    def decode_lowered_text(self, batch: Optional[int] = None) -> str:
        """Pre-optimization StableHLO of one fused-decode bucket (the
        largest by default), for a test that reads the program itself:
        which collectives it holds
        (:func:`~bluefog_tpu.utils.hlo_bytes.stablehlo_wire_stats`), what
        its cache read slices.  Lowering only: nothing executes and the
        donated cache stays alive."""
        S = batch if batch is not None else self.scfg.batch_buckets[-1]
        if S not in self.scfg.batch_buckets:
            raise ValueError(f"batch lane count {S} is not a declared "
                             f"bucket {self.scfg.batch_buckets}")
        tok, slot, ln = self.idle_lane()
        full = lambda v: np.full((self.m.dp, S), v, np.int32)
        return self._decode_jit.lower(*self._stage_lanes(
            "decode", full(tok)[..., None], full(slot), full(ln), None,
            None)).as_text()

    def update_params(self, params: Any) -> None:
        """Swap in a fresh ``[n, ...]``-stacked tree (shapes must match —
        a shape change would retrace, which the sentinel will report)."""
        self.params = jax.tree.map(
            lambda x: jax.device_put(jnp.asarray(x), self._sharding), params)

    def warmup(self) -> None:
        """Compile every declared shape — prefill and decode buckets, and
        when armed the draft/verify pair per decode bucket and the chunked
        prefill per prefill bucket — then arm the retrace sentinel."""
        scfg = self.scfg
        for Tpad in scfg.prefill_buckets:
            self.prefill(0, 0, [0] * Tpad)
        tok, slot, ln = self.idle_lane()
        R = self.m.dp
        for S in scfg.batch_buckets:
            full = lambda v: np.full((R, S), v, np.int32)
            self.decode(full(tok), full(slot), full(ln))
            if scfg.spec_decode:
                self.spec_decode(full(tok), full(slot), full(ln))
        # a call that runs ahead is fed by the one in flight: every pair
        # of buckets (:meth:`_feed_body`)
        n, steps = R * self.m.slice_size, scfg.decode_steps_per_call
        zeros = lambda *shape: jax.device_put(
            np.zeros((n,) + shape, np.int32), self._sharding)
        for S in scfg.batch_buckets:
            for ahead in scfg.batch_buckets:
                self._feed_jit(zeros(S, 1 + 4 + 1), zeros(steps, ahead))
        if self._use_prefix:
            for Tpad in scfg.prefill_buckets:
                self._chunk_call(np.zeros((R, 1, Tpad), np.int32),
                                 self._trash_vec(1),
                                 np.zeros((R, 1), np.int32), None, None)
        self._warm_sizes = self._jit_sizes()
        _flight.record("serve", name="warmup",
                       batch_buckets=list(scfg.batch_buckets),
                       prefill_buckets=list(scfg.prefill_buckets),
                       spec_decode=scfg.spec_decode,
                       prefix_pages=scfg.prefix_pages,
                       kv_dtype=scfg.kv_dtype,
                       moe_experts=self.cfg.num_experts if self._moe else 0,
                       moe_ep=self.m.ep if self._moe else 0,
                       moe_tile=self._moe_tile if self._moe else 0)
        _metrics.mark_steady_state(True)

    def _jit_sizes(self) -> Tuple[int, ...]:
        return tuple(j._cache_size() if j is not None else 0
                     for j in (self._decode_jit, self._prefill_jit,
                               self._chunk_jit, self._draft_jit,
                               self._feed_jit))

    def _cache_writes(self, kind: str, lanes: int) -> int:
        """``dynamic_update_slice``s into the cache that one call of a
        ``kind`` program (``"decode"``, ``"draft"``, ``"chunk"``,
        ``"prefill"``) makes on each device, known from shapes alone:
        lanes x tensors, per stage hop of the cycle and per fused step,
        once after the layer loop where a decode token's write waits for
        it (:meth:`_defer_appends`) and once per layer everywhere else."""
        scfg = self.scfg
        hops = self.draft.stages if kind == "draft" else self.m.pp
        steps = {"decode": scfg.decode_steps_per_call,
                 "draft": scfg.spec_decode}.get(kind, 1)
        deferred = kind in ("decode", "draft") and self._defer_appends
        if self._ssm:
            # K and V of the attention layers a lane (a token's once after
            # the loop), a state and its convolution inputs a recurrent
            # layer: per lane from a prompt, whole by a decode step
            full, ssm = self.cache_cfg.full_layers, self.cache_cfg.ssm_layers
            return lanes * 2 * (full + ssm) if not deferred \
                else steps * 2 * (lanes + ssm)
        if self._hybrid and not deferred:
            # a layer writes the K and V of its own kind
            return lanes * 2 * self.cfg.layers
        return (lanes * len(self.cache) * hops * steps
                * (1 if deferred else self.cache_cfg.layers))

    def _check_program(self, program: str, fn, args, writes: int,
                       read: Optional[str] = None) -> None:
        """After every device call: the first time a program (``"decode
        S=32"``, ``"prefill Tpad=64"``, ...) is seen, record what the
        compiler built for it, its ``writes`` into the cache a call
        (:meth:`_cache_writes`) and, of a program that attends over the
        cache one token a lane, how it ``read``s it (:meth:`_read_form`);
        once warm, any growth of the jit caches is a retrace."""
        if program not in self._program_bytes:
            # the executable the call above compiled, found again through
            # jit's own caches: nothing is traced or compiled a second
            # time, and lowering reads only the arguments' shapes (the
            # donated cache among them is already consumed)
            compiled = fn.lower(*args).compile()
            # kept for whoever asks which instruction is which named part
            # of the program (tracing.device_scopes); nothing is parsed here
            _tracing.register_program(program, compiled)
            ma = compiled.memory_analysis()
            self._program_bytes[program] = {
                "temp_bytes": int(ma.temp_size_in_bytes),
                "alias_bytes": int(ma.alias_size_in_bytes),
                "cache_writes": writes,
                "pages": self.cache_cfg.page_orders()}
            if read is not None:
                self._program_bytes[program]["read"] = read
            if read == "in_place" and not self._share:
                self._program_bytes[program]["read_step"] = \
                    self.cache_cfg.read_step
            if self._ssm:
                cc = self.cache_cfg
                self._program_bytes[program]["state_bytes"] = \
                    cc.rows * cc.bytes_per_slot()["ssm"]
            _metrics.gauge(
                "bluefog_serve_cache_copy_bytes",
                "temporaries the compiler allocated for one engine program "
                "(per device): activations only while the KV cache is "
                "updated in place, cache-sized once a program copies it"
            ).set(float(ma.temp_size_in_bytes), program=program)
            _metrics.gauge(
                "bluefog_serve_cache_alias_bytes",
                "output bytes of one engine program that reuse a donated "
                "argument (per device): the whole KV cache while it is "
                "updated in place").set(float(ma.alias_size_in_bytes),
                                        program=program)
            _metrics.gauge(
                "bluefog_serve_cache_writes_per_call",
                "in-place writes into the KV cache one call of an engine "
                "program makes (per device): lanes x tensors, once after "
                "the layer loop for a decode token, once per layer "
                "otherwise").set(float(writes), program=program)
        if self._warm_sizes is None:
            return
        sizes = self._jit_sizes()
        if sizes > self._warm_sizes:
            _metrics.note_retrace(detail=f"serve engine {program}")
            self._warm_sizes = sizes

    def program_memory(self) -> dict:
        """``{program: {"temp_bytes", "alias_bytes", "cache_writes",
        "pages"}}`` per device, for every engine program compiled so far
        (``compiled.memory_analysis()``, :meth:`_cache_writes`): whether
        the cache is updated in place is a property of the compiled
        program, so this is its counter — ``alias_bytes`` is the size of
        the cache and the key table, and ``temp_bytes`` stays under one
        layer's pages when it is; ``pages`` names the order each cache
        tensor's pages lie in (:func:`.kv_cache.page_order`:
        ``"token_rows"``, ``"head_dim_minor"`` or ``"positions_minor"``;
        ``"state"``: no positions), which is what a token's write costs; a
        state-space model's programs add ``state_bytes``, the recurrent
        states and convolution inputs among the aliased bytes.  A decode or draft program
        also says how its attention ``read``s the cache: ``"in_place"``
        (no staging buffer among its temporaries) or ``"staged"``; a dense
        program that reads in place adds ``read_step``, the positions its
        read's bound advances by (:attr:`.kv_cache.KVCacheConfig.read_step`:
        a lane's own block of token rows, else the step of the batch's
        bound; ``max_len`` where every row is read whole)."""
        return {k: dict(v) for k, v in self._program_bytes.items()}
