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

Beside it stands the **latent block** (:class:`LatentConfig`,
:func:`latent_block`): RMSNorm with learned scales, multi-head latent
attention whose ``attend`` hook owns its projections (two low-rank paths,
a rotary part beside a part that is never turned, YaRN frequencies) in an
unabsorbed form for whole sequences and an absorbed form over a cache of
one compressed vector per token, and a gated SiLU FFN.  The serving
engine's latent programs call it the same way.

Third, the **hybrid block** (:class:`HybridConfig`, :func:`hybrid_block`):
window and full attention layers in one model by a static layer plan,
separate ``wq``/``wk``/``wv`` with fewer keys than queries, RMSNorm over
each head of q and k, rotary on the window layers only, each sublayer's
OUTPUT normed before it is added; a window layer's whole-sequence
attention is a band of blocks (:func:`window_attention`).
Fourth, the **single-mixer block** (:class:`SsmConfig`,
:func:`mixer_block`): every layer is ONE mixer behind one RMSNorm, by a
static plan a Mamba-2 state-space mixer (:func:`mamba_project`,
:func:`mamba_conv`, :func:`mamba_scan_chunked` for a prompt and
:func:`mamba_step` for a decode token, :func:`mamba_gate_out`), a gated
delta-rule mixer with a decay per channel (:func:`delta_project`,
:func:`delta_split`, :func:`delta_discretize`; a prompt's rule is the Pallas
kernel :func:`bluefog_tpu.ops.pallas_delta.delta_rule`, which
:func:`delta_scan_chunked` hands a block of positions at a time and which
runs in interpreter mode off the TPU; a decode token's :func:`delta_step`;
:func:`delta_gate_out`), grouped-query attention that turns nothing
(:func:`gqa_project`), or ``relu^2`` experts in a latent
(:func:`bluefog_tpu.moe.layers.held_moe_ffn`).
Each block opens the device scopes of its parts (``attn.project`` or
``mla.project``, ``attn.window`` / ``attn.full``, ``ffn``, ``ssm.project``
/ ``ssm.conv`` / ``ssm.scan``; the read-outs ``readout``): plain ``jax.named_scope``s, metadata that changes no
instruction and lets ``utils.tracing.device_scopes`` say which compiled
instruction belongs to which part.
Imports jax and that kernel's module: the callers import this module, never
the reverse.
"""
import contextlib
import dataclasses
import math
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import pallas_delta

ATTENTION_LEAVES = ("wqkv", "wo")
FFN_LEAVES = ("w1", "w2")


def norm(x: jax.Array) -> jax.Array:
    """Parameter-free layer norm over the channel axis."""
    mu = x.mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(x.var(-1, keepdims=True) + 1e-6)


def rope(x: jax.Array, positions: jax.Array,
         base: float = 10000.0,
         freqs: Optional[jax.Array] = None) -> jax.Array:
    """Rotary position embedding on ``x`` ``[..., H, Dh]`` with integer
    ``positions`` shaped ``x.shape[:-2]`` or a suffix of it: ``[T]``
    against ``[B, T, H, Dh]`` (training, prefill), ``[S]`` against ``[S, H,
    Dh]`` (decode: each lane at its own offset), ``[S, T]`` against ``[S,
    T, H, Dh]`` (verify, chunked prefill).  Rotation is per token, so it
    commutes with any sequence sharding, and a token roped through one
    shape matches the same token roped through another bit for bit.
    ``freqs`` ``[Dh // 2]`` replaces the plain ``base`` ladder
    (:func:`yarn_freqs`)."""
    d = x.shape[-1]
    if d % 2:
        raise ValueError(f"rope needs an even head_dim, got {d}: the "
                         "rotation pairs channel i with channel i + d//2")
    half = d // 2
    if freqs is None:
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
    with jax.named_scope("attn.project"):
        q, k, v = jnp.split(norm(x) @ lp["wqkv"], 3, axis=-1)
        q = rope(q.reshape(heads), positions)
        k = rope(k.reshape(heads), positions)
    att, aux = attend(q, k, v.reshape(heads))
    with jax.named_scope("attn.project"):
        x = x + lax.psum(att.reshape(lead + (-1,)) @ lp["wo"], "tp")
    with jax.named_scope("ffn"):
        y, faux = ffn(lp, norm(x))
        return x + y, aux, faux


def lm_logits(shared: Dict[str, jax.Array], x: jax.Array) -> jax.Array:
    """Final norm and read-out through the shared head (the stage select
    and its ``psum`` stay with the caller: they differ)."""
    with jax.named_scope("readout"):
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


# ---------------------------------------------------------------------------
# The latent block: RMSNorm, multi-head latent attention, gated SiLU FFN
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LatentConfig:
    """Sizes of a latent-attention decoder with ``dense_layers`` leading
    dense layers and expert layers behind them, as ONE chip of an
    expert-parallel deployment holds it: the router keeps all
    ``num_experts`` outputs, this chip computes the experts ``held_start
    .. held_start + held_experts - 1`` and the shared expert, and what the
    absent experts would add is left out
    (:func:`bluefog_tpu.moe.layers.held_expert_ffn`).  ``route_bias``: the
    router selects by ``score + e_bias`` and weighs by the raw scores.
    ``streams`` (1: the plain residual): the residual is that many
    streams, which every sublayer reads through a learned combination and
    writes back under a Sinkhorn-normalised remix (:func:`hc_coefficients`,
    ``sinkhorn_iters`` rounds on ``exp`` of logits clipped to
    ``+-res_clamp``, ``hc_eps`` in every normalisation).

    ``shortcut``: every layer is a shortcut-connected DOUBLE layer
    (:func:`latent_double_block`): two latent attentions with their own
    weights and their own cached vectors, two dense gated FFNs of width
    ``dense_ffn``, and ONE expert layer that reads the first half's normed
    activation and whose result joins after the second half's FFN; there
    is then no leading dense layer (``dense_layers`` 0) and the cache
    counts :attr:`attn_layers`, two a layer.  ``router``: ``"softmax"``
    scores all ``num_experts`` outputs by one softmax, selects ``top_k`` by
    ``score + e_bias`` (``route_bias``) and weighs by the raw scores times
    ``route_scale``, with no groups and no renormalisation
    (:func:`bluefog_tpu.moe.layers.router_softmax`).  ``zero_experts``:
    the router's LAST that many outputs are identity experts: a selected
    one adds ``weight * h`` and has no weights.  ``shared_expert`` False:
    the expert layer has no shared expert.  ``q_scale`` / ``kv_scale``
    multiply the query (both parts) and the normed compressed kv vector
    (so the keys' unturned part and the values, never the rotary key) where
    the source rescales its low-rank paths by ``sqrt(d_model / rank)``."""
    vocab: int
    d_model: int
    heads: int
    layers: int                     # the leading dense layer included
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    dense_ffn: int                  # the leading layer's FFN width
    expert_ffn: int                 # a routed / shared expert's width
    num_experts: int                # the router's outputs
    held_experts: int
    top_k: int
    n_group: int
    topk_group: int
    route_scale: float
    held_start: int = 0
    rope_base: float = 10000.0
    rope_factor: float = 1.0        # YaRN: 1 = the plain ladder
    rope_orig_len: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale_all_dim: float = 0.0
    eps: float = 1e-6
    dense_layers: int = 1           # leading layers with one gated FFN
    route_bias: bool = False        # select by score + e_bias
    streams: int = 1                # residual streams; 1: the plain one
    sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    res_clamp: float = 30.0
    shortcut: bool = False          # double layers, the experts' result late
    router: str = "sigmoid_grouped"
    zero_experts: int = 0           # identity outputs, the router's last
    shared_expert: bool = True
    q_scale: float = 1.0            # on the query behind its low-rank path
    kv_scale: float = 1.0           # on the normed compressed kv vector

    @property
    def expert_layers(self) -> int:
        return self.layers - self.dense_layers

    @property
    def attn_layers(self) -> int:
        """Attention sublayers, each with a cached vector a token of its
        own: two a double layer."""
        return self.layers * (2 if self.shortcut else 1)

    @property
    def latent_dim(self) -> int:
        """Values cached per token and layer: compressed kv + rotary key."""
        return self.kv_rank + self.rope_dim

    def validate(self, m: Any) -> None:
        for name in ("vocab", "d_model", "heads", "q_rank", "kv_rank",
                     "nope_dim", "rope_dim", "v_dim", "dense_ffn",
                     "expert_ffn", "num_experts", "held_experts", "top_k",
                     "n_group", "topk_group"):
            if getattr(self, name) < 1:
                raise ValueError(f"LatentConfig.{name} must be >= 1")
        if self.shortcut:
            if self.dense_layers or self.streams != 1:
                raise ValueError(
                    f"latent_shortcut: a model of shortcut-connected double "
                    f"layers has no leading dense layer ({self.dense_layers}"
                    f") and one residual stream ({self.streams})")
        elif not 1 <= self.dense_layers < self.layers:
            raise ValueError(
                f"latent_dense_layers: {self.dense_layers} leading dense "
                f"layers of {self.layers}: layers counts at least one dense "
                "layer and at least one expert layer behind them")
        if self.streams < 1 or self.sinkhorn_iters < 1 \
                or self.hc_eps <= 0 or self.res_clamp <= 0:
            raise ValueError(
                f"latent_streams: {self.streams} streams remixed in "
                f"{self.sinkhorn_iters} Sinkhorn rounds (eps {self.hc_eps}, "
                f"clamp {self.res_clamp}): every one must be positive")
        if self.rope_dim % 2:
            raise ValueError("rope_dim must be even")
        if self.router not in LATENT_ROUTERS:
            raise ValueError(
                f"latent_router: {self.router!r} is none of {LATENT_ROUTERS}")
        if self.router == "softmax" and (self.n_group, self.topk_group) \
                != (1, 1):
            raise ValueError(
                f"latent_router: the softmax router selects over all its "
                f"outputs; n_group {self.n_group} and topk_group "
                f"{self.topk_group} must both be 1")
        if not (0 < self.q_scale < math.inf and 0 < self.kv_scale < math.inf):
            raise ValueError(
                f"latent_mla_scales: q_scale {self.q_scale} and kv_scale "
                f"{self.kv_scale} must be positive and finite")
        if self.num_experts % self.n_group or \
                not self.topk_group <= self.n_group:
            raise ValueError(
                f"latent_router_groups: {self.num_experts} experts do not "
                f"split into {self.n_group} groups of which "
                f"{self.topk_group} are kept")
        if self.top_k > self.topk_group * (self.num_experts // self.n_group):
            raise ValueError("latent_router_groups: top_k exceeds the "
                             "experts of the kept groups")
        if not 0 <= self.held_start <= self.num_experts - self.held_experts:
            raise ValueError(
                f"latent_held_experts: experts {self.held_start}.."
                f"{self.held_start + self.held_experts - 1} are not among "
                f"the router's {self.num_experts}")
        if not 0 <= self.zero_experts <= self.num_experts - self.held_start \
                - self.held_experts:
            raise ValueError(
                f"latent_zero_experts: the last {self.zero_experts} of the "
                f"router's {self.num_experts} outputs cannot be identity "
                f"experts beside held experts {self.held_start}.."
                f"{self.held_start + self.held_experts - 1}")

    @property
    def softmax_scale(self) -> float:
        """``(nope + rope)^-0.5 * m^2``, ``m`` YaRN's attention factor
        for ``mscale_all_dim`` (1 where no context extension is asked)."""
        m = 1.0
        if self.rope_factor > 1.0 and self.rope_mscale_all_dim:
            m = 0.1 * self.rope_mscale_all_dim * math.log(self.rope_factor) + 1
        return (self.nope_dim + self.rope_dim) ** -0.5 * m * m


LATENT_ROUTERS = ("sigmoid_grouped", "softmax")


def rms_norm(x: jax.Array, g: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm with the learned scale ``g``: statistics in float32, the
    result in ``x``'s dtype."""
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def yarn_freqs(cfg: LatentConfig) -> jax.Array:
    """The rotary ladder of ``rope_dim // 2`` frequencies under YaRN:
    pair ``i``'s plain frequency is kept where it turns more than
    ``beta_fast`` times within the original context, divided by
    ``factor`` where fewer than ``beta_slow``, and blended linearly in
    between.  Feed to :func:`rope` as ``freqs``."""
    d = cfg.rope_dim
    f = cfg.rope_base ** (-jnp.arange(d // 2, dtype=jnp.float32) * 2 / d)
    if cfg.rope_factor <= 1.0:
        return f

    def turn_dim(turns):
        return d * math.log(cfg.rope_orig_len / (turns * 2 * math.pi)) / (
            2 * math.log(cfg.rope_base))
    lo = max(math.floor(turn_dim(cfg.rope_beta_fast)), 0)
    hi = min(math.ceil(turn_dim(cfg.rope_beta_slow)), d - 1)
    r = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - lo)
                 / max(hi - lo, 1e-3), 0.0, 1.0)
    return f * (r / cfg.rope_factor + 1.0 - r)


def gated_ffn(h: jax.Array, wg: jax.Array, wu: jax.Array,
              wd: jax.Array) -> jax.Array:
    """``(silu(h wg) * (h wu)) wd``."""
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def dense_gated_ffn(lp: Dict[str, jax.Array], h: jax.Array):
    """The leading layer's ``ffn`` hook: one gated FFN, no routing."""
    return gated_ffn(h, lp["wg"], lp["wu"], lp["wd"]), None


def mla_project(cfg: LatentConfig, lp: Dict[str, jax.Array], h: jax.Array,
                positions: jax.Array):
    """The latent attention's own projections of the normed activation
    ``h`` ``[..., D]``: ``(q_nope [..., H, nope], q_rope [..., H, rope],
    latent [..., kv_rank + rope])``.  ``latent`` is what the cache holds
    per token: the normed compressed kv and ONE rotary key for all heads,
    already turned.  ``cfg.q_scale`` multiplies both parts of the query
    here; ``cfg.kv_scale`` is NOT in ``latent``: the forms that read it
    apply it once (:func:`mla_unabsorbed` to the vector keys and values are
    rebuilt from, :func:`mla_absorb_q` and :func:`mla_unabsorb_out` to what
    crosses ``wkvb``), so the cache holds the vector as the norm left it."""
    with jax.named_scope("mla.project"):
        lead = h.shape[:-1]
        freqs = yarn_freqs(cfg)
        cq = rms_norm(h @ lp["wqa"], lp["gq"], cfg.eps)
        q = (cq @ lp["wqb"]).reshape(
            lead + (cfg.heads, cfg.nope_dim + cfg.rope_dim))
        if cfg.q_scale != 1.0:
            q = q * cfg.q_scale
        q_nope, q_rope = q[..., :cfg.nope_dim], q[..., cfg.nope_dim:]
        q_rope = rope(q_rope, positions, freqs=freqs)
        kv = h @ lp["wkva"]
        ckv = rms_norm(kv[..., :cfg.kv_rank], lp["gkv"], cfg.eps)
        kr = rope(kv[..., None, cfg.kv_rank:], positions, freqs=freqs)
        return q_nope, q_rope, jnp.concatenate([ckv, kr[..., 0, :]], -1)


def _wkvb(cfg: LatentConfig, lp: Dict[str, jax.Array]):
    """``wkvb`` ``[kv_rank, H * (nope + v)]`` as its key and value halves,
    each ``[kv_rank, H, .]``."""
    w = lp["wkvb"].reshape(cfg.kv_rank, cfg.heads, cfg.nope_dim + cfg.v_dim)
    return w[..., :cfg.nope_dim], w[..., cfg.nope_dim:]


# the most float32 scores one pass of the unabsorbed attention keeps alive:
# a chunk of heads whose [heads, T, T] scores fit a v5e's on-chip memory is
# normalised there instead of in four passes over HBM
SCORE_BYTES = 64 << 20


def _chunks(n: int, row_bytes: int) -> int:
    """The largest divisor ``c`` of ``n`` with ``c * row_bytes`` inside
    :data:`SCORE_BYTES` (1 where not even one row is)."""
    return max(c for c in range(1, n + 1)
               if n % c == 0 and (c == 1 or c * row_bytes <= SCORE_BYTES))


def mla_unabsorbed(cfg: LatentConfig, lp: Dict[str, jax.Array],
                   q_nope: jax.Array, q_rope: jax.Array,
                   latent: jax.Array) -> jax.Array:
    """Causal attention of one whole sequence ``[T, ...]`` in the
    unabsorbed form (prefill): keys and values of every head are rebuilt
    from the compressed vector, scores in float32, a chunk of heads at a
    time (:data:`SCORE_BYTES`).  Returns ``[T, H * v]`` before ``wo``."""
    with jax.named_scope("mla.attend"):
        T, H = q_nope.shape[0], cfg.heads
        ckv, kr = latent[..., :cfg.kv_rank], latent[..., cfg.kv_rank:]
        if cfg.kv_scale != 1.0:
            ckv = ckv * jnp.asarray(cfg.kv_scale, ckv.dtype)
        causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]

        def heads(args):
            qn, qr, wk, wv = args       # [T, h, .], [T, h, .], [C, h, .] x 2
            k_nope = jnp.einsum("sc,chd->shd", ckv, wk)
            v = jnp.einsum("sc,chd->shd", ckv, wv)
            s = (jnp.einsum("thd,shd->hts", qn, k_nope,
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("thd,sd->hts", qr, kr,
                              preferred_element_type=jnp.float32))
            p = jax.nn.softmax(
                jnp.where(causal[None], s * cfg.softmax_scale, -jnp.inf), -1)
            return jnp.einsum("hts,shd->thd", p.astype(v.dtype), v)

        chunk = _chunks(H, T * T * 4)
        wk, wv = _wkvb(cfg, lp)
        if chunk == H:
            return heads((q_nope, q_rope, wk, wv)).reshape(T, -1)

        def split(a):                   # [., H, d] -> [H / chunk, ., chunk, d]
            return jnp.moveaxis(
                a.reshape(a.shape[0], H // chunk, chunk, a.shape[2]), 1, 0)
        out = lax.map(heads, (split(q_nope), split(q_rope), split(wk),
                              split(wv)))
        return jnp.moveaxis(out, 0, 1).reshape(T, -1)


def mla_absorb_q(cfg: LatentConfig, lp: Dict[str, jax.Array],
                 q_nope: jax.Array) -> jax.Array:
    """``q~_i = q_nope_i (wkvb_i^K)^T``: the query moved into the
    compressed space ``[..., H, kv_rank]``, so that scores are taken
    against the cached vector itself (decode); times ``cfg.kv_scale``,
    which the keys would have carried."""
    q = jnp.einsum("...hd,chd->...hc", q_nope, _wkvb(cfg, lp)[0])
    return q if cfg.kv_scale == 1.0 else q * jnp.asarray(cfg.kv_scale, q.dtype)


def mla_unabsorb_out(cfg: LatentConfig, lp: Dict[str, jax.Array],
                     u: jax.Array) -> jax.Array:
    """``o_i = u_i wkvb_i^V`` for the attended compressed vectors ``u``
    ``[..., H, kv_rank]``, times ``cfg.kv_scale``, which the values would
    have carried; returns ``[..., H * v]`` before ``wo``."""
    o = jnp.einsum("...hc,chd->...hd", u, _wkvb(cfg, lp)[1])
    if cfg.kv_scale != 1.0:
        o = o * jnp.asarray(cfg.kv_scale, o.dtype)
    return o.reshape(o.shape[:-2] + (-1,))


def hc_coefficients(cfg: LatentConfig, phi: jax.Array, alpha: jax.Array,
                    bias: jax.Array, xs: jax.Array):
    """What one sublayer's maps make of the residual streams ``xs`` ``[n,
    ..., D]``: ``(pre [n, ...], post [n, ...], res [n, n, ...])``, float32
    with the tokens minor.  With ``c = (vec(xs) / rms(vec(xs))) phi`` per
    token (``phi`` ``[n, D, n * n + 2 n]``, the norm over all ``n * D``
    values with no learned scale, the product at full matmul precision):
    ``pre = sigmoid(alpha[0] c[:n] + b[:n])`` weighs the streams into the
    sublayer's input, ``post = 2 sigmoid(alpha[1] c[n:2n] + b[n:2n])``
    spreads its output over them, and ``res``, the remix of the streams
    themselves, is ``exp(clip(alpha[2] c[2n:] + b[2n:], +-res_clamp))``
    as an ``n x n`` matrix (row-major) after ``sinkhorn_iters`` rounds of
    column-then-row normalisation with ``hc_eps`` in each denominator:
    doubly stochastic up to the last round's columns."""
    with jax.named_scope("hc.coef"):
        n = xs.shape[0]
        xf = xs.astype(jnp.float32)
        scale = lax.rsqrt(jnp.mean(xf * xf, axis=(0, -1)) + cfg.eps)
        c = jnp.einsum("n...d,ndc->c...", xf, phi.astype(jnp.float32),
                       precision=lax.Precision.HIGHEST) * scale
        alpha = alpha.astype(jnp.float32)
        b = bias.astype(jnp.float32).reshape((-1,) + (1,) * scale.ndim)
        pre = jax.nn.sigmoid(alpha[0] * c[:n] + b[:n])
        post = 2.0 * jax.nn.sigmoid(alpha[1] * c[n:2 * n] + b[n:2 * n])
        res = jnp.exp(jnp.clip(alpha[2] * c[2 * n:] + b[2 * n:],
                               -cfg.res_clamp, cfg.res_clamp))
        res = res.reshape((n, n) + scale.shape)
        for _ in range(cfg.sinkhorn_iters):
            res = res / (jnp.sum(res, 0, keepdims=True) + cfg.hc_eps)
            res = res / (jnp.sum(res, 1, keepdims=True) + cfg.hc_eps)
        return pre, post, res


def hc_mix(w: jax.Array, xs: jax.Array, post: Optional[jax.Array] = None,
           y: Optional[jax.Array] = None) -> jax.Array:
    """``out_k = sum_j w[k, j] xs_j (+ post[k] y)`` over the streams ``xs``
    ``[n, ..., D]`` with per-token weights ``w`` ``[m, n, ...]``: a
    sublayer's input (``w = pre[None]``) and the streams it leaves (``w =
    res`` with its output ``y`` spread by ``post``).  Sums in float32, the
    result in the streams' dtype, stream axis major.  The result is made
    here and nowhere else (an optimization barrier): left to the compiler,
    a sublayer's input is recomputed from all ``n`` streams inside every
    matmul that reads it."""
    with jax.named_scope("hc.mix"):
        out = jnp.sum(w[..., None] * xs.astype(jnp.float32)[None], axis=1)
        if y is not None:
            out = out + post[..., None] * y.astype(jnp.float32)[None]
        return lax.optimization_barrier(out.astype(xs.dtype))


def hc_fan_out(cfg: LatentConfig, x: jax.Array) -> jax.Array:
    """The residual a streamed model's layers carry, from the embedded
    tokens ``x`` ``[..., D]``: every stream a copy, ``[n, ..., D]`` (``x``
    itself where the config has one stream)."""
    if cfg.streams == 1:
        return x
    with jax.named_scope("hc.mix"):
        return jnp.broadcast_to(x[None], (cfg.streams,) + x.shape)


def hc_collapse(cfg: LatentConfig, xs: jax.Array) -> jax.Array:
    """What the read-out sees of :func:`hc_fan_out`'s residual: the sum of
    the streams."""
    if cfg.streams == 1:
        return xs
    with jax.named_scope("hc.mix"):
        return jnp.sum(xs, axis=0, dtype=jnp.float32).astype(xs.dtype)


def latent_block(cfg: LatentConfig, lp: Dict[str, jax.Array], x: jax.Array,
                 positions: jax.Array, attend: Callable,
                 ffn: Callable) -> Tuple[jax.Array, Any, Any]:
    """One pre-norm latent block on ``x`` ``[..., D]``: RMSNorm →
    ``attend`` → ``wo`` → RMSNorm → ``ffn``, both halves residual.
    ``attend(q_nope, q_rope, latent) -> (att [..., H * v], aux)`` gets the
    block's own projections (:func:`mla_project`) and meets the sequence
    (:func:`mla_unabsorbed`) or the cache (the engine's absorbed form);
    ``ffn(lp, h) -> (y, faux)`` as in :func:`decoder_block`.  Where the
    config has several residual streams ``x`` is all of them ``[n, ...,
    D]`` (:func:`hc_fan_out`): each half reads their learned combination
    and leaves them remixed with its output spread over them, where the
    plain residual is ``x + y`` (:func:`hc_coefficients`; leaves
    ``h1p``/``h1a``/``h1b`` for attention, ``h2*`` for the FFN)."""
    def half(x, k, scope, sublayer):
        if cfg.streams == 1:
            y, aux = sublayer(x)
            with scope:             # the plain residual, in its half's scope
                return x + y, aux
        pre, post, res = hc_coefficients(
            cfg, lp[f"h{k}p"], lp[f"h{k}a"], lp[f"h{k}b"], x)
        y, aux = sublayer(hc_mix(pre[None], x)[0])
        return hc_mix(res, x, post, y), aux

    def attention(h):
        with jax.named_scope("mla.project"):
            h = rms_norm(h, lp["g1"], cfg.eps)
        att, aux = attend(*mla_project(cfg, lp, h, positions))
        with jax.named_scope("mla.project"):
            return att @ lp["wo"], aux

    def feed_forward(h):
        with jax.named_scope("ffn"):
            return ffn(lp, rms_norm(h, lp["g2"], cfg.eps))
    x, aux = half(x, 1, jax.named_scope("mla.project"), attention)
    x, faux = half(x, 2, jax.named_scope("ffn"), feed_forward)
    return x, aux, faux


def latent_double_block(cfg: LatentConfig, lp: Dict[str, jax.Array],
                        lp2: Dict[str, jax.Array], x: jax.Array,
                        positions: jax.Array, attend: Callable,
                        attend2_of: Callable, moe: Callable):
    """One shortcut-connected double layer on ``x`` ``[..., D]``: two
    :func:`latent_block` halves, each a latent attention and a dense gated
    FFN with its own leaves (``lp``, ``lp2``) and its own ``attend`` hook
    (its own cached vectors; the second half's is ``attend2_of(aux)``,
    built from what the first half's hook handed back, so that it meets the
    cache as the first half left it), and ONE expert layer ``moe(lp, h) -> (m,
    faux)`` that reads the FIRST half's normed post-attention activation
    and whose result ``m`` is added after the SECOND half's FFN::

        x1 = x  + MLA_1(RMS(x;  g1_1));   h1 = RMS(x1; g2_1)
        m  = MoE(h1);                     x2 = x1 + FFN_1(h1)
        x3 = x2 + MLA_2(RMS(x2; g1_2))
        x4 = x3 + FFN_2(RMS(x3; g2_2)) + m

    Nothing between ``m``'s making and its use reads it: where the experts
    lie on other chips, their exchange may run under the first FFN and the
    whole second half.  Returns ``(x4, (aux, aux2), faux)``."""
    def first_ffn(lp, h):
        m, faux = moe(lp, h)
        return dense_gated_ffn(lp, h)[0], (m, faux)

    x, aux, (m, faux) = latent_block(cfg, lp, x, positions, attend,
                                     first_ffn)
    x, aux2, _ = latent_block(
        cfg, lp2, x, positions, attend2_of(aux),
        lambda lp, h: (dense_gated_ffn(lp, h)[0] + m, None))
    return x, (aux, aux2), faux


def latent_logits(cfg: LatentConfig, shared: Dict[str, jax.Array],
                  x: jax.Array) -> jax.Array:
    """Final RMSNorm and read-out over this chip's vocabulary slice."""
    with jax.named_scope("readout"):
        return rms_norm(x, shared["gf"], cfg.eps) @ shared["head"]


def latent_param_shapes(cfg: LatentConfig) -> Dict[str, Dict[str, tuple]]:
    """The latent model's parameter tree as shapes: ``first`` (the leading
    dense layer), ``dense`` (the further leading dense layers, stacked;
    only where ``dense_layers > 1``), ``blocks`` (the expert layers,
    stacked), ``shared``.  Names that start with ``g`` are RMSNorm scales;
    ``wr`` is the router, kept in float32, as are its selection bias
    ``eb`` (``route_bias``) and the stream maps of each half (``streams``:
    ``h1p``/``h2p`` ``[n, D, n * n + 2 n]``, the three gains ``h1a``/``h2a``
    and the biases ``h1b``/``h2b``).  A ``shortcut`` model has no
    ``first``: ``blocks`` holds each double layer's first half (attention,
    dense FFN ``wg``/``wu``/``wd``) with the expert layer's leaves, and
    ``blocks2`` its second half, stacked alike; an identity expert has no
    leaf, and without ``shared_expert`` there is no ``wsg``/``wsu``/``wsd``."""
    D, H = cfg.d_model, cfg.heads
    Fe, Eh, Lx = cfg.expert_ffn, cfg.held_experts, cfg.expert_layers
    attn = {"g1": (D,), "wqa": (D, cfg.q_rank), "gq": (cfg.q_rank,),
            "wqb": (cfg.q_rank, H * (cfg.nope_dim + cfg.rope_dim)),
            "wkva": (D, cfg.latent_dim), "gkv": (cfg.kv_rank,),
            "wkvb": (cfg.kv_rank, H * (cfg.nope_dim + cfg.v_dim)),
            "wo": (H * cfg.v_dim, D), "g2": (D,)}
    if cfg.streams > 1:
        n = cfg.streams
        for half in "12":
            attn.update({f"h{half}p": (n, D, n * n + 2 * n),
                         f"h{half}a": (3,), f"h{half}b": (n * n + 2 * n,)})
    first = dict(attn, wg=(D, cfg.dense_ffn), wu=(D, cfg.dense_ffn),
                 wd=(cfg.dense_ffn, D))
    expert = dict(first if cfg.shortcut else attn, wr=(D, cfg.num_experts))
    if cfg.shared_expert:
        expert.update(wsg=(D, Fe), wsu=(D, Fe), wsd=(Fe, D))
    expert.update(weg=(Eh, D, Fe), weu=(Eh, D, Fe), wed=(Eh, Fe, D))
    if cfg.route_bias:
        expert["eb"] = (cfg.num_experts,)
    out = {} if cfg.shortcut else {"first": first}
    if cfg.dense_layers > 1:
        out["dense"] = {k: (cfg.dense_layers - 1,) + v
                        for k, v in first.items()}
    out["blocks"] = {k: (Lx,) + v for k, v in expert.items()}
    if cfg.shortcut:
        out["blocks2"] = {k: (Lx,) + v for k, v in first.items()}
    out["shared"] = {"embed": (cfg.vocab, D), "head": (D, cfg.vocab),
                     "gf": (D,)}
    return out


def latent_param_count(cfg: LatentConfig) -> int:
    """Parameters this chip holds (:func:`latent_param_shapes`)."""
    return sum(math.prod(s) for group in latent_param_shapes(cfg).values()
               for s in group.values())


# ---------------------------------------------------------------------------
# The hybrid block: window and full attention layers in one model, grouped
# query heads with QK-norm, the norm on each sublayer's OUTPUT
# ---------------------------------------------------------------------------

LAYER_KINDS = ("window", "full")
FFN_KINDS = ("dense", "experts")


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    """Sizes of a decoder whose layers attend in two ways, as ONE chip of
    an expert-parallel deployment holds it.  ``plan`` is the layer plan,
    one ``(kind, ffn)`` per layer: a ``"window"`` layer sees the last
    ``window`` positions (the query's own included) and turns q and k by
    rotary, a ``"full"`` layer sees every earlier position and turns
    nothing; ``"dense"`` is one gated FFN of width ``dense_ffn``,
    ``"experts"`` the sigmoid-routed layer of which this chip holds
    ``held_experts`` from ``held_start`` beside the shared expert (the
    held-experts contract of :class:`LatentConfig`).  ``heads`` query
    heads share ``kv_heads`` keys and values, ``heads // kv_heads`` each."""
    vocab: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    window: int
    plan: Tuple[Tuple[str, str], ...]
    dense_ffn: int
    expert_ffn: int
    num_experts: int                # the router's outputs
    held_experts: int
    top_k: int
    route_scale: float
    held_start: int = 0
    n_group: int = 1                # 1 group of which 1 stays: plain top-k
    topk_group: int = 1
    rope_base: float = 1e6
    eps: float = 1e-5

    @property
    def layers(self) -> int:
        return len(self.plan)

    @property
    def expert_layers(self) -> int:
        return sum(ffn == "experts" for _, ffn in self.plan)

    def layers_of(self, kind: str) -> int:
        return sum(k == kind for k, _ in self.plan)

    def index_in_kind(self, layer: int) -> int:
        """Which of its kind's layers ``layer`` is: its place in that
        kind's part of the cache."""
        kind = self.plan[layer][0]
        return sum(k == kind for k, _ in self.plan[:layer])

    def validate(self, m: Any) -> None:
        for name in ("vocab", "d_model", "heads", "kv_heads", "head_dim",
                     "window", "dense_ffn", "expert_ffn", "num_experts",
                     "held_experts", "top_k", "n_group", "topk_group"):
            if getattr(self, name) < 1:
                raise ValueError(f"HybridConfig.{name} must be >= 1")
        if not self.plan or any(
                len(p) != 2 or p[0] not in LAYER_KINDS or p[1] not in FFN_KINDS
                for p in self.plan):
            raise ValueError(
                f"hybrid_layer_plan: every layer is (kind in {LAYER_KINDS}, "
                f"ffn in {FFN_KINDS}), got {self.plan!r}")
        if self.heads % self.kv_heads:
            raise ValueError(
                f"hybrid_grouped_heads: {self.heads} query heads do not "
                f"share {self.kv_heads} key-value heads evenly")
        if self.head_dim % 2:
            raise ValueError("head_dim must be even")
        if self.num_experts % self.n_group or \
                not self.topk_group <= self.n_group or \
                self.top_k > self.topk_group * (self.num_experts
                                                // self.n_group):
            raise ValueError(
                f"hybrid_router_groups: {self.top_k} of {self.num_experts} "
                f"experts in {self.n_group} groups of which "
                f"{self.topk_group} are kept")
        if not 0 <= self.held_start <= self.num_experts - self.held_experts:
            raise ValueError(
                f"hybrid_held_experts: experts {self.held_start}.."
                f"{self.held_start + self.held_experts - 1} are not among "
                f"the router's {self.num_experts}")


def window_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     window: int) -> jax.Array:
    """Causal attention of one whole sequence under a band, with grouped
    heads: ``q`` ``[T, H, Dh]``, ``k``/``v`` ``[T, Hkv, Dh]``, q head ``h``
    on kv head ``h // (H // Hkv)`` (K and V are never repeated in memory);
    a query at ``t`` sees the keys ``t - window + 1 .. t``.  The sequence
    is cut into blocks of ``window`` positions and a block of queries
    meets its own block of keys and the one before it, nothing else: ``2
    * window`` scores a query instead of ``T``.  Returns ``[T, H, Dh]``."""
    T, H, Dh = q.shape
    Hkv, W = k.shape[1], window
    G, pad = H // Hkv, (-T) % W
    if pad:                     # padding lies behind every real query
        q, k, v = (jnp.pad(a, ((0, pad), (0, 0), (0, 0))) for a in (q, k, v))
    nb = (T + pad) // W
    qb = q.reshape(nb, W, Hkv, G, Dh)

    def with_previous(a):                      # [nb, 2W, Hkv, Dh]
        a = a.reshape(nb, W, Hkv, Dh)
        return jnp.concatenate(
            [jnp.concatenate([jnp.zeros_like(a[:1]), a[:-1]]), a], axis=1)
    kk, vv = with_previous(k), with_previous(v)
    # key b of block n sits at (n - 1) W + b, query a at n W + a
    behind = W + jnp.arange(W)[:, None] - jnp.arange(2 * W)[None, :]
    keep = (behind >= 0) & (behind < W)
    keep = keep[None] & ((jnp.arange(nb) > 0)[:, None, None]
                         | (jnp.arange(2 * W) >= W)[None, None, :])

    def blocks(args):
        qq, kc, vc, kp = args        # [c, W, Hkv, G, Dh], [c, 2W, Hkv, Dh]
        s = jnp.einsum("nqkgd,nskd->nkgqs", qq, kc,
                       preferred_element_type=jnp.float32) * Dh ** -0.5
        p = jax.nn.softmax(jnp.where(kp[:, None, None], s, -jnp.inf), -1)
        return jnp.einsum("nkgqs,nskd->nqkgd", p.astype(vc.dtype), vc,
                          preferred_element_type=jnp.float32).astype(q.dtype)

    c = _chunks(nb, H * W * 2 * W * 4)
    if c == nb:
        out = blocks((qb, kk, vv, keep))
    else:
        split = lambda a: a.reshape((nb // c, c) + a.shape[1:])
        out = lax.map(blocks, tuple(map(split, (qb, kk, vv, keep))))
    return out.reshape(nb * W, H, Dh)[:T]


def hybrid_block(cfg: HybridConfig, lp: Dict[str, jax.Array], x: jax.Array,
                 positions: jax.Array, kind: str, attend: Callable,
                 ffn: Callable) -> Tuple[jax.Array, Any, Any]:
    """One block of a ``kind`` layer on ``x`` ``[..., D]``: each sublayer
    reads the residual stream itself and its OUTPUT is normed before it
    is added, ``x += RMS(Attn(x); g1)`` then ``x += RMS(FFN(x); g2)``.
    Attention: separate ``wq`` / ``wk`` / ``wv`` (``heads`` queries,
    ``kv_heads`` keys and values), RMSNorm over each head of q and k with
    one scale vector for all heads (``gq``, ``gk``), then, on a
    ``"window"`` layer only, rotary over the whole head.  ``attend(q, k,
    v) -> (att, aux)`` takes ``[..., heads, head_dim]`` and ``[...,
    kv_heads, head_dim]`` x 2 and returns the attention output in q's
    shape plus what the caller's cache hooks made; ``ffn(lp, x) -> (y,
    faux)`` as in :func:`decoder_block`."""
    lead = x.shape[:-1]
    with jax.named_scope(f"attn.{kind}"):
        with jax.named_scope("attn.project"):
            q = (x @ lp["wq"]).reshape(lead + (cfg.heads, cfg.head_dim))
            k = (x @ lp["wk"]).reshape(lead + (cfg.kv_heads, cfg.head_dim))
            v = (x @ lp["wv"]).reshape(lead + (cfg.kv_heads, cfg.head_dim))
            q = rms_norm(q, lp["gq"], cfg.eps)
            k = rms_norm(k, lp["gk"], cfg.eps)
            if kind == "window":
                q = rope(q, positions, cfg.rope_base)
                k = rope(k, positions, cfg.rope_base)
        att, aux = attend(q, k, v)
        with jax.named_scope("attn.project"):
            x = x + rms_norm(att.reshape(lead + (-1,)) @ lp["wo"], lp["g1"],
                             cfg.eps)
    with jax.named_scope("ffn"):
        y, faux = ffn(lp, x)
        return x + rms_norm(y, lp["g2"], cfg.eps), aux, faux


def hybrid_param_shapes(cfg: HybridConfig) -> Dict[str, Any]:
    """The hybrid model's parameter tree as shapes: ``layers``, one dict
    of leaves per layer of the plan (nothing stacked: a layer's leaves
    are whole arrays and no program slices a stack), and ``shared``.
    Names that start with ``g`` are RMSNorm scales; ``wr`` is the router,
    kept in float32."""
    D, Hd = cfg.d_model, cfg.head_dim
    Fe, Eh = cfg.expert_ffn, cfg.held_experts
    attn = {"wq": (D, cfg.heads * Hd), "wk": (D, cfg.kv_heads * Hd),
            "wv": (D, cfg.kv_heads * Hd), "gq": (Hd,), "gk": (Hd,),
            "wo": (cfg.heads * Hd, D), "g1": (D,), "g2": (D,)}
    ffn = {"dense": {"wg": (D, cfg.dense_ffn), "wu": (D, cfg.dense_ffn),
                     "wd": (cfg.dense_ffn, D)},
           "experts": {"wr": (D, cfg.num_experts), "wsg": (D, Fe),
                       "wsu": (D, Fe), "wsd": (Fe, D), "weg": (Eh, D, Fe),
                       "weu": (Eh, D, Fe), "wed": (Eh, Fe, D)}}
    return {"layers": tuple(dict(attn, **ffn[f]) for _, f in cfg.plan),
            "shared": {"embed": (cfg.vocab, D), "head": (D, cfg.vocab),
                       "gf": (D,)}}


def hybrid_param_count(cfg: HybridConfig) -> int:
    """Parameters this chip holds (:func:`hybrid_param_shapes`)."""
    shapes = hybrid_param_shapes(cfg)
    return sum(math.prod(s) for group in shapes["layers"] + (shapes["shared"],)
               for s in group.values())


# ---------------------------------------------------------------------------
# The single-mixer block: every layer ONE mixer behind one RMSNorm, by a
# static plan a recurrent mixer (Mamba-2, or the gated delta rule with a
# decay per channel), attention that turns nothing, or sigmoid-routed experts
# ---------------------------------------------------------------------------

MIXER_KINDS = ("ssm", "delta", "full", "experts")
# the kinds that keep a recurrent state a slot; a plan holds at most one
RECURRENT_KINDS = ("ssm", "delta")
EXPERT_FORMS = ("relu2", "gated_silu")
# leaves kept in float32 whatever the served dtype: the router and its
# selection bias (near-ties), and what the scan's exponents are made of
FLOAT32_LEAVES = frozenset(("wr", "eb", "dt_bias", "A_log", "Dskip"))


@dataclasses.dataclass(frozen=True)
class SsmConfig:
    """Sizes of a decoder whose every layer is one mixer, ``x += mixer(
    RMS(x))``, as ONE chip of an expert-parallel deployment holds it (a
    pre-norm layer of two sublayers is two entries).  ``plan`` names each
    layer's mixer.  Two kinds are recurrent: per sequence a state
    ``[ssm_heads, ssm_head_dim, ssm_state]`` in float32 and the last
    ``conv_kernel - 1`` inputs of a causal depthwise convolution, no
    positions; a plan holds one of the two.  ``"ssm"`` is a Mamba-2 mixer
    (``ssm_heads`` heads of ``ssm_head_dim`` channels, ``B`` and ``C`` in
    ``ssm_groups`` groups of ``ssm_state``, one scalar decay a head).
    ``"delta"`` is the gated delta rule with a decay per CHANNEL (Kimi
    Delta Attention): ``ssm_heads`` heads whose keys and queries have
    ``ssm_head_dim`` channels and whose values have ``ssm_state``, q, k and
    v convolved side by side and q, k L2-normed, the decay and the output
    gate through low-rank pairs of ``delta_rank``, ``beta = delta_beta_max
    * sigmoid``.  Either scans a prompt in chunks of ``chunk``.  ``"full"``
    is causal attention of ``heads`` queries on ``kv_heads`` keys and
    values with no position signal (the recurrent layers carry the order),
    its output under an elementwise sigmoid gate where ``attn_gate``.
    ``"experts"`` is the sigmoid-routed layer whose experts of width
    ``expert_ffn`` (``expert_form``: ``"relu2"`` with no gate, or
    ``"gated_silu"``) read and write a ``latent``-wide projection of the
    hidden state, or the hidden state itself where ``latent`` is 0, beside
    a shared expert of width ``shared_ffn`` of the same form on the hidden
    state (the held-experts contract of :class:`LatentConfig`)."""
    vocab: int
    d_model: int
    plan: Tuple[str, ...]
    ssm_heads: int
    ssm_head_dim: int
    ssm_groups: int
    ssm_state: int
    heads: int
    kv_heads: int
    head_dim: int
    latent: int
    expert_ffn: int
    shared_ffn: int
    num_experts: int                # the router's outputs
    held_experts: int
    top_k: int
    route_scale: float
    held_start: int = 0
    route_bias: bool = True         # select by score + e_bias
    conv_kernel: int = 4
    chunk: int = 128
    eps: float = 1e-5               # every layer's RMSNorm and the final one
    ssm_eps: float = 1e-5           # the norm inside a recurrent mixer
    expert_form: str = "relu2"
    attn_gate: bool = False         # sigmoid(h wgate) on attention's output
    delta_rank: int = 0             # the delta mixer's low-rank pairs
    delta_beta_max: float = 1.0     # 2: the state's map may turn a key round
    # no fields: this router has no group step (held_moe_ffn reads them)
    n_group = 1
    topk_group = 1

    @property
    def layers(self) -> int:
        return len(self.plan)

    @property
    def expert_layers(self) -> int:
        return self.layers_of("experts")

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def recurrent(self) -> Optional[str]:
        """The plan's recurrent kind, where it has one."""
        return next((k for k in RECURRENT_KINDS if k in self.plan), None)

    @property
    def conv_dim(self) -> int:
        """Channels the convolution runs over: x, B and C side by side (a
        delta mixer's q, k and v)."""
        if self.recurrent == "delta":
            return self.ssm_heads * (2 * self.ssm_head_dim + self.ssm_state)
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state

    def layers_of(self, kind: str) -> int:
        return sum(k == kind for k in self.plan)

    def index_in_kind(self, layer: int) -> int:
        """Which of its kind's layers ``layer`` is: its place in that
        kind's part of the cache."""
        return sum(k == self.plan[layer] for k in self.plan[:layer])

    def validate(self, m: Any) -> None:
        for name in ("vocab", "d_model", "ssm_heads", "ssm_head_dim",
                     "ssm_groups", "ssm_state", "heads", "kv_heads",
                     "head_dim", "expert_ffn", "shared_ffn",
                     "num_experts", "held_experts", "top_k", "chunk"):
            if getattr(self, name) < 1:
                raise ValueError(f"SsmConfig.{name} must be >= 1")
        if not self.plan or any(k not in MIXER_KINDS for k in self.plan):
            raise ValueError(
                f"ssm_layer_plan: every layer is one mixer of {MIXER_KINDS}, "
                f"got {self.plan!r}")
        if all(k in self.plan for k in RECURRENT_KINDS):
            raise ValueError(
                f"ssm_recurrent_kinds: a slot keeps ONE shape of state; the "
                f"plan holds both of {RECURRENT_KINDS}")
        if self.expert_form not in EXPERT_FORMS:
            raise ValueError(
                f"ssm_expert_form: {self.expert_form!r} is none of "
                f"{EXPERT_FORMS}")
        if self.latent < 0:
            raise ValueError(
                f"ssm_latent: a latent of {self.latent} (0: the experts "
                "read the hidden state itself)")
        if "delta" in self.plan and self.delta_rank < 1:
            raise ValueError(
                "ssm_delta_rank: a delta mixer's decay and gate go through "
                f"low-rank pairs; their rank is {self.delta_rank}")
        if "delta" in self.plan and self.chunk & (self.chunk - 1):
            raise ValueError(
                f"ssm_delta_chunk: the delta rule halves a chunk down to "
                f"single positions; {self.chunk} is no power of two")
        if self.ssm_heads % self.ssm_groups:
            raise ValueError(
                f"ssm_head_groups: {self.ssm_heads} state-space heads do not "
                f"share {self.ssm_groups} groups of B and C evenly")
        if self.conv_kernel < 2:
            raise ValueError(
                f"ssm_conv_kernel: a convolution of {self.conv_kernel} tap "
                "keeps no input between steps")
        if self.heads % self.kv_heads:
            raise ValueError(
                f"ssm_grouped_heads: {self.heads} query heads do not share "
                f"{self.kv_heads} key-value heads evenly")
        if self.top_k > self.num_experts:
            raise ValueError(
                f"ssm_router_top_k: {self.top_k} experts a token of the "
                f"router's {self.num_experts}")
        if not 0 <= self.held_start <= self.num_experts - self.held_experts:
            raise ValueError(
                f"ssm_held_experts: experts {self.held_start}.."
                f"{self.held_start + self.held_experts - 1} are not among "
                f"the router's {self.num_experts}")


def relu2_ffn(h: jax.Array, w1: jax.Array, w2: jax.Array) -> jax.Array:
    """``relu(h w1)^2 w2``: no gate."""
    return jnp.square(jax.nn.relu(h @ w1)) @ w2


def mamba_project(cfg: SsmConfig, lp: Dict[str, jax.Array], h: jax.Array):
    """The Mamba mixer's one input projection of the normed activation
    ``h`` ``[..., D]``, split: ``(z [..., d_inner]`` the gate, ``xBC [...,
    conv_dim]`` what the convolution runs over, ``dt [..., ssm_heads]`` the
    raw step sizes)."""
    with jax.named_scope("ssm.project"):
        u = h @ lp["w_in"]
        d, c = cfg.d_inner, cfg.conv_dim
        return u[..., :d], u[..., d:d + c], u[..., d + c:]


def _silu_conv(cfg: SsmConfig, lp: Dict[str, jax.Array], taps):
    """``silu(b_conv + sum_j w_conv[:, j] taps[j])`` in float32 (no
    ``b_conv`` where the layer has none); ``taps`` oldest first, the
    current input last."""
    w = lp["w_conv"].astype(jnp.float32)
    acc = lp["b_conv"].astype(jnp.float32) if "b_conv" in lp else 0.0
    for j, tap in enumerate(taps):
        acc = acc + w[:, j] * tap.astype(jnp.float32)
    return jax.nn.silu(acc)


def mamba_conv(cfg: SsmConfig, lp: Dict[str, jax.Array], xbc: jax.Array,
               prev: Optional[jax.Array] = None,
               true_len: Optional[jax.Array] = None):
    """The causal depthwise convolution over time (``conv_kernel`` taps,
    ``w_conv`` ``[conv_dim, taps]`` with the current input under the LAST
    tap, bias ``b_conv``) and the SiLU behind it.  A prompt (``prev``
    None): ``xbc`` ``[T, conv_dim]`` with zeros before it; returns the
    convolved sequence and the ``taps - 1`` raw inputs before ``true_len``
    (zeros where the prompt is shorter), what a decode step needs of it.
    A decode step: ``xbc`` ``[S, conv_dim]`` behind each lane's kept inputs
    ``prev`` ``[S, taps - 1, conv_dim]``; returns the lanes' outputs and
    their kept inputs moved on by one.  Sums in float32, the result in
    ``xbc``'s dtype; the kept inputs are raw, in ``xbc``'s dtype."""
    with jax.named_scope("ssm.conv"):
        K = cfg.conv_kernel
        if prev is not None:
            window = jnp.concatenate([prev.astype(xbc.dtype), xbc[:, None]],
                                     axis=1)                # [S, K, C]
            out = _silu_conv(cfg, lp, [window[:, j] for j in range(K)])
            return out.astype(xbc.dtype), window[:, 1:]
        T = xbc.shape[0]
        padded = jnp.concatenate(
            [jnp.zeros((K - 1,) + xbc.shape[1:], xbc.dtype), xbc])
        out = _silu_conv(cfg, lp, [padded[j:j + T] for j in range(K)])
        # padded[i + K - 1] is xbc[i]: the K - 1 inputs before true_len
        kept = lax.dynamic_slice_in_dim(padded, true_len, K - 1)
        return out.astype(xbc.dtype), kept


def mamba_split(cfg: SsmConfig, xbc: jax.Array):
    """The convolved channels as ``(x [..., heads, head_dim], B [...,
    groups, state], C [..., groups, state])``."""
    lead, d = xbc.shape[:-1], cfg.d_inner
    gn = cfg.ssm_groups * cfg.ssm_state
    shape = lead + (cfg.ssm_groups, cfg.ssm_state)
    return (xbc[..., :d].reshape(lead + (cfg.ssm_heads, cfg.ssm_head_dim)),
            xbc[..., d:d + gn].reshape(shape),
            xbc[..., d + gn:].reshape(shape))


def mamba_discretize(lp: Dict[str, jax.Array], x: jax.Array, dt: jax.Array,
                     live: Optional[jax.Array] = None):
    """Per head the step ``delta = softplus(dt + dt_bias)`` (no clamp), its
    log decay ``delta * A`` with ``A = -exp(A_log)`` (so ``<= 0``) and the
    input it lets in, ``delta * x``: ``(log_a [..., H], dx [..., H, P])``,
    float32.  Where ``live`` is false the step is 0: the state passes such
    a position unchanged (a prompt's padding)."""
    f32 = jnp.float32
    delta = jax.nn.softplus(dt.astype(f32) + lp["dt_bias"].astype(f32))
    if live is not None:
        delta = jnp.where(live[..., None], delta, 0.0)
    return (delta * -jnp.exp(lp["A_log"].astype(f32)),
            delta[..., None] * x.astype(f32))


def _per_head(cfg: SsmConfig, t: jax.Array, axis: int) -> jax.Array:
    """``t`` with its ``heads`` axis split ``[groups, heads per group]``."""
    return t.reshape(t.shape[:axis] + (cfg.ssm_groups, -1)
                     + t.shape[axis + 1:])


def mamba_scan_chunked(cfg: SsmConfig, lp: Dict[str, jax.Array],
                       x: jax.Array, B: jax.Array, C: jax.Array,
                       dt: jax.Array, true_len: jax.Array):
    """The state-space recurrence ``S_t = a_t S_{t-1} + delta_t x_t (x)
    B_t``, ``y_t = S_t C_t + Dskip x_t`` (``S_{-1} = 0``) over one prompt
    ``x`` ``[T, heads, head_dim]``, ``B``/``C`` ``[T, groups, state]``,
    ``dt`` ``[T, heads]``, in chunks of ``cfg.chunk`` positions: within a
    chunk the masked product ``(C B^T o decay) (delta x)``, between chunks
    the carried state.  Every exponent is a difference of cumulative
    ``delta A`` that is ``<= 0``; everything in float32, the four matrix
    products at the backend's default matmul precision (a TPU rounds their
    operands to bfloat16 and accumulates in float32: the state then lies
    0.002 of its norm from the token-by-token recurrence).  Positions from
    ``true_len`` on take a step of 0, so the returned state ``[heads,
    head_dim, state]`` is the one after the last REAL token.  Returns
    ``(y [T, heads, head_dim]`` in ``x``'s dtype, state float32)."""
    with jax.named_scope("ssm.scan"):
        T, H, P = x.shape
        G, N, Q = cfg.ssm_groups, cfg.ssm_state, cfg.chunk
        pad = (-T) % Q
        if pad:                         # behind every real position
            x, B, C, dt = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                           for a in (x, B, C, dt))
        nc = (T + pad) // Q
        live = jnp.arange(T + pad) < jnp.minimum(true_len, T)
        log_a, dx = mamba_discretize(lp, x, dt, live)
        dx = _per_head(cfg, dx, 1).reshape(nc, Q, G, H // G, P)
        cum = jnp.cumsum(_per_head(cfg, log_a, 1).reshape(
            nc, Q, G, H // G), axis=1)                      # inclusive
        Bc = B.astype(jnp.float32).reshape(nc, Q, G, N)
        Cc = C.astype(jnp.float32).reshape(nc, Q, G, N)
        # within a chunk: s <= t
        seen = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]
        seen = seen[None, :, :, None, None]
        decay = jnp.exp(jnp.where(seen, cum[:, :, None] - cum[:, None], 0.0))
        scores = jnp.einsum("ctgn,csgn->ctsg", Cc, Bc)
        y = jnp.einsum("ctsgr,csgrp->ctgrp",
                       jnp.where(seen, decay, 0.0) * scores[..., None], dx)
        # what each chunk adds to the state by its end, and the state each
        # chunk starts from
        to_end = jnp.exp(cum[:, -1:] - cum)                 # [nc, Q, G, R]
        local = jnp.einsum("csgr,csgrp,csgn->cgrpn", to_end, dx, Bc)

        def carry(S, chunk):
            total, add = chunk
            return jnp.exp(total)[..., None, None] * S + add, S
        S, starts = lax.scan(
            carry, jnp.zeros((G, H // G, P, N), jnp.float32),
            (cum[:, -1], local))
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "ctgn,cgrpn->ctgrp", Cc, starts)
        y = y.reshape(T + pad, H, P)[:T] \
            + lp["Dskip"].astype(jnp.float32)[:, None] * x[:T].astype(
                jnp.float32)
        return y.astype(x.dtype), S.reshape(H, P, N)


def mamba_step(cfg: SsmConfig, state: jax.Array, log_a: jax.Array,
               dx: jax.Array, B: jax.Array, C: jax.Array):
    """One step of the recurrence for a batch of states ``[R, heads,
    head_dim, state]`` (float32): ``S <- exp(log_a) S + dx (x) B``, ``y = S
    C`` with ``log_a`` ``[R, heads]`` and ``dx`` ``[R, heads, head_dim]``
    from :func:`mamba_discretize` and ``B``/``C`` ``[R, groups, state]``.
    A batch entry with ``log_a = 0`` and ``dx = 0`` keeps its state as it
    is.  Returns ``(y [R, heads, head_dim] float32, the new states)``; the
    skip ``Dskip x`` is the caller's to add."""
    of_head = lambda t: jnp.repeat(t.astype(jnp.float32),
                                   cfg.ssm_heads // cfg.ssm_groups, axis=1)
    new = jnp.exp(log_a)[..., None, None] * state \
        + dx[..., None] * of_head(B)[:, :, None, :]
    return jnp.sum(new * of_head(C)[:, :, None, :], axis=-1), new


def mamba_gate_out(cfg: SsmConfig, lp: Dict[str, jax.Array], y: jax.Array,
                   z: jax.Array) -> jax.Array:
    """What leaves the Mamba mixer: ``y`` ``[..., heads, head_dim]`` gated
    by ``silu(z)`` FIRST, then RMS-normed per group of ``d_inner /
    ssm_groups`` channels under the scale ``g_y``, then ``w_out``."""
    with jax.named_scope("ssm.project"):
        lead = z.shape[:-1]
        v = y.reshape(lead + (-1,)).astype(jnp.float32) \
            * jax.nn.silu(z.astype(jnp.float32))
        v = v.reshape(lead + (cfg.ssm_groups, -1))
        v = v * lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + cfg.ssm_eps)
        v = v.reshape(lead + (-1,)) * lp["g_y"].astype(jnp.float32)
        return v.astype(z.dtype) @ lp["w_out"]


def delta_project(cfg: SsmConfig, lp: Dict[str, jax.Array], h: jax.Array):
    """The delta mixer's projections of the normed activation ``h`` ``[...,
    D]``: ``(qkv [..., conv_dim]`` what the convolution runs over, q, k and
    v side by side, ``f [..., heads * head_dim]`` the raw decay of every key
    channel through its low-rank pair, ``b [..., heads]`` the raw ``beta``,
    ``z [..., delta_rank]`` the output gate's input)."""
    with jax.named_scope("ssm.project"):
        return (h @ lp["w_in"], (h @ lp["wfa"]) @ lp["wfb"], h @ lp["wb"],
                h @ lp["wga"])


def delta_split(cfg: SsmConfig, qkv: jax.Array):
    """The convolved channels as ``(q, k [..., heads, head_dim], v [...,
    heads, state])``, q and k each scaled to unit length per head (``1e-6``
    under the root) and q by ``head_dim ** -0.5``; in ``qkv``'s dtype."""
    with jax.named_scope("ssm.project"):
        H, K, V = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
        lead = qkv.shape[:-1]

        def unit(t, scale):
            t = t.reshape(lead + (H, K)).astype(jnp.float32)
            t = t * (scale * lax.rsqrt(
                jnp.sum(t * t, -1, keepdims=True) + 1e-6))
            return t.astype(qkv.dtype)
        return (unit(qkv[..., :H * K], K ** -0.5),
                unit(qkv[..., H * K:2 * H * K], 1.0),
                qkv[..., 2 * H * K:].reshape(lead + (H, V)))


def delta_discretize(cfg: SsmConfig, lp: Dict[str, jax.Array], f: jax.Array,
                     b: jax.Array, live: Optional[jax.Array] = None):
    """Per head and key channel the log decay ``g = -exp(A_log) *
    softplus(f + dt_bias)`` (so ``<= 0``) and per head ``beta =
    delta_beta_max * sigmoid(b)``: ``(g [..., H, K], beta [..., H])``,
    float32.  Where ``live`` is false both are 0: the state passes such a
    position unchanged (a prompt's padding)."""
    f32 = jnp.float32
    lead = b.shape[:-1]
    g = jax.nn.softplus(f.astype(f32) + lp["dt_bias"].astype(f32)).reshape(
        lead + (cfg.ssm_heads, cfg.ssm_head_dim)) \
        * -jnp.exp(lp["A_log"].astype(f32))[:, None]
    beta = cfg.delta_beta_max * jax.nn.sigmoid(b.astype(f32))
    if live is not None:
        g = jnp.where(live[..., None, None], g, 0.0)
        beta = jnp.where(live[..., None], beta, 0.0)
    return g, beta


# a prompt passes the delta mixer this many positions at a time: what a block
# holds between the convolution and the kernel (q, k, v and the decays,
# [block, heads, head_dim] each) stays in the tens of MB at any prompt length
_DELTA_BLOCK = 1024


def delta_scan_chunked(cfg: SsmConfig, lp: Dict[str, jax.Array],
                       qkv: jax.Array, f: jax.Array, b: jax.Array,
                       true_len: jax.Array):
    """The gated delta rule ``S_t = (I - beta_t k_t k_t^T) diag(exp g_t)
    S_{t-1} + beta_t k_t v_t^T``, ``o_t = S_t^T q_t`` (``S_{-1} = 0``) over
    one prompt, from the RAW projections of :func:`delta_project` (``qkv``
    ``[T, conv_dim]`` with zeros before it, ``f``, ``b``).  The prompt passes
    in blocks of ``_DELTA_BLOCK`` positions: a block is convolved behind the
    last inputs of the block before it (:func:`mamba_conv`'s sum and SiLU; a
    16,384-token prompt convolved whole would hold its 24,576 channels three
    times over), split and normed (:func:`delta_split`), discretised
    (:func:`delta_discretize`), and handed with the state the block before
    left to the kernel :func:`bluefog_tpu.ops.pallas_delta.delta_rule`,
    which makes everything else in VMEM: the chunks' systems, their
    inverses and the products with the state (in chunks of ``cfg.chunk``,
    several of them solved against each other before the state meets them;
    EVERY exponent a sum of ``g`` that is ``<= 0``, so a decay of
    ``exp(-20)`` a step underflows to 0 and overflows nowhere; exponents,
    cumulative sums and the state in float32, the matrix products at the
    backend's default precision).  Off the TPU the kernel runs in interpreter
    mode.  Positions from ``true_len`` on take ``g = 0`` and ``beta = 0``, so
    the returned state ``[heads, head_dim, state]`` is the one after the last
    REAL token.  Returns ``(o [T, heads, state]`` in ``qkv``'s dtype, state
    float32, the ``conv_kernel - 1`` raw inputs before ``true_len``: zeros
    where the prompt is shorter)."""
    with jax.named_scope("ssm.scan"):
        T, H = qkv.shape[0], cfg.ssm_heads
        V, taps = cfg.ssm_state, cfg.conv_kernel - 1
        block = min(_DELTA_BLOCK, T)
        Tp = T + (-T) % block

        def blocked(a):                 # [Tp // block, block, ...]
            if a.shape[0] < Tp:         # behind every real position
                a = jnp.pad(a, ((0, Tp - T),) + ((0, 0),) * (a.ndim - 1))
            return a.reshape((Tp // block, block) + a.shape[1:])

        def over_block(carry, xs):
            S, tail = carry
            raw, fb, bb, alive = xs
            with jax.named_scope("ssm.conv"):
                window = jnp.concatenate([tail, raw])
                conv = _silu_conv(cfg, lp, [window[j:j + block]
                                            for j in range(taps + 1)])
            q, k, v = (a.reshape(block, -1) for a in delta_split(
                cfg, conv.astype(raw.dtype)))
            g, beta = delta_discretize(cfg, lp, fb, bb, alive)
            o, S = pallas_delta.delta_rule(
                q, k, v, g.reshape(block, -1), beta, S, chunk=cfg.chunk)
            return (S, raw[block - taps:]), o
        live = jnp.arange(Tp) < jnp.minimum(true_len, T)
        (S, _), o = lax.scan(
            over_block,
            (jnp.zeros((H, cfg.ssm_head_dim, V), jnp.float32),
             jnp.zeros((taps, qkv.shape[1]), qkv.dtype)),
            tuple(blocked(a) for a in (qkv, f, b, live)))
        with jax.named_scope("ssm.conv"):
            at = true_len - taps + jnp.arange(taps)
            kept = jnp.where((at >= 0)[:, None],
                             qkv[jnp.clip(at, 0, T - 1)], 0)
        return o.reshape(Tp, H, V)[:T], S, kept


def delta_step(state: jax.Array, g: jax.Array, beta: jax.Array,
               q: jax.Array, k: jax.Array, v: jax.Array):
    """One step of the delta rule for a batch of states ``[R, heads,
    head_dim, state]`` (float32): ``S <- diag(exp g) S``, then ``S <- S +
    beta k (x) (v - S^T k)``, ``o = S^T q``, with ``g`` ``[R, heads,
    head_dim]`` and ``beta`` ``[R, heads]`` from :func:`delta_discretize``
    and ``q``/``k``/``v`` from :func:`delta_split`.  A batch entry with ``g
    = 0`` and ``beta = 0`` keeps its state as it is.  The decayed state is
    read once for both of its products (``S^T k`` and ``S^T q``; the new
    state's read-out is theirs plus ``beta (k . q) u``) and once more to be
    written.  Returns ``(o [R, heads, state] float32, the new states)``."""
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    decayed = jnp.exp(g)[..., None] * state
    u = beta[..., None] * (v - jnp.sum(decayed * k[..., None], axis=-2))
    o = jnp.sum(decayed * q[..., None], axis=-2) \
        + jnp.sum(k * q, -1, keepdims=True) * u
    return o, decayed + k[..., None] * u[..., None, :]


def delta_gate_out(cfg: SsmConfig, lp: Dict[str, jax.Array], o: jax.Array,
                   z: jax.Array) -> jax.Array:
    """What leaves the delta mixer: ``o`` ``[..., heads, state]`` RMS-normed
    per head under the scale ``g_o`` FIRST, then gated by ``sigmoid(z
    wgb)`` (``z`` the gate's low-rank input), then ``w_out``."""
    with jax.named_scope("ssm.project"):
        y = rms_norm(o.astype(jnp.float32), lp["g_o"], cfg.ssm_eps)
        gate = jax.nn.sigmoid((z @ lp["wgb"]).astype(jnp.float32))
        return (y.reshape(z.shape[:-1] + (-1,)) * gate).astype(z.dtype) \
            @ lp["w_out"]


def gqa_project(cfg: SsmConfig, lp: Dict[str, jax.Array], h: jax.Array):
    """The attention mixer's projections of the normed activation ``h``:
    ``(q [..., heads, head_dim], k, v [..., kv_heads, head_dim])``; nothing
    is normed and nothing is turned."""
    with jax.named_scope("attn.project"):
        lead = h.shape[:-1]
        return ((h @ lp["wq"]).reshape(lead + (cfg.heads, cfg.head_dim)),
                (h @ lp["wk"]).reshape(lead + (cfg.kv_heads, cfg.head_dim)),
                (h @ lp["wv"]).reshape(lead + (cfg.kv_heads, cfg.head_dim)))


def gqa_out(cfg: SsmConfig, lp: Dict[str, jax.Array], att: jax.Array,
            h: jax.Array) -> jax.Array:
    """What leaves the attention mixer: the heads' outputs ``att`` ``[T,
    heads, head_dim]`` side by side, under ``sigmoid(h wgate)`` element by
    element where the configuration has the gate, through ``wo``."""
    with jax.named_scope("attn.project"):
        y = att.reshape(h.shape[0], -1)
        if cfg.attn_gate:
            y = y * jax.nn.sigmoid(h @ lp["wgate"])
        return y @ lp["wo"]


# per kind of layer: the device scope round the whole layer (its mixer's
# own scopes lie inside; a recurrent mixer's parts name themselves), and the
# one the layer's norm and residual run under
_MIXER_SCOPES = {"ssm": (None, "ssm.project"),
                 "delta": (None, "ssm.project"),
                 "full": ("attn.full", "attn.project"),
                 "experts": ("ffn", None)}


def _scoped(name: Optional[str]):
    return jax.named_scope(name) if name else contextlib.nullcontext()


def mixer_block(cfg: SsmConfig, lp: Dict[str, jax.Array], x: jax.Array,
                kind: str, mix: Callable) -> Tuple[jax.Array, Any]:
    """One layer on ``x`` ``[..., D]``: ``x + mix(RMS(x; g))``.  ``mix(h)
    -> (y, aux)`` is the layer's one mixer on the normed activation, built
    by the caller from this file's parts (a recurrent mixer over a prompt or
    a state, attention over a sequence or a cache, the expert layer); the
    layer runs under the ``kind``'s own device scopes."""
    whole, own = _MIXER_SCOPES[kind]
    with _scoped(whole):
        with _scoped(own):
            h = rms_norm(x, lp["g"], cfg.eps)
        y, aux = mix(h)
        with _scoped(own):
            return x + y, aux


def ssm_param_shapes(cfg: SsmConfig) -> Dict[str, Any]:
    """The single-mixer model's parameter tree as shapes: ``layers``, one
    dict of leaves per layer of the plan (nothing stacked), and ``shared``.
    ``g`` is each layer's RMSNorm scale; :data:`FLOAT32_LEAVES` are kept in
    float32."""
    D, H = cfg.d_model, cfg.ssm_heads
    F, Eh, La = cfg.expert_ffn, cfg.held_experts, cfg.latent
    keys, r = H * cfg.ssm_head_dim, cfg.delta_rank
    In = La or D                    # what the routed experts read and write
    experts = {"we1": (Eh, In, F), "we2": (Eh, F, In),
               "ws1": (D, cfg.shared_ffn), "ws2": (cfg.shared_ffn, D)} \
        if cfg.expert_form == "relu2" else {
            "weg": (Eh, In, F), "weu": (Eh, In, F), "wed": (Eh, F, In),
            "wsg": (D, cfg.shared_ffn), "wsu": (D, cfg.shared_ffn),
            "wsd": (cfg.shared_ffn, D)}
    kinds = {
        "ssm": {"g": (D,),
                "w_in": (D, 2 * cfg.d_inner
                         + 2 * cfg.ssm_groups * cfg.ssm_state + H),
                "w_conv": (cfg.conv_dim, cfg.conv_kernel),
                "b_conv": (cfg.conv_dim,), "dt_bias": (H,), "A_log": (H,),
                "Dskip": (H,), "g_y": (cfg.d_inner,),
                "w_out": (cfg.d_inner, D)},
        "delta": {"g": (D,), "w_in": (D, cfg.conv_dim),
                  "w_conv": (cfg.conv_dim, cfg.conv_kernel),
                  "wfa": (D, r), "wfb": (r, keys), "A_log": (H,),
                  "dt_bias": (keys,), "wb": (D, H), "wga": (D, r),
                  "wgb": (r, H * cfg.ssm_state), "g_o": (cfg.ssm_state,),
                  "w_out": (H * cfg.ssm_state, D)},
        "full": {"g": (D,), "wq": (D, cfg.heads * cfg.head_dim),
                 "wk": (D, cfg.kv_heads * cfg.head_dim),
                 "wv": (D, cfg.kv_heads * cfg.head_dim),
                 "wo": (cfg.heads * cfg.head_dim, D)},
        # (a leaf's place in its group seeds its draw: the order stands)
        "experts": {"g": (D,), "wr": (D, cfg.num_experts),
                    **({"wdn": (D, La), "wup": (La, D)} if La else {}),
                    **experts}}
    if cfg.attn_gate:
        kinds["full"]["wgate"] = (D, cfg.heads * cfg.head_dim)
    if cfg.route_bias:
        kinds["experts"]["eb"] = (cfg.num_experts,)
    return {"layers": tuple(dict(kinds[k]) for k in cfg.plan),
            "shared": {"embed": (cfg.vocab, D), "head": (D, cfg.vocab),
                       "gf": (D,)}}


def ssm_param_count(cfg: SsmConfig) -> int:
    """Parameters this chip holds (:func:`ssm_param_shapes`)."""
    shapes = ssm_param_shapes(cfg)
    return sum(math.prod(s) for group in shapes["layers"] + (shapes["shared"],)
               for s in group.values())
