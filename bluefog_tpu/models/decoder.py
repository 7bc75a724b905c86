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
Imports jax only: the callers import this module, never the reverse.
"""
import dataclasses
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


# ---------------------------------------------------------------------------
# The latent block: RMSNorm, multi-head latent attention, gated SiLU FFN
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LatentConfig:
    """Sizes of a latent-attention decoder with a leading dense layer and
    expert layers behind it, as ONE chip of an expert-parallel deployment
    holds it: the router keeps all ``num_experts`` outputs, this chip
    computes the experts ``held_start .. held_start + held_experts - 1``
    and the shared expert, and what the absent experts would add is left
    out (:func:`bluefog_tpu.moe.layers.held_expert_ffn`)."""
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
        if self.layers < 2:
            raise ValueError("LatentConfig.layers counts the leading dense "
                             "layer and at least one expert layer")
        if self.rope_dim % 2:
            raise ValueError("rope_dim must be even")
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

    @property
    def softmax_scale(self) -> float:
        """``(nope + rope)^-0.5 * m^2``, ``m`` YaRN's attention factor
        for ``mscale_all_dim`` (1 where no context extension is asked)."""
        m = 1.0
        if self.rope_factor > 1.0 and self.rope_mscale_all_dim:
            m = 0.1 * self.rope_mscale_all_dim * math.log(self.rope_factor) + 1
        return (self.nope_dim + self.rope_dim) ** -0.5 * m * m


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
    already turned."""
    with jax.named_scope("mla.project"):
        lead = h.shape[:-1]
        freqs = yarn_freqs(cfg)
        cq = rms_norm(h @ lp["wqa"], lp["gq"], cfg.eps)
        q = (cq @ lp["wqb"]).reshape(
            lead + (cfg.heads, cfg.nope_dim + cfg.rope_dim))
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

        chunk = max(c for c in range(1, H + 1)
                    if H % c == 0 and (c == 1 or c * T * T * 4 <= SCORE_BYTES))
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
    against the cached vector itself (decode)."""
    return jnp.einsum("...hd,chd->...hc", q_nope, _wkvb(cfg, lp)[0])


def mla_unabsorb_out(cfg: LatentConfig, lp: Dict[str, jax.Array],
                     u: jax.Array) -> jax.Array:
    """``o_i = u_i wkvb_i^V`` for the attended compressed vectors ``u``
    ``[..., H, kv_rank]``; returns ``[..., H * v]`` before ``wo``."""
    o = jnp.einsum("...hc,chd->...hd", u, _wkvb(cfg, lp)[1])
    return o.reshape(o.shape[:-2] + (-1,))


def latent_block(cfg: LatentConfig, lp: Dict[str, jax.Array], x: jax.Array,
                 positions: jax.Array, attend: Callable,
                 ffn: Callable) -> Tuple[jax.Array, Any, Any]:
    """One pre-norm latent block on ``x`` ``[..., D]``: RMSNorm →
    ``attend`` → ``wo`` → RMSNorm → ``ffn``, both halves residual.
    ``attend(q_nope, q_rope, latent) -> (att [..., H * v], aux)`` gets the
    block's own projections (:func:`mla_project`) and meets the sequence
    (:func:`mla_unabsorbed`) or the cache (the engine's absorbed form);
    ``ffn(lp, h) -> (y, faux)`` as in :func:`decoder_block`."""
    h = rms_norm(x, lp["g1"], cfg.eps)
    att, aux = attend(*mla_project(cfg, lp, h, positions))
    x = x + att @ lp["wo"]
    y, faux = ffn(lp, rms_norm(x, lp["g2"], cfg.eps))
    return x + y, aux, faux


def latent_logits(cfg: LatentConfig, shared: Dict[str, jax.Array],
                  x: jax.Array) -> jax.Array:
    """Final RMSNorm and read-out over this chip's vocabulary slice."""
    return rms_norm(x, shared["gf"], cfg.eps) @ shared["head"]


def latent_param_shapes(cfg: LatentConfig) -> Dict[str, Dict[str, tuple]]:
    """The latent model's parameter tree as shapes: ``first`` (the leading
    dense layer), ``blocks`` (the expert layers, stacked), ``shared``.
    Names that start with ``g`` are RMSNorm scales; ``wr`` is the router,
    kept in float32."""
    D, H = cfg.d_model, cfg.heads
    Fe, Eh, Lx = cfg.expert_ffn, cfg.held_experts, cfg.layers - 1
    attn = {"g1": (D,), "wqa": (D, cfg.q_rank), "gq": (cfg.q_rank,),
            "wqb": (cfg.q_rank, H * (cfg.nope_dim + cfg.rope_dim)),
            "wkva": (D, cfg.latent_dim), "gkv": (cfg.kv_rank,),
            "wkvb": (cfg.kv_rank, H * (cfg.nope_dim + cfg.v_dim)),
            "wo": (H * cfg.v_dim, D), "g2": (D,)}
    first = dict(attn, wg=(D, cfg.dense_ffn), wu=(D, cfg.dense_ffn),
                 wd=(cfg.dense_ffn, D))
    expert = dict(attn, wr=(D, cfg.num_experts), wsg=(D, Fe), wsu=(D, Fe),
                  wsd=(Fe, D), weg=(Eh, D, Fe), weu=(Eh, D, Fe),
                  wed=(Eh, Fe, D))
    return {"first": first,
            "blocks": {k: (Lx,) + v for k, v in expert.items()},
            "shared": {"embed": (cfg.vocab, D), "head": (D, cfg.vocab),
                       "gf": (D,)}}


def latent_param_count(cfg: LatentConfig) -> int:
    """Parameters this chip holds (:func:`latent_param_shapes`)."""
    return sum(math.prod(s) for group in latent_param_shapes(cfg).values()
               for s in group.values())
