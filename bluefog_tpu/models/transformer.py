"""Decoder transformer with ring-attention sequence parallelism.

Beyond the reference (which predates long-context training, SURVEY.md §5):
a GPT-style decoder whose attention runs over a sequence SHARDED across the
mesh — each device holds ``seq_len / n`` tokens and K/V blocks rotate via the
same ring ``ppermute`` primitive the gossip layer uses
(:func:`bluefog_tpu.ops.ring_attention`).  Combine with the decentralized
optimizer strategies for gossip-DP x ring-SP 2-D parallel training.
"""
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import local_flash_attention, ring_attention, ulysses_attention
from ..ops.ulysses import dense_attention
from . import decoder


def init_decode_cache(model: "RingTransformerLM", batch: int, max_len: int,
                      dtype: Any = None):
    """Fresh per-layer KV cache for :meth:`RingTransformerLM.__call__`'s
    decode path: a tuple of ``{"k", "v"}`` dicts shaped
    ``[batch, max_len, num_kv_heads, head_dim]`` (grouped-query aware —
    the cache holds the COMPACT kv heads, G x smaller than the q heads)."""
    Hkv = model.num_kv_heads or model.num_heads
    Dh = model.d_model // model.num_heads
    dt = model.dtype if dtype is None else dtype
    return tuple(
        {"k": jnp.zeros((batch, max_len, Hkv, Dh), dt),
         "v": jnp.zeros((batch, max_len, Hkv, Dh), dt)}
        for _ in range(model.num_layers))


class RingTransformerBlock(nn.Module):
    """Pre-LN decoder block; attention is ring-parallel when ``axis`` is set."""
    num_heads: int
    num_kv_heads: Optional[int] = None  # grouped-query attention (ring only):
                                        # compact kv — G x fewer ring bytes
    mlp_ratio: int = 4
    axis: Optional[str] = None          # mesh axis the sequence is sharded over
    dtype: Any = jnp.bfloat16
    sp_mode: str = "ring"               # "ring" (K/V rotation) | "ulysses"
                                        # (head-scatter all_to_all)
    sp_layout: str = "contiguous"       # "zigzag": balanced causal ring
                                        # (sequence pre-permuted, ring only)
    rope: bool = False                  # rotary positions on q/k
    use_pallas: bool = False            # VMEM flash kernel for the attention
    pallas_interpret: Optional[bool] = None   # override backend auto-detect
    scan_compat: bool = False           # return (x, None) for nn.scan

    @nn.compact
    def __call__(self, x, positions=None, cache=None):
        # x: [batch, local_seq, d_model]
        B, T, C = x.shape
        H = self.num_heads
        h = nn.LayerNorm(dtype=jnp.float32)(x).astype(self.dtype)
        Hkv = self.num_kv_heads or H
        Dh = C // H
        if Hkv == H:
            qkv = nn.Dense(3 * C, use_bias=False, dtype=self.dtype)(h)
            q, k, v = jnp.split(qkv, 3, axis=-1)
        else:
            if H % Hkv:
                raise ValueError(
                    f"num_heads {H} not a multiple of num_kv_heads {Hkv}")
            qkv = nn.Dense(C + 2 * Hkv * Dh, use_bias=False,
                           dtype=self.dtype)(h)
            q = qkv[..., :C]
            k = qkv[..., C:C + Hkv * Dh]
            v = qkv[..., C + Hkv * Dh:]
        q = q.reshape(B, T, H, Dh)
        k = k.reshape(B, T, Hkv, Dh)
        v = v.reshape(B, T, Hkv, Dh)
        if self.rope:
            if positions is None:
                raise ValueError("rope needs the tokens' global positions")
            q = decoder.rope(q, positions)
            k = decoder.rope(k, positions)
        if cache is not None:
            # decode step: append this chunk's compact kv at pos_offset
            # (= positions[0]) and attend over everything written so far.
            # Attention numerics mirror dense_attention exactly (f32
            # scores, scale folded into q, -inf masking) so a token
            # decoded here is logit-identical to the full forward.
            if self.axis is not None:
                raise ValueError(
                    "decode with a KV cache is a single-device path; the "
                    "serve engine handles PP/TP sharding itself "
                    "(bluefog_tpu.serve.engine)")
            offset = positions[0]
            ck = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, offset, 0, 0))
            cv = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, offset, 0, 0))
            new_cache = {"k": ck, "v": cv}
            if Hkv != H:
                ck = jnp.repeat(ck, H // Hkv, axis=2)
                cv = jnp.repeat(cv, H // Hkv, axis=2)
            L = ck.shape[1]
            ct = jnp.promote_types(q.dtype, jnp.float32)
            s = jnp.einsum("bthd,bshd->bths",
                           q.astype(ct) * (Dh ** -0.5),
                           ck.astype(ct))
            valid = (jnp.arange(L)[None, :]
                     <= (offset + jnp.arange(T))[:, None])       # [T, L]
            s = jnp.where(valid[None, :, None, :], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            att = jnp.einsum("bths,bshd->bthd", p,
                             cv.astype(ct)).astype(q.dtype)
            att = att.astype(self.dtype).reshape(B, T, C)
            x = x + nn.Dense(C, use_bias=False, dtype=self.dtype)(att)
            h = nn.LayerNorm(dtype=jnp.float32)(x).astype(self.dtype)
            h = nn.Dense(self.mlp_ratio * C, dtype=self.dtype)(h)
            h = nn.gelu(h)
            x = x + nn.Dense(C, dtype=self.dtype)(h)
            return x, new_cache
        if self.sp_mode not in ("ring", "ulysses"):
            raise ValueError(
                f"unknown sp_mode {self.sp_mode!r}; choose 'ring' or "
                "'ulysses'")
        if self.sp_layout not in ("contiguous", "zigzag"):
            raise ValueError(f"unknown sp_layout {self.sp_layout!r}")
        if self.sp_layout == "zigzag" and self.sp_mode != "ring":
            raise ValueError("sp_layout='zigzag' is a ring-attention layout")
        if self.axis is not None:
            if self.sp_mode == "ring":
                att = ring_attention(
                    q, k, v, axis=self.axis, causal=True,
                    layout=self.sp_layout, use_pallas=self.use_pallas,
                    pallas_interpret=self.pallas_interpret)
            else:
                att = ulysses_attention(
                    q, k, v, axis=self.axis, causal=True,
                    use_pallas=self.use_pallas,
                    pallas_interpret=self.pallas_interpret)
        else:
            # single-device fallback (expand GQA kv).  use_pallas matters
            # HERE too: dense_attention materializes the full [B,T,H,T]
            # f32 score tensor (4.3 GB at batch 4 / seq 4096 / 16 heads),
            # while the flash kernel keeps each [block_q, T] tile in VMEM
            # and recomputes scores in the backward — on one chip it is
            # the only way long sequences fit in HBM at all.
            if self.use_pallas:
                # compact GQA kv goes straight in (the kernel's index map
                # routes q head h to kv head h//group); positional args:
                # custom_vjp nondiff_argnums (causal, scale, block_q,
                # interpret, axis)
                att = local_flash_attention(
                    q, k, v, True, Dh ** -0.5, 512,
                    self.pallas_interpret, None).astype(self.dtype)
            else:
                if Hkv != H:            # dense oracle needs full-width kv
                    k = jnp.repeat(k, H // Hkv, axis=2)
                    v = jnp.repeat(v, H // Hkv, axis=2)
                att = dense_attention(q, k, v, causal=True).astype(self.dtype)
        att = att.reshape(B, T, C)
        x = x + nn.Dense(C, use_bias=False, dtype=self.dtype)(att)

        h = nn.LayerNorm(dtype=jnp.float32)(x).astype(self.dtype)
        h = nn.Dense(self.mlp_ratio * C, dtype=self.dtype)(h)
        h = nn.gelu(h)
        x = x + nn.Dense(C, dtype=self.dtype)(h)
        return (x, None) if self.scan_compat else x


class RingTransformerLM(nn.Module):
    """Small GPT-style LM; input token ids ``[batch, local_seq]``.

    Positions are global: pass ``pos_offset`` = this device's sequence offset
    (``rank * local_seq``) so rotary-free learned positions line up across the
    ring.
    """
    vocab_size: int = 32000
    num_layers: int = 4
    num_heads: int = 8
    num_kv_heads: Optional[int] = None   # GQA (ring sp_mode only)
    d_model: int = 512
    max_seq_len: int = 8192
    axis: Optional[str] = None
    dtype: Any = jnp.bfloat16
    sp_mode: str = "ring"   # sequence-parallel mode: "ring" | "ulysses"
    sp_layout: str = "contiguous"   # "zigzag": balanced causal ring
    rope: bool = False      # rotary positions instead of learned absolute
    remat: bool = False     # rematerialize blocks: trade FLOPs for HBM
    use_pallas: bool = False
    pallas_interpret: Optional[bool] = None
    scan_layers: bool = False   # lax.scan ONE block over depth: compile
                                # time O(1) in num_layers (XLA compiles a
                                # single block body instead of an unrolled
                                # stack — minutes saved per TPU compile).
                                # Params get a leading [num_layers] axis
                                # under 'blocks' (different tree than the
                                # unrolled loop's per-layer modules).

    @nn.compact
    def __call__(self, tokens, pos_offset=0, positions=None, cache=None):
        """``positions`` ([T] int32 global positions) overrides the
        contiguous ``pos_offset + arange`` — required for the zigzag
        layout, where a device's tokens are two non-adjacent chunks
        (:func:`bluefog_tpu.ops.zigzag_positions`).

        ``cache`` switches to the DECODE path: ``tokens`` is the next chunk
        (typically ``[B, 1]``), ``pos_offset`` the number of tokens already
        in the cache (traced scalars are fine), and the per-layer kv of the
        chunk is appended at ``pos_offset`` (see :func:`init_decode_cache`).
        Returns ``(logits, new_cache)`` instead of logits; proven
        logit-identical to the full forward by the float64 oracle in
        tests/test_serve.py.  Single-device only (``axis=None``,
        ``scan_layers=False``) — the sharded serving path lives in
        :mod:`bluefog_tpu.serve`.
        """
        B, T = tokens.shape
        x = nn.Embed(self.vocab_size, self.d_model,
                     dtype=self.dtype)(tokens)
        if positions is None:
            positions = pos_offset + jnp.arange(T)
        if not self.rope:
            pos = nn.Embed(self.max_seq_len, self.d_model, dtype=self.dtype)(
                positions)
            x = x + pos[None]
        if cache is not None:
            if self.scan_layers:
                raise ValueError(
                    "decode with a KV cache needs per-layer modules; "
                    "scan_layers=True folds them into one scanned block")
            if self.axis is not None:
                raise ValueError(
                    "decode with a KV cache is a single-device path; the "
                    "serve engine handles sharding (bluefog_tpu.serve)")
            if len(cache) != self.num_layers:
                raise ValueError(
                    f"cache has {len(cache)} layer entries, model has "
                    f"{self.num_layers} (init_decode_cache builds one)")
        if self.remat:
            # prevent_cse only matters OUTSIDE lax.scan (scan already
            # blocks the CSE it guards against); leaving it on inside the
            # scanned stack litters every iteration with optimization
            # barriers that inhibit fusion in the backward
            Block = nn.remat(
                RingTransformerBlock,
                policy=jax.checkpoint_policies.nothing_saveable,
                prevent_cse=not self.scan_layers)
        else:
            Block = RingTransformerBlock
        kw = dict(
            num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
            axis=self.axis, dtype=self.dtype,
            sp_mode=self.sp_mode, sp_layout=self.sp_layout,
            rope=self.rope, use_pallas=self.use_pallas,
            pallas_interpret=self.pallas_interpret)
        new_cache = []
        if self.scan_layers:
            ScanStack = nn.scan(
                Block, variable_axes={"params": 0},
                split_rngs={"params": True}, in_axes=nn.broadcast,
                length=self.num_layers)
            x, _ = ScanStack(**kw, scan_compat=True,
                             name="blocks")(x, positions)
        elif cache is not None:
            for i in range(self.num_layers):
                x, layer_cache = Block(**kw)(x, positions, cache=cache[i])
                new_cache.append(layer_cache)
        else:
            for _ in range(self.num_layers):
                x = Block(**kw)(x, positions)
        x = nn.LayerNorm(dtype=jnp.float32)(x)
        logits = nn.Dense(self.vocab_size, use_bias=False,
                          dtype=jnp.float32)(x)
        return logits if cache is None else (logits, tuple(new_cache))
