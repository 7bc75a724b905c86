"""Pallas flash-attention partials vs pure-jnp oracle (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import bluefog_tpu as bf
from bluefog_tpu.ops import pallas_attention as pa
from bluefog_tpu.ops import ring_attention

N = 8


def dense_attention(q, k, v, causal, q_off=0, k_off=0, scale=None):
    """Oracle: full softmax attention with optional causal offset masking."""
    d = q.shape[-1]
    scale = scale or 1.0 / np.sqrt(d)
    s = np.einsum("bihd,bjhd->bihj", np.asarray(q, np.float64),
                  np.asarray(k, np.float64)) * scale
    if causal:
        qp = q_off + np.arange(q.shape[1])
        kp = k_off + np.arange(k.shape[1])
        mask = qp[:, None] >= kp[None, :]
        s = np.where(mask[None, :, None, :], s, -np.inf)
    s = s - s.max(axis=-1, keepdims=True)
    p = np.exp(s)
    denom = p.sum(axis=-1, keepdims=True)
    denom = np.where(denom == 0, 1.0, denom)
    return np.einsum("bihj,bjhd->bihd", p / denom, np.asarray(v, np.float64))


def test_block_partial_matches_softmax():
    rng = np.random.default_rng(0)
    B, T, H, D = 2, 16, 2, 8
    q, k, v = (jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
               for _ in range(3))
    o, l, m = pa.attention_block_partial(
        q, k, v, jnp.asarray(0), jnp.asarray(0),
        causal=False, scale=1.0 / np.sqrt(D), interpret=True)
    # single block == full attention after normalization
    out = np.asarray(o) / np.asarray(l)[..., None]
    expected = dense_attention(q, k, v, causal=False)
    np.testing.assert_allclose(out, expected, rtol=1e-5, atol=1e-6)


def test_block_partial_causal_offsets():
    rng = np.random.default_rng(1)
    B, Tq, Tk, H, D = 1, 8, 8, 1, 4
    q = jnp.asarray(rng.normal(size=(B, Tq, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Tk, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Tk, H, D)), jnp.float32)
    # q block at positions 8..15, k block at 0..7 -> fully visible
    o, l, m = pa.attention_block_partial(
        q, k, v, jnp.asarray(8), jnp.asarray(0), causal=True,
        scale=1.0 / np.sqrt(D), interpret=True)
    out = np.asarray(o) / np.asarray(l)[..., None]
    expected = dense_attention(q, k, v, causal=True, q_off=8, k_off=0)
    np.testing.assert_allclose(out, expected, rtol=1e-5, atol=1e-6)
    # q block at 0..7, k block at 8..15 -> fully masked: l == 0, m == -inf
    o2, l2, m2 = pa.attention_block_partial(
        q, k, v, jnp.asarray(0), jnp.asarray(8), causal=True,
        scale=1.0 / np.sqrt(D), interpret=True)
    assert np.all(np.asarray(l2) == 0.0)
    assert np.all(np.isneginf(np.asarray(m2)))
    assert np.all(np.asarray(o2) == 0.0)


def test_merge_partials_equals_joint_softmax():
    rng = np.random.default_rng(2)
    B, T, H, D = 1, 8, 2, 4
    q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    k1, v1, k2, v2 = (jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
                      for _ in range(4))
    p1 = pa.attention_block_partial(
        q, k1, v1, jnp.asarray(0), jnp.asarray(0), causal=False,
        scale=0.5, interpret=True)
    p2 = pa.attention_block_partial(
        q, k2, v2, jnp.asarray(0), jnp.asarray(0), causal=False,
        scale=0.5, interpret=True)
    o0 = jnp.zeros((B, T, H, D), jnp.float32)
    l0 = jnp.zeros((B, T, H), jnp.float32)
    m0 = jnp.full((B, T, H), -jnp.inf, jnp.float32)
    o, l, m = pa.merge_partials(pa.merge_partials((o0, l0, m0), p1), p2)
    out = np.asarray(o) / np.asarray(l)[..., None]
    expected = dense_attention(
        q, jnp.concatenate([k1, k2], 1), jnp.concatenate([v1, v2], 1),
        causal=False, scale=0.5)
    np.testing.assert_allclose(out, expected, rtol=1e-5, atol=1e-6)


def test_ring_attention_pallas_path_matches_jnp(cpu_devices):
    """Full ring attention with use_pallas == the pure-jnp ring path."""
    bf.init(devices=cpu_devices, nodes_per_machine=1)
    try:
        rng = np.random.default_rng(3)
        B, T, H, D = 1, 4, 2, 4       # per-device block of 4 tokens
        shape = (B, N * T, H, D)
        q = jnp.asarray(rng.normal(size=shape), jnp.float32)
        k = jnp.asarray(rng.normal(size=shape), jnp.float32)
        v = jnp.asarray(rng.normal(size=shape), jnp.float32)

        def run(use_pallas):
            def f(qb, kb, vb):
                return ring_attention(
                    qb, kb, vb, axis="rank", causal=True,
                    use_pallas=use_pallas)
            # check_vma=False: the interpret-mode pallas lowering mixes
            # varying and unvarying operands in its internal dynamic_slice
            # (grid bookkeeping), which the vma checker rejects; compiled TPU
            # lowering is unaffected.
            fn = jax.jit(jax.shard_map(
                f, mesh=bf.mesh(),
                in_specs=(P(None, "rank"),) * 3,
                out_specs=P(None, "rank"), check_vma=not use_pallas))
            return np.asarray(fn(q, k, v))

        jnp_out = run(False)
        pallas_out = run(True)
        np.testing.assert_allclose(pallas_out, jnp_out, rtol=1e-4, atol=1e-5)
        expected = dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(pallas_out, expected, rtol=1e-3, atol=1e-4)
    finally:
        bf.shutdown()


def test_pallas_path_is_trainable(cpu_devices):
    """Grads through the pallas path (recompute backward) == jnp-path grads."""
    bf.init(devices=cpu_devices, nodes_per_machine=1)
    try:
        rng = np.random.default_rng(4)
        B, T, H, D = 1, 4, 1, 4
        shape = (B, N * T, H, D)
        q = jnp.asarray(rng.normal(size=shape), jnp.float32)
        k = jnp.asarray(rng.normal(size=shape), jnp.float32)
        v = jnp.asarray(rng.normal(size=shape), jnp.float32)

        def grads(use_pallas):
            def loss(qb, kb, vb):
                out = ring_attention(qb, kb, vb, axis="rank", causal=True,
                                     use_pallas=use_pallas)
                return jax.lax.psum(jnp.sum(out ** 2), "rank")

            g = jax.grad(loss, argnums=(0, 1, 2))
            # check_vma=False for BOTH paths: interpret-mode pallas needs it
            # (see forward test), and psum cotangent semantics differ between
            # vma modes, so the comparison must hold the mode fixed.
            fn = jax.jit(jax.shard_map(
                g, mesh=bf.mesh(), in_specs=(P(None, "rank"),) * 3,
                out_specs=(P(None, "rank"),) * 3, check_vma=False))
            return fn(q, k, v)

        g_jnp = grads(False)
        g_pallas = grads(True)
        for a, b in zip(g_jnp, g_pallas):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)
    finally:
        bf.shutdown()


def test_q_blocking_matches_unblocked():
    """block_q < Tq tiles the grid; result identical to one big block."""
    rng = np.random.default_rng(5)
    B, T, H, D = 1, 32, 2, 8
    q, k, v = (jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
               for _ in range(3))
    full = pa.attention_block_partial(
        q, k, v, jnp.asarray(16), jnp.asarray(0), causal=True,
        scale=0.3, interpret=True, block_q=T)
    tiled = pa.attention_block_partial(
        q, k, v, jnp.asarray(16), jnp.asarray(0), causal=True,
        scale=0.3, interpret=True, block_q=8)
    for a, b in zip(full, tiled):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("T", [24, 7])
def test_q_blocking_non_divisible_pads(T):
    """Tq not a multiple of block_q pads to a block multiple instead of
    falling back to one full [Tq, Tk] tile (round-1 advisor finding)."""
    rng = np.random.default_rng(6)
    B, H, D = 1, 2, 8
    q, k, v = (jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
               for _ in range(3))
    full = pa.attention_block_partial(
        q, k, v, jnp.asarray(0), jnp.asarray(0), causal=True,
        scale=0.3, interpret=True, block_q=T)
    tiled = pa.attention_block_partial(
        q, k, v, jnp.asarray(0), jnp.asarray(0), causal=True,
        scale=0.3, interpret=True, block_q=16)
    for a, b in zip(full, tiled):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def _dense_grads(q, k, v, causal, scale):
    """Oracle gradients of sum(attention**2) via jax autodiff on the dense op."""
    def loss(q_, k_, v_):
        s = jnp.einsum("bihd,bjhd->bihj", q_.astype(jnp.float32),
                       k_.astype(jnp.float32)) * scale
        if causal:
            Tq, Tk = q_.shape[1], k_.shape[1]
            mask = jnp.arange(Tq)[:, None] >= jnp.arange(Tk)[None, :]
            s = jnp.where(mask[None, :, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bihj,bjhd->bihd", p, v_.astype(jnp.float32))
        return jnp.sum(out ** 2), out
    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
    return out, grads


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block_q", [16, 5])
def test_backward_kernel_matches_autodiff(causal, block_q):
    """attention_block_backward == autodiff through dense attention,
    including the padded (non-divisible block_q) grid."""
    rng = np.random.default_rng(7)
    B, T, H, D = 2, 16, 2, 8
    scale = 1.0 / np.sqrt(D)
    q, k, v = (jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
               for _ in range(3))
    out, (dq_e, dk_e, dv_e) = _dense_grads(q, k, v, causal, scale)
    do = 2.0 * out                       # cotangent of sum(out**2)

    # softmax stats from the forward kernel
    _, l, m = pa.attention_block_partial(
        q, k, v, jnp.asarray(0), jnp.asarray(0), causal=causal,
        scale=scale, interpret=True)
    lse = jnp.where(l == 0.0, -jnp.inf, m + jnp.log(jnp.where(l == 0, 1, l)))
    delta = jnp.sum(do * out, axis=-1)

    dq, dk, dv = pa.attention_block_backward(
        q, k, v, do, lse, delta, jnp.asarray(0), jnp.asarray(0),
        causal=causal, scale=scale, interpret=True, block_q=block_q)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(dq_e),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(dk_e),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(dv_e),
                               rtol=1e-4, atol=1e-5)


def test_pallas_bwd_bf16(cpu_devices):
    """bf16 inputs keep bf16 grads through the pallas ring path, finite and
    close to the f32 jnp path at bf16 tolerance."""
    bf.init(devices=cpu_devices, nodes_per_machine=1)
    try:
        rng = np.random.default_rng(8)
        B, T, H, D = 1, 4, 1, 4
        shape = (B, N * T, H, D)
        q = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)

        def grads(use_pallas):
            def loss(qb, kb, vb):
                out = ring_attention(qb, kb, vb, axis="rank", causal=True,
                                     use_pallas=use_pallas)
                return jax.lax.psum(jnp.sum(out.astype(jnp.float32) ** 2),
                                    "rank")
            g = jax.grad(loss, argnums=(0, 1, 2))
            fn = jax.jit(jax.shard_map(
                g, mesh=bf.mesh(), in_specs=(P(None, "rank"),) * 3,
                out_specs=(P(None, "rank"),) * 3, check_vma=False))
            return fn(q, k, v)

        g_pallas = grads(True)
        g_jnp = grads(False)
        for a, b in zip(g_pallas, g_jnp):
            assert a.dtype == jnp.bfloat16
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=0.1, atol=0.05)
    finally:
        bf.shutdown()


class TestGQA:
    """Grouped-query attention: compact [B, T, Hkv, D] k/v, q heads grouped
    onto kv heads via the kernel's BlockSpec index map (zero data expansion)."""

    def _expand(self, kv, G):
        return jnp.repeat(kv, G, axis=2)

    def test_forward_partial_matches_expanded(self):
        rng = np.random.default_rng(20)
        B, T, H, Hkv, D = 2, 16, 4, 2, 8
        q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, T, Hkv, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, T, Hkv, D)), jnp.float32)
        gqa = pa.attention_block_partial(
            q, k, v, jnp.asarray(4), jnp.asarray(0), causal=True,
            scale=0.4, interpret=True)
        full = pa.attention_block_partial(
            q, self._expand(k, 2), self._expand(v, 2), jnp.asarray(4),
            jnp.asarray(0), causal=True, scale=0.4, interpret=True)
        for a, b in zip(gqa, full):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)

    def test_backward_matches_expanded(self):
        """GQA dk/dv equal the head-group SUM of the expanded grads (the
        chain rule through the implicit broadcast)."""
        rng = np.random.default_rng(21)
        B, T, H, Hkv, D = 1, 16, 4, 2, 8
        G = H // Hkv
        scale = 1.0 / np.sqrt(D)
        q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, T, Hkv, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, T, Hkv, D)), jnp.float32)
        ke, ve = self._expand(k, G), self._expand(v, G)

        out, (dq_e, dk_e, dv_e) = _dense_grads(q, ke, ve, True, scale)
        do = 2.0 * out
        _, l, m = pa.attention_block_partial(
            q, k, v, jnp.asarray(0), jnp.asarray(0), causal=True,
            scale=scale, interpret=True)
        lse = jnp.where(l == 0.0, -jnp.inf,
                        m + jnp.log(jnp.where(l == 0, 1, l)))
        delta = jnp.sum(do * out, axis=-1)
        dq, dk, dv = pa.attention_block_backward(
            q, k, v, do, lse, delta, jnp.asarray(0), jnp.asarray(0),
            causal=True, scale=scale, interpret=True, block_q=8)
        np.testing.assert_allclose(np.asarray(dq), np.asarray(dq_e),
                                   rtol=1e-4, atol=1e-5)
        # expanded grads fold back: sum over each head group
        dk_fold = np.asarray(dk_e).reshape(B, T, Hkv, G, D).sum(axis=3)
        dv_fold = np.asarray(dv_e).reshape(B, T, Hkv, G, D).sum(axis=3)
        np.testing.assert_allclose(np.asarray(dk), dk_fold,
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(dv), dv_fold,
                                   rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("use_pallas", [False, True])
    @pytest.mark.parametrize("layout", ["contiguous", "zigzag"])
    def test_ring_gqa_matches_dense(self, cpu_devices, use_pallas, layout):
        bf.init(devices=cpu_devices, nodes_per_machine=1)
        try:
            from bluefog_tpu.ops import zigzag_order, zigzag_inverse
            rng = np.random.default_rng(22)
            B, T, H, Hkv, D = 1, N * 4, 4, 2, 4
            q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
            k = jnp.asarray(rng.normal(size=(B, T, Hkv, D)), jnp.float32)
            v = jnp.asarray(rng.normal(size=(B, T, Hkv, D)), jnp.float32)

            def f(qb, kb, vb):
                return ring_attention(qb, kb, vb, axis="rank", causal=True,
                                      layout=layout, use_pallas=use_pallas)

            fn = jax.jit(jax.shard_map(
                f, mesh=bf.mesh(), in_specs=(P(None, "rank"),) * 3,
                out_specs=P(None, "rank"), check_vma=not use_pallas))
            if layout == "zigzag":
                order = zigzag_order(N, T)
                out = np.asarray(fn(q[:, order], k[:, order], v[:, order]))
                out = out[:, zigzag_inverse(N, T)]
            else:
                out = np.asarray(fn(q, k, v))
            expected = dense_attention(
                q, self._expand(k, 2), self._expand(v, 2), causal=True)
            np.testing.assert_allclose(out, expected, rtol=1e-4, atol=1e-5)
        finally:
            bf.shutdown()


# --- the key axis blocked inside the kernels (PR 48) -------------------------
#
# Blocks forced small so that SEVERAL key blocks are visited at lengths the
# interpreter runs quickly: the private entry points take the block sizes
# the public ones choose from the shapes.

def _oracle(q, k, v, do, *, causal, q_off, k_off, window, scale):
    """float32 autodiff of dense attention under the kernels' mask:
    ``out, lse, (dq, dk, dv)``; a row no key is visible to gives out = 0,
    lse = -inf and no gradient.  Grouped heads: k, v repeated, their
    gradients summed over each group."""
    G = q.shape[2] // k.shape[2]
    qp = q_off + np.arange(q.shape[1])
    kp = k_off + np.arange(k.shape[1])
    keep = np.ones((q.shape[1], k.shape[1]), bool)
    if causal:
        keep = qp[:, None] >= kp[None, :]
        if window:
            keep &= qp[:, None] - kp[None, :] < window
    keep = jnp.asarray(keep)[None, :, None, :]

    def fwd(q_, k_, v_):
        f32 = lambda t: t.astype(jnp.float32)
        s = jnp.einsum("bihd,bjhd->bihj", f32(q_),
                       jnp.repeat(f32(k_), G, axis=2)) * scale
        s = jnp.where(keep, s, -jnp.inf)
        m = jnp.max(s, axis=-1, keepdims=True)
        m = jnp.where(jnp.isneginf(m), 0.0, m)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        out = jnp.einsum("bihj,bjhd->bihd", p / jnp.where(l == 0, 1.0, l),
                         jnp.repeat(f32(v_), G, axis=2))
        lse = jnp.where(l == 0, -jnp.inf, m + jnp.log(jnp.where(l == 0, 1, l)))
        return out, lse[..., 0]

    (out, lse), vjp = jax.vjp(fwd, q, k, v)
    grads = vjp((do.astype(jnp.float32), jnp.zeros_like(lse)))
    return out, lse, grads


def _qkv(seed, B, Tq, Tk, H, Hkv, D, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    q, do = (jnp.asarray(rng.normal(size=(B, Tq, H, D)), dtype)
             for _ in range(2))
    k, v = (jnp.asarray(rng.normal(size=(B, Tk, Hkv, D)), dtype)
            for _ in range(2))
    return q, k, v, do


# (Tq, Tk, block_q, block_k, q_off, k_off, window, H, Hkv, causal, dtype)
KEY_BLOCK_CASES = {
    "kb8": (64, 64, 16, 8, 0, 0, 0, 2, 2, True, jnp.float32),
    "kb16": (64, 64, 16, 16, 0, 0, 0, 2, 2, True, jnp.float32),
    "kb_over_qb": (64, 64, 8, 16, 0, 0, 0, 2, 2, True, jnp.float32),
    "under_diagonal": (64, 64, 16, 8, 64, 0, 0, 2, 2, True, jnp.float32),
    "across_diagonal": (64, 64, 16, 8, 24, 0, 0, 2, 2, True, jnp.float32),
    "keys_ahead": (64, 64, 16, 16, 0, 20, 0, 2, 2, True, jnp.float32),
    "above_diagonal": (64, 64, 16, 8, 0, 64, 0, 2, 2, True, jnp.float32),
    "window": (64, 64, 16, 8, 0, 0, 20, 2, 2, True, jnp.float32),
    "window_offsets": (64, 64, 16, 16, 40, 0, 24, 2, 2, True, jnp.float32),
    "window_under_block": (64, 64, 16, 16, 0, 0, 5, 2, 2, True, jnp.float32),
    "grouped": (64, 64, 16, 8, 0, 0, 0, 4, 2, True, jnp.float32),
    "no_block_divides": (50, 64, 16, 8, 0, 0, 0, 2, 2, True, jnp.float32),
    "bfloat16": (64, 64, 16, 16, 0, 0, 0, 2, 2, True, jnp.bfloat16),
    "non_causal": (64, 64, 16, 8, 0, 0, 0, 2, 2, False, jnp.float32),
    "one_tile": (64, 64, 16, 64, 8, 0, 0, 2, 2, True, jnp.float32),
}


@pytest.mark.parametrize("case", sorted(KEY_BLOCK_CASES))
def test_backward_over_key_blocks_matches_autodiff(case):
    (Tq, Tk, qb, kb, q_off, k_off, window, H, Hkv, causal,
     dtype) = KEY_BLOCK_CASES[case]
    D, scale = 8, 8 ** -0.5
    q, k, v, do = _qkv(30, 2, Tq, Tk, H, Hkv, D, dtype)
    out, lse, want = _oracle(q, k, v, do, causal=causal, q_off=q_off,
                             k_off=k_off, window=window, scale=scale)
    delta = jnp.sum(do.astype(jnp.float32) * out, axis=-1)
    got = pa._block_backward(
        q, k, v, do, lse, delta, jnp.asarray(q_off), jnp.asarray(k_off),
        causal=causal, scale=scale, interpret=True, block_q=qb, block_k=kb,
        window=window)
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=1e-4, atol=1e-5)
    for g, w in zip(got, want):
        assert g.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), **tol)
    if case == "above_diagonal":
        assert all(not np.asarray(g).any() for g in got)


LOCAL_FORWARD_CASES = {
    name: KEY_BLOCK_CASES[name]
    for name in ("kb8", "kb16", "kb_over_qb", "grouped", "no_block_divides",
                 "bfloat16", "non_causal")}
LOCAL_FORWARD_CASES["more_keys_than_queries"] = (
    32, 64, 16, 8, 0, 0, 0, 2, 2, True, jnp.float32)


@pytest.mark.parametrize("case", sorted(LOCAL_FORWARD_CASES))
def test_local_forward_over_key_blocks_matches_dense(case):
    Tq, Tk, qb, kb, _, _, _, H, Hkv, causal, dtype = LOCAL_FORWARD_CASES[case]
    D, scale = 8, 8 ** -0.5
    q, k, v, do = _qkv(31, 2, Tq, Tk, H, Hkv, D, dtype)
    want_out, want_lse, _ = _oracle(q, k, v, do, causal=causal, q_off=0,
                                    k_off=0, window=0, scale=scale)
    out, lse = pa._local_forward(q, k, v, causal=causal, scale=scale,
                                 interpret=True, block_q=qb, block_k=kb)
    assert out.dtype == dtype and lse.dtype == jnp.float32
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want_out), **tol)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                               rtol=1e-5, atol=1e-5)


def test_local_forward_chooses_its_blocks_from_the_shapes():
    """The public entry: no block argument but the q block's upper bound,
    and the same result as the forced-small blocks."""
    q, k, v, _ = _qkv(32, 1, 48, 48, 2, 1, 8)
    out, lse = pa.attention_local_forward(
        q, k, v, causal=True, scale=0.3, interpret=True, block_q=16)
    small = pa._local_forward(q, k, v, causal=True, scale=0.3,
                              interpret=True, block_q=16, block_k=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(small[0]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(small[1]),
                               rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="not a multiple of kv heads"):
        pa.attention_local_forward(q, jnp.tile(k, (1, 1, 3, 1)),
                                   jnp.tile(v, (1, 1, 3, 1)), interpret=True)


@pytest.mark.parametrize("Tk,want", [
    (2048, 512), (1024, 512), (768, 384), (640, 128), (512, 512),
    (384, 384), (100, 100), (1000, 1000), (8192, 512)])
def test_key_block_is_a_lane_tiled_divisor_or_the_whole_row(Tk, want):
    kb = pa._k_blocking(Tk)
    assert kb == want and Tk % kb == 0
    assert kb == Tk or (kb % 128 == 0 and kb <= 512)


def test_q_block_is_no_longer_shrunk_by_long_rows():
    """Score tiles are [block_q, block_k]: at the LM cell's 2,048 keys the
    backward keeps the caller's 512 rows (256 while the tile spanned Tk)."""
    assert pa._q_blocking(2048, 2048, 64, 512, True, block_k=512)[0] == 512
    assert pa._q_blocking(2048, 2048, 64, 512, True)[0] == 256
    assert pa._q_blocking(8192, 8192, 128, 512, True, block_k=512)[0] == 512
    with pytest.raises(ValueError, match="scoped VMEM limit"):
        pa._q_blocking(16384, 16384, 128, 512, True, block_k=512)


@pytest.mark.parametrize("case", sorted(KEY_BLOCK_CASES))
def test_key_blocks_visited_counts_the_unmasked_tiles(case):
    """The counter against a brute-force count, on the grid of cases the
    kernels are held to: a tile is visited iff the mask keeps one of its
    (row, key) pairs (a window's tiles lie between two such edges)."""
    Tq, Tk, qb, kb, q_off, k_off, window, _, _, causal, _ = (
        KEY_BLOCK_CASES[case])
    nq, nk = -(-Tq // qb), -(-Tk // kb)
    qp = q_off + np.arange(nq * qb)[:, None]
    kp = k_off + np.arange(nk * kb)[None, :]
    keep = np.ones((nq * qb, nk * kb), bool)
    if causal:
        keep = qp >= kp
        if window:
            keep &= qp - kp < window
    brute = int(keep.reshape(nq, qb, nk, kb).any(axis=(1, 3)).sum())
    assert pa.key_blocks_visited(
        Tq, Tk, qb, kb, q_off, k_off, causal, window) == (brute, nq * nk)


def test_key_blocks_visited_at_the_lm_cells_shape():
    assert pa.key_blocks_visited(2048, 2048, 512, 512) == (10, 16)
    assert pa.key_blocks_visited(2048, 2048, 256, 256) == (36, 64)
    assert pa.key_blocks_visited(2048, 2048, 512, 512, causal=False) == (
        16, 16)


def test_a_block_above_the_diagonal_is_never_read():
    """K and V rows past a query block's last visited key block hold NaN:
    dq, out and lse stay finite (a masked product would read 0 x NaN), and
    dk, dv of those rows are the zeros nothing was added to."""
    D, scale, qb, kb = 8, 8 ** -0.5, 16, 8
    q, k, v, do = _qkv(33, 1, 16, 64, 2, 2, D)
    q_off = 16                                   # rows 16..31: hi = 4 of 8
    _, _, _, hi = pa._key_block_range(q_off, qb, kb, 64 // kb, True)
    assert hi == 4
    out, lse, want = _oracle(q, k, v, do, causal=True, q_off=q_off, k_off=0,
                             window=0, scale=scale)
    poison = lambda t: t.at[:, hi * kb:].set(jnp.nan)
    kn, vn = poison(k), poison(v)
    delta = jnp.sum(do * out, axis=-1)
    dq, dk, dv = pa._block_backward(
        q, kn, vn, do, lse, delta, jnp.asarray(q_off), jnp.asarray(0),
        causal=True, scale=scale, interpret=True, block_q=qb, block_k=kb,
        window=0)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(want[0]),
                               rtol=1e-4, atol=1e-5)
    assert not np.asarray(dk)[:, hi * kb:].any()
    assert not np.asarray(dv)[:, hi * kb:].any()
    np.testing.assert_allclose(np.asarray(dk)[:, :hi * kb],
                               np.asarray(want[1])[:, :hi * kb],
                               rtol=1e-4, atol=1e-5)
    # the local forward sits at offset 0: 16 queries over 64 keys visit 2
    out_f, lse_f = pa._local_forward(
        q, k.at[:, 16:].set(jnp.nan), v.at[:, 16:].set(jnp.nan),
        causal=True, scale=scale, interpret=True, block_q=qb, block_k=kb)
    assert np.isfinite(np.asarray(out_f)).all()
    assert np.isfinite(np.asarray(lse_f)).all()
    want_f = _oracle(q, k, v, do, causal=True, q_off=0, k_off=0, window=0,
                     scale=scale)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(want_f[0]),
                               rtol=1e-5, atol=1e-6)
