"""Ulysses (all-to-all head-scatter) sequence parallelism.

Pins the second SP mode to the dense oracle, to ring attention, and through
gradients (jnp and Pallas-interpret paths); plus the transformer model
switch and the head-divisibility contract.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from bluefog_tpu import ops

N = 8
B, H, D = 2, 8, 16
T_LOCAL = 4
T = N * T_LOCAL


@pytest.fixture(scope="module")
def mesh(cpu_devices):
    return Mesh(np.array(cpu_devices), ("rank",))


def _reference_attention(q, k, v, causal):
    s = np.einsum("bihd,bjhd->bihj", q, k) / np.sqrt(q.shape[-1])
    if causal:
        mask = np.arange(T)[:, None] >= np.arange(T)[None, :]
        s = np.where(mask[None, :, None, :], s, -np.inf)
    p = np.exp(s - s.max(axis=-1, keepdims=True))
    p /= p.sum(axis=-1, keepdims=True)
    return np.einsum("bihj,bjhd->bihd", p, v)


def _qkv(seed=0):
    rng = np.random.default_rng(seed)
    return tuple(
        rng.normal(size=(B, T, H, D)).astype(np.float32) for _ in range(3))


def _run_sharded(fn, mesh, *arrs):
    # sequence axis sharded: [B, T, H, D] -> per-device [B, T/N, H, D]
    sharded = jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(P(None, "rank"),) * len(arrs),
        out_specs=P(None, "rank")))
    return np.asarray(sharded(*[jnp.asarray(a) for a in arrs]))


@pytest.mark.parametrize("causal", [False, True])
def test_matches_dense_oracle(mesh, causal):
    q, k, v = _qkv()
    out = _run_sharded(
        lambda a, b, c: ops.ulysses_attention(a, b, c, axis="rank",
                                              causal=causal),
        mesh, q, k, v)
    np.testing.assert_allclose(
        out, _reference_attention(q, k, v, causal), rtol=2e-4, atol=2e-5)


def test_matches_ring_attention(mesh):
    q, k, v = _qkv(1)
    ring = _run_sharded(
        lambda a, b, c: ops.ring_attention(a, b, c, axis="rank", causal=True),
        mesh, q, k, v)
    uly = _run_sharded(
        lambda a, b, c: ops.ulysses_attention(a, b, c, axis="rank",
                                              causal=True),
        mesh, q, k, v)
    np.testing.assert_allclose(uly, ring, rtol=2e-4, atol=2e-5)


def test_gradients_match_oracle(mesh):
    q, k, v = _qkv(2)

    def uly_loss(a, b, c):
        out = ops.ulysses_attention(a, b, c, axis="rank", causal=True)
        return jax.lax.psum(jnp.sum(out.astype(jnp.float32) ** 2), "rank")

    grads = jax.jit(jax.shard_map(
        jax.grad(uly_loss, argnums=(0, 1, 2)), mesh=mesh,
        in_specs=(P(None, "rank"),) * 3, out_specs=(P(None, "rank"),) * 3))(
            *(jnp.asarray(a) for a in (q, k, v)))

    def dense_loss(a, b, c):
        s = jnp.einsum("bihd,bjhd->bihj",
                       a.astype(jnp.float32) / np.sqrt(D),
                       b.astype(jnp.float32))
        mask = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
        s = jnp.where(mask[None, :, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bihj,bjhd->bihd", p, c.astype(jnp.float32))
        return jnp.sum(out ** 2)

    expect = jax.grad(dense_loss, argnums=(0, 1, 2))(
        *(jnp.asarray(a) for a in (q, k, v)))
    for g, e in zip(grads, expect):
        np.testing.assert_allclose(np.asarray(g), np.asarray(e),
                                   rtol=5e-4, atol=5e-5)


def test_pallas_path_matches_jnp(mesh):
    q, k, v = _qkv(3)

    def loss(use_pallas):
        def f(a, b, c):
            out = ops.ulysses_attention(
                a, b, c, axis="rank", causal=True, use_pallas=use_pallas,
                pallas_block_q=8)
            return jax.lax.psum(jnp.sum(out.astype(jnp.float32) ** 2), "rank")
        # check_vma=False for BOTH paths: interpret-mode pallas needs it
        # (mixed varying operands, same caveat as test_pallas_attention.py),
        # and without vma the transpose of the loss psum scales cotangents
        # by n — identically in both paths, so the comparison is exact.
        # True-gradient correctness is pinned by test_gradients_match_oracle
        # (vma on, jnp) and the vma-clean compiled TPU path
        # (tests/test_tpu_aot.py::test_ulysses_kernels_lower_for_tpu).
        return jax.jit(jax.shard_map(
            jax.value_and_grad(f, argnums=(0, 1, 2)), mesh=mesh,
            in_specs=(P(None, "rank"),) * 3,
            out_specs=(P(), (P(None, "rank"),) * 3),
            check_vma=False))(
                *(jnp.asarray(a) for a in (q, k, v)))

    (l_j, g_j), (l_p, g_p) = loss(False), loss(True)
    np.testing.assert_allclose(float(l_p), float(l_j), rtol=1e-4)
    for gp, gj in zip(g_p, g_j):
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gj),
                                   rtol=5e-3, atol=5e-4)


def test_rejects_uneven_heads(mesh):
    rng = np.random.default_rng(4)
    arrs = tuple(jnp.asarray(
        rng.normal(size=(B, T, 6, D)).astype(np.float32)) for _ in range(3))
    with pytest.raises(ValueError, match="divisible"):
        _run_sharded(
            lambda a, b, c: ops.ulysses_attention(a, b, c, axis="rank"),
            mesh, *arrs)


def test_transformer_sp_mode_switch(mesh):
    """The LM produces (near-)identical logits under either SP mode with the
    same params — the modes are drop-in swaps at the model level."""
    from bluefog_tpu import models

    V, L = 64, 2
    tokens = jnp.asarray(
        np.random.default_rng(5).integers(0, V, size=(N, B, T_LOCAL)),
        jnp.int32)

    def build(sp_mode):
        return models.RingTransformerLM(
            vocab_size=V, num_layers=L, num_heads=H, d_model=64,
            max_seq_len=T, axis="rank", sp_mode=sp_mode, dtype=jnp.float32)

    m_ring, m_uly = build("ring"), build("ulysses")
    # init with an axis-free twin (identical param tree): ring_attention
    # needs the mesh axis bound, which only exists inside shard_map
    m_init = models.RingTransformerLM(
        vocab_size=V, num_layers=L, num_heads=H, d_model=64,
        max_seq_len=T, axis=None, dtype=jnp.float32)
    params = m_init.init(jax.random.key(0), tokens[0], pos_offset=0)

    def run(model):
        def per_rank(p, tok):
            tok = tok[0]
            off = jax.lax.axis_index("rank") * T_LOCAL
            return model.apply(p, tok, pos_offset=off)[None]
        return np.asarray(jax.jit(jax.shard_map(
            per_rank, mesh=mesh, in_specs=(P(), P("rank")),
            out_specs=P("rank")))(params, tokens))

    np.testing.assert_allclose(run(m_ring), run(m_uly), rtol=1e-4, atol=1e-4)


def test_local_flash_attention_vjp_matches_dense():
    """The exported standalone flash wrapper (and its hand-written VJP) —
    no mesh, no collectives — against the dense oracle."""
    from bluefog_tpu.ops import local_flash_attention
    from bluefog_tpu.ops.ulysses import dense_attention

    rng = np.random.default_rng(11)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 32, 4, 16)).astype(np.float32))
               for _ in range(3))

    for causal in (False, True):
        def loss_flash(a, b, c):
            out = local_flash_attention(
                a, b, c, causal, 1 / np.sqrt(16), 8, True, None)
            return jnp.sum(out.astype(jnp.float32) ** 2)

        def loss_dense(a, b, c):
            out = dense_attention(a, b, c, causal, 1 / np.sqrt(16))
            return jnp.sum(out.astype(jnp.float32) ** 2)

        lf, gf = jax.value_and_grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        ld, gd = jax.value_and_grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(float(lf), float(ld), rtol=1e-5)
        for a, b in zip(gf, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal,share", [(True, 15 / 25), (False, 1.0)])
def test_local_flash_attention_walks_key_blocks_and_says_how_many(
        causal, share):
    """640 positions are five key blocks of 128 (the code's own choice):
    value and gradients against the dense oracle, and the trace-time gauge
    of the share of score tiles each direction's loop computes."""
    from bluefog_tpu.ops import local_flash_attention, pallas_attention as pa
    from bluefog_tpu.ops.ulysses import dense_attention
    from bluefog_tpu.utils import metrics

    T, D = 640, 8
    assert pa._k_blocking(T) == 128
    rng = np.random.default_rng(12)
    q, k, v = (jnp.asarray(rng.normal(size=(1, T, 2, D)).astype(np.float32))
               for _ in range(3))

    def loss(attend):
        def f(a, b, c):
            return jnp.sum(attend(a, b, c).astype(jnp.float32) ** 2)
        return jax.value_and_grad(f, argnums=(0, 1, 2))(q, k, v)

    metrics.reset_metrics()
    lf, gf = loss(lambda a, b, c: local_flash_attention(
        a, b, c, causal, D ** -0.5, 128, True, None))
    ld, gd = loss(lambda a, b, c: dense_attention(a, b, c, causal, D ** -0.5))
    np.testing.assert_allclose(float(lf), float(ld), rtol=1e-5)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
    gauge = metrics.get_metric("bluefog_flash_key_blocks_visited_share")
    for direction in ("forward", "backward"):
        assert gauge.value(**{"pass": direction}) == pytest.approx(share)
