"""What ``decoder.dense_ffn`` leaves for the backward pass.

* **residuals** — under ``lax.scan`` every value the backward pass reads is
  stacked over the layers; the plain expression ``gelu(h @ w1) @ w2`` stacks
  the pre-activation, four intermediates of the tanh gelu and the second
  matmul's operand, ``dense_ffn`` the pre-activation alone;
* **same numbers** — loss and gradients of ``make_lm_grad_fn`` equal the
  plain expression's, with and without the whole-stage ``remat``;
* **forward only** — a program that is never differentiated (serving's
  prefill and decode) lowers and compiles to the same operations.
"""
import collections
import re
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from bluefog_tpu.models import decoder
from bluefog_tpu.parallel import compose
from bluefog_tpu.serve import ServeConfig, ServeEngine


def plain_ffn(lp, h):
    """The FFN written out, as plain AD differentiates it."""
    return lax.psum(jax.nn.gelu(h @ lp["w1"]) @ lp["w2"], "tp"), None


def _ffn_residuals(tp, ffn):
    """Shapes of what ``jax.vjp`` of a scan over ``decoder_block`` keeps
    that is an activation of the FFN's width: ``[layers, B, T, F // tp]``
    (the leaves of the vjp closure; ``tp`` is a vmapped axis in front)."""
    cfg = SimpleNamespace(d_model=16, heads=4, ffn_mult=4)
    L, B, T = 3, 2, 8
    bp = {k: jnp.zeros((tp, L) + s, jnp.float32)
          for k, s in decoder.block_param_shapes(cfg, tp).items()}
    x = jnp.zeros((B, T, cfg.d_model), jnp.float32)

    def stage(bp, x):
        return lax.scan(lambda c, lp: (decoder.decoder_block(
            cfg, tp, lp, c, jnp.arange(T), lambda q, k, v: (q + k + v, None),
            ffn)[0], None), x, bp)[0]

    kept = jax.eval_shape(
        jax.vmap(lambda bp, x: jax.vjp(stage, bp, x)[1], in_axes=(0, None),
                 axis_name="tp"), bp, x)
    want = (tp, L, B, T, cfg.ffn_mult * cfg.d_model // tp)
    return [v.dtype for v in jax.tree.leaves(kept) if v.shape == want]


@pytest.mark.parametrize("tp", [1, 2])
def test_scan_keeps_one_ffn_activation_per_layer(tp):
    assert _ffn_residuals(tp, decoder.dense_ffn) == [jnp.float32]
    # plain AD also keeps intermediates of the tanh gelu and the second
    # matmul's operand (six in all on this jax)
    assert len(_ffn_residuals(tp, plain_ffn)) >= 5


def _loss_and_grads(cpu_devices, remat, use_pallas=False, sp=1):
    cfg = compose.LMConfig(vocab=32, d_model=16, heads=4, layers=4,
                           seq_len=16, micro=2, batch=2)
    m = compose.compose_parallelism(1, 2, 2, sp,
                                    devices=cpu_devices[:4 * sp])
    params = compose.device_put(m, compose.init_lm_params(cfg, m, seed=3))
    toks = compose.make_lm_batch(cfg, m, seed=1)
    grad_fn = compose.make_lm_grad_fn(cfg, m, remat=remat,
                                      use_pallas=use_pallas)

    def per_device(p, t):
        loss, g = grad_fn(jax.tree.map(lambda v: v[0], p), t[0])
        return loss[None], jax.tree.map(lambda v: v[None], g)
    spec = P(compose.AXES)
    return jax.jit(jax.shard_map(per_device, mesh=m.mesh, in_specs=spec,
                                 out_specs=spec, check_vma=False))(
                                     params, toks)


@pytest.mark.parametrize("remat", [False, True])
def test_lm_grads_equal_the_plain_expression(cpu_devices, monkeypatch, remat):
    got_loss, got = _loss_and_grads(cpu_devices, remat)
    block = decoder.decoder_block        # training takes its default ffn
    monkeypatch.setattr(decoder, "decoder_block",
                        lambda *a, **k: block(*a, ffn=plain_ffn, **k))
    want_loss, want = _loss_and_grads(cpu_devices, remat)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-6)
    got = dict(jax.tree_util.tree_leaves_with_path(got))
    for path, w in jax.tree_util.tree_leaves_with_path(want):
        w = np.asarray(w)
        assert np.abs(w).max() > 0, path
        assert np.abs(np.asarray(got[path]) - w).max() \
            <= 1e-6 * np.abs(w).max(), path


@pytest.mark.parametrize("sp,lag", [(1, 0), (1, 2), (1, 5), (2, 0), (2, 2),
                                    (2, 5)])
def test_lm_loss_is_the_mean_over_positions_past_lag_in_each_shard(
        cpu_devices, sp, lag):
    """The step weights a shard's first ``lag`` positions 0 where it used to
    slice the logits (a slice is a copy of all of them on the chip): the loss
    is still the numpy oracle's mean cross-entropy over the positions past
    ``lag`` of every sp shard, against the token ``lag`` back."""
    from test_serve import _ref_forward
    cfg = compose.LMConfig(vocab=32, d_model=16, heads=4, layers=2,
                           seq_len=16, micro=2, batch=2, lag=lag)
    m = compose.compose_parallelism(1, 2, 2, sp,
                                    devices=cpu_devices[:4 * sp])
    params = compose.device_put(m, compose.init_lm_params(cfg, m, seed=3))
    toks = compose.make_lm_batch(cfg, m, seed=1)
    grad_fn = compose.make_lm_grad_fn(cfg, m)
    spec = P(compose.AXES)
    loss = jax.jit(jax.shard_map(
        lambda p, t: grad_fn(jax.tree.map(lambda v: v[0], p), t[0])[0][None],
        mesh=m.mesh, in_specs=spec, out_specs=spec, check_vma=False))(
            params, toks)
    Pn, Tl = jax.tree.map(np.asarray, params), cfg.seq_len // sp
    shards = np.asarray(toks)[:sp]               # stage 0, tp 0: [sp, M, B, Tl]
    ces = []
    for row in np.concatenate(list(shards), -1).reshape(-1, cfg.seq_len):
        lg = _ref_forward(Pn, m, cfg, row)
        logp = lg - lg.max(-1, keepdims=True)
        logp = logp - np.log(np.exp(logp).sum(-1, keepdims=True))
        for t in range(cfg.seq_len):
            if t % Tl >= lag:
                ces.append(-logp[t, row[t - lag]])
    np.testing.assert_allclose(np.asarray(loss), np.mean(ces), rtol=2e-5)


@pytest.mark.parametrize("lag", [1, 3])
def test_lm_grads_equal_the_plain_references(cpu_devices, lag):
    """The loss and every gradient of the step's gradient function on one
    device against ``jax.grad`` of the benchmark's plain reference, which
    slices the logits and gathers the label's logit: the read-out's
    compare-and-weigh form differentiates to the same numbers."""
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "reference_composed_lm", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "perfbench", "reference", "composed_lm.py"))
    reference = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reference)
    cfg = compose.LMConfig(vocab=48, d_model=16, heads=4, layers=2,
                           seq_len=16, micro=2, batch=2, lag=lag)
    m = compose.compose_parallelism(1, 1, 1, 1, devices=cpu_devices[:1])
    params = compose.device_put(m, compose.init_lm_params(cfg, m, seed=3))
    toks = compose.make_lm_batch(cfg, m, seed=1)
    grad_fn = compose.make_lm_grad_fn(cfg, m)

    def per_device(p, t):
        loss, g = grad_fn(jax.tree.map(lambda v: v[0], p), t[0])
        return loss[None], jax.tree.map(lambda v: v[None], g)
    spec = P(compose.AXES)
    got_loss, got = jax.jit(jax.shard_map(
        per_device, mesh=m.mesh, in_specs=spec, out_specs=spec,
        check_vma=False))(params, toks)

    def plain(p):
        flat = {**p["blocks"], **p["shared"]}
        return jnp.mean(jnp.stack([
            reference.copy_task_loss(flat, row, cfg.heads, lag)
            for row in toks[0].reshape(-1, cfg.seq_len)]))
    want_loss, want = jax.value_and_grad(plain)(
        jax.tree.map(lambda v: v[0], params))
    np.testing.assert_allclose(got_loss[0], want_loss, rtol=1e-5)
    got = dict(jax.tree_util.tree_leaves_with_path(got))
    for path, w in jax.tree_util.tree_leaves_with_path(want):
        w = np.asarray(w)
        assert np.abs(w).max() > 0, path
        assert np.abs(np.asarray(got[path][0]) - w).max() \
            <= 1e-4 * np.abs(w).max(), path


def test_lm_grads_under_the_pallas_kernels_equal_the_plain_path(
        cpu_devices):
    """``use_pallas=True`` swaps attention for the flash kernels: on a
    pp x tp x sp carving the loss and every gradient are the plain path's
    (the read-out, the stage mask, the ``1 / TP`` seed and the psums are the
    same code under both)."""
    want_loss, want = _loss_and_grads(cpu_devices, False, sp=2)
    got_loss, got = _loss_and_grads(cpu_devices, False, use_pallas=True, sp=2)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    got = dict(jax.tree_util.tree_leaves_with_path(got))
    for path, w in jax.tree_util.tree_leaves_with_path(want):
        w = np.asarray(w)
        assert np.abs(w).max() > 0, path
        assert np.abs(np.asarray(got[path]) - w).max() \
            <= 2e-5 * np.abs(w).max(), path


def _opcodes(text):
    """Opcode histogram of an HLO or StableHLO module's text."""
    return collections.Counter(
        re.findall(r"= (?:\S+ )?([a-z_][\w.\-]*)\(", text))


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_serving_programs_unchanged(cpu_devices, monkeypatch, program):
    """``dense_ffn``'s checkpoint is never differentiated in serving, so
    the programs hold the plain expression's operations, lowered and
    compiled."""
    cfg = compose.LMConfig(vocab=32, d_model=16, heads=4, layers=2,
                           seq_len=16, micro=1, batch=2)
    m = compose.compose_parallelism(1, 1, 2, 1, devices=cpu_devices[:2])

    def texts():
        eng = ServeEngine(
            m, cfg, compose.init_lm_params(cfg, m, seed=3),
            ServeConfig(batch_buckets=(2,), prefill_buckets=(8,), slots=2,
                        max_len=32, decode_steps_per_call=1))
        n = m.size
        i32 = lambda *s: jnp.zeros((n,) + s, jnp.int32)
        if program == "prefill":
            lowered = eng._build(eng._prefill_body).lower(
                *eng._args(i32(8 + 4)))
        else:
            lowered = eng._build(eng._decode_body).lower(
                *eng._args(i32(2, 1 + 4)))
        return (_opcodes(lowered.as_text(dialect="hlo")),
                _opcodes(lowered.compile().as_text()))

    got = texts()
    monkeypatch.setattr(decoder, "dense_ffn", plain_ffn)
    want = texts()
    assert sum(want[0].values()) > 50 and want[0]["dot"] >= 4
    assert got == want
