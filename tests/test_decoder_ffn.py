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


def _loss_and_grads(cpu_devices, remat):
    cfg = compose.LMConfig(vocab=32, d_model=16, heads=4, layers=4,
                           seq_len=16, micro=2, batch=2)
    m = compose.compose_parallelism(1, 2, 2, 1, devices=cpu_devices[:4])
    params = compose.device_put(m, compose.init_lm_params(cfg, m, seed=3))
    toks = compose.make_lm_batch(cfg, m, seed=1)
    grad_fn = compose.make_lm_grad_fn(cfg, m, remat=remat)

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
