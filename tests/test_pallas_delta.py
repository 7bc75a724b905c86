"""The delta-rule kernel (ops/pallas_delta.py) in interpreter mode against
the single-step form ``decoder.delta_step``, token by token: the real head
sizes at one head, spans past a chunk, a state handed from tile to tile and
from call to call, padding that begins mid-chunk, and the gauge that says
how it engages.  The chunked form's own cases through the decoder's
convolution and split are in tests/test_serve_delta.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from bluefog_tpu.models import decoder
from bluefog_tpu.ops import pallas_delta
from bluefog_tpu.utils import metrics


def inputs(T, H, K, V, seed=0, decay=1.0):
    """(q, k, v, g, beta) flattened as the kernel takes them: unit keys and
    queries, ``g <= 0`` a channel, ``beta`` in (0, 2)."""
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (T, H, K))) * K ** -0.5
    k = unit(jax.random.normal(ks[1], (T, H, K)))
    v = jax.random.normal(ks[2], (T, H, V))
    g = -decay * jnp.exp(jax.random.normal(ks[3], (T, H, K)) - 2.0)
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (T, H)))
    flat = lambda a: a.reshape(T, -1)
    return flat(q), flat(k), flat(v), flat(g), beta


@jax.jit
def token_by_token(q, k, v, g, beta, state):
    """``(o [T, H * V], the state)`` through :func:`decoder.delta_step`."""
    T, H = beta.shape
    per = lambda a: a.reshape(T, 1, H, -1)

    def step(S, x):
        qt, kt, vt, gt, bt = x
        o, S = decoder.delta_step(S, gt, bt, qt, kt, vt)
        return S, o[0]
    S, o = lax.scan(step, state[None],
                    (per(q), per(k), per(v), per(g), beta[:, None]))
    return o.reshape(T, -1), S[0]


def test_one_head_of_the_real_sizes_is_the_recurrence(monkeypatch):
    """head_dim 128, state 128, chunks of 16 in spans of 64, two tiles of
    128 positions: the kernel's blocks as the chip takes them."""
    monkeypatch.setattr(pallas_delta, "_TILE", 128)
    T, H, K, V = 256, 1, 128, 128
    assert pallas_delta.blocking(T, 16, H) == (128, 64, 1, 2)
    args = inputs(T, H, K, V)
    zero = jnp.zeros((H, K, V))
    o, S = pallas_delta.delta_rule(*args, zero, chunk=16)
    want, Sw = token_by_token(*args, zero)
    assert o.shape == (T, H * V) and S.shape == (H, K, V)
    np.testing.assert_allclose(o, want, atol=2e-6)
    np.testing.assert_allclose(S, Sw, atol=2e-6)


@pytest.mark.parametrize("tile,span", [(8, 8), (16, 4), (32, 16), (64, 64)])
def test_a_state_handed_on_equals_one_tile_over_the_whole(tile, span,
                                                          monkeypatch):
    """Tiles of one span and of several (two side by side where a tile has
    an even number), spans of one chunk and of several, three heads abreast
    of widths that are no lane multiple: the
    same outputs and state as ONE tile over all 64 positions, and as two
    calls of which the second starts from the first's state."""
    T, H, K, V, chunk = 64, 3, 8, 12, 4
    args = inputs(T, H, K, V, seed=1, decay=30.0)
    start = jax.random.normal(jax.random.key(9), (H, K, V))
    monkeypatch.setattr(pallas_delta, "_TILE", T)
    whole_o, whole_S = pallas_delta.delta_rule(*args, start, chunk=chunk)
    want, Sw = token_by_token(*args, start)
    np.testing.assert_allclose(whole_o, want, atol=5e-6)
    np.testing.assert_allclose(whole_S, Sw, atol=5e-6)
    monkeypatch.setattr(pallas_delta, "_TILE", tile)
    monkeypatch.setattr(pallas_delta, "_SPAN", span)
    assert pallas_delta.blocking(T, chunk, H)[:3] == (tile, span, 3)
    o, S = pallas_delta.delta_rule(*args, start, chunk=chunk)
    np.testing.assert_allclose(o, whole_o, atol=5e-6)
    np.testing.assert_allclose(S, whole_S, atol=5e-6)
    first = [a[:40] for a in args]
    then = [a[40:] for a in args]
    o1, S1 = pallas_delta.delta_rule(*first, start, chunk=chunk)
    o2, S2 = pallas_delta.delta_rule(*then, S1, chunk=chunk)
    np.testing.assert_allclose(jnp.concatenate([o1, o2]), whole_o, atol=5e-6)
    np.testing.assert_allclose(S2, whole_S, atol=5e-6)


@pytest.mark.parametrize("real", [1, 7, 21, 22])
def test_padding_that_begins_mid_chunk_leaves_the_last_real_tokens_state(
        real, monkeypatch):
    """``g = 0`` and ``beta = 0`` from position ``real`` on (inside a chunk,
    a span and a tile): the state is the recurrence's after ``real``
    tokens."""
    monkeypatch.setattr(pallas_delta, "_TILE", 16)
    T, H, K, V = 40, 2, 8, 12
    q, k, v, g, beta = inputs(T, H, K, V, seed=2)
    live = jnp.arange(T) < real
    g, beta = jnp.where(live[:, None], g, 0.0), jnp.where(
        live[:, None], beta, 0.0)
    zero = jnp.zeros((H, K, V))
    o, S = pallas_delta.delta_rule(q, k, v, g, beta, zero, chunk=4)
    want, Sw = token_by_token(*(a[:real] for a in (q, k, v, g, beta)), zero)
    np.testing.assert_allclose(o[:real], want, atol=2e-6)
    np.testing.assert_allclose(S, Sw, atol=2e-6)
    assert bool(jnp.isfinite(o).all())


def test_the_gauge_says_how_often_a_thousand_positions_rewrite_a_state():
    """From the wrapper's own constants: 15.6 at spans of 64 positions
    (62.5 were it a chunk of 16 a pass), whatever the tile; a prompt
    shorter than a span passes once."""
    assert pallas_delta.blocking(16384, 16, 64) == (
        pallas_delta._TILE, pallas_delta._SPAN, pallas_delta._HEADS,
        pallas_delta._GROUP)
    assert pallas_delta.state_passes_per_ktok(16384, 16, 64) \
        == 1000.0 / pallas_delta._SPAN
    assert pallas_delta.state_passes_per_ktok(20, 4, 3) == 1000.0 / 16
    metrics.reset_metrics()
    args = inputs(20, 3, 8, 12)
    jax.jit(lambda *a: pallas_delta.delta_rule(*a, chunk=4))(
        *args, jnp.zeros((3, 8, 12)))
    gauge = metrics.get_metric("bluefog_delta_scan_state_passes_per_ktok")
    assert gauge.value(tile="16") == 62.5
