"""A prompt's gated delta rule as a Pallas kernel: everything between the
convolved, split and discretised inputs and the outputs made in VMEM.

The recurrence (Kimi Delta Attention: a decay per key CHANNEL), per head
with a state ``S`` ``[K, V]`` that starts at zero::

    S <- diag(exp g_t) S;  u_t = beta_t (v_t - S^T k_t);  S <- S + k_t u_t^T
    o_t = S^T q_t

Over a SPAN of positions that a state ``S`` enters, with ``G`` the inclusive
cumulative ``g`` inside the span, the ``u`` solve a unit lower triangular
system: ``(I + diag(beta) tril(A, -1)) U = beta (V - (K o exp G) S)`` with
``A[t, s] = sum_d k_t[d] k_s[d] exp(G_t[d] - G_s[d])``; then ``O = (Q o exp
G) S + Aqk U`` (``Aqk`` as ``A`` with ``q_t`` for ``k_t``, ``s <= t``) and
the span leaves ``exp(G_end) o S + (K o exp(G_end - G))^T U``.

Two stages, as the published kernels of this layer have them.  *What no
state enters* (:func:`span_terms`): the system, its inverse ``T``, ``W = T
beta (K o exp G)``, ``Uv = T beta V`` and ``Aqk``, for all of a span's
positions in ``[span, 128]`` and ``[span, span]`` operations, never a chunk
alone.  ``A``, ``Aqk`` and ``T`` grow from single positions by DOUBLING: a
segment of ``2m`` positions is its two halves and the quadrant between them,
whose exponents are taken against the earlier half's last position, ``exp(G_t
- G_ref)`` on the later half's rows and ``exp(G_ref - G_s)`` on the earlier
half's columns: each a SUM of ``g`` over positions between the two, so ``<=
0`` whatever the decay, and nothing overflows.  The sums come from adds (a
segment's running sums grow with the segment: no product with a triangle of
ones), in float32 like every exponent and the state; the inverse of a block
lower triangular matrix is ``[[T1, 0], [-T2 M21 T1, T2]]``, exact, no power of
``A`` taken.  The doubling does not stop at the configuration's chunk: it runs
on to the span, which is the chunks whose ``U`` are solved against each other
before the state meets them.  *What the state enters* (:func:`span_pass`):
two products of ``[span + span, 128] x [128, 128]`` size a span (``W`` and
``Q o exp G`` against the state, then ``Aqk`` and the decayed keys against
``U``) and the decay, span after span, the head's state in a VMEM scratch
carried over the position axis of the grid.  A chain of dependent products
waits for the matrix unit half the time, so both functions take LISTS of
independent spans (four heads, two spans of each) and walk them abreast,
operation by operation.  Matrix products take float32 operands at the
backend's default precision (a TPU rounds them to bfloat16 and accumulates
in float32).

HBM sees ``q``, ``k``, ``v``, ``g`` and ``beta`` once and ``o`` and the
state once; no transpose precedes the kernel (a head's block is ``(tile,
128)`` at column block ``h`` of the ``[T, heads * 128]`` arrays).  Off the
TPU the same kernel runs in interpreter mode; sizes that are no lane multiple
(the tests' heads of 8 channels) are padded with channels of ``q = k = v = g
= 0``, which change nothing.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["delta_rule", "blocking", "state_passes_per_ktok"]

_LANE = 128
# positions the state's products take at once (a power of two: the doubling
# ends there), positions a grid step holds, heads a grid step walks, and
# spans of each head whose systems are made side by side: heads and spans
# abreast fill the matrix unit's latency (one alone waits for it half the
# time); chosen on the chip at [16384, 64, 128] (docs/PERF_PR49_RECORD.md)
_SPAN = 64
_TILE = 512
_HEADS = 4
_GROUP = 2


def blocking(T: int, chunk: int, heads: int) -> Tuple[int, int, int, int]:
    """``(tile, span, heads a grid step, spans of a head side by side)`` for
    ``T`` positions in chunks of ``chunk``: the span is whole chunks, the
    tile whole spans, and neither is longer than the positions' chunks."""
    whole = -(-T // chunk) * chunk
    span = chunk * max(1, min(_SPAN, whole) // chunk)
    span = 1 << (span.bit_length() - 1)         # the doubling's last segment
    tile = span * max(1, min(_TILE, whole) // span)
    most = lambda limit, n: max(d for d in range(1, limit + 1) if n % d == 0)
    return tile, span, most(_HEADS, heads), most(_GROUP, tile // span)


def state_passes_per_ktok(T: int, chunk: int, heads: int) -> float:
    """How often a thousand positions rewrite a head's state."""
    return 1000.0 / blocking(T, chunk, heads)[1]


def _dot(a, b, contract=((1,), (0,))):
    return lax.dot_general(a, b, (contract, ((), ())),
                           preferred_element_type=jnp.float32)


def _dot_nt(a, b):
    return _dot(a, b, ((1,), (1,)))


def _column(row):
    """``[1, n]`` as ``[n, 1]``: the diagonal of its broadcast, summed over
    lanes (exact: one term a row)."""
    n = row.shape[1]
    eye = lax.broadcasted_iota(jnp.int32, (n, n), 0) \
        == lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _each(f, *lists):
    """``f`` over the units' arrays, unit after unit: the units' operations
    stand side by side in program order, so one unit's product runs while
    another's waits for the matrix unit."""
    return [f(*xs) for xs in zip(*lists)]


def span_terms(q, k, v, g, beta, roll=functools.partial(jnp.roll, axis=0)):
    """Everything of a span that no state enters, for several independent
    spans (of any heads) at once: each argument a LIST with one array a
    span, ``q``, ``k`` ``[span, K]``, ``v`` ``[span, V]``, ``g`` ``[span,
    K]`` (``<= 0``) and ``beta`` ``[span, 1]``, float32.  Returns the list
    of ``(W [span, K], Uv [span, V], Aqk [span, span], Qg [span, K], KdT [K,
    span], decay [K, 1])`` (the module docstring's names; ``KdT`` is ``(K o
    exp(G_end - G))^T`` and ``decay`` ``exp(G_end)``).  ``roll(x, s)`` moves
    rows down by ``s``, wrapping."""
    B = q[0].shape[0]
    at = lax.broadcasted_iota(jnp.int32, g[0].shape, 0)
    row = lax.broadcasted_iota(jnp.int32, (B, B), 0)
    col = lax.broadcasted_iota(jnp.int32, (B, B), 1)
    # the highest bit in which two positions differ names the doubling that
    # joins them
    apart = jnp.where(row > col, row ^ col, 0)
    eye = (row == col).astype(jnp.float32)
    Aqk = _each(lambda q, k: eye * _dot_nt(q, k), q, k)
    T = [eye] * len(q)
    # of each position's segment (of m positions): the sum of g from its
    # start to the position, and from behind the position to its end
    upto, after = g, _each(jnp.zeros_like, g)
    m = 1
    while m < B:
        later = (at & m) != 0           # in the later half of its 2m
        joined = (apart >= m) & (apart < 2 * m)

        def quadrant(q, k, upto, after):
            """Keys' and queries' rows against the same columns, one
            product; and the sums of the segments of 2m: each half gains
            the other half's whole sum."""
            e = jnp.exp(jnp.where(later, upto, after))
            kf = k * e
            both = _dot_nt(jnp.concatenate([kf, q * e]), kf)
            whole = upto + after
            other = jnp.where(later, roll(whole, m), roll(whole, B - m))
            return (both, upto + jnp.where(later, other, 0.0),
                    after + jnp.where(later, 0.0, other))
        both, upto, after = zip(*_each(quadrant, q, k, upto, after))

        A = _each(lambda both, b: b * jnp.where(joined, both[:B], 0.0),
                  both, beta)
        Aqk = _each(lambda Aqk, both: Aqk + jnp.where(joined, both[B:], 0.0),
                    Aqk, both)
        if m == 1:                      # T is still the identity
            T = _each(jnp.subtract, T, A)
        else:
            TA = _each(_dot, T, A)
            T = _each(lambda T, TA: T - _dot(TA, T), T, TA)
        m *= 2
    decayed = _each(jnp.exp, upto)
    W = _each(lambda T, b, k, d: _dot(T, b * (k * d)), T, beta, k, decayed)
    Uv = _each(lambda T, b, v: _dot(T, b * v), T, beta, v)
    return list(zip(
        W, Uv, Aqk, _each(jnp.multiply, q, decayed),
        _each(lambda k, a: (k * jnp.exp(a)).T, k, after),
        _each(lambda u: _column(jnp.exp(u[B - 1:])), upto)))


def span_pass(S, terms):
    """The products of a span with the state it starts from, for several
    independent heads at once: ``S`` a list of states ``[K, V]``,
    ``terms`` a list of :func:`span_terms`' tuples.  Returns ``(the list of
    o [span, V], the list of the states the spans leave)``."""
    W, Uv, Aqk, Qg, KdT, decay = zip(*terms)
    B = W[0].shape[0]
    # W and Qg meet the same state, Aqk and KdT the same U: a product each
    WQ = _each(lambda W, Qg, S: _dot(jnp.concatenate([W, Qg]), S), W, Qg, S)
    U = _each(lambda Uv, WQ: Uv - WQ[:B], Uv, WQ)
    AK = _each(lambda Aqk, KdT, U: _dot(jnp.concatenate([Aqk, KdT]), U),
               Aqk, KdT, U)
    o = _each(lambda WQ, AK: WQ[B:] + AK[:B], WQ, AK)
    new = _each(lambda d, S, AK: d * S + AK[B:], decay, S, AK)
    return o, new


def _kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, start_ref, o_ref, state_ref,
            S_ref, bcol_ref, *, span: int, K: int, V: int, side: int,
            group: int):
    j = pl.program_id(1)
    tile = q_ref.shape[0]
    f32 = jnp.float32

    @pl.when(j == 0)
    def _():
        S_ref[...] = start_ref[...]

    # beta arrives as rows [1, tile]; the spans want it a column
    lanes = min(_LANE, tile)
    for h in range(side):
        for c in range(0, tile, lanes):
            bcol_ref[h, c:c + lanes, :] = _column(
                beta_ref[h, :, c:c + lanes])

    roll = functools.partial(pltpu.roll, axis=0)
    heads = range(side)

    def over_spans(i, carry):
        # what no state enters, for `group` spans of every head at once
        at = [pl.ds(pl.multiple_of((i * group + s) * span, span), span)
              for s in range(group)]
        units = [(rows, h) for rows in at for h in heads]
        wide = lambda ref, X: [ref[rows, h * X:(h + 1) * X].astype(f32)
                               for rows, h in units]
        terms = span_terms(
            wide(q_ref, K), wide(k_ref, K), wide(v_ref, V), wide(g_ref, K),
            [bcol_ref[h, rows, :] for rows, h in units], roll)
        # then the state through them, span after span, the heads abreast
        S = [S_ref[h] for h in heads]
        for s, rows in enumerate(at):
            o, S = span_pass(S, terms[s * side:(s + 1) * side])
            for h in heads:
                o_ref[rows, h * V:(h + 1) * V] = o[h].astype(o_ref.dtype)
        for h in heads:
            S_ref[h] = S[h]
        return carry
    lax.fori_loop(0, tile // (span * group), over_spans, 0)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        state_ref[...] = S_ref[...]


def delta_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
               beta: jax.Array, state: jax.Array, *, chunk: int,
               interpret: Optional[bool] = None):
    """The gated delta rule over ``T`` positions of one sequence from the
    state ``[H, K, V]`` (float32) before them, the heads' channels side by
    side: ``q``, ``k`` ``[T, H * K]`` and ``v`` ``[T, H * V]`` (any float
    dtype), ``g`` ``[T, H * K]`` float32 (``<= 0``), ``beta`` ``[T, H]``
    float32.  A position with ``g = 0`` and ``beta = 0`` passes the state
    unchanged (a prompt's padding).  Returns ``(o [T, H * V]`` in ``v``'s
    dtype, the state after the last position)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    T, H = beta.shape
    tile, span, side, group = blocking(T, chunk, H)
    from ..utils import metrics
    metrics.gauge(
        "bluefog_delta_scan_state_passes_per_ktok",
        "how often a thousand of a prompt's positions rewrite a delta-rule "
        "head's state, as last traced",
    ).set(state_passes_per_ktok(T, chunk, H), tile=str(tile))
    return _delta_rule(q, k, v, g, beta, state, tile, span, side, group,
                       interpret)


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10))
def _delta_rule(q, k, v, g, beta, state, tile, span, side, group, interpret):
    T, H = beta.shape
    K, V = q.shape[1] // H, v.shape[1] // H
    Kp, Vp = K + (-K) % _LANE, V + (-V) % _LANE
    Tp = T + (-T) % tile

    def padded(a, width):       # rows to whole tiles, heads to whole lanes
        a = a.reshape(T, H, -1)
        a = jnp.pad(a, ((0, Tp - T), (0, 0), (0, width - a.shape[-1])))
        return a.reshape(Tp, H * width)
    rows = lambda width: pl.BlockSpec((tile, side * width),
                                      lambda h, j: (j, h))
    held = pl.BlockSpec((side, Kp, Vp), lambda h, j: (h, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_kernel, span=span, K=Kp, V=Vp, side=side,
                          group=group),
        grid=(H // side, Tp // tile),
        in_specs=[rows(Kp), rows(Kp), rows(Vp), rows(Kp),
                  pl.BlockSpec((side, 1, tile), lambda h, j: (h, 0, j)),
                  held],
        out_specs=[rows(Vp), held],
        out_shape=[jax.ShapeDtypeStruct((Tp, H * Vp), v.dtype),
                   jax.ShapeDtypeStruct((H, Kp, Vp), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((side, Kp, Vp), jnp.float32),
                        pltpu.VMEM((side, tile, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(padded(q, Kp), padded(k, Kp), padded(v, Vp),
      padded(g.astype(jnp.float32), Kp),
      jnp.pad(beta.astype(jnp.float32), ((0, Tp - T), (0, 0))).T[:, None],
      jnp.pad(state.astype(jnp.float32), ((0, 0), (0, Kp - K), (0, Vp - V))))
    return (o.reshape(Tp, H, Vp)[:T, :, :V].reshape(T, H * V),
            state[:, :K, :V])
