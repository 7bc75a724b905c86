"""Pallas TPU kernel: the grouped expert GEMM for dropless MoE.

The portable XLA path (:func:`bluefog_tpu.moe.dropless.grouped_ffn_xla`)
gathers ``w1[tile_eid]`` / ``w2[tile_eid]`` into ``[n_tiles, D, F]``
weight copies before the batched einsum — at production expert counts
that materializes each expert's weights once *per tile* in HBM.  This
kernel keeps the weights where they live: the ``tile_eid`` map rides the
scalar-prefetch channel (``pltpu.PrefetchScalarGridSpec``), each grid
step's BlockSpec index map reads ``eids[i]`` to DMA exactly ONE expert's
``w1``/``w2`` block into VMEM, and both matmuls (gelu between) run on
the MXU without the scores or the gathered weights ever round-tripping
through HBM.

Same interface as the XLA path — ``(xt [G, tile, D], tile_eid [G],
w1 [E, D, F], w2 [E, F, D]) -> [G, tile, D]``, no tp psum inside — so
``BLUEFOG_MOE_GROUPED_IMPL=pallas`` is a drop-in swap.  The backward
pass is a ``custom_vjp`` in plain XLA (dgrad/wgrad einsums +
scatter-add over ``tile_eid``): exactly the operations AD derives for
the XLA path, so gradients are path-identical.  Off-TPU the kernel runs
in interpreter mode (slow but correct) — the CPU tests exercise the
same code path; tests/test_tpu_aot.py AOT-lowers it through Mosaic
under the same xfail guard as the flash-attention kernels.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["grouped_ffn_pallas"]

# f32 tiles are (8, 128) sublane x lane: a grouped block's second-to-minor
# dim (the row tile) must be a multiple of 8.  Decode-regime tiles are
# smaller (T = lanes * top_k is tiny), so _forward pads them up to the
# sublane minimum and slices the pad rows back off — the pad rows are
# zeros through both matmuls, never gathered, so this costs one VMEM-size
# bump and no correctness.
_MIN_SUBLANE = 8

# What a kernel may allocate on a v5e core (Mosaic's default scoped limit is
# 16 MiB); the block of F is sized to stay under it with room for Mosaic's
# own temporaries.
_VMEM_BUDGET = 14 * 1024 * 1024
_LANE = 128


def _block_f(tile: int, D: int, F: int, x_bytes: int, w_bytes: int) -> int:
    """Largest lane-aligned divisor of ``F`` whose working set fits
    :data:`_VMEM_BUDGET`.  Per grid step the pipeline double-buffers the x
    tile, one ``[D, bf]`` and one ``[bf, D]`` weight slice and the f32
    output tile; the body adds the f32 casts of both slices (when they
    are not f32 already) and the ``[tile, bf]`` activation.  An ``F`` that
    is not a multiple of 128 can only be taken whole (a block's minor dim
    is a lane multiple or the full dim)."""
    def need(bf):
        pipelined = 2 * (tile * D * x_bytes + 2 * D * bf * w_bytes
                         + tile * D * 4)
        casts = 2 * D * bf * 4 if w_bytes != 4 else 0
        body = casts + 2 * tile * bf * 4 + tile * D * 4
        return pipelined + body

    if F % _LANE:
        candidates = [F]
    else:
        candidates = [bf for bf in range(F, 0, -_LANE) if F % bf == 0]
    for bf in candidates:
        if need(bf) <= _VMEM_BUDGET:
            return bf
    raise ValueError(
        f"grouped_ffn_pallas: tile {tile} x D {D} with the smallest F block "
        f"({candidates[-1]} of {F}) needs {need(candidates[-1])} bytes of "
        f"VMEM, over the {_VMEM_BUDGET}-byte budget (16 MiB scoped limit)")


def _vma_of(x: jax.Array):
    # under shard_map the output varies over the same mesh axes as the input
    return getattr(jax.typeof(x), "vma", frozenset()) or frozenset()


def _grouped_kernel(eids_ref, x_ref, w1_ref, w2_ref, o_ref):
    x = x_ref[0].astype(jnp.float32)                   # [tile, D]
    u = jax.nn.gelu(jax.lax.dot_general(
        x, w1_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32))           # [tile, block_f]
    part = jax.lax.dot_general(
        u, w2_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)            # [tile, D]
    # gelu is elementwise in F, so the F blocks sum: the output block index
    # ignores the F grid axis and stays resident as the accumulator
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        o_ref[0] = part

    @pl.when(j > 0)
    def _():
        o_ref[0] += part


def _forward(xt: jax.Array, tile_eid: jax.Array, w1: jax.Array,
             w2: jax.Array, interpret: bool) -> jax.Array:
    G, real_tile, D = xt.shape
    if real_tile < _MIN_SUBLANE:                       # decode-regime tiles
        xt = jnp.pad(xt, ((0, 0), (0, _MIN_SUBLANE - real_tile), (0, 0)))
    G, tile, D = xt.shape
    _, _, F = w1.shape
    bf = _block_f(tile, D, F, xt.dtype.itemsize, w1.dtype.itemsize)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                         # tile_eid
        grid=(G, F // bf),
        in_specs=[
            pl.BlockSpec((1, tile, D), lambda i, j, eids: (i, 0, 0)),
            pl.BlockSpec((1, D, bf), lambda i, j, eids: (eids[i], 0, j)),
            pl.BlockSpec((1, bf, D), lambda i, j, eids: (eids[i], j, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile, D), lambda i, j, eids: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _grouped_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((G, tile, D), jnp.float32,
                                       vma=_vma_of(xt)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(tile_eid.astype(jnp.int32), xt, w1, w2)
    if real_tile < tile:
        out = out[:, :real_tile]
    return out.astype(xt.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _grouped_ffn(xt, tile_eid, w1, w2, interpret):
    return _forward(xt, tile_eid, w1, w2, interpret)


def _grouped_fwd(xt, tile_eid, w1, w2, interpret):
    return _forward(xt, tile_eid, w1, w2, interpret), (xt, tile_eid, w1, w2)


def _grouped_bwd(interpret, res, g):
    # Plain-XLA backward: the same dgrad/wgrad einsums AD derives for the
    # portable path, with the per-tile weight grads scatter-added back to
    # their experts over tile_eid — path-identical gradients by design.
    xt, tile_eid, w1, w2 = res
    w1g, w2g = w1[tile_eid], w2[tile_eid]              # [G, D, F] / [G, F, D]
    s = jnp.einsum("gtd,gdf->gtf", xt, w1g)
    u, gelu_vjp = jax.vjp(jax.nn.gelu, s)
    du = jnp.einsum("gtd,gfd->gtf", g, w2g)
    dw2 = jnp.zeros_like(w2).at[tile_eid].add(
        jnp.einsum("gtf,gtd->gfd", u, g))
    ds = gelu_vjp(du)[0]
    dxt = jnp.einsum("gtf,gdf->gtd", ds, w1g)
    dw1 = jnp.zeros_like(w1).at[tile_eid].add(
        jnp.einsum("gtd,gtf->gdf", xt, ds))
    d_eid = np.zeros(tile_eid.shape, jax.dtypes.float0)
    return dxt, d_eid, dw1, dw2


_grouped_ffn.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_ffn_pallas(xt: jax.Array, tile_eid: jax.Array, w1: jax.Array,
                       w2: jax.Array, *,
                       interpret: Optional[bool] = None) -> jax.Array:
    """Grouped expert FFN on the MXU: ``gelu(xt @ w1[eid]) @ w2[eid]``
    per tile, with the per-tile expert weights DMA'd by the
    scalar-prefetched ``tile_eid`` map.  Drop-in for
    :func:`bluefog_tpu.moe.dropless.grouped_ffn_xla` (no tp psum inside;
    the caller reduces)."""
    if xt.ndim != 3 or tile_eid.shape != (xt.shape[0],):
        raise ValueError(
            f"grouped_ffn_pallas: xt must be [n_tiles, tile, D] with "
            f"tile_eid [n_tiles], got {xt.shape} / {tile_eid.shape}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _grouped_ffn(xt, tile_eid, w1, w2, bool(interpret))
