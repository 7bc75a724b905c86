"""Tensor fusion: bucket pytrees into per-dtype buffers for collective ops.

TPU-native counterpart of the reference's fusion-buffer machinery
(``FusionBufferManager``, ``tensor_queue.h:75-124``; fused neighbor ops,
``mpi_controller.cc:519-745``; response fusion in the coordinator,
``operations.cc:943-1020``).  The reference copies up to 8 MB of tensors into
a persistent fusion buffer so one MPI/NCCL call carries many tensors; the
motivation — amortize per-message latency over the edge set — applies equally
to ICI collectives: a gossip step over a pytree with L leaves otherwise lowers
to ``L x num_rounds`` ``ppermute`` ops, each with its own latency and its own
barrier against XLA's latency-hiding scheduler.  Fusing the pytree into one
buffer per dtype makes it ``num_rounds`` permutes total, independent of
model depth.

Unlike the reference there is no threshold or cycle timer: the bucketing is
static (shapes are known at trace time) and fuses the *whole* tree (XLA
handles multi-hundred-MB permutes fine; no 8 MB ceiling).  It is not free,
and what it costs depends on the ORDER of the buffer.  A TPU keeps a matrix
in ``(rows, 128)`` tiles (8 rows at four bytes, 16 at two, 32 at one) and a
1-D array in linear order, so there are two bucketings:

* :func:`fuse_tree` — one 1-D buffer per dtype.  ``ravel`` into it and
  ``reshape`` out of it are each a relayout, a copy of the leaf through
  another order, that XLA does NOT fold away on a TPU: round one combine
  over 1.62 GB of f32 matrices the compiled program holds five ``copy`` and
  seven ``reshape`` passes beside the concatenation and the split.  It is
  the bucketing for callers that pad, shard or split a buffer by element
  count: the mailbox (window) strategies, the sharded-update paths,
  ``gradient_allreduce`` and ``hierarchical_communicator`` through
  :func:`fused_leaf_op`.
* :func:`tile_tree` — one ``[n_tiles, rows, 128]`` buffer per dtype, in the
  leaves' own tile order.  A leaf whose last dimension is a multiple of 128
  and whose leading dimensions multiply to a multiple of ``rows`` enters as
  a bitcast (one pass: the write into the buffer) and a tile-wise op on its
  span of the buffer is viewed back as a bitcast; any other leaf (a bias, a
  scalar, a ``[3, 3, 64, 64]`` kernel) is ravelled and zero-padded to whole
  tiles, which costs what :func:`fuse_tree` costs.  The choice is made per
  leaf from its shape and dtype.  ``neighbor_communicator(fuse=True)``, and
  through it every strategy built from a ``communication_type`` string,
  gossips this buffer and combines span by span.
"""
from __future__ import annotations

from typing import Any, Callable, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

__all__ = ["fuse_tree", "FusedTree", "fused_leaf_op", "tile_tree",
           "TiledTree"]

_LANES = 128


class FusedTree:
    """Flat per-dtype buffers + the recipe to rebuild the original tree."""

    def __init__(self, buffers: List[jax.Array], treedef, groups, shapes):
        self.buffers = buffers          # one 1-D array per dtype group
        self._treedef = treedef
        self._groups = groups           # per group: list of leaf indices
        self._shapes = shapes           # per leaf: original shape

    def unfuse(self) -> Any:
        leaves: List[Any] = [None] * len(self._shapes)
        for buf, idxs in zip(self.buffers, self._groups):
            off = 0
            for i in idxs:
                shape = self._shapes[i]
                n = int(np.prod(shape)) if shape else 1
                # offsets are Python ints known at trace time: a static
                # lax.slice folds into the surrounding program, where a
                # dynamic-slice would survive into the step HLO as a real op
                leaves[i] = jax.lax.slice_in_dim(
                    buf, off, off + n, axis=0).reshape(shape)
                off += n
        return jax.tree.unflatten(self._treedef, leaves)


def _dtype_groups(leaves) -> List[List[int]]:
    """Leaf indices by dtype, dtypes in a stable order."""
    by_dtype = {}
    for i, leaf in enumerate(leaves):
        by_dtype.setdefault(jnp.asarray(leaf).dtype, []).append(i)
    return [idxs for _, idxs in sorted(by_dtype.items(),
                                       key=lambda kv: str(kv[0]))]


def fuse_tree(tree: Any) -> FusedTree:
    """Flatten a pytree into one 1-D buffer per dtype (stable leaf order).

    On a TPU the ``ravel`` in and the ``reshape`` out are each a copy of
    the leaf out of (into) its ``(rows, 128)`` tiles, which XLA does not
    fold away.  For callers that pad, shard or split the buffer by element
    count (the window strategies, the sharded-update paths,
    :func:`fused_leaf_op`); neighbour averaging uses :func:`tile_tree`.
    """
    leaves, treedef = jax.tree.flatten(tree)
    groups = _dtype_groups(leaves)
    buffers = [
        jnp.concatenate([jnp.ravel(leaves[i]) for i in idxs])
        for idxs in groups
    ]
    shapes = [jnp.shape(leaf) for leaf in leaves]
    return FusedTree(buffers, treedef, groups, shapes)


class TiledTree:
    """Per-dtype buffers in the leaves' own tile order + the recipe back.

    ``buffers[g]`` is ``[n_tiles, rows, 128]`` and ``spans[g]`` the
    ``[start, stop)`` tiles of each of its leaves, in leaf order: a leaf
    made of whole tiles lies there as the bytes it already has.
    """

    def __init__(self, buffers, spans, treedef, groups, shapes):
        self.buffers = buffers          # one [n_tiles, rows, 128] per dtype
        self.spans = spans              # per group: (start, stop) per leaf
        self._treedef = treedef
        self._groups = groups           # per group: list of leaf indices
        self._shapes = shapes           # per leaf: original shape

    def untile(self, tiles: List[List[jax.Array]]) -> Any:
        """The tree from, per buffer, its leaves' ``[stop - start, rows,
        128]`` tiles in span order (``buffers[g][start:stop]``, or what a
        tile-wise op made of them)."""
        leaves: List[Any] = [None] * len(self._shapes)
        for idxs, group in zip(self._groups, tiles):
            for i, t in zip(idxs, group):
                leaves[i] = _untile(t, self._shapes[i])
        return jax.tree.unflatten(self._treedef, leaves)


def _tile_rows(dtype) -> int:
    """Sublanes of one TPU tile: 8 at four bytes, 16 at two, 32 at one."""
    return max(1, 32 // jnp.dtype(dtype).itemsize)


def _whole_tiles(shape, rows: int) -> bool:
    lead = int(np.prod(shape[:-1])) if len(shape) > 1 else 0
    return (lead > 0 and lead % rows == 0
            and shape[-1] > 0 and shape[-1] % _LANES == 0)


def _tile(x: jax.Array) -> jax.Array:
    """``x`` as ``[n_tiles, rows, 128]`` in the order a TPU lays it out."""
    rows = _tile_rows(x.dtype)
    if _whole_tiles(x.shape, rows):
        cols = x.shape[-1]
        return (x.reshape(-1, rows, cols // _LANES, _LANES)
                .transpose(0, 2, 1, 3).reshape(-1, rows, _LANES))
    flat = jnp.ravel(x)
    pad = -flat.size % (rows * _LANES)
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(flat.size // (rows * _LANES), rows, _LANES)


def _untile(t: jax.Array, shape) -> jax.Array:
    rows = t.shape[1]
    if _whole_tiles(shape, rows):
        cols = shape[-1]
        return (t.reshape(-1, cols // _LANES, rows, _LANES)
                .transpose(0, 2, 1, 3).reshape(shape))
    n = int(np.prod(shape)) if shape else 1
    return lax.slice_in_dim(t.reshape(-1), 0, n, axis=0).reshape(shape)


def tile_tree(tree: Any) -> TiledTree:
    """Bucket a pytree into one tile-ordered buffer per dtype.

    ``[n_tiles, rows, 128]`` with ``rows`` the dtype's sublanes (8 at four
    bytes, 16 at two, 32 at one).  A leaf whose last dimension is a
    multiple of 128 and whose leading dimensions multiply to a multiple of
    ``rows`` enters as the tiles a TPU already keeps it in (a bitcast
    there, and back); any other leaf is ravelled and zero-padded to whole
    tiles, which costs what :func:`fuse_tree` costs.  Decided per leaf
    from its shape and dtype.  Used by ``neighbor_communicator(fuse=True)``.
    """
    leaves, treedef = jax.tree.flatten(tree)
    leaves = [jnp.asarray(leaf) for leaf in leaves]
    groups = _dtype_groups(leaves)
    buffers, spans = [], []
    for idxs in groups:
        tiles = [_tile(leaves[i]) for i in idxs]
        stops = np.cumsum([t.shape[0] for t in tiles]).tolist()
        spans.append(list(zip([0] + stops[:-1], stops)))
        buffers.append(jnp.concatenate(tiles))
    shapes = [leaf.shape for leaf in leaves]
    return TiledTree(buffers, spans, treedef, groups, shapes)


def fused_leaf_op(op: Callable[[jax.Array], jax.Array]) -> Callable[[Any], Any]:
    """Lift a per-array collective to a whole-pytree op via fusion.

    ``op`` must be shape-preserving (neighbor_allreduce, pmean, ...).  The
    returned function fuses the tree, applies ``op`` once per dtype buffer,
    and unfuses — turning L per-leaf collectives into one per dtype.
    """
    def tree_op(tree: Any) -> Any:
        fused = fuse_tree(tree)
        fused.buffers = [op(buf) for buf in fused.buffers]
        return fused.unfuse()
    return tree_op
