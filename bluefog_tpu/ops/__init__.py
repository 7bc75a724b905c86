"""SPMD collective primitives (used inside ``shard_map`` / ``pjit``).

These are the TPU-native equivalents of the reference's controller op set
(``mpi_controller.cc`` / ``nccl_controller.cc``): pure functions over a mesh
axis, compiled by XLA into ICI collectives.  The outer blocking API in
:mod:`bluefog_tpu.api` wraps them in ``shard_map`` over the global mesh.
"""
from .collectives import (
    my_rank,
    neighbor_allreduce,
    neighbor_exchange,
    neighbor_combine,
    neighbor_allgather,
    ragged_neighbor_allgather,
    allreduce,
    allgather,
    broadcast,
    pair_gossip,
    hierarchical_neighbor_allreduce,
)
from .ring import (ring_pass, ring_allreduce, ring_attention,
                   zigzag_order, zigzag_inverse, zigzag_positions)
from .ulysses import ulysses_attention, local_flash_attention

__all__ = [
    "my_rank",
    "neighbor_allreduce",
    "neighbor_exchange",
    "neighbor_combine",
    "neighbor_allgather",
    "ragged_neighbor_allgather",
    "allreduce",
    "allgather",
    "broadcast",
    "pair_gossip",
    "hierarchical_neighbor_allreduce",
    "ring_pass",
    "ring_allreduce",
    "ring_attention",
    "zigzag_order",
    "zigzag_inverse",
    "zigzag_positions",
    "ulysses_attention",
    "local_flash_attention",
]
