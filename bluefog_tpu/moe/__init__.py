"""Routed mixture-of-experts as a first-class composed workload.

The package folds GShard/Switch-Transformer-style routed MoE into the
5-axis composed world (``parallel.compose``): a top-k router with static
capacity, expert-parallel dispatch over the ``"expert"`` mesh axis
(``parallel.expert``), the auxiliary load-balance and router-z losses
folded into training, and a reference routed-MoE LM on the PR 9 composed
LM skeleton — pipelined over ``stage``, Megatron-TP inside every expert,
Ulysses over ``sp``, gossip-DP over ``rank``, experts over ``expert``.

Gossip remains the ONLY DCN-crossing axis: every expert all_to_all is
intra-slice by construction (slice-major device sort keeps gossip-DP
outermost), which tests/test_moe.py proves from the pre-optimization
StableHLO.

Two dispatch modes share the wiring: the classic static-``capacity``
padded path (Switch), and the **dropless** fast path
(``MoELMConfig.dispatch="dropless"``) — sort-based grouped dispatch with
a grouped GEMM over ragged expert groups (:mod:`.dropless`,
:mod:`..ops.pallas_moe`) and optional **expert-choice** routing
(``router_mode="expert_choice"``): statically perfect load balance, zero
dropped tokens, zero capacity-padding FLOPs.
"""
from .dropless import (dropless_rows, grouped_ffn, grouped_ffn_xla,
                       sort_by_expert, tile_layout)
from .layers import (moe_ffn_dense, moe_ffn_dense_ec, moe_ffn_dropless,
                     moe_ffn_expert_choice, moe_ffn_routed,
                     router_expert_choice, router_topk)
from .model import (MoELMConfig, init_moe_params, make_moe_batch,
                    make_moe_grad_fn, make_moe_probe)

__all__ = [
    "router_topk", "router_expert_choice",
    "moe_ffn_routed", "moe_ffn_dropless", "moe_ffn_expert_choice",
    "moe_ffn_dense", "moe_ffn_dense_ec",
    "dropless_rows", "tile_layout", "sort_by_expert",
    "grouped_ffn", "grouped_ffn_xla",
    "MoELMConfig", "init_moe_params", "make_moe_batch",
    "make_moe_grad_fn", "make_moe_probe",
]
