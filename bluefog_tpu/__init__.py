"""bluefog_tpu: decentralized deep-learning training, TPU-native.

A ground-up JAX/XLA re-design of the capabilities of Bluefog
(https://github.com/Bluefog-Lib/bluefog): virtual-topology gossip averaging
(static, dynamic, and hierarchical) compiled to ``ppermute``/``psum``
collectives over an ICI/DCN device mesh instead of MPI/NCCL background
threads.

Typical use::

    import bluefog_tpu as bf
    bf.init(topology_fn=lambda: bf.topology.ExponentialTwoGraph(8))
    x_avg = bf.neighbor_allreduce(x)          # x: [n_ranks, ...]
"""
from . import topology
from . import topology as topology_util       # reference-familiar alias
from . import schedule
from . import ops
from . import optimizers
from . import fusion
from . import checkpoint
from . import data
from . import utils
from .utils import (
    timeline_start_activity, timeline_end_activity, timeline_context,
    start_timeline, stop_timeline,
    start_metrics, stop_metrics, metrics_summary,
    render_prometheus, start_http_server, stop_http_server,
    broadcast_parameters, allreduce_parameters, broadcast_optimizer_state,
)
from .parallel import (
    init, shutdown, is_initialized,
    size, local_size, machine_size,
    mesh, mesh_2d, devices,
    load_topology, is_topology_weighted, set_topology,
    load_machine_topology, is_machine_topology_weighted, set_machine_topology,
    in_neighbor_ranks, out_neighbor_ranks,
    in_neighbor_machine_ranks, out_neighbor_machine_ranks,
    static_schedule, machine_schedule, get_context,
    machine_rank, local_rank, suspend, resume,
    set_dynamic_topology, clear_dynamic_topology, dynamic_schedules,
    set_round_parallel, round_parallel, set_dcn_wire, dcn_wire,
    set_async_gossip, async_gossip_bound,
    apply_plan,
    win_create, win_free, win_put, win_accumulate, win_get,
    win_update, win_update_then_collect, win_mutex, get_win_version,
    get_win_stamps, win_staleness,
    win_associated_p,
    turn_on_win_ops_with_associated_p, turn_off_win_ops_with_associated_p,
)
from .api import (
    allreduce, allgather, ragged_allgather, broadcast,
    neighbor_allreduce, neighbor_allgather, ragged_neighbor_allgather,
    pair_gossip, hierarchical_neighbor_allreduce,
    barrier, synchronize, poll, hard_sync, resolve_schedule, shard_distributed,
)
from . import diagnostics
from .diagnostics import (
    diagnose_consensus, consensus_distance, check_finite, detect_stragglers,
)
from . import resilience
from .resilience import (
    mark_rank_dead, dead_ranks, guard_step,
    admit_rank, retire_rank, join_rank, advance_membership,
    bootstrap_params, retired_ranks, live_ranks,
)
from . import autotune as autotune_lib
from .autotune import autotune, Plan, load_plan
from .utils import chaos
from .utils import flight

__version__ = "0.1.0"
