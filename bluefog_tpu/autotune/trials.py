"""Tier-3 evidence: live micro-trials of the top-K candidates.

A trial dispatches the candidate's already-compiled probe program (the
same executable tier-1 counted bytes from — the context program cache
makes this free) a few times and takes the median wall-clock per step.
Each trial's artifact is banked to ``docs/measured/`` the moment it
finishes (incremental banking: a mid-search death loses nothing), marked
``on_accelerator`` only when it ran on real chips so a CPU trial can
never steer a future hardware tune.
"""
from __future__ import annotations

import hashlib
import time
from typing import Dict, List, Optional

from ..parallel import context as _mesh
from .bank import bank_trial
from .candidates import Candidate, schedule_for
from .cost_model import probe_compiled, _params_struct_key


def trial_id(cand: Candidate, device_kind: str, n: int) -> str:
    h = hashlib.sha256(
        f"{cand.key}|{device_kind}|{n}".encode()).hexdigest()
    return h[:12]


def run_trials(
    cands: List[Candidate],
    params,
    n: int,
    opt_factory,
    *,
    iters: int = 5,
    mdir: Optional[str] = None,
    bank: bool = True,
) -> Dict[str, float]:
    """Measure ``seconds_per_step`` for each candidate; returns key->s.

    The timed program is the strategy *update* (gossip + optimizer math,
    zero grads) — the communication cost under comparison, without a user
    model's compute drowning the signal on small probes.  A trial that
    fails to execute is skipped (its candidate keeps its tier-1 score).
    """
    import jax

    from ..optimizers import STRATEGIES, init_distributed, replicate

    ctx = _mesh.get_context()
    device_kind = ctx.devices[0].device_kind
    on_accel = ctx.devices[0].platform != "cpu"
    out: Dict[str, float] = {}
    for cand in cands:
        try:
            sched = schedule_for(cand.topology, cand.weights, n)
            strategy = STRATEGIES[cand.algorithm].build(
                opt_factory(), schedule=sched, wire=cand.wire,
                concurrent=None, delayed=False,
                num_steps_per_communication=1)
            compiled = _mesh.cached_program(
                ("autotune-probe", cand.compile_group, n,
                 _params_struct_key(params)),
                lambda: probe_compiled(strategy, params, n))
            dist_params = replicate(params, n)
            dist_state = init_distributed(strategy, dist_params)
            p, s = compiled(dist_params, dist_state)     # warmup
            jax.block_until_ready(p)
            samples = []
            for _ in range(max(iters, 1)):
                t0 = time.perf_counter()
                p, s = compiled(p, s)
                jax.block_until_ready(p)
                samples.append(time.perf_counter() - t0)
            samples.sort()
            sec = samples[len(samples) // 2]
        except Exception:                                # noqa: BLE001
            continue
        out[cand.key] = sec
        if bank:
            bank_trial({
                "schema": "bluefog-autotune-trial-1",
                "trial_id": trial_id(cand, device_kind, n),
                "key": cand.key,
                "algorithm": cand.algorithm,
                "config": cand.config(),
                "seconds_per_step": round(sec, 9),
                "iters": iters,
                "device": device_kind,
                "n_chips": n,
                "ok": True,
                "on_accelerator": on_accel,
            }, mdir)
    return out
