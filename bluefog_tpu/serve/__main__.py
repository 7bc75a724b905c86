"""``python -m bluefog_tpu.serve`` — the demo loop ``bfrun-tpu --serve``
launches when no command is given.

Carves every visible device into replicas (pp from ``BLUEFOG_SERVE_PP``,
tp from ``BLUEFOG_SERVE_TP``, remaining devices become replicas), brings
up an engine + scheduler with fresh random weights, answers a burst of
copy-task prompts, and prints a one-line JSON summary.  It exists so the
launcher path is exercisable end to end on any machine — production
entrypoints build the same objects around a real checkpoint
(:func:`bluefog_tpu.checkpoint.load_for_serving`) and a traffic source.
"""
from __future__ import annotations

import json
import os
import sys


def main() -> int:
    import numpy as np

    from ..parallel import context as _ctx
    from ..parallel.compose import LMConfig, compose_parallelism, \
        init_lm_params
    from ..utils import metrics as _metrics
    from .engine import ServeConfig, ServeEngine
    from .scheduler import Scheduler

    pp = int(os.environ.get("BLUEFOG_SERVE_PP", "1"))
    tp = int(os.environ.get("BLUEFOG_SERVE_TP", "1"))
    scfg = ServeConfig.from_env()
    ep = scfg.moe_ep if scfg.moe_experts else 1
    # every entry point starts here: logs the platform, and on a TPU places
    # the libtpu flags and the persistent compile cache
    devices = list(_ctx.init().devices)
    slice_sz = pp * tp * ep
    if len(devices) % slice_sz:
        print(f"bluefog-serve: {len(devices)} devices do not carve into "
              f"pp={pp} x tp={tp} x ep={ep} slices", file=sys.stderr)
        return 2
    dp = len(devices) // slice_sz
    layers = 4 if 4 % pp == 0 else 2 * pp
    if scfg.moe_experts:
        from ..moe.model import MoELMConfig, init_moe_params
        m = compose_parallelism(dp, pp, tp, 1, ep, devices=devices,
                                num_experts=scfg.moe_experts)
        cfg = MoELMConfig(layers=layers, batch=ep,
                          num_experts=scfg.moe_experts,
                          top_k=scfg.moe_top_k, dispatch="dropless")
        params = init_moe_params(cfg, m, seed=0)
    else:
        m = compose_parallelism(dp, pp, tp, 1, devices=devices)
        cfg = LMConfig(layers=layers)
        params = init_lm_params(cfg, m, seed=0)
    engine = ServeEngine(m, cfg, params, scfg)
    engine.warmup()
    sched = Scheduler(engine)
    rng = np.random.default_rng(0)
    n_req = int(os.environ.get("BLUEFOG_SERVE_DEMO_REQUESTS", "8"))
    for _ in range(n_req):
        n = int(rng.integers(2, engine.scfg.prefill_buckets[-1] + 1))
        sched.submit(rng.integers(0, cfg.vocab, n).tolist(),
                     max_new_tokens=4)
    sched.drain()
    print(json.dumps({
        "schema": "bluefog-serve-demo-1",
        "replicas": dp, "pp": pp, "tp": tp, "ep": ep,
        "moe_experts": scfg.moe_experts,
        "completed": len(sched.completed),
        "tokens": int(_metrics.counter(
            "bluefog_tokens_generated_total").total()),
        "retraces": int(_metrics.counter(
            "bluefog_retrace_after_warmup_total").total()),
    }))
    sched.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
