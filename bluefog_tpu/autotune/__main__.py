"""CLI for the autotuner: tune, emit the plan, optionally apply + train.

``python -m bluefog_tpu.autotune --virtual-cpu --smoke --apply-steps 5``
runs the end-to-end proof the smoke target uses: tune on a restricted
space, print the plan as one JSON line, then apply
it, build the strategy + train step it prescribes, run N steps, and
report donation/retrace health alongside the plan id.
"""
import argparse
import json
import os
import sys


def main(argv=None):
    parser = argparse.ArgumentParser(prog="python -m bluefog_tpu.autotune")
    parser.add_argument("--virtual-cpu", action="store_true",
                        help="force an 8-device virtual CPU mesh")
    parser.add_argument("--objective", default="step_time",
                        help="step_time | consensus_per_byte | JSON blend "
                             'dict like {"step_time": 1, '
                             '"consensus_per_byte": 0.5}')
    parser.add_argument("--trials", default="0",
                        help='0, an int K, or "auto" '
                             "(BLUEFOG_AUTOTUNE_TRIALS)")
    parser.add_argument("--smoke", action="store_true",
                        help="restrict the space to a fast representative "
                             "subset (CI / battery rehearsal)")
    parser.add_argument("--out", default=None,
                        help="write the plan JSON to this path")
    parser.add_argument("--apply-steps", type=int, default=0,
                        help="after tuning: apply the plan, train N steps "
                             "on a tiny model, verify donation + retraces")
    args = parser.parse_args(argv)

    if args.virtual_cpu:
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()

    import jax
    if args.virtual_cpu:
        jax.config.update("jax_platforms", "cpu")

    import bluefog_tpu as bf
    from bluefog_tpu.autotune import autotune

    bf.init(platform="cpu" if args.virtual_cpu else None)

    objective = args.objective
    if objective.lstrip().startswith("{"):
        objective = json.loads(objective)
    trials = args.trials if args.trials == "auto" else int(args.trials)

    space = {}
    if args.smoke:
        n = bf.size()
        space = {
            "algorithms": ("allreduce", "neighbor_cta", "neighbor_atc",
                           "push_diging"),
            "topologies": ({"family": "exp2", "size": n},
                           {"family": "ring", "size": n}),
            "wires": (None,),
            "fused_k": (1, 4),
        }

    plan = autotune(objective=objective, trials=trials, **space)
    print(plan.to_json())
    if args.out:
        plan.save(args.out)

    if args.apply_steps <= 0:
        return 0

    # apply + train: the plan must reconstruct a working configuration
    import jax.numpy as jnp
    import optax

    from bluefog_tpu import optimizers as bfopt
    from bluefog_tpu.utils import metrics as bfm

    plan.apply()
    n = bf.size()
    params = {"w": jnp.ones((64, 16), jnp.float32),
              "b": jnp.zeros((16,), jnp.float32)}

    def grad_fn(p, batch):
        x, y = batch
        pred = x @ p["w"] + p["b"]
        loss = jnp.mean((pred - y) ** 2)
        return loss, jax.grad(
            lambda q: jnp.mean((x @ q["w"] + q["b"] - y) ** 2))(p)

    strategy = plan.build_strategy(optax.sgd(0.01))
    step = bfopt.make_train_step(grad_fn, strategy,
                                 donate=True, **plan.train_step_kwargs())
    dist_params = bfopt.replicate(params, n)
    dist_state = bfopt.init_distributed(strategy, dist_params)
    batch = (jnp.ones((n, 8, 64), jnp.float32),
             jnp.zeros((n, 8, 16), jnp.float32))
    loss = None
    for _ in range(args.apply_steps):
        dist_params, dist_state, loss = step(dist_params, dist_state, batch)
    bf.hard_sync(loss)
    retraces = int(bfm.counter("bluefog_retrace_after_warmup_total").total())
    report = {
        "applied": True,
        "plan_id": plan.plan_id,
        "algorithm": plan.algorithm,
        "steps": args.apply_steps,
        "loss_finite": bool(jnp.isfinite(loss).all()),
        "donated": True,
        "retraces_after_warmup": retraces,
        "ok": retraces == 0,
    }
    print(json.dumps(report, sort_keys=True))
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
