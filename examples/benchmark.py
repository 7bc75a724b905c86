"""Synthetic throughput benchmark over every distributed-optimizer flavor.

Equivalent of the reference's ``examples/pytorch_benchmark.py``: synthetic
image batches through a chosen model with the chosen decentralized strategy,
reporting img/sec; ``--dist-optimizer`` selects the strategy
(reference :108-132), ``--dynamic-topology`` cycles the inner/outer Exp2
schedules per step (reference :162-208), and ``--dist-optimizer allreduce``
plays the role of the reference's horovod comparison mode (:69-70) — global
ring allreduce vs neighbor gossip on the same hardware.

Run (8 virtual CPU devices, tiny model):
    python examples/benchmark.py --virtual-cpu --model mlp --num-iters 5
Run (TPU): python examples/benchmark.py --model resnet50 --batch-size 64
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--virtual-cpu", action="store_true")
    parser.add_argument("--model", default="resnet50",
                        choices=["resnet50", "resnet18", "vgg16", "vgg11", "cnn", "mlp"])
    parser.add_argument("--dist-optimizer", default="neighbor_allreduce",
                        choices=["neighbor_allreduce", "gradient_allreduce",
                                 "zero_allreduce", "choco",
                                 "allreduce", "hierarchical_neighbor_allreduce",
                                 "win_put", "pull_get", "push_sum",
                                 "powersgd", "empty"])
    parser.add_argument("--atc", action="store_true")
    parser.add_argument("--wire", default=None,
                        help="compress gossip bytes on the wire (neighbor/"
                             "hierarchical strategies): bf16 | int8 | fp8; "
                             "quantizers accept an @B block suffix "
                             "(e.g. int8@256)")
    parser.add_argument("--dynamic-topology", action="store_true")
    parser.add_argument("--batch-size", type=int, default=32)
    parser.add_argument("--num-warmup", type=int, default=1)
    parser.add_argument("--num-iters", type=int, default=10)
    parser.add_argument("--steps-per-call", type=int, default=1,
                        help="scan K optimizer steps into one compiled "
                             "program (amortizes dispatch)")
    parser.add_argument("--profile", default=None,
                        help="write a timeline to this path prefix")
    args = parser.parse_args()

    if args.virtual_cpu:
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()

    import jax
    if args.virtual_cpu:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np
    import optax
    import bluefog_tpu as bf
    from bluefog_tpu import models, schedule as sch
    from bluefog_tpu import optimizers as bfopt
    from bluefog_tpu import topology as topology_util
    from bluefog_tpu.utils import timeline

    hier = args.dist_optimizer.startswith("hier")
    bf.init(platform="cpu" if args.virtual_cpu else None,
            nodes_per_machine=4 if hier else None)
    n = bf.size()
    dev = bf.devices()[0]
    if dev.platform == "cpu" and not args.virtual_cpu:
        sys.exit("benchmark: no accelerator — JAX picked the CPU. A rate "
                 "from here is not a device number; pass --virtual-cpu for "
                 "the 8-device CPU emulation.")
    print(f"devices: {n} x {dev.device_kind} (platform {dev.platform})")
    topo = topology_util.ExponentialTwoGraph(n)
    bf.set_topology(topo, is_weighted=True)
    if hier:
        bf.set_machine_topology(
            topology_util.RingGraph(bf.machine_size()), is_weighted=True)

    if args.model == "resnet50":
        model, img = models.ResNet50(num_classes=1000), (224, 224, 3)
    elif args.model == "resnet18":
        model, img = models.ResNet18(num_classes=1000), (224, 224, 3)
    elif args.model.startswith("vgg"):
        Model = models.VGG16 if args.model == "vgg16" else models.VGG11
        model, img = Model(num_classes=1000), (224, 224, 3)
    elif args.model == "cnn":
        model, img = models.MnistCNN(), (28, 28, 1)
    else:
        model, img = models.MLP(features=(256, 128, 10)), (64,)

    B = args.batch_size
    xb = jnp.ones((n, B) + img, jnp.float32)
    yb = jnp.zeros((n, B), jnp.int32)
    has_bn = args.model.startswith("resnet")
    has_train_flag = has_bn or args.model in ("cnn",) or args.model.startswith("vgg")
    variables = (model.init(jax.random.key(0), xb[0], train=False)
                 if has_train_flag else model.init(jax.random.key(0), xb[0]))

    if has_bn:
        def grad_fn(train_state, batch):
            images, labels = batch

            def loss_fn(p):
                logits, upd = model.apply(
                    {"params": p, "batch_stats": train_state["bs"]}, images,
                    train=True, mutable=["batch_stats"])
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits, labels).mean(), upd

            (loss, _), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(train_state["params"])
            return loss, {"params": grads,
                          "bs": jax.tree.map(jnp.zeros_like, train_state["bs"])}
        state0 = {"params": variables["params"], "bs": variables["batch_stats"]}
    else:
        def grad_fn(params, batch):
            images, labels = batch

            def loss_fn(p):
                logits = (model.apply(p, images, train=False)
                          if has_train_flag else model.apply(p, images))
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits, labels).mean()

            return jax.value_and_grad(loss_fn)(params)
        state0 = variables

    opt = optax.sgd(0.01, momentum=0.9)
    scheds = None
    if args.dynamic_topology:
        if hier:
            # machine-level one-peer Exp2 schedules ride the machine axis
            # (reference: GetExp2DynamicSendRecvMachineRanks, :360-396)
            L = bf.local_size()
            gen = lambda m: topology_util.GetExp2DynamicSendRecvMachineRanks(
                n, L, m * L, 0)
            scheds = sch.compile_dynamic_schedules(gen, bf.machine_size())
        elif bf.local_size() > 2 and n > bf.local_size():
            # flat rank-level inner/outer Exp2 (reference :466-554, used with
            # plain neighbor_allreduce in pytorch_benchmark.py:162-208)
            gen = lambda r: topology_util.GetInnerOuterExpo2DynamicSendRecvRanks(
                n, bf.local_size(), r)
            scheds = sch.compile_dynamic_schedules(gen, n)
        else:
            gen = lambda r: topology_util.GetDynamicOnePeerSendRecvRanks(topo, r)
            scheds = sch.compile_dynamic_schedules(gen, n)

    name = args.dist_optimizer
    if args.wire and name in ("gradient_allreduce", "zero_allreduce",
                              "push_sum", "allreduce", "powersgd", "empty"):
        raise SystemExit(
            f"--wire applies to the gossip strategies (neighbor/"
            f"hierarchical/win_put/pull_get/choco), not {name}")
    if name == "gradient_allreduce":
        strategy = bfopt.gradient_allreduce(opt)
    elif name == "zero_allreduce":
        # ZeRO-1: same trajectory as gradient_allreduce, 1/n optimizer state
        strategy = bfopt.zero_gradient_allreduce(opt)
    elif name == "choco":
        # error-compensated compressed gossip (defaults to int8 wire)
        strategy = bfopt.choco_gossip(opt, wire=args.wire or "int8")
    elif name == "win_put":
        strategy = bfopt.DistributedWinPutOptimizer(opt, wire=args.wire)
    elif name == "pull_get":
        strategy = bfopt.DistributedPullGetOptimizer(opt, wire=args.wire)
    elif name == "push_sum":
        strategy = bfopt.DistributedPushSumOptimizer(opt)
    elif name == "powersgd":
        # rank-r low-rank gradient compression (error feedback)
        strategy = bfopt.powersgd_allreduce(opt, compression_rank=4)
    else:
        factory = (bfopt.DistributedAdaptThenCombineOptimizer if args.atc
                   else bfopt.DistributedAdaptWithCombineOptimizer)
        strategy = factory(opt, communication_type=name,
                           **({"schedules": scheds} if scheds else {}),
                           **({"wire": args.wire} if args.wire else {}))

    dist_params = bfopt.replicate(state0)
    dist_state = bfopt.init_distributed(strategy, dist_params)
    spc = args.steps_per_call
    step = bfopt.make_train_step(grad_fn, strategy, steps_per_call=spc)

    if args.profile:
        timeline.start_timeline(args.profile)

    if spc > 1:
        # steps axis after the rank axis (make_train_step's scan contract)
        xb = jnp.broadcast_to(xb[:, None], (xb.shape[0], spc) + xb.shape[1:])
        yb = jnp.broadcast_to(yb[:, None], (yb.shape[0], spc) + yb.shape[1:])
    # one row per device from the start, like replicate(): the first step
    # call should not have to move the stacks off device 0
    batch = (bf.shard_distributed(xb), bf.shard_distributed(yb))
    for _ in range(args.num_warmup):
        dist_params, dist_state, loss = step(dist_params, dist_state, batch)
    bf.hard_sync(loss)      # host-transfer barrier: see bf.hard_sync

    t0 = time.perf_counter()
    with timeline.timeline_context("benchmark", "TRAIN"):
        for _ in range(args.num_iters):
            dist_params, dist_state, loss = step(dist_params, dist_state, batch)
        bf.hard_sync(loss)
    dt = time.perf_counter() - t0

    if args.profile:
        timeline.stop_timeline()

    total = args.num_iters * spc * B * n
    print(f"Model: {args.model}, optimizer: {name}"
          f"{'+dynamic' if args.dynamic_topology else ''}"
          f"{' (ATC)' if args.atc else ''}")
    print(f"Total img/sec on {n} device(s): {total / dt:.1f} "
          f"({total / dt / n:.1f} per device)")
    assert np.isfinite(np.asarray(loss)).all()


if __name__ == "__main__":
    main()
