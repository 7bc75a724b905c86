"""Migrate a PyTorch (reference-style) training job to decentralized TPU.

The walk a Bluefog/torch user takes to get here, end to end:

  1. an EXISTING torch workflow — the reference's MNIST CNN
     (``examples/pytorch_mnist.py``) trains a few steps in plain torch;
  2. ``torch_compat.from_torch`` + the layout helpers move the weights into
     a flax param tree (NCHW->NHWC kernel axes and the flatten-order fix are
     the only real work — both are one call here);
  3. logits parity is asserted (torch and JAX agree on the same batch);
  4. training CONTINUES decentralized: 8 ranks, neighbor-allreduce gossip,
     each rank on its own data shard;
  5. the consensus model converts back with ``to_torch`` and the torch
     model keeps serving it — parity asserted again.

Run: python examples/torch_migration.py --virtual-cpu
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from mnist import synthetic_mnist  # noqa: E402  (same synthetic dataset)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--virtual-cpu", action="store_true")
    parser.add_argument("--torch-steps", type=int, default=40)
    parser.add_argument("--epochs", type=int, default=2,
                        help="decentralized epochs after migration")
    parser.add_argument("--batch-size", type=int, default=32)
    parser.add_argument("--lr", type=float, default=0.05)
    parser.add_argument("--seed", type=int, default=42)
    args = parser.parse_args()

    if args.virtual_cpu:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()

    import numpy as np
    import torch
    import torch.nn.functional as F

    # ------------------------------------------------------------------
    # 1. the existing torch workflow (reference examples/pytorch_mnist.py net)
    # ------------------------------------------------------------------
    class TorchCNN(torch.nn.Module):
        def __init__(self):
            super().__init__()
            self.conv1 = torch.nn.Conv2d(1, 32, 3)
            self.conv2 = torch.nn.Conv2d(32, 64, 3)
            self.fc1 = torch.nn.Linear(64 * 12 * 12, 128)
            self.fc2 = torch.nn.Linear(128, 10)

        def forward(self, x):                       # x: [B, 1, 28, 28]
            x = F.relu(self.conv1(x))
            x = F.relu(self.conv2(x))
            x = F.max_pool2d(x, 2)
            x = torch.flatten(x, 1)                 # (C, H, W) order
            x = F.relu(self.fc1(x))
            return self.fc2(x)

    rng = np.random.default_rng(args.seed)
    x_all, y_all = synthetic_mnist(rng)
    torch.manual_seed(args.seed)
    tmodel = TorchCNN()
    topt = torch.optim.SGD(tmodel.parameters(), lr=args.lr, momentum=0.9)
    xt = torch.from_numpy(np.transpose(x_all, (0, 3, 1, 2)))   # NHWC -> NCHW
    yt = torch.from_numpy(y_all.astype(np.int64))
    for i in range(args.torch_steps):
        lo, hi = i * args.batch_size, (i + 1) * args.batch_size
        topt.zero_grad()
        loss = F.cross_entropy(tmodel(xt[lo:hi]), yt[lo:hi])
        loss.backward()
        topt.step()
    print(f"torch phase: {args.torch_steps} steps, final loss {loss:.4f}")

    # ------------------------------------------------------------------
    # 2. migrate the weights (this is the whole conversion)
    # ------------------------------------------------------------------
    import jax

    if args.virtual_cpu:
        # before the first jnp.asarray below picks a backend
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import optax
    import bluefog_tpu as bf
    from bluefog_tpu import models
    from bluefog_tpu import optimizers as bfopt
    from bluefog_tpu import topology as topology_util
    from bluefog_tpu.utils import torch_compat as tc

    sd = tc.from_torch(tmodel.state_dict())
    params = {"params": {
        "Conv_0": {"kernel": tc.conv_kernel(sd["conv1"]["weight"]),
                   "bias": sd["conv1"]["bias"]},
        "Conv_1": {"kernel": tc.conv_kernel(sd["conv2"]["weight"]),
                   "bias": sd["conv2"]["bias"]},
        # fc after flatten: NCHW flattens (C,H,W), NHWC flattens (H,W,C) —
        # flatten_kernel reorders the input axis accordingly
        "Dense_0": {"kernel": tc.flatten_kernel(sd["fc1"]["weight"],
                                                chw=(64, 12, 12)),
                    "bias": sd["fc1"]["bias"]},
        "Dense_1": {"kernel": tc.linear_kernel(sd["fc2"]["weight"]),
                    "bias": sd["fc2"]["bias"]},
    }}

    # ------------------------------------------------------------------
    # 3. parity gate: both frameworks produce the same logits
    # ------------------------------------------------------------------
    model = models.MnistCNN()
    probe = x_all[:64]
    with torch.no_grad():
        t_logits = tmodel(torch.from_numpy(
            np.transpose(probe, (0, 3, 1, 2)))).numpy()
    j_logits = np.asarray(model.apply(params, jnp.asarray(probe), train=False))
    np.testing.assert_allclose(j_logits, t_logits, atol=2e-4)
    print("parity gate: torch and JAX logits match (atol 2e-4)")

    # ------------------------------------------------------------------
    # 4. continue training decentralized (the reference's MNIST flow)
    # ------------------------------------------------------------------
    bf.init(platform="cpu" if args.virtual_cpu else None)
    n = bf.size()
    bf.set_topology(topology_util.ExponentialTwoGraph(n), is_weighted=True)

    def grad_fn(p, batch):
        xb, yb = batch

        def loss_fn(q):
            logits = model.apply(q, xb, train=False)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, yb).mean()

        return jax.value_and_grad(loss_fn)(p)

    strategy = bfopt.DistributedAdaptWithCombineOptimizer(
        optax.sgd(args.lr, momentum=0.9))
    from bluefog_tpu.data import ShardedLoader
    loader = ShardedLoader([x_all, y_all], args.batch_size, shuffle=True,
                           seed=args.seed)
    dist_params = bfopt.replicate(params)
    dist_state = bfopt.init_distributed(strategy, dist_params)
    step = bfopt.make_train_step(grad_fn, strategy,
                                 steps_per_call=loader.steps_per_epoch())
    for epoch in range(args.epochs):
        xb, yb = loader.epoch_arrays()
        dist_params, dist_state, losses = step(dist_params, dist_state, (xb, yb))
        losses = np.asarray(jax.block_until_ready(losses))
        print(f"decentralized epoch {epoch}: mean loss {losses.mean():.4f}")

    # ------------------------------------------------------------------
    # 5. hand the consensus model back to torch
    # ------------------------------------------------------------------
    p0 = jax.tree.map(lambda x: x[0], dist_params)["params"]
    tmodel.load_state_dict({
        "conv1.weight": torch.from_numpy(np.array(
            tc.conv_kernel_to_torch(p0["Conv_0"]["kernel"]))),
        "conv1.bias": torch.from_numpy(np.array(p0["Conv_0"]["bias"])),
        "conv2.weight": torch.from_numpy(np.array(
            tc.conv_kernel_to_torch(p0["Conv_1"]["kernel"]))),
        "conv2.bias": torch.from_numpy(np.array(p0["Conv_1"]["bias"])),
        "fc1.weight": torch.from_numpy(np.array(
            tc.flatten_kernel_to_torch(p0["Dense_0"]["kernel"],
                                       chw=(64, 12, 12)))),
        "fc1.bias": torch.from_numpy(np.array(p0["Dense_0"]["bias"])),
        "fc2.weight": torch.from_numpy(np.array(
            tc.linear_kernel_to_torch(p0["Dense_1"]["kernel"]))),
        "fc2.bias": torch.from_numpy(np.array(p0["Dense_1"]["bias"])),
    })
    x_test, y_test = synthetic_mnist(np.random.default_rng(args.seed + 1), 512)
    with torch.no_grad():
        t_logits = tmodel(torch.from_numpy(
            np.transpose(x_test, (0, 3, 1, 2)))).numpy()
    j_logits = np.asarray(model.apply(
        {"params": p0}, jnp.asarray(x_test), train=False))
    np.testing.assert_allclose(j_logits, t_logits, atol=2e-4)
    acc = float((np.argmax(t_logits, -1) == y_test).mean())
    print(f"round-trip parity ok; torch serves the consensus model: "
          f"test accuracy {acc:.3f}")
    assert acc > 0.5, "decentralized phase should have kept learning"


if __name__ == "__main__":
    main()
