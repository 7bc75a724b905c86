"""Family ``resnet``: bluefog_tpu.models.ResNet50 under SGD momentum with
neighbour averaging (adapt_with_combine over the context's static schedule,
Exp2 on several chips, a self-loop on one), through optimizers.replicate /
init_distributed / make_train_step with donation: bench.py's wiring at the
program's defaults (one optimizer step per call).  Batch-norm running
statistics stay at their initial values, as in bench.py: the step trains in
batch-statistics mode and only the optax channel is optimized.
"""
import numpy as np

from perfbench.families import _checks
from perfbench.reference import resnet as reference

# |program - reference| <= tol * max(1, |reference|): the program convolves in
# bf16 (f32 batch norm and head), the reference in f32 at `highest`.  Measured
# on the chip after a window of training: 0.0016-0.0045 in 15 runs (PERF.md,
# PR 23), so about four times the largest.
TRAIN_LOSS_TOL = 2e-2

# Forward pass of one 224x224 image through ResNet-50: 4.089e9 multiply-adds
# (the figure torchvision and bench.py give as "GFLOPs"), i.e. 8.178e9 FLOPs
# with a multiply and an add counted apart, as the published peak counts
# them.  bench.py divides the multiply-add count by that peak and so reports
# half the utilization (0.125 where this gives 25 %); listed in PERF.md.
# Training = forward + backward = 3x.  Scales with the pixel count.
FORWARD_FLOPS_224 = 2 * 4.089e9


def flops_per_item(cfg, traffic):
    """Training FLOPs per image required by forward and backward."""
    return 3.0 * FORWARD_FLOPS_224 * (cfg["image_size"] / 224.0) ** 2


def make_grad_fn(model):
    """bench.py's grad_fn: loss and gradients in batch-statistics mode, the
    running statistics' channel zeroed."""
    import jax
    import jax.numpy as jnp
    import optax

    def grad_fn(train_state, data):
        params, batch_stats = train_state["params"], train_state["bs"]
        imgs, lbls = data

        def loss_fn(p):
            logits, updates = model.apply(
                {"params": p, "batch_stats": batch_stats}, imgs,
                train=True, mutable=["batch_stats"])
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, lbls).mean()
            return loss, updates["batch_stats"]

        (loss, new_bs), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        return loss, {"params": grads,
                      "bs": jax.tree.map(jnp.zeros_like, new_bs)}
    return grad_fn


class Train:
    def __init__(self, cfg, traffic, devices, seed):
        import jax
        import jax.numpy as jnp
        import optax

        import bluefog_tpu as bf
        from bluefog_tpu import models
        from bluefog_tpu import optimizers as bfopt
        from bluefog_tpu import topology as topology_util

        if cfg["depth"] != 50:
            raise ValueError("perfbench's resnet adapter builds ResNet-50")
        n = bf.size()
        if n != len(devices):
            raise ValueError(f"context holds {n} devices, cell {len(devices)}")
        self.n_chips, self.seed = n, seed
        self.topology = None
        if n > 1:
            self.topology = topology_util.ExponentialTwoGraph(n)
            bf.set_topology(self.topology, is_weighted=True)
        B, S, C = traffic["batch"], cfg["image_size"], cfg["num_classes"]

        def make_batch(key):
            ki, kl = jax.random.split(key)
            return (jax.random.normal(ki, (n, B, S, S, 3), jnp.float32),
                    jax.random.randint(kl, (n, B), 0, C, jnp.int32))
        images, labels = jax.jit(make_batch)(jax.random.key(seed + 1))
        self.data = (bf.shard_distributed(images), bf.shard_distributed(labels))

        self.model = model = models.ResNet50(num_classes=C)
        variables = jax.jit(lambda k: model.init(
            k, jnp.ones((1, S, S, 3), jnp.float32), train=False))(
                jax.random.key(seed))

        opt = optax.sgd(traffic["learning_rate"], momentum=0.9)
        self.comm = bfopt.neighbor_communicator(bf.static_schedule())
        strategy = bfopt.adapt_with_combine(opt, self.comm)
        train_state = {"params": variables["params"],
                       "bs": variables["batch_stats"]}
        self.params = bfopt.replicate(train_state, n)
        self.state = bfopt.init_distributed(strategy, self.params)
        self.step = bfopt.make_train_step(make_grad_fn(model), strategy,
                                          donate=True)
        self.steps_per_call = 1
        self.items_per_call = B                               # per chip
        self.flops_per_item = flops_per_item(cfg, traffic)

    def call(self):
        self.params, self.state, loss = self.step(self.params, self.state,
                                                  self.data)
        return loss

    def reference_check(self):
        """The program's loss on replica 0's batch against the plain f32
        reference on the same parameters (read before one more call donates
        them)."""
        import jax
        p0 = _checks.row0(self.params["params"])
        imgs, lbls = _checks.row0(self.data)
        want = float(np.asarray(jax.jit(
            lambda p, x, y: reference.loss(
                jax.tree.map(lambda a: a[0], p), x[0], y[0]))(p0, imgs, lbls)))
        del p0
        got = float(np.asarray(self.call()).reshape(self.n_chips, -1)[0, 0])
        return _checks.loss_agrees(got, want, TRAIN_LOSS_TOL)

    def structure_check(self):
        import bluefog_tpu as bf
        from jax.sharding import PartitionSpec as P
        rounds = bf.static_schedule().num_rounds if self.n_chips > 1 else 0
        facts = _checks.hlo_facts(
            self.step, (self.params, self.state, self.data), self.n_chips,
            expect_permutes=rounds)
        if self.n_chips > 1:
            facts["mixing"] = _checks.mixing_check(
                self.comm, bf.mesh(), P("rank"), self.topology, self.seed)
            facts["ok"] = facts["ok"] and facts["mixing"]["ok"]
        return facts


def build_train(cfg, traffic, devices, seed):
    return Train(cfg, traffic, devices, seed)
