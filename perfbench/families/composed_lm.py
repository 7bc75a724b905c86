"""Family ``composed_lm``: the repo's composed decoder-only LM
(bluefog_tpu.parallel.compose.LMConfig) at a configuration file's sizes,
trained through compose_parallelism / make_lm_grad_fn / make_train_step and
served through ServeEngine + Scheduler: the entry points a user calls.

The configuration file uses the source's key names (hidden_size,
num_hidden_layers, ...); this file maps them onto LMConfig, makes the
weights on the device from the seed in one jitted call, and holds the
family's FLOPs-per-item function and its comparison with the plain
reference (perfbench/reference/composed_lm.py).
"""
import numpy as np

from perfbench.families import _checks
from perfbench.reference import composed_lm as reference

# |program - reference| <= tol * max(1, |reference|).  The program multiplies
# f32 weights at the TPU's default precision, the reference at `highest`;
# measured on the chip after a window of training: at most 4.9e-5 in 19 runs
# (PERF.md, PR 23), so 20 times that.
TRAIN_LOSS_TOL = 1e-3
# serving runs in bf16 end to end (weights, activations, cache): logits
# differ from the f32 reference by bf16 rounding through every layer.  As a
# share of the largest reference logit; measured 0.029-0.042 absolute where
# the largest logit is 3.0-3.8 (30 requests, PERF.md, PR 23), i.e. at most
# 1.3 %: three times that.  An int8 or fp8 cache or weights would not pass.
SERVE_LOGIT_TOL = 4e-2


def n_params(cfg):
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    per_block = D * 3 * D + D * D + D * F + F * D
    return cfg["num_hidden_layers"] * per_block + 2 * cfg["vocab_size"] * D


def flops_per_item(cfg, traffic):
    """Training FLOPs per token that the forward and backward passes
    require: 6N for the weights plus the attention score and value matmuls
    (6 * layers * d_model * seq_len); recomputation not counted.  A copy of
    compose.LMConfig.flops_per_token's arithmetic."""
    return (6.0 * n_params(cfg) + 6.0 * cfg["num_hidden_layers"]
            * cfg["hidden_size"] * traffic["seq_len"])


def param_shapes(cfg):
    """One replica's parameter tree as shapes (the compose-LM layout at
    pp = tp = 1)."""
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    L, V = cfg["num_hidden_layers"], cfg["vocab_size"]
    return {"blocks": {"wqkv": (L, D, 3 * D), "wo": (L, D, D),
                       "w1": (L, D, F), "w2": (L, F, D)},
            "shared": {"embed": (V, D), "head": (D, V)}}


def serve_config(traffic):
    """The traffic file's ``engine`` group as a ServeConfig."""
    import jax.numpy as jnp
    from bluefog_tpu.serve import ServeConfig
    eng = dict(traffic["engine"])
    eng["dtype"] = getattr(jnp, eng["dtype"])
    for key in ("batch_buckets", "prefill_buckets"):
        eng[key] = tuple(eng[key])
    return ServeConfig(**eng)


def _lm_config(cfg, **sizes):
    from bluefog_tpu.parallel import compose
    if cfg["intermediate_size"] % cfg["hidden_size"]:
        raise ValueError("intermediate_size must be a multiple of hidden_size")
    return compose.LMConfig(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        heads=cfg["num_attention_heads"], layers=cfg["num_hidden_layers"],
        ffn_mult=cfg["intermediate_size"] // cfg["hidden_size"], **sizes)


def _init_params(cfg, m, seed, dtype):
    """The compose-LM tree, every leaf [n, ...] on the carving's mesh, all
    replicas equal, normal(0, initializer_range), made on the device."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from bluefog_tpu.parallel import compose
    if (m.pp, m.tp, m.sp, m.ep) != (1, 1, 1, 1):
        raise ValueError("perfbench's composed_lm adapter makes weights for "
                         "dp-only carvings; a pp/tp carving is a later cell")
    n, std, shapes = m.size, cfg["initializer_range"], param_shapes(cfg)

    def make(key):
        leaves, treedef = jax.tree.flatten(
            shapes, is_leaf=lambda s: isinstance(s, tuple))
        keys = jax.random.split(key, len(leaves))
        out = [jnp.broadcast_to(
            (jax.random.normal(k, s, jnp.float32) * std).astype(dtype)[None],
            (n,) + s) for k, s in zip(keys, leaves)]
        return jax.tree.unflatten(treedef, out)

    sharding = NamedSharding(m.mesh, m.spec)
    return jax.jit(make, out_shardings=sharding)(jax.random.key(seed))


def _flat(p0):
    """{"blocks", "shared"} with leading [1, ...] -> the reference's flat
    dict (sliced inside the caller's jit, so nothing is copied here)."""
    return {**{k: v[0] for k, v in p0["blocks"].items()},
            **{k: v[0] for k, v in p0["shared"].items()}}


class Train:
    """One dp-only carving of the LM under the compose default strategy
    (adapt_with_combine over Exp2(dp), delayed, donated)."""

    def __init__(self, cfg, traffic, devices, seed):
        import jax
        import jax.numpy as jnp
        import optax
        from jax.sharding import NamedSharding

        from bluefog_tpu import optimizers as bfopt
        from bluefog_tpu.parallel import compose

        self.cfg, self.traffic = cfg, traffic
        dp = traffic["dp"]
        if dp != len(devices):
            raise ValueError(f"traffic asks dp={dp} on {len(devices)} devices")
        self.m = m = compose.compose_parallelism(dp, 1, 1, 1, devices=devices)
        self.lm = lm = _lm_config(cfg, seq_len=traffic["seq_len"],
                                  micro=traffic["micro"],
                                  batch=traffic["batch"])
        grad_fn = compose.make_lm_grad_fn(
            lm, m, use_pallas=bool(traffic.get("use_pallas", False)))
        self.step, strategy = compose.make_train_step(
            m, grad_fn, optax.adam(traffic["learning_rate"]))
        self.params = _init_params(cfg, m, seed, jnp.float32)
        self.state = bfopt.init_distributed(strategy, self.params)
        sharding = NamedSharding(m.mesh, m.spec)
        self.toks = jax.jit(
            lambda k: jax.random.randint(
                k, (m.size, lm.micro, lm.batch, lm.seq_len), 0, lm.vocab,
                jnp.int32),
            out_shardings=sharding)(jax.random.key(seed + 1))
        self.n_chips = m.size
        self.steps_per_call = 1
        self.items_per_call = lm.micro * lm.batch * lm.seq_len   # per chip
        self.flops_per_item = flops_per_item(cfg, traffic)
        self.seed = seed

    def call(self):
        self.params, self.state, loss = self.step(self.params, self.state,
                                                  self.toks)
        return loss

    def reference_check(self):
        """The program's loss on replica 0 against the plain reference on
        the same parameters and tokens.  Reads the parameters, then makes
        one more program call (which donates them)."""
        import jax
        heads, lag = self.lm.heads, self.lm.lag
        p0 = _checks.row0(self.params)
        toks0 = _checks.row0(self.toks)                         # [1, M, B, T]
        ref_loss = jax.jit(
            lambda p, t: reference.copy_task_loss(_flat(p), t, heads, lag))
        seqs = [(i, j) for i in range(self.lm.micro)
                for j in range(self.lm.batch)]
        want = float(np.mean([np.asarray(ref_loss(p0, toks0[0, i, j]))
                              for i, j in seqs]))
        del p0
        got = float(np.asarray(self.call()).reshape(self.n_chips, -1)[0, 0])
        return _checks.loss_agrees(got, want, TRAIN_LOSS_TOL,
                                   sequences=len(seqs))

    def structure_check(self):
        from bluefog_tpu import optimizers as bfopt
        facts = _checks.hlo_facts(
            self.step, (self.params, self.state, self.toks), self.n_chips,
            expect_permutes=self.m.schedule.num_rounds)
        if self.n_chips > 1:
            comm = bfopt.neighbor_communicator(self.m.schedule, axis="rank",
                                               wire=self.m.wire)
            facts["mixing"] = _checks.mixing_check(
                comm, self.m.mesh, self.m.spec, self.m.topology, self.seed)
            facts["ok"] = facts["ok"] and facts["mixing"]["ok"]
        return facts


def build_train(cfg, traffic, devices, seed):
    return Train(cfg, traffic, devices, seed)


class Serve:
    """One replica of ServeEngine + Scheduler over the LM in bf16."""

    def __init__(self, cfg, traffic, devices, seed):
        from bluefog_tpu.parallel import compose
        from bluefog_tpu.serve import Scheduler, ServeEngine

        self.cfg = cfg
        scfg = serve_config(traffic)
        self.m = compose.compose_parallelism(len(devices), 1, 1, 1,
                                             devices=devices)
        self.lm = _lm_config(cfg)
        self.params = _init_params(cfg, self.m, seed, scfg.dtype)
        self.engine = ServeEngine(self.m, self.lm, self.params, scfg)
        self._Scheduler = Scheduler
        self.vocab = cfg["vocab_size"]

    def warmup(self):
        self.engine.warmup()

    def scheduler(self):
        return self._Scheduler(self.engine)

    def retraces(self):
        from bluefog_tpu.utils import metrics
        return int(metrics.counter(
            "bluefog_retrace_after_warmup_total").total())

    def reference_check(self, prompts, output_tokens):
        """Prefill then decode through the cache, by way of a fresh
        Scheduler, against the reference's full forward pass: the prefill's
        last-position logits compared number by number, and every decoded
        token's reference logit within tolerance of the reference's largest
        (random weights: the argmax itself flips on rounding)."""
        import jax
        import jax.numpy as jnp
        heads = self.lm.heads
        sched = self.scheduler()
        reqs = [sched.submit(p, max_new_tokens=output_tokens) for p in prompts]
        sched.drain()
        sched.close()
        pad = -(-max(len(p) + output_tokens for p in prompts) // 128) * 128
        ref_logits = jax.jit(
            lambda p, t: reference.logits(_flat(p), t, heads))
        p0 = _checks.row0(self.params)
        rows, ok = [], True
        for slot, (prompt, req) in enumerate(zip(prompts, reqs)):
            seq = list(prompt) + [int(t) for t in req.generated]
            toks = np.zeros((pad,), np.int32)
            toks[:len(seq)] = seq
            want = np.asarray(ref_logits(p0, jnp.asarray(toks)))
            scale = float(np.max(np.abs(want[:len(seq)])))
            _, got = self.engine.prefill(0, slot, list(prompt))
            prefill_err = float(np.max(np.abs(
                np.asarray(got, np.float32) - want[len(prompt) - 1])))
            # generated[j] was chosen from position len(prompt) - 1 + j
            gaps = [float(want[len(prompt) - 1 + j].max()
                          - want[len(prompt) - 1 + j, int(t)])
                    for j, t in enumerate(req.generated)]
            whole = req.state == "done" \
                and len(req.generated) == output_tokens
            good = (whole and prefill_err <= SERVE_LOGIT_TOL * scale
                    and max(gaps) <= SERVE_LOGIT_TOL * scale)
            ok = ok and good
            rows.append({"prompt_tokens": len(prompt),
                         "prefill_logit_max_abs_err": prefill_err,
                         "decode_logit_gap_max": max(gaps),
                         "reference_logit_max_abs": scale,
                         "off_length": int(not whole), "ok": bool(good)})

        def worst(key):
            return max(r[key] / r["reference_logit_max_abs"] for r in rows)
        return {"ok": bool(ok), "tolerance": SERVE_LOGIT_TOL,
                "requests": rows, "compared": {
                    "prefill_logit_err_share": [
                        worst("prefill_logit_max_abs_err"), SERVE_LOGIT_TOL],
                    "decode_logit_gap_share": [
                        worst("decode_logit_gap_max"), SERVE_LOGIT_TOL],
                    "requests_off_length": [sum(
                        r["off_length"] for r in rows), 0]}}


def build_serve(cfg, traffic, devices, seed):
    return Serve(cfg, traffic, devices, seed)
