"""chip_smoke.py — does the system start on the chip?

Drives the decentralized trainer (the main path) and what stands beside it
once, through the entry points a user calls, at the full width of
ResNet-50, on whatever TPUs this host has (one chip or four), in ONE
process.  Weights and data are random from a seed; every phase checks its
output by the repo's own means.  Readings it prints (compile seconds,
seconds per call, peak bytes) are smoke readings, not benchmark results.

    python chip_smoke.py            # no arguments; sets no JAX_PLATFORMS

Exits non-zero, printing no result line, when JAX finds no TPU (before
compiling anything) or when any phase fails.  On success the last line of
stdout is ``{"ok": true, "device": {"platform", "kind", "count"}}``.

Phases: gossip_train (bf.init -> Exp2 topology -> ResNet-50 b64 ->
adapt_with_combine(neighbor_communicator) -> fused donated train step),
gossip_ops (each collective against the numpy product of its mixing
matrix), timing_barrier (block_until_ready vs hard_sync), kernels (every
Pallas kernel with interpret=False against its XLA twin), lm_and_serve
(the composed LM and ServeEngine + Scheduler).
"""
import importlib.metadata
import json
import math
import os
import sys
import time
import traceback

SEED = 0

# Widths of the size a deployment runs on the chip.  A CPU rehearsal passes
# smaller ones to the phase functions; the command line has no way to.  The
# LM (d_model 1024, 16 heads, seq 2048, vocab 32768) keeps its pipeline fill
# and batch small: at micro 4*pp x batch 4 the [micro, batch, seq, vocab] f32
# logits and their gradient alone pass a v5e's 16 GB, and with dense
# attention the [batch, heads, seq, seq] scores do at any fill, so the LM
# runs the flash kernel (both found by compiling for v5e:2x2).
WIDTHS = {
    "image": 224, "classes": 1000, "batch": 64, "steps_per_call": 5,
    "calls": 4,
    "attention": dict(B=4, T=2048, H=16, D=64),
    "decode": dict(lanes=8, rows=16, H=16, L=4096, D=64, block_k=128),
    "moe": dict(E=8, G=16, D=1024, F=4096),
    "delta": dict(T=1024, H=4, K=128, chunk=16),
    "lm": dict(seq=2048, d_model=1024, heads=16, vocab=32768, batch=2,
               micro_per_stage=2, steps_per_call=4, calls=2),
    "serve": dict(d_model=1024, heads=16, layers=4, vocab=32768,
                  requests=8, max_new=8),
}


class SmokeFailure(Exception):
    """A phase's own check did not hold."""


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def say(phase, msg):
    print(f"[{phase}] {msg}", flush=True)


def peak_bytes(devices):
    """Per device: peak bytes of live arrays, and of the allocator's
    reservation, which is where a compiled program's temporaries count."""
    stats = [d.memory_stats() for d in devices]
    return {k: [int(s[k]) for s in stats]
            for k in ("peak_bytes_in_use", "peak_bytes_reserved")}


def close(got, want, tol, what):
    """max|got - want| <= tol * max(1, max|want|), both taken to f32."""
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(np.all(np.isfinite(got)), f"{what}: non-finite values")
    err = float(np.max(np.abs(got - want)))
    bound = tol * max(1.0, float(np.max(np.abs(want))))
    check(err <= bound, f"{what}: max abs err {err:.3e} > {bound:.3e}")
    return err


# ---------------------------------------------------------------------------
# gossip_train
# ---------------------------------------------------------------------------

def phase_gossip_train(w, shared):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import bluefog_tpu as bf
    from bluefog_tpu import models
    from bluefog_tpu import optimizers as bfopt
    from bluefog_tpu import topology as topology_util
    from bluefog_tpu.utils.hlo_bytes import wire_stats

    n = bf.size()
    if n > 1:
        bf.set_topology(topology_util.ExponentialTwoGraph(n), is_weighted=True)
    sched = bf.static_schedule()

    model = models.ResNet50(num_classes=w["classes"])
    variables = model.init(
        jax.random.key(SEED),
        jnp.ones((1, w["image"], w["image"], 3), jnp.float32), train=False)

    def grad_fn(train_state, data):
        images, labels = data

        def loss_fn(p):
            logits, updates = model.apply(
                {"params": p, "batch_stats": train_state["bs"]}, images,
                train=True, mutable=["batch_stats"])
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, labels).mean()
            return loss, updates["batch_stats"]

        (loss, new_bs), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(train_state["params"])
        return loss, {"params": grads,
                      "bs": jax.tree.map(jnp.zeros_like, new_bs)}

    strategy = bfopt.adapt_with_combine(
        optax.sgd(0.02, momentum=0.9), bfopt.neighbor_communicator(sched))
    train_state = {"params": variables["params"],
                   "bs": variables["batch_stats"]}
    params = bfopt.replicate(train_state, n)
    state = bfopt.init_distributed(strategy, params)
    jax.block_until_ready((params, state))
    placed = peak_bytes(bf.devices())
    say("gossip_train", f"peak bytes per device after placement: {placed}")

    k = w["steps_per_call"]
    step = bfopt.make_train_step(grad_fn, strategy, donate=True,
                                 steps_per_call=k, reuse_batch=True)
    rng = np.random.default_rng(SEED)            # rank-varying data
    data = (
        bf.shard_distributed(rng.standard_normal(
            (n, w["batch"], w["image"], w["image"], 3), np.float32)),
        bf.shard_distributed(rng.integers(
            0, w["classes"], (n, w["batch"]), np.int32)))

    # the probe compiles here, inside the warmup, and reads 0 on replicas
    check(bf.diagnose_consensus(params)["consensus_distance_max"] == 0.0,
          "replicas differ before the first step")
    compile_s = None
    if n > 1:
        # the compiled schedule: one collective-permute per gossip round
        # (fused across leaves), and gossip never falls back to all-reduce.
        # The first call below then finds the program in the persistent
        # cache, so this is the compile that gets timed.
        t0 = time.perf_counter()
        hlo = step.lower(params, state, data).compile().as_text()
        compile_s = time.perf_counter() - t0
        counts, _ = wire_stats(hlo)
        say("gossip_train", f"collectives in the compiled step: {counts}")
        rounds = len(sched.rounds)
        check(counts.get("collective-permute", 0) == rounds,
              f"expected {rounds} collective-permutes, got {counts}")
        check("all-reduce" not in counts, f"all-reduce in the step: {counts}")

    losses, times = [], []
    for _ in range(w["calls"]):
        t0 = time.perf_counter()
        params, state, loss = step(params, state, data)
        losses.append(np.asarray(jax.block_until_ready(loss)))   # [n, k]
        times.append(time.perf_counter() - t0)
    losses = np.stack(losses)                                    # [calls, n, k]
    if compile_s is None:
        compile_s = times[0] - min(times[1:])
    say("gossip_train",
        f"{w['calls']} calls x {k} steps; compile {compile_s:.1f} s, first "
        f"call {times[0]:.1f} s, then {min(times[1:]):.3f} s per call")
    first, last = losses[0, :, 0], losses[-1, :, -1]
    say("gossip_train", f"loss per rank: first step {first}, last step {last}")
    check(np.all(np.isfinite(losses)), "non-finite loss")
    check(last.mean() < first.mean(),
          f"loss did not decrease: {first.mean()} -> {last.mean()}")

    for leaf in jax.tree.leaves((params, state)):
        check(len(leaf.sharding.device_set) == n
              and leaf.addressable_shards[0].data.shape[0] == 1,
              f"leaf {leaf.shape} is not one row per device over {n} "
              f"devices: {leaf.sharding}")
    diag = bf.diagnose_consensus(params)
    say("gossip_train",
        f"consensus distance max {diag['consensus_distance_max']:.3e}")
    check(math.isfinite(diag["consensus_distance_max"]),
          "consensus distance is not finite")
    say("gossip_train",
        f"peak bytes per device after training: {peak_bytes(bf.devices())}")
    shared["train"] = (step, params, state, data)
    return {"compile_s": round(compile_s, 1),
            "call_s": round(min(times[1:]), 4)}


# ---------------------------------------------------------------------------
# gossip_ops
# ---------------------------------------------------------------------------

def phase_gossip_ops(w, shared):
    import jax.numpy as jnp
    import numpy as np

    import bluefog_tpu as bf
    from bluefog_tpu import topology as tu

    n = bf.size()
    rng = np.random.default_rng(SEED + 1)
    x_np = rng.normal(size=(n, 1024)).astype(np.float32)
    x = bf.shard_distributed(jnp.asarray(x_np))
    if n == 1:
        say("gossip_ops", "one device: self-loop, checking the identity")
        close(bf.neighbor_allreduce(x), x_np, 1e-5, "neighbor_allreduce")
        close(bf.allreduce(x), x_np, 1e-5, "allreduce")
        return {"self_loop": True}

    topo = tu.ExponentialTwoGraph(n)
    bf.set_topology(topo, is_weighted=True)
    W = tu.to_weight_matrix(topo)                       # W[src, dst]
    errs = {"static": close(bf.neighbor_allreduce(x), W.T @ x_np, 1e-5,
                            "neighbor_allreduce (static)")}

    bf.set_dynamic_topology(
        lambda r: tu.GetDynamicOnePeerSendRecvRanks(topo, r))
    gens = [tu.GetDynamicOnePeerSendRecvRanks(topo, r) for r in range(n)]
    for t in range(int(math.log2(n)) + 1):
        recv = [next(g)[1] for g in gens]
        want = np.stack([(x_np[r] + sum(x_np[s] for s in recv[r]))
                         / (len(recv[r]) + 1) for r in range(n)])
        errs[f"dynamic_{t}"] = close(
            bf.neighbor_allreduce(x, step=t), want, 1e-5,
            f"neighbor_allreduce (one-peer, step {t})")
    bf.clear_dynamic_topology()

    errs["allreduce"] = close(
        bf.allreduce(x), np.broadcast_to(x_np.mean(0), x_np.shape), 1e-5,
        "allreduce")

    bf.win_create(x, "chip_smoke", zero_init=True)
    bf.win_put(x, "chip_smoke")
    errs["win"] = close(bf.win_update("chip_smoke"), W.T @ x_np, 1e-5,
                        "win_put + win_update")
    bf.win_free("chip_smoke")

    # two chips a machine: intra-machine mean, then machine-level gossip
    L, M = 2, n // 2
    bf.init(nodes_per_machine=L)
    mtopo = tu.ExponentialTwoGraph(M)
    bf.set_machine_topology(mtopo, is_weighted=True)
    mavg = x_np.reshape(M, L, -1).mean(axis=1)
    want = np.repeat(tu.to_weight_matrix(mtopo).T @ mavg, L, axis=0)
    errs["hierarchical"] = close(
        bf.hierarchical_neighbor_allreduce(bf.shard_distributed(
            jnp.asarray(x_np))), want, 1e-5,
        "hierarchical_neighbor_allreduce")
    bf.init()                      # back to one machine for the next phases
    say("gossip_ops", f"max abs errors vs numpy: {errs}")
    return {"max_err": max(errs.values())}


# ---------------------------------------------------------------------------
# timing_barrier
# ---------------------------------------------------------------------------

def phase_timing_barrier(w, shared):
    import jax

    import bluefog_tpu as bf

    check("train" in shared, "needs the train step of gossip_train")
    step, params, state, data = shared.pop("train")
    calls, rounds = 4, 3
    best = {}
    for _ in range(rounds):
        for name, barrier in (("block_until_ready", jax.block_until_ready),
                              ("hard_sync", bf.hard_sync)):
            t0 = time.perf_counter()
            for _ in range(calls):
                params, state, loss = step(params, state, data)
            barrier(loss)
            dt = (time.perf_counter() - t0) / calls
            best[name] = min(best.get(name, dt), dt)
    ratio = best["block_until_ready"] / best["hard_sync"]
    say("timing_barrier",
        f"seconds per call: {best}; block_until_ready / hard_sync = "
        f"{ratio:.4f}")
    check(abs(ratio - 1.0) <= 0.05,
          f"block_until_ready and hard_sync disagree: ratio {ratio:.4f} "
          "(a barrier that returns at dispatch reads far below 1)")
    return {"ratio": round(ratio, 4), "call_s": best}


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

# bf16-level: one bf16 rounding is 2^-9 = 2e-3 relative, and XLA's twin
# runs its f32 matmuls through the MXU at bf16 operand precision, so a few
# roundings accumulate.  Relative to the largest reference value.
TOL_BF16 = 3e-2


def _kernel_attention(a, interpret):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bluefog_tpu.ops.ulysses import (dense_attention,
                                         local_flash_attention)

    rng = np.random.default_rng(SEED + 2)
    shape = (a["B"], a["T"], a["H"], a["D"])
    q, k, v, ct = (jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
                   for _ in range(4))
    scale = a["D"] ** -0.5

    def flash(q_, k_, v_):
        return local_flash_attention(q_, k_, v_, True, scale, 512, interpret)

    def dense(q_, k_, v_):
        return dense_attention(q_, k_, v_, True, scale)

    def run(f):
        def loss(q_, k_, v_):
            out = f(q_, k_, v_)
            return jnp.sum((out * ct).astype(jnp.float32)), out
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True))(q, k, v)

    (_, out_f), grads_f = run(flash)
    (_, out_d), grads_d = run(dense)
    errs = {"out": close(out_f, out_d, TOL_BF16, "flash attention forward")}
    for name, gf, gd in zip(("dq", "dk", "dv"), grads_f, grads_d):
        errs[name] = close(gf, gd, TOL_BF16, f"flash attention {name}")
    return {k_: float(np.round(e, 5)) for k_, e in errs.items()}


def _kernel_decode(a, interpret):
    import jax.numpy as jnp
    import numpy as np

    from bluefog_tpu.ops import pallas_decode
    from bluefog_tpu.serve import kv_cache

    rng = np.random.default_rng(SEED + 3)
    S, R, H, L, D = a["lanes"], a["rows"], a["H"], a["L"], a["D"]
    q = jnp.asarray(rng.normal(size=(S, H, D)), jnp.bfloat16)
    pages = rng.normal(size=(2, R, H, L, D)).astype(np.float32)
    slots = jnp.asarray(rng.permutation(R)[:S], jnp.int32)
    lens = jnp.asarray(rng.integers(0, L, size=S), jnp.int32)   # ragged
    errs = {}
    for store in ("bf16", "int8"):
        if store == "bf16":
            kl, vl = (jnp.asarray(p, jnp.bfloat16) for p in pages)
            scales = {}
        else:
            (kl, ks), (vl, vs) = (kv_cache.quantize_rows(
                jnp.asarray(p), "int8") for p in pages)
            scales = {"k_scale": ks, "v_scale": vs}
        got = pallas_decode.flash_attend_rows(
            q, kl, vl, slots, lens, block_k=a["block_k"],
            interpret=interpret, **scales)
        want = kv_cache.attend_rows(q, kl, vl, slots, lens, **scales)
        errs[store] = float(np.round(close(
            got, want, TOL_BF16, f"flash decode ({store} pages)"), 5))
    return errs


def _kernel_moe(a, interpret):
    import jax.numpy as jnp
    import numpy as np

    from bluefog_tpu.moe.dropless import grouped_ffn_xla
    from bluefog_tpu.ops.pallas_moe import grouped_ffn_pallas

    rng = np.random.default_rng(SEED + 4)
    E, G, D, F = a["E"], a["G"], a["D"], a["F"]
    w1 = rng.normal(size=(E, D, F)).astype(np.float32) / math.sqrt(D)
    w2 = rng.normal(size=(E, F, D)).astype(np.float32) / math.sqrt(F)
    eid = jnp.asarray(np.sort(rng.integers(0, E, size=G)), jnp.int32)
    errs = {}
    for tile in (128, 8):
        xt = rng.normal(size=(G, tile, D)).astype(np.float32)
        for dtype in (jnp.bfloat16, jnp.float32):
            args = (jnp.asarray(xt, dtype), eid, jnp.asarray(w1, dtype),
                    jnp.asarray(w2, dtype))
            got = grouped_ffn_pallas(*args, interpret=interpret)
            want = grouped_ffn_xla(*args)
            name = f"tile{tile}_{jnp.dtype(dtype).name}"
            errs[name] = float(np.round(close(
                got, want, TOL_BF16, f"grouped MoE FFN ({name})"), 5))
    return errs


def _kernel_delta(a, interpret):
    """A prompt's delta rule against the single step, token by token."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bluefog_tpu.models import decoder
    from bluefog_tpu.ops import pallas_delta

    rng = np.random.default_rng(SEED + 5)
    T, H, K = a["T"], a["H"], a["K"]
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = jnp.asarray(unit(rng.normal(size=(T, H, K))) * K ** -0.5,
                    jnp.bfloat16)
    k = jnp.asarray(unit(rng.normal(size=(T, H, K))), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(T, H, K)), jnp.bfloat16)
    g = jnp.asarray(-np.exp(rng.normal(size=(T, H, K)) - 3.0), jnp.float32)
    beta = jnp.asarray(2.0 / (1.0 + np.exp(rng.normal(size=(T, H)))),
                       jnp.float32)
    zero = jnp.zeros((H, K, K), jnp.float32)
    o, S = pallas_delta.delta_rule(
        *(t.reshape(T, -1) for t in (q, k, v, g)), beta, zero,
        chunk=a["chunk"], interpret=interpret)

    def step(S_, x):
        o_, S_ = decoder.delta_step(S_, *x)
        return S_, o_[0]
    Sw, ow = jax.jit(lambda *x: jax.lax.scan(step, zero[None], x))(
        g[:, None], beta[:, None], q[:, None], k[:, None], v[:, None])
    return {"out": float(np.round(close(
                o.reshape(T, H, K), ow, TOL_BF16, "delta rule outputs"), 5)),
            "state": float(np.round(close(
                S, Sw[0], TOL_BF16, "delta rule state"), 5))}


def phase_kernels(w, shared, interpret=False):
    out = {}
    for name, fn in (("attention", _kernel_attention),
                     ("decode", _kernel_decode), ("moe", _kernel_moe),
                     ("delta", _kernel_delta)):
        out[name] = fn(w[name], interpret)
        say("kernels", f"{name}: max abs err vs the XLA path {out[name]} "
            f"(tolerance {TOL_BF16} of the largest reference value)")
    return out


# ---------------------------------------------------------------------------
# lm_and_serve
# ---------------------------------------------------------------------------

def phase_lm_and_serve(w, shared):
    import jax
    import numpy as np
    import optax

    import bluefog_tpu as bf
    from bluefog_tpu import optimizers as bfopt
    from bluefog_tpu.parallel import compose
    from bluefog_tpu.serve import Scheduler, ServeConfig, ServeEngine
    from bluefog_tpu.utils import metrics

    n = bf.size()
    lm = w["lm"]
    dp, pp = (2, 2) if n == 4 else (n, 1)
    m = compose.compose_parallelism(dp, pp, 1, 1)
    cfg = compose.LMConfig(
        vocab=lm["vocab"], d_model=lm["d_model"], heads=lm["heads"],
        layers=2 * pp, seq_len=lm["seq"], micro=lm["micro_per_stage"] * pp,
        batch=lm["batch"])
    k = lm["steps_per_call"]
    step, strategy = compose.make_train_step(
        m, compose.make_lm_grad_fn(cfg, m, use_pallas=True), optax.adam(5e-3),
        delayed=dp > 1, steps_per_call=k, reuse_batch=True)
    params = compose.device_put(m, compose.init_lm_params(cfg, m, seed=SEED))
    state = bfopt.init_distributed(strategy, params)
    toks = compose.device_put(m, compose.make_lm_batch(cfg, m, seed=SEED))
    losses, times = [], []
    for _ in range(lm["calls"] + 1):
        t0 = time.perf_counter()
        params, state, loss = step(params, state, toks)
        losses.append(np.asarray(jax.block_until_ready(loss)))
        times.append(time.perf_counter() - t0)
    losses = np.stack(losses)
    first, last = float(losses[0, :, 0].mean()), float(losses[-1, :, -1].mean())
    say("lm_and_serve",
        f"LM {m.describe()} {cfg.n_params / 1e6:.0f}M params: first call "
        f"{times[0]:.1f} s, then {min(times[1:]):.3f} s per {k}-step call; "
        f"loss {first:.4f} -> {last:.4f}")
    check(np.all(np.isfinite(losses)), "LM loss is not finite")
    check(last < first, f"LM loss did not decrease: {first} -> {last}")
    del params, state, step

    sv = w["serve"]
    m = compose.compose_parallelism(n, 1, 1, 1)
    cfg = compose.LMConfig(vocab=sv["vocab"], d_model=sv["d_model"],
                           heads=sv["heads"], layers=sv["layers"])
    engine = ServeEngine(m, cfg, compose.init_lm_params(cfg, m, seed=SEED),
                         ServeConfig())
    t0 = time.perf_counter()
    engine.warmup()
    warm_s = time.perf_counter() - t0
    rng = np.random.default_rng(SEED + 5)
    prompts = [rng.integers(0, cfg.vocab, int(rng.integers(
        2, engine.scfg.prefill_buckets[-1] + 1))).tolist()
        for _ in range(sv["requests"])]

    def answer():
        sched = Scheduler(engine)
        reqs = [sched.submit(p, max_new_tokens=sv["max_new"])
                for p in prompts]
        sched.drain()
        sched.close()
        check(all(r.state == "done" and len(r.generated) == sv["max_new"]
                  for r in reqs),
              f"requests not completed: {[(r.state, len(r.generated)) for r in reqs]}")
        return [list(r.generated) for r in reqs]

    retrace_counter = metrics.counter("bluefog_retrace_after_warmup_total")
    armed = retrace_counter.total()          # warmup armed the sentinel
    t0 = time.perf_counter()
    tokens = answer()
    first_pass_s = time.perf_counter() - t0
    check(answer() == tokens, "greedy tokens differ on a second pass")
    retraces = int(retrace_counter.total() - armed)
    say("lm_and_serve",
        f"serve: warmup {warm_s:.1f} s, {sv['requests']} requests x "
        f"{sv['max_new']} tokens in {first_pass_s:.2f} s on {n} replica(s), "
        f"retraces {retraces}, second pass identical")
    check(retraces == 0, f"{retraces} retraces after warmup")
    return {"lm_call_s": round(min(times[1:]), 4), "serve_warmup_s":
            round(warm_s, 1)}


PHASES = (("gossip_train", phase_gossip_train),
          ("gossip_ops", phase_gossip_ops),
          ("timing_barrier", phase_timing_barrier),
          ("kernels", phase_kernels),
          ("lm_and_serve", phase_lm_and_serve))


def cache_entries(path):
    return len(os.listdir(path)) if path and os.path.isdir(path) else 0


def main():
    try:
        import jax
        import jaxlib

        import bluefog_tpu as bf
        from bluefog_tpu import _native
        from bluefog_tpu.utils import metrics
    except ImportError as e:
        print(f"chip_smoke: cannot import the program: {e}", file=sys.stderr)
        return 2
    # bf.init before anything else touches a backend: on a TPU it places the
    # libtpu flags and the compile cache.  It compiles nothing.
    bf.init()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX picked platform {dev.platform!r} "
              f"({dev.device_kind}); refusing to run", file=sys.stderr)
        return 2
    n = len(jax.devices())
    if n not in (1, 4):
        print(f"chip_smoke: {n} devices; written for one chip or a four-chip "
              "host", file=sys.stderr)
        return 2

    hits = {"hit": 0, "miss": 0}

    def count_cache_event(event, **_):
        if event.endswith("/cache_hits"):
            hits["hit"] += 1
        elif event.endswith("/cache_misses"):
            hits["miss"] += 1

    jax.monitoring.register_event_listener(count_cache_event)
    cache_dir = jax.config.jax_compilation_cache_dir
    print(f"platform {dev.platform}, device_kind {dev.device_kind}, "
          f"{n} device(s): "
          f"{[(d.id, getattr(d, 'coords', None)) for d in bf.devices()]}")
    print(f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, libtpu "
          f"{importlib.metadata.version('libtpu')}; native (C++) components "
          f"available: {_native.available()}")
    print(f"LIBTPU_INIT_ARGS: {os.environ.get('LIBTPU_INIT_ARGS', '')}")
    print(f"compile cache {cache_dir}: {cache_entries(cache_dir)} entries "
          "before", flush=True)

    shared, failed, t_start = {}, [], time.perf_counter()
    for name, fn in PHASES:
        t0 = time.perf_counter()
        before = dict(hits)
        # each phase is a new workload with its own warmup: what it compiles
        # first is not a steady-state retrace of the phase before
        metrics.mark_steady_state(False)
        try:
            report = fn(WIDTHS, shared)
        except Exception:                    # recorded; the run ends non-zero
            traceback.print_exc()
            failed.append(name)
            report = "FAILED"
        say(name, f"{report} in {time.perf_counter() - t0:.1f} s; compile "
            f"cache hits {hits['hit'] - before['hit']}, misses "
            f"{hits['miss'] - before['miss']}")
    print(f"compile cache {cache_dir}: {cache_entries(cache_dir)} entries "
          f"after; hits {hits['hit']}, misses {hits['miss']}; total "
          f"{time.perf_counter() - t_start:.0f} s", flush=True)
    if failed:
        print(f"chip_smoke: FAILED phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": n}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
