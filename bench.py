"""Benchmark driver: ResNet-50 synthetic throughput (reference headline).

Counterpart of ``examples/pytorch_benchmark.py`` + ``docs/performance.rst``:
synthetic ImageNet-shaped data through ResNet-50 with the decentralized
neighbor-allreduce optimizer, reporting images/sec per chip — the quantity
the reference reports per GPU (~269 img/sec/V100,
``docs/performance.rst:8-24``).  One process drives every local chip; on
one chip the topology is a self-loop and the number is compute throughput.

Prints ONE json line: {"metric", "value", "unit", "vs_baseline", ...}.

The measurement runs on a TPU or not at all: without one the script exits
non-zero with the reason and prints no metric line, and on the chip any
failure (compile, profiler, a device with no entry in the peak table) is
an error.  ``BLUEFOG_BENCH_FORCE_CPU=1`` is the explicit opt-in the tests
use to exercise the same code at toy shapes on the CPU; its line says
``"on_accelerator": false`` and is not a device measurement.

  BLUEFOG_BENCH_BATCH / _ITERS / _STEPS_PER_CALL   workload overrides
  BLUEFOG_BENCH_IMAGE_SIZE / _CLASSES   shrink the model for CI smoke tests
  BLUEFOG_BENCH_OVERLAP=1 (or --overlap)   also measure sequential vs
    pipelined (delayed=True + overlap=True) steps under a profiler trace;
    the artifact gains an "overlap" object with per-mode per_step_s,
    overlap_fraction, comm_exposed_s, top_exposed_comm_ops, and deltas
"""
import json
import os
import sys
import time

BASELINE_PER_GPU = 4310.6 / 16  # reference: img/sec per V100, 16-GPU run

# Chip spec tables (public spec sheets), substring-matched against
# device_kind — longer keys first so "v5p" wins over "v5".  Single source
# for every tool that needs a spec denominator (bench MFU, lm_bench,
# chip_calibrate's above-peak tripwires).
PEAK_FLOPS = {               # dense bf16 FLOP/s per chip
    "v6": 918e12,            # Trillium / v6e
    "v5p": 459e12,
    "v5": 197e12,            # v5e / "TPU v5 lite"
    "v4": 275e12,
    "v3": 123e12,
    "v2": 45e12,
}

HBM_PEAK_GBPS = {            # HBM bandwidth, GB/s per chip
    "v6": 1640,              # Trillium / v6e
    "v5p": 2765,
    "v5": 819,               # v5e / "TPU v5 lite"
    "v4": 1228,
    "v3": 900,
    "v2": 700,
}


def _match_spec(device_kind: str, table: dict):
    kind = device_kind.lower()
    for key, peak in table.items():
        if key in kind:
            return peak
    raise KeyError(
        f"no published peak for device_kind {device_kind!r}: add it to the "
        "spec tables in bench.py with its source")


def _peak_flops(device_kind: str):
    return _match_spec(device_kind, PEAK_FLOPS)


def _peak_hbm_gbps(device_kind: str):
    return _match_spec(device_kind, HBM_PEAK_GBPS)


def _env_int(name, default):
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


class NoTpuError(RuntimeError):
    """The measurement was asked for and JAX found no TPU."""


def run_bench(on_accelerator: bool) -> dict:
    """The measurement itself.  ``on_accelerator=True`` requires a TPU and
    raises without one; ``False`` is the tests' explicit CPU opt-in."""
    import jax
    import jax.numpy as jnp

    import optax

    import bluefog_tpu as bf
    from bluefog_tpu import models
    from bluefog_tpu import optimizers as bfopt
    from bluefog_tpu import topology as topology_util

    # bf.init before anything touches a backend: on a TPU it places the
    # libtpu flags and the persistent compile cache.  All local devices.
    bf.init(platform=None if on_accelerator else "cpu")
    dev = jax.devices()[0]
    if on_accelerator and dev.platform != "tpu":
        raise NoTpuError(
            f"no TPU: JAX picked platform {dev.platform!r} "
            f"({dev.device_kind}); bench.py measures on the chip or not at "
            "all (BLUEFOG_BENCH_FORCE_CPU=1 runs the toy CPU exercise)")
    n = bf.size()

    batch = _env_int("BLUEFOG_BENCH_BATCH", 64 if on_accelerator else 4)
    iters = _env_int("BLUEFOG_BENCH_ITERS", 10 if on_accelerator else 2)
    # scan several optimizer steps inside one compiled program: one dispatch
    # per scan amortizes the host launch cost, and XLA can overlap step t's
    # gossip with step t+1's compute across the scan body.  The CPU exercise
    # also defaults to a fused call (k=4) so it covers the fused+donated path.
    steps_per_call = _env_int("BLUEFOG_BENCH_STEPS_PER_CALL",
                              5 if on_accelerator else 4)
    config_source = "default"
    image_size = _env_int("BLUEFOG_BENCH_IMAGE_SIZE", 224)
    num_classes = _env_int("BLUEFOG_BENCH_CLASSES", 1000)
    # fused calls run in reuse_batch mode: the synthetic batch is constant
    # across the k scanned steps, so batch leaves stay [n, ...] — no k-fold
    # HBM replication for a steps axis the workload doesn't need
    if n > 1:
        bf.set_topology(topology_util.ExponentialTwoGraph(n), is_weighted=True)
    image = bf.shard_distributed(
        jnp.ones((n, batch, image_size, image_size, 3), jnp.float32))
    labels = bf.shard_distributed(jnp.zeros((n, batch), jnp.int32))

    model = models.ResNet50(num_classes=num_classes)
    variables = model.init(
        jax.random.key(0),
        jnp.ones((1, image_size, image_size, 3), jnp.float32), train=False)
    params, batch_stats = variables["params"], variables["batch_stats"]

    def grad_fn(train_state, data):
        params, batch_stats = train_state["params"], train_state["bs"]
        images, labels = data

        def loss_fn(p):
            logits, updates = model.apply(
                {"params": p, "batch_stats": batch_stats}, images,
                train=True, mutable=["batch_stats"])
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, labels).mean()
            return loss, updates["batch_stats"]

        (loss, new_bs), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        return loss, {"params": grads, "bs": jax.tree.map(jnp.zeros_like, new_bs)}

    # strategy: by default the neighbor-allreduce CTA baseline; with
    # BLUEFOG_BENCH_PLAN (set by --plan) an autotune plan replays its EXACT
    # configuration — algorithm, topology, wire, fused-k, overlap — so a
    # banked plan's prediction can be verified by measurement.  BN running
    # stats intentionally stay at init (synthetic throughput: only the
    # optax channel is optimized).
    opt = optax.sgd(0.1, momentum=0.9)
    plan = None
    plan_path = os.environ.get("BLUEFOG_BENCH_PLAN")
    if plan_path:
        from bluefog_tpu.autotune import load_plan
        plan = load_plan(plan_path)
        if int(plan.doc["n_chips"]) != n:
            raise RuntimeError(
                f"plan {plan.plan_id} was tuned for "
                f"{plan.doc['n_chips']} chips but this mesh has {n}; "
                "re-tune on this mesh (plans replay exactly or not at all)")
        plan.apply()
        strategy = plan.build_strategy(opt)
        algorithm = plan.algorithm
        step_kwargs = plan.train_step_kwargs()
        steps_per_call = step_kwargs["steps_per_call"]
        config_source = f"plan:{plan.plan_id}"
    else:
        strategy = bfopt.adapt_with_combine(
            opt, bfopt.neighbor_communicator(bf.static_schedule()))
        algorithm = "neighbor_cta"
        step_kwargs = {"steps_per_call": steps_per_call,
                       "reuse_batch": steps_per_call > 1}

    train_state = {"params": params, "bs": batch_stats}
    dist_params = bfopt.replicate(train_state, n)
    dist_state = bfopt.init_distributed(strategy, dist_params)
    # the fused k-step driver with donated params/opt-state: ONE executable
    # runs the whole k-step loop and updates both pytrees in place
    step = bfopt.make_train_step(grad_fn, strategy, donate=True,
                                 **step_kwargs)

    data = (image, labels)
    # compile ONCE via the context's AOT cache and reuse the executable for
    # both the FLOP accounting and the benchmark loop (a second compile of
    # ResNet-50 costs a minute on TPU; the cache also means an in-process
    # re-run of run_bench never re-lowers)
    from bluefog_tpu.parallel import context as bfctx
    compiled = bfctx.cached_lowering(
        ("bench-step", n, batch, steps_per_call, image_size, num_classes,
         algorithm, plan.plan_id if plan else None),
        step, dist_params, dist_state, data)
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    xla_flops_per_call = float(ca.get("flops", 0.0)) or None
    step = compiled
    # MFU uses analytic *model* FLOPs (the convention): ResNet-50 fwd
    # ~4.09 GFLOP/img, train ~3x.  XLA's cost_analysis count (reported
    # alongside as xla_call_flops) covers the whole steps_per_call-step
    # scan and includes non-model work, so it runs ~2x steps_per_call
    # times the per-step analytic number.
    flops_per_call = 3 * 4.089e9 * batch * n * steps_per_call

    # warmup call, then the timed window closed by a host transfer
    dist_params, dist_state, loss = step(dist_params, dist_state, data)
    bf.hard_sync(loss)

    t0 = time.perf_counter()
    for _ in range(iters):
        dist_params, dist_state, loss = step(dist_params, dist_state, data)
    bf.hard_sync(loss)
    dt = time.perf_counter() - t0

    # feed the telemetry registry from the synced totals: one amortized
    # fused-call observation per iter — per-call host times are dispatch
    # times under async dispatch, not step times (the AOT executable
    # bypasses make_train_step's own instrumentation)
    from bluefog_tpu.utils import metrics as bfmetrics
    for _ in range(iters):
        bfmetrics.record_step(dt / iters, steps=steps_per_call,
                              donated=True, fused_k=steps_per_call)

    total_imgs = iters * steps_per_call * batch * n
    imgs_per_sec = total_imgs / dt
    per_chip = imgs_per_sec / n
    fused_per_step_s = dt / (iters * steps_per_call)

    # optional amortization probe: re-measure the SAME workload at k=1 so
    # the artifact itself carries the fused-vs-unfused per-step comparison.
    # Costs a second compile, so it's opt-in (tools/step_sweep.py owns the
    # full scan on hardware; tests enable it on tiny shapes).
    fused_vs_spc1 = None
    if steps_per_call > 1 and os.environ.get(
            "BLUEFOG_BENCH_COMPARE_SPC1") == "1":
        step1 = bfopt.make_train_step(grad_fn, strategy, steps_per_call=1,
                                      donate=True)
        p1 = bfopt.replicate(train_state, n)
        s1 = bfopt.init_distributed(strategy, p1)
        p1, s1, l1 = step1(p1, s1, data)        # warmup/compile
        bf.hard_sync(l1)
        n1 = max(iters, iters * steps_per_call // 2)
        t1 = time.perf_counter()
        for _ in range(n1):
            p1, s1, l1 = step1(p1, s1, data)
        bf.hard_sync(l1)
        spc1_per_step_s = (time.perf_counter() - t1) / n1
        fused_vs_spc1 = {
            "spc1_per_step_s": round(spc1_per_step_s, 6),
            "fused_per_step_s": round(fused_per_step_s, 6),
            "fused_speedup": round(spc1_per_step_s / fused_per_step_s, 4),
        }

    # pipelined-vs-sequential gossip comparison (--overlap /
    # BLUEFOG_BENCH_OVERLAP=1): measure the SAME workload with the
    # one-step-delayed communicator (adapt_with_combine(delayed=True) +
    # overlap=True) and with the bulk-sequential strategy, capture a
    # profiler trace of each, and attribute comm exposure via
    # tools/trace_analyze — the artifact then carries the overlap proof
    # (overlap_fraction / comm_exposed_s / fused_per_step_s deltas), not
    # just a throughput number.
    overlap_report = None
    if "--overlap" in sys.argv or os.environ.get("BLUEFOG_BENCH_OVERLAP") == "1":
        overlap_report = _overlap_compare(
            bf, bfopt, grad_fn, opt, train_state, n, data,
            steps_per_call, iters)

    # MFU against the published peak of this device kind; a TPU that is
    # not in the table is an error, the CPU exercise reports none.
    # flops_per_call is cluster-total, so the denominator is n chips' peak
    device_kind = dev.device_kind
    peak = _peak_flops(device_kind) if on_accelerator else None
    mfu = (flops_per_call * iters / dt / (peak * n)) if peak else None

    # live-telemetry summary for the artifact: step-time histogram
    # percentiles, HLO-derived comm bytes (parsed from the compiled
    # program, not timed), compile-cache hit ratio, and one consensus-probe
    # sample on the final params
    from bluefog_tpu import diagnostics as bfdiag
    from bluefog_tpu.utils.hlo_bytes import wire_stats
    metrics_summary = bfmetrics.metrics_summary()
    counts, wire_b = wire_stats(compiled.as_text())
    metrics_summary["comm"] = {
        "per_call_bytes_per_chip": int(sum(wire_b.values())),
        "collectives": counts,
    }
    d = bfdiag.diagnose_consensus(dist_params)
    metrics_summary["consensus"] = {
        "distance_max": d["consensus_distance_max"],
        "distance_mean": d["consensus_distance_mean"],
        "neighbor_disagreement_max": d["neighbor_disagreement_max"],
    }

    return {
        "schema": "bluefog-bench-2",  # v2: strategy-aware artifacts
        "metric": "resnet50_synthetic_imgs_per_sec_per_chip",
        "value": round(per_chip, 2),
        "unit": "img/s/chip",
        "vs_baseline": round(per_chip / BASELINE_PER_GPU, 3),
        "ok": True,
        "strategy": algorithm,        # registry name (optimizers.STRATEGIES)
        "algorithm": algorithm,
        "plan_id": plan.plan_id if plan else None,
        "on_accelerator": on_accelerator,
        "platform": dev.platform,
        "device": device_kind,
        "n_chips": n,
        "batch_per_chip": batch,
        "mfu": round(mfu, 4) if mfu is not None else None,
        "mfu_spec": round(mfu, 4) if mfu is not None else None,
        "mfu_ceiling_source": "spec" if peak else None,
        "steps_per_call": steps_per_call,
        "donated": True,              # params/opt-state donated in the step
        "fused_per_step_s": round(fused_per_step_s, 6),
        "fused_vs_spc1": fused_vs_spc1,
        "overlap": overlap_report,
        "image_size": image_size,
        "num_classes": num_classes,
        "config_source": config_source,
        "step_flops": flops_per_call / steps_per_call,
        "xla_call_flops": xla_flops_per_call,
        "metrics_summary": metrics_summary,
    }


def _trace_overlap_stats(trace_dir):
    """Run tools/trace_analyze on a fresh profiler trace dir, in-process.
    Returns the analysis doc, or None when the trace holds nothing to
    attribute (the analyzer's own ``ok: false``)."""
    tools_dir = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools")
    if tools_dir not in sys.path:
        sys.path.insert(0, tools_dir)
    import trace_analyze as ta
    doc = ta.analyze(ta.load_events(ta.find_trace_file(trace_dir)))
    return doc if doc.get("ok") else None


def _overlap_compare(bf, bfopt, grad_fn, opt, train_state, n, data,
                     steps_per_call, iters):
    """Measure sequential vs pipelined (one-step-delayed) gossip.

    Both variants run the identical fused workload; the pipelined one uses
    ``adapt_with_combine(..., delayed=True)`` + ``overlap=True`` so the
    permute chain is data-independent of the update and the scheduler can
    hide it.  Each variant is profiled and fed through trace_analyze for
    ``overlap_fraction`` / ``comm_exposed_s``; deltas summarize the win.
    """
    import shutil
    import tempfile

    import jax

    def measure(delayed):
        comm = bfopt.neighbor_communicator(bf.static_schedule())
        strat = bfopt.adapt_with_combine(opt, comm, delayed=delayed)
        p = bfopt.replicate(train_state, n)
        s = bfopt.init_distributed(strat, p)
        step = bfopt.make_train_step(
            grad_fn, strat, steps_per_call=steps_per_call,
            reuse_batch=steps_per_call > 1, donate=True, overlap=delayed)
        p, s, loss = step(p, s, data)            # warmup/compile untraced
        bf.hard_sync(loss)
        trace_dir = tempfile.mkdtemp(prefix="bf-bench-overlap-")
        try:
            t0 = time.perf_counter()
            with jax.profiler.trace(trace_dir):
                for _ in range(iters):
                    p, s, loss = step(p, s, data)
                bf.hard_sync(loss)
            dt = time.perf_counter() - t0
            stats = _trace_overlap_stats(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        row = {"per_step_s": round(dt / (iters * steps_per_call), 6)}
        if stats is not None:
            row["overlap_fraction"] = stats.get("overlap_fraction")
            row["comm_exposed_s"] = round(
                stats.get("comm_exposed_ms", 0.0) / 1e3, 6)
            row["comm_s"] = round(stats.get("comm_ms", 0.0) / 1e3, 6)
            row["top_exposed_comm_ops"] = stats.get(
                "top_exposed_comm_ops", [])[:3]
        return row

    seq = measure(delayed=False)
    pipe = measure(delayed=True)
    deltas = {
        "per_step_speedup": round(
            seq["per_step_s"] / pipe["per_step_s"], 4)
        if pipe["per_step_s"] else None,
    }
    if "comm_exposed_s" in seq and "comm_exposed_s" in pipe:
        deltas["comm_exposed_s_delta"] = round(
            seq["comm_exposed_s"] - pipe["comm_exposed_s"], 6)
    if (seq.get("overlap_fraction") is not None
            and pipe.get("overlap_fraction") is not None):
        deltas["overlap_fraction_delta"] = round(
            pipe["overlap_fraction"] - seq["overlap_fraction"], 4)
    return {"ok": True, "iters": iters, "steps_per_call": steps_per_call,
            "sequential": seq, "pipelined": pipe, "deltas": deltas}


def main():
    # --plan <path> rides an env var so run_bench (also imported by tools
    # and tests) reads one source for it
    if "--plan" in sys.argv:
        idx = sys.argv.index("--plan")
        if idx + 1 >= len(sys.argv):
            print("bench: --plan requires a path", file=sys.stderr)
            sys.exit(2)
        os.environ["BLUEFOG_BENCH_PLAN"] = sys.argv[idx + 1]
    force_cpu = os.environ.get("BLUEFOG_BENCH_FORCE_CPU") == "1"
    try:
        result = run_bench(not force_cpu)
    except NoTpuError as e:
        print(f"bench: {e}", file=sys.stderr)    # the reason, no metric line
        sys.exit(1)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
