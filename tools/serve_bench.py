"""End-to-end grader for the decentralized serving engine.

Brings up the full train→serve estate on one device set: a gossip-DP
training fleet (``compose.make_train_step``) on the first ``train_dp``
slices and a :class:`bluefog_tpu.serve.ServeEngine` +
:class:`~bluefog_tpu.serve.Scheduler` on the rest, with a
:class:`~bluefog_tpu.serve.WeightRefresher` pulling fresh params
mid-traffic.  Grades serving on every axis ISSUE 10's claim rides on:

* **tokens/sec** of the continuous-batching drain (prefill + decode,
  training interleaved on the same host);
* **p50 / p99 per-token latency** from the
  ``bluefog_serve_token_latency_seconds`` histogram, plus TTFT
  percentiles from the completed requests themselves;
* **decode MFU** against the trusted roofline ceiling
  (``bench._peak_flops``; null off-TPU) using forward-only decode
  FLOPs/token (2N weight term + exact per-request attention context);
* **refresh staleness**: max and final value of the
  ``bluefog_serve_staleness_steps`` gauge, and the pull count — the
  freshness the gossip leaf actually delivered under load;
* **invariants**: KV-cache donation intact after the drain, retrace
  sentinel 0 after warmup (every served shape hit a declared bucket);

and, when the fast paths are armed (schema 2 rows):

* **speculative decoding** (``--spec-decode k[@stages]``): acceptance
  rate, accepted-tokens/s, and a bit-identity probe — the same prompts
  decoded by a plain-greedy reference engine must produce byte-identical
  token streams;
* **prefix sharing** (``--prefix-pages P[xT]``): hit/miss counts plus a
  same-prompt TTFT probe — the second, prefix-hit submission of an
  identical prompt must beat the cold one that sealed the page;
* **KV quantization** (``--kv-dtype int8|fp8``): KV bytes/token against
  the raw layout (the float64 logit-drift bound is pinned in
  ``tests/test_serve_fast.py``).

With ``--decode-kernel pallas[@block_k]`` (schema 4) the engine serves
through the paged flash-decode Pallas kernel (``ops/pallas_decode.py``)
instead of the XLA gather-then-attend path; the artifact gains a
``decode`` section with (a) a kernel-vs-XLA token bit-identity gate on
the same prompts and (b) decode-MFU-at-context rows — the decode
attention hot path timed at context x occupancy x KV-dtype points for
both the configured kernel and the XLA reference, with achieved
FLOPs/sec against the roofline ceiling.

With ``--serve-moe E[xK][@EP][:TILE]`` (schema 5) the whole estate goes
MoE: the training fleet optimizes a dropless routed-MoE LM
(``make_moe_grad_fn``) and the serving engine decodes through the
grouped-GEMM dropless path on an ``ep``-carved mesh, with the refresher
pulling router + expert tables live.  The artifact gains a ``moe``
section with (a) a greedy-token bit-identity gate (MoE speculative
decode vs plain MoE greedy), (b) tokens/s against the **dense twin at
equal active params** (``MoELMConfig.dense_twin`` — Switch-Transformer
accounting) on the same prompts, (c) the router-entropy / hot-expert
histogram the expert-load-aware scheduler reads, and (d) an AOT wire
proof — the fused-decode program's collectives classified per chip with
``stablehlo_wire_stats`` — gating that the dispatch/combine all_to_alls
are ICI-side (zero DCN all_to_alls).

With ``--traffic-trace`` (schema 3) the drain is followed by a bursty
traffic phase driven by a synthetic arrival trace (``diurnal`` — one
day-cycle sinusoid — or ``flash-crowd`` — a low base rate with a sudden
spike): the highest serve replica starts *parked* (out of rotation) and
an :class:`~bluefog_tpu.serve.scheduler.AutoScaler` watching queue depth
+ EWMA p99 must grow it back into the spike (writing the bfrun scale
file on the way) and retire it after the cooldown.  The artifact's
``trace`` row records the grow step, SLO recovery time (asserted under a
bound), scale events, and the requeued-vs-failed split — the gate
demands **zero failed requests** across the scale events.

Emits a ``bluefog-serve-bench-5`` JSON artifact (last stdout line, and
``--out``).

Run:    python tools/serve_bench.py --train-dp 2 --serve-dp 2 --pp 2 --out ...
Smoke:  python tools/serve_bench.py --virtual-cpu --smoke
Fast:   python tools/serve_bench.py --virtual-cpu --smoke \
            --spec-decode 3@1 --prefix-pages 2x8 --kv-dtype int8
Flash:  python tools/serve_bench.py --virtual-cpu --smoke \
            --decode-kernel pallas@8 --kv-dtype int8 --prefix-pages 2x8
MoE:    python tools/serve_bench.py --virtual-cpu --smoke \
            --serve-moe 4x2@2:4 --spec-decode 2@1
Trace:  python tools/serve_bench.py --virtual-cpu --smoke \
            --traffic-trace flash-crowd
"""
import argparse
import dataclasses
import importlib.util
import json
import os
import sys
import tempfile
import time

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, REPO)

SCHEMA = "bluefog-serve-bench-5"


def _trace_arrivals(shape, steps, slots, rng):
    """Per-step request arrival counts for a synthetic traffic shape.

    ``diurnal``: one full day cycle, midnight troughs and a midday peak
    sized to breach the queue-depth watermark.  ``flash-crowd``: a low
    base rate with a sudden spike of ``3*slots`` requests one third in.
    """
    import math
    if shape == "diurnal":
        # peak sized to oversubscribe ONE replica (forcing the grow) while
        # staying drainable by two before the recovery bound
        hi = max(4, (3 * slots) // 4)
        return [int(round(hi * 0.5 * (1.0 - math.cos(2.0 * math.pi
                                                     * t / steps))))
                for t in range(steps)]
    if shape == "flash-crowd":
        arrivals = [1] * steps
        arrivals[steps // 3] += 3 * slots
        return arrivals
    raise ValueError(f"unknown traffic shape {shape!r}")


def _run_traffic_trace(engine, shape, *, steps, vocab, max_new, rng,
                       slo_p99_ms=None):
    """The schema-3 bursty phase: parked reserve replica, arrival-trace
    traffic, and an AutoScaler that must grow into the spike.  Returns
    the artifact's ``trace`` row."""
    import tempfile
    from bluefog_tpu.diagnostics import SLOEngine
    from bluefog_tpu.run.launcher import _read_scale
    from bluefog_tpu.serve import Scheduler
    from bluefog_tpu.serve.scheduler import AutoScaler

    sched = Scheduler(engine)
    parked = [sched.replicas - 1] if sched.replicas >= 2 else []
    for r in parked:
        # no traffic yet: clean park (slice intact, eligible for re-admit)
        sched.fail_replica(r, reason="parked", park=True)
    scale_file = os.path.join(tempfile.mkdtemp(prefix="bfscale_"),
                              "bluefog_scale")
    scaler = AutoScaler(
        sched,
        slo_p99_s=(slo_p99_ms / 1000.0) if slo_p99_ms else None,
        queue_high=engine.scfg.slots,       # breach when one replica's
        cooldown_steps=3,                   # worth of slots is waiting
        scale_file=scale_file, min_replicas=1)
    # the SLO engine scores the same phase: burn-rate gauges every step,
    # fast-burn tripwire when the spike torches the error budget
    slo = SLOEngine(p99_ms=scaler.slo_p99_s * 1000.0)
    sched.attach_slo(slo)
    burn_peak = None
    arrivals = _trace_arrivals(shape, steps, engine.scfg.slots, rng)
    submitted = 0
    grow_step = None
    recovered_step = None
    t = 0

    def _tick():
        nonlocal grow_step, recovered_step, burn_peak
        sched.step()
        rate = slo.last_burn.get(("5m", "p99"))
        if rate is not None and (burn_peak is None or rate > burn_peak):
            burn_peak = rate
        ev = scaler.observe()
        if ev and ev["action"] == "grow" and grow_step is None:
            grow_step = t
        if (grow_step is not None and recovered_step is None
                and sched.pending == 0):
            recovered_step = t

    for t in range(steps):
        for _ in range(arrivals[t]):
            n = int(rng.integers(2, engine.scfg.prefill_buckets[-1] + 1))
            sched.submit(rng.integers(0, vocab, n).tolist(),
                         max_new_tokens=max_new)
            submitted += 1
        _tick()
    while not sched.done:
        t += 1
        if t > steps + 100_000:
            raise RuntimeError("traffic trace failed to drain")
        _tick()

    bound = 2 * steps
    recovery = (recovered_step - grow_step
                if grow_step is not None and recovered_step is not None
                else None)
    # the scale file speaks RANKS: live replicas x slice size.  Gate the
    # actual written value, not just its presence — a replica-count write
    # would make the supervisor SIGTERM ranks during the breach.
    scale_target = _read_scale(scale_file)
    expected_world = len(sched.live_replicas()) * engine.m.slice_size
    row = {
        "shape": shape,
        "steps": steps,
        "parked_replicas": parked,
        "submitted": submitted,
        "completed": len(sched.completed),
        "failed": len(sched.failed),
        "requeued": sched.requeued_total,
        "grow_step": grow_step,
        "recovery_steps": recovery,
        "recovery_bound_steps": bound,
        "slo_p99_s": scaler.slo_p99_s,
        "ewma_p99_s": scaler.ewma_p99,
        "slo": {
            "burn_peak_5m_p99": (round(burn_peak, 3)
                                 if burn_peak is not None else None),
            "burn_final": {f"{w}/{s}": (round(v, 3) if v is not None
                                        else None)
                           for (w, s), v in sorted(slo.last_burn.items())},
            "tripwires": sorted({f["kind"] for f in slo.fired}),
        },
        "scale_events": scaler.events,
        "scale_file_target": scale_target,
        "ranks_per_replica": engine.m.slice_size,
        "expected_world": expected_world,
        "ok": bool(submitted == len(sched.completed)
                   and not sched.failed
                   and grow_step is not None
                   and recovery is not None and recovery <= bound
                   and scale_target == expected_world
                   and (not scaler.events
                        or scale_target ==
                        scaler.events[-1]["target_world"])),
    }
    sched.close()
    return row


def _decode_attend_bench(scfg, heads, head_dim, *, kernel, block_k,
                         on_tpu, peak, iters):
    """Schema-4 decode-MFU-at-context rows.

    Times the decode attention hot path — one new token per lane over a
    slot-paged KV cache — at context x occupancy (live lanes) x KV-dtype
    points, for the configured kernel AND the XLA gather-then-attend
    reference on the same pages.  Attention FLOPs are exact (score +
    value matmuls over the attended context); MFU is against the trusted
    roofline ceiling, null off-TPU where interpret-mode Pallas timings
    grade nothing.
    """
    import numpy as np
    import jax
    import jax.numpy as jnp
    from bluefog_tpu.ops import pallas_decode as _pd
    from bluefog_tpu.serve import kv_cache as _kv

    L, n_rows = scfg.max_len, scfg.slots + 1
    rng = np.random.default_rng(0)
    contexts = sorted({max(1, L // 4), max(1, L // 2), L})
    lanes = sorted({1, max(1, scfg.slots // 2), scfg.slots})
    dtypes = ["raw"] + ([scfg.kv_dtype] if scfg.kv_dtype != "raw" else [])

    def flash_fn(q, kl, vl, slots, lens, ksc, vsc):
        return _pd.flash_attend_rows(q, kl, vl, slots, lens,
                                     k_scale=ksc, v_scale=vsc,
                                     block_k=block_k)

    def xla_fn(q, kl, vl, slots, lens, ksc, vsc):
        return _kv.attend_rows(q, kl, vl, slots, lens,
                               k_scale=ksc, v_scale=vsc)

    fns = {"xla": jax.jit(xla_fn)}
    if kernel == "pallas":
        fns["pallas"] = jax.jit(flash_fn)

    def _time(fn, args):
        fn(*args).block_until_ready()           # compile
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        out.block_until_ready()
        return (time.perf_counter() - t0) / iters

    rows = []
    for store in dtypes:
        kraw = jnp.asarray(
            rng.normal(size=(n_rows, heads, L, head_dim)), jnp.float32)
        vraw = jnp.asarray(
            rng.normal(size=(n_rows, heads, L, head_dim)), jnp.float32)
        if store == "raw":
            kl, vl, ksc, vsc = kraw, vraw, None, None
        else:
            kl, ksc = _kv.quantize_rows(kraw, store)
            vl, vsc = _kv.quantize_rows(vraw, store)
        for ctx in contexts:
            for S in lanes:
                q = jnp.asarray(
                    rng.normal(size=(S, heads, head_dim)), jnp.float32)
                slots = jnp.arange(S, dtype=jnp.int32)
                lens = jnp.full((S,), ctx - 1, jnp.int32)
                args = (q, kl, vl, slots, lens, ksc, vsc)
                walls = {name: _time(fn, args) for name, fn in fns.items()}
                flops = 4.0 * S * heads * head_dim * ctx
                wall = walls.get("pallas", walls["xla"])
                rows.append({
                    "kv_dtype": store,
                    "context": int(ctx),
                    "lanes": int(S),
                    "wall_us": round(wall * 1e6, 2),
                    "xla_wall_us": round(walls["xla"] * 1e6, 2),
                    "attn_flops": flops,
                    "flops_per_sec": round(flops / wall, 1) if wall else None,
                    "mfu": (round(flops / wall / peak, 8)
                            if on_tpu and peak and wall else None),
                })
    return rows


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name + "_mod", os.path.join(REPO, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--virtual-cpu", action="store_true",
                    help="virtual CPU mesh sized (train_dp+serve_dp)*pp*tp"
                         "*ep (smoke/tests)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for CI (implies quick compile)")
    ap.add_argument("--train-dp", type=int, default=2,
                    help="training gossip-DP replicas")
    ap.add_argument("--serve-dp", type=int, default=2,
                    help="serving replicas (engine gossip-DP axis)")
    ap.add_argument("--pp", type=int, default=1, help="pipeline stages")
    ap.add_argument("--tp", type=int, default=1, help="tensor-parallel ways")
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--d-model", type=int, default=None)
    ap.add_argument("--heads", type=int, default=None)
    ap.add_argument("--vocab", type=int, default=None)
    ap.add_argument("--requests", type=int, default=None,
                    help="concurrent requests to drain (default 16)")
    ap.add_argument("--max-new", type=int, default=None,
                    help="tokens generated per request (default 8)")
    ap.add_argument("--buckets", default=None,
                    help="'<batch,..>@<prompt_len,..>' serve shape buckets "
                         "(default from BLUEFOG_SERVE_BUCKETS or 1,2,4@8,16)")
    ap.add_argument("--slots", type=int, default=None,
                    help="KV slots per replica (default 8)")
    ap.add_argument("--max-len", type=int, default=None,
                    help="KV rows per slot (default 64)")
    ap.add_argument("--decode-steps-per-call", type=int, default=None,
                    help="fused decode steps per engine call (default 2)")
    ap.add_argument("--spec-decode", default=None,
                    help="self-speculative decoding: '<k>' or '<k>@<stages>'"
                         " draft depth / draft pipeline stages (default off)")
    ap.add_argument("--kv-dtype", default=None,
                    choices=("raw", "int8", "fp8"),
                    help="KV page storage (default raw)")
    ap.add_argument("--prefix-pages", default=None,
                    help="shared prefix pages: '<pages>' or "
                         "'<pages>x<page_tokens>' (default off)")
    ap.add_argument("--decode-kernel", default=None,
                    help="decode-attention backend: 'xla' or 'pallas' or "
                         "'pallas@<block_k>' (schema 4 row; default xla)")
    ap.add_argument("--serve-moe", default=None,
                    help="MoE estate: '<experts>[x<top_k>][@<ep>][:<tile>]'"
                         " e.g. '4x2@2:4' — dropless routed MoE trained and"
                         " served on ep-carved meshes (schema 5 row; "
                         "default BLUEFOG_SERVE_MOE or dense)")
    ap.add_argument("--traffic-trace", default=None,
                    choices=("diurnal", "flash-crowd"),
                    help="bursty traffic phase with a parked reserve "
                         "replica + SLO-driven autoscaling (schema 3 row)")
    ap.add_argument("--trace-steps", type=int, default=None,
                    help="scheduler steps in the traffic trace (default 24)")
    ap.add_argument("--slo-p99-ms", type=float, default=None,
                    help="autoscaler p99 SLO (default BLUEFOG_SLO_P99_MS "
                         "or 250)")
    ap.add_argument("--train-steps", type=int, default=None,
                    help="train steps interleaved with serving (default 6)")
    ap.add_argument("--refresh-every", type=int, default=None,
                    help="pull fresh weights every N train steps "
                         "(default from BLUEFOG_REFRESH_EVERY or 2)")
    ap.add_argument("--out", default=None, help="json artifact path")
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args()

    if args.serve_moe is None:
        args.serve_moe = os.environ.get("BLUEFOG_SERVE_MOE") or None
    # ep widens the slice, so it must enter the chip math before jax
    # initializes; only the @ep token is read here — the full grammar is
    # validated by engine._parse_serve_moe once the libraries are up
    moe_ep = 1
    if args.serve_moe:
        ep_s = args.serve_moe.partition(":")[0].partition("@")[2]
        if ep_s.isdigit():
            moe_ep = int(ep_s)
    n_chips = (args.train_dp + args.serve_dp) * args.pp * args.tp * moe_ep
    if args.virtual_cpu:
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count="
                f"{n_chips}").strip()
    import jax
    if args.virtual_cpu:
        jax.config.update("jax_platforms", "cpu")

    # bf.init places the libtpu flags and the compile cache when the
    # devices are TPUs; the carving below takes its own device slices
    import bluefog_tpu as bf
    bf.init(platform="cpu" if args.virtual_cpu else None)

    dev = jax.devices()[0]
    on_tpu = jax.default_backend() == "tpu"
    if dev.platform == "cpu" and not (args.virtual_cpu or args.allow_cpu):
        print("refusing: no accelerator (pass --virtual-cpu or --allow-cpu)",
              file=sys.stderr)
        sys.exit(2)
    if len(jax.devices()) < n_chips:
        raise SystemExit(
            f"need {n_chips} devices for (train_dp+serve_dp)*pp*tp*ep, "
            f"have {len(jax.devices())}")

    smoke = args.smoke or (args.virtual_cpu and not on_tpu)
    layers = args.layers or (args.pp * (2 if smoke else 2))
    d_model = args.d_model or (32 if smoke else 1024)
    heads = args.heads or (4 if smoke else 16)
    vocab = args.vocab or (64 if smoke else 32768)
    n_requests = args.requests or 16
    max_new = args.max_new or 8
    slots = args.slots or 8
    max_len = args.max_len or 64
    steps_per_call = args.decode_steps_per_call or 2
    train_steps = args.train_steps if args.train_steps is not None else 6
    refresh_every = args.refresh_every
    if refresh_every is None and smoke and "BLUEFOG_REFRESH_EVERY" not in \
            os.environ:
        refresh_every = 2

    import numpy as np
    import optax
    import bluefog_tpu.optimizers as bfopt
    from bluefog_tpu.parallel import compose
    from bluefog_tpu.serve import (ServeConfig, ServeEngine, Scheduler,
                                   WeightRefresher)
    from bluefog_tpu.serve.engine import _parse_buckets
    from bluefog_tpu.utils import metrics as bfm
    from bluefog_tpu.utils import tracing as _tracing

    # arm request tracing before any scheduler exists so every request in
    # the drain gets a span tree; the bundle feeds the latency-breakdown
    # block at the end (BLUEFOG_TRACE wins if the operator set it)
    trace_dir = os.environ.get(_tracing.ENV_TRACE) or tempfile.mkdtemp(
        prefix="bftrace_")
    _tracing.configure(trace_dir)

    devs = jax.devices()
    slice_sz = args.pp * args.tp * moe_ep
    train_devs = devs[:args.train_dp * slice_sz]
    serve_devs = devs[args.train_dp * slice_sz:n_chips]

    lm_kw = dict(vocab=vocab, d_model=d_model, heads=heads, layers=layers,
                 seq_len=32 if smoke else 128, micro=max(2 * args.pp, 2))
    if args.serve_moe:
        from bluefog_tpu.moe.model import (MoELMConfig, init_moe_params,
                                           make_moe_batch, make_moe_grad_fn)
        from bluefog_tpu.serve.engine import _parse_serve_moe
        moe_E, moe_k, moe_ep_full, moe_tile = _parse_serve_moe(
            args.serve_moe)
        if moe_ep_full != moe_ep:
            raise SystemExit(f"--serve-moe ep token {moe_ep_full} did not "
                             f"survive the chip-math pre-parse ({moe_ep})")
        cfg = MoELMConfig(batch=2 * moe_ep, num_experts=moe_E, top_k=moe_k,
                          dispatch="dropless", **lm_kw)
        train_m = compose.compose_parallelism(
            args.train_dp, args.pp, args.tp, 1, moe_ep, devices=train_devs,
            num_experts=moe_E)
        serve_m = compose.compose_parallelism(
            args.serve_dp, args.pp, args.tp, 1, moe_ep, devices=serve_devs,
            num_experts=moe_E)
    else:
        cfg = compose.LMConfig(batch=2, **lm_kw)
        train_m = compose.compose_parallelism(
            args.train_dp, args.pp, args.tp, 1, devices=train_devs)
        serve_m = compose.compose_parallelism(
            args.serve_dp, args.pp, args.tp, 1, devices=serve_devs)
    cfg.validate(train_m)

    sc_kw = dict(slots=slots, max_len=max_len,
                 decode_steps_per_call=steps_per_call)
    if args.spec_decode:
        k_s, _, st_s = args.spec_decode.partition("@")
        sc_kw["spec_decode"] = int(k_s)
        if st_s:
            sc_kw["spec_stages"] = int(st_s)
    if args.kv_dtype:
        sc_kw["kv_dtype"] = args.kv_dtype
    if args.decode_kernel:
        kern, _, bk_s = args.decode_kernel.partition("@")
        sc_kw["decode_kernel"] = kern       # ServeConfig validates the token
        if bk_s:
            sc_kw["decode_block_k"] = int(bk_s)
    if args.prefix_pages:
        pg_s, _, pt_s = args.prefix_pages.partition("x")
        sc_kw["prefix_pages"] = int(pg_s)
        if pt_s:
            sc_kw["prefix_page_tokens"] = int(pt_s)
    if args.serve_moe:
        sc_kw.update(moe_experts=moe_E, moe_top_k=moe_k, moe_ep=moe_ep,
                     moe_tile=moe_tile)
    if args.buckets:
        bb, pb = _parse_buckets(args.buckets)
        scfg = ServeConfig(batch_buckets=bb, prefill_buckets=pb, **sc_kw)
    else:
        scfg = ServeConfig.from_env(**sc_kw)

    # -- training fleet -----------------------------------------------------
    if args.serve_moe:
        grad_fn = make_moe_grad_fn(cfg, train_m)
        train_params = init_moe_params(cfg, train_m, seed=1)
        toks = make_moe_batch(cfg, train_m)
    else:
        grad_fn = compose.make_lm_grad_fn(cfg, train_m)
        train_params = compose.init_lm_params(cfg, train_m, seed=1)
        toks = compose.make_lm_batch(cfg, train_m)
    step, strategy = compose.make_train_step(
        train_m, grad_fn, optax.adam(5e-3))
    state = bfopt.init_distributed(strategy, train_params)
    train_params = compose.device_put(train_m, train_params)

    # -- serving fleet ------------------------------------------------------
    serve_params = (init_moe_params(cfg, serve_m, seed=0) if args.serve_moe
                    else compose.init_lm_params(cfg, serve_m, seed=0))
    engine = ServeEngine(serve_m, cfg, serve_params, scfg)
    engine.warmup()

    rng = np.random.default_rng(0)

    def _drain_tokens(eng, prompts):
        """Drain ``prompts`` through a throwaway scheduler; per-request
        token streams (probe harness — closed before the traffic run)."""
        s = Scheduler(eng)
        reqs = [s.submit(p, max_new_tokens=max_new) for p in prompts]
        s.drain()
        s.close()
        return reqs

    # probe (a): speculative bit-identity — the same prompts through a
    # plain-greedy reference engine must produce identical token streams
    spec_probe = None
    if scfg.spec_decode:
        probe_prompts = [rng.integers(0, vocab, int(rng.integers(
            2, scfg.prefill_buckets[-1] + 1))).tolist() for _ in range(3)]
        ref_eng = ServeEngine(serve_m, cfg, serve_params,
                              dataclasses.replace(scfg, spec_decode=0))
        ref_eng.warmup()
        ref = [r.generated for r in _drain_tokens(ref_eng, probe_prompts)]
        got = [r.generated for r in _drain_tokens(engine, probe_prompts)]
        spec_probe = {"prompts": len(probe_prompts),
                      "bit_identical": bool(ref == got)}
        del ref_eng

    # probe (b): prefix-hit TTFT — an identical prompt submitted twice;
    # the first seals the shared page (cold), the second attaches (hit)
    prefix_probe = None
    if scfg.prefix_pages:
        ptoks = scfg.prefix_page_tokens
        shared = rng.integers(0, vocab, ptoks).tolist()
        probe_prompt = shared + rng.integers(
            0, vocab, max(1, min(4, scfg.prefill_buckets[-1] - ptoks))
        ).tolist()
        cold = _drain_tokens(engine, [probe_prompt])[0]
        hit = _drain_tokens(engine, [probe_prompt])[0]
        prefix_probe = {
            "ttft_cold_s": round(cold.ttft, 6),
            "ttft_hit_s": round(hit.ttft, 6),
            "hit_prefix_len": hit.prefix_len,
            "hit_faster": bool(hit.ttft < cold.ttft),
            "tokens_identical": bool(cold.generated == hit.generated)}
    else:
        shared = None

    # probe (c): flash-decode bit-identity — the pallas-kernel engine must
    # emit the same greedy token streams as the XLA gather-then-attend path
    flash_probe = None
    if scfg.decode_kernel == "pallas":
        probe_prompts = [rng.integers(0, vocab, int(rng.integers(
            2, scfg.prefill_buckets[-1] + 1))).tolist() for _ in range(3)]
        ref_eng = ServeEngine(serve_m, cfg, serve_params,
                              dataclasses.replace(scfg, decode_kernel="xla"))
        ref_eng.warmup()
        ref = [r.generated for r in _drain_tokens(ref_eng, probe_prompts)]
        got = [r.generated for r in _drain_tokens(engine, probe_prompts)]
        flash_probe = {"prompts": len(probe_prompts),
                       "bit_identical": bool(ref == got)}
        del ref_eng

    # probe (d), schema 5: MoE serving — greedy bit-identity through the
    # speculative path, the dense twin at equal ACTIVE params timed on
    # the same prompts, and the AOT wire split of the fused decode
    moe_probe = None
    if args.serve_moe:
        from bluefog_tpu.utils.hlo_bytes import stablehlo_wire_stats

        def _timed_tps(eng, prompts):
            before = bfm.counter("bluefog_tokens_generated_total").total()
            w0 = time.perf_counter()
            _drain_tokens(eng, prompts)
            wall = time.perf_counter() - w0
            made = bfm.counter(
                "bluefog_tokens_generated_total").total() - before
            return (made / wall) if wall > 0 else None

        moe_prompts = [rng.integers(0, vocab, int(rng.integers(
            2, scfg.prefill_buckets[-1] + 1))).tolist() for _ in range(8)]
        if spec_probe is not None:
            bit = dict(spec_probe)          # spec-MoE vs plain-greedy-MoE
        else:
            spec_eng = ServeEngine(
                serve_m, cfg, serve_params,
                dataclasses.replace(scfg, spec_decode=2, spec_stages=1))
            spec_eng.warmup()
            got = [r.generated
                   for r in _drain_tokens(spec_eng, moe_prompts[:3])]
            ref = [r.generated
                   for r in _drain_tokens(engine, moe_prompts[:3])]
            bit = {"prompts": 3, "bit_identical": bool(ref == got)}
            del spec_eng
        # the fair baseline: same skeleton, ffn_mult scaled by top_k —
        # equal FLOPs per token, 1/E-th the FFN capacity per chip set
        dense_cfg = cfg.dense_twin()
        dense_m = compose.compose_parallelism(
            args.serve_dp, args.pp, args.tp, 1,
            devices=serve_devs[:args.serve_dp * args.pp * args.tp])
        dense_eng = ServeEngine(
            dense_m, dense_cfg,
            compose.init_lm_params(dense_cfg, dense_m, seed=0),
            dataclasses.replace(scfg, moe_experts=0, moe_top_k=1,
                                moe_ep=1, moe_tile=0))
        dense_eng.warmup()
        moe_tps = _timed_tps(engine, moe_prompts)
        dense_tps = _timed_tps(dense_eng, moe_prompts)
        del dense_eng
        moe_probe = {
            "bit": bit, "tps_moe": moe_tps, "tps_dense": dense_tps,
            "dense_n_params": dense_cfg.n_params,
            "wire": stablehlo_wire_stats(engine.decode_lowered_text(),
                                         serve_m.slice_size),
        }

    refresher = WeightRefresher(engine, train_m, every=refresh_every)
    sched = Scheduler(engine)
    cache_probe = engine.cache["k"]       # donated into the first decode

    spec0 = {n: bfm.counter(n).total() for n in
             ("bluefog_serve_spec_drafted_total",
              "bluefog_serve_spec_accepted_total")}
    hitmiss0 = {n: bfm.counter(n).total() for n in
                ("bluefog_serve_prefix_hits_total",
                 "bluefog_serve_prefix_misses_total")}
    tokens0 = bfm.counter("bluefog_tokens_generated_total").total()

    prompt_lens = []
    for i in range(n_requests):
        if shared is not None and i % 2 == 0:
            # the million-user shape: half the traffic reuses one system
            # prompt — its page seals once per replica and then every
            # admission is a remainder-only chunk prefill
            room = scfg.prefill_buckets[-1] - len(shared)
            p = shared + rng.integers(
                0, vocab, int(rng.integers(1, room + 1))).tolist()
        else:
            n = int(rng.integers(2, scfg.prefill_buckets[-1] + 1))
            p = rng.integers(0, vocab, n).tolist()
        prompt_lens.append(len(p))
        sched.submit(p, max_new_tokens=max_new)

    # -- interleaved drain: serve steps with training advancing live --------
    stal_max, pulls, train_done = 0.0, 0, 0
    t0 = time.perf_counter()
    guard = 0
    while not sched.done:
        guard += 1
        if guard > 100_000:
            raise RuntimeError("scheduler failed to drain")
        sched.step()
        if train_done < train_steps:
            train_params, state, _ = step(train_params, state, toks)
            train_done += 1
            refresher.note_train_step(train_done)
            stal_max = max(stal_max, refresher.staleness() or 0.0)
            if refresher.maybe_refresh(train_params, train_done):
                pulls += 1
    dt = time.perf_counter() - t0
    stal_final = refresher.staleness()

    # probes above generate tokens too — tokens/s uses the timed-drain delta
    tokens = int(bfm.counter("bluefog_tokens_generated_total").total()
                 - tokens0)
    tok_per_sec = tokens / dt if dt > 0 else None

    # -- bursty traffic + autoscaling phase (schema 3) ----------------------
    trace_doc = None
    if args.traffic_trace:
        trace_doc = _run_traffic_trace(
            engine, args.traffic_trace, steps=args.trace_steps or 24,
            vocab=vocab, max_new=max_new, rng=rng,
            slo_p99_ms=args.slo_p99_ms)

    lat = bfm.get_metric("bluefog_serve_token_latency_seconds")
    ttfts = sorted(r.ttft for r in sched.completed if r.ttft is not None)

    # decode FLOPs/token: forward weight term + the exact attention
    # context each generated token attended over (score + value matmuls)
    n_tok, ctx_sum = 0, 0
    for req in sched.completed:
        p = len(req.prompt)
        for i in range(len(req.generated)):
            n_tok += 1
            ctx_sum += p + i
    avg_ctx = (ctx_sum / n_tok) if n_tok else 0.0
    # MoE: the weight term counts ACTIVE params only — a decoded token
    # touches its top-k experts, not the full table
    n_weight = getattr(cfg, "n_active_params", cfg.n_params)
    decode_flops_per_token = (2.0 * n_weight
                              + 4.0 * cfg.layers * cfg.d_model * avg_ctx)
    bench = _load_tool("bench")
    peak = bench._peak_flops(dev.device_kind) if on_tpu else None
    serve_chips = args.serve_dp * slice_sz

    retraces = int(bfm.counter("bluefog_retrace_after_warmup_total").total())

    # -- fast-path rows (schema 2) ------------------------------------------
    spec_doc = None
    if scfg.spec_decode:
        drafted = int(bfm.counter("bluefog_serve_spec_drafted_total").total()
                      - spec0["bluefog_serve_spec_drafted_total"])
        accepted = int(
            bfm.counter("bluefog_serve_spec_accepted_total").total()
            - spec0["bluefog_serve_spec_accepted_total"])
        spec_doc = {
            "k": scfg.spec_decode,
            "stages": scfg.spec_stages,
            "cost_fraction": round(engine.draft.cost_fraction, 4),
            "drafted": drafted,
            "accepted": accepted,
            "acceptance_rate": (round(accepted / drafted, 4)
                                if drafted else None),
            "accepted_tokens_per_sec": (round(accepted / dt, 1)
                                        if dt > 0 else None),
            **spec_probe,
        }
    prefix_doc = None
    if scfg.prefix_pages:
        hits = int(bfm.counter("bluefog_serve_prefix_hits_total").total()
                   - hitmiss0["bluefog_serve_prefix_hits_total"])
        misses = int(bfm.counter("bluefog_serve_prefix_misses_total").total()
                     - hitmiss0["bluefog_serve_prefix_misses_total"])
        prefix_doc = {
            "pages": scfg.prefix_pages,
            "page_tokens": scfg.prefix_page_tokens,
            "hits": hits,
            "misses": misses,
            **prefix_probe,
        }
    kv_doc = None
    if engine.cache_cfg.quantized:
        bpt = engine.cache_cfg.bytes_per_token()
        raw_bpt = dataclasses.replace(
            engine.cache_cfg, store="raw").bytes_per_token()
        kv_doc = {
            "dtype": scfg.kv_dtype,
            "bytes_per_token": bpt,
            "raw_bytes_per_token": raw_bpt,
            "ratio": round(bpt / raw_bpt, 4),
        }

    # -- flash-decode rows (schema 4) ----------------------------------------
    decode_doc = None
    if scfg.decode_kernel == "pallas":
        decode_doc = {
            "kernel": scfg.decode_kernel,
            "block_k": scfg.decode_block_k,
            **flash_probe,
            "attend": _decode_attend_bench(
                scfg, heads, d_model // heads, kernel=scfg.decode_kernel,
                block_k=scfg.decode_block_k, on_tpu=on_tpu, peak=peak,
                iters=3 if smoke else 20),
        }

    # -- MoE serving rows (schema 5) -----------------------------------------
    moe_doc = None
    if moe_probe is not None:
        ws = moe_probe["wire"]
        a2a_ici = ws["ici"].get("all_to_all", {"count": 0, "bytes": 0})
        a2a_dcn = ws["dcn"].get("all_to_all", {"count": 0, "bytes": 0})
        live = [row for row in (engine.moe_load() or []) if row["tokens"]]
        hist = (np.mean([row["fractions"] for row in live], axis=0)
                if live else np.zeros(scfg.moe_experts))
        tps_m, tps_d = moe_probe["tps_moe"], moe_probe["tps_dense"]
        moe_doc = {
            "experts": scfg.moe_experts,
            "top_k": scfg.moe_top_k,
            "ep": scfg.moe_ep,
            "tile": engine._moe_tile,
            "n_params_total": cfg.n_params,
            "n_params_active": cfg.n_active_params,
            "dense_twin_n_params": moe_probe["dense_n_params"],
            "tokens_per_sec_moe": round(tps_m, 1) if tps_m else None,
            "tokens_per_sec_dense_twin": (round(tps_d, 1)
                                          if tps_d else None),
            "vs_dense_equal_active": (round(tps_m / tps_d, 4)
                                      if tps_m and tps_d else None),
            "serve_chips_moe": args.serve_dp * slice_sz,
            "serve_chips_dense_twin": args.serve_dp * args.pp * args.tp,
            "bit_identity": moe_probe["bit"],
            "router_entropy_mean": (round(float(np.mean(
                [row["entropy"] for row in live])), 4) if live else None),
            "hot_expert": {
                "counts": [int(c) for c in (np.sum(
                    [row["counts"] for row in live], axis=0) if live
                    else np.zeros(scfg.moe_experts))],
                "fractions": [round(float(f), 4) for f in hist],
                "max_fraction": (round(float(hist.max()), 4)
                                 if len(hist) else None),
            },
            "wire": {
                "per_chip_ici_bytes": ws["ici_bytes"],
                "per_chip_dcn_bytes": ws["dcn_bytes"],
                "all_to_all_ici": a2a_ici,
                "all_to_all_dcn": a2a_dcn,
            },
        }

    # -- per-request latency breakdown from the tracer ----------------------
    breakdown_doc = None
    bundle = _tracing.flush()
    if bundle:
        tr = _load_tool("tools/trace_report")
        tr_doc, _ = tr.report_from_files([bundle])
        reqs_tr = tr_doc["requests"]
        if reqs_tr:
            def _mean(key):
                return round(sum(v[key] for v in reqs_tr.values())
                             / len(reqs_tr), 6)
            breakdown_doc = {
                "n_requests": len(reqs_tr),
                "queue_mean_s": _mean("queue_s"),
                "prefill_mean_s": _mean("prefill_s"),
                "decode_mean_s": _mean("decode_s"),
                "gap_mean_s": _mean("gap_s"),
                "slowest": [[t, round(total, 6)] for t, total, *_ in
                            tr_doc["critical_path"][:5]],
                "bundle": bundle,
            }

    doc = {
        "schema": SCHEMA,
        "ok": True,
        "on_accelerator": on_tpu,
        "device": dev.device_kind,
        "serve": {"replicas": args.serve_dp, "pp": args.pp, "tp": args.tp,
                  "slots": slots, "max_len": max_len,
                  "decode_steps_per_call": steps_per_call,
                  "batch_buckets": list(scfg.batch_buckets),
                  "prefill_buckets": list(scfg.prefill_buckets),
                  "kv_dtype": scfg.kv_dtype,
                  "kv_cache_bytes": engine.cache_cfg.bytes(),
                  "kv_bytes_per_token": engine.cache_cfg.bytes_per_token()},
        "train": {"replicas": args.train_dp, "steps": train_done},
        "config": {"d_model": d_model, "heads": heads, "layers": layers,
                   "vocab": vocab, "n_params": cfg.n_params},
        "requests": {"submitted": n_requests,
                     "completed": len(sched.completed),
                     "failed": len(sched.failed),
                     "max_new_tokens": max_new,
                     "tokens_generated": tokens,
                     "avg_prompt_len": round(float(np.mean(prompt_lens)), 2)},
        "wall_s": round(dt, 4),
        "tokens_per_sec": round(tok_per_sec, 1) if tok_per_sec else None,
        "latency": {
            "per_token_p50_s": (round(lat.percentile(0.5), 6)
                                if lat is not None else None),
            "per_token_p99_s": (round(lat.percentile(0.99), 6)
                                if lat is not None else None),
            "ttft_p50_s": (round(ttfts[len(ttfts) // 2], 6)
                           if ttfts else None),
            "ttft_max_s": round(ttfts[-1], 6) if ttfts else None,
        },
        "mfu": {"decode_flops_per_token": round(decode_flops_per_token, 1),
                "avg_context": round(avg_ctx, 1),
                "model_flops_per_sec": (
                    round(tok_per_sec * decode_flops_per_token, 1)
                    if tok_per_sec else None),
                "peak_flops_per_chip": peak,
                "mfu": (round(tok_per_sec * decode_flops_per_token
                              / (peak * serve_chips), 6)
                        if peak and tok_per_sec else None)},
        "refresh": {"every": refresher.every, "pulls": pulls,
                    "staleness_max_steps": stal_max,
                    "staleness_final_steps": stal_final},
        "spec": spec_doc,
        "prefix": prefix_doc,
        "kv": kv_doc,
        "decode": decode_doc,
        "moe": moe_doc,
        "trace": trace_doc,
        "latency_breakdown": breakdown_doc,
        "invariants": {
            "donation_intact": bool(cache_probe.is_deleted()),
            "retraces_after_warmup": retraces,
        },
    }
    fast_ok = True
    if spec_doc is not None:
        fast_ok &= spec_doc["bit_identical"]
    if prefix_doc is not None:
        fast_ok &= bool(prefix_doc["hit_faster"]
                        and prefix_doc["tokens_identical"]
                        and prefix_doc["hits"] >= 1)
    if kv_doc is not None and scfg.kv_dtype == "int8":
        fast_ok &= kv_doc["ratio"] <= 0.5
    if decode_doc is not None:
        fast_ok &= decode_doc["bit_identical"]
    if moe_doc is not None:
        # the ISSUE 19 gate: spec-vs-greedy token identity, a measured
        # dense-twin comparison, and dispatch/combine a2a traffic that is
        # entirely intra-slice (ICI) — any DCN all_to_all fails the run
        fast_ok &= bool(moe_doc["bit_identity"]["bit_identical"]
                        and moe_doc["tokens_per_sec_moe"]
                        and moe_doc["tokens_per_sec_dense_twin"]
                        and moe_doc["wire"]["all_to_all_ici"]["count"] >= 1
                        and moe_doc["wire"]["all_to_all_dcn"]["count"] == 0)
    doc["ok"] = bool(len(sched.completed) == n_requests
                     and doc["invariants"]["donation_intact"]
                     and retraces == 0
                     and fast_ok
                     and (trace_doc is None or trace_doc["ok"])
                     and (train_steps == 0 or pulls >= 1))
    sched.close()
    _emit(doc, args.out)


def _emit(doc, out):
    if out:
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        with open(out, "w") as f:
            json.dump(doc, f, indent=1)
    print(json.dumps(doc))


if __name__ == "__main__":
    main()
