"""Trusted roofline: measured MXU FLOP/s and HBM GB/s with tripwires.

A NAIVE measured peak misleads: an earlier calibration reported a
"641 TF/s" matmul on a 197 TF/s chip because XLA's algebraic simplifier
rewrote the splat-operand matmul into an O(n^2) column reduction that
never touched the MXU.  A measured ceiling is only a sanity row beside the
published peak if the measurement DEMONSTRABLY exercised the unit it
claims to measure.

This tool produces that ceiling.  Every MXU probe must pass three
tripwires before it is marked ``trusted``:

  1. structural — the optimized HLO of the timed program must contain a
     real dot/GEMM op (``assert_real_dot``): if the simplifier folded the
     operand away, the probe is rejected BEFORE it is timed;
  2. rate bound — the achieved FLOP/s must not exceed the spec peak
     (``check_rate_bound``): above-spec throughput always means a broken
     measurement (folded body or a sync barrier that returned at
     dispatch), never an overachieving chip;
  3. scaling — with two sizes, time(2n)/time(n) must look O(n^3)
     (~8x, threshold 4x): folding flattens the curve even when the
     absolute rate sneaks under the peak.

The MFU denominators in bench.py are the published peaks; this tool's
rows say whether the chip reaches them.  Not yet run on a chip.

The HBM probe is chunked and dispatch-corrected: per-call time for a
large read+write body, minus the measured per-call dispatch overhead of
an 8-element body, alongside the one-dispatch ``lax.scan`` gold number
(per-dispatch latency charged to every copy understates the bandwidth).

Operands are random ROW-STOCHASTIC matrices (rows sum to 1): the scan
carry stays O(1) across chained matmuls, and unlike a ``jnp.full(1/n)``
splat there is no broadcast-of-scalar for the simplifier to rewrite.

Run:  python tools/roofline.py [--out PATH]     (on the chip)
      python tools/roofline.py --smoke          (tiny shapes, any backend)
Prints ONE JSON document; ``--out`` also writes it atomically.
Exit code is non-zero when a non-smoke run yields NO trusted MXU probe.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))

# markers that the timed program really multiplies matrices: plain HLO dot,
# or the backend GEMM custom-calls it may lower to (cuBLAS/oneDNN/Mosaic)
DOT_MARKERS = (" dot(", " dot.", "= dot(", "custom_call_target=\"__onednn",
               "custom_call_target=\"__cublas", "cublas$gemm", "$gemm",
               "tpu_custom_call", "dot_general")


class RooflineError(RuntimeError):
    """A roofline tripwire fired: the measurement cannot be trusted."""


def assert_real_dot(hlo_text: str) -> None:
    """Structural tripwire: the optimized HLO must still contain a dot.

    Raises :class:`RooflineError` when no dot/GEMM marker survives
    compilation — i.e. XLA folded the operand (splat rewrite, constant
    propagation) and the timed program would measure something other
    than the MXU."""
    if not isinstance(hlo_text, str) or not hlo_text:
        raise RooflineError("empty HLO: nothing was compiled")
    low = hlo_text.lower()
    if not any(m.lower() in low for m in DOT_MARKERS):
        raise RooflineError(
            "no dot/GEMM op in the optimized HLO: XLA folded the matmul "
            "(splat operand or constant propagation) — the probe would "
            "time a reduction, not the MXU")


def check_rate_bound(flops_per_sec: float, peak_flops) -> None:
    """Rate tripwire: measured FLOP/s above the spec peak is impossible.

    Raises :class:`RooflineError` when ``flops_per_sec`` exceeds
    ``peak_flops`` (None disables the check — unknown device kind)."""
    if flops_per_sec <= 0:
        raise RooflineError(f"non-positive FLOP rate {flops_per_sec!r}")
    if peak_flops and flops_per_sec > peak_flops:
        raise RooflineError(
            f"{flops_per_sec / 1e12:.1f} TF/s exceeds the "
            f"{peak_flops / 1e12:.0f} TF/s spec peak: the operand was "
            "folded or the sync barrier returned at dispatch")


def _bench_mod():
    import bench
    return bench


def _row_stochastic(n: int, seed: int = 0):
    """Random row-stochastic [n, n] bf16 operand (rows sum to 1)."""
    import jax
    import jax.numpy as jnp
    a = jax.random.uniform(jax.random.key(seed or n), (n, n), jnp.float32,
                           0.5, 1.5)
    return (a / a.sum(axis=1, keepdims=True)).astype(jnp.bfloat16)


def _scan_fn(body, iters):
    import jax
    from jax import lax
    return jax.jit(lambda x0: lax.scan(
        lambda c, _: (body(c), None), x0, None, length=iters)[0])


def _timed(hard_sync, f, x):
    t0 = time.perf_counter()
    hard_sync(f(x))
    return time.perf_counter() - t0


def _dispatch_overhead_s(hard_sync, iters: int) -> float:
    """Per-call host->device dispatch overhead, from an 8-element body
    whose device time is negligible next to the launch cost."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda y: y * 1.0001)
    y = hard_sync(f(jnp.ones((8,), jnp.float32)))
    t0 = time.perf_counter()
    for _ in range(iters):
        y = f(y)
    hard_sync(y)
    return (time.perf_counter() - t0) / iters


def mxu_probe(n: int, iters: int, hard_sync, spec_peak) -> dict:
    """One tripwired MXU calibration at size ``n``.

    Returns a row with ``flops_per_sec`` and ``trusted``/``suspect``
    flags; tripwire failures are recorded in the row (``suspect`` +
    ``note``) rather than raised, so one bad size cannot abort the
    other sizes."""
    a = _row_stochastic(n)
    f = _scan_fn(lambda c: a @ c, iters)
    row = {"probe": f"mxu_bf16_{n}", "n": n, "iters": iters,
           "trusted": False, "suspect": False,
           "spec_peak_tflops": round(spec_peak / 1e12, 1)
           if spec_peak else None}
    try:
        compiled = f.lower(a).compile()
        assert_real_dot(compiled.as_text())
    except RooflineError as e:
        row.update(suspect=True, note=f"structural tripwire: {e}")
        return row
    hard_sync(compiled(a))                        # warm
    per_iter = _timed(hard_sync, compiled, a) / iters
    flops = 2.0 * n ** 3 / per_iter
    row.update(ms=round(per_iter * 1e3, 3),
               flops_per_sec=flops, tflops=round(flops / 1e12, 1))
    try:
        check_rate_bound(flops, spec_peak)
    except RooflineError as e:
        row.update(suspect=True, note=f"rate tripwire: {e}")
        return row
    if spec_peak is None:
        row["note"] = ("unknown device kind: above-peak check skipped, "
                       "trust rests on the structural tripwire alone")
    row["trusted"] = True
    return row


def apply_scaling_tripwire(rows: list) -> None:
    """Cross-size O(n^3) check over the trusted MXU rows, in place.

    time(2n)/time(n) under 4x (expected ~8x) demotes BOTH rows: a
    flattened curve means folding or an early-return barrier even when
    the absolute rates sit under the spec peak."""
    timed = [r for r in rows if "ms" in r]
    if len(timed) < 2:
        return
    lo, hi = min(timed, key=lambda r: r["n"]), max(timed, key=lambda r: r["n"])
    if hi["n"] != 2 * lo["n"]:
        return
    ratio = hi["ms"] / max(lo["ms"], 1e-9)
    if ratio < 4.0:
        msg = (f"scaling tripwire: time({hi['n']})/time({lo['n']}) = "
               f"{ratio:.2f}x, expected ~8x for O(n^3) — folding or "
               "early-return barrier")
        for r in (lo, hi):
            r["trusted"] = False
            r["suspect"] = True
            r["note"] = (r["note"] + "; " + msg) if r.get("note") else msg


def hbm_probe(size: int, iters: int, hard_sync, overhead_s: float,
              spec_gbps) -> dict:
    """Chunked, dispatch-corrected HBM read+write bandwidth at ``size``
    f32 elements."""
    import jax
    import jax.numpy as jnp
    x = jnp.ones((size,), jnp.float32)
    bytes_per_iter = 2 * 4 * size                  # read + write, f32
    scanned = _scan_fn(lambda y: y * 1.0001, iters)
    hard_sync(scanned(x))                          # compile + warm
    per_scan = _timed(hard_sync, scanned, x) / iters
    g = jax.jit(lambda y: y * 1.0001)
    y = hard_sync(g(x))
    t0 = time.perf_counter()
    for _ in range(iters):
        y = g(y)
    hard_sync(y)
    per_call = (time.perf_counter() - t0) / iters
    corrected = max(per_call - overhead_s, 1e-12)
    gbps = bytes_per_iter / per_scan / 1e9
    row = {"probe": f"hbm_rw_{4 * size // 2 ** 20}MiB", "iters": iters,
           "gbps": round(gbps, 1),
           "per_dispatch_gbps": round(bytes_per_iter / per_call / 1e9, 1),
           "dispatch_corrected_gbps":
               round(bytes_per_iter / corrected / 1e9, 1),
           "dispatch_overhead_ms": round(overhead_s * 1e3, 3),
           "trusted": True, "suspect": False,
           "spec_peak_gbps": spec_gbps}
    # the scan number is the gold one; the corrected per-dispatch number
    # cross-checks it — a large residual gap means the overhead model is
    # wrong (e.g. transfers overlap the next dispatch) and the probe is
    # demoted rather than published as a ceiling
    if spec_gbps and gbps > spec_gbps:
        row.update(trusted=False, suspect=True,
                   note=f"{gbps:.0f} GB/s exceeds the {spec_gbps} GB/s "
                        "spec peak: broken barrier or folded body")
    return row


def run(smoke: bool = False, sizes=None, hbm_sizes=None,
        iters: int = None) -> dict:
    import jax
    if smoke:
        jax.config.update("jax_platforms", "cpu")
    from bluefog_tpu.api import hard_sync
    bench = _bench_mod()
    d = jax.devices()[0]
    # published peaks arm the above-spec tripwires; the CPU smoke has none
    spec_peak = None if smoke else bench._peak_flops(d.device_kind)
    spec_gbps = None if smoke else bench._peak_hbm_gbps(d.device_kind)
    # smoke uses ONE size: at CPU-smoke shapes the timing is dispatch-bound,
    # so the O(n^3) scaling tripwire would fire on every healthy run
    if sizes is None:
        sizes = (256,) if smoke else (4096, 8192)
    if hbm_sizes is None:
        hbm_sizes = (2 ** 18,) if smoke else (2 ** 27, 2 ** 28)
    if iters is None:
        iters = 4 if smoke else 50
    mxu = [mxu_probe(n, iters, hard_sync, spec_peak) for n in sizes]
    apply_scaling_tripwire(mxu)
    overhead = _dispatch_overhead_s(hard_sync, max(iters * 4, 16))
    hbm = [hbm_probe(s, iters, hard_sync, overhead, spec_gbps)
           for s in hbm_sizes]
    return {
        "ok": True,
        "device": d.device_kind,
        "platform": d.platform,
        "smoke": smoke,
        "mxu": mxu,
        "hbm": hbm,
        "utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes on whatever backend is attached")
    ap.add_argument("--out", default=None,
                    help="also write the JSON document here (atomic)")
    ap.add_argument("--sizes", default=None,
                    help="comma-separated MXU matmul sizes")
    ap.add_argument("--iters", type=int, default=None)
    args = ap.parse_args(argv)
    sizes = tuple(int(s) for s in args.sizes.split(",")) \
        if args.sizes else None
    doc = run(smoke=args.smoke, sizes=sizes, iters=args.iters)
    line = json.dumps(doc)
    print(line)
    if args.out:
        tmp = args.out + f".tmp{os.getpid()}"
        with open(tmp, "w") as f:
            f.write(line + "\n")
        os.replace(tmp, args.out)
    trusted = [r for r in doc["mxu"] if r.get("trusted")]
    if not trusted and not args.smoke:
        # fail LOUDLY: an all-suspect roofline is not a ceiling
        print("roofline: every MXU probe failed a tripwire — no trusted "
              "ceiling was measured", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
