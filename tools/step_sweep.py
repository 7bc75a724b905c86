"""Dispatch-amortization sweep + step trace for the headline benchmark.

Runs bench.py's exact measurement (``bench.run_bench``) at several
``steps_per_call`` values on the attached accelerator, showing how scanning
K optimizer steps into one compiled program amortizes the host->device
dispatch cost.  Optionally captures a profiler trace of the steady-state
step for compute/comm/host attribution (``tools/trace_analyze.py``).

Run (on the chip; one process owns it):
    python tools/step_sweep.py [--trace chiprun_out/bench_trace] \
        [--out chiprun_out/step_sweep.json]
"""
import argparse
import importlib.util
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _summarize(device_kind, batch, rows, partial):
    """vs_spc1/amortization from whatever rows exist so far (the k=1
    baseline runs first in the sorted sweep) — single home for the
    formula, shared by the stdout summary and the JSON artifact."""
    base = rows[0]["imgs_per_sec_per_chip"]
    rows = [dict(r, vs_spc1=round(r["imgs_per_sec_per_chip"] / base, 3))
            for r in rows]
    summary = {"device": device_kind, "batch": batch, "rows": rows,
               "dispatch_amortization":
                   round(max(r["imgs_per_sec_per_chip"] for r in rows)
                         / base, 3)}
    if partial:
        summary["partial"] = True      # sweep did not finish all k values
    return summary


def _write_summary(out, device_kind, batch, rows, partial):
    summary = _summarize(device_kind, batch, rows, partial)
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    tmp = f"{out}.tmp{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(summary, f, indent=1)
    os.replace(tmp, out)
    return summary


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--sweep", default="1,2,5,10",
                        help="comma-separated steps_per_call values")
    parser.add_argument("--batch", type=int, default=64)
    parser.add_argument("--iters", type=int, default=10)
    parser.add_argument("--trace", default=None,
                        help="directory for a jax.profiler trace of the "
                             "largest steps_per_call run")
    parser.add_argument("--out", default=None, help="json artifact path")
    parser.add_argument("--allow-cpu", action="store_true")
    args = parser.parse_args()

    import jax

    if args.allow_cpu:
        jax.config.update("jax_platforms", "cpu")
    dev = jax.devices()[0]
    if dev.platform == "cpu" and not args.allow_cpu:
        print("refusing: no accelerator (pass --allow-cpu to force)",
              file=sys.stderr)
        sys.exit(2)

    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(os.path.dirname(__file__), os.pardir,
                              "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    sweep = [int(s) for s in args.sweep.split(",")]
    on_accel = dev.platform != "cpu"
    os.environ["BLUEFOG_BENCH_BATCH"] = str(args.batch)
    os.environ["BLUEFOG_BENCH_ITERS"] = str(args.iters)

    rows = []
    for i, spc in enumerate(sorted(sweep)):
        os.environ["BLUEFOG_BENCH_STEPS_PER_CALL"] = str(spc)
        tracing = args.trace and spc == max(sweep)
        if tracing:
            jax.profiler.start_trace(args.trace)
        r = bench.run_bench(on_accel)
        if tracing:
            jax.profiler.stop_trace()
        row = {"steps_per_call": spc, "imgs_per_sec_per_chip": r["value"],
               "mfu": r["mfu"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
        # write INCREMENTALLY: rows already measured survive a later
        # failure.  partial is positional: only the LAST iteration's write
        # claims a complete sweep.
        if args.out:
            summary = _write_summary(args.out, dev.device_kind, args.batch,
                                     rows, partial=i != len(sweep) - 1)

    if not args.out:
        summary = _summarize(dev.device_kind, args.batch, rows,
                             partial=False)
    print(json.dumps({"summary": summary["dispatch_amortization"],
                      "best": max(summary["rows"],
                                  key=lambda r: r["imgs_per_sec_per_chip"])}))
    if args.out:
        print(f"wrote {args.out}", file=sys.stderr)


if __name__ == "__main__":
    main()
