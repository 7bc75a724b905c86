"""One run of one cell of BENCHMARK.json.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Resolves the cell to its configuration file, its traffic file, the runner of
the traffic's ``kind`` and the adapter of the configuration's ``family`` (all
found by name, see harness/manifest.py), runs it on this machine's chips and
prints, as the last line of stdout, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``,
traced ``breakdown``, and last ``checks``: each number ``correct`` rests
on beside its limit (also the last lines of standard error).  Exits
non-zero and prints no result line without a
TPU, with fewer chips than the cell asks for, or without the program.

``--rehearse`` is for the tests: the files' ``tiny`` sizes on CPU devices.
Its last line carries no metric value at all (``metrics`` is empty; the
names that would be reported are listed under ``would_report``).
"""
import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# this checkout's perfbench/ and bluefog_tpu/ before any other on the path
sys.path[:] = [ROOT] + [p for p in sys.path if p != ROOT]

from perfbench.harness import manifest  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tests only: tiny sizes on CPU, no metric values")
    ap.add_argument("--out-dir", default=os.path.join(ROOT, "perfbench_out"),
                    help="where series and the trace go (tests hand each "
                         "run a directory of its own)")
    args = ap.parse_args(argv)
    try:
        man = manifest.load()
        cell = manifest.resolve_cell(man, args.workload)
    except manifest.ManifestError as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    ctx = {
        "workload": args.workload, "seed": args.seed, "trace": args.trace,
        "seconds": float(man["run_seconds"] if args.seconds is None
                         else args.seconds),
        "rehearsal": args.rehearse, "cell": cell["cell"],
        "config": manifest.sized(cell["config"], args.rehearse),
        "traffic": manifest.sized(cell["traffic"], args.rehearse),
        "out_dir": os.path.abspath(args.out_dir),
        "t_process_start": T_PROCESS_START,
    }
    from perfbench.runners import _common
    try:
        runner = manifest.load_module("runners", ctx["traffic"]["kind"])
        record = runner.run(ctx)
    except (_common.Refused, manifest.ManifestError) as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 3
    run = {**record, "workload": args.workload, "setup_s": ctx["setup_s"],
           "spans": ctx["spans"], "config": ctx["config"],
           "traffic": ctx["traffic"], "out_dir": ctx["out_dir"]}
    if "program_spans" in ctx:
        run["program_spans"] = ctx["program_spans"]
    group = "per_layer" if args.trace else "end_to_end"
    metrics = manifest.read_metrics(man, args.workload, group, run)
    device = dict(record["device"])
    line = {"correct": record["correct"], "attempted": record["attempted"],
            "failed": record["failed"], "metrics": metrics, "device": device}
    if args.trace and not (args.rehearse and record["trace"] is None):
        if record["trace"] is None:
            print("perfbench: the traced window holds no device operation",
                  file=sys.stderr)
            return 4
        device["busy_s"] = record["trace"]["busy_s"]
        device["window_s"] = record["trace"]["window_s"]
        line["breakdown"] = record["trace"]["breakdown"]
    if args.rehearse:
        # a CPU run never writes a number under a device metric's name
        line["would_report"] = sorted(metrics)
        line["metrics"] = {}
        line["rehearsal"] = True
        device.pop("busy_s", None)
        device.pop("window_s", None)
        line.pop("breakdown", None)
    # every number `correct` rests on beside its limit: the last lines of
    # standard error, and the last key of the result line
    line["checks"] = record["checks"]
    for name, c in record["checks"].items():
        print(f"perfbench: check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
