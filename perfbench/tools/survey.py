"""Run cells as the driver does (fresh processes, one after another, the
manifest's command) and keep every run: the spread survey that PERF.md
tabulates, and the way to repeat it.

    python3 perfbench/tools/survey.py --label A --runs <cell>:<n>[:<trace>] ... [--seconds S] [--seed0 N] [--cwd DIR]

Writes one JSON line per run (label, cell, run index, seed, exit code, wall
seconds, the run's last line, its series summaries) to
chiprun_out/survey/<label>.jsonl and the tail of each run's output to
chiprun_out/survey/<label>.log.  This process never touches JAX: each run
owns the chip alone.
"""
import argparse
import json
import os
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--label", required=True)
    ap.add_argument("--runs", nargs="+", required=True,
                    help="<cell>:<count>[:<trace 0|1>]")
    ap.add_argument("--seconds", type=int, default=None)
    ap.add_argument("--seed0", type=int, default=100)
    ap.add_argument("--cwd", default=ROOT,
                    help="checkout to run from (e.g. an unpacked git archive)")
    args = ap.parse_args()
    with open(os.path.join(args.cwd, "BENCHMARK.json")) as f:
        man = json.load(f)
    seconds = args.seconds or man["run_seconds"]
    out_dir = os.path.join(ROOT, "chiprun_out", "survey")
    os.makedirs(out_dir, exist_ok=True)
    seed = args.seed0
    with open(os.path.join(out_dir, args.label + ".jsonl"), "a") as rows, \
            open(os.path.join(out_dir, args.label + ".log"), "a") as log:
        for spec in args.runs:
            parts = spec.split(":")
            cell, count = parts[0], int(parts[1])
            trace = int(parts[2]) if len(parts) > 2 else 0
            for i in range(count):
                seed += 1
                cmd = man["command"] + [
                    "--workload", cell, "--seed", str(seed),
                    "--seconds", str(seconds), "--trace", str(trace)]
                t0 = time.time()
                p = subprocess.run(cmd, cwd=args.cwd, capture_output=True,
                                   text=True)
                wall = time.time() - t0
                lines = p.stdout.strip().splitlines()
                try:
                    last = json.loads(lines[-1]) if lines else None
                except ValueError:
                    last = None
                series = [l for l in lines if l.startswith("[perfbench]")]
                row = {"label": args.label, "cell": cell, "run": i,
                       "seed": seed, "seconds": seconds, "trace": trace,
                       "rc": p.returncode, "wall_s": round(wall, 2),
                       "last": last, "notes": series}
                kept = os.path.join(
                    args.cwd, "perfbench_out", "series",
                    f"{cell}.seed{seed}.trace{trace}.json")
                if os.path.isfile(kept):
                    os.makedirs(os.path.join(out_dir, "series"), exist_ok=True)
                    shutil.copy(kept, os.path.join(
                        out_dir, "series", f"{args.label}.{cell}.run{i}."
                        f"seed{seed}.trace{trace}.json"))
                rows.write(json.dumps(row) + "\n")
                rows.flush()
                log.write(f"=== {cell} run {i} seed {seed} rc {p.returncode} "
                          f"wall {wall:.1f}s\n{p.stdout[-6000:]}\n--- stderr\n"
                          f"{p.stderr[-3000:]}\n")
                log.flush()
                print(f"{args.label} {cell} run {i} rc {p.returncode} "
                      f"wall {wall:.1f}s "
                      + (json.dumps(last["metrics"]) if last else "NO LINE"),
                      flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
