"""Step-time decomposition from a jax.profiler trace.

Parses the Chrome-trace JSON that ``jax.profiler.trace`` writes (a traced
benchmark run leaves one under ``perfbench_out/trace/<cell>/``), and
attributes device time to COMPUTE vs COMM, measuring how much
communication is EXPOSED (not overlapped by compute).  The reference's
>=95% scaling claim (``README.rst:26-34``) rests on gossip permutes hiding
behind backward compute; whether the XLA async-collective schedule hides
them here is what this tool reads off a trace (the benchmark's
``gossip.exposed_s_per_step`` is the same arithmetic; PERF.md has the
chip's readings).

Method: take the device track(s) (process names matching TPU/device;
fallback: the busiest track), classify complete events by op name
(collective ops vs everything else), merge each class into disjoint
intervals, and measure comm time not covered by compute intervals.
Reported numbers:

    wall_ms            last device event end - first start
    compute_ms         union of compute intervals
    comm_ms            union of comm intervals
    comm_exposed_ms    comm intervals minus compute coverage
    overlap_fraction   1 - exposed/comm (1.0 = fully hidden)
    idle_ms            wall - union(all device intervals) — dispatch gaps
    top_exposed_comm_ops  per-op attribution of the exposed time: comm
                       events grouped by canonical op name (trailing
                       ``.N`` instance suffix stripped), each group's
                       intervals measured against the compute cover,
                       top-k by exposed ms — so a regression names the
                       offending collective instead of an aggregate

Run: python tools/trace_analyze.py <trace_dir_or_file> [--out out.json]
"""
import argparse
import glob
import gzip
import json
import os
import re
import sys

# Collective classifier.  Substring match over the comm-op token set,
# tolerant of the spellings XLA traces actually contain: dashed HLO names
# ("all-reduce.3"), underscore/camel-case metadata ("AllToAll"), ragged
# variants ("ragged-all-to-all.1"), and async pairs — including
# fusion-wrapped ones like "loop_fusion.collective-permute-start.5" —
# whose -start/-done halves must both count as comm.  "copy-start"/"copy-
# done" (async D2D copies) must NOT match: no comm token, no match.
COMM_RE = re.compile(
    r"ragged[-_]?all[-_]?to[-_]?all"
    r"|all[-_]?reduce|all[-_]?gather|reduce[-_]?scatter"
    r"|collective[-_]?permute|all[-_]?to[-_]?all|collective[-_]?broadcast"
    r"|\bsend(?:[-_]done)?\b|\brecv(?:[-_]done)?\b"
    r"|ppermute|collective", re.I)
DEVICE_RE = re.compile(r"tpu|/device:|gpu", re.I)


def find_trace_file(path):
    if os.path.isfile(path):
        return path
    for pat in ("**/*.trace.json.gz", "**/*.trace.json"):
        hits = sorted(glob.glob(os.path.join(path, pat), recursive=True))
        if hits:
            return hits[-1]                  # newest run dir sorts last
    raise FileNotFoundError(f"no *.trace.json[.gz] under {path}")


def load_events(trace_file):
    opener = gzip.open if trace_file.endswith(".gz") else open
    with opener(trace_file, "rt") as f:
        doc = json.load(f)
    return doc.get("traceEvents", doc if isinstance(doc, list) else [])


def merge(intervals):
    """Union of [start, end) intervals; returns merged list + total."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out, sum(e - s for s, e in out)


def subtract(base, cover):
    """Total length of ``base`` intervals not covered by ``cover``."""
    total = 0.0
    ci = 0
    for s, e in base:
        pos = s
        while pos < e:
            while ci < len(cover) and cover[ci][1] <= pos:
                ci += 1
            if ci >= len(cover) or cover[ci][0] >= e:
                total += e - pos
                break
            c0, c1 = cover[ci]
            if c0 > pos:
                total += c0 - pos
            pos = c1
    return total


_INSTANCE_RE = re.compile(r"(\.\d+)+$")


def canonical_op(name):
    """Collapse per-instance HLO names: ``collective-permute-start.5`` and
    ``collective-permute-start.12`` are the same op for attribution."""
    return _INSTANCE_RE.sub("", name or "")


def top_exposed_comm_ops(comm_events, comp_cover, k=5):
    """Per-op exposed time: group comm events by canonical name, measure
    each group's merged intervals against the compute cover.  Returns the
    top-k groups by exposed ms (ties broken by total ms), each as
    ``{"name", "count", "total_ms", "exposed_ms"}``."""
    by_name = {}
    for ev in comm_events:
        iv = (float(ev["ts"]), float(ev["ts"]) + float(ev["dur"]))
        by_name.setdefault(canonical_op(ev.get("name", "")), []).append(iv)
    us = 1e-3
    rows = []
    for name, ivs in by_name.items():
        merged, total = merge(ivs)
        rows.append({
            "name": name,
            "count": len(ivs),
            "total_ms": round(total * us, 3),
            "exposed_ms": round(subtract(merged, comp_cover) * us, 3),
        })
    rows.sort(key=lambda r: (-r["exposed_ms"], -r["total_ms"], r["name"]))
    return rows[:k]


def analyze(events):
    pid_names = {}
    for ev in events:
        if ev.get("ph") == "M" and ev.get("name") == "process_name":
            pid_names[ev.get("pid")] = ev.get("args", {}).get("name", "")
    device_pids = {p for p, n in pid_names.items() if DEVICE_RE.search(n)}
    xs = [ev for ev in events
          if ev.get("ph") == "X" and ev.get("dur", 0) > 0]
    if device_pids:
        xs = [ev for ev in xs if ev.get("pid") in device_pids]
    elif xs:
        # fallback: the busiest pid is the device/op track
        busy = {}
        for ev in xs:
            busy[ev.get("pid")] = busy.get(ev.get("pid"), 0) + ev["dur"]
        top = max(busy, key=busy.get)
        xs = [ev for ev in xs if ev.get("pid") == top]
    if not xs:
        return {"ok": False, "error": "no complete events on device tracks"}

    comm_iv, comp_iv, comm_events = [], [], []
    for ev in xs:
        iv = (float(ev["ts"]), float(ev["ts"]) + float(ev["dur"]))
        if COMM_RE.search(ev.get("name", "")):
            comm_iv.append(iv)
            comm_events.append(ev)
        else:
            comp_iv.append(iv)
    comm_m, comm_total = merge(comm_iv)
    comp_m, comp_total = merge(comp_iv)
    all_m, busy_total = merge(comm_iv + comp_iv)
    wall = max(e for _, e in all_m) - min(s for s, _ in all_m)
    exposed = subtract(comm_m, comp_m)
    us = 1e-3                                 # trace timestamps are in us
    return {
        "ok": True,
        "n_events": len(xs),
        "wall_ms": round(wall * us, 3),
        "busy_ms": round(busy_total * us, 3),
        "compute_ms": round(comp_total * us, 3),
        "comm_ms": round(comm_total * us, 3),
        "comm_exposed_ms": round(exposed * us, 3),
        "overlap_fraction": (round(1.0 - exposed / comm_total, 4)
                             if comm_total > 0 else None),
        "idle_ms": round((wall - busy_total) * us, 3),
        "top_exposed_comm_ops": top_exposed_comm_ops(comm_events, comp_m),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("trace", help="trace dir (or .trace.json[.gz] file)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    try:
        tf = find_trace_file(args.trace)
        doc = analyze(load_events(tf))
        doc["trace_file"] = tf
    except (OSError, ValueError, FileNotFoundError) as e:
        doc = {"ok": False, "error": f"{type(e).__name__}: {e}"[:300]}
    print(json.dumps(doc))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=1)
    sys.exit(0 if doc.get("ok") else 1)


if __name__ == "__main__":
    main()
