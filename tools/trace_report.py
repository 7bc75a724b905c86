"""Merge per-rank trace bundles into Chrome-trace + critical-path report.

Each rank armed with ``BLUEFOG_TRACE=<dir>`` writes
``<dir>/trace_rank<r>.trace.jsonl`` (schema ``bluefog-trace-1``: one
``meta`` line carrying a ``(monotonic, wall)`` clock anchor, then one
line per span — see ``bluefog_tpu/utils/tracing.py``).  This tool is the
job-level view:

* **merge** — every bundle's spans on one wall-clock axis (span
  endpoints are per-rank ``time.monotonic()``; the meta anchor converts
  them: ``wall = meta.wall + (t - meta.mono)``),
* **--chrome** — a ``chrome://tracing`` / Perfetto file (``traceEvents``
  with ``ph: "X"`` complete events, one process per rank, one thread
  lane per trace id),
* **critical path** — per-request breakdown from the ``cat="serve"``
  span tree: queue wait vs prefill vs summed fused-decode time vs the
  scheduling gap (host time between calls).  The root ``request`` span's
  endpoints are the scheduler's own ``submitted_at``/``finished_at``
  stamps, so ``total_s`` IS the request's measured E2E latency and
  ``queue + prefill + decode + gap == total`` by construction.

* **stages** — the operator's view of the stage ring that ``flush``
  writes beside each bundle (``<dir>/stages_rank<r>.json``, schema
  ``bluefog-stages-1``: every ``tracing.stage``'s entry and exit, kept
  with nothing armed): seconds by stage and bucket, and the ten largest
  excesses with their paths.  The grouping rule is the one the benchmark's
  reader states (``perfbench/harness/stage_ring.py`` keeps its own copy:
  its files do not import the program's analysis): records are nested by
  time; an INSTANCE is a stage with none beneath it, or the time a stage
  spent outside those beneath it (``.../(self)``); instances are grouped by
  path (``serve.step/engine.decode_call/collect/wait``) and bucket (the
  nearest ``S``, ``Tpad`` or ``T`` up the path); an instance's EXCESS is
  its seconds over its group's median, and a group of fewer than 5
  instances is not judged.  ``bf:host.pause`` records (the armed
  observer's late wakes) are listed apart.

Run: python tools/trace_report.py <bundle.trace.jsonl> ... [--dir DIR]
     [--out report.json] [--chrome trace.json]

Output schema (stable, pinned by tests/test_tracing.py):
    {"ok": bool, "schema": "bluefog-trace-report-1",
     "n_ranks": int, "ranks": [...], "n_spans": int, "dropped": int,
     "requests": {trace_id: {"total_s", "queue_s", "prefill_s",
                             "decode_s", "gap_s", "n_decode_calls",
                             "tokens", "replica", "prefix_hit",
                             "spec_accepted"}},
     "critical_path": [[trace_id, total_s, queue_s, prefill_s, decode_s,
                        gap_s], ...]   # slowest first
     "train": {"steps": int, "step_mean_s": float|None,
               "probes": int},
     "stages": {rank: {"n_records", "dropped", "unjudged_groups",
                       "groups": [[path, bucket, n, p50_s, p90_s, p99_s,
                                   max_s], ...],      # most time first
                       "largest_excesses": [[wall_ts, path, bucket,
                                             excess_s, median_s, n], ...],
                       "pauses": [[wall_ts, seconds], ...]}}}
     # "stages" only where a stages_rank<r>.json lies beside a bundle
"""
import argparse
import glob
import json
import os
import statistics
import sys
import time

SCHEMA = "bluefog-trace-report-1"
BUNDLE_SCHEMA = "bluefog-trace-1"


def load_bundle(path, notes=None):
    """One bundle -> (meta, [spans]).  Torn trailing lines (the writer
    died mid-append) are skipped with a warning, never fatal."""
    meta, spans = None, []
    with open(path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                doc = json.loads(line)
            except json.JSONDecodeError as e:
                msg = (f"warning: {path}:{lineno}: skipping torn JSONL "
                       f"line ({e.msg})")
                print(msg, file=sys.stderr)
                if notes is not None:
                    notes.append(msg)
                continue
            if doc.get("kind") == "meta":
                meta = doc
            elif doc.get("kind") == "span":
                spans.append(doc)
    if meta is None:
        raise ValueError(f"{path}: no meta line (not a {BUNDLE_SCHEMA} "
                         "bundle?)")
    if meta.get("schema") != BUNDLE_SCHEMA:
        raise ValueError(f"{path}: schema {meta.get('schema')!r} != "
                         f"{BUNDLE_SCHEMA!r}")
    return meta, spans


def _wall(meta, t):
    """Per-rank monotonic timestamp -> shared wall-clock seconds."""
    return meta["wall"] + (t - meta["mono"])


ATTR_SKIP = {"kind", "seq", "trace", "span", "name", "t0", "t1", "cat",
             "parent"}


def chrome_trace(bundles):
    """``[(meta, spans)]`` -> Chrome-trace dict (``traceEvents``).

    One pid per rank, one tid lane per trace id within the rank; ts/dur
    in microseconds relative to the earliest span across all ranks.
    """
    t_min = None
    for meta, spans in bundles:
        for s in spans:
            w = _wall(meta, s["t0"])
            t_min = w if t_min is None or w < t_min else t_min
    events = []
    for meta, spans in bundles:
        rank = meta.get("rank", 0)
        events.append({"ph": "M", "pid": rank, "tid": 0,
                       "name": "process_name",
                       "args": {"name": f"rank{rank}"}})
        lanes = {}
        for s in spans:
            trace = s.get("trace", "")
            tid = lanes.get(trace)
            if tid is None:
                tid = lanes[trace] = len(lanes) + 1
                events.append({"ph": "M", "pid": rank, "tid": tid,
                               "name": "thread_name",
                               "args": {"name": trace}})
            w0 = _wall(meta, s["t0"])
            dur = max(s["t1"] - s["t0"], 0.0)
            events.append({
                "ph": "X", "pid": rank, "tid": tid,
                "name": s.get("name", "?"), "cat": s.get("cat") or "span",
                "ts": round((w0 - (t_min or 0.0)) * 1e6, 3),
                "dur": round(dur * 1e6, 3),
                "args": {k: v for k, v in s.items() if k not in ATTR_SKIP},
            })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def critical_path(bundles):
    """Per-request breakdown from the serve span trees.

    Only requests with a root ``request`` span (i.e. retired) get a row.
    ``gap_s`` is everything the named child spans don't cover: host-side
    scheduling between the fused calls.
    """
    reqs = {}
    for meta, spans in bundles:
        for s in spans:
            if s.get("cat") != "serve":
                continue
            acc = reqs.setdefault(s["trace"], {
                "queue_s": 0.0, "prefill_s": 0.0, "decode_s": 0.0,
                "n_decode_calls": 0, "spec_accepted": 0,
                "prefix_hit": None, "total_s": None})
            name = s.get("name")
            dur = max(s["t1"] - s["t0"], 0.0)
            if name == "queue":
                acc["queue_s"] += dur
            elif name == "prefill":
                acc["prefill_s"] += dur
                acc["prefix_hit"] = bool(s.get("hit"))
            elif name == "decode":
                acc["decode_s"] += dur
                acc["n_decode_calls"] += 1
                acc["spec_accepted"] += int(s.get("accepted", 0))
            elif name == "request":
                acc["total_s"] = dur
                acc["tokens"] = s.get("tokens")
                acc["replica"] = s.get("replica")
    out = {}
    for trace, acc in reqs.items():
        if acc["total_s"] is None:
            continue                          # still in flight at flush
        acc["gap_s"] = max(acc["total_s"] - acc["queue_s"]
                           - acc["prefill_s"] - acc["decode_s"], 0.0)
        out[trace] = {k: (round(v, 9) if isinstance(v, float) else v)
                      for k, v in acc.items()}
    return out


def train_summary(bundles):
    steps, probes, total = 0, 0, 0.0
    for meta, spans in bundles:
        for s in spans:
            if s.get("cat") != "train":
                continue
            if s.get("name") == "train_step":
                steps += 1
                total += max(s["t1"] - s["t0"], 0.0)
            elif s.get("name") == "consensus_probe":
                probes += 1
    return {"steps": steps,
            "step_mean_s": round(total / steps, 9) if steps else None,
            "probes": probes}


PAUSE_NAME = "bf:host.pause"
MIN_GROUP = 5


def _quantile(sorted_vals, q):
    return sorted_vals[min(len(sorted_vals) - 1, int(q * len(sorted_vals)))]


def stage_view(doc, cut=None):
    """One rank's stage ring (``stages_rank<r>.json``) -> its entry of the
    report's ``stages``; the module docstring has the grouping rule.
    ``cut``: keep records that ended at or after this wall-clock time."""
    wall = lambda t: doc["wall"] + (t - doc["perf"])          # noqa: E731
    names = doc["names"]
    rows = sorted((r for r in doc["records"]
                   if cut is None or wall(r[3]) >= cut),
                  key=lambda r: (r[2], -r[3]))
    pauses, groups, stack = [], {}, []   # stack: [t0, t1, path, bucket, cat,
    #                                               seconds in children]

    def close(node):
        t0, t1, path, bucket, _cat, inside = node
        key = (path + "/(self)", bucket) if inside else (path, bucket)
        groups.setdefault(key, []).append((t1 - t0 - sum(inside), t0))

    for name_id, bucket, t0, t1, _depth, _cpu in rows:
        name = names[name_id]
        if name == PAUSE_NAME:
            pauses.append([round(wall(t0), 6), round(t1 - t0, 6)])
            continue
        while stack and stack[-1][1] <= t0:
            close(stack.pop())
        cat, _, short = name[len("bf:"):].partition(".")
        path = cat + "." + short
        if stack:
            parent = stack[-1]
            parent[5].append(t1 - t0)
            # a stage's category is written where it differs from its
            # parent's; the bucket is the nearest one up the path
            path = parent[2] + "/" + (short if parent[4] == cat else path)
            if bucket is None:
                bucket = parent[3]
        stack.append([t0, t1, path, bucket, cat, []])
    while stack:
        close(stack.pop())
    table, excesses, unjudged = [], [], 0
    for (path, bucket), members in groups.items():
        secs = sorted(s for s, _ in members)
        table.append([path, bucket, len(secs), _quantile(secs, 0.5),
                      _quantile(secs, 0.9), _quantile(secs, 0.99), secs[-1],
                      sum(secs)])
        if len(secs) < MIN_GROUP:
            unjudged += 1
            continue
        median = statistics.median(secs)
        excesses += [[round(wall(t0), 6), path, bucket,
                      round(s - median, 9), round(median, 9), len(secs)]
                     for s, t0 in members]
    table.sort(key=lambda row: -row[-1])
    return {"n_records": len(rows), "dropped": doc.get("dropped", 0),
            "unjudged_groups": unjudged,
            "groups": [[*row[:3], *(round(v, 9) for v in row[3:7])]
                       for row in table],
            "largest_excesses": sorted(excesses, key=lambda e: -e[3])[:10],
            "pauses": pauses}


def stage_views(paths, cut=None):
    """The ``stages`` of the report: one view per ``stages_rank<r>.json``
    that lies beside a bundle of ``paths``."""
    out = {}
    for d in sorted({os.path.dirname(p) or "." for p in paths}):
        for f in sorted(glob.glob(os.path.join(d, "stages_rank*.json"))):
            with open(f) as fh:
                doc = json.load(fh)
            if doc.get("schema") == "bluefog-stages-1":
                out[str(doc.get("rank", 0))] = stage_view(doc, cut)
    return out


def window_bounds(since=None, last=None, now=None):
    """``--since <wall-ts>`` / ``--last <secs>`` -> one lower wall-clock
    bound (None = keep everything; both given: later bound wins)."""
    if since is None and last is None:
        return None
    bounds = []
    if since is not None:
        bounds.append(float(since))
    if last is not None:
        if last <= 0:
            raise ValueError(f"--last must be > 0 seconds, got {last}")
        bounds.append((time.time() if now is None else float(now))
                      - float(last))
    return max(bounds)


def filter_bundles(bundles, cut):
    """Drop spans that *ended* before wall time ``cut`` (a span still
    running into the window counts: its tail is inside)."""
    if cut is None:
        return bundles
    return [(meta,
             [s for s in spans if _wall(meta, s["t1"]) >= cut])
            for meta, spans in bundles]


def report_from_files(paths, since=None, last=None):
    notes = []
    cut = window_bounds(since, last)
    bundles = [load_bundle(p, notes=notes) for p in paths]
    if cut is not None:
        before = sum(len(s) for _, s in bundles)
        bundles = filter_bundles(bundles, cut)
        dropped = before - sum(len(s) for _, s in bundles)
        if dropped:
            notes.append(f"window filter dropped {dropped} span(s) "
                         f"ending before {cut:.3f}")
    reqs = critical_path(bundles)
    table = sorted(
        ([t, v["total_s"], v["queue_s"], v["prefill_s"], v["decode_s"],
          v["gap_s"]] for t, v in reqs.items()),
        key=lambda row: -row[1])
    doc = {
        "ok": True,
        "schema": SCHEMA,
        "n_ranks": len(bundles),
        "ranks": sorted(m.get("rank", 0) for m, _ in bundles),
        "n_spans": sum(len(s) for _, s in bundles),
        "dropped": sum(m.get("dropped", 0) for m, _ in bundles),
        "requests": reqs,
        "critical_path": table,
        "train": train_summary(bundles),
    }
    stages = stage_views(paths, cut)
    if stages:
        doc["stages"] = stages
    if cut is not None:
        doc["window"] = {"since_ts": cut}
    if notes:
        doc["notes"] = notes
    return doc, bundles


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("bundles", nargs="*",
                    help="per-rank *.trace.jsonl bundles")
    ap.add_argument("--dir", default=None,
                    help="glob <dir>/*.trace.jsonl in addition to bundles")
    ap.add_argument("--out", default=None, help="write the report JSON here")
    ap.add_argument("--chrome", default=None,
                    help="write a chrome://tracing file here")
    ap.add_argument("--since", type=float, default=None, metavar="WALL_TS",
                    help="only report spans ending at/after this wall-clock "
                         "unix timestamp (slice a long-run artifact without "
                         "pre-splitting the JSONL)")
    ap.add_argument("--last", type=float, default=None, metavar="SECS",
                    help="only report spans from the trailing SECS seconds "
                         "(combines with --since: later bound wins)")
    args = ap.parse_args()
    paths = list(args.bundles)
    if args.dir:
        paths += sorted(glob.glob(os.path.join(args.dir, "*.trace.jsonl")))
    if not paths:
        print(json.dumps({"ok": False, "error": "no bundles given"}))
        sys.exit(1)
    try:
        doc, bundles = report_from_files(paths, since=args.since,
                                         last=args.last)
    except (OSError, ValueError) as e:
        doc = {"ok": False, "error": f"{type(e).__name__}: {e}"[:300]}
        bundles = None
    if args.chrome and bundles is not None:
        os.makedirs(os.path.dirname(args.chrome) or ".", exist_ok=True)
        with open(args.chrome, "w") as f:
            json.dump(chrome_trace(bundles), f)
        doc["chrome"] = args.chrome
    print(json.dumps(doc))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=1)
    sys.exit(0 if doc.get("ok") else 1)


if __name__ == "__main__":
    main()
