"""fleet_top: live terminal dashboard over the gossiped fleet view.

Any rank running with a fleet view armed (``BLUEFOG_FLEET_EVERY=K`` /
``bfrun-tpu --fleet-view K``) and the metrics HTTP server up
(``--metrics-port``) serves its view of the *whole fleet* at ``/fleet``
— per-rank step time, consensus distance, queue depth, SLO burn,
hot-expert skew, and the staleness age of every row.  This tool renders
that JSON as a ranks × signals table with a refresh loop; because the
view is gossiped, pointing it at ANY rank shows the whole fleet.

Sources (one required):
    --url http://host:port/fleet    scrape a live rank
    --from-file fleet.json          render a saved view
    --virtual-cpu                   self-contained 8-virtual-rank CPU
                                    estate: trains a few steps with the
                                    carrier armed, scrapes its own /fleet
                                    over HTTP (the CI/battery path)

Modes:
    (default)                       refresh loop (--interval seconds)
    --once                          one frame, then exit
    --once --json [--out f.json]    machine-readable frame for CI: the
                                    raw /fleet JSON, schema-checked

Exit codes: 0 ok; 1 source unreachable / not armed / bad schema.
"""
import argparse
import json
import os
import sys
import time
import urllib.request

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SCHEMA = "bluefog-fleet-1"

# dashboard columns: (header, metric name, format)
COLUMNS = (
    ("step_s", "bluefog_step_time_ewma_s", "{:.4f}"),
    ("consens", "bluefog_consensus_distance_max", "{:.2e}"),
    ("stale", "bluefog_async_staleness_steps", "{:.0f}"),
    ("queue", "bluefog_serve_queue_depth", "{:.0f}"),
    ("p99_s", "bluefog_serve_p99_s", "{:.4f}"),
    ("burn", "bluefog_slo_burn_rate", "{:.2f}"),
    ("hot_exp", "bluefog_serve_hot_expert_fraction", "{:.2f}"),
)


def check_schema(doc):
    """Raise ValueError unless ``doc`` looks like a /fleet frame (the CI
    schema assert)."""
    if not isinstance(doc, dict):
        raise ValueError("fleet frame is not a JSON object")
    if doc.get("schema") != SCHEMA:
        raise ValueError(f"schema {doc.get('schema')!r} != {SCHEMA!r}")
    for key in ("n", "round", "live_ranks", "staleness", "metrics"):
        if key not in doc:
            raise ValueError(f"fleet frame missing key {key!r}")
    st = doc["staleness"]
    for key in ("rounds_per_rank", "rounds_max", "bound_rounds"):
        if key not in st:
            raise ValueError(f"fleet staleness missing key {key!r}")
    for name, m in doc["metrics"].items():
        if "kind" not in m:
            raise ValueError(f"metric {name!r} missing kind")
        if m["kind"] != "histogram" and "per_rank" not in m:
            raise ValueError(f"metric {name!r} missing per_rank table")
    return doc


def _per_rank(doc, name, rank):
    m = doc.get("metrics", {}).get(name)
    if not m or m.get("kind") == "histogram":
        return None
    per = m.get("per_rank", {})
    # JSON object keys are strings; in-process dicts use ints
    return per.get(str(rank), per.get(rank))


def render(doc):
    """One frame as text: header + ranks × signals table."""
    st = doc["staleness"]
    ages = st.get("rounds_per_rank") or []
    dead = set(doc.get("dead_ranks", ()))
    lines = [
        f"fleet_top — {len(doc['live_ranks'])}/{doc['n']} ranks live, "
        f"round {doc['round']}, view of rank {doc.get('rank', '?')}",
        f"staleness: max {st.get('rounds_max')} rounds "
        f"(bound {st.get('bound_rounds')}), "
        f"probe cadence {_fmt(st.get('probe_cadence_s'), '{:.3f}')}s, "
        f"age est {_fmt(st.get('age_s_est'), '{:.3f}')}s",
        "",
    ]
    headers = ["rank"] + [h for h, _, _ in COLUMNS] + ["age", ""]
    rows = [headers]
    for r in range(int(doc["n"])):
        cells = [str(r)]
        for _, name, fmt in COLUMNS:
            cells.append(_fmt(_per_rank(doc, name, r), fmt))
        age = ages[r] if r < len(ages) else None
        cells.append(_fmt(age, "{:.0f}"))
        cells.append("DEAD" if r in dead else "")
        rows.append(cells)
    widths = [max(len(row[i]) for row in rows) for i in range(len(headers))]
    for i, row in enumerate(rows):
        lines.append("  ".join(c.rjust(w) for c, w in zip(row, widths)))
        if i == 0:
            lines.append("  ".join("-" * w for w in widths))
    global_bits = []
    for name, m in sorted(doc.get("metrics", {}).items()):
        if m.get("kind") == "counter" and m.get("global") is not None:
            short = name[len("bluefog_"):] if name.startswith("bluefog_") \
                else name
            global_bits.append(f"{short}={m['global']:g}")
    if global_bits:
        lines += ["", "fleet totals: " + "  ".join(global_bits)]
    return "\n".join(lines)


def _fmt(v, fmt):
    if v is None:
        return "-"
    try:
        return fmt.format(float(v))
    except (TypeError, ValueError):
        return str(v)


def fetch(url, timeout=10.0):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return json.loads(resp.read().decode())


# ---------------------------------------------------------------------------
# --virtual-cpu: the self-contained estate (CI smoke)
# ---------------------------------------------------------------------------

def _self_estate(n=8, steps=6, every=1):
    """Spin an n-virtual-rank CPU estate, train ``steps`` gossip steps
    with the fleet carrier armed, serve /fleet over HTTP, and return
    (frame fetched over HTTP, invariants dict)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import optax

    sys.path.insert(0, REPO)
    import bluefog_tpu as bf
    from bluefog_tpu import optimizers as bfopt
    from bluefog_tpu import topology as tu
    from bluefog_tpu.utils import fleetview as bffleet
    from bluefog_tpu.utils import metrics as bfm

    bf.init(devices=jax.devices()[:n])
    bf.set_topology(tu.ExponentialTwoGraph(n), is_weighted=True)
    bffleet.arm(every=every)
    port = bfm.start_http_server(0)

    d = 16

    def grad_fn(params, batch):
        loss = jnp.mean((params["w"] - batch) ** 2)
        return loss, jax.grad(
            lambda p: jnp.mean((p["w"] - batch) ** 2))(params)

    strat = bfopt.adapt_with_combine(
        optax.sgd(0.0), bfopt.neighbor_communicator(bf.static_schedule()))
    params = {"w": jnp.broadcast_to(
        jnp.arange(float(n))[:, None], (n, d)).astype(jnp.float32)}
    state = bfopt.init_distributed(strat, params)
    step = bfopt.make_train_step(grad_fn, strat)   # cadence from the arm
    batch = jnp.zeros((n, d), jnp.float32)
    for _ in range(steps):
        params, state, loss = step(params, state, batch)
    jax.block_until_ready(loss)

    frame = fetch(f"http://127.0.0.1:{port}/fleet")
    health = fetch(f"http://127.0.0.1:{port}/healthz")
    invariants = {
        "retraces_after_warmup": bfm.counter(
            "bluefog_retrace_after_warmup_total").total(),
        "healthz_ok": health.get("status") == "ok",
        "fleet_armed": bool(health.get("fleet_armed")),
        "train_steps": steps,
    }
    bfm.stop_http_server()
    bf.shutdown()
    return frame, invariants


def main():
    ap = argparse.ArgumentParser(
        description="Live terminal dashboard over the gossiped fleet view.")
    src = ap.add_mutually_exclusive_group()
    src.add_argument("--url", default=None,
                     help="a live rank's /fleet endpoint "
                          "(http://host:port/fleet)")
    src.add_argument("--from-file", default=None,
                     help="render a saved /fleet JSON instead of scraping")
    src.add_argument("--virtual-cpu", action="store_true",
                     help="self-contained 8-virtual-rank CPU estate "
                          "(trains briefly, scrapes its own /fleet)")
    ap.add_argument("--once", action="store_true",
                    help="one frame, then exit (no refresh loop)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="print the raw fleet JSON (schema-checked) "
                         "instead of the table")
    ap.add_argument("--interval", type=float, default=2.0,
                    help="refresh period in seconds (default 2)")
    ap.add_argument("--frames", type=int, default=None,
                    help="stop after this many frames (default: forever)")
    ap.add_argument("--out", default=None,
                    help="also write the last frame's JSON here")
    args = ap.parse_args()
    if not (args.url or args.from_file or args.virtual_cpu):
        ap.error("give --url, --from-file, or --virtual-cpu")
    if args.virtual_cpu and not args.once:
        args.once = True                # the self-estate is one-shot

    invariants = None

    def get_frame():
        if args.from_file:
            with open(args.from_file) as f:
                return json.load(f)
        return fetch(args.url)

    try:
        if args.virtual_cpu:
            frame, invariants = _self_estate()
        else:
            frame = get_frame()
        check_schema(frame)
    except Exception as e:
        print(json.dumps({"ok": False,
                          "error": f"{type(e).__name__}: {e}"[:300]}))
        sys.exit(1)

    frames = 0
    while True:
        doc = dict(frame)
        if invariants is not None:
            doc["invariants"] = invariants
            doc["ok"] = (invariants["retraces_after_warmup"] == 0
                         and invariants["healthz_ok"])
        if args.as_json:
            print(json.dumps(doc))
        else:
            if not args.once:
                print("\033[2J\033[H", end="")       # clear + home
            print(render(frame))
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(doc, f, indent=1)
        frames += 1
        if args.once or (args.frames is not None and frames >= args.frames):
            break
        try:
            time.sleep(args.interval)
            frame = check_schema(get_frame())
        except KeyboardInterrupt:
            break
        except Exception as e:
            print(f"fleet_top: source lost: {e}", file=sys.stderr)
            sys.exit(1)
    sys.exit(0)


if __name__ == "__main__":
    main()
