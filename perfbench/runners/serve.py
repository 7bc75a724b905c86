"""Kind ``serve``: requests from the general generator through a family's
serving program (ServeEngine + Scheduler), timed from the client's side.

One thread drives the scheduler and plays every client.  After each
``Scheduler.step`` the clients see the tokens that step produced: time to
first token and the gaps between tokens are taken on the benchmark's clock
at that moment, not from the program's own stamps.  Closed loop: the window
opens once every client has had a request retired (the ramp is set-up).
Open loop: requests are submitted when due, timed from when they were due,
and the window opens after ``ramp_seconds``.  Completed tokens per second
is every token delivered in the window over the window's length; the median
over its slices of ``slice_seconds`` (1 by default) stands beside it as a
per-layer statistic.
"""
import gc
import time

import numpy as np

from perfbench.harness import estimators, manifest
from perfbench.harness.spans import Spans
from perfbench.harness.traffic import Requests
from perfbench.runners import _common


class _Live:
    __slots__ = ("req", "client", "due", "asked", "seen", "times")

    def __init__(self, req, client, due, asked):
        self.req, self.client, self.due, self.asked = req, client, due, asked
        self.seen, self.times = 0, []


def longest_steps(step_ends, records, t_open, t_close, k=5):
    """The window's ``k`` longest scheduler steps, each with the time the
    benchmark's own spans took inside it: a stall then says which call it
    sat in (or ``other``: the scheduler's own code and this loop)."""
    ends = [t for t in step_ends if t_open < t <= t_close]
    steps = sorted(zip(ends, ends[1:]), key=lambda ab: ab[0] - ab[1])[:k]
    out = []
    for a, b in steps:
        inside = {}
        for name, t0, t1 in records:
            if t0 >= a and t1 <= b:
                inside[name] = inside.get(name, 0.0) + t1 - t0
        inside["other"] = b - a - sum(inside.values())
        out.append({"at_s": round(a - t_open, 3), "seconds": round(b - a, 6),
                    "spans": {n: round(v, 6) for n, v in inside.items()}})
    return out


def run(ctx):
    devices = _common.start(ctx)
    cfg, traffic = ctx["config"], ctx["traffic"]
    family = manifest.load_module("families", cfg["family"])
    spans = ctx["spans"] = Spans()
    prog = family.build_serve(cfg, traffic, devices, ctx["seed"])
    prog.warmup()
    setup_misses = ctx["watch"].misses
    retraces_before = prog.retraces()
    engine = prog.engine
    engine.prefill = spans.wrap("prefill_call", engine.prefill)
    engine.decode = spans.wrap("decode_call", engine.decode)
    sched = prog.scheduler()
    gen = Requests(traffic, prog.vocab, ctx["seed"])
    closed = traffic["loop"] == "closed"
    clients = traffic["clients"] if closed else 0
    live, finished = {}, []          # request id -> _Live; retired _Live
    deliveries_t, deliveries_n, lanes = [], [], []
    retired_once = set()

    def submit(client, due, ramp=False):
        prompt, out = gen.next(ramp)
        with spans.span("admit"):
            req = sched.submit(prompt, max_new_tokens=out)
        live[req.id] = _Live(req, client, due, out)

    def step():
        retired = sched.step()
        now = time.perf_counter()
        new = 0
        for lv in live.values():
            n = len(lv.req.generated)
            if n > lv.seen:
                lv.times.extend([now] * (n - lv.seen))
                new += n - lv.seen
                lv.seen = n
        deliveries_t.append(now)
        deliveries_n.append(new)
        lanes.append((now, sched.in_flight + len(retired)))
        with spans.span("retire"):
            for req in retired:
                lv = live.pop(req.id)
                finished.append(lv)
                retired_once.add(lv.client)
                if closed:
                    submit(lv.client, now)
        return now

    now = time.perf_counter()
    if closed:
        for c in range(clients):
            submit(c, now, ramp=True)
        while len(retired_once) < clients:
            step()
    else:
        horizon = traffic.get("ramp_seconds", 2.0) + ctx["seconds"] \
            + (traffic.get("traced_seconds", 3.0) if ctx["trace"] else 0.0)
        due = [now + a for a in gen.arrivals(traffic["rate_per_s"], horizon)]
        due_i = 0

        def submit_due(now):
            nonlocal due_i
            while due_i < len(due) and due[due_i] <= now:
                submit(-1, due[due_i])
                due_i += 1
        while time.perf_counter() - now < traffic.get("ramp_seconds", 2.0):
            submit_due(time.perf_counter())
            step()

    def serve_until(t_end):
        while time.perf_counter() < t_end:
            if not closed:
                submit_due(time.perf_counter())
                if sched.done:
                    time.sleep(0.0005)
                    continue
            step()

    gc.collect()
    gc.freeze()
    compiles_before = ctx["watch"].compiles
    t_open = time.perf_counter()
    ctx["setup_s"] = t_open - ctx["t_process_start"]
    serve_until(t_open + ctx["seconds"])
    t_close = time.perf_counter()
    compiles_in_window = ctx["watch"].compiles - compiles_before
    retraces = prog.retraces() - retraces_before
    dev = _common.device_report(devices)
    stalls = longest_steps(deliveries_t, spans.records, t_open, t_close)

    reduced = None
    if ctx["trace"]:
        spans.annotate = True
        n_before = len(spans.records)
        reduced = _common.trace_tail(ctx, lambda: serve_until(
            time.perf_counter() + traffic.get("traced_seconds", 3.0)))
        spans.annotate = False
        del spans.records[n_before:]
    sched.close()

    # requests submitted (due) in the window: time to first token
    everyone = finished + list(live.values())
    ttft = [lv.times[0] - lv.due for lv in everyone
            if t_open <= lv.due < t_close and lv.times
            and lv.times[0] <= t_close]
    # requests that ran wholly inside the window: gaps between their tokens
    whole = [lv for lv in finished
             if lv.due >= t_open and lv.times and lv.times[-1] <= t_close]
    gaps = [b - a for lv in whole for a, b in zip(lv.times, lv.times[1:])]
    in_window = [lv for lv in finished
                 if lv.times and t_open <= lv.times[-1] <= t_close]
    short = [lv for lv in in_window
             if lv.req.state != "done" or len(lv.req.generated) != lv.asked]
    rates = estimators.slice_rates(deliveries_t, deliveries_n, t_open,
                                   t_close, traffic.get("slice_seconds", 1.0))
    tokens_in_window = sum(n for t, n in zip(deliveries_t, deliveries_n)
                           if t_open < t <= t_close)
    check = traffic["check"]
    rng = np.random.default_rng(ctx["seed"] + 7)
    ref = prog.reference_check(
        [rng.integers(0, prog.vocab, n).tolist()
         for n in check["prompt_tokens"]], check["output_tokens"])
    _common.say(f"reference check: {ref}")
    _common.say(f"longest steps of the window: {stalls}")
    _common.say(f"window {t_close - t_open:.2f} s: {len(in_window)} requests "
                f"retired ({len(short)} short or failed), "
                f"{len(sched.failed)} failed in the scheduler, {len(ttft)} "
                f"first tokens, {len(gaps)} token gaps, compilations "
                f"{compiles_in_window}, retraces {retraces}")
    _common.keep_series(ctx, {
        "serve_tok_per_s": (rates, tokens_in_window / (t_close - t_open)),
        "ttft_s": (ttft, None),
        "token_gap_s": (gaps, None),
    }, extra={"reference_check": ref, "longest_steps": stalls,
              "slice_seconds": traffic.get("slice_seconds", 1.0),
              "deliveries": [[t - t_open, n] for t, n in
                             zip(deliveries_t, deliveries_n) if t >= t_open],
              # when each request was due and when the client saw each of
              # its tokens, from the window's opening: any estimator, over
              # any part of the window, can be priced with no further run
              "requests": [[round(lv.due - t_open, 6), lv in live.values(),
                            [round(t - t_open, 6) for t in lv.times]]
                           for lv in everyone if lv.times]})
    return {
        "correct": bool(ref["ok"] and not short and not sched.failed
                        and compiles_in_window == 0 and retraces == 0
                        and len(in_window) > 0),
        "attempted": len(in_window) + len(sched.failed),
        "failed": len(short) + len(sched.failed),
        "device": dev,
        "trace": reduced,
        "checks": _common.compared(
            ref, requests_short_or_failed=(len(short) + len(sched.failed), 0),
            compiles_in_window=(compiles_in_window, 0),
            retraces=(retraces, 0)),
        "readings": {"serve_tok_per_s": rates, "ttft_s": ttft,
                     "token_gap_s": gaps},
        "facts": {"setup_cache_misses": setup_misses,
                  "tokens_in_window": tokens_in_window,
                  "lanes_per_decode_call": [n for t, n in lanes
                                            if t_open < t <= t_close],
                  "window": (t_open, t_close),
                  "compiles_in_window": compiles_in_window},
    }
