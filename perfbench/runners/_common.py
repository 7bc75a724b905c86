"""What every kind of run does the same way: bring the program up on the
cell's chips (or refuse), trace a tail of the run, keep the series."""
import json
import os
import shutil
import sys
import time

import numpy as np

from perfbench.harness import device as hw
from perfbench.harness import estimators, program_spans, trace


class Refused(Exception):
    """The run cannot be a measurement on the chips the cell asks for."""


def start(ctx):
    """bf.init before anything else touches a backend (on a TPU it places
    the libtpu flags and the compile cache in the checkout), then hold the
    run to the cell's platform and chip count.  Returns the devices."""
    try:
        import jax
        import bluefog_tpu as bf
    except ImportError as e:
        raise Refused(f"cannot import the program under test: {e}") from e
    ctx["watch"] = hw.CompileWatch()
    chips = ctx["cell"]["chips"]
    if ctx["rehearsal"]:
        # tests only: tiny sizes on (virtual) CPU devices, no device number
        devs = jax.devices("cpu")
        if len(devs) < chips:
            raise Refused(f"rehearsal needs {chips} CPU devices, found "
                          f"{len(devs)} (XLA_FLAGS=--xla_force_host_platform_"
                          "device_count)")
        bf.init(devices=devs[:chips])
        return devs[:chips]
    bf.init()
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"no accelerator: JAX picked platform "
                      f"{devs[0].platform!r} ({devs[0].device_kind}); the "
                      "benchmark measures on the chip or not at all")
    if len(devs) != chips:
        raise Refused(f"cell {ctx['workload']} asks for {chips} chip(s), "
                      f"this machine holds {len(devs)}")
    return list(np.ravel(bf.devices()))


def trace_tail(ctx, body):
    """Run ``body()`` under the profiler inside a ``pb:window`` span and
    reduce the trace.  Returns the reduction (None if no device op ran)."""
    import jax
    trace_dir = os.path.join(ctx["out_dir"], "trace", ctx["workload"])
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    jax.profiler.start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            body()
    finally:
        jax.profiler.stop_trace()
    t0 = time.perf_counter()
    xplane = trace.find_xplane(trace_dir)
    # the program's own spans first: they say how far the device's events
    # are stamped ahead of the host's, which the naming of idle gaps needs
    ana = ctx["program_spans"] = program_spans.Analysis(
        program_spans.load(xplane))
    for text in ana.report() if ana.spans else ():
        say(text)
    reduced = trace.reduce(trace.load_xplane(xplane),
                           device_shift_ns=ana.shift_ns)
    say(f"trace reduced in {time.perf_counter() - t0:.1f} s: "
        + json.dumps({k: reduced[k] for k in
                      ("n_devices", "window_s", "window_from", "busy_s",
                       "device_shift_s")} if reduced else None))
    return reduced


def compared(*reports, **own):
    """Every number a run's ``correct`` rests on beside its limit, as
    {name: {"value", "limit"}}: what the family's checks compared
    (``compared`` in their reports, nested ones too) and the runner's own
    counts.  run.py prints them as a run's last lines on standard error
    and as the last key of its result line."""
    out = {}

    def take(report):
        for name, (value, limit) in report.get("compared", {}).items():
            # a number that is not finite has no place in a JSON line
            out[name] = {"value": float(value) if np.isfinite(value) else None,
                         "limit": float(limit)}
        for v in report.values():
            if isinstance(v, dict):
                take(v)
    for r in reports:
        take(r)
    take({"compared": own})
    return out


def device_report(devices):
    """The ``device`` object of the last line (harness/device.py), with the
    fullest device's whole ``memory_stats()`` printed beside it: what the
    reading was made from stays on record."""
    say("memory_stats of the fullest device: "
        + json.dumps(hw.fullest(devices)))
    return hw.device_info(devices)


def say(msg):
    print(f"[perfbench] {msg}", flush=True)


def keep_series(ctx, series, extra=None):
    """Print each series' distribution and write the series to a file under
    the benchmark's output directory (never into the last line)."""
    doc = {"workload": ctx["workload"], "seed": ctx["seed"],
           "seconds": ctx["seconds"], "trace": ctx["trace"],
           "rehearsal": ctx["rehearsal"], "series": {}, **(extra or {})}
    for name, (readings, whole) in series.items():
        if not readings:
            continue
        if ctx["rehearsal"]:
            name = "cpu_rehearsal." + name    # never a device metric's name
        summ = estimators.summary(readings, whole)
        doc["series"][name] = {"summary": summ, "readings": readings}
        say(f"series {name}: " + " ".join(
            f"{k}={v:.6g}" for k, v in summ.items()))
    path = os.path.join(
        ctx["out_dir"], "series",
        f"{ctx['workload']}.seed{ctx['seed']}.trace{ctx['trace']}.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f)
    say(f"series kept in {path}")
