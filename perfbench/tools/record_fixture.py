"""Record the small chip trace that tests check the trace reduction on.

    python3 perfbench/tools/record_fixture.py      (on the chip)

Three calls of a small jitted program (two matmuls and a scan of
elementwise work, so that op events nest) with the benchmark's own host
spans around them, under the profiler; the trace is converted to the plain
structure of harness/trace.py and written, gzipped, to
chiprun_out/fixture/trace_small.json.gz together with what the run itself
observed (host-clock window, calls), which the test compares with.
"""
import gzip
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from perfbench.harness import trace  # noqa: E402


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("record_fixture: no TPU", file=sys.stderr)
        return 2

    @jax.jit
    def prog(a, b):
        c = a @ b

        def body(x, _):
            return jnp.tanh(x) * 1.01 + 0.1, None
        c, _ = jax.lax.scan(body, c, None, length=4)
        return (c @ a).sum()

    a = jnp.ones((2048, 2048), jnp.bfloat16)
    b = jnp.ones((2048, 2048), jnp.bfloat16)
    jax.block_until_ready(prog(a, b))
    out_dir = os.path.join(ROOT, "chiprun_out", "fixture")
    tdir = os.path.join(out_dir, "raw")
    os.makedirs(tdir, exist_ok=True)
    calls = 3
    jax.profiler.start_trace(tdir)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        for _ in range(calls):
            with jax.profiler.TraceAnnotation("pb:step_call"):
                r = prog(a, b)
            with jax.profiler.TraceAnnotation("pb:wait_loss"):
                jax.block_until_ready(r)
            with jax.profiler.TraceAnnotation("pb:host_pause"):
                time.sleep(0.002)
    host_window_s = time.perf_counter() - t0
    jax.profiler.stop_trace()
    plain = trace.load_xplane(trace.find_xplane(tdir))
    plain["recorded"] = {"device_kind": dev.device_kind, "calls": calls,
                         "host_window_s": host_window_s,
                         "jax": jax.__version__}
    reduced = trace.reduce(plain)
    print(json.dumps({k: v for k, v in reduced.items()
                      if k != "self_time_s"}))
    print("planes:", [(p["name"], [(l["name"], len(l["events"]))
                                   for l in p["lines"]])
                      for p in plain["planes"]])
    with gzip.open(os.path.join(out_dir, "trace_small.json.gz"), "wt") as f:
        json.dump(plain, f)
    import shutil
    shutil.rmtree(tdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
