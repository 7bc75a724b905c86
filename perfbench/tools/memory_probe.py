"""What this runtime's ``Device.memory_stats()`` keys mean, found out on
the chip: the whole dict on a fresh device, after a ``device_put`` of
1 GiB, after a jitted call whose compiled ``memory_analysis()`` counts a
2 GiB temporary, and after the 1 GiB array is deleted; beside each, what
``harness/device.py``'s ``device_info`` makes of it.

    python3 perfbench/tools/memory_probe.py            (on the chip)
    JAX_PLATFORMS=cpu python3 perfbench/tools/memory_probe.py --aot

``--aot`` compiles the probe's program for a described v5e (no chip) and
prints its ``memory_analysis()`` only.  One JSON object per line; the last
line holds the 1 GiB step as ``device_info`` read it and whether any
reading passed the device's ``bytes_limit``.
"""
import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

GIB = 1 << 30
N = 23168            # N * N f32 = 2.147 GB = 2.0 GiB


def program(a):
    """``a``: f32 [N].  A loop fills an [N, N] buffer row by row and the
    buffer is read whole afterwards: a loop's carry cannot be fused away,
    so the compiled program holds a temporary of 2 GiB."""
    import jax
    import jax.numpy as jnp

    def body(i, buf):
        return jax.lax.dynamic_update_slice(
            buf, (a * i.astype(jnp.float32))[None], (i, 0))
    buf = jax.lax.fori_loop(0, N, body, jnp.zeros((N, N), jnp.float32))
    return jnp.abs(buf).max()


def analysis(compiled):
    ma = compiled.memory_analysis()
    return {k: int(getattr(ma, k + "_size_in_bytes"))
            for k in ("argument", "output", "alias", "temp")}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--aot", action="store_true")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench.harness import device as hw
    if args.aot:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        a = jax.ShapeDtypeStruct(
            (N,), jnp.float32, sharding=SingleDeviceSharding(topo.devices[0]))
        print(json.dumps({"program": analysis(
            jax.jit(program).lower(a).compile())}))
        return 0
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("memory_probe: no TPU", file=sys.stderr)
        return 2
    rows = []

    def say(stage, **more):
        info = hw.device_info([dev])
        rows.append(info)
        print(json.dumps({"stage": stage, "memory_stats": dev.memory_stats(),
                          "device_info": info, **more}), flush=True)

    say("fresh")
    x = jax.device_put(np.zeros((GIB,), np.uint8), dev)
    jax.block_until_ready(x)
    say("after device_put of 1 GiB")
    a = jnp.ones((N,), jnp.float32)
    compiled = jax.jit(program).lower(a).compile()
    jax.block_until_ready(compiled(a))
    say("after a call with a 2 GiB temporary", program=analysis(compiled))
    x.delete()
    say("after the 1 GiB array is deleted")
    jax.block_until_ready(compiled(a))
    say("after a second call, the 1 GiB array gone")
    limit = int((dev.memory_stats() or {}).get("bytes_limit", 0))
    step = rows[1]["memory_peak_bytes"] - rows[0]["memory_peak_bytes"]
    print(json.dumps({
        "step_bytes": step, "step_over_gib": step / GIB,
        "temp_step_bytes": (rows[2]["memory_peak_bytes"]
                            - rows[1]["memory_peak_bytes"]),
        "bytes_limit": limit,
        "over_limit": bool(limit and any(r["memory_peak_bytes"] > limit
                                         for r in rows))}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
