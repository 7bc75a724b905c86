"""The real-size rehearsal without the chip (on-chip-measurement guide,
section 2): compile a cell's programs for a DESCRIBED v5e:2x2 and print
what the chip's compiler says: bytes per device from memory_analysis() and
the cross-chip collectives in the compiled HLO.  Nothing runs; no number
printed here is a measurement.

    JAX_PLATFORMS=cpu python3 perfbench/tools/rehearse_aot.py --workload <cell> [--set key=value ...]

``--set`` overrides a traffic size for the compile (e.g. micro=8), to size a
batch before a cell's file is written.  After a cell's programs comes one
line for the cell: what its fullest device "would hold" (the largest, over
its programs, of arguments + temporaries + the output bytes that reuse no
argument) beside the driver's floors for a new cell, so that a cell's bytes
can be reckoned before any chip call.
"""
import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from perfbench.harness import manifest, wire  # noqa: E402

GIB = 1 << 30
# the driver refuses a new cell whose fullest device holds less than a
# quarter of a v5e's 16 GiB, or an eighth where the device is busy for at
# least three quarters of the traced window
FLOOR_BYTES, FLOOR_BUSY_BYTES = 4 * GIB, 2 * GIB


def would_hold(ma):
    """Bytes one device holds while this program runs, from its
    ``memory_analysis()``: arguments (weights, state, cache, inputs),
    temporaries, and the outputs that are not written into a donated
    argument."""
    return (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + max(0, ma.output_size_in_bytes - ma.alias_size_in_bytes))


def report(name, compiled, n_dev):
    ma = compiled.memory_analysis()
    counts, bytes_ = wire.wire_stats(compiled.as_text())
    row = {"program": name, "devices": n_dev,
           "argument_gb": ma.argument_size_in_bytes / 1e9,
           "output_gb": ma.output_size_in_bytes / 1e9,
           "alias_gb": ma.alias_size_in_bytes / 1e9,
           "temp_gb": ma.temp_size_in_bytes / 1e9,
           "would_hold_gb": would_hold(ma) / 1e9,
           "collectives": counts, "wire_bytes": bytes_}
    print(json.dumps(row), flush=True)
    return row


def sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def lm_train(cfg, traffic, devices):
    import optax
    from bluefog_tpu import optimizers as bfopt
    from bluefog_tpu.parallel import compose
    fam = manifest.load_module("families", "composed_lm")
    m = compose.compose_parallelism(traffic["dp"], 1, 1, 1, devices=devices)
    lm = fam._lm_config(cfg, seq_len=traffic["seq_len"],
                        micro=traffic["micro"], batch=traffic["batch"])
    step, strategy = compose.make_train_step(
        m, compose.make_lm_grad_fn(lm, m, use_pallas=bool(
            traffic.get("use_pallas", False))),
        optax.adam(traffic["learning_rate"]))
    sh = NamedSharding(m.mesh, m.spec)
    n, row = m.size, fam.param_shapes(cfg)
    is_shape = lambda s: isinstance(s, tuple)
    template = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s, jnp.float32),
                            row, is_leaf=is_shape)
    params = jax.tree.map(lambda s: sds((n,) + s, jnp.float32, sh), row,
                          is_leaf=is_shape)
    state = jax.tree.map(lambda x: sds((n,) + x.shape, x.dtype, sh),
                         jax.eval_shape(strategy.init, template))
    if strategy.pipelined:
        state = state._replace(comm_state=params)
    toks = sds((n, lm.micro, lm.batch, lm.seq_len), jnp.int32, sh)
    return [("train_step", step.lower(params, state, toks).compile(), n)]


def lm_serve(cfg, traffic, devices):
    from bluefog_tpu.parallel import compose
    from bluefog_tpu.serve import KVCacheConfig, ServeEngine
    from bluefog_tpu.serve import kv_cache as kv
    fam = manifest.load_module("families", "composed_lm")
    scfg = fam.serve_config(traffic)
    dtype = scfg.dtype
    m = compose.compose_parallelism(1, 1, 1, 1, devices=devices[:1])
    lm = fam._lm_config(cfg)
    sh = NamedSharding(m.mesh, m.spec)
    params = jax.tree.map(lambda s: sds((1,) + s, dtype, sh),
                          fam.param_shapes(cfg),
                          is_leaf=lambda s: isinstance(s, tuple))
    # the engine's own jitted bodies, without building an engine (which
    # places parameters and a cache on real devices)
    eng_obj = ServeEngine.__new__(ServeEngine)
    eng_obj._moe, eng_obj.m, eng_obj.cfg, eng_obj.scfg = False, m, lm, scfg
    eng_obj._moe_chunk_tile, eng_obj.draft = None, None
    cc = KVCacheConfig(
        layers=lm.layers, slots=scfg.slots, max_len=scfg.max_len,
        kv_heads=lm.heads, head_dim=lm.d_model // lm.heads, dtype=dtype,
        store=scfg.kv_dtype, prefix_slots=scfg.prefix_pages)
    # every program is body(params, cache, keys, staged): the cache in the
    # order its shapes give (kv_cache.page_order), the sampler keys a row
    # each, and the one staged array of a call
    state = lambda: (
        {k: sds((1,) + v.shape, v.dtype, sh)
         for k, v in jax.eval_shape(lambda: kv.init_cache(cc)).items()},
        sds((1, cc.rows, 2), jnp.uint32, sh))
    i32 = lambda *s: sds((1,) + s, jnp.int32, sh)
    dec = eng_obj._build(eng_obj._decode_body)
    pre = eng_obj._build(eng_obj._prefill_body)
    return [(f"decode_S{S}",
             dec.lower(params, *state(), i32(S, 1 + 4)).compile(), 1)
            for S in scfg.batch_buckets] + [
        (f"prefill_T{T}",
         pre.lower(params, *state(), i32(T + 4)).compile(), 1)
        for T in scfg.prefill_buckets]


def resnet_train(cfg, traffic, devices):
    import optax
    from jax.sharding import Mesh
    import numpy as np
    from bluefog_tpu import models
    from bluefog_tpu import optimizers as bfopt
    from bluefog_tpu import schedule as sch
    from bluefog_tpu import topology as tu
    n = traffic["dp"]
    mesh = Mesh(np.array(devices[:n]), ("rank",))
    topo = tu.ExponentialTwoGraph(n) if n > 1 else tu.FullyConnectedGraph(1)
    strategy = bfopt.adapt_with_combine(
        optax.sgd(traffic["learning_rate"], momentum=0.9),
        bfopt.neighbor_communicator(sch.compile_topology(topo, True)))
    B, S, C = traffic["batch"], cfg["image_size"], cfg["num_classes"]
    model = models.ResNet50(num_classes=C)
    variables = jax.eval_shape(lambda k: model.init(
        k, jnp.ones((1, S, S, 3), jnp.float32), train=False),
        jax.random.key(0))

    fam = manifest.load_module("families", "resnet")
    step = bfopt.make_train_step(fam.make_grad_fn(model), strategy,
                                 donate=True, mesh=mesh)
    sh = NamedSharding(mesh, P("rank"))
    ts = {"params": variables["params"], "bs": variables["batch_stats"]}
    stack = lambda t: jax.tree.map(
        lambda x: sds((n,) + x.shape, x.dtype, sh), t)
    data = (sds((n, B, S, S, 3), jnp.float32, sh), sds((n, B), jnp.int32, sh))
    return [("train_step", step.lower(
        stack(ts), stack(jax.eval_shape(strategy.init, ts)), data).compile(),
        n)]


BUILDERS = {("composed_lm", "train"): lm_train,
            ("composed_lm", "serve"): lm_serve,
            ("resnet", "train"): resnet_train}


def programs(man, workload, devices, rehearsal=False, overrides=()):
    """``[(name, compiled, n devices)]`` of the cell's programs compiled
    for ``devices`` (a described chip's, or CPU devices with the files'
    ``tiny`` sizes: ``rehearsal``), by this file's builder of the cell's
    (family, kind) or, for a family this file does not know, by the
    family adapter's own ``aot_programs(cfg, traffic, devices)``."""
    cell = manifest.resolve_cell(man, workload)
    cfg = manifest.sized(cell["config"], rehearsal)
    traffic = manifest.sized(cell["traffic"], rehearsal)
    for kv in overrides:
        k, v = kv.split("=", 1)
        traffic[k] = json.loads(v)
    build = BUILDERS.get((cfg["family"], traffic["kind"]))
    if build is None:
        family = manifest.load_module("families", cfg["family"])
        if not hasattr(family, "aot_programs"):
            raise manifest.ManifestError(
                f"perfbench/families/{cfg['family']}.py has no "
                "aot_programs(cfg, traffic, devices)")
        build = family.aot_programs
    return build(cfg, traffic, list(devices)[:cell["cell"]["chips"]])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--set", action="append", default=[])
    args = ap.parse_args()
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    rows = [report(f"{args.workload}:{name}", compiled, n)
            for name, compiled, n in programs(
                manifest.load(), args.workload, topo.devices,
                overrides=args.set)]
    held = max(r["would_hold_gb"] for r in rows) * 1e9
    print(json.dumps({
        "cell": args.workload, "would_hold_gb": held / 1e9,
        "would_hold_gib": held / GIB, "floor_gib": FLOOR_BYTES / GIB,
        "floor_gib_if_busy_75pc": FLOOR_BUSY_BYTES / GIB,
        "passes_floor": held >= FLOOR_BYTES,
        "passes_floor_if_busy_75pc": held >= FLOOR_BUSY_BYTES}), flush=True)


if __name__ == "__main__":
    main()
