"""Checks shared by the families' programs: the comparison of a loss with
the plain reference's, what the gossip did to the parameters, and which
collectives the compiled step holds."""
import numpy as np

from perfbench.harness import wire


def row0(tree):
    """Replica 0's row of every [n, ...] leaf, still with its leading axis of
    one, on the device that holds it (no copy; slice it inside a jit)."""
    import jax

    def pick(x):
        for shard in x.addressable_shards:
            if shard.index[0].start in (0, None):
                return shard.data
        raise ValueError("no addressable shard holds row 0")
    return jax.tree.map(pick, tree)


def loss_agrees(got, want, tol, **more):
    """|program - reference| <= tol * max(1, |reference|), as a report."""
    gap = abs(got - want) / max(1.0, abs(want))
    ok = np.isfinite(got) and gap <= tol
    return {"ok": bool(ok), "program_loss": got, "reference_loss": want,
            "tolerance": tol, "compared": {"loss_gap": [gap, tol]}, **more}


def mixing_check(comm, mesh, spec, topology, seed, tol=1e-5):
    """One combine of a seeded [n, 1024] tensor through the program's
    communicator against the numpy product with the topology's mixing
    matrix (W[src, dst]; row r of the result is sum_s W[s, r] x[s])."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from bluefog_tpu import topology as topo_util

    n = mesh.devices.size
    x_np = np.random.default_rng(seed).normal(size=(n, 1024)).astype(np.float32)
    x = jax.device_put(jnp.asarray(x_np), NamedSharding(mesh, spec))
    mixed = jax.jit(jax.shard_map(lambda v: comm(v, 0), mesh=mesh,
                                  in_specs=spec, out_specs=spec,
                                  check_vma=False))(x)
    want = topo_util.to_weight_matrix(topology).T @ x_np
    err = float(np.max(np.abs(np.asarray(mixed) - want)))
    return {"ok": bool(err <= tol), "max_abs_err": err, "tolerance": tol,
            "compared": {"mixing_max_abs_err": [err, tol]}}


def hlo_facts(step, args, n_chips, expect_permutes):
    """Collective counts and wire bytes of the compiled step (exact, from
    the HLO).  On several chips the step must hold exactly the schedule's
    ``collective-permute``s and no ``all-reduce``."""
    text = step.lower(*args).compile().as_text()
    counts, bytes_ = wire.wire_stats(text)
    ok, compared = True, {}
    if n_chips > 1:
        compared = {
            "permutes_off_schedule": [abs(counts.get("collective-permute", 0)
                                          - expect_permutes), 0],
            "cross_chip_all_reduces": [counts.get("all-reduce", 0), 0]}
        ok = all(v <= limit for v, limit in compared.values())
    return {"ok": ok, "collective_counts": counts, "compared": compared,
            "expect_permutes": expect_permutes,
            "wire_bytes_per_call": int(sum(bytes_.values()))}
