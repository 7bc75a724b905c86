"""Decentralized LLM at production shape: gossip-DP x PP x TP x Ulysses.

One call to :func:`bluefog_tpu.parallel.compose.compose_parallelism` carves
the device mesh into four axes and validates the carving eagerly; the
composed transformer then trains through the full step machinery — buffer
donation, ``adapt_with_combine(delayed=True)`` pipelined gossip, and the
retrace sentinel all survive composition:

* ``dp``  — gossip data parallelism over the OUTERMOST axis: each replica
  neighbor-averages its parameters with its DP peers over the configured
  graph (default ``ExponentialTwoGraph``) instead of an allreduce.  With
  slice-major device order these permutes are the only cross-slice (DCN)
  traffic, and ``--wire bf16`` compresses exactly them.
* ``pp``  — the block stack is pipelined (activations ``ppermute`` stage
  to stage; ``jax.grad`` through the schedule IS the backward pipeline).
* ``tp``  — Megatron tensor parallelism inside every decoder block
  (column-split qkv/up, row-split out/down, one ``psum`` per sublayer).
* ``sp``  — Ulysses sequence parallelism (two ``all_to_all``s re-shard
  heads <-> sequence around local attention).
* ``ep``  — expert parallelism (``--ep``, with ``--experts`` /
  ``--capacity-factor``): swaps the dense FFN for the routed-MoE
  reference LM (``bluefog_tpu.moe``), sharding ``num_experts // ep``
  experts per peer with dispatch/combine ``all_to_all``s that stay
  intra-slice — gossip remains the only DCN-crossing traffic.

A copy-task LM (predict the token ``lag`` positions back) trains to low
loss, proving gradients flow through every stage boundary, tp psum, sp
all_to_all, AND the gossip mixing at once.  The same model/recipe is what
the benchmark's ``pythia-410m`` training cells run at published widths and
``tests/test_compose.py`` pins against float64 oracles.

Run:  python examples/llm_3d.py --virtual-cpu --steps 60
      python examples/llm_3d.py --virtual-cpu --sp 2 --tp 1 --wire fp8@64
      python examples/llm_3d.py --virtual-cpu --tp 1 --ep 2 --experts 4 \\
          --steps 40
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--virtual-cpu", action="store_true")
    parser.add_argument("--dp", type=int, default=2)
    parser.add_argument("--pp", "--stages", type=int, default=2, dest="pp")
    parser.add_argument("--tp", type=int, default=2)
    parser.add_argument("--sp", type=int, default=1,
                        help="Ulysses sequence-parallel ways")
    parser.add_argument("--ep", type=int, default=1,
                        help="expert-parallel ways (routed MoE when > 1 "
                             "or when --experts is given)")
    parser.add_argument("--experts", type=int, default=None,
                        help="total routed experts (enables the MoE LM)")
    parser.add_argument("--capacity-factor", type=float, default=2.0,
                        help="expert capacity factor for the MoE LM")
    parser.add_argument("--wire", default=None,
                        help="gossip DCN codec (bf16 / fp8@64 / ...)")
    parser.add_argument("--layers", type=int, default=4)
    parser.add_argument("--micro", type=int, default=4)
    parser.add_argument("--seq-len", type=int, default=32)
    parser.add_argument("--d-model", type=int, default=32)
    parser.add_argument("--heads", type=int, default=4)
    parser.add_argument("--steps", type=int, default=100)
    parser.add_argument("--lag", type=int, default=2)
    parser.add_argument("--lr", type=float, default=5e-3)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()

    moe = args.experts is not None or args.ep > 1
    n_needed = args.dp * args.pp * args.tp * args.sp * args.ep
    if args.virtual_cpu:
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count="
                f"{n_needed}").strip()

    import jax
    if args.virtual_cpu:
        jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import optax

    import bluefog_tpu as bf
    from bluefog_tpu import optimizers as bfopt
    from bluefog_tpu.parallel import compose

    bf.init(platform="cpu" if args.virtual_cpu else None)

    # one call carves + validates the whole 5-axis layout
    carve_kw = {}
    if moe:
        from bluefog_tpu import moe as bfmoe
        num_experts = args.experts or 4
        cfg = bfmoe.MoELMConfig(
            d_model=args.d_model, heads=args.heads, layers=args.layers,
            seq_len=args.seq_len, micro=args.micro, lag=args.lag,
            batch=max(2, args.ep), num_experts=num_experts,
            capacity_factor=args.capacity_factor)
        carve_kw = {"num_experts": num_experts,
                    "capacity_factor": args.capacity_factor}
    m = compose.compose_parallelism(
        args.dp, args.pp, args.tp, args.sp, args.ep,
        devices=bf.devices().ravel()[:n_needed], wire=args.wire,
        **carve_kw)
    if not moe:
        cfg = compose.LMConfig(
            d_model=args.d_model, heads=args.heads, layers=args.layers,
            seq_len=args.seq_len, micro=args.micro, lag=args.lag)
    cfg.validate(m)
    print(f"[llm_3d] carving {m.describe()}")

    grad_fn = (bfmoe.make_moe_grad_fn(cfg, m) if moe
               else compose.make_lm_grad_fn(cfg, m))
    step, strategy = compose.make_train_step(
        m, grad_fn, optax.adam(args.lr))
    if moe:
        params = bfmoe.init_moe_params(cfg, m, seed=args.seed)
        toks = bfmoe.make_moe_batch(cfg, m, seed=args.seed)
    else:
        params = compose.init_lm_params(cfg, m, seed=args.seed)
        toks = compose.make_lm_batch(cfg, m, seed=args.seed)
    state = bfopt.init_distributed(strategy, params)
    params = compose.device_put(m, params)

    first = l = None
    for i in range(args.steps):
        params, state, loss = step(params, state, toks)
        l = float(np.asarray(loss).mean())
        first = l if first is None else first
        if i % 20 == 0 or i == args.steps - 1:
            print(f"step {i}: loss {l:.4f}", flush=True)
    print(f"[llm_3d] mesh dp={m.dp} x pp={m.pp} x tp={m.tp} x sp={m.sp}"
          f" x ep={m.ep}"
          + (f" (E={m.num_experts} cf={m.capacity_factor})" if moe else "")
          + f" (wire={m.wire}): loss {first:.3f} -> {l:.3f}")
    assert l < first * 0.7, "composed LM failed to train"


if __name__ == "__main__":
    main()
