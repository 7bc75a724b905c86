"""Kind ``train``: blocks of K program calls of a family's training step.

After warm-up, blocks of ``calls_per_block`` calls run until ``--seconds``
have passed.  Inside a block the host does not wait for the device; a block
ends in one barrier on its last loss.  A reading is the block's items over
its host time, the metric the MEDIAN of the readings; the first block after
warm-up is not a reading.  Correctness is checked after the window: every
loss finite, nothing compiled inside the window, the program's loss equal
to the plain reference's on the trained parameters, and on several chips
the mixing product and the compiled step's collectives.
"""
import gc
import time

import numpy as np

from perfbench.harness import estimators, manifest
from perfbench.harness.spans import Spans
from perfbench.runners import _common


def run(ctx):
    import jax
    devices = _common.start(ctx)
    cfg, traffic = ctx["config"], ctx["traffic"]
    family = manifest.load_module("families", cfg["family"])
    spans = ctx["spans"] = Spans()
    prog = family.build_train(cfg, traffic, devices, ctx["seed"])
    K = traffic["calls_per_block"]
    items_per_block = K * prog.items_per_call

    def block():
        t0 = time.perf_counter()
        for _ in range(K):
            with spans.span("step_call"):
                loss = prog.call()
        with spans.span("wait_loss"):
            jax.block_until_ready(loss)
        return loss, time.perf_counter() - t0

    for _ in range(traffic.get("warmup_calls", 2)):
        jax.block_until_ready(prog.call())
    block()                                # first block: not a reading
    setup_misses = ctx["watch"].misses
    gc.collect()
    gc.freeze()
    compiles_before = ctx["watch"].compiles
    blocks, losses = [], []
    t_open = time.perf_counter()
    ctx["setup_s"] = t_open - ctx["t_process_start"]
    while time.perf_counter() - t_open < ctx["seconds"]:
        loss, dt = block()
        blocks.append((items_per_block, dt))
        losses.append(loss)
    window_s = time.perf_counter() - t_open
    compiles_in_window = ctx["watch"].compiles - compiles_before
    dev = _common.device_report(devices)   # before the reference's buffers

    reduced = None
    if ctx["trace"]:
        spans.annotate = True
        traced_blocks = traffic.get("traced_blocks", 3)

        def body():
            for _ in range(traced_blocks):
                block()
        n_before = len(spans.records)
        reduced = _common.trace_tail(ctx, body)
        spans.annotate = False
        del spans.records[n_before:]       # window spans only, below
        ctx["traced_steps"] = traced_blocks * K * prog.steps_per_call

    losses = np.stack([np.asarray(l, np.float32).reshape(-1) for l in losses])
    finite = np.isfinite(losses).all(axis=1)
    steps_per_block = K * prog.steps_per_call
    ref = prog.reference_check()
    structure = prog.structure_check() \
        if (prog.n_chips > 1 or ctx["trace"]) else {"ok": True}
    _common.say(f"reference check: {ref}")
    _common.say(f"structure check: {structure}")
    _common.say(f"compilations inside the window: {compiles_in_window}; "
                f"loss first block {losses[0].mean():.4f}, last "
                f"{losses[-1].mean():.4f}")
    rates = estimators.block_rates(blocks)
    _common.keep_series(ctx, {
        "train_items_per_s_per_chip": (rates,
                                       estimators.whole_window_rate(blocks)),
    }, extra={"block_seconds": [s for _, s in blocks], "window_s": window_s,
              "reference_check": ref})
    return {
        "correct": bool(finite.all() and ref["ok"] and structure["ok"]
                        and compiles_in_window == 0),
        "attempted": int(len(blocks) * steps_per_block),
        "failed": int((~finite).sum() * steps_per_block),
        "device": dev,
        "trace": reduced,
        "checks": _common.compared(
            ref, structure, nonfinite_blocks=(int((~finite).sum()), 0),
            compiles_in_window=(compiles_in_window, 0)),
        "readings": {"train_items_per_s_per_chip": rates},
        "facts": {"setup_cache_misses": setup_misses,
                  "items_per_step": prog.items_per_call // prog.steps_per_call,
                  "steps_per_call": prog.steps_per_call,
                  "flops_per_item": prog.flops_per_item,
                  "n_chips": prog.n_chips,
                  "traced_steps": ctx.get("traced_steps"),
                  "structure": structure,
                  "compiles_in_window": compiles_in_window},
    }
