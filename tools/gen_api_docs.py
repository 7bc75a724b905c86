"""Generate docs/API.md from the package's docstrings.

The counterpart of the reference's sphinx tree (``docs/*.rst``): one
markdown file covering the public surface, cross-linked to the reference
names documented in ``docs/PARITY.md``.  Regenerate after API changes:

    python tools/gen_api_docs.py
"""
import inspect
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

MODULES = [
    ("bluefog_tpu.api", "Core API (init, ops, synchronization)"),
    ("bluefog_tpu.topology", "Topologies (static + dynamic generators)"),
    ("bluefog_tpu.schedule", "Communication schedules (topology compiler)"),
    ("bluefog_tpu.optimizers", "Distributed optimizer strategies"),
    ("bluefog_tpu.ops.collectives", "Collective ops (gossip primitives)"),
    ("bluefog_tpu.ops.windows", "Window ops (one-sided mailboxes)"),
    ("bluefog_tpu.ops.ring", "Ring attention (sequence parallelism)"),
    ("bluefog_tpu.ops.ulysses", "Ulysses attention (all-to-all SP)"),
    ("bluefog_tpu.ops.pallas_attention", "Pallas flash-attention kernels"),
    ("bluefog_tpu.ops.pallas_decode", "Paged flash-decode kernel (serving)"),
    ("bluefog_tpu.ops.pallas_delta",
     "Delta-rule kernel (a prompt's gated delta rule, serving)"),
    ("bluefog_tpu.parallel.context", "Mesh context (init/topology state)"),
    ("bluefog_tpu.parallel.exec_cache",
     "Warm executable pool (recompile-free regrowth)"),
    ("bluefog_tpu.parallel.windows", "Window registry (named windows)"),
    ("bluefog_tpu.parallel.pipeline", "Pipeline parallelism"),
    ("bluefog_tpu.parallel.compose",
     "Composed parallelism (gossip-DP x PP x TP x Ulysses x EP)"),
    ("bluefog_tpu.parallel.tensor_parallel", "Tensor parallelism"),
    ("bluefog_tpu.parallel.expert", "Expert (MoE) parallelism"),
    ("bluefog_tpu.moe.layers", "Routed-MoE layers (router + expert FFN)"),
    ("bluefog_tpu.moe.model", "Routed-MoE reference LM"),
    ("bluefog_tpu.checkpoint", "Checkpointing (orbax, elastic, async)"),
    ("bluefog_tpu.serve.engine", "Serving engine (prefill + fused decode)"),
    ("bluefog_tpu.serve.kv_cache", "Slotted paged KV cache"),
    ("bluefog_tpu.serve.scheduler", "Continuous batching scheduler"),
    ("bluefog_tpu.serve.refresh", "Live gossip weight refresh"),
    ("bluefog_tpu.data", "Sharded input pipeline"),
    ("bluefog_tpu.fusion", "Tensor fusion (per-dtype bucketing)"),
    ("bluefog_tpu.models", "Model zoo"),
    ("bluefog_tpu.models.decoder",
     "The composed LM's decoder block (norm, rope, block, shapes)"),
    ("bluefog_tpu.run.launcher", "bfrun-tpu launcher"),
    ("bluefog_tpu.run.interactive", "Interactive multi-host mode"),
    ("bluefog_tpu.utils.utility", "Broadcast utilities (restart flow)"),
    ("bluefog_tpu.utils.torch_compat", "PyTorch migration helpers"),
    ("bluefog_tpu.utils.tf_compat", "TensorFlow/Keras migration helpers"),
    ("bluefog_tpu.utils.config", "Environment configuration"),
    ("bluefog_tpu.utils.timeline", "Timeline tracing"),
    ("bluefog_tpu.utils.metrics", "Live metrics registry + exporters"),
    ("bluefog_tpu.utils.tracing", "Request-scoped span tracing"),
    ("bluefog_tpu.utils.timeseries", "Bounded metric history rings"),
    ("bluefog_tpu.utils.fleetview",
     "Fleet view (gossiped whole-fleet metric carrier)"),
    ("bluefog_tpu.diagnostics", "Consensus-health probes + peer health"),
    ("bluefog_tpu.utils.watchdog", "Stall watchdog"),
    ("bluefog_tpu.resilience", "Fault tolerance (healing + rollback)"),
    ("bluefog_tpu.utils.chaos", "Deterministic fault injection"),
    ("bluefog_tpu.autotune.tuner", "Strategy autotuner (bf.autotune)"),
    ("bluefog_tpu.autotune.plan", "Autotune plans (persist/apply/replay)"),
    ("bluefog_tpu.autotune.candidates", "Autotune candidate enumeration"),
    ("bluefog_tpu.autotune.cost_model", "Autotune analytic cost model"),
    ("bluefog_tpu.autotune.bank", "Autotune measurement bank (tier 2)"),
    ("bluefog_tpu.autotune.trials", "Autotune live micro-trials (tier 3)"),
    ("bluefog_tpu.utils.hlo_bytes", "Wire-byte accounting from HLO"),
]


def _strip_addrs(text):
    import re
    # repr'd default objects embed memory addresses — nondeterministic
    # churn on every regeneration (signatures AND dataclass auto-docstrings)
    return re.sub(r" at 0x[0-9a-f]+", "", text)


def _doc_head(obj, max_paras=1):
    doc = inspect.getdoc(obj)
    if not doc:
        return "*(no docstring)*"
    paras = doc.split("\n\n")
    return _strip_addrs("\n\n".join(paras[:max_paras]).strip())


def _signature(obj):
    try:
        sig = str(inspect.signature(obj))
    except (ValueError, TypeError):
        return "(...)"
    return _strip_addrs(sig)


def _members(mod):
    names = getattr(mod, "__all__", None)
    out = []
    for name in names if names else sorted(vars(mod)):
        if name.startswith("_"):
            continue
        obj = getattr(mod, name, None)
        if obj is None or inspect.ismodule(obj):
            continue
        defined_here = getattr(obj, "__module__", None) == mod.__name__
        if not (names or defined_here):
            continue   # without __all__, skip re-exports
        # a jitted function is a wrapper object round the function
        if inspect.isfunction(inspect.unwrap(obj)) or inspect.isclass(obj):
            out.append((name, obj))
    return out


def main():
    import importlib

    lines = [
        "# API reference",
        "",
        "Generated from docstrings by `tools/gen_api_docs.py` — do not edit",
        "by hand.  Reference-name cross-links: `docs/PARITY.md`; design",
        "rationale: `docs/DESIGN.md`; measured numbers:",
        "`docs/PERFORMANCE.md`.",
        "",
        "Most names are re-exported at the top level: `import bluefog_tpu as",
        "bf; bf.neighbor_allreduce(...)`, `bf.optimizers.*`, `bf.topology.*`.",
        "",
    ]
    toc = ["## Contents", ""]
    body = []
    for mod_name, title in MODULES:
        mod = importlib.import_module(mod_name)
        anchor = mod_name.replace(".", "")
        toc.append(f"- [`{mod_name}` — {title}](#{anchor})")
        body += [f'<a name="{anchor}"></a>', "", f"## `{mod_name}` — {title}",
                 ""]
        mod_doc = _doc_head(mod, max_paras=1)
        if mod_doc != "*(no docstring)*":
            body += [mod_doc, ""]
        for name, obj in _members(mod):
            if inspect.isclass(obj):
                body += [f"### `{name}`", "", _doc_head(obj, 2), ""]
                methods = [
                    (n, m) for n, m in inspect.getmembers(obj)
                    if not n.startswith("_")
                    and (inspect.isfunction(m) or inspect.ismethod(m))
                    and m.__qualname__.startswith(obj.__name__ + ".")]
                for mname, meth in methods:
                    body += [f"- **`.{mname}{_signature(meth)}`** — "
                             f"{_doc_head(meth, 1)}"]
                if methods:
                    body.append("")
            else:
                body += [f"### `{name}{_signature(obj)}`", "",
                         _doc_head(obj, 2), ""]
    out = "\n".join(lines + toc + [""] + body).rstrip() + "\n"
    path = os.path.join(os.path.dirname(__file__), os.pardir, "docs",
                        "API.md")
    with open(path, "w") as f:
        f.write(out)
    print(f"wrote {os.path.normpath(path)} "
          f"({len(out.splitlines())} lines, {len(MODULES)} modules)")


if __name__ == "__main__":
    main()
