"""Record the small chip trace that tests check the scope reader on.

    python3 perfbench/tools/record_scopes_fixture.py      (on the chip)

A tiny dense serving engine (4 layers of 256, 8 slots x 128, one decode
bucket, two prefill buckets) is warmed up and then, under the profiler and
inside a ``pb:window`` span, prefills two prompts a bucket and decodes
eight steps.  The trace is converted to the plain structure of
harness/scopes.py and written, gzipped, to
chiprun_out/fixture/scopes_small.json.gz together with the programs' scope
tables (``tracing.device_scopes()``) and what the run itself observed
(calls by program key, prompt tokens), which the test compares with.  The
stats of one enqueue and one module event are printed: what joins them.
"""
import gzip
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from perfbench.harness import program_spans, scopes, trace  # noqa: E402


def main():
    import bluefog_tpu as bf
    from bluefog_tpu.parallel import compose
    from bluefog_tpu.serve import ServeConfig, ServeEngine
    from bluefog_tpu.utils import tracing
    bf.init()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("record_scopes_fixture: no TPU", file=sys.stderr)
        return 2
    cfg = compose.LMConfig(vocab=1024, d_model=256, heads=4, layers=4,
                           ffn_mult=4, seq_len=128)
    m = compose.compose_parallelism(1, 1, 1, 1, devices=[dev])
    eng = ServeEngine(m, cfg, compose.init_lm_params(cfg, m, seed=1),
                      ServeConfig(batch_buckets=(8,), prefill_buckets=(16, 32),
                                  slots=8, max_len=128, dtype=jnp.bfloat16))
    eng.warmup()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, n).tolist() for n in (9, 14, 20, 31)]
    out_dir = os.path.join(ROOT, "chiprun_out", "fixture")
    tdir = os.path.join(out_dir, "raw_scopes")
    os.makedirs(tdir, exist_ok=True)
    steps = 8
    jax.profiler.start_trace(tdir)
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        toks = np.zeros((1, 8), np.int32)
        slots = np.full((1, 8), eng.cache_cfg.trash_slot, np.int32)
        lens = np.zeros((1, 8), np.int32)
        for slot, prompt in enumerate(prompts):
            toks[0, slot] = eng.prefill(0, slot, prompt)[0]
            slots[0, slot], lens[0, slot] = slot, len(prompt)
        for _ in range(steps):
            toks = eng.decode(toks, slots, lens)[:, -1]
            lens = lens + (slots < 8)
    jax.profiler.stop_trace()
    path = trace.find_xplane(tdir)
    from jax.profiler import ProfileData
    shown = set()
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                kind = ev.name if ev.name in (
                    program_spans.ENQUEUE, program_spans.COMPLETE) else (
                    line.name if line.name == scopes.MODULE_LINE else None)
                if kind and kind not in shown:
                    shown.add(kind)
                    print(f"stats of one {kind!r} event "
                          f"({plane.name} / {line.name}): {dict(ev.stats)}")
    doc = scopes.load(path)
    tables = tracing.device_scopes()
    shift = program_spans.Analysis(doc).shift_ns
    ana = scopes.Analysis(doc, tables, shift)
    print("\n".join(ana.report()))
    doc["tables"] = tables
    doc["recorded"] = {
        "device_kind": dev.device_kind, "jax": jax.__version__,
        "shift_ns": shift, "decode_calls": steps,
        "prefill_calls": {"prefill Tpad=16": 2, "prefill Tpad=32": 2},
        "prefill_tokens": {"prefill Tpad=16": 9 + 14,
                           "prefill Tpad=32": 20 + 31}}
    with gzip.open(os.path.join(out_dir, "scopes_small.json.gz"), "wt") as f:
        json.dump(doc, f)
    print("fixture bytes:",
          os.path.getsize(os.path.join(out_dir, "scopes_small.json.gz")))
    import shutil
    shutil.rmtree(tdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
