"""CI coverage for the benchmark driver's exact train-step path.

Round-2 postmortem: ``bench.py`` crashed in the driver's official run because
its ``steps_per_call=1`` path built the batch with a steps axis
that :func:`bluefog_tpu.optimizers.make_train_step` only expects when
``steps_per_call > 1`` — and no test imported the flagship ResNet or the bench
script.  These tests run the real bench code (tiny shapes, the explicit
``BLUEFOG_BENCH_FORCE_CPU=1`` opt-in) on both sides of the steps-axis
contract so the measured path can never silently rot again; what bench.py
does without the opt-in and without a TPU is in tests/test_bringup.py.
Reference contrast: ``test/test_all_example.sh`` smokes every example; this is
the same idea for the benchmark driver.

Both steps-axis contracts run the script end to end in 1-device subprocesses
(cheap: no 8-way shard_map compile); the virtual-mesh test keeps the n>1
branch (topology + batch broadcast) covered in-process on the conftest mesh.
"""
import importlib.util
import json
import os
import re
import subprocess
import sys

import pytest

_BENCH = os.path.join(os.path.dirname(__file__), os.pardir, "bench.py")


def _strip_device_count(flags: str) -> str:
    return re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                  flags).strip()


def _bench_env(steps_per_call: int, device_count: int = 1) -> dict:
    env = dict(os.environ,
               BLUEFOG_BENCH_FORCE_CPU="1",
               JAX_PLATFORMS="cpu",
               BLUEFOG_BENCH_BATCH="1",
               BLUEFOG_BENCH_ITERS="1",
               BLUEFOG_BENCH_STEPS_PER_CALL=str(steps_per_call),
               BLUEFOG_BENCH_IMAGE_SIZE="32",
               BLUEFOG_BENCH_CLASSES="10")
    flags = _strip_device_count(env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count="
                        + str(device_count)).strip()
    return env


@pytest.mark.parametrize("steps_per_call", [1, 2])
def test_bench_script_both_steps_axis_contracts(steps_per_call):
    """End-to-end: the script under the CPU opt-in must exit 0 and print
    exactly one valid JSON line — on BOTH sides of the steps-axis contract
    (the round-2 crash was the steps_per_call=1 side)."""
    p = subprocess.run([sys.executable, _BENCH],
                       env=_bench_env(steps_per_call),
                       stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                       text=True, timeout=600)
    assert p.returncode == 0
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1
    out = json.loads(lines[0])
    assert out["metric"] == "resnet50_synthetic_imgs_per_sec_per_chip"
    assert out["value"] > 0
    assert out["unit"] == "img/s/chip"
    assert out["on_accelerator"] is False
    assert out["platform"] == "cpu"
    assert out["steps_per_call"] == steps_per_call


def test_run_bench_in_process_on_virtual_mesh(monkeypatch):
    """run_bench on the conftest's 8-device mesh: covers the n>1 branch
    (topology + batch broadcast) that the 1-device subprocess runs skip."""
    import jax

    spec = importlib.util.spec_from_file_location("bench", _BENCH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    monkeypatch.setenv("BLUEFOG_BENCH_BATCH", "1")
    monkeypatch.setenv("BLUEFOG_BENCH_ITERS", "1")
    monkeypatch.setenv("BLUEFOG_BENCH_STEPS_PER_CALL", "1")
    monkeypatch.setenv("BLUEFOG_BENCH_IMAGE_SIZE", "32")
    monkeypatch.setenv("BLUEFOG_BENCH_CLASSES", "10")
    result = mod.run_bench(False)
    assert result["value"] > 0
    # tiny-shape CPU throughput rounds vs_baseline down to 0.0 — only the
    # sign is meaningful here
    assert result["vs_baseline"] >= 0
    assert result["n_chips"] == jax.device_count()
    assert result["on_accelerator"] is False and result["mfu"] is None
    # schema-2 artifacts are strategy-aware even on the default path
    assert result["schema"] == "bluefog-bench-2"
    assert result["strategy"] == "neighbor_cta"
    assert result["algorithm"] == "neighbor_cta"
    assert result["plan_id"] is None
    # the artifact always reports the donation contract
    assert result["donated"] is True
    assert result["fused_per_step_s"] > 0


@pytest.mark.slow
def test_run_bench_fused_vs_spc1_probe(monkeypatch):
    """BLUEFOG_BENCH_COMPARE_SPC1=1 makes the artifact carry the fused vs
    single-step per-step comparison on the SAME workload."""
    spec = importlib.util.spec_from_file_location("bench", _BENCH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    monkeypatch.setenv("BLUEFOG_BENCH_BATCH", "1")
    monkeypatch.setenv("BLUEFOG_BENCH_ITERS", "1")
    monkeypatch.setenv("BLUEFOG_BENCH_STEPS_PER_CALL", "2")
    monkeypatch.setenv("BLUEFOG_BENCH_IMAGE_SIZE", "32")
    monkeypatch.setenv("BLUEFOG_BENCH_CLASSES", "10")
    monkeypatch.setenv("BLUEFOG_BENCH_COMPARE_SPC1", "1")
    result = mod.run_bench(False)
    cmp = result["fused_vs_spc1"]
    assert cmp is not None
    assert cmp["spc1_per_step_s"] > 0 and cmp["fused_per_step_s"] > 0
    assert cmp["fused_speedup"] > 0   # tiny CPU shapes: sign only, no bound


def _plan_doc(n_chips, fused_k=2):
    from bluefog_tpu.autotune.plan import make_plan_doc
    return make_plan_doc(
        config={"algorithm": "neighbor_cta",
                "topology": {"family": "exp2", "size": n_chips},
                "wire": None, "weights": "recv", "fused_k": fused_k,
                "delayed": False, "concurrent": None},
        objective="step_time", n_chips=n_chips, device_kind="cpu",
        predicted={}, audit={})


def test_run_bench_replays_autotune_plan(tmp_path, monkeypatch):
    """--plan replays the plan's EXACT configuration: algorithm, topology,
    fused-k — and the artifact records which plan steered it."""
    import jax

    spec = importlib.util.spec_from_file_location("bench_plan", _BENCH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    n = jax.device_count()
    doc = _plan_doc(n)
    plan_path = tmp_path / "plan.json"
    with open(plan_path, "w") as f:
        json.dump(doc, f)

    monkeypatch.setenv("BLUEFOG_BENCH_BATCH", "1")
    monkeypatch.setenv("BLUEFOG_BENCH_ITERS", "1")
    monkeypatch.setenv("BLUEFOG_BENCH_IMAGE_SIZE", "32")
    monkeypatch.setenv("BLUEFOG_BENCH_CLASSES", "10")
    monkeypatch.setenv("BLUEFOG_BENCH_PLAN", str(plan_path))
    result = mod.run_bench(False)
    assert result["value"] > 0
    assert result["schema"] == "bluefog-bench-2"
    assert result["strategy"] == "neighbor_cta"
    assert result["algorithm"] == "neighbor_cta"
    assert result["plan_id"] == doc["plan_id"]
    assert result["config_source"] == "plan:" + doc["plan_id"]
    assert result["steps_per_call"] == 2          # the plan's fused_k
    assert result["donated"] is True


def test_run_bench_refuses_plan_for_other_mesh(tmp_path, monkeypatch):
    """Plans replay exactly or not at all: a plan tuned for a different
    chip count aborts the run instead of silently re-configuring."""
    spec = importlib.util.spec_from_file_location("bench_planx", _BENCH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    doc = _plan_doc(4)                            # conftest mesh has 8
    plan_path = tmp_path / "plan.json"
    with open(plan_path, "w") as f:
        json.dump(doc, f)
    monkeypatch.setenv("BLUEFOG_BENCH_BATCH", "1")
    monkeypatch.setenv("BLUEFOG_BENCH_ITERS", "1")
    monkeypatch.setenv("BLUEFOG_BENCH_IMAGE_SIZE", "32")
    monkeypatch.setenv("BLUEFOG_BENCH_CLASSES", "10")
    monkeypatch.setenv("BLUEFOG_BENCH_PLAN", str(plan_path))
    with pytest.raises(RuntimeError, match="re-tune on this mesh"):
        mod.run_bench(False)


def test_wire_stats_per_collective_accounting():
    """wire_stats derives per-chip wire bytes per collective kind: permute
    counts the transferred buffer once (also for the -start (in, out, sync)
    tuple), all-gather counts out - in (-start tuple double-counts the
    operand), reduce-scatter counts in - out, and all-reduce-start counts
    the payload once, NOT halved (round-3 advisor item)."""
    sys.path.insert(0, os.path.join(os.path.dirname(_BENCH), "tools"))
    from strategy_bench import wire_stats

    hlo = "\n".join([
        # permute: 1024 f32 = 4096 B moved once
        "  %cp = f32[1024]{0} collective-permute(%a), "
        "source_target_pairs={{0,1}}",
        # permute-start: (in, out, sync, sync) tuple — still 4096 B
        "  %cps = (f32[1024]{0:T(8)}, f32[1024]{0:T(8)}, u32[], u32[]) "
        "collective-permute-start(%b), source_target_pairs={{0,1}}",
        # all-gather over 8 chips: out 8192 f32 -> wire = out*7/8 = 7*4096 B
        "  %ag = f32[8192]{0} all-gather(%c), dimensions={0}, "
        "replica_groups={{0,1,2,3,4,5,6,7}}",
        # all-gather-start result tuple (in, out): out - in = 7*4096 B
        "  %ags = (f32[1024]{0}, f32[8192]{0}) all-gather-start(%d), "
        "dimensions={0}, replica_groups=[1,8]<=[8]",
        # reduce-scatter over 8: out 1024 f32 -> wire = out*7 = 7*4096 B
        "  %rs = f32[1024]{0} reduce-scatter(%e), dimensions={0}, "
        "replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%add",
        # all-reduce-start: result IS the payload shape — not halved
        "  %ars = f32[1024]{0} all-reduce-start(%f), to_apply=%add",
        # combined multi-buffer permute-start (XLA's combiner): tuple is
        # (in f32, in bf16, out f32, out bf16, syncs) -> 4096 + 1024 B
        "  %cpm = (f32[1024]{0}, bf16[512]{0}, f32[1024]{0}, bf16[512]{0}, "
        "u32[], u32[]) collective-permute-start(%i, %j), "
        "source_target_pairs={{0,1}}",
        # fused all-reduce over two buffers: payload is their sum
        "  %ar = (f32[1024]{0}, bf16[512]{0}) all-reduce(%g, %h), "
        "to_apply=%add",
    ])
    counts, bytes_ = wire_stats(hlo)
    assert counts == {"collective-permute": 3, "all-gather": 2,
                      "reduce-scatter": 1, "all-reduce": 2}
    assert bytes_["collective-permute"] == 2 * 4096 + (4096 + 1024)
    assert bytes_["all-gather"] == 2 * 7 * 4096
    assert bytes_["reduce-scatter"] == 7 * 4096
    assert bytes_["all-reduce"] == 4096 + (4096 + 1024)
