"""End-to-end grader proofs for tools/lm_bench.py (the composed LLM at
production shape, gossip-DP x PP x TP x Ulysses on one mesh).

Three claims are pinned here, all on the host backend:

* the live smoke run emits the full ``bluefog-lm-bench-2`` artifact with
  the step invariants intact (donation, retrace sentinel, loss descent)
  and a wire sweep whose DCN bytes shrink with the codec — and, with
  ``--moe``, the routed-MoE run's routing-health block on top;
* **AOT proofs** (``--aot-only``, test_pod_scale.py style): cross-slice
  (DCN) bytes follow the DP-leader out-degree — doubling the rank count
  moves the byte bill by degree ratio 3/2, not 2x — while PP/TP/SP
  collectives stay intra-slice at f32 and only the gossip permutes carry
  the wire codec dtype;
* **chaos**: a straggler-injected run's flight bundle is blamed by
  tools/postmortem.py with the right rank AND the right onset step, both
  live (subprocess) and against a committed fixture bundle.
"""
import importlib.util
import json
import math
import os
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
TOOL = os.path.join(REPO, "tools", "lm_bench.py")
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "flight_straggler.json")


def _load_postmortem():
    spec = importlib.util.spec_from_file_location(
        "postmortem_mod", os.path.join(REPO, "tools", "postmortem.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(*flags, timeout=420):
    """Run lm_bench in a clean subprocess and return the artifact.

    XLA_FLAGS must NOT leak from the pytest parent (conftest pins an
    8-device host platform; ``--virtual-cpu`` sizes the child's own mesh
    to dp*pp*tp*sp, which these proofs push to 16).
    """
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("BLUEFOG_") and k != "XLA_FLAGS"}
    p = subprocess.run(
        [sys.executable, TOOL, "--virtual-cpu", *flags],
        cwd=REPO, capture_output=True, text=True, timeout=timeout, env=env)
    assert p.returncode == 0, (p.stderr[-3000:], p.stdout[-500:])
    line = [ln for ln in p.stdout.splitlines() if ln.strip()][-1]
    return json.loads(line)


def test_lm_bench_smoke_artifact(tmp_path):
    """One command on the virtual mesh -> the full graded artifact."""
    out = tmp_path / "lm.json"
    doc = _run("--smoke", "--no-trace", "--wire", "bf16",
               "--out", str(out))
    assert doc == json.load(open(out))    # stdout line == --out artifact
    assert doc["schema"] == "bluefog-lm-bench-2"
    assert doc["moe"] is None             # dense run: the block stays null
    assert doc["ok"] is True
    assert doc["on_accelerator"] is False
    m = doc["mesh"]
    assert (m["dp"], m["pp"], m["tp"], m["sp"]) == (2, 2, 2, 1)
    assert m["n_chips"] == 8 and m["wire"] == "bf16"
    assert m["leader_degree"] >= 1 and m["spectral_gap"] > 0

    # throughput + roofline fields (MFU null off-TPU, by design)
    assert doc["per_step_s"] > 0 and doc["tokens_per_sec"] > 0
    assert doc["mfu"]["flops_per_token"] > 0
    assert doc["mfu"]["model_flops_per_sec"] > 0
    assert doc["mfu"]["peak_flops_per_chip"] is None
    assert doc["mfu"]["mfu"] is None

    # step invariants survive the full 4-axis composition
    inv = doc["invariants"]
    assert inv["donated"] and inv["donation_intact"]
    assert inv["retraces_after_warmup"] == 0
    assert doc["loss_decreased"] is True
    assert doc["losses"][1] < doc["losses"][0]

    # byte attribution: gossip is the only DCN traffic and carries bf16
    wb = doc["wire_bytes"]
    assert set(wb["dcn"]) == {"collective_permute"}
    assert wb["dcn_dtypes"] == ["bf16"]
    assert wb["ici_dtypes"] == ["f32"]
    assert wb["dcn_bytes"] > 0 and wb["ici_bytes"] > 0
    assert not wb["unknown"]

    # wire sweep: each codec strictly cheaper on DCN, ICI untouched
    sweep = {row["wire"]: row for row in doc["wire_sweep"]}
    assert set(sweep) == {None, "bf16", "fp8@64"}
    assert sweep[None]["dcn_bytes"] == 2 * sweep["bf16"]["dcn_bytes"]
    assert sweep["fp8@64"]["dcn_bytes"] < sweep["bf16"]["dcn_bytes"]
    assert len({row["ici_bytes"] for row in doc["wire_sweep"]}) == 1
    assert "f8E4M3FN" in sweep["fp8@64"]["dcn_dtypes"]


def test_lm_bench_moe_artifact():
    """``--moe --ep 2`` grades the routed-MoE LM on the 5-axis carve:
    schema-2 artifact with the routing-health block (entropy, dropped
    fraction, aux/z, usage entropy), invariants intact, expert
    all_to_alls intra-slice and gossip still the only DCN traffic."""
    doc = _run("--smoke", "--no-trace", "--no-sweep", "--moe",
               "--dp", "2", "--pp", "2", "--tp", "1", "--sp", "1",
               "--ep", "2", "--experts", "4", "--wire", "bf16")
    assert doc["schema"] == "bluefog-lm-bench-2"
    assert doc["ok"] is True
    m = doc["mesh"]
    assert (m["dp"], m["pp"], m["ep"]) == (2, 2, 2)
    assert m["num_experts"] == 4
    inv = doc["invariants"]
    assert inv["donation_intact"] and inv["retraces_after_warmup"] == 0
    assert doc["loss_decreased"] is True

    moe = doc["moe"]
    assert moe["num_experts"] == 4 and moe["ep"] == 2
    assert moe["capacity"] >= 1
    assert 0 < moe["n_active_params"] < doc["config"]["n_params"]
    assert 0.0 <= moe["dropped_fraction"] <= 1.0
    assert 0.0 <= moe["routing_entropy"] <= math.log(4) + 1e-6
    assert 0.0 <= moe["usage_entropy"] <= math.log(4) + 1e-6
    assert moe["aux_loss"] >= 1.0 - 1e-5      # Switch lower bound
    assert moe["z_loss"] > 0

    # the expert dispatch all_to_alls are intra-slice; DCN = gossip@bf16
    wb = doc["wire_bytes"]
    assert "all_to_all" in wb["ici"]
    assert "all_to_all" not in wb["dcn"]
    assert set(wb["dcn"]) == {"collective_permute"}
    assert wb["dcn_dtypes"] == ["bf16"]


def test_lm_bench_moe_dropless_artifact():
    """``--moe --dropless --router expert_choice``: the fast-path grader
    carries the dispatch head-to-head — the dropless step's compiled dot
    FLOPs beat the capacity twin's by at least the padding fraction, the
    row accounting explains the win exactly, zero tokens drop (hard-gated
    into ``ok``), expert choice reports coverage + perfectly flat usage,
    and the capacity twin's live step time is banked alongside."""
    doc = _run("--smoke", "--no-trace", "--no-sweep", "--moe",
               "--dropless", "--router", "expert_choice",
               "--dp", "2", "--pp", "2", "--tp", "1", "--sp", "1",
               "--ep", "2", "--experts", "4", "--wire", "bf16",
               timeout=600)
    assert doc["schema"] == "bluefog-lm-bench-2"
    assert doc["ok"] is True
    moe = doc["moe"]
    assert moe["dispatch"] == "dropless"
    assert moe["router_mode"] == "expert_choice"
    assert moe["dropped_fraction"] == 0.0      # by construction, ok-gated
    assert moe["aux_loss"] == 0.0              # EC needs no balance loss
    # perfectly flat usage up to the f32 metrics-carrier rounding
    assert abs(moe["usage_entropy"] - math.log(4)) < 1e-3
    assert 0.0 < moe["ec_coverage"] <= 1.0
    # active-FLOP MFU accounting is declared, not silently dense
    assert doc["mfu"]["flops_source"] == "active"

    # the graded head-to-head: compiled dot FLOPs, dropless vs capacity
    f = moe["dot_flops"]
    assert f["dropless"] < f["capacity"]
    assert f["ratio"] < 1.0
    assert f["delta"] >= f["min_expected_delta"] > 0
    r = f["rows_per_device"]
    # EC's static groups pad nothing: the GEMM-row win IS the padding
    # fraction the capacity scheme wastes (cf=1.25 -> 20% fewer rows)
    assert r["row_ratio"] <= 1.0 - f["padding_fraction"] + 1e-9
    assert r["dropless"] < r["capacity"]
    # the capacity twin ran live on the same mesh for the wall-clock delta
    assert doc["moe"]["per_step_s_capacity"] > 0

    # dispatch scheme changes nothing cross-slice: gossip-only DCN
    wb = doc["wire_bytes"]
    assert "all_to_all" in wb["ici"]
    assert set(wb["dcn"]) == {"collective_permute"}


def test_aot_dcn_bytes_follow_leader_degree():
    """The pod-scale scaling law at the heart of the decentralized claim:
    cross-slice bytes follow DP-leader out-degree (log2 dp for Exp2), not
    total rank count.  dp=4 -> dp=8 doubles the chips but moves the DCN
    byte bill only by 3/2 (degree 2 -> 3), at identical per-round bytes."""
    a = _run("--smoke", "--aot-only", "--no-sweep",
             "--dp", "4", "--pp", "2", "--tp", "1", "--sp", "1",
             "--wire", "bf16")
    b = _run("--smoke", "--aot-only", "--no-sweep",
             "--dp", "8", "--pp", "2", "--tp", "1", "--sp", "1",
             "--wire", "bf16")
    assert a["mesh"]["n_chips"] == 8 and b["mesh"]["n_chips"] == 16
    assert a["mesh"]["leader_degree"] == 2
    assert b["mesh"]["leader_degree"] == 3

    da, db = a["wire_bytes"]["dcn"], b["wire_bytes"]["dcn"]
    assert set(da) == set(db) == {"collective_permute"}
    # one cross-slice permute per gossip round == per out-edge
    assert da["collective_permute"]["count"] == 2
    assert db["collective_permute"]["count"] == 3
    # same per-chip model shards -> identical bytes per round; the total
    # scales as degree (3/2), NOT as rank count (2x)
    per_round_a = da["collective_permute"]["bytes"] // 2
    per_round_b = db["collective_permute"]["bytes"] // 3
    assert per_round_a == per_round_b > 0
    assert (db["collective_permute"]["bytes"] * 2
            == da["collective_permute"]["bytes"] * 3)


def test_aot_pp_tp_sp_stay_intra_slice():
    """Full 4-axis carving at 16 chips: every PP ppermute, TP/stage psum
    and Ulysses all_to_all is classified intra-slice at f32; the DCN side
    holds only the gossip permutes, carrying the fp8 codec payload."""
    doc = _run("--smoke", "--aot-only", "--no-sweep",
               "--dp", "2", "--pp", "2", "--tp", "2", "--sp", "2",
               "--wire", "fp8@64")
    wb = doc["wire_bytes"]
    assert wb["slice_size"] == 8
    assert set(wb["dcn"]) == {"collective_permute"}
    assert "f8E4M3FN" in wb["dcn_dtypes"]     # fp8 payload (+ f32 scales)
    # PP activations, TP/stage reductions and Ulysses head scatter all on
    # the intra-slice side, none downcast by the gossip codec
    assert set(wb["ici"]) >= {"all_reduce", "collective_permute",
                              "all_to_all"}
    assert wb["ici_dtypes"] == ["f32"]
    assert not wb["unknown"]


def test_chaos_straggler_blamed_by_postmortem(tmp_path):
    """Live chaos loop: inject a throttle on rank 5 from step 2, dump the
    flight bundle, and require tools/postmortem.py to blame the right
    rank at the right onset step."""
    fdir = tmp_path / "flight"
    doc = _run("--smoke", "--no-sweep", "--no-trace", "--iters", "6",
               "--chaos", "throttle:from=2,until=99,t=0.05,rank=5",
               "--flight-dir", str(fdir))
    assert doc["straggler"]["detected_ranks"] == [5]
    times = doc["straggler"]["step_times_s"]
    assert len(times) == 8 and max(times) == times[5]

    bundle = doc["flight_bundle"]
    assert os.path.exists(bundle)
    pm = _load_postmortem()
    rep = pm.report_from_files([bundle])
    assert rep["ok"] is True
    st = rep["step_time"]
    assert st["straggler_rank"] == 5
    assert st["skew_s"] == pytest.approx(0.05, rel=0.25)
    # right step: the first injected throttle lands at step 2 (from=2)
    chaos = [e for e in json.load(open(bundle))["events"]
             if e.get("kind") == "chaos"]
    assert chaos and min(e["step"] for e in chaos) == 2
    assert all(e["rank"] == 5 for e in chaos)


def test_postmortem_blames_committed_fixture():
    """Deterministic (no subprocess): the committed straggler bundle is
    blamed with rank 5, onset step 2 — schema drift in either the flight
    recorder or the postmortem tool breaks this first."""
    pm = _load_postmortem()
    rep = pm.report_from_files([FIXTURE])
    assert rep["schema"] == "bluefog-flight-1"
    st = rep["step_time"]
    assert st["straggler_rank"] == 5
    assert st["skew_s"] == pytest.approx(0.05, rel=0.25)
    bundle = json.load(open(FIXTURE))
    chaos = [e for e in bundle["events"] if e.get("kind") == "chaos"]
    assert min(e["step"] for e in chaos) == 2
    assert {e["rank"] for e in chaos} == {5}
    # the in-bundle consensus probe saw the same skew the report blames
    cons = [e for e in bundle["events"] if e.get("kind") == "consensus"]
    assert cons[-1]["stragglers"] == [5]
