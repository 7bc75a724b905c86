"""What a run says its chip held: ``device_info`` on stand-in devices whose
``memory_stats()`` are the dicts a TPU v5 lite gave (perfbench/tools/
memory_probe.py and the cells' runs, PR 32, PERF.md section 7), on a
platform that reports none, and on one that reports a single key; the
reader of the reserved peak; the rehearsal's "would hold"."""
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import _shape  # noqa: E402
from perfbench.harness import device, manifest  # noqa: E402

GIB = 1 << 30
LIMIT = 16909336064


def tpu(in_use, peak_in_use, reserved, peak_reserved=None, **more):
    return {"num_allocs": 17, "bytes_in_use": in_use,
            "peak_bytes_in_use": peak_in_use, "largest_alloc_size": 1 << 30,
            "bytes_limit": LIMIT, "bytes_reserved": reserved,
            "peak_bytes_reserved": reserved if peak_reserved is None
            else peak_reserved, "bytes_reservable_limit": LIMIT - peak_in_use,
            "largest_free_block_bytes": LIMIT - peak_in_use - reserved,
            **more}


# as recorded on the chip (my chip runs, PR 32)
PROBE = {
    "fresh": tpu(27136, 27136, 0),
    "put_1gib": tpu(1073768960, 1073768960, 0),
    "called_2gib_temp": tpu(1074078208, 1074078720, 2147057664),
    "deleted": tpu(336384, 1074078720, 2147057664),
}
CELLS = {
    # cell: (stats after the window, arguments the compiler counts,
    #        what the chip holds: live now + reserved)
    "pythia-410m.serve-closed32": (
        tpu(4155178496, 4155383808, 51740672), 4.13e9,
        4155178496 + 51740672),
    # set-up held two copies of parameters and optimizer state for a moment
    # (12.96 GB live at most): a transient that counts for no floor
    "pythia-410m.train-seq2048": (
        tpu(6496606208, 12961761792, 5005606912), 6.48e9,
        6496606208 + 5005606912),
    "resnet50.train-b256": (
        tpu(452898304, 674215936, 8642233344), 0.36e9,
        452898304 + 8642233344),
    "pythia-410m.gossip4-seq2048": (
        tpu(6497442816, 12963179520, 7488749568), 6.48e9,
        6497442816 + 7488749568),
}


class Dev:
    platform, device_kind = "tpu", "TPU v5 lite"

    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


@pytest.mark.parametrize("cell", list(CELLS))
def test_peak_is_resident_plus_temporaries_on_the_recorded_dicts(cell):
    stats, arguments, peak = CELLS[cell]
    info = device.device_info([Dev(stats)])
    assert info["memory_reserved_peak_bytes"] == stats["peak_bytes_reserved"]
    assert info["memory_peak_bytes"] == peak == (stats["bytes_in_use"]
                                                 + stats["bytes_reserved"])
    assert "memory_held_bytes" not in info
    # at least the arguments the compiler counts and nine tenths of the
    # program's temporaries; never over the limit, which the two peaks'
    # SUM would be in LM training
    assert info["memory_peak_bytes"] >= arguments + 0.9 * stats[
        "peak_bytes_reserved"]
    assert info["memory_peak_bytes"] <= LIMIT
    assert (info["platform"], info["kind"], info["count"]) == (
        "tpu", "TPU v5 lite", 1)


def test_the_probes_steps():
    peak = {k: device.memory_peak(v) for k, v in PROBE.items()}
    assert peak["put_1gib"] - peak["fresh"] == pytest.approx(GIB, rel=0.02)
    # the compiler counted a temporary of 2,147,089,920 bytes
    assert peak["called_2gib_temp"] - peak["put_1gib"] == pytest.approx(
        2147089920, rel=0.02)
    # what is held NOW: once the 1 GiB array is deleted it counts no more
    assert peak["deleted"] == 336384 + 2147057664
    assert all(p <= LIMIT for p in peak.values())


@pytest.mark.parametrize("stats,want", [
    (None, (0, 0)),                                    # the CPU rehearsal
    ({}, (0, 0)),
    ({"peak_bytes_in_use": 5 * GIB}, (0, 0)),          # a live peak alone
    ({"bytes_in_use": 3 * GIB}, (3 * GIB, 0)),
    ({"bytes_in_use": 3 * GIB, "peak_bytes_in_use": 2 * GIB,
      "bytes_reserved": GIB, "peak_bytes_reserved": GIB}, (4 * GIB, GIB)),
    # a set-up transient (6 GiB live for a moment) passes no floor
    ({"bytes_in_use": GIB, "peak_bytes_in_use": 6 * GIB,
      "bytes_reserved": GIB, "peak_bytes_reserved": 2 * GIB},
     (2 * GIB, 2 * GIB)),
])
def test_platforms_that_report_few_keys_or_none(stats, want):
    info = device.device_info([Dev(stats)])
    assert (info["memory_peak_bytes"],
            info["memory_reserved_peak_bytes"]) == want


def test_the_fullest_chip_is_reported():
    low = tpu(6 * GIB, 6 * GIB, 7 * GIB)
    high = tpu(6 * GIB, 6 * GIB + 4096, 8 * GIB)
    info = device.device_info([Dev(low), Dev(high), Dev(None)])
    assert info["count"] == 3
    assert info["memory_peak_bytes"] == 14 * GIB
    assert info["memory_reserved_peak_bytes"] == 8 * GIB
    assert device.fullest([Dev(None), Dev(low)]) == low


@pytest.mark.parametrize("cell,gb", [
    ("resnet50.train-b256", 8.64), ("pythia-410m.train-seq2048", 5.01),
    ("pythia-410m.gossip4-seq2048", 7.49)])           # the ledger's, PR 31
def test_peak_hbm_reserved_reads_the_reserved_field(cell, gb):
    read = manifest.load_module(
        "metrics", "train_step.peak_hbm_reserved_gb").read
    run = {"facts": {"items_per_step": 1},
           "device": device.device_info([Dev(CELLS[cell][0])])}
    assert read(run) == pytest.approx(gb, rel=0.01)
    assert read(run) < run["device"]["memory_peak_bytes"] / 1e9
    # a serving run has no train step; a platform without the key, nothing
    assert read({"facts": {}, "device": run["device"]}) is None
    assert read({"facts": {"items_per_step": 1},
                 "device": device.device_info([Dev(None)])}) is None


def the_tool():
    sys.modules.pop("rehearse_aot", None)
    tools = os.path.join(ROOT, "perfbench", "tools")
    sys.path.insert(0, tools)
    try:
        import rehearse_aot
    finally:
        sys.path.remove(tools)
    return rehearse_aot


@pytest.mark.parametrize("family,kind", list(_shape.first_cells(
    manifest.load())))
def test_the_rehearsal_tool_builds_every_family_and_kind(family, kind):
    """perfbench/tools/rehearse_aot.py on CPU devices at the files' tiny
    sizes: every (family, kind) the manifest has lowers and compiles, by
    the tool's own builder or by the family adapter's ``aot_programs``,
    and each program's row says what its device would hold.  (At the real
    sizes for a described v5e the same call is the tool's ``main``.)"""
    import jax
    tool, man = the_tool(), manifest.load()
    cell = _shape.first_cells(man)[(family, kind)]
    chips = manifest.resolve_cell(man, cell)["cell"]["chips"]
    built = tool.programs(man, cell, jax.devices("cpu"), rehearsal=True)
    assert built and all(n == chips for _, _, n in built)
    if kind == "serve":     # a program a bucket, as the engine warms up
        eng = manifest.sized(manifest.resolve_cell(man, cell)["traffic"],
                             True)["engine"]
        assert [name for name, _, _ in built] == [
            f"decode_S{S}" for S in eng["batch_buckets"]] + [
            f"prefill_T{T}" for T in eng["prefill_buckets"]]
    for name, compiled, n in built:
        ma = compiled.memory_analysis()
        assert tool.would_hold(ma) >= ma.argument_size_in_bytes > 0
        if kind == "serve":      # the donated cache comes back in place
            assert ma.alias_size_in_bytes > 0


def test_a_family_the_tool_does_not_know_brings_its_own_builder(monkeypatch):
    """No builder of the tool's and no ``aot_programs`` of the family's:
    refused by name; with the hook, the family's word is taken as it is."""
    import jax
    tool, man = the_tool(), manifest.load()
    cell = "a.x-k1.serve-closed128-p2048"
    assert ("latent_moe", "serve") not in tool.BUILDERS
    real = manifest.load_module

    def a_family_with(**hooks):
        return lambda kind_dir, name, *a: types.SimpleNamespace(**hooks) \
            if kind_dir == "families" else real(kind_dir, name, *a)
    monkeypatch.setattr(manifest, "load_module", a_family_with())
    with pytest.raises(manifest.ManifestError, match="aot_programs"):
        tool.programs(man, cell, jax.devices("cpu"), rehearsal=True)
    seen = []
    monkeypatch.setattr(manifest, "load_module", a_family_with(
        aot_programs=lambda cfg, traffic, devices: seen.append(
            (cfg["family"], traffic["kind"], len(devices))) or []))
    assert tool.programs(man, cell, jax.devices("cpu"), rehearsal=True) == []
    assert seen == [("latent_moe", "serve", 1)]


@pytest.mark.parametrize("ma,want", [
    # the decode program: the donated cache comes back in place
    (dict(argument=4131916288, output=3321890816, alias=3321888768,
          temp=4764672), 4136683008),
    # nothing donated: outputs lie beside the arguments
    (dict(argument=100, output=40, alias=0, temp=7), 147),
    (dict(argument=100, output=40, alias=64, temp=7), 107),
])
def test_would_hold_counts_arguments_temporaries_and_fresh_outputs(ma, want):
    rehearse_aot = the_tool()
    stats = types.SimpleNamespace(**{k + "_size_in_bytes": v
                                     for k, v in ma.items()})
    assert rehearse_aot.would_hold(stats) == want
    assert rehearse_aot.FLOOR_BYTES == 4 * GIB == 2 * \
        rehearse_aot.FLOOR_BUSY_BYTES

