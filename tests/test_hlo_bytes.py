"""Unit tests for the shared wire-byte counter (bluefog_tpu.utils.hlo_bytes).

Hand-written HLO lines pin the per-collective accounting rules the cost
model and the benchmark's wire-byte reader both rely on: sync/async double-count
avoidance, tuple results, tile annotations, and group-size parsing.  The
"counter agrees with a real compile" cross-check lives in
tests/test_autotune.py, where the cost model's predicted bytes are
compared against compiled candidates.
"""
from bluefog_tpu.utils.hlo_bytes import total_wire_bytes, wire_stats


def test_permute_sync_counts_payload_once():
    txt = ("  %cp = f32[1024]{1,0} collective-permute(f32[1024]{1,0} %x), "
           "source_target_pairs={{0,1},{1,0}}\n")
    counts, bytes_ = wire_stats(txt)
    assert counts == {"collective-permute": 1}
    assert bytes_ == {"collective-permute": 4096}


def test_permute_start_tuple_halved_and_done_ignored():
    # -start result is (in…, out…, sync flags): the u32[] scalars are
    # dropped, the data half counted once; -done reuses the buffer.
    txt = (
        "  %cps = (f32[1024]{1,0}, f32[1024]{1,0}, u32[], u32[]) "
        "collective-permute-start(f32[1024]{1,0} %x), "
        "source_target_pairs={{0,1},{1,2},{2,3},{3,0}}\n"
        "  %cpd = f32[1024]{1,0} collective-permute-done("
        "(f32[1024]{1,0}, f32[1024]{1,0}, u32[], u32[]) %cps)\n"
    )
    counts, bytes_ = wire_stats(txt)
    assert counts == {"collective-permute": 1}
    assert bytes_ == {"collective-permute": 4096}


def test_permute_combined_tuple_sums_all_buffers():
    # the combiner can merge several buffers into one permute: a sync
    # permute with a tuple result counts every transferred buffer
    txt = ("  %cp = (f32[256]{1,0}, bf16[512]{1,0}) "
           "collective-permute((f32[256], bf16[512]) %t), "
           "source_target_pairs={{0,1}}\n")
    _, bytes_ = wire_stats(txt)
    assert bytes_ == {"collective-permute": 256 * 4 + 512 * 2}


def test_all_gather_sends_n_minus_1_shards():
    # each chip contributes a 1/n shard to n-1 peers: out * (n-1)/n
    txt = ("  %ag = f32[8192]{1,0} all-gather(f32[1024]{1,0} %x), "
           "replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}\n")
    counts, bytes_ = wire_stats(txt)
    assert counts == {"all-gather": 1}
    assert bytes_ == {"all-gather": 8192 * 4 * 7 // 8}


def test_all_gather_start_uses_out_minus_in():
    txt = ("  %ags = (f32[1024]{1,0}, f32[8192]{1,0}) "
           "all-gather-start(f32[1024]{1,0} %x), "
           "replica_groups=[1,8]<=[8], dimensions={0}\n"
           "  %agd = f32[8192]{1,0} all-gather-done("
           "(f32[1024]{1,0}, f32[8192]{1,0}) %ags)\n")
    counts, bytes_ = wire_stats(txt)
    assert counts == {"all-gather": 1}
    assert bytes_ == {"all-gather": (8192 - 1024) * 4}


def test_reduce_scatter_counts_outbound_difference():
    # in - out = out * (n-1) bytes leave each chip
    txt = ("  %rs = f32[1024]{1,0} reduce-scatter(f32[8192]{1,0} %x), "
           "replica_groups=[1,8]<=[8], dimensions={0}, "
           "to_apply=%add\n")
    _, bytes_ = wire_stats(txt)
    assert bytes_ == {"reduce-scatter": 1024 * 4 * 7}


def test_all_reduce_payload_once_even_async():
    # -start result is the payload shape itself (not an (in, out) pair):
    # counted once, never halved
    sync = ("  %ar = f32[2048]{1,0} all-reduce(f32[2048]{1,0} %x), "
            "replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%add\n")
    start = ("  %ars = f32[2048]{1,0} all-reduce-start(f32[2048]{1,0} %x), "
             "replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%add\n"
             "  %ard = f32[2048]{1,0} all-reduce-done(f32[2048]{1,0} %ars)\n")
    for txt in (sync, start):
        counts, bytes_ = wire_stats(txt)
        assert counts == {"all-reduce": 1}
        assert bytes_ == {"all-reduce": 8192}


def test_all_to_all_counted_in_full():
    txt = ("  %a2a = bf16[4096]{1,0} all-to-all(bf16[4096]{1,0} %x), "
           "replica_groups=[1,8]<=[8], dimensions={0}\n")
    _, bytes_ = wire_stats(txt)
    assert bytes_ == {"all-to-all": 4096 * 2}


def test_tile_annotations_and_fusion_indent_tolerated():
    # TPU layouts carry tile annotations with parens; collectives printed
    # inside a fusion body are just deeper-indented lines of the same form
    txt = ("      %cp.1 = f32[1024]{1,0:T(8,128)} collective-permute("
           "f32[1024]{1,0:T(8,128)} %p), source_target_pairs={{0,1}}\n")
    counts, bytes_ = wire_stats(txt)
    assert bytes_ == {"collective-permute": 4096}
    assert counts == {"collective-permute": 1}


def test_group_size_iota_and_explicit_agree():
    explicit = ("  %ag = f32[800]{1,0} all-gather(f32[200]{1,0} %x), "
                "replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}\n")
    iota = ("  %ag = f32[800]{1,0} all-gather(f32[200]{1,0} %x), "
            "replica_groups=[2,4]<=[8], dimensions={0}\n")
    for txt in (explicit, iota):
        _, bytes_ = wire_stats(txt)
        assert bytes_ == {"all-gather": 800 * 4 * 3 // 4}


def test_non_collective_and_unknown_dtype_lines_ignored():
    txt = ("  %add = f32[1024]{1,0} add(f32[1024] %a, f32[1024] %b)\n"
           "  %tok = token[] after-all()\n"
           "  ROOT %t = (f32[1024]{1,0}) tuple(f32[1024]{1,0} %add)\n")
    counts, bytes_ = wire_stats(txt)
    assert counts == {} and bytes_ == {}


def test_total_is_sum_across_kinds():
    txt = ("  %ar = f32[2048]{1,0} all-reduce(f32[2048] %x), "
           "replica_groups=[1,8]<=[8], to_apply=%add\n"
           "  %cp = f32[1024]{1,0} collective-permute(f32[1024] %y), "
           "source_target_pairs={{0,1}}\n")
    assert total_wire_bytes(txt) == 8192 + 4096


def test_wire_stats_per_collective_accounting():
    """wire_stats derives per-chip wire bytes per collective kind: permute
    counts the transferred buffer once (also for the -start (in, out, sync)
    tuple), all-gather counts out - in (-start tuple double-counts the
    operand), reduce-scatter counts in - out, and all-reduce-start counts
    the payload once, NOT halved (round-3 advisor item)."""
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
