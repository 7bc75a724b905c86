"""Device scopes: which instruction of a compiled program belongs to which
named part of it.  The parse of compiled HLO text, the registry that keeps
compiled programs and parses nothing until asked, the scopes each family's
programs and the LM's train step carry, what a warm call does not touch,
where the held-work mark sits, and what ``BLUEFOG_TRACE`` arms."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import bluefog_tpu as bf
from bluefog_tpu.parallel import compose
from bluefog_tpu.serve import ServeConfig, ServeEngine
from bluefog_tpu.utils import hlo_bytes
from bluefog_tpu.utils import tracing

# what a device trace shows and a scope should name: the heavy instructions
HEAVY = ("fusion", "dot", "custom-call", "dynamic-update-slice",
         "convolution")


@pytest.fixture(autouse=True)
def _clean():
    tracing.reset()
    yield
    tracing.reset()


def small_step(w, x):
    def loss(w):
        with jax.named_scope("ffn"):
            h = jnp.tanh(x @ w["a"])
        with jax.named_scope("readout"):
            return jnp.sum((h @ w["b"]) ** 2)
    with jax.named_scope("GRADIENT"):
        l, g = jax.value_and_grad(loss)(w)
    with jax.named_scope("COMMUNICATE"):
        g = jax.tree.map(lambda v: v * 0.5 + jnp.roll(v, 1, 0) * 0.5, g)
    with jax.named_scope("ADAPT"):
        w = jax.tree.map(lambda p, q: p - 0.1 * q, w, g)
    return l, w


def small_compiled():
    w = {"a": jnp.ones((64, 64)), "b": jnp.ones((64, 64))}
    return jax.jit(small_step).lower(w, jnp.ones((8, 64))).compile()


@pytest.mark.parametrize("op_name,want", [
    ("jit(f)/GRADIENT/jvp(ffn)/tanh", ("ffn", "fwd")),
    ("jit(f)/GRADIENT/transpose(jvp(ffn))/transpose", ("ffn", "bwd")),
    ("jit(f)/GRADIENT/jvp(while)/body/dot_general", ("GRADIENT", "fwd")),
    ("jit(f)/ADAPT/sub", ("ADAPT", "")),
    ("jit(_decode_body)/while/body/closed_call/attn.window/attn.project/"
     "dot_general", ("attn.project", "")),
    ("jit(body)/attn.full/mla.attend/cache.read/exp", ("cache.read", "")),
    # the residual streams' maps: their own scopes, inside a layer loop and
    # inside another scope alike
    ("jit(_latent_prefill_body)/while/body/closed_call/hc.coef/div",
     ("hc.coef", "")),
    ("jit(_latent_decode_body)/while/body/ffn/hc.mix/reduce_sum",
     ("hc.mix", "")),
    # a jitted function called ``ffn`` is not the scope ``ffn``
    ("jit(f)/jit(ffn)/dot_general", ("", "")),
    ("jit(f)/pjit(readout)/jvp(mul)", ("", "fwd")),
    ("ragged-dot-none", ("", "")), ("", ("", "")),
])
def test_scope_of_takes_the_innermost_name_and_the_direction(op_name, want):
    assert tracing.scope_of(op_name) == want


def test_the_parse_gives_forward_backward_adapt_and_communicate_rows():
    """A small jitted step: every instruction of the entry computation
    with its own ``op_name``; a fusion by its root, with the ``op_name``s
    it holds; fused interiors left out."""
    text = small_compiled().as_text()
    module, comps = hlo_bytes.op_names(text)
    assert module == "jit_small_step"
    rows = {name: row for ops in comps.values() for name, row in ops.items()}
    found = {tracing.scope_of(op_name) for _, op_name, _, _ in rows.values()}
    assert {("ffn", "fwd"), ("ffn", "bwd"), ("readout", "fwd"),
            ("readout", "bwd"), ("ADAPT", "")} <= found
    # the exchange is fused into the update behind it: the fusion goes by
    # its root, and what it holds says so
    held = {tracing.scope_of(f) for r in rows.values() for f in r[2]}
    assert {("COMMUNICATE", ""), ("ADAPT", "")} <= held
    # a fusion is listed once, its fused instructions are not rows
    fusions = {n: r for n, r in rows.items() if r[0] == "fusion"}
    assert fusions and any(r[2] for r in fusions.values())
    inner = {m for line in text.splitlines() if " fusion(" in line
             for m in [line.split("calls=%")[1].split(",")[0].split(")")[0]]}
    assert inner and not inner & set(comps)
    # operands name instructions of the same computation
    for ops in comps.values():
        assert all(set(r[3]) <= set(ops) for r in ops.values())


def test_a_fusion_across_scopes_is_marked_and_what_has_no_scope_inherits():
    tracing.register_program("small", small_compiled())
    tab = tracing.device_scopes()["small"]
    assert tab["module"] == "jit_small_step"
    # the compiler fuses the gradient's last product with the exchange or
    # the update behind it: such a fusion is told by the scopes it holds
    assert tab["mixed"], tab
    for name, held in tab["mixed"].items():
        assert len(held) > 1 and tab["ops"][name][0] in held
    assert set(tab["inherited"].values()) <= {"fused", "operands", "users"}
    for name in tab["inherited"]:
        assert tab["ops"][name][0] in tracing.DEVICE_SCOPES


def test_inheritance_goes_by_operands_then_users_through_forwarding_ops():
    text = """HloModule jit_f, entry_computation_layout={()->f32[4]}

%body (p: (f32[4], f32[8,4])) -> (f32[4], f32[8,4]) {
  %p = (f32[4], f32[8,4]) parameter(0)
  %gte.0 = f32[4] get-tuple-element(%p), index=0
  %gte.1 = f32[8,4] get-tuple-element(%p), index=1
  %slice.1 = f32[1,4] dynamic-slice(%gte.1), metadata={op_name="jit(f)/while/body/dynamic_slice"}
  %bitcast.1 = f32[4] bitcast(%slice.1)
  %mul.1 = f32[4] multiply(%gte.0, %bitcast.1), metadata={op_name="jit(f)/while/body/ffn/mul"}
  %kernel.1 = f32[4] custom-call(%mul.1, %gte.0), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %add.1 = f32[4] add(%kernel.1, %mul.1), metadata={op_name="jit(f)/while/body/readout/add"}
  %copy.1 = f32[4] copy(%mul.1)
  %both.1 = f32[4] subtract(%mul.1, %add.1)
  %restack.1 = f32[1,4] fusion(%mul.1, %add.1), kind=kLoop, calls=%fused_restack, metadata={op_name="jit(f)/broadcast_in_dim"}
  ROOT %tuple.1 = (f32[4], f32[8,4]) tuple(%both.1, %gte.1)
}

%fused_restack (a: f32[4], b: f32[4]) -> f32[1,4] {
  %a.1 = f32[4] parameter(0)
  %b.1 = f32[4] parameter(1)
  %upd.1 = f32[4] multiply(%a.1, %b.1), metadata={op_name="jit(f)/ADAPT/mul"}
  ROOT %bc.1 = f32[1,4] bitcast(%upd.1), metadata={op_name="jit(f)/broadcast_in_dim"}
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4] parameter(0)
  ROOT %neg.1 = f32[4] negate(%a)
}
"""
    tab = tracing._scope_table(text)
    assert tab["module"] == "jit_f"
    # the scan's slice of its weights: nothing scoped among its operands,
    # so the part that reads it (through a bitcast)
    assert tab["ops"]["slice.1"] == ("ffn", "")
    assert tab["inherited"]["slice.1"] == "users"
    # the kernel the compiler expanded an op into: the part its operands
    # come from; a copy of a scoped value likewise
    assert tab["ops"]["kernel.1"] == ("ffn", "")
    assert tab["ops"]["copy.1"] == ("ffn", "")
    assert tab["inherited"]["kernel.1"] == tab["inherited"]["copy.1"] \
        == "operands"
    # a fusion whose root lies outside every scope (the step's re-stacking
    # of its outputs) goes by what it holds, whatever its operands
    assert tab["ops"]["restack.1"] == ("ADAPT", "")
    assert tab["inherited"]["restack.1"] == "fused"
    assert "upd.1" not in tab["ops"]                 # a fused interior
    # operands in two scopes: left alone; forwarding ops are never named
    assert tab["ops"]["both.1"] == ("", "") and "both.1" not in tab["inherited"]
    for name in ("gte.0", "bitcast.1", "tuple.1", "p"):
        assert tab["ops"][name] == ("", "")
    assert tab["ops"]["neg.1"] == ("", "")


class _Counted:
    """A compiled program that counts how often its text is asked for."""

    def __init__(self, compiled):
        self.compiled, self.asked = compiled, 0

    def as_text(self):
        self.asked += 1
        return self.compiled.as_text()


def test_the_registry_parses_nothing_until_asked_and_then_once():
    prog, made = _Counted(small_compiled()), []
    tracing.register_program("eager", prog)
    tracing.register_program("lazy", lambda: made.append(1) or prog)
    assert prog.asked == 0 and made == []
    tabs = tracing.device_scopes()
    assert sorted(tabs) == ["eager", "lazy"] and made == [1]
    assert prog.asked == 2
    assert tracing.device_scopes()["lazy"] is tabs["lazy"]
    assert prog.asked == 2 and made == [1]
    # registered again: the newer program, parsed again when asked
    tracing.register_program("eager", prog)
    assert prog.asked == 2
    tracing.device_scopes()
    assert prog.asked == 3
    tracing.reset()
    assert tracing.device_scopes() == {}


def heavy_share(compiled, table):
    """Share, by count, of the program's fusions, dots, custom calls and
    in-place writes that the table puts under a scope."""
    _, comps = hlo_bytes.op_names(compiled.as_text())
    heavy = [n for ops in comps.values() for n, r in ops.items()
             if r[0] in HEAVY]
    return sum(bool(table["ops"][n][0]) for n in heavy) / len(heavy)


def scopes_in(table):
    return {scope for scope, _ in table["ops"].values()} - {""}


DENSE = dict(vocab=64, d_model=32, heads=4, layers=2, seq_len=16)
VOCABULARY = {
    "dense": ({"attn.project", "cache.read", "cache.write", "ffn",
               "readout"},
              {"attn.project", "attn", "cache.write", "ffn", "readout"}),
    "latent": ({"mla.project", "mla.attend", "cache.read", "cache.write",
                "ffn", "moe.route", "moe.experts", "moe.shared", "readout"},
               {"mla.project", "mla.attend", "cache.write", "ffn",
                "moe.route", "moe.experts", "moe.shared", "readout"}),
    "hybrid": ({"attn.project", "cache.read", "cache.write", "ffn",
                "moe.route", "moe.experts", "moe.shared", "readout"},
               {"attn.project", "attn.window", "attn.full", "cache.write",
                "ffn", "moe.route", "moe.experts", "moe.shared", "readout"}),
}
# one mixer a layer: a Mamba mixer's three scopes, the attention layer's,
# the expert layer's with its latent projections; the state is read and
# written under ssm.scan and ssm.conv, never under cache.*
VOCABULARY["ssm"] = tuple(
    {"ssm.project", "ssm.conv", "ssm.scan", "attn.project", "cache.write",
     "ffn", "moe.route", "moe.latent", "moe.experts", "moe.shared",
     "readout"} | more for more in ({"cache.read"}, {"attn.full"}))
# the same family's second recurrent kind: a delta-rule mixer lies under the
# three scopes a Mamba mixer has (they mean the same parts), its experts read
# the hidden state itself (no latent projections)
VOCABULARY["delta"] = tuple(scopes - {"moe.latent"}
                            for scopes in VOCABULARY["ssm"])
# four residual streams round the latent block: the latent programs'
# vocabulary with the stream maps' two scopes
VOCABULARY["latent_streams"] = tuple(
    scopes | {"hc.coef", "hc.mix"} for scopes in VOCABULARY["latent"])


def make_engine(family, cpu_devices):
    if family == "dense":
        cfg = compose.LMConfig(**DENSE)
        m = compose.compose_parallelism(1, 1, 1, 1, devices=cpu_devices[:1])
        return ServeEngine(
            m, cfg, compose.init_lm_params(cfg, m, seed=3),
            ServeConfig(batch_buckets=(2,), prefill_buckets=(4, 8), slots=2,
                        max_len=16))
    import test_serve_hybrid
    import test_serve_latent
    import test_serve_delta
    import test_serve_ssm
    if family == "ssm":
        return test_serve_ssm.make_engine(cpu_devices)
    if family == "delta":
        return test_serve_delta.make_engine(cpu_devices)
    if family == "latent_streams":
        return test_serve_latent.make_engine(cpu_devices,
                                             test_serve_latent.STREAMED)
    mod = {"latent": test_serve_latent, "hybrid": test_serve_hybrid}[family]
    return mod.make_engine(cpu_devices)


@pytest.mark.parametrize("family", list(VOCABULARY))
def test_serving_programs_carry_their_vocabulary_and_warm_calls_touch_nothing(
        family, cpu_devices, monkeypatch):
    """Every engine program is registered as it is first compiled, the
    jit caches do not grow for it, its table shows every scope of the
    family's vocabulary, and a warm call reaches neither the registry nor
    a compiled program's text."""
    eng = make_engine(family, cpu_devices)
    eng.warmup()
    sizes = eng._jit_sizes()
    assert sorted(tracing._programs) == sorted(eng.program_memory())
    assert tracing._tables == {}                  # nothing parsed yet
    tabs = tracing.device_scopes()
    assert eng._jit_sizes() == sizes
    decode, prefill = VOCABULARY[family]
    for key, tab in tabs.items():
        want = decode if key.startswith("decode") else prefill
        assert want <= scopes_in(tab), (key, want - scopes_in(tab))
        assert heavy_share(tracing._programs[key], tab) >= 0.9, key
        assert tab["module"].startswith("jit__") and "_body" in tab["module"]
    # two buckets of one jit: one module name, two tables
    assert len({t["module"] for k, t in tabs.items()
                if k.startswith("prefill")}) == 1

    def never(*a, **k):
        raise AssertionError("a warm call reached the scope registry")
    monkeypatch.setattr(tracing, "register_program", never)
    monkeypatch.setattr(tracing, "device_scopes", never)
    monkeypatch.setattr(jax.stages.Compiled, "as_text", never)
    S = eng.scfg.batch_buckets[0]
    tok, _ = eng.prefill(0, 0, [1, 2, 3])
    toks = np.zeros((1, S), np.int32)
    toks[0, 0] = tok
    slots = np.full((1, S), eng.cache_cfg.trash_slot, np.int32)
    slots[0, 0] = 0
    lens = np.zeros((1, S), np.int32)
    lens[0, 0] = 3
    eng.decode(toks, slots, lens)
    assert eng._jit_sizes() == sizes


def lm_step(cpu_devices, dp=2):
    cfg = compose.LMConfig(vocab=64, d_model=32, heads=4, layers=2,
                           seq_len=16, micro=1, batch=2)
    m = compose.compose_parallelism(dp, 1, 1, 1, devices=cpu_devices[:dp])
    step, strategy = compose.make_train_step(
        m, compose.make_lm_grad_fn(cfg, m), optax.adam(1e-2))
    params = compose.device_put(m, compose.init_lm_params(cfg, m, seed=0))
    from bluefog_tpu import optimizers as bfopt
    state = bfopt.init_distributed(strategy, params)
    return step, params, state, compose.make_lm_batch(cfg, m, seed=0)


def test_lm_train_step_splits_by_phase_direction_and_block_scope(
        cpu_devices, monkeypatch):
    """The LM's step: forward and backward rows under GRADIENT alone, the
    block's scopes in both directions, the optimizer and the neighbour
    exchange under their own; registered lazily by the first call, which
    lowers nothing, and never touched by a warm call."""
    step, params, state, batch = lm_step(cpu_devices)
    params, state, loss = step(params, state, batch)[:3]
    assert list(tracing._programs) == ["train_step"]
    # the step's own outputs as its inputs: the shardings it settles on
    params, state, loss = step(params, state, batch)[:3]
    jax.block_until_ready(loss)
    assert not hasattr(tracing._programs["train_step"], "as_text")  # a thunk
    size = step._cache_size()
    tab = tracing.device_scopes()["train_step"]
    assert step._cache_size() == size
    rows = set(tab["ops"].values())
    for scope in ("attn.project", "attn", "ffn", "readout"):
        assert {(scope, "fwd"), (scope, "bwd")} <= rows, (scope, rows)
    assert {("ADAPT", ""), ("COMMUNICATE", "")} <= rows
    assert heavy_share(tracing._programs["train_step"], tab) >= 0.9
    # differentiation happens under GRADIENT and nowhere else
    _, comps = hlo_bytes.op_names(
        tracing._programs["train_step"].as_text())
    for ops in comps.values():
        for _, op_name, _, _ in ops.values():
            if "jvp(" in op_name or "transpose(" in op_name:
                assert "GRADIENT" in op_name, op_name

    def never(*a, **k):
        raise AssertionError("a warm call reached the scope registry")
    monkeypatch.setattr(tracing, "register_program", never)
    monkeypatch.setattr(tracing, "device_scopes", never)
    monkeypatch.setattr(jax.stages.Compiled, "as_text", never)
    assert "_call" not in vars(step)        # the first call took itself out
    jax.block_until_ready(step(params, state, batch)[2])
    assert step._cache_size() == size


@pytest.mark.parametrize("family", ["latent", "hybrid", "ssm", "delta"])
def test_held_work_mark_sits_under_the_decode_call_not_in_collect(
        family, cpu_devices, monkeypatch):
    """``collect`` (over its ``wait`` and ``read_back`` and nothing else)
    closes before the mark opens, and the mark closes before
    ``decode_call`` does: a mark never goes inside a stage."""
    eng = make_engine(family, cpu_devices)
    eng.warmup()
    order, real = [], eng._stage

    class Recorded:
        def __init__(self, name, attrs):
            self.name, self.stage = name, real(name, **attrs)

        def __enter__(self):
            order.append(("open", self.name))
            return self.stage.__enter__()

        def __exit__(self, *exc):
            order.append(("close", self.name))
            return self.stage.__exit__(*exc)

    monkeypatch.setattr(eng, "_stage", lambda name, **a: Recorded(name, a))
    S = eng.scfg.batch_buckets[0]
    trash = np.full((1, S), eng.cache_cfg.trash_slot, np.int32)
    eng.decode(np.zeros((1, S), np.int32), trash, np.zeros((1, S), np.int32))
    assert order[-9:] == [("open", "collect"),
                          ("open", "wait"), ("close", "wait"),
                          ("open", "read_back"), ("close", "read_back"),
                          ("close", "collect"),
                          ("open", "held_work"), ("close", "held_work"),
                          ("close", "decode_call")]


def test_every_instruction_of_a_delta_layer_lies_under_the_recurrent_scopes():
    """``ffn``, ``attn.*`` and ``cache.*`` keep meaning what they mean: a
    delta-rule layer, over a prompt (projections, the blocks' convolution,
    the L2 norms, the in-chunk system, the state's landing) and over a
    state (one token a lane), compiles to instructions under
    ``ssm.project``, ``ssm.conv`` and ``ssm.scan`` alone, all three."""
    import test_serve_delta as delta
    from bluefog_tpu.models import decoder
    from bluefog_tpu.serve import kv_cache as kv
    cfg = delta.CFG
    lp = jax.tree.map(lambda a: a[0], delta.make_params(cfg, 1)["layers"][2])
    cc = kv.SsmCacheConfig.of(cfg, 4, 16, jnp.float32)
    cache = {k: jnp.zeros(s, cc.dtypes()[k]) for k, s in cc.shapes().items()}

    def prompt(lp, x, cache):
        def mix(h):
            qkv, f, b, z = decoder.delta_project(cfg, lp, h)
            o, state, kept = decoder.delta_scan_chunked(cfg, lp, qkv, f, b,
                                                        jnp.int32(6))
            return decoder.delta_gate_out(cfg, lp, o, z), kv.ssm_prefill(
                cache, 1, jnp.int32(2), state, kept)
        return decoder.mixer_block(cfg, lp, x, "delta", mix)

    def token(lp, x, cache):
        slots = jnp.array([2, 0, 4, 4])

        def mix(h):
            qkv, f, b, z = decoder.delta_project(cfg, lp, h)
            qkv, nc = kv.ssm_conv_step(
                cache, 1, slots, qkv,
                lambda x, prev: decoder.mamba_conv(cfg, lp, x, prev))
            with jax.named_scope("ssm.scan"):
                g, beta = decoder.delta_discretize(cfg, lp, f, b)
            o, nc = kv.ssm_state_step(nc, 1, slots, decoder.delta_step, g,
                                      beta, *decoder.delta_split(cfg, qkv))
            return decoder.delta_gate_out(cfg, lp, o, z), nc
        return decoder.mixer_block(cfg, lp, x, "delta", mix)

    for fn, T in ((prompt, 8), (token, 4)):
        x = jnp.ones((T, cfg.d_model))
        tab = tracing._scope_table(
            jax.jit(fn).lower(lp, x, cache).compile().as_text())
        scopes = {scope for scope, _ in tab["ops"].values()} - {""}
        assert scopes == {"ssm.project", "ssm.conv", "ssm.scan"}, scopes


def test_bluefog_trace_arms_at_init_and_flush_writes_the_tables(
        tmp_path, monkeypatch, cpu_devices):
    monkeypatch.setenv(tracing.ENV_TRACE, str(tmp_path))
    assert not tracing.enabled()
    bf.init(devices=cpu_devices)
    assert tracing.enabled()
    tracing.register_program("small", small_compiled())
    path = tracing.flush()
    assert os.path.dirname(path) == str(tmp_path)
    with open(tracing.scopes_path()) as f:
        doc = json.load(f)
    assert doc["schema"] == tracing.SCHEMA and doc["rank"] == 0
    assert doc["programs"]["small"]["module"] == "jit_small_step"
    assert ["ADAPT", ""] in doc["programs"]["small"]["ops"].values()
    # no program, no table file
    tracing.reset()
    tracing.configure(str(tmp_path / "empty"))
    tracing.flush()
    assert os.listdir(tmp_path / "empty") == ["trace_rank0.trace.jsonl"]
