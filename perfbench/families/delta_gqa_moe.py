"""Family ``delta_gqa_moe``: a pre-norm decoder whose layers are a mixer (a
gated delta-rule layer with a decay per channel and a matrix state a head,
or on the layers ``gqa_layers`` names gated grouped-query attention with no
position signal) and an expert layer (gated SiLU experts under a
bias-corrected sigmoid router beside a shared expert), served as ONE chip's
share of an expert-parallel deployment through ServeEngine + Scheduler on
the single-mixer programs (bluefog_tpu.models.decoder.SsmConfig: every
sublayer one entry of the plan): the router keeps its published width, the
chip holds the experts and the vocabulary slice the configuration file's
``deployment`` names, and what the absent experts would add is left out in
program and reference alike.

This file maps the source's key names onto SsmConfig, makes the weights on
the device from the seed leaf by leaf, and holds the bytes and operations
the per-layer shares are made of.  The comparison with the plain reference
(perfbench/reference/delta_gqa_moe.py) is the ``ssm_latent_moe`` family's,
number by number, under this file's limits.
"""
import numpy as np

from perfbench.families import _checks, latent_moe, ssm_latent_moe
from perfbench.families.composed_lm import serve_config
from perfbench.reference import delta_gqa_moe as reference

# What each limit stands between is in CHIP_READINGS below; the runs they
# come from are in PERF.md section 6 and docs/PERF_PR47_RECORD.md.  The cell
# serves in bf16 (weights, activations, K and V, the convolution's kept
# inputs; router, decays, the delta rule and its state in f32).  No sublayer
# injects more than 0.3-0.6 % of its output (call L of the record), but
# every entry's output is smaller than the residual it joins and a delta
# layer passes its input's distance on amplified, so the distance to the
# float32 reference grows by 0.006 a delta layer and 0.002 an expert layer
# to 0.06 of the residual behind the sixteenth entry: the logits of a sound
# run lie 0.043-0.060 of the largest from the reference's (17 runs), five times the
# state-space family's, and the limits are this family's own.  The CPU
# rehearsal states float32: the program then IS the reference's function up
# to the order of its sums.
# |program - reference| as a share of the largest reference logit, with the
# reference evaluated under the PROGRAM's expert selections
SERVE_LOGIT_TOL = {"bfloat16": 0.11, "float32": 1e-3}
DECODE_LOGIT_TOL = {"bfloat16": 0.12, "float32": 1e-3}
# how far the reference's best logit lies over its logit of the token the
# program chose, as a share of the largest reference logit
DECODE_GAP_TOL = {"bfloat16": 0.08, "float32": 1e-3}
# the state a slot holds after its last decode call against the reference's
# after the same tokens: the MEDIAN head's |held - reference| / |reference|
# of the FIRST delta layer.  That layer is the THIRD entry: its input is
# already 0.010 off, and its state reads 0.0166-0.0180 in a sound run (17).  The
# limit holds a wrong decay, beta or key to the reference (dt_bias zeroed
# reads 0.96); it does NOT tell a state kept in bfloat16 (0.0186 beside the
# same seed's 0.0174; by the elements over four times a head's rms 0.0114
# beside 0.0097), which BF16_STATE_SHARE below does.
STATE_TOL = {"bfloat16": 0.035, "float32": 1e-4}
# the share of the held states' nonzero elements whose float32 value a
# bfloat16 holds exactly.  The configuration states the state float32; a
# float32 sum reads 0.00002 here, a state kept in (or passed through)
# bfloat16 reads 1.
BF16_STATE_SHARE = 0.5
# every expert by which a selection differs from the reference's own has to
# lie within this much of the reference's top_k-th biased score
ROUTE_TIE_DELTA = {"bfloat16": 0.15, "float32": 1e-4}
# what the bf16 limits above stand between: name -> (the most the sound
# program read on the chip over its seeds, the least the nearest control
# read: its weights through int8 and back for the logits and the state, its
# router bias zeroed for the tie distance); my chip runs, PR 47
CHIP_READINGS = {"prefill_logit_err_share": (0.0515, 0.274),
                 "decode_logit_err_share": (0.0599, 0.318),
                 "decode_logit_gap_share": (0.0448, 0.225),
                 "route_tie_distance": (0.0628, 0.462),
                 "ssm_state_err_share": (0.0180, 0.109)}

KINDS = {"L": "delta", "G": "full", "E": "experts"}
# flops the delta rule itself costs a state element and token: the decay
# (1), S^T k (2), the rank-one update (2) and S^T q (2)
SCAN_FLOPS_PER_ELEMENT = 7

_plan = reference.plan


def ssm_config(cfg):
    from bluefog_tpu.models import decoder
    dep, lin = cfg["deployment"], cfg["linear_attn_config"]
    held = dep["held_experts"]
    if held[1] - held[0] != cfg["n_routed_experts"]:
        raise ValueError("n_routed_experts is the count HELD here and must "
                         "equal the deployment's held_experts range")
    if cfg["first_k_dense_replace"] or cfg["use_rope"] \
            or cfg["kda_use_full_proj"] or lin["num_kv_heads"] is not None:
        raise ValueError(
            "this family has no leading dense layer, no rotary, the decay "
            "and gate through low-rank pairs and a key-value head a head")
    return decoder.SsmConfig(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        plan=tuple(KINDS[c] for c in _plan(cfg)),
        ssm_heads=lin["num_heads"], ssm_head_dim=lin["head_dim"],
        ssm_groups=1, ssm_state=lin["head_dim"],
        conv_kernel=lin["short_conv_kernel_size"], chunk=cfg["chunk_size"],
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        latent=0, expert_ffn=cfg["moe_intermediate_size"],
        shared_ffn=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        num_experts=dep["router_outputs"], held_experts=held[1] - held[0],
        held_start=held[0], top_k=cfg["num_experts_per_tok"],
        route_scale=cfg["routed_scaling_factor"], eps=cfg["rms_norm_eps"],
        ssm_eps=cfg["rms_norm_eps"], expert_form="gated_silu",
        attn_gate=cfg["use_gqa_gate"], delta_rank=cfg["kda_proj_rank"],
        delta_beta_max=2.0 if cfg["kda_allow_neg_eigval"] else 1.0)


# --- what the shares are made of: my own arithmetic from the file's keys,
# --- for the layers and the slice this chip holds

def layers_of(cfg, letter):
    return _plan(cfg).count(letter)


held_experts = latent_moe.held_experts


def expert_layers(cfg):
    """Layers with routed experts, of those held here."""
    return layers_of(cfg, "E")


def _delta_sizes(cfg):
    """(channels of a layer's keys, and of its values, alike; convolved
    channels; elements of one state)."""
    lin = cfg["linear_attn_config"]
    d = lin["num_heads"] * lin["head_dim"]
    return d, 3 * d, d * lin["head_dim"]


def _matrix_params(cfg):
    """Parameters of the matrices a token of each kind of entry meets
    whatever it routes: ``{"L", "G", "E"}`` (an expert layer's without its
    routed experts and its float32 router)."""
    D, r = cfg["hidden_size"], cfg["kda_proj_rank"]
    d, conv, _ = _delta_sizes(cfg)
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    F = cfg["moe_intermediate_size"]
    return {"L": D * conv + 2 * (D * r + r * d)
            + D * cfg["linear_attn_config"]["num_heads"] + d * D,
            "G": (2 + cfg["use_gqa_gate"]) * D * q + 2 * D * kv,
            "E": 3 * D * cfg["n_shared_experts"] * F}


def weight_bytes(cfg, itemsize=2):
    """Bytes of the layers' and the head's weights a decode call reads
    whatever it routes: everything but the embedding table (a call reads
    one row a lane) and the routed experts (counted per expert that got a
    token, :func:`decode_floor_bytes`).  The router's weight and bias and
    a delta mixer's ``A_log`` and ``dt_bias`` are float32."""
    D, lin = cfg["hidden_size"], cfg["linear_attn_config"]
    d, conv, _ = _delta_sizes(cfg)
    mats = _matrix_params(cfg)
    small = {"L": D + conv * lin["short_conv_kernel_size"] + lin["head_dim"],
             "G": D, "E": D}
    served = sum(layers_of(cfg, c) * (mats[c] + small[c]) for c in "LGE") \
        + D * cfg["vocab_size"] + D
    f32 = layers_of(cfg, "L") * (lin["num_heads"] + d) \
        + layers_of(cfg, "E") * (D + 1) * cfg["deployment"]["router_outputs"]
    return served * itemsize + f32 * 4


def expert_bytes(cfg, itemsize=2):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * itemsize


def position_bytes(cfg, itemsize=2):
    """K and V of one cached position in one attention layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def ssm_state_bytes(cfg, state_lanes, itemsize=2):
    """The bytes the recurrent states cost the decode calls whose marks
    sum to ``state_lanes`` live lanes: in every delta layer each lane's
    state (float32) and its convolution's kept inputs, read AND written.
    A lower bound: a program that passes over the rows no lane names, or
    over a state twice, moves more."""
    _, conv, state = _delta_sizes(cfg)
    taps = cfg["linear_attn_config"]["short_conv_kernel_size"] - 1
    return 2 * state_lanes * layers_of(cfg, "L") * (
        state * 4 + taps * conv * itemsize)


def decode_floor_bytes(cfg, calls, experts_hit, positions, state_lanes,
                       itemsize=2):
    """The bytes ``calls`` decode calls cannot avoid: every weight byte of
    the layers and the head once a call, each held expert once per call
    and layer in which a token fell on it (``experts_hit``, summed over
    the calls), the live lanes' recurrent states read and written
    (``state_lanes``, summed), and the lanes' LIVE positions in the
    attention layers (``positions``, summed)."""
    return (calls * weight_bytes(cfg, itemsize)
            + experts_hit * expert_bytes(cfg, itemsize)
            + ssm_state_bytes(cfg, state_lanes, itemsize)
            + positions * layers_of(cfg, "G") * position_bytes(cfg, itemsize))


def ssm_scan_flops(cfg, tokens):
    """The operations the delta rule itself needs for ``tokens`` real
    tokens through the delta layers held here, token by token as the
    equations state it: ``SCAN_FLOPS_PER_ELEMENT`` a state element (the
    decay's multiply, ``S^T k``, the rank-one update and ``S^T q``).  What
    a chunked form adds (the in-chunk system and its inverse, the products
    with the decayed keys, the exponentials) counts for nothing: a lower
    bound, and of the chunked form mostly not what it computes."""
    return tokens * layers_of(cfg, "L") * SCAN_FLOPS_PER_ELEMENT \
        * _delta_sizes(cfg)[2]


def prefill_flops(cfg, tokens):
    """The operations a prompt of ``tokens`` REAL tokens needs through the
    layers held here (2 per multiply-add): every matmul of a token (a delta
    mixer's projections and low-rank pairs, attention's five, an expert
    layer's router and shared expert, and of the routed experts the
    expected share that falls on the held ones: top_k x held / router
    outputs pairs a token), the delta rule (:func:`ssm_scan_flops`), causal
    attention on the attention layers (a query at t meets t + 1 keys;
    scores and weighted sum), the head for the one position read out."""
    D, dep = cfg["hidden_size"], cfg["deployment"]
    mats = _matrix_params(cfg)
    pairs = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
             / dep["router_outputs"])
    per_token = sum(layers_of(cfg, c) * mats[c] for c in "LGE") \
        + layers_of(cfg, "E") * (
            D * dep["router_outputs"]
            + pairs * 3 * D * cfg["moe_intermediate_size"])
    causal = tokens * (tokens + 1) // 2
    return (2 * per_token * tokens + ssm_scan_flops(cfg, tokens)
            + 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"] * causal
            * layers_of(cfg, "G") + 2 * D * cfg["vocab_size"])


def aot_programs(cfg, traffic, devices):
    """The cell's decode and prefill programs compiled for ``devices[0]``
    from shapes alone (perfbench/tools/rehearse_aot.py): ServeEngine's own
    jitted bodies without an engine."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from bluefog_tpu.models import decoder
    from bluefog_tpu.parallel import compose
    from bluefog_tpu.serve import ServeEngine
    from bluefog_tpu.serve import kv_cache as kv
    lm, scfg = ssm_config(cfg), serve_config(traffic)
    m = compose.compose_parallelism(1, 1, 1, 1, devices=devices[:1])
    eng = ServeEngine.__new__(ServeEngine)
    eng._moe = eng._latent = eng._hybrid = False
    eng._share = eng._ssm = True
    eng.m, eng.cfg, eng.scfg = m, lm, scfg
    sh = NamedSharding(m.mesh, m.spec)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct((1,) + tuple(shape), dtype, sharding=sh)
    leaf = lambda name, shape: sds(
        shape, jnp.float32 if name in decoder.FLOAT32_LEAVES else scfg.dtype)
    shapes = decoder.ssm_param_shapes(lm)
    group = lambda leaves: {n: leaf(n, s) for n, s in leaves.items()}
    params = {"layers": tuple(group(g) for g in shapes["layers"]),
              "shared": group(shapes["shared"])}
    cc = kv.SsmCacheConfig.of(lm, scfg.slots, scfg.max_len, scfg.dtype)
    state = lambda: ({k: sds(shape, cc.dtypes()[k])
                      for k, shape in cc.shapes().items()},
                     sds((cc.rows, 2), jnp.uint32))
    decode = eng._build(eng._ssm_decode_body)
    prefill = eng._build(eng._ssm_prefill_body)
    return [(f"decode_S{S}", decode.lower(
        params, *state(), sds((S, 1 + 4), jnp.int32)).compile(), 1)
        for S in scfg.batch_buckets] + [(f"prefill_T{T}", prefill.lower(
            params, *state(), sds((T + 4,), jnp.int32)).compile(), 1)
        for T in scfg.prefill_buckets]


# gains on a matrix's normal(0, initializer_range) draw, by leaf: the raw
# decays and betas then spread over their ranges with the token
# (``assumed.draws`` in the configuration file)
GAINS = {"wfb": 4.0, "wb": 2.0}


def _draw(name, key, shape, cfg):
    """One leaf's float32 draw, by its name (the configuration file's
    ``assumed.draws`` says why each)."""
    import jax
    import jax.numpy as jnp
    normal = lambda: jax.random.normal(key, shape, jnp.float32)
    if name in ("g", "gf", "g_o"):
        return 1.0 + 0.1 * normal()
    if name == "eb":
        return 0.1 * normal()
    if name == "w_conv":
        return jax.random.uniform(key, shape, jnp.float32, -0.5, 0.5)
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, np.log(1e-3), np.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))        # softplus^-1(dt)
    return GAINS.get(name, 1.0) * cfg["initializer_range"] * normal()


def init_group(cfg, m, seed, dtype, gi, leaves):
    """One group of leaves (``gi`` 0: the shared ones; ``i + 1``: entry
    ``i``'s) as ``{name: [n, ...]}`` on the carving's mesh, replicas equal,
    one jitted call a leaf (the largest leaf's float32 draw is the only
    temporary alive).  A group's draws depend on the seed and its own
    number alone, so one entry can be drawn again by itself."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from bluefog_tpu.models import decoder
    sharding = NamedSharding(m.mesh, m.spec)
    key = jax.random.key(seed)
    out = {}
    for li, (name, shape) in enumerate(leaves.items()):
        dt = jnp.float32 if name in decoder.FLOAT32_LEAVES else dtype

        def make(k, name=name, shape=shape, dt=dt):
            return jnp.broadcast_to(_draw(name, k, shape, cfg).astype(dt)[None],
                                    (m.size,) + shape)
        out[name] = jax.jit(make, out_shardings=sharding)(
            jax.random.fold_in(key, 100 * gi + li))
    return out


def _init_params(cfg, lm, m, seed, dtype):
    """The single-mixer tree, every leaf [n, ...] on the carving's mesh."""
    from bluefog_tpu.models import decoder
    shapes = decoder.ssm_param_shapes(lm)
    return {"layers": tuple(init_group(cfg, m, seed, dtype, i + 1, leaves)
                            for i, leaves in enumerate(shapes["layers"])),
            "shared": init_group(cfg, m, seed, dtype, 0, shapes["shared"])}


selection_report = ssm_latent_moe.selection_report


class Serve(ssm_latent_moe.Serve):
    """One replica of ServeEngine + Scheduler over the delta-rule model.
    Warm-up, the Scheduler, what is served and what is compared are the
    ``ssm_latent_moe`` family's (every asked prompt's prefill logits and
    every decoded position's against the reference under the program's
    selections, each differing selection held to a rounding tie, the state
    each slot is left with); the model, its reference, the limits and the
    check that the state is held in float32 are this file's."""

    def __init__(self, cfg, traffic, devices, seed):
        from bluefog_tpu.parallel import compose
        from bluefog_tpu.serve import Scheduler, ServeEngine

        self.cfg = cfg
        scfg = serve_config(traffic)
        self.m = compose.compose_parallelism(len(devices), 1, 1, 1,
                                             devices=devices)
        self.lm = ssm_config(cfg)
        self.params = _init_params(cfg, self.lm, self.m, seed, scfg.dtype)
        self.engine = ServeEngine(self.m, self.lm, self.params, scfg)
        self._Scheduler = Scheduler
        self.vocab = cfg["vocab_size"]
        dtype = traffic["engine"]["dtype"]
        self.tol = SERVE_LOGIT_TOL[dtype]
        self.decode_tol = DECODE_LOGIT_TOL[dtype]
        self.gap_tol = DECODE_GAP_TOL[dtype]
        self.tie_delta = ROUTE_TIE_DELTA[dtype]
        self.state_tol = STATE_TOL[dtype]

    def compare(self, prompts, reqs, got, output_tokens):
        """The state-space family's comparison, and of the states the slots
        held (every delta layer's) the share of nonzero elements that a
        bfloat16 holds exactly: ``BF16_STATE_SHARE`` says why."""
        report = super().compare(prompts, reqs, got, output_tokens)
        held = np.concatenate([np.ravel(g[4]) for g in got]).astype(np.float32)
        exact = (held.view(np.uint32) & 0xFFFF) == 0
        share = float(exact[held != 0].mean()) if (held != 0).any() else 0.0
        report["compared"]["ssm_state_bfloat16_share"] = [share,
                                                          BF16_STATE_SHARE]
        report["ok"] = bool(report["ok"] and share <= BF16_STATE_SHARE)
        return report

    def _reference(self, seq, pad, chosen):
        """(logits [T, V], biased scores [layers, T, E], picked [layers, T,
        k], states [delta layers, H, K, V] after ``seq``'s last token) of
        the reference on ``self.params`` for ``seq`` under the selections
        ``chosen`` [layers, T, k], one entry upcast at a time."""
        import jax.numpy as jnp
        p0 = _checks.row0(self.params)
        toks = np.zeros((pad,), np.int32)
        toks[:len(seq)] = seq
        sets = np.full((chosen.shape[0], pad, chosen.shape[2]), -1, np.int32)
        sets[:, :len(seq)] = chosen
        want, by, picked, states = reference.forward(
            self.cfg, lambda i: {k: v[0] for k, v in p0["layers"][i].items()},
            {k: v[0] for k, v in p0["shared"].items()}, jnp.asarray(toks),
            self.lm.held_start, chosen=jnp.asarray(sets), true_len=len(seq))
        T = len(seq)
        return (np.asarray(want)[:T], np.asarray(by)[:, :T],
                np.asarray(picked)[:, :T], np.asarray(states))


def build_serve(cfg, traffic, devices, seed):
    return Serve(cfg, traffic, devices, seed)
