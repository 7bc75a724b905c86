"""Family ``ssm_latent_moe``: a decoder whose every layer is ONE mixer (a
Mamba-2 state-space mixer, grouped-query attention with no position signal,
or ``relu^2`` experts computed in a latent under a bias-corrected sigmoid
router), served as ONE chip's share of an expert-parallel deployment through
ServeEngine + Scheduler (bluefog_tpu.models.decoder.SsmConfig): the router
keeps its published width, the chip holds the experts and the vocabulary
slice the configuration file's ``deployment`` names, and what the absent
experts would add is left out in program and reference alike.

This file maps the source's key names onto SsmConfig, makes the weights on
the device from the seed leaf by leaf, holds the comparison with the plain
reference (perfbench/reference/ssm_latent_moe.py) and the bytes and
operations the new per-layer shares are made of: what a decode call cannot
avoid (``engine.decode_hbm_roofline_share.ssm``), what the recurrent states
cost it (``ssm.state_hbm_roofline_share``) and what a prompt's recurrence
needs (``ssm.scan_mxu_roofline_share``).
"""
import time

import numpy as np

from perfbench.families import _checks, latent_moe
from perfbench.families.composed_lm import serve_config
from perfbench.reference import ssm_latent_moe as reference

# |program - reference| as a share of the largest reference logit, by the
# precision the traffic file states for the engine, with the reference
# evaluated under the PROGRAM's expert selections (below).  The cell serves
# in bf16 (weights, activations, K and V, the convolution's kept inputs;
# router, scan and recurrent state in f32).  On the chip at the cell's size
# (my chip runs, PR 43; PERF.md section 6, docs/PERF_PR43_RECORD.md): the
# sound program read 0.0090-0.0114 on prefill and 0.0108-0.0127 on decode
# over 26 seeds; with its matrices through int8 and back 0.0518 and 0.0639,
# its scaling factor dropped 0.107 and 0.149, b_conv zeroed 0.255 and 0.307,
# Dskip zeroed 0.880 and 0.993.  0.03 is 2.4 times the largest sound reading
# and under 0.6 of the smallest int8 one.  The logits do NOT tell a recurrent
# state kept in bfloat16 from one in float32 (decode 0.01150 beside the same
# seed's 0.01146; 0.0134 / 0.0125, 0.0109 / 0.0117, 0.0124 / 0.0118, 0.0122 /
# 0.0121 at four more): STATE_TOL
# below does.  The CPU rehearsal states float32: the program then IS the
# reference's function up to the order of its sums (the chunked scan against
# the recurrence token by token reads 1e-6), and every control fails by a
# factor of ten or more.
SERVE_LOGIT_TOL = {"bfloat16": 3e-2, "float32": 1e-3}
DECODE_LOGIT_TOL = {"bfloat16": 3e-2, "float32": 1e-3}
# How far the reference's best logit lies over its logit of the token the
# program chose, as a share of the largest reference logit, at most over the
# decoded positions.  Sound: 0.0014-0.0086 (26 seeds); the matrices through
# int8 0.0334, the scaling factor dropped 0.094, b_conv zeroed 0.182.  0.017
# is twice the largest sound reading and half the int8 one.
DECODE_GAP_TOL = {"bfloat16": 1.7e-2, "float32": 1e-3}
# The recurrent state a slot holds after its last decode call against the
# reference's after the same tokens: per head |held - reference| over
# |reference|, the MEDIAN head of the FIRST state-space layer.  Why that
# one: the first layer's inputs are the embeddings, equal on both sides up
# to the program's own rounding, so its heads carry the bf16 activations'
# error and nothing a layer before them added (0.0048-0.0051 at the median
# head over 9 seeds x 2 prompts of 200 and 1,800 tokens + 63 decoded; the
# second layer already reads 0.0102-0.0108 and the fifth 0.0156-0.0170, and
# a whole layer's norm is a few heavy heads': 0.0051-0.0061).  With the
# state kept in bfloat16 (the cache's dtype alone; 63 roundings of one part
# in 500 each, and steps that decay a slow head by less than half a unit in
# the last place not taken at all) the same head reads 0.0067-0.0073 (4
# seeds x 2 prompts), and that run `correct: false` by this limit alone; by the whole layer's norm 0.0060-0.0077, not apart
# from the sound 0.0061, and after the PROMPT alone (one rounding) 0.0055
# beside 0.0052: it takes the decoded steps to show.  0.0058 is 1.14 times
# the largest sound reading and 1 / 1.15 of the smallest control (both
# tight: the median of 128 heads moved by 5 % over the nine seeds).
STATE_TOL = {"bfloat16": 5.8e-3, "float32": 1e-4}
# At 22 of 512 the cut sits where the scores lie 0.0025 apart (512 x the
# normal density at its 95.7th percentile, through the sigmoid's slope), and
# the program's bf16 activations move a score by about as much: at 0.177 to
# 0.191 of all (token, layer) pairs the program chose another SET than the
# reference (17 seeds).  A margin that leaves such positions out (the other
# held-experts families' ROUTE_MARGIN) would compare nothing here.  Instead
# the program hands out the experts each layer chose, the reference is
# evaluated UNDER THOSE SELECTIONS (weights still from its own scores), and
# every expert by which a selection differs from the reference's own has to
# lie within this much of the reference's ``top_k``-th biased score: a
# rounding tie.  Anything farther is a route fault, and one fault is not
# correct.  The farthest tied expert of a sound run lay 0.0074-0.0145 from
# the cut (23 seeds, 10,630 pairs each); with e_bias zeroed on the program's
# side the farthest lay 0.402 off and 195,349 experts were faults.  0.04 is
# 2.8 times the largest sound reading and a tenth of the control's.
ROUTE_TIE_DELTA = {"bfloat16": 4e-2, "float32": 1e-4}
# what the bf16 limits above stand between: name -> (the most the sound
# program read on the chip over its seeds, the least the nearest control
# read: its weights through int8 and back for the logits, its router bias
# zeroed for the tie distance, its recurrent state kept in bfloat16 for the
# state); my chip runs, PR 43
CHIP_READINGS = {"prefill_logit_err_share": (0.0114, 0.0518),
                 "decode_logit_err_share": (0.0127, 0.0639),
                 "decode_logit_gap_share": (0.0086, 0.0334),
                 "route_tie_distance": (0.0145, 0.402),
                 "ssm_state_err_share": (0.0051, 0.0067)}

KINDS = {"M": "ssm", "*": "full", "E": "experts"}


def _plan(cfg):
    return cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]


def ssm_config(cfg):
    from bluefog_tpu.models import decoder
    dep = cfg["deployment"]
    held = dep["held_experts"]
    if held[1] - held[0] != cfg["n_routed_experts"]:
        raise ValueError("n_routed_experts is the count HELD here and must "
                         "equal the deployment's held_experts range")
    if cfg["mamba_num_heads"] * cfg["mamba_head_dim"] \
            != cfg["expand"] * cfg["hidden_size"]:
        raise ValueError("mamba_num_heads x mamba_head_dim is not expand x "
                         "hidden_size")
    if (cfg["n_group"], cfg["topk_group"]) != (1, 1):
        raise ValueError("this family's router has no group step: n_group "
                         "and topk_group are 1")
    return decoder.SsmConfig(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        plan=tuple(KINDS[c] for c in _plan(cfg)),
        ssm_heads=cfg["mamba_num_heads"], ssm_head_dim=cfg["mamba_head_dim"],
        ssm_groups=cfg["n_groups"], ssm_state=cfg["ssm_state_size"],
        conv_kernel=cfg["conv_kernel"], chunk=cfg["chunk_size"],
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        latent=cfg["moe_latent_size"],
        expert_ffn=cfg["moe_intermediate_size"],
        shared_ffn=cfg["moe_shared_expert_intermediate_size"],
        num_experts=dep["router_outputs"], held_experts=held[1] - held[0],
        held_start=held[0], top_k=cfg["num_experts_per_tok"],
        route_scale=cfg["routed_scaling_factor"], eps=cfg["norm_eps"],
        ssm_eps=cfg["layer_norm_epsilon"])


# --- what the shares are made of: my own arithmetic from the file's keys,
# --- for the layers and the slice this chip holds

def layers_of(cfg, letter):
    return _plan(cfg).count(letter)


held_experts = latent_moe.held_experts


def expert_layers(cfg):
    """Layers with routed experts, of those held here."""
    return layers_of(cfg, "E")


def _mamba_sizes(cfg):
    """(inner channels, convolved channels, elements of one state)."""
    d_in = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    return (d_in, d_in + 2 * cfg["n_groups"] * cfg["ssm_state_size"],
            d_in * cfg["ssm_state_size"])


def _matrix_params(cfg):
    """Parameters of the matrices a token of each kind of layer meets
    whatever it routes: ``{"M", "*", "E"}`` (an expert layer's without its
    routed experts and its float32 router)."""
    D = cfg["hidden_size"]
    d_in, conv, _ = _mamba_sizes(cfg)
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    return {"M": D * (d_in + conv + cfg["mamba_num_heads"]) + d_in * D,
            "*": 2 * D * q + 2 * D * kv,
            "E": 2 * D * cfg["moe_latent_size"]
            + 2 * D * cfg["moe_shared_expert_intermediate_size"]}


def weight_bytes(cfg, itemsize=2):
    """Bytes of the layers' and the head's weights a decode call reads
    whatever it routes: everything but the embedding table (a call reads
    one row a lane) and the routed experts (counted per expert that got a
    token, :func:`decode_floor_bytes`).  The router's weight and bias and
    a Mamba mixer's three vectors of one number a head are float32."""
    D, H = cfg["hidden_size"], cfg["mamba_num_heads"]
    d_in, conv, _ = _mamba_sizes(cfg)
    mats = _matrix_params(cfg)
    small = {"M": D + conv * (cfg["conv_kernel"] + 1) + d_in, "*": D, "E": D}
    served = sum(layers_of(cfg, c) * (mats[c] + small[c]) for c in "M*E") \
        + D * cfg["vocab_size"] + D
    f32 = layers_of(cfg, "M") * 3 * H \
        + layers_of(cfg, "E") * (D + 1) * cfg["deployment"]["router_outputs"]
    return served * itemsize + f32 * 4


def expert_bytes(cfg, itemsize=2):
    return 2 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"] * itemsize


def position_bytes(cfg, itemsize=2):
    """K and V of one cached position in one attention layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def ssm_state_bytes(cfg, state_lanes, itemsize=2):
    """The bytes the recurrent states cost the decode calls whose marks
    sum to ``state_lanes`` live lanes: in every state-space layer each
    lane's state (float32) and its convolution's kept inputs, read AND
    written.  A lower bound: a program that passes over the rows no lane
    names, or over a state twice, moves more."""
    _, conv, state = _mamba_sizes(cfg)
    return 2 * state_lanes * layers_of(cfg, "M") * (
        state * 4 + (cfg["conv_kernel"] - 1) * conv * itemsize)


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
            + positions * layers_of(cfg, "*") * position_bytes(cfg, itemsize))


def ssm_scan_flops(cfg, tokens):
    """The operations the state-space recurrence itself needs for
    ``tokens`` real tokens through the state-space layers held here (2 per
    multiply-add): the state's update by ``delta x (x) B`` and its read-out
    against ``C``, one multiply-add a state element each.  The decay's own
    multiply, a chunked form's scores and masked products, the cumulative
    sums and the exponentials count for nothing: a lower bound, and mostly
    not matrix work."""
    return tokens * layers_of(cfg, "M") * 4 * _mamba_sizes(cfg)[2]


def prefill_flops(cfg, tokens):
    """The operations a prompt of ``tokens`` REAL tokens needs through the
    layers held here (2 per multiply-add): every matmul of a token (a Mamba
    mixer's two projections, attention's four, an expert layer's router,
    latent projections and shared expert, and of the routed experts the
    expected share that falls on the held ones: top_k x held / router
    outputs pairs a token), the recurrence (:func:`ssm_scan_flops`), causal
    attention on the attention layers (a query at t meets t + 1 keys;
    scores and weighted sum), the head for the one position read out."""
    D, dep = cfg["hidden_size"], cfg["deployment"]
    mats = _matrix_params(cfg)
    pairs = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
             / dep["router_outputs"])
    per_token = sum(layers_of(cfg, c) * mats[c] for c in "M*E") \
        + layers_of(cfg, "E") * (
            D * dep["router_outputs"] + pairs * 2 * cfg["moe_latent_size"]
            * cfg["moe_intermediate_size"])
    causal = tokens * (tokens + 1) // 2
    return (2 * per_token * tokens + ssm_scan_flops(cfg, tokens)
            + 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"] * causal
            * layers_of(cfg, "*") + 2 * D * cfg["vocab_size"])


def _cache_config(lm, scfg):
    from bluefog_tpu.serve import kv_cache as kv
    return kv.SsmCacheConfig(
        full_layers=lm.layers_of("full"), ssm_layers=lm.layers_of("ssm"),
        slots=scfg.slots, max_len=scfg.max_len, kv_heads=lm.kv_heads,
        head_dim=lm.head_dim, ssm_heads=lm.ssm_heads,
        ssm_head_dim=lm.ssm_head_dim, ssm_state=lm.ssm_state,
        conv_taps=lm.conv_kernel - 1, conv_dim=lm.conv_dim, dtype=scfg.dtype)


def aot_programs(cfg, traffic, devices):
    """The cell's decode and prefill programs compiled for ``devices[0]``
    from shapes alone (perfbench/tools/rehearse_aot.py): ServeEngine's own
    jitted bodies without an engine, as latent_moe.share_programs builds
    the other held-experts families'."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from bluefog_tpu.models import decoder
    from bluefog_tpu.parallel import compose
    from bluefog_tpu.serve import ServeEngine
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
    cc = _cache_config(lm, scfg)
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


def _draw(name, key, shape, std, cfg):
    """One leaf's float32 draw, by its name (the configuration file's
    ``assumed.draws`` says why each)."""
    import jax
    import jax.numpy as jnp
    normal = lambda: jax.random.normal(key, shape, jnp.float32)
    if name in ("g", "gf", "g_y", "Dskip"):
        return 1.0 + 0.1 * normal()
    if name in ("b_conv", "eb"):
        return 0.1 * normal()
    if name == "w_conv":
        return jax.random.uniform(key, shape, jnp.float32, -0.5, 0.5)
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, np.log(cfg["time_step_min"]),
            np.log(cfg["time_step_max"])))
        return dt + jnp.log(-jnp.expm1(-dt))        # softplus^-1(dt)
    return std * normal()


def init_group(cfg, m, seed, dtype, gi, leaves):
    """One group of leaves (``gi`` 0: the shared ones; ``i + 1``: layer
    ``i``'s) as ``{name: [n, ...]}`` on the carving's mesh, replicas equal,
    one jitted call a leaf (the largest leaf's float32 draw is the only
    temporary alive).  A group's draws depend on the seed and its own
    number alone, so one layer can be drawn again by itself."""
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
            z = _draw(name, k, shape, cfg["initializer_range"], cfg)
            return jnp.broadcast_to(z.astype(dt)[None], (m.size,) + shape)
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


def selection_report(by, picked, chosen, delta):
    """How the program's selections ``chosen`` ``[layers, T, k]`` stand to
    the reference's own ``picked`` ``[layers, T, k]`` under its biased
    scores ``by`` ``[layers, T, E]``: ``(differing, faults, pairs,
    farthest)``: the (token, layer) pairs at which the two SETS differ, of
    the experts by which they differ those farther than ``delta`` from the
    reference's ``k``-th biased score (a tie a rounding decides lies within
    it), the pairs looked at, and how far the farthest such expert lies."""
    by = np.asarray(by)
    E = by.shape[-1]

    def member(idx):            # [layers, T, E] bool; -1 selects nothing
        idx = np.asarray(idx)
        out = np.zeros(by.shape[:-1] + (E + 1,), bool)
        np.put_along_axis(out, np.where(idx < 0, E, idx), True, -1)
        return out[..., :E]
    mine, theirs = member(chosen), member(picked)
    cut = np.take_along_axis(by, np.asarray(picked)[..., -1:], -1)  # k-th
    apart = mine != theirs
    off = np.where(apart, np.abs(by - cut), 0.0)
    return (int(apart.any(-1).sum()), int((off > delta).sum()),
            int(apart[..., 0].size), float(off.max()))


def state_report(held, states):
    """How the recurrent states a slot ``held`` stand to the reference's
    ``states`` (both ``[ssm layers, H, P, N]``): per layer the MEDIAN over
    the heads of a head's ``|held - states| / |states|`` (Frobenius norms
    over its ``[P, N]``)."""
    held, states = np.asarray(held, np.float64), np.asarray(states, np.float64)
    err = np.sqrt(np.sum((held - states) ** 2, (2, 3)))
    norm = np.sqrt(np.sum(states ** 2, (2, 3)))
    return np.median(err / np.maximum(norm, 1e-30), axis=1).tolist()


class Serve(latent_moe.Serve):
    """One replica of ServeEngine + Scheduler over the single-mixer model.
    Warm-up, the Scheduler and the retrace count are the latent family's;
    the model, its reference and what is compared are this file's."""

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

    def reference_check(self, prompts, output_tokens):
        """Prefill then decode through the state cache, by way of a fresh
        Scheduler, against the reference's full forward pass a layer at a
        time, evaluated under the experts the program's own layers chose:
        every asked prompt's prefill logits and every decoded position's
        logits number by number, and every selection that differs from the
        reference's own held to a rounding tie.  The cache is given back to
        the device before the reference runs."""
        prompts = [list(p) for p in prompts]
        t0 = time.perf_counter()
        reqs, got = self.serve_prompts(prompts, output_tokens)
        served = time.perf_counter() - t0
        report = self.compare(prompts, reqs, got, output_tokens)
        report["seconds"] = {"program": round(served, 3), "reference": round(
            time.perf_counter() - t0 - served, 3)}
        return report

    def serve_prompts(self, prompts, output_tokens):
        """The program's side: the requests through a fresh Scheduler,
        stepped here so that after every step each request's row of the
        decode program's logits and selections can be kept (``{j: ...}``:
        what ``generated[j]`` was chosen from, ``j >= 1``); then what each
        request's slot holds of recurrent state (every request has a slot
        to itself, and a retired lane's state is passed over: the state
        after its last decode call); then each prompt's prefill logits and
        selections; then the cache is deleted.  Returns the requests and
        per request ``(prefill logits, prefill selections [layers, n, k],
        decode logits, decode selections, states [ssm layers, H, P, N])``."""
        sched = self.scheduler()
        reqs = [sched.submit(p, max_new_tokens=output_tokens) for p in prompts]
        logits, chosen = [{} for _ in reqs], [{} for _ in reqs]
        for _ in range(10_000):
            if sched.done:
                break
            before = [len(r.generated) for r in reqs]
            sched.step()
            slots, rows = self.engine.decode_logits(0)
            lane = {int(s): i for i, s in enumerate(slots)}
            rows = np.asarray(rows)                     # [steps, S, vocab]
            sets = np.asarray(self.engine.decode_chosen(0)[1])
            for r, n0, keep, kept in zip(reqs, before, logits, chosen):
                first = max(n0, 1)      # generated[0] is the prefill's
                for j in range(first, len(r.generated)):
                    keep[j] = rows[j - first, lane[r.slot]]
                    kept[j] = sets[j - first, :, lane[r.slot]]
        sched.close()
        states = [np.asarray(self.engine.cache["ssm"][0, :, r.slot],
                             np.float32) for r in reqs]
        got = []
        for p, dec, sel, S in zip(prompts, logits, chosen, states):
            last = np.asarray(self.engine.prefill(0, 0, p)[1], np.float32)
            first = np.asarray(self.engine.prefill_chosen(0))[:, :len(p)]
            got.append((last, first, dec, sel, S))
        for leaf in self.engine.cache.values():
            leaf.delete()
        return reqs, got

    def _reference(self, seq, pad, chosen):
        """(logits [T, V], biased scores [layers, T, E], picked [layers, T,
        k], states [ssm layers, H, P, N] after ``seq``'s last token) of
        the reference on ``self.params`` for ``seq`` under the selections
        ``chosen`` [layers, T, k], one layer upcast at a time."""
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

    def compare(self, prompts, reqs, got, output_tokens):
        """The reference's side, per asked prompt: position ``n - 1`` from
        the prefill, position ``n - 1 + j`` (``j >= 1``) from the decode
        call that chose ``generated[j]``, and the recurrent states the slot
        held after its last decode call against the reference's after the
        same tokens."""
        rows = []
        for p, req, (first, sel0, dec, sel, held) in zip(prompts, reqs, got):
            gen = [int(t) for t in req.generated]
            whole = req.state == "done" and len(gen) == output_tokens \
                and sorted(dec) == list(range(1, len(gen)))
            at = sorted(dec)
            seq = p + gen[:len(at)]
            chosen = np.concatenate(
                [sel0] + [sel[j][:, None] for j in at], axis=1)
            pad = -(-len(seq) // 128) * 128
            want, by, picked, states = self._reference(seq, pad, chosen)
            differing, faults, pairs, farthest = selection_report(
                by, picked, chosen, self.tie_delta)
            last = len(p) - 1
            errs = [float(np.max(np.abs(dec[j] - want[last + j])))
                    for j in at]
            gaps = [float(want[last + j].max() - want[last + j, gen[j]])
                    for j in at]
            rows.append({
                "prompt_tokens": len(p),
                "prefill_logit_max_abs_err": float(
                    np.max(np.abs(first - want[last]))),
                "decode_positions": len(at),
                "decode_logit_max_abs_err": max(errs, default=0.0),
                "decode_logit_abs_err_p50": float(np.median(errs))
                if errs else 0.0,
                "decode_logit_gap_max": max(gaps, default=0.0),
                "scale": float(np.max(np.abs(want))),
                "selections": pairs, "selections_tied": differing,
                "route_faults": faults, "route_tie_distance": farthest,
                "ssm_state_rel_err": state_report(held, states),
                "off_length": int(not whole)})

        def worst(key):
            return max((r[key] / r["scale"] for r in rows), default=0.0)
        total = lambda key: sum(r[key] for r in rows)
        compared = {
            "prefill_logit_err_share": [worst("prefill_logit_max_abs_err"),
                                        self.tol],
            "decode_logit_err_share": [worst("decode_logit_max_abs_err"),
                                       self.decode_tol],
            "decode_logit_gap_share": [worst("decode_logit_gap_max"),
                                       self.gap_tol],
            "route_faults": [total("route_faults"), 0],
            "route_tie_distance": [
                max(r["route_tie_distance"] for r in rows), self.tie_delta],
            # the first state-space layer's: STATE_TOL says why
            "ssm_state_err_share": [
                max(r["ssm_state_rel_err"][0] for r in rows),
                self.state_tol],
            "requests_off_length": [total("off_length"), 0]}
        ok = all(value <= limit for value, limit in compared.values())
        # a diagnostic, with the limit it cannot pass: the share of (token,
        # layer) pairs at which the program chose another set than the
        # reference, every one of them a tie within ``tie_delta``
        compared["route_tied_share"] = [
            total("selections_tied") / max(total("selections"), 1), 1.0]
        return {"ok": bool(ok), "tolerance": self.tol,
                "decode_tolerance": self.decode_tol,
                "gap_tolerance": self.gap_tol,
                "state_tolerance": self.state_tol,
                "tie_delta": self.tie_delta, "requests": rows,
                "compared": compared}


def build_serve(cfg, traffic, devices, seed):
    return Serve(cfg, traffic, devices, seed)
