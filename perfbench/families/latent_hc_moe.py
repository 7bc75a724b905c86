"""Family ``latent_hc_moe``: the latent family's decoder (latent attention,
sigmoid-routed experts beside a shared one) with a residual of several
streams that every sublayer reads and remixes through learned,
Sinkhorn-normalised maps, several leading dense layers, and a router that
selects under a score-correction bias, served through ServeEngine +
Scheduler on the latent family's two programs
(bluefog_tpu.models.decoder.LatentConfig with ``streams``, ``dense_layers``
and ``route_bias``).  The chip holds what the configuration file's
``deployment`` names: here every routed expert of a layer and the whole
vocabulary.

This file maps the source's key names onto LatentConfig, makes the weights
on the device from the seed leaf by leaf, holds the comparison with the
plain reference (perfbench/reference/latent_hc_moe.py), the bytes a decode
call cannot avoid (``engine.decode_hbm_roofline_share``), the operations a
prompt needs (``engine.prefill_mxu_roofline_share.latent``) and the bytes
the stream maps cannot avoid (``hc.mix_hbm_roofline_share``).  How a check
serves its prompts and which positions it compares is the latent family's
(perfbench/families/latent_moe.py: candidates by prefixes, positions the
reference's own router decides).
"""
import dataclasses
import functools

import numpy as np

from perfbench.families import _checks, latent_moe
from perfbench.families.composed_lm import serve_config
from perfbench.reference import latent_hc_moe as reference

# |program - reference| as a share of the largest reference logit: one limit
# a number that is compared, by the precision the traffic file states for
# the engine.  The cell serves in bf16 end to end (weights, activations, the
# four streams, the latent cache; the router with its bias and the stream
# maps in f32).  Each limit lies between its two readings on the chip at the
# cell's size (my chip runs, PR 41; PERF.md section 6, every run in
# docs/PERF_PR41_RECORD.md): what the sound program read at most over 22
# seeds, and the LEAST it read over three seeds with its WEIGHTS through
# int8 and back, the reference's left alone (the nearest precision below the
# served one).
#   prefill_largest  the largest prefill error of a length's compared
#                    prompts: sound 0.0128-0.0284, int8 0.228-0.293; 0.08 is
#                    2.8 times the one and 0.35 of the other.  One wrong
#                    prefill among the 5-15 of a length (a slot's, a
#                    bucket's) is caught here.
#   prefill_but_one  the largest error but one of a length: sound
#                    0.0125-0.0224, int8 0.213-0.232; 0.04 is 1.8 times and
#                    0.19.  The tighter hold on every prefill but the one a
#                    flipped selection may cost (ROUTE_MARGIN: 0.13 % of
#                    decided positions; the one met, call B1, read 0.0199,
#                    under this limit too: the statistic has excused nothing
#                    so far).
#   decode_gap       a decoded token's logit under the reference's largest
#                    at its position: sound 0-0.0093, int8 0.0317-0.149;
#                    0.02 is 2.15 times and 0.63.  Decode is another
#                    compiled program (absorbed attention over the cache,
#                    every lane through every held expert), so it is held by
#                    a number of its own: a loss of precision in that
#                    program alone reads not correct.
# With the program's phi zeroed the three read 0.709-0.785 / 0.617-0.647 /
# 0.389-0.523, with its router bias zeroed 0.583-0.592 / 0.474-0.547 /
# 0.247-0.443.  The CPU rehearsal states float32: the program then IS the
# reference's function (it reads 3e-7), and each of those controls fails by
# a factor of ten or more.
# (the sound program's largest over its seeds, the int8 control's least)
CHIP_READINGS = {"prefill_largest": (0.0284, 0.228),
                 "prefill_but_one": (0.0224, 0.213),
                 "decode_gap": (0.0093, 0.0317)}
SERVE_LIMITS = {
    "bfloat16": {"prefill_largest": 8e-2, "prefill_but_one": 4e-2,
                 "decode_gap": 2e-2},
    "float32": {"prefill_largest": 1e-3, "prefill_but_one": 1e-3,
                "decode_gap": 1e-3}}
# A position is compared only where the REFERENCE's own router, on its
# BIASED scores, puts every selection in every expert layer at least this
# far from flipping (reference.held_margin; a share of the router logits'
# root mean square): nearer than that the function jumps, and the program's
# bf16 activations land on either side.  Every expert of a layer is held
# here, so every flip counts (the latent family's chip holds 12 of 192):
# 7-10 % of (token, layer) pairs select another set under the program's own
# block in bf16.  By margin, over 13 runs at the cell's size (positions
# whose five layers are all that far from flipping, and those among them
# where some layer's selection differs): at 0.012 45 % of the positions and
# 4 % of those differ; at 0.03 12.5-13.8 % and 11 of 8,185 (0.13 %); at 0.06
# 1.9 % and none of 1,000, too few to find among 64 candidates.  Coverage
# is made as in the latent family, by the reference alone: of each asked
# length ``check.candidates`` prompts are served (the asked prompt and its
# prefixes one token shorter each), the prefill of EVERY one whose last
# position is decided is compared, and the longest such is followed through
# its decode; a length with fewer than two compared prefills, or fewer
# decided decode positions than ``check.decode_positions_floor`` over the
# lengths, is not correct (64 candidates: 5-15 compared a length; 128
# outputs: 7-28 decided decode positions a length, floor 6).
ROUTE_MARGIN = 0.03

# leaves kept in float32 whatever the served type: the router with its
# bias, and the stream maps
FLOAT32 = ("wr", "eb", "h1p", "h1a", "h1b", "h2p", "h2a", "h2b")


def latent_config(cfg):
    from bluefog_tpu.models import decoder
    from perfbench.runners import _common
    if "streams" not in {f.name for f in
                         dataclasses.fields(decoder.LatentConfig)}:
        raise _common.Refused(
            "the program under test has no residual streams "
            "(decoder.LatentConfig.streams): it cannot run this family")
    dep, sc = cfg["deployment"], cfg["rope_scaling"]
    held = dep["held_experts"]
    if held[1] - held[0] != cfg["n_routed_experts"]:
        raise ValueError("n_routed_experts is the count HELD here and must "
                         "equal the deployment's held_experts range")
    if -cfg["mhc_h_res_clamp_min"] != cfg["mhc_h_res_clamp_max"]:
        raise ValueError("the program clips the remix logits to +-one bound")
    return decoder.LatentConfig(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        heads=cfg["num_attention_heads"], layers=cfg["num_hidden_layers"],
        q_rank=cfg["q_lora_rank"], kv_rank=cfg["kv_lora_rank"],
        nope_dim=cfg["qk_nope_head_dim"], rope_dim=cfg["qk_rope_head_dim"],
        v_dim=cfg["v_head_dim"], dense_ffn=cfg["intermediate_size"],
        expert_ffn=cfg["moe_intermediate_size"],
        num_experts=dep["router_outputs"], held_experts=held[1] - held[0],
        held_start=held[0], top_k=cfg["num_experts_per_tok"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        route_scale=cfg["routed_scaling_factor"],
        rope_base=float(cfg["rope_theta"]), rope_factor=sc["factor"],
        rope_orig_len=sc["original_max_position_embeddings"],
        rope_beta_fast=sc["beta_fast"], rope_beta_slow=sc["beta_slow"],
        rope_mscale_all_dim=sc["mscale_all_dim"],
        eps=cfg["rms_norm_eps"],
        dense_layers=cfg["first_k_dense_replace"],
        route_bias=cfg["topk_method"] == "noaux_tc",
        streams=cfg["hc_mult"], sinkhorn_iters=cfg["hc_sinkhorn_iters"],
        hc_eps=cfg["hc_eps"], res_clamp=float(cfg["mhc_h_res_clamp_max"]))


def held_experts(cfg):
    """Routed experts this chip holds in each expert layer."""
    lo, hi = cfg["deployment"]["held_experts"]
    return hi - lo


def expert_layers(cfg):
    """Layers with routed experts: all but the leading dense ones."""
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def _attention_params(cfg):
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    return (D * cfg["q_lora_rank"] + cfg["q_lora_rank"] * H * (nope + rope)
            + D * (cfg["kv_lora_rank"] + rope)
            + cfg["kv_lora_rank"] * H * (nope + cfg["v_head_dim"])
            + H * cfg["v_head_dim"] * D)


def _map_params(cfg):
    """One sublayer's stream maps: phi, the three gains, the bias."""
    n = cfg["hc_mult"]
    c = n * n + 2 * n
    return n * cfg["hidden_size"] * c + 3 + c


def weight_bytes(cfg, itemsize=2):
    """Bytes of the layers' and the head's weights a decode call reads
    whatever it routes: everything but the embedding table (a call reads
    one row a lane) and the routed experts (counted per expert that got a
    token, :func:`decode_floor_bytes`).  My own arithmetic from the file's
    keys; the router with its bias and the stream maps are float32."""
    D = cfg["hidden_size"]
    L, Ld = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    norms = 2 * D + cfg["q_lora_rank"] + cfg["kv_lora_rank"]
    served = (L * (_attention_params(cfg) + norms)
              + Ld * 3 * D * cfg["intermediate_size"]
              + (L - Ld) * 3 * D * cfg["moe_intermediate_size"]
              + D * cfg["vocab_size"] + D)
    kept = (L * 2 * _map_params(cfg)
            + (L - Ld) * (D + 1) * cfg["deployment"]["router_outputs"])
    return served * itemsize + kept * 4


def expert_bytes(cfg, itemsize=2):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * itemsize


def decode_floor_bytes(cfg, calls, experts_hit, live_positions, itemsize=2):
    """The bytes ``calls`` decode calls cannot avoid: every weight byte of
    the layers and the head once a call, each held expert once per call
    and layer in which a token fell on it (``experts_hit``, summed over
    the calls), and the LIVE cache positions of the calls' lanes
    (``live_positions``, summed) in every layer.  A lower bound: the four
    streams' own traffic (0.13 GB a call of 96 lanes) counts for nothing."""
    latent = (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * itemsize
    return (calls * weight_bytes(cfg, itemsize)
            + experts_hit * expert_bytes(cfg, itemsize)
            + live_positions * cfg["num_hidden_layers"] * latent)


def prefill_flops(cfg, tokens):
    """The operations a prompt of ``tokens`` REAL tokens needs through the
    layers held here (2 per multiply-add): every matmul of a token (the
    latent attention's five, keys and values rebuilt per head from the
    compressed vector; the dense FFNs; the shared expert, the router and
    ``num_experts_per_tok`` routed experts, all of them held; the two
    stream maps' 24 coefficients a layer), causal attention (a query at t
    meets t + 1 keys: scores over nope + rope, the weighted sum over v),
    and the head for the one position that is read out.  The mixing of the
    streams is elementwise and counts for nothing here."""
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    L, Ld = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    n = cfg["hc_mult"]
    dep = cfg["deployment"]
    held_pairs = (cfg["num_experts_per_tok"] * held_experts(cfg)
                  / dep["router_outputs"])
    per_token = (L * (_attention_params(cfg)
                      + 2 * n * D * (n * n + 2 * n))
                 + Ld * 3 * D * cfg["intermediate_size"]
                 + (L - Ld) * (D * dep["router_outputs"]
                               + (1 + held_pairs) * 3 * D
                               * cfg["moe_intermediate_size"]))
    keys_met = L * tokens * (tokens + 1) // 2
    per_key = H * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                   + cfg["v_head_dim"])
    return (2 * per_token * tokens + 2 * per_key * keys_met
            + 2 * D * cfg["vocab_size"])


def hc_floor_bytes(cfg, tokens, calls, itemsize=2):
    """The bytes the stream maps of ``calls`` prompts of ``tokens`` REAL
    tokens together cannot avoid: per token and sublayer the ``n`` streams
    read for the sublayer's input and that input written, then the ``n``
    streams and the sublayer's output read and the ``n`` streams written
    (``3 n + 2`` vectors of ``hidden_size``), and each sublayer's phi
    (float32) once a call.  A lower bound: it charges nothing for reading
    the streams a second time to compute the coefficients the first read
    is weighed by."""
    n, D = cfg["hc_mult"], cfg["hidden_size"]
    sublayers = 2 * cfg["num_hidden_layers"]
    return sublayers * (tokens * (3 * n + 2) * D * itemsize
                        + calls * _map_params(cfg) * 4)


def _tree(shapes, leaf):
    return {group: {name: leaf(name, shape)
                    for name, shape in leaves.items()}
            for group, leaves in shapes.items()}


def aot_programs(cfg, traffic, devices):
    """The cell's decode and prefill programs compiled for ``devices``
    from shapes alone (perfbench/tools/rehearse_aot.py)."""
    from bluefog_tpu.models import decoder
    from bluefog_tpu.serve import kv_cache as kv
    lm, scfg = latent_config(cfg), serve_config(traffic)
    # share_programs' leaf keeps what it is told is the router in float32
    return latent_moe.share_programs(
        lm, scfg, devices,
        lambda leaf: _tree(decoder.latent_param_shapes(lm),
                           lambda name, shape: leaf(
                               "wr" if name in FLOAT32 else name, shape)),
        kv.LatentCacheConfig(layers=lm.layers, slots=scfg.slots,
                             max_len=scfg.max_len, kv_rank=lm.kv_rank,
                             rope_dim=lm.rope_dim, dtype=scfg.dtype))


def _init_params(lcfg, m, seed, dtype, std, hc):
    """The latent tree, every leaf [n, ...] on the carving's mesh, replicas
    equal, one jitted call a leaf (the largest leaf's float32 draw is the
    only temporary alive): matrices normal(0, std) in ``dtype``; in float32
    the router, its bias normal(0, e_bias_std) and the stream maps (phi
    normal(0, phi_std), the gains alpha_mean + alpha_std normal, the biases
    normal(0, bias_std): ``hc``, the configuration's ``hc_init``, says why
    each scale); RMSNorm scales 1 + 0.1 normal."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from bluefog_tpu.models import decoder
    sharding = NamedSharding(m.mesh, m.spec)
    key = jax.random.key(seed)

    def draw(name):
        """(mean, std) of a leaf by its name."""
        if name.startswith("g"):
            return 1.0, 0.1
        if name == "eb":
            return 0.0, hc["e_bias_std"]
        if name in ("h1p", "h2p"):
            return 0.0, hc["phi_std"]
        if name in ("h1a", "h2a"):
            return hc["alpha_mean"], hc["alpha_std"]
        if name in ("h1b", "h2b"):
            return 0.0, hc["bias_std"]
        return 0.0, std

    out = {}
    for gi, (group, leaves) in enumerate(
            decoder.latent_param_shapes(lcfg).items()):
        out[group] = {}
        for li, (name, shape) in enumerate(leaves.items()):
            dt = jnp.float32 if name in FLOAT32 else dtype
            mean, dev = draw(name)

            def make(k, shape=shape, dt=dt, mean=mean, dev=dev):
                z = mean + dev * jax.random.normal(k, shape, jnp.float32)
                return jnp.broadcast_to(z.astype(dt)[None], (m.size,) + shape)
            out[group][name] = jax.jit(make, out_shardings=sharding)(
                jax.random.fold_in(key, 100 * gi + li))
    return out


def layer_leaves(lcfg, tree, i, lead=()):
    """Layer ``i``'s leaves out of the parameter tree: the first, one of
    the further dense layers, or an expert layer.  ``lead`` indexes what
    stands before a leaf's own axes (``(0,)``: replica 0's row); only the
    one layer is sliced, so outside a jit only that layer is copied."""
    group, at = ("first", ()) if i == 0 \
        else ("dense", (i - 1,)) if i < lcfg.dense_layers \
        else ("blocks", (i - lcfg.dense_layers,))
    return {k: v[lead + at] if lead + at else v
            for k, v in tree[group].items()}


@functools.lru_cache(maxsize=None)
def _selections(lcfg):
    import jax
    import jax.numpy as jnp

    from bluefog_tpu.models import decoder
    from bluefog_tpu.moe.layers import held_moe_ffn

    def held_of(idx):
        local = idx - lcfg.held_start
        return jnp.any(local[..., None] == jnp.arange(lcfg.held_experts),
                       axis=1)

    def run(p, toks):
        positions = jnp.arange(toks.shape[0])
        p = jax.tree.map(lambda a: a[0], p)
        x = decoder.hc_fan_out(lcfg, p["shared"]["embed"][toks])
        sels = []
        for i in range(lcfg.layers):
            lp = layer_leaves(lcfg, p, i)

            def ffn(lp, h):
                if "wr" not in lp:
                    return decoder.dense_gated_ffn(lp, h)
                y, idx, _ = held_moe_ffn(lcfg, lp, h)
                return y, held_of(idx)
            x, _, sel = decoder.latent_block(
                lcfg, lp, x, positions, lambda qn, qr, lat, lp=lp: (
                    decoder.mla_unabsorbed(lcfg, lp, qn, qr, lat), None), ffn)
            if sel is not None:
                sels.append(sel)
        return jnp.stack(sels)
    return jax.jit(run)


def program_selections(lcfg, params, toks):
    """The held experts the PROGRAM's own block selects for every token of
    one sequence, in its own precision: the engine's layer functions
    (decoder.latent_block over the streams, moe.layers.held_moe_ffn) over
    the whole sequence, unabsorbed, no cache; NOT the timed programs.
    Returns bool [expert layers, T, held]."""
    return _selections(lcfg)(params, toks)


class Serve(latent_moe.Serve):
    """One replica of ServeEngine + Scheduler over the streamed latent
    model.  How the check serves its prompts (``reference_check``,
    ``serve_prompts``: a fresh Scheduler, candidates by prefixes, the cache
    given back before the reference runs) is the latent family's; the
    model, its reference and the comparison's limits are this file's."""

    def __init__(self, cfg, traffic, devices, seed):
        from bluefog_tpu.parallel import compose
        from bluefog_tpu.serve import Scheduler, ServeEngine

        self.cfg = cfg
        scfg = serve_config(traffic)
        self.m = compose.compose_parallelism(len(devices), 1, 1, 1,
                                             devices=devices)
        self.lm = latent_config(cfg)
        self.params = _init_params(self.lm, self.m, seed, scfg.dtype,
                                   cfg["initializer_range"], cfg["hc_init"])
        self.engine = ServeEngine(self.m, self.lm, self.params, scfg)
        self._Scheduler = Scheduler
        self.vocab = cfg["vocab_size"]
        self.limits = SERVE_LIMITS[traffic["engine"]["dtype"]]
        self.candidates = traffic["check"]["candidates"]
        self.decode_floor = traffic["check"]["decode_positions_floor"]

    def _reference(self, seq, pad):
        """(logits [T, V], selected [expert layers, T, held], margin
        [expert layers, T], the padded tokens) of the reference on
        ``self.params`` for ``seq``, one layer upcast at a time."""
        import jax.numpy as jnp
        p0 = _checks.row0(self.params)
        start, held = self.lm.held_start, self.lm.held_experts
        toks = np.zeros((pad,), np.int32)
        toks[:len(seq)] = seq
        want, sel, margin = reference.forward(
            self.cfg, lambda i: layer_leaves(self.lm, p0, i, (0,)),
            {k: v[0] for k, v in p0["shared"].items()}, jnp.asarray(toks),
            start)
        return (np.asarray(want)[:len(seq)],
                np.asarray(sel)[:, :len(seq), start:start + held],
                np.asarray(margin)[:, :len(seq)], toks)

    def compare(self, groups, output_tokens):
        """The reference's side.  ``groups``: per asked length its
        candidate prompts (longest first), their requests and their
        prefill logits."""
        import jax.numpy as jnp
        rows, flips, pairs = [], 0, 0
        for cands, reqs, got in groups:
            # a length of its own per asked prompt: the short one's two
            # passes cost a fraction of the long one's
            pad = -(-(len(cands[0]) + output_tokens) // 128) * 128
            whole = all(r.state == "done"
                        and len(r.generated) == output_tokens for r in reqs)
            want, _, margin, _ = self._reference(cands[0], pad)
            decided = margin.min(0) >= ROUTE_MARGIN
            scale = float(np.max(np.abs(want)))
            errs = sorted(float(np.max(np.abs(mine - want[len(c) - 1])))
                          for c, mine in zip(cands, got)
                          if decided[len(c) - 1])
            row = {"prompt_tokens": len(cands[0]), "candidates": len(cands),
                   "prefills_compared": len(errs),
                   "prefill_logit_max_abs_err": max(errs, default=0.0),
                   # the largest but one, under its own tighter limit; a
                   # length with fewer than two compared prefills counts as
                   # not compared
                   "prefill_logit_abs_err_but_one":
                       errs[-2] if len(errs) > 1 else 0.0,
                   "prefill_scale": scale,
                   "positions_decided_share": float(decided.mean()),
                   "off_length": int(not whole)}
            pick = next((j for j, c in enumerate(cands)
                         if decided[len(c) - 1]), None)
            if pick is not None:
                # the decode of the longest decided candidate: generated[j]
                # was chosen from position len(prompt) - 1 + j, and from
                # j = 1 on by the decode program
                seq = cands[pick] + [int(t) for t in reqs[pick].generated]
                want, sel, margin, toks = self._reference(seq, pad)
                decided = margin.min(0) >= ROUTE_MARGIN
                last = len(cands[pick]) - 1
                gaps = [float(want[last + j].max() - want[last + j, int(t)])
                        for j, t in enumerate(reqs[pick].generated)
                        if j and decided[last + j]]
                prog = np.asarray(program_selections(
                    self.lm, self.params, jnp.asarray(toks)))[:, :len(seq)]
                differ = (prog != sel).any(-1)        # [expert layers, T]
                flips += int(differ.sum())
                pairs += differ.size
                row.update({
                    "decode_of_prompt_tokens": len(cands[pick]),
                    "decode_positions_decided": len(gaps),
                    "decode_logit_gap_max": max(gaps, default=0.0),
                    "decode_scale": float(np.max(np.abs(want))),
                    "selection_flips_at_decided": int(
                        differ[:, decided].sum()),
                    # per margin: positions whose EVERY layer is at least
                    # that far from flipping, and those among them at
                    # which some layer's selection differs
                    "flips_by_margin": {
                        d: [int((margin.min(0) >= d).sum()),
                            int(differ[:, margin.min(0) >= d].any(0).sum())]
                        for d in reference.LADDER}})
            rows.append(row)

        def worst(key, scale):
            return max((r[key] / r[scale] for r in rows if key in r),
                       default=0.0)
        compared = {
            "prefill_logit_err_share_largest": [
                worst("prefill_logit_max_abs_err", "prefill_scale"),
                self.limits["prefill_largest"]],
            "prefill_logit_err_share": [
                worst("prefill_logit_abs_err_but_one", "prefill_scale"),
                self.limits["prefill_but_one"]],
            "decode_logit_gap_share": [
                worst("decode_logit_gap_max", "decode_scale"),
                self.limits["decode_gap"]],
            "prefill_lengths_not_compared": [
                sum(r["prefills_compared"] < 2 for r in rows), 0],
            "decode_positions_short_of_floor": [
                max(0, self.decode_floor - sum(
                    r.get("decode_positions_decided", 0) for r in rows)), 0],
            "requests_off_length": [sum(r["off_length"] for r in rows), 0]}
        ok = all(value <= limit for value, limit in compared.values())
        # a diagnostic, with the limit it cannot pass: the share of (token,
        # layer) pairs at which the program's block functions, in the
        # program's precision but NOT through its timed programs
        # (program_selections), select another set of experts than the
        # reference.  What holds the timed programs' routing to the
        # reference is the logits at the decided positions
        compared["route_flip_share"] = [flips / max(pairs, 1), 1.0]
        return {"ok": bool(ok), "tolerance": self.limits,
                "route_margin": ROUTE_MARGIN, "requests": rows,
                "compared": compared}


def build_serve(cfg, traffic, devices, seed):
    return Serve(cfg, traffic, devices, seed)
