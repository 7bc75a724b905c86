"""Family ``latent_scmoe``: a latent-attention decoder of shortcut-connected
double layers (two latent attentions with their own cached vectors, two
dense gated FFNs, ONE expert layer that reads the first half's normed
activation and whose result joins after the second half's FFN) under a
softmax router of which the last outputs are identity experts that compute
nothing, with no shared expert and no leading dense layer, served as ONE
chip's share of an expert-parallel deployment through ServeEngine +
Scheduler on the latent family's two programs
(bluefog_tpu.models.decoder.LatentConfig with ``shortcut``, ``router``,
``zero_experts``, ``shared_expert``, ``q_scale``, ``kv_scale``): the router
keeps its published width, the chip holds the experts and the vocabulary
slice the configuration file's ``deployment`` names, and what the absent
experts would add is left out in program and reference alike.

This file maps the source's key names onto LatentConfig, makes the weights
on the device from the seed leaf by leaf, holds the comparison with the
plain reference (perfbench/reference/latent_scmoe.py), the bytes a decode
call cannot avoid (``engine.decode_hbm_roofline_share``) and the operations
a prompt needs (``engine.prefill_mxu_roofline_share.latent``).
"""
import dataclasses
import time

import numpy as np

from perfbench.families import _checks, latent_hc_moe, latent_moe
from perfbench.families.composed_lm import serve_config
from perfbench.reference import latent_scmoe as reference

# |program - reference| as a share of the largest reference logit: one limit
# a number that is compared, by the precision the traffic file states for
# the engine, with the reference evaluated under the PROGRAM's expert
# selections (ROUTE_TIE below).  The cell serves in bf16 (weights,
# activations, the latent cache; router, its scores and its bias in f32).
# Each limit lies between its two readings on the chip at the cell's size
# (CHIP_READINGS; my chip runs, PR 50; docs/PERF_PR50_RECORD.md has every
# run): the most the sound program read over seventeen seeds, and what the
# reference read with its leaves through int8 and back (the nearest
# precision below the served one).  The sound readings are two to three
# times the other held-experts families': the two scales put 6.9 times the
# size on an attention score (the softmax is that much sharper), and the
# weights of twelve outputs a token are raw softmax scores, which an error
# of the router's input moves by its own share.
#   prefill   the asked prompts' prefill logits, number by number: sound
#             0.0260-0.0382, int8 0.147; 0.07 is 1.8 times the one and
#             0.48 of the other
#   decode    every decoded position's logits, number by number (126 a
#             run): decode is another compiled program (absorbed attention
#             over the cache, every lane through every held expert), held
#             by its own number: sound 0.0345-0.0452, int8 0.179; 0.08 is
#             1.77 times and 0.45
#   gap       how far the reference's best logit lies over its logit of the
#             token the program chose, at most over the decoded positions:
#             sound 0.0097-0.0344, int8 0.107; 0.06 is 1.74 times and 0.56
# With the identity experts dropped the three read 0.289 / 0.376 / 0.173,
# with the experts' result joined a sublayer early 0.208 / 0.516 / 0.318,
# without kv_scale 0.878 / 1.17 / 1.07, without q_scale 1.00 / 1.06 / 0.84.
# The CPU rehearsal states float32: the program then IS the reference's
# function (it reads 2e-7), and every control fails by a factor of ten or
# more.
SERVE_LIMITS = {
    "bfloat16": {"prefill": 7e-2, "decode": 8e-2, "gap": 6e-2},
    "float32": {"prefill": 1e-3, "decode": 1e-3, "gap": 1e-3}}
# At 12 of 768 the cut sits where neighbouring softmax scores lie 5 % of
# their size apart (768 x the normal density at its 98.4th percentile), and
# the program's bf16 activations move a score by about as much: at a third
# of all (token, layer) pairs the program chose another SET than the
# reference (0.323-0.336, seventeen seeds).  Of the 768 outputs 272 change the
# result when they flip (16 held experts, 256 identity experts), so a
# margin that leaves undecided positions out (the other latent families'
# ROUTE_MARGIN) would keep one position in ten thousand (numpy at the
# published widths: a tenth of the (token, layer) pairs are decided at
# 0.03, all four layers of a position at 1e-4 of them).  Instead the
# programs hand out the outputs each layer chose (ServeEngine.decode_chosen
# / prefill_chosen), the reference is evaluated UNDER THOSE SELECTIONS
# (weights still from its own scores), and the outputs by which a selection
# differs from the reference's own are held to a rounding tie: how far an
# output's router logit and the cut's would each have to move towards the
# other for its ``score + bias`` to meet the 12th largest, as a share of
# the router logits' root mean square (reference.tie_distance).  Two
# numbers hold them: of the outputs swapped at most ``far_share`` lie
# farther than ``delta`` (sound: 0.0005-0.0014 of some 12,000 swapped
# outputs a run, fourteen seeds; a program that drops the selection bias
# 0.205, the int8 reference 0.239), and none farther than ``farthest``
# (sound 0.073-0.089 in thirteen runs and 0.147 in one; 0.431 and 0.403).
# The limits are 21 times the sound share and a seventh of the controls',
# twice the largest sound distance and three quarters of the controls': a
# run's farthest is one output of 12,000, and its tail is long.
# (The first form of the distance, in the output's own logit alone, read
# 0.21-0.59 sound: an output of a small score near the cut on its bias is
# flipped by the CUT's move.)
ROUTE_TIE = {
    "bfloat16": {"delta": 6e-2, "far_share": 0.03, "farthest": 0.3},
    "float32": {"delta": 1e-3, "far_share": 0.0, "farthest": 1e-3}}
# what the bf16 limits above stand between: name -> (the most the sound
# program read on the chip over its seeds, the least a control read: the
# reference's leaves through int8 and back and, for the two tie numbers, a
# program that selects without the bias as well); my chip runs, PR 50
CHIP_READINGS = {"prefill": (0.0382, 0.147), "decode": (0.0452, 0.179),
                 "gap": (0.0344, 0.107), "far_share": (0.0014, 0.205),
                 "farthest": (0.1471, 0.403)}

# leaves kept in float32 whatever the served type: the router and its bias
FLOAT32 = ("wr", "eb")
assert set(FLOAT32) <= set(latent_hc_moe.FLOAT32)


def latent_config(cfg):
    from bluefog_tpu.models import decoder
    from perfbench.runners import _common
    if "shortcut" not in {f.name for f in
                          dataclasses.fields(decoder.LatentConfig)}:
        raise _common.Refused(
            "the program under test has no shortcut-connected double layer "
            "(decoder.LatentConfig.shortcut): it cannot run this family")
    dep = cfg["deployment"]
    held = dep["held_experts"]
    if held[1] - held[0] != cfg["n_routed_experts"]:
        raise ValueError("n_routed_experts is the count HELD here and must "
                         "equal the deployment's held_experts range")
    if cfg["zero_expert_type"] != "identity" \
            or cfg["attention_method"] != "MLA" or cfg["attention_bias"]:
        raise ValueError("this family serves identity zero experts under "
                         "latent attention with no bias")
    s_q, s_kv = reference.scales(cfg)
    return decoder.LatentConfig(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        heads=cfg["num_attention_heads"], layers=cfg["num_layers"],
        q_rank=cfg["q_lora_rank"], kv_rank=cfg["kv_lora_rank"],
        nope_dim=cfg["qk_nope_head_dim"], rope_dim=cfg["qk_rope_head_dim"],
        v_dim=cfg["v_head_dim"], dense_ffn=cfg["ffn_hidden_size"],
        expert_ffn=cfg["expert_ffn_hidden_size"],
        num_experts=dep["router_outputs"], held_experts=held[1] - held[0],
        held_start=held[0], top_k=cfg["moe_topk"], n_group=1, topk_group=1,
        route_scale=float(cfg["routed_scaling_factor"]),
        rope_base=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"],
        dense_layers=0, route_bias=True, shortcut=True, router="softmax",
        zero_experts=cfg["zero_expert_num"], shared_expert=False,
        q_scale=s_q, kv_scale=s_kv)


def held_experts(cfg):
    """Routed experts this chip holds in each expert layer."""
    lo, hi = cfg["deployment"]["held_experts"]
    return hi - lo


def expert_layers(cfg):
    """Layers with routed experts: every double layer has one."""
    return cfg["num_layers"]


def weight_bytes(cfg, itemsize=2):
    """Bytes of the layers' and the head's weights a decode call reads
    whatever it routes: a layer's two attentions with their norms and its
    two dense FFNs, the router with its bias (float32), the head and the
    final norm; not the embedding table (a call reads one row a lane) nor
    the routed experts (counted per expert that got a token,
    :func:`decode_floor_bytes`).  An identity expert has no byte.  My own
    arithmetic from the file's keys."""
    D, L = cfg["hidden_size"], cfg["num_layers"]
    norms = 2 * D + cfg["q_lora_rank"] + cfg["kv_lora_rank"]
    served = (L * 2 * (latent_hc_moe._attention_params(cfg) + norms
                       + 3 * D * cfg["ffn_hidden_size"])
              + D * cfg["vocab_size"] + D)
    kept = L * (D + 1) * cfg["deployment"]["router_outputs"]
    return served * itemsize + kept * 4


def expert_bytes(cfg, itemsize=2):
    return 3 * cfg["hidden_size"] * cfg["expert_ffn_hidden_size"] * itemsize


def position_bytes(cfg, itemsize=2):
    """Bytes one cached position costs over all attention sublayers."""
    return (2 * cfg["num_layers"]
            * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * itemsize)


def decode_floor_bytes(cfg, calls, experts_hit, live_positions, itemsize=2):
    """The bytes ``calls`` decode calls cannot avoid: every weight byte of
    the layers and the head once a call, each held expert once per call
    and layer in which a token fell on it (``experts_hit``, summed over
    the calls), and the LIVE cache positions of the calls' lanes
    (``live_positions``, summed) in every attention sublayer (9,216 bytes
    a position at the published sizes).  Identity outputs cost no byte.  A
    lower bound: a program that reads a slot's whole row, or an expert
    no token fell on, reads more."""
    return (calls * weight_bytes(cfg, itemsize)
            + experts_hit * expert_bytes(cfg, itemsize)
            + live_positions * position_bytes(cfg, itemsize))


def prefill_flops(cfg, tokens):
    """The operations a prompt of ``tokens`` REAL tokens needs through the
    layers held here (2 per multiply-add): every matmul of a token (both
    latent attentions' five, keys and values rebuilt per head from the
    compressed vector; both dense FFNs; the router; of the ``moe_topk``
    selected outputs the share that falls on HELD experts at an even
    spread, identity and absent outputs costing nothing), causal attention
    of both sublayers (a query at t meets t + 1 keys: scores over nope +
    rope, the weighted sum over v), and the head for the one position that
    is read out."""
    D, H, L = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_layers"]
    outputs = cfg["deployment"]["router_outputs"]
    held_pairs = cfg["moe_topk"] * held_experts(cfg) / outputs
    per_token = L * (2 * (latent_hc_moe._attention_params(cfg)
                          + 3 * D * cfg["ffn_hidden_size"])
                     + D * outputs
                     + held_pairs * 3 * D * cfg["expert_ffn_hidden_size"])
    keys_met = 2 * L * tokens * (tokens + 1) // 2
    per_key = H * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                   + cfg["v_head_dim"])
    return (2 * per_token * tokens + 2 * per_key * keys_met
            + 2 * D * cfg["vocab_size"])


def _cache_config(lm, scfg):
    from bluefog_tpu.serve import kv_cache as kv
    return kv.LatentCacheConfig(layers=lm.attn_layers, slots=scfg.slots,
                                max_len=scfg.max_len, kv_rank=lm.kv_rank,
                                rope_dim=lm.rope_dim, dtype=scfg.dtype)


def aot_programs(cfg, traffic, devices):
    """The cell's decode and prefill programs compiled for ``devices``
    from shapes alone (perfbench/tools/rehearse_aot.py)."""
    from bluefog_tpu.models import decoder
    lm, scfg = latent_config(cfg), serve_config(traffic)
    shapes = decoder.latent_param_shapes(lm)
    # share_programs' leaf keeps what it is told is the router in float32
    return latent_moe.share_programs(
        lm, scfg, devices,
        lambda leaf: {group: {name: leaf(
            "wr" if name in FLOAT32 else name, shape)
            for name, shape in leaves.items()}
            for group, leaves in shapes.items()},
        _cache_config(lm, scfg))


def layer_leaves(tree, i, lead=()):
    """Double layer ``i``'s ``(first half, second half)`` leaves out of the
    parameter tree.  ``lead`` indexes what stands before a leaf's own axes
    (``(0,)``: replica 0's row); only the one layer is sliced."""
    return tuple({k: v[lead + (i,)] for k, v in tree[group].items()}
                 for group in ("blocks", "blocks2"))


def selection_report(dist, picked, chosen, delta):
    """How the program's selections ``chosen`` ``[layers, T, k]`` stand to
    the reference's own ``picked`` ``[layers, T, k]``, ``dist`` ``[layers,
    T, outputs]`` each output's distance from the reference's cut
    (reference.tie_distance): ``(differing, swapped, far, pairs,
    farthest)``: the (token, layer) pairs at which the two SETS differ,
    the outputs by which they differ, those of them farther than ``delta``
    from the cut (a tie a rounding decides lies within it), the pairs
    looked at, and how far the farthest such output lies."""
    dist = np.asarray(dist)
    E = dist.shape[-1]

    def member(idx):            # [layers, T, E] bool; -1 selects nothing
        idx = np.asarray(idx)
        out = np.zeros(dist.shape[:-1] + (E + 1,), bool)
        np.put_along_axis(out, np.where(idx < 0, E, idx), True, -1)
        return out[..., :E]
    apart = member(chosen) != member(picked)
    off = np.where(apart, dist, 0.0)
    return (int(apart.any(-1).sum()), int(apart.sum()),
            int((off > delta).sum()), int(apart[..., 0].size),
            float(off.max()))


class Serve(latent_moe.Serve):
    """One replica of ServeEngine + Scheduler over the double-layer model.
    Warm-up, the Scheduler and the retrace count are the latent family's;
    the model, its reference and what is compared are this file's."""

    def __init__(self, cfg, traffic, devices, seed):
        from bluefog_tpu.parallel import compose
        from bluefog_tpu.serve import Scheduler, ServeEngine

        self.cfg = cfg
        scfg = serve_config(traffic)
        self.m = compose.compose_parallelism(len(devices), 1, 1, 1,
                                             devices=devices)
        self.lm = latent_config(cfg)
        # leaf by leaf on the device: matrices normal(0, initializer_range),
        # norm scales 1 + 0.1 normal, in float32 the router and its
        # selection bias normal(0, e_bias_std)
        self.params = latent_hc_moe._init_params(
            self.lm, self.m, seed, scfg.dtype, cfg["initializer_range"],
            {"e_bias_std": cfg["e_bias_std"]})
        self.engine = ServeEngine(self.m, self.lm, self.params, scfg)
        self._Scheduler = Scheduler
        self.vocab = cfg["vocab_size"]
        dtype = traffic["engine"]["dtype"]
        self.limits = SERVE_LIMITS[dtype]
        self.tie = ROUTE_TIE[dtype]

    def reference_check(self, prompts, output_tokens):
        """Prefill then decode through the latent cache, by way of a fresh
        Scheduler, against the reference's full forward pass a sublayer at
        a time, evaluated under the outputs the program's own layers
        chose: every asked prompt's prefill logits and every decoded
        position's logits number by number, and every selection that
        differs from the reference's own held to a rounding tie.  The cache
        is given back to the device before the reference runs."""
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
        what ``generated[j]`` was chosen from, ``j >= 1``); then each
        prompt's prefill logits and selections; then the cache is deleted.
        Returns the requests and per request ``(prefill logits, prefill
        selections [layers, n, k], decode logits, decode selections)``."""
        sched = self.scheduler()
        reqs = [sched.submit(p, max_new_tokens=output_tokens) for p in prompts]
        logits, chosen = [{} for _ in reqs], [{} for _ in reqs]
        for _ in range(10_000):
            if sched.done:
                break
            before = [len(r.generated) for r in reqs]
            sched.step()
            handed = self.engine.decode_logits(0)
            if handed is None:          # a step of prefills alone
                continue
            slots, rows = handed
            lane = {int(s): i for i, s in enumerate(slots)}
            rows = np.asarray(rows)                     # [steps, S, vocab]
            sets = np.asarray(self.engine.decode_chosen(0)[1])
            for r, n0, keep, kept in zip(reqs, before, logits, chosen):
                first = max(n0, 1)      # generated[0] is the prefill's
                for j in range(first, len(r.generated)):
                    keep[j] = rows[j - first, lane[r.slot]]
                    kept[j] = sets[j - first, :, lane[r.slot]]
        sched.close()
        got = []
        for p, dec, sel in zip(prompts, logits, chosen):
            last = np.asarray(self.engine.prefill(0, 0, p)[1], np.float32)
            first = np.asarray(self.engine.prefill_chosen(0))[:, :len(p)]
            got.append((last, first, dec, sel))
        for leaf in self.engine.cache.values():
            leaf.delete()
        return reqs, got

    def _reference(self, seq, pad, chosen):
        """(logits [T, V], distances [layers, T, outputs], picked [layers,
        T, k]) of the reference on ``self.params`` for ``seq`` under the
        selections ``chosen`` [layers, T, k], a sublayer upcast at a
        time."""
        import jax.numpy as jnp
        p0 = _checks.row0(self.params)
        toks = np.zeros((pad,), np.int32)
        toks[:len(seq)] = seq
        sets = np.full((chosen.shape[0], pad, chosen.shape[2]), -1, np.int32)
        sets[:, :len(seq)] = chosen
        want, p, by, picked, size = reference.forward(
            self.cfg, lambda i: layer_leaves(p0, i, (0,)),
            {k: v[0] for k, v in p0["shared"].items()}, jnp.asarray(toks),
            self.lm.held_start, chosen=jnp.asarray(sets))
        T = len(seq)
        dist = reference.tie_distance(p, by, picked, size)
        return (np.asarray(want)[:T], np.asarray(dist)[:, :T],
                np.asarray(picked)[:, :T])

    def compare(self, prompts, reqs, got, output_tokens):
        """The reference's side, per asked prompt: position ``n - 1`` from
        the prefill, position ``n - 1 + j`` (``j >= 1``) from the decode
        call that chose ``generated[j]``."""
        rows = []
        for p, req, (first, sel0, dec, sel) in zip(prompts, reqs, got):
            gen = [int(t) for t in req.generated]
            whole = req.state == "done" and len(gen) == output_tokens \
                and sorted(dec) == list(range(1, len(gen)))
            at = sorted(dec)
            seq = p + gen[:len(at)]
            chosen = np.concatenate(
                [sel0] + [sel[j][:, None] for j in at], axis=1)
            pad = -(-len(seq) // 128) * 128
            want, dist, picked = self._reference(seq, pad, chosen)
            differing, swapped, far, pairs, farthest = selection_report(
                dist, picked, chosen, self.tie["delta"])
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
                "outputs_swapped": swapped, "outputs_swapped_far": far,
                "route_tie_distance": farthest,
                # the share of the selected pairs on identity outputs and
                # on held experts, as the program chose them
                "zero_pair_share": float(np.mean(
                    chosen >= self.lm.num_experts - self.lm.zero_experts)),
                "off_length": int(not whole)})

        def worst(key):
            return max((r[key] / r["scale"] for r in rows), default=0.0)
        total = lambda key: sum(r[key] for r in rows)
        compared = {
            "prefill_logit_err_share": [worst("prefill_logit_max_abs_err"),
                                        self.limits["prefill"]],
            "decode_logit_err_share": [worst("decode_logit_max_abs_err"),
                                       self.limits["decode"]],
            "decode_logit_gap_share": [worst("decode_logit_gap_max"),
                                       self.limits["gap"]],
            "route_far_share": [
                total("outputs_swapped_far")
                / max(total("outputs_swapped"), 1), self.tie["far_share"]],
            "route_tie_distance": [
                max(r["route_tie_distance"] for r in rows),
                self.tie["farthest"]],
            "requests_off_length": [total("off_length"), 0]}
        ok = all(value <= limit for value, limit in compared.values())
        # a diagnostic, with the limit it cannot pass: the share of (token,
        # layer) pairs at which the program chose another set than the
        # reference, each held to a tie by the two numbers above
        compared["route_tied_share"] = [
            total("selections_tied") / max(total("selections"), 1), 1.0]
        return {"ok": bool(ok), "tolerance": self.limits,
                "tie": self.tie, "requests": rows,
                "compared": compared}


def build_serve(cfg, traffic, devices, seed):
    return Serve(cfg, traffic, devices, seed)
