"""Family ``latent_moe``: a latent-attention decoder with a leading dense
layer and sigmoid-routed expert layers, served as ONE chip's share of an
expert-parallel deployment through ServeEngine + Scheduler
(bluefog_tpu.models.decoder.LatentConfig): the router keeps its published
width, the chip holds the experts and the vocabulary slice the
configuration file's ``deployment`` names, and what the absent experts
would add is left out in program and reference alike.

This file maps the source's key names onto LatentConfig, makes the weights
on the device from the seed leaf by leaf, holds the comparison with the
plain reference (perfbench/reference/latent_moe.py) and the bytes a decode
call cannot avoid (for ``engine.decode_hbm_roofline_share``).
"""
import functools
import time

import numpy as np

from perfbench.families import _checks
from perfbench.families.composed_lm import serve_config
from perfbench.reference import latent_moe as reference

# |program - reference| as a share of the largest reference logit, by the
# precision the traffic file states for the engine.  The cell serves in bf16
# end to end (weights, activations, the latent cache; the router in f32).
# On the chip at the cell's size (my chip runs, PR 33; PERF.md section 6):
# prefill logits read 0.011-0.015 at decided positions (0.011-0.013 at
# undecided ones that did not flip, 0.071 at one that did), decoded tokens'
# gaps 0-0.007; with the PROGRAM's weights through int8 and back the same
# prompts read 0.069 and 0.042: correct false by both.  0.03 is twice the
# largest bf16 reading and under half the int8 one.  The CPU rehearsal
# states float32: the program then IS the reference's function (it reads
# 2e-7), and int8 weights (0.010-0.013 at the tiny sizes) or bf16 fail by a
# factor of ten.
SERVE_LOGIT_TOL = {"bfloat16": 3e-2, "float32": 1e-3}
# A position is compared only where the REFERENCE's own router puts every
# held expert's selection, in every layer, at least this far from flipping
# (reference.held_margin; a share of the router logits' root mean square):
# nearer than that the function jumps (one flipped held expert moves the
# logits by 7 % of the largest), and the program's bf16 activations, 1.3 %
# off by the last layer, land on either side: 1.7-2.2 % of (token, layer)
# pairs select another set of held experts.  At 0.012 two to seven such
# flips a run fell on positions that counted as decided, at 0.03 none in 16
# prompts (a third of the positions stay); the flips that are left at
# 0.03 are of tokens an earlier layer had already flipped.
#
# A third is too few to leave to chance (two prompts of eight outputs
# compared no prefill at all in four seeds of ten), so the check makes its
# own coverage, by the reference alone: of each asked length it serves
# ``check.candidates`` prompts, the asked prompt and its prefixes one token
# shorter each, compares the prefill of EVERY one whose last position the
# reference decides, and follows the longest such through its decode; a
# length with no decided candidate, or fewer decided decode positions than
# ``check.decode_positions_floor`` over the lengths, is not correct.
ROUTE_MARGIN = 0.03


def latent_config(cfg):
    from bluefog_tpu.models import decoder
    dep, sc = cfg["deployment"], cfg["rope_scaling"]
    held = dep["held_experts"]
    if held[1] - held[0] != cfg["n_routed_experts"]:
        raise ValueError("n_routed_experts is the count HELD here and must "
                         "equal the deployment's held_experts range")
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
        eps=cfg["rms_norm_eps"])


def held_experts(cfg):
    """Routed experts this chip holds in each expert layer."""
    lo, hi = cfg["deployment"]["held_experts"]
    return hi - lo


def expert_layers(cfg):
    """Layers with routed experts: all but the leading dense one."""
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def weight_bytes(cfg, itemsize=2):
    """Bytes of the six layers' and the head's weights a decode call reads
    whatever it routes: everything but the embedding table (a call reads
    one row a lane) and the routed experts (counted per expert that got a
    token, :func:`decode_floor_bytes`).  My own arithmetic from the file's
    keys; the router's weight is float32."""
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    attn = (D * cfg["q_lora_rank"] + cfg["q_lora_rank"] * H * (nope + rope)
            + D * (cfg["kv_lora_rank"] + rope)
            + cfg["kv_lora_rank"] * H * (nope + cfg["v_head_dim"])
            + H * cfg["v_head_dim"] * D
            + 2 * D + cfg["q_lora_rank"] + cfg["kv_lora_rank"])
    L = cfg["num_hidden_layers"]
    fixed = (L * attn + 3 * D * cfg["intermediate_size"]
             + (L - 1) * 3 * D * cfg["moe_intermediate_size"]
             + D * cfg["vocab_size"] + D) * itemsize
    router = (L - 1) * D * cfg["deployment"]["router_outputs"] * 4
    return fixed + router


def expert_bytes(cfg, itemsize=2):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * itemsize


def decode_floor_bytes(cfg, calls, experts_hit, live_positions, itemsize=2):
    """The bytes ``calls`` decode calls cannot avoid: every weight byte of
    the layers and the head once a call, each held expert once per call
    and layer in which a token fell on it (``experts_hit``, summed over
    the calls), and the LIVE cache positions of the calls' lanes
    (``live_positions``, summed) in every layer.  A lower bound: a program
    that reads a slot's whole row, or an expert twice, reads more."""
    latent = (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * itemsize
    return (calls * weight_bytes(cfg, itemsize)
            + experts_hit * expert_bytes(cfg, itemsize)
            + live_positions * cfg["num_hidden_layers"] * latent)


def share_programs(lm, scfg, devices, params_of, cache_cfg):
    """The programs a held-experts engine compiles for ``lm`` (decode at
    every batch bucket, prefill at every prompt bucket) for ``devices[0]``,
    from shapes alone: ``ServeEngine``'s own jitted bodies
    without an engine (which would place parameters and a cache on real
    devices; ``devices`` may be a described chip's).  ``params_of(leaf)``
    builds the family's parameter tree from ``leaf(name, shape)``.
    Returns ``[(name, compiled, 1)]`` for perfbench/tools/rehearse_aot.py."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from bluefog_tpu.models import decoder
    from bluefog_tpu.parallel import compose
    from bluefog_tpu.serve import ServeEngine
    m = compose.compose_parallelism(1, 1, 1, 1, devices=devices[:1])
    eng = ServeEngine.__new__(ServeEngine)
    eng._moe, eng._share = False, True
    eng._latent = isinstance(lm, decoder.LatentConfig)
    eng._hybrid = not eng._latent
    eng.m, eng.cfg, eng.scfg = m, lm, scfg
    sh = NamedSharding(m.mesh, m.spec)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct((1,) + tuple(shape), dtype, sharding=sh)
    # the router's weight is float32, every other leaf the served type
    params = params_of(lambda name, shape: sds(
        shape, jnp.float32 if name == "wr" else scfg.dtype))
    state = lambda: ({k: sds(shape, scfg.dtype)
                      for k, shape in cache_cfg.shapes().items()},
                     sds((cache_cfg.rows, 2), jnp.uint32))
    decode, prefill = (
        (eng._latent_decode_body, eng._latent_prefill_body) if eng._latent
        else (eng._hybrid_decode_body, eng._hybrid_prefill_body))
    decode, prefill = eng._build(decode), eng._build(prefill)
    # the one staged array of a call: a token and 4 integers a lane, or a
    # prompt's tokens and 4 integers
    return [(f"decode_S{S}", decode.lower(
        params, *state(), sds((S, 1 + 4), jnp.int32)).compile(), 1)
        for S in scfg.batch_buckets] + [(f"prefill_T{T}", prefill.lower(
            params, *state(), sds((T + 4,), jnp.int32)).compile(), 1)
        for T in scfg.prefill_buckets]


def aot_programs(cfg, traffic, devices):
    """The cell's decode and prefill programs compiled for ``devices``
    from shapes alone (perfbench/tools/rehearse_aot.py)."""
    from bluefog_tpu.models import decoder
    from bluefog_tpu.serve import kv_cache as kv
    lm, scfg = latent_config(cfg), serve_config(traffic)
    shapes = decoder.latent_param_shapes(lm)
    return share_programs(
        lm, scfg, devices,
        lambda leaf: {group: {name: leaf(name, shape)
                              for name, shape in leaves.items()}
                      for group, leaves in shapes.items()},
        kv.LatentCacheConfig(layers=lm.layers, slots=scfg.slots,
                             max_len=scfg.max_len, kv_rank=lm.kv_rank,
                             rope_dim=lm.rope_dim, dtype=scfg.dtype))


def _init_params(lcfg, m, seed, dtype, std):
    """The latent tree, every leaf [n, ...] on the carving's mesh, replicas
    equal, one jitted call a leaf (the largest leaf's float32 draw is the
    only temporary alive): matrices normal(0, std) in ``dtype``, the router
    in float32, RMSNorm scales 1 + 0.1 normal."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from bluefog_tpu.models import decoder
    sharding = NamedSharding(m.mesh, m.spec)
    key = jax.random.key(seed)
    out = {}
    for gi, (group, leaves) in enumerate(
            decoder.latent_param_shapes(lcfg).items()):
        out[group] = {}
        for li, (name, shape) in enumerate(leaves.items()):
            dt = jnp.float32 if name == "wr" else dtype
            scale = name.startswith("g")

            def make(k, shape=shape, dt=dt, scale=scale):
                z = jax.random.normal(k, shape, jnp.float32)
                z = 1.0 + 0.1 * z if scale else std * z
                return jnp.broadcast_to(z.astype(dt)[None], (m.size,) + shape)
            out[group][name] = jax.jit(make, out_shardings=sharding)(
                jax.random.fold_in(key, 100 * gi + li))
    return out


@functools.lru_cache(maxsize=None)
def _selections(lcfg):
    import jax
    import jax.numpy as jnp

    from bluefog_tpu.models import decoder
    from bluefog_tpu.moe.layers import held_moe_ffn

    def attend_of(lp):
        return lambda qn, qr, lat: (
            decoder.mla_unabsorbed(lcfg, lp, qn, qr, lat), None)

    def held_of(idx):
        local = idx - lcfg.held_start
        return jnp.any(local[..., None] == jnp.arange(lcfg.held_experts),
                       axis=1)

    def run(p, toks):
        positions = jnp.arange(toks.shape[0])
        p = jax.tree.map(lambda a: a[0], p)
        x = p["shared"]["embed"][toks]
        x, _, _ = decoder.latent_block(lcfg, p["first"], x, positions,
                                       attend_of(p["first"]),
                                       decoder.dense_gated_ffn)

        def body(x, lp):
            def ffn(lp, h):
                y, idx, _ = held_moe_ffn(lcfg, lp, h)
                return y, held_of(idx)
            x, _, sel = decoder.latent_block(lcfg, lp, x, positions,
                                             attend_of(lp), ffn)
            return x, sel
        return jax.lax.scan(body, x, p["blocks"])[1]
    return jax.jit(run)


def program_selections(lcfg, params, toks):
    """The held experts the PROGRAM's own block selects for every token of
    one sequence, in its own precision: the engine's layer functions
    (decoder.latent_block, moe.layers.held_moe_ffn) over the whole
    sequence, unabsorbed, no cache; NOT the timed programs.  Returns bool
    [L-1, T, held]."""
    return _selections(lcfg)(params, toks)


class Serve:
    """One replica of ServeEngine + Scheduler over the latent model."""

    def __init__(self, cfg, traffic, devices, seed):
        from bluefog_tpu.parallel import compose
        from bluefog_tpu.serve import Scheduler, ServeEngine

        self.cfg = cfg
        scfg = serve_config(traffic)
        self.m = compose.compose_parallelism(len(devices), 1, 1, 1,
                                             devices=devices)
        self.lm = latent_config(cfg)
        self.params = _init_params(self.lm, self.m, seed, scfg.dtype,
                                   cfg["initializer_range"])
        self.engine = ServeEngine(self.m, self.lm, self.params, scfg)
        self._Scheduler = Scheduler
        self.vocab = cfg["vocab_size"]
        self.tol = SERVE_LOGIT_TOL[traffic["engine"]["dtype"]]
        self.candidates = traffic["check"]["candidates"]
        self.decode_floor = traffic["check"]["decode_positions_floor"]

    def warmup(self):
        self.engine.warmup()
        print("[perfbench] program_memory: %s" % self.engine.program_memory(),
              flush=True)

    def scheduler(self):
        return self._Scheduler(self.engine)

    def retraces(self):
        from bluefog_tpu.utils import metrics
        return int(metrics.counter(
            "bluefog_retrace_after_warmup_total").total())

    def reference_check(self, prompts, output_tokens):
        """Prefill then decode through the latent cache, by way of a fresh
        Scheduler, against the reference's full forward pass a layer at a
        time.  Of each asked prompt ``candidates`` prefixes are served (see
        ROUTE_MARGIN): every prefill whose last position the reference's
        routing decides is compared number by number, and the longest
        such candidate's decoded tokens each have their reference logit
        within tolerance of the reference's largest, at the decided
        positions.  The cache is given back to the device before the
        reference runs: one layer in float32 has to fit beside the
        weights."""
        groups = [[list(p[:len(p) - j]) for j in range(self.candidates)
                   if len(p) - j >= 1] for p in prompts]
        flat = [c for g in groups for c in g]
        t0 = time.perf_counter()
        reqs, got = self.serve_prompts(flat, output_tokens)
        served = time.perf_counter() - t0
        out, at = [], 0
        for g in groups:
            out.append((g, reqs[at:at + len(g)], got[at:at + len(g)]))
            at += len(g)
        report = self.compare(out, output_tokens)
        # how long the check holds the chip after the window, by side
        report["seconds"] = {"program": round(served, 3), "reference": round(
            time.perf_counter() - t0 - served, 3)}
        return report

    def serve_prompts(self, prompts, output_tokens):
        """The program's side: the requests through a fresh Scheduler and
        each prompt's prefill logits; then the cache is deleted."""
        sched = self.scheduler()
        reqs = [sched.submit(p, max_new_tokens=output_tokens) for p in prompts]
        sched.drain()
        sched.close()
        got = [np.asarray(self.engine.prefill(0, 0, p)[1], np.float32)
               for p in prompts]
        for leaf in self.engine.cache.values():
            leaf.delete()
        return reqs, got

    def _reference(self, seq, pad):
        """(logits [T, V], selected [L-1, T, held], margin [L-1, T], the
        padded tokens) of the reference on ``self.params`` for ``seq``,
        one layer upcast at a time."""
        import jax.numpy as jnp
        p0 = _checks.row0(self.params)
        start, held = self.lm.held_start, self.lm.held_experts

        def leaves(i):
            if i == 0:
                return {k: v[0] for k, v in p0["first"].items()}
            return {k: v[0, i - 1] for k, v in p0["blocks"].items()}
        toks = np.zeros((pad,), np.int32)
        toks[:len(seq)] = seq
        want, sel, margin = reference.forward(
            self.cfg, leaves, {k: v[0] for k, v in p0["shared"].items()},
            jnp.asarray(toks), start)
        margin = np.asarray(margin)[:, :len(seq)]
        return (np.asarray(want)[:len(seq)],
                np.asarray(sel)[:, :len(seq), start:start + held], margin,
                toks)

    def compare(self, groups, output_tokens):
        """The reference's side.  ``groups``: per asked length its
        candidate prompts (longest first), their requests and their
        prefill logits."""
        import jax.numpy as jnp
        pad = -(-max(len(g[0]) + output_tokens for g, _, _ in groups)
                // 128) * 128
        rows, flips, pairs = [], 0, 0
        for cands, reqs, got in groups:
            whole = all(r.state == "done"
                        and len(r.generated) == output_tokens for r in reqs)
            want, _, margin, _ = self._reference(cands[0], pad)
            decided = margin.min(0) >= ROUTE_MARGIN
            scale = float(np.max(np.abs(want)))
            errs = [float(np.max(np.abs(mine - want[len(c) - 1])))
                    for c, mine in zip(cands, got) if decided[len(c) - 1]]
            row = {"prompt_tokens": len(cands[0]), "candidates": len(cands),
                   "prefills_compared": len(errs),
                   "prefill_logit_max_abs_err": max(errs, default=0.0),
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
                differ = (prog != sel).any(-1)                  # [L-1, T]
                flips += int(differ.sum())
                pairs += differ.size
                row.update({
                    "decode_of_prompt_tokens": len(cands[pick]),
                    "decode_positions_decided": len(gaps),
                    "decode_logit_gap_max": max(gaps, default=0.0),
                    "decode_scale": float(np.max(np.abs(want))),
                    "held_selection_flips_at_decided": int(
                        differ[:, decided].sum()),
                    # per margin: (token, layer) pairs at least that far
                    # from flipping, and the flips among them
                    "flips_by_margin": {
                        d: [int((margin >= d).sum()),
                            int(differ[margin >= d].sum())]
                        for d in reference.LADDER}})
            rows.append(row)

        def worst(key, scale):
            return max((r[key] / r[scale] for r in rows if key in r),
                       default=0.0)
        compared = {
            "prefill_logit_err_share": [
                worst("prefill_logit_max_abs_err", "prefill_scale"),
                self.tol],
            "decode_logit_gap_share": [
                worst("decode_logit_gap_max", "decode_scale"), self.tol],
            "prefill_lengths_not_compared": [
                sum(not r["prefills_compared"] for r in rows), 0],
            "decode_positions_short_of_floor": [
                max(0, self.decode_floor - sum(
                    r.get("decode_positions_decided", 0) for r in rows)), 0],
            "requests_off_length": [sum(r["off_length"] for r in rows), 0]}
        ok = all(value <= limit for value, limit in compared.values())
        # a diagnostic, with the limit it cannot pass: the share of (token,
        # layer) pairs at which the program's block functions, in the
        # program's precision but NOT through its timed programs
        # (program_selections), select another set of held experts than
        # the reference.  What holds the timed programs' routing to the
        # reference is the logits at the decided positions: one wrong
        # selection there moves them by twice the tolerance
        compared["route_flip_share"] = [flips / max(pairs, 1), 1.0]
        return {"ok": bool(ok), "tolerance": self.tol,
                "route_margin": ROUTE_MARGIN, "requests": rows,
                "compared": compared}


def build_serve(cfg, traffic, devices, seed):
    return Serve(cfg, traffic, devices, seed)
