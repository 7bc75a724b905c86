"""Family ``hybrid_moe``: a decoder whose layers attend in two ways (a
window or every earlier position), with grouped-query heads, QK-norm, the
norm on each sublayer's output, a leading dense layer and sigmoid-routed
expert layers, served as ONE chip's share of an expert-parallel deployment
through ServeEngine + Scheduler (bluefog_tpu.models.decoder.HybridConfig):
the router keeps its published width, the chip holds the experts and the
vocabulary slice the configuration file's ``deployment`` names, and what
the absent experts would add is left out in program and reference alike.

This file maps the source's key names onto HybridConfig, makes the weights
on the device from the seed leaf by leaf, holds the comparison with the
plain reference (perfbench/reference/hybrid_moe.py), the bytes a decode
call cannot avoid (``engine.decode_hbm_roofline_share.kv``) and the
operations a prompt needs (``engine.prefill_mxu_roofline_share``).
"""
import numpy as np

from perfbench.families import _checks, latent_moe
from perfbench.families.composed_lm import serve_config
from perfbench.reference import hybrid_moe as reference

# |program - reference| as a share of the largest reference logit, by the
# precision the traffic file states for the engine.  The cell serves in
# bf16 end to end (weights, activations, both kinds of cache; the router in
# f32).  Prefill logits never read the cache; what decode READS of it is
# held to the reference by the decode program's own logits
# (ServeEngine.decode_logits), number by number like a prefill's.  On the
# chip at the cell's size (my chip runs, PR 35; PERF.md section 6): prefill
# 0.0087-0.0127 over 31 seeds and 0.0928-0.1178 with the reference's
# weights through int8 and back; decode 0.0095-0.0119 over 16 seeds,
# 0.0956 with those weights, 0.227 with decode's ring write one entry off
# and 0.875 with rings that decode leaves unwritten.  0.03 is 2.4 times the
# largest sound reading of either and under a third of the smallest of
# those controls.  NOT separated: K and V of the cache through int8 and
# back read 0.0139-0.0141 on decode (a third over the same seed's sound
# reading, where seeds alone differ by a fifth): the int8 step adds about
# as much as the program's bf16 activations already put between it and the
# float32 reference, in quadrature, and a limit under it would refuse sound
# runs.  The CPU rehearsal states float32: the program then IS the
# reference's function, and every one of those, the int8 cache among them,
# fails by a factor of ten.
SERVE_LOGIT_TOL = {"bfloat16": 3e-2, "float32": 1e-3}
DECODE_LOGIT_TOL = {"bfloat16": 3e-2, "float32": 1e-3}
# A position is compared only where the REFERENCE's own router puts every
# held expert's selection, in every expert layer, at least this far from
# flipping (reference.held_margin; a share of the router logits' root mean
# square): nearer than that the function jumps, and the program's bf16
# activations land on either side (perfbench/families/latent_moe.py gives
# the readings this margin was set from; the router is the same function
# at n_group 1).  The check makes its own coverage by the reference alone:
# of each asked length it serves ``check.candidates`` prompts, the asked
# prompt and its prefixes one token shorter each, compares the prefill of
# EVERY one whose last position the reference decides, and follows the
# longest such through its decode; a length with no decided candidate, or
# fewer decided decode positions than ``check.decode_positions_floor`` over
# the lengths, is not correct.
ROUTE_MARGIN = 0.03

KINDS = {"sliding_attention": "window", "full_attention": "full"}
FFNS = {"dense": "dense", "sparse": "experts"}


def hybrid_config(cfg):
    from bluefog_tpu.models import decoder
    dep, L = cfg["deployment"], cfg["num_hidden_layers"]
    held = dep["held_experts"]
    if held[1] - held[0] != cfg["num_experts"]:
        raise ValueError("num_experts is the count HELD here and must "
                         "equal the deployment's held_experts range")
    for i in range(L):
        want = cfg["sliding_window"] \
            if cfg["layer_types"][i] == "sliding_attention" else 0
        if cfg["sliding_windows"][i] != want:
            raise ValueError(f"sliding_windows[{i}] is not layer_types' own")
    return decoder.HybridConfig(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        window=cfg["sliding_window"],
        plan=tuple((KINDS[cfg["layer_types"][i]],
                    FFNS[cfg["mlp_layer_types"][i]]) for i in range(L)),
        dense_ffn=cfg["intermediate_size"],
        expert_ffn=cfg["moe_intermediate_size"],
        num_experts=dep["router_outputs"], held_experts=held[1] - held[0],
        held_start=held[0], top_k=cfg["num_experts_per_tok"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        route_scale=cfg["routed_scaling_factor"],
        rope_base=float(cfg["rope_parameters"]["rope_theta"]),
        eps=cfg["rms_norm_eps"])


# --- what the roofline shares are made of: my own arithmetic from the
# --- file's keys, for the layers and the slice this chip holds

def _plan(cfg):
    L = cfg["num_hidden_layers"]
    return list(zip(cfg["layer_types"][:L], cfg["mlp_layer_types"][:L]))


def _attention_params(cfg):
    D, Dh = cfg["hidden_size"], cfg["head_dim"]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return D * H * Dh + 2 * D * Hkv * Dh + H * Dh * D + 2 * Dh + 2 * D


def weight_bytes(cfg, itemsize=2):
    """Bytes of the layers' and the head's weights a decode call reads
    whatever it routes: everything but the embedding table (a call reads
    one row a lane) and the routed experts (counted per expert that got a
    token, :func:`decode_floor_bytes`).  The router's weight is float32."""
    D = cfg["hidden_size"]
    dense = sum(f == "dense" for _, f in _plan(cfg))
    sparse = len(_plan(cfg)) - dense
    fixed = (len(_plan(cfg)) * _attention_params(cfg)
             + dense * 3 * D * cfg["intermediate_size"]
             + sparse * 3 * D * cfg["moe_intermediate_size"]   # shared expert
             + D * cfg["vocab_size"] + D) * itemsize
    return fixed + sparse * D * cfg["deployment"]["router_outputs"] * 4


def expert_bytes(cfg, itemsize=2):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * itemsize


def position_bytes(cfg, itemsize=2):
    """K and V of one cached position in one layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def layers_of(cfg, kind):
    return sum(k == kind for k, _ in _plan(cfg))


held_experts = latent_moe.held_experts


def expert_layers(cfg):
    """Layers with routed experts, of those held here."""
    return sum(f == "sparse" for _, f in _plan(cfg))


def decode_floor_bytes(cfg, calls, experts_hit, positions, positions_window,
                       itemsize=2):
    """The bytes ``calls`` decode calls cannot avoid: every weight byte of
    the layers and the head once a call, each held expert once per call
    and layer in which a token fell on it (``experts_hit``, summed over
    the calls), and of the cache the lanes' LIVE positions in every full
    layer (``positions``, summed) and at most a window of them in every
    window layer (``positions_window``).  A lower bound: a program that
    reads a slot's whole row, or an expert twice, reads more."""
    return (calls * weight_bytes(cfg, itemsize)
            + experts_hit * expert_bytes(cfg, itemsize)
            + position_bytes(cfg, itemsize) * (
                positions * layers_of(cfg, "full_attention")
                + positions_window * layers_of(cfg, "sliding_attention")))


def prefill_flops(cfg, tokens):
    """The operations a prompt of ``tokens`` REAL tokens needs through the
    layers held here (2 per multiply-add): every matmul of a token
    (attention's four, the dense FFN, the shared expert and the router,
    and of the routed experts the expected share that falls on the held
    ones: top_k x held / router outputs pairs a token), causal attention on
    the full layers (a query at t meets t + 1 keys) and the band on the
    window layers (min(t + 1, window) keys), scores and weighted sum; the
    head for the one position that is read out."""
    D, Dh = cfg["hidden_size"], cfg["head_dim"]
    H, W = cfg["num_attention_heads"], cfg["sliding_window"]
    dep = cfg["deployment"]
    held_pairs = (cfg["num_experts_per_tok"] * cfg["num_experts"]
                  / dep["router_outputs"])
    per_token = 0
    for _, f in _plan(cfg):
        per_token += _attention_params(cfg) - 2 * Dh - 2 * D
        if f == "dense":
            per_token += 3 * D * cfg["intermediate_size"]
        else:
            per_token += (D * dep["router_outputs"]
                          + (1 + held_pairs) * 3 * D
                          * cfg["moe_intermediate_size"])
    n = tokens
    causal = n * (n + 1) // 2
    band = causal if n <= W else W * (W + 1) // 2 + (n - W) * W
    keys_met = (layers_of(cfg, "full_attention") * causal
                + layers_of(cfg, "sliding_attention") * band)
    return (2 * per_token * n + 2 * 2 * H * Dh * keys_met
            + 2 * D * cfg["vocab_size"])


def aot_programs(cfg, traffic, devices):
    """The cell's decode and prefill programs compiled for ``devices``
    from shapes alone (perfbench/tools/rehearse_aot.py)."""
    from bluefog_tpu.models import decoder
    from bluefog_tpu.serve import kv_cache as kv
    lm, scfg = hybrid_config(cfg), serve_config(traffic)
    shapes = decoder.hybrid_param_shapes(lm)
    group = lambda leaf, leaves: {name: leaf(name, shape)
                                  for name, shape in leaves.items()}
    return latent_moe.share_programs(
        lm, scfg, devices,
        lambda leaf: {"layers": tuple(group(leaf, leaves)
                                      for leaves in shapes["layers"]),
                      "shared": group(leaf, shapes["shared"])},
        kv.HybridCacheConfig(
            full_layers=lm.layers_of("full"),
            window_layers=lm.layers_of("window"), slots=scfg.slots,
            max_len=scfg.max_len, window=lm.window, kv_heads=lm.kv_heads,
            head_dim=lm.head_dim, dtype=scfg.dtype))


def _init_params(hcfg, m, seed, dtype, std):
    """The hybrid tree, every leaf [n, ...] on the carving's mesh, replicas
    equal, one jitted call a leaf (the largest leaf's float32 draw is the
    only temporary alive): matrices normal(0, std) in ``dtype``, the router
    in float32, RMSNorm scales 1 + 0.1 normal."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from bluefog_tpu.models import decoder
    sharding = NamedSharding(m.mesh, m.spec)
    key = jax.random.key(seed)

    def group(gi, leaves):
        out = {}
        for li, (name, shape) in enumerate(leaves.items()):
            dt = jnp.float32 if name == "wr" else dtype
            scale = name.startswith("g")

            def make(k, shape=shape, dt=dt, scale=scale):
                z = jax.random.normal(k, shape, jnp.float32)
                z = 1.0 + 0.1 * z if scale else std * z
                return jnp.broadcast_to(z.astype(dt)[None], (m.size,) + shape)
            out[name] = jax.jit(make, out_shardings=sharding)(
                jax.random.fold_in(key, 100 * gi + li))
        return out
    shapes = decoder.hybrid_param_shapes(hcfg)
    return {"layers": tuple(group(i + 1, leaves)
                            for i, leaves in enumerate(shapes["layers"])),
            "shared": group(0, shapes["shared"])}


class Serve(latent_moe.Serve):
    """One replica of ServeEngine + Scheduler over the hybrid model.  How
    the check serves its prompts (``reference_check``: a fresh Scheduler,
    candidates by prefixes, the cache given back before the reference
    runs: one layer in float32 and a block of heads' scores have to fit
    beside the weights) is the latent family's, over another cache; the
    model, its reference and what is compared are this file's."""

    def __init__(self, cfg, traffic, devices, seed):
        from bluefog_tpu.parallel import compose
        from bluefog_tpu.serve import Scheduler, ServeEngine

        self.cfg = cfg
        scfg = serve_config(traffic)
        self.m = compose.compose_parallelism(len(devices), 1, 1, 1,
                                             devices=devices)
        self.lm = hybrid_config(cfg)
        self.params = _init_params(self.lm, self.m, seed, scfg.dtype,
                                   cfg["initializer_range"])
        self.engine = ServeEngine(self.m, self.lm, self.params, scfg)
        self._Scheduler = Scheduler
        self.vocab = cfg["vocab_size"]
        self.tol = SERVE_LOGIT_TOL[traffic["engine"]["dtype"]]
        self.decode_tol = DECODE_LOGIT_TOL[traffic["engine"]["dtype"]]
        self.candidates = traffic["check"]["candidates"]
        self.decode_floor = traffic["check"]["decode_positions_floor"]

    def serve_prompts(self, prompts, output_tokens):
        """The program's side: the requests through a fresh Scheduler,
        stepped here so that after every step each request's row of the
        decode program's logits can be kept (``{j: [vocab]}``: what
        ``generated[j]`` was chosen from, ``j >= 1``), and each prompt's
        prefill logits; then the cache is deleted.  Returns the requests
        and per request ``(prefill logits, decode logits)``."""
        sched = self.scheduler()
        reqs = [sched.submit(p, max_new_tokens=output_tokens) for p in prompts]
        decoded = [{} for _ in reqs]
        for _ in range(10_000):
            if sched.done:
                break
            before = [len(r.generated) for r in reqs]
            sched.step()
            slots, rows = self.engine.decode_logits(0)
            lane = {int(s): i for i, s in enumerate(slots)}
            rows = np.asarray(rows)                     # [steps, S, vocab]
            for r, n0, keep in zip(reqs, before, decoded):
                first = max(n0, 1)      # generated[0] is the prefill's
                for j in range(first, len(r.generated)):
                    keep[j] = rows[j - first, lane[r.slot]]
        sched.close()
        got = [np.asarray(self.engine.prefill(0, 0, p)[1], np.float32)
               for p in prompts]
        for leaf in self.engine.cache.values():
            leaf.delete()
        return reqs, list(zip(got, decoded))

    def _reference(self, seq, pad):
        """(logits [T, V], margin [expert layers, T]) of the reference on
        ``self.params`` for ``seq``, one layer upcast at a time."""
        import jax.numpy as jnp
        p0 = _checks.row0(self.params)
        toks = np.zeros((pad,), np.int32)
        toks[:len(seq)] = seq
        want, _, margin = reference.forward(
            self.cfg, lambda i: {k: v[0] for k, v in p0["layers"][i].items()},
            {k: v[0] for k, v in p0["shared"].items()}, jnp.asarray(toks),
            self.lm.held_start)
        return np.asarray(want)[:len(seq)], np.asarray(margin)[:, :len(seq)]

    def compare(self, groups, output_tokens):
        """The reference's side.  ``groups``: per asked length its
        candidate prompts (longest first), their requests and per request
        its prefill logits and its decode logits."""
        rows = []
        for cands, reqs, got in groups:
            # a length of its own per asked prompt: the short one's two
            # passes cost a twentieth of the long one's
            pad = -(-(len(cands[0]) + output_tokens) // 128) * 128
            whole = all(r.state == "done"
                        and len(r.generated) == output_tokens for r in reqs)
            want, margin = self._reference(cands[0], pad)
            decided = margin.min(0) >= ROUTE_MARGIN
            scale = float(np.max(np.abs(want)))
            errs = [float(np.max(np.abs(mine - want[len(c) - 1])))
                    for c, (mine, _) in zip(cands, got)
                    if decided[len(c) - 1]]
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
                # j = 1 on by the decode program, out of the logits it
                # handed out
                gen, mine = reqs[pick].generated, got[pick][1]
                seq = cands[pick] + [int(t) for t in gen]
                want, margin = self._reference(seq, pad)
                decided = margin.min(0) >= ROUTE_MARGIN
                last = len(cands[pick]) - 1
                at = [j for j in range(1, len(gen)) if decided[last + j]]
                gaps = [float(want[last + j].max() - want[last + j, gen[j]])
                        for j in at]
                errs = [float(np.max(np.abs(mine[j] - want[last + j])))
                        for j in at]
                row.update({
                    "decode_of_prompt_tokens": len(cands[pick]),
                    "decode_positions_decided": len(at),
                    "decode_logit_gap_max": max(gaps, default=0.0),
                    "decode_logit_max_abs_err": max(errs, default=0.0),
                    "decode_logit_abs_err_p50": float(np.median(errs))
                    if errs else 0.0,
                    "decode_scale": float(np.max(np.abs(want)))})
            rows.append(row)

        def worst(key, scale):
            return max((r[key] / r[scale] for r in rows if key in r),
                       default=0.0)
        compared = {
            "prefill_logit_err_share": [
                worst("prefill_logit_max_abs_err", "prefill_scale"),
                self.tol],
            "decode_logit_err_share": [
                worst("decode_logit_max_abs_err", "decode_scale"),
                self.decode_tol],
            "decode_logit_gap_share": [
                worst("decode_logit_gap_max", "decode_scale"), self.tol],
            "prefill_lengths_not_compared": [
                sum(not r["prefills_compared"] for r in rows), 0],
            "decode_positions_short_of_floor": [
                max(0, self.decode_floor - sum(
                    r.get("decode_positions_decided", 0) for r in rows)), 0],
            "requests_off_length": [sum(r["off_length"] for r in rows), 0]}
        ok = all(value <= limit for value, limit in compared.values())
        return {"ok": bool(ok), "tolerance": self.tol,
                "decode_tolerance": self.decode_tol,

                "route_margin": ROUTE_MARGIN, "requests": rows,
                "compared": compared}


def build_serve(cfg, traffic, devices, seed):
    return Serve(cfg, traffic, devices, seed)
