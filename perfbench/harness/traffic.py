"""The one general generator of serving traffic.  A traffic mix is a data
file of parameters; this file turns it and ``--seed`` into requests.

    "loop": "closed" with "clients": N      each client submits its next
                                            request when its last retires
    "loop": "open" with "rate_per_s": R     Poisson arrivals at a fixed rate,
                                            timed from when each was due
    "prompt_tokens" / "output_tokens"       a distribution (below)
    "ramp_output_tokens"                    closed loop: each client's FIRST
                                            request, so that clients fall out
                                            of step before the window opens
    "shared_prefix_tokens": P               the first P tokens of every prompt
                                            are one seeded system prompt
    "cycle": C                              lengths are drawn in cycles of C
    "length_order": {"seed": S}             the ORDER of the lengths comes from
                                            S, not from --seed (tokens still
                                            do): every run then retires its
                                            requests at the same steps

Distributions: {"dist": "constant", "value": v}, {"dist": "uniform", "lo",
"hi"}, {"dist": "loguniform", "lo", "hi"} (inclusive integers).  Lengths
are STRATIFIED: each cycle of C requests holds the distribution's C evenly
spaced quantiles, in an order shuffled by the seed.  Every run, whatever its
seed, so serves the same amount of work per cycle; only the order and the
tokens differ.
"""
import math

import numpy as np


def quantile(dist, q):
    kind = dist["dist"]
    if kind not in ("constant", "uniform", "loguniform"):
        raise ValueError(f"unknown distribution {kind!r}")
    if kind == "constant":
        return int(dist["value"])
    lo, hi = dist["lo"], dist["hi"]
    if kind == "uniform":
        return int(min(hi, math.floor(lo + q * (hi - lo + 1))))
    return int(min(hi, math.floor(
        math.exp(math.log(lo) + q * (math.log(hi + 1) - math.log(lo))))))


class Lengths:
    """An endless stratified stream of one distribution's draws."""

    def __init__(self, dist, cycle, rng):
        self.values = [quantile(dist, (i + 0.5) / cycle) for i in range(cycle)]
        self.rng, self.order, self.pos = rng, None, cycle
        self.cycle = cycle

    def next(self):
        if self.pos >= self.cycle:
            self.order, self.pos = self.rng.permutation(self.cycle), 0
        v = self.values[self.order[self.pos]]
        self.pos += 1
        return v


class Requests:
    """(prompt tokens, output tokens) pairs from a traffic file and a seed."""

    def __init__(self, traffic, vocab, seed):
        self.rng = np.random.default_rng(seed)
        fixed = traffic.get("length_order")
        order = np.random.default_rng(fixed["seed"]) if fixed else self.rng
        cycle = traffic.get("cycle", 64)
        self.vocab = vocab
        self.prompts = Lengths(traffic["prompt_tokens"], cycle, order)
        self.outputs = Lengths(traffic["output_tokens"], cycle, order)
        ramp = traffic.get("ramp_output_tokens")
        self.ramp = Lengths(ramp, traffic.get("clients", cycle), order) \
            if ramp else None
        self.prefix = self.rng.integers(
            0, vocab, traffic.get("shared_prefix_tokens", 0)).tolist()

    def next(self, ramp=False):
        n = self.prompts.next()
        body = self.rng.integers(0, self.vocab,
                                 max(1, n - len(self.prefix))).tolist()
        out = self.ramp.next() if (ramp and self.ramp) else self.outputs.next()
        return self.prefix + body, out

    def arrivals(self, rate_per_s, horizon_s):
        """Poisson arrival offsets (seconds) up to ``horizon_s``."""
        t, out = 0.0, []
        while True:
            t += self.rng.exponential(1.0 / rate_per_s)
            if t >= horizon_s:
                return out
            out.append(t)
