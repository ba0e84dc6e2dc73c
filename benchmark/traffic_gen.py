"""The one general traffic generator: reads a mix's data file, draws
lengths and token ids from the seed.

Every seed gets the SAME multiset of sizes in another order, so that two
seeds differ in arrangement and not in the amount of work: a length
distribution is turned into ``pool`` values at evenly spaced quantiles
(so the pool is the distribution, not a sample of it), and a run walks
the pool cycle after cycle, each cycle in an order drawn from the seed.

A length spec is ``{"dist": "lognormal", "median": m, "sigma": s,
"min": a, "max": b}`` or ``{"dist": "fixed", "value": v}``.
"""
from __future__ import annotations

import math
import statistics

import numpy as np


def quantile_values(spec, n):
    """``n`` whole lengths at the quantiles (i + 1/2) / n of ``spec``."""
    if spec["dist"] == "fixed":
        return [int(spec["value"])] * n
    if spec["dist"] != "lognormal":
        raise ValueError("unknown length distribution %r" % (spec["dist"],))
    normal = statistics.NormalDist()
    mu = math.log(spec["median"])
    out = []
    for i in range(n):
        z = normal.inv_cdf((i + 0.5) / n)
        value = int(round(math.exp(mu + spec["sigma"] * z)))
        out.append(min(max(value, int(spec["min"])), int(spec["max"])))
    return out


def length_pool(mix):
    """[(prompt tokens, output tokens)] of one cycle. Prompt and output
    lengths are paired by a permutation fixed in the mix (``pair_seed``),
    not by the run's seed: the pool is part of the mix."""
    n = int(mix["pool"])
    prompts = quantile_values(mix["prompt_tokens"], n)
    outputs = quantile_values(mix["output_tokens"], n)
    order = np.random.default_rng(int(mix["pair_seed"])).permutation(n)
    return [(prompts[i], outputs[int(j)]) for i, j in enumerate(order)]


def summary(values):
    ordered = sorted(values)

    def at(q):
        return ordered[min(int(q * len(ordered)), len(ordered) - 1)]

    return {"n": len(ordered), "min": ordered[0], "p50": at(0.5),
            "p90": at(0.9), "max": ordered[-1],
            "mean": round(sum(ordered) / len(ordered), 1)}


class RequestStream:
    """Endless seeded stream of (prompt token ids, output tokens)."""

    def __init__(self, mix, vocab_size, seed):
        self.pool = length_pool(mix)
        self.vocab_size = int(vocab_size)
        self.rng = np.random.default_rng(int(seed))
        self._cycle = []

    def fractions(self, n):
        """``n`` evenly spaced fractions in (0, 1), in seeded order."""
        values = (np.arange(n) + 0.5) / n
        return [float(v) for v in self.rng.permutation(values)]

    def tokens(self, n):
        return self.rng.integers(0, self.vocab_size, int(n)).tolist()

    def next(self):
        if not self._cycle:
            self._cycle = [self.pool[int(i)] for i in
                           self.rng.permutation(len(self.pool))]
        prompt_len, out_len = self._cycle.pop()
        return self.tokens(prompt_len), out_len
