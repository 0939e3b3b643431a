"""Open-loop chat traffic: independent users, one shared system prompt, a
user part and an answer length from clipped lognormals, Poisson-like arrivals
at a fixed rate.

The schedule is the mix's, not the seed's. A phase (ramp, window, tail) holds
round(rate x its seconds) requests; their user and output lengths are the
lognormal's quantiles at (i + 0.5) / n and their gaps the exponential's
quantiles scaled to fill the phase exactly, each list shuffled by the mix's
own `order_seed`. `--seed` makes the tokens (and, in the runner, the
weights). Shuffling by the seed was tried first (PERF.md, PR 24): with ~100
requests a window, which long prompt meets which long answer moved the
latency percentiles by 9-15 % between seeds, against 1-3 % between two runs
of one seed, so a seed compared orders, not code. Another order is another
traffic file.
"""

import math
from statistics import NormalDist

import numpy as np

SYSTEM_SEED = 7  # the shared system prompt's tokens: the same in every run


def _lognormal_quantiles(n, median, sigma, lo, hi):
    nd = NormalDist()
    q = [median * math.exp(sigma * nd.inv_cdf((i + 0.5) / n)) for i in range(n)]
    return [int(min(max(round(v), lo), hi)) for v in q]


def _exponential_gaps(n, seconds):
    g = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    return g * (seconds / g.sum())


def schedule(params, seed, phases, vocab):
    """[{due_s, prompt, max_new_tokens, phase}] sorted by due time.
    `phases` is [(name, seconds)] laid end to end from 0."""
    rng = np.random.default_rng(seed)
    order = np.random.default_rng(params["order_seed"])
    system = [int(v) for v in np.random.default_rng(SYSTEM_SEED)
              .integers(2, vocab, params["system_tokens"])]
    u, o = params["user_tokens"], params["output_tokens"]
    out, t0 = [], 0.0
    for name, seconds in phases:
        n = int(round(params["rate_per_s"] * seconds))
        if n:
            users = _lognormal_quantiles(n, u["median"], u["sigma"], u["min"], u["max"])
            outs = _lognormal_quantiles(n, o["median"], o["sigma"], o["min"], o["max"])
            gaps = _exponential_gaps(n, seconds)
            order.shuffle(users)
            order.shuffle(outs)
            order.shuffle(gaps)
            # each request at the middle of its gap, so every due time
            # lies inside [t0, t0 + seconds)
            due = t0 + np.cumsum(gaps) - gaps / 2.0
            for d, nu, no in zip(due, users, outs):
                out.append({
                    "due_s": float(d),
                    "prompt": system + [int(v) for v in rng.integers(2, vocab, nu)],
                    "max_new_tokens": int(no),
                    "phase": name,
                })
        t0 += seconds
    out.sort(key=lambda r: r["due_s"])
    return out
