"""Open-loop chat traffic with bursts: `open_loop_chat`'s users (one shared
system prompt, a user part and an answer length from clipped lognormals),
but a share of the requests arrives clumped, as when a page loads for many
users at once or a job fans out.

A phase (ramp, window, tail) holds round(rate x its seconds) requests.
`burst_share` of them arrive in bursts: one burst every `burst_every_s`
seconds, the first `burst_first_s` into the phase, the bursts' requests
dealt to them as evenly as whole requests allow (the earlier bursts take the
remainder) and each burst's due evenly over `burst_within_s` seconds from
its start. The others arrive with `open_loop_chat`'s exponential-quantile
gaps over the whole phase. As there, the schedule is the mix's and not the
seed's: lengths, gaps and which request falls into a burst are fixed by the
traffic file and its `order_seed`; `--seed` makes the tokens (and, in the
runner, the weights). Another order is another traffic file.
"""

import numpy as np

from .open_loop_chat import (
    SYSTEM_SEED, _exponential_gaps, _lognormal_quantiles)


def burst_starts(params, seconds):
    """The bursts' start times inside a phase of `seconds`, from its start."""
    first, every = params["burst_first_s"], params["burst_every_s"]
    return [first + k * every
            for k in range(int(max(0.0, seconds - first) // every) + 1)
            if first + k * every < seconds]


def arrivals(params, seconds, order):
    """(due times from the phase's start, sorted; which of them are a
    burst's) for one phase; `order` shuffles the steady gaps."""
    n = int(round(params["rate_per_s"] * seconds))
    starts = burst_starts(params, seconds)
    n_burst = int(round(params["burst_share"] * n)) if starts else 0
    steady = n - n_burst
    due, in_burst = [], []
    if steady:
        gaps = _exponential_gaps(steady, seconds)
        order.shuffle(gaps)
        # each request at the middle of its gap: inside [0, seconds)
        due += list(np.cumsum(gaps) - gaps / 2.0)
        in_burst += [False] * steady
    for k, start in enumerate(starts):
        m = n_burst // len(starts) + (k < n_burst % len(starts))
        due += [start + j * params["burst_within_s"] / m for j in range(m)]
        in_burst += [True] * m
    by_time = np.argsort(np.asarray(due), kind="stable")
    return [float(due[i]) for i in by_time], [in_burst[i] for i in by_time]


def schedule(params, seed, phases, vocab):
    """[{due_s, prompt, max_new_tokens, phase, burst}] sorted by due time.
    `phases` is [(name, seconds)] laid end to end from 0."""
    rng = np.random.default_rng(seed)
    order = np.random.default_rng(params["order_seed"])
    system = [int(v) for v in np.random.default_rng(SYSTEM_SEED)
              .integers(2, vocab, params["system_tokens"])]
    u, o = params["user_tokens"], params["output_tokens"]
    out, t0 = [], 0.0
    for name, seconds in phases:
        due, in_burst = arrivals(params, seconds, order)
        n = len(due)
        if n:
            users = _lognormal_quantiles(n, u["median"], u["sigma"], u["min"], u["max"])
            outs = _lognormal_quantiles(n, o["median"], o["sigma"], o["min"], o["max"])
            order.shuffle(users)
            order.shuffle(outs)
            for d, b, nu, no in zip(due, in_burst, users, outs):
                out.append({
                    "due_s": t0 + d,
                    "prompt": system + [int(v) for v in rng.integers(2, vocab, nu)],
                    "max_new_tokens": int(no),
                    "phase": name,
                    "burst": bool(b),
                })
        t0 += seconds
    out.sort(key=lambda r: r["due_s"])
    return out
