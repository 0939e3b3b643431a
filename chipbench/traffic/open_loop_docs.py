"""Open-loop questions over a handful of long documents: a team asking
about contracts, code files or papers it has loaded once. Every request is
one of `documents.count` fixed documents followed by a short question, so
with the documents in the prefix cache (the runner prefills each once in
set-up) a request prefills its question alone and then decodes over the
document's 4k-12k cached positions.

open_loop_chat's interface and its rule: the schedule is the mix's, not the
seed's. A phase holds round(rate x its seconds) requests; question and
answer lengths are the lognormals' quantiles and the gaps the exponential's,
each list shuffled by the mix's own `order_seed`; request j (counted across
the phases) asks about document perm[j % count], perm drawn once from
`order_seed`: each document by every count-th request. `--seed` makes the
tokens, the documents' and the questions'.
"""

import numpy as np

from .open_loop_chat import _exponential_gaps, _lognormal_quantiles


def document_lengths(params):
    d = params["documents"]
    return [d["first_tokens"] + d["step_tokens"] * i for i in range(d["count"])]


def documents(params, seed, vocab):
    """The documents' tokens, from the seed alone: the runner prefills these
    in set-up and schedule() puts them in front of the questions."""
    rng = np.random.default_rng([int(seed), 0xD0C5])
    return [[int(v) for v in rng.integers(2, vocab, n)]
            for n in document_lengths(params)]


def schedule(params, seed, phases, vocab):
    """[{due_s, prompt, max_new_tokens, phase, document}] sorted by due
    time. `phases` is [(name, seconds)] laid end to end from 0."""
    rng = np.random.default_rng(seed)
    order = np.random.default_rng(params["order_seed"])
    docs = documents(params, seed, vocab)
    perm = order.permutation(len(docs))
    q, o = params["question_tokens"], params["output_tokens"]
    out, t0, j = [], 0.0, 0
    for name, seconds in phases:
        n = int(round(params["rate_per_s"] * seconds))
        if n:
            asks = _lognormal_quantiles(n, q["median"], q["sigma"], q["min"], q["max"])
            outs = _lognormal_quantiles(n, o["median"], o["sigma"], o["min"], o["max"])
            gaps = _exponential_gaps(n, seconds)
            order.shuffle(asks)
            order.shuffle(outs)
            order.shuffle(gaps)
            # each request at the middle of its gap, so every due time
            # lies inside [t0, t0 + seconds)
            due = t0 + np.cumsum(gaps) - gaps / 2.0
            for d, nq, no in zip(due, asks, outs):
                doc = int(perm[j % len(docs)])
                j += 1
                out.append({
                    "due_s": float(d),
                    "prompt": docs[doc] + [int(v) for v in rng.integers(2, vocab, nq)],
                    "max_new_tokens": int(no),
                    "phase": name,
                    "document": doc,
                })
        t0 += seconds
    out.sort(key=lambda r: r["due_s"])
    return out
