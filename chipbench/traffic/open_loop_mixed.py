"""Open-loop short chats and questions about long documents in one queue:
an assistant whose users both chat and ask about contracts, code files or
reports they have loaded once. Half the requests (the first ceil(n / 2) of
a phase's n, before the shuffle) are questions: one of `documents.count`
fixed documents followed by a short question, so that with the documents
in the prefix cache (the runner prefills each once in set-up) a question
prefills itself alone and decodes over its document's cached positions.
The other half are chats: open_loop_chat's shared system prompt and a user
part.

open_loop_chat's interface and its rule: the schedule is the mix's, not the
seed's. A phase holds round(rate x its seconds) requests; the kinds, each
kind's prompt and answer lengths (the lognormals' quantiles) and the gaps
(the exponential's) are each shuffled by the mix's own `order_seed`; the
j-th question (counted across the phases) asks about document perm[j %
count], perm drawn once from `order_seed`: each document by every count-th
question. `--seed` makes the tokens, the documents' and the prompts'.
"""

import numpy as np

from .open_loop_chat import SYSTEM_SEED, _exponential_gaps, _lognormal_quantiles
from .open_loop_docs import document_lengths, documents  # noqa: F401


def _lengths(n, spec, order):
    out = _lognormal_quantiles(n, spec["median"], spec["sigma"], spec["min"],
                               spec["max"])
    order.shuffle(out)
    return out


def schedule(params, seed, phases, vocab):
    """[{due_s, prompt, max_new_tokens, phase, document}] sorted by due
    time, `document` None for a chat. `phases` is [(name, seconds)] laid end
    to end from 0."""
    rng = np.random.default_rng(seed)
    order = np.random.default_rng(params["order_seed"])
    docs = documents(params, seed, vocab)
    perm = order.permutation(len(docs))
    system = [int(v) for v in np.random.default_rng(SYSTEM_SEED)
              .integers(2, vocab, params["system_tokens"])]
    out, t0, j = [], 0.0, 0
    for name, seconds in phases:
        n = int(round(params["rate_per_s"] * seconds))
        if n:
            n_docs = (n + 1) // 2
            kinds = [True] * n_docs + [False] * (n - n_docs)
            order.shuffle(kinds)
            asks = iter(_lengths(n_docs, params["question_tokens"], order))
            doc_outs = iter(_lengths(n_docs, params["doc_output_tokens"], order))
            users = iter(_lengths(n - n_docs, params["user_tokens"], order))
            chat_outs = iter(_lengths(n - n_docs, params["chat_output_tokens"],
                                      order))
            gaps = _exponential_gaps(n, seconds)
            order.shuffle(gaps)
            # each request at the middle of its gap, so every due time
            # lies inside [t0, t0 + seconds)
            due = t0 + np.cumsum(gaps) - gaps / 2.0
            for d, is_doc in zip(due, kinds):
                if is_doc:
                    doc = int(perm[j % len(docs)])
                    j += 1
                    prompt = docs[doc] + [
                        int(v) for v in rng.integers(2, vocab, next(asks))]
                    n_out = next(doc_outs)
                else:
                    doc = None
                    prompt = system + [
                        int(v) for v in rng.integers(2, vocab, next(users))]
                    n_out = next(chat_outs)
                out.append({"due_s": float(d), "prompt": prompt,
                            "max_new_tokens": int(n_out), "phase": name,
                            "document": doc})
        t0 += seconds
    out.sort(key=lambda r: r["due_s"])
    return out
