"""The plain reference of the decoder-only configuration: the whole sequence
through GPTDecoder's block in straightforward float32 jax.numpy, no cache, no
kernel, no batching, matrix products at the highest precision. It reads the
weights it is given by the names GPTDecoder.param_names() defines, so the
served engine's own arrays are the only copy on the chip.

The block, as models/gpt_decoder.py builds it (departures from GPT-2 are in
the configuration file): token + learned position embedding; per layer
pre-LN attention without biases (q, k, v, o) under a causal mask, residual,
pre-LN MLP with relu and biases, residual; final LN; untied head, no bias.
"""


import functools


def _ln(x, w, b, eps=1e-5):
    import jax.numpy as jnp

    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _forward_row(weights, tokens, row, prefix, n_layer, n_head):
    import jax
    import jax.numpy as jnp

    p = lambda *parts: weights["_".join((prefix,) + parts)].astype(jnp.float32)
    t = tokens.shape[0]
    with jax.default_matmul_precision("highest"):
        x = p("tok_emb")[tokens] + p("pos_emb")[:t]
        d_head = x.shape[-1] // n_head
        mask = jnp.tril(jnp.ones((t, t), bool))
        for i in range(n_layer):
            li = "l%d" % i
            h = _ln(x, p(li, "ln1_w"), p(li, "ln1_b"))
            heads = lambda y: y.reshape(t, n_head, d_head).transpose(1, 0, 2)
            q, k, v = (heads(h @ p(li, s)) for s in ("q_w", "k_w", "v_w"))
            scores = jnp.einsum("hqd,hkd->hqk", q, k) * d_head ** -0.5
            scores = jnp.where(mask, scores, -jnp.inf)
            ctx = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(scores, -1), v)
            x = x + ctx.transpose(1, 0, 2).reshape(t, -1) @ p(li, "o_w")
            h = _ln(x, p(li, "ln2_w"), p(li, "ln2_b"))
            f = jax.nn.relu(h @ p(li, "ff1_w") + p(li, "ff1_b"))
            x = x + f @ p(li, "ff2_w") + p(li, "ff2_b")
        h = _ln(x[row], p("lnf_w"), p("lnf_b"))
        return h @ p("head_w")


def logits_after(weights, prefix, n_layer, n_head, tokens, lengths):
    """[logits [vocab] after the first n of `tokens`, for n in lengths],
    float32. The whole of `tokens` goes through once a length, under the
    causal mask, and row n - 1 is read: one compiled program for every
    length, since a row does not see the rows after it."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(functools.partial(
        _forward_row, prefix=prefix, n_layer=n_layer, n_head=n_head))
    tokens = jnp.asarray(tokens, jnp.int32)
    return [fn(weights, tokens, jnp.int32(n - 1)) for n in lengths]
