"""The encoder-decoder Transformer train step, built from a configuration
file's sizes through models/transformer.py, with the count of the operations
a step requires. The benchmark's own copy of what bench.build_transformer
does (PERF.md, Open questions: the original is for a later PR to delete)."""

import numpy as np

FEEDS = (
    ("src_word", "int64"), ("src_pos", "int64"), ("trg_word", "int64"),
    ("trg_pos", "int64"), ("label", "int64"), ("label_weight", "float32"),
)


def build(config, t):
    """(main, startup, loss)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu import framework
    from paddle_tpu.models import transformer as T

    c = config["sizes"]
    main, startup = framework.Program(), framework.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        feeds = {}
        for name, dtype in FEEDS:
            shape = [t, 1] if name == "label_weight" else [t]
            feeds[name] = fluid.layers.data(name=name, shape=shape, dtype=dtype)
        loss, _ = T.transformer(
            feeds["src_word"], feeds["src_pos"], feeds["trg_word"],
            feeds["trg_pos"], None, None, None,
            feeds["label"], feeds["label_weight"],
            src_vocab_size=c["vocab_size"], trg_vocab_size=c["vocab_size"],
            n_layer=c["n_layer"], n_head=c["n_head"], d_model=c["d_model"],
            d_inner=c["d_inner"], d_key=c["d_key"], d_value=c["d_value"],
            dropout=0.0, max_length=t + 1,
            label_smooth_eps=c["label_smoothing"],
            use_flash=True, padded=False,
        )
        opt = config["optimizer"]
        fluid.optimizer.Adam(
            learning_rate=opt["learning_rate"],
            moment_dtype=opt["moment_dtype"],
        ).minimize(loss)
    return main, startup, loss


def batches(config, b, t, seed, n):
    """`n` distinct host batches of b sequences of t packed positions, from
    the seed."""
    vocab = config["sizes"]["vocab_size"]
    rng = np.random.default_rng(seed)
    pos = np.tile(np.arange(t), (b, 1)).astype("int64")
    out = []
    for _ in range(n):
        out.append({
            "src_word": rng.integers(0, vocab, (b, t)).astype("int64"),
            "src_pos": pos,
            "trg_word": rng.integers(0, vocab, (b, t)).astype("int64"),
            "trg_pos": pos,
            "label": rng.integers(0, vocab, (b, t)).astype("int64"),
            "label_weight": np.ones((b, t, 1), "float32"),
        })
    return out


def tokens_per_step(b, t):
    """Source plus target positions."""
    return 2 * b * t


def flops_per_step(config, b, t):
    """Operations the forward and backward passes require: 2 per
    multiply-add over the matrix multiplications and the attention products,
    backward counted as twice forward. Embedding gathers, softmax, norms and
    the optimizer are not counted. bench.build_transformer's count, except
    that the causal block counts the half of its products the mask leaves
    (the repository's count takes it in full)."""
    c = config["sizes"]
    d, di, n, v = c["d_model"], c["d_inner"], c["n_layer"], c["vocab_size"]
    enc_mm = n * (4 * d * d + 2 * d * di)
    dec_mm = n * (8 * d * d + 2 * d * di)
    mm = 2 * b * t * (enc_mm + dec_mm) + 2 * b * t * d * v
    return 3 * (mm + attention_flops_forward(config, b, t))


def attention_flops_forward(config, b, t):
    """QK^T and PV of every attention block, forward: 4*b*t*t*(heads*d_head)
    for a full block. A layer pair has two full blocks (encoder self, cross)
    and one causal block (decoder self), which needs half."""
    c = config["sizes"]
    full = 4 * b * t * t * c["n_head"] * c["d_key"]
    return int(full * 2.5 * c["n_layer"])


def attention_flops_step(config, b, t):
    """Forward plus backward of the attention products, backward as twice
    forward: what the mathematics needs. The flash backward also recomputes
    QK^T; recomputed operations do not count."""
    return 3 * attention_flops_forward(config, b, t)
