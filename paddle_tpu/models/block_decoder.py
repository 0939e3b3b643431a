"""A decoder assembled from a per-layer block specification (ROADMAP D4):
operator kind x feed-forward kind, under one norm and one position scheme,
so that a configuration is data. It implements the GenerationEngine's model
protocol (``build_prefill`` / ``build_decode`` / ``build_forward`` /
``cache_specs`` / ``ensure_params``) over one shared parameter set.

A layer is ``h = x + OP(norm(x)); x' = h + FF(norm(h))`` with

  * OP ``"attention"``: grouped-query attention, per-head RMS norm of q and
    k before rotary positions (half-rotation form), causal, through
    ``kv_cache_write`` / ``paged_attention`` over pools whose rows are
    ``n_kv_head * d_head`` wide;
  * OP ``"conv"``: the gated short convolution (ops/decoder_ops.py
    ``short_conv``), whose per-sequence state — ``conv_taps - 1`` rows of
    ``d_model`` — lives in a per-slot state pool beside the pages;
  * FF ``"dense"``: SwiGLU of width ``d_ffn``;
  * FF ``"moe"``: sigmoid top-k router over ``num_experts`` and the held
    experts' gated feed-forwards of width ``moe_width`` (``moe_router`` /
    ``moe_experts``; ``experts_held`` is the chip's share, default all).

The head is tied to the token embedding; there is no position embedding.
Parameters, activations and caches are in ``dtype`` (bfloat16 as published;
float32 for tight tests); norm scales, the router's matrix and bias and the
conv taps are float32, and the norms, router, softmax and the conv's
accumulation compute in float32 whatever ``dtype`` is. Logits leave the
last product in float32.

``lfm2_moe(config)`` turns an ``lfm2_moe`` ``config.json`` into such a
decoder: the architecture is data for this module.
"""

import numpy as np

from .. import framework, unique_name
from .. import layers
from ..executor import Executor
from ..initializer import Normal
from ..layer_helper import LayerHelper
from ..param_attr import ParamAttr

__all__ = ["BlockDecoder", "lfm2_moe"]

OPERATORS = ("attention", "conv")
FEED_FORWARDS = ("dense", "moe")


class BlockDecoder:
    def __init__(self, vocab_size, d_model, blocks, n_head, n_kv_head, d_head,
                 d_ffn, moe=None, conv_taps=3, norm_eps=1e-5, rope_theta=1e6,
                 max_context=2048, eos_id=-1, prefix="bd", dtype="bfloat16",
                 init_std=0.02):
        """``blocks`` is ``[(operator, feed_forward)]``, a layer each.
        ``moe`` is ``{num_experts, k, width, use_bias, norm_topk, scale,
        experts_held}`` (the last optional)."""
        self.blocks = [(str(o), str(f)) for o, f in blocks]
        for o, f in self.blocks:
            if o not in OPERATORS or f not in FEED_FORWARDS:
                raise ValueError("unknown block (%r, %r)" % (o, f))
        if n_head % n_kv_head:
            raise ValueError("n_head must be a multiple of n_kv_head")
        if dtype not in ("bfloat16", "float32"):
            raise ValueError("dtype must be 'bfloat16' or 'float32'")
        self.vocab_size = int(vocab_size)
        self.d_model = int(d_model)
        self.n_layer = len(self.blocks)
        self.n_head, self.n_kv_head = int(n_head), int(n_kv_head)
        self.d_head = int(d_head)
        self.d_ffn = int(d_ffn)
        self.moe = dict(moe or {})
        if any(f == "moe" for _, f in self.blocks) and not self.moe:
            raise ValueError("a moe block needs the moe settings")
        self.conv_taps = int(conv_taps)
        self.norm_eps = float(norm_eps)
        self.rope_theta = float(rope_theta)
        self.max_context = int(max_context)
        self.eos_id = int(eos_id)
        self.prefix = prefix
        self.dtype = self.kv_dtype = dtype
        self.init_std = float(init_std)

    # ---------------------------------------------------------------- names

    def _p(self, *parts):
        return "_".join((self.prefix,) + parts)

    def spec(self):
        """The block specification as plain data: what the compile cache's
        key folds in beside the program (GenerationEngine.geometry)."""
        return {
            "blocks": [list(b) for b in self.blocks], "dtype": self.dtype,
            "n_head": self.n_head, "n_kv_head": self.n_kv_head,
            "d_head": self.d_head, "d_model": self.d_model,
            "d_ffn": self.d_ffn, "conv_taps": self.conv_taps,
            "moe": {k: self.moe[k] for k in sorted(self.moe)},
        }

    def moe_layers(self):
        return [i for i, (_, f) in enumerate(self.blocks) if f == "moe"]

    def param_names(self):
        names = [self._p("tok_emb")]
        for i, (op, ff) in enumerate(self.blocks):
            parts = ["op_norm_w"]
            if op == "conv":
                parts += ["conv_in_w", "conv_k", "conv_out_w"]
            else:
                parts += ["q_w", "k_w", "v_w", "q_norm_w", "k_norm_w", "o_w"]
            parts.append("ffn_norm_w")
            if ff == "dense":
                parts += ["ff1_w", "ff3_w", "ff2_w"]
            else:
                parts += ["router_w"]
                if self.moe.get("use_bias", True):
                    parts.append("router_b")
                parts += ["exp_w1", "exp_w3", "exp_w2"]
            names += [self._p("l%d" % i, s) for s in parts]
        names.append(self._p("final_norm_w"))
        return names

    def cache_specs(self):
        """One entry a cache tensor, in layer order: ``kind`` "paged" (a
        ``[pool_rows, width]`` pool indexed through block tables) or "state"
        (a ``[max_slots + 1, width]`` pool of fixed per-slot rows, row 0
        scratch)."""
        specs = []
        for i, (op, _) in enumerate(self.blocks):
            li = "l%d" % i
            if op == "attention":
                for s in ("kv_k", "kv_v"):
                    specs.append({
                        "name": self._p(li, s), "kind": "paged", "layer": i,
                        "width": self.n_kv_head * self.d_head,
                        "dtype": self.dtype})
            else:
                specs.append({
                    "name": self._p(li, "conv_state"), "kind": "state",
                    "layer": i, "width": (self.conv_taps - 1) * self.d_model,
                    "dtype": self.dtype})
        return specs

    def kv_pool_names(self):
        """[(k_pool, v_pool)] of the attention layers."""
        paged = [s["name"] for s in self.cache_specs() if s["kind"] == "paged"]
        return list(zip(paged[0::2], paged[1::2]))

    # ------------------------------------------------------------ submodules

    def _attr(self, i, suffix, std=None, mean=0.0):
        return ParamAttr(
            name=self._p("l%d" % i, suffix),
            initializer=Normal(mean, self.init_std if std is None else std))

    def _norm(self, x, name, groups=1):
        # scales from the seed around 1, so that `* w` is exercised
        return layers.rms_norm(
            x, epsilon=self.norm_eps, groups=groups,
            param_attr=ParamAttr(name=name, initializer=Normal(1.0, 0.1)))

    def _linear(self, x, i, suffix, size):
        return layers.fc(x, size=size, num_flatten_dims=1,
                         param_attr=self._attr(i, suffix), bias_attr=False)

    def _embed(self, tokens):
        return layers.embedding(
            tokens, size=[self.vocab_size, self.d_model], dtype=self.dtype,
            param_attr=ParamAttr(name=self._p("tok_emb"),
                                 initializer=Normal(0.0, self.init_std)))

    def _qkv(self, u, i, pos):
        q = self._linear(u, i, "q_w", self.n_head * self.d_head)
        k = self._linear(u, i, "k_w", self.n_kv_head * self.d_head)
        v = self._linear(u, i, "v_w", self.n_kv_head * self.d_head)
        q = self._norm(q, self._p("l%d" % i, "q_norm_w"), groups=self.n_head)
        k = self._norm(k, self._p("l%d" % i, "k_norm_w"), groups=self.n_kv_head)
        q = layers.rotary_embedding(q, pos, self.n_head, self.rope_theta)
        k = layers.rotary_embedding(k, pos, self.n_kv_head, self.rope_theta)
        return q, k, v

    def _conv(self, u, i, **state):
        bcz = self._linear(u, i, "conv_in_w", 3 * self.d_model)
        y = layers.short_conv(
            bcz, self.conv_taps, param_attr=self._attr(i, "conv_k", std=0.5),
            **state)
        return self._linear(y, i, "conv_out_w", self.d_model)

    def _feed_forward(self, u, i, ff, live, picks_out):
        if ff == "dense":
            g = layers.swiglu(self._linear(u, i, "ff1_w", self.d_ffn),
                              self._linear(u, i, "ff3_w", self.d_ffn))
            return self._linear(g, i, "ff2_w", self.d_model)
        m = self.moe
        picks, weights = layers.moe_router(
            u, m["num_experts"], m["k"], live=live,
            use_bias=m.get("use_bias", True),
            norm_topk=m.get("norm_topk", True), scale=m.get("scale", 1.0),
            param_attr=self._attr(i, "router_w"),
            # a learned buffer in the checkpoint; from the seed here, so
            # that the `+ b` of the choice is exercised
            bias_attr=self._attr(i, "router_b", std=0.05))
        picks_out.append(picks)
        return layers.moe_experts(
            u, picks, weights, m["num_experts"], m["width"],
            experts_held=m.get("experts_held"),
            w1_attr=self._attr(i, "exp_w1"), w3_attr=self._attr(i, "exp_w3"),
            w2_attr=self._attr(i, "exp_w2"))

    def _layer(self, x, i, operator, live, picks_out):
        """x: [rows, d_model]; operator(u, i) -> OP's output."""
        op, ff = self.blocks[i]
        li = "l%d" % i
        o = operator(self._norm(x, self._p(li, "op_norm_w")), i)
        h = layers.elementwise_add(x, o)
        f = self._feed_forward(
            self._norm(h, self._p(li, "ffn_norm_w")), i, ff, live, picks_out)
        return layers.elementwise_add(h, f)

    def _logits(self, x):
        """[rows, vocab] float32: the last norm, then the tied head."""
        h = self._norm(x, self._p("final_norm_w"))
        helper = LayerHelper("tied_head")
        emb = framework.default_main_program().global_block().var(
            self._p("tok_emb"))
        out = helper.create_variable_for_type_inference("float32")
        helper.append_op(
            type="matmul", inputs={"X": [h.name], "Y": [emb.name]},
            outputs={"Out": [out.name]},
            attrs={"transpose_X": False, "transpose_Y": True, "alpha": 1.0,
                   "out_dtype": "float32"})
        return out

    def _picks(self, picks_out):
        """[n_moe_layers, rows, k] int32, or None without moe layers."""
        return layers.stack(picks_out, axis=0) if picks_out else None

    def _cache_vars(self, pool_rows, state_rows):
        block = framework.default_main_program().global_block()
        out = {}
        for s in self.cache_specs():
            rows = pool_rows if s["kind"] == "paged" else state_rows
            out[s["name"]] = block.create_var(
                name=s["name"], shape=[rows, s["width"]], dtype=s["dtype"],
                persistable=True)
        return out

    def _cached_operator(self, caches, table, pos, page_size, conv_state):
        """OP through the caches: attention writes its K/V rows and attends
        the pool; conv reads and writes its state row(s)."""
        def operator(u, i):
            li = "l%d" % i
            if self.blocks[i][0] == "conv":
                return self._conv(
                    u, i, state=caches[self._p(li, "conv_state")], **conv_state)
            q, k, v = self._qkv(u, i, pos)
            k_pool = caches[self._p(li, "kv_k")]
            v_pool = caches[self._p(li, "kv_v")]
            layers.kv_cache_write(k_pool, k, table, pos, page_size)
            layers.kv_cache_write(v_pool, v, table, pos, page_size)
            att = layers.paged_attention(
                q, k_pool, v_pool, table, pos, n_head=self.n_head,
                page_size=page_size, n_kv_head=self.n_kv_head)
            return self._linear(att, i, "o_w", self.d_model)
        return operator

    # -------------------------------------------------------------- programs

    def build_forward(self, batch, t):
        """Whole sequences, no cache: feed fwd_tokens [batch, t, 1] int64,
        fetch logits [batch, t, vocab] float32 (and the routers' picks
        [n_moe, batch * t, k] after them, with moe layers)."""
        main, startup = framework.Program(), framework.Program()
        group = self.n_head // self.n_kv_head
        with framework.program_guard(main, startup), unique_name.guard(
            "%s_fw%dx%d_" % (self.prefix, batch, t)
        ):
            tokens = layers.data(
                "fwd_tokens", [batch, t, 1], append_batch_size=False, dtype="int64")
            pos = layers.assign(np.tile(np.arange(t, dtype="int64"), batch))
            # causal within a KV head's group of query heads: the rows of
            # one sequence are (group member, position)
            tri = layers.assign(np.tile(
                np.triu(np.full((t, t), -1e9, "float32"), k=1), (group, 1)))
            x = layers.reshape(self._embed(tokens), [batch * t, self.d_model])

            def operator(u, i):
                if self.blocks[i][0] == "conv":
                    return self._conv(u, i, seq_len=t)
                q, k, v = self._qkv(u, i, pos)
                # [b, n_kv, group * t, d] against [b, n_kv, t, d]
                q = layers.reshape(
                    layers.transpose(layers.reshape(
                        q, [batch, t, self.n_kv_head, group, self.d_head]),
                        [0, 2, 3, 1, 4]),
                    [batch, self.n_kv_head, group * t, self.d_head])
                kv = lambda y: layers.transpose(layers.reshape(
                    y, [batch, t, self.n_kv_head, self.d_head]), [0, 2, 1, 3])
                scores = layers.matmul(
                    q, kv(k), transpose_y=True, alpha=self.d_head ** -0.5)
                w = layers.softmax(layers.elementwise_add(
                    layers.cast(scores, "float32"), tri))
                ctx = layers.matmul(layers.cast(w, self.dtype), kv(v))
                ctx = layers.reshape(
                    layers.transpose(layers.reshape(
                        ctx, [batch, self.n_kv_head, group, t, self.d_head]),
                        [0, 3, 1, 2, 4]),
                    [batch * t, self.n_head * self.d_head])
                return self._linear(ctx, i, "o_w", self.d_model)

            picks_out = []
            for i in range(self.n_layer):
                x = self._layer(x, i, operator, None, picks_out)
            logits = layers.reshape(self._logits(x), [batch, t, self.vocab_size])
            picks = self._picks(picks_out)
        fetches = [logits.name] + ([picks.name] if picks is not None else [])
        return main, startup, ["fwd_tokens"], fetches

    def build_prefill(self, t, page_size, max_pages, pool_rows, state_rows=1):
        """One prompt chunk of t rows (batch 1), as GPTDecoder.build_prefill,
        with two more feeds: gen_live [t] int32 (1 for a real row, 0 for the
        padding behind them: padding routes to no expert and does not enter
        the conv state) and gen_state_row [1] int32 (the slot's row of the
        conv state pools). Fetch the gen_last row's logits [1, vocab]
        float32, then the routers' picks [n_moe, t, k]."""
        main, startup = framework.Program(), framework.Program()
        with framework.program_guard(main, startup), unique_name.guard(
            "%s_pf%d_" % (self.prefix, t)
        ):
            data = lambda n, shape, dtype: layers.data(
                n, shape, append_batch_size=False, dtype=dtype)
            tokens = data("gen_tokens", [1, t, 1], "int64")
            start = data("gen_start", [1], "int64")
            last = data("gen_last", [1], "int64")
            pages = data("gen_pages", [max_pages], "int32")
            live = data("gen_live", [t], "int32")
            state_row = data("gen_state_row", [1], "int32")
            caches = self._cache_vars(pool_rows, state_rows)
            pos = layers.elementwise_add(
                layers.assign(np.arange(t, dtype="int64")), start)
            x = layers.reshape(self._embed(tokens), [t, self.d_model])
            operator = self._cached_operator(
                caches, pages, pos, page_size,
                dict(rows=state_row, start=start, live=live, mode="chunk"))
            picks_out = []
            for i in range(self.n_layer):
                x = self._layer(x, i, operator, live, picks_out)
            logits = self._logits(layers.gather(x, last))
            picks = self._picks(picks_out)
        feeds = ["gen_tokens", "gen_start", "gen_last", "gen_pages",
                 "gen_live", "gen_state_row"]
        fetches = [logits.name] + ([picks.name] if picks is not None else [])
        return main, startup, feeds, fetches

    def build_decode(self, slots, page_size, max_pages, pool_rows, state_rows=1):
        """One token for every slot, as GPTDecoder.build_decode, with two
        more feeds: dec_live [slots] int32 (0 for an idle or mid-prefill
        slot: it routes to no expert) and dec_state_rows [slots] int32 (each
        slot's row of the conv state pools, the scratch row 0 for a slot
        that is not live). Fetch logits [slots, vocab] float32, then the
        routers' picks [n_moe, slots, k]."""
        main, startup = framework.Program(), framework.Program()
        with framework.program_guard(main, startup), unique_name.guard(
            "%s_dec%d_" % (self.prefix, slots)
        ):
            data = lambda n, shape, dtype: layers.data(
                n, shape, append_batch_size=False, dtype=dtype)
            tokens = data("dec_tokens", [slots, 1], "int64")
            positions = data("dec_positions", [slots, 1], "int64")
            table = data("dec_block_table", [slots, max_pages], "int32")
            live = data("dec_live", [slots], "int32")
            state_rows_ = data("dec_state_rows", [slots], "int32")
            caches = self._cache_vars(pool_rows, state_rows)
            x = self._embed(tokens)
            operator = self._cached_operator(
                caches, table, positions, page_size,
                dict(rows=state_rows_, mode="step"))
            picks_out = []
            for i in range(self.n_layer):
                x = self._layer(x, i, operator, live, picks_out)
            logits = self._logits(x)
            picks = self._picks(picks_out)
        feeds = ["dec_tokens", "dec_positions", "dec_block_table", "dec_live",
                 "dec_state_rows"]
        fetches = [logits.name] + ([picks.name] if picks is not None else [])
        return main, startup, feeds, fetches

    # ---------------------------------------------------------------- params

    def ensure_params(self, scope, place=None):
        """Initialize the shared parameter set into `scope` if absent (the
        forward startup program, once)."""
        if all(n in scope.vars for n in self.param_names()):
            return
        _, startup, _, _ = self.build_forward(1, min(8, self.max_context))
        from ..executor import scope_guard

        with scope_guard(scope):
            Executor(place).run(startup)


def lfm2_moe(config, **kw):
    """The BlockDecoder of an ``lfm2_moe`` ``config.json`` (as a dict, under
    the source's own keys). ``layer_types`` gives each layer's operator;
    the first ``num_dense_layers`` layers have the dense feed-forward and
    the rest the experts. Further keywords (max_context, dtype, eos_id,
    experts_held, prefix) pass through."""
    c = config
    types = {"conv": "conv", "full_attention": "attention"}
    n = int(c["num_hidden_layers"])
    if len(c["layer_types"]) != n:
        raise ValueError("layer_types must name num_hidden_layers layers")
    blocks = [
        (types[t], "dense" if i < int(c["num_dense_layers"]) else "moe")
        for i, t in enumerate(c["layer_types"])]
    moe = {
        "num_experts": int(c["num_experts"]),
        "k": int(c["num_experts_per_tok"]),
        "width": int(c["moe_intermediate_size"]),
        "use_bias": bool(c.get("use_expert_bias", True)),
        "norm_topk": bool(c.get("norm_topk_prob", True)),
        "scale": float(c.get("routed_scaling_factor", 1.0)),
    }
    held = kw.pop("experts_held", None)
    if held is not None:
        moe["experts_held"] = [int(e) for e in held]
    d_model, n_head = int(c["hidden_size"]), int(c["num_attention_heads"])
    kw.setdefault("max_context", int(c["max_position_embeddings"]))
    kw.setdefault("prefix", "lfm2")
    return BlockDecoder(
        vocab_size=int(c["vocab_size"]), d_model=d_model, blocks=blocks,
        n_head=n_head, n_kv_head=int(c["num_key_value_heads"]),
        d_head=int(c.get("head_dim") or d_model // n_head),
        d_ffn=int(c["intermediate_size"]), moe=moe,
        conv_taps=int(c["conv_L_cache"]), norm_eps=float(c["norm_eps"]),
        rope_theta=float(c["rope_theta"]), **kw)
