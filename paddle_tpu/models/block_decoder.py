"""A decoder assembled from a per-layer block specification (ROADMAP D4):
operator kind x feed-forward kind, under one norm and one position scheme,
so that a configuration is data. It implements the GenerationEngine's model
protocol (``build_prefill`` / ``build_decode`` / ``build_forward`` /
``cache_specs`` / ``ensure_params``) over one shared parameter set.

A layer is two sub-layers, ``h = x + OP(norm(x)); x' = h + FF(norm(h))``
under the ``plain`` residual, with

  * OP ``"attention"``: grouped-query attention, per-head RMS norm of q and
    k (``qk_norm``) before rotary positions (``position`` "rotary", the
    half-rotation form; "none": no position enters at all; a list gives each
    layer its own), scores scaled by ``attn_scale`` (default ``d_head **
    -0.5``), causal, through ``kv_cache_write`` / ``paged_attention`` over
    pools whose rows are ``n_kv_head * d_head`` wide. A layer with a
    ``windows`` entry W > 0 is a sliding-window layer: query t attends only
    t - W < s <= t (``window_attention``), and its pools are a page space of
    their own whose pages the engine returns once every position on them
    lies behind the window (``cache_specs``: ``window``). With
    ``attn_gate`` the context is gated before the output projection,
    ``W_o (ctx * sigmoid(u W_g))``, one gate an element, u the layer's
    normed input;
  * OP ``"mamba"``: the Mamba-2 state-space operator (``ssm={heads, d_head,
    d_state, groups, conv, chunk}``): one projection gives ``[z | xBC |
    dt]``, ``xBC`` goes through a depthwise causal convolution with bias and
    silu (``short_conv``, form "silu"), and ``ssm_scan`` runs the recurrence
    ``H_t = exp(dt_t A) H_{t-1} + dt_t x_t (outer) B_t``, ``y_t = H_t C_t +
    D x_t``, gates with ``silu(z)`` and RMS-norms, all in float32. A
    sequence keeps two state rows a layer beside the pages: the convolution's
    last ``conv - 1`` inputs in ``dtype`` and the recurrent state ``H``
    (``heads * d_head * d_state`` values) in float32;
  * OP ``"conv"``: the gated short convolution (ops/decoder_ops.py
    ``short_conv``), whose per-sequence state — ``conv_taps - 1`` rows of
    ``d_model`` — lives in a per-slot state pool beside the pages;
  * OP ``"mla"``: latent attention (DeepSeek-V2's MLA). The query goes
    through a low-rank bottleneck with its own norm; one projection gives
    the latent ``c_kv`` (``kv_rank`` wide, RMS-normed) and a rotary key
    ``k_r`` (``d_rope`` wide) that every head shares; each head's query is
    ``[q_nope | q_rope]``, its key ``[c_kv W_uk_h | k_r]``, its value ``c_kv
    W_uv_h``. The cache is ONE paged pool a layer whose row is ``[c_kv | k_r
    | zeros to whole lane tiles]``, and the served path attends in the
    absorbed form: ``q_nope W_uk_h^T`` against ``c_kv`` (``mla_attention``
    reads each pooled row once for all the heads), the context ``softmax
    c_kv`` through ``W_uv_h``. Rotary is partial (the ``d_rope`` columns),
    interleaved pairs, YaRN frequencies where the configuration scales them;
  * OP ``"dsa"``: latent attention over a selection (DeepSeek-V3.2's learned
    sparse attention, ``dsa={heads, d_head, topk, rope_dim, norm_eps,
    roles}``). A layer whose role is ``"full"`` runs a lightning indexer:
    index queries ``rot(c_q W_qI)`` (``heads`` of ``d_head``, from the MLA
    query's own normed latent ``c_q``), one index key ``rot(LayerNorm(u
    W_kI))`` a position that every index head shares, kept in a paged pool of
    its own beside the latent pool, head weights ``(u W_w) heads^-0.5``; the
    score of position s for query t is ``d_head^-0.5 sum_j w_j ReLU(q_j .
    k_s)`` over s <= t, and the query keeps its ``topk`` best positions
    (``dsa_index``, ``dsa_topk``; rotary on the first ``rope_dim`` of the
    ``d_head`` columns, interleaved). A ``"shared"`` layer has no indexer and
    reuses the selection of the nearest full layer before it. Either way the
    layer attends absorbed as ``"mla"`` does, over the selected positions
    alone (``mla_sparse_attention``);
  * FF ``"dense"``: SwiGLU of width ``d_ffn``;
  * FF ``"moe"``: sigmoid top-k router over ``num_experts`` and the held
    experts' gated feed-forwards of width ``moe_width`` (``moe_router`` /
    ``moe_experts``; ``experts_held`` is the chip's share, default all),
    plus, with ``shared_width``, a dense SwiGLU of that width that every row
    takes (the shared expert: plain ``fc`` / ``swiglu`` ops).

With ``sandwich_norm`` each sub-layer's output is RMS-normed again before
it is added, ``h = x + norm'(OP(norm(x)))``, ``x' = h + norm'(FF(norm(h)))``.

Under the ``hyper`` residual (``hyper={streams, iters, eps, clamp}``) the
stream between layers is ``streams`` copies wide, ``[rows, streams *
d_model]``: each sub-layer reads a learned, input-dependent mix of the
copies and writes back through a doubly stochastic mix of them
(``hyper_connection`` / ``hyper_connection_post``, ops/decoder_ops.py). The
embedding is repeated into every copy and the copies are summed before the
last norm.

The head is tied to the token embedding unless ``tied_head=False`` (its own
``[vocab, d_model]`` matrix); there is no position embedding. Three
multipliers scale the stream (all 1 by default): the embedding times
``embed_scale``, every sub-layer's output times ``residual_scale`` before it
is added, the logits over ``logit_scale``.
Parameters, activations and caches are in ``dtype`` (bfloat16 as published;
float32 for tight tests); norm scales, the router's matrix and bias and the
conv taps are float32, and the norms, router, softmax and the conv's
accumulation compute in float32 whatever ``dtype`` is. Logits leave the
last product in float32.

``lfm2_moe(config)``, ``xing4_0(config)``, ``granitemoehybrid(config)``,
``glm_moe_dsa(config)`` and ``afmoe(config)``
turn a ``config.json`` of those model types into such a decoder: the architecture is data for this
module.
"""

import numpy as np

from .. import framework, unique_name
from .. import layers
from ..executor import Executor
from ..initializer import Constant, Initializer, Normal
from ..layer_helper import LayerHelper
from ..param_attr import ParamAttr

__all__ = ["BlockDecoder", "lfm2_moe", "xing4_0", "granitemoehybrid",
           "glm_moe_dsa", "afmoe"]

OPERATORS = ("attention", "conv", "mla", "mamba", "dsa")
_LANES = 128  # a latent pool's row is padded to whole lane tiles
FEED_FORWARDS = ("dense", "moe")


class _MambaInit(Initializer):
    """From the seed, by Mamba-2's own initialisation of a state-space
    layer's rates and step sizes. ``"a_log"``: A uniform in [1, 16], the
    parameter its log. ``"dt_bias"``: dt log-uniform in [1e-3, 1e-1], the
    parameter its inverse softplus, dt + log(1 - exp(-dt))."""

    def __init__(self, which):
        self.which = which

    def __call__(self, var, block):
        lo, hi = ((1.0, 16.0) if self.which == "a_log"
                  else (float(np.log(1e-3)), float(np.log(1e-1))))
        block.append_op(
            type="uniform_random", outputs={"Out": [var.name]},
            attrs={"shape": list(var.shape), "dtype": var.dtype,
                   "min": lo, "max": hi, "seed": 0})
        one = lambda t, src, dst, **attrs: block.append_op(
            type=t, inputs={"X": [src.name]}, outputs={"Out": [dst.name]},
            attrs=attrs)
        if self.which == "a_log":
            return one("log", var, var)
        tmp = block.create_var(
            name=unique_name.generate(var.name + "_init"), shape=var.shape,
            dtype=var.dtype)
        one("exp", var, var)  # dt
        one("scale", var, tmp, scale=-1.0)
        one("exp", tmp, tmp)
        one("scale", tmp, tmp, scale=-1.0, bias=1.0)
        one("log", tmp, tmp)  # log(1 - exp(-dt))
        return block.append_op(
            type="elementwise_add", inputs={"X": [var.name], "Y": [tmp.name]},
            outputs={"Out": [var.name]}, attrs={"axis": -1})


class BlockDecoder:
    def __init__(self, vocab_size, d_model, blocks, n_head, n_kv_head, d_head,
                 d_ffn, moe=None, conv_taps=3, norm_eps=1e-5, rope_theta=1e6,
                 max_context=2048, eos_id=-1, prefix="bd", dtype="bfloat16",
                 init_std=0.02, mla=None, hyper=None, tied_head=True,
                 ssm=None, position="rotary", qk_norm=True, attn_scale=None,
                 embed_scale=1.0, residual_scale=1.0, logit_scale=1.0,
                 dsa=None, windows=None, attn_gate=False,
                 sandwich_norm=False):
        """``blocks`` is ``[(operator, feed_forward)]``, a layer each.
        ``moe`` is ``{num_experts, k, width, use_bias, norm_topk, scale,
        experts_held, shared_width}`` (the last two optional). ``mla`` is
        ``{q_rank, kv_rank, d_nope, d_rope, d_value, sm_scale, yarn,
        rope_mscale}`` (``yarn`` = [factor, original positions, beta_fast,
        beta_slow] or None). ``hyper`` is ``{streams, iters, eps, clamp}``
        or None for the plain residual. ``dsa`` is ``{heads, d_head, topk,
        rope_dim, norm_eps, roles}``, ``roles`` a layer each ("full",
        "shared", or None where the operator is not "dsa"). ``windows``: a
        layer each, an attention layer's sliding window (0 or None: its
        whole context). ``position``: "rotary", "none", or a layer each of
        those."""
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
        self.mla = dict(mla or {})
        if any(o in ("mla", "dsa") for o, _ in self.blocks) and not self.mla:
            raise ValueError("an mla block needs the mla settings")
        self.dsa = dict(dsa or {})
        roles = self.dsa.get("roles") or [None] * len(self.blocks)
        if len(roles) != len(self.blocks) or any(
                (o == "dsa") != (r in ("full", "shared"))
                for (o, _), r in zip(self.blocks, roles)):
            raise ValueError("dsa roles: 'full' or 'shared' for each dsa layer, "
                             "None for every other")
        if [r for r in roles if r][:1] == ["shared"]:
            raise ValueError("a shared dsa layer needs a full one before it")
        self.ssm = dict(ssm or {})
        if any(o == "mamba" for o, _ in self.blocks) and not self.ssm:
            raise ValueError("a mamba block needs the ssm settings")
        positions = (list(position) if isinstance(position, (list, tuple))
                     else [position] * len(self.blocks))
        if (len(positions) != len(self.blocks)
                or set(positions) - {"rotary", "none"}):
            raise ValueError("position must be 'rotary' or 'none', or one of "
                             "those a layer")
        self.position = position
        self.positions = positions
        self.windows = [int(w or 0) for w in (windows or [0] * len(self.blocks))]
        if len(self.windows) != len(self.blocks) or any(
                w and o != "attention"
                for w, (o, _) in zip(self.windows, self.blocks)):
            raise ValueError("windows: a layer each, and only attention "
                             "layers may have one")
        self.attn_gate = bool(attn_gate)
        self.sandwich_norm = bool(sandwich_norm)
        self.qk_norm = bool(qk_norm)
        self.attn_scale = (
            self.d_head ** -0.5 if attn_scale is None else float(attn_scale))
        self.embed_scale = float(embed_scale)
        self.residual_scale = float(residual_scale)
        self.logit_scale = float(logit_scale)
        self.hyper = dict(hyper) if hyper else None
        self.tied_head = bool(tied_head)
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
        spec = {
            "blocks": [list(b) for b in self.blocks], "dtype": self.dtype,
            "n_head": self.n_head, "n_kv_head": self.n_kv_head,
            "d_head": self.d_head, "d_model": self.d_model,
            "d_ffn": self.d_ffn, "conv_taps": self.conv_taps,
            "moe": {k: self.moe[k] for k in sorted(self.moe)},
            "mla": {k: self.mla[k] for k in sorted(self.mla)},
            "hyper": self.hyper, "tied_head": self.tied_head,
        }
        # what a decoder built before these settings existed does not say:
        # its key in the compile cache stays what it was
        if self.ssm:
            spec["ssm"] = {k: self.ssm[k] for k in sorted(self.ssm)}
        if self.dsa:
            spec["dsa"] = {k: self.dsa[k] for k in sorted(self.dsa)}
        if any(self.windows):
            spec["windows"] = list(self.windows)
        if self.attn_gate:
            spec["attn_gate"] = True
        if self.sandwich_norm:
            spec["sandwich_norm"] = True
        own = {"position": self.position, "qk_norm": self.qk_norm,
               "attn_scale": self.attn_scale, "embed_scale": self.embed_scale,
               "residual_scale": self.residual_scale,
               "logit_scale": self.logit_scale}
        default = {"position": "rotary", "qk_norm": True,
                   "attn_scale": self.d_head ** -0.5, "embed_scale": 1.0,
                   "residual_scale": 1.0, "logit_scale": 1.0}
        spec.update({k: v for k, v in own.items() if v != default[k]})
        return spec

    def ssm_widths(self):
        """(d_inner, conv_dim): the state-space operator's inner width,
        heads * d_head, and the width of what its convolution sees, [x | B |
        C] = d_inner + 2 * groups * d_state."""
        m = self.ssm
        d_inner = m["heads"] * m["d_head"]
        return d_inner, d_inner + 2 * m.get("groups", 1) * m["d_state"]

    @property
    def experts_held(self):
        """The global ids of the experts this decoder holds (None: all)."""
        return self.moe.get("experts_held")

    def latent_width(self):
        """(values, columns) of a latent pool's row: [c_kv | k_r] and the
        whole lane tiles it is padded to."""
        values = self.mla["kv_rank"] + self.mla["d_rope"]
        return values, -(-values // _LANES) * _LANES

    def moe_layers(self):
        return [i for i, (_, f) in enumerate(self.blocks) if f == "moe"]

    def dsa_role(self, i):
        """"full", "shared" or None: layer i's part in the selection."""
        return (self.dsa.get("roles") or [None] * self.n_layer)[i]

    def dsa_groups(self):
        """[(full layer, the layers that attend its selection)], in layer
        order: a full layer and the shared layers behind it."""
        groups = []
        for i in range(self.n_layer):
            role = self.dsa_role(i)
            if role == "full":
                groups.append((i, [i]))
            elif role == "shared":
                groups[-1][1].append(i)
        return groups

    def param_names(self):
        names = [self._p("tok_emb")]
        for i, (op, ff) in enumerate(self.blocks):
            parts = ["op_norm_w"]
            if op == "conv":
                parts += ["conv_in_w", "conv_k", "conv_out_w"]
            elif op in ("mla", "dsa"):
                parts += ["q_a_w", "q_a_norm_w", "q_b_w", "kv_a_w",
                          "kv_a_norm_w", "uk_w", "uv_w", "o_w"]
                if self.dsa_role(i) == "full":
                    parts += ["idx_q_w", "idx_k_w", "idx_k_norm_w",
                              "idx_k_norm_b", "idx_w_w"]
            elif op == "mamba":
                parts += ["ssm_in_w", "ssm_conv_k", "ssm_conv_b",
                          "ssm_dt_bias", "ssm_a_log", "ssm_d", "ssm_norm_w",
                          "ssm_out_w"]
            else:
                parts += ["q_w", "k_w", "v_w"]
                if self.qk_norm:
                    parts += ["q_norm_w", "k_norm_w"]
                if self.attn_gate:
                    parts.append("g_w")
                parts.append("o_w")
            if self.sandwich_norm:
                parts.append("op_post_norm_w")
            parts.append("ffn_norm_w")
            if ff == "dense":
                parts += ["ff1_w", "ff3_w", "ff2_w"]
            else:
                parts += ["router_w"]
                if self.moe.get("use_bias", True):
                    parts.append("router_b")
                parts += ["exp_w1", "exp_w3", "exp_w2"]
                if self.moe.get("shared_width"):
                    parts += ["sh_w1", "sh_w3", "sh_w2"]
            if self.sandwich_norm:
                parts.append("ffn_post_norm_w")
            if self.hyper:
                parts += [w + s for w in ("op", "ffn")
                          for s in ("_hc_w", "_hc_a", "_hc_b")]
            names += [self._p("l%d" % i, s) for s in parts]
        names.append(self._p("final_norm_w"))
        if not self.tied_head:
            names.append(self._p("head_w"))
        return names

    def cache_specs(self):
        """One entry a cache tensor, in layer order: ``kind`` "paged" (a
        ``[pool_rows, width]`` pool indexed through block tables) or "state"
        (a ``[max_slots + 1, width]`` pool of fixed per-slot rows, row 0
        scratch). An attention layer has a K and a V pool; an mla layer ONE
        pool, ``form`` "latent", whose row holds ``values`` = kv_rank +
        d_rope values and zeros up to ``width``; a dsa layer the same, and a
        full one a second pool beside it, ``form`` "index", one index key
        (``d_head`` values) a position, on the same pages; a mamba layer two state
        pools of different dtype and width, the convolution's and the
        float32 recurrent state's (``form`` "recurrent"; its row is not
        flat but ``shape`` = [d_state, heads * d_head], so a pool is
        ``[max_slots + 1, d_state, heads * d_head]``: a sequence's state in
        tiles of its own). A sliding-window attention layer's K and V pools
        carry ``window``: they are indexed through the window block table,
        in a page space of their own."""
        specs = []
        for i, (op, _) in enumerate(self.blocks):
            li = "l%d" % i
            if op == "attention":
                for s in ("kv_k", "kv_v"):
                    specs.append({
                        "name": self._p(li, s), "kind": "paged", "layer": i,
                        "width": self.n_kv_head * self.d_head,
                        "dtype": self.dtype})
                    if self.windows[i]:
                        specs[-1]["window"] = self.windows[i]
            elif op in ("mla", "dsa"):
                values, width = self.latent_width()
                specs.append({
                    "name": self._p(li, "kv_latent"), "kind": "paged",
                    "layer": i, "width": width, "values": values,
                    "form": "latent", "dtype": self.dtype})
                if self.dsa_role(i) == "full":
                    d = self.dsa["d_head"]
                    specs.append({
                        "name": self._p(li, "index_k"), "kind": "paged",
                        "layer": i, "width": d, "values": d, "form": "index",
                        "dtype": self.dtype})
            elif op == "mamba":
                d_inner, conv_dim = self.ssm_widths()
                specs.append({
                    "name": self._p(li, "ssm_conv_state"), "kind": "state",
                    "layer": i, "width": (self.ssm["conv"] - 1) * conv_dim,
                    "dtype": self.dtype})
                specs.append({
                    "name": self._p(li, "ssm_state"), "kind": "state",
                    "layer": i, "width": d_inner * self.ssm["d_state"],
                    "shape": [self.ssm["d_state"], d_inner],
                    "form": "recurrent", "dtype": "float32"})
            else:
                specs.append({
                    "name": self._p(li, "conv_state"), "kind": "state",
                    "layer": i, "width": (self.conv_taps - 1) * self.d_model,
                    "dtype": self.dtype})
        return specs

    def window(self):
        """The sliding window of the model's window layers (0: none has
        one). One width for all of them: they share a page space."""
        ws = {w for w in self.windows if w}
        if len(ws) > 1:
            raise ValueError("window layers of different widths: %s" % sorted(ws))
        return ws.pop() if ws else 0

    def kv_pool_names(self):
        """[(k_pool, v_pool)] of the attention layers (an mla or dsa layer's
        pools are in cache_specs alone)."""
        paged = [s["name"] for s in self.cache_specs()
                 if s["kind"] == "paged" and s.get("form") is None]
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

    def _param(self, i, suffix, shape, dtype=None):
        """A parameter no layer function makes: a matrix of several axes."""
        return LayerHelper("block_decoder").create_parameter(
            self._attr(i, suffix), shape, dtype or self.dtype)

    def _embed(self, tokens, rows):
        """[rows, d_model] token embeddings; under the hyper residual
        [rows, streams * d_model], every copy the embedding."""
        x = layers.reshape(layers.embedding(
            tokens, size=[self.vocab_size, self.d_model], dtype=self.dtype,
            param_attr=ParamAttr(name=self._p("tok_emb"),
                                 initializer=Normal(0.0, self.init_std))),
            [rows, self.d_model])
        if self.embed_scale != 1.0:
            x = self._scaled(x, self.embed_scale)
        if self.hyper:
            x = layers.concat([x] * self.hyper["streams"], axis=1)
        return x

    def _mla_rotary(self, x, pos, n_head, rotary_dim=None):
        m = self.mla
        return layers.rotary_embedding(
            x, pos, n_head, self.rope_theta, rotary_dim=rotary_dim,
            form="interleaved", yarn=m.get("yarn"),
            mscale=m.get("rope_mscale", 1.0))

    def _latent_row(self, parts, lead, axis):
        """parts ([c | rope], shaped lead + [.]) joined and padded with
        zeros to the latent pool's width."""
        values, width = self.latent_width()
        if width > values:
            parts = parts + [layers.fill_constant(
                lead + [width - values], self.dtype, 0.0)]
        return layers.concat(parts, axis=axis)

    def _mla_cq(self, u, i):
        """The query's normed latent c_q = rms(u W_qa) [rows, q_rank]."""
        return self._norm(self._linear(u, i, "q_a_w", self.mla["q_rank"]),
                          self._p("l%d" % i, "q_a_norm_w"))

    def _mla_query(self, u, i, pos, c_q=None):
        """The absorbed query [rows, n_head * width]: a head's [q_nope @
        W_uk_h^T | rot(q_rope) | zeros], what meets a latent pool's row."""
        m, rows = self.mla, int(u.shape[0])
        h, dn, dr = self.n_head, m["d_nope"], m["d_rope"]
        if c_q is None:
            c_q = self._mla_cq(u, i)
        q = self._mla_rotary(
            self._linear(c_q, i, "q_b_w", h * (dn + dr)), pos, h, dr)
        q_nope, q_rope = layers.split(
            layers.reshape(q, [rows, h, dn + dr]), [dn, dr], dim=2)
        uk = self._param(i, "uk_w", [h, dn, m["kv_rank"]])
        q_lat = layers.transpose(layers.matmul(
            layers.transpose(q_nope, [1, 0, 2]), uk), [1, 0, 2])
        return layers.reshape(
            self._latent_row([q_lat, q_rope], [rows, h], 2),
            [rows, h * self.latent_width()[1]])

    def _mla_latent(self, u, i, pos):
        """The row a position leaves in the cache: [rms(c_kv) | rot(k_r) |
        zeros] [rows, width]."""
        m, li = self.mla, "l%d" % i
        c_kv, k_r = layers.split(
            self._linear(u, i, "kv_a_w", m["kv_rank"] + m["d_rope"]),
            [m["kv_rank"], m["d_rope"]], dim=1)
        return self._latent_row(
            [self._norm(c_kv, self._p(li, "kv_a_norm_w")),
             self._mla_rotary(k_r, pos, 1)], [int(u.shape[0])], 1)

    def _index_rotary(self, x, pos, heads):
        """x [rows, heads * d_head] with rotary on the first rope_dim columns
        of every head, interleaved pairs."""
        rows, d, dr = int(x.shape[0]), self.dsa["d_head"], self.dsa["rope_dim"]
        rope, rest = layers.split(
            layers.reshape(x, [rows, heads, d]), [dr, d - dr], dim=2)
        rope = layers.reshape(self._mla_rotary(
            layers.reshape(rope, [rows, heads * dr]), pos, heads),
            [rows, heads, dr])
        return layers.reshape(layers.concat([rope, rest], axis=2),
                              [rows, heads * d])

    def _indexer(self, u, c_q, i, pos):
        """A full layer's index queries [rows, heads * d_head], each head's
        weight [rows, heads] float32 (d_head^-0.5 heads^-0.5 folded in) and
        the rows' index keys [rows, d_head]."""
        x, li = self.dsa, "l%d" % i
        heads, d = x["heads"], x["d_head"]
        q = self._index_rotary(
            self._linear(c_q, i, "idx_q_w", heads * d), pos, heads)
        k = layers.layer_norm(
            layers.cast(self._linear(u, i, "idx_k_w", d), "float32"),
            epsilon=x["norm_eps"],
            param_attr=ParamAttr(name=self._p(li, "idx_k_norm_w"),
                                 initializer=Normal(1.0, 0.1)),
            bias_attr=ParamAttr(name=self._p(li, "idx_k_norm_b"),
                                initializer=Normal(0.0, 0.1)))
        k = self._index_rotary(layers.cast(k, self.dtype), pos, 1)
        w = layers.fc(layers.cast(u, "float32"), size=heads, num_flatten_dims=1,
                      param_attr=ParamAttr(name=self._p(li, "idx_w_w"),
                                           initializer=Normal(0.0, self.init_std)),
                      bias_attr=False)
        return q, layers.scale(w, scale=float((heads * d) ** -0.5)), k

    def _mla_output(self, ctx, i):
        """Each head's context over c_kv [rows, n_head * kv_rank] through
        its W_uv, then the output projection."""
        m, rows, h = self.mla, int(ctx.shape[0]), self.n_head
        uv = self._param(i, "uv_w", [h, m["kv_rank"], m["d_value"]])
        v = layers.transpose(layers.matmul(layers.transpose(
            layers.reshape(ctx, [rows, h, m["kv_rank"]]), [1, 0, 2]), uv),
            [1, 0, 2])
        return self._linear(
            layers.reshape(v, [rows, h * m["d_value"]]), i, "o_w", self.d_model)

    def _qkv(self, u, i, pos):
        q = self._linear(u, i, "q_w", self.n_head * self.d_head)
        k = self._linear(u, i, "k_w", self.n_kv_head * self.d_head)
        v = self._linear(u, i, "v_w", self.n_kv_head * self.d_head)
        if self.qk_norm:
            q = self._norm(q, self._p("l%d" % i, "q_norm_w"), groups=self.n_head)
            k = self._norm(
                k, self._p("l%d" % i, "k_norm_w"), groups=self.n_kv_head)
        if self.positions[i] == "rotary":
            q = layers.rotary_embedding(q, pos, self.n_head, self.rope_theta)
            k = layers.rotary_embedding(k, pos, self.n_kv_head, self.rope_theta)
        return q, k, v

    def _mamba(self, u, i, conv_state=None, ssm_state=None, **state):
        """The state-space operator: [z | xBC | dt] = u W_in; the
        convolution over xBC; the scan, gate and norm; W_out."""
        m = self.ssm
        d_inner, conv_dim = self.ssm_widths()
        z, xbc, dt = layers.split(
            self._linear(u, i, "ssm_in_w", d_inner + conv_dim + m["heads"]),
            [d_inner, conv_dim, m["heads"]], dim=1)
        xbc = layers.short_conv(
            xbc, m["conv"], form="silu", state=conv_state,
            param_attr=self._attr(i, "ssm_conv_k", std=0.5),
            bias_attr=self._attr(i, "ssm_conv_b", std=0.1), **state)
        at = lambda suffix, init: ParamAttr(
            name=self._p("l%d" % i, suffix), initializer=init)
        y = layers.ssm_scan(
            xbc, dt, z, m["heads"], m["d_head"], m["d_state"],
            groups=m.get("groups", 1), chunk=m.get("chunk", 256),
            epsilon=self.norm_eps, state=ssm_state,
            # the rates and step sizes as Mamba-2 initialises them: with
            # Normal(0, init_std) every decay is ~exp(-0.7) and the state
            # forgets in a few steps, so nothing would tell a wrong state
            dt_bias_attr=at("ssm_dt_bias", _MambaInit("dt_bias")),
            a_log_attr=at("ssm_a_log", _MambaInit("a_log")),
            d_attr=at("ssm_d", Constant(1.0)),
            norm_attr=at("ssm_norm_w", Normal(1.0, 0.1)), **state)
        return self._linear(y, i, "ssm_out_w", self.d_model)

    def _attn_out(self, att, u, i):
        """The output projection of an attention layer's context [rows,
        n_head * d_head]; with ``attn_gate`` the context times sigmoid(u
        W_g) first, the sigmoid in float32."""
        if self.attn_gate:
            g = layers.sigmoid(layers.cast(
                self._linear(u, i, "g_w", self.n_head * self.d_head), "float32"))
            att = layers.elementwise_mul(att, layers.cast(g, self.dtype))
        return self._linear(att, i, "o_w", self.d_model)

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
        routed = layers.moe_experts(
            u, picks, weights, m["num_experts"], m["width"],
            experts_held=m.get("experts_held"),
            w1_attr=self._attr(i, "exp_w1"), w3_attr=self._attr(i, "exp_w3"),
            w2_attr=self._attr(i, "exp_w2"))
        if not m.get("shared_width"):
            return routed
        # the shared expert: every row's, dense, outside op:moe_*
        g = layers.swiglu(self._linear(u, i, "sh_w1", m["shared_width"]),
                          self._linear(u, i, "sh_w3", m["shared_width"]))
        return layers.elementwise_add(
            routed, self._linear(g, i, "sh_w2", self.d_model))

    def _scaled(self, x, factor):
        """x * factor, the product in float32 and rounded once (the scale
        op's factor is rounded to x's dtype first: 0.22 is 0.2197 in
        bfloat16)."""
        if x.dtype in ("float32", np.float32):
            return layers.scale(x, scale=factor)
        return layers.cast(
            layers.scale(layers.cast(x, "float32"), scale=factor), self.dtype)

    def _sublayer(self, x, i, which, fn):
        """One sub-layer under the residual scheme: x + fn(norm(x)), or the
        same through a hyper-connection over the stream's copies. `which`
        is "op" or "ffn": the names of its norm and its connection."""
        norm = self._p("l%d" % i, which + "_norm_w")
        if self.sandwich_norm:
            bare = fn
            fn = lambda u: self._norm(
                bare(u), self._p("l%d" % i, which + "_post_norm_w"))
        if self.residual_scale != 1.0:
            inner = fn
            fn = lambda u: self._scaled(inner(u), self.residual_scale)
        if not self.hyper:
            return layers.elementwise_add(x, fn(self._norm(x, norm)))
        h = self.hyper
        # alpha, bias from the seed at sizes at which the maps depend on the
        # input and differ from a plain sum (a checkpoint's are learned)
        u, maps = layers.hyper_connection(
            x, h["streams"], h["iters"], h["eps"], h["clamp"],
            norm_eps=self.norm_eps, w_attr=self._attr(i, which + "_hc_w"),
            alpha_attr=self._attr(i, which + "_hc_a", std=0.1, mean=0.5),
            bias_attr=self._attr(i, which + "_hc_b", std=0.5))
        return layers.hyper_connection_post(
            x, fn(self._norm(u, norm)), maps, h["streams"])

    def _layer(self, x, i, operator, live, picks_out):
        """x: the residual stream [rows, d_model] (hyper: streams times as
        wide); operator(u, i) -> OP's output."""
        ff = self.blocks[i][1]
        h = self._sublayer(x, i, "op", lambda u: operator(u, i))
        return self._sublayer(
            h, i, "ffn",
            lambda u: self._feed_forward(u, i, ff, live, picks_out))

    def _logits(self, x):
        """[rows, vocab] float32: the stream's copies summed (hyper), the
        last norm, then the head (tied to the embedding, or its own)."""
        if self.hyper:
            x = layers.reduce_sum(layers.reshape(
                x, [int(x.shape[0]), self.hyper["streams"], self.d_model]),
                dim=1)
        h = self._norm(x, self._p("final_norm_w"))
        helper = LayerHelper("tied_head")
        if self.tied_head:
            emb = framework.default_main_program().global_block().var(
                self._p("tok_emb"))
        else:
            emb = helper.create_parameter(
                ParamAttr(name=self._p("head_w"),
                          initializer=Normal(0.0, self.init_std)),
                [self.vocab_size, self.d_model], self.dtype)
        out = helper.create_variable_for_type_inference("float32")
        helper.append_op(
            type="matmul", inputs={"X": [h.name], "Y": [emb.name]},
            outputs={"Out": [out.name]},
            attrs={"transpose_X": False, "transpose_Y": True,
                   "alpha": 1.0 / self.logit_scale, "out_dtype": "float32"})
        return out

    def _picks(self, picks_out):
        """[n_moe_layers, rows, k] int32, or None without moe layers."""
        return layers.stack(picks_out, axis=0) if picks_out else None

    def _fetches(self, logits, picks, selection):
        """logits, then the routers' picks (moe layers), then the full dsa
        layers' selections [n_full, rows, topk] int32 and the distinct rows
        they name, [n_full] int32 (dsa_count's a full layer)."""
        out = [logits.name] + ([picks.name] if picks is not None else [])
        if selection["sels"]:
            out += [layers.stack(selection["sels"], axis=0).name,
                    layers.concat(selection["counts"], axis=0).name]
        return out

    def _dsa_attend(self, u, i, pos, pool, table, page_size, selection,
                    index_pool=None):
        """A dsa layer's attention: a full layer writes its index keys, scans
        and selects (selection["sel"], and the count of what it read); every
        dsa layer then attends the latent pool over the selection, for the
        rows selection["live"] names where the variant has that feed."""
        c_q = self._mla_cq(u, i)
        if self.dsa_role(i) == "full":
            q_i, w_i, k_i = self._indexer(u, c_q, i, pos)
            if index_pool is None:  # a whole sequence: its keys are the pool
                index_pool = k_i
            else:
                layers.kv_cache_write(index_pool, k_i, table, pos, page_size)
            sel, mask = layers.dsa_topk(layers.dsa_index(
                q_i, w_i, index_pool, table, pos, page_size),
                self.dsa["topk"])
            selection["sel"] = sel
            selection["sels"].append(sel)
            selection["counts"].append(layers.dsa_count(
                mask, table, selection.get("count_pos", pos), page_size))
        return self._mla_output(layers.mla_sparse_attention(
            self._mla_query(u, i, pos, c_q), pool, table, selection["sel"],
            page_size, self.mla["kv_rank"], self.mla["sm_scale"],
            live=selection.get("live")), i)

    def _cache_vars(self, pool_rows, state_rows, window_rows=None):
        block = framework.default_main_program().global_block()
        out = {}
        for s in self.cache_specs():
            rows = pool_rows if s["kind"] == "paged" else state_rows
            if s.get("window"):
                rows = window_rows
            out[s["name"]] = block.create_var(
                name=s["name"], shape=[rows] + list(s.get("shape") or [s["width"]]),
                dtype=s["dtype"], persistable=True)
        return out

    def _cached_operator(self, caches, table, pos, page_size, conv_state,
                         selection=None, window_table=None):
        """OP through the caches: attention writes its K/V rows and attends
        the pool (a window layer through `window_table`, its own page
        space); mla writes its latent row and attends it absorbed (dsa
        over its selection, `selection` carrying it from a full layer to the
        shared ones behind it); conv and mamba read and write their state
        row(s)."""
        def operator(u, i):
            li = "l%d" % i
            if self.blocks[i][0] == "dsa":
                pool = caches[self._p(li, "kv_latent")]
                layers.kv_cache_write(
                    pool, self._mla_latent(u, i, pos), table, pos, page_size)
                return self._dsa_attend(
                    u, i, pos, pool, table, page_size, selection,
                    caches.get(self._p(li, "index_k")))
            if self.blocks[i][0] == "conv":
                return self._conv(
                    u, i, state=caches[self._p(li, "conv_state")], **conv_state)
            if self.blocks[i][0] == "mamba":
                return self._mamba(
                    u, i, conv_state=caches[self._p(li, "ssm_conv_state")],
                    ssm_state=caches[self._p(li, "ssm_state")], **conv_state)
            if self.blocks[i][0] == "mla":
                pool = caches[self._p(li, "kv_latent")]
                layers.kv_cache_write(
                    pool, self._mla_latent(u, i, pos), table, pos, page_size)
                return self._mla_output(layers.mla_attention(
                    self._mla_query(u, i, pos), pool, table, pos, page_size,
                    self.mla["kv_rank"], self.mla["sm_scale"]), i)
            q, k, v = self._qkv(u, i, pos)
            k_pool = caches[self._p(li, "kv_k")]
            v_pool = caches[self._p(li, "kv_v")]
            window = self.windows[i]
            pages = window_table if window else table
            layers.kv_cache_write(k_pool, k, pages, pos, page_size)
            layers.kv_cache_write(v_pool, v, pages, pos, page_size)
            att = layers.paged_attention(
                q, k_pool, v_pool, pages, pos, n_head=self.n_head,
                page_size=page_size, n_kv_head=self.n_kv_head,
                sm_scale=(None if self.attn_scale == self.d_head ** -0.5
                          else self.attn_scale), window=window or None)
            return self._attn_out(att, u, i)
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
            # a window layer's: and nothing W or more positions back
            tri_w = {w: layers.assign(np.tile(
                np.triu(np.full((t, t), -1e9, "float32"), k=1)
                + np.tril(np.full((t, t), -1e9, "float32"), k=-w),
                (group, 1))) for w in set(self.windows) if w}
            x = self._embed(tokens, batch * t)
            # mla: a sequence's rows are (position, head), every head of a
            # position under the same mask
            tri_mla = layers.assign(np.repeat(
                np.triu(np.full((t, t), -1e9, "float32"), k=1),
                self.n_head, axis=0)) if self.mla else None

            def mla_operator(u, i):
                # the absorbed form, dense, a sequence at a time
                m = self.mla
                q = self._mla_query(u, i, pos)
                lat = self._mla_latent(u, i, pos)
                width = self.latent_width()[1]
                ctxs = []
                for b in range(batch):
                    rows = lambda y: layers.slice(y, [0], [b * t], [(b + 1) * t])
                    lat_b = rows(lat)
                    scores = layers.matmul(
                        layers.reshape(rows(q), [t * self.n_head, width]),
                        lat_b, transpose_y=True, alpha=m["sm_scale"])
                    w = layers.softmax(layers.elementwise_add(
                        layers.cast(scores, "float32"), tri_mla))
                    ctxs.append(layers.reshape(layers.matmul(
                        layers.cast(w, self.dtype),
                        layers.slice(lat_b, [1], [0], [m["kv_rank"]])),
                        [t, self.n_head * m["kv_rank"]]))
                return self._mla_output(layers.concat(ctxs, axis=0), i)

            # dsa: a sequence's latent rows are its pool, one page of t rows
            one_page = layers.assign(np.zeros([1], "int32")) if self.dsa else None
            pos_t = layers.assign(np.arange(t, dtype="int64")) if self.dsa else None
            selections = [{"sels": [], "counts": []} for _ in range(batch)]

            def dsa_operator(u, i):
                rows = lambda y, b: layers.slice(y, [0], [b * t], [(b + 1) * t])
                lat = self._mla_latent(u, i, pos)
                outs = []
                for b in range(batch):
                    outs.append(self._dsa_attend(
                        rows(u, b), i, pos_t, rows(lat, b), one_page, t,
                        selections[b]))
                return layers.concat(outs, axis=0)

            def operator(u, i):
                if self.blocks[i][0] == "conv":
                    return self._conv(u, i, seq_len=t)
                if self.blocks[i][0] == "mamba":
                    return self._mamba(u, i, seq_len=t)
                if self.blocks[i][0] == "mla":
                    return mla_operator(u, i)
                if self.blocks[i][0] == "dsa":
                    return dsa_operator(u, i)
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
                    q, kv(k), transpose_y=True, alpha=self.attn_scale)
                w = layers.softmax(layers.elementwise_add(
                    layers.cast(scores, "float32"),
                    tri_w[self.windows[i]] if self.windows[i] else tri))
                ctx = layers.matmul(layers.cast(w, self.dtype), kv(v))
                ctx = layers.reshape(
                    layers.transpose(layers.reshape(
                        ctx, [batch, self.n_kv_head, group, t, self.d_head]),
                        [0, 3, 1, 2, 4]),
                    [batch * t, self.n_head * self.d_head])
                return self._attn_out(ctx, u, i)

            picks_out = []
            for i in range(self.n_layer):
                x = self._layer(x, i, operator, None, picks_out)
            logits = layers.reshape(self._logits(x), [batch, t, self.vocab_size])
            picks = self._picks(picks_out)
            # the selections [n_full, batch * t, topk], sequence after sequence
            selection = {"sels": [], "counts": []}
            for f in range(len(selections[0]["sels"])):
                selection["sels"].append(layers.concat(
                    [sl["sels"][f] for sl in selections], axis=0))
                selection["counts"].append(selections[0]["counts"][f])
            fetches = self._fetches(logits, picks, selection)
        return main, startup, ["fwd_tokens"], fetches

    def build_prefill(self, t, page_size, max_pages, pool_rows, state_rows=1,
                      window_rows=None):
        """One prompt chunk of t rows (batch 1), as GPTDecoder.build_prefill,
        with two more feeds: gen_live [t] int32 (1 for a real row, 0 for the
        padding behind them: padding routes to no expert and does not enter
        the conv state) and gen_state_row [1] int32 (the slot's row of the
        conv state pools); with window layers a third, gen_window_pages
        [max_pages] int32, the slot's table into the window layers' pools
        (window_rows rows). Fetch the gen_last row's logits [1, vocab]
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
            wpages = (data("gen_window_pages", [max_pages], "int32")
                      if self.window() else None)
            caches = self._cache_vars(pool_rows, state_rows, window_rows)
            pos = layers.elementwise_add(
                layers.assign(np.arange(t, dtype="int64")), start)
            x = self._embed(tokens, t)
            selection = {"sels": [], "counts": [], "live": live}
            if self.dsa:  # the padding rows behind the prompt count nothing
                selection["count_pos"] = layers.elementwise_add(
                    layers.elementwise_mul(
                        layers.elementwise_add(pos, layers.fill_constant(
                            [t], "int64", 1.0)),
                        layers.cast(live, "int64")),
                    layers.fill_constant([t], "int64", -1.0))
            operator = self._cached_operator(
                caches, pages, pos, page_size,
                dict(rows=state_row, start=start, live=live, mode="chunk"),
                selection, wpages)
            picks_out = []
            for i in range(self.n_layer):
                x = self._layer(x, i, operator, live, picks_out)
            logits = self._logits(layers.gather(x, last))
            picks = self._picks(picks_out)
            fetches = self._fetches(logits, picks, selection)
        feeds = ["gen_tokens", "gen_start", "gen_last", "gen_pages",
                 "gen_live", "gen_state_row"]
        if wpages is not None:
            feeds.append("gen_window_pages")
        return main, startup, feeds, fetches

    def build_decode(self, slots, page_size, max_pages, pool_rows, state_rows=1,
                     window_rows=None):
        """One token for every slot, as GPTDecoder.build_decode, with two
        more feeds: dec_live [slots] int32 (0 for an idle or mid-prefill
        slot: it routes to no expert, and a dsa layer's latent walk gathers
        nothing for it) and dec_state_rows [slots] int32 (each
        slot's row of the conv state pools, the scratch row 0 for a slot
        that is not live); with window layers a third, dec_window_table
        [slots, max_pages] int32 (build_prefill's gen_window_pages a slot).
        Fetch logits [slots, vocab] float32, then the routers' picks
        [n_moe, slots, k]."""
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
            wtable = (data("dec_window_table", [slots, max_pages], "int32")
                      if self.window() else None)
            caches = self._cache_vars(pool_rows, state_rows, window_rows)
            x = self._embed(tokens, slots)
            selection = {"sels": [], "counts": [], "live": live}
            operator = self._cached_operator(
                caches, table, positions, page_size,
                dict(rows=state_rows_, mode="step"), selection, wtable)
            picks_out = []
            for i in range(self.n_layer):
                x = self._layer(x, i, operator, live, picks_out)
            logits = self._logits(x)
            picks = self._picks(picks_out)
            fetches = self._fetches(logits, picks, selection)
        feeds = ["dec_tokens", "dec_positions", "dec_block_table", "dec_live",
                 "dec_state_rows"]
        if wtable is not None:
            feeds.append("dec_window_table")
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


def xing4_0(config, **kw):
    """The BlockDecoder of a ``xing4_0`` ``config.json`` (as a dict, under
    the source's own keys): every layer latent attention (``q_lora_rank``,
    ``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
    ``v_head_dim``; YaRN from ``rope_scaling``), the first
    ``first_k_dense_replace`` layers with the dense feed-forward and the
    rest ``n_routed_experts`` sigmoid top-k experts (``noaux_tc``: a bias
    picks) beside ``n_shared_experts`` shared ones, a ``hc_mult``-stream
    hyper-connection residual, an untied head. ``experts_held`` (keyword)
    lists the routed experts this chip holds; the router keeps its width.
    The multi-token-prediction module (``num_nextn_predict_layers``) is
    built nowhere: the main model's forward pass does not read it. Further
    keywords (max_context, dtype, eos_id, prefix) pass through."""
    c = config
    if int(c.get("n_group", 1)) != 1 or int(c.get("topk_group", 1)) != 1:
        raise ValueError("group-limited routing (n_group > 1) is not built")
    if c.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError("only the sigmoid router is built")
    n, dense = int(c["num_hidden_layers"]), int(c["first_k_dense_replace"])
    blocks = [("mla", "dense" if i < dense else "moe") for i in range(n)]
    moe = {
        "num_experts": int(c["n_routed_experts"]),
        "k": int(c["num_experts_per_tok"]),
        "width": int(c["moe_intermediate_size"]),
        "use_bias": c.get("topk_method", "noaux_tc") == "noaux_tc",
        "norm_topk": bool(c.get("norm_topk_prob", True)),
        "scale": float(c.get("routed_scaling_factor", 1.0)),
        "shared_width": (int(c.get("n_shared_experts") or 0)
                         * int(c["moe_intermediate_size"])),
    }
    held = kw.pop("experts_held", None)
    if held is not None:
        moe["experts_held"] = [int(e) for e in held]
    d_nope, d_rope = int(c["qk_nope_head_dim"]), int(c["qk_rope_head_dim"])
    mla = {
        "q_rank": int(c["q_lora_rank"]), "kv_rank": int(c["kv_lora_rank"]),
        "d_nope": d_nope, "d_rope": d_rope, "d_value": int(c["v_head_dim"]),
        "sm_scale": (d_nope + d_rope) ** -0.5, "yarn": None,
        "rope_mscale": 1.0,
    }
    rs = c.get("rope_scaling")
    if rs:
        if rs.get("type") != "yarn":
            raise ValueError("rope_scaling type %r is not built" % rs.get("type"))
        factor = float(rs["factor"])
        # YaRN's attention temperature: 0.1 * m * ln(factor) + 1
        m_of = lambda m: 0.1 * float(m) * np.log(factor) + 1.0 if factor > 1 else 1.0
        all_dim = m_of(rs.get("mscale_all_dim", 0))
        mla["yarn"] = [factor, float(rs["original_max_position_embeddings"]),
                       float(rs["beta_fast"]), float(rs["beta_slow"])]
        mla["rope_mscale"] = float(m_of(rs.get("mscale", 1)) / all_dim)
        mla["sm_scale"] = float(mla["sm_scale"] * all_dim * all_dim)
    hyper = None
    if int(c.get("hc_mult") or 1) > 1:
        hyper = {
            "streams": int(c["hc_mult"]),
            "iters": int(c["hc_sinkhorn_iters"]), "eps": float(c["hc_eps"]),
            "clamp": [float(c["mhc_h_res_clamp_min"]),
                      float(c["mhc_h_res_clamp_max"])],
        }
    n_head = int(c["num_attention_heads"])
    kw.setdefault("max_context", int(c["max_position_embeddings"]))
    kw.setdefault("prefix", "xing4")
    return BlockDecoder(
        vocab_size=int(c["vocab_size"]), d_model=int(c["hidden_size"]),
        blocks=blocks, n_head=n_head, n_kv_head=n_head,
        d_head=d_nope + d_rope, d_ffn=int(c["intermediate_size"]), moe=moe,
        norm_eps=float(c["rms_norm_eps"]), rope_theta=float(c["rope_theta"]),
        mla=mla, hyper=hyper,
        tied_head=bool(c.get("tie_word_embeddings", False)), **kw)


def granitemoehybrid(config, **kw):
    """The BlockDecoder of a ``granitemoehybrid`` ``config.json`` (as a
    dict, under the source's own keys). ``layer_types`` gives each layer's
    operator, "mamba" (``mamba_n_heads`` heads of ``mamba_d_head`` on a
    state of ``mamba_d_state``, ``mamba_n_groups`` groups of B and C, a
    convolution of ``mamba_d_conv`` taps with bias, chunks of
    ``mamba_chunk_size``) or "attention" (grouped-query, no q/k norm,
    scores times ``attention_multiplier``, rotary positions only where
    ``position_embedding_type`` is "rope": "nope" has none); every layer
    has the dense gated feed-forward of ``shared_intermediate_size``. The
    embedding is scaled by ``embedding_multiplier``, every sub-layer's
    output by ``residual_multiplier``, the logits by 1 /
    ``logits_scaling``; the head is tied. Routed experts beside the shared
    feed-forward (``num_local_experts`` > 0) are not built. Further
    keywords (max_context, dtype, eos_id, prefix) pass through."""
    c = config
    if int(c.get("num_local_experts") or 0) > 0:
        raise ValueError(
            "num_local_experts > 0: routed experts beside the shared "
            "feed-forward are not built")
    for key in ("attention_bias", "mamba_proj_bias"):
        if c.get(key):
            raise ValueError("%s is not built" % key)
    if not c.get("mamba_conv_bias", True):
        raise ValueError("a convolution without its bias is not built")
    if c.get("normalization_function", "rmsnorm") != "rmsnorm":
        raise ValueError("only rmsnorm is built")
    if not c.get("tie_word_embeddings", True):
        raise ValueError("an untied head is not built for this model type")
    n = int(c["num_hidden_layers"])
    if len(c["layer_types"]) != n:
        raise ValueError("layer_types must name num_hidden_layers layers")
    blocks = [(str(t), "dense") for t in c["layer_types"]]
    d_model, n_head = int(c["hidden_size"]), int(c["num_attention_heads"])
    ssm = {
        "heads": int(c["mamba_n_heads"]), "d_head": int(c["mamba_d_head"]),
        "d_state": int(c["mamba_d_state"]),
        "groups": int(c.get("mamba_n_groups", 1)),
        "conv": int(c["mamba_d_conv"]),
        "chunk": int(c.get("mamba_chunk_size", 256)),
    }
    if ssm["heads"] * ssm["d_head"] != int(c["mamba_expand"]) * d_model:
        raise ValueError("mamba_n_heads x mamba_d_head != mamba_expand x hidden_size")
    position = {"nope": "none", "rope": "rotary"}[
        c.get("position_embedding_type", "nope")]
    kw.setdefault("max_context", int(c["max_position_embeddings"]))
    kw.setdefault("prefix", "granite4h")
    return BlockDecoder(
        vocab_size=int(c["vocab_size"]), d_model=d_model, blocks=blocks,
        n_head=n_head, n_kv_head=int(c["num_key_value_heads"]),
        d_head=int(c.get("head_dim") or d_model // n_head),
        d_ffn=int(c["shared_intermediate_size"]),
        norm_eps=float(c["rms_norm_eps"]), rope_theta=float(c["rope_theta"]),
        ssm=ssm, position=position, qk_norm=False,
        attn_scale=float(c["attention_multiplier"]),
        embed_scale=float(c["embedding_multiplier"]),
        residual_scale=float(c["residual_multiplier"]),
        logit_scale=float(c["logits_scaling"]), **kw)


def glm_moe_dsa(config, **kw):
    """The BlockDecoder of a ``glm_moe_dsa`` ``config.json`` (as a dict,
    under the source's own keys): every layer latent attention (``q_lora_rank``,
    ``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
    ``v_head_dim``; rotary from ``rope_parameters``, interleaved) over a
    learned sparse selection: ``indexer_types`` gives each layer's role, a
    "full" layer runs the lightning indexer (``index_n_heads`` heads of
    ``index_head_dim``, rotary on the first ``qk_rope_head_dim`` of them,
    ``indexer_rope_interleave``) and keeps ``index_topk`` positions a query,
    a "shared" one reuses the selection of the full layer before it.
    ``mlp_layer_types`` gives each layer's feed-forward: "dense" (SwiGLU of
    ``intermediate_size``) or "sparse" (``n_routed_experts`` sigmoid top-k
    experts, ``noaux_tc``, beside ``n_shared_experts`` shared ones). The head
    is untied unless ``tie_word_embeddings``. ``experts_held`` (keyword)
    lists the routed experts this chip holds; the router keeps its width.
    Not built: the multi-token-prediction layers (``num_nextn_predict_layers``:
    the main model's forward pass does not read them), DeepSeek-V3.2's
    Hadamard rotation and FP8 of the index queries and keys (the rotation is
    orthogonal and leaves q . k as it is). Further keywords (max_context,
    dtype, eos_id, prefix) pass through."""
    c = config

    def need(key, want):
        raise ValueError("%s = %r is not built (only %s)" % (
            key, c.get(key), want))

    for key, want in (("n_group", 1), ("topk_group", 1), ("moe_layer_freq", 1),
                      ("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
                      ("hidden_act", "silu"), ("attention_bias", False),
                      ("rope_interleave", True),
                      ("indexer_rope_interleave", True),
                      ("index_topk_pattern", None)):
        if c.get(key, want) != want:
            need(key, repr(want))
    rope = c.get("rope_parameters") or {}
    if rope.get("rope_type", "default") != "default":
        need("rope_parameters", "rope_type 'default'")
    n, n_head = int(c["num_hidden_layers"]), int(c["num_attention_heads"])
    if int(c.get("num_key_value_heads", n_head)) != n_head:
        need("num_key_value_heads", n_head)
    d_nope, d_rope = int(c["qk_nope_head_dim"]), int(c["qk_rope_head_dim"])
    if int(c.get("qk_head_dim", d_nope + d_rope)) != d_nope + d_rope:
        need("qk_head_dim", d_nope + d_rope)
    roles, mlps = list(c.get("indexer_types") or []), list(c.get("mlp_layer_types") or [])
    if len(roles) != n or set(roles) - {"full", "shared"} or roles[0] != "full":
        need("indexer_types", "'full' or 'shared' for each of num_hidden_layers "
             "layers, the first 'full'")
    if len(mlps) != n or set(mlps) - {"dense", "sparse"}:
        need("mlp_layer_types", "'dense' or 'sparse' for each layer")
    blocks = [("dsa", "dense" if f == "dense" else "moe") for f in mlps]
    moe = {
        "num_experts": int(c["n_routed_experts"]),
        "k": int(c["num_experts_per_tok"]),
        "width": int(c["moe_intermediate_size"]),
        "use_bias": True,
        "norm_topk": bool(c.get("norm_topk_prob", True)),
        "scale": float(c.get("routed_scaling_factor", 1.0)),
        "shared_width": (int(c.get("n_shared_experts") or 0)
                         * int(c["moe_intermediate_size"])),
    }
    held = kw.pop("experts_held", None)
    if held is not None:
        moe["experts_held"] = [int(e) for e in held]
    mla = {
        "q_rank": int(c["q_lora_rank"]), "kv_rank": int(c["kv_lora_rank"]),
        "d_nope": d_nope, "d_rope": d_rope, "d_value": int(c["v_head_dim"]),
        "sm_scale": (d_nope + d_rope) ** -0.5, "yarn": None, "rope_mscale": 1.0,
    }
    dsa = {
        "heads": int(c["index_n_heads"]), "d_head": int(c["index_head_dim"]),
        "topk": int(c["index_topk"]), "rope_dim": d_rope,
        # DeepSeek-V3.2's index-key LayerNorm; the config does not say
        "norm_eps": 1e-6, "roles": roles,
    }
    kw.setdefault("max_context", int(c["max_position_embeddings"]))
    kw.setdefault("prefix", "glm5")
    return BlockDecoder(
        vocab_size=int(c["vocab_size"]), d_model=int(c["hidden_size"]),
        blocks=blocks, n_head=n_head, n_kv_head=n_head,
        d_head=d_nope + d_rope, d_ffn=int(c["intermediate_size"]), moe=moe,
        norm_eps=float(c["rms_norm_eps"]),
        rope_theta=float(rope.get("rope_theta", c.get("rope_theta", 10000.0))),
        mla=mla, dsa=dsa,
        tied_head=bool(c.get("tie_word_embeddings", False)), **kw)


def afmoe(config, **kw):
    """The BlockDecoder of an ``afmoe`` ``config.json`` (Arcee's Trinity;
    as a dict, under the source's own keys). ``layer_types`` gives each
    layer's attention: "sliding_attention" attends the last
    ``sliding_window`` positions with rotary positions over the whole head
    (``rope_theta``), "full_attention" its whole context with no position
    encoding at all; both are grouped-query (``num_key_value_heads`` of
    ``head_dim``) with a per-head RMS norm of q and k and a sigmoid gate an
    element on the context before the output projection. Every sub-layer is
    sandwiched between two RMS norms. The first ``num_dense_layers`` layers
    have the dense SwiGLU of ``intermediate_size``, the rest ``num_experts``
    sigmoid-scored experts of ``moe_intermediate_size``, top
    ``num_experts_per_tok`` of score + a bias, their scores normalised to
    sum 1 (``route_norm``) and times ``route_scale``, beside
    ``num_shared_experts`` shared ones. With ``mup_enabled`` the embedding
    is scaled by hidden_size^1/2. The head is untied unless
    ``tie_word_embeddings``. ``experts_held`` (keyword) lists the routed
    experts this chip holds; the router keeps its width. Further keywords
    (max_context, dtype, eos_id, prefix) pass through."""
    c = config

    def need(key, want):
        raise ValueError("%s = %r is not built (only %s)" % (
            key, c.get(key), want))

    for key, want in (("n_group", 1), ("topk_group", 1),
                      ("num_expert_groups", 1), ("num_limited_groups", 1),
                      ("score_func", "sigmoid"), ("hidden_act", "silu"),
                      ("rope_scaling", None)):
        if c.get(key, want) != want:
            need(key, repr(want))
    n = int(c["num_hidden_layers"])
    types = list(c.get("layer_types") or [])
    if len(types) != n or set(types) - {"sliding_attention", "full_attention"}:
        need("layer_types", "'sliding_attention' or 'full_attention' for each "
             "of num_hidden_layers layers")
    dense = int(c["num_dense_layers"])
    blocks = [("attention", "dense" if i < dense else "moe") for i in range(n)]
    window = int(c["sliding_window"])
    moe = {
        "num_experts": int(c["num_experts"]),
        "k": int(c["num_experts_per_tok"]),
        "width": int(c["moe_intermediate_size"]),
        "use_bias": True,
        "norm_topk": bool(c.get("route_norm", True)),
        "scale": float(c.get("route_scale", 1.0)),
        "shared_width": (int(c.get("num_shared_experts") or 0)
                         * int(c["moe_intermediate_size"])),
    }
    held = kw.pop("experts_held", None)
    if held is not None:
        moe["experts_held"] = [int(e) for e in held]
    d_model, n_head = int(c["hidden_size"]), int(c["num_attention_heads"])
    sliding = [t == "sliding_attention" for t in types]
    kw.setdefault("max_context", int(c["max_position_embeddings"]))
    kw.setdefault("prefix", "afmoe")
    return BlockDecoder(
        vocab_size=int(c["vocab_size"]), d_model=d_model, blocks=blocks,
        n_head=n_head, n_kv_head=int(c["num_key_value_heads"]),
        d_head=int(c.get("head_dim") or d_model // n_head),
        d_ffn=int(c["intermediate_size"]), moe=moe,
        norm_eps=float(c["rms_norm_eps"]), rope_theta=float(c["rope_theta"]),
        position=["rotary" if w else "none" for w in sliding],
        windows=[window if w else 0 for w in sliding], qk_norm=True,
        attn_gate=True, sandwich_norm=True,
        embed_scale=float(d_model ** 0.5) if c.get("mup_enabled") else 1.0,
        tied_head=bool(c.get("tie_word_embeddings", False)), **kw)
