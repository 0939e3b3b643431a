"""Direct numeric tests of the block decoder's ops (ops/decoder_ops.py)
against numpy, the precision each must keep, the expert share, and
grouped-query paged attention (the Pallas kernel in interpret mode and the
dense lowering) against a dense gather."""

import unittest

import jax.numpy as jnp
import numpy as np
import pytest

from op_test import OpTest
from paddle_tpu.ops import registry


def _lower(op_type, ins, attrs):
    """The op's lowering on concrete arrays: {slot: [array]} -> {slot: [array]}."""
    return registry.get(op_type).lower(None, ins, attrs)


def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def _silu(x):
    return x * _sigmoid(x)


def _rms_np(x, w, eps, groups=1):
    shape = x.shape
    x = x.astype(np.float64).reshape(shape[:-1] + (groups, shape[-1] // groups))
    y = x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * w
    return y.reshape(shape)


class TestRmsNorm(OpTest):
    def setUp(self):
        self.op_type = "rms_norm"
        x = np.random.randn(6, 32).astype("float32")
        w = np.random.rand(32).astype("float32") + 0.5
        self.inputs = {"X": x, "Scale": w}
        self.attrs = {"epsilon": 1e-5}
        self.outputs = {"Out": _rms_np(x, w, 1e-5).astype("float32")}

    def test_check_output(self):
        self.check_output(atol=1e-5)


class TestRmsNormPerHead(OpTest):
    """groups: each head's slice normed on its own with one [d_head] scale."""

    def setUp(self):
        self.op_type = "rms_norm"
        x = np.random.randn(5, 4 * 8).astype("float32")
        w = np.random.rand(8).astype("float32") + 0.5
        self.inputs = {"X": x, "Scale": w}
        self.attrs = {"epsilon": 1e-5, "groups": 4}
        self.outputs = {"Out": _rms_np(x, w, 1e-5, groups=4).astype("float32")}

    def test_check_output(self):
        self.check_output(atol=1e-5)


def _rotary_np(x, pos, n_head, theta):
    rows = x.shape[0]
    d = x.shape[1] // n_head
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = pos.reshape(rows, 1).astype(np.float64) * inv[None, :]
    cos = np.concatenate([np.cos(ang)] * 2, -1)[:, None, :]
    sin = np.concatenate([np.sin(ang)] * 2, -1)[:, None, :]
    xh = x.astype(np.float64).reshape(rows, n_head, d)
    rot = np.concatenate([-xh[..., d // 2:], xh[..., : d // 2]], -1)
    return (xh * cos + rot * sin).reshape(x.shape)


class TestRotaryEmbedding(OpTest):
    def setUp(self):
        self.op_type = "rotary_embedding"
        x = np.random.randn(7, 3 * 8).astype("float32")
        pos = np.array([0, 1, 5, 17, 100, 1000, 2047], "int64")
        self.inputs = {"X": x, "Pos": pos}
        self.attrs = {"n_head": 3, "theta": 1e6}
        self.outputs = {"Out": _rotary_np(x, pos, 3, 1e6).astype("float32")}

    def test_check_output(self):
        self.check_output(atol=1e-5)


class TestSwiglu(OpTest):
    def setUp(self):
        self.op_type = "swiglu"
        g = np.random.randn(4, 10).astype("float32")
        u = np.random.randn(4, 10).astype("float32")
        self.inputs = {"X": g, "Y": u}
        self.outputs = {"Out": (_silu(g.astype(np.float64)) * u).astype("float32")}

    def test_check_output(self):
        self.check_output(atol=1e-6)


def _conv_np(bcz, kern, prev):
    """(C * conv(B * z), v) for one sequence; prev: the taps - 1 rows of v
    before it."""
    d, taps = kern.shape
    b, c, z = (bcz[:, i * d:(i + 1) * d].astype(np.float64) for i in range(3))
    v = b * z
    vv = np.concatenate([prev, v], 0)
    conv = sum(vv[j:j + len(v)] * kern[:, j] for j in range(taps))
    return c * conv, vv


class TestShortConvWholeSequences(OpTest):
    """No state: the rows are two sequences of five positions from zeros."""

    def setUp(self):
        self.op_type = "short_conv"
        d, t = 6, 5
        x = np.random.randn(2 * t, 3 * d).astype("float32")
        kern = np.random.randn(d, 3).astype("float32")
        self.inputs = {"X": x, "Kernel": kern}
        self.attrs = {"mode": "chunk", "seq_len": t}
        zeros = np.zeros((2, d))
        self.outputs = {"Out": np.concatenate([
            _conv_np(x[i * t:(i + 1) * t], kern, zeros)[0] for i in range(2)
        ]).astype("float32")}

    def test_check_output(self):
        self.check_output(atol=1e-5)


class TestShortConvChunk(OpTest):
    """A chunk of one sequence from position 12 with two rows of padding:
    reads the state row, writes the state after the last live row."""

    def setUp(self):
        self.op_type = "short_conv"
        d, t, live = 6, 8, 6
        x = np.random.randn(t, 3 * d).astype("float32")
        kern = np.random.randn(d, 3).astype("float32")
        state = np.random.randn(4, 2 * d).astype("float32")
        self.inputs = {
            "X": x, "Kernel": kern, "State": state,
            "Rows": np.array([2], "int32"), "Start": np.array([12], "int64"),
            "Live": (np.arange(t) < live).astype("int32")}
        self.attrs = {"mode": "chunk"}
        out, vv = _conv_np(x, kern, state[2].reshape(2, d))
        new = state.copy()
        new[2] = vv[live:live + 2].reshape(-1)
        self.outputs = {"Out": out.astype("float32"), "StateOut": new}

    def test_check_output(self):
        self.check_output(atol=1e-5)


class TestShortConvStep(OpTest):
    """One position a row, each with its own state row; rows 1 and 3 are
    idle (the scratch row 0)."""

    def setUp(self):
        self.op_type = "short_conv"
        d, rows = 6, 4
        x = np.random.randn(rows, 3 * d).astype("float32")
        kern = np.random.randn(d, 3).astype("float32")
        state = np.random.randn(5, 2 * d).astype("float32")
        idx = np.array([3, 0, 1, 0], "int32")
        self.inputs = {"X": x, "Kernel": kern, "State": state, "Rows": idx}
        self.attrs = {"mode": "step"}
        out, new = np.zeros((rows, d)), state.copy()
        for r in (0, 2):
            o, vv = _conv_np(x[r:r + 1], kern, state[idx[r]].reshape(2, d))
            out[r], new[idx[r]] = o[0], vv[1:3].reshape(-1)
        self.outputs = {"Out": out.astype("float32"), "StateOut": new}

    def test_check_output(self):
        main, startup = self._build()
        got = self._exe.run(main, feed=self._feed, fetch_list=["Out", "StateOut"])
        live = [0, 2]
        np.testing.assert_allclose(
            np.asarray(got[0])[live], self.outputs["Out"][live], atol=1e-5)
        # every state row but the scratch row: the idle rows wrote only there
        np.testing.assert_allclose(
            np.asarray(got[1])[1:], self.outputs["StateOut"][1:], atol=1e-5)


def test_short_conv_does_not_depend_on_the_cut():
    """A sequence in one chunk, and cut into a chunk, a shorter chunk with
    padding and three steps, through a bf16 state: the same bits."""
    rng = np.random.default_rng(5)
    d, t = 8, 12
    x = jnp.asarray(rng.standard_normal((t, 3 * d)), jnp.bfloat16)
    kern = jnp.asarray(rng.standard_normal((d, 3)), jnp.float32)
    whole = _lower("short_conv", {"X": [x], "Kernel": [kern]},
                   {"mode": "chunk"})["Out"][0]
    state = jnp.zeros((3, 2 * d), jnp.bfloat16)
    outs = []
    for start, n, rows in ((0, 5, 5), (5, 4, 8)):
        chunk = jnp.zeros((rows, 3 * d), jnp.bfloat16).at[:n].set(x[start:start + n])
        got = _lower("short_conv", {
            "X": [chunk], "Kernel": [kern], "State": [state],
            "Rows": [jnp.array([1], jnp.int32)],
            "Start": [jnp.array([start], jnp.int32)],
            "Live": [(jnp.arange(rows) < n).astype(jnp.int32)]}, {"mode": "chunk"})
        state = got["StateOut"][0]
        outs.append(got["Out"][0][:n])
    for pos in range(9, t):
        got = _lower("short_conv", {
            "X": [x[pos:pos + 1]], "Kernel": [kern], "State": [state],
            "Rows": [jnp.array([1], jnp.int32)]}, {"mode": "step"})
        state = got["StateOut"][0]
        outs.append(got["Out"][0])
    np.testing.assert_array_equal(
        np.asarray(jnp.concatenate(outs), np.float32), np.asarray(whole, np.float32))


def _router_np(x, w, b, k, live=None, norm=True, scale=1.0):
    s = _sigmoid(x.astype(np.float64) @ w.astype(np.float64))
    sel = s + (0.0 if b is None else b)
    picks = np.argsort(-sel, axis=1, kind="stable")[:, :k]
    g = np.take_along_axis(s, picks, axis=1)
    if norm:
        g = g / (g.sum(-1, keepdims=True) + 1e-6)
    g = g * scale
    if live is not None:
        picks = np.where(live[:, None] != 0, picks, w.shape[1])
        g = np.where(live[:, None] != 0, g, 0.0)
    return picks.astype("int32"), g.astype("float32")


class TestMoeRouter(OpTest):
    """The bias picks and does not weigh; a row that is not live routes
    nowhere."""

    def setUp(self):
        self.op_type = "moe_router"
        x = np.random.randn(9, 16).astype("float32")
        w = (np.random.randn(16, 8) * 0.5).astype("float32")
        b = (np.random.randn(8) * 0.3).astype("float32")
        live = np.array([1, 1, 0, 1, 1, 1, 0, 1, 1], "int32")
        self.inputs = {"X": x, "W": w, "Bias": b, "Live": live}
        self.attrs = {"k": 3, "norm_topk": True, "scale": 1.0}
        picks, g = _router_np(x, w, b, 3, live)
        self.outputs = {"Picks": picks, "Weights": g}

    def test_check_output(self):
        self.check_output(atol=1e-5)


def _experts_np(x, picks, g, w1, w3, w2, held):
    out = np.zeros((x.shape[0], w2.shape[-1]))
    x = x.astype(np.float64)
    for r in range(x.shape[0]):
        for e, ge in zip(picks[r], g[r]):
            if e in held:
                j = held.index(e)
                out[r] += ge * ((_silu(x[r] @ w1[j]) * (x[r] @ w3[j])) @ w2[j])
    return out


def _moe_case(rows=11, d=16, f=12, n_exp=8, k=3, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((rows, d)).astype("float32")
    live = (rng.random(rows) > 0.2).astype("int32")
    picks, g = _router_np(
        x, rng.standard_normal((d, n_exp)).astype("float32") * 0.5,
        rng.standard_normal(n_exp).astype("float32") * 0.3, k, live)
    w1, w3 = (rng.standard_normal((n_exp, d, f)).astype("float32") * 0.3
              for _ in range(2))
    w2 = rng.standard_normal((n_exp, f, d)).astype("float32") * 0.3
    return x, picks, g, w1, w3, w2


class TestMoeExperts(OpTest):
    def setUp(self):
        self.op_type = "moe_experts"
        x, picks, g, w1, w3, w2 = _moe_case()
        self.inputs = {"X": x, "Picks": picks, "Weights": g,
                       "W1": w1, "W3": w3, "W2": w2}
        self.attrs = {"num_experts": 8, "experts_held": []}
        self.outputs = {"Out": _experts_np(
            x, picks, g, w1, w3, w2, list(range(8))).astype("float32")}

    def test_check_output(self):
        self.check_output(atol=1e-4)


def test_moe_expert_shares_add_up_to_the_whole_layer():
    """experts_held: four shares of eight of 32 experts, each op holding only
    its own experts' weights and routing over all 32; the four partial
    results add up to the uncut reference's MoE layer (every expert, the
    plain reference's own router)."""
    from paddle_tpu.models import lfm2_moe_reference as ref

    rng = np.random.default_rng(3)
    rows, d, f, n_exp, k = 40, 16, 12, 32, 4
    x = rng.standard_normal((rows, d)).astype("float32")
    router_w = rng.standard_normal((d, n_exp)).astype("float32") * 0.5
    router_b = rng.standard_normal(n_exp).astype("float32") * 0.3
    w1, w3 = (rng.standard_normal((n_exp, d, f)).astype("float32") * 0.3
              for _ in range(2))
    w2 = rng.standard_normal((n_exp, f, d)).astype("float32") * 0.3
    moe = {"num_experts": n_exp, "k": k, "norm_topk": True, "scale": 1.0}
    whole, _, _ = ref.moe_layer(
        jnp.asarray(x), jnp.asarray(router_w), jnp.asarray(router_b),
        jnp.asarray(w1), jnp.asarray(w3), jnp.asarray(w2), moe)
    routed = _lower("moe_router", {
        "X": [jnp.asarray(x)], "W": [jnp.asarray(router_w)],
        "Bias": [jnp.asarray(router_b)]}, {"k": k, "norm_topk": True})
    total = np.zeros((rows, d))
    for share in range(4):
        held = list(range(share * 8, share * 8 + 8))
        part = _lower("moe_experts", {
            "X": [jnp.asarray(x)], "Picks": routed["Picks"],
            "Weights": routed["Weights"], "W1": [jnp.asarray(w1[held])],
            "W3": [jnp.asarray(w3[held])], "W2": [jnp.asarray(w2[held])]},
            {"num_experts": n_exp, "experts_held": held})["Out"][0]
        assert np.abs(np.asarray(part)).max() > 0.01  # every share gives a part
        total += np.asarray(part, np.float64)
    np.testing.assert_allclose(total, np.asarray(whole), atol=2e-4)
    assert np.abs(np.asarray(whole)).max() > 0.1


def test_moe_rows_without_a_live_token_route_nowhere():
    """A row whose picks are `num_experts` (no expert) is in no group: the
    groups' sizes count live rows only, and its output is zero."""
    x, picks, g, w1, w3, w2 = _moe_case(seed=1)
    dead = np.flatnonzero((picks == 8).all(1))
    assert len(dead) >= 1
    out = _moe_lower(x, picks, g, w1, w3, w2, 8)
    assert not np.asarray(out)[dead].any()


# ---- the grouped kernel (ops.pallas_kernels.grouped_expert_ffn, interpret mode)


def _moe_lower(x, picks, g, w1, w3, w2, n_exp, held=None):
    attrs = {"num_experts": n_exp}
    if held is not None:
        attrs["experts_held"] = held
    return _lower("moe_experts", {
        "X": [jnp.asarray(x)], "Picks": [jnp.asarray(picks)],
        "Weights": [jnp.asarray(g)], "W1": [jnp.asarray(w1)],
        "W3": [jnp.asarray(w3)], "W2": [jnp.asarray(w2)]}, attrs)["Out"][0]


def _kernel_case(name):
    """(x, picks, g, w1, w3, w2, n_exp) at widths the kernel takes."""
    rows, d, f, n_exp, k = {
        # a decode step: 32 rows x 4 picks = 128 sorted rows, one row tile
        "decode": (32, 128, 256, 32, 4),
        "one expert for every row": (32, 128, 256, 32, 4),
        # a chunk: 160 x 4 = 640 sorted rows, two row tiles of 512, and
        # groups of ~80 rows: three 32-row windows each
        "chunk": (160, 128, 128, 8, 4),
    }[name]
    x, picks, g, w1, w3, w2 = _moe_case(rows, d, f, n_exp, k, seed=len(name))
    if name == "one expert for every row":
        live = picks[:, 0] < n_exp
        picks[live, 0] = np.where((picks[live, 1:] == 5).any(1), picks[live, 0], 5)
        assert ((picks == 5).sum(1) == 1)[live].all()
    return x, picks, g, w1 * 0.3, w3 * 0.3, w2 * 0.3, n_exp


@pytest.mark.parametrize("name", ["decode", "one expert for every row", "chunk"])
def test_moe_grouped_kernel_matches_the_per_expert_sum(name):
    """Dead rows, experts nobody picked, one expert picked by every live
    row, and groups that straddle row tiles and take several row windows:
    the kernel's result is the plain per-expert sum."""
    from paddle_tpu.ops import pallas_kernels as pk

    x, picks, g, w1, w3, w2, n_exp = _kernel_case(name)
    dead = np.flatnonzero((picks == n_exp).all(1))
    unpicked = set(range(n_exp)) - set(picks.ravel())
    assert len(dead) >= 1
    if name == "decode":
        assert unpicked  # an expert without rows is in no grid step's work
    pk.KERNEL_DISPATCHES.pop("moe_grouped", None)
    out = np.asarray(_moe_lower(x, picks, g, w1, w3, w2, n_exp))
    assert pk.KERNEL_DISPATCHES.get("moe_grouped") == 1
    want = _experts_np(x, picks, g, w1, w3, w2, list(range(n_exp)))
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(out, want, atol=1e-4)
    assert not out[dead].any()


@pytest.mark.parametrize("block_rows, window", [(32, 16), (64, 64), (128, 32)])
def test_moe_grouped_kernel_tiles_rows_without_changing_the_result(
        block_rows, window):
    """Groups cut by row tiles of every size, windows narrower than a group
    and as wide as the tile: the same sums."""
    from paddle_tpu.ops import pallas_kernels as pk

    rng = np.random.default_rng(block_rows)
    sizes = np.array([0, 37, 5, 0, 70, 1, 48, 0], np.int32)  # 161 of 200 rows
    xs = rng.standard_normal((200, 128)).astype("float32")
    w1, w3 = (rng.standard_normal((8, 128, 256)).astype("float32") * 0.1
              for _ in range(2))
    w2 = rng.standard_normal((8, 256, 128)).astype("float32") * 0.1
    got = np.asarray(pk.grouped_expert_ffn(
        jnp.asarray(xs), jnp.asarray(w1), jnp.asarray(w3), jnp.asarray(w2),
        jnp.asarray(sizes), block_rows=block_rows, block_f=128,
        row_window=window))
    assert got.shape == (200, 128) and got.dtype == np.float32
    e = np.repeat(np.arange(8), sizes)
    u = xs[:161].astype(np.float64)
    want = np.stack([
        (_silu(u[r] @ w1[e[r]]) * (u[r] @ w3[e[r]])) @ w2[e[r]]
        for r in range(161)])
    np.testing.assert_allclose(got[:161], want, atol=1e-4)


def test_moe_expert_shares_add_up_through_the_grouped_kernel():
    """experts_held at the kernel's widths: four shares of eight experts,
    each holding only its own weights; a pick of an expert held elsewhere is
    in no group, and the four parts are the whole layer."""
    x, picks, g, w1, w3, w2, n_exp = _kernel_case("decode")
    whole = _experts_np(x, picks, g, w1, w3, w2, list(range(n_exp)))
    total = np.zeros_like(whole)
    for share in range(4):
        held = list(range(share * 8, share * 8 + 8))
        part = np.asarray(_moe_lower(
            x, picks, g, w1[held], w3[held], w2[held], n_exp, held), np.float64)
        np.testing.assert_allclose(
            part, _experts_np(x, picks, g, w1[held], w3[held], w2[held], held),
            atol=1e-4)
        total += part
    np.testing.assert_allclose(total, whole, atol=2e-4)


def test_moe_grouped_kernel_in_bf16_rounds_h_and_the_result_only():
    """bf16 in, bf16 out: float32 products, h rounded once to bf16, the
    picks weighed and summed in float32, one last rounding. Against that
    computation in float64 the result is within one bf16 ulp."""
    x, picks, g, w1, w3, w2, n_exp = _kernel_case("decode")
    b = lambda a: jnp.asarray(a, jnp.bfloat16)
    out = _moe_lower(b(x), picks, g, b(w1), b(w3), b(w2), n_exp)
    assert out.dtype == jnp.bfloat16
    xb, w1b, w3b, w2b = (np.asarray(b(a), np.float64) for a in (x, w1, w3, w2))
    exact = np.zeros(x.shape)
    for r in range(x.shape[0]):
        for e, ge in zip(picks[r], g[r]):
            if e < n_exp:
                h = _silu(xb[r] @ w1b[e]) * (xb[r] @ w3b[e])
                h = np.asarray(b(h.astype(np.float32)), np.float64)
                exact[r] += np.float32(ge) * (h @ w2b[e])
    err = np.abs(np.asarray(out, np.float64) - exact)
    # h within float32 rounding of a bf16 tie may round the other way
    assert (err <= 1.01 * _bf16_ulp(exact)).all()
    assert (err <= 0.51 * _bf16_ulp(exact)).mean() > 0.99


@pytest.mark.parametrize("d, f, dtype", [
    (128, 192, "float32"), (96, 128, "float32"), (128, 128, "float16")],
    ids=["f not whole lanes", "d not whole lanes", "a dtype it does not take"])
def test_moe_shapes_the_kernel_declines_stay_on_ragged_dot(d, f, dtype):
    from paddle_tpu.ops import pallas_kernels as pk

    assert not pk.grouped_ffn_path_taken(d, f, dtype, dtype)
    assert pk.grouped_ffn_path_taken(128, 256, "bfloat16", "bfloat16")
    assert not pk.grouped_ffn_path_taken(128, 256, "bfloat16", "float32")
    x, picks, g, w1, w3, w2 = _moe_case(12, d, f, 8, 3, seed=2)
    w1, w3, w2 = w1 * 0.3, w3 * 0.3, w2 * 0.3
    cast = lambda a: jnp.asarray(a, dtype)
    pk.KERNEL_DISPATCHES.pop("moe_grouped", None)
    out = _moe_lower(cast(x), picks, g, cast(w1), cast(w3), cast(w2), 8)
    assert pk.KERNEL_DISPATCHES.get("moe_grouped", 0) == 0
    want = _experts_np(x, picks, g, w1, w3, w2, list(range(8)))
    np.testing.assert_allclose(
        np.asarray(out, np.float64), want,
        atol=1e-4 if dtype == "float32" else 5e-2)


# ---- precision: the norms and the router run in float32 whatever the dtype


def _bf16_ulp(x):
    return 2.0 ** (np.floor(np.log2(np.maximum(np.abs(x), 1e-30))) - 7)


def test_rms_norm_of_bf16_rounds_once():
    """bf16 in, bf16 out, float32 in between: the result is the exact value
    rounded once (within 0.51 ulp). A norm that squares, sums or scales in
    bf16 misses by whole ulps."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((16, 256)) * 3.0, jnp.bfloat16)
    w = jnp.asarray(rng.random(256) + 0.5, jnp.float32)
    out = _lower("rms_norm", {"X": [x], "Scale": [w]}, {"epsilon": 1e-5})["Out"][0]
    assert out.dtype == jnp.bfloat16
    exact = _rms_np(np.asarray(x, np.float32), np.asarray(w), 1e-5)
    err = np.abs(np.asarray(out, np.float64) - exact)
    assert (err <= 0.51 * _bf16_ulp(exact)).all()
    # and the bf16 form of the same computation does miss: the test can fail
    xb = x * x
    low = (x * (1.0 / jnp.sqrt(jnp.mean(xb, -1, keepdims=True) + 1e-5)).astype(
        jnp.bfloat16) * w.astype(jnp.bfloat16))
    assert (np.abs(np.asarray(low, np.float64) - exact)
            > 0.51 * _bf16_ulp(exact)).any()


def test_router_scores_are_float32_of_a_bf16_input():
    """bf16 activations in, float32 weights out that agree with the float64
    router of the same (bf16-rounded) input to 1e-5; the bf16 router of it
    is a hundred times further off and picks other experts."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((64, 128)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((128, 32)) * 0.1, jnp.float32)
    b = jnp.asarray(rng.standard_normal(32) * 0.05, jnp.float32)
    got = _lower("moe_router", {"X": [x], "W": [w], "Bias": [b]},
                 {"k": 4, "norm_topk": True})
    picks, g = np.asarray(got["Picks"][0]), got["Weights"][0]
    assert g.dtype == jnp.float32
    want_picks, want_g = _router_np(
        np.asarray(x, np.float32), np.asarray(w), np.asarray(b), 4)
    same = (np.sort(picks, 1) == np.sort(want_picks, 1)).all(1)
    assert same.mean() > 0.98  # a tie within float32 rounding may flip a row
    order = np.argsort(picks, 1)
    want_order = np.argsort(want_picks, 1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(g), order, 1)[same],
        np.take_along_axis(want_g, want_order, 1)[same], atol=1e-5)
    low = _router_np(
        np.asarray((x @ w.astype(jnp.bfloat16)).astype(jnp.float32)),
        np.eye(32, dtype="float32"), np.asarray(b), 4)  # scores from bf16 logits
    low_same = (np.sort(low[0], 1) == np.sort(want_picks, 1)).all(1)
    assert low_same.mean() < same.mean()


# ---- grouped-query paged attention


def _gqa_np(q, kp, vp, bt, pos, n_head, n_kv, ps):
    rows, d = q.shape[0], q.shape[1] // n_head
    out = np.zeros(q.shape, np.float64)
    for r in range(rows):
        table = bt if bt.ndim == 1 else bt[r]
        n = int(pos[r]) + 1
        if n <= 0:
            continue
        idx = [table[p // ps] * ps + p % ps for p in range(n)]
        k = kp[idx].reshape(n, n_kv, d).astype(np.float64)
        v = vp[idx].reshape(n, n_kv, d).astype(np.float64)
        for h in range(n_head):
            j = h // (n_head // n_kv)
            s = k[:, j] @ q[r, h * d:(h + 1) * d] * d ** -0.5
            w = np.exp(s - s.max())
            out[r, h * d:(h + 1) * d] = (w / w.sum()) @ v[:, j]
    return out


def _gqa_case(per_row, dtype, n_head=8, n_kv=2, d=16, ps=16, n_pages=12, seed=0):
    rng = np.random.default_rng(seed)
    pool = lambda: np.asarray(jnp.asarray(
        rng.standard_normal((n_pages * ps, n_kv * d)), dtype), np.float32)
    kp, vp = pool(), pool()
    if per_row:
        rows = 5
        bt = np.zeros((rows, 4), np.int32)
        bt[:, :2] = rng.permutation(np.arange(1, n_pages))[:rows * 2].reshape(rows, 2)
        pos = np.array([0, 5, 17, 31, -1], np.int32)
    else:
        rows = 8
        bt = np.array([3, 7, 2, 0], np.int32)
        pos = np.arange(20, 20 + rows).astype(np.int32)
    q = np.asarray(jnp.asarray(
        rng.standard_normal((rows, n_head * d)), dtype), np.float32)
    return q, kp, vp, bt, pos, n_head, n_kv, ps


@pytest.mark.parametrize("per_row", [True, False], ids=["decode", "chunk"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("path", ["kernel", "dense"])
def test_gqa_paged_attention_against_a_dense_gather(per_row, dtype, path):
    """32 query heads on 8 KV heads in miniature (8 on 2): pools n_kv_head *
    d_head wide; the Pallas walk in interpret mode and the dense lowering
    both agree with a per-row gather in numpy."""
    from paddle_tpu import flags

    q, kp, vp, bt, pos, n_head, n_kv, ps = _gqa_case(per_row, jnp.dtype(dtype))
    want = _gqa_np(q, kp, vp, bt, pos, n_head, n_kv, ps)
    prev = flags.get_flags("paged_flash")
    flags.set_flags({"paged_flash": "on" if path == "kernel" else "off"})
    try:
        got = _lower("paged_attention", {
            "Q": [jnp.asarray(q, dtype)], "KPool": [jnp.asarray(kp, dtype)],
            "VPool": [jnp.asarray(vp, dtype)], "BlockTable": [jnp.asarray(bt)],
            "Pos": [jnp.asarray(pos)]},
            {"n_head": n_head, "n_kv_head": n_kv, "page_size": ps})["Out"][0]
    finally:
        flags.set_flags(prev)
    assert got.dtype == jnp.dtype(dtype)
    tol = 1e-5 if dtype == "float32" else 8e-3  # one bf16 rounding of |out| < 2
    np.testing.assert_allclose(np.asarray(got, np.float64), want, atol=tol)


# ---------------------------------------------------------------------------
# partial YaRN rotary, the hyper-connection, latent paged attention
# ---------------------------------------------------------------------------


def _yarn_np(dim, theta, factor, original, beta_fast, beta_slow):
    """YaRN's blended frequencies, transcribed from the published DeepSeek
    modelling code (find_correction_range, linear_ramp_mask)."""
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / factor
    corr = lambda turns: dim * np.log(original / (turns * 2 * np.pi)) / (
        2 * np.log(theta))
    low, high = max(np.floor(corr(beta_fast)), 0), min(np.ceil(corr(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    return inter * (1 - mask) + extra * mask


@pytest.mark.parametrize("yarn", [None, [64.0, 4096.0, 32.0, 1.0], [4.0, 64.0, 32.0, 1.0]],
                         ids=["plain", "published", "small"])
def test_partial_interleaved_rotary_against_numpy(yarn):
    """The last 64 features of each 192-wide head rotated in interleaved
    pairs at YaRN's frequencies; the 128 before them pass through."""
    rng = np.random.default_rng(0)
    n_head, d_head, d_rot = 3, 192, 64
    x = rng.standard_normal((6, n_head * d_head)).astype("float32")
    pos = np.array([0, 1, 77, 4095, 4096, 12479], "int64")
    inv = (_yarn_np(d_rot, 10000.0, *yarn) if yarn
           else 10000.0 ** (-np.arange(0, d_rot, 2) / d_rot))
    if yarn and yarn[0] == 64.0:
        # the published settings blend: neither every pair scaled nor none
        plain = 10000.0 ** (-np.arange(0, d_rot, 2) / d_rot)
        assert inv[0] == plain[0] and np.isclose(inv[-1], plain[-1] / 64)
        assert 0 < np.sum((inv < plain) & (inv > plain / 64)) < d_rot // 2
    ang = pos[:, None] * inv[None, :]
    xh = x.astype(np.float64).reshape(6, n_head, d_head)
    want = xh.copy()
    x1, x2 = xh[..., 128::2], xh[..., 129::2]
    cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
    want[..., 128::2] = x1 * cos - x2 * sin
    want[..., 129::2] = x2 * cos + x1 * sin
    attrs = {"n_head": n_head, "theta": 10000.0, "rotary_dim": d_rot,
             "form": "interleaved"}
    if yarn:
        attrs["yarn"] = yarn
    got = _lower("rotary_embedding", {
        "X": [jnp.asarray(x)], "Pos": [jnp.asarray(pos)]}, attrs)["Out"][0]
    # float32 angles of positions up to 12479: ~1e-3 rad at the fastest pair
    np.testing.assert_allclose(np.asarray(got), want.reshape(x.shape), atol=5e-3)
    np.testing.assert_array_equal(
        np.asarray(got).reshape(6, n_head, d_head)[..., :128], xh[..., :128].astype("float32"))


def _hyper_np(x, w, alpha, bias, n, iters, eps, clamp, norm_eps=1e-6):
    x = x.astype(np.float64)
    rows = x.shape[0]
    xt = x / np.sqrt(np.mean(x * x, -1, keepdims=True) + norm_eps)
    raw = xt @ w * np.repeat(alpha, [n, n, n * n]) + bias
    h_pre, h_post = _sigmoid(raw[:, :n]), 2 * _sigmoid(raw[:, n:2 * n])
    m = np.exp(np.clip(raw[:, 2 * n:], *clamp)).reshape(rows, n, n)
    for _ in range(iters):
        m = m / (m.sum(-1, keepdims=True) + eps)
        m = m / (m.sum(-2, keepdims=True) + eps)
    return h_pre, h_post, m


def _hyper_case(dtype="float32", rows=9, n=4, d=24, seed=0, past_clamp=False):
    """Pre-activations of about the served model's size (std ~1.3)."""
    rng = np.random.default_rng(seed)
    x = np.asarray(jnp.asarray(rng.standard_normal((rows, n * d)), dtype), np.float32)
    w = (rng.standard_normal((n * d, 2 * n + n * n)) * 0.12).astype("float32")
    alpha = np.array([0.5, 0.7, 1.0], "float32")
    bias = (rng.standard_normal(2 * n + n * n) * 0.5).astype("float32")
    if past_clamp:
        # two entries of one row past the clamp: equal under it, e^5 apart
        # without it
        bias[2 * n], bias[2 * n + 1] = 40.0, 35.0
    return x, w, alpha, bias


HC_ATTRS = {"streams": 4, "iters": 20, "eps": 1e-6, "clamp_min": -30.0,
            "clamp_max": 30.0, "norm_eps": 1e-6}


def test_hyper_connection_against_numpy():
    """The read side (u = H_pre X and the maps) and the write side (X' =
    H_res X + H_post^T y) against numpy in float64; H_res is doubly
    stochastic after 20 rounds, an entry past the clamp included."""
    x, w, alpha, bias = _hyper_case()
    n, d = 4, 24
    h_pre, h_post, h_res = _hyper_np(x, w, alpha, bias, n, 20, 1e-6, (-30, 30))
    out = _lower("hyper_connection", {
        "X": [jnp.asarray(x)], "W": [jnp.asarray(w)],
        "Alpha": [jnp.asarray(alpha)], "Bias": [jnp.asarray(bias)]}, HC_ATTRS)
    u, maps = np.asarray(out["Out"][0]), np.asarray(out["Maps"][0])
    xs = x.astype(np.float64).reshape(-1, n, d)
    np.testing.assert_allclose(u, np.einsum("rj,rjd->rd", h_pre, xs), atol=1e-5)
    np.testing.assert_allclose(maps[:, :n], h_post, atol=1e-5)
    got_res = maps[:, n:].reshape(-1, n, n)
    np.testing.assert_allclose(got_res, h_res, atol=1e-5)
    assert np.abs(got_res.sum(-1) - 1).max() < 1e-4
    assert np.abs(got_res.sum(-2) - 1).max() < 1e-4
    assert got_res.max() < 0.95 and got_res.min() > 0.0  # mixed, not a permutation
    # an entry past the clamp is the clamp's: numpy with the clip agrees,
    # numpy without it does not
    xc, wc, ac, bc = _hyper_case(past_clamp=True)
    clamped = np.asarray(_lower("hyper_connection", {
        "X": [jnp.asarray(xc)], "W": [jnp.asarray(wc)],
        "Alpha": [jnp.asarray(ac)], "Bias": [jnp.asarray(bc)]},
        HC_ATTRS)["Maps"][0])[:, n:].reshape(-1, n, n)
    np.testing.assert_allclose(
        clamped, _hyper_np(xc, wc, ac, bc, n, 20, 1e-6, (-30, 30))[2], atol=1e-5)
    assert np.abs(
        clamped - _hyper_np(xc, wc, ac, bc, n, 20, 1e-6, (-99, 99))[2]).max() > 1e-4
    y = np.random.default_rng(1).standard_normal((x.shape[0], d)).astype("float32")
    post = _lower("hyper_connection_post", {
        "X": [jnp.asarray(x)], "Y": [jnp.asarray(y)],
        "Maps": [jnp.asarray(maps)]}, {"streams": n})["Out"][0]
    want = np.einsum("rjk,rkd->rjd", h_res, xs) + h_post[:, :, None] * y[:, None, :]
    np.testing.assert_allclose(np.asarray(post), want.reshape(x.shape), atol=1e-5)


def test_hyper_connection_of_bf16_computes_its_maps_in_float32():
    """A bfloat16 stream: the maps are float32 and doubly stochastic to 1e-4;
    the same rounds in bfloat16 (what the op must not do) miss that by far,
    and u and X' are rounded once, at the end."""
    from paddle_tpu.ops.decoder_ops import sinkhorn

    x, w, alpha, bias = _hyper_case("bfloat16")
    n = 4
    out = _lower("hyper_connection", {
        "X": [jnp.asarray(x, jnp.bfloat16)], "W": [jnp.asarray(w)],
        "Alpha": [jnp.asarray(alpha)], "Bias": [jnp.asarray(bias)]}, HC_ATTRS)
    maps = out["Maps"][0]
    assert maps.dtype == jnp.float32 and out["Out"][0].dtype == jnp.bfloat16
    res = np.asarray(maps)[:, n:].reshape(-1, n, n)
    assert np.abs(res.sum(-1) - 1).max() < 1e-4
    assert np.abs(res.sum(-2) - 1).max() < 1e-4
    _, _, want = _hyper_np(x, w, alpha, bias, n, 20, 1e-6, (-30, 30))
    np.testing.assert_allclose(res, want, atol=1e-5)
    raw = np.log(np.maximum(want, 1e-30))
    low = np.asarray(sinkhorn(jnp.asarray(raw, jnp.bfloat16), 20, 1e-6), np.float64)
    assert max(np.abs(low.sum(-1) - 1).max(), np.abs(low.sum(-2) - 1).max()) > 1e-3
    h_pre = _hyper_np(x, w, alpha, bias, n, 20, 1e-6, (-30, 30))[0]
    u = np.einsum("rj,rjd->rd", h_pre, x.astype(np.float64).reshape(-1, n, 24))
    got = np.asarray(out["Out"][0], np.float64)
    assert np.all(np.abs(got - u) <= _bf16_ulp(u) * 0.51 + 1e-6)


class TestHyperConnection(OpTest):
    def setUp(self):
        self.op_type = "hyper_connection"
        x, w, alpha, bias = _hyper_case()
        h_pre, h_post, h_res = _hyper_np(x, w, alpha, bias, 4, 20, 1e-6, (-30, 30))
        self.inputs = {"X": x, "W": w, "Alpha": alpha, "Bias": bias}
        self.attrs = dict(HC_ATTRS)
        self.outputs = {
            "Out": np.einsum("rj,rjd->rd", h_pre, x.reshape(-1, 4, 24)
                             ).astype("float32"),
            "Maps": np.concatenate(
                [h_post, h_res.reshape(-1, 16)], -1).astype("float32")}

    def test_check_output(self):
        self.check_output(atol=1e-5)


class TestHyperConnectionPost(OpTest):
    def setUp(self):
        self.op_type = "hyper_connection_post"
        rng = np.random.default_rng(2)
        x = rng.standard_normal((7, 4 * 24)).astype("float32")
        y = rng.standard_normal((7, 24)).astype("float32")
        maps = rng.random((7, 20)).astype("float32")
        want = np.einsum("rjk,rkd->rjd", maps[:, 4:].reshape(7, 4, 4),
                         x.reshape(7, 4, 24)) + maps[:, :4, None] * y[:, None, :]
        self.inputs = {"X": x, "Y": y, "Maps": maps}
        self.attrs = {"streams": 4}
        self.outputs = {"Out": want.reshape(7, 96).astype("float32")}

    def test_check_output(self):
        self.check_output(atol=1e-5)


@pytest.mark.parametrize("rows", [9, 32, 256])
def test_hyper_maps_kernel_matches_the_rounds_in_numpy(rows):
    """The Pallas kernel the op takes on a TPU (interpret mode here): the
    sigmoids and 20 Sinkhorn rounds of every row in one call, against numpy
    and against the op's own jax.numpy path."""
    from paddle_tpu.ops import pallas_kernels as pk

    x, w, alpha, bias = _hyper_case(rows=rows, past_clamp=True)
    n = 4
    h_pre, h_post, h_res = _hyper_np(x, w, alpha, bias, n, 20, 1e-6, (-30, 30))
    xt = x / np.sqrt(np.mean(x.astype(np.float64) ** 2, -1, keepdims=True) + 1e-6)
    raw = (xt @ w * np.repeat(alpha, [n, n, n * n]) + bias).astype("float32")
    before = pk.KERNEL_DISPATCHES.get("hyper_maps", 0)
    got = np.asarray(pk.hyper_maps(
        jnp.asarray(raw), n, 20, 1e-6, (-30.0, 30.0), interpret=True))
    assert pk.KERNEL_DISPATCHES["hyper_maps"] == before + 1
    assert got.shape == (rows, 24) and got.dtype == np.float32
    np.testing.assert_allclose(got[:, :n], h_pre, atol=1e-5)
    np.testing.assert_allclose(got[:, n:2 * n], h_post, atol=1e-5)
    np.testing.assert_allclose(
        got[:, 2 * n:].reshape(rows, n, n), h_res, atol=1e-5)
    maps = np.asarray(_lower("hyper_connection", {
        "X": [jnp.asarray(x)], "W": [jnp.asarray(w)],
        "Alpha": [jnp.asarray(alpha)], "Bias": [jnp.asarray(bias)]},
        HC_ATTRS)["Maps"][0])
    np.testing.assert_allclose(got[:, n:], maps, atol=1e-5)
    assert not pk.hyper_maps_path_taken()  # off the chip the op stays in XLA


def _latent_np(q, pool, bt, pos, n_head, d_value, ps, scale):
    rows, width = q.shape[0], pool.shape[1]
    out = np.zeros((rows, n_head * d_value), np.float64)
    for r in range(rows):
        table = bt if bt.ndim == 1 else bt[r]
        n = int(pos[r]) + 1
        if n <= 0:
            continue
        k = pool[[table[p // ps] * ps + p % ps for p in range(n)]].astype(np.float64)
        for h in range(n_head):
            s = k @ q[r, h * width:(h + 1) * width] * scale
            w = np.exp(s - s.max())
            out[r, h * d_value:(h + 1) * d_value] = (w / w.sum()) @ k[:, :d_value]
    return out


def _latent_case(per_row, dtype, n_head=8, width=128, ps=16, n_pages=40, seed=0):
    rng = np.random.default_rng(seed)
    pool = np.asarray(jnp.asarray(
        rng.standard_normal((n_pages * ps, width)), dtype), np.float32)
    if per_row:  # five slots, one past a compute block of 512 positions
        rows = 5
        bt = np.zeros((rows, 36), np.int32)
        bt[0, :36] = rng.permutation(np.arange(1, n_pages))[:36]
        bt[1:, :2] = rng.permutation(np.arange(1, n_pages))[:8].reshape(4, 2)
        pos = np.array([530, 5, 17, 31, -1], np.int32)
    else:  # 20 rows: two programs of 16, the second padded
        rows = 20
        bt = np.concatenate([rng.permutation(np.arange(1, n_pages))[:4],
                             np.zeros(2, np.int64)]).astype(np.int32)
        pos = np.arange(30, 30 + rows).astype(np.int32)
    q = np.asarray(jnp.asarray(
        rng.standard_normal((rows, n_head * width)) * 0.3, dtype), np.float32)
    return q, pool, bt, pos


class TestMlaAttention(OpTest):
    """The op through a program (its shape rule too): decode rows."""

    def setUp(self):
        self.op_type = "mla_attention"
        q, pool, bt, pos = _latent_case(True, jnp.dtype("float32"))
        self.inputs = {"Q": q, "Pool": pool, "BlockTable": bt, "Pos": pos}
        self.attrs = {"page_size": 16, "d_value": 96, "sm_scale": 0.11}
        self.outputs = {"Out": _latent_np(
            q, pool, bt, pos, 8, 96, 16, 0.11).astype("float32")}

    def test_check_output(self):
        self.check_output(atol=1e-5)


@pytest.mark.parametrize("per_row", [True, False], ids=["decode", "chunk"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("path", ["kernel", "dense"])
def test_latent_paged_attention_against_a_dense_gather(per_row, dtype, path):
    """One pool whose row is every head's key and, its first 96 columns,
    their value: the Pallas walk in interpret mode and the dense lowering
    both agree with a per-row gather in numpy, decode rows (one past a
    compute block, one fully masked) and a chunk over a shared table."""
    from paddle_tpu import flags
    from paddle_tpu.ops import pallas_kernels as pk

    q, pool, bt, pos = _latent_case(per_row, jnp.dtype(dtype))
    want = _latent_np(q, pool, bt, pos, 8, 96, 16, 0.11)
    prev = flags.get_flags("paged_flash")
    flags.set_flags({"paged_flash": "on" if path == "kernel" else "off"})
    before = pk.KERNEL_DISPATCHES.get("paged_latent", 0)
    try:
        got = _lower("mla_attention", {
            "Q": [jnp.asarray(q, dtype)], "Pool": [jnp.asarray(pool, dtype)],
            "BlockTable": [jnp.asarray(bt)], "Pos": [jnp.asarray(pos)]},
            {"page_size": 16, "d_value": 96, "sm_scale": 0.11})["Out"][0]
    finally:
        flags.set_flags(prev)
    assert pk.KERNEL_DISPATCHES.get("paged_latent", 0) - before == (path == "kernel")
    assert got.dtype == jnp.dtype(dtype) and got.shape == (q.shape[0], 8 * 96)
    # bf16: the probabilities are rounded before they meet the values
    tol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float64), want, atol=tol)


def test_latent_positions_walked_mirrors_the_kernel():
    from paddle_tpu.ops import pallas_kernels as pk

    # decode: 512-position blocks of 64-row pages, a 200-page table
    assert pk.latent_positions_walked([0, 511, 512, 12479, -1], 64, 200) == (
        512 * (1 + 1 + 2 + 25 + 0))
    # a chunk of 40 rows: programs of 16, 16 and 8 rows, 1024-position blocks
    pos = np.arange(1000, 1040)
    assert pk.latent_positions_walked(pos, 64, 200, shared=True) == 1024 * (1 + 2 + 2)
    # a table narrower than a block
    assert pk.latent_positions_walked([5, 70], 16, 6) == 2 * 96


# ---------------------------------------------------------------------------
# the state-space scan (Mamba-2) and the convolution in front of it
# ---------------------------------------------------------------------------


def test_short_conv_silu_form_against_numpy_and_through_its_state():
    """form "silu": X is convolved itself, Out = silu(conv + bias); whole
    sequences, and the same bits through a chunk, a padded chunk and steps
    over a bf16 state."""
    rng = np.random.default_rng(7)
    d, t, taps = 8, 11, 4
    x = jnp.asarray(rng.standard_normal((t, d)), jnp.bfloat16)
    kern = jnp.asarray(rng.standard_normal((d, taps)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal(d), jnp.float32)
    attrs = {"mode": "chunk", "form": "silu"}
    whole = _lower("short_conv", {"X": [x], "Kernel": [kern], "Bias": [bias]},
                   attrs)["Out"][0]
    x64 = np.concatenate([np.zeros((taps - 1, d)), np.asarray(x, np.float64)])
    conv = sum(x64[j:j + t] * np.asarray(kern, np.float64)[:, j]
               for j in range(taps)) + np.asarray(bias, np.float64)
    np.testing.assert_allclose(
        np.asarray(whole, np.float64), _silu(conv), rtol=1e-2, atol=1e-2)
    assert whole.shape == (t, d) and whole.dtype == jnp.bfloat16
    state = jnp.zeros((3, (taps - 1) * d), jnp.bfloat16)
    outs = []
    for start, n, rows in ((0, 5, 5), (5, 3, 8)):
        chunk = jnp.zeros((rows, d), jnp.bfloat16).at[:n].set(x[start:start + n])
        got = _lower("short_conv", {
            "X": [chunk], "Kernel": [kern], "Bias": [bias], "State": [state],
            "Rows": [jnp.array([2], jnp.int32)],
            "Start": [jnp.array([start], jnp.int32)],
            "Live": [(jnp.arange(rows) < n).astype(jnp.int32)]}, attrs)
        state = got["StateOut"][0]
        outs.append(got["Out"][0][:n])
    for pos in range(8, t):
        got = _lower("short_conv", {
            "X": [x[pos:pos + 1]], "Kernel": [kern], "Bias": [bias],
            "State": [state], "Rows": [jnp.array([2], jnp.int32)]},
            {"mode": "step", "form": "silu"})
        state = got["StateOut"][0]
        outs.append(got["Out"][0])
    np.testing.assert_array_equal(
        np.asarray(jnp.concatenate(outs), np.float32), np.asarray(whole, np.float32))


SSM = dict(heads=8, d_head=16, d_state=16, groups=1, chunk=64, epsilon=1e-5)
_HP = SSM["heads"] * SSM["d_head"]


def _ssm_case(t, seed=0, groups=1):
    rng = np.random.default_rng(seed)
    h, n = SSM["heads"], SSM["d_state"]
    f32 = lambda a: np.asarray(a, np.float32)
    return {
        "x": f32(rng.standard_normal((t, _HP + 2 * groups * n))),
        "dt": f32(rng.standard_normal((t, h))),
        "z": f32(rng.standard_normal((t, _HP))),
        "dt_bias": f32(np.log(np.expm1(np.exp(rng.uniform(
            np.log(1e-3), np.log(1e-1), h))))),
        "a_log": f32(np.log(rng.uniform(1, 16, h))),
        "d": f32(rng.standard_normal(h)),
        "w": f32(1 + 0.1 * rng.standard_normal(_HP)),
    }


def _ssm_recurrence(c, h0=None, groups=1, dtype=np.float32, state_dtype=None,
                    decay_dtype=None):
    """The definition, position by position, in `dtype` (float32: what the
    op is held to; `state_dtype` / `decay_dtype` jnp.bfloat16: the state, or
    dt and the decays, kept one precision lower). Returns (out [t, H * P],
    the last state [H, P, N])."""
    h, p, n = SSM["heads"], SSM["d_head"], SSM["d_state"]
    t = c["x"].shape[0]
    low = lambda a, dt: np.asarray(jnp.asarray(a, dt), dtype) if dt else a
    state = np.zeros((h, p, n), dtype) if h0 is None else h0.astype(dtype)
    a = -np.exp(c["a_log"].astype(dtype))
    out = np.zeros((t, _HP), dtype)
    for i in range(t):
        xs = c["x"][i, :_HP].reshape(h, p).astype(dtype)
        b, cc = (np.repeat(m.reshape(groups, n), h // groups, 0).astype(dtype)
                 for m in (c["x"][i, _HP:_HP + groups * n],
                           c["x"][i, _HP + groups * n:]))
        dt = low(np.logaddexp(0, c["dt"][i].astype(dtype) + c["dt_bias"]).astype(dtype),
                 decay_dtype)
        decay = low(np.exp(dt * low(a, decay_dtype)).astype(dtype), decay_dtype)
        state = low((decay[:, None, None] * state
                     + (dt[:, None] * xs)[:, :, None] * b[:, None, :]).astype(dtype),
                    state_dtype)
        y = np.einsum("hpn,hn->hp", state, cc) + c["d"][:, None] * xs
        g = y.reshape(-1) * _silu(c["z"][i].astype(dtype))
        out[i] = g / np.sqrt(np.mean(g * g) + SSM["epsilon"]) * c["w"]
    return out, state


def _ssm_ins(c, rows=slice(None), **more):
    ins = {"X": [jnp.asarray(c["x"][rows])], "Dt": [jnp.asarray(c["dt"][rows])],
           "Z": [jnp.asarray(c["z"][rows])], "DtBias": [jnp.asarray(c["dt_bias"])],
           "ALog": [jnp.asarray(c["a_log"])], "D": [jnp.asarray(c["d"])],
           "NormScale": [jnp.asarray(c["w"])]}
    ins.update({k: [jnp.asarray(v)] for k, v in more.items()})
    return ins


def _to_pool(h):
    """[H, P, N] -> the pool's row [N, H * P]."""
    return np.transpose(h, (2, 0, 1)).reshape(h.shape[2], -1)


SSM_TOL = dict(rtol=1e-5, atol=1e-5)


class TestSsmScanThroughTheExecutor(OpTest):
    """The op as a program runs it (shape rule, dtypes, slots): one sequence
    of 70 positions from zeros, two chunks of the form."""

    def setUp(self):
        self.op_type = "ssm_scan"
        c = _ssm_case(70, 12)
        self.inputs = {"X": c["x"], "Dt": c["dt"], "Z": c["z"],
                       "DtBias": c["dt_bias"], "ALog": c["a_log"], "D": c["d"],
                       "NormScale": c["w"]}
        self.attrs = dict(SSM, mode="chunk", seq_len=0)
        self.outputs = {"Out": _ssm_recurrence(c)[0]}

    def test_check_output(self):
        self.check_output(atol=1e-5)


@pytest.mark.parametrize("groups", [1, 2])
def test_ssm_scan_whole_sequences_against_the_recurrence(groups):
    """No state: two sequences of 150 positions from zeros, three chunks of
    the quadratic form each, against the recurrence position by position."""
    t = 150
    cases = [_ssm_case(t, seed, groups) for seed in (1, 2)]
    both = {k: (np.concatenate([c[k] for c in cases]) if cases[0][k].ndim == 2
                else cases[0][k]) for k in cases[0]}
    got = _lower("ssm_scan", _ssm_ins(both), dict(
        SSM, groups=groups, mode="chunk", seq_len=t))["Out"][0]
    want = np.concatenate([
        _ssm_recurrence(dict(both, **{k: c[k] for k in ("x", "dt", "z")}),
                        groups=groups)[0] for c in cases])
    assert got.shape == (2 * t, _HP) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), want, **SSM_TOL)


def test_ssm_scan_chunk_reads_and_writes_its_state_row():
    """A chunk of one sequence from position 40 with three rows of padding:
    it starts from its state row, the padding rows leave the state what the
    last live row made it, and every other row of the pool keeps its bits."""
    c = _ssm_case(16, 3)
    rng = np.random.default_rng(4)
    h0 = rng.standard_normal((8, 16, 16)).astype(np.float32)
    pool = rng.standard_normal((4, 16, _HP)).astype(np.float32)
    pool[2] = _to_pool(h0)
    live = 13
    got = _lower("ssm_scan", _ssm_ins(
        c, State=pool, Rows=np.array([2], np.int32),
        Start=np.array([40], np.int64),
        Live=(np.arange(16) < live).astype(np.int32)), dict(SSM, mode="chunk"))
    part = {k: (v[:live] if v.ndim == 2 else v) for k, v in c.items()}
    want, last = _ssm_recurrence(part, h0)
    np.testing.assert_allclose(np.asarray(got["Out"][0])[:live], want, **SSM_TOL)
    new = np.asarray(got["StateOut"][0])
    np.testing.assert_allclose(new[2], _to_pool(last), **SSM_TOL)
    np.testing.assert_array_equal(new[[0, 1, 3]], pool[[0, 1, 3]])
    # Start 0: from zeros, whatever the row holds (a cold admission writes
    # no zeros to the pool)
    cold = _lower("ssm_scan", _ssm_ins(
        c, State=pool, Rows=np.array([2], np.int32),
        Start=np.array([0], np.int64),
        Live=np.ones(16, np.int32)), dict(SSM, mode="chunk"))
    np.testing.assert_allclose(
        np.asarray(cold["Out"][0]), _ssm_recurrence(c)[0], **SSM_TOL)
    # a chunk of padding alone leaves its row bit for bit
    idle = _lower("ssm_scan", _ssm_ins(
        c, State=pool, Rows=np.array([2], np.int32),
        Start=np.array([40], np.int64),
        Live=np.zeros(16, np.int32)), dict(SSM, mode="chunk"))
    np.testing.assert_array_equal(np.asarray(idle["StateOut"][0]), pool)


@pytest.mark.parametrize("groups", [1, 2])
def test_ssm_scan_step_updates_live_rows_and_leaves_idle_slots_alone(groups):
    """One position a row, each with its own state row; rows 1 and 3 are
    idle (Rows 0): every state row keeps its bits but the live rows'."""
    c = _ssm_case(4, 5, groups)
    rng = np.random.default_rng(6)
    pool = rng.standard_normal((6, 16, _HP)).astype(np.float32)
    idx = np.array([3, 0, 5, 0], np.int32)
    got = _lower("ssm_scan", _ssm_ins(c, State=pool, Rows=idx),
                 dict(SSM, groups=groups, mode="step"))
    out, new = np.asarray(got["Out"][0]), np.asarray(got["StateOut"][0])
    for r in (0, 2):
        one = {k: (v[r:r + 1] if v.ndim == 2 else v) for k, v in c.items()}
        h0 = np.transpose(pool[idx[r]].reshape(16, 8, 16), (1, 2, 0))
        want, last = _ssm_recurrence(one, h0, groups=groups)
        np.testing.assert_allclose(out[r], want[0], **SSM_TOL)
        np.testing.assert_allclose(new[idx[r]], _to_pool(last), **SSM_TOL)
    np.testing.assert_array_equal(new[[0, 1, 2, 4]], pool[[0, 1, 2, 4]])


def test_ssm_scan_does_not_depend_on_the_cut():
    """A 200-token prompt as one call (four chunks of the form) and as 128 +
    72 through the state row, the second padded to 128, then three steps:
    the same rows to float32 rounding, and the recurrence's."""
    c = _ssm_case(203, 8)
    want, _ = _ssm_recurrence(c)
    attrs = dict(SSM, mode="chunk")
    whole = np.asarray(_lower("ssm_scan", _ssm_ins(c), attrs)["Out"][0])
    np.testing.assert_allclose(whole, want, **SSM_TOL)
    state = jnp.asarray(np.random.default_rng(9).standard_normal(
        (3, 16, _HP)), jnp.float32)  # stale: Start 0 reads none of it
    outs = []
    for start, n, rows in ((0, 128, 128), (128, 72, 128)):
        pad = {k: np.concatenate([v[start:start + n], np.zeros(
            (rows - n,) + v.shape[1:], v.dtype)]) if v.ndim == 2 else v
            for k, v in c.items()}
        got = _lower("ssm_scan", _ssm_ins(
            pad, State=state, Rows=np.array([1], np.int32),
            Start=np.array([start], np.int64),
            Live=(np.arange(rows) < n).astype(np.int32)), attrs)
        state = got["StateOut"][0]
        outs.append(np.asarray(got["Out"][0])[:n])
    for pos in range(200, 203):
        got = _lower("ssm_scan", _ssm_ins(
            c, slice(pos, pos + 1), State=state, Rows=np.array([1], np.int32)),
            dict(SSM, mode="step"))
        state = got["StateOut"][0]
        outs.append(np.asarray(got["Out"][0]))
    cut = np.concatenate(outs)
    np.testing.assert_allclose(cut, whole, **SSM_TOL)
    np.testing.assert_allclose(cut, want, **SSM_TOL)


@pytest.mark.parametrize("kept_low", ["state", "decay"])
def test_ssm_scan_bar_tells_a_bfloat16_state_or_decay_apart(kept_low):
    """The bar the op is held to fails the same recurrence with its state,
    or its dt and decays, kept in bfloat16: by a hundred times the bar, so
    an op that kept either there could not pass the tests above. And the op
    keeps them in float32 whatever its rows' dtype: bf16 rows read the bf16
    inputs' own recurrence in float32 to bf16's last place, where the lower
    precision inside does not."""
    c = _ssm_case(200, 10)
    want, _ = _ssm_recurrence(c)
    low, _ = _ssm_recurrence(c, **{kept_low + "_dtype": jnp.bfloat16})
    worst = np.max(np.abs(low - want) - 1e-5 * np.abs(want))
    assert worst > 100 * 1e-5, worst
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(low, want, **SSM_TOL)
    # bf16 rows: inputs rounded, everything inside float32, one rounding out
    rounded = {k: (np.asarray(jnp.asarray(v, jnp.bfloat16), np.float32)
                   if k in ("x", "dt", "z") else v) for k, v in c.items()}
    ins = _ssm_ins(rounded)
    for k in ("X", "Dt", "Z"):
        ins[k] = [ins[k][0].astype(jnp.bfloat16)]
    got = _lower("ssm_scan", ins, dict(SSM, mode="chunk"))["Out"][0]
    assert got.dtype == jnp.bfloat16
    exact, _ = _ssm_recurrence(rounded)
    got = np.asarray(got, np.float32)
    assert np.max(np.abs(got - exact) / (np.abs(exact) + 1e-2)) < 2 ** -7
    inside_low, _ = _ssm_recurrence(rounded, **{kept_low + "_dtype": jnp.bfloat16})
    assert np.max(np.abs(got - exact)) < np.max(np.abs(inside_low - exact))


@pytest.mark.parametrize("rows", [
    [3, 0, 1, 0, 6, 0], [0] * 6, [1, 2, 3, 4, 5, 6], [0, 0, 0, 0, 0, 4]])
def test_ssm_state_step_kernel_matches_the_lowering(rows, monkeypatch):
    """The Pallas step (interpret mode) against numpy over a pool of six
    slots, two blocks of lanes a row: live rows updated, every other row bit
    for bit, idle rows' y 0."""
    from paddle_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "_SSM_BLOCK_LANES", 128)

    rng = np.random.default_rng(11)
    slots, hp, n = 6, 256, 16
    pool = rng.standard_normal((slots + 1, n, hp)).astype(np.float32)
    xdt, b, c = (rng.standard_normal(s).astype(np.float32)
                 for s in ((slots, hp), (slots, n), (slots, n)))
    decay = rng.uniform(0, 1, (slots, hp)).astype(np.float32)
    rows = np.asarray(rows, np.int32)
    before = pk.KERNEL_DISPATCHES.get("ssm_step", 0)
    y, new = pk.ssm_state_step(
        jnp.asarray(pool), jnp.asarray(rows), jnp.asarray(xdt),
        jnp.asarray(decay), jnp.asarray(b), jnp.asarray(c), interpret=True)
    assert pk.KERNEL_DISPATCHES.get("ssm_step", 0) == before + 1
    y, new = np.asarray(y), np.asarray(new)
    live = rows != 0
    want_rows = (pool[rows] * decay[:, None, :]
                 + b[:, :, None] * xdt[:, None, :])
    want_y = np.sum(want_rows * c[:, :, None], axis=1)
    np.testing.assert_allclose(y[live], want_y[live], rtol=1e-5, atol=1e-5)
    assert (y[~live] == 0).all()
    np.testing.assert_allclose(new[rows[live]], want_rows[live], rtol=1e-6, atol=1e-6)
    untouched = np.setdiff1d(np.arange(slots + 1), rows[live])
    np.testing.assert_array_equal(new[untouched], pool[untouched])
    # the decision the op makes: the kernel on a TPU, for whole tiles
    assert not pk.ssm_step_path_taken(256, 16, 1)  # this backend is no TPU


if __name__ == "__main__":
    unittest.main()
