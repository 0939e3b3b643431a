"""OpTest harness sweep: unary activations / elementwise math.

Reference pattern: unittests/test_activation_op.py — one OpTest subclass per
op with numpy reference output + finite-difference gradient check. Inputs are
nudged away from non-smooth points (kinks/discontinuities) exactly as the
reference does (e.g. test_activation_op.py offsets abs/relu inputs), and
integer-valued or piecewise-constant ops skip the grad check.
"""

import zlib

import numpy as np

from op_test import OpTest


def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def _away_from(x, points, margin=0.15):
    """Shift entries within `margin` of any kink point outward."""
    for p in points:
        near = np.abs(x - p) < margin
        x = np.where(near, p + margin * np.where(x >= p, 1.0, -1.0) * 2, x)
    return x


def _gen_default(shape, rng):
    return rng.uniform(-3, 3, shape).astype("float32")


def _gen_positive(shape, rng):
    return rng.uniform(0.2, 3, shape).astype("float32")


def _gen_away0(shape, rng):
    return _away_from(rng.uniform(-3, 3, shape), [0.0]).astype("float32")


# (op_type, numpy reference(x, attrs), attrs, input gen, check_grad?, tol)
_UNARY_CASES = [
    ("relu", lambda x, a: np.maximum(x, 0), {}, _gen_away0, True, None),
    ("sigmoid", lambda x, a: _sigmoid(x), {}, _gen_default, True, None),
    ("logsigmoid", lambda x, a: np.log(_sigmoid(x)), {}, _gen_default, True, None),
    ("tanh", lambda x, a: np.tanh(x), {}, _gen_default, True, None),
    ("tanh_shrink", lambda x, a: x - np.tanh(x), {}, _gen_default, True, None),
    ("sqrt", lambda x, a: np.sqrt(x), {}, _gen_positive, True, None),
    ("rsqrt", lambda x, a: 1.0 / np.sqrt(x), {}, _gen_positive, True, None),
    ("abs", lambda x, a: np.abs(x), {}, _gen_away0, True, None),
    ("ceil", lambda x, a: np.ceil(x), {}, _gen_away0, False, None),
    ("floor", lambda x, a: np.floor(x), {}, _gen_away0, False, None),
    ("round", lambda x, a: np.round(x), {}, _gen_away0, False, None),
    ("sign", lambda x, a: np.sign(x), {}, _gen_away0, False, None),
    ("cos", lambda x, a: np.cos(x), {}, _gen_default, True, None),
    ("sin", lambda x, a: np.sin(x), {}, _gen_default, True, None),
    ("reciprocal", lambda x, a: 1.0 / x, {}, _gen_positive, True, None),
    ("exp", lambda x, a: np.exp(x), {}, _gen_default, True, None),
    ("log", lambda x, a: np.log(x), {}, _gen_positive, True, None),
    ("square", lambda x, a: np.square(x), {}, _gen_default, True, None),
    (
        "softplus",
        lambda x, a: np.log1p(np.exp(-np.abs(x))) + np.maximum(x, 0),
        {},
        _gen_default,
        True,
        None,
    ),
    ("softsign", lambda x, a: x / (1 + np.abs(x)), {}, _gen_away0, True, None),
    (
        "softshrink",
        lambda x, a: np.sign(x) * np.maximum(np.abs(x) - a["lambda"], 0),
        {"lambda": 0.5},
        lambda s, r: _away_from(r.uniform(-3, 3, s), [-0.5, 0.5]).astype("f4"),
        True,
        None,
    ),
    (
        "hard_shrink",
        lambda x, a: np.where(np.abs(x) > a["threshold"], x, 0),
        {"threshold": 0.5},
        lambda s, r: _away_from(r.uniform(-3, 3, s), [-0.5, 0.5]).astype("f4"),
        True,
        None,
    ),
    (
        "brelu",
        lambda x, a: np.clip(x, a["t_min"], a["t_max"]),
        {"t_min": -1.0, "t_max": 2.0},
        lambda s, r: _away_from(r.uniform(-3, 3, s), [-1.0, 2.0]).astype("f4"),
        True,
        None,
    ),
    (
        "leaky_relu",
        lambda x, a: np.where(x >= 0, x, x * a["alpha"]),
        {"alpha": 0.1},
        _gen_away0,
        True,
        None,
    ),
    (
        "soft_relu",
        lambda x, a: np.log1p(np.exp(np.clip(x, -a["threshold"], a["threshold"]))),
        {"threshold": 40.0},
        _gen_default,
        True,
        None,
    ),
    (
        "elu",
        lambda x, a: np.where(x >= 0, x, a["alpha"] * (np.exp(x) - 1)),
        {"alpha": 1.0},
        _gen_away0,
        True,
        None,
    ),
    (
        "relu6",
        lambda x, a: np.clip(x, 0, a["threshold"]),
        {"threshold": 6.0},
        lambda s, r: _away_from(r.uniform(-3, 8, s), [0.0, 6.0]).astype("f4"),
        True,
        None,
    ),
    (
        "pow",
        lambda x, a: np.power(x, a["factor"]),
        {"factor": 3.0},
        _gen_positive,
        True,
        None,
    ),
    (
        "stanh",
        lambda x, a: a["scale_b"] * np.tanh(a["scale_a"] * x),
        {"scale_a": 0.67, "scale_b": 1.7159},
        _gen_default,
        True,
        None,
    ),
    (
        "hard_sigmoid",
        lambda x, a: np.clip(a["slope"] * x + a["offset"], 0, 1),
        {"slope": 0.2, "offset": 0.5},
        lambda s, r: _away_from(r.uniform(-4, 4, s), [-2.5, 2.5]).astype("f4"),
        True,
        None,
    ),
    (
        "swish",
        lambda x, a: x * _sigmoid(a["beta"] * x),
        {"beta": 1.0},
        _gen_default,
        True,
        None,
    ),
    (
        "gelu",
        lambda x, a: 0.5 * x * (1 + np.vectorize(__import__("math").erf)(x / np.sqrt(2))),
        {},
        _gen_default,
        True,
        1e-3,  # erf curvature vs f32 central differences
    ),
    (
        "thresholded_relu",
        lambda x, a: np.where(x > a["threshold"], x, 0),
        {"threshold": 1.0},
        lambda s, r: _away_from(r.uniform(-3, 3, s), [1.0]).astype("f4"),
        True,
        None,
    ),
]


def _make_case(op, ref, attrs, gen, grad, tol):
    class _Case(OpTest):
        def setUp(self):
            # a constant seed: str hashes are salted per process
            rng = np.random.RandomState(zlib.crc32(op.encode()))
            x = gen((3, 7), rng)
            self.op_type = op
            self.inputs = {"X": x}
            self.attrs = dict(attrs)
            self.outputs = {"Out": ref(x.astype("float64"), self.attrs)}

        def test_check_output(self):
            self.check_output(atol=1e-5)

        if grad:

            def test_check_grad(self):
                self.check_grad(
                    ["X"], max_relative_error=tol if tol else 0.005
                )

    _Case.__name__ = "Test%sOp" % "".join(p.title() for p in op.split("_"))
    return _Case


for _c in _UNARY_CASES:
    _cls = _make_case(*_c)
    globals()[_cls.__name__] = _cls
del _cls


class TestPreluOp(OpTest):
    def setUp(self):
        rng = np.random.RandomState(7)
        x = _away_from(rng.uniform(-3, 3, (3, 6)), [0.0]).astype("float32")
        alpha = rng.uniform(0.1, 0.5, (1,)).astype("float32")
        self.op_type = "prelu"
        self.inputs = {"X": x, "Alpha": alpha}
        self.attrs = {"mode": "all"}
        self.outputs = {"Out": np.where(x >= 0, x, x * alpha[0])}

    def test_check_output(self):
        self.check_output()

    def test_check_grad(self):
        self.check_grad(["X", "Alpha"])


class TestClipOp(OpTest):
    def setUp(self):
        rng = np.random.RandomState(8)
        x = _away_from(rng.uniform(-3, 3, (4, 5)), [-1.0, 1.5]).astype("float32")
        self.op_type = "clip"
        self.inputs = {"X": x}
        self.attrs = {"min": -1.0, "max": 1.5}
        self.outputs = {"Out": np.clip(x, -1.0, 1.5)}

    def test_check_output(self):
        self.check_output()

    def test_check_grad(self):
        self.check_grad(["X"])


class TestClipByNormOp(OpTest):
    def setUp(self):
        rng = np.random.RandomState(9)
        x = rng.uniform(-3, 3, (4, 5)).astype("float32")
        norm = np.sqrt((x.astype("float64") ** 2).sum())
        self.op_type = "clip_by_norm"
        self.inputs = {"X": x}
        self.attrs = {"max_norm": 2.0}
        self.outputs = {"Out": x * (2.0 / norm) if norm > 2.0 else x}

    def test_check_output(self):
        self.check_output(atol=1e-4)


class TestSquaredL2NormOp(OpTest):
    def setUp(self):
        rng = np.random.RandomState(10)
        x = rng.uniform(-2, 2, (3, 4)).astype("float32")
        self.op_type = "squared_l2_norm"
        self.inputs = {"X": x}
        self.outputs = {"Out": np.asarray([(x.astype("float64") ** 2).sum()])}

    def test_check_output(self):
        self.check_output(atol=1e-4)

    def test_check_grad(self):
        self.check_grad(["X"])


if __name__ == "__main__":
    import unittest

    unittest.main()
