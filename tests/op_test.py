"""OpTest harness (reference python/paddle/fluid/tests/unittests/op_test.py:132).

Subclasses declare `op_type`, `inputs`, `outputs`, `attrs` as numpy data;
`check_output()` runs the single op through a real Program/Executor and
compares against the declared numpy reference; `check_grad()` compares
analytic gradients (via append_backward over the generic vjp grad ops) against
central finite differences (reference op_test.py:43 get_numeric_gradient).

Place parametrization (reference op_test.py:303-385,427 runs each op on
CPUPlace AND CUDAPlace): PADDLE_OPTEST_PLACE=tpu runs the same checks against
the real chip (see scripts/optest_tpu.py). On TPU:
- check_output tolerances are scaled (_TOL_SCALE): default-precision f32
  matmuls/convs execute as bf16 passes on the MXU (~8-bit mantissa inputs,
  f32 accumulate), so elementwise-exact f32 comparison is the wrong bar —
  the loosened bar still catches wrong algorithms, off-by-one windows, and
  layout bugs, which is what a second place exists to catch.
- check_grad runs under jax.default_matmul_precision("highest") (f32-exact
  on the MXU via multi-pass): central differences divide ~1e-3 loss deltas,
  which bf16 rounding noise would drown; highest-precision mode verifies the
  device LOWERING of every grad op while keeping the finite-difference
  comparison meaningful — the analog of the reference checking fp32 CUDA
  kernels (not its fp16 tier) under its grad harness.
"""

import os
import unittest
from contextlib import nullcontext

import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu import framework
from paddle_tpu.executor import Executor, Scope, scope_guard

_PLACE = os.environ.get("PADDLE_OPTEST_PLACE", "cpu").lower()
_TOL_SCALE = float(
    os.environ.get("PADDLE_OPTEST_TOL_SCALE", "1000" if _PLACE == "tpu" else "1")
)
# ops whose lowering never touches the MXU execute in f32 on the VPU and
# should be near-exact vs the numpy reference — they get at most this scale
# and a tight atol cap (a blanket 1000x turned e.g. atol=1e-3 into atol=1,
# vacuous for elementwise/reduction/indexing ops)
_NON_MXU_TOL_SCALE = float(os.environ.get("PADDLE_OPTEST_NONMXU_TOL_SCALE", "10"))

# primitives whose presence in the lowered jaxpr means the op's compute
# crosses the MXU (bf16 multiply passes under default precision)
_MXU_PRIMS = frozenset(
    ["dot_general", "conv_general_dilated", "pallas_call"]
)


def _jaxpr_crosses_mxu(jaxpr):
    """Recursively scan a (Closed)Jaxpr for MXU-bearing primitives, walking
    nested jaxprs (pjit / scan / while / cond / custom_vjp bodies)."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in _MXU_PRIMS:
            return True
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                    if _jaxpr_crosses_mxu(sub):
                        return True
    return False
# grad checks run at highest matmul precision, so only reduction-order f32
# differences vs the CPU-tuned bounds remain — a mild scale absorbs them
_GRAD_TOL_SCALE = float(
    os.environ.get("PADDLE_OPTEST_GRAD_TOL_SCALE", "4" if _PLACE == "tpu" else "1")
)


def _grad_precision_ctx():
    if _PLACE == "tpu":
        import jax

        return jax.default_matmul_precision("highest")
    return nullcontext()


class OpTest(unittest.TestCase):
    @classmethod
    def setUpClass(cls):
        cls._exe = Executor(
            fluid.TPUPlace() if _PLACE == "tpu" else fluid.CPUPlace()
        )

    def run(self, result=None):
        # seed before the subclass setUp generates inputs (subclasses override
        # setUp without calling super, so seeding there would never execute)
        np.random.seed(90125)
        return super().run(result)

    def _build(self):
        main = framework.Program()
        startup = framework.Program()
        self._feed = {}
        with fluid.program_guard(main, startup):
            block = main.global_block()
            op_inputs = {}
            for slot, data in getattr(self, "inputs", {}).items():
                entries = data if isinstance(data, list) else [(slot, data)]
                names = []
                for name, arr in entries:
                    arr = np.asarray(arr)
                    block.create_var(
                        name=name,
                        shape=arr.shape,
                        dtype=framework.convert_np_dtype(arr.dtype),
                        stop_gradient=False,
                    )
                    self._feed[name] = arr
                    names.append(name)
                op_inputs[slot] = names
            op_outputs = {}
            self._expect = {}
            for slot, data in self.outputs.items():
                entries = data if isinstance(data, list) else [(slot, data)]
                names = []
                for name, arr in entries:
                    names.append(name)
                    self._expect[name] = np.asarray(arr)
                    block.create_var(name=name, shape=None, dtype=None)
                op_outputs[slot] = names
            block.append_op(
                type=self.op_type,
                inputs=op_inputs,
                outputs=op_outputs,
                attrs=getattr(self, "attrs", {}),
            )
        return main, startup

    def _crosses_mxu(self, main):
        """Whether this op's lowering contains an MXU-bearing primitive —
        decided from the traced jaxpr of the built program, so the policy
        tracks the actual lowering rather than a hand-maintained op list.
        Unlowerable/host ops default to True (the looser bar)."""
        try:
            import jax

            from paddle_tpu.executor import _CompiledBlock

            with scope_guard(Scope()):
                cb = _CompiledBlock(
                    main, main.global_block(), list(self._feed),
                    list(self._expect), Scope(),
                )
                jaxpr = jax.make_jaxpr(
                    lambda feeds, key: cb.fn(feeds, {}, {}, key)[0]
                )(
                    {n: np.asarray(v) for n, v in self._feed.items()},
                    jax.random.PRNGKey(0),
                )
            return _jaxpr_crosses_mxu(jaxpr)
        except Exception:
            return True

    def check_output(self, atol=1e-5, rtol=1e-5, no_check_set=None):
        main, _ = self._build()
        if _TOL_SCALE > 1:
            if self._crosses_mxu(main):
                # MXU ops run bf16 multiplies under default precision:
                # ~2^-8 relative per product and sqrt(K)-scaled absolute
                # cancellation noise near zero — rtol-dominant, with the
                # atol cap sized for O(1) inputs (outputs of O(0.01-0.1)
                # ops must still not pass vacuously)
                atol = min(atol * _TOL_SCALE, 2e-2)
                rtol = min(rtol * _TOL_SCALE, 2e-2)
            else:
                # f32 VPU ops: only transcendental approximation and
                # reduction order separate them from numpy
                atol = min(atol * _NON_MXU_TOL_SCALE, 1e-3)
                rtol = min(rtol * _NON_MXU_TOL_SCALE, 1e-3)
        fetch = [n for n in self._expect if n not in (no_check_set or [])]
        with scope_guard(Scope()):
            results = self._exe.run(main, feed=self._feed, fetch_list=fetch)
        for name, got in zip(fetch, results):
            want = self._expect[name]
            np.testing.assert_allclose(
                got.astype(np.float64) if got.dtype != bool else got,
                want.astype(np.float64) if want.dtype != object and want.dtype != bool else want,
                atol=atol,
                rtol=rtol,
                err_msg="output %r of op %s mismatch" % (name, self.op_type),
            )

    def _loss_program(self):
        """Scalar loss = sum over outputs of mean(out * W_fixed). The fixed
        random weighting avoids degenerate gradients (e.g. mean of softmax is
        constant, making d(loss)/dX identically zero)."""
        main, _ = self._build()
        rng = np.random.RandomState(123)
        with fluid.program_guard(main, framework.Program()):
            block = main.global_block()
            means = []
            for name in self._expect:
                v = block.var(name)
                if not framework.is_float_dtype(v.dtype):
                    continue
                w_name = name + "@LOSS_W"
                w = rng.uniform(0.1, 1.0, self._expect[name].shape).astype("float32")
                block.create_var(
                    name=w_name, shape=w.shape, dtype="float32", stop_gradient=True
                )
                self._feed[w_name] = w
                weighted = block.create_var(dtype=v.dtype)
                block.append_op(
                    type="elementwise_mul",
                    inputs={"X": [name], "Y": [w_name]},
                    outputs={"Out": [weighted.name]},
                    attrs={"axis": -1},
                )
                means.append(fluid.layers.mean(weighted))
            loss = means[0]
            for m in means[1:]:
                loss = fluid.layers.elementwise_add(loss, m)
        return main, loss

    def check_grad(
        self,
        inputs_to_check,
        output_names=None,
        max_relative_error=0.005,
        numeric_grad_delta=1e-3,
        no_grad_set=None,
    ):
        with _grad_precision_ctx():
            self._check_grad_impl(
                inputs_to_check, max_relative_error * _GRAD_TOL_SCALE,
                numeric_grad_delta, no_grad_set,
            )

    def _check_grad_impl(
        self, inputs_to_check, max_relative_error, numeric_grad_delta,
        no_grad_set,
    ):
        main, loss = self._loss_program()
        with fluid.program_guard(main, framework.Program()):
            pg = fluid.append_backward(loss, no_grad_set=no_grad_set)
        grad_names = [framework.grad_var_name(n) for n in inputs_to_check]
        with scope_guard(Scope()):
            analytic = self._exe.run(main, feed=self._feed, fetch_list=grad_names)

        # numeric: central differences on the loss program. ONE scope for the
        # whole sweep — the executor's program cache is scope-keyed, so a
        # fresh Scope per evaluation would recompile the program for every
        # perturbed element (thousands of XLA compiles for an RNN op's grad
        # check; measured as the dominant harness cost, and each compile is a
        # roll of the flaky XLA-CPU-compiler dice).
        # The loss program is stateless (inputs are fed, nothing persists),
        # so sharing the scope only shares the compiled executable.
        fwd_main, fwd_loss = self._loss_program()
        num_scope = Scope()

        def loss_at(feed):
            with scope_guard(num_scope):
                (val,) = self._exe.run(fwd_main, feed=feed, fetch_list=[fwd_loss.name])
            return float(val.reshape(()))

        for name, a_grad in zip(inputs_to_check, analytic):
            base = self._feed[name].astype(np.float64)
            num = np.zeros_like(base, dtype=np.float64)
            flat = base.reshape(-1)
            for i in range(flat.size):
                orig = flat[i]
                feed = dict(self._feed)
                pert = base.copy().reshape(-1)
                pert[i] = orig + numeric_grad_delta
                feed[name] = pert.reshape(base.shape).astype(self._feed[name].dtype)
                up = loss_at(feed)
                pert[i] = orig - numeric_grad_delta
                feed[name] = pert.reshape(base.shape).astype(self._feed[name].dtype)
                down = loss_at(feed)
                num.reshape(-1)[i] = (up - down) / (2 * numeric_grad_delta)
            abs_max = max(np.abs(num).max(), np.abs(a_grad).max(), 1e-3)
            diff = np.abs(num - a_grad.astype(np.float64)).max() / abs_max
            self.assertLessEqual(
                diff,
                max_relative_error,
                "gradient of %r for op %s: max rel err %.5f (analytic vs numeric)"
                % (name, self.op_type, diff),
            )
