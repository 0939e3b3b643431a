"""chip_smoke.py's phases at toy width on the CPU (Pallas in interpret mode),
the placement of the compile cache, and the refusals that keep a missing chip
from passing: only chip_smoke.main() and TPUPlace insist on a TPU."""

import json
import os
import subprocess
import sys

import pytest

import chip_smoke
import paddle_tpu.fluid as fluid
from paddle_tpu import flags

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_train_phase_toy_width():
    """The whole train phase — unfused reference step, fused steps, one
    multi-step call, every check but the tpu_custom_call ones — at the
    smallest width the fused kernels' tile predicates accept."""
    facts = chip_smoke.phase_train(
        dict(b=1, t=128, d=128, n_layer=1, vocab=128), n_steps=2, multistep=2
    )
    assert facts["loss_last"] < facts["loss_first"]
    # one fused GEMM tile body off the chip; the optimizer is XLA's own;
    # flash's tier at 16 heads of 8: sixteen a 128-lane block
    assert set(facts["dispatches"]) == {
        "gemm_epilogue", "layer_norm", "layer_norm_grad",
        "flash_heads_a_block:16"}
    assert facts["custom_calls"] is None  # interpreted: nothing to find


def test_serve_phase_toy_width():
    saved = flags.get_flags("paged_flash")
    flags.set_flags({"paged_flash": "on"})  # "auto" is the dense gather off-TPU
    try:
        facts = chip_smoke.phase_serve(dict(
            model=dict(vocab_size=128, n_layer=1, n_head=2, d_model=128,
                       d_inner=256, max_context=128),
            engine=dict(max_slots=4, prefill_chunk=4),
            prompt_lens=(60, 40, 100, 5, 40, 40),
            shared_prefix=32,
            new_tokens=4,
        ))
    finally:
        flags.set_flags(saved)
    assert facts["variants"] == 3  # decode + chunks of 2, 4
    assert facts["prefix_pages_hit"] >= 4
    assert facts["first_token_logits_max_abs_err"] < 1e-4


def test_smoke_failure_is_not_swallowed():
    with pytest.raises(chip_smoke.SmokeFailure, match="no flash"):
        chip_smoke.check(False, "no flash")
    hlo = (
        '%a = f32[8] custom-call(%x), custom_call_target="tpu_custom_call", '
        'metadata={op_name="jit(run)/pallas_kernel=layer_norm_grad.lng3/pallas_call"}\n'
        '%b = f32[8] custom-call(%x), custom_call_target="tpu_custom_call", '
        'metadata={op_name="jit(run)/flash_attention/out=y/pallas_call"}\n'
        '%c = f32[8] custom-call(%x), custom_call_target="Sharding"\n'
    )
    assert chip_smoke.custom_call_families(hlo) == {
        "layer_norm_grad": 1, "flash_attention": 1,
    }


def test_main_refuses_a_cpu_backend(capsys):
    """JAX_PLATFORMS=cpu (the sandbox): non-zero, no result line, nothing
    built."""
    assert chip_smoke.main([]) == 2
    out = capsys.readouterr()
    assert out.out == ""
    assert "needs a TPU" in out.err and "cpu" in out.err


def test_result_line_holds_ok_and_device_and_nothing_else():
    import jax

    line = chip_smoke.result_line(True, jax.devices())
    assert "\n" not in line
    got = json.loads(line)
    assert got == {"ok": True, "device": {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices()),
    }}
    assert type(got["device"]["count"]) is int


def test_tpu_place_raises_without_a_tpu():
    with pytest.raises(RuntimeError, match="default backend is 'cpu'"):
        fluid.TPUPlace().jax_device()
    with pytest.raises(RuntimeError, match="needs a TPU"):
        fluid.Executor(fluid.TPUPlace())
    with pytest.raises(RuntimeError, match="needs a TPU"):
        fluid.Executor(fluid.CUDAPlace(0))
    fluid.Executor(fluid.CPUPlace())  # pins nothing, raises nothing


_IMPORT_AND_COMPILE = """
import jax, jax.numpy as jnp
import paddle_tpu
# module-level jnp values would freeze the platform before the CPU bootstrap
# can run (regression: detection_ops NEG, Scope.rng_key)
from jax._src import xla_bridge as xb
assert not xb._backends, "backend initialized at import: %r" % xb._backends
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
print(jax.jit(lambda x: jnp.tanh(x) @ x.T)(jnp.ones((64, 64))).sum())
print("CACHE_DIR", jax.config.jax_compilation_cache_dir)
"""


def _import_and_compile(checkout, cache_env):
    """Start a fresh process that imports the package and compiles one small
    function, from a scratch checkout that holds nothing but (a link to) the
    package, so the default cache directory is the scratch checkout's own."""
    checkout.mkdir()
    os.symlink(os.path.join(_ROOT, "paddle_tpu"), checkout / "paddle_tpu")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(cache_env, JAX_PLATFORMS="cpu")
    return subprocess.Popen(
        [sys.executable, "-c", _IMPORT_AND_COMPILE], cwd=str(checkout),
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )


def test_import_places_the_compile_cache_and_no_backend(tmp_path):
    """Importing the package initializes no backend and decides the compile
    cache once: with JAX_COMPILATION_CACHE_DIR set the program sets no
    directory of its own and writes nowhere else; without it entries land in
    <checkout>/.jax_cache."""
    outside = tmp_path / "outside"
    placed, default = tmp_path / "placed", tmp_path / "default"
    children = [
        _import_and_compile(placed, {"JAX_COMPILATION_CACHE_DIR": str(outside)}),
        _import_and_compile(default, {}),
    ]
    outs = []
    for child in children:
        out, err = child.communicate(timeout=300)
        assert child.returncode == 0, err[-2000:]
        outs.append(out)
    assert "CACHE_DIR %s" % outside in outs[0]
    assert os.listdir(outside)
    assert not (placed / ".jax_cache").exists()
    assert "CACHE_DIR %s" % (default / ".jax_cache") in outs[1]
    assert os.listdir(default / ".jax_cache")
