"""Quantized serving tier (passes/quant.py, ops/quant_ops.py, the Pallas
quant-GEMM family, the int8 paged-KV pool): calibrated-int8 ServingEngine
output parity, quant-GEMM kernel-vs-dense parity under FLAGS_quantized_gemm,
fuse_attention substitution bit-parity and decline rules, kv-int8 generation
parity with the paged-flash kernel pinned on, quantize_static op semantics,
and int8/native variants coexisting in one
persistent compile cache across fresh processes."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import flags as pt_flags
from paddle_tpu import framework
from paddle_tpu.executor import Scope, scope_guard
from paddle_tpu.models.gpt_decoder import GPTDecoder
from paddle_tpu.passes.manager import PassManager
from paddle_tpu.serving import GenerationEngine, ServingEngine


@pytest.fixture
def restore_flags():
    keep = pt_flags.get_flags(["quantized_gemm", "paged_flash"])
    yield
    pt_flags.set_flags(keep)


def _save_fc_stack(tmp_path, d_in=256, hidden=256, classes=128, seed=7):
    main, startup = framework.Program(), framework.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="qx", shape=[d_in], dtype="float32")
        h = fluid.layers.fc(x, size=hidden, act="relu")
        y = fluid.layers.fc(h, size=classes)
    exe = fluid.Executor(fluid.CPUPlace())
    model_dir = str(tmp_path / "qmlp")
    with scope_guard(Scope(seed=seed)):
        exe.run(startup)
        fluid.io.save_inference_model(model_dir, ["qx"], [y], exe,
                                      main_program=main)
    return model_dir


def _calib(rng, d_in=256, n=4):
    return [{"qx": rng.randn(8, d_in).astype("float32")} for _ in range(n)]


# ------------------------------------------------- calibrated int8 serving


def test_int8_serving_output_parity(tmp_path):
    """The inference_int8 pipeline end to end through ServingEngine: every
    fc mul quantizes, scales freeze into the scope, and the int8 output
    tracks the fp32 engine within per-tensor-int8 tolerance."""
    rng = np.random.RandomState(0)
    model_dir = _save_fc_stack(tmp_path)
    e_f32 = ServingEngine(model_dir, name="tq_f32", cache_dir=None)
    e_i8 = ServingEngine(model_dir, name="tq_i8", cache_dir=None,
                         precision="int8", calibration_feeds=_calib(rng))
    q = e_i8.stats()["quant"]
    assert q["quantized_muls"] == 2
    assert q["weights_frozen"] == 2
    assert q["fused_groups"] == 2
    assert q["calibrated_ranges"] > 0
    assert e_i8.stats()["precision"] == "int8"
    assert e_f32.stats()["precision"] == "native"

    x = rng.randn(32, 256).astype("float32")
    (ref,) = e_f32.run({"qx": x})
    (got,) = e_i8.run({"qx": x})
    rel = np.abs(np.asarray(got) - np.asarray(ref)).max() / (
        np.abs(np.asarray(ref)).max() + 1e-9
    )
    assert rel < 0.05, rel


def test_int8_requires_calibration_feeds(tmp_path):
    with pytest.raises(ValueError):
        ServingEngine(_save_fc_stack(tmp_path), name="tq_nofeeds",
                      cache_dir=None, precision="int8")


def test_quant_gemm_kernel_parity(tmp_path, restore_flags):
    """FLAGS_quantized_gemm=on must dispatch the fused gemm_int8 Pallas
    path for the tagged chains, and the kernel output must match the dense
    per-op int8 reference (same levels math, one f32 rounding)."""
    from paddle_tpu.ops.pallas_kernels import KERNEL_DISPATCHES

    rng = np.random.RandomState(1)
    model_dir = _save_fc_stack(tmp_path)
    calib = _calib(rng)
    x = rng.randn(32, 256).astype("float32")

    pt_flags.set_flags({"quantized_gemm": "off"})
    e_dense = ServingEngine(model_dir, name="tq_dense", cache_dir=None,
                            precision="int8", calibration_feeds=calib)
    (dense,) = e_dense.run({"qx": x})

    pt_flags.set_flags({"quantized_gemm": "on"})
    e_kern = ServingEngine(model_dir, name="tq_kern", cache_dir=None,
                           precision="int8", calibration_feeds=calib)
    before = KERNEL_DISPATCHES.get("gemm_int8", 0)
    (kern,) = e_kern.run({"qx": x})
    assert KERNEL_DISPATCHES.get("gemm_int8", 0) - before == 2
    np.testing.assert_allclose(np.asarray(kern), np.asarray(dense),
                               rtol=0, atol=1e-3)


# -------------------------------------------------------- quantize_static


def test_quantize_static_op_semantics():
    """quantize_static: saturating symmetric int8 levels from a frozen
    scale; zero scale must not divide by zero; the fake_dequantize
    round-trip bounds the error at half a level."""
    from paddle_tpu.ops import registry
    from paddle_tpu.ops.registry import LowerCtx
    import jax
    import jax.numpy as jnp

    ctx = LowerCtx(jax.random.key(0), is_test=True)

    def lower(op_type, ins, attrs):
        return registry.get(op_type).lower(ctx, ins, attrs)

    x = jnp.asarray(np.linspace(-2.0, 2.0, 64, dtype=np.float32))
    scale = jnp.asarray([1.5], jnp.float32)  # absmax < x's tail: saturates
    (q,) = lower(op_type="quantize_static",
                 ins={"X": [x], "Scale": [scale]},
                 attrs={"bit_length": 8})["Out"]
    assert q.dtype == jnp.int8
    assert int(jnp.max(q)) == 127 and int(jnp.min(q)) == -127
    (dq,) = lower("fake_dequantize_max_abs",
                  {"X": [q.astype(jnp.float32)], "Scale": [scale]},
                  {"max_range": 127.0})["Out"]
    clipped = np.clip(np.asarray(x), -1.5, 1.5)
    assert np.abs(np.asarray(dq) - clipped).max() <= 1.5 / 127.0 + 1e-6

    (q0,) = lower("quantize_static",
                  {"X": [x], "Scale": [jnp.zeros((1,), jnp.float32)]},
                  {"bit_length": 8})["Out"]
    assert np.isfinite(np.asarray(q0, np.float32)).all()


# --------------------------------------------------------- fuse_attention


def _build_tiny_decoder(t=8):
    dec = GPTDecoder(vocab_size=64, d_model=32, n_head=4, n_layer=2,
                     max_context=16, prefix="tfa")
    main, startup, feeds, fetches = dec.build_forward(batch=1, t=t)
    return main, startup, feeds, fetches


def test_fuse_attention_substitution_parity():
    """The unfused matmul→mask-add→softmax→matmul chain must collapse to
    one flash_attention op per layer with bit-level output parity (same
    dense math off-TPU, one op instead of five)."""
    main, startup, feeds, fetches = _build_tiny_decoder()
    rng = np.random.RandomState(3)
    toks = rng.randint(0, 64, size=(1, 8, 1)).astype("int64")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = Scope(seed=11)
    with scope_guard(scope):
        exe.run(startup)
        (ref,) = exe.run(main, feed={feeds[0]: toks}, fetch_list=fetches)
        fused = PassManager(["fuse_attention"]).apply(
            main, scope=scope, feed_names=feeds, fetch_names=fetches,
        )
        assert fused._pass_results["fuse_attention"]["fused"] == 2
        types = [op.type for op in fused.global_block().ops]
        assert "softmax" not in types
        assert types.count("flash_attention") == 2
        (got,) = exe.run(fused, feed={feeds[0]: toks}, fetch_list=fetches)
        err = np.abs(np.asarray(got) - np.asarray(ref)).max()
        assert err < 1e-4, err

        # still fuses after constant_fold moves the mask into the scope
        folded = PassManager(["constant_fold", "fuse_attention"]).apply(
            main, scope=scope, feed_names=feeds, fetch_names=fetches,
        )
        assert folded._pass_results["fuse_attention"]["fused"] == 2
        (got2,) = exe.run(folded, feed={feeds[0]: toks}, fetch_list=fetches)
        assert np.abs(np.asarray(got2) - np.asarray(ref)).max() < 1e-4


def test_fuse_attention_declines_on_fetched_intermediate():
    """A fetched softmax output is an outside consumer: that layer's chain
    must survive unfused while the other layer still fuses."""
    main, startup, feeds, fetches = _build_tiny_decoder()
    sm_out = [op.output("Out")[0] for op in main.global_block().ops
              if op.type == "softmax"][0]
    scope = Scope(seed=11)
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        res = PassManager(["fuse_attention"]).apply(
            main, scope=scope, feed_names=feeds,
            fetch_names=list(fetches) + [sm_out],
        )
    assert res._pass_results["fuse_attention"]["fused"] == 1


# ------------------------------------------------------- int8 paged KV pool


KV_KW = dict(vocab_size=48, n_layer=2, n_head=2, d_model=16, d_inner=32,
             max_context=16)
KV_NO_EOS = 999


def _kv_engines(paged_flash=None, max_slots_f32=2, with_f32=True):
    # one prefill bucket (= max_context) keeps warmup to two compiles per
    # engine; every prompt these tests feed fits it
    if paged_flash is not None:
        pt_flags.set_flags({"paged_flash": paged_flash})
    e_f32 = None
    if with_f32:
        e_f32 = GenerationEngine(
            GPTDecoder(**KV_KW), name="tkv_f32_%s" % (paged_flash or "auto"),
            max_slots=max_slots_f32, page_size=4, cache_dir=None,
            prefill_buckets=(KV_KW["max_context"],), scope=Scope(seed=5),
        )
    e_i8 = GenerationEngine(
        GPTDecoder(kv_dtype="int8", **KV_KW),
        name="tkv_i8_%s" % (paged_flash or "auto"),
        max_slots=2 * max_slots_f32, page_size=4, cache_dir=None,
        prefill_buckets=(KV_KW["max_context"],), scope=Scope(seed=5),
    )
    return e_f32, e_i8


_KV_F32_REF = []


def _kv_f32_ref():
    """The dense fp32-pool reference engine, built once for the module: it
    is AOT-compiled at construction, so the paged_flash flag value a later
    test sets cannot re-lower it."""
    if not _KV_F32_REF:
        pt_flags.set_flags({"paged_flash": "off"})
        _KV_F32_REF.append(_kv_engines("off", with_f32=True)[0])
    return _KV_F32_REF[0]


@pytest.mark.parametrize("paged_flash, block_pages", [
    ("off", None), ("on", None),
    ("on", 1),  # the 4-page table walked a page a block, decode and chunks
])
def test_kv_int8_generation_drift_bounded(paged_flash, block_pages,
                                          restore_flags, monkeypatch):
    """int8-with-per-page-scales KV at 2x the slots in ~half the pool
    bytes: the last-step logits must track the fp32-pool engine within the
    quantization drift bound — on the dense reference AND with the paged
    flash kernel pinned on (inline dequant on the block-table walk)."""
    from paddle_tpu.ops import pallas_kernels as pk

    e_f32 = _kv_f32_ref()
    if block_pages:
        monkeypatch.setattr(pk, "_PAGED_PAGES_PER_BLOCK", block_pages)
        monkeypatch.setattr(pk, "_PAGED_PAGES_PER_BLOCK_CHUNK", block_pages)
    _, e_i8 = _kv_engines(paged_flash, with_f32=False)
    assert e_i8.pool.stats()["storage_dtype"] == "int8"
    assert e_i8.pool.stats()["resident_bytes"] < (
        0.75 * e_f32.pool.stats()["resident_bytes"]
    )
    rng = np.random.RandomState(2)
    for _ in range(2):
        L = int(rng.randint(3, 10))
        p = [int(t) for t in rng.randint(0, KV_KW["vocab_size"], size=L)]
        r32 = e_f32.generate(p, max_new_tokens=4, eos_id=KV_NO_EOS)
        l32 = e_f32.last_logits[0].copy()
        ri8 = e_i8.generate(p, max_new_tokens=4, eos_id=KV_NO_EOS)
        li8 = e_i8.last_logits[0].copy()
        assert len(r32.tokens) == len(ri8.tokens)
        drift = np.abs(l32 - li8).max() / (np.abs(l32).max() + 1e-9)
        assert drift < 0.05, drift


def test_kv_int8_write_populates_scales():
    """kv_cache_write in int8 mode: written pool rows are int8 levels with
    a nonzero per-row f32 scale; untouched rows keep the 1.0 boot default
    (the scatter only lands on the slot's block-table pages)."""
    _, e_i8 = _kv_engines(with_f32=False)
    p = [1, 2, 3, 4, 5]
    e_i8.generate(p, max_new_tokens=3, eos_id=KV_NO_EOS)
    model = e_i8.model
    wrote = 0
    for (k_name, v_name), (ks_name, vs_name) in zip(
            model.kv_pool_names(), model.kv_scale_names()):
        # decode steps donate the pool args: the live arrays are the
        # engine's mutable state, scope.vars holds the pre-donation boot
        k = np.asarray(e_i8._state[k_name])
        ks = np.asarray(e_i8._state[ks_name])
        assert k.dtype == np.int8
        assert ks.dtype == np.float32
        written = np.abs(k).max(axis=1) > 0
        wrote += int(written.sum())
        assert (ks[written] > 0).all()
        # rows the scatter never touched keep the boot default scale (1.0,
        # the zero-division guard), so the write trail is exact
        assert (ks[~written] == 1.0).all()
    assert wrote >= 2 * len(p)  # k and v rows for every cached token


# ------------------------------------- compile-cache precision coexistence

_PRECISION_BOOT = r"""
import os, json, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
import paddle_tpu.fluid as fluid
from paddle_tpu import framework
from paddle_tpu.executor import Scope, scope_guard
from paddle_tpu.serving import ServingEngine

model_dir, cache_dir, precision = sys.argv[1], sys.argv[2], sys.argv[3]
if not os.path.isdir(model_dir) or not os.listdir(model_dir):
    main, startup = framework.Program(), framework.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="cx", shape=[16], dtype="float32")
        h = fluid.layers.fc(x, size=16, act="relu")
        y = fluid.layers.fc(h, size=8)
    exe = fluid.Executor(fluid.CPUPlace())
    with scope_guard(Scope(seed=9)):
        exe.run(startup)
        fluid.io.save_inference_model(model_dir, ["cx"], [y], exe,
                                      main_program=main)
rng = np.random.RandomState(0)
kw = {}
if precision == "int8":
    kw = dict(precision="int8", calibration_feeds=[
        {"cx": rng.randn(4, 16).astype("float32")} for _ in range(2)])
eng = ServingEngine(model_dir, name="coex", cache_dir=cache_dir,
                    batch_buckets=(2, 4), **kw)
eng.warmup()
(out,) = eng.run({"cx": np.ones((2, 16), "float32")})
print(json.dumps({"traces": eng.traces, "cache_hits": eng.cache_hits,
                  "out0": float(np.asarray(out).ravel()[0])}))
"""


@pytest.mark.slow
def test_int8_and_native_share_cache_without_collisions(tmp_path):
    """int8 and native variants of the SAME model in the SAME persistent
    compile cache: each precision traces its own variants on first boot
    (distinct keys — the precision geometry), each re-boot is all hits,
    and neither boot ever replays the other's executables (the int8 boot
    after a native warm cache still traces)."""
    model_dir = str(tmp_path / "coex_model")
    cache = str(tmp_path / "coex_cache")
    os.makedirs(model_dir, exist_ok=True)
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    def boot(precision):
        out = subprocess.run(
            [sys.executable, "-c", _PRECISION_BOOT, model_dir, cache,
             precision],
            capture_output=True, text=True, env=env, timeout=600,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    native1 = boot("native")
    assert native1["traces"] == 2 and native1["cache_hits"] == 0
    int8_1 = boot("int8")  # warm native cache must NOT serve int8 keys
    assert int8_1["traces"] == 2 and int8_1["cache_hits"] == 0
    native2 = boot("native")
    assert native2["traces"] == 0 and native2["cache_hits"] == 2
    int8_2 = boot("int8")
    assert int8_2["traces"] == 0 and int8_2["cache_hits"] == 2
    # both precisions compute the model, not each other's artifacts
    assert native2["out0"] == native1["out0"]
    assert int8_2["out0"] == int8_1["out0"]
    assert native1["out0"] != int8_1["out0"]
