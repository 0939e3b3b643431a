"""Every Pallas family, and the one-layer training_fused step, lowered for
platforms=["tpu"] through jax.export with interpret=False — on the CPU, with
no chip. Lowering runs the Pallas→Mosaic translation, so it refuses what the
chip would refuse first: a primitive Mosaic lacks (dynamic_slice on a value,
erf), a block whose last two dimensions are illegal, a vector read out of a
scalar-prefetch ref. It says nothing about Mosaic's own compile (VMEM, tile
alignment) or about speed; chip_smoke.py's kernels phase covers those on the
chip. This is the check that would have caught, without a chip, the three
refusals PR 21 repaired.

The kernels pick interpret mode from jax.default_backend(); the tests pin that
to "tpu" for the duration of a lowering, which also makes every `auto` flag
choose what it would choose on the chip.
"""

import collections
import re

import pytest

import jax
import jax.numpy as jnp
from jax import export

from paddle_tpu import flags
from paddle_tpu.ops import pallas_kernels as pk

bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32


def S(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


@pytest.fixture
def as_on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def lower_for_tpu(fn, *avals):
    """The exported module's text; raises what the TPU lowering raises."""
    text = export.export(jax.jit(fn), platforms=["tpu"])(*avals).mlir_module()
    assert "tpu_custom_call" in text, "lowered without a Mosaic kernel"
    return text


def _flash(causal):
    return lambda q, k, v: pk.flash_attention(q, k, v, causal)


def _flash_btf(causal, n_head, grad=False):
    """Over [b, t, h·d] operands, the heads side by side on the last axis."""
    def fwd(q, k, v):
        return pk.flash_attention(q, k, v, causal, None, None, None, None, n_head)

    def loss(q, k, v):
        return fwd(q, k, v).astype(f32).sum()

    return jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd


def _flash_grad(causal):
    def loss(q, k, v):
        return pk.flash_attention(q, k, v, causal).astype(f32).sum()

    return jax.grad(loss, argnums=(0, 1, 2))


_PAGED = dict(n_head=2, d=64, page=8, pages=4, pool_rows=72)
# gpt2l_chat's own geometry: 24 slots x a 128-page table, 20 heads of 64
_PAGED_CELL = dict(n_head=20, d=64, page=8, pages=128, pool_rows=24584)
# lfm2moe_chat's: 32 slots x a 128-page table of 16-row pages, 32 query heads
# of 64 on 8 KV heads, bf16 pools 512 wide
_PAGED_GQA = dict(n_head=32, n_kv=8, d=64, page=16, pages=128,
                  pool_rows=65552, dtype=bf16)


def _paged(quant, per_row, rows, g=_PAGED):
    feat = g["n_head"] * g["d"]
    dtype = g.get("dtype", f32)

    def fn(q, kp, vp, bt, pos, *scales):
        return pk.paged_flash_attention(
            q, kp, vp, bt, pos, n_head=g["n_head"], page_size=g["page"],
            k_scales=scales[0] if quant else None,
            v_scales=scales[1] if quant else None, n_kv_head=g.get("n_kv"),
        )

    pool = S((g["pool_rows"], g.get("n_kv", g["n_head"]) * g["d"]),
             jnp.int8 if quant else dtype)
    avals = [
        S((rows, feat), dtype), pool, pool,
        S((rows, g["pages"]) if per_row else (g["pages"],), i32),
        S((rows,), i32),
    ]
    if quant:
        avals += [S((g["pool_rows"],), f32)] * 2
    return fn, avals


# xing4_docs_chat's: 32 slots x a 200-page table of 64-row pages, 32 query
# heads over one latent pool 640 wide (512 + 64 + 64 zeros), bf16, the value
# its first 512 columns
_LATENT_CELL = dict(n_head=32, width=640, d_value=512, page=64, pages=200,
                    pool_rows=196672, dtype=bf16)
_LATENT = dict(n_head=8, width=128, d_value=64, page=16, pages=6,
               pool_rows=112, dtype=f32)


def _latent(per_row, rows, g):
    def fn(q, pool, bt, pos):
        return pk.latent_paged_attention(
            q, pool, bt, pos, n_head=g["n_head"], page_size=g["page"],
            d_value=g["d_value"], sm_scale=0.1)

    return fn, [
        S((rows, g["n_head"] * g["width"]), g["dtype"]),
        S((g["pool_rows"], g["width"]), g["dtype"]),
        S((rows, g["pages"]) if per_row else (g["pages"],), i32),
        S((rows,), i32),
    ]


def _dsa_index(per_row, rows, pages, pool_rows, heads=32, width=128, page=64):
    """glm52_longdocs' index scan: 32 heads of 128 over a bf16 index-key pool
    of 64-row pages, a 1026-page table; decode rows and a 256-row chunk."""
    def fn(q, w, pool, bt, pos):
        return pk.dsa_index_scores(q, w, pool, bt, pos, heads=heads,
                                   page_size=page)

    return fn, [
        S((rows, heads * width), bf16), S((rows, heads), f32),
        S((pool_rows, width), bf16),
        S((rows, pages) if per_row else (pages,), i32), S((rows,), i32),
    ]


def _ssm_step(slots, hp, n):
    """granite4h_burst_chat's decode step at slots=48, hp=4096, n=128."""
    return (lambda pool, rows, xdt, decay, b, c:
            pk.ssm_state_step(pool, rows, xdt, decay, b, c)), [
        S((slots + 1, n, hp), f32), S((slots,), i32), S((slots, hp), f32),
        S((slots, hp), f32), S((slots, n), f32), S((slots, n), f32)]


def _gemm(act, dbuf):
    return (
        lambda x, w, b: pk.gemm_bias_act(x, w, b, act),
        [S((256, 512), bf16), S((512, 256), bf16), S((1, 256), bf16)],
        {"gemm_double_buffer": dbuf},
    )


def _quant_gemm(dtype, m):
    return (
        lambda x, w, b: pk.quant_gemm_bias_act(x, w, 0.01, b, "relu"),
        [S((m, 256), dtype), S((256, 128), dtype), S((1, 128), f32)],
        {"quantized_gemm": "on"},
    )


def _moe_grouped(sorted_rows, d=2048, f=1792, held=32):
    """lfm2moe_chat's expert layer: 32 experts of 2048 x 1792, bf16; a decode
    step brings 32 rows x 4 picks, a prefill chunk 128 x 4."""
    return (
        pk.grouped_expert_ffn,
        [S((sorted_rows, d), bf16), S((held, d, f), bf16),
         S((held, d, f), bf16), S((held, f, d), bf16), S((held,), i32)],
    )


_LN = [S((256, 512), bf16), S((512,), f32), S((256,), f32)]  # x, scale, stat

# family -> (function, avals[, flags to lower it under])
FAMILIES = {
    "flash": (_flash(False), [S((2, 2, 256, 64), bf16)] * 3),
    "flash causal": (_flash(True), [S((2, 2, 256, 64), bf16)] * 3),
    "flash_grad": (_flash_grad(False), [S((2, 2, 256, 64), bf16)] * 3),
    "flash_grad causal": (_flash_grad(True), [S((2, 2, 256, 64), bf16)] * 3),
    # [b, t, h·d]: two 64-wide heads a 128-lane block, one 128-wide head,
    # cross attention (tq != tk), heads that have to be split first (d 96)
    "flash two heads a block": (_flash_btf(False, 4), [S((2, 256, 256), bf16)] * 3),
    "flash two heads a block causal": (_flash_btf(True, 4), [S((2, 256, 256), bf16)] * 3),
    "flash_grad two heads a block": (
        _flash_btf(False, 4, grad=True), [S((2, 256, 256), bf16)] * 3),
    "flash_grad two heads a block causal": (
        _flash_btf(True, 4, grad=True), [S((2, 256, 256), bf16)] * 3),
    "flash_grad one head a block causal": (
        _flash_btf(True, 2, grad=True), [S((2, 256, 256), bf16)] * 3),
    "flash_grad two heads a block cross": (
        _flash_btf(False, 2, grad=True),
        [S((2, 128, 128), bf16), S((2, 384, 128), bf16), S((2, 384, 128), bf16)]),
    "flash_grad split heads": (
        _flash_btf(True, 2, grad=True), [S((2, 128, 192), bf16)] * 3),
    "flash_grad two heads a block streamed": (
        _flash_btf(True, 2, grad=True), [S((1, 16384, 128), bf16)] * 3),
    # the long-context tier takes over past ~8k tokens at d 128
    "flash streamed": (_flash(True), [S((1, 1, 16384, 128), bf16)] * 3),
    "flash_grad streamed": (_flash_grad(True), [S((1, 1, 16384, 128), bf16)] * 3),
    "gemm_epilogue": _gemm(None, "off"),
    "gemm_epilogue relu": _gemm("relu", "off"),
    "gemm_epilogue tanh": _gemm("tanh", "off"),
    "gemm_epilogue sigmoid": _gemm("sigmoid", "off"),
    "gemm_dbuf": _gemm(None, "on"),
    "gemm_dbuf tanh": _gemm("tanh", "on"),
    "gemm_int8 m=64": _quant_gemm(jnp.int8, 64),
    "gemm_int8 m=1024": _quant_gemm(jnp.int8, 1024),
    "gemm_fp8 m=64": _quant_gemm(jnp.float8_e4m3fn, 64),
    "gemm_fp8 m=1024": _quant_gemm(jnp.float8_e4m3fn, 1024),
    "layer_norm": (
        lambda x, s, b: pk.fused_layer_norm(x, None, s, b, 1e-5),
        [_LN[0], _LN[1], _LN[1]],
    ),
    "layer_norm residual": (
        lambda x, r, s, b: pk.fused_layer_norm(x, r, s, b, 1e-5),
        [_LN[0], _LN[0], _LN[1], _LN[1]],
    ),
    "layer_norm_grad": (
        lambda x, s, m, v, dy: pk.fused_layer_norm_grad(x, s, m, v, dy, 1e-5),
        [_LN[0], _LN[1], _LN[2], _LN[2], _LN[0]],
    ),
    "paged_flash decode": _paged(False, True, 4),
    "paged_flash shared": _paged(False, False, 8),
    "paged_flash shared 2 rows": _paged(False, False, 2),
    "paged_flash_int8 decode": _paged(True, True, 4),
    "paged_flash_int8 shared": _paged(True, False, 8),
    "paged_flash decode gpt2l_chat": _paged(False, True, 24, _PAGED_CELL),
    "paged_flash shared gpt2l_chat": _paged(False, False, 32, _PAGED_CELL),
    "paged_flash_int8 decode gpt2l_chat": _paged(True, True, 24, _PAGED_CELL),
    "paged_flash_int8 shared gpt2l_chat": _paged(True, False, 32, _PAGED_CELL),
    "paged_flash gqa bf16 decode lfm2moe_chat": _paged(False, True, 32, _PAGED_GQA),
    "paged_flash gqa bf16 shared lfm2moe_chat": _paged(False, False, 128, _PAGED_GQA),
    "hyper_maps decode xing4_docs_chat": (
        lambda raw: pk.hyper_maps(raw, 4, 20, 1e-6, (-30.0, 30.0)),
        [S((32, 24), f32)]),
    "hyper_maps chunk xing4_docs_chat": (
        lambda raw: pk.hyper_maps(raw, 4, 20, 1e-6, (-30.0, 30.0)),
        [S((256, 24), f32)]),
    "paged_latent decode": _latent(True, 3, _LATENT),
    "paged_latent shared": _latent(False, 8, _LATENT),
    "paged_latent bf16 decode xing4_docs_chat": _latent(True, 32, _LATENT_CELL),
    "paged_latent bf16 shared 256 xing4_docs_chat": _latent(False, 256, _LATENT_CELL),
    "paged_latent bf16 shared 32 xing4_docs_chat": _latent(False, 32, _LATENT_CELL),
    "dsa_index decode glm52_longdocs": _dsa_index(True, 32, 1026, 467008),
    "dsa_index shared 256 glm52_longdocs": _dsa_index(False, 256, 1026, 467008),
    "dsa_index shared 64 glm52_longdocs": _dsa_index(False, 64, 1026, 467008),
    "ssm_step toy": _ssm_step(4, 128, 16),
    "ssm_step granite4h_burst_chat": _ssm_step(48, 4096, 128),
    "moe_grouped decode lfm2moe_chat": _moe_grouped(128),
    "moe_grouped chunk lfm2moe_chat": _moe_grouped(512),
    "moe_grouped two row tiles": _moe_grouped(640, d=256, f=384, held=4),
}

# family -> the lowering's own message, for a family `auto` no longer selects
# on a TPU because it cannot be made to lower without a redesign. Empty: every
# family lowers (and compiles: chip_smoke.py, CHANGES.md PR 21).
WITHDRAWN = {}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_family_lowers_for_tpu(family, as_on_tpu):
    fn, avals, *under = FAMILIES[family]
    saved = flags.get_flags(["gemm_double_buffer", "quantized_gemm"])
    try:
        flags.set_flags(under[0] if under else {})
        if family in WITHDRAWN:
            with pytest.raises(Exception, match=WITHDRAWN[family]):
                lower_for_tpu(fn, *avals)
        else:
            lower_for_tpu(fn, *avals)
    finally:
        flags.set_flags(saved)


def test_exact_gelu_is_declined_at_tagging():
    """No erf in the Pallas TPU lowering: the kernel has no gelu epilogue and
    fuse_gemm_epilogue tags only the GEMM+bias in front of one."""
    from paddle_tpu.passes import builtin

    assert "gelu" not in pk._GEMM_ACT_F32
    assert set(builtin._PALLAS_GEMM_ACTS) == set(pk._GEMM_ACT_F32)


def test_paged_auto_declines_a_page_that_is_not_whole_tiles(as_on_tpu):
    assert pk.paged_flash_path_taken(8, 128, 8, 12, 64)
    assert pk.paged_flash_path_taken(8, 32, 32, 12, 64)
    assert not pk.paged_flash_path_taken(8, 128, 4, 12, 64)
    assert not pk.paged_flash_path_taken(8, 128, 12, 12, 64)


@pytest.fixture(scope="module")
def fused_step():
    """The one-layer chip_smoke transformer step under training_fused,
    exported for the TPU: (the module's text, KERNEL_DISPATCHES of its
    trace)."""
    import chip_smoke
    import paddle_tpu.fluid as fluid
    from paddle_tpu.executor import (
        Scope, _apply_pass_pipeline, _CompiledBlock, scope_guard,
    )
    from paddle_tpu.transpiler.bf16_transpiler import Bf16Transpiler

    main, startup, feed, loss = chip_smoke.build_transformer(
        b=1, t=128, d=128, n_layer=1, vocab=128, moment_dtype="bfloat16"
    )
    scope = Scope(seed=0)
    with scope_guard(scope), pytest.MonkeyPatch.context() as patch:
        fluid.Executor().run(startup)  # on the CPU: the scope's avals
        Bf16Transpiler().transpile(main)
        patch.setattr(jax, "default_backend", lambda: "tpu")
        feeds = list(feed)
        prog = _apply_pass_pipeline(
            main, scope, feeds, [loss.name], pipeline="training_fused"
        )
        pk.KERNEL_DISPATCHES.clear()
        block = _CompiledBlock(prog, prog.global_block(), feeds, [loss.name], scope)
        aval = lambda a: S(a.shape, a.dtype)
        text = export.export(block.jitted, platforms=["tpu"])(
            {n: aval(a) for n, a in feed.items()},
            {n: aval(scope.vars[n]) for n in block.ro_names},
            {n: aval(scope.vars[n]) for n in block.mut_names},
            aval(scope.rng_key),
        ).mlir_module()
        return text, dict(pk.KERNEL_DISPATCHES)


def test_training_fused_step_lowers_for_tpu(fused_step):
    """Every fused family and both flash directions in one module."""
    text, dispatches = fused_step
    for fam in ("gemm_dbuf", "gemm_epilogue", "layer_norm", "layer_norm_grad"):
        assert dispatches.get(fam, 0) > 0, dispatches
    # 3 flash forward + 3 backward, 4 GEMMs, 5 + 5 layer norms
    assert text.count("@tpu_custom_call") == 20, text.count("@tpu_custom_call")


def test_training_fused_step_leaves_the_optimizer_to_xla(fused_step):
    """Each custom call sits under the scope its lowering ran in
    (registry.lower_ops: "jit(run)/<scope>/.../pallas_call"): flash, the
    fused GEMM and the layer norms, and none under an adam op, whose update
    is in the module as plain XLA."""
    text, _ = fused_step
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    scopes = [
        locs[ref].split("/")[1].split(".")[0]
        for ref in re.findall(
            r"@tpu_custom_call.*loc\((#loc\d+)\)$", text, re.M)
    ]
    assert collections.Counter(scopes) == {
        "flash_attention": 3, "flash_attention_grad": 3,
        "pallas_kernel=gemm_epilogue": 4, "pallas_kernel=layer_norm": 5,
        "pallas_kernel=layer_norm_grad": 5,
    }
    assert any(name.startswith("jit(run)/adam/") for name in locs.values())
