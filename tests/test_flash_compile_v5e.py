"""The three flash kernels (multi-head at D = 128, grouped-query 32 over 8
at D = 64 with a softmax scale of its own, and latent attention's 192-wide q
and k against a 128-wide v), the two grouped-product kernels (all of 64
experts, and a share of 16 whose groups do not fill the row buffer), the
expert layer of such a share whole (its loops over the live windows),
the chunked state-space scan's two kernels, the gated delta rule's two with
the four of its mixer's fused passes (L2 norms + log-decay, head norm then
gate), the Mamba-2 mixer's four fused kernels (convolution + silu, gated
norm), and the chunked loss's loop at three cells' heads (what its body
writes, read off the compiled module)
COMPILED for a
described v5e at the benchmark cells' shapes (no chip attached, nothing runs): what interpret mode cannot
see — scoped VMEM, tiling and layout faults of a kernel edit — is refused
here, on the CPU, by the TPU's own compiler.

The topology is described inside a module fixture, never at import: only
one process may load the TPU's library, and every xdist worker imports
every test file. Keep these tests in this one file.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.common.device_names import (FLASH_BWD_DKV, FLASH_BWD_DQ,
                                             FLASH_FWD, MOE_EXPERTS_GMM,
                                             MOE_EXPERTS_TGMM)
from horovod_tpu.ops import grouped_matmul as gm
from horovod_tpu.ops.flash_attention import (DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q,
                                             flash_attention)

# (B, T, H, D) of lm217m_long_1chip and lm217m_short_1chip (bf16 activations);
# olmoe_seq4096_1chip's four q blocks; and three q blocks, no cell's: the
# folded causal-dense grid's odd case (the middle block's row, half of it
# dead, its index maps clamped and its steps' predicates dynamic)
CELL_SHAPES = {"long": (1, 16384, 8, 128), "short": (16, 1024, 8, 128),
               "olmoe": (4, 4096, 16, 128), "odd": (1, 3072, 8, 128)}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler on this machine
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one: keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _flash(q, k, v):
    return flash_attention(q, k, v, True, DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)


def _forward_backward(q, k, v):
    return jax.grad(lambda *a: jnp.sum(_flash(*a).astype(jnp.float32)),
                    argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
@pytest.mark.parametrize("fn,kernels", [
    (_flash, (FLASH_FWD,)),
    (_forward_backward, (FLASH_FWD, FLASH_BWD_DQ, FLASH_BWD_DKV)),
], ids=["forward", "forward_backward"])
def test_kernels_compile_for_v5e(one_chip, no_persistent_cache, cell, fn,
                                 kernels):
    x = jax.ShapeDtypeStruct(CELL_SHAPES[cell], jnp.bfloat16,
                             sharding=one_chip)
    text = jax.jit(fn).lower(x, x, x).compile().as_text()
    assert "tpu_custom_call" in text
    for name in kernels:
        assert name in text, f"{name} is not in the compiled module"


def _swiglu_grads_compiled(one_chip, rows, dim, width, experts, dtype,
                           precision=None, weights=None):
    """(compiled gradients of ``sum(gmm(gmm(x, w_gate), w_down))`` w.r.t. all
    three, their shapes) at ``rows`` rows of ``dtype`` through ``experts``
    experts of ``dim x width`` whose weights are ``weights`` (default: the
    rows' dtype): both kernels are in the module and no ``ragged_dot``."""
    def swiglu_grads(x, w_gate, w_down, sizes):
        plan = gm.grouped_plan(sizes, rows, gm.row_tile(x.dtype.itemsize))

        def loss(x, w_gate, w_down):
            h = gm.grouped_matmul(x, w_gate, plan)
            return jnp.sum(gm.grouped_matmul(h, w_down, plan)
                           .astype(jnp.float32))

        return jax.grad(loss, argnums=(0, 1, 2))(x, w_gate, w_down)

    def shape(*dims, of):
        return jax.ShapeDtypeStruct(dims, of, sharding=one_chip)

    weights = weights or dtype
    args = (shape(rows, dim, of=dtype), shape(experts, dim, width, of=weights),
            shape(experts, width, dim, of=weights),
            shape(experts, of=jnp.int32))
    assert gm.takes_kernel(*args[:2])
    with jax.default_matmul_precision(precision):
        compiled = jax.jit(swiglu_grads).lower(*args).compile()
    text = compiled.as_text()
    for name in (MOE_EXPERTS_GMM, MOE_EXPERTS_TGMM):
        assert name in text, f"{name} is not in the compiled module"
    assert "ragged" not in text
    return text, jax.eval_shape(swiglu_grads, *args)


# olmoe_seq4096_1chip: 16,384 tokens x top-8 rows through 64 experts of
# 2048 x 1024 in the step (bf16); one row of 4096 tokens in the check's two
# legs (bf16 as trained, f32 traced under "highest").
@pytest.mark.parametrize("rows,dtype,precision", [
    (131072, jnp.bfloat16, None),
    (32768, jnp.bfloat16, None),
    (32768, jnp.float32, "highest"),
], ids=["step_bf16", "check_bf16", "check_f32_highest"])
def test_grouped_kernels_compile_for_v5e(one_chip, no_persistent_cache, rows,
                                         dtype, precision):
    _swiglu_grads_compiled(one_chip, rows, 2048, 1024, 64, dtype, precision)


# granite4h_long_1chip: 32 query heads over 8 key/value heads of 64 at 16,384
# tokens, scores scaled by attention_multiplier (not 64 ** -0.5): the
# grouped-query index maps and the lane slice of the row statistics below 128.
GQA = dict(t=16384, heads=32, kv_heads=8, head_dim=64, scale=0.015625)


@pytest.mark.parametrize("backward", [False, True],
                         ids=["forward", "forward_backward"])
def test_grouped_query_kernels_at_head_size_64_compile_for_v5e(
        one_chip, no_persistent_cache, backward):
    def flash(q, k, v):
        return flash_attention(q, k, v, True, DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K,
                               False, GQA["scale"])

    def grads(q, k, v):
        return jax.grad(lambda *a: jnp.sum(flash(*a).astype(jnp.float32)),
                        argnums=(0, 1, 2))(q, k, v)

    def shape(heads):
        return jax.ShapeDtypeStruct((1, GQA["t"], heads, GQA["head_dim"]),
                                    jnp.bfloat16, sharding=one_chip)

    q, kv = shape(GQA["heads"]), shape(GQA["kv_heads"])
    text = jax.jit(grads if backward else flash).lower(q, kv, kv).compile().as_text()
    for name in ((FLASH_FWD, FLASH_BWD_DQ, FLASH_BWD_DKV) if backward
                 else (FLASH_FWD,)):
        assert name in text, f"{name} is not in the compiled module"


SCAN_SHAPES = {     # (b, T, heads, head_dim, state, chunk) of the two Mamba cells
    "granite": (1, 16384, 64, 64, 128, 256),
    "nemotron": (2, 8192, 16, 64, 128, 128),
}


@pytest.mark.parametrize("dtype,precision", [(jnp.bfloat16, None),
                                             (jnp.float32, "highest")],
                         ids=["bf16", "f32_highest"])
@pytest.mark.parametrize("cell", sorted(SCAN_SHAPES))
def test_the_scan_compiles_for_v5e_at_the_cells_shape(one_chip,
                                                      no_persistent_cache,
                                                      cell, dtype, precision):
    """(16384, 64 heads x 64, state 128) at chunk 256 and (2 x 8192, 16 heads
    x 64, state 128) at chunk 128, forward and backward, as trained and as the
    configurations' own scan checks run it (float32 ``u``, ``B``, ``C`` under
    ``highest``: blocks twice as large): the two kernels of ``ops/ssd.py``
    under their 100 MiB of VMEM, and beside them the carried states of a
    row's chunks alone (128 MiB in float32 at granite's shape), nothing sized
    heads x chunk x chunk."""
    from horovod_tpu.common.device_names import SSD_SCAN
    from horovod_tpu.ops.ssd import ssd, takes_kernel

    b, t, h, p, n, chunk = SCAN_SHAPES[cell]

    def grads(u, dt, A, B, C, D):
        return jax.grad(lambda *a: jnp.sum(ssd(*a, chunk).astype(jnp.float32)),
                        argnums=(0, 1, 2, 3, 4, 5))(u, dt, A, B, C, D)

    def shape(*dims, of=jnp.float32):
        return jax.ShapeDtypeStruct(dims, of, sharding=one_chip)

    args = (shape(b, t, h, p, of=dtype), shape(b, t, h), shape(h),
            shape(b, t, 1, n, of=dtype), shape(b, t, 1, n, of=dtype), shape(h))
    assert takes_kernel(args[0], args[1], args[3], args[4], chunk)
    with jax.default_matmul_precision(precision):
        compiled = jax.jit(grads).lower(*args).compile()
    text = compiled.as_text()
    for name in (SSD_SCAN + "_fwd", SSD_SCAN + "_bwd"):
        assert name in text, f"{name} is not in the compiled module"
    assert " while(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("rows,dtype", [(16384, jnp.bfloat16),
                                        (1024, jnp.float32)],
                         ids=["cell_bf16", "check_f32"])
@pytest.mark.parametrize("chain", ["conv_silu", "gate_norm"])
def test_the_mixers_fused_kernels_compile_for_v5e(one_chip,
                                                  no_persistent_cache, chain,
                                                  rows, dtype):
    """granite4h_long_1chip's mixer: the convolution + silu over (16384,
    4352 channels, 4 taps) and the gated norm over (16384, 4096), forward and
    backward, as trained and at the 1024 rows of the check's float32 leg
    (``ops/mamba_fused.py``; blocks of 4.25 MiB under a 64 MiB VMEM limit)."""
    from horovod_tpu.common.device_names import (MAMBA_CONV_BWD,
                                                 MAMBA_CONV_FWD,
                                                 MAMBA_GATE_NORM_BWD,
                                                 MAMBA_GATE_NORM_FWD)
    from horovod_tpu.ops import mamba_fused

    def shape(*dims, of=jnp.float32):
        return jax.ShapeDtypeStruct(dims, of, sharding=one_chip)

    if chain == "conv_silu":
        def fused(x, kernel, bias):
            return mamba_fused.conv_silu(x, kernel, bias)

        args = (shape(1, rows, 4352, of=dtype), shape(4, 4352), shape(4352))
        kernels = (MAMBA_CONV_FWD, MAMBA_CONV_BWD)
        assert mamba_fused.conv_takes_kernel(*args[:2])
    else:
        def fused(y, z, scale):
            return mamba_fused.gate_norm(y, z, scale, 1, 1e-5)

        args = (shape(1, rows, 4096, of=dtype),) * 2 + (shape(4096),)
        kernels = (MAMBA_GATE_NORM_FWD, MAMBA_GATE_NORM_BWD)
        assert mamba_fused.norm_takes_kernel(*args[:2], 1)

    def value_and_grads(*a):    # the value too: a forward nobody reads is cut
        out, vjp = jax.vjp(fused, *a)
        return out, vjp(out)

    text = jax.jit(value_and_grads).lower(*args).compile().as_text()
    for name in kernels:
        assert name in text, f"{name} is not in the compiled module"


@pytest.mark.parametrize("rows,dtype", [(8192, jnp.bfloat16),
                                        (2048, jnp.float32)],
                         ids=["cell_bf16", "check_f32"])
def test_the_gated_convolutions_kernels_compile_for_v5e(one_chip,
                                                        no_persistent_cache,
                                                        rows, dtype):
    """lfm2_seq8192_1chip's mixer: ``C * conv(B * X)`` over (2, 8192, 3 x
    2048) with 3 taps, forward and backward, as trained and at the 2,048 rows
    of the check's float32 leg (``ops/mamba_fused.py`` ``gated_conv``: blocks
    of 6 MiB, 28 MiB held by the backward under a 64 MiB VMEM limit)."""
    from horovod_tpu.common.device_names import (SCONV_CONV_BWD,
                                                 SCONV_CONV_FWD)
    from horovod_tpu.ops import mamba_fused

    args = (jax.ShapeDtypeStruct((2, rows, 6144), dtype, sharding=one_chip),
            jax.ShapeDtypeStruct((3, 2048), jnp.float32, sharding=one_chip))
    assert mamba_fused.gated_conv_takes_kernel(*args)

    def value_and_grads(bcx, kernel):   # the value too: see above
        out, vjp = jax.vjp(mamba_fused.gated_conv, bcx, kernel)
        return out, vjp(out)

    text = jax.jit(value_and_grads).lower(*args).compile().as_text()
    for name in (SCONV_CONV_FWD, SCONV_CONV_BWD):
        assert name in text, f"{name} is not in the compiled module"


# kanana2_seq8192_1chip: latent attention, 32 heads whose q and k are 192
# wide (1.5 x the MXU's 128 lanes: the first shape of the repo that is no power
# of two) against v, the output, dO and dV at 128; 2 rows of 8,192 in the step.
MLA = dict(b=2, t=8192, heads=32, d_qk=192, d_v=128)


@pytest.mark.parametrize("backward", [False, True],
                         ids=["forward", "forward_backward"])
def test_latent_attention_kernels_at_192_128_compile_for_v5e(
        one_chip, no_persistent_cache, backward):
    def flash(q, k, v):
        return flash_attention(q, k, v, True, DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)

    def grads(q, k, v):
        return jax.grad(lambda *a: jnp.sum(flash(*a).astype(jnp.float32)),
                        argnums=(0, 1, 2))(q, k, v)

    def shape(width):
        return jax.ShapeDtypeStruct((MLA["b"], MLA["t"], MLA["heads"], width),
                                    jnp.bfloat16, sharding=one_chip)

    q, v = shape(MLA["d_qk"]), shape(MLA["d_v"])
    compiled = jax.jit(grads if backward else flash).lower(q, q, v).compile()
    text = compiled.as_text()
    for name in ((FLASH_FWD, FLASH_BWD_DQ, FLASH_BWD_DKV) if backward
                 else (FLASH_FWD,)):
        assert name in text, f"{name} is not in the compiled module"
    if not backward:        # the output follows v, not q
        out, = jax.tree_util.tree_leaves(jax.eval_shape(flash, q, q, v))
        assert out.shape == (MLA["b"], MLA["t"], MLA["heads"], MLA["d_v"])


# Since PR 61 the cell's layers hand q and k over in the parts the projections
# wrote: q whole, ``[k_nope | v]`` whole as ``kv_b_proj`` wrote it (``v=None``:
# the index maps read each by its lane block of 128 and dK/dV writes ``[dk |
# dv]`` the same way), and the rotary 64 lanes that ONE head holds for all 32
# (``k_shared``: the kernels join the two in registers at lane 128). The
# step's shape, and the check's float32 leg under ``highest``; k and v in one
# array, and apart.
@pytest.mark.parametrize("kv_in_one", [True, False], ids=["kv", "k_v"])
@pytest.mark.parametrize("b,t,dtype,precision,blocks", [
    (MLA["b"], MLA["t"], jnp.bfloat16, None, (DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)),
    (1, 2048, jnp.float32, "highest", (512, 512)),
], ids=["step", "float32_leg"])
def test_latent_attention_kernels_with_a_shared_key_compile_for_v5e(
        one_chip, no_persistent_cache, b, t, dtype, precision, blocks,
        kv_in_one):
    own = MLA["d_v"]            # 128 | 64: q's 192 = the own lanes + the shared

    def grads(*operands):
        def loss(q, k_nope, v, k_shared):
            return jnp.sum(flash_attention(
                q, k_nope, v, True, *blocks, False, None, None,
                k_shared).astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 3) if kv_in_one else (0, 1, 2, 3))(
            *operands)

    def shape(heads, width):
        return jax.ShapeDtypeStruct((b, t, heads, width), dtype,
                                    sharding=one_chip)

    operands = (shape(MLA["heads"], MLA["d_qk"]),
                shape(MLA["heads"], own + MLA["d_v"] * kv_in_one),
                None if kv_in_one else shape(MLA["heads"], MLA["d_v"]),
                shape(1, MLA["d_qk"] - own))
    with jax.default_matmul_precision(precision or "default"):
        compiled = jax.jit(grads).lower(*operands).compile()
    text = compiled.as_text()
    for name in (FLASH_FWD, FLASH_BWD_DQ, FLASH_BWD_DKV):
        assert name in text, f"{name} is not in the compiled module"
    assert [x.shape for x in jax.tree_util.tree_leaves(
        jax.eval_shape(grads, *operands))] == [
            x.shape for x in operands if x is not None]


# What a call WITHOUT a shared key part lowers to, forward and backward, at
# (1, 2048, heads, width | 128) and the default blocks in bf16: the sha256 of
# the lowered text with the kernels' bodies re-printed without locations
# (``tools/lowered_step_sha.without_locations``), read on the tree BEFORE
# PR 61 gave the kernels their ``shared=``. A PR that changes what the dense
# kernels lower to on purpose re-reads them (print ``digest`` below).
DENSE_LOWERINGS = {
    # grouped-query 4 over 2 at 128: the older cells' kind of call
    (4, 2, 128): "0a4ec69a9513df7b83690a6a40b234a3cb73e0890305e36940d00cb36fb31fb6",
    # 192 | 128 with an ASSEMBLED k: what latent attention called until PR 61
    (2, 2, 192): "6bc0d7b813d21e19df22ff13d746f3961dc1f8a1be1380f318355090ef998aa9",
}


@pytest.mark.parametrize("heads,kv_heads,width", sorted(DENSE_LOWERINGS))
def test_a_call_without_a_shared_key_lowers_to_what_it_did(
        one_chip, no_persistent_cache, heads, kv_heads, width):
    import hashlib

    from tools.lowered_step_sha import without_locations

    def grads(q, k, v):     # the digests hold this function's name
        return jax.grad(lambda *a: jnp.sum(flash_attention(
            *a, True, DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K).astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)

    def shape(h, d):
        return jax.ShapeDtypeStruct((1, 2048, h, d), jnp.bfloat16,
                                    sharding=one_chip)

    text = jax.jit(grads).lower(shape(heads, width), shape(kv_heads, width),
                                shape(kv_heads, 128)).as_text()
    digest = hashlib.sha256(without_locations(text).encode()).hexdigest()
    assert digest == DENSE_LOWERINGS[heads, kv_heads, width]


# float32 operands stay float32 in all three kernels and are traced under
# ``highest``: the kanana2 check's float32 leg (512 / 512 blocks, the row's
# first 2,048 tokens, 192 | 128), and the DEFAULT blocks at 16 heads x 4096 x
# 128, where the backward's score-sized temporaries are what fills scoped
# VMEM (sub-tiles of 512 x 512 below the diagonal overflow it in dkv there).
@pytest.mark.parametrize("t,heads,d_qk,d_v,blocks", [
    (2048, MLA["heads"], MLA["d_qk"], MLA["d_v"], (512, 512)),
    (4096, 16, 128, 128, (DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)),
], ids=["kanana2_check_leg", "default_blocks"])
def test_float32_kernels_under_highest_compile_for_v5e(
        one_chip, no_persistent_cache, t, heads, d_qk, d_v, blocks):
    def grads(q, k, v):
        return jax.grad(lambda *a: jnp.sum(
            flash_attention(*a, True, *blocks)), argnums=(0, 1, 2))(q, k, v)

    def shape(width):
        return jax.ShapeDtypeStruct((1, t, heads, width), jnp.float32,
                                    sharding=one_chip)

    q, v = shape(d_qk), shape(d_v)
    with jax.default_matmul_precision("highest"):
        text = jax.jit(grads).lower(q, q, v).compile().as_text()
    for name in (FLASH_FWD, FLASH_BWD_DQ, FLASH_BWD_DKV):
        assert name in text, f"{name} is not in the compiled module"


# kanana2_seq8192_1chip: 16 held experts of 2048 x 768 over a row buffer of
# 16,384 tokens x top-6 = 98,304 rows of which an eighth are live (the group
# sizes sum to LESS than the buffer); 2,048 tokens in the check's two legs.
@pytest.mark.parametrize("rows,dtype,precision", [
    (98304, jnp.bfloat16, None),
    (12288, jnp.bfloat16, None),
    (12288, jnp.float32, "highest"),
], ids=["step_bf16", "check_bf16", "check_f32_highest"])
def test_grouped_kernels_compile_for_v5e_at_a_share_of_the_experts(
        one_chip, no_persistent_cache, rows, dtype, precision):
    _swiglu_grads_compiled(one_chip, rows, 2048, 768, 16, dtype, precision)


def test_the_held_layer_compiles_for_v5e_at_the_cells_shape(
        one_chip, no_persistent_cache):
    """``dropless_experts`` for one expert-parallel rank's share, forward and
    backward at ``kanana2_seq8192_1chip``'s shapes: the loops over the live
    windows, the buffers that are memory as it comes (a ``pallas_call`` that
    writes nothing) and the nine grouped products. No zero-filled and no
    copied 98,304-row array: the buffers are written in place."""
    from horovod_tpu.ops import moe

    tokens, top_k, dim, width, held, of = 16384, 6, 2048, 768, 16, 128

    def loss(x, weights, w_gate, w_up, w_down, experts):
        return jnp.sum(moe.dropless_experts(
            x, weights, experts, w_gate, w_up, w_down,
            held=(0, held, of)).astype(jnp.float32))

    def shape(*dims, of=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, of, sharding=one_chip)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        shape(tokens, dim), shape(tokens, top_k, of=jnp.float32),
        shape(held, dim, width), shape(held, dim, width),
        shape(held, width, dim), shape(tokens, top_k, of=jnp.int32)).compile()
    text = compiled.as_text()
    for name in (MOE_EXPERTS_GMM, MOE_EXPERTS_TGMM):
        assert name in text, f"{name} is not in the compiled module"
    assert " while(" in text
    rows = f"[{tokens * top_k},{dim}]"
    for line in text.splitlines():
        if " broadcast(" in line or " copy(" in line:
            assert rows not in line.split(" = ")[1].split("(")[0], line
    # rows, gate | up | h, out, a run-sum buffer each way, dout, dx's two
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * 2 ** 30


# bf16 rows against the FLOAT32 master weights (PR 63), which the rows x
# weights kernel rounds in VMEM where it multiplies them: the f32 block's two
# buffers and its bf16 form at each expert cell's widest block. OLMoE's
# 2048 x 1024 (8 + 8 MiB + 4), solar's 4096 x 640 of 4096 x 1280 (10.5 + 10.5
# + 5.2), the latent experts' 1024 x 2688 (10.5 + 10.5 + 5.25).
@pytest.mark.parametrize("rows,dim,width,experts", [
    (131072, 2048, 1024, 64), (65536, 4096, 1280, 8), (131072, 1024, 2688, 8),
], ids=["olmoe", "solar_open2", "nemotron3s_latent"])
def test_grouped_kernels_round_master_weights_for_v5e(
        one_chip, no_persistent_cache, rows, dim, width, experts):
    text, (dx, dw_gate, dw_down) = _swiglu_grads_compiled(
        one_chip, rows, dim, width, experts, jnp.bfloat16,
        weights=jnp.float32)
    _no_bf16_copy_of_a_stack(text, experts, dim, width)
    assert dx.dtype == jnp.bfloat16
    assert dw_gate.dtype == dw_down.dtype == jnp.float32


def _no_bf16_copy_of_a_stack(text, experts, dim, width):
    """No instruction of a compiled module COMPUTES a bf16 ``(E, K, N)`` but
    the weight gradient's kernel (its output, as ever; XLA may move that)."""
    for stack in (f"bf16[{experts},{dim},{width}]",
                  f"bf16[{experts},{width},{dim}]"):
        for line in text.splitlines():
            if f" = {stack}" in line.replace(", ", ","):
                assert MOE_EXPERTS_TGMM in line or not any(
                    op in line for op in (" fusion(", " convert(")), line[:300]


def test_the_held_layer_on_master_weights_compiles_for_v5e(
        one_chip, no_persistent_cache):
    """``dropless_experts`` for a rank's share at ``solar_open2_seq8192_1chip``'s
    shapes (8 of 320 experts of 4096 x 1280, 8,192 tokens x top-8), bf16 rows
    on the float32 parameters, forward and backward: the kernels, no bf16
    copy of a weight stack, and row buffers of bf16."""
    from horovod_tpu.ops import moe

    tokens, top_k, dim, width, held, of = 8192, 8, 4096, 1280, 8, 320

    def loss(x, weights, w_gate, w_up, w_down, experts):
        return jnp.sum(moe.dropless_experts(
            x, weights, experts, w_gate, w_up, w_down,
            held=(0, held, of)).astype(jnp.float32))

    def shape(*dims, of=jnp.float32):
        return jax.ShapeDtypeStruct(dims, of, sharding=one_chip)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        shape(tokens, dim, of=jnp.bfloat16), shape(tokens, top_k),
        shape(held, dim, width), shape(held, dim, width),
        shape(held, width, dim), shape(tokens, top_k, of=jnp.int32)).compile()
    text = compiled.as_text()
    for name in (MOE_EXPERTS_GMM, MOE_EXPERTS_TGMM):
        assert name in text, f"{name} is not in the compiled module"
    assert "ragged" not in text
    _no_bf16_copy_of_a_stack(text, held, dim, width)
    # every buffer of all the pairs' rows is bf16
    text = text.replace(", ", ",")
    pairs = tokens * top_k
    assert f"bf16[{pairs},{dim}]" in text and f"bf16[{pairs},{width}]" in text
    assert f"f32[{pairs},{dim}]" not in text
    assert f"f32[{pairs},{width}]" not in text


# laguna_xs2_seq16384_1chip: 64 query heads over 8 key/value heads of 128
# under a window of 512 on the sliding layers (the default blocks; grids that
# hold only the band's blocks, index maps that clamp at the sequence's ends;
# and at blocks of the window's size), 48 over 8 causal-dense on the full ones,
# and the check's float32 leg at 512 / 512 blocks on the row's first 2,048.
SWA = dict(t=16384, kv_heads=8, head_dim=128, window=512)


@pytest.mark.parametrize("heads,window,t,dtype,precision,blocks", [
    (64, SWA["window"], SWA["t"], jnp.bfloat16, None, (None, None)),
    (48, None, SWA["t"], jnp.bfloat16, None, (None, None)),
    (64, SWA["window"], 2048, jnp.float32, "highest", (512, 512)),
    (64, SWA["window"], SWA["t"], jnp.bfloat16, None, (1024, 512)),
    (64, SWA["window"], SWA["t"], jnp.bfloat16, None, (512, 512)),
    (48, None, 3072, jnp.bfloat16, None, (1024, 512)),
], ids=["sliding_64_over_8", "full_48_over_8", "sliding_check_leg_f32",
        "sliding_block_q_twice_block_k", "sliding_blocks_of_the_window",
        "full_48_over_8_odd_block_q_twice_block_k"])
def test_window_and_full_kernels_of_the_hybrid_compile_for_v5e(
        one_chip, no_persistent_cache, heads, window, t, dtype, precision,
        blocks):
    from horovod_tpu.common.device_names import (FLASH_WIN_BWD_DKV,
                                                 FLASH_WIN_BWD_DQ,
                                                 FLASH_WIN_FWD)

    def grads(q, k, v):
        return jax.grad(lambda *a: jnp.sum(flash_attention(
            *a, True, *blocks, False, None, window).astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)

    def shape(n):
        return jax.ShapeDtypeStruct((1, t, n, SWA["head_dim"]), dtype,
                                    sharding=one_chip)

    q, kv = shape(heads), shape(SWA["kv_heads"])
    with jax.default_matmul_precision(precision):
        text = jax.jit(grads).lower(q, kv, kv).compile().as_text()
    names = ((FLASH_FWD, FLASH_BWD_DQ, FLASH_BWD_DKV) if window is None
             else (FLASH_WIN_FWD, FLASH_WIN_BWD_DQ, FLASH_WIN_BWD_DKV))
    for name in names:
        assert name in text, f"{name} is not in the compiled module"
    if window is not None:
        assert FLASH_FWD not in text and FLASH_BWD_DQ not in text


# nemotron3s_seq8192_1chip: one tensor- / expert-parallel rank's share of
# Nemotron-3-Super. 8 held relu² experts of 1,024 x 2,688 (21 x 128: no power
# of two) chosen 22 of 512 from 16,384 tokens: 360,448 pairs, 131,072 buffer
# rows (a token's 22 are distinct, 8 held at most), ~5,632 live; the Mamba-2
# mixer at 16 heads x 64, ONE group of state 128, chunk 128, batch 2; the
# flash kernels at 4 query heads over 1 key/value head of 128.
def test_the_latent_experts_compile_for_v5e_at_the_cells_shape(
        one_chip, no_persistent_cache):
    """``dropless_experts`` WITHOUT a gate for the rank's share, forward and
    backward: the two kernels at K = 1,024 / N = 2,688 and its transpose
    (weight blocks of 5.25 MiB, the weight gradient's accumulator 10.5), six
    grouped products, row buffers of ``_buffer_rows`` and not of every pair."""
    from horovod_tpu.ops import moe

    tokens, top_k, latent, width, held, of = 16384, 22, 1024, 2688, 8, 512
    assert moe._buffer_rows(tokens * top_k, top_k, held) == tokens * held

    def loss(x, weights, w_up, w_down, experts):
        return jnp.sum(moe.dropless_experts(
            x, weights, experts, None, w_up, w_down,
            held=(0, held, of)).astype(jnp.float32))

    def shape(*dims, of=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, of, sharding=one_chip)

    assert gm.takes_kernel(shape(tokens * held, latent),
                           shape(held, latent, width))
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        shape(tokens, latent), shape(tokens, top_k, of=jnp.float32),
        shape(held, latent, width), shape(held, width, latent),
        shape(tokens, top_k, of=jnp.int32)).compile()
    text = compiled.as_text()
    for name in (MOE_EXPERTS_GMM, MOE_EXPERTS_TGMM):
        assert name in text, f"{name} is not in the compiled module"
    assert " while(" in text and "ragged" not in text
    # no array of all 360,448 pairs' ROWS: indices of them alone
    for dims in (f"[{tokens * top_k},{latent}]", f"[{tokens * top_k},{width}]"):
        assert dims not in text, dims
    # rows, up, h, out, a run-sum buffer each way, dout, dh, dx's
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2 ** 30


@pytest.mark.parametrize("part", ["scan", "conv_silu", "gate_norm", "flash"])
def test_the_hybrids_mixers_compile_for_v5e_at_the_held_share(
        one_chip, no_persistent_cache, part):
    """The shapes no older cell gives the shared kernels: the scan at (2,
    8192, 16 heads x 64, state 128) and chunk 128; the convolution + silu over
    1,280 channels and the gated norm over 1,024 at batch 2; the three flash
    kernels at (2, 8192, 4 over 1, 128). Forward and backward."""
    from horovod_tpu.common.device_names import (MAMBA_CONV_BWD,
                                                 MAMBA_CONV_FWD,
                                                 MAMBA_GATE_NORM_BWD,
                                                 MAMBA_GATE_NORM_FWD)
    from horovod_tpu.ops import mamba_fused
    from horovod_tpu.ops.ssd import ssd

    b, t, h, p, n = 2, 8192, 16, 64, 128
    bf16 = jnp.bfloat16

    def shape(*dims, of=jnp.float32):
        return jax.ShapeDtypeStruct(dims, of, sharding=one_chip)

    if part == "scan":
        def fn(*a):
            return ssd(*a, 128)

        args = (shape(b, t, h, p, of=bf16), shape(b, t, h), shape(h),
                shape(b, t, 1, n, of=bf16), shape(b, t, 1, n, of=bf16), shape(h))
        kernels = ("hvd_ssd_scan_fwd", "hvd_ssd_scan_bwd")
    elif part == "conv_silu":
        def fn(x, kernel, bias):
            return mamba_fused.conv_silu(x, kernel, bias, splits=(1024, 256))

        args = (shape(b, t, 1280, of=bf16), shape(4, 1280), shape(1280))
        assert mamba_fused.conv_takes_kernel(*args[:2], (1024, 256))
        kernels = (MAMBA_CONV_FWD, MAMBA_CONV_BWD)
    elif part == "gate_norm":
        def fn(y, z, scale):
            return mamba_fused.gate_norm(y, z, scale, 1, 1e-5)

        args = (shape(b, t, 1024, of=bf16),) * 2 + (shape(1024),)
        assert mamba_fused.norm_takes_kernel(*args[:2], 1)
        kernels = (MAMBA_GATE_NORM_FWD, MAMBA_GATE_NORM_BWD)
    else:
        fn = _flash
        args = (shape(b, t, 4, 128, of=bf16),) + (shape(b, t, 1, 128, of=bf16),) * 2
        kernels = (FLASH_FWD, FLASH_BWD_DQ, FLASH_BWD_DKV)

    def value_and_grads(*a):    # the value too: a forward nobody reads is cut
        out, vjp = jax.vjp(fn, *a)
        return out, vjp(out)

    text = jax.jit(value_and_grads).lower(*args).compile().as_text()
    for name in kernels:
        assert name in text, f"{name} is not in the compiled module"
    if part == "scan":
        assert " while(" not in text


# (row, heads, beta in (0, 2): the solve block by block)
DELTA_RULE_CELLS = {"kimi": (16384, 32, False), "solar_open2": (8192, 8, True)}


@pytest.mark.parametrize("dtype,precision", [(jnp.bfloat16, None),
                                             (jnp.float32, "highest")],
                         ids=["bf16", "f32_highest"])
@pytest.mark.parametrize("cell", sorted(DELTA_RULE_CELLS))
def test_the_delta_rules_kernels_compile_for_v5e_at_the_cells_shape(
        one_chip, no_persistent_cache, cell, dtype, precision):
    """kimi_linear_seq16384_1chip's row through ``ops.kda.kda``: (1, 16384, 32
    heads of 128 | 128), and solar_open2_seq8192_1chip's: (1, 8192, 8 heads)
    with beta in (0, 2) (``neg_eigval``: the solve block by block); chunks of
    64 in blocks of four, the step's bf16 and the check's float32 under
    "highest". Forward and backward: both kernels are in the compiled
    program, and no loop over blocks is."""
    from horovod_tpu.common.device_names import KDA_SCAN
    from horovod_tpu.ops import kda as kda_ops

    t, heads, neg_eigval = DELTA_RULE_CELLS[cell]

    def shape(*dims, of=dtype):
        return jax.ShapeDtypeStruct(dims, of, sharding=one_chip)

    x = shape(1, t, heads, 128)
    args = (x, x, x, shape(1, t, heads, 128, of=jnp.float32),
            shape(1, t, heads, of=jnp.float32))
    assert kda_ops.takes_kernel(x, x, x, *kda_ops.plan(t)[::2])

    def value_and_grads(*a):    # the value too: a forward nobody reads is cut
        out, vjp = jax.vjp(lambda *a: kda_ops.kda(*a, neg_eigval=neg_eigval),
                           *a)
        return out, vjp(out)

    with jax.default_matmul_precision(precision):
        text = jax.jit(value_and_grads).lower(*args).compile().as_text()
    for name in (KDA_SCAN + "_fwd", KDA_SCAN + "_bwd"):
        assert name in text, f"{name} is not in the compiled module"
    assert " while(" not in text


@pytest.mark.parametrize("t,dtype", [(16384, jnp.bfloat16),
                                     (2048, jnp.float32)],
                         ids=["step_bf16", "check_f32"])
def test_the_delta_rule_mixers_fused_passes_compile_for_v5e(
        one_chip, no_persistent_cache, t, dtype):
    """kimi_linear_seq16384_1chip's mixer between its convolutions and
    ``o_proj`` on the (1, T, 32 x 128) form: ``ops.kda_fused.gate``,
    ``ops.kda.kda_lanes`` and ``ops.kda_fused.out_norm``, forward and
    backward, at the step's row in bf16 and the check's prefix in float32. All
    six kernels are in the compiled program and nothing in it is laid out
    (.., 32, 128)."""
    from horovod_tpu.common.device_names import (KDA_GATE, KDA_OUT_NORM,
                                                 KDA_SCAN)
    from horovod_tpu.ops import kda as kda_ops
    from horovod_tpu.ops import kda_fused

    heads, d = 32, 128

    def shape(*dims, of=dtype):
        return jax.ShapeDtypeStruct(dims, of, sharding=one_chip)

    x = shape(1, t, heads * d)
    args = (x, x, x, x, x, shape(1, t, heads, of=jnp.float32),
            shape(heads, of=jnp.float32), shape(heads * d, of=jnp.float32),
            shape(d, of=jnp.float32))
    assert kda_fused.takes_kernel(x, x, x, x, x, heads, kda_ops.CHUNK)

    def between(q, k, v, decay, gate, beta, a_log, dt_bias, scale):
        q, k, g = kda_fused.gate(q, k, decay, a_log, dt_bias)
        return kda_fused.out_norm(kda_ops.kda_lanes(q, k, v, g, beta), gate,
                                  scale, 1e-5)

    def value_and_grads(*a):    # the value too: a forward nobody reads is cut
        out, vjp = jax.vjp(between, *a)
        return out, vjp(out)

    precision = "highest" if dtype == jnp.float32 else None
    with jax.default_matmul_precision(precision):
        text = jax.jit(value_and_grads).lower(*args).compile().as_text()
    for name in (KDA_GATE, KDA_SCAN, KDA_OUT_NORM):
        for kernel in ("_fwd", "_bwd"):
            assert name + kernel in text, f"{name + kernel} is not compiled"
    assert f",{heads},{d}]" not in text


# keye_vl2_seq16384_1chip: 32 query heads over 4 key/value heads of 128 over a
# selection that is data (the packed words one more operand, the liveness and
# fetch tables scalar-prefetched), an indexer of 16 heads of 64 against one
# key head, 2,048 keys a query in chunks of 512: the step's row of 16,384 in
# bf16 at the default blocks, and the check's 4,096-token prefix in float32
# under "highest" at 512 blocks.
SPARSE = {"step_bf16": (16384, jnp.bfloat16, (None, None), None),
          "check_f32_highest": (4096, jnp.float32, (512, 512), "highest")}


@pytest.mark.parametrize("case", sorted(SPARSE))
@pytest.mark.parametrize("piece", ["select", "flash", "align"])
def test_sparse_attention_kernels_compile_for_v5e(one_chip, no_persistent_cache,
                                                  case, piece):
    from horovod_tpu.common import device_names
    from horovod_tpu.ops import sparse_attention as dsa
    from horovod_tpu.ops.flash_attention import selected_attention

    t, dtype, blocks, precision = SPARSE[case]
    heads, kv_heads, d, index_heads, index_dim = 32, 4, 128, 16, 64

    def shape(*dims, of=dtype):
        return jax.ShapeDtypeStruct(dims, of, sharding=one_chip)

    q, kv = shape(1, t, heads, d), shape(1, t, kv_heads, d)
    qi, ki = shape(1, t, index_heads, index_dim), shape(1, t, index_dim)
    w = shape(1, t, index_heads, of=jnp.float32)
    words = shape(1, t, dsa.word_columns(t, 512), of=jnp.int32)
    lse, lse_i = (shape(1, heads, t, of=jnp.float32),
                  shape(1, t, of=jnp.float32))

    def flash(q, k, v, words):
        return jax.grad(lambda q, k, v: jnp.sum(selected_attention(
            q, k, v, words, *blocks)[0].astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)

    def align(q, k, lse, qi, ki, w, words, lse_i):
        return jax.value_and_grad(lambda qi, ki, w: dsa.align_loss(
            q, k, lse, qi, ki, w, words, lse_i), argnums=(0, 1, 2))(qi, ki, w)

    fn, args, names = {
        "select": (lambda qi, ki, w: dsa.select(qi, ki, w, 2048), (qi, ki, w),
                   (device_names.DSA_INDEXER_SCORES,
                    f'{device_names.DSA_SELECT}/pallas_call')),
        "flash": (flash, (q, kv, kv, words),
                  (device_names.FLASH_SEL_FWD, device_names.FLASH_SEL_BWD_DQ,
                   device_names.FLASH_SEL_BWD_DKV)),
        "align": (align, (q, kv, lse, qi, ki, w, words, lse_i),
                  (device_names.DSA_ALIGN_TILES,)),
    }[piece]
    with jax.default_matmul_precision(precision):
        compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    for name in names:
        assert name in text, f"{name} is not in the compiled module"
    # nothing (T x T) lives in HBM: no array of the module has two axes of T
    import re
    for dims in re.findall(r"\b(?:f32|bf16|s32|u32|pred|s8|u8)\[([0-9,]+)\]", text):
        assert sum(int(n) >= t for n in dims.split(",")) < 2, dims


@pytest.mark.parametrize("dtype,precision", [(jnp.bfloat16, None),
                                             (jnp.float32, "highest")],
                         ids=["bf16", "f32_highest"])
def test_the_scalar_decay_rules_kernels_compile_for_v5e_at_the_cells_shape(
        one_chip, no_persistent_cache, dtype, precision):
    """olmo_hybrid_seq16384_1chip's row through ``ops.gdn.gdn``: (1, 16384, 15
    heads of 96 | 192) with ONE decay a head and beta in (0, 2), laid out on
    128 | 256 lanes a head, blocks of 512 positions; the step's bf16 and the
    check's float32 under "highest". Forward and backward: both kernels are
    in the compiled program, and no loop over blocks is."""
    from horovod_tpu.common.device_names import GDN_SCAN_BWD, GDN_SCAN_FWD
    from horovod_tpu.ops import gdn as gdn_ops

    t, heads = 16384, 15

    def shape(*dims, of=dtype):
        return jax.ShapeDtypeStruct(dims, of, sharding=one_chip)

    args = (shape(1, t, heads, 96), shape(1, t, heads, 96),
            shape(1, t, heads, 192), shape(1, t, heads, of=jnp.float32),
            shape(1, t, heads, of=jnp.float32))
    assert gdn_ops.takes_kernel(*args[:3], 64)

    def value_and_grads(*a):    # the value too: a forward nobody reads is cut
        out, vjp = jax.vjp(lambda *a: gdn_ops.gdn(*a, neg_eigval=True), *a)
        return out, vjp(out)

    with jax.default_matmul_precision(precision):
        text = jax.jit(value_and_grads).lower(*args).compile().as_text()
    for name in (GDN_SCAN_FWD, GDN_SCAN_BWD):
        assert name in text, f"{name} is not in the compiled module"
    assert " while(" not in text


def test_the_three_convolutions_side_by_side_compile_for_v5e(
        one_chip, no_persistent_cache):
    """olmo_hybrid_seq16384_1chip's [q | k | v] through ONE call of the
    convolution's kernel pair: (1, 16384, 5760) bf16, 5.6 MiB a block of rows
    (``_CONV_BLOCK_BYTES``), under the mixer's names."""
    from horovod_tpu.models.gdn import CONV_NAMES
    from horovod_tpu.ops import mamba_fused

    def shape(*dims, of=jnp.float32):
        return jax.ShapeDtypeStruct(dims, of, sharding=one_chip)

    args = (shape(1, 16384, 5760, of=jnp.bfloat16), shape(4, 5760),
            shape(5760))
    assert mamba_fused.conv_takes_kernel(*args[:2])

    def value_and_grads(*a):
        out, vjp = jax.vjp(lambda *a: mamba_fused.conv_silu(
            *a, names=CONV_NAMES), *a)
        return out, vjp(out)

    text = jax.jit(value_and_grads).lower(*args).compile().as_text()
    for name in CONV_NAMES:
        assert name in text, f"{name} is not in the compiled module"


LOSS_CHUNK = 2048

# (B, T, d) hidden states x vocabulary, and whether the kernel is a transposed
# embedding, of lfm2_seq8192_1chip, kanana2_seq8192_1chip, olmoe_seq4096_1chip;
# and ouro_seq8192_1chip's, whose one loop reads the head once a PASS under
# weights a row (a fourth entry: the passes)
CELL_HEADS = {"lfm2": ((2, 8192, 2048), 8192, True),
              "kanana2": ((2, 8192, 2048), 16032, False),
              "olmoe": ((4, 4096, 2048), 50304, False),
              "ouro": ((1, 8192, 2048), 49152, False, 4)}


def _loop_bodies(text, scope):
    """The text of each computation that a ``while`` of ``text`` names as its
    body and that carries the name ``scope``."""
    bodies = []
    for name in set(re.findall(r"\bwhile\(.*\bbody=(%[\w.\-]+)", text)):
        start = text.index(f"\n{name} (")
        body = text[start:text.index("\n}", start)]
        if scope in body:
            bodies.append(body)
    return bodies


def _fusion_outputs(body):
    """``(instruction, [(dtype, dims), ...])`` of each fusion called from
    ``body`` (the lines of nested computations are not in ``body``)."""
    for line in body.splitlines():
        called = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = (.*?) fusion\(", line)
        if called:
            yield called.group(1), [
                (dtype, tuple(int(n) for n in dims.split(",")))
                for dtype, dims in re.findall(r"\b([a-z]+\d+)\[([\d,]+)\]",
                                              called.group(2))]


@pytest.mark.parametrize("cell", sorted(CELL_HEADS))
def test_loss_loop_writes_its_logits_once(one_chip, no_persistent_cache,
                                          cell):
    """``chunked_lm_loss`` under ``jax.value_and_grad`` at three cells' shapes,
    and the form of its loop's body read off the compiled module: the logits
    are written ONCE, by their own product. At lfm2's 2 x 2048 rows of
    vocabulary 8192 the compiler gave a loop handed ``(batch, chunk, d)`` a
    softmax fusion of three outputs, the row max, a row sum and ``logits -
    max`` WRITTEN BACK at the logits' size: 47 of the cell's 57 ms of
    ``hvd_lm_head`` a step (ledger, PR 65). Handed the same rows merged,
    ``(batch * chunk, d)``, it fuses the row max into the product's epilogue
    and reads the logits once for both sums, at every shape here (PR 66);
    so does the WEIGHTED loop over (pass, chunk) of a looped model's four
    readings (PR 67), which multiplies the logits' gradient by a weight a
    row."""
    from horovod_tpu.common.device_names import LM_HEAD
    from horovod_tpu.models.transformer import chunked_lm_loss

    (b, t, d), vocab, tied, *passes = CELL_HEADS[cell]
    rows = b * LOSS_CHUNK

    def loss(hidden, kernel, targets, *weights):
        return chunked_lm_loss(hidden, kernel.T if tied else kernel, targets,
                               LOSS_CHUNK, *weights)

    def shape(*dims, of):
        return jax.ShapeDtypeStruct(dims, of, sharding=one_chip)

    text = jax.jit(jax.value_and_grad(loss, (0, 1, 3) if passes else (0, 1))
                   ).lower(
        shape(*passes, b, t, d, of=jnp.bfloat16),
        shape(*((vocab, d) if tied else (d, vocab)), of=jnp.float32),
        shape(b, t, of=jnp.int32),
        *[shape(*passes, b, t, of=jnp.float32)] * len(passes)
    ).compile().as_text()
    (body,) = _loop_bodies(text, LM_HEAD)

    def logits_sized(dtype, dims):
        return (dtype == "f32" and dims[-1] == vocab
                and math.prod(dims[:-1]) == rows)

    writers = {name: sum(logits_sized(*out) for out in outs)
               for name, outs in _fusion_outputs(body)}
    assert len(writers) >= 4, f"the loop's body was not read: {writers}"
    if d == rows:
        # a row block as tall as the model is wide (ouro's 2,048): the
        # kernel's float32 gradient, (d, vocab), has the logits' size, and
        # the product that adds into it is no writer of logits
        (accumulate,) = (name for name, n in writers.items()
                         if n and "convolution_add" in name)
        del writers[accumulate]
    assert sum(writers.values()) == 1, (
        f"{rows} x {vocab} float32 written by {writers}: the loop's logits "
        "leave one fusion, their product's, and no softmax pass writes "
        "another array of their size")
    (product,) = (name for name, n in writers.items() if n)
    assert re.search(rf"{re.escape(product)} = .*kind=kOutput.*dot_general",
                     body), f"{product} is not the logits' product"
