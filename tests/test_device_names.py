"""The names the program gives its device work (common/device_names.py) reach
the lowered module as metadata: present in the text with debug info, absent
from the text without it, so the step executes the same operations."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import PartitionSpec as P

from horovod_tpu.common import device_names as names
from horovod_tpu.compat import shard_map
from horovod_tpu.ops.flash_attention import flash_attention


@pytest.fixture(scope="module")
def optimizer_step_text():
    import horovod_tpu as hvd

    mesh = hvd.data_parallel_mesh(jax.devices()[:4])
    opt = hvd.jax.DistributedOptimizer(optax.sgd(0.1), compression=hvd.Compression.bf16,
                                       compression_min_bytes=0)
    params = {"w": jnp.ones((16, 8)), "b": jnp.zeros((8,))}

    def train_step(params, opt_state, x):
        grads = jax.grad(lambda p: jnp.mean((x @ p["w"] + p["b"]) ** 2))(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    step = jax.jit(shard_map(train_step, mesh=mesh,
                             in_specs=(P(), P(), P(hvd.HVD_AXIS)),
                             out_specs=(P(), P()), check_vma=False))
    lowered = step.lower(params, opt.init(params), jnp.ones((8, 16)))
    return {True: lowered.as_text(debug_info=True),
            False: lowered.as_text(debug_info=False)}


@pytest.fixture(scope="module")
def flash_text():
    q = jnp.ones((1, 128, 2, 32), jnp.float32)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=True).sum()

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, q)
    return lowered.as_text(debug_info=True)


@pytest.fixture(scope="module")
def moe_text():
    from horovod_tpu.models import MoEMLP

    layer = MoEMLP(dim=16, hidden=8, n_experts=4, top_k=2, dtype=jnp.float32)
    x = jnp.ones((1, 8, 16), jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    lowered = jax.jit(jax.grad(lambda p: layer.apply({"params": p}, x).sum())
                      ).lower(params)
    return {True: lowered.as_text(debug_info=True),
            False: lowered.as_text(debug_info=False)}


def _moe_layer_text(dim, hidden, tokens, dtype, grad):
    """(lowered text with debug info, the border-overhead gauge after the
    trace) of a 4-expert top-2 layer, forward alone or with its gradients."""
    from horovod_tpu.metrics import registry
    from horovod_tpu.models import MoEMLP

    layer = MoEMLP(dim=dim, hidden=hidden, n_experts=4, top_k=2, dtype=dtype,
                   interpret=True)
    x = jnp.ones((1, tokens, dim), jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]

    def f(p, x):
        return layer.apply({"params": p}, x).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(f, argnums=(0, 1)) if grad else f).lower(
        params, x).as_text(debug_info=True)
    return text, registry().gauge("horovod_moe_grouped_border_overhead").value


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "backward"])
def test_aligned_experts_lower_to_the_grouped_kernels_by_name(grad):
    """OLMoE-shaped at an aligned size (widths of 128, 256 tokens x top-2 =
    one bf16 row tile of 512 = 4 row blocks): the rows x weights kernel in
    the forward, both kernels in the backward, under names the benchmark's
    ``hvd_moe_experts`` finds; no ``ragged_dot``; the gauge reads
    (4 + 4 - 1) / 4."""
    for name in (names.MOE_EXPERTS_GMM, names.MOE_EXPERTS_TGMM):
        assert names.MOE_EXPERTS in name
    text, overhead = _moe_layer_text(128, 128, 256, jnp.bfloat16, grad)
    assert names.MOE_EXPERTS_GMM in text
    assert (names.MOE_EXPERTS_TGMM in text) is grad
    assert "ragged_dot" not in text
    assert overhead == 1.75


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "backward"])
def test_tiny_unaligned_experts_keep_ragged_dot(grad):
    text, overhead = _moe_layer_text(16, 8, 8, jnp.float32, grad)
    assert "ragged_dot" in text
    assert names.MOE_EXPERTS_GMM not in text
    assert names.MOE_EXPERTS_TGMM not in text
    assert overhead == 0.0


@pytest.fixture(scope="module")
def recomputed_expert_block():
    """``shape -> (locs, text without debug info)`` of a bf16 expert layer
    that holds 2 of 4 experts inside ``jax.checkpoint``, its value and its
    gradients: the forward, the forward again and the backward."""
    from horovod_tpu.models import MoEMLP

    def lower(dim, hidden, tokens):
        layer = MoEMLP(dim=dim, hidden=hidden, n_experts=4, top_k=2,
                       dtype=jnp.bfloat16, interpret=True, router="sigmoid",
                       held=(0, 2))
        x = jnp.ones((1, tokens, dim), jnp.float32)
        variables = layer.init(jax.random.PRNGKey(0), x)
        state = {k: v for k, v in variables.items() if k != "params"}
        block = jax.checkpoint(
            lambda p, x: layer.apply({"params": p, **state}, x))
        lowered = jax.jit(jax.value_and_grad(
            lambda p, x: block(p, x).astype(jnp.float32).sum())).lower(
                variables["params"], x)
        return _locs(lowered), lowered.as_text(debug_info=False)

    return lower


@pytest.mark.parametrize("shape,kernels", [((128, 128, 256), True),
                                           ((16, 8, 8), False)],
                         ids=["kernels", "ragged_dot"])
def test_the_weights_cast_is_only_where_the_kernels_are_not(
        shape, kernels, recomputed_expert_block):
    """Where the kernels take the products they read the float32 parameters
    themselves: no ``(E, K, N)`` stack is converted to bf16, forward or
    recomputed, and nothing is under ``hvd_moe_weight_cast``. Where they
    refuse the shapes, the three stacks are cast under that name before
    ``lax.ragged_dot``: in the forward, in the forward again and (the
    gradients' cast back) in the backward."""
    import re

    found, text = recomputed_expert_block(*shape)
    dim, hidden, _ = shape
    stacks = {f"2x{dim}x{hidden}", f"2x{hidden}x{dim}"}
    to_bf16 = [s for s in re.findall(
        r"stablehlo\.convert[^\n]*\(tensor<(\w+)xf32>\) -> tensor<\w+xbf16>",
        text) if s in stacks]
    casts = sorted(n for n in found if names.MOE_WEIGHT_CAST in n)
    assert any(names.MOE_EXPERTS_GMM in n for n in found) is kernels
    assert any("ragged_dot" in n for n in found) is not kernels
    if kernels:
        assert not to_bf16 and not casts
        return
    assert len(to_bf16) >= 6            # three stacks, forward and again
    assert all(n.endswith("/convert_element_type") for n in casts)
    assert any("rematted_computation" in n for n in casts)
    assert any("rematted_computation" not in n and "transpose(" not in n
               for n in casts)
    assert any(f"transpose(jvp({names.MOE_WEIGHT_CAST}))" in n for n in casts)


@pytest.mark.parametrize("name", [names.MOE_ROUTE, names.MOE_DISPATCH,
                                  names.MOE_EXPERTS, names.MOE_COMBINE])
def test_moe_scope_is_in_the_lowered_module_as_metadata_only(name, moe_text):
    assert name in moe_text[True]
    assert name not in moe_text[False]
    assert "dot_general" in moe_text[False]     # the work itself is there


@pytest.mark.parametrize("name", [names.FUSION_PACK, names.FUSION_UNPACK,
                                  names.OPTIMIZER_UPDATE,
                                  names.FUSED_ALLREDUCE,
                                  names.FLASH_FWD, names.FLASH_BWD_DQ,
                                  names.FLASH_BWD_DKV])
def test_name_is_in_the_lowered_module_as_metadata_only(name, request):
    if name.startswith("hvd_flash"):
        assert name in request.getfixturevalue("flash_text")
        return
    text = request.getfixturevalue("optimizer_step_text")
    assert name in text[True]
    if name != names.FUSED_ALLREDUCE:   # the three new ones: metadata alone
        assert name not in text[False]


# ------------------------------------------------- the Mamba-2 mixer's scopes

MAMBA_SCOPES = [names.MAMBA_PROJ, names.MAMBA_CONV, names.MAMBA_GATE_NORM,
                names.SSD_SCAN]


@pytest.fixture(scope="module")
def mamba_op_names():
    """The ``op_name``s of a tiny hybrid's gradients (16 chunks: the scan's
    scan runs as one loop over blocks of chunks, as at the
    published sizes; recomputation on, as the cell runs), and the text
    without them."""
    import re

    from horovod_tpu.models import TransformerLM
    from horovod_tpu.models.mamba import Mamba2Dims

    model = TransformerLM(
        vocab=64, dim=32, heads=4, kv_heads=2, layers=2,
        layer_types=("mamba", "attention"),
        mamba=Mamba2Dims(heads=4, head_dim=4, state=8, chunk=4),
        mlp_hidden=48, rope=False, tie_embeddings=True, remat=True,
        dtype=jnp.float32)
    tokens = jnp.zeros((1, 64), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    lowered = jax.jit(jax.grad(
        lambda p: model.apply({"params": p}, tokens).sum())).lower(params)
    found = set(re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True)))
    return found, lowered.as_text(debug_info=False)


@pytest.mark.parametrize("backward", [False, True], ids=["forward", "backward"])
@pytest.mark.parametrize("name", MAMBA_SCOPES)
def test_mamba_scope_survives_the_breakdowns_label(name, backward,
                                                   mamba_op_names):
    """In the module as metadata only, on forward and backward operations,
    and still there in what ``benchmarks/reduce_trace.op_label`` keeps of an
    ``op_name``: its last three segments."""
    found, bare = mamba_op_names
    assert name not in bare
    kept = {"/".join(n.split("/")[-3:]) for n in found
            if ("transpose(jvp(" in n) is backward}
    assert any(name in label for label in kept), sorted(kept)[:20]


@pytest.fixture(scope="module")
def mamba_kernel_op_names():
    """The ``op_name``s of a tiny hybrid's gradients at sizes the mixer's
    fused kernels tile (inner 128, channels 384, 256 rows of float32),
    LOWERED FOR THE TPU (nothing compiles or runs): the four ``pallas_call``s
    are there as the chip names them, inside ``nn.remat(Block)``."""
    import re

    from horovod_tpu.models import TransformerLM
    from horovod_tpu.models.mamba import Mamba2Dims

    model = TransformerLM(
        vocab=64, dim=32, heads=4, kv_heads=2, layers=2,
        layer_types=("mamba", "attention"),
        mamba=Mamba2Dims(heads=4, head_dim=32, state=128, chunk=16),
        mlp_hidden=48, rope=False, tie_embeddings=True, remat=True,
        dtype=jnp.float32)
    tokens = jnp.zeros((1, 256), jnp.int32)
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    lowered = jax.jit(jax.grad(
        lambda p: model.apply({"params": p}, tokens).sum())).trace(
            params).lower(lowering_platforms=("tpu",))
    return set(re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True)))


@pytest.mark.parametrize("name,call,where", [
    (names.MAMBA_CONV_FWD, "_conv_fwd_call", "forward"),
    (names.MAMBA_CONV_FWD, "_conv_fwd_call", "recomputed"),
    (names.MAMBA_CONV_BWD, "_conv_bwd_call", "backward"),
    (names.MAMBA_GATE_NORM_FWD, "_norm_fwd_call", "forward"),
    (names.MAMBA_GATE_NORM_FWD, "_norm_fwd_call", "recomputed"),
    (names.MAMBA_GATE_NORM_BWD, "_norm_bwd_call", "backward"),
])
def test_mixer_kernel_name_survives_the_breakdowns_label(
        name, call, where, mamba_kernel_op_names):
    """A kernel is one jitted call whose body names the ``pallas_call``: the
    device op's ``op_name`` is the call site's plus the body's, and what
    ``benchmarks/reduce_trace.op_label`` keeps of it, the last three segments,
    holds ``hvd_mamba``: the mixer's readers find it."""
    found = mamba_kernel_op_names
    inside = f"{name}/pallas_call"
    assert inside in found
    sites = [n for n in found if n.endswith(f"/mixer/jit({call})")]
    site, = [n for n in sites if {
        "forward": "transpose(jvp(" not in n,
        "recomputed": "/checkpoint/rematted_computation/" in n,
        "backward": "transpose(jvp(" in n and "rematted" not in n}[where]]
    label = "/".join(f"{site}/{inside}".split("/")[-3:])
    assert label == f"jit({call})/{name}/pallas_call"
    assert "hvd_mamba" in label and "hvd_ssd" not in label


@pytest.mark.parametrize("fixture", ["mamba_op_names",
                                     "mamba_kernel_op_names"])
def test_the_scans_loop_is_named_once(fixture, request):
    """The scan over blocks of chunks is a ``while`` op that carries the
    scope; the ops of its body end in ``while/body/...`` and their kept label
    does not, so a reader that sums labels holding ``hvd_ssd`` counts the loop
    once (the device trace lists the loop AND its body's ops). With the
    mixer's fused kernels beside it too."""
    found = request.getfixturevalue(fixture)
    found = found[0] if isinstance(found, tuple) else found
    loops = {"/".join(n.split("/")[-3:]) for n in found
             if n.endswith(f"{names.SSD_SCAN}/while")}
    assert loops == {f"mixer/{names.SSD_SCAN}/while"}
    inside = [n for n in found if "/while/body/" in n and "hvd_ssd" in n]
    assert inside
    assert not any("hvd_ssd" in "/".join(n.split("/")[-3:]) for n in inside)


@pytest.fixture(scope="module")
def scan_kernel_op_names():
    """The ``op_name``s of a tiny hybrid's gradients at sizes the SCAN's
    kernels tile too (2 heads x 64, state 128, chunk 128 over 256 rows of
    float32), LOWERED FOR THE TPU (nothing compiles or runs): no loop over
    blocks of chunks any more, two ``pallas_call``s whose names hold
    ``hvd_ssd`` and are no names of ``common/device_names.py``."""
    import re

    from horovod_tpu.models import TransformerLM
    from horovod_tpu.models.mamba import Mamba2Dims

    model = TransformerLM(
        vocab=64, dim=32, heads=4, kv_heads=2, layers=2,
        layer_types=("mamba", "attention"),
        mamba=Mamba2Dims(heads=2, head_dim=64, state=128, chunk=128),
        mlp_hidden=48, rope=False, tie_embeddings=True, remat=True,
        dtype=jnp.float32)
    tokens = jnp.zeros((1, 256), jnp.int32)
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    lowered = jax.jit(jax.grad(
        lambda p: model.apply({"params": p}, tokens).sum())).trace(
            params).lower(lowering_platforms=("tpu",))
    return set(re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True)))


@pytest.mark.parametrize("kernel,call,where", [
    ("_fwd", "_scan_fwd_call", "forward"),
    ("_fwd", "_scan_fwd_call", "recomputed"),
    ("_bwd", "_scan_bwd_call", "backward"),
])
def test_the_scans_kernels_are_read_under_the_scans_name(
        kernel, call, where, scan_kernel_op_names):
    """Where the shapes tile, the scan is two kernels and no ``while``. Their
    own names are NOT registered: ``device_profile.name_of`` gives a device
    op of theirs to ``hvd_ssd_scan``, the scope both rules of the
    ``custom_vjp`` enter, so ``ssd_scan_ms_per_step`` reads the whole scan;
    the kept label holds ``hvd_ssd`` for the readers that go by substring."""
    from horovod_tpu.metrics import device_profile

    found = scan_kernel_op_names
    name = names.SSD_SCAN + kernel
    assert name not in names.ALL
    assert not [n for n in found if n.endswith(f"{names.SSD_SCAN}/while")]
    inside = f"{name}/pallas_call"
    assert inside in found
    sites = [n for n in found if n.endswith(f"/jit({call})")]
    site, = [n for n in sites if {
        "forward": "transpose(jvp(" not in n and "rematted" not in n,
        "recomputed": "/checkpoint/rematted_computation/" in n,
        "backward": "transpose(jvp(" in n and "rematted" not in n}[where]]
    assert f"/mixer/{names.SSD_SCAN}/" in site + "/"
    op_name = f"{site}/{inside}"
    assert device_profile.name_of(op_name) == names.SSD_SCAN
    assert "hvd_ssd" in "/".join(op_name.split("/")[-3:])


# ------------------------------- latent attention's and the shared expert's

MLA_SCOPES = [names.MLA_PROJ, names.MLA_ROPE, names.MOE_SHARED]


@pytest.fixture(scope="module")
def mla_op_names():
    """The ``op_name``s of a tiny latent-attention mixture of experts'
    gradients (a dense layer, then an expert layer holding a share of the
    experts; recomputation on, as the cell runs), and the text without them."""
    import re

    from horovod_tpu.models import BIAS_COLLECTION, LatentDims, TransformerLM

    model = TransformerLM(
        vocab=64, dim=32, heads=4, layers=2, dtype=jnp.float32, remat=True,
        mla=LatentDims(kv_rank=16, qk_nope=8, qk_rope=4, v=8), rope_theta=1e6,
        rope_interleave=True, first_k_dense=1, mlp_hidden=48, moe_experts=8,
        moe_every=1, moe_top_k=2, moe_hidden=16, moe_router="sigmoid",
        moe_route_scale=2.448, moe_shared_hidden=24, moe_held=(2, 2))
    tokens = jnp.zeros((1, 32), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens)
    lowered = jax.jit(jax.grad(lambda p: model.apply(
        {"params": p, BIAS_COLLECTION: variables[BIAS_COLLECTION]},
        tokens).sum())).lower(variables["params"])
    found = set(re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True)))
    return found, lowered.as_text(debug_info=False)


def test_the_new_names_are_what_the_benchmark_looks_for():
    assert (names.MLA_PROJ, names.MLA_ROPE, names.MOE_SHARED) == (
        "hvd_mla_proj", "hvd_mla_rope", "hvd_moe_shared")
    # the flash kernels keep their names: the cell's readers find them by
    # ``hvd_flash_`` and the mixer by ``hvd_mla``
    assert (names.FLASH_FWD, names.FLASH_BWD_DQ, names.FLASH_BWD_DKV) == (
        "hvd_flash_fwd", "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv")
    assert names.MOE_EXPERTS not in names.MOE_SHARED     # not the experts' time


@pytest.mark.parametrize("backward", [False, True], ids=["forward", "backward"])
@pytest.mark.parametrize("name", MLA_SCOPES)
def test_mla_scope_survives_the_breakdowns_label(name, backward, mla_op_names):
    """In the module as metadata only, on forward and backward operations,
    and still there in what ``benchmarks/reduce_trace.op_label`` keeps of an
    ``op_name``: its last three segments."""
    found, bare = mla_op_names
    assert name not in bare
    kept = {"/".join(n.split("/")[-3:]) for n in found
            if ("transpose(jvp(" in n) is backward}
    assert any(name in label for label in kept), sorted(kept)[:20]


def test_the_four_projections_and_the_latents_norm_are_under_mla_proj(
        mla_op_names):
    found, _ = mla_op_names
    for leaf in ("q_proj", "kv_a_proj", "kv_a_norm", "kv_b_proj", "o_proj"):
        assert any(f"{names.MLA_PROJ}/{leaf}" in n for n in found), leaf
    for leaf in ("shared_gate", "shared_up", "shared_down"):
        assert any(f"{names.MOE_SHARED}/{leaf}" in n for n in found), leaf


LATENT_SCOPES = [names.MOE_LATENT, names.MTP, names.MOE_SHARED,
                 names.MOE_EXPERTS, names.SSD_SCAN]


@pytest.fixture(scope="module")
def latent_moe_op_names():
    """The ``op_name``s of a tiny one-sub-layer hybrid's gradients (a Mamba-2
    layer, an expert layer of relu² experts in a latent holding a share of
    them, an attention layer, and the multi-token-prediction module;
    recomputation on, as the cell runs; both losses through the shared head),
    and the text without them."""
    import re

    from horovod_tpu.models import BIAS_COLLECTION, TransformerLM
    from horovod_tpu.models.mamba import Mamba2Dims
    from horovod_tpu.models.transformer import lm_loss_with_mtp

    model = TransformerLM(
        vocab=64, dim=32, heads=4, kv_heads=1, head_dim=8, layers=3,
        layer_types=("mamba_only", "experts_only", "attention_only"),
        mtp_layer_types=("attention_only", "experts_only"),
        mamba=Mamba2Dims(heads=4, head_dim=4, state=8, chunk=4), rope=False,
        moe_experts=8, moe_top_k=3, moe_hidden=16, moe_router="sigmoid",
        moe_route_scale=5.0, moe_shared_hidden=24, moe_held=(2, 2),
        moe_activation="relu2", moe_latent=16, remat=True, dtype=jnp.float32)
    tokens = jnp.zeros((1, 64), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens)

    def loss(p):
        hidden = model.apply({"params": p,
                              BIAS_COLLECTION: variables[BIAS_COLLECTION]},
                             tokens, return_hidden=True)
        return lm_loss_with_mtp(*hidden, p["lm_head"]["kernel"], tokens, 0.3,
                                32)[0]

    lowered = jax.jit(jax.grad(loss)).lower(variables["params"])
    found = set(re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True)))
    return found, lowered.as_text(debug_info=False)


def test_the_latent_and_mtp_names_are_what_the_benchmark_looks_for():
    assert (names.MOE_LATENT, names.MTP) == ("hvd_moe_latent", "hvd_mtp")
    assert {names.MOE_LATENT, names.MTP} <= set(names.ALL)
    # found by equality in a device profile: neither is a prefix of another
    assert not [n for n in names.ALL if n != names.MTP and names.MTP in n]
    assert names.MOE_EXPERTS not in names.MOE_LATENT    # not the experts' time


@pytest.mark.parametrize("backward", [False, True], ids=["forward", "backward"])
@pytest.mark.parametrize("name", LATENT_SCOPES)
def test_latent_moe_scope_survives_the_breakdowns_label(name, backward,
                                                        latent_moe_op_names):
    """In the module as metadata only, on forward and backward operations,
    and still there in what ``benchmarks/reduce_trace.op_label`` keeps of an
    ``op_name``: its last three segments."""
    found, bare = latent_moe_op_names
    assert name not in bare
    kept = {"/".join(n.split("/")[-3:]) for n in found
            if ("transpose(jvp(" in n) is backward}
    assert any(name in label for label in kept), sorted(kept)[:20]


def test_the_latent_projections_and_the_modules_parts_are_under_their_names(
        latent_moe_op_names):
    found, _ = latent_moe_op_names
    for leaf in ("fc1_latent", "fc2_latent"):
        assert any(f"{names.MOE_LATENT}/{leaf}" in n for n in found), leaf
    for leaf in ("mtp_hidden_norm", "mtp_embed_norm", "mtp_proj", "mtp_norm"):
        assert any(f"{names.MTP}/{leaf}" in n for n in found), leaf
    # experts without a gate: the shared expert has no gate either
    assert any(f"{names.MOE_SHARED}/shared_up" in n for n in found)
    assert not any("shared_gate" in n for n in found)
    # the module's own blocks go by the blocks' names, not by the module's
    assert not any(f"{names.MTP}/mtp_block" in n for n in found)
    # the module's loss pass: a second chunked loss under the module's name
    assert any(names.MTP in n and "while" in n for n in found)


# ------------------------------------- the rest of the step (ISSUE 50's names)

STEP_NAMES = [names.MLP, names.ATTN_PROJ, names.ATTN, names.NORM_ADD,
              names.EMBED, names.LM_HEAD, names.MOE_LOGITS,
              names.MOE_WEIGHT_CAST]
OLDER_NAMES = tuple(n for n in names.ALL if n not in STEP_NAMES)


def _locs(lowered):
    import re

    return set(re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True)))


@pytest.fixture(scope="module")
def step_names_text():
    """A tiny bf16 model with every half the new names go round: a dense
    layer (gelu MLP), then an expert layer; multi-head attention through the
    dense einsum path; an untied head that returns logits; recomputation on."""
    from horovod_tpu.models import TransformerLM

    model = TransformerLM(
        vocab=64, dim=32, heads=4, layers=2, dtype=jnp.bfloat16, remat=True,
        tie_embeddings=False, first_k_dense=1, moe_experts=4, moe_every=1,
        moe_top_k=2, moe_hidden=16)
    tokens = jnp.zeros((1, 32), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    lowered = jax.jit(jax.grad(lambda p: model.apply(
        {"params": p}, tokens).astype(jnp.float32).sum())).lower(params)
    return {True: lowered.as_text(debug_info=True),
            False: lowered.as_text(debug_info=False), "locs": _locs(lowered)}


def test_the_step_names_are_what_the_benchmark_looks_for():
    assert STEP_NAMES == [
        "hvd_mlp", "hvd_attn_proj", "hvd_attn", "hvd_norm_add", "hvd_embed",
        "hvd_lm_head", "hvd_moe_logits", "hvd_moe_weight_cast"]
    assert set(STEP_NAMES) <= set(names.ALL)
    # the readers that go by substring over the breakdown's labels
    # (benchmarks/moe_cost.py, ssd_cost.py, mla_cost.py) find none of them
    for name in STEP_NAMES:
        for held in ("hvd_moe_experts", "hvd_mamba", "hvd_ssd", "hvd_mla",
                     "hvd_flash_"):
            assert held not in name, (held, name)


@pytest.mark.parametrize("name", STEP_NAMES)
def test_step_name_is_in_the_lowered_module_as_metadata_only(
        name, step_names_text):
    assert name in step_names_text[True]
    assert name not in step_names_text[False]
    assert "dot_general" in step_names_text[False]  # the work itself is there


@pytest.mark.parametrize("name,leaves", [
    (names.MLP, ("mlp_in/dot_general", "mlp_out/dot_general")),
    (names.ATTN_PROJ, ("qkv/dot_general", "o_proj/dot_general")),
    (names.NORM_ADD, ("RMSNorm_0/rsqrt", "RMSNorm_1/rsqrt", "add")),
    (names.EMBED, ("embed/jit(_take)",)),
    (names.LM_HEAD, ("lm_head/dot_general",)),
    (names.MOE_LOGITS, ("dot_general", "convert_element_type")),
    (names.MOE_WEIGHT_CAST, ("convert_element_type",)),
])
def test_a_step_name_goes_round_its_ops_forward_and_backward(
        name, leaves, step_names_text):
    from horovod_tpu.metrics import device_profile

    found = step_names_text["locs"]
    for leaf in leaves:
        sites = [n for n in found if n.endswith(f"{name}/{leaf}")]
        assert sites, (name, leaf)
        assert {device_profile.name_of(n) for n in sites} == {name}
        if leaf != "rsqrt":
            assert any("transpose(jvp(" in n for n in sites), (name, leaf)


def test_attention_is_an_outer_scope_and_its_projections_an_inner_one(
        step_names_text):
    """The dense path's einsums read under ``hvd_attn``; the projections
    inside it under their own name, the LAST on the path."""
    from horovod_tpu.metrics import device_profile

    found = step_names_text["locs"]
    einsums = [n for n in found if "bqhd,bkhd->bhqk/dot_general" in n]
    assert einsums
    assert {device_profile.name_of(n) for n in einsums} == {names.ATTN}
    inner = [n for n in found if n.endswith("o_proj/dot_general")]
    assert inner and all(
        f"/{names.ATTN}/" in n and device_profile.name_of(n) == names.ATTN_PROJ
        for n in inner)


@pytest.fixture(scope="module")
def sparse_op_names():
    """The ``op_name``s of a tiny sparse-attention mixture of experts'
    gradients (both of its losses; recomputation on, as the cell runs)."""
    from horovod_tpu.models import (RotaryScheme, SparseDims, TransformerLM,
                                    align_losses)

    model = TransformerLM(
        vocab=64, dim=32, heads=4, kv_heads=2, head_dim=16, layers=2,
        dtype=jnp.float32, attention="flash", flash_interpret=True,
        block_q=32, block_k=32, qk_head_norm=True, rope_theta=1e4,
        rotary=RotaryScheme(theta=1e4, sections=(2, 3, 3)),
        sparse=SparseDims(index_heads=2, index_dim=8, topk=12, kv_chunk=16,
                          q_chunk=16),
        moe_experts=4, moe_top_k=2, moe_hidden=16, moe_every=1,
        moe_norm_topk=True, remat=True)
    tokens = jnp.zeros((1, 64), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]

    def loss(p):
        logits, state = model.apply({"params": p}, tokens,
                                    mutable=["intermediates"])
        return logits.sum() + align_losses(state["intermediates"])[0]

    return _locs(jax.jit(jax.grad(loss)).lower(params)), None


@pytest.mark.parametrize("fixture,older", [
    ("latent_moe_op_names", (names.MTP, names.MOE_LATENT, names.MOE_SHARED,
                             names.MOE_ROUTE, names.MAMBA_PROJ)),
    ("mla_op_names", (names.MLA_PROJ, names.MLA_ROPE, names.MOE_SHARED,
                      names.MOE_ROUTE, names.MOE_DISPATCH)),
    ("sparse_op_names", (names.DSA_INDEXER, names.DSA_SELECT, names.DSA_ALIGN,
                         names.ATTN_ROPE, names.FLASH_SEL_FWD,
                         names.FLASH_SEL_BWD_DQ)),
    ("mamba_op_names", (names.MAMBA_PROJ, names.SSD_SCAN)),
])
def test_no_new_scope_is_inside_an_older_one(fixture, older, request):
    """``name_of`` takes the LAST name on a path, so a new name under an
    older scope would take its time: wherever an older name won before the
    new ones were registered, it still wins. On models with a
    multi-token-prediction module, experts in a latent and a shared expert;
    latent attention; a learned selection; a Mamba-2 mixer."""
    from horovod_tpu.metrics import device_profile

    found, _ = request.getfixturevalue(fixture)
    won = {n: device_profile.name_of(n, names=OLDER_NAMES) for n in found}
    assert {w for w in won.values() if w} >= set(older)
    for op_name, before in won.items():
        if before is not None:
            assert device_profile.name_of(op_name) == before, op_name
    # and the new names are there, on paths that had no name
    assert {device_profile.name_of(n) for n in found} & set(STEP_NAMES)


def test_the_modules_own_work_keeps_the_modules_name(latent_moe_op_names):
    """``lm_loss_with_mtp`` names its MAIN pass ``hvd_lm_head``; the module's
    pass of the shared head, its norms, its projection and its embedding of
    the next token stay ``hvd_mtp``'s, and the latent's projections stay
    ``hvd_moe_latent``'s."""
    from horovod_tpu.metrics import device_profile

    found, _ = latent_moe_op_names
    # the two loss loops: whole paths outside every block
    loops = {device_profile.name_of(n) for n in found if n.endswith("/while")
             and n.startswith("jit(") and "block_" not in n}
    assert loops == {names.LM_HEAD, names.MTP}
    for n in found:
        assert not (names.MTP in n and any(new in n for new in STEP_NAMES)), n
    for leaf in ("mtp_hidden_norm", "mtp_embed_norm", "mtp_proj", "mtp_norm",
                 "embed"):
        assert any(f"{names.MTP}/{leaf}" in n for n in found), leaf
    assert any(f"{names.EMBED}/embed" in n for n in found)  # the main lookup
