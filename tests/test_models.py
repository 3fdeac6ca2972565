"""Model zoo shape/grad sanity — every benchmark family the reference
measures (ResNet, VGG, Inception; BASELINE.md) plus the long-context
transformer builds, runs forward, and produces finite gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu import models as zoo


@pytest.mark.parametrize("name,image", [
    ("ResNet18", 32),
    ("ResNet50", 64),
    ("VGG16", 32),
    ("InceptionV3", 96),
])
@pytest.mark.slow
def test_cnn_forward_and_grad(name, image):
    model = getattr(zoo, name)(num_classes=10)
    x = jnp.ones((2, image, image, 3), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    out = model.apply(variables, x, train=False)
    assert out.shape == (2, 10)
    assert out.dtype == jnp.float32
    assert np.isfinite(np.asarray(out)).all()

    def loss(params):
        logits, _ = model.apply(
            {"params": params, "batch_stats": variables["batch_stats"]},
            x, train=True, mutable=["batch_stats"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, jnp.zeros((2,), jnp.int32)).mean()

    grads = jax.jit(jax.grad(loss))(variables["params"])
    flat = jax.tree_util.tree_leaves(grads)
    assert all(np.isfinite(np.asarray(g)).all() for g in flat)
    assert any(float(jnp.abs(g).max()) > 0 for g in flat)


def test_transformer_forward():
    model = zoo.TransformerLM(vocab=64, dim=32, heads=4, layers=2)
    tokens = jnp.ones((2, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)
    logits = model.apply(params, tokens)
    assert logits.shape == (2, 16, 64)
    assert np.isfinite(np.asarray(logits)).all()


def test_mlp_and_convnet():
    for model, shape in ((zoo.MLP(), (2, 28, 28)), (zoo.ConvNet(), (2, 28, 28, 1))):
        x = jnp.ones(shape, jnp.float32)
        params = model.init(jax.random.PRNGKey(0), x)
        out = model.apply(params, x)
        assert out.shape == (2, 10)


@pytest.mark.slow
def test_resnet_space_to_depth_stem():
    """s2d stem: same output shape and downsampling as the 7x7/s2 stem,
    trains (finite grads) — the MXU-friendly MLPerf stem variant."""
    model = zoo.ResNet18(num_classes=10, space_to_depth=True)
    x = jnp.ones((2, 32, 32, 3), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    out = model.apply(variables, x, train=False)
    assert out.shape == (2, 10)
    # conv_init sees 12 channels (2x2 s2d of RGB) with a 4x4 kernel
    k = variables["params"]["conv_init"]["kernel"]
    assert k.shape == (4, 4, 12, 64)
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.slow
def test_transformer_remat_matches_no_remat():
    """jax.checkpoint on the blocks must not change loss or gradients —
    only the activation-memory/FLOPs trade. Covers composition with the
    flash-attention custom_vjp (checkpoint replays its forward)."""
    import optax

    from horovod_tpu.models import TransformerLM

    tok = jax.random.randint(jax.random.PRNGKey(4), (2, 64), 0, 64)
    kw = dict(vocab=64, dim=32, heads=4, layers=2, dtype=jnp.float32,
              attention="flash", flash_interpret=True)
    plain = TransformerLM(**kw)
    remat = TransformerLM(**kw, remat=True)
    params = plain.init(jax.random.PRNGKey(0), tok)["params"]

    def loss(model, params):
        logits = model.apply({"params": params}, tok)
        targets = jnp.roll(tok, -1, axis=1)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, targets).mean()

    with jax.default_matmul_precision("highest"):
        l0, g0 = jax.value_and_grad(lambda p: loss(plain, p))(params)
        l1, g1 = jax.value_and_grad(lambda p: loss(remat, p))(params)
    np.testing.assert_allclose(float(l1), float(l0), atol=1e-6, rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5), g1, g0)


@pytest.mark.slow
def test_chunked_lm_loss_matches_full():
    """Chunked loss head under a whole model: identical loss AND gradients
    to the full-logits path (the gradients come out of the loss's forward
    loop; the tier-1 cases on a bare head are below)."""
    import optax

    from horovod_tpu.models import TransformerLM
    from horovod_tpu.models.transformer import chunked_lm_loss

    tok = jax.random.randint(jax.random.PRNGKey(4), (2, 64), 0, 64)
    model = TransformerLM(vocab=64, dim=32, heads=4, layers=2,
                          dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0), tok)["params"]
    targets = jnp.roll(tok, -1, axis=1)

    def full(params):
        logits = model.apply({"params": params}, tok)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, targets).mean()

    def chunked(params):
        hidden = model.apply({"params": params}, tok, return_hidden=True)
        return chunked_lm_loss(hidden, params["lm_head"]["kernel"],
                               targets, chunk=16)

    with jax.default_matmul_precision("highest"):
        l0, g0 = jax.value_and_grad(full)(params)
        l1, g1 = jax.value_and_grad(chunked)(params)
    np.testing.assert_allclose(float(l1), float(l0), atol=1e-6, rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5), g1, g0)


def _tiny_head(dtype, b, t=16, d=8, vocab=40):
    hidden = jax.random.normal(jax.random.PRNGKey(0), (b, t, d), dtype)
    kernel = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (d, vocab))
    targets = jax.random.randint(jax.random.PRNGKey(2), (b, t), 0, vocab)
    return hidden, kernel, targets


def _full_logits_loss(hidden, kernel, targets):
    return optax.softmax_cross_entropy_with_integer_labels(
        hidden.astype(jnp.float32) @ kernel, targets).mean()


def _assert_head_grads(got, want, hidden_dtype, scale=1.0):
    assert got[0].dtype == hidden_dtype and got[1].dtype == jnp.float32
    # bf16 hidden: d_hidden is rounded to bf16 (eps 2^-8) on both sides.
    tol = 1e-5 if hidden_dtype == jnp.float32 else 2 ** -7
    np.testing.assert_allclose(np.asarray(got[0], np.float32),
                               scale * np.asarray(want[0], np.float32),
                               atol=tol * 1e-2, rtol=tol)
    np.testing.assert_allclose(np.asarray(got[1]), scale * np.asarray(want[1]),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("b", [1, 3])
@pytest.mark.parametrize("chunks", [1, 2, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_chunked_lm_loss_and_gradients_match_full_logits(tied, dtype, chunks,
                                                         b):
    """The loss (differentiated or not) and both gradients of the chunked
    head against optax's cross entropy on the full logits. ``tied``: the
    kernel is a TRANSPOSED ``(vocab, d)`` embedding (lfm2's and granite's
    call) and the gradient is the embedding's; at ``b`` = 3 over several
    chunks that holds the un-merge of ``d_hidden``'s rows as well."""
    from horovod_tpu.models.transformer import chunked_lm_loss

    hidden, kernel, targets = _tiny_head(dtype, b)
    chunk = hidden.shape[1] // chunks
    turned = jnp.transpose if tied else (lambda w: w)
    kernel = turned(kernel)

    def full(hidden, kernel, targets):
        return _full_logits_loss(hidden, turned(kernel), targets)

    def chunked(hidden, kernel, targets, chunk):
        return chunked_lm_loss(hidden, turned(kernel), targets, chunk)

    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.value_and_grad(full, (0, 1))(
            hidden, kernel, targets)
        plain = chunked(hidden, kernel, targets, chunk)
        got, got_grads = jax.value_and_grad(chunked, (0, 1))(
            hidden, kernel, targets, chunk)
    np.testing.assert_allclose(float(plain), float(want), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(float(got), float(want), atol=1e-5, rtol=1e-5)
    assert got_grads[1].shape == kernel.shape
    _assert_head_grads(got_grads, want_grads, dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_chunked_lm_loss_scales_with_its_cotangent(dtype):
    from horovod_tpu.models.transformer import chunked_lm_loss

    hidden, kernel, targets = _tiny_head(dtype, 3)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(_full_logits_loss, (0, 1))(hidden, kernel, targets)
        got = jax.grad(lambda h, w: 3.0 * chunked_lm_loss(h, w, targets, 4),
                       (0, 1))(hidden, kernel)
    _assert_head_grads(got, want, dtype, scale=3.0)


@pytest.mark.parametrize("chunk,message", [
    (0, "loss chunk must be positive, got 0"),
    (5, "sequence 16 not divisible by loss chunk 5"),
])
def test_chunked_lm_loss_refuses_a_chunk_that_does_not_fit(chunk, message):
    from horovod_tpu.models.transformer import chunked_lm_loss

    with pytest.raises(ValueError, match=message):
        chunked_lm_loss(*_tiny_head(jnp.float32, 1), chunk)


def test_chunked_lm_loss_issues_three_products_a_chunk_and_no_remat():
    """The mechanism: differentiated, the one loop over chunks holds the
    logits product and the two gradient products and nothing is checkpointed
    (no logits computed again); not differentiated, the logits product
    alone. The gauge says which of the two was traced last."""
    from horovod_tpu.metrics import registry
    from horovod_tpu.models.transformer import chunked_lm_loss

    hidden, kernel, targets = _tiny_head(jnp.bfloat16, 3)

    def gauge():
        return registry().snapshot()["gauges"][
            "horovod_chunked_loss_products_per_chunk"]

    def loss(h, w):
        return chunked_lm_loss(h, w, targets, 4)

    # The loop is rolled: its body, and so each product, is printed once.
    text = str(jax.make_jaxpr(jax.grad(loss, (0, 1)))(hidden, kernel))
    assert text.count(" scan[") == 1 and text.count("dot_general[") == 3
    assert "remat" not in text and "checkpoint" not in text
    assert gauge() == 3
    text = str(jax.make_jaxpr(loss)(hidden, kernel))
    assert text.count(" scan[") == 1 and text.count("dot_general[") == 1
    assert gauge() == 1
