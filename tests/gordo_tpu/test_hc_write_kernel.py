"""The residual streams' write as a Pallas kernel pair
(``ops/pallas_kernels/hc_write.py``), run by the Pallas interpreter: against
the jnp body of ``ops/nn.py _hc_write`` in value and in the gradients of all
four inputs, under ``vmap``, and where ``_hc_write_kernel_ok`` sends a write
to it and counts it."""

import dataclasses
import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gordo_tpu.models.factories.latent import latent_moe_model
from gordo_tpu.ops import nn

kernel_module = importlib.import_module("gordo_tpu.ops.pallas_kernels.hc_write")
interpreted = functools.partial(kernel_module.hc_write, interpret=True)

N, B, T, D = 4, 2, 256, 768  # two token tiles of 256, three lane tiles of 256


def _inputs(dtype, lead=()):
    k = jax.random.split(jax.random.PRNGKey(40), 5)
    res = jax.random.uniform(k[0], lead + (N, N, B, T), jnp.float32)
    post = 2.0 * jax.random.uniform(k[1], lead + (N, B, T), jnp.float32)
    x = jax.random.normal(k[2], lead + (N, B, T, D)).astype(dtype)
    y = jax.random.normal(k[3], lead + (B, T, D)).astype(dtype)
    w = jax.random.normal(k[4], lead + (N, B, T, D))  # a loss that weighs every output
    return res, post, x, y, w


def _value_and_grads(write, res, post, x, y, w):
    def loss(res, post, x, y):
        return jnp.sum(write(res, post, x, y).astype(jnp.float32) * w)

    return write(res, post, x, y), jax.grad(loss, argnums=(0, 1, 2, 3))(res, post, x, y)


def _close(got, want, dtype, err_msg=""):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape, err_msg
    if dtype == jnp.float32:
        rtol = 1e-5
    else:  # one rounding to bfloat16 on either side of a reordered float32 sum
        rtol = 2.0**-7
    np.testing.assert_allclose(got, want, rtol=rtol, atol=1e-5 * np.abs(want).max(), err_msg=err_msg)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_kernel_is_the_jnp_write_in_value_and_gradients(dtype):
    res, post, x, y, w = _inputs(dtype)
    # the coefficients' gradients add up over more than one lane tile
    assert kernel_module.tiles(B * T, D) and B * T // kernel_module.TOKEN_TILE == 2
    assert D // kernel_module._lane_tile(D) == 3
    out, grads = _value_and_grads(interpreted, res, post, x, y, w)
    want_out, want_grads = _value_and_grads(nn._hc_write, res, post, x, y, w)
    assert out.dtype == x.dtype
    _close(out, want_out, dtype, "value")
    for name, got, want, arg in zip(("res", "post", "x", "y"), grads, want_grads, (res, post, x, y)):
        assert got.dtype == arg.dtype, name
        # the coefficients' gradients are float32 sums over d in either dtype
        _close(got, want, jnp.float32 if name in ("res", "post") else dtype, name)


def test_kernel_under_vmap_over_machines():
    """The fleet's chunk program maps a machine axis over the block: the
    kernel's batching rule gives each machine its own grid."""
    res, post, x, y, w = _inputs(jnp.bfloat16, lead=(2,))
    got = jax.vmap(functools.partial(_value_and_grads, interpreted))(res, post, x, y, w)
    want = jax.vmap(functools.partial(_value_and_grads, nn._hc_write))(res, post, x, y, w)
    _close(got[0], want[0], jnp.bfloat16, "value")
    for name, g, h in zip(("res", "post", "x", "y"), got[1], want[1]):
        _close(g, h, jnp.float32 if name in ("res", "post") else jnp.bfloat16, name)


@pytest.mark.parametrize(
    "tokens,d,ok",
    [((2, 256), 256, True), ((16, 256), 3584, True), ((16, 1), 3584, False),
     ((3, 100), 256, False), ((2, 256), 200, False)],
    ids=["tiles", "cell", "last-position", "tokens", "width"],
)
def test_the_shapes_choose_the_path(monkeypatch, tokens, d, ok):
    """On a TPU the write takes the kernel where its tokens are whole tiles and
    its width whole 128-lane vectors; the kernel itself refuses the rest."""
    x = jax.ShapeDtypeStruct((N,) + tokens + (d,), jnp.bfloat16)
    assert not nn._hc_write_kernel_ok(x)  # not on this backend, at any shape
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert nn._hc_write_kernel_ok(x) is ok
    monkeypatch.undo()
    if not ok:
        res = jnp.zeros((N, N) + tokens)
        with pytest.raises(ValueError, match="whole tiles"):
            interpreted(res, res[0], jnp.zeros(x.shape, x.dtype), jnp.zeros(x.shape[1:], x.dtype))


MODEL = {
    "d_model": 128, "ff_dim": 96, "expert_dim": 32, "num_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_theta": 10000.0,
    "num_experts": 8, "experts_held": 4, "expert_offset": 2, "top_k": 2,
    "shared_experts": 1, "routed_scale": 2.0, "streams": 4, "sinkhorn_iters": 20,
    "hc_eps": 1e-6, "hc_clamp": 30.0, "norm_eps": 1e-6, "lookback_window": 32,
    "ffns": ["dense", "routed"],
}
N_TAGS, BATCH = 4, 8  # 8 windows of 32: a step's 256 tokens are one tile


@pytest.fixture
def kernel_on(monkeypatch):
    """Calling it sends every write whose shapes tile to the interpreted
    kernel, as a TPU would send it to the compiled one. The choice is made
    when a block is traced, so the traces are dropped on the way in and out."""

    def turn_on():
        monkeypatch.setattr(
            nn, "_hc_write_kernel_ok",
            lambda x: kernel_module.tiles(x.shape[1] * x.shape[2], x.shape[3]),
        )
        monkeypatch.setattr(kernel_module, "hc_write", interpreted)
        jax.clear_caches()

    yield turn_on
    monkeypatch.undo()
    jax.clear_caches()


def _epoch_stats(spec):
    from gordo_tpu.ops.train import make_masked_epoch_fn, make_optimizer

    params = nn.init_model_params(jax.random.PRNGKey(1), spec)
    rows = spec.lookback_window + 2 * BATCH - 1  # 16 windows: two steps of eight
    X = jax.random.normal(jax.random.PRNGKey(2), (rows, N_TAGS))
    n_max = rows - spec.lookback_window + 1
    epoch = jax.jit(make_masked_epoch_fn(spec, n_max, BATCH, shuffle=True))
    opt_state = make_optimizer(spec.optimizer).init(params)
    new_params, _, loss, stats = epoch(params, opt_state, X, X, jax.random.PRNGKey(3), n_max)
    return new_params, float(loss), stats


def test_a_latent_build_counts_the_writes_that_took_the_kernel(kernel_on):
    """Two latent blocks, the second at the last position alone: a step's
    first two writes (256 tokens) take the kernel and the trimmed block's two
    (8 tokens) the jnp body, so a compiled epoch of two live steps counts 4
    of its 8 sublayer-steps. The same epoch through the jnp body alone counts
    none and trains to the same parameters."""
    spec = latent_moe_model(N_TAGS, attention="xla", **MODEL)
    assert nn.zero_stats(spec)["hc_write_kernel_steps"].dtype == jnp.int32
    plain_params, plain_loss, plain = _epoch_stats(spec)
    assert int(plain["hc_write_kernel_steps"]) == 0 and int(plain["hc_sublayer_steps"]) == 8

    kernel_on()
    params, loss, stats = _epoch_stats(spec)
    assert int(stats["hc_write_kernel_steps"]) == 2 * 2
    assert int(stats["hc_sublayer_steps"]) == 2 * 4
    np.testing.assert_allclose(loss, plain_loss, rtol=1e-5)
    for got, want in zip(jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(plain_params)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)


def test_a_hybrid_spec_counts_no_kernel_writes():
    from gordo_tpu.models.factories.hybrid import hybrid_moe_model

    spec = hybrid_moe_model(
        N_TAGS, d_model=32, ff_dim=48, expert_dim=16, num_heads=4, num_kv_heads=2,
        num_experts=8, experts_held=4, top_k=2, operators=["attention", "conv"],
        ffns=["dense", "routed"], lookback_window=12, attention="xla",
    )
    assert "hc_write_kernel_steps" not in nn.zero_stats(spec)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, N_TAGS))
    _, _, counted = nn.apply_model_stats(
        spec, nn.init_model_params(jax.random.PRNGKey(1), spec), x
    )
    assert "hc_write_kernel_steps" not in counted and "moe_tokens" in counted


def test_the_count_reaches_its_counter():
    """``_note_layer_counts`` adds a chunk's live lanes' kernel writes to
    ``gordo_build_hc_write_kernel_steps_total`` beside the sublayer-steps."""
    from gordo_tpu.observability import metrics as metric_catalog
    from gordo_tpu.parallel import batch_trainer

    before = (
        metric_catalog.HC_WRITE_KERNEL_STEPS.value(),
        metric_catalog.HC_SUBLAYER_STEPS.value(),
    )
    counted = {
        "hc_sublayer_steps": np.array([1200, 1200, 1200], np.int32),
        "hc_stochastic_gap": np.zeros(3, np.float32),
        "hc_write_kernel_steps": np.array([960, 960, 960], np.int32),
    }
    batch_trainer._note_layer_counts(counted, rows=[0, 1, 2], n_live=2)  # lane 2 pads
    assert metric_catalog.HC_WRITE_KERNEL_STEPS.value() - before[0] == 1920
    assert metric_catalog.HC_SUBLAYER_STEPS.value() - before[1] == 2400


def test_the_cells_spec_takes_the_kernel_for_eight_writes_of_ten(monkeypatch):
    """``xing4_0_29b_a4b``'s five latent blocks at 16 windows of 256 on a TPU:
    every full-size write tiles, the last block's two at 16 tokens do not."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    full = jax.ShapeDtypeStruct((4, 16, 256, 3584), jnp.bfloat16)
    last = jax.ShapeDtypeStruct((4, 16, 1, 3584), jnp.bfloat16)
    writes = [full] * 8 + [last] * 2
    assert sum(nn._hc_write_kernel_ok(x) for x in writes) == 8


def test_a_rematerialised_model_differentiates_through_the_kernel(kernel_on):
    """With ``remat`` each block is recomputed in the backward pass, the
    kernel's forward with it: the loss and every leaf's gradient are the jnp
    body's, and the count is the same two writes."""
    spec = dataclasses.replace(latent_moe_model(N_TAGS, attention="xla", **MODEL), remat=True)
    x = jax.random.normal(jax.random.PRNGKey(0), (BATCH, 32, N_TAGS))
    target = jax.random.normal(jax.random.PRNGKey(4), (BATCH, N_TAGS))
    params = nn.init_model_params(jax.random.PRNGKey(1), spec)

    def loss(p):
        out, _, counted = nn.apply_model_stats(spec, p, x)
        return jnp.mean((out - target) ** 2), counted

    (want, plain), want_grads = jax.value_and_grad(loss, has_aux=True)(params)
    kernel_on()
    (got, counted), grads = jax.value_and_grad(loss, has_aux=True)(params)
    assert int(plain["hc_write_kernel_steps"]) == 0 and int(counted["hc_write_kernel_steps"]) == 2
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    for layer, want_layer in zip(grads, want_grads):
        # a leaf whose gradient nearly cancels (a mixing bias) is held to
        # the rounding of its layer's largest terms, not to its own size
        scale = max((float(np.abs(np.asarray(g)).max()) for g in want_layer.values()), default=0)
        for name in layer:
            np.testing.assert_allclose(
                np.asarray(layer[name]), np.asarray(want_layer[name]),
                rtol=1e-4, atol=1e-5 * scale, err_msg=name,
            )
