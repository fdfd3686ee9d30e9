"""The latent block (latent attention with heads of two widths, residual
streams mixed by a Sinkhorn-projected matrix, a shared expert beside the routed
ones) at a small size, float32, seeded: against the plain model
``chipbench/configs/xing_moe.py``, and what each mechanism promises."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference
from chipbench.configs import xing_moe
from gordo_tpu.models.factories.latent import latent_moe_model
from gordo_tpu.models.spec import LatentBlock
from gordo_tpu.ops import attention, flops, nn

YARN = {
    "type": "yarn", "factor": 64, "original_max_position_embeddings": 4096,
    "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
}
MODEL = {
    "d_model": 64, "ff_dim": 96, "expert_dim": 32, "num_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_theta": 10000.0,
    "rope_scaling": YARN, "num_experts": 8, "experts_held": 4, "expert_offset": 2,
    "top_k": 2, "shared_experts": 1, "routed_scale": 2.0, "streams": 4,
    "sinkhorn_iters": 20, "hc_eps": 1e-6, "hc_clamp": 30.0, "norm_eps": 1e-6,
    "lookback_window": 12,
}
N_TAGS = 4


def _model(ffns, **over):
    return dict(MODEL, ffns=list(ffns), **over)


def _spec(model):
    return latent_moe_model(N_TAGS, attention="xla", **model)


def _stirred(params):
    """Weights far enough from their small start that every term matters."""
    return jax.tree_util.tree_map(
        lambda a: a + 0.3 * jax.random.normal(jax.random.PRNGKey(a.size), a.shape), params
    )


@pytest.mark.parametrize("ffn,streams", [("dense", 2), ("routed", 4), ("routed", 2)])
def test_block_against_the_plain_model(ffn, streams):
    model = _model([ffn], streams=streams)
    spec = _spec(model)
    key = jax.random.PRNGKey(5)
    params = nn.init_model_params(key, spec)
    plain = xing_moe.init_params(key, model, N_TAGS)
    # the same recipe, key for key; to a rounding of the last bit, because the
    # plain model draws its whole tree in one compiled program, where the
    # compiler multiplies the normal's two factors into one
    assert [sorted(p) for p in params] == [sorted(p) for p in plain]
    for ours, theirs in zip(params, plain):
        for name in ours:
            assert ours[name].dtype == theirs[name].dtype and not theirs[name].weak_type, name
            np.testing.assert_allclose(
                np.asarray(ours[name]), np.asarray(theirs[name]), rtol=2.5e-7, atol=0, err_msg=name
            )
    params = _stirred(params)
    x = jax.random.normal(jax.random.PRNGKey(6), (3, 12, N_TAGS))
    y = jax.random.normal(jax.random.PRNGKey(7), (3, N_TAGS))
    mm = reference.matmul("float32")

    def ours_loss(p):
        return jnp.mean((nn.apply_model(spec, p, x)[0] - y) ** 2)

    def plain_loss(p):
        return jnp.mean((xing_moe.forward(model, p, x, mm) - y) ** 2)

    np.testing.assert_allclose(
        np.asarray(nn.apply_model(spec, params, x)[0]),
        np.asarray(xing_moe.forward(model, params, x, mm)), rtol=2e-4, atol=2e-5,
    )
    (l_ours, g_ours), (l_plain, g_plain) = (
        jax.value_and_grad(loss)(params) for loss in (ours_loss, plain_loss)
    )
    np.testing.assert_allclose(float(l_ours), float(l_plain), rtol=1e-4)
    for layer_ours, layer_plain in zip(g_ours, g_plain):
        for name in layer_ours:
            a, b = np.asarray(layer_ours[name]), np.asarray(layer_plain[name])
            np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-5 * (1 + np.abs(b).max()), err_msg=name)
    block = g_ours[2]
    # every mechanism's leaves take a gradient
    for name in ("w_dq", "w_uq", "w_dkv", "w_ukv", "wo", "hc_op_phi", "hc_ffn_b_res", "hc_op_alpha"):
        assert np.asarray(block[name]).any(), name
    if ffn == "routed":
        assert np.asarray(block["shared_w2"]).any()
        # the bias selects only: no gradient reaches it
        assert not np.asarray(block["expert_bias"]).any()


def _layer(**over):
    fields = {
        k: v for k, v in MODEL.items()
        if k not in ("expert_dim", "rope_scaling", "lookback_window")
    }
    fields.update(ffn="routed", ff_dim=32, rope_factor=64.0, rope_mscale_all_dim=1.0)
    return LatentBlock(**dict(fields, **over))


def test_latent_attention_is_plain_attention_over_materialised_k_and_v():
    """The projections by hand, then ordinary multi-head attention (a softmax
    over all of a head's keys) over the k and v they give."""
    layer = _layer()
    p = _stirred(nn.init_latent_block(jax.random.PRNGKey(3), 64, layer))
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 12, 64))
    got = nn._latent_attention(layer, p, h)

    def norm(a, g):
        return a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True) + 1e-6) * g

    def heads(a):
        return a.reshape(2, 12, 4, -1).transpose(0, 2, 1, 3)

    freq = nn.yarn_inv_freq(layer)
    # YaRN at factor 64 slows every pair but the fastest ones
    plain_freq = 10000.0 ** (-np.arange(0, 8, 2) / 8)
    assert freq[0] == pytest.approx(plain_freq[0]) and freq[-1] == pytest.approx(plain_freq[-1] / 64)
    q = heads(norm(h @ p["w_dq"], p["q_norm"]) @ p["w_uq"])
    down = h @ p["w_dkv"]
    kv = heads(norm(down[..., :16], p["kv_norm"]) @ p["w_ukv"])
    k_rope = nn._rope(down[:, None, :, 16:], 0.0, jnp.asarray(freq))
    q = jnp.concatenate([q[..., :16], nn._rope(q[..., 16:], 0.0, jnp.asarray(freq))], -1)
    k = jnp.concatenate([kv[..., :16], jnp.broadcast_to(k_rope, (2, 4, 12, 8))], -1)
    v = kv[..., 16:]
    assert (q.shape[-1], k.shape[-1], v.shape[-1]) == (24, 24, 16)
    m = 0.1 * np.log(64.0) + 1.0
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (24 ** -0.5 * m * m)
    logits = jnp.where(jnp.tril(jnp.ones((12, 12), bool)), logits, -jnp.inf)
    out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(logits, -1), v)
    want = out.transpose(0, 2, 1, 3).reshape(2, 12, 64) @ p["wo"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)


def test_dispatcher_takes_two_head_widths_and_a_scale():
    """q and k of 24 (or 192) lanes, v of 16 (or 128), a scale that is not
    ``Dh ** -0.5``: the XLA path, forward and gradients, against the
    materialised softmax."""
    t, dq, dv, scale = 128, 24, 16, 0.37
    ks = jax.random.split(jax.random.PRNGKey(8), 4)
    q, k = (jax.random.normal(key, (2, 3, t, dq)) for key in ks[:2])
    v, g = (jax.random.normal(key, (2, 3, t, dv)) for key in ks[2:])

    def plain(q, k, v):
        logits = jnp.einsum("...qd,...kd->...qk", q, k) * scale
        logits = jnp.where(jnp.tril(jnp.ones((t, t), bool)), logits, -jnp.inf)
        return jnp.einsum("...qk,...kd->...qd", jax.nn.softmax(logits, -1), v)

    def ours(q, k, v):
        return attention.dot_product_attention(q, k, v, causal=True, impl="xla", scale=scale)

    got, pull = jax.vjp(ours, q, k, v)
    want, pull_plain = jax.vjp(plain, q, k, v)
    assert got.shape == (2, 3, t, dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)
    for a, b in zip(pull(g), pull_plain(g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("impl", ["flash", "ring"])
@pytest.mark.parametrize("dv, scale", [(16, None), (24, 0.37)])
def test_the_kernels_refuse_two_head_widths_or_a_scale(impl, dv, scale):
    """The flash kernel and ring attention take one head width and the default
    scale (the kernel at 192 / 128 lost to the XLA path on the chip): asked by
    name for anything else they say so, and ``auto`` never sends it there."""
    q = jnp.ones((2, 3, 256, 24))
    v = jnp.ones((2, 3, 256, dv))
    with pytest.raises(ValueError, match="one head width and the default scale"):
        attention.dot_product_attention(q, q, v, causal=True, impl=impl, scale=scale)
    wide = jnp.ones((2, 3, 256, 128))
    assert not attention._flash_ok(wide, wide, wide[..., :64])
    assert not attention._flash_ok(wide, wide, wide, 0.37)


@pytest.mark.parametrize("streams", [2, 4])
def test_h_res_is_doubly_stochastic_after_twenty_iterations(streams):
    layer = _layer(streams=streams)
    p = nn.init_latent_block(jax.random.PRNGKey(9), 64, layer)
    # logits of ordinary size: alpha 1 and a random b_res, far from the identity
    p["hc_op_alpha"] = jnp.ones((3,))
    p["hc_op_b_res"] = 2.0 * jax.random.normal(jax.random.PRNGKey(10), (streams, streams))
    x = jax.random.normal(jax.random.PRNGKey(11), (streams, 2, 12, 64))
    pre, post, res, gap = nn.hc_coefficients(layer, p, "hc_op_", x)
    assert res.shape == (streams, streams, 2, 12) and pre.shape == (streams, 2, 12)
    assert bool((res > 0).all())
    np.testing.assert_allclose(np.asarray(res.sum(0)), 1.0, atol=1e-3)
    np.testing.assert_allclose(np.asarray(res.sum(1)), 1.0, atol=1e-3)
    assert 0.0 <= float(gap) < 1e-3
    assert bool(((pre > 0) & (pre < 1) & (post > 0) & (post < 2)).all())
    # the plain model's mixing gives the same three, token for token
    model = _model(["routed"], streams=streams)
    pre_p, post_p, res_p = xing_moe.mixing(model, p, "hc_op_", jnp.moveaxis(x, 0, 2))
    np.testing.assert_allclose(np.asarray(jnp.moveaxis(res, (0, 1), (2, 3))), np.asarray(res_p), rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(jnp.moveaxis(pre, 0, 2)), np.asarray(pre_p), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(jnp.moveaxis(post, 0, 2)), np.asarray(post_p), rtol=1e-4)
    # one iteration is not enough from these logits: the twenty are needed
    _, _, _, early = nn.hc_coefficients(dataclasses.replace(layer, sinkhorn_iters=1), p, "hc_op_", x)
    assert float(early) > float(gap)


def test_the_start_is_the_plain_residual():
    """At the initial coefficients ``H_res`` is the identity to 1e-3 and
    ``H_post`` is 1: every stream keeps itself and takes F's output once."""
    layer = _layer()
    p = nn.init_latent_block(jax.random.PRNGKey(12), 64, layer)
    x = jax.random.normal(jax.random.PRNGKey(13), (4, 2, 12, 64))
    _, post, res, _ = nn.hc_coefficients(layer, p, "hc_op_", x)
    np.testing.assert_allclose(
        np.asarray(res[:, :, 0, 0]), np.eye(4), atol=2e-3
    )
    np.testing.assert_allclose(np.asarray(post), 1.0, atol=0.05)


def test_one_stream_is_the_gated_residual_block():
    """``streams`` 1: ``H_res`` is 1 by 1 and Sinkhorn makes it 1, ``H_pre``
    is a factor the norm takes out, so a sublayer is ``x + H_post
    F(norm(x))`` with one scalar gate a token."""
    layer = _layer(streams=1, ffn="dense", ff_dim=96)
    p = _stirred(nn.init_latent_block(jax.random.PRNGKey(14), 64, layer))
    x = jax.random.normal(jax.random.PRNGKey(15), (1, 2, 12, 64))
    got, stats = nn._apply_latent_block(layer, p, x)
    assert int(stats["hc_sublayer_steps"]) == 2 and "moe_tokens" not in stats
    want = x[0]
    for prefix, gain, sublayer in (
        ("hc_op_", "op_norm", lambda h: nn._latent_attention(layer, p, h)),
        ("hc_ffn_", "ffn_norm", lambda h: nn._swiglu(p, h)),
    ):
        _, post, res, _ = nn.hc_coefficients(layer, p, prefix, want[None])
        np.testing.assert_allclose(np.asarray(res), 1.0, atol=1e-5)
        want = want + post[0][..., None] * sublayer(nn._rms(want, p[gain], 1e-6))
    # H_pre is taken out by the norm only up to its eps: stirred weights give
    # values of tens, compared to a part in 1e4 of the largest
    np.testing.assert_allclose(
        np.asarray(got[0]), np.asarray(want), rtol=1e-3, atol=1e-4 * float(jnp.abs(want).max())
    )


def _routed_params(layer, key=21):
    p = nn.init_latent_block(jax.random.PRNGKey(key), layer.d_model, layer)
    # a router and experts of ordinary size: the small start would hide a fault
    names = ("router", "w1", "w3", "w2", "shared_w1", "shared_w3", "shared_w2")
    for name, k in zip(names, jax.random.split(jax.random.PRNGKey(key + 1), len(names))):
        p[name] = (1.0 if name == "router" else 0.3) * jax.random.normal(k, p[name].shape)
    return p


def test_the_shares_add_up_with_the_shared_expert_counted_once():
    """The FFN of all ``num_experts / experts_held`` shares of one layer: what
    every share computes alike (the shared expert) counted once, their routed
    parts added, equal the uncut plain model's layer."""
    whole = _layer(num_experts=8, experts_held=8, expert_offset=0, streams=1)
    p = _routed_params(whole)
    h = jax.random.normal(jax.random.PRNGKey(23), (2, 12, 64))
    model = _model(["routed"], experts_held=8, expert_offset=0)
    uncut = xing_moe._routed(model, p, h, reference.matmul("float32"))
    shared = nn._swiglu(p, h, "shared_")
    routed, held = 0.0, 0
    for offset in (0, 2, 4, 6):
        share = dataclasses.replace(whole, experts_held=2, expert_offset=offset)
        p_share = dict(p, **{k: p[k][offset : offset + 2] for k in ("w1", "w3", "w2")})
        part, counted = nn.routed_ffn(
            share, p_share, h.reshape(24, 64), scale=share.routed_scale, gate_eps=1e-20
        )
        assert int(counted["moe_held"]) + int(counted["moe_absent"]) == 24 * 2
        routed, held = routed + part.reshape(2, 12, 64), held + int(counted["moe_held"])
    assert held == 24 * 2  # every assignment is some share's
    np.testing.assert_allclose(np.asarray(shared + routed), np.asarray(uncut), rtol=1e-4, atol=1e-5)
    # and the weights carry the scale: top-2 normalised weights sum to 2
    scores = jax.nn.sigmoid(h.reshape(24, 64) @ p["router"])
    top = jax.lax.top_k(scores, 2)[0]
    assert float(jnp.abs((top / top.sum(-1, keepdims=True) * 2.0).sum(-1) - 2.0).max()) < 1e-5


def test_counters_come_out_of_the_compiled_epoch():
    """The mixing's two counters and the routed layers' five, summed over the
    live steps of a compiled epoch."""
    from gordo_tpu.ops.train import make_masked_epoch_fn
    from gordo_tpu.ops.train import make_optimizer

    model = _model(["dense", "routed"])
    spec = _spec(model)
    params = nn.init_model_params(jax.random.PRNGKey(1), spec)
    assert nn.zero_stats(spec)["hc_stochastic_gap"].dtype == jnp.float32
    rows, batch = 40, 8
    X = jax.random.normal(jax.random.PRNGKey(2), (rows, N_TAGS))
    n_max = rows - 12 + 1  # 29 windows: four steps of eight
    epoch = jax.jit(make_masked_epoch_fn(spec, n_max, batch, shuffle=True))
    opt_state = make_optimizer(spec.optimizer).init(params)
    _, _, loss, stats = epoch(params, opt_state, X, X, jax.random.PRNGKey(3), n_max)
    assert np.isfinite(float(loss))
    steps = 4
    assert int(stats["hc_sublayer_steps"]) == steps * 2 * 2  # two blocks, two sublayers
    assert 0.0 <= float(stats["hc_stochastic_gap"]) < 1e-2
    assert int(stats["moe_layer_steps"]) == steps
    assert int(stats["moe_tokens"]) == steps * batch * 12
    assert int(stats["moe_held"]) + int(stats["moe_absent"]) == steps * batch * 12 * 2


def test_flops_count_is_the_plain_models():
    """``ops/flops.py`` (the serving MFU gauge) and the benchmark's count of
    the same forward pass agree."""
    model = _model(["dense", "routed", "routed"])
    spec = _spec(model)
    config = {"model": model, "n_tags": N_TAGS}
    # the serving count applies the head at every position it is given: one
    assert flops.forward_flops_per_sample(spec) == pytest.approx(
        xing_moe.forward_flops_per_window(config), rel=1e-9
    )
    params = nn.init_model_params(jax.random.PRNGKey(0), spec)
    assert flops.spec_param_count(spec) == sum(
        a.size for a in jax.tree_util.tree_leaves(params)
    )


def test_factory_refusals():
    with pytest.raises(ValueError, match="not among"):
        _spec(_model(["routed"], experts_held=8, expert_offset=4))
    with pytest.raises(ValueError, match="one entry a layer"):
        _spec(_model([]))
    with pytest.raises(ValueError, match="rope_scaling"):
        _spec(_model(["dense"], rope_scaling=dict(YARN, type="linear")))
    with pytest.raises(ValueError, match="unknown rope_scaling"):
        _spec(_model(["dense"], rope_scaling=dict(YARN, beta=1)))
