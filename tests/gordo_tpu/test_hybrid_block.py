"""The hybrid block (gated short convolution | grouped-query attention, dense
SwiGLU | routed experts) at a small size, float32, seeded: against the plain
model ``chipbench/configs/lfm2_moe.py``, and the routed layer's promises (the
shares add up, nothing is dropped, the bias selects and does not weigh)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference
from chipbench.configs import lfm2_moe
from gordo_tpu.models.factories.hybrid import hybrid_moe_model
from gordo_tpu.models.spec import HybridBlock
from gordo_tpu.ops import nn

MODEL = {
    "d_model": 16, "ff_dim": 40, "expert_dim": 24, "num_heads": 4,
    "num_kv_heads": 2, "head_dim": 8, "rope_theta": 1000000.0, "conv_kernel": 3,
    "num_experts": 8, "experts_held": 4, "expert_offset": 2, "top_k": 2,
    "norm_eps": 1e-5, "lookback_window": 12,
}
N_TAGS = 4


def _model(operators, ffns, **over):
    return dict(MODEL, operators=list(operators), ffns=list(ffns), **over)


def _spec(model):
    return hybrid_moe_model(N_TAGS, attention="xla", **model)


@pytest.mark.parametrize(
    "operator,ffn", [("conv", "dense"), ("attention", "routed"), ("conv", "routed")]
)
def test_block_against_the_plain_model(operator, ffn):
    model = _model([operator], [ffn])
    spec = _spec(model)
    key = jax.random.PRNGKey(5)
    params = nn.init_model_params(key, spec)
    plain = lfm2_moe.init_params(key, model, N_TAGS)
    # the same recipe, key for key
    assert [sorted(p) for p in params] == [sorted(p) for p in plain]
    for ours, theirs in zip(params, plain):
        for name in ours:
            np.testing.assert_array_equal(np.asarray(ours[name]), np.asarray(theirs[name]))
    # weights far enough from their small start that every term matters
    params = jax.tree_util.tree_map(
        lambda a: a + 0.3 * jax.random.normal(jax.random.PRNGKey(a.size), a.shape), params
    )
    x = jax.random.normal(jax.random.PRNGKey(6), (3, 12, N_TAGS))
    y = jax.random.normal(jax.random.PRNGKey(7), (3, N_TAGS))
    mm = reference.matmul("float32")

    def ours_loss(p):
        return jnp.mean((nn.apply_model(spec, p, x)[0] - y) ** 2)

    def plain_loss(p):
        return jnp.mean((lfm2_moe.forward(model, p, x, mm) - y) ** 2)

    np.testing.assert_allclose(
        np.asarray(nn.apply_model(spec, params, x)[0]),
        np.asarray(lfm2_moe.forward(model, params, x, mm)), rtol=2e-4, atol=2e-5,
    )
    g_ours, g_plain = jax.grad(ours_loss)(params), jax.grad(plain_loss)(params)
    for layer_ours, layer_plain in zip(g_ours, g_plain):
        for name in layer_ours:
            a, b = np.asarray(layer_ours[name]), np.asarray(layer_plain[name])
            np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-5 * (1 + np.abs(b).max()), err_msg=name)
    if ffn == "routed":
        # the bias selects only: no gradient reaches it
        assert not np.asarray(g_ours[1]["expert_bias"]).any()


def _routed_layer(**over):
    fields = dict(
        d_model=16, operator="conv", ffn="routed", ff_dim=24, num_experts=32,
        experts_held=32, expert_offset=0, top_k=4,
    )
    return HybridBlock(**dict(fields, **over))


def _routed_params(layer, key=11):
    p = nn.init_hybrid_block(jax.random.PRNGKey(key), layer.d_model, layer)
    # a router and experts of ordinary size: the small start would hide a fault
    ks = jax.random.split(jax.random.PRNGKey(key + 1), 4)
    p["router"] = jax.random.normal(ks[0], p["router"].shape)
    for name, k in zip(("w1", "w3", "w2"), ks[1:]):
        p[name] = 0.3 * jax.random.normal(k, p[name].shape)
    return p


def test_the_four_shares_add_up_to_the_uncut_layer():
    whole = _routed_layer()
    p = _routed_params(whole)
    h = jax.random.normal(jax.random.PRNGKey(12), (40, 16))
    uncut, stats = nn.routed_ffn(whole, p, h)
    assert int(stats["moe_held"]) == 40 * 4 and int(stats["moe_absent"]) == 0
    total, held = 0.0, 0
    for offset in (0, 8, 16, 24):
        share = dataclasses.replace(whole, experts_held=8, expert_offset=offset)
        p_share = dict(p, **{k: p[k][offset : offset + 8] for k in ("w1", "w3", "w2")})
        part, counted = nn.routed_ffn(share, p_share, h)
        assert int(counted["moe_held"]) + int(counted["moe_absent"]) == 40 * 4
        total, held = total + part, held + int(counted["moe_held"])
    assert held == 40 * 4  # every assignment is some share's
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), rtol=1e-4, atol=1e-5)


def test_no_assignment_is_dropped_under_total_imbalance():
    layer = _routed_layer(experts_held=8)
    p = _routed_params(layer)
    p = dict(p, **{k: p[k][:8] for k in ("w1", "w3", "w2")})
    # every token to the same four held experts: a zero router scores 0.5
    # everywhere, and the bias picks experts 0-3
    p["router"] = jnp.zeros_like(p["router"])
    p["expert_bias"] = jnp.zeros((32,)).at[:4].set(1.0)
    h = jax.random.normal(jax.random.PRNGKey(13), (64, 16))
    out, stats = nn.routed_ffn(layer, p, h)
    gate = 0.5 / (4 * 0.5 + 1e-6)
    dense = sum(
        gate * (jax.nn.silu(h @ p["w1"][e]) * (h @ p["w3"][e])) @ p["w2"][e]
        for e in range(4)
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), rtol=1e-4, atol=1e-5)
    assert int(stats["moe_held"]) == 64 * 4 and int(stats["moe_absent"]) == 0
    assert int(stats["moe_peak_load"]) == 64  # four experts, sixty-four tokens each


def test_the_bias_changes_the_selection_and_not_the_weights():
    layer = _routed_layer()
    p = _routed_params(layer)
    h = jax.random.normal(jax.random.PRNGKey(14), (24, 16))
    plain, _ = nn.routed_ffn(layer, p, h)
    bias = jnp.zeros((32,)).at[5].set(10.0)  # expert 5 is now always selected
    biased, _ = nn.routed_ffn(layer, dict(p, expert_bias=bias), h)
    assert not np.allclose(np.asarray(plain), np.asarray(biased))
    # by hand: the selection takes the bias, the weights are the bare scores
    scores = jax.nn.sigmoid(h @ p["router"])
    _, chosen = jax.lax.top_k(scores + bias, 4)
    assert bool((chosen == 5).any(axis=-1).all())
    weight = jnp.take_along_axis(scores, chosen, axis=-1)
    weight = weight / (weight.sum(-1, keepdims=True) + 1e-6)
    by_hand = jnp.zeros_like(h)
    for slot in range(4):
        e = chosen[:, slot]
        y = jnp.einsum(
            "nf,nfd->nd",
            jax.nn.silu(jnp.einsum("nd,ndf->nf", h, p["w1"][e]))
            * jnp.einsum("nd,ndf->nf", h, p["w3"][e]),
            p["w2"][e],
        )
        by_hand = by_hand + weight[:, slot, None] * y
    np.testing.assert_allclose(np.asarray(biased), np.asarray(by_hand), rtol=1e-4, atol=1e-5)


def test_grouped_matmul_under_vmap_and_grad():
    """The fleet's machine axis: the grouped product and both its gradients,
    a lane at a time, against a product a group."""
    rows, k, n, groups = 12, 5, 7, 3
    ks = jax.random.split(jax.random.PRNGKey(15), 3)
    x = jax.random.normal(ks[0], (2, rows, k))
    w = jax.random.normal(ks[1], (2, groups, k, n))
    sizes = jnp.array([[3, 0, 5], [4, 4, 4]], jnp.int32)  # lane 0 leaves four rows dead

    def plain(x, w, sizes):
        ends = np.cumsum(np.asarray(sizes))
        group = np.searchsorted(ends, np.arange(rows), side="right")
        live = (group < groups)[:, None]
        return jnp.where(live, jnp.einsum("rk,rkn->rn", x, w[np.minimum(group, groups - 1)]), 0.0)

    def loss(fn, x, w, sizes):
        return jnp.sum(jnp.sin(fn(x, w, sizes)))

    got = jax.vmap(jax.value_and_grad(lambda *a: loss(nn.grouped_matmul, *a), argnums=(0, 1)))(x, w, sizes)
    for lane in range(2):
        want = jax.value_and_grad(lambda *a: loss(plain, *a), argnums=(0, 1))(x[lane], w[lane], sizes[lane])
        np.testing.assert_allclose(float(got[0][lane]), float(want[0]), rtol=1e-5)
        for a, b in zip(got[1], want[1]):
            np.testing.assert_allclose(np.asarray(a[lane]), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_factory_refuses_a_share_outside_the_router():
    with pytest.raises(ValueError, match="not among"):
        _spec(_model(["conv"], ["routed"], experts_held=8, expert_offset=4))
    with pytest.raises(ValueError, match="one entry a layer"):
        _spec(_model(["conv", "conv"], ["routed"]))
