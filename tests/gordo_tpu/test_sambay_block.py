"""The SambaY layers (``models/spec.py SambaYBlock``, ``ops/nn.py``) against
their equations and against the plain model ``chipbench/configs/sambay.py``
on seeded random weights, at tiny widths on the CPU: d 32, heads 4 / 2 of 8,
d_inner 64, d_state 4, and a window of 8 under a lookback of 32, so that the
window cuts.

Tolerances: both sides compute in float32 here, the program at the default
matmul precision of the CPU (float32) and the plain model at ``highest``;
what is left is the order of float32 sums (a chunked against a sequential
scan, the flash-free XLA path against a per-pair loop), some 1e-6 of the
values, so forward values are held to 1e-5 and gradients, which add up more
terms, to 1e-4 relative."""

import dataclasses
import importlib
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gordo_tpu.models.factories.sambay import sambay_layout, sambay_model
from gordo_tpu.models.spec import SambaYBlock
from gordo_tpu.ops import attention, flops, nn

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY = dict(
    d_model=32, num_hidden_layers=8, layers_held=[2, 3, 4, 5, 6, 7], mb_per_layer=2,
    sliding_window=8, ff_dim=48, num_heads=4, num_kv_heads=2, head_dim=8, d_state=4,
    d_conv=4, expand=2, dt_rank=2, norm_eps=1e-5, lookback_window=32,
)
T, B, TAGS = 32, 3, 4


def _plain():
    from chipbench.configs import sambay

    return sambay


def _mm():
    from chipbench import reference

    return reference.matmul("float32")


def _spec(**changes):
    return sambay_model(TAGS, **dict(TINY, **changes))


def _noisy(tree, key, scale=0.3):
    """The initial weights moved off their start (unit gains, zero biases, a
    zero λ would hide a wrong formula)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(jax.random.PRNGKey(key), len(leaves))
    return jax.tree_util.tree_unflatten(
        treedef,
        [a + scale * jnp.std(a + 1.0) * jax.random.normal(k, a.shape) for a, k in zip(leaves, keys)],
    )


def _close(got, want, rtol, err=""):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    np.testing.assert_allclose(got, want, rtol=rtol, atol=rtol * (1e-3 + np.abs(want).max()), err_msg=err)


# ------------------------------------------------------------ the scan
def _sequential_scan(u, delta, a, b, c, d_skip):
    """The recurrence as written: one position after another."""

    def step(h, xs):
        dt, ut, bt, ct = xs
        h = jnp.exp(dt[:, :, None] * a) * h + (dt * ut)[:, :, None] * bt[:, None, :]
        return h, jnp.sum(h * ct[:, None, :], axis=-1)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (delta, u, b, c))
    _, ys = jax.lax.scan(step, jnp.zeros((u.shape[0],) + a.shape), xs)
    return jnp.moveaxis(ys, 0, 1) + u * d_skip


def _scan_inputs(t=T, channels=64, states=4):
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    u = jax.random.normal(ks[0], (B, t, channels))
    delta = jax.nn.softplus(jax.random.normal(ks[1], (B, t, channels)) - 1.0)
    a = -jnp.exp(jax.random.normal(ks[2], (channels, states)))
    b, c = (jax.random.normal(k, (B, t, states)) for k in ks[3:5])
    return u, delta, a, b, c, jax.random.normal(ks[5], (channels,))


@pytest.mark.parametrize("chunk", [None, 1, 8, 5, 32, 64])
def test_the_chunked_scan_is_the_sequential_one(chunk):
    """Chunks that divide T (8, 32), that do not (5: a padded tail), the
    module's own and longer than T: the same values and gradients as the
    position-by-position recurrence."""
    inputs = _scan_inputs()
    got = nn.selective_scan(*inputs, chunk=chunk)
    want = _sequential_scan(*inputs)
    _close(got, want, 1e-5)
    w = jax.random.normal(jax.random.PRNGKey(8), want.shape)
    grads = [
        jax.grad(lambda *xs, f=f: jnp.sum(f(*xs) * w), argnums=tuple(range(6)))(*inputs)
        for f in (lambda *xs: nn.selective_scan(*xs, chunk=chunk), _sequential_scan)
    ]
    for name, g, h in zip(("u", "delta", "a", "b", "c", "d"), *grads):
        _close(g, h, 1e-4, name)


def test_the_scan_keeps_a_state_a_chunk_and_no_state_a_position(capsys):
    """What the backward pass keeps of the scan: the chunk-boundary states,
    never a (B, T, channels, states) array."""
    import re

    from jax.ad_checkpoint import print_saved_residuals

    inputs = _scan_inputs(t=64, channels=48, states=4)
    print_saved_residuals(lambda *xs: jnp.sum(nn.selective_scan(*xs, chunk=8)), *inputs)
    shapes = [
        tuple(int(n) for n in dims.split(",") if n)
        for dims in re.findall(r"f32\[([\d,]*)\]", capsys.readouterr().out)
    ]
    assert (64 // 8, B, 4, 48) in shapes  # a state a chunk
    assert max(np.prod(s) for s in shapes) < B * 64 * 48 * 4, shapes


# ------------------------------------------------------------ the band
def _explicit_band(q, k, v, window):
    """softmax over the keys each query sees, pair by pair."""
    tq, tk = q.shape[-2], k.shape[-2]
    out = np.zeros(q.shape[:-1] + (v.shape[-1],))
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    for i in range(tq):
        t = tk - tq + i
        seen = [s for s in range(tk) if s <= t and (window is None or s > t - window)]
        scores = np.einsum("...d,...sd->...s", q[..., i, :], k[..., seen, :]) / math.sqrt(q.shape[-1])
        weights = np.exp(scores - scores.max(-1, keepdims=True))
        weights /= weights.sum(-1, keepdims=True)
        out[..., i, :] = np.einsum("...s,...sd->...d", weights, v[..., seen, :])
    return out


@pytest.mark.parametrize("t, tq, window", [(32, 32, 8), (32, 32, 1), (32, 1, 8), (8, 8, 8), (8, 8, 32)])
def test_the_band_mask_is_the_per_pair_mask(t, tq, window):
    """Where the window cuts (T > window), the XLA path's band against a mask
    written out query by query; where it does not, the causal mask itself."""
    ks = jax.random.split(jax.random.PRNGKey(t + window), 3)
    q = jax.random.normal(ks[0], (2, 3, tq, 8))
    k = jax.random.normal(ks[1], (2, 3, t, 8))
    v = jax.random.normal(ks[2], (2, 3, t, 16))
    got = attention.dot_product_attention_xla(q, k, v, causal=True, window=window)
    _close(got, _explicit_band(q, k, v, window), 1e-5)
    if window >= t:
        _close(got, attention.dot_product_attention_xla(q, k, v, causal=True), 1e-6)
    else:
        assert not np.allclose(got, attention.dot_product_attention_xla(q, k, v, causal=True))


def test_the_scan_look_rehearses(capsys):
    """``scripts/sambay_path_look.py``, which ``SCAN_CHUNK`` was chosen
    with, on the CPU at its tiny size: a line a chunk length."""
    look = importlib.import_module("scripts.sambay_path_look")
    assert look.main(["--rehearsal", "--chunks", "2", "5", "--runs", "1"]) == 0
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert [line["chunk"] for line in lines] == [2, 5]
    assert all(line["forward_backward_ms"] > 0 for line in lines)


# -------------------------------------------------- differential attention
def _layer(mixer, **changes):
    return next(
        layer for layer in _spec(**changes).layers
        if isinstance(layer, SambaYBlock) and layer.mixer == mixer
    )


def _diff_by_formula(layer, p, h, kv=None):
    """Pair by pair, as the family's equations read."""
    h = np.asarray(h, np.float64)
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    dh, pairs = layer.head_dim, layer.num_heads // 2
    q = (h @ p["wq"]).reshape(h.shape[0], h.shape[1], pairs, 2, dh)
    if kv is None:
        k = (h @ p["wk"]).reshape(h.shape[0], h.shape[1], -1, 2, dh)
        v = (h @ p["wv"]).reshape(h.shape[0], h.shape[1], -1, 2 * dh)
    else:
        k, v = (np.asarray(a, np.float64) for a in kv)
    group = pairs // k.shape[2]
    init = 0.8 - 0.6 * math.exp(-0.3 * layer.layer_index)
    lam = (
        math.exp(p["lambda_q1"] @ p["lambda_k1"]) - math.exp(p["lambda_q2"] @ p["lambda_k2"]) + init
    )
    window = layer.window or None
    out = np.zeros(q.shape[:3] + (2 * dh,))
    for j in range(pairs):
        g = j // group
        a1, a2 = (
            _explicit_band(q[:, None, :, j, i], k[:, None, :, g, i], v[:, None, :, g], window)[:, 0]
            for i in (0, 1)
        )
        o = a1 - lam * a2
        o = o / np.sqrt((o * o).mean(-1, keepdims=True) + 1e-5) * p["subln"] * (1 - init)
        out[:, :, j] = o
    return out.reshape(h.shape[0], h.shape[1], -1) @ p["wo"]


@pytest.mark.parametrize("mixer, window", [("attention", 8), ("attention", 0), ("cross_attention", 0)])
def test_differential_attention_is_its_formula(mixer, window):
    layer = dataclasses.replace(_layer(mixer), window=window)
    p = _noisy(nn.init_sambay_block(jax.random.PRNGKey(3), 32, layer), 4)
    h = jax.random.normal(jax.random.PRNGKey(5), (B, T, 32))
    kv = None
    if mixer == "cross_attention":
        kv = (
            jax.random.normal(jax.random.PRNGKey(6), (B, T, 1, 2, 8)),
            jax.random.normal(jax.random.PRNGKey(7), (B, T, 1, 16)),
        )
    got, _ = nn._diff_attention(layer, p, h, kv=kv)
    _close(got, _diff_by_formula(layer, p, h, kv), 1e-5)
    lam, init = nn.diff_lambda(layer, p)
    assert init == pytest.approx(0.8 - 0.6 * math.exp(-0.3 * layer.layer_index))


# ------------------------------------------------- blocks against the plain model
def _shared(key=11):
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    return {
        "memory": jax.random.normal(ks[0], (B, T, 64)),
        "kv": (jax.random.normal(ks[1], (B, T, 1, 2, 8)), jax.random.normal(ks[2], (B, T, 1, 16))),
    }


def _plain_block(layer, p, x, shared):
    sambay = _plain()
    s = sambay.sizes(dict(TINY, expand=2))
    spec = (layer.mixer, layer.window, layer.keeps, layer.layer_index)
    with jax.default_matmul_precision("highest"):
        return sambay._block(s, spec, p, x, shared, _mm())


@pytest.mark.parametrize("mixer", ["mamba", "attention", "gmu", "cross_attention"])
def test_each_block_is_the_plain_models(mixer):
    """A block of each mixer, given another block's memory or keys and values
    where it reads them, against the plain model's block: output and what
    it keeps for later blocks."""
    layer = _layer(mixer)
    p = _noisy(nn.init_sambay_block(jax.random.PRNGKey(9), 32, layer), 10)
    x = jax.random.normal(jax.random.PRNGKey(12), (B, T, 32))
    shared = _shared()
    got, kept, _ = nn._apply_sambay_block(layer, p, x, shared)
    want, want_kept = _plain_block(layer, p, x, shared)
    _close(got, want, 1e-5)
    assert set(kept) == set(want_kept) == ({layer.keeps} - {""})
    for key in kept:
        for a, b in zip(jax.tree_util.tree_leaves(kept[key]), jax.tree_util.tree_leaves(want_kept[key])):
            _close(a, b, 1e-5, key)


@pytest.mark.parametrize("reads", ["kv", "memory"])
def test_a_consumer_reads_the_state_it_is_handed(reads):
    """Cross-attention projects queries alone (no W_k or W_v) and moves with
    layer 17's keys; a gated memory unit moves with layer 16's memory."""
    layer = _layer("cross_attention" if reads == "kv" else "gmu")
    p = nn.init_sambay_block(jax.random.PRNGKey(9), 32, layer)
    if reads == "kv":
        assert "wq" in p and not {"wk", "wv"} & set(p)
    x = jax.random.normal(jax.random.PRNGKey(12), (B, T, 32))
    shared = _shared()
    base, _, stats = nn._apply_sambay_block(layer, p, x, shared)
    moved = dict(shared)
    if reads == "kv":
        k, v = shared["kv"]
        moved["kv"] = (k.at[:, : T // 2].add(1.0), v)
    else:
        moved["memory"] = shared["memory"] + 1.0
    other, _, _ = nn._apply_sambay_block(layer, p, x, moved)
    assert float(jnp.abs(other - base).max()) > 1e-3
    assert stats == {f"shared_{reads}_reads": 1}


@pytest.mark.parametrize("mixer", ["mamba", "attention", "gmu", "cross_attention"])
def test_the_trimmed_last_block_is_the_whole_blocks_last_position(mixer):
    """``last_only``: the last position's output alone, in value and in every
    gradient (weights, input, the state read from earlier blocks)."""
    layer = _layer(mixer)
    p = _noisy(nn.init_sambay_block(jax.random.PRNGKey(13), 32, layer), 14)
    x = jax.random.normal(jax.random.PRNGKey(15), (B, T, 32))
    shared = _shared(16)

    def whole(p, x, shared):
        return nn._apply_sambay_block(layer, p, x, shared)[0][:, -1:]

    def trimmed(p, x, shared):
        return nn._apply_sambay_block(layer, p, x, shared, last_only=True)[0]

    a, b = whole(p, x, shared), trimmed(p, x, shared)
    assert b.shape == (B, 1, 32)
    _close(b, a, 1e-5)
    grads = [
        jax.grad(lambda *args, f=f: jnp.sum(jnp.sin(f(*args))), argnums=(0, 1, 2))(p, x, shared)
        for f in (whole, trimmed)
    ]
    for g, h in zip(jax.tree_util.tree_leaves(grads[0]), jax.tree_util.tree_leaves(grads[1])):
        _close(h, g, 1e-4)


# ------------------------------------------------------------ the model
@pytest.mark.parametrize("remat", [False, True])
def test_the_tiny_model_is_the_plain_model(remat):
    """The whole tiny model (window 8 under a lookback of 32) against the plain
    model from the same key: the initial weights, the forward pass and every
    leaf's gradient, with the program's blocks recomputed or kept."""
    sambay = _plain()
    spec = dataclasses.replace(_spec(), remat=remat)
    key = jax.random.PRNGKey(17)
    ours = nn.init_model_params(key, spec)
    plain = sambay.init_params(key, dict(TINY, expand=2), TAGS)
    for a, b in zip(ours, plain):
        assert sorted(a) == sorted(b)
        for name in a:
            # the same draws; the plain model draws inside one jit, which can
            # round a transcendental in the last place
            _close(a[name], b[name], 1e-6, name)
    params = _noisy(plain, 18, scale=0.1)
    x = jax.random.normal(jax.random.PRNGKey(19), (5, T, TAGS))
    got = nn.apply_model(spec, params, x)[0]
    with jax.default_matmul_precision("highest"):
        want = sambay.forward(dict(TINY, expand=2), params, x, _mm())
    _close(got, want, 1e-5)
    grads = [
        jax.grad(lambda p, f=f: jnp.sum(jnp.sin(f(p))))(params)
        for f in (
            lambda p: nn.apply_model(spec, p, x)[0],
            lambda p: sambay.forward(dict(TINY, expand=2), p, x, _mm()),
        )
    ]
    for i, (g, h) in enumerate(zip(*grads)):
        for name in h:
            _close(g[name], h[name], 1e-4, f"{i} {name}")


def test_counts_and_the_trimmed_block():
    spec = _spec()
    params = nn.init_model_params(jax.random.PRNGKey(0), spec)
    _, _, stats = nn.apply_model_stats(spec, params, jnp.zeros((B, T, TAGS)))
    assert set(stats) == set(nn.zero_stats(spec))
    assert {k: int(v) for k, v in stats.items()} == {
        "ssm_layer_steps": 2, "ssm_positions": 2 * B * T, "shared_kv_reads": 1,
        "shared_memory_reads": 1, "last_position_steps": 1,
    }
    # the last block, cross-attention, runs at the last position alone
    assert nn._last_only_block(spec) == len(spec.layers) - 4


@pytest.mark.parametrize("held", [[6, 7], [5, 6, 7], [4, 6, 7], [2, 3]])
def test_a_reader_without_its_keeper_is_refused(held):
    """Layers 18 and 19 read what 16 and 17 keep: a cut that holds a reader
    and not its keeper is refused when the spec is built. A cut of the
    self-decoder alone reads nothing and is built."""
    if held == [2, 3]:
        assert [layer.mixer for layer in _spec(layers_held=held).layers[1:3]] == ["mamba", "attention"]
        return
    with pytest.raises(ValueError, match="none keeps it"):
        _spec(layers_held=held)


@pytest.mark.parametrize("i, want", [
    (0, ("mamba", 0, "")), (14, ("mamba", 0, "")), (15, ("attention", 512, "")),
    (16, ("mamba", 0, "memory")), (17, ("attention", 0, "kv")), (18, ("gmu", 0, "")),
    (19, ("cross_attention", 0, "")), (31, ("cross_attention", 0, "")),
])
def test_the_published_layout(i, want):
    assert sambay_layout(i, 32, 2, 512) == want
    model = {"num_hidden_layers": 32, "mb_per_layer": 2, "sliding_window": 512, "layers_held": [i]}
    assert tuple(_plain().layout(model)[0][:3]) == want


def test_the_gauge_counts_the_cut():
    """The serving gauge's parameter count and operations at the cell's
    widths: 633,096,840 parameters, as the plain model holds; its forward
    pass at every position (the program's own work)."""
    with open(os.path.join(ROOT, "chipbench", "configs", "phi4_mini_flash_3_8b.json")) as fh:
        config = json.load(fh)
    from gordo_tpu.models.models import TransformerAutoEncoder

    spec = TransformerAutoEncoder(**config["model"]).build_spec(8, 8)
    assert flops.spec_param_count(spec) == 633_096_840
    shapes = jax.eval_shape(lambda k: nn.init_model_params(k, spec), jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree_util.tree_leaves(shapes)) == 633_096_840
    t, d, ff, di = 256, 2560, 10240, 5120
    mamba = 2 * (2 * d * di + di * 192 + 160 * di + di * d)
    attention_ = 2 * d * (2 * 2560 + 2 * 1280)
    per_token = 2 * mamba + 2 * attention_ + 4 * d * di + 2 * d * 2 * 2560 + 6 * 6 * d * ff
    maps = 3 * 6.0 * 2560 * 0.5 * t * t
    hand = 2 * 8 * d * t + per_token * t + maps + 2 * d * 8
    assert flops.forward_flops_per_sample(spec) == pytest.approx(hand, rel=1e-12)


# ------------------------------------------------------- the other kinds
SAMBAY_STATS = {"ssm_layer_steps", "ssm_positions", "shared_kv_reads", "shared_memory_reads"}


@pytest.mark.parametrize("name", ["lstm_ae_tiny", "lfm2_tiny", "xing_tiny"])
def test_the_other_kinds_count_no_sambay_work(name):
    """The other benchmarked kinds' specs start no SambaY count: their chunk
    programs carry none of its stats."""
    with open(os.path.join(ROOT, "chipbench", "rehearsal", f"{name}.json")) as fh:
        config = json.load(fh)
    module, cls = config["estimator"].rsplit(".", 1)
    n = int(config["n_tags"])
    spec = getattr(importlib.import_module(module), cls)(**config["model"]).build_spec(n, n)
    assert not set(nn.zero_stats(spec)) & SAMBAY_STATS
