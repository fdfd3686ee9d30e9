"""The LSTM layer's hand-written backward (ops/nn.py:_lstm) against a plain
``lax.scan`` left to JAX's autodiff, and the structure that makes it fast:
the time scans stack two small buffers and carry no weight gradient."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gordo_tpu.models.spec import LSTMLayer
from gordo_tpu.ops import nn

BATCH, TIME, MACHINES = 3, 6, 2
WEIGHTS = ("kernel", "recurrent_kernel", "bias")


def plain_lstm(layer, p, x):
    """The layer as it was before the rewrite: the fused [x_t, h] @ W gate
    product inside the scan, differentiated by JAX."""
    units = layer.units
    act = nn._activation(layer.activation)
    rec_act = nn._activation(layer.recurrent_activation)
    W = jnp.concatenate([p["kernel"], p["recurrent_kernel"]], axis=0)

    def step(carry, xt):
        h, c = carry
        z = (jnp.concatenate([xt, h.astype(xt.dtype)], axis=1) @ W
             + p["bias"]).astype(jnp.float32)
        i = rec_act(z[:, :units])
        f = rec_act(z[:, units : 2 * units])
        g = act(z[:, 2 * units : 3 * units])
        o = rec_act(z[:, 3 * units :])
        c = f * c + i * g
        h = o * act(c)
        return (h, c), (h.astype(xt.dtype) if layer.return_sequences else None)

    zeros = jnp.zeros((x.shape[0], units), jnp.float32)
    (h, _), hs = jax.lax.scan(step, (zeros, zeros), jnp.swapaxes(x, 0, 1))
    return jnp.swapaxes(hs, 0, 1) if layer.return_sequences else h.astype(x.dtype)


def _inputs(layer, in_dim, machines=None, time=TIME):
    lead = () if machines is None else (machines,)
    keys = jax.random.split(jax.random.PRNGKey(in_dim + layer.units), 4)
    p = nn.init_lstm_layer(keys[0], in_dim, layer.units)
    # a bias and kernels away from their tidy initial values
    p = {k: v + 0.1 * jax.random.normal(keys[1], v.shape) for k, v in p.items()}
    if machines is not None:
        p = {
            k: v * jnp.linspace(0.5, 1.0, machines).reshape((-1,) + (1,) * v.ndim)
            for k, v in p.items()
        }
    x = jax.random.normal(keys[2], lead + (BATCH, time, in_dim))
    out_shape = (BATCH, time, layer.units) if layer.return_sequences else (
        BATCH, layer.units)
    return p, x, jax.random.normal(keys[3], lead + out_shape)


def _value_and_grads(fn, layer, dtype, wrap, p, x, w):
    """(outputs, gradients of sum(outputs * w) by the weights and by x), with
    the parameters cast per call as ``apply_model`` casts them."""

    def loss(p, x, w):
        p = {k: v.astype(dtype) for k, v in p.items()}
        out = fn(layer, p, x.astype(dtype)).astype(jnp.float32)
        return jnp.sum(out * w), out

    (_, out), grads = wrap(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))(
        p, x, w
    )
    return out, {**grads[0], "x": grads[1]}


def _rel(a, b):
    return float(jnp.linalg.norm((a - b).ravel()) / jnp.linalg.norm(b.ravel()))


WRAPS = {
    "plain": (None, lambda f: f),
    "vmap": (MACHINES, jax.vmap),
    "checkpoint": (None, lambda f: f),
}


@pytest.mark.parametrize("how", sorted(WRAPS))
@pytest.mark.parametrize(
    "activations", [("tanh", "sigmoid"), ("softsign", "hard_sigmoid")],
    ids=lambda a: "-".join(a),
)
@pytest.mark.parametrize("dims", [(3, 8), (8, 4)], ids=["in<units", "in>units"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("return_sequences", [True, False], ids=["seq", "last"])
def test_layer_matches_the_plain_scan(return_sequences, dtype, dims, activations, how):
    in_dim, units = dims
    layer = LSTMLayer(units, *activations, return_sequences)
    machines, wrap = WRAPS[how]
    args = _inputs(layer, in_dim, machines)
    new = nn._apply_lstm
    if how == "checkpoint":
        new = lambda layer, p, x: jax.checkpoint(  # noqa: E731
            lambda p, x: nn._apply_lstm(layer, p, x))(p, x)

    want_out, want = _value_and_grads(plain_lstm, layer, jnp.float32, wrap, *args)
    got_out, got = _value_and_grads(new, layer, jnp.dtype(dtype), wrap, *args)
    assert got_out.shape == want_out.shape
    assert {k: v.shape for k, v in got.items()} == {k: v.shape for k, v in want.items()}
    if dtype == "float32":
        np.testing.assert_allclose(got_out, want_out, rtol=1e-5, atol=1e-6)
        for name in WEIGHTS + ("x",):
            assert _rel(got[name], want[name]) < 1e-5, name
        return
    # bfloat16: no farther from the float32 answers than the old body is,
    # within the luck of the last rounding (both hand back bfloat16 values)
    old_out, old = _value_and_grads(plain_lstm, layer, jnp.bfloat16, wrap, *args)
    luck = float(jnp.finfo(jnp.bfloat16).eps) / 2
    assert _rel(got_out, want_out) <= 1.25 * _rel(old_out, want_out) + luck
    for name in WEIGHTS + ("x",):
        gap, old_gap = _rel(got[name], want[name]), _rel(old[name], want[name])
        assert gap <= 1.25 * old_gap + luck, name


@pytest.mark.parametrize("return_sequences", [True, False], ids=["seq", "last"])
def test_lookback_of_one_is_one_product_and_one_step(return_sequences):
    layer = LSTMLayer(4, return_sequences=return_sequences)
    args = _inputs(layer, 3, time=1)
    want_out, want = _value_and_grads(plain_lstm, layer, jnp.float32, jax.jit, *args)
    got_out, got = _value_and_grads(nn._apply_lstm, layer, jnp.float32, jax.jit, *args)
    np.testing.assert_allclose(got_out, want_out, rtol=1e-5, atol=1e-6)
    for name in WEIGHTS + ("x",):
        np.testing.assert_allclose(got[name], want[name], rtol=1e-5, atol=1e-6)


# ------------------------------------------------------------- the structure
# the benchmark cell's widest layer: 12 machines, batch 64, lookback 144,
# 128 → 256 units, bfloat16 operands. Traced, never run.
CELL = dict(machines=12, batch=64, time=144, in_dim=128, units=256)


def _eqns(jaxpr, into_scans=True):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "scan" and not into_scans:
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub, into_scans)


def _scans(jaxpr):
    return [eqn for eqn in _eqns(jaxpr) if eqn.primitive.name == "scan"]


@pytest.fixture(scope="module")
def cell_scans():
    layer = LSTMLayer(CELL["units"], "tanh", "sigmoid", True)
    m, u4 = CELL["machines"], 4 * CELL["units"]

    def loss(p, x):
        p = {k: v.astype(jnp.bfloat16) for k, v in p.items()}
        return jnp.sum(nn._apply_lstm(layer, p, x).astype(jnp.float32))

    p = {
        "kernel": jax.ShapeDtypeStruct((m, CELL["in_dim"], u4), jnp.float32),
        "recurrent_kernel": jax.ShapeDtypeStruct((m, CELL["units"], u4), jnp.float32),
        "bias": jax.ShapeDtypeStruct((m, u4), jnp.float32),
    }
    x = jax.ShapeDtypeStruct(
        (m, CELL["batch"], CELL["time"], CELL["in_dim"]), jnp.bfloat16
    )
    closed = jax.make_jaxpr(jax.vmap(jax.value_and_grad(loss, argnums=(0, 1))))(p, x)
    (forward,) = [s for s in _scans(closed.jaxpr) if not s.params["reverse"]]
    (backward,) = [s for s in _scans(closed.jaxpr) if s.params["reverse"]]
    outside = [
        eqn.outvars[0].aval.shape
        for eqn in _eqns(closed.jaxpr, into_scans=False)
        if eqn.primitive.name == "dot_general"
    ]
    return {"forward": forward, "backward": backward, "products_outside": outside}


def _body(scan):
    return scan.params["jaxpr"].jaxpr


def _stacked(scan):
    return _body(scan).outvars[scan.params["num_carry"]:]


def _carry(scan):
    first = scan.params["num_consts"]
    return _body(scan).invars[first : first + scan.params["num_carry"]]


def _primitives(scan):
    return [eqn.primitive.name for eqn in _eqns(_body(scan))]


def test_forward_scan_stacks_only_what_cannot_be_recomputed(cell_scans):
    forward = cell_scans["forward"]
    m, b, u = CELL["machines"], CELL["batch"], CELL["units"]
    stacked = sorted((v.aval.shape, str(v.aval.dtype)) for v in _stacked(forward))
    # the cell state each step started from and the outputs: 6·units bytes a
    # (timestep, machine, sample) row where autodiff of a plain scan saves 49
    assert stacked == [((m, b, u), "bfloat16"), ((m, b, u), "float32")]
    assert [v.aval.shape for v in _carry(forward)] == [(m, b, u)] * 2


def test_reverse_scan_carries_no_weight_gradient_and_pads_nothing(cell_scans):
    backward = cell_scans["backward"]
    m, b, u = CELL["machines"], CELL["batch"], CELL["units"]
    weight_shapes = {
        (m, CELL["in_dim"], 4 * u), (m, u, 4 * u), (m, CELL["in_dim"] + u, 4 * u),
        (m, 4 * u),
    }
    carry = [v.aval for v in _carry(backward)]
    assert [a.shape for a in carry] == [(m, b, u)] * 2  # (dh, dc)
    assert all(str(a.dtype) == "float32" for a in carry)
    assert not weight_shapes & {a.shape for a in carry}
    assert "pad" not in _primitives(backward)
    # what it stacks: dz alone, at the dtype it has as a matmul operand
    assert [(v.aval.shape, str(v.aval.dtype)) for v in _stacked(backward)] == [
        ((m, b, 4 * u), "bfloat16")
    ]


@pytest.mark.parametrize("which, products", [("forward", 2), ("backward", 3)])
def test_scan_bodies_hold_the_gate_products_and_nothing_weight_shaped(
    cell_scans, which, products
):
    """Forward: x_t @ kernel and h @ recurrent_kernel (hoisting the first as
    one float32 [T, B, 4·units] product measured slower on the chip: its
    bytes cost more than 144 small products). Backward: the same two again,
    recomputing the gates, and dz @ recurrent_kernelᵀ. No [x_t, h] or weight
    concatenation in the forward step; the weight gradients' products are
    outside both loops."""
    names = _primitives(cell_scans[which])
    assert names.count("dot_general") == products
    if which == "forward":
        assert "concatenate" not in names


def test_weight_gradients_are_one_product_outside_the_loops(cell_scans):
    m, u, n_in = CELL["machines"], CELL["units"], CELL["in_dim"]
    outside = [s for s in cell_scans["products_outside"] if s[-1] == 4 * u]
    # kernel, recurrent kernel and bias (the weight of a constant-one input)
    assert outside == [(m, n_in + u + 1, 4 * u)]


def test_plain_call_stacks_the_outputs_only():
    """Predictions and serving run the same body and save nothing."""
    for return_sequences, n_stacked in ((True, 1), (False, 0)):
        layer = LSTMLayer(8, return_sequences=return_sequences)
        p = nn.init_lstm_layer(jax.random.PRNGKey(0), 3, 8)
        closed = jax.make_jaxpr(lambda x: nn._apply_lstm(layer, p, x))(
            jnp.zeros((BATCH, TIME, 3))
        )
        (scan,) = _scans(closed.jaxpr)
        assert len(_stacked(scan)) == n_stacked
        assert _primitives(scan).count("dot_general") == 2
