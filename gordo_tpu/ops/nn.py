"""
Layer init/apply as pure functions over parameter pytrees.

Initialization matches Keras defaults (the reference's models are Keras
Sequential stacks, gordo/machine/model/factories/): Dense → glorot-uniform
kernel, zero bias; LSTM → glorot-uniform input kernel, orthogonal recurrent
kernel, zero bias with unit forget-gate bias.

Everything is shape-static and vmap-safe: parameters are dicts of jnp arrays,
and ``apply_model`` is a pure function of (spec, params, x).
"""

import functools
import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax.custom_batching import sequential_vmap

from gordo_tpu.models.spec import (
    DenseLayer,
    HybridBlock,
    LatentBlock,
    LayerNormLayer,
    LSTMLayer,
    ModelSpec,
    MoEBlock,
    PoolLayer,
    PositionalEncoding,
    RMSNormLayer,
    SambaYBlock,
    StreamLayer,
    TCNBlock,
    TransformerBlock,
    check_shared_state,
)
from gordo_tpu.ops.attention import (
    dot_product_attention,
    dot_product_attention_xla,
    multihead_attention,
)

Params = List[Dict[str, Any]]

ACTIVATIONS = {
    "linear": lambda x: x,
    "tanh": jnp.tanh,
    "relu": jax.nn.relu,
    "sigmoid": jax.nn.sigmoid,
    "elu": jax.nn.elu,
    "selu": jax.nn.selu,
    "softplus": jax.nn.softplus,
    "softsign": jax.nn.soft_sign,
    "swish": jax.nn.swish,
    "gelu": jax.nn.gelu,
    "leaky_relu": jax.nn.leaky_relu,
    "exponential": jnp.exp,
    "hard_sigmoid": jax.nn.hard_sigmoid,
}


def _activation(name: str):
    try:
        return ACTIVATIONS[name]
    except KeyError:
        raise ValueError(
            f"Unknown activation {name!r}; available: {sorted(ACTIVATIONS)}"
        ) from None


def _glorot_uniform(rng, shape, dtype=jnp.float32):
    fan_in, fan_out = shape[-2], shape[-1]
    limit = jnp.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(rng, shape, dtype, -limit, limit)


def _orthogonal(rng, shape, dtype=jnp.float32):
    return jax.nn.initializers.orthogonal()(rng, shape, dtype)


def init_dense_layer(rng, in_dim: int, units: int) -> Dict[str, jnp.ndarray]:
    return {
        "kernel": _glorot_uniform(rng, (in_dim, units)),
        "bias": jnp.zeros((units,), jnp.float32),
    }


def init_lstm_layer(rng, in_dim: int, units: int) -> Dict[str, jnp.ndarray]:
    k1, k2 = jax.random.split(rng)
    bias = jnp.zeros((4 * units,), jnp.float32)
    # unit forget-gate bias (Keras unit_forget_bias=True); gate order i,f,g,o
    bias = bias.at[units : 2 * units].set(1.0)
    return {
        "kernel": _glorot_uniform(k1, (in_dim, 4 * units)),
        "recurrent_kernel": _orthogonal(k2, (units, 4 * units)),
        "bias": bias,
    }


def _init_attention_params(ks, d: int) -> Dict[str, jnp.ndarray]:
    """Pre-LN MHA sublayer params shared by Transformer and MoE blocks
    (ks: four RNG keys for wq/wk/wv/wo)."""
    return {
        "ln1_scale": jnp.ones((d,), jnp.float32),
        "ln1_bias": jnp.zeros((d,), jnp.float32),
        "wq": _glorot_uniform(ks[0], (d, d)),
        "wk": _glorot_uniform(ks[1], (d, d)),
        "wv": _glorot_uniform(ks[2], (d, d)),
        "wo": _glorot_uniform(ks[3], (d, d)),
        "bq": jnp.zeros((d,), jnp.float32),
        "bk": jnp.zeros((d,), jnp.float32),
        "bv": jnp.zeros((d,), jnp.float32),
        "bo": jnp.zeros((d,), jnp.float32),
        "ln2_scale": jnp.ones((d,), jnp.float32),
        "ln2_bias": jnp.zeros((d,), jnp.float32),
    }


def init_transformer_block(rng, in_dim: int, layer: TransformerBlock):
    if in_dim != layer.d_model:
        raise ValueError(
            f"TransformerBlock d_model={layer.d_model} but incoming dim is "
            f"{in_dim}; insert a Dense projection first"
        )
    d, ff = layer.d_model, layer.ff_dim
    ks = jax.random.split(rng, 6)
    return {
        **_init_attention_params(ks[:4], d),
        "w_ff1": _glorot_uniform(ks[4], (d, ff)),
        "b_ff1": jnp.zeros((ff,), jnp.float32),
        "w_ff2": _glorot_uniform(ks[5], (ff, d)),
        "b_ff2": jnp.zeros((d,), jnp.float32),
    }


def init_moe_block(rng, in_dim: int, layer: MoEBlock):
    if in_dim != layer.d_model:
        raise ValueError(
            f"MoEBlock d_model={layer.d_model} but incoming dim is "
            f"{in_dim}; insert a Dense projection first"
        )
    d, f, e = layer.d_model, layer.expert_dim, layer.num_experts
    ks = jax.random.split(rng, 7)
    return {
        **_init_attention_params(ks[:4], d),
        "router": _glorot_uniform(ks[4], (d, e)),
        # experts stacked on a leading axis — the axis expert parallelism
        # shards over (parallel/expert_parallel.py)
        "w1": jax.vmap(lambda k: _glorot_uniform(k, (d, f)))(
            jax.random.split(ks[5], e)
        ),
        "b1": jnp.zeros((e, f), jnp.float32),
        "w2": jax.vmap(lambda k: _glorot_uniform(k, (f, d)))(
            jax.random.split(ks[6], e)
        ),
        "b2": jnp.zeros((e, d), jnp.float32),
    }


def _normal(rng, shape, std=0.02):
    return std * jax.random.normal(rng, shape, jnp.float32)


# differential attention's four λ vectors, in the order their keys are drawn
_DIFF_LAMBDAS = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")


def _init_ffn(k_ffn, layer, p) -> None:
    """The FFN's leaves of a hybrid or latent block, into ``p``: SwiGLU of
    ``ff_dim`` (``dense``), or the router, the zero selection bias and the
    held experts' stacked SwiGLUs (``routed``)."""
    d, f = layer.d_model, layer.ff_dim
    if layer.ffn == "dense":
        ks = jax.random.split(k_ffn, 3)
        p["w1"] = _normal(ks[0], (d, f))
        p["w3"] = _normal(ks[1], (d, f))
        p["w2"] = _normal(ks[2], (f, d))
    elif layer.ffn == "routed":
        ks = jax.random.split(k_ffn, 4)
        held = layer.experts_held
        p["router"] = _normal(ks[0], (d, layer.num_experts))
        # enters the selection only: no gradient reaches it, and its update
        # rule (not in the published config) is not implemented
        p["expert_bias"] = jnp.zeros((layer.num_experts,), jnp.float32)
        p["w1"] = _normal(ks[1], (held, d, f))
        p["w3"] = _normal(ks[2], (held, d, f))
        p["w2"] = _normal(ks[3], (held, f, d))
    else:
        raise ValueError(f"Unknown {type(layer).__name__} ffn {layer.ffn!r}")


def init_hybrid_block(rng, in_dim: int, layer: HybridBlock):
    """normal(0, 0.02) matrices and taps (the LFM2 family's
    ``initializer_range``), unit gains, a zero selection bias; one key for
    the operator and one for the FFN, each split over its matrices."""
    if in_dim != layer.d_model:
        raise ValueError(
            f"HybridBlock d_model={layer.d_model} but incoming dim is "
            f"{in_dim}; insert a Dense projection first"
        )
    d = layer.d_model
    k_op, k_ffn = jax.random.split(rng)
    p = {
        "op_norm": jnp.ones((d,), jnp.float32),
        "ffn_norm": jnp.ones((d,), jnp.float32),
    }
    if layer.operator == "conv":
        ks = jax.random.split(k_op, 3)
        p["conv_in"] = _normal(ks[0], (d, 3 * d))
        p["conv_taps"] = _normal(ks[1], (d, layer.conv_kernel))
        p["conv_out"] = _normal(ks[2], (d, d))
    elif layer.operator == "attention":
        ks = jax.random.split(k_op, 4)
        hq = layer.num_heads * layer.head_dim
        hkv = layer.num_kv_heads * layer.head_dim
        p["wq"] = _normal(ks[0], (d, hq))
        p["wk"] = _normal(ks[1], (d, hkv))
        p["wv"] = _normal(ks[2], (d, hkv))
        p["wo"] = _normal(ks[3], (hq, d))
        p["q_norm"] = jnp.ones((layer.head_dim,), jnp.float32)
        p["k_norm"] = jnp.ones((layer.head_dim,), jnp.float32)
    else:
        raise ValueError(f"Unknown HybridBlock operator {layer.operator!r}")
    _init_ffn(k_ffn, layer, p)
    return p


# b_res at the start: 0 on the diagonal and this much under it elsewhere, so
# that Sinkhorn(exp(b_res)) is the identity to 3e-4 (the mixing starts as the
# plain residual, arXiv:2512.24880 section 4)
_HC_RES_OFF_DIAGONAL = -8.0


def init_latent_block(rng, in_dim: int, layer: LatentBlock):
    """normal(0, 0.02) matrices (the family's ``initializer_range``), unit
    gains, a zero selection bias; the streams' coefficients start as the
    plain residual: alpha 0.01, ``b_pre`` = ``b_post`` = 0 (``H_pre`` a half
    a stream, ``H_post`` 1), ``b_res`` the identity's logits. One key for the
    attention, one for the FFN, one for the two sublayers' mixing."""
    if in_dim != layer.d_model:
        raise ValueError(
            f"LatentBlock d_model={layer.d_model} but incoming dim is "
            f"{in_dim}; insert a Dense projection first"
        )
    d, n, heads = layer.d_model, layer.streams, layer.num_heads
    rank_q, rank_kv = layer.q_lora_rank, layer.kv_lora_rank
    nope, rope, dv = layer.qk_nope_head_dim, layer.qk_rope_head_dim, layer.v_head_dim
    k_op, k_ffn, k_hc = jax.random.split(rng, 3)
    ks = jax.random.split(k_op, 5)
    p = {
        "op_norm": jnp.ones((d,), jnp.float32),
        "ffn_norm": jnp.ones((d,), jnp.float32),
        "w_dq": _normal(ks[0], (d, rank_q)),
        "q_norm": jnp.ones((rank_q,), jnp.float32),
        "w_uq": _normal(ks[1], (rank_q, heads * (nope + rope))),
        "w_dkv": _normal(ks[2], (d, rank_kv + rope)),
        "kv_norm": jnp.ones((rank_kv,), jnp.float32),
        "w_ukv": _normal(ks[3], (rank_kv, heads * (nope + dv))),
        "wo": _normal(ks[4], (heads * dv, d)),
    }
    k_routed, k_shared = jax.random.split(k_ffn)
    _init_ffn(k_routed, layer, p)
    if layer.ffn == "routed" and layer.shared_experts:
        ks = jax.random.split(k_shared, 3)
        f = layer.shared_experts * layer.ff_dim
        p["shared_w1"] = _normal(ks[0], (d, f))
        p["shared_w3"] = _normal(ks[1], (d, f))
        p["shared_w2"] = _normal(ks[2], (f, d))
    for prefix, key in zip(("hc_op_", "hc_ffn_"), jax.random.split(k_hc)):
        # columns [pre (n) | post (n) | res (n x n, row-major)]
        p[prefix + "phi"] = _normal(key, (n, d, 2 * n + n * n))
        p[prefix + "alpha"] = jnp.full((3,), 0.01, jnp.float32)
        p[prefix + "b_pre"] = jnp.zeros((n,), jnp.float32)
        p[prefix + "b_post"] = jnp.zeros((n,), jnp.float32)
        p[prefix + "b_res"] = _HC_RES_OFF_DIAGONAL * (
            1.0 - jnp.eye(n, dtype=jnp.float32)
        )
    return p


def init_sambay_block(rng, in_dim: int, layer: SambaYBlock):
    """normal(0, 0.02) matrices, unit gains and zero biases of the two
    LayerNorms, a zero convolution bias; Mamba's own start for the scan
    (``A_log = log(1..d_state)`` a channel, ``D = 1``, Δ's bias the inverse
    softplus of a log-uniform draw in [1e-3, 1e-1], floored at 1e-4) and
    differential attention's (its four λ vectors normal(0, 0.1), a unit gain
    on the head norm). One key for the mixer and one for the MLP, each split
    over its matrices."""
    if in_dim != layer.d_model:
        raise ValueError(
            f"SambaYBlock d_model={layer.d_model} but incoming dim is "
            f"{in_dim}; insert a Dense projection first"
        )
    d, di, ds, dh = layer.d_model, layer.d_inner, layer.d_state, layer.head_dim
    k_mix, k_ffn = jax.random.split(rng)
    p = {
        "ln1_scale": jnp.ones((d,), jnp.float32),
        "ln1_bias": jnp.zeros((d,), jnp.float32),
        "ln2_scale": jnp.ones((d,), jnp.float32),
        "ln2_bias": jnp.zeros((d,), jnp.float32),
    }
    if layer.mixer == "mamba":
        ks = jax.random.split(k_mix, 6)
        lo, hi = math.log(1e-3), math.log(1e-1)
        dt = jnp.exp(jax.random.uniform(ks[4], (di,), jnp.float32) * (hi - lo) + lo)
        dt = jnp.maximum(dt, 1e-4)
        p["w_in"] = _normal(ks[0], (d, 2 * di))
        p["conv_taps"] = _normal(ks[1], (di, layer.d_conv))
        p["conv_bias"] = jnp.zeros((di,), jnp.float32)
        p["w_x"] = _normal(ks[2], (di, layer.dt_rank + 2 * ds))
        p["w_dt"] = _normal(ks[3], (layer.dt_rank, di))
        p["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
        p["a_log"] = jnp.log(
            jnp.broadcast_to(jnp.arange(1, ds + 1, dtype=jnp.float32), (di, ds))
        )
        p["d_skip"] = jnp.ones((di,), jnp.float32)
        p["w_out"] = _normal(ks[5], (di, d))
    elif layer.mixer in ("attention", "cross_attention"):
        ks = jax.random.split(k_mix, 8)
        p["wq"] = _normal(ks[0], (d, layer.num_heads * dh))
        if layer.mixer == "attention":
            p["wk"] = _normal(ks[1], (d, layer.num_kv_heads * dh))
            p["wv"] = _normal(ks[2], (d, layer.num_kv_heads * dh))
        p["wo"] = _normal(ks[3], (layer.num_heads * dh, d))
        for name, key in zip(_DIFF_LAMBDAS, ks[4:]):
            p[name] = _normal(key, (dh,), std=0.1)
        p["subln"] = jnp.ones((2 * dh,), jnp.float32)
    elif layer.mixer == "gmu":
        ks = jax.random.split(k_mix, 2)
        p["w_gate"] = _normal(ks[0], (d, di))
        p["w_gmu_out"] = _normal(ks[1], (di, d))
    else:
        raise ValueError(f"Unknown SambaYBlock mixer {layer.mixer!r}")
    ks = jax.random.split(k_ffn, 3)
    p["w1"] = _normal(ks[0], (d, layer.ff_dim))
    p["w3"] = _normal(ks[1], (d, layer.ff_dim))
    p["w2"] = _normal(ks[2], (layer.ff_dim, d))
    return p


def init_tcn_block(rng, in_dim: int, layer: TCNBlock):
    k1, k2, k3 = jax.random.split(rng, 3)
    filters, ksize = layer.filters, layer.kernel_size
    params = {
        # conv kernels in WIO layout: (width, in_channels, out_channels)
        "conv1_kernel": _glorot_uniform(k1, (ksize, in_dim, filters)),
        "conv1_bias": jnp.zeros((filters,), jnp.float32),
        "conv2_kernel": _glorot_uniform(k2, (ksize, filters, filters)),
        "conv2_bias": jnp.zeros((filters,), jnp.float32),
    }
    if in_dim != filters:
        params["res_kernel"] = _glorot_uniform(k3, (1, in_dim, filters))
    return params


def layer_out_dim(layer, in_dim: int) -> int:
    """Feature dimension a layer produces given its input dimension."""
    if isinstance(layer, (DenseLayer, LSTMLayer)):
        return layer.units
    if isinstance(
        layer, (TransformerBlock, MoEBlock, HybridBlock, LatentBlock, SambaYBlock)
    ):
        return layer.d_model
    if isinstance(layer, TCNBlock):
        return layer.filters
    if isinstance(
        layer, (PositionalEncoding, PoolLayer, RMSNormLayer, LayerNormLayer, StreamLayer)
    ):
        return in_dim
    raise TypeError(f"Unknown layer spec: {layer!r}")


def init_model_params(rng: jax.Array, spec: ModelSpec) -> Params:
    """Initialize the full parameter pytree for a ModelSpec."""
    params: Params = []
    in_dim = spec.n_features
    rngs = jax.random.split(rng, len(spec.layers))
    for layer, layer_rng in zip(spec.layers, rngs):
        if isinstance(layer, DenseLayer):
            params.append(init_dense_layer(layer_rng, in_dim, layer.units))
        elif isinstance(layer, LSTMLayer):
            params.append(init_lstm_layer(layer_rng, in_dim, layer.units))
        elif isinstance(layer, TransformerBlock):
            params.append(init_transformer_block(layer_rng, in_dim, layer))
        elif isinstance(layer, MoEBlock):
            params.append(init_moe_block(layer_rng, in_dim, layer))
        elif isinstance(layer, HybridBlock):
            params.append(init_hybrid_block(layer_rng, in_dim, layer))
        elif isinstance(layer, LatentBlock):
            params.append(init_latent_block(layer_rng, in_dim, layer))
        elif isinstance(layer, SambaYBlock):
            params.append(init_sambay_block(layer_rng, in_dim, layer))
        elif isinstance(layer, RMSNormLayer):
            params.append({"scale": jnp.ones((in_dim,), jnp.float32)})
        elif isinstance(layer, LayerNormLayer):
            params.append(
                {
                    "scale": jnp.ones((in_dim,), jnp.float32),
                    "bias": jnp.zeros((in_dim,), jnp.float32),
                }
            )
        elif isinstance(layer, TCNBlock):
            params.append(init_tcn_block(layer_rng, in_dim, layer))
        elif isinstance(layer, (PositionalEncoding, PoolLayer, StreamLayer)):
            params.append({})
        else:
            raise TypeError(f"Unknown layer spec: {layer!r}")
        in_dim = layer_out_dim(layer, in_dim)
    return params


# The named scopes here, in ops/train.py and in parallel/batch_trainer.py
# (dense, lstm_input_proj, lstm_cell, lstm_weight_grad, attention, rms_norm,
# gated_conv, moe_router, moe_dispatch, moe_experts, window_gather,
# optimizer_update, fold_predict)
# are what a device trace is reduced by (scripts/trace_by_scope.py; the table
# is in docs/observability.md): metadata only, and stable names, so rename
# none. JAX writes jvp(...) / transpose(jvp(...)) into the op_name for the
# forward and backward pass under differentiation.
@jax.named_scope("dense")
def _apply_dense(layer: DenseLayer, p, x):
    out = x @ p["kernel"] + p["bias"]
    return _activation(layer.activation)(out)


def _lstm_gates(layer: LSTMLayer, kernel, recurrent_kernel, bias, x_t, h_prev):
    """One step's gates (i, f, g, o; Keras' order) as (activation,
    pre-activation) pairs. The products' operands are at the compute dtype;
    both are accumulated, and summed with the bias, in float32."""
    with jax.named_scope("lstm_input_proj"):
        z = jnp.dot(x_t, kernel, preferred_element_type=jnp.float32) + bias.astype(
            jnp.float32
        )
    z = z + jnp.dot(h_prev, recurrent_kernel, preferred_element_type=jnp.float32)
    act = _activation(layer.activation)
    rec_act = _activation(layer.recurrent_activation)
    units = layer.units
    return [
        (fn, z[..., k * units : (k + 1) * units])
        for k, fn in enumerate((rec_act, rec_act, act, rec_act))
    ]


def _lstm_scan(layer: LSTMLayer, kernel, recurrent_kernel, bias, x, save: bool):
    """The layer's forward pass: a scan over time whose state (h, c) is
    carried in float32 — bf16's 8-bit mantissa drifts badly over long scans
    in ``c = f*c + i*g``.

    ``save`` (the differentiated call) stacks, time-major, the little the
    hand-written backward cannot recompute: the cell state each step started
    from, and the outputs. The plain call stacks the outputs only, and
    nothing at all for a many-to-one tail layer.
    """
    act = _activation(layer.activation)
    x = jnp.swapaxes(x, 0, 1)  # time-major, as the scan reads and stacks

    @jax.named_scope("lstm_cell")
    def step(carry, x_t):
        h, c_prev = carry
        i, f, g, o = (
            fn(z) for fn, z in _lstm_gates(
                layer, kernel, recurrent_kernel, bias, x_t, h.astype(x.dtype)
            )
        )
        c = f * c_prev + i * g
        h = o * act(c)
        out = h.astype(x.dtype)
        if save:
            return (h, c), (c_prev, out)
        return (h, c), (out if layer.return_sequences else None)

    zeros = jnp.zeros((x.shape[1], layer.units), jnp.float32)
    (h, _), stacked = jax.lax.scan(step, (zeros, zeros), x)
    hs = stacked[1] if save else stacked
    out = jnp.swapaxes(hs, 0, 1) if layer.return_sequences else h.astype(x.dtype)
    return out, ((x, *stacked) if save else ())


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _lstm(layer: LSTMLayer, kernel, recurrent_kernel, bias, x):
    return _lstm_scan(layer, kernel, recurrent_kernel, bias, x, save=False)[0]


def _lstm_fwd(layer, kernel, recurrent_kernel, bias, x):
    out, saved = _lstm_scan(layer, kernel, recurrent_kernel, bias, x, save=True)
    return out, (kernel, recurrent_kernel, bias, *saved)


def _lstm_bwd(layer, residuals, d_out):
    """Backward pass by hand. JAX's transpose of the forward scan saves every
    operand of every step (thirteen stacked buffers a layer) and carries the
    weight gradients through the loop; on the chip that step is bound by
    those bytes, not by its arithmetic. Here the reverse scan recomputes a
    step's gates from its inputs (two products, the same as forward), carries
    (dh, dc) only and stacks the pre-activations' cotangent ``dz``. After
    it, the kernel, recurrent-kernel and bias gradients are ONE product over
    all of (time, batch) — the bias as the weight of a constant-one input —
    and the input gradient another, accumulated in float32."""
    kernel, recurrent_kernel, bias, x, c_prevs, hs = residuals  # x time-major
    act = _activation(layer.activation)
    f32 = jnp.float32
    # h[t-1], the recurrent product's operand: the outputs shifted by one step
    h_prevs = jnp.concatenate([jnp.zeros_like(hs[:1]), hs[:-1]], axis=0)

    @jax.named_scope("lstm_cell")
    def step(carry, saved):
        dh, dc = carry
        x_t, h_prev, c_prev, dh_t = saved
        if layer.return_sequences:
            dh = dh + dh_t.astype(f32)
        # any activation the spec allows: jax.vjp takes its derivative at the
        # recomputed pre-activation, so none is refused or approximated
        (i, d_i), (f, d_f), (g, d_g), (o, d_o) = (
            jax.vjp(fn, z) for fn, z in _lstm_gates(
                layer, kernel, recurrent_kernel, bias, x_t, h_prev
            )
        )
        act_c, d_act = jax.vjp(act, f * c_prev + i * g)
        dc = dc + d_act(dh * o)[0]
        dz = jnp.concatenate(
            [d_i(dc * g)[0], d_f(dc * c_prev)[0], d_g(dc * i)[0],
             d_o(dh * act_c)[0]],
            axis=-1,
        ).astype(x.dtype)
        dh_prev = jax.lax.dot_general(
            dz, recurrent_kernel, (((1,), (1,)), ((), ())),
            preferred_element_type=f32,
        )
        return (dh_prev, dc * f), dz

    zeros = jnp.zeros(c_prevs.shape[1:], f32)
    if layer.return_sequences:
        carry, dhs = (zeros, zeros), jnp.swapaxes(d_out, 0, 1)
    else:
        carry, dhs = (d_out.astype(f32), zeros), None
    _, dzs = jax.lax.scan(step, carry, (x, h_prevs, c_prevs, dhs), reverse=True)

    with jax.named_scope("lstm_weight_grad"):
        n_in = x.shape[-1]
        inputs = jnp.concatenate(
            [x, h_prevs, jnp.ones(x.shape[:2] + (1,), x.dtype)], axis=-1
        )
        d_weights = jnp.tensordot(
            inputs, dzs, ((0, 1), (0, 1)), preferred_element_type=f32
        )
        dx = jnp.tensordot(dzs, kernel, ((2,), (1,)), preferred_element_type=f32)
    return (
        d_weights[:n_in].astype(kernel.dtype),
        d_weights[n_in:-1].astype(recurrent_kernel.dtype),
        d_weights[-1].astype(bias.dtype),
        jnp.swapaxes(dx, 0, 1).astype(x.dtype),
    )


_lstm.defvjp(_lstm_fwd, _lstm_bwd)


def _apply_lstm(layer: LSTMLayer, p, x):
    """x: (batch, time, in_dim) → (batch, time, units) or (batch, units)."""
    return _lstm(layer, p["kernel"], p["recurrent_kernel"], p["bias"], x)


def _layer_norm(x, scale, bias, eps=1e-6):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _apply_positional_encoding(layer: PositionalEncoding, x):
    """x: (batch, time, d). Sinusoidal PE (Vaswani et al.), added to x."""
    _, t, d = x.shape
    pos = jnp.arange(t, dtype=jnp.float32)[:, None]
    half = (d + 1) // 2
    freqs = jnp.exp(
        -jnp.log(layer.max_wavelength) * jnp.arange(half, dtype=jnp.float32)
        / jnp.maximum(half - 1, 1)
    )[None, :]
    angles = pos * freqs
    pe = jnp.zeros((t, d), x.dtype)
    pe = pe.at[:, 0::2].set(jnp.sin(angles)[:, : (d + 1) // 2])
    pe = pe.at[:, 1::2].set(jnp.cos(angles)[:, : d // 2])
    return x + pe[None, :, :]


@jax.named_scope("attention")
def _attention_sublayer(layer, p, x, fuse_qkv=None):
    """Pre-LN MHA + residual, shared by TransformerBlock and MoEBlock
    (same param keys, same dispatch). ``fuse_qkv=None`` defers to the
    layer's own flag (shard_map callers — PP stages, EP — hold local
    params, where fusion is always safe)."""
    h = _layer_norm(x, p["ln1_scale"], p["ln1_bias"])
    fuse = fuse_qkv if fuse_qkv is not None else getattr(layer, "fuse_qkv", True)
    if fuse:
        # one fused (d, 3d) projection instead of three (d, d) matmuls —
        # same math, fewer dispatches (params stay separate, so the
        # artifact format is untouched). prepare_tp_spec disables this:
        # under the Megatron column shardings the concat costs collectives.
        w_qkv = jnp.concatenate([p["wq"], p["wk"], p["wv"]], axis=1)
        b_qkv = jnp.concatenate([p["bq"], p["bk"], p["bv"]])
        q, k, v = jnp.split(h @ w_qkv + b_qkv, 3, axis=-1)
    else:
        q = h @ p["wq"] + p["bq"]
        k = h @ p["wk"] + p["bk"]
        v = h @ p["wv"] + p["bv"]
    # an explicit per-layer impl pins the choice; "auto" defers to the
    # dispatcher (and its GORDO_TPU_ATTENTION_IMPL env override)
    layer_impl = getattr(layer, "attention_impl", "auto")
    attn = multihead_attention(
        q,
        k,
        v,
        layer.num_heads,
        causal=layer.causal,
        impl=None if layer_impl == "auto" else layer_impl,
    )
    return x + attn @ p["wo"] + p["bo"]


def _apply_transformer_block(layer: TransformerBlock, p, x, fuse_qkv=None):
    """Pre-LN encoder block. x: (batch, time, d_model)."""
    x = _attention_sublayer(layer, p, x, fuse_qkv=fuse_qkv)
    h = _layer_norm(x, p["ln2_scale"], p["ln2_bias"])
    ff = _activation(layer.activation)(h @ p["w_ff1"] + p["b_ff1"])
    return x + ff @ p["w_ff2"] + p["b_ff2"]


def moe_capacity(layer: MoEBlock, n_tokens: int) -> int:
    """Per-expert token capacity (Switch Transformer semantics)."""
    import math

    return max(1, math.ceil(n_tokens * layer.capacity_factor / layer.num_experts))


def moe_dispatch_ffn(
    layer: MoEBlock,
    expert_w,
    h: jnp.ndarray,
    gates: jnp.ndarray,
    expert_offset: int,
    n_local: int,
):
    """Routed-FFN contribution of ``n_local`` experts starting at
    ``expert_offset``. Shared by the single-device path (offset 0, all
    experts) and the expert-parallel shard_map (each device its slice, then
    psum) — one definition, so the two paths cannot drift.

    ``h``: (N, D) post-LN tokens; ``gates``: (N, E) router softmax over ALL
    experts (the router is replicated; only expert FFN weights shard).
    ``expert_w``: dict with ``w1`` (n_local, D, F), ``b1``, ``w2``, ``b2``.
    Returns (N, D): gate-weighted expert outputs, zeros for tokens routed
    elsewhere or over capacity.

    Mechanics: top-1 routing; per-expert token position via a one-hot
    cumsum; tokens scatter into a fixed (n_local, C+1, D) buffer (row C is
    the overflow dump), experts run as one batched einsum on the MXU, and
    outputs gather back by the same positions.
    """
    n_tokens, d = h.shape
    cap = moe_capacity(layer, n_tokens)
    top1 = jnp.argmax(gates, axis=-1)  # (N,)
    gate = jnp.take_along_axis(gates, top1[:, None], axis=1)[:, 0]
    onehot = jax.nn.one_hot(top1, layer.num_experts, dtype=jnp.float32)
    # position of each token within its expert's buffer, same for every
    # shard (cumsum over the full token axis in token order)
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0
    pos1 = jnp.take_along_axis(pos, top1[:, None], axis=1)[:, 0].astype(jnp.int32)
    local = jnp.logical_and(
        top1 >= expert_offset, top1 < expert_offset + n_local
    )
    keep = jnp.logical_and(local, pos1 < cap)
    idx_e = jnp.where(keep, top1 - expert_offset, 0)
    idx_c = jnp.where(keep, pos1, cap)  # overflow/foreign -> dump row
    buf = jnp.zeros((n_local, cap + 1, d), h.dtype)
    buf = buf.at[idx_e, idx_c].set(h)[:, :cap]
    act = _activation(layer.activation)
    mid = act(
        jnp.einsum("ecd,edf->ecf", buf, expert_w["w1"])
        + expert_w["b1"][:, None, :].astype(buf.dtype)
    )
    out_buf = jnp.einsum("ecf,efd->ecd", mid, expert_w["w2"]) + expert_w[
        "b2"
    ][:, None, :].astype(buf.dtype)
    tok_out = out_buf[idx_e, jnp.clip(pos1, 0, cap - 1)]
    weight = (gate * keep.astype(gate.dtype)).astype(tok_out.dtype)
    return tok_out * weight[:, None]


def moe_aux_loss(layer: MoEBlock, gates: jnp.ndarray) -> jnp.ndarray:
    """Switch load-balancing loss: E * sum_e f_e * P_e (Fedus et al. §2.2),
    where f_e is the fraction of tokens whose top-1 expert is e and P_e the
    mean router probability for e. Minimized (= 1) under uniform routing;
    differentiable through P_e, so the router learns to spread load."""
    top1 = jnp.argmax(gates, axis=-1)
    f = jnp.mean(
        jax.nn.one_hot(top1, layer.num_experts, dtype=jnp.float32), axis=0
    )
    p_mean = jnp.mean(gates, axis=0)
    return layer.num_experts * jnp.sum(f * p_mean)


def _apply_moe_block(
    layer: MoEBlock, p, x, ffn_fn=None, return_aux=False, fuse_qkv=None
):
    """Pre-LN MoE encoder block. x: (batch, time, d_model).

    ``ffn_fn(layer, expert_w, flat, gates)`` overrides the routed-FFN
    execution — expert parallelism passes its shard_map here; attention and
    routing are identical either way. With ``return_aux`` the weighted
    Switch load-balancing loss rides along for the training penalty.
    """
    x = _attention_sublayer(layer, p, x, fuse_qkv=fuse_qkv)
    h = _layer_norm(x, p["ln2_scale"], p["ln2_bias"])
    b, t, d = h.shape
    flat = h.reshape(b * t, d)
    # router runs in float32: argmax ties and tiny gate logits are routing
    # decisions, not activations
    gates = jax.nn.softmax((flat.astype(jnp.float32) @ p["router"]), axis=-1)
    expert_w = {key: p[key] for key in ("w1", "b1", "w2", "b2")}
    if ffn_fn is None:
        ffn = moe_dispatch_ffn(
            layer, expert_w, flat, gates, 0, layer.num_experts
        )
    else:
        ffn = ffn_fn(layer, expert_w, flat, gates)
    out = x + ffn.reshape(b, t, d)
    if not return_aux:
        return out
    weight = float(getattr(layer, "aux_loss_weight", 0.0) or 0.0)
    aux = weight * moe_aux_loss(layer, gates) if weight > 0.0 else jnp.asarray(
        0.0, jnp.float32
    )
    return out, aux


def _rms(x, scale, eps: float):
    """``x * rsqrt(mean(x^2) + eps) * scale`` over the last axis, computed in
    float32 whatever the compute dtype."""
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)).astype(x.dtype)


# a block's own norms; a norm inside another scope (the latent projections')
# calls _rms and is counted there
_rms_norm = jax.named_scope("rms_norm")(_rms)


def _rope(x, theta: float, inv_freq=None, first: int = 0):
    """Rotary position embedding, rotate-half convention, on (..., T, Dh)
    whose rows are the positions from ``first`` on: pair ``i`` of the two
    halves turns by ``t * inv_freq[i]``, ``theta**(-2i/Dh)`` unless the
    caller brings its own frequencies."""
    t, dh = x.shape[-2], x.shape[-1]
    if inv_freq is None:
        inv_freq = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    angles = jnp.arange(first, first + t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)
    x32 = x.astype(jnp.float32)
    rotated = jnp.concatenate([-x32[..., dh // 2 :], x32[..., : dh // 2]], axis=-1)
    return (x32 * cos + rotated * sin).astype(x.dtype)


def _causal_depthwise_conv(z, taps):
    """A depthwise causal convolution over time of (B, T, C) by (C, K) taps:
    ``conv(z)[t] = sum_j taps[:, j] * z[t - (K-1) + j]``, zeros before the
    start."""
    kw, t = taps.shape[-1], z.shape[1]
    zp = jnp.pad(z, ((0, 0), (kw - 1, 0), (0, 0)))
    return sum(zp[:, j : j + t, :] * taps[:, j] for j in range(kw))


@jax.named_scope("gated_conv")
def _gated_conv(layer: HybridBlock, p, h):
    """``[b, c, u] = split(h @ W_in)``; ``y = c * conv(b * u)`` with a
    depthwise causal convolution over time (:func:`_causal_depthwise_conv`
    of ``conv_kernel`` taps); out ``y @ W_out``."""
    b, c, u = jnp.split(h @ p["conv_in"], 3, axis=-1)
    return (c * _causal_depthwise_conv(b * u, p["conv_taps"])) @ p["conv_out"]


def _attention_impl(layer, last_only: bool):
    """The dispatcher's ``impl`` for a block's attention. The last position's
    single query row goes to the XLA path whatever the spec asks: the flash
    kernel and the ring take equal query and key lengths."""
    if last_only:
        return "xla"
    return None if layer.attention_impl == "auto" else layer.attention_impl


@jax.named_scope("attention")
def _gqa_attention(layer: HybridBlock, p, h, last_only: bool = False):
    """Grouped-query causal attention: RMSNorm a head on q and k, RoPE, each
    key/value head serving ``num_heads / num_kv_heads`` consecutive query
    heads; through the dispatcher of ops/attention.py. With ``last_only`` the
    queries are the last position's alone, against every position's keys
    and values: (B, 1, d) out."""
    bsz, t, _ = h.shape
    dh, rep = layer.head_dim, layer.num_heads // layer.num_kv_heads
    h_q = h[:, -1:] if last_only else h

    def heads(a, n):
        return a.reshape(bsz, a.shape[1], n, dh).transpose(0, 2, 1, 3)

    q = heads(h_q @ p["wq"], layer.num_heads)
    k = heads(h @ p["wk"], layer.num_kv_heads)
    v = heads(h @ p["wv"], layer.num_kv_heads)
    q = _rope(
        _rms_norm(q, p["q_norm"], layer.norm_eps), layer.rope_theta,
        first=t - h_q.shape[1],
    )
    k = _rope(_rms_norm(k, p["k_norm"], layer.norm_eps), layer.rope_theta)
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    out = dot_product_attention(
        q, k, v, causal=True, impl=_attention_impl(layer, last_only)
    )
    return out.transpose(0, 2, 1, 3).reshape(
        bsz, h_q.shape[1], layer.num_heads * dh
    ) @ p["wo"]


def _swiglu(p, h, prefix: str = ""):
    w1, w3, w2 = (p[prefix + name] for name in ("w1", "w3", "w2"))
    return (jax.nn.silu(h @ w1) * (h @ w3)) @ w2


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature: ``0.1 * mscale * ln(factor) + 1`` past a
    factor of 1."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(layer: LatentBlock) -> np.ndarray:
    """The ``qk_rope_head_dim / 2`` rotary frequencies (arXiv:2309.00071 as
    DeepSeek-V2 applies it): ``theta**(-2i/D)`` where pair ``i`` turns more
    than ``beta_fast`` times within the original context, that over
    ``factor`` where it turns fewer than ``beta_slow`` times, and a linear
    ramp over the pair index between the two correction dims."""
    dim = layer.qk_rope_head_dim
    extra = layer.rope_theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if layer.rope_factor <= 1.0:
        return extra.astype(np.float32)

    def correction_dim(rotations: float) -> float:
        return (
            dim * math.log(layer.rope_original_max / (rotations * 2 * math.pi))
        ) / (2 * math.log(layer.rope_theta))

    low = max(math.floor(correction_dim(layer.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(layer.rope_beta_slow)), dim - 1)
    ramp = np.clip(
        (np.arange(dim // 2, dtype=np.float64) - low) / max(high - low, 0.001), 0, 1
    )
    return (extra / layer.rope_factor * ramp + extra * (1 - ramp)).astype(np.float32)


def _latent_attention(layer: LatentBlock, p, h, last_only: bool = False):
    """Multi-head latent attention (DeepSeek-V2 section 2.1) on (B, T, d):
    ``c_q = RMSNorm(h W_dq)``; ``[q_nope | q_rope] = c_q W_uq`` a head;
    ``[c_kv | k_r] = h W_dkv``, ``c_kv = RMSNorm(c_kv)``; ``[k_nope | v] =
    c_kv W_ukv`` a head; ``q = [q_nope | RoPE(q_rope)]``, ``k = [k_nope |
    RoPE(k_r)]`` with the one rotary key head shared by every head; causal
    ``softmax(q k^T s) v`` with ``s = (nope + rope)^-1/2 m^2``, ``m`` YaRN's
    ``0.1 mscale_all_dim ln(factor) + 1``; out ``concat(o) W_o``. No bias.
    RoPE's cos and sin are scaled by ``mscale / mscale_all_dim``'s ratio of
    temperatures (1 in the published config). With ``last_only`` the query
    side (``W_dq``, ``W_uq``, the scores, ``W_o``) is the last position's
    alone, its RoPE turned by ``T - 1``, and the keys and values every
    position's: (B, 1, d) out."""
    bsz, t, _ = h.shape
    heads, rank_kv = layer.num_heads, layer.kv_lora_rank
    nope, rope, dv = layer.qk_nope_head_dim, layer.qk_rope_head_dim, layer.v_head_dim
    h_q = h[:, -1:] if last_only else h
    t_q = h_q.shape[1]

    def split_heads(a):
        return a.reshape(bsz, a.shape[1], heads, -1).transpose(0, 2, 1, 3)

    with jax.named_scope("mla_down"):
        c_q = _rms(h_q @ p["w_dq"], p["q_norm"], layer.norm_eps)
        down = h @ p["w_dkv"]
        c_kv = _rms(down[..., :rank_kv], p["kv_norm"], layer.norm_eps)
        k_rope = down[..., rank_kv:]
    with jax.named_scope("mla_up"):
        q = split_heads(c_q @ p["w_uq"])
        kv = split_heads(c_kv @ p["w_ukv"])
        inv_freq = jnp.asarray(yarn_inv_freq(layer))
        factor = layer.rope_factor
        turn = yarn_mscale(factor, layer.rope_mscale) / yarn_mscale(
            factor, layer.rope_mscale_all_dim
        )

        def rotary(a, first=0):
            a = _rope(a, layer.rope_theta, inv_freq, first)
            return a if turn == 1.0 else (a * turn).astype(a.dtype)

        q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:], t - t_q)], axis=-1)
        k_rope = jnp.broadcast_to(
            rotary(k_rope[:, None]), (bsz, heads, t, rope)
        )
        k = jnp.concatenate([kv[..., :nope], k_rope], axis=-1)
        v = kv[..., nope:]
    scale = (nope + rope) ** -0.5 * yarn_mscale(factor, layer.rope_mscale_all_dim) ** 2
    with jax.named_scope("attention"):
        out = dot_product_attention(
            q, k, v, causal=True, impl=_attention_impl(layer, last_only), scale=scale
        )
    with jax.named_scope("mla_out"):
        return out.transpose(0, 2, 1, 3).reshape(bsz, t_q, heads * dv) @ p["wo"]


def hc_coefficients(layer: LatentBlock, p, prefix: str, x):
    """The mixing coefficients of one sublayer (arXiv:2512.24880 section 3;
    its leaves are ``p[prefix + ...]``) from the streams ``x``: (n, B, T, d),
    in float32. With ``x~ =
    RMSNorm(vec(X))`` over a token's ``n x d`` values (no gain):
    ``H_pre = sigmoid(a_pre x~ phi_pre + b_pre)`` (n), ``H_post = 2
    sigmoid(a_post x~ phi_post + b_post)`` (n), ``H_res =
    Sinkhorn(exp(clip(a_res mat(x~ phi_res) + b_res, -c, c)))`` (n x n):
    ``sinkhorn_iters`` times, every row then every column divided by its sum
    plus ``hc_eps``. Returns ``(H_pre (n, B, T), H_post (n, B, T), H_res (n,
    n, B, T), gap)``; ``gap`` is the mean over tokens of the largest |row or
    column sum - 1| of ``H_res``. The coefficients sit with the tokens on the
    minor axes, so a row's or a column's sum is an add of n slabs."""
    n = layer.streams
    phi, alpha = p[prefix + "phi"], p[prefix + "alpha"]
    with jax.named_scope("hc_coeff"):
        x32 = x.astype(jnp.float32)
        ms = jnp.sum(x32 * x32, axis=(0, -1)) / (n * x.shape[-1])
        # the norm has no gain, so it is one factor a token behind the
        # product (vec(X) phi)^T; by dot_general, not einsum, which names a
        # scope of its own (the innermost scope is what a trace is reduced by)
        raw = jax.lax.dot_general(
            phi, x32, (((0, 1), (0, 3)), ((), ()))
        ) * jax.lax.rsqrt(ms + layer.norm_eps)

        def logits(part, a, name):
            b = p[prefix + name]
            return a * part.reshape(b.shape + part.shape[1:]) + b[..., None, None]

        pre = jax.nn.sigmoid(logits(raw[:n], alpha[0], "b_pre"))
        post = 2.0 * jax.nn.sigmoid(logits(raw[n : 2 * n], alpha[1], "b_post"))
        res = logits(raw[2 * n :], alpha[2], "b_res")
        res = jnp.exp(jnp.clip(res, -layer.hc_clamp, layer.hc_clamp))
        for _ in range(layer.sinkhorn_iters):
            res = res / (jnp.sum(res, axis=1, keepdims=True) + layer.hc_eps)
            res = res / (jnp.sum(res, axis=0, keepdims=True) + layer.hc_eps)
        off = jnp.maximum(
            jnp.max(jnp.abs(jnp.sum(res, axis=1) - 1.0), axis=0),
            jnp.max(jnp.abs(jnp.sum(res, axis=0) - 1.0), axis=0),
        )
        return pre, post, res, jax.lax.stop_gradient(jnp.mean(off))


@jax.named_scope("hc_mix")
def _hc_read(pre, x):
    """``H_pre X``: the sublayer's input, (B, T, d)."""
    mixed = sum(pre[j][..., None] * x[j].astype(jnp.float32) for j in range(x.shape[0]))
    return mixed.astype(x.dtype)


def _hc_write_kernel_ok(x) -> bool:
    """Whether the write of streams ``x`` (n, B, T, d) takes the Pallas
    kernel pair (:mod:`gordo_tpu.ops.pallas_kernels.hc_write`): on a TPU,
    with ``d`` whole 128-lane vectors and ``B * T`` whole token tiles. The
    shapes decide, as ``ops/attention.py _flash_ok`` does for attention; the
    last block's write at the last position alone (B tokens) keeps the jnp
    body. Pallas is imported here, on a TPU, and not with this module."""
    if jax.default_backend() != "tpu":
        return False
    from gordo_tpu.ops.pallas_kernels.hc_write import tiles

    _, b, t, d = x.shape
    return tiles(b * t, d)


@jax.named_scope("hc_mix")
def _hc_write(res, post, x, y, kernel: bool = False):
    """``H_res X + H_post^T y``: the streams after the sublayer. With
    ``kernel`` (:func:`_hc_write_kernel_ok`) one pass of the Pallas kernel
    pair; else the jnp body below, which is the kernel's reference."""
    if kernel:
        from gordo_tpu.ops.pallas_kernels.hc_write import hc_write

        return hc_write(res, post, x, y)
    n = x.shape[0]
    x32, y32 = x.astype(jnp.float32), y.astype(jnp.float32)
    rows = [
        sum(res[i, j][..., None] * x32[j] for j in range(n)) + post[i][..., None] * y32
        for i in range(n)
    ]
    return jnp.stack(rows).astype(x.dtype)


# ---- grouped products over the experts held. Rows are sorted by group; group
# g owns the next group_sizes[g] rows; rows past the live count (the sum)
# belong to no group and give zeros, in the product and in its gradients.
# jax.lax.ragged_dot is one kernel a product on the TPU, which takes no batch
# dimension: under vmap (the fleet's machine axis, the server's model axis)
# the products run one lane after another, and the gradients are written out
# so that differentiation never meets the loop.
_ROWS_BY_ROWS = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=[0],
    rhs_group_dimensions=[],
)


def _live_rows(rows, group_sizes, n_rows: int):
    live = jnp.arange(n_rows) < jnp.sum(group_sizes)
    return jnp.where(live[:, None], rows, jnp.zeros((), rows.dtype))


@sequential_vmap
def _grouped_rows(x, w, group_sizes):
    """(R, K) rows by (G, K, N) weights → (R, N)."""
    out = jax.lax.ragged_dot(x, w, group_sizes, preferred_element_type=jnp.float32)
    return _live_rows(out, group_sizes, x.shape[0]).astype(x.dtype)


@sequential_vmap
def _grouped_weights(x, dy, group_sizes):
    """(R, K) rows and (R, N) rows, contracted group by group → (G, K, N)."""
    return jax.lax.ragged_dot_general(
        x, dy, group_sizes, _ROWS_BY_ROWS, preferred_element_type=jnp.float32
    )


@jax.custom_vjp
def grouped_matmul(x, w, group_sizes):
    return _grouped_rows(x, w, group_sizes)


def _grouped_matmul_fwd(x, w, group_sizes):
    return _grouped_rows(x, w, group_sizes), (x, w, group_sizes)


def _grouped_matmul_bwd(residuals, dy):
    x, w, group_sizes = residuals
    # the weights transposed by a copy: handed the contraction over the
    # weights' last axis instead, the TPU compiler leaves its grouped kernel
    # for a masked dense product over every group (compiled and read, PR 35)
    dx = _grouped_rows(dy, jnp.swapaxes(w, 1, 2), group_sizes)
    dw = _grouped_weights(x, dy, group_sizes).astype(w.dtype)
    return dx, dw, None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


@jax.custom_vjp
def _permute_rows(x, perm, inverse):
    """``x[perm]`` for a permutation whose inverse is known: the gradient is
    a gather too (``g[inverse]``), where autodiff would scatter."""
    return x[perm]


_permute_rows.defvjp(
    lambda x, perm, inverse: (x[perm], (perm, inverse)),
    lambda res, g: (g[res[1]], None, None),
)


def _stays_float32(leaf: str) -> bool:
    """A block's leaves that are not cast to a bfloat16 compute dtype: a
    router's (routing is a decision), the streams' mixing coefficients', and
    the scan's and differential attention's scalars a channel (``A_log``,
    ``D``, Δ's bias, the λ vectors)."""
    return (
        leaf in ("router", "expert_bias", "a_log", "d_skip", "dt_bias")
        or leaf.startswith("hc_")
        or leaf in _DIFF_LAMBDAS
    )


MOE_STATS = ("moe_held", "moe_absent", "moe_tokens", "moe_layer_steps", "moe_peak_load")


def zero_stats(spec: ModelSpec) -> Dict[str, jnp.ndarray]:
    """What :func:`apply_model_stats` counts for ``spec``, at zero: the sums'
    start: int32 counts of the routed layers, and of a latent block's mixing
    its sublayer-steps (int32), their summed stochastic gap (float32) and the
    writes that took the Pallas kernel pair (int32); a SambaY spec's Mamba
    layer-steps and the positions they scanned, and its reads of another
    block's keys and values or memory (int32). Empty for a spec with none of
    them."""
    stats = {}
    for layer in spec.layers:
        if isinstance(layer, SambaYBlock):
            keys = {
                "mamba": ("ssm_layer_steps", "ssm_positions"),
                "cross_attention": ("shared_kv_reads",),
                "gmu": ("shared_memory_reads",),
            }.get(layer.mixer, ())
            stats.update({key: jnp.zeros((), jnp.int32) for key in keys})
        if isinstance(layer, (HybridBlock, LatentBlock)) and layer.ffn == "routed":
            stats.update({key: jnp.zeros((), jnp.int32) for key in MOE_STATS})
        if isinstance(layer, LatentBlock):
            stats["hc_sublayer_steps"] = jnp.zeros((), jnp.int32)
            stats["hc_stochastic_gap"] = jnp.zeros((), jnp.float32)
            stats["hc_write_kernel_steps"] = jnp.zeros((), jnp.int32)
    if _last_only_block(spec) is not None:
        stats["last_position_steps"] = jnp.zeros((), jnp.int32)
    return stats


def routed_ffn(layer, p, h, scale: float = 1.0, gate_eps: float = 1e-6):
    """The routed FFN on (N, D) tokens: what the experts held here give.
    ``layer`` (a hybrid or a latent block) says which: ``top_k`` of the
    router's outputs by ``sigmoid(h W_r)`` plus the selection bias, weights
    the scores at the selection over (their sum + ``gate_eps``) times
    ``scale``, experts ``expert_offset`` … ``+ experts_held`` computed.
    Returns ``(out, stats)``; ``stats`` counts, for this layer and step, the
    assignments to held and to absent experts, the tokens, and the fullest
    held expert's assignments."""
    n, d = h.shape
    k, held_n = layer.top_k, layer.experts_held
    with jax.named_scope("moe_router"):
        # float32 in and out: routing is a decision, not an activation
        scores = jax.nn.sigmoid(h.astype(jnp.float32) @ p["router"])
        _, chosen = jax.lax.top_k(
            jax.lax.stop_gradient(scores) + p["expert_bias"], k
        )
        gate = jnp.take_along_axis(scores, chosen, axis=-1)
        gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + gate_eps)
        if scale != 1.0:
            gate = gate * scale
    with jax.named_scope("moe_dispatch"):
        local = chosen.reshape(-1) - layer.expert_offset  # (N * k,)
        # an assignment's group: its held expert, or one past them if absent
        group = jnp.where((local >= 0) & (local < held_n), local, held_n)
        # held assignments first, by expert; the absent ones after them
        order = jnp.argsort(group, stable=True)
        inverse = jnp.argsort(order)
        group_sizes = jnp.sum(jax.nn.one_hot(group, held_n, dtype=jnp.int32), axis=0)
        rows = _permute_rows(jnp.repeat(h, k, axis=0), order, inverse)
    with jax.named_scope("moe_experts"):
        mid = jax.nn.silu(grouped_matmul(rows, p["w1"], group_sizes))
        mid = mid * grouped_matmul(rows, p["w3"], group_sizes)
        rows = grouped_matmul(mid, p["w2"], group_sizes)
    with jax.named_scope("moe_dispatch"):
        back = _permute_rows(rows, inverse, order).reshape(n, k, d)
        out = jnp.sum(back * gate[..., None].astype(back.dtype), axis=1)
    n_held = jnp.sum(group_sizes)
    counted = (n_held, n * k - n_held, n, 1, jnp.max(group_sizes))
    return out, {
        key: jnp.asarray(value, jnp.int32) for key, value in zip(MOE_STATS, counted)
    }


def _apply_hybrid_block(layer: HybridBlock, p, x, last_only: bool = False):
    """x: (batch, time, d_model) → ``(x, stats)``; ``stats`` is empty for a
    dense FFN. With ``last_only`` → (batch, 1, d_model), the last position's
    state alone (:func:`_last_only_block`): a convolution reads the last
    ``conv_kernel`` positions, attention every position's keys and values,
    and the FFN sees the last position's tokens."""
    if layer.operator == "conv":
        # the last row of the causal convolution over the last kw rows is the
        # whole sequence's last row: it reads no row before them
        src = x[:, -layer.conv_kernel :] if last_only else x
        y = _gated_conv(layer, p, _rms_norm(src, p["op_norm"], layer.norm_eps))
        y = y[:, -1:] if last_only else y
    else:
        y = _gqa_attention(layer, p, _rms_norm(x, p["op_norm"], layer.norm_eps), last_only)
    x = (x[:, -1:] if last_only else x) + y
    h = _rms_norm(x, p["ffn_norm"], layer.norm_eps)
    if layer.ffn == "dense":
        return x + _swiglu(p, h), {}
    b, t, d = h.shape
    ffn, stats = routed_ffn(layer, p, h.reshape(b * t, d))
    return x + ffn.reshape(b, t, d), stats


# a jit of its own inside the caller's: layers of one spec and one shape (the
# routed layers after the leading dense ones) are traced, differentiated and
# batched once and not once each, and XLA inlines the calls again: a model of
# 1 + 4 layers traces and lowers in 12 s where it took 25 (the chunk
# program of xing4_0_29b_a4b on a sandbox's CPU, PERF.md section 6, PR 37)
@functools.partial(jax.jit, static_argnums=(0, 3))
def _apply_latent_block(layer: LatentBlock, p, x, last_only: bool = False):
    """x: (streams, batch, time, d_model) → ``(x, stats)``. For each of the
    two sublayers ``F`` (latent attention, then the FFN): ``X <- H_res X +
    H_post^T F(RMSNorm(H_pre X))`` (:func:`hc_coefficients`). The routed FFN
    is ``Shared(h) + sum over picked and held experts of w_e Expert_e(h)``:
    the shared expert is computed for every token on every share of the
    layer, the routed part as :func:`routed_ffn` gives it. With
    ``last_only`` → (streams, batch, 1, d_model), the last position's state
    alone (:func:`_last_only_block`): the attention sublayer's coefficients,
    ``H_pre X`` and its keys and values at every position, its queries and
    its write at the last; the FFN sublayer wholly at the last position."""

    def ffn(h):
        b, t, d = h.shape
        if layer.ffn == "dense":
            return _swiglu(p, h), {}
        # the family's gate adds 1e-20 to the picked scores' sum, not 1e-6
        out, stats = routed_ffn(
            layer, p, h.reshape(b * t, d), scale=layer.routed_scale, gate_eps=1e-20
        )
        out = out.reshape(b, t, d)
        if layer.shared_experts:
            with jax.named_scope("moe_shared"):
                out = out + _swiglu(p, h, "shared_")
        return out, stats

    sublayers = (
        ("hc_op_", "op_norm", lambda h: (_latent_attention(layer, p, h, last_only), {})),
        ("hc_ffn_", "ffn_norm", ffn),
    )
    stats = {"hc_sublayer_steps": jnp.asarray(len(sublayers), jnp.int32)}
    gaps = jnp.zeros((), jnp.float32)
    kernel_writes = 0
    for mixing, norm, sublayer in sublayers:
        pre, post, res, gap = hc_coefficients(layer, p, mixing, x)
        y, counted = sublayer(_rms_norm(_hc_read(pre, x), p[norm], layer.norm_eps))
        if last_only:
            post, res, x = post[..., -1:], res[..., -1:], x[:, :, -1:]
        kernel = _hc_write_kernel_ok(x)
        x = _hc_write(res, post, x, y, kernel)
        kernel_writes += kernel
        gaps = gaps + gap
        stats.update(counted)
    stats["hc_stochastic_gap"] = gaps
    stats["hc_write_kernel_steps"] = jnp.asarray(kernel_writes, jnp.int32)
    return x, stats


# positions a chunk of the selective scan (PERF.md section 6)
SCAN_CHUNK = 2


@jax.named_scope("ssm_scan")
def selective_scan(u, delta, a, b, c, d_skip, chunk: int = None):
    """The selective state-space scan of Mamba-1 over (B, T, C) channels of
    ``d_state`` states each: ``h_t = exp(delta_t a) * h_{t-1} + (delta_t u_t)
    b_t`` and ``y_t = h_t c_t + d_skip u_t``, from ``h_0 = 0``. ``u``,
    ``delta``: (B, T, C); ``a``: (C, S); ``b``, ``c``: (B, T, S); ``d_skip``:
    (C,). Returns ``y`` in ``u``'s dtype.

    Chunked: a ``lax.scan`` over chunks of ``chunk`` positions
    (:data:`SCAN_CHUNK` unless given) carries the state in float32, and inside
    a chunk an associative scan runs over the pairs ``(exp(delta a), delta u
    b)``. The chunk's body is recomputed in the backward pass
    (``jax.checkpoint``), so what is kept is a state a chunk, never one a
    position. The state is held (B, S, C), the channels minor: on a TPU the
    minor axis fills the 128 lanes, which ``d_state`` would fill an eighth
    of. T need not be a multiple of ``chunk``: the tail is padded with
    ``delta = 0`` and ``u = 0``, a step that leaves the state as it is."""
    bsz, t, channels = u.shape
    q = min(chunk or SCAN_CHUNK, t)
    n = -(-t // q)
    f32 = jnp.float32
    a_t = a.astype(f32).T  # (S, C)

    def chunks(x):
        # (B, T, ...) -> (n, B, q, ...), the tail padded with zeros
        x = jnp.pad(x.astype(f32), ((0, 0), (0, n * q - t)) + ((0, 0),) * (x.ndim - 2))
        return jnp.moveaxis(x.reshape((bsz, n, q) + x.shape[2:]), 1, 0)

    def combine(first, then):
        return first[0] * then[0], then[0] * first[1] + then[1]

    @jax.checkpoint
    def one_chunk(h, xs):
        dt, dtu, bq, cq = xs  # (B, q, C), (B, q, C), (B, q, S), (B, q, S)
        decay = jnp.exp(dt[:, :, None, :] * a_t)
        drive = dtu[:, :, None, :] * bq[..., None]
        decay, drive = jax.lax.associative_scan(combine, (decay, drive), axis=1)
        states = decay * h[:, None] + drive
        # an elementwise product and a sum over the states: float32 throughout
        return states[:, -1], jnp.sum(states * cq[..., None], axis=2)

    delta = delta.astype(f32)
    xs = (chunks(delta), chunks(delta * u.astype(f32)), chunks(b), chunks(c))
    _, ys = jax.lax.scan(one_chunk, jnp.zeros((bsz, a.shape[-1], channels), f32), xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(bsz, n * q, channels)[:, :t]
    return (y + u.astype(f32) * d_skip.astype(f32)).astype(u.dtype)


def _mamba_mixer(layer: SambaYBlock, p, h):
    """Mamba-1 on (B, T, d): ``[u, z] = h W_in``; ``u = SiLU(conv(u) +
    b_conv)`` (:func:`_causal_depthwise_conv` of ``d_conv`` taps); ``[δ, B, C]
    = u W_x``; ``Δ = softplus(δ W_dt + b_dt)`` in float32; ``A = -exp(A_log)``;
    ``y`` the :func:`selective_scan`; ``m = y * SiLU(z)``. Returns ``(m W_out,
    m)``: the mixer's output and the gated scan output, the memory a later
    gated memory unit reads."""
    r, ds = layer.dt_rank, layer.d_state
    with jax.named_scope("ssm_in"):
        u, z = jnp.split(h @ p["w_in"], 2, axis=-1)
        u = jax.nn.silu(_causal_depthwise_conv(u, p["conv_taps"]) + p["conv_bias"])
        dbc = u @ p["w_x"]
        delta = (dbc[..., :r] @ p["w_dt"]).astype(jnp.float32) + p["dt_bias"]
        delta = jax.nn.softplus(delta)
        a = -jnp.exp(p["a_log"])
    y = selective_scan(u, delta, a, dbc[..., r : r + ds], dbc[..., r + ds :], p["d_skip"])
    with jax.named_scope("ssm_out"):
        m = y * jax.nn.silu(z)
        return m @ p["w_out"], m


def diff_lambda(layer: SambaYBlock, p):
    """``(λ, λ_init)`` of a differential attention layer, float32:
    ``λ = exp(λ_q1 · λ_k1) - exp(λ_q2 · λ_k2) + λ_init`` and ``λ_init = 0.8 -
    0.6 exp(-0.3 layer_index)`` at the published depth."""
    init = 0.8 - 0.6 * math.exp(-0.3 * layer.layer_index)
    q1, k1, q2, k2 = (p[name].astype(jnp.float32) for name in _DIFF_LAMBDAS)
    return jnp.exp(jnp.sum(q1 * k1)) - jnp.exp(jnp.sum(q2 * k2)) + init, init


def _diff_attention(layer: SambaYBlock, p, h, kv=None, last_only: bool = False):
    """Differential attention (arXiv:2410.05258) on (B, T, d), no positional
    encoding: the ``num_heads`` query heads of ``head_dim`` taken in pairs
    ``(q1, q2)``, the ``num_kv_heads`` key heads in pairs ``(k1, k2)``, each key
    pair serving ``num_heads / num_kv_heads`` consecutive query pairs, the
    values as ``num_kv_heads / 2`` heads of ``2 head_dim``. ``A_i =
    softmax(q_i k_i^T / sqrt(head_dim) + mask)``, ``o = A_1 v - λ A_2 v``
    (:func:`diff_lambda`), ``o <- RMSNorm(o) (1 - λ_init)`` a pair, then
    ``concat(o) W_o``. The mask is causal, and a band of ``window`` rows where
    ``window`` > 0. ``kv`` is an earlier block's ``(k, v)``: then the block
    projects queries alone. With ``last_only`` the queries are the last
    position's, against every position's keys and values: (B, 1, d) out.
    Returns ``(out, (k, v))``.

    The maps take the XLA path: the values are twice the queries' width, and
    the flash kernel a slice of the values at a time, four calls a layer,
    took 11.95 ms forward + backward for 20 query pairs and 16 windows of 256
    rows in bfloat16 against the XLA path's 6.86 ms (chip run, PERF.md
    section 6)."""
    bsz, t, _ = h.shape
    dh, pairs = layer.head_dim, layer.num_heads // 2
    h_q = h[:, -1:] if last_only and kv is None else h
    tq = h_q.shape[1]
    q = (h_q @ p["wq"]).reshape(bsz, tq, pairs, 2, dh)
    if kv is None:
        kv_pairs = layer.num_kv_heads // 2
        k = (h @ p["wk"]).reshape(bsz, t, kv_pairs, 2, dh)
        v = (h @ p["wv"]).reshape(bsz, t, kv_pairs, 2 * dh)
    else:
        k, v = kv
    with jax.named_scope("diff_attention"):
        rep = pairs // k.shape[2]

        def heads(a):
            # (B, T, pairs, width) -> (B, pairs, T, width)
            return jnp.repeat(a, rep, axis=2).transpose(0, 2, 1, 3)

        vh = heads(v)
        window = layer.window if layer.window > 0 else None
        maps = [
            dot_product_attention_xla(
                q[..., i, :].transpose(0, 2, 1, 3), heads(k[..., i, :]), vh,
                causal=True, window=window,
            ).astype(jnp.float32)
            for i in (0, 1)
        ]
        lam, init = diff_lambda(layer, p)
        o = _rms(maps[0] - lam * maps[1], p["subln"], 1e-5) * (1.0 - init)
        o = o.transpose(0, 2, 1, 3).reshape(bsz, tq, pairs * 2 * dh).astype(h.dtype)
    return o @ p["wo"], (k, v)


@jax.named_scope("gmu")
def _gmu(p, h, memory):
    """The gated memory unit: ``(SiLU(h W_1) * m) W_2`` on (B, T, d), ``m``
    an earlier Mamba layer's gated scan output at the same positions."""
    return (jax.nn.silu(h @ p["w_gate"]) * memory) @ p["w_gmu_out"]


def _ln(x, scale, bias, eps: float):
    """LayerNorm over the last axis computed in float32 whatever the compute
    dtype."""
    out = _layer_norm(
        x.astype(jnp.float32), scale.astype(jnp.float32), bias.astype(jnp.float32), eps
    )
    return out.astype(x.dtype)


def _apply_sambay_block(layer: SambaYBlock, p, x, shared, last_only: bool = False):
    """x: (batch, time, d_model) → ``(x, kept, stats)``: ``x + mixer(LN(x))``,
    then ``x + SwiGLU(LN(x))``. ``shared`` holds what earlier blocks kept
    (``memory``, ``kv``); ``kept`` is what this one keeps for later blocks;
    ``stats`` counts a Mamba layer's step and positions, and a read of
    another block's state. With ``last_only`` → (batch, 1, d_model), the last
    position's state alone (:func:`_last_only_block`): a Mamba layer scans
    every position and keeps the last, differential attention takes every
    position's keys and values and the last position's queries, and a gated
    memory unit, cross-attention and the MLP see the last position alone."""
    eps = layer.norm_eps
    position_wise = last_only and bool(layer.reads)
    src = x[:, -1:] if position_wise else x
    h = _ln(src, p["ln1_scale"], p["ln1_bias"], eps)
    kept, stats = {}, {}
    if layer.mixer == "mamba":
        y, memory = _mamba_mixer(layer, p, h)
        stats["ssm_layer_steps"] = jnp.asarray(1, jnp.int32)
        stats["ssm_positions"] = jnp.asarray(h.shape[0] * h.shape[1], jnp.int32)
        if layer.keeps == "memory":
            kept["memory"] = memory
    elif layer.mixer == "attention":
        y, kv = _diff_attention(layer, p, h, last_only=last_only)
        if layer.keeps == "kv":
            kept["kv"] = kv
    elif layer.mixer == "cross_attention":
        y, _ = _diff_attention(layer, p, h, kv=shared["kv"], last_only=last_only)
        stats["shared_kv_reads"] = jnp.asarray(1, jnp.int32)
    else:
        memory = shared["memory"]
        y = _gmu(p, h, memory[:, -1:] if position_wise else memory)
        stats["shared_memory_reads"] = jnp.asarray(1, jnp.int32)
    if last_only and not position_wise:
        x, y = x[:, -1:], y[:, -1:]
    x = src + y if position_wise else x + y
    return x + _swiglu(p, _ln(x, p["ln2_scale"], p["ln2_bias"], eps)), kept, stats


def _apply_stream_layer(layer: StreamLayer, x):
    if layer.mode == "expand":
        return jnp.broadcast_to(x[None], (layer.streams,) + x.shape)
    if layer.mode == "collapse":
        return jnp.sum(x.astype(jnp.float32), axis=0).astype(x.dtype)
    raise ValueError(f"Unknown stream mode {layer.mode!r}")


def _causal_conv1d(x, kernel, dilation: int):
    """Causal dilated conv. x: (..., time, c_in), kernel: (width, c_in, c_out).

    Implemented as ONE clean 2-D matmul of the raw series against all
    ``width`` taps' kernels, followed by a fused shifted-add of the tap
    outputs — rather than ``lax.conv_general_dilated`` (XLA CPU's dilated
    NWC conv path was measured ~38x slower; no fast kernel) and rather
    than ``width`` matmuls over PADDED/SHIFTED inputs (the earlier form):
    XLA fuses pads/slices into dot operands, which knocks the dot off the
    GEMM library fast path on CPU (measured 3x slower GEMM) and forces
    awkward MXU tiling on TPU. Here the dot's lhs is a contiguous reshape
    of ``x`` itself — nothing fuses into it — and the causal boundary is
    handled on the OUTPUT side, where the front-zero pads fuse into the
    cheap add loop. Numerically identical to the shifted-input form:
    ``out[t] = sum_i x[t - (k-1-i)*d] @ W[i]`` (missing rows = 0).
    """
    kw, c_in, c_out = kernel.shape
    t = x.shape[-2]
    lead = x.shape[:-1]
    # (width, c_in, c_out) -> (c_in, width*c_out): tap-major columns
    z = x.reshape(-1, c_in) @ kernel.transpose(1, 0, 2).reshape(
        c_in, kw * c_out
    )
    z = z.reshape(*lead, kw, c_out)
    pad_spec = [(0, 0)] * (x.ndim - 2)
    out = None
    for i in range(kw):  # kw is a small static width: unrolled taps
        off = (kw - 1 - i) * dilation
        if off >= t:
            # the tap's whole output precedes the sequence start: all zero
            # (can happen on short predict windows); the last tap always
            # has off == 0, so `out` is never left unset
            continue
        zi = z[..., : t - off, i, :]
        zi = jnp.pad(zi, (*pad_spec, (off, 0), (0, 0)))
        out = zi if out is None else out + zi
    return out


def _apply_tcn_block(layer: TCNBlock, p, x):
    act = _activation(layer.activation)
    h = act(_causal_conv1d(x, p["conv1_kernel"], layer.dilation) + p["conv1_bias"])
    h = act(_causal_conv1d(h, p["conv2_kernel"], layer.dilation) + p["conv2_bias"])
    res = x if "res_kernel" not in p else _causal_conv1d(x, p["res_kernel"], 1)
    return act(h + res)


def _apply_pool(layer: PoolLayer, x):
    if layer.mode == "last":
        return x[:, -1, :]
    if layer.mode == "mean":
        return jnp.mean(x, axis=1)
    if layer.mode == "max":
        return jnp.max(x, axis=1)
    raise ValueError(f"Unknown pool mode {layer.mode!r}")


def _last_only_block(spec: ModelSpec):
    """The index of the layer that runs ``last_only``, or None: the spec's
    last hybrid, latent or SambaY block, where every layer after it up to a
    ``PoolLayer(mode="last")`` is position-wise (``RMSNormLayer``,
    ``LayerNormLayer``, ``StreamLayer(mode="collapse")``). Nothing reads that
    block's other positions: no later block asks for their keys and values
    or their memory, and the pool keeps one. (A SambaY cross-attention
    block's keys and values are an earlier block's, which runs whole.) A
    ``DenseLayer`` in between does not qualify (its ``l1_activity`` sums over
    positions), nor a mean or max pool. ``TransformerBlock``, ``MoEBlock``,
    ``TCNBlock`` and ``LSTMLayer`` specs keep every position: no benchmark
    cell runs them, and the pipeline-parallel path consumes
    ``TransformerBlock``s whole."""
    blocks = [
        i for i, layer in enumerate(spec.layers)
        if isinstance(layer, (HybridBlock, LatentBlock, SambaYBlock))
    ]
    if not blocks:
        return None
    for layer in spec.layers[blocks[-1] + 1 :]:
        if isinstance(layer, PoolLayer):
            return blocks[-1] if layer.mode == "last" else None
        position_wise = isinstance(layer, (RMSNormLayer, LayerNormLayer)) or (
            isinstance(layer, StreamLayer) and layer.mode == "collapse"
        )
        if not position_wise:
            return None
    return None


def apply_model(spec: ModelSpec, params: Params, x: jnp.ndarray):
    """
    Forward pass.

    Returns ``(output, activity_penalty)`` where the penalty is the summed l1
    activity regularization (reference parity:
    factories/feedforward_autoencoder.py:78-85 — l1(1e-4) on non-first encoder
    layers), normalized by batch size to keep loss scale batch-invariant.
    """
    out, penalty, _ = apply_model_stats(spec, params, x)
    return out, penalty


def apply_model_stats(spec: ModelSpec, params: Params, x: jnp.ndarray):
    """:func:`apply_model` and, third, what the layers counted on the way
    (:func:`zero_stats` names them), empty for a spec that counts nothing.
    A SambaY block's keys and values or memory go from the block that keeps
    them to the later blocks that read them (:func:`_apply_sambay_block`)."""
    compute_dtype = jnp.dtype(getattr(spec, "compute_dtype", "float32"))
    batch = x.shape[0]
    out = x
    if out.dtype != compute_dtype:
        out = out.astype(compute_dtype)
    if compute_dtype != jnp.float32:
        # params stay float32 at rest (optimizer state, serialization);
        # cast per forward so matmuls run at the compute dtype. The MoE
        # router weights are EXEMPT: routing is a decision, not an
        # activation — quantizing the router matrix to bf16 can flip
        # argmax top-1 assignments relative to the float32 model, which
        # the router's own f32 compute (`_apply_moe_block`) cannot undo
        def _cast(a):
            return (
                a.astype(compute_dtype)
                if jnp.issubdtype(a.dtype, jnp.floating)
                else a
            )

        params = [
            {
                k: (v if _stays_float32(k)
                    and isinstance(
                        layer, (MoEBlock, HybridBlock, LatentBlock, SambaYBlock)
                    )
                    else jax.tree_util.tree_map(_cast, v))
                for k, v in p.items()
            }
            if isinstance(p, dict)
            else jax.tree_util.tree_map(_cast, p)
            for layer, p in zip(spec.layers, params)
        ]
    # remat: recompute sequence-layer activations on the backward pass
    # instead of storing them — O(layers) fewer (B, T, D) live buffers, the
    # HBM-for-FLOPs trade for long lookback windows. Dense/PE/Pool layers
    # are cheap and stay stored.
    remat = bool(getattr(spec, "remat", False))

    def _seq_layer(fn, layer, p, x):
        if remat:
            return jax.checkpoint(functools.partial(fn, layer))(p, x)
        return fn(layer, p, x)

    # pipeline parallelism: the contiguous TransformerBlock run executes as
    # one GPipe shard_map over the `pipe` mesh axis instead of this loop
    # (parallel/pipeline_parallel.py); remaining layers run replicated
    pp_blocks = (
        [i for i, l in enumerate(spec.layers) if isinstance(l, TransformerBlock)]
        if int(getattr(spec, "pipeline_parallel", 0) or 0) > 1
        else []
    )

    # fusion gate computed at the point of use: a TP spec must never run
    # the fused QKV projection over column-sharded weights, regardless of
    # where the spec came from (prepare_tp_spec pins layer.fuse_qkv=False
    # for canonical specs, but an artifact pickled before that field
    # existed would default back on — this guard makes it structural)
    tp_active = int(getattr(spec, "tensor_parallel", 0) or 0) > 1

    def _fuse(layer):
        return getattr(layer, "fuse_qkv", True) and not tp_active

    trimmed = _last_only_block(spec)
    penalty = jnp.asarray(0.0, jnp.float32)
    stats: Dict[str, jnp.ndarray] = {}
    shared: Dict[str, Any] = {}
    for i, (layer, p) in enumerate(zip(spec.layers, params)):
        if pp_blocks and i in pp_blocks:
            if i != pp_blocks[0]:
                continue  # consumed by the pipeline call below
            from gordo_tpu.parallel.pipeline_parallel import (
                apply_pipelined_blocks,
            )

            out = apply_pipelined_blocks(
                spec, layer, [params[j] for j in pp_blocks], out
            )
        elif isinstance(layer, DenseLayer):
            out = _apply_dense(layer, p, out)
            if layer.l1_activity > 0.0:
                penalty = penalty + layer.l1_activity * jnp.sum(
                    jnp.abs(out.astype(jnp.float32))
                ) / batch
        elif isinstance(layer, LSTMLayer):
            out = _seq_layer(_apply_lstm, layer, p, out)
        elif isinstance(layer, PositionalEncoding):
            out = _apply_positional_encoding(layer, out)
        elif isinstance(layer, TransformerBlock):
            out = _seq_layer(
                functools.partial(_apply_transformer_block, fuse_qkv=_fuse(layer)),
                layer, p, out,
            )
        elif isinstance(layer, MoEBlock):
            if int(getattr(spec, "expert_parallel", 0) or 0) > 1:
                from gordo_tpu.parallel.expert_parallel import apply_ep_moe_block

                ep_fn = functools.partial(
                    apply_ep_moe_block, spec, layer, return_aux=True
                )
                if remat:
                    # same remat policy as every other sequence layer —
                    # EP must not silently keep its activations live
                    ep_fn = jax.checkpoint(ep_fn)
                out, aux = ep_fn(p, out)
            else:
                out, aux = _seq_layer(
                    functools.partial(
                        _apply_moe_block, return_aux=True,
                        fuse_qkv=_fuse(layer),
                    ),
                    layer, p, out,
                )
            penalty = penalty + aux
        elif isinstance(layer, (HybridBlock, LatentBlock)):
            block = (
                _apply_hybrid_block if isinstance(layer, HybridBlock)
                else _apply_latent_block
            )
            if i == trimmed:
                block = functools.partial(block, last_only=True)
            out, counted = _seq_layer(block, layer, p, out)
            if i == trimmed:
                counted = dict(counted, last_position_steps=jnp.asarray(1, jnp.int32))
            stats.update(
                {key: stats.get(key, 0) + value for key, value in counted.items()}
            )
        elif isinstance(layer, SambaYBlock):
            if layer.reads and layer.reads not in shared:
                check_shared_state(spec.layers)
            block = functools.partial(
                _apply_sambay_block, layer, last_only=i == trimmed
            )
            if remat:
                block = jax.checkpoint(block)
            out, kept, counted = block(p, out, shared)
            shared.update(kept)
            if i == trimmed:
                counted = dict(counted, last_position_steps=jnp.asarray(1, jnp.int32))
            stats.update(
                {key: stats.get(key, 0) + value for key, value in counted.items()}
            )
        elif isinstance(layer, StreamLayer):
            out = _apply_stream_layer(layer, out)
        elif isinstance(layer, RMSNormLayer):
            out = _rms_norm(out, p["scale"], layer.eps)
        elif isinstance(layer, LayerNormLayer):
            out = _ln(out, p["scale"], p["bias"], layer.eps)
        elif isinstance(layer, TCNBlock):
            out = _seq_layer(_apply_tcn_block, layer, p, out)
        elif isinstance(layer, PoolLayer):
            out = _apply_pool(layer, out)
        else:
            raise TypeError(f"Unknown layer spec: {layer!r}")
    if out.dtype != jnp.float32:
        out = out.astype(jnp.float32)
    return out, penalty, stats
