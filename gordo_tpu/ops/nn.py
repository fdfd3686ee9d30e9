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
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
from jax.custom_batching import sequential_vmap

from gordo_tpu.models.spec import (
    DenseLayer,
    HybridBlock,
    LSTMLayer,
    ModelSpec,
    MoEBlock,
    PoolLayer,
    PositionalEncoding,
    RMSNormLayer,
    TCNBlock,
    TransformerBlock,
)
from gordo_tpu.ops.attention import dot_product_attention, multihead_attention

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


def init_hybrid_block(rng, in_dim: int, layer: HybridBlock):
    """normal(0, 0.02) matrices and taps (the LFM2 family's
    ``initializer_range``), unit gains, a zero selection bias; one key for
    the operator and one for the FFN, each split over its matrices."""
    if in_dim != layer.d_model:
        raise ValueError(
            f"HybridBlock d_model={layer.d_model} but incoming dim is "
            f"{in_dim}; insert a Dense projection first"
        )
    d, f = layer.d_model, layer.ff_dim
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
        raise ValueError(f"Unknown HybridBlock ffn {layer.ffn!r}")
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
    if isinstance(layer, (TransformerBlock, MoEBlock, HybridBlock)):
        return layer.d_model
    if isinstance(layer, TCNBlock):
        return layer.filters
    if isinstance(layer, (PositionalEncoding, PoolLayer, RMSNormLayer)):
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
        elif isinstance(layer, RMSNormLayer):
            params.append({"scale": jnp.ones((in_dim,), jnp.float32)})
        elif isinstance(layer, TCNBlock):
            params.append(init_tcn_block(layer_rng, in_dim, layer))
        elif isinstance(layer, (PositionalEncoding, PoolLayer)):
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


@jax.named_scope("rms_norm")
def _rms_norm(x, scale, eps: float):
    """``x * rsqrt(mean(x^2) + eps) * scale`` over the last axis, computed in
    float32 whatever the compute dtype."""
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta: float):
    """Rotary position embedding, rotate-half convention, on (..., T, Dh):
    pair ``i`` of the two halves turns by ``t * theta**(-2i/Dh)``."""
    t, dh = x.shape[-2], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)
    x32 = x.astype(jnp.float32)
    rotated = jnp.concatenate([-x32[..., dh // 2 :], x32[..., : dh // 2]], axis=-1)
    return (x32 * cos + rotated * sin).astype(x.dtype)


@jax.named_scope("gated_conv")
def _gated_conv(layer: HybridBlock, p, h):
    """``[b, c, u] = split(h @ W_in)``; ``y = c * conv(b * u)`` with a
    depthwise causal convolution over time, ``conv(z)[t] = sum_j taps[:, j] *
    z[t - (K-1) + j]`` and zeros before the start; out ``y @ W_out``."""
    b, c, u = jnp.split(h @ p["conv_in"], 3, axis=-1)
    z = b * u
    kw, t = layer.conv_kernel, z.shape[1]
    zp = jnp.pad(z, ((0, 0), (kw - 1, 0), (0, 0)))
    conv = sum(zp[:, j : j + t, :] * p["conv_taps"][:, j] for j in range(kw))
    return (c * conv) @ p["conv_out"]


@jax.named_scope("attention")
def _gqa_attention(layer: HybridBlock, p, h):
    """Grouped-query causal attention: RMSNorm a head on q and k, RoPE, each
    key/value head serving ``num_heads / num_kv_heads`` consecutive query
    heads; through the dispatcher of ops/attention.py."""
    bsz, t, _ = h.shape
    dh, rep = layer.head_dim, layer.num_heads // layer.num_kv_heads

    def heads(a, n):
        return a.reshape(bsz, t, n, dh).transpose(0, 2, 1, 3)

    q = heads(h @ p["wq"], layer.num_heads)
    k = heads(h @ p["wk"], layer.num_kv_heads)
    v = heads(h @ p["wv"], layer.num_kv_heads)
    q = _rope(_rms_norm(q, p["q_norm"], layer.norm_eps), layer.rope_theta)
    k = _rope(_rms_norm(k, p["k_norm"], layer.norm_eps), layer.rope_theta)
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    impl = layer.attention_impl
    out = dot_product_attention(
        q, k, v, causal=True, impl=None if impl == "auto" else impl
    )
    return out.transpose(0, 2, 1, 3).reshape(bsz, t, layer.num_heads * dh) @ p["wo"]


def _swiglu(p, h):
    return (jax.nn.silu(h @ p["w1"]) * (h @ p["w3"])) @ p["w2"]


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


MOE_STATS = ("moe_held", "moe_absent", "moe_tokens", "moe_layer_steps", "moe_peak_load")


def zero_stats(spec: ModelSpec) -> Dict[str, jnp.ndarray]:
    """What :func:`apply_model_stats` counts for ``spec``, at zero: the sums'
    start. Empty for a spec with no routed layer."""
    routed = any(
        isinstance(layer, HybridBlock) and layer.ffn == "routed"
        for layer in spec.layers
    )
    return {key: jnp.zeros((), jnp.int32) for key in MOE_STATS} if routed else {}


def routed_ffn(layer: HybridBlock, p, h):
    """The routed FFN on (N, D) tokens: what the experts held here give.
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
        gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-6)
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


def _apply_hybrid_block(layer: HybridBlock, p, x):
    """x: (batch, time, d_model) → ``(x, stats)``; ``stats`` is empty for a
    dense FFN."""
    h = _rms_norm(x, p["op_norm"], layer.norm_eps)
    operator = _gated_conv if layer.operator == "conv" else _gqa_attention
    x = x + operator(layer, p, h)
    h = _rms_norm(x, p["ffn_norm"], layer.norm_eps)
    if layer.ffn == "dense":
        return x + _swiglu(p, h), {}
    b, t, d = h.shape
    ffn, stats = routed_ffn(layer, p, h.reshape(b * t, d))
    return x + ffn.reshape(b, t, d), stats


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
    """:func:`apply_model` and, third, what the layers counted on the way: a
    dict of int32 scalars summed over the spec's routed layers
    (:func:`routed_ffn`), empty for a spec that has none."""
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
                k: (v if k in ("router", "expert_bias")
                    and isinstance(layer, (MoEBlock, HybridBlock))
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

    penalty = jnp.asarray(0.0, jnp.float32)
    stats: Dict[str, jnp.ndarray] = {}
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
        elif isinstance(layer, HybridBlock):
            out, counted = _seq_layer(_apply_hybrid_block, layer, p, out)
            stats = {
                key: stats.get(key, 0) + value for key, value in counted.items()
            }
        elif isinstance(layer, RMSNormLayer):
            out = _rms_norm(out, p["scale"], layer.eps)
        elif isinstance(layer, TCNBlock):
            out = _seq_layer(_apply_tcn_block, layer, p, out)
        elif isinstance(layer, PoolLayer):
            out = _apply_pool(layer, out)
        else:
            raise TypeError(f"Unknown layer spec: {layer!r}")
    if out.dtype != jnp.float32:
        out = out.astype(jnp.float32)
    return out, penalty, stats
