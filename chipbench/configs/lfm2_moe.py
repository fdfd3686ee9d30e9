"""Plain reference of the model kind ``hybrid_moe_model``: the layer of the LFM2
family (``model_type`` ``lfm2_moe``, https://huggingface.co/LiquidAI/LFM2-8B-A1B)
as a windowed sensor model. A dense projection of the tags to ``d_model``,
then one block a layer, a final RMSNorm, the last position, a dense head.
With ``n(x) = x * rsqrt(mean(x^2) + eps) * g``:

- block: ``x = x + operator(n_op(x))``; ``x = x + ffn(n_ffn(x))``
- gated short convolution: ``[b, c, u] = split3(h W_in)``; ``y = c * conv(b * u)``,
  ``conv(z)[t] = sum_j taps[:, j] * z[t - (K-1) + j]`` (depthwise, causal, zeros
  before the start, no bias); out ``y W_out``
- attention: ``q = h W_q``, ``k = h W_k``, ``v = h W_v`` (no biases), RMSNorm over
  each head of q and k, RoPE (rotate-half), causal softmax of ``q k^T / sqrt(Dh)``,
  a key/value head serving ``num_heads / num_kv_heads`` consecutive query heads
- dense FFN: ``W2(silu(W1 h) * W3 h)``
- routed FFN: ``s = sigmoid(h W_r)`` in float32; the ``top_k`` of ``s + bias``
  are selected; weights ``s`` at the selection over ``(their sum + 1e-6)``;
  output the weighted sum of ``W2e(silu(W1e h) * W3e h)`` over the selected
  experts that are held here (ids ``expert_offset`` … ``+ experts_held``).
  Every held expert is applied to every token and weighted by its gate, zero
  where it was not selected: no sort, no capacity, no kernel. ``bias`` is a
  leaf no gradient reaches.

Departures from the published model, each also in the configuration's file:
no token embedding and no vocabulary (sensor rows in through a dense layer
with a bias, a sensor row out through a dense head: both glorot-uniform, as
the system's own dense layers are), the router computed in float32 whatever
``mm`` rounds, and only the held experts' part of a routed layer.

Initial weights: normal(0, 0.02) for every matrix and tap of a block, unit
gains, a zero selection bias; one key a layer, split as the program splits it.
``mm`` is the matmul the caller chose (:func:`chipbench.reference.matmul`); a
layer is recomputed in the backward pass (``jax.checkpoint``), so a machine of
half a billion parameters keeps one layer's float32 activations at a time.
:func:`forward_flops_per_window` is the kind's operation count (conventions:
:mod:`chipbench.flops`)."""

import jax
import jax.numpy as jnp

from chipbench.reference import HIGHEST, dense_init


def _normal(key, shape):
    return 0.02 * jax.random.normal(key, shape, jnp.float32)


def _block_init(key, model: dict, operator: str, ffn: str) -> dict:
    d = int(model["d_model"])
    k_op, k_ffn = jax.random.split(key)
    p = {"op_norm": jnp.ones((d,)), "ffn_norm": jnp.ones((d,))}
    if operator == "conv":
        ks = jax.random.split(k_op, 3)
        p["conv_in"] = _normal(ks[0], (d, 3 * d))
        p["conv_taps"] = _normal(ks[1], (d, int(model["conv_kernel"])))
        p["conv_out"] = _normal(ks[2], (d, d))
    else:
        ks = jax.random.split(k_op, 4)
        dh = int(model["head_dim"])
        hq, hkv = int(model["num_heads"]) * dh, int(model["num_kv_heads"]) * dh
        p["wq"], p["wk"] = _normal(ks[0], (d, hq)), _normal(ks[1], (d, hkv))
        p["wv"], p["wo"] = _normal(ks[2], (d, hkv)), _normal(ks[3], (hq, d))
        p["q_norm"], p["k_norm"] = jnp.ones((dh,)), jnp.ones((dh,))
    if ffn == "dense":
        ks = jax.random.split(k_ffn, 3)
        f = int(model["ff_dim"])
        p["w1"], p["w3"] = _normal(ks[0], (d, f)), _normal(ks[1], (d, f))
        p["w2"] = _normal(ks[2], (f, d))
    else:
        ks = jax.random.split(k_ffn, 4)
        f, held = int(model["expert_dim"]), int(model["experts_held"])
        p["router"] = _normal(ks[0], (d, int(model["num_experts"])))
        p["expert_bias"] = jnp.zeros((int(model["num_experts"]),))
        p["w1"], p["w3"] = _normal(ks[1], (held, d, f)), _normal(ks[2], (held, d, f))
        p["w2"] = _normal(ks[3], (held, f, d))
    return p


def init_params(key, model: dict, n_tags: int) -> list:
    d, kinds = int(model["d_model"]), list(zip(model["operators"], model["ffns"]))
    keys = jax.random.split(key, len(kinds) + 4)  # dense, blocks, norm, pool, dense
    params = [dense_init(keys[0], n_tags, d)]
    params += [_block_init(k, model, *kind) for k, kind in zip(keys[1:], kinds)]
    params += [{"scale": jnp.ones((d,))}, {}, dense_init(keys[-1], d, n_tags)]
    return params


def _rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _rope(x, theta):
    """x: (batch, heads, time, Dh); rotate-half."""
    t, dh = x.shape[-2], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles), jnp.cos(angles)], axis=-1)
    sin = jnp.concatenate([jnp.sin(angles), jnp.sin(angles)], axis=-1)
    rotated = jnp.concatenate([-x[..., dh // 2:], x[..., : dh // 2]], axis=-1)
    return x * cos + rotated * sin


def _gated_conv(model, p, h, mm):
    b, c, u = jnp.split(mm(h, p["conv_in"]), 3, axis=-1)
    z, kw, t = b * u, int(model["conv_kernel"]), h.shape[1]
    zp = jnp.pad(z, ((0, 0), (kw - 1, 0), (0, 0)))
    conv = sum(zp[:, j : j + t, :] * p["conv_taps"][:, j] for j in range(kw))
    return mm(c * conv, p["conv_out"])


def _attention(model, p, h, mm):
    bsz, t, _ = h.shape
    dh, hq, hkv = (int(model[k]) for k in ("head_dim", "num_heads", "num_kv_heads"))
    eps, theta = float(model["norm_eps"]), float(model["rope_theta"])

    def heads(a, n):
        return a.reshape(bsz, t, n, dh).transpose(0, 2, 1, 3)

    q, k, v = heads(mm(h, p["wq"]), hq), heads(mm(h, p["wk"]), hkv), heads(mm(h, p["wv"]), hkv)
    q = _rope(_rms_norm(q, p["q_norm"], eps), theta)
    k = _rope(_rms_norm(k, p["k_norm"], eps), theta)
    k, v = jnp.repeat(k, hq // hkv, axis=1), jnp.repeat(v, hq // hkv, axis=1)
    logits = mm(q, jnp.swapaxes(k, -1, -2)) / jnp.sqrt(jnp.float32(dh))
    logits = jnp.where(jnp.tril(jnp.ones((t, t), bool)), logits, -1e30)
    out = mm(jax.nn.softmax(logits, axis=-1), v)
    return mm(out.transpose(0, 2, 1, 3).reshape(bsz, t, hq * dh), p["wo"])


def _swiglu(w1, w3, w2, h, mm):
    return mm(jax.nn.silu(mm(h, w1)) * mm(h, w3), w2)


def _routed(model, p, h, mm):
    n_experts, k = int(model["num_experts"]), int(model["top_k"])
    held, offset = int(model["experts_held"]), int(model["expert_offset"])
    scores = jax.nn.sigmoid(jnp.matmul(h, p["router"], precision=HIGHEST))
    _, chosen = jax.lax.top_k(jax.lax.stop_gradient(scores) + p["expert_bias"], k)
    weight = jnp.take_along_axis(scores, chosen, axis=-1)
    weight = weight / (weight.sum(-1, keepdims=True) + 1e-6)
    # (…, experts): an expert's gate, zero where it was not selected
    gates = (jax.nn.one_hot(chosen, n_experts) * weight[..., None]).sum(-2)
    out = jnp.zeros_like(h)
    for e in range(held):
        out = out + gates[..., offset + e, None] * _swiglu(
            p["w1"][e], p["w3"][e], p["w2"][e], h, mm
        )
    return out


def _block(model, operator, ffn, p, x, mm):
    eps = float(model["norm_eps"])
    h = _rms_norm(x, p["op_norm"], eps)
    x = x + (_gated_conv if operator == "conv" else _attention)(model, p, h, mm)
    h = _rms_norm(x, p["ffn_norm"], eps)
    if ffn == "dense":
        return x + _swiglu(p["w1"], p["w3"], p["w2"], h, mm)
    return x + _routed(model, p, h, mm)


def forward(model: dict, params: list, x, mm):
    """x: (batch, lookback, tags) → (batch, tags)."""
    x = mm(x, params[0]["kernel"]) + params[0]["bias"]
    for operator, ffn, p in zip(model["operators"], model["ffns"], params[1:-3]):
        x = jax.checkpoint(
            lambda p, x, operator=operator, ffn=ffn: _block(model, operator, ffn, p, x, mm)
        )(p, x)
    x = _rms_norm(x, params[-3]["scale"], float(model["norm_eps"]))
    return mm(x[:, -1, :], params[-1]["kernel"]) + params[-1]["bias"]


def forward_flops_per_window(config: dict, held_load=None) -> float:
    """Matrix products of one forward pass over one window. Attention's scores
    and weighted values are counted causal (half of T x T); a routed layer's
    experts at ``held_load`` assignments a token to the experts held here:
    what a run's counters read where the caller has them
    (``fleet_step_mfu_routed``), else what an even router gives the share,
    ``top_k x experts_held / num_experts`` (``fleet_step_mfu``: an upper
    reading wherever the run routes less than that to its share)."""
    model, tags = config["model"], int(config["n_tags"])
    t, d = int(model["lookback_window"]), int(model["d_model"])
    dh, hq, hkv = (int(model[k]) for k in ("head_dim", "num_heads", "num_kv_heads"))
    load = held_load
    if load is None:
        load = int(model["top_k"]) * int(model["experts_held"]) / int(model["num_experts"])
    total = 2.0 * tags * d * t  # the tag projection, every position
    for operator, ffn in zip(model["operators"], model["ffns"]):
        if operator == "conv":
            total += 2.0 * d * 3 * d * t + 2.0 * d * d * t
        else:
            total += 2.0 * d * (2 * hq + 2 * hkv) * dh * t  # q, o; k, v
            total += 0.5 * 4.0 * t * t * hq * dh
        if ffn == "dense":
            total += 3 * 2.0 * d * int(model["ff_dim"]) * t
        else:
            total += 2.0 * d * int(model["num_experts"]) * t  # the router
            total += load * 3 * 2.0 * d * int(model["expert_dim"]) * t
    return total + 2.0 * d * tags  # the head sees the last position only
