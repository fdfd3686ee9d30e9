"""Plain reference of the model kind ``transformer_model``: a dense projection to d_model,
sinusoidal positions (Vaswani et al. 2017), pre-LayerNorm causal encoder
blocks (multi-head attention + residual, feed-forward + residual), the last
position, a linear dense layer. Glorot-uniform matrices, zero biases, unit
LayerNorm scales; one key a layer, the parameter-free ones included.
``mm`` is the matmul the caller chose (:func:`chipbench.reference.matmul`).
:func:`forward_flops_per_window` is the kind's operation count (conventions:
:mod:`chipbench.flops`)."""

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import ACT, dense_init, glorot


def _block_init(key, d, ff):
    ks = jax.random.split(key, 6)
    p = {"ln1_scale": jnp.ones((d,)), "ln1_bias": jnp.zeros((d,)),
         "ln2_scale": jnp.ones((d,)), "ln2_bias": jnp.zeros((d,))}
    for name, k in zip(("wq", "wk", "wv", "wo"), ks[:4]):
        p[name] = glorot(k, (d, d))
        p["b" + name[1]] = jnp.zeros((d,))
    p["w_ff1"], p["b_ff1"] = glorot(ks[4], (d, ff)), jnp.zeros((ff,))
    p["w_ff2"], p["b_ff2"] = glorot(ks[5], (ff, d)), jnp.zeros((d,))
    return p


def init_params(key, model: dict, n_tags: int) -> list:
    d, blocks = int(model["d_model"]), int(model["num_blocks"])
    keys = jax.random.split(key, blocks + 4)  # dense, positions, blocks, pool, dense
    params = [dense_init(keys[0], n_tags, d), {}]
    params += [_block_init(keys[2 + b], d, int(model["ff_dim"])) for b in range(blocks)]
    params += [{}, dense_init(keys[-1], d, n_tags)]
    return params


def _layer_norm(x, scale, bias):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + 1e-6) * scale + bias


def _positions(t, d):
    half = (d + 1) // 2
    freqs = jnp.exp(-jnp.log(10000.0) * jnp.arange(half) / max(half - 1, 1))
    angles = jnp.arange(t)[:, None] * freqs[None, :]
    pe = jnp.zeros((t, d))
    pe = pe.at[:, 0::2].set(jnp.sin(angles)[:, : (d + 1) // 2])
    return pe.at[:, 1::2].set(jnp.cos(angles)[:, : d // 2])


def _block(p, x, heads, act, causal, mm):
    b, t, d = x.shape
    h = _layer_norm(x, p["ln1_scale"], p["ln1_bias"])

    def split(a):
        return a.reshape(b, t, heads, d // heads).transpose(0, 2, 1, 3)

    q, k, v = (split(mm(h, p[w]) + p[bias]) for w, bias in
               (("wq", "bq"), ("wk", "bk"), ("wv", "bv")))
    logits = mm(q, jnp.swapaxes(k, -1, -2)) / np.sqrt(d // heads)
    if causal:
        logits = jnp.where(jnp.tril(jnp.ones((t, t), bool)), logits, -1e30)
    attn = mm(jax.nn.softmax(logits, axis=-1), v)
    x = x + mm(attn.transpose(0, 2, 1, 3).reshape(b, t, d), p["wo"]) + p["bo"]
    h = _layer_norm(x, p["ln2_scale"], p["ln2_bias"])
    return x + mm(act(mm(h, p["w_ff1"]) + p["b_ff1"]), p["w_ff2"]) + p["b_ff2"]


def forward(model: dict, params: list, x, mm):
    """x: (batch, lookback, tags) → (batch, tags)."""
    d = int(model["d_model"])
    x = mm(x, params[0]["kernel"]) + params[0]["bias"]
    x = x + _positions(x.shape[1], d)[None]
    for p in params[2:-2]:
        x = _block(p, x, int(model["num_heads"]), ACT[model.get("func", "relu")],
                   bool(model.get("causal", True)), mm)
    if model.get("pool", "last") != "last":
        raise ValueError("the reference pools the last position only")
    return mm(x[:, -1, :], params[-1]["kernel"]) + params[-1]["bias"]


def forward_flops_per_window(config: dict) -> float:
    model, tags = config["model"], int(config["n_tags"])
    t, d, ff = (int(model[k]) for k in ("lookback_window", "d_model", "ff_dim"))
    total = 2.0 * tags * d * t  # input projection, every position
    per_block = 8.0 * d * d * t  # q, k, v and output projections
    per_block += 4.0 * t * t * d  # scores and weighted values
    per_block += 4.0 * d * ff * t  # feed-forward
    total += per_block * int(model["num_blocks"])
    return total + 2.0 * d * tags  # the output layer sees the last position only
