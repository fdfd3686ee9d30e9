"""Plain reference of the model kind ``sambay_model``: layers of the SambaY
decoder-hybrid-decoder (Phi-4-mini-flash, ``model_type`` ``phi4flash``,
https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning, arXiv:2507.06607)
as a windowed sensor model. A dense projection of the tags to ``d`` =
``d_model``, one block a held layer, a final LayerNorm, the last position, a
dense head. Every block is ``x <- x + mixer(LN_1(x))``, ``x <- x + W2(silu(W1
h) * W3 h)`` with ``h = LN_2(x)``, ``LN`` with a gain and a bias at
``norm_eps``. The mixer of published layer ``i`` of ``num_hidden_layers``
(``half`` of them):

- Mamba-1 (the self-decoder's every ``mb_per_layer``-th layer, and layer
  ``half``): ``[u, z] = h W_in``; ``u = silu(conv(u) + b_conv)``, a depthwise
  causal convolution of ``d_conv`` taps; ``[δ, B, C] = u W_x``; ``Δ =
  softplus(δ W_dt + b_dt)``; ``A = -exp(A_log)``; ``h_t = exp(Δ_t A) h_{t-1} +
  (Δ_t u_t) B_t``, ``y_t = h_t C_t + D u_t``, a plain ``lax.scan`` over
  positions; out ``(y * silu(z)) W_out``. Layer ``half`` keeps ``m = y *
  silu(z)``, the memory;
- differential attention (the self-decoder's other layers, over a band of
  ``sliding_window`` rows; layer ``half + 1``, full, which keeps its keys and
  values; and the cross-decoder's odd layers, which read layer ``half + 1``'s
  keys and values with queries of their own): heads in pairs, ``A_i =
  softmax(q_i k_i^T / sqrt(head_dim) + mask)``, ``o = A_1 v - λ A_2 v``, ``λ =
  exp(λ_q1 · λ_k1) - exp(λ_q2 · λ_k2) + λ_init``, ``λ_init = 0.8 - 0.6
  exp(-0.3 i)``; ``o <- g RMSNorm(o) (1 - λ_init)`` a pair (eps 1e-5); out
  ``concat(o) W_o``. The mask is written out pair by pair: key ``s`` is seen
  from query ``t`` iff ``t - window < s <= t``. No positional encoding;
- a gated memory unit (the cross-decoder's even layers): ``(silu(h W_1) * m)
  W_2``.

Departures from the published model, each also in the configuration's file:
no token embedding and no vocabulary (sensor rows in through a dense layer
with a bias, a sensor row out through a dense head: both glorot-uniform, as
the system's own dense layers are), the Mamba sizes the config does not give
at the family's defaults, normal(0, 0.02) matrices and convolution taps.

Initial weights: one key a layer, split as the program splits it; unit gains
and zero biases; ``A_log = log(1..d_state)`` a channel, ``D = 1``, Δ's bias
the inverse softplus of a log-uniform draw in [1e-3, 1e-1] floored at 1e-4
(Mamba's own start), λ vectors normal(0, 0.1) (the differential
transformer's). ``mm`` is the matmul the caller chose
(:func:`chipbench.reference.matmul`); the scan and the norms are float32.

What a run of the cell pays for this file: a block is recomputed in the
backward pass (``jax.checkpoint``), and so is a step of the scan, so that of
the scan only its state a position (1.34 GB a Mamba layer at 4,096 tokens)
is kept, for one layer at a time; and the
trailing blocks that read another block's state (a gated memory unit,
cross-attention) and are otherwise position-wise are computed at the last
position alone, which is all the head reads of them: the same function, to
the rounding of a sum's order. :func:`forward_flops_per_window` counts the
work the model requires (conventions: :mod:`chipbench.flops`), those trailing
blocks at the last position."""

import functools
import json
import math

import jax
import jax.numpy as jnp

from chipbench.reference import HIGHEST, dense_init

_LAMBDAS = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")


def layout(model: dict) -> list:
    """``(mixer, window, keeps, index)`` of each held layer, by the published
    layout: ``half = num_hidden_layers / 2``; Mamba at ``half`` keeps the
    memory, full attention at ``half + 1`` the keys and values; after them a
    gated memory unit on the even layers and cross-attention on the odd;
    before them Mamba every ``mb_per_layer``-th layer and window attention
    between."""
    n, every = int(model["num_hidden_layers"]), int(model["mb_per_layer"])
    window, half = int(model["sliding_window"]), n // 2
    out = []
    for i in model["layers_held"]:
        if i == half:
            out.append(("mamba", 0, "memory", i))
        elif i == half + 1:
            out.append(("attention", 0, "kv", i))
        elif i > half + 1:
            out.append(("gmu" if i % 2 == 0 else "cross_attention", 0, "", i))
        elif i % every == 0:
            out.append(("mamba", 0, "", i))
        else:
            out.append(("attention", window, "", i))
    return out


def sizes(model: dict) -> dict:
    d = int(model["d_model"])
    return {
        "d": d, "ff": int(model["ff_dim"]), "heads": int(model["num_heads"]),
        "kv": int(model["num_kv_heads"]), "dh": int(model["head_dim"]),
        "di": int(model["expand"]) * d, "ds": int(model["d_state"]),
        "conv": int(model["d_conv"]), "rank": int(model["dt_rank"]),
        "eps": float(model["norm_eps"]),
    }


def _normal(key, shape, std=0.02):
    return std * jax.random.normal(key, shape, jnp.float32)


def _block_init(key, model: dict, mixer: str) -> dict:
    s = sizes(model)
    d, di, ds, dh = s["d"], s["di"], s["ds"], s["dh"]
    k_mix, k_ffn = jax.random.split(key)
    p = {
        "ln1_scale": jnp.ones((d,)), "ln1_bias": jnp.zeros((d,)),
        "ln2_scale": jnp.ones((d,)), "ln2_bias": jnp.zeros((d,)),
    }
    if mixer == "mamba":
        ks = jax.random.split(k_mix, 6)
        lo, hi = math.log(1e-3), math.log(1e-1)
        dt = jnp.exp(jax.random.uniform(ks[4], (di,), jnp.float32) * (hi - lo) + lo)
        dt = jnp.maximum(dt, 1e-4)
        p["w_in"] = _normal(ks[0], (d, 2 * di))
        p["conv_taps"] = _normal(ks[1], (di, s["conv"]))
        p["conv_bias"] = jnp.zeros((di,))
        p["w_x"] = _normal(ks[2], (di, s["rank"] + 2 * ds))
        p["w_dt"] = _normal(ks[3], (s["rank"], di))
        p["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))  # softplus(dt_bias) = dt
        p["a_log"] = jnp.log(jnp.broadcast_to(jnp.arange(1, ds + 1, dtype=jnp.float32), (di, ds)))
        p["d_skip"] = jnp.ones((di,))
        p["w_out"] = _normal(ks[5], (di, d))
    elif mixer in ("attention", "cross_attention"):
        ks = jax.random.split(k_mix, 8)
        p["wq"] = _normal(ks[0], (d, s["heads"] * dh))
        if mixer == "attention":
            p["wk"] = _normal(ks[1], (d, s["kv"] * dh))
            p["wv"] = _normal(ks[2], (d, s["kv"] * dh))
        p["wo"] = _normal(ks[3], (s["heads"] * dh, d))
        for name, k in zip(_LAMBDAS, ks[4:]):
            p[name] = _normal(k, (dh,), std=0.1)
        p["subln"] = jnp.ones((2 * dh,))
    else:
        ks = jax.random.split(k_mix, 2)
        p["w_gate"], p["w_gmu_out"] = _normal(ks[0], (d, di)), _normal(ks[1], (di, d))
    ks = jax.random.split(k_ffn, 3)
    p["w1"], p["w3"] = _normal(ks[0], (d, s["ff"])), _normal(ks[1], (d, s["ff"]))
    p["w2"] = _normal(ks[2], (s["ff"], d))
    return p


@functools.lru_cache(maxsize=None)
def _init_fn(model_key: str, n_tags: int):
    model = json.loads(model_key)

    def init(key):
        d, layers = int(model["d_model"]), layout(model)
        # dense, blocks, norm, pool, dense
        keys = jax.random.split(key, len(layers) + 4)
        params = [dense_init(keys[0], n_tags, d)]
        params += [_block_init(k, model, m[0]) for k, m in zip(keys[1:], layers)]
        params += [{"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))}, {}]
        return params + [dense_init(keys[-1], d, n_tags)]

    return jax.jit(init)


def init_params(key, model: dict, n_tags: int) -> list:
    # one compiled program for the whole tree: leaf by leaf, every shape's
    # generator would compile anew in every process
    return _init_fn(json.dumps(model, sort_keys=True), int(n_tags))(key)


def _layer_norm(x, gain, bias, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * gain + bias


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _mamba(s, p, h, mm):
    """h: (batch, time, d) → (the mixer's output, the memory y * silu(z))."""
    di, ds, rank = s["di"], s["ds"], s["rank"]
    t = h.shape[1]
    xz = mm(h, p["w_in"])
    u, z = xz[..., :di], xz[..., di:]
    padded = jnp.pad(u, ((0, 0), (s["conv"] - 1, 0), (0, 0)))
    conv = sum(padded[:, j : j + t] * p["conv_taps"][:, j] for j in range(s["conv"]))
    u = _silu(conv + p["conv_bias"])
    dbc = mm(u, p["w_x"])
    delta = jax.nn.softplus(mm(dbc[..., :rank], p["w_dt"]) + p["dt_bias"])
    b, c = dbc[..., rank : rank + ds], dbc[..., rank + ds :]
    a = -jnp.exp(p["a_log"]).T  # (d_state, d_inner)

    def step(state, inputs):
        # the state (batch, d_state, d_inner): on a TPU the minor axis fills
        # the lanes, and the state a position that the backward pass keeps
        # takes its size and not eight times it
        dt, ut, bt, ct = inputs
        state = jnp.exp(dt[:, None, :] * a) * state + (dt * ut)[:, None, :] * bt[..., None]
        return state, jnp.einsum("bsi,bs->bi", state, ct, precision=HIGHEST)

    inputs = tuple(jnp.moveaxis(x, 1, 0) for x in (delta, u, b, c))
    # the backward pass keeps the state a position and recomputes the rest
    _, ys = jax.lax.scan(jax.checkpoint(step), jnp.zeros((h.shape[0], ds, di)), inputs)
    memory = (jnp.moveaxis(ys, 0, 1) + u * p["d_skip"]) * _silu(z)
    return mm(memory, p["w_out"]), memory


def _diff_attention(s, p, h, kv, index, window, mm):
    """Queries from ``h``, keys and values from ``h`` too or, for
    cross-attention, ``kv``, whose ``t`` positions end where ``h``'s do.
    Returns (out, (k, v))."""
    bsz, tq = h.shape[:2]
    dh, pairs = s["dh"], s["heads"] // 2
    q = mm(h, p["wq"]).reshape(bsz, tq, pairs, 2, dh)
    if kv is None:
        kv = (
            mm(h, p["wk"]).reshape(bsz, tq, s["kv"] // 2, 2, dh),
            mm(h, p["wv"]).reshape(bsz, tq, s["kv"] // 2, 2 * dh),
        )
    t = kv[0].shape[1]
    rep = pairs // kv[0].shape[2]
    k, v = (jnp.repeat(a, rep, axis=2) for a in kv)
    rows = jnp.arange(t - tq, t)[:, None]
    cols = jnp.arange(t)[None, :]
    seen = cols <= rows
    if window:
        seen = seen & (cols > rows - window)
    vh = v.transpose(0, 2, 1, 3)
    maps = []
    for i in (0, 1):
        scores = mm(q[..., i, :].transpose(0, 2, 1, 3), k[..., i, :].transpose(0, 2, 3, 1))
        scores = jnp.where(seen, scores / math.sqrt(dh), -1e30)
        maps.append(mm(jax.nn.softmax(scores, axis=-1), vh))
    init = 0.8 - 0.6 * math.exp(-0.3 * index)
    q1, k1, q2, k2 = (p[name] for name in _LAMBDAS)
    lam = jnp.exp(jnp.sum(q1 * k1)) - jnp.exp(jnp.sum(q2 * k2)) + init
    o = maps[0] - lam * maps[1]
    o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + 1e-5) * p["subln"] * (1.0 - init)
    o = o.transpose(0, 2, 1, 3).reshape(bsz, tq, pairs * 2 * dh)
    return mm(o, p["wo"]), kv


def _block(s, spec, p, x, shared, mm):
    """x: (batch, time, d) → (x, what the block keeps). ``spec`` is its
    ``layout`` entry. A gated memory unit or cross-attention given ``x`` of
    fewer positions than the memory or the keys reads their last positions."""
    mixer, window, keeps, index = spec
    h = _layer_norm(x, p["ln1_scale"], p["ln1_bias"], s["eps"])
    kept = {}
    if mixer == "mamba":
        y, memory = _mamba(s, p, h, mm)
        if keeps:
            kept["memory"] = memory
    elif mixer == "attention":
        y, kv = _diff_attention(s, p, h, None, index, window, mm)
        if keeps:
            kept["kv"] = kv
    elif mixer == "cross_attention":
        y, _ = _diff_attention(s, p, h, shared["kv"], index, 0, mm)
    else:
        memory = shared["memory"][:, -x.shape[1] :]
        y = mm(_silu(mm(h, p["w_gate"])) * memory, p["w_gmu_out"])
    x = x + y
    h = _layer_norm(x, p["ln2_scale"], p["ln2_bias"], s["eps"])
    return x + mm(_silu(mm(h, p["w1"])) * mm(h, p["w3"]), p["w2"]), kept


def forward(model: dict, params: list, x, mm):
    """x: (batch, lookback, tags) → (batch, tags)."""
    s, layers = sizes(model), layout(model)
    x = mm(x, params[0]["kernel"]) + params[0]["bias"]
    # the trailing blocks that read another block's state are position-wise
    # otherwise: the head reads their last position alone
    tail = len(layers)
    while tail and layers[tail - 1][0] in ("gmu", "cross_attention"):
        tail -= 1
    shared = {}
    for i, (spec, p) in enumerate(zip(layers, params[1:-3])):
        if i == tail:
            x = x[:, -1:]
        x, kept = jax.checkpoint(
            lambda p, x, shared, spec=spec: _block(s, spec, p, x, shared, mm)
        )(p, x, shared)
        shared.update(kept)
    x = _layer_norm(x[:, -1], params[-3]["scale"], params[-3]["bias"], s["eps"])
    return mm(x, params[-1]["kernel"]) + params[-1]["bias"]


def _keys_seen(t: int, window: int) -> float:
    """Query-key pairs a causal map counts over ``t`` positions, half of
    ``t x t`` as the other kinds count it; a band of ``window`` rows that cuts
    keeps ``t window - window^2 / 2`` of them."""
    if not window or window >= t:
        return 0.5 * t * t
    return t * window - 0.5 * window * window


def forward_flops_per_window(config: dict) -> float:
    """Matrix products of one forward pass over one window, as the model
    requires them: every position of the blocks up to the last that does not
    read another block's state, and the trailing gated memory units and
    cross-attention at the last position alone (all the head reads of them;
    cross-attention's last query against every key). A differential map pair
    is two maps of scores over ``head_dim`` and weighted values of
    ``2 head_dim`` a query pair. The scan, the convolution and the norms are
    elementwise and not counted (:func:`ssm_scan_bytes` counts the scan's bytes)."""
    model, tags = config["model"], int(config["n_tags"])
    s, layers = sizes(model), layout(model)
    t, d, ff, di, dh = int(model["lookback_window"]), s["d"], s["ff"], s["di"], s["dh"]
    hq, hkv = s["heads"] * dh, s["kv"] * dh
    maps = s["heads"] // 2 * 2 * (2 * dh + 2 * 2 * dh)  # a query position, a key
    tail = len(layers)
    while tail and layers[tail - 1][0] in ("gmu", "cross_attention"):
        tail -= 1
    total = 2.0 * tags * d * t  # the tag projection, every position
    for i, (mixer, window, _, _) in enumerate(layers):
        rows = 1 if i >= tail else t
        total += 3 * 2.0 * d * ff * rows  # the MLP
        if mixer == "mamba":
            total += 2.0 * t * (d * 2 * di + di * (s["rank"] + 2 * s["ds"]) + s["rank"] * di + di * d)
        elif mixer == "attention":
            total += 2.0 * t * d * (2 * hq + 2 * hkv) + maps * _keys_seen(t, window)
        elif mixer == "cross_attention":
            keys = t if rows == 1 else _keys_seen(t, 0)
            total += 2.0 * rows * d * 2 * hq + maps * keys
        else:
            total += 2.0 * rows * 2 * d * di
    return total + 2.0 * d * tags  # the head sees the last position only


def ssm_scan_bytes(model: dict, positions: float, layer_steps: float = 1) -> float:
    """The least HBM bytes of the selective scan, forward and backward, over
    ``positions`` positions in ``layer_steps`` scans of a Mamba layer,
    counted from the work and not from an implementation: the scan's inputs
    and output as the program holds them, ``u``, ``B``, ``C`` and ``y`` in
    bfloat16 and ``Δ`` in float32 a position, ``A`` and ``D`` in float32
    once a scan. Forward the inputs are read and ``y`` written; backward the
    inputs are read again with ``dy``, and their gradients, each in its
    input's dtype, written."""
    s = sizes(model)
    di, ds = s["di"], s["ds"]
    inputs = positions * (2 * di + 4 * di + 2 * 2 * ds) + layer_steps * 4 * (di * ds + di)
    output = positions * 2 * di
    return 3 * inputs + 2 * output
