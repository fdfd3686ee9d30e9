"""Plain reference of the model kind ``latent_moe_model``: the layer of the
Xing4.0 family (``model_type`` ``xing4_0``,
https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B) as a windowed sensor
model. A dense projection of the tags to ``d`` = ``d_model``, the row copied
into ``n`` = ``streams`` residual streams, one block a layer, the streams
summed, a final RMSNorm, the last position, a dense head. With ``N(x) = x *
rsqrt(mean(x^2) + eps)`` and a gain ``g`` where one is named:

- residual streams (manifold-constrained hyper-connections, arXiv:2512.24880
  section 3, over hyper-connections, arXiv:2409.19606), for each of a block's
  two sublayers ``F`` (attention, then the FFN) and a token's state ``X``
  (n x d): ``x~ = N(vec(X))`` (no gain); ``H_pre = sigmoid(a_pre x~ phi_pre +
  b_pre)`` (n); ``H_post = 2 sigmoid(a_post x~ phi_post + b_post)`` (n);
  ``H_res = Sinkhorn(exp(clip(a_res mat(x~ phi_res) + b_res, -c, c)))``
  (n x n): ``sinkhorn_iters`` times, every row then every column divided by
  its sum plus ``hc_eps``; ``X <- H_res X + H_post^T F(g N(H_pre X))``
- latent attention (DeepSeek-V2 section 2.1): ``c_q = g_q N(h W_dq)``;
  ``[q_nope | q_rope] = c_q W_uq`` a head; ``[c_kv | k_r] = h W_dkv``; ``c_kv =
  g_kv N(c_kv)``; ``[k_nope | v] = c_kv W_ukv`` a head; ``q = [q_nope |
  RoPE(q_rope)]``, ``k = [k_nope | RoPE(k_r)]``, the one rotary key head shared
  by all heads; causal ``softmax(q k^T s) v``, ``s = (nope + rope)^-1/2 m^2``,
  ``m = 0.1 mscale_all_dim ln(factor) + 1``; out ``concat(o) W_o``. RoPE is
  rotate-half at YaRN's frequencies (arXiv:2309.00071: ``theta^(-2i/D)`` where
  pair ``i`` turns over ``beta_fast`` times in the original context, that
  over ``factor`` where it turns under ``beta_slow`` times, a linear ramp over
  ``i`` between the two correction dims), cos and sin times ``mscale``'s
  temperature over ``mscale_all_dim``'s. No bias
- dense FFN: ``W2(silu(W1 h) * W3 h)``
- routed FFN: ``s = sigmoid(h W_r)`` in float32; the ``top_k`` of ``s + bias``
  are picked; weights ``s`` at the picked over ``(their sum + 1e-20)``, times
  ``routed_scale``; output ``Shared(h) +`` the weighted sum of the picked
  experts that are held here (ids ``expert_offset`` … ``+ experts_held``).
  Every held expert is applied to every token and weighted by its gate, zero
  where it was not picked (one SwiGLU as wide as the held experts together,
  an expert's hidden units scaled by its gate): no sort, no capacity, no
  kernel. ``Shared`` is a
  SwiGLU of ``shared_experts x expert_dim`` that every token takes; ``bias``
  is a leaf no gradient reaches.

Departures from the published model, each also in the configuration's file:
no token embedding and no vocabulary (sensor rows in through a dense layer
with a bias, a sensor row out through a dense head: both glorot-uniform, as
the system's own dense layers are), no multi-token prediction module, only
the held experts' part of a routed layer, the router and the mixing
coefficients computed in float32 whatever ``mm`` rounds, rotate-half RoPE.

Initial weights: normal(0, 0.02) for every matrix (``phi`` too), unit gains,
a zero selection bias, ``a`` 0.01, ``b_pre`` = ``b_post`` = 0, ``b_res`` 0 on
the diagonal and -8 off it; one key a layer, split as the program splits it.
``mm`` is the matmul the caller chose (:func:`chipbench.reference.matmul`).

What a run of the cell pays for this file, and what was done about it: at
0.64 billion parameters the reference is a third of a run's wall, and the
driver stops a run at 360 s. So (1) :func:`init_params` draws the whole tree
in one compiled program; (2) a layer is recomputed in the backward pass
(``jax.checkpoint``), so that a machine keeps one layer's float32 activations
at a time, but the outputs of its widest products (the names ``KEPT``: 105 KB
a token a layer) are kept from the first pass and not computed twice; (3) the
last block computes its queries, its output projection, its FFN and its
mixing at the last position alone, because the head reads nothing else of it
and no later block asks for the other positions' keys and values: the same
function, to the rounding of a sum's order (every position still gives the
last one its keys and values). The step is 13.5 TFLOP where the plain reading
of the same equations was 21.5 (compiler, PR 37).
:func:`forward_flops_per_window` is the kind's operation count (conventions:
:mod:`chipbench.flops`): every position of every layer, as the program
computes them."""

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from chipbench.reference import HIGHEST, dense_init

# what a recomputed layer keeps from its first pass (``jax.checkpoint``'s
# policy): the outputs of its widest products, so that the backward pass
# computes them once
KEPT = "kept"


def _normal(key, shape):
    return 0.02 * jax.random.normal(key, shape, jnp.float32)


def _block_init(key, model: dict, ffn: str) -> dict:
    d, n, heads = int(model["d_model"]), int(model["streams"]), int(model["num_heads"])
    rank_q, rank_kv = int(model["q_lora_rank"]), int(model["kv_lora_rank"])
    nope, rope, dv = (
        int(model[k]) for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim")
    )
    k_op, k_ffn, k_hc = jax.random.split(key, 3)
    ks = jax.random.split(k_op, 5)
    p = {
        "op_norm": jnp.ones((d,)),
        "ffn_norm": jnp.ones((d,)),
        "w_dq": _normal(ks[0], (d, rank_q)),
        "q_norm": jnp.ones((rank_q,)),
        "w_uq": _normal(ks[1], (rank_q, heads * (nope + rope))),
        "w_dkv": _normal(ks[2], (d, rank_kv + rope)),
        "kv_norm": jnp.ones((rank_kv,)),
        "w_ukv": _normal(ks[3], (rank_kv, heads * (nope + dv))),
        "wo": _normal(ks[4], (heads * dv, d)),
    }
    k_routed, k_shared = jax.random.split(k_ffn)
    if ffn == "dense":
        ks, f = jax.random.split(k_routed, 3), int(model["ff_dim"])
        p["w1"], p["w3"] = _normal(ks[0], (d, f)), _normal(ks[1], (d, f))
        p["w2"] = _normal(ks[2], (f, d))
    else:
        ks = jax.random.split(k_routed, 4)
        f, held = int(model["expert_dim"]), int(model["experts_held"])
        p["router"] = _normal(ks[0], (d, int(model["num_experts"])))
        p["expert_bias"] = jnp.zeros((int(model["num_experts"]),))
        p["w1"], p["w3"] = _normal(ks[1], (held, d, f)), _normal(ks[2], (held, d, f))
        p["w2"] = _normal(ks[3], (held, f, d))
        if int(model["shared_experts"]):
            ks, f = jax.random.split(k_shared, 3), int(model["shared_experts"]) * f
            p["shared_w1"], p["shared_w3"] = _normal(ks[0], (d, f)), _normal(ks[1], (d, f))
            p["shared_w2"] = _normal(ks[2], (f, d))
    for prefix, k in zip(("hc_op_", "hc_ffn_"), jax.random.split(k_hc)):
        # (n, d, 2n + n^2): the rows are vec(X)'s, the columns [pre | post | res]
        p[prefix + "phi"] = _normal(k, (n, d, 2 * n + n * n))
        # a stated dtype: a fill from a Python scalar is weakly typed, the step's
        # outputs are not, and the step would compile a second time for them
        p[prefix + "alpha"] = jnp.full((3,), 0.01, jnp.float32)
        p[prefix + "b_pre"], p[prefix + "b_post"] = jnp.zeros((n,)), jnp.zeros((n,))
        p[prefix + "b_res"] = -8.0 * (1.0 - jnp.eye(n))
    return p


@functools.lru_cache(maxsize=None)
def _init_fn(model_key: str, n_tags: int):
    model = json.loads(model_key)

    def init(key):
        d, ffns = int(model["d_model"]), list(model["ffns"])
        # dense, expand, blocks, collapse, norm, pool, dense
        keys = jax.random.split(key, len(ffns) + 6)
        params = [dense_init(keys[0], n_tags, d), {}]
        params += [_block_init(k, model, ffn) for k, ffn in zip(keys[2:], ffns)]
        params += [{}, {"scale": jnp.ones((d,))}, {}, dense_init(keys[-1], d, n_tags)]
        return params

    return jax.jit(init)


def init_params(key, model: dict, n_tags: int) -> list:
    # one compiled program for the whole tree: leaf by leaf, every shape's
    # generator compiles anew in every process (40 s at 0.64 billion parameters)
    return _init_fn(json.dumps(model, sort_keys=True), int(n_tags))(key)


def _norm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _mscale(factor, mscale):
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn(model) -> tuple:
    """(the rope_dim / 2 frequencies, the factor on cos and sin, m)."""
    dim, theta = int(model["qk_rope_head_dim"]), float(model["rope_theta"])
    plain = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    yarn = model.get("rope_scaling")
    if not yarn or float(yarn["factor"]) <= 1.0:
        return plain.astype(np.float32), 1.0, 1.0
    factor, context = float(yarn["factor"]), float(yarn["original_max_position_embeddings"])

    def pair_that_turns(times):
        return dim * math.log(context / (times * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair_that_turns(float(yarn["beta_fast"]))), 0)
    high = min(math.ceil(pair_that_turns(float(yarn["beta_slow"]))), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 0.001), 0.0, 1.0)
    freq = plain / factor * ramp + plain * (1.0 - ramp)
    m_all = _mscale(factor, float(yarn["mscale_all_dim"]))
    return freq.astype(np.float32), _mscale(factor, float(yarn["mscale"])) / m_all, m_all


def _rope(x, freq, turn, first=0):
    """x: (batch, heads, time, D), its rows the positions from ``first`` on;
    rotate-half."""
    t, half = x.shape[-2], x.shape[-1] // 2
    angles = jnp.arange(first, first + t, dtype=jnp.float32)[:, None] * jnp.asarray(freq)[None, :]
    cos = turn * jnp.concatenate([jnp.cos(angles), jnp.cos(angles)], axis=-1)
    sin = turn * jnp.concatenate([jnp.sin(angles), jnp.sin(angles)], axis=-1)
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


def _attention(model, p, h, mm, last_only=False):
    """h: (batch, time, d) → (batch, time, d); with ``last_only`` the queries
    are the last position's alone (it sees every key) → (batch, 1, d)."""
    bsz, t, _ = h.shape
    heads, rank_kv = int(model["num_heads"]), int(model["kv_lora_rank"])
    nope, rope = int(model["qk_nope_head_dim"]), int(model["qk_rope_head_dim"])
    eps = float(model["norm_eps"])
    freq, turn, m = _yarn(model)

    def split_heads(a):
        return a.reshape(bsz, a.shape[1], heads, -1).transpose(0, 2, 1, 3)

    h_q, first = (h[:, -1:], t - 1) if last_only else (h, 0)
    c_q = p["q_norm"] * _norm(checkpoint_name(mm(h_q, p["w_dq"]), KEPT), eps)
    q = split_heads(mm(c_q, p["w_uq"]))
    down = checkpoint_name(mm(h, p["w_dkv"]), KEPT)
    c_kv = p["kv_norm"] * _norm(down[..., :rank_kv], eps)
    k_r = _rope(down[..., None, :, rank_kv:].reshape(bsz, 1, t, rope), freq, turn)
    kv = split_heads(mm(c_kv, p["w_ukv"]))
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], freq, turn, first)], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.tile(k_r, (1, heads, 1, 1))], axis=-1)
    v = kv[..., nope:]
    logits = mm(q, jnp.swapaxes(k, -1, -2)) * ((nope + rope) ** -0.5 * m * m)
    if not last_only:
        logits = jnp.where(jnp.tril(jnp.ones((t, t), bool)), logits, -1e30)
    out = mm(jax.nn.softmax(logits, axis=-1), v)
    out = mm(out.transpose(0, 2, 1, 3).reshape(bsz, out.shape[2], -1), p["wo"])
    return checkpoint_name(out, KEPT)


def _swiglu(w1, w3, w2, h, mm):
    gate, up = checkpoint_name(mm(h, w1), KEPT), checkpoint_name(mm(h, w3), KEPT)
    return mm(jax.nn.silu(gate) * up, w2)


def _routed(model, p, h, mm):
    n_experts, k = int(model["num_experts"]), int(model["top_k"])
    held, offset = int(model["experts_held"]), int(model["expert_offset"])
    scores = jax.nn.sigmoid(jnp.matmul(h, p["router"], precision=HIGHEST))
    _, picked = jax.lax.top_k(jax.lax.stop_gradient(scores) + p["expert_bias"], k)
    weight = jnp.take_along_axis(scores, picked, axis=-1)
    weight = weight / (weight.sum(-1, keepdims=True) + 1e-20) * float(model["routed_scale"])
    # (…, experts): an expert's gate, zero where it was not picked
    gates = (jax.nn.one_hot(picked, n_experts) * weight[..., None]).sum(-2)
    # all held experts at once, as one SwiGLU as wide as they are together
    # (``held x expert_dim`` hidden units, an expert's own block of them scaled
    # by its gate before the way back): the sum over experts is the second
    # product's own, and no (experts, tokens, d) array is made
    w1, w3, w2 = p["w1"], p["w3"], p["w2"]
    f = w1.shape[-1]
    hidden = jax.nn.silu(
        checkpoint_name(mm(h, jnp.moveaxis(w1, 0, 1).reshape(w1.shape[1], held * f)), KEPT)
    ) * checkpoint_name(mm(h, jnp.moveaxis(w3, 0, 1).reshape(w3.shape[1], held * f)), KEPT)
    hidden = hidden * jnp.repeat(gates[..., offset : offset + held], f, axis=-1)
    out = mm(hidden, w2.reshape(held * f, w2.shape[-1]))
    if int(model["shared_experts"]):
        out = out + _swiglu(p["shared_w1"], p["shared_w3"], p["shared_w2"], h, mm)
    return checkpoint_name(out, KEPT)


def sinkhorn(matrix, iters: int, eps: float):
    """(…, n, n) positive → doubly stochastic: rows, then columns, ``iters``
    times."""
    for _ in range(iters):
        matrix = matrix / (matrix.sum(-1, keepdims=True) + eps)
        matrix = matrix / (matrix.sum(-2, keepdims=True) + eps)
    return matrix


def mixing(model, p, prefix, x):
    """x: (batch, time, n, d) → H_pre (…, n), H_post (…, n), H_res (…, n, n),
    float32 at ``highest`` whatever the caller's matmul rounds."""
    n, d = x.shape[-2], x.shape[-1]
    flat = _norm(x.reshape(x.shape[:-2] + (n * d,)), float(model["norm_eps"]))
    raw = jnp.matmul(flat, p[prefix + "phi"].reshape(n * d, -1), precision=HIGHEST)
    raw = checkpoint_name(raw, KEPT)
    a = p[prefix + "alpha"]
    pre = jax.nn.sigmoid(a[0] * raw[..., :n] + p[prefix + "b_pre"])
    post = 2.0 * jax.nn.sigmoid(a[1] * raw[..., n : 2 * n] + p[prefix + "b_post"])
    res = a[2] * raw[..., 2 * n :].reshape(raw.shape[:-1] + (n, n)) + p[prefix + "b_res"]
    clamp = float(model["hc_clamp"])
    res = sinkhorn(
        jnp.exp(jnp.clip(res, -clamp, clamp)),
        int(model["sinkhorn_iters"]), float(model["hc_eps"]),
    )
    return pre, post, res


def _block(model, ffn, p, x, mm, last_only=False):
    """x: (batch, time, n, d) → the same; with ``last_only`` → (batch, 1, n, d),
    the last position's state alone: what the model's last block owes, whose
    other positions nothing reads (the head sees the last position, and no
    later block asks for their keys and values). Every position still gives
    its keys and values to the last one's attention."""
    eps = float(model["norm_eps"])

    def feed_forward(h):
        if ffn == "dense":
            return checkpoint_name(_swiglu(p["w1"], p["w3"], p["w2"], h, mm), KEPT)
        return _routed(model, p, h, mm)

    sublayers = (
        ("hc_op_", "op_norm", lambda h: _attention(model, p, h, mm, last_only)),
        ("hc_ffn_", "ffn_norm", feed_forward),
    )
    for prefix, gain, sublayer in sublayers:
        pre, post, res = mixing(model, p, prefix, x)
        h = jnp.einsum("btn,btnd->btd", pre, x, precision=HIGHEST)
        y = sublayer(p[gain] * _norm(h, eps))
        if last_only and x.shape[1] > 1:
            post, res, x = post[:, -1:], res[:, -1:], x[:, -1:]
        x = jnp.einsum("btij,btjd->btid", res, x, precision=HIGHEST) + (
            post[..., None] * y[..., None, :]
        )
    return x


def forward(model: dict, params: list, x, mm):
    """x: (batch, lookback, tags) → (batch, tags)."""
    x = mm(x, params[0]["kernel"]) + params[0]["bias"]
    x = jnp.repeat(x[..., None, :], int(model["streams"]), axis=-2)
    blocks = list(zip(model["ffns"], params[2:-4]))
    keep = jax.checkpoint_policies.save_only_these_names(KEPT)
    for i, (ffn, p) in enumerate(blocks):
        last = i == len(blocks) - 1
        x = jax.checkpoint(
            lambda p, x, ffn=ffn, last=last: _block(model, ffn, p, x, mm, last), policy=keep
        )(p, x)
    x = params[-3]["scale"] * _norm(x.sum(-2), float(model["norm_eps"]))
    return mm(x[:, -1, :], params[-1]["kernel"]) + params[-1]["bias"]


def forward_flops_per_window(config: dict, held_load=None) -> float:
    """Matrix products of one forward pass over one window. Attention's scores
    and weighted values are counted causal (half of T x T); a routed layer's
    experts at ``held_load`` assignments a token to the experts held here:
    what a run's counters read where the caller has them
    (``fleet_step_mfu_routed``), else what an even router gives the share,
    ``top_k x experts_held / num_experts``; the shared expert at 1. The
    streams' mixing is counted as the products it is: the coefficients'
    (``n d`` by ``2n + n^2``) and ``H_pre X``, ``H_res X``, ``H_post^T y``."""
    model, tags = config["model"], int(config["n_tags"])
    t, d, n = int(model["lookback_window"]), int(model["d_model"]), int(model["streams"])
    heads, rank_q, rank_kv = (
        int(model[k]) for k in ("num_heads", "q_lora_rank", "kv_lora_rank")
    )
    nope, rope, dv = (
        int(model[k]) for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim")
    )
    load = held_load
    if load is None:
        load = int(model["top_k"]) * int(model["experts_held"]) / int(model["num_experts"])
    attention = 2.0 * t * (
        d * rank_q + rank_q * heads * (nope + rope)  # W_dq, W_uq
        + d * (rank_kv + rope) + rank_kv * heads * (nope + dv)  # W_dkv, W_ukv
        + heads * dv * d  # W_o
    ) + 0.5 * 2.0 * t * t * heads * (nope + rope + dv)
    mix = 2 * (2.0 * n * d * (2 * n + n * n) + 2.0 * (n + n * n + n) * d) * t
    total = 2.0 * tags * d * t  # the tag projection, every position
    for ffn in model["ffns"]:
        total += attention + mix
        if ffn == "dense":
            total += 3 * 2.0 * d * int(model["ff_dim"]) * t
        else:
            total += 2.0 * d * int(model["num_experts"]) * t  # the router
            total += (load + int(model["shared_experts"])) * 3 * 2.0 * d * int(
                model["expert_dim"]
            ) * t
    return total + 2.0 * d * tags  # the head sees the last position only
