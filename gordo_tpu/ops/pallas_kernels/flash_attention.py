"""
Flash attention for TPU in Pallas: blockwise online-softmax attention that
never materializes the (T, T) score matrix in HBM — forward AND backward.

Design (see /opt/skills/guides/pallas_guide.md):
- Forward grid: (batch*heads, T // BLOCK_Q). Each program owns one query
  block in VMEM; K/V for its (batch, head) slice are staged into VMEM whole,
  and the kernel loops over key blocks with the standard running
  (max, denom, acc) online-softmax update. Score blocks are
  (BLOCK_Q, BLOCK_K) fp32 — VPU-sized — and the two matmuls per block ride
  the MXU. The forward also emits the per-row logsumexp, the only residual
  the backward needs beyond q/k/v/o.
- Backward: two kernels sharing the forward's blocking, both O(T) memory:
  a dQ kernel (grid over query blocks, loop over key blocks) and a dK/dV
  kernel (grid over key blocks, loop over query blocks). Each recomputes its
  score block as P = exp(S - lse) — no stored probabilities, no O(T²)
  anything — and uses the FlashAttention-2 identity
  dS = P ∘ (dP − D) with D = rowsum(dO ∘ O).
- Accumulation in float32 regardless of input dtype (bfloat16-safe).

The kernels compile through Mosaic and therefore need a TPU backend. A
caller that wants the Pallas interpreter (the CPU tests) asks for it with
``interpret=True``; nothing selects it on the caller's behalf.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30

BLOCK_Q = 128
BLOCK_K = 128
# trailing lane-replication axis for per-row statistics (lse, delta): TPU
# vector blocks need their last dim 128-tileable, so row vectors are
# stored broadcast across 128 lanes and sliced back to one lane on read
LANES = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale: float,
                  causal: bool, block_k: int):
    """One query block vs all key blocks, online softmax."""
    q = q_ref[0].astype(jnp.float32)  # (BLOCK_Q, Dh)
    block_q, dh = q.shape
    t_k = k_ref.shape[1]
    n_kb = t_k // block_k
    qi = pl.program_id(1)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)

    def body(j, carry):
        m_prev, l_prev, acc = carry
        k_blk = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = (q @ k_blk.T) * scale  # (BLOCK_Q, BLOCK_K)
        if causal:
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1
            )
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        correction = jnp.exp(m_prev - m_new)
        l_new = l_prev * correction + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * correction + p @ v_blk
        return m_new, l_new, acc

    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, dh), jnp.float32)
    if causal:
        # key blocks strictly past the diagonal are fully masked — bound
        # the loop at the last block that can contain k_pos <= max(q_pos)
        # instead of burning MXU cycles on provably-zero work
        n_kb = jnp.minimum(n_kb, ((qi + 1) * block_q + block_k - 1) // block_k)
    m_fin, l_fin, acc = jax.lax.fori_loop(0, n_kb, body, (m0, l0, acc0))
    o_ref[0] = (acc / jnp.maximum(l_fin, 1e-30)).astype(o_ref.dtype)
    # lse is REPLICATED across the LANES axis (its block's trailing dim):
    # Mosaic requires the last two block dims be (8, 128)-tileable, so a
    # flat (1, block_q) row vector cannot be a TPU output block. The
    # standard trick (same as jax's own TPU flash kernel) is an extra
    # 128-lane axis carrying the broadcast value; readers slice lane 0.
    lse_ref[0] = jnp.broadcast_to(
        m_fin + jnp.log(jnp.maximum(l_fin, 1e-30)), (block_q, LANES)
    )


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref, dq_ref,
                     *, scale: float, causal: bool, block_k: int):
    """dQ for one query block: loop over key blocks, recomputing P from lse."""
    q = q_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0][:, :1]         # (BLOCK_Q, 1) — lane 0 of the broadcast
    # D = rowsum(dO ∘ O), recomputed from the already-staged blocks: a
    # VPU-trivial reduction that avoids materializing a lane-broadcast
    # delta tensor in HBM and staging it in VMEM (review finding)
    delta = jnp.sum(do * o_ref[0].astype(jnp.float32), axis=-1,
                    keepdims=True)
    block_q, dh = q.shape
    t_k = k_ref.shape[1]
    n_kb = t_k // block_k
    qi = pl.program_id(1)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)

    def body(j, dq):
        k_blk = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = (q @ k_blk.T) * scale
        if causal:
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1
            )
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse)                       # (BLOCK_Q, BLOCK_K)
        dp = do @ v_blk.T                          # (BLOCK_Q, BLOCK_K)
        ds = p * (dp - delta)
        return dq + (ds @ k_blk) * scale

    dq0 = jnp.zeros((block_q, dh), jnp.float32)
    if causal:
        # same diagonal bound as the forward: masked blocks have p == 0
        n_kb = jnp.minimum(n_kb, ((qi + 1) * block_q + block_k - 1) // block_k)
    dq_ref[0] = jax.lax.fori_loop(0, n_kb, body, dq0).astype(dq_ref.dtype)


def _flash_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref,
                      dk_ref, dv_ref, *, scale: float, causal: bool,
                      block_q: int):
    """dK/dV for one key block: loop over query blocks."""
    k_blk = k_ref[0].astype(jnp.float32)   # (BLOCK_K, Dh)
    v_blk = v_ref[0].astype(jnp.float32)
    block_k, dh = k_blk.shape
    t_q = q_ref.shape[1]
    n_qb = t_q // block_q
    ki = pl.program_id(1)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(i * block_q, block_q), :1]
        # recomputed per q-block from the staged dO/O (see _flash_dq_kernel)
        delta = jnp.sum(
            do * o_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32),
            axis=-1, keepdims=True,
        )
        s = (q @ k_blk.T) * scale          # (BLOCK_Q, BLOCK_K)
        if causal:
            q_pos = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, 1), 0
            )
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse)
        dv = dv + p.T @ do
        dp = do @ v_blk.T
        ds = p * (dp - delta)
        dk = dk + (ds.T @ q) * scale
        return dk, dv

    dk0 = jnp.zeros((block_k, dh), jnp.float32)
    dv0 = jnp.zeros((block_k, dh), jnp.float32)
    # for causal, query blocks strictly BEFORE this key block see none of
    # it (q_pos < k_pos everywhere): start the loop at the diagonal
    start = (ki * block_k) // block_q if causal else 0
    dk, dv = jax.lax.fori_loop(start, n_qb, body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _block_sizes(t: int):
    block_q = min(BLOCK_Q, t)
    block_k = min(BLOCK_K, t)
    if t % block_q or t % block_k:
        raise ValueError(f"sequence length {t} must be divisible by {block_q}")
    return block_q, block_k


def _flash_forward(q, k, v, causal: bool, interpret: bool):
    """q, k, v: (BH, T, Dh) — flattened leading batch*heads axis.
    Returns (out, lse)."""
    bh, t, dh = q.shape
    if k.shape[1] != t or v.shape[1] != t:
        # the kernel's key-block loop and causal mask assume start-aligned
        # self-attention; cross-length attention must use the XLA path
        raise ValueError(
            f"flash_attention requires equal Q/K/V sequence lengths, got "
            f"q={t}, k={k.shape[1]}, v={v.shape[1]}"
        )
    block_q, block_k = _block_sizes(t)
    scale = 1.0 / (dh**0.5)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, block_k=block_k
    )
    return pl.pallas_call(
        kernel,
        grid=(bh, t // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, t, dh), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, t, dh), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dh), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bh, t, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


def _flash_backward(q, k, v, o, lse, g, causal: bool, interpret: bool):
    """Fused O(T)-memory backward: returns (dq, dk, dv)."""
    bh, t, dh = q.shape
    block_q, block_k = _block_sizes(t)
    scale = 1.0 / (dh**0.5)

    full = lambda b, i: (b, 0, 0)
    dq_kernel = functools.partial(
        _flash_dq_kernel, scale=scale, causal=causal, block_k=block_k
    )
    dq = pl.pallas_call(
        dq_kernel,
        grid=(bh, t // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, t, dh), full),
            pl.BlockSpec((1, t, dh), full),
            pl.BlockSpec((1, block_q, dh), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, dh), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(q, k, v, g, lse, o)

    dkv_kernel = functools.partial(
        _flash_dkv_kernel, scale=scale, causal=causal, block_q=block_q
    )
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(bh, t // block_k),
        in_specs=[
            pl.BlockSpec((1, t, dh), full),
            pl.BlockSpec((1, block_k, dh), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, dh), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, t, dh), full),
            pl.BlockSpec((1, t, LANES), full),
            pl.BlockSpec((1, t, dh), full),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, dh), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, dh), lambda b, j: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        interpret=interpret,
    )(q, k, v, g, lse, o)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_attention(q, k, v, causal, interpret):
    out, _ = _flash_forward(q, k, v, causal, interpret)
    return out


def _flash_fwd(q, k, v, causal, interpret):
    out, lse = _flash_forward(q, k, v, causal, interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, interpret, residuals, g):
    q, k, v, o, lse = residuals
    return _flash_backward(q, k, v, o, lse, g, causal, interpret)


_flash_attention.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal: bool = False, interpret: bool = False):
    """
    Blockwise flash attention. q, k, v: (..., T, Dh); any leading batch dims.

    Compiled unless the caller passes ``interpret=True``: on a backend
    without Mosaic the call raises instead of running interpreted.
    """
    lead = q.shape[:-2]
    t, dh = q.shape[-2:]
    qf = q.reshape((-1, t, dh))
    kf = k.reshape((-1, k.shape[-2], dh))
    vf = v.reshape((-1, v.shape[-2], dh))
    out = _flash_attention(qf, kf, vf, causal, interpret)
    return out.reshape(lead + (t, dh))
