"""
Ring attention: sequence-parallel exact attention over a mesh axis.

NEW capability with no reference analog (SURVEY.md §5: "long-context /
sequence parallelism: absent" — gordo's sequences are bounded lookback
windows). For lookback windows too long for one chip's HBM/VMEM, the
sequence axis is sharded over a mesh axis and attention runs as a ring:
each device holds one query shard resident and circulates K/V shards
around the ring with ``lax.ppermute`` (one ICI hop per step), folding each
incoming block into a running online-softmax accumulator — the same
blockwise math as the flash kernel (gordo_tpu/ops/pallas_kernels/
flash_attention.py), so results are exact, not approximate.

Communication pattern: n-1 ppermute steps of the local K/V block; compute
(2·T_local²·Dh FLOPs per step) overlaps the next block's transfer under
XLA's async collectives. Memory per device is O(T_local) — total sequence
length scales linearly with the number of devices in the ring.

Tested on the 8-virtual-device CPU mesh (conftest.py) against full
attention; the same program runs unchanged over ICI on a TPU pod slice.
"""

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NEG_INF = -1e30


def _block_update(q, k_blk, v_blk, q_off, k_off, scale, causal, carry):
    """Fold one K/V block into the running online-softmax accumulator."""
    m_prev, l_prev, acc = carry
    s = jnp.einsum("...qd,...kd->...qk", q, k_blk).astype(jnp.float32) * scale
    if causal:
        t_q, t_k = s.shape[-2], s.shape[-1]
        q_pos = q_off + jnp.arange(t_q)[:, None]
        k_pos = k_off + jnp.arange(t_k)[None, :]
        mask = (q_pos >= k_pos).astype(jnp.float32)
        s = jnp.where(mask > 0, s, NEG_INF)
    else:
        mask = None
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    if mask is not None:
        # a fully-masked block has m_new == NEG_INF; exp(s - m_new) would be
        # exp(0) = 1 there, so zero masked entries explicitly
        p = p * mask
    correction = jnp.exp(m_prev - m_new)
    l_new = l_prev * correction + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * correction + jnp.einsum(
        "...qk,...kd->...qd", p, v_blk.astype(jnp.float32)
    )
    return m_new, l_new, acc_new


def _ring_attention_local(q, k, v, axis_name: str, causal: bool):
    """
    Runs inside shard_map. q, k, v: this device's sequence shard
    (..., T_local, Dh). Returns the local shard of the attention output.
    """
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    t_local, dh = q.shape[-2], q.shape[-1]
    scale = 1.0 / (dh**0.5)
    q32 = q.astype(jnp.float32)
    q_off = idx * t_local

    # receive from the next device, send to the previous: after s steps the
    # local K/V block is the one that started on device (idx + s) % n
    perm = [(i, (i - 1) % n) for i in range(n)]

    def step(s, carry):
        k_blk, v_blk, m, l, acc = carry
        k_off = ((idx + s) % n) * t_local
        m, l, acc = _block_update(
            q32, k_blk.astype(jnp.float32), v_blk, q_off, k_off, scale, causal,
            (m, l, acc),
        )
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return k_blk, v_blk, m, l, acc

    lead = q.shape[:-2]
    m0 = jnp.full(lead + (t_local, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros(lead + (t_local, 1), jnp.float32)
    acc0 = jnp.zeros(lead + (t_local, dh), jnp.float32)
    # the accumulators become device-varying inside the loop (they depend on
    # this device's q shard); mark the replicated initial values accordingly
    # so the fori_loop carry types line up under shard_map
    m0, l0, acc0 = jax.lax.pcast((m0, l0, acc0), (axis_name,), to="varying")
    # the last step's ppermute is redundant but keeps the loop uniform; XLA
    # dead-code-eliminates unused collective results only when safe, so we
    # run n-1 communication steps and fold the final block outside the loop
    k_blk, v_blk, m, l, acc = (k, v, m0, l0, acc0)
    k_blk, v_blk, m, l, acc = jax.lax.fori_loop(
        0, n - 1, step, (k_blk, v_blk, m, l, acc)
    )
    k_off = ((idx + n - 1) % n) * t_local
    m, l, acc = _block_update(
        q32, k_blk.astype(jnp.float32), v_blk, q_off, k_off, scale, causal,
        (m, l, acc),
    )
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)


def make_ring_attention(mesh: Mesh, seq_axis: str = "seq", causal: bool = False):
    """
    Build a jittable ``f(q, k, v) -> out`` over (batch_heads, T, Dh) arrays
    whose sequence axis is sharded over ``mesh`` axis ``seq_axis``.

    T must be divisible by the mesh axis size. The output carries the same
    sequence sharding as the inputs.
    """
    spec = P(None, seq_axis, None)
    local = functools.partial(
        _ring_attention_local, axis_name=seq_axis, causal=causal
    )
    fn = shard_map(
        lambda q, k, v: local(q, k, v),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return jax.jit(fn)


def sequence_sharding(mesh: Mesh, seq_axis: str = "seq") -> NamedSharding:
    """Sharding that splits the time axis of (BH, T, Dh) over the mesh."""
    return NamedSharding(mesh, P(None, seq_axis, None))
