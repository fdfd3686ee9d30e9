"""
Multi-head scaled-dot-product attention with pluggable implementations.

The reference has no attention models at all (SURVEY §5: "long-context /
sequence parallelism: absent") — this op underpins the *new-capability*
Transformer model family (BASELINE.json stretch config) and is written
TPU-first:

- ``impl="xla"``: plain jnp einsum formulation — XLA fuses softmax into the
  two MXU matmuls; this is the reference implementation and CPU/test path.
- ``impl="flash"``: Pallas TPU kernel (blockwise online-softmax, O(T) memory;
  see :mod:`gordo_tpu.ops.pallas_kernels.flash_attention`). Compiled by
  Mosaic: on a backend without it the call raises.
- ``impl="auto"``: flash on a TPU for the shapes ``_flash_ok`` admits (those
  proven to compile on the chip), else xla.

Sequence-parallel exact attention for windows too long for one chip (ring
attention over a mesh axis via shard_map + ppermute) lives in
:mod:`gordo_tpu.parallel.ring_attention`; it shares this module's blockwise
online-softmax math.
"""

import os

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _default_impl() -> str:
    return os.environ.get("GORDO_TPU_ATTENTION_IMPL", "auto")


def split_heads(x: jnp.ndarray, num_heads: int) -> jnp.ndarray:
    """(B, T, D) -> (B, H, T, D//H)"""
    b, t, d = x.shape
    if d % num_heads:
        raise ValueError(f"model dim {d} not divisible by num_heads {num_heads}")
    return x.reshape(b, t, num_heads, d // num_heads).transpose(0, 2, 1, 3)


def merge_heads(x: jnp.ndarray) -> jnp.ndarray:
    """(B, H, T, Dh) -> (B, T, H*Dh)"""
    b, h, t, dh = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, h * dh)


def dot_product_attention_xla(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, causal: bool = False,
    scale: float = None,
) -> jnp.ndarray:
    """
    Reference attention. q, k: (..., T, Dh) and v: (..., T, Dv) with any
    leading batch dims; the values' width need not be the queries'. ``scale``
    multiplies the scores: ``Dh ** -0.5`` unless given.

    Softmax is computed in float32 regardless of input dtype (bfloat16-safe),
    matching the flash kernel's accumulator precision.
    """
    dh = q.shape[-1]
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.asarray(dh, jnp.float32))
    logits = jnp.einsum("...qd,...kd->...qk", q, k).astype(jnp.float32) * scale
    if causal:
        t_q, t_k = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((t_q, t_k), bool), t_k - t_q)
        logits = jnp.where(mask, logits, NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("...qk,...kd->...qd", weights, v)


import functools


@functools.lru_cache(maxsize=8)
def _ring_fn(n_devices: int, causal: bool):
    """Jitted ring attention over all LOCAL devices on a cached 'seq' mesh.

    Local, not global, like every other per-model axis (parallel/mesh.py
    axis_mesh): in a multi-process fleet a ring-spec machine is owned by
    one process on the serial-fallback path, and a shard_map over other
    hosts' non-addressable chips would fail at runtime."""
    from jax.sharding import Mesh

    from gordo_tpu.parallel.ring_attention import make_ring_attention

    mesh = Mesh(jax.local_devices()[:n_devices], ("seq",))
    return make_ring_attention(mesh, seq_axis="seq", causal=causal)


def _ring_ok(q: jnp.ndarray, k: jnp.ndarray) -> bool:
    """Whether ring attention can run: self-attention, >1 device, divisible T."""
    n = len(jax.local_devices())
    t = q.shape[-2]
    return n > 1 and k.shape[-2] == t and t % n == 0


def ring_attention(q, k, v, causal: bool = False) -> jnp.ndarray:
    """
    Sequence-parallel exact attention: the time axis is sharded over all
    LOCAL devices and K/V blocks circulate the ring
    (parallel/ring_attention.py). q, k, v: (..., T, Dh). T must divide by
    the local device count.
    """
    n = len(jax.local_devices())
    t, dh = q.shape[-2], q.shape[-1]
    if n == 1:
        # a 1-device ring is plain attention; lets ring-configured models
        # serve on a single chip unchanged
        return dot_product_attention_xla(q, k, v, causal=causal)
    if not _ring_ok(q, k):
        raise ValueError(
            f"ring attention needs self-attention with T divisible by the "
            f"device count (T={t}, devices={n}, k_len={k.shape[-2]})"
        )
    lead = q.shape[:-2]
    fn = _ring_fn(n, causal)
    out = fn(
        q.reshape((-1, t, dh)), k.reshape((-1, t, dh)), v.reshape((-1, t, dh))
    )
    return out.reshape(lead + (t, dh))


def spec_may_use_ring(spec) -> bool:
    """Whether a ModelSpec's attention could resolve to the ring impl —
    declared explicitly, forced via $GORDO_TPU_ATTENTION_IMPL, or reachable
    through the opt-in auto-ring threshold. Ring is shard_map over the whole
    mesh, so any vmapping caller (the fleet trainer's vmap-over-machines,
    the serving batcher's vmap-over-models) must route such specs to its
    non-vmapped path."""
    impls = {
        getattr(layer, "attention_impl", None)
        for layer in getattr(spec, "layers", ())
        if hasattr(layer, "attention_impl")
    }
    if not impls:
        return False
    if "ring" in impls:
        return True
    if os.environ.get("GORDO_TPU_ATTENTION_IMPL") == "ring" and "auto" in impls:
        return True
    threshold = os.environ.get("GORDO_TPU_RING_THRESHOLD")
    return (
        threshold is not None
        and "auto" in impls
        and spec.lookback_window >= int(threshold)
    )


def _flash_ok(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray = None, scale: float = None
) -> bool:
    """
    Whether ``auto`` sends these shapes to the Pallas flash kernel: on a TPU,
    self-attention (equal Q/K lengths), T a multiple of the kernel's 128-row
    blocks between 256 and 4096, and one head dim of 64 or 128 for q, k and v.

    Those are the (T, head dim) corners ``chip_smoke.py`` compiles and
    compares on the chip, forward and backward, f32 and bf16. The head dim is
    the kernel's lane dimension and decides how Mosaic tiles every block, so
    only the two widths that were compiled are admitted (72..120 were never
    tried). T only sets the grid size, the loop trip counts and the dK/dV
    kernel's whole-sequence staging, which grows with T and was compiled at
    both ends. What a v5e showed (chip run, PR 21): head dims 16, 32, 64, 128
    and 256 and T 128, 256, 512, 1024, 4096 and 8192 compile, except
    T 8192 x dh 128 in f32, where the staging of q, dO, O and the
    lane-replicated lse runs out of the 16 MiB scoped VMEM by 128 KiB — so the
    upper bounds stay a factor of two inside what fits. The lower bounds are
    not compile limits: whether the kernel beats XLA below 256 rows or with
    heads narrower than 64 lanes is not measured, and until a benchmark cell
    decides it those shapes stay on the XLA path. Longer sequences belong to
    ring attention (parallel/ring_attention.py).

    Heads whose queries and keys are wider than their values (latent
    attention: 192 / 128, with a softmax scale of its own) stay on the XLA
    path: the kernel takes one width and the default scale, and
    ``impl="flash"`` refuses anything else. A kernel widened to the two widths
    was compiled and compared at (T 256, 192 / 128) on the chip and lost: 3.40
    ms forward + backward for 16 windows x 32 heads in bfloat16 against the XLA
    path's 1.81 ms, 3.46 ms with q and k zero-padded to 256 lanes (chip run,
    PR 37, PERF.md section 6). The loser was taken out again.
    """
    if jax.default_backend() != "tpu":
        return False
    t, dh = q.shape[-2], q.shape[-1]
    return (
        k.shape[-2] == t
        and t % 128 == 0
        and 256 <= t <= 4096
        and dh in (64, 128)
        and (v is None or v.shape[-1] == dh)
        and scale is None
    )


def dot_product_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = False,
    impl: str = None,
    scale: float = None,
) -> jnp.ndarray:
    """
    Dispatching attention over q, k: (..., T, Dh) and v: (..., T, Dv)
    tensors; ``scale`` multiplies the scores (``Dh ** -0.5`` unless given).

    Deliberately not jitted at this level: the impl choice (including the
    ``GORDO_TPU_ATTENTION_IMPL`` env override) must be re-read per call, not
    baked into a jit cache; callers jit the surrounding model anyway.
    """
    impl = impl or _default_impl()
    if impl == "auto":
        # opt-in auto-ring: past $GORDO_TPU_RING_THRESHOLD rows the window is
        # taken to exceed one chip and the sequence goes over the mesh. Kept
        # opt-in because ring (shard_map) cannot run under the fleet
        # trainer's vmap-over-machines.
        ring_threshold = os.environ.get("GORDO_TPU_RING_THRESHOLD")
        if (
            ring_threshold is not None
            and q.shape[-2] >= int(ring_threshold)
            and _ring_ok(q, k)
        ):
            impl = "ring"
        else:
            impl = "flash" if _flash_ok(q, k, v, scale) else "xla"
    if impl in ("ring", "flash") and (
        scale is not None or v.shape[-1] != q.shape[-1]
    ):
        raise ValueError(
            f"{impl} attention takes one head width and the default scale"
        )
    if impl == "ring":
        return ring_attention(q, k, v, causal=causal)
    if impl == "flash":
        from gordo_tpu.ops.pallas_kernels.flash_attention import flash_attention

        return flash_attention(q, k, v, causal=causal)
    if impl == "xla":
        return dot_product_attention_xla(q, k, v, causal=causal, scale=scale)
    raise ValueError(f"Unknown attention impl {impl!r}")


def multihead_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    num_heads: int,
    causal: bool = False,
    impl: str = None,
) -> jnp.ndarray:
    """
    Multi-head attention over (B, T, D) tensors (projections applied by the
    caller). Returns (B, T, D).
    """
    qh = split_heads(q, num_heads)
    kh = split_heads(k, num_heads)
    vh = split_heads(v, num_heads)
    out = dot_product_attention(qh, kh, vh, causal=causal, impl=impl)
    return merge_heads(out)
