"""
Expert parallelism: shard MoE expert weights over an ``expert`` mesh axis.

Fifth and last scaling axis (machines/dp, ring/sp, TP, PP — SURVEY §2: the
reference's only axis is more pods). A :class:`~gordo_tpu.models.spec.MoEBlock`
holds E experts stacked on a leading parameter axis; with
``expert_parallel: N`` that axis shards over N chips — each chip stores and
runs E/N experts, so expert memory AND routed-FFN compute scale with the
mesh while the attention/router weights stay replicated.

TPU-first mechanics: tokens are replicated and the router's top-1
assignment is computed identically on every chip (same cumsum positions,
same capacity drops — bit-identical to the single-device path). Each chip
scatters only the tokens routed to ITS experts into its local capacity
buffer, runs one batched einsum on the MXU, and the gate-weighted outputs
combine with a single ``psum`` over ICI. No all_to_all is needed because
the token axis is not sharded here (the fleet dimension is how this
framework scales batch); the communication cost is one (tokens, d_model)
all-reduce per block.

The routing math itself lives in :func:`gordo_tpu.ops.nn.moe_dispatch_ffn`
— one definition shared with the single-device path, so the two cannot
drift. Like ring/TP/PP, EP specs keep off both vmap paths.
"""

import functools
import logging

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from gordo_tpu.models.spec import ModelSpec, MoEBlock

logger = logging.getLogger(__name__)

AXIS = "expert"


def ep_degree(spec) -> int:
    """The spec's expert-shard count (0/1 = off); pickle-tolerant."""
    return int(getattr(spec, "expert_parallel", 0) or 0)


def prepare_ep_spec(spec: ModelSpec) -> ModelSpec:
    """Validate an expert-parallel spec at build time."""
    ep = ep_degree(spec)
    if ep <= 1:
        return spec
    for other in ("tensor_parallel", "pipeline_parallel"):
        if int(getattr(spec, other, 0) or 0) > 1:
            raise ValueError(
                f"expert_parallel and {other} cannot combine on one spec "
                f"yet — pick one mesh axis per model"
            )
    moe = [l for l in spec.layers if isinstance(l, MoEBlock)]
    if not moe:
        raise ValueError(
            f"expert_parallel={ep} requires MoEBlock layers; "
            f"got {[type(l).__name__ for l in spec.layers]}"
        )
    for layer in moe:
        if layer.num_experts % ep:
            raise ValueError(
                f"expert_parallel={ep} needs num_experts divisible by the "
                f"shard count, got num_experts={layer.num_experts}"
            )
    return spec


def ep_mesh(n_shards: int) -> Mesh:
    """A 1-D ``expert`` mesh over the first ``n_shards`` addressable devices
    (shared builder: parallel/mesh.axis_mesh)."""
    from .mesh import axis_mesh

    return axis_mesh(AXIS, n_shards, "expert_parallel")


def ep_shardings(spec: ModelSpec, params, mesh: Mesh):
    """Per-leaf shardings: expert FFN weights (leading expert axis) shard
    over the ``expert`` mesh axis; router, attention and every other layer
    replicate."""
    repl = NamedSharding(mesh, P())
    shard = NamedSharding(mesh, P(AXIS))
    shardings = jax.tree_util.tree_map(lambda _: repl, params)
    for i, layer in enumerate(spec.layers):
        if isinstance(layer, MoEBlock):
            layer_shardings = dict(shardings[i])
            for key in ("w1", "b1", "w2", "b2"):
                layer_shardings[key] = shard
            shardings[i] = layer_shardings
    return shardings


def shard_params_ep(spec: ModelSpec, params, strict: bool = True):
    """Commit expert weights to the ``expert`` mesh (no-op when EP is off).

    After this each chip STORES E/N experts — params, grads and optimizer
    state all inherit the sharding through the jitted step — instead of
    holding the full pytree and paying a reshard per call.

    ``strict=False`` (serving) degrades to unsharded params when the host
    has fewer chips than the shard count; the single-device dispatch in
    :func:`apply_ep_moe_block` then runs all experts locally. Training
    keeps ``strict=True`` because EP is a capacity claim there.
    """
    ep = ep_degree(spec)
    if ep <= 1:
        return params
    try:
        mesh = ep_mesh(ep)
    except ValueError as exc:
        if strict:
            raise
        logger.warning(
            "expert_parallel=%d model degrading to all-local experts: %s",
            ep, exc,
        )
        return params
    return jax.device_put(params, ep_shardings(spec, params, mesh))


@functools.lru_cache(maxsize=32)
def _ep_ffn_fn(layer: MoEBlock, n_shards: int):
    """shard_map'd routed FFN: expert weights sharded, tokens replicated,
    one psum combines the per-shard contributions."""
    from jax import shard_map

    from gordo_tpu.ops.nn import moe_dispatch_ffn

    mesh = ep_mesh(n_shards)
    n_local = layer.num_experts // n_shards

    def local_ffn(expert_w, flat, gates):
        offset = jax.lax.axis_index(AXIS) * n_local
        out = moe_dispatch_ffn(layer, expert_w, flat, gates, offset, n_local)
        return jax.lax.psum(out, AXIS)

    return shard_map(
        local_ffn,
        mesh=mesh,
        in_specs=(P(AXIS), P(), P()),
        out_specs=P(),
        check_vma=False,
    )


def apply_ep_moe_block(spec: ModelSpec, layer: MoEBlock, p, x, return_aux=False):
    """Apply one MoE block with its experts sharded over the mesh.

    Degrades to the single-device all-experts dispatch when this host has
    fewer chips than the shard count (an EP-trained artifact serving on a
    small host) — routing math is shared, so outputs are identical."""
    from gordo_tpu.ops.nn import _apply_moe_block

    ep = ep_degree(spec)
    if ep > len(jax.local_devices()):
        logger.warning(
            "expert_parallel=%d but only %d addressable device(s); "
            "dispatching all experts locally",
            ep, len(jax.local_devices()),
        )
        return _apply_moe_block(layer, p, x, return_aux=return_aux)

    fn = _ep_ffn_fn(layer, ep)

    def ffn(layer_, expert_w, flat, gates):
        return fn(expert_w, flat, gates)

    return _apply_moe_block(layer, p, x, ffn_fn=ffn, return_aux=return_aux)
