"""
Pipeline parallelism: stream microbatches through stage-sharded blocks.

Fourth scaling axis (after the machine-sharded fleet, ring attention, and
tensor parallelism; the reference scales only by adding pods — SURVEY §2).
A Transformer's ``num_blocks`` identical encoder blocks are split into
``pipeline_parallel`` contiguous stages, one stage per chip of a ``pipe``
mesh axis; the batch is cut into microbatches that stream through the
stages GPipe-style, so all chips compute concurrently once the pipe fills
(S-1 bubble ticks out of M+S-1 total).

TPU-first mechanics: the whole schedule is ONE ``lax.scan`` inside ONE
``shard_map`` — no host round-trips, no per-tick dispatch. Activations hop
stages via ``jax.lax.ppermute`` over ICI, and the scan carry holds only one
microbatch per stage, so the schedule is compiler-visible and the backward
pass (ppermute transposes to the reverse hop) rematerializes cleanly.

Homogeneity is what makes this expressible as SPMD: every stage holds the
same pytree *shapes* (k = num_blocks/S blocks each), so stage params stack
on a leading axis sharded over ``pipe``. That is also why this module
pipelines the Transformer families only — heterogeneous layer runs
(Dense/LSTM zoo) have no stackable stage axis. Head/tail layers (input
projection, positional encoding, pool, output head) are tiny and run
replicated outside the pipeline.

Scaling honesty: this axis scales COMPUTE, not parameter HBM. Params and
optimizer state are stored replicated (the per-layer-dict pytree has no
persistent stage axis); the stack-and-shard happens per call, so each step
pays one small relayout. For capacity scaling of weights use
tensor_parallel (stored NamedShardings) or expert_parallel (expert weights
stored sharded); the pipeline's win is keeping all chips busy on depth.

Like ring attention and TP, pipelined specs are guarded off the
vmap-over-machines/models paths: the pipe claims the mesh for one model.
"""

import functools
import logging
from dataclasses import replace
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from gordo_tpu.models.spec import ModelSpec, TransformerBlock

logger = logging.getLogger(__name__)

AXIS = "pipe"


def pp_degree(spec) -> int:
    """The spec's pipeline-stage count (0/1 = off); pickle-tolerant."""
    return int(getattr(spec, "pipeline_parallel", 0) or 0)


def prepare_pp_spec(spec: ModelSpec) -> ModelSpec:
    """Validate a pipelined spec; pin attention to the shard_map-safe impl.

    Requirements: a single contiguous run of *identical* TransformerBlocks
    whose count divides into the stage count; no tensor parallelism on the
    same spec (one mesh axis per model for now).
    """
    pp = pp_degree(spec)
    if pp <= 1:
        return spec
    if int(getattr(spec, "tensor_parallel", 0) or 0) > 1:
        raise ValueError(
            "pipeline_parallel and tensor_parallel cannot combine on one "
            "spec yet — pick one mesh axis per model"
        )
    blocks = [l for l in spec.layers if isinstance(l, TransformerBlock)]
    if not blocks:
        raise ValueError(
            f"pipeline_parallel={pp} requires TransformerBlock layers; "
            f"got {[type(l).__name__ for l in spec.layers]}"
        )
    if len(blocks) % pp:
        raise ValueError(
            f"pipeline_parallel={pp} needs num_blocks divisible by the "
            f"stage count, got num_blocks={len(blocks)}"
        )
    first = blocks[0]
    layers = []
    run_started = run_ended = False
    for layer in spec.layers:
        if not isinstance(layer, TransformerBlock):
            if run_started:
                run_ended = True
            layers.append(layer)
            continue
        if run_ended:
            raise ValueError(
                "pipeline_parallel requires one contiguous run of "
                "TransformerBlocks"
            )
        run_started = True
        if layer.attention_impl in ("flash", "ring"):
            raise ValueError(
                f"attention={layer.attention_impl!r} cannot run inside the "
                f"pipeline's shard_map; use attention='xla' (or 'auto') "
                f"with pipeline_parallel"
            )
        pinned = replace(layer, attention_impl="xla")
        if pinned != replace(first, attention_impl="xla"):
            raise ValueError(
                "pipeline_parallel requires identical TransformerBlocks "
                "(stages must hold same-shaped params)"
            )
        layers.append(pinned)
    return replace(spec, layers=tuple(layers))


def pp_mesh(n_stages: int) -> Mesh:
    """A 1-D ``pipe`` mesh over the first ``n_stages`` addressable devices
    (shared builder: parallel/mesh.axis_mesh)."""
    from .mesh import axis_mesh

    return axis_mesh(AXIS, n_stages, "pipeline_parallel")


@functools.lru_cache(maxsize=32)
def make_pipeline_blocks_fn(
    layer: TransformerBlock,
    n_stages: int,
    blocks_per_stage: int,
    n_microbatches: int,
    remat: bool = False,
):
    """Build ``fn(stacked_params, x) -> y`` running S×k identical blocks as
    a GPipe pipeline over the ``pipe`` mesh axis.

    ``stacked_params``: block params stacked to leaves of shape
    ``(n_stages, blocks_per_stage, ...)``, sharded on axis 0.
    ``x``: (B, T, D) replicated, B divisible by ``n_microbatches``.
    Returns (B, T, D), replicated, numerically equal to applying the
    blocks sequentially (up to reduction order).
    """
    from jax import shard_map

    from gordo_tpu.ops.nn import _apply_transformer_block

    mesh = pp_mesh(n_stages)
    S, M = n_stages, n_microbatches

    def stage_apply(stage_params, act):
        # one stage = blocks_per_stage sequential blocks; under remat each
        # block recomputes its activations on the backward pass, same as
        # the non-pipelined path's jax.checkpoint per block
        def body(a, p):
            apply = functools.partial(_apply_transformer_block, layer)
            if remat:
                apply = jax.checkpoint(apply)
            return apply(p, a), None

        out, _ = jax.lax.scan(body, act, stage_params)
        return out

    def pipelined(stacked_params, x):
        # inside shard_map: params (1, k, ...) -> (k, ...); x replicated
        stage_params = jax.tree_util.tree_map(lambda a: a[0], stacked_params)
        stage = jax.lax.axis_index(AXIS)
        b_total, t_len, d = x.shape
        mb = b_total // M
        x_mb = x.reshape(M, mb, t_len, d)

        perm = [(i, i + 1) for i in range(S - 1)]

        def tick(carry, t):
            act, out_buf = carry
            # stage 0 ingests microbatch t (clamped; masked by validity
            # downstream via the drain schedule), others take the hop
            mb_in = jax.lax.dynamic_index_in_dim(
                x_mb, jnp.clip(t, 0, M - 1), keepdims=False
            )
            act = jnp.where(stage == 0, mb_in, act)
            act = stage_apply(stage_params, act)
            # last stage drains microbatch t-(S-1) once the pipe is full
            out_idx = jnp.clip(t - (S - 1), 0, M - 1)
            drain = jnp.logical_and(stage == S - 1, t >= S - 1)
            out_buf = jnp.where(
                drain,
                jax.lax.dynamic_update_index_in_dim(
                    out_buf, act, out_idx, axis=0
                ),
                out_buf,
            )
            # hop activations to the next stage for the next tick
            if perm:
                act = jax.lax.ppermute(act, AXIS, perm)
            return (act, out_buf), None

        act0 = jnp.zeros((mb, t_len, d), x.dtype)
        out0 = jnp.zeros_like(x_mb)
        (_, out_buf), _ = jax.lax.scan(
            tick, (act0, out0), jnp.arange(M + S - 1)
        )
        # only the last stage's buffer is real; replicate it to all stages
        out_buf = jax.lax.psum(
            jnp.where(stage == S - 1, out_buf, jnp.zeros_like(out_buf)), AXIS
        )
        return out_buf.reshape(b_total, t_len, d)

    return shard_map(
        pipelined,
        mesh=mesh,
        in_specs=(P(AXIS), P()),
        out_specs=P(),
        check_vma=False,
    )


def apply_pipelined_blocks(spec: ModelSpec, layer: TransformerBlock,
                           block_params: list, x: jnp.ndarray) -> jnp.ndarray:
    """Run a spec's contiguous TransformerBlock run through the pipeline.

    Falls back to the sequential loop when the batch cannot be cut into
    the stage count's microbatches (e.g. odd predict remainders) or when
    this host has fewer chips than the stage count (a PP-trained artifact
    serving on a small host) — the math is identical either way, only the
    schedule changes.
    """
    from gordo_tpu.ops.nn import _apply_transformer_block

    pp = pp_degree(spec)
    remat = bool(getattr(spec, "remat", False))
    n_blocks = len(block_params)
    n_micro = pp  # M = S keeps the bubble at 50% worst case, 0 host knobs
    mesh_available = pp <= len(jax.local_devices())
    if not mesh_available:
        logger.warning(
            "pipeline_parallel=%d but only %d addressable device(s); "
            "running the sequential block loop",
            pp, len(jax.local_devices()),
        )
    if not mesh_available or x.shape[0] % n_micro:
        for p in block_params:
            apply = functools.partial(_apply_transformer_block, layer)
            if remat:
                apply = jax.checkpoint(apply)
            x = apply(p, x)
        return x
    stacked = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves).reshape(
            (pp, n_blocks // pp) + leaves[0].shape
        ),
        *block_params,
    )
    fn = make_pipeline_blocks_fn(layer, pp, n_blocks // pp, n_micro, remat)
    return fn(stacked, x)
