"""
BatchedModelBuilder: train N machines as ONE XLA program.

The reference trains each machine in its own k8s pod (Argo DAG,
argo-workflow.yml.template:1511-1525; ~1 CPU + 3.9GB per pod,
normalized_config.py:77-83). Here machines with identical architecture
(same ModelSpec) and data shape are *bucketed*, their data stacked on a
leading machine axis, and the full per-machine build — per-fold CV training,
fold predictions, final fit, input scaling — runs as a single
``vmap``-over-machines program, jitted with the machine axis sharded over the
device mesh. Each chip trains its shard of machines; there is no
inter-machine communication, so scaling is linear in chips.

Numerical parity notes:
- CV fold boundaries come from sklearn's TimeSeriesSplit on host, so fold
  slicing matches the serial path exactly.
- MinMaxScaler semantics are computed in-program per fold (min/max over the
  fold's train slice), matching Pipeline(MinMaxScaler, model).fit on a fold.
- Threshold math (rolling(6).min().max() etc., reference diff.py:184-276)
  runs on host over the fold predictions using the same code paths as the
  serial DiffBasedAnomalyDetector.
- RNG streams differ from the serial path (which draws from numpy's global
  RNG); results are deterministic given the machine's evaluation.seed.

Machines whose model config the planner cannot express (arbitrary sklearn
steps, custom estimators) fall back to the serial ModelBuilder — capability
is never lost, only speed.

The pipeline of one ``build()``, in the order of this file:

1. **plan** (``_plan_fleet``, once for both orchestrators): the resume
   prefilter returns what the registry already holds, ``_plan_machine``
   plans what can be batched, the rest is listed for the serial builder.
   Who owns a cache hit is the one thing the orchestrators decide: the hash
   partition (``_build_all``) or a claim (``_build_all_elastic``).
2. **fetch stream** (``_fetch_stage``): every planned machine's data is
   fetched on a pool, in the order the chunks will consume it; the stage
   ends when the first chunk's machines have arrived. A build that must
   agree on membership before a launch (several processes, elastic hosts)
   waits the stream out and takes every outcome first (``_fetch_all``).
3. **buckets** (``_Plan.bucket_key``): machines of one compiled program,
   grouped by what is known of them — everything but the row count and
   warm/cold before their data is there, the full key after.
4. **chunk loop** (``_build_bucket``): the fold geometry
   (``_fold_geometry``) and the program (``_program_for`` →
   ``_bucket_program``) from the bucket's first arrival, then fixed-width
   chunks, two in flight; a later chunk waits for its own fetches just
   before it is stacked. Behind each chunk's program goes the verdict on
   its parameters (``_verdict_program``: one bool a lane, on the device).
   ``_build_bucket_guarded`` is the recovery ladder round it.
5. **assembly** (``_assemble_and_persist``, on a pool, chunk by chunk under
   the device): a machine's slice of the chunk's outputs and its divergence
   check (the losses here, the parameters by the device's verdict),
   thresholds, scores, metadata with the chunk's own durations, the
   artifact's dump and its registry keys. A machine is final when its job
   returns: the bucket's end adds nothing to any artifact.
"""

import datetime
import functools
from concurrent.futures import Future, ThreadPoolExecutor
from concurrent.futures import wait as wait_for_futures
import logging
import os
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
from sklearn.model_selection import KFold, TimeSeriesSplit
from sklearn.pipeline import Pipeline
from sklearn.preprocessing import MinMaxScaler

from gordo_tpu import __version__, serializer
from gordo_tpu.builder.build_model import ModelBuilder
from gordo_tpu.serializer import programs
from gordo_tpu.dataset import GordoBaseDataset
from gordo_tpu.machine import Machine
from gordo_tpu.machine.metadata import (
    BuildMetadata,
    CrossValidationMetaData,
    DatasetBuildMetadata,
    ModelBuildMetadata,
)
from gordo_tpu.models.anomaly.diff import (
    DiffBasedAnomalyDetector,
    DiffBasedKFCVAnomalyDetector,
)
from gordo_tpu.models.models import BaseJaxEstimator
from gordo_tpu.models.spec import ModelSpec
from gordo_tpu.ops.nn import apply_model, init_model_params, zero_stats
from gordo_tpu.ops.train import (
    make_masked_epoch_fn,
    make_optimizer,
    n_train_samples,
)
from gordo_tpu.observability import metrics as metric_catalog
from gordo_tpu.observability import telemetry, tracing
from gordo_tpu.util import faults
from gordo_tpu.util.faults import FaultPolicy, QuarantineRecord
from .mesh import default_mesh, machines_sharding

logger = logging.getLogger(__name__)

# phase-histogram children resolved once (spans observe these on exit;
# .labels() takes the metric lock per call)
_PHASE_FETCH = metric_catalog.BUILD_PHASE_SECONDS.labels(phase="fetch")
_PHASE_VALIDATE = metric_catalog.BUILD_PHASE_SECONDS.labels(phase="validate")
_PHASE_COMPILE = metric_catalog.BUILD_PHASE_SECONDS.labels(phase="compile")
_PHASE_TRAIN = metric_catalog.BUILD_PHASE_SECONDS.labels(phase="train")
_PHASE_SERIALIZE = metric_catalog.BUILD_PHASE_SECONDS.labels(phase="serialize")
_PHASE_ASSEMBLE = metric_catalog.BUILD_PHASE_SECONDS.labels(phase="assemble")
# the stages of the thread that runs build(): contiguous, non-overlapping
# walls that tile it (compile, train and tail are parents), where the six
# above are per machine on pool threads or bucket walls. Each is a span and
# a phase label of its own name (docs/observability.md has the table)
_STAGE_SECONDS = {
    name: metric_catalog.BUILD_PHASE_SECONDS.labels(phase=name)
    for name in (
        "plan", "fetch_stage", "validate_stage", "bucket_prep", "fetch_wait",
        "stack_h2d", "launch", "wait", "d2h", "slice", "tail", "drain",
    )
}


def _stage(name: str, **attrs):
    """One stage of the build thread as a span that observes its wall into
    ``gordo_build_phase_seconds{phase=<name>}``."""
    return telemetry.span(name, _STAGE_SECONDS[name], **attrs)


# first-compile wall per bucket-program cache key: a later cache hit credits
# this wall to the compile-seconds-saved counter (the measured wall includes
# trace+lower+compile+first chunk dispatch — jit compiles synchronously on
# the first call, execution is dispatched async, so it is compile-dominated)
_first_compile_walls: Dict[Tuple, float] = {}


def _machine_trace(name: str):
    """A fresh trace root per machine (memoized by name): every span one
    machine emits — fetch, validate, assemble, serialize, across phases
    and thread-pool lanes — shares one trace_id in the exported Chrome
    trace, so Perfetto's args filter isolates a single machine out of a
    fleet build. Null when spans are dormant: the disabled build path
    must keep allocating nothing."""
    import contextlib

    if not telemetry.spans_enabled():
        return contextlib.nullcontext()
    return tracing.attach(tracing.root_for(name))


def _machine_seed(machine: Machine) -> int:
    """Combine evaluation.seed with the machine name into one RNG stream id."""
    import zlib

    seed = int(machine.evaluation.get("seed", 0))
    return (zlib.crc32(machine.name.encode()) ^ (seed * 2654435761)) & 0xFFFFFFFF


# ------------------------------------------------------------------ planning
@dataclass
class _Plan:
    machine: Machine
    estimator_cls: type
    estimator_params: dict
    spec: ModelSpec
    scale_x: bool
    wrap_anomaly: bool
    kfcv: bool = False
    anomaly_kwargs: Dict[str, Any] = field(default_factory=dict)
    epochs: int = 1
    batch_size: int = 32
    shuffle: bool = True
    n_splits: int = 3
    # fold geometry: ("tss", n_splits) or ("kfold", n_splits, shuffle, seed)
    cv: Tuple = ("tss", 3)
    # filled during data load
    X: Optional[np.ndarray] = None
    y: Optional[np.ndarray] = None
    index: Optional[pd.DatetimeIndex] = None
    columns: Optional[List[str]] = None
    target_columns: Optional[List[str]] = None
    query_duration: float = 0.0
    dataset_meta: Dict[str, Any] = field(default_factory=dict)
    # how many data-fetch attempts it took (>1 = transient faults absorbed;
    # recorded in BuildMetadata.fault_domain for observability)
    fetch_attempts: int = 1
    # warm-start delta rebuild: the prior artifact's trained params, used as
    # init in place of init_model_params when only the machine's data
    # drifted (same spec/config — the warm registry key matched)
    warm_params: Optional[Any] = None
    # the machine's fetch in flight on a pool thread (load, non-finite
    # check, warm params); its result is the machine's quarantine record,
    # or None. The build thread takes it once (``_arrived``) and clears it
    fetch: Optional[Future] = None

    def bucket_key(self) -> Tuple:
        """What machines of one compiled program share. Before the machine's
        data is there the key leaves out what only the data tells (the row
        count, warm or cold): by that partial key machines are ordered and
        grouped while their fetches are in flight."""
        key = (
            self.spec,
            self.epochs,
            self.batch_size,
            self.shuffle,
            self.scale_x,
            self.n_splits,
            self.cv,
        )
        if self.X is None:
            return key
        # warm and cold machines cannot share a program (different argument
        # structure), so they bucket separately
        return key + (len(self.X), self.warm_params is not None)


def _plan_machine(machine: Machine) -> Optional[_Plan]:
    """Introspect the machine's model definition into a batchable plan."""
    # only the default cv_mode is batchable; cross_val_only / no-CV modes
    # have different output contracts and take the serial path
    if machine.evaluation.get("cv_mode", "full_build") != "full_build":
        return None
    # all requested metrics must be expressible by the vectorized scorer,
    # otherwise scores would be silently dropped — serial path instead
    for m in machine.evaluation.get("metrics") or []:
        if m.rsplit(".", 1)[-1] not in _METRIC_NAMES:
            return None
    try:
        model = serializer.from_definition(machine.model)
    except Exception:
        return None

    wrap_anomaly = isinstance(model, DiffBasedAnomalyDetector)
    kfcv = isinstance(model, DiffBasedKFCVAnomalyDetector)
    anomaly_kwargs: Dict[str, Any] = {}
    inner = model
    if wrap_anomaly:
        anomaly_kwargs = {
            "require_thresholds": model.require_thresholds,
            "window": model.window,
            "smoothing_method": model.smoothing_method,
            "shuffle": model.shuffle,
        }
        if kfcv:
            # under the builder the fold geometry comes from evaluation.cv
            # (TimeSeriesSplit(3) by default — both builders pass cv= into
            # the detector, overriding its standalone KFold(5) default:
            # reference build_model.py:233-243) even for the KFCV detector,
            # so the contiguous-fold program applies; a configured seeded
            # KFold instead runs through per-stage permutations (see the
            # cv-config block below). Only the threshold assembly
            # (percentile of the smoothed validation-error series) differs.
            # The detector-level pre-fit shuffle is subsumed by the
            # in-program batch shuffling — an RNG-stream difference, like the
            # batched path's seeds (module docstring).
            if type(model) is not DiffBasedKFCVAnomalyDetector:
                return None
            anomaly_kwargs["threshold_percentile"] = model.threshold_percentile
        else:
            if type(model) is not DiffBasedAnomalyDetector:
                return None  # unknown subclass: serial fallback
            if model.shuffle:
                return None  # pre-shuffled fit: serial fallback
        if not isinstance(model.scaler, MinMaxScaler):
            return None
        if tuple(getattr(model.scaler, "feature_range", (0, 1))) != (0, 1):
            # the threshold mirrors scale by raw 1/(max-min); a non-default
            # feature_range would diverge from the serial scaler's span
            return None
        inner = model.base_estimator

    scale_x = False
    if isinstance(inner, Pipeline):
        if len(inner.steps) == 2 and isinstance(inner.steps[0][1], MinMaxScaler):
            if tuple(
                getattr(inner.steps[0][1], "feature_range", (0, 1))
            ) != (0, 1):
                # the program's in-stage scaling hardcodes the default range
                return None
            scale_x = True
            inner = inner.steps[1][1]
        elif len(inner.steps) == 1:
            inner = inner.steps[0][1]
        else:
            return None
    if not isinstance(inner, BaseJaxEstimator):
        return None
    if inner.lookahead is None:
        return None

    # CV config: TimeSeriesSplit is batchable for every plan; a seeded
    # KFold additionally for KFCV plans — the KFCV scatter-percentile
    # threshold math is well-defined for arbitrary fold index sets (the
    # per-fold permutation runs inside the bucket program), while the plain
    # detector's rolling-window thresholds need contiguous folds
    n_splits = 3
    cv_desc: Tuple = ("tss", 3)
    cv_cfg = machine.evaluation.get("cv")
    if cv_cfg is not None:
        try:
            cv_obj = serializer.from_definition(cv_cfg)
        except Exception:
            return None
        if isinstance(cv_obj, TimeSeriesSplit):
            # non-default gap/test_size/max_train_size change fold geometry
            # in ways _fold_bounds does not model — those configs stay serial
            if (
                getattr(cv_obj, "gap", 0) != 0
                or getattr(cv_obj, "test_size", None) is not None
                or getattr(cv_obj, "max_train_size", None) is not None
            ):
                return None
            n_splits = cv_obj.n_splits
            cv_desc = ("tss", n_splits)
        elif isinstance(cv_obj, KFold) and kfcv:
            shuffle_cv = bool(getattr(cv_obj, "shuffle", False))
            seed_cv = getattr(cv_obj, "random_state", None)
            if shuffle_cv and not isinstance(seed_cv, (int, np.integer)):
                # unseeded shuffled folds are irreproducible — the serial
                # path would even disagree with its own split metadata
                return None
            n_splits = cv_obj.n_splits
            cv_desc = (
                "kfold",
                n_splits,
                shuffle_cv,
                int(seed_cv) if seed_cv is not None else None,
            )
        else:
            return None

    fit_args = inner.extract_supported_fit_args(inner.kwargs)
    if fit_args.get("callbacks") or fit_args.get("validation_split"):
        return None  # host-loop features: serial fallback

    tags = [t.name for t in machine.dataset.tag_list]
    n_features = len(tags)
    n_features_out = len(machine.dataset.target_tag_list)
    try:
        spec = inner.build_spec(n_features, n_features_out)
    except Exception:
        return None
    if kfcv and spec.output_offset != 0:
        # windowed KFCV scatter-fill needs aligned prediction rows; the
        # serial path has the same restriction (length-mismatched .iloc set)
        return None
    from gordo_tpu.ops.attention import spec_may_use_ring

    if spec_may_use_ring(spec):
        # ring attention is shard_map over the whole mesh — it cannot run
        # under this builder's vmap-over-machines; serial path owns it
        return None
    from gordo_tpu.parallel.data_parallel import dp_degree
    from gordo_tpu.parallel.expert_parallel import ep_degree
    from gordo_tpu.parallel.pipeline_parallel import pp_degree
    from gordo_tpu.parallel.tensor_parallel import tp_degree

    if (
        tp_degree(spec) > 1
        or pp_degree(spec) > 1
        or ep_degree(spec) > 1
        or dp_degree(spec) > 1
    ):
        # model-axis-sharded params / the pipeline's or expert shard_map /
        # a batch sharded over the data mesh all claim the mesh for ONE
        # machine; the serial path owns such machines
        return None

    return _Plan(
        machine=machine,
        estimator_cls=type(inner),
        estimator_params=inner.get_params(),
        spec=spec,
        scale_x=scale_x,
        wrap_anomaly=wrap_anomaly,
        kfcv=kfcv,
        anomaly_kwargs=anomaly_kwargs,
        epochs=int(fit_args.get("epochs", 1)),
        batch_size=int(fit_args.get("batch_size", 32)),
        shuffle=bool(fit_args.get("shuffle", True)),
        n_splits=n_splits,
        cv=cv_desc,
    )


# ------------------------------------------------------------ the programs
@jax.named_scope("fold_predict")
def _predict_windows(spec: ModelSpec, params, X):
    """Model output over a contiguous slice (windowed for recurrent specs)."""
    if spec.lookback_window <= 1 and spec.lookahead == 0:
        out, _ = apply_model(spec, params, X)
        return out
    n_out = X.shape[0] - spec.lookback_window + 1 - spec.lookahead
    idx = jnp.arange(n_out)
    window = jnp.arange(spec.lookback_window)
    with jax.named_scope("window_gather"):
        xb = X[idx[:, None] + window[None, :]]
    out, _ = apply_model(spec, params, xb)
    return out


def _note_layer_counts(counted: Dict[str, np.ndarray], rows, n_live: int) -> None:
    """Add a chunk's layer counts (a value a lane; padding lanes left out) to
    the build's counters: the routed layers', a latent block's mixing, the
    selective scans' and the reads of another block's state, and the blocks
    run at the last position alone. Nothing to add for a spec that has none
    of them."""
    if not counted:
        return
    live = np.asarray(rows) < n_live
    total = {key: np.asarray(value)[live].sum() for key, value in counted.items()}
    if "moe_tokens" in total:
        metric_catalog.MOE_ASSIGNMENTS.labels(where="held").inc(int(total["moe_held"]))
        metric_catalog.MOE_ASSIGNMENTS.labels(where="absent").inc(int(total["moe_absent"]))
        metric_catalog.MOE_TOKENS.inc(int(total["moe_tokens"]))
        metric_catalog.MOE_LAYER_STEPS.inc(int(total["moe_layer_steps"]))
        metric_catalog.MOE_PEAK_LOAD.inc(int(total["moe_peak_load"]))
    if "hc_sublayer_steps" in total:
        metric_catalog.HC_SUBLAYER_STEPS.inc(int(total["hc_sublayer_steps"]))
        metric_catalog.HC_STOCHASTIC_GAP.inc(float(total["hc_stochastic_gap"]))
        metric_catalog.HC_WRITE_KERNEL_STEPS.inc(int(total["hc_write_kernel_steps"]))
    if "ssm_layer_steps" in total:
        metric_catalog.SSM_LAYER_STEPS.inc(int(total["ssm_layer_steps"]))
        metric_catalog.SSM_POSITIONS.inc(int(total["ssm_positions"]))
    for kind in ("kv", "memory"):
        if f"shared_{kind}_reads" in total:
            metric_catalog.SHARED_STATE_READS.labels(kind=kind).inc(
                int(total[f"shared_{kind}_reads"])
            )
    if "last_position_steps" in total:
        metric_catalog.LAST_POSITION_BLOCK_STEPS.inc(int(total["last_position_steps"]))


_H2D = metric_catalog.TRANSFER_BYTES.labels(direction="h2d")
_D2H = metric_catalog.TRANSFER_BYTES.labels(direction="d2h")


def _nbytes(tree) -> int:
    """Bytes of a tree of host arrays."""
    return sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(tree))


@functools.lru_cache(maxsize=64)
def _bucket_program(
    spec: ModelSpec,
    n_rows: int,
    fold_bounds: Tuple[Tuple[int, int, int], ...],
    epochs: int,
    batch_size: int,
    shuffle: bool,
    scale_x: bool,
    out_sharding=None,
    use_perms: bool = False,
    warm_start: bool = False,
):
    """
    Compile the full per-machine build for one bucket:
    per-fold (scale → init → train → predict-test), then final fit.
    Returns a function of stacked (X, y, seeds) suitable for vmap, producing
    ``(final_params, final_losses, fold_preds, stats)``: one prediction a
    fold, and what the model's layers counted over every stage's live steps
    (``ops/nn.apply_model_stats``; an empty dict for a spec with no routed
    layer).

    The CV folds and the final fit all run through ONE ``lax.scan`` over
    "stages" sharing a single mask-padded fit body
    (ops/train.make_masked_epoch_fn): each stage's live-sample count /
    scaling-row count / test-slice start are traced scan inputs. XLA
    therefore compiles one fit, not folds+1 differently-shaped fits —
    compile time was ~40% of a cold fleet build and scaled with the fold
    count before this.

    ``use_perms``: the program takes a fourth, non-vmapped argument
    ``perms`` of shape (n_folds+1, n_rows) — a per-stage row permutation
    applied to X/y before training (one gather). This is how seeded
    shuffled-KFold geometry runs through the same contiguous-fold machinery:
    each stage's permutation is [train_idx..., test_idx...], so "train
    prefix" and "test tail slice" stay static shapes. The final stage's
    permutation must be the identity.

    ``out_sharding``: force every output's machine axis onto this sharding.
    Required in multi-process mode, where each host reads back only its
    addressable rows — XLA must not replicate outputs.

    ``warm_start``: the program takes a trailing, vmapped pytree argument
    ``warm`` — each machine's prior trained params, used as init in place
    of ``init_model_params`` for every stage (each CV fold and the final
    fit). A delta rebuild whose data merely drifted starts each fit from
    yesterday's optimum instead of a random init.
    """
    # one predict shape serves every fold: TimeSeriesSplit's test slices are
    # all n // (n_splits + 1) rows, and _fold_geometry pads KFold's to the
    # largest fold
    te_lens = {te_end - te_start for _, te_start, te_end in fold_bounds}
    if len(te_lens) != 1:
        raise ValueError(
            f"_bucket_program needs one test length across the folds, got "
            f"{sorted(te_lens)} from fold_bounds={fold_bounds}"
        )
    te_len = te_lens.pop()

    n_full = n_train_samples(spec, n_rows)
    batch_eff = min(batch_size, max(n_full, 1))
    epoch_fn = make_masked_epoch_fn(spec, n_full, batch_eff, shuffle)
    opt = make_optimizer(spec.optimizer)
    n_folds = len(fold_bounds)

    # per-stage traced inputs: folds first, the full fit last
    tr_rows = np.array([tr_end for tr_end, _, _ in fold_bounds] + [n_rows])
    n_valids = np.array(
        [n_train_samples(spec, tr_end) for tr_end, _, _ in fold_bounds] + [n_full]
    )
    te_starts = np.array([te_start for _, te_start, _ in fold_bounds] + [0])

    def one_machine(X, y, seed, *extra):
        # extra: (perms?, warm?) — perms is shared (not vmapped), warm is
        # per-machine (vmapped); order fixed by the in_axes below
        perms = extra[0] if use_perms else None
        warm = extra[len(extra) - 1] if warm_start else None
        rng = jax.random.fold_in(jax.random.PRNGKey(0), seed)

        def stage(last, inp):
            if use_perms:
                k, tr_row, n_valid, te_start, perm = inp
                Xk, yk = X[perm], y[perm]
            else:
                k, tr_row, n_valid, te_start = inp
                Xk, yk = X, y
            k_init, k_fit = jax.random.split(jax.random.fold_in(rng, k))
            if scale_x:
                in_train = (jnp.arange(n_rows) < tr_row)[:, None]
                mn = jnp.min(jnp.where(in_train, Xk, jnp.inf), axis=0)
                mx = jnp.max(jnp.where(in_train, Xk, -jnp.inf), axis=0)
                span = mx - mn
                tiny = 10 * jnp.finfo(Xk.dtype).eps
                scale = 1.0 / jnp.where(span < tiny, 1.0, span)
                Xs = (Xk - mn) * scale
            else:
                Xs = Xk
            params = warm if warm_start else init_model_params(k_init, spec)
            opt_state = opt.init(params)

            def epoch_body(carry, epoch_rng):
                p, o, counted = carry
                p, o, loss, stats = epoch_fn(p, o, Xs, yk, epoch_rng, n_valid)
                counted = {key: counted[key] + stats[key] for key in counted}
                return (p, o, counted), loss

            (params, _, counted), losses = jax.lax.scan(
                epoch_body, (params, opt_state, last[2]),
                jax.random.split(k_fit, epochs),
            )
            Xte = jax.lax.dynamic_slice(Xs, (te_start, 0), (te_len, Xs.shape[1]))
            pred = _predict_windows(spec, params, Xte)
            # the stage's parameters and losses are the carry: only the last
            # stage's leave the scan, not a stack of every stage's
            return (params, losses, counted), pred

        stages = (
            jnp.arange(n_folds + 1),
            jnp.asarray(tr_rows),
            jnp.asarray(n_valids),
            jnp.asarray(te_starts),
        )
        if use_perms:
            stages = stages + (perms,)
        start = (
            jax.tree_util.tree_map(
                lambda a: jnp.zeros(a.shape, a.dtype),
                jax.eval_shape(lambda key: init_model_params(key, spec), rng),
            ),
            jnp.zeros((epochs,), jnp.float32),
            zero_stats(spec),
        )
        (p_final, losses, counted), preds_all = jax.lax.scan(stage, start, stages)
        # one prediction a fold; the final stage's is not an output
        return p_final, losses, tuple(preds_all[k] for k in range(n_folds)), counted

    in_axes: Tuple = (0, 0, 0)
    if use_perms:
        in_axes = in_axes + (None,)
    if warm_start:
        in_axes = in_axes + (0,)
    batched = jax.vmap(one_machine, in_axes=in_axes)
    if out_sharding is not None:
        return jax.jit(batched, out_shardings=out_sharding)
    return jax.jit(batched)


def _fold_geometry(
    plan: _Plan, n_rows: int
) -> Tuple[
    Tuple[Tuple[int, int, int], ...],
    Optional[List[Tuple[np.ndarray, np.ndarray]]],
    Optional[np.ndarray],
]:
    """The fold geometry of a bucket, from its first machine's plan and row
    count: ``(fold_bounds, kfold_folds, perms)``.

    ``fold_bounds`` is ``(train_end, test_start, test_end)`` a fold, every
    test slice of one length (what ``_bucket_program`` needs). For
    ``TimeSeriesSplit`` they are sklearn's own and the other two are None.
    For seeded shuffled-KFold geometry (KFCV plans) the exact sklearn fold
    assignment is computed on host — identical to the serial detector's —
    as ``kfold_folds`` (train and test index arrays a fold) and expressed
    as ``perms``, per-stage row permutations [train..., test...] (the last,
    for the final fit, the identity), so the program keeps static
    train-prefix / test-tail shapes. The bounds then pad every fold's test
    slice to the largest fold; assembly discards the padded leading rows.
    """
    spec = plan.spec
    kfold_folds = None
    perms = None
    if plan.cv[0] == "kfold":
        _, n_splits, shuffle, seed = plan.cv
        splitter = KFold(
            n_splits=n_splits, shuffle=shuffle,
            random_state=seed if shuffle else None,
        )
        kfold_folds = list(splitter.split(np.zeros((n_rows, 1))))
        te_max = max(len(te) for _, te in kfold_folds)
        fold_bounds = tuple(
            (len(tr), n_rows - te_max, n_rows) for tr, _ in kfold_folds
        )
        perms = np.stack(
            [np.concatenate([tr, te]) for tr, te in kfold_folds]
            + [np.arange(n_rows)]
        ).astype(np.int32)
    else:
        splitter = TimeSeriesSplit(n_splits=plan.n_splits)
        fold_bounds = tuple(
            (int(tr[-1]) + 1, int(te[0]), int(te[-1]) + 1)
            for tr, te in splitter.split(np.zeros((n_rows, 1)))
        )

    # every CV fold must yield at least one training sample, mirroring the
    # serial path's explicit error (ops/train.py fit_arrays)
    for tr_end, _, _ in fold_bounds:
        if n_train_samples(spec, tr_end) <= 0:
            raise ValueError(
                f"CV fold with {tr_end} rows yields no training samples for "
                f"lookback_window={spec.lookback_window} "
                f"lookahead={spec.lookahead} "
                f"(the bucket of machine {plan.machine.name})"
            )
    return fold_bounds, kfold_folds, perms


def _program_for(
    plan: _Plan, n_rows: int, fold_bounds, use_perms: bool, out_sharding
):
    """The compiled-once chunk program of the bucket whose first machine is
    ``plan``: ``(program, key, cached)``, with the program cache's
    effectiveness counted — a hit reuses an already-traced program, and its
    remembered first-compile wall (``_first_compile_walls[key]``, which the
    chunk loop notes after a miss's first dispatch) is credited as time
    saved."""
    key = (
        plan.spec,
        n_rows,
        fold_bounds,
        plan.epochs,
        plan.batch_size,
        plan.shuffle,
        plan.scale_x,
        out_sharding,
        use_perms,
        plan.warm_params is not None,
    )
    hits_before = _bucket_program.cache_info().hits
    program = _bucket_program(*key)
    cached = _bucket_program.cache_info().hits > hits_before
    metric_catalog.PROGRAM_CACHE.labels(result="hit" if cached else "miss").inc()
    if cached:
        saved = _first_compile_walls.get(key)
        if saved:
            metric_catalog.COMPILE_SECONDS_SAVED.inc(saved)
    return program, key, cached


@functools.lru_cache(maxsize=8)
def _verdict_program(out_sharding=None):
    """The divergence verdict where the parameters are: one bool a lane of a
    chunk's stacked parameters, true where any floating leaf of the lane
    holds a NaN or an infinity (what ``faults.params_non_finite`` says of
    the lane's slice on the host, a pass over every byte of it). A program
    of its own, so the chunk program's compile-cache entry stays what it
    was. ``out_sharding`` as in ``_bucket_program``: across processes the
    flags keep the machines sharding."""

    def lanes_non_finite(params_stack):
        leaves = jax.tree_util.tree_leaves(params_stack)
        bad = jnp.zeros((leaves[0].shape[0],), jnp.bool_)
        for leaf in leaves:
            if jnp.issubdtype(leaf.dtype, jnp.floating):
                finite = jnp.isfinite(leaf).reshape(leaf.shape[0], -1)
                bad = bad | ~jnp.all(finite, axis=1)
        return bad

    return jax.jit(lanes_non_finite, out_shardings=out_sharding)


# ------------------------------------------------- vectorized fold metrics
def _note_shard_devices(stacked_data, params_stack) -> None:
    """Record over how many devices a chunk's stacked data and trained
    params are laid out: with a mesh of n devices both must say n, or the
    fleet program is not spreading machines over the mesh."""
    leaves = jax.tree_util.tree_leaves(params_stack) + [stacked_data]
    metric_catalog.FLEET_SHARD_DEVICES.set(
        min(len(leaf.sharding.device_set) for leaf in leaves)
    )


def _metric_per_column(name: str, yt: np.ndarray, yp: np.ndarray) -> np.ndarray:
    """Per-column metric over stacked machines. yt/yp: (M, n, D) → (M, D).
    Formulas match sklearn's defaults (uniform_average over outputs)."""
    if name == "mean_squared_error":
        return ((yt - yp) ** 2).mean(axis=1)
    if name == "mean_absolute_error":
        return np.abs(yt - yp).mean(axis=1)
    if name == "r2_score":
        ss_res = ((yt - yp) ** 2).sum(axis=1)
        ss_tot = ((yt - yt.mean(axis=1, keepdims=True)) ** 2).sum(axis=1)
        with np.errstate(divide="ignore", invalid="ignore"):
            r2 = 1.0 - ss_res / ss_tot
        return np.where(ss_tot == 0.0, np.where(ss_res == 0.0, 1.0, 0.0), r2)
    if name == "explained_variance_score":
        err = yt - yp
        num = err.var(axis=1)
        den = yt.var(axis=1)
        with np.errstate(divide="ignore", invalid="ignore"):
            ev = 1.0 - num / den
        return np.where(den == 0.0, np.where(num == 0.0, 1.0, 0.0), ev)
    raise ValueError(f"Unsupported metric {name!r}")


_METRIC_NAMES = {
    "explained_variance_score",
    "r2_score",
    "mean_squared_error",
    "mean_absolute_error",
}


# --------------------------------------------------------------- the builder
class BatchedModelBuilder:
    """
    Train many machines at once on a device mesh.

    >>> # BatchedModelBuilder(machines).build() -> [(model, machine), ...]
    """

    def __init__(
        self,
        machines: List[Machine],
        mesh=None,
        serial_fallback: bool = True,
        chunk_size: Optional[int] = None,
        output_dir: Optional[str] = None,
        model_register_dir: Optional[str] = None,
        replace_cache: bool = False,
        fail_fast: bool = False,
        fault_policy: Optional[FaultPolicy] = None,
        elastic: Optional[bool] = None,
        warm_start: Optional[bool] = None,
        scheduler_dir: Optional[str] = None,
        scheduler_policy: str = "elastic",
        lease_timeout_s: Optional[float] = None,
        heartbeat_s: Optional[float] = None,
        host_rank: Optional[int] = None,
        num_hosts: Optional[int] = None,
    ):
        """
        ``chunk_size``: machines per compiled program. Large buckets are cut
        into fixed-size chunks so XLA compiles ONE program (per bucket shape)
        and reuses it for every chunk — compilation is the dominant cost of a
        cold build (~15s vs ~1s of compute for 64 small machines), and a
        fixed leading dimension makes it a one-time cost regardless of fleet
        size. Rounded up to a multiple of the mesh size. Default from
        $GORDO_TPU_CHUNK_MACHINES, else 256 (measured sweet spot on one
        v5e chip for the 4-tag hourglass workload: big enough to amortize
        dispatch, small enough to overlap transfers with compute).

        ``output_dir``/``model_register_dir``: checkpoint/resume for fleet
        builds. With both set, every machine is persisted (serializer.dump
        into ``{output_dir}/{name}``) and content-hash-registered AS SOON as
        its chunk finishes — a killed 10k-machine build resumes from the
        last chunk, with already-built machines loaded from cache instead of
        retrained (the fleet-scale form of the reference's whole-model cache,
        gordo/builder/build_model.py:92-167). ``replace_cache`` forces
        retraining, as in the serial builder.

        ``fail_fast``: restore pre-fault-domain behavior — the first fault
        aborts the whole build instead of quarantining the machine and
        degrading machine-by-machine (docs/robustness.md).

        ``fault_policy``: retry/backoff/classification policy; defaults to
        ``FaultPolicy.from_env()`` (``GORDO_TPU_FAULT_*`` variables).

        ``elastic``: replace the static multi-host partition with the
        work-stealing scheduler (parallel/scheduler.py): hosts lease
        buckets from a shared queue under ``output_dir`` and steal a peer's
        remaining units when they drain their own share or the peer's
        lease expires. Each host runs a *single-process* jax world (do not
        combine with ``distributed.initialize``); coordination is purely
        the shared filesystem. Default from ``$GORDO_TPU_ELASTIC``.
        ``scheduler_policy="static"`` keeps the queue's nominal partition
        with no stealing (the baseline stealing is compared
        against). ``host_rank``/``num_hosts`` default to
        ``$GORDO_TPU_PROCESS_ID``/``$GORDO_TPU_NUM_PROCESSES``.

        ``warm_start``: when a machine's full cache key misses but its
        *warm* key (config/spec, data excluded —
        ``ModelBuilder.calculate_warm_key``) matches a registered
        artifact, reuse that artifact's trained params as training init
        instead of a random init (delta rebuild of a drifted fleet).
        Default on with a ``model_register_dir``; ``$GORDO_TPU_WARM_START=0``
        disables.
        """
        self.machines = machines
        self.mesh = mesh if mesh is not None else default_mesh()
        self.serial_fallback = serial_fallback
        if chunk_size is None:
            chunk_size = int(os.environ.get("GORDO_TPU_CHUNK_MACHINES", "256"))
        self.chunk_size = max(1, chunk_size)
        self.output_dir = output_dir
        self.model_register_dir = model_register_dir
        self.replace_cache = replace_cache
        self.fail_fast = fail_fast
        self.fault_policy = fault_policy or FaultPolicy.from_env()
        if elastic is None:
            elastic = os.environ.get("GORDO_TPU_ELASTIC", "") not in ("", "0")
        self.elastic = bool(elastic)
        if warm_start is None:
            raw = os.environ.get("GORDO_TPU_WARM_START", "")
            warm_start = raw not in ("0",)
        self.warm_start = bool(warm_start)
        self.scheduler_dir = scheduler_dir
        self.scheduler_policy = scheduler_policy
        self.lease_timeout_s = lease_timeout_s
        self.heartbeat_s = heartbeat_s
        self.host_rank = host_rank
        self.num_hosts = num_hosts
        # the live ElasticScheduler of the current/most recent elastic
        # build(): tests read its stats
        self.scheduler = None
        # fault-domain outcome of the last build(): Machine objects whose
        # BuildMetadata.fault_domain records stage/reason, plus the raw
        # records (the CLI exit report reads both)
        self.quarantined: List[Machine] = []
        self.quarantine_records: List[QuarantineRecord] = []
        self._quarantined_names: set = set()
        # how this build quarantines a machine lost before training (to its
        # fetch, or to its serial build): plainly, or claim-gated in the
        # elastic build. Fixed once a build(), called as (machine, record)
        self._quarantine_lost = self._quarantine
        # machines (by index) that arrived with another shape than the
        # bucket they were grouped into before their data was there;
        # _build_all buckets them again once that bucket has ended
        self._set_aside: Dict[int, _Plan] = {}
        # fleet programs that failed to compile during the last build():
        # {"bucket", "machines", "error"} each. The fault ladder may still
        # have built those machines some other way, so the exit report
        # names them: such a build did not use the fleet path as planned
        self.compile_failures: List[Dict[str, Any]] = []

    # -------------------------------------------------------------- data
    def _load_data(self, plan: _Plan):
        t0 = time.time()
        with _machine_trace(plan.machine.name), telemetry.span(
            "fetch", _PHASE_FETCH, machine=plan.machine.name
        ):
            faults.fault_point("data_fetch", machine=plan.machine.name)
            dataset = GordoBaseDataset.from_dict(plan.machine.dataset.to_dict())
            X, y = dataset.get_data()
            plan.X = faults.maybe_poison(
                plan.machine.name, np.ascontiguousarray(X.to_numpy(np.float32))
            )
            plan.y = np.ascontiguousarray(y.to_numpy(np.float32))
        plan.index = X.index
        plan.columns = list(X.columns)
        plan.target_columns = list(y.columns)
        plan.query_duration = time.time() - t0
        plan.dataset_meta = dataset.get_metadata()

    def _load_data_guarded(self, plan: _Plan) -> Optional[QuarantineRecord]:
        """Per-machine data fetch with transient retry + backoff; returns a
        quarantine record instead of raising once attempts are exhausted (a
        single machine's feed outage must not abort the fleet)."""
        if self.fail_fast:
            self._load_data(plan)
            return None
        name = plan.machine.name
        try:
            _, attempts = faults.retry_call(
                lambda: self._load_data(plan),
                self.fault_policy,
                key=name,
                describe=f"data fetch for machine {name}",
            )
            plan.fetch_attempts = attempts
            return None
        except Exception as exc:
            kind = self.fault_policy.classify(exc)
            return QuarantineRecord(
                machine=name,
                stage=faults.STAGE_DATA_FETCH,
                reason=f"{kind}_fetch_failure",
                error=f"{type(exc).__name__}: {exc}",
                attempts=(
                    self.fault_policy.max_attempts if kind == "transient" else 1
                ),
            )

    def _fetch_machine(self, plan: _Plan) -> Optional[QuarantineRecord]:
        """One machine's whole fetch, on a pool thread: the guarded load,
        the non-finite check, the warm-start params. Returns the machine's
        quarantine record, or None with the plan filled. A NaN column would
        train to NaN params and poison nothing but its own vmap lane — but
        its thresholds/scores would be garbage and, before it is stacked,
        it is trivially isolable."""
        record = self._load_data_guarded(plan)
        if record is not None:
            return record
        name = plan.machine.name
        with _machine_trace(name), telemetry.span(
            "validate", _PHASE_VALIDATE, machine=name
        ):
            bad = faults.non_finite_report(plan.X, plan.y)
        if bad is not None:
            if self.fail_fast:
                raise faults.NonFiniteDataError(f"machine {name}: {bad}")
            return QuarantineRecord(
                machine=name,
                stage=faults.STAGE_DATA_VALIDATION,
                reason="non_finite_data",
                error=bad,
            )
        plan.warm_params = self._maybe_warm_params(plan.machine, plan.spec)
        return None

    def _arrived(self, plan: _Plan) -> bool:
        """Block until the machine's fetch has ended and say whether its
        data is there to build from. The first call takes the fetch's
        outcome — under ``fail_fast`` that raises the machine's own
        exception, here on the build thread; otherwise a failed machine is
        quarantined as this build quarantines (``_quarantine_lost``) — and
        a later call (a bucket retried or bisected) only answers: the
        provider is asked once a machine."""
        pending, plan.fetch = plan.fetch, None
        if pending is not None:
            record = pending.result()
            if record is not None:
                self._quarantine_lost(plan.machine, record)
            elif plan.warm_params is not None:
                metric_catalog.WARM_STARTS.inc()
        return plan.machine.name not in self._quarantined_names

    # -------------------------------------------------------- quarantine
    def _quarantine(self, machine: Machine, record: QuarantineRecord) -> None:
        """Drop one machine from the build, recording why. The machine's
        reasons land in a fresh ``BuildMetadata.fault_domain`` (the fleet
        analog of a crashed pod's termination message)."""
        logger.error(
            "Machine %s QUARANTINED at %s (%s): %s",
            record.machine, record.stage, record.reason, record.error,
        )
        faults.record_quarantine(record.stage)
        machine_out = Machine(
            name=machine.name,
            dataset=machine.dataset.to_dict(),
            # to_dict round-trip: the quarantined copy must not alias (and
            # mutate) the input machine's Metadata
            metadata=machine.metadata.to_dict(),
            model=machine.model,
            project_name=machine.project_name,
            evaluation=machine.evaluation,
            runtime=machine.runtime,
        )
        machine_out.metadata.build_metadata = BuildMetadata(
            fault_domain=record.to_dict()
        )
        self.quarantine_records.append(record)
        self.quarantined.append(machine_out)
        self._quarantined_names.add(machine.name)

    # ------------------------------------------------------------- build
    def build(self) -> List[Tuple[Any, Machine]]:
        """
        Train and return ``(model, machine)`` per machine.

        Single-process: results cover every machine, input order. In a
        multi-process world (``parallel.distributed``), each process returns
        only the machines whose mesh rows are on its local devices plus its
        round-robin share of serial-fallback machines — together the
        processes cover the fleet exactly once, and each host persists its
        own share (the SPMD replacement for one-pod-per-machine fan-out).
        """
        from gordo_tpu.parallel import distributed
        from gordo_tpu.util.profiling import maybe_profile

        self.quarantined = []
        self.quarantine_records = []
        self._quarantined_names = set()
        self._quarantine_lost = self._quarantine
        self._set_aside = {}
        self.compile_failures = []
        with maybe_profile("batched-build"):
            with telemetry.span("batched_build", machines=len(self.machines)):
                return self._build_all(distributed)

    def _machine_output_dir(self, name: str) -> Optional[str]:
        if not self.output_dir:
            return None
        return os.path.join(self.output_dir, name)

    def _cached_path(self, machine: Machine) -> Optional[str]:
        """Registry lookup only (no unpickle); handles replace_cache."""
        if self.replace_cache:
            from gordo_tpu.util import disk_registry

            disk_registry.delete_value(
                self.model_register_dir, ModelBuilder.calculate_cache_key(machine)
            )
            return None
        return ModelBuilder(machine).check_cache(self.model_register_dir)

    def _load_cached_guarded(self, i: int, path: str):
        """Unpickle one cache hit; a corrupt/truncated artifact must not
        kill a resuming fleet build — evict the registry entry and let the
        machine rebuild through the normal path instead."""
        try:
            return ModelBuilder.load_from_cache(path)
        except Exception as exc:
            if self.fail_fast:
                raise
            logger.warning(
                "Machine %s: corrupt cache artifact at %s (%s: %s); "
                "evicting registry entry and rebuilding",
                self.machines[i].name, path, type(exc).__name__, exc,
            )
            from gordo_tpu.util import disk_registry

            disk_registry.delete_value(
                self.model_register_dir,
                ModelBuilder.calculate_cache_key(self.machines[i]),
            )
            return None

    def _persist(self, machine: Machine, model, machine_out: Machine) -> None:
        """Dump + register one machine the moment it is assembled, so an
        interrupted fleet build resumes instead of restarting."""
        model_dir = self._machine_output_dir(machine_out.name)
        if model_dir is None:
            return
        os.makedirs(model_dir, exist_ok=True)
        with _machine_trace(machine_out.name), telemetry.span(
            "serialize", _PHASE_SERIALIZE, machine=machine_out.name
        ):
            serializer.dump(model, model_dir, metadata=machine_out.to_dict())
        metric_catalog.ARTIFACT_BYTES.inc(
            os.path.getsize(os.path.join(model_dir, "model.pkl"))
        )
        # build-to-serve (ISSUE 14): ship the fused serving executables
        # alongside the params so a cold serving node deserializes instead
        # of compiling. Best-effort — a shipping failure costs warmth on
        # the serving side, never the build.
        if programs.ship_enabled():
            try:
                programs.ship_programs(
                    model, model_dir, expected_fleet=len(self.machines)
                )
            except Exception as exc:  # noqa: BLE001
                logger.warning(
                    "Machine %s: shipping AOT serving programs failed "
                    "(%s: %s); artifact serves via the jit/prelower path",
                    machine_out.name, type(exc).__name__, exc,
                )
        if self.model_register_dir:
            from gordo_tpu.util import disk_registry

            disk_registry.write_key(
                self.model_register_dir,
                ModelBuilder.calculate_cache_key(machine),
                model_dir,
            )
            # warm-start registry: a future build whose full key misses
            # (data drifted) finds this artifact by config/spec alone and
            # reuses its params as training init
            disk_registry.write_key(
                self.model_register_dir,
                ModelBuilder.calculate_warm_key(machine),
                model_dir,
            )

    def _maybe_warm_params(self, machine: Machine, spec: ModelSpec):
        """The prior artifact's trained params for a warm-start delta
        rebuild, or None: warm registry miss, unloadable artifact, or a
        param tree whose structure/shapes no longer match the spec (the
        "only data drifted" premise failed — cold init is the safe answer).
        """
        if not self.warm_start or not self.model_register_dir:
            return None
        from gordo_tpu.util import disk_registry

        path = disk_registry.get_value(
            self.model_register_dir, ModelBuilder.calculate_warm_key(machine)
        )
        if not path or not os.path.isdir(path):
            return None
        try:
            model = serializer.load(path)
        except Exception:  # noqa: BLE001 — a corrupt prior artifact only
            return None  # costs the warm start, never the build
        inner = model
        if isinstance(inner, DiffBasedAnomalyDetector):
            inner = inner.base_estimator
        if isinstance(inner, Pipeline):
            inner = inner.steps[-1][1]
        params = getattr(inner, "params_", None)
        if params is None:
            return None
        try:
            ref = jax.eval_shape(
                lambda: init_model_params(jax.random.PRNGKey(0), spec)
            )
            ref_leaves, ref_def = jax.tree_util.tree_flatten(ref)
            leaves, tree_def = jax.tree_util.tree_flatten(params)
            if tree_def != ref_def or len(leaves) != len(ref_leaves):
                return None
            out = []
            for leaf, r in zip(leaves, ref_leaves):
                arr = np.asarray(leaf)
                if arr.shape != tuple(r.shape):
                    return None
                out.append(arr.astype(r.dtype, copy=False))
            return jax.tree_util.tree_unflatten(ref_def, out)
        except Exception:  # noqa: BLE001 — same rationale as above
            return None

    def _plan_fleet(
        self, owns_hit
    ) -> Tuple[Dict[int, Tuple[Any, Machine]], Dict[int, _Plan], List[int]]:
        """The plan stage of both orchestrators: what this process returns
        from the cache, the batchable machines' plans, and the machines
        only the serial builder can build (all by global index).

        Resume prefilter: registry lookups (cheap) run threaded for the
        whole fleet, and each hit is returned by exactly one process — the
        one for which ``owns_hit(machine)`` is true: the hash partition in
        the static build, whoever claims the hit first in the elastic one.
        The owner unpickles and returns it, the others skip it entirely.
        Ownership goes by the machine, never by its position in the locally
        observed hit list: registries can drift between processes
        (overlapping builds registering keys mid-prefilter), and
        position-keyed ownership would then double- or zero-own a machine.
        """
        results: Dict[int, Tuple[Any, Machine]] = {}
        plans: Dict[int, _Plan] = {}
        serial: List[int] = []

        hits: Dict[int, str] = {}
        if self.model_register_dir and self.machines:
            with ThreadPoolExecutor(
                max_workers=min(16, len(self.machines))
            ) as pool:
                paths = pool.map(self._cached_path, self.machines)
                hits = {i: path for i, path in enumerate(paths) if path}
        owned = {
            i: path for i, path in hits.items() if owns_hit(self.machines[i])
        }
        loaded: Dict[int, Optional[Tuple[Any, Machine]]] = {}
        if owned:
            with ThreadPoolExecutor(max_workers=min(16, len(owned))) as pool:
                loaded = dict(
                    zip(
                        owned,
                        pool.map(self._load_cached_guarded, owned, owned.values()),
                    )
                )

        for i, machine in enumerate(self.machines):
            if i in hits and i not in loaded:
                continue  # cached; another process owns and returns it
            cached = loaded.get(i)
            if cached is not None:
                logger.info("Machine %s: loaded from cache", machine.name)
                metric_catalog.BUILD_MACHINES.labels(outcome="cached").inc()
                results[i] = cached
                model_dir = self._machine_output_dir(machine.name)
                if model_dir and not os.path.exists(
                    os.path.join(model_dir, "model.pkl")
                ):
                    # cache hit from a previous run's output_dir; materialize
                    # the artifact in this run's tree too
                    self._persist(machine, *cached)
                continue
            # no hit, or a corrupt one this process owns (evicted): build it
            plan = _plan_machine(machine)
            if plan is None:
                serial.append(i)
            else:
                plans[i] = plan

        if serial and not self.serial_fallback:
            raise ValueError(
                f"Machine {self.machines[serial[0]].name} is not batchable "
                f"and serial_fallback=False"
            )
        return results, plans, serial

    def _build_serial(
        self, machine: Machine, reason: str, stage: str, quarantine
    ) -> Optional[Tuple[Any, Machine]]:
        """One machine through the serial ``ModelBuilder``, counted under
        ``reason``; one whose serial build fails too is handed to
        ``quarantine(machine, record)`` at ``stage`` and answers None."""
        logger.info("Machine %s: serial build (%s)", machine.name, reason)
        metric_catalog.SERIAL_FALLBACKS.labels(reason=reason).inc()
        try:
            return ModelBuilder(machine).build(
                output_dir=self._machine_output_dir(machine.name),
                model_register_dir=self.model_register_dir,
            )
        except Exception as exc:
            if self.fail_fast:
                raise
            quarantine(
                machine,
                QuarantineRecord(machine.name, stage, type(exc).__name__, str(exc)),
            )
            return None

    def _build_all(self, distributed) -> List[Tuple[Any, Machine]]:
        if self.elastic:
            return self._build_all_elastic(distributed)

        # ownership keyed by a stable hash of the machine name: the hit and
        # serial lists' composition depends on local cache state, so
        # list-POSITION ownership could diverge between processes, while raw
        # global indices could concentrate load on one process when
        # unbatchable machines land on a stride
        def owns(machine: Machine) -> bool:
            return distributed.owns_serial_machine(_machine_seed(machine))

        with _stage("plan", machines=len(self.machines)):
            results, plans, serial = self._plan_fleet(owns)
            for i in serial:
                if not owns(self.machines[i]):
                    continue
                built = self._build_serial(
                    self.machines[i], "unbatchable",
                    faults.STAGE_SERIAL_BUILD, self._quarantine_lost,
                )
                if built is not None:
                    results[i] = built

        # the fetch is a stream that the chunk loop consumes: a bucket's
        # first chunk is dispatched when its own machines have arrived, and
        # every later chunk's data is fetched while the device runs the
        # chunk before it (_build_bucket waits for a group's fetches just
        # before it stacks the group). Across processes every one has to
        # agree on each chunk's membership before it is launched, so there
        # the stream is waited out first (_fetch_all)
        pool = ThreadPoolExecutor(
            max_workers=min(16, max(len(plans), 1)),
            thread_name_prefix="gordo-fetch",
        )
        try:
            if distributed.is_multiprocess():
                buckets = self._fetch_all(pool, plans)
            else:
                # no data yet: ordered and grouped by the partial key
                with _stage("validate_stage", machines=len(plans)):
                    buckets = self._buckets_of(plans)
                first = next(iter(buckets.values()), [])
                self._fetch_stage(
                    pool,
                    [plans[i] for idxs in buckets.values() for i in idxs],
                    min(len(first), self._chunk_width(len(first))),
                )
            while buckets:
                for idxs in buckets.values():
                    bucket_plans = [plans[i] for i in idxs]
                    for i, built in self._build_bucket_guarded(bucket_plans, idxs):
                        results[i] = built
                # machines that arrived with another shape than their
                # bucket's first were set aside: their data is there now, so
                # they are bucketed by the full key and built as ever
                plans, self._set_aside = self._set_aside, {}
                buckets = self._buckets_of(plans)
        finally:
            # nothing is pending unless the build is being abandoned
            pool.shutdown(wait=True, cancel_futures=True)

        return [results[i] for i in sorted(results)]

    @staticmethod
    def _buckets_of(plans: Dict[int, _Plan]) -> Dict[Tuple, List[int]]:
        buckets: Dict[Tuple, List[int]] = {}
        for i, plan in plans.items():
            buckets.setdefault(plan.bucket_key(), []).append(i)
        return buckets

    def _chunk_width(self, n_machines: int) -> int:
        """Machines a dispatch of a bucket of ``n_machines``: a fixed width
        (a multiple of the mesh size), so that one compiled program is
        reused for every chunk and compile cost does not scale with the
        bucket."""
        n_dev = int(np.prod(list(self.mesh.shape.values())))
        return ((min(self.chunk_size, n_machines) + n_dev - 1) // n_dev) * n_dev

    def _fetch_stage(
        self, pool: ThreadPoolExecutor, order: List[_Plan], n_first: int
    ) -> None:
        """Start the fetch of every planned machine on the caller's
        ``pool`` (provider I/O is the per-machine serial cost the reference
        paid per pod), each with its non-finite check and warm params on
        the same thread, and return when the first ``n_first`` of ``order``
        have ended. Those have the threads to themselves — nothing is
        dispatched before they are there — and the rest are submitted as
        the stage ends, in the order the chunks will consume them.

        A fetch retries transient faults with backoff; on exhaustion the
        machine is quarantined — one dead sensor feed degrades one machine,
        not the fleet (the blast radius the reference got from
        one-pod-per-machine). Whoever waits for a machine takes its outcome
        (``_arrived``)."""
        with _stage("fetch_stage", machines=len(order)):
            first, rest = order[:n_first], order[n_first:]
            for plan in first:
                plan.fetch = pool.submit(self._fetch_machine, plan)
            wait_for_futures([plan.fetch for plan in first])
            for plan in rest:
                plan.fetch = pool.submit(self._fetch_machine, plan)

    def _fetch_all(
        self, pool: ThreadPoolExecutor, plans: Dict[int, _Plan]
    ) -> Dict[Tuple, List[int]]:
        """The barrier, as a use of the stream: wait for every fetch, take
        every outcome (a machine lost to its fetch is quarantined and leaves
        ``plans``), and only then bucket, by the full key. For the builds
        that have to agree on each chunk's membership before anything is
        launched: across processes, and every host of an elastic build."""
        self._fetch_stage(pool, list(plans.values()), len(plans))
        with _stage("validate_stage", machines=len(plans)):
            for i in list(plans):
                if not self._arrived(plans[i]):
                    del plans[i]
            return self._buckets_of(plans)

    def _build_all_elastic(self, distributed) -> List[Tuple[Any, Machine]]:
        """The work-stealing fleet build (parallel/scheduler.py): every
        host plans the same fleet deterministically, derives the same work
        units, then leases them one at a time from the shared queue until
        no unit is pending. Fast hosts drain their nominal share and steal
        a peer's; a dead host's lease goes stale and its in-flight unit is
        re-leased, re-entering the normal fault ladder
        (``_build_bucket_guarded``) on the stealing host.

        Per-host data fetches cover every *planned* machine (each host may
        end up building any bucket), a deliberate v1 tradeoff documented in
        docs/components/fleet_training.md — the provider I/O is threaded
        and the artifacts, not the fetches, dominate a fleet build.
        """
        from gordo_tpu.parallel.scheduler import (
            ElasticScheduler,
            WorkUnit,
            scheduler_dir_for,
            unit_id_for,
        )

        if distributed.is_multiprocess():
            raise RuntimeError(
                "elastic scheduling replaces the jax.distributed world: "
                "run one single-process build per host against the shared "
                "output_dir (no --coordinator-address)"
            )
        base_dir = self.scheduler_dir or (
            scheduler_dir_for(self.output_dir) if self.output_dir else None
        )
        if base_dir is None:
            raise ValueError(
                "elastic builds need shared state: set output_dir (the "
                "queue lives in its _scheduler/ subdir) or scheduler_dir"
            )

        sched = ElasticScheduler(
            base_dir,
            host_rank=self.host_rank,
            num_hosts=self.num_hosts,
            lease_timeout_s=self.lease_timeout_s,
            heartbeat_s=self.heartbeat_s,
            policy=self.scheduler_policy,
        )
        self.scheduler = sched
        try:
            n_done = sum(
                1 for n in os.listdir(sched.done_dir) if n.endswith(".json")
            )
        except OSError:
            n_done = 0
        if n_done:
            # scheduler state is per-BUILD-ATTEMPT: markers from a crashed
            # run of this same build correctly skip completed units, but a
            # logically new build must not inherit them
            logger.warning(
                "elastic scheduler state at %s already holds %d done "
                "markers: resuming that build (units they cover are "
                "skipped; a new build needs a fresh output_dir or "
                "scheduler_dir)",
                base_dir, n_done,
            )
        # every host observes the same bad feed or unbuildable machine;
        # exactly one records it
        self._quarantine_lost = functools.partial(self._quarantine_claimed, sched)
        try:
            with _stage("plan", machines=len(self.machines)):
                # a full-key registry hit is claimed exactly once fleet-wide
                # by a done marker: whoever claims first loads and returns
                # the machine (or, the artifact corrupt, rebuilds it)
                results, plans, serial = self._plan_fleet(
                    lambda machine: sched.try_claim(
                        unit_id_for([machine.name], "cached"),
                        {"machine": machine.name},
                    )
                )

            with ThreadPoolExecutor(
                max_workers=min(16, max(len(plans), 1))
            ) as pool:
                buckets = self._fetch_all(pool, plans)

            units: Dict[str, WorkUnit] = {}
            members: Dict[str, Tuple[str, List[int]]] = {}
            for key, idxs in buckets.items():
                # lease granularity is the dispatch chunk, not the whole
                # bucket: a big bucket becomes several units SHARING one
                # compile signature, so (a) it balances across hosts at
                # all and (b) the placement affinity + in-process program
                # cache actually get same-shaped leases to reuse
                for start in range(0, len(idxs), self.chunk_size):
                    group = idxs[start : start + self.chunk_size]
                    names = tuple(
                        sorted(self.machines[i].name for i in group)
                    )
                    uid = unit_id_for(names, "bucket")
                    units[uid] = WorkUnit(
                        unit_id=uid,
                        machines=names,
                        # compile-affinity signature: the program cache
                        # key's shape-determining parts (everything but
                        # membership)
                        signature=repr(key),
                        kind="bucket",
                        cost=len(group),
                    )
                    members[uid] = ("bucket", group)
            for i in serial:
                name = self.machines[i].name
                uid = unit_id_for([name], "serial")
                units[uid] = WorkUnit(
                    unit_id=uid, machines=(name,), kind="serial", cost=1
                )
                members[uid] = ("serial", [i])

            while True:
                lease = sched.next_lease(units)
                if lease is None:
                    break
                faults.fault_point(
                    "scheduler_lease", machines=lease.unit.machines
                )
                kind, idxs = members[lease.unit.unit_id]
                if kind == "serial":
                    built = self._build_serial(
                        self.machines[idxs[0]], "unbatchable",
                        faults.STAGE_SERIAL_BUILD, self._quarantine_lost,
                    )
                    built_list = [(idxs[0], built)] if built is not None else []
                else:
                    bucket_plans = [plans[i] for i in idxs]
                    built_list = self._build_bucket_guarded(bucket_plans, idxs)
                if not sched.still_current(lease):
                    # a peer stole this lease mid-build (we looked dead);
                    # its result is authoritative, ours is the byte-same
                    # duplicate — discard without recording
                    logger.warning(
                        "lost lease on %s to a peer mid-build; discarding "
                        "this host's duplicate results", lease.unit.unit_id,
                    )
                    continue
                for i, built in built_list:
                    results[i] = built
                sched.note_compiled(lease.unit.signature)
                sched.mark_done(lease, {"built": len(built_list)})
        finally:
            sched.close()

        return [results[i] for i in sorted(results)]

    def _quarantine_claimed(self, sched, machine: Machine, record) -> None:
        """Quarantine under the elastic exactly-once contract: the claim
        winner records the machine (report + metrics); losers only mark it
        locally dead so no bucket re-admits it."""
        from gordo_tpu.parallel.scheduler import unit_id_for

        if sched.try_claim(
            unit_id_for([record.machine], "quarantine"), record.to_dict()
        ):
            self._quarantine(machine, record)
        else:
            self._quarantined_names.add(record.machine)

    def _build_bucket_guarded(
        self,
        bucket: List[_Plan],
        global_idxs: List[int],
        attempt: int = 1,
    ) -> List[Tuple[int, Tuple[Any, Machine]]]:
        """Run one bucket with the fault-domain recovery ladder:

        1. transient failure → retry the bucket (minus any members
           quarantined in the meantime) with backoff, up to the policy's
           attempt budget;
        2. device OOM → bisect the bucket and recurse on each half (each
           sub-bucket compiles with half the machine axis, so peak HBM
           halves too — the in-process analog of rescheduling pods onto
           emptier nodes);
        3. anything else, or an exhausted budget → per-machine serial
           ``ModelBuilder`` as the last resort, quarantining machines whose
           serial build also fails.

        ``fail_fast`` skips the whole ladder (pre-fault-domain behavior).
        """
        # drop members quarantined, or set aside for a bucket of their own
        # shape, since this bucket was assembled (e.g. on the retry after a
        # mixed failure). A member whose fetch is still in flight stays:
        # whoever builds it waits for that fetch, and nobody fetches again
        live = [
            (p, i)
            for p, i in zip(bucket, global_idxs)
            if p.machine.name not in self._quarantined_names
            and i not in self._set_aside
        ]
        if not live:
            return []
        bucket = [p for p, _ in live]
        global_idxs = [i for _, i in live]
        if self.fail_fast:
            return self._build_bucket(bucket, global_idxs)
        try:
            return self._build_bucket(bucket, global_idxs)
        except Exception as exc:
            names = [p.machine.name for p in bucket]
            if faults.is_oom(exc) and len(bucket) > 1:
                mid = len(bucket) // 2
                logger.warning(
                    "Bucket of %d machines hit device OOM (%s); bisecting "
                    "into %d + %d", len(bucket), exc, mid, len(bucket) - mid,
                )
                metric_catalog.OOM_BISECTIONS.inc()
                return self._build_bucket_guarded(
                    bucket[:mid], global_idxs[:mid]
                ) + self._build_bucket_guarded(bucket[mid:], global_idxs[mid:])
            if (
                self.fault_policy.classify(exc) == "transient"
                and attempt < self.fault_policy.max_attempts
            ):
                delay = self.fault_policy.backoff(attempt, names[0])
                logger.warning(
                    "Bucket of %d machines failed transiently "
                    "(attempt %d/%d, retrying in %.2fs): %s",
                    len(bucket), attempt, self.fault_policy.max_attempts,
                    delay, exc,
                )
                metric_catalog.BUCKET_RETRIES.inc()
                time.sleep(delay)
                return self._build_bucket_guarded(
                    bucket, global_idxs, attempt=attempt + 1
                )
            logger.warning(
                "Bucket of %d machines failed (%s: %s); falling back to "
                "serial builds per machine", len(bucket),
                type(exc).__name__, exc,
            )
            return self._bucket_serial_last_resort(bucket, global_idxs)

    def _note_compile_failure(
        self, bucket_name: str, n_machines: int, exc: BaseException
    ) -> None:
        """A fleet program failed in its first dispatch, which is where jit
        compiles: make it loud — counted, logged at ERROR with the
        compiler's message, and kept for the exit report — before the fault
        ladder (or ``fail_fast``) decides what happens to the machines."""
        metric_catalog.FLEET_COMPILE_FAILURES.inc()
        error = f"{type(exc).__name__}: {exc}"
        logger.error(
            "Fleet program for bucket %s (%d machines) FAILED TO COMPILE: %s",
            bucket_name, n_machines, error,
        )
        self.compile_failures.append(
            {"bucket": bucket_name, "machines": n_machines, "error": error}
        )

    def _bucket_serial_last_resort(
        self, bucket: List[_Plan], global_idxs: List[int]
    ) -> List[Tuple[int, Tuple[Any, Machine]]]:
        """Per-machine serial rebuild of a failed bucket: capability over
        speed, and per-machine blast radius — a machine whose serial build
        also fails is quarantined, never the fleet."""
        out = []
        for i, plan in zip(global_idxs, bucket):
            built = self._build_serial(
                plan.machine, "bucket_failure", faults.STAGE_TRAINING,
                self._quarantine,
            )
            if built is not None:
                out.append((i, built))
        return out

    def _build_bucket(
        self, bucket: List[_Plan], global_idxs: List[int]
    ) -> List[Tuple[int, Tuple[Any, Machine]]]:
        with _stage("bucket_prep", machines=len(bucket)):
            faults.fault_point(
                "bucket_compile", machines=[p.machine.name for p in bucket]
            )
            # M and the chunk are those of the bucket as planned: a machine
            # lost to its fetch leaves a padding lane, not a narrower program
            M = len(bucket)
            chunk = self._chunk_width(M)
            members = list(zip(bucket, global_idxs))
            key: Optional[Tuple] = None

            def take(start: int) -> List[Tuple[_Plan, int]]:
                """The chunk's machines that are there to build, each with
                its global index, once their fetches have ended: a machine
                whose fetch failed is quarantined by then, and one that
                arrived with another shape than the bucket's first (the row
                count, warm or cold: what only the data tells) is set aside
                for a bucket of its own."""
                nonlocal key
                group = []
                for plan, i in members[start : start + chunk]:
                    if not self._arrived(plan):
                        continue
                    arrived_as = plan.bucket_key()
                    if key is None:
                        key = arrived_as
                    if arrived_as != key:
                        self._set_aside[i] = plan
                        continue
                    group.append((plan, i))
                return group

            # the first chunk that has a machine left fixes the shapes
            starts = list(range(0, M, chunk))
            first_group = take(starts[0])
            while not first_group:
                del starts[0]
                if not starts:
                    return []
                first_group = take(starts[0])
            plan0 = first_group[0][0]
            n_rows = len(plan0.X)
            fold_bounds, kfold_folds, perms = _fold_geometry(plan0, n_rows)

            from gordo_tpu.parallel import distributed

            multiprocess = distributed.is_multiprocess()
            warm = plan0.warm_params is not None
            sharding = machines_sharding(self.mesh)
            program, program_key, program_cached = _program_for(
                plan0, n_rows, fold_bounds, perms is not None,
                sharding if multiprocess else None,
            )
            verdict = _verdict_program(sharding if multiprocess else None)
            perms_d = None
            if perms is not None:
                from jax.sharding import NamedSharding, PartitionSpec

                # fold permutations are identical for every machine (same seed,
                # same row count): one replicated array, not a vmapped axis.
                # make_global_stacked handles the multi-process world, where a
                # plain device_put cannot address other hosts' devices
                perms_d = distributed.make_global_stacked(
                    NamedSharding(self.mesh, PartitionSpec()), perms
                )
                _H2D.inc(perms.nbytes)

        t0 = time.time()

        def take_late(start: int, before):
            """A later chunk's machines: their fetches ran under the chunks
            before it, and whatever is left of them the device now waits
            for. Where it waited and the chunk before (``before``, its
            outputs) had finished by the end of the wait, the device idled
            for the fetch: that chunk starved."""
            late = sum(
                1
                for plan, _ in members[start : start + chunk]
                if plan.fetch is not None and not plan.fetch.done()
            )
            with _stage("fetch_wait", chunk_start=start, machines=late):
                group = take(start)
            starved = late > 0 and jax.tree_util.tree_leaves(before)[0].is_ready()
            metric_catalog.FETCH_STARVED_CHUNKS.inc(int(starved))
            return group

        def dispatch(start: int, group):
            with _stage("stack_h2d", chunk_start=start):
                lanes = [plan for plan, _ in group]
                pad = chunk - len(lanes)
                X = np.stack([p.X for p in lanes] + [lanes[0].X] * pad)
                y = np.stack([p.y for p in lanes] + [lanes[0].y] * pad)
                # per-machine RNG stream derived from (evaluation.seed, machine
                # name): independent of bucket composition/ordering, so a
                # machine's weights are reproducible no matter which other
                # machines train alongside it
                seeds = np.array(
                    [_machine_seed(p.machine) for p in lanes] + [0] * pad,
                    dtype=np.uint32,
                )
                X_d = distributed.make_global_stacked(sharding, X)
                y_d = distributed.make_global_stacked(sharding, y)
                seeds_d = distributed.make_global_stacked(sharding, seeds)
                _H2D.inc(X.nbytes + y.nbytes + seeds.nbytes)
                args = (X_d, y_d, seeds_d)
                if perms_d is not None:
                    args = args + (perms_d,)
                if warm:
                    # stack each machine's prior params on the machine axis
                    # (padding lanes replicate lanes[0], like X/y above) and
                    # shard the stacked tree exactly like the other inputs
                    trees = [p.warm_params for p in lanes] + [
                        lanes[0].warm_params
                    ] * pad
                    stacked = jax.tree_util.tree_map(
                        lambda *leaves: np.stack(leaves), *trees
                    )
                    warm_d = jax.tree_util.tree_map(
                        lambda a: distributed.make_global_stacked(sharding, a),
                        stacked,
                    )
                    _H2D.inc(_nbytes(stacked))
                    args = args + (warm_d,)
            with _stage("launch", chunk_start=start):
                # returns once the execution is queued (the first call
                # compiles or loads the program before that)
                outputs = program(*args)
                # queued behind this chunk and before the next one's launch:
                # wait() covers it, and fetch() never waits for it behind a
                # later chunk's execution
                non_finite = verdict(outputs[0])
                _note_shard_devices(X_d, outputs[0])
            return group, (*outputs, non_finite)

        # the completion of the chunk before: the bucket's start for the first
        chunk_done = t0

        def wait(group, outputs):
            """Block until the chunk is ready, and take its wall: from the
            completion of the chunk before to its own (so a first compile is
            charged to the first chunk's machines). It is what the chunk's
            machines report as their training durations."""
            nonlocal chunk_done
            with _stage("wait"):
                jax.block_until_ready(outputs)
            done = time.time()
            wall, chunk_done = done - chunk_done, done
            return wall

        def fetch(group, outputs):
            with _stage("d2h"):
                params_stack, losses, fold_preds, counted, non_finite = outputs
                if not multiprocess:
                    # one batched host transfer for the whole tree
                    losses_np = np.asarray(jax.device_get(losses))
                    rows = np.arange(losses_np.shape[0])
                    counted_np = jax.device_get(counted)
                    params_np = jax.device_get(params_stack)
                    fold_preds_np = [np.asarray(jax.device_get(fp)) for fp in fold_preds]
                    non_finite_np = np.asarray(jax.device_get(non_finite))
                else:
                    # multi-process: only this host's rows are addressable;
                    # every output shares the machines sharding, so the rows
                    # from `losses` apply to all leaves
                    rows, losses_np = distributed.local_rows(losses)
                    counted_np = {
                        k: distributed.local_rows(v)[1] for k, v in counted.items()
                    }
                    params_np = jax.tree_util.tree_map(
                        lambda a: distributed.local_rows(a)[1], params_stack
                    )
                    fold_preds_np = [distributed.local_rows(fp)[1] for fp in fold_preds]
                    non_finite_np = distributed.local_rows(non_finite)[1]
                _D2H.inc(_nbytes(
                    (params_np, losses_np, fold_preds_np, non_finite_np, counted_np)
                ))
                _note_layer_counts(counted_np, rows, len(group))
                return group, rows, params_np, losses_np, fold_preds_np, non_finite_np

        # host-side assembly per machine (its slice of the chunk, threshold
        # stats, scores, metadata, the dump: ~25ms each on the chip's host,
        # PERF.md §5) runs on a thread pool, enqueued per chunk AS SOON as
        # that chunk is fetched — it overlaps the next chunks' device time
        # instead of serializing after the whole fleet has trained. The build
        # thread only submits
        futures = []

        def enqueue_assembly(pool, fetched, wall, chunk_start):
            with _stage("slice", chunk_start=chunk_start):
                group, rows, *stacks = fetched
                per_machine = wall / len(group)
                for j, row in enumerate(int(r) for r in rows):
                    if row >= len(group):
                        continue  # padding rows replicate the first lane; skip
                    plan, idx = group[row]
                    assembled = pool.submit(
                        self._assemble_and_persist, plan, j, *stacks,
                        fold_bounds, per_machine, kfold_folds,
                    )
                    futures.append((idx, plan, assembled))

        # keep at most 2 chunks in flight: dispatch chunk k+1 (async) before
        # fetching chunk k, so transfers overlap compute while peak HBM stays
        # O(chunk) rather than O(M)
        bucket_name = f"{plan0.machine.name}+{M - 1}"
        with ThreadPoolExecutor(max_workers=8) as pool:
            # jit compiles synchronously during the first call (execution is
            # dispatched async), so the first-dispatch span is the compile
            # span — on a warm program cache it collapses to device_put time.
            # compile and train are parents: the stages inside dispatch(),
            # wait(), fetch() and enqueue_assembly() tile them
            with telemetry.span(
                "compile", _PHASE_COMPILE, bucket=bucket_name,
                machines=M, cached=program_cached,
            ):
                t_compile = time.time()
                try:
                    in_flight = dispatch(starts[0], first_group)
                    in_flight_start = starts[0]
                except Exception as exc:
                    if not program_cached:
                        self._note_compile_failure(bucket_name, M, exc)
                    raise
                if not program_cached:
                    _first_compile_walls[program_key] = time.time() - t_compile
            with telemetry.span(
                "train", _PHASE_TRAIN, bucket=bucket_name, machines=M,
                chunk=chunk,
            ):
                for start in starts[1:]:
                    group = take_late(start, in_flight[1])
                    if not group:
                        continue  # every machine of the chunk was lost
                    next_in_flight = dispatch(start, group)
                    wall = wait(*in_flight)
                    enqueue_assembly(
                        pool, fetch(*in_flight), wall, in_flight_start
                    )
                    in_flight, in_flight_start = next_in_flight, start
                # the device's work on this bucket ends with this wait
                wall = wait(*in_flight)
            # ... and everything after it is the tail: the last chunk's pull
            # and submits, and the assembly pool's drain
            with _stage("tail", bucket=bucket_name, machines=M):
                enqueue_assembly(pool, fetch(*in_flight), wall, in_flight_start)
                logger.info(
                    "Batched bucket: %d machines (chunk %d, %s start) trained "
                    "in %.2fs",
                    M, chunk, "warm" if warm else "cold", chunk_done - t0,
                )
                out = []
                with _stage("drain"):
                    # a diverged lane comes back as its verdict and is
                    # quarantined here: _quarantine is the build thread's
                    for idx, plan, assembled in futures:
                        result = assembled.result()
                        if isinstance(result, QuarantineRecord):
                            self._quarantine(plan.machine, result)
                        else:
                            out.append((idx, result))
                return out

    # --------------------------------------------------------- assembly
    def _assemble_and_persist(
        self, plan: _Plan, j: int, params_stack, losses, fold_preds, non_finite,
        fold_bounds, per_machine: float, kfold_folds=None,
    ):
        """The pool job that makes one machine final, once: lane ``j`` of its
        chunk's outputs sliced and checked (its losses here, its parameters
        by ``non_finite[j]``, the device's verdict), the machine assembled and
        its artifact written. Its durations are its share of its chunk's wall
        (``per_machine``: the wall over the chunk's live lanes), split by
        fold count, since the fused program interleaves CV-fold training
        with the final fit. Returns ``(model, machine)``; for a lane that
        diverged, its ``QuarantineRecord`` (raised under ``fail_fast``):
        nothing of it is persisted, and the build thread quarantines it when
        it takes the future."""
        name = plan.machine.name
        n_stages = len(fold_bounds) + 1
        with _machine_trace(name), telemetry.span(
            "assemble", _PHASE_ASSEMBLE, machine=name
        ):
            params = jax.tree_util.tree_map(lambda a: a[j], params_stack)
            # post-build divergence detection: a lane that trained to NaN/Inf
            # params (bad lr, degenerate data) is quarantined — its garbage
            # must not be persisted as a servable artifact
            bad = faults.params_non_finite(None, losses[j])
            if bad is None and non_finite[j]:
                # only a lane the device flagged is walked, to name the leaf
                bad = (
                    faults.params_non_finite(params)
                    or "non-finite model parameters"
                )
            if bad is None and faults.should_fire("diverge", name):
                bad = "injected divergence"
            if bad is not None:
                if self.fail_fast:
                    raise faults.DivergedModelError(f"machine {name}: {bad}")
                return QuarantineRecord(
                    name, faults.STAGE_TRAINING, "diverged", bad
                )
            built = self._assemble(
                plan, params, losses[j], [fp[j] for fp in fold_preds],
                fold_bounds,
                per_machine / n_stages,
                per_machine * len(fold_bounds) / n_stages,
                kfold_folds,
            )
        self._persist(plan.machine, *built)
        metric_catalog.BUILD_MACHINES.labels(outcome="built").inc()
        return built

    def _assemble(
        self,
        plan: _Plan,
        params,
        losses: np.ndarray,
        fold_preds: List[np.ndarray],
        fold_bounds,
        train_duration: float,
        cv_duration: float,
        kfold_folds=None,
    ) -> Tuple[Any, Machine]:
        machine = plan.machine
        X, y, index = plan.X, plan.y, plan.index

        # the inner JAX estimator, fitted
        est = plan.estimator_cls(**plan.estimator_params)
        est.spec_ = plan.spec
        est.params_ = params
        est.history = {
            "loss": [float(l) for l in losses],
            "params": {
                "epochs": plan.epochs,
                "batch_size": plan.batch_size,
                "metrics": ["loss"],
            },
        }

        model: Any = est
        if plan.scale_x:
            mm = MinMaxScaler().fit(X)
            model = Pipeline([("step_0", mm), ("step_1", est)])

        if plan.wrap_anomaly:
            detector_cls = (
                DiffBasedKFCVAnomalyDetector if plan.kfcv else DiffBasedAnomalyDetector
            )
            detector = detector_cls(
                base_estimator=model,
                scaler=MinMaxScaler(),
                **plan.anomaly_kwargs,
            )
            detector.scaler.fit(y)
            if plan.kfcv:
                self._set_kfcv_thresholds(
                    detector, plan, fold_preds, fold_bounds, kfold_folds
                )
            else:
                self._set_thresholds(detector, plan, fold_preds, fold_bounds)
            model = detector

        scores = self._fold_scores(plan, fold_preds, fold_bounds, kfold_folds)
        splits = self._split_metadata(index, fold_bounds, kfold_folds)

        machine_out = Machine(
            name=machine.name,
            dataset=machine.dataset.to_dict(),
            metadata=machine.metadata,
            model=machine.model,
            project_name=machine.project_name,
            evaluation=machine.evaluation,
            runtime=machine.runtime,
        )
        machine_out.metadata.build_metadata = BuildMetadata(
            model=ModelBuildMetadata(
                model_offset=plan.spec.output_offset,
                model_creation_date=str(
                    datetime.datetime.now(datetime.timezone.utc).astimezone()
                ),
                model_builder_version=__version__,
                model_training_duration_sec=train_duration,
                cross_validation=CrossValidationMetaData(
                    cv_duration_sec=cv_duration, scores=scores, splits=splits
                ),
                model_meta=ModelBuilder._extract_metadata_from_model(model),
            ),
            dataset=DatasetBuildMetadata(
                query_duration_sec=plan.query_duration,
                dataset_meta=plan.dataset_meta,
            ),
            fault_domain=(
                {"quarantined": False, "data_fetch_attempts": plan.fetch_attempts}
                if plan.fetch_attempts > 1
                else {}
            ),
            # serial-path parity (build_model.py): the batched equivalents
            # are apportioned shares of the chunk's wall, like the legacy
            # duration fields above
            phases={
                "fetch": plan.query_duration,
                "cross_validation": cv_duration,
                "fit": train_duration,
            },
        )
        return model, machine_out

    @staticmethod
    def _rolling_min_max(a: np.ndarray, window: int):
        """pandas ``rolling(window).min().max()``: max over sliding-window
        minima, where a window containing NaN has a NaN min and the final
        max skips NaN windows (pandas skipna). Uses the O(n) native kernel
        when built; numpy sliding-window fallback otherwise. For a 2D array
        the reduction is per column; returns scalar for 1D input."""
        from gordo_tpu import native

        if native.available():
            if a.ndim == 1:
                return native.rolling_min_max(a, window)
            return np.array(
                [native.rolling_min_max(a[:, d], window) for d in range(a.shape[1])]
            )
        if a.shape[0] < window:
            return (
                np.nan if a.ndim == 1 else np.full(a.shape[1:], np.nan)
            )
        mins = np.lib.stride_tricks.sliding_window_view(a, window, axis=0).min(
            axis=-1
        )
        # nanmax skips NaN windows (pandas skipna); it warns on all-NaN
        # slices, where the NaN result is exactly what pandas returns
        import warnings as _warnings

        with _warnings.catch_warnings():
            _warnings.simplefilter("ignore", RuntimeWarning)
            return np.nanmax(mins, axis=0)

    def _set_thresholds(self, detector, plan, fold_preds, fold_bounds):
        """Replicate DiffBasedAnomalyDetector.cross_validate's threshold math
        (reference diff.py:184-276) from the in-program fold predictions.
        Pure numpy (sliding-window minima instead of pandas rolling): at 1k+
        machines the pandas-object overhead dominated assembly time."""
        offset = plan.spec.output_offset
        detector.aggregate_thresholds_per_fold_ = {}
        detector.smooth_aggregate_thresholds_per_fold_ = {}
        feature_rows = []
        smooth_rows = []
        tag_thresholds_fold = None
        aggregate_threshold_fold = None
        smooth_tag = None
        smooth_agg = None

        for k, ((tr_end, te_start, te_end), y_pred) in enumerate(
            zip(fold_bounds, fold_preds)
        ):
            y_true = plan.y[te_start + offset : te_end]
            # per-fold scaling by the fold's train targets (MinMaxScaler
            # semantics, parity with a fold-fitted detector's scaler)
            train_y = plan.y[:tr_end]
            mn = train_y.min(axis=0)
            rng = train_y.max(axis=0) - mn
            # sklearn's _handle_zeros_in_scale: near-zero range ⇒ constant
            tiny = 10 * np.finfo(rng.dtype).eps
            scale = 1.0 / np.where(rng < tiny, 1.0, rng)
            scaled_mse = (((y_pred - y_true) * scale) ** 2).mean(axis=1)
            mae = np.abs(y_true - y_pred)

            aggregate_threshold_fold = float(self._rolling_min_max(scaled_mse, 6))
            detector.aggregate_thresholds_per_fold_[f"fold-{k}"] = (
                aggregate_threshold_fold
            )
            tag_thresholds_fold = pd.Series(
                self._rolling_min_max(mae, 6), name=f"fold-{k}"
            )
            feature_rows.append(tag_thresholds_fold)
            if detector.window is not None:
                smooth_agg = float(self._rolling_min_max(scaled_mse, detector.window))
                detector.smooth_aggregate_thresholds_per_fold_[f"fold-{k}"] = smooth_agg
                smooth_tag = pd.Series(
                    self._rolling_min_max(mae, detector.window), name=f"fold-{k}"
                )
                smooth_rows.append(smooth_tag)

        detector.feature_thresholds_per_fold_ = (
            pd.DataFrame(feature_rows) if feature_rows else pd.DataFrame()
        )
        detector.smooth_feature_thresholds_per_fold_ = (
            pd.DataFrame(smooth_rows) if smooth_rows else pd.DataFrame()
        )
        detector.feature_thresholds_ = tag_thresholds_fold
        detector.aggregate_threshold_ = aggregate_threshold_fold
        detector.smooth_aggregate_threshold_ = smooth_agg
        detector.smooth_feature_thresholds_ = smooth_tag

    def _set_kfcv_thresholds(
        self, detector, plan, fold_preds, fold_bounds, kfold_folds=None
    ):
        """Percentile thresholds from the in-program fold predictions.

        Serial parity (DiffBasedKFCVAnomalyDetector.cross_validate, reference
        diff.py:465-645): scatter each fold's validation predictions into
        full-length series — rows no fold visits stay zero for y_pred and NaN
        for the mse series, exactly as the serial path initializes them —
        then smooth with the detector's configured method and take its
        percentile. The per-fold mse scaling uses the fold model's y-scaler
        stats, i.e. min/max of that fold's train targets.

        With ``kfold_folds`` (seeded-KFold geometry) the scatter targets are
        each fold's test index array and the scaler stats come from its
        train index array; the fold predictions were computed over a
        padded test tail, so only the last ``len(test_idx)`` rows are real.
        """
        y = plan.y
        y_pred = np.zeros_like(y)
        val_mse = np.full(len(y), np.nan, dtype=y.dtype)
        if kfold_folds is not None:
            for (train_idx, test_idx), pred_padded in zip(kfold_folds, fold_preds):
                pred = pred_padded[-len(test_idx):]
                y_true = y[test_idx]
                train_y = y[train_idx]
                mn = train_y.min(axis=0)
                rng = train_y.max(axis=0) - mn
                tiny = 10 * np.finfo(rng.dtype).eps
                scale = 1.0 / np.where(rng < tiny, 1.0, rng)
                y_pred[test_idx] = pred
                val_mse[test_idx] = (((pred - y_true) * scale) ** 2).mean(axis=1)
            detector.aggregate_threshold_ = float(
                detector._calculate_threshold(pd.Series(val_mse))
            )
            detector.feature_thresholds_ = detector._calculate_threshold(
                pd.DataFrame(np.abs(y - y_pred))
            )
            return
        for (tr_end, te_start, te_end), pred in zip(fold_bounds, fold_preds):
            y_true = y[te_start:te_end]
            train_y = y[:tr_end]
            mn = train_y.min(axis=0)
            rng = train_y.max(axis=0) - mn
            tiny = 10 * np.finfo(rng.dtype).eps
            scale = 1.0 / np.where(rng < tiny, 1.0, rng)
            y_pred[te_start:te_end] = pred
            val_mse[te_start:te_end] = (((pred - y_true) * scale) ** 2).mean(axis=1)

        detector.aggregate_threshold_ = float(
            detector._calculate_threshold(pd.Series(val_mse))
        )
        detector.feature_thresholds_ = detector._calculate_threshold(
            pd.DataFrame(np.abs(y - y_pred))
        )

    def _fold_scores(
        self, plan, fold_preds, fold_bounds, kfold_folds=None
    ) -> Dict[str, Any]:
        """Per-tag + aggregate fold scores, matching the serial builder's
        scorer names/shape (build_model.py:351-420)."""
        evaluation = plan.machine.evaluation
        metric_names = []
        for m in evaluation.get("metrics") or [
            "explained_variance_score",
            "r2_score",
            "mean_squared_error",
            "mean_absolute_error",
        ]:
            short = m.rsplit(".", 1)[-1]
            if short in _METRIC_NAMES:
                metric_names.append(short)

        scaler = None
        scoring_scaler = evaluation.get("scoring_scaler")
        if scoring_scaler:
            scaler = (
                serializer.from_definition(scoring_scaler)
                if isinstance(scoring_scaler, (str, dict))
                else scoring_scaler
            )
            scaler.fit(plan.y)

        offset = plan.spec.output_offset
        scores: Dict[str, Any] = {}
        per_metric_fold_cols: Dict[str, List[np.ndarray]] = {m: [] for m in metric_names}
        per_metric_fold_agg: Dict[str, List[float]] = {m: [] for m in metric_names}

        if kfold_folds is not None:
            fold_pairs = [
                (plan.y[test_idx], pred_padded[-len(test_idx):])
                for (_, test_idx), pred_padded in zip(kfold_folds, fold_preds)
            ]
        else:
            fold_pairs = [
                (plan.y[te_start + offset : te_end], y_pred)
                for (tr_end, te_start, te_end), y_pred in zip(
                    fold_bounds, fold_preds
                )
            ]
        for y_true, y_pred in fold_pairs:
            yt, yp = y_true, y_pred
            if scaler is not None:
                yt = scaler.transform(yt)
                yp = scaler.transform(yp)
            yt3, yp3 = yt[None], yp[None]
            for m in metric_names:
                cols = _metric_per_column(m, yt3, yp3)[0]
                per_metric_fold_cols[m].append(cols)
                per_metric_fold_agg[m].append(float(cols.mean()))

        for m in metric_names:
            metric_str = m.replace("_", "-")
            cols_per_fold = np.stack(per_metric_fold_cols[m])  # (folds, D)
            for d, col in enumerate(plan.target_columns):
                vals = cols_per_fold[:, d]
                entry = {
                    "fold-mean": float(vals.mean()),
                    "fold-std": float(vals.std()),
                    "fold-max": float(vals.max()),
                    "fold-min": float(vals.min()),
                }
                entry.update({f"fold-{k+1}": float(v) for k, v in enumerate(vals)})
                scores[f"{metric_str}-{col.replace(' ', '-')}"] = entry
            agg = np.array(per_metric_fold_agg[m])
            entry = {
                "fold-mean": float(agg.mean()),
                "fold-std": float(agg.std()),
                "fold-max": float(agg.max()),
                "fold-min": float(agg.min()),
            }
            entry.update({f"fold-{k+1}": float(v) for k, v in enumerate(agg)})
            scores[metric_str] = entry
        return scores

    def _split_metadata(self, index, fold_bounds, kfold_folds=None) -> Dict[str, Any]:
        splits: Dict[str, Any] = {}
        if kfold_folds is not None:
            # mirror the serial builder's build_split_dict keys exactly
            # (builder/build_model.py) — shuffled folds have no contiguous
            # date range; first/last visited rows are what it records
            for k, (train_rows, test_rows) in enumerate(kfold_folds, start=1):
                for part, rows in (("train", train_rows), ("test", test_rows)):
                    splits[f"fold-{k}-{part}-start"] = index[rows[0]]
                    splits[f"fold-{k}-{part}-end"] = index[rows[-1]]
                    splits[f"fold-{k}-n-{part}"] = len(rows)
            return splits
        for k, (tr_end, te_start, te_end) in enumerate(fold_bounds):
            splits.update(
                {
                    f"fold-{k+1}-train-start": index[0],
                    f"fold-{k+1}-train-end": index[tr_end - 1],
                    f"fold-{k+1}-test-start": index[te_start],
                    f"fold-{k+1}-test-end": index[te_end - 1],
                    f"fold-{k+1}-n-train": tr_end,
                    f"fold-{k+1}-n-test": te_end - te_start,
                }
            )
        return splits
