"""
The fused training engine.

One epoch = one XLA program: ``lax.scan`` over minibatches with in-place
(donated) parameter updates. Static shapes throughout — the sample count is
padded up to a whole number of batches with zero-weighted index padding, so
XLA compiles exactly one program per (spec, n_samples-bucket, batch_size).

Windowed (LSTM) models never materialize the window tensor in HBM: each scan
step gathers its (batch, lookback, features) block from the flat series,
trading a tiny gather for O(lookback)× memory. Window/lookahead semantics
match the reference's timeseries generator (gordo/machine/model/models.py:
715-796): window i covers rows [i, i+lookback) and its target is row
i + lookback - 1 + lookahead.

Host↔device traffic: X/y are transferred once per ``fit``; per-epoch work is
a single device call returning a scalar loss.
"""

import functools
import logging
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from gordo_tpu.models.spec import ModelSpec, OptimizerSpec
from .nn import apply_model, apply_model_stats, zero_stats

logger = logging.getLogger(__name__)


# --------------------------------------------------------------- optimizers
def make_optimizer(spec: OptimizerSpec) -> optax.GradientTransformation:
    """Build an optax optimizer from a Keras-style optimizer spec."""
    kwargs = spec.as_dict()
    lr = kwargs.pop("learning_rate", kwargs.pop("lr", None))
    name = spec.name.lower()
    if name == "adam":
        return optax.adam(
            learning_rate=lr if lr is not None else 1e-3,
            b1=kwargs.get("beta_1", 0.9),
            b2=kwargs.get("beta_2", 0.999),
            eps=kwargs.get("epsilon", 1e-7),
        )
    if name == "sgd":
        return optax.sgd(
            learning_rate=lr if lr is not None else 1e-2,
            momentum=kwargs.get("momentum", 0.0) or None,
            nesterov=kwargs.get("nesterov", False),
        )
    if name == "rmsprop":
        return optax.rmsprop(
            learning_rate=lr if lr is not None else 1e-3,
            decay=kwargs.get("rho", 0.9),
            eps=kwargs.get("epsilon", 1e-7),
            momentum=kwargs.get("momentum", 0.0),
        )
    if name == "adagrad":
        return optax.adagrad(learning_rate=lr if lr is not None else 1e-3)
    if name == "nadam":
        return optax.nadam(learning_rate=lr if lr is not None else 1e-3)
    if name == "adamax":
        return optax.adamax(learning_rate=lr if lr is not None else 1e-3)
    if name == "adamw":
        return optax.adamw(learning_rate=lr if lr is not None else 1e-3)
    raise ValueError(f"Unknown optimizer {spec.name!r}")


def _loss_terms(spec: ModelSpec, params, xb, yb, wb):
    """``(loss, stats)``: the weighted batch loss, and what the model's layers
    counted on the way (:func:`gordo_tpu.ops.nn.apply_model_stats`)."""
    out, penalty, stats = apply_model_stats(spec, params, xb)
    if spec.loss in ("mse", "mean_squared_error"):
        per_sample = jnp.mean((out - yb) ** 2, axis=-1)
    elif spec.loss in ("mae", "mean_absolute_error"):
        per_sample = jnp.mean(jnp.abs(out - yb), axis=-1)
    else:
        raise ValueError(f"Unknown loss {spec.loss!r}")
    w_sum = jnp.maximum(jnp.sum(wb), 1.0)
    return jnp.sum(per_sample * wb) / w_sum + penalty, stats


@jax.named_scope("window_gather")
def _gather_batch(spec: ModelSpec, X, y, idx):
    """Gather a minibatch by sample (or window-start) indices."""
    if spec.lookback_window <= 1 and spec.lookahead == 0:
        return X[idx], y[idx]
    window = jnp.arange(spec.lookback_window)
    xb = X[idx[:, None] + window[None, :]]  # (B, L, D)
    yb = y[idx + spec.lookback_window - 1 + spec.lookahead]
    return xb, yb


def n_train_samples(spec: ModelSpec, n_rows: int) -> int:
    """Number of training samples (windows) obtainable from n_rows rows."""
    if spec.lookback_window <= 1 and spec.lookahead == 0:
        return n_rows
    return max(n_rows - spec.lookback_window + 1 - spec.lookahead, 0)


# ----------------------------------------------------------- jitted kernels
def make_epoch_fn(
    spec: ModelSpec, n_samples: int, batch_size: int, shuffle: bool
) -> Callable:
    """
    Pure single-epoch step ``epoch(params, opt_state, X, y, rng) ->
    (params, opt_state, mean_loss)``: one ``lax.scan`` over minibatches with
    zero-weighted index padding. The host-loop trainer (``fit_arrays``)
    runs it; the fleet program's ``make_masked_epoch_fn`` is the same body
    with a traced live-sample count, and a test holds the two equal.
    """
    n_steps = max((n_samples + batch_size - 1) // batch_size, 1)
    n_pad = n_steps * batch_size
    opt = make_optimizer(spec.optimizer)
    from gordo_tpu.parallel.data_parallel import batch_constraint, dp_degree

    dp = dp_degree(spec)

    def epoch(params, opt_state, X, y, rng):
        base_idx = jnp.arange(n_samples)
        if shuffle:
            base_idx = jax.random.permutation(rng, n_samples)
        # pad index stream with zero-weighted repeats of index 0
        idx_stream = jnp.concatenate(
            [base_idx, jnp.zeros((n_pad - n_samples,), base_idx.dtype)]
        )
        w_stream = jnp.concatenate(
            [jnp.ones((n_samples,), jnp.float32), jnp.zeros((n_pad - n_samples,), jnp.float32)]
        )

        def body(carry, i):
            params, opt_state, loss_sum, w_sum = carry
            idx = jax.lax.dynamic_slice(idx_stream, (i * batch_size,), (batch_size,))
            wb = jax.lax.dynamic_slice(w_stream, (i * batch_size,), (batch_size,))
            xb, yb = _gather_batch(spec, X, y, idx)
            if dp > 1:
                # batch axis split over the `data` mesh: GSPMD partitions
                # fwd/bwd and all-reduces the grads (params replicated)
                xb, yb, wb = batch_constraint(spec, xb, yb, wb)
            (loss, _), grads = jax.value_and_grad(
                _loss_terms, argnums=1, has_aux=True
            )(spec, params, xb, yb, wb)
            with jax.named_scope("optimizer_update"):
                updates, opt_state = opt.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
            bw = jnp.sum(wb)
            return (params, opt_state, loss_sum + loss * bw, w_sum + bw), None

        init = (params, opt_state, jnp.asarray(0.0), jnp.asarray(0.0))
        (params, opt_state, loss_sum, w_sum), _ = jax.lax.scan(
            body, init, jnp.arange(n_steps)
        )
        return params, opt_state, loss_sum / jnp.maximum(w_sum, 1.0)

    return epoch


@functools.lru_cache(maxsize=256)
def _build_epoch_fn(
    spec: ModelSpec, n_samples: int, batch_size: int, shuffle: bool
) -> Callable:
    return jax.jit(
        make_epoch_fn(spec, n_samples, batch_size, shuffle), donate_argnums=(0, 1)
    )


@functools.lru_cache(maxsize=256)
def _build_eval_fn(spec: ModelSpec, n_samples: int, batch_size: int = 2048) -> Callable:
    """Full-dataset loss, batched with the same padding scheme (no grad)."""
    n_steps = max((n_samples + batch_size - 1) // batch_size, 1)
    n_pad = n_steps * batch_size

    def evaluate(params, X, y):
        idx_stream = jnp.concatenate(
            [jnp.arange(n_samples), jnp.zeros((n_pad - n_samples,), jnp.int32)]
        )
        w_stream = jnp.concatenate(
            [jnp.ones((n_samples,), jnp.float32), jnp.zeros((n_pad - n_samples,), jnp.float32)]
        )

        def body(carry, i):
            loss_sum, w_sum = carry
            idx = jax.lax.dynamic_slice(idx_stream, (i * batch_size,), (batch_size,))
            wb = jax.lax.dynamic_slice(w_stream, (i * batch_size,), (batch_size,))
            xb, yb = _gather_batch(spec, X, y, idx)
            loss, _ = _loss_terms(spec, params, xb, yb, wb)
            bw = jnp.sum(wb)
            return (loss_sum + loss * bw, w_sum + bw), None

        (loss_sum, w_sum), _ = jax.lax.scan(
            body, (jnp.asarray(0.0), jnp.asarray(0.0)), jnp.arange(n_steps)
        )
        return loss_sum / jnp.maximum(w_sum, 1.0)

    return jax.jit(evaluate)


def evaluate_loss(spec: ModelSpec, params, X, y) -> float:
    n = n_train_samples(spec, len(X))
    fn = _build_eval_fn(spec, n)
    return float(fn(params, jnp.asarray(X), jnp.asarray(y)))


def make_masked_epoch_fn(
    spec: ModelSpec, n_max: int, batch_size: int, shuffle: bool
) -> Callable:
    """
    Like :func:`make_epoch_fn` but the live-sample count is a *traced* value
    ``n_valid <= n_max``: the index stream is ordered valid-first (shuffled
    within the valid prefix when ``shuffle``), trailing all-padding batches
    are optimizer no-ops (params and opt state carried through unchanged, so
    Adam's moments/step-count see exactly the live steps).

    This is what lets the batched trainer run every CV fold — each a
    different train-prefix length — through ONE compiled body inside a
    ``lax.scan`` over folds, instead of unrolling a separately-shaped fit per
    fold. Compile time of the fleet program drops by ~the fold count.

    The minibatch loop is a ``lax.while_loop`` with the live step count
    ``ceil(n_valid / batch_size)`` as its (traced) bound, so short folds run
    only their live steps instead of the full-fit step count — the static
    schedule was measured executing ~1.6x the live work across a 3-fold CV
    build, each dead step a full windowed forward+backward for LSTM/
    Transformer fleets. Fold schedules are uniform across a bucket's
    machines, so under the machine vmap every lane ends at the same bound;
    a non-uniform caller still gets correct results (late lanes' steps are
    zero-weight masked no-ops), just max-lane timing.

    Returns ``(params, opt_state, mean_loss, stats)``; ``stats`` is what the
    model's layers counted (``apply_model_stats``), summed over the live
    steps: an empty dict for a spec with no routed layer.
    """
    n_steps = max((n_max + batch_size - 1) // batch_size, 1)
    n_pad = n_steps * batch_size
    opt = make_optimizer(spec.optimizer)
    grad_fn = jax.value_and_grad(_loss_terms, argnums=1, has_aux=True)

    def epoch(params, opt_state, X, y, rng, n_valid):
        pos = jnp.arange(n_max)
        if shuffle:
            # valid-first shuffled order: push invalid keys after every valid
            keys = jax.random.uniform(rng, (n_max,))
            order = jnp.argsort(jnp.where(pos < n_valid, keys, keys + 2.0))
        else:
            order = pos
        live = order < n_valid
        # clamp dead slots to sample 0 (make_epoch_fn's padding convention):
        # without this, zero-weighted rows past the fold's train prefix would
        # still leak into the unweighted activity penalty in _loss_terms
        order = jnp.where(live, order, 0)
        idx_stream = jnp.concatenate(
            [order, jnp.zeros((n_pad - n_max,), order.dtype)]
        )
        w_stream = jnp.concatenate(
            [
                live.astype(jnp.float32),
                jnp.zeros((n_pad - n_max,), jnp.float32),
            ]
        )
        n_live_steps = jnp.clip(
            (n_valid + batch_size - 1) // batch_size, 1, n_steps
        )

        def cond(state):
            return state[0] < n_live_steps

        def body(state):
            i, params, opt_state, loss_sum, w_sum, stats_sum = state
            idx = jax.lax.dynamic_slice(idx_stream, (i * batch_size,), (batch_size,))
            wb = jax.lax.dynamic_slice(w_stream, (i * batch_size,), (batch_size,))
            xb, yb = _gather_batch(spec, X, y, idx)
            (loss, stats), grads = grad_fn(spec, params, xb, yb, wb)
            bw = jnp.sum(wb)
            live = bw > 0
            # the scope holds the live-step select too: XLA fuses the update
            # into it and names the fusion after its root
            with jax.named_scope("optimizer_update"):
                updates, new_opt_state = opt.update(grads, opt_state, params)
                new_params = optax.apply_updates(params, updates)
                pick = functools.partial(
                    jax.tree_util.tree_map, lambda a, b: jnp.where(live, a, b)
                )
                params = pick(new_params, params)
                opt_state = pick(new_opt_state, opt_state)
            loss = jnp.where(live, loss, 0.0)
            stats_sum = {
                key: total + jnp.where(live, stats[key], 0)
                for key, total in stats_sum.items()
            }
            return (
                i + 1, params, opt_state, loss_sum + loss * bw, w_sum + bw,
                stats_sum,
            )

        # the sums and the optimizer's zeros start as one value for every
        # machine; under the builder's vmap an unbatched carry that the body
        # returns batched makes JAX trace the body (the model and its
        # gradient) a second time to find that out. Tied to the machine's own
        # key they are batched from the start: a third of a large model's
        # trace (37 s to 24 s at 0.64 billion parameters); XLA folds the zero
        lane = jax.random.key_data(rng).ravel()[0] * 0
        per_machine = functools.partial(
            jax.tree_util.tree_map, lambda a: a + lane.astype(a.dtype)
        )
        init = (
            jnp.asarray(0, n_live_steps.dtype), params,
            *per_machine((opt_state, jnp.asarray(0.0), jnp.asarray(0.0), zero_stats(spec))),
        )
        _, params, opt_state, loss_sum, w_sum, stats = jax.lax.while_loop(
            cond, body, init
        )
        return params, opt_state, loss_sum / jnp.maximum(w_sum, 1.0), stats

    return epoch


# ------------------------------------------------------------------ fitting
@dataclass
class TrainResult:
    params: Any
    history: Dict[str, List[float]] = field(default_factory=dict)
    epochs_trained: int = 0


def fit_arrays(
    spec: ModelSpec,
    params,
    X: np.ndarray,
    y: np.ndarray,
    *,
    epochs: int = 1,
    batch_size: int = 32,
    shuffle: bool = True,
    validation_split: float = 0.0,
    rng: Optional[jax.Array] = None,
    callbacks: Optional[List] = None,
) -> TrainResult:
    """
    Train ``params`` on (X, y). Host loop over epochs; each epoch is one
    device call. Supports Keras-style validation_split (holds out the *last*
    fraction of samples, as Keras does) and EarlyStopping-style callbacks.
    """
    X = jnp.asarray(X, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    callbacks = callbacks or []

    n_rows = len(X)
    if validation_split and 0.0 < validation_split < 1.0:
        split = max(int(n_rows * (1.0 - validation_split)), 1)
        X_train, y_train = X[:split], y[:split]
        X_val, y_val = X[split:], y[split:]
    else:
        X_train, y_train = X, y
        X_val = y_val = None

    n_samples = n_train_samples(spec, len(X_train))
    if n_samples <= 0:
        raise ValueError(
            f"Not enough rows ({len(X_train)}) for lookback_window="
            f"{spec.lookback_window} lookahead={spec.lookahead}"
        )
    batch_size = min(batch_size, max(n_samples, 1))
    from gordo_tpu.parallel.data_parallel import (
        dp_degree,
        dp_mesh,
        replicate_params_dp,
    )
    from gordo_tpu.parallel.expert_parallel import ep_degree, shard_params_ep
    from gordo_tpu.parallel.pipeline_parallel import pp_degree, pp_mesh
    from gordo_tpu.parallel.tensor_parallel import shard_params_tp, tp_degree

    dp = dp_degree(spec)
    if dp > 1:
        dp_mesh(dp)  # training claims capacity: fail loudly on small hosts
        if batch_size % dp:
            if batch_size < dp:
                raise ValueError(
                    f"data_parallel={dp} but the effective batch size is "
                    f"{batch_size}; the split needs at least one sample "
                    f"per chip"
                )
            # round down so every chip gets equal batch slices
            batch_size -= batch_size % dp
        params = replicate_params_dp(spec, params)
    pp = pp_degree(spec)
    if pp > 1 and batch_size % pp:
        # the clamp above can break the divisibility fit() validated; a
        # non-divisible batch would silently run every step on the
        # sequential fallback, so round down to re-engage the pipe — or
        # fail loudly when the dataset is smaller than the stage count
        if batch_size < pp:
            raise ValueError(
                f"pipeline_parallel={pp} but only {n_samples} training "
                f"sample(s); the pipeline needs at least one sample per stage"
            )
        batch_size -= batch_size % pp
    if tp_degree(spec) > 1:
        # commit the weights to the `model` mesh; every jitted step below
        # then runs SPMD with XLA-inserted collectives, unchanged
        params = shard_params_tp(spec, params)
    if pp > 1:
        # training claims capacity: fail loudly here rather than silently
        # running every step on the sequential fallback (serving degrades
        # instead — apply_pipelined_blocks falls back with a warning)
        pp_mesh(pp)
    if ep_degree(spec) > 1:
        # commit expert weights to the `expert` mesh: each chip STORES its
        # E/N experts; grads and optimizer state inherit the sharding
        params = shard_params_ep(spec, params)
    epoch_fn = _build_epoch_fn(spec, n_samples, batch_size, shuffle)

    opt = make_optimizer(spec.optimizer)
    opt_state = opt.init(params)

    history: Dict[str, List[float]] = {"loss": []}
    if X_val is not None:
        history["val_loss"] = []
        if n_train_samples(spec, len(X_val)) <= 0:
            # a windowed model whose holdout is shorter than one lookback
            # window records NO val_loss — and EarlyStopping's fallback
            # would then silently monitor the TRAINING loss. Say so.
            logger.warning(
                "validation_split holdout (%d rows) yields no full "
                "lookback-%d window: val_loss will not be recorded and "
                "callbacks monitoring it fall back to training loss",
                len(X_val), spec.lookback_window,
            )

    for cb in callbacks:
        if hasattr(cb, "on_train_begin"):
            cb.on_train_begin()

    epochs_trained = 0
    stop = False
    for epoch in range(epochs):
        rng, epoch_rng = jax.random.split(rng)
        params, opt_state, loss = epoch_fn(params, opt_state, X_train, y_train, epoch_rng)
        logs = {"loss": float(loss)}
        if X_val is not None and len(X_val) > 0:
            n_val = n_train_samples(spec, len(X_val))
            if n_val > 0:
                val_fn = _build_eval_fn(spec, n_val)
                logs["val_loss"] = float(val_fn(params, X_val, y_val))
        for key, value in logs.items():
            history.setdefault(key, []).append(value)
        epochs_trained = epoch + 1
        for cb in callbacks:
            if hasattr(cb, "on_epoch_end") and cb.on_epoch_end(epoch, logs, params):
                stop = True
        if stop:
            break

    for cb in callbacks:
        if hasattr(cb, "on_train_end"):
            restored = cb.on_train_end(params)
            if restored is not None:
                params = restored

    return TrainResult(params=params, history=history, epochs_trained=epochs_trained)


def predict_fn(spec: ModelSpec) -> Callable:
    """
    Return a cached, jitted predictor ``f(params, X) -> np.ndarray`` with
    power-of-two shape bucketing so serving-time requests of varying length
    hit a bounded set of compiled programs.
    """
    return _build_predictor(spec)


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


def note_trace_compile() -> None:
    """Mark one serving-path jit trace+compile.

    Called from INSIDE the traced function bodies — Python only executes
    those while jax traces, once per compiled program variant — so the
    counter (``gordo_server_trace_compiles_total``) prices exactly the
    trace+compile events the serving path paid. Warmup/AOT pre-lowering
    (server/warmup.py) exists to pay them all before traffic: steady
    state must read a flat 0."""
    from gordo_tpu.observability import metrics as metric_catalog

    metric_catalog.TRACE_COMPILES.inc()


@functools.lru_cache(maxsize=256)
def _build_predictor(spec: ModelSpec):
    @functools.lru_cache(maxsize=32)
    def padded_apply(n_pad: int):
        if spec.lookback_window <= 1 and spec.lookahead == 0:

            def run(params, X):
                note_trace_compile()
                out, _ = apply_model(spec, params, X)
                return out

        else:

            def run(params, X):
                note_trace_compile()
                idx = jnp.arange(n_pad)
                window = jnp.arange(spec.lookback_window)
                xb = X[idx[:, None] + window[None, :]]
                out, _ = apply_model(spec, params, xb)
                return out

        return jax.jit(run)

    def predict(params, X: np.ndarray) -> np.ndarray:
        X_pad, n_pad, n_keep = pad_for_predict(spec, X)
        out = padded_apply(n_pad)(params, jnp.asarray(X_pad))
        # transfer the padded buffer and slice on host: slicing the device
        # array first would dispatch a second program before the copy
        return np.asarray(out)[:n_keep]

    return predict


def pad_for_predict(spec: ModelSpec, X) -> Tuple[np.ndarray, int, int]:
    """
    Power-of-two padding for a serving-time predict.

    Returns ``(X_pad, n_pad, n_keep)``: the padded input, the bucketed
    output length the compiled program produces, and how many leading output
    rows are real. Shared between the per-request predictor
    (:func:`predict_fn`) and the cross-model batcher
    (server/batcher.py), so both hit the same compiled-shape buckets.
    """
    X = np.asarray(X, np.float32)
    n_out = n_train_samples(spec, len(X))
    if n_out <= 0:
        raise ValueError(
            f"Need at least {spec.lookback_window + spec.lookahead} rows, got {len(X)}"
        )
    if spec.lookback_window <= 1 and spec.lookahead == 0:
        n_pad = _next_pow2(len(X))
        X_pad = np.zeros((n_pad, X.shape[1]), np.float32)
        X_pad[: len(X)] = X
        return X_pad, n_pad, len(X)
    n_pad = _next_pow2(n_out)
    # pad the flat series so every window start up to n_pad is valid;
    # targets index up to n_pad-1 + lookback-1 + lookahead. Must also
    # hold all of X itself.
    rows_needed = max(n_pad + spec.lookback_window - 1 + spec.lookahead, len(X))
    X_pad = np.zeros((rows_needed, X.shape[1]), np.float32)
    X_pad[: len(X)] = X
    return X_pad, n_pad, n_out
