"""The plain reference: one machine's whole build in straightforward
``jax.numpy``, float32, every matmul at ``highest`` precision.

It imports nothing of the program and takes nothing the program has made:
the sensor rows come from :mod:`chipbench.traffic`, the model (its initial
weights from the seed by the published recipe, its forward pass) from the
configuration's own ``chipbench/configs/<reference>.py``, the batches from
the same key stream, Adam is written out. What it follows is what ``batch-build``
promises for a machine: per TimeSeriesSplit fold scale → init → fit →
predict the test slice, then the final fit on all rows, then the detector's
thresholds from the fold predictions.

``precision`` rounds the operands of every matmul: ``float32`` (the
reference), ``bfloat16`` (what the configurations state; a diagnostic) and
``float8`` (the control: the nearest precision below bfloat16).
``half_batch`` plants the fault "half of the batch left out, the mean taken
over the rest".

A machine of half a billion parameters has to fit beside nothing but itself:
the step is given its weights and Adam's two moments to update in place
(16 bytes a parameter with the gradient, not 28), and a stage's state is
freed before the next stage's weights are made.

No kernels, no cache, no vmap over a fleet: sampled machines are stacked on
a leading axis only so that one compiled step serves them all.
"""

from __future__ import annotations

import functools
import importlib
import json
import zlib
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
_ROUND = {
    "float32": None,
    "bfloat16": jnp.bfloat16,
    "float8": jnp.float8_e4m3fn,
}


def matmul(precision: str):
    """Matmul whose operands are rounded to ``precision`` and whose products
    accumulate in float32."""
    dtype = _ROUND[precision]

    def rnd(a):
        return a if dtype is None else a.astype(dtype).astype(jnp.float32)

    def mm(a, b):
        return jnp.matmul(rnd(a), rnd(b), precision=HIGHEST)

    return mm


# ------------------------------------------------------------- the fleet's
# conventions a reference has to share with any implementation: how a seed
# and a name become a key, and where the folds lie.
def machine_stream(name: str, seed: int) -> int:
    return (zlib.crc32(name.encode()) ^ (int(seed) * 2654435761)) & 0xFFFFFFFF


def fold_bounds(n_rows: int, n_splits: int) -> List[Tuple[int, int, int]]:
    """TimeSeriesSplit: equal test slices at the end, each fold trains on
    every row before its test slice. (train_end, test_start, test_end)."""
    test = n_rows // (n_splits + 1)
    starts = range(n_rows - n_splits * test, n_rows, test)
    return [(s, s, s + test) for s in starts]


def probe_rows(n_rows: int, n_splits: int) -> Tuple[int, int]:
    """The rows a finished model is applied to for the comparison: the last
    test slice."""
    _, start, end = fold_bounds(n_rows, n_splits)[-1]
    return start, end


# ------------------------------------------------------------------ models
def glorot(key, shape):
    limit = np.sqrt(6.0 / (shape[-2] + shape[-1]))
    return jax.random.uniform(key, shape, jnp.float32, -limit, limit)


def dense_init(key, n_in, n_out):
    return {"kernel": glorot(key, (n_in, n_out)), "bias": jnp.zeros((n_out,))}


ACT = {"tanh": jnp.tanh, "relu": jax.nn.relu, "linear": lambda x: x}


def model_reference(config: dict):
    """The configuration's own plain model, beside its file of sizes:
    ``chipbench/configs/<reference>.py`` with ``init_params`` and ``forward``."""
    return importlib.import_module(f"chipbench.configs.{config['reference']}")


# ---------------------------------------------------------------- training
def _windows(X, starts, lookback):
    return X[starts[:, None] + jnp.arange(lookback)[None, :]]


@functools.lru_cache(maxsize=None)
def _step_fn(reference: str, model_key: str, opt_key: str, precision: str):
    """The compiled training step and prediction of one configuration."""
    model, opt = json.loads(model_key), json.loads(opt_key)
    forward = model_reference({"reference": reference}).forward
    mm = matmul(precision)
    lookback = int(model["lookback_window"])
    lr, b1, b2, eps = (float(opt[k]) for k in
                       ("learning_rate", "beta_1", "beta_2", "epsilon"))

    def loss_fn(params, X, y, idx, w):
        out = forward(model, params, _windows(X, idx, lookback), mm)
        per_sample = ((out - y[idx + lookback - 1]) ** 2).mean(-1)
        return (per_sample * w).sum() / jnp.maximum(w.sum(), 1.0)

    def step(params, m, v, t, X, y, idx, w):
        loss, g = jax.value_and_grad(loss_fn)(params, X, y, idx, w)
        m = jax.tree_util.tree_map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
        v = jax.tree_util.tree_map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)

        def upd(p, mi, vi):
            return p - lr * (mi / (1 - b1**t)) / (jnp.sqrt(vi / (1 - b2**t)) + eps)

        return jax.tree_util.tree_map(upd, params, m, v), m, v, loss

    def predict(params, X, starts):
        return forward(model, params, _windows(X, starts, lookback), mm)

    # the sampled machines ride on a leading axis; `t`, the batch order's
    # length and the window starts are the same for all of them. The step
    # is given its state to overwrite: the caller keeps nothing of what it
    # passes as params, m and v
    return (
        jax.jit(
            jax.vmap(step, in_axes=(0, 0, 0, None, 0, 0, 0, 0)),
            donate_argnums=(0, 1, 2),
        ),
        jax.jit(jax.vmap(predict, in_axes=(0, 0, None))),
    )


def _minmax_scale(X, train_rows):
    mn, mx = X[:train_rows].min(0), X[:train_rows].max(0)
    span = mx - mn
    span = np.where(span < 10 * np.finfo(np.float32).eps, 1.0, span)
    return ((X - mn) / span).astype(np.float32)


def _rolling_min_max(a: np.ndarray, window: int):
    """max over the minima of every run of ``window`` consecutive rows."""
    mins = np.lib.stride_tricks.sliding_window_view(a, window, axis=0).min(-1)
    return mins.max(0)


def build_machines(
    config: dict,
    names: Sequence[str],
    frames: Sequence[np.ndarray],
    seed: int,
    precision: str = "float32",
    half_batch: bool = False,
) -> List[Dict[str, object]]:
    """Build each named machine from its (rows, tags) frame. Returns per
    machine: ``init``, ``first_grad`` and ``params`` of the final fit, its epoch ``loss``,
    the detector's ``aggregate_threshold`` and ``feature_thresholds``, and
    ``scaler`` stats needed to apply the model (x min/span, y min/span)."""
    model, n_tags = config["model"], int(config["n_tags"])
    if int(model["epochs"]) != 1:
        raise ValueError("the reference follows one epoch a stage")
    lookback, batch = int(model["lookback_window"]), int(model["batch_size"])
    init_params = model_reference(config).init_params
    step, predict = _step_fn(
        config["reference"],
        json.dumps(model, sort_keys=True),
        json.dumps(config["optimizer"], sort_keys=True),
        precision,
    )
    X_raw = np.stack(frames)  # (S, rows, tags); autoencoder: y is X
    n_rows = X_raw.shape[1]
    n_max = n_rows - lookback + 1
    folds = fold_bounds(n_rows, int(config["cv_splits"]))
    # the final fit is probed on the last test slice's rows
    stages = folds + [(n_rows, folds[-1][1], n_rows)]
    window = int(config["detector"]["threshold_window"])
    streams = [machine_stream(name, seed) for name in names]
    out: List[Dict[str, object]] = [dict() for _ in names]
    fold_errors = None
    with jax.default_matmul_precision("highest"):
        for k, (tr_end, te_start, te_end) in enumerate(stages):
            final = k == len(stages) - 1
            n_valid = tr_end - lookback + 1
            Xs = np.stack([_minmax_scale(x, tr_end) for x in X_raw])
            inits, orders = [], []
            for stream in streams:
                rng = jax.random.fold_in(jax.random.PRNGKey(0), np.uint32(stream))
                k_init, k_fit = jax.random.split(jax.random.fold_in(rng, k))
                inits.append(init_params(k_init, model, n_tags))
                keys = jax.random.uniform(
                    jax.random.split(k_fit, 1)[0], (n_max,)
                )
                pos = jnp.arange(n_max)
                order = jnp.argsort(jnp.where(pos < n_valid, keys, keys + 2.0))
                orders.append(np.asarray(order)[:n_valid])
            params = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *inits)
            del inits
            # the step overwrites what it is given: the initial weights go to
            # the host before the first one (a copy: on a CPU backend
            # np.asarray would be a view of the buffer given away)
            init = jax.tree_util.tree_map(np.array, params) if final else None
            m = jax.tree_util.tree_map(jnp.zeros_like, params)
            v = jax.tree_util.tree_map(jnp.zeros_like, params)
            t = 0
            order = np.stack(orders)  # (S, n_valid)
            losses = []
            Xd, yd = jnp.asarray(Xs), jnp.asarray(X_raw)
            for start in range(0, n_valid, batch):
                idx = np.zeros((len(names), batch), np.int32)
                w = np.zeros((len(names), batch), np.float32)
                live = min(batch, n_valid - start)
                idx[:, :live] = order[:, start : start + live]
                w[:, :live] = 1.0
                if half_batch:
                    w[:, live // 2 : live] = 0.0
                t += 1  # Adam's step count, the same for every machine
                params, m, v, loss = step(params, m, v, float(t), Xd, yd, idx, w)
                if final and t == 1:
                    # the first gradient, as Adam got it: m = (1 - b1) g
                    b1 = float(config["optimizer"]["beta_1"])
                    first_grad = jax.tree_util.tree_map(
                        lambda a: np.asarray(a) / (1.0 - b1), m
                    )
                losses.append(loss * live)
            loss_sum = np.asarray(sum(losses))
            starts = jnp.arange(te_end - te_start - lookback + 1) + te_start
            pred = np.asarray(predict(params, Xd, starts))
            trained = jax.tree_util.tree_map(np.asarray, params) if final else None
            # the next stage's weights are made beside nothing: this stage's
            # state goes first (a stage's peak is its own 12 bytes a parameter)
            del params, m, v
            y_true = X_raw[:, te_start + lookback - 1 : te_end]
            if not final:
                # the last fold's errors set the detector's thresholds
                mn = X_raw[:, :tr_end].min(1)
                span = X_raw[:, :tr_end].max(1) - mn
                span = np.where(span < 10 * np.finfo(np.float32).eps, 1.0, span)
                fold_errors = (
                    ((((pred - y_true) / span[:, None]) ** 2).mean(-1)),
                    np.abs(y_true - pred),
                )
                continue
            for s, rec in enumerate(out):
                mn, mx = X_raw[s].min(0), X_raw[s].max(0)
                span = np.where(mx - mn < 10 * np.finfo(np.float32).eps, 1.0, mx - mn)
                rec["init"] = jax.tree_util.tree_map(lambda a: a[s], init)
                rec["params"] = jax.tree_util.tree_map(lambda a: a[s], trained)
                rec["first_grad"] = jax.tree_util.tree_map(lambda a: a[s], first_grad)
                rec["loss"] = float(loss_sum[s] / n_valid)
                rec["output"] = pred[s]  # over the windows of probe_rows()
                rec["scaler"] = {"min": mn, "span": span}
                rec["aggregate_threshold"] = float(
                    _rolling_min_max(fold_errors[0][s], window)
                )
                rec["feature_thresholds"] = _rolling_min_max(fold_errors[1][s], window)
    return out
