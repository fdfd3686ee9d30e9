"""Plain reference of the model kind ``lstm_symmetric``: the symmetric
stacked-LSTM autoencoder of Equinor gordo's ``lstm_symmetric`` factory (Keras semantics: gates i, f, g,
o from one (in + units) x 4·units matmul, sigmoid gates, unit forget bias,
glorot-uniform input kernel, orthogonal recurrent kernel), all layers but the
last returning sequences, then a linear dense layer on the last state.
``mm`` is the matmul the caller chose (:func:`chipbench.reference.matmul`).
:func:`forward_flops_per_window` is the kind's operation count (conventions:
:mod:`chipbench.flops`)."""

from typing import List, Tuple

import jax
import jax.numpy as jnp

from chipbench.reference import ACT, dense_init, glorot


def _lstm_init(key, n_in, units):
    k1, k2 = jax.random.split(key)
    bias = jnp.zeros((4 * units,)).at[units : 2 * units].set(1.0)
    return {
        "kernel": glorot(k1, (n_in, 4 * units)),
        "recurrent_kernel": jax.nn.initializers.orthogonal()(
            k2, (units, 4 * units), jnp.float32
        ),
        "bias": bias,
    }


def lstm_layers(model: dict) -> List[Tuple[int, str, bool]]:
    dims, funcs = list(model["dims"]), list(model["funcs"])
    units = dims + dims[::-1]
    acts = funcs + funcs[::-1]
    return [(u, a, i != len(units) - 1) for i, (u, a) in enumerate(zip(units, acts))]


def init_params(key, model: dict, n_tags: int) -> list:
    """One key a layer."""
    layers = lstm_layers(model)
    keys = jax.random.split(key, len(layers) + 1)
    params, n_in = [], n_tags
    for (units, _, _), k in zip(layers, keys):
        params.append(_lstm_init(k, n_in, units))
        n_in = units
    params.append(dense_init(keys[-1], n_in, n_tags))
    return params


def _lstm(p, x, units, act, return_sequences, mm):
    w = jnp.concatenate([p["kernel"], p["recurrent_kernel"]], axis=0)

    def step(carry, xt):
        h, c = carry
        z = mm(jnp.concatenate([xt, h], axis=1), w) + p["bias"]
        i, f = jax.nn.sigmoid(z[:, :units]), jax.nn.sigmoid(z[:, units : 2 * units])
        g, o = act(z[:, 2 * units : 3 * units]), jax.nn.sigmoid(z[:, 3 * units :])
        c = f * c + i * g
        h = o * act(c)
        return (h, c), h

    zeros = jnp.zeros((x.shape[0], units))
    (h, _), hs = jax.lax.scan(step, (zeros, zeros), jnp.swapaxes(x, 0, 1))
    return jnp.swapaxes(hs, 0, 1) if return_sequences else h


def forward(model: dict, params: list, x, mm):
    """x: (batch, lookback, tags) → (batch, tags)."""
    for p, (units, act, seq) in zip(params, lstm_layers(model)):
        x = _lstm(p, x, units, ACT[act], seq, mm)
    return mm(x, params[-1]["kernel"]) + params[-1]["bias"]


def forward_flops_per_window(config: dict) -> float:
    model, tags = config["model"], int(config["n_tags"])
    total, n_in = 0.0, tags
    for units, _, _ in lstm_layers(model):
        # four gates, each an (in + units) x units matmul, every timestep
        total += 8.0 * (n_in * units + units * units) * int(model["lookback_window"])
        n_in = units
    return total + 2.0 * n_in * tags  # the output layer sees the last state only
