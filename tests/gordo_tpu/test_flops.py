"""Analytic FLOPs/MFU accounting (ops/flops.py) — the serving MFU gauge's inputs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gordo_tpu.models.models import (
    AutoEncoder,
    LSTMAutoEncoder,
    TransformerAutoEncoder,
)
from gordo_tpu.ops import flops as flops_mod
from gordo_tpu.ops.nn import init_model_params, moe_aux_loss
from gordo_tpu.models.spec import MoEBlock


def _spec(est):
    return est.build_spec(8, 8)


@pytest.mark.parametrize(
    "est",
    [
        AutoEncoder(kind="feedforward_hourglass"),
        LSTMAutoEncoder(
            kind="lstm_symmetric", dims=[64, 32], funcs=["tanh", "tanh"],
            lookback_window=16,
        ),
        TransformerAutoEncoder(kind="transformer_model", lookback_window=16),
        TransformerAutoEncoder(
            kind="moe_transformer_model", lookback_window=16, num_experts=4
        ),
        TransformerAutoEncoder(
            kind="hybrid_moe_model", lookback_window=16, experts_held=4
        ),
        TransformerAutoEncoder(
            kind="latent_moe_model", lookback_window=16, experts_held=4
        ),
    ],
    ids=["hourglass", "lstm", "transformer", "moe", "hybrid", "latent"],
)
def test_param_count_matches_initialized_tree(est):
    """The layer-walk parameter count must match the real pytree — the same
    walk prices the FLOPs, so a drift here means wrong MFU."""
    spec = _spec(est)
    params = init_model_params(jax.random.PRNGKey(0), spec)
    # the walk counts matmul/recurrent weights + their biases; layernorm
    # scales/biases and attention biases are excluded (negligible FLOPs).
    counted = flops_mod.spec_param_count(spec)
    actual = sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(params))
    assert counted <= actual
    assert counted >= 0.7 * actual, (counted, actual)


def test_forward_flops_scale_with_window_and_width():
    lstm16 = _spec(LSTMAutoEncoder(
        kind="lstm_symmetric", dims=[64, 32], funcs=["tanh", "tanh"],
        lookback_window=16,
    ))
    lstm64 = _spec(LSTMAutoEncoder(
        kind="lstm_symmetric", dims=[64, 32], funcs=["tanh", "tanh"],
        lookback_window=64,
    ))
    f16 = flops_mod.forward_flops_per_sample(lstm16)
    f64 = flops_mod.forward_flops_per_sample(lstm64)
    assert f16 > 0
    # LSTM cost is linear in T
    np.testing.assert_allclose(f64 / f16, 4.0, rtol=0.01)

    # attention adds a quadratic-in-T term: more than 4x when T quadruples
    tr16 = _spec(TransformerAutoEncoder(kind="transformer_model", lookback_window=16))
    tr64 = _spec(TransformerAutoEncoder(kind="transformer_model", lookback_window=64))
    assert (
        flops_mod.forward_flops_per_sample(tr64)
        > 4.0 * flops_mod.forward_flops_per_sample(tr16)
    )


def test_hybrid_block_flops_are_the_benchmarks_count():
    """The serving gauge's walk and the benchmark's own count of the same
    configuration (``chipbench/configs/lfm2_moe.py``) agree to the last
    operation, and the parameter walk is exact for this block."""
    import json
    import os

    from chipbench import flops as bench_flops

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "chipbench", "rehearsal", "lfm2_tiny.json")) as fh:
        config = json.load(fh)
    model = {k: v for k, v in config["model"].items() if k not in ("kind", "batch_size", "epochs", "compute_dtype")}
    n_tags = config["n_tags"]
    spec = TransformerAutoEncoder(kind="hybrid_moe_model", **model).build_spec(n_tags, n_tags)
    assert flops_mod.forward_flops_per_sample(spec) == bench_flops.forward_flops_per_window(config)
    params = init_model_params(jax.random.PRNGKey(0), spec)
    actual = sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(params))
    assert flops_mod.spec_param_count(spec) == actual


def test_mfu_and_peak_lookup():
    assert flops_mod.chip_peak_flops("TPU v4") == 275e12
    # the bf16 figure; 394e12 is the v5e's int8 peak
    assert flops_mod.chip_peak_flops("TPU v5 lite") == 197e12
    assert flops_mod.chip_peak_flops("TPU v5e") == 197e12
    assert flops_mod.mfu(1e12, 1.0, "TPU v4") == pytest.approx(1e12 / 275e12)
    # aggregate peak scales with device count
    assert flops_mod.mfu(1e12, 1.0, "TPU v4", n_devices=4) == pytest.approx(
        1e12 / (4 * 275e12)
    )
    assert flops_mod.mfu(1e9, 0.0, "TPU v4") is None


def test_cpu_has_no_peak_and_no_mfu():
    # a host has no datasheet peak: its MFU is "not measured", never a
    # number derived from a timed GEMM
    assert flops_mod.chip_peak_flops("cpu") is None
    assert flops_mod.mfu(1e12, 1.0, "cpu") is None


def test_unknown_device_kind_raises(monkeypatch):
    # no environment override rescues an unknown accelerator either
    monkeypatch.setenv("GORDO_TPU_PEAK_FLOPS", "1e15")
    with pytest.raises(ValueError, match="no peak FLOP/s on record"):
        flops_mod.chip_peak_flops("TPU v9 imaginary")
    with pytest.raises(ValueError, match="no peak FLOP/s on record"):
        flops_mod.mfu(1e12, 1.0, "unknown")


# --------------------------------------------------------- MoE aux loss
def test_moe_aux_loss_uniform_vs_collapsed():
    """Switch load-balancing loss: 1.0 under uniform routing, -> E under
    full collapse (every token to one expert)."""
    layer = MoEBlock(d_model=8, num_experts=4)
    n = 64
    uniform = jnp.tile(jnp.full((1, 4), 0.25), (n, 1))
    # perturb so argmax spreads evenly across experts
    bump = jax.nn.one_hot(jnp.arange(n) % 4, 4) * 0.01
    val_uniform = float(moe_aux_loss(layer, uniform + bump))
    assert val_uniform == pytest.approx(1.0, rel=0.05)

    collapsed = jnp.tile(jnp.asarray([[0.97, 0.01, 0.01, 0.01]]), (n, 1))
    val_collapsed = float(moe_aux_loss(layer, collapsed))
    assert val_collapsed > 3.5  # ~ E * P_hot


def test_moe_aux_loss_reaches_training_penalty():
    """apply_model threads the weighted aux loss into the penalty the
    training loss adds — the mechanism that prevents expert collapse."""
    import dataclasses

    from gordo_tpu.ops.nn import apply_model

    est = TransformerAutoEncoder(
        kind="moe_transformer_model", lookback_window=8, num_experts=4
    )
    spec = est.build_spec(4, 4)
    params = init_model_params(jax.random.PRNGKey(0), spec)
    x = jax.random.uniform(jax.random.PRNGKey(1), (6, 8, 4))
    _, penalty = apply_model(spec, params, x)
    assert float(penalty) > 0.0

    moe_idx = [
        i for i, l in enumerate(spec.layers) if isinstance(l, MoEBlock)
    ]
    zeroed_layers = tuple(
        dataclasses.replace(l, aux_loss_weight=0.0) if isinstance(l, MoEBlock) else l
        for l in spec.layers
    )
    spec0 = dataclasses.replace(spec, layers=zeroed_layers)
    _, penalty0 = apply_model(spec0, params, x)
    assert float(penalty0) < float(penalty)
    assert moe_idx  # the factory really emits MoE blocks
