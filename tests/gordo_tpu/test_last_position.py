"""When the model's last sequence block runs at the last position alone
(``ops/nn._last_only_block``): only where nothing after it, up to a pool that
keeps the last position, mixes positions; and what counts it, from the block
through a fleet build's counter."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from gordo_tpu.models.factories.hybrid import hybrid_moe_model
from gordo_tpu.models.factories.latent import latent_moe_model
from gordo_tpu.models.factories.lstm_autoencoder import lstm_symmetric
from gordo_tpu.models.factories.transformer import transformer_model
from gordo_tpu.models.spec import DenseLayer, PoolLayer
from gordo_tpu.observability import metrics as metric_catalog
from gordo_tpu.ops import attention, nn
from gordo_tpu.parallel import BatchedModelBuilder, default_mesh
from gordo_tpu.workflow.normalized_config import NormalizedConfig

# lfm2_tiny's model (chipbench/rehearsal/lfm2_tiny.json): the last block is
# attention with the one routed FFN
TINY = {
    "d_model": 16, "operators": ["conv", "attention"], "ffns": ["dense", "routed"],
    "ff_dim": 40, "expert_dim": 24, "num_heads": 4, "num_kv_heads": 2, "head_dim": 8,
    "rope_theta": 1000000.0, "conv_kernel": 3, "num_experts": 8, "experts_held": 4,
    "expert_offset": 2, "top_k": 2, "norm_eps": 1e-05, "lookback_window": 12,
}


def _hybrid():
    return hybrid_moe_model(4, attention="xla", **TINY)


def _latent():
    return latent_moe_model(
        3, lookback_window=4, d_model=8, ffns=["dense", "routed"], ff_dim=8,
        expert_dim=8, num_heads=2, q_lora_rank=6, kv_lora_rank=4, qk_nope_head_dim=4,
        qk_rope_head_dim=2, v_head_dim=4, num_experts=8, experts_held=2, expert_offset=2,
        top_k=4, streams=2, attention="xla",
    )


def _replace_layer(spec, kind, new):
    i = max(i for i, layer in enumerate(spec.layers) if isinstance(layer, kind))
    return dataclasses.replace(spec, layers=spec.layers[:i] + (new,) + spec.layers[i + 1 :])


def test_the_trim_engages_before_norms_collapse_and_a_last_pool():
    hybrid, latent = _hybrid(), _latent()
    # [dense, conv, attention, norm, pool, head]; [dense, expand, 2 blocks, collapse, ...]
    assert nn._last_only_block(hybrid) == 2
    assert nn._last_only_block(latent) == 3
    assert "last_position_steps" in nn.zero_stats(hybrid)


@pytest.mark.parametrize("pool", ["mean", "max"])
def test_a_pool_over_every_position_keeps_the_whole_block(pool):
    for spec in (_hybrid(), _latent()):
        spec = _replace_layer(spec, PoolLayer, PoolLayer(mode=pool))
        assert nn._last_only_block(spec) is None
        assert "last_position_steps" not in nn.zero_stats(spec)


def test_a_dense_layer_after_the_block_keeps_the_whole_block():
    """Its ``l1_activity`` sums over positions: every position is read."""
    spec = _hybrid()
    i = spec.layers.index(PoolLayer(mode="last"))
    dense = DenseLayer(units=TINY["d_model"], activation="tanh", l1_activity=1e-4)
    spec = dataclasses.replace(spec, layers=spec.layers[:i] + (dense,) + spec.layers[i:])
    assert nn._last_only_block(spec) is None
    out, penalty, stats = jax.jit(nn.apply_model_stats, static_argnums=0)(
        spec, nn.init_model_params(jax.random.PRNGKey(0), spec), jnp.ones((2, 12, 4))
    )
    assert out.shape == (2, 4) and float(penalty) > 0 and "last_position_steps" not in stats


@pytest.mark.parametrize(
    "make_spec",
    [
        lambda: transformer_model(4, lookback_window=8, d_model=8, num_heads=2, ff_dim=8),
        lambda: lstm_symmetric(4, lookback_window=8, dims=(6, 4), funcs=("tanh", "tanh")),
    ],
    ids=["transformer", "lstm"],
)
def test_other_sequence_specs_keep_every_position(make_spec):
    spec = make_spec()
    assert nn._last_only_block(spec) is None
    assert nn.zero_stats(spec) == {}


def test_one_query_against_every_key_is_not_for_the_flash_kernel(monkeypatch):
    """The trimmed block's attention is one query row against T keys:
    ``_flash_ok`` admits equal lengths only, on a TPU as anywhere."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    k = jnp.ones((2, 4, 256, 64))
    assert attention._flash_ok(k, k, k)  # the shape the kernel does take
    assert not attention._flash_ok(k[..., -1:, :], k, k)


def _machines(n=2):
    estimator = {
        "gordo_tpu.models.models.TransformerAutoEncoder": dict(
            TINY, kind="hybrid_moe_model", attention="auto", batch_size=16, epochs=1
        )
    }
    machines = [
        {
            "name": f"last-{i}",
            "dataset": {
                "tags": [f"last-{i}-{c}" for c in "abcd"],
                "train_start_date": "2019-01-01T00:00:00+00:00",
                "train_end_date": "2019-01-02T00:00:00+00:00",
                "data_provider": {"type": "RandomDataProvider"},
            },
            "model": {
                "gordo_tpu.models.anomaly.diff.DiffBasedAnomalyDetector": {
                    "require_thresholds": True,
                    "base_estimator": {
                        "sklearn.pipeline.Pipeline": {
                            "steps": ["sklearn.preprocessing.MinMaxScaler", estimator]
                        }
                    },
                }
            },
        }
        for i in range(n)
    ]
    return NormalizedConfig({"machines": machines}, project_name="last").machines


def test_a_fleet_build_counts_one_trimmed_block_a_live_step(tmp_path):
    """The chunk program's ``last_position_steps`` reaches
    ``gordo_build_last_position_block_steps_total``: one a live training step
    of each machine, as the one routed layer (which is the trimmed block's)
    counts its layer-steps."""
    before = (
        metric_catalog.LAST_POSITION_BLOCK_STEPS.value(),
        metric_catalog.MOE_LAYER_STEPS.value(),
    )
    results = BatchedModelBuilder(
        _machines(), mesh=default_mesh(devices=jax.devices()[:1]), chunk_size=2,
        output_dir=str(tmp_path), serial_fallback=False, fail_fast=True,
    ).build()
    assert len(results) == 2
    trimmed = metric_catalog.LAST_POSITION_BLOCK_STEPS.value() - before[0]
    layer_steps = metric_catalog.MOE_LAYER_STEPS.value() - before[1]
    assert trimmed > 0 and trimmed == layer_steps
