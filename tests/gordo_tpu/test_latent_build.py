"""The kind ``latent_moe_model`` on the system's normal path: a YAML with the
``kind:`` through ``BatchedModelBuilder.build()``, the artifact back through
``serializer.load``, a score as a server worker gives it; and the chunk
program's counters in the build's catalog."""

import os

import jax
import numpy as np
import pandas as pd
import pytest
import yaml

from gordo_tpu import serializer
from gordo_tpu.models.spec import LatentBlock, StreamLayer
from gordo_tpu.observability import metrics as metric_catalog
from gordo_tpu.parallel import BatchedModelBuilder, batch_trainer, default_mesh
from gordo_tpu.workflow.normalized_config import NormalizedConfig

ESTIMATOR = """gordo_tpu.models.models.TransformerAutoEncoder:
                kind: latent_moe_model
                d_model: 16
                ffns: [dense, routed]
                ff_dim: 24
                expert_dim: 8
                num_heads: 2
                q_lora_rank: 12
                kv_lora_rank: 8
                qk_nope_head_dim: 8
                qk_rope_head_dim: 4
                v_head_dim: 8
                rope_scaling: {type: yarn, factor: 64, original_max_position_embeddings: 4096,
                               beta_fast: 32, beta_slow: 1, mscale: 1, mscale_all_dim: 1}
                num_experts: 8
                experts_held: 4
                expert_offset: 2
                top_k: 2
                shared_experts: 1
                routed_scale: 2.0
                streams: 2
                lookback_window: 6
                batch_size: 16
                epochs: 1"""


def _machines(n=2):
    blocks = "".join(
        f"""
  - name: latent-{i}
    dataset:
      tags: [latent-{i}-a, latent-{i}-b, latent-{i}-c]
      train_start_date: '2019-01-01T00:00:00+00:00'
      train_end_date: '2019-01-02T00:00:00+00:00'
      data_provider: {{type: RandomDataProvider}}
    model:
      gordo_tpu.models.anomaly.diff.DiffBasedAnomalyDetector:
        require_thresholds: true
        base_estimator:
          sklearn.pipeline.Pipeline:
            steps:
            - sklearn.preprocessing.MinMaxScaler
            - {ESTIMATOR}
"""
        for i in range(n)
    )
    return NormalizedConfig(yaml.safe_load("machines:" + blocks), project_name="latent").machines


@pytest.fixture(scope="module")
def built(tmp_path_factory):
    out = tmp_path_factory.mktemp("latent-build")
    before = {
        "steps": metric_catalog.HC_SUBLAYER_STEPS.value(),
        "gap": metric_catalog.HC_STOCHASTIC_GAP.value(),
        "moe": metric_catalog.MOE_LAYER_STEPS.value(),
        "serial": sum(v for _, v in metric_catalog.SERIAL_FALLBACKS.snapshot()),
    }
    results = BatchedModelBuilder(
        _machines(), mesh=default_mesh(devices=jax.devices()[:1]), chunk_size=2,
        output_dir=str(out), serial_fallback=False, fail_fast=True,
    ).build()
    return out, results, before


def test_the_kind_goes_through_the_fleet_program(built):
    _, results, before = built
    assert len(results) == 2
    # the batched path, not the serial builder: the plan is vmappable
    assert sum(v for _, v in metric_catalog.SERIAL_FALLBACKS.snapshot()) == before["serial"]
    plan = batch_trainer._plan_machine(_machines(1)[0])
    kinds = [type(layer) for layer in plan.spec.layers]
    assert kinds.count(LatentBlock) == 2 and kinds.count(StreamLayer) == 2
    # the chunk program's counts reached the catalog: two blocks of two
    # sublayers and one routed layer a live step
    steps = metric_catalog.HC_SUBLAYER_STEPS.value() - before["steps"]
    moe = metric_catalog.MOE_LAYER_STEPS.value() - before["moe"]
    assert steps > 0 and steps == 4 * moe
    gap = metric_catalog.HC_STOCHASTIC_GAP.value() - before["gap"]
    assert 0.0 <= gap < 1e-3 * steps


def test_the_artifact_loads_and_scores(built):
    out, results, _ = built
    model, machine = results[0]
    loaded = serializer.load(os.path.join(str(out), machine.name))
    index = pd.date_range("2019-02-01", periods=40, freq="10min", tz="UTC")
    rows = np.random.default_rng(0).normal(size=(40, 3)).astype(np.float32)
    X = pd.DataFrame(rows, index=index, columns=[f"latent-0-{c}" for c in "abc"])
    frame = loaded.anomaly(X, X, frequency=pd.Timedelta("10min"))
    assert len(frame) == 40 - 6 + 1
    total = frame["total-anomaly-scaled"].to_numpy()
    assert np.isfinite(total).all() and (total >= 0).all()
    # the loaded model is the built one: the same rows score alike
    again = model.anomaly(X, X, frequency=pd.Timedelta("10min"))
    np.testing.assert_allclose(total, again["total-anomaly-scaled"].to_numpy(), rtol=1e-5)
