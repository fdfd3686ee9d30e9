import numpy as np
import pandas as pd
import pytest
import yaml

from gordo_tpu.parallel import BatchedModelBuilder, default_mesh
from gordo_tpu.workflow.normalized_config import NormalizedConfig


def _machine_block(name, n_tags=4, epochs=1, model=None):
    tags = "".join(f"\n      - {name}-tag-{j}" for j in range(n_tags))
    model = model or f"""
      gordo_tpu.models.anomaly.diff.DiffBasedAnomalyDetector:
        require_thresholds: true
        base_estimator:
          sklearn.pipeline.Pipeline:
            steps:
            - sklearn.preprocessing.MinMaxScaler
            - gordo_tpu.models.models.AutoEncoder:
                kind: feedforward_hourglass
                epochs: {epochs}"""
    return f"""
  - name: {name}
    dataset:
      tags:{tags}
      train_start_date: '2019-01-01T00:00:00+00:00'
      train_end_date: '2019-01-03T00:00:00+00:00'
      data_provider: {{type: RandomDataProvider}}
    model:{model}
"""


def _machines(config_yaml):
    return NormalizedConfig(yaml.safe_load(config_yaml), project_name="pp").machines


def test_mesh_has_8_virtual_devices():
    mesh = default_mesh()
    assert int(np.prod(list(mesh.shape.values()))) == 8


@pytest.fixture(scope="module")
def batch_results():
    cfg = "machines:" + "".join(_machine_block(f"bm-{i}") for i in range(3))
    machines = _machines(cfg)
    return machines, BatchedModelBuilder(machines).build()


def test_batched_build_returns_in_order(batch_results):
    machines, results = batch_results
    assert len(results) == 3
    for machine, (model, machine_out) in zip(machines, results):
        assert machine_out.name == machine.name


def test_batched_artifacts_match_serial_api(batch_results):
    _, results = batch_results
    model, machine_out = results[0]
    md = machine_out.to_dict()["metadata"]["build_metadata"]["model"]
    # same metadata surface as the serial ModelBuilder
    assert md["model_offset"] == 0
    assert "aggregate-threshold" in md["model_meta"]
    assert "feature-thresholds" in md["model_meta"]
    scores = md["cross_validation"]["scores"]
    assert "r2-score" in scores
    assert {"fold-mean", "fold-std", "fold-1", "fold-2", "fold-3"} <= set(
        scores["r2-score"]
    )
    splits = md["cross_validation"]["splits"]
    assert "fold-1-train-start" in splits
    for entry in scores.values():
        assert all(np.isfinite(v) for v in entry.values())


def test_batched_model_scores_anomalies(batch_results):
    machines, results = batch_results
    model, _ = results[1]
    cols = [t.name for t in machines[1].dataset.tag_list]
    idx = pd.date_range("2020-01-01", periods=20, freq="10min", tz="UTC")
    X = pd.DataFrame(np.random.rand(20, 4), columns=cols, index=idx)
    frame = model.anomaly(X, X, frequency=pd.Timedelta("10min"))
    assert "total-anomaly-confidence" in frame.columns.get_level_values(0)
    assert len(frame) == 20


def _kfcv_block(name, n_tags=4, window=12):
    return _machine_block(
        name,
        n_tags=n_tags,
        model=f"""
      gordo_tpu.models.anomaly.diff.DiffBasedKFCVAnomalyDetector:
        require_thresholds: true
        window: {window}
        base_estimator:
          sklearn.pipeline.Pipeline:
            steps:
            - sklearn.preprocessing.MinMaxScaler
            - gordo_tpu.models.models.AutoEncoder:
                kind: feedforward_hourglass
                epochs: 1""",
    )


def test_kfcv_machines_take_batched_path():
    from gordo_tpu.parallel.batch_trainer import _plan_machine

    machines = _machines("machines:" + _kfcv_block("kf-0"))
    plan = _plan_machine(machines[0])
    assert plan is not None and plan.kfcv


def test_kfcv_batched_build_end_to_end():
    from gordo_tpu.models.anomaly.diff import DiffBasedKFCVAnomalyDetector

    cfg = "machines:" + _kfcv_block("kf-a") + _kfcv_block("kf-b")
    machines = _machines(cfg)
    results = BatchedModelBuilder(machines, serial_fallback=False).build()
    assert len(results) == 2
    for model, machine_out in results:
        assert isinstance(model, DiffBasedKFCVAnomalyDetector)
        assert np.isfinite(model.aggregate_threshold_)
        assert np.isfinite(model.feature_thresholds_).all()
        md = machine_out.to_dict()["metadata"]["build_metadata"]["model"]
        assert "aggregate-threshold" in md["model_meta"]
    model, _ = results[0]
    cols = [t.name for t in machines[0].dataset.tag_list]
    idx = pd.date_range("2020-01-01", periods=30, freq="10min", tz="UTC")
    X = pd.DataFrame(np.random.rand(30, 4), columns=cols, index=idx)
    frame = model.anomaly(X, X, frequency=pd.Timedelta("10min"))
    assert "total-anomaly-confidence" in frame.columns.get_level_values(0)


def test_kfcv_threshold_math_matches_serial():
    """_set_kfcv_thresholds must reproduce the serial KFCV detector's
    percentile thresholds exactly, given the same fold predictions (here
    from a deterministic LinearRegression base estimator)."""
    from types import SimpleNamespace

    from sklearn.linear_model import LinearRegression
    from sklearn.model_selection import TimeSeriesSplit
    from sklearn.preprocessing import MinMaxScaler

    from gordo_tpu.models.anomaly.diff import DiffBasedKFCVAnomalyDetector

    rng = np.random.RandomState(7)
    X = rng.rand(300, 4)
    y = X @ rng.rand(4, 4) + 0.01 * rng.rand(300, 4)

    serial = DiffBasedKFCVAnomalyDetector(
        base_estimator=LinearRegression(),
        scaler=MinMaxScaler(),
        window=24,
        shuffle=False,
    )
    serial.cross_validate(
        X=pd.DataFrame(X), y=pd.DataFrame(y), cv=TimeSeriesSplit(n_splits=3)
    )

    # batched-side replication from per-fold predictions
    bounds, fold_preds = [], []
    for train_idx, test_idx in TimeSeriesSplit(n_splits=3).split(X):
        tr_end = int(train_idx[-1]) + 1
        te_start, te_end = int(test_idx[0]), int(test_idx[-1]) + 1
        bounds.append((tr_end, te_start, te_end))
        lr = LinearRegression().fit(X[:tr_end], y[:tr_end])
        fold_preds.append(lr.predict(X[te_start:te_end]))

    batched = DiffBasedKFCVAnomalyDetector(
        base_estimator=LinearRegression(),
        scaler=MinMaxScaler(),
        window=24,
        shuffle=False,
    )
    BatchedModelBuilder._set_kfcv_thresholds(
        None, batched, SimpleNamespace(y=y), fold_preds, bounds
    )
    np.testing.assert_allclose(
        batched.aggregate_threshold_, serial.aggregate_threshold_, rtol=1e-9
    )
    np.testing.assert_allclose(
        np.asarray(batched.feature_thresholds_),
        np.asarray(serial.feature_thresholds_),
        rtol=1e-9,
    )


def test_heterogeneous_buckets_and_fallback():
    cfg = "machines:" + (
        _machine_block("small-0", n_tags=2)
        + _machine_block("small-1", n_tags=2)
        + _machine_block("wide-0", n_tags=6)
        + _machine_block(
            "plain-sklearn",
            n_tags=2,
            model="""
      sklearn.pipeline.Pipeline:
        steps:
        - sklearn.preprocessing.MinMaxScaler
        - sklearn.linear_model.LinearRegression
""",
        )
    )
    machines = _machines(cfg)
    results = BatchedModelBuilder(machines).build()
    assert len(results) == 4
    # 2-tag and 6-tag machines end up in different buckets but both train
    m_small, _ = results[0]
    m_wide, _ = results[2]
    assert m_small.base_estimator.steps[1][1].spec_.n_features == 2
    assert m_wide.base_estimator.steps[1][1].spec_.n_features == 6
    # sklearn model went through the serial fallback and is fitted
    m_sk, machine_sk = results[3]
    X = np.random.rand(5, 2)
    assert m_sk.predict(X).shape[0] == 5


def test_batched_seed_determinism():
    cfg = "machines:" + _machine_block("det-0")
    machines1 = _machines(cfg)
    r1 = BatchedModelBuilder(machines1).build()
    machines2 = _machines(cfg)
    r2 = BatchedModelBuilder(machines2).build()
    cols = [t.name for t in machines1[0].dataset.tag_list]
    X = pd.DataFrame(np.random.RandomState(0).rand(16, 4), columns=cols)
    out1 = r1[0][0].predict(X)
    out2 = r2[0][0].predict(X)
    assert np.allclose(out1, out2)


@pytest.mark.parametrize("elastic", [False, True], ids=["static", "elastic"])
def test_serial_fallback_disabled_raises(elastic, tmp_path):
    """Both orchestrators refuse in the plan stage, by the machine's name,
    before anything is fetched or built."""
    import os

    cfg = "machines:" + _machine_block("fall-0", n_tags=2) + _machine_block(
        "nofall",
        n_tags=2,
        model="""
      sklearn.pipeline.Pipeline:
        steps:
        - sklearn.preprocessing.MinMaxScaler
        - sklearn.linear_model.LinearRegression
""",
    )
    machines = _machines(cfg)
    builder = BatchedModelBuilder(
        machines, serial_fallback=False, elastic=elastic,
        output_dir=str(tmp_path),
    )
    with pytest.raises(ValueError, match="nofall is not batchable"):
        builder.build()
    assert set(os.listdir(tmp_path)) <= {"_scheduler"}


def test_seed_independent_of_bucket_composition():
    """A machine's weights must not depend on which machines share its bucket."""
    solo = _machines("machines:" + _machine_block("indep-a"))
    r_solo = BatchedModelBuilder(solo).build()
    pair = _machines(
        "machines:" + _machine_block("indep-b") + _machine_block("indep-a")
    )
    r_pair = BatchedModelBuilder(pair).build()
    cols = [t.name for t in solo[0].dataset.tag_list]
    X = pd.DataFrame(np.random.RandomState(1).rand(16, 4), columns=cols)
    out_solo = r_solo[0][0].predict(X)
    out_pair = r_pair[1][0].predict(X)  # indep-a is second in the pair config
    assert np.allclose(out_solo, out_pair)


def test_cross_val_only_goes_serial():
    cfg = "machines:" + _machine_block("cvonly")
    machines = _machines(cfg)
    machines[0].evaluation["cv_mode"] = "cross_val_only"
    results = BatchedModelBuilder(machines).build()
    model, machine_out = results[0]
    # serial cross_val_only contract: inner estimator not fitted
    ae = model.base_estimator.steps[-1][1]
    assert not hasattr(ae, "params_")
    assert machine_out.metadata.build_metadata.model.cross_validation.scores


def test_unsupported_metric_goes_serial():
    cfg = "machines:" + _machine_block("oddmetric")
    machines = _machines(cfg)
    machines[0].evaluation["metrics"] = ["sklearn.metrics.max_error"]
    results = BatchedModelBuilder(machines).build()
    _, machine_out = results[0]
    scores = machine_out.metadata.build_metadata.model.cross_validation.scores
    assert any("max-error" in k for k in scores)


@pytest.mark.parametrize("force_numpy", [False, True])
def test_rolling_min_max_matches_pandas(force_numpy, monkeypatch):
    """The threshold math (native kernel and numpy fallback) must equal
    pandas rolling(w).min().max() — including NaN inputs, where a window
    containing NaN has NaN min and the final max skips NaN windows."""
    if force_numpy:
        from gordo_tpu import native

        monkeypatch.setattr(native, "_lib", None)
        monkeypatch.setattr(native, "_load_failed", True)
    rng = np.random.RandomState(7)
    for n, w in [(200, 6), (144, 144), (50, 6), (5, 6), (6, 6)]:
        for nan_frac in (0.0, 0.15, 1.0):
            series = rng.rand(n)
            if nan_frac:
                series[rng.rand(n) < nan_frac] = np.nan
            expected = pd.Series(series).rolling(w).min().max()
            got = BatchedModelBuilder._rolling_min_max(series, w)
            if np.isnan(expected):
                assert np.isnan(got), (n, w, nan_frac)
            else:
                assert np.isclose(got, expected), (n, w, nan_frac)

            frame = rng.rand(n, 4)
            if nan_frac:
                frame[rng.rand(n, 4) < nan_frac] = np.nan
            expected_df = pd.DataFrame(frame).rolling(w).min().max()
            got_df = BatchedModelBuilder._rolling_min_max(frame, w)
            assert np.allclose(
                np.asarray(got_df), expected_df.to_numpy(), equal_nan=True
            ), (n, w, nan_frac)


def test_chunked_build_matches_unchunked():
    """Chunking is an execution detail: results must be identical for any
    chunk size (same seeds, same data)."""
    import jax

    cfg = "machines:" + "".join(_machine_block(f"ck-{i}") for i in range(3))
    small = BatchedModelBuilder(_machines(cfg), chunk_size=1).build()
    big = BatchedModelBuilder(_machines(cfg), chunk_size=64).build()
    for (m_small, _), (m_big, _) in zip(small, big):
        a = m_small.base_estimator.steps[-1][1].params_
        b = m_big.base_estimator.steps[-1][1].params_
        for la, lb in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
            assert np.allclose(np.asarray(la), np.asarray(lb))
    # thresholds identical too (assembly independent of chunking)
    assert np.isclose(
        small[0][0].aggregate_threshold_, big[0][0].aggregate_threshold_
    )


def _cache_marker(machine_out):
    return (machine_out.metadata.user_defined or {}).get(
        "build-metadata", {}
    ) == {"from_cache": True}


def test_fleet_checkpoint_resume(tmp_path):
    """A fleet build with output/register dirs persists each machine as it
    finishes; a rerun loads everything from cache, and wiping one machine's
    cache entry retrains only that machine."""
    import os
    import shutil

    from gordo_tpu.builder.build_model import ModelBuilder
    from gordo_tpu.util import disk_registry

    config = "machines:" + "".join(_machine_block(f"ckpt-{i}") for i in range(4))
    out_dir, reg_dir = str(tmp_path / "out"), str(tmp_path / "reg")

    machines = _machines(config)
    first = BatchedModelBuilder(
        machines, output_dir=out_dir, model_register_dir=reg_dir
    ).build()
    assert len(first) == 4
    for _, mo in first:
        assert not _cache_marker(mo)
        assert os.path.exists(os.path.join(out_dir, mo.name, "model.pkl"))
        assert os.path.exists(os.path.join(out_dir, mo.name, "metadata.json"))
        # checkpointed metadata carries the apportioned durations, not the
        # provisional zeros written at assembly time
        from gordo_tpu import serializer

        meta = serializer.load_metadata(os.path.join(out_dir, mo.name))
        assert (
            meta["metadata"]["build_metadata"]["model"]
            ["model_training_duration_sec"] > 0.0
        )

    second = BatchedModelBuilder(
        _machines(config), output_dir=out_dir, model_register_dir=reg_dir
    ).build()
    assert all(_cache_marker(mo) for _, mo in second)

    # wipe one machine's entry: only it retrains
    victim = machines[2]
    disk_registry.delete_value(reg_dir, ModelBuilder(victim).cache_key)
    shutil.rmtree(os.path.join(out_dir, victim.name))
    third = BatchedModelBuilder(
        _machines(config), output_dir=out_dir, model_register_dir=reg_dir
    ).build()
    markers = {mo.name: _cache_marker(mo) for _, mo in third}
    assert markers == {
        "ckpt-0": True, "ckpt-1": True, "ckpt-2": False, "ckpt-3": True,
    }
    assert os.path.exists(os.path.join(out_dir, victim.name, "model.pkl"))


def test_fleet_replace_cache_retrains(tmp_path):
    config = "machines:" + _machine_block("rc-0")
    out_dir, reg_dir = str(tmp_path / "out"), str(tmp_path / "reg")
    kwargs = dict(output_dir=out_dir, model_register_dir=reg_dir)
    BatchedModelBuilder(_machines(config), **kwargs).build()
    again = BatchedModelBuilder(
        _machines(config), replace_cache=True, **kwargs
    ).build()
    assert not _cache_marker(again[0][1])


@pytest.mark.parametrize("nested", [False, True])
def test_profile_dir_captures_device_trace(tmp_path, monkeypatch, nested):
    """GORDO_TPU_PROFILE_DIR wraps the fleet build in a jax.profiler session
    and leaves an openable trace on disk (SURVEY §5 tracing hookup). Inside
    a session that is open already (a harness's, /debug/profile?device=1:
    jax allows one) it opens none and the build proceeds inside that one."""
    import os

    import jax

    monkeypatch.setenv("GORDO_TPU_PROFILE_DIR", str(tmp_path / "own"))
    config = "machines:" + _machine_block(f"prof-{int(nested)}")
    if nested:
        jax.profiler.start_trace(str(tmp_path / "outer"))
    try:
        assert len(BatchedModelBuilder(_machines(config)).build()) == 1
    finally:
        if nested:
            jax.profiler.stop_trace()  # still the outer session's to close
    trace_root = tmp_path / ("outer" if nested else "own/batched-build")
    files = [
        os.path.join(r, f)
        for r, _, fs in os.walk(trace_root)
        for f in fs
    ]
    assert files, "profiler produced no trace files"
    if nested:
        assert not any(fs for _, _, fs in os.walk(tmp_path / "own"))


# --------------------------------------------------- seeded-KFold KFCV plans
def _kfold_kfcv_block(name, n_splits=5, window=12):
    block = _kfcv_block(name, window=window)
    return block + f"""    evaluation:
      cv:
        sklearn.model_selection.KFold:
          n_splits: {n_splits}
          shuffle: true
          random_state: 0
"""


def test_kfold_kfcv_machines_take_batched_path():
    from gordo_tpu.parallel.batch_trainer import _plan_machine

    machines = _machines("machines:" + _kfold_kfcv_block("kfold-0"))
    plan = _plan_machine(machines[0])
    assert plan is not None and plan.kfcv
    assert plan.cv == ("kfold", 5, True, 0)


def test_kfold_cv_stays_serial_outside_kfcv():
    """Shuffled folds break the plain detector's rolling-threshold math and
    unseeded shuffles are irreproducible — both stay on the serial path."""
    from gordo_tpu.parallel.batch_trainer import _plan_machine

    plain = _machines("machines:" + _machine_block("plain-kf"))
    plain[0].evaluation["cv"] = {
        "sklearn.model_selection.KFold": {
            "n_splits": 5, "shuffle": True, "random_state": 0,
        }
    }
    assert _plan_machine(plain[0]) is None

    unseeded = _machines("machines:" + _kfcv_block("unseeded-kf"))
    unseeded[0].evaluation["cv"] = {
        "sklearn.model_selection.KFold": {"n_splits": 5, "shuffle": True}
    }
    assert _plan_machine(unseeded[0]) is None


def test_kfold_kfcv_threshold_math_matches_serial():
    """With seeded-KFold geometry (uneven fold sizes ⇒ padded test slices),
    _set_kfcv_thresholds must reproduce the serial KFCV detector's
    percentile thresholds exactly, given the same fold predictions."""
    from types import SimpleNamespace

    from sklearn.linear_model import LinearRegression
    from sklearn.model_selection import KFold
    from sklearn.preprocessing import MinMaxScaler

    from gordo_tpu.models.anomaly.diff import DiffBasedKFCVAnomalyDetector

    rng = np.random.RandomState(11)
    n_rows = 302  # 302 % 5 != 0: folds of 61/61/60/60/60 exercise padding
    X = rng.rand(n_rows, 4)
    y = X @ rng.rand(4, 4) + 0.01 * rng.rand(n_rows, 4)
    cv = KFold(n_splits=5, shuffle=True, random_state=0)

    serial = DiffBasedKFCVAnomalyDetector(
        base_estimator=LinearRegression(),
        scaler=MinMaxScaler(),
        window=24,
        shuffle=False,
    )
    serial.cross_validate(X=pd.DataFrame(X), y=pd.DataFrame(y), cv=cv)

    folds = [(tr, te) for tr, te in cv.split(X)]
    te_max = max(len(te) for _, te in folds)
    fold_bounds = [(len(tr), n_rows - te_max, n_rows) for tr, _ in folds]
    fold_preds = []
    for tr, te in folds:
        lr = LinearRegression().fit(X[tr], y[tr])
        pred = lr.predict(X[te])
        pad = te_max - len(te)
        if pad:
            # the program's padded test tail starts with train rows whose
            # predictions the assembly must discard
            pred = np.vstack([np.full((pad, y.shape[1]), 1e6), pred])
        fold_preds.append(pred)

    batched = DiffBasedKFCVAnomalyDetector(
        base_estimator=LinearRegression(),
        scaler=MinMaxScaler(),
        window=24,
        shuffle=False,
    )
    BatchedModelBuilder._set_kfcv_thresholds(
        None, batched, SimpleNamespace(y=y), fold_preds, fold_bounds, folds
    )
    np.testing.assert_allclose(
        batched.aggregate_threshold_, serial.aggregate_threshold_, rtol=1e-9
    )
    np.testing.assert_allclose(
        np.asarray(batched.feature_thresholds_),
        np.asarray(serial.feature_thresholds_),
        rtol=1e-9,
    )


def test_kfold_kfcv_batched_build_matches_serial_builder():
    """End to end: a seeded-KFold KFCV machine built batched vs the serial
    ModelBuilder. Fold geometry (splits metadata) must match EXACTLY; the
    thresholds come from independently-initialized trainings, so they match
    statistically (same order of magnitude), not bit-for-bit."""
    from gordo_tpu.builder.build_model import ModelBuilder
    from gordo_tpu.models.anomaly.diff import DiffBasedKFCVAnomalyDetector

    cfg = "machines:" + _kfold_kfcv_block("kfold-e2e")
    [(batched_model, batched_out)] = BatchedModelBuilder(
        _machines(cfg), serial_fallback=False
    ).build()
    serial_model, serial_out = ModelBuilder(_machines(cfg)[0]).build()
    assert isinstance(batched_model, DiffBasedKFCVAnomalyDetector)

    b_splits = batched_out.metadata.build_metadata.model.cross_validation.splits
    s_splits = serial_out.metadata.build_metadata.model.cross_validation.splits
    assert set(b_splits) == set(s_splits)
    for key in s_splits:
        assert str(b_splits[key]) == str(s_splits[key]), key

    ratio = batched_model.aggregate_threshold_ / serial_model.aggregate_threshold_
    assert 1 / 3 < ratio < 3, ratio
    feat_ratio = np.asarray(batched_model.feature_thresholds_) / np.asarray(
        serial_model.feature_thresholds_
    )
    assert np.all((feat_ratio > 1 / 3) & (feat_ratio < 3)), feat_ratio


def test_plain_detector_kfold_builds_via_serial_path():
    """A non-KFCV detector with a KFold cv config is rejected by the planner
    (rolling thresholds need contiguous folds) but must still BUILD through
    the serial ModelBuilder — capability is never lost, only speed."""
    from gordo_tpu.models.anomaly.diff import DiffBasedAnomalyDetector

    machines = _machines("machines:" + _machine_block("plain-kf-build"))
    machines[0].evaluation["cv"] = {
        "sklearn.model_selection.KFold": {
            "n_splits": 3, "shuffle": True, "random_state": 0,
        }
    }
    [(model, machine_out)] = BatchedModelBuilder(machines).build()
    assert isinstance(model, DiffBasedAnomalyDetector)
    assert np.isfinite(model.aggregate_threshold_)
    splits = machine_out.metadata.build_metadata.model.cross_validation.splits
    assert splits["fold-1-n-test"] > 0
    scores = machine_out.metadata.build_metadata.model.cross_validation.scores
    assert any("r2" in key for key in scores)
