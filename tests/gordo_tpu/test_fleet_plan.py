"""The fleet build's plan stage apart from its loop: the fold geometry of a
bucket and the program lookup as plain functions, the table of what
``_plan_machine`` leaves to the serial builder, and the plan stage (resume
prefilter, serial list) through both orchestrators."""

import copy
import os

import jax
import numpy as np
import pytest
from sklearn.model_selection import KFold, TimeSeriesSplit

from gordo_tpu.builder.build_model import ModelBuilder
from gordo_tpu.machine import Machine
from gordo_tpu.observability import metrics as metric_catalog
from gordo_tpu.parallel import BatchedModelBuilder, batch_trainer, default_mesh
from gordo_tpu.parallel.batch_trainer import (
    _bucket_program,
    _fold_geometry,
    _plan_machine,
    _program_for,
)
from gordo_tpu.util import disk_registry

AUTOENCODER = "gordo_tpu.models.models.AutoEncoder"
DETECTOR = "gordo_tpu.models.anomaly.diff.DiffBasedAnomalyDetector"
KFCV_DETECTOR = "gordo_tpu.models.anomaly.diff.DiffBasedKFCVAnomalyDetector"
PIPELINE = "sklearn.pipeline.Pipeline"
MINMAX = "sklearn.preprocessing.MinMaxScaler"
TSS = "sklearn.model_selection.TimeSeriesSplit"
KFOLD = "sklearn.model_selection.KFold"


def _config(name, detector=DETECTOR, estimator=None, **estimator_kwargs):
    """A machine the fleet program can build: detector(pipeline(scaler,
    hourglass)) on two tags and two days of 10-minute rows."""
    estimator = estimator or {
        AUTOENCODER: {
            "kind": "feedforward_hourglass", "epochs": 1, **estimator_kwargs
        }
    }
    return {
        "name": name,
        "dataset": {
            "type": "RandomDataset",
            "train_start_date": "2019-01-01T00:00:00+00:00",
            "train_end_date": "2019-01-03T00:00:00+00:00",
            "tags": [f"{name}-a", f"{name}-b"],
        },
        "model": {
            detector: {
                "base_estimator": {PIPELINE: {"steps": [MINMAX, estimator]}}
            }
        },
    }


def _machine(config, cv=None):
    machine = Machine.from_config(copy.deepcopy(config), project_name="plan")
    if cv is not None:
        machine.evaluation["cv"] = cv
    return machine


def _steps(config):
    (detector,) = config["model"].values()
    return detector["base_estimator"][PIPELINE]["steps"]


# ------------------------------------------------------------ fold geometry
@pytest.mark.parametrize("n_rows", [433, 1008, 4032])
@pytest.mark.parametrize("n_splits", [2, 3, 5])
def test_time_series_geometry_is_sklearns_with_one_test_length(n_splits, n_rows):
    plan = _plan_machine(_machine(_config("tss"), cv={TSS: {"n_splits": n_splits}}))
    fold_bounds, kfold_folds, perms = _fold_geometry(plan, n_rows)
    assert kfold_folds is None and perms is None
    rows = np.arange(n_rows)
    expected = list(TimeSeriesSplit(n_splits=n_splits).split(rows[:, None]))
    assert len(fold_bounds) == n_splits
    for (tr_end, te_start, te_end), (train, test) in zip(fold_bounds, expected):
        np.testing.assert_array_equal(rows[:tr_end], train)
        np.testing.assert_array_equal(rows[te_start:te_end], test)
    assert {te_end - te_start for _, te_start, te_end in fold_bounds} == {
        n_rows // (n_splits + 1)
    }


@pytest.mark.parametrize("n_rows", [433, 1009])
@pytest.mark.parametrize("n_splits", [3, 5])
@pytest.mark.parametrize("shuffle", [True, False], ids=["seeded", "unshuffled"])
def test_kfold_geometry_pads_every_fold_to_the_largest(shuffle, n_splits, n_rows):
    assert n_rows % n_splits  # uneven folds: the case the padding is for
    cv = {"n_splits": n_splits, "shuffle": shuffle}
    if shuffle:
        cv["random_state"] = 7
    plan = _plan_machine(
        _machine(_config("kfold", detector=KFCV_DETECTOR), cv={KFOLD: cv})
    )
    assert plan.cv == ("kfold", n_splits, shuffle, 7 if shuffle else None)
    fold_bounds, kfold_folds, perms = _fold_geometry(plan, n_rows)
    expected = list(
        KFold(
            n_splits=n_splits, shuffle=shuffle,
            random_state=7 if shuffle else None,
        ).split(np.zeros((n_rows, 1)))
    )
    te_max = max(len(test) for _, test in expected)
    assert {te_end - te_start for _, te_start, te_end in fold_bounds} == {te_max}
    assert perms.shape == (n_splits + 1, n_rows) and perms.dtype == np.int32
    for k, (train, test) in enumerate(expected):
        np.testing.assert_array_equal(kfold_folds[k][0], train)
        np.testing.assert_array_equal(kfold_folds[k][1], test)
        # the stage's rows are [train..., test...]: a train prefix and a
        # test tail, whose last len(test) rows are the fold's own
        assert fold_bounds[k] == (len(train), n_rows - te_max, n_rows)
        np.testing.assert_array_equal(perms[k][: len(train)], train)
        np.testing.assert_array_equal(perms[k][-len(test):], test)
        assert sorted(perms[k]) == list(range(n_rows))
    np.testing.assert_array_equal(perms[-1], np.arange(n_rows))


def test_bucket_program_refuses_unequal_test_lengths_by_name():
    plan = _plan_machine(_machine(_config("unequal")))
    unequal = ((100, 100, 200), (200, 200, 310), (310, 310, 410))
    with pytest.raises(ValueError, match="one test length") as refused:
        _bucket_program(plan.spec, 410, unequal, 1, 32, True, True)
    assert "[100, 110]" in str(refused.value)
    assert str(unequal) in str(refused.value)


def test_fold_without_a_training_sample_is_refused_before_any_program():
    windowed = {
        "gordo_tpu.models.models.LSTMAutoEncoder": {
            "kind": "lstm_symmetric", "dims": [4], "funcs": ["tanh"],
            "lookback_window": 120, "epochs": 1,
        }
    }
    plan = _plan_machine(_machine(_config("short", estimator=windowed)))
    assert _fold_geometry(plan, 1008)[0][0][0] == 252
    with pytest.raises(ValueError, match="yields no training samples.*short"):
        _fold_geometry(plan, 433)  # the first fold trains on 109 rows


def test_program_lookup_counts_its_cache_and_credits_the_first_compile():
    plan = _plan_machine(_machine(_config("lookup")))
    fold_bounds, _, perms = _fold_geometry(plan, 288)

    def count(result):
        return metric_catalog.PROGRAM_CACHE.value(result=result)

    _bucket_program.cache_clear()
    misses, hits = count("miss"), count("hit")
    saved = metric_catalog.COMPILE_SECONDS_SAVED.value()
    try:
        program, key, cached = _program_for(plan, 288, fold_bounds, False, None)
        assert not cached and (count("miss"), count("hit")) == (misses + 1, hits)
        # the chunk loop notes the wall of a miss's first dispatch ...
        batch_trainer._first_compile_walls[key] = 2.5
        again, key_again, cached = _program_for(plan, 288, fold_bounds, False, None)
        # ... and a later bucket of the same shapes is credited with it
        assert cached and again is program and key_again == key
        assert (count("miss"), count("hit")) == (misses + 1, hits + 1)
        assert metric_catalog.COMPILE_SECONDS_SAVED.value() == saved + 2.5
        # what only the data tells is part of the key
        _, other_key, cached = _program_for(plan, 432, fold_bounds, False, None)
        assert not cached and other_key != key
    finally:
        batch_trainer._first_compile_walls.pop(key, None)
        _bucket_program.cache_clear()


# ------------------------------------------------------- batchability table
def _detector_scaler(feature_range):
    def mutate(config):
        (detector,) = config["model"].values()
        detector["scaler"] = {MINMAX: {"feature_range": feature_range}}
    return mutate


def _pipeline_scaler(feature_range):
    def mutate(config):
        _steps(config)[0] = {MINMAX: {"feature_range": feature_range}}
    return mutate


def _estimator_kwargs(**kwargs):
    def mutate(config):
        (estimator,) = _steps(config)[1].values()
        estimator.update(kwargs)
    return mutate


def _detector_kwargs(**kwargs):
    def mutate(config):
        (detector,) = config["model"].values()
        detector.update(kwargs)
    return mutate


def _third_step(config):
    _steps(config).insert(1, "sklearn.preprocessing.StandardScaler")


def _single_step(config):
    del _steps(config)[0]


TRANSFORMER = {
    "gordo_tpu.models.models.TransformerAutoEncoder": {
        "kind": "transformer_model", "lookback_window": 16, "d_model": 16,
        "num_heads": 2, "ff_dim": 16, "num_blocks": 1, "epochs": 1,
    }
}


def _transformer(**kwargs):
    def mutate(config):
        estimator = copy.deepcopy(TRANSFORMER)
        next(iter(estimator.values())).update(kwargs)
        _steps(config)[1] = estimator
    return mutate


# reason -> (what leaves the machine to the serial builder, its nearest
# neighbour that the fleet program does build); a mutation edits the config,
# a dict is the machine's evaluation.cv (on the KFCV detector for KFold)
UNBATCHABLE = {
    "detector_scaler_feature_range": (
        _detector_scaler([0, 2]), _detector_scaler([0, 1])),
    "pipeline_scaler_feature_range": (
        _pipeline_scaler([-1, 1]), _pipeline_scaler([0, 1])),
    "time_series_split_gap": (
        {TSS: {"n_splits": 3, "gap": 2}}, {TSS: {"n_splits": 3, "gap": 0}}),
    "time_series_split_test_size": (
        {TSS: {"n_splits": 3, "test_size": 40}}, {TSS: {"n_splits": 4}}),
    "time_series_split_max_train_size": (
        {TSS: {"n_splits": 3, "max_train_size": 100}}, {TSS: {"n_splits": 2}}),
    "kfold_shuffled_unseeded": (
        {KFOLD: {"n_splits": 3, "shuffle": True}},
        {KFOLD: {"n_splits": 3, "shuffle": True, "random_state": 3}}),
    "another_splitter": (
        {"sklearn.model_selection.ShuffleSplit": {"n_splits": 3}},
        {KFOLD: {"n_splits": 3}}),
    "callbacks": (
        _estimator_kwargs(callbacks=[
            {"gordo_tpu.models.callbacks.EarlyStopping": {"monitor": "loss"}}
        ]),
        _estimator_kwargs(callbacks=[])),
    "validation_split": (
        _estimator_kwargs(validation_split=0.2),
        _estimator_kwargs(validation_split=0.0)),
    "detector_shuffle": (
        _detector_kwargs(shuffle=True), _detector_kwargs(shuffle=False)),
    "three_step_pipeline": (_third_step, _single_step),
    "tensor_parallel": (
        _transformer(tensor_parallel=2), _transformer(tensor_parallel=1)),
    "data_parallel": (
        _estimator_kwargs(data_parallel=2), _estimator_kwargs(data_parallel=1)),
    "ring_attention": (
        _transformer(attention="ring"), _transformer(attention="flash")),
}


@pytest.mark.parametrize("reason", sorted(UNBATCHABLE))
def test_plan_machine_leaves_to_the_serial_builder(reason):
    def machine_of(change):
        cv = change if isinstance(change, dict) else None
        detector = KFCV_DETECTOR if cv and KFOLD in cv else DETECTOR
        config = _config(reason.replace("_", "-"), detector=detector)
        if cv is None:
            change(config)
        return _machine(config, cv=cv)

    serial, planned = UNBATCHABLE[reason]
    assert _plan_machine(machine_of(serial)) is None
    plan = _plan_machine(machine_of(planned))
    assert plan is not None and plan.spec.n_features == 2


# ------------------------------------ the plan stage, in both orchestrators
# (serial_fallback=False in both: test_parallel.py's
# test_serial_fallback_disabled_raises)
ORCHESTRATORS = ["static", "elastic"]
UNBATCHABLE_MODEL = {
    PIPELINE: {"steps": [MINMAX, "sklearn.linear_model.LinearRegression"]}
}


def _fleet(prefix, n=2, unbatchable=0):
    configs = [_config(f"{prefix}-{i}") for i in range(n)]
    for i in range(unbatchable):
        configs.append(dict(_config(f"{prefix}-sk-{i}"), model=UNBATCHABLE_MODEL))
    return [_machine(config) for config in configs]


def _builder(orchestrator, machines, root, run="run", **kwargs):
    """A builder of ``machines`` whose artifacts go to ``root/run`` and
    whose registry is ``root/registry``: a second ``run`` is a logically new
    build (its own scheduler state) over the same registry."""
    return BatchedModelBuilder(
        machines,
        mesh=default_mesh(devices=jax.devices()[:1]),
        output_dir=str(root / run),
        model_register_dir=str(root / "registry"),
        elastic=orchestrator == "elastic",
        **kwargs,
    )


def _from_cache(machine_out):
    user_defined = machine_out.metadata.user_defined or {}
    return user_defined.get("build-metadata", {}) == {"from_cache": True}


def _spy_on_buckets(monkeypatch):
    trained = []
    build_bucket = BatchedModelBuilder._build_bucket

    def spy(self, bucket, global_idxs):
        trained.extend(plan.machine.name for plan in bucket)
        return build_bucket(self, bucket, global_idxs)

    monkeypatch.setattr(BatchedModelBuilder, "_build_bucket", spy)
    return trained


@pytest.mark.parametrize("orchestrator", ORCHESTRATORS)
def test_cache_hit_is_returned_once_and_not_retrained(
    orchestrator, tmp_path, monkeypatch
):
    prefix = f"hit-{orchestrator}"
    names = [f"{prefix}-{i}" for i in range(2)]
    first = _builder(orchestrator, _fleet(prefix), tmp_path).build()
    assert not any(_from_cache(m) for _, m in first)
    trained = _spy_on_buckets(monkeypatch)
    cached = metric_catalog.BUILD_MACHINES.value(outcome="cached")
    again = _builder(orchestrator, _fleet(prefix), tmp_path).build()
    assert [m.name for _, m in again] == names
    assert all(_from_cache(m) for _, m in again)
    assert trained == []
    assert metric_catalog.BUILD_MACHINES.value(outcome="cached") == cached + 2


@pytest.mark.parametrize("orchestrator", ORCHESTRATORS)
def test_corrupt_cache_hit_is_evicted_and_rebuilt(orchestrator, tmp_path, monkeypatch):
    prefix = f"corrupt-{orchestrator}"
    machines = _fleet(prefix)
    _builder(orchestrator, machines, tmp_path).build()
    victim = machines[1]
    with open(tmp_path / "run" / victim.name / "model.pkl", "wb") as f:
        f.write(b"not a pickle")
    trained = _spy_on_buckets(monkeypatch)
    again = _builder(orchestrator, _fleet(prefix), tmp_path, run="rerun").build()
    assert {m.name: _from_cache(m) for _, m in again} == {
        f"{prefix}-0": True, victim.name: False,
    }
    assert trained == [victim.name]
    # the registry now names the rebuilt artifact, which loads
    path = disk_registry.get_value(
        str(tmp_path / "registry"), ModelBuilder.calculate_cache_key(victim)
    )
    assert path == str(tmp_path / "rerun" / victim.name)
    assert ModelBuilder.load_from_cache(path) is not None


@pytest.mark.parametrize("orchestrator", ORCHESTRATORS)
def test_hit_from_another_output_dir_is_materialised_in_this_one(
    orchestrator, tmp_path
):
    prefix = f"moved-{orchestrator}"
    _builder(orchestrator, _fleet(prefix), tmp_path, run="yesterday").build()
    again = _builder(orchestrator, _fleet(prefix), tmp_path, run="today").build()
    assert all(_from_cache(m) for _, m in again)
    for _, machine_out in again:
        for run in ("yesterday", "today"):
            artifact = tmp_path / run / machine_out.name
            assert os.path.exists(artifact / "model.pkl"), artifact
            assert os.path.exists(artifact / "metadata.json"), artifact


@pytest.mark.parametrize("orchestrator", ORCHESTRATORS)
def test_unbatchable_machine_is_built_serially_and_counted(orchestrator, tmp_path):
    prefix = f"serial-{orchestrator}"
    before = metric_catalog.SERIAL_FALLBACKS.value(reason="unbatchable")
    builder = _builder(orchestrator, _fleet(prefix, unbatchable=1), tmp_path)
    results = builder.build()
    assert [m.name for _, m in results] == [
        f"{prefix}-0", f"{prefix}-1", f"{prefix}-sk-0"
    ]
    assert metric_catalog.SERIAL_FALLBACKS.value(reason="unbatchable") == before + 1
    model, _ = results[-1]
    assert model.predict(np.random.rand(5, 2)).shape[0] == 5
    assert os.path.exists(tmp_path / "run" / f"{prefix}-sk-0" / "model.pkl")
    assert builder.quarantine_records == []
