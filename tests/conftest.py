"""
Test harness configuration.

XLA-CPU is the "fake backend" for TPU (SURVEY.md §4 takeaway): tests force the
CPU platform with 8 virtual devices so mesh/sharding logic runs anywhere; the
same code path runs unchanged on real TPU chips.
"""


import pytest

from gordo_tpu import serializer
from gordo_tpu.builder.local_build import local_build
from gordo_tpu.dataset import SensorTag


def _under_xdist(config) -> bool:
    return getattr(config.option, "dist", "no") != "no"


def pytest_configure(config):
    """Under xdist (``-n 6 --dist loadfile``, the tier-1 command) the files
    go out in the order :func:`pytest_collection_modifyitems` leaves them
    in, not in xdist's own."""
    if _under_xdist(config) and hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


def pytest_collection_modifyitems(config, items):
    """xdist's order, largest file first, with tests/chipbench/ put before
    the rest, so that it runs on fresh workers. Its
    ``test_a_stage_starts_beside_nothing`` adds up the whole process's
    ``jax.live_arrays()`` with 15.9 KB of room, and a worker that has run
    test_server, test_batcher, test_pipeline_parallel or test_expert_parallel
    keeps 40-460 KB live (``CrossModelBatcher``'s dispatcher threads never
    stop, and hold their banks and their last batch): largest-first alone put
    those ahead of it. A run in one process keeps its collection order."""
    if not _under_xdist(config):
        return
    size = {}
    for item in items:
        path = item.nodeid.split("::", 1)[0]
        size[path] = size.get(path, 0) + 1
    # a stable sort: files of one size, and a file's tests, stay as collected
    items.sort(key=lambda item: (
        "tests/chipbench/" not in item.nodeid,
        -size[item.nodeid.split("::", 1)[0]],
    ))


@pytest.fixture(scope="session")
def sensors():
    return [SensorTag(f"tag-{i}", asset="asset") for i in range(4)]


@pytest.fixture(scope="session")
def gordo_name():
    return "machine-1"


@pytest.fixture(scope="session")
def second_gordo_name():
    return "machine-2"


@pytest.fixture(scope="session")
def gordo_project():
    return "gordo-test"


@pytest.fixture(scope="session")
def config_str(gordo_name: str, second_gordo_name: str, sensors):
    tag_lines = "\n".join(f"        - {t.name}" for t in sensors)
    return f"""
machines:
  - name: {gordo_name}
    dataset:
      tags:
{tag_lines}
      target_tag_list:
{tag_lines}
      train_start_date: '2019-01-01T00:00:00+00:00'
      train_end_date: '2019-01-10T00:00:00+00:00'
      asset: asgb
      data_provider:
        type: RandomDataProvider
    metadata:
      information: Some sweet information about the model
    model:
      gordo_tpu.models.anomaly.diff.DiffBasedAnomalyDetector:
        require_thresholds: false
        base_estimator:
          sklearn.pipeline.Pipeline:
            steps:
            - sklearn.preprocessing.MinMaxScaler
            - gordo_tpu.models.models.AutoEncoder:
                kind: feedforward_hourglass
                epochs: 1
  - name: {second_gordo_name}
    dataset:
      tags:
{tag_lines}
      target_tag_list:
{tag_lines}
      train_start_date: '2019-01-01T00:00:00+00:00'
      train_end_date: '2019-01-10T00:00:00+00:00'
      asset: asgb
      data_provider:
        type: RandomDataProvider
    metadata:
      information: Some sweet information about the model
    model:
      gordo_tpu.models.anomaly.diff.DiffBasedAnomalyDetector:
        window: 144
        require_thresholds: false
        base_estimator:
          sklearn.pipeline.Pipeline:
            steps:
            - sklearn.preprocessing.MinMaxScaler
            - gordo_tpu.models.models.AutoEncoder:
                kind: feedforward_hourglass
                epochs: 1
"""


@pytest.fixture(scope="session")
def gordo_revision():
    return "1604321820000"


@pytest.fixture(scope="session")
def model_collection_directory(tmp_path_factory, gordo_revision: str):
    path = tmp_path_factory.mktemp("collection") / gordo_revision
    path.mkdir(parents=True, exist_ok=True)
    return str(path)


@pytest.fixture(scope="session")
def trained_model_directories(model_collection_directory: str, config_str: str):
    """Train real models once per session (reference conftest.py:225-244)."""
    import os as _os

    model_directories = {}
    for model, machine in local_build(config_str=config_str):
        metadata_dict = machine.to_dict()
        model_name = metadata_dict["name"]
        model_dir = _os.path.join(model_collection_directory, model_name)
        _os.makedirs(model_dir, exist_ok=True)
        serializer.dump(model, model_dir, metadata=metadata_dict)
        model_directories[model_name] = model_dir
    return model_directories


@pytest.fixture(scope="session")
def trained_model_directory(trained_model_directories, gordo_name):
    return trained_model_directories[gordo_name]


@pytest.fixture
def metadata(trained_model_directory):
    return serializer.load_metadata(trained_model_directory)


@pytest.fixture(scope="session")
def X_payload(sensors):
    """The canonical server-test input frame (20 rows x the session's
    sensor tags) — shared by the in-process server tests and the
    multi-process pool drive so the two suites pin one wire payload."""
    import numpy as np
    import pandas as pd

    idx = pd.date_range("2020-01-01", periods=20, freq="10min", tz="UTC")
    return pd.DataFrame(
        np.random.RandomState(0).rand(20, len(sensors)),
        columns=[t.name for t in sensors],
        index=idx,
    )
