"""The fleet's fetch as a stream that the chunk loop consumes: a bucket's
first chunk is dispatched when its own machines have arrived, later chunks'
data is fetched under the device, and a machine lost to its fetch, or one
that arrives with another shape, ends as it did behind the old barrier."""

import collections
import json
import os
import pickle
import threading

import jax
import pytest
import yaml

from gordo_tpu.dataset.data_provider import (
    RandomDataProvider,
    register_data_provider,
)
from gordo_tpu.observability import telemetry
from gordo_tpu.parallel import BatchedModelBuilder, batch_trainer, default_mesh
from gordo_tpu.util import faults
from gordo_tpu.workflow.normalized_config import NormalizedConfig

CHUNK = 2
N = 3 * CHUNK  # three chunks of two on one device
LATE = N - CHUNK + 1  # a machine of the last chunk


@register_data_provider
class GatedDataProvider(RandomDataProvider):
    """RandomDataProvider that counts its calls by machine and holds a
    gated machine's fetch until the gate opens (tags are ``<machine>-a``)."""

    calls: collections.Counter = collections.Counter()
    gated: frozenset = frozenset()
    gate = threading.Event()
    timed_out = False

    @classmethod
    def arm(cls, gated=()):
        cls.calls = collections.Counter()
        cls.gated = frozenset(gated)
        cls.gate = threading.Event()
        cls.timed_out = False

    def load_series(self, train_start_date, train_end_date, tag_list, dry_run=False):
        machine = tag_list[0].name.rsplit("-", 1)[0]
        type(self).calls[machine] += 1
        if machine in self.gated and not self.gate.wait(timeout=60):
            type(self).timed_out = True
        return super().load_series(train_start_date, train_end_date, tag_list, dry_run)


def _machines(prefix, short=()):
    """``N`` small hourglass machines; those in ``short`` get half a day of
    rows where the rest get a day."""
    blocks = "".join(
        f"""
  - name: {prefix}-{i}
    dataset:
      tags: [{prefix}-{i}-a, {prefix}-{i}-b]
      train_start_date: '2019-01-01T00:00:00+00:00'
      train_end_date: '2019-01-0{1 if i in short else 2}T{12 if i in short else 0:02d}:00:00+00:00'
      data_provider: {{type: GatedDataProvider}}
    model:
      gordo_tpu.models.anomaly.diff.DiffBasedAnomalyDetector:
        require_thresholds: true
        base_estimator:
          sklearn.pipeline.Pipeline:
            steps:
            - sklearn.preprocessing.MinMaxScaler
            - gordo_tpu.models.models.AutoEncoder:
                kind: feedforward_hourglass
                epochs: 1
"""
        for i in range(N)
    )
    config = yaml.safe_load("machines:" + blocks)
    return NormalizedConfig(config, project_name="stream").machines


def _builder(machines, out_dir=None, **kwargs):
    return BatchedModelBuilder(
        machines,
        mesh=default_mesh(devices=jax.devices()[:1]),
        chunk_size=CHUNK,
        output_dir=str(out_dir) if out_dir else None,
        **kwargs,
    )


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    monkeypatch.delenv(faults.PLAN_ENV, raising=False)
    monkeypatch.setenv("GORDO_TPU_FAULT_BACKOFF_BASE", "0")
    faults.reset_plan()
    telemetry.reset()
    GatedDataProvider.arm()
    yield
    GatedDataProvider.gate.set()
    faults.reset_plan()
    telemetry.reset()


def _set_plan(monkeypatch, rules):
    monkeypatch.setenv(faults.PLAN_ENV, json.dumps({"rules": rules}))
    faults.reset_plan()


def _open_gate_at_first_launch(monkeypatch):
    """The gated fetches may return only once a chunk has been launched:
    behind a barrier they would never (the gate then times out)."""
    noted = batch_trainer._note_shard_devices

    def note(*args):
        GatedDataProvider.gate.set()
        return noted(*args)

    monkeypatch.setattr(batch_trainer, "_note_shard_devices", note)


def _fetch_everything_first(monkeypatch):
    """The old barrier: every fetch has ended before the first dispatch.
    The stage waits for the first ``n`` of its machines; here for all."""
    stage = BatchedModelBuilder._fetch_stage

    def wait_for_all(self, pool, order, n_first):
        stage(self, pool, order, len(order))
        assert all(plan.fetch.done() for plan in order)

    monkeypatch.setattr(BatchedModelBuilder, "_fetch_stage", wait_for_all)


def _artifact(model, machine):
    """What of a machine's build does not depend on the clock."""
    build = machine.metadata.build_metadata
    dataset_meta = dict(build.dataset.dataset_meta)
    assert dataset_meta.pop("query_duration_sec") > 0
    return (
        pickle.dumps(model),
        json.dumps(build.model.cross_validation.scores, sort_keys=True),
        str(build.model.cross_validation.splits),
        json.dumps(build.model.model_meta, sort_keys=True, default=str),
        json.dumps(dataset_meta, sort_keys=True, default=str),
        build.model.model_offset,
    )


def test_first_chunk_is_launched_while_the_last_chunk_is_being_fetched(
    monkeypatch, tmp_path
):
    last_chunk = {f"gate-{i}" for i in range(N - CHUNK, N)}
    GatedDataProvider.arm(gated=last_chunk)
    _open_gate_at_first_launch(monkeypatch)
    telemetry.start_trace()
    results = _builder(_machines("gate"), tmp_path).build()
    events = telemetry.stop_trace()["traceEvents"]
    assert not GatedDataProvider.timed_out
    assert [m.name for _, m in results] == [f"gate-{i}" for i in range(N)]
    first_launch_end = min(
        e["ts"] + e["dur"] for e in events if e["name"] == "launch"
    )
    gated_fetch_ends = [
        e["ts"] + e["dur"]
        for e in events
        if e["name"] == "fetch" and e["args"]["machine"] in last_chunk
    ]
    assert len(gated_fetch_ends) == CHUNK
    assert first_launch_end < min(gated_fetch_ends)
    # the last chunk's stage waited for both, and said so
    waits = [e for e in events if e["name"] == "fetch_wait"]
    assert [e["args"]["chunk_start"] for e in waits] == ["2", "4"]
    assert set(GatedDataProvider.calls.values()) == {1}


def test_streamed_build_is_bitwise_the_build_that_fetched_everything_first(
    monkeypatch, tmp_path
):
    machines = _machines("same")
    with monkeypatch.context() as patch:
        _fetch_everything_first(patch)
        barrier = {
            m.name: _artifact(model, m)
            for model, m in _builder(machines, tmp_path / "barrier").build()
        }
    GatedDataProvider.arm(gated={f"same-{i}" for i in range(CHUNK, N)})
    _open_gate_at_first_launch(monkeypatch)
    streamed = {
        m.name: _artifact(model, m)
        for model, m in _builder(machines, tmp_path / "streamed").build()
    }
    assert not GatedDataProvider.timed_out
    assert sorted(streamed) == sorted(m.name for m in machines)
    for name in streamed:
        assert streamed[name] == barrier[name], name


LOST = {
    # fault -> (the fault plan's rule, the stage and reason it is quarantined with)
    "exhausted": (
        {"site": "data_fetch", "times": -1, "error": "permanent"},
        (faults.STAGE_DATA_FETCH, "permanent_fetch_failure"),
        faults.PermanentFault,
    ),
    "non_finite": (
        {"site": "poison_nan"},
        (faults.STAGE_DATA_VALIDATION, "non_finite_data"),
        faults.NonFiniteDataError,
    ),
}


def _persisted(out_dir):
    return sorted(
        name for name in os.listdir(out_dir)
        if os.path.exists(os.path.join(out_dir, name, "model.pkl"))
    )


@pytest.mark.parametrize("fault", sorted(LOST))
def test_machine_lost_in_a_later_chunk_is_quarantined_alone(
    fault, monkeypatch, tmp_path
):
    rule, (stage, reason), _ = LOST[fault]
    prefix = fault.replace("_", "-")
    lost = f"{prefix}-{LATE}"
    _set_plan(monkeypatch, [dict(rule, machine=lost)])
    _open_gate_at_first_launch(monkeypatch)
    GatedDataProvider.arm(gated={lost})
    builder = _builder(_machines(prefix), tmp_path)
    results = builder.build()
    others = [f"{prefix}-{i}" for i in range(N) if i != LATE]
    assert [m.name for _, m in results] == others
    assert _persisted(tmp_path) == others  # and no padding lane
    [record] = builder.quarantine_records
    assert (record.machine, record.stage, record.reason) == (lost, stage, reason)


@pytest.mark.parametrize("fault", sorted(LOST))
def test_barrier_is_the_stream_waited_out_and_quarantines_alike(
    fault, monkeypatch, tmp_path
):
    """Where every fetch is waited for and every outcome taken before
    anything is bucketed (``_fetch_all``: the elastic build here), a machine
    lost to its fetch gets the record the stream gives it."""
    rule, (stage, reason), _ = LOST[fault]
    prefix = f"all-{fault.replace('_', '-')}"
    lost = f"{prefix}-{LATE}"
    _set_plan(monkeypatch, [dict(rule, machine=lost)])
    waited_out = []
    fetch_all = BatchedModelBuilder._fetch_all

    def spy(self, pool, plans):
        buckets = fetch_all(self, pool, plans)
        # nothing is in flight, the lost machine is out, the key is full
        waited_out.append(sorted(p.machine.name for p in plans.values()))
        assert all(p.fetch is None and p.X is not None for p in plans.values())
        assert set(buckets) == {p.bucket_key() for p in plans.values()}
        return buckets

    monkeypatch.setattr(BatchedModelBuilder, "_fetch_all", spy)
    records = {}
    for mode in ("streamed", "waited_out"):
        faults.reset_plan()
        builder = _builder(
            _machines(prefix), tmp_path / mode, elastic=mode == "waited_out"
        )
        results = builder.build()
        assert [m.name for _, m in results] == [
            f"{prefix}-{i}" for i in range(N) if i != LATE
        ]
        [record] = builder.quarantine_records
        records[mode] = record.to_dict()
    assert waited_out == [[f"{prefix}-{i}" for i in range(N) if i != LATE]]
    assert records["streamed"] == records["waited_out"]
    assert (records["streamed"]["stage"], records["streamed"]["reason"]) == (
        stage, reason,
    )


@pytest.mark.parametrize("fault", sorted(LOST))
def test_fail_fast_raises_the_lost_machines_own_error(fault, monkeypatch, tmp_path):
    rule, _, error = LOST[fault]
    lost = f"ff-{fault.replace('_', '-')}-{LATE}"
    _set_plan(monkeypatch, [dict(rule, machine=lost)])
    builder = _builder(_machines(lost[: -len(f"-{LATE}")]), tmp_path, fail_fast=True)
    with pytest.raises(error, match=lost):
        builder.build()
    # the pool that outlives the fetch stage does not outlive the build
    assert not [t for t in threading.enumerate() if t.name.startswith("gordo-fetch")]


def test_machine_with_another_row_count_is_built_in_a_second_bucket(
    monkeypatch, tmp_path
):
    buckets = []
    build_bucket = BatchedModelBuilder._build_bucket

    def spy(self, bucket, global_idxs):
        buckets.append([p.machine.name for p in bucket])
        return build_bucket(self, bucket, global_idxs)

    monkeypatch.setattr(BatchedModelBuilder, "_build_bucket", spy)
    builder = _builder(_machines("rows", short={LATE}), tmp_path)
    results = builder.build()
    names = [f"rows-{i}" for i in range(N)]
    assert [m.name for _, m in results] == names
    assert _persisted(tmp_path) == names
    assert builder.quarantine_records == []
    assert buckets == [names, [f"rows-{LATE}"]]
    n_train = {
        m.name: m.metadata.build_metadata.model.cross_validation.splits["fold-3-n-train"]
        for _, m in results
    }
    assert n_train[f"rows-{LATE}"] < n_train["rows-0"]
    assert set(GatedDataProvider.calls.values()) == {1}


@pytest.mark.parametrize("error, counter", [
    ("transient", "BUCKET_RETRIES"),
    ("resource_exhausted", "OOM_BISECTIONS"),
])
def test_recovery_ladder_asks_the_provider_once_a_machine(
    error, counter, monkeypatch, tmp_path
):
    """A bucket retried, and a bucket bisected, reuse what has arrived and
    wait for what has not: no machine is fetched twice."""
    from gordo_tpu.observability import metrics as metric_catalog

    prefix = f"ladder-{error.split('_')[0]}"
    _set_plan(monkeypatch, [
        {"site": "bucket_compile", "machine": f"{prefix}-0", "times": 1,
         "error": error},
    ])
    GatedDataProvider.arm(gated={f"{prefix}-{i}" for i in range(N - CHUNK, N)})
    _open_gate_at_first_launch(monkeypatch)
    before = getattr(metric_catalog, counter).value()
    builder = _builder(_machines(prefix), tmp_path)
    results = builder.build()
    assert not GatedDataProvider.timed_out
    assert getattr(metric_catalog, counter).value() == before + 1
    assert [m.name for _, m in results] == [f"{prefix}-{i}" for i in range(N)]
    assert builder.quarantine_records == []
    assert GatedDataProvider.calls == {f"{prefix}-{i}": 1 for i in range(N)}
