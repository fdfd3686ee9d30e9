"""A machine is final when its own chunk is assembled: its durations are its
chunk's wall, written once with the artifact; its slice of the chunk and its
divergence check run in its pool job, and a diverged lane ends as it did when
the build thread checked it."""

import json
import os
import threading
from concurrent.futures import Future

import jax
import pytest

from gordo_tpu import serializer
from gordo_tpu.observability import telemetry
from gordo_tpu.parallel import BatchedModelBuilder, batch_trainer, default_mesh
from gordo_tpu.util import faults

from test_build_stages import _machines
from test_fetch_stream import _artifact, _persisted

CHUNK = 2
N = 2 * CHUNK  # one bucket of two chunks of two, on one device
N_FOLDS = 3


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    monkeypatch.delenv(faults.PLAN_ENV, raising=False)
    monkeypatch.setenv("GORDO_TPU_FAULT_BACKOFF_BASE", "0")
    faults.reset_plan()
    telemetry.reset()
    yield
    faults.reset_plan()
    telemetry.reset()


def _builder(prefix, out_dir=None, **kwargs):
    return BatchedModelBuilder(
        _machines(prefix, n=N),
        mesh=default_mesh(devices=jax.devices()[:1]),
        chunk_size=CHUNK,
        output_dir=str(out_dir) if out_dir else None,
        **kwargs,
    )


def _durations(build_metadata):
    """(fit, cv, phases.fit, phases.cross_validation) of a ``BuildMetadata``
    as a dict, which is how the file holds it."""
    return (
        build_metadata["model"]["model_training_duration_sec"],
        build_metadata["model"]["cross_validation"]["cv_duration_sec"],
        build_metadata["phases"]["fit"],
        build_metadata["phases"]["cross_validation"],
    )


@pytest.fixture(scope="module")
def traced_build(tmp_path_factory):
    """One build of the bucket with ``output_dir``, its results and the
    build thread's spans."""
    out_dir = tmp_path_factory.mktemp("durations")
    telemetry.reset()
    telemetry.start_trace()
    results = _builder("dur", out_dir).build()
    events = [
        e for e in telemetry.stop_trace()["traceEvents"]
        if e["tid"] == threading.get_ident()
    ]
    telemetry.reset()
    assert [m.name for _, m in results] == [f"dur-{i}" for i in range(N)]
    return out_dir, results, events


def test_durations_in_the_files_are_those_of_the_returned_machines(traced_build):
    out_dir, results, _ = traced_build
    for _, machine in results:
        returned = _durations(machine.to_dict()["metadata"]["build_metadata"])
        on_disk = _durations(
            serializer.load_metadata(os.path.join(out_dir, machine.name))
            ["metadata"]["build_metadata"]
        )
        assert on_disk == returned, machine.name
        assert returned[0] > 0.0 and returned[:2] == returned[2:]


def test_machines_of_a_chunk_share_its_wall_split_by_fold_count(traced_build):
    _, results, _ = traced_build
    durations = [
        _durations(m.to_dict()["metadata"]["build_metadata"]) for _, m in results
    ]
    for start in range(0, N, CHUNK):
        assert len(set(durations[start : start + CHUNK])) == 1
    # each chunk reports its own wall: the first bears the compile
    assert durations[0] != durations[CHUNK]
    for fit, cv, _, _ in durations:
        assert cv == pytest.approx(N_FOLDS * fit, rel=1e-12)


def test_durations_of_a_bucket_sum_to_its_train_wall(traced_build):
    _, results, events = traced_build
    (compile_start,) = [e["ts"] for e in events if e["name"] == "compile"]
    (train_end,) = [e["ts"] + e["dur"] for e in events if e["name"] == "train"]
    train_wall = (train_end - compile_start) / 1e6  # the first dispatch on
    reported = sum(
        sum(_durations(m.to_dict()["metadata"]["build_metadata"])[:2])
        for _, m in results
    )
    assert reported == pytest.approx(train_wall, rel=0.10)


def _diverge(monkeypatch, machine):
    monkeypatch.setenv(
        faults.PLAN_ENV,
        json.dumps({"rules": [{"site": "diverge", "machine": machine}]}),
    )
    faults.reset_plan()


def test_diverged_lane_is_quarantined_from_its_pool_job(monkeypatch, tmp_path):
    lost = f"dv-{N - 1}"  # a lane of the second chunk
    _diverge(monkeypatch, lost)
    verdicts = {}
    assemble_and_persist = BatchedModelBuilder._assemble_and_persist

    def job(self, plan, *args, **kwargs):
        verdict = assemble_and_persist(self, plan, *args, **kwargs)
        verdicts[plan.machine.name] = (threading.get_ident(), verdict)
        return verdict

    monkeypatch.setattr(BatchedModelBuilder, "_assemble_and_persist", job)
    builder = _builder("dv", tmp_path)
    results = builder.build()
    others = [f"dv-{i}" for i in range(N - 1)]
    assert [m.name for _, m in results] == others
    assert _persisted(tmp_path) == others
    assert not os.path.exists(tmp_path / lost)  # nothing of it, not a file
    # the verdict came from the pool, and is the record the build keeps
    thread, verdict = verdicts[lost]
    assert thread != threading.get_ident()
    [record] = builder.quarantine_records
    assert record is verdict
    assert record.to_dict() == faults.QuarantineRecord(
        lost, faults.STAGE_TRAINING, "diverged", "injected divergence"
    ).to_dict()
    [quarantined] = builder.quarantined
    assert quarantined.name == lost
    assert quarantined.metadata.build_metadata.fault_domain == record.to_dict()


def test_fail_fast_raises_the_diverged_lanes_error_out_of_build(
    monkeypatch, tmp_path
):
    lost = f"dvff-{N - 1}"
    _diverge(monkeypatch, lost)
    builder = _builder("dvff", tmp_path, fail_fast=True)
    with pytest.raises(faults.DivergedModelError, match=lost):
        builder.build()
    assert lost not in _persisted(tmp_path)
    assert builder.quarantine_records == []


class _BuildThreadPool:
    """The assembly pool as the build thread itself: a job runs where it is
    submitted, which is where a machine was sliced and checked before."""

    def __init__(self, max_workers=None):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def submit(self, fn, *args, **kwargs):
        future = Future()
        try:
            future.set_result(fn(*args, **kwargs))
        except Exception as exc:  # noqa: BLE001 - the future carries it
            future.set_exception(exc)
        return future


def test_artifacts_are_bitwise_those_sliced_on_the_build_thread(
    monkeypatch, tmp_path
):
    """Same seed, same weights, thresholds and scores, bit for bit, wherever
    a machine's slice of its chunk is taken."""
    pooled = {
        m.name: _artifact(model, m)
        for model, m in _builder("bits", tmp_path / "pool").build()
    }
    build_bucket = BatchedModelBuilder._build_bucket

    def on_the_build_thread(self, bucket, global_idxs):
        # the fetch pool is running by now: only the assembly pool is swapped
        with monkeypatch.context() as patch:
            patch.setattr(batch_trainer, "ThreadPoolExecutor", _BuildThreadPool)
            return build_bucket(self, bucket, global_idxs)

    monkeypatch.setattr(BatchedModelBuilder, "_build_bucket", on_the_build_thread)
    inline = {
        m.name: _artifact(model, m)
        for model, m in _builder("bits", tmp_path / "inline").build()
    }
    assert sorted(pooled) == [f"bits-{i}" for i in range(N)]
    for name in pooled:
        assert inline[name] == pooled[name], name
