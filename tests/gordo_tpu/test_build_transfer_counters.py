"""What a fleet build counts of its host link and of its fetch stream:
``gordo_build_transfer_bytes_total{direction}`` holds the bytes of what
``dispatch()`` stacked and put on the device and of what ``fetch()`` pulled
back, and ``gordo_build_fetch_starved_chunks_total`` counts a later chunk
whose own fetch outlasted the chunk before it on the device."""

import threading

import jax
import numpy as np
import pytest
import yaml

from gordo_tpu.dataset.data_provider import (
    RandomDataProvider,
    register_data_provider,
)
from gordo_tpu.observability import metrics as metric_catalog
from gordo_tpu.observability import telemetry
from gordo_tpu.parallel import BatchedModelBuilder, batch_trainer, default_mesh
from gordo_tpu.parallel import distributed
from gordo_tpu.workflow.normalized_config import NormalizedConfig

CHUNK = 2
N = 2 * CHUNK  # two chunks of two on one device


@register_data_provider
class SlowSecondChunkProvider(RandomDataProvider):
    """RandomDataProvider that holds a gated machine's fetch until the gate
    opens (tags are ``<machine>-a``)."""

    gated: frozenset = frozenset()
    gate = threading.Event()

    @classmethod
    def arm(cls, gated=()):
        cls.gated = frozenset(gated)
        cls.gate = threading.Event()

    def load_series(self, train_start_date, train_end_date, tag_list, dry_run=False):
        machine = tag_list[0].name.rsplit("-", 1)[0]
        if machine in self.gated:
            assert self.gate.wait(timeout=60), f"{machine}'s gate never opened"
        return super().load_series(train_start_date, train_end_date, tag_list, dry_run)


def _machines(prefix, epochs):
    """``N`` small hourglass machines; ``epochs`` gives each test a program
    of its own, so the first chunk's launch compiles."""
    blocks = "".join(
        f"""
  - name: {prefix}-{i}
    dataset:
      tags: [{prefix}-{i}-a, {prefix}-{i}-b, {prefix}-{i}-c]
      train_start_date: '2019-01-01T00:00:00+00:00'
      train_end_date: '2019-01-02T00:00:00+00:00'
      data_provider: {{type: SlowSecondChunkProvider}}
    model:
      gordo_tpu.models.anomaly.diff.DiffBasedAnomalyDetector:
        require_thresholds: true
        base_estimator:
          sklearn.pipeline.Pipeline:
            steps:
            - sklearn.preprocessing.MinMaxScaler
            - gordo_tpu.models.models.AutoEncoder:
                kind: feedforward_hourglass
                epochs: {epochs}
"""
        for i in range(N)
    )
    return NormalizedConfig(yaml.safe_load("machines:" + blocks), project_name="link").machines


def _build(machines, tmp_path):
    builder = BatchedModelBuilder(
        machines, mesh=default_mesh(devices=jax.devices()[:1]),
        chunk_size=CHUNK, output_dir=str(tmp_path),
    )
    results = builder.build()
    assert [m.name for _, m in results] == [m.name for m in machines]
    return results


@pytest.fixture(autouse=True)
def _fresh():
    telemetry.reset()
    SlowSecondChunkProvider.arm()
    yield
    SlowSecondChunkProvider.gate.set()
    telemetry.reset()


def _counted():
    return {
        "h2d": metric_catalog.TRANSFER_BYTES.value(direction="h2d"),
        "d2h": metric_catalog.TRANSFER_BYTES.value(direction="d2h"),
        "starved": metric_catalog.FETCH_STARVED_CHUNKS.value(),
    }


def _nbytes(tree):
    return sum(np.asarray(leaf).nbytes for leaf in jax.tree_util.tree_leaves(tree))


def test_bytes_across_the_link_are_what_was_stacked_and_pulled(monkeypatch, tmp_path):
    put, pulled, lock = [], {}, threading.Lock()
    make_global_stacked = distributed.make_global_stacked

    def spy_put(sharding, arr):
        put.append(np.asarray(arr).nbytes)
        return make_global_stacked(sharding, arr)

    assemble = BatchedModelBuilder._assemble_and_persist

    def spy_assemble(self, plan, j, params_stack, losses, fold_preds, non_finite, *rest):
        with lock:  # a chunk's machines share its pulled outputs
            pulled[id(losses)] = _nbytes((params_stack, losses, fold_preds, non_finite))
        return assemble(self, plan, j, params_stack, losses, fold_preds, non_finite, *rest)

    note_layer_counts = batch_trainer._note_layer_counts
    stats = []

    def spy_counts(counted, rows, n_live):
        stats.append(_nbytes(counted))
        return note_layer_counts(counted, rows, n_live)

    monkeypatch.setattr(distributed, "make_global_stacked", spy_put)
    monkeypatch.setattr(BatchedModelBuilder, "_assemble_and_persist", spy_assemble)
    monkeypatch.setattr(batch_trainer, "_note_layer_counts", spy_counts)
    _build(_machines("link", 2), tmp_path)
    counted = _counted()
    # X, y, seeds a chunk, and the bucket's fold permutations once if it has any
    assert len(put) in (2 * 3, 2 * 3 + 1)
    assert counted["h2d"] == sum(put) > 0
    assert len(pulled) == len(stats) == 2
    assert counted["d2h"] == sum(pulled.values()) + sum(stats) > 0
    # every fetch had ended under the first chunk's compile: nothing starved
    assert counted["starved"] == 0


def test_a_chunk_whose_fetch_outlasts_the_chunk_before_it_starved(monkeypatch, tmp_path):
    """The second chunk's fetch is held until the first chunk has finished on
    the device: its wait ends with the device idle."""
    machines = _machines("starve", 3)
    SlowSecondChunkProvider.arm(gated={m.name for m in machines[CHUNK:]})
    note_shard_devices = batch_trainer._note_shard_devices
    openers = []

    def open_when_done(X_d, outputs0):
        if not openers:
            def opener():
                jax.block_until_ready(outputs0)
                SlowSecondChunkProvider.gate.set()

            openers.append(threading.Thread(target=opener, daemon=True))
            openers[0].start()
        return note_shard_devices(X_d, outputs0)

    monkeypatch.setattr(batch_trainer, "_note_shard_devices", open_when_done)
    _build(machines, tmp_path)
    assert _counted()["starved"] == 1


def test_a_chunk_whose_fetch_ends_under_the_chunk_before_did_not_starve(
    monkeypatch, tmp_path
):
    """The second chunk's fetch is held until its wait has begun, and then
    returns while the first chunk still runs (thousands of epochs, far
    longer than two fetches): it waited, and the device did not."""
    machines = _machines("overlap", 3000)
    SlowSecondChunkProvider.arm(gated={m.name for m in machines[CHUNK:]})
    arrived = BatchedModelBuilder._arrived

    def open_in_the_wait(self, plan):
        if plan.machine.name in SlowSecondChunkProvider.gated:
            SlowSecondChunkProvider.gate.set()
        return arrived(self, plan)

    monkeypatch.setattr(BatchedModelBuilder, "_arrived", open_in_the_wait)
    telemetry.start_trace()
    _build(machines, tmp_path)
    events = telemetry.stop_trace()["traceEvents"]
    [wait] = [e for e in events if e["name"] == "fetch_wait"]
    assert wait["args"]["machines"] == str(CHUNK)
    assert _counted()["starved"] == 0
