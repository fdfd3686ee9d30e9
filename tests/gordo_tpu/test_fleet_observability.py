"""The fleet observability plane (ISSUE 9): telemetry shards + merge
semantics (observability/shared.py), per-model SLO windows and burn rates
(observability/slo.py), and the device telemetry sampler
(observability/device.py)."""

import json
import os

import pytest

from gordo_tpu.observability import device, shared, slo, telemetry


@pytest.fixture(autouse=True)
def _clean_plane(tmp_path, monkeypatch):
    monkeypatch.setenv(shared.ENV_DIR, str(tmp_path))
    shared.reset_for_tests()
    slo.reset()
    device.reset_for_tests()
    yield
    shared.reset_for_tests()
    slo.reset()
    device.reset_for_tests()


def _registry_with_traffic() -> telemetry.MetricsRegistry:
    registry = telemetry.MetricsRegistry()
    registry.counter("gordo_server_t_requests_total", "test requests").inc(3)
    registry.gauge("gordo_server_t_queue_depth", "test depth").set(2.0)
    registry.histogram(
        "gordo_server_t_latency_seconds", "test latency"
    ).observe(0.05)
    return registry


# -------------------------------------------------------------- shard I/O
def test_shard_write_read_roundtrip(tmp_path):
    registry = _registry_with_traffic()
    assert shared.flush(force=True, registry=registry)
    shards = shared.read_shards()
    assert len(shards) == 1
    assert shards[0]["pid"] == os.getpid()
    by_name = {m["name"]: m for m in shards[0]["metrics"]}
    assert by_name["gordo_server_t_requests_total"]["series"] == [[[], 3.0]]


def test_flush_throttles_between_forced_writes():
    registry = _registry_with_traffic()
    assert shared.flush(force=True, registry=registry)
    # within the flush interval an unforced flush is a no-op
    assert not shared.flush(registry=registry)
    assert shared.flush(force=True, registry=registry)


def test_flush_noop_without_dir(monkeypatch):
    monkeypatch.delenv(shared.ENV_DIR)
    assert not shared.flush(force=True)
    assert shared.render_fleet_text() is None
    assert shared.fleet_vars() is None


def test_torn_shard_is_skipped(tmp_path):
    # odd seqlock version = writer died mid-slot; the reader must skip it
    payload = json.dumps({"schema": shared.PAYLOAD_SCHEMA, "pid": 1}).encode()
    torn = shared._HEADER.pack(shared._MAGIC, 1, len(payload)) + payload
    with open(shared.shard_path(1), "wb") as fh:
        fh.write(torn)
    assert shared.read_shards() == []


def test_garbage_shard_is_skipped(tmp_path):
    with open(shared.shard_path(2), "wb") as fh:
        fh.write(b"not a shard at all")
    assert shared.read_shards() == []


def test_mark_shard_dead_removes_file():
    registry = _registry_with_traffic()
    shared.flush(force=True, registry=registry)
    path = shared.shard_path(os.getpid())
    assert os.path.exists(path)
    shared.mark_shard_dead(os.getpid())
    assert not os.path.exists(path)
    assert shared.read_shards() == []


# ---------------------------------------------------------------- merging
def _fake_shard(pid: int, metrics) -> dict:
    return {"schema": shared.PAYLOAD_SCHEMA, "pid": pid, "metrics": metrics}


def test_merge_counters_sum_across_workers():
    entry = {
        "name": "gordo_server_t_requests_total",
        "kind": "counter",
        "help": "h",
        "labelnames": ["endpoint"],
        "series": [[["/predict"], 5.0]],
    }
    families = shared.merge_shards(
        [_fake_shard(1, [entry]), _fake_shard(2, [entry])]
    )
    family = families["gordo_server_t_requests_total"]
    assert family["series"][("/predict",)] == 10.0


def test_merge_gauges_sum_by_default_max_for_ratios():
    def gauge(name, value):
        return {
            "name": name, "kind": "gauge", "help": "h",
            "labelnames": [], "series": [[[], value]],
        }

    ratio_name = "gordo_server_device_busy_ratio"
    assert ratio_name in shared.GAUGE_MAX_MERGE
    shards = [
        _fake_shard(1, [gauge("gordo_server_t_depth", 2.0),
                        gauge(ratio_name, 0.9)]),
        _fake_shard(2, [gauge("gordo_server_t_depth", 3.0),
                        gauge(ratio_name, 0.4)]),
    ]
    families = shared.merge_shards(shards)
    # additive gauge: fleet total is the sum
    assert families["gordo_server_t_depth"]["series"][()] == 5.0
    # ratio gauge: summing workers' duty cycles into 1.3 would be a lie
    assert families[ratio_name]["series"][()] == 0.9
    # per-worker series keep each worker's own value
    assert families[ratio_name]["per_worker"][("1",)] == 0.9
    assert families[ratio_name]["per_worker"][("2",)] == 0.4


def test_merge_histograms_elementwise():
    entry = {
        "name": "gordo_server_t_latency_seconds",
        "kind": "histogram",
        "help": "h",
        "labelnames": [],
        "buckets": [0.1, 1.0, "inf"],
        "series": [[[], [[1, 2, 0], 0.5]]],
    }
    families = shared.merge_shards(
        [_fake_shard(1, [entry]), _fake_shard(2, [entry])]
    )
    counts, total = families["gordo_server_t_latency_seconds"]["series"][()]
    assert counts == [2, 4, 0]
    assert total == 1.0


# -------------------------------------------------------------- rendering
def test_render_fleet_text_exposition():
    # render flushes the DEFAULT registry into this process's shard, so
    # the probe series must live there (unique names: the registry is a
    # process-global get-or-create)
    telemetry.counter("gordo_server_t_render_total", "probe").inc(3)
    telemetry.histogram("gordo_server_t_render_seconds", "probe").observe(
        0.05
    )
    text = shared.render_fleet_text()
    assert "gordo_server_fleet_workers 1" in text
    assert "# TYPE gordo_server_fleet_workers gauge" in text
    assert "gordo_server_t_render_total 3" in text
    # histogram exposition: cumulative buckets + sum + count
    assert 'gordo_server_t_render_seconds_bucket{le="+Inf"} 1' in text
    assert "gordo_server_t_render_seconds_count 1" in text


def test_fleet_vars_census_and_merge():
    telemetry.counter("gordo_server_t_vars_total", "probe").inc(7)
    fleet = shared.fleet_vars()
    assert fleet["workers"] == 1
    assert fleet["pids"] == [os.getpid()]
    merged = fleet["merged"]["gordo_server_t_vars_total"]
    assert merged["series"][""] == 7.0


def test_fleet_extras_roundtrip():
    shared.register_extra("blob", lambda: {"answer": 42})
    shared.flush(force=True, registry=telemetry.MetricsRegistry())
    extras = shared.fleet_extras("blob")
    assert extras == [(os.getpid(), {"answer": 42})]


def test_sampler_runs_before_flush():
    seen = []
    shared.register_sampler(lambda: seen.append(1))
    shared.flush(force=True, registry=telemetry.MetricsRegistry())
    assert seen == [1]


# -------------------------------------------------------------------- SLO
def test_slo_snapshot_and_burn_rates(monkeypatch):
    monkeypatch.setenv("GORDO_TPU_SLO_P99_MS", "100")
    monkeypatch.setenv("GORDO_TPU_SLO_ERROR_BUDGET", "0.01")
    for _ in range(96):
        slo.record("model-a", 0.01, 200)
    for _ in range(2):
        slo.record("model-a", 0.5, 200)  # slow: > 100ms objective
    for _ in range(2):
        slo.record("model-a", 0.01, 500)  # errors
    snap = slo.snapshot()
    window = snap["models"]["model-a"]["5m"]
    assert window["requests"] == 100
    assert window["errors"] == 2
    assert window["slow"] == 2
    assert window["error_rate"] == pytest.approx(0.02)
    # 2% errors against a 1% budget: burning at 2x
    assert window["error_burn_rate"] == pytest.approx(2.0)
    assert window["latency_burn_rate"] == pytest.approx(2.0)
    assert window["p99_ms"] is not None
    # both windows exist and agree on totals at this timescale
    assert snap["models"]["model-a"]["1h"]["requests"] == 100


def test_slo_merge_payloads_is_exact(monkeypatch):
    monkeypatch.setenv("GORDO_TPU_SLO_P99_MS", "100")
    for _ in range(10):
        slo.record("model-a", 0.01, 200)
    slo.record("model-a", 0.2, 500)
    payload = slo.shard_payload()
    # two identical workers: epoch-aligned sub-windows merge by summing
    fleet = slo.merge_payloads([(1, payload), (2, payload)])
    window = fleet["models"]["model-a"]["5m"]
    assert fleet["workers"] == 2
    assert window["requests"] == 22
    assert window["errors"] == 2
    local = slo.snapshot()["models"]["model-a"]["5m"]
    assert window["error_rate"] == pytest.approx(local["error_rate"])


def test_slo_merge_tolerates_garbage_payloads():
    fleet = slo.merge_payloads(
        [(1, "not a dict"), (2, {"m": {"5m": [["bad row"]]}})]
    )
    assert fleet["models"].get("m", {}).get("5m", {}).get("requests", 0) == 0


def test_slo_refresh_gauges_exports_series():
    from gordo_tpu.observability import metrics as metric_catalog

    slo.record("model-b", 0.01, 200)
    slo.refresh_gauges()
    series = dict(metric_catalog.SLO_REQUESTS.snapshot())
    assert series[("model-b", "5m")] >= 1


def test_slo_empty_model_name_ignored():
    slo.record("", 0.01, 200)
    assert slo.snapshot()["models"] == {}


def test_slo_rides_the_shard(monkeypatch):
    slo.install_shard_hooks()
    slo.record("model-c", 0.02, 200)
    shared.flush(force=True, registry=telemetry.MetricsRegistry())
    extras = shared.fleet_extras("slo")
    assert len(extras) == 1
    _pid, payload = extras[0]
    assert "model-c" in payload
    fleet = slo.merge_payloads(extras)
    assert fleet["models"]["model-c"]["5m"]["requests"] == 1


# ----------------------------------------------------------------- device
def test_device_sample_and_snapshot():
    # no batcher, CPU backend: everything must still be best-effort green
    device.sample()
    snap = device.snapshot()
    assert set(snap) >= {
        "busy_ratio", "busy_seconds_total", "achieved_flops_total",
        "online_mfu", "peak_flops", "param_bank_bytes",
        "param_bank_occupancy", "program_cache_entries",
    }
    # the block says where the process ran, as JAX reports it
    import jax

    assert snap["platform"] == jax.devices()[0].platform == "cpu"
    assert snap["device_kind"] == jax.devices()[0].device_kind
    assert snap["device_count"] == len(jax.devices())
    # a CPU has no peak on record: MFU there is "not measured"
    assert snap["peak_flops"] is None


def test_device_busy_ratio_clamped(monkeypatch):
    from gordo_tpu.observability import metrics as metric_catalog

    device.reset_for_tests()
    device.sample()  # establishes the baseline sample
    # an absurd busy-seconds jump must clamp the duty cycle at 1.0
    metric_catalog.DEVICE_BUSY_SECONDS.inc(1e6)
    import time

    time.sleep(0.02)  # past the scrape-storm guard interval
    device.sample()
    assert metric_catalog.DEVICE_BUSY_RATIO.value() <= 1.0


def test_device_hooks_register_sampler():
    device.install_shard_hooks()
    shared.flush(force=True, registry=telemetry.MetricsRegistry())
    from gordo_tpu.observability import metrics as metric_catalog

    # the flush ran the sampler: program-cache gauge has a real value
    assert metric_catalog.PROGRAM_CACHE_ENTRIES.value() >= 0
