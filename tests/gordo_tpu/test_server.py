import numpy as np
import pandas as pd
import pytest

from gordo_tpu import serializer
from gordo_tpu.server import build_app
from gordo_tpu.server import utils as server_utils
from gordo_tpu.server.utils import (
    dataframe_from_dict,
    dataframe_from_parquet_bytes,
    dataframe_into_parquet_bytes,
    dataframe_to_dict,
)


@pytest.fixture(scope="module")
def app(model_collection_directory, trained_model_directories):
    server_utils.clear_model_caches()
    return build_app({"MODEL_COLLECTION_DIR": model_collection_directory})


@pytest.fixture(scope="module")
def client(app):
    return app.test_client()


def _assert_server_timing(resp, phased: bool):
    """Server-Timing contract: the reference-parity walltime entry first,
    plus the decode/predict/encode breakdown on prediction routes. Every
    entry is `name;dur=<float seconds>`."""
    header = resp.headers["Server-Timing"]
    entries = {}
    for raw in header.split(","):
        name, _, dur = raw.strip().partition(";dur=")
        entries[name] = float(dur)  # malformed dur would raise here
    assert "request_walltime_s" in entries
    if phased:
        for phase in ("decode_s", "predict_s", "encode_s"):
            assert phase in entries, header
            assert 0.0 <= entries[phase] <= entries["request_walltime_s"]
    return entries


def test_healthcheck(client):
    resp = client.get("/healthcheck")
    assert resp.status_code == 200
    # non-prediction routes keep the single reference-parity entry
    entries = _assert_server_timing(resp, phased=False)
    assert set(entries) == {"request_walltime_s"}


def test_server_version(client):
    resp = client.get("/server-version")
    assert resp.status_code == 200
    assert "version" in resp.get_json()


def test_model_list(client, gordo_project, gordo_name, second_gordo_name):
    resp = client.get(f"/gordo/v0/{gordo_project}/models")
    assert resp.status_code == 200
    models = resp.get_json()["models"]
    assert gordo_name in models and second_gordo_name in models


def test_revision_list(client, gordo_project, gordo_revision):
    resp = client.get(f"/gordo/v0/{gordo_project}/revisions")
    body = resp.get_json()
    assert body["latest"] == gordo_revision
    assert gordo_revision in body["available-revisions"]


def test_expected_models(client, gordo_project):
    resp = client.get(f"/gordo/v0/{gordo_project}/expected-models")
    assert resp.status_code == 200
    assert "expected-models" in resp.get_json()


def test_metadata(client, gordo_project, gordo_name):
    resp = client.get(f"/gordo/v0/{gordo_project}/{gordo_name}/metadata")
    assert resp.status_code == 200
    body = resp.get_json()
    assert body["metadata"]["name"] == gordo_name
    assert resp.headers["revision"]


def test_metadata_unknown_model_404(client, gordo_project):
    resp = client.get(f"/gordo/v0/{gordo_project}/no-such-model/metadata")
    assert resp.status_code == 404


def test_revision_missing_410(client, gordo_project, gordo_name):
    resp = client.get(
        f"/gordo/v0/{gordo_project}/{gordo_name}/metadata?revision=999"
    )
    assert resp.status_code == 410
    assert "not found" in resp.get_json()["error"]


def test_prediction_json(client, gordo_project, gordo_name, X_payload):
    payload = {"X": dataframe_to_dict(X_payload)}
    resp = client.post(
        f"/gordo/v0/{gordo_project}/{gordo_name}/prediction", json=payload
    )
    assert resp.status_code == 200
    body = resp.get_json()
    assert "data" in body
    assert "model-output" in body["data"]
    assert body["revision"]
    _assert_server_timing(resp, phased=True)


def test_prediction_missing_X_400(client, gordo_project, gordo_name):
    resp = client.post(
        f"/gordo/v0/{gordo_project}/{gordo_name}/prediction", json={"noX": 1}
    )
    assert resp.status_code == 400


def test_prediction_wrong_width_400(client, gordo_project, gordo_name):
    X = pd.DataFrame(np.random.rand(5, 2))
    resp = client.post(
        f"/gordo/v0/{gordo_project}/{gordo_name}/prediction",
        json={"X": dataframe_to_dict(X)},
    )
    assert resp.status_code == 400


def test_anomaly_json(client, gordo_project, gordo_name, X_payload):
    payload = {
        "X": dataframe_to_dict(X_payload),
        "y": dataframe_to_dict(X_payload),
    }
    resp = client.post(
        f"/gordo/v0/{gordo_project}/{gordo_name}/anomaly/prediction", json=payload
    )
    assert resp.status_code == 200
    data = resp.get_json()["data"]
    assert "total-anomaly-scaled" in data
    assert "tag-anomaly-scaled" in data
    # smoothed columns dropped by default
    assert not any(k.startswith("smooth-") for k in data)
    _assert_server_timing(resp, phased=True)


def test_anomaly_all_columns(
    client, gordo_project, second_gordo_name, X_payload
):
    payload = {
        "X": dataframe_to_dict(X_payload),
        "y": dataframe_to_dict(X_payload),
    }
    resp = client.post(
        f"/gordo/v0/{gordo_project}/{second_gordo_name}/anomaly/prediction"
        "?all_columns=true",
        json=payload,
    )
    assert resp.status_code == 200
    data = resp.get_json()["data"]
    assert any(k.startswith("smooth-") for k in data)


def test_anomaly_requires_y(client, gordo_project, gordo_name, X_payload):
    resp = client.post(
        f"/gordo/v0/{gordo_project}/{gordo_name}/anomaly/prediction",
        json={"X": dataframe_to_dict(X_payload)},
    )
    assert resp.status_code == 400
    assert "y" in resp.get_json()["message"]


def test_prediction_parquet_roundtrip(client, gordo_project, gordo_name, X_payload):
    resp = client.post(
        f"/gordo/v0/{gordo_project}/{gordo_name}/prediction?format=parquet",
        data={"X": (io_bytes(X_payload), "X")},
    )
    assert resp.status_code == 200
    df = dataframe_from_parquet_bytes(resp.data)
    assert "model-output" in df.columns.get_level_values(0)


def io_bytes(df):
    import io

    return io.BytesIO(dataframe_into_parquet_bytes(df))


def test_download_model(client, gordo_project, gordo_name, X_payload):
    resp = client.get(f"/gordo/v0/{gordo_project}/{gordo_name}/download-model")
    assert resp.status_code == 200
    model = serializer.loads(resp.data)
    assert hasattr(model, "anomaly")
    out = model.predict(X_payload)
    assert out.shape == (20, 4)


def test_dataframe_dict_roundtrip(X_payload):
    as_dict = dataframe_to_dict(X_payload)
    df = dataframe_from_dict(as_dict)
    assert np.allclose(df.values, X_payload.values)


def test_prometheus_metrics(model_collection_directory):
    app = build_app(
        {
            "MODEL_COLLECTION_DIR": model_collection_directory,
            "ENABLE_PROMETHEUS": True,
            "PROJECT": "test-proj",
        }
    )
    client = app.test_client()
    client.get("/healthcheck")
    body = app._prometheus.expose().decode()
    assert "gordo_server_requests_total" in body
    assert 'project="test-proj"' in body
    # metrics are reachable over HTTP, not just collected
    resp = client.get("/metrics")
    assert resp.status_code == 200
    assert "gordo_server_requests_total" in resp.get_data(as_text=True)


def test_prometheus_custom_registry(model_collection_directory):
    from prometheus_client import CollectorRegistry, generate_latest

    registry = CollectorRegistry()
    app = build_app(
        {
            "MODEL_COLLECTION_DIR": model_collection_directory,
            "ENABLE_PROMETHEUS": True,
            "PROJECT": "test-proj",
        },
        prometheus_registry=registry,
    )
    app.test_client().get("/healthcheck")
    # collectors registered in the caller-supplied registry
    assert b"gordo_server_requests_total" in generate_latest(registry)


def test_prometheus_sidecar_app(tmp_path, monkeypatch, model_collection_directory):
    """Standalone /metrics sidecar aggregates the multiprocess dir
    (reference prometheus/server.py + gunicorn_config.py)."""
    from werkzeug.test import Client

    from gordo_tpu.server.prometheus.server import (
        build_metrics_app,
        mark_worker_dead,
    )

    from prometheus_client import values

    monkeypatch.setenv("PROMETHEUS_MULTIPROC_DIR", str(tmp_path))
    try:
        # a "worker" records a request into the multiproc dir
        app = build_app(
            {
                "MODEL_COLLECTION_DIR": model_collection_directory,
                "ENABLE_PROMETHEUS": True,
                "PROJECT": "side-proj",
            }
        )
        app.test_client().get("/healthcheck")

        sidecar = Client(build_metrics_app())
        assert sidecar.get("/healthcheck").status_code == 200
        body = sidecar.get("/metrics").get_data(as_text=True)
        assert "gordo_server_requests_total" in body
        assert 'project="side-proj"' in body
        assert sidecar.get("/nope").status_code == 404

        # reaping a fake dead pid must not raise, /metrics keeps serving
        mark_worker_dead(999999)
        assert sidecar.get("/metrics").status_code == 200
    finally:
        # restore the in-memory value backend so later tests don't mmap
        # into this test's (soon-deleted) tmp dir
        monkeypatch.delenv("PROMETHEUS_MULTIPROC_DIR")
        values.ValueClass = values.get_value_class()


def test_metrics_404_when_disabled(client):
    assert client.get("/metrics").status_code == 404


def test_revision_traversal_rejected(client, gordo_project):
    # path separators / dot-runs in ?revision= must not escape the tree
    for bad in ("../../../../etc", "..", "a/b", "foo%2F..%2Fbar"):
        resp = client.get(f"/gordo/v0/{gordo_project}/models?revision={bad}")
        assert resp.status_code == 410, bad


def test_openapi_spec_matches_url_map(client):
    resp = client.get("/gordo/v0/openapi.json")
    assert resp.status_code == 200
    spec = resp.get_json()
    assert spec["openapi"].startswith("3.")

    # every URL rule must be documented, and vice versa
    from gordo_tpu.server.server import GordoServer

    def to_openapi(rule_str):
        return rule_str.replace("<", "{").replace(">", "}")

    rule_paths = {
        to_openapi(r.rule) for r in GordoServer.url_map.iter_rules()
    }
    spec_paths = set(spec["paths"])
    assert rule_paths <= spec_paths, rule_paths - spec_paths


def test_prometheus_batcher_metrics(
    model_collection_directory, trained_model_directories, monkeypatch
):
    """The batcher's counters and self-A/B decisions surface as gauges."""
    import json
    import threading

    from gordo_tpu.server import batcher as batcher_mod

    monkeypatch.setenv("GORDO_TPU_SERVING_BATCH", "1")
    monkeypatch.setattr(batcher_mod, "_batcher", None)
    app = build_app(
        {
            "MODEL_COLLECTION_DIR": model_collection_directory,
            "ENABLE_PROMETHEUS": True,
            "PROJECT": "test-proj",
        }
    )
    client = app.test_client()
    machine = "machine-1"
    n_tags = 4
    X = np.random.RandomState(0).rand(20, n_tags).tolist()
    body = json.dumps({"X": X, "y": X}).encode()
    path = f"/gordo/v0/test-proj/{machine}/prediction"

    def post():
        resp = client.post(path, data=body, content_type="application/json")
        assert resp.status_code == 200

    post()  # warm (model load + compile)
    threads = [threading.Thread(target=post) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    text = app._prometheus.expose().decode()
    assert "gordo_server_batcher_items" in text
    assert "gordo_server_batcher_device_calls" in text
    stats = batcher_mod._batcher.stats
    assert stats["items"] >= 5
    assert f'gordo_server_batcher_items{{project="test-proj"}} {float(stats["items"])}' in text


def test_warmup_collection(
    model_collection_directory, trained_model_directories, monkeypatch
):
    """Warmup compiles one predict program per (model, row bucket) and
    reports what it did."""
    from gordo_tpu.server import warmup

    # an ambient GORDO_TPU_WARMUP_ROWS would change the default bucket set
    monkeypatch.delenv("GORDO_TPU_WARMUP_ROWS", raising=False)
    result = warmup.warmup_collection(model_collection_directory)
    assert result["failed"] == []
    assert result["models"] == len(trained_model_directories)
    assert result["programs"] == result["models"] * len(warmup.DEFAULT_BUCKET_ROWS)


def test_warmup_windowed_model_uses_offset(tmp_path):
    """A windowed artifact warms at bucket+offset rows so the compiled
    program bucket matches real requests of that size."""
    import numpy as np

    from gordo_tpu import serializer
    from gordo_tpu.machine import Machine
    from gordo_tpu.builder.build_model import ModelBuilder
    from gordo_tpu.server import warmup

    machine = Machine.from_config(
        {
            "name": "warm-lstm",
            "dataset": {
                "type": "RandomDataset",
                "tags": ["w-0", "w-1"],
                "train_start_date": "2019-01-01T00:00:00+00:00",
                "train_end_date": "2019-01-02T00:00:00+00:00",
            },
            "model": {
                "gordo_tpu.models.models.LSTMAutoEncoder": {
                    "kind": "lstm_symmetric",
                    "lookback_window": 4,
                    "epochs": 1,
                }
            },
        },
        project_name="warm",
    )
    model, machine_out = ModelBuilder(machine).build()
    mdir = tmp_path / "warm-lstm"
    mdir.mkdir()
    serializer.dump(model, str(mdir), metadata=machine_out.to_dict())

    result = warmup.warmup_collection(str(tmp_path), bucket_rows=(8,))
    assert result == {
        "models": 1, "programs": 1, "aot_programs": 0,
        "aot_shipped": 0, "aot_rejected": 0, "compile_seconds_saved": 0.0,
        "registered_params": 0,
        "seconds": result["seconds"], "failed": [],
    }
    # the warmed bucket serves a real 8-output-row request without error
    offset = machine_out.metadata.build_metadata.model.model_offset
    assert offset == 3  # lookback 4, lookahead 0
    X = np.random.RandomState(0).rand(8 + offset, 2)
    assert len(model.predict(X)) == 8


def test_warmup_survives_broken_model(tmp_path):
    """A corrupt artifact is reported, not raised — warmup must never stop
    the server from starting."""
    from gordo_tpu.server import warmup

    bad = tmp_path / "broken"
    bad.mkdir()
    (bad / "metadata.json").write_text("{}")
    result = warmup.warmup_collection(str(tmp_path))
    assert result["models"] == 0
    assert result["failed"] == ["broken"]


def test_warmup_triggers_batcher_calibration(
    model_collection_directory, trained_model_directories, monkeypatch
):
    """In a worker with the batcher in auto mode (the run-server default),
    warmup's predicts route through the batcher like real traffic: the
    per-architecture self-A/B runs DURING warmup, so both the fused
    programs and the on/off decision are in place before the first
    request."""
    from gordo_tpu.server import batcher as batcher_mod
    from gordo_tpu.server import warmup

    monkeypatch.setenv("GORDO_TPU_SERVING_BATCH", "auto")
    monkeypatch.setenv("GORDO_TPU_BATCH_AB_USERS", "2")
    monkeypatch.setenv("GORDO_TPU_BATCH_AB_ROUNDS", "1")
    monkeypatch.setattr(batcher_mod, "_batcher", None)

    result = warmup.warmup_collection(model_collection_directory)
    assert result["failed"] == []
    b = batcher_mod.peek_batcher()
    assert b is not None
    on, off = b.decision_counts()
    assert on + off >= 1  # calibration ran and recorded a decision


@pytest.mark.parametrize("batching_wins", [True, False])
def test_warmup_compiles_both_paths_whatever_the_self_ab_decided(
    model_collection_directory, trained_model_directories, monkeypatch,
    batching_wins,
):
    """The program set a worker compiles must not depend on which way the
    measured self-A/B fell: with the batcher on, warmup runs the per-request
    program of every (architecture, row bucket) too."""
    from gordo_tpu.ops import train as train_ops
    from gordo_tpu.server import batcher as batcher_mod
    from gordo_tpu.server import warmup

    monkeypatch.setenv("GORDO_TPU_SERVING_BATCH", "auto")
    monkeypatch.setattr(batcher_mod, "_batcher", None)
    monkeypatch.setattr(
        batcher_mod.CrossModelBatcher, "_calibrate",
        lambda self, spec, params, X: self._spec_on.setdefault(
            spec, batching_wins
        ),
    )
    direct_rows = []
    real_predict_fn = train_ops.predict_fn

    def recording_predict_fn(spec):
        fn = real_predict_fn(spec)
        return lambda params, X: direct_rows.append(len(X)) or fn(params, X)

    monkeypatch.setattr(train_ops, "predict_fn", recording_predict_fn)
    result = warmup.warmup_collection(model_collection_directory)
    assert result["failed"] == []
    assert set(direct_rows) == set(warmup.DEFAULT_BUCKET_ROWS)


def test_warmup_rows_env_parsing(monkeypatch):
    """A malformed GORDO_TPU_WARMUP_ROWS falls back to the defaults with a
    warning instead of aborting warmup (best-effort contract)."""
    from gordo_tpu.server import warmup

    monkeypatch.setenv("GORDO_TPU_WARMUP_ROWS", "256")
    assert warmup._default_bucket_rows() == (256,)
    monkeypatch.setenv("GORDO_TPU_WARMUP_ROWS", "64,512")
    assert warmup._default_bucket_rows() == (64, 512)
    for bad in ("128;1024", "128, abc", " , ", "0", "-5"):
        monkeypatch.setenv("GORDO_TPU_WARMUP_ROWS", bad)
        assert warmup._default_bucket_rows() == warmup.DEFAULT_BUCKET_ROWS


def test_prometheus_labels_bounded_for_scanner_paths(model_collection_directory):
    """Metrics label by the MATCHED route, never the raw path: a scanner
    probing random URLs must not mint unbounded timeseries."""
    from prometheus_client import CollectorRegistry, generate_latest

    registry = CollectorRegistry()
    app = build_app(
        {
            "MODEL_COLLECTION_DIR": model_collection_directory,
            "ENABLE_PROMETHEUS": True,
            "PROJECT": "test-proj",
        },
        prometheus_registry=registry,
    )
    c = app.test_client()
    for i in range(20):
        c.get(f"/wp-admin/{i}/.env")
    c.get("/healthcheck")
    for i in range(10):
        c.get(f"/gordo/v0/proj/scan-{i}/whatever")      # matches no rule
        c.get(f"/gordo/v0/proj/scan-{i}/metadata")      # matches, 404s
        c.get(f"/gordo/v0/proj/scan-{i}/prediction")    # matches, 405s
    body = generate_latest(registry).decode()
    assert "wp-admin" not in body
    assert "scan-" not in body  # gordo_name only for RESOLVED machines
    # ...but the 405 keeps endpoint attribution (matched rule, no name)
    assert 'path="/gordo/v0/<gordo_project>/<gordo_name>/prediction"' in body
    assert 'path="(unmatched)"' in body
    assert 'path="/healthcheck"' in body


def test_readiness_gates_on_expected_models(
    model_collection_directory, trained_model_directories, gordo_name,
    second_gordo_name
):
    """/readiness is the zero-downtime rollover gate: 503 while any
    EXPECTED_MODELS artifact is missing, 200 once the build completes."""
    app = build_app(
        {
            "MODEL_COLLECTION_DIR": model_collection_directory,
            "EXPECTED_MODELS": [gordo_name, second_gordo_name, "not-built"],
        }
    )
    c = app.test_client()
    resp = c.get("/readiness")
    assert resp.status_code == 503
    body = resp.get_json()
    assert body["ready"] is False and body["missing"] == ["not-built"]

    app = build_app(
        {
            "MODEL_COLLECTION_DIR": model_collection_directory,
            "EXPECTED_MODELS": [gordo_name, second_gordo_name],
        }
    )
    resp = app.test_client().get("/readiness")
    assert resp.status_code == 200
    assert resp.get_json()["ready"] is True

    # no expectation set: ready (a manually-run server must come up)
    app = build_app({"MODEL_COLLECTION_DIR": model_collection_directory})
    assert app.test_client().get("/readiness").status_code == 200


def test_readiness_file_based_expectation(
    model_collection_directory, trained_model_directories, gordo_name,
    second_gordo_name, tmp_path, monkeypatch
):
    """EXPECTED_MODELS_FILE (large fleets: the list lives on the shared
    volume, not in a Deployment env) is read PER REQUEST — it may be
    written after pod start — and a declared-but-unreadable expectation
    means NOT ready."""
    import json as _json

    path = tmp_path / "expected-models.json"
    monkeypatch.setenv("EXPECTED_MODELS_FILE", str(path))
    app = build_app({"MODEL_COLLECTION_DIR": model_collection_directory})
    c = app.test_client()
    assert c.get("/readiness").status_code == 503  # declared, not yet staged

    path.write_text(_json.dumps([gordo_name, "not-built"]))
    assert c.get("/readiness").status_code == 503  # staged, build incomplete

    path.write_text(_json.dumps([gordo_name, second_gordo_name]))
    assert c.get("/readiness").status_code == 200  # same process, no restart


def test_expected_models_endpoint_shares_file_resolution(
    model_collection_directory, trained_model_directories, gordo_project,
    tmp_path, monkeypatch
):
    """/expected-models and /readiness resolve the fleet the SAME way —
    the staged-file mechanism must show up in both."""
    import json as _json

    path = tmp_path / "expected-models.json"
    path.write_text(_json.dumps(["m-a", "m-b"]))
    monkeypatch.setenv("EXPECTED_MODELS_FILE", str(path))
    app = build_app({"MODEL_COLLECTION_DIR": model_collection_directory})
    resp = app.test_client().get(f"/gordo/v0/{gordo_project}/expected-models")
    assert resp.get_json()["expected-models"] == ["m-a", "m-b"]


# ------------------------------------------------------- proxy adaptation
# Reference parity: gordo/server/server.py:46-119 (adapt_proxy_deployment) —
# the server must work behind a prefixed ingress (Envoy/Ambassador, Istio
# VirtualService prefix routing, the topology the workflow template deploys).


def test_proxy_envoy_stripped_prefix(client):
    """Ingress stripped the prefix: PATH_INFO is local, the original full
    path rides X-Envoy-Original-Path. Routing must still hit the route."""
    resp = client.get(
        "/healthcheck",
        headers={"X-Envoy-Original-Path": "/gordo/v0/proj/tgt/healthcheck"},
    )
    assert resp.status_code == 200


def test_proxy_envoy_full_path_forwarded(client, gordo_project, gordo_name):
    """Proxy forwarded the FULL external path as PATH_INFO: the adapter must
    localize it (strip the prefix it derives from the Envoy header) or the
    absolute route table 404s."""
    local = f"/gordo/v0/{gordo_project}/{gordo_name}/metadata"
    resp = client.get(
        f"/prefixed/ingress{local}",
        headers={"X-Envoy-Original-Path": "/prefixed/ingress"},
    )
    assert resp.status_code == 200
    assert resp.get_json()["metadata"]["name"] == gordo_name


def test_proxy_forwarded_prefix(client, gordo_project):
    """Generic ingress convention: X-Forwarded-Prefix names the stripped
    prefix; a full-path PATH_INFO must be localized against it."""
    resp = client.get(
        f"/svc/gordo/v0/{gordo_project}/models",
        headers={"X-Forwarded-Prefix": "/svc"},
    )
    assert resp.status_code == 200
    assert "models" in resp.get_json()


def test_proxy_no_headers_prefixed_path_404s(client):
    """Without proxy headers a prefixed path must NOT silently match."""
    assert client.get("/some/prefix/healthcheck").status_code == 404


def test_proxy_sets_script_name_and_scheme():
    """The middleware rewrites SCRIPT_NAME/PATH_INFO/url_scheme exactly."""
    from gordo_tpu.server.server import adapt_proxy_deployment

    seen = {}

    def inner(environ, start_response):
        seen.update(environ)
        start_response("200 OK", [("Content-Type", "text/plain")])
        return [b"ok"]

    wrapped = adapt_proxy_deployment(inner)
    environ = {
        "PATH_INFO": "/svc/metadata",
        "HTTP_X_FORWARDED_PREFIX": "/svc/",
        "HTTP_X_FORWARDED_PROTO": "https",
        "wsgi.url_scheme": "http",
    }
    assert wrapped(environ, lambda *a: None) == [b"ok"]
    assert seen["SCRIPT_NAME"] == "/svc"
    assert seen["PATH_INFO"] == "/metadata"
    assert seen["wsgi.url_scheme"] == "https"


def test_proxy_envoy_prefix_suffix_strip_not_substring():
    """The prefix is ORIGINAL_PATH minus the PATH_INFO *suffix* — a local
    path that also appears mid-prefix must not be clipped out of the middle
    (the reference's str.replace would)."""
    from gordo_tpu.server.server import adapt_proxy_deployment

    seen = {}

    def inner(environ, start_response):
        seen.update(environ)
        return []

    environ = {
        "PATH_INFO": "/metrics",
        "HTTP_X_ENVOY_ORIGINAL_PATH": "/metrics/service/metrics",
    }
    adapt_proxy_deployment(inner)(environ, lambda *a: None)
    assert seen["SCRIPT_NAME"] == "/metrics/service"
    assert seen["PATH_INFO"] == "/metrics"


def test_proxy_envoy_trailing_slash_same_prefix():
    """A trailing-slash request must derive the SAME prefix as its
    slashless sibling: for PATH_INFO '/metadata/' with original
    '/svc/metadata/', SCRIPT_NAME is '/svc' — not the whole original
    path (which would corrupt every generated URL)."""
    from gordo_tpu.server.server import adapt_proxy_deployment

    seen = {}

    def inner(environ, start_response):
        seen.update(environ)
        return []

    wrapped = adapt_proxy_deployment(inner)
    environ = {
        "PATH_INFO": "/metadata/",
        "HTTP_X_ENVOY_ORIGINAL_PATH": "/svc/metadata/",
    }
    wrapped(environ, lambda *a: None)
    assert seen["SCRIPT_NAME"] == "/svc"
    assert seen["PATH_INFO"] == "/metadata/"  # routing path untouched

    # and the slashless sibling agrees
    seen.clear()
    environ = {
        "PATH_INFO": "/metadata",
        "HTTP_X_ENVOY_ORIGINAL_PATH": "/svc/metadata",
    }
    wrapped(environ, lambda *a: None)
    assert seen["SCRIPT_NAME"] == "/svc"


def test_proxy_envoy_trailing_slash_routes(client):
    """End-to-end: a trailing-slash healthcheck behind a stripped prefix
    still routes (strict_slashes off) with the right prefix derivation."""
    resp = client.get(
        "/healthcheck/",
        headers={"X-Envoy-Original-Path": "/svc/healthcheck/"},
    )
    assert resp.status_code == 200


def test_proxy_envoy_header_query_string_ignored():
    """Envoy's header carries the original :path INCLUDING the query
    string; only the path part may join prefix derivation."""
    from gordo_tpu.server.server import adapt_proxy_deployment

    seen = {}

    def inner(environ, start_response):
        seen.update(environ)
        return []

    environ = {
        "PATH_INFO": "/prediction",
        "QUERY_STRING": "format=csv",
        "HTTP_X_ENVOY_ORIGINAL_PATH": "/svc/prediction?format=csv",
    }
    adapt_proxy_deployment(inner)(environ, lambda *a: None)
    assert seen["SCRIPT_NAME"] == "/svc"
    assert seen["PATH_INFO"] == "/prediction"


def test_proxy_prefix_boundary_not_false_match():
    """'/svc' must not localize '/svc2/metadata' (segment boundary), and a
    stripped path keeps its leading slash (PEP 3333)."""
    from gordo_tpu.server.server import adapt_proxy_deployment

    seen = {}

    def inner(environ, start_response):
        seen.update(environ)
        return []

    wrapped = adapt_proxy_deployment(inner)
    environ = {
        "PATH_INFO": "/svc2/metadata",
        "HTTP_X_FORWARDED_PREFIX": "/svc",
    }
    wrapped(environ, lambda *a: None)
    assert seen["PATH_INFO"] == "/svc2/metadata"  # unchanged

    seen.clear()
    environ = {
        "PATH_INFO": "/svc/metadata",
        "HTTP_X_ENVOY_ORIGINAL_PATH": "/svc/",
    }
    wrapped(environ, lambda *a: None)
    assert seen["PATH_INFO"] == "/metadata"
    assert seen["SCRIPT_NAME"] == "/svc"


# ----------------------------------------- tracing headers on error classes
# Server-Timing and X-Gordo-Trace must ride EVERY response — the failures
# (4xx/5xx, shed 503, deadline 504, breaker fast-fail) are exactly the
# responses worth attributing to a trace (ISSUE 5 satellite).
def _assert_trace_headers(resp):
    entries = _assert_server_timing(resp, phased=False)
    assert "request_walltime_s" in entries
    trace_id = resp.headers.get("X-Gordo-Trace")
    assert trace_id and len(trace_id) == 32, resp.headers
    return trace_id


def test_error_headers_400_missing_X(client, gordo_project, gordo_name):
    resp = client.post(
        f"/gordo/v0/{gordo_project}/{gordo_name}/prediction", json={"noX": 1}
    )
    assert resp.status_code == 400
    _assert_trace_headers(resp)


def test_error_headers_404_unknown_model(client, gordo_project):
    resp = client.post(
        f"/gordo/v0/{gordo_project}/no-such-model/prediction", json={}
    )
    assert resp.status_code == 404
    _assert_trace_headers(resp)


def test_error_headers_405_wrong_method(client, gordo_project, gordo_name):
    resp = client.get(
        f"/gordo/v0/{gordo_project}/{gordo_name}/prediction"
    )
    assert resp.status_code == 405
    _assert_trace_headers(resp)


def test_error_headers_410_missing_revision(client, gordo_project, gordo_name):
    resp = client.get(
        f"/gordo/v0/{gordo_project}/{gordo_name}/metadata?revision=999"
    )
    assert resp.status_code == 410
    _assert_trace_headers(resp)


def test_error_headers_shed_503(client, gordo_project, gordo_name, monkeypatch):
    from gordo_tpu.server import resilience

    monkeypatch.setenv("GORDO_TPU_MAX_INFLIGHT", "1")
    # occupy the only slot so the next prediction POST is shed
    assert resilience.try_admit() is None
    try:
        resp = client.post(
            f"/gordo/v0/{gordo_project}/{gordo_name}/prediction", json={}
        )
        assert resp.status_code == 503
        assert resp.headers.get("Retry-After")
        _assert_trace_headers(resp)
    finally:
        resilience.release()


def test_error_headers_breaker_503(
    client, gordo_project, gordo_name, monkeypatch
):
    from gordo_tpu.server import resilience
    from gordo_tpu.util import faults

    monkeypatch.setenv("GORDO_TPU_BREAKER_THRESHOLD", "1")
    monkeypatch.setenv("GORDO_TPU_BREAKER_COOLDOWN_S", "60")
    try:
        breaker = resilience.breaker_for(gordo_name)
        breaker.record_failure(faults.PermanentFault("poisoned artifact"))
        resp = client.post(
            f"/gordo/v0/{gordo_project}/{gordo_name}/prediction", json={}
        )
        assert resp.status_code == 503
        assert gordo_name in resp.get_json()["error"]
        assert resp.headers.get("Retry-After")
        _assert_trace_headers(resp)
    finally:
        resilience.reset_breakers()


def test_error_headers_deadline_504(
    client, gordo_project, gordo_name, X_payload, monkeypatch
):
    import json as _json

    from gordo_tpu.util import faults

    monkeypatch.setenv(
        faults.PLAN_ENV,
        _json.dumps(
            {
                "rules": [
                    {
                        "site": "serve_predict",
                        "times": 1,
                        "error": "wedge",
                        "seconds": 0.4,
                    }
                ]
            }
        ),
    )
    faults.reset_plan()
    try:
        resp = client.post(
            f"/gordo/v0/{gordo_project}/{gordo_name}/prediction",
            json={"X": dataframe_to_dict(X_payload)},
            headers={"X-Gordo-Deadline-Ms": "100"},
        )
        assert resp.status_code == 504, resp.get_data(as_text=True)
        _assert_trace_headers(resp)
    finally:
        monkeypatch.delenv(faults.PLAN_ENV, raising=False)
        faults.reset_plan()


def test_traceparent_continued_and_echoed(client):
    trace_id = "ab" * 16
    resp = client.get(
        "/healthcheck",
        headers={"traceparent": f"00-{trace_id}-{'cd' * 8}-01"},
    )
    assert resp.headers["X-Gordo-Trace"] == trace_id
    # malformed traceparent: fresh trace, request unaffected
    resp = client.get("/healthcheck", headers={"traceparent": "garbage"})
    assert resp.status_code == 200
    assert len(resp.headers["X-Gordo-Trace"]) == 32


def test_debug_endpoints_404_without_knob(client):
    for path in ("/debug/flight", "/debug/vars", "/debug/config"):
        assert client.get(path).status_code == 404, path
