"""
The telemetry runtime (observability/telemetry.py): span semantics, the
dependency-light metrics registry, both exporters, and the end-to-end
``batch-build --trace-file/--metrics-file`` contract under fault injection.
"""

import json
import sys
import threading

import pytest

from gordo_tpu.observability import metrics as metric_catalog
from gordo_tpu.observability import telemetry
from gordo_tpu.util import faults, profiling


@pytest.fixture(autouse=True)
def _fresh_telemetry(monkeypatch):
    """Every test starts with spans disabled, no trace, zeroed values, and
    no leaked fault plan or profile dir."""
    monkeypatch.delenv("GORDO_TPU_PROFILE_DIR", raising=False)
    monkeypatch.delenv(faults.PLAN_ENV, raising=False)
    faults.reset_plan()
    telemetry.reset()
    yield
    faults.reset_plan()
    telemetry.reset()


# ---------------------------------------------------------------- registry
def test_counter_gauge_histogram_roundtrip():
    reg = telemetry.MetricsRegistry()
    c = reg.counter("gordo_t_events_total", "events", ("kind",))
    c.labels(kind="a").inc()
    c.labels(kind="a").inc(2.5)
    c.labels(kind="b").inc()
    assert c.value(kind="a") == 3.5
    g = reg.gauge("gordo_t_level", "level")
    g.set(7)
    assert g.value() == 7.0
    h = reg.histogram(
        "gordo_t_dur_seconds", "durations", buckets=(0.1, 1.0)
    )
    h.observe(0.05)
    h.observe(0.5)
    h.observe(100.0)  # lands in the implicit +Inf bucket
    assert h.count() == 3


def test_registry_get_or_create_and_kind_mismatch():
    reg = telemetry.MetricsRegistry()
    c1 = reg.counter("gordo_t_x_total", "x", ("a",))
    c2 = reg.counter("gordo_t_x_total", "x again", ("a",))
    assert c1 is c2  # module re-imports converge on one series
    with pytest.raises(ValueError):
        reg.gauge("gordo_t_x_total", "not a counter", ("a",))
    with pytest.raises(ValueError):
        reg.counter("gordo_t_x_total", "other labels", ("b",))
    with pytest.raises(ValueError):
        reg.counter("gordo_t_y_total", "")  # empty help rejected at runtime
    with pytest.raises(ValueError):
        reg.counter("bad name!", "help")


def test_textfile_exposition_format(tmp_path):
    """The pure-python renderer must be valid Prometheus text format 0.0.4
    — this is the no-prometheus_client code path (it imports nothing)."""
    reg = telemetry.MetricsRegistry()
    c = reg.counter("gordo_t_total", "with \"quotes\" and\nnewline", ("m",))
    c.labels(m='va"l').inc(2)
    h = reg.histogram("gordo_t_s", "hist help", buckets=(0.5, 1.0))
    h.observe(0.25)
    h.observe(0.75)
    text = reg.render_text()
    assert '# HELP gordo_t_total with "quotes" and\\nnewline' in text
    assert "# TYPE gordo_t_total counter" in text
    assert 'gordo_t_total{m="va\\"l"} 2.0' in text
    # histogram: cumulative buckets, +Inf, _sum and _count
    assert 'gordo_t_s_bucket{le="0.5"} 1' in text
    assert 'gordo_t_s_bucket{le="1.0"} 2' in text
    assert 'gordo_t_s_bucket{le="+Inf"} 2' in text
    assert "gordo_t_s_count 2" in text
    assert "gordo_t_s_sum 1.0" in text
    out = tmp_path / "metrics" / "out.prom"
    reg.write_textfile(str(out))
    assert out.read_text() == text


def test_prometheus_bridge_exposes_registry_values():
    prometheus_client = pytest.importorskip("prometheus_client")
    reg = telemetry.MetricsRegistry()
    reg.counter("gordo_t_br_total", "bridged", ("k",)).labels(k="x").inc(4)
    h = reg.histogram("gordo_t_br_s", "bridged hist", buckets=(1.0,))
    h.observe(0.5)
    prom = prometheus_client.CollectorRegistry()
    assert telemetry.prometheus_bridge(prom, reg) is not None
    out = prometheus_client.generate_latest(prom).decode()
    assert 'gordo_t_br_total{k="x"} 4.0' in out
    assert 'gordo_t_br_s_bucket{le="1.0"} 1.0' in out
    assert "gordo_t_br_s_sum 0.5" in out


def test_prometheus_bridge_multiprocess_mode(tmp_path, monkeypatch):
    """The bridge coexists with the MultiProcessCollector: in multiprocess
    serving mode /metrics still carries the worker's telemetry series."""
    prometheus_client = pytest.importorskip("prometheus_client")
    monkeypatch.setenv("PROMETHEUS_MULTIPROC_DIR", str(tmp_path))
    from gordo_tpu.server.prometheus.metrics import create_registry

    prom = create_registry()
    reg = telemetry.MetricsRegistry()
    reg.counter("gordo_t_mp_total", "multiproc bridged").inc()
    telemetry.prometheus_bridge(prom, reg)
    out = prometheus_client.generate_latest(prom).decode()
    assert "gordo_t_mp_total 1.0" in out


def test_prometheus_bridge_without_prometheus_client(monkeypatch):
    """Absent prometheus_client the bridge declines (None) and the textfile
    path still works — batch jobs export without the dependency."""
    monkeypatch.setitem(sys.modules, "prometheus_client", None)
    monkeypatch.setitem(sys.modules, "prometheus_client.core", None)
    reg = telemetry.MetricsRegistry()
    reg.counter("gordo_t_nopc_total", "no client").inc()

    class _Sink:
        def register(self, collector):  # pragma: no cover - must not run
            raise AssertionError("bridge must not register without client")

    assert telemetry.prometheus_bridge(_Sink(), reg) is None
    assert "gordo_t_nopc_total 1.0" in reg.render_text()


# ------------------------------------------------------------------- spans
def test_disabled_span_is_shared_noop_singleton():
    """The acceptance guard: with no trace and no span timing, span()
    allocates nothing — every call returns the same no-op object and
    records no events or metrics."""
    s1 = telemetry.span("compile", machine="m-1")
    s2 = telemetry.span("train")
    assert s1 is s2
    with s1:
        pass
    assert telemetry.chrome_trace() is None
    assert not telemetry.spans_enabled()
    # and nothing observed into the phase histogram
    assert metric_catalog.BUILD_PHASE_SECONDS.count(phase="compile") == 0
    # the fleet build's stage spans are the same object, and so are their
    # phase labels' call sites
    from gordo_tpu.parallel import batch_trainer

    for name in batch_trainer._STAGE_SECONDS:
        assert batch_trainer._stage(name, machines=3) is s1
        assert metric_catalog.BUILD_PHASE_SECONDS.count(phase=name) == 0


def test_span_records_chrome_trace_event_and_histogram():
    telemetry.start_trace()
    hist = metric_catalog.BUILD_PHASE_SECONDS.labels(phase="compile")
    with telemetry.span("compile", hist, bucket="b0", machines=3):
        pass
    trace = telemetry.chrome_trace()
    [event] = trace["traceEvents"]
    assert event["name"] == "compile"
    assert event["ph"] == "X"
    assert event["ts"] >= 0 and event["dur"] >= 0
    assert event["args"] == {"bucket": "b0", "machines": "3"}
    assert isinstance(event["pid"], int) and isinstance(event["tid"], int)
    json.dumps(trace)  # schema must be JSON-serializable as-is
    assert metric_catalog.BUILD_PHASE_SECONDS.count(phase="compile") == 1


def test_enable_spans_times_without_recording_events():
    """--metrics-file without --trace-file: histograms fill, no event
    buffer grows."""
    telemetry.enable_spans()
    hist = metric_catalog.BUILD_PHASE_SECONDS.labels(phase="fetch")
    with telemetry.span("fetch", hist, machine="m"):
        pass
    assert telemetry.chrome_trace() is None
    assert metric_catalog.BUILD_PHASE_SECONDS.count(phase="fetch") == 1


def test_spans_thread_safe_under_concurrency():
    telemetry.start_trace()
    n_threads, n_spans = 8, 50
    counter = telemetry.default_registry().counter(
        "gordo_t_thread_total", "thread-safety probe"
    )
    # all threads in-flight together: thread idents are only guaranteed
    # distinct among live threads
    barrier = threading.Barrier(n_threads)

    def work(tid):
        barrier.wait()
        for i in range(n_spans):
            with telemetry.span("work", thread=tid, i=i):
                counter.inc()

    threads = [
        threading.Thread(target=work, args=(t,)) for t in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    trace = telemetry.stop_trace()
    assert len(trace["traceEvents"]) == n_threads * n_spans
    assert counter.value() == n_threads * n_spans
    # distinct tids recorded per thread
    assert len({e["tid"] for e in trace["traceEvents"]}) == n_threads


def test_trace_buffer_bounded(monkeypatch):
    monkeypatch.setattr(telemetry._TraceBuffer, "MAX_EVENTS", 3)
    telemetry.start_trace()
    for i in range(5):
        with telemetry.span("e", i=i):
            pass
    trace = telemetry.stop_trace()
    assert len(trace["traceEvents"]) == 3
    assert trace["otherData"]["droppedEvents"] == 2


def test_write_trace_roundtrip(tmp_path):
    telemetry.start_trace()
    with telemetry.span("fetch", machine="m-0"):
        pass
    path = tmp_path / "trace" / "out.json"
    telemetry.write_trace(str(path))
    data = json.loads(path.read_text())
    assert data["traceEvents"][0]["name"] == "fetch"
    assert data["displayTimeUnit"] == "ms"


def test_write_trace_without_trace_raises(tmp_path):
    with pytest.raises(RuntimeError):
        telemetry.write_trace(str(tmp_path / "out.json"))


# ------------------------------------------------- profiling integration
def test_profile_dir_alone_leaves_spans_off(monkeypatch, tmp_path):
    """The variable names where a session's trace goes; it is whoever opens
    the session that turns spans on, for as long as it is open."""
    monkeypatch.setenv(profiling.PROFILE_DIR_ENV, str(tmp_path))
    assert telemetry.span("compile") is telemetry._NULL_SPAN
    with telemetry.spans_on():
        assert telemetry.span("compile") is not telemetry._NULL_SPAN
    assert telemetry.span("compile") is telemetry._NULL_SPAN
    # an enclosing trace or enable_spans() outlives the session
    telemetry.enable_spans()
    with telemetry.spans_on():
        pass
    assert telemetry.spans_enabled()


def test_live_span_is_a_gordo_mark_on_the_profilers_clock(tmp_path):
    """A live span opens a TraceAnnotation ``gordo.<name>`` itself: inside a
    jax.profiler session it is an event of the session, outside one a
    branch."""
    import glob

    import jax
    from jax.profiler import ProfileData

    telemetry.enable_spans()
    with telemetry.span("outside_any_session"):
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.span("fetch", machine="m-0"):
            pass
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    names = {
        event.name
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines
        for event in line.events
    }
    assert "gordo.fetch" in names
    assert "gordo.outside_any_session" not in names


def test_span_disabled_path_reads_no_environment(monkeypatch):
    class _NoEnviron(dict):
        def get(self, *args):  # pragma: no cover - must not run
            raise AssertionError(f"span() read os.environ: {args}")

        __getitem__ = get

    monkeypatch.setattr(telemetry.os, "environ", _NoEnviron())
    assert telemetry.span("fetch") is telemetry._NULL_SPAN


# --------------------------------------------------------- end-to-end CLI
def _machine_block(name):
    tags = "".join(f"\n      - {name}-tag-{j}" for j in range(4))
    return f"""
  - name: {name}
    dataset:
      tags:{tags}
      train_start_date: '2019-01-01T00:00:00+00:00'
      train_end_date: '2019-01-03T00:00:00+00:00'
      data_provider: {{type: RandomDataProvider}}
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


def test_batch_build_trace_and_metrics_files(tmp_path, monkeypatch):
    """The acceptance contract: a faulted 3-machine batch-build with
    --trace-file/--metrics-file produces (1) valid Chrome-trace JSON with
    fetch/compile/train/serialize spans for each machine/bucket and (2) a
    Prometheus textfile including the fault-domain counters."""
    from click.testing import CliRunner

    from gordo_tpu.cli.cli import gordo

    monkeypatch.setenv("GORDO_TPU_FAULT_BACKOFF_BASE", "0")
    monkeypatch.setenv(
        faults.PLAN_ENV,
        json.dumps(
            {
                "rules": [
                    {"site": "data_fetch", "machine": "tl-1", "times": -1,
                     "error": "permanent"}
                ]
            }
        ),
    )
    faults.reset_plan()
    config_file = tmp_path / "config.yaml"
    config_file.write_text(
        "machines:" + "".join(_machine_block(f"tl-{i}") for i in range(3))
    )
    trace_file = tmp_path / "out.json"
    metrics_file = tmp_path / "out.prom"
    result = CliRunner().invoke(
        gordo,
        [
            "batch-build", str(config_file),
            "--output-dir", str(tmp_path / "models"),
            "--trace-file", str(trace_file),
            "--metrics-file", str(metrics_file),
        ],
    )
    assert result.exit_code == faults.EXIT_PARTIAL, result.output

    # (1) the Chrome trace: parseable, and phase spans per machine/bucket
    trace = json.loads(trace_file.read_text())
    events = trace["traceEvents"]
    assert events and all(e["ph"] == "X" for e in events)
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    fetch_machines = {
        e["args"]["machine"] for e in by_name["fetch"]
    }
    assert {"tl-0", "tl-1", "tl-2"} <= fetch_machines
    serialize_machines = {
        e["args"]["machine"] for e in by_name["serialize"]
    }
    assert serialize_machines == {"tl-0", "tl-2"}  # tl-1 quarantined
    assert by_name["compile"] and by_name["train"]
    assert all("bucket" in e["args"] for e in by_name["compile"])

    # (2) the Prometheus textfile: fault-domain counters present
    prom = metrics_file.read_text()
    assert 'gordo_build_quarantines_total{stage="data_fetch"} 1.0' in prom
    assert 'gordo_build_machines_total{outcome="quarantined"} 1.0' in prom
    assert 'gordo_build_machines_total{outcome="built"} 2.0' in prom
    assert "gordo_build_phase_seconds_bucket" in prom
    assert 'gordo_build_program_cache_requests_total' in prom
