"""Keep the benchmarks/ harnesses working (reference benchmarks/ dir;
excluded from its CI too, so here we only run tiny smoke shapes)."""

import json
import os
import sys
import threading
import wsgiref.simple_server

import pytest

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)

from benchmarks import load_test  # noqa: E402
from gordo_tpu.server.server import build_app  # noqa: E402


class _QuietHandler(wsgiref.simple_server.WSGIRequestHandler):
    def log_message(self, *args):
        pass


@pytest.fixture()
def live_server(model_collection_directory, trained_model_directories):
    """Serve the WSGI app over real HTTP in a daemon thread."""
    app = build_app({"MODEL_COLLECTION_DIR": model_collection_directory})
    server = wsgiref.simple_server.make_server(
        "127.0.0.1", 0, app, handler_class=_QuietHandler
    )
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    yield f"http://127.0.0.1:{server.server_port}"
    server.shutdown()


def test_load_test_against_live_server(live_server, gordo_project, capsys):
    rc = load_test.main(
        [
            "--host",
            live_server,
            "--project",
            gordo_project,
            "--users",
            "2",
            "--duration",
            "2",
            "--samples",
            "10",
        ]
    )
    assert rc == 0
    report = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert report["requests"] > 0
    assert report["errors"] == 0
    assert report["p95_ms"] >= report["p50_ms"]


def test_load_test_discover(live_server, gordo_project, gordo_name, sensors):
    machine, tags = load_test.discover(live_server, gordo_project)
    assert machine == gordo_name
    assert tags == [t.name for t in sensors]


def test_bench_server_smoke(monkeypatch):
    """Two-round bench run end-to-end (builds its own tiny model)."""
    from benchmarks import bench_server

    assert bench_server.run(rounds=2, samples=10, n_tags=2) == 0


def test_bench_budget_skips_sections_but_always_emits_record(
    capsys, monkeypatch, tmp_path
):
    """GORDO_TPU_BENCH_BUDGET_S is a hard wall: with the budget exhausted,
    no section subprocess is even started, yet the final summary line is
    still emitted and parseable — a bench run can never end with no
    parsed output (the round-5 rc=124 failure mode)."""
    import bench

    monkeypatch.setenv("GORDO_TPU_BENCH_BUDGET_S", "0")
    monkeypatch.setenv("BENCH_DETAIL_FILE", str(tmp_path / "detail.json"))
    started = []
    monkeypatch.setattr(
        bench, "_run_section", lambda *a, **k: started.append(a) or {}
    )
    bench.main()
    assert started == []  # zero budget: no child ever launched
    record = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(record["skipped_for_budget"]) == set(bench.SECTION_NAMES)
    assert record["value"] is None
    # schema v2: every canonical section accounted for with a status
    assert record["schema_version"] == bench.RECORD_SCHEMA_VERSION
    assert set(record["sections"]) == set(bench.SECTION_NAMES)
    assert all(
        status == "skipped_for_budget"
        for status in record["sections"].values()
    )


def test_bench_section_child_fails_without_an_accelerator(monkeypatch):
    """A section child that finds no accelerator fails instead of falling
    back; only an explicit ``JAX_PLATFORMS=cpu`` runs on CPU (tagged so)."""
    import bench

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(SystemExit, match="no accelerator"):
        bench._setup_section_child()
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert bench._setup_section_child() == "cpu"


def test_bench_section_timeout_partial_recovery(monkeypatch):
    """A section child killed on its leash must not lose the phases it
    already printed: the parent recovers the LAST partial envelope from the
    captured stdout and marks it hung+partial (round-5: windowed families
    and headline phases emit partials as they complete)."""
    import subprocess

    import bench

    partial1 = json.dumps({"platform": "cpu", "result": {"fam_a": {"x": 1}}})
    partial2 = json.dumps(
        {"platform": "cpu", "result": {"fam_a": {"x": 1}, "fam_b": {"x": 2}}}
    )
    stdout = f"noise\n{partial1}\n{partial2}\nnot json".encode()

    def fake_run(*args, **kwargs):
        raise subprocess.TimeoutExpired(
            cmd="x", timeout=7, output=stdout, stderr=b"stderr tail"
        )

    monkeypatch.setattr(subprocess, "run", fake_run)
    entry = bench._run_section("windowed", timeout=7)
    assert entry["hung"] and entry["partial"]
    assert entry["platform"] == "cpu"
    assert entry["result"] == {"fam_a": {"x": 1}, "fam_b": {"x": 2}}
    assert entry["status"] == "timeout" and "error" in entry


def test_bench_section_timeout_no_partials(monkeypatch):
    """Timeout with no parseable partial still returns the plain hang
    entry."""
    import subprocess

    import bench

    def fake_run(*args, **kwargs):
        raise subprocess.TimeoutExpired(cmd="x", timeout=7, output=b"garbage")

    monkeypatch.setattr(subprocess, "run", fake_run)
    entry = bench._run_section("headline", timeout=7)
    assert entry["hung"] and "partial" not in entry and "result" not in entry


def test_bench_emit_record_partial_sections(capsys, tmp_path, monkeypatch):
    """Incremental emission: the compact line renders at every stage of
    completeness — empty sections, smoke-only (serving falls back to the
    smoke's mini measurement), and budget-skipped sections listed."""
    import bench

    monkeypatch.setenv("BENCH_DETAIL_FILE", str(tmp_path / "detail.json"))
    sections = {n: {} for n in ("tpu_smoke", "headline", "windowed",
                                "batch_ab")}
    bench._emit_record(sections)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["value"] is None

    sections["tpu_smoke"] = {
        "platform": "tpu",
        "result": {"flash": {"ok": True}, "bf16_fleet": {"ok": True},
                   "serving": {"p50_ms": 3.0, "samples_per_sec": 100.0}},
    }
    sections["windowed"] = {"skipped_for_budget": True, "remaining_sec": 10}
    bench._emit_record(sections)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["serving_source"] == "tpu_smoke"
    assert line["server_p50_anomaly_ms"] == 3.0
    assert line["tpu_smoke"]["flash_ok"] is True
    assert line["skipped_for_budget"] == ["windowed"]
    # the compact line must stay one readable stdout line, far under the
    # driver tail capture that truncated round 3's multi-10-KiB line (the
    # gateway arm's flat keys pushed the null-valued skeleton past 2 KiB;
    # the v7 UDS/syscall/pipeline keys past 3)
    assert len(json.dumps(line)) < 1024 * 4


def test_bench_section_crash_partial_recovery(monkeypatch):
    """A child that dies with a non-zero exit (OOM kill) keeps its printed
    partials too — not just the timeout path."""
    import subprocess

    import bench

    partial = json.dumps({"platform": "tpu", "result": {"fam_a": {"x": 1}}})

    class Proc:
        returncode = -9
        stdout = f"{partial}\n"
        stderr = "killed"

    monkeypatch.setattr(subprocess, "run", lambda *a, **k: Proc())
    entry = bench._run_section("windowed", timeout=7)
    assert entry["partial"] and entry["result"] == {"fam_a": {"x": 1}}
    assert "error" in entry


def test_bench_run_section_status_vocabulary(monkeypatch):
    """Every _run_section exit path stamps an explicit schema-v2 status."""
    import subprocess

    import bench

    class Good:
        returncode = 0
        stdout = json.dumps({"platform": "cpu", "result": {"x": 1}}) + "\n"
        stderr = ""

    monkeypatch.setattr(subprocess, "run", lambda *a, **k: Good())
    entry = bench._run_section("windowed", timeout=7)
    assert entry["status"] == "completed"
    assert entry["timeout_s"] == 7 and "wall_sec" in entry

    def hang(*a, **k):
        raise subprocess.TimeoutExpired(cmd="x", timeout=7, output=b"")

    monkeypatch.setattr(subprocess, "run", hang)
    assert bench._run_section("windowed", timeout=7)["status"] == "timeout"

    class Crash:
        returncode = 1
        stdout = ""
        stderr = "boom"

    monkeypatch.setattr(subprocess, "run", lambda *a, **k: Crash())
    assert bench._run_section("windowed", timeout=7)["status"] == "failed"

    class Garbage:
        returncode = 0
        stdout = "not json"
        stderr = ""

    monkeypatch.setattr(subprocess, "run", lambda *a, **k: Garbage())
    assert bench._run_section("windowed", timeout=7)["status"] == "failed"


def test_bench_tiny_budget_subprocess_emits_complete_record(tmp_path):
    """Acceptance: a REAL ``python bench.py`` run under
    GORDO_TPU_BENCH_BUDGET_S exits rc=0 with a parseable final record in
    which every canonical section is present with an explicit status —
    the rc=124 total-data-loss mode is structurally gone."""
    import subprocess

    import bench

    repo = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    env = {
        **os.environ,
        "GORDO_TPU_BENCH_BUDGET_S": "1",
        "JAX_PLATFORMS": "cpu",
        "BENCH_DETAIL_FILE": str(tmp_path / "detail.json"),
    }
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py")],
        capture_output=True, text=True, timeout=180, env=env,
        cwd=str(tmp_path),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    record = json.loads(proc.stdout.strip().splitlines()[-1])
    assert record["schema_version"] == bench.RECORD_SCHEMA_VERSION
    assert set(record["sections"]) == set(bench.SECTION_NAMES)
    assert all(
        status in bench.SECTION_STATUSES
        for status in record["sections"].values()
    )
    assert set(record["skipped_for_budget"]) == set(bench.SECTION_NAMES)
    # the detail record carries the same accounting
    detail = json.loads((tmp_path / "detail.json").read_text())
    assert set(detail["sections"]) == set(bench.SECTION_NAMES)


def test_bench_section_selector_env(capsys, monkeypatch, tmp_path):
    """GORDO_TPU_BENCH_SECTIONS selects sections; the others are recorded
    as disabled, never silently dropped."""
    import bench

    monkeypatch.setenv("GORDO_TPU_BENCH_SECTIONS", "tpu_smoke,serving_load")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("GORDO_TPU_BENCH_BUDGET_S", "0")  # skip instantly
    monkeypatch.setenv("BENCH_DETAIL_FILE", str(tmp_path / "detail.json"))
    monkeypatch.setattr(bench, "_run_section", lambda *a, **k: {})
    bench.main()
    record = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert record["sections"]["tpu_smoke"] == "skipped_for_budget"
    assert record["sections"]["serving_load"] == "skipped_for_budget"
    assert record["sections"]["headline"] == "disabled"
    assert record["sections"]["windowed"] == "disabled"
    assert record["sections"]["batch_ab"] == "disabled"


# ------------------------------------------------ load generator (rewrite)
def test_load_test_qps_mode_live_server(live_server, gordo_project, capsys):
    """Open-loop QPS mode end-to-end: merged histogram percentiles
    (p50/p90/p99/p99.9), Server-Timing-fed phase histograms, trace ids."""
    rc = load_test.main(
        [
            "--host", live_server, "--project", gordo_project,
            "--mode", "qps", "--qps", "20", "--duration", "2",
            "--warmup", "0.5", "--users", "4", "--samples", "5",
            "--no-flight",
        ]
    )
    assert rc == 0
    report = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert report["mode"] == "qps" and report["qps_target"] == 20.0
    assert report["requests"] > 0 and report["errors"] == 0
    for key in ("p50_ms", "p90_ms", "p95_ms", "p99_ms", "p999_ms"):
        assert isinstance(report[key], float), key
    assert report["p999_ms"] >= report["p50_ms"]
    assert 0 < report["latency_rel_error_bound"] < 0.02
    # per-phase histograms fed from the Server-Timing header (PR 2)
    assert "request_walltime" in report["phases"]
    assert report["phases"]["request_walltime"]["p99_ms"] > 0
    # slowest requests carry trace ids for the flight cross-check
    assert report["slowest"] and report["slowest"][0]["trace_id"]


def test_load_test_ramp_mode(live_server, gordo_project, capsys):
    rc = load_test.main(
        [
            "--host", live_server, "--project", gordo_project,
            "--mode", "ramp", "--ramp-users", "1,2", "--duration", "1",
            "--samples", "5", "--no-flight",
        ]
    )
    assert rc == 0
    report = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert [step["users"] for step in report["steps"]] == [1, 2]
    assert all(step["requests"] > 0 for step in report["steps"])
    assert report["requests"] == sum(s["requests"] for s in report["steps"])


def test_load_test_open_loop_surfaces_stall_in_tail():
    """Coordinated omission: one 0.6s server stall at 25 QPS. Open-loop
    accounting measures every queued request from its INTENDED send time,
    so the backlog the stall created lands in the tail — p99 must report
    hundreds of ms while p50 stays fast. (A naive closed-loop would have
    recorded one slow sample and ~fast everything else.)"""
    import threading as _threading
    import time as _time

    from benchmarks.load_test import run_open, summarize

    calls = [0]
    lock = _threading.Lock()

    def send():
        with lock:
            calls[0] += 1
            n = calls[0]
        _time.sleep(0.6 if n == 10 else 0.002)
        return None, None, {}

    stats, wall = run_open(send, users=1, qps=25, duration=2.0, warmup=0.0)
    report = summarize(stats, wall, 1)
    assert report["requests"] >= 40
    assert report["p99_ms"] > 200, report
    assert report["p50_ms"] < 100, report


def test_load_test_multiprocess_open_loop_covers_schedule_exactly():
    """``--processes N`` forks the generator: children stride-slice one
    global schedule (child k owns i ≡ k mod N), so the union covers every
    arrival index exactly once — measured request count must equal the
    single-process schedule's, with zero errors."""
    import time as _time

    from benchmarks.load_test import run_open_processes, summarize

    def send():
        _time.sleep(0.002)
        return None, None, {"request_walltime": 0.002}

    qps, duration, warmup = 50, 1.0, 0.2
    stats, wall = run_open_processes(
        send, users=2, qps=qps, duration=duration, warmup=warmup,
        processes=2,
    )
    report = summarize(stats, wall, 1)
    total = int(round((warmup + duration) * qps))
    first_measured = int(round(warmup * qps))
    assert report["requests"] == total - first_measured
    assert report["errors"] == 0
    assert report["p50_ms"] and report["p50_ms"] >= 2.0
    # phase histograms survive the pipe and merge
    assert report["phases"]["request_walltime"]["p50_ms"] == pytest.approx(
        2.0, rel=0.02
    )


def test_load_test_histograms_merge_exactly_across_processes():
    """The merge the parent performs on child histograms is exact: bucket
    counts add, so quantiles of the merged histogram equal quantiles of
    one histogram fed every sample — serialization round trip included."""
    import json as _json

    import numpy as np

    from benchmarks.load_test import (
        WorkerStats, _stats_from_dict, _stats_to_dict,
    )
    from gordo_tpu.observability.latency import LatencyHistogram

    rng = np.random.RandomState(7)
    samples = rng.gamma(2.0, 0.004, size=4000)
    reference = LatencyHistogram()
    shards = [WorkerStats(), WorkerStats(), WorkerStats()]
    for i, value in enumerate(samples):
        reference.record(float(value))
        shards[i % 3].observe(float(value), None, None, {}, measured=True)

    # round trip through the pipe wire format, then merge
    wired = [
        _stats_from_dict(_json.loads(_json.dumps(_stats_to_dict(s))))
        for s in shards
    ]
    merged = LatencyHistogram.merged(w.hist for w in wired)
    assert merged.count == reference.count == len(samples)
    for q in (0.5, 0.9, 0.99, 0.999):
        assert merged.quantile(q) == reference.quantile(q), q


def test_load_test_flight_cross_check(live_server, gordo_project,
                                      monkeypatch, capsys):
    """The closing argument: the report's worst requests come back with
    their span trees pulled from the PR-5 flight recorder."""
    from gordo_tpu.observability import flight

    monkeypatch.setenv("GORDO_TPU_DEBUG_ENDPOINTS", "1")
    # keep every trace: a tiny threshold + a ring big enough that the
    # slowest requests can't be evicted before the final fetch
    monkeypatch.setenv("GORDO_TPU_FLIGHT_SLOW_S", "0.0001")
    monkeypatch.setenv("GORDO_TPU_FLIGHT_CAPACITY", "4096")
    flight.reset()
    try:
        rc = load_test.main(
            [
                "--host", live_server, "--project", gordo_project,
                "--duration", "1", "--users", "2", "--samples", "5",
            ]
        )
        assert rc == 0
        report = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        worst = report["flight"]
        assert worst["available"] is True
        assert worst["recorded"] >= 1
        recorded = [w for w in worst["worst_requests"] if w["recorded"]]
        assert recorded and recorded[0]["trace_id"]
        span_names = {
            span["name"] for w in recorded for span in w["spans"]
        }
        assert "serve_request" in span_names
    finally:
        flight.reset()


def test_load_test_flight_gated_off_degrades(live_server, gordo_project,
                                             capsys):
    """Without GORDO_TPU_DEBUG_ENDPOINTS the cross-check degrades to a
    reason string, never an error."""
    rc = load_test.main(
        [
            "--host", live_server, "--project", gordo_project,
            "--duration", "1", "--users", "2", "--samples", "5",
        ]
    )
    assert rc == 0
    report = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert report["flight"]["available"] is False
    assert "GORDO_TPU_DEBUG_ENDPOINTS" in report["flight"]["reason"]


def test_bench_serving_load_section(monkeypatch, tmp_path):
    """The bench harness's serving_load section end-to-end (tiny knobs):
    builds a model, serves it over real HTTP, drives the open-loop load
    generator, and returns QPS + ramp reports with tail percentiles,
    flight-recorded worst requests, and the merged fleet-plane summary."""
    import bench
    from gordo_tpu.observability import flight, shared, slo

    monkeypatch.setenv("GORDO_TPU_DEBUG_ENDPOINTS", "1")
    monkeypatch.setenv("GORDO_TPU_FLIGHT_SLOW_S", "0.0001")
    monkeypatch.setenv("GORDO_TPU_FLIGHT_CAPACITY", "4096")
    monkeypatch.setenv("GORDO_TPU_BENCH_LOAD_QPS", "20")
    monkeypatch.setenv("GORDO_TPU_BENCH_LOAD_SECONDS", "1.5")
    monkeypatch.setenv("GORDO_TPU_BENCH_LOAD_WARMUP_S", "0.3")
    monkeypatch.setenv("GORDO_TPU_BENCH_LOAD_USERS", "2")
    # every env knob the section would os.environ.setdefault must be
    # monkeypatched here, or the setdefault leaks into the test process
    # (the telemetry dir would flip later tests' /metrics into fleet mode)
    monkeypatch.setenv("GORDO_TPU_TELEMETRY_DIR", str(tmp_path))
    monkeypatch.setattr(bench, "EPOCHS", 1)  # one-epoch model build
    flight.reset()
    shared.reset_for_tests()
    slo.reset()
    try:
        result = bench._bench_serving_load()
    finally:
        flight.reset()
        shared.reset_for_tests()
        slo.reset()
    # fleet-plane summary (ISSUE 9): the one-worker fleet's census and the
    # model's merged 5m SLO window, travelled through the full shard path
    fleet = result["fleet"]
    assert "error" not in fleet, fleet
    assert fleet["workers"] == 1
    assert fleet["requests_total"] > 0
    assert fleet["p99_ms"] is not None and fleet["p99_ms"] > 0
    assert fleet["latency_burn_rate"] is not None
    qps = result["qps"]
    assert qps["requests"] > 0 and qps["mode"] == "qps"
    assert qps["p999_ms"] >= qps["p50_ms"] > 0
    assert qps["flight"]["available"] is True
    assert [s["users"] for s in result["ramp"]["steps"]] == [1, 2, 4]
    # the fast-lane arm (ISSUE 7): same schedule through the socket front
    # end, including the /debug/flight pull over the WSGI fallback
    fastlane_qps = result["fastlane_qps"]
    assert "error" not in fastlane_qps, fastlane_qps
    assert fastlane_qps["requests"] > 0
    assert fastlane_qps["errors"] == 0
    assert fastlane_qps["p999_ms"] >= fastlane_qps["p50_ms"] > 0
    assert fastlane_qps["flight"]["available"] is True
    # the serving_gateway arm (ISSUE 12): same schedule routed through
    # the consistent-hash gateway over two lease-registered nodes, then
    # the machine's ring primary is killed and recovery is timed
    gateway = result["gateway"]
    assert "error" not in gateway, gateway
    assert gateway["requests"] > 0
    assert gateway["nodes"] == 2
    assert gateway["p99_ms"] >= gateway["p50_ms"] > 0
    assert gateway["p50_overhead_ms"] is not None
    assert gateway["recovery_s"] is not None
    assert gateway["recovery_s"] < 10.0


# ------------------------------------------------------- bench_compare gate
def _run_compare(*args):
    import subprocess

    script = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
        "scripts",
        "bench_compare.py",
    )
    return subprocess.run(
        [sys.executable, script, *map(str, args)],
        capture_output=True,
        text=True,
    )


def _record(tmp_path, name, **parsed):
    path = tmp_path / name
    base = {"platform": "cpu"}
    base.update(parsed)
    path.write_text(json.dumps({"n": 1, "parsed": base}))
    return path


def test_bench_compare_no_regression(tmp_path):
    old = _record(tmp_path, "old.json", value=100.0,
                  server_samples_per_sec=1000.0,
                  server_p50_net_of_floor_ms=10.0)
    new = _record(tmp_path, "new.json", value=110.0,
                  server_samples_per_sec=1200.0,
                  server_p50_net_of_floor_ms=8.0)
    result = _run_compare(old, new)
    assert result.returncode == 0, result.stdout + result.stderr
    assert "no regression" in result.stdout


def test_bench_compare_flags_regression_past_threshold(tmp_path):
    old = _record(tmp_path, "old.json", value=100.0,
                  server_p50_net_of_floor_ms=10.0)
    # 30% slower headline, 2x worse serving p50: both past the 15% default
    new = _record(tmp_path, "new.json", value=70.0,
                  server_p50_net_of_floor_ms=20.0)
    result = _run_compare(old, new)
    assert result.returncode == 1, result.stdout + result.stderr
    assert "REGRESSION" in result.stdout
    assert "server_p50_net_of_floor_ms" in result.stdout
    # a wide-open threshold accepts the same pair (worst delta is the
    # doubled p50 = -100%)
    assert _run_compare(old, new, "--threshold", "1.5").returncode == 0


def test_bench_compare_platform_mismatch_not_a_regression(tmp_path):
    old = _record(tmp_path, "old.json", value=100.0, platform="tpu")
    new = _record(tmp_path, "new.json", value=10.0, platform="cpu")
    result = _run_compare(old, new)
    assert result.returncode == 0
    assert "not comparable" in result.stdout
    assert _run_compare(old, new, "--strict-platform").returncode == 2


def test_bench_compare_unusable_record(tmp_path):
    old = _record(tmp_path, "old.json", value=100.0)
    junk = tmp_path / "junk.json"
    junk.write_text("{}")  # no parsed block
    assert _run_compare(old, junk).returncode == 2
    assert _run_compare(tmp_path / "missing.json", old).returncode == 2


def _v2_record(tmp_path, name, statuses=None, **parsed):
    """A schema-v2 record: full section accounting + summary keys."""
    import bench

    sections = {n: "completed" for n in bench.SECTION_NAMES}
    sections.update(statuses or {})
    base = {
        "schema_version": bench.RECORD_SCHEMA_VERSION,
        "platform": "cpu",
        "serving_source": "headline",
        "sections": sections,
    }
    base.update(parsed)
    path = tmp_path / name
    path.write_text(json.dumps({"n": 1, "parsed": base}))
    return path


def test_bench_compare_section_matching_excludes_incomplete(tmp_path):
    """Comparable-section matching: a metric whose feeding section did
    not complete in one record is 'not comparable', never a regression —
    a timed-out headline must not read as a 90% slowdown."""
    old = _v2_record(tmp_path, "old.json", value=100.0,
                     server_load_p99_ms=10.0)
    # headline timed out in the new record; its partial value would
    # otherwise read as a catastrophic regression
    new = _v2_record(tmp_path, "new.json", value=9.0,
                     server_load_p99_ms=10.5,
                     statuses={"headline": "timeout"})
    result = _run_compare(old, new)
    assert result.returncode == 0, result.stdout + result.stderr
    assert "value: skipped (section headline is 'timeout'" in result.stdout


def test_bench_compare_gates_on_load_tail_regression(tmp_path):
    """The new serving_load metrics are first-class gate inputs: a
    doubled open-loop p99 or halved sustained rate trips the gate."""
    old = _v2_record(tmp_path, "old.json", value=100.0,
                     server_load_p99_ms=10.0, server_load_req_per_sec=50.0)
    new = _v2_record(tmp_path, "new.json", value=101.0,
                     server_load_p99_ms=20.0, server_load_req_per_sec=48.0)
    result = _run_compare(old, new)
    assert result.returncode == 1, result.stdout + result.stderr
    assert "server_load_p99_ms" in result.stdout
    # but not when the serving_load section was budget-skipped
    skipped = _v2_record(
        tmp_path, "skipped.json", value=101.0, server_load_p99_ms=None,
        statuses={"serving_load": "skipped_for_budget"},
    )
    assert _run_compare(old, skipped).returncode == 0


def test_bench_compare_gates_on_gateway_regression(tmp_path):
    """The serving_gateway arm's keys are first-class gate inputs: a
    blown-up node-kill recovery time or routed overhead trips the gate;
    records predating the arm (keys absent) compare clean."""
    old = _v2_record(tmp_path, "old.json", value=100.0,
                     server_gateway_recovery_s=2.0,
                     server_gateway_p50_overhead_ms=1.0)
    new = _v2_record(tmp_path, "new.json", value=100.0,
                     server_gateway_recovery_s=8.0,
                     server_gateway_p50_overhead_ms=1.1)
    result = _run_compare(old, new)
    assert result.returncode == 1, result.stdout + result.stderr
    assert "server_gateway_recovery_s" in result.stdout
    # pre-gateway baseline: keys absent on one side → skipped, not a gate
    legacy = _v2_record(tmp_path, "legacy.json", value=100.0)
    result = _run_compare(legacy, new)
    assert result.returncode == 0, result.stdout + result.stderr
    assert "server_gateway_recovery_s: skipped" in result.stdout


def test_bench_compare_latest_mode(tmp_path):
    """--latest picks the two most recent records; fewer than two is a
    note, not an error (first round of a fresh repo)."""
    assert _run_compare("--latest", tmp_path).returncode == 0
    _v2_record(tmp_path, "BENCH_r01.json", value=100.0)
    _v2_record(tmp_path, "BENCH_r02.json", value=99.0)
    _v2_record(tmp_path, "BENCH_r03.json", value=50.0)  # regressed vs r02
    # a newer DATA-LOSS record (parsed: null, the r04 failure shape) is
    # skipped — the gate compares the most recent USABLE pair
    (tmp_path / "BENCH_r04.json").write_text(
        json.dumps({"n": 4, "rc": 124, "parsed": None})
    )
    result = _run_compare("--latest", tmp_path)
    assert result.returncode == 1
    assert "BENCH_r02.json" in result.stdout
    assert "BENCH_r03.json" in result.stdout


def test_bench_compare_smoke_on_checked_in_records():
    """The r01–r05 trajectory is at least parseable by the gate: the
    script must classify every checked-in record pair without crashing
    (older records may legitimately be unusable/not-comparable)."""
    repo = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    records = sorted(
        os.path.join(repo, f)
        for f in os.listdir(repo)
        if f.startswith("BENCH_r") and f.endswith(".json")
    )
    assert records, "no BENCH_r*.json records checked in"
    result = _run_compare(records[0], records[-1])
    assert result.returncode in (0, 1, 2), result.stderr


# ------------------------------------------- shaped open-loop schedules
def test_build_schedule_flat_is_the_plain_open_loop_grid():
    """Default-off pin: the flat shape IS run_open's implicit ``i/qps``
    arrival grid, element for element — shapes are a superset of the
    plain open loop, never a drift from it."""
    qps, duration = 37.0, 2.0
    schedule = load_test.build_schedule("flat", qps, duration)
    assert schedule == [i / qps for i in range(int(round(duration * qps)))]


def test_build_schedule_diurnal_exact_inversion():
    """Diurnal arrivals invert the closed-form cumulative-rate curve: the
    i-th arrival t_i satisfies N(t_i) == i to float precision, offsets
    are strictly increasing, and amp=0 degenerates to the flat grid."""
    import math

    qps, duration, amp = 20.0, 4.0, 0.5
    schedule = load_test.build_schedule("diurnal", qps, duration, amp=amp)
    assert schedule == sorted(schedule)
    assert len(schedule) == int(round(qps * duration))
    two_pi = 2.0 * math.pi

    def cum(t):
        return qps * (
            t - amp * duration / two_pi
            * (math.cos(two_pi * t / duration) - 1.0)
        )

    for i, t in enumerate(schedule):
        assert abs(cum(t) - i) < 1e-6, (i, t)

    flat_again = load_test.build_schedule("diurnal", qps, duration, amp=0.0)
    grid = load_test.build_schedule("flat", qps, duration)
    assert all(abs(a - b) < 1e-6 for a, b in zip(flat_again, grid))


def test_build_schedule_flash_burst_placement():
    """Flash = the flat base plus an extra (peak-1)x burst of evenly
    spaced arrivals confined to [flash_at, flash_at + flash_len)."""
    qps, duration = 10.0, 4.0
    schedule = load_test.build_schedule(
        "flash", qps, duration, peak=4.0, flash_at=1.0, flash_len=1.0
    )
    base = load_test.build_schedule("flat", qps, duration)
    extra = sorted(schedule)
    for t in base:
        extra.remove(t)
    assert len(extra) == int(round(1.0 * qps * 3.0))  # (peak-1) * len * qps
    assert all(1.0 <= t < 2.0 for t in extra), extra
    assert schedule == sorted(schedule)

    with pytest.raises(ValueError):
        load_test.build_schedule("sawtooth", qps, duration)


def test_skewed_key_picker_deterministic_hot_key():
    keys = [f"m-{i:03d}" for i in range(10)]
    pick = load_test.skewed_key_picker(keys, hot_pct=40.0, seed=3)
    again = load_test.skewed_key_picker(keys, hot_pct=40.0, seed=3)
    chosen = [pick(i) for i in range(1000)]
    assert chosen == [again(i) for i in range(1000)]  # pure determinism
    hot = keys[3 % len(keys)]
    hot_share = chosen.count(hot) / len(chosen)
    assert hot_share > 0.30  # ~40% + its round-robin turns
    # no skew -> plain round-robin
    rr = load_test.skewed_key_picker(keys, hot_pct=0.0)
    assert [rr(i) for i in range(20)] == [keys[i % 10] for i in range(20)]


def test_run_open_sharded_lease_split_and_exact_merge(tmp_path):
    """Filesystem-lease sharding: independent workers claim disjoint
    shards of ONE global schedule via O_EXCL lease files, and the merged
    result accounts for every arrival exactly once — histogram counts
    add, no double-sends, no gaps."""
    from gordo_tpu.observability.latency import LatencyHistogram

    schedule = load_test.build_schedule("flat", 200.0, 0.5)
    shard_dir = str(tmp_path / "shards")
    os.makedirs(shard_dir)
    sent = []
    sent_lock = threading.Lock()

    def send(key):
        with sent_lock:
            sent.append(key)
        return None, None, {}

    keys = [f"m-{i:03d}" for i in range(5)]
    key_of = load_test.skewed_key_picker(keys, hot_pct=20.0, seed=1)
    claimed = []
    workers = [
        threading.Thread(
            target=lambda who: claimed.extend(
                load_test.run_open_sharded(
                    send, 2, schedule, 4, shard_dir,
                    owner=who, keep_log=True, key_of=key_of,
                )
            ),
            args=(f"owner-{w}",),
        )
        for w in range(2)
    ]
    for t in workers:
        t.start()
    for t in workers:
        t.join()

    assert sorted(claimed) == [0, 1, 2, 3]  # every shard claimed exactly once
    assert len(sent) == len(schedule)       # no double-sends, no gaps
    stats_list, wall, missing = load_test.merge_shard_results(
        shard_dir, 4, timeout=10.0
    )
    assert missing == []
    merged = LatencyHistogram.merged(s.hist for s in stats_list)
    assert merged.count == len(schedule)
    # and the logged per-arrival keys match the deterministic picker
    logged_keys = sorted(
        entry[3] for s in stats_list for entry in s.log
    )
    assert logged_keys == sorted(key_of(i) for i in range(len(schedule)))


def test_chaff_and_pipelined_burst_against_threaded_server():
    """slow-loris chaff gives up at its deadline (server surviving), a
    scanner gets answered without killing the listener, and the
    pipelining probe gets every response in order on one connection."""
    import http.server
    import socketserver

    class Handler(http.server.BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def do_GET(self):
            body = b'{"ok": true}'
            self.send_response(200 if self.path == "/ping" else 404)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *args):
            pass

    httpd = socketserver.ThreadingTCPServer(
        ("127.0.0.1", 0), Handler, bind_and_activate=True
    )
    httpd.daemon_threads = True
    port = httpd.server_address[1]
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    try:
        loris = load_test.run_chaff(
            "127.0.0.1", port, "slow_loris", conns=2, duration=0.6
        )
        assert loris["opened"] == 2
        scan = load_test.run_chaff(
            "127.0.0.1", port, "scanner", conns=2, duration=0.5
        )
        assert scan["opened"] >= 2
        assert scan["responses"] >= 2  # 404s, but answered — server alive

        burst = load_test.pipelined_burst(
            "127.0.0.1", port, "/ping", burst=4, rounds=2
        )
        assert burst["responses"] == 8
        assert burst["ok"] == 8
        assert "error" not in burst

        # the server survived the abuse: a normal request still works
        import http.client

        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=5)
        conn.request("GET", "/ping")
        assert conn.getresponse().status == 200
        conn.close()
    finally:
        httpd.shutdown()
        httpd.server_close()
