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
