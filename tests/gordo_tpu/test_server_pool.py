"""
Multi-process serving pool: run_server's prefork arbiter as real processes.

The reference delegates worker pooling to gunicorn (server.py:233-297) and
never tests worker death; here the arbiter is ours, so the contract — N
workers accepting on one inherited socket, dead workers reaped and
respawned, traffic surviving a worker SIGKILL — is pinned by this drive.
Runs the server as a subprocess on the CPU backend (the verify recipe's
multi-process drive, automated).
"""

import json
import os
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

from _nethelpers import free_port as _free_port
from _nethelpers import wait_for as _wait_for

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_SERVER_SCRIPT = """
import logging, os, sys
logging.basicConfig(level=logging.INFO, stream=sys.stderr)
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, {repo!r})
import jax
jax.config.update("jax_platforms", "cpu")
from gordo_tpu.server.server import run_server
run_server(host="127.0.0.1", port={port}, workers={workers}, warmup={warmup})
"""


def _get(url: str, timeout: float = 10.0):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, resp.read()


def _post_json(url: str, payload: dict, timeout: float = 60.0):
    req = urllib.request.Request(
        url,
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, resp.read()


def _worker_pids(arbiter_pid: int):
    # pgrep -P is portable (procps and BSD alike, unlike ps --ppid)
    proc = subprocess.run(
        ["pgrep", "-P", str(arbiter_pid)], capture_output=True, text=True
    )
    # exit 1 = no children (valid); anything else is a tooling failure that
    # must not masquerade as a pool assertion
    if proc.returncode not in (0, 1):
        raise RuntimeError(f"pgrep failed rc={proc.returncode}: {proc.stderr}")
    return [int(p) for p in proc.stdout.split()]


@pytest.fixture()
def server_pool(model_collection_directory, trained_model_directories, tmp_path):
    yield from _pool(model_collection_directory, tmp_path)


@pytest.fixture()
def server_pool_fastlane(
    model_collection_directory, trained_model_directories, tmp_path
):
    """The same 3-worker prefork pool with the socket fast lane mounted
    (GORDO_TPU_FAST_LANE=1) — every pool guarantee must hold identically."""
    yield from _pool(
        model_collection_directory, tmp_path,
        extra_env={"GORDO_TPU_FAST_LANE": "1"},
    )


@pytest.fixture()
def server_pool_fleet(
    model_collection_directory, trained_model_directories, tmp_path
):
    """3-worker pool with telemetry shards on an operator-provided dir and
    prometheus DISABLED (the default config): /metrics must serve the
    merged fleet exposition with no prometheus_client in the loop."""
    telemetry_dir = tmp_path / "telemetry"
    telemetry_dir.mkdir()
    yield from _pool(
        model_collection_directory, tmp_path,
        extra_env={
            "GORDO_TPU_TELEMETRY_DIR": str(telemetry_dir),
            # flush every request: the scrape assertions below must see
            # the last request's increments without waiting out the
            # 0.25s write throttle
            "GORDO_TPU_TELEMETRY_FLUSH_S": "0",
            "GORDO_TPU_DEBUG_ENDPOINTS": "1",
        },
    )


def _pool(model_collection_directory, tmp_path, extra_env=None):
    port = _free_port()
    env = {
        "PATH": os.environ.get("PATH", ""),
        "HOME": os.environ.get("HOME", "/root"),
        "JAX_PLATFORMS": "cpu",
        "MODEL_COLLECTION_DIR": model_collection_directory,
        "PROJECT": "gordo-test",
    }
    env.update(extra_env or {})
    # stderr to a file, not a PIPE: four processes share the stream and an
    # undrained pipe would block a worker mid-request once it fills
    errlog = tmp_path / "server-stderr.log"
    with open(errlog, "w") as errfh:
        # new session so teardown can killpg the WHOLE pool — SIGKILLing
        # only the arbiter would orphan three live worker processes
        proc = subprocess.Popen(
            [sys.executable, "-c",
             _SERVER_SCRIPT.format(repo=REPO, port=port, workers=3, warmup=True)],
            env=env,
            stdout=subprocess.DEVNULL,
            stderr=errfh,
            start_new_session=True,
        )
    base = f"http://127.0.0.1:{port}"

    def _teardown(sig=signal.SIGTERM):
        try:
            os.killpg(proc.pid, sig)
        except ProcessLookupError:
            pass
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait(timeout=10)

    deadline = time.monotonic() + 120
    last_err = None
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            # the arbiter may have died abruptly (no finally-block cleanup)
            # with forked workers still alive in its session — reap them
            _teardown(signal.SIGKILL)
            raise RuntimeError(
                f"server exited rc={proc.returncode}: "
                f"{errlog.read_text()[-2000:]}"
            )
        try:
            status, _ = _get(f"{base}/healthcheck", timeout=5)
            if status == 200:
                break
        except (urllib.error.URLError, OSError) as exc:
            last_err = exc
        # sleep on BOTH the not-ready and non-200 paths — a half-up server
        # answering 500s must not be hammered in a tight loop
        time.sleep(0.5)
    else:
        _teardown()
        raise RuntimeError(
            f"server never came up: {last_err}; stderr: "
            f"{errlog.read_text()[-2000:]}"
        )
    yield proc, base, errlog
    _teardown()


def test_pool_serves_and_survives_worker_kill(
    server_pool, gordo_project, gordo_name, X_payload
):
    # the canonical frame + the real wire encoding — shared with the
    # in-process server tests so both suites pin one payload
    from gordo_tpu.server.utils import dataframe_to_dict

    proc, base, errlog = server_pool
    url = f"{base}/gordo/v0/{gordo_project}/{gordo_name}/anomaly/prediction"
    frame = dataframe_to_dict(X_payload)
    payload = {"X": frame, "y": frame}

    # the pool booted with warmup: each worker precompiled its serving
    # programs before accepting (run-server --warmup end-to-end)
    assert "serving warmup:" in errlog.read_text()

    status, body = _post_json(url, payload)
    assert status == 200
    assert json.loads(body)["data"]

    workers = _worker_pids(proc.pid)
    assert len(workers) == 3, f"expected 3 workers, got {workers}"

    os.kill(workers[0], signal.SIGKILL)

    # probe the tooling once OUTSIDE _wait_for: its blanket except would
    # swallow _worker_pids' fail-fast RuntimeError for the full timeout
    _worker_pids(proc.pid)

    # the pool keeps serving while the arbiter reaps and respawns — retried
    # because the killed worker may have held in-flight accepts
    assert _wait_for(
        lambda: _post_json(url, payload, timeout=30)[0] == 200, timeout=60
    ), "pool stopped serving after a worker SIGKILL"

    # the arbiter respawns back to full strength
    assert _wait_for(
        lambda: len(
            [p for p in _worker_pids(proc.pid) if p != workers[0]]
        ) == 3,
        timeout=60,
    ), f"pool never respawned to 3 workers: {_worker_pids(proc.pid)}"


def test_pool_fast_lane_serves_hot_and_fallback_routes(
    server_pool_fastlane, gordo_project, gordo_name, X_payload
):
    """run_server with GORDO_TPU_FAST_LANE=1: the prefork pool mounts the
    socket fast lane on the shared listening socket — hot prediction
    POSTs, WSGI-fallback routes, and worker-kill survival all hold."""
    from gordo_tpu.server.utils import dataframe_to_dict

    proc, base, errlog = server_pool_fastlane
    url = f"{base}/gordo/v0/{gordo_project}/{gordo_name}/anomaly/prediction"
    frame = dataframe_to_dict(X_payload)
    payload = {"X": frame, "y": frame}

    status, body = _post_json(url, payload)
    assert status == 200
    data = json.loads(body)["data"]
    assert "total-anomaly-scaled" in data

    # fallback routes answer through the same port
    status, body = _get(f"{base}/gordo/v0/{gordo_project}/models")
    assert status == 200
    assert gordo_name in json.loads(body)["models"]

    workers = _worker_pids(proc.pid)
    assert len(workers) == 3
    os.kill(workers[0], signal.SIGKILL)
    _worker_pids(proc.pid)
    assert _wait_for(
        lambda: _post_json(url, payload, timeout=30)[0] == 200, timeout=60
    ), "fast-lane pool stopped serving after a worker SIGKILL"


def test_pool_metrics_serve_fleet_sums_without_prometheus(
    server_pool_fleet, gordo_project, gordo_name, X_payload
):
    """ISSUE 9 acceptance drive: a 3-worker prefork pool with
    GORDO_TPU_TELEMETRY_DIR set and prometheus disabled answers /metrics
    with the FLEET-SUMMED counters and merged histograms — whichever
    worker takes the scrape, the prediction total equals the requests
    actually sent, and /debug/slo reports the merged per-model burn
    rates."""
    import re

    from gordo_tpu.server.utils import dataframe_to_dict

    proc, base, errlog = server_pool_fleet
    url = f"{base}/gordo/v0/{gordo_project}/{gordo_name}/anomaly/prediction"
    frame = dataframe_to_dict(X_payload)
    payload = {"X": frame, "y": frame}

    n_requests = 4
    for _ in range(n_requests):
        status, _body = _post_json(url, payload)
        assert status == 200

    series_re = re.compile(
        r"^gordo_server_fleet_requests_total\{([^}]*)\}\s+([0-9.eE+-]+)$",
        re.MULTILINE,
    )
    count_re = re.compile(
        r"^gordo_server_fleet_request_seconds_count\{([^}]*)\}"
        r"\s+([0-9.eE+-]+)$",
        re.MULTILINE,
    )

    def _prediction_sum(pattern, text):
        # sum across workers AND status/endpoint series: the scrape may be
        # answered by any worker, but the merge must account for every
        # prediction the pool served regardless of which worker took it
        return sum(
            float(value)
            for labels, value in pattern.findall(text)
            if "prediction" in labels
        )

    def _scrape():
        status, body = _get(f"{base}/metrics", timeout=10)
        assert status == 200
        return body.decode()

    # the observability feed runs as the response goes out; poll the scrape
    # until every prediction has landed in some worker's shard
    assert _wait_for(
        lambda: _prediction_sum(series_re, _scrape()) >= n_requests,
        timeout=30,
    ), f"fleet counter never reached {n_requests}: {_scrape()[:2000]}"

    text = _scrape()
    # dependency-free Prometheus exposition, not prometheus_client output
    assert "# TYPE gordo_server_fleet_requests_total counter" in text
    assert "# TYPE gordo_server_fleet_workers gauge" in text
    assert _prediction_sum(series_re, text) == n_requests
    # merged histogram: element-wise sum across shards — the prediction
    # count equals the counter total even when workers split the traffic
    assert _prediction_sum(count_re, text) == n_requests
    workers_match = re.search(
        r"^gordo_server_fleet_workers\s+([0-9.]+)$", text, re.MULTILINE
    )
    assert workers_match, text[:2000]
    assert 1 <= float(workers_match.group(1)) <= 3

    # /debug/slo: the merged per-model view over the same shards
    status, body = _get(f"{base}/debug/slo", timeout=10)
    assert status == 200
    fleet = json.loads(body)["fleet"]
    window = fleet["models"][gordo_name]["5m"]
    assert window["requests"] == n_requests
    assert window["errors"] == 0
    assert window["p99_ms"] is not None
    assert window["error_burn_rate"] == 0.0
    assert window["latency_burn_rate"] is not None


def test_boot_failure_during_slow_warmup_trips_throttle(tmp_path):
    """A worker that dies DURING warmup — after more than the fast-death
    wall-clock threshold — must still count as a boot failure (readiness
    pipe, not just wall-clock): before the readiness signal existed, slow
    boot deaths reset the throttle and the arbiter crash-looped forever."""
    # a collection whose model "artifact" kills the process ~2.5s into
    # unpickling — an OOM-kill/abort stand-in the worker cannot catch
    mdir = tmp_path / "boom"
    mdir.mkdir()
    (mdir / "metadata.json").write_text(
        json.dumps({"dataset": {"tags": ["t-0", "t-1"]},
                    "metadata": {"build_metadata": {"model": {"model_offset": 0}}}})
    )
    # hand-written pickle opcodes: GLOBAL exec, TUPLE1 of the source, REDUCE
    payload = (
        b"c__builtin__\nexec\n"
        b"(Vimport time,os; time.sleep(2.5); os._exit(7)\ntR."
    )
    (mdir / "model.pkl").write_bytes(payload)

    port = _free_port()
    env = {
        "PATH": os.environ.get("PATH", ""),
        "HOME": os.environ.get("HOME", "/root"),
        "JAX_PLATFORMS": "cpu",
        "MODEL_COLLECTION_DIR": str(tmp_path),
        "PROJECT": "gordo-test",
    }
    errlog = tmp_path / "stderr.log"
    with open(errlog, "w") as errfh:
        # workers=2 engages the prefork arbiter (workers=1 serves
        # inline); still only ~6 boot-death cycles to the throttle
        proc = subprocess.Popen(
            [sys.executable, "-c",
             _SERVER_SCRIPT.format(repo=REPO, port=port, workers=2, warmup=True)],
            env=env, stdout=subprocess.DEVNULL, stderr=errfh,
            start_new_session=True,
        )
    try:
        # ~6 boot-death cycles, each paying a fresh jax import (~20s on a
        # loaded 1-core host) before the ~2.5s crash; without the
        # readiness classification this NEVER exits (each death looks
        # like a runtime death and resets the throttle)
        rc = proc.wait(timeout=420)
        assert rc != 0
        assert "boot" in errlog.read_text()
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass


def test_arbiter_drain_on_sigterm_finishes_inflight(
    model_collection_directory, trained_model_directories, tmp_path,
    gordo_project, gordo_name, X_payload,
):
    """Graceful drain (PR 3): SIGTERM to the arbiter forwards TERM to the
    workers, which stop accepting, FINISH the in-flight request (a fault
    plan wedges it for several seconds), and exit — the whole pool shuts
    down rc=0 and the listener is closed afterwards."""
    import threading

    from gordo_tpu.server.utils import dataframe_to_dict

    port = _free_port()
    env = {
        "PATH": os.environ.get("PATH", ""),
        "HOME": os.environ.get("HOME", "/root"),
        "JAX_PLATFORMS": "cpu",
        "MODEL_COLLECTION_DIR": model_collection_directory,
        "PROJECT": "gordo-test",
        # hold the in-flight request inside the handler long enough that
        # SIGTERM provably lands mid-request (first predict only)
        "GORDO_TPU_FAULT_PLAN": json.dumps(
            {"rules": [{"site": "serve_predict", "times": 1,
                        "error": "wedge", "seconds": 6}]}
        ),
        # the wedged request also pays its first-predict compile; the
        # drain budget must outlast it on a loaded CPU host
        "GORDO_TPU_DRAIN_S": "180",
    }
    errlog = tmp_path / "drain-stderr.log"
    with open(errlog, "w") as errfh:
        proc = subprocess.Popen(
            [sys.executable, "-c",
             _SERVER_SCRIPT.format(repo=REPO, port=port, workers=2,
                                   warmup=False)],
            env=env, stdout=subprocess.DEVNULL, stderr=errfh,
            start_new_session=True,
        )
    base = f"http://127.0.0.1:{port}"
    try:
        assert _wait_for(
            lambda: _get(f"{base}/healthcheck", timeout=5)[0] == 200,
            timeout=120,
        ), f"pool never came up: {errlog.read_text()[-2000:]}"

        url = (
            f"{base}/gordo/v0/{gordo_project}/{gordo_name}"
            f"/anomaly/prediction"
        )
        frame = dataframe_to_dict(X_payload)
        result = {}

        def inflight():
            try:
                result["resp"] = _post_json(
                    url, {"X": frame, "y": frame}, timeout=240
                )
            except BaseException as exc:  # noqa: BLE001
                result["error"] = exc

        t = threading.Thread(target=inflight)
        t.start()
        time.sleep(2.0)  # the request is wedged inside a worker
        assert proc.poll() is None
        os.kill(proc.pid, signal.SIGTERM)  # the ARBITER only

        t.join(timeout=240)
        assert not t.is_alive(), "in-flight request never completed"
        assert "error" not in result, (
            f"in-flight request cut during drain: {result['error']!r}; "
            f"stderr: {errlog.read_text()[-2000:]}"
        )
        status, body = result["resp"]
        assert status == 200
        assert json.loads(body)["data"]

        # the whole pool exits cleanly within the drain budget
        rc = proc.wait(timeout=240)
        assert rc == 0, f"stderr: {errlog.read_text()[-2000:]}"
        assert "draining" in errlog.read_text()

        # listener closed: nothing accepts on the port anymore
        with pytest.raises((urllib.error.URLError, OSError)):
            _get(f"{base}/healthcheck", timeout=5)
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass


# ------------------------------------------------- one worker per TPU chip
def test_chip_count_needs_both_the_bus_and_a_device_file(monkeypatch):
    """A sealed one-chip machine lists four chips on PCI but hands out one
    device file (seen on the chip tool's machine); a VFIO group without a
    TPU on the bus is not a chip; and a process held off the TPU counts
    none, because no worker will take one."""
    from gordo_tpu.util import chips

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(chips, "_chips_on_pci", lambda: 4)
    monkeypatch.setattr(chips, "_chip_device_files", lambda: 1)
    assert chips.attached_tpu_chips() == 1
    monkeypatch.setattr(chips, "_chip_device_files", lambda: 4)
    assert chips.attached_tpu_chips() == 4
    monkeypatch.setattr(chips, "_chips_on_pci", lambda: 0)
    assert chips.attached_tpu_chips() == 0
    monkeypatch.setattr(chips, "_chips_on_pci", lambda: 4)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    assert chips.attached_tpu_chips() == 4
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert chips.attached_tpu_chips() == 0


def test_pool_is_one_worker_per_chip_or_refused(monkeypatch):
    from gordo_tpu.server.server import resolve_workers
    from gordo_tpu.util import chips

    monkeypatch.setattr(chips, "attached_tpu_chips", lambda: 4)
    assert resolve_workers(None) == (4, 4)   # the default: one per chip
    assert resolve_workers(2) == (2, 4)      # fewer is allowed
    monkeypatch.setattr(chips, "attached_tpu_chips", lambda: 1)
    assert resolve_workers(None) == (1, 1)
    # more workers than chips could never start: refused, naming the count
    with pytest.raises(ValueError, match="1 TPU chip"):
        resolve_workers(2)
    monkeypatch.setattr(chips, "attached_tpu_chips", lambda: 0)
    assert resolve_workers(None) == (2, 0)   # no chips: the old default
    assert resolve_workers(3) == (3, 0)


def test_run_server_cli_refuses_more_workers_than_chips(monkeypatch):
    from click.testing import CliRunner

    from gordo_tpu.cli.cli import gordo
    from gordo_tpu.util import chips

    monkeypatch.setattr(chips, "attached_tpu_chips", lambda: 1)
    result = CliRunner().invoke(gordo, ["run-server", "--workers", "2"])
    assert result.exit_code == 2
    assert "this host has 1 TPU chip" in result.output


def test_pinning_describes_a_one_chip_topology(monkeypatch):
    from gordo_tpu.util import chips

    for name in ("TPU_VISIBLE_CHIPS", "TPU_PROCESS_BOUNDS", "TPU_PROCESS_PORT"):
        monkeypatch.delenv(name, raising=False)
    monkeypatch.setattr(os, "environ", dict(os.environ))
    chips.pin_process_to_chip(2)
    assert os.environ["TPU_VISIBLE_CHIPS"] == "2"
    assert os.environ["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert os.environ["TPU_PROCESS_BOUNDS"] == "1,1,1"
    # distinct ports, so four pinned workers never meet
    assert os.environ["TPU_PROCESS_PORT"] == "8478"


_UNCOUNTED_CHIP_SCRIPT = """
import logging, os, sys
logging.basicConfig(level=logging.INFO, stream=sys.stderr)
sys.path.insert(0, {repo!r})
import jax
jax.config.update("jax_platforms", "cpu")
from gordo_tpu.observability import device
from gordo_tpu.server.server import ChipLayoutError, run_server
from gordo_tpu.util import chips
# the launcher counts no chip, yet every worker's jax reports a TPU
chips.attached_tpu_chips = lambda: 0
device.describe = lambda: dict(
    platform="tpu", device_kind="TPU v5 lite", device_count=1
)
try:
    run_server(host="127.0.0.1", port={port}, workers=2, warmup=False)
except ChipLayoutError as exc:
    print("REFUSED:", exc, flush=True)
    sys.exit(3)
"""


def test_pool_stops_when_a_worker_finds_chips_the_launcher_did_not_count(
    tmp_path,
):
    """The arbiter stays off jax and counts chips from sysfs; where that
    reads 0 on a real TPU host it starts unpinned workers and only one can
    hold the chips. The worker that does says so and the whole pool exits
    with the chip count — no respawn loop behind a pool that reports up."""
    script = tmp_path / "serve.py"
    script.write_text(
        _UNCOUNTED_CHIP_SCRIPT.format(repo=REPO, port=_free_port())
    )
    proc = subprocess.run(
        [sys.executable, str(script)], capture_output=True, text=True,
        timeout=120, env={**os.environ, "MODEL_COLLECTION_DIR": str(tmp_path)},
    )
    assert proc.returncode == 3, proc.stderr[-2000:]
    assert "REFUSED: this worker found 1 TPU chip(s) (TPU v5 lite)" in proc.stdout
    assert "launcher counted 0" in proc.stdout
    assert "run with --workers 1" in proc.stdout
    # stopped at the first report: nothing was respawned
    assert "spawning replacement" not in proc.stderr
    assert "retrying one respawn" not in proc.stderr
