"""
The quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user calls, at the
full width of the paper's headline configuration, on one TPU chip (or one
host of them), and checks what comes out:

- *kernel*: the Pallas flash-attention kernel, forward and backward,
  compiled by Mosaic (never interpreted) against the XLA reference at the
  corners of the dispatch gate ``ops/attention._flash_ok``, under ``vmap`` as
  the fleet trainer calls it, and from a model config (``attention: flash``)
  through ``ModelBuilder``;
- *build*: YAML config -> ``gordo-tpu batch-build --fail-fast
  --no-serial-fallback`` -> 1,024 hourglass artifacts, no serial fallback,
  no quarantine, no fleet compile failure, every device of the mesh used;
- *serve*: ``gordo-tpu run-server --warmup`` (default workers: one per
  chip) on that collection, a few dozen anomaly POSTs over several machines,
  200s with finite scores, and no trace compile after warmup;
- *windowed*: an 8-machine bf16 LSTM fleet at the bench's widths, built and
  predicted once.

A chip belongs to one process at a time. This parent process never imports
jax: it runs the legs one after another. The kernel and windowed legs compute
in a child each; the build and serve legs start the product's own command,
which is then the one process on the chip, and read where it ran from what
that process says (its start-up INFO line, its metrics file, ``/debug/vars``).

Weights are random (a seed) and depth is cut (few epochs); widths are not.
Without a TPU the script exits non-zero and prints no result. The last line
of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

``python chip_smoke.py --legs kernel,windowed`` runs a subset (not a pass of
the smoke: the result line is only printed for the full set).
"""

import argparse
import contextlib
import functools
import glob
import http.client
import json
import math
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
LEGS = ("kernel", "build", "serve", "windowed")
# legs that compute in this file's own child process; the others start a
# product command that holds the chip, so their driver must stay off jax
_JAX_LEGS = ("kernel", "windowed")

# ------------------------------------------------------------------ sizes
# (T, head dim): both ends of the T range and both head dims that
# ops/attention._flash_ok admits (one width for q, k and v: a kernel for heads
# of 192 / 128 was compiled and compared on the chip, lost to the XLA path and
# was taken out again, so there is no corner for it here; PERF.md section 6)
FLASH_CORNERS = ((256, 64), (256, 128), (4096, 64), (4096, 128))
# the config-driven kernel check: one Transformer machine whose attention is
# the kernel (d_model / num_heads = head dim 64, lookback = T = 256)
FLASH_MACHINE = {
    "d_model": 256, "num_heads": 4, "ff_dim": 256, "num_blocks": 1,
    "lookback_window": 256, "batch_size": 64, "epochs": 1,
    # 3-fold TimeSeriesSplit: the first fold trains on a quarter of the rows,
    # which must exceed the lookback — four weeks of 10-minute samples
    "train_end_date": "2019-01-29T00:00:00+00:00",
}
# the paper's headline configuration (examples/config.yaml's first
# machine): 4 tags, 7 days of 10-minute rows = 1,008
BUILD = {"machines": 1024, "epochs": 5, "tags": 4}
SERVE = {"posts": 48, "machines": 8, "rows": 100, "clients": 8}
# one sequence family, cut to start quickly: LSTM [64, 32] on 8 tags (the
# benchmark's cell runs the published widths, chipbench/configs/)
WINDOWED = {
    "machines": 8, "dims": [64, 32], "tags": 8, "lookback_window": 144,
    "batch_size": 64, "epochs": 1, "compute_dtype": "bfloat16",
}

# Tolerances of kernel against reference, relative to the reference's largest
# magnitude. The reference runs under jax.default_matmul_precision("highest")
# — on a TPU an f32 einsum at default precision is ONE bf16 pass, so without
# it the "reference" would be the less accurate side. The kernel keeps jax's
# default precision, as in production, and Mosaic's default for its f32
# matmuls is also a bf16 pass: so for f32 inputs it agrees with a true-f32
# reference to a few bf16 roundings (2^-8), not to f32 epsilon. Measured on a
# v5e over these corners (chip run, PR 21): f32 forward <= 0.0047, gradients
# <= 0.011; bf16 inputs, which add the rounding of the bf16 output and of the
# reference's bf16 softmax weights, forward <= 0.011, gradients <= 0.024.
# Traced under "highest" itself the same kernel is within 3e-7 / 6e-5, i.e.
# the gap is rounding, not arithmetic. The bounds leave a factor of two.
FLASH_TOLERANCE = {"float32": 2e-2, "bfloat16": 4e-2}


# ----------------------------------------------------------- jax-side legs
def require_tpu() -> dict:
    """The device as jax reports it; exits non-zero unless it is a TPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: jax reports platform {devices[0].platform!r}, not "
            f"'tpu'; this smoke proves the program on the chip and has "
            f"nothing to say without one"
        )
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


# report key -> ``source`` label of gordo_build_xla_compiles_total
_COMPILE_SOURCES = {"compiles": "compiled", "cache_hits": "persistent_cache"}
# ``stage`` labels of gordo_build_xla_compile_seconds_total that the backend
# spent: compiling a program, or loading it from the persistent cache
_BACKEND_STAGES = ("compile", "load")


def _compile_counts() -> dict:
    """What this process compiled so far, from jax's own monitoring events
    (util/xla_cache.py feeds them into the telemetry registry)."""
    from gordo_tpu.observability import metrics as metric_catalog

    return {
        **{
            key: int(metric_catalog.XLA_COMPILES.value(source=source))
            for key, source in _COMPILE_SOURCES.items()
        },
        "compile_wall_s": round(
            sum(metric_catalog.XLA_COMPILE_SECONDS.value(stage=s) for s in _BACKEND_STAGES),
            2,
        ),
    }


def _rel_err(got, ref) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    if not np.all(np.isfinite(got)):
        raise AssertionError("kernel produced non-finite values")
    return float(np.max(np.abs(got - ref)) / (np.max(np.abs(ref)) + 1e-9))


def flash_vs_reference(
    t: int, dh: int, causal: bool, dtype: str, interpret: bool = False,
    vmap_machines: int = 0,
) -> dict:
    """Flash attention forward and backward at one shape against
    ``dot_product_attention_xla``; raises unless both agree within
    ``FLASH_TOLERANCE``. ``vmap_machines`` > 0 runs both under ``jax.vmap``
    over a leading machine axis, as the fleet trainer does."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gordo_tpu.ops.attention import dot_product_attention_xla
    from gordo_tpu.ops.pallas_kernels.flash_attention import flash_attention

    rng = np.random.RandomState(t + dh)
    shape = (1, 2, t, dh)  # (batch, heads, T, head dim)
    if vmap_machines:
        shape = (vmap_machines,) + shape
    q, k, v = (
        jnp.asarray(rng.standard_normal(shape), jnp.float32).astype(dtype)
        for _ in range(3)
    )

    def value_and_grads(attention):
        def loss(q, k, v):
            out = attention(q, k, v)
            return jnp.sum(out.astype(jnp.float32) ** 2), out

        fn = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)
        if vmap_machines:
            fn = jax.vmap(fn)
        (_, out), grads = jax.jit(fn)(q, k, v)
        return out, grads

    out, grads = value_and_grads(
        functools.partial(flash_attention, causal=causal, interpret=interpret)
    )
    with jax.default_matmul_precision("highest"):
        ref_out, ref_grads = value_and_grads(
            functools.partial(dot_product_attention_xla, causal=causal)
        )
    fwd = _rel_err(out, ref_out)
    grad = max(_rel_err(g, r) for g, r in zip(grads, ref_grads))
    tolerance = FLASH_TOLERANCE[dtype]
    if not (fwd < tolerance and grad < tolerance):
        raise AssertionError(
            f"flash attention disagrees with the reference at T={t} dh={dh} "
            f"causal={causal} {dtype}: fwd {fwd:.3g}, grad {grad:.3g} "
            f"(tolerance {tolerance})"
        )
    return {"fwd_rel_err": float(f"{fwd:.3g}"),
            "grad_rel_err": float(f"{grad:.3g}")}


def _machine(name: str, estimator: str, kwargs: dict, tags: int,
             train_end: str = "2019-01-08T00:00:00+00:00") -> dict:
    """One machine in the shape of examples/config.yaml: random data, a
    MinMaxScaler + estimator pipeline under the diff-based anomaly detector
    with thresholds (3-fold CV is the evaluation default)."""
    return {
        "name": name,
        "dataset": {
            "tags": [f"{name}-tag-{j}" for j in range(tags)],
            "train_start_date": "2019-01-01T00:00:00+00:00",
            "train_end_date": train_end,
            "data_provider": {"type": "RandomDataProvider"},
        },
        "model": {
            "gordo_tpu.models.anomaly.diff.DiffBasedAnomalyDetector": {
                "require_thresholds": True,
                "base_estimator": {
                    "sklearn.pipeline.Pipeline": {
                        "steps": [
                            "sklearn.preprocessing.MinMaxScaler",
                            {estimator: kwargs},
                        ]
                    }
                },
            }
        },
    }


def flash_machine_from_config(machine: dict, interpret: bool = False) -> dict:
    """Build one ``transformer_model`` machine with ``attention: flash``
    through ``ModelBuilder`` — the kernel reached from a config — and predict
    with it once."""
    import numpy as np
    from jax.experimental.pallas import tpu as pltpu

    from gordo_tpu.builder.build_model import ModelBuilder
    from gordo_tpu.machine import Machine

    machine = dict(machine)
    train_end = machine.pop("train_end_date")
    config = _machine(
        "smoke-flash",
        "gordo_tpu.models.models.TransformerAutoEncoder",
        {"kind": "transformer_model", "attention": "flash", **machine},
        tags=4, train_end=train_end,
    )
    # the kernel itself takes no interpret argument from a config: a CPU
    # rehearsal asks pallas for its interpreter around the whole build
    scope = pltpu.force_tpu_interpret_mode() if interpret else (
        contextlib.nullcontext()
    )
    t0 = time.time()
    with scope:
        model, _ = ModelBuilder(
            Machine.from_config(config, project_name="smoke")
        ).build()
        rows = np.random.RandomState(0).random_sample(
            (machine["lookback_window"] + 16, 4)
        ).astype(np.float32)
        scores = model.anomaly(_frame(rows, config), _frame(rows, config))
    _assert_finite_frame(scores)
    return {"build_and_predict_s": round(time.time() - t0, 1),
            "anomaly_rows": len(scores)}


def _frame(rows, machine_config: dict):
    import pandas as pd

    index = pd.date_range(
        "2019-02-01", periods=len(rows), freq="10min", tz="UTC"
    )
    return pd.DataFrame(
        rows, index=index, columns=machine_config["dataset"]["tags"]
    )


# the column groups of an anomaly frame that come straight from the model
# and its scaler; the confidence columns divide by thresholds, which a tiny
# rehearsal's few rows cannot give
SCORE_COLUMNS = ("model-output", "tag-anomaly-scaled", "total-anomaly-scaled")


def _assert_finite_frame(frame) -> None:
    import numpy as np

    values = frame[list(SCORE_COLUMNS)].to_numpy(np.float64)
    if values.size == 0 or not np.all(np.isfinite(values)):
        raise AssertionError("anomaly scores are missing or not finite")


def kernel_leg(corners=FLASH_CORNERS, machine=FLASH_MACHINE,
               interpret: bool = False) -> dict:
    """Every (T, head dim) corner, causal and full, f32 and bf16; the first
    corner once more under vmap; then the kernel from a model config."""
    shapes = {}
    for t, dh in corners:
        for dtype in ("float32", "bfloat16"):
            for causal in (False, True):
                key = f"T{t}_dh{dh}_{dtype}_{'causal' if causal else 'full'}"
                shapes[key] = flash_vs_reference(
                    t, dh, causal, dtype, interpret=interpret
                )
    t, dh = corners[0]
    shapes[f"T{t}_dh{dh}_float32_causal_vmap2"] = flash_vs_reference(
        t, dh, True, "float32", interpret=interpret, vmap_machines=2
    )
    return {
        "shapes": shapes,
        "from_config": flash_machine_from_config(machine, interpret),
    }


def windowed_leg(sizes=WINDOWED) -> dict:
    """A small fleet of one sequence family (LSTM autoencoder, the scan
    program) in the bench's compute dtype: built by the fleet trainer, no
    serial fallback, then one anomaly prediction."""
    import numpy as np

    from gordo_tpu.machine import Machine
    from gordo_tpu.parallel import BatchedModelBuilder

    sizes = dict(sizes)
    n, tags = sizes.pop("machines"), sizes.pop("tags")
    configs = [
        _machine(
            f"smoke-lstm-{i}",
            "gordo_tpu.models.models.LSTMAutoEncoder",
            {"kind": "lstm_symmetric",
             "funcs": ["tanh"] * len(sizes["dims"]), **sizes},
            tags=tags,
        )
        for i in range(n)
    ]
    builder = BatchedModelBuilder(
        [Machine.from_config(c, project_name="smoke") for c in configs],
        serial_fallback=False, fail_fast=True,
    )
    t0 = time.time()
    results = builder.build()
    build_s = time.time() - t0
    if len(results) != n or builder.compile_failures:
        raise AssertionError(
            f"windowed fleet built {len(results)}/{n} machines, compile "
            f"failures: {builder.compile_failures}"
        )
    model, _ = results[0]
    rows = np.random.RandomState(0).random_sample(
        (sizes["lookback_window"] + 16, tags)
    ).astype(np.float32)
    scores = model.anomaly(_frame(rows, configs[0]), _frame(rows, configs[0]))
    _assert_finite_frame(scores)
    return {"machines": n, "build_s": round(build_s, 1),
            "anomaly_rows": len(scores)}


def _run_jax_leg(name: str) -> dict:
    """Child entry of a leg that computes here: take the chip (and fail
    without one), place the compile cache, run, report from inside."""
    device = require_tpu()
    from gordo_tpu.util.xla_cache import setup_persistent_xla_cache

    setup_persistent_xla_cache()
    t0 = time.time()
    result = {"kernel": kernel_leg, "windowed": windowed_leg}[name]()
    return {"leg": name, **device, "wall_s": round(time.time() - t0, 1),
            **_compile_counts(), **result}


# ------------------------------------------- legs that start the product
_PLACEMENT = re.compile(
    r"running on platform=(\w+) device_kind='([^']*)' device_count=(\d+)"
)


def _placements(log_text: str) -> list:
    """Where product processes ran, from the INFO line each prints at
    start (observability/device.log_placement)."""
    return [
        {"platform": platform, "kind": kind, "count": int(count)}
        for platform, kind, count in _PLACEMENT.findall(log_text)
    ]


def _prom_values(text: str) -> dict:
    """A Prometheus textfile as ``{series: value}``, a series being the
    metric name with its ``{labels}`` if it has any."""
    values: dict = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            series, _, value = line.rpartition(" ")
            values[series] = float(value)
    return values


def _prom_sum(values: dict, name: str, label: str = "") -> float:
    """A metric summed over its series, or over those whose labels contain
    ``label`` (``source="compiled"``)."""
    return sum(
        value for series, value in values.items()
        if series.split("{", 1)[0] == name and label in series
    )


def _gordo(*args) -> list:
    return [sys.executable, "-m", "gordo_tpu.cli.cli", *args]


def write_build_config(path: str, machines: int, epochs: int, tags: int):
    import yaml

    config = {
        "machines": [
            _machine(
                f"smoke-m-{i:04d}",
                "gordo_tpu.models.models.AutoEncoder",
                {"kind": "feedforward_hourglass", "epochs": epochs,
                 "batch_size": 128},
                tags=tags,
            )
            for i in range(machines)
        ]
    }
    with open(path, "w") as fh:
        yaml.safe_dump(config, fh)


def build_leg(workdir: str, sizes=BUILD) -> dict:
    """``batch-build`` of the headline fleet as a user runs it. The command
    is the one process on the chip; everything asserted here comes from its
    exit code, its artifacts, its start-up line and its metrics file."""
    config = os.path.join(workdir, "config.yaml")
    collection = os.path.join(workdir, "collection")
    metrics_file = os.path.join(workdir, "build.prom")
    report_file = os.path.join(workdir, "quarantine.json")
    log_file = os.path.join(workdir, "build.log")
    write_build_config(config, **sizes)
    t0 = time.time()
    with open(log_file, "w") as log:
        proc = subprocess.run(
            _gordo(
                "batch-build", config, "--output-dir", collection,
                "--project-name", "smoke", "--fail-fast",
                "--no-serial-fallback", "--metrics-file", metrics_file,
                "--quarantine-report-file", report_file,
            ),
            cwd=REPO, stdout=log, stderr=subprocess.STDOUT, timeout=1100,
        )
    wall = time.time() - t0
    with open(log_file) as fh:
        log_text = fh.read()
    if proc.returncode != 0:
        raise AssertionError(
            f"batch-build exited {proc.returncode}:\n{log_text[-3000:]}"
        )
    (device,) = _placements(log_text)
    artifacts = [
        d for d in glob.glob(os.path.join(collection, "*"))
        if os.path.exists(os.path.join(d, "metadata.json"))
    ]
    with open(metrics_file) as fh:
        metrics = _prom_values(fh.read())
    with open(report_file) as fh:
        report = json.load(fh)
    checks = {
        "artifacts": len(artifacts),
        "serial_fallbacks": _prom_sum(
            metrics, "gordo_build_serial_fallbacks_total"
        ),
        "quarantined": len(report["quarantined"]),
        "fleet_compile_failures": len(report["fleet_compile_failures"]),
        # stacked data and params laid out over every device of the mesh
        "shard_devices": int(metrics["gordo_build_fleet_shard_devices"]),
    }
    expected = {
        "artifacts": sizes["machines"], "serial_fallbacks": 0,
        "quarantined": 0, "fleet_compile_failures": 0,
        "shard_devices": device["count"],
    }
    if checks != expected:
        raise AssertionError(f"build leg: {checks}, expected {expected}")
    return {
        "leg": "build", **device, "wall_s": round(wall, 1),
        **{
            key: int(_prom_sum(
                metrics, "gordo_build_xla_compiles_total",
                f'source="{source}"',
            ))
            for key, source in _COMPILE_SOURCES.items()
        },
        "compile_wall_s": round(
            sum(
                _prom_sum(metrics, "gordo_build_xla_compile_seconds_total", f'stage="{s}"')
                for s in _BACKEND_STAGES
            ),
            2,
        ),
        **checks,
    }


def _free_port() -> int:
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]


def _get_json(port: int, path: str, timeout: float = 60):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        response = conn.getresponse()
        body = response.read()
    finally:
        conn.close()
    return response.status, (json.loads(body) if body else None)


def _metric(debug_vars: dict, name: str, label: str = None) -> float:
    """A counter summed over its series, or over those carrying the label
    value ``label`` — over every worker of a pool (the telemetry shards'
    merged view) when there is more than one."""
    if debug_vars["fleet"]:
        merged = debug_vars["fleet"]["merged"].get(name, {})
        return sum(
            value for labels, value in merged.get("series", {}).items()
            if label is None or label in labels.split(",")
        )
    series = debug_vars["metrics"].get(name, {}).get("series", [])
    return sum(
        s["value"] for s in series
        if label is None or label in s["labels"].values()
    )


def _all_finite(node) -> bool:
    """Every leaf of a decoded JSON column group is a finite number."""
    if isinstance(node, dict):
        return bool(node) and all(_all_finite(v) for v in node.values())
    if isinstance(node, list):
        return bool(node) and all(_all_finite(v) for v in node)
    return isinstance(node, (int, float)) and math.isfinite(node)


def serve_leg(workdir: str, sizes=SERVE, tags: int = BUILD["tags"]) -> dict:
    """``run-server --warmup`` with its default workers on the collection the
    build leg left, then concurrent anomaly POSTs over several machines."""
    import numpy as np

    collection = os.path.join(workdir, "collection")
    names = sorted(
        name for name in os.listdir(collection)
        if os.path.exists(os.path.join(collection, name, "metadata.json"))
    )[: sizes["machines"]]
    port = _free_port()
    log_file = os.path.join(workdir, "server.log")
    env = {
        **os.environ,
        "MODEL_COLLECTION_DIR": collection,
        "PROJECT": "smoke",
        "GORDO_TPU_DEBUG_ENDPOINTS": "1",
        # a pool's workers publish their counters on every request, so the
        # merged view read below is current
        "GORDO_TPU_TELEMETRY_FLUSH_S": "0",
    }
    t0 = time.time()
    with open(log_file, "w") as log:
        server = subprocess.Popen(
            _gordo("run-server", "--host", "127.0.0.1", "--port", str(port),
                   "--warmup"),
            cwd=REPO, env=env, stdout=log, stderr=subprocess.STDOUT,
            # a group of its own, so that arbiter AND workers can be stopped
            start_new_session=True,
        )
    try:
        # a worker warms up before it accepts; ready means every worker of
        # the pool said where it runs and finished its warmup
        deadline = time.time() + 1000
        while True:
            if server.poll() is not None:
                raise AssertionError(
                    f"run-server exited {server.returncode} during boot"
                )
            with open(log_file) as fh:
                log_text = fh.read()
            if "worker failed to boot/serve" in log_text:
                raise AssertionError("a run-server worker failed to boot")
            pool = re.search(r"Starting server on \S+ with (\d+) worker", log_text)
            if pool and log_text.count("serving warmup:") == int(pool.group(1)):
                try:
                    if _get_json(port, "/healthcheck", timeout=5)[0] == 200:
                        break
                except OSError:
                    pass
            if time.time() > deadline:
                raise AssertionError("run-server did not come up in 1000s")
            time.sleep(1.0)
        boot_s = time.time() - t0
        workers = _placements(log_text)
        if len(workers) != int(pool.group(1)):
            raise AssertionError(
                f"{pool.group(1)} workers, {len(workers)} placement lines"
            )
        before = _get_json(port, "/debug/vars")[1]
        warm = before["warmup"]
        if warm is None or warm["failed"] or _metric(
            before, "gordo_server_warmup_failures_total"
        ):
            raise AssertionError(f"serving warmup failed: {warm}")

        rng = np.random.RandomState(0)
        body = json.dumps(
            {"X": rng.random_sample((sizes["rows"], tags)).tolist(),
             "y": rng.random_sample((sizes["rows"], tags)).tolist()}
        )
        targets = [names[i % len(names)] for i in range(sizes["posts"])]
        failures: list = []

        def client(mine):
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
            try:
                for name in mine:
                    conn.request(
                        "POST",
                        f"/gordo/v0/smoke/{name}/anomaly/prediction",
                        body=body,
                        headers={"Content-Type": "application/json"},
                    )
                    response = conn.getresponse()
                    payload = response.read()
                    if response.status != 200:
                        failures.append((name, response.status, payload[:200]))
                    else:
                        data = json.loads(payload)["data"]
                        if not all(_all_finite(data.get(c)) for c in SCORE_COLUMNS):
                            failures.append((name, "non-finite scores"))
            except Exception as exc:  # noqa: BLE001 — joined and re-raised
                failures.append((mine, repr(exc)))
            finally:
                conn.close()

        threads = [
            threading.Thread(target=client, args=(targets[i::sizes["clients"]],))
            for i in range(sizes["clients"])
        ]
        t1 = time.time()
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join(timeout=600)
        posts_s = time.time() - t1
        if failures or any(t.is_alive() for t in threads):
            raise AssertionError(f"anomaly POSTs failed: {failures[:5]}")

        after = _get_json(port, "/debug/vars")[1]
        trace_compiles = (
            _metric(before, "gordo_server_trace_compiles_total"),
            _metric(after, "gordo_server_trace_compiles_total"),
        )
        if trace_compiles[0] != trace_compiles[1]:
            raise AssertionError(
                f"serving traced and compiled after warmup: "
                f"gordo_server_trace_compiles_total {trace_compiles}"
            )
        # the worker that answered must agree with what the pool logged
        answering = {
            "platform": after["device"]["platform"],
            "kind": after["device"]["device_kind"],
            "count": after["device"]["device_count"],
        }
        if answering not in workers:
            raise AssertionError(f"/debug/vars says {answering}, log {workers}")
        # every device held by exactly one worker: the pool's device count
        device = {**answering, "count": sum(w["count"] for w in workers)}
    except BaseException:
        with open(log_file) as fh:
            sys.stderr.write(fh.read()[-4000:])
        raise
    finally:
        _stop_group(server)
    with open(log_file) as fh:
        decisions = re.findall(r"serving batcher self-A/B for .*", fh.read())
    return {
        "leg": "serve", **device, "wall_s": round(time.time() - t0, 1),
        "boot_and_warmup_s": round(boot_s, 1),
        **{
            key: int(_metric(after, "gordo_build_xla_compiles_total", source))
            for key, source in _COMPILE_SOURCES.items()
        },
        "compile_wall_s": round(
            sum(
                _metric(after, "gordo_build_xla_compile_seconds_total", s)
                for s in _BACKEND_STAGES
            ),
            2,
        ),
        "workers": len(workers),
        "devices_per_worker": answering["count"],
        "warmup": {k: warm[k] for k in
                   ("models", "programs", "aot_programs", "seconds")},
        "posts": sizes["posts"], "post_machines": len(names),
        "posts_s": round(posts_s, 2),
        "trace_compiles_after_warmup": trace_compiles[1] - trace_compiles[0],
        # printed, not judged: the auto mode measures and may stand down
        "batcher": after["batcher"],
        "batcher_self_ab": decisions,
    }


def _stop_group(server: subprocess.Popen) -> None:
    """Stop run-server and every worker it forked: SIGTERM drains, a second
    SIGTERM makes a worker that is still draining exit at once
    (server.py's drain handler), SIGKILL is the last resort — no process
    may outlive the smoke and keep the chip."""
    for sig, wait in ((signal.SIGTERM, 20), (signal.SIGTERM, 10),
                      (signal.SIGKILL, 10)):
        try:
            os.killpg(server.pid, sig)
        except ProcessLookupError:
            break
        try:
            server.wait(timeout=wait)
        except subprocess.TimeoutExpired:
            continue
    server.wait()


# ------------------------------------------------------------------ parent
def _run_leg(name: str, workdir: str) -> dict:
    if name in _JAX_LEGS:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child", name],
            cwd=REPO, stdout=subprocess.PIPE, text=True, timeout=1100,
        )
        if proc.returncode != 0:
            raise SystemExit(
                f"chip_smoke: leg {name} failed (exit {proc.returncode})"
            )
        return json.loads(proc.stdout.strip().splitlines()[-1])
    return {"build": build_leg, "serve": serve_leg}[name](workdir)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--legs", default=",".join(LEGS))
    parser.add_argument("--child", help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    if args.child:
        print(json.dumps(_run_jax_leg(args.child)))
        return 0

    legs = [leg for leg in args.legs.split(",") if leg]
    unknown = set(legs) - set(LEGS)
    if unknown:
        parser.error(f"unknown legs {sorted(unknown)}; choose from {LEGS}")
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        print(
            f"chip_smoke: JAX_PLATFORMS={platforms} keeps jax off the TPU; "
            f"this smoke does not run without one", file=sys.stderr,
        )
        return 1
    if not os.path.isdir(os.path.join(REPO, "gordo_tpu")):
        print(f"chip_smoke: no gordo_tpu package beside {__file__}",
              file=sys.stderr)
        return 1
    from gordo_tpu.util import xla_cache  # imports no jax

    cache = {"dir": xla_cache.cache_dir()}
    cache["entries_before"] = xla_cache.cache_stats(cache["dir"])[0]
    workdir = tempfile.mkdtemp(prefix="chip-smoke-")
    # what is too long for the end of the output is kept here, leg by leg
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)

    def emit(record: dict) -> None:
        line = json.dumps(record)
        print(line, flush=True)
        with open(os.path.join(out_dir, "chip_smoke.jsonl"), "a") as fh:
            fh.write(line + "\n")

    reports = []
    t0 = time.time()
    try:
        for name in legs:
            reports.append(_run_leg(name, workdir))
            emit(reports[-1])
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    cache["entries_after"] = xla_cache.cache_stats(cache["dir"])[0]

    device = {k: reports[0][k] for k in ("platform", "kind", "count")}
    for report in reports:
        here = {k: report[k] for k in ("platform", "kind", "count")}
        if here != device or here["platform"] != "tpu":
            print(f"chip_smoke: leg {report['leg']} ran on {here}, the first "
                  f"leg on {device}", file=sys.stderr)
            return 1
    summary = {
        "wall_s": round(time.time() - t0, 1),
        "compile_wall_s": round(sum(r["compile_wall_s"] for r in reports), 1),
        "compiles": sum(r["compiles"] for r in reports),
        "cache_hits": sum(r["cache_hits"] for r in reports),
        "cache": cache,
    }
    emit({"summary": summary})
    if legs != list(LEGS):
        print(f"chip_smoke: ran only {legs}; no result without every leg",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
