"""One run of one cell:

    python -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process: it holds the chip, calls the fleet trainer in-process through
the entry ``batch-build`` itself calls, and (with ``--trace 1``) opens and
closes the profiler session itself. The last line of standard output is the
result. See ``chipbench/README.md``.
"""

import time

_PROCESS_START = time.time()

import argparse
import dataclasses
import gc
import importlib
import json
import os
import shutil
import sys
import tempfile
from typing import Dict, List, Optional

import numpy as np

from chipbench import check, reference, trace, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Refused(SystemExit):
    """The run cannot produce a result line; exit non-zero with the reason."""

    def __init__(self, reason: str):
        print(f"chipbench: {reason}", file=sys.stderr)
        super().__init__(2)


# ------------------------------------------------------------------ the cell
def load_cell(manifest_path: str, workload: str) -> dict:
    with open(manifest_path) as fh:
        manifest = json.load(fh)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in {manifest_path}; has {sorted(cells)}")
    cell = cells[workload]
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as fh:
        config = json.load(fh)

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    return {
        "name": workload,
        "chips": int(cell["chips"]),
        "config": config,
        "traffic": traffic.Traffic.load(cell["traffic"]),
        "file": traffic.load_json("workloads", f"{workload}.json"),
        "end_to_end": [m for m in manifest["end_to_end"] if applies(m)],
        "per_layer": [m for m in manifest["per_layer"] if applies(m)],
    }


# ------------------------------------------- what is taken from the program
PROGRAM_SERIES = "gordo_build_"


def program_counters() -> Dict[str, float]:
    """A snapshot of the program's own counters and phase sums: the keys the
    harness itself reads, and every series of the program's build catalog
    (``gordo_build_*`` in its default registry, whoever registered it) under
    ``<name>{label=value,…}`` (the bare name where it has no labels):
    counters and gauges as their values, a histogram as its sum."""
    from gordo_tpu.observability import metrics as catalog
    from gordo_tpu.observability import telemetry

    out = {
        "compiles": catalog.XLA_COMPILES.value(source="compiled"),
        "oom_bisections": catalog.OOM_BISECTIONS.value(),
        "bucket_retries": catalog.BUCKET_RETRIES.value(),
        "serial_fallbacks": sum(v for _, v in catalog.SERIAL_FALLBACKS.snapshot()),
    }
    for (phase,), (_, total) in catalog.BUILD_PHASE_SECONDS.snapshot():
        out[f"phase_s.{phase}"] = total
    for metric in telemetry.default_registry().collect():
        if not metric.name.startswith(PROGRAM_SERIES):
            continue
        for labels, value in metric.snapshot():
            pairs = ",".join(f"{k}={v}" for k, v in zip(metric.labelnames, labels))
            key = f"{metric.name}{{{pairs}}}" if pairs else metric.name
            out[key] = value[1] if metric.kind == "histogram" else value
    return out


def observe_artifact(path: str, frame: np.ndarray, probe: slice) -> Dict[str, object]:
    """Load one machine's artifact back from disk and apply it, as a server
    would: the numbers :mod:`chipbench.check` compares."""
    import pandas as pd

    from gordo_tpu import serializer

    model = serializer.load(path)
    estimator = model.base_estimator.steps[-1][1]
    rows = pd.DataFrame(frame[probe].astype(np.float64))
    scored = model.anomaly(rows, rows)
    return {
        "loss": float(estimator.history["loss"][-1]),
        "params": [
            {k: np.asarray(v) for k, v in layer.items()} for layer in estimator.params_
        ],
        "aggregate_threshold": float(model.aggregate_threshold_),
        "feature_thresholds": np.asarray(model.feature_thresholds_, np.float64),
        "output": scored["model-output"].to_numpy(),
        "confidence": scored["total-anomaly-confidence"].to_numpy().ravel(),
    }


class Fleet:
    """The cell's machines, made from the seed, and the builds of them."""

    def __init__(self, cell: dict, seed: int, out_root: str):
        self.seed, self.out_root = int(seed), out_root
        self.config, self.traffic = cell["config"], cell["traffic"]
        self.next_index = 0
        self.builds = 0

    def build(self, n_machines: int) -> dict:
        """One ``build()`` of ``n_machines`` new machines, the way
        ``batch-build`` runs it. Returns what the harness saw of it."""
        import jax

        from gordo_tpu.machine import Machine
        from gordo_tpu.parallel import BatchedModelBuilder

        with jax.profiler.TraceAnnotation("chipbench.make_machines"):
            names = [
                traffic.machine_name(self.config["name"], self.seed, self.next_index + i)
                for i in range(n_machines)
            ]
            self.next_index += n_machines
            machines = [
                Machine.from_config(
                    traffic.machine_config(self.config, self.traffic, self.seed, name),
                    project_name="chipbench",
                )
                for name in names
            ]
            out_dir = os.path.join(self.out_root, f"build-{self.builds:04d}")
            self.builds += 1
        before = program_counters()
        t0 = time.time()
        with jax.profiler.TraceAnnotation("chipbench.build"):
            builder = BatchedModelBuilder(
                machines,
                output_dir=out_dir,
                chunk_size=self.traffic.chunk_machines,
                serial_fallback=False,
                fail_fast=True,
                elastic=False,
            )
            results = builder.build()
        t1 = time.time()
        after = program_counters()
        built = {machine.name for _, machine in results}
        ready, persisted = [], []
        with jax.profiler.TraceAnnotation("chipbench.stat_artifacts"):
            for name in names:
                path = os.path.join(out_dir, name)
                files = [os.path.join(path, f) for f in ("model.pkl", "metadata.json")]
                if name in built and all(os.path.exists(f) for f in files):
                    last = max(os.stat(f).st_mtime_ns for f in os.scandir(path)) * 1e-9
                    ready.append(last - t0)
                    persisted.append(name)
        degraded = sum(
            after[k] - before[k]
            for k in ("oom_bisections", "bucket_retries", "serial_fallbacks")
        )
        if degraded or builder.quarantined:
            # a build that left the fleet path is not a timed success
            persisted, ready = [], []
        return {
            "names": names,
            "persisted": persisted,
            "ready_s": ready,
            "start": t0,
            "end": t1,
            "out_dir": out_dir,
            "before": before,
            "after": after,
        }


# ------------------------------------------------------------------ the run
def set_up(cell: dict, rehearsal: bool):
    """What ``batch-build`` does before it builds: the native helpers, the
    compile cache where it places it. Then the look for the chip, and the
    benchmark's data provider. Returns the devices the cell uses."""
    import jax

    from gordo_tpu import native
    from gordo_tpu.util.xla_cache import setup_persistent_xla_cache

    native.prebuild(block=True)
    setup_persistent_xla_cache()
    devices, chips = jax.devices(), cell["chips"]
    if devices[0].platform != "tpu" and not rehearsal:
        raise Refused(
            f"JAX found platform {devices[0].platform!r}, not a TPU; a measurement "
            f"never falls back (CPU rehearsals pass --rehearsal)"
        )
    if len(devices) < chips:
        raise Refused(f"the cell asks for {chips} chip(s), JAX found {len(devices)}")
    traffic.register_provider()
    return devices[:chips]


# The session over the window keeps the runtime's host events and leaves the
# device plane out: a scan issues operations by the million, and the device's
# trace buffer drops what comes after some six million (PERF.md).
WINDOW_TRACE_MODE = "TRACE_ONLY_HOST"


def profiler_options(mode: Optional[str]):
    """Host marks only on the host side, no Python tracer, no HLO dump;
    ``mode`` is the TPU's trace mode (None: the profiler's own, operation by
    operation)."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    options.enable_hlo_proto = False
    if mode:
        options.advanced_configuration = {"tpu_trace_mode": mode}
    return options


def memory_peak(devices) -> int:
    """The fullest chip's peak: its buffers' peak plus what the runtime
    reserved for the loaded programs' scratch memory. The TPU runtime keeps
    the two apart (``peak_bytes_in_use`` saw 53 MB while 4.7 GB were reserved
    and gone from the free pool; PERF.md)."""
    peaks = []
    for device in devices:
        stats = device.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use", 0) + stats.get("peak_bytes_reserved", 0))
    return int(max(peaks))


def device_bytes(devices) -> Dict[str, int]:
    """What the fullest chip holds now: its live buffers and what the runtime
    keeps reserved for the loaded programs' scratch."""
    stats = [device.memory_stats() or {} for device in devices]
    return {
        key: int(max(s.get(key, 0) for s in stats))
        for key in ("bytes_in_use", "bytes_reserved")
    }


def release_program(devices) -> None:
    """Give the device back before the reference runs on it: the builds'
    results are out of scope by now, so collect what cycles still hold, and
    drop every compiled program jit has loaded (the fleet trainer's chunk
    programs are plain ``jax.jit``s; the runtime keeps a loaded program's
    scratch reserved until the executable goes). The window, ``setup_s`` and
    the memory peak are read before this; what runs afterwards (the
    reference, the artifacts' own predictions) compiles or loads anew."""
    import jax

    before = device_bytes(devices)
    gc.collect()
    jax.clear_caches()
    gc.collect()
    print(
        f"chipbench: released the program's device memory: before {json.dumps(before)}, "
        f"after {json.dumps(device_bytes(devices))}",
        file=sys.stderr,
    )


def calibrate():
    """A program of the harness's own, some 0.3 s of matmuls, run five times
    alone and to its end inside the operation-level session: the executions
    that a traced run reads both ways, from the device plane and from the
    runtime's host events (:func:`chipbench.trace.cross_check`, which holds
    the median pair: hence an odd number of them)."""
    import jax
    import jax.numpy as jnp

    n = 8192 if jax.devices()[0].platform == "tpu" else 64

    def chipbench_calibration(x, w):
        return jax.lax.fori_loop(0, 48, lambda _, a: jnp.dot(a, w), x)

    program = jax.jit(chipbench_calibration)
    x = jnp.full((n, n), 1.0 / n, jnp.bfloat16)
    program(x, x).block_until_ready()  # compiled or loaded outside the session's mark
    with jax.profiler.TraceAnnotation(trace.CALIBRATE_MARK):
        for _ in range(5):
            program(x, x).block_until_ready()


def device_ops_detail(fleet: "Fleet", tracing: dict, out_dir: str, rehearsal: bool):
    """Which operations take the device's time, and whether the window's
    reading of device time can be trusted: after the window, one session
    traced operation by operation over the calibration program and one more
    build of one chunk. Where a chunk issues millions of operations the
    session is cut ``detail_seconds`` into that build: the scan's steps are
    all alike. Returns what :func:`chipbench.trace.read_detail` reads of the
    cut (operations by self time, by kind, by compiled instance and by named
    scope) and the calibration's two readings; raises
    :class:`chipbench.trace.TraceError` where they differ."""
    import threading

    import jax

    seconds = float(tracing.get("detail_seconds", 0))
    closed = threading.Event()

    def close():
        if not closed.is_set():
            closed.set()
            jax.profiler.stop_trace()

    jax.profiler.start_trace(out_dir, profiler_options=profiler_options(None))
    calibrate()
    timer = threading.Timer(seconds, close) if seconds else None
    if timer:
        timer.start()
    fleet.build(fleet.traffic.chunk_machines)
    if timer:
        timer.cancel()
        timer.join()
    close()
    path = trace.find_xplane(out_dir)
    planes = trace.read_planes(path)
    checked = None
    if not rehearsal:
        checked = trace.cross_check(planes)
    return trace.read_detail(planes, trace.op_names(path), rehearsal), checked


def read_metrics(metrics: List[dict], ctx: dict) -> Dict[str, dict]:
    """Each metric through the reader of its own name,
    ``chipbench/metrics/<name>.py``; a reader that finds nothing to read
    returns None and the metric is left out."""
    out = {}
    for metric in metrics:
        reader = importlib.import_module(f"chipbench.metrics.{metric['name']}")
        value = reader.read(ctx)
        if value is not None:
            out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out


def sample(cell: dict, seed: int, builds: List[dict]):
    """The machines a run compares: drawn from the seed out of everything
    the builds attempted, each with its artifact's path (None where it was
    not persisted) and its sensor rows; and the probe rows' slice."""
    config, tr = cell["config"], cell["traffic"]
    done = [(b, name) for b in builds for name in b["names"]]
    picks = [done[i] for i in check.sample_indices(seed, len(done), tr.check_machines)]
    names = [name for _, name in picks]
    paths = [
        os.path.join(b["out_dir"], name) if name in b["persisted"] else None
        for b, name in picks
    ]
    frames = [
        traffic.machine_frame(seed, name, int(config["n_tags"]), tr) for name in names
    ]
    return names, paths, frames, slice(*reference.probe_rows(tr.rows, int(config["cv_splits"])))


def program_gaps(paths, frames, refs, probe: slice) -> List[Dict[str, float]]:
    """Each sampled artifact, loaded back and applied, against its reference."""
    missing = {k: float("nan") for k in check.NUMBERS}
    return [
        check.gaps(observe_artifact(path, frame, probe), ref, frame[probe])
        if path else missing
        for path, frame, ref in zip(paths, frames, refs)
    ]


def compare(cell: dict, seed: int, builds: List[dict]) -> Dict[str, Dict[str, float]]:
    """Decide ``correct``: the sampled machines against the plain reference."""
    names, paths, frames, probe = sample(cell, seed, builds)
    t0 = time.time()
    refs = reference.build_machines(cell["config"], names, frames, seed)
    print(f"chipbench: reference_s {time.time() - t0:.3f}", file=sys.stderr)
    return check.verdict(
        check.typical(program_gaps(paths, frames, refs, probe)), cell["file"]["limits"]
    )


def run_window(fleet: Fleet, seconds: float, trace_dir: Optional[str]):
    """Build after build until ``seconds`` have passed; the build in flight
    finishes. With ``trace_dir`` one profiler session covers every build.
    Returns the builds and the seconds spent starting and stopping the
    profiler, which are not the window's."""
    import jax

    builds: List[dict] = []
    start, unclocked = time.time(), 0.0
    if trace_dir:
        jax.profiler.start_trace(
            trace_dir, profiler_options=profiler_options(WINDOW_TRACE_MODE)
        )
        unclocked = time.time() - start
    while time.time() - start - unclocked < seconds:
        builds.append(fleet.build(fleet.traffic.machines_per_build))
    if trace_dir:
        t = time.time()
        jax.profiler.stop_trace()
        unclocked += time.time() - t
    return builds, unclocked


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"))
    parser.add_argument(
        "--rehearsal", action="store_true",
        help="allow a CPU backend: exercises the control flow, measures nothing",
    )
    args = parser.parse_args(argv)
    cell = load_cell(args.manifest, args.workload)
    tr = cell["traffic"]

    devices = set_up(cell, args.rehearsal)
    if args.trace:
        # batch-build --metrics-file: spans time the phases, nothing is buffered
        from gordo_tpu.observability import telemetry

        telemetry.enable_spans()

    out_root = tempfile.mkdtemp(prefix="chipbench-")
    try:
        fleet = Fleet(cell, args.seed, out_root)
        warm = fleet.build(tr.chunk_machines)
        if len(warm["persisted"]) != tr.chunk_machines:
            raise Refused("the warm-up build did not persist its machines")
        shutil.rmtree(warm["out_dir"], ignore_errors=True)

        trace_dir = os.path.join(out_root, "trace") if args.trace else None
        window_start = time.time()
        setup_s = window_start - _PROCESS_START
        builds, unclocked = run_window(fleet, args.seconds, trace_dir)
        window_s = builds[-1]["end"] - window_start - unclocked
        peak = memory_peak(devices)

        attempted = sum(len(b["names"]) for b in builds)
        persisted = sum(len(b["persisted"]) for b in builds)
        device = {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": peak,
        }
        result = {"correct": False, "attempted": attempted, "failed": attempted - persisted}
        ctx = {
            "cell": cell,
            "machines": persisted,
            "window_s": window_s,
            "setup_s": setup_s,
            "ready_s": [s for b in builds for s in b["ready_s"]],
            "before": builds[0]["before"],
            "after": builds[-1]["after"],
            "device_kind": devices[0].device_kind,
            "n_devices": len(devices),
        }
        if args.trace:
            # the session's length: the first build's start to the last's return
            reduced = trace.reduce_planes(
                trace.read_planes(trace.find_xplane(trace_dir)),
                builds[-1]["end"] - builds[0]["start"],
                args.rehearsal,
            )
            t_detail = time.time()
            detail, checked = device_ops_detail(
                fleet, cell["file"]["trace"], os.path.join(out_root, "trace-detail"),
                args.rehearsal,
            )
            reduced = dataclasses.replace(reduced, **detail)
            print(
                f"chipbench: calibration read both ways {json.dumps(checked)}; "
                f"the operation-level session took {time.time() - t_detail:.1f} s",
                file=sys.stderr,
            )
            result["cross_check"] = checked
            ctx["trace"] = reduced
            result["metrics"] = read_metrics(cell["per_layer"], ctx)
            device["busy_s"] = reduced.busy_s
            device["window_s"] = reduced.window_s
            result["breakdown"] = {
                "device_ops": reduced.device_ops,
                "idle_gaps": reduced.idle_gaps,
                "device_scopes": reduced.device_scopes(),
            }
        else:
            result["metrics"] = read_metrics(cell["end_to_end"], ctx)
            if len(result["metrics"]) != len(cell["end_to_end"]):
                raise Refused("no machine was persisted inside the window")
        result["device"] = device

        # the comparison runs once the window has closed, the peak is read
        # and the program's state is freed
        release_program(devices)
        compared = compare(cell, args.seed, builds)
        result["correct"] = check.is_correct(compared) and result["failed"] == 0
        result["compared"] = compared
    finally:
        shutil.rmtree(out_root, ignore_errors=True)
    check.report(compared)
    print(json.dumps(result))
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except trace.TraceError as exc:
        print(f"chipbench: {exc}", file=sys.stderr)
        sys.exit(3)
