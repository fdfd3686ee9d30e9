"""
The gordo-tpu CLI.

Reference parity: gordo/cli/cli.py:53-384 — ``build`` (env-var driven for
workers: MACHINE, OUTPUT_DIR, MODEL_REGISTER_DIR; jinja --model-parameter
expansion; full model-config expansion round-trip; stable exception exit
codes; katib-format CV score printing) and ``run-server``.

New TPU-native addition: ``batch-build`` trains a whole multi-machine config
in one process on the device mesh (gordo_tpu.parallel) — the in-process
replacement for the reference's one-pod-per-machine fan-out.

Fault injection: the reference hard-codes a failure for machines whose name
contains "err" (cli.py:179-180 — a test hook in production code). Here fault
injection is explicit: set ``GORDO_TPU_FAULT_INJECTION=<ExceptionName>`` to
raise after a successful build (used to exercise exit-code plumbing e2e).
"""

import json
import logging
import os
import sys
import traceback
from typing import Any, List, Tuple

import click
import jinja2
import yaml

from gordo_tpu import __version__, native, serializer
from gordo_tpu.builder import ModelBuilder
from gordo_tpu.dataset.datasets import InsufficientDataError
from gordo_tpu.dataset.sensor_tag import SensorTagNormalizationError
from gordo_tpu.machine import Machine
from gordo_tpu.reporters.base import ReporterException
from gordo_tpu.util.faults import (
    EXIT_NONE_BUILT,
    EXIT_PARTIAL,
    NonFiniteDataError,
)
from .custom_types import HostIP, key_value_par
from .exceptions_reporter import ExceptionsReporter, ReportLevel

logger = logging.getLogger(__name__)

_exceptions_reporter = ExceptionsReporter(
    (
        (Exception, 1),
        (PermissionError, 20),
        (FileNotFoundError, 30),
        (SensorTagNormalizationError, 60),
        (InsufficientDataError, 80),
        (NonFiniteDataError, 83),
        (ReporterException, 90),
    )
)

FAULT_INJECTION_ENV = "GORDO_TPU_FAULT_INJECTION"
_INJECTABLE_FAULTS = {
    "FileNotFoundError": FileNotFoundError,
    "PermissionError": PermissionError,
    "InsufficientDataError": InsufficientDataError,
    "Exception": Exception,
}


@click.group("gordo-tpu")
@click.version_option(version=__version__, message=__version__)
@click.option(
    "--log-level",
    type=click.Choice(
        ["CRITICAL", "ERROR", "WARNING", "INFO", "DEBUG"],
        case_sensitive=False,
    ),
    default="INFO",
    envvar="GORDO_LOG_LEVEL",
    help="Run with custom log-level.",
)
@click.pass_context
def gordo(gordo_ctx: click.Context, **ctx):
    """The main entry point for the CLI interface."""
    logging.basicConfig(
        level=getattr(logging, str(gordo_ctx.params.get("log_level")).upper()),
        format="[%(asctime)s] %(levelname)s [%(name)s.%(funcName)s:%(lineno)d] %(message)s",
    )
    # GORDO_TPU_LOG_FORMAT=json: one JSON object per line, stamped with
    # the active trace/span ids (observability/logs.py) — no-op otherwise
    from gordo_tpu.observability import logs

    logs.maybe_configure()
    gordo_ctx.obj = gordo_ctx.params


def expand_model(model_config: str, model_parameters: dict):
    """Render the jinja-templated model config with the given parameters."""
    try:
        model_template = jinja2.Environment(
            loader=jinja2.BaseLoader(), undefined=jinja2.StrictUndefined
        ).from_string(model_config)
        model_config = model_template.render(**model_parameters)
    except jinja2.exceptions.UndefinedError as e:
        raise ValueError("Model parameter missing value!") from e
    return yaml.safe_load(model_config)


def get_all_score_strings(machine) -> List[str]:
    """Katib-format '{metric}_{fold}={value}' lines from CV scores."""
    all_scores = []
    for metric_name, scores in (
        machine.metadata.build_metadata.model.cross_validation.scores.items()
    ):
        metric_name = metric_name.replace(" ", "-")
        for score_name, score_val in scores.items():
            score_name = score_name.replace(" ", "-")
            all_scores.append(f"{metric_name}_{score_name}={score_val}")
    return all_scores


def _maybe_inject_fault():
    fault = os.environ.get(FAULT_INJECTION_ENV)
    if fault:
        exc = _INJECTABLE_FAULTS.get(fault, Exception)
        raise exc(f"fault injected via {FAULT_INJECTION_ENV}={fault}")


def _reporter_options(f):
    """The exceptions-reporter CLI surface, shared by build and batch-build
    (one copy — the two commands' options must not drift)."""
    f = click.option(
        "--exceptions-report-level",
        type=click.Choice(ReportLevel.get_names(), case_sensitive=False),
        default=ReportLevel.MESSAGE.name,
        envvar="EXCEPTIONS_REPORT_LEVEL",
        help="Detail level for exception reporting",
    )(f)
    f = click.option(
        "--exceptions-reporter-file",
        envvar="EXCEPTIONS_REPORTER_FILE",
        help="JSON output file for exception information",
    )(f)
    return f


@click.command()
@click.argument("machine-config", envvar="MACHINE", type=yaml.safe_load)
@click.argument("output-dir", default="/data", envvar="OUTPUT_DIR")
@click.option(
    "--model-register-dir",
    default=None,
    envvar="MODEL_REGISTER_DIR",
    type=click.Path(exists=False, file_okay=False, dir_okay=True),
)
@click.option(
    "--print-cv-scores", help="Prints CV scores to stdout", is_flag=True, default=False
)
@click.option(
    "--model-parameter",
    type=key_value_par,
    multiple=True,
    default=(),
    help="Key,value pair for model config jinja variables; repeatable.",
)
@_reporter_options
def build(
    machine_config: dict,
    output_dir: str,
    model_register_dir,
    print_cv_scores: bool,
    model_parameter: List[Tuple[str, Any]],
    exceptions_reporter_file: str,
    exceptions_report_level: str,
):
    """Build a model for a single machine and deposit it into output_dir."""
    try:
        # Compile the native data-layer kernels now (cache-hit after the
        # first pod) instead of stalling mid-build on first use.
        native.prebuild(block=True)
        # XLA compiles persist across pod restarts/retries the same way
        # (shared dir scheme with bench and serving warmup)
        from gordo_tpu.util.xla_cache import setup_persistent_xla_cache

        setup_persistent_xla_cache()
        if isinstance(machine_config["model"], str):
            # expand whenever the model is a string (reference cli.py:166):
            # a jinja-free template must still yaml-load — gating on
            # --model-parameter would crash parameterless string configs
            machine_config["model"] = expand_model(
                machine_config["model"], dict(model_parameter or ())
            )

        machine = Machine.from_config(
            machine_config,
            project_name=machine_config.get("project_name", "project"),
        )

        logger.info("Building, output will be at: %s", output_dir)

        # round-trip the model config so all defaults are recorded
        machine.model = serializer.into_definition(
            serializer.from_definition(machine.model)
        )

        builder = ModelBuilder(machine=machine)
        _, machine_out = builder.build(output_dir, model_register_dir)

        machine_out.report()

        _maybe_inject_fault()

        if print_cv_scores:
            for score in get_all_score_strings(machine_out):
                print(score)

    except click.ClickException:
        raise  # a usage error, not a build failure: click prints it cleanly
    except Exception:
        _report_exception_and_exit(
            exceptions_reporter_file, exceptions_report_level
        )
    return 0


def _report_exception_and_exit(
    exceptions_reporter_file: str, exceptions_report_level: str
):
    """Shared failure plumbing for the builder commands: print the
    traceback, write the k8s termination-message report, exit with the
    exception's stable code (one copy — build and batch-build must not
    drift)."""
    traceback.print_exc()
    exc_type, exc_value, exc_traceback = sys.exc_info()
    exit_code = _exceptions_reporter.exception_exit_code(exc_type)
    if exceptions_reporter_file:
        _exceptions_reporter.safe_report(
            ReportLevel.get_by_name(
                exceptions_report_level, ReportLevel.EXIT_CODE
            ),
            exc_type,
            exc_value,
            exc_traceback,
            exceptions_reporter_file,
            max_message_len=2024 - 500,
        )
    sys.exit(exit_code)


@click.command("batch-build")
@click.argument("config-file", type=click.Path(exists=True), envvar="CONFIG_FILE")
@click.option("--output-dir", default="/data", envvar="OUTPUT_DIR")
@click.option("--project-name", default="batch", envvar="PROJECT_NAME")
@click.option(
    "--machines",
    default="",
    envvar="MACHINES",
    help="Comma-separated machine names: train only this subset of the "
    "config (used by workflow chunk tasks, which pass names instead of "
    "embedding full configs in workflow parameters)",
)
@click.option(
    "--no-serial-fallback",
    is_flag=True,
    default=False,
    help="Fail instead of falling back to serial builds for unbatchable models",
)
@click.option(
    "--coordinator-address",
    default=None,
    envvar="GORDO_TPU_COORDINATOR_ADDRESS",
    help="host:port of process 0 for multi-host training "
    "(jax.distributed); omit for single-host",
)
@click.option(
    "--num-processes",
    type=int,
    default=None,
    envvar="GORDO_TPU_NUM_PROCESSES",
    help="Total number of hosts in the multi-host world",
)
@click.option(
    "--process-id",
    type=int,
    default=None,
    envvar="GORDO_TPU_PROCESS_ID",
    help="This host's rank in the multi-host world",
)
@click.option(
    "--model-register-dir",
    default=None,
    envvar="MODEL_REGISTER_DIR",
    help="Content-hash registry dir: machines are checkpointed as soon as "
    "their chunk finishes and an interrupted fleet build resumes from "
    "cache instead of retraining",
)
@click.option(
    "--elastic",
    is_flag=True,
    default=False,
    envvar="GORDO_TPU_ELASTIC",
    help="Work-stealing fleet scheduler instead of the static multi-host "
    "partition: each host runs single-process and leases buckets from a "
    "shared queue under --output-dir, stealing a peer's units when it "
    "drains its own share or the peer's lease expires (host death). Do "
    "not combine with --coordinator-address; --process-id/--num-processes "
    "become the host's nominal rank/count for steal accounting. See "
    "docs/components/fleet_training.md",
)
@click.option(
    "--lease-timeout-s",
    type=float,
    default=None,
    envvar="GORDO_TPU_LEASE_TIMEOUT_S",
    help="Elastic mode: seconds without a heartbeat before a peer's lease "
    "counts as dead and its unit is stolen (default 60)",
)
@click.option(
    "--heartbeat-s",
    type=float,
    default=None,
    envvar="GORDO_TPU_HEARTBEAT_S",
    help="Elastic mode: interval between lease-file heartbeat rewrites "
    "(default lease-timeout/4)",
)
@click.option(
    "--warm-start/--no-warm-start",
    default=None,
    envvar="GORDO_TPU_WARM_START",
    help="Delta rebuilds: when a machine's full cache key misses but its "
    "config/spec fingerprint matches a registered artifact (only the data "
    "drifted), reuse that artifact's params as training init instead of a "
    "random init. Default on when --model-register-dir is set",
)
@click.option(
    "--fail-fast",
    is_flag=True,
    default=False,
    envvar="GORDO_TPU_FAIL_FAST",
    help="Abort the whole fleet build on the first fault instead of "
    "quarantining the affected machine and degrading machine-by-machine "
    "(restores pre-fault-domain behavior; see docs/robustness.md)",
)
@click.option(
    "--quarantine-report-file",
    default=None,
    envvar="GORDO_TPU_QUARANTINE_REPORT_FILE",
    help="Write quarantined machines and their reasons to this JSON file "
    "in addition to stdout",
)
@click.option(
    "--trace-file",
    default=None,
    envvar="GORDO_TPU_TRACE_FILE",
    help="Record build telemetry spans (per-machine fetch, per-bucket "
    "compile/train, per-machine serialize) and write them as Chrome "
    "trace-event JSON to this path — open it in Perfetto or "
    "chrome://tracing. Off by default: dormant spans are no-ops.",
)
@click.option(
    "--metrics-file",
    default=None,
    envvar="GORDO_TPU_METRICS_FILE",
    help="Write the build's telemetry metrics (phase-duration histograms, "
    "fault-domain counters, cache effectiveness) as a Prometheus textfile "
    "to this path — the push-style export for batch jobs scraped via the "
    "node-exporter textfile collector.",
)
@click.option(
    "--drain-drift-queue",
    is_flag=True,
    default=False,
    help="Instead of building the whole config, drain the drift-rebuild "
    "queue (--drift-queue-dir): claim each pending drift request, "
    "warm-start rebuild exactly those machines with their data windows "
    "slid forward to the detection time, and publish them as a delta "
    "revision dir under --output-dir for serving-side hot swap. See "
    "docs/components/drift.md",
)
@click.option(
    "--drift-queue-dir",
    default=None,
    envvar="GORDO_TPU_DRIFT_QUEUE_DIR",
    help="The drift-rebuild queue directory serving nodes enqueue into "
    "(used with --drain-drift-queue)",
)
@_reporter_options
def batch_build(
    config_file: str,
    output_dir: str,
    project_name: str,
    machines: str,
    no_serial_fallback: bool,
    coordinator_address: str,
    num_processes: int,
    process_id: int,
    model_register_dir: str,
    elastic: bool,
    lease_timeout_s: float,
    heartbeat_s: float,
    warm_start: bool,
    fail_fast: bool,
    quarantine_report_file: str,
    trace_file: str,
    metrics_file: str,
    drain_drift_queue: bool,
    drift_queue_dir: str,
    exceptions_reporter_file: str,
    exceptions_report_level: str,
):
    """
    Train EVERY machine in a config in one SPMD program on the device mesh
    (the TPU-native replacement for per-machine worker pods). With
    --coordinator-address/--num-processes/--process-id the mesh spans hosts
    and each host trains + saves its shard of the fleet.

    Fault domains: a machine whose data fetch, validation, or training
    fails is QUARANTINED (reasons recorded in its BuildMetadata and the
    exit report) while the rest of the fleet builds on. Exit code 0 = all
    machines built, 81 = partial (some quarantined), 82 = none built.
    --fail-fast restores abort-on-first-fault.
    """
    # same exceptions-reporter/exit-code plumbing as `build`: the workflow
    # template wires EXCEPTIONS_REPORTER_FILE + terminationMessagePath to
    # the chunk workers too — a fleet failure must be diagnosable from the
    # k8s termination message with a stable exit code
    from gordo_tpu.observability import telemetry

    if trace_file:
        telemetry.start_trace()
    elif metrics_file:
        # metrics-only collection: spans time (filling phase histograms)
        # without growing an event buffer
        telemetry.enable_spans()
    try:
        from gordo_tpu.parallel import BatchedModelBuilder, distributed
        from gordo_tpu.workflow.normalized_config import NormalizedConfig

        if elastic:
            # elastic mode replaces the jax.distributed world: each host is
            # an independent single-process runtime coordinating only via
            # the shared output_dir queue
            if coordinator_address:
                logger.warning(
                    "--elastic ignores --coordinator-address: hosts "
                    "coordinate through the shared output_dir, not "
                    "jax.distributed"
                )
        else:
            distributed.initialize(
                coordinator_address, num_processes, process_id
            )
        native.prebuild(block=True)
        from gordo_tpu.observability import device
        from gordo_tpu.util.xla_cache import setup_persistent_xla_cache

        setup_persistent_xla_cache()
        device.log_placement(logger, "batch-build")
        with open(config_file) as f:
            config = yaml.safe_load(f)
        norm = NormalizedConfig(config, project_name=project_name)
        selected = norm.machines
        if machines:
            wanted = {
                name.strip() for name in machines.split(",") if name.strip()
            }
            by_name = {m.name: m for m in norm.machines}
            missing = wanted - set(by_name)
            if missing:
                raise click.ClickException(
                    f"--machines names not in config: {sorted(missing)}"
                )
            selected = [by_name[name] for name in sorted(wanted)]
        if drain_drift_queue:
            if not drift_queue_dir:
                raise click.ClickException(
                    "--drain-drift-queue needs --drift-queue-dir "
                    "(or GORDO_TPU_DRIFT_QUEUE_DIR)"
                )
            from gordo_tpu.builder import drift_rebuild

            report = drift_rebuild.drain_drift_queue(
                selected,
                drift_queue_dir,
                output_dir,
                model_register_dir=model_register_dir,
                warm_start=warm_start,
                serial_fallback=not no_serial_fallback,
                fail_fast=fail_fast,
            )
            for name in report["built"]:
                click.echo(
                    f"drift-rebuilt: {name} -> "
                    f"{os.path.join(output_dir, report['revision'], name)}"
                )
            click.echo(
                f"drift drain: requests={report['requests']} "
                f"built={len(report['built'])} "
                f"failed={len(report['failed'])} "
                f"skipped={len(report['skipped'])} "
                f"revision={report['revision']}"
            )
            if report["failed"]:
                sys.exit(
                    EXIT_PARTIAL if report["built"] else EXIT_NONE_BUILT
                )
            return 0
        builder = BatchedModelBuilder(
            selected,
            serial_fallback=not no_serial_fallback,
            output_dir=output_dir,
            model_register_dir=model_register_dir,
            fail_fast=fail_fast,
            elastic=elastic,
            warm_start=warm_start,
            lease_timeout_s=lease_timeout_s,
            heartbeat_s=heartbeat_s,
            host_rank=process_id,
            num_hosts=num_processes,
        )
        # the builder persists every machine as soon as its chunk finishes
        # (checkpoint/resume); reporting stays here, after the fleet
        # completes
        results = builder.build()
        for model, machine_out in results:
            machine_out.report()
            click.echo(
                f"built: {machine_out.name} -> "
                f"{os.path.join(output_dir, machine_out.name)}"
            )
        _report_quarantine_and_exit(
            builder, len(results), quarantine_report_file
        )
    except click.ClickException:
        raise  # a usage error (e.g. unknown --machines name), not a failure
    except Exception:
        _report_exception_and_exit(
            exceptions_reporter_file, exceptions_report_level
        )
    finally:
        # runs on every exit path, including the quarantine sys.exit above
        # and the exception reporter's: a partially-failed build is exactly
        # when the trace and fault counters are most wanted
        _flush_telemetry(trace_file, metrics_file)
    return 0


def _flush_telemetry(trace_file: str, metrics_file: str) -> None:
    """Export the build's telemetry: refresh the XLA-cache gauges, then
    write the Chrome trace and/or Prometheus textfile (atomic writes)."""
    if not trace_file and not metrics_file:
        return
    from gordo_tpu.observability import telemetry
    from gordo_tpu.util import xla_cache

    try:
        xla_cache.record_cache_growth()
    except Exception:  # noqa: BLE001 — export must not mask the build result
        logger.exception("could not refresh XLA cache metrics")
    try:
        if trace_file:
            telemetry.write_trace(trace_file)
            telemetry.stop_trace()
            click.echo(
                f"telemetry trace written: {trace_file} "
                "(open in Perfetto or chrome://tracing)",
                err=True,
            )
        if metrics_file:
            telemetry.write_metrics(metrics_file)
            click.echo(
                f"telemetry metrics written: {metrics_file}", err=True
            )
    except Exception:  # noqa: BLE001 — export must not mask the build result
        logger.exception("telemetry export failed")


def _report_quarantine_and_exit(
    builder, n_built: int, quarantine_report_file: str
) -> None:
    """The fleet-build exit report: one line per fleet program that failed
    to compile and per quarantined machine, an optional JSON report file,
    and the documented exit-code contract (0 all built / 81 partial / 82
    none built; docs/robustness.md). A compile failure does not change the
    code when the fault ladder built the machines another way, so the
    report says it: such a build did not use the fleet path as planned."""
    records = builder.quarantine_records
    for failure in builder.compile_failures:
        click.echo(
            f"fleet-compile-failure: bucket={failure['bucket']} "
            f"machines={failure['machines']} error={failure['error']}",
            err=True,
        )
    for record in records:
        click.echo(
            f"quarantined: {record.machine} stage={record.stage} "
            f"reason={record.reason} attempts={record.attempts} "
            f"error={record.error}",
            err=True,
        )
    if quarantine_report_file:
        with open(quarantine_report_file, "w") as f:
            json.dump(
                {
                    "built": n_built,
                    "quarantined": [r.to_dict() for r in records],
                    "fleet_compile_failures": builder.compile_failures,
                },
                f,
                indent=2,
            )
    if records:
        sys.exit(EXIT_PARTIAL if n_built else EXIT_NONE_BUILT)


@click.command("run-server")
@click.option(
    "--host", type=HostIP(), default="0.0.0.0", envvar="GORDO_SERVER_HOST"
)
@click.option("--port", type=click.IntRange(1, 65535), default=5555, envvar="GORDO_SERVER_PORT")
@click.option(
    "--workers",
    type=click.IntRange(min=1),
    default=None,
    envvar="GORDO_SERVER_WORKERS",
    help="Worker processes. Default: one per TPU chip of this host (a chip "
    "belongs to one process at a time, so more workers than chips is "
    "refused), or 2 on a host without chips.",
)
@click.option(
    "--worker-connections",
    type=click.IntRange(1, 400),
    default=50,
    envvar="GORDO_SERVER_WORKER_CONNECTIONS",
)
@click.option(
    "--batch-predicts/--no-batch-predicts",
    default=True,
    # NOT GORDO_TPU_SERVING_BATCH: that env var carries the non-boolean
    # mode string ("auto") this command exports below — click's BOOL
    # coercion would crash on its own output on re-invocation
    envvar="GORDO_SERVER_BATCH_PREDICTS",
    help="Fuse concurrent same-architecture predicts into one device call "
    "(self-measuring: a startup A/B per architecture stands batching down "
    "where the fused call loses to per-request dispatch)",
)
@click.option(
    "--warmup/--no-warmup",
    default=False,
    envvar="GORDO_TPU_SERVING_WARMUP",
    help="Precompile every model's serving predict programs (per padded "
    "row bucket) in each worker before it accepts traffic, so the first "
    "requests don't pay XLA compiles — on TPU, tens of seconds each. "
    "Compiles land in the persistent XLA cache and are shared across "
    "workers and restarts.",
)
def run_server_cli(host, port, workers, worker_connections, batch_predicts, warmup):
    """Run the gordo-tpu model server."""
    from gordo_tpu.server import run_server
    from gordo_tpu.server.server import ChipLayoutError

    # the switch must be in env before workers fork; each worker process
    # then builds its own batcher on first use. "auto" = measured per-spec
    # self-A/B at first use (server/batcher.py), never a blind always-on
    os.environ["GORDO_TPU_SERVING_BATCH"] = "auto" if batch_predicts else "0"
    try:
        run_server(
            host, port, workers, worker_connections=worker_connections,
            warmup=warmup,
        )
    except ChipLayoutError as exc:
        # more workers than chips (refused before anything is bound), or a
        # worker found chips the launcher had not counted (pool stopped)
        raise click.UsageError(str(exc))


@click.command("run-gateway")
@click.option(
    "--host", type=HostIP(), default="0.0.0.0", envvar="GORDO_GATEWAY_HOST"
)
@click.option(
    "--port", type=click.IntRange(1, 65535), default=5556,
    envvar="GORDO_GATEWAY_PORT",
)
@click.option(
    "--membership-dir",
    type=click.Path(file_okay=False),
    default=None,
    envvar="GORDO_TPU_GATEWAY_DIR",
    help="Shared membership directory the serving nodes heartbeat their "
    "leases into (filesystem membership — no etcd/consul). Defaults to "
    "GORDO_TPU_GATEWAY_DIR.",
)
def run_gateway_cli(host, port, membership_dir):
    """Run the fault-tolerant cross-node serving gateway.

    Consistent-hash placement of machines onto lease-registered serving
    nodes, SLO-burn-driven drain, and budgeted hedged failover — see
    docs/components/gateway.md.
    """
    from gordo_tpu.server.gateway import run_gateway

    run_gateway(host=host, port=port, directory=membership_dir)


@click.command("drift-rebuilder")
@click.argument(
    "config-file", type=click.Path(exists=True), envvar="CONFIG_FILE"
)
@click.option(
    "--queue-dir",
    required=True,
    envvar="GORDO_TPU_DRIFT_QUEUE_DIR",
    help="The drift-rebuild queue directory serving nodes enqueue into "
    "(GORDO_TPU_DRIFT_QUEUE_DIR on the servers)",
)
@click.option("--output-dir", default="/data", envvar="OUTPUT_DIR")
@click.option(
    "--model-register-dir",
    default=None,
    envvar="MODEL_REGISTER_DIR",
    help="Content-hash registry the warm starts seed from; without it the "
    "delta rebuilds fall back to cold inits",
)
@click.option("--project-name", default="batch", envvar="PROJECT_NAME")
@click.option(
    "--once",
    is_flag=True,
    default=False,
    help="One drain pass instead of polling forever (cron-style operation)",
)
@click.option(
    "--poll-interval",
    type=float,
    default=30.0,
    envvar="GORDO_TPU_DRIFT_POLL_S",
    help="Seconds between queue polls in daemon mode",
)
def drift_rebuilder(
    config_file: str,
    queue_dir: str,
    output_dir: str,
    model_register_dir: str,
    project_name: str,
    once: bool,
    poll_interval: float,
):
    """Consume the drift-rebuild queue: warm-start delta rebuilds.

    The daemon half of the self-healing loop (docs/components/drift.md):
    serving nodes detect drift and enqueue rebuild requests
    (observability/drift.py -> parallel/drift_queue.py); this command
    claims them through the generation-fenced queue, rebuilds exactly the
    drifted machines with their training windows slid forward to the
    detection time, and publishes the result as a ``drift-<epoch-ms>``
    delta revision dir that serving nodes hot-swap in. Multiple
    rebuilders may watch one queue: claims are exclusive, stale claims
    are stolen after the timeout.
    """
    import time as _time

    from gordo_tpu.builder import drift_rebuild
    from gordo_tpu.parallel import drift_queue as _queue
    from gordo_tpu.workflow.normalized_config import NormalizedConfig

    native.prebuild(block=True)
    from gordo_tpu.util.xla_cache import setup_persistent_xla_cache

    setup_persistent_xla_cache()
    with open(config_file) as f:
        config = yaml.safe_load(f)
    norm = NormalizedConfig(config, project_name=project_name)
    while True:
        if _queue.depth(queue_dir):
            report = drift_rebuild.drain_drift_queue(
                norm.machines,
                queue_dir,
                output_dir,
                model_register_dir=model_register_dir,
            )
            if report["built"] or report["failed"]:
                click.echo(
                    f"drift drain: built={report['built']} "
                    f"failed={report['failed']} "
                    f"revision={report['revision']}"
                )
        if once:
            return 0
        _time.sleep(poll_interval)


@click.group("chaos")
def chaos_cli():
    """Chaos conductor: failure drills against a real gateway + fleet.

    Scenario files (resources/chaos/*.yaml) declare the stack, the
    shaped load, the fault timeline and the invariants; ``run`` spins
    the whole thing up, fires it, and exits nonzero if any invariant
    fails. See docs/robustness.md ("Chaos conductor").
    """


@chaos_cli.command("run")
@click.argument("scenario", type=click.Path(exists=True))
@click.option(
    "--dir",
    "work_dir",
    type=click.Path(),
    default=None,
    help="Working directory for the drill (membership leases, drift "
    "queue). Default: a fresh temporary directory, removed afterwards.",
)
@click.option(
    "--out",
    type=click.Path(),
    default=None,
    help="Also write the full JSON report to this path",
)
@click.option("--verbose", is_flag=True, default=False,
              help="Stack and gateway logs to stderr")
def chaos_run(scenario: str, work_dir: str, out: str, verbose: bool):
    """Run one chaos scenario; exit 0 iff every invariant holds."""
    import shutil
    import tempfile

    from gordo_tpu.chaos import load_scenario, run_scenario

    if verbose:
        logging.basicConfig(level=logging.INFO)
    spec = load_scenario(scenario)
    directory = work_dir or tempfile.mkdtemp(prefix="gordo-chaos-")
    try:
        report = run_scenario(spec, directory)
    finally:
        if work_dir is None:
            shutil.rmtree(directory, ignore_errors=True)
    if out:
        with open(out, "w") as fh:
            json.dump(report, fh, indent=1)
    for res in report["invariants"]:
        mark = "PASS" if res["ok"] else "FAIL"
        click.echo(f"[{mark}] {res['check']}: {res['detail']}")
    click.echo(
        f"{report['scenario']}: availability={report['availability']} "
        f"p99={report['p99_ms']}ms failover_s={report['failover_s']} "
        f"-> {'OK' if report['ok'] else 'FAILED'}"
    )
    sys.exit(0 if report["ok"] else 1)


@chaos_cli.command("list")
@click.option(
    "--dir",
    "scenario_dir",
    type=click.Path(exists=True),
    default="resources/chaos",
    help="Directory of scenario files",
)
def chaos_list(scenario_dir: str):
    """List the committed scenarios and their declared invariants."""
    from gordo_tpu.chaos import load_scenario

    for name in sorted(os.listdir(scenario_dir)):
        if not name.endswith((".yaml", ".yml", ".json")):
            continue
        path = os.path.join(scenario_dir, name)
        try:
            spec = load_scenario(path)
        except Exception as exc:  # noqa: BLE001 — a broken file is listed as such
            click.echo(f"{name}: INVALID ({exc})")
            continue
        checks = ",".join(inv.check for inv in spec.invariants)
        click.echo(f"{name}: {spec.name} — nodes={spec.nodes} "
                   f"phases={len(spec.phases)} invariants=[{checks}]")


@click.command("trace")
@click.argument("trace_id")
@click.option("--host", default="127.0.0.1", show_default=True,
              help="Gateway host (a node works too — you get its subtree)")
@click.option("--port", default=5556, show_default=True, type=int,
              help="Gateway port (``gordo run-gateway`` default)")
@click.option("--out", type=click.Path(), default=None,
              help="Also write the raw stitched Chrome-trace JSON here "
                   "(open in Perfetto / chrome://tracing)")
def trace_cli(trace_id: str, host: str, port: int, out: str):
    """Fetch one request's stitched cross-node trace from a gateway.

    Wraps ``GET /debug/flight?trace=<id>`` (``GORDO_TPU_DEBUG_ENDPOINTS``
    must be on): the gateway returns its own span tree for the request
    with each upstream node's subtree grafted under the proxy attempt
    that hit it, and this prints that tree — indented, durations in ms,
    node-side spans tagged with their node id. A partial stitch (dead
    node, gated-off debug surface) is reported per node, not fatal.
    """
    import http.client

    status, raw = 0, b""
    conn = http.client.HTTPConnection(host, port, timeout=10)
    try:
        conn.request("GET", f"/debug/flight?trace={trace_id}")
        resp = conn.getresponse()
        status, raw = resp.status, resp.read()
    except OSError as exc:
        click.echo(f"error: cannot reach {host}:{port} ({exc})", err=True)
        sys.exit(2)
    finally:
        conn.close()
    if status != 200:
        click.echo(
            f"error: {host}:{port} answered {status}: "
            f"{raw[:200].decode(errors='replace')}",
            err=True,
        )
        sys.exit(1)
    doc = json.loads(raw)
    if out:
        with open(out, "w") as fh:
            json.dump(doc, fh, indent=1)
    events = doc.get("traceEvents") or []
    known = {e.get("args", {}).get("span_id") for e in events}
    children: dict = {}
    roots = []
    for event in events:
        parent = event.get("args", {}).get("parent_span_id") or ""
        if parent and parent in known:
            children.setdefault(parent, []).append(event)
        else:
            roots.append(event)

    def emit(event, depth):
        args = dict(event.get("args") or {})
        span_id = args.get("span_id")
        node = args.pop("gordo_node", None)
        attrs = " ".join(
            f"{k}={args[k]}" for k in sorted(args)
            if k not in ("trace_id", "span_id", "parent_span_id", "links")
        )
        where = f" @{node}" if node else ""
        dur_ms = float(event.get("dur", 0.0)) / 1000.0
        line = f"{'  ' * depth}{event.get('name')}{where} {dur_ms:.2f}ms"
        click.echo(f"{line}  {attrs}".rstrip())
        for child in sorted(children.get(span_id, ()),
                            key=lambda c: c.get("ts", 0.0)):
            emit(child, depth + 1)

    click.echo(f"trace {trace_id}")
    for root in sorted(roots, key=lambda e: e.get("ts", 0.0)):
        emit(root, 1)
    stitch = doc.get("gordoStitch") or {}
    for entry in stitch.get("nodes", ()):
        mark = "ok" if entry.get("ok") else f"MISSING ({entry.get('reason')})"
        click.echo(f"stitch {entry.get('node')}: {mark}")
    if stitch and not stitch.get("complete"):
        click.echo("stitch: PARTIAL — some node subtrees are missing")


gordo.add_command(build)
gordo.add_command(batch_build)
gordo.add_command(run_server_cli)
gordo.add_command(run_gateway_cli)
gordo.add_command(drift_rebuilder)
gordo.add_command(chaos_cli)
gordo.add_command(trace_cli)


def _append_workflow_commands():
    # registered lazily so the CLI works before the workflow module lands
    try:
        from .workflow_generator import workflow_cli

        gordo.add_command(workflow_cli)
    except ImportError:
        pass


_append_workflow_commands()

if __name__ == "__main__":
    gordo()
