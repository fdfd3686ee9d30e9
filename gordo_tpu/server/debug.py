"""
Read-only introspection endpoints: the operator's first stop on a pager.

All routes are gated by ``GORDO_TPU_DEBUG_ENDPOINTS=1`` (without it
they answer 404 exactly like unknown paths — a production server exposes
nothing new by default):

- ``GET /debug/flight`` — the flight recorder's kept request traces as
  Chrome trace-event JSON (save the body to a file, open it in Perfetto
  or ``chrome://tracing``; the ``gordoFlight`` sidecar lists per-trace
  summaries for grepping). This is the per-incident forensics surface:
  find the trace whose id a client quoted from its ``X-Gordo-Trace``
  header, and read the request's whole span tree.
- ``GET /debug/vars`` — a live snapshot of every telemetry metric series
  plus batcher/in-flight process state, as JSON. Unlike ``/metrics`` it
  needs no prometheus_client, no scrape pipeline, and returns structured
  values (``curl | jq`` during an incident).
- ``GET /debug/config`` — the resolved ``GORDO_TPU_*`` knob values this
  process is actually running with (env-set knobs verbatim, effective
  values for the serving knobs that have defaults). Values whose name
  suggests a secret are redacted.
- ``GET /debug/slo`` — per-model rolling-window latency/error summaries
  and burn rates against the configured objectives
  (observability/slo.py): this process's view always, plus the merged
  fleet view when ``GORDO_TPU_TELEMETRY_DIR`` shards are active.
- ``GET /debug/drift`` — the drift detector's per-model state
  (observability/drift.py): baseline mean/std, CUSUM level, status,
  rolling-window summary; plus the merged fleet view when telemetry
  shards are active and the rebuild-queue depth when a drift queue is
  configured.
- ``GET /debug/profile?seconds=N`` — on-demand burst capture from the
  sampling profiler (observability/profiler.py): sample the registered
  hot threads for N seconds (``hz=`` overrides the burst rate) and
  return collapsed stacks, or ``format=chrome`` for a Chrome trace,
  ``format=collapsed`` for plain text a flamegraph tool ingests
  directly. Works whether or not the steady sampler
  (``GORDO_TPU_PROFILE_HZ``) is running; ``steady=1`` returns the
  steady sampler's accumulated view instead of capturing, and
  ``device=1`` runs an on-demand ``jax.profiler`` device trace into
  ``GORDO_TPU_PROFILE_DIR``.
- ``GET /debug/perf`` — the latency-attribution engine's live view
  (observability/attribution.py): per-phase window quantiles, the
  current-vs-previous-window decomposition (which phase moved p50/p99
  and by how much, plus the traffic mix-shift term), and the
  perf-regression sentinel's per-phase CUSUM state
  (observability/sentinel.py).
- ``POST /debug/prewarm?machine=<name>[&revision=<rev>]`` — the one
  deliberate exception to read-only: run the warmup pre-registration
  (server/warmup.py — serving-program compiles, param-bank pinning, AOT
  pre-lowering) for one machine (or the whole collection without
  ``machine``). The gateway calls this on a draining node's ring
  successors so the spilled segment lands warm, and during a hot-swap
  cutover with an explicit ``revision=`` so the pre-warm targets the
  NEW artifact revision rather than whatever warmup last saw (ISSUE
  13); warming caches is the endpoint's entire point and it mutates
  nothing else.

Everything else here is read-only: no handler mutates server state (the
telemetry-shard flush a fleet view triggers only refreshes this
process's own shard file).
"""

import os
import re
from typing import Any, Dict

try:
    import simplejson
except ImportError:  # pragma: no cover - environment-dependent
    from gordo_tpu.util import _simplejson as simplejson

from werkzeug.wrappers import Response

from gordo_tpu.observability import flight, telemetry
from gordo_tpu.server import resilience

# substrings that mark a knob's VALUE as sensitive — never echo those
# through an HTTP endpoint, even a gated one
_SECRET_MARKERS = ("PASSWORD", "SECRET", "TOKEN", "KEY", "CREDENTIAL")


def enabled() -> bool:
    return os.environ.get("GORDO_TPU_DEBUG_ENDPOINTS", "").lower() in (
        "1", "true", "yes",
    )


def _json(payload: Dict[str, Any], status: int = 200) -> Response:
    return Response(
        simplejson.dumps(payload, ignore_nan=True),
        status=status,
        mimetype="application/json",
    )


def dispatch(endpoint: str, config: Dict[str, Any], request=None) -> Response:
    """Route one ``debug_*`` endpoint; 404 when the gate is off."""
    if not enabled():
        # indistinguishable from an unknown route: the debug surface is
        # invisible unless explicitly enabled
        return Response("Not Found", status=404)
    if endpoint == "debug_flight":
        return flight_view(request)
    if endpoint == "debug_vars":
        return vars_view(config)
    if endpoint == "debug_slo":
        return slo_view()
    if endpoint == "debug_drift":
        return drift_view()
    if endpoint == "debug_prewarm":
        return prewarm_view(config, request)
    if endpoint == "debug_profile":
        return profile_view(request)
    if endpoint == "debug_perf":
        return perf_view()
    return config_view()


# -------------------------------------------------------------- /debug/flight
def flight_view(request=None) -> Response:
    """The flight ring as Chrome trace JSON, now with a ``gordoProfile``
    sidecar: the steady profiler's collapsed stacks keyed to the worst
    kept trace, so the evidence of *what the CPU was doing* ships next
    to the evidence of *which requests were bad*.

    ``?trace=<id>`` filters to that one trace's subtree — the shape the
    gateway's cross-node stitcher fetches — answering 404 when this
    node's recorder never kept the id."""
    from gordo_tpu.observability import profiler

    trace_id = request.args.get("trace") if request is not None else None
    if trace_id:
        payload = flight.default_recorder().chrome_trace(trace_id)
        if payload is None:
            return _json(
                {"error": "trace not kept", "trace_id": trace_id},
                status=404,
            )
        return _json(payload)
    payload = flight.default_recorder().chrome_trace()
    worst = flight.default_recorder().worst_trace()
    payload["gordoProfile"] = {
        "worst_trace": None if worst is None else {
            "trace_id": worst["trace_id"],
            "class": worst["class"],
            "duration_s": worst["duration_s"],
            "endpoint": worst["endpoint"],
        },
        "profile": profiler.snapshot(top=20),
    }
    return _json(payload)


# ------------------------------------------------------------- /debug/profile
def _float_arg(request, name: str, default: float) -> float:
    if request is None:
        return default
    try:
        return float(request.args.get(name, default))
    except (TypeError, ValueError):
        return default


def profile_view(request=None) -> Response:
    """On-demand profiling surface (see module docstring). Burst capture
    runs inline in the handling thread — the other lane's hot threads
    keep serving while this request samples them."""
    from gordo_tpu.observability import profiler

    if request is not None and request.args.get("device") in ("1", "true"):
        seconds = _float_arg(request, "seconds", 2.0)
        return _json({"device_trace": profiler.device_trace(seconds)})

    fmt = request.args.get("format", "json") if request is not None else "json"
    if request is not None and request.args.get("steady") in ("1", "true"):
        counter = profiler.steady_counter()
    else:
        seconds = _float_arg(request, "seconds", 2.0)
        hz = _float_arg(request, "hz", profiler.DEFAULT_HZ)
        counter = profiler.burst(seconds, hz=hz)
    if fmt == "collapsed":
        return Response(
            "\n".join(counter.collapsed()) + "\n",
            status=200, mimetype="text/plain",
        )
    if fmt == "chrome":
        return _json(counter.chrome_trace(profiler.steady_hz()
                                          or profiler.DEFAULT_HZ))
    payload = counter.to_dict(top=100)
    payload["steady"] = profiler.snapshot(top=0)
    return _json(payload)


# ---------------------------------------------------------------- /debug/perf
def perf_view() -> Response:
    """The live latency decomposition + sentinel state."""
    from gordo_tpu.observability import attribution, sentinel

    return _json(
        {
            "attribution": attribution.snapshot(),
            "sentinel": sentinel.snapshot(),
        }
    )


# ---------------------------------------------------------------- /debug/vars
def vars_view(config: Dict[str, Any]) -> Response:
    """Every telemetry series' current value, plus process serving state."""
    metrics: Dict[str, Any] = {}
    for metric in telemetry.default_registry().collect():
        series = []
        for key, value in metric.snapshot():
            labels = dict(zip(metric.labelnames, key))
            if metric.kind == "histogram":
                counts, total = value
                series.append(
                    {"labels": labels, "count": sum(counts), "sum": total}
                )
            else:
                series.append({"labels": labels, "value": value})
        metrics[metric.name] = {"kind": metric.kind, "series": series}

    from gordo_tpu.observability import device, shared
    from gordo_tpu.server import warmup
    from gordo_tpu.server.batcher import peek_batcher

    batcher = peek_batcher()
    recorder = flight.default_recorder()
    return _json(
        {
            "metrics": metrics,
            "server": {
                "inflight_requests": resilience.inflight_requests(),
                "gated_inflight": resilience.gated_inflight(),
                "draining": resilience.is_draining(),
                "project": config.get("PROJECT"),
            },
            # dispatch counters plus the auto mode's measured per-architecture
            # decisions: how many batch, how many stood down
            "batcher": None if batcher is None else {
                **batcher.stats,
                "self_ab": dict(
                    zip(("batching", "stood_down"), batcher.decision_counts())
                ),
            },
            # last warmup report (boot / hot-swap pre-warm / /debug/prewarm):
            # AOT program counts incl. shipped-vs-compiled and the compile
            # seconds shipped programs saved — the node's warmth at a glance
            "warmup": warmup.last_report(),
            # duty cycle / online MFU / param-bank residency / memory
            # (observability/device.py; refreshes the gauges it reports)
            "device": device.snapshot(),
            # cross-worker merged view; None without GORDO_TPU_TELEMETRY_DIR
            "fleet": shared.fleet_vars(),
            "flight": {
                "seen": recorder.seen,
                "kept": recorder.kept,
                "slow_threshold_s": recorder.slow_threshold_s(),
            },
        }
    )


# ----------------------------------------------------------------- /debug/slo
def slo_view() -> Response:
    """Per-model SLO summaries and burn rates: always this process's local
    tracker; plus the fleet merge over every worker's shard payload when
    telemetry shards are enabled."""
    from gordo_tpu.observability import shared, slo

    payload: Dict[str, Any] = {"local": slo.snapshot()}
    if shared.enabled():
        # flush first so the answering worker's own windows are in the merge
        shared.flush(force=True)
        payload["fleet"] = slo.merge_payloads(shared.fleet_extras("slo"))
    return _json(payload)


# --------------------------------------------------------------- /debug/drift
def drift_view() -> Response:
    """Per-model drift detector state: this process's view always, the
    merged fleet view when telemetry shards are active, and the rebuild
    queue depth when a drift queue dir is configured."""
    from gordo_tpu.observability import drift, shared

    payload: Dict[str, Any] = {
        "enabled": drift.enabled(),
        "local": drift.snapshot(),
        "drifted": drift.drifted_models(),
    }
    if shared.enabled():
        shared.flush(force=True)
        payload["fleet"] = drift.merge_payloads(shared.fleet_extras("drift"))
    directory = drift.queue_dir()
    if directory:
        from gordo_tpu.parallel import drift_queue

        payload["queue"] = {
            "dir": directory,
            "depth": drift_queue.depth(directory),
            "pending": [r.get("machine") for r in drift_queue.pending(directory)],
        }
    return _json(payload)


# ------------------------------------------------------------- /debug/prewarm
# same token shape GordoServer._resolve_revision enforces: a revision is a
# plain directory name, never a path
_REVISION_RE = re.compile(r"^[A-Za-z0-9][A-Za-z0-9._-]*$")


def prewarm_view(config: Dict[str, Any], request=None) -> Response:
    """Warm one machine's (or the whole collection's) serving programs
    through the standard warmup pre-registration — the gateway's
    successor pre-warm target. An explicit ``revision=`` warms that
    sibling revision dir instead of the serving collection (the
    hot-swap cutover pre-warm, ISSUE 13); an unknown revision is 410
    like the prediction routes."""
    machine = request.args.get("machine") if request is not None else None
    revision = request.args.get("revision") if request is not None else None
    collection_dir = config.get("MODEL_COLLECTION_DIR")
    if not collection_dir:
        return _json({"error": "MODEL_COLLECTION_DIR unset"}, status=409)
    if revision:
        candidate = os.path.join(collection_dir, "..", revision)
        if (
            not _REVISION_RE.match(revision)
            or ".." in revision
            or not os.path.isdir(candidate)
        ):
            return _json(
                {"error": f"Revision '{revision}' not found."}, status=410
            )
        collection_dir = candidate
    from gordo_tpu.server.warmup import warmup_collection

    try:
        result = warmup_collection(
            collection_dir, names=[machine] if machine else None
        )
    except Exception as exc:  # noqa: BLE001 — warming is best-effort
        return _json({"error": str(exc)}, status=500)
    if revision:
        result = dict(result)
        result["revision"] = revision
    return _json(result)


# -------------------------------------------------------------- /debug/config
def _redact(name: str, value: str) -> str:
    if any(marker in name.upper() for marker in _SECRET_MARKERS):
        return "<redacted>"
    return value


def config_view() -> Response:
    """The knobs as this process resolved them: raw env for everything
    GORDO_TPU_*-shaped that is set, plus the effective values of serving
    knobs with live defaults (what the code would actually use NOW)."""
    env = {
        name: _redact(name, value)
        for name, value in sorted(os.environ.items())
        if name.startswith("GORDO_TPU_")
    }
    resolved = {
        "max_inflight": resilience.max_inflight(),
        "retry_after_s": resilience.retry_after_s(),
        "deadline_ms_default": resilience.deadline_ms_from({}),
        "breaker_threshold": resilience.breaker_threshold(),
        "drain_budget_s": resilience.drain_budget_s(),
        "watchdog_threshold_s": resilience.watchdog_threshold_s(),
        "validate_output": resilience.validate_output_enabled(),
        "flight_capacity": flight.capacity_from_env(),
        "flight_slow_s": flight.default_recorder().slow_threshold_s(),
        "debug_endpoints": enabled(),
        "log_format": os.environ.get("GORDO_TPU_LOG_FORMAT", "plain"),
        "serving_batch": os.environ.get("GORDO_TPU_SERVING_BATCH", "off"),
        "fast_codec": os.environ.get("GORDO_TPU_FAST_CODEC", "1"),
    }
    return _json({"env": env, "resolved": resolved})
