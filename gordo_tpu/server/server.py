"""
The model server: a plain WSGI application on werkzeug.

Reference parity: gordo/server/server.py:36-297 — same env-driven config
(MODEL_COLLECTION_DIR, EXPECTED_MODELS, ENABLE_PROMETHEUS, PROJECT), same
route table, same per-request revision resolution (?revision= / header with
410 on missing), same response post-processing (revision key+header,
Server-Timing header), /healthcheck and /server-version.

Differences by design: no Flask/gunicorn dependency — the app is a small
werkzeug-routed WSGI callable; ``run_server`` serves it with a threaded
werkzeug server (model inference is released-GIL device compute, so threads
scale; multiple processes can still be run behind any WSGI server).
"""

import contextlib
import json
import logging
import math
import os
import re
import timeit
from typing import Any, Dict, Optional

try:
    import simplejson
except ImportError:  # pragma: no cover - environment-dependent
    from gordo_tpu.util import _simplejson as simplejson
from werkzeug.exceptions import HTTPException, MethodNotAllowed
from werkzeug.routing import Map, Rule
from werkzeug.wrappers import Request, Response

from gordo_tpu import __version__
from gordo_tpu.observability import (
    attribution,
    drift,
    flight,
    metrics as metric_catalog,
    sentinel,
    shared,
    slo,
    telemetry,
    tracing,
)
from gordo_tpu.server import resilience, views

logger = logging.getLogger(__name__)

# routes that hold device resources: admission control and deadlines apply
# here and nowhere else (healthcheck/readiness/metrics must answer even on
# a saturated server — that is what load shedding protects)
_GATED_ENDPOINTS = ("base_prediction", "anomaly_prediction")


def observe_request_outcome(
    rule: str, model: str, duration_s: float, status: int,
    slo_eligible: bool = False,
    phases: Optional[Dict[str, float]] = None,
) -> None:
    """Per-request fleet/SLO feed, shared verbatim by the WSGI edge and the
    socket fast lane so the two lanes produce identical observability
    (pinned by tests/gordo_tpu/test_fastlane.py). Labels by the matched
    RULE and the status CLASS — both bounded — and flushes this process's
    telemetry shard (throttled) so the fleet view stays fresh under load.
    ``phases`` (ctx.timings: decode/predict/encode wall seconds) feeds the
    latency-attribution windows and the perf-regression sentinel, both of
    which no-op before taking any lock when their knobs are unset."""
    try:
        status_class = f"{int(status) // 100}xx"
        metric_catalog.FLEET_REQUESTS.labels(
            endpoint=rule, status=status_class
        ).inc()
        metric_catalog.FLEET_REQUEST_SECONDS.labels(
            endpoint=rule
        ).observe(duration_s)
        if slo_eligible and model:
            slo.record(model, duration_s, status)
        if slo_eligible and status < 400:
            attribution.observe(model, duration_s, phases)
            sentinel.observe_phases(duration_s, phases)
        shared.flush()
    except Exception:  # noqa: BLE001 — observability must not fail requests
        logger.debug("request observability feed failed", exc_info=True)


def default_config() -> Dict[str, Any]:
    expected_models = os.environ.get("EXPECTED_MODELS")
    return {
        "MODEL_COLLECTION_DIR": os.environ.get("MODEL_COLLECTION_DIR"),
        "EXPECTED_MODELS": json.loads(expected_models) if expected_models else [],
        "EXPECTED_MODELS_FILE": os.environ.get("EXPECTED_MODELS_FILE"),
        "ENABLE_PROMETHEUS": os.environ.get("ENABLE_PROMETHEUS", "false").lower()
        in ("1", "true", "yes"),
        "PROJECT": os.environ.get("PROJECT"),
    }


def adapt_proxy_deployment(wsgi_app):
    """WSGI middleware for prefixed-ingress deployments — Envoy/Ambassador
    path prefixes and Istio VirtualService prefix routing (the deployment
    topology the workflow template generates).

    Reference parity: gordo/server/server.py:46-119. When the ingress
    strips a route prefix before forwarding, the app sees only the local
    path; the original full path arrives in ``X-Envoy-Original-Path``
    (Envoy/Ambassador), or the stripped prefix alone in
    ``X-Forwarded-Prefix`` (the generic ingress convention). Rewrites
    ``SCRIPT_NAME``/``PATH_INFO`` so werkzeug's router matches the local
    route and generated URLs carry the external prefix, and honours
    ``X-Forwarded-Proto`` for the scheme.
    """
    from functools import wraps

    def _localize(environ, prefix: str):
        """Strip ``prefix`` off PATH_INFO at a path-segment boundary only:
        '/svc' must localize '/svc/metadata' but never '/svc2/metadata',
        and the result keeps its leading slash (PEP 3333)."""
        path_info = environ.get("PATH_INFO", "")
        if path_info == prefix:
            environ["PATH_INFO"] = "/"
        elif path_info.startswith(prefix + "/"):
            environ["PATH_INFO"] = path_info[len(prefix):]

    @wraps(wsgi_app)
    def wrapper(environ, start_response):
        path_info = environ.get("PATH_INFO", "")
        # Envoy's header carries the original :path INCLUDING any query
        # string — only the path part participates in prefix derivation
        original = environ.get(
            "HTTP_X_ENVOY_ORIGINAL_PATH", ""
        ).split("?", 1)[0]
        if original:
            local = path_info.rstrip("/")
            # match against the rstripped original too: '/svc/metadata/'
            # must derive the same prefix as '/svc/metadata' — otherwise a
            # trailing-slash request turns the WHOLE original path into
            # SCRIPT_NAME and corrupts generated URLs (round-5 advisor)
            stripped = original.rstrip("/")
            if local and stripped.endswith(local):
                # the prefix is the full original path minus the local path
                prefix = stripped[: -len(local)]
            else:
                # header names the prefix itself (or PATH_INFO already IS
                # the full external path, which _localize then strips)
                prefix = original
            prefix = prefix.rstrip("/")
            environ["SCRIPT_NAME"] = prefix
            if prefix:
                _localize(environ, prefix)
        else:
            prefix = environ.get("HTTP_X_FORWARDED_PREFIX", "").rstrip("/")
            if prefix:
                environ["SCRIPT_NAME"] = prefix
                _localize(environ, prefix)
        scheme = environ.get("HTTP_X_FORWARDED_PROTO", "")
        if scheme:
            environ["wsgi.url_scheme"] = scheme
        return wsgi_app(environ, start_response)

    return wrapper


class RequestContext:
    """Per-request state (the no-flask equivalent of flask.g)."""

    def __init__(self, config: Dict[str, Any]):
        self.config = config
        self.start_time = timeit.default_timer()
        self.collection_dir: Optional[str] = None
        self.current_revision: Optional[str] = None
        self.revision: Optional[str] = None
        # True when the client named a revision explicitly (?revision= or
        # header): the hot-swap override map must not redirect a pin
        self.revision_pinned: bool = False
        # per-phase durations (seconds) recorded by the view handlers via
        # phase(); rendered into the response's Server-Timing header
        self.timings: Dict[str, float] = {}

    @contextlib.contextmanager
    def phase(self, name: str):
        """Time one request phase (decode/predict/encode). Repeated phases
        accumulate. Doubles as a telemetry span, so a traced server process
        shows per-request phases on the same timeline as device work."""
        from gordo_tpu.observability import telemetry

        with telemetry.span(f"serve_{name}"):
            t0 = timeit.default_timer()
            try:
                yield
            finally:
                self.timings[name] = self.timings.get(name, 0.0) + (
                    timeit.default_timer() - t0
                )


class GordoServer:
    url_map = Map(
        [
            Rule("/healthcheck", endpoint="healthcheck"),
            Rule("/readiness", endpoint="readiness"),
            Rule("/server-version", endpoint="server_version"),
            Rule("/metrics", endpoint="metrics"),
            # read-only introspection (server/debug.py), 404 unless
            # GORDO_TPU_DEBUG_ENDPOINTS=1
            Rule("/debug/flight", endpoint="debug_flight"),
            Rule("/debug/vars", endpoint="debug_vars"),
            Rule("/debug/config", endpoint="debug_config"),
            Rule("/debug/slo", endpoint="debug_slo"),
            Rule("/debug/drift", endpoint="debug_drift"),
            Rule("/debug/prewarm", endpoint="debug_prewarm"),
            Rule("/debug/profile", endpoint="debug_profile"),
            Rule("/debug/perf", endpoint="debug_perf"),
            Rule("/gordo/v0/openapi.json", endpoint="openapi_spec"),
            Rule(
                "/gordo/v0/<gordo_project>/models",
                endpoint="model_list",
            ),
            Rule(
                "/gordo/v0/<gordo_project>/expected-models",
                endpoint="expected_models",
            ),
            Rule(
                "/gordo/v0/<gordo_project>/revisions",
                endpoint="revision_list",
            ),
            Rule(
                "/gordo/v0/<gordo_project>/<gordo_name>/prediction",
                endpoint="base_prediction",
                methods=["POST"],
            ),
            Rule(
                "/gordo/v0/<gordo_project>/<gordo_name>/anomaly/prediction",
                endpoint="anomaly_prediction",
                methods=["POST"],
            ),
            Rule(
                "/gordo/v0/<gordo_project>/<gordo_name>/metadata",
                endpoint="metadata_view",
            ),
            Rule(
                "/gordo/v0/<gordo_project>/<gordo_name>/healthcheck",
                endpoint="metadata_view",
            ),
            Rule(
                "/gordo/v0/<gordo_project>/<gordo_name>/download-model",
                endpoint="download_model",
            ),
        ],
        strict_slashes=False,
    )

    def __init__(
        self,
        config: Optional[Dict[str, Any]] = None,
        prometheus_registry=None,
    ):
        self.config = default_config()
        if config:
            self.config.update(config)
        self.testing = False
        self._ready_memo: set = set()
        # fleet observability hooks: SLO gauges + window state and the
        # device-telemetry sampler ride every telemetry-shard flush (both
        # idempotent; no-ops until GORDO_TPU_TELEMETRY_DIR enables shards)
        slo.install_shard_hooks()
        from gordo_tpu.observability import device as device_telemetry

        device_telemetry.install_shard_hooks()
        # drift detector windows ride the same shard flushes (no-op until
        # GORDO_TPU_DRIFT_DETECT records anything)
        drift.install_shard_hooks()
        # latency-attribution windows + perf-sentinel gauges likewise
        # (no-op until their knobs record anything)
        attribution.install_shard_hooks()
        sentinel.install_shard_hooks()
        self._prometheus = None
        if self.config["ENABLE_PROMETHEUS"]:
            from gordo_tpu.server.prometheus.metrics import (
                GordoServerPrometheusMetrics,
            )

            self._prometheus = GordoServerPrometheusMetrics(
                project=self.config.get("PROJECT"),
                registry=prometheus_registry,
            )

    # a revision is a plain directory-name token; anything with path
    # separators or dot-runs would escape the model collection tree
    _REVISION_RE = re.compile(r"^[A-Za-z0-9][A-Za-z0-9._-]*$")

    # ------------------------------------------------------------ dispatch
    def _resolve_revision(self, ctx: RequestContext, request):
        """?revision=/header override with 410 on missing (ref :171-189).

        Duck-typed over ``request.args.get`` / ``request.headers.get`` so
        the socket fast lane (server/fastlane.py) shares this exact
        resolution; returns a :class:`views.PlainResponse` on error (the
        WSGI edge converts, the fast lane writes it straight out)."""
        collection_dir = self.config.get("MODEL_COLLECTION_DIR") or os.environ.get(
            "MODEL_COLLECTION_DIR", ""
        )
        ctx.collection_dir = collection_dir
        ctx.current_revision = os.path.basename(os.path.normpath(collection_dir or ""))
        revision = request.args.get("revision") or request.headers.get("revision")
        if revision:
            ctx.revision_pinned = True
            candidate = os.path.join(collection_dir, "..", revision)
            if (
                not self._REVISION_RE.match(revision)
                or ".." in revision
                or not os.path.isdir(candidate)
            ):
                ctx.revision = revision
                return views.PlainResponse(
                    simplejson.dumps({"error": f"Revision '{revision}' not found."}),
                    status=410,
                )
            ctx.collection_dir = candidate
            ctx.revision = revision
        else:
            ctx.revision = ctx.current_revision
        return None

    def expected_models(self):
        """The project's expected machine list: the EXPECTED_MODELS env, or
        the workflow-staged file (EXPECTED_MODELS_FILE — large fleets:
        inlining 10k names into a Deployment env would blow k8s object-size
        limits). The file is read per call, not at boot: stage-config may
        write it after pod start. Raises OSError/ValueError when a declared
        file is unreadable. Shared by /readiness and the
        /expected-models route so the two can never disagree."""
        expected = self.config.get("EXPECTED_MODELS") or []
        expected_file = self.config.get("EXPECTED_MODELS_FILE")
        if not expected and expected_file:
            with open(expected_file) as fh:
                expected = json.load(fh)
        return expected

    def _readiness_response(self, ctx: RequestContext) -> Response:
        """200 iff every expected artifact is present in the collection dir
        (503 otherwise; 200 when no expectation is set).

        This is what makes revision rollover zero-downtime: the workflow
        deploys the new revision's server at DAG start, but with a
        readiness probe on this route plus maxUnavailable: 0, the previous
        revision's pods keep serving until the new revision's models have
        all been built.
        """
        # memoized once ready: artifacts of a revision are never un-built,
        # and MODEL_COLLECTION_DIR is immutable per pod — without this,
        # every kubelet probe would re-stat the whole fleet (10k models x
        # every replica, forever) against the shared volume
        memo_key = ctx.collection_dir
        if memo_key in self._ready_memo:
            return Response(
                simplejson.dumps({"ready": True}), mimetype="application/json"
            )
        try:
            expected = self.expected_models()
        except (OSError, ValueError):
            expected_file = self.config.get("EXPECTED_MODELS_FILE")
            return Response(
                simplejson.dumps(
                    {"ready": False,
                     "missing": [f"(expected-models file "
                                 f"{expected_file!r} unreadable)"],
                     "n_missing": 1}
                ),
                status=503,
                mimetype="application/json",
            )
        missing = [
            name for name in expected
            if not os.path.exists(
                os.path.join(ctx.collection_dir or "", name, "metadata.json")
            )
        ]
        if missing:
            return Response(
                simplejson.dumps(
                    {"ready": False, "missing": missing[:20],
                     "n_missing": len(missing)}
                ),
                status=503,
                mimetype="application/json",
            )
        self._ready_memo.add(memo_key)
        return Response(
            simplejson.dumps({"ready": True}), mimetype="application/json"
        )

    def dispatch_request(self, request: Request) -> Response:
        ctx = RequestContext(self.config)
        # every request runs under a trace context: continue the caller's
        # W3C traceparent when present, else mint a fresh trace. The root
        # span and everything below it (decode/predict/encode phases, the
        # batcher queue, the fused device call) attach to one tree the
        # flight recorder can keep when the request turns out interesting.
        with tracing.request_root(
            request.headers.get("traceparent")
        ) as rtrace:
            with telemetry.span(
                "serve_request", method=request.method
            ) as root_span:
                response = self._route_and_dispatch(
                    ctx, request, root_span
                )
            # Server-Timing: the reference's single request_walltime_s
            # entry (kept first, same name/unit, for client parity) plus a
            # per-phase breakdown recorded by the views (decode/predict/
            # encode — where a prediction request's time actually went).
            # Seconds throughout, marked by the _s suffix (the reference
            # already broke the spec's milliseconds convention; consistency
            # wins over mixing units). Stamped on EVERY response — error
            # classes included (4xx/5xx, shed 503, deadline 504): the
            # failures are exactly the responses worth attributing.
            runtime_s = timeit.default_timer() - ctx.start_time
            entries = [f"request_walltime_s;dur={runtime_s}"]
            entries.extend(
                f"{name}_s;dur={duration}"
                for name, duration in ctx.timings.items()
            )
            response.headers["Server-Timing"] = ", ".join(entries)
            if ctx.revision:
                response.headers["revision"] = ctx.revision
            # the trace id echoed back: a caller quoting this header names
            # the exact trace in /debug/flight and the JSON logs
            response.headers["X-Gordo-Trace"] = rtrace.trace_id
            logger.debug(
                "request %s %s -> %d in %.4fs",
                request.method, request.path, response.status_code,
                runtime_s,
            )
        matched_rule = request.environ.get("gordo_tpu.rule")
        rule = matched_rule if matched_rule is not None else request.path
        model = request.environ.get("gordo_tpu.model", "")
        flight.default_recorder().observe(
            rtrace.collector,
            status=response.status_code,
            duration_s=runtime_s,
            endpoint=rule,
            model=model,
        )
        observe_request_outcome(
            # the raw path is fine for the bounded flight ring above, but
            # metric labels must stay bounded: scanner probes of random
            # URLs collapse into one series, matching the prometheus layer
            matched_rule if matched_rule is not None else "(unmatched)",
            model, runtime_s, response.status_code,
            # SLO windows track the two prediction routes only (the routes
            # a latency objective is about); the rule suffix identifies
            # them the same way on both lanes
            slo_eligible=bool(matched_rule)
            and matched_rule.endswith("/prediction"),
            phases=ctx.timings,
        )
        return response

    def _route_and_dispatch(
        self, ctx: RequestContext, request: Request, root_span
    ) -> Response:
        adapter = self.url_map.bind_to_environ(request.environ)
        try:
            rule, values = adapter.match(return_rule=True)
            endpoint = rule.endpoint
            # the metrics layer labels by the matched RULE, not the raw
            # path: raw paths are unbounded label cardinality (any bot
            # scanning random URLs would mint a new timeseries per hit)
            request.environ["gordo_tpu.rule"] = rule.rule
            root_span.set_attrs(endpoint=endpoint, rule=rule.rule)
        except MethodNotAllowed as exc:
            # the PATH matched a real route (wrong method): keep endpoint
            # attribution in the metrics instead of lumping the 405 into
            # the unmatched bucket with scanner noise
            if exc.valid_methods:
                try:
                    rule, _ = adapter.match(
                        method=exc.valid_methods[0], return_rule=True
                    )
                    request.environ["gordo_tpu.rule"] = rule.rule
                except HTTPException:
                    pass
            return exc.get_response()
        except HTTPException as exc:
            return exc.get_response()
        if values.get("gordo_name"):
            request.environ["gordo_tpu.model"] = values["gordo_name"]
            root_span.set_attrs(model=values["gordo_name"])

        # ----------------------------------------------- serving resilience
        # (every knob defaults off: with none set, this block admits every
        # request with no deadline and adds nothing to the response)
        admitted = False
        scope = None
        shed = None
        if endpoint in _GATED_ENDPOINTS:
            shed = resilience.try_admit()
            if shed is None:
                admitted = True
                scope = resilience.request_scope(
                    model=values.get("gordo_name"),
                    deadline_ms=resilience.deadline_ms_from(request.headers),
                )
                scope.__enter__()

        try:
            return self._dispatch_endpoint(
                ctx, request, endpoint, values, shed
            )
        finally:
            if admitted:
                scope.__exit__(None, None, None)
                resilience.release()

    def _dispatch_endpoint(
        self, ctx: RequestContext, request: Request, endpoint, values, shed
    ) -> Response:
        if shed is not None:
            # admission control said no: fast 503 + Retry-After, the
            # LB/client backs off instead of queueing behind the device
            response = Response(
                simplejson.dumps(shed),
                status=503,
                mimetype="application/json",
            )
            response.headers["Retry-After"] = str(
                int(math.ceil(shed.get("retry-after-seconds", 0.0)))
            )
            return response

        error = self._resolve_revision(ctx, request)
        if error is not None:
            response = error.to_werkzeug()
        else:
            try:
                if endpoint == "healthcheck":
                    stuck = resilience.stuck_device_call_s()
                    if stuck is not None:
                        # device watchdog: the dispatcher has been inside
                        # ONE device call past the threshold — tell k8s to
                        # restart this pod instead of routing to it
                        response = Response(
                            simplejson.dumps(
                                {"error": "device watchdog: dispatcher "
                                 "stuck in one device call",
                                 "stuck-seconds": round(stuck, 3)}
                            ),
                            status=503,
                            mimetype="application/json",
                        )
                    else:
                        response = Response("", status=200)
                elif endpoint == "readiness":
                    response = self._readiness_response(ctx)
                elif endpoint == "server_version":
                    response = views.json_response(ctx, {"version": __version__})
                elif endpoint == "openapi_spec":
                    from gordo_tpu.server.openapi import openapi_document

                    response = Response(
                        simplejson.dumps(openapi_document()),
                        mimetype="application/json",
                    )
                elif endpoint.startswith("debug_"):
                    from gordo_tpu.server import debug

                    response = debug.dispatch(
                        endpoint, self.config, request=request
                    )
                elif endpoint == "metrics":
                    if self._prometheus is not None:
                        response = Response(
                            self._prometheus.expose(),
                            mimetype="text/plain; version=0.0.4",
                        )
                    else:
                        # no prometheus_client required: with a telemetry
                        # dir configured, /metrics serves the merged fleet
                        # view straight from the per-worker shards
                        fleet = shared.render_fleet_text()
                        if fleet is None:
                            response = Response(
                                "metrics disabled", status=404
                            )
                        else:
                            response = Response(
                                fleet,
                                mimetype="text/plain; version=0.0.4",
                            )
                elif endpoint == "expected_models":
                    # the SAME resolution as /readiness (env or staged
                    # file) — the two must never disagree about the fleet
                    try:
                        expected = self.expected_models()
                    except (OSError, ValueError):
                        # mirror /readiness: a declared-but-unreadable
                        # expectation is an error, not an empty fleet
                        expected = None
                    if expected is None:
                        response = Response(
                            simplejson.dumps(
                                {"error": "expected-models file declared "
                                 "but unreadable"}
                            ),
                            status=503,
                            mimetype="application/json",
                        )
                    else:
                        response = views.json_response(
                            ctx, {"expected-models": expected}
                        )
                else:
                    handler = getattr(views, endpoint)
                    response = handler(ctx, request, **values)
            except HTTPException as exc:
                response = exc.get_response()
            except Exception:
                logger.exception("Unhandled server error")
                response = Response(
                    simplejson.dumps({"error": "Internal server error"}),
                    status=500,
                    mimetype="application/json",
                )
        return response

    def wsgi_app(self, environ, start_response):
        from werkzeug.wsgi import ClosingIterator

        request = Request(environ)
        # in-flight accounting for graceful drain: decremented when the
        # response iterable is CLOSED (after the body hit the socket), so
        # a draining worker cannot exit mid-write
        resilience.request_started()
        try:
            if self._prometheus is not None:
                start = timeit.default_timer()
                response = self.dispatch_request(request)
                self._prometheus.record(request, response, start)
            else:
                response = self.dispatch_request(request)
            return ClosingIterator(
                response(environ, start_response), resilience.request_finished
            )
        except BaseException:
            resilience.request_finished()
            raise

    def __call__(self, environ, start_response):
        return self.wsgi_app(environ, start_response)

    # ------------------------------------------------------- test support
    def test_client(self):
        from werkzeug.test import Client

        return Client(self)


def build_app(
    config: Optional[Dict[str, Any]] = None, prometheus_registry=None
) -> GordoServer:
    """Build the WSGI app (reference build_app, server.py:139-231; the
    proxy adaptation mirrors its :156)."""
    app = GordoServer(config, prometheus_registry=prometheus_registry)
    # instance attribute shadows the bound method, exactly like the
    # reference's ``app.wsgi_app = adapt_proxy_deployment(app.wsgi_app)``
    app.wsgi_app = adapt_proxy_deployment(app.wsgi_app)
    # revision hot-swap watcher (server/hotswap.py): a daemon thread per
    # serving process, polling for committed delta revisions. Gated on
    # GORDO_TPU_HOT_SWAP — without it this is a single env read.
    from gordo_tpu.server import hotswap

    if hotswap.enabled():
        collection_dir = app.config.get("MODEL_COLLECTION_DIR") or os.environ.get(
            "MODEL_COLLECTION_DIR", ""
        )
        if collection_dir:
            hotswap.start_watcher(collection_dir)
        else:
            logger.warning(
                "GORDO_TPU_HOT_SWAP set but MODEL_COLLECTION_DIR unset; "
                "no hot-swap watcher started"
            )
    return app


class ChipLayoutError(ValueError):
    """The pool that was asked for cannot have one process per TPU chip."""


def resolve_workers(workers: Optional[int]) -> "tuple[int, int]":
    """``(workers, chips)`` for a serving pool on this host.

    A TPU chip belongs to one process at a time and a serving worker
    dispatches to one device, so on a host with chips the pool is one worker
    per chip: that is the default, fewer is allowed, more is refused here —
    a surplus worker could never start its backend and the arbiter would
    respawn it forever. Without chips (CPU) the default stays two workers.
    Counted without touching jax (util/chips.py)."""
    from gordo_tpu.util import chips as chips_mod

    chips = chips_mod.attached_tpu_chips()
    if workers is None:
        workers = chips or 2
    workers = max(1, workers)
    if chips and workers > chips:
        raise ChipLayoutError(
            f"{workers} workers asked for, but this host has {chips} TPU "
            f"chip(s) and a chip belongs to one process at a time: run at "
            f"most {chips} worker(s) here"
        )
    return workers, chips


def run_server(
    host: str = "0.0.0.0",
    port: int = 5555,
    workers: Optional[int] = None,
    worker_connections: int = 50,
    warmup: bool = False,
    **kwargs,
):
    """
    Serve the app (reference run_server shells out to gunicorn,
    server.py:233-297; here: a prefork pool of threaded werkzeug servers).

    The listening socket is bound once and inherited by ``workers`` forked
    processes that all accept on it; each worker serves threaded (device
    compute releases the GIL, so threads provide request concurrency on one
    warm model cache per worker). ``workers=None`` means one per TPU chip
    of the host, or two without chips; each forked worker is pinned to its
    own chip before it initialises jax, and the arbiter parent never
    touches jax (:func:`resolve_workers`). A pool that cannot have one
    process per chip raises :class:`ChipLayoutError`: at once when more
    workers than chips were asked for, or when the first unpinned worker
    finds a TPU the arbiter had not counted. With prometheus enabled and
    workers > 1, PROMETHEUS_MULTIPROC_DIR is set before the per-worker app
    build so /metrics aggregates across the pool. ``worker_connections`` is
    accepted for reference-CLI parity; the werkzeug server has no
    connection cap.
    """
    import signal
    import socket
    import tempfile
    import threading

    from werkzeug.serving import make_server

    def _make_http_server(app, listen_sock):
        """The worker's HTTP front end: the socket fast lane when
        ``GORDO_TPU_FAST_LANE=1`` (hot prediction routes served at
        socket level, everything else through the same WSGI app
        in-process — server/fastlane.py), else the threaded werkzeug
        server. Both expose serve_forever/shutdown/server_close, so the
        drain handling below is lane-agnostic."""
        from gordo_tpu.server import fastlane

        if fastlane.enabled():
            return fastlane.make_server(
                app, host, port, fd=listen_sock.fileno()
            )
        return make_server(
            host, port, app, threaded=True, fd=listen_sock.fileno()
        )

    workers, chips = resolve_workers(workers)
    if workers > 1 and os.environ.get("GORDO_TPU_UDS_PATH"):
        # forked workers would fight over one socket path (each bind
        # unlinks its predecessor's), so the Unix-domain lane is a
        # single-worker feature; the TCP listener is SO_REUSEADDR-shared
        # and unaffected
        logger.warning(
            "GORDO_TPU_UDS_PATH ignored with %d workers (a prefork pool "
            "cannot share one socket path)", workers,
        )
        os.environ.pop("GORDO_TPU_UDS_PATH", None)
    # multi-worker pools get a telemetry shard dir by default: without it
    # a /metrics or /debug/vars scrape answered by one worker would show
    # that worker's numbers only (observability/shared.py). Honour an
    # operator-provided dir; the env propagates through fork to children.
    if workers > 1 and not shared.enabled():
        os.environ[shared.ENV_DIR] = tempfile.mkdtemp(
            prefix="gordo-telemetry-"
        )
    if (
        workers > 1
        and default_config()["ENABLE_PROMETHEUS"]
        and "PROMETHEUS_MULTIPROC_DIR" not in os.environ
    ):
        os.environ["PROMETHEUS_MULTIPROC_DIR"] = tempfile.mkdtemp(
            prefix="gordo-prometheus-"
        )
        from gordo_tpu.server.prometheus.metrics import use_multiprocess_values

        use_multiprocess_values()

    def _boot_worker():
        # per process, AFTER any fork (jax/XLA state must not cross fork):
        # place the persistent compile cache — with or without warmup, so
        # lazy compiles are kept too — and say which device this worker
        # took. Either failing fails the worker's boot.
        from gordo_tpu.observability import device
        from gordo_tpu.util.xla_cache import setup_persistent_xla_cache

        setup_persistent_xla_cache()
        placement = device.log_placement(logger, "run-server worker")
        if workers > 1 and not chips and placement["platform"] == "tpu":
            # the arbiter counted no chip (util/chips.py reads sysfs and
            # /dev, which a container may hide), so it pinned nobody — yet
            # jax found a TPU. This worker now holds every chip and its
            # siblings can never start: say so and stop the pool, before
            # any warmup
            raise ChipLayoutError(
                f"this worker found {placement['device_count']} TPU chip(s) "
                f"({placement['device_kind']}) but the launcher counted 0 on "
                f"this host and started {workers} unpinned workers; a chip "
                f"belongs to one process at a time: run with --workers 1"
            )
        if warmup:
            _warm_worker()
        # publish what boot counted (warmup compiles, failures) to the
        # pool's merged view now, not at this worker's first request
        shared.flush(force=True)

    def _warm_worker():
        # On a fresh boot every worker warms itself — workers fork together
        # and the XLA cache has no in-flight dedupe — but the persistent
        # cache makes restarts (and later workers' stragglers) near-free.
        collection_dir = default_config()["MODEL_COLLECTION_DIR"]
        if not collection_dir:
            logger.warning("warmup requested but MODEL_COLLECTION_DIR unset")
            return
        try:
            from gordo_tpu.server.warmup import warmup_collection

            warmup_collection(collection_dir)
        except Exception:  # noqa: BLE001 — the worker still serves: an
            # unreadable collection dir would otherwise crash every
            # respawned worker until the fast-death throttle kills the
            # whole pool. But a warmup that failed is an ERROR and is
            # counted, because every program now compiles inside a request
            metric_catalog.WARMUP_FAILURES.labels(scope="collection").inc()
            logger.exception(
                "serving warmup FAILED; programs will compile in the "
                "request path"
            )

    def _install_drain_handler(server):
        """Graceful drain: the first SIGTERM stops the accept loop (from a
        helper thread — shutdown() called from the serving thread's own
        signal frame would deadlock serve_forever) and lets in-flight
        requests finish; a second SIGTERM exits immediately."""

        def _on_term(signum, frame):
            if not resilience.begin_drain():
                logger.warning("second SIGTERM during drain; exiting now")
                os._exit(0)
            logger.info(
                "SIGTERM: draining — closing listener, finishing %d "
                "in-flight request(s) within %.1fs",
                resilience.inflight_requests(), resilience.drain_budget_s(),
            )
            threading.Thread(
                target=server.shutdown, name="gordo-drain", daemon=True
            ).start()

        signal.signal(signal.SIGTERM, _on_term)

    def _finish_drain(server):
        """After serve_forever returns on a drain: wait out in-flight
        requests (bounded by the drain budget), then close the listener."""
        if resilience.is_draining():
            resilience.wait_drained()
            logger.info("drain complete; worker exiting")
        try:
            server.server_close()
        except OSError:  # pragma: no cover - double-close on some paths
            pass

    def _register_node(listen_sock):
        """Gateway membership (server/membership.py): when
        ``GORDO_TPU_GATEWAY_DIR`` is set, this server heartbeats a lease
        in the shared directory so the gateway places its ring shard
        here; the registration is withdrawn on exit (graceful leave —
        the gateway re-places the shard on the next membership poll
        instead of waiting out the lease timeout). One lease per server,
        held by the process that owns the listening socket: workers
        share the socket, so the pool is one node."""
        from gordo_tpu.server import membership

        directory = membership.gateway_dir()
        if not directory:
            return None
        advertise = os.environ.get("GORDO_TPU_GATEWAY_ADVERTISE")
        if not advertise:
            bind_host = (
                socket.gethostname() if host in ("0.0.0.0", "::") else host
            )
            advertise = f"{bind_host}:{listen_sock.getsockname()[1]}"
        # advertise the Unix-domain lane (GORDO_TPU_UDS_PATH) alongside the
        # TCP address so a co-located gateway can prefer it; the fast lane
        # binds the path when it mounts, and the gateway falls back to TCP
        # if the socket never appears
        from gordo_tpu.server import fastlane

        uds = fastlane.uds_path() if fastlane.enabled() else None
        try:
            return membership.NodeRegistration(
                directory, address=advertise, uds=uds
            )
        except OSError:
            logger.exception(
                "gateway registration failed; serving without membership"
            )
            return None

    sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    sock.bind((host, port))
    sock.listen(max(128, worker_connections))

    registration = _register_node(sock)
    logger.info(
        "Starting server on %s:%s with %d worker(s) (%s)", host, port,
        workers,
        f"{chips} TPU chip(s) on this host, one worker per chip"
        if chips else "no TPU chip on this host",
    )
    if workers == 1:
        # single worker: serve inline, no arbiter
        app = build_app()
        _boot_worker()
        server = _make_http_server(app, sock)
        _install_drain_handler(server)
        try:
            server.serve_forever()
            _finish_drain(server)
        finally:
            if registration is not None:
                registration.close()
        return

    # Prefork pool with a pure arbiter parent (the reference's gunicorn
    # arbiter, server.py:233-297): the parent owns no serving threads, so
    # forking replacement workers after a death is fork-safe. Dead workers
    # are reaped (retiring their multiprocess metric files — gunicorn
    # child_exit hook analog) and respawned, so the pool never shrinks.
    import signal
    import time as _time

    from gordo_tpu.server.prometheus.server import mark_worker_dead

    worker_pids: set = set()
    # pid -> pool slot: with several chips a worker is pinned to the chip of
    # its slot, and its replacement inherits slot and chip
    slots: dict = {}
    spawn_times: dict = {}
    ready_fds: dict = {}
    shutting_down = False
    # A worker that dies before signalling readiness (one byte on its
    # readiness pipe, sent just before serve_forever) OR within
    # FAST_DEATH_S of its spawn counts as a boot failure; MAX_FAST_DEATHS
    # consecutive ones stop the respawn loop (the gunicorn arbiter's
    # worker-boot-error throttle) instead of fork-bombing. The pipe —
    # not wall-clock alone — classifies deaths because warmup makes a
    # legitimate boot take arbitrarily long: a worker OOM-killed 30s into
    # model loading must still count as a boot failure.
    FAST_DEATH_S = 2.0
    MAX_FAST_DEATHS = 5
    fast_deaths = 0

    def _serve_child(ready_w: int, slot: int) -> "None":  # never returns
        # any escape path must os._exit: an exception unwinding out of the
        # forked child would execute the arbiter's inherited finally block
        # (SIGTERM-ing healthy siblings) in the child
        try:
            if chips > 1:
                # before anything initialises jax in this process
                from gordo_tpu.util import chips as chips_mod

                chips_mod.pin_process_to_chip(slot)
            signal.signal(signal.SIGCHLD, signal.SIG_DFL)
            # default TERM until the server exists (a TERM during boot just
            # kills the booting worker; there is nothing to drain yet)
            signal.signal(signal.SIGTERM, signal.SIG_DFL)
            # app built per worker process: model cache and metric values are
            # process-local (metrics aggregate via the multiprocess dir)
            app = build_app()
            _boot_worker()
            server = _make_http_server(app, sock)
            # from here on SIGTERM drains: stop accepting, finish in-flight
            # within the budget, exit — revision rollover no longer cuts
            # responses mid-flight
            _install_drain_handler(server)
            try:
                os.write(ready_w, b"R")
                os.close(ready_w)
            except OSError:
                pass
            server.serve_forever()
            _finish_drain(server)
        except ChipLayoutError as exc:
            # not a boot failure to retry: hand the arbiter the reason over
            # the readiness pipe, so it stops the whole pool with it
            logger.error("%s", exc)
            os.write(ready_w, b"L" + str(exc).encode())
            os._exit(1)
        except BaseException:
            logger.exception("worker failed to boot/serve")
            os._exit(1)
        os._exit(0)

    def _spawn(slot: int) -> None:
        start = _time.monotonic()
        # the write end is held ONLY by this child (the parent closes its
        # copy right after fork, and earlier siblings predate the pipe), so
        # the child's death guarantees EOF — _reap's read can never block
        ready_r, ready_w = os.pipe()
        os.set_blocking(ready_r, False)
        pid = os.fork()
        if pid == 0:
            os.close(ready_r)
            # also close inherited read ends of live siblings' readiness
            # pipes — harmless for EOF semantics, but stale fds would
            # otherwise accumulate in long-lived workers over respawn churn
            for fd in ready_fds.values():
                try:
                    os.close(fd)
                except OSError:
                    pass
            _serve_child(ready_w, slot)
        os.close(ready_w)
        # spawn time recorded before the pid becomes reapable via
        # worker_pids, so _reap never sees a missing entry
        spawn_times[pid] = start
        ready_fds[pid] = ready_r
        slots[pid] = slot
        worker_pids.add(pid)

    def _reap():
        # Called ONLY from the arbiter's poll loop (the SIGCHLD handler is
        # a no-op waker): reap-and-respawn used to run inside the handler,
        # and a handler interrupting a loop-side sweep mid-pid could
        # double-count one death — and rapid consecutive deaths were
        # OBSERVED leaving an unreaped zombie and a stalled pool when
        # delivery landed in an unlucky window. Single-threaded sweeps are
        # immune to both; worst-case reaction is one poll tick.
        # Only pids in worker_pids are waited on, so exit statuses of
        # unrelated subprocesses are never stolen from their owners.
        nonlocal fast_deaths
        for pid in list(worker_pids):
            try:
                reaped, _status = os.waitpid(pid, os.WNOHANG)
            except ChildProcessError:
                worker_pids.discard(pid)
                continue
            if reaped == pid:
                worker_pids.discard(pid)
                slot = slots.pop(pid)
                mark_worker_dead(pid)
                # retire the dead worker's telemetry shard too, or its last
                # counters would stay in the fleet sum forever
                shared.mark_shard_dead(pid)
                if shutting_down:
                    continue
                lifetime = _time.monotonic() - spawn_times.pop(pid, 0.0)
                ready_r = ready_fds.pop(pid, None)
                became_ready = False
                if ready_r is not None:
                    try:
                        said = os.read(ready_r, 1024)
                    except OSError:
                        said = b""
                    os.close(ready_r)
                    if said.startswith(b"L"):
                        # a worker found a chip layout no respawn can fix
                        raise ChipLayoutError(said[1:].decode())
                    became_ready = said == b"R"
                if lifetime < FAST_DEATH_S or not became_ready:
                    fast_deaths += 1
                else:
                    fast_deaths = 0
                if fast_deaths >= MAX_FAST_DEATHS:
                    logger.error(
                        "worker %d died after %.1fs; %d consecutive boot "
                        "failures — throttling respawn",
                        pid, lifetime, fast_deaths,
                    )
                    continue
                logger.warning("worker %d died; spawning replacement", pid)
                _spawn(slot)

    # SIGTERM must run the cleanup below (the default action would kill the
    # arbiter outright, orphaning the pool), so convert it to SystemExit
    def _terminate(signum, frame):
        raise SystemExit(0)

    try:
        # handlers installed inside the try so a SIGTERM arriving while
        # workers are being forked still reaches the cleanup block
        signal.signal(signal.SIGTERM, _terminate)
        # a no-op HANDLER (not SIG_IGN, which would auto-discard child
        # statuses and break waitpid): keeps children reapable while all
        # actual reaping happens in the poll loop below
        signal.signal(signal.SIGCHLD, lambda signum, frame: None)
        for slot in range(workers):
            _spawn(slot)
        _reap()
        RETRY_S = 10.0
        last_retry = _time.monotonic()
        while True:
            # poll-sleep arbiter (gunicorn-style): every tick sweeps with
            # WNOHANG — SIGCHLD delivery is not a reliable queue, so the
            # sweep, not the signal, is the source of truth
            _reap()
            if fast_deaths >= MAX_FAST_DEATHS and not worker_pids:
                raise RuntimeError(
                    "all workers failed at boot; see logs for the child error"
                )
            # throttled healing: once the fast-death limit trips, lost
            # slots are retried at most once per RETRY_S (a transient boot
            # failure must not permanently shrink the pool, but a
            # persistent one must not fork-bomb)
            now = _time.monotonic()
            if (
                len(worker_pids) < workers
                and fast_deaths >= MAX_FAST_DEATHS
                and now - last_retry >= RETRY_S
            ):
                last_retry = now
                logger.warning(
                    "pool at %d/%d workers; retrying one respawn",
                    len(worker_pids), workers,
                )
                _spawn(min(set(range(workers)) - set(slots.values())))
            _time.sleep(1)
    except (KeyboardInterrupt, SystemExit):
        pass
    finally:
        shutting_down = True
        # a second SIGTERM must not abort the cleanup midway
        signal.signal(signal.SIGTERM, signal.SIG_IGN)
        for pid in list(worker_pids):
            try:
                os.kill(pid, signal.SIGTERM)
            except ProcessLookupError:
                pass
        for pid in list(worker_pids):
            try:
                os.waitpid(pid, 0)
            except ChildProcessError:
                pass
        if registration is not None:
            registration.close()
