"""
Serving warmup: precompile every artifact's predict programs before traffic.

The first predict of a (spec, padded-shape) bucket pays an XLA compile — on
a TPU that is tens of seconds of first-request latency (the reference has no
analog: its Keras models execute eagerly, gordo/server loads pickles lazily
per request, server/utils.py:323-343). Serving shapes here are padded to
power-of-two buckets (ops/train.pad_for_predict), so the program set is
finite: warming compiles the programs for the configured row buckets
(``GORDO_TPU_WARMUP_ROWS``, default 128 and 1024 — a request padding to a
bucket outside that list still pays its first compile), and the
persistent XLA cache (``$JAX_COMPILATION_CACHE_DIR`` or the in-checkout
default, set up by every run-server worker — util/xla_cache.py) carries
compiles across worker processes and restarts.

``run-server --warmup`` (or ``GORDO_TPU_SERVING_WARMUP=1``) runs this in
each worker after fork, before the worker starts accepting; models sharing
a ModelSpec share programs (ops/train._build_predictor caches by spec), so
fleets of same-architecture machines warm in one compile. When the
cross-model batcher is enabled (the run-server default), the warmup
predicts route through it like real traffic — in auto mode the first
predict per architecture runs the batcher's measured self-A/B, so both
the fused programs and the on/off decision are in place before the first
request (pinned by tests).

Commit-once parameter residency (ISSUE 7): besides precompiling, warmup
pins every artifact's params into the batcher's device-resident
``_ParamBank`` (``register_params``) after its first predict commits
them — so the first fused call of real traffic gathers from an
already-stacked bank instead of paying a restack in the request path
(``gordo_server_param_bank_restacks_total`` stays flat from boot).
"""

import logging
import os
import threading
import time
from typing import Iterable, Optional

import numpy as np

from gordo_tpu.util import faults

logger = logging.getLogger(__name__)

# the most recent warmup_collection report (any trigger: boot, hot-swap
# pre-warm, /debug/prewarm) — surfaced on /debug/vars so an operator can
# read the node's warmth (AOT program counts, compile seconds saved)
# without grepping logs
_last_report: Optional[dict] = None
_last_report_lock = threading.Lock()


def last_report() -> Optional[dict]:
    """The most recent warmup report, or None before any warmup ran."""
    with _last_report_lock:
        return None if _last_report is None else dict(_last_report)


def _jax_estimators(model):
    """Yield every fitted BaseJaxEstimator reachable inside an artifact
    (the estimator itself, a sklearn Pipeline's steps, or an anomaly
    detector's base_estimator) — the (spec_, params_) owners the param
    bank stacks."""
    seen = set()
    stack = [model]
    while stack:
        node = stack.pop()
        if id(node) in seen or node is None:
            continue
        seen.add(id(node))
        if hasattr(node, "spec_") and hasattr(node, "params_"):
            yield node
            continue
        if hasattr(node, "base_estimator"):
            stack.append(node.base_estimator)
        if hasattr(node, "steps"):  # sklearn Pipeline
            stack.extend(step for _name, step in node.steps)


def _load_shipped_programs(model, artifact_dir) -> int:
    """Deserialize-first AOT population (ISSUE 14): when the artifact
    ships a ``programs/`` manifest and ``GORDO_TPU_LOAD_SHIPPED_PROGRAMS``
    is on, walk the fingerprint ladder and install every cleared program
    straight into the batcher's AOT cache — BEFORE the first warmup
    predict, so even warmup's own traffic runs on the shipped executables
    instead of paying trace+compile. A manifest rejected on a real-ISA
    mismatch is counted loudly (``gordo_server_aot_programs_total
    {source="rejected"}``) and its programs are never executed; serving
    proceeds on the ordinary compile path. Returns programs installed."""
    from gordo_tpu.serializer import programs as programs_mod
    from gordo_tpu.server.batcher import get_batcher

    if not artifact_dir or not programs_mod.load_enabled():
        return 0
    batcher = get_batcher()
    if batcher is None:
        return 0
    manifest = programs_mod.load_manifest(artifact_dir)
    if manifest is None:
        return 0
    try:
        # chaos hook (ISSUE 16): an ``aot_program_load`` rule rejects this
        # artifact's shipped programs (serving proceeds on the ordinary
        # compile path, counted like a real fingerprint rejection); a
        # ``wedge`` rule stalls here — the slow-disk artifact-load stand-in
        faults.fault_point(
            "aot_program_load", machine=os.path.basename(artifact_dir)
        )
    except Exception as exc:  # noqa: BLE001 — injected: reject, don't crash
        entries = manifest.get("programs") or []
        batcher.note_rejected_shipment(len(entries))
        logger.warning(
            "rejecting %d shipped AOT program(s) from %s: injected "
            "aot_program_load fault (%s)", len(entries), artifact_dir, exc,
        )
        return 0
    status, reason = programs_mod.classify_manifest(manifest)
    if status == "rejected":
        entries = manifest.get("programs") or []
        batcher.note_rejected_shipment(len(entries))
        logger.warning(
            "rejecting %d shipped AOT program(s) from %s: %s — serving "
            "falls back to the jit/prelower path",
            len(entries), artifact_dir, reason,
        )
        return 0
    if status == "cosmetic":
        logger.info(
            "loading shipped AOT programs from %s despite a fingerprint "
            "mismatch: the CPU-feature diff is cosmetic "
            "(prefer-no-gather-style tuning pseudo-features)", artifact_dir,
        )
    by_spec = programs_mod.shipped_index(artifact_dir, manifest)
    loaded = 0
    for estimator in _jax_estimators(model):
        entries = by_spec.get(programs_mod.spec_key(estimator.spec_))
        if entries:
            loaded += batcher.load_shipped(estimator.spec_, entries)
    return loaded


def _prelower_programs(model, bucket_rows, offset, n_features) -> int:
    """AOT pre-lower + compile the batcher's stacked serving programs for
    every (row bucket, fuse-width bucket) this artifact's spec can hit
    (CrossModelBatcher.prelower). Warmup's own predicts only compile the
    width the sequential warmup traffic produces; the wider fuse buckets
    would otherwise pay their trace+compile inside the first real burst.
    Returns how many programs were compiled."""
    from gordo_tpu.ops.train import pad_for_predict
    from gordo_tpu.server.batcher import get_batcher

    batcher = get_batcher()
    if batcher is None:
        return 0
    compiled = 0
    for estimator in _jax_estimators(model):
        for bucket in bucket_rows:
            try:
                X = np.zeros(
                    (int(bucket) + int(offset), n_features), np.float32
                )
                X_pad, n_pad, _ = pad_for_predict(estimator.spec_, X)
                compiled += batcher.prelower(estimator.spec_, X_pad, n_pad)
            except Exception as exc:  # noqa: BLE001 — warmup is best-effort
                logger.warning(
                    "AOT pre-lowering failed for bucket %s: %s", bucket, exc
                )
    return compiled


def _warm_direct_program(model, X, bucket, done: set) -> None:
    """With the batcher on, compile the per-request (unfused) program of
    this artifact's architecture for one row bucket too, once per
    architecture: the self-A/B is a measurement that can fall either way
    from one boot to the next, and the warmup predict above only compiled
    the path it chose. Warming both makes the compiled-program set — and so
    what the persistent cache holds after one boot — the same whichever way
    it fell."""
    from gordo_tpu.server import batcher as batcher_mod

    if batcher_mod.get_batcher() is None:
        return  # every predict is already direct
    key = (tuple(e.spec_ for e in _jax_estimators(model)), int(bucket))
    if key not in done:
        done.add(key)
        with batcher_mod.direct_path():
            model.predict(X)


def _register_params(model) -> int:
    """Commit-once pre-registration: push the artifact's params into the
    cross-model batcher's device-resident bank (when batching is enabled)
    so the first fused call after startup gathers from an already-stacked
    bank instead of paying a restack in the request path. Best-effort —
    returns how many estimators were registered."""
    from gordo_tpu.server.batcher import get_batcher

    batcher = get_batcher()
    if batcher is None:
        return 0
    registered = 0
    for estimator in _jax_estimators(model):
        try:
            batcher.register_params(estimator.spec_, estimator.params_)
            registered += 1
        except Exception as exc:  # noqa: BLE001 — warmup is best-effort
            logger.warning("param-bank pre-registration failed: %s", exc)
    return registered


def _default_bucket_rows():
    """Serving-time row buckets to precompile per model. 128 covers the
    reference benchmark harness shape (100 samples x tags, padded to 128);
    1024 brackets typical client batch sizes. A malformed
    ``GORDO_TPU_WARMUP_ROWS`` falls back to the defaults with a warning —
    warmup is best-effort and must not abort over a config typo."""
    env = os.environ.get("GORDO_TPU_WARMUP_ROWS")
    if env:
        try:
            rows = tuple(
                int(part) for part in env.split(",") if part.strip()
            )
        except ValueError:
            rows = ()
        if rows and all(r > 0 for r in rows):
            return rows
        logger.warning(
            "malformed GORDO_TPU_WARMUP_ROWS=%r; using defaults %s",
            env, DEFAULT_BUCKET_ROWS,
        )
    return DEFAULT_BUCKET_ROWS


DEFAULT_BUCKET_ROWS = (128, 1024)


def _model_names(collection_dir: str) -> list:
    names = []
    for name in sorted(os.listdir(collection_dir)):
        path = os.path.join(collection_dir, name)
        if os.path.isdir(path) and os.path.exists(
            os.path.join(path, "metadata.json")
        ):
            names.append(name)
    return names


def warmup_collection(
    collection_dir: str,
    bucket_rows: Optional[Iterable[int]] = None,
    names: Optional[Iterable[str]] = None,
) -> dict:
    """Load each model in the collection and run one predict per row
    bucket, compiling the serving programs traffic will hit.

    Returns ``{"models": N, "programs": M, "seconds": S, "failed": [...]}``.
    A model that fails to warm is skipped — the lazy path still serves it —
    but loudly: logged at ERROR with its traceback, counted in
    ``gordo_server_warmup_failures_total`` and listed under ``failed``.
    """
    from gordo_tpu.server.utils import load_metadata, load_model

    t0 = time.monotonic()
    # kick the native codec build in the background: it races the (much
    # slower) XLA compiles below, so the first request finds the parser/
    # encoder .so ready without warmup ever blocking on gcc
    try:
        from gordo_tpu import native

        native.prebuild(block=False)
    except Exception:  # noqa: BLE001 — warmup is best-effort
        pass
    if bucket_rows is None:
        bucket_rows = _default_bucket_rows()
    names = list(names) if names is not None else _model_names(collection_dir)
    programs = 0
    aot_programs = 0
    warmed = 0
    registered = 0
    failed = []
    # snapshot the batcher's AOT source accounting so the report's
    # shipped/rejected/seconds-saved keys cover exactly THIS warmup
    from gordo_tpu.server.batcher import peek_batcher

    def _aot_stats():
        batcher = peek_batcher()
        if batcher is None:
            return {"shipped": 0, "rejected": 0, "compile_seconds_saved": 0.0}
        return dict(batcher.aot_stats)

    aot_before = _aot_stats()
    # (architectures, row bucket) whose per-request program is compiled
    direct_warm: set = set()
    for name in names:
        try:
            metadata = load_metadata(collection_dir, name)
            tags = (
                metadata.get("dataset", {}).get("tags")
                or metadata.get("dataset", {}).get("tag_list")
                or []
            )
            offset = (
                metadata.get("metadata", {})
                .get("build_metadata", {})
                .get("model", {})
                .get("model_offset", 0)
            )
            n_features = len(tags)
            if n_features == 0:
                raise ValueError("no tags in metadata")
            model = load_model(collection_dir, name)
            # deserialize-first (ISSUE 14): install any shipped AOT
            # executables BEFORE the first predict, so even warmup's own
            # traffic runs on them instead of paying trace+compile
            _load_shipped_programs(
                model, os.path.join(collection_dir, name)
            )
            for bucket in bucket_rows:
                # + offset so windowed models produce exactly `bucket`
                # output rows — the same power-of-two program bucket real
                # requests of that size compile
                X = np.zeros((int(bucket) + int(offset), n_features), np.float32)
                model.predict(X)
                programs += 1
                _warm_direct_program(model, X, bucket, direct_warm)
            # commit-once: AFTER the first predict (which device-commits
            # params_, fixing the object identity the bank keys on), pin
            # this artifact's params into the batcher's device-resident
            # bank so the first fused call of real traffic never restacks
            # — including specs the auto-A/B stood down and re-enables
            # later. Lazy registration would pay the stack in-request.
            registered += _register_params(model)
            # AOT (ISSUE 11): with params resident the bank's stacked
            # shapes are final — pre-lower the fused programs for every
            # fuse-width bucket so no steady-state request ever traces
            aot_programs += _prelower_programs(
                model, bucket_rows, offset, n_features
            )
            warmed += 1
        except Exception:  # noqa: BLE001 — the other models still warm,
            # and this one compiles in its first request instead: an ERROR
            # with the traceback, counted, and named in the report
            from gordo_tpu.observability import metrics as metric_catalog

            metric_catalog.WARMUP_FAILURES.labels(scope="model").inc()
            logger.exception("warmup FAILED for model %r", name)
            failed.append(name)
    seconds = time.monotonic() - t0
    aot_after = _aot_stats()
    aot_shipped = aot_after["shipped"] - aot_before["shipped"]
    aot_rejected = aot_after["rejected"] - aot_before["rejected"]
    saved = (
        aot_after["compile_seconds_saved"]
        - aot_before["compile_seconds_saved"]
    )
    logger.info(
        "serving warmup: %d model(s), %d predict program(s), %d AOT "
        "pre-lowered fused program(s), %d shipped AOT program(s) loaded "
        "(%.1f compile-seconds saved, %d rejected), %d param-bank "
        "registration(s) in %.1fs%s",
        warmed, programs, aot_programs, aot_shipped, saved, aot_rejected,
        registered, seconds,
        f" ({len(failed)} failed: {failed})" if failed else "",
    )
    report = {
        "models": warmed,
        "programs": programs,
        "aot_programs": aot_programs,
        "aot_shipped": aot_shipped,
        "aot_rejected": aot_rejected,
        "compile_seconds_saved": round(saved, 2),
        "registered_params": registered,
        "seconds": round(seconds, 2),
        "failed": failed,
    }
    global _last_report
    with _last_report_lock:
        _last_report = dict(report)
    return report
