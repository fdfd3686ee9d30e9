"""
Fault-domain layer for fleet builds: classification, retry/backoff,
quarantine records, and a deterministic fault-injection harness.

The reference gets per-machine blast-radius isolation for free from
Kubernetes — every machine trains in its own Argo pod, so one bad sensor
feed kills one pod, not the fleet. The vmapped ``BatchedModelBuilder``
collapses thousands of pods into one process and one XLA program per
bucket; this module re-earns the reference's guarantee *inside* the
process:

- ``FaultPolicy`` decides whether an exception is worth retrying
  (transient: network hiccups, injected transients) or terminal
  (permanent: config errors, bad data), how many attempts to spend, and
  how long to back off between them (exponential with deterministic
  jitter, so two builds of the same fleet behave identically).
- ``QuarantineRecord`` is the unit of degradation: a machine that
  exhausts its retries is *quarantined* — removed from the build with a
  recorded stage/reason — instead of aborting the fleet.
- ``FaultPlan`` is the deterministic injection harness: the
  ``GORDO_TPU_FAULT_PLAN`` environment variable carries a JSON plan
  ("fail machine X's first two data fetches", "poison machine Y's data
  with NaNs", "raise RESOURCE_EXHAUSTED on the first compile of the
  bucket containing Z") so every recovery path in the builders is
  exercisable on CPU, in-process, with no real faults required.

Exit-code contract for fleet builds (``gordo-tpu batch-build``):
``EXIT_ALL_BUILT`` (0) every requested machine built,
``EXIT_PARTIAL`` (81) some machines quarantined but at least one built,
``EXIT_NONE_BUILT`` (82) every machine quarantined.

Plan schema (``GORDO_TPU_FAULT_PLAN``, JSON; a leading ``@`` means "read
the plan from this file path")::

    {"rules": [
      {"site": "data_fetch",     "machine": "m-1", "times": 2,
       "error": "transient"},
      {"site": "data_fetch",     "machine": "m-2", "times": -1,
       "error": "permanent"},
      {"site": "poison_nan",     "machine": "m-3"},
      {"site": "bucket_compile", "machine": "m-4", "times": 1,
       "error": "resource_exhausted"}
    ]}

``times``: how many matching invocations fire the rule (-1 = every
invocation; ``poison_nan`` defaults to -1, fault sites to 1).
``after``: how many matching invocations to let pass before the rule
starts firing (0 = fire from the first match) — "wedge the *Nth* device
call" is ``{"after": N-1, "times": 1}``.
``error``: ``transient`` | ``permanent`` | ``resource_exhausted`` |
``wedge`` (sleep ``seconds`` at the fault point instead of raising — a
stuck device call / hung dependency stand-in) | ``die`` (hard-exit the
process via ``os._exit`` at the fault point — host death for the elastic
scheduler's chaos suite; the victim's lease goes stale and a surviving
host steals the unit).
A ``bucket_compile`` rule matches any bucket whose member list contains
``machine``. Rules are matched in order and count their own firings, so a
plan is a deterministic script, not a probability.

Serve-side sites (PR 3, server/resilience.py): ``serve_model_load`` fires
in the server's model-load path (machine = model name),
``serve_predict`` in the request handler before the model's predict
(supports ``wedge``), ``serve_device_call`` at the top of every fused
device call in the cross-model batcher (machine matched against the fused
group's members; supports ``wedge``), ``serve_poison_nan`` NaN-poisons
the request's feature matrix before predict (pair with
``GORDO_TPU_VALIDATE_OUTPUT=1`` to turn the poisoned lane into a typed
failure), and ``serve_encode`` fires inside the response-encode phase of
both prediction cores (machine = model name; supports ``wedge`` — the
deterministic encode-phase slowdown the perf-regression sentinel's e2e
test injects, ISSUE 17).

Elastic-scheduler site (ISSUE 10, parallel/batch_trainer.py):
``scheduler_lease`` fires right after a host acquires a lease on a work
unit (machine matched against the unit's members) — pair it with
``error="die"`` to kill a host at a deterministic point mid-build and
exercise the lease-expiry steal path.

Gateway sites (ISSUE 12, server/gateway.py + server/membership.py):
``gateway_route`` fires at the top of gateway routing (machine = the
placement key, i.e. the machine name) — an injected transient becomes a
503 with ``Retry-After``, exercising the client's bounded-retry path;
``node_partition`` fires just before each upstream connect (machine =
the target node id) — the gateway treats it as a connect failure and
spends its hedge on the next replica in ring order; ``node_dead`` fires
inside a serving node's membership heartbeat (machine = node id) — any
injected error stops the heartbeat and runs the registration's
``on_dead`` callback, the in-process stand-in for kill -9 (the lease
goes stale and the gateway spills the node's ring segment).

Drift-loop sites (ISSUE 13, observability/drift.py + parallel/drift_queue.py
+ server/hotswap.py): ``drift_detect`` fires when the detector is about to
emit a drift event (machine = the drifted model) — inject a transient to
check a failed emit neither crashes the serving path nor loses the CUSUM
state; ``drift_enqueue`` fires at the top of the rebuild-queue enqueue
(machine = the drifted model) — an injected error means the request file
is never created, exercising the next detection window's retry;
``swap_commit`` fires at the start of a hot-swap cutover (machine = the
model being swapped) — an injected error leaves the OLD revision serving
untouched and the next watcher poll retries the swap.

Chaos-conductor sites (ISSUE 16, gordo_tpu/chaos/ + server/warmup.py +
server/membership.py): ``aot_program_load`` fires before a shipped AOT
serving-program manifest is loaded (machine = the model name) — an
injected permanent rejects the artifact's programs (serving falls back
to the ordinary compile path, counted loudly), a ``wedge`` is the
slow-disk stand-in that stalls the artifact load; ``lease_refresh``
fires inside a serving node's heartbeat just before the lease-file
refresh (machine = node id) — an injected error SKIPS that refresh
(the node keeps serving while its lease goes stale: the
expired-but-alive split the gateway must route around), unlike
``node_dead`` which kills the whole heartbeat. The conductor
(``gordo chaos run``) scripts these sites from declarative scenario
files; ``KNOWN_SITES`` below is the vocabulary
``scripts/lint_chaos_scenario.py`` validates scenario fault rules
against.
"""

import json
import logging
import os
import time
import zlib
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

logger = logging.getLogger(__name__)

PLAN_ENV = "GORDO_TPU_FAULT_PLAN"

# fleet-build exit-code contract (docs/robustness.md); chosen outside the
# CLI's existing per-exception codes (1..90 block: 20/30/60/80/90)
EXIT_ALL_BUILT = 0
EXIT_PARTIAL = 81
EXIT_NONE_BUILT = 82

# every fault-plan site wired somewhere under gordo_tpu/ — the single
# source of truth for scenario linting (scripts/lint_chaos_scenario.py)
# and the chaos conductor's plan validation. Append-only: a site name in
# a committed scenario file is a public contract.
KNOWN_SITES = (
    # build plane
    "data_fetch", "poison_nan", "diverge", "bucket_compile",
    "scheduler_lease",
    # serve plane
    "serve_model_load", "serve_predict", "serve_device_call",
    "serve_poison_nan", "serve_encode",
    # gateway / membership plane
    "gateway_route", "node_partition", "node_dead", "lease_refresh",
    # drift loop
    "drift_detect", "drift_enqueue", "swap_commit",
    # build-to-serve artifacts
    "aot_program_load",
)

# quarantine stages (where in the build the machine was dropped)
STAGE_DATA_FETCH = "data_fetch"
STAGE_DATA_VALIDATION = "data_validation"
STAGE_TRAINING = "training"
STAGE_SERIAL_BUILD = "serial_build"
STAGE_CACHE = "cache"


# --------------------------------------------------------------- exceptions
class TransientFault(RuntimeError):
    """An injected (or wrapped) fault that retrying may clear."""


class PermanentFault(RuntimeError):
    """An injected (or wrapped) fault no retry will clear."""


class InjectedOOM(RuntimeError):
    """An injected device allocation failure; message mirrors the runtime's
    RESOURCE_EXHAUSTED so :func:`is_oom` has one code path for both."""


class NonFiniteDataError(ValueError):
    """Pre-flight validation found NaN/Inf in a machine's training data."""


class DivergedModelError(ValueError):
    """Post-build validation found non-finite params/losses (training
    diverged); only raised in fail-fast mode — the fleet path quarantines."""


_TRANSIENT_TYPE_NAMES = {
    # network/provider hiccups by type name, so requests/urllib3 types are
    # recognized without importing them here
    "ConnectionError",
    "ConnectTimeout",
    "ReadTimeout",
    "Timeout",
    "ProtocolError",
    "TemporaryFailure",
}


def is_transient(exc: BaseException) -> bool:
    """Whether retrying has a chance of clearing this exception."""
    if isinstance(exc, (PermanentFault, NonFiniteDataError, DivergedModelError)):
        return False
    if isinstance(exc, (TransientFault, TimeoutError, ConnectionError)):
        return True
    if isinstance(exc, OSError):
        return True
    return any(
        t.__name__ in _TRANSIENT_TYPE_NAMES for t in type(exc).__mro__
    )


_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "OUT OF MEMORY", "OOM")


def is_oom(exc: BaseException) -> bool:
    """Whether the exception is a device allocation failure (the signal for
    bucket bisection: half the machine axis, half the live buffers)."""
    if isinstance(exc, InjectedOOM):
        return True
    if type(exc).__name__ == "XlaRuntimeError" and "RESOURCE_EXHAUSTED" in str(exc):
        return True
    text = str(exc).upper()
    return isinstance(exc, MemoryError) or any(m in text for m in _OOM_MARKERS)


# -------------------------------------------------------------------- policy
@dataclass
class FaultPolicy:
    """Retry/backoff policy for fleet-build fault handling.

    ``backoff(attempt, key)`` is exponential with *deterministic* jitter:
    the jitter fraction is a hash of ``(key, attempt)``, so a rebuilt fleet
    replays the same schedule — reproducibility is a feature of the fault
    path too, not just the happy path.

    >>> p = FaultPolicy(max_attempts=4, backoff_base=0.5, jitter=0.0)
    >>> [round(p.backoff(a, "m"), 2) for a in (1, 2, 3)]
    [0.5, 1.0, 2.0]
    """

    max_attempts: int = 3
    backoff_base: float = 0.5
    backoff_factor: float = 2.0
    backoff_max: float = 30.0
    jitter: float = 0.1

    @classmethod
    def from_env(cls) -> "FaultPolicy":
        """Build a policy from ``GORDO_TPU_FAULT_*`` environment variables
        (``MAX_ATTEMPTS``, ``BACKOFF_BASE``, ``BACKOFF_FACTOR``,
        ``BACKOFF_MAX``, ``JITTER``); unset vars keep the defaults."""
        def _get(name, cast, default):
            raw = os.environ.get(f"GORDO_TPU_FAULT_{name}")
            if raw is None:
                return default
            try:
                return cast(raw)
            except ValueError:
                logger.warning(
                    "Invalid GORDO_TPU_FAULT_%s=%r; using %r", name, raw, default
                )
                return default

        return cls(
            max_attempts=max(1, _get("MAX_ATTEMPTS", int, cls.max_attempts)),
            backoff_base=_get("BACKOFF_BASE", float, cls.backoff_base),
            backoff_factor=_get("BACKOFF_FACTOR", float, cls.backoff_factor),
            backoff_max=_get("BACKOFF_MAX", float, cls.backoff_max),
            jitter=_get("JITTER", float, cls.jitter),
        )

    def classify(self, exc: BaseException) -> str:
        """``"transient"`` (retry may help) or ``"permanent"``."""
        return "transient" if is_transient(exc) else "permanent"

    def backoff(self, attempt: int, key: str = "") -> float:
        """Seconds to wait after the ``attempt``-th failure (1-based)."""
        delay = min(
            self.backoff_base * self.backoff_factor ** (attempt - 1),
            self.backoff_max,
        )
        if self.jitter:
            frac = (zlib.crc32(f"{key}:{attempt}".encode()) % 1000) / 1000.0
            delay *= 1.0 + self.jitter * frac
        return delay


def record_retry(operation: str) -> None:
    """Count one absorbed transient retry in the telemetry registry
    (observability/metrics.py). Guarded: the fault path must survive even
    a broken observability layer."""
    try:
        from gordo_tpu.observability import metrics as metric_catalog

        metric_catalog.FAULT_RETRIES.labels(operation=operation).inc()
    except Exception:  # noqa: BLE001 — metrics must never mask the fault
        logger.debug("could not record retry metric", exc_info=True)


def record_quarantine(stage: str) -> None:
    """Count one quarantined machine by stage (same guard rationale)."""
    try:
        from gordo_tpu.observability import metrics as metric_catalog

        metric_catalog.QUARANTINES.labels(stage=stage).inc()
        metric_catalog.BUILD_MACHINES.labels(outcome="quarantined").inc()
    except Exception:  # noqa: BLE001 — metrics must never mask the fault
        logger.debug("could not record quarantine metric", exc_info=True)


def retry_call(
    fn,
    policy: FaultPolicy,
    key: str = "",
    describe: str = "operation",
    sleep=time.sleep,
) -> Tuple[Any, int]:
    """Run ``fn()`` under the policy. Returns ``(result, attempts)``;
    re-raises the last exception once a permanent fault is seen or the
    attempt budget is exhausted."""
    attempt = 0
    while True:
        attempt += 1
        try:
            return fn(), attempt
        except Exception as exc:
            if policy.classify(exc) != "transient" or attempt >= policy.max_attempts:
                raise
            delay = policy.backoff(attempt, key)
            logger.warning(
                "%s failed transiently (attempt %d/%d, retrying in %.2fs): %s",
                describe, attempt, policy.max_attempts, delay, exc,
            )
            record_retry(describe.split(" for ", 1)[0].replace(" ", "_"))
            sleep(delay)


# ---------------------------------------------------------------- quarantine
def _observer_host() -> str:
    """Identity of the host recording a quarantine: honors the elastic
    scheduler's GORDO_TPU_HOST_ID so a pod-scale report attributes each
    entry to the process that observed the fault."""
    import socket

    return (
        os.environ.get("GORDO_TPU_HOST_ID")
        or f"{socket.gethostname()}-{os.getpid()}"
    )


def _observer_process_index() -> int:
    """This host's rank: the multi-host flag if set, else the live jax
    process index when jax is already imported and initialized, else 0."""
    raw = os.environ.get("GORDO_TPU_PROCESS_ID")
    if raw:
        try:
            return int(raw)
        except ValueError:
            pass
    import sys

    jax = sys.modules.get("jax")
    if jax is not None:
        try:
            return int(jax.process_index())
        except Exception:  # noqa: BLE001 — attribution must never fail a build
            pass
    return 0


@dataclass
class QuarantineRecord:
    """Why one machine was dropped from a fleet build — and by whom: the
    ``host``/``process_index`` attribution makes a merged pod-scale
    quarantine report traceable to the host that observed each fault."""

    machine: str
    stage: str
    reason: str
    error: str = ""
    attempts: int = 1
    host: str = field(default_factory=_observer_host)
    process_index: int = field(default_factory=_observer_process_index)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "quarantined": True,
            "machine": self.machine,
            "stage": self.stage,
            "reason": self.reason,
            "error": self.error,
            "attempts": self.attempts,
            "host": self.host,
            "process_index": self.process_index,
        }


# ----------------------------------------------------------------- injection
@dataclass
class _FaultRule:
    site: str
    machine: Optional[str] = None
    times: int = 1
    error: str = "transient"
    # skip the first `after` matching invocations ("fail the Nth call")
    after: int = 0
    # wedge duration for error == "wedge" (a stuck-call stand-in)
    seconds: float = 0.0
    fired: int = field(default=0, compare=False)
    seen: int = field(default=0, compare=False)

    def matches(self, site: str, machine: Optional[str], machines: Sequence[str]):
        if site != self.site:
            return False
        if self.machine is None:
            return True
        if machine is not None and machine == self.machine:
            return True
        return self.machine in machines

    def armed(self) -> bool:
        """Count one matching invocation; True when the rule fires on it
        (past its ``after`` skip window, firing budget not exhausted)."""
        self.seen += 1
        if self.seen <= self.after:
            return False
        if self.times >= 0 and self.fired >= self.times:
            return False
        self.fired += 1
        return True

    def make_error(self, site: str, machine: Optional[str]) -> Exception:
        target = machine or self.machine or "*"
        msg = f"injected {self.error} fault at {site} for {target}"
        if self.error in ("resource_exhausted", "oom"):
            return InjectedOOM(f"RESOURCE_EXHAUSTED: {msg}")
        if self.error == "permanent":
            return PermanentFault(msg)
        return TransientFault(msg)


class FaultPlan:
    """A deterministic script of faults to inject, parsed from JSON."""

    def __init__(self, rules: List[_FaultRule]):
        self.rules = rules

    @classmethod
    def parse(cls, raw: str) -> "FaultPlan":
        if raw.startswith("@"):
            with open(raw[1:]) as f:
                raw = f.read()
        data = json.loads(raw)
        entries = data["rules"] if isinstance(data, dict) else data
        rules = []
        for entry in entries:
            entry = dict(entry)
            site = entry.pop("site")
            # data-altering sites apply on every matching call by default;
            # raising sites fire once
            times = entry.pop(
                "times",
                -1
                if site in ("poison_nan", "serve_poison_nan", "diverge")
                else 1,
            )
            rules.append(
                _FaultRule(
                    site=site,
                    machine=entry.pop("machine", None),
                    times=int(times),
                    error=entry.pop("error", "transient"),
                    after=int(entry.pop("after", 0)),
                    seconds=float(entry.pop("seconds", 0.0)),
                )
            )
            if entry:
                logger.warning("fault plan rule has unknown keys: %s", entry)
        return cls(rules)

    def fire(
        self,
        site: str,
        machine: Optional[str] = None,
        machines: Sequence[str] = (),
    ) -> None:
        """Raise the first matching, armed rule's error — or, for a
        ``wedge`` rule, sleep its ``seconds`` in place (one action per
        fault point either way)."""
        for rule in self.rules:
            if not rule.matches(site, machine, machines):
                continue
            if not rule.armed():
                continue
            if rule.error == "wedge":
                logger.warning(
                    "fault plan: wedging %s for %.1fs", site, rule.seconds
                )
                time.sleep(rule.seconds)
                return
            if rule.error == "die":
                # host death: no exception to catch, no atexit, no flushed
                # buffers — the process is simply gone, exactly what the
                # lease-expiry steal path must survive
                logger.warning(
                    "fault plan: host death at %s (machine %s)", site, machine
                )
                os._exit(17)
            raise rule.make_error(site, machine)

    def should_fire(self, site: str, machine: str) -> bool:
        """Boolean form of :meth:`fire` for sites that alter data instead
        of raising (``poison_nan``, ``diverge``); consumes the rule's
        firing budget the same way."""
        for rule in self.rules:
            if rule.matches(site, machine, ()) and rule.armed():
                return True
        return False


# the process-wide active plan: re-parsed whenever the env string changes,
# so a plan's firing counters survive across calls within one build but a
# test switching plans (monkeypatch.setenv) gets a fresh script
_active_plan: Optional[FaultPlan] = None
_active_raw: Optional[str] = None


def get_plan() -> Optional[FaultPlan]:
    global _active_plan, _active_raw
    raw = os.environ.get(PLAN_ENV)
    if not raw:
        _active_plan = _active_raw = None
        return None
    if raw != _active_raw:
        _active_plan = FaultPlan.parse(raw)
        _active_raw = raw
    return _active_plan


def reset_plan() -> None:
    """Forget the active plan (tests: re-arm firing counters)."""
    global _active_plan, _active_raw
    _active_plan = _active_raw = None


def fault_point(
    site: str,
    machine: Optional[str] = None,
    machines: Sequence[str] = (),
) -> None:
    """Injection hook: no-op unless the active plan scripts a fault here."""
    plan = get_plan()
    if plan is not None:
        plan.fire(site, machine=machine, machines=machines)


def should_fire(site: str, machine: str) -> bool:
    """Injection hook for boolean sites (e.g. ``diverge``): False unless
    the active plan scripts a fault here."""
    plan = get_plan()
    return plan is not None and plan.should_fire(site, machine)


def maybe_poison(machine: str, X, site: str = "poison_nan"):
    """Injection hook: NaN-poison a machine's feature matrix (ndarray or
    DataFrame) per plan. Returns ``X`` unchanged when no rule matches (the
    common case). ``site`` distinguishes the build-side hook (default)
    from the serving twin (``serve_poison_nan``)."""
    plan = get_plan()
    if plan is None or not plan.should_fire(site, machine):
        return X
    import numpy as np

    if hasattr(X, "iloc"):  # pandas
        X = X.copy()
        X.iloc[:, 0] = np.nan
    else:
        X = np.array(X, copy=True)
        X[:, 0] = np.nan
    logger.warning("fault plan: NaN-poisoned data for machine %s", machine)
    return X


# ---------------------------------------------------------------- validation
def non_finite_report(X, y=None) -> Optional[str]:
    """None when all values are finite; otherwise a short description of
    what is wrong (used both for pre-flight data validation and post-build
    divergence detection)."""
    import numpy as np

    for name, arr in (("X", X), ("y", y)):
        if arr is None:
            continue
        arr = np.asarray(arr)
        if not np.issubdtype(arr.dtype, np.floating):
            continue
        n_bad = int(np.size(arr) - np.count_nonzero(np.isfinite(arr)))
        if n_bad:
            return f"{n_bad} non-finite values in {name} (shape {arr.shape})"
    return None


def params_non_finite(params, losses=None) -> Optional[str]:
    """Divergence check over a trained pytree + loss history: any NaN or
    infinity in a floating leaf. The fleet build asks the device the same of
    a whole chunk (``batch_trainer._verdict_program``) and walks a lane here
    only to name the leaf."""
    import numpy as np

    if losses is not None:
        losses = np.asarray(losses)
        if not np.all(np.isfinite(losses)):
            return "non-finite training loss"
    floating = np.issubdtype
    try:
        import jax

        leaves = jax.tree_util.tree_leaves(params)
        # bfloat16 and the float8s are floating to jax and not to numpy
        floating = jax.numpy.issubdtype
    except Exception:
        leaves = [params]
    for leaf in leaves:
        arr = np.asarray(leaf)
        if floating(arr.dtype, np.floating) and not np.all(np.isfinite(arr)):
            return f"non-finite model parameters (leaf shape {arr.shape})"
    return None
