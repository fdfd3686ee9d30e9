"""
Elastic fleet-build scheduler: a shared work queue with host work-stealing.

The static multi-host partition (``distributed.owns_serial_machine``) carves
the fleet at plan time: one slow or dead host strands its whole shard while
the rest of the pod idles. This module replaces that carve with a *queue*:
every host enumerates the same work units (bucket programs, serial-fallback
machines, cache claims), then leases units one at a time from shared state
on the build ``output_dir`` — the same filesystem contract the resume
prefilter already relies on, so elasticity adds **no new network
dependency** (no gRPC world, no coordinator process).

The protocol, all plain POSIX files under ``{output_dir}/_scheduler``:

- ``leases/{unit}.g{N}`` — generation-numbered lease files. Acquisition is
  ``open(O_CREAT|O_EXCL)``: exactly one host can create generation N, so a
  lease race has one winner with no locking beyond the filesystem's own
  atomic create. The holder's heartbeat thread rewrites the file (atomic
  temp + rename) every ``heartbeat_s``, refreshing its mtime.
- a lease whose mtime is older than ``lease_timeout_s`` is *stale*: the
  holder is presumed dead (or wedged) and any peer may **steal** the unit
  by creating generation N+1. The previous holder, if merely slow, loses
  the fencing check below and discards its result — artifacts are
  deterministic and written atomically, so even a double build is
  byte-identical, never corrupt.
- ``done/{unit}.json`` — completion markers. A done marker always wins over
  any lease. ``try_claim`` creates one with O_EXCL directly (no lease), the
  exactly-once primitive used for cache hits and quarantine reports.

**Placement** (``next_lease`` ordering) encodes the two perf levers:

1. compile-reuse affinity — units whose shape signature this host has
   already compiled sort first, so the in-process bucket-program cache and
   the persistent XLA cache keep hitting (``compile_seconds_saved``);
2. longest-processing-time — larger units first within an affinity class,
   the classic greedy bound on makespan.

Each unit has a *nominal owner* (stable hash of the unit id modulo the
host count). Leasing your own share counts as ``kind="fresh"``; leasing a
peer's share — because you drained yours early, or their lease expired —
counts as ``kind="steal"`` (``gordo_build_scheduler_leases_total``).
``policy="static"`` restricts every host to its nominal share with no
stealing: the baseline elastic mode is compared against.

Host death is injectable for the chaos suite: the builder fires the
``scheduler_lease`` fault site as each lease activates, and a fault-plan
rule with ``error="die"`` hard-exits the process there (util/faults.py).
"""

import hashlib
import json
import logging
import os
import socket
import tempfile
import threading
import time
import zlib
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from gordo_tpu.observability import metrics as metric_catalog

logger = logging.getLogger(__name__)

SCHEDULER_DIRNAME = "_scheduler"

DEFAULT_LEASE_TIMEOUT_S = 60.0


def default_host_id() -> str:
    """This host's identity in lease files and done markers:
    ``$GORDO_TPU_HOST_ID`` (set one per host when several build processes
    share a machine), else hostname-pid."""
    return (
        os.environ.get("GORDO_TPU_HOST_ID")
        or f"{socket.gethostname()}-{os.getpid()}"
    )


def unit_id_for(machines: Sequence[str], kind: str = "bucket") -> str:
    """Stable unit id from the member machine names: every host derives the
    same id for the same work without exchanging a manifest (hosts plan the
    fleet deterministically from the same config)."""
    digest = hashlib.sha1(
        ("\x1f".join([kind] + sorted(machines))).encode()
    ).hexdigest()
    return f"{kind}-{digest[:16]}"


@dataclass(frozen=True)
class WorkUnit:
    """One leasable piece of the fleet build."""

    unit_id: str
    machines: Tuple[str, ...]
    # compile-shape signature: units sharing it reuse one compiled bucket
    # program (and persistent-XLA-cache entries) on the same host
    signature: str = ""
    kind: str = "bucket"  # bucket | serial
    cost: int = 1  # machines in the unit (LPT weight + remaining gauge)


@dataclass
class Lease:
    """A held lease on one unit (generation-fenced)."""

    unit: WorkUnit
    generation: int
    path: str
    stolen: bool = False
    acquired_at: float = field(default_factory=time.time)


class ElasticScheduler:
    """Filesystem work queue for one fleet build.

    ``host_rank``/``num_hosts`` define nominal ownership for steal
    accounting (and the whole assignment under ``policy="static"``); they
    default to ``$GORDO_TPU_PROCESS_ID`` / ``$GORDO_TPU_NUM_PROCESSES`` so
    ``batch-build --elastic`` reuses the existing multi-host flags without
    bringing up a jax.distributed world.
    """

    def __init__(
        self,
        scheduler_dir: str,
        host_id: Optional[str] = None,
        host_rank: Optional[int] = None,
        num_hosts: Optional[int] = None,
        lease_timeout_s: Optional[float] = None,
        heartbeat_s: Optional[float] = None,
        policy: str = "elastic",
    ):
        if policy not in ("elastic", "static"):
            raise ValueError(f"unknown scheduler policy {policy!r}")
        self.dir = scheduler_dir
        self.leases_dir = os.path.join(scheduler_dir, "leases")
        self.done_dir = os.path.join(scheduler_dir, "done")
        os.makedirs(self.leases_dir, exist_ok=True)
        os.makedirs(self.done_dir, exist_ok=True)
        self.host_id = host_id or default_host_id()
        if host_rank is None:
            host_rank = int(os.environ.get("GORDO_TPU_PROCESS_ID", "0") or 0)
        if num_hosts is None:
            num_hosts = int(os.environ.get("GORDO_TPU_NUM_PROCESSES", "1") or 1)
        self.host_rank = host_rank
        self.num_hosts = max(1, num_hosts)
        if lease_timeout_s is None:
            lease_timeout_s = float(
                os.environ.get(
                    "GORDO_TPU_LEASE_TIMEOUT_S", str(DEFAULT_LEASE_TIMEOUT_S)
                )
            )
        self.lease_timeout_s = max(0.1, lease_timeout_s)
        if heartbeat_s is None:
            raw = os.environ.get("GORDO_TPU_HEARTBEAT_S")
            heartbeat_s = float(raw) if raw else self.lease_timeout_s / 4.0
        self.heartbeat_s = max(0.05, heartbeat_s)
        self.policy = policy
        # shapes this host has already compiled (affinity ordering)
        self._compiled: set = set()
        self.stats: Dict[str, int] = {
            "leases_fresh": 0,
            "leases_steal": 0,
            "lease_expirations": 0,
            "claims": 0,
        }
        self._active: Optional[Lease] = None
        self._hb_stop = threading.Event()
        self._hb_thread: Optional[threading.Thread] = None

    # ------------------------------------------------------------- markers
    def _done_path(self, unit_id: str) -> str:
        return os.path.join(self.done_dir, f"{unit_id}.json")

    def is_done(self, unit_id: str) -> bool:
        return os.path.exists(self._done_path(unit_id))

    def try_claim(self, unit_id: str, payload: Optional[dict] = None) -> bool:
        """Exactly-once claim of a unit that needs no lease (cache hits,
        quarantine reports): O_EXCL-create its done marker. True for the
        one caller fleet-wide that wins the claim."""
        record = dict(payload or {})
        record.setdefault("host", self.host_id)
        record.setdefault("claimed", True)
        try:
            fd = os.open(
                self._done_path(unit_id), os.O_CREAT | os.O_EXCL | os.O_WRONLY
            )
        except FileExistsError:
            return False
        with os.fdopen(fd, "w") as f:
            json.dump(record, f)
        self.stats["claims"] += 1
        return True

    def mark_done(self, lease: Lease, payload: Optional[dict] = None) -> None:
        """Complete a leased unit: write its done marker (idempotent — the
        losing side of a slow-holder race just confirms the same outcome)
        and stop heartbeating the lease."""
        record = {
            "unit": lease.unit.unit_id,
            "kind": lease.unit.kind,
            "machines": list(lease.unit.machines),
            "host": self.host_id,
            "generation": lease.generation,
            "stolen": lease.stolen,
            "wall_sec": round(time.time() - lease.acquired_at, 3),
            **(payload or {}),
        }
        path = self._done_path(lease.unit.unit_id)
        try:
            fd = os.open(path, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
            with os.fdopen(fd, "w") as f:
                json.dump(record, f)
        except FileExistsError:
            logger.info(
                "unit %s already marked done by a peer; this host's "
                "duplicate result is discarded", lease.unit.unit_id,
            )
        self._compiled.add(lease.unit.signature)
        self._detach(lease)

    def summary(self) -> List[dict]:
        """Every done marker's payload (the fleet-wide completion ledger)."""
        out = []
        for name in sorted(os.listdir(self.done_dir)):
            if not name.endswith(".json"):
                continue
            try:
                with open(os.path.join(self.done_dir, name)) as f:
                    out.append(json.load(f))
            except (OSError, ValueError):
                continue  # a marker mid-write; the next reader sees it whole
        return out

    # -------------------------------------------------------------- leases
    def _nominal_owner(self, unit_id: str) -> int:
        return zlib.crc32(unit_id.encode()) % self.num_hosts

    def _current_lease(self, unit_id: str) -> Optional[Tuple[int, str, float]]:
        """(generation, path, age_seconds) of the highest-generation lease
        file, or None when the unit was never leased."""
        best: Optional[Tuple[int, str]] = None
        prefix = f"{unit_id}.g"
        try:
            names = os.listdir(self.leases_dir)
        except OSError:
            return None
        for name in names:
            if not name.startswith(prefix):
                continue
            try:
                gen = int(name[len(prefix):])
            except ValueError:
                continue
            if best is None or gen > best[0]:
                best = (gen, os.path.join(self.leases_dir, name))
        if best is None:
            return None
        try:
            age = time.time() - os.stat(best[1]).st_mtime
        except OSError:
            # raced with nothing that deletes leases — treat as just born
            age = 0.0
        return best[0], best[1], age

    def _lease_payload(self) -> str:
        return json.dumps({"host": self.host_id, "ts": time.time()})

    def _try_acquire(self, unit: WorkUnit, generation: int, stolen: bool):
        path = os.path.join(
            self.leases_dir, f"{unit.unit_id}.g{generation}"
        )
        try:
            fd = os.open(path, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
        except FileExistsError:
            return None  # a peer won this generation
        with os.fdopen(fd, "w") as f:
            f.write(self._lease_payload())
        lease = Lease(unit=unit, generation=generation, path=path, stolen=stolen)
        foreign = self._nominal_owner(unit.unit_id) != self.host_rank
        kind = "steal" if (stolen or foreign) else "fresh"
        self.stats["leases_steal" if kind == "steal" else "leases_fresh"] += 1
        if stolen:
            self.stats["lease_expirations"] += 1
            metric_catalog.SCHEDULER_LEASE_EXPIRATIONS.inc()
            logger.warning(
                "lease on %s (machines %s) expired past %.1fs; host %s "
                "steals it at generation %d",
                unit.unit_id, ",".join(unit.machines[:4]),
                self.lease_timeout_s, self.host_id, generation,
            )
        metric_catalog.SCHEDULER_LEASES.labels(kind=kind).inc()
        self._attach(lease)
        return lease

    def next_lease(
        self, units: Dict[str, WorkUnit], poll_s: Optional[float] = None
    ) -> Optional[Lease]:
        """Block until a unit is acquired, or return None once every unit
        this host may work on is done (elastic: the whole queue; static:
        this host's nominal share — peers' pending units are not waited
        on, exactly like the partition being replaced)."""
        if poll_s is None:
            # capped at 1s: a listdir poll is cheap, and a host that just
            # lost a lease race must not idle a whole heartbeat interval
            # while leasable work sits in the queue
            poll_s = min(self.heartbeat_s, self.lease_timeout_s / 4.0, 1.0)
        while True:
            pending = [u for u in units.values() if not self.is_done(u.unit_id)]
            if self.policy == "static":
                pending = [
                    u
                    for u in pending
                    if self._nominal_owner(u.unit_id) == self.host_rank
                ]
            metric_catalog.FLEET_MACHINES_REMAINING.set(
                sum(u.cost for u in pending)
            )
            if not pending:
                return None
            candidates = []
            # signatures a live peer is building RIGHT NOW (fresh lease on
            # a sibling unit): avoid opening a second front on a shape
            # someone else is already paying the compile for
            active_sigs = set()
            for unit in pending:
                current = self._current_lease(unit.unit_id)
                if current is None:
                    candidates.append((unit, 1, False))
                    continue
                gen, _, age = current
                if age <= self.lease_timeout_s:
                    active_sigs.add(unit.signature)
                if self.policy == "elastic" and age > self.lease_timeout_s:
                    candidates.append((unit, gen + 1, True))
                elif self.policy == "static":
                    # static: "my share" can still hold a crashed attempt's
                    # lease from a previous run of the same host; re-lease
                    # once stale rather than deadlocking on our own ghost
                    if age > self.lease_timeout_s:
                        candidates.append((unit, gen + 1, False))

            def _contended(unit: WorkUnit) -> int:
                # a signature I compiled is free to take (the whole point
                # of affinity); a signature some peer holds a live lease on
                # is one I should leave to them — stealing it means BOTH
                # hosts compile the same program
                if unit.signature in self._compiled:
                    return 0
                return 1 if unit.signature in active_sigs else 0

            # placement: never-expired units before steals; within each,
            # compile-affinity first, then own share, then keep off shapes
            # a peer is mid-compile on, then LPT
            candidates.sort(
                key=lambda c: (
                    c[2],
                    0 if c[0].signature in self._compiled else 1,
                    0 if self._nominal_owner(c[0].unit_id) == self.host_rank
                    else 1,
                    _contended(c[0]),
                    -c[0].cost,
                    c[0].unit_id,
                )
            )
            for unit, generation, stolen in candidates:
                lease = self._try_acquire(unit, generation, stolen)
                if lease is not None:
                    return lease
            # everything pending is freshly leased by live peers (or we
            # lost every race): wait for a done marker or an expiry
            time.sleep(poll_s)

    def still_current(self, lease: Lease) -> bool:
        """Fencing check before a result is recorded: False when a peer
        stole this lease (a higher generation exists) or a done marker
        already landed from elsewhere."""
        current = self._current_lease(lease.unit.unit_id)
        if current is not None and current[0] > lease.generation:
            return False
        return True

    def note_compiled(self, signature: str) -> None:
        self._compiled.add(signature)

    # ----------------------------------------------------------- heartbeat
    def _attach(self, lease: Lease) -> None:
        self._active = lease
        if self._hb_thread is None:
            self._hb_thread = threading.Thread(
                target=self._heartbeat_loop,
                name="gordo-lease-heartbeat",
                daemon=True,
            )
            self._hb_thread.start()

    def _detach(self, lease: Lease) -> None:
        if self._active is lease:
            self._active = None

    def _heartbeat_loop(self) -> None:
        while not self._hb_stop.wait(self.heartbeat_s):
            lease = self._active
            if lease is None:
                continue
            try:
                # atomic rewrite: a peer's staleness probe must never read
                # a half-written lease; the replace refreshes the mtime the
                # probe measures
                fd, tmp = tempfile.mkstemp(
                    dir=self.leases_dir,
                    prefix=os.path.basename(lease.path) + ".hb-",
                )
                with os.fdopen(fd, "w") as f:
                    f.write(self._lease_payload())
                os.replace(tmp, lease.path)
            except OSError:
                logger.debug("lease heartbeat failed", exc_info=True)

    def close(self) -> None:
        """Stop the heartbeat thread (the build is over; any still-active
        lease goes stale and becomes stealable, which is correct for a
        build that is abandoning it)."""
        self._hb_stop.set()
        if self._hb_thread is not None:
            self._hb_thread.join(timeout=self.heartbeat_s * 4)
            self._hb_thread = None
        self._active = None

    def __enter__(self) -> "ElasticScheduler":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def scheduler_dir_for(output_dir: str) -> str:
    """Where a build's shared queue lives: ``$GORDO_TPU_SCHEDULER_DIR``
    override, else ``{output_dir}/_scheduler`` (the leading underscore
    keeps it out of the per-machine artifact namespace)."""
    return os.environ.get("GORDO_TPU_SCHEDULER_DIR") or os.path.join(
        output_dir, SCHEDULER_DIRNAME
    )
