"""
Cross-model request batcher: many models' predicts, one device call.

The reference scales serving by adding gunicorn processes behind an HPA
(gordo/server/server.py:233-297) — each request runs its own Keras forward
pass. On an accelerator that leaves the matrix units idle: one 100×4
autoencoder forward is far below the chip's saturation point. This batcher
is the serving-side twin of the BatchedModelBuilder: concurrent predicts
whose models share a ModelSpec (and padded input shape) are stacked on a
leading axis and executed as ONE vmapped, jitted program; results fan back
out to the waiting request threads.

Correctness: vmap evaluates each (params, X) pair independently — outputs
are identical to per-request predicts (asserted by tests/test_batcher.py).
Shape discipline: inputs are pre-padded with the same power-of-two buckets
as the per-request path (ops/train.py pad_for_predict) and the batch axis
is padded to powers of two, so the compiled-program set stays bounded.

Batching only pays when the fused device call beats the per-request
dispatches it replaces — true on an accelerator with real per-call latency,
false for a host-bound microburst. So the batcher can MEASURE itself:
``$GORDO_TPU_SERVING_BATCH=auto`` (what ``run-server --batch-predicts``
sets) runs a one-time concurrent A/B per spec at first use — direct
predicts vs batched submits under synthetic thread load — and stands down
for that spec when batching loses, logging the measured numbers. ``=1``
forces batching on (the benchmark harness uses this to record the A/B).
``BaseJaxEstimator.predict`` routes through ``maybe_submit`` which no-ops
to the direct path when disabled or stood down.

Scheduling is work-conserving: the dispatcher drains whatever requests have
accumulated while the previous device call ran and fuses exactly those —
no timed window, no artificial latency floor (a fixed window was measured
adding ~2-800ms p50 at low concurrency). ``GORDO_TPU_BATCH_WINDOW_MS``
re-enables a timed collection window if ever wanted.
"""

import contextlib
import functools
import logging
import os
import select
import threading
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from gordo_tpu.observability import metrics as metric_catalog

logger = logging.getLogger(__name__)


def device_pipeline_enabled() -> bool:
    """``GORDO_TPU_DEVICE_PIPELINE`` gate (default on): the dispatcher
    overlaps the drain (blocking D2H + per-rider fan-out) of fused call N
    with the stage + async dispatch of call N+1, so the device starts the
    next batch while the host is still unpacking the last one. The
    staging buffers are double-buffered for exactly this (see
    ``_stacked_inputs``). Set to 0 for the strict-serial device path
    (results are byte-identical either way — only the overlap changes)."""
    return os.environ.get(
        "GORDO_TPU_DEVICE_PIPELINE", "1"
    ).lower() not in ("0", "false", "no")


@dataclass
class _Item:
    spec: Any
    params: Any
    X_pad: np.ndarray
    n_pad: int
    n_keep: int
    done: threading.Event = field(default_factory=threading.Event)
    result: Optional[np.ndarray] = None
    error: Optional[BaseException] = None
    # monotonic submit time: queue-wait = device-call start - submit
    # (gordo_server_batcher_queue_wait_seconds)
    t_submit: float = 0.0
    # the serving model's name (resilience request scope) — fault-plan
    # matching and abandoned-item logging; "" outside a request
    tag: str = ""
    # set by the waiter when its timeout/deadline expires: the dispatcher
    # skips abandoned items at fan-out instead of computing for nobody
    abandoned: bool = False
    # trace context captured at enqueue (inside the waiter's queue span):
    # the dispatcher fans the fused device-call span into every rider's
    # trace, parented here, with span-links to the co-fused riders — one
    # slow fuse then explains N slow requests
    trace_ctx: Any = None


class _SubmitRing:
    """Caller-side wait-free submit channel (many producers, one
    dispatcher).

    ``queue.Queue.put`` takes a mutex and signals a condition variable on
    EVERY enqueue — pure overhead on the request thread, paid even when
    the dispatcher is already awake draining. Here a producer publishes
    with ONE atomic C-level operation (``deque.append`` executes as a
    single opcode under the GIL, which is exactly the fetch-and-publish
    a hardware MPSC ring buys with a CAS — multi-producer safety with no
    lock, no spin, no condvar) and then pokes the dispatcher's single
    eventfd-style wakeup ONLY when it is actually parked. The dispatcher
    drains with non-blocking ``popleft``, spins briefly (yielding the
    GIL) when the channel runs dry — steady-state arrivals land inside
    the spin and skip the park/wake syscall pair entirely — and only
    then parks on the fd.

    Bounded: a producer observing ``capacity`` queued items sleeps a
    tick and retries (admission control caps in-flight requests far
    below any sane capacity, so this is a backstop against unbounded
    memory, not a working backpressure path)."""

    # dry-channel spins before parking; each iteration yields the GIL so
    # producers can run (this box may be single-core)
    SPINS = 100

    def __init__(self, capacity: int = 4096):
        self.capacity = capacity
        self._q: "deque[_Item]" = deque()
        self._parked = False
        try:
            fd = os.eventfd(0, os.EFD_NONBLOCK)  # type: ignore[attr-defined]
            self._rfd = self._wfd = fd
        except (AttributeError, OSError):  # pragma: no cover - non-Linux
            self._rfd, self._wfd = os.pipe()
            os.set_blocking(self._rfd, False)

    def __len__(self) -> int:
        return len(self._q)

    # ------------------------------------------------------------ producers
    def put(self, item: "_Item") -> None:
        q = self._q
        while len(q) >= self.capacity:  # backstop, see class docstring
            time.sleep(0.0005)
        q.append(item)
        # benign race with the dispatcher parking: it re-checks the deque
        # AFTER raising its parked flag, so either it sees this item or it
        # sees the flag-up write below — never a lost wakeup. A stale poke
        # (dispatcher already drained the item) only costs one spurious
        # pass through its drain loop.
        if self._parked:
            try:
                os.write(self._wfd, b"\x01\x00\x00\x00\x00\x00\x00\x00")
            except OSError:  # pragma: no cover - fd closed at shutdown
                pass

    # ----------------------------------------------------------- dispatcher
    def pop(self) -> Optional["_Item"]:
        try:
            return self._q.popleft()
        except IndexError:
            return None

    def pop_wait(self, timeout: Optional[float] = None) -> Optional["_Item"]:
        """One item, blocking: spin (GIL-yielding) then park on the fd.
        ``None`` only when a timeout was given and expired."""
        q = self._q
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            try:
                return q.popleft()
            except IndexError:
                pass
            for _ in range(self.SPINS):
                time.sleep(0)
                try:
                    return q.popleft()
                except IndexError:
                    continue
            self._parked = True
            try:
                # lost-wakeup guard: an item published before the flag
                # went up would never poke the fd — look again first
                try:
                    return q.popleft()
                except IndexError:
                    pass
                if deadline is None:
                    select.select([self._rfd], [], [])
                else:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        return self.pop()
                    select.select([self._rfd], [], [], remaining)
                self._drain_fd()
            finally:
                self._parked = False

    def _drain_fd(self) -> None:
        # eventfd: one read returns-and-zeroes the whole counter; pipe
        # fallback: a large read slurps every pending poke byte
        try:
            os.read(self._rfd, 65536)
        except (BlockingIOError, InterruptedError):
            pass


# completion waiters, pooled per submitting thread: one reusable Event per
# connection (thread lane maps connections 1:1 onto threads; the event-loop
# lane's single thread reuses one) instead of a fresh Event allocated and
# garbage-collected per predict. A waiter that ABANDONS its item must not
# reuse the Event — the dispatcher may still set() it late — so the abandon
# path drops the pooled instance and the next submit starts fresh.
_waiter_pool = threading.local()


def _checkout_waiter() -> threading.Event:
    waiter = getattr(_waiter_pool, "event", None)
    if waiter is None:
        waiter = threading.Event()
        _waiter_pool.event = waiter
    waiter.clear()
    return waiter


def _discard_waiter() -> None:
    _waiter_pool.event = None


@functools.lru_cache(maxsize=1024)
def _spec_forward_flops(spec) -> float:
    """Analytic forward FLOPs per sample for the achieved-FLOPs counter
    (device duty-cycle/MFU telemetry — observability/device.py). 0.0 when
    the spec walk fails: accounting must never fail a device call."""
    try:
        from gordo_tpu.ops.flops import forward_flops_per_sample

        return float(forward_flops_per_sample(spec))
    except Exception:  # noqa: BLE001
        return 0.0


@functools.lru_cache(maxsize=256)
def _stacked_apply(spec, n_pad: int, batch: int, capacity: int):
    """One compiled program per (spec, padded length, batch bucket, bank
    capacity bucket): gather ``batch`` models' params out of the resident
    bank by index, then vmap the forward over them.

    On accelerator backends the stacked input X is *donated*: XLA may
    alias its device buffer for the output, so the H2D staging buffer of
    call N and the D2H pull of call N-1 can overlap instead of holding
    two live copies. The host side double-buffers its staging arrays
    (``_stacked_inputs``) for the same reason. CPU gets no donation —
    jax emits an unusable-donation warning per call there."""
    import jax
    import jax.numpy as jnp

    from gordo_tpu.ops.nn import apply_model

    if spec.lookback_window <= 1 and spec.lookahead == 0:

        def one(params, X):
            out, _ = apply_model(spec, params, X)
            return out

    else:

        def one(params, X):
            idx = jnp.arange(n_pad)
            window = jnp.arange(spec.lookback_window)
            xb = X[idx[:, None] + window[None, :]]
            out, _ = apply_model(spec, params, xb)
            return out

    def gathered(bank_params, model_idx, X):
        from gordo_tpu.ops.train import note_trace_compile

        note_trace_compile()
        params = jax.tree_util.tree_map(lambda a: a[model_idx], bank_params)
        return jax.vmap(one)(params, X)

    donate = (2,) if jax.default_backend() in ("tpu", "gpu") else ()
    return jax.jit(gathered, donate_argnums=donate)


@functools.lru_cache(maxsize=256)
def _single_apply(spec, n_pad: int):
    """Un-fused single-model program: the serial rescue rung of the fused
    group's fault-isolation ladder. Deliberately bypasses the param bank
    and the gather program — when those are what broke, the rescue must
    not share their fate."""
    import jax
    import jax.numpy as jnp

    from gordo_tpu.ops.nn import apply_model
    from gordo_tpu.ops.train import note_trace_compile

    if spec.lookback_window <= 1 and spec.lookahead == 0:

        def one(params, X):
            note_trace_compile()
            out, _ = apply_model(spec, params, X)
            return out

    else:

        def one(params, X):
            note_trace_compile()
            idx = jnp.arange(n_pad)
            window = jnp.arange(spec.lookback_window)
            xb = X[idx[:, None] + window[None, :]]
            out, _ = apply_model(spec, params, xb)
            return out

    return jax.jit(one)


class _ParamBank:
    """Device-resident stacked params for every model of one spec.

    Each model's pytree is stacked into the bank ONCE (on its first batched
    predict, or ahead of traffic by warmup's commit-once pre-registration
    — server/warmup.py); after that a batch call ships only an int32 index
    vector and the inputs. Restacking params per call was measured at
    ~30 ms/model over the device link — it made the batcher lose its own
    A/B in round 2. Capacity grows in powers of two so the gather program
    recompiles only when the model count crosses a bucket boundary.

    At capacity (``GORDO_TPU_PARAM_BANK_MAX``, default 512) the bank
    evicts the least-recently-used model *in place*: the newcomer's
    params overwrite the victim's slot on device (one ``.at[slot].set``,
    no restack), its host pytree reference replaces the victim's in
    ``trees`` — so host memory is bounded under model churn instead of
    retaining every pytree ever registered — and every OTHER slot stays
    valid (the old clear-everything reset stranded the whole bank's
    in-flight slot resolutions on the ``generation`` check).

    Thread-safe: warmup registers from the boot thread while the
    dispatcher registers from the batcher thread.
    """

    MAX_MODELS = 512

    def __init__(self):
        self._lock = threading.Lock()
        # id(params) -> slot, in LRU order (oldest touch first)
        self.slots: "OrderedDict[int, int]" = OrderedDict()
        self.trees: List[Any] = []
        self.stacked: Any = None
        self.capacity = 0
        # bumped on every eviction so callers resolving a batch of slots
        # can detect that earlier-resolved slots went stale mid-batch.
        # (LRU order makes that near-impossible — a slot resolved moments
        # ago is MRU, never the victim — but the guard stays.)
        self.generation = 0
        raw = os.environ.get("GORDO_TPU_PARAM_BANK_MAX", "")
        try:
            configured = int(raw) if raw.strip() else 0
        except ValueError:
            logger.warning(
                "invalid GORDO_TPU_PARAM_BANK_MAX=%r; using %d",
                raw, self.MAX_MODELS,
            )
            configured = 0
        self.max_models = configured if configured > 0 else self.MAX_MODELS

    def __len__(self) -> int:
        with self._lock:
            return len(self.trees)

    def slot_of(self, params) -> int:
        with self._lock:
            return self._slot_of_locked(params)

    def _slot_of_locked(self, params) -> int:
        key = id(params)
        slot = self.slots.get(key)
        if slot is not None:
            self.slots.move_to_end(key)  # touch: now MRU
            return slot
        import jax

        if len(self.trees) >= self.max_models:
            # bank full (long-lived server under model churn): evict the
            # LRU entry in place — one on-device slot write, no restack,
            # no strand of the other resident models
            _victim_key, slot = self.slots.popitem(last=False)
            metric_catalog.PARAM_BANK_EVICTIONS.inc()
            self.generation += 1
            self.trees[slot] = params  # drops the victim's host pytree
            self.slots[key] = slot
            self.stacked = jax.tree_util.tree_map(
                lambda bank, leaf: bank.at[slot].set(leaf),
                self.stacked, params,
            )
            return slot
        slot = len(self.trees)
        self.trees.append(params)  # keeps `params` alive, so id() stays unique
        self.slots[key] = slot
        # capacity floor of 8: growing 1->2->4->8 would recompile the gather
        # program at every step while a server warms its first models. The
        # padding copies cost <=8x ONE model's params in HBM (<<1MB for this
        # model zoo) — accepted for the compile stability
        cap = 8
        while cap < len(self.trees):
            cap <<= 1
        cap = min(cap, max(8, self.max_models))
        if cap == self.capacity:
            # capacity unchanged: write the one new tree into its slot
            # in place rather than re-uploading the whole bank (O(N^2)
            # stacking across N registrations otherwise)
            self.stacked = jax.tree_util.tree_map(
                lambda bank, leaf: bank.at[slot].set(leaf), self.stacked, params
            )
        else:
            self._restack(cap)
        return slot

    def replace(self, old_params, new_params) -> Optional[int]:
        """Overwrite one resident model's slot in place with its rebuilt
        params (revision hot-swap, ISSUE 13): one on-device
        ``.at[slot].set``, no restack, no capacity change — so every AOT
        pre-lowered program (keyed on bank capacity) stays valid and the
        swap costs zero steady-state trace compiles. Returns the slot, or
        None when ``old_params`` was never resident (the caller falls
        back to a plain registration)."""
        with self._lock:
            slot = self.slots.pop(id(old_params), None)
            if slot is None:
                return None
            import jax

            self.generation += 1
            self.trees[slot] = new_params  # drops the old host pytree
            self.slots[id(new_params)] = slot  # registered as MRU
            self.stacked = jax.tree_util.tree_map(
                lambda bank, leaf: bank.at[slot].set(leaf),
                self.stacked, new_params,
            )
            return slot

    def _restack(self, cap: int):
        import jax
        import jax.numpy as jnp

        metric_catalog.PARAM_BANK_RESTACKS.inc()
        pad = [self.trees[0]] * (cap - len(self.trees))
        self.stacked = jax.tree_util.tree_map(
            lambda *leaves: jnp.stack(leaves), *(self.trees + pad)
        )
        self.capacity = cap


class CrossModelBatcher:
    """Collects concurrent predict submissions for a short window and runs
    each same-shape group as one stacked device call."""

    def __init__(
        self,
        window_ms: float = 0.0,
        max_batch: int = 64,
        timeout_s: Optional[float] = None,
        self_ab: bool = False,
    ):
        self.window_s = window_ms / 1e3
        self.max_batch = max_batch
        # generous default: the first batched predict of a (spec, shape)
        # pays an XLA compile, which over a remote-device link can take
        # tens of seconds; a timeout surfaces a wedged device as a 500
        # instead of a request thread stuck forever
        if timeout_s is None:
            timeout_s = float(os.environ.get("GORDO_TPU_BATCH_TIMEOUT_S", "300"))
        # <=0 means wait without limit
        self.timeout_s = timeout_s if timeout_s > 0 else None
        self._ring = _SubmitRing()
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()
        self._banks: Dict[Any, _ParamBank] = {}
        # auto mode: per-spec measured go/no-go, filled by _calibrate
        self.self_ab = self_ab
        self._spec_on: Dict[Any, bool] = {}
        self._calibrating: set = set()
        # (spec, shape) pairs whose abandonment has been logged already
        self._abandon_logged: set = set()
        # reusable stacking buffers, keyed by (input shape, dtype, fuse
        # bucket): _device_call used to np.stack a fresh (b_pad, *shape)
        # array plus an index vector per fused call — steady-state serving
        # re-allocates the identical buffers thousands of times a second.
        # Each entry holds TWO (X, idx) pairs plus a toggle (double
        # buffering — see _stacked_inputs); only the dispatcher thread
        # fills/ships them.
        self._stack_buffers: Dict[Tuple, list] = {}
        # AOT pre-lowered serving programs (ISSUE 11): (spec, n_pad, b_pad,
        # bank capacity) -> (expected X shape, compiled executable). Filled
        # by prelower() at warmup; _device_call prefers these — calling a
        # compiled executable never re-traces, so steady state keeps
        # gordo_server_trace_compiles_total flat
        self._aot: Dict[Tuple, Tuple[Tuple, Any]] = {}
        # how the AOT cache was populated (ISSUE 14): shipped = programs
        # deserialized from an artifact's programs/ manifest, compiled =
        # lowered+compiled fresh by prelower, rejected = manifest entries
        # refused on a real-ISA fingerprint mismatch (warmup counts those
        # here so the report and /debug/vars agree with the counters);
        # compile_seconds_saved credits each shipped program with the
        # compile wall the BUILD host paid for it
        self.aot_stats = {
            "shipped": 0, "compiled": 0, "rejected": 0,
            "compile_seconds_saved": 0.0,
        }
        # observability: exposed through /healthcheck-adjacent metrics and
        # asserted by tests
        self.stats = {
            "items": 0, "device_calls": 0, "largest_batch": 0,
            "pipeline_overlaps": 0,
        }
        # monotonic start of the device call the dispatcher is currently
        # inside (None between calls): the device-watchdog signal
        # (resilience.stuck_device_call_s -> /healthcheck 503)
        self._busy_since: Optional[float] = None
        # device-path pipelining (ISSUE 19): overlap drain of call N with
        # stage+dispatch of call N+1. Only meaningful in work-conserving
        # mode (window_s == 0) — a timed window blocks in pop_wait, so the
        # loop settles any in-flight call before opening one.
        self._pipeline = device_pipeline_enabled()
        # wall-clock end of the last drained call: busy-seconds for
        # overlapping pipelined calls are unioned against this so the
        # device duty-cycle gauge stays a true wall-clock fraction
        self._last_drain_end = 0.0

    # ------------------------------------------------------------- public
    def decision_counts(self) -> Tuple[int, int]:
        """(architectures batching, architectures stood down) — the public
        snapshot the metrics mirror reads (prometheus/metrics.py)."""
        decisions = list(self._spec_on.values())
        on = sum(1 for d in decisions if d)
        return on, len(decisions) - on

    def device_call_stuck_s(self) -> float:
        """Seconds the dispatcher has been inside its current device call
        (0.0 between calls) — read by the device watchdog."""
        t0 = self._busy_since
        return 0.0 if t0 is None else max(0.0, time.monotonic() - t0)

    def register_params(self, spec, params) -> int:
        """Commit one model's params into its spec's device-resident bank
        ahead of traffic (warmup's commit-once pre-registration). Lazy
        registration restacks the bank every time capacity crosses a
        power-of-two bucket — registering the whole expected fleet at
        boot settles the final capacity once, so the first fused call
        after startup gathers from a bank that never restacks again (and
        warmup's predicts compile the gather program at that final
        capacity, not an interim one). Returns the assigned slot."""
        bank = self._banks.setdefault(spec, _ParamBank())
        return bank.slot_of(params)

    def bank_size(self, spec) -> int:
        """Resident models in the spec's bank (0 when no bank exists)."""
        bank = self._banks.get(spec)
        return 0 if bank is None else len(bank)

    def swap_params(self, spec, old_params, new_params) -> bool:
        """Revision hot-swap (ISSUE 13): replace the old artifact's
        resident params with the rebuilt ones IN PLACE — the slot, the
        bank capacity, and therefore every AOT pre-lowered program are
        all preserved, so the swap is invisible to steady-state latency.
        False when the old params weren't resident (caller should
        ``register_params`` the new ones instead)."""
        bank = self._banks.get(spec)
        if bank is None:
            return False
        return bank.replace(old_params, new_params) is not None

    def load_shipped(self, spec, entries) -> int:
        """Deserialize-first AOT population (ISSUE 14): install an
        artifact's shipped serving executables straight into ``_aot``
        without touching trace-time Python — no bank required yet, no
        trace, no XLA compile. ``entries`` are the manifest rows for this
        spec (serializer/programs.shipped_index), ALREADY fingerprint-
        cleared by the caller: this method never sees a rejected
        manifest. Entries are keyed by their own baked-in capacity —
        one that doesn't match the bank capacity serving settles on is
        simply never hit (and prelower compiles the real bucket fresh).
        Returns how many programs were installed."""
        from gordo_tpu.serializer import programs as programs_mod

        loaded = 0
        for entry in entries:
            try:
                n_pad = int(entry["n_pad"])
                b_pad = int(entry["b_pad"])
                capacity = int(entry["capacity"])
                x_shape = tuple(int(d) for d in entry["x_shape"])
            except (KeyError, TypeError, ValueError) as exc:
                logger.warning("malformed shipped-program entry: %s", exc)
                continue
            key = (spec, n_pad, b_pad, capacity)
            if key in self._aot:
                continue
            try:
                executable = programs_mod.deserialize(entry["path"])
            except Exception as exc:  # noqa: BLE001 — prelower compiles it
                logger.warning(
                    "deserializing shipped program %s failed (will compile "
                    "fresh instead): %s", entry.get("file"), exc,
                )
                continue
            self._aot[key] = (x_shape, executable)
            self.aot_stats["shipped"] += 1
            self.aot_stats["compile_seconds_saved"] += float(
                entry.get("compile_s") or 0.0
            )
            metric_catalog.AOT_PROGRAMS.labels(source="shipped").inc()
            loaded += 1
        return loaded

    def note_rejected_shipment(self, count: int) -> None:
        """Record ``count`` shipped programs refused on a real-ISA
        fingerprint mismatch (warmup walks the ladder; the batcher owns
        the stats so one snapshot covers all three sources)."""
        if count > 0:
            self.aot_stats["rejected"] += count
            metric_catalog.AOT_PROGRAMS.labels(source="rejected").inc(count)

    def prelower(
        self,
        spec,
        X_pad: np.ndarray,
        n_pad: int,
        fuse_widths: Tuple[int, ...] = (1, 4, 16, 64),
    ) -> int:
        """AOT pre-lower + compile the stacked serving programs for one
        (spec, padded shape) across the fuse-width buckets real traffic
        hits (``_device_call`` grows batches 1→4→16→64), via
        ``jax.jit(...).lower(shapes).compile()`` over ShapeDtypeStructs —
        no input arrays materialized, no device call executed.

        Steady-state serving then runs entirely on these executables:
        calling a compiled program never re-traces, so
        ``gordo_server_trace_compiles_total`` stays flat once warmup is
        done. Compiles land in the persistent XLA cache
        (util/xla_cache.py) like any other, so a restarted worker
        re-lowers but reloads the compiled artifact instead of paying XLA.

        Requires the spec's param bank to be stacked already (warmup
        registers params first); returns how many programs were
        compiled. Best-effort: a failing width is logged and skipped —
        the jit path serves it lazily instead."""
        import jax

        bank = self._banks.get(spec)
        if bank is None or bank.stacked is None:
            return 0
        bank_shapes = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), bank.stacked
        )
        compiled = 0
        for width in fuse_widths:
            b_pad = min(width, self.max_batch)
            key = (spec, n_pad, b_pad, bank.capacity)
            if key in self._aot:
                continue
            x_shape = (b_pad,) + X_pad.shape
            try:
                program = _stacked_apply(spec, n_pad, b_pad, bank.capacity)
                executable = program.lower(
                    bank_shapes,
                    jax.ShapeDtypeStruct((b_pad,), np.int32),
                    jax.ShapeDtypeStruct(x_shape, X_pad.dtype),
                ).compile()
            except Exception as exc:  # noqa: BLE001 — jit path still serves
                metric_catalog.PRELOWER_FAILURES.inc()
                logger.warning(
                    "AOT pre-lower failed for (n_pad=%d, fuse=%d): %s",
                    n_pad, b_pad, exc,
                )
                continue
            self._aot[key] = (x_shape, executable)
            self.aot_stats["compiled"] += 1
            metric_catalog.AOT_PROGRAMS.labels(source="compiled").inc()
            compiled += 1
        return compiled

    def submit(self, spec, params, X) -> Optional[np.ndarray]:
        """Blocking predict through the batch queue (thread-safe).

        In auto (self-A/B) mode, returns ``None`` when measurement decided
        batching loses for this spec — the caller then predicts direct.
        """
        if self.self_ab:
            decision = self._spec_on.get(spec)
            if decision is None:
                decision = self._calibrate(spec, params, X)
            if not decision:
                return None
        return self._force_submit(spec, params, X)

    # -------------------------------------------------------- calibration
    def _calibrate(self, spec, params, X) -> bool:
        """One-time measured A/B for this spec: concurrent direct predicts
        vs concurrent batched submits on the live input shape. The batched
        arm doubles as program prewarm (stacked apply for the buckets real
        load will hit), and compiles run before timing so the decision
        reflects steady state. Returns (and records) whether batching won;
        the measured numbers are logged either way.
        """
        from gordo_tpu.ops.train import predict_fn

        with self._lock:
            if spec in self._spec_on:
                return self._spec_on[spec]
            if spec in self._calibrating:
                # another thread is measuring this spec right now; don't
                # queue behind it — predict direct this once
                return False
            self._calibrating.add(spec)
        won: Optional[bool] = None
        try:
            # clamped: zero users/rounds would leave the sample list empty
            # and turn a config mistake into a cryptic stand-down
            users = max(1, int(os.environ.get("GORDO_TPU_BATCH_AB_USERS", "8")))
            rounds = max(1, int(os.environ.get("GORDO_TPU_BATCH_AB_ROUNDS", "4")))
            direct = predict_fn(spec)

            hostwork_s = float(
                os.environ.get("GORDO_TPU_BATCH_AB_HOSTWORK_MS", "2")
            ) / 1e3

            def host_work():
                """GIL-holding busy work between calls, standing in for the
                serving path's parse/validate/frame-assembly share. Without
                it the microworld is a predict-only storm whose GIL
                contention inflates direct's per-call latency — it chose
                batching for a host-bound model the real workload then lost
                by 2x. With realistic gaps, predicts arrive sparsely, which
                is exactly the arrival pattern the decision must survive."""
                deadline = time.monotonic() + hostwork_s
                count = 0
                while time.monotonic() < deadline:
                    count += 1

            def drive(fn) -> float:
                """Median PER-CALL latency under thread concurrency with
                host-work gaps.

                Per-call latency, not aggregate wall: back-to-back walls
                under-weight the queue/event sync each batched call pays.
                Where the device call dominates — the regime batching
                exists for — the fused call still wins per-call latency,
                because direct dispatches serialize at the device while one
                batch runs them together.
                """
                errors: List[BaseException] = []
                times: List[float] = []
                lock = threading.Lock()

                def worker():
                    try:
                        for r in range(rounds):
                            if r:
                                host_work()  # inter-call gap only, no
                                # dead spin after the final sample
                            t0 = time.monotonic()
                            fn()
                            elapsed = time.monotonic() - t0
                            with lock:
                                times.append(elapsed)
                    except BaseException as exc:  # noqa: BLE001
                        errors.append(exc)

                threads = [
                    threading.Thread(target=worker) for _ in range(users)
                ]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                if errors:
                    raise errors[0]
                times.sort()
                return times[len(times) // 2]

            # warm both arms (XLA compiles, param-bank stack) before timing
            direct(params, np.asarray(X))
            self._force_submit(spec, params, X)
            drive(lambda: self._force_submit(spec, params, X))

            p50_direct = drive(lambda: direct(params, np.asarray(X)))
            p50_batched = drive(lambda: self._force_submit(spec, params, X))
            won = p50_batched < p50_direct
            arch = "/".join(
                sorted({type(layer).__name__ for layer in spec.layers})
            )
            logger.info(
                "serving batcher self-A/B for %s (lookback %d) models "
                "(%d users x %d rounds): per-call p50 direct %.2fms, "
                "batched %.2fms -> batching %s",
                arch or "?", spec.lookback_window,
                users, rounds, p50_direct * 1e3, p50_batched * 1e3,
                "ON" if won else "OFF (stood down: fused call loses to "
                "per-request dispatch on this backend)",
            )
        except Exception as exc:  # noqa: BLE001 — measurement must not 500;
            # KeyboardInterrupt/SystemExit propagate (an operator's Ctrl-C
            # must not be converted into a silent stand-down)
            logger.warning("batcher self-A/B failed (%s); standing down", exc)
            won = False
        finally:
            # ALWAYS leave the calibrating set, even on a propagating
            # BaseException (worker shutdown mid-A/B): a leaked entry would
            # silently pin this spec to the direct path forever with no
            # recorded decision. Decision-record and discard happen under
            # ONE lock acquisition — discarding first would let another
            # thread start a duplicate A/B storm in the gap. A propagated
            # BaseException leaves `won` None and records nothing, so the
            # next submit re-attempts calibration.
            with self._lock:
                if won is not None:
                    self._spec_on[spec] = won
                self._calibrating.discard(spec)
        return won

    def _force_submit(self, spec, params, X) -> np.ndarray:
        """submit() minus the auto-mode gate (used by calibration).

        The wait honors both the batcher's own timeout and the request's
        deadline budget (resilience.request_scope) — queue-wait counts
        against the budget. A waiter that gives up marks its item
        *abandoned*: the dispatcher skips it at fan-out instead of
        computing a result nobody is waiting for."""
        from gordo_tpu.observability import telemetry, tracing
        from gordo_tpu.ops.train import pad_for_predict
        from gordo_tpu.server import resilience

        X_pad, n_pad, n_keep = pad_for_predict(spec, X)
        item = _Item(spec, params, X_pad, n_pad, n_keep,
                     done=_checkout_waiter())
        item.t_submit = time.monotonic()
        item.tag = resilience.current_model() or ""
        # budget already spent (e.g. decode ate it): never even queue
        resilience.check_deadline("queue_wait")
        remaining = resilience.remaining_s()
        timeout = self.timeout_s
        deadline_bound = False
        if remaining is not None and (timeout is None or remaining < timeout):
            timeout = remaining
            deadline_bound = True
        self._ensure_thread()
        # the queue span covers enqueue → fan-out; the context captured
        # INSIDE it is what the dispatcher parents the device-call span
        # under, so the request's tree reads: request → queue → device call
        with telemetry.span("serve_batch_queue", model=item.tag):
            item.trace_ctx = tracing.capture()
            self._ring.put(item)
            if not item.done.wait(timeout=timeout):
                item.abandoned = True
                # the dispatcher may still set() this Event after we walk
                # away — drop it from the pool so the late set lands on an
                # orphan, never on this thread's NEXT item
                _discard_waiter()
                self._record_abandoned(item)
                if deadline_bound:
                    resilience.record_deadline_exceeded("queue_wait")
                    raise resilience.DeadlineExceeded(
                        f"batched predict abandoned: request deadline "
                        f"({timeout * 1e3:.0f}ms remaining at submit) "
                        f"expired in the batch queue"
                    )
                raise TimeoutError(
                    f"batched predict timed out after {timeout:.0f}s"
                )
        if item.error is not None:
            raise item.error
        return item.result

    def _record_abandoned(self, item: _Item) -> None:
        """Count one abandoned item; log its spec/shape once per (spec,
        shape) so a recurring wedge is diagnosable without a log flood."""
        metric_catalog.BATCHER_ABANDONED.inc()
        key = (item.spec, item.X_pad.shape)
        with self._lock:
            if key in self._abandon_logged:
                return
            self._abandon_logged.add(key)
        arch = "/".join(
            sorted({type(layer).__name__ for layer in item.spec.layers})
        )
        logger.warning(
            "batched predict abandoned by its waiter (model %r, arch %s, "
            "padded shape %s); further abandons for this (spec, shape) "
            "are counted but not logged",
            item.tag or "?", arch or "?", item.X_pad.shape,
        )

    # ------------------------------------------------------------ worker
    def _ensure_thread(self):
        if self._thread is not None and self._thread.is_alive():
            return
        with self._lock:
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._loop, daemon=True, name="gordo-batcher"
                )
                self._thread.start()

    def _loop(self):
        # the dispatcher is a named hot thread for the sampling profiler
        # (no-op singleton unless a profiler/debug knob is set)
        from gordo_tpu.observability import profiler

        profiler.register_thread("gordo-batcher")
        # the fused call dispatched but not yet drained (device-path
        # pipelining, depth 1): its D2H + fan-out run AFTER the next
        # batch's stage + dispatch, so the device computes while the host
        # unpacks. Depth 1 matches the double-buffered staging arrays —
        # a buffer is never refilled before its call has drained.
        pending = None
        while True:
            if pending is not None:
                nxt = self._ring.pop()
                if nxt is None:
                    # nothing queued behind the in-flight call: settle it
                    # now — pipelining never delays an idle ring's result
                    self._drain_call(pending)
                    pending = None
                    self._busy_since = None
                    continue
                batch = [nxt]
            else:
                batch = [self._ring.pop_wait()]
            if self.window_s > 0:
                # optional timed collection window (off by default); the
                # window blocks in pop_wait, so pipelining is inert here
                deadline = time.monotonic() + self.window_s
                while len(batch) < self.max_batch:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        break
                    nxt = self._ring.pop_wait(timeout=remaining)
                    if nxt is None:
                        break
                    batch.append(nxt)
            else:
                # work-conserving: fuse exactly the requests that piled up
                # while the previous device call ran; never wait for more
                while len(batch) < self.max_batch:
                    nxt = self._ring.pop()
                    if nxt is None:
                        break
                    batch.append(nxt)
            if not self._pipeline or self.window_s > 0:
                self._run(batch)
                continue
            # dispatch the NEW batch first (async stage + device call),
            # then drain the previous call — its blocking D2H and fan-out
            # overlap the new call's H2D/compute instead of preceding it
            dispatched = self._run_async(batch)
            if dispatched:
                overlapped = (
                    len(dispatched) if pending is not None
                    else len(dispatched) - 1
                )
                if overlapped > 0:
                    self.stats["pipeline_overlaps"] += overlapped
                    metric_catalog.DEVICE_PIPELINE_OVERLAPS.inc(overlapped)
            if pending is not None:
                self._drain_call(pending)
            # several groups in one batch were dispatched back-to-back:
            # drain all but the last now, keep the last in flight
            for extra in dispatched[:-1]:
                self._drain_call(extra)
            pending = dispatched[-1] if dispatched else None
            # re-arm the device watchdog for whatever is still in flight
            # (drains clear nothing themselves — the loop owns the signal)
            self._busy_since = pending[3] if pending is not None else None

    def _run(self, batch: List[_Item]):
        groups: Dict[Tuple, List[_Item]] = {}
        for item in batch:
            key = (item.spec, item.X_pad.shape)
            groups.setdefault(key, []).append(item)
        for (spec, _shape), items in groups.items():
            try:
                self._run_group(spec, items)
            except BaseException as exc:  # noqa: BLE001 — fan the error out
                for item in items:
                    item.error = exc
                    item.done.set()

    def _run_group(self, spec, items: List[_Item]):
        # telemetry histograms (process-local, no prometheus_client needed;
        # bridged into /metrics by server/prometheus/metrics.py): how long
        # each predict queued before this fused call, and the fuse width
        now = time.monotonic()
        for item in items:
            metric_catalog.BATCHER_QUEUE_WAIT_SECONDS.observe(
                max(0.0, now - item.t_submit)
            )
        metric_catalog.BATCHER_FUSE_WIDTH.observe(len(items))
        self._execute(spec, items)

    def _execute(self, spec, items: List[_Item]):
        """The serving twin of the build side's recovery ladder: run the
        fused call; on failure bisect and retry the halves, bottoming out
        in a serial (un-fused) rescue per item — one poisoned submission
        degrades only itself, never its cohort."""
        try:
            self._device_call(spec, items)
        except BaseException as exc:  # noqa: BLE001 — ladder, then fan out
            if len(items) == 1:
                self._serial_rescue(spec, items[0], exc)
                return
            metric_catalog.GROUP_BISECTIONS.inc()
            logger.warning(
                "fused device call over %d predicts failed (%s: %s); "
                "bisecting", len(items), type(exc).__name__, exc,
            )
            mid = len(items) // 2
            self._execute(spec, items[:mid])
            self._execute(spec, items[mid:])

    # -------------------------------------------- pipelined device path
    def _run_async(self, batch: List[_Item]) -> List[Tuple]:
        """Group a batch and dispatch each group WITHOUT draining: the
        stage + async device call of _device_call, with the blocking D2H
        and fan-out deferred to _drain_call (the pipelined loop drains a
        call only after dispatching its successor). A group that fails at
        dispatch — nothing computed yet — falls back to the strict-serial
        recovery ladder alone."""
        groups: Dict[Tuple, List[_Item]] = {}
        for item in batch:
            key = (item.spec, item.X_pad.shape)
            groups.setdefault(key, []).append(item)
        pendings: List[Tuple] = []
        for (spec, _shape), items in groups.items():
            now = time.monotonic()
            for item in items:
                metric_catalog.BATCHER_QUEUE_WAIT_SECONDS.observe(
                    max(0.0, now - item.t_submit)
                )
            metric_catalog.BATCHER_FUSE_WIDTH.observe(len(items))
            try:
                pending = self._device_dispatch(spec, items)
            except BaseException as exc:  # noqa: BLE001 — ladder fallback
                logger.warning(
                    "pipelined dispatch over %d predicts failed (%s: %s); "
                    "re-running strict-serial",
                    len(items), type(exc).__name__, exc,
                )
                try:
                    self._execute(spec, items)
                except BaseException as exc2:  # noqa: BLE001 — fan out
                    for item in items:
                        item.error = exc2
                        item.done.set()
                continue
            if pending is not None:
                pendings.append(pending)
        return pendings

    def _device_dispatch(self, spec, items: List[_Item]) -> Optional[Tuple]:
        """Stage + dispatch phase of the pipelined device path: resolve
        bank slots, fill the alternating staging buffer, ship the stacked
        input with an explicit (async) jax.device_put and issue the fused
        call. jax dispatches asynchronously, so this returns while the
        device is still computing — the blocking D2H lives in _drain_call.
        Returns (spec, items, out_dev, t0, n), or None when every rider
        was abandoned."""
        from gordo_tpu.util import faults

        import jax

        items = [it for it in items if not it.abandoned]
        if not items:
            return None
        n = len(items)
        b_pad = 1
        while b_pad < min(n, self.max_batch):
            b_pad <<= 2
        b_pad = min(b_pad, self.max_batch)
        bank = self._banks.setdefault(spec, _ParamBank())
        if len({id(it.params) for it in items}) > bank.max_models:
            raise RuntimeError(
                f"fused group of {len(items)} spans more distinct models "
                f"than the param bank holds ({bank.max_models}); bisecting"
            )
        gen = bank.generation
        slots = [bank.slot_of(it.params) for it in items]
        if bank.generation != gen:
            # same churn guard as _device_call: re-resolve once, then fail
            # the group into the recovery ladder
            gen = bank.generation
            slots = [bank.slot_of(it.params) for it in items]
            if bank.generation != gen:
                raise RuntimeError(
                    "param bank churned twice during slot resolution; "
                    "retrying through the recovery ladder"
                )
        X, idx = self._stacked_inputs(items, slots, b_pad)
        t0 = time.monotonic()
        if self._busy_since is None:
            self._busy_since = t0
        try:
            faults.fault_point(
                "serve_device_call", machines=[it.tag for it in items]
            )
            aot = self._aot.get((spec, items[0].n_pad, b_pad, bank.capacity))
            if aot is not None and aot[0] == X.shape:
                program = aot[1]
            else:
                program = _stacked_apply(
                    spec, items[0].n_pad, b_pad, bank.capacity
                )
            # explicit H2D off the pinned staging buffer: device_put frees
            # the staging array for the NEXT fuse as soon as the copy is
            # enqueued, and the donated device copy feeds the program
            X_dev = jax.device_put(X)
            out_dev = program(bank.stacked, idx, X_dev)
        except BaseException as exc:  # noqa: BLE001 — span then re-raise
            self._emit_device_span(items, t0, error=exc)
            raise
        return (spec, items, out_dev, t0, n)

    def _drain_call(self, pending: Tuple) -> None:
        """Drain phase of the pipelined device path: block on the fused
        call's device output (D2H), then run the same fan-out tail as the
        strict-serial path. A compute error surfacing here re-runs the
        whole group through the recovery ladder — the failed call's
        results never left the device, so strict-serial re-execution is
        the correctness fallback, not a duplicate."""
        spec, items, out_dev, t0, n = pending
        try:
            out = np.asarray(out_dev)
        except BaseException as exc:  # noqa: BLE001 — ladder fallback
            self._emit_device_span(items, t0, error=exc)
            self._account_busy(t0)
            logger.warning(
                "pipelined fused call over %d predicts failed at drain "
                "(%s: %s); re-running strict-serial",
                n, type(exc).__name__, exc,
            )
            try:
                self._execute(spec, items)
            except BaseException as exc2:  # noqa: BLE001 — fan out
                for item in items:
                    item.error = exc2
                    item.done.set()
            return
        self._account_busy(t0)
        self._emit_device_span(items, t0)
        metric_catalog.DEVICE_FLOPS.inc(
            _spec_forward_flops(spec) * float(items[0].n_pad) * n
        )
        self.stats["items"] += n
        self.stats["device_calls"] += 1
        self.stats["largest_batch"] = max(self.stats["largest_batch"], n)
        self._fan_out(items, out)

    def _account_busy(self, t0: float) -> None:
        """Busy-seconds for a drained pipelined call, unioned against the
        previous drain's window: overlapping calls must not double-count
        wall-clock, or the duty-cycle gauge would read above 1.0."""
        end = time.monotonic()
        start = max(t0, self._last_drain_end)
        if end > start:
            metric_catalog.DEVICE_BUSY_SECONDS.inc(end - start)
        self._last_drain_end = end

    def _fan_out(self, items: List[_Item], out: np.ndarray) -> None:
        """Per-rider result fan-out shared by the strict-serial and
        pipelined drains: slice each rider's lane, per-lane finite guard,
        wake the waiter."""
        from gordo_tpu.server import resilience
        from gordo_tpu.util import faults

        validate = resilience.validate_output_enabled()
        for i, item in enumerate(items):
            result = out[i, : item.n_keep]
            if validate and not np.all(np.isfinite(result)):
                # per-lane guard: vmap lanes are independent, so a
                # poisoned submission fails alone while its cohort's
                # results fan out untouched
                item.error = faults.NonFiniteDataError(
                    f"non-finite fused-predict output for model "
                    f"{item.tag or '?'!r}"
                )
            else:
                item.result = result
            item.done.set()

    def _stacked_inputs(
        self, items: List[_Item], slots: List[int], b_pad: int
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Fill (and reuse) pinned per-fuse-width stacking buffers instead
        of allocating a fresh (b_pad, *shape) array + index vector per
        call. Pad lanes repeat item 0 (same values the old np.stack
        shipped).

        DOUBLE-buffered per key: jax dispatches device calls
        asynchronously, and with donated inputs (``_stacked_apply``) the
        previous call's H2D buffer may still be feeding the device while
        the dispatcher assembles the next fuse — alternating between two
        staging arrays lets consecutive fused calls overlap
        fill/H2D/compute instead of serializing on one shared buffer."""
        sample = items[0].X_pad
        key = (sample.shape, sample.dtype.str, b_pad)
        entry = self._stack_buffers.get(key)
        if entry is None:
            if len(self._stack_buffers) >= 64:
                # bounded: shapes are bucketed, but a pathological client
                # mix must not grow this into a leak
                self._stack_buffers.clear()
            entry = [
                tuple(
                    (
                        np.empty((b_pad,) + sample.shape, dtype=sample.dtype),
                        np.empty(b_pad, dtype=np.int32),
                    )
                )
                for _ in range(2)
            ] + [0]
            self._stack_buffers[key] = entry
        toggle = entry[2]
        entry[2] = 1 - toggle
        X, idx = entry[toggle]
        for i, item in enumerate(items):
            X[i] = item.X_pad
        X[len(items):] = sample
        idx[: len(slots)] = slots
        idx[len(slots):] = slots[0]
        return X, idx

    def _device_call(self, spec, items: List[_Item]):
        from gordo_tpu.util import faults

        # a waiter that timed out while these queued is gone: computing
        # its lane would be work for nobody (satellite: abandoned items
        # are skipped at fan-out, counted by the waiter itself)
        items = [it for it in items if not it.abandoned]
        if not items:
            return
        n = len(items)
        # few fixed batch buckets per (spec, shape): every new bucket is a
        # fresh XLA compile at serving time (measured as multi-second p95
        # spikes in the A/B bench). Buckets grow 4x so padding waste stays
        # under 4x even for compute-heavy (windowed) specs, where idle vmap
        # lanes are real FLOPs, not noise.
        b_pad = 1
        while b_pad < min(n, self.max_batch):
            b_pad <<= 2
        b_pad = min(b_pad, self.max_batch)
        bank = self._banks.setdefault(spec, _ParamBank())
        if len({id(it.params) for it in items}) > bank.max_models:
            # more distinct models than the bank can hold at once: raising
            # here hands the group to the recovery ladder, which bisects it
            # into bank-sized halves (and bottoms out in the bankless
            # serial rescue) — never a silent wrong-params gather
            raise RuntimeError(
                f"fused group of {len(items)} spans more distinct models "
                f"than the param bank holds ({bank.max_models}); bisecting"
            )
        gen = bank.generation
        slots = [bank.slot_of(it.params) for it in items]
        if bank.generation != gen:
            # an LRU eviction occurred mid-resolution (concurrent warmup
            # registration, or this batch itself churning a full bank):
            # slots resolved before the eviction may point at overwritten
            # lanes — re-resolve, and if the bank churns AGAIN during the
            # second pass, fail the group into the recovery ladder rather
            # than gather from slots of unknown vintage
            gen = bank.generation
            slots = [bank.slot_of(it.params) for it in items]
            if bank.generation != gen:
                raise RuntimeError(
                    "param bank churned twice during slot resolution; "
                    "retrying through the recovery ladder"
                )
        X, idx = self._stacked_inputs(items, slots, b_pad)
        # the busy window feeds the device watchdog: a wedged call here is
        # what flips /healthcheck to 503 (resilience.stuck_device_call_s)
        t0 = time.monotonic()
        self._busy_since = t0
        try:
            faults.fault_point(
                "serve_device_call", machines=[it.tag for it in items]
            )
            # AOT-first: a pre-lowered executable for this exact program
            # never re-traces; shapes are double-checked because windowed
            # specs can (pathologically) pad to more rows than the warmup
            # exemplar — a mismatch quietly takes the jit path instead of
            # failing the group into the recovery ladder
            aot = self._aot.get((spec, items[0].n_pad, b_pad, bank.capacity))
            if aot is not None and aot[0] == X.shape:
                program = aot[1]
            else:
                program = _stacked_apply(
                    spec, items[0].n_pad, b_pad, bank.capacity
                )
            out = np.asarray(program(bank.stacked, idx, X))
        except BaseException as exc:  # noqa: BLE001 — span then re-raise
            self._emit_device_span(items, t0, error=exc)
            raise
        finally:
            self._busy_since = None
            # duty-cycle accounting: busy-seconds accumulate whether the
            # call succeeded or not — the device was occupied either way
            # (unioned against any pipelined drain sharing this window)
            end = time.monotonic()
            metric_catalog.DEVICE_BUSY_SECONDS.inc(
                max(0.0, end - max(t0, self._last_drain_end))
            )
            self._last_drain_end = end
        # recorded BEFORE fan-out (done.set): a rider resuming at its
        # event must already find the device-call span in its trace
        self._emit_device_span(items, t0)
        # achieved FLOPs: useful lanes only (n real riders x n_pad windows
        # each) — padding lanes are waste the MFU numerator must not claim
        metric_catalog.DEVICE_FLOPS.inc(
            _spec_forward_flops(spec) * float(items[0].n_pad) * n
        )
        self.stats["items"] += n
        self.stats["device_calls"] += 1
        self.stats["largest_batch"] = max(self.stats["largest_batch"], n)
        self._fan_out(items, out)

    def _emit_device_span(
        self,
        items: List[_Item],
        t0: float,
        error: Optional[BaseException] = None,
        rescue: bool = False,
    ) -> None:
        """Record the finished device call as a span in EVERY rider's
        trace (parented at that rider's enqueue point, span-links naming
        the co-fused riders) plus one event in the global trace buffer.
        Runs in the dispatcher thread, which never holds a request
        context — hence explicit fan-out instead of telemetry.span."""
        from gordo_tpu.observability import telemetry, tracing

        duration = time.monotonic() - t0
        attrs: Dict[str, Any] = {"fused": len(items)}
        if rescue:
            attrs["rescue"] = 1
        if error is not None:
            attrs["error"] = type(error).__name__
        telemetry.add_trace_event("serve_device_call", t0, duration, **attrs)
        riders = [it for it in items if it.trace_ctx is not None]
        for item in riders:
            links = [
                (other.trace_ctx.trace_id, other.trace_ctx.span_id or "")
                for other in riders
                if other is not item
            ]
            tracing.record_into(
                item.trace_ctx, "serve_device_call", t0, duration,
                links=links, model=item.tag, **attrs,
            )

    def _serial_rescue(self, spec, item: _Item, group_exc: BaseException):
        """Last ladder rung: retry one predict through the un-fused
        program. Its failure (or a matching injected fault) lands on this
        item alone."""
        from gordo_tpu.server import resilience
        from gordo_tpu.util import faults

        if item.abandoned:
            return
        metric_catalog.GROUP_SERIAL_RESCUES.inc()
        try:
            t0 = time.monotonic()
            self._busy_since = t0
            try:
                faults.fault_point("serve_device_call", machines=[item.tag])
                out = np.asarray(
                    _single_apply(spec, item.n_pad)(item.params, item.X_pad)
                )
            finally:
                self._busy_since = None
                metric_catalog.DEVICE_BUSY_SECONDS.inc(
                    max(0.0, time.monotonic() - t0)
                )
                self._emit_device_span([item], t0, rescue=True)
            metric_catalog.DEVICE_FLOPS.inc(
                _spec_forward_flops(spec) * float(item.n_pad)
            )
            result = out[: item.n_keep]
            if resilience.validate_output_enabled() and not np.all(
                np.isfinite(result)
            ):
                raise faults.NonFiniteDataError(
                    f"non-finite predict output for model "
                    f"{item.tag or '?'!r}"
                )
            item.result = result
        except BaseException as rescue_exc:  # noqa: BLE001 — this item only
            logger.warning(
                "serial rescue failed for model %r (group error %s: %s): %s",
                item.tag or "?", type(group_exc).__name__, group_exc,
                rescue_exc,
            )
            item.error = rescue_exc
        item.done.set()


# ------------------------------------------------------------ global switch
_batcher: Optional[CrossModelBatcher] = None
_batcher_lock = threading.Lock()


def peek_batcher() -> Optional[CrossModelBatcher]:
    """The process batcher if one exists — never creates one (observability
    callers must not flip batching on as a side effect)."""
    return _batcher


def get_batcher() -> Optional[CrossModelBatcher]:
    """The process batcher, created on first use when enabled by env.

    ``GORDO_TPU_SERVING_BATCH``: ``auto`` = on with per-spec measured
    self-A/B (stands down where batching loses); ``1``/``true``/``yes`` =
    forced on (benchmark harness); anything else = off.
    """
    global _batcher
    if _batcher is not None:
        return _batcher
    mode = os.environ.get("GORDO_TPU_SERVING_BATCH", "").lower()
    if mode not in ("1", "true", "yes", "auto"):
        return None
    with _batcher_lock:
        if _batcher is None:
            window_ms = float(os.environ.get("GORDO_TPU_BATCH_WINDOW_MS", "0"))
            max_batch = int(os.environ.get("GORDO_TPU_BATCH_MAX", "64"))
            _batcher = CrossModelBatcher(
                window_ms, max_batch, self_ab=mode == "auto"
            )
            logger.info(
                "cross-model batcher on (window %.1fms, max %d, self-A/B %s)",
                window_ms, max_batch, "on" if mode == "auto" else "off",
            )
    return _batcher


_direct = threading.local()


@contextlib.contextmanager
def direct_path():
    """This thread's predicts go direct inside the block, whatever the
    batcher decided. Warmup compiles the per-request program with it: which
    of the two paths serves an architecture is a measured decision that can
    fall either way between two boots, and the program set a worker
    compiles — and leaves in the persistent cache — must not depend on it."""
    _direct.on = True
    try:
        yield
    finally:
        _direct.on = False


def maybe_submit(spec, params, X) -> Optional[np.ndarray]:
    """Route through the batcher when enabled; None means 'go direct'.

    The dispatcher thread itself must not re-enter the queue (a model whose
    predict is invoked inside another predict would deadlock), so it always
    goes direct.
    """
    batcher = get_batcher()
    if batcher is None:
        return None
    if (
        threading.current_thread().name == "gordo-batcher"
        or getattr(_direct, "on", False)
    ):
        return None
    from gordo_tpu.ops.attention import spec_may_use_ring

    if spec_may_use_ring(spec):
        # ring attention (shard_map) cannot run under this batcher's
        # vmap-over-models; such specs always predict direct
        return None
    from gordo_tpu.parallel.data_parallel import dp_degree
    from gordo_tpu.parallel.expert_parallel import ep_degree
    from gordo_tpu.parallel.pipeline_parallel import pp_degree
    from gordo_tpu.parallel.tensor_parallel import tp_degree

    if (
        tp_degree(spec) > 1
        or pp_degree(spec) > 1
        or ep_degree(spec) > 1
        or dp_degree(spec) > 1
    ):
        # tensor-parallel params are sharded over the mesh, the
        # pipeline/expert shard_maps can't nest under vmap, and dp params
        # live replicated on their own mesh — predict direct
        return None
    return batcher.submit(spec, params, X)
