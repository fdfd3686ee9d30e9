"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: how long the device was busy, how long each compiled program
ran on it, which operations took the time, and where the device sat idle.

Read with ``jax.profiler.ProfileData`` and nothing else. On a TPU each chip is
a plane ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per
executed operation and whose line ``XLA Modules`` one per executed program.
An LSTM scan issues two million operations a chunk, and the device's trace
buffer drops what comes after some six million: a session over whole builds
is therefore taken in the mode ``TRACE_ONLY_HOST``, which leaves the device
plane out and keeps the runtime's own host events. There each execution of a
program is the stretch from its launch (``tpu::System::Execute``, or the end
of the execution before it, whichever is later: the device runs one program
at a time, in order) to its ``tpu::System::Execute=>Done``; host and device
events share a clock, and the two readings of one execution agree to a
millisecond (tests/chipbench); every traced run checks that again on a
program of the harness's own (:func:`cross_check`). A CPU rehearsal has
neither; with ``rehearsal=True`` the host plane's XLA client threads stand in, so that the
harness's control flow can be exercised. Such numbers are never device numbers.

The operation-level session after the window is also read by named scope
(:func:`read_detail`): an event's ``op_name``, where ``jax.named_scope`` and
the transformations leave their names, is the stat ``tf_op`` of the event's
*metadata* (libtpu 0.0.34), which ``ProfileData`` does not expose, so that one
map is read off the file's wire format (:func:`op_names`).
"""

from __future__ import annotations

import glob
import os
import re
import statistics
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]  # start, end in seconds on the trace's clock

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
REHEARSAL_LINE = "tf_XLA"  # the CPU client's and its thread pool's threads
# the harness's own marks and the program's stages (``telemetry.span``), on
# one clock: an idle gap goes to the innermost of either that covers it, on
# the threads the harness marked itself (the build thread's stages say what
# kept the device waiting; the pools' jobs run beside them)
HARNESS_MARK = "chipbench."
HOST_MARK = (HARNESS_MARK, "gordo.")
LAUNCH = "tpu::System::Execute"
DONE = "tpu::System::Execute=>Done"
DROPPED = "Trace Buffers Dropped"
CALL = "PjitFunction("
CALIBRATE_MARK = "chipbench.calibrate"
CALIBRATION = "chipbench_calibration"


class TraceError(RuntimeError):
    """The trace cannot give ``busy_s`` and ``window_s``."""


@dataclass
class Reduced:
    window_s: float
    busy_s: float  # averaged over the device planes
    n_devices: int
    n_ops: int
    module_s: Dict[str, float] = field(default_factory=dict)  # summed over devices
    module_runs: Dict[str, int] = field(default_factory=dict)
    device_ops: List[List[object]] = field(default_factory=list)
    idle_gaps: List[List[object]] = field(default_factory=list)
    # from the operation-level session after the window (:func:`read_detail`)
    detail_s: float = 0.0
    scope_s: Dict[str, float] = field(default_factory=dict)
    op_s: Dict[str, List[float]] = field(default_factory=dict)
    op_scope: Dict[str, str] = field(default_factory=dict)

    def device_scopes(self, top: int = 10) -> List[List[object]]:
        """The detail cut's named scopes by self time; the operations under
        no scope are the row ``no_scope``."""
        return _rank({k or "no_scope": v for k, v in self.scope_s.items()}, top)

    def program_seconds(self, pattern: str) -> Optional[float]:
        """Device seconds of every execution of the programs whose name
        contains ``pattern``; None where none ran."""
        hits = [s for name, s in self.module_s.items() if pattern in name]
        return sum(hits) if hits else None


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise TraceError(f"the profiler left no .xplane.pb under {log_dir}")
    return files[-1]


def union_seconds(intervals: Sequence[Interval]) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def gaps(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The stretches of [lo, hi] no interval covers."""
    out, end = [], lo
    for a, b in sorted(intervals):
        if a > end:
            out.append((end, min(a, hi)))
        end = max(end, b)
        if end >= hi:
            break
    if end < hi:
        out.append((end, hi))
    return [(a, b) for a, b in out if b > a]


def _events(line) -> List[Tuple[str, float, float]]:
    return [
        (e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
        for e in line.events
        if e.duration_ns > 0
    ]


def _base_name(name: str) -> str:
    """``%fusion.123 = bf16[…] fusion(…)`` → ``fusion``;
    ``%broadcast.16879.clone`` → ``broadcast``; ``jit_f(123456)`` → ``jit_f``:
    one row a kind of operation or a program, not one a compiled instance."""
    name = name.split(" = ")[0].split("(")[0].lstrip("%")
    while name.endswith(".clone"):
        name = name[: -len(".clone")]
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def _instruction(name: str) -> str:
    """``%fusion.123 = bf16[…] fusion(…)`` → ``fusion.123``: one compiled
    instance of an operation."""
    return name.split(" = ")[0].lstrip("%")


def self_times(events: Sequence[Tuple[str, float, float]], key=_base_name) -> Dict[str, List[float]]:
    """[seconds an operation ran itself, its runs] by ``key`` of its name: an
    enclosing operation (a ``while`` around its body) is charged only what
    its children leave."""
    out: Dict[str, List[float]] = {}
    stack: List[List[object]] = []  # [key, end, start, seconds of children]

    def close():
        name, end, start, inner = stack.pop()
        entry = out.setdefault(name, [0.0, 0])
        entry[0] += (end - start) - inner
        entry[1] += 1
        if stack:
            stack[-1][3] += end - start

    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= a:
            close()
        stack.append([key(name), b, a, 0.0])
    while stack:
        close()
    return out


def self_seconds(events: Sequence[Tuple[str, float, float]]) -> Dict[str, float]:
    """Seconds an operation ran itself, by kind."""
    return {kind: seconds for kind, (seconds, _) in self_times(events).items()}


def host_executions(host_events: Sequence[Tuple[str, float, float]]) -> List[Tuple[str, float, float]]:
    """Program executions on the device as the runtime's host events tell
    them: (program, start, end), the program named by the jitted call that
    launched it."""
    launches = sorted(e for e in host_events if e[0] == LAUNCH)
    dones = sorted((e for e in host_events if e[0] == DONE), key=lambda e: e[1])
    calls = [e for e in host_events if e[0].startswith(CALL)]
    if len(launches) != len(dones):
        raise TraceError(
            f"{len(launches)} program launches but {len(dones)} completions in the trace"
        )
    runs, free_at = [], float("-inf")
    for (_, launch, launched), (_, done, _) in zip(
        sorted(launches, key=lambda e: e[1]), dones
    ):
        inside = [c for c in calls if c[1] <= launch <= c[2]]
        name = min(inside, key=lambda c: c[2] - c[1])[0] if inside else "unnamed"
        runs.append(("jit_" + name[len(CALL):].rstrip(")"), max(launched, free_at), done))
        free_at = done
    return runs


def _sort(planes, rehearsal: bool):
    """The planes' events: per device (operations, programs), and the host's."""
    planes = list(planes)
    devices = []
    host_events: List[Tuple[str, float, float]] = []
    for plane in planes:
        if plane.name.startswith(DEVICE_PLANE):
            lines = {line.name: _events(line) for line in plane.lines}
            if any(e[0] == DROPPED for events in lines.values() for e in events):
                raise TraceError(
                    "the device's trace buffer overflowed and dropped events: "
                    "too many operations for one operation-level session"
                )
            if OPS_LINE in lines:
                devices.append((lines[OPS_LINE], lines.get(MODULES_LINE, [])))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_events += _events(line)
    if not devices and rehearsal:
        ops = []
        for plane in planes:
            for line in plane.lines:
                if line.name.startswith(REHEARSAL_LINE):
                    ops += _events(line)
        if ops:
            devices.append((ops, []))
    return devices, host_events


def _stage_marks(planes) -> List[Tuple[str, float, float]]:
    """The host marks of every thread that carries one of the harness's own."""
    out: List[Tuple[str, float, float]] = []
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                marks = [e for e in _events(line) if e[0].startswith(HOST_MARK)]
                if any(e[0].startswith(HARNESS_MARK) for e in marks):
                    out += marks
    return out


def _mark(host_events, name: str) -> Interval:
    """The stretch a host mark covers; everything, where it is not there."""
    hits = [(a, b) for n, a, b in host_events if n == name]
    return hits[0] if hits else (float("-inf"), float("inf"))


def cross_check(planes, program: str = CALIBRATION, mark: str = CALIBRATE_MARK,
                tolerance: float = 0.01) -> Dict[str, float]:
    """The same executions read both ways in one operation-level session:
    from the device plane's ``XLA Modules`` and from the runtime's host events
    as :func:`host_executions` reads a window, paired in order. Returns the
    two sums and the gap of each pair as a share of the device's reading, the
    median pair's and the worst's. The median over ``tolerance`` is an
    error: the window's device time could not be trusted then. One pair may
    read far off, and is reported: the host can see a completion tens of
    milliseconds late (one run of three read one of three pairs 48 ms late;
    PERF.md), where a reading that is wrong by its method is wrong in every
    pair."""
    devices, host_events = _sort(planes, rehearsal=False)
    lo, hi = _mark(host_events, mark)
    on_device = sorted(
        (a, b - a) for _, modules in devices for name, a, b in modules
        if program in name and lo <= a <= hi
    )
    inside = [e for e in host_events if e[0].startswith(CALL) or lo <= e[1] <= hi]
    by_host = sorted((a, b - a) for name, a, b in host_executions(inside) if program in name)
    if not on_device or len(on_device) != len(by_host):
        raise TraceError(
            f"cross-check: {len(on_device)} executions of {program} on the device "
            f"plane, {len(by_host)} among the host events"
        )
    pair_gaps = sorted(abs(h - d) / d for (_, d), (_, h) in zip(on_device, by_host))
    checked = {
        "device_plane_s": sum(d for _, d in on_device),
        "host_events_s": sum(h for _, h in by_host),
        "executions": len(pair_gaps),
        "median_gap": statistics.median(pair_gaps),
        "worst_gap": pair_gaps[-1],
    }
    if checked["median_gap"] > tolerance:
        raise TraceError(
            f"cross-check: {program} ran {checked['device_plane_s']:.6f} s by the device "
            f"plane but {checked['host_events_s']:.6f} s by the runtime's host events; "
            f"the median execution's readings differ by {checked['median_gap']:.4f} of it"
        )
    return checked


def op_ranking(planes, rehearsal: bool = False, after: str = CALIBRATE_MARK, top: int = 10):
    """The device's operations by self time, the ``top`` kinds; operations
    that started before the mark ``after`` ended are left out."""
    return read_detail(planes, {}, rehearsal, after, top)["device_ops"]


# ---------------------------------------------------- device time by scope
def _varint(buf, i):
    value = shift = 0
    while True:
        value |= (buf[i] & 0x7F) << shift
        shift, i = shift + 7, i + 1
        if buf[i - 1] < 0x80:
            return value, i


def _fields(buf):
    """(field number, int | bytes | None for fixed-width) of one message."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        if key & 7 == 0:
            value, i = _varint(buf, i)
        elif key & 7 == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        else:
            value, i = None, i + (8 if key & 7 == 1 else 4)
        yield key >> 3, value


def op_names(path: str) -> Dict[str, str]:
    """{event name (the HLO's text): op_name} of the TPU planes. XSpace.planes=1;
    XPlane.name=2, .event_metadata=4, .stat_metadata=5 (maps: key=1, value=2);
    X*Metadata.name=2, .stats=5; XStat.metadata_id=1, .str_value=5, .ref_value=7."""
    out = {}
    with open(path, "rb") as fh:
        space = fh.read()
    for plane in (list(_fields(v)) for n, v in _fields(space) if n == 1):
        if not dict(plane)[2].startswith(DEVICE_PLANE.encode()):
            continue
        entries = [(n, dict(_fields(v))) for n, v in plane if n in (4, 5)]
        stat_name = {e[1]: dict(_fields(e[2]))[2].decode() for n, e in entries if n == 5}
        for meta in (list(_fields(e[2])) for n, e in entries if n == 4):
            for stat in (dict(_fields(v)) for n, v in meta if n == 5):
                if stat_name.get(stat.get(1)) == "tf_op":
                    op_name = stat[5].decode() if 5 in stat else stat_name.get(stat.get(7), "")
                    out[dict(meta)[2].decode()] = op_name
    return out


# what JAX itself writes between the scopes of an op_name: control flow, and
# the functions that jit and pmap name
_NOT_A_SCOPE = re.compile(r"while|body|cond|body_pred|closed_call|checkpoint|rematted_computation|branch_\d+_fun")
_CALLS = {"jit", "pjit", "pmap"}


def classify(op_name: str) -> Tuple[str, str]:
    """(innermost named scope, '' where there is none; 'bwd' | 'fwd' |
    'plain') of an operation's ``op_name``, any scope name. JAX writes
    ``a/b/primitive`` with ``jax.named_scope`` names as plain parts and each
    transformation round the part it was applied under: ``jvp(...)`` round a
    forward under differentiation, ``transpose(jvp(...))`` round its
    backward; neither is a plain forward."""
    way = "bwd" if "transpose(" in op_name else "fwd" if "jvp(" in op_name else "plain"
    for part in reversed(op_name.rstrip(":").split("/")[:-1]):
        called = False
        while part.endswith(")") and "(" in part:
            head, part = part[:-1].split("(", 1)
            called = called or head in _CALLS
        if part and not called and not _NOT_A_SCOPE.fullmatch(part):
            return part, way
    return "", way


def read_detail(planes, names: Dict[str, str], rehearsal: bool = False,
                after: str = CALIBRATE_MARK, top: int = 10) -> Dict[str, object]:
    """The operation-level session after the window, once the mark ``after``
    has ended (the detail build's cut), as fields of :class:`Reduced`:
    ``detail_s`` the device self seconds the cut holds; ``op_s`` every
    compiled operation's [self seconds, runs], summed over devices;
    ``op_scope`` its innermost named scope by ``names`` (:func:`op_names`);
    ``scope_s`` the self seconds by scope, unscoped time under ``""``;
    ``device_ops`` the ``top`` kinds of operation."""
    devices, host_events = _sort(planes, rehearsal)
    _, since = _mark(host_events, after)
    since = since if since != float("inf") else float("-inf")
    op_s: Dict[str, List[float]] = {}
    kind_s: Dict[str, float] = {}
    op_scope: Dict[str, str] = {}
    for ops, _ in devices:
        cut = [e for e in ops if e[1] >= since]
        for name, (seconds, runs) in self_times(cut, key=lambda n: n).items():
            op = _instruction(name)
            entry = op_s.setdefault(op, [0.0, 0])
            entry[0] += seconds
            entry[1] += runs
            op_scope[op] = classify(names.get(name, ""))[0]
            kind = _base_name(name)
            kind_s[kind] = kind_s.get(kind, 0.0) + seconds
    scope_s: Dict[str, float] = {}
    for op, (seconds, _) in op_s.items():
        scope_s[op_scope[op]] = scope_s.get(op_scope[op], 0.0) + seconds
    return {
        "detail_s": sum(scope_s.values()),
        "op_s": op_s,
        "op_scope": op_scope,
        "scope_s": scope_s,
        "device_ops": _rank(kind_s, top),
    }


def _rank(seconds_by_name: Dict[str, float], top: int) -> List[List[object]]:
    return [[k, v] for k, v in sorted(seconds_by_name.items(), key=lambda kv: -kv[1])[:top]]


def reduce_planes(planes, window_s: float, rehearsal: bool = False, top: int = 10) -> Reduced:
    planes = list(planes)
    devices, host_events = _sort(planes, rehearsal)
    marks = _stage_marks(planes)
    if not devices and any(e[0] == LAUNCH for e in host_events):
        # a host-only session: one chip's executions, as the runtime saw them
        runs = host_executions(host_events)
        devices.append((runs, runs))
    if not devices:
        raise TraceError("the trace has no device plane with an 'XLA Ops' line")

    busy, n_ops = [], 0
    module_s: Dict[str, float] = {}
    module_runs: Dict[str, int] = {}
    op_s: Dict[str, float] = {}
    idle: Dict[str, float] = {}
    for ops, modules in devices:
        spans = [(a, b) for _, a, b in ops]
        busy.append(union_seconds(spans))
        n_ops += len(ops)
        for key, seconds in self_seconds(ops).items():
            op_s[key] = op_s.get(key, 0.0) + seconds
        for name, a, b in modules:
            key = _base_name(name)
            module_s[key] = module_s.get(key, 0.0) + (b - a)
            module_runs[key] = module_runs.get(key, 0) + 1
        if spans:
            lo = min(a for a, _ in spans)
            hi = max(b for _, b in spans)
            if marks:
                lo = min(lo, min(a for _, a, _ in marks))
                hi = max(hi, max(b for _, _, b in marks))
            for a, b in gaps(spans, lo, hi):
                if b - a < 1e-4:
                    continue  # the gaps between operations of one program
                mid = 0.5 * (a + b)
                inside = [m for m in marks if m[1] <= mid <= m[2]]
                # the innermost mark says what the host was doing
                what = min(inside, key=lambda m: m[2] - m[1])[0] if inside else "outside_marks"
                idle[what] = idle.get(what, 0.0) + (b - a)
    busy_s = sum(busy) / len(busy)
    if not busy_s > 0:
        raise TraceError("no operation ran on the device inside the traced window")
    if busy_s > window_s:
        raise TraceError(f"busy_s {busy_s:.6f} exceeds window_s {window_s:.6f}")

    return Reduced(
        window_s=window_s,
        busy_s=busy_s,
        n_devices=len(devices),
        n_ops=n_ops,
        module_s=module_s,
        module_runs=module_runs,
        device_ops=_rank(op_s, top),
        idle_gaps=_rank(idle, top),
    )


def read_planes(path: str):
    from jax.profiler import ProfileData

    return list(ProfileData.from_file(path).planes)
