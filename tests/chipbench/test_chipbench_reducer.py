"""The trace reducer against a small trace recorded on the chip
(``chipbench/fixtures/transformer_chunk.xplane.pb``: one chunk of
``transformer_144.fleet_build`` of PR 25's first round, 24 machines, trimmed to the device's
Steps / XLA Modules lines, the first 1,976 operations and the host events the
reducer reads) and against hand-made events."""

import os
from types import SimpleNamespace

import pytest

from chipbench import trace
from chipbench.metrics import device_idle_share, fleet_step_ms_per_machine

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FIXTURE = os.path.join(ROOT, "chipbench", "fixtures", "transformer_chunk.xplane.pb")
WINDOW_S = 6.3503  # the fixture's chipbench.build mark: 0.048646 .. 6.398985


@pytest.fixture(scope="module")
def planes():
    from jax.profiler import ProfileData

    return list(ProfileData.from_file(FIXTURE).planes)


def test_recorded_chunk(planes):
    reduced = trace.reduce_planes(planes, WINDOW_S)
    # by hand from the file: one execution of the chunk program,
    # 0.629505674 .. 5.768015054 on the device's clock
    assert reduced.module_runs == {"jit_one_machine": 1}
    assert reduced.program_seconds("jit_one_machine") == pytest.approx(5.13850938, abs=1e-8)
    # the operations kept start 0.32 us into the program and the enclosing
    # while ends 58.9 us before it does
    assert reduced.busy_s == pytest.approx(5.138450497, abs=1e-8)
    assert 0 < reduced.busy_s <= reduced.window_s == WINDOW_S
    assert reduced.n_devices == 1 and reduced.n_ops == 1976
    ctx = {"trace": reduced, "machines": 24}
    assert device_idle_share.read(ctx) == pytest.approx(100 * (1 - 5.138450497 / 6.3503))
    assert device_idle_share.read(ctx) == pytest.approx(19.083, abs=1e-3)
    assert fleet_step_ms_per_machine.read(ctx) == pytest.approx(5138.50938 / 24)
    # the idle stretches before and after the program lie inside build()
    (what, seconds), = reduced.idle_gaps
    assert what == "chipbench.build"
    assert seconds == pytest.approx((0.629505992 - 0.043531538) + (6.415102 - 5.768014816), abs=1e-4)
    # self time: the while that encloses the scan is charged only what its
    # body leaves
    kinds = dict(reduced.device_ops)
    assert 0.999 * reduced.busy_s < sum(kinds.values()) <= reduced.busy_s  # the ten largest kinds
    assert len(reduced.device_ops) <= 10


def test_host_events_tell_the_same_execution(planes):
    host = [
        e for plane in planes if plane.name.startswith("/host:")
        for line in plane.lines for e in trace._events(line)
    ]
    (name, start, end), = trace.host_executions(host)
    assert name == "jit_one_machine"
    # launch 0.629272835, completion seen 5.768630968: 0.85 ms more than the
    # device's own 5.13850938 s
    assert end - start == pytest.approx(5.139358133, abs=1e-8)
    assert abs((end - start) - 5.13850938) < 1e-3
    # the cross-check every traced run makes, here on the chunk program: 0.017 %
    checked = trace.cross_check(planes, program="one_machine")
    assert checked["device_plane_s"] == pytest.approx(5.13850938, abs=1e-8)
    assert checked["host_events_s"] == pytest.approx(end - start)
    assert checked["executions"] == 1
    assert checked["median_gap"] == checked["worst_gap"] == pytest.approx(0.000165, abs=1e-6)
    with pytest.raises(trace.TraceError, match="cross-check"):
        trace.cross_check(planes, program="one_machine", tolerance=1e-4)
    with pytest.raises(trace.TraceError, match="0 executions of chipbench_calibration"):
        trace.cross_check(planes)
    # operations by self time, as the detail session ranks them
    assert trace.op_ranking(planes) == trace.reduce_planes(planes, WINDOW_S).device_ops
    # with the device plane left out (TRACE_ONLY_HOST) the reducer falls back on them
    host_only = [p for p in planes if not p.name.startswith(trace.DEVICE_PLANE)]
    reduced = trace.reduce_planes(host_only, WINDOW_S)
    assert reduced.busy_s == pytest.approx(5.139358133, abs=1e-8)
    assert reduced.program_seconds("jit_one_machine") == pytest.approx(reduced.busy_s)


def _plane(name, lines):
    def event(n, a, b):
        return SimpleNamespace(name=n, start_ns=a * 1e9, duration_ns=(b - a) * 1e9)

    return SimpleNamespace(
        name=name,
        lines=[SimpleNamespace(name=ln, events=[event(*e) for e in evs]) for ln, evs in lines],
    )


def test_queued_programs_run_one_after_another():
    # two launches back to back; the second starts when the first is done
    host = _plane("/host:CPU", [
        ("python3", [("PjitFunction(one_machine)", 1.0, 1.01), ("PjitFunction(one_machine)", 1.02, 1.03),
                     ("chipbench.build", 0.5, 10.0)]),
        ("main", [("tpu::System::Execute", 1.005, 1.006), ("tpu::System::Execute", 1.025, 1.026)]),
        ("done", [("tpu::System::Execute=>Done", 5.0, 5.0002), ("tpu::System::Execute=>Done", 9.0, 9.0002)]),
    ])
    reduced = trace.reduce_planes([host], 9.5)
    assert reduced.busy_s == pytest.approx((5.0 - 1.006) + (9.0 - 5.0))
    assert reduced.module_runs == {"jit_one_machine": 2}
    assert dict(reduced.idle_gaps)["chipbench.build"] == pytest.approx(0.506 + 1.0)


def test_cross_check_reads_the_calibration_alone():
    # an operation-level session: the calibration's warm-up run, its three
    # marked runs, then a chunk program the session is cut inside of
    runs = [(0.1, 0.4), (1.0, 1.3), (1.4, 1.7), (1.8, 2.1)]
    cal = "jit_chipbench_calibration(42)"

    def session(stretch, late=0.0):
        # ``late``: the second marked run's completion is seen that much later
        seen = [b + 0.0005 + (late if i == 2 else 0.0) for i, (_, b) in enumerate(runs)]
        return [
            _plane("/device:TPU:0", [
                ("XLA Ops", [("%dot.1 = d", a, a + stretch * (b - a)) for a, b in runs]
                 + [("%fusion.7 = f", 3.0, 3.2)]),
                ("XLA Modules", [(cal, a, a + stretch * (b - a)) for a, b in runs]),
            ]),
            _plane("/host:CPU", [
                ("python3", [("PjitFunction(chipbench_calibration)", a - 0.002, a - 0.001) for a, _ in runs]
                 + [("chipbench.calibrate", 0.9, 2.2), ("PjitFunction(one_machine)", 2.9, 2.95)]),
                ("main", [("tpu::System::Execute", a - 0.0016, a - 0.0015) for a, _ in runs]
                 + [("tpu::System::Execute", 2.93, 2.931)]),
                ("done", [("tpu::System::Execute=>Done", b, b + 0.0001) for b in seen]),
            ]),
        ]

    checked = trace.cross_check(session(1.0))
    assert checked["device_plane_s"] == pytest.approx(0.9)
    assert checked["host_events_s"] == pytest.approx(0.9 + 3 * 0.002)
    assert checked["executions"] == 3
    assert checked["median_gap"] == pytest.approx(0.002 / 0.3)
    assert checked["worst_gap"] == pytest.approx(0.002 / 0.3)
    # a device that finished a tenth earlier than the host saw, every time
    with pytest.raises(trace.TraceError, match="cross-check"):
        trace.cross_check(session(0.9))
    # one completion seen 48 ms late is reported, and is no fault of the method
    checked = trace.cross_check(session(1.0, late=0.048))
    assert checked["median_gap"] == pytest.approx(0.002 / 0.3)
    assert checked["worst_gap"] == pytest.approx(0.050 / 0.3)
    # what ran before the mark ended is not the detail build's
    assert trace.op_ranking(session(1.0)) == [["fusion", pytest.approx(0.2)]]


def test_interval_arithmetic():
    assert trace.union_seconds([(0, 2), (1, 3), (5, 6), (5.5, 5.8)]) == pytest.approx(4.0)
    assert trace.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    nested = [("%while.1 = w", 0, 10), ("%fusion.2 = f", 1, 3), ("%fusion.3 = f", 3, 4),
              ("%while.9 = w", 5, 9), ("%copy.1 = c", 6, 7), ("%dot = d", 11, 12)]
    assert trace.self_seconds(nested) == {"fusion": 3.0, "copy": 1.0, "while": 6.0, "dot": 1.0}
    assert trace._base_name("jit_one_machine(1916783542078539670)") == "jit_one_machine"
    assert trace._base_name("%broadcast.16879.clone.clone = bf16[12,64]{1,0} broadcast(...)") == "broadcast"


@pytest.mark.parametrize(
    "planes,reason",
    [
        ([_plane("/host:CPU", [("python3", [("chipbench.build", 0, 1)])])], "no device plane"),
        ([_plane("/device:TPU:0", [("XLA Ops", [("%a = x", 0, 1)]),
                                   ("XLA TraceMe", [("Trace Buffers Dropped", 1, 2)])])], "dropped"),
        ([_plane("/device:TPU:0", [("XLA Ops", [("%a = x", 0, 3)])])], "exceeds window_s"),
        ([_plane("/host:CPU", [("main", [("tpu::System::Execute", 1, 1.1)])])], "launches but 0 completions"),
    ],
)
def test_a_trace_that_cannot_give_busy_is_an_error(planes, reason):
    with pytest.raises(trace.TraceError, match=reason):
        trace.reduce_planes(planes, 2.0)
