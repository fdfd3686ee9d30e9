"""What the program writes reaches a reader that is a file of its own: any
series of the program's build catalog through ``ctx["before"]`` and
``ctx["after"]``, and device time by named scope and by compiled operation
through ``ctx["trace"]``. The two readers beside this file are examples, not
metrics of ``BENCHMARK.json``."""

import importlib.util
import json
import os
from types import SimpleNamespace

import pytest

from chipbench import readers, run, trace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MANIFEST = os.path.join(ROOT, "chipbench", "rehearsal", "manifest.json")
FIXTURE = os.path.join(ROOT, "chipbench", "fixtures", "transformer_scopes.xplane.pb")


def _reader(name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(HERE, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_a_series_registered_anywhere_reaches_a_reader():
    from gordo_tpu.observability import telemetry

    registry = telemetry.default_registry()
    dropped = registry.counter(
        "gordo_build_test_tokens_dropped_total", "tokens no expert took (a test's)", ("expert",)
    )
    idle = registry.gauge("gordo_build_test_experts_idle", "experts with no token (a test's)")
    routed = registry.histogram("gordo_build_test_route_seconds", "routing (a test's)", ("layer",))
    other = registry.counter("gordo_server_test_total", "not the build's (a test's)")
    before = run.program_counters()
    dropped.labels(expert="3").inc(5)
    idle.set(2)
    routed.labels(layer="1").observe(0.25)
    routed.labels(layer="1").observe(0.5)
    other.inc()
    ctx = {"before": before, "after": run.program_counters()}
    assert readers.counter_delta(ctx, "gordo_build_test_tokens_dropped_total{expert=3}") == 5
    assert readers.counter_delta(ctx, "gordo_build_test_experts_idle") == 2
    assert readers.counter_delta(ctx, "gordo_build_test_route_seconds{layer=1}") == pytest.approx(0.75)
    assert readers.counter_delta(ctx, "gordo_build_test_tokens_dropped_total{expert=4}") is None
    assert not any(key.startswith("gordo_server_") for key in ctx["after"])
    # the keys the harness reads itself are as they were
    assert {"compiles", "oom_bisections", "bucket_retries", "serial_fallbacks"} <= set(before)
    assert all(isinstance(v, (int, float)) for v in ctx["after"].values())


def test_every_reader_is_an_entry_and_every_entry_reads_an_accepted_cell():
    """No reader under ``chipbench/metrics/`` is stranded: ``BENCHMARK.json``
    names it, or a manifest of a tiny twin or of the rehearsals does. And
    ``BENCHMARK.json`` lists a metric only on its own cells, none a twin's."""
    def load(*parts):
        with open(os.path.join(ROOT, *parts)) as fh:
            return json.load(fh)

    accepted = load("BENCHMARK.json")
    manifests = [accepted, load("chipbench", "rehearsal", "manifest.json")] + [
        load("tests", "chipbench", name) for name in ("lfm2_manifest.json", "xing_manifest.json")
    ]
    named = {m["name"] for own in manifests for m in own["end_to_end"] + own["per_layer"]}
    files = {
        name[: -len(".py")] for name in os.listdir(os.path.join(ROOT, "chipbench", "metrics"))
        if name.endswith(".py") and name != "__init__.py"
    }
    assert files <= named, sorted(files - named)
    cells = {w["name"] for w in accepted["workloads"]}
    for metric in accepted["end_to_end"] + accepted["per_layer"]:
        assert set(metric.get("workloads", cells)) <= cells, metric["name"]


def test_example_readers_read_the_rehearsals_ctx(capsys, monkeypatch):
    seen = {}
    real = run.read_metrics

    def spy(metrics, ctx):
        seen.update(ctx)
        return real(metrics, ctx)

    monkeypatch.setattr(run, "read_metrics", spy)
    code = run.main([
        "--workload", "transformer_tiny.rehearsal", "--seed", "3400000002", "--seconds", "0.5",
        "--trace", "1", "--rehearsal", "--manifest", MANIFEST,
    ])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert code == 0 and line["correct"] is True
    # one bucket a build, its program traced by the warm-up: a hit a build
    builds = line["attempted"] / seen["cell"]["traffic"].machines_per_build
    assert _reader("reader_program_cache_hits").read(seen) == builds
    # a series exists once it was touched; the harness's own key reads 0 until then
    assert seen["after"].get("gordo_build_xla_compiles_total{source=compiled}", 0.0) == seen["after"]["compiles"]
    # a CPU rehearsal's cut names no scope: every operation is unscoped, the
    # scope's reader has nothing to read, and the breakdown says so
    reduced = seen["trace"]
    assert set(reduced.scope_s) == {""} and reduced.detail_s == pytest.approx(reduced.scope_s[""])
    assert reduced.detail_s == pytest.approx(sum(s for s, _ in reduced.op_s.values()))
    assert all(runs >= 1 for _, runs in reduced.op_s.values())
    assert _reader("reader_scope_share").read(seen) is None
    assert [name for name, _ in line["breakdown"]["device_scopes"]] == ["no_scope"]
    # idle gaps go to the build thread's stages, the program's among them
    marks = [name for name, _ in line["breakdown"]["idle_gaps"]]
    assert marks and all(m.startswith(trace.HOST_MARK) or m == "outside_marks" for m in marks)


@pytest.mark.parametrize(
    "op_name,scope,way",
    [
        ("jit(one_machine)/jit(main)/vmap()/while/body/lstm_cell/dot_general", "lstm_cell", "plain"),
        ("jit(one_machine)/jit(main)/while/body/jvp(vmap(attention))/softmax/reduce_max", "softmax", "fwd"),
        ("jit(f)/jit(main)/transpose(jvp(vmap(expert_ffn)))/dot_general:", "expert_ffn", "bwd"),
        ("jit(f)/jit(main)/router/while/body/closed_call/checkpoint/mul", "router", "plain"),
        ("jit(f)/jit(main)/dense/vmap(jit(_where))/select_n", "dense", "plain"),
        ("jit(f)/jit(main)/cond/branch_1_fun/jvp()/add", "", "fwd"),
        ("jit(one_machine)/jit(main)/vmap()/while/body/transpose(jvp())/mul", "", "bwd"),
        ("", "", "plain"),
    ],
)
def test_innermost_scope_of_an_op_name(op_name, scope, way):
    assert trace.classify(op_name) == (scope, way)


def _plane(name, lines):
    def event(n, a, b):
        return SimpleNamespace(name=n, start_ns=a * 1e9, duration_ns=(b - a) * 1e9)

    return SimpleNamespace(
        name=name,
        lines=[SimpleNamespace(name=ln, events=[event(*e) for e in evs]) for ln, evs in lines],
    )


def test_detail_by_operation_and_scope():
    # after the calibration's mark: a while (10 s) round two runs of one
    # fusion and one of a custom call; before it, what is not the build's
    planes = [
        _plane("/device:TPU:0", [("XLA Ops", [
            ("%dot.1 = d", 0.1, 0.4),
            ("%while.9 = w", 1.0, 11.0),
            ("%fusion.7 = f32[8] fusion(...)", 2.0, 3.0), ("%fusion.7 = f32[8] fusion(...)", 4.0, 5.5),
            ("%custom-call.3 = c", 6.0, 8.0),
        ])]),
        _plane("/host:CPU", [("python3", [("chipbench.calibrate", 0.0, 0.5)])]),
    ]
    names = {
        "%fusion.7 = f32[8] fusion(...)": "jit(f)/jit(main)/while/body/jvp(expert_ffn)/dot_general",
        "%custom-call.3 = c": "jit(f)/jit(main)/while/body/attention/pallas_call",
    }
    detail = trace.read_detail(planes, names)
    assert detail["op_s"] == {
        "while.9": [pytest.approx(5.5), 1], "fusion.7": [pytest.approx(2.5), 2],
        "custom-call.3": [pytest.approx(2.0), 1],
    }
    assert detail["op_scope"] == {"while.9": "", "fusion.7": "expert_ffn", "custom-call.3": "attention"}
    assert detail["scope_s"] == {"": pytest.approx(5.5), "expert_ffn": pytest.approx(2.5),
                                 "attention": pytest.approx(2.0)}
    assert detail["detail_s"] == pytest.approx(10.0)
    assert detail["device_ops"] == trace.op_ranking(planes)
    reduced = trace.Reduced(window_s=20.0, busy_s=10.0, n_devices=1, n_ops=4, **detail)
    assert reduced.device_scopes() == [["no_scope", pytest.approx(5.5)], ["expert_ffn", pytest.approx(2.5)],
                                       ["attention", pytest.approx(2.0)]]
    assert _reader("reader_scope_share").read({"trace": reduced}) == pytest.approx(20.0)
    # a kernel's reader: its custom call's seconds and runs
    seconds, runs = reduced.op_s["custom-call.3"]
    assert (seconds, runs) == (pytest.approx(2.0), 1)


def test_scopes_of_a_cut_recorded_on_the_chip():
    """``fixtures/transformer_scopes.xplane.pb``: the detail session of a
    traced run of the stand-in at half its depth (5 blocks, d_model 2048;
    PR 34), compiled into an empty cache so that the scopes show, trimmed to
    the calibration's last 60 operations, a 0.1 s stretch of the chunk
    program 1.0 s after the calibration's mark (two minibatch steps, 4,339
    operations, each name cut to its instruction) and the host events the
    reducer reads."""
    names = trace.op_names(FIXTURE)
    planes = trace.read_planes(FIXTURE)
    detail = trace.read_detail(planes, names)
    assert len(names) == 2433 and len(detail["op_s"]) == 2424
    # the device never idles inside the stretch
    assert detail["detail_s"] == pytest.approx(0.09992309, abs=1e-8)
    assert detail["scope_s"] == {
        "": pytest.approx(0.050226556, abs=1e-8),
        "attention": pytest.approx(0.048012416, abs=1e-8),
        "optimizer_update": pytest.approx(0.001545982, abs=1e-8),
        "dense": pytest.approx(0.000094284, abs=1e-8),
        "window_gather": pytest.approx(0.000043852, abs=1e-8),
    }
    assert sum(s for s, _ in detail["op_s"].values()) == pytest.approx(detail["detail_s"])
    assert {detail["op_scope"][op] for op in detail["op_s"]} == set(detail["scope_s"])
    # a kernel by its compiled instance: the flash kernel's backward of one
    # block ran once a minibatch step, under the scope the program gave it
    assert detail["op_s"]["transpose_jvp_attention__.84"] == [pytest.approx(0.002162813, abs=1e-8), 2]
    assert detail["op_scope"]["transpose_jvp_attention__.84"] == "attention"
    assert names["%transpose_jvp_attention__.84 = _"].endswith("transpose(jvp(attention))/pallas_call:")
    reduced = trace.Reduced(window_s=1.0, busy_s=0.1, n_devices=1, n_ops=4339, **detail)
    assert [name for name, _ in reduced.device_scopes()] == [
        "no_scope", "attention", "optimizer_update", "dense", "window_gather"
    ]
    assert _reader("reader_scope_share").read({"trace": reduced}) == pytest.approx(48.0494, abs=1e-3)
    # what ran before the calibration's mark ended is not the build's
    with_calibration = trace.read_detail(planes, names, after="no_such_mark")
    assert with_calibration["detail_s"] > detail["detail_s"] + 0.1
    assert detail["device_ops"] == trace.op_ranking(planes)
    # the session's cross-check still reads off the trimmed file
    assert trace.cross_check(planes)["executions"] == 5
