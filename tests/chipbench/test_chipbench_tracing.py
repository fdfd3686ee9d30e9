"""The readers of the set-up's compile stages, of the pull's rate and of the
chunks that starved for their fetch: their entries in ``BENCHMARK.json``,
each reader on hand-made counters (nothing to read on a program without the
series), and one CPU rehearsal that prints all five."""

import importlib
import json
import os
from types import SimpleNamespace

import pytest

from chipbench import run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
REHEARSAL = os.path.join(ROOT, "chipbench", "rehearsal", "manifest.json")
LSTM = "lstm_ae_144.fleet_build"
SETUP = ("setup_trace_s", "setup_lower_s", "setup_backend_s")
NEW = SETUP + ("pull_gbps", "fetch_starved_chunks")

STAGE = "gordo_build_xla_compile_seconds_total{{stage={}}}"
D2H = "gordo_build_transfer_bytes_total{direction=d2h}"
STARVED = "gordo_build_fetch_starved_chunks_total"


def _load(path):
    with open(path) as fh:
        return json.load(fh)


def _read(name, ctx):
    return importlib.import_module(f"chipbench.metrics.{name}").read(ctx)


def test_manifest_is_the_benchmark_and_the_five_readers_after_it():
    accepted = _load(MANIFEST)
    names = [m["name"] for m in accepted["per_layer"]]
    nine = names.index("fetch_wait_ms_per_machine") + 1
    # appended in their order after the nine entries they came after
    assert [n for n in names if n in NEW] == list(NEW)
    assert min(names.index(n) for n in NEW) >= nine
    added = {m["name"]: m for m in accepted["per_layer"] if m["name"] in NEW}
    cells = [w["name"] for w in accepted["workloads"]]
    # the layers they read are layers the nine entries already named
    layers = {m["layer"] for m in accepted["per_layer"][:nine]}
    for name, metric in added.items():
        assert metric["layer"] in layers, name
        assert metric["workloads"] == ([LSTM] if name == "fetch_starved_chunks" else cells)
        assert metric["moves"] == {
            "pull_gbps": "machine_ready_p95_s", "fetch_starved_chunks": "machines_per_min",
        }.get(name, "setup_s")
        importlib.import_module(f"chipbench.metrics.{name}")
    for cell in cells:
        listed = [m["name"] for m in run.load_cell(MANIFEST, cell)["per_layer"]]
        expected = set(NEW) - ({"fetch_starved_chunks"} if cell != LSTM else set())
        assert expected <= set(listed)


def _ctx(before, after, machines_per_build=60, chunk_machines=12):
    traffic = SimpleNamespace(
        machines_per_build=machines_per_build, chunk_machines=chunk_machines
    )
    return {"cell": {"traffic": traffic}, "machines": 180, "before": before, "after": after}


# the set-up's snapshot (the first window build's ``before``) and the last
# build's ``after``, as the program of this tree leaves them
BEFORE = {
    STAGE.format("trace"): 4.25, STAGE.format("lower"): 2.875,
    STAGE.format("compile"): 0.5, STAGE.format("load"): 1.25,
    D2H: 1e9, "phase_s.d2h": 0.25, STARVED: 0.0,
}
AFTER = dict(BEFORE, **{D2H: 4.6e9, "phase_s.d2h": 1.05, STARVED: 2.0})
# the parent commit: one unlabelled compile-seconds series, no bytes, no count
PARENT = {"gordo_build_xla_compile_seconds_total": 9.0, "phase_s.d2h": 0.25}


@pytest.mark.parametrize("name, ctx, expected", [
    # the set-up's own seconds: the snapshot itself, not a difference
    ("setup_trace_s", _ctx(BEFORE, AFTER), 4.25),
    ("setup_lower_s", _ctx(BEFORE, AFTER), 2.875),
    ("setup_backend_s", _ctx(BEFORE, AFTER), 1.75),
    # a cold set-up that compiled everything has no load yet, a warm one no compile
    ("setup_backend_s", _ctx({STAGE.format("compile"): 3.0}, AFTER), 3.0),
    ("setup_backend_s", _ctx({STAGE.format("load"): 0.75}, AFTER), 0.75),
    # Δ bytes ÷ Δ seconds of d2h: 3.6 GB in 0.8 s
    ("pull_gbps", _ctx(BEFORE, AFTER), 4.5),
    ("pull_gbps", _ctx({}, AFTER), 4.6 / 1.05),
    ("pull_gbps", _ctx(BEFORE, dict(AFTER, **{"phase_s.d2h": 0.25})), None),
    ("fetch_starved_chunks", _ctx(BEFORE, AFTER), 2.0),
    ("fetch_starved_chunks", _ctx(BEFORE, dict(AFTER, **{STARVED: 0.0})), 0.0),
    # a build of one chunk has no later chunk to starve
    ("fetch_starved_chunks", _ctx(BEFORE, AFTER, 1, 1), None),
] + [
    # the parent commit: nothing to read
    (name, _ctx(PARENT, dict(PARENT, **{"phase_s.d2h": 1.0})), None) for name in NEW
])
def test_reader(name, ctx, expected):
    got = _read(name, ctx)
    if expected is None:
        assert got is None
    else:
        assert got == pytest.approx(expected)


def test_rehearsal_prints_all_five(tmp_path, capsys, monkeypatch):
    """A traced run of builds of two chunks through the five entries of
    ``BENCHMARK.json``, on the rehearsal's tiny cell: every reader finds its
    series."""
    import jax

    from gordo_tpu.observability import telemetry
    from gordo_tpu.parallel import batch_trainer, default_mesh

    # one device, as the cells have: over the tests' eight virtual devices a
    # build of four machines would be one chunk
    one_device = default_mesh(devices=jax.devices()[:1])
    monkeypatch.setattr(batch_trainer, "default_mesh", lambda: one_device)
    manifest = _load(REHEARSAL)
    manifest["per_layer"] = [
        dict(m, workloads=["lstm_tiny.rehearsal"])
        for m in _load(MANIFEST)["per_layer"] if m["name"] in NEW
    ]
    path = tmp_path / "manifest.json"
    path.write_text(json.dumps(manifest))
    # the set-up traces, lowers and compiles or loads from here on
    jax.clear_caches()
    telemetry.reset()
    try:
        code = run.main([
            "--workload", "lstm_tiny.rehearsal", "--seed", "3900000001",
            "--seconds", "0.5", "--trace", "1", "--rehearsal",
            "--manifest", str(path),
        ])
    finally:
        telemetry.reset()
    assert code == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    metrics = line["metrics"]
    assert set(metrics) == set(NEW)
    assert all(metrics[name]["value"] > 0 for name in SETUP + ("pull_gbps",))
    assert metrics["fetch_starved_chunks"]["value"] in range(line["attempted"])
    assert [metrics[name]["unit"] for name in NEW] == ["s", "s", "s", "GB/s", "count"]
