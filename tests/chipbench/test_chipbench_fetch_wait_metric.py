"""The metric that reads the build thread's ``fetch_wait`` stage: the
reader's arithmetic on hand-made counters, nothing to read on a program
without the stage, and one CPU rehearsal that prints it."""

import json
import os

import pytest

from chipbench import run
from chipbench.metrics import fetch_wait_ms_per_machine as fetch_wait

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MANIFEST = os.path.join(ROOT, "chipbench", "rehearsal", "manifest.json")
NAME = "fetch_wait_ms_per_machine"

BEFORE = {"phase_s.fetch_wait": 0.5, "phase_s.fetch": 10.0}
AFTER = {"phase_s.fetch_wait": 0.875, "phase_s.fetch": 40.0, "phase_s.fetch_stage": 2.0}


def _ctx(machines=180, before=BEFORE, after=AFTER):
    return {"machines": machines, "before": before, "after": after}


@pytest.mark.parametrize("ctx, expected", [
    # Δ fetch_wait ÷ machines
    (_ctx(), 1e3 * 0.375 / 180),
    # the warm-up build is one chunk and never waits: the label is first
    # seen inside the window and counts from zero
    (_ctx(before={"phase_s.fetch": 10.0}), 1e3 * 0.875 / 180),
    # every chunk found its data there
    (_ctx(after=dict(AFTER, **{"phase_s.fetch_wait": 0.5})), 0.0),
    # a program without the stage (the parent commit): nothing to read
    (_ctx(after={"phase_s.fetch": 40.0, "phase_s.fetch_stage": 2.0}), None),
    # no machine persisted
    (_ctx(machines=0), None),
])
def test_reader(ctx, expected):
    got = fetch_wait.read(ctx)
    if expected is None:
        assert got is None
    else:
        assert got == pytest.approx(expected)


def test_benchmark_declares_it_beside_the_heads_metric():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    entry, head = by_name[NAME], by_name["build_head_ms_per_machine"]
    assert manifest["per_layer"][-1] is entry  # appended, nothing moved
    assert {k: entry[k] for k in ("layer", "moves", "workloads", "source")} == {
        k: head[k] for k in ("layer", "moves", "workloads", "source")
    }
    assert (entry["unit"], entry["better"]) == ("ms", "lower")


def test_rehearsal_prints_it(tmp_path, capsys, monkeypatch):
    """A traced run of builds of two chunks reads the stage from the
    program's own span: once a chunk after the first, and small beside the
    build's wall."""
    import jax

    from gordo_tpu.observability import telemetry
    from gordo_tpu.parallel import batch_trainer, default_mesh

    # one device, as the cell has: over the tests' eight virtual devices a
    # build of four machines would be one chunk, which waits for nothing
    one_device = default_mesh(devices=jax.devices()[:1])
    monkeypatch.setattr(batch_trainer, "default_mesh", lambda: one_device)
    with open(MANIFEST) as fh:
        manifest = json.load(fh)
    manifest["per_layer"] = [
        m for m in manifest["per_layer"] if m["name"] == "fetch_ms_per_machine"
    ] + [
        {"name": NAME, "unit": "ms", "better": "lower", "source": "program_span",
         "layer": "fleet plan and data fetch", "moves": "machines_per_min",
         "workloads": ["lstm_tiny.rehearsal"]}
    ]
    path = tmp_path / "manifest.json"
    path.write_text(json.dumps(manifest))
    try:
        code = run.main([
            "--workload", "lstm_tiny.rehearsal", "--seed", "3100000001",
            "--seconds", "0.5", "--trace", "1", "--rehearsal",
            "--manifest", str(path),
        ])
    finally:
        telemetry.reset()
    assert code == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    metrics = line["metrics"]
    assert set(metrics) == {"fetch_ms_per_machine", NAME}
    assert metrics[NAME]["unit"] == "ms"
    wall_ms = 1e3 * line["device"]["window_s"] / line["attempted"]
    assert 0 <= metrics[NAME]["value"] < wall_ms
