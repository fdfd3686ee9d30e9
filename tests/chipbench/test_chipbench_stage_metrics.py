"""The two metrics that read the build thread's stage spans: the readers'
arithmetic on hand-made counters, and one CPU rehearsal that prints both."""

import json
import os

import pytest

from chipbench import run
from chipbench.metrics import build_head_ms_per_machine as head
from chipbench.metrics import build_tail_ms_per_machine as tail

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MANIFEST = os.path.join(ROOT, "chipbench", "rehearsal", "manifest.json")
NEW = [
    ("build_head_ms_per_machine", "fleet plan and data fetch", "machines_per_min"),
    ("build_tail_ms_per_machine", "assembly and checkpoint", "machine_ready_p95_s"),
]

BEFORE = {f"phase_s.{s}": 1.0 for s in head.STAGES + ("tail",)}
AFTER = {
    "phase_s.plan": 1.25, "phase_s.fetch_stage": 3.5, "phase_s.validate_stage": 1.125,
    "phase_s.bucket_prep": 1.0625, "phase_s.compile": 1.0625, "phase_s.tail": 1.75,
    "phase_s.fetch": 40.0,
}


def _ctx(machines=120, before=BEFORE, after=AFTER):
    return {"machines": machines, "before": before, "after": after}


def _without(counters, key):
    return {k: v for k, v in counters.items() if k != key}


@pytest.mark.parametrize("reader, ctx, expected", [
    # Δ(plan + fetch_stage + validate_stage + bucket_prep + compile) ÷ machines
    (head, _ctx(), 1e3 * (0.25 + 2.5 + 0.125 + 0.0625 + 0.0625) / 120),
    (tail, _ctx(), 1e3 * 0.75 / 120),
    # a label first seen inside the window counts from zero
    (tail, _ctx(before={}), 1e3 * 1.75 / 120),
    # a program without the stage spans (the parent commit): nothing to read
    (head, _ctx(after=_without(AFTER, "phase_s.bucket_prep")), None),
    (head, _ctx(after={"phase_s.fetch": 40.0}), None),
    (tail, _ctx(after=_without(AFTER, "phase_s.tail")), None),
    # no machine persisted
    (head, _ctx(machines=0), None),
    (tail, _ctx(machines=0), None),
])
def test_reader(reader, ctx, expected):
    got = reader.read(ctx)
    if expected is None:
        assert got is None
    else:
        assert got == pytest.approx(expected)


def test_rehearsal_prints_both(tmp_path, capsys):
    """A traced run on a manifest that lists the two metrics reads both from
    the program's own spans, and they tile what the per-machine spans see
    from outside: head + tail stay under the build's wall."""
    from gordo_tpu.observability import telemetry

    with open(MANIFEST) as fh:
        manifest = json.load(fh)
    manifest["per_layer"] = [
        m for m in manifest["per_layer"] if m["name"] == "fetch_ms_per_machine"
    ] + [
        {"name": name, "unit": "ms", "better": "lower", "source": "program_span",
         "layer": layer, "moves": moves, "workloads": ["lstm_tiny.rehearsal"]}
        for name, layer, moves in NEW
    ]
    path = tmp_path / "manifest.json"
    path.write_text(json.dumps(manifest))
    try:
        code = run.main([
            "--workload", "lstm_tiny.rehearsal", "--seed", "2600000001",
            "--seconds", "0.5", "--trace", "1", "--rehearsal",
            "--manifest", str(path),
        ])
    finally:
        telemetry.reset()
    assert code == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    metrics = line["metrics"]
    assert set(metrics) == {"fetch_ms_per_machine"} | {name for name, _, _ in NEW}
    values = {name: metrics[name]["value"] for name, _, _ in NEW}
    assert all(v > 0 for v in values.values())
    assert all(metrics[name]["unit"] == "ms" for name in values)
    wall_ms = 1e3 * line["device"]["window_s"] / line["attempted"]
    assert sum(values.values()) < wall_ms
