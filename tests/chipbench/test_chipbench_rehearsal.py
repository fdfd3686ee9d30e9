"""A CPU rehearsal of ``chipbench.run`` at a tiny size: the control flow of a
run, both with and without the profiler, and the shape of its last line. No
number here is a measurement."""

import json
import os

import pytest

from chipbench import flops, run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MANIFEST = os.path.join(ROOT, "chipbench", "rehearsal", "manifest.json")
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def _run(capsys, workload, trace, seed):
    code = run.main([
        "--workload", workload, "--seed", str(seed), "--seconds", "0.5",
        "--trace", str(trace), "--rehearsal", "--manifest", MANIFEST,
    ])
    out = capsys.readouterr()
    assert code == 0
    return json.loads(out.out.strip().splitlines()[-1]), out.err


@pytest.fixture
def host_peak(monkeypatch):
    """A host has no published peak; the rehearsal borrows a made-up one so
    that the reader's arithmetic runs."""
    real = flops.peaks
    monkeypatch.setattr(
        flops, "peaks",
        lambda kind: {"bf16_flops_per_s": 1e12} if kind == "cpu" else real(kind),
    )


@pytest.mark.parametrize("workload", ["lstm_tiny.rehearsal", "transformer_tiny.rehearsal"])
@pytest.mark.parametrize("trace", [0, 1])
def test_last_line(capsys, host_peak, workload, trace):
    with open(MANIFEST) as fh:
        manifest = json.load(fh)
    line, err = _run(capsys, workload, trace, seed=2_500_000_000 + trace)
    assert list(line)[-1] == "compared"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    wanted = manifest["per_layer"] if trace else manifest["end_to_end"]
    assert set(line["metrics"]) == {m["name"] for m in wanted}
    for metric in wanted:
        got = line["metrics"][metric["name"]]
        assert got["unit"] == metric["unit"] and isinstance(got["value"], float)
    assert DEVICE_KEYS <= set(line["device"])
    if trace:
        device = line["device"]
        assert 0 < device["busy_s"] <= device["window_s"]
        assert 0 < line["metrics"]["fleet_step_mfu"]["value"]
        assert line["metrics"]["window_compiles"]["value"] == 0
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps", "device_scopes"}
        assert all(len(v) <= 10 for v in line["breakdown"].values())
    else:
        assert all(v["value"] > 0 for v in line["metrics"].values())
    # every number compared is printed beside its limit, last on stderr
    assert err.strip().splitlines()[-1].startswith("compared {")


def test_refuses_without_a_chip(capsys):
    with pytest.raises(SystemExit) as stop:
        run.main(["--workload", "lstm_tiny.rehearsal", "--seed", "1", "--seconds", "1",
                  "--manifest", MANIFEST])
    assert stop.value.code != 0
    assert capsys.readouterr().out == ""


def test_same_seed_same_inputs():
    from chipbench import traffic

    tr = traffic.Traffic.load("rehearsal")
    a = traffic.machine_frame(2**31 + 5, "m-0", 4, tr)
    assert (a == traffic.machine_frame(2**31 + 5, "m-0", 4, tr)).all()
    assert not (a == traffic.machine_frame(5, "m-0", 4, tr)).all()
    assert a.shape == (tr.rows, 4)
