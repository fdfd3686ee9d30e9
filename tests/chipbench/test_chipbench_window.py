"""The window's housekeeping: where the check compares the window's last
machine alone, every earlier build's artifacts are gone once the window
closes, and the last build's are there to be compared; otherwise every
build's artifacts stay."""
import os
import types

import pytest

from chipbench import run


class _Fleet:
    """Builds that take 0.1 s on a clock of their own and persist one small
    artifact each."""

    def __init__(self, root: str, check_machines: int):
        self.root = root
        self.traffic = types.SimpleNamespace(check_machines=check_machines, machines_per_build=1)
        self.n = 0
        self.now = 1000.0

    def time(self) -> float:
        return self.now

    def build(self, n_machines: int) -> dict:
        out_dir = os.path.join(self.root, f"build-{self.n:04d}")
        self.n += 1
        os.makedirs(os.path.join(out_dir, "machine"))
        with open(os.path.join(out_dir, "machine", "model.pkl"), "wb") as fh:
            fh.write(b"\0" * 4096)
        self.now += 0.1
        return {"out_dir": out_dir}


@pytest.mark.parametrize("check_machines, kept", [(1, "last"), (3, "all")])
def test_window_keeps_what_the_check_reads(tmp_path, monkeypatch, check_machines, kept):
    fleet = _Fleet(str(tmp_path), check_machines)
    monkeypatch.setattr(run, "time", types.SimpleNamespace(time=fleet.time))
    builds, unclocked = run.run_window(fleet, 0.35, None)
    assert len(builds) == 4 and unclocked == 0.0
    present = [os.path.isdir(b["out_dir"]) for b in builds]
    if kept == "last":
        assert present == [False] * (len(builds) - 1) + [True]
    else:
        assert all(present)


def test_pool_gives_every_seed_the_same_machines():
    from chipbench import traffic

    tr = traffic.Traffic.load("rehearsal_pool")
    n = tr.distinct_machines
    runs = [
        [traffic.machine_name("cfg", seed, i, tr) for i in range(2 * n)]
        for seed in (2**31 + 11, 4_100_000_001)
    ]
    # the same set in each seed's first n builds, and again in the next n
    assert set(runs[0][:n]) == set(runs[1][:n]) == set(runs[0][n:]) and len(set(runs[0])) == n
    assert runs[0][:n] != runs[1][:n]
    # the same rows and seed for a machine, whatever the run's seed
    assert traffic.content_seed(tr, 1) == traffic.content_seed(tr, 2) == tr.pool_seed
    name = runs[0][0]
    frames = [traffic.machine_frame(traffic.content_seed(tr, s), name, 4, tr) for s in (1, 2)]
    assert (frames[0] == frames[1]).all()
    # a mix without a pool keeps a machine of its own for every seed and index
    plain = traffic.Traffic.load("rehearsal")
    assert traffic.machine_name("cfg", 5, 0, plain) != traffic.machine_name("cfg", 6, 0, plain)
    assert traffic.content_seed(plain, 5) == 5


def test_pooled_run_compares_a_pool_machine(tmp_path, capsys, monkeypatch):
    """The whole run on a pooled mix: builds of pool machines, the last one
    compared against its reference, made from the pool's seed."""
    import json

    manifest = json.load(open(os.path.join(run.ROOT, "chipbench", "rehearsal", "manifest.json")))
    for cell in manifest["workloads"]:
        if cell["name"] == "lstm_tiny.rehearsal":
            cell["traffic"] = "rehearsal_pool"
    path = tmp_path / "manifest.json"
    path.write_text(json.dumps(manifest))
    built = []
    real = run.Fleet.build

    def build(self, n_machines):
        out = real(self, n_machines)
        built.extend(out["names"])
        return out

    monkeypatch.setattr(run.Fleet, "build", build)
    code = run.main([
        "--workload", "lstm_tiny.rehearsal", "--seed", str(2**31 + 3), "--seconds", "0.5",
        "--trace", "0", "--rehearsal", "--manifest", str(path),
    ])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert code == 0 and line["correct"] is True and line["failed"] == 0
    assert len(built) >= 3 and all("-pool7-" in name for name in built)
