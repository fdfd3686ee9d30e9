"""``correct`` has to be able to fail. At a size a test run can hold:

- the control: the reference computed in float8, the nearest precision below
  the configurations' bfloat16, put in the program's place, is not correct
  by the cells' own limits;
- a run driven past the look for a chip with the timed path broken
  underneath comes out with ``correct`` false, once for each fault these
  cells can have: a training step that returns its state unchanged; half of
  every batch left out, the mean taken over the rest; an answer altered
  where it is produced (the weights, as the serializer writes them). There is
  no exchange between chips to leave out: every cell runs on one.
"""

import json
import os

import numpy as np
import pytest

from chipbench import check, reference, run, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MANIFEST = os.path.join(ROOT, "chipbench", "rehearsal", "manifest.json")
CELLS = os.path.join(ROOT, "chipbench", "workloads")


def _tiny(name):
    with open(os.path.join(ROOT, "chipbench", "rehearsal", name + ".json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize(
    "tiny,cell",
    [("lstm_ae_tiny", "lstm_ae_144.fleet_build"), ("transformer_tiny", "transformer_tiny.rehearsal")],
)
def test_float8_control_is_not_correct(tiny, cell):
    config, tr = _tiny(tiny), traffic.Traffic.load("rehearsal")
    with open(os.path.join(CELLS, cell + ".json")) as fh:
        limits = json.load(fh)["limits"]
    seed, names = 2_200_000_001, ["m-0", "m-1"]
    frames = [traffic.machine_frame(seed, n, int(config["n_tags"]), tr) for n in names]
    start, end = reference.probe_rows(tr.rows, int(config["cv_splits"]))
    refs = reference.build_machines(config, names, frames, seed)

    def compared(**how):
        others = reference.build_machines(config, names, frames, seed, **how)
        return check.verdict(
            check.typical([
                check.gaps(check.as_observed(o, f[start:end]), r, f[start:end])
                for o, f, r in zip(others, frames, refs)
            ]),
            limits,
        )

    assert check.is_correct(compared())  # the reference agrees with itself
    control = compared(precision="float8")
    assert not check.is_correct(control)
    # the stated precision reads well under the control in every number
    stated = compared(precision="bfloat16")
    assert all(stated[k]["value"] < control[k]["value"] for k in stated)


def test_a_few_wrong_machines_are_not_correct():
    """A sound median does not hide a wrong machine: of five sampled, one
    whose update was lost, one small leaf left unmoved, or one artifact in
    its neighbour's slot fails the worst machine's limits."""
    config, tr = _tiny("lstm_ae_tiny"), traffic.Traffic.load("rehearsal")
    with open(os.path.join(CELLS, "lstm_ae_144.fleet_build.json")) as fh:
        limits = json.load(fh)["limits"]
    seed, names = 2_200_000_003, [f"m-{i}" for i in range(5)]
    frames = [traffic.machine_frame(seed, n, int(config["n_tags"]), tr) for n in names]
    start, end = reference.probe_rows(tr.rows, int(config["cv_splits"]))
    refs = reference.build_machines(config, names, frames, seed)

    def compared(others):
        return check.verdict(
            check.typical([
                check.gaps(check.as_observed(o, f[start:end]), r, f[start:end])
                for o, f, r in zip(others, frames, refs)
            ]),
            limits,
        )

    assert check.is_correct(compared(refs))
    lost = compared([dict(refs[0], params=refs[0]["init"])] + refs[1:])
    assert lost["weights"]["value"] == 0 and lost["weights_worst"]["value"] == pytest.approx(1.0)
    assert not check.is_correct(lost)
    # one leaf alone left where it started: the smallest that moves as far
    # as the median leaf
    where = [(i, k) for i, layer in enumerate(refs[0]["params"]) for k in sorted(layer)]
    moved = [np.linalg.norm(refs[0]["params"][i][k] - refs[0]["init"][i][k]) for i, k in where]
    layer, key = min((m, w) for m, w in zip(moved, where) if m >= np.median(moved))[1]
    params = [dict(layer) for layer in refs[0]["params"]]
    params[layer][key] = refs[0]["init"][layer][key]
    unmoved = compared([dict(refs[0], params=params)] + refs[1:])
    assert unmoved["leaf_worst"]["value"] == pytest.approx(1.0)
    assert unmoved["weights_worst"]["value"] < unmoved["weights_worst"]["limit"]
    assert not check.is_correct(unmoved)
    swapped = compared([refs[1], refs[0]] + refs[2:])
    assert swapped["weights"]["value"] == 0 and not check.is_correct(swapped)


def _run(capsys, workload="lstm_tiny.rehearsal"):
    code = run.main([
        "--workload", workload, "--seed", "2200000002", "--seconds", "0.2",
        "--trace", "0", "--rehearsal", "--manifest", MANIFEST,
    ])
    assert code == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.fixture
def fresh_programs():
    """The fleet trainer keeps its traced programs; a fault planted under
    them needs them traced anew, and so does the test that follows."""
    from gordo_tpu.parallel import batch_trainer

    batch_trainer._bucket_program.cache_clear()
    yield
    batch_trainer._bucket_program.cache_clear()


def test_state_unchanged_is_not_correct(capsys, monkeypatch, fresh_programs):
    import optax

    monkeypatch.setattr(optax, "apply_updates", lambda params, updates: params)
    line = _run(capsys)
    assert line["correct"] is False
    assert line["compared"]["weights"]["value"] == pytest.approx(1.0, abs=1e-3)


def test_half_batch_is_not_correct(capsys, monkeypatch, fresh_programs):
    from gordo_tpu.ops import train

    whole = train._loss_terms

    def half(spec, params, xb, yb, wb):
        keep = (np.arange(wb.shape[0]) < wb.shape[0] // 2).astype(np.float32)
        return whole(spec, params, xb, yb, wb * keep)

    monkeypatch.setattr(train, "_loss_terms", half)
    line = _run(capsys)
    assert line["correct"] is False
    assert line["compared"]["weights"]["value"] > line["compared"]["weights"]["limit"]


def test_altered_answer_is_not_correct(capsys, monkeypatch):
    from gordo_tpu import serializer

    honest = serializer.dump

    def altered(model, dest_dir, metadata=None):
        out = model.base_estimator.steps[-1][1].params_[-1]
        out["kernel"] = np.asarray(out["kernel"]) * 1.01
        return honest(model, dest_dir, metadata=metadata)

    monkeypatch.setattr(serializer, "dump", altered)
    line = _run(capsys)
    assert line["correct"] is False
    assert line["compared"]["output"]["value"] > line["compared"]["output"]["limit"]


def test_sound_run_is_correct(capsys, fresh_programs):
    assert _run(capsys, "transformer_tiny.rehearsal")["correct"] is True
