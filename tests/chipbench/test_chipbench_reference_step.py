"""The reference's training step at a size a test run can hold: it updates
its state in place (the weights and Adam's two moments are given away to
it) and a stage's state is freed before the next stage's weights are made,
which is what lets a machine of half a billion parameters fit beside nothing
but itself; what the comparison reads survives both."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MACHINES, ROWS, BATCH = 2, 80, 16


def _tiny(name):
    with open(os.path.join(ROOT, "chipbench", "rehearsal", name + ".json")) as fh:
        return json.load(fh)


def _programs(config, precision="float32"):
    return reference._step_fn(
        config["reference"],
        json.dumps(config["model"], sort_keys=True),
        json.dumps(config["optimizer"], sort_keys=True),
        precision,
    )


def _inputs(config, half_batch):
    """Two machines' stacked state and three batches of window starts, the
    last one short (its tail has weight 0, as ``build_machines`` pads it)."""
    model, tags = config["model"], int(config["n_tags"])
    init = reference.model_reference(config).init_params
    keys = jax.random.split(jax.random.PRNGKey(7), MACHINES)
    params = jax.tree_util.tree_map(
        lambda *a: jnp.stack(a), *[init(k, model, tags) for k in keys]
    )
    rng = np.random.default_rng(11)
    X = jnp.asarray(rng.uniform(0, 1, (MACHINES, ROWS, tags)).astype(np.float32))
    n_valid = ROWS - int(model["lookback_window"]) + 1
    batches = []
    for live in (BATCH, BATCH, 5):
        idx = np.zeros((MACHINES, BATCH), np.int32)
        w = np.zeros((MACHINES, BATCH), np.float32)
        idx[:, :live] = rng.integers(0, n_valid, (MACHINES, live))
        w[:, :live] = 1.0
        if half_batch:
            w[:, live // 2 : live] = 0.0
        batches.append((idx, w))
    return params, X, batches


@pytest.mark.parametrize("tiny", ["transformer_tiny", "lstm_ae_tiny"])
def test_step_updates_its_state_in_place(tiny):
    config = _tiny(tiny)
    step, _ = _programs(config)
    params, X, batches = _inputs(config, False)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    idx, w = batches[0]
    state_bytes = 3 * sum(a.nbytes for a in jax.tree_util.tree_leaves(params))
    compiled = step.lower(params, m, v, 1.0, X, X, idx, w).compile()
    analysis = compiled.memory_analysis()
    if analysis is not None:
        # weights, m and v are not counted twice: every output byte of them
        # is an argument's
        assert analysis.alias_size_in_bytes >= state_bytes
        assert (
            analysis.argument_size_in_bytes + analysis.output_size_in_bytes
            - analysis.alias_size_in_bytes
        ) < state_bytes * 17 / 12
    given = jax.tree_util.tree_leaves((params, m, v))
    out = step(params, m, v, 1.0, X, X, idx, w)
    jax.block_until_ready(out)
    assert all(a.is_deleted() for a in given)
    assert not X.is_deleted()


def test_build_keeps_what_the_comparison_reads():
    """What ``build_machines`` hands the comparison survives the donation:
    float32 trees on the host, the same from build to build."""
    from chipbench import check, traffic

    config, tr = _tiny("transformer_tiny"), traffic.Traffic.load("rehearsal")
    seed, names = 3_400_000_001, ["m-0", "m-1"]
    frames = [traffic.machine_frame(seed, n, int(config["n_tags"]), tr) for n in names]
    start, end = reference.probe_rows(tr.rows, int(config["cv_splits"]))
    whole = reference.build_machines(config, names, frames, seed)
    again = reference.build_machines(config, names, frames, seed)
    for rec, same, frame in zip(whole, again, frames):
        for key in ("init", "first_grad", "params"):
            for layer, layer_b in zip(rec[key], same[key]):
                for name in layer:
                    assert isinstance(layer[name], np.ndarray) and layer[name].dtype == np.float32
                    assert np.array_equal(layer[name], layer_b[name])
        # the initial weights are not the trained ones: the step did not
        # overwrite what was kept of them
        assert check.gaps(
            check.as_observed(dict(rec, params=rec["init"]), frame[start:end]), rec, frame[start:end]
        )["weights"] == pytest.approx(1.0)
        numbers = check.gaps(check.as_observed(same, frame[start:end]), rec, frame[start:end])
        assert set(numbers.values()) == {0.0}


def test_a_stage_starts_beside_nothing(monkeypatch):
    """When a stage's weights are made, the stage before has given its
    weights and moments back: the device never holds two stages' state."""
    import types

    from chipbench import traffic

    config, tr = _tiny("transformer_tiny"), traffic.Traffic.load("rehearsal")
    seed, names = 3_400_000_002, ["m-0", "m-1"]
    frames = [traffic.machine_frame(seed, n, int(config["n_tags"]), tr) for n in names]
    plain = reference.model_reference(config)
    live = []

    def init_params(key, model, n_tags):
        live.append(sum(a.nbytes for a in jax.live_arrays()))
        return plain.init_params(key, model, n_tags)

    monkeypatch.setattr(
        reference, "model_reference",
        lambda _: types.SimpleNamespace(init_params=init_params, forward=plain.forward),
    )
    reference._step_fn.cache_clear()
    try:
        refs = reference.build_machines(config, names, frames, seed)
    finally:
        reference._step_fn.cache_clear()
    one_machine = sum(a.nbytes for layer in refs[0]["params"] for a in layer.values())
    stages = int(config["cv_splits"]) + 1
    assert len(live) == stages * len(names)
    # at each stage's first machine little is live (the sensor rows, the
    # losses), where the stage before held two machines' weights and moments:
    # six times one machine's weights
    assert max(live[:: len(names)]) < 3 * one_machine


def _config_files():
    for folder in ("configs", "rehearsal"):
        for name in sorted(os.listdir(os.path.join(ROOT, "chipbench", folder))):
            if name.endswith(".json") and name != "manifest.json":
                yield os.path.join(folder, name)


@pytest.mark.parametrize("path", list(_config_files()))
def test_program_and_reference_are_told_one_learning_rate(path):
    """A configuration's ``optimizer`` block is what the reference follows;
    the program reads only the estimator's block. A rate that is not the
    estimator's default (Adam's 1e-3) has to be in both."""
    with open(os.path.join(ROOT, "chipbench", path)) as fh:
        config = json.load(fh)
    told = config["model"].get("optimizer_kwargs", {})
    assert config["model"].get("optimizer", "Adam") == config["optimizer"]["name"] == "Adam"
    assert told.get("learning_rate", 1e-3) == config["optimizer"]["learning_rate"]
    for theirs, ours in (("beta_1", 0.9), ("beta_2", 0.999), ("epsilon", 1e-7)):
        assert told.get(theirs, ours) == config["optimizer"][theirs]
