"""``check.gaps`` reads the weights a leaf at a time. Held here against the
formula it replaced, written out over whole float64 copies of the trees, on
a random tree with a leaf that is not counted, a leaf left unmoved and a leaf
moved double; and against its own promise that no tree is copied whole."""

import tracemalloc

import numpy as np
import pytest

from chipbench import check

PROBE = 9


def _whole_trees(observed, ref):
    """The weights' three numbers as ``check.gaps`` computed them until PR 34."""

    def leaves(tree):
        return [np.asarray(layer[key], np.float64) for layer in tree for key in sorted(layer)]

    init, grads = leaves(ref["init"]), leaves(ref["first_grad"])
    d_ref = [a - b for a, b in zip(leaves(ref["params"]), init)]
    d_obs = [a - b for a, b in zip(leaves(observed["params"]), init)]
    grad_norms = np.array([np.linalg.norm(g) for g in grads])
    counted = grad_norms >= 1e-3 * np.median(grad_norms)
    diff = np.sqrt(sum(np.sum((a - b) ** 2) for a, b, c in zip(d_obs, d_ref, counted) if c))
    base = np.sqrt(sum(np.sum(b**2) for b, c in zip(d_ref, counted) if c))
    ref_norms = np.array([np.linalg.norm(b) for b in d_ref])
    obs_norms = np.array([np.linalg.norm(a) for a in d_obs])
    leaf_gaps = np.abs(obs_norms - ref_norms) / np.maximum(ref_norms, np.median(ref_norms))
    return float(diff / base), float(np.max(leaf_gaps[counted])), counted


def _machine(rng, shapes):
    """A reference record and a program's, a rounding apart."""
    tree = lambda scale: [  # noqa: E731
        {k: (scale * rng.standard_normal(shape)).astype(np.float32) for k, shape in layer.items()}
        for layer in shapes
    ]
    init, change, grad = tree(0.3), tree(0.01), tree(1.0)
    params = [{k: i[k] + c[k] for k in i} for i, c in zip(init, change)]
    noisy = [
        {k: (p[k] + 1e-4 * rng.standard_normal(p[k].shape)).astype(np.float32) for k in p}
        for p in params
    ]
    out = rng.standard_normal((PROBE, 3))
    ref = {
        "init": init, "first_grad": grad, "params": params, "loss": 0.5, "output": out,
        "scaler": {"span": np.ones(3)}, "aggregate_threshold": 0.7,
        "feature_thresholds": np.array([0.1, 0.2, 0.3]),
    }
    frame = rng.standard_normal((PROBE, 3)).astype(np.float32)
    observed = check.as_observed(dict(ref, params=noisy, loss=0.5001), frame)
    return observed, ref, frame


SHAPES = [{"kernel": (8, 16), "bias": (16,)}, {}, {"wq": (16, 16), "bq": (16,), "bk": (16,)},
          {"kernel": (16, 3), "bias": (3,)}]


@pytest.mark.parametrize("fault", ["none", "uncounted", "unmoved", "doubled"])
def test_streamed_numbers_are_the_whole_trees_numbers(fault):
    observed, ref, frame = _machine(np.random.default_rng(34), SHAPES)
    if fault == "uncounted":
        # a key's bias under softmax: a gradient of rounding, a change of noise
        ref["first_grad"][2]["bk"] *= 1e-7
        observed["params"][2]["bk"] = ref["init"][2]["bk"] - 3 * (ref["params"][2]["bk"] - ref["init"][2]["bk"])
    elif fault == "unmoved":
        observed["params"][0]["kernel"] = ref["init"][0]["kernel"]
    elif fault == "doubled":
        observed["params"][2]["wq"] = 2 * ref["params"][2]["wq"] - ref["init"][2]["wq"]
    numbers = check.gaps(observed, ref, frame)
    weights, leaf, counted = _whole_trees(observed, ref)
    assert numbers["weights"] == pytest.approx(weights, rel=1e-12)
    assert numbers["leaf"] == pytest.approx(leaf, rel=1e-12)
    assert set(numbers) == set(check.PER_MACHINE)
    assert counted.sum() == (6 if fault == "uncounted" else 7)
    if fault in ("none", "uncounted"):
        assert numbers["weights"] < 0.02 and numbers["leaf"] < 0.02
    else:
        # a leaf as large as the median one, unmoved or moved double, reads 1
        assert numbers["leaf"] == pytest.approx(1.0, abs=0.02)
    assert numbers["loss"] == pytest.approx(2e-4, rel=1e-6)
    assert numbers["output"] == 0 and numbers["threshold"] == 0 and numbers["confidence"] == 0


def test_no_tree_is_copied_whole():
    # forty leaves of 0.8 MB in float64: eight whole copies were 256 MB
    shapes = [{"kernel": (100, 1000)} for _ in range(40)]
    observed, ref, frame = _machine(np.random.default_rng(35), shapes)
    leaf_bytes = 100 * 1000 * 8
    tracemalloc.start()
    try:
        check.gaps(observed, ref, frame)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert peak < 8 * leaf_bytes
