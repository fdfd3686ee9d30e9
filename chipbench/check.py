"""The comparison that decides ``correct``.

For a sample of the machines the window built, drawn from the seed, the
harness hands over what the timed path left on disk (the artifact loaded
back and applied) and :mod:`chipbench.reference` builds the same machines
plainly. Six numbers are read off each sampled machine:

``loss``        the final fit's epoch loss, relative gap
``weights``     norm of (program's change − reference's change) of the final
                fit's weights over the norm of the reference's change; leaves
                whose first gradient in the reference is under a thousandth
                of the median leaf's move by round-off alone and are left out
``output``      the loaded artifact applied to the probe rows against the
                reference's final model on the same rows, rms gap over rms
``threshold``   the detector's aggregate and per-tag thresholds (fold
                predictions → assembly), worst relative gap
``confidence``  the served ``total-anomaly-confidence`` on the probe rows
                (y-scaler, output and threshold together), rms gap over rms
``leaf``        the worst counted leaf's gap between the norm of the
                program's change and the norm of the reference's, over the
                reference's norm of that leaf or of the median leaf,
                whichever is larger: a leaf as large as the median one that
                is left unmoved, or moved double, reads 1

Each is compared twice, each time with a limit of its own from the cell's
file: as the median over the sample (``loss`` …), which is steady from seed
to seed and separates the stated precision from the one below; and as the
worst machine of the sample (``loss_worst`` …), loosely, so that a few
machines cannot be wrong behind a sound median: one chunk's update lost, an
artifact in the wrong slot. A number the cell's file gives no limit is not
compared.

Pure numpy: nothing of the program is imported here.
"""

from __future__ import annotations

import json
import sys
from typing import Dict, List, Sequence

import numpy as np

PER_MACHINE = ("loss", "weights", "output", "threshold", "confidence", "leaf")
NUMBERS = PER_MACHINE + tuple(f"{name}_worst" for name in PER_MACHINE)


def sample_indices(seed: int, n_built: int, n_sample: int) -> List[int]:
    """Which of the window's machines are compared: drawn from the seed,
    the window's last machine always among them."""
    rng = np.random.default_rng([int(seed), n_built])
    others = rng.choice(n_built - 1, size=min(n_sample, n_built) - 1, replace=False)
    return sorted(others.tolist() + [n_built - 1])


def _leaf_keys(tree) -> List[tuple]:
    """Where the leaves of a list-of-dicts parameter tree lie, by layer then key."""
    return [(i, key) for i, layer in enumerate(tree) for key in sorted(layer)]


def _rms(a) -> float:
    return float(np.sqrt(np.mean(np.square(np.asarray(a, np.float64)))))


def reference_confidence(ref: Dict[str, object], frame_probe: np.ndarray) -> np.ndarray:
    """``total-anomaly-confidence`` as the detector defines it: the mean
    over tags of the squared min-max-scaled residual, over the aggregate
    threshold."""
    out = np.asarray(ref["output"], np.float64)
    y = frame_probe[-len(out):].astype(np.float64)
    span = np.asarray(ref["scaler"]["span"], np.float64)
    return np.square((out - y) / span).mean(axis=1) / float(ref["aggregate_threshold"])


def as_observed(rec: Dict[str, object], frame_probe: np.ndarray) -> Dict[str, object]:
    """A reference build put in the program's place (the control, a planted
    fault): its numbers in the shape the harness reads off an artifact."""
    return {
        "loss": rec["loss"],
        "params": rec["params"],
        "aggregate_threshold": rec["aggregate_threshold"],
        "feature_thresholds": rec["feature_thresholds"],
        "output": rec["output"],
        "confidence": reference_confidence(rec, frame_probe),
    }


def gaps(observed: Dict[str, object], ref: Dict[str, object], frame_probe: np.ndarray) -> Dict[str, float]:
    """The six numbers for one machine. The weights are read a leaf at a
    time, each converted to float64 alone and dropped: a machine's trees are
    never copied whole."""

    def leaf(tree, where):
        return np.asarray(tree[where[0]][where[1]], np.float64)

    grad_norms, ref_norms, obs_norms, diff_sq, base_sq = [], [], [], [], []
    for where in _leaf_keys(ref["init"]):
        init = leaf(ref["init"], where)
        d_ref = leaf(ref["params"], where) - init
        d_obs = leaf(observed["params"], where) - init
        grad_norms.append(np.linalg.norm(leaf(ref["first_grad"], where)))
        ref_norms.append(np.linalg.norm(d_ref))
        obs_norms.append(np.linalg.norm(d_obs))
        diff_sq.append(np.sum((d_obs - d_ref) ** 2))
        base_sq.append(np.sum(d_ref**2))
    grad_norms, ref_norms, obs_norms = (np.array(a) for a in (grad_norms, ref_norms, obs_norms))
    counted = grad_norms >= 1e-3 * np.median(grad_norms)
    diff = np.sqrt(sum(a for a, c in zip(diff_sq, counted) if c))
    base = np.sqrt(sum(b for b, c in zip(base_sq, counted) if c))
    leaf_gaps = np.abs(obs_norms - ref_norms) / np.maximum(ref_norms, np.median(ref_norms))
    thr_obs = np.concatenate([[observed["aggregate_threshold"]], np.ravel(observed["feature_thresholds"])])
    thr_ref = np.concatenate([[ref["aggregate_threshold"]], np.ravel(ref["feature_thresholds"])])
    conf_ref = reference_confidence(ref, frame_probe)
    return {
        "loss": abs(float(observed["loss"]) - ref["loss"]) / abs(ref["loss"]),
        "weights": float(diff / base),
        "output": _rms(np.asarray(observed["output"]) - ref["output"]) / _rms(ref["output"]),
        "threshold": float(np.max(np.abs(thr_obs - thr_ref) / np.abs(thr_ref))),
        "confidence": _rms(np.asarray(observed["confidence"]) - conf_ref) / _rms(conf_ref),
        "leaf": float(np.max(leaf_gaps[counted])),
    }


def typical(per_machine: Sequence[Dict[str, float]]) -> Dict[str, float]:
    """The sample's numbers: each number's median machine and its worst. The
    worst machine's gap swings by its nature: a few machines' fits amplify
    rounding tenfold, in the program and in a reference computed at the
    stated precision alike (PERF.md), so only the median separates the stated
    precision from the one below, and the worst is held loosely, against
    faults. A machine that gave no number (a missing artifact, a NaN) spoils
    both."""
    out = {}
    for name in PER_MACHINE:
        vals = [g[name] for g in per_machine]
        sound = bool(np.all(np.isfinite(vals)))
        out[name] = float(np.median(vals)) if sound else float("nan")
        out[f"{name}_worst"] = float(np.max(vals)) if sound else float("nan")
    return out


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> Dict[str, Dict[str, float]]:
    """Each number beside its limit; a number that is not finite fails."""
    return {
        name: {"value": numbers[name], "limit": float(limits[name])}
        for name in NUMBERS
        if name in limits
    }


def is_correct(compared: Dict[str, Dict[str, float]]) -> bool:
    return bool(compared) and all(
        np.isfinite(c["value"]) and c["value"] <= c["limit"] for c in compared.values()
    )


def report(compared: Dict[str, Dict[str, float]], stream=None) -> None:
    stream = stream or sys.stderr
    for name, c in compared.items():
        flag = "ok" if np.isfinite(c["value"]) and c["value"] <= c["limit"] else "FAIL"
        print(f"compared {name}: {c['value']:.6g} limit {c['limit']:.6g} {flag}", file=stream)
    print("compared " + json.dumps(compared), file=stream)
