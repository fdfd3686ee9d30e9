"""The operations a machine's build requires, counted from the
configuration's own sizes, and the table of peaks they are held against.

Conventions: a matmul of (m, k) x (k, n) is 2·m·k·n operations; a training
step is three forward passes' worth (forward, gradient with respect to the
inputs, gradient with respect to the weights); elementwise work and anything
recomputed are not counted. A "window" is one sample: ``lookback_window``
consecutive rows of ``n_tags`` sensors.
"""

from __future__ import annotations

import importlib
from typing import Dict

from chipbench.traffic import load_json


def forward_flops_per_window(config: dict) -> float:
    """One forward pass over one window: counted by the configuration's own
    plain model, ``chipbench/configs/<reference>.py``, beside its
    ``init_params`` and ``forward``."""
    reference = importlib.import_module(f"chipbench.configs.{config['reference']}")
    return float(reference.forward_flops_per_window(config))


def windows(config: dict, n_rows: int) -> int:
    return max(n_rows - int(config["model"]["lookback_window"]) + 1, 0)


def build_flops_per_machine(config: dict, n_rows: int) -> float:
    """One machine's build: every TimeSeriesSplit fold trained on its prefix
    and applied to its test slice, then the final fit on all rows."""
    fwd = forward_flops_per_window(config)
    train = 3.0 * fwd * int(config["model"]["epochs"])
    splits = int(config["cv_splits"])
    fold = n_rows // (splits + 1)
    total = train * windows(config, n_rows)
    for k in range(1, splits + 1):
        total += train * windows(config, k * fold) + fwd * windows(config, fold)
    return total


def peaks(device_kind: str) -> Dict[str, float]:
    """The chip's published peaks. A device that is not in the table is an
    error, never a default."""
    table = load_json("peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(
            f"no peaks on record for device_kind {device_kind!r}; add it to "
            f"chipbench/peaks.json with its source"
        )
    return table["devices"][device_kind]
