"""The one traffic generator: a fleet of machines made from ``--seed``.

A traffic mix is a data file ``chipbench/mixes/<name>.json`` (slice and
chunk sizes, rows, the signal's parameters); a configuration is
``chipbench/configs/<name>.json`` (the model block and its tags). This module
turns the pair and a seed into machine definitions for the program, and into
the same sensor series for the plain reference. The series are the
benchmark's own: the program receives them through its data-provider
interface (:class:`ChipbenchDataProvider`), the reference calls
:func:`machine_frame` directly, so neither side takes its inputs from the
other.
"""

from __future__ import annotations

import functools
import json
import os
import zlib
from dataclasses import dataclass
from typing import Dict, Iterable, List

import numpy as np
import pandas as pd

HERE = os.path.dirname(os.path.abspath(__file__))


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as fh:
        return json.load(fh)


@dataclass(frozen=True)
class Traffic:
    name: str
    machines_per_build: int
    chunk_machines: int
    rows: int
    resolution: str
    train_start_date: str
    signal: Dict[str, object]
    check_machines: int

    @classmethod
    @functools.lru_cache(maxsize=None)
    def load(cls, name: str) -> "Traffic":
        doc = load_json("mixes", f"{name}.json")
        return cls(
            name=name,
            machines_per_build=int(doc["machines_per_build"]),
            chunk_machines=int(doc["chunk_machines"]),
            rows=int(doc["rows"]),
            resolution=str(doc["resolution"]),
            train_start_date=str(doc["train_start_date"]),
            signal=dict(doc["signal"]),
            check_machines=int(doc["check_machines"]),
        )

    @property
    def train_end_date(self) -> str:
        start = pd.Timestamp(self.train_start_date)
        return (start + self.rows * pd.Timedelta(self.resolution)).isoformat()


def tag_series(seed: int, tag: str, rows: int, signal: Dict[str, object]) -> np.ndarray:
    """One sensor: a mixture of slow sines, white noise and an offset, drawn
    from ``(seed, tag name)`` alone, so any process regenerates it."""
    rng = np.random.default_rng([int(seed), zlib.crc32(tag.encode())])
    n_sines = int(signal["sines"])
    t = np.arange(rows, dtype=np.float64)
    freqs = rng.uniform(*signal["freq"], size=n_sines)
    amps = rng.uniform(*signal["amp"], size=n_sines)
    phases = rng.uniform(0.0, 2.0 * np.pi, size=n_sines)
    base = (amps[:, None] * np.sin(2.0 * np.pi * freqs[:, None] * t + phases[:, None])).sum(0)
    noise = rng.normal(0.0, float(signal["noise_sd"]), size=rows)
    offset = rng.uniform(*signal["offset"])
    return base + noise + offset


def machine_name(config: str, seed: int, index: int) -> str:
    """Names carry the seed and a running index: no two slices and no two
    seeds share a name, a tag, a weight stream or a registry entry."""
    return f"cb-{config.replace('_', '-')}-s{int(seed)}-{index:05d}"


def machine_tags(name: str, n_tags: int) -> List[str]:
    return [f"{name}-tag-{j}" for j in range(n_tags)]


def machine_frame(seed: int, name: str, n_tags: int, traffic: Traffic) -> np.ndarray:
    """The (rows, tags) float32 matrix a machine trains on. The series sit
    on the resolution's own grid, so the program's resample-and-join must
    hand back exactly these rows."""
    cols = [
        tag_series(seed, tag, traffic.rows, traffic.signal)
        for tag in machine_tags(name, n_tags)
    ]
    return np.stack(cols, axis=1).astype(np.float32)


def machine_config(config: dict, traffic: Traffic, seed: int, name: str) -> dict:
    """The machine block ``batch-build`` would read from a project YAML."""
    return {
        "name": name,
        "dataset": {
            "type": "TimeSeriesDataset",
            "tags": machine_tags(name, int(config["n_tags"])),
            "train_start_date": traffic.train_start_date,
            "train_end_date": traffic.train_end_date,
            "resolution": traffic.resolution,
            "data_provider": {
                "type": "ChipbenchDataProvider",
                "seed": int(seed),
                "traffic": traffic.name,
            },
        },
        "evaluation": {"seed": int(seed)},
        "model": {
            "gordo_tpu.models.anomaly.diff.DiffBasedAnomalyDetector": {
                "require_thresholds": True,
                "base_estimator": {
                    "sklearn.pipeline.Pipeline": {
                        "steps": [
                            "sklearn.preprocessing.MinMaxScaler",
                            {config["estimator"]: dict(config["model"])},
                        ]
                    }
                },
            }
        },
    }


def register_provider():
    """Register the provider with the program's provider registry (the
    program resolves providers by class name). Imported lazily: the
    reference and the tests of the arithmetic need none of the program."""
    from gordo_tpu.dataset.data_provider import (
        GordoBaseDataProvider,
        register_data_provider,
    )

    @register_data_provider
    class ChipbenchDataProvider(GordoBaseDataProvider):
        def __init__(self, seed: int = 0, traffic: str = "fleet_build", **_):
            self.seed = int(seed)
            self.traffic = Traffic.load(traffic)
            self._init_kwargs = dict(seed=self.seed, traffic=traffic)

        def load_series(
            self, train_start_date, train_end_date, tag_list, dry_run=False
        ) -> Iterable[pd.Series]:
            index = pd.date_range(
                start=train_start_date,
                end=train_end_date,
                freq=self.traffic.resolution,
                inclusive="left",
            )
            for tag in tag_list:
                values = tag_series(
                    self.seed, tag.name, len(index), self.traffic.signal
                )
                yield pd.Series(values, index=index, name=tag.name)

    return ChipbenchDataProvider
