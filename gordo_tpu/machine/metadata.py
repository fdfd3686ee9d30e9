"""
Metadata dataclasses recorded during a model build.

Reference parity: gordo/machine/metadata/metadata.py:16-56 — same schema
(user_defined/build_metadata split; model/dataset build sections; CV scores and
durations), serialized with dataclasses_json just like the reference.
"""

from dataclasses import dataclass, field
from typing import Any, Dict, Optional

try:
    from dataclasses_json import dataclass_json
except ImportError:  # pragma: no cover - environment-dependent
    from gordo_tpu.util._dataclasses_json import dataclass_json


@dataclass_json
@dataclass
class CrossValidationMetaData:
    scores: Dict[str, Any] = field(default_factory=dict)
    cv_duration_sec: Optional[float] = None
    splits: Dict[str, Any] = field(default_factory=dict)


@dataclass_json
@dataclass
class ModelBuildMetadata:
    model_offset: int = 0
    model_creation_date: Optional[str] = None
    model_builder_version: Optional[str] = None
    cross_validation: CrossValidationMetaData = field(
        default_factory=CrossValidationMetaData
    )
    model_training_duration_sec: Optional[float] = None
    model_meta: Dict[str, Any] = field(default_factory=dict)


@dataclass_json
@dataclass
class DatasetBuildMetadata:
    query_duration_sec: Optional[float] = None
    dataset_meta: Dict[str, Any] = field(default_factory=dict)


@dataclass_json
@dataclass
class BuildMetadata:
    model: ModelBuildMetadata = field(default_factory=ModelBuildMetadata)
    dataset: DatasetBuildMetadata = field(default_factory=DatasetBuildMetadata)
    # fault-domain outcome for fleet builds (util/faults.py): quarantine
    # records ({"quarantined": True, "stage", "reason", "error", "attempts"})
    # or retry provenance for machines that recovered
    # ({"quarantined": False, "data_fetch_attempts": n}); empty for a clean
    # single-attempt build
    fault_domain: Dict[str, Any] = field(default_factory=dict)
    # per-phase build durations in seconds (observability/telemetry.py span
    # taxonomy: fetch/validate/cross_validation/fit/...). The serial builder
    # records measured walls; the fleet builder apportions each chunk's wall
    # the same way it does the legacy *_duration_sec fields
    phases: Dict[str, float] = field(default_factory=dict)


@dataclass_json
@dataclass
class Metadata:
    user_defined: Dict[str, Any] = field(default_factory=dict)
    build_metadata: BuildMetadata = field(default_factory=BuildMetadata)
