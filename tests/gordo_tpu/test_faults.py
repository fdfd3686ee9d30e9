"""
Unit tests for the fault-domain layer (util/faults.py): classification,
retry/backoff, the fault-plan parser, and the validation helpers.
"""

import json

import numpy as np
import pandas as pd
import pytest

from gordo_tpu.util import faults
from gordo_tpu.util.faults import (
    FaultPlan,
    FaultPolicy,
    InjectedOOM,
    NonFiniteDataError,
    PermanentFault,
    QuarantineRecord,
    TransientFault,
    is_oom,
    is_transient,
    retry_call,
)


# ---------------------------------------------------------- classification
def test_classification():
    assert is_transient(TransientFault("x"))
    assert is_transient(TimeoutError("x"))
    assert is_transient(ConnectionError("x"))
    assert is_transient(OSError("x"))
    assert not is_transient(PermanentFault("x"))
    assert not is_transient(ValueError("x"))
    assert not is_transient(NonFiniteDataError("x"))


def test_classification_by_type_name():
    """requests/urllib3 exception types are recognized without importing
    those libraries here (matched by type name in the MRO)."""

    class ReadTimeout(Exception):
        pass

    assert is_transient(ReadTimeout("x"))


def test_is_oom():
    assert is_oom(InjectedOOM("RESOURCE_EXHAUSTED: injected"))
    assert is_oom(MemoryError())
    assert is_oom(RuntimeError("RESOURCE_EXHAUSTED: out of memory on device"))
    assert is_oom(RuntimeError("Allocator ran OOM trying to allocate 2GiB"))
    assert not is_oom(RuntimeError("shape mismatch"))
    assert not is_oom(TransientFault("x"))


# ----------------------------------------------------------------- policy
def test_policy_backoff_is_exponential_and_capped():
    p = FaultPolicy(backoff_base=1.0, backoff_factor=2.0, backoff_max=3.0, jitter=0.0)
    assert p.backoff(1) == 1.0
    assert p.backoff(2) == 2.0
    assert p.backoff(3) == 3.0  # capped
    assert p.backoff(10) == 3.0


def test_policy_backoff_jitter_is_deterministic():
    p = FaultPolicy(backoff_base=1.0, jitter=0.5)
    assert p.backoff(1, "machine-a") == p.backoff(1, "machine-a")
    # different machines get different (decorrelated) jitter
    assert p.backoff(1, "machine-a") != p.backoff(1, "machine-b")
    # jitter only ever lengthens the delay, bounded by the fraction
    assert 1.0 <= p.backoff(1, "machine-a") <= 1.5


def test_policy_from_env(monkeypatch):
    monkeypatch.setenv("GORDO_TPU_FAULT_MAX_ATTEMPTS", "5")
    monkeypatch.setenv("GORDO_TPU_FAULT_BACKOFF_BASE", "0.25")
    p = FaultPolicy.from_env()
    assert p.max_attempts == 5
    assert p.backoff_base == 0.25
    # invalid values fall back to defaults instead of crashing the build
    monkeypatch.setenv("GORDO_TPU_FAULT_MAX_ATTEMPTS", "banana")
    assert FaultPolicy.from_env().max_attempts == FaultPolicy.max_attempts


def test_retry_call_retries_transient_then_succeeds():
    policy = FaultPolicy(max_attempts=3, backoff_base=0.0, jitter=0.0)
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise TransientFault("flake")
        return "ok"

    result, attempts = retry_call(flaky, policy, sleep=lambda _s: None)
    assert result == "ok" and attempts == 3


def test_retry_call_raises_permanent_immediately():
    policy = FaultPolicy(max_attempts=5, backoff_base=0.0)
    calls = {"n": 0}

    def broken():
        calls["n"] += 1
        raise PermanentFault("dead")

    with pytest.raises(PermanentFault):
        retry_call(broken, policy, sleep=lambda _s: None)
    assert calls["n"] == 1


def test_retry_call_exhausts_budget():
    policy = FaultPolicy(max_attempts=3, backoff_base=0.0)
    calls = {"n": 0}

    def always_flaky():
        calls["n"] += 1
        raise TransientFault("flake")

    with pytest.raises(TransientFault):
        retry_call(always_flaky, policy, sleep=lambda _s: None)
    assert calls["n"] == 3


# ------------------------------------------------------------- fault plan
def test_plan_parse_and_fire_counts():
    plan = FaultPlan.parse(
        json.dumps(
            {
                "rules": [
                    {"site": "data_fetch", "machine": "m-1", "times": 2,
                     "error": "transient"},
                    {"site": "data_fetch", "machine": "m-2", "times": -1,
                     "error": "permanent"},
                ]
            }
        )
    )
    # m-1: exactly two firings, then clean
    with pytest.raises(TransientFault):
        plan.fire("data_fetch", machine="m-1")
    with pytest.raises(TransientFault):
        plan.fire("data_fetch", machine="m-1")
    plan.fire("data_fetch", machine="m-1")  # exhausted: no raise
    # m-2: every invocation, forever
    for _ in range(3):
        with pytest.raises(PermanentFault):
            plan.fire("data_fetch", machine="m-2")
    # unmatched machine/site: never fires
    plan.fire("data_fetch", machine="m-3")
    plan.fire("bucket_compile", machines=["m-1", "m-2"])


def test_plan_bucket_compile_matches_membership():
    plan = FaultPlan.parse(
        '[{"site": "bucket_compile", "machine": "m-4", '
        '"times": 1, "error": "resource_exhausted"}]'
    )
    plan.fire("bucket_compile", machines=["m-1", "m-2"])  # not a member
    with pytest.raises(InjectedOOM) as exc_info:
        plan.fire("bucket_compile", machines=["m-3", "m-4"])
    assert is_oom(exc_info.value)
    plan.fire("bucket_compile", machines=["m-3", "m-4"])  # budget spent


def test_plan_from_file(tmp_path):
    plan_file = tmp_path / "plan.json"
    plan_file.write_text(
        '{"rules": [{"site": "data_fetch", "machine": "m", '
        '"error": "permanent"}]}'
    )
    plan = FaultPlan.parse(f"@{plan_file}")
    with pytest.raises(PermanentFault):
        plan.fire("data_fetch", machine="m")


def test_plan_env_roundtrip(monkeypatch):
    monkeypatch.setenv(
        faults.PLAN_ENV,
        '[{"site": "data_fetch", "machine": "m", "error": "transient"}]',
    )
    faults.reset_plan()
    with pytest.raises(TransientFault):
        faults.fault_point("data_fetch", machine="m")
    faults.fault_point("data_fetch", machine="m")  # budget spent
    # counters survive repeated get_plan() calls while env is unchanged
    faults.fault_point("data_fetch", machine="m")
    monkeypatch.delenv(faults.PLAN_ENV)
    faults.fault_point("data_fetch", machine="m")  # no plan: no-op


def test_maybe_poison_ndarray_and_dataframe(monkeypatch):
    monkeypatch.setenv(
        faults.PLAN_ENV, '[{"site": "poison_nan", "machine": "m"}]'
    )
    faults.reset_plan()
    X = np.ones((4, 3), dtype=np.float32)
    Xp = faults.maybe_poison("m", X)
    assert np.isnan(Xp[:, 0]).all()
    assert np.isfinite(X).all()  # original untouched
    df = pd.DataFrame(np.ones((4, 3)))
    dfp = faults.maybe_poison("m", df)
    assert dfp.iloc[:, 0].isna().all()
    assert np.isfinite(df.to_numpy()).all()
    # non-matching machine passes through unchanged (identity)
    assert faults.maybe_poison("other", X) is X


# ------------------------------------------------------------- validation
def test_non_finite_report():
    assert faults.non_finite_report(np.ones((3, 2))) is None
    X = np.ones((3, 2))
    X[1, 1] = np.nan
    report = faults.non_finite_report(X)
    assert "1 non-finite" in report and "X" in report
    y = np.full((3, 1), np.inf)
    assert "y" in faults.non_finite_report(np.ones((3, 2)), y)
    # integer arrays are trivially finite
    assert faults.non_finite_report(np.ones((3, 2), dtype=np.int64)) is None


def test_params_non_finite():
    good = {"w": np.ones((2, 2)), "b": np.zeros(2)}
    assert faults.params_non_finite(good, np.array([0.1, 0.05])) is None
    assert "loss" in faults.params_non_finite(good, np.array([0.1, np.nan]))
    bad = {"w": np.array([[1.0, np.inf]])}
    assert "parameters" in faults.params_non_finite(bad)


def test_quarantined_machines_record_to_dict():
    record = QuarantineRecord(
        machine="m", stage="data_fetch", reason="permanent_fetch_failure",
        error="boom", attempts=3,
    )
    d = record.to_dict()
    assert d["quarantined"] is True
    assert d["machine"] == "m" and d["attempts"] == 3


def test_quarantined_machines_record_attributes_observing_host(monkeypatch):
    """A merged pod-scale quarantine report must say WHICH host observed
    each fault: host/process_index ride along in every record."""
    monkeypatch.setenv("GORDO_TPU_HOST_ID", "host-east-3")
    monkeypatch.setenv("GORDO_TPU_PROCESS_ID", "3")
    d = QuarantineRecord(
        machine="m", stage="data_fetch", reason="r", error="e"
    ).to_dict()
    assert d["host"] == "host-east-3"
    assert d["process_index"] == 3


def test_quarantined_machines_record_attribution_defaults(monkeypatch):
    """Without the env knobs the attribution still resolves: hostname-pid
    and the live jax process index (0 in a single-process world)."""
    monkeypatch.delenv("GORDO_TPU_HOST_ID", raising=False)
    monkeypatch.delenv("GORDO_TPU_PROCESS_ID", raising=False)
    d = QuarantineRecord(machine="m", stage="s", reason="r", error="e").to_dict()
    assert d["host"] and "-" in d["host"]
    assert isinstance(d["process_index"], int) and d["process_index"] == 0
