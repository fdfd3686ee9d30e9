"""
Latency attribution engine (ISSUE 17, layer 2): gated observe, epoch
windows, the budget-closing decomposition contract (rows sum EXACTLY to
the headline delta, for live windows and for recorded ones), mix-shift,
shard merge.
"""

import pytest

from gordo_tpu.observability import attribution


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    for var in (
        "GORDO_TPU_PERF_ATTRIBUTION",
        "GORDO_TPU_PERF_SENTINEL",
        "GORDO_TPU_PERF_WINDOW_S",
    ):
        monkeypatch.delenv(var, raising=False)
    attribution.reset()
    yield
    attribution.reset()


# ------------------------------------------------------------ gated observe
def test_observe_is_noop_when_disabled():
    attribution.observe(
        "m", 0.010, {"decode": 0.002, "predict": 0.004}, now=1000.0
    )
    index = attribution.current_window_index(1000.0)
    assert attribution.window_stats(index) is None
    assert attribution.snapshot()["enabled"] is False


def test_sentinel_knob_also_enables_attribution(monkeypatch):
    """The sentinel feeds on these windows, so its knob opens this gate."""
    monkeypatch.setenv("GORDO_TPU_PERF_SENTINEL", "1")
    assert attribution.enabled() is True


# ----------------------------------------------------------- epoch windows
def test_observe_fills_epoch_windows(monkeypatch):
    monkeypatch.setenv("GORDO_TPU_PERF_ATTRIBUTION", "1")
    monkeypatch.setenv("GORDO_TPU_PERF_WINDOW_S", "100")
    for i in range(50):
        attribution.observe(
            "model-a", 0.010,
            {"decode": 0.002, "predict": 0.004, "encode": 0.001},
            now=1000.0 + i,
        )
    stats = attribution.window_stats(
        attribution.current_window_index(1000.0)
    )
    assert stats is not None
    assert stats["total"]["count"] == 50
    assert {"decode", "predict", "encode", "server_other"} <= set(
        stats["phases"]
    )
    assert stats["models"]["model-a"]["count"] == 50
    # server_other closes the in-request budget: 10 - (2+4+1) = ~3ms
    assert stats["phases"]["server_other"]["p50_ms"] == pytest.approx(
        3.0, rel=0.10
    )


def test_old_windows_expire(monkeypatch):
    monkeypatch.setenv("GORDO_TPU_PERF_ATTRIBUTION", "1")
    monkeypatch.setenv("GORDO_TPU_PERF_WINDOW_S", "100")
    attribution.observe("m", 0.010, {"decode": 0.002}, now=1000.0)
    old_index = attribution.current_window_index(1000.0)
    # five windows later the old one must have been dropped
    attribution.observe("m", 0.010, {"decode": 0.002}, now=1500.0)
    assert attribution.window_stats(old_index) is None


# ----------------------------------------------------------- decomposition
def _stats(p50, p99, phases):
    return {
        "total": {"p50_ms": p50, "p99_ms": p99},
        "phases": {
            name: {"p50_ms": value, "p99_ms": value}
            for name, value in phases.items()
        },
    }


def test_decomposition_rows_sum_exactly_to_headline():
    base = _stats(10.0, 20.0, {"decode": 2.0, "predict": 5.0, "encode": 1.0})
    cur = _stats(12.0, 40.0, {"decode": 2.0, "predict": 5.0, "encode": 21.0})
    decomp = attribution.decompose_stats(base, cur, "p99_ms")
    assert decomp["headline_delta_ms"] == pytest.approx(20.0)
    assert sum(r["delta_ms"] for r in decomp["rows"]) == pytest.approx(
        decomp["headline_delta_ms"]
    )
    rows = {r["name"]: r for r in decomp["rows"]}
    assert rows["encode"]["delta_ms"] == pytest.approx(20.0)
    assert rows["encode"]["share"] == pytest.approx(1.0)
    assert rows["decode"]["delta_ms"] == pytest.approx(0.0)


def test_walltime_splits_queue_from_server_other():
    """With request_walltime present, the derived rows split the delta
    into in-server remainder vs queue/transport — and still close the
    budget exactly."""
    base = _stats(
        10.0, 20.0,
        {"decode": 2.0, "predict": 5.0, "encode": 1.0,
         "request_walltime": 9.0},
    )
    cur = _stats(
        12.0, 35.0,
        {"decode": 2.0, "predict": 5.0, "encode": 1.0,
         "request_walltime": 9.5},
    )
    decomp = attribution.decompose_stats(base, cur, "p99_ms")
    names = {r["name"] for r in decomp["rows"]}
    assert "queue/transport" in names
    assert "server_other" in names
    assert "unattributed" not in names
    assert sum(r["delta_ms"] for r in decomp["rows"]) == pytest.approx(
        decomp["headline_delta_ms"]
    )
    rows = {r["name"]: r for r in decomp["rows"]}
    # walltime moved +0.5 with flat phases; the client total moved +15,
    # so queue/transport carries the other +14.5
    assert rows["server_other"]["delta_ms"] == pytest.approx(0.5)
    assert rows["queue/transport"]["delta_ms"] == pytest.approx(14.5)


def test_mix_shift_shift_share():
    base = {
        "a": {"count": 50, "mean_ms": 1.0},
        "b": {"count": 50, "mean_ms": 9.0},
    }
    cur = {
        "a": {"count": 10, "mean_ms": 1.0},
        "b": {"count": 90, "mean_ms": 9.0},
    }
    # b's share rose 0.4 at base-mean 9ms, a's fell 0.4 at 1ms
    assert attribution.mix_shift(base, cur) == pytest.approx(
        0.4 * 9.0 - 0.4 * 1.0
    )
    assert attribution.mix_shift(None, cur) is None
    assert attribution.mix_shift(base, {}) is None


def test_live_decomposition_current_vs_closed_window(monkeypatch):
    monkeypatch.setenv("GORDO_TPU_PERF_ATTRIBUTION", "1")
    monkeypatch.setenv("GORDO_TPU_PERF_WINDOW_S", "100")
    for i in range(40):
        attribution.observe(
            "m", 0.010, {"encode": 0.001}, now=1000.0 + i
        )
    for i in range(40):
        attribution.observe(
            "m", 0.030, {"encode": 0.021}, now=1100.0 + i
        )
    decomp = attribution.live_decomposition("p50_ms", now=1100.0)
    assert decomp is not None
    assert decomp["base_window"] == 10
    assert decomp["cur_window"] == 11
    rows = {r["name"]: r for r in decomp["rows"]}
    # the +20ms move is the encode phase (log-bucket resolution ~1.6%)
    assert rows["encode"]["delta_ms"] == pytest.approx(20.0, rel=0.2)
    assert sum(r["delta_ms"] for r in decomp["rows"]) == pytest.approx(
        decomp["headline_delta_ms"]
    )


def test_format_decomposition_renders_table():
    base = _stats(10.0, 20.0, {"decode": 2.0, "predict": 5.0, "encode": 1.0})
    cur = _stats(12.0, 40.0, {"decode": 2.0, "predict": 5.0, "encode": 21.0})
    lines = attribution.format_decomposition(
        attribution.decompose_stats(base, cur, "p99_ms")
    )
    assert any("headline" in line for line in lines)
    assert any(line.lstrip().startswith("encode") for line in lines)


# -------------------------------------------------------------- fleet merge
def test_shard_payload_merge_doubles_counts(monkeypatch):
    monkeypatch.setenv("GORDO_TPU_PERF_ATTRIBUTION", "1")
    monkeypatch.setenv("GORDO_TPU_PERF_WINDOW_S", "100")
    for i in range(10):
        attribution.observe(
            "m", 0.010, {"decode": 0.002}, now=1000.0 + i
        )
    payload = attribution.shard_payload()
    assert payload
    merged = attribution.merge_payloads([(1, payload), (2, payload)])
    index = str(attribution.current_window_index(1000.0))
    assert merged[index]["models"]["m"][0] == 20
    total = merged[index]["phases"]["total"]
    from gordo_tpu.observability.latency import LatencyHistogram

    assert LatencyHistogram.from_dict(total).count == 20


# ------------------------------------------- two recorded serving windows
# phase stats of two recorded serving-load windows (CPU host timings, kept
# as fixed inputs: what is tested is the arithmetic, not a speed)
_RECORDED = (
    {
        "total": {"p50_ms": 2.884, "p99_ms": 15.198},
        "phases": {
            "decode": {"p50_ms": 0.515, "p99_ms": 1.427},
            "encode": {"p50_ms": 0.368, "p99_ms": 0.53},
            "predict": {"p50_ms": 0.652, "p99_ms": 1.793},
            "request_walltime": {"p50_ms": 1.884, "p99_ms": 5.035},
        },
    },
    {
        "total": {"p50_ms": 3.25, "p99_ms": 35.4},
        "phases": {
            "decode": {"p50_ms": 0.576, "p99_ms": 2.029},
            "predict": {"p50_ms": 0.698, "p99_ms": 2.457},
            "encode": {"p50_ms": 0.399, "p99_ms": 0.568},
            "request_walltime": {"p50_ms": 2.06, "p99_ms": 4.608},
        },
    },
)


def test_recorded_windows_decomposition_sums_within_ten_percent():
    """ISSUE 17 acceptance: the p99 decomposition's per-phase rows sum
    within 10% of the headline p99 delta (exactly, by construction — the
    derived rows close the budget)."""
    decomp = attribution.decompose_stats(_RECORDED[0], _RECORDED[1], "p99_ms")
    assert decomp is not None
    headline = decomp["headline_delta_ms"]
    assert headline != 0
    row_sum = sum(r["delta_ms"] for r in decomp["rows"])
    assert abs(row_sum - headline) <= 0.10 * abs(headline)
