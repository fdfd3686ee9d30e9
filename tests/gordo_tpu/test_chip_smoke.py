"""CPU rehearsal of ``chip_smoke.py``: each leg at a tiny size, so that a
broken leg is found here and not on the chip, where time is budgeted. The
kernel runs in the Pallas interpreter because the test asks for it
(``interpret=True``); nothing in the smoke selects it on its own, and without
a TPU the smoke itself refuses to run."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_smoke_refuses_to_run_off_the_tpu(tmp_path):
    """With jax held to the CPU the script exits non-zero before doing any
    work and prints no result line."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=60, cwd=str(tmp_path),
    )
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "JAX_PLATFORMS=cpu" in proc.stderr
    # and a leg's own check says no as well (the conftest's backend is CPU)
    with pytest.raises(SystemExit, match="not 'tpu'"):
        chip_smoke.require_tpu()


def test_smoke_alone_in_a_directory_fails(tmp_path):
    """The script without the program beside it has nothing to run."""
    lonely = tmp_path / "chip_smoke.py"
    lonely.write_text(open(os.path.join(REPO, "chip_smoke.py")).read())
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    proc = subprocess.run(
        [sys.executable, str(lonely)], env={**env, "PYTHONPATH": ""},
        capture_output=True, text=True, timeout=60, cwd=str(tmp_path),
    )
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "no gordo_tpu package" in proc.stderr


def test_kernel_leg_rehearsal():
    report = chip_smoke.kernel_leg(
        corners=((64, 8),),
        machine={
            "d_model": 8, "num_heads": 1, "ff_dim": 8, "num_blocks": 1,
            "lookback_window": 8, "batch_size": 64, "epochs": 1,
            "train_end_date": "2019-01-01T08:00:00+00:00",
        },
        interpret=True,
    )
    # causal and full, f32 and bf16, plus the vmapped run
    assert len(report["shapes"]) == 5
    f32 = report["shapes"]["T64_dh8_float32_causal"]
    # in the interpreter both sides are true f32: far inside the tolerance
    assert f32["fwd_rel_err"] < 1e-5 and f32["grad_rel_err"] < 1e-4
    assert report["from_config"]["anomaly_rows"] > 0


def test_flash_check_fails_on_a_wrong_kernel(monkeypatch):
    """The comparison is a real one: a kernel that returns something else
    fails the leg."""
    import importlib

    kernel_module = importlib.import_module(
        "gordo_tpu.ops.pallas_kernels.flash_attention"
    )
    monkeypatch.setattr(
        kernel_module, "flash_attention",
        lambda q, k, v, causal=False, interpret=False: v * 1.5,
    )
    with pytest.raises(AssertionError, match="disagrees with the reference"):
        chip_smoke.flash_vs_reference(64, 8, True, "float32", interpret=True)


def test_build_and_serve_legs_rehearsal(tmp_path):
    """config -> batch-build -> artifacts -> run-server -> anomaly POSTs
    through the real commands, on whatever device the commands find (here:
    the CPU, said so by the processes themselves)."""
    build = chip_smoke.build_leg(
        str(tmp_path), {"machines": 8, "epochs": 1, "tags": 4}
    )
    assert build["platform"] == "cpu" and build["artifacts"] == 8
    assert build["serial_fallbacks"] == 0 and build["quarantined"] == 0
    # the conftest's eight virtual CPU devices reach the command through
    # XLA_FLAGS: the stacked fleet must lie over all of them
    assert build["shard_devices"] == build["count"] == 8
    # compiled here or loaded from the persistent cache an earlier run left
    assert build["compiles"] + build["cache_hits"] >= 1
    assert build["compile_wall_s"] > 0

    serve = chip_smoke.serve_leg(
        str(tmp_path), {"posts": 16, "machines": 8, "rows": 100, "clients": 4}
    )
    assert serve["platform"] == "cpu"
    # without chips the default pool is two workers
    assert serve["workers"] == 2
    assert serve["count"] == 2 * serve["devices_per_worker"]
    assert serve["warmup"]["models"] == 8
    assert serve["trace_compiles_after_warmup"] == 0
    assert sum(serve["batcher"]["self_ab"].values()) >= 1
    json.dumps(serve)  # the report is one JSON line


def test_windowed_leg_rehearsal():
    report = chip_smoke.windowed_leg(
        {"machines": 2, "dims": [8, 4], "tags": 3, "lookback_window": 8,
         "batch_size": 32, "epochs": 1, "compute_dtype": "bfloat16"}
    )
    assert report["machines"] == 2 and report["anomaly_rows"] > 0
