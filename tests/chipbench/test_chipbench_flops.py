"""The benchmark's own operation count against a hand count."""

import json
import os

import pytest

from chipbench import flops

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _config(name):
    with open(os.path.join(ROOT, "chipbench", "configs", name + ".json")) as fh:
        return json.load(fh)


def _rehearsal(name):
    with open(os.path.join(ROOT, "chipbench", "rehearsal", name + ".json")) as fh:
        return json.load(fh)


def test_lstm_forward_by_hand():
    # gates: 8·(in·u + u²) a step, layers 8→256→128→64→64→128→256, 144 steps; then 256→8
    step = 8 * (
        (8 * 256 + 256 * 256) + (256 * 128 + 128 * 128) + (128 * 64 + 64 * 64)
        + (64 * 64 + 64 * 64) + (64 * 128 + 128 * 128) + (128 * 256 + 256 * 256)
    )
    assert step == 2_080_768
    hand = step * 144 + 2 * 256 * 8
    assert flops.forward_flops_per_window(_config("lstm_ae_144")) == hand
    assert hand / 1e6 == pytest.approx(299.63, abs=0.005)


def test_lstm_forward_at_other_widths():
    # the widths of the repo's windowed fleet at 6755c13, as ISSUE 25 counted them
    config = dict(_config("lstm_ae_144"))
    config["model"] = dict(config["model"], dims=[64, 32], funcs=["tanh", "tanh"])
    step = 8 * ((8 * 64 + 64 * 64) + (64 * 32 + 32 * 32) + (32 * 32 + 32 * 32) + (32 * 64 + 64 * 64))
    assert flops.forward_flops_per_window(config) == step * 144 + 2 * 64 * 8
    assert flops.forward_flops_per_window(config) / 1e6 == pytest.approx(18.29, abs=0.005)


def test_transformer_forward_by_hand():
    # the factory's defaults, as ISSUE 25 counted them
    config = dict(_rehearsal("transformer_tiny"), n_tags=8)
    config["model"] = dict(config["model"], lookback_window=144, d_model=64, ff_dim=128, num_blocks=2)
    t, d, ff = 144, 64, 128
    block = 8 * d * d * t + 4 * t * t * d + 4 * d * ff * t
    hand = 2 * 8 * d * t + 2 * block + 2 * d * 8
    assert flops.forward_flops_per_window(config) == hand
    assert hand / 1e6 == pytest.approx(29.64, abs=0.005)


def test_a_kind_without_a_plain_model_has_no_count():
    with pytest.raises(ImportError):
        flops.forward_flops_per_window({"reference": "no_such_kind"})


def test_build_flops_at_the_cells_rows():
    config = _config("lstm_ae_144")
    # 4,032 rows, 3 folds: trained windows 865 + 1,873 + 2,881 + 3,889, and
    # three test slices of 865 windows applied once
    fwd = flops.forward_flops_per_window(config)
    hand = 3 * fwd * (865 + 1873 + 2881 + 3889) + fwd * 3 * 865
    assert flops.build_flops_per_machine(config, 4032) == hand
    assert hand / 1e12 == pytest.approx(9.324, abs=0.0006)


def test_unknown_device_has_no_default_peak():
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks("TPU v99")
