"""
Analytic FLOPs accounting per :class:`~gordo_tpu.models.spec.ModelSpec`.

The reference publishes no performance numbers at all (BASELINE.md); for a
TPU-native framework the honest single-chip yardstick is MFU — achieved
FLOP/s divided by the chip's peak for the compute dtype. This module derives
the FLOP count of a forward pass by walking the spec's layers, so the serving
MFU gauge (server/batcher.py, observability/device.py) needs no
instrumentation of the compiled program. A build's operations are counted by
the benchmark's own ``chipbench/flops.py``.

Conventions (standard accounting, matmul-dominated):
- a matmul of (m, k) x (k, n) costs 2*m*k*n FLOPs
- elementwise work (activations, norms, residuals) is ignored — it is
  bandwidth-, not FLOP-, bound and contributes <1% on these shapes
"""

from __future__ import annotations

from typing import Optional

from gordo_tpu.models.spec import (
    DenseLayer,
    HybridBlock,
    LatentBlock,
    LayerNormLayer,
    LSTMLayer,
    ModelSpec,
    MoEBlock,
    PoolLayer,
    PositionalEncoding,
    RMSNormLayer,
    SambaYBlock,
    StreamLayer,
    TCNBlock,
    TransformerBlock,
)
from gordo_tpu.ops.nn import layer_out_dim


def forward_flops_per_sample(spec: ModelSpec) -> float:
    """FLOPs of one forward pass for one sample.

    For windowed models a "sample" is one lookback window of T =
    ``spec.lookback_window`` timesteps; for dense models it is one row.
    """
    T = max(int(spec.lookback_window), 1)
    windowed = T > 1
    in_dim = spec.n_features
    total = 0.0
    seq = windowed  # whether the current tensor still has a time axis
    for layer in spec.layers:
        steps = T if seq else 1
        if isinstance(layer, DenseLayer):
            total += 2.0 * in_dim * layer.units * steps
        elif isinstance(layer, LSTMLayer):
            # 4 gates, each an (in + hidden) x hidden matmul per timestep
            total += 8.0 * (in_dim * layer.units + layer.units**2) * T
            seq = layer.return_sequences
        elif isinstance(layer, TransformerBlock):
            d, ff = layer.d_model, layer.ff_dim
            # QKVO projections: 4 d x d matmuls per token
            total += 8.0 * d * d * T
            # scores (T x d x T) + weighted values (T x T x d), per sequence
            total += 4.0 * T * T * d
            # FFN: d->ff->d per token
            total += 4.0 * d * ff * T
        elif isinstance(layer, MoEBlock):
            d = layer.d_model
            total += 8.0 * d * d * T + 4.0 * T * T * d
            # router + top-1 expert FFN per token
            total += 2.0 * d * layer.num_experts * T
            total += 4.0 * d * layer.expert_dim * T
        elif isinstance(layer, HybridBlock):
            d = layer.d_model
            if layer.operator == "conv":
                # in-projection to 3d and out-projection (the taps are elementwise)
                total += 2.0 * d * 3 * d * T + 2.0 * d * d * T
            else:
                hq = layer.num_heads * layer.head_dim
                hkv = layer.num_kv_heads * layer.head_dim
                total += 2.0 * d * (2 * hq + 2 * hkv) * T
                # causal: half of scores (T x Dh x T) + weighted values a head
                total += 2.0 * T * T * hq
            if layer.ffn == "dense":
                total += 6.0 * d * layer.ff_dim * T
            else:
                # the router, and the held experts at the load an even router
                # gives them: top_k x experts_held / num_experts a token
                load = layer.top_k * layer.experts_held / layer.num_experts
                total += 2.0 * d * layer.num_experts * T
                total += load * 6.0 * d * layer.ff_dim * T
        elif isinstance(layer, LatentBlock):
            d, n, heads = layer.d_model, layer.streams, layer.num_heads
            qk = layer.qk_nope_head_dim + layer.qk_rope_head_dim
            up_kv = layer.qk_nope_head_dim + layer.v_head_dim
            # down- and up-projections of the two latents, and the output's
            total += 2.0 * T * (
                d * layer.q_lora_rank + layer.q_lora_rank * heads * qk
                + d * (layer.kv_lora_rank + layer.qk_rope_head_dim)
                + layer.kv_lora_rank * heads * up_kv
                + heads * layer.v_head_dim * d
            )
            # causal: half of scores (T x qk x T) + weighted values (T x T x v)
            total += T * T * heads * (qk + layer.v_head_dim)
            # the two sublayers' coefficient products; the mixing itself
            # (H_pre X, H_res X, H_post^T y) is counted as products too
            total += 2 * 2.0 * n * d * (2 * n + n * n) * T
            total += 2 * 2.0 * (n + n * n + n) * d * T
            if layer.ffn == "dense":
                total += 6.0 * d * layer.ff_dim * T
            else:
                load = layer.top_k * layer.experts_held / layer.num_experts
                total += 2.0 * d * layer.num_experts * T
                total += (load + layer.shared_experts) * 6.0 * d * layer.ff_dim * T
        elif isinstance(layer, SambaYBlock):
            d, dh, di = layer.d_model, layer.head_dim, layer.d_inner
            hq, hkv = layer.num_heads * dh, layer.num_kv_heads * dh
            total += 6.0 * d * layer.ff_dim * T
            if layer.mixer == "mamba":
                # W_in, W_x, W_dt, W_out (the scan and the taps are elementwise)
                rank = layer.dt_rank
                total += 2.0 * T * (2 * d * di + di * (rank + 2 * layer.d_state) + rank * di + di * d)
            elif layer.mixer == "gmu":
                total += 4.0 * d * di * T
            else:
                total += 2.0 * d * (2 * hq + (2 * hkv if layer.mixer == "attention" else 0)) * T
                # two maps' scores (Dh) and weighted values (2 Dh) a query
                # pair and key: half of T x T causal, less where a window cuts
                w = layer.window
                seen = 0.5 * T * T if not w or w >= T else T * w - 0.5 * w * w
                total += 6.0 * hq * seen
        elif isinstance(layer, TCNBlock):
            # two causal dilated convs (+ a possible 1x1 residual projection)
            k, f = layer.kernel_size, layer.filters
            total += 2.0 * k * in_dim * f * T + 2.0 * k * f * f * T
            if in_dim != f:
                total += 2.0 * in_dim * f * T
        elif isinstance(
            layer, (PoolLayer, PositionalEncoding, RMSNormLayer, LayerNormLayer, StreamLayer)
        ):
            if isinstance(layer, PoolLayer):
                seq = False
        in_dim = layer_out_dim(layer, in_dim)
    return total


# bf16 peak matmul FLOP/s per chip, by jax device_kind substring. Source:
# Google Cloud TPU documentation, the "TPU v5e" / "TPU v5p" / "TPU v6e" /
# "TPU v4" system-architecture pages (v5e: 197 TFLOP/s bf16 — 394 is its
# int8 figure). fp32 compute on TPU routes through the same MXU in several
# bf16 passes — MFU here is always reported against the bf16 peak, the
# hardest denominator.
_PEAK_BF16 = {
    "v6e": 918e12,
    "v6 lite": 918e12,
    "v5p": 459e12,
    "v5e": 197e12,
    "v5 lite": 197e12,
    "v4": 275e12,
    "v3": 123e12,
    "v2": 45e12,
}


def chip_peak_flops(device_kind: str) -> Optional[float]:
    """Peak bf16 FLOP/s for a ``jax.devices()[0].device_kind`` string.

    ``None`` for a CPU device: a host has no datasheet peak, so its MFU is
    "not measured". Any other kind missing from the table raises — an
    accelerator whose peak is unknown must be added with its source, never
    defaulted."""
    kind = (device_kind or "").lower()
    for key, peak in _PEAK_BF16.items():
        if key in kind:
            return peak
    if kind == "cpu":
        return None
    raise ValueError(
        f"no peak FLOP/s on record for device_kind {device_kind!r}; add it "
        f"to gordo_tpu.ops.flops._PEAK_BF16 with its source"
    )


def mfu(
    total_flops: float, wall_sec: float, device_kind: str, n_devices: int = 1
) -> Optional[float]:
    """Model FLOPs utilization in [0, 1] against the HOST's aggregate peak
    (chip peak x device count — a fleet build spreads machines over every
    chip). ``None`` on CPU (no peak to divide by) and for a zero wall."""
    peak = chip_peak_flops(device_kind)
    if not peak or wall_sec <= 0:
        return None
    return total_flops / wall_sec / (peak * max(n_devices, 1))


def spec_param_count(spec: ModelSpec) -> int:
    """Parameter count by the same layer walk (used for sanity checks)."""
    in_dim = spec.n_features
    total = 0
    for layer in spec.layers:
        if isinstance(layer, DenseLayer):
            total += in_dim * layer.units + layer.units
        elif isinstance(layer, LSTMLayer):
            total += 4 * (in_dim * layer.units + layer.units**2 + layer.units)
        elif isinstance(layer, TransformerBlock):
            d = layer.d_model
            total += 4 * d * d + 2 * d * layer.ff_dim
        elif isinstance(layer, MoEBlock):
            d = layer.d_model
            total += 4 * d * d
            total += d * layer.num_experts
            total += layer.num_experts * 2 * d * layer.expert_dim
        elif isinstance(layer, HybridBlock):
            d, f = layer.d_model, layer.ff_dim
            if layer.operator == "conv":
                total += 4 * d * d + d * layer.conv_kernel
            else:
                hq = layer.num_heads * layer.head_dim
                hkv = layer.num_kv_heads * layer.head_dim
                total += 2 * d * hq + 2 * d * hkv + 2 * layer.head_dim
            if layer.ffn == "dense":
                total += 3 * d * f
            else:
                total += (d + 1) * layer.num_experts + layer.experts_held * 3 * d * f
            total += 2 * d
        elif isinstance(layer, LatentBlock):
            d, f, n, heads = layer.d_model, layer.ff_dim, layer.streams, layer.num_heads
            qk = layer.qk_nope_head_dim + layer.qk_rope_head_dim
            total += d * layer.q_lora_rank + layer.q_lora_rank * (1 + heads * qk)
            total += d * (layer.kv_lora_rank + layer.qk_rope_head_dim)
            total += layer.kv_lora_rank * (
                1 + heads * (layer.qk_nope_head_dim + layer.v_head_dim)
            )
            total += heads * layer.v_head_dim * d
            if layer.ffn == "dense":
                total += 3 * d * f
            else:
                total += (d + 1) * layer.num_experts
                total += (layer.experts_held + layer.shared_experts) * 3 * d * f
            total += 2 * d + 2 * (n * d * (2 * n + n * n) + 3 + 2 * n + n * n)
        elif isinstance(layer, SambaYBlock):
            d, dh, di = layer.d_model, layer.head_dim, layer.d_inner
            total += 4 * d + 3 * d * layer.ff_dim
            if layer.mixer == "mamba":
                rank, ds = layer.dt_rank, layer.d_state
                total += d * 2 * di + di * (layer.d_conv + 1)
                total += di * (rank + 2 * ds) + (rank + 1) * di + di * ds + di + di * d
            elif layer.mixer == "gmu":
                total += 2 * d * di
            else:
                kv = 2 * d * layer.num_kv_heads * dh if layer.mixer == "attention" else 0
                total += 2 * d * layer.num_heads * dh + kv + 4 * dh + 2 * dh
        elif isinstance(layer, RMSNormLayer):
            total += in_dim
        elif isinstance(layer, LayerNormLayer):
            total += 2 * in_dim
        elif isinstance(layer, TCNBlock):
            k, f = layer.kernel_size, layer.filters
            total += k * in_dim * f + k * f * f
        in_dim = layer_out_dim(layer, in_dim)
    return total
