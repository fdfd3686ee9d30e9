"""
The state-space / differential-attention / cross-decoder factory (NEW
capability — no reference analog): the layers of the SambaY
decoder-hybrid-decoder (Phi-4-mini-flash, ``model_type`` ``phi4flash``,
arXiv:2507.06607) as a windowed many-to-one sensor model.

Architecture: Dense projection of the tags to ``d_model`` (in the place of
a token embedding) → one :class:`~gordo_tpu.models.spec.SambaYBlock` a held
layer → LayerNorm → last position → Dense head. No positional layer: the
published model has none.

Which mixer a layer runs follows the published layout from its depth ``i``
among ``num_hidden_layers`` (``half`` = ``num_hidden_layers / 2``):

- the self-decoder, ``i < half``: Mamba every ``mb_per_layer``-th layer,
  differential attention over a band of ``sliding_window`` rows between;
- ``i == half``: Mamba, whose gated scan output is kept as the memory;
- ``i == half + 1``: full differential attention, whose keys and values are
  kept;
- the cross-decoder, ``i > half + 1``: a gated memory unit over the memory on
  the even layers, cross-attention over the kept keys and values on the odd.
"""

import math
from typing import Any, Dict, Optional, Sequence

from gordo_tpu.models.register import register_model_builder
from gordo_tpu.models.spec import (
    DenseLayer,
    LayerNormLayer,
    ModelSpec,
    PoolLayer,
    SambaYBlock,
    check_shared_state,
)
from .feedforward_autoencoder import _optimizer_spec


def sambay_layout(i: int, num_hidden_layers: int, mb_per_layer: int, sliding_window: int):
    """``(mixer, window, keeps)`` of published layer ``i``."""
    half = num_hidden_layers // 2
    if i == half:
        return "mamba", 0, "memory"
    if i == half + 1:
        return "attention", 0, "kv"
    if i > half + 1:
        return ("gmu" if i % 2 == 0 else "cross_attention"), 0, ""
    if i % mb_per_layer == 0:
        return "mamba", 0, ""
    return "attention", int(sliding_window), ""


@register_model_builder(type="TransformerAutoEncoder")
@register_model_builder(type="TransformerForecast")
def sambay_model(
    n_features: int,
    n_features_out: int = None,
    lookback_window: int = 144,
    d_model: int = 64,
    num_hidden_layers: int = 8,
    layers_held: Optional[Sequence[int]] = None,
    mb_per_layer: int = 2,
    sliding_window: int = 16,
    ff_dim: int = 256,
    num_heads: int = 4,
    num_kv_heads: int = 2,
    head_dim: Optional[int] = None,
    d_state: int = 16,
    d_conv: int = 4,
    expand: int = 2,
    dt_rank: Optional[int] = None,
    norm_eps: float = 1e-5,
    out_func: str = "linear",
    optimizer: str = "Adam",
    optimizer_kwargs: Optional[Dict[str, Any]] = None,
    compile_kwargs: Optional[Dict[str, Any]] = None,
    lookahead: int = 0,
    **kwargs,
) -> ModelSpec:
    """The published layers ``layers_held`` (0-based, all of them by default)
    of a SambaY model of ``num_hidden_layers``, each a block of its mixer and
    a SwiGLU of ``ff_dim``. Mamba has ``expand x d_model`` channels of
    ``d_state`` states, ``d_conv`` taps and a Δ of rank ``dt_rank``
    (``ceil(d_model / 16)`` by default); attention ``num_heads`` query heads on
    ``num_kv_heads`` key/value heads of ``head_dim`` (``d_model / num_heads`` by
    default), both even. A held layer that reads the memory or the keys and
    values needs the layer that keeps them held too."""
    n_features_out = n_features_out or n_features
    held = list(range(num_hidden_layers) if layers_held is None else layers_held)
    if lookback_window < 2:
        raise ValueError(f"sambay_model requires lookback_window >= 2, got {lookback_window}")
    if num_heads % 2 or num_kv_heads % 2 or num_heads % num_kv_heads:
        raise ValueError(
            f"differential attention pairs its heads: num_heads {num_heads} and "
            f"num_kv_heads {num_kv_heads} must be even, the first a multiple of "
            f"the second"
        )
    if not held or any(not 0 <= i < num_hidden_layers for i in held) or held != sorted(set(held)):
        raise ValueError(
            f"layers_held {held} are not distinct rising layers of {num_hidden_layers}"
        )
    head_dim = int(head_dim or d_model // num_heads)
    dt_rank = int(dt_rank or math.ceil(d_model / 16))

    layers = [DenseLayer(units=int(d_model), activation="linear")]
    for i in held:
        mixer, window, keeps = sambay_layout(i, num_hidden_layers, mb_per_layer, sliding_window)
        layers.append(
            SambaYBlock(
                d_model=int(d_model),
                mixer=mixer,
                ff_dim=int(ff_dim),
                num_heads=int(num_heads),
                num_kv_heads=int(num_kv_heads),
                head_dim=head_dim,
                window=window,
                d_inner=int(expand * d_model),
                d_state=int(d_state),
                d_conv=int(d_conv),
                dt_rank=dt_rank,
                keeps=keeps,
                layer_index=int(i),
                norm_eps=float(norm_eps),
            )
        )
    check_shared_state(layers[1:])
    layers.append(LayerNormLayer(eps=float(norm_eps)))
    layers.append(PoolLayer(mode="last"))
    layers.append(DenseLayer(units=int(n_features_out), activation=out_func))

    loss = (compile_kwargs or {}).get("loss", "mse")
    return ModelSpec(
        layers=tuple(layers),
        n_features=int(n_features),
        n_features_out=int(n_features_out),
        lookback_window=int(lookback_window),
        lookahead=int(lookahead),
        optimizer=_optimizer_spec(optimizer, optimizer_kwargs),
        loss=loss,
    )
