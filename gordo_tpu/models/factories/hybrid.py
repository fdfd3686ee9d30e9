"""
The hybrid convolution / attention / mixture-of-experts factory (NEW
capability — no reference analog): the layer of the LFM2 family
(``model_type`` ``lfm2_moe``) as a windowed many-to-one sensor model.

Architecture: Dense projection of the tags to ``d_model`` (in the place of
a token embedding) → one :class:`~gordo_tpu.models.spec.HybridBlock` an
entry of ``operators`` / ``ffns`` → RMSNorm → last position → Dense head.
No positional layer: RoPE and the convolutions carry the order.
"""

from typing import Any, Dict, Optional, Sequence

from gordo_tpu.models.register import register_model_builder
from gordo_tpu.models.spec import (
    DenseLayer,
    HybridBlock,
    ModelSpec,
    PoolLayer,
    RMSNormLayer,
)
from .feedforward_autoencoder import _optimizer_spec


def held_experts(
    num_experts: int, experts_held: Optional[int], expert_offset: int, top_k: int
) -> int:
    """How many experts a routed layer holds (all of them by default), once
    the share ``expert_offset`` … ``+ experts_held`` and ``top_k`` are checked
    against the router's ``num_experts``."""
    held = int(num_experts if experts_held is None else experts_held)
    if not 0 < held <= num_experts - expert_offset or expert_offset < 0:
        raise ValueError(
            f"experts {expert_offset}..{expert_offset + held} are not among "
            f"the router's {num_experts}"
        )
    if top_k > num_experts:
        raise ValueError(f"top_k {top_k} exceeds num_experts {num_experts}")
    return held


@register_model_builder(type="TransformerAutoEncoder")
@register_model_builder(type="TransformerForecast")
def hybrid_moe_model(
    n_features: int,
    n_features_out: int = None,
    lookback_window: int = 144,
    d_model: int = 64,
    operators: Sequence[str] = ("conv", "attention"),
    ffns: Sequence[str] = ("dense", "routed"),
    ff_dim: int = 256,
    expert_dim: int = 64,
    num_heads: int = 4,
    num_kv_heads: int = 2,
    head_dim: int = 16,
    rope_theta: float = 1000000.0,
    conv_kernel: int = 3,
    num_experts: int = 8,
    experts_held: Optional[int] = None,
    expert_offset: int = 0,
    top_k: int = 2,
    norm_eps: float = 1e-5,
    out_func: str = "linear",
    attention: str = "auto",
    optimizer: str = "Adam",
    optimizer_kwargs: Optional[Dict[str, Any]] = None,
    compile_kwargs: Optional[Dict[str, Any]] = None,
    lookahead: int = 0,
    **kwargs,
) -> ModelSpec:
    """Layer ``i`` runs ``operators[i]`` (``conv`` | ``attention``) then
    ``ffns[i]`` (``dense``: SwiGLU of ``ff_dim``; ``routed``: ``top_k`` of
    ``num_experts`` SwiGLU experts of ``expert_dim``, of which this model
    holds ``experts_held`` from ``expert_offset``: all of them by default)."""
    n_features_out = n_features_out or n_features
    if len(operators) != len(ffns) or not operators:
        raise ValueError(
            f"operators and ffns name one entry a layer, got {len(operators)} "
            f"and {len(ffns)}"
        )
    if lookback_window < 2:
        raise ValueError(
            f"hybrid_moe_model requires lookback_window >= 2, got {lookback_window}"
        )
    if attention not in ("auto", "xla", "flash"):
        raise ValueError(f"attention must be one of auto|xla|flash, got {attention!r}")
    if num_heads % num_kv_heads:
        raise ValueError(
            f"num_heads {num_heads} is not a multiple of num_kv_heads {num_kv_heads}"
        )
    held = held_experts(num_experts, experts_held, expert_offset, top_k)
    for kinds, allowed in ((operators, ("conv", "attention")), (ffns, ("dense", "routed"))):
        unknown = set(kinds) - set(allowed)
        if unknown:
            raise ValueError(f"unknown layer kinds {sorted(unknown)}; one of {allowed}")

    layers = [DenseLayer(units=int(d_model), activation="linear")]
    for operator, ffn in zip(operators, ffns):
        layers.append(
            HybridBlock(
                d_model=int(d_model),
                operator=operator,
                ffn=ffn,
                ff_dim=int(ff_dim if ffn == "dense" else expert_dim),
                num_heads=int(num_heads),
                num_kv_heads=int(num_kv_heads),
                head_dim=int(head_dim),
                rope_theta=float(rope_theta),
                conv_kernel=int(conv_kernel),
                num_experts=int(num_experts),
                experts_held=held,
                expert_offset=int(expert_offset),
                top_k=int(top_k),
                norm_eps=float(norm_eps),
                attention_impl=attention,
            )
        )
    layers.append(RMSNormLayer(eps=float(norm_eps)))
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
