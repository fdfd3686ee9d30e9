"""
The latent-attention / hyper-connection / shared-expert factory (NEW
capability — no reference analog): the layer of the Xing4.0 family
(``model_type`` ``xing4_0``; DeepSeek-V2's attention and expert layer over a
widened residual) as a windowed many-to-one sensor model.

Architecture: Dense projection of the tags to ``d_model`` (in the place of a
token embedding) → the row copied into ``streams`` residual streams → one
:class:`~gordo_tpu.models.spec.LatentBlock` an entry of ``ffns`` → the
streams summed → RMSNorm → last position → Dense head. No positional layer:
RoPE carries the order.
"""

from typing import Any, Dict, Optional, Sequence

from gordo_tpu.models.register import register_model_builder
from gordo_tpu.models.spec import (
    DenseLayer,
    LatentBlock,
    ModelSpec,
    PoolLayer,
    RMSNormLayer,
    StreamLayer,
)
from .feedforward_autoencoder import _optimizer_spec
from .hybrid import held_experts


@register_model_builder(type="TransformerAutoEncoder")
@register_model_builder(type="TransformerForecast")
def latent_moe_model(
    n_features: int,
    n_features_out: int = None,
    lookback_window: int = 144,
    d_model: int = 64,
    ffns: Sequence[str] = ("dense", "routed"),
    ff_dim: int = 256,
    expert_dim: int = 64,
    num_heads: int = 4,
    q_lora_rank: int = 24,
    kv_lora_rank: int = 16,
    qk_nope_head_dim: int = 16,
    qk_rope_head_dim: int = 8,
    v_head_dim: int = 16,
    rope_theta: float = 10000.0,
    rope_scaling: Optional[Dict[str, Any]] = None,
    num_experts: int = 8,
    experts_held: Optional[int] = None,
    expert_offset: int = 0,
    top_k: int = 2,
    shared_experts: int = 1,
    routed_scale: float = 1.0,
    streams: int = 4,
    sinkhorn_iters: int = 20,
    hc_eps: float = 1e-6,
    hc_clamp: float = 30.0,
    norm_eps: float = 1e-6,
    out_func: str = "linear",
    attention: str = "auto",
    optimizer: str = "Adam",
    optimizer_kwargs: Optional[Dict[str, Any]] = None,
    compile_kwargs: Optional[Dict[str, Any]] = None,
    lookahead: int = 0,
    **kwargs,
) -> ModelSpec:
    """Layer ``i`` runs latent attention then ``ffns[i]`` (``dense``: SwiGLU
    of ``ff_dim``; ``routed``: ``top_k`` of ``num_experts`` SwiGLU experts of
    ``expert_dim``, of which this model holds ``experts_held`` from
    ``expert_offset``, all of them by default, beside ``shared_experts`` that
    every token takes), each sublayer through the ``streams`` residual
    streams' mixing. ``rope_scaling`` takes the published YaRN keys
    (``factor``, ``original_max_position_embeddings``, ``beta_fast``,
    ``beta_slow``, ``mscale``, ``mscale_all_dim``); None is plain RoPE."""
    n_features_out = n_features_out or n_features
    if not ffns:
        raise ValueError("ffns names one entry a layer, got none")
    if lookback_window < 2:
        raise ValueError(
            f"latent_moe_model requires lookback_window >= 2, got {lookback_window}"
        )
    if attention not in ("auto", "xla", "flash"):
        raise ValueError(f"attention must be one of auto|xla|flash, got {attention!r}")
    held = held_experts(num_experts, experts_held, expert_offset, top_k)
    unknown = set(ffns) - {"dense", "routed"}
    if unknown:
        raise ValueError(f"unknown layer kinds {sorted(unknown)}; one of dense|routed")
    if qk_rope_head_dim % 2 or streams < 1:
        raise ValueError(
            f"qk_rope_head_dim {qk_rope_head_dim} must be even and streams "
            f"{streams} at least 1"
        )
    yarn = dict(rope_scaling or {})
    if yarn.pop("type", "yarn") != "yarn":
        raise ValueError("rope_scaling is YaRN's (type yarn) or absent")
    rope = dict(
        rope_factor=float(yarn.pop("factor", 1.0)),
        rope_original_max=int(yarn.pop("original_max_position_embeddings", 4096)),
        rope_beta_fast=float(yarn.pop("beta_fast", 32.0)),
        rope_beta_slow=float(yarn.pop("beta_slow", 1.0)),
        rope_mscale=float(yarn.pop("mscale", 1.0)),
        rope_mscale_all_dim=float(yarn.pop("mscale_all_dim", 0.0)),
    )
    if yarn:
        raise ValueError(f"unknown rope_scaling keys {sorted(yarn)}")

    layers = [
        DenseLayer(units=int(d_model), activation="linear"),
        StreamLayer(mode="expand", streams=int(streams)),
    ]
    for ffn in ffns:
        layers.append(
            LatentBlock(
                d_model=int(d_model),
                ffn=ffn,
                ff_dim=int(ff_dim if ffn == "dense" else expert_dim),
                num_heads=int(num_heads),
                q_lora_rank=int(q_lora_rank),
                kv_lora_rank=int(kv_lora_rank),
                qk_nope_head_dim=int(qk_nope_head_dim),
                qk_rope_head_dim=int(qk_rope_head_dim),
                v_head_dim=int(v_head_dim),
                rope_theta=float(rope_theta),
                num_experts=int(num_experts),
                experts_held=held,
                expert_offset=int(expert_offset),
                top_k=int(top_k),
                shared_experts=int(shared_experts),
                routed_scale=float(routed_scale),
                streams=int(streams),
                sinkhorn_iters=int(sinkhorn_iters),
                hc_eps=float(hc_eps),
                hc_clamp=float(hc_clamp),
                norm_eps=float(norm_eps),
                attention_impl=attention,
                **rope,
            )
        )
    layers.append(StreamLayer(mode="collapse", streams=int(streams)))
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
