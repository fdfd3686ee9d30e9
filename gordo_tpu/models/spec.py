"""
Declarative model specifications.

Where the reference's factories build *compiled Keras models*
(gordo/machine/model/factories/), gordo_tpu factories build ``ModelSpec``
values: frozen, hashable descriptions of architecture + optimizer. Specs are
static arguments to jitted training functions, so two machines with the same
spec share one compiled XLA program — the property the batched multi-machine
trainer exploits (bucket by spec, vmap over the parameter stack).
"""

from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple, Union


@dataclass(frozen=True)
class DenseLayer:
    units: int
    activation: str = "linear"
    # l1 activity regularization coefficient (reference applies l1(10e-5) on
    # non-first encoder layers, factories/feedforward_autoencoder.py:78-85)
    l1_activity: float = 0.0


@dataclass(frozen=True)
class LSTMLayer:
    units: int
    activation: str = "tanh"
    recurrent_activation: str = "sigmoid"
    return_sequences: bool = False


@dataclass(frozen=True)
class PositionalEncoding:
    """Parameter-free sinusoidal positional encoding added to a (B, T, D)
    sequence (new capability — the reference has no attention models)."""

    max_wavelength: float = 10000.0


@dataclass(frozen=True)
class TransformerBlock:
    """
    Pre-LayerNorm Transformer encoder block: MHA + residual, FFN + residual.
    Input and output are (B, T, d_model); ``d_model`` must match the incoming
    feature dim (factories insert a Dense projection first).
    """

    d_model: int
    num_heads: int = 4
    ff_dim: int = 128
    activation: str = "relu"
    causal: bool = False
    # attention implementation: auto | xla | flash | ring
    # (ring = sequence-parallel exact attention over the device mesh, for
    # lookback windows too long for one chip — parallel/ring_attention.py)
    attention_impl: str = "auto"
    # one fused (d, 3d) QKV projection instead of three (d, d) matmuls —
    # same math, fewer dispatches. prepare_tp_spec turns it OFF: the concat
    # of column-sharded weights breaks the Megatron comm pattern (measured:
    # all-gathers/all-to-alls appear). Read with getattr(default True) so
    # specs pickled before this field existed keep working.
    fuse_qkv: bool = True


@dataclass(frozen=True)
class MoEBlock:
    """
    Mixture-of-experts Transformer encoder block (new capability — the
    reference has no attention models at all): pre-LN MHA + residual, then a
    Switch-style routed FFN + residual. Each token is routed to its top-1
    expert by a learned router; experts have a hard capacity
    ``ceil(tokens * capacity_factor / num_experts)`` and over-capacity
    tokens pass through unchanged (standard Switch semantics). With
    ``expert_parallel: N`` the expert weights shard over an ``expert`` mesh
    axis (parallel/expert_parallel.py).
    """

    d_model: int
    num_heads: int = 4
    num_experts: int = 8
    expert_dim: int = 128
    capacity_factor: float = 1.25
    activation: str = "relu"
    causal: bool = False
    attention_impl: str = "auto"
    # see TransformerBlock.fuse_qkv (same attention sublayer)
    fuse_qkv: bool = True
    # Switch load-balancing auxiliary loss weight (Fedus et al. §2.2:
    # num_experts * sum_e fraction_routed_e * mean_gate_e). Without it the
    # top-1 router is prone to expert collapse — one hot expert absorbs all
    # tokens and the num_experts/expert_parallel capacity trains unused.
    aux_loss_weight: float = 0.01


@dataclass(frozen=True)
class HybridBlock:
    """
    Pre-RMSNorm block of two residual sublayers, ``x + operator(norm(x))``
    then ``x + ffn(norm(x))`` (the LFM2 family's layer; equations in
    ops/nn.py). ``operator`` is a gated short convolution (``conv``:
    in-projection to 3·d_model, ``c * causal_depthwise_conv(b * u)``,
    out-projection) or grouped-query attention (``attention``: ``num_heads``
    query heads on ``num_kv_heads`` key/value heads of ``head_dim``, RMSNorm a
    head on q and k, RoPE, causal). ``ffn`` is a SwiGLU of width ``ff_dim``
    (``dense``), or ``routed``: a sigmoid router over ``num_experts`` picks
    ``top_k`` by score plus a selection bias, and the layer computes the part
    its own ``experts_held`` SwiGLU experts (ids ``expert_offset`` and up)
    give — every assignment to a held expert, none dropped; what experts held
    elsewhere would add is left out (one chip's share of a layer divided over
    ``num_experts / experts_held`` chips).
    """

    d_model: int
    operator: str = "conv"
    ffn: str = "dense"
    ff_dim: int = 128
    num_heads: int = 4
    num_kv_heads: int = 4
    head_dim: int = 16
    rope_theta: float = 1000000.0
    conv_kernel: int = 3
    num_experts: int = 8
    experts_held: int = 8
    expert_offset: int = 0
    top_k: int = 2
    norm_eps: float = 1e-5
    # attention implementation, as TransformerBlock's: auto | xla | flash
    attention_impl: str = "auto"


@dataclass(frozen=True)
class LatentBlock:
    """
    The layer of the Xing4.0 / DeepSeek-V2 family over ``streams`` residual
    streams (equations in ops/nn.py): two sublayers ``F``, latent attention
    then an FFN, each read and written through manifold-constrained
    hyper-connections (arXiv:2512.24880): ``X <- H_res X + H_post^T
    F(norm(H_pre X))`` on a state ``X`` of ``streams x d_model`` a token,
    ``H_res`` made doubly stochastic by ``sinkhorn_iters`` Sinkhorn
    iterations. Input and output are (streams, batch, time, d_model): a
    :class:`StreamLayer` expands before the first block and collapses after
    the last.

    Latent attention: queries through a rank-``q_lora_rank`` latent, keys and
    values through a rank-``kv_lora_rank`` latent (an RMSNorm inside each),
    ``num_heads`` heads whose queries and keys are ``qk_nope_head_dim +
    qk_rope_head_dim`` wide and whose values are ``v_head_dim`` wide; the
    rotary part of the keys is one head that all heads share; YaRN
    frequencies where ``rope_factor`` > 1. ``ffn`` is a SwiGLU of ``ff_dim``
    (``dense``) or ``routed``: as :class:`HybridBlock`'s (``top_k`` of
    ``num_experts`` by sigmoid score, ``experts_held`` from ``expert_offset``
    computed here, none dropped), the weights times ``routed_scale``, beside
    ``shared_experts`` experts of ``ff_dim`` that every token takes.
    """

    d_model: int
    ffn: str = "dense"
    ff_dim: int = 128
    num_heads: int = 4
    q_lora_rank: int = 24
    kv_lora_rank: int = 16
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    rope_theta: float = 10000.0
    # YaRN (factor 1: plain RoPE, and the five below are not read)
    rope_factor: float = 1.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    num_experts: int = 8
    experts_held: int = 8
    expert_offset: int = 0
    top_k: int = 2
    shared_experts: int = 0
    routed_scale: float = 1.0
    streams: int = 1
    sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: float = 30.0
    norm_eps: float = 1e-6
    # attention implementation, as TransformerBlock's: auto | xla | flash
    attention_impl: str = "auto"


@dataclass(frozen=True)
class SambaYBlock:
    """
    A layer of the SambaY decoder-hybrid-decoder (Phi-4-mini-flash,
    arXiv:2507.06607; equations in ops/nn.py): pre-LayerNorm, ``x + mixer(LN(x))``
    then ``x + SwiGLU(LN(x))`` of width ``ff_dim``, LayerNorm with a bias at
    ``norm_eps``. ``mixer`` is one of

    - ``mamba``: a selective state-space scan (Mamba-1) of ``d_inner``
      channels and ``d_state`` states a channel, a depthwise causal
      convolution of ``d_conv`` taps before it, Δ through a rank-``dt_rank``
      projection;
    - ``attention``: differential attention (arXiv:2410.05258) of
      ``num_heads`` query heads on ``num_kv_heads`` key/value heads of
      ``head_dim``, taken in pairs; causal, and a band of ``window`` rows
      where ``window`` > 0; no positional encoding;
    - ``cross_attention``: the same with queries of its own against the keys
      and values an earlier ``attention`` block keeps;
    - ``gmu``: a gated memory unit, ``(SiLU(h W_1) * m) W_2`` over the memory
      ``m`` an earlier ``mamba`` block keeps.

    ``keeps`` names what the block hands to later blocks: ``memory`` (a
    ``mamba`` block's gated scan output, before its out-projection) or ``kv``
    (an ``attention`` block's keys and values); ``""`` for nothing.
    ``layer_index`` is the published depth, which sets differential
    attention's ``lambda_init``.
    """

    d_model: int
    mixer: str = "mamba"
    ff_dim: int = 128
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 8
    window: int = 0
    d_inner: int = 64
    d_state: int = 4
    d_conv: int = 4
    dt_rank: int = 4
    keeps: str = ""
    layer_index: int = 0
    norm_eps: float = 1e-5

    @property
    def reads(self) -> str:
        """What the block reads of an earlier block's: ``memory`` (a gated
        memory unit), ``kv`` (cross-attention) or nothing (``""``)."""
        return {"gmu": "memory", "cross_attention": "kv"}.get(self.mixer, "")


def check_shared_state(layers) -> None:
    """Refuse layers whose gated memory units or cross-attention blocks have
    no earlier block that keeps what they read (a ``mamba`` block that keeps
    ``memory``, an ``attention`` block that keeps ``kv``), or whose blocks keep
    what their mixer does not make."""
    kept = set()
    for layer in layers:
        if not isinstance(layer, SambaYBlock):
            continue
        if layer.reads and layer.reads not in kept:
            raise ValueError(
                f"SambaYBlock {layer.mixer} reads the {layer.reads} of an "
                f"earlier block, and none keeps it"
            )
        makes = {"mamba": "memory", "attention": "kv"}.get(layer.mixer)
        if layer.keeps and layer.keeps != makes:
            raise ValueError(f"a {layer.mixer} block cannot keep {layer.keeps!r}")
        if layer.keeps:
            kept.add(layer.keeps)


@dataclass(frozen=True)
class LayerNormLayer:
    """``(x - mean) * rsqrt(var + eps) * scale + bias`` over the feature axis."""

    eps: float = 1e-5


@dataclass(frozen=True)
class StreamLayer:
    """``expand``: (batch, time, d) copied into ``streams`` residual streams,
    (streams, batch, time, d); ``collapse``: the streams summed back
    (hyper-connections, arXiv:2409.19606). No parameters."""

    mode: str = "expand"
    streams: int = 1


@dataclass(frozen=True)
class RMSNormLayer:
    """``x * rsqrt(mean(x^2) + eps) * scale`` over the feature axis."""

    eps: float = 1e-5


@dataclass(frozen=True)
class TCNBlock:
    """
    Temporal-convolutional residual block: two causal dilated 1-D convs with
    a residual (1×1-projected when channel counts differ). (B, T, C_in) →
    (B, T, filters).
    """

    filters: int
    kernel_size: int = 3
    dilation: int = 1
    activation: str = "relu"


@dataclass(frozen=True)
class PoolLayer:
    """Collapse the time axis: (B, T, D) → (B, D). mode ∈ {last, mean, max}."""

    mode: str = "last"


LayerSpec = Union[
    DenseLayer,
    LSTMLayer,
    PositionalEncoding,
    TransformerBlock,
    MoEBlock,
    HybridBlock,
    LatentBlock,
    SambaYBlock,
    StreamLayer,
    RMSNormLayer,
    LayerNormLayer,
    TCNBlock,
    PoolLayer,
]


@dataclass(frozen=True)
class OptimizerSpec:
    name: str = "Adam"
    # stored as a sorted tuple of (key, value) pairs to stay hashable
    kwargs: Tuple[Tuple[str, Any], ...] = ()

    @classmethod
    def create(cls, name: str = "Adam", kwargs: Optional[Dict[str, Any]] = None):
        items = tuple(sorted((kwargs or {}).items()))
        return cls(name=name, kwargs=items)

    def as_dict(self) -> Dict[str, Any]:
        return dict(self.kwargs)


@dataclass(frozen=True)
class ModelSpec:
    """
    A full architecture: an ordered tuple of layers plus IO dims, windowing,
    and optimizer/loss configuration.

    ``lookback_window`` / ``lookahead`` carry the timeseries window semantics
    of the reference's LSTM estimators (gordo/machine/model/models.py:461-796);
    dense models use lookback_window=1.
    """

    layers: Tuple[LayerSpec, ...]
    n_features: int
    n_features_out: int
    lookback_window: int = 1
    lookahead: int = 0
    optimizer: OptimizerSpec = field(default_factory=OptimizerSpec)
    loss: str = "mse"
    # activation/matmul dtype inside apply_model ("float32" | "bfloat16");
    # params, loss and outputs stay float32. bfloat16 is the MXU-native
    # precision on TPU
    compute_dtype: str = "float32"
    # shard this model's Transformer weights over an N-chip `model` mesh
    # axis (parallel/tensor_parallel.py). 0/1 = single-device params. Like
    # ring attention, TP models keep off the vmap-over-machines/models paths
    tensor_parallel: int = 0
    # rematerialize sequence layers (LSTM/Transformer/TCN) on the backward
    # pass (jax.checkpoint): activations are recomputed instead of stored,
    # trading FLOPs for HBM — the standard long-window training lever on TPU
    remat: bool = False
    # stream microbatches through the Transformer blocks split into N
    # pipeline stages over a `pipe` mesh axis (parallel/pipeline_parallel.py).
    # 0/1 = off. Pipelined models keep off the vmap paths, like ring/TP
    pipeline_parallel: int = 0
    # shard MoE expert weights over an N-chip `expert` mesh axis
    # (parallel/expert_parallel.py). 0/1 = all experts on every chip
    expert_parallel: int = 0
    # shard THIS machine's training batch over an N-chip `data` mesh axis
    # (parallel/data_parallel.py): params replicated, activations/grads
    # split, one GSPMD gradient all-reduce per step. The within-machine
    # form of the fleet's across-machines data parallelism. 0/1 = off
    data_parallel: int = 0

    @property
    def is_recurrent(self) -> bool:
        return any(isinstance(l, LSTMLayer) for l in self.layers)

    @property
    def output_offset(self) -> int:
        """How many fewer rows the model outputs than it is given
        (= lookback_window - 1 + lookahead for windowed models, 0 for dense)."""
        if self.lookback_window <= 1 and self.lookahead == 0:
            return 0
        return self.lookback_window - 1 + self.lookahead
