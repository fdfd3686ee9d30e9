"""
Transformer/TCN model families + attention ops (new capability — the
reference zoo stops at LSTMs, SURVEY.md §5 "long-context: absent").
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gordo_tpu.models import models
from gordo_tpu.models.factories import tcn_model, transformer_model
from gordo_tpu.models.spec import (
    ModelSpec,
    PoolLayer,
    TCNBlock,
    TransformerBlock,
    DenseLayer,
    PositionalEncoding,
)
from gordo_tpu.ops import nn
from gordo_tpu.ops.attention import (
    dot_product_attention_xla,
    multihead_attention,
)
from gordo_tpu.ops.pallas_kernels import flash_attention
from gordo_tpu.serializer import from_definition, into_definition


# ---------------------------------------------------------------- attention
@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_matches_reference(causal):
    rng = np.random.RandomState(0)
    q, k, v = (
        jnp.asarray(rng.randn(2, 256, 8).astype(np.float32)) for _ in range(3)
    )
    ref = dot_product_attention_xla(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_flash_attention_grad_matches_reference():
    rng = np.random.RandomState(1)
    q, k, v = (
        jnp.asarray(rng.randn(1, 128, 8).astype(np.float32)) for _ in range(3)
    )

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(dot_product_attention_xla(q, k, v, causal=True) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_flash_attention_never_picks_the_interpreter_itself():
    """Off the TPU the kernel cannot compile. Without ``interpret=True`` the
    call must fail there — never fall into the interpreter on its own — and
    so must ``attention: flash`` reached through the dispatcher."""
    from gordo_tpu.ops.attention import dot_product_attention

    q = jnp.zeros((1, 2, 128, 8), jnp.float32)
    assert jax.default_backend() == "cpu"
    with pytest.raises(ValueError, match="interpret mode"):
        flash_attention(q, q, q)
    with pytest.raises(ValueError, match="interpret mode"):
        dot_product_attention(q, q, q, impl="flash")
    # the caller who asks for the interpreter gets it
    assert flash_attention(q, q, q, interpret=True).shape == q.shape


def test_multihead_attention_shapes_and_heads():
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(3, 16, 32).astype(np.float32))
    out = multihead_attention(x, x, x, num_heads=4)
    assert out.shape == (3, 16, 32)
    with pytest.raises(ValueError):
        multihead_attention(x, x, x, num_heads=5)


# ------------------------------------------------------------------ factories
def test_transformer_factory_spec():
    spec = transformer_model(
        n_features=6, lookback_window=32, d_model=16, num_heads=2, num_blocks=3
    )
    assert isinstance(spec, ModelSpec)
    assert spec.lookback_window == 32
    blocks = [l for l in spec.layers if isinstance(l, TransformerBlock)]
    assert len(blocks) == 3
    assert all(b.d_model == 16 and b.num_heads == 2 for b in blocks)
    assert isinstance(spec.layers[0], DenseLayer) and spec.layers[0].units == 16
    assert isinstance(spec.layers[1], PositionalEncoding)
    assert isinstance(spec.layers[-2], PoolLayer)
    assert spec.layers[-1].units == 6
    # frozen + hashable → usable as a jit static arg / bucket key
    assert hash(spec) == hash(
        transformer_model(
            n_features=6, lookback_window=32, d_model=16, num_heads=2, num_blocks=3
        )
    )


def test_tcn_factory_spec_dilations():
    spec = tcn_model(n_features=4, lookback_window=16, filters=8, num_blocks=3)
    blocks = [l for l in spec.layers if isinstance(l, TCNBlock)]
    assert [b.dilation for b in blocks] == [1, 2, 4]


def test_factories_reject_degenerate_configs():
    with pytest.raises(ValueError):
        tcn_model(n_features=4, num_blocks=0)
    with pytest.raises(ValueError):
        tcn_model(n_features=4, dilations=())
    with pytest.raises(ValueError):
        transformer_model(n_features=4, lookback_window=1)
    with pytest.raises(ValueError):
        models.TransformerAutoEncoder(kind="transformer_model", lookback_window=1)


def test_sequence_estimators_default_lookback_window():
    model = models.TCNAutoEncoder(kind="tcn_model")
    assert model.lookback_window == 144


def test_ops_attention_importable_standalone():
    """gordo_tpu.ops.attention as a process's first gordo_tpu import must not
    trip the ops ↔ models import cycle."""
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable, "-c", "import gordo_tpu.ops.attention; print('ok')"],
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    assert "ok" in proc.stdout


# ---------------------------------------------------------------- causality
def _sequence_output(layers, n_features, x):
    spec = ModelSpec(
        layers=layers, n_features=n_features, n_features_out=n_features
    )
    params = nn.init_model_params(jax.random.PRNGKey(0), spec)
    out, _ = nn.apply_model(spec, params, jnp.asarray(x))
    return np.asarray(out)


@pytest.mark.parametrize(
    "layers",
    [
        (TCNBlock(filters=8, kernel_size=3, dilation=2),),
        (
            DenseLayer(units=8),
            TransformerBlock(d_model=8, num_heads=2, ff_dim=16, causal=True),
        ),
    ],
    ids=["tcn", "transformer-causal"],
)
def test_causal_layers_ignore_future(layers):
    rng = np.random.RandomState(3)
    x = rng.randn(1, 12, 4).astype(np.float32)
    out_a = _sequence_output(layers, 4, x)
    x_perturbed = x.copy()
    x_perturbed[:, 8:, :] += 10.0  # change only the future
    out_b = _sequence_output(layers, 4, x_perturbed)
    np.testing.assert_allclose(out_a[:, :8], out_b[:, :8], atol=1e-5)
    assert not np.allclose(out_a[:, 8:], out_b[:, 8:])


# --------------------------------------------------------------- estimators
@pytest.mark.parametrize(
    "cls,kind,lookahead",
    [
        (models.TransformerAutoEncoder, "transformer_model", 0),
        (models.TransformerForecast, "transformer_model", 1),
        (models.TCNAutoEncoder, "tcn_model", 0),
        (models.TCNForecast, "tcn_model", 1),
    ],
)
def test_estimator_fit_predict_window_semantics(cls, kind, lookahead):
    rng = np.random.RandomState(4)
    X = rng.rand(40, 3).astype(np.float32)
    model = cls(
        kind=kind,
        lookback_window=8,
        batch_size=16,
        epochs=1,
        d_model=8,
        num_heads=2,
        ff_dim=16,
        num_blocks=1,
        filters=8,
    )
    model.fit(X, X)
    out = model.predict(X)
    assert out.shape == (40 - 8 + 1 - lookahead, 3)
    assert np.all(np.isfinite(out))
    assert isinstance(model.score(X, X), float)


def test_transformer_training_reduces_loss():
    rng = np.random.RandomState(5)
    t = np.linspace(0, 20 * np.pi, 300)
    X = np.stack([np.sin(t), np.cos(t)], axis=1).astype(np.float32)
    model = models.TransformerAutoEncoder(
        kind="transformer_model",
        lookback_window=16,
        batch_size=32,
        epochs=15,
        d_model=16,
        num_heads=2,
        ff_dim=32,
        num_blocks=1,
    )
    model.fit(X, X)
    losses = model.history["loss"]
    assert losses[-1] < losses[0] * 0.7


# -------------------------------------------------------------- serializer
def test_transformer_round_trips_through_definition():
    definition = {
        "gordo_tpu.models.models.TransformerAutoEncoder": {
            "kind": "transformer_model",
            "lookback_window": 12,
            "d_model": 8,
            "num_heads": 2,
            "epochs": 1,
        }
    }
    model = from_definition(definition)
    assert isinstance(model, models.TransformerAutoEncoder)
    assert model.lookback_window == 12
    round_tripped = into_definition(model)
    assert from_definition(round_tripped).get_params() == model.get_params()


def test_pickle_fitted_tcn():
    import pickle

    rng = np.random.RandomState(6)
    X = rng.rand(30, 2).astype(np.float32)
    model = models.TCNAutoEncoder(
        kind="tcn_model", lookback_window=4, epochs=1, filters=4, num_blocks=2
    )
    model.fit(X, X)
    clone = pickle.loads(pickle.dumps(model))
    np.testing.assert_allclose(clone.predict(X), model.predict(X), atol=1e-6)


def test_flash_attention_rejects_cross_length_kv():
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(1, 128, 8).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 192, 8).astype(np.float32))
    with pytest.raises(ValueError, match="equal Q/K/V sequence lengths"):
        flash_attention(q, k, k, interpret=True)


def test_ring_impl_matches_xla_from_config():
    """attention=ring on the Transformer factory routes through the
    sequence-parallel ring and matches the xla path numerically."""
    from gordo_tpu.ops.attention import dot_product_attention

    rng = np.random.RandomState(11)
    q = jnp.asarray(rng.randn(2, 4, 64, 8).astype(np.float32))
    k = jnp.asarray(rng.randn(2, 4, 64, 8).astype(np.float32))
    v = jnp.asarray(rng.randn(2, 4, 64, 8).astype(np.float32))
    ring = dot_product_attention(q, k, v, causal=True, impl="ring")
    xla = dot_product_attention(q, k, v, causal=True, impl="xla")
    np.testing.assert_allclose(np.asarray(ring), np.asarray(xla), atol=1e-5)

    # and end-to-end from a model definition
    spec = transformer_model(
        4, lookback_window=64, d_model=16, num_heads=2, num_blocks=1,
        attention="ring",
    )
    assert all(
        blk.attention_impl == "ring"
        for blk in spec.layers
        if hasattr(blk, "attention_impl")
    )
    model = models.TransformerAutoEncoder(
        kind="transformer_model", lookback_window=64, d_model=16, num_heads=2,
        ff_dim=32, num_blocks=1, attention="ring", epochs=1, batch_size=8,
    )
    X = np.random.RandomState(3).rand(80, 4).astype(np.float32)
    model.fit(X, X)
    assert np.all(np.isfinite(model.predict(X)))


def test_ring_machines_take_serial_path():
    from gordo_tpu.machine import Machine
    from gordo_tpu.parallel.batch_trainer import _plan_machine

    cfg = {
        "name": "ring-m",
        "dataset": {
            "type": "RandomDataset",
            "tags": ["r-0", "r-1", "r-2", "r-3"],
            "train_start_date": "2019-01-01T00:00:00+00:00",
            "train_end_date": "2019-01-03T00:00:00+00:00",
        },
        "model": {
            "gordo_tpu.models.models.TransformerAutoEncoder": {
                "kind": "transformer_model",
                "lookback_window": 64,
                "attention": "ring",
            }
        },
    }
    assert _plan_machine(Machine.from_config(cfg, project_name="t")) is None
    cfg["model"]["gordo_tpu.models.models.TransformerAutoEncoder"]["attention"] = "auto"
    assert _plan_machine(Machine.from_config(cfg, project_name="t")) is not None


def test_fused_qkv_matches_unfused_and_tp_disables_it():
    """The fused (d, 3d) QKV projection is bit-equivalent math to the three
    separate matmuls, and prepare_tp_spec turns it off — the concat of
    column-sharded weights would break the Megatron comm pattern."""
    import dataclasses

    spec = transformer_model(n_features=4, lookback_window=16)
    params = nn.init_model_params(jax.random.PRNGKey(0), spec)
    x = jnp.asarray(np.random.RandomState(0).rand(3, 16, 4), jnp.float32)
    out_fused, _ = nn.apply_model(spec, params, x)

    unfused_layers = tuple(
        dataclasses.replace(l, fuse_qkv=False)
        if isinstance(l, TransformerBlock) else l
        for l in spec.layers
    )
    spec_unfused = dataclasses.replace(spec, layers=unfused_layers)
    out_unfused, _ = nn.apply_model(spec_unfused, params, x)
    np.testing.assert_allclose(
        np.asarray(out_fused), np.asarray(out_unfused), rtol=1e-6, atol=1e-6
    )

    # TP pins fusion off on every block (and a pre-field pickle defaults on)
    from gordo_tpu.parallel.tensor_parallel import prepare_tp_spec

    tp_spec = prepare_tp_spec(
        dataclasses.replace(
            transformer_model(n_features=4, lookback_window=16, num_heads=4),
            tensor_parallel=4,
        )
    )
    blocks = [l for l in tp_spec.layers if isinstance(l, TransformerBlock)]
    assert blocks and all(not b.fuse_qkv for b in blocks)


def test_causal_conv_matmul_form_matches_conv_general_dilated():
    """The TCN causal conv is implemented as k shifted matmuls (XLA CPU has
    no fast dilated-conv path — measured ~32x slower — and matmuls are the
    MXU's native op). Pin it against lax.conv_general_dilated."""
    rng = np.random.RandomState(0)
    for dilation in (1, 2, 4, 8):
        x = jnp.asarray(rng.rand(3, 50, 5).astype(np.float32))
        w = jnp.asarray(rng.rand(3, 5, 7).astype(np.float32))
        ref = jax.lax.conv_general_dilated(
            x, w, window_strides=(1,),
            padding=[((w.shape[0] - 1) * dilation, 0)],
            rhs_dilation=(dilation,),
            dimension_numbers=("NWC", "WIO", "NWC"),
        )
        got = nn._causal_conv1d(x, w, dilation)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
        )


def test_causal_conv1d_matches_lax_conv_and_short_windows():
    """The no-pad post-shift causal conv (one clean GEMM + fused shifted
    adds; round-5 CPU fast-path rework) must match XLA's own dilated conv
    bit-for-bit in f32, including sequences SHORTER than the receptive
    field (taps whose whole output precedes the series start contribute
    zero)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gordo_tpu.ops.nn import _causal_conv1d

    rng = np.random.RandomState(7)
    for t, dilation in [(144, 1), (144, 8), (16, 8), (3, 2), (1, 4)]:
        x = jnp.asarray(rng.standard_normal((2, t, 5)), jnp.float32)
        kernel = jnp.asarray(rng.standard_normal((3, 5, 4)), jnp.float32)
        got = _causal_conv1d(x, kernel, dilation)
        ref = jax.lax.conv_general_dilated(
            x,
            kernel,
            window_strides=(1,),
            padding=[((kernel.shape[0] - 1) * dilation, 0)],
            rhs_dilation=(dilation,),
            dimension_numbers=("NWC", "WIO", "NWC"),
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5,
            err_msg=f"t={t} dilation={dilation}",
        )


def test_flash_attention_lowers_through_mosaic_for_tpu():
    """The interpret-mode tests above prove the kernel's MATH; this proves
    its TILING. jax.export with platforms=["tpu"] runs the real Mosaic
    lowering on a CPU host — which round 5 found rejecting the kernel
    outright (the flat (1, block_q) lse output block violates the (8, 128)
    tile rule; lse/delta are now lane-replicated). Any future block-spec
    edit that breaks TPU lowering fails here, in CI, without a TPU."""
    import jax
    import jax.numpy as jnp
    from jax import export

    q = jnp.zeros((2, 4, 512, 64), jnp.float32)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    def grads(q, k, v):
        return jax.grad(
            lambda a, b, c: jnp.sum(fwd(a, b, c) ** 2), argnums=(0, 1, 2)
        )(q, k, v)

    fwd_mlir = export.export(jax.jit(fwd), platforms=["tpu"])(
        q, q, q
    ).mlir_module()
    assert fwd_mlir.count("tpu_custom_call") == 1
    bwd_mlir = export.export(jax.jit(grads), platforms=["tpu"])(
        q, q, q
    ).mlir_module()
    # fwd kernel + dq kernel + fused dk/dv kernel
    assert bwd_mlir.count("tpu_custom_call") == 3

    # bfloat16 — the windowed fleets' TPU compute dtype — has DIFFERENT
    # minimum tiles ((16, 128) vs f32's (8, 128)), so its lowering is a
    # separate thing to prove
    qb = jnp.zeros((2, 4, 512, 64), jnp.bfloat16)
    bf16_mlir = export.export(jax.jit(grads), platforms=["tpu"])(
        qb, qb, qb
    ).mlir_module()
    assert bf16_mlir.count("tpu_custom_call") == 3


def test_flash_dispatch_gate_is_the_rectangle_proven_on_the_chip(monkeypatch):
    """``auto`` admits exactly the (T, head dim) rectangle whose corners
    ``chip_smoke.py`` compiles on the chip — and nothing off the TPU."""
    import jax.numpy as jnp

    import chip_smoke
    from gordo_tpu.ops import attention

    def ok(t, dh, t_k=None):
        q = jnp.zeros((1, 2, t, dh), jnp.float32)
        k = jnp.zeros((1, 2, t_k or t, dh), jnp.float32)
        return attention._flash_ok(q, k)

    assert not ok(512, 64)  # the tests' backend is the CPU
    monkeypatch.setattr(attention.jax, "default_backend", lambda: "tpu")
    assert all(ok(t, dh) for t, dh in chip_smoke.FLASH_CORNERS)
    assert set(chip_smoke.FLASH_CORNERS) == {
        (256, 64), (256, 128), (4096, 64), (4096, 128)
    }
    assert ok(512, 64) and ok(1024, 128)
    # outside the rectangle in each direction
    assert not ok(128, 64) and not ok(8192, 64)
    assert not ok(512, 16) and not ok(512, 32) and not ok(512, 256)
    # the head dim is the kernel's lane dimension: only the widths the chip
    # compiled, nothing in between
    assert not ok(2048, 96) and not ok(512, 72) and not ok(512, 120)
    assert not ok(300, 64)           # not a multiple of the 128-row blocks
    assert not ok(512, 64, t_k=256)  # cross-length attention


def test_flash_attention_bfloat16_matches_reference():
    """bf16 inputs with f32 accumulators: within bf16 tolerance of the XLA
    reference (the windowed fleets' TPU compute dtype)."""
    rng = np.random.RandomState(3)
    q, k, v = (
        jnp.asarray(rng.standard_normal((2, 2, 256, 32)), jnp.float32).astype(
            jnp.bfloat16
        )
        for _ in range(3)
    )
    raw = flash_attention(q, k, v, causal=True, interpret=True)
    # output stays at the input dtype; accumulation is f32 inside
    assert raw.dtype == jnp.bfloat16
    ref = dot_product_attention_xla(q, k, v, causal=True).astype(jnp.float32)
    got = raw.astype(jnp.float32)
    assert ref.shape == got.shape
    rel = float(
        jnp.max(jnp.abs(ref - got)) / (jnp.max(jnp.abs(ref)) + 1e-9)
    )
    assert rel < 2e-2, rel
