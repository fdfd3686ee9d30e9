"""Compiles for the chip, without the chip: the TPU's own compiler, for a
described ``v5e:2x2`` device, on the kernels of the main path at real widths.
Nothing runs, so nothing here is a result or a time; what is asserted is
what the compiler made of the program. One file, so that one xdist worker
loads the TPU's library (the on-chip-measurement guide, section 2)."""

import os
import re

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described device is written to the persistent cache
    and cannot be read back without a chip: keep it out."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_grouped_products_and_their_gradients_are_grouped_kernels(one_chip, no_compile_cache):
    """``grouped_matmul`` at ``lfm2_8b_a1b``'s widths (16,384 rows, 8 experts
    of 2,048 x 1,792, bfloat16), differentiated: the product, the rows'
    gradient and the weights' gradient each compile to the compiler's grouped
    kernel, and to no masked dense product over every group (which the rows'
    gradient became when handed the contraction over the weights' last axis:
    eight times the work, PERF.md section 6, PR 35)."""
    import jax
    import jax.numpy as jnp

    from gordo_tpu.ops.nn import grouped_matmul

    rows, d, f, held = 16_384, 2_048, 1_792, 8

    def grads(x, w, sizes, dy):  # a loss whose gradient needs the product itself
        def loss(x, w):
            return jnp.sum((grouped_matmul(x, w, sizes).astype(jnp.float32) - dy) ** 2)

        return jax.grad(loss, argnums=(0, 1))(x, w)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = jax.jit(grads).lower(
        shape((rows, d), jnp.bfloat16), shape((held, d, f), jnp.bfloat16),
        shape((held,), jnp.int32), shape((rows, f), jnp.float32),
    ).compile()
    text = compiled.as_text()
    kernels = re.findall(r"= \S+ custom-call\([^\n]*ragged[^\n]*", text)
    products = [line for line in kernels if "metadata" not in line.split("custom-call")[0]]
    assert len(products) == 3, [line[:120] for line in kernels]
    # nothing beside them multiplies matrices: a masked dense product would
    assert not re.findall(r"= \S+ (?:dot|convolution)\(", text)
    # and the operations the compiler counts are the three products', once
    flops = compiled.cost_analysis().get("flops")
    if flops:
        assert flops < 1.5 * 3 * 2 * rows * d * f


def test_latent_block_compiles_at_the_cells_widths(one_chip, no_compile_cache):
    """One routed layer of ``xing4_0_29b_a4b`` (latent attention with heads of
    192 / 128 on the XLA path, four residual streams, 8 held of 64 experts
    beside the shared one; 16 windows of 256 rows, bfloat16 operands),
    differentiated: the chip's compiler takes it, the grouped products stay the
    compiler's grouped kernels, and the forty normalisations a sublayer are
    unrolled into the program, not a loop of launches."""
    import dataclasses
    import json

    import jax
    import jax.numpy as jnp

    from gordo_tpu.models.factories.latent import latent_moe_model
    from gordo_tpu.ops import nn

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "chipbench", "configs", "xing4_0_29b_a4b.json")) as fh:
        model = dict(json.load(fh)["model"], ffns=["routed"], attention="xla")
    for key in ("kind", "batch_size", "epochs", "compute_dtype"):
        model.pop(key)
    spec = dataclasses.replace(latent_moe_model(8, **model), compute_dtype="bfloat16")
    layer = spec.layers[2]
    assert (layer.qk_nope_head_dim + layer.qk_rope_head_dim, layer.v_head_dim) == (192, 128)

    def loss(params, x):
        return jnp.sum(nn.apply_model(spec, params, x)[0] ** 2)

    shapes = jax.eval_shape(lambda key: nn.init_model_params(key, spec), jax.random.PRNGKey(0))
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), shapes
    )
    x = jax.ShapeDtypeStruct((16, 256, 8), jnp.float32, sharding=one_chip)
    compiled = jax.jit(jax.grad(loss)).lower(shapes, x).compile()
    text = compiled.as_text()
    kernels = re.findall(r"= \S+ custom-call\([^\n]*ragged[^\n]*", text)
    products = [line for line in kernels if "metadata" not in line.split("custom-call")[0]]
    assert len(products) == 9, len(products)  # three a pass: forward, rows' and weights' gradients
    assert " while(" not in text  # Sinkhorn's twenty iterations are unrolled
    # a layer's gradient step beside its 128 M parameters fits a chip several times over
    assert compiled.memory_analysis().temp_size_in_bytes < 6e9
