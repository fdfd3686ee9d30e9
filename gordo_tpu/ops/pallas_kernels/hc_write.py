"""
The residual streams' write after a sublayer, ``X'_i = sum_j H_res[i, j] X_j
+ H_post[i] y`` (arXiv:2512.24880 section 3), as one pass over the streams in
each direction.

Design:
- Tokens flattened: the streams are (n, N, d), the sublayer's output y is
  (N, d), the coefficients are token-major, ``H_res`` as (N, n * n) and
  ``H_post`` as (N, n) in float32 (a token's coefficients are one row, so a
  coefficient is a (tile, 1) column that broadcasts along the lanes).
- Grid: (N // TOKEN_TILE, d // lane tile). A step holds the n streams of its
  tile, y's tile and the tile's n * n + n coefficients in VMEM, forms the n
  outputs in float32 and rounds each once to the streams' dtype: X and y read
  once, X' written once (2 + 1/n times X's bytes with n streams).
- Backward, over the same tiles: reads g = dX', X and y once and writes
  ``dX_j = sum_i H_res[i, j] g_i`` and ``dy = sum_i H_post[i] g_i`` in their
  dtypes, and the coefficients' gradients ``<g_i, X_j>`` and ``<g_i, y>``,
  per token, in float32: a step's partial sums over its lanes, added over the
  lane tiles (the grid's last, sequential axis) into one output block a token
  tile. The residuals are X, y and the coefficients: nothing of X's size in
  float32 is kept between the passes.

The kernels compile through Mosaic and therefore need a TPU backend. A
caller that wants the Pallas interpreter (the CPU tests) asks for it with
``interpret=True``; nothing selects it on the caller's behalf. Where the
shapes do not tile (:func:`tiles`), the caller keeps its jnp body.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TOKEN_TILE = 256
# lane tiles, widest first: a step's blocks stay a few MiB at any stream count
# a model uses, inside the default scoped VMEM
LANE_TILES = (512, 256, 128)


def tiles(n_tokens: int, d: int) -> bool:
    """Whether the kernel takes streams of ``n_tokens`` tokens of width
    ``d``: whole token tiles and a width of whole 128-lane vectors."""
    return n_tokens % TOKEN_TILE == 0 and d % 128 == 0


def _lane_tile(d: int) -> int:
    return next(tile for tile in LANE_TILES if d % tile == 0)


def _column(block, k: int):
    """Coefficient ``k`` of each token of a token-major block: (tile, 1)."""
    return block[:, k : k + 1]


def _write_kernel(res_ref, post_ref, x_ref, y_ref, out_ref):
    n = x_ref.shape[0]
    res, post = res_ref[...], post_ref[...]
    xs = [x_ref[j].astype(jnp.float32) for j in range(n)]
    y = y_ref[...].astype(jnp.float32)
    for i in range(n):
        acc = _column(res, i * n) * xs[0]
        for j in range(1, n):
            acc = acc + _column(res, i * n + j) * xs[j]
        out_ref[i] = (acc + _column(post, i) * y).astype(out_ref.dtype)


def _write_bwd_kernel(res_ref, post_ref, x_ref, y_ref, g_ref,
                      dx_ref, dy_ref, dres_ref, dpost_ref):
    n = x_ref.shape[0]
    res, post = res_ref[...], post_ref[...]
    gs = [g_ref[i].astype(jnp.float32) for i in range(n)]
    y = y_ref[...].astype(jnp.float32)
    for j in range(n):
        acc = _column(res, j) * gs[0]
        for i in range(1, n):
            acc = acc + _column(res, i * n + j) * gs[i]
        dx_ref[j] = acc.astype(dx_ref.dtype)
    acc = _column(post, 0) * gs[0]
    for i in range(1, n):
        acc = acc + _column(post, i) * gs[i]
    dy_ref[...] = acc.astype(dy_ref.dtype)

    # <g_i, X_j> and <g_i, y> over this step's lanes, one column a
    # coefficient: placed by a select on the lane index, which Mosaic takes
    # where a store into one lane of a block is not assured
    tt = res.shape[0]
    lane_res = jax.lax.broadcasted_iota(jnp.int32, (tt, n * n), 1)
    lane_post = jax.lax.broadcasted_iota(jnp.int32, (tt, n), 1)
    dres = jnp.zeros((tt, n * n), jnp.float32)
    dpost = jnp.zeros((tt, n), jnp.float32)
    for j in range(n):
        xj = x_ref[j].astype(jnp.float32)
        for i in range(n):
            dot = jnp.sum(gs[i] * xj, axis=-1, keepdims=True)
            dres = jnp.where(lane_res == i * n + j, dot, dres)
    for i in range(n):
        dot = jnp.sum(gs[i] * y, axis=-1, keepdims=True)
        dpost = jnp.where(lane_post == i, dot, dpost)

    @pl.when(pl.program_id(1) == 0)
    def _():
        dres_ref[...] = dres
        dpost_ref[...] = dpost

    @pl.when(pl.program_id(1) > 0)
    def _():
        dres_ref[...] += dres
        dpost_ref[...] += dpost


def _specs(n: int, n_tokens: int, d: int):
    """Block specs of the coefficients, the streams and y, and the grid."""
    td = _lane_tile(d)
    tokens = lambda t, c: (t, 0)
    return (
        pl.BlockSpec((TOKEN_TILE, n * n), tokens),
        pl.BlockSpec((TOKEN_TILE, n), tokens),
        pl.BlockSpec((n, TOKEN_TILE, td), lambda t, c: (0, t, c)),
        pl.BlockSpec((TOKEN_TILE, td), lambda t, c: (t, c)),
    ), (n_tokens // TOKEN_TILE, d // td)


def _forward(res, post, x, y, interpret: bool):
    """Token-major coefficients (N, n * n), (N, n); x (n, N, d); y (N, d)."""
    n, n_tokens, d = x.shape
    (res_s, post_s, x_s, y_s), grid = _specs(n, n_tokens, d)
    return pl.pallas_call(
        _write_kernel,
        grid=grid,
        in_specs=[res_s, post_s, x_s, y_s],
        out_specs=x_s,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=interpret,
    )(res, post, x, y)


def _backward(res, post, x, y, g, interpret: bool):
    n, n_tokens, d = x.shape
    (res_s, post_s, x_s, y_s), grid = _specs(n, n_tokens, d)
    return pl.pallas_call(
        _write_bwd_kernel,
        grid=grid,
        in_specs=[res_s, post_s, x_s, y_s, x_s],
        out_specs=[x_s, y_s, res_s, post_s],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(y.shape, y.dtype),
            jax.ShapeDtypeStruct(res.shape, jnp.float32),
            jax.ShapeDtypeStruct(post.shape, jnp.float32),
        ],
        # the coefficients' gradients add up over the lane tiles
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(res, post, x, y, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _hc_write(res, post, x, y, interpret):
    return _forward(res, post, x, y, interpret)


def _hc_write_fwd(res, post, x, y, interpret):
    return _forward(res, post, x, y, interpret), (res, post, x, y)


def _hc_write_bwd(interpret, residuals, g):
    res, post, x, y = residuals
    dx, dy, dres, dpost = _backward(res, post, x, y, g, interpret)
    return dres.astype(res.dtype), dpost.astype(post.dtype), dx, dy


_hc_write.defvjp(_hc_write_fwd, _hc_write_bwd)


def hc_write(res, post, x, y, interpret: bool = False):
    """``H_res X + H_post^T y``: ``res`` (n, n, B, T), ``post`` (n, B, T),
    the streams ``x`` (n, B, T, d), the sublayer's output ``y`` (B, T, d) →
    the streams after the sublayer, (n, B, T, d) in ``x``'s dtype. The
    products and sums in float32, one rounding. The shapes must tile
    (:func:`tiles`).

    Compiled unless the caller passes ``interpret=True``: on a backend
    without Mosaic the call raises instead of running interpreted.
    """
    n, b, t, d = x.shape
    if not tiles(b * t, d):
        raise ValueError(f"hc_write takes whole tiles: {b * t} tokens of width {d}")
    res_tm = res.reshape(n * n, b * t).T.astype(jnp.float32)
    post_tm = post.reshape(n, b * t).T.astype(jnp.float32)
    out = _hc_write(res_tm, post_tm, x.reshape(n, b * t, d), y.reshape(b * t, d), interpret)
    return out.reshape(x.shape)
