"""Pallas TPU kernel: fused MoR tile-mask predictor.

One pass over the activations produces the per-tile liveness mask:
int8 sign matmul (binary rookie) -> fitted line + BN fold (+ optional
per-element residual input) -> AND with the proxy rookie's verdict ->
any() reduction over the tile.  The mask feeds ``gather_matmul`` for the
main matmul, so the predictor runs ahead of the heavy compute exactly
like the paper's binCUs overlap the CUs (§4.1).

The coef table carries SIX rows: [m, b, bn_scale, bn_bias, enable,
res_scale].  Rows 0-4 are per-column; row 5 scales the (M, N)
``residual`` tensor input (1.0 when a residual is attached, 0.0 rows
make the input a no-op) — per-element residual inputs (paper §3.2.1:
"the residual input is added") cannot ride in a per-column table, so
they arrive as a second VMEM input with the same block tiling as the
proxy verdicts.

The ``proxy_neg`` input is tri-state int8: 0/1 = the proxy rookie's
verdict, 2 = forced skip.  State 2 marks both shape padding AND (in the
batched-expert MoE path) capacity-buffer rows holding the zero pad row
— without it the fitted intercept alone can mark pad rows live.  Like
``gather_matmul``, the kernel composes with ``jax.vmap`` over a leading
expert axis (x/w/coef/proxy_neg all (E, ...)-stacked): one trace, one
expert-grid kernel for every expert's predictor pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


N_COEF_ROWS = 6
# each tile verdict is written as one aligned (1, _LANES) row block
_LANES = 128


def _kernel(has_res, x_ref, w_ref, coef_ref, pn_ref, *rest):
    if has_res:
        res_ref, o_ref, acc_ref = rest
    else:
        o_ref, acc_ref = rest
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # ±1 signs as exact bf16 MXU operands (fp32 accumulation keeps the
    # dot exact), compared in fp32 so Mosaic never lays out a packed
    # bf16 mask
    xs = jnp.where(x_ref[...].astype(jnp.float32) > 0, 1.0, -1.0
                   ).astype(jnp.bfloat16)                # act: 0 -> -1
    ws = jnp.where(w_ref[...].astype(jnp.float32) >= 0, 1.0, -1.0
                   ).astype(jnp.bfloat16)                # weight sign bit
    acc_ref[...] += jax.lax.dot_general(
        xs, ws, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _done():
        p_bin = acc_ref[...]
        m, b = coef_ref[0:1, :], coef_ref[1:2, :]
        sc, bi = coef_ref[2:3, :], coef_ref[3:4, :]
        en = coef_ref[4:5, :]
        p_hat = (m * p_bin + b) * sc + bi
        if has_res:
            p_hat = p_hat + coef_ref[5:6, :] * res_ref[...]
        pn = pn_ref[...].astype(jnp.int32)
        # pn: 0 = proxy predicted non-zero, 1 = proxy predicted zero,
        # 2 = padded row/col (forced skip, so padding never marks a tile
        # live — matches the oracle's pad-with-False reduction)
        skip = ((p_hat < 0.0) & (en > 0.5) & (pn == 1)) | (pn > 1)
        live = jnp.where(skip, 0, 1).max(axis=1, keepdims=True).max(
            axis=0, keepdims=True)                       # (1, 1)
        # the tile's verdict fills its aligned (1, 128) output block
        o_ref[0] = jnp.broadcast_to(live, o_ref.shape[1:])


@functools.partial(jax.jit, static_argnames=("tile_m", "tile_n", "bk",
                                             "interpret"))
def mor_tile_mask(x: jax.Array, w: jax.Array, coef: jax.Array,
                  proxy_neg: jax.Array, residual=None, *, tile_m: int = 8,
                  tile_n: int = 128, bk: int = 512,
                  interpret: bool = False) -> jax.Array:
    """x: (M, K); w: (K, N); coef: (6, N) float32 rows = [m, b, bn_scale,
    bn_bias, enable, res_scale]; proxy_neg: (M, N) int8 (0 = proxy
    predicted non-zero, 1 = proxy predicted zero, 2 = padding: forced
    skip); residual: optional (M, N) float32 per-element ReLU-input
    residual (scaled by coef row 5).
    -> (M/tile_m, N/tile_n) int32 tile liveness."""
    M, K = x.shape
    _, N = w.shape
    tile_m, bk, tile_n = min(tile_m, M), min(bk, K), min(tile_n, N)
    assert M % tile_m == 0 and K % bk == 0 and N % tile_n == 0
    assert coef.shape[0] == N_COEF_ROWS
    grid = (M // tile_m, N // tile_n, K // bk)
    has_res = residual is not None
    in_specs = [
        pl.BlockSpec((tile_m, bk), lambda i, j, k: (i, k)),
        pl.BlockSpec((bk, tile_n), lambda i, j, k: (k, j)),
        pl.BlockSpec((N_COEF_ROWS, tile_n), lambda i, j, k: (0, j)),
        pl.BlockSpec((tile_m, tile_n), lambda i, j, k: (i, j)),
    ]
    args = [x, w, coef, proxy_neg]
    if has_res:
        in_specs.append(pl.BlockSpec((tile_m, tile_n),
                                     lambda i, j, k: (i, j)))
        args.append(residual.astype(jnp.float32))
    out = pl.pallas_call(
        functools.partial(_kernel, has_res),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, _LANES), lambda i, j, k: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((grid[0], 1, grid[1] * _LANES),
                                       jnp.int32),
        scratch_shapes=[pltpu.VMEM((tile_m, tile_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*args)
    return out[:, 0, ::_LANES]
