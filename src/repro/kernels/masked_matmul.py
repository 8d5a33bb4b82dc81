"""Pallas TPU kernel: matmul with tile-granular output skipping.

The MoR tile mask (scalar-prefetched into SMEM) gates the MXU work for
each (row-block x 128-col) output tile: dead tiles write zeros without
issuing dot products.  This is the compute-skip half of the paper's
benefit; the DMA-skip half needs the compacted variant
(``gather_matmul``), because block DMAs declared via BlockSpec are
unconditional under a static grid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(mask_ref, x_ref, w_ref, o_ref, acc_ref):
    j, k = pl.program_id(1), pl.program_id(2)
    live = mask_ref[pl.program_id(0) * pl.num_programs(1) + j] != 0

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _mac():
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("tile_m", "tile_n", "bk", "interpret",
                                    "return_counts"))
def masked_matmul(x: jax.Array, w: jax.Array, tile_mask: jax.Array, *,
                  tile_m: int = 128, tile_n: int = 128, bk: int = 512,
                  interpret: bool = False, return_counts: bool = False):
    """x: (M, K) @ w: (K, N) with (M/tile_m, N/tile_n) bool tile mask.

    ``return_counts`` additionally returns the live-tile count — the
    liveness counter for the compute-skip path (gather_matmul's
    counters are the ones the executor wires into serving telemetry)."""
    M, K = x.shape
    _, N = w.shape
    tile_m, bk, tile_n = min(tile_m, M), min(bk, K), min(tile_n, N)
    assert M % tile_m == 0 and K % bk == 0 and N % tile_n == 0
    grid = (M // tile_m, N // tile_n, K // bk)
    assert tile_mask.shape == (grid[0], grid[1]), (tile_mask.shape, grid)
    mask_flat = tile_mask.reshape(-1).astype(jnp.int32)
    if return_counts:
        out = masked_matmul(x, w, tile_mask, tile_m=tile_m, tile_n=tile_n,
                            bk=bk, interpret=interpret)
        return out, jnp.sum(mask_flat)
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((tile_m, bk), lambda i, j, k, m_ref: (i, k)),
                pl.BlockSpec((bk, tile_n), lambda i, j, k, m_ref: (k, j)),
            ],
            out_specs=pl.BlockSpec((tile_m, tile_n),
                                   lambda i, j, k, m_ref: (i, j)),
            scratch_shapes=[pltpu.VMEM((tile_m, tile_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(mask_flat, x, w)


def _kernel_kdim(mask_ref, x_ref, w_ref, o_ref, acc_ref):
    i, k = pl.program_id(0), pl.program_id(2)
    live = mask_ref[i * pl.num_programs(2) + k] != 0

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _mac():
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("tile_m", "tile_k", "bn", "interpret"))
def masked_matmul_kdim(x: jax.Array, w: jax.Array, tile_mask: jax.Array, *,
                       tile_m: int = 8, tile_k: int = 128, bn: int = 128,
                       interpret: bool = False) -> jax.Array:
    """x: (M, K) @ w: (K, N) skipping dead CONTRACTION blocks.

    ``tile_mask``: (M/tile_m, K/tile_k) bool — the MoR down-projection
    mask: tile_mask[i, k] == 0 means rows [k*tile_k, (k+1)*tile_k) of
    ``x`` block-row i are known-zero (a dead FFN hidden tile), so the
    accumulation for that (i, k) pair never issues.  Exact when the dead
    x tiles really are zero (the MoR contract)."""
    M, K = x.shape
    _, N = w.shape
    tile_m, tile_k, bn = min(tile_m, M), min(tile_k, K), min(bn, N)
    assert M % tile_m == 0 and K % tile_k == 0 and N % bn == 0
    grid = (M // tile_m, N // bn, K // tile_k)
    assert tile_mask.shape == (grid[0], grid[2]), (tile_mask.shape, grid)
    mask_flat = tile_mask.reshape(-1).astype(jnp.int32)
    return pl.pallas_call(
        _kernel_kdim,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((tile_m, tile_k), lambda i, j, k, m_ref: (i, k)),
                pl.BlockSpec((tile_k, bn), lambda i, j, k, m_ref: (k, j)),
            ],
            out_specs=pl.BlockSpec((tile_m, bn),
                                   lambda i, j, k, m_ref: (i, j)),
            scratch_shapes=[pltpu.VMEM((tile_m, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(mask_flat, x, w)
