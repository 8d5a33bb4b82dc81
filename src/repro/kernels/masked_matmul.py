"""Pallas TPU kernels: matmuls that skip MoR tiles.

``masked_matmul`` (output-masked): the MoR tile mask (scalar-prefetched
into SMEM) gates the MXU work for each (row-block x 128-col) output
tile: dead tiles write zeros without issuing dot products.  This is the
compute-skip half of the paper's benefit; the DMA-skip half needs the
compacted variant (``gather_matmul``), because block DMAs declared via
BlockSpec are unconditional under a static grid.  Not on the serving
path; its blocks are the MoR tiles.

``masked_matmul_kdim`` (contraction-masked, the MoR down projection):
tile versus block.  The MoR decision tile (``tile_m`` x ``tile_k``, 8 x
128 in serving) is the unit of the ``kept`` mask it receives.  Its
Pallas block is derived from the shapes alone (``kdim_blocks``, rule in
``blocking``): all rows of the call in one block while they fit, output
columns whole up to ``MAX_BN``, and contraction blocks of ``tile_k``
multiples sized to a ``WEIGHT_BLOCK_BYTES`` weight block.
granite-3-2b's down projection ((24 | 640) x 8192 @ 8192 x 2048, bf16)
runs 16 grid steps of (512, 2048) weight blocks per call, where 8 x 128
blocks ran (M/8) x 16 x 64.

Skipping.  A (row block, contraction block) pair with no kept tile is
skipped: per row block, the issued contraction blocks are compacted into
a scalar-prefetched slot list, and a padded slot repeats the block
indices of the last issued one, so the pipeline issues no DMA for it and
``pl.when`` skips its dot.  A partly live block is computed whole, which
is exact under the contract that dead x tiles are exact zeros
(``MoRExecutionPlan.ffn`` makes them zero through ``gather_matmul``'s
select).  Trade: a block is skipped only when every tile in it is dead,
so with real sparsity a prefill block of hundreds of rows skips fewer
MACs than 8-row tiles did, but each 8 x 128 tile cost a fixed grid step
that outweighed the MACs it saved.  Weight DMA is once per issued block
per row block, where tile blocking read a live tile's weight rows once
per live row tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import blocking as bl

MAX_BN = 2048                  # output columns whole up to this many


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




def kdim_blocks(m: int, k: int, n: int, tile_m: int, tile_k: int,
                itemsize: int):
    """The shape-derived block rule -> (bm, padded rows, bk, bn)."""
    bm, mp = bl.row_blocks(m, tile_m)
    bn = n if n <= MAX_BN else bl.divisor_block(n, 128, MAX_BN)
    bk = bl.divisor_block(k, tile_k,
                          bl.WEIGHT_BLOCK_BYTES // (bn * itemsize))
    return bm, mp, bk, bn


def _kernel_kdim(meta_ref, x_ref, w_ref, o_ref, acc_ref, *, n_slots: int):
    r, s = pl.program_id(1), pl.program_id(2)
    nkb = pl.num_programs(2)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(meta_ref[2 * n_slots + r * nkb + s] != 0)
    def _mac():
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(s == nkb - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("tile_m", "tile_k", "block_m", "block_n",
                                    "block_k", "interpret", "return_counts"))
def masked_matmul_kdim(x: jax.Array, w: jax.Array, tile_mask: jax.Array, *,
                       tile_m: int = 8, tile_k: int = 128, block_m=None,
                       block_n=None, block_k=None, interpret: bool = False,
                       return_counts: bool = False):
    """x: (M, K) @ w: (K, N) skipping dead CONTRACTION blocks.

    ``tile_mask``: (M/tile_m, K/tile_k) bool — the MoR down-projection
    mask: tile_mask[i, k] == 0 means rows [k*tile_k, (k+1)*tile_k) of
    ``x`` block-row i are known-zero (a dead FFN hidden tile).  A
    (row block, k block) pair with no live tile never issues its DMA or
    its dot.  Exact when the dead x tiles really are zero (the MoR
    contract).

    ``block_m`` / ``block_n`` / ``block_k`` replace the block rule's
    choice for one dimension (``block_k`` a multiple of ``tile_k`` that
    divides K, ``block_n`` one of 128 that divides N).
    ``return_counts`` additionally returns int32 [weight blocks DMA'd
    and multiplied, weight blocks in the grid]."""
    M, K = x.shape
    _, N = w.shape
    tile_m, tile_k = min(tile_m, M), min(tile_k, K)
    assert M % tile_m == 0 and K % tile_k == 0
    assert tile_mask.shape == (M // tile_m, K // tile_k), tile_mask.shape
    bm, mp, bk, bn = kdim_blocks(M, K, N, tile_m, tile_k, x.dtype.itemsize)
    if block_m is not None:
        bm, mp = block_m, bl.cdiv(M, block_m) * block_m
    bn, bk = block_n or bn, block_k or bk
    assert bm % tile_m == 0 and bk % tile_k == 0
    assert N % bn == 0 and K % bk == 0
    nrb, nkb, ncb = mp // bm, K // bk, N // bn
    n_slots = nrb * nkb
    row, kb, issued = bl.compact_slots(
        bl.block_liveness(tile_mask, mp, bm, bk, tile_m, tile_k))
    meta = jnp.concatenate([row, kb, issued])
    xp = jnp.pad(x, ((0, mp - M), (0, 0))) if mp > M else x
    out = pl.pallas_call(
        functools.partial(_kernel_kdim, n_slots=n_slots),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(ncb, nrb, nkb),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda j, r, s, meta: (
                    meta[r * nkb + s], meta[n_slots + r * nkb + s])),
                pl.BlockSpec((bk, bn), lambda j, r, s, meta: (
                    meta[n_slots + r * nkb + s], j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda j, r, s, meta: (r, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((mp, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=bl.vmem_limit(bm, bk, bn, x.dtype.itemsize)),
        interpret=interpret,
    )(meta, xp, w)[:M]
    if return_counts:
        n_blocks = jnp.stack([jnp.sum(issued) * ncb,
                              jnp.asarray(n_slots * ncb, jnp.int32)])
        return out, n_blocks.astype(jnp.int32)
    return out
