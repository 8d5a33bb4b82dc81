"""Pallas TPU kernel: block-compacted matmul — the DMA-skipping MoR
execution path (the gate and up projections).

Tile versus block.  The MoR decision tile (``tile_m`` x ``tile_n``, 8 x
128 in serving) decides what is kept: the prediction, the ``kept`` mask,
the capacity clip and the tile counters are all per tile, and none of
them depends on how the kernel blocks the matmul.  The Pallas block is
derived from the shapes alone (``blocks``, rule in ``blocking``): all
rows of the call in one block while they fit, the whole contraction K
while it is at most ``MAX_BK`` (the x block then stays resident across
every column block), and column blocks of ``tile_n`` multiples sized to
a ``WEIGHT_BLOCK_BYTES`` weight block.  granite-3-2b's gate/up
((24 | 640) x 2048 @ 2048 x 8192, bf16) runs 16 grid steps of
(2048, 512) weight blocks per call, where 8 x 128 blocks ran
(M/8) x 64 x 4.

Skipping.  A (row block, column block) pair is issued when it holds any
kept tile.  For each row block the issued column blocks are compacted,
in order, into a scalar-prefetched slot list over a static grid (row
blocks x column blocks, then contraction blocks).  A padded slot
repeats the block indices of the last issued slot, so the pipeline
issues no DMA and no write-back for it, and ``pl.when`` skips its dot.
Blocks no slot issues are never written; the output is selected to
exact zero outside ``kept``, at tile granularity, after the call.

Trade.  A block is skipped only when all of its tiles are dead.  With
real sparsity a prefill block of hundreds of rows therefore skips fewer
MACs than 8-row tiles did, but each 8 x 128 tile cost a fixed grid step
that outweighed the MACs it saved.  Weight DMA is once per issued block
per row block: a (bk, bn) block is read whole if any of its
``bn / tile_n`` column tiles is kept in any row tile, where tile
blocking read a kept tile's weight once per live row tile.  With every
tile kept (the random-weight case) a decode step reads the weight once,
where tile blocking read it once per 8-row tile.

Batched-expert contract (MoE): the kernel composes with ``jax.vmap`` —
E experts' (x, w, tile_mask, cap_live) stacks run with per-expert slot
lists and per-expert traced ``cap_live`` clamps.  This is how
``MoRExecutionPlan.expert_ffn`` executes kernel-mode expert FFNs
(oracle: ``ref.expert_gather_matmul_ref``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import blocking as bl

MAX_BK = 4096                  # the whole contraction in one block up to this


def blocks(m: int, k: int, n: int, tile_m: int, tile_n: int,
           itemsize: int):
    """The shape-derived block rule -> (bm, padded rows, bk, bn)."""
    bm, mp = bl.row_blocks(m, tile_m)
    bk = k if k <= MAX_BK else bl.divisor_block(k, 128, MAX_BK // 2)
    bn = bl.divisor_block(n, tile_n,
                          bl.WEIGHT_BLOCK_BYTES // (bk * itemsize))
    return bm, mp, bk, bn


def _kernel(meta_ref, x_ref, w_ref, o_ref, *scratch, n_slots: int):
    p, k = pl.program_id(0), pl.program_id(1)

    @pl.when(meta_ref[2 * n_slots + p] != 0)
    def _mac():
        part = jax.lax.dot_general(
            x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if not scratch:                  # the whole contraction at once
            o_ref[...] = part.astype(o_ref.dtype)
            return
        acc_ref, = scratch

        @pl.when(k == 0)
        def _init():
            acc_ref[...] = part

        @pl.when(k > 0)
        def _acc():
            acc_ref[...] += part

        @pl.when(k == pl.num_programs(1) - 1)
        def _done():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_m", "tile_n", "capacity",
                                             "block_m", "block_n", "block_k",
                                             "interpret", "return_counts"))
def gather_matmul(x: jax.Array, w: jax.Array, tile_mask: jax.Array, *,
                  capacity: int, tile_m: int = 128, tile_n: int = 128,
                  cap_live=None, block_m=None, block_n=None, block_k=None,
                  interpret: bool = False, return_counts: bool = False):
    """x: (M, K) @ w: (K, N); only the first ``capacity`` live tiles (in
    row-major order) are kept.  Tiles not kept are exact zeros.

    ``capacity`` is the STATIC tile budget.  ``cap_live`` is an optional
    TRACED int32 budget clamped under it — the telemetry-calibrated
    per-layer capacity: scan-stacked layers share one compiled body (one
    static capacity) while each layer's realised compute is cut to its
    own observed liveness quantile.

    ``block_m`` / ``block_n`` / ``block_k`` replace the block rule's
    choice for one dimension (``block_n`` a multiple of ``tile_n`` that
    divides N, ``block_k`` one that divides K).

    ``return_counts`` additionally returns (n_live_total, n_computed,
    blocks): the tile counters the executor stashes on its prediction
    for the serving telemetry, and ``blocks`` = int32 [weight blocks
    DMA'd and multiplied, weight blocks in the grid]."""
    M, K = x.shape
    _, N = w.shape
    tile_m, tile_n = min(tile_m, M), min(tile_n, N)
    assert M % tile_m == 0 and N % tile_n == 0
    nm, nn = M // tile_m, N // tile_n
    assert tile_mask.shape == (nm, nn)
    assert 1 <= capacity <= nm * nn
    bm, mp, bk, bn = blocks(M, K, N, tile_m, tile_n, x.dtype.itemsize)
    if block_m is not None:
        bm, mp = block_m, bl.cdiv(M, block_m) * block_m
    bn, bk = block_n or bn, block_k or bk
    assert bm % tile_m == 0 and bn % tile_n == 0
    assert N % bn == 0 and K % bk == 0
    nkb = K // bk
    n_slots = (mp // bm) * (N // bn)

    flat = tile_mask.reshape(-1).astype(bool)
    n_live_total = jnp.sum(flat).astype(jnp.int32)
    cap_eff = jnp.asarray(capacity, jnp.int32)
    if cap_live is not None:
        cap_eff = jnp.minimum(cap_eff, jnp.maximum(
            jnp.asarray(cap_live, jnp.int32), 1))
    n_live = jnp.minimum(n_live_total, cap_eff)
    live_rank = jnp.cumsum(flat) - 1
    kept = (flat & (live_rank < cap_eff)).reshape(nm, nn)
    row, col, issued = bl.compact_slots(
        bl.block_liveness(kept, mp, bm, bn, tile_m, tile_n))
    meta = jnp.concatenate([row, col, issued])
    xp = jnp.pad(x, ((0, mp - M), (0, 0))) if mp > M else x

    def k_of(p, k, meta):
        # k on an issued slot; on a padded one the last k block, where
        # the issued slot it repeats ended, so nothing is fetched
        f = meta[2 * n_slots + p]
        return k + (1 - f) * (nkb - 1 - k)

    out = pl.pallas_call(
        functools.partial(_kernel, n_slots=n_slots),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_slots, nkb),
            in_specs=[
                pl.BlockSpec((bm, bk),
                             lambda p, k, meta: (meta[p], k_of(p, k, meta))),
                pl.BlockSpec((bk, bn),
                             lambda p, k, meta: (k_of(p, k, meta),
                                                 meta[n_slots + p])),
            ],
            out_specs=pl.BlockSpec(
                (bm, bn), lambda p, k, meta: (meta[p], meta[n_slots + p])),
            scratch_shapes=([pltpu.VMEM((bm, bn), jnp.float32)]
                            if nkb > 1 else []),
        ),
        out_shape=jax.ShapeDtypeStruct((mp, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=bl.vmem_limit(bm, bk, bn, x.dtype.itemsize)),
        interpret=interpret,
    )(meta, xp, w)
    # blocks no slot issued hold undefined memory, and an issued block
    # holds real products in its dead tiles -> select every tile outside
    # ``kept`` to zero (a select is garbage-safe, unlike multiplying by 0)
    keep = jnp.repeat(jnp.repeat(kept, tile_m, 0), tile_n, 1)
    out = jnp.where(keep, out[:M], jnp.zeros((), out.dtype))
    if return_counts:
        n_blocks = jnp.stack([jnp.sum(issued) * nkb,
                              jnp.asarray(n_slots * nkb, jnp.int32)])
        return out, n_live_total, n_live, n_blocks.astype(jnp.int32)
    return out
