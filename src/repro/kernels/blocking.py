"""The block rule of the MoR FFN matmul kernels (``gather_matmul``,
``masked_matmul_kdim``): Pallas block shapes derived from the call's
shapes alone, apart from the MoR decision tile.

The decision tile (``tile_m`` x ``tile_n``, 8 x 128 in serving) is the
unit of prediction, of the ``kept`` mask, of the capacity clip and of
the tile counters.  A kernel block covers many tiles:

- rows: every row of the call in one block while there are at most
  ``MAX_BLOCK_ROWS``, else equal blocks of at most ``SPLIT_ROWS``
  (``row_blocks``);
- weight blocks of about ``WEIGHT_BLOCK_BYTES``, in multiples of the
  tile along the FFN hidden dimension (the kernels pick the other side).

A decode call (tens of rows, bound by HBM) and a chunked prefill
(hundreds of rows, bound by the MXU) then both read each issued weight
block once per row block, in tens of grid steps per call instead of one
step per 8 x 128 tile.  ``compact_slots`` turns a block-liveness mask
into the scalar-prefetched slot list both kernels skip dead blocks
with.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

MAX_BLOCK_ROWS = 1024          # all rows in one block up to this many
SPLIT_ROWS = 512               # else equal row blocks of at most this many
WEIGHT_BLOCK_BYTES = 2 << 20   # target bytes of one weight block
_MIN_VMEM_LIMIT = 32 << 20
_MAX_VMEM_LIMIT = 100 << 20


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def divisor_block(dim: int, unit: int, target: int) -> int:
    """The largest multiple of ``unit`` that divides ``dim`` and is at
    most ``max(target, unit)``; ``dim`` itself (one full-extent block)
    when ``dim`` is not a multiple of ``unit``."""
    if dim % unit:
        return dim
    n = dim // unit
    return unit * max(d for d in range(1, n + 1)
                      if n % d == 0 and d * unit <= max(target, unit))


def row_blocks(m: int, tile_m: int):
    """-> (bm, padded rows) for ``m`` rows (a multiple of ``tile_m``):
    one block of every row up to ``MAX_BLOCK_ROWS``, else equal blocks
    of at most ``SPLIT_ROWS``, each a multiple of ``tile_m`` and of the
    16-row bf16 sublane tile."""
    if m <= MAX_BLOCK_ROWS:
        return m, m
    unit = math.lcm(tile_m, 16)
    nb = cdiv(m, SPLIT_ROWS)
    bm = cdiv(cdiv(m, nb), unit) * unit
    return bm, nb * bm


def vmem_limit(bm: int, bk: int, bn: int, itemsize: int) -> int:
    """Scoped VMEM for double-buffered x, w and out blocks plus the f32
    accumulator, with headroom."""
    need = 2 * (bm * bk + bk * bn + bm * bn) * itemsize + 4 * bm * bn
    return int(min(max(_MIN_VMEM_LIMIT, need * 3 // 2), _MAX_VMEM_LIMIT))


def compact_slots(live: jax.Array):
    """Slot list of a (rows, cols) block-liveness mask: per row, the live
    columns first, in order, then padding.  -> (row, col, issued), each
    flat (rows * cols,) int32.  A padded slot repeats the (row, col) of
    the last issued slot before it ((0, 0) before any), so its block
    indices equal the previous grid step's and the pipeline moves
    nothing for it."""
    nr, nc = live.shape
    order = jnp.argsort(~live, axis=1, stable=True).astype(jnp.int32)
    issued = jnp.take_along_axis(live, order, 1).reshape(-1)
    row = jnp.repeat(jnp.arange(nr, dtype=jnp.int32), nc)
    col = order.reshape(-1)
    pos = jnp.arange(nr * nc, dtype=jnp.int32)
    last = jax.lax.cummax(jnp.where(issued, pos, -1))
    src = jnp.maximum(last, 0)
    row = jnp.where(last >= 0, row[src], 0)
    col = jnp.where(last >= 0, col[src], 0)
    return row, col, issued.astype(jnp.int32)


def block_liveness(kept: jax.Array, rows: int, bm: int, bn: int,
                   tile_m: int, tile_n: int) -> jax.Array:
    """(nm, nn) tile mask -> (rows / bm, nn * tile_n / bn) bool: a block
    is live when it holds any kept tile.  ``rows`` is the block-padded
    row count; the padding rows are dead."""
    nm, nn = kept.shape
    kept = jnp.pad(kept.astype(bool), ((0, rows // tile_m - nm), (0, 0)))
    return kept.reshape(rows // bm, bm // tile_m, nn * tile_n // bn,
                        bn // tile_n).any(axis=(1, 3))

