"""Pallas TPU kernel: capacity-compacted matmul — the DMA-skipping MoR
execution path.

The wrapper compacts the live (row-block, col-tile) pairs into a static
``capacity``-slot index list (MoE-capacity style; calibration picks the
provisioning, DESIGN.md §2).  The grid iterates over slots, and the
weight/x/out BlockSpec index_maps read the tile coordinates from the
scalar-prefetched list — so **only live weight tiles are ever DMA'd from
HBM**, which is where decode-time FFNs spend their roofline.

Slot layout of the prefetch array ``meta``:
  meta[0]            = n_live (clamped to capacity)
  meta[1 + s]        = flattened tile id (i * n_tiles_n + j) for slot s;
                       padded slots repeat a designated dead tile (whose
                       correct output is zero) or tile 0 when fully live.

Batched-expert contract (MoE): the kernel composes with ``jax.vmap`` —
the batching rule prepends the expert axis to the grid, so E experts'
(x, w, tile_mask, cap_live) stacks run as one expert-grid kernel with
per-expert slot lists and per-expert traced ``cap_live`` clamps.  This
is how ``MoRExecutionPlan.expert_ffn`` executes kernel-mode expert FFNs
(oracle: ``ref.expert_gather_matmul_ref``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(meta_ref, x_ref, w_ref, o_ref, acc_ref):
    s, k = pl.program_id(0), pl.program_id(1)
    n_live = meta_ref[0]

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(s < n_live)
    def _mac():
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(1) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_m", "tile_n", "bk",
                                             "capacity", "interpret",
                                             "return_counts"))
def gather_matmul(x: jax.Array, w: jax.Array, tile_mask: jax.Array, *,
                  capacity: int, tile_m: int = 128, tile_n: int = 128,
                  bk: int = 512, cap_live=None, interpret: bool = False,
                  return_counts: bool = False):
    """x: (M, K) @ w: (K, N); only the first ``capacity`` live tiles (in
    row-major order) are computed.  Dead/overflow tiles are exact zeros.

    ``capacity`` is the STATIC slot provisioning (it sizes the grid, so
    it bounds the DMA issue).  ``cap_live`` is an optional TRACED int32
    budget clamped under it — the telemetry-calibrated per-layer
    capacity: scan-stacked layers share one compiled body (one static
    capacity) while each layer's realised compute is cut to its own
    observed liveness quantile.

    ``return_counts`` additionally returns (n_live_total, n_computed) —
    the tile-liveness counters the executor stashes on its prediction
    (``MoRPrediction.kernel_counts``) for the serving telemetry."""
    M, K = x.shape
    _, N = w.shape
    tile_m, bk, tile_n = min(tile_m, M), min(bk, K), min(tile_n, N)
    assert M % tile_m == 0 and K % bk == 0 and N % tile_n == 0
    nm, nn = M // tile_m, N // tile_n
    assert tile_mask.shape == (nm, nn)
    assert 1 <= capacity <= nm * nn

    flat = tile_mask.reshape(-1).astype(bool)
    n_tiles = nm * nn
    # live tiles first (stable), then dead tiles (used for slot padding)
    order = jnp.argsort(~flat, stable=True).astype(jnp.int32)
    n_live_total = jnp.sum(flat).astype(jnp.int32)
    cap_eff = jnp.asarray(capacity, jnp.int32)
    if cap_live is not None:
        cap_eff = jnp.minimum(cap_eff, jnp.maximum(
            jnp.asarray(cap_live, jnp.int32), 1))
    n_live = jnp.minimum(n_live_total, cap_eff)
    # padded slots point at the first dead tile; if everything is live,
    # they point at live tiles already computed (harmless re-compute).
    first_dead = order[jnp.minimum(n_live_total, n_tiles - 1)]
    slots = order[:capacity]
    slot_ids = jnp.where(jnp.arange(capacity) < n_live, slots, first_dead)
    meta = jnp.concatenate([n_live[None], slot_ids]).astype(jnp.int32)

    grid = (capacity, K // bk)
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((tile_m, bk),
                             lambda s, k, meta: (meta[1 + s] // nn, k)),
                pl.BlockSpec((bk, tile_n),
                             lambda s, k, meta: (k, meta[1 + s] % nn)),
            ],
            out_specs=pl.BlockSpec(
                (tile_m, tile_n),
                lambda s, k, meta: (meta[1 + s] // nn, meta[1 + s] % nn)),
            scratch_shapes=[pltpu.VMEM((tile_m, tile_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(meta, x, w)
    # tiles never visited by any slot hold undefined memory -> select them
    # to zero with the (cheap, VPU) mask expansion.  jnp.where (a select)
    # is garbage-safe, unlike multiplying by 0.
    live_rank = jnp.cumsum(flat) - 1
    kept = (flat & (live_rank < cap_eff)).reshape(nm, nn)
    keep = jnp.repeat(jnp.repeat(kept, tile_m, 0), tile_n, 1)
    out = jnp.where(keep, out, jnp.zeros((), out.dtype))
    if return_counts:
        return out, n_live_total, n_live
    return out
