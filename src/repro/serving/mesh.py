"""repro.serving.mesh — the mesh-sharded paged serving layout.

``Engine(layout="paged-sharded", mesh=...)`` runs the whole serving hot
loop under ONE ``shard_map`` over the mesh's page axis
(``sharding_rules.PAGE_AXIS``):

  * every page-pool leaf of the paged cache ((stack, n_pages, page, ...)
    attention/latent pools, (L, n_spages, ...) recurrent-state pools) is
    partitioned on its page dimension — HBM capacity for the KV cache
    scales with the mesh while params, tokens, block tables and the
    residual compute stay replicated;
  * the host-side ``BlockAllocator`` is replicated but ownership-aware
    (each logical page pins to the shard that physically holds it;
    fresh allocations round-robin shards, COW destinations stay on
    their source's shard), so the packed page-edit vector splits into
    one row per shard and ``kv_pool.apply_cache_ops`` runs unchanged,
    shard-locally, inside the same compiled step;
  * attention over the paged ring becomes a DISTRIBUTED flash decode:
    each shard computes partial (m, l, acc) statistics over its
    locally-resident pages and the shards combine with one collective
    per attention layer (``collectives.flash_merge``); recurrent state
    uses a single-owner psum gather (``decode_attention.state_*``).

Prefix caching, copy-on-write and eviction keep working UNCHANGED on
top: they only ever manipulate global page ids host-side, and global
ids shard deterministically.  This module holds the glue — partition
specs for an arbitrary paged cache pytree, sharded placement, and the
``shard_map``-wrapped step/apply builders the engine and pool use.
"""
from __future__ import annotations

from typing import Dict

import jax
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed.decode_attention import page_shard_context
from repro.distributed.sharding_rules import PAGE_AXIS
from repro.serving import kv_pool

__all__ = ["cache_partition_specs", "shard_cache", "replicate",
           "sharded_apply", "make_sharded_step", "make_sharded_shadow_step"]


def cache_partition_specs(cache: Dict) -> Dict:
    """PartitionSpec pytree for a paged cache: page-pool leaves split on
    their page axis (axis 1 — the layer stack leads), tables / pos
    replicated."""
    def kv(node):
        # per-layer tuple leaves: each element is (n_pages, page, ...)
        # with the page dim LEADING (no stack axis)
        return {k: (tuple(P(PAGE_AXIS) for _ in v)
                    if isinstance(v, tuple) else P(None, PAGE_AXIS))
                for k, v in node.items()}

    def stl(a):
        return P(None, PAGE_AXIS)

    specs: Dict = {}
    for k, v in cache.items():
        if k in kv_pool._TABLE_KEYS:
            specs[k] = P()
            continue
        v = kv_pool.map_kv_nodes(v, kv)
        specs[k] = kv_pool.map_state_leaves(v, stl)
    return specs


def _walk2(a, b, fn):
    """Zip-walk two parallel dict trees (specs are P leaves, which jax's
    tree utils may treat as tuples — so walk dicts and per-layer leaf
    tuples explicitly)."""
    if isinstance(a, dict):
        return {k: _walk2(a[k], b[k], fn) for k in a}
    if isinstance(a, tuple):                     # per-layer pool leaves
        return tuple(fn(x, s) for x, s in zip(a, b))
    return fn(a, b)


def shard_cache(cache: Dict, mesh, specs: Dict = None) -> Dict:
    """Place a freshly-built paged cache on the mesh, page-sharded."""
    specs = specs if specs is not None else cache_partition_specs(cache)
    return _walk2(cache, specs,
                  lambda a, s: jax.device_put(a, NamedSharding(mesh, s)))


def replicate(tree, mesh):
    """Place every array leaf of ``tree`` on all mesh devices (the
    ``P()`` operands of the sharded step)."""
    return jax.device_put(tree, NamedSharding(mesh, P()))


def sharded_apply(mesh, specs: Dict):
    """The standalone (overflow-round) cache-ops apply as a shard_map
    step: each shard applies its own ops row to its local page range.
    The copy-pad widths are static (the pool buckets them to {0, max})
    so copy-free rounds compile without the scatter."""
    n = mesh.shape[PAGE_AXIS]

    def fn(cache, ops, pads):
        def body(cache, ops):
            with page_shard_context(PAGE_AXIS, n):
                return kv_pool.apply_cache_ops(cache, ops[0], *pads)

        return shard_map(body, mesh=mesh, in_specs=(specs, P(PAGE_AXIS)),
                         out_specs=specs, check_vma=False)(cache, ops)

    return jax.jit(fn, donate_argnums=(0,), static_argnums=(2,))


def make_sharded_step(body, mesh, cache: Dict):
    """Wrap the engine's dispatch-step body in ONE shard_map over the
    page axis and jit it (cache donated, like the single-device step).

    ``body(params, mor, cache, tokens, n_valid, use_pending, pending,
    key, ops, metrics)`` is ``Engine._step_impl`` with its static
    leading args bound; inside the region the page-shard context is
    active, so the models' paged branches run the distributed flash
    decode and the fused ``apply_cache_ops`` consumes this shard's ops
    row.  Everything except the page pools is replicated (specs
    ``P()``): the sharded layout trades replicated FFN/projection
    compute for a P-way partitioned KV cache and one merge collective
    per attention layer — multi-host serving as a config flag, not a
    cache rewrite.

    The obs device-metrics block (``metrics``, (n_shards, size) int32,
    None when observability is off) shards one row per page shard like
    the ops vector: each shard accumulates into its local row (header
    fields land replicated, page-edit counts shard-local) and the row
    rides back out still sharded — the host aggregates rows only at
    flush time."""
    specs = cache_partition_specs(cache)
    n = mesh.shape[PAGE_AXIS]

    def stepfn(params, mor, cache, tokens, n_valid, use_pending, pending,
               key, ops, metrics=None, n_active=None, copy_pads=(0, 0)):
        # n_active / copy_pads are static (bucketed active-block width
        # and {0, max} copy-pad widths) — they ride into the body via
        # closure, not as shard_map operands
        def inner(params, mor, cache, tokens, n_valid, use_pending,
                  pending, key, ops, metrics):
            with page_shard_context(PAGE_AXIS, n):
                return body(params, mor, cache, tokens, n_valid,
                            use_pending, pending, key,
                            None if ops is None else ops[0], metrics,
                            n_active, copy_pads)

        return shard_map(
            inner, mesh=mesh,
            in_specs=(P(), P(), specs, P(), P(), P(), P(), P(),
                      P(PAGE_AXIS), P(PAGE_AXIS)),
            out_specs=(P(), P(), specs, P(), P(PAGE_AXIS)),
            check_vma=False,
        )(params, mor, cache, tokens, n_valid, use_pending, pending, key,
          ops, metrics)

    return jax.jit(stepfn, donate_argnums=(2, 9), static_argnums=(10, 11))


def make_sharded_shadow_step(body, mesh, cache: Dict):
    """The shadow-oracle scoring pass (``Engine._shadow_impl`` with its
    leading args bound) under the same page-axis ``shard_map`` as the
    primary step.  It reads the SAME sharded cache the primary step is
    about to consume — so the cache is NOT donated here (only the
    metrics block, its one output, is) — and returns the per-shard
    metrics rows, ``P(PAGE_AXIS)`` like the primary step's."""
    specs = cache_partition_specs(cache)
    n = mesh.shape[PAGE_AXIS]

    def stepfn(params, mor, cache, tokens, n_valid, use_pending, pending,
               ops, metrics, n_active=None, copy_pads=(0, 0)):
        def inner(params, mor, cache, tokens, n_valid, use_pending,
                  pending, ops, metrics):
            with page_shard_context(PAGE_AXIS, n):
                return body(params, mor, cache, tokens, n_valid,
                            use_pending, pending,
                            None if ops is None else ops[0], metrics,
                            n_active, copy_pads)

        return shard_map(
            inner, mesh=mesh,
            in_specs=(P(), P(), specs, P(), P(), P(), P(),
                      P(PAGE_AXIS), P(PAGE_AXIS)),
            out_specs=P(PAGE_AXIS),
            check_vma=False,
        )(params, mor, cache, tokens, n_valid, use_pending, pending,
          ops, metrics)

    return jax.jit(stepfn, donate_argnums=(8,), static_argnums=(9, 10))
