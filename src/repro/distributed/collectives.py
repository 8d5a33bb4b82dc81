"""Explicit-schedule collectives (shard_map): overlapped all-gather
matmul.

XLA's GSPMD inserts a *blocking* all-gather before an FSDP matmul.  The
classic fix (Wang et al., "Overlap communication with dependent
computation") is a bidirectional ring: at each of ceil(P/2) steps the
local shard pair is matmul'd while the next shards ppermute in both ring
directions — compute hides the collective.  ``ag_matmul_overlapped`` is
that schedule in ``shard_map`` form; the dry-run HLO shows
collective-permute ops interleaved with dots instead of one fused
all-gather, and the §Perf log measures the collective-term change.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map


def _ring_ag_matmul(x, w, axis_name: str, axis_size: int):
    """Per-shard body: x is the *local* activation shard (M_local, K);
    w is the local K-shard of the weight (K, N) split along K across the
    axis: w_local (K/P, N).  Computes x @ w_full with the x K-dim gathered
    ring-wise and overlapped.

    Layout convention: x: (M, K/P) sharded on K; w: (K/P, N) sharded on K.
    Result: (M, N) partial-sum all-reduced over the axis.

    ``axis_size`` is threaded statically from the mesh (jax 0.4.x has no
    ``jax.lax.axis_size``; the ring schedule needs it at trace time to
    pick the step count anyway).
    """
    p = axis_size
    idx = jax.lax.axis_index(axis_name)
    kb = w.shape[0]

    def xslice(k):
        return jax.lax.dynamic_slice_in_dim(x, k * kb, kb, axis=1)

    # bidirectional ring: our shard pair circulates both ways; each step
    # matmuls the two resident shards while the next pair permutes in —
    # the collective hides behind the dependent compute.
    def step(carry, i):
        acc, fwd, bwd = carry
        k_fwd = (idx + i) % p
        k_bwd = (idx - i) % p
        acc = acc + xslice(k_fwd) @ fwd
        use_bwd = ((i > 0) & (k_bwd != k_fwd)).astype(acc.dtype)
        acc = acc + use_bwd * (xslice(k_bwd) @ bwd)
        fwd = jax.lax.ppermute(
            fwd, axis_name, [(j, (j - 1) % p) for j in range(p)])
        bwd = jax.lax.ppermute(
            bwd, axis_name, [(j, (j + 1) % p) for j in range(p)])
        return (acc, fwd, bwd), None

    acc0 = jnp.zeros((x.shape[0], w.shape[1]), jnp.float32)
    n_steps = (p + 1) // 2 + (0 if p % 2 else 1)
    (acc, _, _), _ = jax.lax.scan(step, (acc0, w, w),
                                  jnp.arange(max(n_steps, 1)))
    return acc.astype(x.dtype)


def ag_matmul_overlapped(x: jax.Array, w: jax.Array, mesh: Mesh,
                         axis: str = "model") -> jax.Array:
    """x: (M, K) activations (replicated over ``axis``); w: (K, N)
    K-sharded over ``axis`` (as FSDP leaves it).  Computes x @ w_full
    WITHOUT materialising the weight all-gather: the ring circulates the
    w shards while each is consumed against its matching x column block.
    Returns (M, N) replicated over ``axis``."""
    fn = shard_map(
        functools.partial(_ring_ag_matmul, axis_name=axis,
                          axis_size=mesh.shape[axis]),
        mesh=mesh,
        in_specs=(P(None, None), P(axis, None)),
        out_specs=P(None, None),
        check_vma=False,
    )
    return fn(x, w)


def flash_merge(m: jax.Array, l: jax.Array, acc: jax.Array,
                axis: str) -> jax.Array:
    """Exact softmax merge of per-shard flash-attention partials with
    ONE collective (the distributed flash-decode combine, ISSUE 5).

    Each shard holds partial statistics over its locally-resident KV
    pages: running max ``m`` (..., ), denominator ``l`` (..., ) and
    un-normalised accumulator ``acc`` (..., Dv).  The naive exact merge
    is a pmax (global max) followed by two psums (rescaled l and acc) —
    three collectives per attention layer (see ``_tp_flash_decode``'s
    sequence-sharded schedule).  Here the three tensors are packed into
    one (..., Dv + 2) buffer and ALL-GATHERED once over ``axis``; every
    shard then combines all P partials locally:

        m* = max_i m_i;  o = sum_i e^{m_i - m*} acc_i
                             / max(sum_i e^{m_i - m*} l_i, eps)

    The combine is O(P * Dv) local flops against one collective of the
    same bytes a psum pair would move — one collective per attention
    layer per dispatch, which is what the serve-sharded acceptance
    criterion counts.  Fully-masked shards (no resident in-window pages
    for a row: m_i at the mask floor) get weight ~0 from the max-shift,
    so empty shards never pollute the merge.  Must be called inside a
    ``shard_map`` region over ``axis``; returns the normalised output
    (..., Dv) in float32."""
    packed = jnp.concatenate(
        [m[..., None].astype(jnp.float32),
         l[..., None].astype(jnp.float32),
         acc.astype(jnp.float32)], axis=-1)
    allp = jax.lax.all_gather(packed, axis)          # (P, ..., Dv + 2)
    m_all, l_all, a_all = allp[..., 0], allp[..., 1], allp[..., 2:]
    m_glob = m_all.max(0)
    w = jnp.exp(m_all - m_glob[None])
    l_tot = (w * l_all).sum(0)
    acc_tot = (w[..., None] * a_all).sum(0)
    return acc_tot / jnp.maximum(l_tot, 1e-30)[..., None]


def psum_scatter_matmul(x: jax.Array, w: jax.Array, mesh: Mesh,
                        axis: str = "model") -> jax.Array:
    """TP down-projection: x (M, F/P) local, w (F/P, N) local ->
    reduce-scattered (M, N/P) result, letting the matmul and the
    reduce-scatter pipeline in one shard_map region."""
    def body(xl, wl):
        out = xl @ wl                    # (M, N) partial sum
        return jax.lax.psum_scatter(out, axis, scatter_dimension=1,
                                    tiled=True)
    fn = shard_map(body, mesh=mesh,
                   in_specs=(P(None, axis), P(axis, None)),
                   out_specs=P(None, axis), check_vma=False)
    return fn(x, w)
