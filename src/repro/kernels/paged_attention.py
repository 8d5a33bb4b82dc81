"""Fused Pallas paged flash-decode: attend straight off the block table.

The paged serving layouts (``serving.kv_pool.PagedPool``) used to pay
for every attend twice: gather the whole ring view through the block
table (``pool[block_table].reshape(B, ring, ...)``), THEN run dense
masked attention over it — every null page (reserved id 0) and, under
the mesh-sharded layout, every foreign page was materialised, masked
and softmaxed.  This kernel inverts that: the (B, n_blocks) block table
rides in as a SCALAR-PREFETCH operand, the grid walks (slot, block),
and each step's BlockSpec index_map pulls exactly one KV page out of
the pool — pages that are null (never written) or foreign (resident on
another shard) are grid-level skips (``pl.when``), so their DMA target
is the always-resident null page and their FLOPs never issue.  Online
softmax statistics (m, l, acc) accumulate in VMEM scratch across the
block dimension, exactly the ``gather_matmul`` DMA-on-demand idiom
applied to KV pages instead of weight tiles.

Two layouts share the machinery:

  * GQA rings: pools (n_pages, page, hkv, hd), grouped queries
    (B, C, H, hd), causal + optional sliding window — ring wrap needs
    no special casing because masking is entirely position-tag driven;
  * absorbed-MLA latent: pools (n_pages, page, kr) / (n_pages, page,
    rd), scores in the rank-kr latent space (W_uk already absorbed into
    the query), accumulator over the latent rows.

Each kernel has two output variants: the normalised output (single-
device paged layout) and the raw partial (m, l, acc) flash statistics
(``partial=True``) — the mesh-sharded layout feeds those straight into
``collectives.flash_merge``, so the sharded attends keep their
one-collective-per-layer contract without ever building the ring view.

Like every kernel here it runs ``interpret=True`` off-TPU; mode
selection for the serving paths lives in ``enabled()`` (the kernel on
TPU, the jnp gather fallback elsewhere), mirroring ``ops._interpret``.  The pure-jnp
oracles are ``ref.gqa_paged_ref`` / ``ref.mla_paged_ref``.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import _interpret

NEG_INF = -1e30
# MLA query rows per grid step: bounds the (rows, kv_lora_rank) fp32
# accumulator in VMEM at deepseek-v2 widths (128 heads x chunk 32)
MLA_ROW_BLOCK = 256


def _named_jit(name: str, **jit_kw):
    """``jax.jit`` under ``name``.  A Pallas kernel's custom call takes
    the name of the innermost jit that issues it, so a device trace
    shows the kernel as ``name`` and not as the serving step around it
    (an unnamed ``partial``, ``_unknown_`` in the trace)."""
    def wrap(fn):
        fn.__name__ = fn.__qualname__ = name
        return jax.jit(fn, **jit_kw)
    return wrap


def enabled() -> bool:
    """Kernel-vs-jnp choice for the PAGED ATTEND serving paths: the
    fused kernel on TPU, the jnp gather fallback elsewhere
    (interpret-mode Pallas serialises the page grid, so CPU serving
    keeps the fused-XLA path; the differential tests patch this to
    force the kernel)."""
    return jax.default_backend() == "tpu"


# trace-time dispatch counters: how many pallas_call sites each serving
# step compiled in (telemetry / CI proof that the kernel path engaged —
# a cached executable re-dispatches without retracing, so these count
# kernel *traces*, not per-token launches).  Counters are a SCOPE
# STACK: the root scope is process-global (historical behaviour), and
# ``trace_scope()`` pushes a fresh frame so back-to-back benchmark
# scenarios can each read their own counts without bleed-through —
# kernels bump every active frame, ``kernel_traces()`` reads the
# innermost.
_SCOPES = [{"gqa": 0, "mla": 0}]


def _bump_trace(kind: str) -> None:
    for frame in _SCOPES:
        frame[kind] += 1


def kernel_traces() -> dict:
    """Counts in the innermost active scope (the process-global root
    when no ``trace_scope`` is open)."""
    return dict(_SCOPES[-1])


def reset_kernel_traces() -> None:
    """Zero the innermost active scope."""
    for k in _SCOPES[-1]:
        _SCOPES[-1][k] = 0


@contextlib.contextmanager
def trace_scope():
    """Scoped kernel-trace counting: yields a dict that accumulates
    only the traces that happen inside the ``with`` block (it keeps its
    final counts after exit); outer scopes keep accumulating too."""
    frame = {"gqa": 0, "mla": 0}
    _SCOPES.append(frame)
    try:
        yield frame
    finally:
        _SCOPES.remove(frame)


def _live_tables(block_table, lo, n_local):
    """(pool page index to DMA, live flag) per (slot, block).  Null
    pages (global id 0) are never live; under a shard's local window
    [lo, lo + n_local) foreign pages aren't either — both DMA the
    always-resident page 0 and skip all compute."""
    if lo is None:
        loc, ok = block_table, block_table > 0
    else:
        loc = block_table - lo
        ok = (block_table > 0) & (loc >= 0) & (loc < n_local)
    return (jnp.where(ok, loc, 0).astype(jnp.int32),
            ok.astype(jnp.int32))


# ==========================================================================
# GQA over paged rings
# ==========================================================================

def _online_softmax_step(s, v, m_s, l_s, a_s, idx):
    """One flash step for one score block: s (R, page) fp32 scores
    (masked entries at NEG_INF), v (page, Dv) fp32 values; the running
    (m, l, acc) statistics live in scratch rows ``idx``."""
    m_prev = m_s[idx]                                        # (R, 1)
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_s[idx] = l_s[idx] * corr + p.sum(-1, keepdims=True)
    a_s[idx] = a_s[idx] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_s[idx] = m_new


def _emit_stats(refs, m_s, l_s, a_s, partial: bool):
    """Write the finished statistics: raw (m, l, acc) with ``partial``,
    else the normalised output."""
    if partial:
        m_ref, l_ref, a_ref = refs
        m_ref[0] = m_s[...]
        l_ref[0] = l_s[...]
        a_ref[0] = a_s[...]
    else:
        (o_ref,) = refs
        o_ref[0] = (a_s[...] / jnp.maximum(l_s[...], 1e-30)
                    ).astype(o_ref.dtype)


def _gqa_kernel(tbl_ref, live_ref, qp_ref, q_ref, k_ref, v_ref, p_ref,
                *refs, n_blocks: int, scale: float, window: int,
                partial: bool):
    out_refs, (m_s, l_s, a_s) = refs[:-3], refs[-3:]
    b, j = pl.program_id(0), pl.program_id(1)
    hkv = k_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s[...], NEG_INF)
        l_s[...] = jnp.zeros_like(l_s[...])
        a_s[...] = jnp.zeros_like(a_s[...])

    @pl.when(live_ref[b, j] > 0)
    def _block():
        tags = p_ref[0]                          # (1, page) int32
        qp = qp_ref[0]                           # (G*C, 1) int32
        rel = qp - tags                          # (G*C, page)
        ok = (tags >= 0) & (rel >= 0)
        if window > 0:
            ok &= rel < window
        # one 2-D dot per kv head: rows are that head's (G, C) queries
        for h in range(hkv):
            q = q_ref[0, h].astype(jnp.float32)              # (G*C, D)
            k = k_ref[0, :, h, :].astype(jnp.float32)        # (page, D)
            v = v_ref[0, :, h, :].astype(jnp.float32)        # (page, Dv)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(ok, s, NEG_INF)
            _online_softmax_step(s, v, m_s, l_s, a_s, h)

    @pl.when(j == n_blocks - 1)
    def _emit():
        _emit_stats(out_refs, m_s, l_s, a_s, partial)


def gqa_paged_flash(q, kpool, vpool, ppool, block_table, qpos, *,
                    window: int = 0, lo=None, n_local: Optional[int] = None,
                    partial: bool = False, interpret: Optional[bool] = None):
    """Fused GQA paged flash decode.  q: (B, C, H, D); pools:
    (n_pages, page, hkv, ·) with position tags ``ppool`` (n_pages,
    page); block_table: (B, n_blocks) page ids (global under sharding —
    pass ``lo``/``n_local`` for the shard's resident window); qpos:
    (B, C) query positions.  Returns (B, C, H, Dv) in q's dtype, or the
    partial flash stats ((B, hkv, G, C) m / l, (B, hkv, G, C, Dv) acc,
    all fp32) with ``partial=True`` — the ``flash_merge`` operands.

    Inside the kernel each kv head's queries are one (G*C, D) row block
    and every operand keeps its last two dims whole, so Mosaic needs no
    relayout at any chunk width."""
    _bump_trace("gqa")
    tbl, live = _live_tables(block_table, lo, n_local)
    return _gqa_paged_flash(
        q, kpool, vpool, ppool, tbl, live, qpos, window=window,
        partial=partial,
        interpret=_interpret() if interpret is None else interpret)


@_named_jit("gqa_paged_flash",
            static_argnames=("window", "partial", "interpret"))
def _gqa_paged_flash(q, kpool, vpool, ppool, tbl, live, qpos, *,
                     window: int, partial: bool, interpret: bool):
    B, C, H, D = q.shape
    n_pages, page, hkv = kpool.shape[:3]
    Dv = vpool.shape[-1]
    G = H // hkv
    R = G * C
    n_blocks = tbl.shape[1]
    # rows ordered (g, c) per kv head; each row carries its query position
    qh = q.reshape(B, C, hkv, G, D).transpose(0, 2, 3, 1, 4).reshape(
        B, hkv, R, D)
    qp = jnp.tile(qpos.astype(jnp.int32), (1, G))[..., None]    # (B, R, 1)
    kernel = functools.partial(_gqa_kernel, n_blocks=n_blocks,
                               scale=D ** -0.5, window=window,
                               partial=partial)
    in_specs = [
        pl.BlockSpec((1, R, 1), lambda b, j, tbl, live: (b, 0, 0)),
        pl.BlockSpec((1, hkv, R, D), lambda b, j, tbl, live: (b, 0, 0, 0)),
        pl.BlockSpec((1, page, hkv, D),
                     lambda b, j, tbl, live: (tbl[b, j], 0, 0, 0)),
        pl.BlockSpec((1, page, hkv, Dv),
                     lambda b, j, tbl, live: (tbl[b, j], 0, 0, 0)),
        pl.BlockSpec((1, 1, page), lambda b, j, tbl, live: (tbl[b, j], 0, 0)),
    ]
    stat = pl.BlockSpec((1, hkv, R, 1), lambda b, j, tbl, live: (b, 0, 0, 0))
    acc = pl.BlockSpec((1, hkv, R, Dv), lambda b, j, tbl, live: (b, 0, 0, 0))
    if partial:
        out_shape = (jax.ShapeDtypeStruct((B, hkv, R, 1), jnp.float32),) * 2 \
            + (jax.ShapeDtypeStruct((B, hkv, R, Dv), jnp.float32),)
        out_specs = (stat, stat, acc)
    else:
        out_shape = jax.ShapeDtypeStruct((B, hkv, R, Dv), q.dtype)
        out_specs = acc
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, n_blocks),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((hkv, R, 1), jnp.float32),
                pltpu.VMEM((hkv, R, 1), jnp.float32),
                pltpu.VMEM((hkv, R, Dv), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(tbl, live, qp, qh, kpool, vpool, ppool.reshape(n_pages, 1, page))
    if partial:
        m, l, a = out
        return (m.reshape(B, hkv, G, C), l.reshape(B, hkv, G, C),
                a.reshape(B, hkv, G, C, Dv))
    return out.reshape(B, hkv, G, C, Dv).transpose(0, 3, 1, 2, 4).reshape(
        B, C, H, Dv)


# ==========================================================================
# absorbed-MLA over paged latent pools
# ==========================================================================

def _mla_kernel(tbl_ref, live_ref, qp_ref, ql_ref, qe_ref, ck_ref, pe_ref,
                p_ref, *refs, n_blocks: int, scale: float, partial: bool):
    out_refs, (m_s, l_s, a_s) = refs[:-3], refs[-3:]
    b, j = pl.program_id(0), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s[...], NEG_INF)
        l_s[...] = jnp.zeros_like(l_s[...])
        a_s[...] = jnp.zeros_like(a_s[...])

    @pl.when(live_ref[b, j] > 0)
    def _block():
        ql = ql_ref[0].astype(jnp.float32)       # (rows, kr)
        qe = qe_ref[0].astype(jnp.float32)       # (rows, rd)
        ck = ck_ref[0].astype(jnp.float32)       # (page, kr)
        pe = pe_ref[0].astype(jnp.float32)       # (page, rd)
        tags = p_ref[0]                          # (1, page) int32
        qp = qp_ref[0]                           # (rows, 1) int32
        nt = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(ql, ck, nt,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qe, pe, nt,
                                   preferred_element_type=jnp.float32)
             ) * scale
        ok = (tags >= 0) & (tags <= qp)
        s = jnp.where(ok, s, NEG_INF)            # (rows, page)
        _online_softmax_step(s, ck, m_s, l_s, a_s, ...)

    @pl.when(j == n_blocks - 1)
    def _emit():
        _emit_stats(out_refs, m_s, l_s, a_s, partial)


def mla_paged_flash(q_lat, q_pe, ck_pool, cpe_pool, cp_pool, block_table,
                    qpos, *, scale: float, lo=None,
                    n_local: Optional[int] = None, partial: bool = False,
                    interpret: Optional[bool] = None):
    """Fused absorbed-MLA paged flash decode (latent space).  q_lat:
    (B, C, h, kr) with W_uk absorbed, q_pe: (B, C, h, rd); pools:
    (n_pages, page, ·) latent / rope rows with position tags
    ``cp_pool``.  Returns o_lat (B, C, h, kr) in q_lat's dtype (the
    caller absorbs W_uv), or with ``partial=True`` the flash stats
    ((B, h, C) m / l, (B, h, C, kr) acc, fp32) for ``flash_merge``.

    Every head reads the one shared latent, so the kernel sees all
    queries as (h*C) rows, cut into row blocks of at most
    ``MLA_ROW_BLOCK`` to bound the VMEM accumulator."""
    _bump_trace("mla")
    tbl, live = _live_tables(block_table, lo, n_local)
    return _mla_paged_flash(
        q_lat, q_pe, ck_pool, cpe_pool, cp_pool, tbl, live, qpos,
        scale=scale, partial=partial,
        interpret=_interpret() if interpret is None else interpret)


@_named_jit("mla_paged_flash",
            static_argnames=("scale", "partial", "interpret"))
def _mla_paged_flash(q_lat, q_pe, ck_pool, cpe_pool, cp_pool, tbl, live,
                     qpos, *, scale: float, partial: bool,
                     interpret: bool):
    B, C, h, kr = q_lat.shape
    rd = q_pe.shape[-1]
    n_pages, page = ck_pool.shape[:2]
    n_blocks = tbl.shape[1]
    R = h * C
    rb = MLA_ROW_BLOCK if R % MLA_ROW_BLOCK == 0 else R
    ql = q_lat.transpose(0, 2, 1, 3).reshape(B, R, kr)     # rows (h, c)
    qe = q_pe.transpose(0, 2, 1, 3).reshape(B, R, rd)
    qp = jnp.tile(qpos.astype(jnp.int32), (1, h))[..., None]    # (B, R, 1)
    kernel = functools.partial(_mla_kernel, n_blocks=n_blocks, scale=scale,
                               partial=partial)
    rows = lambda w: pl.BlockSpec((1, rb, w),
                                  lambda b, i, j, tbl, live: (b, i, 0))
    in_specs = [
        rows(1), rows(kr), rows(rd),
        pl.BlockSpec((1, page, kr),
                     lambda b, i, j, tbl, live: (tbl[b, j], 0, 0)),
        pl.BlockSpec((1, page, rd),
                     lambda b, i, j, tbl, live: (tbl[b, j], 0, 0)),
        pl.BlockSpec((1, 1, page),
                     lambda b, i, j, tbl, live: (tbl[b, j], 0, 0)),
    ]
    if partial:
        out_shape = (jax.ShapeDtypeStruct((B, R, 1), jnp.float32),) * 2 \
            + (jax.ShapeDtypeStruct((B, R, kr), jnp.float32),)
        out_specs = (rows(1), rows(1), rows(kr))
    else:
        out_shape = jax.ShapeDtypeStruct((B, R, kr), q_lat.dtype)
        out_specs = rows(kr)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, R // rb, n_blocks),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((rb, 1), jnp.float32),
                pltpu.VMEM((rb, 1), jnp.float32),
                pltpu.VMEM((rb, kr), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(tbl, live, qp, ql, qe, ck_pool, cpe_pool,
      cp_pool.reshape(n_pages, 1, page))
    if partial:
        m, l, a = out
        return (m.reshape(B, h, C), l.reshape(B, h, C),
                a.reshape(B, h, C, kr))
    return out.reshape(B, h, C, kr).transpose(0, 2, 1, 3)
