"""Attention: GQA/MQA (full + sliding-window), MLA (DeepSeek-V2), with
flash-style chunked softmax for long sequences and ring-buffer /
absorbed-latent decode caches.

All einsums accumulate softmax statistics in fp32; activations stay in the
model compute dtype.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed import decode_attention as da
from repro.kernels import paged_attention as pk
from repro.models.layers.common import dense_init, split_keys
from repro.models.layers.norms import norm_init, apply_norm
from repro.models.layers.rope import apply_rope

NEG_INF = da.NEG_INF      # one mask floor across paged/sharded layouts
_FLASH_THRESHOLD = 4096   # use chunked attention above this many kv positions
_CHUNK = 1024


def set_flash_threshold(n: int) -> None:
    """Perf knob (dry-run --flash-threshold): kv length above which the
    chunked-softmax path replaces the S x S materialising sdpa."""
    global _FLASH_THRESHOLD
    _FLASH_THRESHOLD = n


# ===========================================================================
# GQA / MQA
# ===========================================================================

def gqa_init(key, cfg: ModelConfig) -> Dict:
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    pd = jnp.dtype(cfg.param_dtype)
    ks = split_keys(key, 4)
    p = {
        "wq": dense_init(ks[0], d, h * hd, pd),
        "wk": dense_init(ks[1], d, hkv * hd, pd),
        "wv": dense_init(ks[2], d, hkv * hd, pd),
        "wo": dense_init(ks[3], h * hd, d, pd),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), pd)
        p["bk"] = jnp.zeros((hkv * hd,), pd)
        p["bv"] = jnp.zeros((hkv * hd,), pd)
    return p


def _qkv(params, cfg: ModelConfig, x):
    B, S, _ = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = x.dtype
    q = x @ params["wq"].astype(dt)
    k = x @ params["wk"].astype(dt)
    v = x @ params["wv"].astype(dt)
    if cfg.qkv_bias:
        q = q + params["bq"].astype(dt)
        k = k + params["bk"].astype(dt)
        v = v + params["bv"].astype(dt)
    return (q.reshape(B, S, h, hd), k.reshape(B, S, hkv, hd),
            v.reshape(B, S, hkv, hd))


def _mask_bias(q_pos, kv_pos, causal: bool, window: int):
    """(Sq, Skv) additive bias from position vectors — the unbatched
    face of ``decode_attention.position_ok``, so the teacher-forced,
    slotted, paged and sharded paths all share ONE mask predicate."""
    ok = da.position_ok(q_pos[:, None], kv_pos[None, :], causal, window)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def _sdpa(q, k, v, bias):
    """q:(B,Sq,H,D) k:(B,Skv,Hkv,D) v:(B,Skv,Hkv,Dv) bias:(Sq,Skv)."""
    B, Sq, H, D = q.shape
    Hkv, Dv = k.shape[2], v.shape[-1]
    G = H // Hkv
    qf = q.reshape(B, Sq, Hkv, G, D)
    s = jnp.einsum("bqkgd,btkd->bkgqt", qf, k,
                   preferred_element_type=jnp.float32)
    s = s * (D ** -0.5) + bias
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqt,btkd->bqkgd", p.astype(v.dtype), v)
    return o.reshape(B, Sq, H, Dv)


def _flash(q, k, v, q_pos, kv_pos, causal: bool, window: int):
    """Chunked-softmax attention: scan over kv chunks with running
    (max, denom, acc) — bounds temp memory to one (Sq, CHUNK) tile."""
    B, Sq, H, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    G = H // Hkv
    C = min(_CHUNK, Skv)
    n_chunks = (Skv + C - 1) // C
    pad = n_chunks * C - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, (0, pad), constant_values=-1)
    kc = k.reshape(B, n_chunks, C, Hkv, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, C, Hkv, Dv).transpose(1, 0, 2, 3, 4)
    pc = kv_pos.reshape(n_chunks, C)
    qf = (q.reshape(B, Sq, Hkv, G, D) * (D ** -0.5)).astype(q.dtype)

    def step(carry, inp):
        m, l, acc = carry
        kb, vb, pb = inp
        s = jnp.einsum("bqkgd,btkd->bkgqt", qf, kb,
                       preferred_element_type=jnp.float32)
        s = s + _mask_bias(q_pos, pb, causal, window)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bkgqt,btkd->bkgqd", p.astype(vb.dtype), vb).astype(jnp.float32)
        return (m_new, l, acc), None

    m0 = jnp.full((B, Hkv, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, Hkv, G, Sq, Dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kc, vc, pc))
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    return o.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, Dv).astype(q.dtype)


def _banded(q, k, v, q_pos, kv_pos, window: int):
    """Sliding-window attention that only touches in-window kv chunks:
    for q-chunk i, dynamic-slice kv rows [i*C - W, i*C + C).  Sub-quadratic
    in sequence length (O(S * (W + C)))."""
    B, S, H, D = q.shape
    C = min(_CHUNK, S)
    n_chunks = S // C if S % C == 0 else None
    assert n_chunks is not None, "banded path expects seq % chunk == 0"
    W = window
    span = W + C

    kp = jnp.pad(k, ((0, 0), (W, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (W, 0), (0, 0), (0, 0)))
    pp = jnp.pad(kv_pos, (W, 0), constant_values=-1)

    def one_chunk(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * C, C, axis=1)
        qpi = jax.lax.dynamic_slice_in_dim(q_pos, i * C, C)
        ki = jax.lax.dynamic_slice_in_dim(kp, i * C, span, axis=1)
        vi = jax.lax.dynamic_slice_in_dim(vp, i * C, span, axis=1)
        ppi = jax.lax.dynamic_slice_in_dim(pp, i * C, span)
        bias = _mask_bias(qpi, ppi, True, W)
        return _sdpa(qi, ki, vi, bias)

    out = jax.lax.map(one_chunk, jnp.arange(n_chunks))
    return out.transpose(1, 0, 2, 3, 4).reshape(B, S, H, v.shape[-1])


def attend(q, k, v, q_pos, kv_pos, *, causal: bool, window: int = 0):
    Skv = k.shape[1]
    if window and q.shape[1] == k.shape[1] and q.shape[1] % min(_CHUNK, q.shape[1]) == 0 \
            and q.shape[1] > window:
        return _banded(q, k, v, q_pos, kv_pos, window)
    if Skv > _FLASH_THRESHOLD:
        return _flash(q, k, v, q_pos, kv_pos, causal, window)
    bias = _mask_bias(q_pos, kv_pos, causal, window)
    return _sdpa(q, k, v, bias)


def attend_batched(q, k, v, q_pos, kv_pos, *, causal: bool = True,
                   window: int = 0):
    """Attention with PER-BATCH-ROW positions: q_pos (B, Sq), kv_pos
    (B, Skv).  This is the continuous-batching slot-pool case — every
    slot sits at its own position, so the additive bias carries a batch
    dim instead of being shared.  kv entries tagged -1 are masked.
    The mask itself lives in ``decode_attention.batched_bias`` so the
    sharded partial-flash path shares the exact same semantics."""
    bias = da.batched_bias(q_pos, kv_pos, causal, window)
    return _sdpa(q, k, v, bias[:, None, None])


def gqa_forward(params, cfg: ModelConfig, x, positions):
    q, k, v = _qkv(params, cfg, x)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    pos1 = positions[0] if positions.ndim == 2 else positions
    o = attend(q, k, v, pos1, pos1, causal=cfg.causal,
               window=cfg.sliding_window)
    B, S = x.shape[:2]
    return o.reshape(B, S, -1) @ params["wo"].astype(x.dtype)


# --- decode cache -----------------------------------------------------------

def gqa_cache_init(cfg: ModelConfig, batch: int, max_len: int, dtype):
    W = cfg.sliding_window or max_len
    L = min(W, max_len)
    hkv, hd = cfg.n_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, L, hkv, hd), dtype),
        "v": jnp.zeros((batch, L, hkv, hd), dtype),
        "pos": jnp.full((L,), -1, jnp.int32),
    }


def _tp_flash_decode(q, k, v, kv_pos, pos, window: int):
    """Decode attention over a sequence-sharded KV cache: each 'model'
    shard computes local flash statistics (max, denom, acc) over its
    S/P slice; one pmax + two psums merge the softmax exactly.  Replaces
    GSPMD's derived strategy, which all-gathered the sharded KV
    (measured 16 GB/step on qwen2-7b decode_32k)."""
    from repro.distributed.sharding_rules import _TLS
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    ctx = getattr(_TLS, "ctx", None)
    if ctx is None:
        return None
    mesh, _ = ctx
    if "model" not in mesh.axis_names or mesh.shape["model"] == 1:
        return None
    P_ = mesh.shape["model"]
    B, Skv = k.shape[0], k.shape[1]
    if Skv % P_ != 0:
        return None
    dp_axes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    dp = 1
    for a in dp_axes:
        dp *= mesh.shape[a]
    b_spec = (dp_axes if len(dp_axes) > 1 else dp_axes[0]) \
        if (dp_axes and B % dp == 0) else None

    def body(qb, kb, vb, pb):
        D = qb.shape[-1]
        s = jnp.einsum("bqkgd,btkd->bkgqt", qb, kb,
                       preferred_element_type=jnp.float32) * (D ** -0.5)
        s = s + _mask_bias(jnp.full((1,), pos, jnp.int32), pb[0],
                           True, window)
        m_loc = s.max(-1)
        m_glob = jax.lax.pmax(m_loc, "model")
        p = jnp.exp(s - m_glob[..., None])
        l = jax.lax.psum(p.sum(-1), "model")
        acc = jax.lax.psum(
            jnp.einsum("bkgqt,btkd->bkgqd", p.astype(vb.dtype),
                       vb).astype(jnp.float32), "model")
        return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(qb.dtype)

    Hkv = k.shape[2]
    G = q.shape[2] // Hkv
    qf = q.reshape(B, 1, Hkv, G, q.shape[-1])
    out = shard_map(
        body, mesh=mesh,
        in_specs=(P(b_spec), P(b_spec, "model"), P(b_spec, "model"),
                  P(None, "model")),
        out_specs=P(b_spec), check_vma=False,
    )(qf, k, v, kv_pos[None, :])
    return out.reshape(B, 1, q.shape[2], q.shape[-1])


def gqa_decode(params, cfg: ModelConfig, x, cache, pos):
    """x: (B, 1, d); pos: scalar int32 current position."""
    B = x.shape[0]
    q, k, v = _qkv(params, cfg, x)
    pvec = jnp.full((B, 1), pos, jnp.int32)
    q = apply_rope(q, pvec, cfg.rope_theta)
    k = apply_rope(k, pvec, cfg.rope_theta)
    L = cache["k"].shape[1]
    slot = pos % L
    ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
    cp = jax.lax.dynamic_update_slice_in_dim(
        cache["pos"], jnp.full((1,), pos, jnp.int32), slot, axis=0)
    o = _tp_flash_decode(q, ck, cv, cp, pos, cfg.sliding_window)
    if o is None:
        o = attend(q, ck, cv, jnp.full((1,), pos, jnp.int32), cp,
                   causal=True, window=cfg.sliding_window)
    y = o.reshape(B, 1, -1) @ params["wo"].astype(x.dtype)
    return y, {"k": ck, "v": cv, "pos": cp}


def gqa_prefill(params, cfg: ModelConfig, x, cache):
    """Batched prefill: consume the whole (B, S, d) prompt in one step,
    attending within the prompt (forward-style causal attention) while
    writing all S kv rows into the FRESH decode cache at once.  Replaces
    S single-token decode dispatches with one compiled step.

    Assumes the cache is empty (pos == 0) and S fits the ring buffer
    (S <= cache length); ``launch.steps.make_prefill_step`` falls back to
    a scanned decode when that doesn't hold."""
    B, S, _ = x.shape
    q, k, v = _qkv(params, cfg, x)
    pvec = jnp.arange(S, dtype=jnp.int32)[None, :].repeat(B, 0)
    q = apply_rope(q, pvec, cfg.rope_theta)
    k = apply_rope(k, pvec, cfg.rope_theta)
    ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, 0, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, 0, axis=1)
    tags = jnp.arange(S, dtype=jnp.int32)
    if cache["pos"].ndim == 2:      # slot-pool layout: per-slot (B, Lr) tags
        cp = jax.lax.dynamic_update_slice(
            cache["pos"], jnp.broadcast_to(tags[None], (B, S)), (0, 0))
    else:
        cp = jax.lax.dynamic_update_slice_in_dim(cache["pos"], tags, 0,
                                                 axis=0)
    pos1 = jnp.arange(S, dtype=jnp.int32)
    o = attend(q, k, v, pos1, pos1, causal=True, window=cfg.sliding_window)
    y = o.reshape(B, S, -1) @ params["wo"].astype(x.dtype)
    return y, {"k": ck, "v": cv, "pos": cp}


def gqa_chunk(params, cfg: ModelConfig, x, cache, pos, valid,
              block_table=None):
    """Slot-pool chunk step: consume x (B, C, d) starting at PER-SLOT
    positions ``pos`` (B,), with ``valid`` (B, C) marking real tokens
    (a slot mid-prompt has a full row; an idle or decoding slot has
    n_valid 0 or 1).  Invalid tokens are dropped from the ring-buffer
    write (out-of-range scatter index), so an idle slot's cache is
    bit-identical before and after the dispatch.

    With ``block_table`` (B, n_blocks) the cache is the PAGED layout
    ({k/v (n_pages, page, hkv, hd), pos (n_pages, page)}): ring row
    ``r = qpos % (n_blocks * page)`` lives at physical page
    ``block_table[b, r // page]``, offset ``r % page``.  Reads gather
    the slot's ring view through the table (null-page rows carry -1
    position tags and mask out); writes scatter through the same
    indirection — the pool guarantees every written page is exclusively
    owned (copy-on-write happens host-side before dispatch).

    Under a page-shard context (``distributed.decode_attention``, the
    engine's ``paged-sharded`` layout) the pool arrays are the LOCAL
    page range of a mesh-sharded pool: writes drop pages another shard
    owns, reads gather only locally-resident pages, and attention
    becomes a distributed flash decode — partial (m, l, acc) statistics
    per shard merged with one collective per layer.

    The ring must have ≥ chunk-length slack above the attention window
    (``serving.kv_pool`` allocates window + serve_chunk) so that the
    oldest in-window entries are not overwritten by the chunk itself."""
    B, C, _ = x.shape
    q, k, v = _qkv(params, cfg, x)
    qpos = pos[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    q = apply_rope(q, qpos, cfg.rope_theta)
    k = apply_rope(k, qpos, cfg.rope_theta)
    if block_table is not None:
        page = cache["k"].shape[1]
        ring = block_table.shape[1] * page
        r = qpos % ring
        blk, off = r // page, r % page
        pidx = jnp.take_along_axis(block_table, blk, axis=1)
        ck = da.pool_set(cache["k"], pidx, off, k, valid)
        cv = da.pool_set(cache["v"], pidx, off, v, valid)
        cp = da.pool_set(cache["pos"], pidx, off, qpos, valid)
        if da.shard_info() is not None:
            o = da.gqa_paged_attend(q, ck, cv, cp, block_table, qpos,
                                    window=cfg.sliding_window)
        elif pk.enabled():
            # fused paged flash decode: attend straight off the block
            # table — no materialised ring view, null pages skipped at
            # the grid level
            o = pk.gqa_paged_flash(q, ck, cv, cp, block_table, qpos,
                                   window=cfg.sliding_window)
        else:
            hkv, hd = k.shape[2], k.shape[3]
            gk = da.pool_view(ck, block_table, 0).reshape(B, ring, hkv, hd)
            gv = da.pool_view(cv, block_table, 0).reshape(B, ring, hkv, hd)
            gp = da.pool_view(cp, block_table, -1).reshape(B, ring)
            o = attend_batched(q, gk, gv, qpos, gp, causal=True,
                               window=cfg.sliding_window)
        y = o.reshape(B, C, -1) @ params["wo"].astype(x.dtype)
        return y, {"k": ck, "v": cv, "pos": cp}
    Lr = cache["k"].shape[1]
    slot = jnp.where(valid, qpos % Lr, Lr)          # Lr is OOB -> dropped
    bidx = jnp.arange(B)[:, None]
    ck = cache["k"].at[bidx, slot].set(k, mode="drop")
    cv = cache["v"].at[bidx, slot].set(v, mode="drop")
    cp = cache["pos"].at[bidx, slot].set(qpos, mode="drop")
    o = attend_batched(q, ck, cv, qpos, cp, causal=True,
                       window=cfg.sliding_window)
    y = o.reshape(B, C, -1) @ params["wo"].astype(x.dtype)
    return y, {"k": ck, "v": cv, "pos": cp}


# ===========================================================================
# MLA (DeepSeek-V2): low-rank joint kv compression + decoupled RoPE head
# ===========================================================================

def mla_init(key, cfg: ModelConfig) -> Dict:
    d, h = cfg.d_model, cfg.n_heads
    nd, rd, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    qr, kr = cfg.q_lora_rank, cfg.kv_lora_rank
    pd = jnp.dtype(cfg.param_dtype)
    ks = split_keys(key, 6)
    return {
        "wq_a": dense_init(ks[0], d, qr, pd),
        "q_norm": norm_init(cfg.norm, qr),
        "wq_b": dense_init(ks[1], qr, h * (nd + rd), pd),
        "wkv_a": dense_init(ks[2], d, kr + rd, pd),
        "kv_norm": norm_init(cfg.norm, kr),
        "wk_b": dense_init(ks[3], kr, h * nd, pd),
        "wv_b": dense_init(ks[4], kr, h * vd, pd),
        "wo": dense_init(ks[5], h * vd, d, pd),
    }


def _mla_q(params, cfg: ModelConfig, x, positions):
    B, S, _ = x.shape
    h = cfg.n_heads
    nd, rd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    dt = x.dtype
    cq = apply_norm(cfg.norm, params["q_norm"], x @ params["wq_a"].astype(dt))
    q = (cq @ params["wq_b"].astype(dt)).reshape(B, S, h, nd + rd)
    q_nope, q_pe = q[..., :nd], q[..., nd:]
    q_pe = apply_rope(q_pe, positions, cfg.rope_theta)
    return q_nope, q_pe


def _mla_kv_compress(params, cfg: ModelConfig, x, positions):
    kr, rd = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    dt = x.dtype
    kv = x @ params["wkv_a"].astype(dt)
    c_kv = apply_norm(cfg.norm, params["kv_norm"], kv[..., :kr])
    k_pe = apply_rope(kv[..., kr:][:, :, None, :], positions,
                      cfg.rope_theta)[:, :, 0, :]
    return c_kv, k_pe


def mla_forward(params, cfg: ModelConfig, x, positions):
    """Prefill/train path: expand the latent kv to per-head k/v."""
    B, S, _ = x.shape
    h = cfg.n_heads
    nd, rd, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    kr = cfg.kv_lora_rank
    dt = x.dtype
    q_nope, q_pe = _mla_q(params, cfg, x, positions)
    c_kv, k_pe = _mla_kv_compress(params, cfg, x, positions)
    k_nope = (c_kv @ params["wk_b"].astype(dt)).reshape(B, S, h, nd)
    v = (c_kv @ params["wv_b"].astype(dt)).reshape(B, S, h, vd)
    # pack rope dims into k/q so we can reuse the shared attend()
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None, :], (B, S, h, rd))], -1)
    q_full = jnp.concatenate([q_nope, q_pe], -1)
    pos1 = positions[0] if positions.ndim == 2 else positions
    o = attend(q_full, k_full, v, pos1, pos1, causal=True, window=0)
    return o.reshape(B, S, h * vd) @ params["wo"].astype(dt)


def mla_cache_init(cfg: ModelConfig, batch: int, max_len: int, dtype):
    return {
        "c_kv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
        "k_pe": jnp.zeros((batch, max_len, cfg.qk_rope_head_dim), dtype),
    }


def mla_prefill(params, cfg: ModelConfig, x, cache):
    """Batched MLA prefill: run the expanded (forward-style) attention
    over the whole prompt while writing the latent kv cache rows [0, S)
    in one shot.  Assumes a fresh cache (pos == 0)."""
    B, S, _ = x.shape
    h = cfg.n_heads
    nd, rd, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    dt = x.dtype
    pvec = jnp.arange(S, dtype=jnp.int32)[None, :].repeat(B, 0)
    q_nope, q_pe = _mla_q(params, cfg, x, pvec)
    c_kv, k_pe = _mla_kv_compress(params, cfg, x, pvec)
    ck = jax.lax.dynamic_update_slice_in_dim(cache["c_kv"], c_kv, 0, axis=1)
    cp = jax.lax.dynamic_update_slice_in_dim(cache["k_pe"], k_pe, 0, axis=1)
    k_nope = (c_kv @ params["wk_b"].astype(dt)).reshape(B, S, h, nd)
    v = (c_kv @ params["wv_b"].astype(dt)).reshape(B, S, h, vd)
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None, :], (B, S, h, rd))], -1)
    q_full = jnp.concatenate([q_nope, q_pe], -1)
    pos1 = jnp.arange(S, dtype=jnp.int32)
    o = attend(q_full, k_full, v, pos1, pos1, causal=True, window=0)
    y = o.reshape(B, S, h * vd) @ params["wo"].astype(dt)
    new_cache = {"c_kv": ck, "k_pe": cp}
    if "pos" in cache:              # slot-pool layout carries kv pos tags
        new_cache["pos"] = jax.lax.dynamic_update_slice(
            cache["pos"], jnp.broadcast_to(pos1[None], (B, S)), (0, 0))
    return y, new_cache


def mla_chunk(params, cfg: ModelConfig, x, cache, pos, valid,
              block_table=None):
    """Slot-pool chunk step for MLA (absorbed latent attention): x
    (B, C, d) at per-slot positions ``pos`` (B,); ``valid`` (B, C) gates
    the cache scatter.  The cache carries per-slot position tags
    (``cache["pos"]``, (B, max_len), -1 = empty) so each slot only
    attends to its own written prefix.

    With ``block_table`` (B, n_blocks) the latent cache is the PAGED
    layout ({c_kv (n_pages, page, kr), k_pe, pos (n_pages, page)}):
    absolute position p lives at page ``block_table[b, p // page]``,
    offset ``p % page`` (no ring — MLA caches the full max_len).  Under
    a page-shard context the pools are the local range of a mesh-
    sharded pool and the absorbed attention runs as a distributed flash
    decode in latent space (partial stats per shard, one collective
    merge, W_uv absorbed after the merge)."""
    B, C, _ = x.shape
    h, nd, vd = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    kr, rd = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    dt = x.dtype
    qpos = pos[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    q_nope, q_pe = _mla_q(params, cfg, x, qpos)          # (B,C,h,nd/rd)
    c_kv_t, k_pe_t = _mla_kv_compress(params, cfg, x, qpos)
    wk_b = params["wk_b"].astype(dt).reshape(kr, h, nd)
    wv_b = params["wv_b"].astype(dt).reshape(kr, h, vd)
    q_lat = jnp.einsum("bchd,khd->bchk", q_nope, wk_b)   # absorb W_uk
    if block_table is not None:
        page = cache["c_kv"].shape[1]
        blk, off = qpos // page, qpos % page
        pidx = jnp.take_along_axis(block_table, blk, axis=1)
        ck_pool = da.pool_set(cache["c_kv"], pidx, off, c_kv_t, valid)
        cpe_pool = da.pool_set(cache["k_pe"], pidx, off, k_pe_t, valid)
        cp_pool = da.pool_set(cache["pos"], pidx, off, qpos, valid)
        new_cache = {"c_kv": ck_pool, "k_pe": cpe_pool, "pos": cp_pool}
        if da.shard_info() is not None:
            o_lat = da.mla_paged_attend(q_lat, q_pe, ck_pool, cpe_pool,
                                        cp_pool, block_table, qpos,
                                        scale=(nd + rd) ** -0.5)
            o = jnp.einsum("bchk,khv->bchv", o_lat, wv_b)  # absorb W_uv
            y = o.reshape(B, C, h * vd) @ params["wo"].astype(dt)
            return y, new_cache
        if pk.enabled():
            o_lat = pk.mla_paged_flash(q_lat, q_pe, ck_pool, cpe_pool,
                                       cp_pool, block_table, qpos,
                                       scale=(nd + rd) ** -0.5)
            o = jnp.einsum("bchk,khv->bchv", o_lat, wv_b)  # absorb W_uv
            y = o.reshape(B, C, h * vd) @ params["wo"].astype(dt)
            return y, new_cache
        ring = block_table.shape[1] * page
        ck = da.pool_view(ck_pool, block_table, 0).reshape(B, ring, kr)
        cpe = da.pool_view(cpe_pool, block_table, 0).reshape(B, ring, rd)
        cp = da.pool_view(cp_pool, block_table, -1).reshape(B, ring)
    else:
        ML = cache["c_kv"].shape[1]
        idx = jnp.where(valid, qpos, ML)                 # ML is OOB -> drop
        bidx = jnp.arange(B)[:, None]
        ck = cache["c_kv"].at[bidx, idx].set(c_kv_t, mode="drop")
        cpe = cache["k_pe"].at[bidx, idx].set(k_pe_t, mode="drop")
        cp = cache["pos"].at[bidx, idx].set(qpos, mode="drop")
        new_cache = {"c_kv": ck, "k_pe": cpe, "pos": cp}
    s = (jnp.einsum("bchk,btk->bhct", q_lat, ck,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bchr,btr->bhct", q_pe, cpe,
                      preferred_element_type=jnp.float32))
    s = s * ((nd + rd) ** -0.5)
    ok = da.position_ok(qpos[:, None, :, None], cp[:, None, None, :], True, 0)
    s = jnp.where(ok, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(dt)
    o_lat = jnp.einsum("bhct,btk->bchk", p, ck)
    o = jnp.einsum("bchk,khv->bchv", o_lat, wv_b)        # absorb W_uv
    y = o.reshape(B, C, h * vd) @ params["wo"].astype(dt)
    return y, new_cache


def mla_decode(params, cfg: ModelConfig, x, cache, pos):
    """Absorbed decode: attention runs in the rank-512 latent space; the
    per-head k/v are never materialised (cache is (S, kv_lora+rope))."""
    B = x.shape[0]
    h, nd, vd = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    kr, rd = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    dt = x.dtype
    pvec = jnp.full((B, 1), pos, jnp.int32)
    q_nope, q_pe = _mla_q(params, cfg, x, pvec)      # (B,1,h,nd/rd)
    c_kv_t, k_pe_t = _mla_kv_compress(params, cfg, x, pvec)
    ck = jax.lax.dynamic_update_slice_in_dim(cache["c_kv"], c_kv_t, pos, axis=1)
    cp = jax.lax.dynamic_update_slice_in_dim(cache["k_pe"], k_pe_t, pos, axis=1)
    wk_b = params["wk_b"].astype(dt).reshape(kr, h, nd)
    wv_b = params["wv_b"].astype(dt).reshape(kr, h, vd)
    q_lat = jnp.einsum("bohd,khd->bhk", q_nope, wk_b)        # absorb W_uk
    s = (jnp.einsum("bhk,btk->bht", q_lat, ck,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bohr,btr->bht", q_pe, cp,
                      preferred_element_type=jnp.float32))
    s = s * ((nd + rd) ** -0.5)
    t_idx = jnp.arange(ck.shape[1])
    s = jnp.where(t_idx[None, None, :] <= pos, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(dt)
    o_lat = jnp.einsum("bht,btk->bhk", p, ck)
    o = jnp.einsum("bhk,khv->bhv", o_lat, wv_b)              # absorb W_uv
    y = o.reshape(B, 1, h * vd) @ params["wo"].astype(dt)
    return y, {"c_kv": ck, "k_pe": cp}
