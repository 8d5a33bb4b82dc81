"""Jit'd public wrappers around the Pallas kernels: shape padding,
interpret-mode fallback on CPU, and the MoRLayer-facing helpers.

Off TPU every kernel runs with ``interpret=True`` — the kernel body
executes in Python against the same BlockSpec tiling the TPU uses, so
the CPU tests validate correctness (incl. the scalar-prefetch index
plumbing); on TPU the platform alone selects the Mosaic lowering.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import binary_dot as _bd
from repro.kernels import gather_matmul as _gm
from repro.kernels import masked_matmul as _mm
from repro.kernels import mor_predict as _mp


def _interpret() -> bool:
    """Interpret mode exactly when the platform is not a TPU."""
    return jax.default_backend() != "tpu"


def _pad_to(x, mult0, mult1):
    p0 = (-x.shape[0]) % mult0
    p1 = (-x.shape[1]) % mult1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


def binary_dot(x: jax.Array, w: jax.Array, *, bm: int = 128, bk: int = 512,
               bn: int = 128) -> jax.Array:
    """Padded/unpadded wrapper for kernels.binary_dot."""
    M, K = x.shape
    N = w.shape[1]
    bm_, bk_, bn_ = min(bm, max(M, 8)), min(bk, K), min(bn, N)
    xp = _pad_to(x, bm_, bk_)
    wp = _pad_to(w, bk_, bn_)
    out = _bd.binary_dot(xp, wp, bm=bm_, bk=bk_, bn=bn_,
                         interpret=_interpret())
    # K padding contributes sign_act(0)*sign_w(0) = (-1)*(+1) = -1 per
    # padded k to every cell (exactly), so add it back.
    k_pad = xp.shape[1] - K
    if k_pad:
        out = out + float(k_pad)
    return out[:M, :N]


def masked_matmul(x: jax.Array, w: jax.Array, tile_mask: jax.Array, *,
                  tile_m: int = 8, tile_n: int = 128,
                  bk: int = 512, with_counts: bool = False):
    """``with_counts`` also returns the live-tile count (telemetry)."""
    M, K = x.shape
    N = w.shape[1]
    bk_ = min(bk, K)
    if K % bk_ != 0:
        bk_ = K  # single K step when K is small/odd
    xp = _pad_to(x, tile_m, bk_)
    wp = _pad_to(w, bk_, tile_n)
    nm = xp.shape[0] // tile_m
    nn = wp.shape[1] // tile_n
    mask = tile_mask
    if mask.shape != (nm, nn):
        mask = jnp.pad(mask.astype(jnp.int32),
                       ((0, nm - mask.shape[0]), (0, nn - mask.shape[1])))
    out = _mm.masked_matmul(xp, wp, mask, tile_m=tile_m, tile_n=tile_n,
                            bk=bk_, interpret=_interpret(),
                            return_counts=with_counts)
    if with_counts:
        out, n_live = out
        return out[:M, :N], n_live
    return out[:M, :N]


def gather_matmul(x: jax.Array, w: jax.Array, tile_mask: jax.Array, *,
                  capacity: Optional[int] = None, capacity_frac: float = 1.0,
                  capacity_frac_live=None, tile_m: int = 8, tile_n: int = 128,
                  with_counts: bool = False):
    """``capacity``/``capacity_frac`` provision the STATIC tile budget;
    ``capacity_frac_live`` (traced scalar fraction, e.g. the serving
    telemetry's per-layer calibrated budget) clamps the realised live
    count under it without recompiling.  The kernel's blocks follow from
    the shapes (``gather_matmul.blocks``).  ``with_counts`` also returns
    (n_live_total, n_computed) tile counters and the kernel's int32
    [weight blocks issued, weight blocks] pair."""
    M = x.shape[0]
    N = w.shape[1]
    xp = _pad_to(x, tile_m, 1)
    wp = _pad_to(w, 1, tile_n)
    nm = xp.shape[0] // tile_m
    nn = wp.shape[1] // tile_n
    mask = tile_mask
    if mask.shape != (nm, nn):
        mask = jnp.pad(mask.astype(jnp.int32),
                       ((0, nm - mask.shape[0]), (0, nn - mask.shape[1])))
    if capacity is None:
        capacity = max(1, int(capacity_frac * nm * nn))
    capacity = min(capacity, nm * nn)
    cap_live = None
    if capacity_frac_live is not None:
        cap_live = jnp.maximum(1, jnp.ceil(
            jnp.asarray(capacity_frac_live, jnp.float32) * nm * nn)
        ).astype(jnp.int32)
    out = _gm.gather_matmul(xp, wp, mask, capacity=capacity, tile_m=tile_m,
                            tile_n=tile_n, cap_live=cap_live,
                            interpret=_interpret(),
                            return_counts=with_counts)
    if with_counts:
        out, n_live, n_comp, n_blocks = out
        return out[:M, :N], n_live, n_comp, n_blocks
    return out[:M, :N]


def masked_matmul_kdim(x: jax.Array, w: jax.Array, tile_mask: jax.Array, *,
                       tile_m: int = 8, tile_k: int = 128,
                       with_counts: bool = False):
    """Contraction-masked matmul (MoR down projection): tile_mask[i, k]
    gates the (tile_m x tile_k) block of x rows feeding output row-block
    i — dead FFN hidden tiles (exact zeros) are skipped, never MAC'd.
    The kernel's blocks follow from the shapes
    (``masked_matmul.kdim_blocks``).  ``with_counts`` also returns the
    kernel's int32 [weight blocks issued, weight blocks] pair."""
    M = x.shape[0]
    N = w.shape[1]
    xp = _pad_to(x, tile_m, tile_k)
    wp = _pad_to(w, tile_k, 1)
    nm = xp.shape[0] // tile_m
    nk = xp.shape[1] // tile_k
    mask = tile_mask
    if mask.shape != (nm, nk):
        # padded x blocks are zero -> mark them dead (skip is exact)
        mask = jnp.pad(mask.astype(jnp.int32),
                       ((0, nm - mask.shape[0]), (0, nk - mask.shape[1])))
    out = _mm.masked_matmul_kdim(xp, wp, mask, tile_m=tile_m, tile_k=tile_k,
                                 interpret=_interpret(),
                                 return_counts=with_counts)
    if with_counts:
        out, n_blocks = out
        return out[:M, :N], n_blocks
    return out[:M, :N]


def mor_tile_mask(x: jax.Array, w_perm: jax.Array, mor, proxy_neg: jax.Array,
                  *, residual=None, tile_m: int = 8, tile_n: int = 128,
                  bk: int = 512) -> jax.Array:
    """Fused predictor: build the (6, N) coef table from a MoRLayer and
    run the fused kernel.  proxy_neg: (M, N) bool or tri-state int8
    (0/1 = proxy verdict, 2 = forced skip, e.g. MoE capacity-pad rows).
    ``residual``: optional (M, N) per-element ReLU-input residual —
    enabled through the coef table's 6th row (res_scale = 1), so
    kernel-mode masks with a residual input no longer fall back to the
    jnp predictor.

    Counts as ONE predictor evaluation (same counter as the jnp
    ``hybrid_predict`` oracle — the MoRExecutionPlan once-per-forward
    contract is asserted across both paths)."""
    from repro.core.predictor import note_predictor_eval
    note_predictor_eval()
    M, K = x.shape
    N = w_perm.shape[1]
    res_row = (jnp.ones((N,), jnp.float32) if residual is not None
               else jnp.zeros((N,), jnp.float32))
    coef = jnp.stack([mor["m"], mor["b"], mor["bn_scale"], mor["bn_bias"],
                      mor["enable"].astype(jnp.float32), res_row], 0)
    bk_ = min(bk, K)
    if K % bk_ != 0:
        bk_ = K
    xp = _pad_to(x, tile_m, bk_)
    wp = _pad_to(w_perm, bk_, tile_n)
    # K padding adds (-1)*(+1) to every p_bin entry -> pre-compensate b
    k_pad = xp.shape[1] - K
    if k_pad:
        coef = coef.at[1, :].add(coef[0, :] * k_pad)
    n_pad = wp.shape[1] - N
    if n_pad:
        coef = jnp.pad(coef, ((0, 0), (0, n_pad)))
    # padded rows/cols must never mark a tile live (the jnp oracle pads
    # the neuron mask with False): encode them as proxy_neg = 2, the
    # kernel's forced-skip sentinel
    pn = jnp.pad(proxy_neg.astype(jnp.int8),
                 ((0, xp.shape[0] - M), (0, n_pad)), constant_values=2)
    res = None
    if residual is not None:
        res = jnp.pad(residual.astype(jnp.float32),
                      ((0, xp.shape[0] - M), (0, n_pad)))
    mask = _mp.mor_tile_mask(xp, wp, coef, pn, res, tile_m=tile_m,
                             tile_n=tile_n, bk=bk_, interpret=_interpret())
    return mask.astype(bool)
