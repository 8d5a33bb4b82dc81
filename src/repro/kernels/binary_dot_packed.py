"""Pallas TPU kernel: binary dot product from BIT-PACKED sign weights.

The paper keeps 1-bit weights in a dedicated 2 KB binWeight SRAM (§4.4)
at zero DRAM overhead (sign bits of the 8-bit weights).  The TPU
translation: the signs are packed offline 8-per-uint8 (`pack_signs`), so
the predictor's weight traffic is 1/16 of the bf16 weights — the packed
table stays VMEM-resident for realistic layer sizes, exactly like the
paper's SRAM.  The kernel unpacks in-register (shift+mask on the VPU)
and feeds the +-1 int8 matmul to the MXU.

Layout: packed[k8, n] bit b of packed[k8, n] = sign bit (1 = negative)
of w[k8 * 8 + b, n].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def pack_signs(w: jax.Array) -> jax.Array:
    """(K, N) float -> (ceil(K/8), N) uint8 sign bitmap (1 = negative)."""
    K, N = w.shape
    pad = (-K) % 8
    bits = (w < 0).astype(jnp.uint8)
    if pad:
        bits = jnp.pad(bits, ((0, pad), (0, 0)))  # pad signs = 0 -> +1
    bits = bits.reshape(-1, 8, N)
    shifts = jnp.arange(8, dtype=jnp.uint8)[None, :, None]
    return jnp.sum(bits << shifts, axis=1).astype(jnp.uint8)


def unpack_signs(packed: jax.Array, K: int) -> jax.Array:
    """Inverse of pack_signs -> (K, N) int8 in {+1, -1}."""
    k8, N = packed.shape
    shifts = jnp.arange(8, dtype=jnp.uint8)[None, :, None]
    bits = (packed[:, None, :] >> shifts) & jnp.uint8(1)
    signs = 1 - 2 * bits.astype(jnp.int8)
    return signs.reshape(k8 * 8, N)[:K]


def _kernel(x_ref, wp_ref, o_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xs = jnp.where(x_ref[...] > 0, 1, -1).astype(jnp.int8)
    # in-register unpack: (bk/8, bn) uint8 -> (bk, bn) +-1 int8
    packed = wp_ref[...]
    bk8, bn = packed.shape
    shifts = jax.lax.broadcasted_iota(jnp.uint8, (bk8, 8, bn), 1)
    bits = (packed[:, None, :] >> shifts) & jnp.uint8(1)
    ws = (1 - 2 * bits.astype(jnp.int8)).reshape(bk8 * 8, bn)
    acc_ref[...] += jax.lax.dot_general(
        xs, ws, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret"))
def binary_dot_packed(x: jax.Array, w_packed: jax.Array, *, bm: int = 128,
                      bk: int = 512, bn: int = 128,
                      interpret: bool = False) -> jax.Array:
    """x: (M, K) float; w_packed: (K/8, N) uint8 -> (M, N) float32.
    K must be a multiple of 8 and of bk; M/N multiples of bm/bn."""
    M, K = x.shape
    k8, N = w_packed.shape
    assert k8 * 8 == K, (x.shape, w_packed.shape)
    bm, bk, bn = min(bm, M), min(bk, K), min(bn, N)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0 and bk % 8 == 0
    grid = (M // bm, N // bn, K // bk)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk // 8, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w_packed)
