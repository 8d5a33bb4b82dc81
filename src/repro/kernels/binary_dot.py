"""Pallas TPU kernel: the binary rookie's +-1 sign matmul.

TPU adaptation of the paper's binary Compute Units (binCUs, §4.4): signs
are materialised as int8 in VMEM and the product runs on the MXU as an
int8 x int8 -> int32 matmul.  Block shapes keep the working set
(bm*bk + bk*bn int8 + bm*bn int32) well under VMEM and MXU-aligned
(multiples of 128 in the lane dim).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, o_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xs = jnp.where(x_ref[...] > 0, 1, -1).astype(jnp.int8)   # act: 0 -> -1
    ws = jnp.where(w_ref[...] >= 0, 1, -1).astype(jnp.int8)  # weight sign
    acc_ref[...] += jax.lax.dot_general(
        xs, ws, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret"))
def binary_dot(x: jax.Array, w: jax.Array, *, bm: int = 128, bk: int = 512,
               bn: int = 128, interpret: bool = False) -> jax.Array:
    """x: (M, K), w: (K, N) -> float32 (M, N) = sign(x) @ sign(w).
    M/K/N must be multiples of the block shape (ops.py pads)."""
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (x.shape, w.shape)
    bm, bk, bn = min(bm, M), min(bk, K), min(bn, N)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0
    grid = (M // bm, N // bn, K // bk)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w)
