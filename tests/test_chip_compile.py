"""Compile the serving path's Pallas kernels at published widths for a
described TPU v5e — no chip needed.  Interpret mode (every other kernel
test) cannot see what Mosaic refuses: unaligned blocks, layouts it
cannot relayout, VMEM overflow.  This file can.

The topology is described inside a module fixture (never at import):
only one process may load the TPU library at a time, and under xdist
the worker that runs this file is the one that loads it.  All such
compiles live in this one file for the same reason."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import gather_matmul as gm
from repro.kernels import masked_matmul as mm
from repro.kernels import mor_predict as mp
from repro.kernels import paged_attention as pk

GRANITE = get_config("granite-3-2b")
DEEPSEEK = get_config("deepseek-v2-236b")
N_SLOTS = 8                                   # the serving smoke trace
MAX_LEN = 512 + 32 + 2
ROWS = N_SLOTS * GRANITE.serve_chunk          # one chunked-prefill dispatch


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off
    (a described-device compile is written but can never be read back)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None
    return compiled


@pytest.mark.parametrize("residual", [False, True])
def test_mor_tile_mask_compiles(one_chip, residual):
    K, N = GRANITE.d_model, GRANITE.d_ff
    shapes = [((ROWS, K), jnp.bfloat16), ((K, N), jnp.bfloat16),
              ((mp.N_COEF_ROWS, N), jnp.float32), ((ROWS, N), jnp.int8)]
    if residual:
        shapes.append(((ROWS, N), jnp.float32))
    _compile(lambda *a: mp.mor_tile_mask(*a, interpret=False), shapes,
             one_chip)


def test_gather_matmul_compiles(one_chip):
    K, N = GRANITE.d_model, GRANITE.d_ff
    tm, tn = GRANITE.mor.tile_m, GRANITE.mor.tile_n
    n_tiles = (ROWS // tm) * (N // tn)
    _compile(lambda x, w, m: gm.gather_matmul(
        x, w, m, capacity=n_tiles, tile_m=tm, tile_n=tn, interpret=False),
        [((ROWS, K), jnp.bfloat16), ((K, N), jnp.bfloat16),
         ((ROWS // tm, N // tn), jnp.int32)], one_chip)


def test_masked_matmul_kdim_compiles(one_chip):
    K, N = GRANITE.d_ff, GRANITE.d_model        # the down projection
    tm = GRANITE.mor.tile_m
    _compile(lambda x, w, m: mm.masked_matmul_kdim(
        x, w, m, tile_m=tm, interpret=False),
        [((ROWS, K), jnp.bfloat16), ((K, N), jnp.bfloat16),
         ((ROWS // tm, K // 128), jnp.int32)], one_chip)


# the benchmark cell's dispatches: 20 decode rows (padded to 24) and a
# 640-row mixed prefill, with 8 x 128 MoR masks
@pytest.mark.parametrize("rows", [24, 640])
def test_gather_matmul_compiles_at_cell_shapes(one_chip, rows):
    K, N = GRANITE.d_model, GRANITE.d_ff        # gate and up
    tm, tn = GRANITE.mor.tile_m, GRANITE.mor.tile_n
    n_tiles = (rows // tm) * (N // tn)
    _compile(lambda x, w, m: gm.gather_matmul(
        x, w, m, capacity=n_tiles, tile_m=tm, tile_n=tn, interpret=False),
        [((rows, K), jnp.bfloat16), ((K, N), jnp.bfloat16),
         ((rows // tm, N // tn), jnp.int32)], one_chip)


@pytest.mark.parametrize("rows", [24, 640])
def test_masked_matmul_kdim_compiles_at_cell_shapes(one_chip, rows):
    K, N = GRANITE.d_ff, GRANITE.d_model        # down
    tm, tk = GRANITE.mor.tile_m, GRANITE.mor.tile_n
    _compile(lambda x, w, m: mm.masked_matmul_kdim(
        x, w, m, tile_m=tm, tile_k=tk, interpret=False),
        [((rows, K), jnp.bfloat16), ((K, N), jnp.bfloat16),
         ((rows // tm, K // tk), jnp.int32)], one_chip)


def _paged_geometry(cfg):
    page = cfg.serve_page
    n_blocks = -(-MAX_LEN // page)
    return page, n_blocks, N_SLOTS * n_blocks + 1


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("partial", [False, True])
@pytest.mark.parametrize("C", [1, GRANITE.serve_chunk])
def test_gqa_paged_flash_compiles(one_chip, C, partial, dtype):
    """bfloat16 serves; float32 is the four-chip smoke comparison."""
    cfg = GRANITE
    page, n_blocks, n_pages = _paged_geometry(cfg)
    hkv, D = cfg.n_kv_heads, cfg.head_dim
    _compile(lambda q, k, v, p, t, qp: pk.gqa_paged_flash(
        q, k, v, p, t, qp, partial=partial, interpret=False),
        [((N_SLOTS, C, cfg.n_heads, D), dtype),
         ((n_pages, page, hkv, D), dtype),
         ((n_pages, page, hkv, D), dtype),
         ((n_pages, page), jnp.int32), ((N_SLOTS, n_blocks), jnp.int32),
         ((N_SLOTS, C), jnp.int32)], one_chip)


@pytest.mark.parametrize("partial", [False, True])
@pytest.mark.parametrize("C", [1, DEEPSEEK.serve_chunk])
def test_mla_paged_flash_compiles(one_chip, C, partial):
    cfg = DEEPSEEK
    page, n_blocks, n_pages = _paged_geometry(cfg)
    h, kr, rd = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    scale = (cfg.qk_nope_head_dim + rd) ** -0.5
    _compile(lambda ql, qe, ck, cpe, cp, t, qp: pk.mla_paged_flash(
        ql, qe, ck, cpe, cp, t, qp, scale=scale, partial=partial,
        interpret=False),
        [((N_SLOTS, C, h, kr), jnp.bfloat16),
         ((N_SLOTS, C, h, rd), jnp.bfloat16),
         ((n_pages, page, kr), jnp.bfloat16),
         ((n_pages, page, rd), jnp.bfloat16),
         ((n_pages, page), jnp.int32), ((N_SLOTS, n_blocks), jnp.int32),
         ((N_SLOTS, C), jnp.int32)], one_chip)
