"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(interpret mode on CPU; lowering targets TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.predictor import make_identity_layer
from repro.kernels import ops, ref

RNG = np.random.default_rng(3)

SHAPES = [(8, 128, 128), (16, 256, 384), (48, 200, 300), (128, 512, 256),
          (5, 64, 130)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_binary_dot_sweep(shape, dtype):
    M, K, N = shape
    x = jnp.asarray(RNG.normal(size=(M, K)), dtype)
    w = jnp.asarray(RNG.normal(size=(K, N)), dtype)
    got = ops.binary_dot(x, w)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.binary_dot_ref(x, w)))


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_masked_matmul_sweep(shape, dtype):
    M, K, N = shape
    tm, tn = 8, 128
    x = jnp.asarray(RNG.normal(size=(M, K)), dtype)
    w = jnp.asarray(RNG.normal(size=(K, N)), dtype)
    nm, nn = -(-M // tm), -(-N // tn)
    mask = jnp.asarray(RNG.random((nm, nn)) > 0.5)
    got = ops.masked_matmul(x, w, mask, tile_m=tm, tile_n=tn)
    want = ref.masked_matmul_ref(x, w, mask, tm, tn)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol * 10)


def test_masked_matmul_dead_tiles_exact_zero():
    x = jnp.asarray(RNG.normal(size=(16, 64)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(64, 256)), jnp.float32)
    mask = jnp.zeros((2, 2), bool).at[0, 0].set(True)
    out = np.asarray(ops.masked_matmul(x, w, mask, tile_m=8, tile_n=128))
    assert np.all(out[8:, :] == 0.0)
    assert np.all(out[:, 128:] == 0.0)
    assert np.any(out[:8, :128] != 0.0)


@pytest.mark.parametrize("capacity_frac", [0.25, 0.5, 1.0])
def test_gather_matmul_capacity(capacity_frac):
    M, K, N = 32, 128, 512
    tm, tn = 8, 128
    x = jnp.asarray(RNG.normal(size=(M, K)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(K, N)), jnp.float32)
    nm, nn = M // tm, N // tn
    mask = jnp.asarray(RNG.random((nm, nn)) > 0.4)
    cap = max(1, int(capacity_frac * nm * nn))
    got = ops.gather_matmul(x, w, mask, capacity=cap, tile_m=tm, tile_n=tn)
    want = ref.gather_matmul_ref(x, w, mask, tm, tn, cap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-4)


def test_gather_and_masked_matmul_liveness_counts():
    """The telemetry-facing count outputs: n_live = mask sum; n_computed
    clamps to the static capacity AND the traced cap_live budget."""
    M, K, N = 32, 128, 512
    tm, tn = 8, 128
    x = jnp.asarray(RNG.normal(size=(M, K)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(K, N)), jnp.float32)
    nm, nn = M // tm, N // tn
    mask = jnp.asarray(RNG.random((nm, nn)) > 0.4)
    n_mask = int(np.asarray(mask).sum())
    out, n_live, n_comp, _ = ops.gather_matmul(x, w, mask, tile_m=tm,
                                               tile_n=tn, with_counts=True)
    assert int(n_live) == n_mask and int(n_comp) == n_mask
    # traced per-layer budget clamps the computed count, not the demand
    out2, n_live2, n_comp2, _ = ops.gather_matmul(
        x, w, mask, tile_m=tm, tile_n=tn,
        capacity_frac_live=jnp.asarray(0.25, jnp.float32),
        with_counts=True)
    assert int(n_live2) == n_mask
    assert int(n_comp2) == min(n_mask, max(1, int(np.ceil(0.25 * nm * nn))))
    out3, n_live3 = ops.masked_matmul(x, w, mask, tile_m=tm, tile_n=tn,
                                      with_counts=True)
    assert int(n_live3) == n_mask
    np.testing.assert_allclose(np.asarray(out), np.asarray(out3),
                               rtol=2e-5, atol=2e-4)


def test_gather_matmul_all_live_fully_dense():
    M, K, N = 16, 64, 256
    x = jnp.asarray(RNG.normal(size=(M, K)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(K, N)), jnp.float32)
    mask = jnp.ones((2, 2), bool)
    got = ops.gather_matmul(x, w, mask, capacity=4, tile_m=8, tile_n=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x @ w),
                               rtol=2e-5, atol=2e-4)


@pytest.mark.parametrize("shape", [(16, 128, 256), (40, 96, 384)])
def test_fused_mor_tile_mask(shape):
    M, K, N = shape
    x = jnp.asarray(RNG.normal(size=(M, K)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(K, N)), jnp.float32)
    mor = make_identity_layer(N)
    mor["enable"] = jnp.asarray(RNG.random(N) > 0.3)
    mor["m"] = jnp.asarray(RNG.normal(1, 0.3, N), jnp.float32)
    mor["b"] = jnp.asarray(RNG.normal(0, 2, N), jnp.float32)
    mor["bn_scale"] = jnp.asarray(RNG.gamma(2, 1, N), jnp.float32)
    mor["bn_bias"] = jnp.asarray(RNG.normal(0, 1, N), jnp.float32)
    pn = jnp.asarray(RNG.random((M, N)) > 0.4)
    got = ops.mor_tile_mask(x, w, mor, pn, tile_m=8, tile_n=128)
    want = ref.mor_tile_mask_ref(x, w, mor["m"], mor["b"], mor["bn_scale"],
                                 mor["bn_bias"], mor["enable"], pn, 8, 128)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("shape", [(16, 128, 256), (40, 96, 384)])
def test_fused_mor_tile_mask_residual(shape):
    """The 6th coef row: a per-element residual input shifts the fitted
    line inside the fused kernel (matching hybrid_predict's residual
    handling) — kernel-mode masks with residual inputs no longer fall
    back to the jnp predictor."""
    M, K, N = shape
    x = jnp.asarray(RNG.normal(size=(M, K)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(K, N)), jnp.float32)
    mor = make_identity_layer(N)
    mor["enable"] = jnp.asarray(RNG.random(N) > 0.3)
    mor["m"] = jnp.asarray(RNG.normal(1, 0.3, N), jnp.float32)
    mor["b"] = jnp.asarray(RNG.normal(0, 2, N), jnp.float32)
    mor["bn_scale"] = jnp.asarray(RNG.gamma(2, 1, N), jnp.float32)
    mor["bn_bias"] = jnp.asarray(RNG.normal(0, 1, N), jnp.float32)
    pn = jnp.asarray(RNG.random((M, N)) > 0.4)
    res = jnp.asarray(RNG.normal(0, 3, (M, N)), jnp.float32)
    got = ops.mor_tile_mask(x, w, mor, pn, residual=res, tile_m=8,
                            tile_n=128)
    want = ref.mor_tile_mask_ref(x, w, mor["m"], mor["b"], mor["bn_scale"],
                                 mor["bn_bias"], mor["enable"], pn, 8, 128,
                                 residual=res)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # a dominating negative residual must kill every tile the proxy +
    # rookie agree on (here: all of them) — proving the input is wired
    mor2 = dict(mor)
    mor2["enable"] = jnp.ones((N,), bool)
    kill = jnp.full((M, N), -1e6, jnp.float32)
    dead = ops.mor_tile_mask(x, w, mor2, jnp.ones((M, N), bool),
                             residual=kill, tile_m=8, tile_n=128)
    assert not np.any(np.asarray(dead))


def test_executor_kernel_mode_residual_uses_fused_predictor(monkeypatch):
    """ROADMAP follow-up closed: mode='kernel' with a residual input must
    route through the fused kernel, never the jnp hybrid_predict."""
    import repro.core.executor as executor
    from repro.core.masked_ffn import mor_relu_matmul
    from repro.core.policy import build_mor_layer
    from repro.configs.base import MoRConfig
    K, N, T = 64, 256, 32
    w = RNG.normal(size=(K, N)).astype(np.float32)
    xs = RNG.normal(size=(T, K)).astype(np.float32)
    m = np.ones(N, np.float32)
    b = np.zeros(N, np.float32)
    c = np.full(N, 0.9, np.float32)
    mor = build_mor_layer(m, b, c, None, MoRConfig(corr_threshold=0.5))
    res = jnp.asarray(RNG.normal(size=(T, N)), jnp.float32)

    def _boom(*a, **k):
        raise AssertionError("jnp hybrid_predict called in kernel mode "
                             "with residual")
    monkeypatch.setattr(executor, "hybrid_predict", _boom)
    y, st = mor_relu_matmul(jnp.asarray(xs), jnp.asarray(w), mor,
                            activation="relu", mode="kernel", residual=res)
    assert np.isfinite(np.asarray(y)).all()
    # tiled oracle agrees on the outputs
    monkeypatch.undo()
    y_t, _ = mor_relu_matmul(jnp.asarray(xs), jnp.asarray(w), mor,
                             activation="relu", mode="tiled", residual=res)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_t),
                               rtol=2e-4, atol=2e-3)


@pytest.mark.parametrize("shape", [(16, 128, 256), (32, 512, 384)])
def test_binary_dot_packed(shape):
    """Bit-packed sign weights (8/byte, the binWeight-SRAM analogue)
    reproduce the unpacked binary dot exactly."""
    from repro.kernels.binary_dot_packed import (binary_dot_packed,
                                                 pack_signs, unpack_signs)
    M, K, N = shape
    x = jnp.asarray(RNG.normal(size=(M, K)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(K, N)), jnp.float32)
    packed = pack_signs(w)
    assert packed.shape == (K // 8, N) and packed.dtype == jnp.uint8
    # pack/unpack roundtrip
    signs = unpack_signs(packed, K)
    np.testing.assert_array_equal(
        np.asarray(signs), np.where(np.asarray(w) < 0, -1, 1))
    got = binary_dot_packed(x, packed, interpret=True)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.binary_dot_ref(x, w)))


# -- MoR FFN matmuls: kernel blocks apart from the 8 x 128 decision tile ------

BLOCK_ROWS = [8, 24, 40, 136]
BLOCK_MASKS = ["all_live", "all_dead", "one_per_block", "random",
               "capacity"]
# "rule": the shape-derived blocks (one block at these sizes); "split":
# small explicit blocks, so rows, columns and contraction span several
# blocks and the row count is ragged against them
GATHER_BLOCKS = {"rule": {},
                 "split": {"block_m": 16, "block_n": 256, "block_k": 128}}
KDIM_BLOCKS = {"rule": {},
               "split": {"block_m": 16, "block_n": 128, "block_k": 256}}
TM, TN = 8, 128


def _tile_mask(kind, nm, nn, rows_per_block, cols_per_block, rng):
    """(nm, nn) bool tile mask; "one_per_block" puts exactly one live
    tile in each (rows_per_block x cols_per_block) block of tiles."""
    if kind in ("all_live",):
        return np.ones((nm, nn), bool)
    if kind == "all_dead":
        return np.zeros((nm, nn), bool)
    if kind == "one_per_block":
        m = np.zeros((nm, nn), bool)
        for bi in range(-(-nm // rows_per_block)):
            for bj in range(nn // cols_per_block):
                r = bi * rows_per_block + (bi + bj) % rows_per_block
                m[min(r, nm - 1), bj * cols_per_block + bi % cols_per_block] \
                    = True
        return m
    return rng.random((nm, nn)) > (0.5 if kind == "random" else 0.3)


def _capacity_clip(mask, cap):
    flat = mask.reshape(-1)
    return (flat & (np.cumsum(flat) - 1 < cap)).reshape(mask.shape)


def _blocks_issued(kept, rows, bm, bn, tile_m, tile_n):
    nm, nn = kept.shape
    k = np.zeros((rows // tile_m, nn), bool)
    k[:nm] = kept
    return int(k.reshape(rows // bm, bm // tile_m, nn * tile_n // bn,
                         bn // tile_n).any(axis=(1, 3)).sum())


@pytest.mark.parametrize("blocks", sorted(GATHER_BLOCKS))
@pytest.mark.parametrize("kind", BLOCK_MASKS)
@pytest.mark.parametrize("rows", BLOCK_ROWS)
def test_gather_matmul_blocks_oracle(rows, kind, blocks):
    """Blocks apart from tiles: the kept tiles match the oracle, every
    other tile is an exact zero, the tile counters are the tile mask's
    and a block is issued exactly when it holds a kept tile."""
    from repro.kernels import blocking
    from repro.kernels import gather_matmul as gm
    rng = np.random.default_rng(rows * 31 + len(kind))
    K, N = 256, 512
    nm, nn = rows // TM, N // TN
    bm, mp, bk, bn = gm.blocks(rows, K, N, TM, TN, 4)
    opts = GATHER_BLOCKS[blocks]
    if opts:
        bm, bn, bk = opts["block_m"], opts["block_n"], opts["block_k"]
        mp = -(-rows // bm) * bm
    mask = _tile_mask(kind, nm, nn, bm // TM, bn // TN, rng)
    x = jnp.asarray(rng.normal(size=(rows, K)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, N)), jnp.float32)
    cap, cap_live = nm * nn, None
    if kind == "capacity":                    # static + traced budgets
        cap, cap_live = max(1, nm * nn // 2), max(1, (3 * nm * nn) // 10)
    out, n_live, n_comp, n_blocks = gm.gather_matmul(
        x, w, jnp.asarray(mask), capacity=cap, tile_m=TM, tile_n=TN,
        cap_live=None if cap_live is None else jnp.int32(cap_live),
        interpret=True, return_counts=True, **opts)
    want = ref.gather_matmul_cap_ref(x, w, jnp.asarray(mask), TM, TN, cap,
                                     cap_live=cap_live)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-4)
    kept = _capacity_clip(mask, min(cap, cap_live or cap))
    dead = ~np.repeat(np.repeat(kept, TM, 0), TN, 1)
    assert np.all(np.asarray(out)[dead] == 0.0)
    assert int(n_live) == mask.sum() and int(n_comp) == kept.sum()
    n_kb = K // bk
    total = (mp // bm) * (N // bn) * n_kb
    issued = _blocks_issued(kept, mp, bm, bn, TM, TN) * n_kb
    assert np.asarray(n_blocks).tolist() == [issued, total]
    if kind in ("all_live", "one_per_block"):
        assert issued == total
    if kind == "all_dead":
        assert issued == 0 < total
    if blocks == "rule":
        assert (bm, mp) == blocking.row_blocks(rows, TM) == (rows, rows)


@pytest.mark.parametrize("blocks", sorted(KDIM_BLOCKS))
@pytest.mark.parametrize("kind", BLOCK_MASKS)
@pytest.mark.parametrize("rows", BLOCK_ROWS)
def test_masked_matmul_kdim_blocks_oracle(rows, kind, blocks):
    """The contraction-masked kernel over blocks of many tiles: exact
    under the MoR contract (dead x tiles are zeros), dead (row block,
    k block) pairs never issued."""
    from repro.kernels import masked_matmul as mm
    rng = np.random.default_rng(rows * 17 + len(kind))
    K, N = 512, 256
    nm, nk = rows // TM, K // TN
    bm, mp, bk, bn = mm.kdim_blocks(rows, K, N, TM, TN, 4)
    opts = KDIM_BLOCKS[blocks]
    if opts:
        bm, bn, bk = opts["block_m"], opts["block_n"], opts["block_k"]
        mp = -(-rows // bm) * bm
    mask = _tile_mask(kind, nm, nk, bm // TM, bk // TN, rng)
    if kind == "capacity":
        mask = _capacity_clip(mask, max(1, (3 * nm * nk) // 10))
    x = jnp.asarray(rng.normal(size=(rows, K)), jnp.float32)
    xz = jnp.where(np.repeat(np.repeat(mask, TM, 0), TN, 1), x, 0.0)
    w = jnp.asarray(rng.normal(size=(K, N)), jnp.float32)
    out, n_blocks = mm.masked_matmul_kdim(
        xz, w, jnp.asarray(mask), tile_m=TM, tile_k=TN, interpret=True,
        return_counts=True, **opts)
    want = ref.masked_matmul_kdim_ref(xz, w, jnp.asarray(mask), TM, TN)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-4)
    n_cb = N // bn
    total = (mp // bm) * (K // bk) * n_cb
    issued = _blocks_issued(mask, mp, bm, bk, TM, TN) * n_cb
    assert np.asarray(n_blocks).tolist() == [issued, total]
    if kind == "all_dead":
        assert issued == 0 < total and not np.any(np.asarray(out))
    if kind in ("all_live", "one_per_block"):
        assert issued == total


@pytest.mark.parametrize("blocks", ["rule", "split"])
@pytest.mark.parametrize("kernel", ["gather_matmul", "masked_matmul_kdim"])
def test_mor_ffn_matmul_blocks_under_vmap(kernel, blocks):
    """The batched-expert path: per-expert slot lists and per-expert
    traced cap_live under jax.vmap, against expert_gather_matmul_ref
    (gather) and the vmapped kdim oracle; an expert whose tiles are all
    dead issues no block."""
    from repro.kernels import gather_matmul as gm
    from repro.kernels import masked_matmul as mm
    rng = np.random.default_rng(5)
    E, M, K, N = 3, 40, 256, 4 * TN
    nm = M // TM
    masks = np.stack([np.zeros((nm, 4), bool), rng.random((nm, 4)) > 0.5,
                      np.ones((nm, 4), bool)])
    if kernel == "gather_matmul":
        opts = GATHER_BLOCKS[blocks]
        x = jnp.asarray(rng.normal(size=(E, M, K)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(E, K, N)), jnp.float32)
        cap, caps = nm * 4, jnp.asarray([4, 7, nm * 4], jnp.int32)
        out, _, _, n_blocks = jax.vmap(
            lambda xe, we, me, ce: gm.gather_matmul(
                xe, we, me, capacity=cap, tile_m=TM, tile_n=TN, cap_live=ce,
                interpret=True, return_counts=True, **opts))(
            x, w, jnp.asarray(masks), caps)
        want = ref.expert_gather_matmul_ref(x, w, jnp.asarray(masks), TM, TN,
                                            cap, cap_live=caps)
    else:
        opts = KDIM_BLOCKS[blocks]
        keep = np.repeat(np.repeat(masks, TM, 1), TN, 2)
        x = jnp.where(keep, rng.normal(size=keep.shape), 0.0).astype(
            jnp.float32)
        w = jnp.asarray(rng.normal(size=(E, 4 * TN, 256)), jnp.float32)
        out, n_blocks = jax.vmap(
            lambda xe, we, me: mm.masked_matmul_kdim(
                xe, we, me, tile_m=TM, tile_k=TN, interpret=True,
                return_counts=True, **opts))(x, w, jnp.asarray(masks))
        want = jax.vmap(lambda xe, we, me: ref.masked_matmul_kdim_ref(
            xe, we, me, TM, TN))(x, w, jnp.asarray(masks))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-4)
    n_blocks = np.asarray(n_blocks)
    assert n_blocks[0, 0] == 0 and n_blocks[2, 0] == n_blocks[2, 1] > 0
