"""MoRExecutionPlan contract tests: ONE predictor evaluation per FFN
forward in every mode (incl. the GLU path), fused-kernel routing in
``kernel`` mode, capacity clipping, and the contraction-masked down
projection."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoRConfig
from repro.core import (build_mor_layer, cluster_layer, finalize_regression,
                        init_accumulator, update_accumulator,
                        predictor_eval_count, reset_predictor_eval_count)
from repro.core.executor import MoRExecutionPlan, as_plan
from repro.core.masked_ffn import mor_ffn_apply, mor_relu_matmul
from repro.core.predictor import binary_preact

RNG = np.random.default_rng(7)


@pytest.fixture(scope="module")
def calibrated():
    K, N, T = 96, 256, 512
    base = RNG.normal(size=(K, 32))
    w = np.stack([base[:, RNG.integers(32)] + 0.3 * RNG.normal(size=K)
                  for _ in range(N)], 1).astype(np.float32)
    x = RNG.normal(size=(T, K)).astype(np.float32)
    acc = init_accumulator(N)
    xj, wj = jnp.asarray(x[:384]), jnp.asarray(w)
    acc = update_accumulator(acc, binary_preact(xj, wj), xj @ wj)
    m, b, c = finalize_regression(acc)
    cl = cluster_layer(w, 85.0)
    mor = build_mor_layer(np.asarray(m), np.asarray(b), np.asarray(c), cl,
                          MoRConfig(corr_threshold=0.5))
    w_perm = wj[:, mor["perm"]]
    xe = jnp.asarray(x[384:])
    return xe, w_perm, mor


MODES = ("exact", "tiled", "kernel")


@pytest.mark.parametrize("mode", MODES)
def test_predictor_runs_once_relu_matmul(calibrated, mode):
    xe, w_perm, mor = calibrated
    reset_predictor_eval_count()
    y, st = mor_relu_matmul(xe, w_perm, mor, activation="relu", mode=mode)
    assert predictor_eval_count() == 1, mode
    assert np.isfinite(np.asarray(y)).all()


@pytest.mark.parametrize("mode", MODES)
def test_predictor_runs_once_glu_ffn(calibrated, mode):
    """The acceptance criterion: the GLU path historically re-ran
    hybrid_predict for the up matmul; a plan's single prediction now
    gates gate, up, AND down projections."""
    xe, w_perm, mor = calibrated
    K, N = w_perm.shape
    w_up = jnp.asarray(RNG.normal(size=(K, N)), jnp.float32)
    w_down = jnp.asarray(RNG.normal(size=(N, K)), jnp.float32)
    reset_predictor_eval_count()
    y, st = mor_ffn_apply(xe, w_up, w_down, mor, activation="relu",
                          mode=mode, w_gate=w_perm)
    assert predictor_eval_count() == 1, mode
    assert np.isfinite(np.asarray(y)).all()


@pytest.mark.parametrize("mode", MODES)
def test_predictor_runs_once_nonglu_ffn(calibrated, mode):
    xe, w_perm, mor = calibrated
    K, N = w_perm.shape
    w_down = jnp.asarray(RNG.normal(size=(N, K)), jnp.float32)
    reset_predictor_eval_count()
    y, _ = mor_ffn_apply(xe, w_perm, w_down, mor, activation="relu",
                         mode=mode)
    assert predictor_eval_count() == 1, mode
    assert np.isfinite(np.asarray(y)).all()


def test_kernel_mode_uses_fused_predictor_not_jnp(calibrated, monkeypatch):
    """mode='kernel' must route through kernels.ops.mor_tile_mask +
    gather_matmul, never the jnp hybrid_predict oracle."""
    import repro.core.executor as executor
    from repro.kernels import ops as kops

    xe, w_perm, mor = calibrated

    def _boom(*a, **k):
        raise AssertionError("jnp hybrid_predict called in kernel mode")

    monkeypatch.setattr(executor, "hybrid_predict", _boom)
    called = {}
    real_gather = kops.gather_matmul

    def spy_gather(*a, **k):
        called["gather"] = True
        return real_gather(*a, **k)

    monkeypatch.setattr(kops, "gather_matmul", spy_gather)
    y, st = mor_relu_matmul(xe, w_perm, mor, activation="relu",
                            mode="kernel")
    assert called.get("gather"), "kernel mode must use gather_matmul"
    assert np.isfinite(np.asarray(y)).all()


def test_glu_kernel_equals_tiled(calibrated):
    """The full GLU FFN (gate + up + contraction-masked down) in kernel
    mode matches the pure-jnp tiled oracle."""
    xe, w_perm, mor = calibrated
    K, N = w_perm.shape
    w_up = jnp.asarray(RNG.normal(size=(K, N)), jnp.float32)
    w_down = jnp.asarray(RNG.normal(size=(N, K)), jnp.float32)
    y_t, st_t = mor_ffn_apply(xe, w_up, w_down, mor, activation="relu",
                              mode="tiled", w_gate=w_perm)
    y_k, st_k = mor_ffn_apply(xe, w_up, w_down, mor, activation="relu",
                              mode="kernel", w_gate=w_perm)
    np.testing.assert_allclose(np.asarray(y_t), np.asarray(y_k),
                               rtol=2e-4, atol=2e-3)
    assert float(st_t["frac_tiles_live"]) == float(st_k["frac_tiles_live"])


def test_masked_matmul_kdim_oracle():
    from repro.kernels import ops, ref
    M, K, N = 32, 512, 96
    tm, tk = 8, 128
    x = jnp.asarray(RNG.normal(size=(M, K)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(K, N)), jnp.float32)
    mask = jnp.asarray(RNG.random((M // tm, K // tk)) > 0.4)
    # the MoR contract: dead x tiles are exact zeros
    from repro.core.policy import expand_tile_mask
    xz = jnp.where(expand_tile_mask(mask, tm, tk, M, K), x, 0.0)
    got = ops.masked_matmul_kdim(xz, w, mask, tile_m=tm, tile_k=tk)
    want = ref.masked_matmul_kdim_ref(xz, w, mask, tm, tk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-4)
    # and skipping really zeroes the dead tiles' contribution
    np.testing.assert_allclose(np.asarray(got), np.asarray(xz @ w),
                               rtol=2e-5, atol=2e-4)


def test_capacity_clip_limits_live_tiles(calibrated):
    xe, w_perm, mor = calibrated
    plan = as_plan(mor, mode="kernel", tile_m=8, tile_n=128,
                   capacity_frac=0.25)
    pred = plan.predict(xe, w_perm)
    n_tiles = pred.tiles.size
    assert int(jnp.sum(pred.kept)) <= max(1, int(0.25 * n_tiles))
    # kept is a subset of predicted-live
    assert bool(jnp.all(~pred.kept | pred.tiles))
    y, st = plan.relu_matmul(xe, w_perm, activation="relu")
    assert np.isfinite(np.asarray(y)).all()


def test_plan_is_a_pytree_and_scans():
    """Plans ride through tree_map and lax.scan: the MoRLayer is the
    child, mode/tiling are static aux — exactly what deploy.attach_plans
    relies on for scan-stacked models."""
    from repro.core.predictor import make_identity_layer
    L, N = 3, 128
    one = make_identity_layer(N)
    stacked = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (L,) + a.shape), one)
    plan = MoRExecutionPlan(stacked, mode="tiled", tile_m=8, tile_n=128)
    leaves, treedef = jax.tree_util.tree_flatten(plan)
    plan2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert plan2.mode == "tiled" and plan2.tile_m == 8

    def body(carry, p):
        assert isinstance(p, MoRExecutionPlan) and p.mode == "tiled"
        return carry, p.mor["m"].sum()

    _, sums = jax.lax.scan(body, 0.0, plan)
    assert sums.shape == (L,)


def test_as_plan_passthrough_and_wrapping(calibrated):
    _, _, mor = calibrated
    p = as_plan(mor, mode="tiled", tile_m=8, tile_n=128)
    assert p.active and p.mode == "tiled"
    # an existing plan's own config is authoritative
    p2 = as_plan(p, mode="kernel")
    assert p2 is p
    # non-MoRLayer dicts (e.g. {"experts": ...}) deactivate cleanly
    p3 = as_plan({"experts": None}, mode="tiled")
    assert not p3.active
    assert not as_plan(None, mode="kernel").active


def _column_layer(N, dead):
    """A MoRLayer whose predictor marks the columns in ``dead`` zero for
    every input (line -1, no proxy) and keeps every other column."""
    from repro.core.predictor import make_identity_layer
    mor = dict(make_identity_layer(N))
    enable = jnp.zeros((N,), bool).at[dead].set(True)
    mor.update(enable=enable, is_proxy=jnp.zeros((N,), bool),
               proxy_slot=jnp.full((N,), -1, jnp.int32),
               m=jnp.zeros((N,), jnp.float32),
               b=jnp.full((N,), -1.0, jnp.float32))
    return mor


@pytest.mark.parametrize("dead", ["none", "all"])
def test_frac_blocks_issued_glu_ffn(dead):
    """Kernel mode reports the share of weight blocks its three FFN
    kernels issued: 1.0 when every tile is live, below 1 when whole
    blocks are dead; the output matches the tiled oracle either way and
    non-kernel modes report 1.0."""
    K, N, T = 64, 256, 24
    mor = _column_layer(N, slice(0, N) if dead == "all" else slice(0, 0))
    x = jnp.asarray(RNG.normal(size=(T, K)), jnp.float32)
    wg, wu = (jnp.asarray(RNG.normal(size=(K, N)), jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(RNG.normal(size=(N, K)), jnp.float32)
    out = {}
    for mode in ("tiled", "kernel"):
        plan = as_plan(mor, mode=mode, tile_m=8, tile_n=128)
        out[mode] = plan.ffn(x, wu, wd, activation="relu", w_gate=wg)
    (y_t, st_t), (y_k, st_k) = out["tiled"], out["kernel"]
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_t),
                               rtol=2e-4, atol=2e-3)
    assert float(st_t["frac_blocks_issued"]) == 1.0
    assert float(st_k["frac_tiles_computed"]) == \
        float(st_t["frac_tiles_computed"])
    if dead == "all":
        assert float(st_k["frac_blocks_issued"]) < 1.0
        assert not np.any(np.asarray(y_k))
    else:
        assert float(st_k["frac_blocks_issued"]) == 1.0


def test_frac_blocks_issued_expert_ffn():
    """Under vmap (MoE expert_ffn): an expert with no routed rows issues
    no block, the others issue every block; outputs match tiled mode."""
    E, C, K, N = 3, 16, 64, 256
    one = _column_layer(N, slice(0, 0))
    em = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (E,) + a.shape), one)
    eb = jnp.asarray(RNG.normal(size=(E, C, K)), jnp.float32)
    wg, wu = (jnp.asarray(RNG.normal(size=(E, K, N)), jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(RNG.normal(size=(E, N, K)), jnp.float32)
    row_mask = jnp.ones((E, C), bool).at[0].set(False)
    eb = jnp.where(row_mask[..., None], eb, 0.0)
    res = {}
    for mode in ("tiled", "kernel"):
        plan = MoRExecutionPlan(em, mode=mode, tile_m=8, tile_n=128)
        res[mode] = plan.expert_ffn(eb, wu, wd, activation="relu",
                                    w_gate=wg, row_mask=row_mask)
    np.testing.assert_allclose(np.asarray(res["kernel"][0]),
                               np.asarray(res["tiled"][0]),
                               rtol=2e-4, atol=2e-3)
    frac = np.asarray(res["kernel"][1]["frac_blocks_issued"])
    assert frac.shape == (E,)
    assert frac[0] < 1.0 and np.all(frac[1:] == 1.0)
    assert np.all(np.asarray(res["tiled"][1]["frac_blocks_issued"]) == 1.0)
