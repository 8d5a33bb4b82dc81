"""Expert-level MoR differential matrix (ISSUE 3): exact == tiled ==
kernel expert outputs for ``moe_apply`` AND ``moe_apply_a2a``, swept
over (experts, top_k, capacity factor, tile geometry, dtype) including
ragged tails; dispatch/capacity property tests (plain seeded versions —
the hypothesis variants live in test_property_hypothesis.py); and the
dense-mode regression (no predictor work when MoR is off)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_config
from repro.core.predictor import (predictor_eval_count,
                                  reset_predictor_eval_count)
from repro.models.layers import moe

RNG = np.random.default_rng(11)


def truth_proxy_layer(f: int, E: int) -> dict:
    """(E,)-stacked MoRLayer whose skips are EXACTLY the true zeros:
    every neuron is its own proxy (evaluated at base precision), the
    binary rookie always votes skip (m=0, b=-1), so skip == true ReLU
    zero.  Predicted-dead neurons then contribute exact zeros in every
    mode, making exact (neuron-granular) == tiled/kernel (tile-granular)
    == dense a hard equality — the differential matrix's oracle."""
    idx = jnp.arange(f, dtype=jnp.int32)
    one = {
        "m": jnp.zeros((f,), jnp.float32),
        "b": jnp.full((f,), -1.0, jnp.float32),
        "enable": jnp.ones((f,), bool),
        "proxy_slot": idx,
        "is_proxy": jnp.zeros((f,), bool),
        "perm": idx,
        "inv_perm": idx,
        "bn_scale": jnp.ones((f,), jnp.float32),
        "bn_bias": jnp.zeros((f,), jnp.float32),
    }
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (E,) + a.shape), one)


def _moe_cfg(E, k, cf, tile_m, tile_n):
    from repro.configs.base import MoRConfig
    cfg = reduce_config(get_config("mixtral-8x7b"))
    return cfg.replace(
        n_experts=E, top_k=k, capacity_factor=cf, n_shared_experts=0,
        mor=MoRConfig(enabled=True, relufied=True, tile_m=tile_m,
                      tile_n=tile_n))


# -- the differential matrix: moe_apply ------------------------------------

@pytest.mark.parametrize("E,k,cf", [(4, 2, 1.25), (8, 2, 4.0), (4, 1, 2.0)])
@pytest.mark.parametrize("tile_m,tile_n", [(8, 128), (4, 16), (8, 32)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_moe_modes_differential(E, k, cf, tile_m, tile_n, dtype):
    """exact == tiled == kernel (== dense, since the truth-proxy layer
    only skips true zeros) over routing/capacity/tile sweeps.  T = 21
    gives ragged capacity buffers (C % tile_m != 0) for every cf."""
    cfg = _moe_cfg(E, k, cf, tile_m, tile_n)
    key = jax.random.PRNGKey(E * 10 + k)
    params = moe.moe_init(key, cfg)
    dt = jnp.dtype(dtype)
    x = jax.random.normal(key, (3, 7, cfg.d_model), jnp.float32).astype(dt)
    f = cfg.moe_d_ff or cfg.d_ff
    em = truth_proxy_layer(f, E)

    y_dense, _ = moe.moe_apply(params, cfg, x)
    tol = dict(rtol=2e-4, atol=2e-3) if dtype == "float32" else \
        dict(rtol=4e-2, atol=8e-2)
    outs = {}
    for mode in ("exact", "tiled", "kernel"):
        y, aux = moe.moe_apply(params, cfg, x, mor={"experts": em},
                               mor_mode=mode)
        outs[mode] = np.asarray(y, np.float32)
        stats = aux["mor_stats"]
        assert np.asarray(stats["frac_tiles_live"]).shape == (E,)
        np.testing.assert_allclose(outs[mode],
                                   np.asarray(y_dense, np.float32),
                                   err_msg=f"{mode} vs dense", **tol)
    # modes agree with each other even tighter than with dense
    np.testing.assert_allclose(outs["tiled"], outs["exact"], **tol)
    np.testing.assert_allclose(outs["kernel"], outs["tiled"], **tol)


def test_moe_modes_differential_with_token_mask():
    """Same equality through the serving path (token_mask + the
    serving-shape-aware lossless capacity)."""
    cfg = _moe_cfg(4, 2, 1.25, 4, 16)
    key = jax.random.PRNGKey(3)
    params = moe.moe_init(key, cfg)
    x = jax.random.normal(key, (2, 9, cfg.d_model), jnp.float32)
    tm = jnp.asarray(np.array([[True] * 9, [True] * 5 + [False] * 4]))
    f = cfg.moe_d_ff or cfg.d_ff
    em = truth_proxy_layer(f, 4)
    y_dense, _ = moe.moe_apply(params, cfg, x, token_mask=tm)
    for mode in ("exact", "tiled", "kernel"):
        y, _ = moe.moe_apply(params, cfg, x, mor={"experts": em},
                             mor_mode=mode, token_mask=tm)
        valid = np.asarray(tm)[..., None]
        np.testing.assert_allclose(
            np.asarray(y) * valid, np.asarray(y_dense) * valid,
            rtol=2e-4, atol=2e-3, err_msg=mode)


# -- the differential matrix: moe_apply_a2a (EP shard_map) ------------------

_A2A_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config, reduce_config
from repro.configs.base import MoRConfig
from repro.distributed.sharding_rules import activation_context
from repro.models.layers import moe

E, k = 4, 2
cfg = reduce_config(get_config("mixtral-8x7b")).replace(
    n_experts=E, top_k=k, n_shared_experts=0,
    capacity_factor=float(E) / k,          # lossless: local == global
    expert_sharding="ep_shmap",
    mor=MoRConfig(enabled=True, relufied=True, tile_m=4, tile_n=16))
key = jax.random.PRNGKey(0)
params = moe.moe_init(key, cfg)
f = cfg.moe_d_ff or cfg.d_ff
idx = jnp.arange(f, dtype=jnp.int32)
one = {"m": jnp.zeros((f,), jnp.float32),
       "b": jnp.full((f,), -1.0, jnp.float32),
       "enable": jnp.ones((f,), bool),
       "proxy_slot": idx, "is_proxy": jnp.zeros((f,), bool),
       "perm": idx, "inv_perm": idx,
       "bn_scale": jnp.ones((f,), jnp.float32),
       "bn_bias": jnp.zeros((f,), jnp.float32)}
em = jax.tree_util.tree_map(
    lambda a: jnp.broadcast_to(a[None], (E,) + a.shape), one)
# tokens divisible by dp * MP on a (data=4, model=2) mesh
x = jax.random.normal(key, (8, 4, cfg.d_model), jnp.float32)
mesh = jax.make_mesh((4, 2), ("data", "model"))
with activation_context(mesh):
    y_dense, _ = moe.moe_apply_a2a(params, cfg, x)
    assert y_dense is not None
    for mode in ("exact", "tiled", "kernel"):
        y, _ = moe.moe_apply_a2a(params, cfg, x, mor={"experts": em},
                                 mor_mode=mode)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_dense),
                                   rtol=2e-4, atol=2e-3, err_msg=mode)
# single-chip reference: same math without the mesh
y_ref, _ = moe.moe_apply(params, cfg.replace(expert_sharding="tp"), x)
np.testing.assert_allclose(np.asarray(y_dense), np.asarray(y_ref),
                           rtol=2e-4, atol=2e-3)
# an attached plan's calibrated per-expert cap_live budget must engage
# on the a2a path too (sliced over the expert axis like the weights)
from repro.core.executor import MoRExecutionPlan
capped = MoRExecutionPlan(em, mode="tiled", tile_m=4, tile_n=16,
                          cap_live=jnp.full((E,), 0.05, jnp.float32))
with activation_context(mesh):
    y_cap, _ = moe.moe_apply_a2a(params, cfg, x, mor={"experts": capped})
assert np.isfinite(np.asarray(y_cap)).all()
assert float(np.abs(np.asarray(y_cap) - np.asarray(y_dense)).max()) > 1e-4, \
    "cap_live budget did not engage on the a2a path"
print("A2A_MODES_OK")
"""


def test_moe_a2a_modes_differential():
    """Expert slicing (EP shard_map): exact == tiled == kernel with
    expert-MoR leaves sliced over the model axis, and the sharded result
    matches the single-chip moe_apply."""
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", _A2A_SCRIPT],
                       capture_output=True, text=True, env=env,
                       cwd=os.getcwd())
    assert r.returncode == 0, r.stderr[-3000:]
    assert "A2A_MODES_OK" in r.stdout


# -- dense-mode regression: MoR off must mean NO predictor work -------------

def test_moe_dense_mode_runs_no_predictor():
    """mor_mode="dense" (MoR off) must skip predictor work entirely in
    MoE — the old code built exact-mode plans regardless of the
    requested mode.  Also: an attached plan whose own mode is "dense"
    stays off even under a non-dense mor_mode argument."""
    from repro.core.executor import MoRExecutionPlan
    cfg = _moe_cfg(4, 2, 1.25, 8, 128)
    key = jax.random.PRNGKey(1)
    params = moe.moe_init(key, cfg)
    x = jax.random.normal(key, (2, 6, cfg.d_model), jnp.float32)
    f = cfg.moe_d_ff or cfg.d_ff
    em = truth_proxy_layer(f, 4)

    reset_predictor_eval_count()
    y, aux = moe.moe_apply(params, cfg, x, mor={"experts": em},
                           mor_mode="dense")
    assert predictor_eval_count() == 0
    assert "mor_stats" not in aux
    # attached plan with mode="dense" is authoritative (never re-armed)
    plan = MoRExecutionPlan(em, mode="dense")
    y2, aux2 = moe.moe_apply(params, cfg, x,
                             mor={"experts": plan}, mor_mode="tiled")
    assert predictor_eval_count() == 0
    assert "mor_stats" not in aux2
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y))
    # and a live mode runs the predictor EXACTLY once per layer call
    moe.moe_apply(params, cfg, x, mor={"experts": em}, mor_mode="tiled")
    assert predictor_eval_count() == 1


# -- per-expert capacity clamps --------------------------------------------

def test_expert_cap_live_clamps_per_expert():
    """Per-expert traced cap_live budgets clamp each expert's realised
    tile compute independently (the attach path for calibrated
    per-(layer, expert) capacities)."""
    from repro.core.executor import MoRExecutionPlan
    E, C, d, f = 3, 16, 64, 256
    rng = np.random.default_rng(5)
    eb = jnp.asarray(rng.normal(size=(E, C, d)), jnp.float32)
    wu = jnp.asarray(rng.normal(size=(E, d, f)), jnp.float32)
    wd = jnp.asarray(rng.normal(size=(E, f, d)), jnp.float32)
    # everything predicted live (enable off -> no skips) so the clamp is
    # the only thing cutting compute
    idx = jnp.arange(f, dtype=jnp.int32)
    one = {"m": jnp.ones((f,), jnp.float32),
           "b": jnp.zeros((f,), jnp.float32),
           "enable": jnp.zeros((f,), bool),
           "proxy_slot": jnp.full((f,), -1, jnp.int32),
           "is_proxy": jnp.zeros((f,), bool), "perm": idx, "inv_perm": idx,
           "bn_scale": jnp.ones((f,), jnp.float32),
           "bn_bias": jnp.zeros((f,), jnp.float32)}
    em = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (E,) + a.shape), one)
    caps = jnp.asarray([0.25, 0.5, 1.0], jnp.float32)
    for mode in ("tiled", "kernel"):
        plan = MoRExecutionPlan(em, mode=mode, tile_m=8, tile_n=64,
                                cap_live=caps)
        _, stats = plan.expert_ffn(eb, wu, wd, activation="relu")
        comp = np.asarray(stats["frac_tiles_computed"])
        n_tiles = (C // 8) * (f // 64)
        for e in range(E):
            budget = np.ceil(float(caps[e]) * n_tiles) / n_tiles
            assert comp[e] <= budget + 1e-6, (mode, e, comp[e], budget)
        # tighter budget -> no more compute than the looser one
        assert comp[0] <= comp[1] + 1e-6 <= comp[2] + 2e-6


def test_gather_matmul_cap_counts_and_zeroes():
    """The kernel's count outputs never exceed cap_live, and rows of
    tiles beyond the clamp are exact zeros (plain seeded version of the
    hypothesis property; oracle = ref.gather_matmul_cap_ref)."""
    from repro.kernels import ops as kops
    from repro.kernels.ref import gather_matmul_cap_ref
    rng = np.random.default_rng(9)
    for trial in range(6):
        nm, nn = int(rng.integers(1, 5)), int(rng.integers(1, 5))
        tm, tn = 8, 16
        M, K, N = nm * tm, 32, nn * tn
        x = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(K, N)), jnp.float32)
        mask = jnp.asarray(rng.random((nm, nn)) > 0.4)
        cap_frac = float(rng.uniform(0.2, 1.0))
        cap_live = float(rng.uniform(0.1, 1.0))
        out, n_live, n_comp, _ = kops.gather_matmul(
            x, w, mask, capacity_frac=cap_frac,
            capacity_frac_live=cap_live, tile_m=tm, tile_n=tn,
            with_counts=True)
        n_tiles = nm * nn
        cap = max(1, int(cap_frac * n_tiles))
        cl = max(1, int(np.ceil(cap_live * n_tiles)))
        assert int(n_live) == int(np.asarray(mask).sum())
        assert int(n_comp) <= min(cap, cl, int(n_live))
        want = gather_matmul_cap_ref(x, w, mask, tm, tn, capacity=cap,
                                     cap_live=cl)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-4, atol=2e-3)
        # non-kept tiles are EXACT zeros
        flat = np.asarray(mask).reshape(-1)
        kept = flat & (np.cumsum(flat) - 1 < min(cap, cl))
        for t in range(n_tiles):
            i, j = t // nn, t % nn
            tile = np.asarray(out)[i * tm:(i + 1) * tm,
                                   j * tn:(j + 1) * tn]
            if not kept[t]:
                assert np.all(tile == 0.0)


# -- _dispatch_indices properties (plain seeded; hypothesis twin in
#    test_property_hypothesis.py) -------------------------------------------

def _check_dispatch(top_idx: np.ndarray, E: int, C: int):
    slot = np.asarray(moe._dispatch_indices(jnp.asarray(top_idx), E, C))
    T, k = top_idx.shape
    per_expert_slots = {}
    for t in range(T):
        for kk in range(k):
            e = top_idx[t, kk]
            s = slot[t, kk]
            if e >= E:                       # sentinel (masked token)
                assert s == E * C
                continue
            if s < E * C:
                # kept: lands in its own expert's buffer, exactly once
                assert s // C == e
                per_expert_slots.setdefault(e, set())
                assert s % C not in per_expert_slots[e], "slot reused"
                per_expert_slots[e].add(s % C)
    counts = np.bincount(top_idx[top_idx < E].reshape(-1), minlength=E)
    for e in range(E):
        kept = len(per_expert_slots.get(e, ()))
        # drops happen ONLY on capacity overflow, and earlier tokens win
        assert kept == min(counts[e], C)
        dropped = [(t, kk) for t in range(T) for kk in range(k)
                   if top_idx[t, kk] == e and slot[t, kk] == E * C]
        if dropped:
            assert counts[e] > C
            first_drop_t = min(t for t, _ in dropped)
            kept_ts = [t for t in range(T) for kk in range(k)
                       if top_idx[t, kk] == e and slot[t, kk] < E * C]
            assert all(t <= first_drop_t for t in kept_ts)


def test_dispatch_indices_properties():
    for trial in range(20):
        rng = np.random.default_rng(trial)
        E = int(rng.integers(1, 9))
        k = int(rng.integers(1, min(E, 4) + 1))
        T = int(rng.integers(1, 33))
        C = int(rng.integers(1, 2 * T + 1))
        top = np.stack([rng.choice(E, size=k, replace=False)
                        for _ in range(T)]).astype(np.int32)
        if trial % 3 == 0:       # masked-token sentinel rows
            top[rng.random(T) < 0.3] = E
        _check_dispatch(top, E, C)
