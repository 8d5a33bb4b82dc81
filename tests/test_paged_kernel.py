"""Differential tests for the fused paged flash-decode kernel (PR 6):
``gqa_paged_flash`` / ``mla_paged_flash`` vs the dense ``ref`` oracles
(null-page and foreign-page grid skips, sliding windows, fully-masked
slots, the partial flash stats merged shard-style), the engine-level
kernel-vs-jnp token identity across the 5-family matrix (ragged prefill
chunks, sliding-window ring wrap, prefix cache on/off — the existing
serving matrix ties the jnp path to slotted and teacher-forced, so
equality here closes the chain), and the 4-shard subprocess run with
the kernel forced on (partial stats + flash_merge branch)."""
import functools
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_config
from repro.kernels import paged_attention as pk
from repro.kernels import ref
from repro.models import get_model
from repro.serving import Engine


def _reduced(arch):
    cfg = reduce_config(get_config(arch))
    if arch == "deepseek-v2-236b":
        cfg = cfg.replace(family="dense", n_experts=0, top_k=0,
                          first_k_dense=0, n_shared_experts=0)
    return cfg


# -- synthetic paged rings --------------------------------------------------

def _gqa_case(seed, B=3, C=3, n_blocks=4, page=4, hkv=2, G=2, D=8,
              n_pages=11):
    """Random pools + a block table exercising every grid-skip case:
    null pages (global id 0), partially-written pages (-1 tags), and
    slot B-1 entirely null (a fully-masked query row)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, C, hkv * G, D), jnp.float32)
    kpool = jax.random.normal(ks[1], (n_pages, page, hkv, D), jnp.float32)
    vpool = jax.random.normal(ks[2], (n_pages, page, hkv, D), jnp.float32)
    ring = n_blocks * page
    ppool = jax.random.randint(ks[3], (n_pages, page), -1, ring,
                               dtype=jnp.int32)
    ppool = ppool.at[0].set(-1)          # the null page is never written
    tbl = jax.random.randint(ks[4], (B, n_blocks), 0, n_pages,
                             dtype=jnp.int32)
    tbl = tbl.at[B - 1].set(0)           # fully-masked slot
    qpos = jnp.arange(ring // 2, ring // 2 + B * C,
                      dtype=jnp.int32).reshape(B, C)
    return q, kpool, vpool, ppool, tbl, qpos


def _mla_case(seed, B=3, C=2, n_blocks=4, page=4, h=3, kr=8, rd=4,
              n_pages=11):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q_lat = jax.random.normal(ks[0], (B, C, h, kr), jnp.float32)
    q_pe = jax.random.normal(ks[1], (B, C, h, rd), jnp.float32)
    ck = jax.random.normal(ks[2], (n_pages, page, kr), jnp.float32)
    cpe = jax.random.normal(ks[3], (n_pages, page, rd), jnp.float32)
    ring = n_blocks * page
    cp = jax.random.randint(ks[4], (n_pages, page), -1, ring,
                            dtype=jnp.int32)
    cp = cp.at[0].set(-1)
    tbl = jax.random.randint(ks[5], (B, n_blocks), 0, n_pages,
                             dtype=jnp.int32)
    tbl = tbl.at[B - 1].set(0)
    qpos = jnp.arange(ring // 2, ring // 2 + B * C,
                      dtype=jnp.int32).reshape(B, C)
    return q_lat, q_pe, ck, cpe, cp, tbl, qpos


def _merge_partials(parts):
    """Exact local merge of (m, l, acc) flash stats — the single-device
    mirror of ``collectives.flash_merge``, with the same fully-masked
    liveness guard (m stays at NEG_INF only when no page contributed)."""
    m = functools.reduce(jnp.maximum, [p[0] for p in parts])
    l = sum(pl * jnp.exp(pm - m) for pm, pl, _ in parts)
    acc = sum(pa * jnp.exp(pm - m)[..., None] for pm, _, pa in parts)
    live = m > -1e29
    o = acc / jnp.where(live, l, 1.0)[..., None]
    return jnp.where(live[..., None], o, 0.0)


# -- kernel vs oracle (interpret mode) --------------------------------------

@pytest.mark.parametrize("window", [0, 7])
def test_gqa_kernel_matches_ref(window):
    """Fused GQA kernel == dense oracle over a table mixing live, null
    and partially-written pages, plus one fully-masked slot (emits
    zeros, not NaNs), causal and sliding-window."""
    q, kp, vp, pp, tbl, qpos = _gqa_case(0)
    out = pk.gqa_paged_flash(q, kp, vp, pp, tbl, qpos, window=window,
                             interpret=True)
    want = ref.gqa_paged_ref(q, kp, vp, pp, tbl, qpos, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    assert np.all(np.isfinite(np.asarray(out)))
    assert np.allclose(np.asarray(out)[-1], 0.0)   # fully-masked slot


def test_gqa_kernel_foreign_pages():
    """With a shard window [lo, lo + n_local) the kernel must skip
    foreign pages exactly like the oracle's masked gather."""
    q, kp, vp, pp, tbl, qpos = _gqa_case(1)
    lo, n_local = 4, 3
    out = pk.gqa_paged_flash(q, kp[lo:lo + n_local], vp[lo:lo + n_local],
                             pp[lo:lo + n_local], tbl, qpos,
                             lo=lo, n_local=n_local, interpret=True)
    want = ref.gqa_paged_ref(q, kp[lo:lo + n_local], vp[lo:lo + n_local],
                             pp[lo:lo + n_local], tbl, qpos,
                             lo=lo, n_local=n_local)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_gqa_partial_stats_merge_to_full():
    """Partial (m, l, acc) stats from two disjoint shard windows, merged
    flash_merge-style, equal the unsharded kernel AND oracle outputs —
    the correctness core of the sharded kernel decode path."""
    q, kp, vp, pp, tbl, qpos = _gqa_case(2)
    n_pages = kp.shape[0]
    parts = []
    for lo, hi in [(1, 6), (6, n_pages)]:
        parts.append(pk.gqa_paged_flash(
            q, kp[lo:hi], vp[lo:hi], pp[lo:hi], tbl, qpos,
            lo=lo, n_local=hi - lo, partial=True, interpret=True))
    merged = _merge_partials(parts)            # (B, hkv, G, C, Dv)
    B, C, H, D = q.shape
    got = merged.transpose(0, 3, 1, 2, 4).reshape(B, C, H, -1)
    full = pk.gqa_paged_flash(q, kp, vp, pp, tbl, qpos, interpret=True)
    want = ref.gqa_paged_ref(q, kp, vp, pp, tbl, qpos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(full),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("h,C", [(3, 2), (128, 4)])
def test_mla_kernel_matches_ref(h, C):
    """(128, 4) spans two MLA_ROW_BLOCK row blocks of the grid."""
    ql, qe, ck, cpe, cp, tbl, qpos = _mla_case(3, h=h, C=C)
    scale = (ql.shape[-1] + qe.shape[-1]) ** -0.5
    out = pk.mla_paged_flash(ql, qe, ck, cpe, cp, tbl, qpos, scale=scale,
                             interpret=True)
    want = ref.mla_paged_ref(ql, qe, ck, cpe, cp, tbl, qpos, scale=scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    assert np.all(np.isfinite(np.asarray(out)))
    assert np.allclose(np.asarray(out)[-1], 0.0)


def test_mla_partial_stats_merge_to_full():
    ql, qe, ck, cpe, cp, tbl, qpos = _mla_case(4)
    scale = (ql.shape[-1] + qe.shape[-1]) ** -0.5
    n_pages = ck.shape[0]
    parts = []
    for lo, hi in [(1, 6), (6, n_pages)]:
        parts.append(pk.mla_paged_flash(
            ql, qe, ck[lo:hi], cpe[lo:hi], cp[lo:hi], tbl, qpos,
            scale=scale, lo=lo, n_local=hi - lo, partial=True,
            interpret=True))
    merged = _merge_partials(parts)            # (B, h, C, kr)
    got = merged.transpose(0, 2, 1, 3)
    want = ref.mla_paged_ref(ql, qe, ck, cpe, cp, tbl, qpos, scale=scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


# -- engine-level kernel == jnp token identity ------------------------------

_TRACE_KEY = {"deepseek-v2-236b": "mla"}


@pytest.mark.parametrize("arch", ["granite-3-2b", "qwen2-7b",
                                  "deepseek-v2-236b", "mixtral-8x7b",
                                  "zamba2-7b"])
def test_engine_kernel_matches_jnp(arch, monkeypatch):
    """The paged kernel path must be token-identical to the jnp gather
    fallback on ragged prompts (3-13 toks vs chunk 8 → partial final
    chunks); mixtral keeps sliding_window=16 and generates past it, so
    its ring wraps through the kernel's window mask.  The jnp path is
    already tied to slotted and teacher-forced by test_serving, so this
    closes kernel == jnp == slotted == teacher-forced."""
    cfg = _reduced(arch)
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    reqs = [(rng.integers(0, cfg.vocab_size,
                          size=int(rng.integers(3, 14))),
             int(rng.integers(3, 6))) for _ in range(3)]
    if arch == "mixtral-8x7b":          # force a ring wrap past window=16
        reqs[0] = (rng.integers(0, cfg.vocab_size, size=22), 6)
    monkeypatch.setattr(pk, "enabled", lambda: False)
    res_jnp = Engine(cfg, params, n_slots=2, max_len=64,
                     layout="paged").run(list(reqs))
    monkeypatch.setattr(pk, "enabled", lambda: True)
    pk.reset_kernel_traces()
    res_k = Engine(cfg, params, n_slots=2, max_len=64,
                   layout="paged").run(list(reqs))
    assert res_k == res_jnp, f"{arch}: kernel tokens diverge from jnp"
    key = _TRACE_KEY.get(arch, "gqa")
    assert pk.kernel_traces()[key] > 0, \
        f"{arch}: kernel path never traced ({pk.kernel_traces()})"


def test_engine_kernel_prefix_cache_on_off(monkeypatch):
    """Kernel path with the shared-prefix dedup engaged (warm pool must
    actually skip chunks) == kernel path cold == jnp cold."""
    cfg = _reduced("granite-3-2b")
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    prefix = rng.integers(0, cfg.vocab_size, size=16)
    reqs = [(np.concatenate([prefix,
                             rng.integers(0, cfg.vocab_size, size=4)]), 4)
            for _ in range(3)]
    monkeypatch.setattr(pk, "enabled", lambda: False)
    res_jnp = Engine(cfg, params, n_slots=2, max_len=64,
                     prefix_cache=False).run(list(reqs))
    monkeypatch.setattr(pk, "enabled", lambda: True)
    cold = Engine(cfg, params, n_slots=2, max_len=64,
                  prefix_cache=False).run(list(reqs))
    warm_eng = Engine(cfg, params, n_slots=2, max_len=64)
    warm = warm_eng.run(list(reqs))
    assert cold == res_jnp
    assert warm == res_jnp
    assert warm_eng._prefix_counters()["chunks_skipped"] > 0


# -- 4-shard subprocess with the kernel forced on ---------------------------

_SHARDED_KERNEL_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, numpy as np
from repro.configs import get_config, reduce_config
from repro.models import get_model
from repro.serving import Engine
from repro.launch.mesh import make_page_mesh
from repro.kernels import paged_attention as pk
pk.enabled = lambda: True            # the kernel path, as on TPU

cfg = reduce_config(get_config("granite-3-2b"))
api = get_model(cfg)
params = api.init(jax.random.PRNGKey(0), cfg)
rng = np.random.default_rng(0)
reqs = [(rng.integers(0, cfg.vocab_size, size=10), 4) for _ in range(2)]
res_p = Engine(cfg, params, n_slots=2, max_len=64,
               layout="paged").run(list(reqs))
pk.reset_kernel_traces()
mesh = make_page_mesh(4)
res_m = Engine(cfg, params, n_slots=2, max_len=64,
               layout="paged-sharded", mesh=mesh).run(list(reqs))
assert res_m == res_p, "sharded kernel tokens diverge from single-device"
assert pk.kernel_traces()["gqa"] > 0, pk.kernel_traces()
print("SHARDED_KERNEL_OK")
"""


def test_paged_sharded_kernel_multidevice():
    """The sharded decode path with the paged kernel on (partial flash
    stats + one flash_merge per layer) is token-identical to the
    single-device kernel engine, on 4 forced host devices in a
    subprocess (jax device count locks at first init).  Kept to one
    small GQA run: interpret-mode Pallas serialises the page grid."""
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__),
                                       "..", "src"))
    r = subprocess.run([sys.executable, "-c", _SHARDED_KERNEL_SCRIPT],
                       capture_output=True, text=True, env=env,
                       timeout=1800)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "SHARDED_KERNEL_OK" in r.stdout
