"""Property-based tests (hypothesis) on the system's invariants."""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # property tests need the dev extra
from hypothesis import given, settings, strategies as st
from hypothesis.extra import numpy as hnp

from repro.core.policy import expand_tile_mask, tile_mask_from_neuron_mask
from repro.core.predictor import binary_preact
from repro.kernels.ref import binary_dot_ref
from repro.optim.compression import (compress_int8, decompress_int8,
                                     error_feedback_allreduce,
                                     init_residuals)

floats = st.floats(-100, 100, allow_nan=False, width=32)


@settings(max_examples=30, deadline=None)
@given(hnp.arrays(np.float32, hnp.array_shapes(min_dims=2, max_dims=2,
                                               min_side=1, max_side=24),
                  elements=floats),
       st.integers(1, 24))
def test_binary_preact_equals_oracle_any_input(x, n):
    """Including zeros, negatives, repeated values."""
    k = x.shape[1]
    w = np.linspace(-1, 1, k * n, dtype=np.float32).reshape(k, n)
    got = np.asarray(binary_preact(jnp.asarray(x), jnp.asarray(w)))
    want = np.asarray(binary_dot_ref(jnp.asarray(x), jnp.asarray(w)))
    np.testing.assert_array_equal(got, want)


@settings(max_examples=30, deadline=None)
@given(hnp.arrays(np.bool_, hnp.array_shapes(min_dims=2, max_dims=2,
                                             min_side=1, max_side=64)),
       st.sampled_from([1, 2, 8]), st.sampled_from([1, 4, 16]))
def test_tile_mask_roundtrip_is_superset(mask, tm, tn):
    """expand(reduce(mask)) >= mask pointwise: tile granularity may only
    ADD computed neurons, never drop one (correctness invariant that
    makes tiled mode safe)."""
    m = jnp.asarray(mask)
    tiles = tile_mask_from_neuron_mask(m, tm, tn)
    back = expand_tile_mask(tiles, tm, tn, mask.shape[0], mask.shape[1])
    assert bool(jnp.all(back >= m))
    # and a tile is live only if some neuron in it was live
    assert int(tiles.sum()) <= mask.sum() + tiles.size - 1 or mask.any()


@settings(max_examples=40, deadline=None)
@given(hnp.arrays(np.float32, st.integers(1, 257), elements=floats))
def test_int8_compression_error_bound(x):
    q, s = compress_int8(jnp.asarray(x))
    deq = np.asarray(decompress_int8(q, s))
    assert np.all(np.abs(deq - x) <= float(s) * 0.5 + 1e-5)


@settings(max_examples=25, deadline=None)
@given(hnp.arrays(np.float32, 33, elements=floats),
       hnp.arrays(np.float32, 33, elements=floats))
def test_error_feedback_conservation(g, r):
    """deq + new_residual == grad + residual exactly (nothing lost)."""
    grads = {"w": jnp.asarray(g)}
    resid = {"w": jnp.asarray(r)}
    red, r_new = error_feedback_allreduce(grads, resid, axis_name=None)
    np.testing.assert_allclose(
        np.asarray(red["w"]) + np.asarray(r_new["w"]),
        g.astype(np.float64) + r.astype(np.float64), rtol=1e-5, atol=1e-4)


@settings(max_examples=40, deadline=None)
@given(st.integers(1, 8), st.integers(1, 3), st.integers(1, 24),
       st.integers(1, 12), st.integers(0, 2 ** 32 - 1), st.floats(0, 0.5))
def test_dispatch_indices_kept_once_drops_only_on_overflow(
        E, k, T, C, seed, mask_frac):
    """MoE dispatch invariants (ISSUE 3): every kept (token, k) pair
    lands in its chosen expert's buffer exactly once; pairs are dropped
    ONLY on capacity overflow (kept-per-expert == min(count, C), earlier
    tokens winning); sentinel pairs (masked tokens, expert id == E) land
    exactly on the E*C drop slot."""
    from repro.models.layers.moe import _dispatch_indices
    k = min(k, E)
    rng = np.random.default_rng(seed)
    top = np.stack([rng.choice(E, size=k, replace=False)
                    for _ in range(T)]).astype(np.int32)
    top[rng.random(T) < mask_frac] = E
    slot = np.asarray(_dispatch_indices(jnp.asarray(top), E, C))
    seen = {}
    for t in range(T):
        for kk in range(k):
            e, s = top[t, kk], slot[t, kk]
            if e >= E:
                assert s == E * C
            elif s < E * C:
                assert s // C == e
                assert (e, s % C) not in seen
                seen[(e, s % C)] = t
    counts = np.bincount(top[top < E].reshape(-1), minlength=E)
    for e in range(E):
        kept_ts = sorted(t for (ee, _), t in seen.items() if ee == e)
        assert len(kept_ts) == min(counts[e], C)
        dropped_ts = [t for t in range(T) for kk in range(k)
                      if top[t, kk] == e and slot[t, kk] == E * C]
        assert all(kt <= dt for kt in kept_ts for dt in dropped_ts), \
            "a later token displaced an earlier one"


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 4), st.integers(1, 4), st.floats(0.1, 1.0),
       st.floats(0.05, 1.0), st.integers(0, 2 ** 32 - 1))
def test_gather_matmul_cap_live_clamp(nm, nn, cap_frac, cap_live, seed):
    """The real Pallas gather_matmul under the traced cap_live clamp:
    count outputs never exceed min(capacity, cap_live, n_live); computed
    tiles match x @ w; clamped/dead tiles are EXACT zeros."""
    from repro.kernels import ops as kops
    from repro.kernels.ref import gather_matmul_cap_ref
    rng = np.random.default_rng(seed)
    tm, tn = 8, 16
    x = jnp.asarray(rng.normal(size=(nm * tm, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(32, nn * tn)), jnp.float32)
    mask = jnp.asarray(rng.random((nm, nn)) > 0.4)
    out, n_live, n_comp, _ = kops.gather_matmul(
        x, w, mask, capacity_frac=cap_frac, capacity_frac_live=cap_live,
        tile_m=tm, tile_n=tn, with_counts=True)
    n_tiles = nm * nn
    cap = max(1, int(cap_frac * n_tiles))
    cl = max(1, int(np.ceil(cap_live * n_tiles)))
    assert int(n_live) == int(np.asarray(mask).sum())
    assert int(n_comp) <= min(cap, cl, int(n_live))
    want = np.asarray(gather_matmul_cap_ref(x, w, mask, tm, tn,
                                            capacity=cap, cap_live=cl))
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-4, atol=2e-3)
    flat = np.asarray(mask).reshape(-1)
    kept = flat & (np.cumsum(flat) - 1 < min(cap, cl))
    for t in range(n_tiles):
        if not kept[t]:
            i, j = t // nn, t % nn
            assert np.all(np.asarray(out)[i * tm:(i + 1) * tm,
                                          j * tn:(j + 1) * tn] == 0.0)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 6), st.integers(1, 6), st.floats(0.05, 1.0))
def test_gather_capacity_never_exceeds(nm, nn, frac):
    """gather_matmul computes at most `capacity` tiles, whatever the mask."""
    from repro.kernels.ref import gather_matmul_ref, masked_matmul_ref
    rng = np.random.default_rng(nm * 7 + nn)
    tm = tn = 4
    x = jnp.asarray(rng.normal(size=(nm * tm, 8)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(8, nn * tn)), jnp.float32)
    mask = jnp.asarray(rng.random((nm, nn)) > 0.5)
    cap = max(1, int(frac * nm * nn))
    out = np.asarray(gather_matmul_ref(x, w, mask, tm, tn, cap))
    nonzero_tiles = 0
    for i in range(nm):
        for j in range(nn):
            if np.any(out[i * tm:(i + 1) * tm, j * tn:(j + 1) * tn] != 0):
                nonzero_tiles += 1
    assert nonzero_tiles <= cap


# -- paged KV pool allocator (ISSUE 4) -------------------------------------
# hypothesis twins of tests/test_paged_pool.py's seeded machine: same
# invariants (no page leaked, no page double-owned, COW never drops a
# shared source), minimised counterexamples when they fail.

_alloc_op = st.one_of(
    st.tuples(st.just("write"), st.integers(0, 2),
              st.lists(st.integers(0, 3), min_size=1, max_size=4,
                       unique=True)),
    st.tuples(st.just("share"), st.integers(0, 2), st.integers(0, 2),
              st.integers(0, 3)),
    st.tuples(st.just("release"), st.integers(0, 2)),
    st.tuples(st.just("publish"), st.integers(0, 2), st.integers(0, 3)),
    st.tuples(st.just("evict"),),
)


@settings(max_examples=60, deadline=None)
@given(st.lists(_alloc_op, min_size=1, max_size=40))
def test_block_allocator_invariants(ops):
    from test_paged_pool import run_allocator_ops
    run_allocator_ops(ops)


@settings(max_examples=60, deadline=None)
@given(st.lists(_alloc_op, min_size=1, max_size=40),
       st.sampled_from([2, 4]))
def test_block_allocator_invariants_sharded(ops, n_shards):
    """Mesh-sharded twin (ISSUE 5): same machine, plus COW destinations
    never leave their source's shard and per-shard occupancy accounting
    stays consistent with the refcounts."""
    from test_paged_pool import run_allocator_ops
    run_allocator_ops(ops, n_shards=n_shards)


@settings(max_examples=40, deadline=None)
@given(st.lists(st.lists(st.integers(0, 7), min_size=2, max_size=24),
                min_size=1, max_size=8),
       st.integers(2, 5))
def test_prefix_trie_prefix_property(prompts, page):
    from test_paged_pool import check_prefix_trie_prefix_property
    check_prefix_trie_prefix_property(prompts, page)
