"""The plain float32 references against the program at reduced size.

Both sides get the same seeded weights (``reference.make_weights`` on
the program's parameter shapes).  The program's teacher-forced forward
in float32 must give the reference's logits to float32 rounding; the
fp8 control must not."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import reference  # noqa: E402

CASES = {
    "granite-3-2b": ("gqa_glu", lambda c: {
        "num_attention_heads": c.n_heads,
        "num_key_value_heads": c.n_kv_heads}),
}


def _setup(arch, seed):
    from repro.configs import get_config, reduce_config
    from repro.models import get_model
    cfg = reduce_config(get_config(arch))
    api = get_model(cfg)
    abstract = jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0), cfg))
    kind, numbers = CASES[arch]
    params = reference.make_weights(kind, abstract, seed)
    return cfg, api, kind, numbers(cfg), params, abstract


@pytest.mark.parametrize("arch", sorted(CASES))
def test_weights_follow_the_program_tree_and_the_seed(arch):
    cfg, api, kind, _, params, abstract = _setup(arch, 2**31 + 5)
    assert jax.tree_util.tree_structure(params) == \
        jax.tree_util.tree_structure(abstract)
    for p, a in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(abstract)):
        assert p.shape == a.shape and p.dtype == a.dtype
    again = reference.make_weights(kind, abstract, 2**31 + 5)
    other = reference.make_weights(kind, abstract, 6)
    flat = jax.tree_util.tree_leaves
    assert all(np.array_equal(x, y) for x, y in zip(flat(params),
                                                    flat(again)))
    assert not np.array_equal(flat(params)[0], flat(other)[0])


@pytest.mark.parametrize("arch", sorted(CASES))
def test_reference_matches_program_forward(arch):
    cfg, api, kind, numbers, params, _ = _setup(arch, 11)
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, 24)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(api.forward(params, cfg,
                                      {"tokens": jnp.asarray(toks)[None]})[0]
                          [0], np.float32)
    got = np.asarray(reference.logits(kind, params, jnp.asarray(toks),
                                      "f32", numbers))
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 1e-4 * scale
    ctl = np.asarray(reference.logits(kind, params, jnp.asarray(toks),
                                      "fp8", numbers))
    assert np.abs(ctl - want).max() > 1e-2 * scale


@pytest.mark.parametrize("arch", sorted(CASES))
def test_gaps_read_the_served_token_and_the_control(arch):
    cfg, api, kind, numbers, params, _ = _setup(arch, 12)
    prompt = np.random.default_rng(1).integers(0, cfg.vocab_size, 10)
    ref = np.asarray(reference.logits(kind, params, jnp.asarray(prompt),
                                      "f32", numbers))
    best = ref.argmax(-1)
    # a sequence that always continues with the reference's best token
    # reads gap 0; the worst token reads the full spread
    seq = np.concatenate([prompt, [best[-1]]]).astype(np.int32)
    g, c = reference.gaps(kind, params, seq, 16, numbers, control=True)
    assert g.shape == (10,) and g[-1] == pytest.approx(0.0, abs=1e-6)
    worst = ref[-1].argmin()
    seq[-1] = worst
    g, _ = reference.gaps(kind, params, seq, 16, numbers)
    assert g[-1] == pytest.approx(ref[-1].max() - ref[-1].min(), rel=1e-5)
    assert (c >= 0).all()
