"""Launch-layer tests: dry-run machinery on a small fake mesh
(subprocess: device count locks at jax init), roofline parsing, and the
experiments aggregation."""
import json
import os
import subprocess
import sys

import pytest

from repro.launch import roofline


_SMALL_DRYRUN = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from repro.configs import get_config, reduce_config, input_specs
from repro.configs.base import ShapeSpec
from repro.distributed.sharding_rules import (activation_context,
                                              batch_sharding, param_sharding)
from repro.launch.steps import make_train_step, make_serve_step
from repro.models import get_model, param_shapes, cache_shapes
from repro.optim import OptConfig, adamw_init

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
cfg = reduce_config(get_config("granite-3-2b")).replace(
    d_model=64, d_ff=128, n_heads=4, n_kv_heads=4, d_head=16)
p_sds = param_shapes(cfg)
p_sh = param_sharding(p_sds, mesh)
shape = ShapeSpec("t", 32, 8, "train")
data = input_specs(cfg, shape)
opt_sds = jax.eval_shape(lambda p: adamw_init(p, OptConfig()), p_sds)
with activation_context(mesh, sequence_parallel=True):
    step = make_train_step(cfg, OptConfig())
    lowered = jax.jit(step, in_shardings=(p_sh, None, batch_sharding(data, mesh))
                      ).lower(p_sds, opt_sds, data)
    compiled = lowered.compile()
assert compiled.memory_analysis() is not None
cost = compiled.cost_analysis()
print("TRAIN_LOWER_OK")

# decode on the same mesh (exercises _tp_flash_decode inside jit)
c_sds = cache_shapes(cfg, 8, 64)
tok = jax.ShapeDtypeStruct((8, 1), jnp.int32)
with activation_context(mesh):
    serve = make_serve_step(cfg)
    comp2 = jax.jit(serve).lower(p_sds, c_sds, tok).compile()
hlo = comp2.as_text()
assert "all-reduce" in hlo or "collective" in hlo
print("DECODE_LOWER_OK")
"""


def test_small_mesh_lower_compile():
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", _SMALL_DRYRUN],
                       capture_output=True, text=True, env=env,
                       cwd=os.getcwd(), timeout=420)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "TRAIN_LOWER_OK" in r.stdout
    assert "DECODE_LOWER_OK" in r.stdout


_CACHE_PROBE = r"""
import os, jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
where = enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: x * 3 + 1).lower(jnp.arange(7.0)).compile()
print(where)
print(len(os.listdir(where)))
"""


@pytest.mark.parametrize("env_dir", [False, True])
def test_compile_cache_location(env_dir, tmp_path):
    """The persistent cache lands in $JAX_COMPILATION_CACHE_DIR when it is
    set, else at the one fixed, git-ignored path in the checkout."""
    from repro.launch.compile_cache import DEFAULT_DIR
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = DEFAULT_DIR
    if env_dir:
        want = tmp_path / "cache"
        env["JAX_COMPILATION_CACHE_DIR"] = str(want)
    r = subprocess.run([sys.executable, "-c", _CACHE_PROBE],
                       capture_output=True, text=True, env=env,
                       cwd=os.getcwd(), timeout=120)
    assert r.returncode == 0, r.stderr[-3000:]
    where, n_entries = r.stdout.split()[-2:]
    assert where == str(want) and int(n_entries) > 0
    ignored = (DEFAULT_DIR.parent / ".gitignore").read_text().split()
    assert f"{DEFAULT_DIR.name}/" in ignored


def test_parse_collectives_factors():
    hlo = """
  %ag = f32[4,256]{1,0} all-gather(%x), replica_groups={{0,1,2,3}}
  %ar.1 = f32[128]{0} all-reduce-start(%y), replica_groups=[4,2]<=[8]
  %rs = bf16[64,32]{1,0} reduce-scatter(%z), replica_groups={{0,1}}
"""
    out = roofline.parse_collectives(hlo)
    assert out["all-gather"] == 4 * 256 * 4 * 1.0
    assert out["all-reduce"] == 128 * 4 * 2.0          # 2x ring factor
    assert out["reduce-scatter"] == 64 * 32 * 2 * 1.0
    assert out["total_wire_bytes"] == (out["all-gather"] + out["all-reduce"]
                                       + out["reduce-scatter"])


def test_roofline_terms_dominance():
    cost = {"flops": 197e12, "bytes accessed": 819e9 * 2}
    colls = {"total_wire_bytes": 0.0, "dci_bytes": 0.0}
    t = roofline.roofline_terms(cost, colls)
    assert t["dominant"] == "memory"
    assert abs(t["t_compute_s"] - 1.0) < 1e-6
    assert abs(t["t_memory_s"] - 2.0) < 1e-6


def test_model_flops_conventions():
    from repro.configs import get_config, SHAPES
    cfg = get_config("qwen2-7b")
    mf_train = roofline.model_flops(cfg, SHAPES["train_4k"], 256)
    mf_dec = roofline.model_flops(cfg, SHAPES["decode_32k"], 256)
    # train = 6ND, decode = 2N * batch tokens
    assert mf_train["model_flops_per_chip"] > 1000 * mf_dec[
        "model_flops_per_chip"]
    assert mf_train["params_total"] == mf_train["params_active"]
    moe = get_config("mixtral-8x7b")
    mfm = roofline.model_flops(moe, SHAPES["train_4k"], 256)
    assert mfm["params_active"] < 0.4 * mfm["params_total"]


def test_experiments_md_generator(tmp_path, monkeypatch):
    """The generator runs against whatever records exist."""
    sys.path.insert(0, ".")
    from benchmarks import make_experiments_md
    monkeypatch.chdir(os.getcwd())
    make_experiments_md.main()
    text = open("EXPERIMENTS.md").read()
    for section in ("§Paper-validation", "§Dry-run", "§Roofline", "§Perf"):
        assert section in text
