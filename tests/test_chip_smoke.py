"""Rehearse ``chip_smoke.py`` on the CPU: its kernel and serving phases
at reduced granite-3-2b widths (Pallas in interpret mode, the paged
attention kernel forced on), and its refusal to run without a TPU."""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.kernels import paged_attention as pk

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke_mod():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod          # dataclasses resolve it
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def small(smoke_mod):
    return smoke_mod.Smoke(reduced=True, n_slots=2, n_requests=3,
                           prompt_min=8, prompt_max=24, gen_len=4)


def test_kernel_phase_reduced(smoke_mod, small):
    out = smoke_mod.kernel_phase(small.config(), small)
    assert 0.0 < out["mor_tile_mask_live_frac"] < 1.0
    for key in ("gather_matmul_err", "masked_matmul_kdim_err",
                "gqa_paged_flash_c1_err", "gqa_paged_flash_c8_err"):
        assert out[key] <= smoke_mod.KERNEL_TOL, (key, out)


def test_serve_phase_reduced(smoke_mod, small, monkeypatch):
    monkeypatch.setattr(pk, "enabled", lambda: True)   # as on TPU
    out = smoke_mod.serve_phase(small)
    assert out["requests_finished"] == small.n_requests
    # float32 on the CPU: no near-ties, so every agreement is exact
    assert out["next_token_agreement_vs_dense"] == 1.0
    assert out["dense_engine_next_token_agreement"] == 1.0
    assert out["free_running_token_agreement"] == 1.0
    assert 0.0 < out["mor_frac_tiles_computed"] <= 1.0
    assert out["kernel_traces"]["gqa"] > 0


def test_next_token_agreement_is_teacher_forced(smoke_mod):
    """One early flip costs one token, not the rest of the request."""
    prompts = [np.array([5, 6, 7]), np.array([1, 2])]
    gens = [[10, 11, 12], [20, 21]]
    # reference next token after each position of prompt + generated
    ref = [np.array([0, 0, 99, 11, 12, 0]), np.array([0, 20, 21, 0])]
    assert smoke_mod._next_token_agreement(prompts, gens, ref) == 0.8


_SHARDED_REHEARSAL = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, os.getcwd())
import chip_smoke as cs
from repro.kernels import paged_attention as pk
pk.enabled = lambda: True                  # the kernel path, as on TPU
small = cs.Smoke(reduced=True, n_slots=2, n_requests=3, prompt_min=8,
                 prompt_max=24, gen_len=4)
out = cs.sharded_phase(small)
assert out["token_agreement"] == 1.0, out
assert out["kernel_traces"]["gqa"] > 0, out
print("SHARDED_PHASE_OK", out["kv_pages_hiwater_per_shard"])
"""


def test_sharded_phase_reduced():
    """The four-chip phase on 4 forced host devices, in a subprocess
    (the device count locks at JAX's first use)."""
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", _SHARDED_REHEARSAL],
                       cwd=ROOT, capture_output=True, text=True, env=env,
                       timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "SHARDED_PHASE_OK" in r.stdout


def test_main_refuses_cpu(smoke_mod, capsys):
    assert smoke_mod.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_script_alone_fails(tmp_path):
    """Without the rest of the repo beside it the script fails and
    prints no result line, whatever the platform."""
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
