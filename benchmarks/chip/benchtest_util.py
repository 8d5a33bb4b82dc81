"""Shared set-up of the CPU dry runs: ``run.py`` loaded by path, a cell
loaded from the repository's files by name, its traffic cut to a size a
test run holds, and JAX's global settings restored afterwards."""
import dataclasses
import importlib.util
import sys
from pathlib import Path

import jax

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
# the reduced configs serve float32, where a sound engine picks the
# reference's best token to rounding (gap 0); the chip limit in the
# configuration files is for bfloat16 at the published widths
REDUCED_LIMIT = 1e-3


def load_run():
    spec = importlib.util.spec_from_file_location("chipbench_run",
                                                  HERE / "run.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod          # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def small_cell(run, name: str):
    from chipbench.traffic import LengthDist
    cell = run.load_cell(ROOT, name)
    run.import_program(ROOT)
    cell.traffic = dataclasses.replace(
        cell.traffic, slots=2, max_len=64,
        prompt_len=LengthDist("loguniform", 4, 24),
        output_len=LengthDist("loguniform", 4, 12), cycle=16, backlog=2,
        check={"tokens": 20, "min_requests": 2, "max_requests": 3})
    cell.config = dict(cell.config, correct={"max_logit_gap": REDUCED_LIMIT})
    return cell


def run_small(run, name: str, seed: int, trace: bool = False, **kw):
    cell = small_cell(run, name)
    return run.run_cell(cell, seed, 1.5, trace, reduced=True,
                        device_kind="TPU v5 lite", **kw), cell


class KeepJaxConfig:
    """Restore the settings a run changes (compile cache, its threshold)."""
    KEYS = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")

    def __enter__(self):
        self.saved = {k: getattr(jax.config, k) for k in self.KEYS}
        return self

    def __exit__(self, *exc):
        for k, v in self.saved.items():
            jax.config.update(k, v)
