"""CPU dry runs of ``run.py``: the command refuses to measure without a
TPU or without the program, and the rest of a run (a cell loaded from
its files by name, set-up, warm-up, window, metric readers, the output
check) goes through at reduced size in Pallas interpret mode."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import benchtest_util as U  # noqa: E402

CMD = [sys.executable, "benchmarks/chip/run.py", "--seed", "3000000001",
       "--seconds", "2", "--trace", "0"]


def _cli(cwd, workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(CMD + ["--workload", workload], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _no_result(stdout: str) -> bool:
    return not any(line.strip().startswith("{")
                   for line in stdout.splitlines())


def test_cli_refuses_without_a_tpu():
    p = _cli(U.ROOT, "granite-3-2b.mor.decode")
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "needs a TPU" in p.stderr


def test_cli_refuses_with_only_the_benchmark(tmp_path):
    shutil.copy(U.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(U.HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path, "granite-3-2b.mor.decode")
    assert p.returncode != 0 and _no_result(p.stdout)
    assert "program" in p.stderr


def test_cli_refuses_an_unknown_workload():
    p = _cli(U.ROOT, "no-such-cell")
    assert p.returncode != 0 and _no_result(p.stdout)


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    mp.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path_factory.mktemp("jc")))
    with U.KeepJaxConfig():
        yield U.load_run()
    mp.undo()


@pytest.fixture(scope="module")
def decode(run):
    return U.run_small(run, "granite-3-2b.mor.decode", 2**31 + 99,
                       control=True)


def test_dry_run_decode_cell_from_files(decode):
    res, cell = decode
    assert res["correct"] is True
    assert list(res)[-2] == "compared"             # then private readings
    assert res["attempted"] > 0 and res["failed"] == 0
    # a CPU run reports host-clock metrics only, never a device number
    assert set(res["metrics"]) == {"out_tok_s", "itl_p95_ms", "setup_s"}
    assert res["device"]["platform"] == "cpu"
    assert "busy_s" not in res["device"]
    c = res["compared"]["max_logit_gap"]
    assert c["value"] <= c["limit"] == U.REDUCED_LIMIT
    # the warm-up built every step variant the window reached
    assert res["_readings"]["window_executables"] == 0
    setup = res["_readings"]["setup"]
    assert setup["walk_s"] > 0 and setup["standins_s"] > 0
    json.dumps({k: v for k, v in res.items() if k != "_readings"})


def test_control_reads_far_above_the_program(decode):
    res, _ = decode
    served = res["_readings"]["served_gaps"]
    ctl = res["_readings"]["control_gaps"]
    assert served.size == ctl.size > 0
    assert ctl.max() > 10 * max(served.max(), U.REDUCED_LIMIT)


def test_dry_run_decode_cell_traced(run):
    res, cell = U.run_small(run, "granite-3-2b.mor.decode", 17, trace=True)
    assert res["correct"] is True
    # every per-layer metric comes from the device: none on a CPU
    assert res["metrics"] == {}
    assert "breakdown" not in res               # no device plane on a CPU


def test_executable_built_in_the_window_refuses_the_run(run, monkeypatch):
    def no_warm_up(drv, deadline):
        drv.loop = True
        return {"walk_s": 0.0, "standins_s": 0.0, "warmup_dispatches": 0}
    monkeypatch.setattr(run, "warm_up", no_warm_up)
    with pytest.raises(run.Refused, match="inside the window"):
        U.run_small(run, "granite-3-2b.mor.decode", 2**31 + 41)
