"""On-chip benchmark: one cell, one run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell is looked up by name in
``BENCHMARK.json``; its configuration file (``configs/<config>.json``),
its traffic mix (``traffic/<traffic>.json``) and each of its metrics'
readers (``metrics/<metric>.py``) are found by name beside this file,
so a cell, a configuration or a metric is added by adding files.

The run drives ``repro.serving.Engine`` (paged layout) through its
public ``submit`` / ``step`` / ``drain``: set-up makes the weights on the
device from the seed, calibrates the MoR plans (``calibrate_lm``),
builds the engine and warms it up (``warm_up``); then it measures for
``--seconds`` with the closed loop running.  A run in which the engine
built an executable inside the window is refused.  ``--trace 1``
records a profiler trace over part of the window and reports the
per-layer metrics instead of the end-to-end ones.  After the window the
served tokens are checked against the plain float32 reference
(``chipbench.check``).

A run needs a TPU: with no accelerator, or fewer chips than the cell
asks for, it prints no result and exits non-zero.  The last line of
standard output is one JSON object; the numbers compared are the last
lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from chipbench import check, cost, reference  # noqa: E402
from chipbench.peaks import peaks_for  # noqa: E402
from chipbench.traffic import Traffic, TrafficSpec  # noqa: E402
from chipbench.window import window_stats  # noqa: E402

# kernel -> the HLO op names its calls carry in a device trace; the paged
# attention kernel's pallas_call has no named jit around it, and is the
# one "_unknown_" custom call of the granite step (a count check in
# chipbench.readers silences the metric if that stops being so)
KERNELS = {"mor_tile_mask": ("mor_tile_mask",),
           "gather_matmul": ("gather_matmul",),
           "masked_matmul_kdim": ("masked_matmul_kdim",),
           "gqa_paged_flash": ("gqa_paged_flash", "_unknown_")}
TRACE_MAX_S = 4.0               # profiler on for at most this long
WARMUP_CAP_S = 1000.0           # give up on the warm-up (first run compiles)
CACHE_DIR = ".jax_cache"        # persistent compile cache, in the checkout


class Refused(Exception):
    """The run cannot measure: exit non-zero, print no result."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# -- the cell, found by name -------------------------------------------------------

@dataclass
class Cell:
    name: str
    chips: int
    config: Dict                      # configs/<config>.json
    traffic: TrafficSpec
    end_to_end: List[Dict]
    per_layer: List[Dict]


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: Path, name: str) -> Cell:
    bench_file = root / "BENCHMARK.json"
    if not bench_file.is_file():
        raise Refused(f"no BENCHMARK.json in {root}")
    bench = json.loads(bench_file.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = TrafficSpec.load(HERE / "traffic" / f"{w['traffic']}.json")
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def load_reader(name: str) -> Callable:
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- the program ------------------------------------------------------------------------

def import_program(root: Path) -> None:
    src = root / "src"
    if not (src / "repro" / "serving").is_dir():
        raise Refused(f"the program (src/repro) is not in {root}")
    sys.path.insert(0, str(src))


def check_device(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"needs a TPU; JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise Refused(f"the cell asks for {chips} chips; JAX found "
                      f"{len(devs)}")
    peaks_for(devs[0].device_kind)          # an unknown chip is refused
    return devs


class CompileClock:
    """Count and seconds of executables built (compiled or loaded from
    the persistent cache), and persistent-cache hits, from JAX's own
    monitoring events.  Installed once per process."""
    _installed: Optional["CompileClock"] = None

    def __init__(self):
        self.n = 0
        self.s = 0.0
        self.hits = 0
        self.names: List[str] = []

    @classmethod
    def get(cls) -> "CompileClock":
        if cls._installed is None:
            import jax
            clock = cls()

            def on_duration(event, duration, fun_name="", **_):
                if event == "/jax/core/compile/backend_compile_duration":
                    clock.n += 1
                    clock.s += duration
                    clock.names.append(fun_name)

            def on_event(event, **_):
                if event == "/jax/compilation_cache/cache_hits":
                    clock.hits += 1
            jax.monitoring.register_event_duration_secs_listener(on_duration)
            jax.monitoring.register_event_listener(on_event)
            cls._installed = clock
        return cls._installed

    def snap(self):
        return self.n, self.s, self.hits


def program_config(cell: Cell, reduced: bool):
    from repro.configs import get_config, reduce_config
    cfg = get_config(cell.config["arch"])
    if reduced:
        return reduce_config(cfg)
    for key, want in cell.config["program_config"].items():
        obj = cfg
        for part in key.split("."):
            obj = getattr(obj, part)
        if obj != want:
            raise Refused(f"{cell.config['name']}: the program's {key} is "
                          f"{obj!r}, the configuration states {want!r}")
    return cfg


def arch_numbers(cell: Cell, cfg, reduced: bool) -> Dict:
    """The sizes the reference and the cost counts use: the file's, or
    the reduced program config's in a test."""
    if not reduced:
        return cell.config
    return {"reference": cell.config["reference"],
            "hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
            "num_hidden_layers": cfg.n_layers, "vocab_size": cfg.vocab_size,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads}


def build_engine(cell: Cell, cfg, seed: int, timing: Dict):
    """Weights from the seed, calibrated MoR plans, the engine."""
    import jax
    import jax.numpy as jnp
    from repro.core.deploy import calibrate_lm
    from repro.data.pipeline import synthetic_lm_batch
    from repro.models import get_model
    from repro.serving import Engine

    api = get_model(cfg)
    abstract = jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0), cfg))
    t = time.perf_counter()
    params = reference.make_weights(cell.config["reference"], abstract, seed)
    jax.block_until_ready(params)
    timing["weights_s"] = time.perf_counter() - t
    mor = None
    mode = cell.config["mor_mode"]
    if mode != "dense":
        cal = cell.config["calibration"]
        t = time.perf_counter()

        def batches():
            s = 0
            while True:
                b = synthetic_lm_batch(cfg, cal["batch"], cal["seq"],
                                       seed=seed, step=s)
                yield {"tokens": jnp.asarray(b["tokens"])}
                s += 1
        params, mor, _ = calibrate_lm(params, cfg, api.forward, batches(),
                                      cal["batches"])
        jax.block_until_ready((params, mor))
        timing["calibration_s"] = time.perf_counter() - t
    t = time.perf_counter()
    eng = Engine(cfg, params, mor=mor, mor_mode=mode,
                 n_slots=cell.traffic.slots, max_len=cell.traffic.max_len,
                 layout="paged", prefix_cache=cell.traffic.prefix_cache,
                 spare_pages=cell.traffic.spare_pages)
    timing["engine_s"] = time.perf_counter() - t
    return eng, abstract


def _tiles_computed(eng):
    """(sum of per-dispatch mean computed-tile fractions, dispatches)."""
    tel = eng.telemetry
    if tel is None or "mor_stats" not in tel.sums:
        return 0.0, 0
    return float(tel.sums["mor_stats"]["frac_tiles_computed"].mean()), \
        tel.n_updates


# -- what the metric readers see -----------------------------------------------------

@dataclass
class RunView:
    cell: Cell
    shape: cost.Shape
    peaks: Dict
    setup_s: float
    w0: float
    w1: float
    window: Dict
    spans: list
    dispatches: list
    live: Optional[float]             # computed-tile fraction in the window
    trace: Optional[object] = None    # xtrace.TraceSummary
    traced: list = field(default_factory=list)
    trace_host_s: float = 0.0


def warm_up(drv, deadline: float) -> Dict:
    """Build every step variant the window can reach, then put the
    closed loop in its long-run state.

    First a walk: one request from the mix's shortest prompt to its
    longest context, alone in the batch, so a decode dispatch runs at
    every context length the window can see, on a step that opens a
    page and on one that does not.  Then one stand-in request per slot
    (``Traffic.stationary``), all admitted together: their prompts are
    prefilled side by side, so a mixed dispatch runs at every chunk of
    context up to the longest stand-in, and each slot then decodes from
    a uniform point of a request's life.  The closed loop starts with
    them; the window opens once every stand-in has its first token."""
    spec = drv.spec
    t = time.perf_counter()
    walk = drv.submit(drv.traffic.tokens(5, 0, spec.prompt_len.lo),
                      spec.longest - spec.prompt_len.lo)
    if not drv.run_until(lambda: walk.done, deadline):
        raise Refused("the warm-up walk did not finish")
    walk_s = time.perf_counter() - t
    t = time.perf_counter()
    standins = [drv.submit(p, n) for p, n in drv.traffic.stationary()]
    drv.loop = True
    if not drv.run_until(lambda: all(r.token_t for r in standins), deadline):
        raise Refused("the stand-in requests did not start in time")
    return {"walk_s": walk_s, "standins_s": time.perf_counter() - t,
            "warmup_dispatches": len(drv.dispatches)}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             reduced: bool = False, control: bool = False,
             device_kind: Optional[str] = None,
             cache_dir: Optional[str] = None) -> Dict:
    """Set up, warm up, measure, check.  -> the result dict (not printed).
    ``reduced`` (tests only) serves the program's reduced config;
    ``cache_dir`` is the persistent compile cache's directory (default:
    the program's own choice)."""
    import jax
    from chipbench.driver import Driver
    from chipbench import xtrace

    clock = CompileClock.get()
    if cache_dir is not None:
        Path(cache_dir).mkdir(parents=True, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    else:
        from repro.launch.compile_cache import enable_compile_cache
        cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    dev = jax.devices()[0]
    kind = device_kind or dev.device_kind
    peaks = peaks_for(kind)
    limit = cell.config.get("correct", {}).get("max_logit_gap")
    if limit is None:
        raise Refused(f"{cell.config['name']}: no output limit measured")
    cfg = program_config(cell, reduced)
    arch = arch_numbers(cell, cfg, reduced)
    timing: Dict = {}
    eng, abstract = build_engine(cell, cfg, seed, timing)
    traffic = Traffic(cell.traffic, seed, arch["vocab_size"])
    drv = Driver(eng, traffic)
    t = time.perf_counter()
    timing.update(warm_up(drv, t + WARMUP_CAP_S))
    timing["warmup_s"] = time.perf_counter() - t
    n0, s0, h0 = clock.snap()
    lsum0, ln0 = _tiles_computed(eng)
    w0 = time.perf_counter()
    setup_s = w0 - T_START
    w1 = w0 + seconds
    trace_dir = None
    t_tr0 = t_tr1 = None
    if trace:
        trace_s = min(TRACE_MAX_S, 0.5 * seconds)
        drv.run_until(lambda: time.perf_counter() >= w0 + 0.25 * seconds)
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(trace_dir)
        t_tr0 = time.perf_counter()
        drv.run_until(lambda: time.perf_counter() >= t_tr0 + trace_s)
        t_tr1 = time.perf_counter()
        jax.profiler.stop_trace()
    drv.run_until(lambda: time.perf_counter() >= w1)
    n1, s1, h1 = clock.snap()
    lsum1, ln1 = _tiles_computed(eng)
    n_in_window = n1 - n0
    win = window_stats(drv.reqs, w0, w1, closed_loop=True)
    stats = dev.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))
    cal_s = timing.get("calibration_s", 0.0)
    log(f"setup: {setup_s:.2f} s = weights {timing['weights_s']:.2f} s, "
        f"calibration {cal_s:.2f} s, engine {timing['engine_s']:.2f} s, "
        f"warm-up {timing['warmup_s']:.2f} s (walk {timing['walk_s']:.2f} "
        f"s, stand-ins {timing['standins_s']:.2f} s, "
        f"{timing['warmup_dispatches']} dispatches); executables built "
        f"{n0} ({s0:.2f} s), persistent-cache hits {h0}; cache {cache_dir}")
    log(f"window: {seconds} s, {len(drv.dispatches)} dispatches so far, "
        f"executables built inside the window {n_in_window} "
        f"({s1 - s0:.2f} s, cache hits {h1 - h0}: "
        f"{clock.names[n0:n1]}); peak HBM {memory_peak}")
    if n_in_window:
        raise Refused(f"{n_in_window} executables were built inside the "
                      f"window: the warm-up missed a step variant")
    live = ((lsum1 - lsum0) / (ln1 - ln0)) if ln1 > ln0 else None
    view = RunView(cell=cell, shape=cost.Shape.from_config(arch),
                   peaks=peaks, setup_s=setup_s, w0=w0, w1=w1, window=win,
                   spans=drv.spans, dispatches=drv.dispatches, live=live)
    if trace:
        view.trace = xtrace.reduce_dir(Path(trace_dir), KERNELS)
        shutil.rmtree(trace_dir, ignore_errors=True)
        view.traced = [d for d in drv.dispatches
                       if d.t0 >= t_tr0 and d.t1 <= t_tr1]
        view.trace_host_s = t_tr1 - t_tr0
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        if dev.platform != "tpu" and m["source"] != "host_clock":
            continue                # no device number from another platform
        v = load_reader(m["name"])(view)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # -- the check, once the window is closed and the program freed --
    finished = []
    for r in drv.reqs:
        if r.rid >= 0 and r.done:
            toks = eng.results.get(r.rid, [])
            finished.append((r, check.Served(r.prompt, np.asarray(
                toks, np.int32))))
    chk = cell.traffic.check
    picked = check.sample(finished, w0, seed, chk["tokens"],
                          chk["min_requests"], chk["max_requests"])
    drv = eng = None
    gc.collect()
    t = time.perf_counter()
    ref_params = reference.make_weights(cell.config["reference"], abstract,
                                        seed)
    g, c = check.served_gaps(cell.config["reference"], ref_params, picked,
                             cell.traffic.max_len, arch, control=control)
    ref_params = None
    n_tok = int(g.size)
    max_gap = float(g.max()) if n_tok else float("nan")
    limit = float(limit)
    log(f"check: {len(picked)} requests, {n_tok} served tokens, reference "
        f"{time.perf_counter() - t:.2f} s")
    out = {"correct": None, "attempted": win["attempted"],
           "failed": win["failed"], "metrics": metrics,
           "device": {"platform": dev.platform, "kind": kind,
                      "count": len(jax.devices()),
                      "memory_peak_bytes": memory_peak}}
    if trace and view.trace is not None:
        out["device"]["busy_s"] = view.trace.busy_s
        out["device"]["window_s"] = view.trace.window_s
        out["breakdown"] = {"device_ops": view.trace.top_ops,
                            "idle_gaps": view.trace.idle_gaps}
    v = check.verdict(max_gap, limit, n_tok)
    out["correct"] = v["correct"]
    out["compared"] = v["compared"]
    out["_readings"] = {"served_gaps": g, "control_gaps": c,
                        "window_executables": n_in_window,
                        "setup": dict(timing, setup_s=setup_s,
                                      executables=n0, executables_s=s0,
                                      cache_hits=h0)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = Path.cwd()
    try:
        cell = load_cell(root, args.workload)
        import_program(root)
        check_device(cell.chips)
        res = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       cache_dir=str(root / CACHE_DIR))
    except Refused as e:
        print(f"[bench] refused: {e}", file=sys.stderr)
        return 2
    res.pop("_readings")
    for name, c in res["compared"].items():
        log(f"compared {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
