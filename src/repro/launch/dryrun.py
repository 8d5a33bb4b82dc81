import os
# a described-device tool: 512 placeholder CPU devices, never the chip
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("REPRO_EXTRA_XLA_FLAGS", ""))

"""Multi-pod dry-run: lower + compile every (arch x shape) cell against
the production mesh, prove memory fit, and extract roofline terms.

MUST be its own process (the XLA_FLAGS line above runs before any other
import so the 512 placeholder devices exist before jax locks the device
count).  Smoke tests / benches never import this module.

Usage:
  python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k \
      --mesh pod --out experiments/dryrun/qwen2-7b_train_4k_pod.json
Perf-iteration knobs: --no-seq-parallel --remat ... --grad-accum N
--moe-sharding ep|tp --mor-mode dense|tiled
"""
import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_config, input_specs
from repro.configs.base import ModelConfig, ShapeSpec
from repro.distributed.sharding_rules import (
    activation_context, batch_sharding, param_sharding)
from repro.launch import roofline
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import make_serve_step, make_train_step, make_loss_fn
from repro.models import cache_shapes, get_model, param_shapes, \
    supports_long_context
from repro.optim import OptConfig, adamw_init

SKIP_REASONS = {
    ("decode", "audio"): "encoder-only arch: no decode step",
}


def cell_status(cfg: ModelConfig, shape: ShapeSpec) -> str:
    """'run' or a skip reason (DESIGN.md §Arch-applicability)."""
    if shape.kind == "decode" and cfg.family == "audio":
        return "skip: encoder-only arch has no decode step"
    if shape.name == "long_500k" and not supports_long_context(cfg):
        return ("skip: full-attention arch is quadratic/unbounded-KV at "
                "500k (sub-quadratic archs only)")
    return "run"


def _cache_sharding(cache_sds, mesh):
    """Heuristic cache sharding: batch (dim 1) over dp; largest later dim
    divisible by the model-axis size over 'model'."""
    dp_axes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    dp = 1
    for a in dp_axes:
        dp *= mesh.shape[a]
    mp = mesh.shape.get("model", 1)
    dp_spec = dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes else None)

    def one(x):
        spec = [None] * x.ndim
        if x.ndim >= 2 and x.shape[1] % dp == 0 and x.shape[1] >= dp:
            spec[1] = dp_spec
        best, best_dim = 0, -1
        for i in range(2, x.ndim):
            if x.shape[i] % mp == 0 and x.shape[i] > best:
                best, best_dim = x.shape[i], i
        if best_dim >= 0 and mp > 1:
            spec[best_dim] = "model"
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map(one, cache_sds)


def lower_cell(cfg: ModelConfig, shape: ShapeSpec, mesh, *,
               seq_parallel: bool = True, mor_mode: str = "dense",
               layout: str = "fsdp_tp"):
    """Returns (lowered, n_chips)."""
    api = get_model(cfg)
    p_sds = param_shapes(cfg)
    p_shard = param_sharding(p_sds, mesh, moe_mode=cfg.expert_sharding,
                             layout=layout)
    data = input_specs(cfg, shape)

    with activation_context(mesh, sequence_parallel=seq_parallel):
        if shape.kind == "train":
            opt_sds = jax.eval_shape(
                lambda p: adamw_init(p, OptConfig()), p_sds)
            o_shard = {"step": NamedSharding(mesh, P()),
                       "mu": jax.tree_util.tree_map(lambda s: s, p_shard),
                       "nu": jax.tree_util.tree_map(lambda s: s, p_shard)}
            if "master" in opt_sds:
                o_shard["master"] = jax.tree_util.tree_map(
                    lambda s: s, p_shard)
            b_shard = batch_sharding(data, mesh)
            step = make_train_step(cfg, OptConfig())
            fn = jax.jit(step,
                         in_shardings=(p_shard, o_shard, b_shard),
                         out_shardings=(p_shard, o_shard, None),
                         donate_argnums=(0, 1))
            return fn.lower(p_sds, opt_sds, data)
        if shape.kind == "prefill":
            from repro.launch.steps import make_prefill
            fn = jax.jit(make_prefill(cfg, mor_mode=mor_mode),
                         in_shardings=(p_shard, batch_sharding(data, mesh)))
            return fn.lower(p_sds, data)
        # decode
        c_sds = cache_shapes(cfg, shape.global_batch, shape.seq_len)
        c_shard = _cache_sharding(c_sds, mesh)
        b_shard = batch_sharding(data["tokens"], mesh)
        step = make_serve_step(cfg, mor_mode=mor_mode)
        fn = jax.jit(step, in_shardings=(p_shard, c_shard, b_shard),
                     out_shardings=(None, c_shard), donate_argnums=(1,))
        return fn.lower(p_sds, c_sds, data["tokens"])


def run_cell(arch: str, shape_name: str, mesh_kind: str, *,
             seq_parallel: bool = True, mor_mode: str = "dense",
             remat: str = None, grad_accum: int = None,
             moe_sharding: str = None, out_path: str = None,
             layout: str = None) -> dict:
    cfg = get_config(arch)
    layout = layout or cfg.param_layout
    from repro.models.layers.attention import set_flash_threshold
    set_flash_threshold(cfg.flash_threshold)
    if remat:
        cfg = cfg.replace(remat=remat)
    if grad_accum:
        cfg = cfg.replace(grad_accum=grad_accum)
    if moe_sharding:
        cfg = cfg.replace(expert_sharding=moe_sharding)
    shape = SHAPES[shape_name]
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "seq_parallel": seq_parallel, "mor_mode": mor_mode,
           "remat": cfg.remat, "grad_accum": cfg.grad_accum,
           "layout": layout}

    status = cell_status(cfg, shape)
    if status != "run":
        rec["status"] = status
        print(f"[dryrun] {arch} x {shape_name} x {mesh_kind}: {status}")
        if out_path:
            os.makedirs(os.path.dirname(out_path), exist_ok=True)
            with open(out_path, "w") as f:
                json.dump(rec, f, indent=1)
        return rec

    multi_pod = mesh_kind == "multipod"
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    devices_per_pod = 256 if multi_pod else None
    t0 = time.time()
    try:
        lowered = lower_cell(cfg, shape, mesh, seq_parallel=seq_parallel,
                             mor_mode=mor_mode, layout=layout)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        hlo = compiled.as_text()
        from repro.launch import hlo_cost
        tripped = hlo_cost.analyze(
            hlo, bf16_promoted=(cfg.dtype == "bfloat16"))
        summary = roofline.summarize(cost or {}, hlo, cfg, shape, n_chips,
                                     devices_per_pod, tripped=tripped)
        summary["xla_cost_analysis_raw"] = {
            "flops": float((cost or {}).get("flops", 0.0)),
            "bytes_accessed": float((cost or {}).get("bytes accessed", 0.0)),
            "note": "loop bodies counted once by XLA; see hlo_cost",
        }
        mem_rec = {}
        for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                     "output_size_in_bytes", "generated_code_size_in_bytes",
                     "alias_size_in_bytes"):
            v = getattr(mem, attr, None)
            if v is not None:
                mem_rec[attr] = int(v)
        per_dev_bytes = (mem_rec.get("temp_size_in_bytes", 0)
                         + mem_rec.get("argument_size_in_bytes", 0))
        # CPU FloatNormalization promotes bf16 buffers to f32, doubling
        # the reported temp vs the TPU target; correct temp by 0.5 for
        # bf16-dtype models (optimizer args stay as measured).
        if cfg.dtype == "bfloat16":
            corrected = (mem_rec.get("temp_size_in_bytes", 0) * 0.5
                         + mem_rec.get("argument_size_in_bytes", 0))
        else:
            corrected = per_dev_bytes
        rec["per_device_gib_bf16_corrected"] = round(corrected / 2**30, 3)
        # memory-bound cells: fraction of ideal traffic (args+outputs-alias
        # = every byte that must be touched at least once) vs actual
        min_bytes = (mem_rec.get("argument_size_in_bytes", 0)
                     + mem_rec.get("output_size_in_bytes", 0)
                     - mem_rec.get("alias_size_in_bytes", 0))
        if summary.get("hlo_bytes_per_chip"):
            summary["memory_roofline_fraction"] = round(
                min_bytes / summary["hlo_bytes_per_chip"], 4)
        rec.update({
            "status": "ok",
            "n_chips": int(n_chips),
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            "memory_analysis": mem_rec,
            "per_device_bytes": per_dev_bytes,
            "per_device_gib": round(per_dev_bytes / 2**30, 3),
            "fits_16gib_hbm": corrected < 16 * 2**30,
            "roofline": summary,
        })
        print(f"[dryrun] {arch} x {shape_name} x {mesh_kind}: OK "
              f"(lower {t_lower:.0f}s compile {t_compile:.0f}s, "
              f"{rec['per_device_gib']} GiB/dev, "
              f"dominant={summary['dominant']}, "
              f"roofline_frac={summary['roofline_fraction']:.3f})")
        print("  memory_analysis:", mem_rec)
        print("  cost_analysis: flops=%.3e bytes=%.3e" %
              (summary["hlo_flops_per_chip"], summary["hlo_bytes_per_chip"]))
    except Exception as e:  # noqa: BLE001 — record the failure, don't die
        rec["status"] = f"error: {type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        print(f"[dryrun] {arch} x {shape_name} x {mesh_kind}: FAILED {e}",
              file=sys.stderr)
    if out_path:
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(rec, f, indent=1, default=str)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=sorted(SHAPES))
    ap.add_argument("--mesh", default="pod", choices=("pod", "multipod"))
    ap.add_argument("--out", default=None)
    ap.add_argument("--no-seq-parallel", action="store_true")
    ap.add_argument("--mor-mode", default="dense")
    ap.add_argument("--remat", default=None)
    ap.add_argument("--grad-accum", type=int, default=None)
    ap.add_argument("--moe-sharding", default=None,
                choices=(None, "ep", "tp", "ep_shmap"))
    ap.add_argument("--flash-threshold", type=int, default=None)
    ap.add_argument("--param-layout", default=None,
                    choices=(None, "fsdp_tp", "contract_tp"))
    args = ap.parse_args()
    if args.flash_threshold is not None:
        from repro.models.layers.attention import set_flash_threshold
        set_flash_threshold(args.flash_threshold)
    rec = run_cell(args.arch, args.shape, args.mesh,
                   seq_parallel=not args.no_seq_parallel,
                   mor_mode=args.mor_mode, remat=args.remat,
                   grad_accum=args.grad_accum,
                   moe_sharding=args.moe_sharding, out_path=args.out,
                   layout=args.param_layout)
    if rec["status"].startswith("error"):
        sys.exit(1)


if __name__ == "__main__":
    main()
