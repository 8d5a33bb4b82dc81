"""Benchmark harness: one entry per paper table/figure + the roofline
aggregation.  Prints ``name,us_per_call,derived`` CSV (timing = wall time
of the reproduction; derived = the figure's headline number).

``--scenario serve-engine`` instead benchmarks the continuous-batching
serving engine on a fixed mixed prompt-length trace (dense vs tiled vs
kernel execution, engine vs static-batch), emitting ``BENCH_serve.json``
— the CI smoke job runs it reduced-size."""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _run(name, fn):
    t0 = time.time()
    derived, detail = fn()
    us = (time.time() - t0) * 1e6
    print(f"{name},{us:.0f},{derived:.4f}", flush=True)
    return {"name": name, "us_per_call": us, "derived": derived,
            "detail": detail}


QUANTILE = 0.95     # tile-liveness quantile for capacity calibration


def scenario_serve_engine(modes=("dense", "tiled", "kernel"),
                          n_requests: int = 16, prompt_min: int = 8,
                          prompt_max: int = 96, gen_min: int = 4,
                          gen_len: int = 96, n_slots: int = 4,
                          chunk: int = 16, compute_scale: bool = True,
                          out: str = "BENCH_serve.json") -> dict:
    """Fixed mixed-length trace (heterogeneous prompts AND generation
    lengths) through the serving engine, per mode, plus the static-batch
    baseline for the tiled mode.  ``compute_scale`` adds rows at
    d_model=256/d_ff=1024/L=4 — the scale where per-dispatch compute
    dominates Python dispatch overhead, i.e. what the engine-vs-static
    comparison looks like off the toy config — in BOTH cache layouts,
    so the paged indirection's overhead is visible next to the slotted
    baseline.  Prefix caching is OFF here (the best-of-3 harness re-runs
    one trace, so the cache would hit its own prior passes and the
    tokens-dispatched accounting would stop meaning throughput); the
    dedicated shared-prompt benchmark is --scenario serve-prefix."""
    from repro.launch.serve import main as serve_main

    def run_mode(mode, extra, label, prefix_cache=False):
        argv = ["--arch", "granite-3-2b", "--reduced",
                "--batch", str(n_slots), "--requests", str(n_requests),
                "--prompt-min", str(prompt_min),
                "--prompt-max", str(prompt_max),
                "--gen-min", str(gen_min),
                "--gen-len", str(gen_len), "--chunk", str(chunk),
                "--mor", mode, "--calib-steps", "2"] + extra
        if not prefix_cache:
            argv.append("--no-prefix-cache")
        rep = serve_main(argv)
        row = {
            "tokens_per_s": rep["tokens_per_s"],
            "decode_tokens_per_s": rep["decode_tokens_per_s"],
            "requests": rep["requests_finished"],
            "dispatches": rep["dispatches"],
        }
        for k in ("static_batch_tokens_per_s", "engine_speedup_vs_static",
                  "token_agreement_vs_dense", "per_layer_capacity",
                  "calibrated_tokens_per_s", "per_layer_frac_tiles_live",
                  "obs", "static_capacity"):
            if k in rep:
                row[k] = rep[k]
        print(f"serve_engine_{label},0,{rep['tokens_per_s']:.1f}",
              flush=True)
        return row

    rows = {}
    for mode in modes:
        extra = []
        if mode != "dense":
            extra += ["--calibrate-capacity", str(QUANTILE)]
        if mode == "tiled":
            extra += ["--baseline", "--compare"]
        rows[mode] = run_mode(mode, extra, mode)
    if compute_scale:
        rows["dense@d256"] = run_mode(
            "dense", ["--dims", "256,1024,4", "--chunk", "32",
                      "--baseline"], "dense_d256")
        rows["dense@d256-slotted"] = run_mode(
            "dense", ["--dims", "256,1024,4", "--chunk", "32",
                      "--baseline", "--layout", "slotted"],
            "dense_d256_slotted")
        # layout_cost: paged / slotted throughput at the compute-bound
        # point — ≥ 1.0 means the paged indirection is free (or wins,
        # via in-place pool updates + active-window attends)
        for k in ("tokens_per_s", "decode_tokens_per_s"):
            rows["dense@d256"][f"layout_cost_{k}"] = round(
                rows["dense@d256"][k]
                / max(rows["dense@d256-slotted"][k], 1e-9), 3)
        # obs A/B at the same compute-dominated point: the full obs stack
        # (device-resident dispatch counters accumulated inside the
        # compiled step + the span tracer) vs the plain engine.  The
        # counters ride the step's return tuple and drain only at flush,
        # so the cost budget is < 3% tokens/s (acceptance criterion).
        # Separate-process A/B is hopeless for a 3% question on a shared
        # CPU (run-to-run spread ~10-20%), so both engines live in THIS
        # process and timed passes alternate off/on — best-of-N per side
        # over interleaved walls cancels the drift both sides see.
        import jax

        from repro.configs import get_config, reduce_config
        from repro.launch.serve import _run_engine, _trace
        from repro.models import get_model
        from repro.obs import Observability
        cfg = reduce_config(get_config("granite-3-2b")).replace(
            serve_chunk=32, d_model=256, d_ff=1024, n_layers=4)
        api = get_model(cfg)
        params = api.init(jax.random.PRNGKey(0), cfg)
        reqs = _trace(cfg, n_requests, prompt_min, prompt_max, gen_min,
                      gen_len, 0)
        kw = dict(mor=None, mor_mode="dense", n_slots=n_slots,
                  max_len=prompt_max + gen_len + 2, chunk=32,
                  prefix_cache=False)
        eng_off, _, _ = _run_engine(cfg, params, reqs, **kw)
        eng_on, _, rep_on = _run_engine(cfg, params, reqs,
                                        obs=Observability(), **kw)
        walls = {"off": float("inf"), "on": float("inf")}
        for _ in range(5):
            for label, eng in (("off", eng_off), ("on", eng_on)):
                eng.reset_counters()
                t0 = time.time()
                eng.run(list(reqs))
                walls[label] = min(walls[label], time.time() - t0)
        n_tok = rep_on["prefill_tokens"] + rep_on["decode_tokens"]
        rows["dense@d256-obs"] = {
            "tokens_per_s": n_tok / walls["on"],
            "decode_tokens_per_s": rep_on["decode_tokens"] / walls["on"],
            "paired_off_tokens_per_s": n_tok / walls["off"],
            "requests": rep_on["requests_finished"],
            "dispatches": rep_on["dispatches"],
            "obs": rep_on["obs"],
        }
        obs_overhead = round(1.0 - walls["off"] / walls["on"], 4)
        print(f"serve_engine_dense_d256_obs,0,{n_tok / walls['on']:.1f}",
              flush=True)
        print(f"serve_engine_obs_overhead,0,{obs_overhead:.4f}",
              flush=True)
    # obs demo: tiled mode with a static 0.5 capacity clamp (random-init
    # weights predict everything live, so the clamp is what makes the
    # tile-skip counters nonzero) and a shared prompt prefix (nonzero
    # prefix-hit counters) — the registry snapshot, device counters and
    # TTFT/ITL summaries land in BENCH_serve.json for the EXPERIMENTS.md
    # observability section
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        mpath = os.path.join(td, "metrics.json")
        tpath = os.path.join(td, "trace.json")
        rep_obs = serve_main(
            ["--arch", "granite-3-2b", "--reduced",
             "--batch", str(n_slots), "--requests", str(n_requests),
             "--prompt-min", str(prompt_min),
             "--prompt-max", str(max(prompt_max // 2, prompt_min)),
             "--gen-min", str(gen_min), "--gen-len", str(max(gen_len // 4, 4)),
             "--chunk", str(chunk), "--mor", "tiled", "--calib-steps", "2",
             "--capacity", "0.5", "--shared-prefix", str(2 * chunk),
             "--metrics-json", mpath, "--trace-out", tpath])
        metrics = json.load(open(mpath))["metrics"]
        trace = json.load(open(tpath))
    from repro.obs import validate_chrome_trace
    obs_demo = {
        "metrics": metrics,
        "device_metrics": rep_obs["obs"]["device_metrics"],
        "tracing": rep_obs["obs"]["tracing"],
        "tokens_per_s": rep_obs["tokens_per_s"],
        "trace_events": len(trace.get("traceEvents", [])),
        "trace_problems": validate_chrome_trace(trace),
        "static_capacity": rep_obs.get("static_capacity"),
    }
    print(f"serve_engine_obs_demo,0,{rep_obs['tokens_per_s']:.1f}",
          flush=True)
    result = {"trace": {"n_requests": n_requests, "prompt_min": prompt_min,
                        "prompt_max": prompt_max, "gen_min": gen_min,
                        "gen_len": gen_len, "n_slots": n_slots,
                        "chunk": chunk, "arch": "granite-3-2b (reduced)",
                        "quantile": QUANTILE,
                        "compute_scale": compute_scale},
              "modes": rows,
              "obs_demo": obs_demo}
    if compute_scale:
        result["obs_overhead"] = obs_overhead
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"wrote {out}")
    return result


def scenario_serve_prefix(archs=("granite-3-2b", "rwkv6-3b"),
                          n_requests: int = 8, prefix_len: int = 48,
                          suffix_min: int = 4, suffix_max: int = 24,
                          gen_len: int = 16, n_slots: int = 2,
                          chunk: int = 8,
                          out: str = "BENCH_prefix.json") -> dict:
    """Prefix caching on a shared-prompt trace (ISSUE 4): every request
    carries the same ``prefix_len``-token system prompt plus a unique
    suffix — the workload paged KV + prefix caching dedups.  Per arch
    (attention = shared full pages, ssm = recurrent-state snapshots),
    runs the SAME trace cold (prefix cache off) and warm (on), asserts
    zero token divergence, and reports the hit rate, chunks/pages
    skipped and the warm-vs-cold speedup."""
    import jax
    import numpy as np

    from repro.configs import get_config, reduce_config
    from repro.launch.serve import _run_engine, _trace
    from repro.models import get_model

    rows = {}
    for arch in archs:
        cfg = reduce_config(get_config(arch)).replace(serve_chunk=chunk)
        api = get_model(cfg)
        params = api.init(jax.random.PRNGKey(0), cfg)
        reqs = _trace(cfg, n_requests, suffix_min, suffix_max, gen_len // 2,
                      gen_len, 0, shared_prefix=prefix_len)
        max_len = prefix_len + suffix_max + gen_len + 2
        kw = dict(mor=None, mor_mode="dense", n_slots=n_slots,
                  max_len=max_len, chunk=chunk)
        _, res_cold, rep_cold = _run_engine(cfg, params, reqs,
                                            prefix_cache=False, **kw)
        _, res_warm, rep_warm = _run_engine(cfg, params, reqs,
                                            prefix_cache=True, **kw)
        assert res_cold == res_warm, f"{arch}: prefix cache changed tokens"
        pc = rep_warm["prefix_cache"]
        # throughput on the SAME trace: tokens *served* per second
        # (prompt + generated), not tokens *dispatched* — a prefix hit
        # serves prompt tokens without dispatching them, which is the
        # whole point
        n_trace = sum(len(p) + g for p, g in reqs)
        row = {
            "cold_trace_tokens_per_s": n_trace / rep_cold["wall_s"],
            "warm_trace_tokens_per_s": n_trace / rep_warm["wall_s"],
            "speedup": round(rep_cold["wall_s"] / rep_warm["wall_s"], 3),
            "cold_prefill_tokens": rep_cold["prefill_tokens"],
            "warm_prefill_tokens": rep_warm["prefill_tokens"],
            "cold_dispatches": rep_cold["dispatches"],
            "warm_dispatches": rep_warm["dispatches"],
            "hit_rate": pc["hit_rate"],
            "chunks_skipped": pc["chunks_skipped"],
            "pages_shared": pc["pages_shared"],
            "pages_cowed": pc["pages_cowed"],
            "snapshots": pc["snapshots"],
            "snap_restores": pc["snap_restores"],
            "tokens_match": True,
        }
        print(f"serve_prefix_{arch},0,{row['speedup']:.3f}", flush=True)
        rows[arch] = row
    result = {"trace": {"n_requests": n_requests, "prefix_len": prefix_len,
                        "suffix_min": suffix_min, "suffix_max": suffix_max,
                        "gen_len": gen_len, "n_slots": n_slots,
                        "chunk": chunk, "archs": list(archs),
                        "note": "reduced configs; warm = best-of-3 after "
                                "a warmup pass, so the warm rows measure "
                                "a fully-populated prefix cache"},
              "archs": rows}
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"wrote {out}")
    return result


def scenario_serve_sharded(n_requests: int = 16, prompt_min: int = 8,
                           prompt_max: int = 96, gen_min: int = 4,
                           gen_len: int = 96, n_slots: int = 4,
                           chunk: int = 16,
                           out: str = "BENCH_sharded.json") -> dict:
    """Mesh-sharded paged serving (ISSUE 5): the serve-engine mixed
    trace (prompts 8-96 x gens 4-96) through
    ``Engine(layout="paged-sharded")`` on a page mesh over every visible
    device vs the single-device paged engine.  Asserts token-identical
    output with the prefix cache ON and OFF, nonzero page high-water on
    EVERY shard, and exactly ONE flash-merge collective per attention
    layer in the compiled decode step (the acceptance criteria).  Run
    under ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` on a
    single host; on real multi-device hardware the same flag-free
    invocation shards over the accelerators.  Throughput rows are a
    layout-cost datapoint on forced host devices (the shards contend
    for the same CPU), NOT a speedup claim — the win this layout buys
    is KV capacity: per-device page memory drops by 1/P (reported as
    ``kv_pages_per_shard`` vs the single-device pool)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config, reduce_config
    from repro.launch.mesh import make_page_mesh
    from repro.launch.serve import _run_engine, _trace
    from repro.models import get_model

    n_dev = len(jax.devices())
    assert n_dev >= 2, (
        "serve-sharded needs a multi-device mesh: run with "
        "XLA_FLAGS=--xla_force_host_platform_device_count=4")
    mesh = make_page_mesh(n_dev)
    cfg = reduce_config(get_config("granite-3-2b")).replace(
        serve_chunk=chunk)
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)
    reqs = _trace(cfg, n_requests, prompt_min, prompt_max, gen_min,
                  gen_len, 0)
    max_len = prompt_max + gen_len + 2
    kw = dict(mor=None, mor_mode="dense", n_slots=n_slots,
              max_len=max_len, chunk=chunk)
    rows = {}
    eng_sh = None
    for prefix in (False, True):
        label = "prefix_on" if prefix else "prefix_off"
        eng_p, res_p, rep_p = _run_engine(cfg, params, reqs,
                                          prefix_cache=prefix, **kw)
        eng_sh, res_m, rep_m = _run_engine(cfg, params, reqs,
                                           layout="paged-sharded",
                                           mesh=mesh, prefix_cache=prefix,
                                           **kw)
        assert res_p == res_m, f"{label}: sharded tokens diverge"
        sh = rep_m["sharding"]
        hw = sh["kv_pages_hiwater_per_shard"]
        assert all(n > 0 for n in hw), f"{label}: empty shard {hw}"
        rows[label] = {
            "paged_tokens_per_s": rep_p["tokens_per_s"],
            "sharded_tokens_per_s": rep_m["tokens_per_s"],
            "layout_cost": round(rep_p["tokens_per_s"]
                                 / max(rep_m["tokens_per_s"], 1e-9), 3),
            "dispatches": rep_m["dispatches"],
            "kv_pages_single_device": eng_p.pool.n_pages,
            "kv_pages_per_shard": sh["kv_pages_per_shard"],
            "kv_pages_hiwater_per_shard": hw,
            "tokens_match": True,
        }
        print(f"serve_sharded_{label},0,{rep_m['tokens_per_s']:.1f}",
              flush=True)
    # one collective per attention layer per dispatch: the compiled
    # decode step's layer scan carries exactly one all-gather (the
    # packed flash merge) and no other collective
    lowered = eng_sh._step.lower(
        params, None, eng_sh.cache, jnp.zeros((n_slots, 1), jnp.int32),
        jnp.ones((n_slots,), jnp.int32), jnp.ones((n_slots,), bool),
        eng_sh._pending, eng_sh._base_key, None)
    lines = lowered.as_text().splitlines()
    n_ag = sum(1 for ln in lines
               if "all_gather" in ln or "all-gather" in ln)
    n_other = sum(1 for ln in lines
                  if "all_reduce" in ln or "all-reduce" in ln
                  or "collective_permute" in ln
                  or "collective-permute" in ln)
    # the paged layer loop is UNROLLED (per-layer tuple pool leaves, so
    # scatters stay in-place): the lowered step shows one all-gather
    # per layer rather than one inside a scan body
    assert n_ag == cfg.n_layers and n_other == 0, \
        (n_ag, cfg.n_layers, n_other)
    result = {"trace": {"arch": "granite-3-2b (reduced)",
                        "n_requests": n_requests,
                        "prompt_min": prompt_min, "prompt_max": prompt_max,
                        "gen_min": gen_min, "gen_len": gen_len,
                        "n_slots": n_slots, "chunk": chunk,
                        "n_shards": n_dev,
                        "note": "forced host devices share one CPU: the "
                                "tok/s rows price the shard_map layout, "
                                "the per-shard page counts show the "
                                "1/P KV-capacity scaling"},
              "collectives_per_attention_layer": 1,
              "modes": rows}
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"wrote {out}")
    return result


def scenario_serve_spec(ks=(2, 4, 8), caps=(0.0, 0.5), dims="256,1024,4",
                        n_requests: int = 10, prompt_min: int = 8,
                        prompt_max: int = 48, gen_min: int = 8,
                        gen_len: int = 32, n_slots: int = 4,
                        chunk: int = 32, with_mor: bool = True,
                        out: str = "BENCH_spec.json") -> dict:
    """Self-speculative decoding (ISSUE 9): the serve-engine mixed trace
    through ``Engine(spec_k=k, draft_cap=c)`` swept over the draft
    length and the MoR draft capacity, against the non-spec engine on
    the SAME seeded trace.  Greedy identity is ASSERTED for every
    dense-mode row (speculation must not change tokens).  Two engine
    families: ``dense`` (draft == target plans — the acceptance
    ceiling and the pure round-shape cost) and calibrated ``tiled``
    (clamped draft plans; ``draft_cap`` is a traced leaf, so the sweep
    shares one compiled step per phase).

    ITL accounting: the tracer observes inter-DISPATCH latency, which
    under speculation is the round cadence (a round emits
    ``1 + acceptance*k`` tokens at once), so rows carry both the raw
    round ITL and the per-token effective ITL (round ITL / mean tokens
    per round) — the headline compares effective ITL at the
    compute-dominated d256 scale, where a verify pass's k+1 positions
    ride one dispatch."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, reduce_config
    from repro.launch.serve import _run_engine, _trace
    from repro.models import get_model
    from repro.obs import Observability

    cfg = reduce_config(get_config("granite-3-2b")).replace(
        serve_chunk=chunk)
    if dims and dims != "none":
        d, f, L = (int(x) for x in dims.split(","))
        cfg = cfg.replace(d_model=d, d_ff=f, n_layers=L)
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)
    reqs = _trace(cfg, n_requests, prompt_min, prompt_max, gen_min,
                  gen_len, 0)
    max_len = prompt_max + gen_len + 2
    # prefix cache off: best-of-3 re-runs one trace (see serve-engine)
    kw = dict(n_slots=n_slots, max_len=max_len, chunk=chunk,
              prefix_cache=False)

    def run_one(label, params_r, mor, mor_mode, spec_k=0, draft_cap=0.0):
        eng, results, rep = _run_engine(
            cfg, params_r, reqs, mor=mor, mor_mode=mor_mode,
            obs=Observability(), spec_k=spec_k, draft_cap=draft_cap,
            **kw)
        itl = eng.obs.tracer.summary().get("itl") or {}
        row = {"tokens_per_s": rep["tokens_per_s"],
               "decode_tokens_per_s": rep["decode_tokens_per_s"],
               "itl_round_p50_ms": round((itl.get("p50") or 0.0) * 1e3,
                                         3),
               "itl_round_p99_ms": round((itl.get("p99") or 0.0) * 1e3,
                                         3),
               "dispatches": rep["dispatches"],
               "requests": rep["requests_finished"]}
        tokens_per_round = 1.0
        if spec_k:
            sp = rep["spec"]
            tokens_per_round = (rep["decode_tokens"]
                                / max(sp["rounds"], 1))
            row.update(
                k=spec_k, draft_cap=draft_cap,
                acceptance_rate=round(sp["acceptance_rate"], 4),
                rounds=sp["rounds"], replays=sp["replays"],
                aborts=sp["aborts"],
                tokens_drafted=sp["tokens_drafted"],
                tokens_accepted=sp["tokens_accepted"],
                tokens_per_round=round(tokens_per_round, 3))
            dm = rep.get("obs", {}).get("device_metrics", {})
            for key in ("tokens_drafted", "tokens_accepted"):
                if key in dm:
                    row[f"device_{key}"] = dm[key]
        row["itl_per_token_p50_ms"] = round(
            row["itl_round_p50_ms"] / max(tokens_per_round, 1.0), 3)
        print(f"serve_spec_{label},0,{rep['tokens_per_s']:.1f}",
              flush=True)
        return results, row

    modes = {}
    res_base, base = run_one("dense_base", params, None, "dense")
    dense = {"baseline": base, "spec": []}
    for k in ks:
        res_s, row = run_one(f"dense_k{k}", params, None, "dense",
                             spec_k=k)
        row["tokens_match_baseline"] = (res_s == res_base)
        assert row["tokens_match_baseline"], \
            f"k={k}: speculation changed greedy tokens"
        dense["spec"].append(row)
    modes["dense"] = dense
    if with_mor:
        from repro.core.deploy import calibrate_lm
        from repro.data.pipeline import synthetic_lm_batch

        def batches():
            s = 0
            while True:
                b = synthetic_lm_batch(cfg, 4, 64, seed=0, step=s)
                yield {"tokens": jnp.asarray(b["tokens"])}
                s += 1
        params_m, mor, _ = calibrate_lm(params, cfg, api.forward,
                                        batches(), 2)
        res_mb, mbase = run_one("tiled_base", params_m, mor, "tiled")
        tiled = {"baseline": mbase, "spec": []}
        for k in ks:
            for cap in caps:
                res_m, row = run_one(f"tiled_k{k}_c{cap}", params_m, mor,
                                     "tiled", spec_k=k, draft_cap=cap)
                # informational only: tile capacity couples tokens
                # within a dispatch, so K+1-wide verify under tiled
                # plans is not bit-equal to 1-wide decode (greedy
                # identity is a dense-mode guarantee)
                row["tokens_match_baseline"] = (res_m == res_mb)
                tiled["spec"].append(row)
        modes["tiled"] = tiled

    # headline: best dense-mode config meeting the acceptance bar, its
    # effective ITL against the non-spec baseline (5% wall noise slack)
    cand = [r for r in dense["spec"] if r["acceptance_rate"] >= 0.5]
    best = max(cand or dense["spec"], key=lambda r: r["tokens_per_s"])
    headline = {
        "baseline_tokens_per_s": base["tokens_per_s"],
        "baseline_itl_p50_ms": base["itl_per_token_p50_ms"],
        "best_k": best["k"], "best_draft_cap": best["draft_cap"],
        "best_tokens_per_s": best["tokens_per_s"],
        "best_itl_per_token_p50_ms": best["itl_per_token_p50_ms"],
        "best_acceptance_rate": best["acceptance_rate"],
        "speedup_vs_baseline": round(
            best["tokens_per_s"] / max(base["tokens_per_s"], 1e-9), 3),
        "meets_acceptance": best["acceptance_rate"] >= 0.5,
        "itl_no_worse": (best["itl_per_token_p50_ms"]
                         <= base["itl_per_token_p50_ms"] * 1.05),
    }
    print(f"serve_spec_best_k{best['k']},0,"
          f"{headline['speedup_vs_baseline']:.3f}", flush=True)
    print(f"serve_spec_acceptance,0,{best['acceptance_rate']:.4f}",
          flush=True)
    result = {"trace": {"arch": "granite-3-2b (reduced)", "dims": dims,
                        "n_requests": n_requests,
                        "prompt_min": prompt_min,
                        "prompt_max": prompt_max, "gen_min": gen_min,
                        "gen_len": gen_len, "n_slots": n_slots,
                        "chunk": chunk, "ks": list(ks),
                        "draft_caps": list(caps),
                        "note": "ITL is per emitting dispatch = per "
                                "round under speculation; per-token "
                                "effective ITL divides by the round's "
                                "mean emitted tokens"},
              "modes": modes,
              "headline": headline}
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"wrote {out}")
    return result


def scenario_moe_modes(modes=("dense", "exact", "tiled", "kernel"),
                       n_requests: int = 8, prompt_min: int = 4,
                       prompt_max: int = 24, gen_min: int = 4,
                       gen_len: int = 12, n_slots: int = 2, chunk: int = 8,
                       dead_frac: float = 0.5,
                       out: str = "BENCH_moe_modes.json") -> dict:
    """Expert-level MoR through the serving engine, per execution mode
    (ISSUE 3): a mixed-length trace on reduced mixtral-8x7b with
    per-(layer, expert) calibrated predictors, reporting each mode's
    expert tile-skip fraction, step time and throughput vs dense, plus
    the telemetry-calibrated per-(layer, expert) capacities.

    Random-init models have no structured ReLU sparsity (measured
    frac_tiles_live = 1.0 in BENCH_serve.json), so the calibration
    injects a trained-model-like column sparsity profile
    (``calibrate_moe(inject_dead_frac=...)``, paper Fig. 1) — the skip
    fractions measure the machinery end to end, not model quality."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config, reduce_config
    from repro.configs.base import MoRConfig
    from repro.core.deploy import calibrate_moe
    from repro.data.pipeline import synthetic_lm_batch
    from repro.launch.serve import _run_engine, _trace
    from repro.models import get_model

    cfg = reduce_config(get_config("mixtral-8x7b")).replace(
        serve_chunk=chunk,
        # narrow tiles: at reduced dims (f = 64) the default 8x128 tile
        # covers a whole expert row-block, leaving nothing to skip
        mor=MoRConfig(enabled=True, relufied=True, corr_threshold=0.5,
                      tile_m=4, tile_n=16))
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)

    def batches():
        s = 0
        while True:
            b = synthetic_lm_batch(cfg, 4, 32, seed=0, step=s)
            yield {"tokens": jnp.asarray(b["tokens"])}
            s += 1

    params, mor, cal = calibrate_moe(params, cfg, api.forward, batches(), 2,
                                     cluster_experts=False,
                                     inject_dead_frac=dead_frac)
    # held-out probe batch for the predictor-driven skip measurement
    probe = {"tokens": jnp.asarray(
        synthetic_lm_batch(cfg, 4, 32, seed=1, step=999)["tokens"])}
    reqs = _trace(cfg, n_requests, prompt_min, prompt_max, gen_min,
                  gen_len, 0)
    max_len = prompt_max + gen_len + 2
    rows = {}
    dense_tps = None
    for mode in modes:
        # prefix cache off: the harness re-runs one trace best-of-3, so
        # the cache would dedup prefill and skew the tok/s accounting
        eng, results, rep = _run_engine(
            cfg, params, reqs, mor=mor if mode != "dense" else None,
            mor_mode=mode, n_slots=n_slots, max_len=max_len, chunk=chunk,
            prefix_cache=False)
        row = {
            "tokens_per_s": rep["tokens_per_s"],
            "decode_tokens_per_s": rep["decode_tokens_per_s"],
            "dispatches": rep["dispatches"],
            "step_ms": round(rep["wall_s"] / max(rep["dispatches"], 1)
                             * 1e3, 3),
        }
        if mode == "dense":
            dense_tps = rep["tokens_per_s"]
        else:
            # predictor-driven skip: measured on the training-path
            # forward, where expert buffers run at expected occupancy
            # (C = cf*T*k/E).  The serving-telemetry fractions below
            # denominate over the full serving capacity buffer (C = T,
            # pad rows force-skipped), so they also count buffer
            # under-occupancy as skip — report both, assert on the
            # predictor one (CI moe-modes-smoke).
            _, aux = api.forward(params, cfg, probe, mor=mor,
                                 mor_mode=mode)
            comp = np.asarray(aux["moe_mor_stats"]["frac_tiles_computed"])
            row["expert_tile_skip_frac"] = round(1.0 - float(comp.mean()),
                                                 4)
            scomp = np.asarray(rep["per_expert_frac_tiles_computed"])
            row["serving_expert_tile_skip_frac"] = \
                round(1.0 - float(scomp.mean()), 4)
            row["per_expert_frac_tiles_live"] = \
                rep["per_expert_frac_tiles_live"]
            caps = eng.calibrate_capacities(quantile=QUANTILE)
            row["per_expert_capacity"] = \
                np.asarray(caps["moe_mor_stats"]).round(4).tolist()
        if dense_tps:
            row["speedup_vs_dense"] = round(row["tokens_per_s"]
                                            / dense_tps, 3)
        print(f"moe_modes_{mode},0,{rep['tokens_per_s']:.1f}", flush=True)
        rows[mode] = row
    result = {"trace": {"arch": "mixtral-8x7b (reduced)",
                        "n_requests": n_requests, "prompt_min": prompt_min,
                        "prompt_max": prompt_max, "gen_min": gen_min,
                        "gen_len": gen_len, "n_slots": n_slots,
                        "chunk": chunk, "tile_m": cfg.mor.tile_m,
                        "tile_n": cfg.mor.tile_n,
                        "inject_dead_frac": dead_frac,
                        "quantile": QUANTILE},
              "calibration": cal,
              "modes": rows}
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"wrote {out}")
    return result


def scenario_paged_kernel(batch_sizes=(2, 4, 8), blocks=(8, 16, 32),
                          page: int = 8, hkv: int = 4, groups: int = 2,
                          head_dim: int = 64, reps: int = 50,
                          out: str = "BENCH_paged_kernel.json") -> dict:
    """Paged flash-decode microbench (the PR 6 tentpole kernel): one
    decode step of ``gqa_paged_flash`` against the pure-jnp gather
    fallback (``pool_view`` + ``attend_batched``) across batch sizes and
    per-slot page counts, plus a bandwidth roofline per point — the
    pool bytes a decode step must touch (k+v pages of the active
    window) over the measured wall, formatted with the same helpers the
    EXPERIMENTS.md roofline tables use (``roofline_table.fmt_s``).  Off
    TPU the kernel row runs in Pallas interpret mode and is priced for
    CORRECTNESS visibility only (``kernel_backend`` says which); the
    jnp rows and the roofline columns are the portable signal."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    try:
        from benchmarks.roofline_table import fmt_s
    except ModuleNotFoundError:        # invoked as benchmarks/run.py
        from roofline_table import fmt_s
    from repro.distributed import decode_attention as da
    from repro.kernels import paged_attention as pk
    from repro.models.layers.attention import attend_batched

    on_tpu = jax.default_backend() == "tpu"
    rng = np.random.RandomState(0)
    rows = []
    # scoped kernel-trace frame: this benchmark reports ITS OWN dispatch
    # counts even when something else in the process (another scenario,
    # a prior engine run) already bumped the process-global counters
    trace_ctx = pk.trace_scope()
    scope_counts = trace_ctx.__enter__()
    for B in batch_sizes:
        for nb in blocks:
            n_pages = 1 + B * nb + B * nb // 2
            ring = nb * page
            key = jax.random.PRNGKey(nb * 131 + B)
            ks = jax.random.split(key, 8)
            kpool = jax.random.normal(ks[0], (n_pages, page, hkv, head_dim),
                                      jnp.float32)
            vpool = jax.random.normal(ks[1], (n_pages, page, hkv, head_dim),
                                      jnp.float32)
            perm = rng.permutation(np.arange(1, n_pages))[:B * nb]
            bt = jnp.asarray(perm.reshape(B, nb), jnp.int32)
            qpos = jnp.full((B, 1), ring - 1, jnp.int32)
            tags = jnp.arange(ring, dtype=jnp.int32).reshape(nb, page)
            ppool = jnp.full((n_pages, page), -1, jnp.int32)
            ppool = ppool.at[bt[0]].set(tags)
            for b in range(1, B):
                ppool = ppool.at[bt[b]].set(tags)
            q = jax.random.normal(ks[2], (B, 1, hkv * groups, head_dim),
                                  jnp.float32)

            def jnp_gather(q, kpool, vpool, ppool):
                gk = da.pool_view(kpool, bt, 0).reshape(B, ring, hkv,
                                                        head_dim)
                gv = da.pool_view(vpool, bt, 0).reshape(B, ring, hkv,
                                                        head_dim)
                gp = da.pool_view(ppool, bt, -1).reshape(B, ring)
                return attend_batched(q, gk, gv, qpos, gp, causal=True,
                                      window=0)

            def jnp_pool_direct(q, kpool, vpool, ppool):
                kv_pos = da.pool_positions(ppool, bt)
                return da.gqa_pool_flash(q, kpool, vpool, kv_pos, qpos,
                                         window=0)

            def kernel(q, kpool, vpool, ppool):
                return pk.gqa_paged_flash(q, kpool, vpool, ppool, bt,
                                          qpos, window=0,
                                          interpret=not on_tpu)

            def time_fn(fn, n):
                f = jax.jit(fn)
                o = f(q, kpool, vpool, ppool)
                jax.block_until_ready(o)
                t0 = time.time()
                for _ in range(n):
                    o = f(q, kpool, vpool, ppool)
                jax.block_until_ready(o)
                return (time.time() - t0) / n, o

            t_g, o_g = time_fn(jnp_gather, reps)
            t_d, o_d = time_fn(jnp_pool_direct, reps)
            t_k, o_k = time_fn(kernel, reps if on_tpu else 2)
            assert np.allclose(o_g, o_k, atol=2e-5), (B, nb)
            assert np.allclose(o_g, o_d, atol=2e-5), (B, nb)
            # roofline: a decode step must read the active window's k+v
            # pages once — anything above that is gather/copy overhead
            window_bytes = 2 * B * ring * hkv * head_dim * 4
            row = {"batch": B, "blocks_per_slot": nb, "ring": ring,
                   "window_bytes": window_bytes,
                   "jnp_gather_us": round(t_g * 1e6, 1),
                   "jnp_pool_direct_us": round(t_d * 1e6, 1),
                   "kernel_us": round(t_k * 1e6, 1),
                   "jnp_gather_gbps": round(window_bytes / t_g / 1e9, 2),
                   "jnp_pool_direct_gbps": round(window_bytes / t_d / 1e9,
                                                 2),
                   "kernel_gbps": round(window_bytes / t_k / 1e9, 2),
                   "kernel_vs_gather": round(t_g / t_k, 3)}
            rows.append(row)
            print(f"paged_kernel_B{B}_nb{nb},"
                  f"{t_k*1e6:.0f},{t_g/t_k:.4f}", flush=True)
    trace_ctx.__exit__(None, None, None)   # scope counts survive the exit
    md = ["| B | blocks | window | jnp gather | pool direct | kernel | "
          "kernel GB/s |", "|---|---|---|---|---|---|---|"]
    for r in rows:
        md.append(f"| {r['batch']} | {r['blocks_per_slot']} | "
                  f"{r['ring']} | {fmt_s(r['jnp_gather_us']/1e6)} | "
                  f"{fmt_s(r['jnp_pool_direct_us']/1e6)} | "
                  f"{fmt_s(r['kernel_us']/1e6)} | {r['kernel_gbps']} |")
    result = {"shape": {"page": page, "n_kv_heads": hkv, "groups": groups,
                        "head_dim": head_dim, "dtype": "float32"},
              "kernel_backend": ("pallas-tpu" if on_tpu
                                 else "pallas-interpret"),
              "kernel_traces": dict(scope_counts),
              "rows": rows,
              "markdown": "\n".join(md)}
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"wrote {out}")
    return result


def scenario_serve_slo(policies=("fcfs", "priority", "sjf"),
                       rate_mults=(0.5, 1.0, 2.5),
                       duration_s: float = 4.0, n_slots: int = 4,
                       chunk: int = 8, gen_max: int = 16,
                       seed: int = 0, hi_pri_frac: float = 0.25,
                       out: str = "BENCH_slo.json") -> dict:
    """SLO under open-loop load (ISSUE 8): seeded Poisson arrivals at
    0.5x/1x/2.5x the measured closed-loop capacity drive each admission
    policy over the SAME offered load (same seed => byte-identical
    arrivals), reporting p50/p99 TTFT per priority class and ITL per
    policy.  A 5% oversize-injection exercises the typed rejection path
    mid-load.  Alongside the sweep, a deterministic preemption twin
    checks that a run with page-spill preemptions is token-identical to
    its FCFS no-preemption twin."""
    import jax
    import numpy as np

    from repro.configs import get_config, reduce_config
    from repro.models import get_model
    from repro.obs import Observability
    from repro.serving import Engine
    from repro.serving.loadgen import (latency_stats, poisson_trace,
                                       run_open_loop)

    cfg = reduce_config(get_config("granite-3-2b")).replace(
        serve_chunk=chunk)
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)
    p_lo, p_hi = 6, 3 * chunk
    max_len = p_hi + gen_max + 2

    engines = {p: Engine(cfg, params, mor_mode="dense", n_slots=n_slots,
                         max_len=max_len, chunk=chunk, telemetry=False,
                         obs=Observability(), policy=p)
               for p in policies}

    # closed-loop capacity (also the compile warmup): how many requests
    # per second the engine serves when the driver never lets it idle —
    # the sweep's offered loads are multiples of this
    rng = np.random.default_rng(seed)
    warm = [(rng.integers(1, cfg.vocab_size,
                          size=rng.integers(p_lo, p_hi + 1)
                          ).astype(np.int32),
             int(rng.integers(4, gen_max + 1))) for _ in range(12)]
    cap_wall = None
    for name, eng in engines.items():
        eng.run(list(warm))                      # compile everything
        eng.reset_counters()
        t0 = time.perf_counter()
        eng.run(list(warm))
        wall = time.perf_counter() - t0
        if name == "fcfs":
            cap_wall = wall
    capacity_req_s = len(warm) / cap_wall
    print(f"serve_slo_capacity_req_s,0,{capacity_req_s:.2f}", flush=True)

    # warm the preemption path too: the first spill/restore round-trip
    # compiles its gather/scatter kernels, and without this the stall
    # lands in the tail latencies of whichever timed run first hits
    # pool pressure (or a priority preemption)
    for eng in engines.values():
        for p, _ in warm[:n_slots + 1]:
            eng.submit(p, 4)
        for _ in range(2):
            eng.step()
        victim = eng.policy.spill_victim(eng.scheduler.slots)
        if eng._can_preempt and victim is not None:
            eng._preempt(victim)
        eng.run()
        eng.reset_counters()

    runs = []
    for mult in rate_mults:
        rate = capacity_req_s * mult
        arrivals = poisson_trace(
            rate, duration_s, cfg.vocab_size, seed=seed,
            prompt_len=(p_lo, p_hi), max_new=(4, gen_max),
            hi_pri_frac=hi_pri_frac, oversize_frac=0.05,
            max_len=max_len)
        for name, eng in engines.items():
            eng.reset_counters()
            res = run_open_loop(eng, arrivals)
            spans = eng.obs.tracer.request_spans()
            ttft = latency_stats(spans, res.submitted, arrivals)
            lost = sum(
                1 for rid, idx in res.submitted.items()
                if len(eng.results.get(rid, ()))
                != arrivals[idx].max_new_tokens)
            tr = eng.obs.tracer.summary()
            row = {
                "policy": name, "offered_x": mult,
                "rate_req_s": round(rate, 3),
                "n_arrivals": len(arrivals),
                "n_submitted": res.n_submitted,
                "n_rejected": len(res.rejected),
                "requests_lost": lost,
                "preemptions": eng.counters["preemptions"],
                "restores": eng.pool.spill_events["restores"],
                "ttft": ttft, "itl": tr["itl"],
                "queue_wait": tr["queue_wait"],
                "wall_s": round(res.wall_s, 3),
            }
            runs.append(row)
            p99 = ttft.get("all", {}).get("p99", float("nan"))
            print(f"serve_slo_{name}_x{mult},0,{p99:.4f}", flush=True)

    # deterministic preemption twin: same requests, priority policy
    # (forced preemptions) vs FCFS (none) — greedy sampling makes the
    # per-request token streams scheduling-invariant, so any divergence
    # is a spill/restore bug
    twin_prompts = [rng.integers(1, cfg.vocab_size,
                                 size=rng.integers(p_lo, p_hi + 1)
                                 ).astype(np.int32)
                    for _ in range(n_slots + 4)]
    e_f, e_p = engines["fcfs"], engines.get("priority")
    rids_f = [e_f.submit(p, gen_max) for p in twin_prompts]
    e_f.run()
    pre0 = e_p.counters["preemptions"]
    rids_p = [e_p.submit(p, gen_max)
              for p in twin_prompts[:n_slots + 1]]
    for _ in range(3):
        e_p.step()
    rids_p += [e_p.submit(p, gen_max, priority=5)
               for p in twin_prompts[n_slots + 1:]]
    e_p.run()
    twin = {
        "preemptions": e_p.counters["preemptions"] - pre0,
        "identical": all(
            e_f.results[rf] == e_p.results[rp]
            for rf, rp in zip(rids_f, rids_p)),
    }
    print(f"serve_slo_twin_identical,0,{int(twin['identical'])}",
          flush=True)

    # headline: at the top offered load, does the priority policy beat
    # FCFS on high-priority p99 TTFT?
    top = max(rate_mults)
    hi = {r["policy"]: r["ttft"].get("pri5", {}).get("p99")
          for r in runs if r["offered_x"] == top}
    headline = {
        "offered_x": top,
        "fcfs_hi_p99_ttft_s": hi.get("fcfs"),
        "priority_hi_p99_ttft_s": hi.get("priority"),
        "priority_beats_fcfs": (
            hi.get("priority") is not None and hi.get("fcfs") is not None
            and hi["priority"] < hi["fcfs"]),
    }
    print(f"serve_slo_priority_beats_fcfs,0,"
          f"{int(bool(headline['priority_beats_fcfs']))}", flush=True)

    result = {
        "trace": {"arch": "granite-3-2b (reduced)", "n_slots": n_slots,
                  "chunk": chunk, "prompt_len": [p_lo, p_hi],
                  "max_new": [4, gen_max], "duration_s": duration_s,
                  "seed": seed, "hi_pri_frac": hi_pri_frac,
                  "oversize_frac": 0.05,
                  "capacity_req_s": round(capacity_req_s, 3),
                  "rate_mults": list(rate_mults),
                  "policies": list(policies)},
        "runs": runs,
        "token_identity_twin": twin,
        "headline": headline,
    }
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"wrote {out}")
    return result


def scenario_serve_quality(n_requests: int = 8, prompt_min: int = 8,
                           prompt_max: int = 48, gen_min: int = 4,
                           gen_len: int = 16, n_slots: int = 4,
                           chunk: int = 16, shadow_rate: float = 0.25,
                           drift_threshold: float = 0.25,
                           inject_drift: bool = True,
                           inject_layer: int = 1,
                           compute_scale: bool = True,
                           out: str = "BENCH_quality.json") -> dict:
    """Predictor-quality observability (ISSUE 10): shadow-oracle
    scoring + per-layer drift detection, in four phases —

    1. PARITY: the same trace through a shadow-off and a shadow-on
       engine; the shadow pass writes only to the metrics block, so
       generated tokens must be bit-identical.
    2. CLEAN: several passes with shadow scoring on a healthy
       calibrated predictor — the drift detector must stay silent.
    3. INJECTED (``inject_drift``): one layer's calibration
       coefficients are perturbed mid-run
       (``obs.quality.inject_coefficient_drift`` via
       ``Engine.update_mor`` — no recompile) and the detector must
       flag that layer, and ONLY that layer, with a drift event in the
       Perfetto timeline.
    4. OVERHEAD (``compute_scale``): paired A/B at the d256
       compute-dominated point, shadow_rate=1/16 vs 0, interleaved
       timed passes in ONE process (same harness as serve-engine's obs
       A/B — separate-process A/B can't resolve a few percent on a
       shared CPU); acceptance budget < 5% tokens/s."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config, reduce_config
    from repro.core.deploy import calibrate_lm
    from repro.data.pipeline import synthetic_lm_batch
    from repro.launch.serve import _trace
    from repro.models import get_model
    from repro.obs import Observability, validate_chrome_trace
    from repro.obs.quality import inject_coefficient_drift
    from repro.serving import Engine

    cfg = reduce_config(get_config("granite-3-2b"))
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)

    def batches(c, a):
        s = 0
        while True:
            b = synthetic_lm_batch(c, n_slots, 64, seed=0, step=s)
            yield {"tokens": jnp.asarray(b["tokens"])}
            s += 1

    params, mor, _cal = calibrate_lm(params, cfg, api.forward,
                                     batches(cfg, api), 2)
    reqs = _trace(cfg, n_requests, prompt_min, prompt_max, gen_min,
                  gen_len, 0)
    max_len = prompt_max + gen_len + 2
    kw = dict(mor=mor, mor_mode="tiled", n_slots=n_slots,
              max_len=max_len, chunk=chunk, prefix_cache=False)

    def tok(res):
        return {int(r): [int(t) for t in np.asarray(v)]
                for r, v in res.items()}

    # 1) parity: shadow scoring must not touch the primary path's tokens
    eng_off = Engine(cfg, params, **kw)
    res_off = eng_off.run(list(reqs))
    obs = Observability()
    eng = Engine(cfg, params, obs=obs, shadow_rate=shadow_rate,
                 drift_threshold=drift_threshold, **kw)
    res_on = eng.run(list(reqs))
    parity = tok(res_off) == tok(res_on)
    print(f"serve_quality_parity,0,{int(parity)}", flush=True)

    # 2) clean passes: a healthy predictor must not trip the detector
    eng.run(list(reqs))
    rep_clean = eng.report()
    q_clean = rep_clean["quality"]
    clean = {"shadow_dispatches": q_clean["shadow_dispatches"],
             "groups": q_clean["groups"],
             "n_drifted": q_clean["drift"]["n_drifted"],
             "n_series": q_clean["drift"]["n_series"]}
    print(f"serve_quality_clean_drifted,0,{clean['n_drifted']}",
          flush=True)

    # 3) mid-run coefficient injection -> the detector must fire on the
    # perturbed layer only (two passes: the EWMA needs two drifted
    # flushes to cross an absolute threshold — by design, one noisy
    # flush can't flap the flag)
    injected = None
    if inject_drift:
        group = sorted(eng.raw_mor.keys())[0]
        eng.update_mor(inject_coefficient_drift(eng.raw_mor, group,
                                                inject_layer))
        eng.run(list(reqs))
        eng.run(list(reqs))
        rep_inj = eng.report()
        q_inj = rep_inj["quality"]
        # drift events carry the STAT-group name (e.g. "mor_stats"),
        # not the raw-mor group the injection keyed on — compare the
        # (layer, expert) coordinates, which are shared
        drifted = sorted({(e["layer"], e["expert"])
                          for e in q_inj["drift"]["drifted"]})
        trace = obs.tracer.to_chrome_trace()
        n_drift_ev = sum(1 for e in trace["traceEvents"]
                         if str(e.get("name", "")).startswith("drift "))
        injected = {
            "group": group, "layer": inject_layer,
            "shadow_dispatches": q_inj["shadow_dispatches"],
            "groups": q_inj["groups"],
            "drifted": q_inj["drift"]["drifted"],
            "fired_on_injected_only": drifted == [(inject_layer, None)],
            "trace_drift_events": n_drift_ev,
            "trace_problems": validate_chrome_trace(trace),
        }
        print(f"serve_quality_injected_fired,0,"
              f"{int(injected['fired_on_injected_only'])}", flush=True)

    # 4) shadow-overhead A/B at the compute-dominated scale
    overhead = None
    rows = {}
    if compute_scale:
        cfg2 = reduce_config(get_config("granite-3-2b")).replace(
            serve_chunk=32, d_model=256, d_ff=1024, n_layers=4)
        api2 = get_model(cfg2)
        params2 = api2.init(jax.random.PRNGKey(0), cfg2)
        params2, mor2, _ = calibrate_lm(params2, cfg2, api2.forward,
                                        batches(cfg2, api2), 2)
        reqs2 = _trace(cfg2, n_requests, prompt_min, prompt_max,
                       gen_min, gen_len, 0)
        kw2 = dict(mor=mor2, mor_mode="tiled", n_slots=n_slots,
                   max_len=max_len, chunk=32, prefix_cache=False)
        eng0 = Engine(cfg2, params2, obs=Observability(),
                      shadow_rate=0.0, **kw2)
        eng1 = Engine(cfg2, params2, obs=Observability(),
                      shadow_rate=1.0 / 16, **kw2)
        eng0.run(list(reqs2))
        eng1.run(list(reqs2))           # compile warmup, untimed
        walls = {"off": float("inf"), "on": float("inf")}
        for _ in range(5):
            for label, e in (("off", eng0), ("on", eng1)):
                e.reset_counters()
                t0 = time.time()
                e.run(list(reqs2))
                walls[label] = min(walls[label], time.time() - t0)
        rep1 = eng1.report()
        n_tok = rep1["prefill_tokens"] + rep1["decode_tokens"]
        overhead = round(1.0 - walls["off"] / walls["on"], 4)
        rows["tiled@d256-shadow"] = {
            "tokens_per_s": n_tok / walls["on"],
            "paired_off_tokens_per_s": n_tok / walls["off"],
            "shadow_rate": 1.0 / 16,
            "shadow_dispatches":
                rep1["quality"]["shadow_dispatches"],
        }
        print(f"serve_quality_overhead,0,{overhead:.4f}", flush=True)

    result = {"trace": {"n_requests": n_requests,
                        "prompt_min": prompt_min,
                        "prompt_max": prompt_max, "gen_min": gen_min,
                        "gen_len": gen_len, "n_slots": n_slots,
                        "chunk": chunk,
                        "arch": "granite-3-2b (reduced)",
                        "shadow_rate": shadow_rate,
                        "drift_threshold": drift_threshold,
                        "compute_scale": compute_scale},
              "token_parity": parity,
              "clean": clean,
              "injected": injected,
              "modes": rows}
    if overhead is not None:
        result["shadow_overhead"] = overhead
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"wrote {out}")
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenario", default="figures",
                    choices=("figures", "serve-engine", "moe-modes",
                             "serve-prefix", "serve-sharded",
                             "paged-kernel", "serve-slo", "serve-spec",
                             "serve-quality"))
    ap.add_argument("--archs", default=None,
                    help="serve-prefix: comma-separated arch list "
                         "(default granite-3-2b,rwkv6-3b)")
    ap.add_argument("--prefix-len", type=int, default=48)
    ap.add_argument("--modes", default=None,
                    help="default: dense,tiled,kernel (serve-engine) / "
                         "dense,exact,tiled,kernel (moe-modes)")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-max", type=int, default=96)
    ap.add_argument("--gen-len", type=int, default=96)
    ap.add_argument("--no-compute-scale", action="store_true",
                    help="skip the d256 compute-dominated row (CI smoke)")
    ap.add_argument("--slo-duration", type=float, default=4.0,
                    help="serve-slo: seconds of offered load per run")
    ap.add_argument("--slo-rates", default=None,
                    help="serve-slo: comma-separated offered-load "
                         "multiples of capacity (default 0.5,1.0,2.5)")
    ap.add_argument("--policies", default=None,
                    help="serve-slo: comma-separated policy list "
                         "(default fcfs,priority,sjf)")
    ap.add_argument("--spec-ks", default=None,
                    help="serve-spec: comma-separated draft lengths "
                         "(default 2,4)")
    ap.add_argument("--spec-caps", default=None,
                    help="serve-spec: comma-separated draft_cap values "
                         "for the tiled rows (default 0.0,0.5)")
    ap.add_argument("--spec-dims", default="256,1024,4",
                    help="serve-spec: d_model,d_ff,n_layers override "
                         "('none' keeps the plain reduced config — the "
                         "CI smoke size)")
    ap.add_argument("--no-mor-draft", action="store_true",
                    help="serve-spec: skip the calibrated tiled rows "
                         "(CI smoke)")
    ap.add_argument("--no-inject-drift", action="store_true",
                    help="serve-quality: skip the mid-run coefficient "
                         "injection phase")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.scenario == "serve-quality":
        scenario_serve_quality(
            n_requests=args.requests,
            inject_drift=not args.no_inject_drift,
            compute_scale=not args.no_compute_scale,
            out=args.out or "BENCH_quality.json")
        return
    if args.scenario == "serve-spec":
        scenario_serve_spec(
            ks=tuple(int(x) for x in (args.spec_ks or "2,4,8").split(",")),
            caps=tuple(float(x) for x in
                       (args.spec_caps or "0.0,0.5").split(",")),
            dims=args.spec_dims,
            n_requests=args.requests,
            prompt_max=args.prompt_max, gen_len=args.gen_len,
            with_mor=not args.no_mor_draft,
            out=args.out or "BENCH_spec.json")
        return
    if args.scenario == "serve-slo":
        scenario_serve_slo(
            policies=tuple((args.policies
                            or "fcfs,priority,sjf").split(",")),
            rate_mults=tuple(float(x) for x in (
                args.slo_rates or "0.5,1.0,2.5").split(",")),
            duration_s=args.slo_duration,
            out=args.out or "BENCH_slo.json")
        return
    if args.scenario == "moe-modes":
        scenario_moe_modes(modes=tuple((args.modes
                                        or "dense,exact,tiled,kernel"
                                        ).split(",")),
                           n_requests=args.requests,
                           prompt_max=args.prompt_max,
                           gen_len=args.gen_len,
                           out=args.out or "BENCH_moe_modes.json")
        return
    if args.scenario == "paged-kernel":
        scenario_paged_kernel(out=args.out or "BENCH_paged_kernel.json")
        return
    if args.scenario == "serve-sharded":
        scenario_serve_sharded(n_requests=args.requests,
                               prompt_max=args.prompt_max,
                               gen_len=args.gen_len,
                               out=args.out or "BENCH_sharded.json")
        return
    if args.scenario == "serve-prefix":
        scenario_serve_prefix(archs=tuple((args.archs
                                           or "granite-3-2b,rwkv6-3b"
                                           ).split(",")),
                              n_requests=args.requests,
                              prefix_len=args.prefix_len,
                              gen_len=args.gen_len,
                              out=args.out or "BENCH_prefix.json")
        return
    if args.scenario == "serve-engine":
        scenario_serve_engine(modes=tuple((args.modes
                                           or "dense,tiled,kernel"
                                           ).split(",")),
                              n_requests=args.requests,
                              prompt_max=args.prompt_max,
                              gen_len=args.gen_len,
                              compute_scale=not args.no_compute_scale,
                              out=args.out or "BENCH_serve.json")
        return
    from benchmarks import figures
    results = []
    results.append(_run("fig1_negative_relu_input_fraction",
                        figures.fig1_negative_fraction))
    results.append(_run("fig3_relu_mac_fraction",
                        figures.fig3_mac_breakdown))
    results.append(_run("fig5_binary_pearson_mean",
                        figures.fig5_correlation))
    results.append(_run("fig8_closest_angle_mean_deg",
                        figures.fig8_angles))
    results.append(_run("fig6_binary_alone_best_savings",
                        figures.fig6_threshold_binary_alone))
    results.append(_run("fig9_hybrid_best_savings", figures.fig9_hybrid))
    results.append(_run("fig12_mispredicted_zero_rate",
                        figures.fig12_breakdown))
    results.append(_run("fig13_modeled_speedup",
                        figures.fig13_speedup_energy))

    # roofline: aggregate whatever dry-run records exist
    from benchmarks import roofline_table
    recs = roofline_table.load_records()
    if recs:
        s = roofline_table.summary(recs)
        print(f"roofline_cells_ok,{0:.0f},{s['ok']}")
        print(f"roofline_mean_train_fraction,{0:.0f},"
              f"{s['mean_roofline_fraction_train']:.4f}")

    import json
    import os
    os.makedirs("experiments", exist_ok=True)
    with open("experiments/bench_results.json", "w") as f:
        json.dump(results, f, indent=1, default=str)


if __name__ == "__main__":
    main()
