"""Serving driver: the continuous-batching MoR engine under a mixed
prompt-length trace — the paper's deployment scenario (inference
accelerator serving real traffic).

  PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b \
      --reduced --requests 16 --prompt-min 16 --prompt-max 512 \
      --gen-len 32 --mor tiled --calibrate-capacity 0.95 --compare

Requests with heterogeneous prompt/generation lengths stream through a
fixed slot pool (``repro.serving.Engine``): prompts are prefilled in
fixed-size chunks mixed into the same dispatches as ongoing decodes,
finished sequences are evicted and their KV pages recycled mid-flight.
The cache is PAGED by default (block-table indirection + refcounted
pages) with prefix caching across requests: a --shared-prefix trace
dedups its common prompt pages and skips the fully-hit prefill chunks
(--no-prefix-cache / --layout slotted select the baselines).  Sampling
is greedy by default; --temperature/--top-k enable seeded sampling.
Reports tokens/s, the realised PER-LAYER skip fractions from the
serving telemetry, the prefix-cache hit counters, and (with
--calibrate-capacity) the per-layer gather_matmul capacities chosen
from the observed tile-liveness quantiles.  --baseline additionally
measures the static-batch path (every prompt padded to the trace
maximum) on the same trace.

--obs / --metrics-json / --trace-out attach the ``repro.obs`` stack to
the primary engine: a metrics registry (JSON/Prometheus export), the
device-resident dispatch counters (accumulated inside the compiled
step, drained only at flush boundaries — zero extra device syncs), and
the span tracer whose timeline loads in Perfetto / chrome://tracing.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, reduce_config
from repro.data.pipeline import synthetic_lm_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import make_decode_step, make_prefill_step
from repro.models import get_model
from repro.serving import Engine
from repro.serving.telemetry import STAT_KEYS


def generate(cfg, api, params, prompts, gen_len: int, mor=None,
             mor_mode: str = "dense"):
    """Static-batch generate (the pre-engine serving path, kept as the
    baseline): prompts (B, P) -> (tokens (B, gen_len), stats).

    Prefill is one batched dispatch (or chunked-prefill dispatches for
    recurrent families / prompts beyond the sliding-window ring); decode
    is a width-1 chunk step per token.  stats carries throughput AND the
    realised per-layer skip fractions accumulated over decode steps."""
    from repro.serving import kv_pool
    B, P = prompts.shape
    max_len = P + gen_len + 1
    cache = kv_pool.init(cfg, B, max_len)
    prefill = make_prefill_step(cfg, mor=mor, mor_mode=mor_mode)
    step = jax.jit(make_decode_step(cfg, mor=mor, mor_mode=mor_mode),
                   donate_argnums=(1,))

    t0 = time.time()
    nxt, cache = prefill(params, cache, prompts)
    jax.block_until_ready(nxt)
    prefill_dt = time.time() - t0

    tok = nxt[:, None]
    out = []
    layer_stats = []
    # the first decode step JIT-compiles the (B, 1) step; keep it outside
    # the timed window so tok/s reports steady-state throughput
    nxt, cache, aux = step(params, cache, tok)
    tok = nxt[:, None]
    out.append(nxt)
    jax.block_until_ready(tok)
    timed = max(gen_len - 1, 1)
    t0 = time.time()
    for t in range(gen_len - 1):
        nxt, cache, aux = step(params, cache, tok)
        tok = nxt[:, None]
        out.append(nxt)
        if aux:
            layer_stats.append(aux)
    jax.block_until_ready(tok)
    dt = max(time.time() - t0, 1e-9)
    toks = np.stack([np.asarray(o) for o in out], 1)
    stats = {"decode_tokens_per_s": B * timed / dt,
             "decode_ms_per_step": dt / timed * 1e3,
             "prefill_tokens_per_s": B * P / max(prefill_dt, 1e-9),
             "prefill_ms": prefill_dt * 1e3}
    stats.update(_mean_layer_stats(layer_stats))
    return toks, stats


# report-key prefix per stat group: the dense-stack stats keep the
# historical per_layer_* names; expert stats ((L, E)-shaped) get their
# own namespace so groups never overwrite each other in the report
_STAT_PREFIX = {"mor_stats": "per_layer_",
                "dense_mor_stats": "per_layer_dense_",
                "moe_mor_stats": "per_expert_"}


def _mean_layer_stats(aux_list):
    """Average per-layer MoR skip stats over dispatches -> report lists
    (nested (L, E) lists for the expert group)."""
    out = {}
    for key in STAT_KEYS:
        rows = [a[key] for a in aux_list if a.get(key)]
        if not rows:
            continue
        for name in ("frac_computed", "frac_tiles_live",
                     "frac_tiles_computed", "frac_blocks_issued"):
            vals = [np.asarray(r[name], np.float64) for r in rows
                    if name in r]
            if vals:
                out[_STAT_PREFIX[key] + name] = \
                    np.mean(vals, 0).round(4).tolist()
    return out


def _trace(cfg, n_requests, pmin, pmax, gmin, gmax, seed,
           shared_prefix: int = 0):
    """Mixed trace: log-uniform prompt lengths in [pmin, pmax] AND
    generation lengths in [gmin, gmax] — heterogeneous on both axes,
    like real traffic (the static batch convoys on the longest of
    each per group; the engine evicts at each request's own length).

    ``shared_prefix`` > 0 prepends the SAME ``shared_prefix``-token
    prompt prefix to every request (system-prompt traffic) — the
    shared-prompt trace the prefix cache dedups."""
    rng = np.random.default_rng(seed)
    prefix = np.zeros((0,), np.int32)
    if shared_prefix:
        prefix = np.asarray(
            synthetic_lm_batch(cfg, 1, shared_prefix, seed=seed, step=999)
            ["tokens"][0], np.int32)
    reqs = []
    for i in range(n_requests):
        plen = (int(np.exp(rng.uniform(np.log(pmin), np.log(pmax))))
                if pmax > pmin else pmin)
        glen = (int(np.exp(rng.uniform(np.log(gmin), np.log(gmax))))
                if gmax > gmin else gmax)
        prompt = np.asarray(
            synthetic_lm_batch(cfg, 1, plen, seed=seed, step=1000 + i)
            ["tokens"][0], np.int32)
        reqs.append((np.concatenate([prefix, prompt]), glen))
    return reqs


def _run_engine(cfg, params, reqs, *, mor, mor_mode, n_slots, max_len,
                chunk=0, capacities=None, layout="paged",
                prefix_cache=True, temperature=0.0, top_k=0,
                sample_seed=0, mesh=None, obs=None, policy=None,
                spec_k=0, draft_cap=0.0, spec_draft_temperature=None,
                shadow_rate=0.0, drift_threshold=0.25):
    eng = Engine(cfg, params, mor=mor, mor_mode=mor_mode, n_slots=n_slots,
                 max_len=max_len, chunk=chunk, capacities=capacities,
                 layout=layout, prefix_cache=prefix_cache,
                 temperature=temperature, top_k=top_k,
                 sample_seed=sample_seed, mesh=mesh, obs=obs,
                 policy=policy, spec_k=spec_k, draft_cap=draft_cap,
                 spec_draft_temperature=spec_draft_temperature,
                 shadow_rate=shadow_rate, drift_threshold=drift_threshold)
    # first pass compiles the two dispatch shapes; then take the best of
    # three timed passes — single-shot wall clock on a shared CPU is
    # ~2x noisy (the static baseline gets the same warmup + best-of).
    # eng.run() ends with a blocking flush, so these walls include the
    # device drain (unlike counters["wall_s"], which is host dispatch
    # time only — the hot loop never syncs).
    t0 = time.time()
    eng.run(list(reqs))
    warmup = time.time() - t0
    wall = float("inf")
    for _ in range(3):
        eng.reset_counters()
        t0 = time.time()
        results = eng.run(list(reqs))   # deterministic: passes agree
        wall = min(wall, max(time.time() - t0, 1e-9))
    base = min(results)
    results = {rid - base: toks for rid, toks in results.items()}
    rep = eng.report()
    rep["requests_finished"] = len(results)      # the timed pass only
    # generated tokens per request, in trace order (a finished request
    # holds exactly its generation length)
    rep["request_tokens"] = [list(map(int, results[r]))
                             for r in sorted(results)]
    rep["warmup_wall_s"] = warmup                # compiles + one pass
    total = rep["prefill_tokens"] + rep["decode_tokens"]
    rep["tokens_per_s"] = total / wall
    rep["decode_tokens_per_s"] = rep["decode_tokens"] / wall
    rep["wall_s"] = wall
    tel = rep.pop("telemetry", None)
    if tel:
        for key in STAT_KEYS:
            if key in tel:
                for name, vals in tel[key].items():
                    if name in ("frac_computed", "frac_tiles_live",
                                "frac_tiles_computed",
                                "frac_blocks_issued"):
                        rep[_STAT_PREFIX[key] + name] = \
                            np.round(np.asarray(vals), 4).tolist()
    return eng, results, rep


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--dims", default=None,
                    help="override reduced dims: d_model,d_ff,n_layers "
                         "(bench knob for compute-dominated scales)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--batch", type=int, default=8,
                    help="slot-pool size (n_slots)")
    ap.add_argument("--requests", type=int, default=0,
                    help="trace length (default: one per slot)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--prompt-min", type=int, default=0,
                    help="mixed trace: min prompt length (default uniform)")
    ap.add_argument("--prompt-max", type=int, default=0)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--gen-min", type=int, default=0,
                    help="mixed trace: min generation length "
                         "(default uniform = gen-len)")
    ap.add_argument("--chunk", type=int, default=0,
                    help="prefill chunk length (default cfg.serve_chunk)")
    ap.add_argument("--layout", default="paged",
                    choices=("paged", "paged-sharded", "slotted"),
                    help="KV cache layout (paged-sharded = page pool "
                         "partitioned over a device mesh, distributed "
                         "flash decode; slotted = PR 2 baseline)")
    ap.add_argument("--shards", type=int, default=0,
                    help="paged-sharded: mesh size over the page axis "
                         "(default: all visible devices; force host "
                         "devices via XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--stream", action="store_true",
                    help="demo the detokenizing stream API: re-serve "
                         "the first request through Engine.stream() "
                         "and report the incrementally streamed tokens")
    ap.add_argument("--prefix-cache", dest="prefix_cache",
                    action="store_true", default=True,
                    help="prefix caching across requests (default on; "
                         "paged layout only)")
    ap.add_argument("--no-prefix-cache", dest="prefix_cache",
                    action="store_false")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend a shared N-token prefix to every "
                         "request (shared-prompt trace)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy argmax)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k truncation for temperature sampling "
                         "(0 = full distribution)")
    ap.add_argument("--sample-seed", type=int, default=0)
    ap.add_argument("--spec-k", type=int, default=0,
                    help="self-speculative decoding: draft up to k "
                         "tokens per slot per round and verify them in "
                         "one target pass (0 = off; paged layout only; "
                         "greedy output is token-identical to vanilla)")
    ap.add_argument("--draft-cap", type=float, default=0.0,
                    help="MoR capacity fraction for the DRAFT pass "
                         "(traced leaf — sweeping it never recompiles; "
                         "0 = draft at full target capacity)")
    ap.add_argument("--spec-draft-temperature", type=float, default=None,
                    help="draft-pass sampling temperature (default: "
                         "the target --temperature)")
    ap.add_argument("--mor", default="dense",
                    choices=("dense", "exact", "tiled", "kernel"))
    ap.add_argument("--calib-steps", type=int, default=4)
    ap.add_argument("--capacity", type=float, default=0.0,
                    help="static gather_matmul capacity fraction applied "
                         "to every MoR layer (0 = cfg.mor.capacity; the "
                         "clamp drops live tiles beyond the budget, so "
                         "tile-skip counters are nonzero even on "
                         "uncalibrated weights)")
    ap.add_argument("--obs", action="store_true",
                    help="enable the repro.obs stack (metrics registry, "
                         "device-resident dispatch counters, request "
                         "tracer) on the primary engine; implied by "
                         "--metrics-json / --trace-out")
    ap.add_argument("--metrics-json", default=None,
                    help="write the obs metrics-registry snapshot "
                         "(counters, gauges, histogram summaries) to "
                         "this path as JSON")
    ap.add_argument("--trace-out", default=None,
                    help="write the request tracer's timeline to this "
                         "path as Chrome-trace JSON (load in Perfetto "
                         "or chrome://tracing)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve GET /metrics (Prometheus text) and "
                         "GET /metrics.json (registry snapshot) on "
                         "this port from a stdlib http.server thread "
                         "for the run's duration (implies --obs; 0 = "
                         "ephemeral port, printed at startup)")
    ap.add_argument("--shadow-rate", type=float, default=0.0,
                    help="shadow-oracle predictor scoring: sample "
                         "1-in-round(1/RATE) dispatches through a "
                         "dense-oracle twin that scores the predictor's "
                         "tile decisions (false skips / false keeps) "
                         "into the device metrics block; tokens stay "
                         "identical to --shadow-rate 0 (implies --obs; "
                         "needs --mor != dense)")
    ap.add_argument("--drift-threshold", type=float, default=0.25,
                    help="per-(layer, expert) EWMA false-skip-rate "
                         "threshold above which the drift detector "
                         "flags the series")
    ap.add_argument("--calibrate-capacity", type=float, default=0.0,
                    help="liveness quantile for per-layer gather capacity "
                         "(0 = static cfg.mor.capacity)")
    ap.add_argument("--compare", action="store_true",
                    help="also run the dense engine; report token agreement")
    ap.add_argument("--baseline", action="store_true",
                    help="also run the static-batch path on the same trace")
    ap.add_argument("--policy", default="fcfs",
                    choices=("fcfs", "priority", "sjf"),
                    help="admission/preemption policy (priority can "
                         "spill lower classes; sjf = shortest remaining "
                         "prefill first)")
    ap.add_argument("--prefill-budget", type=int, default=0,
                    help="cap on prompt tokens per mixed dispatch "
                         "(decode-vs-prefill knob; 0 = unlimited)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out-json", default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg)
    if args.dims:
        d, ff, L = (int(v) for v in args.dims.split(","))
        cfg = cfg.replace(d_model=d, d_ff=ff, n_layers=L)
    api = get_model(cfg)
    assert api.has_decode, f"{cfg.name} is encoder-only"
    key = jax.random.PRNGKey(args.seed)
    params = api.init(key, cfg)
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir)
        state, _ = mgr.restore({"params": params})
        params = state["params"]

    mor = None
    report = {"arch": cfg.name, "mor_mode": args.mor}
    t_cal = time.time()
    if args.mor != "dense":
        from repro.core.deploy import (calibrate_hybrid, calibrate_lm,
                                       calibrate_moe)

        def batches():
            s = 0
            while True:
                b = synthetic_lm_batch(cfg, args.batch, 128,
                                       seed=args.seed, step=s)
                yield {"tokens": jnp.asarray(b["tokens"])}
                s += 1
        if cfg.family == "moe":
            # per-(layer, expert) predictors for the expert FFNs plus the
            # calibrate_lm treatment for any leading dense layers
            params, mor, cal = calibrate_moe(params, cfg, api.forward,
                                             batches(), args.calib_steps)
        elif cfg.family == "hybrid":
            # the one shared block's MLP, observed at every segment
            # boundary, gets a single MoRLayer under mor["shared"]
            params, mor, cal = calibrate_hybrid(params, cfg, api.forward,
                                                batches(), args.calib_steps)
        else:
            params, mor, cal = calibrate_lm(params, cfg, api.forward,
                                            batches(), args.calib_steps)
        report["calibration"] = cal
        report["calibration_s"] = time.time() - t_cal

    pmin = args.prompt_min or args.prompt_len
    pmax = args.prompt_max or args.prompt_len
    gmin = args.gen_min or args.gen_len
    reqs = _trace(cfg, args.requests or args.batch, pmin, pmax,
                  gmin, args.gen_len, args.seed,
                  shared_prefix=args.shared_prefix)
    max_len = args.shared_prefix + pmax + args.gen_len + 2

    mesh = None
    if args.layout == "paged-sharded":
        from repro.launch.mesh import make_page_mesh
        mesh = make_page_mesh(args.shards)

    if args.shadow_rate > 0:
        assert args.mor != "dense", \
            "--shadow-rate scores the MoR predictor; pick --mor " \
            "exact/tiled/kernel"
    obs = None
    if args.obs or args.metrics_json or args.trace_out or \
            args.shadow_rate > 0 or args.metrics_port is not None:
        from repro.obs import Observability
        obs = Observability()
    server = None
    if args.metrics_port is not None:
        from repro.obs import MetricsServer
        server = MetricsServer(obs, port=args.metrics_port)
        print(f"[serve] metrics endpoint: {server.url}/metrics "
              f"(+ /metrics.json)")

    capacities = None
    if args.capacity > 0 and args.mor != "dense":
        from repro.serving.telemetry import mor_group_map
        capacities = {k: args.capacity for k in mor_group_map(cfg)}
        report["static_capacity"] = args.capacity

    from repro.serving.policy import get_policy
    policy = get_policy(args.policy, prefill_budget=args.prefill_budget)
    eng, results, rep = _run_engine(
        cfg, params, reqs, mor=mor, mor_mode=args.mor, n_slots=args.batch,
        max_len=max_len, chunk=args.chunk, capacities=capacities,
        layout=args.layout, prefix_cache=args.prefix_cache,
        temperature=args.temperature, top_k=args.top_k,
        sample_seed=args.sample_seed, mesh=mesh, obs=obs, policy=policy,
        spec_k=args.spec_k, draft_cap=args.draft_cap,
        spec_draft_temperature=args.spec_draft_temperature,
        shadow_rate=args.shadow_rate,
        drift_threshold=args.drift_threshold)
    report.update(rep)
    report["policy"] = args.policy
    if args.prefill_budget:
        report["prefill_budget"] = args.prefill_budget
    print(f"[serve] {cfg.name} mor={args.mor} layout={args.layout}: "
          f"{rep['tokens_per_s']:.1f} tok/s over {len(reqs)} requests "
          f"({rep['dispatches']} dispatches, "
          f"prompts {pmin}-{pmax})")
    if "quality" in rep:
        q = rep["quality"]
        dr = q.get("drift", {})
        print(f"[serve] shadow oracle: rate {q['shadow_rate']:.4f} "
              f"(1 in {q['shadow_every']}), "
              f"{q.get('shadow_dispatches', 0)} dispatches scored, "
              f"{dr.get('n_drifted', 0)}/{dr.get('n_series', 0)} "
              f"series drifted")
    if "spec" in rep:
        sp = rep["spec"]
        print(f"[serve] spec: k={sp['k']} draft_cap={sp['draft_cap']} "
              f"acceptance {sp['acceptance_rate']:.2f} "
              f"({sp['tokens_accepted']}/{sp['tokens_drafted']} drafts "
              f"over {sp['rounds']} rounds, {sp['replays']} replays, "
              f"{sp['aborts']} aborts)")
    if "sharding" in rep:
        sh = rep["sharding"]
        print(f"[serve] page mesh: {sh['n_shards']} shards, kv pages "
              f"hiwater/shard "
              f"{sh.get('kv_pages_hiwater_per_shard', sh.get('state_pages_hiwater_per_shard'))}")

    if args.stream:
        # detokenizing stream demo: re-serve request 0 through the
        # iterator API (tokens arrive at flush granularity — the hot
        # loop stays device-resident, no per-token syncs)
        p0, g0 = reqs[0]
        streamed = list(eng.stream(p0, g0, interval=1))
        report["stream"] = {"tokens": len(streamed), "interval": 1}
        print(f"[serve] --stream: request 0 re-served, {len(streamed)} "
              f"tokens streamed incrementally")
    if "prefix_cache" in rep:
        pc = rep["prefix_cache"]
        print(f"[serve] prefix cache: hit rate {pc['hit_rate']:.2f} "
              f"({pc['prefix_hits']}/{pc['prefix_queries']} requests), "
              f"{pc['pages_shared']} pages shared, "
              f"{pc['chunks_skipped']} prefill chunks skipped, "
              f"{pc['pages_cowed']} pages copy-on-written")

    if args.calibrate_capacity > 0 and args.mor not in ("dense",):
        caps = eng.calibrate_capacities(quantile=args.calibrate_capacity)
        _, results_cal, rep_cal = _run_engine(
            cfg, params, reqs, mor=mor, mor_mode=args.mor,
            n_slots=args.batch, max_len=max_len, chunk=args.chunk,
            capacities=caps, layout=args.layout,
            prefix_cache=args.prefix_cache, mesh=mesh)
        report["per_layer_capacity"] = {
            k: np.asarray(v).round(4).tolist() for k, v in caps.items()}
        report["calibrated_tokens_per_s"] = rep_cal["tokens_per_s"]
        # token_agreement_vs_dense below measures the UNCALIBRATED run;
        # the capacity clamp intentionally drops live tiles beyond the
        # chosen quantile, so its accuracy cost is reported separately:
        report["calibrated_token_agreement"] = float(np.mean([
            np.mean(np.asarray(results_cal[r]) == np.asarray(results[r]))
            for r in results]))
        print(f"[serve] capacity-calibrated "
              f"(q={args.calibrate_capacity}): "
              f"{rep_cal['tokens_per_s']:.1f} tok/s; per-layer capacity "
              f"{report['per_layer_capacity']}")

    if args.compare and args.mor != "dense":
        _, results_d, rep_d = _run_engine(cfg, params, reqs, mor=None,
                                          mor_mode="dense",
                                          n_slots=args.batch,
                                          max_len=max_len, chunk=args.chunk,
                                          layout=args.layout,
                                          prefix_cache=args.prefix_cache,
                                          mesh=mesh)
        agree = np.mean([
            np.mean(np.asarray(results[r]) == np.asarray(results_d[r]))
            for r in results_d])
        report["dense_tokens_per_s"] = rep_d["tokens_per_s"]
        report["dense_request_tokens"] = rep_d["request_tokens"]
        report["token_agreement_vs_dense"] = float(agree)
        print(f"[serve] dense baseline: {rep_d['tokens_per_s']:.1f} tok/s; "
              f"token agreement {agree:.3f}")

    if args.baseline:
        # static batch: every prompt padded to the TRACE maximum, groups
        # of n_slots at a time — what serve.py did before the engine.
        # The steps compile ONCE (fixed (B, Pmax) shapes) and a warmup
        # group runs outside the timer, so the speedup measures padding/
        # convoy waste, not compile time.
        from repro.serving import kv_pool
        Pmax = max(len(p) for p, _ in reqs)
        prefill = make_prefill_step(cfg, mor=mor, mor_mode=args.mor)
        step = jax.jit(make_decode_step(cfg, mor=mor, mor_mode=args.mor),
                       donate_argnums=(1,))

        def run_group(group):
            prompts = np.zeros((args.batch, Pmax), np.int32)
            for j, (p, _) in enumerate(group):
                prompts[j, Pmax - len(p):] = p   # left-pad to trace max
            cache = kv_pool.init(cfg, args.batch, Pmax + args.gen_len + 1)
            nxt, cache = prefill(params, cache, jnp.asarray(prompts))
            tok = nxt[:, None]
            # the convoy effect: every slot rides until the group's
            # longest generation finishes
            for _ in range(max(g for _, g in group)):
                nxt, cache, _ = step(params, cache, tok)
                tok = nxt[:, None]
            jax.block_until_ready(tok)

        groups = [reqs[i:i + args.batch]
                  for i in range(0, len(reqs), args.batch)]
        run_group(groups[0])                     # compile warmup, untimed
        wall = float("inf")
        for _ in range(3):                       # best-of-3, like the engine
            t0 = time.time()
            for group in groups:
                run_group(group)
            wall = min(wall, max(time.time() - t0, 1e-9))
        n_tok = sum(len(p) + g for p, g in reqs)
        report["static_batch_tokens_per_s"] = n_tok / wall
        report["engine_speedup_vs_static"] = \
            report["tokens_per_s"] / (n_tok / wall)
        print(f"[serve] static-batch baseline: {n_tok / wall:.1f} tok/s "
              f"(engine speedup "
              f"{report['engine_speedup_vs_static']:.2f}x)")

    if obs is not None:
        # files are written LAST so --stream / calibration re-runs on the
        # same engine land in the exported snapshot too
        if args.metrics_json:
            obs.write_metrics_json(args.metrics_json)
        if args.trace_out and obs.tracer is not None:
            obs.write_trace(args.trace_out)
        tr = obs.tracer.summary() if obs.tracer is not None else {}
        ttft = (tr.get("ttft") or {}).get("p50")
        itl = (tr.get("itl") or {}).get("p50")
        print(f"[serve] obs: {len(obs.registry.snapshot())} metric "
              f"families"
              + (f", ttft p50 {ttft * 1e3:.1f} ms" if ttft else "")
              + (f", itl p50 {itl * 1e3:.2f} ms" if itl else "")
              + (f"; metrics -> {args.metrics_json}"
                 if args.metrics_json else "")
              + (f"; trace -> {args.trace_out}" if args.trace_out else ""))
    if server is not None:
        # written files above already captured the final flush; shut
        # the scrape thread down cleanly with the run
        server.close()

    if args.out_json:
        with open(args.out_json, "w") as f:
            json.dump(report, f, indent=1)
    return report


if __name__ == "__main__":
    main()
