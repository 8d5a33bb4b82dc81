"""Chip smoke run: the serving main path on one TPU, at full width.

    python chip_smoke.py              # one chip: kernel oracles + serving
    python chip_smoke.py --chips 4    # four chips: paged-sharded vs paged

One process, no fallback: it exits non-zero unless JAX's first device
is a TPU.  With no option it runs two phases on one chip:

1. kernels — every Pallas kernel of the serving path once at
   granite-3-2b widths against its ``repro.kernels.ref`` oracle;
2. serving — ``repro.launch.serve.main`` with granite-3-2b at full
   width (random weights from ``--seed``), ``--mor kernel --compare``:
   the MoR kernel engine and its dense twin on one mixed trace, checked
   for finished requests, finite logits, engaged kernels and
   kernel-vs-dense next-token agreement.  That agreement is
   teacher-forced: the dense forward's greedy next token after each
   generated token's own context.  Free-running greedy agreement is
   printed too, but not held to a limit: with random weights the top
   two logits are often within bf16 rounding, and the first flip makes
   the rest of the request a different text.

``--chips 4`` runs only the page-sharded layout
(``Engine(layout="paged-sharded")`` on a 4-device page mesh) against
the single-device paged engine on the same trace, in float32 at full
matmul precision, so identical greedy tokens test the page sharding and
the flash merge rather than bf16 rounding order.  The last line of
standard output is one JSON object naming the devices used.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.metadata
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

MIN_AGREEMENT = 0.9     # kernel-vs-dense teacher-forced next-token agreement
# the four-chip comparison checks page sharding and the flash merge, which
# depth does not change; 2 of granite's 40 layers keep the cold compiles
# of two engines' step variants (float32, full precision) short at four
# times the chip cost
SHARDED_LAYERS = 2
KERNEL_TOL = 2e-2       # kernel vs oracle, relative to the oracle's peak


@dataclasses.dataclass(frozen=True)
class Smoke:
    """The serving trace: slots, requests, log-uniform prompt lengths."""
    arch: str = "granite-3-2b"
    reduced: bool = False
    n_slots: int = 8
    n_requests: int = 16
    prompt_min: int = 64
    prompt_max: int = 512
    gen_len: int = 32
    seed: int = 0
    n_layers: int = 0                  # 0 keeps the published depth

    @property
    def max_len(self) -> int:          # what serve.main sizes the pool to
        return self.prompt_max + self.gen_len + 2

    def config(self):
        from repro.configs import get_config, reduce_config
        cfg = get_config(self.arch)
        cfg = reduce_config(cfg) if self.reduced else cfg
        return cfg.replace(n_layers=self.n_layers) if self.n_layers else cfg


def _log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def _close(name: str, got, want, tol: float = KERNEL_TOL) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert got.shape == want.shape, (name, got.shape, want.shape)
    assert np.all(np.isfinite(got)), f"{name}: non-finite kernel output"
    err = float(np.max(np.abs(got - want)) /
                max(1.0, float(np.max(np.abs(want)))))
    assert err <= tol, f"{name}: relative error {err:.3g} > {tol}"
    return err


# -- phase 1: kernels vs oracles ---------------------------------------------

def kernel_phase(cfg, smoke: Smoke) -> dict:
    """Each serving-path Pallas kernel once at ``cfg``'s widths (rows =
    one chunked-prefill dispatch, slots x chunk) against its oracle."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    from repro.kernels import paged_attention as pk

    dt = jnp.dtype(cfg.dtype)
    K, N = cfg.d_model, cfg.d_ff
    tm, tn = cfg.mor.tile_m, cfg.mor.tile_n
    M = smoke.n_slots * cfg.serve_chunk
    rng = np.random.default_rng(smoke.seed)
    out = {}

    # mor_tile_mask: every row shares one activation sign pattern s, so
    # the sign dot is +K on tiles whose weight columns copy s, -K on
    # tiles that negate it (dead), and mixed on random tiles (live)
    s = np.where(rng.random(K) < 0.5, 1.0, -1.0)
    x = np.abs(rng.standard_normal((M, K))) * s
    x[:, : K // 16] = 0.0                          # zero acts sign as -1
    s[: K // 16] = -1.0
    kind = (np.arange(N // tn) % 3).repeat(tn)     # 0 dead, 1 live, 2 random
    w = np.abs(rng.standard_normal((K, N))) * np.where(
        kind == 0, -s[:, None], s[:, None])
    w[:, kind == 2] = rng.standard_normal((K, int((kind == 2).sum())))
    mor = {"m": jnp.asarray(rng.uniform(0.5, 1.5, N), jnp.float32),
           "b": jnp.asarray(rng.uniform(-K / 4, K / 4, N), jnp.float32),
           "bn_scale": jnp.ones((N,), jnp.float32),
           "bn_bias": jnp.zeros((N,), jnp.float32),
           "enable": jnp.ones((N,), bool)}
    proxy_neg = jnp.ones((M, N), bool)
    xj, wj = jnp.asarray(x, dt), jnp.asarray(w, dt)
    got = ops.mor_tile_mask(xj, wj, mor, proxy_neg, tile_m=tm, tile_n=tn)
    with jax.default_matmul_precision("highest"):
        want = ref.mor_tile_mask_ref(
            xj.astype(jnp.float32), wj.astype(jnp.float32), mor["m"],
            mor["b"], mor["bn_scale"], mor["bn_bias"], mor["enable"],
            proxy_neg, tm, tn)
    got, want = np.asarray(got), np.asarray(want)
    assert np.array_equal(got, want), "mor_tile_mask: mask != oracle"
    assert 0 < want.sum() < want.size, "mor_tile_mask: degenerate mask"
    out["mor_tile_mask_live_frac"] = float(want.mean())

    # gather_matmul / masked_matmul_kdim: half the tiles live
    x = jnp.asarray(rng.standard_normal((M, K)), dt)
    w = jnp.asarray(rng.standard_normal((K, N)) / np.sqrt(K), dt)
    mask = jnp.asarray(rng.random((M // tm, N // tn)) < 0.5)
    n_tiles = mask.size
    got = ops.gather_matmul(x, w, mask, capacity=n_tiles, tile_m=tm,
                            tile_n=tn)
    with jax.default_matmul_precision("highest"):
        want = ref.gather_matmul_ref(x, w, mask, tm, tn, n_tiles)
    out["gather_matmul_err"] = _close("gather_matmul", got, want)

    h = jnp.asarray(rng.standard_normal((M, N)), dt)
    w_down = jnp.asarray(rng.standard_normal((N, K)) / np.sqrt(N), dt)
    kmask = jnp.asarray(rng.random((M // tm, N // 128)) < 0.5)
    # the kernel's contract: dead h tiles are exact zeros (gather_matmul
    # makes them so in the FFN); it skips only blocks with no live tile
    h = jnp.where(jnp.repeat(jnp.repeat(kmask, tm, 0), 128, 1), h, 0)
    got = ops.masked_matmul_kdim(h, w_down, kmask, tile_m=tm)
    with jax.default_matmul_precision("highest"):
        want = ref.masked_matmul_kdim_ref(h, w_down, kmask, tm, 128)
    out["masked_matmul_kdim_err"] = _close("masked_matmul_kdim", got, want)

    # gqa_paged_flash at decode (C = 1) and chunked-prefill widths
    hkv, H, D = cfg.n_kv_heads, cfg.n_heads, cfg.head_dim
    page = cfg.serve_page
    B = smoke.n_slots
    n_blocks = -(-smoke.max_len // page)
    n_pages = B * n_blocks + 1
    ring = n_blocks * page
    kp = jnp.asarray(rng.standard_normal((n_pages, page, hkv, D)), dt)
    vp = jnp.asarray(rng.standard_normal((n_pages, page, hkv, D)), dt)
    tags = rng.integers(-1, ring, (n_pages, page)).astype(np.int32)
    tags[0] = -1                                   # the null page
    tbl = rng.integers(0, n_pages, (B, n_blocks)).astype(np.int32)
    tbl[-1] = 0                                    # one fully-masked slot
    pp, tbl = jnp.asarray(tags), jnp.asarray(tbl)
    for C in (1, cfg.serve_chunk):
        q = jnp.asarray(rng.standard_normal((B, C, H, D)), dt)
        qpos = jnp.asarray(rng.integers(ring // 2, ring - C, (B, 1))
                           + np.arange(C)[None], jnp.int32)
        got = pk.gqa_paged_flash(q, kp, vp, pp, tbl, qpos)
        with jax.default_matmul_precision("highest"):
            want = ref.gqa_paged_ref(q, kp, vp, pp, tbl, qpos)
        out[f"gqa_paged_flash_c{C}_err"] = _close(
            f"gqa_paged_flash C={C}", got, want)
    return out


# -- phase 2: serving through serve.main --------------------------------------

def reference_next_tokens(cfg, seed: int, seqs, batch: int = 8):
    """The plain teacher-forced forward (``api.forward``, dense FFN, the
    seeded weights) over each token sequence -> its greedy next token at
    every position.  Sequences are right-padded to one length, so the
    forward compiles once; a causal model never reads the padding."""
    import jax
    import jax.numpy as jnp
    from repro.models import get_model

    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(seed), cfg)
    T = max(len(s) for s in seqs)
    rows = -(-len(seqs) // batch) * batch
    toks = np.zeros((rows, T), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s

    @jax.jit
    def greedy(p, t):
        logits = api.forward(p, cfg, {"tokens": t})[0]
        return logits.argmax(-1), jnp.isfinite(logits).all()

    out = []
    for i in range(0, rows, batch):
        nxt, finite = greedy(params, jnp.asarray(toks[i:i + batch]))
        assert bool(finite), "non-finite reference logits"
        out.append(np.asarray(nxt))
    nxt = np.concatenate(out)
    return [nxt[i, :len(s)] for i, s in enumerate(seqs)]


def _next_token_agreement(prompts, gens, ref_next) -> float:
    """Share of generated tokens equal to the reference's greedy next
    token for the same context (teacher-forced: one early difference
    does not carry into the rest of the request)."""
    hits = [np.asarray(g) == r[len(p) - 1:len(p) - 1 + len(g)]
            for p, g, r in zip(prompts, gens, ref_next)]
    return float(np.mean(np.concatenate(hits)))


def serve_phase(smoke: Smoke) -> dict:
    """``serve.main`` with ``--mor kernel --compare`` on the smoke trace,
    then the teacher-forced dense forward over every generated sequence
    of both engines as the logits and next-token check."""
    from repro.kernels import paged_attention as pk
    from repro.launch import serve

    argv = ["--arch", smoke.arch, "--mor", "kernel", "--compare",
            "--batch", str(smoke.n_slots),
            "--requests", str(smoke.n_requests),
            "--prompt-min", str(smoke.prompt_min),
            "--prompt-max", str(smoke.prompt_max),
            "--gen-len", str(smoke.gen_len), "--seed", str(smoke.seed)]
    if smoke.reduced:
        argv.append("--reduced")
    with pk.trace_scope() as traces:
        rep = serve.main(argv)
    cfg = smoke.config()
    assert traces["gqa"] > 0, f"paged attention kernel never ran: {traces}"
    assert rep["requests_finished"] == smoke.n_requests, rep
    toks = rep["request_tokens"]
    assert [len(t) for t in toks] == [smoke.gen_len] * smoke.n_requests, \
        [len(t) for t in toks]
    flat = np.concatenate(toks)
    assert flat.min() >= 0 and flat.max() < cfg.vocab_size
    for key in ("per_layer_frac_tiles_live", "per_layer_frac_tiles_computed"):
        assert len(rep.get(key, ())) == cfg.n_layers, \
            f"MoR tile counter {key} missing"

    # the reference: the dense forward on the same seeded weights
    # (calibration only permutes FFN neurons), teacher-forced over the
    # sequences of the kernel engine and of its dense twin
    prompts = [p for p, _ in serve._trace(
        cfg, smoke.n_requests, smoke.prompt_min, smoke.prompt_max,
        smoke.gen_len, smoke.gen_len, smoke.seed)]
    dense_toks = rep["dense_request_tokens"]
    ref = reference_next_tokens(
        cfg, smoke.seed, [np.concatenate([p, g]) for p, g in
                          zip(prompts + prompts, toks + dense_toks)])
    n = len(prompts)
    return {"requests_finished": rep["requests_finished"],
            "next_token_agreement_vs_dense": _next_token_agreement(
                prompts, toks, ref[:n]),
            "dense_engine_next_token_agreement": _next_token_agreement(
                prompts, dense_toks, ref[n:]),
            "free_running_token_agreement": rep["token_agreement_vs_dense"],
            "mor_frac_tiles_computed": float(np.mean(
                rep["per_layer_frac_tiles_computed"])),
            "calibration_s": rep.get("calibration_s"),
            "warmup_wall_s": rep["warmup_wall_s"],
            "timed_pass_wall_s": rep["wall_s"],
            "kernel_traces": dict(traces)}


# -- four chips: paged-sharded vs single-device paged -------------------------

def sharded_phase(smoke: Smoke, n_shards: int = 4) -> dict:
    """The page-sharded engine over ``n_shards`` devices against the
    single-device paged engine, greedy, on the same trace, in float32
    at full matmul precision (the two differ only in summation order)."""
    import jax
    from repro.kernels import paged_attention as pk
    from repro.launch import serve
    from repro.launch.mesh import make_page_mesh
    from repro.models import get_model
    from repro.serving import Engine

    assert len(jax.devices()) == n_shards, jax.devices()
    cfg = smoke.config().replace(dtype="float32", param_dtype="float32")
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(smoke.seed), cfg)
    reqs = serve._trace(cfg, smoke.n_requests, smoke.prompt_min,
                        smoke.prompt_max, smoke.gen_len, smoke.gen_len,
                        smoke.seed)
    kw = dict(n_slots=smoke.n_slots, max_len=smoke.max_len)
    with jax.default_matmul_precision("highest"):
        t0 = time.time()
        res_p = Engine(cfg, params, layout="paged", **kw).run(list(reqs))
        t_paged = time.time() - t0
        t0 = time.time()
        with pk.trace_scope() as traces:
            eng = Engine(cfg, params, layout="paged-sharded",
                         mesh=make_page_mesh(n_shards), **kw)
            res_m = eng.run(list(reqs))
        t_sharded = time.time() - t0
    agree = float(np.mean([np.mean(np.asarray(res_m[r]) ==
                                   np.asarray(res_p[r])) for r in res_p]))
    _log(f"sharded vs paged token agreement {agree:.4f}")
    assert res_m == res_p, "paged-sharded tokens diverge from paged"
    assert traces["gqa"] > 0, f"paged kernel never ran: {traces}"
    hw = eng.pool.shard_report()["kv_pages_hiwater_per_shard"]
    assert len(hw) == n_shards and min(hw) > 0, f"idle shard: {hw}"
    for leaf in jax.tree_util.tree_leaves((eng.params, eng.cache)):
        assert len(leaf.sharding.device_set) == n_shards, leaf.sharding
    return {"token_agreement": agree, "kv_pages_hiwater_per_shard": hw,
            "paged_wall_s": t_paged, "sharded_wall_s": t_sharded,
            "kernel_traces": dict(traces)}


# -- entry point --------------------------------------------------------------

def _compile_clock():
    """Seconds and count of XLA backend compilations, summed."""
    import jax
    spent = {"s": 0.0, "n": 0}

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            spent["s"] += duration
            spent["n"] += 1
    jax.monitoring.register_event_duration_secs_listener(listen)
    return spent


def _timed(name: str, fn, compile_clock) -> dict:
    c0, n0, t0 = compile_clock["s"], compile_clock["n"], time.time()
    res = fn()
    res = dict(res, wall_s=time.time() - t0,
               xla_compile_s=compile_clock["s"] - c0,
               xla_compiles=compile_clock["n"] - n0)
    _log(f"phase {name}: {json.dumps(res, default=float)}")
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    import jaxlib
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"[chip_smoke] needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    from repro.kernels import ops
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    _log(f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, libtpu "
         f"{importlib.metadata.version('libtpu')}")
    _log(f"device {dev.device_kind} x{len(jax.devices())}; compile cache "
         f"{cache_dir}")
    assert not ops._interpret(), "Pallas interpret mode on a TPU"
    smoke = Smoke(seed=args.seed)
    clock = _compile_clock()
    if args.chips == 4:
        smoke = dataclasses.replace(smoke, n_layers=SHARDED_LAYERS)
        _timed("sharded", lambda: sharded_phase(smoke), clock)
    else:
        _timed("kernels", lambda: kernel_phase(smoke.config(), smoke), clock)
        res = _timed("serving", lambda: serve_phase(smoke), clock)
        agree = res["next_token_agreement_vs_dense"]
        assert agree >= MIN_AGREEMENT, \
            f"kernel-vs-dense next-token agreement {agree:.3f} < " \
            f"{MIN_AGREEMENT}"
    stats = dev.memory_stats() or {}
    _log(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
