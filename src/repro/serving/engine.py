"""The continuous-batching serving engine.

One compiled chunk step per dispatch shape ((B, chunk) mixed and (B, 1)
decode-only) drives the whole request stream: the scheduler packs each
dispatch, the paged kv pool allocates/copy-on-writes the pages the
dispatch will touch (host-side, count-based — no device sync), the
telemetry accumulates per-layer tile-liveness from every dispatch's MoR
stats, and ``calibrate_capacities`` turns that into per-layer
gather_matmul capacity fractions (attached to the execution plans as a
traced leaf — updating them does NOT recompile the step).

Cache layouts: ``layout="paged"`` (default) runs on
``kv_pool.PagedPool`` — block-table indirection, refcounted pages, and
prefix caching (requests sharing a prompt prefix map their leading
block-table entries to the same physical pages; fully-hit prefill
chunks are never dispatched).  ``layout="paged-sharded"`` is the same
pool MESH-SHARDED over a page axis (``serving.mesh``): physical pages
partitioned across the mesh devices, block tables replicated, the hot
loop one shard_map'd step with a distributed flash decode (one merge
collective per attention layer) — multi-device KV capacity as a config
flag.  ``layout="slotted"`` is the PR 2 contiguous layout, kept as the
differential baseline.

Sampling: greedy argmax by default; ``temperature`` > 0 enables
temperature sampling (optionally top-k truncated), seeded and
device-resident like the greedy path.

Streaming: ``submit(..., on_token=cb)`` registers a per-request token
callback and ``stream()`` wraps one request as a generator.  Callbacks
fire at token FLUSH time (tokens already land host-side there), so the
default path keeps its zero extra device syncs; ``run(stream_interval=
N)`` opts into flushing every N dispatches for incremental delivery.
"""
from __future__ import annotations

import time
from functools import partial
from typing import Callable, Dict, Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import get_model
from repro.obs.tracer import span
from repro.serving import kv_pool
from repro.serving.policy import Policy, get_policy
from repro.serving.scheduler import (FREE, Request, RequestRejected,
                                     Scheduler)
from repro.serving.spec import SpecDecoder, sample_step
from repro.serving.telemetry import (STAT_KEYS, ServingTelemetry,
                                     calibrate_capacity, export_telemetry,
                                     mor_group_map)

__all__ = ["Engine", "Request", "RequestRejected"]


class Engine:
    """Continuous-batching serving engine over a fixed slot pool.

    ``mor`` is the RAW calibrated MoR pytree ({layer group -> stacked
    MoRLayer}) as produced by ``deploy.calibrate_lm`` /
    ``deploy.calibrate_moe``; the engine attaches per-layer execution
    plans itself so that capacity calibration can re-attach them with
    per-layer budgets (per-(layer, expert) for the MoE expert group,
    whose stats arrive (L, E)-shaped via aux["moe_mor_stats"])."""

    def __init__(self, cfg: ModelConfig, params, *, mor: Optional[Dict] = None,
                 mor_mode: str = "dense", n_slots: int = 8,
                 max_len: int = 256, chunk: int = 0,
                 capacities: Optional[Dict] = None, telemetry: bool = True,
                 layout: str = "paged", page: int = 0,
                 prefix_cache: bool = True,
                 spare_pages: Optional[int] = None,
                 temperature: float = 0.0, top_k: int = 0,
                 sample_seed: int = 0, mesh=None, obs=None,
                 policy=None, spec_k: int = 0, draft_cap: float = 0.0,
                 spec_draft_temperature: Optional[float] = None,
                 shadow_rate: float = 0.0, drift_threshold: float = 0.25,
                 drift_detector: str = "ewma"):
        api = get_model(cfg)
        assert api.prefill_chunk is not None, \
            f"{cfg.name} ({cfg.family}) has no serving chunk step"
        assert layout in ("paged", "paged-sharded", "slotted")
        self.cfg = cfg
        self.api = api
        self.params = params
        self.mor_mode = mor_mode
        self.raw_mor = mor if mor_mode != "dense" else None
        self.chunk = chunk or cfg.serve_chunk
        self.n_slots = n_slots
        self.max_len = max_len
        self.layout = layout
        self.mesh = None
        if layout == "paged-sharded":
            if mesh is None:
                from repro.launch.mesh import make_page_mesh
                mesh = make_page_mesh()
            # params and plans are replicated on every page shard ONCE
            # here, not re-sent from device 0 on every dispatch
            from repro.serving.mesh import replicate
            self.mesh = mesh
            self.params = replicate(params, mesh)
        self.mor = self._attach(capacities)
        self.capacities = capacities
        if layout in ("paged", "paged-sharded"):
            n_shards = 1
            if layout == "paged-sharded":
                from repro.distributed.sharding_rules import PAGE_AXIS
                n_shards = mesh.shape[PAGE_AXIS]
            self.pool: Optional[kv_pool.PagedPool] = kv_pool.PagedPool(
                cfg, n_slots, max_len, chunk=self.chunk, page=page,
                spare_pages=spare_pages, prefix_cache=prefix_cache,
                n_shards=n_shards, mesh=self.mesh)
            self.cache = self.pool.build()
            self._reset = None
        else:
            self.pool = None
            self.cache = kv_pool.init(cfg, n_slots, max_len, self.chunk)
            self._reset = jax.jit(kv_pool.reset_slots, donate_argnums=(0,))
        # scheduling policy (SLO layer): a Policy instance or a name
        # ("fcfs" / "priority" / "sjf") — see repro.serving.policy
        if isinstance(policy, str):
            policy = get_policy(policy)
        self.scheduler = Scheduler(n_slots, self.chunk, policy=policy)
        self.policy: Policy = self.scheduler.policy
        # preemption spills pages through host copies of the
        # single-device pool leaves — gated off for the sharded layout
        # (its pages live mesh-distributed) and the slotted baseline
        self._can_preempt = (layout == "paged")
        # spilled (preempted) requests' host-side page images, by rid;
        # re-admission restores them into whatever slot frees up
        self._spilled: Dict[int, kv_pool.SpillRecord] = {}
        self.telemetry = ServingTelemetry() if telemetry else None
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self._base_key = jax.random.PRNGKey(sample_seed)
        # observability (repro.obs.Observability): metrics registry +
        # request tracer + packed device-resident metrics block.  The
        # block's layout is fixed NOW from the step's aux stat shapes
        # (jax.eval_shape — no compile) so the jit signature is stable.
        self.obs = obs
        self._tr = obs.tracer if obs is not None else None
        self._mspec = self._mblock = None
        if obs is not None and obs.device_metrics:
            from repro.obs.device import DeviceMetricsSpec
            self._mspec = DeviceMetricsSpec(self._probe_stat_shapes())
            n_rows = self.pool.n_shards if self.pool is not None else 1
            self._mblock = self._mspec.init(n_rows)
        body = partial(self._step_impl, cfg, api, mor_mode,
                       self.temperature, self.top_k, self._mspec)
        # the jit's name: what its compile spans and its device-trace
        # module are called (a bare partial is "<unknown>")
        body.__name__ = "engine_step"
        if layout == "paged-sharded":
            from repro.serving.mesh import make_sharded_step
            self._step = make_sharded_step(body, self.mesh, self.cache)
        else:
            # n_active (arg 10) is the static block-table width
            # (bucketed multiples of four) and copy_pads (arg 11) the
            # static {0, max} copy-pad widths — a handful of
            # executables total.  The metrics block (arg 9) is donated
            # like the cache: it round-trips through every dispatch.
            self._step = jax.jit(body, donate_argnums=(2, 9),
                                 static_argnums=(10, 11))
        # shadow-oracle predictor scoring (obs.quality): every Nth
        # vanilla dispatch is scored against the dense oracle and the
        # exact per-(layer, expert) false-skip / false-keep counts land
        # in the device metrics block.  TWO execution strategies,
        # picked per plan mode:
        #
        # - tiled plans: the cheap IN-STEP twin (mode="scored") — the
        #   sampled dispatch itself runs the scoring forward, whose
        #   activations are bitwise identical to the tiled path (tiled
        #   mode evaluates the dense matmul and selects), so it
        #   REPLACES the primary dispatch and the only extra cost is
        #   the elementwise truth arithmetic;
        # - kernel / exact plans: a standalone dense twin
        #   (mode="shadow") dispatched alongside the primary — those
        #   modes cannot guarantee bitwise identity (gather_matmul may
        #   reassociate the accumulation; exact is neuron-granular), so
        #   the primary's tokens stay authoritative and the twin's only
        #   output is the updated metrics block.
        #
        # Either way shadow-on is token-identical to shadow-off, and
        # shadow_rate=0 never builds or calls any twin, so the default
        # path's device-sync count is untouched.  Speculative rounds
        # bypass step()'s vanilla dispatch, so sampling covers vanilla
        # dispatches only.
        self.shadow_rate = float(shadow_rate)
        self._shadow_every: Optional[int] = None
        self._shadow_step = None
        self._shadow_mor = None
        self.drift = None
        if self.shadow_rate > 0.0:
            assert self.raw_mor is not None, \
                "shadow_rate needs a calibrated MoR tree " \
                "(mor_mode != 'dense')"
            assert self._mspec is not None, \
                "shadow_rate needs Observability(device_metrics=True)"
            from repro.core.executor import map_plans
            from repro.obs.quality import DriftDetector
            self._shadow_every = max(1, int(round(1.0 / self.shadow_rate)))
            modes = set()
            map_plans(self.mor, lambda p: (
                modes.add(p.mode) if p.active else None, p)[1])
            twin = bool(modes - {"tiled"})
            self._shadow_as = (
                (lambda p: p.as_shadow()) if twin else
                (lambda p: p.as_scored() if p.mode == "tiled" else p))
            self._shadow_mor = map_plans(self.mor, self._shadow_as)
            if twin:
                sbody = partial(self._shadow_impl, cfg, api, mor_mode,
                                self._mspec)
                if layout == "paged-sharded":
                    from repro.serving.mesh import make_sharded_shadow_step
                    self._shadow_step = make_sharded_shadow_step(
                        sbody, self.mesh, self.cache)
                else:
                    self._shadow_step = jax.jit(sbody, donate_argnums=(8,),
                                                static_argnums=(9, 10))
            self.drift = DriftDetector(threshold=drift_threshold,
                                       detector=drift_detector)
        # self-speculative decoding: MoR-capacitated draft passes
        # verified through the paged-COW block tables (serving.spec).
        # Gated to the single-device paged layout — rounds use
        # spill-style host-side fork/rollback and their own jitted
        # phase bodies, not the sharded step's fixed out_specs.
        self.spec: Optional[SpecDecoder] = None
        if spec_k > 0:
            assert layout == "paged", \
                "speculative decoding requires layout='paged'"
            # draft rows past the committed position must stay outside
            # any window: the pool sizes windowed rings with `chunk`
            # slack, which bounds how far a round may write ahead
            assert spec_k <= self.chunk, \
                f"spec_k={spec_k} must be <= chunk={self.chunk}"
            self.spec = SpecDecoder(
                self, spec_k=spec_k, draft_cap=draft_cap,
                draft_temperature=spec_draft_temperature)
        self._stream_cbs: Dict[int, Callable[[int, int], None]] = {}
        self._stream_done: set = set()
        self._next_rid = 0
        self._aux_log: List[Dict] = []
        # device-resident hot loop: each slot's last sampled token lives
        # in ``_pending`` and each dispatch's (emits, nxt) pair in
        # ``_tok_log`` — token values are fetched to host ONCE at flush,
        # so the dispatch loop never blocks on the accelerator pipeline
        # (completion is count-based; see scheduler._Slot)
        self._pending = jnp.zeros((n_slots,), jnp.int32)
        self._tok_log: List = []
        self.results: Dict[int, List[int]] = {}
        self.counters = {"prefill_tokens": 0, "decode_tokens": 0,
                         "dispatches": 0, "wall_s": 0.0,
                         "preemptions": 0, "requests_rejected": 0}
        # rejection reasons -> counts (mirrored into the obs registry)
        self.rejections: Dict[str, int] = {}
        # last host-side read of the device metrics block (set by
        # _flush_obs; surfaced in report()["obs"])
        self._last_device_metrics: Optional[Dict] = None

    def _flush_tokens(self) -> None:
        if self._tok_log:
            # ONE host transfer for the whole log.  Entries are either
            # (emits, nxt (B,)) vanilla dispatches or
            # (emits, tokens (B, K+1), counts (B,)) speculative rounds
            # (counts already host-side — it was the round's one sync)
            with span("engine.fetch", dispatches=len(self._tok_log)):
                fetched = jax.device_get([e[1] for e in self._tok_log])
            t_home = time.perf_counter()
            with span("engine.deliver"):
                delivered: Dict[int, int] = {}
                for entry, toks in zip(self._tok_log, fetched):
                    emits = entry[0]
                    counts = entry[2] if len(entry) > 2 else None
                    toks = np.asarray(toks)
                    for s, rid in emits:
                        if counts is None:
                            vals = (int(toks[s]),)
                        else:
                            vals = tuple(int(t) for t in
                                         toks[s, :int(counts[s])])
                        cb = self._stream_cbs.get(rid)
                        res = self.results.setdefault(rid, [])
                        for t in vals:
                            res.append(t)
                            if cb is not None:
                                cb(rid, t)
                        delivered[rid] = delivered.get(rid, 0) + len(vals)
                self._tok_log.clear()
                if self._tr is not None:
                    for rid, n in delivered.items():
                        self._tr.on_deliver(rid, n, t_home)
        # a flush drains every pending dispatch, so finished requests'
        # callbacks have now delivered their last token — drop them
        # (long-lived engines would otherwise leak one closure per
        # streamed request)
        for rid in self._stream_done:
            self._stream_cbs.pop(rid, None)
        self._stream_done.clear()

    def _flush_telemetry(self) -> None:
        if self.telemetry is not None:
            for aux in self._aux_log:
                self.telemetry.update(aux)
            if self.pool is not None and self.pool.prefix is not None:
                self.telemetry.update_prefix(self._prefix_counters())
            if self.pool is not None and self.pool.n_shards > 1:
                self.telemetry.update_sharding(self.pool.shard_report())
        self._aux_log.clear()

    def _flush_obs(self) -> None:
        """Mirror every counter source into the obs registry: the
        device metrics block (ONE host transfer), the pool's host-side
        accounting, kernel traces, and the telemetry summary.  Runs at
        flush boundaries only — never on the dispatch hot path.  All
        series are labeled by layout and written idempotently, so
        repeated flushes (and several engines sharing one registry)
        overwrite their own series instead of double-reporting."""
        if self.obs is None:
            return
        reg = self.obs.registry
        lay = self.layout
        if self._mblock is not None:
            dm = self._mspec.read(self._mblock)
            self._last_device_metrics = dm
            reg.counter("repro_engine_dispatches_total",
                        "compiled-step dispatches (device-counted)",
                        ("layout",)).set(dm["dispatches"], layout=lay)
            ctok = reg.counter(
                "repro_engine_tokens_total",
                "tokens processed by the compiled step",
                ("layout", "phase"))
            ctok.set(dm["prefill_tokens"], layout=lay, phase="prefill")
            ctok.set(dm["decode_tokens"], layout=lay, phase="decode")
            reg.counter(
                "repro_engine_pages_touched_total",
                "live (slot, block) table entries visible to the paged "
                "attends, summed over dispatches",
                ("layout",)).set(dm["pages_touched"], layout=lay)
            reg.counter(
                "repro_spec_tokens_drafted_total",
                "draft tokens proposed by speculative rounds "
                "(device-counted)",
                ("layout",)).set(dm["tokens_drafted"], layout=lay)
            reg.counter(
                "repro_spec_tokens_accepted_total",
                "draft tokens the target verify accepted "
                "(device-counted)",
                ("layout",)).set(dm["tokens_accepted"], layout=lay)
            cpe = reg.counter(
                "repro_pool_page_events_total",
                "device page edits applied by the fused cache-ops step",
                ("layout", "table", "event"))
            for table in ("kv", "state"):
                cpe.set(dm[f"{table}_page_resets"], layout=lay,
                        table=table, event="reset")
                cpe.set(dm[f"{table}_page_copies"], layout=lay,
                        table=table, event="copy")
            ct = reg.counter(
                "repro_mor_tiles_total",
                "predictor tile-grid size, summed over dispatches",
                ("layout", "group", "layer", "expert"))
            cs = reg.counter(
                "repro_mor_tiles_skipped_total",
                "tiles the predictor skipped, summed over dispatches",
                ("layout", "group", "layer", "expert"))
            gl = reg.gauge(
                "repro_mor_frac_tiles_live",
                "mean live-tile fraction (device fixed-point)",
                ("layout", "group", "layer", "expert"))
            for g, d in dm["groups"].items():
                for idx in np.ndindex(d["tiles_total"].shape):
                    lab = {"layout": lay, "group": g, "layer": idx[0],
                           "expert": idx[1] if len(idx) > 1 else ""}
                    ct.set(int(d["tiles_total"][idx]), **lab)
                    cs.set(int(d["tiles_skipped"][idx]), **lab)
                    gl.set(float(d["mean_frac_tiles_live"][idx]), **lab)
            if self._shadow_every is not None:
                # predictor-quality mirrors + drift detection over the
                # freshly drained shadow-oracle counters (obs.quality)
                reg.counter(
                    "repro_engine_shadow_dispatches_total",
                    "dispatches scored by the shadow-oracle twin",
                    ("layout",)).set(dm["shadow_dispatches"], layout=lay)
                cfs = reg.counter(
                    "repro_mor_false_skip_total",
                    "tiles the predictor skipped that the dense oracle "
                    "says were live (shadow-sampled)",
                    ("layout", "group", "layer", "expert"))
                cfk = reg.counter(
                    "repro_mor_false_keep_total",
                    "tiles the predictor kept that the dense oracle "
                    "says were dead (shadow-sampled)",
                    ("layout", "group", "layer", "expert"))
                gfs = reg.gauge(
                    "repro_mor_false_skip_rate",
                    "false skips over truly-live tiles, last flush "
                    "window (drift-detector input)",
                    ("layout", "group", "layer", "expert"))
                gsa = reg.gauge(
                    "repro_mor_shadow_sign_agree",
                    "mean predictor/oracle sign-agreement rate per "
                    "shadow dispatch",
                    ("layout", "group", "layer", "expert"))
                gse = reg.gauge(
                    "repro_mor_shadow_err",
                    "mean relative output-error norm of the MoR-masked "
                    "activation vs dense, per shadow dispatch",
                    ("layout", "group", "layer", "expert"))
                gdr = reg.gauge(
                    "repro_mor_drift",
                    "1 while the drift detector flags this series",
                    ("layout", "group", "layer", "expert"))
                for ev in self.drift.update(dm):
                    if self._tr is not None:
                        self._tr.on_drift(ev["group"], ev["layer"],
                                          ev["expert"], ev["rate"])
                dst = self.drift.state()
                for g, d in dm["groups"].items():
                    drifted = dst.get(g, {}).get("drifted")
                    for idx in np.ndindex(d["false_skip"].shape):
                        lab = {"layout": lay, "group": g,
                               "layer": idx[0],
                               "expert": idx[1] if len(idx) > 1 else ""}
                        cfs.set(int(d["false_skip"][idx]), **lab)
                        cfk.set(int(d["false_keep"][idx]), **lab)
                        gfs.set(float(d["false_skip_rate"][idx]), **lab)
                        gsa.set(float(d["mean_sign_agree"][idx]), **lab)
                        gse.set(float(d["mean_shadow_err"][idx]), **lab)
                        gdr.set(1.0 if drifted is not None
                                and bool(drifted[idx]) else 0.0, **lab)
        csd = reg.counter("repro_scheduler_dispatches_total",
                          "dispatches built, by kind",
                          ("layout", "kind"))
        for kind, v in self.scheduler.dispatch_kinds.items():
            csd.set(v, layout=lay, kind=kind)
        crj = reg.counter("repro_requests_rejected_total",
                          "requests rejected at submit validation",
                          ("layout", "reason"))
        for reason, v in self.rejections.items():
            crj.set(v, layout=lay, reason=reason)
        if self.pool is not None:
            cpre = reg.counter(
                "repro_preemptions_total",
                "slot preemptions: page spills to host and restores",
                ("layout", "event"))
            for k, v in self.pool.spill_events.items():
                cpre.set(v, layout=lay, event=k)
        if self.pool is not None:
            cal = reg.counter(
                "repro_pool_alloc_events_total",
                "host allocator page alloc/free events",
                ("layout", "table", "event"))
            for k, v in self.pool.alloc_events().items():
                table, event = k.split("_")
                cal.set(v, layout=lay, table=table, event=event)
            sh = self.pool.shard_report()
            giu = reg.gauge("repro_pool_pages_in_use",
                            "pages currently allocated, per shard",
                            ("layout", "table", "shard"))
            ghw = reg.gauge("repro_pool_pages_hiwater",
                            "page-occupancy high-water mark, per shard",
                            ("layout", "table", "shard"))
            for table in ("kv", "state"):
                key = f"{table}_pages_in_use_per_shard"
                if key not in sh:
                    continue
                for s, v in enumerate(sh[key]):
                    giu.set(v, layout=lay, table=table, shard=s)
                for s, v in enumerate(
                        sh[f"{table}_pages_hiwater_per_shard"]):
                    ghw.set(v, layout=lay, table=table, shard=s)
            if self.pool.prefix is not None:
                pc = self._prefix_counters()
                cpr = reg.counter("repro_prefix_events_total",
                                  "prefix-cache event counters",
                                  ("layout", "event"))
                for k, v in pc.items():
                    if k == "hit_rate":
                        continue
                    cpr.set(v, layout=lay, event=k)
                reg.gauge("repro_prefix_hit_rate",
                          "prefix-cache hit rate since last reset",
                          ("layout",)).set(pc["hit_rate"], layout=lay)
                gtr = reg.gauge("repro_prefix_trie",
                                "prefix-trie occupancy",
                                ("layout", "stat"))
                for k, v in self.pool.prefix.stats().items():
                    gtr.set(v, layout=lay, stat=k)
        from repro.kernels import paged_attention as pk
        ckt = reg.counter("repro_kernel_traces_total",
                          "paged-attention kernel traces (innermost "
                          "scope)", ("kind",))
        for kind, v in pk.kernel_traces().items():
            ckt.set(v, kind=kind)
        if self.telemetry is not None:
            export_telemetry(reg, self.telemetry, layout=lay,
                             capacities=self.capacities)
        if self._tr is not None:
            self._tr.mirror_spans()

    def _probe_stat_shapes(self) -> Dict[str, tuple]:
        """Shapes of the step's per-layer MoR stat leaves, via
        ``jax.eval_shape`` on the UNJITTED step body (abstract cache —
        nothing compiles, nothing runs).  Fixes the device metrics
        block's layout before the first dispatch."""
        sds = lambda a: jax.ShapeDtypeStruct(jnp.shape(a),
                                             jnp.result_type(a))
        cache_abs = jax.tree_util.tree_map(sds, self.cache)
        body = partial(self._step_impl, self.cfg, self.api, self.mor_mode,
                       self.temperature, self.top_k, None)
        out = jax.eval_shape(
            body, self.params, self.mor, cache_abs,
            jax.ShapeDtypeStruct((self.n_slots, self.chunk), jnp.int32),
            jax.ShapeDtypeStruct((self.n_slots,), jnp.int32),
            jax.ShapeDtypeStruct((self.n_slots,), jnp.bool_),
            jax.ShapeDtypeStruct((self.n_slots,), jnp.int32),
            self._base_key, None)
        aux = out[3]
        return {k: tuple(aux[k]["frac_tiles_live"].shape)
                for k in STAT_KEYS if aux.get(k)}

    # -- plan attachment ---------------------------------------------------
    def _attach(self, capacities: Optional[Dict]):
        if self.raw_mor is None:
            return None
        from repro.core.deploy import attach_plans
        caps = None
        if capacities is not None:
            gmap = mor_group_map(self.cfg)
            caps = {gmap.get(k, k): v for k, v in capacities.items()}
        plans = attach_plans(self.raw_mor, self.cfg, self.mor_mode,
                             capacities=caps)
        if self.mesh is not None:
            from repro.serving.mesh import replicate
            plans = replicate(plans, self.mesh)
        return plans

    @staticmethod
    def _step_impl(cfg, api, mor_mode, temperature, top_k, mspec,
                   params, mor, cache, tokens, n_valid, use_pending,
                   pending, key, ops, metrics=None, n_active=None,
                   copy_pads=(0, 0)):
        # obs page-edit counts mirror the ops walk against the pre-edit
        # cache (same static slices apply_cache_ops uses) — entirely on
        # device, rides in the metrics block
        mcounts = {}
        if metrics is not None and ops is not None:
            mcounts = kv_pool.ops_counts(cache, ops, *copy_pads)
        # paged layout: fuse the pool's pending page edits (resets, COW
        # copies, table uploads — one packed int32 vector) into THIS
        # compiled step; clean steps pass ops=None and jit caches a
        # second executable without the apply at all, so the steady
        # decode loop pays nothing for the allocator
        if ops is not None:
            cache = kv_pool.apply_cache_ops(cache, ops, *copy_pads)
        # active-block-width: slice the (post-ops) block table down to
        # the width this dispatch needs (``PagedPool.active_blocks``) —
        # the attends then never touch the provably-null tail columns.
        # The table itself is only ever edited host-side (via ops), so
        # the full table is restored verbatim in the returned cache.
        full_bt = None
        if n_active is not None and "block_table" in cache and \
                n_active < cache["block_table"].shape[1]:
            full_bt = cache["block_table"]
            cache = dict(cache, block_table=full_bt[:, :n_active])
        # pages this dispatch's attends can touch: live entries in the
        # active slots' (sliced) block tables
        bt_active = cache.get("block_table")
        # splice each decoding slot's device-resident last token into
        # column 0 (inside jit: no extra op dispatches on the hot loop)
        tokens = tokens.at[:, 0].set(
            jnp.where(use_pending, pending, tokens[:, 0]))
        # attached plans carry their own mode; mor_mode covers bare layers
        logits, cache, aux = api.prefill_chunk(
            params, cfg, tokens, cache, n_valid=n_valid, mor=mor,
            mor_mode=mor_mode)
        if full_bt is not None:
            cache = dict(cache, block_table=full_bt)
        last = jnp.clip(n_valid - 1, 0)
        lg = jnp.take_along_axis(logits, last[:, None, None], axis=1)[:, 0]
        # shared sampling head (serving.spec) — the speculative verify
        # uses the same function per position, which is what makes
        # greedy speculation token-identical to this vanilla step
        nxt, _ = sample_step(lg, temperature=temperature, top_k=top_k,
                             key=key)
        new_pending = jnp.where(n_valid > 0, nxt, pending)
        if metrics is not None:
            # all operands already live on device — the block update
            # fuses into this executable, no extra dispatch or sync
            dec = jnp.where(use_pending, n_valid, 0).sum(dtype=jnp.int32)
            scalars = dict(mcounts, dispatches=1,
                           decode_tokens=dec,
                           prefill_tokens=n_valid.sum(
                               dtype=jnp.int32) - dec)
            # an in-step scored dispatch (mode="scored" plans) carries
            # shadow_* quality leaves in its aux; it IS the primary
            # dispatch, so base lanes count once and the quality lanes
            # ride the same delta
            if any(isinstance(st, dict) and "shadow_false_skip" in st
                   for st in aux.values()):
                scalars["shadow_dispatches"] = 1
            if bt_active is not None:
                scalars["pages_touched"] = (
                    (bt_active > 0) & (n_valid > 0)[:, None]).sum(
                        dtype=jnp.int32)
            metrics = mspec.accumulate(metrics, scalars, aux)
        return nxt, new_pending, cache, aux, metrics

    @staticmethod
    def _shadow_impl(cfg, api, mor_mode, mspec, params, mor, cache,
                     tokens, n_valid, use_pending, pending, ops, metrics,
                     n_active=None, copy_pads=(0, 0)):
        """The dense-oracle twin of ``_step_impl``: reconstruct exactly
        the cache state the primary dispatch will see (the same pending
        page edits, the same active-block slice, the same pending-token
        splice), run the forward through mode="shadow" plans — dense
        math, with the predictor SCORED against the dense truth — and
        fold the shadow_* stat leaves into the metrics block.  Nothing
        else escapes: the cache copy is discarded (NOT donated — the
        primary step consumes the real one right after) and no tokens
        are sampled, so the primary path stays authoritative."""
        if ops is not None:
            cache = kv_pool.apply_cache_ops(cache, ops, *copy_pads)
        if n_active is not None and "block_table" in cache and \
                n_active < cache["block_table"].shape[1]:
            cache = dict(cache, block_table=cache["block_table"][:, :n_active])
        tokens = tokens.at[:, 0].set(
            jnp.where(use_pending, pending, tokens[:, 0]))
        _, _, aux = api.prefill_chunk(params, cfg, tokens, cache,
                                      n_valid=n_valid, mor=mor,
                                      mor_mode=mor_mode)
        # keep ONLY the shadow_* quality leaves: the primary dispatch
        # already accumulated this batch's base tile lanes, and the
        # quality lanes are what the shadow pass exists to fill
        qaux = {}
        for g, st in (aux or {}).items():
            if isinstance(st, dict):
                sh = {k: v for k, v in st.items()
                      if k.startswith("shadow_")}
                if sh:
                    qaux[g] = sh
        return mspec.accumulate(metrics, {"shadow_dispatches": 1}, qaux)

    # -- request API -------------------------------------------------------
    def _reject(self, reason: str, msg: str) -> None:
        self.counters["requests_rejected"] += 1
        self.rejections[reason] = self.rejections.get(reason, 0) + 1
        raise RequestRejected(reason, msg)

    def submit(self, prompt, max_new_tokens: int = 16,
               on_token: Optional[Callable[[int, int], None]] = None,
               priority: int = 0) -> int:
        """Queue a request; returns its rid.  ``on_token(rid, token)``
        is the detokenizing-stream hook: invoked for each generated
        token IN ORDER when the engine flushes its device-resident token
        log (end of ``run`` by default, every ``stream_interval``
        dispatches when opted in) — streaming adds no device syncs.
        ``priority`` feeds the scheduling policy (higher admits first;
        under ``PriorityPolicy`` it may preempt lower classes).

        Unservable requests raise ``RequestRejected`` (and count into
        ``requests_rejected``) BEFORE touching the queue — arrival-
        driven load records the rejection and keeps serving, where the
        old bare ``assert`` vanished under ``python -O`` and took the
        whole engine down."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            self._reject("empty_prompt", "prompt must have >= 1 token")
        if max_new_tokens < 1:
            # max_new_tokens=0 used to slip through and STILL emit one
            # token (prompt completion always samples) — reject upfront
            self._reject("nonpositive_max_new_tokens",
                         f"max_new_tokens={max_new_tokens} must be >= 1")
        if prompt.size + max_new_tokens + 1 > self.max_len:
            self._reject("oversize",
                         f"prompt {prompt.size} + max_new "
                         f"{max_new_tokens} exceeds max_len "
                         f"{self.max_len}")
        rid = self._next_rid
        self._next_rid += 1
        if on_token is not None:
            self._stream_cbs[rid] = on_token
        if self._tr is not None:
            self._tr.on_submit(rid)
        self.scheduler.add(Request(rid, prompt, max_new_tokens,
                                   priority=priority))
        return rid

    # -- preemption --------------------------------------------------------
    def _preempt(self, slot: int) -> None:
        """Spill ``slot``'s pages to host and requeue its request at its
        exact progress.  The slot's device-resident pending token (its
        last sample — about to be consumed when decode resumes) rides in
        the spill record; ``_place`` splices it back on restore."""
        req = self.scheduler.slots[slot].req
        self.cache, rec = self.pool.spill(slot, self.cache)
        rec.rid = req.rid
        rec.last_token = int(jax.device_get(self._pending[slot]))
        self._spilled[req.rid] = rec
        self.scheduler.preempt(slot)
        self.counters["preemptions"] += 1
        if self._tr is not None:
            self._tr.on_preempt(req.rid, slot)

    def _place(self, slot: int, entry) -> Optional[int]:
        """Scheduler admission callback: attach ``entry`` to ``slot``
        through the paged pool — prefix-cache admission for fresh
        requests, spill-record restore for preempted resumes.  Returns
        the prompt offset to start from, or None to DEFER the admission
        (pool exhausted — the engine may spill a victim and retry)."""
        if entry.resume:
            rec = self._spilled[entry.req.rid]
            try:
                self.cache = self.pool.restore(slot, rec, self.cache)
            except kv_pool.PoolExhausted:
                return None
            del self._spilled[entry.req.rid]
            self._pending = self._pending.at[slot].set(rec.last_token)
            if self._tr is not None:
                self._tr.on_restore(entry.req.rid, slot)
            return entry.offset
        try:
            return self.pool.admit(slot, entry.req.prompt)
        except kv_pool.PoolExhausted:
            return None

    def step(self) -> List[int]:
        """One scheduler iteration: admit (preempting victims when the
        policy or pool pressure demands it), dispatch, ingest.  Returns
        the rids that finished this step.

        The iteration is an ``engine.step`` span (``repro.obs.tracer``)
        whose args record the dispatch: ``kind``, ``rows`` run, each
        slot's ``pos`` before it and ``n_valid``, and the ``admitted``
        count.  Its children: ``engine.admit`` (preemption, admission,
        batch building), ``engine.plan`` (pool hooks, ``plan_writes``,
        the packed page edits), ``engine.enqueue`` (the compiled step's
        call and its uploads) and ``engine.feed`` (bookkeeping)."""
        with span("engine.step") as sp:
            return self._step_in(sp)

    def _step_in(self, sp: span) -> List[int]:
        t0 = time.perf_counter()
        sched = self.scheduler
        with span("engine.admit"):
            # policy-driven preemption: when no slot is free, the policy
            # may evict a running victim so the top waiting request
            # (after its ordering) gets served now — slots are the
            # scarce resource
            if self._can_preempt and sched.waiting and \
                    not any(s.state is FREE for s in sched.slots):
                self.policy.order(sched.waiting)
                victim = self.policy.select_victim(sched.slots,
                                                   sched.waiting[0])
                if victim is not None:
                    self._preempt(victim)
            place = self._place if self.pool is not None else None
            admitted = sched.admit(place)
            if self.pool is not None:
                # admission deferred on pool pressure: spill victims
                # (their exclusive pages move to host) and retry —
                # bounded, never touching slots admitted THIS step
                for _ in range(self.n_slots):
                    if not sched.deferred or not self._can_preempt:
                        break
                    victim = self.policy.spill_victim(sched.slots,
                                                      exclude=admitted)
                    if victim is None:
                        break
                    self._preempt(victim)
                    admitted += sched.admit(place)
            if admitted and self.pool is None:
                mask = np.zeros((self.n_slots,), bool)
                mask[admitted] = True
                self.cache = self._reset(self.cache, jnp.asarray(mask))
            kind = sched.peek_kind()
            if kind is None:
                if sched.waiting:
                    # nothing runs AND nothing can be admitted: without
                    # a victim to spill this can never make progress
                    raise kv_pool.PoolExhausted(
                        "no waiting request can be admitted and nothing "
                        "is running — pool exhausted with no preemption "
                        "victim")
                return []
            # decode-only dispatches upgrade to a speculative round
            # (draft k tokens cheap, verify them in one target pass) —
            # atomic inside this step, so preemption above always sees
            # committed state.  ready() backs off one step after a
            # pool-pressure abort so the vanilla path's spill machinery
            # can run.
            spec_round = (self.spec is not None and kind == "decode"
                          and self.spec.ready())
            if not spec_round:
                tokens, n_valid, use_pending, emits, finishing, \
                    prefilling = sched.build_batch(kind)
        sp.args["admitted"] = len(admitted)
        if spec_round:
            sp.args["kind"] = "spec"
            return self.spec.round(t0, admitted)
        ops = None
        with span("engine.plan"):
            if self.pool is not None:
                # pre-dispatch: snapshot recurrent state of slots whose
                # prompt finishes here (the state at ``offset`` is what
                # the previous dispatches left in the pool), publish the
                # prefix of windowed prompts about to wrap their ring
                # (their pages are still intact NOW — after this
                # dispatch they aren't), then allocate / copy-on-write
                # every page this dispatch will touch; the resulting
                # device edits ride into the fused step as ``ops``.
                # Pool exhaustion mid-plan spills a victim and REBUILDS
                # the batch (the victim may have been in it); the hooks
                # are idempotent and ``plan_writes`` resumes past blocks
                # already made exclusive, so retrying is safe.
                for _ in range(self.n_slots + 1):
                    for s, off in finishing:
                        self.pool.maybe_snapshot(
                            s, sched.slots[s].req.prompt, off)
                    for s, off, take in prefilling:
                        self.pool.maybe_publish_prewrap(
                            s, sched.slots[s].req.prompt, off, take)
                    try:
                        self.pool.plan_writes(n_valid)
                        break
                    except kv_pool.PoolExhausted:
                        victim = (self.policy.spill_victim(
                            sched.slots, exclude=admitted)
                            if self._can_preempt else None)
                        if victim is None:
                            raise
                        self._preempt(victim)
                        kind = sched.peek_kind()
                        if kind is None:    # spilled the whole batch
                            return []
                        (tokens, n_valid, use_pending, emits, finishing,
                         prefilling) = sched.build_batch(kind)
                else:
                    raise kv_pool.PoolExhausted(
                        "dispatch cannot fit even after spilling victims")
                self.cache, ops = self.pool.drain(self.cache)
            sched.dispatch_kinds[kind] += 1
            # decode riders in a mixed dispatch: counted at BUILD time
            # (feed() below flips prefill->decode / frees finished slots)
            ndec = int(use_pending.sum()) if kind == "mixed" else 0
            key = jax.random.fold_in(self._base_key,
                                     self.counters["dispatches"]) \
                if self.temperature > 0.0 else self._base_key
            n_active = (self.pool.active_blocks(n_valid)
                        if self.pool is not None else None)
            copy_pads = (self.pool.last_pads
                         if self.pool is not None and ops is not None
                         else (0, 0))
        sp.args.update(kind=kind, rows=int(tokens.size), n_valid=n_valid)
        if self.pool is not None:
            sp.args["pos"] = self.pool.pos.copy()
        # tracer bookkeeping (rid lookups must happen BEFORE feed()
        # below frees finished slots); None when tracing is off
        if self._tr is not None:
            slots = self.scheduler.slots
            tr_t0 = self._tr.now()
            tr_admitted = [(s, slots[s].req.rid) for s in admitted]
            tr_prefilling = [(s, slots[s].req.rid, off, take)
                             for s, off, take in prefilling]
        # shadow-oracle sampling: every Nth vanilla dispatch is scored.
        # Tiled plans swap the scored twin INTO the primary dispatch
        # (bitwise-identical activations, no extra forward); kernel /
        # exact plans run the standalone dense twin first — BEFORE the
        # primary step, which donates the cache and metrics block the
        # twin reads — and keep the primary's tokens authoritative.
        sampled = (self._shadow_every is not None and
                   self.counters["dispatches"] % self._shadow_every == 0)
        with span("engine.enqueue"):
            if sampled and self._shadow_step is not None:
                self._mblock = self._shadow_step(
                    self.params, self._shadow_mor, self.cache,
                    jnp.asarray(tokens), jnp.asarray(n_valid),
                    jnp.asarray(use_pending), self._pending, ops,
                    self._mblock, n_active, copy_pads)
            mor_step = (self._shadow_mor if sampled and
                        self._shadow_step is None else self.mor)
            nxt, self._pending, self.cache, aux, self._mblock = self._step(
                self.params, mor_step, self.cache, jnp.asarray(tokens),
                jnp.asarray(n_valid), jnp.asarray(use_pending),
                self._pending, key, ops, self._mblock, n_active, copy_pads)
        with span("engine.feed"):
            if self.pool is not None:
                self.pool.advance(n_valid)
            if emits:
                self._tok_log.append((emits, nxt))
            if self.telemetry is not None and aux:
                # buffer the (device) stat arrays; host conversion
                # happens lazily in _flush_telemetry so the dispatch
                # loop never syncs on telemetry
                self._aux_log.append(aux)
            finished, entering = self.scheduler.feed(n_valid)
            for _, req in finished:
                if req.rid in self._stream_cbs:
                    self._stream_done.add(req.rid)
            if self.pool is not None:
                # publish AFTER the dispatch that wrote the prompt's
                # last pages; release AFTER publish so a request
                # finishing in the same step still shares its pages
                for s, req in entering:
                    self.pool.publish(s, req.prompt)
                for s, _ in finished:
                    self.pool.release(s)
            self.counters["dispatches"] += 1
            nv_total = int(n_valid.sum())
            if kind == "decode":
                self.counters["decode_tokens"] += nv_total
            else:
                # decode slots riding in a mixed dispatch contribute 1
                self.counters["decode_tokens"] += ndec
                self.counters["prefill_tokens"] += nv_total - ndec
            self.counters["wall_s"] += time.perf_counter() - t0
            if self._tr is not None:
                self._tr.on_dispatch(
                    kind, tr_t0, self._tr.now(), admitted=tr_admitted,
                    prefilling=tr_prefilling, emits=emits,
                    finished=[req.rid for _, req in finished],
                    queue_depth=len(self.scheduler.waiting),
                    n_active=int(np.count_nonzero(n_valid)))
        return [req.rid for _, req in finished]

    def reset_counters(self) -> None:
        """Zero the throughput AND prefix-cache counters (e.g. between a
        compile-warmup pass and a timed pass) — so a report's hit rate /
        skipped chunks describe the same pass as its token counts.  The
        cache CONTENTS survive: only the accounting resets.  With
        observability on, the device metrics block and the tracer reset
        with them (registry mirrors follow at the next flush)."""
        self.counters = {"prefill_tokens": 0, "decode_tokens": 0,
                         "dispatches": 0, "wall_s": 0.0,
                         "preemptions": 0, "requests_rejected": 0}
        self.rejections = {}
        self.scheduler.chunks_skipped = 0
        self.scheduler.tokens_skipped = 0
        self.scheduler.dispatch_kinds = {"mixed": 0, "decode": 0,
                                         "draft": 0, "verify": 0,
                                         "replay": 0}
        if self.spec is not None:
            self.spec.reset()
        if self.pool is not None:
            self.pool.reset_event_counters()
        if self._mblock is not None:
            n_rows = self.pool.n_shards if self.pool is not None else 1
            self._mblock = self._mspec.init(n_rows)
        if self.drift is not None:
            # the cumulative source counters just zeroed; detector
            # state (EWMA / PH accumulators, raised flags) survives
            self.drift.rebase()
        if self._tr is not None:
            self._tr.reset()

    def drain(self) -> None:
        """Flush boundary without draining the queue: deliver the token
        log to host (+ stream callbacks) and push telemetry/obs mirrors.
        Open-loop drivers stepping the engine themselves call this once
        the arrival stream ends (``run`` does it implicitly).  An
        ``engine.drain`` span, with children ``engine.fetch`` (the one
        ``jax.device_get`` of the token log: the host waiting on the
        device), ``engine.deliver`` (callbacks, ``results``),
        ``engine.telemetry`` and ``engine.obs``."""
        with span("engine.drain"):
            self._flush_tokens()
            with span("engine.telemetry"):
                self._flush_telemetry()
            with span("engine.obs"):
                self._flush_obs()

    def run(self, requests=None,
            stream_interval: int = 0) -> Dict[int, List[int]]:
        """Drive the queue (plus optional (prompt, max_new) pairs) to
        completion; returns {rid: generated tokens} for the requests
        submitted via THIS call (all-time results stay in
        ``self.results``).  ``stream_interval`` > 0 flushes the token
        log (firing ``on_token`` stream callbacks) every that many
        dispatches instead of only at the end — the opt-in trade of
        periodic device syncs for incremental delivery."""
        first_rid = self._next_rid
        if requests:
            for prompt, max_new in requests:
                self.submit(prompt, max_new)
        while self.scheduler.has_work:
            self.step()
            if stream_interval > 0 and \
                    self.counters["dispatches"] % stream_interval == 0:
                self._flush_tokens()
        self.drain()
        if requests:
            return {rid: toks for rid, toks in self.results.items()
                    if rid >= first_rid}
        return dict(self.results)

    def stream(self, prompt, max_new_tokens: int = 16,
               interval: int = 1) -> Iterator[int]:
        """Detokenizing-stream iterator for ONE request: submit it NOW
        and return a generator yielding its tokens as they reach the
        host (the token log flushes every ``interval`` dispatches —
        already-host-side values, no extra per-token syncs).  Other
        queued requests keep being served by the same dispatches."""
        got: List[int] = []
        self.submit(prompt, max_new_tokens,
                    on_token=lambda _rid, tok: got.append(tok))

        def gen() -> Iterator[int]:
            served = 0
            while self.scheduler.has_work:
                self.step()
                if self.counters["dispatches"] % max(interval, 1) == 0:
                    self._flush_tokens()
                while served < len(got):
                    yield got[served]
                    served += 1
            self.drain()
            while served < len(got):
                yield got[served]
                served += 1

        return gen()

    # -- telemetry-driven capacity calibration -----------------------------
    def calibrate_capacities(self, quantile: float = 0.95,
                             floor: float = 0.05) -> Dict[str, np.ndarray]:
        """Set per-layer gather_matmul capacities from the accumulated
        tile-liveness histograms and re-attach the execution plans.
        Returns the chosen {stat group -> capacity fractions}, (L,) for
        dense stacks and (L, E) for the MoE expert group."""
        assert self.telemetry is not None and self.raw_mor is not None
        self._flush_telemetry()
        caps = calibrate_capacity(self.telemetry, quantile=quantile,
                                  floor=floor)
        self.capacities = caps
        self.mor = self._attach(caps)
        if self._shadow_mor is not None:
            # the shadow twin mirrors the active plans' capacity clip
            from repro.core.executor import map_plans
            self._shadow_mor = map_plans(self.mor, self._shadow_as)
        if self.spec is not None:
            # the draft tree wraps the (re-attached) target plans
            self.spec.refresh()
        return caps

    def update_mor(self, raw_mor: Dict) -> None:
        """Swap the calibrated MoR tree in place — the online-recalib
        hook (ROADMAP item 4) and the benchmark's drift-injection knob.
        Coefficients are traced leaves of the attached plans, so the
        compiled step does NOT recompile; the shadow twin and the
        speculative draft tree re-wrap the fresh plans."""
        assert self.raw_mor is not None, \
            "engine was built without a MoR tree"
        self.raw_mor = raw_mor
        self.mor = self._attach(self.capacities)
        if self._shadow_mor is not None:
            from repro.core.executor import map_plans
            self._shadow_mor = map_plans(self.mor, self._shadow_as)
        if self.spec is not None:
            self.spec.refresh()

    def _prefix_counters(self) -> Dict:
        """Prefix-cache counters merged across the pool (pages, hits)
        and the scheduler (chunks whose dispatch was skipped)."""
        pc = self.pool.report()
        return {
            "hit_rate": pc.get("hit_rate", 0.0),
            "prefix_queries": pc.get("prefix_queries", 0),
            "prefix_hits": pc.get("prefix_hits", 0),
            "tokens_reused": pc.get("tokens_reused", 0),
            "pages_shared": pc.get("pages_shared", 0),
            "pages_published": pc.get("pages_published", 0),
            "pages_cowed": pc.get("pages_cowed", 0),
            "pages_evicted": pc.get("pages_evicted", 0),
            "snapshots": pc.get("snapshots", 0),
            "snap_restores": pc.get("snap_restores", 0),
            "chunks_skipped": self.scheduler.chunks_skipped,
            "tokens_skipped": self.scheduler.tokens_skipped,
        }

    def report(self) -> Dict:
        self._flush_tokens()
        c = dict(self.counters)
        # counters["wall_s"] is HOST dispatch time (the device-resident
        # loop never blocks per step) — an upper bound on throughput.
        # serve._run_engine overrides the rates with a blocking
        # end-to-end wall clock; prefer those for published numbers.
        wall = max(c["wall_s"], 1e-9)
        rep = {
            "n_slots": self.n_slots, "chunk": self.chunk,
            "mor_mode": self.mor_mode, "layout": self.layout,
            "requests_finished": len(self.results),
            "tokens_per_s": (c["decode_tokens"] + c["prefill_tokens"]) / wall,
            "decode_tokens_per_s": c["decode_tokens"] / wall,
            **c,
        }
        if self.temperature > 0.0:
            rep["sampling"] = {"temperature": self.temperature,
                               "top_k": self.top_k}
        if self.spec is not None:
            rep["spec"] = self.spec.report()
        if self.pool is not None:
            rep["page"] = self.pool.page
            if self.pool.n_shards > 1:
                rep["sharding"] = self.pool.shard_report()
            if self.pool.prefix is not None:
                rep["prefix_cache"] = self._prefix_counters()
        if self.telemetry is not None:
            self._flush_telemetry()
            rep["telemetry"] = self.telemetry.summary()
        if self.capacities is not None:
            rep["per_layer_capacity"] = {
                k: np.asarray(v).tolist() for k, v in self.capacities.items()}
        if self.obs is not None:
            self._flush_obs()
            obs_rep: Dict = {}
            if self._mspec is not None and self._mblock is not None:
                obs_rep["device_metrics"] = self._mspec.read_json(
                    self._mblock)
            if self._tr is not None:
                obs_rep["tracing"] = self._tr.summary()
            rep["obs"] = obs_rep
        if self._shadow_every is not None:
            q: Dict = {"shadow_rate": self.shadow_rate,
                       "shadow_every": self._shadow_every}
            dm = self._last_device_metrics
            if dm is not None:
                q["shadow_dispatches"] = dm["shadow_dispatches"]
                q["groups"] = {
                    g: {"shadow_tiles": int(d["shadow_tiles"].sum()),
                        "false_skip": int(d["false_skip"].sum()),
                        "false_keep": int(d["false_keep"].sum()),
                        "truth_live": int(d["truth_live"].sum()),
                        "false_skip_rate": np.round(
                            d["false_skip_rate"], 6).tolist(),
                        "false_keep_rate": np.round(
                            d["false_keep_rate"], 6).tolist(),
                        "mean_sign_agree": np.round(
                            d["mean_sign_agree"], 6).tolist(),
                        "mean_shadow_err": np.round(
                            d["mean_shadow_err"], 6).tolist()}
                    for g, d in dm["groups"].items()}
            if self.drift is not None:
                q["drift"] = self.drift.summary()
            rep["quality"] = q
        return rep
