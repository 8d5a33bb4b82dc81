"""Self-speculative decoding: MoR-capacitated draft passes verified
through paged-COW block tables.

One set of weights serves both roles.  The DRAFT pass is the same
model under clamped execution plans (``MoRExecutionPlan.as_draft`` +
``attach_draft_caps``: ``draft_cap`` is a traced leaf like
``cap_live``, so sweeping it never recompiles) — the rookie-heavy
cheap configuration proposes up to ``k`` tokens per decoding slot
autoregressively.  The VERIFY pass is one chunked-prefill-shaped
dispatch under the full-capacity target plans scoring all ``k+1``
positions at once; the standard accept/reject rule keeps the longest
target-consistent prefix plus one correction/bonus token, so GREEDY
output is token-identical to vanilla decode by construction and
SEEDED sampling follows the exact rejection-sampling rule (the
emitted marginal equals the target distribution for ANY draft
proposal).

Speculation is a block-table operation, not a cache copy:

- fork: ``PagedPool.spec_fork`` records the committed position and
  block-table row, and backs recurrent state up to a spare page (the
  only content copy; KV needs none).
- draft writes land in COW-forked / freshly-allocated pages exactly
  like any other dispatch (``plan_writes``).
- rollback: truncate the position to the accepted prefix and drop
  pages the round allocated wholly past it.  Stale draft rows beyond
  the committed position carry tags greater than any future query
  position and self-mask on the shared causal check
  (``decode_attention.position_ok``); the committed frontier row is
  overwritten by the next dispatch's write-before-attend.
- recurrent-state families (rwkv / hybrid) restore the backup before
  verify (which recomputes state under target weights) and, on a
  partial accept, once more before ONE batched replay dispatch of the
  accepted tokens — device state always ends at the last verified
  token, which also makes mid-speculation preemption safe: rounds are
  atomic inside ``Engine.step`` and spill reads committed state.

The whole round costs ONE host sync (the per-slot emit counts);
emitted token values stay device-resident in the engine's token log,
and the drafted/accepted counters ride the packed device metrics
block (drained once per flush).
"""
from __future__ import annotations

import time
from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.tracer import span
from repro.serving import kv_pool

__all__ = ["sample_step", "accept_greedy", "accept_sampled",
           "emit_matrix", "SpecDecoder"]


# -- sampling (shared with the engine's vanilla step) ----------------------

def _scaled_logits(lg, temperature: float, top_k: int):
    """Temperature-scaled, optionally top-k-truncated logits (f32)."""
    lgs = lg.astype(jnp.float32) / temperature
    if top_k > 0:
        k = min(top_k, lgs.shape[-1])
        kth = jax.lax.top_k(lgs, k)[0][..., -1:]
        lgs = jnp.where(lgs < kth, -jnp.inf, lgs)
    return lgs


def sample_step(lg, *, temperature: float, top_k: int, key,
                with_probs: bool = False):
    """One sampling step over logits ``lg`` (..., V): greedy argmax at
    ``temperature == 0`` (``key`` unused), else seeded categorical over
    the temperature/top-k distribution.  Returns ``(tokens, probs)``
    where ``probs`` is the post-truncation categorical distribution
    (..., V) f32 the tokens were drawn from — the speculative rejection
    rule consumes it — or None when greedy / not requested (a pytree
    None output costs nothing)."""
    if temperature > 0.0:
        lgs = _scaled_logits(lg, temperature, top_k)
        toks = jax.random.categorical(key, lgs, axis=-1).astype(jnp.int32)
        return toks, (jax.nn.softmax(lgs, axis=-1) if with_probs else None)
    return jnp.argmax(lg, axis=-1).astype(jnp.int32), None


# -- acceptance rules (pure; unit-tested directly) -------------------------

def accept_greedy(drafts, targets, k_valid):
    """Greedy acceptance: keep the longest prefix of ``drafts`` (B, K)
    matching the target argmax ``targets`` (B, K+1) position-wise,
    considering only the first ``k_valid`` (B,) drafted positions.
    Returns ``(n_accept (B,), correction (B,))`` — the correction is
    the target token at the first mismatch (or the bonus token when
    everything matched), so the emitted stream is EXACTLY the vanilla
    greedy sequence regardless of what the draft proposed."""
    K = drafts.shape[1]
    idx = jnp.arange(K)[None, :]
    match = (drafts == targets[:, :K]) & (idx < k_valid[:, None])
    n_accept = jnp.cumprod(match.astype(jnp.int32), axis=1).sum(axis=1)
    correction = jnp.take_along_axis(
        targets, n_accept[:, None], axis=1)[:, 0]
    return n_accept, correction


def accept_sampled(drafts, draft_probs, tgt_probs, k_valid, key):
    """The exact speculative rejection rule: position ``i`` accepts
    draft ``d_i`` iff ``u_i <= p_i(d_i) / q_i(d_i)`` (``p`` target,
    ``q`` draft distribution, u ~ U[0,1)); the first rejection samples
    the correction from the residual ``norm(max(p - q, 0))`` and full
    acceptance samples the bonus from ``p`` at the next position.  The
    emitted marginal equals ``p`` for any proposal ``q`` with
    ``q(d) > 0`` on drawn tokens.

    drafts (B, K) int32; draft_probs (B, K, V); tgt_probs (B, K+1, V);
    k_valid (B,) drafted counts.  Returns ``(n_accept, correction)``."""
    B, K = drafts.shape
    ku, kr = jax.random.split(key)
    u = jax.random.uniform(ku, (B, K))
    p_d = jnp.take_along_axis(
        tgt_probs[:, :K], drafts[..., None], axis=-1)[..., 0]
    q_d = jnp.take_along_axis(
        draft_probs, drafts[..., None], axis=-1)[..., 0]
    idx = jnp.arange(K)[None, :]
    ok = (u * jnp.maximum(q_d, 1e-20) <= p_d) & (idx < k_valid[:, None])
    n_accept = jnp.cumprod(ok.astype(jnp.int32), axis=1).sum(axis=1)
    # residual at the rejection position (clamped gather; masked away
    # for fully-accepted rows below)
    j = jnp.minimum(n_accept, K - 1)
    p_j = jnp.take_along_axis(
        tgt_probs, j[:, None, None], axis=1)[:, 0]
    q_j = jnp.take_along_axis(
        draft_probs, j[:, None, None], axis=1)[:, 0]
    resid = jnp.clip(p_j - q_j, 0.0)
    rs = resid.sum(axis=-1, keepdims=True)
    # numerically-empty residual (q covers p) degenerates to p itself
    resid = jnp.where(rs > 1e-20, resid / jnp.maximum(rs, 1e-20), p_j)
    p_bonus = jnp.take_along_axis(
        tgt_probs, k_valid[:, None, None], axis=1)[:, 0]
    dist = jnp.where((n_accept >= k_valid)[:, None], p_bonus, resid)
    correction = jax.random.categorical(
        kr, jnp.log(jnp.maximum(dist, 1e-30)), axis=-1).astype(jnp.int32)
    return n_accept, correction


def emit_matrix(drafts, n_accept, correction, n_valid):
    """Pack the round's emissions: (B, K+1) tokens — the accepted draft
    prefix then the correction/bonus at column ``n_accept`` — plus the
    per-slot emit count ``n_accept + 1`` (0 for slots that sat the
    round out)."""
    K = drafts.shape[1]
    idx = jnp.arange(K + 1)[None, :]
    toks = jnp.where(idx[:, :K] < n_accept[:, None], drafts, 0)
    toks = jnp.concatenate(
        [toks, jnp.zeros((drafts.shape[0], 1), jnp.int32)], axis=1)
    toks = jnp.where(idx == n_accept[:, None], correction[:, None], toks)
    n_emit = jnp.where(n_valid > 0, n_accept + 1, 0)
    return toks, n_emit


# -- compiled phase bodies -------------------------------------------------
# Each mirrors Engine._step_impl's spine (fused cache ops -> active
# block-table slice -> pending splice into column 0 -> chunk step ->
# metrics accumulate) with phase-specific heads.  They are separate
# jits from the engine step on purpose: the sharded path's fixed
# out_specs never sees them (speculation is gated to layout="paged").

def _dispatch_core(cfg, api, mor_mode, mspec, params, mor, cache,
                   tokens, n_valid, pending, ops, metrics, n_active,
                   copy_pads):
    mcounts = {}
    if metrics is not None and ops is not None:
        mcounts = kv_pool.ops_counts(cache, ops, *copy_pads)
    if ops is not None:
        cache = kv_pool.apply_cache_ops(cache, ops, *copy_pads)
    full_bt = None
    if n_active is not None and "block_table" in cache and \
            n_active < cache["block_table"].shape[1]:
        full_bt = cache["block_table"]
        cache = dict(cache, block_table=full_bt[:, :n_active])
    bt_active = cache.get("block_table")
    use_pending = n_valid > 0
    tokens = tokens.at[:, 0].set(
        jnp.where(use_pending, pending, tokens[:, 0]))
    logits, cache, aux = api.prefill_chunk(
        params, cfg, tokens, cache, n_valid=n_valid, mor=mor,
        mor_mode=mor_mode)
    if full_bt is not None:
        cache = dict(cache, block_table=full_bt)
    pages = None
    if bt_active is not None:
        pages = ((bt_active > 0) & (n_valid > 0)[:, None]).sum(
            dtype=jnp.int32)
    return logits, cache, aux, mcounts, pages


def draft_step_impl(cfg, api, mor_mode, temperature, top_k, mspec,
                    params, mor, cache, n_valid, pending, key, ops,
                    metrics=None, n_active=None, copy_pads=(0, 0)):
    """One autoregressive draft step under the clamped plans: feed each
    live slot's pending token, propose the next.  Slots past their
    per-slot draft length ride with ``n_valid == 0`` — no state change,
    no KV write, pending preserved."""
    tokens = jnp.zeros((n_valid.shape[0], 1), jnp.int32)
    logits, cache, aux, mcounts, pages = _dispatch_core(
        cfg, api, mor_mode, mspec, params, mor, cache, tokens, n_valid,
        pending, ops, metrics, n_active, copy_pads)
    nxt, probs = sample_step(
        logits[:, 0], temperature=temperature, top_k=top_k, key=key,
        with_probs=temperature > 0.0)
    new_pending = jnp.where(n_valid > 0, nxt, pending)
    if metrics is not None:
        scalars = dict(mcounts, dispatches=1,
                       tokens_drafted=n_valid.sum(dtype=jnp.int32))
        if pages is not None:
            scalars["pages_touched"] = pages
        # draft aux stats stay out of the MoR tile lanes: they describe
        # the clamped pass and would skew capacity calibration
        metrics = mspec.accumulate(metrics, scalars, {})
    return nxt, probs, new_pending, cache, metrics


def verify_step_impl(cfg, api, mor_mode, temperature, top_k, mspec,
                     params, mor, cache, tokens, n_valid, pending, key,
                     draft_probs, ops, metrics=None, n_active=None,
                     copy_pads=(0, 0)):
    """The chunked-prefill-shaped verify: ``tokens`` (B, K+1) carries
    the pending token (spliced into column 0) followed by the drafted
    continuation; ``n_valid[s] = k_s + 1`` scores every position under
    the TARGET plans in one pass (rewriting the draft KV rows with
    target values before any attend — write-before-attend).  Slots with
    ``k_s == 0`` degenerate to vanilla decode: the correction is the
    target's column-0 token.  Returns the emit matrix, per-slot emit
    counts, and the new pending (correction/bonus) token."""
    drafts = tokens[:, 1:]
    logits, cache, aux, mcounts, pages = _dispatch_core(
        cfg, api, mor_mode, mspec, params, mor, cache, tokens, n_valid,
        pending, ops, metrics, n_active, copy_pads)
    k_valid = jnp.maximum(n_valid - 1, 0)
    if temperature > 0.0:
        tgt_probs = jax.nn.softmax(
            _scaled_logits(logits, temperature, top_k), axis=-1)
        if draft_probs is None:
            # greedy draft under a sampled target: q is a point mass
            draft_probs = jax.nn.one_hot(
                drafts, logits.shape[-1], dtype=jnp.float32)
        n_accept, correction = accept_sampled(
            drafts, draft_probs, tgt_probs, k_valid, key)
    else:
        targets = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        n_accept, correction = accept_greedy(drafts, targets, k_valid)
    emit_toks, n_emit = emit_matrix(drafts, n_accept, correction, n_valid)
    new_pending = jnp.where(n_valid > 0, correction, pending)
    if metrics is not None:
        acc = jnp.where(n_valid > 0, n_emit - 1, 0).sum(dtype=jnp.int32)
        scalars = dict(mcounts, dispatches=1,
                       decode_tokens=n_emit.sum(dtype=jnp.int32),
                       tokens_accepted=acc)
        if pages is not None:
            scalars["pages_touched"] = pages
        metrics = mspec.accumulate(metrics, scalars, aux)
    return emit_toks, n_emit, new_pending, cache, aux, metrics


def replay_step_impl(cfg, api, mor_mode, mspec, params, mor, cache,
                     tokens, n_valid, pending, ops, metrics=None,
                     n_active=None, copy_pads=(0, 0)):
    """Partial-accept state replay: re-feed the ACCEPTED tokens
    (``n_valid[s] = m_s``) from the restored fork-point state under the
    target plans, so recurrent state lands exactly at the last verified
    token.  The KV rows it rewrites are identical to what verify wrote
    (same inputs, same weights); logits are discarded and nothing is
    emitted — pending is untouched."""
    _, cache, aux, mcounts, pages = _dispatch_core(
        cfg, api, mor_mode, mspec, params, mor, cache, tokens, n_valid,
        pending, ops, metrics, n_active, copy_pads)
    if metrics is not None:
        scalars = dict(mcounts, dispatches=1)
        if pages is not None:
            scalars["pages_touched"] = pages
        metrics = mspec.accumulate(metrics, scalars, {})
    return cache, metrics


# -- the round orchestrator ------------------------------------------------

class SpecDecoder:
    """Drives speculative rounds for an :class:`~repro.serving.engine.
    Engine` (paged layout, single device).  Holds the draft-mode plan
    tree and the three jitted phase bodies; ``round`` replaces one
    vanilla decode dispatch inside ``Engine.step`` whenever every live
    slot is decoding."""

    def __init__(self, engine, *, spec_k: int, draft_cap: float = 0.0,
                 draft_temperature: Optional[float] = None):
        assert spec_k >= 1
        self.eng = engine
        self.k = int(spec_k)
        self.draft_cap = float(draft_cap)
        # greedy targets may still DRAFT at temperature (forces
        # rejections while the emitted stream stays exactly greedy —
        # the rollback paths get exercised without changing output)
        self.draft_temperature = (
            engine.temperature if draft_temperature is None
            else float(draft_temperature))
        self.counters: Dict[str, float] = {
            "rounds": 0, "tokens_drafted": 0, "tokens_accepted": 0,
            "replays": 0, "aborts": 0}
        self._cooldown = 0
        self.refresh()
        e = engine
        self._draft = jax.jit(
            partial(draft_step_impl, e.cfg, e.api, e.mor_mode,
                    self.draft_temperature, e.top_k, e._mspec),
            donate_argnums=(2, 7), static_argnums=(8, 9))
        self._verify = jax.jit(
            partial(verify_step_impl, e.cfg, e.api, e.mor_mode,
                    e.temperature, e.top_k, e._mspec),
            donate_argnums=(2, 9), static_argnums=(10, 11))
        self._replay = jax.jit(
            partial(replay_step_impl, e.cfg, e.api, e.mor_mode,
                    e._mspec),
            donate_argnums=(2, 7), static_argnums=(8, 9))

    def refresh(self) -> None:
        """(Re)derive the draft plan tree from the engine's current
        plans — called at construction and after ``calibrate_capacities``
        re-attaches them.  ``draft == target`` when the engine runs
        dense (no plans); with plans, ``draft_cap > 0`` clamps every
        layer's live-tile capacity for the draft pass (a traced leaf:
        re-running this with a new value never recompiles)."""
        if self.eng.mor is None:
            self.mor_draft = None
            return
        from repro.core.executor import attach_draft_caps, map_plans
        md = self.eng.mor
        if self.draft_cap > 0.0:
            md = attach_draft_caps(md, self.draft_cap)
        self.mor_draft = map_plans(md, lambda p: p.as_draft())

    def reset(self) -> None:
        for k in self.counters:
            self.counters[k] = 0
        self._cooldown = 0

    def report(self) -> Dict:
        c = dict(self.counters)
        return {"k": self.k, "draft_cap": self.draft_cap,
                "draft_temperature": self.draft_temperature,
                "acceptance_rate": (
                    c["tokens_accepted"] / max(c["tokens_drafted"], 1)),
                **c}

    def ready(self) -> bool:
        """One-step backoff after an aborted round (pool pressure): the
        next step takes the vanilla path, whose spill machinery can
        free pages, before speculation resumes."""
        if self._cooldown > 0:
            self._cooldown -= 1
            return False
        return True

    # -- round helpers ----------------------------------------------------

    def _plan_round(self) -> Tuple[np.ndarray, np.ndarray]:
        """Per-slot draft lengths: ``min(k, remaining - 1)`` so a round
        never overshoots a request's token budget, then capped so the
        round's total verified positions ride the policy's
        ``prefill_budget`` (verify IS a prefill-shaped chunk; the first
        speculating slot always keeps >= 1, mirroring the scheduler's
        starvation guard)."""
        eng = self.eng
        k_s = np.zeros((eng.n_slots,), np.int64)
        active = np.zeros((eng.n_slots,), bool)
        budget = eng.policy.prefill_budget
        left = budget if budget > 0 else None
        for s in range(eng.n_slots):
            rem = eng.scheduler.decode_remaining(s)
            if rem <= 0:
                continue
            active[s] = True
            take = min(self.k, rem - 1)
            if left is not None and take > 0:
                cap = max(left, 0) if k_s.any() else max(left, 1)
                take = min(take, cap)
                left -= take
            k_s[s] = take
        return k_s, active

    def _abort(self, forks: List) -> None:
        for f in forks:
            self.eng.pool.spec_abort(f)
        self.counters["aborts"] += 1
        self._cooldown = 1

    # -- the round --------------------------------------------------------

    def round(self, t0: float, admitted: List[int]) -> List[int]:
        """One speculative round: fork -> k draft dispatches -> verify
        dispatch -> commit/rollback (+ optional state replay) -> feed.
        Exactly one host sync (the per-slot emit counts, an
        ``engine.fetch`` span).  Falls back to one vanilla
        ``Engine.step`` when the pool cannot host the round.  Runs
        inside the caller's ``engine.step`` span; each dispatch has its
        ``engine.plan``, ``engine.enqueue`` (args ``kind``: draft /
        verify / replay) and ``engine.feed`` spans, as a vanilla step
        does."""
        eng = self.eng
        sched, pool = eng.scheduler, eng.pool
        K, B = self.k, eng.n_slots
        k_s, active = self._plan_round()
        kmax = int(k_s.max(initial=0))
        forks: List = []
        try:
            for s in np.nonzero(k_s > 0)[0]:
                forks.append(pool.spec_fork(int(s)))
        except kv_pool.PoolExhausted:
            self._abort(forks)
            return eng.step()

        # -- draft loop: kmax host iterations of ONE compiled step
        # (n_valid masks slots past their per-slot length) ------------
        pending = eng._pending          # round-local; committed pending
        draft_toks: List = []           # stays in eng._pending for
        draft_probs: List = []          # rollback / preemption safety
        try:
            for i in range(kmax):
                with span("engine.plan"):
                    nv = (k_s > i).astype(np.int32)
                    pool.plan_writes(nv)
                    eng.cache, ops = pool.drain(eng.cache)
                    n_active = pool.active_blocks(nv)
                    copy_pads = (pool.last_pads if ops is not None
                                 else (0, 0))
                    key = (jax.random.fold_in(eng._base_key,
                                              eng.counters["dispatches"])
                           if self.draft_temperature > 0.0
                           else eng._base_key)
                tr_t0 = eng._tr.now() if eng._tr is not None else 0.0
                with span("engine.enqueue", kind="draft"):
                    nxt, probs, pending, eng.cache, eng._mblock = \
                        self._draft(
                            eng.params, self.mor_draft, eng.cache,
                            jnp.asarray(nv), pending, key, ops,
                            eng._mblock, n_active, copy_pads)
                with span("engine.feed"):
                    pool.advance(nv)
                    draft_toks.append(nxt)
                    draft_probs.append(probs)
                    eng.counters["dispatches"] += 1
                    sched.dispatch_kinds["draft"] += 1
                    self.counters["tokens_drafted"] += int(nv.sum())
                    if eng._tr is not None:
                        eng._tr.on_dispatch(
                            "draft", tr_t0, eng._tr.now(),
                            queue_depth=len(sched.waiting),
                            n_active=int(nv.sum()))

            # -- verify: reset to the fork point, score k+1 positions
            # under the target plans in one prefill-shaped pass -------
            with span("engine.plan"):
                for f in forks:
                    pool.spec_set_pos(f.slot, f.pos)
                    pool.spec_restore_state(f)
                nvv = np.where(active, k_s + 1, 0).astype(np.int32)
                pool.plan_writes(nvv)
        except kv_pool.PoolExhausted:
            self._abort(forks)
            return eng.step()
        with span("engine.plan"):
            eng.cache, ops = pool.drain(eng.cache)
            n_active = pool.active_blocks(nvv)
            copy_pads = pool.last_pads if ops is not None else (0, 0)
            if kmax:
                dstack = jnp.stack(draft_toks, axis=1)
                if kmax < K:
                    dstack = jnp.concatenate(
                        [dstack, jnp.zeros((B, K - kmax), jnp.int32)],
                        axis=1)
            else:
                dstack = jnp.zeros((B, K), jnp.int32)
            tokens = jnp.concatenate(
                [jnp.zeros((B, 1), jnp.int32), dstack], axis=1)
            qstack = None
            if eng.temperature > 0.0 and self.draft_temperature > 0.0:
                V = (draft_probs[0].shape[-1] if kmax
                     else eng.cfg.vocab_size)
                if kmax:
                    qstack = jnp.stack(draft_probs, axis=1)
                    if kmax < K:
                        qstack = jnp.concatenate(
                            [qstack,
                             jnp.full((B, K - kmax, V), 1.0, jnp.float32)],
                            axis=1)
                else:
                    qstack = jnp.full((B, K, V), 1.0, jnp.float32)
            key = (jax.random.fold_in(eng._base_key,
                                      eng.counters["dispatches"])
                   if eng.temperature > 0.0 else eng._base_key)
        tr_t0 = eng._tr.now() if eng._tr is not None else 0.0
        with span("engine.enqueue", kind="verify"):
            emit_toks, n_emit_dev, new_pending, eng.cache, aux, \
                eng._mblock = self._verify(
                    eng.params, eng.mor, eng.cache, tokens,
                    jnp.asarray(nvv), eng._pending, key, qstack, ops,
                    eng._mblock, n_active, copy_pads)
        pool.advance(nvv)
        eng.counters["dispatches"] += 1
        sched.dispatch_kinds["verify"] += 1
        if eng.telemetry is not None and aux:
            eng._aux_log.append(aux)

        # the round's single host sync: per-slot emit counts drive the
        # host-side commit/rollback and the scheduler feed
        with span("engine.fetch", what="emit_counts"):
            n_emit = np.asarray(jax.device_get(n_emit_dev), np.int64)

        # -- commit / rollback ----------------------------------------
        replays: List[Tuple] = []
        for f in forks:
            m = int(n_emit[f.slot])
            committed = f.pos + m
            if m < int(k_s[f.slot]) + 1:
                pool.spec_rollback_pages(f, committed)
                pool.spec_set_pos(f.slot, committed)
                if f.st_backup:
                    replays.append((f, m))
                    continue
            pool.spec_drop_backup(f)
        if replays:
            # one batched replay re-derives recurrent state at the last
            # verified token (verify over-advanced it by the rejected
            # tail); attention-only families need none — their rollback
            # is pure position truncation
            with span("engine.plan"):
                nvr = np.zeros((B,), np.int32)
                for f, m in replays:
                    pool.spec_set_pos(f.slot, f.pos)
                    pool.spec_restore_state(f)
                    nvr[f.slot] = m
                # every page involved is already exclusively owned
                # (written this round), so this plan cannot raise
                pool.plan_writes(nvr)
                eng.cache, ops = pool.drain(eng.cache)
                n_active = pool.active_blocks(nvr)
                copy_pads = pool.last_pads if ops is not None else (0, 0)
            tr_t0r = eng._tr.now() if eng._tr is not None else 0.0
            with span("engine.enqueue", kind="replay"):
                eng.cache, eng._mblock = self._replay(
                    eng.params, eng.mor, eng.cache, tokens,
                    jnp.asarray(nvr), eng._pending, ops, eng._mblock,
                    n_active, copy_pads)
            pool.advance(nvr)
            eng.counters["dispatches"] += 1
            sched.dispatch_kinds["replay"] += 1
            self.counters["replays"] += 1
            for f, _ in replays:
                pool.spec_drop_backup(f)
            if eng._tr is not None:
                eng._tr.on_dispatch(
                    "replay", tr_t0r, eng._tr.now(),
                    queue_depth=len(sched.waiting),
                    n_active=len(replays))

        # -- feed / emit ------------------------------------------------
        with span("engine.feed"):
            eng._pending = new_pending
            slots = sched.slots
            emits = [(int(s), slots[s].req.rid)
                     for s in np.nonzero(active)[0]]
            if eng._tr is not None:
                tr_admitted = [(s, slots[s].req.rid) for s in admitted]
                tr_counts = [int(n_emit[s]) for s, _ in emits]
            eng._tok_log.append((emits, emit_toks, n_emit))
            finished = sched.feed_counts(n_emit)
            for _, req in finished:
                if req.rid in eng._stream_cbs:
                    eng._stream_done.add(req.rid)
            for s, _ in finished:
                pool.release(s)
            emitted = int(n_emit.sum())
            accepted = emitted - len(emits)
            self.counters["rounds"] += 1
            self.counters["tokens_accepted"] += accepted
            eng.counters["decode_tokens"] += emitted
            eng.counters["wall_s"] += time.perf_counter() - t0
            if eng._tr is not None:
                eng._tr.on_dispatch(
                    "verify", tr_t0, eng._tr.now(), admitted=tr_admitted,
                    emits=emits, emit_counts=tr_counts,
                    finished=[req.rid for _, req in finished],
                    queue_depth=len(sched.waiting),
                    n_active=int(np.count_nonzero(nvv)))
        return [req.rid for _, req in finished]
