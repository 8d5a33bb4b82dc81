"""Mixture-of-Experts FFN: top-k routing with static capacity, sort-based
dispatch (no O(T*E*C) one-hot tensors — scales to 1M-token global batches),
shared experts (DeepSeek-V2 style), load-balancing auxiliary loss.

Expert weights are (E, d, f) so they shard as EP (expert dim over "model")
or TP (f over "model") per ``cfg.expert_sharding``.

Expert-level MoR runs every execution mode (exact / tiled / kernel)
through one batched-expert plan per layer (``executor.expert_ffn``):
per-(layer, expert) predictors, per-expert calibrated capacity clamps,
and (E,)-shaped skip stats in aux["mor_stats"] for the serving
telemetry.  Serving dispatches (the ``token_mask`` path) provision
expert capacity from the dispatch shape (``cfg.serve_expert_capacity``)
so chunked prefill never drops a valid token.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers.common import activation_fn, dense_init, is_glu, split_keys
from repro.models.layers.mlp import effective_activation, mlp_init, mlp_apply


def moe_init(key, cfg: ModelConfig) -> Dict:
    d = cfg.d_model
    f = cfg.moe_d_ff or cfg.d_ff
    E = cfg.n_experts
    pd = jnp.dtype(cfg.param_dtype)
    ks = split_keys(key, 6)
    glu = is_glu(effective_activation(cfg))

    def ew(k, a, b):
        return (jax.random.normal(k, (E, a, b), jnp.float32) * a ** -0.5
                ).astype(pd)

    p = {"router": dense_init(ks[0], d, E, pd, scale=0.02),
         "w_up": ew(ks[1], d, f),
         "w_down": ew(ks[2], f, d)}
    if glu:
        p["w_gate"] = ew(ks[3], d, f)
    if cfg.n_shared_experts:
        shared_cfg = cfg.replace(d_ff=cfg.n_shared_experts * f)
        p["shared"] = mlp_init(ks[4], shared_cfg,
                               d_ff=cfg.n_shared_experts * f)
    return p


def _dispatch_indices(top_idx: jnp.ndarray, E: int, C: int):
    """top_idx: (T, k) expert choice per token-slot.  Returns, per flat
    (token,k) pair, the expert buffer slot it lands in (or E*C if dropped),
    using a stable sort so earlier tokens win capacity — matches standard
    GShard/Switch semantics."""
    T, k = top_idx.shape
    flat = top_idx.reshape(-1)                          # (T*k,)
    order = jnp.argsort(flat, stable=True)              # group by expert
    sorted_e = flat[order]
    counts = jnp.bincount(flat, length=E)               # tokens per expert
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(T * k) - starts[sorted_e]     # rank within expert
    # sentinel pairs (expert id >= E: masked tokens) must land EXACTLY on
    # the E*C drop slot — without the explicit check their rank offset
    # (computed against the clamped starts[E-1]) leaks past E*C and the
    # combine gather only behaves by virtue of jax's clamp semantics
    keep = (pos_in_e < C) & (sorted_e < E)
    slot_sorted = jnp.where(keep, sorted_e * C + pos_in_e, E * C)
    slot = jnp.zeros((T * k,), jnp.int32).at[order].set(
        slot_sorted.astype(jnp.int32))
    return slot.reshape(T, k)


def moe_apply_a2a(params: Dict, cfg: ModelConfig, x, *,
                  mor=None, mor_mode: str = "dense") -> Tuple:
    """Expert-parallel MoE in shard_map ("expert slicing"): tokens are
    dp-sharded and REPLICATED over the model axis (which SP layouts give
    us anyway at the FFN boundary); experts are model-sharded.  Each
    model shard routes the same local tokens, keeps only its own
    experts' buffers, runs the expert FFN locally, and one psum over
    'model' sums the disjoint expert contributions.

    Total comms per layer = ONE (T_loc, d) psum — no dispatch gathers,
    no all_to_all, no (T*k, d) materialisation (the §Perf A-cell lever;
    GSPMD's derived schedule for the same math moved ~14 GB/layer).
    Router compute is replicated across the model axis (negligible).
    Capacity is static: C_loc = cf * T_loc * k / E."""
    from repro.distributed.sharding_rules import _TLS
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    ctx = getattr(_TLS, "ctx", None)
    if ctx is None:
        return None
    mesh, _ = ctx
    if "model" not in mesh.axis_names:
        return None
    MP = mesh.shape["model"]
    E, k = cfg.n_experts, cfg.top_k
    # E >= MP and divisible: experts sharded over model ("ep slicing").
    # E < MP (mixtral: 8 over 16): every shard runs ALL experts on its
    # F/MP slice ("tp slicing") — the same single psum combines either
    # the disjoint expert outputs or the f-slice partials.
    mode_tp = E % MP != 0
    f = cfg.moe_d_ff or cfg.d_ff
    if mode_tp and f % MP != 0:
        return None
    lead = x.shape[:-1]
    d = x.shape[-1]
    xf = x.reshape(-1, d)
    T = xf.shape[0]
    dp_axes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    dp = 1
    for a in dp_axes:
        dp *= mesh.shape[a]
    if T % (dp * MP) != 0:
        return None
    dp_spec = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    T_loc = T // dp
    C_loc = max(int(cfg.capacity_factor * T_loc * k / E), 1)
    E_loc = E if mode_tp else E // MP
    dt = x.dtype
    glu = "w_gate" in params
    act_name = effective_activation(cfg)
    act = activation_fn(act_name)
    from repro.core.executor import MoRExecutionPlan, as_expert_plan
    em = mor.get("experts") if isinstance(mor, dict) else None
    eplan = as_expert_plan(em, mode=mor_mode, tile_m=cfg.mor.tile_m,
                           tile_n=cfg.mor.tile_n,
                           capacity_frac=cfg.mor.capacity)
    # expert-level MoR rides the EP ("expert slicing") layout only: each
    # shard holds its experts' FULL f dim, so the per-column predictor
    # tables and proxy gathers stay local.  TP slicing splits every
    # expert's columns across shards (proxies may live elsewhere) — the
    # expert FFN stays dense there (ROADMAP: a2a-path limit).
    use_mor = (eplan.active and not mode_tp
               and act_name in ("relu", "relu2", "relu_glu"))
    base_act = "relu" if act_name == "relu_glu" else act_name

    def body(xl, router, w_up, w_gate, w_down, em_loc, cap_loc):
        # xl: (T_loc/MP?, ...) — tokens are sharded over dp ONLY, so with
        # in_spec P(dp_spec) each model shard holds the same T_loc tokens;
        # router logits are computed redundantly (cheap) and each model
        # shard extracts its own experts' buffers (no dispatch comms at
        # all — "expert slicing" beats all_to_all when tokens are
        # replicated over the model axis, which SP decode/train gives us).
        logits = (xl @ router).astype(jnp.float32)       # (T_loc, E)
        probs = jax.nn.softmax(logits, -1)
        top_p, top_idx = jax.lax.top_k(probs, k)
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
        slot = _dispatch_indices(top_idx, E, C_loc)      # (T_loc, k)
        e0 = 0 if mode_tp else jax.lax.axis_index("model") * E_loc
        # local slot ids for the experts this shard owns
        loc = slot - e0 * C_loc
        mine = (loc >= 0) & (loc < E_loc * C_loc)
        loc = jnp.where(mine, loc, E_loc * C_loc)
        smap = jnp.full((E_loc * C_loc + 1,), T_loc, jnp.int32)
        smap = smap.at[loc.reshape(-1)].set(
            jnp.broadcast_to(jnp.arange(T_loc, dtype=jnp.int32)[:, None],
                             (T_loc, k)).reshape(-1), mode="drop")
        xpad = jnp.concatenate([xl, jnp.zeros((1, d), dt)], 0)
        eb = jnp.take(xpad, smap[:E_loc * C_loc], 0).reshape(E_loc, C_loc, d)
        if use_mor:
            # per-expert MoR plans over this shard's experts: same static
            # config as the attached plan, leaves sliced by the shard_map
            # in_spec.  Buffer rows past an expert's routed count hold
            # the zero pad row — force-skipped via row_mask.
            counts = jnp.bincount(top_idx.reshape(-1), length=E)
            cnt_loc = jax.lax.dynamic_slice(
                counts, (jnp.asarray(e0, jnp.int32),), (E_loc,))
            row_valid = (jnp.arange(C_loc, dtype=jnp.int32)[None, :]
                         < jnp.minimum(cnt_loc, C_loc)[:, None])
            plan = MoRExecutionPlan(em_loc, mode=eplan.mode,
                                    tile_m=eplan.tile_m,
                                    tile_n=eplan.tile_n,
                                    capacity_frac=eplan.capacity_frac,
                                    cap_live=cap_loc if has_cap else None)
            # per-expert stats stay shard-local (telemetry calibrates on
            # the serving path; this is the training/forward layout)
            out_e, _ = plan.expert_ffn(
                eb, w_up, w_down, activation=base_act,
                w_gate=w_gate if glu else None, row_mask=row_valid)
            out_e = out_e.astype(dt)                     # (E_loc, C_loc, d)
        else:
            up = jnp.einsum("ecd,edf->ecf", eb, w_up)
            if glu:
                h = (act(jnp.einsum("ecd,edf->ecf", eb, w_gate))
                     * up).astype(dt)
            else:
                h = act(up).astype(dt)
            out_e = jnp.einsum("ecf,efd->ecd", h, w_down)  # (E_loc, C_loc, d)
        out_flat = jnp.concatenate(
            [out_e.reshape(E_loc * C_loc, d), jnp.zeros((1, d), dt)], 0)
        # combine: each shard contributes only its experts' outputs
        # (an F/R partial when R > 1); psum over model sums the disjoint
        # expert contributions AND the f-slice partials.
        y = jnp.zeros((T_loc, d), dt)
        for kk in range(k):
            part = jnp.take(out_flat, jnp.where(mine[:, kk], loc[:, kk],
                                                E_loc * C_loc), 0)
            y = y + part * top_p[:, kk:kk + 1].astype(dt)
        y = jax.lax.psum(y, "model")
        # load-balance loss (identical on every shard)
        fr = (jnp.zeros((E,), jnp.float32)
              .at[top_idx.reshape(-1)].add(1.0, mode="drop") / (T_loc * k))
        lb = E * jnp.sum(fr * probs.mean(0))
        return y, lb

    gate = params.get("w_gate")
    if mode_tp:
        up_spec = P(None, None, "model")
        down_spec = P(None, "model", None)
    else:
        up_spec = down_spec = P("model")
    # expert MoR leaves ride in expert-sliced ((E, ...) over "model"),
    # mirroring the EP weight layout; a scalar dummy otherwise.  The
    # calibrated per-expert cap_live budget (an authoritative part of an
    # attached plan) slices the same way.
    em_arg = eplan.mor if use_mor else jnp.zeros((), dt)
    em_spec = P("model") if use_mor else P()
    has_cap = use_mor and eplan.cap_live is not None
    cap_arg = (jnp.broadcast_to(jnp.asarray(eplan.cap_live, jnp.float32),
                                (E,))
               if has_cap else jnp.zeros((), dt))
    cap_spec = P("model") if has_cap else P()
    y, lb = shard_map(
        body, mesh=mesh,
        in_specs=(P(dp_spec), P(), up_spec,
                  up_spec if glu else P(), down_spec, em_spec, cap_spec),
        out_specs=(P(dp_spec), P()),
        check_vma=False,
    )(xf, params["router"].astype(dt), params["w_up"].astype(dt),
      gate.astype(dt) if glu else jnp.zeros((), dt),
      params["w_down"].astype(dt), em_arg, cap_arg)
    aux = {"lb_loss": lb, "router_entropy": jnp.zeros((), jnp.float32)}
    return y.reshape(*lead, d), aux


def moe_apply(params: Dict, cfg: ModelConfig, x, *,
              mor=None, mor_mode: str = "dense",
              token_mask=None) -> Tuple[jnp.ndarray, Dict]:
    """x: (..., d) -> (y, aux).  aux carries the load-balance loss.

    ``token_mask`` (broadcastable to x's leading dims) marks REAL tokens:
    masked-out rows are excluded from routing entirely (their expert id
    is set to the out-of-range sentinel E, so they never claim capacity
    slots).  The serving engine's chunk steps pass their validity mask —
    without it, a co-scheduled slot's padding rows would flood an
    expert's capacity buffer and displace real tokens (capacity is
    assigned by token index, earlier wins)."""
    if cfg.expert_sharding == "ep_shmap" and token_mask is None:
        out = moe_apply_a2a(params, cfg, x, mor=mor, mor_mode=mor_mode)
        if out is not None:
            y, aux = out
            if cfg.n_shared_experts:
                ys, _ = mlp_apply(params["shared"], cfg,
                                  x.reshape(-1, x.shape[-1]),
                                  mor=mor, mor_mode=mor_mode)
                y = y + ys.reshape(y.shape)
            return y, aux
    dt = x.dtype
    lead = x.shape[:-1]
    d = x.shape[-1]
    xf = x.reshape(-1, d)
    T = xf.shape[0]
    E, k = cfg.n_experts, cfg.top_k
    f = cfg.moe_d_ff or cfg.d_ff
    if token_mask is not None and cfg.serve_expert_capacity > 0:
        # serving-shape-aware capacity (ROADMAP item): a serving chunk
        # dispatch provisions each expert for the dispatch shape itself.
        # Every token claims at most ONE slot per expert (top-k indices
        # are distinct), so C = serve_expert_capacity * T with the
        # default factor 1.0 can NEVER drop a valid token — chunked
        # prefill computes the exact (drop-free) MoE and matches the
        # teacher-forced logits instead of diverging by design whenever
        # an expert oversubscribed a small dispatch's cf*T*k/E budget.
        C = max(int(math.ceil(cfg.serve_expert_capacity * T)), 1)
    else:
        C = max(int(cfg.capacity_factor * T * k / E), 1)
    act_name = effective_activation(cfg)
    act = activation_fn(act_name)
    glu = "w_gate" in params

    logits = (xf @ params["router"].astype(dt)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_idx = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    if token_mask is not None:
        tm = jnp.broadcast_to(token_mask, lead).reshape(-1)
        # sentinel expert E: sorts last, drops from bincount/capacity,
        # and lands every masked (token, k) pair on the zero row
        top_idx = jnp.where(tm[:, None], top_idx, E)

    slot = _dispatch_indices(top_idx, E, C)             # (T, k)
    # dispatch = GATHER, not scatter-of-vectors: scattering (T*k, d) rows
    # into the expert buffer made GSPMD all-reduce a (T*k, d) f32 + u32
    # pair per layer (~16 GB/layer at 1M tokens).  Scatter only the int32
    # token ids into the slot map, then gather d-vectors.
    tok_src = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[:, None],
                               (T, k)).reshape(-1)
    slot_map = jnp.full((E * C + 1,), T, jnp.int32)
    slot_map = slot_map.at[slot.reshape(-1)].set(tok_src, mode="drop")
    xf_pad = jnp.concatenate([xf, jnp.zeros((1, d), dt)], 0)
    eb = jnp.take(xf_pad, slot_map[:E * C], axis=0).reshape(E, C, d)
    from repro.distributed.sharding_rules import constrain
    eb = constrain(eb, "expert_buf")
    h_kind = ("expert_hidden_ep" if cfg.expert_sharding == "ep"
              else "expert_hidden_tp")

    # per-expert FFN.  Expert-level MoR (tentpole, ISSUE 3): the stacked
    # expert MoRLayers run through ONE batched-expert execution plan —
    # the attached plan's own mode/tiling/cap_live is authoritative,
    # a bare stacked layer follows the caller's mor_mode exactly like
    # dense FFNs do (so "dense" skips ALL predictor work).  The router
    # itself already acts as the coarse zero predictor for the
    # (E - top_k) unrouted experts.
    from repro.core.executor import as_expert_plan
    em = mor.get("experts") if isinstance(mor, dict) else None
    eplan = as_expert_plan(em, mode=mor_mode, tile_m=cfg.mor.tile_m,
                           tile_n=cfg.mor.tile_n,
                           capacity_frac=cfg.mor.capacity)
    mor_stats = None
    if eplan.active and act_name in ("relu", "relu2", "relu_glu"):
        base_act = "relu" if act_name == "relu_glu" else act_name
        # buffer rows past an expert's routed count replicate xf_pad's
        # zero row; mark them dead so they never hold tiles live (their
        # outputs are never gathered back) and the per-(layer, expert)
        # liveness telemetry reflects real tokens only
        counts = jnp.bincount(top_idx.reshape(-1), length=E)
        row_valid = (jnp.arange(C, dtype=jnp.int32)[None, :]
                     < jnp.minimum(counts, C)[:, None])
        out_e, mor_stats = eplan.expert_ffn(
            eb, params["w_up"].astype(dt), params["w_down"].astype(dt),
            activation=base_act,
            w_gate=params["w_gate"].astype(dt) if glu else None,
            row_mask=row_valid)
        # anchor the expert outputs like the buffer inputs (the (E, C, f)
        # hidden-layout hint stays on the dense path only — the MoR
        # hidden lives inside the vmapped plan)
        out_e = constrain(out_e.astype(dt), "expert_buf")
    else:
        # dense path (einsum over the expert dim — shardable EP or TP)
        up = jnp.einsum("ecd,edf->ecf", eb, params["w_up"].astype(dt))
        if glu:
            g_pre = jnp.einsum("ecd,edf->ecf", eb,
                               params["w_gate"].astype(dt))
            h = (act(g_pre) * up).astype(dt)
        else:
            h = act(up).astype(dt)
        h = constrain(h, h_kind)
        out_e = jnp.einsum("ecf,efd->ecd", h, params["w_down"].astype(dt))
    out_flat = jnp.concatenate(
        [out_e.reshape(E * C, d), jnp.zeros((1, d), dt)], 0)

    # combine: gather each (token,k)'s result back, weight by router prob.
    # One (T, d) gather per routed expert k (unrolled, k is 2..6) keeps
    # the intermediate at (T, d) instead of materialising (T, k, d).
    y = jnp.zeros((T, d), dt)
    for kk in range(k):
        part = jnp.take(out_flat, slot[:, kk], axis=0)
        part = constrain(part, "ffn_in_2d")
        y = y + part * top_p[:, kk:kk + 1].astype(dt)

    if cfg.n_shared_experts:
        ys, _ = mlp_apply(params["shared"], cfg, xf, mor=mor,
                          mor_mode=mor_mode)
        y = y + ys

    # Switch-style load-balance aux loss.  bincount, NOT one_hot: a
    # (T, k, E) one-hot at 1M tokens x 160 experts is ~0.5 TB of f32.
    frac_routed = (jnp.zeros((E,), jnp.float32)
                   .at[top_idx.reshape(-1)].add(1.0, mode="drop")
                   / (T * k))
    mean_prob = probs.mean(0)
    aux = {"lb_loss": E * jnp.sum(frac_routed * mean_prob),
           "router_entropy": -jnp.mean(
               jnp.sum(probs * jnp.log(probs + 1e-9), -1))}
    if mor_stats is not None:
        # (E,)-shaped realised skip fractions per expert — stacked over
        # layers by the model scan into the per-(layer, expert) stats
        # the serving telemetry bins ("moe_mor_stats")
        aux["mor_stats"] = mor_stats
    return y.reshape(*lead, d), aux


def moe_taps(params: Dict, cfg: ModelConfig, x) -> Dict:
    """Calibration taps for the expert FFNs: per-expert (p_bin, p_base)
    of the gate (or up) pre-activation over ALL tokens.  Taps are
    routing-independent — expert dispatch merely subsamples the token
    distribution the fitted line models, so fitting on the full stream
    gives every expert the same estimator with more samples."""
    from repro.core.predictor import binary_preact
    x2 = x.reshape(-1, x.shape[-1])
    w = params.get("w_gate", params["w_up"])            # (E, d, f)
    p_base = jnp.einsum("td,edf->etf", x2.astype(jnp.float32),
                        w.astype(jnp.float32))
    p_bin = jax.vmap(lambda we: binary_preact(x2, we))(w)
    return {"p_bin": p_bin, "p_base": p_base}
