"""Offline MoR deployment: calibrate a trained model, cluster its ReLU
layers, fold the tile permutation into the weights, and emit the stacked
MoRLayer pytree the runtime consumes.

This is the paper's offline stage (§3.2) end-to-end:
  taps -> per-neuron (m, b, c) regression   [calibration.py]
  weights -> angle clusters -> proxies       [clustering.py]
  -> column permutation folded into w_gate/w_up (cols) + w_down (rows)
  -> MoRLayer pytree stacked over layers (scan-consumable)
"""
from __future__ import annotations

from typing import Callable, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.calibration import (finalize_regression, init_accumulator,
                                    update_accumulator)
from repro.core.clustering import cluster_layer
from repro.core.executor import MoRExecutionPlan
from repro.core.policy import build_mor_layer
from repro.obs.tracer import span


def _stack_mor(layers: List[Dict]) -> Dict:
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)


def attach_plans(mor, cfg: ModelConfig, mode: str,
                 capacities: Optional[Dict] = None,
                 draft_cap: Optional[float] = None):
    """Wrap calibrated MoR layers in per-layer execution plans.

    Replaces the old convention of threading bare ``(mor, mode, tile_m,
    tile_n)`` tuples through every call site: the plan carries the mode,
    tile geometry, and gather_matmul capacity from ``cfg.mor`` once, and
    the runtime (``masked_ffn`` / ``executor``) consumes it as-is.

    ``capacities`` (optional, {layer group -> (L,) fractions or scalar})
    attaches PER-LAYER calibrated gather_matmul capacities as the plan's
    traced ``cap_live`` leaf (``serving.telemetry.calibrate_capacity``'s
    output): a stacked plan rides through ``lax.scan`` with one static
    provisioning while every layer clamps to its own observed budget.

    ``draft_cap`` (optional scalar fraction) additionally stores the
    self-speculative draft budget on every plan (see
    ``executor.attach_draft_caps``); it stays dormant until the serving
    engine derives the draft twin with ``as_draft()``.

    Accepts the shapes the calibrators emit — a dict of stacked layer
    pytrees (``calibrate_lm``: plans ride through ``lax.scan`` because
    MoRExecutionPlan is a registered pytree with static aux config) or a
    list of per-layer MoRLayers (``calibrate_cnn`` / ``calibrate_tds``).
    """
    def wrap(layer, caps=None):
        if layer is None:
            return None
        if isinstance(layer, dict) and "experts" in layer:
            # expert-MoR group ({"experts": (L, E)-stacked MoRLayer}):
            # the plan wraps the stack whole; calibrated capacities come
            # back flat from the per-(layer, expert) telemetry and fold
            # to the stack's leading dims, so each scan step sees an
            # (E,)-row of per-expert budgets
            inner = layer["experts"]
            if isinstance(inner, MoRExecutionPlan):
                inner = inner.mor
            if inner is None:
                return {"experts": None}
            cap_live = None
            if caps is not None:
                cap_live = jnp.asarray(caps, jnp.float32)
                if cap_live.ndim > 0:
                    cap_live = cap_live.reshape(inner["m"].shape[:-1])
                else:
                    # scalar spec (serve --capacity): same budget for
                    # every (layer, expert) — broadcast so the stacked
                    # plan's scan/unroll can index its leading dims
                    cap_live = jnp.broadcast_to(cap_live,
                                                inner["m"].shape[:-1])
            return {"experts": MoRExecutionPlan(
                inner, mode=mode, tile_m=cfg.mor.tile_m,
                tile_n=cfg.mor.tile_n, capacity_frac=cfg.mor.capacity,
                cap_live=cap_live)}
        cap_live = None
        if caps is not None:
            cap_live = jnp.asarray(caps, jnp.float32)
            if cap_live.ndim > 0 and layer["m"].ndim == 1:
                # a single shared layer (hybrid) observed at several
                # call sites: provision for the worst of them
                cap_live = cap_live.max()
            elif cap_live.ndim == 0 and layer["m"].ndim > 1:
                # scalar spec (serve --capacity) on a stacked plan:
                # broadcast so scan/unroll can index the layer dim
                cap_live = jnp.broadcast_to(cap_live,
                                            layer["m"].shape[:1])
        return MoRExecutionPlan(layer, mode=mode, tile_m=cfg.mor.tile_m,
                                tile_n=cfg.mor.tile_n,
                                capacity_frac=cfg.mor.capacity,
                                cap_live=cap_live)

    if mor is None or mode == "dense":
        return mor
    if isinstance(mor, MoRExecutionPlan):
        out = mor
    elif isinstance(mor, list):
        out = [wrap(m) for m in mor]
    elif isinstance(mor, dict) and "enable" not in mor:
        caps = capacities or {}
        out = {k: wrap(v, caps.get(k)) for k, v in mor.items()}
    else:
        # bare single layer: only an unambiguous capacity spec is accepted
        caps = capacities
        if isinstance(caps, dict):
            assert len(caps) <= 1, \
                f"ambiguous capacities for a single MoR layer: {sorted(caps)}"
            caps = next(iter(caps.values())) if caps else None
        out = wrap(mor, caps)
    if draft_cap is not None:
        from repro.core.executor import attach_draft_caps
        out = attach_draft_caps(out, draft_cap)
    return out


def calibrate_lm(params: Dict, cfg: ModelConfig, forward: Callable,
                 batches: Iterator[Dict], n_batches: int,
                 layer_key: str = "layers") -> Tuple[Dict, Dict, Dict]:
    """Calibrate a scan-stacked LM (dense/ssm/audio families).

    -> (params with permuted FFN weights, mor pytree {layer_key: stacked},
        report dict with Pearson stats).  Spans (``repro.obs.tracer``): a
    ``calibrate`` root with ``calibrate.taps`` (the forward passes and
    the regression, ending when the host reads it back),
    ``calibrate.cluster`` (per-layer clustering and predictor build) and
    ``calibrate.permute`` (folding the permutations into the weights)."""
    L = cfg.n_layers
    with span("calibrate", layers=L, batches=n_batches):
        # locate the target weight stack: mlp (w_gate|w_up) or rwkv cm
        # w_up
        lp = params[layer_key]
        if "mlp" in lp:
            w_stack = lp["mlp"].get("w_gate", lp["mlp"]["w_up"])
        else:
            w_stack = lp["cm"]["w_up"]
        N = w_stack.shape[-1]

        # the jitted forward passes and accumulator, up to the host
        # reading the regression back (which waits for the device)
        with span("calibrate.taps"):
            acc = jax.vmap(lambda _: init_accumulator(N))(jnp.arange(L))
            upd = jax.jit(jax.vmap(update_accumulator))
            fwd = jax.jit(lambda p, b: forward(p, cfg, b,
                                               with_taps=True)[1]["taps"])
            seen = 0
            for batch in batches:
                taps = fwd(params, batch)
                acc = upd(acc, taps["p_bin"], taps["p_base"])
                seen += 1
                if seen >= n_batches:
                    break
            m, b, c = jax.vmap(finalize_regression)(acc)
            m, b, c = np.asarray(m), np.asarray(b), np.asarray(c)

        with span("calibrate.cluster"):
            mor_layers = []
            w_np = np.asarray(w_stack, np.float32)
            for l in range(L):
                cl = cluster_layer(w_np[l], cfg.mor.max_cluster_angle)
                mor_layers.append(build_mor_layer(m[l], b[l], c[l], cl,
                                                  cfg.mor))
            mor_stack = _stack_mor(mor_layers)

        # fold permutations into the weights (offline, zero runtime cost)
        with span("calibrate.permute"):
            perm = np.asarray(mor_stack["perm"])          # (L, N)
            new_params = jax.tree_util.tree_map(lambda x: x, params)

            def permute_stack(w, axis):
                w = np.asarray(w)
                out = np.empty_like(w)
                for l in range(L):
                    idx = perm[l]
                    out[l] = np.take(w[l], idx, axis=axis - 1)
                return jnp.asarray(out)

            if "mlp" in lp:
                mlp = dict(lp["mlp"])
                if "w_gate" in mlp:
                    mlp["w_gate"] = permute_stack(mlp["w_gate"], 2)
                mlp["w_up"] = permute_stack(mlp["w_up"], 2)
                mlp["w_down"] = permute_stack(mlp["w_down"], 1)
                new_lp = dict(lp)
                new_lp["mlp"] = mlp
                new_params[layer_key] = new_lp
            else:
                cm = dict(lp["cm"])
                cm["w_up"] = permute_stack(cm["w_up"], 2)
                cm["w_down"] = permute_stack(cm["w_down"], 1)
                new_lp = dict(lp)
                new_lp["cm"] = cm
                new_params[layer_key] = new_lp

        report = {
            "pearson_mean": float(c.mean()),
            "pearson_frac_above_T": float(
                (c > cfg.mor.corr_threshold).mean()),
            "n_proxies_mean": float(np.mean([
                len(np.unique(np.asarray(ml["proxy_slot"])))
                for ml in mor_layers])),
            "enabled_frac": float(np.asarray(mor_stack["enable"]).mean()),
        }
    return new_params, {layer_key: mor_stack}, report


def calibrate_hybrid(params: Dict, cfg: ModelConfig, forward: Callable,
                     batches: Iterator[Dict], n_batches: int
                     ) -> Tuple[Dict, Dict, Dict]:
    """Calibrate a hybrid (mamba + shared-attention) model.

    The ONE shared block's MLP is the only ReLU-family FFN; it is
    observed at every segment boundary, so its taps come back
    (n_seg, ...)-stacked and the segment axis folds into the batch —
    one regression, one clustering pass, one MoRLayer under the
    ``"shared"`` key (which is where the runtime looks:
    ``mor.get("shared")`` in the hybrid forward / chunk paths and
    ``telemetry.mor_group_map``)."""
    mlp = params["shared"]["mlp"]
    w = mlp["w_gate"] if "w_gate" in mlp else mlp["w_up"]
    N = w.shape[-1]
    acc = init_accumulator(N)
    upd = jax.jit(update_accumulator)
    fwd = jax.jit(lambda p, b: forward(p, cfg, b, with_taps=True)[1]["taps"])
    seen = 0
    for batch in batches:
        taps = fwd(params, batch)
        acc = upd(acc, taps["p_bin"], taps["p_base"])
        seen += 1
        if seen >= n_batches:
            break
    m, b, c = finalize_regression(acc)
    m, b, c = np.asarray(m), np.asarray(b), np.asarray(c)
    cl = cluster_layer(np.asarray(w, np.float32),
                       cfg.mor.max_cluster_angle)
    ml = build_mor_layer(m, b, c, cl, cfg.mor)

    # fold the permutation into the shared MLP weights (offline)
    perm = np.asarray(ml["perm"])
    mlp2 = dict(mlp)
    if "w_gate" in mlp2:
        mlp2["w_gate"] = jnp.asarray(
            np.take(np.asarray(mlp2["w_gate"]), perm, axis=1))
    mlp2["w_up"] = jnp.asarray(
        np.take(np.asarray(mlp2["w_up"]), perm, axis=1))
    mlp2["w_down"] = jnp.asarray(
        np.take(np.asarray(mlp2["w_down"]), perm, axis=0))
    new_params = jax.tree_util.tree_map(lambda x: x, params)
    new_params["shared"] = dict(params["shared"], mlp=mlp2)

    report = {
        "pearson_mean": float(c.mean()),
        "pearson_frac_above_T": float((c > cfg.mor.corr_threshold).mean()),
        "n_proxies_mean": float(
            len(np.unique(np.asarray(ml["proxy_slot"])))),
        "enabled_frac": float(np.asarray(ml["enable"]).mean()),
    }
    return new_params, {"shared": ml}, report


def calibrate_moe(params: Dict, cfg: ModelConfig, forward: Callable,
                  batches: Iterator[Dict], n_batches: int, *,
                  cluster_experts: bool = True,
                  inject_dead_frac: float = 0.0,
                  inject_scale: float = 4.0) -> Tuple[Dict, Dict, Dict]:
    """Calibrate a scan-stacked MoE LM end to end.

    The leading dense layers get the ``calibrate_lm`` treatment
    (regression + clustering + permutation folded into the mlp weights);
    every (layer, expert) FFN additionally gets its own hybrid predictor
    fitted from routing-independent taps (``moe_taps``: each expert is
    evaluated over the FULL token stream its dispatch subsamples, so all
    E regressions share one forward pass per batch).

    ``cluster_experts=False`` builds binary-rookie-only expert layers
    (identity permutation, no proxies) — no per-expert weight
    permutation, at the cost of the spatial predictor.

    ``inject_dead_frac`` > 0 emulates a trained model's column-skewed
    ReLU sparsity (paper Fig. 1: real DNNs zero 50-90% of ReLU outputs,
    concentrated in persistently-dead neurons) on a random-init model:
    the trailing fraction of each expert's (permuted) columns gets a
    folded bias of ``-inject_scale`` observed pre-activation sigmas.
    The bias is part of the deployed model (bn_bias — exact mode zeroes
    the same neurons), so predictor and truth agree; benchmark scenarios
    use it to exercise tile skipping end to end, since random-init
    weights have no structured sparsity (measured frac_tiles_live = 1.0).

    -> (params with permuted weights,
        {"dense_layers"?: stacked MoRLayer,
         "moe_layers": {"experts": (L_moe, E)-stacked MoRLayer}},
        report)."""
    assert cfg.family == "moe"
    L_d = cfg.first_k_dense
    L_m = cfg.n_layers - L_d
    E = cfg.n_experts
    f = cfg.moe_d_ff or cfg.d_ff

    acc_e = jax.vmap(jax.vmap(lambda _: init_accumulator(f)))(
        jnp.zeros((L_m, E)))
    upd_e = jax.jit(jax.vmap(jax.vmap(update_accumulator)))
    acc_d = None
    if L_d:
        N_d = params["dense_layers"]["mlp"].get(
            "w_gate", params["dense_layers"]["mlp"]["w_up"]).shape[-1]
        acc_d = jax.vmap(lambda _: init_accumulator(N_d))(jnp.arange(L_d))
        upd_d = jax.jit(jax.vmap(update_accumulator))
    fwd = jax.jit(lambda p, b: forward(p, cfg, b, with_taps=True)[1])
    seen = 0
    for batch in batches:
        aux = fwd(params, batch)
        taps = aux["taps"]                        # (L_m, E, T, f)
        acc_e = upd_e(acc_e, taps["p_bin"], taps["p_base"])
        if L_d:
            acc_d = upd_d(acc_d, aux["dense_taps"]["p_bin"],
                          aux["dense_taps"]["p_base"])
        seen += 1
        if seen >= n_batches:
            break
    m, b, c = jax.vmap(jax.vmap(finalize_regression))(acc_e)
    m, b, c = np.asarray(m), np.asarray(b), np.asarray(c)
    # observed per-column base pre-activation sigma (for injection)
    n = np.maximum(np.asarray(acc_e["count"]), 1.0)[..., None]
    sig = np.sqrt(np.maximum(
        np.asarray(acc_e["syy"]) / n
        - (np.asarray(acc_e["sy"]) / n) ** 2, 0.0))

    new_params = jax.tree_util.tree_map(lambda x: x, params)  # shallow copy
    mp = dict(params["moe_layers"])
    moe_p = dict(mp["moe"])
    glu = "w_gate" in moe_p
    w_np = np.asarray(moe_p.get("w_gate", moe_p["w_up"]), np.float32)
    tn = min(cfg.mor.tile_n, f)
    n_dead = 0
    if inject_dead_frac > 0:
        # whole trailing column-tiles so deadness is tile-resolvable
        n_dead = max(int(inject_dead_frac * f) // tn * tn, tn)
        n_dead = min(n_dead, f - tn)              # keep a live leading tile

    w_gate_new = np.array(moe_p["w_gate"], np.float32) if glu else None
    w_up_new = np.array(moe_p["w_up"], np.float32)
    w_down_new = np.array(moe_p["w_down"], np.float32)
    layer_stacks = []
    for l in range(L_m):
        per_expert = []
        for e in range(E):
            cl = (cluster_layer(w_np[l, e], cfg.mor.max_cluster_angle)
                  if cluster_experts else None)
            ml = build_mor_layer(m[l, e], b[l, e], c[l, e], cl, cfg.mor)
            perm = np.asarray(ml["perm"])
            if cluster_experts:
                if glu:
                    w_gate_new[l, e] = w_gate_new[l, e][:, perm]
                w_up_new[l, e] = w_up_new[l, e][:, perm]
                w_down_new[l, e] = w_down_new[l, e][perm, :]
            if n_dead:
                bias = np.asarray(ml["bn_bias"]).copy()
                bias[f - n_dead:] -= inject_scale * sig[l, e][perm][
                    f - n_dead:]
                ml["bn_bias"] = jnp.asarray(bias, jnp.float32)
                # a column whose folded bias exceeds its dynamic range is
                # statically dead — enabling its rookie is always safe
                en = np.asarray(ml["enable"]).copy()
                en[f - n_dead:] = True
                ml["enable"] = jnp.asarray(en)
            per_expert.append(ml)
        layer_stacks.append(_stack_mor(per_expert))
    experts_stack = _stack_mor(layer_stacks)      # leaves (L_m, E, ...)
    if cluster_experts or n_dead:
        if glu:
            moe_p["w_gate"] = jnp.asarray(w_gate_new)
        moe_p["w_up"] = jnp.asarray(w_up_new)
        moe_p["w_down"] = jnp.asarray(w_down_new)
        mp["moe"] = moe_p
        new_params["moe_layers"] = mp

    mor: Dict = {"moe_layers": {"experts": experts_stack}}
    report = {
        "pearson_mean": float(c.mean()),
        "pearson_frac_above_T": float((c > cfg.mor.corr_threshold).mean()),
        "enabled_frac": float(np.asarray(experts_stack["enable"]).mean()),
        "injected_dead_cols": int(n_dead),
    }

    if L_d:
        md, bd, cd = jax.vmap(finalize_regression)(acc_d)
        md, bd, cd = np.asarray(md), np.asarray(bd), np.asarray(cd)
        lp = params["dense_layers"]
        wd_np = np.asarray(lp["mlp"].get("w_gate", lp["mlp"]["w_up"]),
                           np.float32)
        dense_layers = []
        for l in range(L_d):
            cl = cluster_layer(wd_np[l], cfg.mor.max_cluster_angle)
            dense_layers.append(build_mor_layer(md[l], bd[l], cd[l], cl,
                                                cfg.mor))
        dense_stack = _stack_mor(dense_layers)
        perm = np.asarray(dense_stack["perm"])

        def permute_stack(w, axis):
            w = np.asarray(w)
            out = np.empty_like(w)
            for l in range(L_d):
                out[l] = np.take(w[l], perm[l], axis=axis - 1)
            return jnp.asarray(out)

        mlp = dict(lp["mlp"])
        if "w_gate" in mlp:
            mlp["w_gate"] = permute_stack(mlp["w_gate"], 2)
        mlp["w_up"] = permute_stack(mlp["w_up"], 2)
        mlp["w_down"] = permute_stack(mlp["w_down"], 1)
        new_lp = dict(lp)
        new_lp["mlp"] = mlp
        new_params["dense_layers"] = new_lp
        mor["dense_layers"] = dense_stack
        report["dense_pearson_mean"] = float(cd.mean())
    return new_params, mor, report


def calibrate_cnn(params: Dict, state: Dict, cfg: ModelConfig,
                  forward: Callable, batches: Iterator[Dict],
                  n_batches: int) -> Tuple[List, Dict]:
    """Calibrate the paper's CNNs (per-conv-layer MoR with BN folding).
    -> (mor list aligned with conv layers, report)."""
    from repro.models.cnn import bn_fold, layer_weight_matrices
    n_layers = len(params["layers"])
    accs = [init_accumulator(lp["w"].shape[-1]) for lp in params["layers"]]
    upd = jax.jit(update_accumulator)
    fwd = jax.jit(lambda p, s, im: forward(p, s, cfg, im, train=False,
                                           with_taps=True))
    seen = 0
    for batch in batches:
        _, _, aux = fwd(params, state, batch["images"])
        for i, tap in enumerate(aux["taps"]):
            accs[i] = upd(accs[i], tap["p_bin"], tap["p_base"])
        seen += 1
        if seen >= n_batches:
            break
    mors = []
    cs = []
    for i, lp in enumerate(params["layers"]):
        m, b, c = finalize_regression(accs[i])
        w = np.asarray(lp["w"].reshape(-1, lp["w"].shape[-1]), np.float32)
        cl = cluster_layer(w, cfg.mor.max_cluster_angle)
        bn_s = bn_b = None
        if cfg.batchnorm:
            s, bias = bn_fold(lp["bn"], state["bn"][i])
            bn_s, bn_b = np.asarray(s), np.asarray(bias)
        mors.append(build_mor_layer(np.asarray(m), np.asarray(b),
                                    np.asarray(c), cl, cfg.mor,
                                    bn_scale=bn_s, bn_bias=bn_b))
        cs.append(np.asarray(c))
    report = {
        "pearson_mean": float(np.mean([c.mean() for c in cs])),
        "pearson_per_layer": [float(c.mean()) for c in cs],
        "enabled_frac": float(np.mean(
            [np.asarray(m["enable"]).mean() for m in mors])),
    }
    return mors, report


def calibrate_tds(params: Dict, cfg: ModelConfig, forward: Callable,
                  batches: Iterator[Dict], n_batches: int
                  ) -> Tuple[List, Dict]:
    """Calibrate TDS FC1 layers (taps alternate conv/fc — fc are odd)."""
    n_layers = len(params["layers"])
    accs = [init_accumulator(cfg.d_ff) for _ in range(n_layers)]
    upd = jax.jit(update_accumulator)
    fwd = jax.jit(lambda p, b: forward(p, cfg, b, with_taps=True))
    seen = 0
    for batch in batches:
        _, aux = fwd(params, batch)
        fc_taps = aux["taps"][1::2]       # conv tap, fc tap per layer
        for i, tap in enumerate(fc_taps):
            accs[i] = upd(accs[i], tap["p_bin"], tap["p_base"])
        seen += 1
        if seen >= n_batches:
            break
    mors = []
    for i, lp in enumerate(params["layers"]):
        m, b, c = finalize_regression(accs[i])
        w = np.asarray(lp["fc1"], np.float32)
        cl = cluster_layer(w, cfg.mor.max_cluster_angle)
        # the FC bias folds into the predictor's affine term
        mors.append(build_mor_layer(
            np.asarray(m), np.asarray(b), np.asarray(c), cl, cfg.mor,
            bn_bias=np.asarray(lp["fc1_b"])))
    report = {"pearson_mean": float(np.mean(
        [np.asarray(finalize_regression(a)[2]).mean() for a in accs]))}
    return mors, report
