"""MoR execution plans: one predictor pass per layer call, reused
everywhere downstream.

The paper's speedup model (§4.1) runs the cheap binCU predictor strictly
ahead of the heavy compute — once.  ``MoRExecutionPlan`` is the runtime
embodiment of that contract: a per-layer, compile-once bundle of
(MoRLayer, mode, tile geometry, capacity) whose ``predict`` method
produces a single :class:`MoRPrediction`, and whose matmul helpers all
consume that one prediction.  The GLU path in particular threads one
tile mask through the gate matmul, the up-projection, AND the
down-projection row skip — three savings from one predictor evaluation.

Execution modes (see ``core/masked_ffn.py`` for the thin dispatcher):

  dense  — plain matmul, predictor off.
  exact  — full compute, then zero the neurons the hybrid predictor
           would have skipped (bit-identical to the paper's accelerator
           output; accuracy-evaluation mode).
  tiled  — tile-granular skipping semantics in pure jnp: the oracle for
           the Pallas kernels.
  kernel — Pallas fast path: the fused ``kernels.ops.mor_tile_mask``
           predictor (binary rookie int8 matmul + fitted line + proxy
           AND, reduced to tile liveness in one kernel) feeds
           ``gather_matmul``, which only DMAs live weight tiles, under a
           static ``capacity`` budget; the down-projection skips dead
           contraction blocks via ``masked_matmul_kdim``.
  shadow — the dense-oracle scoring twin (predictor-quality
           observability): PROPAGATES the plain dense activations (so a
           shadow forward is the reference computation, bit-for-bit the
           dense path) while evaluating the predictor alongside and
           scoring its tile decisions against the dense truth —
           false-skip / false-keep tile counts, neuron sign agreement,
           and the output-error norm the skips would have caused land
           in the stats dict as ``shadow_*`` leaves.  The serving
           engine samples 1-in-N dispatches through ``as_shadow()``
           twins of the active plans and drains the scores through the
           device metrics block.

Plans are registered pytrees: the MoRLayer is the only child, the mode /
tile / capacity knobs are static aux data.  A plan built from a stacked
(L-leading) MoRLayer pytree can therefore ride through ``jax.lax.scan``
— each scan step sees a per-layer plan with identical static config,
which is exactly how ``deploy.attach_plans`` wires calibrated models.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.predictor import MoRLayer, hybrid_predict
from repro.core.policy import expand_tile_mask, tile_mask_from_neuron_mask

MODES = ("dense", "exact", "tiled", "kernel", "shadow", "scored")

# per-layer predictor-quality leaves the shadow mode adds to its stats
# dict (int tile counters + f32 fractions; the obs device block packs
# them into its quality lanes)
SHADOW_STAT_KEYS = ("shadow_tiles", "shadow_false_skip",
                    "shadow_false_keep", "shadow_truth_live",
                    "shadow_sign_agree", "shadow_err")


def _act(h, activation: str):
    if activation == "relu":
        return jax.nn.relu(h)
    if activation == "relu2":
        return jnp.square(jax.nn.relu(h))
    raise ValueError(f"MoR requires a ReLU-family activation, got {activation!r}")


def _dense_stats(shadow: bool = False) -> Dict[str, jax.Array]:
    z = jnp.zeros((), jnp.float32)
    zi = jnp.zeros((), jnp.int32)
    out = {"frac_computed": jnp.ones((), jnp.float32),
           "frac_tiles_live": jnp.ones((), jnp.float32),
           "frac_tiles_computed": jnp.ones((), jnp.float32),
           "frac_blocks_issued": jnp.ones((), jnp.float32),
           "frac_mispredicted_zero": z,
           # integer tile counters (obs device-metrics lanes); dense
           # has no tile grid, so both are zero — the keyset still has
           # to match MoRPrediction.stats() for per-layer stacking
           "n_tiles": zi, "tiles_skipped": zi}
    if shadow:
        # inactive layers inside a shadow-mode stack score nothing but
        # must emit the same keyset so per-layer stacking stays regular
        out.update(_zero_shadow_stats())
    return out


def _zero_shadow_stats() -> Dict[str, jax.Array]:
    z = jnp.zeros((), jnp.float32)
    zi = jnp.zeros((), jnp.int32)
    return {"shadow_tiles": zi, "shadow_false_skip": zi,
            "shadow_false_keep": zi, "shadow_truth_live": zi,
            "shadow_sign_agree": z, "shadow_err": z}


class MoRPrediction:
    """The result of ONE predictor pass, shared by every consumer.

    ``computed``: (T, N) bool neuron mask, or None in kernel mode (the
    fused kernel reduces straight to tiles without materialising it).
    ``tiles``: (T/tile_m, N/tile_n) bool tile-liveness mask.
    ``kept``: tiles actually computed under the capacity budget (equals
    ``tiles`` when capacity covers every live tile).
    ``kernel_counts``: (n_live, n_computed) tile counters reported by
    ``gather_matmul`` itself in kernel mode — the authoritative source
    for the serving telemetry's realised-skip stats on that path.
    ``block_counts``: int32 [weight blocks issued, weight blocks], summed
    over the kernel-mode matmuls that consumed this prediction (the
    kernels block many tiles; a block is skipped only when all are
    dead)."""

    __slots__ = ("computed", "tiles", "kept", "kernel_counts",
                 "block_counts")

    def __init__(self, computed: Optional[jax.Array], tiles: jax.Array,
                 kept: Optional[jax.Array] = None):
        self.computed = computed
        self.tiles = tiles
        self.kept = tiles if kept is None else kept
        self.kernel_counts = None
        self.block_counts = None

    def note_blocks(self, n_blocks: jax.Array) -> None:
        self.block_counts = (n_blocks if self.block_counts is None
                             else self.block_counts + n_blocks)

    def frac_blocks_issued(self) -> jax.Array:
        """Share of the kernels' weight blocks DMA'd and multiplied (1.0
        outside kernel mode: the dense matmul touches every block)."""
        if self.block_counts is None:
            return jnp.ones((), jnp.float32)
        return (self.block_counts[0].astype(jnp.float32)
                / self.block_counts[1].astype(jnp.float32))

    def keep_mask(self, T: int, N: int, tile_m: int, tile_n: int):
        return expand_tile_mask(self.kept, tile_m, tile_n, T, N)

    def stats(self) -> Dict[str, jax.Array]:
        n_tiles = float(self.tiles.size)
        if self.kernel_counts is not None:
            n_live, n_comp = self.kernel_counts
            tiles_live = n_live.astype(jnp.float32) / n_tiles
            tiles_computed = n_comp.astype(jnp.float32) / n_tiles
            n_computed = n_comp.astype(jnp.int32)
        else:
            tiles_live = self.tiles.mean(dtype=jnp.float32)
            # realised compute after the capacity clamp — the number the
            # serving telemetry compares against the demand
            tiles_computed = self.kept.mean(dtype=jnp.float32)
            n_computed = self.kept.sum(dtype=jnp.int32)
        if self.computed is not None:
            frac_computed = self.computed.mean(dtype=jnp.float32)
        else:
            # kernel mode: the neuron mask never exists; report the
            # tile-level compute fraction (its tight upper bound).
            frac_computed = tiles_live
        n_tiles_i = jnp.asarray(int(n_tiles), jnp.int32)
        return {"frac_computed": frac_computed,
                "frac_tiles_live": tiles_live,
                "frac_tiles_computed": tiles_computed,
                "frac_blocks_issued": self.frac_blocks_issued(),
                "frac_mispredicted_zero": jnp.zeros((), jnp.float32),
                # exact integer tile counters for the obs device block
                "n_tiles": n_tiles_i,
                "tiles_skipped": n_tiles_i - n_computed}


@jax.tree_util.register_pytree_node_class
class MoRExecutionPlan:
    """Per-layer, compile-once MoR execution plan.

    Pytree contract: ``mor`` (a MoRLayer dict pytree, possibly stacked
    over layers, possibly None) and ``cap_live`` (optional TRACED
    per-layer capacity fraction, possibly (L,)-stacked) are the
    children; ``mode``/``tile_m``/``tile_n``/``capacity_frac`` are
    static aux data, so plans survive ``tree_map``, ``lax.scan``
    slicing, and jit boundaries unchanged.

    ``capacity_frac`` (static) provisions the gather_matmul slot list —
    one compiled body for a whole layer scan.  ``cap_live`` (traced) is
    the telemetry-calibrated PER-LAYER budget clamped under it
    (``serving.telemetry.calibrate_capacity``): updating its values
    re-provisions every layer without recompiling the serving step.

    ``draft_cap`` (traced, optional) is a SECOND capacity budget for
    self-speculative decoding: the same weights/predictor with a much
    harsher clamp act as the draft model.  The static ``draft`` flag
    selects which budget is active — draft=True plans read ``draft_cap``
    where target plans read ``cap_live`` — so the serving engine
    compiles exactly two step executables (target + draft treedefs) and
    sweeping draft_cap VALUES never recompiles either.
    """

    def __init__(self, mor: Optional[MoRLayer], *, mode: str = "dense",
                 tile_m: int = 8, tile_n: int = 128,
                 capacity_frac: float = 1.0, cap_live=None,
                 draft_cap=None, draft: bool = False):
        if mode not in MODES:
            raise ValueError(f"unknown MoR mode {mode!r}")
        self.mor = mor
        self.mode = mode
        self.tile_m = tile_m
        self.tile_n = tile_n
        self.capacity_frac = capacity_frac
        self.cap_live = cap_live
        self.draft_cap = draft_cap
        self.draft = draft

    # -- pytree plumbing ---------------------------------------------------
    def tree_flatten(self):
        return ((self.mor, self.cap_live, self.draft_cap),
                (self.mode, self.tile_m, self.tile_n, self.capacity_frac,
                 self.draft))

    @classmethod
    def tree_unflatten(cls, aux, children):
        mode, tile_m, tile_n, capacity_frac, draft = aux
        return cls(children[0], mode=mode, tile_m=tile_m, tile_n=tile_n,
                   capacity_frac=capacity_frac, cap_live=children[1],
                   draft_cap=children[2], draft=draft)

    def __repr__(self):
        return (f"MoRExecutionPlan(mode={self.mode!r}, tile_m={self.tile_m},"
                f" tile_n={self.tile_n}, capacity_frac={self.capacity_frac},"
                f" calibrated={self.mor is not None},"
                f" per_layer_capacity={self.cap_live is not None},"
                f" draft={self.draft})")

    def as_draft(self, draft_cap=None) -> "MoRExecutionPlan":
        """The draft-mode twin of this plan: same weights and leaves,
        ``draft=True`` so ``draft_cap`` becomes the active budget.  When
        ``draft_cap`` is given it replaces the stored leaf (scalar or
        per-layer, broadcastable like ``cap_live``)."""
        dc = self.draft_cap if draft_cap is None else draft_cap
        return MoRExecutionPlan(
            self.mor, mode=self.mode, tile_m=self.tile_m, tile_n=self.tile_n,
            capacity_frac=self.capacity_frac, cap_live=self.cap_live,
            draft_cap=dc, draft=True)

    def as_shadow(self) -> "MoRExecutionPlan":
        """The dense-oracle scoring twin of this plan: same leaves and
        capacity budgets, ``mode="shadow"`` so the forward propagates
        plain dense activations while scoring the predictor's decisions
        against them.  Uncalibrated plans pass through unchanged (there
        is no predictor to score)."""
        if self.mor is None:
            return self
        return MoRExecutionPlan(
            self.mor, mode="shadow", tile_m=self.tile_m,
            tile_n=self.tile_n, capacity_frac=self.capacity_frac,
            cap_live=self.cap_live, draft_cap=self.draft_cap,
            draft=self.draft)

    def as_scored(self) -> "MoRExecutionPlan":
        """The IN-STEP scoring twin of a TILED plan: same dense-oracle
        scoring as ``as_shadow()``, but the forward propagates the
        tile-MASKED activations — bitwise identical to what the tiled
        plan computes, because tiled mode itself evaluates the dense
        matmul and selects (``masked_matmul``).  A scored dispatch can
        therefore REPLACE the primary tiled dispatch outright: one
        forward, tokens unchanged, and the only extra work is the
        elementwise truth/score arithmetic — this is what keeps the
        sampled-scoring overhead a few percent instead of a whole
        second forward.  Only valid as a stand-in for ``tiled`` plans
        (kernel's gather matmul may reassociate accumulation; exact
        mode is neuron- not tile-granular)."""
        if self.mor is None:
            return self
        assert self.mode in ("tiled", "scored"), \
            f"as_scored() replaces tiled plans only, not {self.mode!r}"
        return MoRExecutionPlan(
            self.mor, mode="scored", tile_m=self.tile_m,
            tile_n=self.tile_n, capacity_frac=self.capacity_frac,
            cap_live=self.cap_live, draft_cap=self.draft_cap,
            draft=self.draft)

    # -- predicates --------------------------------------------------------
    @property
    def active(self) -> bool:
        """True when the predictor actually runs (calibrated + not dense)."""
        return self.mor is not None and self.mode != "dense"

    @property
    def _active_cap(self):
        """The traced capacity budget in force: ``draft_cap`` when this
        plan runs as the speculative drafter, ``cap_live`` otherwise."""
        if self.draft and self.draft_cap is not None:
            return self.draft_cap
        return self.cap_live

    # -- the single predictor pass -----------------------------------------
    def predict(self, x: jax.Array, w: jax.Array, *,
                preact_full: Optional[jax.Array] = None,
                residual: Optional[jax.Array] = None,
                row_mask: Optional[jax.Array] = None) -> MoRPrediction:
        """Run the hybrid predictor exactly once -> MoRPrediction.

        ``kernel`` mode routes through the fused Pallas
        ``kernels.ops.mor_tile_mask`` (binary rookie + fitted line +
        proxy AND + tile reduction in one pass over the activations);
        every other mode uses the pure-jnp ``hybrid_predict`` oracle.

        ``row_mask`` (optional (T,) bool, True = real row) force-skips
        dead input rows: MoE expert buffers are capacity-padded with the
        zero row, and without the mask those rows can mark tiles live
        (the fitted intercept alone may predict non-zero at x = 0) and
        pollute the per-expert liveness telemetry the capacity
        calibration reads.  Masked rows use the kernel's forced-skip
        sentinel (proxy state 2), the same mechanism as shape padding.
        """
        assert self.active, "predict() on an inactive plan"
        mor = self.mor
        if self.mode == "kernel" and preact_full is None:
            from repro.kernels import ops as kops
            # proxy rookie at base precision (only the unique proxy
            # columns are touched; they live in the always-computed
            # leading tiles of the permuted layout)
            slot = jnp.maximum(mor["proxy_slot"], 0)
            proxy_cols = jnp.take(w, slot, axis=1)
            proxy_pre = jax.lax.dot_general(
                x, proxy_cols, (((x.ndim - 1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            proxy_relu_in = (proxy_pre * mor["bn_scale"][slot]
                             + mor["bn_bias"][slot])
            if residual is not None:
                proxy_relu_in = proxy_relu_in + jnp.take(
                    residual.astype(jnp.float32), slot, axis=-1)
            proxy_neg = (proxy_relu_in < 0.0) | (mor["proxy_slot"] < 0)
            pn = proxy_neg.astype(jnp.int8)
            if row_mask is not None:
                pn = jnp.where(row_mask[:, None], pn, jnp.int8(2))
            # proxies themselves are always computed: fold ~is_proxy into
            # the kernel's enable row
            mor_eff = dict(mor)
            mor_eff["enable"] = mor["enable"] & ~mor["is_proxy"]
            tiles = kops.mor_tile_mask(x, w, mor_eff, pn,
                                       residual=residual,
                                       tile_m=self.tile_m, tile_n=self.tile_n)
            return MoRPrediction(None, tiles,
                                 kept=self._capacity_clip(tiles))
        computed = hybrid_predict(x, w, mor, preact_full=preact_full,
                                  residual=residual)
        if row_mask is not None:
            computed = computed & row_mask[..., None]
        tiles = tile_mask_from_neuron_mask(
            computed.reshape(-1, computed.shape[-1]), self.tile_m, self.tile_n)
        # shadow mode mirrors whatever clip the active plan would apply
        # (identity when uncapped), so its scored `kept` mask equals the
        # tiled/kernel decision it shadows
        kept = (self._capacity_clip(tiles)
                if self.mode in ("kernel", "shadow", "scored")
                or self._active_cap is not None
                else None)
        return MoRPrediction(computed, tiles, kept=kept)

    def _capacity_clip(self, tiles: jax.Array) -> jax.Array:
        """Capacity truncation mirroring gather_matmul's slot list: only
        the first ``capacity`` live tiles (row-major) are computed.  The
        static ``capacity_frac`` provisions; the traced ``cap_live``
        (per-layer calibrated fraction) clamps under it."""
        cap_live = self._active_cap
        if self.capacity_frac >= 1.0 and cap_live is None:
            return tiles
        n_tiles = tiles.shape[0] * tiles.shape[1]
        capacity = jnp.asarray(max(1, int(self.capacity_frac * n_tiles)),
                               jnp.int32)
        if cap_live is not None:
            capacity = jnp.minimum(capacity, jnp.maximum(1, jnp.ceil(
                jnp.asarray(cap_live, jnp.float32) * n_tiles)
            ).astype(jnp.int32))
        flat = tiles.reshape(-1)
        live_rank = jnp.cumsum(flat) - 1
        return (flat & (live_rank < capacity)).reshape(tiles.shape)

    # -- mask-consuming matmuls --------------------------------------------
    def masked_matmul(self, x: jax.Array, w: jax.Array,
                      pred: MoRPrediction) -> jax.Array:
        """x @ w with ``pred``'s tile mask applied — dead tiles are exact
        zeros.  kernel mode DMAs only live tiles (gather_matmul);
        tiled/exact modes compute densely and select (the jnp oracle).
        Returns float32 pre-activations."""
        T, N = x.shape[0], w.shape[1]
        if self.mode == "kernel":
            from repro.kernels import ops as kops
            # gather_matmul already selects dead/overflow tiles to exact
            # zero internally (same capacity-clipped mask as pred.kept);
            # re-applying the keep mask here would be a redundant (T, N)
            # expansion + select on the serving hot path
            pre, n_live, n_comp, n_blocks = kops.gather_matmul(
                x, w, pred.tiles, capacity_frac=self.capacity_frac,
                capacity_frac_live=self._active_cap, tile_m=self.tile_m,
                tile_n=self.tile_n, with_counts=True)
            # the kernel's own counters feed the serving telemetry
            pred.kernel_counts = (n_live, n_comp)
            pred.note_blocks(n_blocks)
            return pre.astype(jnp.float32)
        pre = (x @ w).astype(jnp.float32)
        keep = pred.keep_mask(T, N, self.tile_m, self.tile_n)
        return jnp.where(keep, pre, 0.0)

    def down_matmul(self, h: jax.Array, w_down: jax.Array,
                    pred: Optional[MoRPrediction]) -> jax.Array:
        """h @ w_down with dead hidden tiles skipped along the CONTRACTION
        dim (the paper's 3x GLU saving: a dead gate tile kills the
        matching up column and down row).  Dead h tiles are exact zeros,
        so the skip is numerically exact.  kernel mode uses the
        contraction-masked Pallas kernel; other modes rely on the zeros
        (XLA sees a dense matmul — the skip is semantic only)."""
        if pred is None or self.mode != "kernel":
            return h @ w_down
        from repro.kernels import ops as kops
        y, n_blocks = kops.masked_matmul_kdim(h, w_down, pred.kept,
                                              tile_m=self.tile_m,
                                              tile_k=self.tile_n,
                                              with_counts=True)
        pred.note_blocks(n_blocks)
        return y.astype(h.dtype)

    # -- the mor_relu_matmul / mor_ffn_apply entry points -------------------
    def relu_matmul(self, x: jax.Array, w: jax.Array, *,
                    activation: str = "relu",
                    residual: Optional[jax.Array] = None,
                    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """y = act(x @ w) with MoR skipping; x: (T, K), w: (K, N) permuted.
        Exactly ONE predictor evaluation regardless of mode."""
        y, pred, stats = self._relu_matmul_pred(x, w, activation=activation,
                                                residual=residual)
        return y, stats

    def _relu_matmul_pred(self, x, w, *, activation: str,
                          residual: Optional[jax.Array] = None,
                          row_mask: Optional[jax.Array] = None):
        """relu_matmul that also returns the MoRPrediction for reuse
        (the GLU path threads it into the up/down projections)."""
        T, N = x.shape[0], w.shape[1]
        if not self.active:
            pre = x @ w
            y = _act(pre + (residual if residual is not None else 0.0),
                     activation)
            return y, None, _dense_stats(
                shadow=self.mode in ("shadow", "scored"))
        mor = self.mor

        if self.mode in ("shadow", "scored"):
            return self._shadow_relu_matmul(x, w, activation=activation,
                                            residual=residual,
                                            row_mask=row_mask)

        if self.mode == "exact":
            pre = (x @ w).astype(jnp.float32)
            pre_bn = pre * mor["bn_scale"] + mor["bn_bias"]
            if residual is not None:
                pre_bn = pre_bn + residual
            pred = self.predict(x, w, preact_full=pre, residual=residual,
                                row_mask=row_mask)
            y = jnp.where(pred.computed, _act(pre_bn, activation),
                          0.0).astype(x.dtype)
            truly_nonzero = pre_bn > 0
            if row_mask is not None:
                truly_nonzero = truly_nonzero & row_mask[:, None]
            stats = pred.stats()
            stats["frac_mispredicted_zero"] = (
                ~pred.computed & truly_nonzero).mean(dtype=jnp.float32)
            return y, pred, stats

        # tiled / kernel: one predictor pass -> tile mask -> masked matmul
        pred = self.predict(x, w, residual=residual, row_mask=row_mask)
        pre = self.masked_matmul(x, w, pred)
        pre_bn = pre * mor["bn_scale"] + mor["bn_bias"]
        if residual is not None:
            pre_bn = pre_bn + residual
        keep = pred.keep_mask(T, N, self.tile_m, self.tile_n)
        y = jnp.where(keep, _act(pre_bn, activation), 0.0).astype(x.dtype)
        return y, pred, pred.stats()

    def _shadow_relu_matmul(self, x, w, *, activation: str,
                            residual: Optional[jax.Array] = None,
                            row_mask: Optional[jax.Array] = None):
        """Dense-oracle scoring pass (modes "shadow" / "scored"):
        compute the DENSE reference pre-activations, run the predictor
        exactly as the tiled/kernel plan would (no ``preact_full`` —
        same decision basis, same capacity clip), and score the tile
        decisions against the dense truth; the stats dict gains the
        ``shadow_*`` quality leaves.  Mode "shadow" propagates the
        DENSE activations (a standalone twin forward IS the reference
        computation); mode "scored" propagates the tile-MASKED
        activations, bitwise identical to the tiled path it stands in
        for (inside a kept tile both paths apply the same elementwise
        BN/act chain to the same dense matmul result; outside, both
        are exact zeros)."""
        mor = self.mor
        T, N = x.shape[0], w.shape[1]
        pre = (x @ w).astype(jnp.float32)
        pre_bn = pre * mor["bn_scale"] + mor["bn_bias"]
        if residual is not None:
            pre_bn = pre_bn + residual
        pred = self.predict(x, w, residual=residual, row_mask=row_mask)
        truth = pre_bn > 0
        if row_mask is not None:
            truth = truth & row_mask[:, None]
        truth_tiles = tile_mask_from_neuron_mask(
            truth.reshape(-1, N), self.tile_m, self.tile_n)
        stats = pred.stats()
        # exact integer tile counters: a false skip silently zeroes a
        # truly-live tile; a false keep burns compute on a dead one
        stats["shadow_tiles"] = jnp.asarray(int(truth_tiles.size),
                                            jnp.int32)
        stats["shadow_false_skip"] = (
            truth_tiles & ~pred.kept).sum(dtype=jnp.int32)
        stats["shadow_false_keep"] = (
            pred.kept & ~truth_tiles).sum(dtype=jnp.int32)
        stats["shadow_truth_live"] = truth_tiles.sum(dtype=jnp.int32)
        stats["shadow_sign_agree"] = (
            pred.computed == truth).mean(dtype=jnp.float32)
        y = _act(pre_bn, activation)
        # relative output-error norm the active plan's skips would have
        # caused on THIS dispatch (<= 1 by construction: the masked
        # output is a subset of the dense one)
        y_mor = jnp.where(pred.keep_mask(T, N, self.tile_m, self.tile_n),
                          y, 0.0)
        norm = jnp.sqrt(jnp.sum(jnp.square(y)))
        stats["shadow_err"] = (jnp.sqrt(jnp.sum(jnp.square(y_mor - y)))
                               / (norm + 1e-6))
        out = y_mor if self.mode == "scored" else y
        return out.astype(x.dtype), pred, stats

    def ffn(self, x: jax.Array, w_up: jax.Array, w_down: jax.Array, *,
            activation: str, w_gate: Optional[jax.Array] = None,
            row_mask: Optional[jax.Array] = None,
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """Full FFN with MoR on the ReLU pre-activation.

        GLU case (relufied SwiGLU -> ReLU-GLU): h = relu(x@w_gate) *
        (x@w_up).  The SINGLE gate prediction gates the up matmul (same
        tile mask — a skipped gate neuron zeroes h, so its up column is
        dead work) and the down matmul (dead h rows skipped along the
        contraction).  One predictor evaluation total.
        """
        if w_gate is not None:
            g, pred, stats = self._relu_matmul_pred(x, w_gate,
                                                    activation=activation,
                                                    row_mask=row_mask)
            if pred is not None and self.mode in ("tiled", "kernel",
                                                  "scored"):
                u = self.masked_matmul(x, w_up, pred).astype(x.dtype)
            else:
                # dense / exact: g already zeroes h where skipped; the
                # up matmul stays dense (exact mode is neuron-granular)
                u = x @ w_up
            h = (g * u).astype(x.dtype)
        else:
            h, pred, stats = self._relu_matmul_pred(x, w_up,
                                                    activation=activation,
                                                    row_mask=row_mask)
        y = self.down_matmul(h, w_down, pred)
        if pred is not None and pred.block_counts is not None:
            # stats were taken after the first matmul: count every kernel
            stats["frac_blocks_issued"] = pred.frac_blocks_issued()
        return y, stats

    # -- batched-expert form (MoE): leading E axis on everything -----------
    def expert_ffn(self, eb: jax.Array, w_up: jax.Array, w_down: jax.Array,
                   *, activation: str, w_gate: Optional[jax.Array] = None,
                   row_mask: Optional[jax.Array] = None,
                   ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """``ffn`` over a stack of experts: eb (E, C, d), weights
        (E, d, f) / (E, f, d), ``self.mor`` an (E,)-stacked MoRLayer and
        ``self.cap_live`` an optional scalar-or-(E,) calibrated budget.

        The per-expert plan (identical static config, per-expert leaves)
        runs under ``jax.vmap``, so the fused ``mor_tile_mask`` /
        ``gather_matmul`` Pallas kernels trace ONCE and batch over the
        expert grid — on TPU the batching rule prepends the expert axis
        to the kernel grid, giving per-expert DMA skipping with
        per-expert ``cap_live`` clamps from one compiled body.

        ``row_mask`` (E, C) marks the rows of each expert's capacity
        buffer that hold real routed tokens; padding rows are force-
        skipped (see ``predict``).  Returns (out (E, C, d), stats with
        (E,)-shaped realised skip fractions — the per-(layer, expert)
        telemetry feed)."""
        assert self.active, "expert_ffn() on an inactive plan"
        mode, tm, tn = self.mode, self.tile_m, self.tile_n
        cf, draft = self.capacity_frac, self.draft
        operands = {"x": eb, "w_up": w_up, "w_down": w_down,
                    "mor": self.mor}
        if w_gate is not None:
            operands["w_gate"] = w_gate
        if row_mask is not None:
            operands["row_mask"] = row_mask
        if self.cap_live is not None:
            operands["cap"] = jnp.broadcast_to(
                jnp.asarray(self.cap_live, jnp.float32), (eb.shape[0],))
        if self.draft_cap is not None:
            operands["dcap"] = jnp.broadcast_to(
                jnp.asarray(self.draft_cap, jnp.float32), (eb.shape[0],))

        def one(o):
            plan = MoRExecutionPlan(o["mor"], mode=mode, tile_m=tm,
                                    tile_n=tn, capacity_frac=cf,
                                    cap_live=o.get("cap"),
                                    draft_cap=o.get("dcap"), draft=draft)
            return plan.ffn(o["x"], o["w_up"], o["w_down"],
                            activation=activation, w_gate=o.get("w_gate"),
                            row_mask=o.get("row_mask"))

        return jax.vmap(one)(operands)


def as_plan(mor, *, mode: str = "dense", tile_m: int = 8, tile_n: int = 128,
            capacity_frac: float = 1.0) -> MoRExecutionPlan:
    """Coerce ``mor`` (a plan, a MoRLayer dict, or None) into a plan.

    An existing plan wins outright — its own mode/tiling is authoritative
    (it was attached offline by ``deploy.attach_plans``).  A bare
    MoRLayer gets wrapped with the caller's knobs (the legacy
    ``(mor, mode, tile_m, tile_n)`` tuple-passing path).
    """
    if isinstance(mor, MoRExecutionPlan):
        return mor
    if mor is not None and not _looks_like_mor_layer(mor):
        # e.g. the expert-MoR pytree {"experts": ...} handled upstream
        mor = None
    return MoRExecutionPlan(mor, mode=mode if mor is not None else "dense",
                            tile_m=tile_m, tile_n=tile_n,
                            capacity_frac=capacity_frac)


def as_expert_plan(em, *, mode: str = "dense", tile_m: int = 8,
                   tile_n: int = 128, capacity_frac: float = 1.0
                   ) -> MoRExecutionPlan:
    """Coerce an expert-MoR entry (``mor["experts"]``: an attached plan,
    an (E,)-stacked MoRLayer pytree, or None) into an execution plan for
    ``expert_ffn``.

    An attached plan's own mode/tiling/budget is authoritative (it was
    wired offline by ``deploy.attach_plans``, possibly with calibrated
    per-(layer, expert) ``cap_live``); a bare stacked MoRLayer gets the
    caller's knobs — exactly the contract dense FFNs get from
    ``as_plan``, so ``mode="dense"`` deactivates the predictor outright
    instead of silently forcing exact mode."""
    if isinstance(em, MoRExecutionPlan):
        return em
    if em is None or not _looks_like_mor_layer(em):
        return MoRExecutionPlan(None)
    return MoRExecutionPlan(em, mode=mode, tile_m=tile_m, tile_n=tile_n,
                            capacity_frac=capacity_frac)


def attach_draft_caps(mor, draft_cap):
    """Store a draft capacity budget on every plan in an attached-MoR
    pytree.  ``draft_cap`` (scalar fraction, or anything broadcastable
    to a plan's stacked leading dims) lands as the traced ``draft_cap``
    leaf — broadcast exactly like ``cap_live`` so stacked plans can ride
    ``lax.scan``/unrolled layer loops — and stays dormant until
    ``as_draft()`` flips the plan into draft mode."""
    def one(p):
        if p.mor is None:
            return p
        dc = jnp.broadcast_to(jnp.asarray(draft_cap, jnp.float32),
                              p.mor["m"].shape[:-1])
        return MoRExecutionPlan(
            p.mor, mode=p.mode, tile_m=p.tile_m, tile_n=p.tile_n,
            capacity_frac=p.capacity_frac, cap_live=p.cap_live,
            draft_cap=dc, draft=p.draft)
    return map_plans(mor, one)


def map_plans(mor, fn):
    """Apply ``fn`` to every MoRExecutionPlan inside an attached-MoR
    pytree (plans are pytree NODES, so a plain tree_map would descend
    into their leaves; this one stops at the plan boundary).  Non-plan
    leaves pass through untouched.  Used by the serving engine to derive
    the draft-mode twin of an attached model in one sweep."""
    return jax.tree_util.tree_map(
        lambda p: fn(p) if isinstance(p, MoRExecutionPlan) else p, mor,
        is_leaf=lambda x: isinstance(x, MoRExecutionPlan))


def _looks_like_mor_layer(mor) -> bool:
    return isinstance(mor, dict) and "enable" in mor and "bn_scale" in mor
