"""Arithmetic shared by the metric readers in ``metrics/``.

A reader takes the run's view (``run.RunView``) and returns one number,
or None when it finds nothing to read (no trace, no dispatch of its
kind, a kernel count that does not match the dispatches traced); the
harness then leaves the metric out of the line.  A share is in percent.
"""
from __future__ import annotations

from typing import Dict, Iterable, Optional, Sequence

from chipbench import cost

# kernel calls per layer per dispatch, by reference kind
CALLS = {
    "gqa_glu": {"mor_tile_mask": 1, "gather_matmul": 2,
                "masked_matmul_kdim": 1, "gqa_paged_flash": 1},
}
MOR_KERNELS = ("mor_tile_mask", "gather_matmul", "masked_matmul_kdim")


def _live(view) -> float:
    return 1.0 if view.live is None else float(view.live)


def step_mfu(view) -> Optional[float]:
    """Model operations of the traced dispatches over the traced window
    at the chip's bf16 peak."""
    if not view.traced or view.trace_host_s <= 0:
        return None
    f = sum(cost.dispatch_model_flops(view.shape, d.pos, d.n_valid,
                                      _live(view)) for d in view.traced)
    return 100.0 * f / (view.trace_host_s * view.peaks["bf16_flops_per_s"])


def step_hbm_share(view) -> Optional[float]:
    if not view.traced or view.trace_host_s <= 0:
        return None
    b = sum(cost.dispatch_bytes(view.shape, d.pos, d.n_valid, _live(view))
            for d in view.traced)
    return 100.0 * b / (view.trace_host_s * view.peaks["hbm_bytes_per_s"])


def expected_calls(view) -> Dict[str, int]:
    per = CALLS[view.shape.kind]
    n = len(view.traced) * view.shape.layers
    return {k: per.get(k, 0) * n for k in cost_kernels(view)}


def cost_kernels(view) -> Sequence[str]:
    return tuple(CALLS[view.shape.kind])


def _counts_match(view, kernels: Iterable[str]) -> bool:
    want = expected_calls(view)
    return all(view.trace.kernel_n.get(k, 0) == want[k] and want[k] > 0
               for k in kernels)


def roofline(view, kernels: Sequence[str]) -> Optional[float]:
    """The least time the traced calls of ``kernels`` could take on this
    chip (each call: the larger of operations over peak and bytes over
    bandwidth) over the device time they took."""
    kernels = [k for k in kernels if k in CALLS[view.shape.kind]]
    if view.trace is None or not view.traced or not kernels:
        return None
    if not _counts_match(view, kernels):
        return None
    pf = view.peaks["bf16_flops_per_s"]
    bw = view.peaks["hbm_bytes_per_s"]
    least = 0.0
    for d in view.traced:
        per = cost.dispatch_kernels(view.shape, d.rows, d.pos,
                                    d.n_valid, _live(view))
        least += sum(max(per[k][0] / pf, per[k][1] / bw) for k in kernels)
    took = sum(view.trace.kernel_s[k] for k in kernels)
    return 100.0 * least / took if took > 0 else None


def predict_share(view) -> Optional[float]:
    """``mor_tile_mask`` device time over the MoR FFN kernels'."""
    kernels = [k for k in MOR_KERNELS if k in CALLS[view.shape.kind]]
    if view.trace is None or not view.traced or \
            not _counts_match(view, kernels):
        return None
    took = sum(view.trace.kernel_s[k] for k in kernels)
    return 100.0 * view.trace.kernel_s["mor_tile_mask"] / took \
        if took > 0 else None


def idle_share(view) -> Optional[float]:
    if view.trace is None or view.trace.window_s <= 0:
        return None
    return 100.0 * view.trace.idle_share
