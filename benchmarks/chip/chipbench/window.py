"""Window arithmetic over the driver's delivery log.

Every number here is taken over ALL requests and gaps of the window, on
the harness's own clock (``time.perf_counter``):

- ``out_tok_s``: generated tokens delivered to the host inside the
  window, over the window's seconds;
- TTFT: from a request's scheduled arrival to its first delivered
  token, for every request whose arrival falls in the window (the
  driver follows each one past the close until its first token comes);
- ITL: the gap between consecutive deliveries of one request, for every
  gap that ends inside the window — a stall inside the window shows up
  as one long gap for every request in flight;
- generator lag: submit time minus scheduled time.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import numpy as np


@dataclass
class ReqLog:
    index: int                   # position in the traffic stream, or -1
    max_new: int
    t_sched: float               # scheduled arrival (closed loop: submit)
    t_submit: float
    rid: int = -1
    token_t: List[float] = field(default_factory=list)
    prompt: Optional[np.ndarray] = None

    @property
    def done(self) -> bool:
        return len(self.token_t) >= self.max_new


def p95(values: Sequence[float]) -> Optional[float]:
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), 95))


def window_stats(reqs: List[ReqLog], w0: float, w1: float,
                 closed_loop: bool = False) -> Dict:
    """-> counts, samples and tails of one window [w0, w1)."""
    span = w1 - w0
    tokens = sum(1 for r in reqs for t in r.token_t if w0 <= t < w1)
    itl = [b - a for r in reqs for a, b in zip(r.token_t, r.token_t[1:])
           if w0 <= b < w1]
    if closed_loop:
        # a closed loop has no schedule of its own: the requests it
        # attempted in the window are those it served there
        attempted = [r for r in reqs
                     if any(w0 <= t < w1 for t in r.token_t)]
        failed = []
        ttft = []
    else:
        attempted = [r for r in reqs if w0 <= r.t_sched < w1]
        failed = [r for r in attempted if not r.token_t]
        ttft = [r.token_t[0] - r.t_sched for r in attempted if r.token_t]
    lag = [r.t_submit - r.t_sched for r in reqs if w0 <= r.t_sched < w1]
    return {
        "window_s": span,
        "tokens": tokens,
        "out_tok_s": tokens / span if span > 0 else None,
        "attempted": len(attempted),
        "failed": len(failed),
        "ttft_s": ttft,
        "itl_s": itl,
        "gen_lag_s": lag,
        "ttft_p95_ms": None if not ttft else 1e3 * p95(ttft),
        "itl_p95_ms": None if not itl else 1e3 * p95(itl),
        "gen_lag_p95_ms": None if not lag else 1e3 * p95(lag),
    }
