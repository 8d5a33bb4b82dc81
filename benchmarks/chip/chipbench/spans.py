"""Readers of the program's own host spans.

The program records its spans in a process-wide ring,
``repro.obs.tracer.host_spans()``: each span has ``id``, ``parent`` (0
for a root), ``name``, ``t0`` and ``t1`` on ``time.perf_counter``, the
clock the harness stamps dispatches and the window with.  A program
without that function reports nothing here: each reader returns None.
"""
from __future__ import annotations

from typing import Optional, Sequence

HOST_ROOTS = ("engine.step", "engine.drain")
DEVICE_WAITS = ("engine.fetch", "engine.enqueue")


def program_spans() -> Optional[Sequence]:
    """The program's span ring, or None where it has none."""
    try:
        from repro.obs import tracer
    except ImportError:
        return None
    read = getattr(tracer, "host_spans", None)
    return None if read is None else read()


def engine_host_ms(view, spans=None) -> Optional[float]:
    """Host milliseconds per traced dispatch that the engine spends
    outside the device: the ``engine.step`` and ``engine.drain`` spans
    that lie inside the traced dispatches (first ``t0`` to last ``t1``),
    less their ``engine.fetch`` (waiting for tokens) and
    ``engine.enqueue`` (handing the step to the runtime) children."""
    spans = program_spans() if spans is None else spans
    if spans is None or not view.traced:
        return None
    lo = min(d.t0 for d in view.traced)
    hi = max(d.t1 for d in view.traced)
    roots = {s.id: s for s in spans
             if s.name in HOST_ROOTS and s.t0 >= lo and s.t1 <= hi}
    if not roots:
        return None
    host = sum(s.t1 - s.t0 for s in roots.values())
    host -= sum(s.t1 - s.t0 for s in spans
                if s.parent in roots and s.name in DEVICE_WAITS)
    return 1e3 * host / len(view.traced)


def calibrate_host_s(view, spans=None) -> Optional[float]:
    """Seconds of the last calibration before the window opened that
    were not its forward passes: the ``calibrate`` root span less its
    ``calibrate.taps`` child (clustering, predictor build, folding the
    permutations into the weights)."""
    spans = program_spans() if spans is None else spans
    if spans is None:
        return None
    roots = [s for s in spans
             if s.name == "calibrate" and s.parent == 0 and s.t1 <= view.w0]
    if not roots:
        return None
    cal = max(roots, key=lambda s: s.t1)
    taps = sum(s.t1 - s.t0 for s in spans
               if s.parent == cal.id and s.name == "calibrate.taps")
    return cal.t1 - cal.t0 - taps
