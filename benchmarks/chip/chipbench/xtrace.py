"""Reduce a JAX profiler trace (``.xplane.pb``) to device metrics.

- busy: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane),
  averaged over the devices, inside the traced window;
- the traced window: from the first to the last of the harness's own
  spans (``bench.*`` host events) in the trace, so device and host
  times share the trace's clock;
- per-kernel device time and call count, matched by HLO op name: a
  Pallas kernel's custom call takes the name of the jitted wrapper that
  issues it (``gather_matmul.17``), or ``_unknown_`` where no named jit
  wraps its ``pallas_call`` (the paged attention kernel);
- the device operations that took most time, and the longest idle
  gaps, each labelled with the harness span that overlaps it most
  (what the host was doing while the device waited).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
HOST_SPAN_PREFIX = "bench."
_NAME = re.compile(r"%?([^\s=]+)")
_SUFFIX = re.compile(r"(\.\d+)+$")


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    n_devices: int
    kernel_s: Dict[str, float] = field(default_factory=dict)
    kernel_n: Dict[str, int] = field(default_factory=dict)
    top_ops: List[Tuple[str, float]] = field(default_factory=list)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def op_base(name: str) -> str:
    """HLO op name without its ``.N`` instance suffix.  A TPU trace names
    an op event by its whole HLO instruction (``%gather_matmul.12 =
    bf16[...] custom-call(...)``); the name is the part before ``=``."""
    m = _NAME.match(name)
    return _SUFFIX.sub("", m.group(1) if m else name)


def merge(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(iv: Sequence[Tuple[float, float]], lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)


def device_planes(pd) -> list:
    return [p for p in pd.planes
            if p.name.startswith("/device:TPU:") and
            any(ln.name == OPS_LINE for ln in p.lines)]


def reduce_profile(pd, kernels: Dict[str, Sequence[str]], top: int = 10
                   ) -> Optional[TraceSummary]:
    """``pd``: a ``jax.profiler.ProfileData``; ``kernels``: kernel name ->
    the HLO op base names its calls carry.  None when the trace holds no
    device operations or no harness span (nothing to read)."""
    op_kernel = {op: k for k, ops in kernels.items() for op in ops}
    host: List[Tuple[str, float, float]] = []
    for p in pd.planes:
        if p.name.startswith("/device:"):
            continue
        for ln in p.lines:
            host += [(n, a, b) for n, a, b in _events(ln)
                     if n.startswith(HOST_SPAN_PREFIX)]
    devs = device_planes(pd)
    if not host or not devs:
        return None
    lo = min(a for _, a, _ in host)
    hi = max(b for _, _, b in host)
    busy_total = 0.0
    kernel_s = {k: 0.0 for k in kernels}
    kernel_n = {k: 0 for k in kernels}
    op_s: Dict[str, float] = {}
    gaps: List[Tuple[float, float]] = []
    for p in devs:
        ops = [ev for ln in p.lines if ln.name == OPS_LINE
               for ev in _events(ln)]
        ops = [(n, a, b) for n, a, b in ops if b > lo and a < hi]
        busy = merge(_clip([(a, b) for _, a, b in ops], lo, hi))
        busy_total += sum(b - a for a, b in busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        for n, a, b in ops:
            base = op_base(n)
            d = min(b, hi) - max(a, lo)
            op_s[base] = op_s.get(base, 0.0) + d
            k = op_kernel.get(base)
            if k is not None:
                kernel_s[k] += b - a
                kernel_n[k] += 1
    n_dev = len(devs)
    ns = 1e-9
    labelled = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        best, over = "none", 0.0
        for n, s0, s1 in host:
            o = min(b, s1) - max(a, s0)
            if o > over:
                best, over = n, o
        labelled.append((best, (b - a) * ns))
    return TraceSummary(
        window_s=(hi - lo) * ns,
        busy_s=busy_total / n_dev * ns,
        n_devices=n_dev,
        kernel_s={k: v / n_dev * ns for k, v in kernel_s.items()},
        kernel_n={k: v // n_dev for k, v in kernel_n.items()},
        top_ops=sorted(((k, v / n_dev * ns) for k, v in op_s.items()),
                       key=lambda kv: -kv[1])[:top],
        idle_gaps=labelled)


def find_xplane(trace_dir: Path) -> Optional[Path]:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    return found[-1] if found else None


def reduce_dir(trace_dir: Path, kernels: Dict[str, Sequence[str]]
               ) -> Optional[TraceSummary]:
    from jax.profiler import ProfileData
    path = find_xplane(trace_dir)
    if path is None:
        return None
    return reduce_profile(ProfileData.from_file(str(path)), kernels)
