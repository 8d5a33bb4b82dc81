"""Host spans and the request tracer.

**Spans.** ``with span(name, **args):`` is the one span API of the
process.  Each span records its name, start and end on
``time.perf_counter`` (the benchmark harness's clock), the id of the
span it opened inside, and its args, into a bounded process-wide ring
(``RING_SIZE`` entries, oldest dropped and counted) that is always on.
Each span is also a ``jax.profiler.TraceAnnotation``, so a profiler
trace holds the same spans on the profiler's clock, nested inside
whatever annotations enclose them.  ``host_spans()`` returns the ring.
The ring is process-wide because its users are: calibration runs with
no engine, and ``jax.profiler`` traces the whole process.  Spans nest by
the order they open and close on the thread that runs them (the
engine's loop is single-threaded).  Every executable JAX builds adds a
``jax.compile`` span (from ``jax.monitoring``'s backend compile event,
args ``fun``), inside the span that was open when it was built.

**Tracer** (``Tracer``, opt-in through ``repro.obs.Observability``):
per-request events from the engine's hooks, for each request queued →
prefill chunks → decode emits → finish.  TTFT is submit → the host
receiving the request's first token (``on_deliver``, stamped when the
engine's token fetch returns); inter-token latency is the gap between
consecutive tokens of a request reaching the host (tokens that arrive
together are 0 apart).  ``mirror_spans`` mirrors the ring into the
registry (``repro_engine_span_seconds_total`` / ``repro_engine_spans_total``
by span name) at the engine's flush.

Export is Chrome-trace JSON (``{"traceEvents": [...]}`` with "ph":"X"
complete events) — loadable in Perfetto / chrome://tracing.  Span
events per request live on a per-slot track so concurrent requests
stack visually the way they share slots physically; the ring's spans
recorded since the tracer's reset ride a "host" track.
"""
from __future__ import annotations

import json
import time
from collections import deque
from dataclasses import dataclass, field
from itertools import islice
from typing import Deque, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
from jax.profiler import TraceAnnotation

__all__ = ["Tracer", "validate_chrome_trace", "span", "host_spans",
           "spans_dropped", "Span", "RING_SIZE"]

RING_SIZE = 1 << 16
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_now = time.perf_counter
_tracing = TraceAnnotation.is_enabled     # a profiler trace is recording


class Span(NamedTuple):
    id: int
    parent: int              # 0: a root
    name: str
    t0: float                # seconds, perf_counter timebase
    t1: float
    args: Dict


class _Ring:
    """The process's spans: a bounded ring, the ids of the spans open
    now (innermost last), and counts of spans recorded and dropped."""

    def __init__(self, size: int):
        self.spans: Deque[Span] = deque(maxlen=size)
        self.open: List[int] = []
        self.next_id = 1
        self.added = 0
        self.dropped = 0

    def add(self, sp: Span) -> None:
        if len(self.spans) == self.spans.maxlen:
            self.dropped += 1
        self.spans.append(sp)
        self.added += 1

    def since(self, n_added: int) -> List[Span]:
        """Spans recorded after the ring had recorded ``n_added``, in
        the order they closed (those since dropped excepted)."""
        k = min(self.added - n_added, len(self.spans))
        return list(islice(reversed(self.spans), k))[::-1]


_RING = _Ring(RING_SIZE)


_new_span = tuple.__new__            # Span(...) without its Python __new__


class span:
    """``with span("engine.step", kind="decode") as sp:`` — one host
    span, recorded into the ring when it closes (``sp.args`` may be
    added to until then) and, while a profiler trace is recording, a
    ``jax.profiler.TraceAnnotation`` (which records nothing when made
    outside a trace, so none is made then)."""

    __slots__ = ("name", "args", "_id", "_parent", "_t0", "_ann")

    def __init__(self, name: str, **args):
        self.name = name
        self.args = args

    def __enter__(self) -> "span":
        r = _RING
        self._id = i = r.next_id
        r.next_id = i + 1
        self._parent = r.open[-1] if r.open else 0
        r.open.append(i)
        if _tracing():
            self._ann = TraceAnnotation(self.name)
            self._ann.__enter__()
        else:
            self._ann = None
        self._t0 = _now()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        t1 = _now()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        r = _RING
        r.open.pop()
        # _Ring.add, inlined: this runs once per span
        if len(r.spans) == RING_SIZE:
            r.dropped += 1
        r.spans.append(_new_span(Span, (self._id, self._parent, self.name,
                                        self._t0, t1, self.args)))
        r.added += 1


def host_spans() -> Deque[Span]:
    """The ring itself, oldest first (a test may ``clear()`` it)."""
    return _RING.spans


def spans_dropped() -> int:
    """Spans the ring has dropped to stay at ``RING_SIZE``."""
    return _RING.dropped


def _on_compile(event: str, duration: float, fun_name: str = "",
                **_) -> None:
    if event != COMPILE_EVENT:
        return
    r = _RING
    t1 = _now()
    i = r.next_id
    r.next_id = i + 1
    r.add(Span(i, r.open[-1] if r.open else 0, "jax.compile",
               t1 - duration, t1, {"fun": fun_name}))


jax.monitoring.register_event_duration_secs_listener(_on_compile)

# inter-token latencies at reduced dims are sub-ms; extend the default
# latency buckets downward for ITL
ITL_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
               0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5)
TTFT_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                0.5, 1.0, 2.5, 5.0, 10.0, 30.0)


@dataclass
class _Req:
    rid: int
    t_submit: float
    t_admit: Optional[float] = None
    t_first_token: Optional[float] = None
    t_last_token: Optional[float] = None
    t_finish: Optional[float] = None
    slot: Optional[int] = None
    n_tokens: int = 0
    finished: bool = False        # its last token was dispatched


@dataclass
class _Event:
    name: str
    ts: float            # seconds, perf_counter timebase
    dur: float
    pid: str
    tid: object
    args: Dict = field(default_factory=dict)


def _jsonable(v):
    return v.tolist() if hasattr(v, "tolist") else v


class Tracer:
    """Collects dispatch + request events on top of the span ring;
    drains into registry histograms, the span counters and a
    Chrome-trace event list."""

    def __init__(self, registry=None, max_events: int = 100_000):
        self.registry = registry
        self.max_events = max_events
        if registry is not None:
            self._h_ttft = registry.histogram(
                "repro_serving_ttft_seconds",
                "time from submit to the first token reaching the host",
                buckets=TTFT_BUCKETS)
            self._h_itl = registry.histogram(
                "repro_serving_itl_seconds",
                "gap between consecutive tokens of a request reaching "
                "the host",
                buckets=ITL_BUCKETS)
            self._h_queue = registry.histogram(
                "repro_serving_queue_wait_seconds",
                "time from submit to slot admission",
                buckets=TTFT_BUCKETS)
            self._c_span_s = registry.counter(
                "repro_engine_span_seconds_total",
                "host seconds inside each span (repro.obs.tracer.span)",
                ("span",))
            self._c_span_n = registry.counter(
                "repro_engine_spans_total",
                "spans recorded (repro.obs.tracer.span)", ("span",))
        else:
            self._h_ttft = self._h_itl = self._h_queue = None
            self._c_span_s = self._c_span_n = None
        self.reset()

    def reset(self) -> None:
        self._reqs: Dict[int, _Req] = {}
        self._events: List[_Event] = []
        self._n_dispatch = 0
        self._n_preempt = 0
        self._n_restore = 0
        self._n_drift = 0
        self._dropped = 0
        # the ring is process-wide: this tracer reads what it recorded
        # after this point (export) and after the last mirror (counters)
        self._ring_base = self._ring_mirrored = _RING.added
        self._span_s: Dict[str, float] = {}
        self._span_n: Dict[str, int] = {}
        # the tracer owns its latency histograms and span counters: a
        # reset boundary (the engine's reset_counters between timed
        # passes) zeroes them too, so exported quantiles describe the
        # LAST pass, not the compile-heavy warmup
        for fam in (self._h_ttft, self._h_itl, self._h_queue,
                    self._c_span_s, self._c_span_n):
            if fam is not None:
                fam.clear()

    # -- hooks the engine calls -------------------------------------------
    @staticmethod
    def now() -> float:
        return _now()

    def on_submit(self, rid: int, t: Optional[float] = None) -> None:
        self._reqs[rid] = _Req(rid, self.now() if t is None else t)

    def on_dispatch(self, kind: str, t0: float, t1: float, *,
                    admitted: Sequence[Tuple[int, int]] = (),
                    prefilling: Sequence[Tuple[int, int, int, int]] = (),
                    emits: Sequence[Tuple[int, int]] = (),
                    emit_counts: Optional[Sequence[int]] = None,
                    finished: Sequence[int] = (),
                    queue_depth: int = 0,
                    n_active: int = 0) -> None:
        """One engine dispatch, enqueued from ``t0`` to ``t1``.
        admitted: (slot, rid) pairs newly placed; prefilling: (slot,
        rid, offset, take) chunks consumed this dispatch; emits: (slot,
        rid) that produced a token (``emit_counts``, aligned with
        ``emits``, carries a speculative verify's per-slot count);
        finished: rids whose last token this dispatch produced.  Token
        latencies come from ``on_deliver``, not from here.  Any
        ``kind`` string flows through to the event name — the
        speculative round uses draft/verify/replay."""
        i = self._n_dispatch
        self._n_dispatch += 1
        n_emits = (sum(emit_counts) if emit_counts is not None
                   else len(emits))
        self._emit(_Event(f"dispatch/{kind}", t0, t1 - t0, "engine",
                          "dispatch",
                          {"i": i, "kind": kind,
                           "queue_depth": queue_depth,
                           "n_active": n_active,
                           "n_emits": n_emits}))

        for slot, rid in admitted:
            r = self._reqs.get(rid)
            if r is None:        # request submitted before tracer reset
                r = self._reqs[rid] = _Req(rid, t0)
            r.t_admit = t0
            r.slot = slot
            self._emit(_Event(f"queued rid={rid}", r.t_submit,
                              t0 - r.t_submit, "requests", f"rid {rid}",
                              {"rid": rid}))
            if self._h_queue is not None:
                self._h_queue.observe(t0 - r.t_submit)

        for item in prefilling:
            slot, rid, off, take = item
            self._emit(_Event(f"prefill rid={rid} [{off}:{off + take}]",
                              t0, t1 - t0, "slots", f"slot {slot}",
                              {"rid": rid, "offset": off, "take": take}))

        for j, (slot, rid) in enumerate(emits):
            n_tok = emit_counts[j] if emit_counts is not None else 1
            self._emit(_Event(f"decode rid={rid}", t0, t1 - t0, "slots",
                              f"slot {slot}",
                              {"rid": rid, "n_tokens": n_tok}))

        for rid in finished:
            r = self._reqs.get(rid)
            if r is not None:
                r.finished = True

    def on_deliver(self, rid: int, n_tokens: int, t: float) -> None:
        """``n_tokens`` of request ``rid`` reached the host at ``t``
        (the end of the engine's token fetch).  Stamps TTFT and the
        inter-token gaps; the request's span ends at the delivery of
        its last token."""
        r = self._reqs.get(rid)
        if r is None or n_tokens <= 0:
            return
        if r.t_first_token is None:
            r.t_first_token = t
            if self._h_ttft is not None:
                self._h_ttft.observe(t - r.t_submit)
        elif self._h_itl is not None:
            self._h_itl.observe(t - r.t_last_token)
        if self._h_itl is not None:
            for _ in range(n_tokens - 1):
                self._h_itl.observe(0.0)
        r.t_last_token = t
        r.n_tokens += n_tokens
        if r.finished and r.t_finish is None:
            r.t_finish = t
            self._emit(_Event(f"request rid={rid}", r.t_submit,
                              t - r.t_submit, "requests", f"rid {rid}",
                              {"rid": rid, "n_tokens": r.n_tokens,
                               "ttft_s": round(r.t_first_token
                                               - r.t_submit, 6)}))

    def on_preempt(self, rid: int, slot: int,
                   t: Optional[float] = None) -> None:
        """Request ``rid`` was spilled out of ``slot`` (pages moved to
        host; it re-enters the waiting queue at its exact progress)."""
        t = self.now() if t is None else t
        self._n_preempt += 1
        self._emit(_Event(f"preempt rid={rid}", t, 0.0, "requests",
                          f"rid {rid}", {"rid": rid, "slot": slot}))

    def on_restore(self, rid: int, slot: int,
                   t: Optional[float] = None) -> None:
        """Spilled request ``rid`` re-admitted into ``slot``."""
        t = self.now() if t is None else t
        self._n_restore += 1
        self._emit(_Event(f"restore rid={rid}", t, 0.0, "requests",
                          f"rid {rid}", {"rid": rid, "slot": slot}))

    def on_drift(self, group: str, layer: int, expert: Optional[int],
                 rate: float, t: Optional[float] = None) -> None:
        """Predictor drift flagged on one (group, layer[, expert])
        series — an instant marker on its own "quality" track so the
        degradation onset lines up against the dispatch timeline."""
        t = self.now() if t is None else t
        self._n_drift += 1
        where = f"{group}/L{layer}" + ("" if expert is None
                                       else f"/E{expert}")
        self._emit(_Event(f"drift {where}", t, 0.0, "quality", group,
                          {"group": group, "layer": layer,
                           "expert": expert,
                           "false_skip_rate": round(rate, 6)}))

    def mirror_spans(self) -> None:
        """Add the spans recorded since the last mirror to the per-name
        totals and set ``repro_engine_span_seconds_total`` /
        ``repro_engine_spans_total`` from them (the engine's flush)."""
        for sp in _RING.since(self._ring_mirrored):
            self._span_s[sp.name] = (self._span_s.get(sp.name, 0.0)
                                     + sp.t1 - sp.t0)
            self._span_n[sp.name] = self._span_n.get(sp.name, 0) + 1
        self._ring_mirrored = _RING.added
        if self._c_span_s is not None:
            for name, s in self._span_s.items():
                self._c_span_s.set(s, span=name)
                self._c_span_n.set(self._span_n[name], span=name)

    def _emit(self, ev: _Event) -> None:
        if len(self._events) >= self.max_events:
            self._dropped += 1
            return
        self._events.append(ev)

    # -- introspection / export -------------------------------------------
    @property
    def n_dispatches(self) -> int:
        return self._n_dispatch

    def request_spans(self) -> Dict[int, Dict]:
        out = {}
        for rid, r in sorted(self._reqs.items()):
            out[rid] = {
                "t_submit": r.t_submit, "t_admit": r.t_admit,
                "t_first_token": r.t_first_token,
                "t_finish": r.t_finish, "slot": r.slot,
                "n_tokens": r.n_tokens,
                "ttft_s": None if r.t_first_token is None
                else r.t_first_token - r.t_submit}
        return out

    def summary(self) -> Dict:
        out: Dict = {"n_dispatches": self._n_dispatch,
                     "n_requests": len(self._reqs),
                     "n_preemptions": self._n_preempt,
                     "n_restores": self._n_restore,
                     "n_drift_events": self._n_drift,
                     "events_dropped": self._dropped,
                     "host_spans_dropped": spans_dropped()}
        if self._h_ttft is not None:
            out["ttft"] = self._h_ttft.summary()
            out["itl"] = self._h_itl.summary()
            out["queue_wait"] = self._h_queue.summary()
        return out

    def to_chrome_trace(self) -> Dict:
        """Chrome trace event format; ts/dur in microseconds."""
        events = list(self._events)
        for sp in _RING.since(self._ring_base):
            events.append(_Event(sp.name, sp.t0, sp.t1 - sp.t0, "host",
                                 "spans",
                                 {"id": sp.id, "parent": sp.parent,
                                  **{k: _jsonable(v)
                                     for k, v in sp.args.items()}}))
        pids = sorted({ev.pid for ev in events})
        pid_ids = {p: i + 1 for i, p in enumerate(pids)}
        tid_ids: Dict[Tuple[str, object], int] = {}
        out: List[Dict] = []
        for pid, pi in pid_ids.items():
            out.append({"ph": "M", "name": "process_name", "pid": pi,
                        "tid": 0, "args": {"name": pid}})
        for ev in events:
            key = (ev.pid, ev.tid)
            if key not in tid_ids:
                tid_ids[key] = len([k for k in tid_ids
                                    if k[0] == ev.pid]) + 1
                out.append({"ph": "M", "name": "thread_name",
                            "pid": pid_ids[ev.pid],
                            "tid": tid_ids[key],
                            "args": {"name": str(ev.tid)}})
            out.append({"ph": "X", "name": ev.name,
                        "ts": round(ev.ts * 1e6, 3),
                        "dur": round(max(ev.dur, 0.0) * 1e6, 3),
                        "pid": pid_ids[ev.pid], "tid": tid_ids[key],
                        "args": ev.args})
        return {"traceEvents": out,
                "displayTimeUnit": "ms",
                "metadata": {"tool": "repro.obs.tracer"}}

    def write_chrome_trace(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)


def validate_chrome_trace(obj) -> List[str]:
    """Schema check for the exported trace; returns a list of problems
    (empty == valid).  Shared by tests and the CI smoke job."""
    problems: List[str] = []
    if not isinstance(obj, dict) or "traceEvents" not in obj:
        return ["missing top-level traceEvents"]
    evs = obj["traceEvents"]
    if not isinstance(evs, list):
        return ["traceEvents is not a list"]
    for i, ev in enumerate(evs):
        if not isinstance(ev, dict):
            problems.append(f"event {i}: not an object")
            continue
        ph = ev.get("ph")
        if ph not in ("X", "M", "B", "E", "i"):
            problems.append(f"event {i}: bad ph {ph!r}")
            continue
        if "name" not in ev or "pid" not in ev:
            problems.append(f"event {i}: missing name/pid")
        if ph == "X":
            ts, dur = ev.get("ts"), ev.get("dur")
            if not isinstance(ts, (int, float)):
                problems.append(f"event {i}: bad ts {ts!r}")
            if not isinstance(dur, (int, float)) or dur < 0:
                problems.append(f"event {i}: bad dur {dur!r}")
    return problems
