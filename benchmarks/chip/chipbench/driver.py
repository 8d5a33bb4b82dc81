"""The harness's own load driver over ``repro.serving.Engine``.

It uses only the engine's public ``submit`` / ``step`` / ``drain``.  Each
tick tops the waiting queue up to the traffic's backlog (once the
closed loop is on), calls ``step()`` and then ``drain()``: the drain
fetches the step's tokens to the host, so every token is stamped by its
``on_token`` callback when it reaches the host, which is when a user
would get it.  Requests can also be handed in one by one (``submit``),
as the warm-up does.

The driver keeps its own spans (``bench.submit``, ``bench.step``,
``bench.drain``) on its clock and as ``jax.profiler.TraceAnnotation``
events, so a device trace can say what the host was doing in each idle
gap.  It also records, per dispatch, the slots' positions and tokens
consumed, read at the pool's ``advance`` call (the moment the engine
books the dispatch), which the operation and byte counts need.

The request logic follows ``repro.serving.loadgen.run_open_loop``, as a
closed loop.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Callable, List, Optional, Tuple

import numpy as np

from chipbench.traffic import Traffic
from chipbench.window import ReqLog


@dataclass
class Dispatch:
    t0: float                    # step() entered
    t1: float                    # drain() returned: the tokens are home
    kind: str                    # "mixed" or "decode"
    pos: np.ndarray              # (slots,) positions before the dispatch
    n_valid: np.ndarray          # (slots,) tokens each slot consumed
    rows: int                    # rows the compiled step ran: slots x chunk


class Driver:
    def __init__(self, engine, traffic: Traffic,
                 clock: Callable[[], float] = time.perf_counter):
        import jax
        self.eng = engine
        self.traffic = traffic
        self.spec = traffic.spec
        self.clock = clock
        self.loop = False             # closed loop on: keep the backlog
        self.next_index = 0           # next request of the traffic stream
        self._annotation = jax.profiler.TraceAnnotation
        self.reqs: List[ReqLog] = []
        self.spans: List[Tuple[str, float, float]] = []
        self.dispatches: List[Dispatch] = []
        self._booked: List[Tuple[np.ndarray, np.ndarray]] = []
        pool = engine.pool
        advance = pool.advance

        def booked_advance(n_valid):
            self._booked.append((pool.pos.copy(),
                                 np.asarray(n_valid, np.int64).copy()))
            return advance(n_valid)
        pool.advance = booked_advance
        self._unbook = lambda: setattr(pool, "advance", advance)

    def close(self) -> None:
        """Hand the pool back as it was."""
        self._unbook()

    # -- spans ---------------------------------------------------------------
    def _span(self, name: str, fn, *a):
        t0 = self.clock()
        with self._annotation(name):
            out = fn(*a)
        self.spans.append((name, t0, self.clock()))
        return out

    # -- requests ------------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new: int,
               index: int = -1) -> ReqLog:
        """Hand one request to the engine; ``index`` is its place in the
        traffic stream (-1 for the warm-up's own)."""
        now = self.clock()
        log = ReqLog(index=index, max_new=max_new, t_sched=now,
                     t_submit=now, prompt=prompt)
        stamp = log.token_t.append
        clock = self.clock
        from repro.serving import RequestRejected
        try:
            log.rid = self.eng.submit(prompt, max_new,
                                      on_token=lambda _r, _t: stamp(clock()))
        except RequestRejected:
            pass                  # no rid: the window counts it as failed
        self.reqs.append(log)
        return log

    def _top_up(self) -> None:
        n = self.spec.backlog - len(self.eng.scheduler.waiting)
        if not self.loop or n <= 0:
            return
        t0 = self.clock()
        with self._annotation("bench.submit"):
            for _ in range(n):
                prompt, max_new = self.traffic.request(self.next_index)
                self.submit(prompt, max_new, self.next_index)
                self.next_index += 1
        self.spans.append(("bench.submit", t0, self.clock()))

    # -- one tick ------------------------------------------------------------
    def tick(self) -> None:
        self._top_up()
        sched = self.eng.scheduler
        if not sched.has_work:
            return
        kinds = dict(sched.dispatch_kinds)
        t0 = self.clock()
        self._span("bench.step", self.eng.step)
        self._span("bench.drain", self.eng.drain)
        t1 = self.clock()
        kind = next((k for k, v in sched.dispatch_kinds.items()
                     if v != kinds.get(k, 0)), None)
        booked, self._booked = self._booked, []
        if kind is not None and booked:
            pos, nv = booked[-1]
            chunk = self.eng.chunk if kind == "mixed" else 1
            self.dispatches.append(Dispatch(t0, t1, kind, pos, nv,
                                            len(nv) * chunk))

    def run_until(self, done: Callable[[], bool],
                  deadline: Optional[float] = None) -> bool:
        """Tick until ``done()``; False if the deadline came first or
        nothing is left to run."""
        while not done():
            if deadline is not None and self.clock() >= deadline:
                return False
            if not (self.loop or self.eng.scheduler.has_work):
                return False
            self.tick()
        return True
