"""The readers of the program's own spans (``chipbench.spans``), on
synthetic views and span lists whose answers are worked out by hand,
and their silence where the program has no span ring."""
import sys
import types
from collections import namedtuple
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import spans  # noqa: E402

S = namedtuple("S", "id parent name t0 t1 args")
D = namedtuple("D", "t0 t1")


def _tick(first_id, t, step, enqueue, fetch, drain):
    """One harness tick at ``t``: an engine.step of ``step`` seconds
    holding an enqueue, then an engine.drain holding a fetch."""
    i = first_id
    return [
        S(i + 1, i, "engine.admit", t + 0.001, t + 0.002, {}),
        S(i + 2, i, "engine.enqueue", t + 0.002, t + 0.002 + enqueue, {}),
        S(i, 0, "engine.step", t, t + step, {"kind": "decode"}),
        S(i + 4, i + 3, "engine.fetch", t + step, t + step + fetch, {}),
        S(i + 5, i + 3, "engine.telemetry", t + step + fetch,
          t + step + drain, {}),
        S(i + 3, 0, "engine.drain", t + step, t + step + drain, {}),
    ]


def test_engine_host_ms_by_hand():
    # three ticks at 10, 11, 12 s: step 10 ms holding a 4 ms enqueue,
    # drain 8 ms holding a 5 ms fetch -> host (10 - 4) + (8 - 5) = 9 ms
    # per tick.  The traced dispatches are the last two: their window
    # [11, 12.018] holds two ticks' spans, 18 ms over 2 dispatches.
    sp = (_tick(1, 10.0, 0.010, 0.004, 0.005, 0.008)
          + _tick(11, 11.0, 0.010, 0.004, 0.005, 0.008)
          + _tick(21, 12.0, 0.010, 0.004, 0.005, 0.008))
    view = types.SimpleNamespace(traced=[D(11.0, 11.018), D(12.0, 12.018)])
    assert spans.engine_host_ms(view, sp) == pytest.approx(9.0)
    # a grandchild wait (a compile inside the enqueue) is not subtracted
    # twice; a wait outside the window is not subtracted at all
    sp.append(S(99, 12, "jax.compile", 11.002, 11.003, {}))
    sp.append(S(98, 0, "engine.fetch", 13.0, 13.5, {}))
    assert spans.engine_host_ms(view, sp) == pytest.approx(9.0)


def test_engine_host_ms_reads_nothing_without_spans_or_dispatches():
    view = types.SimpleNamespace(traced=[D(1.0, 2.0)])
    assert spans.engine_host_ms(view, []) is None
    assert spans.engine_host_ms(types.SimpleNamespace(traced=[]),
                                _tick(1, 1.0, 0.1, 0, 0, 0.1)) is None


def test_calibrate_host_s_by_hand():
    # two calibrations before the window at 100 s: the last counts,
    # 20 s of which 12 s are taps -> 8 s; one nested in another span
    # (not a root) and one after the window are not read
    sp = [S(2, 1, "calibrate.taps", 1.0, 5.0, {}),
          S(1, 0, "calibrate", 1.0, 9.0, {}),
          S(4, 3, "calibrate.taps", 50.0, 62.0, {}),
          S(5, 3, "calibrate.cluster", 62.0, 69.0, {}),
          S(3, 0, "calibrate", 50.0, 70.0, {}),
          S(7, 6, "calibrate", 80.0, 81.0, {}),
          S(6, 0, "engine.step", 79.0, 82.0, {}),
          S(8, 0, "calibrate", 120.0, 130.0, {})]
    view = types.SimpleNamespace(w0=100.0)
    assert spans.calibrate_host_s(view, sp) == pytest.approx(8.0)
    assert spans.calibrate_host_s(types.SimpleNamespace(w0=0.5), sp) is None


def test_readers_silent_without_the_program_ring(monkeypatch):
    """A program with no ``host_spans`` (the commit before the spans):
    both metrics report nothing, and nothing raises."""
    fake = types.ModuleType("repro.obs.tracer")
    pkg = types.ModuleType("repro.obs")
    pkg.tracer = fake
    monkeypatch.setitem(sys.modules, "repro.obs", pkg)
    monkeypatch.setitem(sys.modules, "repro.obs.tracer", fake)
    assert spans.program_spans() is None
    view = types.SimpleNamespace(traced=[D(1.0, 2.0)], w0=3.0)
    assert spans.engine_host_ms(view) is None
    assert spans.calibrate_host_s(view) is None


def test_readers_read_the_program_ring():
    import time
    from repro.obs import tracer
    assert spans.program_spans() is tracer.host_spans()
    with tracer.span("calibrate"):
        with tracer.span("calibrate.taps"):
            time.sleep(0.01)
        time.sleep(0.02)
    got = spans.calibrate_host_s(types.SimpleNamespace(w0=time.perf_counter()))
    assert 0.02 <= got < 0.5
