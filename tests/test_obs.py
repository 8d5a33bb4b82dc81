"""repro.obs tests: the metrics registry (families, labels, histogram
quantiles, Prometheus text), hypothesis property tests on histogram
bucketing, the span tracer (span ordering, TTFT/ITL accounting, Chrome
trace validity), the device-resident metrics block (exact host-mirror
equality, NO extra drains in the hot loop, obs on == off token/dispatch
parity), the kernel-trace scopes, and the telemetry-export layout
dedupe (the slotted+paged double-report fix)."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_config
from repro.models import get_model
from repro.obs import (DeviceMetricsSpec, MetricsRegistry, Observability,
                       Tracer, validate_chrome_trace)
from repro.obs.device import SCALE
from repro.obs.tracer import host_spans, span
from repro.serving import Engine


# -- registry --------------------------------------------------------------

def test_registry_counter_gauge_labels():
    reg = MetricsRegistry()
    c = reg.counter("req_total", "requests", ("kind",))
    c.inc(kind="a")
    c.inc(2, kind="a")
    c.inc(kind="b")
    assert c.get(kind="a") == 3 and c.get(kind="b") == 1
    c.set(7, kind="b")                     # mirror semantics: idempotent
    c.set(7, kind="b")
    assert c.get(kind="b") == 7
    g = reg.gauge("depth", "queue depth")
    g.set(4)
    snap = reg.snapshot()
    assert snap["req_total"]["type"] == "counter"
    assert {tuple(v["labels"].items()) for v in
            snap["req_total"]["values"]} == {(("kind", "a"),),
                                             (("kind", "b"),)}
    assert snap["depth"]["values"][0]["value"] == 4
    # idempotent re-creation returns the same family; kind mismatch raises
    assert reg.counter("req_total", "requests", ("kind",)) is c
    with pytest.raises(AssertionError):
        reg.gauge("req_total", "nope")


def test_registry_histogram_summary_and_quantiles():
    reg = MetricsRegistry()
    h = reg.histogram("lat", "latency", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 4
    assert s["min"] == pytest.approx(0.05) and s["max"] == pytest.approx(5.0)
    assert 0.1 <= s["p50"] <= 1.0          # both 0.5s land in (0.1, 1]
    assert s["p99"] <= 5.0                 # clamped to observed max
    # beyond-last-bucket observations land in +Inf but keep exact max
    h.observe(100.0)
    assert h.summary()["max"] == pytest.approx(100.0)


def test_registry_prometheus_text():
    reg = MetricsRegistry()
    c = reg.counter("x_total", "xs", ("k",))
    c.inc(3, k="v")
    h = reg.histogram("d_seconds", "dur", buckets=(1.0, 2.0))
    h.observe(1.5)
    txt = reg.to_prometheus()
    assert '# TYPE x_total counter' in txt
    assert 'x_total{k="v"} 3' in txt
    assert 'd_seconds_bucket{le="2.0"} 1' in txt    # cumulative
    assert 'd_seconds_bucket{le="+Inf"} 1' in txt
    assert 'd_seconds_count 1' in txt


def test_registry_family_clear():
    reg = MetricsRegistry()
    h = reg.histogram("lat", "latency", buckets=(1.0,))
    h.observe(0.5)
    h.clear()
    assert h.summary()["count"] == 0


# -- histogram bucket invariants (property-tested under hypothesis) --------

def _check_histogram_invariants(values):
    reg = MetricsRegistry()
    edges = (0.001, 0.01, 0.1, 1.0, 10.0, 100.0)
    h = reg.histogram("h", "", buckets=edges)
    for v in values:
        h.observe(v)
    s = h.summary()
    row = next(iter(h.series()))[1]
    # bucket counts partition the observations (last slot = +Inf)
    assert sum(row.counts) == len(values) == s["count"]
    # each bucket count matches the definitional le-partition
    arr = np.asarray(values, np.float64)
    lo = 0.0
    for i, e in enumerate(edges):
        assert row.counts[i] == int(((arr > lo) & (arr <= e)).sum())
        lo = e
    assert row.counts[-1] == int((arr > edges[-1]).sum())
    assert s["sum"] == pytest.approx(float(arr.sum()), rel=1e-6)
    assert s["min"] == pytest.approx(float(arr.min()))
    assert s["max"] == pytest.approx(float(arr.max()))
    # quantiles are monotone and inside the observed range
    qs = [h.quantile(q) for q in (0.1, 0.5, 0.9, 0.99)]
    assert all(a <= b + 1e-12 for a, b in zip(qs, qs[1:]))
    assert all(s["min"] - 1e-12 <= q <= s["max"] + 1e-12 for q in qs)


@pytest.mark.parametrize("values", [
    [0.5], [1e-6, 1e3, 1e3], [0.001, 0.01, 0.1, 1.0, 10.0, 100.0],
    list(np.random.RandomState(0).uniform(1e-6, 200.0, size=64)),
    [150.0, 180.0], [0.0005] * 10 + [50.0] * 3,
])
def test_histogram_bucket_invariants(values):
    _check_histogram_invariants(values)


try:                                      # dev extra; CI installs it
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=50, deadline=None)
    @given(st.lists(st.floats(min_value=1e-6, max_value=1e3,
                              allow_nan=False), min_size=1, max_size=200))
    def test_histogram_bucket_invariants_property(values):
        _check_histogram_invariants(values)
except ModuleNotFoundError:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_histogram_bucket_invariants_property():
        pass


# -- tracer ----------------------------------------------------------------

def _drive_tracer(tr):
    """One scripted request: submit -> mixed dispatch (admit + full
    prefill, emits first token) -> two decode dispatches -> a last
    dispatch that finishes it.  Each dispatch's tokens reach the host
    2 ms after it was enqueued; the last delivery carries two tokens
    (a speculative round's burst)."""
    t = [100.0]

    def tick(dt):
        t[0] += dt
        return t[0]

    tr.on_submit(7, t=tick(0.0))
    t0 = tick(0.010)                        # queued 10ms
    tr.on_dispatch("mixed", t0, tick(0.020), admitted=[(0, 7)],
                   prefilling=[(0, 7, 0, 8)], emits=[(0, 7)],
                   finished=[], queue_depth=0, n_active=1)
    tr.on_deliver(7, 1, tick(0.002))
    for _ in range(2):
        t0 = tick(0.001)
        tr.on_dispatch("decode", t0, tick(0.005), admitted=[],
                       prefilling=[], emits=[(0, 7)], finished=[],
                       queue_depth=0, n_active=1)
        tr.on_deliver(7, 1, tick(0.002))
    t0 = tick(0.001)
    tr.on_dispatch("decode", t0, tick(0.005), admitted=[], prefilling=[],
                   emits=[(0, 7)], finished=[7], queue_depth=0,
                   n_active=1)
    tr.on_deliver(7, 2, tick(0.002))


def test_tracer_span_ordering_and_latencies():
    reg = MetricsRegistry()
    tr = Tracer(registry=reg)
    with span("engine.step", kind="decode"):
        _drive_tracer(tr)
    s = tr.summary()
    assert s["n_requests"] == 1 and s["n_dispatches"] == 4
    # TTFT = submit -> first delivery = 10 + 20 + 2 ms, not the end of
    # the dispatch that emitted it (its enqueue)
    assert s["ttft"]["count"] == 1
    assert s["ttft"]["max"] == pytest.approx(0.032, abs=1e-6)
    # ITL between deliveries 8ms apart, and 0 between the two tokens
    # delivered together
    assert s["itl"]["count"] == 4
    assert s["itl"]["max"] == pytest.approx(0.008, abs=1e-6)
    assert s["itl"]["min"] == 0.0
    assert s["queue_wait"]["max"] == pytest.approx(0.010, abs=1e-6)
    assert tr.request_spans()[7]["n_tokens"] == 5
    obj = tr.to_chrome_trace()
    assert validate_chrome_trace(obj) == []
    pid_name = {e["pid"]: e["args"]["name"] for e in obj["traceEvents"]
                if e["ph"] == "M" and e["name"] == "process_name"}
    evs = [e for e in obj["traceEvents"] if e["ph"] == "X"]
    by_name = {}
    for e in evs:
        by_name.setdefault(e["name"].split()[0].split("/")[0],
                           []).append(e)
    # spans nest: queued precedes prefill, prefill precedes decode,
    # the request span covers submit -> its last token's delivery
    q = by_name["queued"][0]
    pf = by_name["prefill"][0]
    dec = by_name["decode"]
    req = by_name["request"][0]
    assert q["ts"] + q["dur"] <= pf["ts"] + 1
    assert all(pf["ts"] + pf["dur"] <= d["ts"] + 1
               for d in dec[1:])           # decode spans after prefill
    assert req["ts"] <= q["ts"]
    assert req["dur"] == pytest.approx(56e3, abs=1)
    assert req["args"]["ttft_s"] == pytest.approx(0.032, abs=1e-6)
    # dispatch spans ride the engine pid, slot spans the slots pid, the
    # host span ring's spans (args included) the host pid
    assert {pid_name[e["pid"]] for e in by_name["dispatch"]} == {"engine"}
    assert pid_name[pf["pid"]] == "slots"
    (host,) = by_name["engine.step"]
    assert pid_name[host["pid"]] == "host"
    assert host["args"]["kind"] == "decode"


def test_tracer_reset_clears_histograms():
    reg = MetricsRegistry()
    tr = Tracer(registry=reg)
    _drive_tracer(tr)
    tr.reset()
    assert tr.summary()["n_requests"] == 0
    assert reg.get("repro_serving_ttft_seconds").summary()["count"] == 0
    _drive_tracer(tr)                       # usable after reset
    assert tr.summary()["ttft"]["count"] == 1


def test_trace_validator_flags_malformed():
    assert validate_chrome_trace({"nope": 1})
    assert validate_chrome_trace({"traceEvents": [{"ph": "X"}]})
    assert validate_chrome_trace(
        {"traceEvents": [{"ph": "X", "name": "a", "pid": "p",
                          "ts": 1.0, "dur": -2.0}]})
    assert validate_chrome_trace(
        {"traceEvents": [{"ph": "X", "name": "a", "pid": "p",
                          "ts": 1.0, "dur": 2.0}]}) == []


# -- device metrics block --------------------------------------------------

def test_device_metrics_accumulate_and_read():
    spec = DeviceMetricsSpec({"mor_stats": (3,)})
    blk = spec.init()
    aux = {"mor_stats": {
        "n_tiles": jnp.asarray([10, 10, 10], jnp.int32),
        "tiles_skipped": jnp.asarray([2, 0, 5], jnp.int32),
        "frac_tiles_live": jnp.asarray([0.8, 1.0, 0.5], jnp.float32)}}
    scalars = {"dispatches": 1, "prefill_tokens": 16, "decode_tokens": 0,
               "pages_touched": 4, "kv_page_resets": 2,
               "kv_page_copies": 0, "state_page_resets": 0,
               "state_page_copies": 0}
    for _ in range(2):
        blk = spec.accumulate(blk, scalars, aux)
    out = spec.read(blk)
    assert out["dispatches"] == 2 and out["prefill_tokens"] == 32
    assert out["kv_page_resets"] == 4
    g = out["groups"]["mor_stats"]
    np.testing.assert_array_equal(g["tiles_total"], [20, 20, 20])
    np.testing.assert_array_equal(g["tiles_skipped"], [4, 0, 10])
    np.testing.assert_allclose(g["mean_frac_tiles_live"],
                               [0.8, 1.0, 0.5], atol=1.5 / SCALE)
    # multi-row (sharded) blocks: header from row 0, shard-local summed
    blk2 = spec.init(n_rows=2)
    blk2 = blk2 + jnp.stack([spec.delta(scalars, aux)] * 2)
    out2 = spec.read(blk2)
    assert out2["dispatches"] == 1          # replicated header, row 0
    assert out2["kv_page_resets"] == 4      # shard-local, row-summed


# -- engine integration: parity + no extra drains --------------------------

def _mini_engine(obs=None, layout="paged"):
    cfg = reduce_config(get_config("granite-3-2b"))
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, mor_mode="dense", n_slots=2, max_len=96,
                 chunk=8, layout=layout, obs=obs)
    rng = np.random.default_rng(3)
    reqs = [(rng.integers(1, cfg.vocab_size, size=n).astype(np.int32), 5)
            for n in (9, 17, 6)]
    return eng, reqs


def test_engine_obs_on_off_parity_and_single_drain(monkeypatch):
    """The obs stack must not change WHAT the engine computes (tokens,
    dispatch count) and must not add hot-loop device syncs: the metrics
    block is drained host-side exactly once, at the flush boundary."""
    eng_off, reqs = _mini_engine(obs=None)
    out_off = eng_off.run([(p.copy(), g) for p, g in reqs])

    obs = Observability()
    eng_on, _ = _mini_engine(obs=obs)
    calls = {"step": 0, "drain": 0}
    inner_step = eng_on._step

    def counting_step(*a, **kw):
        calls["step"] += 1
        return inner_step(*a, **kw)

    eng_on._step = counting_step
    spec = eng_on._mspec
    assert spec is not None
    inner_read = spec.read

    def counting_read(block):
        calls["drain"] += 1
        return inner_read(block)

    monkeypatch.setattr(spec, "read", counting_read)
    out_on = eng_on.run([(p.copy(), g) for p, g in reqs])

    assert {r: list(map(int, np.asarray(t))) for r, t in out_on.items()} \
        == {r: list(map(int, np.asarray(t))) for r, t in out_off.items()}
    assert calls["step"] == eng_off.counters["dispatches"] \
        == eng_on.counters["dispatches"]
    assert calls["drain"] == 1              # one drain at run()'s flush
    # the device block mirrors the host counters exactly
    dm = eng_on._last_device_metrics
    for k in ("dispatches", "prefill_tokens", "decode_tokens"):
        assert dm[k] == eng_on.counters[k], (k, dm[k], eng_on.counters)


def test_engine_report_obs_sections():
    obs = Observability()
    eng, reqs = _mini_engine(obs=obs)
    eng.run(reqs)
    rep = eng.report()
    assert rep["obs"]["device_metrics"]["dispatches"] == rep["dispatches"]
    t = rep["obs"]["tracing"]
    assert t["n_requests"] == len(reqs)
    assert t["ttft"]["count"] == len(reqs)
    obj = obs.tracer.to_chrome_trace()
    assert validate_chrome_trace(obj) == []
    assert json.loads(json.dumps(rep["obs"])) == rep["obs"]  # JSON-safe
    # registry landed the device counts under the engine families
    reg = obs.registry
    assert reg.get("repro_engine_dispatches_total") \
              .get(layout="paged") == rep["dispatches"]
    # the span ring is mirrored into the span counters at the flush
    assert reg.get("repro_engine_spans_total") \
              .get(span="engine.step") == rep["dispatches"]
    assert reg.get("repro_engine_span_seconds_total") \
              .get(span="engine.enqueue") > 0


# -- host spans inside the engine ----------------------------------------

ENGINE_STEP_CHILDREN = ["engine.admit", "engine.plan", "engine.enqueue",
                        "engine.feed"]


@pytest.fixture(scope="module")
def engine_ticks(tmp_path_factory):
    """A reduced paged engine driven for a few step/drain ticks (as the
    chip harness drives it) under a CPU profiler trace: the span ring's
    spans of those ticks, what the pool's ``advance`` saw per dispatch,
    and the trace's xplane."""
    eng, reqs = _mini_engine()
    for p, g in reqs:
        eng.submit(p, g)
    booked = []
    advance = eng.pool.advance

    def booked_advance(n_valid):
        booked.append((eng.pool.pos.copy(), np.asarray(n_valid).copy()))
        return advance(n_valid)

    eng.pool.advance = booked_advance
    trace_dir = tmp_path_factory.mktemp("trace")
    host_spans().clear()
    n_ticks = 4
    with jax.profiler.trace(str(trace_dir)):
        for _ in range(n_ticks):
            eng.step()
            eng.drain()
    (xplane,) = trace_dir.glob("plugins/profile/*/*.xplane.pb")
    return list(host_spans()), booked, n_ticks, eng, xplane


def _inside(child, parent):
    return parent.t0 <= child.t0 and child.t1 <= parent.t1


def test_engine_span_tree(engine_ticks):
    spans, booked, n_ticks, eng, _ = engine_ticks
    by_id = {s.id: s for s in spans}
    roots = sorted((s for s in spans if s.parent == 0), key=lambda s: s.t0)
    assert [s.name for s in roots] == \
        ["engine.step", "engine.drain"] * n_ticks
    kids = {}
    for s in spans:
        if s.parent:
            assert _inside(s, by_id[s.parent]), s
            kids.setdefault(s.parent, []).append(s)
    steps = [s for s in roots if s.name == "engine.step"]
    for sp, (pos, n_valid) in zip(steps, booked, strict=True):
        names = [c.name for c in sorted(kids[sp.id], key=lambda c: c.t0)]
        assert names == ENGINE_STEP_CHILDREN
        # the step's args carry what the pool's advance was handed
        np.testing.assert_array_equal(sp.args["n_valid"], n_valid)
        np.testing.assert_array_equal(sp.args["pos"], pos)
        chunk = eng.chunk if sp.args["kind"] == "mixed" else 1
        assert sp.args["rows"] == eng.n_slots * chunk
        assert sp.args["admitted"] == (2 if sp is steps[0] else 0)
    fetches = [s for s in spans if s.name == "engine.fetch"]
    assert fetches and all(by_id[f.parent].name == "engine.drain"
                           for f in fetches)
    for dr in (s for s in roots if s.name == "engine.drain"):
        names = [c.name for c in sorted(kids[dr.id], key=lambda c: c.t0)]
        assert names[-2:] == ["engine.telemetry", "engine.obs"]
        assert names[:-2] in ([], ["engine.fetch", "engine.deliver"])


def test_engine_spans_in_device_trace(engine_ticks):
    """The same spans are profiler annotations: the xplane's host plane
    holds them, nested as in the ring."""
    from jax.profiler import ProfileData
    _, _, n_ticks, _, xplane = engine_ticks
    evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
           for p in ProfileData.from_file(str(xplane)).planes
           if not p.name.startswith("/device:")
           for ln in p.lines for e in ln.events
           if e.name.startswith("engine.")]
    by_name = {}
    for ev in evs:
        by_name.setdefault(ev[0], []).append(ev)
    assert len(by_name["engine.step"]) == len(by_name["engine.drain"]) \
        == n_ticks

    def within(name, parent):
        return all(any(a0 <= c0 and c1 <= a1
                       for _, a0, a1 in by_name[parent])
                   for _, c0, c1 in by_name[name])
    assert all(within(c, "engine.step") for c in ENGINE_STEP_CHILDREN)
    assert within("engine.fetch", "engine.drain")


def test_compile_span_names_the_jitted_step(engine_ticks):
    spans = engine_ticks[0]
    by_id = {s.id: s for s in spans}
    comp = [s for s in spans if s.name == "jax.compile"
            and "engine_step" in s.args["fun"]]
    assert comp
    assert all(by_id[s.parent].name == "engine.enqueue" for s in comp)


# -- kernel trace scopes ---------------------------------------------------

def test_kernel_trace_scopes():
    from repro.kernels import paged_attention as pk
    pk.reset_kernel_traces()
    base = pk.kernel_traces()
    assert set(base) == {"gqa", "mla"} and sum(base.values()) == 0
    pk._bump_trace("gqa")
    with pk.trace_scope() as inner:
        pk._bump_trace("gqa")
        pk._bump_trace("mla")
        assert pk.kernel_traces() == {"gqa": 1, "mla": 1}  # innermost
        with pk.trace_scope() as deepest:
            pk._bump_trace("mla")
            assert pk.kernel_traces() == {"gqa": 0, "mla": 1}
        assert deepest == {"gqa": 0, "mla": 1}  # survives scope exit
    assert inner == {"gqa": 1, "mla": 2}
    assert pk.kernel_traces() == {"gqa": 2, "mla": 2}  # root saw all
    pk.reset_kernel_traces()
    assert sum(pk.kernel_traces().values()) == 0


# -- telemetry export: layout dedupe (the double-report fix) ---------------

def test_export_telemetry_layout_dedupe():
    """Slotted + paged engines sharing one registry in one process must
    not double-report: every series is keyed by layout and written with
    idempotent set, so re-export overwrites itself and the two layouts
    coexist as distinct series."""
    from repro.serving.telemetry import ServingTelemetry, export_telemetry
    reg = MetricsRegistry()
    tel = ServingTelemetry()
    tel.update({"mor_stats": {
        "frac_tiles_live": jnp.asarray([0.5, 1.0]),
        "frac_computed": jnp.asarray([0.5, 1.0]),
        "frac_tiles_computed": jnp.asarray([0.5, 1.0])}})
    caps = {"mor_stats": np.asarray([0.6, 0.9])}
    for _ in range(2):                      # re-export: idempotent
        export_telemetry(reg, tel, layout="slotted", capacities=caps)
        export_telemetry(reg, tel, layout="paged", capacities=caps)
    snap = reg.snapshot()
    cap_rows = snap["repro_telemetry_capacity"]["values"]
    by_layout = {}
    for v in cap_rows:
        by_layout.setdefault(v["labels"]["layout"], []).append(v["value"])
    assert set(by_layout) == {"slotted", "paged"}
    # exactly one series per (layout, layer) — no duplicate appends
    assert sorted(by_layout["slotted"]) == [0.6, 0.9]
    assert sorted(by_layout["paged"]) == [0.6, 0.9]
