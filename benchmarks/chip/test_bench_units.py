"""Unit tests of the benchmark's yardstick: the trace reduction, the
operation and byte counts, the peak table, the window arithmetic and
the traffic generator.  CPU only; no program is driven here."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import cost, peaks, readers, xtrace  # noqa: E402
from chipbench.traffic import Traffic, TrafficSpec  # noqa: E402
from chipbench.window import ReqLog, p95, window_stats  # noqa: E402

HERE = Path(__file__).resolve().parent


# -- trace reduction --------------------------------------------------------------

def _xspace(device_ops, host_spans, n_devices=1):
    """A synthetic XSpace text proto: device ops (name, start_us, dur_us)
    on each device's ``XLA Ops`` line, host spans on one host thread."""
    names = sorted({n for n, _, _ in device_ops} |
                   {n for n, _, _ in host_spans} | {"jit_step"})
    ids = {n: i + 1 for i, n in enumerate(names)}
    meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}\n' for n, i in ids.items())

    def events(evs):
        return "".join(f"events {{ metadata_id: {ids[n]} "
                       f"offset_ps: {int(a * 1e6)} "
                       f"duration_ps: {int(d * 1e6)} }}\n"
                       for n, a, d in evs)
    planes = ""
    for k in range(n_devices):
        planes += (f'planes {{ id: {k + 1} name: "/device:TPU:{k}"\n'
                   f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0\n'
                   f'{events(device_ops)} }}\n'
                   f'lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0\n'
                   f'{events([("jit_step", 0, 1000)])} }}\n{meta} }}\n')
    planes += (f'planes {{ id: 99 name: "/host:CPU"\n'
               f'lines {{ id: 1 name: "python" timestamp_ns: 0\n'
               f'{events(host_spans)} }}\n{meta} }}\n')
    return planes


DEVICE_OPS = [("gather_matmul.1", 10, 20), ("gather_matmul.7", 25, 10),
              ("mor_tile_mask.3", 30, 15), ("fusion.2", 60, 10),
              ("gqa_paged_flash", 80, 5), ("_unknown_.4", 86, 4)]
HOST = [("bench.step", 0, 40), ("bench.drain", 40, 30),
        ("bench.idle", 70, 30), ("other_span", 0, 100)]


@pytest.fixture
def xplane_file(tmp_path):
    from jax.profiler import ProfileData
    raw = ProfileData.text_proto_to_serialized_xspace(
        _xspace(DEVICE_OPS, HOST, n_devices=2))
    d = tmp_path / "plugins" / "profile" / "run1"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(raw)
    return tmp_path


def test_trace_busy_union_and_window(xplane_file):
    s = xtrace.reduce_dir(xplane_file, {"gather_matmul": ("gather_matmul",)})
    # window: first to last bench.* span, 0..100 us; busy union of
    # [10,45) [60,70) [80,85) [86,90) = 35 + 10 + 5 + 4 us on each of
    # two devices
    assert s.n_devices == 2
    assert s.window_s == pytest.approx(100e-6)
    assert s.busy_s == pytest.approx(54e-6)
    assert s.idle_share == pytest.approx(0.46)


def test_trace_kernel_time_and_counts(xplane_file):
    s = xtrace.reduce_dir(xplane_file, {
        "gather_matmul": ("gather_matmul",),
        "mor_tile_mask": ("mor_tile_mask",),
        "masked_matmul_kdim": ("masked_matmul_kdim",),
        "gqa_paged_flash": ("gqa_paged_flash", "_unknown_")})
    assert s.kernel_s["gather_matmul"] == pytest.approx(30e-6)
    assert s.kernel_n == {"gather_matmul": 2, "mor_tile_mask": 1,
                          "masked_matmul_kdim": 0, "gqa_paged_flash": 2}
    assert s.kernel_s["gqa_paged_flash"] == pytest.approx(9e-6)
    assert s.kernel_s["mor_tile_mask"] == pytest.approx(15e-6)
    assert dict(s.top_ops)["gather_matmul"] == pytest.approx(30e-6)


def test_trace_idle_gaps_attributed_to_host_spans(xplane_file):
    s = xtrace.reduce_dir(xplane_file, {"gather_matmul": ("gather_matmul",)})
    # per device: [90, 100), [85, 86) and [70, 80) lie in bench.idle,
    # [45, 60) in bench.drain, [0, 10) in bench.step; longest first
    gaps = s.idle_gaps
    assert len(gaps) == 10 and gaps[0][1] >= gaps[-1][1]
    assert gaps[0] == ("bench.drain", pytest.approx(15e-6))
    assert ("bench.idle", pytest.approx(10e-6)) in gaps
    assert ("bench.drain", pytest.approx(15e-6)) in gaps
    assert ("bench.step", pytest.approx(10e-6)) in gaps
    assert all(g[0] != "other_span" for g in gaps)


def test_trace_without_device_reads_nothing(tmp_path):
    from jax.profiler import ProfileData
    text = _xspace([], HOST, n_devices=0)
    assert xtrace.reduce_profile(ProfileData.from_text_proto(text),
                                 {}) is None
    assert xtrace.reduce_dir(tmp_path, {}) is None


@pytest.mark.parametrize("name,base", [
    ("gather_matmul.17", "gather_matmul"), ("fusion.2.1", "fusion"),
    ("copy", "copy"), ("_unknown_.2", "_unknown_"),
    ("%masked_matmul_kdim.49 = bf16[640,2048]{1,0:T(8,128)(2,1)S(1)} "
     "custom-call(s32[5120]{0:T(1024)S(1)} %reshape.2159)",
     "masked_matmul_kdim"),
    ("%while.57 = (s32[]{:T(128)}, bf16[24,32,2560]) while(%tuple)", "while")])
def test_op_base(name, base):
    assert xtrace.op_base(name) == base


# -- operation and byte counts -----------------------------------------------------

SMALL = cost.Shape("gqa_glu", layers=2, d=16, d_ff=256, vocab=100,
                   heads=4, kv_heads=2, head_dim=4)


def test_kernel_counts_by_hand():
    # mor_tile_mask: 2*M*K*N sign MACs; weight bf16, x bf16, int8 proxy
    # verdicts, int32 tile mask of (M/8, N)
    assert cost.mor_tile_mask(8, 16, 256) == (2 * 8 * 16 * 256,
                                              16 * 256 * 2 + 8 * 16 * 2 +
                                              8 * 256 + 1 * 256 * 4)
    # gather_matmul at half the tiles live
    f, b = cost.gather_matmul(8, 16, 256, 0.5)
    assert f == 8 * 16 * 256
    assert b == 0.5 * 16 * 256 * 2 + 8 * 16 * 2 + 0.5 * 8 * 256 * 2
    f, b = cost.masked_matmul_kdim(8, 256, 16, 1.0)
    assert f == 2 * 8 * 256 * 16
    assert b == 256 * 16 * 2 + 8 * 256 * 2 + 8 * 16 * 2


def test_paged_attention_counts_by_hand():
    # slot 0 decodes at position 5 (6 keys), slot 1 prefills 3 tokens
    # from 0 (1 + 2 + 3 keys), slot 2 idle
    pos = np.array([5, 0, 7])
    nv = np.array([1, 3, 0])
    f, b = cost.gqa_paged_flash(SMALL, pos, nv)
    assert f == 4 * (6 + 6) * 4 * 4
    keys = 6 + 3
    assert b == keys * (2 * 2 * 4 * 2 + 4) + 2 * 4 * 4 * 4 * 2


def test_dispatch_counts_by_hand():
    pos, nv = np.array([5, 0]), np.array([1, 1])
    per = cost.dispatch_kernels(SMALL, 16, pos, nv, 1.0)
    assert set(per) == {"mor_tile_mask", "gather_matmul",
                        "masked_matmul_kdim", "gqa_paged_flash"}
    assert per["gather_matmul"][0] == 2 * 2 * (2 * 16 * 16 * 256)
    flops = cost.dispatch_model_flops(SMALL, pos, nv, 1.0)
    per_layer = (16 * 16 * 2 + 2 * 16 * 8) + 3 * 16 * 256
    attn = 4 * (6 + 1) * 4 * 4
    assert flops == 2 * 2 * (2 * per_layer + 16 * 100) + 2 * attn
    byts = cost.dispatch_bytes(SMALL, pos, nv, 1.0)
    assert byts == (2 * (per_layer + 2 * 16 * 256) + 16 * 100) * 2 + \
        2 * cost.gqa_paged_flash(SMALL, pos, nv)[1]


def test_shape_from_config_files():
    import json
    c = json.loads((HERE / "configs" / "granite-3-2b.json").read_text())
    s = cost.Shape.from_config(c)
    assert s.layers == c["num_hidden_layers"]
    assert s.head_dim == 64


# -- peaks ---------------------------------------------------------------------------

def test_peaks_known_device():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5e", ""])
def test_peaks_refuse_unknown_device(kind):
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for(kind)


# -- window arithmetic ------------------------------------------------------------------

def _req(i, t_sched, times, max_new=None):
    r = ReqLog(index=i, max_new=max_new or len(times),
               t_sched=t_sched, t_submit=t_sched + 0.001)
    r.token_t = list(times)
    return r


def test_window_tokens_ttft_itl_with_a_stall():
    w0, w1 = 10.0, 20.0
    reqs = [
        # steady 0.1 s cadence through the whole window
        _req(0, 9.0, [9.5 + 0.1 * k for k in range(110)]),
        # scheduled inside the window, stalled 3 s before its first token
        _req(1, 12.0, [15.0, 15.1, 18.2, 18.3]),
        # scheduled inside the window, first token only after the close
        _req(2, 19.5, [21.0, 21.1]),
        # scheduled before the window: not attempted in it
        _req(3, 5.0, [5.5, 5.6]),
    ]
    st = window_stats(reqs, w0, w1)
    in_window = sum(1 for r in reqs for t in r.token_t if w0 <= t < w1)
    assert st["tokens"] == in_window
    assert st["out_tok_s"] == pytest.approx(in_window / 10.0)
    assert st["attempted"] == 2 and st["failed"] == 0
    assert sorted(st["ttft_s"]) == pytest.approx([1.5, 3.0])
    # the 3.1 s stall of request 1 is one of the gaps in the window
    assert max(st["itl_s"]) == pytest.approx(3.1)
    assert st["itl_p95_ms"] == pytest.approx(1e3 * p95(st["itl_s"]))
    assert st["gen_lag_p95_ms"] == pytest.approx(1.0)


def test_window_counts_a_request_with_no_token_as_failed():
    st = window_stats([_req(0, 11.0, [], max_new=4)], 10.0, 20.0)
    assert st["attempted"] == 1 and st["failed"] == 1
    assert st["ttft_p95_ms"] is None and st["out_tok_s"] == 0


def test_window_closed_loop_attempts_are_requests_served():
    reqs = [_req(0, 1.0, [10.5, 10.6]), _req(1, 1.0, [30.0])]
    st = window_stats(reqs, 10.0, 20.0, closed_loop=True)
    assert st["attempted"] == 1 and st["ttft_s"] == []


# -- traffic ------------------------------------------------------------------------------

def _spec(name):
    return TrafficSpec.load(HERE / "traffic" / f"{name}.json")


def test_traffic_seeds_share_sizes():
    spec = _spec("decode")
    K = spec.cycle
    a, b = Traffic(spec, 1, 1000), Traffic(spec, 2**31 + 7, 1000)
    assert sorted(a.sizes(i) for i in range(K)) == \
        sorted(b.sizes(i) for i in range(K))
    assert [a.sizes(i) for i in range(K)] != [b.sizes(i) for i in range(K)]
    p, o = zip(*(a.sizes(i) for i in range(K)))
    assert min(p) >= spec.prompt_len.lo and max(p) <= spec.prompt_len.hi
    assert min(o) >= spec.output_len.lo and max(o) <= spec.output_len.hi


def test_traffic_request_is_deterministic_per_seed():
    spec = _spec("decode")
    a, b = Traffic(spec, 5, 1000), Traffic(spec, 5, 1000)
    pa, oa = a.request(3)
    pb, ob = b.request(3)
    assert oa == ob and np.array_equal(pa, pb)
    assert pa.dtype == np.int32 and pa.min() >= 0 and pa.max() < 1000


def test_stationary_stand_ins_are_the_same_for_every_seed():
    spec = _spec("decode")
    a, b = Traffic(spec, 3, 1000), Traffic(spec, 2**33 + 1, 1000)
    sizes = a.stationary_sizes()
    assert sizes == b.stationary_sizes() and len(sizes) == spec.slots
    assert all(o >= 1 and spec.prompt_len.lo <= p and p + o <= spec.longest
               for p, o in sizes)
    reqs = a.stationary()
    assert [(len(p), o) for p, o in reqs] == sizes
    assert not np.array_equal(reqs[0][0], b.stationary()[0][0][:len(
        reqs[0][0])])


def test_stationary_stand_ins_follow_the_long_run_law():
    """Each slot holds a request picked in proportion to its output
    length, at a uniform point of it: by hand on a two-size law."""
    spec = TrafficSpec.from_dict("t", {
        "slots": 4, "max_len": 64, "backlog": 1, "cycle": 2,
        "prompt_len": {"dist": "uniform", "min": 10, "max": 10},
        "output_len": {"dist": "uniform", "min": 10, "max": 30},
        "check": {}})
    t = Traffic(spec, 1, 100)
    # cycle lengths 15 and 25 at weights 15/40 and 25/40; slot k takes
    # the weighted quantile (k + 0.5) / 4 and one of four even shares
    lengths = sorted(p - 10 + o for p, o in t.stationary_sizes())
    assert lengths == [15, 15, 25, 25]
    served = sorted((p - 10) / (p - 10 + o) for p, o in t.stationary_sizes())
    assert served == pytest.approx([0.125, 0.375, 0.625, 0.875], abs=0.07)


def test_check_sample_tops_up_from_before_the_window():
    from chipbench import check

    def fin(n_tok, t_last):
        r = ReqLog(index=0, max_new=n_tok, t_sched=0.0, t_submit=0.0)
        r.token_t = [t_last] * n_tok
        return r, check.Served(np.zeros(4, np.int32),
                               np.zeros(n_tok, np.int32))
    reqs = [fin(3, 12.0), fin(50, 5.0), fin(7, 6.0)]
    got = check.sample(reqs, 10.0, 1, tokens=1000, min_requests=2,
                       max_requests=3)
    # the window's only request first, the rest from before it
    assert got[0] is reqs[0][1] and len(got) == 3
    got = check.sample(reqs, 10.0, 1, tokens=1, min_requests=2,
                       max_requests=3)
    assert len(got) == 2 and got[0] is reqs[0][1]
    assert check.sample(reqs[1:], 10.0, 1, 1, 1, 3)[0] is reqs[1][1]


def test_readers_share_arithmetic_on_empty_view():
    class V:
        trace = None
        traced = []
        trace_host_s = 0.0
    assert readers.step_mfu(V) is None
    assert readers.idle_share(V) is None


# -- the benchmark file ----------------------------------------------------------------

def test_benchmark_file_names_files_that_exist():
    import json
    import re
    root = HERE.parents[1]
    b = json.loads((root / "BENCHMARK.json").read_text())
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    configs = {c["name"]: c for c in b["configs"]}
    for c in configs.values():
        assert (root / c["file"]).is_file() and name.match(c["name"])
    cells = {w["name"]: w for w in b["workloads"]}
    for w in cells.values():
        assert w["config"] in configs and name.match(w["name"])
        assert (HERE / "traffic" / f"{w['traffic']}.json").is_file()
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in b["end_to_end"] + b["per_layer"]:
        assert name.match(m["name"])
        assert (HERE / "metrics" / f"{m['name']}.py").is_file()
        assert set(m.get("workloads", cells)) <= set(cells)
        if "moves" in m:
            assert m["moves"] in e2e
            moved = next(x for x in b["end_to_end"] if x["name"] == m["moves"])
            assert set(m["workloads"]) <= set(moved.get("workloads", cells))
    for cell in cells:
        reported = [m for m in b["end_to_end"]
                    if cell in m.get("workloads", [cell])]
        assert "setup_s" in {m["name"] for m in reported}
        assert len(reported) >= 2
        assert any(cell in m["workloads"] for m in b["per_layer"])
