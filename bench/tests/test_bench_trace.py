"""The benchmark's reduction of a profiler trace and its roofline counts."""

import math
import os
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import roofline  # noqa: E402
import spec  # noqa: E402
import tracing  # noqa: E402

V5E = roofline.peaks("TPU v5 lite")


def ev(name, start, dur):
    return SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def plane(name, lines):
    return SimpleNamespace(name=name, lines=[
        SimpleNamespace(name=ln, events=evs) for ln, evs in lines.items()])


def profile(device_ops, mark_at=0):
    return SimpleNamespace(planes=[
        plane("/device:TPU:0", {"XLA Ops": device_ops,
                                "XLA Modules": [ev("jit_main", 0, 1000)]}),
        plane("/host:CPU", {"main": [ev("other", 0, 5),
                                     ev(tracing.MARK, mark_at, 0)]}),
    ])


def reduce(ops, seconds=100e-9, spans=(), mark_at=0):
    return tracing.from_profile(profile(ops, mark_at), 1, seconds,
                                host_spans=spans, host_start=10.0)


# device busy [0, 20) [30, 40) [50, 60) of a [0, 100) window
OPS = [ev("%fusion.1 = f32[8] fusion(...)", 0, 10),
       ev("%pearson_pallas.3 = f32[8,8]{1,0} custom-call(...)", 5, 15),
       ev("%minplus_pallas.5 = f32[4,24,512]{2,1,0} custom-call(...)", 30,
          10),
       ev("%while.2 = (s32[]) while(...)", 50, 10),
       ev("%outside.1 = f32[] add(...)", 200, 10)]
# host-clock spans, the marker at host time 10.0
SPANS = [("bench.call", 10.0, 10.0 + 45e-9),
         ("bench.drain", 10.0 + 55e-9, 10.0 + 100e-9)]


def test_busy_union_idle_share_and_kernel_time():
    t = reduce(OPS, spans=SPANS)
    assert t.window == (0, 100)
    assert t.busy_s() == pytest.approx(40e-9)
    assert t.window_s == pytest.approx(100e-9)
    assert tracing.idle_pct(t) == pytest.approx(60.0)
    assert t.kernel("pearson_pallas") == (1, pytest.approx(15e-9))
    assert t.kernel("minplus_pallas") == (1, pytest.approx(10e-9))
    assert t.kernel_batches("minplus_pallas") == [(4, pytest.approx(10e-9))]
    assert t.kernel_batches("pearson_pallas") == [(1, pytest.approx(15e-9))]
    assert tracing.kernel_seconds(t, "topk_rows_pallas") is None
    share = spec.reader("pallas_share.batch")(SimpleNamespace(trace=t))
    assert share == pytest.approx(100.0 * 25 / 40)


def test_idle_gaps_are_named_by_the_innermost_covering_span():
    t = reduce(OPS, spans=SPANS)
    gaps = t.idle_gaps()
    assert gaps[0] == ["bench.drain", pytest.approx(40e-9)]
    assert sorted(g[0] for g in gaps[1:]) == ["bench.call", "bench.call"]
    assert t.top_ops(2)[0] == ["%pearson_pallas.3", pytest.approx(15e-9)]


def test_the_window_starts_at_the_marker_and_lasts_the_traced_seconds():
    t = reduce(OPS, seconds=30e-9, mark_at=20)
    assert t.window == (20, 50)
    assert t.busy_s() == pytest.approx(10e-9)     # [30, 40)
    assert t.kernel_batches("pearson_pallas") == []   # began before it


def test_union_of_nested_and_disjoint_intervals():
    assert tracing.union_length([(0, 10), (2, 3), (10, 12), (20, 25)]) == 17
    assert tracing.union_length([]) == 0


def test_marker_required():
    pd = profile(OPS)
    pd.planes[1].lines[0].events.pop()
    with pytest.raises(ValueError):
        tracing.from_profile(pd, 1, 1.0)


def test_recorded_trace_carries_the_window_span(tmp_path):
    import jax
    import jax.numpy as jnp

    import drivers

    run = drivers.Run()
    prof = drivers.Profiler(str(tmp_path), 30.0)
    with drivers.window(run, prof):
        with run.spans.span("bench.call"):
            jnp.ones((64, 64)).sum().block_until_ready()
    start, traced = run.traced
    assert 0 < traced < 30.0
    t = tracing.read_dir(str(tmp_path), 1, traced, run.spans.events, start)
    assert t.window_s == pytest.approx(traced)
    assert [s[0] for s in t.spans] == ["bench.call", tracing.WINDOW_SPAN]
    lo, hi = t.window
    assert lo <= t.spans[0][1] <= t.spans[0][2] <= hi
    assert run.compiles >= 1          # the ones() program compiled in it


def test_pearson_counts_by_hand():
    # 2 n^2 L multiply-adds; the series read once, the matrix written once
    assert roofline.pearson_counts(1000, 50) == (1e8, 4.2e6)


def test_minplus_bytes_by_hand():
    # hub APSP at n=400: h = 20; (h, n) x (n, n) and (n, h) x (h, n) both
    # move h n + h n + n^2 floats
    assert roofline.hub_count(400) == 20
    assert roofline.apsp_product_bytes(400) == (8000 + 8000 + 160000) * 4
    assert roofline.minplus_bytes(400, 20, 400) == (8000 + 8000 + 160000) * 4
    # below 200 vertices the stage squares (n, n)
    assert roofline.apsp_product_bytes(100) == 3 * 100 * 100 * 4


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def test_bytes_only_bound_never_reads_over_100():
    n, B = 19412, 4
    least = B * roofline.apsp_product_bytes(n) / V5E["hbm_bytes_per_s"]
    # a kernel exactly at the byte bound reads 100%, however many
    # operations min-plus does (no VPU peak is used)
    assert roofline.least_seconds(1e30, 1.0, V5E, bytes_only=True) == \
        pytest.approx(1.0 / V5E["hbm_bytes_per_s"])
    read = spec.reader("minplus.roofline")
    for factor in (1.0, 1.5, 40.0):
        dur = math.ceil(least * factor * 1e9)
        call = f"%minplus_pallas.1 = f32[{B},140,19456]{{2,1,0}} custom-call"
        t = reduce([ev(call, 0, dur), ev(call, dur, dur)],
                   seconds=2 * dur * 1e-9)
        got = read(SimpleNamespace(trace=t, peak=V5E,
                                   shape=dict(n=n, L=46)))
        assert got == pytest.approx(100.0 / factor, rel=1e-5)
        assert got <= 100.0 + 1e-6


def test_pearson_roofline_reads_the_larger_bound():
    n, L = 19412, 46
    ops, nbytes = roofline.pearson_counts(n, L)
    least = roofline.least_seconds(ops, nbytes, V5E)
    assert least == pytest.approx(nbytes / V5E["hbm_bytes_per_s"])
    dur = int(round(least * 4 * 1e9))
    t = reduce([ev("%pearson_pallas.1 = f32[19456,19456]{1,0} custom-call",
                   0, dur)], seconds=dur * 1e-9)
    ctx = SimpleNamespace(trace=t, peak=V5E, shape=dict(n=n, L=L))
    assert spec.reader("pearson.roofline")(ctx) == pytest.approx(25.0,
                                                                  rel=1e-6)


def test_readers_find_nothing_without_a_trace():
    ctx = SimpleNamespace(trace=None, peak=V5E, shape=dict(n=10, L=4))
    for name in ("device_idle.batch", "pallas_share.batch",
                 "pearson.roofline", "minplus.roofline"):
        assert spec.reader(name)(ctx) is None


if __name__ == "__main__":
    sys.exit(pytest.main([os.path.abspath(__file__), "-q"]))
