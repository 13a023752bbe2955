"""The readers of the approx cell: the top-K scan's roofline share from a
reduced trace, and the sparse TMFG's fallback and pair-miss shares from
the program's own record of the window's calls."""

import itertools
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import drivers  # noqa: E402
import roofline  # noqa: E402
import roofline_topk  # noqa: E402
import spec  # noqa: E402
import tracing  # noqa: E402

V5E = roofline.peaks("TPU v5 lite")
CROP = dict(n=24000, L=46, B=1)
CONFIG = {"pipeline": {"constructor": "approx", "args": {"sim_k": 64}}}
# the kernel's output is a (values, indices) tuple
TOPK_OP = ("%topk_rows_pallas.1 = (f32[24064,64]{1,0}, s32[24064,64]{1,0}) "
           "custom-call(...)")
SHARES = {"approx.fallback_share": ("approx_fallbacks", "approx_lookups"),
          "approx.pair_miss_share": ("approx_pair_misses",
                                     "approx_pair_lookups")}


def reduce(events, seconds):
    ops = [SimpleNamespace(name=name, start_ns=s, duration_ns=d)
           for name, s, d in events]
    mark = SimpleNamespace(name=tracing.MARK, start_ns=0, duration_ns=0)
    planes = [SimpleNamespace(name="/device:TPU:0", lines=[
                  SimpleNamespace(name=tracing.OPS_LINE, events=ops)]),
              SimpleNamespace(name="/host:CPU", lines=[
                  SimpleNamespace(name="main", events=[mark])])]
    return tracing.from_profile(SimpleNamespace(planes=planes), 1, seconds)


def test_topk_counts_at_crop_by_hand():
    # 2 n^2 L multiply-adds; the series read once, K float32 values and K
    # int32 indices written a row
    ops, nbytes = roofline_topk.topk_counts(24000, 46, 64)
    assert ops == 2 * 24000 ** 2 * 46
    assert nbytes == 4 * (24000 * 46 + 2 * 24000 * 64)
    least = roofline.least_seconds(ops, nbytes, V5E)
    assert least == pytest.approx(ops / V5E["bf16_flops_per_s"])
    assert least == pytest.approx(0.269e-3, rel=1e-3)


@pytest.mark.parametrize("factor", [1.0, 4.0])
def test_topk_roofline_reads_least_time_over_each_call(factor):
    least = roofline.least_seconds(
        *roofline_topk.topk_counts(24000, 46, 64), V5E)
    dur = round(least * factor * 1e9)
    t = reduce([(TOPK_OP, 10, dur), ("%fusion.2 = f32[8] fusion(...)",
                                     20 + dur, 5), (TOPK_OP, 40 + dur, dur)],
               seconds=(100 + 2 * dur) * 1e-9)
    ctx = SimpleNamespace(trace=t, peak=V5E, shape=CROP, config=CONFIG)
    got = spec.reader("topk.roofline")(ctx)
    assert got == pytest.approx(100.0 / factor, rel=1e-6)
    assert got <= 100.0 + 1e-6


def test_topk_roofline_clamps_k_as_the_program_does():
    # K above n - 1 counts n - 1 a row
    n, L = 40, 24
    least = roofline.least_seconds(*roofline_topk.topk_counts(n, L, n - 1),
                                   V5E)
    dur = max(1, round(least * 2 * 1e9))
    t = reduce([(TOPK_OP, 0, dur)], seconds=(dur + 10) * 1e-9)
    ctx = SimpleNamespace(trace=t, peak=V5E, shape=dict(n=n, L=L),
                          config=CONFIG)
    assert spec.reader("topk.roofline")(ctx) == pytest.approx(
        100.0 * least / (dur * 1e-9), rel=1e-6)


def test_topk_roofline_finds_nothing_without_the_kernel():
    ctx = SimpleNamespace(trace=None, peak=V5E, shape=CROP, config=CONFIG)
    assert spec.reader("topk.roofline")(ctx) is None
    ctx.trace = reduce([("%pearson_pallas.1 = f32[8,8]{1,0} custom-call",
                         0, 10)], seconds=20e-9)
    assert spec.reader("topk.roofline")(ctx) is None


# the kept ring is the process's own: each test plants its calls in a
# window of its own, far from the host clock's readings
WINDOWS = itertools.count(-10_000.0, -100.0)


def window_run():
    """A run whose window holds one ``bench.call`` span, [lo, lo + 10),
    and ``lo``."""
    run, lo = drivers.Run(), next(WINDOWS)
    run.spans.events.append(("bench.call", lo, lo + 10.0))
    return run, lo


def plant(start, **attrs):
    from repro.obs import trace as obs_trace

    with obs_trace.span("pipeline.fused", keep=True) as sp:
        pass
    sp.attrs.update(attrs)
    sp.start, sp.duration = start, 1.0
    return sp


@pytest.mark.parametrize("metric", sorted(SHARES))
def test_shares_read_the_window_calls_only(metric):
    num, den = SHARES[metric]
    read = spec.reader(metric)
    run, lo = window_run()
    ctx = SimpleNamespace(run=run, trace=None)
    assert read(ctx) is None                       # nothing kept yet
    plant(lo + 2.0, **{num: 3.0, den: 4.0})
    plant(lo + 5.0, **{num: 1.0, den: 6.0})
    plant(lo - 8.0, **{num: 100.0, den: 100.0})    # the warm-up's call
    assert read(ctx) == pytest.approx(100.0 * 4.0 / 10.0)


@pytest.mark.parametrize("metric", sorted(SHARES))
def test_shares_find_nothing_in_a_dense_call(metric):
    """A dense program's calls carry no approx counters."""
    run, lo = window_run()
    ctx = SimpleNamespace(run=run, trace=None)
    plant(lo + 2.0, tmfg_pops=5.0, tmfg_inserts=4.0, problems=1.0)
    assert spec.reader(metric)(ctx) is None
