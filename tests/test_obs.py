"""The observability layer (DESIGN.md §15): tracer, registry, export.

Pins of ISSUE 7's acceptance criteria:
  * span semantics — nesting, per-thread stacks, fenced-vs-unfenced
    (an unfenced span never calls ``jax.block_until_ready``), the
    zero-cost contract (tracing disabled → the fused ``cluster()``
    path adds NO device sync);
  * compile-vs-run separation + the recompile watchdog — replayed
    ``cluster()`` at a fixed (config, shape) compiles nothing, a
    config change compiles at least one program, and the always-on
    alarm log surfaces through ``ClusterService.healthz()``;
  * the metrics registry — get-or-create identity, snapshot/reset,
    collector wiring (jitcache), the Prometheus render golden;
  * wiring — staged ``cluster()`` timings come from the fenced spans,
    the scheduler's dedup counter, the service stats()/healthz()
    contract.
"""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import clustered_similarity
from repro.core import jitcache
from repro.core.config import PipelineConfig
from repro.core.pipeline import cluster
from repro.data.timeseries import make_dataset
from repro.obs import export as obs_export
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.metrics import Registry
from repro.stream import ClusterService
from repro.stream.cache import ResultCache
from repro.stream.scheduler import MicroBatcher


# ---------------------------------------------------------------------------
# spans (§15.1)
# ---------------------------------------------------------------------------

def test_span_measures_and_nests():
    obs_trace.clear()
    with obs_trace.tracing():
        with obs_trace.span("outer") as outer:
            with obs_trace.span("inner") as inner:
                time.sleep(0.01)
    assert outer.duration >= inner.duration >= 0.01
    assert inner.parent == "outer" and inner.depth == 1
    assert outer.parent is None and outer.depth == 0
    names = [s.name for s in obs_trace.spans()]
    assert names == ["inner", "outer"]          # completion order


def test_spans_collected_only_while_enabled():
    obs_trace.clear()
    assert not obs_trace.enabled()
    with obs_trace.span("uncollected") as sp:
        pass
    assert sp.duration >= 0.0                   # still measured...
    assert obs_trace.spans("uncollected") == []  # ...but not buffered


def test_span_thread_safety_per_thread_stacks():
    obs_trace.clear()

    def worker(tag):
        with obs_trace.span(tag):
            with obs_trace.span(tag + ".child"):
                time.sleep(0.01)

    with obs_trace.tracing():
        ts = [threading.Thread(target=worker, args=(f"t{i}",))
              for i in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    for i in range(4):
        child = obs_trace.spans(f"t{i}.child")
        # each child's parent is its OWN thread's outer span, never a
        # concurrent thread's (the per-thread stack contract)
        assert len(child) == 1
        assert child[0].parent == f"t{i}" and child[0].depth == 1
        assert child[0].thread == obs_trace.spans(f"t{i}")[0].thread


def test_fenced_vs_unfenced_span(monkeypatch):
    blocked = []
    orig = jax.block_until_ready

    def slow_block(x):
        blocked.append(x)
        time.sleep(0.03)
        return orig(x)

    monkeypatch.setattr(jax, "block_until_ready", slow_block)
    arr = jnp.ones(7)
    with obs_trace.span("fenced", fence=True) as sp_f:
        sp_f.fence(arr)
    with obs_trace.span("unfenced", fence=False) as sp_u:
        sp_u.fence(arr)
    # the fenced span waited inside its measured region; the unfenced
    # span never called block_until_ready at all
    assert len(blocked) == 1
    assert sp_f.duration >= 0.03 > sp_u.duration


def test_fused_cluster_adds_no_syncs_when_tracing_off(monkeypatch):
    """The §15.1 zero-cost pin: with tracing disabled, the fused path's
    single device_get is its only sync — the span machinery must not
    introduce a single ``jax.block_until_ready`` call."""
    X = make_dataset(24, 32, 3, noise=0.7, seed=0)[0]
    cluster(X, k=3)                              # compile outside the probe
    assert not obs_trace.enabled()
    calls = []
    orig = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: calls.append(x) or orig(x))
    cluster(X, k=3)
    assert calls == [], "fused cluster() must add no device syncs"


def test_staged_cluster_timings_come_from_fenced_spans():
    S, _, _ = clustered_similarity(32, k=3, seed=1)
    obs_trace.clear()
    with obs_trace.tracing():
        res = cluster(S=S, k=3, fused=False, collect_timings=True)
    stages = ("pipeline.similarity", "pipeline.tmfg", "pipeline.dbht+apsp")
    durs = {}
    for name in stages:
        got = obs_trace.spans(name)
        assert got, f"staged cluster() collected no {name} span"
        assert got[-1].fenced
        durs[name.split(".", 1)[1]] = got[-1].duration
    assert res.timings["total"] == pytest.approx(sum(durs.values()))
    for stage, d in durs.items():
        assert res.timings[stage] == d


# ---------------------------------------------------------------------------
# compile counters + the recompile watchdog (§15.2)
# ---------------------------------------------------------------------------

def test_span_attributes_compile_time():
    # a fresh shape forces one (or more) XLA compiles inside the span
    fn = jax.jit(lambda x: x * 2 + 1)
    with obs_trace.span("cold") as cold:
        jax.block_until_ready(fn(jnp.ones(13)))
    assert cold.compiles >= 1 and cold.compile_s > 0.0
    assert cold.run_s == pytest.approx(cold.duration - cold.compile_s)
    with obs_trace.span("warm") as warm:
        jax.block_until_ready(fn(jnp.ones(13)))
    # the replay compiles nothing; run_s is the full duration
    assert warm.compiles == 0 and warm.compile_s == 0.0
    assert warm.run_s == warm.duration


def test_watchdog_silent_on_replay_fires_on_config_churn():
    X = make_dataset(24, 32, 3, noise=0.7, seed=2)[0]
    cfg = PipelineConfig.opt()
    cluster(X, k=3, config=cfg)                  # populate the jitcache
    with obs_trace.watch_recompiles() as w:
        cluster(X, k=3, config=cfg)              # pure replay
    assert w.count == 0 and w.compile_s == 0.0
    assert w.recompile_events == 0
    with obs_trace.watch_recompiles() as w2:
        cluster(X, k=3, config=cfg.replace(prefix=7))   # new config
    assert w2.count >= 1 and w2.compile_s > 0.0


def test_record_recompile_always_logged():
    before = obs_trace.compile_stats()["recompile_events"]
    assert not obs_trace.enabled()
    obs_trace.record_recompile(detail="test alarm", shape="(3, 3)")
    stats = obs_trace.compile_stats()
    assert stats["recompile_events"] == before + 1
    last = obs_trace.recompile_events()[-1]
    assert last["detail"] == "test alarm" and last["shape"] == "(3, 3)"


# ---------------------------------------------------------------------------
# the metrics registry (§15.3)
# ---------------------------------------------------------------------------

def test_registry_get_or_create_and_snapshot():
    reg = Registry()
    c1 = reg.counter("req_total", "requests", path="/a")
    c2 = reg.counter("req_total", path="/a")
    assert c1 is c2                              # same (name, labels)
    c1.inc(); c1.inc(2)
    reg.gauge("depth").set(5)
    snap = reg.snapshot()
    assert snap['req_total{path="/a"}'] == 3.0
    assert snap["depth"] == 5.0
    with pytest.raises(ValueError):
        reg.gauge("req_total", path="/a")        # type mismatch rejected


def test_registry_reset_zeroes_instruments_not_collectors():
    reg = Registry()
    reg.counter("c_total").inc(9)
    reg.register_collector("ext", lambda: {"ext_val": 7.0})
    reg.reset()
    snap = reg.snapshot()
    assert snap["c_total"] == 0.0
    assert snap["ext_val"] == 7.0                # external view untouched


def test_histogram_cumulative_buckets():
    reg = Registry()
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    for v in (0.5, 0.5, 2.0):
        h.observe(v)
    snap = reg.snapshot()
    assert snap['lat_seconds_bucket{le="0.1"}'] == 0
    assert snap['lat_seconds_bucket{le="1"}'] == 2
    assert snap['lat_seconds_bucket{le="+Inf"}'] == 3
    assert snap["lat_seconds_sum"] == pytest.approx(3.0)
    assert snap["lat_seconds_count"] == 3


def test_prometheus_render_golden():
    reg = Registry()
    reg.counter("req_total", "served requests", path="/a").inc(3)
    reg.gauge("depth", "queue depth").set(2)
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    for v in (0.5, 0.5, 2.0):
        h.observe(v)
    reg.register_collector("ext", lambda: {"ext_val": 7.0})
    assert obs_export.render(reg) == (
        '# HELP depth queue depth\n'
        '# TYPE depth gauge\n'
        '# HELP lat_seconds latency\n'
        '# TYPE lat_seconds histogram\n'
        '# HELP req_total served requests\n'
        '# TYPE req_total counter\n'
        'depth 2\n'
        'lat_seconds_bucket{le="0.1"} 0\n'
        'lat_seconds_bucket{le="1"} 2\n'
        'lat_seconds_bucket{le="+Inf"} 3\n'
        'lat_seconds_sum 3\n'
        'lat_seconds_count 3\n'
        'req_total{path="/a"} 3\n'
        'ext_val 7\n'
    )


def test_jitcache_collector_reset_and_staleness():
    jitcache.clear()
    jitcache.reset_stats()
    jitcache.cached(("obs-test", 1), lambda: "a")
    jitcache.cached(("obs-test", 2), lambda: "b")
    jitcache.cached(("obs-test", 1), lambda: "a")      # hit
    assert jitcache.contains(("obs-test", 2))
    assert not jitcache.contains(("obs-test", 3))
    # contains() is the stats-free replay probe
    assert jitcache.stats() == {"hits": 1, "misses": 2, "evictions": 0}
    ages = jitcache.last_hit_ages()
    assert list(ages) == [("obs-test", 2), ("obs-test", 1)]  # LRU-first
    assert all(a >= 0.0 for a in ages.values())
    assert jitcache.oldest_idle_s() >= 0.0
    snap = obs_metrics.snapshot()
    assert snap["jitcache_hits_total"] == 1.0
    assert snap["jitcache_misses_total"] == 2.0
    assert snap["jitcache_size"] == 2.0
    jitcache.reset_stats()
    assert jitcache.stats() == {"hits": 0, "misses": 0, "evictions": 0}
    assert jitcache.size() == 2                  # reset_stats keeps entries
    jitcache.clear()


# ---------------------------------------------------------------------------
# wiring: scheduler dedupe, service stats()/healthz() (§15.3)
# ---------------------------------------------------------------------------

def test_batcher_dedup_counter():
    S, _, _ = clustered_similarity(24, k=3, seed=3)
    before = obs_metrics.counter("batcher_dedup_hits_total").value
    mb = MicroBatcher(max_batch=4, cache=ResultCache(8))
    r1 = mb.submit(S, k=3)
    r2 = mb.submit(S, k=3)                       # same bytes, same flush
    mb.flush()
    assert r1.done and r2.done
    assert np.array_equal(r1.result.labels, r2.result.labels)
    assert mb.dedup_hits == 1                    # the twin never clustered
    assert obs_metrics.counter("batcher_dedup_hits_total").value \
        == before + 1
    # a repeat submit is answered by the cache re-probe at flush time
    r3 = mb.submit(S, k=3)
    mb.flush()
    assert r3.done and r3.cached
    assert mb.dedup_hits == 2


def test_service_stats_one_snapshot():
    rng = np.random.default_rng(4)
    svc = ClusterService(n=16, window=8, k=3)
    for t in range(8):
        svc.tick(rng.normal(size=16).astype(np.float32))
    svc.recluster()
    stats = svc.stats()
    # one snapshot exports every layer: jitcache, content cache,
    # batcher occupancy, stage/tick latency, service-local counters
    for key in ("jitcache_size", "stream_cache_hits_total",
                "batcher_queue_depth", "service_ticks",
                "service_queue_depth", "service_warm_hits",
                "service_batches_run", "service_dedup_hits",
                "service_tick_seconds_count"):
        assert key in stats, f"stats() lost {key}"
    assert stats["service_ticks"] == 8.0
    assert stats["service_tick_seconds_count"] >= 8.0
    assert 'pipeline_total_seconds_count{path="fused"}' in stats


def test_service_healthz_contract():
    rng = np.random.default_rng(5)
    svc = ClusterService(n=16, window=8, k=3, min_ticks=4)
    hz = svc.healthz()
    assert set(hz) == {"status", "ready", "ticks", "window_filled",
                       "window_capacity", "queue_depth",
                       "recompile_events", "jitcache_size",
                       "breaker", "admission_queue_depth",
                       "shed_total", "degraded_total"}
    assert hz["status"] == "warming" and hz["ready"] is False
    # §16 serving keys are always present; without admission control
    # the breaker reads "disabled" and the counters stay zero
    assert hz["breaker"] == "disabled"
    assert hz["admission_queue_depth"] == 0
    assert hz["shed_total"] == 0 and hz["degraded_total"] == 0
    for t in range(4):
        svc.tick(rng.normal(size=16).astype(np.float32))
    hz = svc.healthz()
    assert hz["status"] == "ok" and hz["ready"] is True
    assert hz["ticks"] == 4 and hz["window_filled"] == 4
    assert hz["window_capacity"] == 8 and hz["queue_depth"] == 0
    assert hz["recompile_events"] >= 0 and hz["jitcache_size"] >= 0


# ---------------------------------------------------------------------------
# coverage gaps (ISSUE 8 satellite): concurrent tracing, watch nesting,
# render edge cases
# ---------------------------------------------------------------------------

def test_concurrent_tracing_sessions_from_two_threads():
    """Two overlapping ``tracing()`` sessions on different threads:
    sessions are refcounted, so the first thread to exit must NOT
    switch collection off under the one still inside (the save/restore
    bug this pins).  Sequenced with events — no sleeps, no races."""
    obs_trace.clear()
    a_entered = threading.Event()
    b_exited = threading.Event()
    failures = []

    def worker_a():
        try:
            with obs_trace.tracing():
                a_entered.set()
                assert b_exited.wait(30), "sequencing timeout"
                # thread B's session has opened AND closed by now; this
                # thread's session is still live, so its span collects
                with obs_trace.span("a-late"):
                    pass
        except Exception as e:   # noqa: BLE001 — surface in main thread
            failures.append(e)

    ta = threading.Thread(target=worker_a)
    ta.start()
    try:
        assert a_entered.wait(30), "sequencing timeout"
        with obs_trace.tracing():
            with obs_trace.span("b-inner"):
                pass
        b_exited.set()
    finally:
        ta.join(30)
    assert not failures
    names = [s.name for s in obs_trace.spans()]
    assert "b-inner" in names
    assert "a-late" in names, \
        "thread B's exit turned tracing off under thread A"
    assert not obs_trace.enabled()               # all sessions closed
    obs_trace.clear()


def test_watch_recompiles_nesting():
    """Nested watches: the inner watch counts only its own region and
    freezes at its exit; the outer watch keeps counting across and
    after the inner one (§15.2's windowed-delta semantics compose)."""
    with obs_trace.watch_recompiles() as outer:
        jax.block_until_ready(jax.jit(lambda x: x + 17.0)(jnp.ones(7)))
        with obs_trace.watch_recompiles() as inner:
            jax.block_until_ready(
                jax.jit(lambda x: x * 19.0)(jnp.ones(11)))
        inner_frozen = inner.count
        assert inner_frozen >= 1
        # a compile after the inner block must not leak into it...
        jax.block_until_ready(jax.jit(lambda x: x - 23.0)(jnp.ones(13)))
        assert inner.count == inner_frozen
    # ...but the outer watch saw all three regions
    assert outer.count >= inner_frozen + 2
    assert outer.compile_s > inner.compile_s
    assert outer.recompile_events >= inner.recompile_events


def test_render_empty_registry_is_empty_string():
    """A fresh registry renders as exactly "" — no stray newline; a
    scrape of a process that registered nothing yet is byte-clean."""
    assert obs_export.render(Registry()) == ""


def test_render_label_collision_and_collector_shadowing():
    """One family, several label sets, plus a collector emitting a
    sample under the SAME family name: one HELP/TYPE pair, every
    sample rendered, collector sample grouped into the typed family
    (deterministic golden)."""
    reg = Registry()
    reg.counter("dup_total", "dup family", route="a").inc(1)
    reg.counter("dup_total", route="b").inc(2)
    reg.register_collector("ext", lambda: {"dup_total": 9.0})
    text = obs_export.render(reg)
    assert text == (
        "# HELP dup_total dup family\n"
        "# TYPE dup_total counter\n"
        'dup_total{route="a"} 1\n'
        'dup_total{route="b"} 2\n'
        "dup_total 9\n"
    )
    # label-set identity: the two label sets are distinct instruments,
    # same-name-same-labels is the same instrument, and a same-name
    # different-TYPE registration is rejected
    assert reg.counter("dup_total", route="a") \
        is not reg.counter("dup_total", route="b")
    assert reg.counter("dup_total", route="a") \
        is reg.counter("dup_total", route="a")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("dup_total", route="a")


def test_family_total_sums_label_sets():
    """§16's rollup helper: one number across a family's label sets
    (how the load bench reports total sheds regardless of reason)."""
    reg = Registry()
    reg.counter("shed_total", "sheds", reason="quota").inc(3)
    reg.counter("shed_total", reason="queue_full").inc(2)
    assert reg.family_total("shed_total") == 5.0
    assert reg.family_total("missing_total") == 0.0
