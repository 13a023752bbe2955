"""The fused program's own instrumentation (DESIGN.md §15.5).

Pins:
  * every loop counter equals a plain recount: the lazy TMFG's pops and
    inserts against a numpy replay of its acceptance rule, the hub
    APSP's Bellman-Ford rounds against a host loop to the fixed point
    (the exact program's squarings below the hub size), the HAC's
    rescans against a numpy replay of its cached-neighbour scan;
  * a batch's sums skip its pads (``limit=``) and reach the registry;
  * the four host phase spans nest under ``pipeline.fused`` and sum to
    it, and every fused call leaves one record in the kept ring;
  * the program built with tracing off has no host callback (so its
    persistent-cache entry is written), the one built under
    ``tracing()`` has, under a key of its own, and its stage marks sum
    to no more than the call's time on the device and fire once per
    stage for a whole batch;
  * an ``obs.trace.span`` is an annotation of a profiler session.
"""

import glob
import math
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import apsp as apsp_mod
from repro.core import hac as hac_mod
from repro.core import jitcache
from repro.core.config import PipelineConfig
from repro.core.pipeline import (PHASES, STAGES, cluster, cluster_batch,
                                 run_pipeline_device)
from repro.data.timeseries import make_dataset
from repro.obs import export as obs_export
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

ROOT = Path(__file__).resolve().parents[1]


def last_call():
    return obs_trace.kept_spans("pipeline.fused")[-1]


def similarity(n, seed):
    """A ties-free float32 correlation matrix of n random series."""
    X = np.random.default_rng(seed).normal(size=(n, 3 * n))
    return np.corrcoef(X).astype(np.float32)


# ---------------------------------------------------------------------------
# plain recounts
# ---------------------------------------------------------------------------

def replay_lazy_tmfg(S):
    """The lazy TMFG loop (core/tmfg._build_lazy) in numpy: each pop
    takes the face of highest cached gain; a stale best vertex is
    re-validated, a fresh one inserted.  Returns (pops, edges)."""
    S = np.array(S, np.float32)
    n = S.shape[0]
    np.fill_diagonal(S, -np.inf)
    row_sums = np.where(np.isfinite(S), S, 0.0).sum(axis=1)
    v1, v2, v3, v4 = np.sort(np.argsort(-row_sums, kind="stable")[:4])
    inserted = np.zeros(n, bool)
    inserted[[v1, v2, v3, v4]] = True
    faces = np.zeros((2 * n - 4, 3), np.int64)
    faces[:4] = [(v1, v2, v3), (v1, v2, v4), (v1, v3, v4), (v2, v3, v4)]
    n_faces, n_inserted = 4, 4
    edges = [(v1, v2), (v1, v3), (v1, v4), (v2, v3), (v2, v4), (v3, v4)]

    def best_of_row(w):
        return int(np.argmax(np.where(inserted, -np.inf, S[w])))

    maxcorr = np.array([best_of_row(w) for w in range(n)])
    gains = np.full(2 * n - 4, -np.inf, np.float32)
    best = np.zeros(2 * n - 4, np.int64)

    def pair(f):
        cands = maxcorr[faces[f]]
        g = S[faces[f][:, None], cands[None, :]].sum(axis=0)
        j = int(np.argmax(g))
        best[f], gains[f] = cands[j], g[j]

    for f in range(4):
        pair(f)
    pops = 0
    while n_inserted < n:
        f = int(np.argmax(gains))
        v = int(best[f])
        pops += 1
        if inserted[v]:
            for w in faces[f]:
                maxcorr[w] = best_of_row(w)
            pair(f)
            continue
        a, b, c = faces[f]
        inserted[v] = True
        n_inserted += 1
        edges += [(v, a), (v, b), (v, c)]
        slots = (f, n_faces, n_faces + 1)
        faces[f], faces[n_faces], faces[n_faces + 1] = (
            (v, a, b), (v, b, c), (v, a, c))
        n_faces += 2
        for w in (v, a, b, c):
            maxcorr[w] = best_of_row(w)
        for sl in slots:
            pair(sl)
    return pops, edge_set(edges)


def edge_set(edges):
    return sorted(tuple(sorted(map(int, e))) for e in edges)


def host_bellman_ford_rounds(W, n_hubs=0):
    """Rounds the hub rows of W take to their fixed point (the last
    round is the one that changes nothing), hubs picked as in
    core/apsp: the highest sums of finite incident 1/length."""
    finite = jnp.isfinite(W) & (W > 0)
    strength = jnp.sum(jnp.where(finite, 1.0 / (W + 1e-6), 0.0), axis=1)
    h = apsp_mod.hub_count(W.shape[0], n_hubs)
    hubs = np.asarray(jax.lax.top_k(strength, h)[1])
    W = np.asarray(W, np.float32)
    D = W[hubs]
    rounds = 0
    while True:
        D2 = np.minimum(D, (D[:, :, None] + W[None, :, :]).min(axis=1))
        rounds += 1
        if not (D2 < D).any():
            return rounds
        D = D2


def replay_hac(D, rows_per_step=hac_mod.RESCAN_ROWS):
    """core/hac.complete_linkage in numpy: the global minimum off each
    row's cached nearest alive neighbour, complete-linkage update, and
    the rows whose neighbour merged rescanned a step of
    ``rows_per_step`` at a time.  Returns (Z, rescan steps)."""
    D = np.array(D, np.float32)
    n = D.shape[0]
    np.fill_diagonal(D, np.inf)
    alive = np.ones(n, bool)
    ids, sizes = np.arange(n), np.ones(n, np.int64)

    def nearest(rows):
        s = np.where(alive[None, :], -D[rows], -np.inf)
        return s.max(axis=1), s.argmax(axis=1)

    nv, nc = nearest(np.arange(n))
    Z, steps = [], 0
    for k in range(n - 1):
        vals = np.where(alive, nv, -np.inf)
        i = int(np.argmax(vals))
        h = -vals[i]
        j = 0 if h == np.inf else int(nc[i])
        i = 0 if h == np.inf else i
        i, j = min(i, j), max(i, j)
        Z.append((ids[i], ids[j], h, sizes[i] + sizes[j]))
        row = np.maximum(D[i], D[j])
        D[i, :], D[:, i], D[i, i] = row, row, np.inf
        alive[j] = False
        ids[i], sizes[i] = n + k, sizes[i] + sizes[j]
        stale = alive & ((nc == i) | (nc == j) | (np.arange(n) == i))
        while stale.any():
            rows = np.flatnonzero(stale)[:rows_per_step]
            nv[rows], nc[rows] = nearest(rows)
            stale[rows] = False
            steps += 1
    return np.array(Z, np.float32), steps


def test_tmfg_counters_match_a_replay_of_the_lazy_loop():
    S = similarity(40, seed=3)
    res = cluster(S=S, k=3)
    pops, edges = replay_lazy_tmfg(S)
    assert edge_set(np.asarray(res.tmfg.edges)) == edges  # the loop
    attrs = last_call().attrs
    assert attrs["tmfg_pops"] == pops > 40 - 4
    assert attrs["tmfg_inserts"] == 40 - 4
    assert attrs["problems"] == 1


@pytest.mark.parametrize("n", [40, 208])
def test_apsp_rounds_match_a_host_loop(n):
    """Hub APSP (n >= HUB_MIN_N) counts its Bellman-Ford rounds to the
    fixed point; below the hub size the exact program counts its
    ceil(log2(n-1)) squarings."""
    S = similarity(n, seed=4)
    res = cluster(S=S, k=3)
    W = apsp_mod.edge_lengths(n, jnp.asarray(res.tmfg.edges),
                              jnp.asarray(S))
    rounds = last_call().attrs["apsp_rounds"]
    if n < apsp_mod.HUB_MIN_N:
        assert rounds == math.ceil(math.log2(n - 1))
    else:
        assert rounds == host_bellman_ford_rounds(W) > 1


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_hac_rescans_match_a_numpy_replay(seed):
    """Integer distances (ties everywhere) and +inf pads: the counted
    linkage is the plain one, and both match the replay."""
    rng = np.random.default_rng(seed)
    n = 30
    A = rng.integers(0, 6, size=(n, n)).astype(np.float32)
    D = np.minimum(A, A.T)
    D[n - 3:, :] = D[:, n - 3:] = np.inf
    Z, rescans = hac_mod.complete_linkage_rescans(jnp.asarray(D))
    Z_ref, steps = replay_hac(D)
    assert int(rescans) == steps >= n - 1
    assert np.array_equal(np.asarray(Z), Z_ref)
    assert np.array_equal(np.asarray(hac_mod.complete_linkage(
        jnp.asarray(D))), np.asarray(Z))


def test_pipeline_hac_rescans_match_a_numpy_replay():
    S = similarity(40, seed=5)
    res = cluster(S=S, k=3)
    adj = hac_mod.hierarchical_offsets(
        jnp.asarray(res.dbht.apsp), jnp.asarray(res.dbht.bubble_of),
        jnp.asarray(res.dbht.cluster_of))
    Z_ref, steps = replay_hac(np.asarray(adj))
    attrs = last_call().attrs
    assert attrs["hac_rescans"] == steps
    assert attrs["hac_merges"] == 40 - 1
    assert np.array_equal(res.linkage, Z_ref)


def test_batched_counters_skip_pads():
    Xb = np.stack([make_dataset(40, 24, 3, noise=0.7, seed=s)[0]
                   for s in range(3)])
    singles = []
    for b in range(3):
        cluster(Xb[b], k=3)
        singles.append(last_call().attrs)
    before = obs_metrics.snapshot()
    cluster_batch(Xb, k=3, limit=2)
    after = obs_metrics.snapshot()
    attrs = last_call().attrs
    assert attrs["batch"] == 3 and attrs["problems"] == 2
    for name in ("tmfg_pops", "tmfg_inserts", "apsp_rounds", "hac_rescans",
                 "hac_merges"):
        want = singles[0][name] + singles[1][name]
        assert attrs[name] == want, name
        assert after[f"{name}_total"] - before.get(f"{name}_total", 0) \
            == want, name
    assert after["pipeline_problems_total"] \
        - before["pipeline_problems_total"] == 2


def test_approx_and_filter_programs_share_the_counters():
    X = make_dataset(40, 24, 3, noise=0.7, seed=6)[0]
    res = cluster(X, k=3, config=PipelineConfig.approx(sim_k=8),
                  collect_timings=True)
    attrs = last_call().attrs
    for name in ("tmfg_pops", "apsp_rounds", "hac_rescans",
                 "approx_lookups", "approx_fallbacks", "approx_pair_misses"):
        assert attrs[name] > 0, name
    assert res.timings["sim_fallbacks"] == attrs["approx_fallbacks"]
    cluster(X, k=3, config=PipelineConfig.mst())
    attrs = last_call().attrs
    assert "tmfg_pops" not in attrs                  # no TMFG loop ran
    assert attrs["apsp_rounds"] > 0 and attrs["hac_rescans"] >= 39


# ---------------------------------------------------------------------------
# host phase spans and the kept ring
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("batched", [False, True])
def test_phase_spans_nest_under_fused_and_sum_to_it(batched):
    X = make_dataset(40, 24, 3, noise=0.7, seed=7)[0]
    run = (lambda: cluster_batch(np.stack([X, X[::-1]]), k=3)) if batched \
        else (lambda: cluster(X, k=3))
    with obs_trace.tracing():
        run()                                    # compile outside
        obs_trace.clear()
        kept = len(obs_trace.kept_spans("pipeline.fused"))
        run()
    spans = obs_trace.spans()
    fused = [s for s in spans if s.name == "pipeline.fused"]
    phases = [s for s in spans if s.name in
              tuple(f"pipeline.{p}" for p in PHASES)]
    assert len(fused) == 1 and [s.name for s in phases] == [
        f"pipeline.{p}" for p in PHASES]
    assert all(s.parent == "pipeline.fused" and s.depth == 1
               for s in phases)
    assert abs(sum(s.duration for s in phases) - fused[0].duration) < 1e-3
    # one kept record per call, whatever its batch, carrying each phase
    assert obs_trace.kept_spans("pipeline.fused")[-1] is fused[0]
    assert len(obs_trace.kept_spans("pipeline.fused")) in (
        kept + 1, obs_trace.KEPT_MAX)
    for p, s in zip(PHASES, phases):
        assert fused[0].attrs[f"{p}_s"] == s.duration
    assert fused[0].attrs["compiled"] is False


def test_kept_ring_fills_with_tracing_off():
    X = make_dataset(40, 24, 3, noise=0.7, seed=8)[0]
    assert not obs_trace.enabled()
    cluster(X, k=3)
    sp = last_call()
    assert sp.attrs["problems"] == 1 and sp.attrs["device_s"] > 0
    snap = obs_metrics.snapshot()
    for p in PHASES:
        assert snap[f'pipeline_phase_seconds_count{{phase="{p}"}}'] >= 1


# ---------------------------------------------------------------------------
# opt-in stage marks
# ---------------------------------------------------------------------------

def fused_keys(shape):
    return [k for k in jitcache.keys() if k[0] == "fused" and k[4] == shape]


def test_marks_only_in_the_program_built_under_tracing():
    X = make_dataset(44, 24, 3, noise=0.7, seed=9)[0]
    shape = (44, 24)
    res_off = cluster(X, k=3)
    with obs_trace.tracing():
        res_on = cluster(X, k=3)
    keys = fused_keys(shape)
    off = [k for k in keys if k[-1] is False]
    on = [k for k in keys if k[-1] is True]
    assert len(off) == 1 and len(on) == 1 and off[0][:-1] == on[0][:-1]

    def hlo(key):
        fn = jitcache.cached(key, None)
        return fn.lower(jax.ShapeDtypeStruct(shape, jnp.float32)).as_text()

    assert "callback" not in hlo(off[0])
    assert "callback" in hlo(on[0])
    # the marks change when the program runs, not what it computes
    assert np.array_equal(res_on.labels, res_off.labels)
    assert np.array_equal(res_on.linkage, res_off.linkage)
    assert np.array_equal(res_on.dbht.apsp, res_off.dbht.apsp)


def test_stage_marks_sum_within_the_call():
    X = make_dataset(48, 24, 3, noise=0.7, seed=10)[0]
    before = obs_metrics.snapshot().get(
        'pipeline_stage_seconds_count{stage="hac"}', 0)
    with obs_trace.tracing():
        cluster(X, k=3)                          # compiles the marked one
        cluster(X, k=3)
    attrs = last_call().attrs
    stages = attrs["stages"]
    assert list(stages) == list(STAGES)
    assert all(s >= 0.0 for s in stages.values())
    # the CPU runs a program with host callbacks inside its dispatch; a
    # chip runs it while the host waits in the device phase
    assert 0.0 < sum(stages.values()) <= attrs["dispatch_s"] \
        + attrs["device_s"]
    after = obs_metrics.snapshot()['pipeline_stage_seconds_count{stage="hac"}']
    assert after - before == 2
    # tracing off again: the unmarked program, and no stages
    cluster(X, k=3)
    assert "stages" not in last_call().attrs


def test_stage_marks_fire_once_per_stage_for_a_batch():
    Xb = np.stack([make_dataset(40, 24, 3, noise=0.7, seed=s)[0]
                   for s in range(3)])
    cfg = PipelineConfig.opt()
    with obs_trace.tracing():
        obs_trace.take_marks()
        jax.block_until_ready(run_pipeline_device(
            jnp.asarray(Xb), cfg, is_similarity=False, batched=True))
        marks = obs_trace.take_marks()
    assert [m[0] for m in marks] == ["start", *STAGES]
    assert all(b[1] >= a[1] for a, b in zip(marks, marks[1:]))


def test_persistent_cache_written_only_without_marks(tmp_path):
    """JAX writes no persistent-cache entry for a program with host
    callbacks: the unmarked program's entry is written, the marked
    one's is not."""
    script = """
import os, sys
import jax
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
from repro.core import cluster
from repro.data.timeseries import make_dataset
from repro.obs import trace
X = make_dataset(36, 24, 3, noise=0.7, seed=11)[0]
def entries():
    return len(os.listdir(os.environ["JAX_COMPILATION_CACHE_DIR"]))
cluster(X, k=3)
off = entries()
with trace.tracing():
    cluster(X, k=3)
print(off, entries())
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    off, on = map(int, p.stdout.split()[-2:])
    assert off >= 1 and on == off


# ---------------------------------------------------------------------------
# spans as profiler annotations
# ---------------------------------------------------------------------------

def test_span_is_an_annotation_of_a_profiler_session(tmp_path):
    from jax.profiler import ProfileData

    with obs_export.profile(str(tmp_path)):
        with obs_trace.span("obs.annotated"):
            jax.block_until_ready(jnp.ones(5) * 2)
    with obs_trace.span("obs.not-annotated"):
        pass
    files = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    assert files
    names = {ev.name for plane in ProfileData.from_file(files[-1]).planes
             for line in plane.lines for ev in line.events}
    assert "obs.annotated" in names
    assert "obs.not-annotated" not in names
    assert not obs_trace.enabled()
