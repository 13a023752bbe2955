"""The per-layer metrics read from the program's own spans and counters:
a traced rehearsal of each cell reports them, and they are read from the
calls of the window alone."""

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import drivers  # noqa: E402
import program_spans  # noqa: E402
import run as run_mod  # noqa: E402
import spec  # noqa: E402

SPEC = spec.load_spec()
PROGRAM_METRICS = ("tmfg.pops_per_insert", "apsp.rounds",
                   "hac.rescans_per_merge", "fused.host_share")


def read_all(ctx):
    return {m: spec.reader(m)(ctx) for m in PROGRAM_METRICS}


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_traced_rehearsal_reports_the_program_metrics(workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed",
         str(2 ** 31 + 99), "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = [ln for ln in p.stdout.splitlines() if ln.startswith("{")][-1]
    res = json.loads(line)["rehearsal"]
    assert res["correct"] is True
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(PROGRAM_METRICS) <= set(got)
    assert got["tmfg.pops_per_insert"] >= 1.0
    assert got["apsp.rounds"] >= 1.0
    assert got["hac.rescans_per_merge"] >= 1.0
    assert 0.0 <= got["fused.host_share"] <= 100.0
    cell = spec.cell(SPEC, workload)
    for m in PROGRAM_METRICS:
        entry = next(x for x in cell.per_layer if x["name"] == m)
        assert entry["moves"] == "cluster_s"
        assert entry["source"] in ("program_counter", "program_span")


def test_readers_read_the_window_calls_only():
    """A call outside the window's ``bench.call`` spans (a planted
    warm-up call with absurd counters) changes no reading; the same
    call planted inside the window does."""
    from repro.obs import trace as obs_trace

    config = json.loads((BENCH / "configs" / "sp500-252d-opt.json")
                        .read_text())
    traffic = json.loads(spec.traffic_path("backtest-b64").read_text())
    config, traffic = run_mod.rehearsal_sizes(config, traffic)
    run = drivers.drive_cluster_batch(config, traffic, 2 ** 31 + 5, 1.0,
                                      "interpret", 0.0)
    ctx = SimpleNamespace(run=run, trace=None)
    calls = program_spans.window_calls(ctx)
    assert len(calls) == len(run.calls) >= 1
    assert sum(sp.attrs["problems"] for sp in calls) == run.attempted
    clean = read_all(ctx)
    assert all(v is not None for v in clean.values()), clean

    with obs_trace.span("pipeline.fused", keep=True) as planted:
        pass
    planted.attrs.update(problems=1.0, tmfg_pops=1e6, tmfg_inserts=1.0,
                         apsp_rounds=1e6, hac_rescans=1e6, hac_merges=1.0,
                         device_s=0.0)
    first = min(s for name, s, _ in run.spans.events if name == "bench.call")
    planted.start, planted.duration = first - 1.0, 0.5     # the warm-up
    assert read_all(ctx) == clean
    s, e = next((s, e) for name, s, e in run.spans.events
                if name == "bench.call")
    planted.start, planted.duration = s + (e - s) / 4, (e - s) / 4
    inside = read_all(ctx)
    assert all(inside[m] != clean[m] for m in PROGRAM_METRICS), inside


def test_no_program_record_reads_none():
    """A program that keeps no record of its calls (or a window without
    calls) gives every reader nothing to read."""
    run = drivers.Run()
    ctx = SimpleNamespace(run=run, trace=None)
    assert read_all(ctx) == {m: None for m in PROGRAM_METRICS}
