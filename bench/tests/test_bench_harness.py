"""``BENCHMARK.json`` resolves to its files, keeps to the allowed
characters, and ``bench/run.py`` refuses to measure without a TPU while a
CPU rehearsal of every cell runs end to end."""

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import check  # noqa: E402
import drivers  # noqa: E402
import spec  # noqa: E402

SPEC = spec.load_spec()
WORKLOADS = [w["name"] for w in SPEC["workloads"]]
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]
LINE = re.compile(r"^[^\n\t]{1,200}$")


@pytest.mark.parametrize("workload", WORKLOADS)
def test_workload_resolves_to_its_files(workload):
    cell = spec.cell(SPEC, workload)
    entry = next(w for w in SPEC["workloads"] if w["name"] == workload)
    assert spec.config_path(SPEC, entry["config"]).is_file()
    assert spec.traffic_path(entry["traffic"]).is_file()
    assert cell.traffic["driver"] in drivers.DRIVERS
    assert set(check.NUMBERS) <= set(cell.config["limits"])
    names = [m["name"] for m in cell.end_to_end]
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(spec.reader(m["name"]))
    # a per-layer metric's cell reports the end-to-end metric it moves
    for m in cell.per_layer:
        assert m["moves"] in names


def test_every_configuration_and_metric_is_used():
    cells = [spec.cell(SPEC, w) for w in WORKLOADS]
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    for m in METRICS:
        assert any(m["name"] in [x["name"] for x in c.end_to_end
                                 + c.per_layer] for c in cells), m["name"]
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
    for f in files:
        assert f.startswith(tuple(p + "/" for p in SPEC["paths"]))


def test_names_units_and_lines_use_allowed_characters():
    names = ([c["name"] for c in SPEC["configs"]] + WORKLOADS
             + [m["name"] for m in METRICS]
             + [w["config"] for w in SPEC["workloads"]]
             + [w["traffic"] for w in SPEC["workloads"]]
             + [k for c in SPEC["configs"] for k in c["reduced"]])
    for name in names:
        assert spec.NAME.match(name), name
    for group in (SPEC["configs"], SPEC["workloads"], METRICS):
        assert len({x["name"] for x in group}) == len(group)
    for m in METRICS:
        assert spec.UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for text in ([w["why"] for w in SPEC["workloads"]]
                 + [c["why"] for c in SPEC["configs"]]
                 + [c["source"] for c in SPEC["configs"]]
                 + [m["layer"] for m in SPEC["per_layer"]]
                 + SPEC["command"]):
        assert LINE.match(text), text
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for p in SPEC["paths"]:
        assert re.match(r"^[A-Za-z0-9_./-]{1,200}$", p) and ".." not in p
    for f in (BENCH / "metrics").glob("*.py"):
        assert re.match(r"^[A-Za-z0-9_.-]+$", f.stem), f.name


def run_bench(*args, cwd=ROOT, env_extra=None, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=timeout)


def last_json(stdout):
    lines = [ln for ln in stdout.splitlines() if ln.startswith("{")]
    return json.loads(lines[-1]) if lines else None


def test_no_tpu_no_result():
    p = run_bench("--workload", WORKLOADS[0], "--seed", "1",
                  "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert last_json(p.stdout) is None
    assert "no TPU" in p.stderr


def test_only_the_benchmark_files_is_not_enough(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_bench("--workload", WORKLOADS[0], "--seed", "1", "--seconds",
                  "1", "--trace", "0", "--rehearse", cwd=tmp_path)
    assert p.returncode != 0
    assert last_json(p.stdout) is None


@pytest.mark.parametrize("workload", WORKLOADS)
def test_rehearsal_runs_end_to_end(workload):
    p = run_bench("--workload", workload, "--seed", str(2 ** 31 + 12345),
                  "--seconds", "1", "--trace", "0", "--rehearse")
    assert p.returncode == 0, p.stderr[-3000:]
    out = last_json(p.stdout)
    assert set(out) == {"rehearsal"}
    res = out["rehearsal"]
    assert res["correct"] is True
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    cell = spec.cell(SPEC, workload)
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert res["device"]["platform"] == "cpu"
    # the compared numbers are the last lines on standard error
    tail = p.stderr.strip().splitlines()[-(len(check.NUMBERS) + 1):]
    assert [ln.split(":")[0] for ln in tail] == [
        f"check {n}" for n in check.NUMBERS] + ["check correct"]


def test_due_times_same_load_for_every_seed():
    traffic = {"rate_per_s": 8.0}
    runs = [drivers.due_times(traffic, s, 45.0) for s in (1, 2 ** 31 + 7)]
    assert all(0.0 <= t.min() and t.max() < 45.0 for t in runs)
    assert all(bool(np.all(np.diff(t) > 0)) for t in runs)
    gaps = [np.sort(np.diff(t)) for t in runs]
    # the same gaps in another order: the counts agree within one request
    assert abs(len(runs[0]) - len(runs[1])) <= 1
    assert not np.array_equal(np.diff(runs[0])[:20], np.diff(runs[1])[:20])
    assert abs(gaps[0].mean() - 1 / 8.0) < 0.02


def test_serve_mean_counts_every_request_until_it_ended():
    from types import SimpleNamespace

    reqs = [dict(due=0.0, done=0.5, failed=False),
            dict(due=1.0, done=2.0, failed=True),
            dict(due=2.0, done=None, failed=True)]
    ctx = SimpleNamespace(run=SimpleNamespace(requests=reqs, closed=6.0))
    assert spec.reader("serve_mean_s")(ctx) == pytest.approx((0.5 + 1 + 4) / 3)
    ctx.run.requests = []
    assert spec.reader("serve_mean_s")(ctx) is None


def test_serving_driver_rehearsal_runs_end_to_end():
    """The serving traffic (no cell in BENCHMARK.json yet) at toy size on
    the CPU: every request answered and correct, and its readers read."""
    from types import SimpleNamespace

    import run as run_mod

    config = json.loads((BENCH / "configs" / "sp500-252d-opt.json").read_text())
    traffic = json.loads(spec.traffic_path("serve-poisson").read_text())
    config, traffic = run_mod.rehearsal_sizes(config, traffic)
    run = drivers.drive_serve(config, traffic, 2 ** 31 + 12345, 1.0,
                              "interpret", 0.0)
    assert run.compiles == 0
    assert run.attempted >= 1 and run.failed == 0
    worst = check.run_checks(run.answers, run.inputs, config["k"],
                             traffic["checked"], 5)
    assert check.verdict(worst, config["limits"])[0]
    ctx = SimpleNamespace(run=run, trace=None)
    assert spec.reader("serve_mean_s")(ctx) > 0
    assert spec.reader("serve.submit_ms")(ctx) > 0
    assert spec.reader("serve.per_drain")(ctx) >= 1
    assert spec.reader("device_idle.serve")(ctx) is None


def test_compile_without_running_leaves_nothing_to_compile():
    sys.path.insert(0, str(ROOT / "src"))
    from repro.core import PipelineConfig, cluster
    from repro.obs import trace as obs_trace
    import data

    X = data.ucr_like(40, 24, 3, noise=0.8, warp=0.05, seed=0)
    cfg = PipelineConfig.opt(backend="interpret")
    ran = []
    drivers.compile_without_running(
        lambda: ran.append(cluster(X, k=3, config=cfg)))
    assert not ran
    with obs_trace.watch_recompiles() as w:
        cluster(X, k=3, config=cfg)
    assert w.count == 0
