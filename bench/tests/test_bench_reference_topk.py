"""The a-TMFG reference (``reference_topk.py``) and the lookup that
selects a configuration's reference (``check.reference``).

* At K = n - 1 every candidate is in the table, so the top-K reference
  is the dense one: the same edges, bubble tree and labels.
* ``PipelineConfig.approx(sim_k=16)`` at n = 600, where the fused
  program takes its sparse tail, reads within the limits below against
  the top-K reference, and reads further off against the dense one.
* The reference with a one-pass bfloat16 similarity in the program's
  place fails those limits.

Limits at n = 600, L = 46, 6 classes, K = 16 (``ucr_like``, noise 0.8,
warp 0.05; seeds 0-15 on the CPU): the float32 program read 0 and 0 on
every seed; the bf16 control read ``tmfg_unshared`` 0.0123-0.170 and
``ari_gap`` 0-0.414.  So ``tmfg_unshared`` 0.005, under the control's
least by more than half of it; ``ari_gap`` 0.005, a few vertices of 600
moved by a near-tie.
"""

import ast
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import check  # noqa: E402
import data  # noqa: E402
import drivers  # noqa: E402
import reference_topk as rt  # noqa: E402

LIMITS = {"tmfg_unshared": 0.005, "ari_gap": 0.005}


def approx_config(seed: int, n: int = 600, sim_k: int = 16) -> dict:
    return {"name": "ucr-like-approx", "reference": "topk",
            "data": {"generator": "ucr_like", "seed": seed, "n": n, "L": 46,
                     "classes": 6, "noise": 0.8, "warp": 0.05},
            "k": 6, "pipeline": {"constructor": "approx",
                                 "args": {"sim_k": sim_k}},
            "limits": LIMITS}


@pytest.mark.parametrize("n,L,classes,k,seed",
                         [(60, 24, 3, 3, 1), (300, 46, 6, 6, 2)])
def test_full_table_is_the_dense_reference(n, L, classes, k, seed):
    X = data.ucr_like(n, L, classes, noise=0.8, warp=0.05, seed=seed)
    tm, labels = check.reference_answer(X, k)
    tm_k, labels_k = rt.answer(
        X, k, config={"pipeline": {"args": {"sim_k": n - 1}}})
    for field in ("edges", "bubble_verts", "bubble_parent", "bubble_tri",
                  "home_bubble"):
        assert np.array_equal(getattr(tm, field), getattr(tm_k, field)), \
            field
    assert np.array_equal(labels, labels_k)


def test_table_order_is_value_then_lower_index():
    """Ties at the K-th value keep the lower indices, as ``lax.top_k``."""
    X = np.array([[0, 1, 2, 3], [0, 1, 2, 3], [0, 1, 2, 3], [3, 2, 1, 0],
                  [0, 1, 2, 3], [0, 2, 1, 3]], np.float64)
    vals, idx = rt.top_k(rt.pearson(X), 3)
    assert idx[3].tolist() == [5, 0, 1]         # -0.8, then -1.0 ties
    assert idx[4].tolist() == [0, 1, 2]         # four rows tie at 1.0
    assert np.all(np.diff(vals, axis=1) <= 0)


def program_answer(config: dict):
    from repro.core import cluster

    X = drivers.series(config)
    res = cluster(X, k=config["k"],
                  config=drivers.pipeline_config(config, None))
    assert not res.overflow
    return X, drivers.answer(res, ("seed", config["data"]["seed"]))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_approx_program_against_topk_and_dense_reference(seed):
    from repro.core import PipelineConfig
    from repro.core.fused_approx import use_sparse_tail

    config = approx_config(seed)
    assert use_sparse_tail(PipelineConfig.approx(sim_k=16), 600)
    X, a = program_answer(config)
    worst = check.run_checks([a], lambda src: X, config["k"], 1, seed,
                             config)
    ok, table = check.verdict(worst, config["limits"])
    assert ok, table
    dense = check.run_checks([a], lambda src: X, config["k"], 1, seed)
    assert dense["tmfg_unshared"] > worst["tmfg_unshared"] + 0.05, dense


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_bf16_control_fails_where_the_program_passes(seed):
    config = approx_config(seed)
    X, a = program_answer(config)
    reference = check.reference(config)
    tm, labels = reference.answer(X, config["k"])
    ok, _ = check.verdict(check.compare(a.edges, a.labels, tm, labels),
                          config["limits"])
    assert ok
    tm_c, labels_c = reference.answer(X, config["k"],
                                      reference.controls["bf16"])
    ok, table = check.verdict(check.compare(tm_c.edges, labels_c, tm, labels),
                              config["limits"])
    assert not ok, table


def test_control_reads_a_configuration_file(tmp_path, capsys):
    import control

    path = tmp_path / "approx.json"
    path.write_text(json.dumps(approx_config(4, n=300)))
    assert control.main(["--config-file", str(path), "--seeds", "7",
                         "--similarities", "bf16", "f32"]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [x["similarity"] for x in lines] == ["bf16", "f32"]
    assert all(x["reference_s"] > 0 for x in lines)
    assert lines[1]["tmfg_unshared"] < lines[0]["tmfg_unshared"]


def test_configuration_without_reference_is_dense():
    reference = check.reference({"k": 3})
    assert reference.answer is check.reference_answer
    assert reference.controls is check.CONTROLS


@pytest.mark.parametrize("name", ["nosuch", "../reference", ""])
def test_reference_with_no_module_raises(name):
    with pytest.raises(ValueError, match="no bench/reference_"):
        check.reference({"reference": name})


def test_topk_reference_imports_nothing_of_the_program():
    for module in ("reference_topk.py", "reference.py"):
        tree = ast.parse((BENCH / module).read_text())
        names = [a.name for node in ast.walk(tree)
                 if isinstance(node, ast.Import) for a in node.names]
        names += [node.module or "" for node in ast.walk(tree)
                  if isinstance(node, ast.ImportFrom)]
        assert not [m for m in names if m.split(".")[0] == "repro"], module
    code = ("import sys; import numpy as np; import reference_topk as rt; "
            "X = np.random.default_rng(0).normal(size=(40, 16)); "
            "rt.answer(X, 1, config={'pipeline': {'args': {'sim_k': 8}}}); "
            "print(sorted(m for m in sys.modules if m.split('.')[0] == "
            "'repro'))")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "-c", code], cwd=BENCH,
                         capture_output=True, text=True, check=True,
                         env={**env, "JAX_PLATFORMS": "cpu"}, timeout=300)
    assert out.stdout.strip().splitlines()[-1] == "[]"
