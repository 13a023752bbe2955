"""Whether the timed path's answers are correct: a sample of them, drawn
from the seed, against the configuration's plain reference run on the
same inputs.

The reference is the configuration's: ``"reference": "<name>"`` in its
file names ``bench/reference_<name>.py``, whose ``answer(X, k,
similarity, config=...)`` gives (TMFG, labels) and whose ``CONTROLS``
name the similarities a control plants; without the key it is the dense
reference, ``reference.py`` through :func:`reference_answer`.

Two numbers are compared, each the worst over the sampled problems, each
against the limit the configuration file gives it:

* ``tmfg_unshared``  the share of the program's 3n-6 edges that the
                     reference's TMFG does not have (1 where the program's
                     edges are not 3n-6 distinct pairs): the similarity
                     (Pearson, or the window's co-moments) and the TMFG
                     built on it.
* ``ari_gap``        1 - ARI of the program's labels against the
                     reference's: DBHT, APSP and the nested HAC.
"""

from __future__ import annotations

import functools
import importlib.util
import re
import sys
from pathlib import Path
from types import SimpleNamespace
from typing import Callable, Dict, List, Optional

import numpy as np

import reference as ref

BENCH = Path(__file__).resolve().parent
NUMBERS = ("tmfg_unshared", "ari_gap")


def compare(edges: np.ndarray, labels: np.ndarray, tm: "ref.TMFG",
            ref_labels: np.ndarray) -> Dict[str, float]:
    """The numbers of one answer against its reference."""
    n = len(ref_labels)
    e = np.sort(np.asarray(edges, np.int64), axis=1)
    valid = (e.shape == (3 * n - 6, 2) and e.min() >= 0 and e.max() < n
             and bool(np.all(e[:, 0] != e[:, 1]))
             and len(np.unique(e, axis=0)) == 3 * n - 6)
    key = lambda a: a[:, 0] * n + a[:, 1]           # noqa: E731
    shared = np.intersect1d(key(e), key(tm.edges)).size if valid else 0
    return dict(tmfg_unshared=1.0 - shared / (3 * n - 6),
                ari_gap=1.0 - ref.ari(ref_labels, labels))


def reference_answer(X: np.ndarray, k: int, similarity=ref.pearson):
    """(TMFG, labels) of the reference on series X (n, L), built from
    ``similarity`` (the float64 Pearson matrix unless a control asks for
    a lower precision)."""
    S = similarity(X)
    tm = ref.tmfg_lazy(S)
    return tm, ref.dbht_labels(S, tm, k)


# the dense reference's controls: its similarity one step below the
# configurations' float32 (bf16), and the two above it for comparison
CONTROLS = {"bf16": ref.pearson_bf16, "3pass": ref.pearson_3pass,
            "f32": ref.pearson_f32}


def reference(config: dict) -> SimpleNamespace:
    """The configuration's plain reference: ``answer(X, k[, similarity])``
    -> (TMFG, labels), and ``controls``, the similarities a control puts
    in place of the reference's own, by name.  A named reference with no
    module raises."""
    name = config.get("reference")
    if name is None:
        return SimpleNamespace(answer=reference_answer, controls=CONTROLS)
    path = BENCH / f"reference_{name}.py"
    if not re.fullmatch(r"[A-Za-z0-9_]+", str(name)) or not path.is_file():
        raise ValueError(f"the configuration names reference {name!r}, "
                         f"and there is no bench/reference_{name}.py")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_reference_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return SimpleNamespace(answer=functools.partial(mod.answer, config=config),
                           controls=mod.CONTROLS)


def sample(answers: List, count: int, seed: int) -> List:
    if len(answers) <= count:
        return list(answers)
    pick = np.random.default_rng([seed, 3]).choice(len(answers), count,
                                                   replace=False)
    return [answers[i] for i in sorted(pick)]


def run_checks(answers: List, inputs: Callable, k: int, count: int,
               seed: int, config: Optional[dict] = None) -> Dict[str, float]:
    """The worst of each number over a seeded sample of ``count`` answers
    against ``config``'s reference (every number reads 1 when no answer
    came back)."""
    answer = reference(config or {}).answer
    worst = dict.fromkeys(NUMBERS, 0.0) if answers else \
        dict.fromkeys(NUMBERS, 1.0)
    for a in sample(answers, count, seed):
        tm, labels = answer(inputs(a.source), k)
        got = compare(a.edges, a.labels, tm, labels)
        for name in NUMBERS:
            worst[name] = max(worst[name], got[name])
    return worst


def verdict(worst: Dict[str, float], limits: Dict[str, float]):
    """(correct, {name: {"value", "limit"}}), printed on stderr too."""
    table = {name: {"value": worst[name], "limit": float(limits[name])}
             for name in NUMBERS}
    ok = all(v["value"] <= v["limit"] for v in table.values())
    for name, v in table.items():
        print(f"check {name}: {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    print(f"check correct: {ok}", file=sys.stderr, flush=True)
    return ok, table
