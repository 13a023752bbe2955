"""The control of the comparison that decides ``correct``, at a cell's size.

    python bench/control.py --config <name> --seeds 1 2 3
    python bench/control.py --config-file <path> --seeds 1 2 3

For each seed, on the configuration's own input at its own size (the
whole series matrix, or one window of the return panel), the
configuration's plain reference (``check.reference``) is put in the
program's place with its similarity computed one precision below the
configuration's float32 (``bf16``: one bfloat16 pass), and with a
three-pass bfloat16 similarity (``3pass``, what ``precision=HIGH``
computes) and in float32 (``f32``) for comparison.  Prints one JSON line
per seed and similarity: the numbers of ``check.py`` against the float64
reference, and the seconds the float64 reference took (``reference_s``).
``--config`` names a configuration of ``BENCHMARK.json``;
``--config-file`` reads one that has no cell yet.

With ``--shifts 1 32`` (a return-panel configuration) it also reads the
faults a served or batched answer can have, with the reference in the
program's place: the answer of the window ``d`` days earlier handed back
for the right one (a service returning its previous state is ``d`` >= 1
requests stale; a batch whose second half repeats its first is off by
half the batch).  The benchmark's runs never run this; the limits in the
configuration files were set from it.
"""

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import check  # noqa: E402
import drivers  # noqa: E402
import spec  # noqa: E402


def inputs(config: dict, seed: int):
    """The first problem a run with ``seed`` solves."""
    if config["data"]["generator"] == "ucr_like":
        X = drivers.series(config)
        return X[drivers.permutation(X.shape[0], seed, 0)]
    return drivers.panel(config, seed, config["data"]["window"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    which = ap.add_mutually_exclusive_group(required=True)
    which.add_argument("--config")
    which.add_argument("--config-file", type=Path)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--similarities", nargs="+")
    ap.add_argument("--shifts", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    path = args.config_file or spec.config_path(spec.load_spec(),
                                                args.config)
    config = json.loads(path.read_text())
    reference = check.reference(config)
    k = int(config["k"])
    for seed in args.seeds:
        X = inputs(config, seed)
        t0 = time.perf_counter()
        tm, labels = reference.answer(X, k)
        ref_s = time.perf_counter() - t0
        for name in args.similarities or reference.controls:
            tm_c, lab_c = reference.answer(X, k, reference.controls[name])
            got = check.compare(tm_c.edges, lab_c, tm, labels)
            print(json.dumps(dict(config=config["name"], seed=seed,
                                  similarity=name, reference_s=ref_s,
                                  **got)), flush=True)
        for d in args.shifts:
            W = config["data"]["window"]
            P = drivers.panel(config, seed, W + d)
            tm, labels = reference.answer(P[:, d:], k)
            tm_c, lab_c = reference.answer(P[:, :W], k)
            got = check.compare(tm_c.edges, lab_c, tm, labels)
            print(json.dumps(dict(config=config["name"], seed=seed,
                                  stale_days=d, **got)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
