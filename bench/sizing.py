"""Size a configuration that has no cell yet, on the chip it finds.

    python bench/sizing.py --config-file <path> --seeds 1 2 3

On the configuration's series with its rows in each seed's order (the
input ``control.py`` reads for that seed), through ``cluster()`` with the
configuration's pipeline: one cold call (``compile_s``: the seconds XLA
compiled; ``cold_s``: the whole call), then one warm call per seed
(``call_s``) with its loop counters, whether it overflowed the fused
slot grid (``overflow``: a staged rerun then gave the answer) and, once
every call has run and the peak memory has been read, the numbers of
``check.py`` against the configuration's reference (``reference_s``:
the seconds it took).  Prints one JSON line per record.  The benchmark's
runs never run this; a cell's limits are set from its readings and
``control.py``'s.
"""

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import check  # noqa: E402
import control  # noqa: E402
import drivers  # noqa: E402

COUNTERS = ("tmfg_pops", "tmfg_inserts", "apsp_rounds", "hac_rescans",
            "hac_merges", "approx_lookups", "approx_fallbacks",
            "approx_pair_lookups", "approx_pair_misses")


def emit(**record):
    print(json.dumps(record), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config-file", type=Path, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--backend", default=None,
                    help="kernel backend (interpret: a CPU rehearsal)")
    args = ap.parse_args(argv)
    config = json.loads(args.config_file.read_text())
    k = int(config["k"])

    import jax
    from repro.core import cluster
    from repro.obs import trace

    dev = jax.devices()[0]
    pcfg = drivers.pipeline_config(config, args.backend)
    emit(config=config["name"], platform=dev.platform, kind=dev.device_kind,
         pipeline=repr(pcfg))

    def call(X):
        with trace.watch_recompiles() as w:
            t0 = time.perf_counter()
            res = cluster(X, k=k, config=pcfg)
            wall = time.perf_counter() - t0
        span = trace.kept_spans("pipeline.fused")
        counts = {c: span[-1].attrs.get(c) for c in COUNTERS} if span else {}
        return res, wall, w, counts

    X0 = control.inputs(config, args.seeds[0])
    _, cold, w, _ = call(X0)
    emit(cold_s=cold, compile_s=w.compile_s, programs=w.count)
    answers = []
    for seed in args.seeds:
        X = control.inputs(config, seed)
        res, wall, w, counts = call(X)
        emit(seed=seed, call_s=wall, programs=w.count,
             overflow=bool(res.overflow), **counts)
        answers.append((seed, X, drivers.answer(res, seed)))
    emit(peak_bytes=(dev.memory_stats() or {}).get("peak_bytes_in_use"))
    reference = check.reference(config)
    for seed, X, a in answers:
        t0 = time.perf_counter()
        tm, labels = reference.answer(X, k)
        emit(seed=seed, reference_s=time.perf_counter() - t0,
             **check.compare(a.edges, a.labels, tm, labels))
    return 0


if __name__ == "__main__":
    sys.exit(main())
