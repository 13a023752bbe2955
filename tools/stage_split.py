"""Per-stage time of a benchmark cell's fused calls, stage marks on.

    python tools/stage_split.py --workload <cell> --seed <n> --seconds <s>

Runs one cell of ``BENCHMARK.json`` the way ``bench/run.py --trace 0``
does (same configuration, traffic, warm-up and window), but with
``repro.obs`` tracing on from the start, so the warm-up builds the fused
program that takes a host-clock mark at each stage boundary (DESIGN.md
§15.5).  JAX keeps no persistent-cache entry for such a program, so it
compiles in every process.  Prints one JSON line: ``cluster_s`` as the
benchmark's reader computes it, the set-up seconds, the programs
compiled inside the window, and for each call of the window its
problems, host phase seconds and stage seconds.

It refuses to run anywhere but on a TPU; ``--rehearse`` runs the cell at
the benchmark's toy sizes on the CPU, interpret-mode kernels, for tests.
"""

from __future__ import annotations

import time

START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, toy sizes, interpret-mode kernels")
    args = ap.parse_args(argv)

    import spec

    cell = spec.cell(spec.load_spec(), args.workload)
    config, traffic = cell.config, cell.traffic
    if args.rehearse:
        import run as bench_run
        config, traffic = bench_run.rehearsal_sizes(config, traffic)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"stage_split: no TPU (JAX found {dev.platform})",
              file=sys.stderr)
        return 2

    import drivers
    import program_spans
    from repro.obs import trace as obs_trace

    obs_trace.enable()
    run = drivers.DRIVERS[traffic["driver"]](
        config, traffic, args.seed, args.seconds,
        "interpret" if args.rehearse else None, START)
    obs_trace.disable()
    ctx = SimpleNamespace(run=run)
    calls = [dict(problems=sp.attrs.get("problems"),
                  seconds=sp.duration,
                  phases={p: sp.attrs.get(f"{p}_s")
                          for p in ("put", "dispatch", "device", "assemble")},
                  stages=sp.attrs.get("stages"))
             for sp in program_spans.window_calls(ctx)]
    print(json.dumps(dict(
        workload=args.workload, seed=args.seed, device=dev.device_kind,
        cluster_s=spec.reader("cluster_s")(ctx), setup_s=run.setup_s,
        compiles_in_window=run.compiles, calls=calls)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
