"""Find the knee of a serving cell: the highest offered rate the service
sustains without a growing backlog.

    python bench/sweep.py --workload sp500-serve --seconds 30 --rates 6 8 10

Runs the cell's open loop once per rate, in one process, and prints one
JSON line per rate: requests due, resolved inside the window, still
unresolved when the window closed, p50/p95 latency, and the median
latency of the first and of the last third of the requests (a growing
backlog shows as a last third far slower than the first).  The
benchmark's runs never run this; the cell's traffic file holds the rate
chosen from it.
"""

import argparse
import json
import math
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import spec  # noqa: E402


def nearest_rank(values, q: float) -> float:
    """The q-th percentile by nearest rank (a value that was observed)."""
    v = sorted(values)
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import run as run_mod

    cell = spec.cell(spec.load_spec(), args.workload)
    import os

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run_mod.CACHE_DIR)
    sys.path.insert(0, str(spec.ROOT / "src"))
    import jax

    from repro.core import jitcache

    jitcache.use_persistent_cache()
    print(json.dumps(dict(device=jax.devices()[0].device_kind)), flush=True)
    import drivers

    for rate in args.rates:
        traffic = dict(cell.traffic, rate_per_s=rate)
        run = drivers.drive_serve(cell.config, traffic, args.seed,
                                  args.seconds, None, time.perf_counter())
        reqs = run.requests
        inside = sum(1 for r in reqs
                     if not r["failed"] and r["done"] <= args.seconds)
        ok = [r["done"] - r["due"] for r in reqs if not r["failed"]]
        lat = sorted(ok)
        third = max(1, len(ok) // 3)
        left = len(reqs) - inside
        print(json.dumps(dict(
            rate=rate, due=len(reqs), resolved_in_window=inside,
            left_at_close=left, failed=run.failed,
            p50_s=nearest_rank(lat, 50) if lat else None,
            p95_s=nearest_rank(lat, 95) if lat else None,
            p50_first_third_s=nearest_rank(ok[:third], 50)
            if ok else None,
            p50_last_third_s=nearest_rank(ok[-third:], 50)
            if ok else None,
            per_drain=sum(run.drains) / max(len(run.drains), 1),
            compiles=run.compiles)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
