"""Run one cell of the benchmark once, on the chip it finds.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic and metrics are the files that
``BENCHMARK.json`` names (see ``bench/spec.py``).  The run makes its
inputs from ``--seed``, warms up the cell's shapes, measures for
``--seconds``, compares a seeded sample of the answers with the plain
reference, and prints one JSON object as its last line: ``correct``,
``attempted``, ``failed``, ``metrics`` (the end-to-end metrics, or with
``--trace 1`` the per-layer ones, from a run whose window is the
traffic file's ``trace_seconds``, traced by the profiler),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``: each
number compared with its limit.

It refuses to measure anywhere but on a TPU, and exits non-zero with no
result line when JAX finds none or fewer chips than the cell asks for.
``--rehearse`` runs the same steps on the CPU at toy sizes with the
kernels in interpret mode, for tests; its last line is wrapped as
``{"rehearsal": ...}`` so that it cannot be read as a device result.
"""

from __future__ import annotations

import time

START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import spec as spec_mod  # noqa: E402

# JAX's persistent compilation cache: one fixed directory in the checkout,
# so that every run after a cell's first finds its programs there
CACHE_DIR = ROOT / ".jax_cache"


def rehearsal_sizes(config: dict, traffic: dict):
    """Toy sizes of a cell for a CPU rehearsal."""
    config, traffic = copy.deepcopy(config), dict(traffic)
    d = config["data"]
    for key, cap in (("n", 40), ("L", 24), ("classes", 3), ("window", 24)):
        if key in d:
            d[key] = min(d[key], cap)
    config["k"] = min(config["k"], 3)
    if "batch" in traffic:
        traffic["batch"] = min(traffic["batch"], 2)
    if "rate_per_s" in traffic:
        traffic["rate_per_s"] = min(traffic["rate_per_s"], 4.0)
    traffic["checked"] = min(traffic["checked"], 2)
    return config, traffic


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, toy sizes, interpret-mode kernels")
    args = ap.parse_args(argv)

    spec = spec_mod.load_spec()
    cell = spec_mod.cell(spec, args.workload)
    config, traffic = cell.config, cell.traffic
    if args.rehearse:
        config, traffic = rehearsal_sizes(config, traffic)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from repro.core import jitcache

    jitcache.use_persistent_cache()
    devs = jax.devices()
    if not args.rehearse:
        if devs[0].platform != "tpu":
            print(f"bench: no TPU (JAX found {devs[0].platform}); "
                  "nothing was measured", file=sys.stderr)
            return 2
        if len(devs) < cell.chips:
            print(f"bench: {cell.name} needs {cell.chips} chips, JAX found "
                  f"{len(devs)}; nothing was measured", file=sys.stderr)
            return 2
    peak = None if args.rehearse else __import__("roofline").peaks(
        devs[0].device_kind)

    import drivers

    # a traced run's window is the cell's traced seconds: a whole window's
    # trace would take minutes to stop (see tracing.py)
    seconds = float(traffic["trace_seconds"]) if args.trace else args.seconds
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace \
        else None
    try:
        profiler = drivers.Profiler(trace_dir, seconds) if trace_dir \
            else None
        run = drivers.DRIVERS[traffic["driver"]](
            config, traffic, args.seed, seconds,
            "interpret" if args.rehearse else None, START, trace=profiler)
        trace = None
        if trace_dir:
            import tracing

            t0 = time.perf_counter()
            start, traced = run.traced
            trace = tracing.read_dir(trace_dir, cell.chips, traced,
                                     run.spans.events, start)
            print(f"bench: {traced:.3f} s traced, "
                  f"{sum(map(len, trace.ops))} device ops read in "
                  f"{time.perf_counter() - t0:.1f} s", flush=True)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    used = devs[:cell.chips]
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in used)
    print(f"bench: {run.compiles} programs compiled inside the window",
          flush=True)
    if run.compiles:
        print("bench: the window compiled; the run is void", file=sys.stderr)
        return 3

    ctx = SimpleNamespace(run=run, trace=trace, peak=peak, config=config,
                          traffic=traffic, shape=run.shape)
    metrics = spec_mod.read_metrics(
        cell.per_layer if args.trace else cell.end_to_end, ctx)
    device = dict(platform=devs[0].platform, kind=devs[0].device_kind,
                  count=len(devs), memory_peak_bytes=int(peak_bytes))
    result = dict(correct=False, attempted=run.attempted, failed=run.failed,
                  metrics=metrics, device=device)
    if trace is not None:
        device.update(busy_s=trace.busy_s(), window_s=trace.window_s)
        result["breakdown"] = dict(device_ops=trace.top_ops(),
                                   idle_gaps=trace.idle_gaps())

    import check

    worst = check.run_checks(run.answers, run.inputs, int(config["k"]),
                             int(traffic["checked"]), args.seed, config)
    result["correct"], result["checks"] = check.verdict(
        worst, config["limits"])
    line = {"rehearsal": result} if args.rehearse else result
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
