"""Benchmark driver — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--scale 1.0] [--only fig2,...]
                                            [--strict] [--json out.json]
                                            [--check-schema]

Prints ``name,us_per_call,derived`` CSV per section (plus section-specific
columns).  Sections:
  fig2  runtime per variant            (bench_tmfg)
  fig3  parallel scaling surrogates    (bench_speedup)
  fig5  stage breakdown                (bench_breakdown)
  fig6  ARI per variant                (bench_ari)
  fig7  edge-sum reduction             (bench_edgesum)
  apsp  exact vs hub APSP              (bench_apsp)
  sparse  sparse APSP factor + DBHT tail scaling (bench_sparse_apsp)
  stream  streaming window + service   (bench_stream)
  load  mixed-tenant admission overload drive (bench_load)
  pipeline  fused vs staged latency    (bench_pipeline)
  approx  dense vs top-K similarity    (bench_approx)
  filters  per-filter build + quality  (bench_filters)
  roofline  dry-run roofline table     (roofline; needs results/dryrun)

``--strict`` turns section failures into a nonzero exit code (CI);
``--json`` writes every section's rows to one JSON file (the CI
artifact).  Without ``--strict`` failures print and the run continues,
and the exit code is 0 only off the chip: on a TPU any failed section
exits nonzero.  The persistent compile cache is set up at start
(``core/jitcache.use_persistent_cache``).

``--check-schema`` enforces the observability row contract (DESIGN.md
§15.4): every row of every run section carries a non-empty
``compile_s`` and ``run_s`` (the compile-vs-run split that fixed the
BENCH_5 false regression), and any ``replay_recompiles`` field is 0 —
a warm replay leg that compiles is the §15.2 watchdog's failure mode
surfacing in CI.  Roofline is exempt (a dry-run table with no timed
legs), as are rows reporting a failed/skipped leg.  ``load`` rows
additionally must carry the §16.4 serving columns — ``shed_total`` and
``degraded_total`` present, ``lost_ticks`` exactly 0 — so an admission
regression (silent tick loss, an overload drive that never sheds)
fails CI the same way a recompile does.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
import time
from pathlib import Path

from . import (bench_approx, bench_apsp, bench_ari, bench_breakdown,
               bench_edgesum, bench_filters, bench_load, bench_pipeline,
               bench_sparse_apsp, bench_speedup, bench_stream,
               bench_tmfg, roofline)

SECTIONS = {
    "fig2": lambda scale: bench_tmfg.run(scale),
    "fig3": lambda scale: bench_speedup.run(scale),
    "fig5": lambda scale: bench_breakdown.run(scale),
    "fig6": lambda scale: bench_ari.run(scale),
    "fig7": lambda scale: bench_edgesum.run(scale),
    "apsp": lambda scale: bench_apsp.run(scale),
    "sparse": lambda scale: bench_sparse_apsp.run(scale),
    "stream": lambda scale: bench_stream.run(scale),
    "load": lambda scale: bench_load.run(scale),
    "pipeline": lambda scale: bench_pipeline.run(scale),
    "approx": lambda scale: bench_approx.run(scale),
    "filters": lambda scale: bench_filters.run(scale),
    "roofline": lambda scale: roofline.run(),
}

# dry-run tables with no timed legs — nothing to split (DESIGN.md §15.4)
SCHEMA_EXEMPT = {"roofline"}

_STAMP_RE = re.compile(r"^BENCH_(\d+)\.json$")


def load_trajectory(root="."):
    """The committed ``BENCH_<pr>.json`` stamps, as (pr, data) pairs in
    ascending PR order.

    GAP-TOLERANT by construction: the stamps are globbed and sorted by
    their embedded PR number, never indexed by an expected consecutive
    sequence — PRs whose CI stamp was not committed (BENCH_8) simply
    don't appear, and trajectory consumers must treat "previous stamp"
    as "previous *available* stamp".  Files that don't match the
    ``BENCH_<number>.json`` pattern are ignored."""
    stamps = []
    for p in Path(root).glob("BENCH_*.json"):
        m = _STAMP_RE.match(p.name)
        if not m:
            continue
        try:
            stamps.append((int(m.group(1)), json.loads(p.read_text())))
        except (OSError, json.JSONDecodeError) as e:
            print(f"# trajectory: skipping unreadable {p.name}: {e}",
                  file=sys.stderr)
    return sorted(stamps, key=lambda t: t[0])


def print_trajectory(root=".") -> int:
    """``--trajectory``: one line per available stamp — PR, scale,
    sections present, failures — each compared against the previous
    available stamp (NOT pr-1; see load_trajectory)."""
    traj = load_trajectory(root)
    if not traj:
        print(f"# no BENCH_<pr>.json stamps under {root}", file=sys.stderr)
        return 0
    prev_secs = None
    for pr, data in traj:
        secs = sorted(s for s, rows in data.get("sections", {}).items()
                      if isinstance(rows, list))
        failed = data.get("failed", [])
        delta = ""
        if prev_secs is not None:
            new = sorted(set(secs) - set(prev_secs))
            gone = sorted(set(prev_secs) - set(secs))
            delta = (f" (+{','.join(new)})" if new else "") + \
                    (f" (-{','.join(gone)})" if gone else "")
        print(f"BENCH_{pr}: scale={data.get('scale', '?')} "
              f"sections={','.join(secs)}{delta}"
              + (f" FAILED={','.join(failed)}" if failed else ""))
        prev_secs = secs
    return 0


def check_schema(results) -> list:
    """The §15.4 row contract; returns a list of violation strings."""
    bad = []
    for section, rows in results.items():
        if section in SCHEMA_EXEMPT or not isinstance(rows, list):
            continue
        for i, row in enumerate(rows):
            if not isinstance(row, dict):
                continue
            derived = str(row.get("derived", ""))
            if "FAILED" in derived or "SKIPPED" in derived:
                continue                   # the leg never ran warm
            where = f"{section}[{i}] ({row.get('name', '?')})"
            for field in ("compile_s", "run_s"):
                if str(row.get(field, "")).strip() == "":
                    bad.append(f"{where}: missing {field}")
            rr = row.get("replay_recompiles", 0)
            if int(rr or 0) != 0:
                bad.append(f"{where}: replay_recompiles={rr} (want 0 — "
                           f"a warm replay leg compiled)")
            if section == "load":
                # the §16.4 serving contract: overload rows must show
                # their shed/degraded accounting and zero tick loss
                for field in ("shed_total", "degraded_total"):
                    if str(row.get(field, "")).strip() == "":
                        bad.append(f"{where}: missing {field} (§16.4 "
                                   f"serving column)")
                lt = row.get("lost_ticks", "")
                if str(lt).strip() == "" or int(lt or 0) != 0:
                    bad.append(f"{where}: lost_ticks={lt!r} (want 0 — "
                               f"overload must never drop ingestion)")
    return bad


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0,
                    help="dataset size multiplier (CPU-sized defaults)")
    ap.add_argument("--only", default="",
                    help="comma-separated section subset")
    ap.add_argument("--strict", action="store_true",
                    help="exit nonzero when any requested section fails")
    ap.add_argument("--json", default="", metavar="PATH",
                    help="also write section rows as JSON to PATH")
    ap.add_argument("--check-schema", action="store_true",
                    help="fail unless every run row carries the "
                         "compile_s/run_s split and every "
                         "replay_recompiles field is 0 (DESIGN.md §15.4)")
    ap.add_argument("--trajectory", action="store_true",
                    help="list the committed BENCH_<pr>.json stamps "
                         "(gap-tolerant: non-consecutive PR numbers are "
                         "fine) and exit without benchmarking")
    args = ap.parse_args(argv)

    if args.trajectory:
        return print_trajectory()

    only = [s for s in args.only.split(",") if s] or list(SECTIONS)
    unknown = [s for s in only if s not in SECTIONS]
    if unknown:
        print(f"unknown sections: {unknown}; have {list(SECTIONS)}",
              file=sys.stderr)
        return 2

    import jax
    on_chip = jax.devices()[0].platform == "tpu"

    results, failed = {}, []
    for name in only:
        print(f"\n===== {name} =====")
        t0 = time.time()
        try:
            results[name] = SECTIONS[name](args.scale)
        except Exception as e:  # noqa: BLE001 — report, record, continue
            failed.append(name)
            results[name] = {"error": f"{type(e).__name__}: {e}"}
            print(f"{name},,SECTION-FAILED:{type(e).__name__}: {e}")
        print(f"# {name} done in {time.time() - t0:.1f}s", file=sys.stderr)

    if args.json:
        with open(args.json, "w") as f:
            json.dump({"scale": args.scale, "sections": results,
                       "failed": failed}, f, indent=2, default=str)
        print(f"# wrote {args.json}", file=sys.stderr)

    if args.check_schema:
        bad = check_schema(results)
        for b in bad:
            print(f"# SCHEMA: {b}", file=sys.stderr)
        if bad:
            print(f"# SCHEMA: {len(bad)} violation(s)", file=sys.stderr)
            return 1
        print("# SCHEMA: ok", file=sys.stderr)

    if failed:
        print(f"# FAILED sections: {','.join(failed)}", file=sys.stderr)
        if args.strict or on_chip:
            return 1
    return 0


if __name__ == "__main__":
    from repro.core import jitcache
    print(f"# compile cache: {jitcache.use_persistent_cache()}",
          file=sys.stderr)
    sys.exit(main())
