"""Benchmark harness: one module per paper table/figure.

``python -m benchmarks.run`` runs everything and prints labeled CSV blocks;
``--only fig9`` runs one. ``--report`` instead audits the persisted JSON
artifacts the benches are registered to produce — printing each record's
provenance line, and SKIPPING (with a reason, never a crash) artifacts
that are missing or carry a stale schema, so a perf-trajectory check
stays usable while the repo grows. Roofline-table regeneration from the
dry-run artifacts lives in ``python -m repro.launch.report`` (reads
results/dryrun.jsonl), not here — these are the paper-figure benches.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

BENCHES = ["fig3", "fig9", "fig10_table1", "fig11", "fig12", "kernels",
           "serving", "protocols", "db_updates", "autotune", "replicas",
           "chaos", "batch"]

#: bench -> (artifact file, keys every readable record must carry).
#: A registered bench without a row here produces no persisted artifact.
ARTIFACTS = {
    "kernels": ("BENCH_kernels.json", ("bench", "label", "cells")),
    "serving": ("BENCH_serving.json", ("bench", "label", "sweep")),
    "protocols": ("BENCH_protocols.json", ("bench", "label", "cells")),
    "db_updates": ("BENCH_db.json", ("bench", "label", "updates")),
    "autotune": ("BENCH_autotune.json", ("bench", "label", "cells")),
    "replicas": ("BENCH_replicas.json",
                 ("bench", "label", "schema", "sweep", "failover",
                  "acceptance")),
    "chaos": ("BENCH_chaos.json",
              ("bench", "label", "schema", "verify", "recovery",
               "acceptance")),
    "batch": ("BENCH_batch.json",
              ("bench", "label", "schema", "cells", "records_per_s",
               "acceptance")),
}


def report(names) -> int:
    """Audit registered artifacts: print a provenance line per record,
    SKIP (don't crash) anything missing, unreadable, or schema-stale —
    a half-regenerated checkout must not take the report down."""
    for name in names:
        if name not in ARTIFACTS:
            continue
        path, required = ARTIFACTS[name]
        try:
            with open(path) as f:
                rec = json.load(f)
        except FileNotFoundError:
            print(f"{name:12s} SKIP (missing {path} — run "
                  f"`python -m benchmarks.run --only {name}`)")
            continue
        except (json.JSONDecodeError, OSError) as e:
            print(f"{name:12s} SKIP (unreadable {path}: "
                  f"{type(e).__name__}: {e})")
            continue
        missing = [k for k in required if k not in rec]
        if missing:
            print(f"{name:12s} SKIP (stale schema in {path}: missing "
                  f"{missing} — regenerate)")
            continue
        # records/s column: benches that measure record throughput carry a
        # {cell: records_per_s} summary — report the best cell inline so
        # the perf trajectory is readable without opening the artifact
        rps = rec.get("records_per_s")
        if isinstance(rps, dict) and rps:
            top = max(rps, key=rps.get)
            rps_col = f"{rps[top]:8.1f} ({top})"
        else:
            rps_col = "       -"
        print(f"{name:12s} OK   {path} records/s={rps_col} "
              f"label={rec.get('label')} platform={rec.get('platform')}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=BENCHES)
    ap.add_argument("--report", action="store_true",
                    help="audit persisted JSON artifacts instead of "
                         "running benches (skip-and-report on missing/"
                         "stale files)")
    args = ap.parse_args(argv)
    names = [args.only] if args.only else BENCHES
    if args.report:
        return report(names)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    rc = 0
    for name in names:
        mod = __import__(f"benchmarks.bench_{name}", fromlist=["run"])
        t0 = time.time()
        try:
            csv = mod.run()
        except Exception as e:      # report and continue
            print(f"== bench_{name}: FAILED {type(e).__name__}: {e}",
                  file=sys.stderr)
            rc = 1
            continue
        print(f"== bench_{name} ({time.time() - t0:.1f}s) ==")
        print(csv.dump())
        print()
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
