"""Kernel-path shootout: materialize vs fused-jnp vs fused-scan-pallas.

The §Perf companion to the megakernel (``kernels/fused_scan.py``,
DESIGN.md §13): for each (protocol, bucket) cell, time the three answer
paths on the real (db_view, bucket) shapes — the same jitted
``answer_local`` the tuner measures — and report, per path,

  * QPS (bucket / median wall),
  * the modeled HBM bytes of one answer step
    (``engine.predicted_step_bytes`` — the megakernel's headline is that
    its DB term is per *batch*, not per query), and
  * the achieved-vs-peak bandwidth fraction
    (``analysis/roofline.py achieved_fraction``) — the roofline
    verification number. On this container the roof is the nominal CPU
    figure and rows are labeled measured-cpu; on a TPU the same bench
    judges against the v5e HBM roof.

The tuned row re-reports the measured tuner's pick for the cell
(heuristic always candidate #0, so tuned QPS >= heuristic QPS by
construction). Alongside, the original per-kernel microbenches (dpxor /
ggm_expand / pir_matmul) are kept as layout-true bytes-per-call rows.

Run: PYTHONPATH=src python -m benchmarks.run --only kernels
"""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from benchmarks.common import Csv, record_json, timeit
from repro import engine
from repro.analysis import roofline
from repro.config import PIRConfig
from repro.core import protocol as protocol_mod
from repro.engine.tuner import (TuneBudget, candidate_plans,
                                heuristic_plan, plan_label)
from repro.kernels import ops

LOG_N = 12                      # 4096 records x 32 B (CPU-container scale)
BUCKET = 8
ITEM_BYTES = 32
OUT_JSON = "BENCH_kernels.json"

#: per-cell tuning budget: 4 candidates per kernel family reaches the
#: large-tile fused-pallas points (the measured winners on this
#: container) while keeping the interpret-mode compile bill ~2-3 min per
#: cell; the tuner's winner is persisted to the plan cache with
#: provenance="tuned".
BUDGET = TuneBudget(max_candidates=4, warmup=1, iters=3,
                    max_seconds=300.0)

CELLS = [
    ("xor-dpf-2", PIRConfig(n_items=1 << LOG_N, item_bytes=ITEM_BYTES)),
    ("additive-dpf-2", PIRConfig(n_items=1 << LOG_N, item_bytes=ITEM_BYTES,
                                 protocol="additive-dpf-2")),
]

#: reporting buckets: label -> plan.expand values folded into it
PATH_OF_EXPAND = {"materialize": "materialize", "fused": "fused-jnp",
                  "fused-pallas": "fused-pallas"}


def _plans_by_label(cfg, bucket):
    """label -> plan for every plan the tuner might have timed."""
    out = {}
    for p in [heuristic_plan(cfg, bucket)] + candidate_plans(cfg, bucket):
        out.setdefault(plan_label(p), p)
    return out


def run() -> Csv:
    be = engine.probe_backend()
    peak = roofline.peak_bytes_per_s()
    label = "measured-cpu" if be == "cpu" else f"measured-{be}"
    csv = Csv(["cell", "path", "plan", "qps", "modeled_mb",
               "achieved_frac_pct", "label"])
    cache = engine.plan_cache()
    cells = {}
    for name, cfg in CELLS:
        proto = protocol_mod.get(cfg.protocol)
        shape = engine.problem_shape(cfg, BUCKET)
        res = engine.tune(cfg, BUCKET, budget=BUDGET, cache=cache)
        by_label = _plans_by_label(cfg, BUCKET)
        # fold measured labels into the three comparable paths, keeping
        # each path's best (min-wall) representative
        paths = {}
        for lbl, wall in res.timings.items():
            plan = by_label.get(lbl)
            if plan is None:
                continue
            path = PATH_OF_EXPAND.get(plan.expand, plan.expand)
            if path in paths and paths[path]["wall_s"] <= wall:
                continue
            step_bytes = engine.predicted_step_bytes(
                plan, proto.share_kind, shape)
            paths[path] = {
                "plan": lbl, "wall_s": wall, "qps": BUCKET / wall,
                "modeled_bytes": step_bytes,
                "achieved_frac": roofline.achieved_fraction(
                    step_bytes, wall),
            }
        for path, row in sorted(paths.items()):
            csv.add(f"{name}/b{BUCKET}", path, row["plan"], row["qps"],
                    row["modeled_bytes"] / (1 << 20),
                    100.0 * row["achieved_frac"], label)
        tuned_path = PATH_OF_EXPAND.get(res.plan.expand, res.plan.expand)
        cells[f"{name}/b{BUCKET}"] = {
            "protocol": cfg.protocol, "bucket": BUCKET,
            "paths": paths,
            "tuned_path": tuned_path,
            "tuned_plan": plan_label(res.plan),
            "tuned_qps": BUCKET / res.tuned_s,
            "heuristic_plan": plan_label(res.heuristic),
            "heuristic_qps": BUCKET / res.heuristic_s,
            "n_candidates": res.n_candidates, "n_timed": res.n_timed,
            "n_pruned": res.n_pruned,
        }
    cache.save()

    record_json(OUT_JSON, {
        "bench": "kernels",
        "log_n": LOG_N, "item_bytes": ITEM_BYTES, "bucket": BUCKET,
        "backend": be, "peak_bytes_per_s": peak,
        "cells": cells,
        "micro": _micro_rows(csv),
    })
    return csv


def _micro_rows(csv: Csv) -> dict:
    """The original per-kernel microbenches (layout-true bytes/call)."""
    rng = np.random.default_rng(0)
    micro = {}

    q, r, w = 8, 1 << 14, 8
    db_t = jnp.asarray(rng.integers(0, 1 << 32, size=(w, r),
                                    dtype=np.uint32))
    bits = jnp.asarray(rng.integers(0, 2, size=(q, r), dtype=np.uint32))
    t = timeit(lambda: ops.dpxor_transposed(db_t, bits, tile_r=4096))
    micro["dpxor"] = {"shape": f"q{q}_r{r}_w{w}", "us_per_call": t * 1e6,
                      "mb_touched": (db_t.size + bits.size) * 4 / (1 << 20)}

    n = 1 << 12
    seeds = jnp.asarray(rng.integers(0, 1 << 32, size=(n, 4),
                                     dtype=np.uint32))
    tb = jnp.asarray(rng.integers(0, 2, size=(n,), dtype=np.uint32))
    cw_s = jnp.asarray(rng.integers(0, 1 << 32, size=(4,),
                                    dtype=np.uint32))
    cw_t = jnp.asarray(rng.integers(0, 2, size=(2,), dtype=np.uint32))
    t = timeit(lambda: ops.ggm_expand(seeds, tb, cw_s, cw_t))
    micro["ggm_expand"] = {"shape": f"n{n}", "us_per_call": t * 1e6,
                           "mb_touched": seeds.size * 4 * 3 / (1 << 20)}

    q2, r2, l2 = 8, 1 << 12, 128
    s = jnp.asarray(rng.integers(-128, 128, size=(q2, r2), dtype=np.int8))
    d = jnp.asarray(rng.integers(-128, 128, size=(r2, l2), dtype=np.int8))
    t = timeit(lambda: ops.pir_gemm(s, d))
    micro["pir_matmul"] = {"shape": f"q{q2}_r{r2}_l{l2}",
                           "us_per_call": t * 1e6,
                           "mb_touched": (s.size + d.size) / (1 << 20)}

    for k, v in micro.items():
        csv.add(f"micro/{k}", "-", v["shape"], 0.0,
                v["mb_touched"], 0.0, "micro")
    return micro


if __name__ == "__main__":
    print(run().dump())
