"""Measured autotuner: enumerate feasible plans, time them, keep the winner.

IM-PIR's thesis is that PIR answering is memory-bandwidth-bound — which
makes kernel path + tiling *the* throughput story. The pre-engine stack
chose both by folklore: a hand-written heuristic plus tile
constants hardcoded in ``kernels/ops.py``, never validated against
measurement. The tuner closes that loop:

  1. enumerate candidate ``ExecutionPlan``s from the kernel registry
     (``engine/kernels.py``) — tile/chunk spaces already legalized for the
     concrete shapes and pruned by the VMEM-footprint model,
  2. **time each candidate on the real (db_view, bucket) shapes** — the
     protocol's own ``answer_local`` under ``jax.jit``, exactly the
     contraction one shard executes inside the compiled serve step (the
     cross-shard collective is topology- not tile-bound and is not tuned),
  3. keep the fastest; persist it via the plan cache (``engine/cache.py``)
     keyed by (backend, protocol, spec signature, bucket).

The **heuristic is always candidate #0** and is always measured, so a tune
can only ever match or beat it — and a cache miss falls back to it
bit-for-bit (``heuristic_plan``).

Budgets: measurement costs wall clock (and, on this CPU container, XLA
compiles of interpret-mode Pallas bodies), so every entry point takes a
:class:`TuneBudget`. The CI smoke (``python -m repro.engine.tuner
--smoke``) runs with ≤2 candidates per kernel and single-iteration timing.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, replace
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np

from repro.analysis import roofline
from repro.engine.backend import backend as probe_backend
from repro.engine.cache import spec_signature
from repro.engine.kernels import (ProblemShape, GEMM_TILE_R_DEFAULT,
                                  get_kernel, plans_from_kernel,
                                  predicted_step_bytes, serve_kernels)


# ---------------------------------------------------------------------------
# The deterministic fallback
# ---------------------------------------------------------------------------

def heuristic_plan(cfg, n_queries: int, *, backend: Optional[str] = None,
                   chunk_log: int = 12):
    """Pick the kernel path per (db size, batch bucket, backend).

    The deterministic fallback a plan-cache miss resolves to (DESIGN.md
    §7.3, asserted by tests/test_engine.py against an inline replica):

      * the selection vector is materialized only while the DB fits one
        chunk (``n_items <= 2^chunk_log``): the full-domain evaluation
        keeps ``[Q, rows, 4]`` u32 seeds, lane-padded 32x on a TPU, so at
        2^25 rows one party's single-query step needs 9.5 GiB of temp and
        a four-query additive step 32 GiB;
      * past that, on a TPU, XOR and additive protocols alike take the
        ``fused-pallas`` megakernel of their share algebra at the largest
        tile (<= 2048 rows) whose VMEM footprint fits the queries the
        kernel scans (``xor-dpf-k`` scans one pseudo-query per key
        component); its chunk roots reach it lane-dense, so the step
        needs a few MB of HBM beside its arguments, as the fused path;
      * where no tile fits, and on the CPU (where the Pallas bodies would
        run in interpret mode), XOR protocols take the fused chunked
        expand+scan (its inner fold is always the jnp ``dpxor``, so its
        ``scan`` is "jnp" on every backend) and additive protocols the
        materialized GEMM;
      * LWE contracts with XLA's int32 dot on every backend: the v5e MXU
        has no int32 matmul, so Mosaic refuses the Pallas int32 body.

    The backend probe is ``engine.probe_backend()`` (one probe for the
    whole stack, ``REPRO_FORCE_BACKEND``-overridable).
    """
    from repro.core import protocol as protocol_mod
    if backend is None:
        backend = probe_backend()
    scan = "pallas" if backend == "tpu" else "jnp"
    proto = protocol_mod.get(cfg.protocol)
    small_db = cfg.n_items <= (1 << chunk_log)
    if proto.share_kind == "lwe":
        return protocol_mod.ExecutionPlan(
            expand="materialize", scan="jnp", chunk_log=chunk_log,
            tile_r=GEMM_TILE_R_DEFAULT)
    if backend == "tpu" and not small_db:
        plan = _fitting_fused_pallas(cfg, n_queries, chunk_log,
                                     proto.share_kind)
        if plan is not None:
            return plan
    if proto.share_kind == "additive":
        return protocol_mod.ExecutionPlan(
            expand="materialize", scan=scan, chunk_log=chunk_log,
            tile_r=GEMM_TILE_R_DEFAULT)
    if small_db:
        return protocol_mod.ExecutionPlan(expand="materialize", scan=scan,
                                          chunk_log=chunk_log)
    return protocol_mod.ExecutionPlan(expand="fused", scan="jnp",
                                      chunk_log=chunk_log)


def _fitting_fused_pallas(cfg, n_queries: int, chunk_log: int,
                          share_kind: str):
    """The share algebra's megakernel plan at the largest tile <= 2048
    rows whose VMEM footprint fits, or None when even a 128-row tile does
    not. The footprint is judged at the queries the kernel scans: a key
    with a component axis (``[Q, C, ...]`` leaves, ``xor-dpf-k``) runs
    Q*C pseudo-queries."""
    from repro.core import protocol as protocol_mod
    desc, = [d for d in serve_kernels(share_kind, "tpu")
             if d.expand == "fused-pallas"]
    root = protocol_mod.get(cfg.protocol).key_specs(cfg, n_queries).root_seed
    shape = problem_shape(cfg, int(np.prod(root.shape[:-1])))
    tile = 2048
    while tile >= 128:
        params = desc.legalize_fn(shape, {"tile_r": tile,
                                          "chunk_log": chunk_log,
                                          "depth": 2})
        if desc.feasible(shape, params):
            return protocol_mod.ExecutionPlan(
                expand="fused-pallas", scan="pallas", **params)
        tile //= 2
    return None


# ---------------------------------------------------------------------------
# Candidate enumeration
# ---------------------------------------------------------------------------

def candidate_plans(cfg, bucket: int, *, n_shards: int = 1,
                    chunk_log: int = 12, collective: str = "gather",
                    max_per_kernel: Optional[int] = None,
                    backend: Optional[str] = None) -> List:
    """Feasible ExecutionPlans for (cfg, bucket): the tuner's search space.

    One entry per surviving point of each registered serve kernel's
    parameter space; infeasible tilings (VMEM-footprint model) are pruned
    here, without ever being run, and so are bodies the TPU compiler
    refuses when ``backend`` (default: the probe) is a TPU. ``n_shards``
    scales the per-shard row count the tiles must be legal for.
    """
    from repro.core import protocol as protocol_mod
    proto = protocol_mod.get(cfg.protocol)
    shape = problem_shape(cfg, bucket, n_shards=n_shards)
    base = protocol_mod.ExecutionPlan(chunk_log=min(chunk_log,
                                                    shape.log_rows),
                                      collective=collective)
    plans: List = []
    for desc in serve_kernels(proto.share_kind,
                              backend or probe_backend()):
        for plan in plans_from_kernel(desc, shape, base_plan=base,
                                      max_candidates=max_per_kernel):
            if plan not in plans:
                plans.append(plan)
    return plans


def problem_shape(cfg, bucket: int, *, n_shards: int = 1) -> ProblemShape:
    from repro.db import DatabaseSpec
    rows = DatabaseSpec.from_config(cfg).rows_per_shard(n_shards)
    return ProblemShape(bucket=bucket, rows=rows,
                        item_bytes=cfg.item_bytes)


def plan_label(plan) -> str:
    """Stable human-readable key for timing tables / JSON records.

    Only execution-relevant, non-default fields appear: fused plans carry
    their chunk size, Pallas plans their row/reduction tile, and the GEMM
    tiles (tile_q/tile_l) only when legalization moved them off their
    defaults — XOR-scan plans never set them, so their labels stay clean.
    """
    lbl = f"{plan.expand}/{plan.scan}"
    if plan.expand == "fused":
        lbl += f"/cl{plan.chunk_log}"
    elif plan.expand == "fused-pallas":
        lbl += f"/cl{plan.chunk_log}/tr{plan.tile_r}/d{plan.depth}"
    elif plan.scan == "pallas":
        lbl += f"/tr{plan.tile_r}"
        defaults = _plan_defaults()
        if plan.tile_q != defaults.tile_q:
            lbl += f"/tq{plan.tile_q}"
        if plan.tile_l != defaults.tile_l:
            lbl += f"/tl{plan.tile_l}"
    return lbl


_DEFAULT_PLAN = None


def _plan_defaults():
    global _DEFAULT_PLAN
    if _DEFAULT_PLAN is None:
        from repro.core.protocol import ExecutionPlan
        _DEFAULT_PLAN = ExecutionPlan()
    return _DEFAULT_PLAN


# ---------------------------------------------------------------------------
# Measurement
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class TuneBudget:
    """How much wall clock / search breadth a tune may spend."""
    max_candidates: Optional[int] = 8      # per kernel, post-pruning
    warmup: int = 1                        # compile + cache warm
    iters: int = 3                         # timed reps (median kept)
    max_seconds: float = 120.0             # soft cap, checked between plans
    #: skip candidates whose predicted-bytes model alone — divided by the
    #: backend's peak bandwidth — already exceeds the best measured wall so
    #: far. Bandwidth is a *lower* bound on wall, so a pruned candidate
    #: could not have won even at 100% of peak; the saving is its compile.
    prune_bytes: bool = True


#: the CI smoke budget: ≤2 candidates per kernel, single timed rep
SMOKE_BUDGET = TuneBudget(max_candidates=2, warmup=1, iters=1,
                          max_seconds=90.0)


@dataclass
class TuneResult:
    plan: object                   # the winner, provenance="tuned"
    heuristic: object              # the deterministic fallback (measured)
    timings: Dict[str, float]      # plan_label -> median seconds
    n_candidates: int              # search-space size after pruning
    n_timed: int                   # how many the budget let us measure
    n_pruned: int = 0              # skipped on the bytes bound, no compile

    @property
    def heuristic_s(self) -> float:
        return self.timings[plan_label(self.heuristic)]

    @property
    def tuned_s(self) -> float:
        return self.timings[plan_label(self.plan)]

    @property
    def speedup(self) -> float:
        return self.heuristic_s / self.tuned_s if self.tuned_s else 0.0


def _measurement_inputs(cfg, bucket: int, proto, seed: int):
    """Real-shape inputs for timing: the protocol's declared db view and a
    party-0 batched key pytree of ``bucket`` random queries."""
    from repro.core import pir
    from repro.db import DatabaseSpec
    rng = np.random.default_rng(seed)
    spec = DatabaseSpec.from_config(cfg)
    db_words = pir.make_database(rng, cfg.n_items, cfg.item_bytes)
    db = jax.numpy.asarray(spec.pack_host(db_words, proto.db_view))
    idx = rng.integers(0, cfg.n_items, size=bucket).tolist()
    keys = pir.batch_queries(rng, idx, cfg)[0]
    return db, keys


def time_plan(proto, plan, db, keys, log_local: int,
              budget: TuneBudget) -> float:
    """Median wall time of one plan's jitted shard contraction."""
    fn = jax.jit(lambda d, k: proto.answer_local(d, k, 0, log_local, plan))
    for _ in range(max(budget.warmup, 1)):      # compile off the clock
        jax.block_until_ready(fn(db, keys))
    ts = []
    for _ in range(max(budget.iters, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(db, keys))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def tune(cfg, bucket: int, *, backend: Optional[str] = None,
         budget: Optional[TuneBudget] = None, chunk_log: int = 12,
         collective: str = "gather", cache=None, seed: int = 0
         ) -> TuneResult:
    """Measure the candidate plans for one (cfg, bucket) and pick a winner.

    The heuristic plan is measured first and unconditionally, so the tuned
    result can never be slower than the fallback *on the measured shapes*.
    Pass ``cache`` (a :class:`~repro.engine.cache.PlanCache`) to record the
    winner; the caller owns ``cache.save()``.
    """
    from repro.core import protocol as protocol_mod
    budget = budget or TuneBudget()
    be = backend or probe_backend()
    proto = protocol_mod.get(cfg.protocol)
    heur = heuristic_plan(cfg, bucket, backend=be, chunk_log=chunk_log)
    heur = replace(heur, collective=collective)
    cands = candidate_plans(cfg, bucket, chunk_log=chunk_log,
                            collective=collective,
                            max_per_kernel=budget.max_candidates,
                            backend=be)
    ordered = [heur] + [p for p in cands if p != heur]

    db, keys = _measurement_inputs(cfg, bucket, proto, seed)
    log_local = cfg.log_n
    shape = problem_shape(cfg, bucket)
    peak = roofline.peak_bytes_per_s()        # this device's own kind
    t_start = time.perf_counter()
    timings: Dict[str, float] = {}
    n_pruned = 0
    for i, plan in enumerate(ordered):
        if i > 0 and time.perf_counter() - t_start > budget.max_seconds:
            break                    # budget spent; heuristic was first
        label = plan_label(plan)
        if label in timings:
            continue
        if i > 0 and budget.prune_bytes and timings:
            # bandwidth-bound lower bound: if the plan's modeled HBM
            # traffic can't beat the best measured wall even at 100% of
            # peak, never pay its compile (heuristic is never pruned)
            floor_s = predicted_step_bytes(plan, proto.share_kind,
                                           shape) / peak
            if floor_s > min(timings.values()):
                n_pruned += 1
                continue
        timings[label] = time_plan(proto, plan, db, keys, log_local, budget)

    best_label = min(timings, key=timings.get)
    winner = next(p for p in ordered if plan_label(p) == best_label)
    tuned = replace(winner, provenance="tuned")
    if cache is not None:
        cache.put(be, proto.name, spec_signature(cfg), bucket,
                  tuned, meta={
                      "tuned_s": timings[best_label],
                      "heuristic_s": timings[plan_label(heur)],
                      "n_candidates": len(ordered),
                      "n_timed": len(timings),
                      "n_pruned": n_pruned,
                  })
    return TuneResult(plan=tuned, heuristic=heur, timings=timings,
                      n_candidates=len(ordered), n_timed=len(timings),
                      n_pruned=n_pruned)


def autotune(cfg, buckets: Sequence[int], *,
             backend: Optional[str] = None,
             budget: Optional[TuneBudget] = None,
             cache=None, persist: bool = True,
             seed: int = 0) -> Dict[int, TuneResult]:
    """Tune every bucket of a config and (optionally) persist the winners.

    ``cache=None`` uses the process-wide plan cache (``repro.engine.
    plan_cache()``), so servers built afterwards in the same process pick
    the tuned plans up immediately; ``persist`` additionally writes the
    JSON store for future processes.
    """
    from repro import engine
    cache = cache if cache is not None else engine.plan_cache()
    out = {}
    for b in sorted(set(buckets)):
        out[b] = tune(cfg, b, backend=backend, budget=budget, cache=cache,
                      seed=seed)
    if persist:
        cache.save()
    return out


def tune_standalone(kernel_name: str, n: int, *,
                    budget: Optional[TuneBudget] = None,
                    rounds: int = 12, seed: int = 0) -> Dict:
    """Tune a non-serve kernel (currently ``ggm-expand``) standalone.

    Measures ``ops.ggm_expand`` over its pruned tile space at ``n`` leaf
    nodes; returns {"params", "timings"}. GGM expansion is not part of an
    ``ExecutionPlan`` (DPF eval happens inside ``answer_local``), so its
    tuning result is reported rather than cached.
    """
    from repro.kernels import ops
    budget = budget or TuneBudget()
    desc = get_kernel(kernel_name)
    if desc.serve:
        raise ValueError(f"{kernel_name} is a serve kernel; use tune()")
    shape = ProblemShape(bucket=1, rows=n, item_bytes=4)
    rng = np.random.default_rng(seed)
    seeds = jax.numpy.asarray(
        rng.integers(0, 1 << 32, size=(n, 4), dtype=np.uint32))
    t_bits = jax.numpy.asarray(
        rng.integers(0, 2, size=(n,), dtype=np.uint32))
    cw_s = jax.numpy.asarray(
        rng.integers(0, 1 << 32, size=(4,), dtype=np.uint32))
    cw_t = jax.numpy.asarray(
        rng.integers(0, 2, size=(2,), dtype=np.uint32))
    timings: Dict[str, float] = {}
    for params in desc.candidates(shape, budget.max_candidates):
        fn = lambda: ops.ggm_expand(seeds, t_bits, cw_s, cw_t,
                                    rounds=rounds, tile=params["tile"])
        for _ in range(max(budget.warmup, 1)):
            jax.block_until_ready(fn())
        ts = []
        for _ in range(max(budget.iters, 1)):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            ts.append(time.perf_counter() - t0)
        timings[f"tile{params['tile']}"] = float(np.median(ts))
    best = min(timings, key=timings.get)
    return {"params": {"tile": int(best[4:])}, "timings": timings}


# ---------------------------------------------------------------------------
# CI smoke: tiny-budget tune + heuristic-fallback equivalence gate
# ---------------------------------------------------------------------------

#: the heuristic's choices on the smoke grid, as literals —
#: (protocol, log_n, n_queries, backend) -> (expand, scan). Hardcoded
#: rather than computed so the gate is independent of ``heuristic_plan``
#: (a rule change there cannot silently rewrite its own oracle).
_EXPECTED_PLANS = {
    ("xor-dpf-2", 10, 1, "cpu"): ("materialize", "jnp"),
    ("xor-dpf-2", 10, 4, "cpu"): ("materialize", "jnp"),
    ("xor-dpf-2", 10, 4, "tpu"): ("materialize", "pallas"),
    ("additive-dpf-2", 10, 1, "cpu"): ("materialize", "jnp"),
    ("additive-dpf-2", 10, 4, "cpu"): ("materialize", "jnp"),
    ("additive-dpf-2", 10, 4, "tpu"): ("materialize", "pallas"),
    ("xor-dpf-2", 14, 1, "cpu"): ("fused", "jnp"),         # single query
    ("xor-dpf-2", 14, 4, "cpu"): ("fused", "jnp"),         # big-db regime
    ("xor-dpf-2", 14, 4, "tpu"): ("fused-pallas", "pallas"),
    ("additive-dpf-2", 14, 4, "cpu"): ("materialize", "jnp"),
    ("additive-dpf-2", 14, 4, "tpu"): ("fused-pallas", "pallas"),
    ("lwe-simple-1", 14, 4, "tpu"): ("materialize", "jnp"),
}


def smoke() -> int:
    """Tiny-budget autotune smoke for scripts/ci_check.sh.

    Interpret mode (CPU), ≤2 candidates per kernel, one bucket per
    protocol — and, for every cell of a small grid, asserts the
    heuristic-fallback plan (what an empty cache resolves to) equals the
    choice pinned above as literals. Nothing is persisted.
    (tests/test_engine.py holds the broader independent replica of the
    rules; this is the fast CI spot check.)
    """
    from repro.config import PIRConfig
    from repro.engine.cache import PlanCache

    for (proto, log_n, n_q, be), want in _EXPECTED_PLANS.items():
        cfg = PIRConfig(n_items=1 << log_n, item_bytes=32, protocol=proto,
                        n_servers=1 if proto == "lwe-simple-1" else 2)
        got = heuristic_plan(cfg, n_q, backend=be)
        assert (got.expand, got.scan) == want, (
            f"heuristic drifted from its pinned choice: "
            f"{proto} 2^{log_n} n_q={n_q} {be}: "
            f"{(got.expand, got.scan)} != {want}")
        assert got.provenance == "heuristic"
        if got.expand == "fused-pallas":      # legalized: whole chunks/tile
            assert (1 << got.chunk_log) <= got.tile_r
        else:
            assert got.chunk_log == 12
        if got.expand == "materialize" and proto != "xor-dpf-2":
            assert got.tile_r == GEMM_TILE_R_DEFAULT
    print("[smoke] heuristic fallback == pinned plan choices "
          f"on {len(_EXPECTED_PLANS)} grid cells")
    grid = [
        PIRConfig(n_items=1 << 10, item_bytes=32),
        PIRConfig(n_items=1 << 10, item_bytes=32,
                  protocol="additive-dpf-2"),
    ]

    cache = PlanCache(path=None)             # in-memory only
    for cfg in grid:                         # one tune per share kind
        res = tune(cfg, 2, budget=SMOKE_BUDGET, cache=cache)
        assert res.tuned_s <= res.heuristic_s + 1e-9
        print(f"[smoke] {cfg.protocol}: tuned {plan_label(res.plan)} "
              f"{res.tuned_s * 1e3:.1f} ms vs heuristic "
              f"{res.heuristic_s * 1e3:.1f} ms "
              f"({res.n_timed}/{res.n_candidates} candidates timed)")
        hit = cache.get(probe_backend(), cfg.protocol,
                        spec_signature(cfg), 2)
        assert hit == res.plan and hit.provenance == "tuned"
    print("[smoke] plan cache round-trip ok")

    # megakernel gate: one fused-scan-pallas candidate at the tiniest
    # shape (2^8 rows: the legalized space collapses to a single point,
    # one interpret-mode compile) — byte parity vs the materialized
    # heuristic oracle + descriptor provenance
    from repro.core import protocol as protocol_mod
    from repro.engine.kernels import descriptor_for_plan
    cfg = PIRConfig(n_items=1 << 8, item_bytes=32)
    proto = protocol_mod.get(cfg.protocol)
    fused = [p for p in candidate_plans(cfg, 2)
             if p.expand == "fused-pallas"]
    assert fused, "no legal fused-pallas candidate at 2^8"
    plan = fused[0]
    assert descriptor_for_plan(plan, proto.share_kind).name == \
        "xor-fused-pallas"
    db, keys = _measurement_inputs(cfg, 2, proto, seed=7)
    oracle = heuristic_plan(cfg, 2, backend=probe_backend())
    want = proto.answer_local(db, keys, 0, cfg.log_n, oracle)
    got = proto.answer_local(db, keys, 0, cfg.log_n, plan)
    assert (np.asarray(got) == np.asarray(want)).all(), \
        "fused-pallas answer diverges from the materialized oracle"
    print(f"[smoke] fused-pallas megakernel parity ok "
          f"({plan_label(plan)})")
    return 0


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-budget CI smoke (see scripts/ci_check.sh)")
    args = ap.parse_args(argv)
    if args.smoke:
        return smoke()
    ap.print_help()
    return 2


if __name__ == "__main__":
    raise SystemExit(main())
