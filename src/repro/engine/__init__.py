"""The engine plane: kernel registry + measured autotuner + plan cache.

This package owns plan selection end to end (DESIGN.md §9):

``engine/backend.py``   one backend probe for the whole stack
                        (``REPRO_FORCE_BACKEND`` override) + legal-tile
                        arithmetic (largest legal divisor ≤ requested).
``engine/kernels.py``   descriptors over the answer-kernel bodies with
                        declared tunable spaces and a VMEM-footprint
                        validity model (``analysis/roofline.py`` math) —
                        infeasible candidates are pruned without running.
``engine/tuner.py``     the measured autotuner: times feasible
                        ``ExecutionPlan`` candidates on the real
                        (db_view, bucket) shapes under a budget.
``engine/cache.py``     persistent JSON plan cache keyed by
                        (backend, protocol, spec signature, bucket).

:func:`resolve` is the one owner of the kernel choice: cache hit → the
tuned (or warm-recorded) plan; miss → the deterministic heuristic
(``heuristic_plan``). ``BucketedServeFns.plan_for_bucket`` calls it once
per bucket at build time — never on the dispatch path.
"""
from __future__ import annotations

from typing import Optional

# the probe is re-exported under a DIFFERENT name on purpose: a package
# global named ``backend`` would shadow the ``repro.engine.backend``
# submodule attribute on this package (module globals ARE package attrs),
# making ``import repro.engine.backend as m`` bind the function instead of
# the module. tests/test_engine.py pins the regression.
from repro.engine.backend import backend as probe_backend
from repro.engine.backend import (FORCE_BACKEND_ENV, default_interpret,
                                  legal_tile, on_tpu)
from repro.engine.cache import (PlanCache, cache_path, plan_key,
                                spec_signature)
from repro.engine.kernels import (KERNELS, KernelDescriptor, ProblemShape,
                                  get_kernel, predicted_step_bytes,
                                  serve_kernels)
from repro.engine.tuner import (SMOKE_BUDGET, TuneBudget, TuneResult,
                                autotune, candidate_plans, heuristic_plan,
                                plan_label, problem_shape, tune,
                                tune_standalone)

__all__ = [
    "FORCE_BACKEND_ENV", "probe_backend", "default_interpret", "legal_tile",
    "on_tpu", "PlanCache", "cache_path", "plan_key", "spec_signature",
    "KERNELS", "KernelDescriptor", "ProblemShape", "get_kernel",
    "predicted_step_bytes", "serve_kernels", "SMOKE_BUDGET", "TuneBudget",
    "TuneResult", "autotune", "candidate_plans", "heuristic_plan",
    "plan_label", "problem_shape", "tune", "tune_standalone",
    "plan_cache", "resolve", "plan_report", "record_plans",
]

_PLAN_CACHE: Optional[PlanCache] = None


def plan_cache(reload: bool = False) -> PlanCache:
    """The process-wide plan cache (``REPRO_PLAN_CACHE`` location).

    Loaded lazily once; ``reload=True`` re-reads the file (tests, or after
    an external tuner wrote new entries).
    """
    global _PLAN_CACHE
    if _PLAN_CACHE is None or reload:
        _PLAN_CACHE = PlanCache(cache_path())
    return _PLAN_CACHE


def resolve(cfg, n_queries: int, *, backend_name: Optional[str] = None):
    """The plan for (cfg, bucket): the cached plan on a hit, as it was
    recorded (tiling, chunk size and collective); ``heuristic_plan`` on a
    miss (chunk_log 12, the gather collective)."""
    be = backend_name or probe_backend()
    hit = plan_cache().get(be, cfg.protocol, spec_signature(cfg), n_queries)
    if hit is not None:
        return hit
    return heuristic_plan(cfg, n_queries, backend=be)


def record_plans(cfg, plans: dict, *, backend_name: Optional[str] = None,
                 persist: bool = False) -> int:
    """Seed the process-wide cache with ``{bucket: plan}`` warm entries.

    The replica plane's cross-replica warm start: a healthy replica
    exports its per-bucket plans (``BucketedServeFns.plans``), a rejoining
    one records them here before building serve fns, so its first query is
    served from a measured plan — no re-tuning, no heuristic fallback.
    Warm entries never displace tuned ones (``PlanCache.warm_put``).
    Returns the number of entries written; ``persist=True`` also saves the
    cache file so the warm start survives the process.
    """
    be = backend_name or probe_backend()
    cache = plan_cache()
    sig = spec_signature(cfg)
    written = sum(
        cache.warm_put(be, cfg.protocol, sig, bucket, plan)
        for bucket, plan in plans.items())
    if persist and written:
        cache.save()
    return written


def plan_report(cfg, plan, bucket: int, *, n_shards: int = 1,
                measured_wall_s: Optional[float] = None) -> dict:
    """Reporting row for one bucket's chosen plan: provenance, the modeled
    HBM bytes its answer step moves, and the bandwidth roof of this
    process's device (``analysis.roofline.PEAKS``, keyed by device kind)
    those bytes are judged against (``BucketedServeFns.plan_report``).

    Pass ``measured_wall_s`` (e.g. a tuner timing) to additionally report
    ``achieved_frac`` — the fraction of peak bandwidth the measured run
    achieved over the modeled bytes (``analysis.roofline``).
    """
    from repro.analysis.roofline import achieved_fraction, peak_bytes_per_s
    from repro.core import protocol as protocol_mod
    proto = protocol_mod.get(cfg.protocol)
    shape = problem_shape(cfg, bucket, n_shards=n_shards)
    step_bytes = predicted_step_bytes(plan, proto.share_kind, shape)
    out = {
        "plan": plan.name,
        "label": plan_label(plan),
        "provenance": plan.provenance,
        "predicted_step_bytes": step_bytes,
        "peak_bytes_per_s": peak_bytes_per_s(),
    }
    if measured_wall_s is not None:
        out["measured_wall_s"] = measured_wall_s
        out["achieved_frac"] = achieved_fraction(step_bytes,
                                                 measured_wall_s)
    return out
