"""Persistent JSON plan cache: measured plans survive the process.

Autotuning is measurement, and measurement costs wall clock — the point of
persisting winners is that a serving process never re-pays it. The cache
maps

    (backend, protocol, DatabaseSpec signature, bucket)  ->  ExecutionPlan

where the spec signature is ``"{n_items}x{item_bytes}"`` — exactly the
shape axes plan selection depends on. Lookup happens once per bucket at
``BucketedServeFns`` build time (never on the dispatch path); a hit
returns the tuned plan (provenance ``"tuned"``), a miss falls through to
the deterministic heuristic, so a machine without a cache file runs the
heuristic's plans.

Robustness contract (tested): a missing, corrupted, or stale-schema cache
file silently degrades to "no cache" — tuning artifacts must never be able
to take serving down. Writes are atomic (tmp + rename) so a crashed tuner
can't leave a torn file.

Location: ``REPRO_PLAN_CACHE`` env var; unset -> ``results/plan_cache.json``
relative to the working directory; the literal values ``off``/``none``/``0``
disable persistence entirely.
"""
from __future__ import annotations

import json
import os
import tempfile
from typing import Dict, Optional

SCHEMA_VERSION = 1
DEFAULT_PATH = os.path.join("results", "plan_cache.json")
CACHE_ENV = "REPRO_PLAN_CACHE"

#: ExecutionPlan fields a cache entry round-trips; provenance is stored
#: alongside (entry-level, default "tuned" for pre-provenance files)
#: "depth" (fused-pallas DMA buffers) joined in the megakernel PR; older
#: cache files simply lack the key and fall back to the plan default
_PLAN_FIELDS = ("expand", "scan", "chunk_log", "collective",
                "tile_r", "tile_q", "tile_l", "depth")


def cache_path() -> Optional[str]:
    """The configured cache file, or None when persistence is disabled."""
    raw = os.environ.get(CACHE_ENV)
    if raw is None:
        return DEFAULT_PATH
    raw = raw.strip()
    if raw.lower() in ("", "off", "none", "0"):
        return None
    return raw


def plan_key(backend: str, protocol: str, spec_sig: str, bucket: int) -> str:
    return f"{backend}|{protocol}|{spec_sig}|b{bucket}"


def spec_signature(cfg) -> str:
    """DatabaseSpec signature of a PIRConfig (the cache's shape axes).

    A checksum column widens every stored row by one word, changing the
    shapes plan selection tunes against — checksummed configs get their
    own cache rows (``"+c"`` marker) instead of poisoning the plain ones.
    """
    sig = f"{cfg.n_items}x{cfg.item_bytes}"
    if getattr(cfg, "checksum", False):
        sig += "+c"
    return sig


def plan_to_dict(plan) -> Dict:
    return {f: getattr(plan, f) for f in _PLAN_FIELDS}


def plan_from_dict(d: Dict, provenance: str = "tuned"):
    from repro.core.protocol import ExecutionPlan
    unknown = set(d) - set(_PLAN_FIELDS)
    if unknown:
        raise ValueError(f"unknown plan fields {sorted(unknown)}")
    fields = {f: d[f] for f in _PLAN_FIELDS if f in d}
    for f in ("expand", "scan"):
        if f not in fields or not isinstance(fields[f], str):
            raise ValueError(f"plan entry missing/invalid {f!r}")
    return ExecutionPlan(provenance=provenance, **fields)


class PlanCache:
    """In-memory mirror of the JSON plan store.

    ``path=None`` is a purely in-memory cache (persistence disabled);
    ``save()`` is then a no-op. One process-wide instance is held by
    ``repro.engine`` and consulted by ``resolve``; tests construct their
    own against tmp paths.
    """

    def __init__(self, path: Optional[str] = None, *, chaos=None):
        self.path = path
        self.plans: Dict[str, Dict] = {}
        self.load_error: Optional[str] = None
        #: optional ChaosInjector (repro.chaos) consulted at the
        #: plan_cache.load seam — proves the degrade-to-heuristic
        #: contract holds under injected load failures
        self.chaos = chaos
        if path is not None:
            self._load(path)

    # -- persistence ----------------------------------------------------

    def _load(self, path: str) -> None:
        if self.chaos is not None:
            from repro.chaos import InjectedFault
            try:
                hits = self.chaos.visit("plan_cache.load")  # raises on kill
                dropped = any(ev.action == "drop" for ev in hits)
            except InjectedFault as e:
                # same degrade path as a torn file: serving never dies
                # because a tuning artifact is unreadable
                self.load_error = f"{type(e).__name__}: {e}"
                return
            if dropped:
                self.load_error = "InjectedFault: chaos drop at plan_cache.load"
                return
        if not os.path.exists(path):
            return
        try:
            with open(path) as f:
                raw = json.load(f)
            if not isinstance(raw, dict) or raw.get("schema") \
                    != SCHEMA_VERSION:
                raise ValueError(
                    f"stale cache schema {raw.get('schema')!r} "
                    f"(want {SCHEMA_VERSION})")
            plans = raw.get("plans", {})
            if not isinstance(plans, dict):
                raise ValueError("malformed 'plans' table")
            # validate every entry now: a single bad row must not be able
            # to crash plan resolution later
            for key, entry in plans.items():
                plan_from_dict(entry["plan"])
            self.plans = plans
        except (OSError, ValueError, KeyError, TypeError,
                json.JSONDecodeError) as e:
            # degrade to heuristic-only; remember why for diagnostics
            self.load_error = f"{type(e).__name__}: {e}"
            self.plans = {}

    def save(self) -> Optional[str]:
        if self.path is None:
            return None
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        payload = {"schema": SCHEMA_VERSION, "plans": self.plans}
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(self.path) or ".",
                                   prefix=".plan_cache_")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(payload, f, indent=2, sort_keys=True)
                f.write("\n")
            os.replace(tmp, self.path)
        except BaseException:
            if os.path.exists(tmp):
                os.unlink(tmp)
            raise
        return os.path.abspath(self.path)

    # -- lookup / update ------------------------------------------------

    def get(self, backend: str, protocol: str, spec_sig: str, bucket: int):
        entry = self.plans.get(plan_key(backend, protocol, spec_sig,
                                        bucket))
        if entry is None:
            return None
        try:
            return plan_from_dict(entry["plan"],
                                  entry.get("provenance", "tuned"))
        except (ValueError, KeyError, TypeError):
            return None

    def put(self, backend: str, protocol: str, spec_sig: str, bucket: int,
            plan, meta: Optional[Dict] = None,
            provenance: str = "tuned") -> None:
        self.plans[plan_key(backend, protocol, spec_sig, bucket)] = {
            "plan": plan_to_dict(plan), "meta": meta or {},
            "provenance": provenance,
        }

    def warm_put(self, backend: str, protocol: str, spec_sig: str,
                 bucket: int, plan, meta: Optional[Dict] = None) -> bool:
        """Seed an entry only if the slot is empty (provenance ``"warm"``).

        The cross-replica warm-start path: a rejoining replica records the
        plans a healthy peer is serving with, so its first serve-fn build
        resolves to a measured plan instead of re-paying tuning (or worse,
        falling to the heuristic). A tuned entry always wins over a warm
        one — never overwrite. Returns whether an entry was written.
        """
        key = plan_key(backend, protocol, spec_sig, bucket)
        if key in self.plans:
            return False
        self.plans[key] = {"plan": plan_to_dict(plan), "meta": meta or {},
                           "provenance": "warm"}
        return True

    def __len__(self) -> int:
        return len(self.plans)
