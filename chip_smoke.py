#!/usr/bin/env python3
"""Bring-up smoke test: two-server PIR at the paper's 1 GB point on a TPU.

Drives the serving path through its normal entry points — client keygen,
``MultiServerPIR`` -> ``QueryScheduler`` -> per-party ``PIRServer``
bucketed steps -> one shared ``ShardedDatabase`` -> reconstruction — and
compares every returned record with the database row, byte for byte.

One chip (the default), ``pir-1g``: 2^25 records x 32 B = 1 GiB resident,
``xor-dpf-2`` with both parties sharing the placed database.

  A  the engine's plan per bucket (``path=None``, buckets 1 and 4): one
     single-index query, one 4-index query, then a started session of
     ``submit`` calls;
  B  the same placed database under the ``fused-pallas`` megakernel
     (xor body), bucket 4;
  C  ``additive-dpf-2`` at ``pir-1g-add``, the engine's plan, bucket 4 —
     the megakernel's additive body.

``--chips 4`` runs only the sharded phase: ``pir-8g`` (2^28 x 32 B, 2 GiB
per chip) on a 1 x 4 mesh, whose answers need the cross-shard XOR reduce.

Per phase, lines before the last report the resolved plan per bucket, the
backend compile seconds and count, the device's peak bytes in use, and the
seconds of one warm query (a smoke timing, not a metric). The last line is
one JSON object naming the device. The script exits non-zero, without that
line, unless JAX's first device is a TPU and ``REPRO_FORCE_BACKEND`` is
unset, and when any phase fails. The database is random, made from
``--seed``; the plan cache is off, so what runs depends only on the
checkout. Compiled programs are cached where ``JAX_COMPILATION_CACHE_DIR``
says, else in ``.jax_cache/`` at the checkout's root.

    python chip_smoke.py [--seed 0] [--chips 4]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

N_INDICES = 8
#: the event JAX reports each backend compile's duration under
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def pick_indices(seed: int, n_items: int) -> list:
    """Query indices from the seed, always including 0 and N - 1."""
    rng = np.random.default_rng(seed + 1)
    rest = rng.integers(1, n_items - 1, size=N_INDICES - 2)
    return [0, n_items - 1] + [int(i) for i in rest]


class _CompileLog:
    """Counts backend compiles and their seconds while active."""

    def __init__(self):
        self.n, self.seconds, self._on = 0, 0.0, False
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event, duration, **kwargs):
        if self._on and event == COMPILE_EVENT:
            self.n += 1
            self.seconds += duration

    def __enter__(self):
        self.n, self.seconds, self._on = 0, 0.0, True
        return self

    def __exit__(self, *exc):
        self._on = False


_COMPILES = None


def _compile_log() -> _CompileLog:
    global _COMPILES
    if _COMPILES is None:
        _COMPILES = _CompileLog()
    return _COMPILES


def _peak_bytes(mesh):
    """Highest ``peak_bytes_in_use`` over the mesh's devices (process
    lifetime), or None where the backend keeps no memory stats."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in mesh.devices.flat]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _check(name, got, want):
    if not np.array_equal(np.asarray(got), want):
        bad = [i for i in range(len(want))
               if not np.array_equal(np.asarray(got)[i], want[i])]
        raise AssertionError(f"{name}: records {bad} differ from the DB")


def _expected(cfg, db, idx):
    from repro.core import pir
    from repro.core import protocol as protocol_mod
    rows = db[np.asarray(idx)]
    if protocol_mod.for_config(cfg).share_kind == "xor":
        return rows
    return pir.db_as_bytes(rows)


def _serve(system, cfg, db, indices, buckets, mesh) -> dict:
    """Cold then warm synchronous queries per bucket, byte-checked."""
    out = {"plans": {b: r["label"] for b, r in
                     system.servers[0].plan_report().items()}}
    with _compile_log() as log:
        for b in buckets:
            idx = indices[:b]
            _check(f"bucket {b} cold", system.query(idx),
                   _expected(cfg, db, idx))
    out["compile_s"] = log.seconds
    out["backend_compiles"] = log.n
    for b in buckets:
        idx = indices[-b:]
        t0 = time.perf_counter()
        rec = system.query(idx)
        out[f"warm_query_s_b{b}"] = time.perf_counter() - t0
        _check(f"bucket {b} warm", rec, _expected(cfg, db, idx))
    out["n_compiles"] = sum(s.n_compiles for s in system.servers)
    out["peak_bytes_in_use"] = _peak_bytes(mesh)
    return out


def phase_engine_plans(cfg, mesh, db, indices, seed=0):
    """Phase A: the engine's plan per bucket (1 and 4), sync queries and a
    started session. Returns (report, system) — its database feeds B."""
    from repro.runtime.serve_loop import MultiServerPIR
    system = MultiServerPIR(db, cfg, mesh, path=None, n_queries=4,
                            buckets=(1, 4),
                            client_rng=np.random.default_rng(seed))
    report = _serve(system, cfg, db, [indices[1]] + indices, (1, 4), mesh)
    n_compiles = report["n_compiles"]
    with system:
        futs = [system.submit(i) for i in indices]
        recs = [f.result() for f in futs]
    _check("session", np.stack(recs), _expected(cfg, db, indices))
    if sum(s.n_compiles for s in system.servers) != n_compiles:
        raise AssertionError("the session compiled a new serve step")
    report["session_records"] = len(recs)
    return report, system


def phase_megakernel_xor(cfg, mesh, database, db, indices, seed=0):
    """Phase B: the already placed database, ``fused-pallas`` (xor body),
    bucket 4."""
    from repro.runtime.serve_loop import MultiServerPIR
    system = MultiServerPIR(database, cfg, mesh, path="fused-pallas",
                            n_queries=4, buckets=(4,),
                            client_rng=np.random.default_rng(seed + 2))
    if system.db is not database:
        raise AssertionError("phase B placed the database again")
    report = _serve(system, cfg, db, indices, (4,), mesh)
    if not report["plans"][4].startswith("fused-pallas/"):
        raise AssertionError(f"phase B ran {report['plans'][4]}")
    return report


def phase_additive(cfg, mesh, db, indices, seed=0):
    """Phase C: ``additive-dpf-2``, the engine's plan, bucket 4."""
    from repro.runtime.serve_loop import MultiServerPIR
    system = MultiServerPIR(db, cfg, mesh, path=None, n_queries=4,
                            buckets=(4,),
                            client_rng=np.random.default_rng(seed + 3))
    return _serve(system, cfg, db, indices, (4,), mesh)


def phase_sharded(cfg, mesh, db, indices, seed=0):
    """The DB sharded over the mesh's ``model`` axis: every device must
    hold an equal slice of the words view, and answers need the
    cross-shard XOR reduce. Engine plans, buckets 1 and 4."""
    from repro.runtime.serve_loop import MultiServerPIR
    n_dev = mesh.devices.size
    system = MultiServerPIR(db, cfg, mesh, path=None, n_queries=4,
                            buckets=(1, 4),
                            client_rng=np.random.default_rng(seed + 4))
    shards = system.db.view("words").addressable_shards
    rows = cfg.n_items // n_dev
    if (len(shards) != n_dev or len({s.device for s in shards}) != n_dev
            or any(s.data.shape[0] != rows for s in shards)):
        raise AssertionError(
            f"words view not split over {n_dev} devices: "
            f"{[(str(s.device), s.data.shape) for s in shards]}")
    report = _serve(system, cfg, db, [indices[1]] + indices, (1, 4), mesh)
    report["rows_per_device"] = rows
    return report


def _print_phase(name, report):
    print(f"[{name}] " + json.dumps(report, sort_keys=True, default=str),
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded pir-8g phase")
    args = ap.parse_args(argv)

    if os.environ.get("REPRO_FORCE_BACKEND"):
        raise SystemExit("REPRO_FORCE_BACKEND is set: refusing to run")
    os.environ["REPRO_PLAN_CACHE"] = "off"   # before repro.engine loads
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX's first device is {dev.platform}")
    from repro.configs.pir import PIR_1G, PIR_1G_ADD, PIR_8G
    from repro.core import pir
    from repro.engine.backend import resolve_interpret
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_local_mesh
    if resolve_interpret(None) is not False:
        raise AssertionError("Pallas kernels would run in interpret mode")
    cache_dir = enable_compile_cache()
    print(f"[setup] device={dev.device_kind} count={len(jax.devices())} "
          f"compile_cache={cache_dir}", flush=True)

    if args.chips == 4:
        mesh = make_local_mesh(data=1, model=4)
        phases = [("sharded pir-8g", PIR_8G)]
    else:
        mesh = make_local_mesh()
        phases = [("A pir-1g engine", PIR_1G),
                  ("B pir-1g fused-pallas", PIR_1G),
                  ("C pir-1g-add engine", PIR_1G_ADD)]
    cfg0 = phases[0][1]
    t0 = time.perf_counter()
    db = pir.make_database(np.random.default_rng(args.seed), cfg0.n_items,
                           cfg0.item_bytes)
    indices = pick_indices(args.seed, cfg0.n_items)
    print(f"[setup] db {db.shape} {db.dtype} made in "
          f"{time.perf_counter() - t0:.1f} s; indices {indices}", flush=True)

    if args.chips == 4:
        _print_phase(phases[0][0],
                     phase_sharded(PIR_8G, mesh, db, indices, args.seed))
    else:
        report, system = phase_engine_plans(PIR_1G, mesh, db, indices,
                                            args.seed)
        _print_phase(phases[0][0], report)
        _print_phase(phases[1][0], phase_megakernel_xor(
            PIR_1G, mesh, system.db, db, indices, args.seed))
        del system
        report = phase_additive(PIR_1G_ADD, mesh, db, indices, args.seed)
        if not report["plans"][4].startswith("fused-pallas/"):
            raise AssertionError(f"phase C resolved {report['plans'][4]}")
        _print_phase(phases[2][0], report)
    n_cached = sum(len(f) for _, _, f in os.walk(cache_dir))
    print(f"[setup] compile cache {cache_dir}: {n_cached} files", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
