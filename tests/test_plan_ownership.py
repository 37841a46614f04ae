"""The engine owns the kernel choice at every serving entry point.

Each session and server resolves every bucket's plan through
``engine.resolve``; a caller that wants another plan records it with
``engine.record_plans`` before construction, as a replica's warm start
does. The smoke phases run the serving path at ``PIR_SMOKE`` scale on the
CPU (the megakernel in interpret mode) and compare every record returned
with the database row, byte for byte.
"""
import numpy as np
import pytest

from repro import engine
from repro.config import PIRConfig
from repro.configs.pir import PIR_SMOKE, PIR_SMOKE_ADD
from repro.core import pir
from repro.core.protocol import ExecutionPlan
from repro.launch.mesh import make_local_mesh

N = 1 << 6
BUCKETS = (1, 4)


def _db(n: int, seed: int = 0) -> np.ndarray:
    return pir.make_database(np.random.default_rng(seed), n, 32)


def _entry_point(kind: str):
    """(cfg the plans are resolved for, each party's BucketedServeFns)."""
    from repro.core.server import PIRServer
    from repro.replica import ServeReplica
    from repro.runtime.batch import BatchPIR
    from repro.runtime.serve_loop import MultiServerPIR, SingleServerPIR
    mesh = make_local_mesh()
    kw = dict(n_queries=max(BUCKETS), buckets=BUCKETS)
    if kind == "PIRServer":
        cfg = PIRConfig(n_items=N)
        fns = [PIRServer(0, _db(N), cfg, mesh, **kw).bucketed]
    elif kind == "MultiServerPIR-xor":
        cfg = PIRConfig(n_items=N)
        fns = [s.bucketed for s in MultiServerPIR(_db(N), cfg, mesh,
                                                  **kw).servers]
    elif kind == "MultiServerPIR-additive":
        cfg = PIRConfig(n_items=N, protocol="additive-dpf-2")
        fns = [s.bucketed for s in MultiServerPIR(_db(N), cfg, mesh,
                                                  **kw).servers]
    elif kind == "SingleServerPIR":
        cfg = PIRConfig(n_items=N, protocol="lwe-simple-1", n_servers=1)
        fns = [SingleServerPIR(_db(N), cfg, mesh, **kw).servers[0].bucketed]
    elif kind == "BatchPIR":
        outer = PIRConfig(n_items=1 << 10, item_bytes=32, batch_m=4)
        system = BatchPIR(_db(outer.n_items), outer, mesh, rounds=BUCKETS)
        cfg, fns = system.inner_cfg, system.serve
    else:
        cfg = PIRConfig(n_items=N)
        replica = ServeReplica("r0", _db(N), cfg, mesh, **kw)
        fns = [s.bucketed for s in replica.pir.servers]
    return cfg, fns


@pytest.mark.parametrize("kind", [
    "PIRServer", "MultiServerPIR-xor", "MultiServerPIR-additive",
    "SingleServerPIR", "BatchPIR", "ServeReplica"])
def test_every_entry_point_serves_the_engines_plan(plan_cache_off, kind):
    cfg, fns = _entry_point(kind)
    for bucketed in fns:
        assert bucketed.buckets == BUCKETS
        for b in BUCKETS:
            want = engine.resolve(cfg, b)
            got = bucketed.plan_for_bucket(b)
            assert got == want and got.provenance == want.provenance, (
                kind, b, got, want)


def test_session_refuses_a_path_string():
    from repro.runtime.serve_loop import MultiServerPIR, TwoServerPIR
    cfg = PIRConfig(n_items=N)
    for cls in (MultiServerPIR, TwoServerPIR):
        with pytest.raises(TypeError, match="engine"):
            cls(_db(N), cfg, make_local_mesh(), path="fused")


MEGAKERNEL = ExecutionPlan(expand="fused-pallas", scan="pallas")

#: phase -> (config, bucket, plan recorded before construction or None
#: for the engine's own)
PHASES = {
    "xor-engine-b1": (PIR_SMOKE, 1, None),
    "xor-engine-b4": (PIR_SMOKE, 4, None),
    "xor-megakernel": (PIR_SMOKE, 4, MEGAKERNEL),
    "additive-megakernel": (PIR_SMOKE_ADD, 4, MEGAKERNEL),
    "additive-engine": (PIR_SMOKE_ADD, 4, None),
}


@pytest.fixture(scope="module")
def smoke_db():
    return _db(PIR_SMOKE.n_items)


def _indices(n_items: int) -> list:
    """Both ends of the domain and six rows between, from a fixed seed."""
    rest = np.random.default_rng(1).integers(1, n_items - 1, size=6)
    return [0, n_items - 1] + [int(i) for i in rest]


@pytest.mark.parametrize("phase", sorted(PHASES))
def test_smoke_phases_return_every_record(plan_cache_off, smoke_db, phase):
    from repro.runtime.serve_loop import MultiServerPIR
    cfg, bucket, seeded = PHASES[phase]
    if seeded is not None:
        engine.record_plans(cfg, {bucket: seeded})
    system = MultiServerPIR(smoke_db, cfg, make_local_mesh(),
                            n_queries=bucket, buckets=(bucket,),
                            client_rng=np.random.default_rng(2))
    plan = system.servers[0].bucketed.plan_for_bucket(bucket)
    assert plan == (seeded or engine.resolve(cfg, bucket))
    idx = _indices(cfg.n_items)
    want = smoke_db[idx]
    if system.protocol.share_kind == "additive":
        want = pir.db_as_bytes(want)
    np.testing.assert_array_equal(system.query(idx[:bucket]),
                                  want[:bucket])
    with system:
        futs = [system.submit(i) for i in idx]
        got = np.stack([f.result(timeout=300.0) for f in futs])
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(got, want)
    assert [s.n_compiles for s in system.servers] == [1, 1]
