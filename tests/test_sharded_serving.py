"""The sharded serving path on four virtual CPU devices.

``MultiServerPIR`` over a 1 x 4 mesh: the database row-sharded over the
``model`` axis (the paper's linear layout), queries through ``submit``,
the ``QueryScheduler`` and reconstruction, every record compared with a
numpy row lookup. The indices are the first and last row of every shard,
so every shard's non-zero ``start_block`` and both ends of its leaf range
are read. The fused chunked expand+scan and the megakernel (in interpret
mode) each run under both cross-shard XOR collectives, each case's plan
recorded with ``engine.record_plans`` before the session is built; one
more case
zeroes shard 2's partial answer before the reduce and sees exactly that
shard's records come back wrong, so the comparison catches a dropped
shard.

The device count is fixed when JAX starts, so each case runs this file
as a script in a process of its own (``XLA_FLAGS``
``--xla_force_host_platform_device_count=4``, ``JAX_PLATFORMS=cpu``),
which prints one JSON line.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

N = 1 << 14
SHARDS = 4
DROPPED = 2                      # the shard the fault case zeroes
SEED = 2147483659
TIMEOUT_S = 120
#: case -> (the plan's expand, its collective, drop shard DROPPED)
CASES = {
    "fused-gather": ("fused", "gather", False),
    "fused-butterfly": ("fused", "butterfly", False),
    "megakernel-gather": ("fused-pallas", "gather", False),
    "megakernel-butterfly": ("fused-pallas", "butterfly", False),
    "fused-gather-dropped-shard": ("fused", "gather", True),
}


def _database() -> np.ndarray:
    return np.random.default_rng(SEED).integers(
        0, 1 << 32, size=(N, 8), dtype=np.uint32)


def _indices() -> list:
    rows = N // SHARDS
    return [d * rows + off for d in range(SHARDS) for off in (0, rows - 1)]


def _serve(case: str) -> dict:
    """One case on this process's four devices: the row range each device
    holds, the plan, and the records the indices came back as."""
    import jax
    import jax.numpy as jnp

    from repro import engine
    from repro.config import PIRConfig
    from repro.core.protocol import ExecutionPlan, XorDpf2
    from repro.launch.mesh import make_local_mesh
    from repro.runtime.serve_loop import MultiServerPIR

    class DropShard(XorDpf2):
        """xor-dpf-2 whose shard ``DROPPED`` loses its partial answer."""

        def reduce(self, partial_res, axis, n_shards, plan):
            dropped = jax.lax.axis_index(axis) == DROPPED
            partial_res = jnp.where(dropped, jnp.zeros_like(partial_res),
                                    partial_res)
            return super().reduce(partial_res, axis, n_shards, plan)

    expand, collective, drop = CASES[case]
    cfg = PIRConfig(n_items=N, item_bytes=32)
    engine.record_plans(cfg, {4: ExecutionPlan(
        expand=expand, scan="jnp" if expand == "fused" else "pallas",
        collective=collective)})
    system = MultiServerPIR(
        _database(), cfg, make_local_mesh(data=1, model=SHARDS),
        n_queries=4, buckets=(4,),
        protocol=DropShard() if drop else None,
        client_rng=np.random.default_rng(SEED + 1))
    held = sorted((s.device.id, s.index[0].start or 0,
                   N if s.index[0].stop is None else s.index[0].stop)
                  for s in system.db.view("words").addressable_shards)
    with system:
        futs = [system.submit(i) for i in _indices()]
        records = [np.asarray(f.result(timeout=TIMEOUT_S)).tolist()
                   for f in futs]
    plan = system.servers[0].bucketed.plan_for_bucket(4)
    return {"held": held, "expand": plan.expand,
            "collective": plan.collective, "records": records}


@pytest.mark.parametrize("case", sorted(CASES))
def test_sharded_path_exact_on_four_devices(case):
    env = dict(os.environ, JAX_PLATFORMS="cpu", REPRO_PLAN_CACHE="off",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("REPRO_FORCE_BACKEND", None)
    done = subprocess.run([sys.executable, __file__, case], env=env,
                          capture_output=True, text=True, timeout=TIMEOUT_S)
    assert done.returncode == 0, done.stderr[-4000:]
    got = json.loads(done.stdout.strip().splitlines()[-1])
    expand, collective, drop = CASES[case]
    assert (got["expand"], got["collective"]) == (expand, collective)
    rows = N // SHARDS
    # each device holds exactly its N/4 rows, in row order
    assert got["held"] == [[d, d * rows, (d + 1) * rows]
                           for d in range(SHARDS)]
    want = _database()[_indices()]
    right = [np.array_equal(np.asarray(r, np.uint32), w)
             for r, w in zip(got["records"], want)]
    if drop:
        assert right == [i // rows != DROPPED for i in _indices()]
    else:
        assert all(right)


if __name__ == "__main__":
    print(json.dumps(_serve(sys.argv[1])), flush=True)
