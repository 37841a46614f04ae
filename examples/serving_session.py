"""Streaming PIR session: concurrent clients, one pipelined scheduler.

The quickstart retrieves one synchronous batch; this example runs the
serving frontend the way production traffic hits it (DESIGN.md §6.2):
several client threads submit queries at their own pace, the scheduler
coalesces them into padded bucket batches, double-buffers dispatch, and
resolves each client's ``AnswerFuture`` as the two parties' shares are
reconciled.

Run:  PYTHONPATH=src python examples/serving_session.py
"""
import threading

import numpy as np

from repro.config import PIRConfig
from repro.core import pir
from repro.launch.mesh import make_local_mesh
from repro.runtime.serve_loop import TwoServerPIR

N_CLIENTS = 3
QUERIES_PER_CLIENT = 4


def client(name: str, system: TwoServerPIR, db, rng, errors: list):
    indices = rng.integers(0, system.cfg.n_items,
                           size=QUERIES_PER_CLIENT).tolist()
    futures = [(i, system.submit(i)) for i in indices]   # returns immediately
    for idx, fut in futures:
        row = fut.result(timeout=300.0)
        ok = np.array_equal(row, db[idx])
        print(f"  [{name}] D[{idx:5d}] -> "
              f"{bytes(np.asarray(row).view(np.uint8))[:8].hex()}... "
            f"{'OK' if ok else 'MISMATCH'}")
        if not ok:
            errors.append((name, idx))


def main():
    cfg = PIRConfig(n_items=1 << 12, item_bytes=32)
    db = pir.make_database(np.random.default_rng(0), cfg.n_items,
                           cfg.item_bytes)
    # one bucket keeps this demo to a single XLA compile per party (~40 s
    # on a 1-core CPU container); ragged traffic pads up to it
    system = TwoServerPIR(db, cfg, make_local_mesh(), n_queries=4,
                          buckets=(4,))
    print(f"DB: {cfg.n_items} records x {cfg.item_bytes} B; "
          f"buckets={system.servers[0].buckets}")

    errors: list = []
    with system:                                  # background session thread
        threads = [
            threading.Thread(target=client,
                             args=(f"client{c}", system, db,
                                   np.random.default_rng(100 + c), errors))
            for c in range(N_CLIENTS)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    stats = system.scheduler.stats
    print(f"answered={stats.answered} batches={stats.batches} "
          f"padded={stats.padded} (pad fraction {stats.pad_fraction:.0%})")
    assert not errors, errors
    print("all private retrievals verified.")


if __name__ == "__main__":
    main()
