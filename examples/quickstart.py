"""Quickstart: private information retrieval in ~40 lines.

Spins up the two non-colluding servers, retrieves a record without either
server learning which, and verifies the reconstruction — the paper's
Figure 2 flow on the production code path.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.configs.pir import PIR_SMOKE
from repro.core import pir
from repro.launch.mesh import make_local_mesh
from repro.runtime.serve_loop import TwoServerPIR

def main():
    # A database of 2^14 records, each a 32-byte hash — the paper's
    # certificate-transparency / breached-credentials shape (§5.2).
    cfg = PIR_SMOKE
    rng = np.random.default_rng(0)
    db = pir.make_database(rng, cfg.n_items, cfg.item_bytes)
    print(f"DB: {cfg.n_items} records x {cfg.item_bytes} B "
          f"({cfg.db_bytes / (1 << 20):.0f} MiB)")

    # Two servers, each holding a full replica. The engine picks the kernel
    # plan per batch bucket: here (2^14 rows, CPU) the fused path, which runs
    # DPF evaluation and the select-XOR scan in one pass (IM-PIR's offload,
    # with the GGM tree on-device — see DESIGN.md §2).
    mesh = make_local_mesh()
    system = TwoServerPIR(db, cfg, mesh, n_queries=4)

    secret_indices = [7, 4242, 9000, cfg.n_items - 1]
    print(f"querying indices {secret_indices} (servers never see these)")
    records = system.query(secret_indices)

    for idx, rec in zip(secret_indices, records):
        ok = np.array_equal(rec, db[idx])
        print(f"  D[{idx:6d}] -> {bytes(rec.view(np.uint8))[:8].hex()}... "
              f"{'OK' if ok else 'MISMATCH'}")
        assert ok
    print("private retrieval verified.")


if __name__ == "__main__":
    main()
