"""Three-server PIR session via the protocol plane (beyond-paper).

The paper's deployment is two non-colluding servers; the protocol registry
(``core/protocol.py``) generalizes the share scheme, and this demo runs the
``xor-dpf-k`` protocol with k = 3: one real DPF pair blinded by a ring of
pairwise-shared GGM mask seeds (DESIGN.md §7.2). Each of the three servers
scans the full database with a *dense pseudorandom* selection vector — no
single server (nor its answer share) learns anything about the queried
index — and the client XORs all three answer shares to reconstruct.

Everything below the facade is the same production machinery as the
two-server quickstart: one ``PIRServer`` (bucketed compiled serve steps)
per party, one ``QueryScheduler`` coalescing the query stream, shares
reconciled through ``PIRProtocol.reconstruct``.

Run:  PYTHONPATH=src python examples/multi_server.py
"""
import numpy as np

from repro.configs.pir import PIR_SMOKE_K3
from repro.core import dpf, pir
from repro.core.protocol import for_config
from repro.launch.mesh import make_local_mesh
from repro.runtime.serve_loop import MultiServerPIR


def main():
    cfg = PIR_SMOKE_K3           # 2^12 records x 32 B, xor-dpf-k, k=3
    proto = for_config(cfg)
    rng = np.random.default_rng(0)
    db = pir.make_database(rng, cfg.n_items, cfg.item_bytes)
    print(f"DB: {cfg.n_items} records x {cfg.item_bytes} B; "
          f"protocol={cfg.protocol} ({proto.n_parties(cfg)} parties)")

    # one bucket keeps this demo to one XLA compile per party (~40 s each
    # on a 1-core CPU container); ragged traffic pads up to it
    system = MultiServerPIR(db, cfg, make_local_mesh(), n_queries=4,
                            buckets=(4,))
    assert len(system.servers) == 3

    secret_indices = [7, 1234, 4000, cfg.n_items - 1]
    print(f"querying indices {secret_indices} "
          f"(none of the 3 servers sees these)")
    records = system.query(secret_indices)

    for idx, rec in zip(secret_indices, records):
        ok = np.array_equal(rec, db[idx])
        print(f"  D[{idx:5d}] -> {bytes(rec.view(np.uint8))[:8].hex()}... "
              f"{'OK' if ok else 'MISMATCH'}")
        assert ok

    # show why a single server learns nothing: its share is pseudorandom
    q = pir.query_gen(np.random.default_rng(1), 7, cfg)
    share0 = np.asarray(system.servers[0].answer(
        dpf.stack_keys([q.keys[0]])))[0]
    print(f"server 0's answer share for D[7]: "
          f"{bytes(share0.view(np.uint8))[:8].hex()}... "
          f"(pseudorandom; equals D[7] only after XOR with the other two)")
    assert not np.array_equal(share0, db[7])
    print("3-server private retrieval verified.")


if __name__ == "__main__":
    main()
