"""Online database updates under 3-server PIR: stage → publish → re-query.

The paper freezes the database after preloading (§3.3 excludes transfer
cost from query latency). The database plane (DESIGN.md §8) lifts that:
``MultiServerPIR.update`` stages *public* row writes into a delta log and
``publish`` swaps them in as a new epoch — an O(rows) scatter against the
resident views, never a re-preload, never a serving stall. Updates are
public metadata: privacy protects the *query index*, not the data, so all
three non-colluding parties apply the identical delta and their XOR answer
shares stay consistent. Every answer future is tagged with the epoch it
was computed at.

Run:  PYTHONPATH=src python examples/db_updates.py
"""
import numpy as np

from repro.configs.pir import PIR_SMOKE_UPD
from repro.core import pir
from repro.launch.mesh import make_local_mesh
from repro.runtime.serve_loop import MultiServerPIR


def main():
    cfg = PIR_SMOKE_UPD          # 2^10 records x 32 B, xor-dpf-k, k=3
    rng = np.random.default_rng(0)
    db_host = pir.make_database(rng, cfg.n_items, cfg.item_bytes)

    # one bucket keeps this demo to one XLA compile per party (~40-90 s
    # each on a 1-core CPU container); all 3 parties share ONE placed
    # ShardedDatabase — the DB is public, only key material is per-party
    system = MultiServerPIR(db_host, cfg, make_local_mesh(), n_queries=2,
                            buckets=(2,))
    print(f"DB: {cfg.n_items} records x {cfg.item_bytes} B; "
          f"protocol={cfg.protocol} ({system.n_parties} parties, "
          f"one shared placement: "
          f"{system.db.stats.preload_h2d_bytes} B host->device)")

    target, bystander = 123, 877
    before = system.query([target, bystander])
    assert np.array_equal(before[0], db_host[target])
    assert np.array_equal(before[1], db_host[bystander])
    print(f"epoch {system.epoch}: D[{target}] = "
          f"{bytes(before[0].view(np.uint8))[:8].hex()}...")

    # --- stage + publish one public row write --------------------------
    new_record = rng.integers(0, 1 << 32, size=(1, cfg.item_bytes // 4),
                              dtype=np.uint32)
    system.update([target], new_record)
    epoch = system.publish()
    delta_bytes = system.db.stats.update_h2d_bytes
    print(f"published epoch {epoch}: rewrote D[{target}] "
          f"({delta_bytes} B over the wire, vs {cfg.db_bytes} B full "
          f"re-preload)")
    assert delta_bytes < cfg.db_bytes // 100     # O(rows), not O(db)
    assert system.db.stats.n_full_placements == 1

    # --- re-query through the SAME compiled steps ----------------------
    futs = [system.submit(target), system.submit(bystander)]
    system.scheduler.pump()
    after = [np.asarray(f.result(timeout=360.0)) for f in futs]
    assert np.array_equal(after[0], new_record[0]), "updated row must serve"
    assert np.array_equal(after[1], db_host[bystander]), \
        "untouched row must be unchanged"
    assert all(f.epoch == epoch for f in futs)
    assert all(s.n_compiles == 1 for s in system.servers), \
        "the update path must not recompile serve steps"
    print(f"epoch {epoch}: D[{target}] = "
          f"{bytes(after[0].view(np.uint8))[:8].hex()}... (new record, "
          f"answer futures tagged epoch={futs[0].epoch})")
    print("online update served: updated + untouched rows verified on "
          "3-server PIR.")


if __name__ == "__main__":
    main()
