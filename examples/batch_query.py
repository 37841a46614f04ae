"""Batch PIR: m records per round through cuckoo buckets (DESIGN.md §14).

The amortization demo: a ``BatchPIR`` session retrieves m=4 records per
round by cuckoo-hashing the requested indices into B = 2m buckets (each a
capacity-rows slice of the database, replicated under 3 hash functions)
and issuing exactly ONE real-or-dummy inner query per bucket — the
servers see a fixed B-wide round regardless of which indices were asked,
and the scanned rows per round (B·capacity ≈ 4N) serve m records instead
of one. All B buckets share a single compiled serve step per party
(one shape -> one executable), so the m-fold batching costs zero extra
compiles. Mid-session, a stage+publish write lands in every candidate
bucket and the next round's answer futures carry the new epoch.

Run:  PYTHONPATH=src python examples/batch_query.py
"""
import numpy as np

from repro.configs.pir import PIR_SMOKE_BATCH
from repro.core import pir
from repro.launch.mesh import make_local_mesh
from repro.runtime.batch import BatchPIR


def main():
    cfg = PIR_SMOKE_BATCH        # 2^10 records x 32 B, m=4, checksums on
    rng = np.random.default_rng(0)
    db_host = pir.make_database(rng, cfg.n_items, cfg.item_bytes)

    system = BatchPIR(db_host, cfg, make_local_mesh())
    bdb = system.db
    print(f"DB: {cfg.n_items} records x {cfg.item_bytes} B -> "
          f"m={cfg.batch_m} batch: B={bdb.n_buckets} buckets x "
          f"{bdb.capacity} rows (expansion {bdb.expansion:.1f}x, "
          f"cuckoo failure bound "
          f"{system.layout.params.failure_bound():.3f})")

    # --- one m-record round --------------------------------------------
    batch = [123, 7, 877, 123]           # duplicates share a bucket query
    records = system.query_batch(batch)
    for i, rec in zip(batch, records):
        assert np.array_equal(rec, db_host[i]), f"record {i} mismatch"
    rounds, width = system.dispatch_log[-1]
    assert width == bdb.n_buckets, "every round must be exactly B wide"
    print(f"epoch {bdb.epoch}: {len(batch)} records in {rounds} round(s) "
          f"of {width} per-bucket queries "
          f"(scanned {width * bdb.capacity} rows vs "
          f"{len(set(batch)) * cfg.n_items} single-query)")

    # --- stage + publish mid-session, then re-query --------------------
    target = batch[0]
    new_record = rng.integers(0, 1 << 32, size=(1, cfg.item_bytes // 4),
                              dtype=np.uint32)
    system.update([target], new_record)
    epoch = system.publish()
    fut = system.submit_batch([target, 7])
    system.scheduler.pump()
    after = np.asarray(fut.result(timeout=360.0))
    assert np.array_equal(after[0], new_record[0]), "updated row must serve"
    assert np.array_equal(after[1], db_host[7]), "untouched row unchanged"
    assert fut.epoch == epoch, "answers must carry the published epoch"
    print(f"published epoch {epoch}: D[{target}] rewrote in all "
          f"{len(system.layout.occurrences(target))} candidate buckets; "
          f"post-publish round tagged epoch={fut.epoch}")

    # the whole session — every bucket, every round, pre/post publish —
    # ran on ONE compiled serve step per party
    assert all(s.n_compiles == 1 for s in system.serve), \
        "B buckets must share one compiled step per party"
    print(f"batch session served: {system.n_parties} parties x "
          f"1 compile each, uniform {bdb.n_buckets}-wide rounds, "
          f"checksums verified on every reconstruction.")


if __name__ == "__main__":
    main()
