"""PIR database configurations — the paper's own evaluation grid (§5.2).

Records are 32-byte hashes (SHA-256-sized, the paper's CT / credential-
checking format). DB sizes mirror the paper's 0.5–8 GB sweep; n_items is
db_bytes / 32 and always a power of two (the GGM tree domain).

Share schemes are named by protocol-registry entries (``core/protocol.py``):
``xor-dpf-2`` (default), ``additive-dpf-2``, ``xor-dpf-k``. The old
``mode="xor"|"additive"`` kwarg still works via the deprecation shim in
``PIRConfig`` but new configs should name a protocol.
"""
from repro.config import PIRConfig

# paper evaluation points (Figure 9): 0.5, 1, 2, 4, 8 GB
PIR_512M = PIRConfig(n_items=1 << 24, item_bytes=32)
PIR_1G = PIRConfig(n_items=1 << 25, item_bytes=32)
PIR_2G = PIRConfig(n_items=1 << 26, item_bytes=32)
PIR_4G = PIRConfig(n_items=1 << 27, item_bytes=32)
PIR_8G = PIRConfig(n_items=1 << 28, item_bytes=32)

# the 32 B records at the size of a public credential corpus (Have I Been
# Pwned's Pwned Passwords v8, 847,223,402 hashes, padded to the 2^30 GGM
# domain): 32 GiB, which no single chip holds; served row-sharded over a
# 1 x 4 mesh, 8 GiB per chip (credential checking, Li et al., CCS 2019)
PIR_32G_4CHIP = PIRConfig(n_items=1 << 30, item_bytes=32,
                          protocol="xor-dpf-2")

# additive-share protocol (the MXU batched-matmul path, beyond-paper)
PIR_1G_ADD = PIRConfig(n_items=1 << 25, item_bytes=32,
                       protocol="additive-dpf-2")

# k-server XOR at 1 GB (beyond-paper scenario diversity; k = n_servers)
PIR_1G_K3 = PIRConfig(n_items=1 << 25, item_bytes=32,
                      protocol="xor-dpf-k", n_servers=3)

# single-server LWE at 1 GB (beyond-paper; no non-collusion assumption).
# Parameter selection is validated at query time (core/lwe.py params_for);
# note the client-side A matrix at this N is PRG-regenerated at ~GB scale —
# the 1 GB point is for plan/roofline math, not for this container.
PIR_1G_LWE = PIRConfig(n_items=1 << 25, item_bytes=32,
                       protocol="lwe-simple-1", n_servers=1)

# CPU-container scale for tests/benches/examples
PIR_SMOKE = PIRConfig(n_items=1 << 14, item_bytes=32, batch_queries=4)
PIR_SMOKE_ADD = PIRConfig(n_items=1 << 14, item_bytes=32,
                          protocol="additive-dpf-2", batch_queries=4)
# 2^12 records: three parties' serve steps compile in CI-tolerable time
PIR_SMOKE_K3 = PIRConfig(n_items=1 << 12, item_bytes=32,
                         protocol="xor-dpf-k", n_servers=3, batch_queries=4)
# online-update smoke (examples/db_updates.py): 3-server epoched updates
# at 2^10 records / bucket 2 — the smallest shape where the k-party serve
# steps still compile inside the CI gate's budget
PIR_SMOKE_UPD = PIRConfig(n_items=1 << 10, item_bytes=32,
                          protocol="xor-dpf-k", n_servers=3,
                          batch_queries=2)
# single-server LWE smoke (examples/single_server.py, tests): the LWE
# serve step is slice + int32 GEMM — no GGM chains — so it compiles far
# faster than the DPF steps and fits the CI gate at full smoke scale
PIR_SMOKE_LWE = PIRConfig(n_items=1 << 14, item_bytes=32,
                          protocol="lwe-simple-1", n_servers=1,
                          batch_queries=4)
# replica-plane smoke (examples/replicas.py, tests):
# every replica pays its own serve-step compile at construction, so the
# fleet demos run the cheap LWE step at 2^12 records to keep N compiles
# inside the CI gate's budget
PIR_SMOKE_REPL = PIRConfig(n_items=1 << 12, item_bytes=32,
                           protocol="lwe-simple-1", n_servers=1,
                           batch_queries=4)
# verified-reconstruction smoke (python -m repro.chaos --smoke, tests):
# replica scale + the per-row checksum column,
# so chaos-corrupted shares surface as IntegrityError instead of garbage
PIR_SMOKE_CHK = PIRConfig(n_items=1 << 12, item_bytes=32,
                          protocol="lwe-simple-1", n_servers=1,
                          batch_queries=4, checksum=True)
# batch-PIR smoke (examples/batch_query.py, tests): m=4 indices per round
# cuckoo-hashed into B=8 buckets of ~2^8 rows; checksum on so verified
# reconstruction rides through reassembly. One bucketed serve step is
# shared across all B same-shape bucket views — a single compile/party.
PIR_SMOKE_BATCH = PIRConfig(n_items=1 << 10, item_bytes=32,
                            batch_m=4, batch_queries=1, checksum=True)
# paper-scale batch point (plan/roofline math): 1 GB DB, 256-record batches
PIR_1G_BATCH = PIRConfig(n_items=1 << 25, item_bytes=32, batch_m=256)

PIR_CONFIGS = {
    "pir-512m": PIR_512M,
    "pir-1g": PIR_1G,
    "pir-2g": PIR_2G,
    "pir-4g": PIR_4G,
    "pir-8g": PIR_8G,
    "pir-32g-4chip": PIR_32G_4CHIP,
    "pir-1g-add": PIR_1G_ADD,
    "pir-1g-k3": PIR_1G_K3,
    "pir-1g-lwe": PIR_1G_LWE,
    "pir-smoke": PIR_SMOKE,
    "pir-smoke-add": PIR_SMOKE_ADD,
    "pir-smoke-k3": PIR_SMOKE_K3,
    "pir-smoke-upd": PIR_SMOKE_UPD,
    "pir-smoke-lwe": PIR_SMOKE_LWE,
    "pir-smoke-repl": PIR_SMOKE_REPL,
    "pir-smoke-chk": PIR_SMOKE_CHK,
    "pir-smoke-batch": PIR_SMOKE_BATCH,
    "pir-1g-batch": PIR_1G_BATCH,
}
