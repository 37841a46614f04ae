"""Plain reference of a single-record PIR lookup: the record is the row.

It imports nothing of the system under test. ``db`` is the database as
the harness made it from the seed (``[N, W]`` little-endian u32 words).
A configuration whose shares are XOR returns the record as its u32 words,
one whose shares are additive over Z_256 as its bytes.
"""
import numpy as np


def records(db, indices, config):
    rows = np.asarray(db)[np.asarray(indices, dtype=np.int64)]
    if config["share_kind"] == "additive":
        rows = np.ascontiguousarray(rows, dtype="<u4").view(np.uint8)
        return list(rows.reshape(len(indices), -1))
    return list(rows)
