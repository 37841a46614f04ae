"""System-level behaviour tests: the paper's end-to-end story in one place.

1. Private retrieval is *correct* at system level (client never sends the
   index; two servers answer independently; reconstruction yields the
   record) — across DB sizes and batch sizes, under the engine's plans.
2. The LM serving integration: PIR-backed private token-embedding lookup
   returns bit-exact embedding rows (the Lam et al. [61] use case the
   paper benchmarks against).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.config import PIRConfig
from repro.core import pir
from repro.launch.mesh import make_local_mesh
from repro.runtime.serve_loop import TwoServerPIR

pytestmark = pytest.mark.slow    # compile-heavy: full-step jits on a 1-core CPU



@pytest.fixture(scope="module")
def mesh():
    return make_local_mesh()


@pytest.mark.parametrize("log_n,item_bytes", [(8, 32), (12, 32), (10, 64)])
def test_end_to_end_retrieval(mesh, log_n, item_bytes):
    n = 1 << log_n
    db = pir.make_database(np.random.default_rng(0), n, item_bytes)
    cfg = PIRConfig(n_items=n, item_bytes=item_bytes, batch_queries=2)
    sys2 = TwoServerPIR(db, cfg, mesh, n_queries=2)
    idx = [0, n - 1]
    np.testing.assert_array_equal(sys2.query(idx), db[idx])


def test_private_embedding_lookup(mesh):
    """PIR over an LM embedding table: retrieved rows are bit-exact.

    The table's bf16 rows are viewed as uint32 words (pairs of bf16), the
    XOR-PIR protocol retrieves the row for a *hidden* token id, and the
    client reassembles the bf16 vector — exact retrieval of arbitrary
    payloads, which quantization-based schemes cannot guarantee.
    """
    vocab_pow2, d = 1 << 10, 64
    rng = np.random.default_rng(3)
    table_bf16 = jnp.asarray(rng.standard_normal((vocab_pow2, d)),
                             jnp.bfloat16)
    # view bf16 pairs as uint32 words: [V, d/2]
    table_u16 = np.asarray(table_bf16).view(np.uint16).astype(np.uint32)
    table_words = ((table_u16[:, 1::2] << 16) | table_u16[:, 0::2])

    cfg = PIRConfig(n_items=vocab_pow2, item_bytes=d * 2, batch_queries=2)
    sys2 = TwoServerPIR(table_words, cfg, mesh, n_queries=2)
    token_ids = [17, 513]
    rows = sys2.query(token_ids)                     # [2, d/2] uint32
    # unpack back to the bf16 bit pattern
    out = np.empty((2, d), np.uint16)
    out[:, 0::2] = (rows & 0xFFFF).astype(np.uint16)
    out[:, 1::2] = (rows >> 16).astype(np.uint16)
    want = np.asarray(table_bf16)[np.asarray(token_ids)].view(np.uint16)
    np.testing.assert_array_equal(out, want)


def test_query_privacy_shape_invariance(mesh):
    """Server-visible work is index-independent: the key tensors a server
    receives have identical shapes/dtypes for every query index (the
    all-for-one principle's observable side)."""
    n = 1 << 8
    cfg = PIRConfig(n_items=n)
    rng = np.random.default_rng(4)
    shapes = set()
    for idx in (0, 1, n // 2, n - 1):
        q = pir.query_gen(rng, idx, cfg)
        k = q.keys[0]
        shapes.add((k.root_seed.shape, k.cw_seed.shape, k.cw_t.shape))
    assert len(shapes) == 1
