"""Client + reference-server PIR primitives (paper §2.3, §3, Algorithm 1).

Roles
-----
Client:  ``query_gen`` (Gen + per-party key split, dispatched through the
         protocol registry — ``core/protocol.py``), ``reconstruct_*``
         primitives (r1 ⊕ r2 / r1 + r2).
Server:  ``answer_*`` — the all-for-one scan. Single-device reference forms
         live here; the sharded production form (shard_map over the
         data=clusters / model=DB-shards mesh) lives in ``core.server``,
         parameterized by a registered ``PIRProtocol``.

Share schemes (see ``core/protocol.py`` for the full protocol plane)
--------------------------------------------------------------------
xor-dpf-2       paper-faithful: selection bits t(j) weight an XOR fold over
                DB rows (Figure 2 / Algorithm 1's dpXOR). Bit-exact.
additive-dpf-2  Z_256 byte shares; the batched-query form is an int8 matrix
                product (queries × DB) that the MXU executes natively — the
                beyond-paper operational-intensity lever (DESIGN.md §2).
xor-dpf-k       k-server XOR shares (beyond-paper; DESIGN.md §7.2).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import PIRConfig
from repro.core import dpf

U32 = jnp.uint32


# ---------------------------------------------------------------------------
# Database
# ---------------------------------------------------------------------------

def make_database(rng: np.random.Generator, n_items: int, item_bytes: int = 32,
                  *, checksum: bool = False) -> np.ndarray:
    """Random PIR DB of ``n_items`` records, each ``item_bytes`` long.

    Mirrors the paper's evaluation DB (random 32-byte/256-bit hashes, §5.2).
    Stored as uint32 words: ``[N, item_bytes // 4]``. ``checksum=True``
    appends the verified-reconstruction checksum column (one u32 per row,
    ``repro.db.spec.row_checksum``) — the *stored* layout checksummed
    configs serve from; eager tests and oracles use it to build share
    inputs that match what the serve stack holds.
    """
    if item_bytes % 4:
        raise ValueError("item_bytes must be a multiple of 4")
    words = rng.integers(0, 1 << 32, size=(n_items, item_bytes // 4),
                         dtype=np.uint32)
    if checksum:
        from repro.db.spec import row_checksum
        words = np.concatenate(
            [words, row_checksum(words)[:, None]], axis=1)
    return words


def db_as_bytes(db_words: np.ndarray) -> np.ndarray:
    """[N, W] uint32 -> [N, 4W] uint8 view for the int8-matmul path.

    Compat wrapper over the database plane's host packing primitive
    (works on any [R, W] slice, not just full power-of-two DBs);
    production code keeps the byte view device-resident
    (``ShardedDatabase.view("bytes")``) instead of re-packing on the host.
    """
    from repro.crypto.packing import np_words_to_bytes
    return np_words_to_bytes(np.asarray(db_words))


# ---------------------------------------------------------------------------
# Client
# ---------------------------------------------------------------------------

@dataclass
class Query:
    """A client query: one key pytree per party (k of them).

    Two-server schemes keep the familiar shape ``keys == (k0, k1)``; the
    k-server protocols extend the tuple (one entry per non-colluding party).
    """
    index: int
    keys: Tuple[dpf.DPFKey, ...]


def query_gen(rng: np.random.Generator, index: int, cfg: PIRConfig) -> Query:
    """GENERATEANDSENDKEYS (Algorithm 1 ①-②), via the config's protocol.

    Thin compat wrapper over ``core.protocol``: the registered
    ``PIRProtocol`` named by ``cfg.protocol`` owns key generation.
    """
    from repro.core import protocol as protocol_mod
    proto = protocol_mod.for_config(cfg)
    return Query(index=index, keys=proto.query_gen(rng, index, cfg))


def batch_queries(rng: np.random.Generator, indices: Sequence[int],
                  cfg: PIRConfig) -> Tuple[dpf.DPFKey, ...]:
    """Generate and stack a batch of queries into per-party batched pytrees.

    Returns one batched key pytree per party (two for the 2-server
    protocols, ``cfg.n_servers`` for ``xor-dpf-k``).
    """
    qs = [query_gen(rng, i, cfg) for i in indices]
    n_parties = len(qs[0].keys)
    return tuple(dpf.stack_keys([q.keys[p] for q in qs])
                 for p in range(n_parties))


def reconstruct_xor(r0, r1) -> np.ndarray:
    """D[i] = r1 XOR r2 (Algorithm 1, client ⑦), on the host."""
    from repro.core import protocol as protocol_mod
    return protocol_mod.get("xor-dpf-2").reconstruct([r0, r1])


def reconstruct_additive(r0, r1) -> np.ndarray:
    """D[i] bytes = (r0 + r1) mod 256 (int32 partial sums from the matmul),
    on the host."""
    from repro.core import protocol as protocol_mod
    return protocol_mod.get("additive-dpf-2").reconstruct([r0, r1])


# ---------------------------------------------------------------------------
# Server: reference (single-shard) answer paths
# ---------------------------------------------------------------------------

def xor_fold(rows: jax.Array, axis: int = 0) -> jax.Array:
    """XOR-reduce along ``axis`` (the paper's MASTERXOR stage)."""
    return jax.lax.reduce(rows, np.uint32(0), jax.lax.bitwise_xor, (axis,))


def dpxor(db_words: jax.Array, bits: jax.Array) -> jax.Array:
    """Select-XOR scan: r = ⊕_{j : bits[j]=1} D[j]  (Algorithm 1 ④-⑤).

    Pure-jnp reference; the Pallas kernel (kernels/dpxor.py) implements the
    tiled two-stage parallel-reduction form of the same contraction.
    """
    masked = jnp.where((bits != 0)[:, None], db_words, U32(0))
    return xor_fold(masked, 0)


def answer_xor(db_words: jax.Array, key: dpf.DPFKey) -> jax.Array:
    """Full single-server answer, one query: Eval + dpXOR."""
    n = db_words.shape[0]
    log_n = (n - 1).bit_length()
    _, t = dpf.eval_range(key, 0, log_n)
    return dpxor(db_words, t[:n])


def answer_xor_batch(db_words: jax.Array, keys: dpf.DPFKey) -> jax.Array:
    """Batched XOR answers: [Q, W]."""
    return jax.vmap(lambda k: answer_xor(db_words, k))(keys)


def answer_additive_matmul(db_bytes_i8: jax.Array, shares_u8: jax.Array
                           ) -> jax.Array:
    """Batched additive answers as one int8 GEMM.

    shares_u8: [Q, N] Z_256 shares; db_bytes_i8: [N, L] DB bytes (int8 view).
    Returns int32 partial results [Q, L]; only their value mod 256 matters,
    and int32 wraparound preserves it (2^8 | 2^32).
    """
    return jax.lax.dot_general(
        shares_u8.astype(jnp.int8), db_bytes_i8.astype(jnp.int8),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )


def answer_additive_batch(db_bytes_i8: jax.Array, keys: dpf.DPFKey
                          ) -> jax.Array:
    """Eval byte shares for each key then contract against the DB."""
    n = db_bytes_i8.shape[0]
    log_n = (n - 1).bit_length()
    shares = dpf.eval_bytes_batch(keys, 0, log_n)[:, :n]
    return answer_additive_matmul(db_bytes_i8, shares)


# ---------------------------------------------------------------------------
# Phase-split forms (paper Table 1 instrumentation)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("log_n",))
def phase_eval_bits(keys: dpf.DPFKey, log_n: int) -> jax.Array:
    """Phase ②: DPF evaluation only — materializes Eval(k, ·) bit vectors."""
    return dpf.eval_bits_batch(keys, 0, log_n)


@jax.jit
def phase_dpxor(db_words: jax.Array, bits: jax.Array) -> jax.Array:
    """Phase ④-⑤: dpXOR only, given precomputed selection bits [Q, N]."""
    return jax.vmap(lambda b: dpxor(db_words, b))(bits)
