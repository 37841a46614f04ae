"""`DatabaseSpec` — the one owner of PIR database shape/packing math.

Before the database plane, this arithmetic was smeared across four layers:
``core/pir.py db_as_bytes`` re-packed the whole DB on the host per call,
``core/server.py`` and a dry-run launcher each rebuilt the
``(n_items, item_bytes // 4)`` struct by hand, and the additive protocol
converted words to bytes inside every compiled serve step. The spec
centralizes it: record geometry, the two protocol *views* (u32 words for
the XOR schemes, int8 bytes for the additive GEMM), per-shard row math,
and host/device packing conversions (``crypto/packing.py`` primitives).

A view name is protocol metadata (``PIRProtocol.db_view``): the serve
plumbing asks the spec for that view's shape/dtype/struct instead of
branching on the share scheme.

Verified reconstruction (DESIGN.md §12) adds an optional per-row checksum
column: with ``checksum=True`` every stored record carries one extra u32
word (``row_checksum`` of its payload words) packed after the payload, so
all three views widen by 4 bytes per record while ``item_bytes`` remains
the *logical* payload width the client sees. ``verify_records`` checks and
strips that column at reconstruction time, raising :class:`IntegrityError`
on mismatch — a corrupted share can no longer decode to silent garbage.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import numpy as np

from repro.config import PIRConfig
from repro.crypto.packing import (np_bytes_to_words, np_words_to_bytes,
                                  words_to_bytes_i8, words_to_bytes_i32)

#: registered database views: name -> (dtype, bytes-per-record-column)
VIEWS = {
    "words": np.dtype(np.uint32),   # [N, stored_words] — XOR schemes
    "bytes": np.dtype(np.int8),     # [N, stored_bytes] — additive GEMM
    "bytes32": np.dtype(np.int32),  # [N, stored_bytes] — LWE GEMM
    # bytes32 holds the same byte values 0..255 widened to int32: the LWE
    # contraction is mod-2^32 arithmetic, and the int8 view's reinterpreted
    # negatives (byte >= 128 -> byte - 256) would shift it by 256·k ≠ 0 mod q.
}


class IntegrityError(RuntimeError):
    """A reconstructed record failed verification.

    Raised instead of returning a silently wrong record when the stored
    per-row checksum disagrees with the reconstructed payload (a corrupted
    answer share, a byzantine party, bit rot) or, for the LWE protocol,
    when the recovered noise exceeds the validated budget. ``bad_queries``
    carries the batch-local indices of the offending queries so a router
    can resubmit exactly those.
    """

    def __init__(self, msg: str, bad_queries=()):
        super().__init__(msg)
        self.bad_queries = tuple(int(i) for i in bad_queries)


def row_checksum(words: np.ndarray) -> np.ndarray:
    """Per-row u32 mixing checksum over payload words: [..., W] -> [...].

    A murmur3-finalizer-style avalanche per word, folded left-to-right with
    a position-dependent multiply-add so permuting words changes the sum.
    Pure vectorized numpy over the leading axes (O(rows · W) host work —
    the same order as the packing conversions that already run per
    publish). This is an *integrity* check against corruption, not a MAC:
    a malicious server that knows the scheme can forge it (DESIGN.md §12
    spells out the trust-model delta).
    """
    w = np.asarray(words, dtype=np.uint64)
    if w.ndim < 1 or w.shape[-1] == 0:
        raise ValueError(f"need at least one payload word, got shape {w.shape}")
    h = np.full(w.shape[:-1], 0x9E3779B9, dtype=np.uint64)
    for k in range(w.shape[-1]):
        x = (w[..., k] * np.uint64(0x85EBCA6B)) & np.uint64(0xFFFFFFFF)
        x ^= x >> np.uint64(13)
        x = (x * np.uint64(0xC2B2AE35)) & np.uint64(0xFFFFFFFF)
        x ^= x >> np.uint64(16)
        h = ((h ^ x) * np.uint64(0x9E3779B1) + np.uint64(k)) \
            & np.uint64(0xFFFFFFFF)
    return h.astype(np.uint32)


def verify_records(rec: np.ndarray, item_bytes: int) -> np.ndarray:
    """Check + strip the checksum column of reconstructed records.

    Accepts either record form a protocol reconstructs into, both at
    *stored* width (payload + checksum):

    * words form  ``[Q, item_bytes//4 + 1]`` u32 — the XOR schemes;
    * bytes form  ``[Q, item_bytes + 4]`` integer bytes 0..255 (little-
      endian checksum word in the trailing 4 bytes) — additive / LWE.

    Returns the payload (same form, checksum column stripped) or raises
    :class:`IntegrityError` naming the offending batch indices.
    """
    arr = np.asarray(rec)
    if arr.ndim != 2:
        raise ValueError(f"records must be 2-D, got shape {arr.shape}")
    n_words = item_bytes // 4
    if arr.shape[1] == n_words + 1 and arr.dtype == np.uint32:
        payload_words, stored = arr[:, :n_words], arr[:, n_words]
        payload = payload_words
    elif arr.shape[1] == item_bytes + 4:
        b = (arr.astype(np.int64) & 0xFF).astype(np.uint8)
        payload_words = np_bytes_to_words(b[:, :item_bytes])
        stored = np_bytes_to_words(b[:, item_bytes:])[:, 0]
        payload = arr[:, :item_bytes]
    else:
        raise ValueError(
            f"records must be [Q, {n_words + 1}] u32 words or "
            f"[Q, {item_bytes + 4}] bytes (stored width incl. checksum), "
            f"got {arr.shape} {arr.dtype}")
    bad = np.nonzero(row_checksum(payload_words) != stored)[0]
    if bad.size:
        raise IntegrityError(
            f"checksum mismatch on {bad.size}/{arr.shape[0]} reconstructed "
            f"record(s) (batch indices {bad[:8].tolist()}"
            f"{'...' if bad.size > 8 else ''}): corrupted answer share",
            bad_queries=bad)
    return payload


@dataclass(frozen=True)
class DatabaseSpec:
    """Shape/packing math for one PIR database (N records × L bytes).

    ``item_bytes`` is the *logical* payload width; with ``checksum=True``
    each stored record additionally carries one u32 ``row_checksum`` word
    after the payload (``stored_bytes = item_bytes + 4``), and all views /
    shapes are in stored width — verification strips the column again at
    reconstruction.
    """

    n_items: int
    item_bytes: int = 32
    checksum: bool = False

    def __post_init__(self):
        if self.n_items <= 0 or self.n_items & (self.n_items - 1):
            raise ValueError(
                f"n_items must be a power of two (GGM tree domain), "
                f"got {self.n_items}")
        if self.item_bytes % 4:
            raise ValueError(
                f"item_bytes must be a multiple of 4 (u32 words), "
                f"got {self.item_bytes}")

    @classmethod
    def from_config(cls, cfg: PIRConfig) -> "DatabaseSpec":
        return cls(n_items=cfg.n_items, item_bytes=cfg.item_bytes,
                   checksum=getattr(cfg, "checksum", False))

    # -- geometry -------------------------------------------------------

    @property
    def item_words(self) -> int:
        return self.item_bytes // 4

    @property
    def stored_bytes(self) -> int:
        """Bytes per stored record (payload + optional checksum word)."""
        return self.item_bytes + (4 if self.checksum else 0)

    @property
    def stored_words(self) -> int:
        return self.item_words + (1 if self.checksum else 0)

    @property
    def log_n(self) -> int:
        return (self.n_items - 1).bit_length()

    @property
    def db_bytes(self) -> int:
        return self.n_items * self.item_bytes

    def rows_per_shard(self, n_shards: int) -> int:
        """Rows held by one DB shard; validates the paper's linear layout
        (shard d holds rows [d·B_d, (d+1)·B_d), B_d a power of two)."""
        n_shards = max(n_shards, 1)
        if self.n_items % n_shards:
            raise ValueError(
                f"{self.n_items} rows not divisible by {n_shards} shards")
        rows = self.n_items // n_shards
        if rows & (rows - 1):
            raise ValueError(
                f"per-shard row count must be a power of two, got {rows}")
        return rows

    # -- views ----------------------------------------------------------

    def view_dtype(self, view: str) -> np.dtype:
        if view not in VIEWS:
            raise KeyError(f"unknown db view {view!r}; known: {sorted(VIEWS)}")
        return VIEWS[view]

    def view_shape(self, view: str) -> Tuple[int, int]:
        self.view_dtype(view)
        cols = self.stored_words if view == "words" else self.stored_bytes
        return (self.n_items, cols)

    def view_struct(self, view: str) -> jax.ShapeDtypeStruct:
        """ShapeDtypeStruct of one view (dry-run lowering, `.lower` entries)."""
        return jax.ShapeDtypeStruct(self.view_shape(view),
                                    self.view_dtype(view))

    # -- packing --------------------------------------------------------

    def validate_words(self, db_words: np.ndarray) -> np.ndarray:
        arr = np.asarray(db_words)
        if arr.shape != self.view_shape("words") or arr.dtype != np.uint32:
            raise ValueError(
                f"db_words must be {self.view_shape('words')} uint32, got "
                f"{arr.shape} {arr.dtype}")
        return arr

    def attach_checksums(self, words: np.ndarray) -> np.ndarray:
        """Widen payload word rows to stored width: [R, W] -> [R, W+1].

        No-op when ``checksum`` is off or the rows already carry the
        column (idempotent — safe on replayed deltas). O(R) host work.
        """
        arr = np.asarray(words, dtype=np.uint32)
        if not self.checksum or (arr.ndim == 2
                                 and arr.shape[1] == self.stored_words):
            return arr
        if arr.ndim != 2 or arr.shape[1] != self.item_words:
            raise ValueError(
                f"payload rows must be [R, {self.item_words}] u32, got "
                f"{arr.shape}")
        col = row_checksum(arr)[:, None].astype(np.uint32)
        return np.concatenate([arr, col], axis=1)

    def verify_stored_rows(self, rows: np.ndarray) -> np.ndarray:
        """Check stored-width word rows against their checksum column and
        return the logical payload ([R, W+1] -> [R, W]); identity when
        checksums are off. Raises :class:`IntegrityError` on mismatch."""
        arr = np.asarray(rows, dtype=np.uint32)
        if not self.checksum:
            return arr
        return verify_records(arr, self.item_bytes)

    def words_to_bytes_host(self, words: np.ndarray) -> np.ndarray:
        """[..., W] u32 -> [..., 4W] u8 on the host (little-endian)."""
        return np_words_to_bytes(np.asarray(words))

    def bytes_to_words_host(self, b: np.ndarray) -> np.ndarray:
        """[..., 4W] u8 -> [..., W] u32 on the host (little-endian)."""
        return np_bytes_to_words(np.asarray(b, np.uint8))

    def words_to_bytes_device(self, words: jax.Array) -> jax.Array:
        """[..., W] u32 -> [..., 4W] i8 as a traced jax op (the device-side
        view derivation — never a host round trip)."""
        return words_to_bytes_i8(words)

    def words_to_view_device(self, view: str, words: jax.Array) -> jax.Array:
        """Device-side derivation of any registered view from word rows."""
        if view == "words":
            return words
        if view == "bytes":
            return words_to_bytes_i8(words)
        if view == "bytes32":
            return words_to_bytes_i32(words)
        raise KeyError(f"unknown db view {view!r}; known: {sorted(VIEWS)}")

    def pack_host(self, words: np.ndarray, view: str) -> np.ndarray:
        """Host-side packing of word rows into any registered view
        (tuner measurement inputs, test oracles)."""
        if view == "words":
            return np.asarray(words, np.uint32)
        if view == "bytes":
            return self.words_to_bytes_host(words).view(np.int8)
        if view == "bytes32":
            return self.words_to_bytes_host(words).astype(np.int32)
        raise KeyError(f"unknown db view {view!r}; known: {sorted(VIEWS)}")

    def coerce_rows_to_words(self, values: np.ndarray) -> np.ndarray:
        """Normalize update payloads to [R, W] u32 rows.

        Accepts either the word form ``[R, item_words] u32`` or the byte
        form ``[R, item_bytes] u8`` (converted host-side, O(R) work).
        """
        arr = np.asarray(values)
        if arr.ndim != 2:
            raise ValueError(f"row values must be 2-D, got shape {arr.shape}")
        if arr.shape[1] == self.item_bytes and arr.dtype == np.uint8:
            return self.bytes_to_words_host(arr)
        if arr.shape[1] == self.item_words:
            return arr.astype(np.uint32, copy=False)
        raise ValueError(
            f"row values must be [R, {self.item_words}] u32 words or "
            f"[R, {self.item_bytes}] u8 bytes, got {arr.shape} {arr.dtype}")
