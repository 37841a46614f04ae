"""The protocol plane: pluggable PIR schemes + kernel-path execution plans.

The paper's architecture (§3) is multi-*server* PIR, but everything that
varies between schemes used to hide inside a ``mode="xor"|"additive"``
string branched on across three layers. This module is the seam that
replaces it (DESIGN.md §7):

``PIRProtocol``  what the *parties* compute — key generation, the per-shard
                 answer contraction, the cross-shard reduction algebra, and
                 client-side reconstruction. One implementation per share
                 scheme; a registry (mirroring ``models/registry.py``
                 dispatch) maps names to instances.

``ExecutionPlan``  *how* one answer step runs — which expansion strategy
                 (materialize selection bits vs fused chunked expand+scan),
                 which scan kernel (pure-jnp oracle vs the Pallas
                 ``dpxor``/``pir_matmul`` bodies), and which aggregation
                 collective. The engine plane picks one per (db size, batch
                 bucket, backend): ``repro.engine.resolve``.

Registered protocols
--------------------
xor-dpf-2       the paper's two-server XOR scheme: one GGM DPF pair,
                selection bits weight an XOR fold over DB rows.
additive-dpf-2  two-server Z_256 additive shares; a query batch is one
                int8 GEMM against the byte-viewed DB (the MXU
                operational-intensity lever, beyond-paper).
xor-dpf-k       k>=2 servers, k-of-k XOR shares (beyond-paper, 1-private):
                one real DPF pair (parties 0, 1) blinded by a ring of
                pairwise-shared GGM mask seeds — party i expands masks
                m(s_i) and m(s_{(i+1) mod k}), so every seed is held by
                exactly two parties and every mask cancels in the
                XOR over all k answers while each single server sees only
                pseudorandom selection vectors. Every party scans the full
                DB (equal work), and reconstruction is XOR over all k
                answer shares. k = ``PIRConfig.n_servers``.
lwe-simple-1    single-server SimplePIR-style LWE PIR (beyond-paper,
                DESIGN.md §10): the client ships one LWE-encrypted one-hot
                vector, the server answers with an int32 GEMM over the byte
                DB, and reconstruction subtracts ``s^T.H`` against a
                preprocessed hint ``H = A^T.DB`` (seeded A, never shipped)
                before a modulus switch. No non-collusion assumption;
                reconstruction needs per-query client state + the hint, so
                sessions go through ``reconstruct_with``/``query_gen_full``.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import PIRConfig
from repro.core import dpf
from repro.core.pir import answer_additive_matmul, dpxor, xor_fold
from repro.crypto.chacha import PRG_ROUNDS
from repro.db.spec import IntegrityError, verify_records

U32 = jnp.uint32


# ---------------------------------------------------------------------------
# Execution plans: the kernel-path axis, decoupled from the share scheme
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ExecutionPlan:
    """How one compiled answer step executes (DESIGN.md §7.3, §9).

    expand     "materialize": phase-split — Eval(k,·) selection vectors are
               written out, then scanned (the paper's host-eval structure).
               "fused": chunked expand+scan; selection bits never round-trip
               through HBM (XOR protocols; the GEMM ignores it).
               "fused-pallas": the megakernel (``kernels/fused_scan.py``) —
               one Pallas program expands each DB tile's GGM leaves from
               precomputed chunk roots and folds the tile immediately,
               streaming the DB through double-buffered DMA. Available for
               XOR *and* additive protocols (the additive body reproduces
               the int8 GEMM bit-exactly in-kernel).
    scan       "jnp": the pure-jnp oracle contraction (also the GSPMD
               dry-run path). "pallas": the tiled kernel bodies —
               ``kernels/dpxor.py`` for XOR scans, ``kernels/pir_matmul.py``
               for the additive GEMM.
    chunk_log  fused path: log2 leaves per expand+scan chunk.
    collective "gather" | "butterfly": XOR all-reduce shape over the DB-shard
               axis (additive protocols psum natively and ignore this).

    Tile fields (the engine plane, DESIGN.md §9): the VMEM tilings that
    used to be hardcoded constants in ``kernels/ops.py``. Defaults are the
    pre-engine constants; the autotuner (``engine/tuner.py``) replaces
    them with measured winners. Requested tiles are *legalized* against
    the concrete shapes at kernel entry (``engine.legal_tile``), so a plan
    tuned at one shape stays valid at another.

    tile_r     rows staged through VMEM per grid step: the Pallas scan's
               row tile (``dpxor``, pre-engine 2048) / the GEMM's
               reduction tile (``pir_matmul``, pre-engine 1024).
    tile_q     GEMM query-batch tile (sublane dim).
    tile_l     GEMM record-byte tile (lane dim).
    depth      fused-pallas: rotating DMA buffer count (2 = classic double
               buffer; other paths ignore it).
    provenance "heuristic" (rule-picked fallback) | "tuned" (measured
               winner from the plan cache) | "warm" (recorded from a
               peer, ``engine.record_plans``). Excluded from
               equality/hashing: two plans that execute identically
               compare equal regardless of how they were chosen.
    """
    expand: str = "materialize"
    scan: str = "jnp"
    chunk_log: int = 12
    collective: str = "gather"
    tile_r: int = 2048
    tile_q: int = 8
    tile_l: int = 128
    depth: int = 2
    provenance: str = field(default="heuristic", compare=False)

    @property
    def name(self) -> str:
        return f"{self.expand}/{self.scan}"


# ---------------------------------------------------------------------------
# Protocol interface
# ---------------------------------------------------------------------------

class PIRProtocol:
    """One PIR scheme: what each of the n parties computes.

    Implementations are stateless; all shapes come from the ``PIRConfig``
    and the key pytrees themselves. ``answer_local`` runs *inside*
    shard_map (one DB shard), so it must be pure traced jax.
    """

    name: str = ""
    share_kind: str = "xor"            # xor | additive | lwe (reduction algebra)
    #: which ShardedDatabase view the contraction consumes (db/spec.py
    #: VIEWS): "words" (u32, XOR scan) | "bytes" (int8, the GEMM) |
    #: "bytes32" (int32 bytes, the LWE GEMM). The database plane serves the
    #: declared view; protocols never convert inline inside the compiled step.
    db_view: str = "words"
    #: hint protocols (single-server LWE) need server-side preprocessing
    #: H(db) shipped to clients once per epoch; the session layer
    #: (``SingleServerPIR``) registers ``hint_builder`` with the database
    #: plane and routes reconstruction through ``reconstruct_with``.
    needs_hint: bool = False

    # -- client side ----------------------------------------------------
    def n_parties(self, cfg: PIRConfig) -> int:
        raise NotImplementedError

    def query_gen(self, rng: np.random.Generator, index: int,
                  cfg: PIRConfig) -> Tuple[dpf.DPFKey, ...]:
        """Gen: one per-party key pytree per party, for one query index."""
        raise NotImplementedError

    def query_gen_full(self, rng: np.random.Generator, index: int,
                       cfg: PIRConfig):
        """Gen with client state: ``(keys_tuple, state)``.

        Stateless protocols (all the DPF schemes) carry no client state;
        hint protocols return the per-query secret the reconstruction
        needs. Sessions that support hint protocols call this form.
        """
        return self.query_gen(rng, index, cfg), None

    def reconstruct(self, answers: Sequence[np.ndarray]) -> np.ndarray:
        """Combine all parties' answer shares into the record, on the host
        (device arrays are fetched first)."""
        raise NotImplementedError

    def reconstruct_with(self, answers: Sequence[jax.Array], states, *,
                         cfg: Optional[PIRConfig] = None, hint=None):
        """Reconstruction with per-query client state + epoch hint.

        The general client-side entry point: stateless protocols ignore
        ``states``/``hint`` and defer to :meth:`reconstruct`; hint
        protocols require both. When the config enables verified
        reconstruction (``cfg.checksum``), the combined records are routed
        through :meth:`verify_reconstruction` — a corrupted answer share
        raises :class:`~repro.db.spec.IntegrityError` here instead of
        decoding to silent garbage (DESIGN.md §12).
        """
        rec = self.reconstruct(answers)
        if cfg is not None and getattr(cfg, "checksum", False):
            rec = self.verify_reconstruction(rec, cfg)
        return rec

    def verify_reconstruction(self, rec, cfg: PIRConfig) -> np.ndarray:
        """Check reconstructed stored-width records against their per-row
        checksum column and strip it, returning the logical payload.

        Works for every share algebra because the check runs on the
        *reconstructed* records, not the shares: XOR schemes hand in
        ``[Q, item_words+1]`` u32 rows, byte schemes (additive, LWE)
        ``[Q, item_bytes+4]`` byte rows with the checksum word little-
        endian in the trailing 4 bytes. Raises ``IntegrityError`` naming
        the offending batch indices on any mismatch.
        """
        return verify_records(np.asarray(rec), cfg.item_bytes)

    def record_struct(self, cfg: PIRConfig) -> Tuple[Tuple[int, ...], type]:
        """(shape tail, dtype) of one reconstructed record — XOR schemes
        return u32 words, additive schemes Z_256 bytes."""
        if self.share_kind == "additive":
            return (cfg.item_bytes,), np.uint8
        return (cfg.item_bytes // 4,), np.uint32

    # -- server side ----------------------------------------------------
    def key_specs(self, cfg: PIRConfig, n_queries: int, *, party: int = 0):
        """ShapeDtypeStruct stand-ins for a batched key pytree (dry-run
        input). Aux data (party, rounds) must match real keys exactly for
        treedef-sensitive uses (per-bucket jit in_shardings)."""
        raise NotImplementedError

    def answer_local(self, db_local: jax.Array, keys_local,
                     start_block, log_local: int,
                     plan: ExecutionPlan) -> jax.Array:
        """One shard's partial answers for a batch of keys.

        ``db_local`` is the [rows_local, ...] shard of this protocol's
        declared ``db_view`` (u32 words for XOR schemes, int8 bytes for
        additive); ``start_block`` its shard index (leaf range
        [start_block * rows_local, ...)).
        """
        raise NotImplementedError

    def reduce(self, partial_res: jax.Array, axis: str, n_shards: int,
               plan: ExecutionPlan) -> jax.Array:
        """Cross-shard reduction of partial answers over mesh axis ``axis``."""
        raise NotImplementedError

    # -- hint lifecycle (hint protocols only) ---------------------------
    def hint_builder(self, cfg: PIRConfig):
        """Device fn: words view ``[N, W]`` -> hint array (full rebuild)."""
        raise NotImplementedError(f"{self.name} has no hint")

    def hint_delta(self, cfg: PIRConfig):
        """Device fn: (hint, rows, old_words, new_words) -> updated hint,
        exact (byte-for-byte equal to a full rebuild). None if the
        protocol's hint only supports full recompute."""
        return None

    # -- batching (shared defaults) -------------------------------------
    def pad(self, keys, n_total: int):
        """Pad a batched key pytree up to its bucket (DESIGN.md §6 rule)."""
        return dpf.pad_keys(keys, n_total)

    def n_queries(self, keys) -> int:
        return dpf.n_queries_of(keys)


# ---------------------------------------------------------------------------
# Registry (models/registry.py idiom: names -> implementations)
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, PIRProtocol] = {}


def register(proto: PIRProtocol) -> PIRProtocol:
    if not proto.name:
        raise ValueError("protocol must carry a name")
    _REGISTRY[proto.name] = proto
    return proto


def get(name: str) -> PIRProtocol:
    if name not in _REGISTRY:
        raise KeyError(
            f"unknown protocol {name!r}; registered: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def available() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def for_config(cfg: PIRConfig) -> PIRProtocol:
    """The protocol a config names (``PIRConfig.protocol``; the deprecated
    ``mode=`` strings are aliased to registry names by the config shim)."""
    return get(cfg.protocol)


# ---------------------------------------------------------------------------
# XOR scan helpers shared by the XOR protocols
# ---------------------------------------------------------------------------

def xor_allreduce_gather(partial_res: jax.Array, axis: str) -> jax.Array:
    """XOR all-reduce via all_gather + local fold (paper's host aggregation)."""
    gathered = jax.lax.all_gather(partial_res, axis)          # [P, ...]
    return xor_fold(gathered, 0)


def xor_allreduce_butterfly(partial_res: jax.Array, axis: str, size: int
                            ) -> jax.Array:
    """XOR all-reduce via a recursive-doubling butterfly (log P ppermutes).

    Collective-study alternative for §Perf: moves the same bytes in log P
    rounds of pairwise exchange instead of one P-way gather.
    """
    x = partial_res
    shift = 1
    while shift < size:
        perm = [(i, i ^ shift) for i in range(size)]
        x = x ^ jax.lax.ppermute(x, axis, perm)
        shift <<= 1
    return x


def _xor_scan(db_local: jax.Array, bits: jax.Array,
              plan: ExecutionPlan) -> jax.Array:
    """[R, W] db x [Q, R] bits -> [Q, W], jnp oracle or the Pallas body."""
    if plan.scan == "pallas":
        from repro.kernels import ops
        return ops.dpxor(db_local, bits, tile_r=plan.tile_r)
    return jax.vmap(lambda b: dpxor(db_local, b))(bits)


def _xor_reduce(partial_res: jax.Array, axis: str, n_shards: int,
                plan: ExecutionPlan) -> jax.Array:
    if plan.collective == "butterfly":
        return xor_allreduce_butterfly(partial_res, axis, n_shards)
    return xor_allreduce_gather(partial_res, axis)


def _dpf_key_specs(cfg: PIRConfig, n_queries: int, *, party: int,
                   with_payload: bool,
                   components: Optional[int] = None) -> dpf.DPFKey:
    """Batched DPFKey ShapeDtypeStructs, optionally with a component axis."""
    log_n = cfg.log_n
    lead = (n_queries,) if components is None else (n_queries, components)
    mk = lambda *s: jax.ShapeDtypeStruct(lead + s, np.uint32)
    return dpf.DPFKey(
        party=party, log_n=log_n,
        root_seed=mk(4), cw_seed=mk(log_n, 4), cw_t=mk(log_n, 2),
        cw_final=mk(1) if with_payload else None,
        rounds=PRG_ROUNDS.get(cfg.prf, 12),
    )


# ---------------------------------------------------------------------------
# xor-dpf-2: the paper's two-server scheme
# ---------------------------------------------------------------------------

class _XorProtocol(PIRProtocol):
    """Shared XOR share algebra: reduction collective + XOR reconstruct."""

    share_kind = "xor"

    def reduce(self, partial_res, axis, n_shards, plan):
        return _xor_reduce(partial_res, axis, n_shards, plan)

    def reconstruct(self, answers):
        out = np.asarray(answers[0])
        for a in answers[1:]:
            out = np.bitwise_xor(out, np.asarray(a))
        return out


class XorDpf2(_XorProtocol):
    """Two-server XOR PIR over one GGM DPF pair (paper §2.3, Algorithm 1)."""

    name = "xor-dpf-2"

    def n_parties(self, cfg: PIRConfig) -> int:
        return 2

    def query_gen(self, rng, index, cfg):
        rounds = PRG_ROUNDS[cfg.prf]
        return dpf.gen_keys(rng, index, cfg.log_n, rounds=rounds)

    def key_specs(self, cfg, n_queries, *, party=0):
        return _dpf_key_specs(cfg, n_queries, party=party, with_payload=False)

    def answer_local(self, db_local, keys_local, start_block, log_local,
                     plan):
        if plan.expand == "materialize":
            # Phase ②③ then ④⑤: Eval bits out, then the select-XOR scan.
            bits = dpf.eval_bits_batch(keys_local, start_block, log_local)
            return _xor_scan(db_local, bits, plan)
        if plan.expand == "fused":
            return _fused_xor_answer(db_local, keys_local, start_block,
                                     log_local, plan, _bits_of_key)
        if plan.expand == "fused-pallas":
            return _fused_pallas_xor_answer(db_local, keys_local,
                                            start_block, log_local, plan)
        raise ValueError(f"unknown expand {plan.expand!r}")


def _bits_of_key(key: dpf.DPFKey, block, log_range: int) -> jax.Array:
    """Selection bits of one plain DPF key over one leaf block."""
    _, t = dpf.eval_range(key, block, log_range)
    return dpf.leaf_bits(t)


def _fused_xor_answer(db_local, keys_local, start_block, log_local, plan,
                      bits_fn) -> jax.Array:
    """Chunked expand+scan (lax.scan over subtree blocks): per chunk,
    descend to the chunk subtree root and fold its rows immediately — the
    selection bits never round-trip through HBM."""
    rows_local = db_local.shape[0]
    words = db_local.shape[1]
    n_chunks = max(1, rows_local >> plan.chunk_log)
    clog = min(plan.chunk_log, log_local)
    db_c = db_local.reshape(n_chunks, rows_local // n_chunks, words)

    def one_query(key):
        def body(acc, c):
            blk = start_block * n_chunks + c
            bits = bits_fn(key, blk, clog)
            acc = acc ^ dpxor(db_c[c], bits)
            return acc, ()
        acc0 = jnp.zeros((words,), U32)
        acc, _ = jax.lax.scan(body, acc0,
                              jnp.arange(n_chunks, dtype=jnp.uint32))
        return acc

    return jax.vmap(one_query)(keys_local)


def _fused_pallas_inputs(keys_local, start_block, log_local: int,
                         rows_local: int, plan: ExecutionPlan):
    """Marshal batched DPF keys into the megakernel's chunk-root form.

    Legalizes (tile_r, chunk_log) exactly as the kernel entry point will
    (``ops.fused_tile`` — the slice of correction-word levels must agree
    with the expansion depth the kernel runs), descends every key once to
    the chunk-root level (shared across chunks, unlike the chunked-jnp
    path's per-chunk re-descent), and slices out the last ``clog`` levels
    of correction words the kernel needs in VMEM.
    """
    from repro.kernels import ops
    tile, clog = ops.fused_tile(rows_local, plan.tile_r,
                                min(plan.chunk_log, log_local))
    roots = dpf.eval_roots_batch(keys_local, start_block, log_local, clog)
    log_n = keys_local.log_n
    cw_seed_lv = keys_local.cw_seed[:, log_n - clog:, :]
    cw_t_lv = keys_local.cw_t[:, log_n - clog:, :]
    return tile, roots, cw_seed_lv, cw_t_lv


def _fused_pallas_xor_answer(db_local, keys_local, start_block, log_local,
                             plan: ExecutionPlan) -> jax.Array:
    """Megakernel XOR answer: expand-in-kernel + double-buffered DB stream.

    ``keys_local`` is a batched plain DPFKey pytree ([Q, ...] leaves).
    """
    from repro.kernels import ops
    tile, roots, cw_s, cw_t = _fused_pallas_inputs(
        keys_local, start_block, log_local, db_local.shape[0], plan)
    return ops.fused_scan_xor(db_local, roots, cw_s, cw_t,
                              tile_r=tile, depth=plan.depth)


def _fused_pallas_xor_k_answer(db_local, keys_local, start_block, log_local,
                               plan: ExecutionPlan) -> jax.Array:
    """Megakernel answer for component-stacked keys ([Q, C, ...] leaves).

    AND distributes over XOR, so running the kernel on the Q·C flattened
    pseudo-queries and XOR-folding the answers over the component axis
    equals scanning with the XOR-folded selection bits.
    """
    q = keys_local.root_seed.shape[0]
    flat = jax.tree_util.tree_map(
        lambda x: x.reshape((-1,) + x.shape[2:]), keys_local)
    ans = _fused_pallas_xor_answer(db_local, flat, start_block, log_local,
                                   plan)
    return xor_fold(ans.reshape((q, -1) + ans.shape[1:]), 1)


def _fused_pallas_add_answer(db_local, keys_local, start_block, log_local,
                             plan: ExecutionPlan) -> jax.Array:
    """Megakernel additive answer: in-kernel share conversion + select-add,
    bit-identical int32 to the materialized int8 GEMM."""
    from repro.kernels import ops
    tile, roots, cw_s, cw_t = _fused_pallas_inputs(
        keys_local, start_block, log_local, db_local.shape[0], plan)
    return ops.fused_scan_bytes(db_local, roots, cw_s, cw_t,
                                keys_local.cw_final[:, 0],
                                party=keys_local.party, tile_r=tile,
                                depth=plan.depth)


# ---------------------------------------------------------------------------
# additive-dpf-2: Z_256 shares -> one int8 GEMM per batch (beyond-paper)
# ---------------------------------------------------------------------------

class AdditiveDpf2(PIRProtocol):
    """Two-server additive PIR: Z_256 byte shares, batched-query GEMM.

    A batch of Q queries against one DB shard is one int8 matrix product
    ``shares[Q, R] x db[R, L]`` — the DB is read once per *batch*, not per
    query, multiplying operational intensity by Q (DESIGN.md §2,
    kernels/pir_matmul.py). Answers are int32 byte-columns; only their
    value mod 256 matters, so int32 wraparound preserves it. The int8
    byte view of the DB comes from the database plane (``db_view``) —
    it is resident and incrementally maintained, not re-derived from the
    word store inside every serve step.
    """

    name = "additive-dpf-2"
    share_kind = "additive"
    db_view = "bytes"

    def n_parties(self, cfg: PIRConfig) -> int:
        return 2

    def query_gen(self, rng, index, cfg):
        rounds = PRG_ROUNDS[cfg.prf]
        return dpf.gen_keys(
            rng, index, cfg.log_n,
            payload=np.array([1], np.uint32), payload_mod=256, rounds=rounds,
        )

    def key_specs(self, cfg, n_queries, *, party=0):
        return _dpf_key_specs(cfg, n_queries, party=party, with_payload=True)

    def answer_local(self, db_local, keys_local, start_block, log_local,
                     plan):
        # db_local is already the int8 byte view [rows_local, item_bytes]
        if plan.expand == "fused-pallas":
            return _fused_pallas_add_answer(db_local, keys_local,
                                            start_block, log_local, plan)
        shares = dpf.eval_bytes_batch(keys_local, start_block, log_local)
        if plan.scan == "pallas":
            from repro.kernels import ops
            return ops.pir_gemm(shares.astype(jnp.int8), db_local,
                                tile_q=plan.tile_q, tile_r=plan.tile_r,
                                tile_l=plan.tile_l)
        return answer_additive_matmul(db_local, shares)

    def reduce(self, partial_res, axis, n_shards, plan):
        return jax.lax.psum(partial_res, axis)   # additive: native psum

    def reconstruct(self, answers):
        acc = np.asarray(answers[0]).astype(np.int32)
        for a in answers[1:]:
            acc = acc + np.asarray(a).astype(np.int32)
        return (acc % 256).astype(np.uint8)


# ---------------------------------------------------------------------------
# xor-dpf-k: k >= 2 servers, k-of-k XOR shares (beyond-paper)
# ---------------------------------------------------------------------------

class XorDpfK(_XorProtocol):
    """k-server XOR PIR: one DPF pair blinded by a ring of shared masks.

    Construction (1-private, k-of-k reconstruct; DESIGN.md §7.2): draw
    mask seeds s_0..s_{k-1}; party i expands masks m(s_i) and
    m(s_{(i+1) mod k}) — plain (correction-free) GGM trees, so two parties
    holding the same seed derive the *same* pseudorandom selection vector.
    Parties 0 and 1 additionally hold the real DPF pair (d_0, d_1) for the
    queried index. Each seed appears at exactly two parties, so the XOR of
    all k selection vectors is Eval(d_0) ^ Eval(d_1) = e_alpha, while any
    single party sees only a DPF key and/or fresh random seeds — nothing
    about alpha. Every party's vector is dense pseudorandom, so all k
    servers do identical full-scan work (no idle replicas).

    Per-party keys are batched ``DPFKey`` pytrees with a leading *component*
    axis (3 components for parties 0/1: real key + two masks; 2 for the
    rest), evaluated per component and XOR-folded. k=2 degenerates to the
    two-server scheme (the shared masks cancel pairwise).
    """

    name = "xor-dpf-k"

    def n_parties(self, cfg: PIRConfig) -> int:
        if cfg.n_servers < 2:
            raise ValueError(f"xor-dpf-k needs n_servers >= 2, "
                             f"got {cfg.n_servers}")
        return cfg.n_servers

    @staticmethod
    def _n_components(party: int) -> int:
        return 3 if party < 2 else 2

    def query_gen(self, rng, index, cfg):
        k = self.n_parties(cfg)
        rounds = PRG_ROUNDS[cfg.prf]
        log_n = cfg.log_n
        d0, d1 = dpf.gen_keys(rng, index, log_n, rounds=rounds)
        seeds = [rng.integers(0, 1 << 32, size=4, dtype=np.uint32)
                 for _ in range(k)]
        zero_cw = np.zeros((log_n, 4), np.uint32)
        zero_t = np.zeros((log_n, 2), np.uint32)

        def mask_key(seed: np.ndarray) -> dpf.DPFKey:
            # zero correction words make eval_range a plain GGM PRG tree:
            # its leaf t-bits depend only on the seed, so both holders of a
            # seed derive identical (cancelling) masks.
            return dpf.DPFKey(party=0, log_n=log_n,
                              root_seed=seed,
                              cw_seed=zero_cw, cw_t=zero_t,
                              cw_final=None, rounds=rounds)

        keys = []
        for i in range(k):
            comps = [d0] if i == 0 else [d1] if i == 1 else []
            comps.append(mask_key(seeds[i]))
            comps.append(mask_key(seeds[(i + 1) % k]))
            # aux party must agree across stacked components
            comps = [replace_party(c, i) for c in comps]
            keys.append(dpf.stack_keys(comps))
        return tuple(keys)

    def key_specs(self, cfg, n_queries, *, party=0):
        return _dpf_key_specs(cfg, n_queries, party=party,
                              with_payload=False,
                              components=self._n_components(party))

    def answer_local(self, db_local, keys_local, start_block, log_local,
                     plan):
        if plan.expand == "materialize":
            bits = _component_bits_batch(keys_local, start_block, log_local)
            return _xor_scan(db_local, bits, plan)
        if plan.expand == "fused":
            return _fused_xor_answer(db_local, keys_local, start_block,
                                     log_local, plan, _component_bits)
        if plan.expand == "fused-pallas":
            return _fused_pallas_xor_k_answer(db_local, keys_local,
                                              start_block, log_local, plan)
        raise ValueError(f"unknown expand {plan.expand!r}")


def replace_party(key: dpf.DPFKey, party: int) -> dpf.DPFKey:
    """A key with its (aux) party id rewritten.

    The party id never enters mask evaluation (with zero correction words
    the initial t-bit multiplies nothing), but pytree aux data must agree
    for components to stack and for ``key_specs`` treedefs to match.
    """
    return dpf.DPFKey(party=party, log_n=key.log_n,
                      root_seed=key.root_seed, cw_seed=key.cw_seed,
                      cw_t=key.cw_t, cw_final=key.cw_final,
                      rounds=key.rounds)


def _component_bits(key: dpf.DPFKey, block, log_range: int) -> jax.Array:
    """XOR-fold of one query's component keys' selection bits (leaves [C,...])."""
    bs = jax.vmap(lambda c: _bits_of_key(c, block, log_range))(key)
    return xor_fold(bs, 0)


@partial(jax.jit, static_argnames=("log_range",))
def _component_bits_batch(keys: dpf.DPFKey, start_block, log_range: int
                          ) -> jax.Array:
    """[Q, C, ...] component keys -> [Q, 2^log_range] folded selection bits.

    jit'd (mirroring ``dpf.eval_bytes_batch``): the doubly-vmapped GGM walk
    is minutes of eager dispatch overhead otherwise.
    """
    return jax.vmap(lambda k: _component_bits(k, start_block, log_range))(keys)


# ---------------------------------------------------------------------------
# lwe-simple-1: single-server SimplePIR-style LWE PIR (beyond-paper)
# ---------------------------------------------------------------------------

class LweSimple1(PIRProtocol):
    """Single-server LWE PIR: encrypted one-hot query, int32 GEMM answer.

    The first protocol with no non-collusion assumption (DESIGN.md §10):
    privacy rests on LWE hardness, not on servers never comparing notes.
    The price is a preprocessed *hint* ``H = A^T.DB`` the client needs at
    reconstruction time — built by the database plane per epoch
    (``ShardedDatabase.register_hint``) and delta-updated on ``publish()``.

    Server hot loop: ``ct[Q, N] x db_bytes32[N, L] -> int32 [Q, L]`` —
    structurally the additive GEMM with int32 operands, so it slots into
    the same engine tile space (``lwe-gemm-*`` descriptors). int32
    accumulation wraps mod 2^32 = mod q natively: the GEMM *is* the Z_q
    contraction, and cross-shard psum (also wrapping) is the Z_q sum.

    Correctness is parameterized, not assumed: ``core/lwe.py`` selects
    (n, sigma) from a validated table and ``LWEParams.validate`` raises
    when the noise bound crosses q/(2p) — see the noise-budget property
    tests. Parameters are demonstration-grade, not a security review.
    """

    name = "lwe-simple-1"
    share_kind = "lwe"
    db_view = "bytes32"
    needs_hint = True

    def _params(self, cfg: PIRConfig):
        from repro.core import lwe
        return lwe.params_for(cfg.n_items)

    # -- client side ----------------------------------------------------
    def n_parties(self, cfg: PIRConfig) -> int:
        return 1

    def query_gen_full(self, rng, index, cfg):
        from repro.core import lwe
        ct, state = lwe.encrypt(rng, index, cfg.n_items, self._params(cfg))
        return (ct,), state

    def query_gen(self, rng, index, cfg):
        # keys without the secret: enough for serve-side tooling (tuner
        # measurement inputs); reconstruction requires query_gen_full.
        return self.query_gen_full(rng, index, cfg)[0]

    def reconstruct(self, answers):
        raise NotImplementedError(
            "lwe-simple-1 reconstruction needs per-query client state and "
            "the epoch hint: use reconstruct_with(answers, states, cfg=..., "
            "hint=...) — sessions route this via SingleServerPIR")

    def reconstruct_with(self, answers, states, *, cfg=None, hint=None):
        from repro.core import lwe
        if cfg is None or hint is None or any(s is None for s in states):
            raise ValueError("lwe-simple-1 reconstruct_with needs cfg=, "
                             "hint= and one client state per query")
        params = self._params(cfg)
        secrets = np.stack([s.s for s in states])
        hint_u64 = np.asarray(hint).view(np.uint32).astype(np.uint64)
        records, err = lwe.decode(np.asarray(answers[0]), secrets, hint_u64,
                                  params)
        # correctness-bound assertion. The recovered residual lands in
        # [-Delta/2, Delta/2) by construction, so comparing it to the
        # budget q/(2p) = Delta/2 would be vacuous; the checkable bound
        # is the analytic tail validate() enforces (well under Delta/2):
        # honest noise sits ~TAIL sigmas inside it, while a wrong hint /
        # mismatched epoch makes the residual near-uniform in the Delta
        # window and trips it with overwhelming probability.
        max_err = int(np.abs(err).max()) if err.size else 0
        bound = params.noise_bound(cfg.n_items)
        if max_err >= bound:
            raise IntegrityError(
                f"LWE noise overflow: recovered |e^T.D| = {max_err} >= "
                f"tail bound {bound:.4g} (budget q/(2p) = "
                f"{params.noise_budget}); the answers do not match this "
                f"hint/epoch — reconstruction is not trustworthy")
        if getattr(cfg, "checksum", False):
            # the noise check alone cannot catch a corruption that shifts
            # an answer by a multiple of Delta (it aliases to a clean
            # plaintext shift); the row checksum closes that gap
            records = self.verify_reconstruction(records, cfg)
        return jnp.asarray(records)

    def record_struct(self, cfg: PIRConfig):
        return (cfg.item_bytes,), np.uint8

    # -- server side ----------------------------------------------------
    def key_specs(self, cfg, n_queries, *, party=0):
        from repro.core.lwe import LWECiphertext
        return LWECiphertext(
            ct=jax.ShapeDtypeStruct((n_queries, cfg.n_items), np.int32),
            log_n=cfg.log_n, n=self._params(cfg).n)

    def answer_local(self, db_local, keys_local, start_block, log_local,
                     plan):
        # db_local is the int32 byte view [rows_local, item_bytes]; slice
        # this shard's ciphertext columns (start_block may be traced).
        rows_local = db_local.shape[0]
        ct = keys_local.ct
        start = start_block * rows_local
        ct_local = jax.lax.dynamic_slice_in_dim(ct, start, rows_local, axis=1)
        if plan.scan == "pallas":
            from repro.kernels import ops
            return ops.lwe_gemm(ct_local, db_local, tile_q=plan.tile_q,
                                tile_r=plan.tile_r, tile_l=plan.tile_l)
        return jax.lax.dot_general(ct_local, db_local,
                                   (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.int32)

    def reduce(self, partial_res, axis, n_shards, plan):
        return jax.lax.psum(partial_res, axis)   # int32 psum wraps mod q

    # -- hint lifecycle -------------------------------------------------
    def hint_builder(self, cfg: PIRConfig):
        from repro.core import lwe
        return lwe.hint_build_fn(self._params(cfg), cfg.n_items)

    def hint_delta(self, cfg: PIRConfig):
        from repro.core import lwe
        return lwe.hint_delta_fn(self._params(cfg), cfg.n_items)

    # -- batching: LWECiphertext is not a DPFKey ------------------------
    def pad(self, keys, n_total: int):
        q = self.n_queries(keys)
        if n_total < q:
            raise ValueError(f"cannot pad {q} queries down to {n_total}")
        if n_total == q:
            return keys
        pad = n_total - q

        def pad_leaf(leaf):
            reps = (pad,) + (1,) * (leaf.ndim - 1)
            return jnp.concatenate([leaf, jnp.tile(leaf[-1:], reps)], axis=0)

        return jax.tree_util.tree_map(pad_leaf, keys)

    def n_queries(self, keys) -> int:
        return int(keys.ct.shape[0])


register(XorDpf2())
register(AdditiveDpf2())
register(XorDpfK())
register(LweSimple1())
