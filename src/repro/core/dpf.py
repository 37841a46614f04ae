"""Distributed point functions (DPF) — the cryptographic core of IM-PIR.

Implements the two-party GGM-tree DPF of Gilboa–Ishai [35] with the
Boyle–Gilboa–Ishai correction-word optimization — the same construction the
paper adopts from Lam et al. [61] (§3.1–3.2): each key is a root seed plus
one correction word per tree level (the paper's "two 2-dimensional
codewords C0, C1 ∈ F_{2^λ}^{2×(log N + 1)}").

TPU adaptation (DESIGN.md §2): the paper evaluates the tree on the host CPU
with AES-NI because UPMEM DPUs cannot run AES efficiently and level-by-level
sharing would require inter-DPU communication. Here the PRG is an ARX
permutation (crypto/chacha.py) that vectorizes over 32-bit VPU lanes, so
full-domain evaluation runs *on-device*, breadth-first, one `ggm_double`
call per level — and, crucially, each database shard evaluates only its own
leaf range (`eval_range`): a path descent to the shard's subtree root
followed by local breadth-first expansion. No cross-shard communication,
which is exactly the property the paper could not get from UPMEM.

Output modes
------------
bits   leaf control bits t(j): t0(j) XOR t1(j) = 1{j == alpha}.
       This is the selection vector of the paper's dpXOR stage.
words  additive shares over Z_{2^32}^W: y0(j) + y1(j) = beta * 1{j == alpha}.
bytes  additive shares over Z_256: the MXU-friendly int8 form used by the
       batched-query matmul path (beyond-paper; see kernels/pir_matmul.py).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.crypto.chacha import (LANE_BITS, WORD, chacha_block_packed,
                                 chacha_rows, ggm_double, prg_bits)

U32 = jnp.uint32


@jax.tree_util.register_pytree_node_class
@dataclass
class DPFKey:
    """One party's DPF key (a pytree; vmap-able over a batch of queries).

    Attributes:
      party:     0 or 1 (static).
      log_n:     tree depth = log2(domain size) (static).
      root_seed: [4] uint32 — 128-bit root seed.
      cw_seed:   [log_n, 4] uint32 — per-level seed correction words.
      cw_t:      [log_n, 2] uint32 — per-level (tL, tR) control corrections.
      cw_final:  [W] uint32 / int32 payload correction (None in bit mode).
      rounds:    PRG rounds (static).
    """
    party: int
    log_n: int
    root_seed: jax.Array
    cw_seed: jax.Array
    cw_t: jax.Array
    cw_final: Optional[jax.Array]
    rounds: int = 12

    def tree_flatten(self):
        children = (self.root_seed, self.cw_seed, self.cw_t, self.cw_final)
        aux = (self.party, self.log_n, self.rounds)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        party, log_n, rounds = aux
        root_seed, cw_seed, cw_t, cw_final = children
        return cls(party, log_n, root_seed, cw_seed, cw_t, cw_final, rounds)


# ---------------------------------------------------------------------------
# Key generation (client side; paper Algorithm 1, GENERATEANDSENDKEYS)
# ---------------------------------------------------------------------------

def gen_keys(
    rng: np.random.Generator,
    alpha: int,
    log_n: int,
    *,
    payload: Optional[np.ndarray] = None,
    payload_mod: int = 1 << 32,  # retained for API clarity; arithmetic is native u32 wrap
    rounds: int = 12,
) -> Tuple[DPFKey, DPFKey]:
    """Gen(1^λ, α, β) -> (k0, k1). See module docstring.

    Runs on the host CPU, as the paper's client does: one path of the
    tree is a ChaCha block per party per level (``chacha_block_packed``,
    both parties in one pass), far too little work for a device program,
    and a client's device programs would queue behind the serve step in
    flight. The keys' leaves are ``np.ndarray``; the serve step's one
    ``device_put`` per batch moves them.
    """
    if not (0 <= alpha < (1 << log_n)):
        raise ValueError(f"alpha={alpha} out of domain 2^{log_n}")
    root = [rng.integers(0, 1 << 32, size=4, dtype=np.uint32)
            for _ in (0, 1)]
    # both parties' seed words packed, party b in lane b (LANE_BITS * b)
    hi = LANE_BITS
    both = 1 | (1 << hi)
    s = [int(root[0][w]) | (int(root[1][w]) << hi) for w in range(4)]
    t = [0, 1]                                  # per party: control bit
    cw_seed, cw_t = [], []
    for level in range(log_n):
        bit = (alpha >> (log_n - 1 - level)) & 1
        blk = chacha_block_packed(s, 2, counter=0, rounds=rounds)
        # words 0-3 / 8 are the left child's seed / bit, 4-7 / 9 the right's;
        # a correction word is party 0's word XOR party 1's: the lanes folded
        keep, lose = (4, 0) if bit else (0, 4)
        s_cw = [(x ^ (x >> hi)) & WORD for x in blk[lose:lose + 4]]
        t_l, t_r = ((x ^ (x >> hi)) & 1 for x in blk[8:10])
        t_cw = [t_l ^ bit ^ 1, t_r ^ bit]
        cw_seed.append(s_cw)
        cw_t.append(t_cw)
        corrected = (WORD if t[0] else 0) | ((WORD << hi) if t[1] else 0)
        s = [x ^ ((cw * both) & corrected)
             for x, cw in zip(blk[keep:keep + 4], s_cw)]
        t = [((blk[8 + bit] >> (hi * b)) & 1) ^ (t[b] & t_cw[bit])
             for b in (0, 1)]

    cw_final = None
    if payload is not None:
        # All payload arithmetic is native mod-2^32 uint32 wraparound; the
        # Z_256 byte mode masks with 0xFF at use time (256 | 2^32, so the
        # congruence survives the reduction). The conversion words are
        # prg_bits of each final seed: blocks at counters 1, 2, ...
        beta = np.asarray(payload, dtype=np.uint32)
        n_words = int(beta.shape[-1])
        blocks = [chacha_block_packed(s, 2, counter=ctr, rounds=rounds)
                  for ctr in range(1, 1 + -(-n_words // 16))]
        conv = [np.array([(x >> (hi * b)) & WORD
                          for blk in blocks for x in blk][:n_words],
                         np.uint32) for b in (0, 1)]
        diff = beta - conv[0] + conv[1]
        cw_final = (~diff) + np.uint32(1) if t[1] else diff

    cw_seed = np.array(cw_seed, np.uint32).reshape(log_n, 4)
    cw_t = np.array(cw_t, np.uint32).reshape(log_n, 2)
    return tuple(
        DPFKey(
            party=b,
            log_n=log_n,
            root_seed=root[b],
            cw_seed=cw_seed,
            cw_t=cw_t,
            cw_final=cw_final,
            rounds=rounds,
        )
        for b in (0, 1)
    )


# ---------------------------------------------------------------------------
# Evaluation (server side; paper Algorithm 1, EVALUATEDPF — here on-device)
# ---------------------------------------------------------------------------

def _expand_level(seeds, t_bits, cw_seed_l, cw_t_l, rounds):
    """One breadth-first level: [m,4] seeds -> [2m,4], leaf order preserved."""
    s_l, t_l, s_r, t_r = ggm_double(seeds, rounds=rounds)
    mask = t_bits[:, None] * cw_seed_l[None, :]
    s_l = s_l ^ mask
    s_r = s_r ^ mask
    t_l = t_l ^ (t_bits & cw_t_l[0])
    t_r = t_r ^ (t_bits & cw_t_l[1])
    # interleave children so leaf j sits at index j
    m = seeds.shape[0]
    seeds2 = jnp.stack([s_l, s_r], axis=1).reshape(2 * m, 4)
    t2 = jnp.stack([t_l, t_r], axis=1).reshape(2 * m)
    return seeds2, t2


def _descend(key: DPFKey, start_block, depth: int):
    """Path-descend ``depth`` levels along the bits of ``start_block``
    (MSB first): the corrected seed ``[4]`` and control bit of that
    subtree's root."""
    start_block = jnp.asarray(start_block, U32)
    seeds = key.root_seed
    t = jnp.asarray(key.party, U32)
    for level in range(depth):
        bit = (start_block >> U32(depth - 1 - level)) & U32(1)
        s_l, t_l, s_r, t_r = ggm_double(seeds, rounds=key.rounds)
        s_cw = key.cw_seed[level]
        t_cw = key.cw_t[level]
        s_l = s_l ^ (t * s_cw)
        s_r = s_r ^ (t * s_cw)
        t_l = t_l ^ (t & t_cw[0])
        t_r = t_r ^ (t & t_cw[1])
        seeds = jnp.where(bit, s_r, s_l)
        t = jnp.where(bit, t_r, t_l)
    return seeds, t


def eval_range(
    key: DPFKey,
    start_block: jax.Array | int,
    log_range: int,
) -> Tuple[jax.Array, jax.Array]:
    """Evaluate leaves [start_block * 2^log_range, (start_block+1) * 2^log_range).

    Path-descend ``log_n - log_range`` levels (the bits of ``start_block``,
    MSB first), then breadth-first expand the shard-local subtree. This is
    the shard-parallel form of the paper's EVALUATEDPF: DB shard ``d`` only
    ever computes its own Eval(k, j) slice (paper §3.3 distributes these
    slices from the host; we never materialize the full vector anywhere).

    Returns (seeds [2^log_range, 4] u32, t_bits [2^log_range] u32).
    """
    if log_range > key.log_n:
        raise ValueError("log_range exceeds domain")
    depth = key.log_n - log_range
    seeds, t = _descend(key, start_block, depth)
    seeds = seeds[None, :]
    t = t[None]
    for level in range(depth, key.log_n):
        seeds, t = _expand_level(
            seeds, t, key.cw_seed[level], key.cw_t[level], key.rounds
        )
    return seeds, t


def eval_all(key: DPFKey) -> Tuple[jax.Array, jax.Array]:
    """Full-domain evaluation (single shard / reference path)."""
    return eval_range(key, 0, key.log_n)


def _interleave(left: jax.Array, right: jax.Array, axis: int) -> jax.Array:
    """Children to node order along ``axis``: the two child arrays become
    one with that axis doubled, child b of node i at ``2i + b``."""
    shape = list(left.shape)
    shape[axis] *= 2
    return jnp.stack([left, right], axis=axis + 1).reshape(shape)


#: the chunk-root expansion's last levels grow a major axis of up to
#: 2^_ROW_LEVELS rows instead of the lanes (see eval_roots_batch)
_ROW_LEVELS = 7


@partial(jax.jit, static_argnames=("log_range", "stop_log"))
def eval_roots_batch(keys: DPFKey, start_block, log_range: int,
                     stop_log: int) -> jax.Array:
    """Partial evaluation of a batch of keys at chunk granularity.

    The same descent and breadth expansion as :func:`eval_range` (the same
    ChaCha stream, so parity is by construction), stopped ``stop_log``
    levels above the leaves: the corrected subtree roots of the shard's
    ``C = 2^(log_range - stop_log)`` chunks of ``2^stop_log`` leaves each.
    These are the inputs of the fused-scan megakernel
    (``kernels/fused_scan.py``), which expands the remaining ``stop_log``
    levels in VMEM — one descent shared across all chunks, unlike the
    chunked-jnp fused path which re-descends per chunk.

    Lane-dense: the expansion keeps each seed word as its own array of
    nodes (``chacha_rows``), never ``[C, 4]``, whose 4-word minor axis a
    TPU pads to 128 lanes (32x the bytes). The first levels interleave
    children along the lanes; the last ``_ROW_LEVELS`` along a major axis,
    since a lane interleave passes through a ``[..., 2]`` array (64x) at
    full width. One transpose of ``[2^7, C / 2^7]`` puts the chunks in
    order. The query axis is explicit rather than vmapped: the serve step
    traces this once per bucket and party, and vmap doubles that time.

    Returns ``[Q, 5, C]`` u32 — per query, rows 0-3 the seed words of its
    chunk roots, row 4 their control bits.
    """
    if log_range > keys.log_n:
        raise ValueError("log_range exceeds domain")
    if not (0 <= stop_log <= log_range):
        raise ValueError(f"stop_log={stop_log} outside [0, {log_range}]")
    depth = keys.log_n - log_range
    stop = keys.log_n - stop_log
    seeds, t = jax.vmap(lambda k: _descend(k, start_block, depth))(keys)
    q = t.shape[0]
    rows = [seeds[:, w].reshape(q, 1, 1) for w in range(4)]
    t = t.reshape(q, 1, 1)
    for level in range(depth, stop):
        axis = 1 if stop - level <= _ROW_LEVELS else 2
        out = chacha_rows(rows, counter=0, rounds=keys.rounds)
        s_cw = keys.cw_seed[:, level, :, None, None]         # [Q, 4, 1, 1]
        t_cw = keys.cw_t[:, level, :, None, None]            # [Q, 2, 1, 1]
        rows = [_interleave(out[w] ^ (t * s_cw[:, w]),
                            out[4 + w] ^ (t * s_cw[:, w]), axis)
                for w in range(4)]
        t = _interleave((out[8] & U32(1)) ^ (t & t_cw[:, 0]),
                        (out[9] & U32(1)) ^ (t & t_cw[:, 1]), axis)
    # node (q, i, h) is chunk h * 2^(row levels) + i of query q
    return jnp.stack(rows + [t], axis=1).transpose(0, 1, 3, 2).reshape(
        q, 5, -1)


def leaf_bits(t_bits: jax.Array) -> jax.Array:
    """Selection bits for the dpXOR stage (paper's Eval(k, j) values)."""
    return t_bits.astype(U32)


def leaf_words(
    key: DPFKey, seeds: jax.Array, t_bits: jax.Array, n_words: int
) -> jax.Array:
    """Additive payload shares over Z_{2^32}^W.

    y_b(j) = (-1)^b * (convert(s_j) + t_j * cw_final)  mod 2^32.
    Σ_b y_b(j) = β · 1{j == α}.
    """
    if key.cw_final is None:
        raise ValueError("key was generated without a payload")
    conv = prg_bits(seeds, n_words, rounds=key.rounds)
    share = conv + t_bits[:, None] * key.cw_final[None, :n_words]
    if key.party == 1:
        share = (~share) + U32(1)  # negate mod 2^32
    return share


def leaf_bytes(
    key: DPFKey, seeds: jax.Array, t_bits: jax.Array
) -> jax.Array:
    """Additive scalar shares over Z_256 (int8) — MXU matmul form.

    Requires the key to be generated with ``payload=[1]`` and
    ``payload_mod=256``; uses word 0 of the conversion PRG.
    """
    if key.cw_final is None:
        raise ValueError("key was generated without a payload")
    conv = prg_bits(seeds, 1, rounds=key.rounds)[:, 0] & U32(0xFF)
    share = (conv + t_bits * (key.cw_final[0] & U32(0xFF))) & U32(0xFF)
    if key.party == 1:
        share = (U32(256) - share) & U32(0xFF)
    return share.astype(jnp.uint8)


def eval_bits_batch(keys: DPFKey, start_block, log_range) -> jax.Array:
    """vmap'd selection-bit evaluation for a batch of stacked keys.

    ``keys``: DPFKey with leading query axis on all array leaves.
    Returns ``[Q, 2^log_range] uint32`` selection bits.
    """
    def one(k):
        _, t = eval_range(k, start_block, log_range)
        return leaf_bits(t)

    return jax.vmap(one)(keys)


def stack_keys(keys) -> DPFKey:
    """Stack a list of same-shape keys into one batched pytree.

    Host leaves (``gen_keys``' numpy arrays) stack on the host, so a batch
    reaches the device in the serve step's one ``device_put``; device
    leaves (the LWE ciphertexts of ``lwe-simple-1``) stack on the device.
    """
    def stack(*xs):
        return (np.stack if isinstance(xs[0], np.ndarray) else jnp.stack)(xs)

    return jax.tree_util.tree_map(stack, *keys)


def n_queries_of(keys: DPFKey) -> int:
    """Leading (query) axis length of a batched key pytree."""
    return int(keys.root_seed.shape[0])


def pad_keys(keys: DPFKey, n_total: int) -> DPFKey:
    """Pad a batched key pytree to ``n_total`` queries along the batch axis.

    Pad slots replicate the last real key: every padded slot is a *valid*
    DPF key, so the serve step evaluates it like any other query and the
    extra answers are simply discarded by the caller (DESIGN.md §6 padding
    rule). Because each query's answer is an independent vmap lane, padding
    can never corrupt the real answers. Runs on the host, like
    :func:`stack_keys` for Gen's keys.
    """
    q = n_queries_of(keys)
    if n_total < q:
        raise ValueError(f"cannot pad {q} queries down to {n_total}")
    if n_total == q:
        return keys
    pad = n_total - q

    def pad_leaf(leaf):
        return np.concatenate([leaf, np.repeat(leaf[-1:], pad, axis=0)])

    return jax.tree_util.tree_map(pad_leaf, keys)


@partial(jax.jit, static_argnames=("log_range",))
def eval_bytes_batch(keys: DPFKey, start_block, log_range: int) -> jax.Array:
    """vmap'd Z_256 additive shares: ``[Q, 2^log_range] int8``-compatible u8."""
    def one(k):
        seeds, t = eval_range(k, start_block, log_range)
        return leaf_bytes(k, seeds, t)

    return jax.vmap(one)(keys)
