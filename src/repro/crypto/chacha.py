"""ChaCha-style ARX pseudorandom generator, vectorized for the TPU VPU.

Why not AES (the paper's PRF)
-----------------------------
IM-PIR evaluates the GGM tree on the *host* CPU because UPMEM DPUs have no
crypto acceleration and AES's byte-table / GF(2^8) structure is hostile to
32-bit RISC cores (paper §3.2). A TPU has no AES unit either — but its VPU is
a very wide 32-bit integer SIMD engine, which is exactly the shape of an
ARX (add-rotate-xor) cipher. We therefore instantiate the DPF's length-
doubling PRG with a 12-round ChaCha permutation over 32-bit lanes: every
operation below is a `jnp.uint32` add/xor/rotate that vectorizes over an
arbitrary batch of GGM nodes. This moves DPF evaluation on-device and
eliminates the paper's post-offload bottleneck (DPF eval = 76.45% of query
latency, Table 1).

An AES-128 reference (FIPS-197, pure numpy) lives in ``repro.crypto.aes_ref``
to document construction parity; the PRG is pluggable via ``rounds``.

Layout
------
A GGM seed is 128 bits = ``[..., 4] uint32``. One ChaCha block keyed by the
seed yields 512 bits; the DPF consumes:

  out[0:4]  -> left child seed      out[4:8]  -> right child seed
  out[8]&1  -> left control bit     out[9]&1  -> right control bit
  out[10:]  -> payload-conversion words (additive modes)

The servers expand their trees on the device (``chacha_block``,
``chacha_rows``). The client's Gen walks one path, a block per party per
level, which is too little work to pay for a device program: it runs the
same stream on the host CPU (``chacha_block_packed``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# "expa nd 3 2-by te k" — the standard ChaCha constants.
SIGMA = np.array([0x61707865, 0x3320646E, 0x79622D32, 0x6B206574], dtype=np.uint32)

#: state words 13-15, after the block counter in word 12
NONCE = (0x5049522D, 0x494D5049, 0x52212121)

PRG_ROUNDS = {"chacha8": 8, "chacha12": 12, "chacha20": 20}


# The ARX steps are lax primitives, not jnp operators: each jnp operator
# is a jitted function of its own, and tracing the ~2000 of them in one
# serve step's GGM expansion (chacha_rows, once per tree level) took most
# of the step's trace time.

def _rotl32(x: jax.Array, n: int) -> jax.Array:
    return lax.bitwise_or(lax.shift_left(x, np.uint32(n)),
                          lax.shift_right_logical(x, np.uint32(32 - n)))


def _quarter(a, b, c, d):
    a = lax.add(a, b)
    d = _rotl32(lax.bitwise_xor(d, a), 16)
    c = lax.add(c, d)
    b = _rotl32(lax.bitwise_xor(b, c), 12)
    a = lax.add(a, b)
    d = _rotl32(lax.bitwise_xor(d, a), 8)
    c = lax.add(c, d)
    b = _rotl32(lax.bitwise_xor(b, c), 7)
    return a, b, c, d


def _double_round(x):
    # column rounds
    x[0], x[4], x[8], x[12] = _quarter(x[0], x[4], x[8], x[12])
    x[1], x[5], x[9], x[13] = _quarter(x[1], x[5], x[9], x[13])
    x[2], x[6], x[10], x[14] = _quarter(x[2], x[6], x[10], x[14])
    x[3], x[7], x[11], x[15] = _quarter(x[3], x[7], x[11], x[15])
    # diagonal rounds
    x[0], x[5], x[10], x[15] = _quarter(x[0], x[5], x[10], x[15])
    x[1], x[6], x[11], x[12] = _quarter(x[1], x[6], x[11], x[12])
    x[2], x[7], x[8], x[13] = _quarter(x[2], x[7], x[8], x[13])
    x[3], x[4], x[9], x[14] = _quarter(x[3], x[4], x[9], x[14])
    return x


@partial(jax.jit, static_argnames=("rounds", "counter"))
def chacha_block(key4: jax.Array, *, counter: int = 0, rounds: int = 12) -> jax.Array:
    """ChaCha block function keyed by a 128-bit seed.

    key4: ``[..., 4] uint32``. The 128-bit seed fills both key halves of the
    ChaCha state (the "HChaCha-style" 128-bit-key layout); the counter and
    nonce words are compile-time constants so distinct GGM uses (child
    expansion vs payload conversion) are domain-separated by ``counter``.

    Returns ``[..., 16] uint32`` — one 512-bit block per seed.
    """
    if rounds % 2:
        raise ValueError("rounds must be even")
    key4 = key4.astype(jnp.uint32)
    batch = key4.shape[:-1]
    const = jnp.broadcast_to(jnp.asarray(SIGMA), batch + (4,))
    ctr = jnp.broadcast_to(
        jnp.asarray([counter & 0xFFFFFFFF, *NONCE], dtype=jnp.uint32),
        batch + (4,),
    )
    state = jnp.concatenate([const, key4, key4, ctr], axis=-1)
    # Rolled (not Python-unrolled) double rounds: GGM evaluation instantiates
    # this block once per tree level inside scans/vmaps, and the unrolled ARX
    # graph made XLA compile times grow superlinearly in rounds × levels
    # (eval_bits_batch at log_n=6 took ~45 s to compile on CPU). The loop
    # carry is the 16-row state tuple; op order — hence the keystream — is
    # bit-identical to the unrolled form.
    x = jax.lax.fori_loop(
        0, rounds // 2,
        lambda _, xs: tuple(_double_round(list(xs))),
        tuple(state[..., i] for i in range(16)))
    out = jnp.stack(x, axis=-1) + state
    return out


def chacha_rows(seed_rows, counter: int = 0, rounds: int = 12):
    """:func:`chacha_block` over row vectors: nodes on lanes, not ``[..., 4]``.

    seed_rows: 4 u32 arrays of one shape (one per seed word). Returns the
    16 words of each node's block as 16 arrays of that shape, the same
    stream as ``chacha_block``. This lane-dense form is what the Pallas
    kernels and the chunk-root expansion (``core/dpf.py eval_roots_batch``)
    run.
    """
    shape = seed_rows[0].shape
    const = [jnp.full(shape, np.uint32(c)) for c in SIGMA]
    ctr_words = [counter & 0xFFFFFFFF, *NONCE]
    ctr = [jnp.full(shape, np.uint32(c)) for c in ctr_words]
    state = const + list(seed_rows) + list(seed_rows) + ctr
    # Rolled double rounds, as in chacha_block: callers like the fused
    # megakernel instantiate this permutation once per tree level, and
    # unrolled, the XLA:CPU graph of the interpret-mode emulation grew
    # superlinearly in rounds x levels (the additive fused body hit a
    # >15 min, >20 GB compile at rounds=12).
    x = jax.lax.fori_loop(0, rounds // 2,
                          lambda _, xs: tuple(_double_round(list(xs))),
                          tuple(state))
    return [lax.add(xi, si) for xi, si in zip(x, state)]


#: lane width of the host ChaCha's packed integers: lane ``i`` of a packed
#: word holds one key's copy of that word in bits ``64 i`` to ``64 i + 31``
LANE_BITS = 64
WORD = 0xFFFFFFFF


def _quarter_host(a, b, c, d, m):
    """:func:`_quarter` on packed integers; ``m`` keeps each lane's low 32
    bits. A rotate's two shifts spill only into the zero bits between
    lanes, which the mask clears, as it clears an add's carry."""
    a = (a + b) & m
    d ^= a
    d = ((d << 16) | (d >> 16)) & m
    c = (c + d) & m
    b ^= c
    b = ((b << 12) | (b >> 20)) & m
    a = (a + b) & m
    d ^= a
    d = ((d << 8) | (d >> 24)) & m
    c = (c + d) & m
    b ^= c
    b = ((b << 7) | (b >> 25)) & m
    return a, b, c, d


def chacha_block_packed(key_words, n_lanes: int, *, counter: int = 0,
                        rounds: int = 12):
    """:func:`chacha_block` on the host CPU for ``n_lanes`` keys at once.

    key_words: the 4 key words, each a plain integer holding every key's
    copy of that word in its lane (``LANE_BITS``). Returns the block's 16
    words packed the same way: one pass of the rounds serves every lane.
    The client's Gen keeps its two parties' seeds packed from level to
    level (``core/dpf.py gen_keys``).
    """
    if rounds % 2:
        raise ValueError("rounds must be even")
    ones = sum(1 << (LANE_BITS * i) for i in range(n_lanes))
    m = WORD * ones
    init = ([int(c) * ones for c in SIGMA] + list(key_words) * 2
            + [c * ones for c in (counter & WORD, *NONCE)])
    (x0, x1, x2, x3, x4, x5, x6, x7,
     x8, x9, x10, x11, x12, x13, x14, x15) = init
    for _ in range(rounds // 2):
        x0, x4, x8, x12 = _quarter_host(x0, x4, x8, x12, m)
        x1, x5, x9, x13 = _quarter_host(x1, x5, x9, x13, m)
        x2, x6, x10, x14 = _quarter_host(x2, x6, x10, x14, m)
        x3, x7, x11, x15 = _quarter_host(x3, x7, x11, x15, m)
        x0, x5, x10, x15 = _quarter_host(x0, x5, x10, x15, m)
        x1, x6, x11, x12 = _quarter_host(x1, x6, x11, x12, m)
        x2, x7, x8, x13 = _quarter_host(x2, x7, x8, x13, m)
        x3, x4, x9, x14 = _quarter_host(x3, x4, x9, x14, m)
    return [(x + s) & m for x, s in zip(
        (x0, x1, x2, x3, x4, x5, x6, x7,
         x8, x9, x10, x11, x12, x13, x14, x15), init)]


def ggm_double(seeds: jax.Array, *, rounds: int = 12):
    """GGM node doubling: ``[n, 4]u32 -> (sL, tL, sR, tR)``.

    The core PRG of the DPF tree (paper Eq. 3's ``PRF_s``), vectorized over
    all nodes of one level. Returns left/right child seeds ``[n, 4]`` and
    control bits ``[n]`` (uint32 in {0, 1}).
    """
    blk = chacha_block(seeds, counter=0, rounds=rounds)
    s_l = blk[..., 0:4]
    s_r = blk[..., 4:8]
    t_l = blk[..., 8] & np.uint32(1)
    t_r = blk[..., 9] & np.uint32(1)
    return s_l, t_l, s_r, t_r


def prg_bits(seeds: jax.Array, n_words: int, *, rounds: int = 12) -> jax.Array:
    """Payload-conversion PRG: expand each seed to ``n_words`` uint32 words.

    Domain-separated from child expansion by the block counter. Used to mask
    multi-word payload shares (``convert`` in the DPF literature).
    """
    outs = []
    need = n_words
    ctr = 1
    while need > 0:
        blk = chacha_block(seeds, counter=ctr, rounds=rounds)
        take = min(16, need)
        outs.append(blk[..., :take])
        need -= take
        ctr += 1
    return jnp.concatenate(outs, axis=-1) if len(outs) > 1 else outs[0]
