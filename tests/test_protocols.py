"""Protocol-plane tests: registry, config shim, and oracle parity.

Fast tier: everything here evaluates DPF components *eagerly* (python
loops over ``dpf.eval_range``) or through the small interpret-mode Pallas
kernels — no serve-step compiles (those cost ~40-70 s each on this
container and live in the slow tier / examples).

Oracle pairs:
  * ``kernels/pir_matmul.py`` (Pallas GEMM) vs ``kernels/ref.py`` oracle;
  * ``XorDpfK`` (k = 3) vs a pure-numpy reference: per-party selection
    vectors XOR to the one-hot e_alpha, and numpy-folded answers XOR to
    the DB row — while every single party's vector stays dense
    pseudorandom (the 1-privacy sanity check);
  * the ``pad_keys`` round-trip: pad -> answer -> slice == unpadded.
"""
import warnings

import numpy as np
import pytest
from _prop import given, settings, st

import jax
import jax.numpy as jnp

from repro.config import PIRConfig
from repro.core import dpf, pir
from repro.core import protocol as protocol_mod
from repro.core.protocol import ExecutionPlan, available, for_config, get
from repro.engine import heuristic_plan
from repro.kernels import ops, ref

RNG = np.random.default_rng(7)
LOG_N = 6
N = 1 << LOG_N
DB = pir.make_database(np.random.default_rng(0), N, 32)


# ---------------------------------------------------------------------------
# registry + config shim
# ---------------------------------------------------------------------------

def test_registry_names():
    assert {"xor-dpf-2", "additive-dpf-2", "xor-dpf-k",
            "lwe-simple-1"} <= set(available())
    assert get("xor-dpf-2").n_parties(PIRConfig(n_items=N)) == 2
    with pytest.raises(KeyError, match="unknown protocol"):
        get("nope-9000")
    # record structs drive e.g. MultiServerPIR.query([])'s empty result
    cfg = PIRConfig(n_items=N, item_bytes=32)
    assert get("xor-dpf-2").record_struct(cfg) == ((8,), np.uint32)
    assert get("xor-dpf-k").record_struct(cfg) == ((8,), np.uint32)
    assert get("additive-dpf-2").record_struct(cfg) == ((32,), np.uint8)
    assert get("lwe-simple-1").record_struct(cfg) == ((32,), np.uint8)
    # the single-server protocol: 1 party, hint-carrying, lwe share kind
    lwe_proto = get("lwe-simple-1")
    assert lwe_proto.n_parties(PIRConfig(n_items=N, n_servers=1)) == 1
    assert lwe_proto.needs_hint and lwe_proto.share_kind == "lwe"
    assert PIRConfig(n_items=N, protocol="lwe-simple-1").share_kind == "lwe"


@pytest.mark.parametrize("name", ["xor-dpf-2", "additive-dpf-2",
                                  "xor-dpf-k"])
def test_client_work_stays_on_host(name):
    """Gen, key batching and reconstruction move nothing to or from a
    device and hand back numpy arrays: no client program can queue on
    the chip behind a serve step."""
    cfg = PIRConfig(n_items=N, item_bytes=32, protocol=name, n_servers=3)
    proto = get(name)
    rng = np.random.default_rng(11)
    tail, dtype = proto.record_struct(cfg)
    with jax.transfer_guard("disallow"):
        queries = [proto.query_gen(rng, i, cfg) for i in (1, 2, 60)]
        for p in range(proto.n_parties(cfg)):
            batch = proto.pad(dpf.stack_keys([q[p] for q in queries]), 4)
            assert dpf.n_queries_of(batch) == 4
            leaves = jax.tree_util.tree_leaves(batch)
            assert leaves and all(isinstance(x, np.ndarray)
                                  for x in leaves)
        if proto.share_kind == "additive":
            shares = [rng.integers(-2**31, 2**31, size=(3,) + tail,
                                   dtype=np.int32) for _ in range(2)]
            want = ((shares[0].astype(np.int64) + shares[1]) % 256
                    ).astype(np.uint8)
        else:
            shares = [rng.integers(0, 2**32, size=(3,) + tail,
                                   dtype=np.uint32) for _ in range(3)]
            want = shares[0] ^ shares[1] ^ shares[2]
        rec = proto.reconstruct_with(shares, [None] * 3, cfg=cfg)
    assert isinstance(rec, np.ndarray) and rec.dtype == dtype
    np.testing.assert_array_equal(rec, want)


def test_config_protocol_defaults_and_mode_shim():
    import dataclasses
    cfg = PIRConfig(n_items=N)
    assert cfg.protocol == "xor-dpf-2" and cfg.share_kind == "xor"
    assert cfg.mode == ""              # constructor sugar, never stored
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        legacy = PIRConfig(n_items=N, mode="additive")
    assert any(issubclass(x.category, DeprecationWarning) for x in w)
    assert legacy.protocol == "additive-dpf-2"
    assert legacy.share_kind == "additive"
    assert for_config(legacy).name == "additive-dpf-2"
    with pytest.raises(ValueError, match="unknown PIR mode"):
        PIRConfig(n_items=N, mode="quantum")
    # both replace() directions keep working: protocol switches cleanly,
    # and the pre-protocol-plane mode= idiom still wins over the carried
    # protocol (with the deprecation warning)
    assert dataclasses.replace(cfg, protocol="additive-dpf-2").protocol \
        == "additive-dpf-2"
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        assert dataclasses.replace(cfg, mode="additive").protocol \
            == "additive-dpf-2"
        # consistent share algebra: the richer protocol name survives
        assert PIRConfig(n_items=N, mode="xor",
                         protocol="xor-dpf-k").protocol == "xor-dpf-k"


def test_k_server_party_counts_and_specs():
    cfg = PIRConfig(n_items=N, protocol="xor-dpf-k", n_servers=3)
    proto = for_config(cfg)
    assert proto.n_parties(cfg) == 3
    q = pir.query_gen(RNG, 5, cfg)
    assert len(q.keys) == 3
    batch = pir.batch_queries(RNG, [1, 2], cfg)
    for party in range(3):
        spec = proto.key_specs(cfg, 2, party=party)
        # treedef AND shapes must match real keys (per-bucket jit contract)
        assert (jax.tree_util.tree_structure(batch[party])
                == jax.tree_util.tree_structure(spec))
        assert ([x.shape for x in jax.tree_util.tree_leaves(batch[party])]
                == [x.shape for x in jax.tree_util.tree_leaves(spec)])
    with pytest.raises(ValueError, match="n_servers"):
        proto.n_parties(PIRConfig(n_items=N, protocol="xor-dpf-k",
                                  n_servers=1))


def test_plan_selection_rules():
    # the heuristic fills the non-kernel axes: chunk_log 12, gather
    plan = heuristic_plan(PIRConfig(n_items=N), 4, backend="cpu")
    assert (plan.chunk_log, plan.collective) == (12, "gather")
    # additive protocols on the CPU take the materialized GEMM at its
    # pre-engine reduction tile
    add = heuristic_plan(PIRConfig(n_items=1 << 20,
                                   protocol="additive-dpf-2"), 4,
                         backend="cpu")
    assert (add.expand, add.scan, add.tile_r) == ("materialize", "jnp",
                                                  1024)
    # selector: XOR small db -> materialize; XOR big db -> fused on the
    # CPU at every bucket size (a single query's full-domain eval does not
    # fit at scale), the megakernel on a TPU; Pallas bodies only on TPU
    small = heuristic_plan(PIRConfig(n_items=1 << 10), 4, backend="cpu")
    big = heuristic_plan(PIRConfig(n_items=1 << 20), 8, backend="cpu")
    single = heuristic_plan(PIRConfig(n_items=1 << 20), 1, backend="cpu")
    assert small.expand == "materialize" and big.expand == "fused"
    assert single.expand == "fused"
    assert small.scan == "jnp"   # CPU: interpret-mode Pallas would be slow
    assert heuristic_plan(PIRConfig(n_items=1 << 10), 8,
                          backend="tpu").scan == "pallas"
    # the fused XOR body never reaches a scan kernel: jnp on the CPU; a
    # TPU takes the megakernel instead
    assert big.scan == "jnp"
    tpu_big = heuristic_plan(PIRConfig(n_items=1 << 20), 8, backend="tpu")
    assert (tpu_big.expand, tpu_big.scan) == ("fused-pallas", "pallas")


# ---------------------------------------------------------------------------
# registry conformance: ONE body every registered protocol must pass
# ---------------------------------------------------------------------------

def _conformance_cfg(name: str) -> PIRConfig:
    n_servers = {"xor-dpf-k": 3, "lwe-simple-1": 1}.get(name, 2)
    return PIRConfig(n_items=N, protocol=name, n_servers=n_servers)


def _oracle_records(proto, db_words, indices):
    """What reconstruction must return: u32 words (XOR algebras) or
    Z_256 bytes (GEMM algebras)."""
    if proto.share_kind == "xor":
        return db_words[indices]
    return pir.db_as_bytes(db_words)[indices]


def _answer_one(proto, view_np, key, log_n=LOG_N):
    """One party's answer for ONE query, eagerly, per share algebra.

    Deliberately the single-key evaluation idiom (``dpf.eval_range`` /
    Q=1 ``eval_bytes_batch``) the other fast-tier tests use: those
    primitive shapes are already op-cached in-process, while the batched
    vmap forms would each pay a fresh multi-second lowering here.
    """
    if proto.share_kind == "xor":
        bits = (_party_bits_np(key, log_n) if key.root_seed.ndim > 1
                else _bits_np(key, log_n))
        return _answer_np(view_np, bits)                       # [W] u32
    if proto.share_kind == "additive":
        shares = np.asarray(dpf.eval_bytes_batch(
            dpf.stack_keys([key]), 0, log_n))[0]
        return (shares.astype(np.int64)
                @ view_np.astype(np.int64)).astype(np.int32)   # [L] i32
    # lwe: ct^T.D mod q in numpy (device answer parity lives in test_lwe)
    ct = np.asarray(key.ct).view(np.uint32).astype(np.uint64)
    ans = (ct @ view_np.astype(np.uint64)) & np.uint64(0xFFFFFFFF)
    return ans.astype(np.uint32).view(np.int32)                # [L] i32


def _eager_answers(proto, cfg, view_np, batches):
    """All parties' [Q, ...] answers, slot by slot off the batched keys."""
    out = []
    for p in range(proto.n_parties(cfg)):
        n = proto.n_queries(batches[p])
        rows = [_answer_one(proto, view_np,
                            jax.tree_util.tree_map(lambda x, i=i: x[i],
                                                   batches[p]))
                for i in range(n)]
        out.append(np.stack(rows))
    return out


@pytest.mark.parametrize("name", sorted(available()))
def test_protocol_conformance(name):
    """The registry contract, one shared body per protocol: query_gen_full
    -> batch -> eager answers -> reconstruct_with matches the oracle; the
    pad round-trip leaves real slots untouched; and answers flowing
    through a QueryScheduler are epoch-tagged correctly across a publish.
    Any protocol added to the registry is swept automatically."""
    from repro.db import ShardedDatabase
    from repro.launch.mesh import make_local_mesh
    from repro.runtime.serve_loop import QueryScheduler

    from repro.db import DatabaseSpec

    cfg = _conformance_cfg(name)
    proto = for_config(cfg)
    k = proto.n_parties(cfg)
    indices = [5, N - 1]
    view_np = DatabaseSpec.from_config(cfg).pack_host(DB, proto.db_view)

    full = [proto.query_gen_full(RNG, i, cfg) for i in indices]
    states = [f[1] for f in full]
    batches = [dpf.stack_keys([f[0][p] for f in full]) for p in range(k)]
    for b in batches:
        assert proto.n_queries(b) == 2

    hint = (np.asarray(proto.hint_builder(cfg)(jnp.asarray(DB)))
            if proto.needs_hint else None)
    answers = _eager_answers(proto, cfg, view_np, batches)
    rec = np.asarray(proto.reconstruct_with(answers, states, cfg=cfg,
                                            hint=hint))
    np.testing.assert_array_equal(rec, _oracle_records(proto, DB, indices))

    # pad round-trip: pad -> answer -> slice == unpadded on real slots
    padded = [proto.pad(b, 4) for b in batches]
    for p in padded:
        assert proto.n_queries(p) == 4
    answers_p = _eager_answers(proto, cfg, view_np, padded)
    rec_p = np.asarray(proto.reconstruct_with(
        [a[:2] for a in answers_p], states, cfg=cfg, hint=hint))
    np.testing.assert_array_equal(rec_p, rec)

    # epoch tagging: the same eager answer path behind a QueryScheduler,
    # across a publish — answers carry the epoch they computed against
    db = ShardedDatabase(DB, cfg, make_local_mesh())
    if proto.needs_hint:
        db.register_hint(proto.name, proto.hint_builder(cfg),
                         proto.hint_delta(cfg))

    def dispatch(items):
        epoch, views = db.snapshot((proto.db_view,))
        v_np, sts = np.asarray(views[proto.db_view]), [it[1] for it in items]
        ans = [np.stack([_answer_one(proto, v_np, it[0][p]) for it in items])
               for p in range(k)]
        return ans, sts, epoch

    def finalize(raw, n):
        ans, sts, epoch = raw
        h = (np.asarray(db.hint(proto.name, epoch=epoch))
             if proto.needs_hint else None)
        return list(np.asarray(proto.reconstruct_with(
            [a[:n] for a in ans], sts[:n], cfg=cfg, hint=h)))

    sched = QueryScheduler(
        collate=list, stage=lambda p: p, dispatch=dispatch,
        finalize=finalize, buckets=(2,), epoch_of=lambda raw: raw[2])

    fut0 = sched.submit(proto.query_gen_full(RNG, 9, cfg))
    sched.submit(proto.query_gen_full(RNG, 9, cfg))
    sched.pump()
    assert fut0.epoch == 0
    np.testing.assert_array_equal(fut0.result(0),
                                  _oracle_records(proto, DB, [9])[0])

    new_val = np.random.default_rng(8).integers(
        0, 1 << 32, size=(1, 8), dtype=np.uint32)
    db.stage([9], new_val)
    assert db.publish() == 1
    updated = DB.copy()
    updated[9] = new_val
    fut1 = sched.submit(proto.query_gen_full(RNG, 9, cfg))
    sched.submit(proto.query_gen_full(RNG, 9, cfg))
    sched.pump()
    assert fut1.epoch == 1
    np.testing.assert_array_equal(fut1.result(0),
                                  _oracle_records(proto, updated, [9])[0])


# ---------------------------------------------------------------------------
# numpy reference helpers (eager per-component eval: no compiles)
# ---------------------------------------------------------------------------

def _bits_np(key: dpf.DPFKey, log_n: int) -> np.ndarray:
    """Selection bits of one plain (component-free) DPF key."""
    _, t = dpf.eval_range(key, 0, log_n)
    return np.asarray(t, np.uint32)


def _party_bits_np(party_key: dpf.DPFKey, log_n: int) -> np.ndarray:
    """One k-server party's full selection vector (leaves ``[C, ...]``),
    component-by-component in numpy."""
    n_comp = party_key.root_seed.shape[0]
    acc = np.zeros(1 << log_n, np.uint32)
    for c in range(n_comp):
        comp = jax.tree_util.tree_map(lambda x, c=c: x[c], party_key)
        acc ^= _bits_np(comp, log_n)
    return acc


def _answer_np(db: np.ndarray, bits: np.ndarray) -> np.ndarray:
    """numpy select-XOR oracle: ⊕_{j: bits[j]=1} db[j]."""
    out = np.zeros(db.shape[1], np.uint32)
    for j in np.nonzero(bits)[0]:
        out ^= db[j]
    return out


# ---------------------------------------------------------------------------
# XorDpfK(k=3) vs the numpy reference
# ---------------------------------------------------------------------------

@settings(max_examples=5, deadline=None)
@given(st.integers(0, N - 1))
def test_xor_dpf_k3_matches_numpy_reference(alpha):
    cfg = PIRConfig(n_items=N, protocol="xor-dpf-k", n_servers=3)
    proto = for_config(cfg)
    keys = proto.query_gen(RNG, alpha, cfg)
    bits = [_party_bits_np(k, LOG_N) for k in keys]
    # k-of-k reconstruction: selection vectors XOR to e_alpha ...
    onehot = np.zeros(N, np.uint32)
    onehot[alpha] = 1
    np.testing.assert_array_equal(bits[0] ^ bits[1] ^ bits[2], onehot)
    # ... and numpy-folded answers XOR to the DB row
    answers = [_answer_np(DB, b) for b in bits]
    np.testing.assert_array_equal(answers[0] ^ answers[1] ^ answers[2],
                                  DB[alpha])
    # 1-privacy sanity: every single party's vector is dense pseudorandom
    # (a sparse vector would leak alpha's neighbourhood)
    for b in bits:
        assert 0.2 < b.mean() < 0.8


def test_xor_dpf_k2_degenerates_to_two_server():
    """k=2: the ring masks cancel pairwise; answers equal plain 2-DPF."""
    cfg = PIRConfig(n_items=N, protocol="xor-dpf-k", n_servers=2)
    proto = for_config(cfg)
    keys = proto.query_gen(np.random.default_rng(3), 42, cfg)
    bits = [_party_bits_np(k, LOG_N) for k in keys]
    onehot = np.zeros(N, np.uint32)
    onehot[42] = 1
    np.testing.assert_array_equal(bits[0] ^ bits[1], onehot)


# ---------------------------------------------------------------------------
# pir_matmul (Pallas) vs the jnp oracle
# ---------------------------------------------------------------------------

@settings(max_examples=5, deadline=None)
@given(st.integers(0, (1 << 31) - 1))
def test_pir_matmul_matches_oracle(seed):
    rng = np.random.default_rng(seed)
    q, r, l = 4, 128, 32                 # grid over the reduction dim
    s = jnp.asarray(rng.integers(-128, 128, size=(q, r), dtype=np.int8))
    d = jnp.asarray(rng.integers(-128, 128, size=(r, l), dtype=np.int8))
    got = ops.pir_gemm(s, d, tile_q=4, tile_r=64, tile_l=32)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.pir_matmul_ref(s, d)))


# ---------------------------------------------------------------------------
# pad_keys round-trip: pad -> answer -> slice == unpadded
# ---------------------------------------------------------------------------

@settings(max_examples=3, deadline=None)
@given(st.integers(0, N - 3))
def test_pad_keys_roundtrip_xor2(base):
    """Padded batches answer identically on the real slots (both parties)."""
    cfg = PIRConfig(n_items=N)
    idx = [base, base + 1, base + 2]                 # Q=3 -> bucket 4
    batch = pir.batch_queries(RNG, idx, cfg)
    def slot_answer(keys, i):
        one = jax.tree_util.tree_map(lambda x: x[i], keys)
        return _answer_np(DB, _bits_np(one, LOG_N))

    for party in range(2):
        padded = dpf.pad_keys(batch[party], 4)
        assert dpf.n_queries_of(padded) == 4
        unpadded_ans = [slot_answer(batch[party], i) for i in range(3)]
        padded_ans = [slot_answer(padded, i) for i in range(4)]
        # slice off the pad slot: real answers unchanged
        for i in range(3):
            np.testing.assert_array_equal(padded_ans[i], unpadded_ans[i])
        # the pad slot replicates the last real key's answer
        np.testing.assert_array_equal(padded_ans[3], unpadded_ans[2])


def test_pad_keys_roundtrip_k3_component_axis():
    """pad_keys pads the *query* axis of k-server component pytrees."""
    cfg = PIRConfig(n_items=N, protocol="xor-dpf-k", n_servers=3)
    proto = for_config(cfg)
    batch = pir.batch_queries(RNG, [4, 9], cfg)
    for party, key in enumerate(batch):
        padded = proto.pad(key, 4)
        assert proto.n_queries(padded) == 4
        # component axis untouched; pad slots replicate the last real key
        assert padded.root_seed.shape == (4,) + key.root_seed.shape[1:]
        np.testing.assert_array_equal(np.asarray(padded.root_seed[3]),
                                      np.asarray(key.root_seed[-1]))
        bits_last = _party_bits_np(
            jax.tree_util.tree_map(lambda x: x[1], key), LOG_N)
        bits_pad = _party_bits_np(
            jax.tree_util.tree_map(lambda x: x[3], padded), LOG_N)
        np.testing.assert_array_equal(bits_pad, bits_last)


# ---------------------------------------------------------------------------
# batch composite (cuckoo-bucketed, DESIGN.md §14) conformance
# ---------------------------------------------------------------------------

#: the inner protocols the batch composite serves (every registered
#: k-party protocol; hint protocols are rejected by BatchPIR)
BATCH_PROTOCOLS = ["xor-dpf-2", "additive-dpf-2", "xor-dpf-k"]


def _batch_cfg(name: str) -> PIRConfig:
    n_servers = {"xor-dpf-k": 3}.get(name, 2)
    # checksum ON: PR 8 verified reconstruction must ride through the
    # per-bucket reconstructions (incl. dummy buckets' pad rows)
    return PIRConfig(n_items=N, protocol=name, n_servers=n_servers,
                     batch_m=4, checksum=True)


def _eager_round(proto, bdb, plan):
    """One RoundPlan's per-party per-bucket answers + reassembled records,
    eagerly (single-key eval; no serve-step compiles) — the oracle-side
    mirror of BatchPIR's dispatch/finalize closures."""
    log_n = (bdb.capacity - 1).bit_length()
    epoch, views = bdb.snapshot((proto.db_view,))
    k = proto.n_parties(bdb.inner_cfg)
    shares = [np.stack([_answer_one(proto,
                                    np.asarray(views[proto.db_view][b]),
                                    plan.keys[b][p], log_n)
                        for b in range(bdb.n_buckets)])
              for p in range(k)]
    recs = np.asarray(proto.reconstruct_with(
        shares, [None] * bdb.n_buckets, cfg=bdb.inner_cfg))
    from repro.core.batch import reassemble
    return reassemble(plan, recs), epoch


@pytest.mark.parametrize("name", BATCH_PROTOCOLS)
def test_batch_composite_conformance(name):
    """The batch composite against the numpy oracle, per inner protocol:
    a cuckoo-planned round reconstructs the requested records (duplicates
    included, checksum verification riding through), and staged rows land
    in every candidate bucket's view across a publish (epoch tagging)."""
    from repro.core.batch import plan_round
    from repro.db import BucketedDatabase
    from repro.launch.mesh import make_local_mesh
    from repro.runtime.serve_loop import QueryScheduler

    cfg = _batch_cfg(name)
    proto = for_config(cfg)
    bdb = BucketedDatabase(DB, cfg, make_local_mesh())
    rng = np.random.default_rng(3)

    indices = [5, N - 1, 17, 5]            # duplicate rides one bucket
    plan = plan_round(rng, indices, bdb.layout, bdb.inner_cfg, proto)
    rec, epoch = _eager_round(proto, bdb, plan)
    assert epoch == 0
    np.testing.assert_array_equal(rec, _oracle_records(proto, DB, indices))

    # epoch tagging through a QueryScheduler wired like BatchPIR's: the
    # answer computed after a publish carries the new OUTER epoch and the
    # staged row is served from every candidate bucket it was fanned to
    def dispatch(plans):
        outs = [_eager_round(proto, bdb, p) for p in plans]
        return [o[0] for o in outs], outs[0][1]

    sched = QueryScheduler(
        collate=list, stage=lambda p: p, dispatch=dispatch,
        finalize=lambda raw, n: raw[0][:n], buckets=(1,),
        epoch_of=lambda raw: raw[1])

    target = 9
    fut0 = sched.submit(plan_round(rng, [target], bdb.layout,
                                   bdb.inner_cfg, proto))
    sched.pump()
    assert fut0.epoch == 0
    np.testing.assert_array_equal(fut0.result(0)[0],
                                  _oracle_records(proto, DB, [target])[0])

    new_val = np.random.default_rng(8).integers(
        0, 1 << 32, size=(1, 8), dtype=np.uint32)
    bdb.stage([target], new_val)
    assert bdb.publish() == 1
    updated = DB.copy()
    updated[target] = new_val
    fut1 = sched.submit(plan_round(rng, [target], bdb.layout,
                                   bdb.inner_cfg, proto))
    sched.pump()
    assert fut1.epoch == 1
    np.testing.assert_array_equal(fut1.result(0)[0],
                                  _oracle_records(proto, updated,
                                                  [target])[0])


@pytest.mark.parametrize("name", BATCH_PROTOCOLS)
def test_batch_round_uniform_padding_no_occupancy_leak(name):
    """ACCEPTANCE: every round issues exactly B per-bucket queries with an
    identical server-observable key structure, REGARDLESS of which m
    indices were requested — bucket occupancy never leaks the batch."""
    from repro.core.batch import CuckooLayout, CuckooParams, plan_round
    import dataclasses

    cfg = _batch_cfg(name)
    proto = for_config(cfg)
    params = CuckooParams.from_config(cfg).validate()
    layout = CuckooLayout.build(cfg.n_items, params)
    inner_cfg = dataclasses.replace(cfg, n_items=layout.capacity)
    B = params.n_buckets
    rng = np.random.default_rng(11)

    # adversarial spreads: clustered, spread, partial, duplicated —
    # every round plan must be structurally identical
    batches = [[0, 1, 2, 3], [7, 19, 42, 63], [5], [9, 9, 9, 9],
               [N - 4, N - 3, N - 2, N - 1]]
    ref_struct = None
    for idx in batches:
        plan = plan_round(rng, idx, layout, inner_cfg, proto)
        assert plan.n_buckets == B                       # exactly B queries
        assert len(plan.keys) == B and len(plan.real) == B
        assert sum(plan.real) == len(set(idx))           # rest are dummies
        # the server-observable shape: per-party key pytree structure and
        # leaf shapes are index-independent (dummies share real keygen)
        struct = [
            [(jax.tree_util.tree_structure(plan.keys[b][p]),
              tuple(np.shape(leaf)
                    for leaf in jax.tree_util.tree_leaves(plan.keys[b][p])))
             for b in range(B)]
            for p in range(proto.n_parties(cfg))]
        if ref_struct is None:
            ref_struct = struct
        assert struct == ref_struct


def test_batch_dummy_query_indistinguishability_smoke():
    """Dummy-bucket keys run the real keygen on a uniform slot: their key
    material's marginal statistics match real keys' (loose first-moment
    smoke over DPF root seeds — cryptographic indistinguishability is the
    PRG's job; this guards against e.g. zeroed dummy seeds)."""
    from repro.core.batch import CuckooLayout, CuckooParams, plan_round
    import dataclasses

    cfg = _batch_cfg("xor-dpf-2")
    proto = for_config(cfg)
    params = CuckooParams.from_config(cfg).validate()
    layout = CuckooLayout.build(cfg.n_items, params)
    inner_cfg = dataclasses.replace(cfg, n_items=layout.capacity)
    rng = np.random.default_rng(29)

    real_w, dummy_w = [], []
    for _ in range(64):
        idx = rng.choice(N, size=4, replace=False)
        plan = plan_round(rng, idx, layout, inner_cfg, proto)
        for b in range(plan.n_buckets):
            for p in range(2):
                seed = np.asarray(plan.keys[b][p].root_seed,
                                  np.uint64).ravel()
                (real_w if plan.real[b] else dummy_w).extend(seed.tolist())
    assert len(real_w) >= 256 and len(dummy_w) >= 256
    # both populations are uniform u32 words: means within 10% of range
    mid, tol = 2.0 ** 31, 0.1 * 2.0 ** 32
    assert abs(np.mean(real_w) - mid) < tol
    assert abs(np.mean(dummy_w) - mid) < tol
    assert abs(np.mean(real_w) - np.mean(dummy_w)) < tol
