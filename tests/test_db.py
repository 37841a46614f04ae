"""Database-plane tests: spec math, placement, views, epoched updates.

Fast tier: everything runs eagerly or through tiny elementwise jits (the
scatter/pack helpers compile in well under a second — never a serve-step
compile). The three-protocol parity test contracts per-party answers
*eagerly* against the ``ShardedDatabase`` views after ``stage``+``publish``
and checks reconstruction versus a numpy oracle with the same rows
rewritten; transfer accounting asserts the update path moves
O(rows · item_bytes), not O(db_bytes) — the acceptance bar for online
updates. The full compiled serving stack across a publish lives in the
slow tier (one ``TwoServerPIR`` session) and in ``examples/db_updates.py``
(3-server, wired into CI).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.config import PIRConfig
from repro.core import dpf, pir
from repro.core.protocol import for_config
from repro.db import DatabaseSpec, ShardedDatabase
from repro.launch.mesh import make_local_mesh
from repro.runtime.serve_loop import MultiServerPIR, QueryScheduler

LOG_N = 6
N = 1 << LOG_N
DB = pir.make_database(np.random.default_rng(0), N, 32)
RNG = np.random.default_rng(11)


@pytest.fixture(scope="module")
def mesh():
    return make_local_mesh()


def _fresh_db(mesh, cfg=None) -> ShardedDatabase:
    return ShardedDatabase(DB, cfg or PIRConfig(n_items=N), mesh)


def _rand_rows(rng, n_rows):
    rows = rng.choice(N, size=n_rows, replace=False)
    vals = rng.integers(0, 1 << 32, size=(n_rows, 8), dtype=np.uint32)
    return rows, vals


# ---------------------------------------------------------------------------
# DatabaseSpec: the one owner of shape/packing math
# ---------------------------------------------------------------------------

def test_spec_geometry_and_views():
    cfg = PIRConfig(n_items=N, item_bytes=32)
    spec = DatabaseSpec.from_config(cfg)
    assert (spec.item_words, spec.log_n, spec.db_bytes) == (8, LOG_N, N * 32)
    assert spec.view_shape("words") == (N, 8)
    assert spec.view_shape("bytes") == (N, 32)
    assert spec.view_struct("words").dtype == np.uint32
    assert spec.view_struct("bytes").dtype == np.int8
    with pytest.raises(KeyError, match="unknown db view"):
        spec.view_shape("float16")
    # shard math: divisibility and power-of-two rows enforced here
    assert spec.rows_per_shard(4) == N // 4
    with pytest.raises(ValueError, match="divisible"):
        spec.rows_per_shard(3)
    with pytest.raises(ValueError, match="power of two"):
        DatabaseSpec(n_items=N + 1)
    # host and device packing agree (and round-trip)
    host_bytes = spec.words_to_bytes_host(DB)
    np.testing.assert_array_equal(host_bytes, pir.db_as_bytes(DB))
    np.testing.assert_array_equal(
        np.asarray(spec.words_to_bytes_device(jnp.asarray(DB))).view(
            np.uint8), host_bytes)
    np.testing.assert_array_equal(spec.bytes_to_words_host(host_bytes), DB)


def test_spec_coerce_update_rows():
    spec = DatabaseSpec(n_items=N, item_bytes=32)
    words = RNG.integers(0, 1 << 32, size=(3, 8), dtype=np.uint32)
    np.testing.assert_array_equal(spec.coerce_rows_to_words(words), words)
    as_bytes = spec.words_to_bytes_host(words)
    np.testing.assert_array_equal(spec.coerce_rows_to_words(as_bytes), words)
    with pytest.raises(ValueError, match="2-D"):
        spec.coerce_rows_to_words(words[0])
    with pytest.raises(ValueError, match="row values"):
        spec.coerce_rows_to_words(np.zeros((3, 5), np.uint32))


# ---------------------------------------------------------------------------
# placement + shared residency
# ---------------------------------------------------------------------------

def test_chunked_placement_single_pass(mesh):
    db = _fresh_db(mesh)
    assert db.stats.n_full_placements == 1
    assert db.stats.preload_h2d_bytes == DB.nbytes
    np.testing.assert_array_equal(np.asarray(db.view("words")), DB)
    # byte view derives on device, once, lazily
    assert db.stats.n_view_packs == 0
    np.testing.assert_array_equal(
        np.asarray(db.view("bytes")).view(np.uint8), pir.db_as_bytes(DB))
    assert db.stats.n_view_packs == 1
    db.view("bytes")
    assert db.stats.n_view_packs == 1        # cached, not re-derived


def test_multiserver_shares_one_database(mesh):
    """k parties reference ONE ShardedDatabase: no k-fold host/device
    copies (the PR 4 acceptance bar). Construction compiles nothing."""
    cfg = PIRConfig(n_items=N, protocol="xor-dpf-k", n_servers=3)
    system = MultiServerPIR(DB, cfg, mesh, n_queries=2, buckets=(2,))
    assert len(system.servers) == 3
    assert all(s.db is system.db for s in system.servers)
    assert system.db.stats.n_full_placements == 1
    assert system.db.stats.preload_h2d_bytes == DB.nbytes
    assert system.epoch == 0
    # a pre-built (possibly shared) database passes straight through
    again = MultiServerPIR(system.db, cfg, mesh, n_queries=2, buckets=(2,))
    assert again.db is system.db
    assert system.db.stats.n_full_placements == 1
    # ... but a database whose spec disagrees with the config fails fast
    # at construction, not as a shape error inside the first serve step
    from repro.core.server import PIRServer
    wrong = PIRConfig(n_items=N * 2, protocol="xor-dpf-k", n_servers=3)
    with pytest.raises(ValueError, match="spec"):
        PIRServer(party=0, database=system.db, cfg=wrong, mesh=mesh,
                  n_queries=2, buckets=(2,))
    with pytest.raises(ValueError, match="required"):
        PIRServer(party=0, database=system.db)


# ---------------------------------------------------------------------------
# epoched updates: staging, dedup, incremental views, transfer accounting
# ---------------------------------------------------------------------------

def test_stage_validates_and_publish_applies_last_write_wins(mesh):
    db = _fresh_db(mesh)
    with pytest.raises(ValueError, match="out of range"):
        db.stage([N], np.zeros((1, 8), np.uint32))
    with pytest.raises(ValueError, match="mismatch"):
        db.stage([1, 2], np.zeros((1, 8), np.uint32))
    v1 = RNG.integers(0, 1 << 32, size=(1, 8), dtype=np.uint32)
    v2 = RNG.integers(0, 1 << 32, size=(1, 8), dtype=np.uint32)
    assert db.stage([9], v1) == 1
    assert db.stage([9], v2) == 2            # same row staged twice
    assert db.n_staged == 2
    assert db.publish() == 1
    assert db.n_staged == 0
    expect = DB.copy()
    expect[9] = v2                           # the later write wins
    np.testing.assert_array_equal(np.asarray(db.view("words")), expect)
    assert db.published[-1].n_staged == 2
    np.testing.assert_array_equal(db.published[-1].rows, [9])
    # publishing nothing is a no-op at the same epoch — including when
    # only zero-row stage calls arrived (no epoch churn on empty deltas)
    assert db.publish() == 1
    db.stage(np.zeros((0,), np.int64), np.zeros((0, 8), np.uint32))
    assert db.publish() == 1


def test_publish_notifies_subscribers_with_replayable_delta(mesh):
    """Multi-subscriber fan-out: every publish delivers a PublishedDelta
    whose deduped (rows, vals) replayed into a second database reproduces
    the epoch byte-for-byte (the replica plane's propagation seam)."""
    src, dst = _fresh_db(mesh), _fresh_db(mesh)
    seen = []
    unsubscribe = src.subscribe(seen.append)
    src.subscribe(lambda d: dst.stage(d.rows, d.vals) and dst.publish())
    v1 = RNG.integers(0, 1 << 32, size=(1, 8), dtype=np.uint32)
    v2 = RNG.integers(0, 1 << 32, size=(1, 8), dtype=np.uint32)
    src.stage([9], v1)
    src.stage([9], v2)                       # dedup: last write wins
    src.stage([3], v1)
    assert src.publish() == 1
    assert src.publish() == 1                # no-op publish: no callback
    assert [d.epoch for d in seen] == [1]
    np.testing.assert_array_equal(np.sort(seen[0].rows), [3, 9])
    assert seen[0].vals.shape == (2, 8)      # deduped, unpadded
    assert seen[0].n_staged == 3
    # the replaying subscriber converged to identical epoch AND contents
    assert dst.epoch == 1
    np.testing.assert_array_equal(np.asarray(dst.view("words")),
                                  np.asarray(src.view("words")))
    # unsubscribe stops delivery; the other subscriber keeps receiving
    unsubscribe()
    src.stage([0], v1)
    src.publish()
    assert [d.epoch for d in seen] == [1]
    assert dst.epoch == 2
    np.testing.assert_array_equal(np.asarray(dst.view("words")),
                                  np.asarray(src.view("words")))


def test_byte_view_incremental_after_random_writes(mesh):
    """Random row writes keep the byte view consistent WITHOUT a second
    full pack — the delta scatter maintains it in place."""
    db = _fresh_db(mesh)
    db.view("bytes")
    assert db.stats.n_view_packs == 1
    expect = DB.copy()
    rng = np.random.default_rng(23)
    for _ in range(3):
        rows, vals = _rand_rows(rng, 5)
        db.stage(rows, vals)
        db.publish()
        expect[rows] = vals
        np.testing.assert_array_equal(np.asarray(db.view("words")), expect)
        np.testing.assert_array_equal(
            np.asarray(db.view("bytes")).view(np.uint8),
            pir.db_as_bytes(expect))
    assert db.stats.n_view_packs == 1        # never re-packed from scratch
    assert db.stats.n_full_placements == 1   # never re-placed
    assert db.stats.n_publishes == 3


def test_delta_transfer_is_o_rows_not_o_db(mesh):
    """The acceptance bar: updating R rows moves O(R · item_bytes) over
    the host→device boundary, not O(db_bytes), and triggers no full
    re-pack / re-placement."""
    cfg = PIRConfig(n_items=1 << 12, item_bytes=32)
    big = pir.make_database(np.random.default_rng(1), cfg.n_items, 32)
    db = ShardedDatabase(big, cfg, make_local_mesh())
    db.view("bytes")                          # both views resident
    preload = db.stats.preload_h2d_bytes
    rows = np.asarray([5, 99, 2048, 4095])
    vals = RNG.integers(0, 1 << 32, size=(4, 8), dtype=np.uint32)
    db.stage(rows, vals)
    db.publish()
    # delta = 4 int32 indices + 4 rows of 32 B values (padded pow2: 4)
    assert db.stats.update_h2d_bytes == 4 * 4 + 4 * 32
    assert db.stats.update_h2d_bytes < cfg.db_bytes // 64
    assert db.stats.preload_h2d_bytes == preload   # no re-placement
    assert db.stats.n_full_placements == 1
    assert db.stats.n_view_packs == 1              # no re-pack
    expect = big.copy()
    expect[rows] = vals
    np.testing.assert_array_equal(np.asarray(db.view("words")), expect)


# ---------------------------------------------------------------------------
# epochs: double buffering + answer tagging across a publish
# ---------------------------------------------------------------------------

def test_epoch_double_buffer_pins_previous_epoch(mesh):
    db = _fresh_db(mesh)
    v0 = db.view("words")
    rows, vals = _rand_rows(np.random.default_rng(3), 2)
    db.stage(rows, vals)
    assert db.publish() == 1
    # the captured array is immutable: in-flight work on epoch 0 is exact
    np.testing.assert_array_equal(np.asarray(v0), DB)
    np.testing.assert_array_equal(np.asarray(db.view("words", epoch=0)), DB)
    expect = DB.copy()
    expect[rows] = vals
    np.testing.assert_array_equal(np.asarray(db.view("words")), expect)
    assert db.epoch == 1
    db.stage(rows[:1], vals[:1])
    db.publish()
    with pytest.raises(KeyError, match="not resident"):
        db.view("words", epoch=0)            # two publishes back: released


def test_scheduler_tags_answers_with_dispatch_epoch(mesh):
    """A publish landing while a batch is 'on device' neither corrupts
    nor retags it: the answer reconstructs against the pre-update DB and
    carries the pre-update epoch; later batches compute and tag against
    the new epoch (the scheduler's re-tag across a swap)."""
    db = _fresh_db(mesh)
    new_val = RNG.integers(0, 1 << 32, size=(1, 8), dtype=np.uint32)
    state = {"publish_mid_flight": True}

    def dispatch(staged):
        # the batch-local contract: the epoch rides with the dispatch
        # result, so concurrent dispatchers can never cross-tag
        epoch, views = db.snapshot(("words",))
        if state["publish_mid_flight"]:
            # the swap lands after dispatch captured its snapshot
            db.stage([0], new_val)
            db.publish()
            state["publish_mid_flight"] = False
        return views["words"], staged, epoch

    sched = QueryScheduler(
        collate=list, stage=lambda p: p, dispatch=dispatch,
        finalize=lambda raw, n: [np.asarray(raw[0])[i] for i in raw[1][:n]],
        buckets=(2,), epoch_of=lambda raw: raw[2])

    first = [sched.submit(0), sched.submit(3)]
    sched.pump()
    assert [f.epoch for f in first] == [0, 0]
    np.testing.assert_array_equal(first[0].result(0), DB[0])   # pre-update
    np.testing.assert_array_equal(first[1].result(0), DB[3])
    assert db.epoch == 1

    second = [sched.submit(0), sched.submit(3)]
    sched.pump()
    assert [f.epoch for f in second] == [1, 1]
    np.testing.assert_array_equal(second[0].result(0), new_val[0])
    np.testing.assert_array_equal(second[1].result(0), DB[3])


# ---------------------------------------------------------------------------
# update-then-query parity vs the numpy oracle, all three protocols
# ---------------------------------------------------------------------------

def _party_bits_np(party_key: dpf.DPFKey, log_n: int) -> np.ndarray:
    """One party's full selection vector, component-by-component (eager).

    Handles both plain 2-server keys (no component axis) and the k-server
    component pytrees (leaves ``[C, ...]``) without any compiled dispatch.
    """
    if party_key.root_seed.ndim == 1:          # plain key
        _, t = dpf.eval_range(party_key, 0, log_n)
        return np.asarray(t, np.uint32)
    acc = np.zeros(1 << log_n, np.uint32)
    for c in range(party_key.root_seed.shape[0]):
        comp = jax.tree_util.tree_map(lambda x, c=c: x[c], party_key)
        _, t = dpf.eval_range(comp, 0, log_n)
        acc ^= np.asarray(t, np.uint32)
    return acc


def _xor_answer_np(db_words: np.ndarray, bits: np.ndarray) -> np.ndarray:
    out = np.zeros(db_words.shape[1], np.uint32)
    for j in np.nonzero(bits)[0]:
        out ^= db_words[j]
    return out


@pytest.mark.parametrize("proto_name,n_servers", [
    ("xor-dpf-2", 2), ("additive-dpf-2", 2), ("xor-dpf-k", 3)])
def test_update_then_query_parity(mesh, proto_name, n_servers):
    """stage+publish, then per-party answers contracted eagerly against
    the protocol's declared ShardedDatabase view; reconstruction matches
    the numpy oracle for updated AND untouched rows."""
    cfg = PIRConfig(n_items=N, protocol=proto_name, n_servers=n_servers)
    proto = for_config(cfg)
    db = ShardedDatabase(DB, cfg, mesh)
    rows, vals = _rand_rows(np.random.default_rng(31), 3)
    db.stage(rows, vals)
    db.publish()
    oracle = DB.copy()
    oracle[rows] = vals

    indices = [int(rows[0]), int((rows[0] + 1) % N)]   # updated + untouched
    assert indices[1] not in rows
    view_np = np.asarray(db.view(proto.db_view))
    per_query_keys = [proto.query_gen(RNG, idx, cfg) for idx in indices]

    def one_answer(key):
        if proto.share_kind == "additive":
            shares = np.asarray(dpf.eval_bytes_batch(
                dpf.stack_keys([key]), 0, LOG_N))[0]
            return shares.astype(np.int64) @ view_np.astype(np.int64)
        return _xor_answer_np(view_np, _party_bits_np(key, LOG_N))

    answers = [
        jnp.asarray(np.stack([one_answer(keys[p]) for keys in
                              per_query_keys]).astype(
            np.int32 if proto.share_kind == "additive" else np.uint32))
        for p in range(proto.n_parties(cfg))
    ]
    rec = np.asarray(proto.reconstruct(answers))
    want = (pir.db_as_bytes(oracle)[indices]
            if proto.share_kind == "additive" else oracle[indices])
    np.testing.assert_array_equal(rec, want)


# ---------------------------------------------------------------------------
# hint lifecycle (single-server preprocessing, DESIGN.md §10)
# ---------------------------------------------------------------------------

def _lwe_db(mesh):
    cfg = PIRConfig(n_items=N, protocol="lwe-simple-1", n_servers=1)
    proto = for_config(cfg)
    db = ShardedDatabase(DB, cfg, mesh)
    db.register_hint(proto.name, proto.hint_builder(cfg),
                     proto.hint_delta(cfg))
    return db, proto, cfg


def test_hint_lazy_build_cached_per_epoch(mesh):
    from repro.core import lwe
    db, proto, cfg = _lwe_db(mesh)
    with pytest.raises(KeyError, match="unknown hint"):
        db.hint("never-registered")
    assert db.stats.n_hint_builds == 0       # lazy: nothing built yet
    h = np.asarray(db.hint(proto.name))
    assert db.stats.n_hint_builds == 1
    db.hint(proto.name)
    assert db.stats.n_hint_builds == 1       # cached, not re-derived
    # built hint matches the numpy oracle on the words view
    params = lwe.params_for(N)
    np.testing.assert_array_equal(
        h.view(np.uint32),
        lwe.hint_np(params, pir.db_as_bytes(DB)).astype(np.uint32))


def test_hint_delta_update_matches_full_recompute(mesh):
    """publish() maintains a materialized hint via the registered O(rows)
    delta — byte-for-byte equal to a full rebuild on the new words, and
    exact across dedup (same row staged twice: last write wins once)."""
    db, proto, cfg = _lwe_db(mesh)
    h0 = np.asarray(db.hint(proto.name))
    rng = np.random.default_rng(41)
    rows, vals = _rand_rows(rng, 4)
    db.stage(rows, vals)
    # restage row[0]: the delta must see ONE transition old -> final value
    v_final = rng.integers(0, 1 << 32, size=(1, 8), dtype=np.uint32)
    db.stage(rows[:1], v_final)
    db.publish()
    assert db.stats.n_hint_deltas == 1
    assert db.stats.n_hint_builds == 1       # never a full rebuild
    expect = DB.copy()
    expect[rows] = vals
    expect[rows[0]] = v_final
    want = np.asarray(proto.hint_builder(cfg)(jnp.asarray(expect)))
    got = np.asarray(db.hint(proto.name))
    np.testing.assert_array_equal(got, want)
    assert not np.array_equal(got, h0)       # the hint genuinely moved
    # and the delta-updated hint keeps delta-updating on later epochs
    rows2, vals2 = _rand_rows(np.random.default_rng(43), 2)
    db.stage(rows2, vals2)
    db.publish()
    expect[rows2] = vals2
    np.testing.assert_array_equal(
        np.asarray(db.hint(proto.name)),
        np.asarray(proto.hint_builder(cfg)(jnp.asarray(expect))))
    assert db.stats.n_hint_deltas == 2
    assert db.stats.n_hint_builds == 1


def test_hint_without_delta_dropped_and_rebuilt(mesh):
    """A hint registered with no delta fn is dropped at publish() and
    lazily rebuilt against the new epoch's words on next access."""
    db = _fresh_db(mesh, PIRConfig(n_items=N))
    # wrapping u32 column sums (jax has no x64 here; mod 2^32 is exact)
    db.register_hint("colsum", lambda words: jnp.sum(words, axis=0,
                                                     dtype=jnp.uint32))
    s0 = np.asarray(db.hint("colsum"))
    np.testing.assert_array_equal(s0, DB.sum(axis=0, dtype=np.uint32))
    assert db.stats.n_hint_builds == 1
    rows, vals = _rand_rows(np.random.default_rng(47), 3)
    db.stage(rows, vals)
    db.publish()
    assert db.stats.n_hint_deltas == 0       # no delta fn registered
    expect = DB.copy()
    expect[rows] = vals
    np.testing.assert_array_equal(np.asarray(db.hint("colsum")),
                                  expect.sum(axis=0, dtype=np.uint32))
    assert db.stats.n_hint_builds == 2       # full lazy rebuild


def test_stale_hint_cache_refreshes_on_epoch_bump(mesh):
    """The client-contract half of invalidation: a session caching the
    hint by epoch misses after publish() and fetches the fresh one; the
    retired epoch's hint stays servable for in-flight batches."""
    db, proto, cfg = _lwe_db(mesh)
    cache = {}                               # a client's epoch-keyed cache

    def client_hint(epoch):
        if epoch not in cache:
            cache[epoch] = np.asarray(db.hint(proto.name, epoch=epoch))
        return cache[epoch]

    h0 = client_hint(db.epoch)
    assert client_hint(db.epoch) is h0       # same epoch: cache hit
    rows, vals = _rand_rows(np.random.default_rng(53), 2)
    db.stage(rows, vals)
    db.publish()
    h1 = client_hint(db.epoch)               # stale cache missed: refetch
    assert not np.array_equal(h0, h1)
    # in-flight answers tagged with the retired epoch still reconstruct:
    # the old hint is pinned with the old views (double buffer)
    np.testing.assert_array_equal(
        np.asarray(db.hint(proto.name, epoch=0)), h0)
    # two publishes back the epoch is released, like views
    db.stage(rows[:1], vals[:1])
    db.publish()
    with pytest.raises(KeyError, match="not resident"):
        db.hint(proto.name, epoch=0)


# ---------------------------------------------------------------------------
# config satellite: share_kind fallback is narrow
# ---------------------------------------------------------------------------

def test_share_kind_fallback_only_for_missing_registrations(monkeypatch):
    # unregistered names still resolve by naming convention (KeyError path)
    assert PIRConfig(n_items=N, protocol="additive-frontier-9").share_kind \
        == "additive"
    assert PIRConfig(n_items=N, protocol="xor-frontier-9").share_kind == "xor"
    # ... but a real protocol-plane bug must surface, not degrade silently
    import repro.core.protocol as protocol_mod

    def boom(name):
        raise RuntimeError("protocol plane corrupted")
    monkeypatch.setattr(protocol_mod, "get", boom)
    with pytest.raises(RuntimeError, match="corrupted"):
        PIRConfig(n_items=N).share_kind


# ---------------------------------------------------------------------------
# slow tier: the full compiled serving stack across a publish
# ---------------------------------------------------------------------------

@pytest.mark.slow   # jit-compiles serve steps (~40 s each on this container)
def test_two_server_session_serves_updates(mesh):
    from repro.runtime.serve_loop import TwoServerPIR
    n = 1 << 8
    host = pir.make_database(np.random.default_rng(2), n, 32)
    cfg = PIRConfig(n_items=n, batch_queries=2)
    sys2 = TwoServerPIR(host, cfg, mesh, n_queries=2, buckets=(2,))
    idx = [7, 200]
    np.testing.assert_array_equal(sys2.query(idx), host[idx])
    new_row = RNG.integers(0, 1 << 32, size=(1, 8), dtype=np.uint32)
    sys2.update([7], new_row)
    assert sys2.publish() == 1
    expect = host.copy()
    expect[7] = new_row
    futs = [sys2.submit(i) for i in idx]
    sys2.scheduler.pump()
    np.testing.assert_array_equal(np.stack([f.result(120.0) for f in futs]),
                                  expect[idx])
    assert all(f.epoch == 1 for f in futs)
    # the update path re-used the compiled bucket: no recompiles
    assert all(s.n_compiles == 1 for s in sys2.servers)
