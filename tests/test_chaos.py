"""Chaos plane + verified reconstruction (DESIGN.md §12).

Fast tier throughout (no XLA compiles): FaultPlan/ChaosInjector
mechanics, the row-checksum + verify_records primitives, eager
verified-reconstruction coverage of all four registered protocols
(synthetic shares / numpy LWE oracle), per-query deadlines
(AnswerFuture + the router's hedging reaper), chaos seams in the
scheduler, registry, router publish path and plan cache, seeded backoff
jitter, and the satellite property test driving random fault plans
against a fake-replica fleet — zero lost answers, no silent corruption.
"""
import time

import numpy as np
import pytest

from _prop import given, settings, st
from test_replica import FakeReplica, make_router

from repro.chaos import (ACTIONS, SEAMS, ChaosInjector, FaultEvent,
                         FaultPlan, InjectedFault)
from repro.config import PIRConfig
from repro.core import protocol as protocol_mod
from repro.db.spec import (DatabaseSpec, IntegrityError, row_checksum,
                           verify_records)
from repro.replica import ReplicaLost, ReplicaRegistry, Router
from repro.runtime.fault import RetryStats, retry_step
from repro.runtime.serve_loop import (AnswerFuture, QueryScheduler,
                                      QueryTimeout)


# ---------------------------------------------------------------------------
# FaultPlan / ChaosInjector mechanics
# ---------------------------------------------------------------------------

def test_fault_event_validation():
    with pytest.raises(ValueError):
        FaultEvent(seam="nope", action="kill")
    with pytest.raises(ValueError):
        FaultEvent(seam="heartbeat", action="explode")
    with pytest.raises(ValueError):
        FaultEvent(seam="heartbeat", action="drop", at=-1)
    with pytest.raises(ValueError):
        FaultEvent(seam="heartbeat", action="drop", count=0)


def test_fault_plan_random_is_seed_deterministic():
    p1 = FaultPlan.random(42, targets=("a", "b"))
    p2 = FaultPlan.random(42, targets=("a", "b"))
    assert p1 == p2
    assert p1 != FaultPlan.random(43, targets=("a", "b"))
    for ev in p1.events:
        assert ev.seam in SEAMS and ev.action in ACTIONS
        if ev.action == "corrupt":    # the only share-bearing seam
            assert ev.seam == "replica.serve_step"


def test_injector_visit_window_and_target_matching():
    inj = ChaosInjector(FaultPlan(seed=0, events=(
        FaultEvent("heartbeat", "drop", target="a", at=2, count=2),)))
    assert [inj.should_drop("heartbeat", "a") for _ in range(6)] == \
        [False, False, True, True, False, False]
    assert not inj.should_drop("heartbeat", "b")      # wrong target
    # target None matches any target, with independent visit counters
    inj2 = ChaosInjector(FaultPlan(seed=0, events=(
        FaultEvent("heartbeat", "drop", at=0),)))
    assert inj2.should_drop("heartbeat", "x")
    assert inj2.should_drop("heartbeat", "y")
    assert inj2.fired_actions("heartbeat") == ["drop", "drop"]


def test_injector_kill_raises_and_stall_sleeps_injected():
    inj = ChaosInjector(FaultPlan(seed=0, events=(
        FaultEvent("router.resubmit", "kill", at=0),)))
    with pytest.raises(InjectedFault, match="router.resubmit"):
        inj.visit("router.resubmit")
    sleeps = []
    inj2 = ChaosInjector(FaultPlan(seed=0, events=(
        FaultEvent("db.publish", "stall", at=0, duration_s=1.5),)),
        sleep=sleeps.append)
    inj2.fire("db.publish")
    assert sleeps == [1.5]


def test_corrupt_shares_flips_one_element_deterministically():
    plan = FaultPlan(seed=9, events=(
        FaultEvent("replica.serve_step", "corrupt", at=1),))
    shares = (np.arange(12, dtype=np.uint32).reshape(3, 4),
              np.arange(12, dtype=np.uint32).reshape(3, 4) + 100)
    outs = []
    for _ in range(2):
        inj = ChaosInjector(plan)
        s1 = inj.corrupt_shares("replica.serve_step", None, shares)
        # visit 0 is before the event window: shares pass through intact
        assert all(np.array_equal(a, b) for a, b in zip(s1, shares))
        outs.append(inj.corrupt_shares("replica.serve_step", None, shares))
    # same plan => bit-identical corruption on replay
    assert all(np.array_equal(a, b) for a, b in zip(outs[0], outs[1]))
    diffs = sum(int((np.asarray(a) != np.asarray(b)).sum())
                for a, b in zip(outs[0], shares))
    assert diffs == 1                 # exactly one element of one share
    # and the flip is the repeated-byte top-bit mask
    changed = [k for k, (a, b) in enumerate(zip(outs[0], shares))
               if not np.array_equal(a, b)][0]
    delta = np.asarray(outs[0][changed]) ^ shares[changed]
    assert int(delta.max()) == 0x80808080


# ---------------------------------------------------------------------------
# row checksum + verify_records + DatabaseSpec stored widths
# ---------------------------------------------------------------------------

def test_row_checksum_sensitivity_and_determinism():
    rng = np.random.default_rng(0)
    w = rng.integers(0, 1 << 32, size=(64, 8), dtype=np.uint32)
    c1 = row_checksum(w)
    np.testing.assert_array_equal(c1, row_checksum(w))
    assert c1.dtype == np.uint32
    w2 = w.copy()
    w2[10, 3] ^= np.uint32(1)         # single-bit flip
    c2 = row_checksum(w2)
    assert c2[10] != c1[10]
    np.testing.assert_array_equal(np.delete(c2, 10), np.delete(c1, 10))
    # position-dependent fold: permuting a row's words changes its sum
    w3 = w.copy()
    w3[0] = w[0][::-1]
    assert row_checksum(w3)[0] != c1[0]


def test_verify_records_both_forms_and_bad_indices():
    rng = np.random.default_rng(2)
    w = rng.integers(0, 1 << 32, size=(5, 2), dtype=np.uint32)
    spec = DatabaseSpec(n_items=8, item_bytes=8, checksum=True)
    stored = spec.attach_checksums(w)
    np.testing.assert_array_equal(verify_records(stored, 8), w)
    b = spec.words_to_bytes_host(stored)
    np.testing.assert_array_equal(verify_records(b, 8),
                                  spec.words_to_bytes_host(w))
    bad = stored.copy()
    bad[0, 1] ^= np.uint32(2)
    bad[4, 0] ^= np.uint32(1 << 31)
    with pytest.raises(IntegrityError) as ei:
        verify_records(bad, 8)
    assert ei.value.bad_queries == (0, 4)
    with pytest.raises(ValueError):   # neither stored-width form
        verify_records(np.zeros((2, 7), np.uint8), 8)


def test_spec_stored_widths_and_idempotent_attach():
    spec = DatabaseSpec(n_items=8, item_bytes=8, checksum=True)
    assert (spec.stored_words, spec.stored_bytes) == (3, 12)
    assert spec.view_shape("words") == (8, 3)
    assert spec.view_shape("bytes") == (8, 12)
    w = np.arange(16, dtype=np.uint32).reshape(8, 2)
    st1 = spec.attach_checksums(w)
    np.testing.assert_array_equal(spec.attach_checksums(st1), st1)
    np.testing.assert_array_equal(spec.verify_stored_rows(st1), w)
    bad = st1.copy()
    bad[3, 0] ^= np.uint32(4)
    with pytest.raises(IntegrityError):
        spec.verify_stored_rows(bad)
    off = DatabaseSpec(n_items=8, item_bytes=8)   # checksum off: identity
    assert (off.stored_words, off.stored_bytes) == (2, 8)
    np.testing.assert_array_equal(off.attach_checksums(w), w)
    np.testing.assert_array_equal(off.verify_stored_rows(w), w)


def test_make_database_checksum_layout_and_cache_signature():
    from repro.core import pir
    from repro.engine.cache import spec_signature
    db = pir.make_database(np.random.default_rng(0), 8, 8, checksum=True)
    assert db.shape == (8, 3)
    np.testing.assert_array_equal(db[:, 2], row_checksum(db[:, :2]))
    # checksummed configs get their own plan-cache rows (shape change)
    assert spec_signature(PIRConfig(n_items=8, item_bytes=8,
                                    checksum=True)) == "8x8+c"
    assert spec_signature(PIRConfig(n_items=8, item_bytes=8)) == "8x8"


# ---------------------------------------------------------------------------
# verified reconstruction: every registered protocol, eager (no XLA)
# ---------------------------------------------------------------------------

def test_xor2_verified_reconstruction_detects_share_corruption():
    cfg = PIRConfig(n_items=16, item_bytes=8, checksum=True)
    spec = DatabaseSpec.from_config(cfg)
    rng = np.random.default_rng(0)
    logical = rng.integers(0, 1 << 32, size=(4, 2), dtype=np.uint32)
    stored = spec.attach_checksums(logical)
    s0 = rng.integers(0, 1 << 32, size=stored.shape, dtype=np.uint32)
    s1 = s0 ^ stored
    proto = protocol_mod.for_config(cfg)
    rec = np.asarray(proto.reconstruct_with([s0, s1], [None] * 4, cfg=cfg))
    np.testing.assert_array_equal(rec, logical)   # verified AND stripped
    bad = s1.copy()
    bad[2, 0] ^= np.uint32(0x80808080)
    with pytest.raises(IntegrityError) as ei:
        proto.reconstruct_with([s0, bad], [None] * 4, cfg=cfg)
    assert ei.value.bad_queries == (2,)


def test_additive_verified_reconstruction_detects_byte_flip():
    cfg = PIRConfig(n_items=16, item_bytes=8, protocol="additive-dpf-2",
                    checksum=True)
    spec = DatabaseSpec.from_config(cfg)
    rng = np.random.default_rng(1)
    logical = rng.integers(0, 1 << 32, size=(3, 2), dtype=np.uint32)
    stored_b = spec.words_to_bytes_host(spec.attach_checksums(logical))
    s0 = rng.integers(0, 256, size=stored_b.shape, dtype=np.uint8)
    s1 = ((stored_b.astype(np.int32) - s0) % 256).astype(np.uint8)
    proto = protocol_mod.for_config(cfg)
    rec = np.asarray(proto.reconstruct_with([s0, s1], [None] * 3, cfg=cfg))
    np.testing.assert_array_equal(rec, spec.words_to_bytes_host(logical))
    bad = s1.copy()
    # the 0x80 top-bit flip is +128 mod 256 — never a Z_256 no-op (a
    # bit-31 flip on the int32 accumulator WOULD be: 2^31 ≡ 0 mod 256)
    bad[1, 3] ^= np.uint8(0x80)
    with pytest.raises(IntegrityError) as ei:
        proto.reconstruct_with([s0, bad], [None] * 3, cfg=cfg)
    assert ei.value.bad_queries == (1,)


def test_xor_k_verified_reconstruction_three_shares():
    cfg = PIRConfig(n_items=16, item_bytes=8, protocol="xor-dpf-k",
                    n_servers=3, checksum=True)
    spec = DatabaseSpec.from_config(cfg)
    rng = np.random.default_rng(3)
    logical = rng.integers(0, 1 << 32, size=(2, 2), dtype=np.uint32)
    stored = spec.attach_checksums(logical)
    s0 = rng.integers(0, 1 << 32, size=stored.shape, dtype=np.uint32)
    s1 = rng.integers(0, 1 << 32, size=stored.shape, dtype=np.uint32)
    s2 = s0 ^ s1 ^ stored
    proto = protocol_mod.for_config(cfg)
    rec = np.asarray(proto.reconstruct_with([s0, s1, s2], [None] * 2,
                                            cfg=cfg))
    np.testing.assert_array_equal(rec, logical)
    bad = s0.copy()
    bad[0, 2] ^= np.uint32(0x80808080)   # the checksum word itself
    with pytest.raises(IntegrityError) as ei:
        proto.reconstruct_with([bad, s1, s2], [None] * 2, cfg=cfg)
    assert ei.value.bad_queries == (0,)


def test_lwe_checksum_closes_the_delta_aliasing_gap():
    """The LWE noise bound catches gross corruption, but a shift by a
    multiple of Delta aliases to a clean plaintext shift — noise-check
    blind. The row checksum closes exactly that gap."""
    from repro.core import lwe

    N = 256
    cfg = PIRConfig(n_items=N, item_bytes=8, protocol="lwe-simple-1",
                    n_servers=1, checksum=True)
    spec = DatabaseSpec.from_config(cfg)
    params = lwe.params_for(N)
    rng = np.random.default_rng(0)
    logical = rng.integers(0, 1 << 32, size=(N, 2), dtype=np.uint32)
    stored_b = spec.words_to_bytes_host(spec.attach_checksums(logical))
    hint = lwe.hint_np(params, stored_b).astype(np.uint32)
    proto = protocol_mod.for_config(cfg)
    indices = [3, 200]
    cts, states = [], []
    for i in indices:
        ct, state = lwe.encrypt(rng, i, N, params)
        cts.append(np.asarray(ct.ct).view(np.uint32).astype(np.uint64))
        states.append(state)
    mask = np.uint64(0xFFFFFFFF)
    ans = np.stack([(c @ stored_b.astype(np.uint64)) & mask
                    for c in cts]).astype(np.uint32).view(np.int32)

    rec = np.asarray(proto.reconstruct_with([ans], states, cfg=cfg,
                                            hint=hint))
    np.testing.assert_array_equal(
        rec, spec.words_to_bytes_host(logical)[indices])

    # gross corruption: the analytic noise bound alone catches it
    g = ans.copy()
    g.view(np.uint32)[0, 0] ^= np.uint32(0x80808080)
    with pytest.raises(IntegrityError, match="noise overflow"):
        proto.reconstruct_with([g], states, cfg=cfg, hint=hint)

    # Delta-multiple shift: residual noise unchanged, decoded byte off by
    # one — invisible to the noise check, caught by the checksum
    d = ans.copy()
    dv = d.view(np.uint32)
    dv[1, 2] = np.uint32((int(dv[1, 2]) + params.delta) & 0xFFFFFFFF)
    with pytest.raises(IntegrityError, match="checksum"):
        proto.reconstruct_with([d], states, cfg=cfg, hint=hint)

    # ... and without the checksum column the same shift IS silent
    # corruption (treat the stored layout as a checksum-less 12-byte db)
    cfg0 = PIRConfig(n_items=N, item_bytes=12, protocol="lwe-simple-1",
                     n_servers=1)
    proto0 = protocol_mod.for_config(cfg0)
    rec0 = np.asarray(proto0.reconstruct_with([d], states, cfg=cfg0,
                                              hint=hint))
    np.testing.assert_array_equal(rec0[0], stored_b[indices[0]])
    assert not np.array_equal(rec0[1], stored_b[indices[1]])


# ---------------------------------------------------------------------------
# per-query deadlines: AnswerFuture + QueryTimeout context
# ---------------------------------------------------------------------------

def test_answer_future_deadline_drives_result_timeout():
    fut = AnswerFuture(deadline=time.monotonic() + 0.05)
    fut.context.update(session="s7", bucket=4, replica="r1")
    with pytest.raises(QueryTimeout) as ei:
        fut.result()                 # no explicit timeout: deadline rules
    msg = str(ei.value)
    for frag in ("session=s7", "bucket=4", "replica=r1", "elapsed=",
                 "deadline_over_by="):
        assert frag in msg, f"{frag!r} missing from {msg!r}"
    fut2 = AnswerFuture()            # no deadline: explicit timeout only
    with pytest.raises(QueryTimeout):
        fut2.result(timeout=0.01)


def test_answer_future_deadline_is_no_obstacle_once_resolved():
    fut = AnswerFuture(deadline=time.monotonic() - 1.0)   # already past
    fut.set_result("late but landed")
    assert fut.result() == "late but landed"


# ---------------------------------------------------------------------------
# router deadlines: the reaper hedges at half budget, expires at deadline
# ---------------------------------------------------------------------------

def test_reap_hedges_at_half_budget_then_first_answer_wins():
    t = [0.0]
    router, (r0, r1) = make_router(clock=lambda: t[0])
    s = router.session("dl")
    s.replica = "r0"
    fut = router.submit(5, session=s, deadline_s=10.0)
    assert (r0.queue_depth, r1.queue_depth) == (1, 0)
    assert router.reap() == {"expired": 0, "hedged": 0}   # budget fresh
    t[0] = 5.0
    assert router.reap() == {"expired": 0, "hedged": 1}
    assert r1.queue_depth == 1       # resubmitted, excluding the holder
    assert router.hedges == 1
    assert router.reap()["hedged"] == 0                   # once per query
    r1.pump()
    assert fut.result(0) == ("ans", 5, "r1")
    r0.pump()                        # straggler's late duplicate answer
    assert fut.result(0) == ("ans", 5, "r1")              # first wins
    assert router.reap() == {"expired": 0, "hedged": 0}
    assert router._pending_q == {}   # resolved futures leave the table


def test_reap_expires_past_deadline_with_query_context():
    t = [0.0]
    router, (r0, r1) = make_router(clock=lambda: t[0])
    s = router.session("sess-42")
    s.replica = "r0"
    fut = router.submit(9, session=s, deadline_s=4.0)
    t[0] = 4.5
    out = router.reap()
    assert out["expired"] == 1 and router.deadline_expired == 1
    with pytest.raises(QueryTimeout) as ei:
        fut.result(0)
    msg = str(ei.value)
    assert "session=sess-42" in msg and "deadline_over_by" in msg
    assert router._pending_q == {}


def test_submit_without_deadline_stays_out_of_the_pending_table():
    router, (r0, r1) = make_router()
    router.submit(1)
    assert router._pending_q == {}
    assert router.reap() == {"expired": 0, "hedged": 0}


# ---------------------------------------------------------------------------
# router integrity failover: a corrupting replica is unfit to serve
# ---------------------------------------------------------------------------

class IntegrityFakeReplica(FakeReplica):
    """pump() fails every queued future with IntegrityError — the shape
    a corrupted answer surfaces in after verified reconstruction."""

    def pump(self):
        q, self._q = self._q, []
        for _item, fut in q:
            fut.set_exception(IntegrityError(
                "checksum mismatch on 1/1 reconstructed record(s)",
                bad_queries=(0,)))
        return len(q)


def test_integrity_error_quarantines_and_resubmits():
    router = Router(rng=np.random.default_rng(0), sleep=lambda s: None)
    bad = router.attach(IntegrityFakeReplica("bad"))
    good = router.attach(FakeReplica("good"))
    s = router.session("c")
    s.replica = "bad"
    futs = [router.submit(i, session=s) for i in range(3)]
    bad.pump()                       # integrity failures -> failover
    assert "bad" in router.registry.suspects()
    assert router.integrity_failures == 3
    assert good.queue_depth == 3
    good.pump()
    assert [f.result(0) for f in futs] == [("ans", i, "good")
                                           for i in range(3)]


# ---------------------------------------------------------------------------
# chaos seams: scheduler dispatch, registry heartbeat, publish, plan cache
# ---------------------------------------------------------------------------

def _mini_scheduler(chaos=None, target=None):
    return QueryScheduler(
        collate=list, stage=lambda p: p, dispatch=lambda s: s,
        finalize=lambda raw, n: raw[:n], buckets=(2,), max_wait_s=0.001,
        chaos=chaos, chaos_target=target)


def test_scheduler_dispatch_kill_resolves_every_future():
    inj = ChaosInjector(FaultPlan(seed=0, events=(
        FaultEvent("scheduler.dispatch", "kill", at=0),)))
    sched = _mini_scheduler(chaos=inj)
    sched.start()
    futs = [sched.submit(i) for i in range(6)]
    errors = 0
    for f in futs:                   # nothing hangs: every future resolves
        try:
            f.result(timeout=10.0)
        except InjectedFault:
            errors += 1
    assert errors >= 2               # at least the killed batch
    assert inj.fired_actions("scheduler.dispatch") == ["kill"]
    with pytest.raises(RuntimeError):
        sched.submit(99)             # dead session rejects new work


def test_chaos_heartbeat_drop_ages_replica_into_suspicion():
    t = [0.0]
    reg = ReplicaRegistry(timeout=10.0, clock=lambda: t[0])
    a, b = FakeReplica("a"), FakeReplica("b")
    reg.join(a)
    reg.join(b)
    reg.chaos = ChaosInjector(FaultPlan(seed=0, events=(
        FaultEvent("heartbeat", "drop", target="a", at=0, count=10),)))
    t[0] = 11.0
    reg.beat("a")                    # dropped: never reaches last_seen
    reg.beat("b")
    assert reg.suspects() == ["a"]


def test_chaos_publish_drop_lags_replica_then_converges():
    inj = ChaosInjector(FaultPlan(seed=0, events=(
        FaultEvent("db.publish", "drop", target="r1", at=0),)))
    router, (r0, r1) = make_router(chaos=inj)
    router.update([1], np.full((1, 8), 1, np.uint32))
    router.publish()
    assert (r0.epoch, r1.epoch) == (1, 0)     # r1 missed the fan-out
    assert router.epoch_lag("r1") == 1
    router.update([2], np.full((1, 8), 2, np.uint32))
    router.publish()                 # delta-log replay converges r1
    assert (r0.epoch, r1.epoch) == (2, 2)


def test_plan_cache_chaos_load_degrades_never_crashes(tmp_path):
    import json
    from repro.engine.cache import PlanCache
    path = str(tmp_path / "cache.json")
    with open(path, "w") as f:
        json.dump({"schema": 1, "plans": {}}, f)
    assert PlanCache(path).load_error is None   # healthy load
    for action in ("drop", "kill"):
        inj = ChaosInjector(FaultPlan(seed=0, events=(
            FaultEvent("plan_cache.load", action, at=0),)))
        pc = PlanCache(path, chaos=inj)
        assert pc.load_error is not None        # degraded, remembered why
        assert pc.plans == {}                   # ... to heuristic-only


# ---------------------------------------------------------------------------
# seeded backoff jitter (runtime.fault.retry_step)
# ---------------------------------------------------------------------------

def _always_fail():
    raise RuntimeError("transient")


def test_retry_backoff_jitter_is_seeded_capped_and_accounted():
    sleeps1, stats1 = [], RetryStats()
    with pytest.raises(RuntimeError):
        retry_step(_always_fail, retries=4, base_delay=1.0, max_delay=4.0,
                   sleep=sleeps1.append, jitter=0.5,
                   rng=np.random.default_rng(5), stats=stats1)
    sleeps2 = []
    with pytest.raises(RuntimeError):
        retry_step(_always_fail, retries=4, base_delay=1.0, max_delay=4.0,
                   sleep=sleeps2.append, jitter=0.5,
                   rng=np.random.default_rng(5))
    assert sleeps1 == sleeps2        # seeded rng: bit-identical replay
    base = [1.0, 2.0, 4.0, 4.0]
    assert sleeps1 != base           # jitter actually moved the delays
    for s, b in zip(sleeps1, base):
        assert 0.5 * b <= s <= min(1.5 * b, 4.0)   # spread AND re-capped
    assert stats1.slept_s == sum(sleeps1)          # actual, not nominal
    assert stats1.retried == 4 and stats1.attempts == 5


def test_retry_backoff_without_jitter_keeps_exact_schedule():
    sleeps = []
    with pytest.raises(RuntimeError):
        retry_step(_always_fail, retries=4, base_delay=1.0, max_delay=4.0,
                   sleep=sleeps.append)
    assert sleeps == [1.0, 2.0, 4.0, 4.0]


# ---------------------------------------------------------------------------
# satellite property test: random fault plans over a fake fleet
# ---------------------------------------------------------------------------

class ChaosFakeReplica(FakeReplica):
    """FakeReplica serving real checksummed rows through a ChaosInjector.

    ``pump()`` resolves each queued query the way the real serve stack
    would: a ``kill`` event fails everything with ReplicaLost, a
    ``corrupt`` event trips ``verify_records`` into IntegrityError, and
    clean rows resolve to the logical payload words. Publishes fan in
    through the FakeDB subscription, keeping the stored rows current.
    """

    def __init__(self, rid, spec, stored_words, injector):
        super().__init__(rid)
        self.spec = spec
        self.rows = np.array(stored_words)
        self.injector = injector
        self.db.subscribe(self._apply_delta)

    def _apply_delta(self, delta):
        vals = self.spec.attach_checksums(
            self.spec.coerce_rows_to_words(np.asarray(delta.vals)))
        self.rows[np.asarray(delta.rows)] = vals

    def pump(self):
        q, self._q = self._q, []
        n = 0
        for item, fut in q:
            if self.lost:
                fut.set_exception(ReplicaLost(self.id, "chaos kill"))
                continue
            try:
                (row,) = self.injector.corrupt_shares(
                    "replica.serve_step", self.id,
                    (self.rows[int(item)],))
            except InjectedFault:
                self.kill("chaos kill")       # clears + fails the queue
                fut.set_exception(ReplicaLost(self.id, "chaos kill"))
                continue
            try:
                payload = verify_records(row[None, :],
                                         self.spec.item_bytes)[0]
            except IntegrityError as e:
                fut.set_exception(e)          # never a silently wrong row
                continue
            fut.epoch = self.db.epoch
            fut.set_result(np.array(payload))
            n += 1
        return n


@settings(max_examples=10, deadline=None)
@given(st.integers(min_value=0, max_value=(1 << 32) - 1))
def test_random_fault_plans_never_lose_or_corrupt_answers(seed):
    """Under ANY seeded fault plan: every submitted future resolves
    (zero lost answers), every resolved RESULT is byte-correct (silent
    corruption is impossible — corruption surfaces as IntegrityError and
    is retried), the session's min_epoch ratchet stays within the
    front-tier epoch, and a fired corruption is always counted by the
    router (it can never slip through as data)."""
    spec = DatabaseSpec(n_items=32, item_bytes=8, checksum=True)
    data_rng = np.random.default_rng(123)
    logical = data_rng.integers(0, 1 << 32, size=(32, 2), dtype=np.uint32)
    stored = spec.attach_checksums(logical)

    plan = FaultPlan.random(
        seed, targets=("r0", "r1", "r2", None),
        seams=("replica.serve_step", "heartbeat", "db.publish"),
        actions=("corrupt", "kill", "drop"), n_events=5, max_at=6)
    injector = ChaosInjector(plan)
    t = [0.0]
    reg = ReplicaRegistry(timeout=30.0, clock=lambda: t[0])
    reg.chaos = injector
    router = Router(registry=reg, rng=np.random.default_rng(1),
                    sleep=lambda s: None, retries=6, chaos=injector)
    reps = [router.attach(ChaosFakeReplica(f"r{i}", spec, stored,
                                           injector))
            for i in range(3)]

    s = router.session("prop")
    indices = [1 + (i % (spec.n_items - 1)) for i in range(12)]
    futs = [router.submit(j, session=s) for j in indices]
    # exercise the publish fan-out (and its chaos drops) mid-load; only
    # row 0 changes, and no query targets row 0 — answers stay stable
    router.update([0], np.full((1, spec.item_words), 7, np.uint32))
    router.publish()

    for _ in range(24):
        if all(f.done() for f in futs):
            break
        for r in reps:
            if not r.lost:
                r.pump()
    assert all(f.done() for f in futs), "lost answers under chaos"

    for j, f in zip(indices, futs):
        if f.exception() is None:
            np.testing.assert_array_equal(np.asarray(f.result(0)),
                                          logical[j])
            assert f.epoch is not None
            assert f.epoch <= router.published_epoch
    assert 0 <= s.min_epoch <= router.published_epoch
    visits = {a: {(f.target, f.visit) for f in injector.fired
                  if f.seam == "replica.serve_step" and f.action == a}
              for a in ("corrupt", "kill")}
    if visits["corrupt"] - visits["kill"]:
        # every applied corruption became a counted IntegrityError — the
        # "never silent" half of the contract. A kill on the same visit
        # raises before any share is touched, so that corruption never
        # reaches a client.
        assert router.integrity_failures >= 1
