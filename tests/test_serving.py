"""Serving frontend: dynamic batching, pipelining, straggler shedding.

Fast tier: the scheduler's control plane driven by fake collate/stage/
dispatch/finalize callables (no XLA compiles, deterministic), one
profiler trace of a small real query (one compiled step per party, a
few seconds) and one of a database placement. Slow tier:
the real two-party protocol through the scheduler — ragged batch sizes,
bucket-cache reuse, and the streaming session API — sharing one pair of
compiled serve steps across the module (compiles cost ~40 s each on this
container).
"""
import glob
import threading
import time

import jax
import numpy as np
import pytest

from repro.config import PIRConfig
from repro.core import dpf, pir
from repro.db import ShardedDatabase
from repro.db.sharded import _place_span
from repro.launch.mesh import make_local_mesh
from repro.runtime.fault import StragglerMonitor
from repro.runtime.serve_loop import (DEFAULT_MAX_WAIT_S, LATENCY_WINDOW,
                                      AnswerFuture, MultiServerPIR,
                                      QueryScheduler, TwoServerPIR,
                                      _reconstruct_span)

# ---------------------------------------------------------------------------
# control plane (fast: fake data plane)
# ---------------------------------------------------------------------------


def make_fake_scheduler(log=None, buckets=(2, 4), n_clusters=1, **kw):
    """Scheduler whose 'device' doubles each item; logs stage/dispatch/
    finalize events so tests can assert pipeline interleaving."""
    log = log if log is not None else []

    def collate(items):
        return list(items)

    def stage(payload):
        log.append(("stage", tuple(payload)))
        # padding rule: replicate the last item up to the bucket
        b = next(bb for bb in sorted(buckets) if bb >= len(payload))
        return payload + [payload[-1]] * (b - len(payload))

    def dispatch(staged):
        log.append(("dispatch", tuple(staged)))
        return [x * 2 for x in staged]

    def finalize(raw, n):
        log.append(("finalize", tuple(raw[:n])))
        return raw[:n]

    return QueryScheduler(collate=collate, stage=stage, dispatch=dispatch,
                          finalize=finalize, buckets=buckets,
                          n_clusters=n_clusters, **kw), log


def test_coalesce_pad_and_answer_order():
    sched, _ = make_fake_scheduler(buckets=(2, 4))
    futs = [sched.submit(i) for i in range(5)]       # 4 cut eagerly, 1 left
    n = sched.pump()                                 # flush cuts the tail
    assert n == 5
    assert [f.result(0) for f in futs] == [0, 2, 4, 6, 8]
    assert sched.stats.batches == 2
    assert sched.stats.bucket_counts == {4: 1, 2: 1}
    assert sched.stats.padded == 1                   # 1 query in a 2-bucket
    assert 0 < sched.stats.pad_fraction < 1


def test_double_buffer_stages_next_before_completing_current():
    sched, log = make_fake_scheduler(buckets=(2,))
    for i in range(6):
        sched.submit(i)                              # three 2-query batches
    sched.pump()
    kinds = [k for k, _ in log]
    # batch 2 must be staged AND dispatched before batch 1 finalizes
    assert kinds.index("finalize") > kinds.index("dispatch", 1)
    assert kinds == ["stage", "dispatch", "stage", "dispatch", "finalize",
                     "stage", "dispatch", "finalize", "finalize"]


def test_ragged_bucket_selection():
    sched, _ = make_fake_scheduler(buckets=(2, 4, 8))
    futs = [sched.submit(i) for i in range(3)]
    sched.pump()
    assert [f.result(0) for f in futs] == [0, 2, 4]
    assert sched.stats.bucket_counts == {4: 1}       # 3 -> smallest cover
    assert sched.stats.padded == 1


def test_straggler_reassignment_sheds_queued_batches():
    mon = StragglerMonitor(factor=2.0, alpha=1.0)
    mon.record("cluster0", 50.0)                     # cluster0 is flagged
    mon.record("cluster1", 1.0)
    mon.record("cluster2", 1.1)
    sched, _ = make_fake_scheduler(buckets=(2,), n_clusters=3, monitor=mon)
    for i in range(12):                              # 6 batches round-robin
        sched.submit(i)
    sched.flush()
    assert len(sched.queues["cluster0"]) == 2
    moved = sched.rebalance()
    assert moved == 2
    assert sched.stats.reassignments == 2
    assert sched.queues["cluster0"] == []
    relocated = [b for lane in ("cluster1", "cluster2")
                 for b in sched.queues[lane]]
    assert len(relocated) == 6                       # nothing lost
    for lane in ("cluster1", "cluster2"):
        for b in sched.queues[lane]:
            assert b.cluster == lane                 # ownership rewritten
    # queued work still completes after shedding
    assert sched.pump() == 12


def test_failure_propagates_to_futures():
    def boom(raw, n):
        raise RuntimeError("device lost")
    sched = QueryScheduler(collate=list, stage=lambda p: p,
                           dispatch=lambda s: s, finalize=boom,
                           buckets=(2,))
    futs = [sched.submit(i) for i in range(2)]
    with pytest.raises(RuntimeError):
        sched.pump()
    with pytest.raises(RuntimeError, match="device lost"):
        futs[0].result(0)
    assert futs[1].done()


def test_background_session_thread():
    sched, _ = make_fake_scheduler(buckets=(2, 4), max_wait_s=0.001)
    sched.start()
    try:
        futs = [sched.submit(i) for i in range(7)]
        assert [f.result(10.0) for f in futs] == [2 * i for i in range(7)]
        # under-full tail was cut by the max_wait timer, not lost
        assert sched.stats.answered == 7
    finally:
        sched.stop()
    assert not sched.running
    # stop() drains: a post-stop pump has nothing left
    assert sched.pump() == 0


def test_underfull_batch_waits_for_the_clients_a_completion_answered():
    """An under-full batch is cut only once ``max_wait_s`` has also passed
    since the last completion: the queries pending when a batch completes
    and the next query of a client it answered leave as one batch, so a
    closed loop's groups merge instead of keeping its first cut."""
    t = [0.0]
    gates = [threading.Event(), threading.Event()]
    dispatched = []

    def stage(payload):
        b = next(bb for bb in (1, 2, 4) if bb >= len(payload))
        return payload + [payload[-1]] * (b - len(payload))

    def dispatch(staged):
        dispatched.append(tuple(staged))
        return staged, gates[len(dispatched) - 1]

    def finalize(raw, n):
        staged, gate = raw
        assert gate.wait(30.0)
        return [x * 2 for x in staged[:n]]

    def wait_dispatched(n):
        deadline = time.monotonic() + 30.0
        while len(dispatched) < n and time.monotonic() < deadline:
            time.sleep(0.001)
        assert len(dispatched) == n

    sched = QueryScheduler(collate=list, stage=stage, dispatch=dispatch,
                           finalize=finalize, buckets=(1, 2, 4),
                           max_wait_s=0.005, clock=lambda: t[0])
    sched.start()
    try:
        f0 = sched.submit(0)
        t[0] = 0.01                      # q0 ripe: no completion yet
        wait_dispatched(1)
        pending = [sched.submit(1), sched.submit(2)]
        t[0] = 1.0                       # both long ripe by their own age
        gates[0].set()
        assert f0.result(30.0) == 0      # completion at t = 1.0
        pending.append(sched.submit(3))  # the answered client's next query
        time.sleep(0.05)
        assert len(dispatched) == 1      # held: no max_wait_s since then
        t[0] = 1.006
        wait_dispatched(2)
        assert dispatched[1] == (1, 2, 3, 3)     # one batch, bucket 4
        gates[1].set()
        assert [f.result(30.0) for f in pending] == [2, 4, 6]
    finally:
        gates[1].set()
        sched.stop()


def test_underfull_batch_waits_while_a_batch_is_in_flight():
    """Queries ripe by both clocks are not cut under-full while a batch
    is dispatched: they queue for the device anyway, so they wait there
    for company. Here two queries arrive during a launch that takes a
    second; the next two complete the bucket, and all four leave as one
    batch once the first completes."""
    t = [0.0]
    gates = [threading.Event(), threading.Event()]
    dispatched = []
    late = []

    def dispatch(staged):
        dispatched.append(tuple(staged))
        if len(dispatched) == 1:         # two queries arrive mid-launch
            late.extend(sched.submit(q) for q in (10, 11))
            t[0] = 1.0
        return staged, gates[len(dispatched) - 1]

    def finalize(raw, n):
        staged, gate = raw
        assert gate.wait(30.0)
        return [x * 2 for x in staged[:n]]

    sched = QueryScheduler(collate=list, stage=lambda p: p,
                           dispatch=dispatch, finalize=finalize,
                           buckets=(1, 2, 4), max_wait_s=0.005,
                           clock=lambda: t[0])
    sched.start()
    try:
        first = [sched.submit(q) for q in range(4)]      # cut at submit
        deadline = time.monotonic() + 30.0
        while len(late) < 2 and time.monotonic() < deadline:
            time.sleep(0.001)
        time.sleep(0.05)
        assert dispatched == [(0, 1, 2, 3)]      # (10, 11) held, not cut
        late.extend(sched.submit(q) for q in (12, 13))
        gates[0].set()
        assert [f.result(30.0) for f in first] == [0, 2, 4, 6]
        gates[1].set()
        assert [f.result(30.0) for f in late] == [20, 22, 24, 26]
        assert dispatched[1] == (10, 11, 12, 13)
    finally:
        for gate in gates:
            gate.set()
        sched.stop()


def test_submit_after_stop_raises():
    """submit() on a stopped session must raise, not enqueue into a dead
    loop (the future would otherwise never resolve)."""
    sched, _ = make_fake_scheduler(buckets=(2,), max_wait_s=0.001)
    sched.start()
    fut = sched.submit(1)
    sched.stop()
    assert fut.result(10.0) == 2              # stop() drains in-flight work
    with pytest.raises(RuntimeError, match="stop"):
        sched.submit(2)
    assert sched.pump() == 0                  # pump stays a harmless no-op
    # start() reopens the session: submit works again, then closes again
    sched.start()
    fut2 = sched.submit(3)
    sched.stop()
    assert fut2.result(10.0) == 6
    with pytest.raises(RuntimeError, match="stop"):
        sched.submit(4)
    # a never-started scheduler keeps the synchronous submit+pump mode
    sync_sched, _ = make_fake_scheduler(buckets=(2,))
    sync_sched.stop()                         # no-op: nothing ran yet
    futs = [sync_sched.submit(i) for i in range(2)]
    sync_sched.pump()
    assert [f.result(0) for f in futs] == [0, 2]


def test_submit_after_thread_death_raises():
    """A dead (errored) session thread must also reject new submits."""
    def boom(raw, n):
        raise RuntimeError("device lost")
    sched = QueryScheduler(collate=list, stage=lambda p: p,
                           dispatch=lambda s: s, finalize=boom,
                           buckets=(1,), max_wait_s=0.001)
    sched.start()
    with pytest.raises(RuntimeError, match="device lost"):
        sched.submit(1).result(timeout=30.0)
    deadline = time.monotonic() + 30.0
    while sched.running and time.monotonic() < deadline:
        time.sleep(0.01)                      # thread exits after _fail
    with pytest.raises(RuntimeError, match="stop"):
        sched.submit(2)


def test_session_thread_death_resolves_every_future():
    """A data-plane failure must fail ALL outstanding futures, not hang
    the clients whose batches were queued behind the poisoned one."""
    def boom(raw, n):
        raise RuntimeError("poisoned batch")
    sched = QueryScheduler(collate=list, stage=lambda p: p,
                           dispatch=lambda s: s, finalize=boom,
                           buckets=(2,), max_wait_s=0.001)
    futs = [sched.submit(i) for i in range(6)]     # 3 batches outstanding
    sched.start()
    for f in futs:
        with pytest.raises(RuntimeError, match="poisoned batch"):
            f.result(timeout=30.0)
    sched.stop()


def test_shed_never_assigns_onto_idle_stragglers():
    """A flagged lane with an empty queue is still slow: it must not be a
    reassignment receiver."""
    mon = StragglerMonitor(factor=2.0, alpha=1.0)
    for lane, lat in (("c0", 100.0), ("c1", 100.0), ("c2", 1.0),
                      ("c3", 1.0), ("c4", 1.0)):
        mon.record(lane, lat)
    assert sorted(mon.stragglers()) == ["c0", "c1"]
    queues = {"c0": [], "c1": ["a", "b"], "c2": [], "c3": [], "c4": []}
    out, moved = mon.shed_stragglers(queues)
    assert moved == 2
    assert out["c0"] == [] and out["c1"] == []     # c0 received nothing
    assert sorted(sum((out[c] for c in ("c2", "c3", "c4")), [])) == ["a", "b"]


def test_two_server_facade_rejects_k_party_protocols_before_building():
    """The alias validates up front — no k DB replicas built just to
    throw away on the ValueError."""
    from repro.config import PIRConfig
    from repro.launch.mesh import make_local_mesh
    cfg = PIRConfig(n_items=1 << 6, protocol="xor-dpf-k", n_servers=3)
    db = pir.make_database(np.random.default_rng(0), 1 << 6, 32)
    with pytest.raises(ValueError, match="2-party"):
        TwoServerPIR(db, cfg, make_local_mesh(), n_queries=2, buckets=(2,))


def test_answer_future_timeout():
    fut = AnswerFuture()
    with pytest.raises(TimeoutError):
        fut.result(timeout=0.01)
    fut.set_result(41)
    assert fut.done() and fut.result() == 41


def test_answer_future_first_wins_and_callbacks():
    """First resolution wins; later set_result/set_exception are ignored
    (what makes the router's kill-vs-complete race benign). Callbacks
    fire exactly once, immediately when already done."""
    fut = AnswerFuture()
    seen = []
    fut.add_done_callback(lambda f: seen.append(f.result(0)))
    assert fut.set_result(1) is True
    assert fut.set_result(2) is False            # ignored
    assert fut.set_exception(RuntimeError("late")) is False
    assert fut.result(0) == 1 and fut.exception() is None
    assert seen == [1]
    fut.add_done_callback(lambda f: seen.append(f.result(0)))
    assert seen == [1, 1]                        # immediate on a done future
    # exception-first symmetric case
    bad = AnswerFuture()
    bad.set_exception(RuntimeError("dead"))
    assert bad.set_result(3) is False
    assert isinstance(bad.exception(), RuntimeError)


def test_queue_depth_counts_pending_queued_and_inflight():
    sched, _ = make_fake_scheduler(buckets=(2, 4))
    assert sched.queue_depth == 0
    for i in range(5):                           # 4 cut into a lane, 1 pending
        sched.submit(i)
    assert sched.queue_depth == 5                # pad slots excluded
    sched.pump()
    assert sched.queue_depth == 0


def test_drain_handoff_moves_undispatched_futures():
    """Graceful leave: queued + pending pairs come back FIFO with their
    ORIGINAL futures; resubmitting them under future= on another
    scheduler resolves the same handles the clients already hold."""
    src, _ = make_fake_scheduler(buckets=(2, 4))
    futs = [src.submit(i) for i in range(5)]     # batch of 4 + 1 pending
    pairs = src.drain_handoff()
    assert [item for item, _ in pairs] == [0, 1, 2, 3, 4]   # FIFO
    assert [f for _, f in pairs] == futs                    # same handles
    with pytest.raises(RuntimeError, match="stop"):
        src.submit(9)                            # intake closed
    assert src.pump() == 0                       # nothing left behind
    dst, _ = make_fake_scheduler(buckets=(2, 4))
    for item, fut in pairs:
        assert dst.submit(item, future=fut) is fut
    dst.pump()
    assert [f.result(0) for f in futs] == [0, 2, 4, 6, 8]


def test_kill_fails_all_outstanding_first_wins():
    sched, _ = make_fake_scheduler(buckets=(2, 4))
    futs = [sched.submit(i) for i in range(5)]
    done_early = futs[0]
    done_early.set_result("beat the kill")       # completes before the kill
    sched.kill(RuntimeError("replica lost"))
    for f in futs[1:]:
        assert f.done()
        with pytest.raises(RuntimeError, match="replica lost"):
            f.result(0)
    assert done_early.result(0) == "beat the kill"   # first-wins preserved
    with pytest.raises(RuntimeError, match="stop"):
        sched.submit(9)


def test_kill_aborts_running_session_and_resolves_everything():
    sched, _ = make_fake_scheduler(buckets=(2,), max_wait_s=60.0)
    sched.start()
    try:
        futs = [sched.submit(i) for i in range(3)]   # 1 batch + 1 pending
        sched.kill(RuntimeError("injected fault"))
        for f in futs:
            with pytest.raises(RuntimeError, match="injected fault"):
                f.result(timeout=30.0)
        deadline = time.monotonic() + 30.0
        while sched.running and time.monotonic() < deadline:
            time.sleep(0.01)
        assert not sched.running                 # loop aborted, not hung
    finally:
        sched.stop()


def test_scheduler_heartbeat_fires_per_pump_and_loop():
    beats = []
    sched, _ = make_fake_scheduler(buckets=(2,), heartbeat=lambda:
                                   beats.append(1))
    sched.submit(0), sched.submit(1)
    sched.pump()
    assert len(beats) >= 1                       # pump beats
    n = len(beats)
    sched.start()
    try:
        fut = sched.submit(2)
        sched.submit(3)
        fut.result(timeout=30.0)
    finally:
        sched.stop()
    assert len(beats) > n                        # session loop beats too


def test_queue_wait_sums_submit_to_launch_per_answered_query():
    """queue_wait_s adds, per real query, its batch's launch start minus
    its submit time on the scheduler's clock, once the batch completes;
    every future carries its batch's number."""
    t = [0.0]

    def dispatch(staged):
        t[0] += 1.0                                  # each launch takes 1 s
        return [x * 2 for x in staged]

    sched = QueryScheduler(collate=list, stage=lambda p: p,
                           dispatch=dispatch, finalize=lambda raw, n: raw[:n],
                           buckets=(2,), clock=lambda: t[0])
    futs = []
    for t_submit in (0.0, 1.0, 3.0, 3.5, 4.0):       # batches (0,1) (3,3.5)
        t[0] = t_submit
        futs.append(sched.submit(len(futs)))
    assert sched.stats.queue_wait_s == 0.0           # nothing completed yet
    t[0] = 10.0
    assert sched.pump() == 5                         # (4) cut by the flush
    # launches at 10, 11 and 12 (each dispatch advances the clock by 1)
    assert sched.stats.queue_wait_s == (10 - 0) + (10 - 1) + (11 - 3) + (
        11 - 3.5) + (12 - 4)
    assert [f.context["batch"] for f in futs] == [0, 0, 1, 1, 2]
    assert [f.result(0) for f in futs] == [0, 2, 4, 6, 8]


def test_serve_stats_keep_only_recent_latencies():
    sched, _ = make_fake_scheduler(buckets=(1,))
    for i in range(LATENCY_WINDOW + 5):
        sched.submit(i)
    sched.pump()
    assert sched.stats.batches == LATENCY_WINDOW + 5
    assert len(sched.stats.latencies) == LATENCY_WINDOW


def test_pad_keys_replicates_last_key():
    k0, _ = dpf.gen_keys(np.random.default_rng(0), 3, 5)
    batch = dpf.stack_keys([k0, k0])
    padded = dpf.pad_keys(batch, 4)
    assert dpf.n_queries_of(padded) == 4
    np.testing.assert_array_equal(np.asarray(padded.root_seed[3]),
                                  np.asarray(batch.root_seed[-1]))
    assert padded.cw_seed.shape == (4,) + batch.cw_seed.shape[1:]
    with pytest.raises(ValueError):
        dpf.pad_keys(batch, 1)


def _host_spans(tracedir, prefix):
    """``(name, start_ns, end_ns, metadata)`` of the host-plane events
    whose names start with ``prefix``, from the one trace under
    ``tracedir``."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{tracedir}/**/*.xplane.pb", recursive=True)
    return sorted(
        (e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
        for plane in ProfileData.from_file(path).planes
        if plane.name == "/host:CPU"
        for line in plane.lines for e in line.events
        if e.name.startswith(prefix))


def test_profiler_spans_join_requests_to_their_batch(tmp_path):
    """A traced query shows each request's ``pir.gen_lock`` then
    ``pir.gen``, and its batch's ``pir.reconstruct`` after both; the
    ``request``/``batch`` metadata match the futures' context."""
    n = 1 << 6
    db = pir.make_database(np.random.default_rng(0), n, 32)
    system = MultiServerPIR(db, PIRConfig(n_items=n, item_bytes=32),
                            make_local_mesh(), n_queries=2, buckets=(2,),
                            client_rng=np.random.default_rng(1))
    system.query([1, 2])                             # compile outside the trace
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        futs = [system.submit(i) for i in (5, 60)]
        system.scheduler.pump()
        rows = [f.result(0) for f in futs]
    finally:
        jax.profiler.stop_trace()
    np.testing.assert_array_equal(np.stack(rows), db[[5, 60]])
    spans = _host_spans(tmp_path, "pir.")
    by = {}
    for name, s, e, meta in spans:
        by.setdefault(name, []).append((s, e, meta))
    assert sorted(by) == ["pir.gen", "pir.gen_lock", "pir.reconstruct"]
    (rec_s, _, rec_meta), = by["pir.reconstruct"]
    assert rec_meta == {"batch": futs[0].context["batch"], "bucket": 2,
                        "n": 2}
    assert futs[1].context["batch"] == futs[0].context["batch"]
    assert futs[0].context["request"] + 1 == futs[1].context["request"]
    for fut in futs:
        request = fut.context["request"]
        (lock_s, lock_e, _), = [x for x in by["pir.gen_lock"]
                                if x[2] == {"request": request}]
        (gen_s, gen_e, _), = [x for x in by["pir.gen"]
                              if x[2] == {"request": request}]
        assert lock_s <= lock_e <= gen_s < gen_e <= rec_s


def test_profiler_span_over_database_placement(tmp_path):
    """Placing a database shows one ``pir.db_place`` span carrying its
    ``rows``, ``bytes`` and ``shards``."""
    n = 1 << 6
    db = pir.make_database(np.random.default_rng(0), n, 32)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        placed = ShardedDatabase(db, PIRConfig(n_items=n, item_bytes=32),
                                 make_local_mesh())
    finally:
        jax.profiler.stop_trace()
    np.testing.assert_array_equal(np.asarray(placed.view("words")), db)
    (name, start, end, meta), = _host_spans(tmp_path, "pir.db_place")
    assert start < end
    assert meta == {"rows": n, "bytes": n * 32, "shards": 1}


def test_placement_span_costs_what_the_serving_spans_cost():
    """With no trace active, ``pir.db_place`` costs what the serving
    path's spans cost: both are ``TraceAnnotation``s with three
    metadata fields (best of five runs of 2000 spans each)."""
    def per_span(make):
        best = float("inf")
        for _ in range(5):
            t = time.perf_counter()
            for _ in range(2000):
                with make():
                    pass
            best = min(best, (time.perf_counter() - t) / 2000)
        return best

    place = per_span(lambda: _place_span(1 << 30, 32 << 30, 4))
    serve = per_span(lambda: _reconstruct_span(4, 3))
    assert place < 2 * serve + 2e-6
    assert place < 50e-6


# ---------------------------------------------------------------------------
# data plane (slow: real two-party protocol, shared compiled steps)
# ---------------------------------------------------------------------------

LOG_N = 8
N = 1 << LOG_N


@pytest.fixture(scope="module")
def system():
    db = pir.make_database(np.random.default_rng(0), N, 32)
    cfg = PIRConfig(n_items=N, item_bytes=32, batch_queries=4)
    sys2 = TwoServerPIR(db, cfg, make_local_mesh(), n_queries=4,
                        buckets=(4,))
    return sys2, db


@pytest.mark.slow
def test_ragged_traffic_padded_answers_correct(system):
    """Batch sizes off the bucket grid: padded slots never corrupt answers."""
    sys2, db = system
    for idx in ([3], [9, 200, N - 1], [0, 1, 2, 3]):   # 1, 3, 4 -> bucket 4
        np.testing.assert_array_equal(sys2.query(idx), db[idx])
    assert sys2.scheduler.stats.padded >= 3 + 1        # 1->4 and 3->4 pads


@pytest.mark.slow
def test_bucket_cache_no_recompile_on_repeat_sizes(system):
    """Every ragged size maps onto the one compiled bucket: no recompiles."""
    sys2, db = system
    sys2.query([5])                                    # warm the bucket cache
    before = [s.n_compiles for s in sys2.servers]
    for idx in ([7], [8, 9], [1, 2, 3], [4, 5, 6, 7], [250]):
        np.testing.assert_array_equal(sys2.query(idx), db[idx])
    assert [s.n_compiles for s in sys2.servers] == before
    assert all(c == 1 for c in before)                 # one bucket, one lower


@pytest.mark.slow
def test_streaming_session_reconciles_async(system):
    """submit(index) futures resolve correctly from the session thread."""
    sys2, db = system
    indices = [5, 77, 250, 0, 131, 17]
    with sys2:
        futs = [sys2.submit(i) for i in indices]
        rows = [f.result(timeout=120.0) for f in futs]
    for i, r in zip(indices, rows):
        np.testing.assert_array_equal(r, db[i])
    assert not sys2.scheduler.running

