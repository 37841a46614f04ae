"""PIR serving runtime — the paper's Figure 8 multi-query workflow.

Pipeline stages (paper §3.4):
  ① client keys arrive (streaming per-client queries)   -> pending queue
  ② the scheduler coalesces them into *padded batches* drawn from a small
     set of bucket sizes, each bucket backed by a cached compiled serve
     step (core/server.BucketedServeFns) so ragged traffic never
     recompiles (DESIGN.md §6)
  ③ batches are assigned to DPU *clusters* (mesh data-axis groups, each
     holding a full DB replica sharded over `model`) round-robin
  ④ a double-buffered dispatch loop stages batch k+1's key pytree onto
     devices while batch k executes (host staging ∥ device compute)
  ⑤ answers return to the client through per-query futures; all k
     parties' shares are reconciled (``PIRProtocol.reconstruct``) off the
     dispatch critical path

Straggler mitigation: per-cluster latency EWMA; a flagged cluster's queued
work is re-sharded onto healthy clusters (``StragglerMonitor.shed_stragglers``,
wired into ``QueryScheduler.rebalance``) — the clustered replica topology is
exactly what makes this cheap (paper Take-away 5's structure, used for fault
tolerance too).

Profiler spans (``jax.profiler.TraceAnnotation``, on the host plane of the
device trace and its clock; they record only while a trace is active):
``pir.gen_lock`` and ``pir.gen`` (a request's wait for the client keygen
lock, and keygen under it; metadata ``request``) and ``pir.reconstruct``
(one batch's reconstruction after its answer shares are ready; ``batch``,
``bucket``, ``n``). A future's ``context`` carries its ``request`` and
``batch`` numbers, which join the two (DESIGN.md §6.2).
"""
from __future__ import annotations

import contextlib
import contextvars
import itertools
import math
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import (Any, Callable, Deque, Dict, List, Optional, Sequence,
                    Tuple)

import jax
import numpy as np

from repro.config import PIRConfig
from repro.core import dpf, pir
from repro.core import protocol as protocol_mod
from repro.core.protocol import PIRProtocol
from repro.core.server import PIRServer, bucket_for
from repro.db import ShardedDatabase
from repro.runtime.fault import StragglerMonitor

#: dispatch-queue depth of the double-buffered loop: one batch executing on
#: device, one being staged on the host. Deeper pipelines only add latency.
PIPELINE_DEPTH = 2

#: default batching window — how long a lone query may wait for companions
#: before the scheduler cuts an under-full (padded) batch.
DEFAULT_MAX_WAIT_S = 0.005

#: how many of the most recent batch latencies ``ServeStats`` keeps (the
#: replica snapshot's recent p50/p99); older ones are dropped
LATENCY_WINDOW = 1024

#: sequence number of the batch whose ``finalize`` runs on this thread
#: (set by ``QueryScheduler._complete``): the ``pir.reconstruct`` span's
#: ``batch``, without widening the finalize callable's signature
_FINALIZING_BATCH: contextvars.ContextVar = contextvars.ContextVar(
    "pir_finalizing_batch", default=None)


@dataclass
class ServeStats:
    answered: int = 0
    batches: int = 0
    padded: int = 0              # pad slots computed-and-discarded
    reassignments: int = 0       # queued batches moved off stragglers
    # seconds the answered queries waited from submit to their batch's
    # launch, summed (added at completion, like ``answered``)
    queue_wait_s: float = 0.0
    latencies: Deque[float] = field(
        default_factory=lambda: deque(maxlen=LATENCY_WINDOW))
    bucket_counts: Dict[int, int] = field(default_factory=dict)
    # serving window: earliest dispatch .. latest completion. Overlapped
    # (pipelined) batches make sum(latencies) exceed wall time, so QPS is
    # computed against this window, never against the latency sum.
    t_first: Optional[float] = None
    t_last: Optional[float] = None

    def observe_window(self, t0: float, t1: float):
        self.t_first = t0 if self.t_first is None else min(self.t_first, t0)
        self.t_last = t1 if self.t_last is None else max(self.t_last, t1)

    @property
    def wall_s(self) -> float:
        if self.t_first is None or self.t_last is None:
            return 0.0
        return self.t_last - self.t_first

    @property
    def qps(self) -> float:
        wall = self.wall_s
        return self.answered / wall if wall > 0 else 0.0

    @property
    def pad_fraction(self) -> float:
        slots = self.answered + self.padded
        return self.padded / slots if slots else 0.0


def _reconstruct_span(bucket: int, n: int) -> jax.profiler.TraceAnnotation:
    """Profiler span ``pir.reconstruct`` over the reconstruction of the
    batch being finalized: its ``batch`` number, ``bucket`` and ``n`` real
    queries."""
    return jax.profiler.TraceAnnotation(
        "pir.reconstruct", batch=_FINALIZING_BATCH.get(), bucket=int(bucket),
        n=n)


class QueryTimeout(TimeoutError):
    """``AnswerFuture.result`` ran out of time — with query context.

    The message names everything known about the query (session id,
    batch bucket, answer epoch, elapsed vs deadline) instead of a bare
    "answer not ready", so a timeout in a fleet log is attributable
    without a debugger. Still a ``TimeoutError``: existing handlers keep
    working.
    """

    def __init__(self, fut: Optional["AnswerFuture"] = None,
                 timeout: Optional[float] = None):
        parts = []
        if fut is not None:
            now = time.monotonic()
            ctx = getattr(fut, "context", {})
            if ctx.get("session") is not None:
                parts.append(f"session={ctx['session']}")
            if ctx.get("replica") is not None:
                parts.append(f"replica={ctx['replica']}")
            if ctx.get("bucket") is not None:
                parts.append(f"bucket={ctx['bucket']}")
            if getattr(fut, "epoch", None) is not None:
                parts.append(f"epoch={fut.epoch}")
            created = getattr(fut, "created", None)
            if created is not None:
                parts.append(f"elapsed={now - created:.3f}s")
            deadline = getattr(fut, "deadline", None)
            if deadline is not None:
                parts.append(f"deadline_over_by={now - deadline:+.3f}s")
        if timeout is not None:
            parts.append(f"timeout={timeout:.3f}s")
        detail = f" ({', '.join(parts)})" if parts else ""
        super().__init__(f"answer not ready{detail}")


class AnswerFuture:
    """Per-query result handle: ``submit(index) -> future`` (DESIGN.md §6).

    Thread-safe; ``result()`` blocks until the scheduler completes the
    batch carrying this query (or re-raises the batch's failure).
    ``epoch`` is the database epoch the answer was computed at (set with
    the result when the scheduler has an ``epoch_of`` source; ``None``
    otherwise) — clients of an online-updated DB read it to know which
    version their record reflects.

    ``deadline`` is an absolute ``time.monotonic()`` instant (or ``None``
    for no deadline): ``result()`` with no explicit timeout waits only
    until it, raising :class:`QueryTimeout`, and the replica router's
    reaper uses it to drive hedged resubmits (DESIGN.md §12.3).
    ``context`` accumulates attribution breadcrumbs (session id, bucket,
    routed replica) that the timeout message reports.

    Completion is **first-wins**: once resolved, later ``set_result`` /
    ``set_exception`` calls are ignored (they return ``False``). That is
    what makes a kill-vs-complete race benign — a replica being torn down
    while a batch finishes delivers whichever terminal event lands first,
    exactly once (``replica/router.py`` failover relies on this).
    """

    def __init__(self, *, deadline: Optional[float] = None):
        self._ev = threading.Event()
        self._lock = threading.Lock()
        self._value: Any = None
        self._exc: Optional[BaseException] = None
        self._callbacks: List[Callable[["AnswerFuture"], None]] = []
        self.epoch: Optional[int] = None
        self.deadline = deadline
        self.context: Dict[str, Any] = {}
        self.created = time.monotonic()

    def _resolve(self, value: Any, exc: Optional[BaseException]) -> bool:
        with self._lock:
            if self._ev.is_set():
                return False
            self._value, self._exc = value, exc
            callbacks, self._callbacks = self._callbacks, []
            self._ev.set()
        for cb in callbacks:        # outside the lock: callbacks may block
            cb(self)
        return True

    def set_result(self, value: Any) -> bool:
        return self._resolve(value, None)

    def set_exception(self, exc: BaseException) -> bool:
        return self._resolve(None, exc)

    def add_done_callback(self, fn: Callable[["AnswerFuture"], None]):
        """Call ``fn(self)`` when the future resolves (immediately if it
        already has). Runs on the resolving thread, outside any scheduler
        lock — the replica router chains failover resubmission here."""
        with self._lock:
            if not self._ev.is_set():
                self._callbacks.append(fn)
                return
        fn(self)

    def done(self) -> bool:
        return self._ev.is_set()

    def exception(self) -> Optional[BaseException]:
        """The failure this future resolved with, or None (also None while
        still pending — pair with :meth:`done`)."""
        return self._exc

    def result(self, timeout: Optional[float] = None) -> Any:
        if timeout is None and self.deadline is not None:
            timeout = max(self.deadline - time.monotonic(), 0.0)
        if not self._ev.wait(timeout):
            raise QueryTimeout(self, timeout=timeout)
        if self._exc is not None:
            raise self._exc
        return self._value


@dataclass
class _Batch:
    """One formed (not yet padded) batch bound for a cluster lane."""
    items: List[Any]                  # raw per-query payloads
    futures: List[AnswerFuture]
    cluster: str
    t_submit: List[float]             # each query's submit time (clock)
    seq: int                          # the scheduler's batch number
    payload: Any = None               # collated (stacked) keys
    staged: Any = None                # padded + device_put keys
    bucket: int = 0
    epoch: Optional[int] = None       # DB epoch captured at dispatch
    t_launch: Optional[float] = None  # launch start (clock)


class QueryScheduler:
    """Dynamic batcher + double-buffered dispatcher over cluster lanes.

    Parameterized by four callables so the same engine serves one party
    (share answering) or a k-party deployment (share reconciliation):

      collate(items)        stack raw per-query payloads -> batched pytree
      stage(payload)        pad to bucket + device_put (overlaps compute)
      dispatch(staged)      launch the compiled serve step (async, no block)
      finalize(raw, n)      block + convert the first n real answers

    An optional ``epoch_of(raw)`` callable extracts the database epoch a
    batch was computed at from that batch's *own* dispatch result (the
    dispatcher captures an atomic DB snapshot and threads its epoch
    through ``raw``), and the scheduler stamps it onto every future the
    batch resolves — batch-local, so concurrent dispatchers can never
    cross-tag. Across an epoch swap (``ShardedDatabase.publish``),
    batches already dispatched finish — and stay tagged — against the
    old epoch, while queued/pending batches are re-tagged to the epoch
    they actually compute against. Queries never drain or stall across a
    swap.

    Queries arrive via :meth:`submit` (returns an :class:`AnswerFuture`).
    Batches are cut when a full bucket's worth is pending, or, with no
    batch dispatched or queued, when the oldest query has waited
    ``max_wait_s`` and ``max_wait_s`` has passed since the last batch
    completed (then padded up to the smallest covering bucket). While the
    device has work an under-full batch would only queue behind it, so
    the queries wait for company; the second clock lets the clients a
    completion just answered join too. Without both, a closed loop keeps
    the grouping of any under-full cut (a client whose next query came
    a few ms late), and a run's rate depends on how many such cuts it met.
    Work is spread round-robin over ``n_clusters``
    logical lanes; :meth:`rebalance` sheds a flagged straggler's queued
    batches onto healthy lanes.

    Drive it synchronously with :meth:`pump` (tests, benches) or as a
    background session with :meth:`start` / :meth:`stop`.
    """

    def __init__(
        self,
        *,
        collate: Callable[[List[Any]], Any],
        stage: Callable[[Any], Any],
        dispatch: Callable[[Any], Any],
        finalize: Callable[[Any, int], Sequence[Any]],
        buckets: Sequence[int],
        n_clusters: int = 1,
        max_wait_s: float = DEFAULT_MAX_WAIT_S,
        monitor: Optional[StragglerMonitor] = None,
        depth: int = PIPELINE_DEPTH,
        clock: Callable[[], float] = time.monotonic,
        epoch_of: Optional[Callable[[Any], Optional[int]]] = None,
        heartbeat: Optional[Callable[[], None]] = None,
        chaos=None,
        chaos_target: Optional[str] = None,
    ):
        self._collate = collate
        self._stage = stage
        self._dispatch = dispatch
        self._finalize = finalize
        self._epoch_of = epoch_of
        self.buckets = tuple(sorted(set(buckets)))
        self.n_clusters = max(n_clusters, 1)
        self.max_wait_s = max_wait_s
        self.monitor = monitor if monitor is not None else StragglerMonitor()
        self.depth = max(depth, 1)
        self.clock = clock
        #: liveness hook: called once per dispatch-loop iteration (and per
        #: pump), so a HeartbeatRegistry sees silence exactly when the
        #: session thread stops turning (killed, hung, or crashed). The
        #: replica plane assigns it at registry join.
        self.heartbeat = heartbeat
        #: chaos seam "scheduler.dispatch" (repro/chaos): consulted once
        #: per batch launch — a kill raises InjectedFault (failing the
        #: batch + the session, like a real dispatch crash), stall/delay
        #: sleep. None (production) costs one attribute check per launch.
        self.chaos = chaos
        self.chaos_target = chaos_target
        self.stats = ServeStats()

        self._cv = threading.Condition()
        self._pending: deque = deque()        # (item, future, t_submit)
        self.queues: Dict[str, List[_Batch]] = {
            f"cluster{i}": [] for i in range(self.n_clusters)}
        self._rr = 0                          # round-robin lane counter
        self._n_cut = 0                       # batches formed so far
        self._t_done = -math.inf              # clock() of the last completion
        self._n_inflight = 0                  # real queries dispatched, unresolved
        self._thread: Optional[threading.Thread] = None
        self._stopping = False
        self._closed = False                  # terminal: set by stop()/death
        self._abort_exc: Optional[BaseException] = None   # set by kill()

    # ------------------------------------------------------------------
    # intake
    # ------------------------------------------------------------------

    def submit(self, item: Any, *, future: Optional[AnswerFuture] = None
               ) -> AnswerFuture:
        """Enqueue one query payload; returns its future.

        ``future`` re-enqueues work under an *existing* future — the
        replica router's failover handoff moves a dead replica's
        undispatched queries (item, future) onto a healthy scheduler
        without its clients ever seeing a new handle.

        Raises ``RuntimeError`` once the session is closed (``stop()`` was
        called on a running session, or its thread died) — enqueueing into
        a dead loop would leave the future unresolved forever.
        """
        fut = future if future is not None else AnswerFuture()
        with self._cv:
            if self._closed:
                raise RuntimeError(
                    "QueryScheduler is stopped; submit() after stop()/close()"
                    " would never be answered")
            self._pending.append((item, fut, self.clock()))
            if len(self._pending) >= self.buckets[-1]:
                self._cut_locked(self.buckets[-1])
            self._cv.notify()
        return fut

    @property
    def queue_depth(self) -> int:
        """Real queries accepted but not yet resolved: pending + cut into
        lane queues + dispatched in flight (pad slots excluded). The
        router's power-of-two-choices balancing reads this."""
        with self._cv:
            return (len(self._pending) + self._n_inflight
                    + sum(len(b.items) for lane in self.queues.values()
                          for b in lane))

    def drain_handoff(self) -> List[Tuple[Any, AnswerFuture]]:
        """Graceful leave: close intake and hand back every query that has
        NOT been dispatched, as FIFO ``(item, future)`` pairs.

        Batches already dispatched are not returned — they complete (and
        resolve their futures) here, against this scheduler's data plane.
        The caller re-enqueues the returned pairs elsewhere via
        ``submit(item, future=fut)``; the futures move with the work, so
        no client ever observes the migration. A running session thread
        finishes its in-flight work and exits (stop semantics without the
        join); the scheduler rejects new submits from this point on.
        """
        out: List[Tuple[Any, AnswerFuture]] = []
        with self._cv:
            self._closed = True
            self._stopping = True
            for lane in self.queues.values():
                for batch in lane:
                    out.extend(zip(batch.items, batch.futures))
                lane.clear()
            while self._pending:
                item, fut, _ = self._pending.popleft()
                out.append((item, fut))
            self._cv.notify_all()
        return out

    def kill(self, exc: BaseException):
        """Hard death (crash injection / fault handling): fail every
        outstanding future with ``exc`` and stop without draining.

        Queued and pending work is failed from the calling thread; a
        running session thread aborts its loop and fails its in-flight
        batches the same way, then exits. Races with completing batches
        resolve first-wins (:class:`AnswerFuture`): a batch that beats the
        kill delivers its answers, everything else fails — either way each
        future resolves exactly once, which is what lets the router's
        failover resubmit the losses with zero dropped queries.
        """
        victims: List[AnswerFuture] = []
        with self._cv:
            self._closed = True
            self._stopping = True
            self._abort_exc = exc
            for lane in self.queues.values():
                for batch in lane:
                    victims.extend(batch.futures)
                lane.clear()
            while self._pending:
                _, fut, _ = self._pending.popleft()
                victims.append(fut)
            self._cv.notify_all()
        for fut in victims:          # outside the lock: callbacks may block
            fut.set_exception(exc)

    def flush(self):
        """Cut every pending query into batches now (end-of-stream)."""
        with self._cv:
            while self._pending:
                self._cut_locked(min(len(self._pending), self.buckets[-1]))
            self._cv.notify()

    def bucket_for(self, n: int) -> int:
        return bucket_for(self.buckets, n)

    def _cut_locked(self, n: int):
        """Form one batch of ``n`` pending queries onto the next lane."""
        taken = [self._pending.popleft() for _ in range(n)]
        lane = f"cluster{self._rr % self.n_clusters}"
        self._rr += 1
        batch = _Batch(items=[t[0] for t in taken],
                       futures=[t[1] for t in taken],
                       cluster=lane, t_submit=[t[2] for t in taken],
                       seq=self._n_cut)
        self._n_cut += 1
        batch.bucket = self.bucket_for(n)
        for fut in batch.futures:    # timeout-attribution breadcrumb
            fut.context.setdefault("bucket", batch.bucket)
            fut.context["batch"] = batch.seq
        self.queues[lane].append(batch)

    def _ripe_in_locked(self) -> float:
        """Seconds until the pending queries may be cut under-full: both
        the oldest query and the last completion ``max_wait_s`` old."""
        now = self.clock()
        return max(self.max_wait_s - (now - self._pending[0][2]),
                   self.max_wait_s - (now - self._t_done), 0.0)

    def _cut_ripe_locked(self) -> bool:
        """Cut under-full batches once they are ripe (``_ripe_in_locked``)
        and no batch is dispatched or queued ahead of them."""
        if self._n_inflight or any(self.queues.values()):
            return False
        cut = False
        while self._pending and self._ripe_in_locked() <= 0.0:
            self._cut_locked(min(len(self._pending), self.buckets[-1]))
            cut = True
        return cut

    # ------------------------------------------------------------------
    # straggler shedding
    # ------------------------------------------------------------------

    def rebalance(self) -> int:
        """Move queued batches off flagged straggler lanes; returns moved."""
        with self._cv:
            new_queues, moved = self.monitor.shed_stragglers(self.queues)
            if moved:
                for lane, b_list in new_queues.items():
                    for b in b_list:
                        b.cluster = lane
                self.queues = new_queues
                self.stats.reassignments += moved
        return moved

    def _pop_batch_locked(self) -> Optional[_Batch]:
        for i in range(self.n_clusters):
            lane = f"cluster{(self._rr + i) % self.n_clusters}"
            if self.queues[lane]:
                return self.queues[lane].pop(0)
        return None

    # ------------------------------------------------------------------
    # dispatch engine
    # ------------------------------------------------------------------

    def _launch(self, batch: _Batch) -> Tuple[_Batch, Any, float]:
        """Collate + stage + dispatch one batch (device runs async).

        A failure anywhere in the launch path (including an injected
        chaos kill) fails the batch's futures before propagating — the
        batch has already left the lane queues, so nothing else would
        ever resolve them.
        """
        try:
            batch.t_launch = self.clock()
            if self.chaos is not None:
                self.chaos.visit("scheduler.dispatch", self.chaos_target)
            batch.payload = self._collate(batch.items)
            batch.staged = self._stage(batch.payload)
            t0 = self.clock()
            raw = self._dispatch(batch.staged)
            if self._epoch_of is not None:
                # extracted from THIS batch's dispatch result: the
                # dispatcher snapshots the DB atomically and threads the
                # epoch it read through raw, so tag == data even across a
                # concurrent publish or a second dispatching thread (the
                # dispatched step holds the old epoch's immutable arrays
                # and finishes against them)
                batch.epoch = self._epoch_of(raw)
        except BaseException as e:
            for fut in batch.futures:
                fut.set_exception(e)
            raise
        with self._cv:
            self._n_inflight += len(batch.items)
        return batch, raw, t0

    def _complete(self, batch: _Batch, raw: Any, t0: float):
        finalizing = _FINALIZING_BATCH.set(batch.seq)
        try:
            answers = self._finalize(raw, len(batch.items))
            dt = self.clock() - t0
            for fut, ans in zip(batch.futures, answers):
                fut.epoch = batch.epoch      # before the result event fires
                fut.set_result(ans)
        except BaseException as e:       # propagate to the waiting clients
            for fut in batch.futures:
                fut.set_exception(e)
            raise
        finally:
            _FINALIZING_BATCH.reset(finalizing)
            with self._cv:
                self._n_inflight -= len(batch.items)
                self._t_done = self.clock()
        self.monitor.record(batch.cluster, dt)
        self.stats.observe_window(t0, t0 + dt)
        self.stats.latencies.append(dt)
        self.stats.batches += 1
        self.stats.answered += len(batch.items)
        self.stats.queue_wait_s += sum(batch.t_launch - t
                                       for t in batch.t_submit)
        self.stats.padded += batch.bucket - len(batch.items)
        self.stats.bucket_counts[batch.bucket] = \
            self.stats.bucket_counts.get(batch.bucket, 0) + 1
        self.rebalance()

    def pump(self) -> int:
        """Synchronously drain all pending + queued work, double-buffered.

        Stages/dispatches batch k+1 before blocking on batch k, so host-side
        key staging overlaps device compute. Returns #queries answered.
        """
        if self.heartbeat is not None:
            self.heartbeat()
        self.flush()
        answered0 = self.stats.answered
        inflight: deque = deque()
        while True:
            with self._cv:
                batch = self._pop_batch_locked()
            if batch is None and not inflight:
                break
            if batch is not None:
                inflight.append(self._launch(batch))
            while inflight and (len(inflight) >= self.depth
                                or batch is None):
                self._complete(*inflight.popleft())
        return self.stats.answered - answered0

    # ------------------------------------------------------------------
    # background session mode
    # ------------------------------------------------------------------

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def start(self):
        """Run the dispatch loop as a background session thread.

        Reopens a stopped (or dead) session: the closed flag is cleared,
        so submit() works again until the next stop().
        """
        if self.running:
            return
        with self._cv:
            self._closed = False
            self._stopping = False
            self._abort_exc = None
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="pir-scheduler")
        self._thread.start()

    def stop(self):
        """Flush, answer everything in flight, then join the thread.

        Terminal for the session: subsequent :meth:`submit` calls raise
        (``pump`` remains callable and is a no-op on the drained queues).
        A scheduler that was never started is untouched — the synchronous
        submit-then-pump mode stays available.
        """
        with self._cv:
            # snapshot under the lock: a concurrent stop() may null out
            # self._thread between our aliveness check and the join
            thread = self._thread
            if thread is None or not thread.is_alive():
                return
            # closed BEFORE the join: a submit racing with stop() must
            # raise, not slip into the queue after the drain check and
            # hang its client forever
            self._closed = True
            self._stopping = True
            self._cv.notify()
        thread.join()
        with self._cv:
            # a concurrent start() may have installed a fresh session
            # thread meanwhile — only clear our own dead one
            if self._thread is thread:
                self._thread = None

    def _run(self):
        inflight: deque = deque()
        try:
            while True:
                batch = None
                if self.heartbeat is not None:
                    self.heartbeat()
                with self._cv:
                    if self._abort_exc is not None:   # kill(): no draining
                        raise self._abort_exc
                    self._cut_ripe_locked()
                    if self._stopping:
                        while self._pending:
                            self._cut_locked(
                                min(len(self._pending), self.buckets[-1]))
                    if len(inflight) < self.depth:
                        batch = self._pop_batch_locked()
                    if (batch is None and not inflight and not self._pending
                            and self._stopping):
                        return
                    if batch is None and not inflight:
                        # idle: sleep until a submit arrives or one ripens
                        self._cv.wait(timeout=self._ripe_in_locked()
                                      if self._pending else None)
                        continue
                if batch is not None:
                    inflight.append(self._launch(batch))
                    continue  # keep the pipeline full before blocking
                self._complete(*inflight.popleft())
        except BaseException as e:
            # the session is dead: every outstanding future must resolve,
            # not hang its client until result() times out
            self._fail_outstanding(inflight, e)

    def _fail_outstanding(self, inflight, exc: BaseException):
        victims: List[AnswerFuture] = []
        for batch, _, _ in inflight:
            victims.extend(batch.futures)
        with self._cv:
            self._closed = True      # dead session: reject future submits
            self._n_inflight = 0
            for lane in self.queues.values():
                for batch in lane:
                    victims.extend(batch.futures)
                lane.clear()
            while self._pending:
                _, fut, _ = self._pending.popleft()
                victims.append(fut)
        for fut in victims:          # outside the lock: done-callbacks may
            fut.set_exception(exc)   # re-enter other schedulers (failover)


class MultiServerPIR:
    """End-to-end k-party deployment: client + k non-colluding servers.

    The facade over the protocol plane (``core/protocol.py``): the injected
    ``PIRProtocol`` (default: the one ``cfg.protocol`` names) decides the
    party count, per-party key generation, and reconstruction; one
    :class:`PIRServer` per party owns that party's compiled step family;
    one :class:`QueryScheduler` coalesces all clients' queries and fans
    every batch out to all k parties.

    The database is ONE shared :class:`ShardedDatabase` (DESIGN.md §8):
    its contents are public in the PIR model (privacy protects the query
    index), so k parties referencing the same placed views costs one
    host pass and one device residency instead of k of each. In a real
    deployment each party holds its own replica and applies the identical
    public ``update``/``publish`` delta stream — determinism of the delta
    is what keeps all parties' answer shares consistent; sharing the
    object here is the single-host degenerate case of that.

    All servers run the same binary on disjoint meshes in production; on
    this container they share the device but keep separate key material
    and compiled steps, preserving the protocol structure exactly.

    Two client APIs:

      query(indices)   synchronous batch retrieval (pumps the scheduler
                       inline when no session thread is running)
      submit(index)    streaming session form: returns an
                       :class:`AnswerFuture`; the scheduler coalesces
                       concurrent clients' queries into padded bucket
                       batches and reconciles all parties' answer shares
                       asynchronously. Call :meth:`start` for a background
                       session (or rely on ``query``/``pump``).

    Online updates: :meth:`update` stages public row writes,
    :meth:`publish` atomically swaps in the new epoch (O(rows) transfer,
    no serving stall); every resolved :class:`AnswerFuture` carries the
    ``epoch`` its answer was computed at.
    """

    #: hint protocols (``PIRProtocol.needs_hint``) thread per-query client
    #: state and an epoch hint through the scheduler; only subclasses that
    #: implement that plumbing (SingleServerPIR) may serve them.
    _supports_hint_protocols = False

    def __init__(self, db_words, cfg: PIRConfig, mesh,
                 *, n_queries: int = 4,
                 buckets: Optional[Sequence[int]] = None,
                 max_wait_s: float = DEFAULT_MAX_WAIT_S,
                 n_clusters: int = 1,
                 protocol: Optional[PIRProtocol] = None,
                 client_rng: Optional[np.random.Generator] = None,
                 default_deadline_s: Optional[float] = None,
                 chaos=None, chaos_scope: Optional[str] = None,
                 path: None = None):
        # ``path`` is accepted only as None, for bench/run.py, which still
        # passes ``path=None``; the engine picks every bucket's plan
        if path is not None:
            raise TypeError(
                f"path={path!r}: the engine picks the plan "
                f"(repro.engine.resolve); seed one with "
                f"engine.record_plans")
        self.cfg = cfg
        self.protocol = (protocol if protocol is not None
                         else protocol_mod.for_config(cfg))
        if self.protocol.needs_hint and not self._supports_hint_protocols:
            raise ValueError(
                f"protocol {self.protocol.name!r} needs hint plumbing "
                f"(per-query client state + epoch hints) — use "
                f"SingleServerPIR, not {type(self).__name__}")
        self.n_parties = self.protocol.n_parties(cfg)
        # one shared database plane object for all k parties (a host
        # array is wrapped; an existing ShardedDatabase passes through)
        self.db = (db_words if isinstance(db_words, ShardedDatabase)
                   else ShardedDatabase(db_words, cfg, mesh))
        self.servers = [
            PIRServer(party=b, database=self.db, cfg=cfg, mesh=mesh,
                      n_queries=n_queries, buckets=buckets,
                      protocol=self.protocol)
            for b in range(self.n_parties)
        ]
        # key material (DPF keys, xor-dpf-k mask seeds) must not be
        # replayable: default to OS entropy; inject a seeded Generator
        # only for deterministic tests/benches
        self.rng = (client_rng if client_rng is not None
                    else np.random.default_rng())
        self._lock = threading.Lock()
        self._requests = itertools.count()    # request numbers, submit order
        # per-query deadline default (DESIGN.md §12.3): every submit()
        # stamps an absolute deadline onto its AnswerFuture, which both
        # result() and the replica router's hedging reaper read. The
        # compile-aware default replaces the old hardcoded
        # ``_query_timeout_s``: first dispatch compiles one serve step per
        # party (~1 min each on the dev container), so a cold background
        # session needs the deadline to scale with the party count.
        self.default_deadline_s = (default_deadline_s
                                   if default_deadline_s is not None
                                   else 120.0 * self.n_parties)
        #: chaos plane wiring (repro/chaos; None in production): the
        #: injector is consulted at "scheduler.dispatch" (batch launch)
        #: and "replica.serve_step" (the answer shares of each dispatch,
        #: where the corrupt action flips bits). ``chaos_scope`` is this
        #: deployment's target id — the replica plane passes its replica
        #: id so plans can aim at one replica of a fleet.
        self.chaos = chaos
        self.chaos_scope = chaos_scope
        self.scheduler = self._make_scheduler(max_wait_s, n_clusters)

    def _make_scheduler(self, max_wait_s: float, n_clusters: int
                        ) -> QueryScheduler:
        servers = self.servers
        proto = self.protocol
        parties = range(self.n_parties)
        db = self.db
        cfg = self.cfg
        chaos, chaos_scope = self.chaos, self.chaos_scope

        def collate(items):
            # items: per-query tuples of per-party keys -> per-party batches
            return tuple(dpf.stack_keys([it[p] for it in items])
                         for p in parties)

        def stage(payload):
            return tuple(servers[p].stage_keys(payload[p]) for p in parties)

        def dispatch(staged):
            # one atomic (epoch, views) capture for the whole k-party
            # fan-out: every party answers against the SAME epoch, and the
            # epoch rides WITH the answers, so the tag can never disagree
            # with the data read — even across concurrent dispatchers
            epoch, views = db.snapshot((proto.db_view,))
            view = views[proto.db_view]
            answers = tuple(servers[p].bucketed.answer(view, staged[p])
                            for p in parties)
            if chaos is not None:   # seam: corrupt one party's shares
                answers = chaos.corrupt_shares("replica.serve_step",
                                               chaos_scope, answers)
            return answers, epoch

        def finalize(raw, n):
            answers, _ = raw
            jax.block_until_ready(answers)
            with _reconstruct_span(answers[0].shape[0], n):
                # the shares are fetched, sliced and combined on the host:
                # device programs here would queue behind the next batch's
                # step, already dispatched, and answer this batch a step
                # late. reconstruct_with routes through checksum
                # verification when cfg.checksum — a corrupted share raises
                # IntegrityError here (failing this batch's futures)
                # instead of resolving garbage
                shares = [np.asarray(r)[:n] for r in answers]
                return list(proto.reconstruct_with(shares, [None] * n,
                                                   cfg=cfg))

        return QueryScheduler(
            collate=collate, stage=stage, dispatch=dispatch,
            finalize=finalize, buckets=servers[0].buckets,
            n_clusters=n_clusters, max_wait_s=max_wait_s,
            epoch_of=lambda raw: raw[1],
            chaos=chaos, chaos_target=chaos_scope)

    # -- streaming session API ------------------------------------------

    def start(self):
        """Run the scheduler as a background session thread."""
        self.scheduler.start()

    def close(self):
        self.scheduler.stop()

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.close()

    def _deadline_future(self, deadline_s: Optional[float]) -> AnswerFuture:
        """A fresh future carrying this query's absolute deadline."""
        d = self.default_deadline_s if deadline_s is None else deadline_s
        return AnswerFuture(
            deadline=None if d is None else time.monotonic() + d)

    @contextlib.contextmanager
    def _keygen(self, fut: AnswerFuture):
        """Number one request (``fut.context["request"]``) and hold the
        client keygen lock for it (keygen shares one rng): the wait for
        the lock is the span ``pir.gen_lock``, the time holding it
        ``pir.gen``."""
        request = next(self._requests)
        fut.context["request"] = request
        with jax.profiler.TraceAnnotation("pir.gen_lock", request=request):
            self._lock.acquire()
        try:
            with jax.profiler.TraceAnnotation("pir.gen", request=request):
                yield
        finally:
            self._lock.release()

    def submit(self, index: int, *,
               deadline_s: Optional[float] = None) -> AnswerFuture:
        """Private retrieval of ``db[index]``; resolves to one record
        (``[W]`` u32 words for the XOR protocols, bytes for additive).
        The resolved future's ``epoch`` names the DB version answered.

        ``deadline_s`` (default: ``default_deadline_s``) becomes an
        absolute deadline on the returned future: ``result()`` with no
        explicit timeout waits only until it.
        """
        fut = self._deadline_future(deadline_s)
        with self._keygen(fut):
            q = pir.query_gen(self.rng, index, self.cfg)
        return self.scheduler.submit(q.keys, future=fut)

    # -- online updates (public metadata; privacy model untouched) ------

    @property
    def epoch(self) -> int:
        """Current database epoch (bumped by :meth:`publish`)."""
        return self.db.epoch

    def update(self, rows, values) -> int:
        """Stage public row writes into the pending delta log.

        ``values``: [R, item_words] u32 or [R, item_bytes] u8. Nothing
        is served from the delta until :meth:`publish`. In a multi-host
        deployment every party stages the identical delta (it is public
        metadata), which is what keeps the k answer shares consistent.
        Returns the total staged entry count.
        """
        return self.db.stage(rows, values)

    def publish(self) -> int:
        """Swap staged updates in as the next epoch (O(rows) transfer).

        Serving never stalls: batches already dispatched finish against
        the previous epoch (their answers stay tagged with it); every
        later batch reads the new views. Returns the new current epoch.
        """
        return self.db.publish()

    # -- synchronous batch API ------------------------------------------

    def query(self, indices: Sequence[int]) -> np.ndarray:
        """Private retrieval of ``db[indices]``; returns [Q, ...] records
        (u32 words for XOR protocols, Z_256 bytes for additive)."""
        if not indices:
            tail, dtype = self.protocol.record_struct(self.cfg)
            return np.empty((0,) + tail, dtype)
        futs = [self.submit(i) for i in indices]
        if not self.scheduler.running:
            self.scheduler.pump()
        # each future carries its own deadline (set at submit); result()
        # derives the wait from it
        return np.stack([f.result() for f in futs])

    def query_batch(self, indices: Sequence[int]) -> np.ndarray:
        """Multi-query retrieval; same contract as :meth:`query`.

        Here each index is an independent full-DB-scan query (they only
        share the scheduler's padded-batch dispatch). The cuckoo-bucketed
        composite (``runtime/batch.py`` :class:`BatchPIR`) overrides this
        with the amortized m-records-per-round protocol — callers written
        against ``query_batch`` get the algorithmic speedup wherever the
        deployment provides it.
        """
        return self.query(indices)


class SingleServerPIR(MultiServerPIR):
    """Single-server deployment for hint protocols (``lwe-simple-1``).

    The no-collusion-assumption scenario (DESIGN.md §10): one server, and
    privacy rests on LWE hardness instead of parties never comparing
    notes. Reuses the whole multi-server machinery — ``ShardedDatabase``,
    ``PIRServer``'s bucketed compiled steps, the ``QueryScheduler`` — with
    the two deltas a hint protocol needs:

      * **client state**: :meth:`submit` generates ``(keys, state)`` via
        ``query_gen_full``; the per-query secret rides through the
        scheduler next to the keys (never serialized, never staged onto
        devices) and meets the answers again at finalize;
      * **client-side hint cache**: reconstruction needs the epoch's hint
        ``H = A^T.DB``. The facade plays the client here: it caches the
        hint keyed by the epoch each batch's answers were tagged with and
        re-fetches on a miss — a ``publish()`` bumps the epoch, so stale
        caches are invalidated exactly when the data changes
        (``hint_fetches`` counts the round trips; the server side
        maintains the hint itself incrementally via the registered delta).
    """

    _supports_hint_protocols = True

    def __init__(self, db_words, cfg: PIRConfig, mesh,
                 *args, protocol: Optional[PIRProtocol] = None, **kwargs):
        proto = (protocol if protocol is not None
                 else protocol_mod.for_config(cfg))
        k = proto.n_parties(cfg)
        if k != 1:
            raise ValueError(
                f"SingleServerPIR requires a 1-party protocol; "
                f"{proto.name!r} has {k} parties — use MultiServerPIR")
        # client-side hint cache: set up BEFORE super().__init__ builds
        # the scheduler (whose finalize closure reads it)
        self._hint_lock = threading.Lock()
        self._hint_cache: Dict[int, np.ndarray] = {}
        self.hint_fetches = 0
        super().__init__(db_words, cfg, mesh, *args, protocol=proto,
                         **kwargs)

    def _client_hint(self, epoch: int) -> np.ndarray:
        """The hint for one epoch, through the client-side cache."""
        with self._hint_lock:
            if epoch not in self._hint_cache:
                self.hint_fetches += 1
                self._hint_cache[epoch] = np.asarray(
                    self.db.hint(self.protocol.name, epoch=epoch))
                # two epochs of hysteresis, mirroring the server's
                # retired-view double buffer
                for e in sorted(self._hint_cache)[:-2]:
                    del self._hint_cache[e]
            return self._hint_cache[epoch]

    def _make_scheduler(self, max_wait_s: float, n_clusters: int
                        ) -> QueryScheduler:
        server = self.servers[0]
        proto = self.protocol
        cfg = self.cfg
        db = self.db
        chaos, chaos_scope = self.chaos, self.chaos_scope
        # server-side hint lifecycle: built lazily per epoch, delta-updated
        # on publish (db/sharded.py)
        db.register_hint(proto.name, proto.hint_builder(cfg),
                         proto.hint_delta(cfg))

        def collate(items):
            # items: ((keys,), state) per query — stack party-0 keys,
            # carry the client states alongside (host-only, never staged)
            keys = dpf.stack_keys([it[0][0] for it in items])
            return keys, [it[1] for it in items]

        def stage(payload):
            keys, states = payload
            return server.stage_keys(keys), states

        def dispatch(staged):
            keys, states = staged
            epoch, views = db.snapshot((proto.db_view,))
            ans = server.bucketed.answer(views[proto.db_view], keys)
            if chaos is not None:   # seam: corrupt the answer matrix
                (ans,) = chaos.corrupt_shares("replica.serve_step",
                                              chaos_scope, (ans,))
            return ans, epoch, states

        def finalize(raw, n):
            ans, epoch, states = raw
            jax.block_until_ready(ans)
            with _reconstruct_span(ans.shape[0], n):
                hint = self._client_hint(epoch)
                rec = np.asarray(proto.reconstruct_with(
                    [np.asarray(ans[:n])], states[:n], cfg=cfg, hint=hint))
                return list(rec)

        return QueryScheduler(
            collate=collate, stage=stage, dispatch=dispatch,
            finalize=finalize, buckets=server.buckets,
            n_clusters=n_clusters, max_wait_s=max_wait_s,
            epoch_of=lambda raw: raw[1],
            chaos=chaos, chaos_target=chaos_scope)

    def submit(self, index: int, *,
               deadline_s: Optional[float] = None) -> AnswerFuture:
        """Private retrieval of ``db[index]``; resolves to one record
        ([item_bytes] u8). The per-query LWE secret stays client-side:
        only the ciphertext enters the scheduler's device path."""
        fut = self._deadline_future(deadline_s)
        with self._keygen(fut):
            keys, state = self.protocol.query_gen_full(self.rng, index,
                                                       self.cfg)
        return self.scheduler.submit((keys, state), future=fut)


class TwoServerPIR(MultiServerPIR):
    """Backward-compatible alias: the two-party deployment.

    Kept as a thin ``n_parties == 2`` facade over :class:`MultiServerPIR`
    (the pre-protocol-plane public API). New code should construct
    :class:`MultiServerPIR` with an explicit ``PIRConfig.protocol``.
    """

    def __init__(self, db_words: np.ndarray, cfg: PIRConfig, mesh,
                 *args, protocol: Optional[PIRProtocol] = None, **kwargs):
        # validate BEFORE building servers: k device-resident DB replicas
        # are too expensive to allocate just to throw away
        proto = (protocol if protocol is not None
                 else protocol_mod.for_config(cfg))
        k = proto.n_parties(cfg)
        if k != 2:
            raise ValueError(
                f"TwoServerPIR requires a 2-party protocol; "
                f"{proto.name!r} has {k} parties — use MultiServerPIR")
        super().__init__(db_words, cfg, mesh, *args, protocol=proto,
                         **kwargs)
