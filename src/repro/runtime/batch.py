"""`BatchPIR` — the cuckoo-bucketed multi-query session (DESIGN.md §14).

The runtime half of the batch composite: glues the client-side cuckoo
plan (``core/batch.py``) to the bucketed database (``db/bucketed.py``)
through the SAME :class:`~repro.runtime.serve_loop.QueryScheduler` every
other deployment uses — one scheduler *item* is one :class:`RoundPlan`
(a whole m-record batch), and one dispatch fans its B per-bucket inner
queries out to all k parties.

Why this is the throughput lever (the perf accounting the bench pins):
a single-query round scans all N rows for 1 record; a batch round scans
B · capacity ≈ 2·n_hashes·N rows for m records — records per scanned row
improve by the *algorithmic* factor m·N/(B·capacity) ≈ m/4 at the
defaults, independent of (and multiplicative with) the kernel constants
the engine's measured plans buy per bucket.

Privacy: every round issues exactly ONE real-or-dummy inner query per
bucket (``plan_round``'s uniform padding), and dummies run the identical
keygen as real queries, so the servers' view — B DPF keys per party per
round — is independent of which m indices were requested. The inner
protocol's per-query privacy argument then applies per bucket unchanged.

Compile economics: all B buckets share one shape (``capacity`` rows), so
one :class:`BucketedServeFns` per party serves every bucket view with a
SINGLE compiled step — B × m amortization never multiplies compiles
(``examples/batch_query.py`` asserts ``n_compiles == 1`` per party).
"""
from __future__ import annotations

import threading
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import PIRConfig
from repro.core import dpf
from repro.core import protocol as protocol_mod
from repro.core.batch import (CuckooFailure, RoundPlan, plan_round,
                              reassemble)
from repro.core.protocol import PIRProtocol
from repro.core.server import BucketedServeFns
from repro.db import BucketedDatabase
from repro.runtime.serve_loop import (DEFAULT_MAX_WAIT_S, AnswerFuture,
                                      MultiServerPIR, QueryScheduler)


class BatchPIR(MultiServerPIR):
    """k-party batch deployment: m records per round over B cuckoo buckets.

    Same facade as :class:`MultiServerPIR` — ``query``/``submit``/
    ``update``/``publish``/session lifecycle — plus the batch plane:

      query_batch(indices)    synchronous m-record retrieval (the reason
                              this class exists); splits and retries on
                              the O(1/B)-probability cuckoo failure
      submit_batch(indices)   streaming form -> one AnswerFuture that
                              resolves to [m, ...] records in request
                              order, epoch-tagged like any other answer

    ``db_words`` may be the host array or a prebuilt
    :class:`BucketedDatabase` (replica-plane style pass-through).
    ``rounds`` is the scheduler's batch-size ladder in units of *rounds*
    (RoundPlans per dispatch) — the per-bucket query count of one
    dispatch is ``rounds × 1``, B buckets wide.
    """

    def __init__(self, db_words, cfg: PIRConfig, mesh,
                 *, rounds: Sequence[int] = (1,),
                 max_wait_s: float = DEFAULT_MAX_WAIT_S,
                 n_clusters: int = 1,
                 protocol: Optional[PIRProtocol] = None,
                 client_rng: Optional[np.random.Generator] = None,
                 default_deadline_s: Optional[float] = None):
        if cfg.batch_m < 1:
            raise ValueError(
                f"BatchPIR needs cfg.batch_m >= 1 (got {cfg.batch_m}); "
                f"use MultiServerPIR for single-query serving")
        self.cfg = cfg
        self.protocol = (protocol if protocol is not None
                         else protocol_mod.for_config(cfg))
        if self.protocol.needs_hint:
            raise ValueError(
                f"protocol {self.protocol.name!r} needs hint plumbing; "
                f"the batch composite serves the k-party protocols "
                f"(xor-dpf-2, xor-dpf-k, additive-dpf-2)")
        self.n_parties = self.protocol.n_parties(cfg)
        self.db = (db_words if isinstance(db_words, BucketedDatabase)
                   else BucketedDatabase(db_words, cfg, mesh))
        self.layout = self.db.layout
        #: what the inner protocol keygens/serves against: the bucket
        #: shape (capacity rows). Engine plan resolution and cache keys
        #: see THIS config's spec signature — per bucket shape, as if the
        #: bucket were a standalone database.
        self.inner_cfg = self.db.inner_cfg
        # one serve-fns family per party, shared by ALL B bucket views
        # (same shape + sharding -> one compiled step per rounds-bucket)
        self.serve = [
            BucketedServeFns(self.inner_cfg, mesh, buckets=rounds,
                             party=p, protocol=self.protocol)
            for p in range(self.n_parties)]
        self.rng = (client_rng if client_rng is not None
                    else np.random.default_rng())
        self._lock = threading.Lock()
        # one compiled step per party total (shared across buckets), so
        # the cold-session budget matches MultiServerPIR's per-party scale
        self.default_deadline_s = (default_deadline_s
                                   if default_deadline_s is not None
                                   else 120.0 * self.n_parties)
        self.chaos = None
        self.chaos_scope = None
        #: per-dispatch uniform-padding log: (n_rounds, per-bucket queries
        #: issued per round). The no-occupancy-leak invariant is that the
        #: second element is ALWAYS exactly ``db.n_buckets`` — tests
        #: assert it across adversarial index choices.
        self.dispatch_log: List[Tuple[int, int]] = []
        self.scheduler = self._make_scheduler(max_wait_s, n_clusters)

    # ------------------------------------------------------------------
    # scheduler wiring (items are RoundPlans)
    # ------------------------------------------------------------------

    def _make_scheduler(self, max_wait_s: float, n_clusters: int
                        ) -> QueryScheduler:
        serve = self.serve
        proto = self.protocol
        parties = range(self.n_parties)
        db = self.db
        inner_cfg = self.inner_cfg
        n_buckets = self.db.n_buckets
        log = self.dispatch_log

        def collate(plans: List[RoundPlan]):
            # per party, per cuckoo bucket: this batch's rounds stacked
            # into one key pytree [R, ...] — plans ride along for
            # finalize's reassembly (the scheduler threads the payload
            # through stage/dispatch untouched)
            keys = tuple(
                [dpf.stack_keys([plan.keys[b][p] for plan in plans])
                 for b in range(n_buckets)]
                for p in parties)
            return list(plans), keys

        def stage(payload):
            plans, keys = payload
            return plans, tuple(
                [serve[p].stage(keys[p][b]) for b in range(n_buckets)]
                for p in parties)

        def dispatch(staged):
            plans, keys = staged
            # one atomic capture of ALL B bucket views + the outer epoch:
            # every bucket of every party answers the same DB version
            epoch, views = db.snapshot((proto.db_view,))
            bviews = views[proto.db_view]
            # stack each party's B per-bucket answers on DEVICE (async,
            # off the host): finalize then pays ONE device->host transfer
            # per party instead of B tiny ones — at B=32+ the transfer
            # fan-out, not the scans, would dominate the round otherwise
            answers = tuple(
                jnp.stack([serve[p].answer(bviews[b], keys[p][b])
                           for b in range(n_buckets)])     # [B, Q, ...]
                for p in parties)
            # the server-observable per-round shape: B per-bucket queries,
            # whatever the m requested indices were
            log.append((len(plans), n_buckets))
            return plans, answers, epoch

        def finalize(raw, n):
            plans, answers, _ = raw
            host = [np.asarray(a) for a in answers]        # [B, Q, ...] x k
            out = []
            for r in range(n):
                # per party: this round's B per-bucket shares -> [B, ...]
                shares = [h[:, r] for h in host]
                # checksum verification rides through per bucket — dummy
                # buckets hit real (or zero-pad) rows whose checksums are
                # valid, so IntegrityError still means real corruption
                recs = np.asarray(proto.reconstruct_with(
                    shares, [None] * n_buckets, cfg=inner_cfg))
                out.append(reassemble(plans[r], recs))
            return out

        return QueryScheduler(
            collate=collate, stage=stage, dispatch=dispatch,
            finalize=finalize, buckets=serve[0].buckets,
            n_clusters=n_clusters, max_wait_s=max_wait_s,
            epoch_of=lambda raw: raw[2])

    # ------------------------------------------------------------------
    # client API
    # ------------------------------------------------------------------

    def submit_batch(self, indices: Sequence[int], *,
                     deadline_s: Optional[float] = None) -> AnswerFuture:
        """Retrieve up to m records in one round; resolves to
        ``[len(indices), ...]`` records in request order (duplicates
        allowed — they share one bucket query).

        Raises :class:`CuckooFailure` *synchronously* (before anything is
        enqueued) when this batch's indices cannot be cuckoo-placed —
        probability O(1/B); :meth:`query_batch` handles the split-retry.
        """
        request = [int(i) for i in indices]
        if not request:
            raise ValueError("submit_batch needs at least one index")
        if any(i < 0 or i >= self.cfg.n_items for i in request):
            raise ValueError(
                f"indices out of range [0, {self.cfg.n_items})")
        if len(set(request)) > self.layout.params.m:
            raise ValueError(
                f"batch of {len(set(request))} unique indices exceeds "
                f"m={self.layout.params.m}")
        fut = self._deadline_future(deadline_s)
        with self._lock:    # keygen + cuckoo walk share one client rng
            plan = plan_round(self.rng, request, self.layout,
                              self.inner_cfg, self.protocol)
        return self.scheduler.submit(plan, future=fut)

    def query_batch(self, indices: Sequence[int]) -> np.ndarray:
        """Synchronous batch retrieval of ``db[indices]`` — any length:
        chunks into m-sized rounds, splits-and-retries the rare cuckoo
        failure (a single index always places), reassembles in request
        order. Returns [len(indices), ...] records."""
        request = [int(i) for i in indices]
        if not request:
            tail, dtype = self.protocol.record_struct(self.cfg)
            return np.empty((0,) + tail, dtype)
        unique = list(dict.fromkeys(request))
        m = self.layout.params.m
        groups = [unique[i:i + m] for i in range(0, len(unique), m)]
        futs: List[Tuple[List[int], AnswerFuture]] = []
        while groups:
            g = groups.pop(0)
            try:
                futs.append((g, self.submit_batch(g)))
            except CuckooFailure:
                # Hall-violating index subset (analytic prob O(1/B)):
                # halve and retry — a 1-index batch always places, so
                # this terminates with every index served
                groups.insert(0, g[len(g) // 2:])
                groups.insert(0, g[:len(g) // 2])
        if not self.scheduler.running:
            self.scheduler.pump()
        rec_of = {}
        for g, f in futs:
            out = f.result()
            for i, rec in zip(g, out):
                rec_of[i] = rec
        return np.stack([rec_of[i] for i in request])

    def query(self, indices: Sequence[int]) -> np.ndarray:
        """Alias of :meth:`query_batch` — the batch composite serves every
        retrieval through the bucketed plane."""
        return self.query_batch(indices)

    def submit(self, index: int, *,
               deadline_s: Optional[float] = None) -> AnswerFuture:
        """Single-index streaming form, served as a 1-real-(B-1)-dummy
        round (the padded traffic shape is identical to a full batch —
        a lone streaming client leaks no less than a batching one)."""
        inner = self.submit_batch([index], deadline_s=deadline_s)
        fut = AnswerFuture(deadline=inner.deadline)

        def _unwrap(done: AnswerFuture):
            exc = done.exception()
            if exc is not None:
                fut.set_exception(exc)
            else:
                fut.epoch = done.epoch
                fut.set_result(done.result(timeout=0)[0])

        inner.add_done_callback(_unwrap)
        return fut
