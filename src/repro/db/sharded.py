"""`ShardedDatabase` — mesh placement + epoched online updates.

One object owns what used to be smeared across the serving stack:

Placement (DESIGN.md §8.2)
    The canonical u32 word store is placed **chunked per shard**
    (``jax.make_array_from_callback``): each device's row slice is cut as
    a numpy *view* of the host array and transferred directly, so a
    GB-scale DB is never materialized twice on the host (the old path —
    ``jnp.asarray(db_words)`` then ``device_put`` per party — copied the
    whole DB once per party before it ever reached a device). Layout is
    the paper's linear sharding: rows split over the ``model`` axis,
    replicated across cluster (``data``/``pod``) axes. The placement,
    up to the arrays being on the devices, is the profiler span
    ``pir.db_place``.

Views (DESIGN.md §8.1)
    Protocols declare the view they contract against
    (``PIRProtocol.db_view``): ``words`` (u32, XOR schemes), ``bytes``
    (int8, the additive GEMM) or ``bytes32`` (int32 bytes, the LWE GEMM).
    Derived views are packed **on device** from the resident word view
    (each shard packs its rows, in chunks, lazily on first use) and
    thereafter maintained
    *incrementally* by the update path — never re-packed from scratch,
    never round-tripped through the host.

Hints (DESIGN.md §10)
    Single-server protocols register per-epoch *hints* (server-side
    preprocessing, e.g. the LWE ``H = A^T.DB``): materialized lazily per
    epoch via ``hint(name)``, delta-updated exactly on ``publish()`` when
    the protocol registered a delta fn (dropped and lazily rebuilt
    otherwise). Retired-epoch hints stay fetchable for one epoch of
    hysteresis, matching the view double buffer.

Epoched updates (DESIGN.md §8.3)
    ``stage(rows, values)`` accumulates a public delta log on the host;
    ``publish()`` applies the whole delta to every resident view as one
    O(rows) scatter and bumps the epoch. Updates are *public metadata*
    (the DB contents are public in the PIR model — privacy protects the
    query index, never the data), so staging/publishing identical deltas
    at every party keeps all k parties' replicas — and therefore their
    answer shares — consistent. Publication is double-buffered: jax
    arrays are immutable, so serve steps already dispatched against the
    old epoch finish unperturbed, and the previous epoch's views are
    additionally pinned (one epoch of hysteresis) so epoch-tagged answers
    can be checked against the exact snapshot they were computed at.

All host→device traffic is accounted in :class:`TransferStats`, which is
what lets tests assert the update path moves O(rows · item_bytes), not
O(db_bytes).
"""
from __future__ import annotations

import math
import threading
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config import PIRConfig
from repro.db.spec import DatabaseSpec
from repro.launch.mesh import pir_shard_axis

#: most rows a derived view is packed in at once (see ``_derive``)
_PACK_ROWS = 1 << 20


def _place_span(rows: int, nbytes: int,
                shards: int) -> jax.profiler.TraceAnnotation:
    """Profiler span ``pir.db_place`` over one placement of the word store:
    its ``rows``, ``bytes`` and the ``shards`` it is split into."""
    return jax.profiler.TraceAnnotation(
        "pir.db_place", rows=int(rows), bytes=int(nbytes),
        shards=int(shards))


@dataclass
class TransferStats:
    """Host→device byte accounting (per replica; clusters replicate)."""
    preload_h2d_bytes: int = 0     # full-view placements (epoch 0 only)
    update_h2d_bytes: int = 0      # delta transfers (idx + row values)
    n_full_placements: int = 0     # chunked host→device placements
    n_view_packs: int = 0          # on-device full word→byte derivations
    n_publishes: int = 0
    n_hint_builds: int = 0         # full hint recomputes (lazy, per epoch)
    n_hint_deltas: int = 0         # O(rows) incremental hint updates


@dataclass(frozen=True)
class _HintSpec:
    """One registered hint: full rebuild + optional exact delta update.

    build  words view [N, W] -> hint array (device)
    delta  (hint, rows, old_words, new_words) -> updated hint, or None —
           rows are the deduplicated UNPADDED published indices, old/new
           the [R, W] word rows before/after the scatter. Must be exact
           (byte-for-byte equal to a rebuild); hints without a delta are
           dropped on publish and lazily rebuilt.
    """
    build: object
    delta: object = None


@dataclass
class PublishedDelta:
    """Public metadata of one published epoch (the online-update log).

    ``rows``/``vals`` are the deduplicated (last-write-wins), unpadded
    delta: replaying ``stage(rows, vals); publish()`` against any replica
    of the previous epoch reproduces this epoch byte-for-byte — which is
    exactly what the replica plane's fan-out/catch-up does.
    """
    epoch: int                     # epoch the delta produced
    rows: np.ndarray               # deduplicated row indices written
    n_staged: int                  # staged entries folded into it
    vals: Optional[np.ndarray] = None   # deduplicated [R, item_words] u32


@dataclass
class _Epoch:
    """One immutable DB version: epoch id + its device-resident views
    and lazily materialized per-epoch hints (single-server protocols)."""
    epoch: int
    views: Dict[str, jax.Array] = field(default_factory=dict)
    hints: Dict[str, jax.Array] = field(default_factory=dict)


class ShardedDatabase:
    """The versioned, mesh-placed PIR database shared by all k parties.

    Thread-safe: the serving scheduler reads views from its session thread
    while clients ``stage``/``publish`` from theirs. ``view()`` is the
    only read entry point — callers must re-fetch it per dispatch (never
    cache across batches) so a published epoch is picked up immediately;
    batches already dispatched hold references to the old arrays and
    finish against the old epoch.
    """

    def __init__(self, db_words: np.ndarray,
                 cfg: Union[PIRConfig, DatabaseSpec],
                 mesh: jax.sharding.Mesh):
        self.spec = (cfg if isinstance(cfg, DatabaseSpec)
                     else DatabaseSpec.from_config(cfg))
        self.mesh = mesh
        shard = pir_shard_axis(mesh)
        self.n_shards = mesh.shape[shard] if shard else 1
        self.spec.rows_per_shard(self.n_shards)   # validate the layout
        self._row_spec = P(shard, None)
        self.stats = TransferStats()
        self._lock = threading.RLock()
        self._staged_rows: List[np.ndarray] = []
        self._staged_vals: List[np.ndarray] = []
        self.published: List[PublishedDelta] = []
        self._scatter_cache: dict = {}
        self._pack_cache: dict = {}
        self._hint_specs: Dict[str, _HintSpec] = {}
        self._subscribers: List = []   # publish fan-out callbacks
        #: optional ChaosInjector consulted at the "db.publish" seam
        #: (fault injection is repro/chaos's job; None in production)
        self.chaos = None
        host = np.asarray(db_words)
        if self.spec.checksum:
            # accept logical-width payload rows; the checksum column is
            # this plane's responsibility (attached once, host-side O(N),
            # then maintained through publish() O(rows) deltas)
            host = self.spec.attach_checksums(host)
        host = self.spec.validate_words(host)
        self._current = _Epoch(epoch=0,
                               views={"words": self._place(host)})
        self._retired: Optional[_Epoch] = None

    # ------------------------------------------------------------------
    # placement + views
    # ------------------------------------------------------------------

    def sharding(self, view: str = "words") -> NamedSharding:
        """NamedSharding of one view: rows over the DB-shard axis,
        replicated across cluster axes (both views share the row spec)."""
        self.spec.view_dtype(view)
        return NamedSharding(self.mesh, self._row_spec)

    def _place(self, host_words: np.ndarray) -> jax.Array:
        """Chunked per-shard placement of the canonical word store, up to
        the arrays being on the devices: the profiler span
        ``pir.db_place`` (``rows``, ``bytes``, ``shards``)."""
        with _place_span(host_words.shape[0], host_words.nbytes,
                         self.n_shards):
            arr = jax.block_until_ready(jax.make_array_from_callback(
                self.spec.view_shape("words"), self.sharding("words"),
                lambda idx: host_words[idx]))   # numpy view per device chunk
        self.stats.n_full_placements += 1
        self.stats.preload_h2d_bytes += host_words.nbytes
        return arr

    @property
    def epoch(self) -> int:
        with self._lock:
            return self._current.epoch

    @property
    def n_staged(self) -> int:
        with self._lock:
            return sum(len(r) for r in self._staged_rows)

    def view(self, name: str = "words", *,
             epoch: Optional[int] = None) -> jax.Array:
        """The device-resident array of one view at the current epoch.

        ``epoch`` may name the current epoch or the immediately previous
        one (the double-buffered snapshot kept for in-flight answers);
        anything older has been released.
        """
        with self._lock:
            holder = self._holder(epoch)
            if name not in holder.views:
                holder.views[name] = self._derive(name, holder.views["words"])
            return holder.views[name]

    def _holder(self, epoch: Optional[int]) -> _Epoch:
        """The resident _Epoch an epoch id names (lock held by caller)."""
        if epoch is None or epoch == self._current.epoch:
            return self._current
        if self._retired is None or epoch != self._retired.epoch:
            raise KeyError(
                f"epoch {epoch} is not resident (current="
                f"{self._current.epoch}, retired="
                f"{None if self._retired is None else self._retired.epoch})")
        return self._retired

    def snapshot(self, names: Tuple[str, ...] = ("words",)
                 ) -> Tuple[int, Dict[str, jax.Array]]:
        """Atomically capture (epoch, views) for one dispatch.

        A dispatcher that answers against the returned arrays and tags
        with the returned epoch can never mislabel an answer, even when a
        ``publish`` lands concurrently — the arrays are immutable and the
        pair was read under one lock.
        """
        with self._lock:
            return self._current.epoch, {n: self.view(n) for n in names}

    def _derive(self, name: str, words: jax.Array) -> jax.Array:
        self.spec.view_dtype(name)           # KeyError on unknown views
        if name == "words":
            return words
        # on-device pack; counted so tests can assert it happens at most
        # once per epoch lineage (updates maintain it incrementally)
        self.stats.n_view_packs += 1
        if name not in self._pack_cache:
            spec = self.spec

            def pack(w, name=name):
                # each shard packs its own rows in chunks of <= 2^20: as
                # one op over a GiB-scale shard the TPU compile takes ~2 min
                # and reports 8 GiB of temp; per chunk it takes seconds
                n = w.shape[0]
                chunk = math.gcd(n, _PACK_ROWS)
                out = jax.lax.map(
                    lambda x: spec.words_to_view_device(name, x),
                    w.reshape(n // chunk, chunk, w.shape[1]))
                return out.reshape(n, out.shape[-1])

            self._pack_cache[name] = jax.jit(jax.shard_map(
                pack, mesh=self.mesh, in_specs=self._row_spec,
                out_specs=self._row_spec, check_vma=False))
        return self._pack_cache[name](words)

    # ------------------------------------------------------------------
    # hints (single-server preprocessing, DESIGN.md §10)
    # ------------------------------------------------------------------

    def register_hint(self, name: str, build, delta=None) -> None:
        """Register a per-epoch hint: ``build(words_view) -> hint`` plus an
        optional exact ``delta(hint, rows, old_words, new_words)`` update.

        Hints are epoch-scoped like views: materialized lazily on first
        :meth:`hint` call, delta-updated (or dropped for lazy rebuild when
        no delta is registered) on :meth:`publish`. Re-registering a name
        replaces the spec but keeps already-materialized epoch hints.
        """
        with self._lock:
            self._hint_specs[name] = _HintSpec(build=build, delta=delta)

    def hint(self, name: str, *, epoch: Optional[int] = None) -> jax.Array:
        """The device-resident hint for one epoch (current or retired).

        Clients cache the returned array keyed by the epoch their answers
        were tagged with; a publish bumps the epoch, so stale caches miss
        and re-fetch — that is the hint-invalidation contract.
        """
        with self._lock:
            if name not in self._hint_specs:
                raise KeyError(f"unknown hint {name!r}; registered: "
                               f"{sorted(self._hint_specs)}")
            holder = self._holder(epoch)
            if name not in holder.hints:
                holder.hints[name] = \
                    self._hint_specs[name].build(holder.views["words"])
                self.stats.n_hint_builds += 1
            return holder.hints[name]

    # ------------------------------------------------------------------
    # epoched online updates
    # ------------------------------------------------------------------

    def stage(self, rows, values) -> int:
        """Append row writes to the pending (public) delta log.

        ``rows``: [R] indices; ``values``: [R, item_words] u32 or
        [R, item_bytes] u8. Nothing touches the device until
        :meth:`publish`. Returns the total staged entry count.
        """
        idx = np.atleast_1d(np.asarray(rows, np.int64))
        vals = self.spec.coerce_rows_to_words(values)
        if idx.ndim != 1 or len(idx) != len(vals):
            raise ValueError(
                f"rows/values length mismatch: {idx.shape} vs {vals.shape}")
        if len(idx) and (idx.min() < 0 or idx.max() >= self.spec.n_items):
            raise ValueError(
                f"row indices out of range [0, {self.spec.n_items})")
        with self._lock:
            self._staged_rows.append(idx)
            self._staged_vals.append(np.array(vals, np.uint32, copy=True))
            return sum(len(r) for r in self._staged_rows)

    def subscribe(self, fn) -> "callable":
        """Register ``fn(delta: PublishedDelta)`` to fire after every
        :meth:`publish` that produced a new epoch; returns an unsubscribe
        callable.

        This is the multi-subscriber fan-out seam the replica plane hangs
        off: the front-tier router subscribes to each replica's database
        to track its epoch (bounded-staleness routing), and a downstream
        replica can replay ``delta.rows``/``delta.vals`` into its own
        database to reproduce the epoch exactly. Callbacks run on the
        publishing thread, OUTSIDE the database lock (a subscriber may
        itself stage/publish into another database); they fire in epoch
        order because publishes are serialized by the lock.
        """
        self._subscribers.append(fn)
        def _unsubscribe(fn=fn):
            if fn in self._subscribers:
                self._subscribers.remove(fn)
        return _unsubscribe

    def publish(self) -> int:
        """Apply the staged delta to every resident view; bump the epoch.

        One O(rows) scatter per view: only the deduplicated row indices
        and word values cross the host→device boundary — never a full
        re-pack or re-placement. The previous epoch's views stay pinned
        (double buffer) until the *next* publish. No-op (same epoch) when
        nothing is staged. Returns the now-current epoch. Subscribers
        (:meth:`subscribe`) are notified of the new epoch's delta after
        the swap, outside the lock.
        """
        with self._lock:
            rows = (np.concatenate(self._staged_rows) if self._staged_rows
                    else np.zeros((0,), np.int64))
            if not len(rows):
                # nothing staged (or only zero-row stage calls): no new
                # epoch — epoch churn with identical data would spuriously
                # invalidate epoch-keyed clients
                self._staged_rows.clear()
                self._staged_vals.clear()
                return self._current.epoch
            vals = np.concatenate(self._staged_vals)
            n_staged = len(rows)
            self._staged_rows.clear()
            self._staged_vals.clear()
            # last-write-wins dedup: scatter order is unspecified for
            # duplicate indices, so resolve collisions on the host
            _, first_of_rev = np.unique(rows[::-1], return_index=True)
            keep = np.sort(len(rows) - 1 - first_of_rev)
            rows, vals = rows[keep], vals[keep]
            rows_u, vals_u = rows, vals           # pre-padding references
            # device paths (scatter + hint deltas) run at *stored* width;
            # PublishedDelta.vals stays logical so replicas replaying the
            # delta through stage() re-attach their own checksum column
            vals_st_u = self.spec.attach_checksums(vals_u)
            vals = vals_st_u
            # hint deltas need the deduplicated UNPADDED delta (a padded
            # duplicate would subtract its old row twice) and the old word
            # rows gathered from the pre-publish view, before the scatter
            delta_hints = {n: h for n, h in self._current.hints.items()
                           if self._hint_specs[n].delta is not None}
            if delta_hints:
                old_words = self._current.views["words"][
                    jnp.asarray(rows_u.astype(np.int32))]
            # pad the delta to a power of two (replicating one entry:
            # identical index+value pairs scatter deterministically) so
            # ragged update sizes reuse a small set of compiled scatters
            r_pad = max(1, 1 << (len(rows) - 1).bit_length())
            if r_pad > len(rows):
                pad = r_pad - len(rows)
                rows = np.concatenate([rows, np.repeat(rows[-1:], pad)])
                vals = np.concatenate([vals, np.repeat(vals[-1:], pad,
                                                       axis=0)])
            idx_dev = jnp.asarray(rows.astype(np.int32))
            vals_dev = jnp.asarray(vals)
            self.stats.update_h2d_bytes += rows.astype(np.int32).nbytes \
                + vals.nbytes
            new_views = {
                name: self._scatter(name, len(rows))(arr, idx_dev, vals_dev)
                for name, arr in self._current.views.items()
            }
            # materialized hints: exact O(rows) delta where registered;
            # delta-less hints are dropped and lazily rebuilt on next use
            new_hints = {}
            for name, harr in delta_hints.items():
                new_hints[name] = self._hint_specs[name].delta(
                    harr, rows_u, old_words, jnp.asarray(vals_st_u))
                self.stats.n_hint_deltas += 1
            self._retired = self._current
            self._current = _Epoch(epoch=self._retired.epoch + 1,
                                   views=new_views, hints=new_hints)
            self.stats.n_publishes += 1
            delta = PublishedDelta(epoch=self._current.epoch, rows=rows_u,
                                   n_staged=n_staged, vals=vals_u)
            self.published.append(delta)
            epoch = self._current.epoch
            subscribers = tuple(self._subscribers)
        # chaos seam "db.publish": a drop swallows this epoch's fan-out
        # (subscribers converge via the delta-log catch-up on the next
        # publish); delay/stall events sleep before notification
        chaos = self.chaos
        if chaos is not None and chaos.should_drop("db.publish"):
            return epoch
        for fn in subscribers:       # outside the lock (see subscribe())
            fn(delta)
        return epoch

    def _scatter(self, view: str, r: int):
        """Cached compiled delta application for (view, padded row count).

        The update payload always crosses the host boundary in word form;
        the byte view's int8 rows are derived on device inside the
        scatter, so maintaining both views costs one H2D transfer."""
        key = (view, r)
        if key not in self._scatter_cache:
            sharding = self.sharding(view)
            spec = self.spec
            fn = lambda arr, idx, vals, view=view: arr.at[idx].set(
                spec.words_to_view_device(view, vals))
            self._scatter_cache[key] = jax.jit(fn, out_shardings=sharding)
        return self._scatter_cache[key]
