"""Sharded PIR server — the paper's Figure 5 dataflow on a TPU mesh.

Topology mapping (DESIGN.md §2):

  model axis  = the DPUs of one cluster. The DB is sharded over it in the
                paper's linear layout: shard d holds rows
                [d·B_d, (d+1)·B_d), B_d = N / |model|.
  data (and pod) axes = DPU clusters (paper §3.4): the DB is *replicated*
                across them and the query batch is sharded across them, so
                clusters answer disjoint queries in parallel.

Per-device step (inside shard_map) — Algorithm 1 with the host CPU removed:

  ① eval own DPF leaf range   (paper: host CPU + CPU→DPU copy ②③)
  ② select-XOR scan / GEMM over the local DB rows      (paper: DPU dpXOR ④)
  ③ reduce 32 B subresults over `model`                (paper: DPU→CPU copy
     + host aggregation ⑤⑥)

What runs in steps ①–③ is no longer decided here: the *protocol plane*
(``core/protocol.py``) owns it. A registered ``PIRProtocol`` supplies the
per-shard answer contraction (``answer_local``), the cross-shard reduction
algebra (``reduce`` — XOR all-reduce for the XOR schemes, psum for
additive), and the key pytree shapes (``key_specs``); an ``ExecutionPlan``
picks the kernel path (materialized vs fused expansion, jnp oracle vs the
Pallas bodies, gather vs butterfly collective), and the engine plane
(``repro.engine.resolve``) picks that plan per batch bucket. The
*database plane* (``db/``, DESIGN.md §8) owns what the data looks like and where it lives:
``DatabaseSpec`` centralizes shape/packing math, ``ShardedDatabase`` owns
chunked mesh placement, the per-protocol views (u32 words / int8 bytes —
declared via ``PIRProtocol.db_view``) and epoched online updates. This
module only owns the mesh plumbing: shard_map specs and the
lower-once-per-bucket compile cache.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import engine
from repro.config import PIRConfig
from repro.core import dpf
from repro.core import protocol as protocol_mod
from repro.core.protocol import ExecutionPlan, PIRProtocol
from repro.db import DatabaseSpec, ShardedDatabase

U32 = jnp.uint32


def _cluster_axes(mesh: jax.sharding.Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _shard_axis(mesh: jax.sharding.Mesh) -> Optional[str]:
    return "model" if "model" in mesh.axis_names else None


def _axis_size(mesh, names) -> int:
    n = 1
    for a in names if isinstance(names, tuple) else (names,):
        if a is not None:
            n *= mesh.shape[a]
    return n


def _key_pspec(keys_like, cluster: Tuple[str, ...]):
    """PartitionSpecs matching the batched-key pytree (batch axis sharded)."""
    def spec(leaf):
        rank = len(leaf.shape)
        return P(cluster, *([None] * (rank - 1)))
    return jax.tree_util.tree_map(spec, keys_like)


@dataclass
class ServeFns:
    """Compiled server entry points for one party.

    ``serve`` takes the device array of this protocol's declared DB view
    (``ShardedDatabase.view(protocol.db_view)``) — never a raw host array.
    """
    serve: Callable            # (db_view, keys) -> per-query answer shares
    db_sharding: NamedSharding
    plan: ExecutionPlan
    protocol: PIRProtocol
    # batched-key pytree -> NamedSharding pytree (for async host staging)
    key_shardings: Optional[Callable] = None


def build_serve_fn(
    cfg: PIRConfig,
    mesh: jax.sharding.Mesh,
    *,
    n_queries: int,
    protocol: Optional[PIRProtocol] = None,
    plan: Optional[ExecutionPlan] = None,
) -> ServeFns:
    """Build the sharded serve function for one step of ``n_queries``.

    The protocol defaults to the one named by ``cfg.protocol``; the plan
    defaults to the engine's (``repro.engine.resolve``). ``plan=`` is the
    one place a caller supplies a plan of its own. No share-scheme
    branching happens here — the protocol owns the contraction and
    reduction.
    """
    proto = protocol if protocol is not None else protocol_mod.for_config(cfg)
    if plan is None:
        plan = engine.resolve(cfg, n_queries)
    cluster = _cluster_axes(mesh)
    shard = _shard_axis(mesh)
    n_clusters = _axis_size(mesh, cluster)
    n_shards = _axis_size(mesh, shard)
    if n_queries % max(n_clusters, 1):
        raise ValueError(f"{n_queries} queries not divisible by {n_clusters} clusters")
    # per-shard row math (divisibility, power-of-two) lives in the spec
    rows_local = DatabaseSpec.from_config(cfg).rows_per_shard(n_shards)
    log_local = int(math.log2(rows_local))

    db_spec = P(shard, None)
    keys_spec_builder = lambda keys: _key_pspec(keys, cluster)
    out_spec = P(cluster, None)

    def local_step(db_local, keys_local):
        sidx = jax.lax.axis_index(shard) if shard else 0
        # ①② the protocol's per-shard contraction under the chosen plan
        partial_res = proto.answer_local(db_local, keys_local, sidx,
                                         log_local, plan)
        # ③ aggregation ⑤⑥ over DB shards, in the protocol's share algebra
        if shard:
            partial_res = proto.reduce(partial_res, shard, n_shards, plan)
        return partial_res

    def serve(db, keys):
        ks = keys_spec_builder(keys)
        fn = jax.shard_map(
            local_step, mesh=mesh,
            in_specs=(db_spec, ks), out_specs=out_spec,
            check_vma=False,
        )
        return fn(db, keys)

    def key_shardings(keys_like):
        """NamedSharding pytree for a batched key pytree (host staging)."""
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), _key_pspec(keys_like, cluster),
            is_leaf=lambda x: isinstance(x, P))

    return ServeFns(
        serve=serve,
        db_sharding=NamedSharding(mesh, db_spec),
        plan=plan,
        protocol=proto,
        key_shardings=key_shardings,
    )


def bucket_for(buckets: Sequence[int], n: int) -> int:
    """The padding rule (DESIGN.md §6): smallest bucket >= n.

    Returns the largest bucket when n exceeds it — the caller then chunks
    (``PIRServer.answer``) or cuts batches no larger than it (the
    scheduler). ``buckets`` must be sorted ascending.
    """
    for b in buckets:
        if b >= n:
            return b
    return buckets[-1]


def default_buckets(n_clusters: int = 1, max_bucket: int = 32
                    ) -> Tuple[int, ...]:
    """Power-of-two batch buckets, each divisible by the cluster count.

    The serve step shards the query batch over clusters, so every compiled
    batch size must be a multiple of ``n_clusters``; buckets are the
    doubling ladder from ``n_clusters`` up to ``max_bucket`` (DESIGN.md §6).
    """
    n_clusters = max(n_clusters, 1)
    b = n_clusters
    out = []
    while b <= max(max_bucket, n_clusters):
        out.append(b)
        b *= 2
    return tuple(out)


class BucketedServeFns:
    """Lower-once-per-bucket cache of compiled serve steps for one party.

    Ragged traffic never recompiles: a batch of Q queries is padded up to
    the smallest bucket >= Q (``PIRProtocol.pad``) and answered by that
    bucket's cached ``jax.jit`` step. ``n_compiles`` counts cache misses so
    tests/benches can assert reuse. Each bucket's plan comes from the
    engine plane (plan-cache hit → measured tuned or warm plan, miss → the
    heuristic) — so e.g. small and large buckets of the same server family
    may take different kernel paths. Plan resolution happens HERE, once
    per bucket at build time (``plan_for_bucket``); dispatch never touches
    the tuner or cache I/O.
    """

    def __init__(self, cfg: PIRConfig, mesh: jax.sharding.Mesh, *,
                 buckets: Sequence[int], party: int = 0,
                 protocol: Optional[PIRProtocol] = None):
        n_clusters = _axis_size(mesh, _cluster_axes(mesh))
        for b in buckets:
            if b % max(n_clusters, 1):
                raise ValueError(
                    f"bucket {b} not divisible by {n_clusters} clusters")
        self.cfg = cfg
        self.mesh = mesh
        self.party = party
        self.protocol = (protocol if protocol is not None
                         else protocol_mod.for_config(cfg))
        self.buckets = tuple(sorted(set(buckets)))
        self.n_compiles = 0
        self._cache: dict = {}   # bucket -> (ServeFns, jitted serve)
        self._plans: dict = {}   # bucket -> resolved ExecutionPlan

    def bucket_for(self, n: int) -> int:
        return bucket_for(self.buckets, n)

    def plan_for_bucket(self, bucket: int) -> ExecutionPlan:
        """The bucket's resolved plan — one engine resolution per bucket,
        cached, shared with the compiled step (``fns_for``)."""
        if bucket not in self._plans:
            self._plans[bucket] = engine.resolve(self.cfg, bucket)
        return self._plans[bucket]

    def plan_report(self) -> dict:
        """{bucket: plan provenance + predicted bytes} for every bucket —
        resolved without compiling anything (runtime/launch reporting)."""
        n_shards = _axis_size(self.mesh, _shard_axis(self.mesh))
        n_clusters = max(_axis_size(self.mesh, _cluster_axes(self.mesh)), 1)
        return {b: engine.plan_report(self.cfg, self.plan_for_bucket(b),
                                      b // n_clusters, n_shards=n_shards)
                for b in self.buckets}

    def fns_for(self, bucket: int) -> Tuple[ServeFns, Callable]:
        if bucket not in self._cache:
            fns = build_serve_fn(self.cfg, self.mesh, n_queries=bucket,
                                 protocol=self.protocol,
                                 plan=self.plan_for_bucket(bucket))
            # explicit in_shardings: host-resident and pre-staged
            # (device_put) key batches hit the SAME executable — without
            # this, staging would silently fork a second ~identical
            # compile per bucket (observed +70 s on the dev container)
            keys_like = self.protocol.key_specs(self.cfg, bucket,
                                                party=self.party)
            in_sh = (fns.db_sharding, fns.key_shardings(keys_like))
            self._cache[bucket] = (fns, jax.jit(fns.serve, in_shardings=in_sh))
            self.n_compiles += 1
        return self._cache[bucket]

    def stage(self, keys) -> dpf.DPFKey:
        """Pad a batched key pytree to its bucket and device_put it.

        This is the host-side half of the double-buffered serve pipeline:
        staging batch k+1's keys overlaps batch k's device compute.
        Batches larger than the largest bucket pass through unstaged —
        ``answer`` chunks (and pads per chunk) at dispatch.
        """
        if self.protocol.n_queries(keys) > self.buckets[-1]:
            return keys
        bucket = self.bucket_for(self.protocol.n_queries(keys))
        fns, _ = self.fns_for(bucket)
        padded = self.protocol.pad(keys, bucket)
        if fns.key_shardings is not None:
            padded = jax.device_put(padded, fns.key_shardings(padded))
        return padded

    def answer(self, db: Union[jax.Array, ShardedDatabase], keys
               ) -> jax.Array:
        """Answer a batch of any size; returns exactly [Q, ...] shares.

        ``db`` is either the protocol's view array or a
        :class:`ShardedDatabase` (resolved to ``protocol.db_view`` at
        dispatch, so a freshly published epoch is picked up per batch).
        Q pads up to its bucket (pad answers computed and sliced off);
        batches beyond the largest bucket are chunked. The result is
        asynchronous (no block until the caller consumes it).
        """
        if isinstance(db, ShardedDatabase):
            db = db.view(self.protocol.db_view)
        q = self.protocol.n_queries(keys)
        max_b = self.buckets[-1]
        if q <= max_b:
            return self._answer_one(db, keys)
        chunks = []
        for lo in range(0, q, max_b):
            hi = min(lo + max_b, q)
            part = jax.tree_util.tree_map(lambda x: x[lo:hi], keys)
            chunks.append(self._answer_one(db, part))
        return jnp.concatenate(chunks, axis=0)

    def _answer_one(self, db: jax.Array, keys) -> jax.Array:
        q = self.protocol.n_queries(keys)
        bucket = self.bucket_for(q)
        _, jitted = self.fns_for(bucket)
        return jitted(db, self.protocol.pad(keys, bucket))[:q]


class PIRServer:
    """One logical PIR server (one of the n non-colluding parties).

    References a :class:`ShardedDatabase` (the database plane owns
    placement, views and epochs — paper §3.3 "database preloading":
    transfer cost excluded from query latency) and owns a *family* of
    compiled serve steps, one per batch bucket (lower-once-per-bucket).
    The database may be *shared* across parties (``MultiServerPIR`` does
    exactly that — the DB contents are public, only the key material is
    per-party), so k parties no longer cost k host/device copies. The
    share scheme comes from the injected ``PIRProtocol`` (default: the
    one ``cfg.protocol`` names).

    ``db_words`` (a raw host array, wrapped into a private
    ``ShardedDatabase``) is the legacy construction path; new code passes
    ``database=``.
    """

    def __init__(
        self,
        party: int,
        db_words: Optional[np.ndarray] = None,
        cfg: PIRConfig = None,
        mesh: jax.sharding.Mesh = None,
        *,
        database: Optional[ShardedDatabase] = None,
        n_queries: int = 32,
        buckets: Optional[Sequence[int]] = None,
        protocol: Optional[PIRProtocol] = None,
    ):
        if (db_words is None) == (database is None):
            raise ValueError(
                "pass exactly one of db_words= (legacy host array) or "
                "database= (ShardedDatabase)")
        if cfg is None or mesh is None:
            raise ValueError("cfg= and mesh= are required (the database "
                             "does not substitute for them)")
        if database is not None:
            # fail at construction, not as a shape/sharding error deep
            # inside the first compiled serve step
            expect = DatabaseSpec.from_config(cfg)
            if database.spec != expect:
                raise ValueError(
                    f"database spec {database.spec} does not match the "
                    f"config's {expect}")
            if database.mesh != mesh:
                raise ValueError(
                    "database was placed on a different mesh than the "
                    "serve steps will run on")
        self.party = party
        self.cfg = cfg
        self.mesh = mesh
        n_clusters = _axis_size(mesh, _cluster_axes(mesh))
        if buckets is None:
            buckets = default_buckets(n_clusters,
                                      max_bucket=max(n_queries, 1))
        if n_queries not in buckets:
            buckets = tuple(sorted(set(buckets) | {n_queries}))
        self.bucketed = BucketedServeFns(
            cfg, mesh, buckets=buckets, party=party, protocol=protocol)
        self.protocol = self.bucketed.protocol
        self.n_queries = n_queries
        self.fns = self.bucketed.fns_for(n_queries)[0]
        self.db = (database if database is not None
                   else ShardedDatabase(db_words, cfg, mesh))

    @property
    def n_compiles(self) -> int:
        return self.bucketed.n_compiles

    @property
    def buckets(self) -> Tuple[int, ...]:
        return self.bucketed.buckets

    @property
    def db_epoch(self) -> int:
        """Current epoch of the (possibly shared) database."""
        return self.db.epoch

    def stage_keys(self, keys) -> dpf.DPFKey:
        """Pad + device_put a key batch ahead of dispatch (pipelining)."""
        return self.bucketed.stage(keys)

    def plan_report(self) -> dict:
        """Per-bucket plan provenance (tuned vs warm vs heuristic) +
        predicted step bytes — the engine plane's reporting surface."""
        return self.bucketed.plan_report()

    def answer(self, keys) -> jax.Array:
        """Answer a batch of queries (keys stacked on the leading axis).

        Any batch size works: Q is padded up to its bucket (answers for pad
        slots are computed and discarded) and batches beyond the largest
        bucket are chunked. The database view is re-fetched per call, so
        an epoch published between batches is served immediately; a batch
        already dispatched finishes against the epoch it captured.
        Returns exactly [Q, ...] answer shares.
        """
        return self.bucketed.answer(self.db, keys)
