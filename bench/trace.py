"""From a profiler trace (``.xplane.pb``) to the numbers the metrics read.

Read with ``jax.profiler.ProfileData``. On a TPU each chip is a plane
named ``/device:TPU:<k>``; its ``XLA Modules`` line holds one event per
program execution (``jit_serve(<fingerprint>)`` for a serve step, one per
party and batch). Traced runs compile their programs without per-op trace
marks, so programs are the finest unit read. The host plane ``/host:CPU``
carries the harness's own spans, named ``bench.<stage>``
(``jax.profiler.TraceAnnotation``), on the same clock.

* busy: the union of the intervals in which a program ran on a chip,
  averaged over the chips used. Programs, not ops: a program's ops nest
  and overlap, its own interval does not. A trace past the profiler's
  2 GB limit loses every event after some point, programs too, and marks
  it with a ``Trace Buffers Dropped`` event: the window then ends there.
* idle gaps: the stretches between programs on chip 0, each put down to
  the harness span that covers most of it (``gen``, ``collate``,
  ``stage``, ``dispatch``, ``finalize``), or ``waiting``.
* the bucket of each serve step: one chip runs its programs in the order
  they were enqueued, so the i-th serve program on chip 0 is the i-th
  party-step the harness saw dispatched (``serve_buckets``).
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

#: the XLA module name of the jitted serve step (``jax.jit(serve)``)
SERVE_MODULE = "jit_serve"
SPAN_PREFIX = "bench."
#: the event the profiler writes where it dropped events
DROPPED = "Trace Buffers Dropped"
#: idle gaps shorter than this are dispatch noise, not host stalls
GAP_MIN_NS = 100_000


@dataclass
class TraceRun:
    chips: int
    busy_s: float = 0.0                       # mean over the chips used
    window_s: float = 0.0                     # set by the harness
    modules: Dict[str, List[float]] = field(default_factory=dict)
    #: chip 0's serve steps: (start ns, duration ns, program name)
    serve: List[Tuple[int, int, str]] = field(default_factory=list)
    gaps: List[Tuple[int, int]] = field(default_factory=list)   # chip 0
    spans: List[Tuple[str, int, int]] = field(default_factory=list)
    dropped: bool = False
    kept_s: Optional[float] = None            # where dropping began

    @property
    def serve_s(self) -> float:
        """Device seconds of the serve-step programs, chip 0."""
        return sum(d for _, d, _ in self.serve) / 1e9


def module_name(event_name: str) -> str:
    """``jit_serve(1337...)`` -> ``jit_serve``."""
    return event_name.split("(", 1)[0]


def union_ns(intervals) -> int:
    """Total length of the union of ``(start, end)`` intervals."""
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def _gaps(intervals) -> List[Tuple[int, int]]:
    out, end = [], None
    for s, e in sorted(intervals):
        if end is not None and s - end >= GAP_MIN_NS:
            out.append((end, s))
        end = e if end is None else max(end, e)
    return out


def _dropped_at(line) -> int:
    """Where the profiler began to drop events (ns from the trace's start),
    or None."""
    for e in line.events:
        if e.name == DROPPED:
            return e.start_ns
    return None


def reduce_planes(planes, chips: int = 1) -> TraceRun:
    """Reduce the planes of one trace (``ProfileData.planes``, or any
    objects with ``name``/``lines``/``events``/``start_ns``/``duration_ns``).
    Event times count from the trace's start. Where the profiler dropped
    events, the kept stretch before the drop is the traced window
    (``kept_s``), and only programs that ended inside it count."""
    run = TraceRun(chips=chips)
    device = []
    for plane in planes:
        if plane.name.startswith("/device:TPU:"):
            try:
                chip = int(plane.name.rsplit(":", 1)[1])
            except ValueError:
                continue
            if chip < chips:
                device.append((chip, {line.name: line
                                      for line in plane.lines}))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        run.spans.append((e.name[len(SPAN_PREFIX):],
                                          e.start_ns,
                                          e.start_ns + e.duration_ns))
    drops = [_dropped_at(lines["XLA TraceMe"]) for _, lines in device
             if "XLA TraceMe" in lines]
    drops = [d for d in drops if d is not None]
    if drops:
        run.dropped = True
        run.kept_s = min(drops) / 1e9
    end = min(drops) if drops else None
    busy = []
    for chip, lines in device:
        progs = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                       for e in (lines["XLA Modules"].events
                                 if "XLA Modules" in lines else ())
                       if end is None or e.start_ns + e.duration_ns <= end)
        busy.append(union_ns((s, e) for s, e, _ in progs))
        if chip:
            continue
        for s, e, name in progs:
            agg = run.modules.setdefault(module_name(name), [0, 0.0])
            agg[0] += 1
            agg[1] += (e - s) / 1e9
            if module_name(name) == SERVE_MODULE:
                run.serve.append((s, e - s, name))
        run.gaps = _gaps((s, e) for s, e, _ in progs)
    run.busy_s = sum(busy) / len(busy) / 1e9 if busy else 0.0
    return run


def reduce_dir(tracedir: str, chips: int = 1) -> TraceRun:
    """Reduce the one ``.xplane.pb`` that ``jax.profiler`` wrote under
    ``tracedir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(tracedir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {tracedir}, "
                           f"found {len(files)}")
    return reduce_planes(ProfileData.from_file(files[0]).planes, chips)


def serve_buckets(run: TraceRun, dispatched) -> Optional[List[int]]:
    """The bucket each traced serve step ran, from ``dispatched``: the
    bucket of every party-step the host enqueued since the trace began, in
    order. None where the two disagree: more serve programs than
    dispatches, or one program (fingerprint) at two buckets."""
    if len(run.serve) > len(dispatched):
        return None
    bucket_of = {}
    for (_, _, name), bucket in zip(run.serve, dispatched):
        if bucket_of.setdefault(name, bucket) != bucket:
            return None
    return list(dispatched[:len(run.serve)])


def label_gap(gap, spans) -> str:
    """The harness span that covers most of an idle gap; ``waiting`` where
    the host was in none of them."""
    best, cover = "waiting", 0
    s0, e0 = gap
    for name, s, e in spans:
        c = min(e, e0) - max(s, s0)
        if c > cover:
            best, cover = name, c
    return best


def breakdown(run: TraceRun, top: int = 10) -> dict:
    """The device programs that took most time, and the longest idle
    gaps by what the host was doing, at most ``top`` of each."""
    ops = sorted(((k, v[1]) for k, v in run.modules.items()),
                 key=lambda kv: -kv[1])[:top]
    gaps = sorted(run.gaps, key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[label_gap(g, run.spans), (g[1] - g[0]) / 1e9]
                          for g in gaps]}
