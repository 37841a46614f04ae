#!/usr/bin/env python3
"""Two-server PIR on the chip, one benchmark cell per run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are looked up by name:
the cell in ``BENCHMARK.json`` at the checkout's root, the configuration
in ``bench/configs/<config>.json``, the mix in ``bench/traffic/<traffic>.json``
(read by ``bench/load.py``), and each per-layer metric in
``bench/metrics/<name>.py``. A new cell, mix or metric is a new entry and a
new file; nothing here changes.

A run drives the system's normal path: ``MultiServerPIR.submit`` (client
Gen) -> ``QueryScheduler`` (padded bucket batches, depth-2 dispatch) ->
both parties' ``PIRServer`` bucketed serve steps over one shared
``ShardedDatabase`` -> ``reconstruct_with``. Set-up makes the database on
the host from the seed, places it, and warms every batch size the mix
uses through that same path. The window then runs for ``--seconds``; a
closed mix's window ends at the first answer at or after that time, so
its rate counts whole steps. Once the window has closed and the device's
peak memory is read, every answer the window produced is compared with
the plain reference (``bench/references/<reference>.py``).

The run refuses, exiting non-zero with no result, unless JAX's first
device is a TPU whose ``device_kind`` is in ``bench/peaks.py`` and the
process has the chips the cell asks for, and unless ``REPRO_FORCE_BACKEND``
is unset. The plan cache is off, so the plan that runs depends only on
the checkout. JAX's compilation cache is ``.jax_cache/`` at the checkout's
root. Every run compiles its programs without per-op trace marks
(``LIBTPU_FLAG``), so a ``--trace 1`` run, which traces the whole window,
times the same programs as a ``--trace 0`` run. The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import load  # noqa: E402
import peaks as peaks_mod  # noqa: E402

#: the event JAX reports each backend compile's duration under
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: how long after the window closes an answer may still arrive: eight
#: closed-loop queries outstanding at the close, answered one bucket-1
#: step (7.7 s on pir-1g) at a time, take about a minute
LATE_S = 150.0
#: every run compiles its programs without per-op trace marks, so the
#: profiler records programs and not the millions of ops of a fused XLA
#: serve step (which fill its 2 GB within seconds and then take minutes to
#: write and read back); traced and untraced runs share one set of programs
LIBTPU_FLAG = "--xla_enable_hlo_trace=false"
#: sub-stream ids under the run's seed (load.py holds 1 and 2)
STREAM_DB, STREAM_KEYS, STREAM_WARM = 0, 3, 4
#: the database is drawn in this many blocks, on this many threads
DB_BLOCKS, DB_THREADS = 16, 8


class Refused(SystemExit):
    """The run cannot be made here (no chip, wrong chip, bad cell)."""


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the manifest and the files it names
# ---------------------------------------------------------------------------

def _read_json(path: Path) -> dict:
    if not path.is_file():
        raise Refused(f"{path.relative_to(ROOT)} is missing")
    return json.loads(path.read_text())


def load_cell(name: str, root: Path = ROOT) -> dict:
    """Everything one cell needs, by its name in ``BENCHMARK.json``."""
    manifest = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = _read_json(root / configs[cell["config"]]["file"])
    traffic = load.validate(
        _read_json(root / "bench" / "traffic" / f"{cell['traffic']}.json"))

    def listed(metric):
        return name in metric.get("workloads", [name])

    return {
        "cell": cell,
        "config": config,
        "traffic": traffic,
        "end_to_end": [m for m in manifest["end_to_end"] if listed(m)],
        "per_layer": [m for m in manifest["per_layer"] if listed(m)],
    }


def load_file(path: Path):
    """A module from its file, under a name no other module has."""
    if not path.is_file():
        raise Refused(f"{path.relative_to(ROOT)} is missing")
    name = "bench_" + "_".join(path.relative_to(BENCH).with_suffix("").parts)
    name = name.replace("-", "_").replace(".", "_")
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` (names may hold '-' and '.')."""
    return load_file(BENCH / kind / f"{name}.py")


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------

def require_chip(chips: int) -> dict:
    """The device record, or ``Refused`` unless this is a chip we know."""
    if os.environ.get("REPRO_FORCE_BACKEND"):
        raise Refused("REPRO_FORCE_BACKEND is set: refusing to run")
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise Refused(f"no TPU: JAX's first device is {dev.platform}")
    peaks_mod.peaks(dev.device_kind)          # raises for an unknown kind
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips; JAX sees {len(devs)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips}


def peak_bytes(mesh):
    """Highest ``peak_bytes_in_use`` over the mesh's devices, or None."""
    got = [(d.memory_stats() or {}).get("peak_bytes_in_use")
           for d in mesh.devices.flat]
    got = [p for p in got if p is not None]
    return max(got) if got else None


class CompileLog:
    """Counts backend compiles while on."""

    def __init__(self):
        import jax.monitoring
        self.n, self.seconds, self.on = 0, 0.0, False
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event, duration, **kwargs):
        if self.on and event == COMPILE_EVENT:
            self.n += 1
            self.seconds += duration


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def make_db(config: dict, seed: int) -> np.ndarray:
    """The database, uniform random bits from the seed, as ``[N, W]``
    little-endian u32 words. It is drawn in ``DB_BLOCKS`` blocks, each
    from its own sub-stream, on a few threads: the bits depend on the seed
    alone, not on the thread count."""
    from concurrent.futures import ThreadPoolExecutor
    n, width = int(config["n_items"]), int(config["item_bytes"])
    n64 = n * width // 8
    if n64 % DB_BLOCKS:
        raise ValueError(f"{n} x {width} B is not {DB_BLOCKS} whole blocks")
    out = np.empty(n64, np.uint64)
    per = n64 // DB_BLOCKS

    def fill(k):
        bits = np.random.PCG64(np.random.SeedSequence(
            [seed % (1 << 64), STREAM_DB, k]))
        out[k * per:(k + 1) * per] = bits.random_raw(per)

    with ThreadPoolExecutor(DB_THREADS) as pool:
        list(pool.map(fill, range(DB_BLOCKS)))
    return out.view("<u4").reshape(n, width // 4)


def build_system(config: dict, db: np.ndarray, seed: int, mesh):
    from repro.config import PIRConfig
    from repro.runtime.serve_loop import MultiServerPIR
    cfg = PIRConfig(n_items=int(config["n_items"]),
                    item_bytes=int(config["item_bytes"]),
                    protocol=config["protocol"])
    buckets = tuple(int(b) for b in config["assumed"]["buckets"])
    return MultiServerPIR(db, cfg, mesh, path=None, n_queries=max(buckets),
                          buckets=buckets,
                          client_rng=load.rng(seed, STREAM_KEYS))


def warm_sizes(config: dict) -> list:
    """The batch sizes a mix can form, each warmed once in set-up: every
    size up to the largest bucket, since each compiles programs of its own
    (the bucket's serve step, and the stacking, padding, slicing and
    reconstruction of that many real queries). Closed mixes form ragged
    batches too: a client's next query waits for its Gen."""
    top = max(int(b) for b in config["assumed"]["buckets"])
    return list(range(1, top + 1))


# ---------------------------------------------------------------------------
# host spans (traced runs only)
# ---------------------------------------------------------------------------

def _annotated(name, fn):
    import jax

    def spanned(*a, **k):
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            return fn(*a, **k)
    return spanned


def instrument(system) -> list:
    """Name client Gen and the scheduler's four stages in the profiler's
    trace (host spans ``bench.<stage>``, on the device's clock), from
    outside the program, so that idle gaps on the device can be put down
    to what the host was doing. Returns the list that every party-step
    dispatched from now on appends its bucket to, in dispatch order."""
    sch = system.scheduler
    for stage in ("collate", "stage", "dispatch", "finalize"):
        attr = f"_{stage}"
        if hasattr(sch, attr):
            setattr(sch, attr, _annotated(stage, getattr(sch, attr)))
    system.submit = _annotated("gen", system.submit)
    dispatched = []
    for server in system.servers:
        bucketed = server.bucketed
        answer = bucketed.answer

        def recorded(db, keys, _answer=answer):
            shares = _answer(db, keys)
            dispatched.append(int(shares.shape[0]))
            return shares

        bucketed.answer = recorded
    return dispatched


class Tracer:
    """The profiler over the whole window, with the Python tracer off and
    without HLO protos: the host side holds only the harness's spans."""

    def __init__(self):
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # harness spans only, on the host
        options.host_tracer_level = 1
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.window = (time.monotonic(), None)

    def stop(self):
        import jax
        self.window = (self.window[0], time.monotonic())
        jax.profiler.stop_trace()


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

class Requests:
    """What the window asked and what came back, on time.monotonic."""

    def __init__(self):
        self.rows = []                # dicts, one per request
        self._cv = threading.Condition()
        self.last_done = None

    def add(self, row):
        with self._cv:
            self.rows.append(row)

    def done(self, row, fut):
        t = time.monotonic()
        try:
            row["record"] = np.asarray(fut.result(timeout=0))
        except BaseException as e:    # noqa: BLE001 — counted as failed
            row["error"] = repr(e)
        with self._cv:
            row["t_done"] = t
            self.last_done = t
            self._cv.notify_all()

    def wait_done_after(self, t: float, limit: float):
        """Block until some answer arrives at or after ``t``; its time."""
        with self._cv:
            while self.last_done is None or self.last_done < t:
                left = limit - time.monotonic()
                if left <= 0:
                    return None
                self._cv.wait(left)
            return self.last_done

    def wait_all(self, limit: float) -> bool:
        with self._cv:
            while any("t_done" not in r for r in self.rows):
                left = limit - time.monotonic()
                if left <= 0:
                    return False
                self._cv.wait(left)
            return True


def _submit(system, reqs: Requests, index: int, due: float):
    row = {"index": index, "due": due, "t_submit": time.monotonic()}
    fut = system.submit(index)
    row["t_gen"] = time.monotonic()
    reqs.add(row)
    fut.add_done_callback(lambda f: reqs.done(row, f))
    return fut


def drive_closed(system, traffic, seed, seconds, n_items, reqs):
    """C clients, one query outstanding each. The window ends at the first
    answer at or after ``seconds``. Returns (t0, t_end, client threads)."""
    stop = threading.Event()

    def client(c):
        indices = load.client_indices(seed, c, n_items)
        while not stop.is_set():
            fut = _submit(system, reqs, next(indices), time.monotonic())
            try:
                fut.result()
            except BaseException:     # noqa: BLE001 — recorded by done()
                pass

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(int(traffic["clients"]))]
    t0 = time.monotonic()
    for th in threads:
        th.start()
    t_end = reqs.wait_done_after(t0 + seconds, t0 + seconds + LATE_S)
    stop.set()
    if t_end is None:                 # nothing came: the run has failed
        t_end = time.monotonic()
    return t0, t_end, threads


def drive_open(system, traffic, seed, seconds, n_items, reqs):
    """Arrivals on the seed's schedule; each timed from when it was due."""
    schedule = load.open_schedule(traffic, seed, seconds, n_items)
    t0 = time.monotonic()
    for due, index in schedule:
        wait = t0 + due - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        _submit(system, reqs, index, t0 + due)
    left = t0 + seconds - time.monotonic()
    if left > 0:
        time.sleep(left)
    return t0, t0 + seconds, []


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def _quantile(values, q):
    """``statistics.quantiles``-style (exclusive) quantile; needs 2+."""
    cuts = statistics.quantiles(values, n=100, method="exclusive")
    return cuts[int(round(q * 100)) - 1]


def end_to_end(spec, reqs, t0, t_end, peak, setup_s, config):
    """The cell's end-to-end metrics, from the host clock and the device."""
    in_window = [r for r in reqs.rows
                 if r["due"] < t_end and r["due"] >= t0]
    lat = sorted(r["t_done"] - r["due"] for r in in_window
                 if "t_done" in r and "record" in r)
    done_in = [r for r in reqs.rows if "record" in r
               and t0 < r["t_done"] <= t_end]
    db_bytes = int(config["n_items"]) * int(config["item_bytes"])
    values = {
        "setup_s": setup_s,
        "queries_per_s": len(done_in) / (t_end - t0) if done_in else None,
        "latency_p50_s": statistics.median(lat) if lat else None,
        "latency_p95_s": _quantile(lat, 0.95) if len(lat) >= 2 else None,
        "hbm_peak_per_db_byte": peak / db_bytes if peak else None,
    }
    out = {}
    for m in spec:
        v = values.get(m["name"])
        if v is None:
            raise RuntimeError(f"end-to-end metric {m['name']} has no "
                               f"reading in this run")
        out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out, {"latency_samples": len(lat), "completed_in_window":
                 len(done_in)}


def judge(reqs: Requests, reference, config, db) -> dict:
    """Every answer against the plain reference: the numbers compared."""
    wrong = missing = 0
    answered = [r for r in reqs.rows if "record" in r]
    if answered:
        want = reference.records(db, [r["index"] for r in answered], config)
        got = [r["record"] for r in answered]
        for g, w in zip(got, want):
            if g.shape != w.shape or not np.array_equal(g, w):
                wrong += 1
    missing = sum(1 for r in reqs.rows if "record" not in r)
    return {"wrong_records": {"value": wrong, "limit": 0},
            "unanswered": {"value": missing, "limit": 0},
            "answered": {"value": len(answered), "limit": 1}}


def passed(checks: dict) -> bool:
    """Every count at or under its limit; ``answered`` at or over its."""
    return all(c["value"] >= c["limit"] if name == "answered"
               else c["value"] <= c["limit"] for name, c in checks.items())


def execute(bundle: dict, seed: int, seconds: float, trace: bool, *,
            device: dict, plant=None, t_start: float = None) -> dict:
    """One run of one cell; returns the result line as a dict.

    ``plant`` (tests and the control only) breaks the built system before
    set-up warms it: ``plant(system)``.
    """
    import jax
    from repro.launch.mesh import make_local_mesh
    t_start = T_START if t_start is None else t_start
    cell, config, traffic = bundle["cell"], bundle["config"], bundle["traffic"]
    reference = load_module("references", config["reference"])
    compiles = CompileLog()
    compiles.on = True
    chips = int(cell.get("chips", 1))
    mesh = make_local_mesh(data=1, model=chips)

    db = make_db(config, seed)
    system = build_system(config, db, seed, mesh)
    if plant is not None:
        plant(system)
    jax.block_until_ready(system.db.view(system.protocol.db_view))
    warm_rows = Requests()
    rng = load.rng(seed, STREAM_WARM)
    for size in warm_sizes(config):
        idx = [int(i) for i in rng.integers(0, int(config["n_items"]),
                                            size=size)]
        for i, rec in zip(idx, system.query(idx)):
            warm_rows.add({"index": i, "due": 0.0, "t_done": 0.0,
                           "record": np.asarray(rec)})
    plans = {b: r["label"] for b, r in system.servers[0].plan_report().items()}
    log(f"[setup] plans {plans}; backend compiles {compiles.n} "
        f"({compiles.seconds:.3f} s)")

    tracer = None
    if trace:
        dispatched = instrument(system)
        tracer = Tracer()
    system.start()
    stats0 = _sched_stats(system)
    compiles.n = 0
    setup_s = time.monotonic() - t_start
    reqs = Requests()
    drive = drive_closed if traffic["kind"] == "closed" else drive_open
    t0, t_end, threads = drive(system, traffic, seed, seconds,
                               int(config["n_items"]), reqs)
    if trace:
        tracer.stop()
    stats1 = _sched_stats(system)
    in_window_compiles = compiles.n
    compiles.on = False
    complete = reqs.wait_all(t_end + LATE_S)
    for th in threads:
        th.join(timeout=LATE_S)
    system.close()
    peak = peak_bytes(mesh)
    del system
    log(f"[window] {t_end - t0:.6f} s; requests {len(reqs.rows)}; "
        f"backend compiles inside the window {in_window_compiles}; all "
        f"answered within {LATE_S:.0f} s of the close: {complete}")
    lateness = [r["t_submit"] - r["due"] for r in reqs.rows]
    if traffic["kind"] == "open" and lateness:
        log(f"[generator] late by median {statistics.median(lateness):.6f} s"
            f", max {max(lateness):.6f} s over {len(lateness)} arrivals")

    checks = judge(reqs, reference, config, db)
    checks["warmup_wrong_records"] = judge(
        warm_rows, reference, config, db)["wrong_records"]
    failed = sum(1 for r in reqs.rows if "record" not in r)
    result = {"correct": passed(checks), "attempted": len(reqs.rows),
              "failed": failed}
    if trace:
        trace_mod = load_file(BENCH / "trace.py")
        run = trace_mod.reduce_dir(tracer.dir, chips)
        shutil.rmtree(tracer.dir, ignore_errors=True)
        run.window_s = tracer.window[1] - tracer.window[0]
        if run.kept_s is not None:
            run.window_s = min(run.window_s, run.kept_s)
        trace_window = (tracer.window[0], tracer.window[0] + run.window_s)
        buckets = trace_mod.serve_buckets(run, dispatched)
        answered = sum(1 for r in reqs.rows if "record" in r
                       and trace_window[0] < r["t_done"] <= trace_window[1])
        log(f"[trace] window {run.window_s:.6f} s (events dropped after "
            f"{run.kept_s} s), busy {run.busy_s:.6f} s, answered "
            f"{answered} ({answered / run.window_s:.6f} queries/s), serve "
            f"steps {len(run.serve)} of {len(dispatched)} dispatched, "
            f"device s per step by bucket {_step_times(run, buckets)}")
        ctx = {"config": config, "requests": reqs.rows,
               "window": (t0, t_end), "trace_window": trace_window,
               "sched": _stats_delta(stats0, stats1), "trace": run,
               "serve_buckets": buckets, "work": load_file(BENCH / "work.py"),
               "peaks": peaks_mod.peaks(device["kind"])}
        metrics = {}
        for m in bundle["per_layer"]:
            v = load_module("metrics", m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = dict(device, memory_peak_bytes=peak,
                                busy_s=run.busy_s, window_s=run.window_s)
        result["breakdown"] = trace_mod.breakdown(run)
    else:
        metrics, info = end_to_end(bundle["end_to_end"], reqs, t0, t_end,
                                   peak, setup_s, config)
        log(f"[window] {info}")
        result["metrics"] = metrics
        result["device"] = dict(device, memory_peak_bytes=peak)
    result["checks"] = checks
    return result


def _sched_stats(system) -> dict:
    s = system.scheduler.stats
    return {"answered": s.answered, "padded": s.padded}


def _stats_delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in a}


def _step_times(run, buckets) -> dict:
    """Mean device seconds of a traced serve step, by bucket."""
    if buckets is None:
        return {}
    by = {}
    for (_, dur, _), b in zip(run.serve, buckets):
        by.setdefault(b, []).append(dur / 1e9)
    return {b: (len(v), sum(v) / len(v)) for b, v in sorted(by.items())}


def prepare(bundle: dict) -> dict:
    """Set the process up for the system under test on the chip; return
    the device record (``Refused`` where there is no fitting chip)."""
    if not (ROOT / "src" / "repro").is_dir():
        raise Refused("the system under test (src/repro) is not in this "
                      "checkout")
    os.environ["REPRO_PLAN_CACHE"] = "off"          # before repro.engine
    os.environ["LIBTPU_INIT_ARGS"] = " ".join(
        (os.environ.get("LIBTPU_INIT_ARGS", ""), LIBTPU_FLAG)).strip()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path.insert(0, str(ROOT / "src"))
    device = require_chip(int(bundle["cell"]["chips"]))
    import jax
    # every program, however quick to compile, comes from the cache after
    # the cell's first run, so set-up is the same work in every later run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    return device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bundle = load_cell(args.workload)
    device = prepare(bundle)
    result = execute(bundle, args.seed, args.seconds, bool(args.trace),
                     device=device)
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
