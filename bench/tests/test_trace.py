"""The reduction from trace to metrics: on synthetic planes with known
answers, and on a small trace recorded on a TPU v5e."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

import run

trace = run.load_file(run.BENCH / "trace.py")
FIXTURE = Path(__file__).parent / "data" / "small.xplane.pb"


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v)
                                for k, v in lines.items()])


MS = 1_000_000


def synthetic():
    modules = [ev("jit_serve(1)", 0, 10 * MS), ev("jit_serve(2)", 10 * MS,
                                                  10 * MS),
               ev("jit_concatenate(3)", 25 * MS, 1 * MS),
               ev("jit_serve(1)", 40 * MS, 10 * MS)]
    host = [ev("bench.gen", 26 * MS, 14 * MS), ev("other", 0, 50 * MS),
            ev("bench.finalize", 20 * MS, 1 * MS)]
    return [plane("/device:TPU:0", XLA_Modules=modules),
            plane("/device:TPU:1", XLA_Modules=[ev("jit_serve(9)", 0,
                                                   30 * MS)]),
            plane("/host:CPU", python=host)]


def test_busy_is_the_union_of_programs_on_the_chips_used():
    one = trace.reduce_planes(synthetic(), chips=1)
    assert one.busy_s == pytest.approx(0.031)
    two = trace.reduce_planes(synthetic(), chips=2)
    assert two.busy_s == pytest.approx((0.031 + 0.030) / 2)


def test_serve_steps_are_found_by_name():
    run_ = trace.reduce_planes(synthetic())
    assert [name for _, _, name in run_.serve] == [
        "jit_serve(1)", "jit_serve(2)", "jit_serve(1)"]
    assert run_.serve_s == pytest.approx(0.030)
    assert run_.modules["jit_serve"] == [3, pytest.approx(0.030)]
    assert run_.modules["jit_concatenate"] == [1, pytest.approx(0.001)]
    assert not run_.dropped


def test_idle_gaps_are_put_down_to_host_spans():
    run_ = trace.reduce_planes(synthetic())
    assert run_.gaps == [(20 * MS, 25 * MS), (26 * MS, 40 * MS)]
    b = trace.breakdown(run_)
    assert b["idle_gaps"] == [["gen", pytest.approx(0.014)],
                              ["finalize", pytest.approx(0.005)]]
    assert b["device_ops"] == [["jit_serve", pytest.approx(0.030)],
                               ["jit_concatenate", pytest.approx(0.001)]]


def test_union_of_overlapping_intervals():
    assert trace.union_ns([(0, 10), (5, 15), (20, 30), (21, 22)]) == 25
    assert trace.union_ns([]) == 0


def test_the_window_ends_where_the_profiler_dropped_events():
    planes = synthetic()
    planes[0].lines.append(NS(name="XLA TraceMe", events=[
        ev("Trace Buffers Dropped", 30 * MS, 20 * MS)]))
    run_ = trace.reduce_planes(planes)
    assert run_.dropped and run_.kept_s == pytest.approx(0.030)
    assert run_.busy_s == pytest.approx(0.021)     # the last step is lost
    assert len(run_.serve) == 2
    assert run_.modules["jit_serve"] == [2, pytest.approx(0.020)]


@pytest.mark.parametrize("dispatched, want", [
    ([4, 2, 4], [4, 2, 4]),
    ([4, 2, 4, 1, 1], [4, 2, 4]),     # the last two had not run yet
    ([4, 2], None),                   # more programs than dispatches
    ([4, 2, 1], None),                # one program at two buckets
])
def test_each_serve_step_gets_the_bucket_it_was_dispatched_at(dispatched,
                                                              want):
    run_ = trace.reduce_planes(synthetic())
    assert trace.serve_buckets(run_, dispatched) == want


def test_recorded_chip_trace():
    """Three queries submitted at once to ``pir-1g`` cut to 2^10 rows, on
    one TPU v5e, harness spans on. Trimmed to keep it small: the HLO
    metadata plane, host events other than the harness's spans, event
    stats, and op names past 64 characters are gone."""
    from jax.profiler import ProfileData
    run_ = trace.reduce_planes(ProfileData.from_file(str(FIXTURE)).planes)
    # each query's Gen outlasts the batching window: three one-query
    # batches, each answered by both parties
    assert len(run_.serve) == 6
    assert 0 < run_.serve_s <= run_.busy_s
    # each party's one-query step is a program of its own, run three times
    assert trace.serve_buckets(run_, [1] * 6) == [1] * 6
    assert trace.serve_buckets(run_, [1, 1, 2, 2, 1, 1]) is None
    assert {s[0] for s in run_.spans} == {"gen", "collate", "stage",
                                          "dispatch", "finalize"}
    b = trace.breakdown(run_)
    assert b["device_ops"] and b["idle_gaps"]
    assert all(v > 0 for _, v in b["device_ops"] + b["idle_gaps"])
