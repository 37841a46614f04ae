"""The per-chip kernel metrics of a row-sharded cell, on a synthetic
trace of four chips: each reads one chip's shard of the work, a quarter
of what the whole database's count over the same device time reads."""
import json

import pytest

import peaks
import run

trace = run.load_file(run.BENCH / "trace.py")
work = run.load_file(run.BENCH / "work.py")
CFG = json.loads((run.BENCH / "configs" / "pir-32g-4chip.json").read_text())
N = int(CFG["n_items"])
BUCKETS = [1, 4, 2, 1]


def metric(name):
    return run.load_module("metrics", name).read


def ctx(chips=4, buckets=BUCKETS, traced=True):
    tr = trace.TraceRun(chips=chips, serve=[
        (i * 10**10, (3 + i) * 10**9, f"jit_serve({b})")
        for i, b in enumerate(buckets)])
    return {"config": CFG, "trace": tr if traced else None,
            "serve_buckets": list(buckets) if traced else None,
            "work": work, "peaks": peaks.peaks("TPU v5 lite")}


def test_shard_step_roofline_is_a_quarter_of_the_whole_database():
    got = metric("shard_step_roofline")(ctx())
    whole = metric("serve_step_roofline")(ctx())
    assert got == pytest.approx(whole / 4, rel=1e-12)
    # 8 GiB per party-step at 819 GB/s over 3 + 4 + 5 + 6 s of steps
    assert got == pytest.approx(100 * 4 * (N // 4 * 32 / 819e9) / 18)


def test_shard_chacha_rate_counts_one_chips_shard():
    got = metric("shard_chacha_blocks_per_s")(ctx())
    assert got == pytest.approx(sum(BUCKETS) * (N // 4 - 1) / 18, rel=1e-12)
    whole = metric("chacha_blocks_per_s")(ctx())
    assert got == pytest.approx(whole / 4, rel=1e-8)


@pytest.mark.parametrize("name", ["shard_step_roofline",
                                  "shard_chacha_blocks_per_s"])
def test_one_chip_reads_the_whole_database(name):
    whole = {"shard_step_roofline": "serve_step_roofline",
             "shard_chacha_blocks_per_s": "chacha_blocks_per_s"}[name]
    assert metric(name)(ctx(chips=1)) == pytest.approx(
        metric(whole)(ctx(chips=1)), rel=1e-12)


@pytest.mark.parametrize("name", ["shard_step_roofline",
                                  "shard_chacha_blocks_per_s"])
def test_nothing_to_read_without_a_trace(name):
    assert metric(name)(ctx(traced=False)) is None
    assert metric(name)(ctx(buckets=[])) is None
