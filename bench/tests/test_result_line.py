"""The last line of a run, from runs of the harness on the CPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import run
from helpers import execute, small_bundle

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.mark.parametrize("traffic", [
    {"kind": "closed", "clients": 8},
    {"kind": "open", "rate_per_s": 4.0},
], ids=["closed", "open"])
def test_untraced_line_carries_the_cells_end_to_end_metrics(traffic,
                                                            monkeypatch):
    bundle = small_bundle(traffic=traffic)
    kind = "closed" if traffic["kind"] == "closed" else "open"
    bundle["end_to_end"] = [
        m for m in bundle["end_to_end"]
        if m["name"] in ("setup_s", "hbm_peak_per_db_byte")
        or (kind == "closed") == (m["name"] == "queries_per_s")]
    res = execute(bundle, monkeypatch=monkeypatch)
    assert list(res) == KEYS
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {m["name"] for m in bundle["end_to_end"]}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["device"]["count"] == 1
    assert list(res["checks"])[0] == "wrong_records"
    json.loads(json.dumps(res))


def test_traced_line_adds_breakdown_and_device_times(monkeypatch):
    res = execute(small_bundle(), trace=True, monkeypatch=monkeypatch)
    assert list(res) == KEYS[:5] + ["breakdown", "checks"]
    assert res["correct"] is True
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    # the CPU has no device trace: only the host's readings are there
    assert set(res["metrics"]) <= {"client_gen_ms", "pad_share"}


def test_same_seed_same_answers(monkeypatch):
    bundle = small_bundle(traffic={"kind": "open", "rate_per_s": 4.0})
    a = run.make_db(bundle["config"], 77)
    b = run.make_db(bundle["config"], 77)
    c = run.make_db(bundle["config"], 78)
    assert (a == b).all() and not (a == c).all()
    assert a.shape == (1 << 10, 8) and a.dtype == "uint32"


def _run_command(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "xor1g-closed",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_exits_nonzero_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("REPRO_FORCE_BACKEND", None)
    p = _run_command(run.ROOT, env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_command_exits_nonzero_with_the_benchmark_alone(tmp_path):
    shutil.copy(run.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(run.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_command(tmp_path, dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "system under test" in p.stderr
