"""A small cell for CPU runs of the harness: ``pir-1g`` at 2^10 rows."""
import json

import run

N_SMALL = 1 << 10
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def small_bundle(config="pir-1g", traffic=None, trace=False):
    """The cell's bundle as ``run.load_cell`` builds it, with the database
    cut to ``N_SMALL`` rows and the metrics of every kind listed."""
    manifest = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((run.BENCH / "configs" / f"{config}.json").read_text())
    cfg["n_items"] = N_SMALL
    return {"cell": {"name": "small", "config": config, "chips": 1},
            "config": cfg,
            "traffic": traffic or {"kind": "closed", "clients": 8},
            "end_to_end": manifest["end_to_end"],
            "per_layer": manifest["per_layer"]}


def execute(bundle, seed=12345678901, seconds=3.0, trace=False, plant=None,
            monkeypatch=None):
    """``run.execute`` on the CPU; the CPU keeps no peak memory, so a
    stand-in number is read where the chip's would be."""
    monkeypatch.setattr(run, "peak_bytes", lambda mesh: 1 << 20)
    return run.execute(bundle, seed, seconds, trace, device=dict(DEVICE),
                       plant=plant)
