"""The control and each fault the cells can have, planted under the timed
path, must come out as not correct; the program as it is, as correct."""
import pytest

import faults
from helpers import execute, small_bundle


@pytest.mark.parametrize("config", ["pir-1g", "pir-1g-add"])
def test_sound_runs_are_correct(config, monkeypatch):
    res = execute(small_bundle(config), monkeypatch=monkeypatch)
    assert res["correct"] is True
    assert res["checks"]["wrong_records"]["value"] == 0


@pytest.mark.parametrize("plant", sorted(faults.PLANTS))
@pytest.mark.parametrize("traffic", [
    {"kind": "closed", "clients": 8},
    {"kind": "open", "rate_per_s": 4.0},
], ids=["closed", "open"])
def test_planted_fault_is_not_correct(plant, traffic, monkeypatch):
    res = execute(small_bundle(traffic=traffic), plant=faults.PLANTS[plant],
                  monkeypatch=monkeypatch)
    assert res["correct"] is False
    assert res["checks"]["wrong_records"]["value"] > 0


def test_control_fails_the_additive_cell_too(monkeypatch):
    res = execute(small_bundle("pir-1g-add"), plant=faults.one_server,
                  monkeypatch=monkeypatch)
    assert res["correct"] is False
