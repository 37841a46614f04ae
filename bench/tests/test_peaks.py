import pytest

import peaks
import run


def test_known_kind_has_the_published_v5e_peaks():
    p = peaks.peaks("TPU v5 lite")
    assert p.hbm_bytes_per_s == 819e9
    assert p.int8_ops_per_s == 393e12
    assert p.bf16_flops_per_s == 197e12


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "", "tpu v5 lite"])
def test_unknown_kind_raises(kind):
    with pytest.raises(KeyError):
        peaks.peaks(kind)


def test_the_command_refuses_a_cpu_device(monkeypatch):
    monkeypatch.delenv("REPRO_FORCE_BACKEND", raising=False)
    with pytest.raises(run.Refused, match="no TPU"):
        run.require_chip(1)


def test_the_command_refuses_a_forced_backend(monkeypatch):
    monkeypatch.setenv("REPRO_FORCE_BACKEND", "tpu")
    with pytest.raises(run.Refused, match="REPRO_FORCE_BACKEND"):
        run.require_chip(1)
