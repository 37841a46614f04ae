import itertools

import numpy as np
import pytest

import load

OPEN = {"kind": "open", "rate_per_s": 2.0}
N = 1 << 25


def test_same_seed_same_schedule_and_indices():
    a = load.open_schedule(OPEN, 2**31 + 7, 51, N)
    b = load.open_schedule(OPEN, 2**31 + 7, 51, N)
    assert a == b
    assert len(a) > 50


def test_seeds_reorder_the_same_gaps():
    g1, g2 = load.arrival_gaps(2.0, 200, 1), load.arrival_gaps(2.0, 200, 2)
    assert not np.array_equal(g1, g2)
    assert np.array_equal(np.sort(g1), np.sort(g2))
    assert np.mean(g1) == pytest.approx(0.5, rel=0.05)
    due = [t for t, _ in load.open_schedule(OPEN, 1, 100, N)]
    assert np.diff(due) == pytest.approx(g1_of(1, 100)[1:len(due)])


def g1_of(seed, seconds):
    return load.arrival_gaps(OPEN["rate_per_s"],
                             int(OPEN["rate_per_s"] * seconds) + 1, seed)


def test_schedule_stays_inside_the_window_and_the_db():
    sched = load.open_schedule(OPEN, 5, 30, 1000)
    assert sched[0][0] == 0.0
    assert all(0 <= t < 30 for t, _ in sched)
    assert all(0 <= i < 1000 for _, i in sched)
    assert [t for t, _ in sched] == sorted(t for t, _ in sched)


def test_closed_clients_draw_fixed_streams():
    a = list(itertools.islice(load.client_indices(9, 3, N), 20))
    b = list(itertools.islice(load.client_indices(9, 3, N), 20))
    c = list(itertools.islice(load.client_indices(9, 4, N), 20))
    assert a == b and a != c


def test_negative_and_large_seeds_work():
    for seed in (-1, 0, 2**31 + 12345, 2**63):
        assert load.open_schedule(OPEN, seed, 5, N)


@pytest.mark.parametrize("spec", [
    {"kind": "bursty"},
    {"kind": "closed", "clients": 0},
    {"kind": "open", "rate_per_s": 0},
    {"kind": "open"},
    {"kind": "open", "rate_per_s": 1, "index": "zipf"},
])
def test_bad_mixes_are_refused(spec):
    with pytest.raises((ValueError, KeyError)):
        load.validate(spec)
