"""The work models against hand arithmetic at pir-1g shapes (2^25 x 32 B)."""
import pytest

import work

N, L = 1 << 25, 32


def test_db_bytes_is_one_gib():
    assert work.db_bytes(N, L) == 1073741824


@pytest.mark.parametrize("kind,bucket,ops", [
    ("xor", 4, 0),
    ("additive", 1, 2 * 1 * N * L),            # 2147483648
    ("additive", 4, 8589934592),
])
def test_int8_ops(kind, bucket, ops):
    assert work.int8_ops(kind, bucket, N, L) == ops


@pytest.mark.parametrize("kind,bucket,blocks", [
    ("xor", 1, 33554431),                      # the tree's internal nodes
    ("xor", 4, 134217724),
    ("additive", 1, 33554431 + 33554432),      # plus one per leaf
    ("additive", 4, 268435452),
])
def test_chacha_blocks(kind, bucket, blocks):
    assert work.chacha_blocks(kind, bucket, N) == blocks


@pytest.mark.parametrize("kind", ["xor", "additive"])
def test_least_time_is_the_hbm_stream(kind):
    t, bound = work.least_seconds(kind, 4, N, L, 819e9, 393e12)
    assert bound == "hbm"
    assert t == pytest.approx(1073741824 / 819e9)     # 1.311 ms
    assert t == pytest.approx(1.3110e-3, rel=1e-4)


def test_least_time_turns_to_ops_when_they_dominate():
    t, bound = work.least_seconds("additive", 1 << 16, N, L, 819e9, 393e12)
    assert bound == "int8"
    assert t == pytest.approx(2 * (1 << 16) * N * L / 393e12)
