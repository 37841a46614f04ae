"""Faults planted under the timed path, to show that ``correct`` catches
them, and the control. Each is ``plant(system)`` on a built
``MultiServerPIR``, before set-up warms it. The benchmark's own runs never
plant anything: ``bench/control.py`` and the tests do.

one_server     the control: the second server's share is replaced by
               zeros, what a shortcut that skipped its scan (halving the
               device work) would return; its step still runs, so the
               timing is unchanged. It breaks the configuration's
               guarantee that the record comes back exact from two
               servers' shares.
answer_bit     one answer altered where it is produced: the lowest bit of
               the first word of the first share of every batch flips.
half_batch     half of the batch left out: both servers return zero
               shares for the later half of every batch's slots.
"""
from __future__ import annotations


def _wrap_answers(system, parties, change):
    """Route each listed party's answer shares through ``change``."""
    for p in parties:
        bucketed = system.servers[p].bucketed
        answer = bucketed.answer

        def changed(db, keys, _answer=answer):
            return change(_answer(db, keys))

        bucketed.answer = changed


def one_server(system):
    _wrap_answers(system, [1], lambda a: a * 0)


def answer_bit(system):
    _wrap_answers(system, [0], lambda a: a.at[0, 0].set(a[0, 0] ^ 1))


def half_batch(system):
    _wrap_answers(system, range(len(system.servers)),
                  lambda a: a.at[a.shape[0] // 2:].set(0))


PLANTS = {"one_server": one_server, "answer_bit": answer_bit,
          "half_batch": half_batch}
