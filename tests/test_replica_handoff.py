"""``LocalReplica``'s lock handoff: a caller that waits for the
replica's lock while the serve loop is busy gets it before the next
tick starts, every time, not once in several ticks by chance."""

import threading
import time

import numpy as np
import pytest

from paddle_tpu.serving_router import LocalReplica


class _BusyArena:
    """The part of ``BatchedDecoder`` that ``LocalReplica`` touches:
    always busy, a tick is a few milliseconds of held lock."""

    paged = False
    arena_lost = False
    slots = 2

    def __init__(self, tick_s):
        self.queue, self._pf_order, self.done = [], [], {}
        self.active = np.ones((self.slots,), bool)
        self.tick_s, self.ticks = tick_s, 0

    def _tick(self):
        time.sleep(self.tick_s)
        self.ticks += 1


@pytest.fixture
def busy_replica():
    rep = LocalReplica(_BusyArena(0.004), name="busy").start()
    yield rep
    rep.stop()


def test_a_waiting_caller_gets_in_before_the_next_tick(busy_replica):
    # counted in ticks, not in time: a caller that arrives during tick
    # k holds the lock when tick k ends (1); one that arrives as the
    # loop is between its check and its acquire waits out one more (2),
    # which is rare. Without the handoff the loop retakes the lock it
    # has just dropped before a woken caller can: with this stub every
    # caller waits 2 ticks and up to 6 among 8 threads (on the chip,
    # where a tick ends in host work, 7 to 10 in the mean)
    arena = busy_replica.decoder
    waited = []

    def caller():
        for _ in range(20):
            before = arena.ticks
            with busy_replica._locked("other"):
                waited.append(arena.ticks - before)
            time.sleep(0.001)

    threads = [threading.Thread(target=caller) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert len(waited) == 160
    assert max(waited) <= 2, waited
    assert sum(waited) / len(waited) <= 1.2, waited


def test_callers_from_many_threads_all_get_in_and_the_loop_goes_on(
        busy_replica):
    arena = busy_replica.decoder
    got = []

    def caller():
        for _ in range(10):
            got.append(busy_replica.load()["slots"])

    threads = [threading.Thread(target=caller) for _ in range(8)]
    t0, ticks0 = time.monotonic(), arena.ticks
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert len(got) == 80
    # 80 acquisitions, up to 8 between two ticks: the callers neither
    # wait long nor starve the loop
    assert time.monotonic() - t0 < 5.0
    time.sleep(0.05)
    assert arena.ticks > ticks0
    assert busy_replica._callers == 0 and busy_replica._no_callers.is_set()


def test_a_nested_hold_counts_and_uncounts(busy_replica):
    with busy_replica._locked("other"):
        with busy_replica._locked("drain"):
            pass
    assert busy_replica._callers == 0 and busy_replica._no_callers.is_set()
