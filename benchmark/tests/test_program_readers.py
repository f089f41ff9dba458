"""The readers of the program's counters and span timers: each reads a
small window of whole checks on any backend, and returns None for a
program whose ``profile()`` lacks the key."""

from __future__ import annotations

import pytest

from harness import cells, window
from tests.small import small_cell

READERS = ("verdict.pull_frac.search", "verdict.index_frac.search",
           "entry.first_dispatch_s.small", "race.losers_alive.search",
           "race.losers_alive.small", "host.gc_frac.search",
           "host.gc_frac.small")


@pytest.fixture(scope="module")
def record():
    cell = small_cell("2pc-full")

    def make():  # raced, the racer retired at once: the device wins
        return cells.make_builder(cell.config).tpu_options(
            race=True, race_budget=0.0)

    window.warm_up(cell.traffic, make)
    res = window.closed_loop(cell.traffic, make, 2.0, True,
                             lambda rec: None)
    return {"checks": res["checks"], "window_s": res["window_s"]}


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_the_program(record, name):
    value = cells.reader(name)(record)
    assert value is not None and value >= 0.0
    if name.startswith(("verdict.", "host.")):
        assert value <= 100.0


@pytest.mark.parametrize("name", READERS)
def test_reader_is_silent_without_the_key(record, name):
    bare = {"checks": [dict(c, profile={}) for c in record["checks"]],
            "window_s": record["window_s"]}
    assert cells.reader(name)(bare) is None
