"""The readers of the ordered-network cell's counters: the lane yield
and the chunk program's share of the HBM bandwidth bound, each on a
synthetic record worked by hand, silent where a key is missing, and
read from a small window of whole checks of the cell on any backend."""

from __future__ import annotations

import copy
import os
import sys

import pytest

from harness import cells, window
from tests.small import small_cell

sys.path.insert(0, os.path.join(cells.BENCH_DIR, "metrics"))
import _roofline  # noqa: E402

DEV = "/device:TPU:0"


def synthetic():
    """Two whole checks (one traced), and a cancelled traced one that
    has no profile."""
    prof = {"expanded": 564, "action_lanes": 10, "row_words": 100}
    return {
        "window_s": 1.0,
        "checks": [
            {"whole": True, "traced": True, "unique": 564,
             "generated": 813, "profile": dict(prof)},
            {"whole": True, "traced": False, "unique": 400,
             "generated": 1000, "profile": dict(prof, expanded=500)},
            {"whole": False, "traced": True, "unique": 40,
             "generated": 50},
        ],
        "trace": {"devices": {DEV: {"busy_ns": 1_000_000}},
                  "fullest": DEV, "window_ns": 2_000_000}}


def test_lane_yield_by_hand():
    # (813 + 1000) generated over (564 + 500) rows x 10 lanes
    value = cells.reader("expand.lane_yield.ordered")(synthetic())
    assert value == pytest.approx(100.0 * 1813 / 10640)


def test_hbm_share_by_hand():
    # the traced whole check: (564 + 813) rows x 100 words x 4 bytes,
    # over 1 ms busy at 1e9 bytes/s
    rec = synthetic()
    assert _roofline.least_bytes(rec) == 4 * (564 + 813) * 100
    assert _roofline.hbm_share(rec, 1e9) == pytest.approx(55.08)


@pytest.mark.parametrize("key", ["expanded", "action_lanes", "row_words"])
def test_silent_without_the_key(key):
    rec = synthetic()
    for c in rec["checks"]:
        c.get("profile", {}).pop(key, None)
    if key != "row_words":
        assert cells.reader("expand.lane_yield.ordered")(rec) is None
    if key != "action_lanes":
        assert _roofline.hbm_share(rec, 1e9) is None


def test_hbm_share_silent_without_a_trace_or_peak():
    rec = synthetic()
    assert _roofline.hbm_share(rec, None) is None
    rec["trace"] = None
    assert _roofline.hbm_share(rec, 1e9) is None
    # off a chip the device kind is in no table of peaks.json
    assert cells.reader("search.hbm_share.ordered")(synthetic()) is None


@pytest.fixture(scope="module")
def record():
    cell = small_cell("abd3-ordered")

    def make():
        return cells.make_builder(cell.config)

    window.warm_up(cell.traffic, make)
    res = window.closed_loop(cell.traffic, make, 2.0, True,
                             lambda rec: None)
    for c in res["checks"]:
        c["traced"] = True
    return {"checks": res["checks"], "window_s": res["window_s"],
            "trace": {"devices": {DEV: {"busy_ns": 10**9}},
                      "fullest": DEV, "window_ns": 2 * 10**9}}


def test_readers_read_the_program(record):
    lane_yield = cells.reader("expand.lane_yield.ordered")(record)
    assert 0.0 < lane_yield <= 100.0
    assert _roofline.least_bytes(record) > 0
    bare = copy.deepcopy(record)
    for c in bare["checks"]:
        c["profile"] = {}
    assert cells.reader("expand.lane_yield.ordered")(bare) is None
    assert _roofline.least_bytes(bare) is None


# the host chunk loop's readers that the cell shares with the other
# capped search cells; the racer is off at the test size, so
# race.losers_alive.search has nothing to read here
SHARED = ["chunk_loop.bubble.search", "verdict_read_frac.search",
          "verdict.pull_frac.search", "verdict.index_frac.search",
          "host.gc_frac.search"]


def test_shared_readers_read_the_cell(record):
    listed = {m["name"] for m in cells.load_cell("abd3-ordered",
                                                 True).metrics}
    for name in SHARED + ["race.losers_alive.search"]:
        assert name in listed, name
    for name in SHARED:
        value = cells.reader(name)(record)
        assert value is not None and 0.0 <= value <= 100.0, (name, value)
