"""The reader of ``expand.materialized_share.ordered``: the successor
rows the device steps built, over rows expanded times lanes per row. On
a synthetic record worked by hand, silent where a key is missing (a
program without the counter), and read from a small window of whole
checks of the ordered cell on any backend."""

from __future__ import annotations

import copy
import os

import pytest

from harness import cells, window
from tests.small import small_cell

NAME = "expand.materialized_share.ordered"


def synthetic():
    """Two whole checks and a cancelled one that has no profile."""
    prof = {"expanded": 564, "action_lanes": 10, "materialized": 1280}
    return {
        "window_s": 1.0,
        "checks": [
            {"whole": True, "unique": 564, "generated": 813,
             "profile": dict(prof)},
            {"whole": True, "unique": 400, "generated": 1000,
             "profile": dict(prof, expanded=500, materialized=2560)},
            {"whole": False, "unique": 40, "generated": 50},
        ]}


def test_share_by_hand():
    # (1280 + 2560) rows built over (564 + 500) rows x 10 lanes
    assert cells.reader(NAME)(synthetic()) == pytest.approx(
        100.0 * 3840 / 10640)


@pytest.mark.parametrize("key", ["materialized", "expanded",
                                 "action_lanes"])
def test_silent_without_the_key(key):
    rec = synthetic()
    for c in rec["checks"]:
        c.get("profile", {}).pop(key, None)
    assert cells.reader(NAME)(rec) is None


def test_listed_for_the_ordered_cell_alone():
    entry = {m["name"]: m for m in cells.load_json(os.path.join(
        cells.ROOT, "BENCHMARK.json"))["per_layer"]}[NAME]
    assert entry["workloads"] == ["abd3-ordered"]
    assert entry["moves"] == "uniq_per_s"
    assert NAME in {m["name"] for m in cells.load_cell("abd3-ordered",
                                                       True).metrics}


def test_reads_the_program():
    cell = small_cell("abd3-ordered")

    def make():
        return cells.make_builder(cell.config)

    window.warm_up(cell.traffic, make)
    res = window.closed_loop(cell.traffic, make, 2.0, True,
                             lambda rec: None)
    record = {"checks": res["checks"], "window_s": res["window_s"]}
    value = cells.reader(NAME)(record)
    assert value is not None and value > 0.0
    bare = copy.deepcopy(record)
    for c in bare["checks"]:
        c.get("profile", {}).pop("materialized", None)
    assert cells.reader(NAME)(bare) is None
