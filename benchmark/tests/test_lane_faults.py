"""A run whose ordered step is broken in ``packed_step_lane`` comes out
not correct, and the run took the lane path (rows built after lane
compaction: the row-free validity pass went into the chunk program), so
the fault was on the timed path.

``test_faults.py`` plants ``state_unchanged`` by replacing
``packed_step``, which turns the lane path off for the ordered cell:
this case covers the path that cell times. ``none`` is the sound run on
the same path, which must come out correct."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ENV = dict(os.environ, JAX_PLATFORMS="cpu")
CELL = "abd3-ordered"
SHARE = "expand.materialized_share.ordered"


def _run(cell, fault):
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "lane_fault_run.py"), cell,
         fault],
        capture_output=True, text=True, env=ENV, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", ["none", "state_unchanged"])
def test_lane_fault_is_caught_on_the_lane_path(fault):
    res = _run(CELL, fault)
    print(CELL, fault, res["compared"], res["metrics"].get(SHARE))
    assert res["lane_valid_traced"] > 0
    if fault == "none":  # a dense step reads 100 % or more
        assert res["metrics"][SHARE]["value"] < 100.0, res["metrics"]
    assert res["correct"] is (fault == "none"), res["compared"]
