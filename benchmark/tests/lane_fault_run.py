"""One run of a cell at test size with the lane step of an ordered
actor model broken underneath: ``python lane_fault_run.py <cell>
<fault>``. Prints the run's result line with two additions that show
the lane path ran: ``lane_valid_traced``, the times the row-free
validity pass (called on that path alone) went into a compiled program,
and the metric ``expand.materialized_share.ordered`` (a count: the
window keeps each check's profile, with no trace). Used by
``test_lane_faults.py``.

``fault_run.py`` plants ``state_unchanged`` in ``packed_step``; a model
whose class replaces ``packed_step`` takes the dense step, so that case
never reaches ``packed_step_lane``. Here the fault goes into the lane
itself, and the dense step (a ``vmap`` of it) carries it too."""

from __future__ import annotations

import json
import sys
import time

# fault_run puts the benchmark and the program on the path
import fault_run  # noqa: F401
import run  # noqa: E402
from harness import cells, window  # noqa: E402
from tests.small import small_cell  # noqa: E402

SHARE = "expand.materialized_share.ordered"


def count_lane_path(model_cls) -> list:
    """A one-item list counting the traces of the validity pass."""
    seen = [0]
    valid = model_cls.packed_lane_valid

    def packed_lane_valid(self, words):
        seen[0] += 1
        return valid(self, words)
    model_cls.packed_lane_valid = packed_lane_valid
    return seen


def break_lane(fault: str, model_cls) -> None:
    """Plant one fault in ``model_cls.packed_step_lane``."""
    if fault == "state_unchanged":
        lane = model_cls.packed_step_lane

        def packed_step_lane(self, words, a):
            _row, valid, *rest = lane(self, words, a)
            return (words, valid, *rest)
        model_cls.packed_step_lane = packed_step_lane
    elif fault != "none":
        raise ValueError(fault)


def main() -> None:
    workload, fault = sys.argv[1], sys.argv[2]
    cell = small_cell(workload)
    model_cls = cells.resolve(cell.config["model"])
    seen = count_lane_path(model_cls)
    break_lane(fault, model_cls)
    cell.metrics = list(cell.metrics) + [{"name": SHARE, "unit": "%"}]
    closed_loop = window.closed_loop

    def profiled(traffic, make, seconds, _profile, keep, **kw):
        return closed_loop(traffic, make, seconds, True, keep, **kw)
    window.closed_loop = profiled
    res = run.run(cell, 2**31 + 7, 8.0, False, require_chip=False,
                  t_start=time.perf_counter())
    res["lane_valid_traced"] = seen[0]
    print(json.dumps(res), flush=True)


if __name__ == "__main__":
    main()
