#!/usr/bin/env python3
"""One traced run of a cell, with the device's idle time split by the
program's phases.

    python3 benchmark/phase_run.py --workload <cell> --seed <n> \
        --seconds <s> --trace 1

Runs ``run.py`` unchanged, with its trace reading wrapped: the ``sr.*``
host spans are read from the trace directory before ``run.py`` removes
it, and ``harness/phases.py`` splits the fullest device's idle time by
them. Prints ``run.py``'s result line, then one line ``{"phases":
{...}}``: ``idle_ns``, ``unattributed_ns``, ``busy_ns``, ``window_ns``
and ``idle_gaps``, the idle ns of every phase, largest first.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402
from harness import phases, tracing  # noqa: E402

_collect, _reduce = tracing.collect, tracing.reduce
_box: dict = {}


def _collect_with_spans(trace_dir: str):
    _box["spans"] = phases.collect_spans(trace_dir)
    return _collect(trace_dir)


def _reduce_with_phases(trace):
    red = _reduce(trace)
    _box["phases"] = dict(
        phases.breakdown(trace, red, _box.pop("spans", [])),
        busy_ns=red["devices"][red["fullest"]]["busy_ns"],
        window_ns=red["window_ns"])
    return red


def main(argv=None) -> int:
    tracing.collect, tracing.reduce = _collect_with_spans, _reduce_with_phases
    rc = run.main(argv)
    if "phases" in _box:
        print(json.dumps({"phases": _box["phases"]}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
