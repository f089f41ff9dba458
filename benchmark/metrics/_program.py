"""Readers of the program's own counters and span timers, which ride
each whole check's ``profile()`` (``stateright_tpu/obs``). A program
that lacks the key reports nothing, and the reader returns None."""

from __future__ import annotations

from _lib import whole


def _having(record, key):
    return [c for c in whole(record) if key in c.get("profile", {})]


def share_of_wall(record, key):
    """Share (%) of the whole checks' wall time in ``profile()[key]``
    seconds, over the checks that report the key."""
    got = _having(record, key)
    wall = sum(c["wall_s"] for c in got)
    if not wall:
        return None
    return 100.0 * sum(c["profile"][key] for c in got) / wall


def mean(record, key):
    """Mean of ``profile()[key]`` over the whole checks that report it."""
    got = _having(record, key)
    if not got:
        return None
    return sum(c["profile"][key] for c in got) / len(got)
