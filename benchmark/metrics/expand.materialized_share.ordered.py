"""Share (%) of the expansion's action lanes whose full-width successor
row the device step built: ``profile()['materialized']`` over rows
expanded times lanes per row (``['expanded']`` and
``['action_lanes']``), over the whole checks. A step that builds every
lane's row reads 100 or more (a step's frontier block is not always
full); one that builds rows after keeping the valid lanes reads about
its candidate buffer's share of the lanes."""

from _lib import whole

KEYS = {"materialized", "expanded", "action_lanes"}


def read(record):
    got = [c for c in whole(record) if KEYS <= set(c.get("profile", {}))]
    lanes = sum(c["profile"]["expanded"] * c["profile"]["action_lanes"]
                for c in got)
    if not lanes:
        return None
    return 100.0 * sum(c["profile"]["materialized"] for c in got) / lanes
