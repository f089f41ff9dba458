"""Share (%) of the expansion's action lanes that yield a successor:
generated states over rows expanded times lanes per row
(``profile()['expanded']`` and ``['action_lanes']``), over the whole
checks. The ordered step has a lane per channel head, whether or not
the channel holds a message that moves the system."""

from _lib import whole


def read(record):
    got = [c for c in whole(record)
           if {"expanded", "action_lanes"} <= set(c.get("profile", {}))]
    lanes = sum(c["profile"]["expanded"] * c["profile"]["action_lanes"]
                for c in got)
    if not lanes:
        return None
    return 100.0 * sum(c["generated"] for c in got) / lanes
