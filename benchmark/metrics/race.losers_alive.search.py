"""Mean count of retired host-racer threads still alive when a whole
check's ``profile()`` is read (``profile()['race_losers_alive']``):
cancelled losers that still share the host."""

from _program import mean


def read(record):
    return mean(record, "race_losers_alive")
