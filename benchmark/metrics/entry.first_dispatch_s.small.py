"""Mean seconds from ``spawn_tpu()``'s entry to the end of the check's
first chunk dispatch (``profile()['first_dispatch_s']``): engine
set-up, seeding and the program lookup, all before the device works."""

from _program import mean


def read(record):
    return mean(record, "first_dispatch_s")
