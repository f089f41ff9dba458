"""Share (%) of the whole checks' time in which Python's cyclic
garbage collector ran, process-wide (``profile()['gc_s']``, from the
checker's construction to the read after the verdict)."""

from _program import share_of_wall


def read(record):
    return share_of_wall(record, "gc_s")
