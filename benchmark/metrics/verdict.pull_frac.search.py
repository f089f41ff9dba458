"""Share (%) of the whole checks' time in the verdict read's transfer
of the search log to the host (the program's ``mirror.pull`` span,
``profile()['span_s.mirror.pull']``)."""

from _program import share_of_wall


def read(record):
    return share_of_wall(record, "span_s.mirror.pull")
