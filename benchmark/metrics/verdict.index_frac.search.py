"""Share (%) of the whole checks' time building the host mirror from
the pulled search log (the program's ``mirror.index`` span,
``profile()['span_s.mirror.index']``)."""

from _program import share_of_wall


def read(record):
    return share_of_wall(record, "span_s.mirror.index")
