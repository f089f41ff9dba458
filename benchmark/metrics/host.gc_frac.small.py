"""``host.gc_frac.search``'s reading, in the small cell, where it moves
``verdict_s``."""

from _lib import twin

read = twin("host.gc_frac.search")
