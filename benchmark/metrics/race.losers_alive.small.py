"""``race.losers_alive.search``'s reading, in the small cell, where it
moves ``verdict_s``."""

from _lib import twin

read = twin("race.losers_alive.search")
