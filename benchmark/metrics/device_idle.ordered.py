"""``device_idle.search``'s reading, in the ordered-network cell."""

from _lib import twin

read = twin("device_idle.search")
