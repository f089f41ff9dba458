"""``search.device_ns_per_gen``'s reading, in the ordered-network cell:
device time per generated state over 437-word rows."""

from _lib import twin

read = twin("search.device_ns_per_gen")
