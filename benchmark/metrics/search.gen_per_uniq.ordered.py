"""``search.gen_per_uniq``'s reading, in the ordered-network cell."""

from _lib import twin

read = twin("search.gen_per_uniq")
