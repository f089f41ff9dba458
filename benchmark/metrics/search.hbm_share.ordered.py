"""Share (%) of the HBM bandwidth bound that the chunk program reaches
in the ordered-network cell: the least bytes of the traced whole checks
over what the fullest device could move in its busy time
(``_roofline.py``)."""

from _roofline import hbm_share, peak_hbm_bytes_per_s


def read(record):
    return hbm_share(record, peak_hbm_bytes_per_s())
