"""The chunk program's share of the HBM bandwidth bound.

XLA shows each chunk of the device search as one ``while`` op, so the
share is of the whole program, not of one kernel. Its numerator is the
least bytes the traced whole checks must move: each expanded parent row
read once and each generated successor row written once,
``(expanded + generated) * row_words * 4`` (``profile()['expanded']``
and ``['row_words']``). Its denominator is what the fullest device could
move in its busy time at the peak of ``peaks.json``. The bound on
operations is not counted."""

from __future__ import annotations

from _lib import fullest


def least_bytes(record):
    """Bytes the traced whole checks must move at the least, or None
    where a traced check lacks the program's counters."""
    traced = [c for c in record["checks"] if c.get("traced")
              and c["whole"]]
    if not traced or not all({"expanded", "row_words"}
                             <= set(c.get("profile", {}))
                             for c in traced):
        return None
    return sum(4 * (c["profile"]["expanded"] + c["generated"])
               * c["profile"]["row_words"] for c in traced)


def hbm_share(record, bytes_per_s):
    """``least_bytes`` over the fullest device's busy time at
    ``bytes_per_s``, in %."""
    dev = fullest(record)
    need = least_bytes(record)
    if dev is None or need is None or not dev["busy_ns"] \
            or not bytes_per_s:
        return None
    return 100.0 * need / (dev["busy_ns"] * 1e-9 * bytes_per_s)


def peak_hbm_bytes_per_s():
    """The running device's HBM bandwidth from ``peaks.json``, or None
    for a device the table lacks."""
    import jax
    from harness.device import NoChip, peaks
    try:
        return peaks(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    except NoChip:
        return None
