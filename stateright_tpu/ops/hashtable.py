"""HBM-resident open-addressed fingerprint set with batched parallel insert.

TPU-native replacement for the reference's concurrent visited set
(``DashMap<Fingerprint, Option<Fingerprint>>`` in
`/root/reference/src/checker/bfs.rs:26`). Keys are 64-bit fingerprints stored
as uint32 (hi, lo) pairs; the empty slot marker is ``(0, 0)``, which the hash
kernel guarantees is never a real fingerprint.

Insertion is a lock-free-style parallel probe over **4-slot buckets**
built from scatter/gather rounds inside one ``lax.while_loop``. Probing a
whole aligned bucket per round matters on TPU: the bucket read is a
contiguous 4-word row gather (cheap) and one round resolves almost every
item at engine load factors (< 55%), where slot-at-a-time probing paid one
serialized gather round per collision. Per round:

  1. gather each item's current 4-slot bucket; a key match anywhere in the
     bucket resolves the item as "already present";
  2. items whose bucket has an empty slot race to claim its first empty by
     scattering a unique token and gathering it back (XLA scatter picks
     one winner per slot — the moral equivalent of a CAS);
  3. claim winners scatter their key (race-free: one winner per slot) and
     resolve as "inserted"; claim losers retry the same bucket next round
     (they will observe the winner's key: a match if it was a same-
     fingerprint duplicate inside the batch, or try the bucket's next
     empty slot otherwise);
  4. items whose bucket is full of foreign keys advance to the next
     bucket. Buckets only ever fill (no deletion), so an item's bucket
     scan deterministically revisits every bucket between its start
     bucket and wherever its fingerprint was first inserted — lookups can
     neither stop early nor miss.

Which duplicate wins a slot within a batch is unspecified — the same benign
race the reference tolerates on ``DashMap`` inserts ("Races other threads,
but that's fine", `bfs.rs:198,206,268`).

Parent fingerprints are not stored on device: the host mirrors (fingerprint
-> parent) incrementally from each level's inserted set, which is also the
checkpointable search record (TLC-style, `bfs.rs:314-342`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_PHI = 0x9E3779B9  # 2^32 / golden ratio; scrambles hi into the probe start.


def make_table(capacity: int):
    """Allocate an empty table. ``capacity`` must be a power of two
    >= the bucket width (the probe reads whole 4-slot buckets)."""
    assert capacity & (capacity - 1) == 0, "capacity must be a power of two"
    assert capacity >= _BUCKET, f"capacity must be >= {_BUCKET}"
    return (jnp.zeros((capacity,), dtype=jnp.uint32),
            jnp.zeros((capacity,), dtype=jnp.uint32))


_BUCKET = 4  # slots probed per round (one contiguous row gather)
_MIN_NARROW = 256  # floor for the narrow-tail probe width
_NARROW_THRESHOLD = 4096  # below this, a single probe loop wins
_CLAIM_CELLS = 1 << 16  # claim-arena floor: full capacity would memset
#                         MBs per probe round; hashed cells only cost a
#                         false claim-loss (the loser retries next round)


def _next_pow2(n: int) -> int:
    return 1 << max((n - 1).bit_length(), 0)


def table_insert(key_hi, key_lo, fhi, flo, valid, max_rounds: int = 4096,
                 with_rounds: bool = False):
    """Insert a batch of fingerprints.

    Args:
      key_hi, key_lo: uint32[C] table halves (C a power of two, >= 4), OR
        uint32[C/4, 4] bucket-major halves — callers that carry the table
        across iterations (the device chunk loop) keep the 2-D layout
        permanently: reshaping flat->bucketed per call made XLA insert a
        tile-layout conversion COPY of the whole table in each direction
        per iteration (~1.5 ms x4 at 2^22 capacity, profiler-verified).
        The return layout matches the input layout.
      fhi, flo: uint32[N] fingerprints to insert.
      valid: bool[N]; invalid rows are ignored.
      max_rounds: probe-round bound; hitting it reports overflow.
      with_rounds: also return the int32 probe-round count this insert
        took (free — the loop carries already count rounds; feeds the
        ``probe_rounds`` obs metric).

    Returns:
      (inserted bool[N], key_hi, key_lo, overflowed bool[]) — ``inserted``
      marks rows that claimed a fresh slot (first occurrence of a fingerprint
      across the table's lifetime *and* within this batch). With
      ``with_rounds``, a trailing int32 rounds scalar rides along.
    """
    two_d = key_hi.ndim == 2
    if two_d:
        assert key_hi.shape[1] == _BUCKET, \
            f"2-D table must be (C/{_BUCKET}, {_BUCKET}) bucket-major"
    capacity = key_hi.shape[0] * (key_hi.shape[1] if two_d else 1)
    assert capacity >= _BUCKET, \
        f"table capacity must be >= {_BUCKET} (got {capacity})"
    n_buckets = capacity // _BUCKET
    n = fhi.shape[0]
    gmask = n_buckets - 1
    offs = jnp.arange(_BUCKET, dtype=jnp.uint32)
    group0 = ((flo ^ (fhi * jnp.uint32(_PHI)))
              & jnp.uint32(gmask)).astype(jnp.int32)

    def round_(unresolved, inserted, group, khi2, klo2, fhi, flo, token,
               claim_cells):
        """One probe round at whatever lane width the inputs carry.
        khi2/klo2 stay (n_buckets, 4) throughout: reshaping the flat
        table per round was a full-table relayout each round
        (profiler-measured ~0.9 ms x2 per round at engine sizes)."""
        cmask = jnp.uint32(claim_cells - 1)
        bucket_hi = khi2[group]  # (lanes, 4)
        bucket_lo = klo2[group]
        is_empty = (bucket_hi == 0) & (bucket_lo == 0)
        is_match = (bucket_hi == fhi[:, None]) & (bucket_lo == flo[:, None])
        unresolved = unresolved & ~is_match.any(axis=1)

        has_empty = is_empty.any(axis=1)
        first_empty = jnp.where(is_empty, offs[None, :],
                                jnp.uint32(_BUCKET)).min(axis=1)
        slot = group.astype(jnp.uint32) * jnp.uint32(_BUCKET) + first_empty
        attempt = unresolved & has_empty
        # claim race in a small hashed arena: XLA's scatter picks one
        # winner per cell (the CAS analog). Two lanes CLAIMING different
        # slots can hash to the same cell — the loser just retries next
        # round, exactly like losing a genuine same-slot race; winning a
        # cell always writes the lane's own slot, so no false *win*
        # exists. Sized to the batch (>= 4x the lanes), never the full
        # capacity, whose per-round memset dominated small inserts.
        claim_idx = jnp.where(attempt, slot & cmask,
                              jnp.uint32(claim_cells))
        claim = jnp.zeros((claim_cells,), dtype=jnp.uint32)
        claim = claim.at[claim_idx].set(token, mode="drop")
        won = attempt & (claim[(slot & cmask).astype(jnp.int32)] == token)

        # race-free 2-D write: one winner per slot
        wg = jnp.where(won, group, n_buckets)
        wl = first_empty.astype(jnp.int32)
        khi2 = khi2.at[wg, wl].set(fhi, mode="drop")
        klo2 = klo2.at[wg, wl].set(flo, mode="drop")
        inserted = inserted | won
        unresolved = unresolved & ~won

        # A full-of-foreign bucket sends the item to the next bucket;
        # claim losers retry the same bucket (next round they see the
        # winner's key, or take the bucket's next empty slot).
        advance = unresolved & ~has_empty
        group = jnp.where(advance, (group + 1) & gmask, group)
        return unresolved, inserted, group, khi2, klo2

    if two_d:
        khi2, klo2 = key_hi, key_lo
    else:
        khi2 = key_hi.reshape(n_buckets, _BUCKET)
        klo2 = key_lo.reshape(n_buckets, _BUCKET)

    def out_shape(khi2, klo2):
        if two_d:
            return khi2, klo2
        return khi2.reshape(capacity), klo2.reshape(capacity)

    claim_full = min(capacity, max(_CLAIM_CELLS, _next_pow2(4 * n)))
    token = jnp.arange(1, n + 1, dtype=jnp.uint32)

    if n <= _NARROW_THRESHOLD:
        # small batches: one plain probe loop — the three-phase narrow-
        # tail structure below saves lane-width on big batches but its
        # extra while_loops dominate tiny inserts
        def cond0(c):
            unres, _ins, _g, _khi2, _klo2, rounds = c
            return unres.any() & (rounds < max_rounds)

        def body0(c):
            unres, ins, g, khi2, klo2, rounds = c
            unres, ins, g, khi2, klo2 = round_(
                unres, ins, g, khi2, klo2, fhi, flo, token, claim_full)
            return unres, ins, g, khi2, klo2, rounds + 1

        unres, inserted, _g, khi2, klo2, rounds0 = lax.while_loop(
            cond0, body0, (valid, jnp.zeros((n,), dtype=bool), group0,
                           khi2, klo2, jnp.int32(0)))
        out = (inserted,) + out_shape(khi2, klo2) + (unres.any(),)
        return out + (rounds0,) if with_rounds else out

    # --- round 1 at full width -----------------------------------------
    inserted = jnp.zeros((n,), dtype=bool)
    unresolved, inserted, group, khi2, klo2 = round_(
        valid, inserted, group0, khi2, klo2, fhi, flo, token, claim_full)

    # --- narrow tail ----------------------------------------------------
    # After one round, duplicates have matched and most fresh keys have
    # claimed a slot; the unresolved remainder (claim losers and multi-
    # fresh-keys-per-bucket tails) is a small fraction, but the
    # while_loop's every round used to run at FULL lane width. Compact
    # the stragglers to n/8 lanes and finish narrow; a full-width
    # fallback loop covers the rare over-n/8 case.
    n2 = min(n, max(_MIN_NARROW, _next_pow2((n + 7) // 8)))
    ucount = unresolved.sum(dtype=jnp.int32)
    narrow_ok = ucount <= n2
    pos = jnp.cumsum(unresolved.astype(jnp.int32)) - 1
    sidx = jnp.where(unresolved & (pos < n2), pos, n2)
    src = jnp.zeros((n2 + 1,), jnp.int32).at[sidx].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop")[:n2]
    u2 = (jnp.arange(n2, dtype=jnp.int32) < ucount) & narrow_ok
    fhi2 = fhi[src]
    flo2 = flo[src]
    group2 = group[src]
    token2 = token[src]
    claim_narrow = min(capacity, max(_CLAIM_CELLS, _next_pow2(4 * n2)))

    def cond2(c):
        unres2, _ins2, _g2, _khi2, _klo2, rounds = c
        return unres2.any() & (rounds < max_rounds)

    def body2(c):
        unres2, ins2, g2, khi2, klo2, rounds = c
        unres2, ins2, g2, khi2, klo2 = round_(
            unres2, ins2, g2, khi2, klo2, fhi2, flo2, token2,
            claim_narrow)
        return unres2, ins2, g2, khi2, klo2, rounds + 1

    ins2 = jnp.zeros((n2,), dtype=bool)
    unres2, ins2, _g2, khi2, klo2, rounds2 = lax.while_loop(
        cond2, body2, (u2, ins2, group2, khi2, klo2, jnp.int32(1)))
    inserted = inserted.at[jnp.where(ins2, src, n)].set(
        True, mode="drop")

    # --- full-width fallback (ucount > n2; runs zero rounds otherwise) --
    def cond3(c):
        unres, _ins, _g, _khi2, _klo2, rounds = c
        return unres.any() & (rounds < max_rounds)

    def body3(c):
        unres, ins, g, khi2, klo2, rounds = c
        unres, ins, g, khi2, klo2 = round_(
            unres, ins, g, khi2, klo2, fhi, flo, token, claim_full)
        return unres, ins, g, khi2, klo2, rounds + 1

    unres3, inserted, _g, khi2, klo2, rounds3 = lax.while_loop(
        cond3, body3,
        (unresolved & ~narrow_ok, inserted, group, khi2, klo2,
         jnp.int32(1)))
    overflowed = (unres2 & (rounds2 >= max_rounds)).any() | unres3.any()
    out = (inserted,) + out_shape(khi2, klo2) + (overflowed,)
    if with_rounds:
        # rounds executed: the width-1 round + the narrow loop (counter
        # seeded at 1) + the rare full-width fallback (likewise)
        out = out + (1 + (rounds2 - 1) + (rounds3 - 1),)
    return out


def table_evict_prefix(key_hi, key_lo, evict_pref):
    """Evict every key whose fingerprint falls in a marked prefix range
    and compact each 4-slot bucket (survivors move to the bucket front,
    order preserved) — the device half of the HBM -> host visited-set
    tiering (``checker/resilience.py`` ``SpillPolicy``): the host tier
    (``HostShadow``) already holds every key, so eviction is one
    in-place pass over the table, no host round trip.

    Args:
      key_hi, key_lo: the table halves, flat uint32[C] or bucket-major
        uint32[C/4, 4] (the chunk carries' layout); returned unchanged
        in layout.
      evict_pref: bool[256] — ``evict_pref[p]`` marks the prefix bucket
        of the fingerprint's top 8 bits (``resilience.fp_prefix``) for
        eviction.

    Returns:
      (key_hi, key_lo, evicted_count int32[]).

    Caveat (by design): compaction can open an empty slot in a bucket a
    SURVIVING key once probed past while full, so a later insert of that
    key may claim the earlier slot and report "fresh" again. That is
    the same maybe-fresh outcome as rediscovering an evicted key, and
    the same filter covers both: with tiering active the engines
    re-probe every device-fresh key against the host tier before it
    enters the mirror or the unique counts.
    """
    two_d = key_hi.ndim == 2
    if two_d:
        khi2, klo2 = key_hi, key_lo
    else:
        khi2 = key_hi.reshape(-1, _BUCKET)
        klo2 = key_lo.reshape(-1, _BUCKET)
    nonempty = (khi2 != 0) | (klo2 != 0)
    # top 8 bits of the 64-bit fingerprint = top 8 bits of the hi half
    pref = (khi2 >> jnp.uint32(24)).astype(jnp.int32)
    drop = nonempty & evict_pref[pref]
    keep = nonempty & ~drop
    # stable per-bucket compaction: argsort(False-first) moves kept
    # slots to the front without reordering them — the first-empty-slot
    # insert invariant needs every bucket's occupancy to be a prefix
    order = jnp.argsort(~keep, axis=1, stable=True)
    khi2 = jnp.take_along_axis(jnp.where(keep, khi2, jnp.uint32(0)),
                               order, axis=1)
    klo2 = jnp.take_along_axis(jnp.where(keep, klo2, jnp.uint32(0)),
                               order, axis=1)
    count = drop.sum(dtype=jnp.int32)
    if not two_d:
        return (khi2.reshape(key_hi.shape), klo2.reshape(key_lo.shape),
                count)
    return khi2, klo2, count


def plan_insert_host(fps, capacity: int):
    """Host-side placement plan for seeding an EMPTY table.

    Returns an int64 slot index per fingerprint (-1 for duplicates),
    placing each at the first free slot of the first non-full bucket
    along its probe sequence — exactly the invariant `table_insert`'s
    probe relies on, so later device lookups find every seeded key. Used
    because a standalone `table_insert` dispatch (a data-dependent
    while_loop program) is a whole extra program launch even for a
    16-lane batch, while a plain scatter rides the seed program; seeding
    has the whole-table-empty precondition that makes host planning trivial.
    Raises on a full table (the in-graph path reports overflow instead).
    """
    import numpy as np

    assert capacity & (capacity - 1) == 0 and capacity >= _BUCKET
    n_buckets = capacity // _BUCKET
    buckets: dict = {}
    idx = np.empty((len(fps),), np.int64)
    for k, fp in enumerate(fps):
        fp = int(fp)
        hi, lo = (fp >> 32) & 0xFFFFFFFF, fp & 0xFFFFFFFF
        g = (lo ^ ((hi * _PHI) & 0xFFFFFFFF)) & (n_buckets - 1)
        steps = 0
        while True:
            bucket = buckets.setdefault(g, [])
            if fp in bucket:
                idx[k] = -1  # duplicate fingerprint: nothing to place
                break
            if len(bucket) < _BUCKET:
                idx[k] = g * _BUCKET + len(bucket)
                bucket.append(fp)
                break
            g = (g + 1) & (n_buckets - 1)
            steps += 1
            if steps > n_buckets:
                raise RuntimeError(
                    f"hash table (capacity {capacity}) full while "
                    "planning the seed insert; raise "
                    "checker_builder.tpu_options(capacity=...)")
    return idx
