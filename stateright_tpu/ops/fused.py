"""Fused expand→fingerprint→dedup pipeline (Pallas) with bit-identical
staged fallbacks.

The staged device loops (`checker/device_loop.py`, `parallel/sharded.py`)
run expansion, whitening/fingerprinting, in-batch pre-dedup and the
visited-table probe as separate XLA op groups with the full ``F*A``-wide
intermediates materialized in HBM between stages. BENCH_r04 put a number
on the cost: 2pc7 generates 2.74M rows for 296k unique — ~9.3× duplicate
expansion re-hashed, re-compacted and re-probed every chunk. This module
builds the fused pipeline as TWO Pallas kernels:

**The step kernel** (:func:`build_fused_block_fn`, grid over frontier
blocks) per block:

  * expands successors via the model's vmapped ``packed_step`` (and
    evaluates ``packed_properties`` + clears eventually-bits, exactly
    like ``ops.expand.expand_frontier``);
  * computes the (hi, lo) uint32 fingerprint pair in-register with the
    SAME whitening construction as ``ops.hash_kernel.fp64_device`` — the
    kernel body literally evaluates that function's jaxpr, so host/device
    fingerprint agreement is preserved by construction;
  * drops in-batch duplicate lanes with the SAME scatter-min claim arena
    as ``ops.expand.pre_dedup``;
  * (``props=True``) evaluates the model's safety-property predicates
    and selects discovery witnesses IN-REGISTER (the traced jaxpr of
    ``ops.expand.discovery_candidates``), accumulating sticky
    per-property (hit, witness fp) registers across the sequential grid
    — only the tiny per-property discovery vector leaves the kernel, not
    the ``F×P`` property-bit matrix;
  * (``cc > 0``) probes a small device-resident **cross-chunk recent-key
    ring** (a power-of-two array of fingerprint slots, direct-mapped by
    the dedup-key hash) BEFORE the main table: a hit kills the lane
    in-register. Soundness mirrors ``pre_dedup``'s argument — ring
    entries are only ever written from keys that COMMITTED to the
    visited set, so a hit is always a genuine duplicate, and a false
    miss only costs a table probe (or an exchange hop), never drops a
    fresh key. This is the tier that attacks the ~9× ``gen/uniq``
    re-expansion the in-batch dedup cannot touch (the same key
    re-generated chunks apart);
  * (single-chip, ``probe=True``) probes/claims the 4-slot buckets of
    the visited table with the SAME probe loop as
    ``ops.hashtable.table_insert`` — the table halves (and the cc ring)
    ride the kernel as whole-array refs carried across the sequential
    grid, so a later frontier block observes an earlier block's claims
    exactly like the staged path's batch insert. Duplicate lanes die
    INSIDE the kernel; only fresh-key lanes reach the queue append.

**The owner-side probe kernel** (:func:`build_probe_block_fn`): the
sharded engine fuses the step kernel up to the all-to-all exchange
boundary (children must route to their owner shard BEFORE the table
probe, so its step kernel runs ``probe=False``); the post-exchange
probe/insert — the 4-slot bucket probe + claim + fresh-mask that used to
run as a separate staged program — is now a SECOND Pallas kernel
evaluating ``table_insert``'s own jaxpr, so a sharded chunk iteration is
two kernel dispatches around one collective instead of kernel + staged
op soup. Same probe invariant, same benign which-duplicate-wins race the
staged path (and the reference's DashMap, `bfs.rs:198,206,268`)
tolerates.

Bit-identical by construction: neither kernel reimplements any stage —
they trace the shared staged ops (``packed_step``, ``fp64_device``,
``pre_dedup``, ``discovery_candidates``, ``table_insert``) into jaxprs
and evaluate those jaxprs inside the kernel bodies (array constants the
trace captures — fingerprint column keys, model lookup tables — become
explicit kernel inputs; Pallas forbids captured array constants).

**Selection contract** (`tpu_options(fused=False | 'auto' | True)`):
neither kernel lowers for TPU v5e yet (ROADMAP A1: the step kernel hits
an unimplemented ``reduce`` in the Pallas TPU lowering, the probe
kernel a scatter shape mismatch), so the default is ``False``, the
staged path. ``'auto'`` attempts the build via :func:`verify_build` /
:func:`verify_probe_build` (memoized per model-config/backend) and, on
a failure, raises :class:`FusedUnavailable` naming the cause class
(``checker.resilience.classify_error``) — it never goes on, silently,
on the staged path. ``True`` forces the fused build (interpret mode off
TPU — how the CPU tier-1 parity suite pins the kernels without
hardware). Combinations outside the support matrix (:func:`supports`:
sound-eventually node keys, host-property history dedup, the per-row
``hint`` compaction) stay staged under ``'auto'`` — announced by a
one-time ``fused_unsupported`` trace event naming the reason — and
raise under ``True``.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..checker.device_loop import LruCache, model_cache_key
from .expand import (discovery_candidates, eventually_indices,
                     expand_frontier, pre_dedup)
from .hashtable import _BUCKET, _PHI, table_insert

#: frontier rows per grid block: the largest of these dividing the step's
#: frontier width (engine fmax values are 256-aligned; odd user fmax
#: degrades to one block)
_BLOCK_ROWS = (256, 128, 64, 32, 16, 8, 4, 2, 1)

#: default cross-chunk dedup ring slots (``tpu_options(cc_dedup=True)``):
#: 32k 64-bit fingerprints = 256 KB of HBM, direct-mapped
CC_DEFAULT = 1 << 15

_BUILD_CACHE = LruCache(limit=32)
_VERIFY_CACHE = LruCache(limit=64)


class FusedUnavailable(RuntimeError):
    """The fused kernel cannot be built/compiled for this config on this
    backend (memoized so later runs skip the re-attempt)."""


class FusedOut(NamedTuple):
    """One fused step over a full ``fmax_b``-row frontier slice."""

    pbits: jax.Array     # bool[F, P]    property bits per frontier row
    ebits: jax.Array     # uint32[F]     eventually-bits after clearing
    terminal: jax.Array  # bool[F]       rows with no valid action
    flat: jax.Array      # uint32[F*A, W] children (action-major)
    chi: jax.Array       # uint32[F*A]   child fp (canonical under sym)
    clo: jax.Array
    ohi: jax.Array       # uint32[F*A]   child ORIGINAL-state fp
    olo: jax.Array
    cvalid: jax.Array    # bool[F*A]     raw-valid child lanes
    dvalid: jax.Array    # bool[F*A]     pre-dedup survivors (cc-ring
    #                                    hits already removed when cc>0)
    inserted: jax.Array  # bool[F*A]     fresh-key lanes (probe=True only;
    #                                    aliases dvalid otherwise)
    key_hi: jax.Array    # updated table halves (probe=True; inputs
    key_lo: jax.Array    #                       passed through otherwise)
    xovf: jax.Array      # bool[]   model capacity overflow
    ovf: jax.Array       # bool[]   table probe overflow (probe=True)
    rounds: jax.Array    # int32[]  probe rounds taken (probe=True)
    cch: jax.Array       # int32[]  cross-chunk ring hits (0 when cc=0)
    disc_hit: Any = None  # bool[P]   per-call sticky discovery registers
    disc_hi: Any = None   # uint32[P] (props=True only; the engine merges
    disc_lo: Any = None   #            them into its carry registers)
    ring_hi: Any = None   # updated cc ring (probe=True & cc>0; the
    ring_lo: Any = None   # sharded engine updates its ring staged-side)


def supports(model, *, sound: bool, host_props: bool,
             hint: int = 0) -> Optional[str]:
    """``None`` when the fused path covers this configuration, else the
    reason it stays staged (the README capability-matrix entries)."""
    if sound:
        return ("sound_eventually dedups on (state, ebits) node keys "
                "and logs cross edges — staged only")
    if host_props:
        return ("host-evaluated properties need the in-loop history "
                "dedup — staged only")
    if hint:
        return ("tpu_options(hint=...) selects the per-row staged "
                "compaction — drop it to fuse")
    return None


def _block_rows(fmax_b: int) -> int:
    return next(d for d in _BLOCK_ROWS if fmax_b % d == 0)


def _cc_slot(chi, clo, cc: int):
    """Direct-mapped ring slot per lane: the same multiplicative-hash
    construction the visited table's bucket selection uses."""
    return ((clo ^ (chi * jnp.uint32(_PHI)))
            & jnp.uint32(cc - 1)).astype(jnp.int32)


def cc_ring_update(rhi, rlo, chi, clo, mask, cc: int):
    """Write ``mask``-ed lanes' fingerprints into their ring slots with
    ONE deterministic winner per contested slot (a scatter-min arena
    resolves the race BEFORE the two half-word scatters — two
    independent ``.at[].set`` winners could interleave a hi half from
    one key with a lo half from another, fabricating a chimera entry
    that matches a future real key; the arena makes that impossible).
    Shared by the in-kernel single-chip update and the sharded engine's
    staged post-commit update."""
    lanes = chi.shape[0]
    lane = jnp.arange(lanes, dtype=jnp.int32)
    slot = _cc_slot(chi, clo, cc)
    wslot = jnp.where(mask, slot, cc)
    arena = jnp.full((cc + 1,), lanes, jnp.int32).at[wslot].min(
        lane, mode="drop")
    win = mask & (arena[slot] == lane)
    widx = jnp.where(win, slot, cc)
    rhi = rhi.at[widx].set(chi, mode="drop")
    rlo = rlo.at[widx].set(clo, mode="drop")
    return rhi, rlo


def _staged_block(model, symmetry: bool, probe: bool, eventually_idx,
                  properties, props: bool, cc: int):
    """The staged pipeline over ONE frontier block, as a pure function —
    this is what gets traced into the kernel body, so the fused kernel is
    the staged math by construction. Argument/return arity is fixed per
    (props, probe, cc) configuration; the kernel builder mirrors it."""

    def block(*args):
        it = iter(args)
        rows, ebits, fvalid = next(it), next(it), next(it)
        if props:
            phi, plo = next(it), next(it)
            pfp = (phi, plo)
        else:
            # frontier fingerprints come from the engine's queue cache,
            # not a re-hash — zeros keep the traced jaxpr free of the
            # dead frontier-hash graph when nothing reads phi/plo
            pfp = (jnp.zeros_like(ebits), jnp.zeros_like(ebits))
        key_hi, key_lo = next(it), next(it)
        if cc:
            rhi, rlo = next(it), next(it)
        exp = expand_frontier(model, rows, fvalid, ebits, eventually_idx,
                              symmetry=symmetry, pfp=pfp)
        dvalid = pre_dedup(exp.chi, exp.clo, exp.cvalid)
        if cc:
            # cross-chunk dedup tier: an exact ring match is a key the
            # engine already committed to the visited set — kill the
            # lane before it costs a table probe (or an exchange hop).
            # The empty marker (0, 0) is never a real fingerprint
            # (hash-kernel invariant), so a zeroed slot can't false-hit.
            slot = _cc_slot(exp.chi, exp.clo, cc)
            cchit = dvalid & (rhi[slot] == exp.chi) \
                & (rlo[slot] == exp.clo)
            cch = cchit.sum(dtype=jnp.int32)
            dvalid = dvalid & ~cchit
        else:
            cch = jnp.int32(0)
        if probe:
            inserted, key_hi, key_lo, ovf, rounds = table_insert(
                key_hi, key_lo, exp.chi, exp.clo, dvalid,
                with_rounds=True)
            if cc:
                # ring entries must stay a subset of the committed
                # visited set: only lanes that claimed a table slot are
                # cached (the single-chip fused step has no abort path,
                # so insert == commit here)
                rhi, rlo = cc_ring_update(rhi, rlo, exp.chi, exp.clo,
                                          inserted, cc)
        else:
            inserted = dvalid
            ovf = jnp.bool_(False)
            rounds = jnp.int32(0)
        out = [exp.pbits, exp.ebits, exp.terminal, exp.flat, exp.chi,
               exp.clo, exp.ohi, exp.olo, exp.cvalid, dvalid, inserted,
               key_hi, key_lo]
        if props:
            d_hit, d_hi, d_lo = discovery_candidates(
                properties, exp, fvalid, whi=pfp[0], wlo=pfp[1])
            out += [d_hit, d_hi, d_lo]
        if cc and probe:
            out += [rhi, rlo]
        out += [exp.xovf, ovf, rounds, cch]
        return tuple(out)

    return block


def build_fused_block_fn(model, fmax_b: int, capacity: int, *,
                         symmetry: bool = False, probe: bool = True,
                         interpret: bool = True, props: bool = False,
                         cc: int = 0):
    """Build (memoized) the fused step callable for fixed shapes.

    Returns ``fn(frontier, ebits, fvalid, key_hi=None, key_lo=None,
    pfp=None, ring=None) -> FusedOut`` (``key_hi``/``key_lo`` are the
    2-D bucket-major table halves, required with ``probe=True``;
    ``pfp`` the cached frontier-fingerprint pair, required with
    ``props=True``; ``ring`` the cc-ring halves, required with
    ``cc > 0``). The callable is traceable — the engines embed it inside
    their chunk ``while_loop``.
    """
    mkey = model_cache_key(model)
    key = None
    if mkey is not None:
        key = (mkey, fmax_b, capacity, symmetry, probe, interpret,
               props, cc)
        cached = _BUILD_CACHE.get(key)
        if cached is not None:
            return cached
    fn = _build_fused_block_fn(model, fmax_b, capacity, symmetry, probe,
                               interpret, props, cc)
    if key is not None:
        _BUILD_CACHE[key] = fn
    return fn


def _build_fused_block_fn(model, fmax_b: int, capacity: int,
                          symmetry: bool, probe: bool, interpret: bool,
                          props: bool, cc: int):
    from jax.experimental import pallas as pl

    if cc:
        assert cc & (cc - 1) == 0 and cc >= 4, \
            "cc ring capacity must be a power of two >= 4"
    width = model.packed_width
    n_actions = model.max_actions
    properties = model.properties()
    prop_count = len(properties)
    props = props and prop_count > 0
    eventually_idx = eventually_indices(properties)
    fb = _block_rows(fmax_b)
    nblk = fmax_b // fb
    fab = fb * n_actions
    n_buckets = capacity // _BUCKET if probe else 1

    staged = _staged_block(model, symmetry, probe, eventually_idx,
                           properties, props, cc)

    # trace the staged block once at BLOCK shapes; captured array
    # constants (fp column keys, model tables) become explicit inputs —
    # Pallas kernels may not close over array constants
    sds = jax.ShapeDtypeStruct
    targs = [sds((fb, width), jnp.uint32), sds((fb,), jnp.uint32),
             sds((fb,), jnp.bool_)]
    if props:
        targs += [sds((fb,), jnp.uint32), sds((fb,), jnp.uint32)]
    targs += [sds((n_buckets, _BUCKET), jnp.uint32),
              sds((n_buckets, _BUCKET), jnp.uint32)]
    if cc:
        targs += [sds((cc,), jnp.uint32), sds((cc,), jnp.uint32)]
    closed = jax.make_jaxpr(staged)(*targs)
    consts = [jnp.asarray(c) for c in closed.consts]
    const_in = [c.reshape(1) if c.ndim == 0 else c for c in consts]
    nc = len(consts)
    # input-ref arity before the consts: frontier, ebits, fvalid,
    # [phi, plo], khi, klo, [rhi, rlo]
    nin = 5 + (2 if props else 0) + (2 if cc else 0)
    ring_carried = bool(cc and probe)

    def kernel(*refs):
        it = iter(refs[:nin])
        frontier_ref, ebits_ref, fvalid_ref = (next(it), next(it),
                                               next(it))
        if props:
            phi_ref, plo_ref = next(it), next(it)
        khi_in, klo_in = next(it), next(it)
        if cc:
            rhi_in, rlo_in = next(it), next(it)
        const_refs = refs[nin:nin + nc]
        oit = iter(refs[nin + nc:])
        (pb_ref, eb_ref, term_ref, flat_ref, chi_ref, clo_ref, ohi_ref,
         olo_ref, cv_ref, dv_ref, ins_ref, khi_ref, klo_ref) = (
            next(oit), next(oit), next(oit), next(oit), next(oit),
            next(oit), next(oit), next(oit), next(oit), next(oit),
            next(oit), next(oit), next(oit))
        if props:
            dh_ref, dhi_ref, dlo_ref = next(oit), next(oit), next(oit)
        if ring_carried:
            rhi_ref, rlo_ref = next(oit), next(oit)
        flags_ref = next(oit)
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            # the table (and the cc ring, and the sticky discovery
            # registers) ride the kernel: copied from the input halves
            # once, then carried across the sequential grid so block
            # k+1 probes against block k's claims (the staged batch
            # insert's intra-batch visibility, by construction)
            khi_ref[...] = khi_in[...]
            klo_ref[...] = klo_in[...]
            if ring_carried:
                rhi_ref[...] = rhi_in[...]
                rlo_ref[...] = rlo_in[...]
            if props:
                dh_ref[...] = jnp.zeros((prop_count,), jnp.bool_)
                dhi_ref[...] = jnp.zeros((prop_count,), jnp.uint32)
                dlo_ref[...] = jnp.zeros((prop_count,), jnp.uint32)
            flags_ref[...] = jnp.zeros((4,), jnp.int32)

        cs = [r[...].reshape(c.shape) for r, c in zip(const_refs, consts)]
        args = [frontier_ref[...], ebits_ref[...], fvalid_ref[...]]
        if props:
            args += [phi_ref[...], plo_ref[...]]
        args += [khi_ref[...], klo_ref[...]]
        if cc:
            # probe=True reads the CARRIED ring (earlier blocks' claims
            # visible); probe=False reads the immutable input ring
            if ring_carried:
                args += [rhi_ref[...], rlo_ref[...]]
            else:
                args += [rhi_in[...], rlo_in[...]]
        res = list(jax.core.eval_jaxpr(closed.jaxpr, cs, *args))
        rit = iter(res)
        (pbits, ebits2, terminal, flat, chi, clo, ohi, olo, cvalid,
         dvalid, inserted, khi, klo) = (
            next(rit), next(rit), next(rit), next(rit), next(rit),
            next(rit), next(rit), next(rit), next(rit), next(rit),
            next(rit), next(rit), next(rit))
        if props:
            d_hit, d_hi, d_lo = next(rit), next(rit), next(rit)
        if ring_carried:
            rhi2, rlo2 = next(rit), next(rit)
        xovf, ovf, rounds, cch = (next(rit), next(rit), next(rit),
                                  next(rit))
        pb_ref[...] = pbits[:, :prop_count] if prop_count \
            else jnp.zeros((fb, 1), jnp.bool_)
        eb_ref[...] = ebits2
        term_ref[...] = terminal
        flat_ref[...] = flat
        chi_ref[...] = chi
        clo_ref[...] = clo
        ohi_ref[...] = ohi
        olo_ref[...] = olo
        cv_ref[...] = cvalid
        dv_ref[...] = dvalid
        ins_ref[...] = inserted
        khi_ref[...] = khi
        klo_ref[...] = klo
        if props:
            # sticky-first merge across the sequential grid: the FIRST
            # block with a hit keeps its witness, exactly like the
            # staged path's whole-frontier argmax (blocks are frontier
            # order, and discovery_candidates picks the first row)
            dh = dh_ref[...]
            keep = dh | ~d_hit
            dhi_ref[...] = jnp.where(keep, dhi_ref[...], d_hi)
            dlo_ref[...] = jnp.where(keep, dlo_ref[...], d_lo)
            dh_ref[...] = dh | d_hit
        if ring_carried:
            rhi_ref[...] = rhi2
            rlo_ref[...] = rlo2
        flags = flags_ref[...]
        flags_ref[...] = jnp.stack([
            flags[0] | xovf.astype(jnp.int32),
            flags[1] | ovf.astype(jnp.int32),
            flags[2] + rounds,
            flags[3] + cch])

    def full(shape):
        return pl.BlockSpec(shape, lambda i: tuple(0 for _ in shape))

    tshape = (n_buckets, _BUCKET)
    pcols = max(prop_count, 1)
    row_spec = pl.BlockSpec((fb,), lambda i: (i,))
    in_specs = [pl.BlockSpec((fb, width), lambda i: (i, 0)),
                row_spec, row_spec]
    if props:
        in_specs += [row_spec, row_spec]
    in_specs += [full(tshape), full(tshape)]
    if cc:
        in_specs += [full((cc,)), full((cc,))]
    in_specs += [full(c.shape) for c in const_in]
    lane_spec = pl.BlockSpec((fab,), lambda i: (i,))
    out_specs = [pl.BlockSpec((fb, pcols), lambda i: (i, 0)),
                 row_spec, row_spec,
                 pl.BlockSpec((fab, width), lambda i: (i, 0)),
                 lane_spec, lane_spec, lane_spec, lane_spec, lane_spec,
                 lane_spec, lane_spec,
                 full(tshape), full(tshape)]
    fa_full = fmax_b * n_actions
    out_shape = [sds((fmax_b, pcols), jnp.bool_),
                 sds((fmax_b,), jnp.uint32),
                 sds((fmax_b,), jnp.bool_),
                 sds((fa_full, width), jnp.uint32),
                 sds((fa_full,), jnp.uint32),
                 sds((fa_full,), jnp.uint32),
                 sds((fa_full,), jnp.uint32),
                 sds((fa_full,), jnp.uint32),
                 sds((fa_full,), jnp.bool_),
                 sds((fa_full,), jnp.bool_),
                 sds((fa_full,), jnp.bool_),
                 sds(tshape, jnp.uint32),
                 sds(tshape, jnp.uint32)]
    if props:
        out_specs += [full((prop_count,))] * 3
        out_shape += [sds((prop_count,), jnp.bool_),
                      sds((prop_count,), jnp.uint32),
                      sds((prop_count,), jnp.uint32)]
    if ring_carried:
        out_specs += [full((cc,)), full((cc,))]
        out_shape += [sds((cc,), jnp.uint32), sds((cc,), jnp.uint32)]
    out_specs += [full((4,))]
    out_shape += [sds((4,), jnp.int32)]
    call = pl.pallas_call(
        kernel,
        grid=(nblk,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )

    dummy = jnp.zeros(tshape, jnp.uint32)

    def fn(frontier, ebits, fvalid, key_hi=None, key_lo=None,
           pfp=None, ring=None) -> FusedOut:
        khi = key_hi if probe else dummy
        klo = key_lo if probe else dummy
        ins = [frontier, ebits.astype(jnp.uint32), fvalid]
        if props:
            ins += [pfp[0], pfp[1]]
        ins += [khi, klo]
        if cc:
            ins += [ring[0], ring[1]]
        res = list(call(*ins, *const_in))
        rit = iter(res)
        (pbits, ebits2, terminal, flat, chi, clo, ohi, olo, cvalid,
         dvalid, inserted, khi, klo) = (
            next(rit), next(rit), next(rit), next(rit), next(rit),
            next(rit), next(rit), next(rit), next(rit), next(rit),
            next(rit), next(rit), next(rit))
        d_hit = d_hi = d_lo = None
        if props:
            d_hit, d_hi, d_lo = next(rit), next(rit), next(rit)
        rhi2 = rlo2 = None
        if ring_carried:
            rhi2, rlo2 = next(rit), next(rit)
        elif cc:
            rhi2, rlo2 = ring  # probe=False: read-only, passed through
        flags = next(rit)
        if not probe:
            khi, klo = key_hi, key_lo
        return FusedOut(
            pbits=pbits, ebits=ebits2, terminal=terminal, flat=flat,
            chi=chi, clo=clo, ohi=ohi, olo=olo, cvalid=cvalid,
            dvalid=dvalid, inserted=inserted, key_hi=khi, key_lo=klo,
            xovf=flags[0] > 0, ovf=flags[1] > 0, rounds=flags[2],
            cch=flags[3], disc_hit=d_hit, disc_hi=d_hi, disc_lo=d_lo,
            ring_hi=rhi2, ring_lo=rlo2)

    return fn


def build_probe_block_fn(nlanes: int, capacity: int, *,
                         interpret: bool = True):
    """The owner-side probe kernel: post-exchange bucket probe/insert as
    ONE Pallas kernel evaluating ``table_insert``'s jaxpr (4-slot bucket
    probe + empty-slot claim race + fresh mask), so the sharded fused
    path's probe stage is a kernel dispatch, not a staged program.
    Model-independent — memoized on ``(nlanes, capacity, backend mode)``
    only.

    Returns ``fn(fhi, flo, valid, key_hi, key_lo) -> (inserted, key_hi,
    key_lo, ovf, rounds)`` with the 2-D bucket-major table layout the
    chunk carries use.
    """
    key = ("probe", nlanes, capacity, interpret)
    cached = _BUILD_CACHE.get(key)
    if cached is not None:
        return cached
    from jax.experimental import pallas as pl

    n_buckets = capacity // _BUCKET
    sds = jax.ShapeDtypeStruct

    def staged(fhi, flo, valid, khi, klo):
        return table_insert(khi, klo, fhi, flo, valid, with_rounds=True)

    closed = jax.make_jaxpr(staged)(
        sds((nlanes,), jnp.uint32), sds((nlanes,), jnp.uint32),
        sds((nlanes,), jnp.bool_),
        sds((n_buckets, _BUCKET), jnp.uint32),
        sds((n_buckets, _BUCKET), jnp.uint32))
    consts = [jnp.asarray(c) for c in closed.consts]
    const_in = [c.reshape(1) if c.ndim == 0 else c for c in consts]
    nc = len(consts)

    def kernel(*refs):
        fhi_ref, flo_ref, val_ref, khi_in, klo_in = refs[:5]
        const_refs = refs[5:5 + nc]
        ins_ref, khi_ref, klo_ref, flags_ref = refs[5 + nc:]
        cs = [r[...].reshape(c.shape)
              for r, c in zip(const_refs, consts)]
        ins, khi, klo, ovf, rounds = jax.core.eval_jaxpr(
            closed.jaxpr, cs, fhi_ref[...], flo_ref[...], val_ref[...],
            khi_in[...], klo_in[...])
        ins_ref[...] = ins
        khi_ref[...] = khi
        klo_ref[...] = klo
        flags_ref[...] = jnp.stack([ovf.astype(jnp.int32), rounds])

    tshape = (n_buckets, _BUCKET)
    call = pl.pallas_call(
        kernel,
        out_shape=[sds((nlanes,), jnp.bool_),
                   sds(tshape, jnp.uint32),
                   sds(tshape, jnp.uint32),
                   sds((2,), jnp.int32)],
        interpret=interpret,
    )

    def fn(fhi, flo, valid, key_hi, key_lo):
        ins, khi, klo, flags = call(fhi, flo, valid, key_hi, key_lo,
                                    *const_in)
        return ins, khi, klo, flags[0] > 0, flags[1]

    _BUILD_CACHE[key] = fn
    return fn


def verify_build(model, fmax: int, capacity: int, *, symmetry: bool,
                 probe: bool, interpret: bool, props: bool = False,
                 cc: int = 0) -> None:
    """The ``'auto'`` attempt: build the fused step at the run's real
    shapes and force an end-to-end lower+compile of a standalone wrapper.
    Raises on ANY failure (the caller classifies and re-raises).
    Success AND failure are memoized per (model config, shapes, backend)
    so repeated runs neither re-pay the probe compile nor re-attempt a
    known-bad build.
    """
    backend = jax.default_backend()
    props = props and len(model.properties()) > 0
    if not probe:
        capacity = 0  # table untouched: normalize so the build memo hits
    mkey = model_cache_key(model)
    key = (mkey, fmax, capacity if probe else 0, symmetry, probe,
           interpret, props, cc, backend) if mkey is not None else None
    if key is not None:
        cached = _VERIFY_CACHE.get(key)
        if cached is True:
            return
        if cached is not None:
            raise FusedUnavailable(cached)
    try:
        fn = build_fused_block_fn(model, fmax, capacity,
                                  symmetry=symmetry, probe=probe,
                                  interpret=interpret, props=props,
                                  cc=cc)
        width = model.packed_width
        n_buckets = capacity // _BUCKET if probe else 1
        sds = jax.ShapeDtypeStruct
        tshape = sds((n_buckets, _BUCKET), jnp.uint32)
        args = [sds((fmax, width), jnp.uint32),
                sds((fmax,), jnp.uint32), sds((fmax,), jnp.bool_),
                tshape, tshape]
        if props:
            args += [sds((fmax,), jnp.uint32), sds((fmax,), jnp.uint32)]
        if cc:
            args += [sds((cc,), jnp.uint32), sds((cc,), jnp.uint32)]

        def wrapper(*xs):
            kw = {"key_hi": xs[3], "key_lo": xs[4]}
            k = 5
            if props:
                kw["pfp"] = (xs[k], xs[k + 1])
                k += 2
            if cc:
                kw["ring"] = (xs[k], xs[k + 1])
            return fn(xs[0], xs[1], xs[2], **kw)

        jax.jit(wrapper).lower(*args).compile()
    except Exception as exc:
        if key is not None:
            _VERIFY_CACHE[key] = (f"fused kernel build failed on "
                                  f"{backend}: {type(exc).__name__}: "
                                  f"{exc}")
        raise
    if key is not None:
        _VERIFY_CACHE[key] = True


def verify_probe_build(nlanes: int, capacity: int, *,
                       interpret: bool) -> None:
    """``'auto'`` attempt for the owner-side probe kernel, memoized like
    :func:`verify_build` (model-independent key)."""
    backend = jax.default_backend()
    key = ("probe", nlanes, capacity, interpret, backend)
    cached = _VERIFY_CACHE.get(key)
    if cached is True:
        return
    if cached is not None:
        raise FusedUnavailable(cached)
    try:
        fn = build_probe_block_fn(nlanes, capacity, interpret=interpret)
        sds = jax.ShapeDtypeStruct
        n_buckets = capacity // _BUCKET
        tshape = sds((n_buckets, _BUCKET), jnp.uint32)
        jax.jit(fn).lower(
            sds((nlanes,), jnp.uint32), sds((nlanes,), jnp.uint32),
            sds((nlanes,), jnp.bool_), tshape, tshape).compile()
    except Exception as exc:
        _VERIFY_CACHE[key] = (f"owner-side probe kernel build failed on "
                              f"{backend}: {type(exc).__name__}: {exc}")
        raise
    _VERIFY_CACHE[key] = True
