"""Shared frontier-expansion core used by every device engine.

One place implements what the reference's ``check_block`` does per state
(`/root/reference/src/checker/bfs.rs:165-274`) — property evaluation,
eventually-bit clearing, action expansion with validity masking, and
fingerprinting — so the single-chip level step (`checker/tpu.py`), the
device-resident loop (`checker/device_loop.py`), and the SPMD sharded step
(`parallel/sharded.py`) compose it with their own dedup/enqueue policies
without drifting apart.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp

from ..core import Expectation
from .hash_kernel import fp64_device


class Expansion(NamedTuple):
    pbits: jax.Array     # bool[F, P]  property bits per frontier row
    ebits: jax.Array     # uint32[F]   eventually-bits after clearing
    flat: jax.Array      # uint32[F*A, W] children (action-major per row)
    avalid: jax.Array    # bool[F, A]  per-(row, action) validity
    cvalid: jax.Array    # bool[F*A]   child validity (enabled & non-no-op)
    chi: jax.Array       # uint32[F*A] child fingerprints (canonical under
    clo: jax.Array       #             symmetry reduction)
    ohi: jax.Array       # uint32[F*A] child ORIGINAL-state fingerprints
    olo: jax.Array       #             (aliases chi/clo without symmetry);
    #                                  recorded so witness paths replay
    #                                  through concrete explored states
    phi: jax.Array       # uint32[F]   frontier fingerprints (canonical)
    plo: jax.Array
    terminal: jax.Array  # bool[F]     rows with no valid action
    xovf: jax.Array      # bool[]      model capacity overflow (fatal: a
    #                                  successor could not be encoded, e.g.
    #                                  net_capacity too small)


def eventually_indices(properties) -> list:
    return [i for i, p in enumerate(properties)
            if p.expectation == Expectation.EVENTUALLY]


def expand_frontier(model, frontier, fvalid, ebits,
                    eventually_idx: Sequence[int],
                    symmetry: bool = False, pfp=None,
                    child_fp: bool = True) -> Expansion:
    """Evaluate properties and expand one frontier batch (pure JAX).

    With ``symmetry``, fingerprints are taken over
    ``model.packed_representative`` of each state — dedup (and the host
    mirror) works in canonical-orbit space while the enqueued rows stay
    original, the engine analog of the DFS engine's canonicalize-then-
    hash-but-enqueue-original rule (`dfs.rs:260-285`). Properties are
    evaluated on the original rows, as in the reference.

    Count caveat: a representative function whose ties are broken by
    original position (e.g. 2pc's sort-by-RM-state, `2pc.rs:165-182`) is
    not orbit-invariant, so the reduced unique count depends on which
    orbit member each engine reaches first — the reference's pinned
    DFS-sym counts are specific to DFS order. Reduction stays sound
    either way (never coarser than the orbit partition); value-complete
    representatives (e.g. increment's full-word sort) give engine-
    independent counts.

    ``pfp`` (optional ``(hi, lo)`` uint32[F] pair) supplies the frontier
    fingerprints from the caller's cache — the device queue stores each
    state's fingerprint from when it was inserted, so re-hashing the
    frontier every iteration (a ~W-column hash graph, the single biggest
    op-count item for wide models) is skipped. Under symmetry the cached
    values are the CANONICAL fingerprints (the queue appends exactly what
    dedup inserted).

    With ``child_fp=False`` the child fingerprints (chi/clo/ohi/olo) are
    skipped (returned as None): callers on the gather-early path compact
    valid lanes to the narrow candidate buffer FIRST and hash there —
    hashing (and canonicalizing, under symmetry) at the full ``F*A`` lane
    width was one of the widest per-iteration op groups."""
    fcount = frontier.shape[0]
    width = model.packed_width
    pbits = jax.vmap(model.packed_properties)(frontier)
    if eventually_idx:
        sat = jnp.zeros((fcount,), dtype=jnp.uint32)
        for i in eventually_idx:
            sat = sat | jnp.where(pbits[:, i], jnp.uint32(1 << i),
                                  jnp.uint32(0))
        ebits = ebits & ~sat
    out = jax.vmap(model.packed_step)(frontier)
    if len(out) == 3:  # models reporting per-action encoding overflow
        succ, avalid, aovf = out
        xovf = (aovf & fvalid[:, None]).any()
    else:
        succ, avalid = out
        xovf = jnp.bool_(False)
    avalid = avalid & fvalid[:, None]
    flat = succ.reshape((-1, width))
    if symmetry:
        phi, plo = pfp if pfp is not None \
            else fp64_device(jax.vmap(model.packed_representative)(frontier))
    else:
        phi, plo = pfp if pfp is not None else fp64_device(frontier)
    if not child_fp:
        chi = clo = ohi = olo = None
    elif symmetry:
        canon = jax.vmap(model.packed_representative)
        chi, clo = fp64_device(canon(flat))
        ohi, olo = fp64_device(flat)
    else:
        chi, clo = fp64_device(flat)
        ohi, olo = chi, clo
    terminal = fvalid & ~avalid.any(axis=1)
    return Expansion(pbits=pbits, ebits=ebits, flat=flat,
                     avalid=avalid, cvalid=avalid.reshape(-1),
                     chi=chi, clo=clo, ohi=ohi, olo=olo,
                     phi=phi, plo=plo, terminal=terminal, xovf=xovf)


def pre_dedup(chi, clo, cvalid):
    """EXACT in-batch duplicate-lane mask: drop candidate lanes whose
    fingerprint already appears at an earlier valid lane of this batch.

    One scatter-min claim arena keyed by fingerprint hash; a losing lane
    is dropped only when the winner's fingerprint VERIFIES equal (one
    2-column row gather), so distinct keys colliding on an arena cell
    are kept — sound by construction. Duplicate-heavy models (2pc: >80%
    duplicate lanes) then spend far fewer probe claim-retry rounds, and
    every retained lane is a distinct key. Runs at whatever lane width
    the caller hands it — the gather-early engines compact raw-valid
    lanes to the ``kmax`` candidate buffer first and dedup there.
    Callers skip this under sound mode, where dedup identity is
    (state, ebits) node keys.
    """
    fa = chi.shape[0]
    acells = 1 << max((2 * fa - 1).bit_length(), 0)
    lane = jnp.arange(fa, dtype=jnp.int32)
    slot = ((clo ^ (chi * jnp.uint32(0x9E3779B9)))
            & jnp.uint32(acells - 1)).astype(jnp.int32)
    slot = jnp.where(cvalid, slot, acells)
    arena = jnp.full((acells,), fa, jnp.int32) \
        .at[slot].min(lane, mode="drop")
    win = jnp.minimum(arena[jnp.minimum(slot, acells - 1)], fa - 1)
    fp2 = jnp.stack([chi, clo], axis=1)
    wfp = fp2[win]
    dup = cvalid & (win != lane) \
        & (wfp[:, 0] == chi) & (wfp[:, 1] == clo)
    return cvalid & ~dup


def assemble_candidates(rows_k, ebits_k, s_chi, s_clo, pw_hi, pw_lo,
                        o_hi, o_lo, width: int, symmetry: bool,
                        sound: bool, nk_hi=None, nk_lo=None):
    """ONE source of truth for the candidate-matrix column layout, built
    from pre-gathered per-lane columns (the gather-early engines). The
    column order makes the queue block and the log block each ONE
    contiguous slice of the compacted matrix:

      [packed row (0..W-1) | child ebits (W) | state fp hi/lo (W+1,W+2)
       | (node key hi/lo at W+3,W+4 under sound)
       | parent key hi/lo | original fp hi/lo (symmetry/sound only)]

    so the queue block is ``[:, :W+3]`` and the log block the contiguous
    slice from the returned ``log_off`` (its first two columns are the
    dedup keys). Under ``sound`` pass the node keys
    (``nk_hi``/``nk_lo``); they are spliced at W+3."""
    cand_cols = [rows_k, ebits_k[:, None],
                 s_chi[:, None], s_clo[:, None],
                 pw_hi[:, None], pw_lo[:, None]]
    if symmetry or sound:
        cand_cols += [o_hi[:, None], o_lo[:, None]]
    cand = jnp.concatenate(cand_cols, axis=1)
    if sound:
        cand = splice_node_keys(cand, width, nk_hi, nk_lo)
    return cand, (width + 3 if sound else width + 1)


def splice_node_keys(k_all, width: int, nk_hi, nk_lo):
    """Insert the node-key columns at W+3 (sound mode) — the splice
    :func:`assemble_candidates`'s ``log_off`` expects: after it, the log
    block's first two columns are these node keys."""
    return jnp.concatenate(
        [k_all[:, :width + 3], nk_hi[:, None], nk_lo[:, None],
         k_all[:, width + 3:]], axis=1)


#: thin-frontier knee: iterations with at most this many pending rows
#: take the small compiled step (shared by both engines so the knob
#: lives in one place)
FMAX_SMALL = 256


def small_step_sizes(fmax: int, kmax: int, n_actions: int):
    """The two-size (thin-frontier) compilation sizes shared by the
    single-chip and sharded loops: ``(fmax_small, kmax_small,
    two_size)``."""
    fmax_small = min(FMAX_SMALL, fmax)
    return (fmax_small, min(fmax_small * n_actions, kmax),
            fmax_small < fmax)


def kmax_default(model, fmax: int, sound: bool) -> int:
    """RAW candidate-buffer (``kraw``) width policy shared by the device
    engines: the buffer holds every RAW-valid child lane of an iteration
    (the gather-early engines compact valid lanes into it BEFORE hashing
    and in-batch dedup), so models that declare ``branching_hint`` (max
    valid children per state) get ``fmax*hint`` with a 1/4 margin;
    hint-less models start at fa/2 (2pc's raw branching measures ~30% of
    fa — an fa/4 start cost it a kovf round, and each extra chunk round
    is one more device-to-host stats pull, a sync). Undersizing costs one kovf
    abort-and-rebuild (compile-cached) sized to the observed branching,
    oversizing makes the hash/dedup stage wider forever."""
    fa = fmax * model.max_actions
    hint = getattr(model, "branching_hint", None)
    if hint:
        scale = 5 * fmax * hint // 4
        return min(fa, max(1 << 12, -(-scale // 256) * 256))
    return min(fa, max(1 << 12, fa // 2))


def kfinal_default(model, fmax: int, sound: bool) -> int:
    """Stage-two (post-dedup) candidate-buffer width: the table probe,
    candidate gather, and appends run at this width. Post-dedup
    branching runs well under the raw hint (paxos vmax ~1.9/state vs
    hint 4; 2pc >80% duplicate lanes), so the halved-hint / fa-8th
    sizing from the round-4 single-stage design applies here. Sound
    mode has no in-batch dedup (node-key identity) — stage two
    degenerates and the raw sizing rules."""
    if sound:
        return kmax_default(model, fmax, sound)
    fa = fmax * model.max_actions
    hint = getattr(model, "branching_hint", None)
    if hint:
        scale = 5 * fmax * hint // 8
        return min(fa, max(1 << 12, -(-scale // 256) * 256))
    return min(fa, max(1 << 12, fa // 8))


def discovery_candidates(properties, exp: Expansion, fvalid,
                         whi=None, wlo=None):
    """Per-property (hit, fp_hi, fp_lo) selection on the frontier batch.

    ALWAYS: a row where the condition is false; SOMETIMES: a row where it
    holds; EVENTUALLY: a terminal row whose bit is still set
    (`bfs.rs:192-226`, `:265-272`). ``whi``/``wlo`` override the witness
    identity per row (default: the frontier fingerprints) — the
    sound-eventually engine passes node keys so witnesses stay resolvable
    in its node-keyed mirror.
    """
    if whi is None:
        whi, wlo = exp.phi, exp.plo
    n_props = len(properties)
    if not n_props:
        z32 = jnp.zeros((0,), jnp.uint32)
        return jnp.zeros((0,), bool), z32, z32
    # one (F, P) mask matrix + one any/argmax pair, instead of a Python
    # loop of ~5 dependent ops per property (sequential op COUNT is the
    # per-iteration cost lever on this platform — NOTES.md)
    kind = jnp.asarray([0 if p.expectation == Expectation.ALWAYS
                        else 1 if p.expectation == Expectation.SOMETIMES
                        else 2 for p in properties], jnp.int32)
    term_flush = exp.terminal & (exp.ebits != 0)
    ebit = ((exp.ebits[:, None] >> jnp.arange(n_props, dtype=jnp.uint32))
            & 1).astype(bool)
    masks = jnp.where(
        kind[None, :] == 0, fvalid[:, None] & ~exp.pbits[:, :n_props],
        jnp.where(kind[None, :] == 1,
                  fvalid[:, None] & exp.pbits[:, :n_props],
                  term_flush[:, None] & ebit))
    hit = masks.any(axis=0)
    k = jnp.argmax(masks, axis=0)
    return hit, whi[k], wlo[k]
