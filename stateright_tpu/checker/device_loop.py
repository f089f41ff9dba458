"""Device-resident multi-level search loop for ``spawn_tpu``.

The per-level orchestration in `tpu.py` pays one host round trip per BFS
level — each device-to-host transfer is a sync. This module compiles the
*entire search loop* into one XLA computation: a ``lax.while_loop`` whose carry holds

  * an **append-only FIFO queue** of pending packed states (the device
    analog of the reference's shared ``pending`` deques,
    `/root/reference/src/checker/bfs.rs:29-30`). Every state is enqueued
    exactly once (it enters the queue iff it won its visited-table slot),
    so the queue never wraps: the head only advances, and appends are
    contiguous block writes at the tail;
  * the open-addressed visited table (`ops/hashtable.py`),
  * an append-only **log** of (child fp, parent fp) pairs — the complete
    search record from which the host lazily mirrors its
    fingerprint->parent map for trace reconstruction (TLC-style,
    `bfs.rs:314-342`) and checkpointing,
  * sticky per-property discovery registers (first witnessing fingerprint),
  * counters and overflow flags.

Each ``while_loop`` iteration expands up to ``fmax`` queue rows exactly like
the reference's ``check_block`` hot loop (`bfs.rs:165-274`): property
evaluation, action expansion, fingerprinting, dedup-insert, enqueue. The
host re-enters the loop only every ``steps`` iterations (one dispatch per
chunk) to read a handful of scalars — progress, discoveries, growth/exit
conditions.

TPU performance notes (these shaped the design — every lane of a
data-dependent scatter/gather/probe costs real time on TPU, so the body
minimizes both scatter *count* and operating *lane width*):

  * the expansion produces ``fmax * max_actions`` child slots of which
    only the valid fraction matters. Valid children are immediately
    **shrunk to a narrow static buffer of ``kmax`` lanes** with a
    gather-only compaction (binary search over the validity prefix-sum —
    the inverse of the usual cumsum+scatter), and every downstream op
    (table probe, insert, second compaction) runs at ``kmax`` lanes, not
    ``fmax * max_actions``. If a batch produces more valid children than
    ``kmax``, the iteration aborts *before any mutation* and the host
    rebuilds with a doubled ``kmax`` — no work is lost.
  * the body performs **no row scatters at all**: freshly inserted
    children are compacted to a dense prefix (same gather trick) and both
    the queue append and the log append are contiguous
    ``dynamic_update_slice`` block writes at the tail. The garbage rows
    past ``count`` inside an appended block are never observed: the tail
    advances only by ``count``, and the next block write starts there,
    overwriting them.

Queue order is FIFO, so expansion stays level-ordered (BFS) and discovered
witness paths stay shortest, like ``spawn_bfs``.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..ops.expand import (assemble_candidates, discovery_candidates,
                          eventually_indices, expand_frontier, pre_dedup)
from ..ops.hash_kernel import fp64_device, fp64_node_device
from ..ops.hashtable import _BUCKET, table_insert


class ChunkCarry(NamedTuple):
    # ONE queue matrix and ONE log matrix: every iteration appends each
    # with a single contiguous block write (and reads the frontier with a
    # single block read). The previous four queue columns + four-to-six
    # log columns cost ~8-10 dynamic_update_slice/dynamic_slice kernels
    # per iteration; sequential op COUNT is the per-iteration cost lever
    # on this platform (NOTES.md).
    q: jax.Array        # uint32[qcap, W+3] append-only queue of pending
    #                     states: packed row (cols 0..W-1), eventually-
    #                     bits (col W), cached STATE fingerprint hi/lo
    #                     (cols W+1, W+2 — canonical under symmetry,
    #                     stored at insert time so expansion never
    #                     re-hashes the frontier)
    q_head: jax.Array   # int32[]         next row to expand
    q_tail: jax.Array   # int32[]         next free row (q_size = tail-head)
    key_hi: jax.Array   # uint32[cap/4, 4] visited table, bucket-major —
    key_lo: jax.Array   #                  kept 2-D across iterations so
    #                                      the probe pays no per-iteration
    #                                      tile-layout conversion
    log: jax.Array      # uint32[logcap, 4|6] insertion-order log: child
    #                     fp hi/lo (cols 0,1 — canonical under symmetry,
    #                     node keys under sound), parent fp hi/lo (2,3),
    #                     child ORIGINAL state fp hi/lo (4,5 — present
    #                     under symmetry/sound only)
    log_n: jax.Array    # int32[]
    disc_hit: jax.Array  # bool[P]   property discovered?
    disc_hi: jax.Array   # uint32[P] witnessing state fp (sticky first)
    disc_lo: jax.Array   # uint32[P]
    gen: jax.Array      # int32[]  states generated THIS chunk (host accumulates)
    ovf: jax.Array      # bool[]   table probe overflow (should not happen
    #                              below the growth limit)
    xovf: jax.Array     # bool[]   model capacity overflow (fatal)
    kovf: jax.Array     # bool[]   kmax candidate-buffer overflow (host
    #                              rebuilds with doubled kmax; no data loss)
    steps: jax.Array    # int32[]  remaining step budget for this chunk
    vmax: jax.Array     # int32[]  max RAW-valid children in one iteration
    #                              this chunk — the host right-sizes kraw
    #                              from it (gather cost scales with it)
    dmax: jax.Array     # int32[]  max post-dedup children in one
    #                              iteration this chunk — sizes kmax (the
    #                              probe/append stage-two buffer)
    rmax: jax.Array     # int32[]  max valid children of a single ROW
    #                              this chunk — sizes hint_eff (the
    #                              per-row compaction width)
    # --- host-property history dedup (models with host_property_indices;
    # 1-element dummies otherwise). The table dedups inserted states by
    # their host-property key columns IN the loop body, so the host's
    # per-chunk work shrinks from a standalone reduction dispatch (the
    # ~0.2-0.3s while_loop dispatch floor, NOTES.md) to one small gather
    # of the fresh representatives.
    hkey_hi: jax.Array  # uint32[hcap | 1]  history-key table
    hkey_lo: jax.Array  # uint32[hcap | 1]
    hidx: jax.Array     # int32[logcap | 1] queue index of each distinct
    #                                       key's first (representative) row
    h_n: jax.Array      # int32[]  representatives logged so far
    hovf: jax.Array     # bool[]   history-table probe overflow: the loop
    #                              exits; the host grows hcap, re-seeds the
    #                              table from hidx, and resumes (no loss —
    #                              the iteration aborts before mutation)
    # --- sound-mode cross-edge log (1-row dummy otherwise): dedup HITS
    # whose child node still has pending eventually-bits, as (parent
    # node key, child node key) rows. Insert edges live in the main log;
    # together they are the full node graph the post-exhaustion lasso
    # sweep (checker/lasso.py) needs for cycle-complete liveness.
    elog: jax.Array     # uint32[ecap | 1, 4]
    e_n: jax.Array      # int32[]  edges logged so far
    # --- per-chunk dedup telemetry (obs GLOSSARY: predup_hits /
    # probe_rounds), reset at each dispatch like ``gen``
    pdh: jax.Array      # int32[]  duplicate lanes killed by the in-batch
    #                              pre-dedup this chunk
    prb: jax.Array      # int32[]  visited-table probe rounds this chunk


def shrink_indices(mask, k: int):
    """Compaction plan: ``src[j]`` is the index of the ``j+1``-th set bit
    of ``mask`` (arbitrary value for ``j >= count`` — callers mask by the
    live count). Computed as ONE inverse 1D scatter of the running
    positions — ~25x cheaper in-loop than the binary-search dual on TPU,
    where narrow 1D scatters are cheap but wide gather cascades are not."""
    pos = jnp.cumsum(mask.astype(jnp.int32)) - 1
    # set bits past the k-th are dropped (not collapsed onto lane k-1),
    # so every produced lane < min(count, k) is exact even on overflow
    idx = jnp.where(mask & (pos < k), pos, k)
    inv = jnp.zeros((k + 1,), jnp.int32).at[idx].set(
        jnp.arange(mask.shape[0], dtype=jnp.int32), mode="drop")
    return inv[:k]


class LruCache(dict):
    """Bounded compiled-program cache with least-recently-used eviction
    (the previous wholesale ``.clear()`` at the limit forced a full
    recompile cliff for long-lived processes alternating many model
    configs). Lock-guarded: the caches are module-global and every
    checker runs on its own background thread."""

    def __init__(self, limit: int = 64):
        super().__init__()
        import threading
        self._limit = limit
        self._order: list = []
        self._lock = threading.Lock()

    def get(self, key, default=None):
        with self._lock:
            if key in self:
                self._order.remove(key)
                self._order.append(key)
                return super().__getitem__(key)
            return default

    def _set(self, key, value):
        """Unlocked insert-with-eviction shared by the locked writers."""
        if key not in self:
            while len(self._order) >= self._limit:
                super().__delitem__(self._order.pop(0))
            self._order.append(key)
        super().__setitem__(key, value)

    def __setitem__(self, key, value):
        with self._lock:
            self._set(key, value)

    def merge_max(self, key, values):
        """Atomic elementwise-max merge (the observed-size memo): a
        separate get-max-set would let a concurrent smaller observation
        overwrite a larger one."""
        with self._lock:
            if key in self:
                old = super().__getitem__(key)
                values = tuple(max(a, b) for a, b in zip(old, values))
            self._set(key, tuple(values))


_CHUNK_CACHE = LruCache()


def model_cache_key(model):
    """Composite memoization key: the model's declared config key plus
    everything else that changes the traced program — the concrete class
    (subclasses override packed_step) and mutable flags like
    ``lossy_network_``. None disables caching."""
    mkey = model.cache_key()
    if mkey is None:
        return None
    return (type(model), mkey, getattr(model, "lossy_network_", None),
            getattr(model, "max_crashes_", None),
            getattr(model, "crashable_", None))


def build_chunk_fn(model, qcap: int, capacity: int, fmax: int, kmax: int,
                   symmetry: bool = False, sound: bool = False,
                   hcap: int = 0, n_init: int = 0, kraw: int = 0,
                   hint_eff: int = 0, ecap: int = 0):
    """Compile the K-level chunk runner for fixed buffer shapes.

    Returned callable: ``chunk(carry, target_remaining, grow_limit,
    h_base) -> (carry, stats)`` where ``target_remaining`` bounds ``gen``
    (INT32_MAX when unbounded), ``grow_limit`` is the log length at which
    the loop exits so the host can grow the table, and ``h_base`` anchors
    the representative window at the host's already-pulled count.
    ``kmax`` bounds valid children per iteration; exceeding it sets
    ``kovf`` and leaves the carry untouched.

    Thin frontiers (common at the start and tail of every search) run a
    small compiled step; the program SEQUENCES three ``while_loop``s —
    small, large, small — each gated on its frontier-size window, instead
    of branching per iteration: an in-loop ``lax.cond`` over the two step
    sizes copied every carried buffer per iteration (~1.4 ms at paxos
    shapes, profiler-verified round 5 — the round-3 cond finding), and
    host-chained separate programs paid the dispatch floor per
    launch. Sequential loops in one launch pay neither.

    With ``sound`` (``CheckerBuilder.sound_eventually()``), dedup and the
    log work on (state, pending-ebits) NODE keys (``fp64_node_device``)
    while the log's original-fp columns record the plain state
    fingerprints for replay — fixing the reference's documented
    DAG-rejoin miss (`bfs.rs:239-244`).

    Memoized on :func:`model_cache_key`: checker runs re-use the jitted
    (and already-compiled) chunk across instances of the same model config.
    """
    mkey = model_cache_key(model)
    key = (mkey, qcap, capacity, fmax, kmax, symmetry, sound, hcap,
           n_init, kraw, hint_eff, ecap)
    if mkey is not None:
        cached = _CHUNK_CACHE.get(key)
        if cached is not None:
            return cached
    fn = _build_chunk_fn(model, qcap, capacity, fmax, kmax, symmetry,
                         sound, hcap, n_init, kraw, hint_eff, ecap)
    if mkey is not None:
        _CHUNK_CACHE[key] = fn
    return fn


def _build_chunk_fn(model, qcap: int, capacity: int, fmax: int, kmax: int,
                    symmetry: bool, sound: bool = False, hcap: int = 0,
                    n_init: int = 0, kraw: int = 0, hint_eff: int = 0,
                    ecap: int = 0):
    return jax.jit(
        build_chunk_core(model, qcap, capacity, fmax, kmax, symmetry,
                         sound, hcap, n_init, kraw, hint_eff, ecap),
        donate_argnums=(0,))


def build_chunk_core(model, qcap: int, capacity: int, fmax: int,
                     kmax: int, symmetry: bool, sound: bool = False,
                     hcap: int = 0, n_init: int = 0, kraw: int = 0,
                     hint_eff: int = 0, ecap: int = 0):
    """The UN-jitted chunk program: ``chunk(carry, target_remaining,
    grow_limit, h_base) -> (carry, stats)``. ``build_chunk_fn`` wraps
    it in the solo engines' donating ``jax.jit``; the batch engine
    (``checker/batch_loop.py``) instead maps it over a LANE axis with
    ``jax.vmap`` — one compiled program advancing many small same-shape
    jobs, each lane carrying its own queue/table/log slices."""
    n_actions = model.max_actions
    width = model.packed_width
    properties = model.properties()
    prop_count = len(properties)
    eventually_idx = eventually_indices(properties)
    # host-evaluated properties are discovered between chunks (post-hoc),
    # never by the in-loop registers — their placeholder bits must not
    # stop (or worse, stall) the device loop
    host_idx = frozenset(getattr(model, "host_property_indices", ()))
    device_prop_idx = [i for i in range(prop_count) if i not in host_idx]
    fa = fmax * n_actions
    kmax = min(kmax, fa)
    # a model whose lane validity needs no successor row (the ordered
    # actor step, ``offers_lane_step``) has its rows built AFTER stage
    # one: only the kraw kept lanes, from their parent rows, instead of
    # all fmax * max_actions lanes (and their F*A flat relayout) first
    lanes = bool(getattr(model, "offers_lane_step", False))
    # two-stage candidate compaction: raw-valid lanes compact to the
    # kraw buffer (where hashing and in-batch dedup run); dedup
    # SURVIVORS compact again to the narrower kmax buffer for the table
    # probe, candidate assembly, and appends. Duplicate-heavy models
    # (2pc: >80% duplicate lanes) keep their narrow probe while the
    # hash/dedup still runs far below the fa width. kraw == kmax (the
    # sound-mode default — node-key dedup happens in the table) makes
    # stage two a trace-time no-op.
    #
    # With ``hint_eff`` (models declaring ``branching_hint``: a per-ROW
    # bound on valid children), stage one is PER-ROW instead of global:
    # a tiny top_k over each row's action axis selects its <= hint_eff
    # valid slots and one gather reads them straight out of the 3-D
    # successor tensor — no fa-wide cumsum/scatter, no F*A flat reshape
    # (a tile relayout), and kraw is the static fmax*hint_eff. A row
    # exceeding hint_eff aborts the iteration before any mutation
    # (rmax rides the stats; the host rebuilds with a larger hint).
    if hint_eff and hint_eff >= n_actions:
        hint_eff = 0  # degenerate: the full action axis, use global path
    if hint_eff:
        kraw = fmax * hint_eff
    else:
        kraw = min(kraw, fa) if kraw else kmax
    kmax = min(kmax, kraw)
    # in-loop history-key dedup for host-evaluated properties
    hist_on = hcap > 0
    if hist_on:
        cols = getattr(model, "host_property_cols", None)
        hoff, hwidth = cols if cols is not None \
            else (0, model.packed_width)
        # a full-of-foreign probe advances one bucket per round, so the
        # scan is bounded by the bucket count; claim-loser retries add a
        # small constant. Hitting the bound reports hovf (the growth
        # signal) instead of spinning out the default 4096 rounds.
        h_rounds = min(4096, hcap + 64)
    # the queue slice must cover BOTH the widest append (kmax rows) and
    # the frontier dequeue (fmax rows — dynamic_slice would silently
    # CLAMP its start near the end of the queue, re-expanding consumed
    # rows and skipping pending ones)
    qmargin = max(kmax, fmax)

    def make_cond(lo_water, hi_water):
        def cond(state):
            # the state threads the materialized count between the
            # carry and the scalars
            c, _, target_remaining, grow_limit = state
            avail = c.q_tail - c.q_head
            # [lo, hi] is the loop's frontier-size window: the small loop
            # (hi = fmax_small) yields once the frontier outgrows it, the
            # large loop (lo = fmax_small+1) yields once it thins; the
            # next loop in the chunk's small-large-small sequence picks
            # the frontier up, in the same launch
            go = (avail > 0) & (avail >= lo_water) & (avail <= hi_water) \
                & (c.steps > 0) \
                & ~c.ovf & ~c.xovf & ~c.kovf & ~c.hovf \
                & (c.gen < target_remaining) \
                & (c.log_n < grow_limit) \
                & (c.q_tail <= qcap - qmargin)
            if ecap:
                # the cross-edge log must keep one iteration of headroom;
                # the host grows it on exit
                go = go & (c.e_n <= ecap - qmargin)
            if device_prop_idx and not host_idx:
                # stop once every device-evaluated property has a
                # discovery — but only when no host properties remain:
                # those need the reached set to keep growing between
                # post-hoc passes
                go = go & ~c.disc_hit[jnp.array(device_prop_idx)].all()
            return go
        return cond

    def make_step(fmax_b: int, kraw_b: int, kfin_b: int):
        def step(state):
            # (carry, materialized, target_remaining, grow_limit)
            c, target_remaining, grow_limit = state[0], state[2], state[3]
            sl = jax.lax.dynamic_slice(
                c.q, (c.q_head, 0), (fmax_b, width + 3))
            frontier = sl[:, :width]
            ebits = sl[:, width]
            pfp = (sl[:, width + 1], sl[:, width + 2])
            take = jnp.minimum(c.q_tail - c.q_head, fmax_b)
            fvalid = jnp.arange(fmax_b, dtype=jnp.int32) < take

            # the shared check_block analog (ops/expand.py); the frontier
            # fingerprints come from the queue cache, not a re-hash, and
            # child fingerprints are deferred to the narrow buffer below
            exp = expand_frontier(model, frontier, fvalid, ebits,
                                  eventually_idx, symmetry=symmetry,
                                  pfp=pfp, child_fp=False,
                                  materialize=not lanes)
            cvalid = exp.cvalid
            gen_count = cvalid.sum(dtype=jnp.int32)
            vcount = gen_count
            if hint_eff:
                # per-row bound: abort (before any mutation) only when a
                # single row outgrows the declared branching hint
                rcnt = exp.avalid.sum(axis=1, dtype=jnp.int32)
                rmax_it = rcnt.max()
                kovf = rmax_it > hint_eff
            else:
                rmax_it = jnp.int32(0)
                kovf = vcount > kraw_b

            if sound:
                # node keys: dedup identity = (state fp, pending ebits).
                # The parent's node used its AT-ENQUEUE bits (pre-clear
                # `ebits`); witnesses and log parents use node keys so the
                # host mirror chain stays walkable
                p_whi, p_wlo = fp64_node_device(exp.phi, exp.plo, ebits)
            else:
                p_whi, p_wlo = exp.phi, exp.plo

            # sticky discovery registers (idempotent: safe even if the
            # kovf branch re-expands this frontier after a kmax rebuild)
            disc_hit, disc_hi, disc_lo = c.disc_hit, c.disc_hi, c.disc_lo
            if prop_count:
                new_hit, cand_hi, cand_lo = discovery_candidates(
                    properties, exp, fvalid, whi=p_whi, wlo=p_wlo)
                keep = disc_hit | ~new_hit
                disc_hi = jnp.where(keep, disc_hi, cand_hi)
                disc_lo = jnp.where(keep, disc_lo, cand_lo)
                disc_hit = disc_hit | new_hit

            # GATHER-EARLY, TWO-STAGE: compact the raw-valid lanes to the
            # kraw_b buffer FIRST — hashing (and canonicalization, under
            # symmetry) and the in-batch dedup run there instead of at
            # the full fa width (at paxos shapes that was ~5
            # scatter/gather passes of 131k lanes each; per-lane
            # scatter/gather latency is the iteration's cost floor on
            # this platform, NOTES.md). Dedup SURVIVORS then compact to
            # the narrower kfin_b buffer where the table probe, the
            # candidate-matrix gather, and the appends run — on
            # duplicate-heavy models (2pc: >80% duplicate lanes) the
            # probe would otherwise pay 3-4x its necessary lane width.
            #
            # Abort protocol WITHOUT lax.cond: on this platform each
            # branch of a conditional that threads the big carried
            # buffers costs a full buffer copy EVERY iteration (~25 ms at
            # engine shapes, profiler-verified), so overflow handling is
            # expressed as masks instead. kovf pre-gates the table
            # insert's valid lanes, so nothing mutates and the host can
            # re-expand the same frontier after resizing (kraw and kmax
            # are sized independently from the reported vmax/dmax). hovf
            # COMMITS the iteration (its inserted keys and rows are real)
            # and only stops the loop; the unresolved lanes' keys went
            # unlogged, which the host recovers by rescanning this
            # chunk's queue span (TpuChecker._rescan_history). Garbage
            # rows block-written past an un-advanced tail are never
            # observed: the tail only moves on commit and the next
            # commit overwrites them.
            def kept_rows(src):
                # the rows of the kept lanes (flat index row * A + action)
                if lanes:
                    return jax.vmap(model.packed_step_lane)(
                        frontier[src // n_actions], src % n_actions)[0]
                return exp.flat[src]

            if hint_eff:
                # PER-ROW stage one: hint_eff rounds of argmax-and-mask
                # over each row's action axis (pure elementwise/reduce —
                # no cross-row scan, no fa-wide scatter) pick the row's
                # valid slots in action order; the slots become GLOBAL
                # flat indices for one plain 1-D gather. Parent-side
                # columns broadcast along the hint axis — no gather.
                # (A lax.top_k + 3-D take_along_axis variant measured ~2x
                # slower end-to-end on this platform.)
                avals = jnp.where(
                    exp.avalid,
                    jnp.arange(n_actions, 0, -1, dtype=jnp.int32)[None, :],
                    0)
                acols = jnp.arange(n_actions, dtype=jnp.int32)[None, :]
                cols = []
                for _s in range(hint_eff):
                    j = jnp.argmax(avals, axis=1).astype(jnp.int32)
                    cols.append(j)
                    avals = jnp.where(acols == j[:, None], 0, avals)
                j_table = jnp.stack(cols, axis=1)  # (F, hint)
                src = (jnp.arange(fmax_b, dtype=jnp.int32)[:, None]
                       * n_actions + j_table).reshape(-1)
                rows_k = kept_rows(src)
                rvalid = (jnp.arange(hint_eff, dtype=jnp.int32)[None, :]
                          < rcnt[:, None]).reshape(-1)
                par3 = jnp.broadcast_to(
                    jnp.stack([exp.ebits, p_whi, p_wlo], axis=1)[:, None, :],
                    (fmax_b, hint_eff, 3)).reshape(-1, 3)
            else:
                src = shrink_indices(cvalid, kraw_b)
                rvalid = jnp.arange(kraw_b, dtype=jnp.int32) < vcount
                rows_k = kept_rows(src)
                ridx = src // n_actions  # parent frontier row per lane
                # parent-side columns gathered in ONE 3-column pass
                par3 = jnp.stack([exp.ebits, p_whi, p_wlo], axis=1)[ridx]
            if symmetry:
                canon = jax.vmap(model.packed_representative)
                s_chi, s_clo = fp64_device(canon(rows_k))
                o_hi, o_lo = fp64_device(rows_k)
            else:
                s_chi, s_clo = fp64_device(rows_k)
                o_hi, o_lo = s_chi, s_clo
            ebits_k = par3[:, 0]
            if sound:
                # dedup identity under sound = (state fp, pending ebits)
                # node keys; the state fps stay in the candidate matrix
                # for the queue's fingerprint cache. No in-batch dedup
                # (the table resolves node-key duplicates), so stage two
                # is a no-op: kraw == kmax.
                k_chi, k_clo = fp64_node_device(s_chi, s_clo, ebits_k)
                dvalid = rvalid
            else:
                # EXACT in-batch duplicate-lane drop (ops/expand.py).
                # Load-bearing beyond dedup hygiene: WITHOUT it, same-fp
                # duplicate lanes spiral the table probe's claim-retry
                # rounds (paxos measured 23x slower)
                dvalid = pre_dedup(s_chi, s_clo, rvalid)
                k_chi, k_clo = s_chi, s_clo
            dcount = dvalid.sum(dtype=jnp.int32)
            kovf = kovf | (dcount > kfin_b)

            # ONE candidate matrix, assembled at kraw_b lanes
            # (ops/expand.assemble_candidates owns the column layout)
            cand, log_off = assemble_candidates(
                rows_k, ebits_k, s_chi, s_clo, par3[:, 1], par3[:, 2],
                o_hi, o_lo, width, symmetry, sound,
                nk_hi=k_chi if sound else None,
                nk_lo=k_clo if sound else None)

            if kfin_b < kraw_b:
                # stage two: survivors to the narrow probe buffer
                src2 = shrink_indices(dvalid, kfin_b)
                cand = cand[src2]
                k_chi = k_chi[src2]
                k_clo = k_clo[src2]
                kvalid = (jnp.arange(kfin_b, dtype=jnp.int32) < dcount) \
                    & ~kovf
            else:
                kvalid = dvalid & ~kovf

            inserted, key_hi, key_lo, t_ovf, t_rounds = table_insert(
                c.key_hi, c.key_lo, k_chi, k_clo, kvalid,
                with_rounds=True)
            t_ovf = t_ovf & ~kovf
            cnt = inserted.sum(dtype=jnp.int32)

            # the candidate matrix is gathered ONCE for the inserted lanes
            src3 = shrink_indices(inserted, kfin_b)
            n_all = cand[src3]
            n_flat = n_all[:, :width]

            if sound and ecap:
                # cross edges: dedup HITS whose child node still has
                # pending bits — with the main log's insert edges this
                # completes the node graph for the lasso sweep
                ehit = kvalid & ~inserted & (cand[:, width] != 0)
                ecnt = ehit.sum(dtype=jnp.int32)
                esrc = shrink_indices(ehit, kfin_b)
                erows = jnp.concatenate(
                    [cand[:, width + 5:width + 7],   # parent node key
                     cand[:, width + 3:width + 5]],  # child node key
                    axis=1)[esrc]
                elog = jax.lax.dynamic_update_slice(
                    c.elog, erows, (c.e_n, 0))
                e_n = c.e_n + ecnt
            else:
                elog, e_n = c.elog, c.e_n

            if hist_on:
                # dedup the fresh rows by host-property key against the
                # persistent history table; the queue index of each NEW
                # key's first row is logged for the host's post-chunk
                # pull. Garbage lanes (>= cnt) are masked. On h_ovf the
                # iteration still COMMITS (inserted keys/rows are real;
                # rolling back the big tables would cost a full copy per
                # iteration) — only the unresolved lanes' keys go
                # unlogged, and the host recovers them with a standalone
                # rescan of this chunk's queue span after growing the
                # table (TpuChecker._rescan_history).
                hhi, hlo = fp64_device(n_flat[:, hoff:hoff + hwidth])
                hval = jnp.arange(kfin_b, dtype=jnp.int32) < cnt
                h_ins, hkey_hi, hkey_lo, h_ovf = table_insert(
                    c.hkey_hi, c.hkey_lo, hhi, hlo, hval,
                    max_rounds=h_rounds)
                h_ovf = h_ovf & ~kovf
                hsrc = shrink_indices(h_ins, kfin_b)
                hcnt = h_ins.sum(dtype=jnp.int32)
                hidx = jax.lax.dynamic_update_slice(
                    c.hidx, (c.q_tail + hsrc).astype(jnp.int32),
                    (c.h_n,))
                h_n = c.h_n + hcnt
            else:
                h_ovf = jnp.bool_(False)
                hkey_hi, hkey_lo = c.hkey_hi, c.hkey_lo
                hidx, h_n = c.hidx, c.h_n

            take = jnp.where(kovf, 0, take)
            # generated counts every valid transition (host-engine
            # semantics), not the post-dedup lane count
            vgen = jnp.where(kovf, 0, gen_count)

            # the TWO block appends: queue block = (row | ebits | state
            # fp cache), log block = (dedup key | parent | original) —
            # each one contiguous column slice of the compacted matrix
            q = jax.lax.dynamic_update_slice(
                c.q, n_all[:, :width + 3], (c.q_tail, 0))
            log = jax.lax.dynamic_update_slice(
                c.log, n_all[:, log_off:log_off + c.log.shape[1]],
                (c.log_n, 0))

            nc = c._replace(
                q=q,
                q_head=c.q_head + take,
                q_tail=c.q_tail + cnt,
                key_hi=key_hi, key_lo=key_lo,
                log=log,
                log_n=c.log_n + cnt,
                hkey_hi=hkey_hi, hkey_lo=hkey_lo, hidx=hidx, h_n=h_n,
                elog=elog, e_n=e_n,
                gen=c.gen + vgen,
                ovf=c.ovf | t_ovf,
                disc_hit=disc_hit, disc_hi=disc_hi, disc_lo=disc_lo,
                kovf=c.kovf | kovf, hovf=c.hovf | h_ovf,
                xovf=c.xovf | exp.xovf,
                steps=c.steps - 1,
                vmax=jnp.maximum(c.vmax, vcount),
                dmax=jnp.maximum(c.dmax, dcount),
                rmax=jnp.maximum(c.rmax, rmax_it),
                # dedup telemetry (kovf iterations committed nothing)
                pdh=c.pdh + jnp.where(kovf, 0, vcount - dcount),
                prb=c.prb + jnp.where(kovf, 0, t_rounds))
            # the full-width rows this iteration built (a static count)
            return nc, (rows_k.shape[0] if lanes else fmax_b * n_actions)
        return step

    # thin BFS frontiers (a few hundred pending states) are common at the
    # start and tail of every search, and for narrow models they dominate
    # the iteration count; paying the full fmax*max_actions lane width for
    # them wastes most of the machine — so the chunk sequences a small
    # step loop, the large loop, and a tail small loop (see the
    # build_chunk_fn docstring for why sequencing beats an in-loop cond)
    from ..ops.expand import small_step_sizes
    fmax_small, kmax_small, two_size = small_step_sizes(
        fmax, kmax, n_actions)
    fa_small = fmax_small * n_actions
    kraw_small = fmax_small * hint_eff if hint_eff \
        else min(fa_small, kraw)
    step_large = make_step(fmax, kraw, kmax)
    if two_size:
        # the small step's raw bound is fa_small itself; its stage-two
        # buffer shrinks with kmax but never below what dedup can
        # survive
        step_small = make_step(fmax_small, kraw_small,
                               min(kmax_small, kraw_small))

    def make_body(step):
        def body(state):
            nc, built = step(state)
            return (nc, state[1] + built, state[2], state[3])
        return body

    def run_loops(state):
        imax = jnp.int32(2**31 - 1)
        if two_size:
            # outer loop over the [small-loop, large-loop] pair: a
            # frontier oscillating around the knee keeps running until
            # the steps budget (or another exit condition) is spent,
            # instead of ending the chunk at the first re-crossing and
            # paying a host round trip per crossing
            small = (jnp.int32(0), jnp.int32(fmax_small))
            large = (jnp.int32(fmax_small + 1), imax)

            def outer_body(state):
                state = jax.lax.while_loop(
                    make_cond(*small), make_body(step_small), state)
                return jax.lax.while_loop(
                    make_cond(*large), make_body(step_large), state)

            return jax.lax.while_loop(
                make_cond(jnp.int32(0), imax), outer_body, state)
        return jax.lax.while_loop(
            make_cond(jnp.int32(0), imax),
            make_body(step_large), state)

    def base_stats(out, mat):
        # ALL host-read scalars packed into ONE uint32 vector: every
        # device->host transfer is a sync, and a per-leaf device_get
        # of a dozen scalars is a dozen of them. Layout
        # (tpu.py unpacks positionally — keep in sync):
        # [q_head, q_tail, log_n, gen, ovf, xovf, kovf, h_n, hovf,
        #  vmax, dmax, rmax, e_n, pdh, prb, mat,
        #  disc_hit[P], disc_hi[P], disc_lo[P],
        #  recent queue row (W+3),
        #  then hist window (hist_on)]
        # the most recently enqueued state's queue row rides the sync
        # for free (the Explorer decodes it as live progress — the
        # chunk loop has no per-state visitation to sample from)
        recent = out.q[jnp.maximum(out.q_tail - 1, 0)]
        return jnp.concatenate([
            jnp.stack([out.q_head, out.q_tail, out.log_n, out.gen,
                       out.ovf.astype(jnp.int32),
                       out.xovf.astype(jnp.int32),
                       out.kovf.astype(jnp.int32),
                       out.h_n,
                       out.hovf.astype(jnp.int32),
                       out.vmax, out.dmax, out.rmax,
                       out.e_n, out.pdh, out.prb,
                       mat]).astype(jnp.uint32),
            out.disc_hit.astype(jnp.uint32),
            out.disc_hi, out.disc_lo, recent])

    def chunk(carry: ChunkCarry, target_remaining, grow_limit, h_base):
        # h_base anchors the representative window at the host's pulled
        # count (NOT this launch's entry h_n), covering everything the
        # whole small/large loop sequence logged
        # the loop state threads the chunk-local materialized count
        # beside the carry: a ChunkCarry field would change every
        # program's signature
        state = run_loops((carry, jnp.int32(0), target_remaining,
                           grow_limit))
        out = state[0]
        stats = base_stats(out, state[1])
        if not hist_on:
            return out, stats
        # window over the representatives logged this chunk: rides the
        # host's per-chunk sync, so the common case (few fresh distinct
        # histories) needs NO standalone pull dispatch. Overflow beyond
        # HIST_WINDOW falls back to TpuChecker._pull_host_reps. The rows,
        # witness fps AND the scalar stats ride ONE flat vector: every
        # device->host transfer is a sync, so a separate window
        # transfer would double the per-chunk syncs.
        sel = out.hidx[jnp.minimum(h_base + jnp.arange(HIST_WINDOW),
                                   out.hidx.shape[0] - 1)]
        rows = out.q[jnp.minimum(sel, out.q.shape[0] - 1)][:, :width]
        li = jnp.clip(sel - n_init, 0, out.log.shape[0] - 1)
        win = jnp.concatenate([rows, out.log[li][:, 0:2]], axis=1)
        return out, jnp.concatenate([stats, win.reshape(-1)])

    return chunk


#: representatives returned inline with each chunk's sync; beyond this the
#: host issues a standalone pull for the remainder (rare — distinct
#: host-property keys grow far slower than states)
HIST_WINDOW = 256


_SEED_CACHE = LruCache()


def seed_carry(model, qcap: int, capacity: int, init_rows, full_ebits,
               steps: int = 0, symmetry: bool = False, hcap: int = 0,
               init_fps=None, table_plan=None, ecap: int = 0,
               table=None):
    """Host-side construction of the initial carry (init states enqueued;
    the caller bulk-inserts their fingerprints into the table).
    ``full_ebits`` is a scalar for fresh runs or a per-row array when
    resuming from a checkpointed frontier. ``table`` (a bucket-major
    ``(key_hi, key_lo)`` pair) adopts an EXISTING visited table instead
    of allocating zeros — the spill path re-seeds a fresh epoch around
    the in-place-evicted table without ever pulling its keys to the
    host (mutually exclusive with ``table_plan``).

    The whole construction is ONE jitted dispatch (not a dozen separate
    zeros/update dispatches). The
    engine launches the first chunk with the seed still in flight: the
    round-2/3 measurement that this slowed the loop ~2.5x no longer
    reproduces with the consolidated carry (NOTES.md round 4), and the
    old pre-launch ``block_until_ready`` cost one more sync per
    run."""
    import numpy as np

    width = model.packed_width
    prop_count = len(model.properties())
    k = len(init_rows)
    assert table is None or table_plan is None, \
        "seed_carry: table= and table_plan= are mutually exclusive"
    kt = 0 if table_plan is None else 1 << max(
        (len(table_plan[1]) - 1).bit_length(), 0)
    adopt = table is not None
    key = (qcap, capacity, width, prop_count, symmetry, k, hcap, kt,
           ecap, adopt)
    fn = _SEED_CACHE.get(key)
    if fn is None:
        logcap = capacity

        # NOTE: the adopt=False program keeps the original 5-parameter
        # signature — threading the (unused) table halves through it
        # would change every seed program's HLO and invalidate the
        # persistent compile cache for the whole non-spill test matrix
        def _build(seed_block, t_idx, t_hi, t_lo, steps_s, khi_in,
                   klo_in):
            q = jnp.zeros((qcap, width + 3), jnp.uint32)
            if k:
                q = jax.lax.dynamic_update_slice(q, seed_block, (0, 0))
            if adopt:
                key_hi, key_lo = khi_in, klo_in
            else:
                key_hi = jnp.zeros((capacity // _BUCKET, _BUCKET),
                                   jnp.uint32)
                key_lo = jnp.zeros((capacity // _BUCKET, _BUCKET),
                                   jnp.uint32)
            if kt:
                # seed the visited table from the host placement plan —
                # part of this single program, no separate dispatch
                key_hi = key_hi.at[t_idx // _BUCKET, t_idx % _BUCKET].set(
                    t_hi, mode="drop")
                key_lo = key_lo.at[t_idx // _BUCKET, t_idx % _BUCKET].set(
                    t_lo, mode="drop")
            return ChunkCarry(
                q=q,
                q_head=jnp.int32(0), q_tail=jnp.int32(k),
                key_hi=key_hi,
                key_lo=key_lo,
                log=jnp.zeros((logcap, 6 if symmetry else 4),
                              jnp.uint32),
                log_n=jnp.int32(0),
                disc_hit=jnp.zeros((prop_count,), bool),
                disc_hi=jnp.zeros((prop_count,), jnp.uint32),
                disc_lo=jnp.zeros((prop_count,), jnp.uint32),
                gen=jnp.int32(0), ovf=jnp.bool_(False),
                xovf=jnp.bool_(False), kovf=jnp.bool_(False),
                steps=steps_s,
                hkey_hi=jnp.zeros((hcap if hcap else 1,), jnp.uint32),
                hkey_lo=jnp.zeros((hcap if hcap else 1,), jnp.uint32),
                hidx=jnp.zeros((logcap if hcap else 1,), jnp.int32),
                h_n=jnp.int32(0), hovf=jnp.bool_(False),
                elog=jnp.zeros((ecap if ecap else 1, 4), jnp.uint32),
                e_n=jnp.int32(0),
                vmax=jnp.int32(0), dmax=jnp.int32(0),
                rmax=jnp.int32(0),
                pdh=jnp.int32(0), prb=jnp.int32(0))

        if adopt:
            fn = jax.jit(_build)
        else:
            def build5(seed_block, t_idx, t_hi, t_lo, steps_s):
                return _build(seed_block, t_idx, t_hi, t_lo, steps_s,
                              None, None)

            fn = jax.jit(build5)
        _SEED_CACHE[key] = fn
    if k:
        init_arr = np.stack(init_rows).astype(np.uint32)
        eb_arr = np.broadcast_to(np.asarray(full_ebits, np.uint32),
                                 (k,)).copy()
        fps = np.asarray(init_fps if init_fps is not None
                         else [0] * k, np.uint64)
        seed_block = np.concatenate(
            [init_arr, eb_arr[:, None],
             (fps >> np.uint64(32)).astype(np.uint32)[:, None],
             fps.astype(np.uint32)[:, None]], axis=1)
    else:
        seed_block = np.zeros((0, width + 3), np.uint32)
    if kt:
        plan, seed_keys = table_plan
        arr = np.zeros((kt,), np.uint64)
        arr[:len(seed_keys)] = np.asarray(seed_keys, np.uint64)
        t_idx = np.full((kt,), capacity, np.int64)  # oob rows dropped
        t_idx[:len(plan)] = np.where(plan >= 0, plan, capacity)
        t_idx = t_idx.astype(np.int32)
        t_hi = (arr >> np.uint64(32)).astype(np.uint32)
        t_lo = arr.astype(np.uint32)
    else:
        t_idx = np.zeros((0,), np.int32)
        t_hi = t_lo = np.zeros((0,), np.uint32)
    if adopt:
        return fn(seed_block, t_idx, t_hi, t_lo, jnp.int32(steps),
                  table[0], table[1])
    return fn(seed_block, t_idx, t_hi, t_lo, jnp.int32(steps))
