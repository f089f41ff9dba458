"""SPMD sharded device-resident BFS loop: the multi-chip heart of
``spawn_tpu``.

Replaces the reference's shared-memory job market
(`/root/reference/src/checker/bfs.rs:29-30`, worker sharing at
`bfs.rs:138-150`) with fingerprint-prefix ownership over a
``jax.sharding.Mesh``:

  * the pending-state queue, the visited hash table, and the
    (child fp, parent fp) log are all sharded over one mesh axis (default
    ``"shards"``) — every shard owns a ``1/D`` slice of each;
  * a state is *owned* by the shard selected by the top ``log2(D)`` bits of
    its fingerprint's hi word, so the visited set partitions cleanly and a
    state is only ever deduplicated (and expanded) by one shard;
  * each iteration, every shard dequeues up to ``fmax`` local rows, expands
    them (vmapped ``packed_step`` via the shared `ops/expand.py` core),
    fingerprints the children, drops in-batch duplicate lanes (the same
    exact scatter-min pre-dedup as the single-chip loop), **compacts the
    survivors to a ``kmax``-lane candidate matrix**, and routes that to
    owners with a **ring exchange** (``lax.ppermute`` over ICI): D hops,
    and at each hop a shard claims the in-flight children it owns,
    inserts them into its local table slice, and appends the fresh rows
    to its local queue and log with two contiguous block writes. After D
    hops every child has passed its owner exactly once.

The whole multi-level search runs inside one ``lax.while_loop`` under
``shard_map`` — one launch per K-iteration chunk regardless of chip count,
exactly like the single-chip device loop (`checker/device_loop.py`).
Termination, generation counters, and discovery registers are psum-reduced
each iteration so the loop condition is a replicated scalar and all shards
exit in lockstep (the distributed analog of "all threads waiting and no
jobs", `bfs.rs:94-98`). Everything the host reads per chunk rides ONE
replicated uint32 stats vector (each device-to-host transfer is a
sync).

Two exchanges implement the owner routing (``tpu_options(exchange=...)``):
the **bucketed all_to_all** (default for D > 1; round 5) ranks each
candidate within its destination, scatters into a ``(D, kb)`` send
buffer, and pays ONE collective plus ONE insert/append round; the
**ring** pays D-1 ``ppermute`` hops with an insert/append round per hop.
Compacting to ``kmax`` BEFORE either exchange (round 4) is what bounds
the exchanged bytes (~8x cut on 2pc); the bucketed exchange then removes
the D-sequential-rounds cost on top — measured 1.5x (D=2, 2pc n=5) to
3.3x (D=8) faster on the virtual mesh, exact reached-set parity.

Queue-overflow safety is static: the loop condition requires every shard's
queue to have ``D * kmax`` free slots — the worst case of one iteration
routing every candidate in the machine to a single owner — before another
iteration may start, so block appends can never overrun a slice.

Like the single-chip loop, a batch whose post-dedup valid-children count
exceeds ``kmax`` aborts the iteration BEFORE any mutation (``kovf``), and
the host rebuilds with a doubled ``kmax`` — no work is lost.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.expand import (assemble_candidates, discovery_candidates,
                          eventually_indices, expand_frontier, pre_dedup)
from ..ops.hash_kernel import fp64_device, fp64_node_device
from ..ops.hashtable import _BUCKET, table_insert


class ShardedCarry(NamedTuple):
    """Search state, sharded over the mesh axis unless marked replicated.

    Shapes are global; each shard holds the ``1/D`` row-slice. Per-shard
    scalars (head, tail, log length) are length-``D`` vectors whose local
    view is a one-element array. Queues are **append-only** like the
    single-chip engine: every state enqueues exactly once on its owner
    shard, the head only advances, and the per-shard ``[0, tail)`` prefix
    doubles as the shard's list of every owned state's packed row
    (post-hoc host-property evaluation, checkpointing).
    """

    q: jax.Array        # uint32[D*qloc, W+3] per-shard append-only queues:
    #                     packed row | eventually-bits | cached state fp
    #                     hi/lo (expansion never re-hashes the frontier)
    q_head: jax.Array   # int32[D]          per-shard next row to expand
    q_tail: jax.Array   # int32[D]          per-shard next free row
    key_hi: jax.Array   # uint32[C/4, 4]    visited table, bucket-major
    key_lo: jax.Array   #                   (C/D slots per shard), 2-D so
    #                                       the probe pays no per-iteration
    #                                       tile-layout conversion
    log: jax.Array      # uint32[C, 4|6]    insertion-order log: child fp
    #                     hi/lo (node keys under sound), parent fp hi/lo,
    #                     original fp hi/lo (symmetry/sound only)
    log_n: jax.Array    # int32[D]          per-shard log length
    elog: jax.Array     # uint32[D*eloc|D, 4] sound-mode cross-edge log
    #                     (dedup hits with pending bits, as parent/child
    #                     node-key rows — see checker/device_loop.py);
    #                     1-row-per-shard dummy outside sound mode
    e_n: jax.Array      # int32[D]          per-shard edge-log length
    disc_hit: jax.Array  # bool[P]    replicated: property discovered?
    disc_hi: jax.Array   # uint32[P]  replicated: witness fp (sticky first)
    disc_lo: jax.Array   # uint32[P]
    gen: jax.Array      # int32[]  replicated: states generated this chunk
    ovf: jax.Array      # bool[]   replicated: table probe overflow
    xovf: jax.Array     # bool[]   replicated: model capacity overflow
    kovf: jax.Array     # bool[]   replicated: kmax candidate overflow
    #                              (host rebuilds with doubled kmax)
    vmax: jax.Array     # int32[]  replicated: max RAW-valid children in
    #                              one shard-iteration this chunk (sizes
    #                              the kraw hash/dedup buffer)
    dmax: jax.Array     # int32[]  replicated: max post-dedup children in
    #                              one shard-iteration this chunk (sizes
    #                              the kmax ring/probe buffer)
    bmax: jax.Array     # int32[]  replicated: max children bound for ONE
    #                              destination shard in one iteration
    #                              (sizes the bucketed exchange's kb;
    #                              0 under the ring exchange)
    steps: jax.Array    # int32[]  replicated: remaining step budget
    go: jax.Array       # bool[]   replicated: loop condition
    pavail: jax.Array   # int32[]  replicated: max pending rows on any
    #                              shard — the two-size loop windows key
    #                              on it so every shard takes the same
    #                              sized step
    pdh: jax.Array      # int32[]  replicated: duplicate lanes killed by
    #                              the in-batch pre-dedup this chunk
    #                              (psum across shards; obs predup_hits)
    prb: jax.Array      # int32[]  replicated: visited-table probe
    #                              rounds this chunk (obs probe_rounds)


#: widest supported mesh axis. ``owner_of`` routes by the dedup key's
#: top ``log2(D)`` bits, and the host tier's eviction ranges are
#: top-8-bit prefix buckets (``checker/resilience.py``
#: ``SPILL_PREFIX_BITS``) that must nest INSIDE shard ownership — a
#: fleet wider than ``2**8`` shards would silently mis-route spilled
#: ranges, so the width is guarded with an explicit raise instead.
MAX_MESH_SHARDS = 1 << 8


def _owner_bits(d: int) -> int:
    assert d & (d - 1) == 0, "mesh axis size must be a power of two"
    if d > MAX_MESH_SHARDS:
        raise ValueError(
            f"fleet width {d} exceeds the {MAX_MESH_SHARDS}-shard "
            "limit: owner_of() routes by the fingerprint's top log2(D) "
            "bits and the spill tier's eviction ranges are top-8-bit "
            "prefixes (checker/resilience.py SPILL_PREFIX_BITS) that "
            "must nest inside shard ownership — a wider fleet would "
            "silently mis-route spilled ranges. Check on <= "
            f"{MAX_MESH_SHARDS} devices, or widen SPILL_PREFIX_BITS in "
            "lockstep.")
    return d.bit_length() - 1


def effective_kb(kmax: int, d: int, kb: int = 0) -> int:
    """Per-destination bucket size for the bucketed exchange — ONE
    formula shared by the device build and the host's kovf resize
    (fingerprints are hash-uniform, so counts concentrate near
    dcount/d; the default doubles that)."""
    return min(kmax,
               kb or max(1 << 10, -(-(2 * kmax) // d // 256) * 256))


def carry_specs(axis: str) -> ShardedCarry:
    """PartitionSpecs for each carry field."""
    s, r = P(axis), P()
    return ShardedCarry(
        q=s, q_head=s, q_tail=s, key_hi=s, key_lo=s, log=s, log_n=s,
        elog=s, e_n=s,
        disc_hit=r, disc_hi=r, disc_lo=r, gen=r, ovf=r, xovf=r,
        kovf=r, vmax=r, dmax=r, bmax=r, steps=r, go=r, pavail=r,
        pdh=r, prb=r)


from ..checker.device_loop import LruCache as _LruCache

_SHARDED_CACHE = _LruCache()


def build_sharded_chunk_fn(model, mesh: Mesh, axis: str, qcap: int,
                           capacity: int, fmax: int, kmax: int,
                           symmetry: bool = False, sound: bool = False,
                           kraw: int = 0, exchange: str = "ring",
                           kb: int = 0, ecap: int = 0,
                           fused: bool = False,
                           fused_interpret: bool = False,
                           cc: int = 0):
    """Compile the K-iteration SPMD chunk runner for fixed buffer shapes.

    ``qcap``/``capacity`` are **global**; each shard works on its
    ``qcap // D`` / ``capacity // D`` slice. Returned callable:
    ``chunk(carry, target_remaining, grow_limit) -> (carry, stats)``
    where ``grow_limit`` bounds any single shard's log length (the host
    grows all buffers when a shard approaches its slice capacity) and
    ``stats`` is the replicated uint32 sync vector (see `_stats_layout`).

    With ``sound`` (``CheckerBuilder.sound_eventually()``), dedup,
    ownership routing, and the log work on (state, pending-ebits) NODE
    keys (``fp64_node_device``), while the log's original-fp columns
    record plain state fingerprints for replay — the SPMD analog of the
    single-chip sound mode (`checker/device_loop.py`).

    Memoized like the single-chip chunk (`checker/device_loop.py`).
    """
    from ..checker.device_loop import model_cache_key

    mkey = model_cache_key(model)
    key = None
    if mkey is not None:
        key = ("chunk", mkey, mesh, axis, qcap, capacity, fmax, kmax,
               symmetry, sound, kraw, exchange, kb, ecap, fused,
               fused_interpret, cc)
        cached = _SHARDED_CACHE.get(key)
        if cached is not None:
            return cached
    fn = _build_sharded_chunk_fn(model, mesh, axis, qcap, capacity,
                                 fmax, kmax, symmetry, sound, kraw,
                                 exchange, kb, ecap, fused,
                                 fused_interpret, cc)
    if key is not None:
        _SHARDED_CACHE[key] = fn
    return fn


def _build_sharded_chunk_fn(model, mesh: Mesh, axis: str, qcap: int,
                            capacity: int, fmax: int, kmax: int,
                            symmetry: bool = False,
                            sound: bool = False, kraw: int = 0,
                            exchange: str = "ring", kb: int = 0,
                            ecap: int = 0, fused: bool = False,
                            fused_interpret: bool = False,
                            cc: int = 0):
    from ..checker.device_loop import shrink_indices
    if fused:
        # the sharded fusion boundary is the exchange: expand, hash,
        # property eval and pre-dedup (in-batch arena + the cross-chunk
        # ring) run in the step kernel; the post-exchange probe/insert
        # runs as a SECOND Pallas kernel on the owner shard
        # (ops/fused.py build_probe_block_fn), so a chunk iteration is
        # two kernel dispatches around one collective
        # (supports() keeps sound staged)
        assert not sound, "fused sharded build outside its support matrix"
    else:
        assert not cc, "cc dedup ring is a fused-path structure"

    D = mesh.shape[axis]
    kbits = _owner_bits(D)
    qloc = qcap // D
    closc = capacity // D
    assert closc & (closc - 1) == 0, "per-shard table must be a power of two"
    n_actions = model.max_actions
    width = model.packed_width
    properties = model.properties()
    prop_count = len(properties)
    eventually_idx = eventually_indices(properties)
    host_idx = frozenset(getattr(model, "host_property_indices", ()))
    device_prop_idx = [i for i in range(prop_count) if i not in host_idx]
    logcap = closc
    fa = fmax * n_actions
    kmax = min(kmax, fa)
    # two-stage candidate compaction, exactly like the single-chip loop
    # (checker/device_loop.py): raw-valid lanes compact to kraw (where
    # hashing and in-batch dedup run), dedup survivors compact to the
    # narrower kmax that the ring exchange, per-hop probes, and appends
    # all scale with
    kraw = min(kraw, fa) if kraw else kmax
    kmax = min(kmax, kraw)
    # the queue slice must cover BOTH the worst-case routed appends
    # (every candidate machine-wide on one shard: D*kmax rows) and the
    # frontier dequeue (fmax rows — dynamic_slice would silently CLAMP
    # its start near the end of the queue otherwise)
    ring_headroom = max(D * kmax, fmax)
    ring = [(i, (i + 1) % D) for i in range(D)]
    # bucketed all_to_all exchange (tpu_options(exchange="bucket")): one
    # collective + ONE insert/append round instead of the ring's D-1
    # permutes and D sequential rounds. kb bounds the children any one
    # iteration routes to ONE destination; fingerprints are hash-uniform
    # so counts concentrate near dcount/D — the default doubles that,
    # and a skewed batch aborts pre-mutation via the kovf protocol (the
    # observed bound rides the stats as bmax).
    bucket = exchange == "bucket" and D > 1
    if bucket:
        kb = effective_kb(kmax, D, kb)
    eloc = ecap // D if ecap else 0
    # thin BFS levels (start/tail of every search) would pay the full
    # fmax lane width; like the single-chip loop, the chunk sequences a
    # small-step loop and a large-step loop (an in-loop lax.cond copies
    # every carried buffer per iteration — NOTES.md round 3/5), gated on
    # the REPLICATED pending maximum so every shard takes the same loop
    from ..ops.expand import small_step_sizes
    fmax_small, kmax_small, two_size = small_step_sizes(
        fmax, kmax, n_actions)
    fa_small = fmax_small * n_actions
    kraw_small = min(fa_small, kraw)

    def go_from(pavail, max_tail, max_log, max_e, disc_hit, gen, ovf,
                xovf, kovf, steps, target_remaining, grow_limit):
        """Replicated loop condition from already-reduced maxima — NO
        collectives here: the step folds every per-iteration reduction
        into three fused collectives (measured ~13 separate psum/pmax
        dispatches per iteration before, a ~1-2 ms/iteration floor even
        at D=1)."""
        go = ((pavail > 0) & (steps > 0) & ~ovf & ~xovf & ~kovf
              & (gen < target_remaining)
              & (max_log < grow_limit)
              & (max_tail <= qloc - ring_headroom))
        if eloc:
            # the cross-edge log must keep one iteration of headroom;
            # the host grows all buffers when any shard approaches
            go = go & (max_e <= eloc - ring_headroom)
        if device_prop_idx and not host_idx:
            go = go & ~disc_hit[jnp.array(device_prop_idx)].all()
        return go

    def make_step(fmax_b: int, kraw_b: int, kfin_b: int):
      if fused:
        from ..ops.fused import (build_fused_block_fn,
                                 build_probe_block_fn, cc_ring_update)
        fused_blk = build_fused_block_fn(
            model, fmax_b, 0, symmetry=symmetry, probe=False,
            interpret=fused_interpret, props=bool(prop_count), cc=cc)
        # the kernel's in-register dedup subsumes the kraw staging: the
        # stage-two compaction (and the kovf abort, still pre-mutation
        # here — the probe runs after the exchange) works off the raw
        # F*A lane masks
        kraw_b = fmax_b * n_actions
        # the SECOND kernel of the fused pipeline: the owner-side
        # post-exchange probe/insert (model-independent, sized to the
        # received lane width and the per-shard table slice)
        probe_blk = build_probe_block_fn(
            D * kb if bucket else kfin_b, closc,
            interpret=fused_interpret)

      def step(state):
        if fused and cc:
            c, rhi, rlo, cchv, target_remaining, grow_limit = state
        else:
            c, target_remaining, grow_limit = state
            rhi = rlo = cchv = None
        me = lax.axis_index(axis).astype(jnp.uint32)
        me_i = me.astype(jnp.int32)
        q_head, q_tail, log_n = c.q_head[0], c.q_tail[0], c.log_n[0]
        elog, e_n = c.elog, c.e_n[0]

        take = jnp.minimum(q_tail - q_head, fmax_b)
        sl = lax.dynamic_slice(c.q, (q_head, 0), (fmax_b, width + 3))
        frontier = sl[:, :width]
        ebits = sl[:, width]
        pfp = (sl[:, width + 1], sl[:, width + 2])
        fvalid = jnp.arange(fmax_b, dtype=jnp.int32) < take

        if fused:
            # fused front-end (ops/fused.py): ONE Pallas kernel expands,
            # fingerprints, evaluates the property predicates (discovery
            # lanes flagged in-register — only the per-property sticky
            # registers leave the kernel) and pre-dedups this shard's
            # frontier block — against the in-batch arena AND, with
            # ``cc``, the cross-chunk recent-key ring, so a duplicate
            # re-generated chunks apart dies BEFORE it costs an
            # exchange hop. The staged exchange below consumes the
            # kernel's lane masks directly.
            fout = fused_blk(frontier, ebits, fvalid,
                             pfp=pfp if prop_count else None,
                             ring=(rhi, rlo) if cc else None)
            cvalid = fout.cvalid
            gen_count = cvalid.sum(dtype=jnp.int32)
            vcount = gen_count
            xovf_it = fout.xovf
            p_whi, p_wlo = pfp
            disc_hit, disc_hi, disc_lo = (c.disc_hit, c.disc_hi,
                                          c.disc_lo)
            if prop_count:
                hit_l = fout.disc_hit
                cand_hi, cand_lo = fout.disc_hi, fout.disc_lo
                negsel = jnp.where(hit_l, jnp.int32(D - 1) - me_i,
                                   jnp.int32(-1))
            else:
                negsel = jnp.zeros((0,), jnp.int32)
            rows_k = fout.flat
            rvalid = cvalid
            s_chi, s_clo = fout.chi, fout.clo
            o_hi, o_lo = fout.ohi, fout.olo
            # parent-side columns broadcast along the action axis
            par3 = jnp.repeat(
                jnp.stack([fout.ebits, p_whi, p_wlo], axis=1),
                n_actions, axis=0)
            ebits_k = par3[:, 0]
            dvalid = fout.dvalid
            cch_it = fout.cch
            k_chi, k_clo = s_chi, s_clo
        else:
            # shared check_block analog (ops/expand.py) on local rows;
            # the frontier fingerprints come from the queue cache, not a
            # re-hash, and child fingerprints are deferred to the narrow
            # buffer below
            exp = expand_frontier(model, frontier, fvalid, ebits,
                                  eventually_idx, symmetry=symmetry,
                                  pfp=pfp, child_fp=False)
            cvalid = exp.cvalid
            gen_count = cvalid.sum(dtype=jnp.int32)
            vcount = gen_count

            if sound:
                p_whi, p_wlo = fp64_node_device(exp.phi, exp.plo, ebits)
            else:
                p_whi, p_wlo = exp.phi, exp.plo

            # local discovery candidates; the cross-shard selection
            # rides the fused collectives below (idempotent: safe under
            # kovf re-expansion)
            disc_hit, disc_hi, disc_lo = (c.disc_hit, c.disc_hi,
                                          c.disc_lo)
            if prop_count:
                hit_l, cand_hi, cand_lo = discovery_candidates(
                    properties, exp, fvalid, whi=p_whi, wlo=p_wlo)
                # pmax of (D-1 - shard) selects the LOWEST-indexed shard
                # with a hit; -1 encodes "no hit anywhere"
                negsel = jnp.where(hit_l, jnp.int32(D - 1) - me_i,
                                   jnp.int32(-1))
            else:
                negsel = jnp.zeros((0,), jnp.int32)

            # stage one: compact raw-valid lanes to the kraw buffer;
            # hash (and canonicalize, under symmetry) and in-batch dedup
            # there — local duplicates never enter the ring
            src = shrink_indices(cvalid, kraw_b)
            rvalid = jnp.arange(kraw_b, dtype=jnp.int32) < vcount
            rows_k = exp.flat[src]
            ridx = src // n_actions
            if symmetry:
                canon = jax.vmap(model.packed_representative)
                s_chi, s_clo = fp64_device(canon(rows_k))
                o_hi, o_lo = fp64_device(rows_k)
            else:
                s_chi, s_clo = fp64_device(rows_k)
                o_hi, o_lo = s_chi, s_clo
            par3 = jnp.stack([exp.ebits, p_whi, p_wlo], axis=1)[ridx]
            ebits_k = par3[:, 0]
            if sound:
                # dedup/routing identity under sound = node keys
                k_chi, k_clo = fp64_node_device(s_chi, s_clo, ebits_k)
                dvalid = rvalid
            else:
                dvalid = pre_dedup(s_chi, s_clo, rvalid)
                k_chi, k_clo = s_chi, s_clo
            xovf_it = exp.xovf
            cch_it = jnp.int32(0)
        dcount = dvalid.sum(dtype=jnp.int32)
        if bucket:
            # exact per-destination counts (the dedup key's top bits
            # pick the owner), pre-abort: a skewed batch must not
            # overflow a send bucket mid-mutation
            own_raw = (k_chi >> jnp.uint32(32 - kbits)).astype(jnp.int32)
            oh_raw = (own_raw[:, None]
                      == jnp.arange(D, dtype=jnp.int32)[None, :]) \
                & dvalid[:, None]
            bmax_it = oh_raw.sum(axis=0, dtype=jnp.int32).max()
        else:
            bmax_it = jnp.int32(0)

        # --- fused collective 1 of 3 (pre-ring): every reduction the
        # abort gating needs, in ONE pmax
        pm1 = lax.pmax(jnp.concatenate([
            jnp.stack([vcount, dcount, xovf_it.astype(jnp.int32),
                       bmax_it]),
            negsel]), axis)
        vshard, dshard, bshard = pm1[0], pm1[1], pm1[3]
        xovf_any = pm1[2] > 0
        kovf = c.kovf | (vshard > kraw_b) | (dshard > kfin_b)
        if bucket:
            kovf = kovf | (bshard > kb)
        if prop_count:
            min_shard = jnp.int32(D - 1) - pm1[4:4 + prop_count]
            g_hit = pm1[4:4 + prop_count] >= 0
            pick = hit_l & (me_i == min_shard)

        cand, log_off = assemble_candidates(
            rows_k, ebits_k, s_chi, s_clo, par3[:, 1], par3[:, 2],
            o_hi, o_lo, width, symmetry, sound,
            nk_hi=k_chi if sound else None,
            nk_lo=k_clo if sound else None)
        if kfin_b < kraw_b:
            # stage two: dedup survivors to the ring-width buffer
            src2 = shrink_indices(dvalid, kfin_b)
            k_all = cand[src2]
            kvalid = (jnp.arange(kfin_b, dtype=jnp.int32) < dcount) \
                & ~kovf
        else:
            k_all = cand
            kvalid = dvalid & ~kovf

        if kbits:
            owner = k_all[:, log_off] >> jnp.uint32(32 - kbits)
        else:
            owner = jnp.zeros((kfin_b,), jnp.uint32)

        take = jnp.where(kovf, 0, take)
        q_head = q_head + take
        key_hi, key_lo = c.key_hi, c.key_lo
        q, log = c.q, c.log
        t_ovf = jnp.bool_(False)

        if bucket:
            # bucketed exchange: rank each lane within its destination
            # (exclusive one-hot cumsum — pure elementwise), ONE scatter
            # into the (D, kb) send buffer (a trailing validity column
            # rides along so no separate count exchange is needed), ONE
            # all_to_all, then ONE insert/append round over the D*kb
            # received lanes.
            own_f = owner.astype(jnp.int32)
            oh = ((own_f[:, None]
                   == jnp.arange(D, dtype=jnp.int32)[None, :])
                  & kvalid[:, None]).astype(jnp.int32)
            rank = jnp.take_along_axis(
                jnp.cumsum(oh, axis=0), own_f[:, None], axis=1)[:, 0] - 1
            dst = jnp.where(kvalid, own_f * kb + rank, D * kb)
            sendbuf = jnp.zeros((D * kb, k_all.shape[1] + 1),
                                jnp.uint32)
            payload = jnp.concatenate(
                [k_all, jnp.ones((kfin_b, 1), jnp.uint32)], axis=1)
            sendbuf = sendbuf.at[dst].set(payload, mode="drop")
            recv = lax.all_to_all(
                sendbuf.reshape(D, kb, -1), axis, split_axis=0,
                concat_axis=0, tiled=True).reshape(D * kb, -1)
            mine = recv[:, -1] == 1
            if fused:
                # the owner-side probe/insert as the pipeline's second
                # Pallas kernel (same jaxpr as table_insert — same
                # bucket-probe invariant, same benign race)
                inserted, key_hi, key_lo, t_ovf, prb_it = probe_blk(
                    recv[:, log_off], recv[:, log_off + 1], mine,
                    key_hi, key_lo)
            else:
                inserted, key_hi, key_lo, t_ovf, prb_it = table_insert(
                    key_hi, key_lo, recv[:, log_off],
                    recv[:, log_off + 1], mine, with_rounds=True)
            cnt = inserted.sum(dtype=jnp.int32)
            if sound and eloc:
                # cross edges for the lasso sweep: dedup hits whose
                # child node still has pending bits
                ehit = mine & ~inserted & (recv[:, width] != 0)
                esrc = shrink_indices(ehit, D * kb)
                erows = jnp.concatenate(
                    [recv[:, width + 5:width + 7],
                     recv[:, width + 3:width + 5]], axis=1)[esrc]
                elog = lax.dynamic_update_slice(elog, erows, (e_n, 0))
                e_n = e_n + ehit.sum(dtype=jnp.int32)
            src3 = shrink_indices(inserted, D * kb)
            n_all = recv[src3]
            q = lax.dynamic_update_slice(
                q, n_all[:, :width + 3], (q_tail, 0))
            log = lax.dynamic_update_slice(
                log, n_all[:, log_off:log_off + c.log.shape[1]],
                (log_n, 0))
            q_tail = q_tail + cnt
            log_n = log_n + cnt
        else:
            # ownership routing: D hops around the ring; each shard
            # claims and dedups the in-flight children it owns, then
            # forwards the buffer
            rc = (k_all, kvalid, owner)
            prb_it = jnp.int32(0)
            for hop in range(D):
                k_c, val_c, own_c = rc
                mine = val_c & (own_c == me)
                if fused:
                    inserted, key_hi, key_lo, o, rnds = probe_blk(
                        k_c[:, log_off], k_c[:, log_off + 1], mine,
                        key_hi, key_lo)
                else:
                    inserted, key_hi, key_lo, o, rnds = table_insert(
                        key_hi, key_lo, k_c[:, log_off],
                        k_c[:, log_off + 1], mine, with_rounds=True)
                prb_it = prb_it + rnds
                t_ovf = t_ovf | o
                cnt = inserted.sum(dtype=jnp.int32)
                if sound and eloc:
                    ehit = mine & ~inserted & (k_c[:, width] != 0)
                    esrc = shrink_indices(ehit, kfin_b)
                    erows = jnp.concatenate(
                        [k_c[:, width + 5:width + 7],
                         k_c[:, width + 3:width + 5]], axis=1)[esrc]
                    elog = lax.dynamic_update_slice(elog, erows,
                                                    (e_n, 0))
                    e_n = e_n + ehit.sum(dtype=jnp.int32)
                src3 = shrink_indices(inserted, kfin_b)
                n_all = k_c[src3]
                q = lax.dynamic_update_slice(
                    q, n_all[:, :width + 3], (q_tail, 0))
                log = lax.dynamic_update_slice(
                    log, n_all[:, log_off:log_off + c.log.shape[1]],
                    (log_n, 0))
                q_tail = q_tail + cnt
                log_n = log_n + cnt
                if D > 1 and hop < D - 1:
                    rc = tuple(lax.ppermute(x, axis, ring) for x in rc)

        # --- fused collectives 2 and 3 of 3 (post-ring): the loop
        # condition's maxima in ONE pmax, the sums (generated count and
        # the picked discovery fingerprints) in ONE psum
        pm2 = lax.pmax(jnp.stack([q_tail - q_head, q_tail, log_n, e_n,
                                  t_ovf.astype(jnp.int32)]), axis)
        pavail, max_tail, max_log, max_e = pm2[0], pm2[1], pm2[2], pm2[3]
        ovf = c.ovf | ((pm2[4] > 0) & ~kovf)
        xovf = c.xovf | xovf_any
        # in-batch duplicate lanes this shard (dvalid already excludes
        # the cross-chunk ring hits, counted separately as cch)
        pdh_it = vcount - dcount - cch_it
        if fused and cc:
            # cross-chunk ring update, STAGED and post-commit: ring
            # entries must stay a subset of the committed visited set,
            # so only iterations that neither kovf-aborted (nothing
            # mutated) nor hit a table probe overflow (some exchanged
            # lanes unresolved at their owner) cache their exchanged
            # keys. A key this shard sent was claimed by its owner —
            # fresh or duplicate, it is in the visited set either way.
            commit = ~kovf & (pm2[4] == 0)
            rhi, rlo = cc_ring_update(
                rhi, rlo, k_all[:, log_off], k_all[:, log_off + 1],
                kvalid & commit, cc)
            cchv = cchv + jnp.where(kovf, 0, cch_it)
        if prop_count:
            ps = lax.psum(jnp.concatenate([
                jnp.stack([gen_count, pdh_it,
                           prb_it]).astype(jnp.uint32),
                jnp.where(pick, cand_hi, jnp.uint32(0)),
                jnp.where(pick, cand_lo, jnp.uint32(0))]), axis)
            gen_sum = ps[0].astype(jnp.int32)
            pdh_sum = ps[1].astype(jnp.int32)
            prb_sum = ps[2].astype(jnp.int32)
            g_hi = ps[3:3 + prop_count]
            g_lo = ps[3 + prop_count:3 + 2 * prop_count]
            keep = disc_hit | ~g_hit
            disc_hi = jnp.where(keep, disc_hi, g_hi)
            disc_lo = jnp.where(keep, disc_lo, g_lo)
            disc_hit = disc_hit | g_hit
        else:
            ps = lax.psum(jnp.stack([gen_count, pdh_it, prb_it]), axis)
            gen_sum, pdh_sum, prb_sum = ps[0], ps[1], ps[2]
        gen = c.gen + jnp.where(kovf, 0, gen_sum)
        pdh = c.pdh + jnp.where(kovf, 0, pdh_sum)
        prb = c.prb + jnp.where(kovf, 0, prb_sum)
        vmax = jnp.maximum(c.vmax, vshard)
        dmax = jnp.maximum(c.dmax, dshard)
        bmax_c = jnp.maximum(c.bmax, bshard)
        steps = c.steps - 1
        go = go_from(pavail, max_tail, max_log, max_e, disc_hit, gen,
                     ovf, xovf, kovf, steps, target_remaining,
                     grow_limit)
        nc = ShardedCarry(
            q=q, q_head=q_head[None], q_tail=q_tail[None],
            key_hi=key_hi, key_lo=key_lo,
            log=log, log_n=log_n[None],
            elog=elog, e_n=e_n[None],
            disc_hit=disc_hit, disc_hi=disc_hi, disc_lo=disc_lo,
            gen=gen, ovf=ovf, xovf=xovf, kovf=kovf, vmax=vmax,
            dmax=dmax, bmax=bmax_c, steps=steps, go=go, pavail=pavail,
            pdh=pdh, prb=prb)
        if fused and cc:
            return (nc, rhi, rlo, cchv, target_remaining, grow_limit)
        return (nc, target_remaining, grow_limit)
      return step

    step_large = make_step(fmax, kraw, kmax)
    if two_size:
        step_small = make_step(fmax_small, kraw_small,
                               min(kmax_small, kraw_small))

    cc_state = bool(fused and cc)

    def run_loops(state):
        # sequenced small/large while_loops gated on the REPLICATED
        # pending maximum (carried in pavail, so the loop conditions
        # stay collective-free), wrapped in an outer loop so a frontier
        # oscillating around the knee still spends the whole steps
        # budget in one launch — same structure as the single-chip
        # chunk, for the same reason (an in-loop lax.cond copies every
        # carried buffer per iteration)
        if two_size:
            def cond_small(s):
                return s[0].go & (s[0].pavail <= fmax_small)

            def cond_large(s):
                return s[0].go & (s[0].pavail > fmax_small)

            def outer_body(s):
                s = lax.while_loop(cond_small, step_small, s)
                return lax.while_loop(cond_large, step_large, s)

            return lax.while_loop(lambda s: s[0].go, outer_body, state)
        return lax.while_loop(lambda s: s[0].go, step_large, state)

    def entry_carry(carry, target_remaining, grow_limit):
        pm = lax.pmax(jnp.stack([carry.q_tail[0] - carry.q_head[0],
                                 carry.q_tail[0], carry.log_n[0],
                                 carry.e_n[0]]), axis)
        go = go_from(pm[0], pm[1], pm[2], pm[3], carry.disc_hit,
                     carry.gen, carry.ovf, carry.xovf, carry.kovf,
                     carry.steps, target_remaining, grow_limit)
        return carry._replace(go=go, pavail=pm[0])

    def base_stats(out):
        # ONE replicated sync vector for everything the host reads per
        # chunk (layout parsed by parallel/engine.py — keep in sync):
        # [q_head[D], q_tail[D], log_n[D],
        #  gen, ovf, xovf, kovf, vmax, dmax, bmax, pdh, prb,
        #  disc_hit[P], disc_hi[P], disc_lo[P], e_n[D],
        #  cc ring hits (fused+cc only)]
        hs = lax.all_gather(out.q_head, axis, tiled=True)
        ts = lax.all_gather(out.q_tail, axis, tiled=True)
        ls = lax.all_gather(out.log_n, axis, tiled=True)
        es = lax.all_gather(out.e_n, axis, tiled=True)
        return jnp.concatenate([
            hs.astype(jnp.uint32), ts.astype(jnp.uint32),
            ls.astype(jnp.uint32),
            jnp.stack([out.gen,
                       out.ovf.astype(jnp.int32),
                       out.xovf.astype(jnp.int32),
                       out.kovf.astype(jnp.int32),
                       out.vmax, out.dmax,
                       out.bmax, out.pdh,
                       out.prb]).astype(jnp.uint32),
            out.disc_hit.astype(jnp.uint32),
            out.disc_hi, out.disc_lo, es.astype(jnp.uint32)])

    specs = carry_specs(axis)
    if cc_state:
        def local_chunk_cc(carry, rhi, rlo, target_remaining,
                           grow_limit):
            # the cross-chunk ring threads OUTSIDE ShardedCarry (adding
            # carry fields would change the staged programs' traced
            # signatures — the persistent-compile-cache caveat); cch is
            # chunk-local telemetry re-zeroed per dispatch
            state = run_loops((
                entry_carry(carry, target_remaining, grow_limit),
                rhi, rlo, jnp.int32(0), target_remaining, grow_limit))
            out, rhi2, rlo2 = state[0], state[1], state[2]
            cch = lax.psum(state[3], axis)
            stats = jnp.concatenate([
                base_stats(out),
                jnp.reshape(cch, (1,)).astype(jnp.uint32)])
            return out, rhi2, rlo2, stats

        s = P(axis)
        fn = jax.shard_map(
            local_chunk_cc, mesh=mesh,
            in_specs=(specs, s, s, P(), P()),
            out_specs=(specs, s, s, P()), check_vma=False)
        return jax.jit(fn, donate_argnums=(0, 1, 2))

    def local_chunk(carry, target_remaining, grow_limit):
        state = run_loops((
            entry_carry(carry, target_remaining, grow_limit),
            target_remaining, grow_limit))
        return state[0], base_stats(state[0])

    # check_vma=False throughout: the hash kernel's scan carry starts
    # axis-invariant and becomes varying; skipping the varying-manual-
    # axes check beats threading pcasts through shared kernels
    fn = jax.shard_map(
        local_chunk, mesh=mesh,
        in_specs=(specs, P(), P()), out_specs=(specs, P()),
        check_vma=False)
    return jax.jit(fn, donate_argnums=(0,))


def build_sharded_insert(mesh: Mesh, axis: str):
    """Jitted SPMD bulk insert: each shard inserts its block of the global
    fingerprint arrays into its local (2-D bucket-major) table slice."""
    key = ("insert", mesh, axis)
    cached = _SHARDED_CACHE.get(key)
    if cached is not None:
        return cached

    def local(key_hi, key_lo, fhi, flo, valid):
        _, khi, klo, ovf = table_insert(key_hi, key_lo, fhi, flo, valid)
        return khi, klo, lax.psum(ovf.astype(jnp.int32), axis) > 0

    s = P(axis)
    fn = jax.shard_map(local, mesh=mesh, in_specs=(s, s, s, s, s),
                       out_specs=(s, s, P()), check_vma=False)
    fn = jax.jit(fn)
    _SHARDED_CACHE[key] = fn
    return fn


def build_sharded_rebuild(mesh: Mesh, axis: str):
    """Jitted SPMD table rebuild from the per-shard logs: each shard's log
    slice holds exactly the fingerprints it owns, so after growth the fresh
    table is rebuilt entirely on device — no host routing round trip."""
    key = ("rebuild", mesh, axis)
    cached = _SHARDED_CACHE.get(key)
    if cached is not None:
        return cached

    def local(key_hi, key_lo, log, log_n):
        valid = jnp.arange(log.shape[0], dtype=jnp.int32) < log_n[0]
        _, khi, klo, ovf = table_insert(key_hi, key_lo, log[:, 0],
                                        log[:, 1], valid)
        return khi, klo, lax.psum(ovf.astype(jnp.int32), axis) > 0

    s = P(axis)
    fn = jax.shard_map(local, mesh=mesh, in_specs=(s, s, s, s),
                       out_specs=(s, s, P()), check_vma=False)
    fn = jax.jit(fn)
    _SHARDED_CACHE[key] = fn
    return fn


def owner_of(fp: int, d: int) -> int:
    """The shard owning a 64-bit fingerprint (top log2(d) bits).

    Prefix ownership gives the halving invariant the degradation
    ladder (checker/resilience.py) leans on: ``owner_of(fp, d // 2)
    == owner_of(fp, d) // 2`` — halving the mesh merges ADJACENT shard
    pairs, so a re-route onto ``d // 2`` devices moves every state to
    the shard that already owns its prefix, never scattering one old
    shard's keys across the new mesh."""
    kbits = _owner_bits(d)
    return (fp >> (64 - kbits)) if kbits else 0


def build_sharded_posthoc(model, mesh: Mesh, axis: str, qcap: int,
                          capacity: int, hmax: int):
    """Per-shard post-hoc reduction for host-evaluated properties: each
    shard dedups its own queue prefix by the model's host-property
    columns and emits up to ``hmax`` representative rows plus witness
    fingerprints. Distinct keys may repeat across shards (each shard
    dedups locally); the host merges by key bytes — at most a D-fold
    overcount on the wire for a cross-shard-popular history."""
    from ..checker.device_loop import model_cache_key, shrink_indices
    from ..ops.hash_kernel import fp64_device

    D = mesh.shape[axis]
    qloc = qcap // D
    closc = capacity // D
    width = model.packed_width
    cols = getattr(model, "host_property_cols", None)
    off, hw = cols if cols is not None else (0, width)
    mkey = model_cache_key(model)
    key = None
    if mkey is not None:
        key = ("posthoc", mkey, mesh, axis, qcap, capacity, hmax)
        cached = _SHARDED_CACHE.get(key)
        if cached is not None:
            return cached

    def local(q, q_tail, log, n_init):
        key_cols = q[:, off:off + hw]
        hhi, hlo = fp64_device(key_cols)
        valid = jnp.arange(qloc, dtype=jnp.int32) < q_tail[0]
        khi = jnp.zeros((closc // _BUCKET, _BUCKET), jnp.uint32)
        klo = jnp.zeros((closc // _BUCKET, _BUCKET), jnp.uint32)
        inserted, khi, klo, ovf = table_insert(khi, klo, hhi, hlo, valid)
        hcount = inserted.sum(dtype=jnp.int32)
        src = shrink_indices(inserted, hmax)
        out_rows = q[src][:, :width]
        li = jnp.maximum(src - n_init[0], 0)
        w_hi = log[li, 0]
        w_lo = log[li, 1]
        tovf = lax.psum(ovf.astype(jnp.int32), axis) > 0
        over = lax.psum((hcount > hmax).astype(jnp.int32), axis) > 0
        return (out_rows, src[None, :], w_hi[None, :], w_lo[None, :],
                hcount[None], tovf, over)

    s = P(axis)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(s, s, s, s),
        out_specs=(s, s, s, s, s, P(), P()), check_vma=False)
    fn = jax.jit(fn)
    if key is not None:
        _SHARDED_CACHE[key] = fn
    return fn


def seed_sharded_carry(model, mesh: Mesh, axis: str, qcap: int,
                       capacity: int, init_rows, init_fps, full_ebits,
                       prop_count: int, symmetry: bool = False,
                       sound: bool = False,
                       cache_fps=None, table_plan=None,
                       ecap: int = 0) -> ShardedCarry:
    """Construct the initial sharded carry ON DEVICE: the host routes
    only the init rows (tiny) to their owner shards' blocks; every big
    buffer is zeroed by a shard_map'd device program, instead of
    device_put-ing ~160 MB of host zeros for the queue/table/log. The
    caller inserts the init fingerprints into the table via
    :func:`build_sharded_insert`.

    ``init_fps`` are the DEDUP keys (node keys under sound) — they pick
    the owner shard, matching the in-loop routing. ``cache_fps`` are the
    STATE fingerprints cached in the queue's fp columns (the loop
    re-derives node keys from them plus each row's ebits); they default
    to ``init_fps``, which is only correct outside sound mode."""
    D = mesh.shape[axis]
    qloc = qcap // D
    width = model.packed_width
    log_w = 6 if symmetry or sound else 4
    if cache_fps is None:
        cache_fps = init_fps

    # host-side routing of the init rows into per-shard blocks
    per_shard: list = [[] for _ in range(D)]
    ebs = np.broadcast_to(np.asarray(full_ebits, np.uint32),
                          (len(init_rows),))
    for i, (row, fp) in enumerate(zip(init_rows, init_fps)):
        s = owner_of(fp, D)
        r = np.zeros((width + 3,), np.uint32)
        r[:width] = row
        r[width] = ebs[i]
        r[width + 1] = np.uint32(int(cache_fps[i]) >> 32)
        r[width + 2] = np.uint32(int(cache_fps[i]) & 0xFFFFFFFF)
        per_shard[s].append(r)
    pad = max(1, max((len(b) for b in per_shard), default=0))
    assert pad <= qloc, "init states overflow a shard queue"
    init_block = np.zeros((D * pad, width + 3), np.uint32)
    q_tail = np.zeros((D,), np.int32)
    for s, block in enumerate(per_shard):
        if block:
            init_block[s * pad:s * pad + len(block)] = np.stack(block)
        q_tail[s] = len(block)

    # per-shard host placement plans scattered inside the seed program
    # (small seeds): no bulk-insert dispatch, no blocking overflow pull
    if table_plan is not None:
        plans, keys_by_shard = table_plan
        kt = 1 << max((max((len(b) for b in keys_by_shard), default=1)
                       - 1).bit_length(), 0)
        t_idx = np.full((D * kt,), capacity // D, np.int64)
        t_hi = np.zeros((D * kt,), np.uint32)
        t_lo = np.zeros((D * kt,), np.uint32)
        for s, (plan, keys) in enumerate(zip(plans, keys_by_shard)):
            arr = np.asarray(keys, np.uint64)
            t_idx[s * kt:s * kt + len(plan)] = np.where(
                plan >= 0, plan, capacity // D)
            t_hi[s * kt:s * kt + len(keys)] = \
                (arr >> np.uint64(32)).astype(np.uint32)
            t_lo[s * kt:s * kt + len(keys)] = arr.astype(np.uint32)
        t_idx = t_idx.astype(np.int32)
    else:
        kt = 0
        t_idx = np.zeros((D,), np.int32)
        t_hi = t_lo = np.zeros((D,), np.uint32)

    key = ("seed", mesh, axis, qcap, capacity, width, log_w, pad,
           prop_count, kt, ecap)
    fn = _SHARDED_CACHE.get(key)
    if fn is None:
        def local(blk, tail, t_idx, t_hi, t_lo):
            q = jnp.zeros((qloc, width + 3), jnp.uint32)
            q = lax.dynamic_update_slice(q, blk, (0, 0))
            z = jnp.int32(0)
            f = jnp.bool_(False)
            key_hi = jnp.zeros(
                (capacity // D // _BUCKET, _BUCKET), jnp.uint32)
            key_lo = jnp.zeros(
                (capacity // D // _BUCKET, _BUCKET), jnp.uint32)
            if kt:
                key_hi = key_hi.at[t_idx // _BUCKET,
                                   t_idx % _BUCKET].set(t_hi,
                                                        mode="drop")
                key_lo = key_lo.at[t_idx // _BUCKET,
                                   t_idx % _BUCKET].set(t_lo,
                                                        mode="drop")
            return ShardedCarry(
                q=q,
                q_head=jnp.zeros((1,), jnp.int32),
                q_tail=tail,
                key_hi=key_hi,
                key_lo=key_lo,
                log=jnp.zeros((capacity // D, log_w), jnp.uint32),
                log_n=jnp.zeros((1,), jnp.int32),
                elog=jnp.zeros((ecap // D if ecap else 1, 4),
                               jnp.uint32),
                e_n=jnp.zeros((1,), jnp.int32),
                disc_hit=jnp.zeros((prop_count,), bool),
                disc_hi=jnp.zeros((prop_count,), jnp.uint32),
                disc_lo=jnp.zeros((prop_count,), jnp.uint32),
                gen=z, ovf=f, xovf=f, kovf=f, vmax=z, dmax=z, bmax=z,
                steps=z, go=f, pavail=z, pdh=z, prb=z)

        s = P(axis)
        fn = jax.jit(jax.shard_map(
            local, mesh=mesh, in_specs=(s, s, s, s, s),
            out_specs=carry_specs(axis), check_vma=False))
        _SHARDED_CACHE[key] = fn
    sh = NamedSharding(mesh, P(axis))
    return fn(jax.device_put(init_block, sh), jax.device_put(q_tail, sh),
              jax.device_put(t_idx, sh), jax.device_put(t_hi, sh),
              jax.device_put(t_lo, sh))
