"""Multi-chip checking engine: ``spawn_tpu()`` over a ``jax.sharding.Mesh``.

Selected by ``checker_builder.tpu_options(mesh=mesh)``. Orchestrates the
SPMD chunk loop built in `sharded.py` the same way ``TpuChecker._run_device``
drives the single-chip device loop: the host re-enters the jitted loop once
per K-iteration chunk, reads a handful of replicated scalars (progress,
discoveries, growth pressure), grows the sharded buffers when any shard
approaches its slice capacity, and finally pulls the per-shard
(child fp, parent fp) logs to complete the host mirror used for trace
reconstruction by replay (TLC-style,
`/root/reference/src/checker/bfs.rs:314-342`).

Host-evaluated properties (e.g. the linearizability search) work like the
single-chip device engine: each shard's append-only queue prefix is its
list of owned states, so every chunk each shard dedups its prefix by the
model's host-property columns on device and the host evaluates each
distinct key once (merging across shards by key bytes). Per-state visitors
remain unsupported (a host feature; use the per-level engine).
"""

from __future__ import annotations

from contextlib import nullcontext
from typing import Dict, List, Optional

import numpy as np

from ..checker.builder import CheckerBuilder
from ..checker.tpu import TpuChecker, _combine64, auto_fmax
from .sharded import (MAX_MESH_SHARDS, ShardedCarry,
                      build_sharded_chunk_fn, build_sharded_insert,
                      effective_kb, owner_of, seed_sharded_carry)


class ShardedTpuChecker(TpuChecker):
    """Fingerprint-prefix-sharded BFS over a device mesh."""

    def __init__(self, builder: CheckerBuilder,
                 spawn_t0: Optional[float] = None):
        super().__init__(builder, spawn_t0)
        opts = builder.tpu_options_
        self._mesh = opts["mesh"]
        self._axis = str(opts.get("mesh_axis", "shards"))
        if self._axis not in self._mesh.shape:
            raise ValueError(
                f"mesh has no axis {self._axis!r}; axes: "
                f"{tuple(self._mesh.shape)}")
        d = self._mesh.shape[self._axis]
        if d & (d - 1):
            raise ValueError("mesh axis size must be a power of two")
        if d > MAX_MESH_SHARDS:
            raise ValueError(
                f"fleet width {d} exceeds the {MAX_MESH_SHARDS}-shard "
                "limit: owner_of() top-bit routing must nest the spill "
                "tier's 8-bit prefix ranges (checker/resilience.py "
                "SPILL_PREFIX_BITS) inside shard ownership — shard "
                f"over <= {MAX_MESH_SHARDS} devices")
        if self._capacity % d:
            raise ValueError("capacity must be divisible by the mesh axis")
        if int(opts.get("hint", 0)):
            # the per-row stage-one compaction is a single-chip knob
            # (checker/device_loop.py); silently ignoring it here skewed
            # single-chip vs sharded A/B comparisons — fail loudly
            raise ValueError(
                "tpu_options(hint=...) is not supported with mesh=...: "
                "the sharded chunk loop has no per-row compaction stage, "
                "so the hint would be silently ignored and skew A/B "
                "comparisons against the single-chip engine. Drop "
                "hint=... (or drop mesh=...)")
        if getattr(self, "_sound", False) and self._host_props:
            raise NotImplementedError(
                "sound_eventually() with host-evaluated properties is "
                "not supported on the sharded engine")
        if self._host_ev:
            # mirrors the single-chip mode='device' check (tpu.py): the
            # sharded loop has no per-level orchestration point to
            # correct ebits before enqueue, so a violated host-evaluated
            # EVENTUALLY property would silently report as passing
            raise NotImplementedError(
                "host-evaluated eventually properties need the per-level "
                "engine; drop tpu_options(mesh=...) or use single-chip "
                "spawn_tpu")

    # ------------------------------------------------------------------
    def _pull_global(self, arrays):
        """``jax.device_get`` of carry pieces, safe when the mesh spans
        processes (``cluster.mesh.pull_global`` replicates over DCN
        first). COLLECTIVE on a multi-process mesh: every rank's host
        loop takes the same pulls in the same order — guaranteed
        because all control flow derives from the replicated stats."""
        from ..cluster.mesh import pull_global
        return pull_global(arrays, self._mesh)

    # ------------------------------------------------------------------
    def _run_steps(self):
        # generator form of the sharded chunk loop (each yield = one
        # processed chunk / handled intervention), driven blocking by
        # the inherited TpuChecker._run or stepwise by the job
        # service's StepDriver; a pending request_pause() drains the
        # pipeline and writes the resume_from-loadable checkpoint
        import jax

        mesh, axis = self._mesh, self._axis
        D = mesh.shape[axis]
        model = self._model
        properties = self._properties
        prop_count = len(properties)
        n_actions = model.max_actions
        from ..ops.expand import eventually_indices
        full_ebits = np.uint32(sum(1 << i
                                   for i in eventually_indices(properties)))
        generated = self._generated
        discoveries: Dict[str, int] = {}
        target = self._target_state_count
        opts = self._tpu_options
        k_steps = int(opts.get("chunk_steps", 64))

        if self._resume_path is not None:
            # checkpoints are shard-agnostic (the single-chip format):
            # the frontier re-routes by owner on THIS mesh, which may
            # differ from the mesh (or single chip) that wrote it.
            # Routing uses the DEDUP key — the cached fp as-is (state,
            # or canonical under symmetry), or the node key re-derived
            # from it plus the row's pending ebits under sound — so it
            # matches the in-loop owner computation exactly.
            init_rows, seed_ebits, resume_cache_fps = \
                self._load_checkpoint(discoveries)
            if self._sound:
                from ..fingerprint import fp64_node
                frontier_fps = [
                    fp64_node(fp, int(eb))
                    for fp, eb in zip(resume_cache_fps, seed_ebits)]
            else:
                frontier_fps = list(resume_cache_fps)
        else:
            init_rows = self._seed_inits()
            seed_ebits = full_ebits
            frontier_fps = list(generated.keys())
            resume_cache_fps = None
        base_unique = len(generated)
        n_init = len(init_rows)
        if prop_count == 0:
            return  # vacuously done (bfs.rs:121-128)

        # --- resilience plumbing (checker/resilience.py), created
        # BEFORE the seed: with memory tiering the shadow decides which
        # keys are device-resident at all. Identical contract to the
        # single-chip engine — with retry/autosave/tiering on, the host
        # shadow is maintained per chunk (per shard); a transient fault
        # re-seeds a fresh sharded carry from it (re-routing the
        # pending frontier by owner exactly like a checkpoint resume),
        # a capacity fault spills cold prefix ranges to the host tier
        # first, and past the retry budget the DEGRADATION LADDER takes
        # over (degrade_step below) — a rung inherits the survivor
        # shards' spill state through HostShadow.reshard.
        from ..checker.resilience import (CorruptionError, FaultAttributor,
                                          FaultKind, audit_chunk_rows,
                                          blamed_device, classify_error,
                                          find_candidate_overflow,
                                          gather_rows, match_device,
                                          pack_qrows, resolve_grant,
                                          select_survivors,
                                          spill_eligible)

        policy = self._retry_policy
        ladder = self._degrade_policy
        spill_pol = self._spill_policy
        audit_pol = self._audit_policy
        corrupt_hook = self._corrupt_hook
        spill_on = spill_pol.enabled and not self._sound
        attributor = FaultAttributor(ladder.blame_after)
        shadow = self._make_shadow(D)
        table_fps = (shadow.hot_keys() if shadow is not None
                     else list(generated.keys()))

        # two-stage candidate widths, exactly like the single-chip
        # engine: kraw (hash/dedup width) and kmax (ring/probe/append
        # width), independently resized on kovf from the reported
        # vmax/dmax
        from ..checker.device_loop import model_cache_key
        from ..checker.tpu import _SIZE_MEMO, candidate_sizes
        fmax = int(opts.get("fmax", auto_fmax(model, shards=D)))
        fa = fmax * n_actions
        # observed-size autotuning, shared with the single-chip engine
        # (keyed per mesh size: per-shard maxima shrink with D)
        size_key = model_cache_key(model)
        if size_key is not None:
            size_key = (size_key, fmax, self._sound, self._symmetry, D)
        kraw, kmax = candidate_sizes(model, fmax, self._sound, opts,
                                     size_key)
        # bucketed all_to_all is the default exchange for D > 1: one
        # collective + one insert round vs the ring's D sequential
        # rounds — measured 1.5x (D=2) to 3.3x (D=8) faster end-to-end
        # on the virtual mesh, with exact reached-set parity. The ring
        # (tpu_options(exchange="ring")) remains for A/B on real ICI.
        exchange = str(opts.get("exchange", "bucket"))
        if exchange not in ("ring", "bucket"):
            raise ValueError(
                f"unknown tpu_options exchange {exchange!r}; expected "
                "'ring' or 'bucket'")
        kb = int(opts.get("kb", 0))
        # sound mode logs cross edges for the post-exhaustion lasso
        # sweep, exactly like the single-chip engine
        ecap = self._capacity if self._sound else 0
        headroom = max(D * kmax, fmax)
        # per-shard slice must keep one worst-case iteration of headroom
        # below the growth limit (same invariant as the single-chip
        # loop); ``preload`` — the table keys seeded before the first
        # chunk — is subtracted from the per-shard growth limit so total
        # occupancy still trips growth at ~grow_at on resumed and
        # fault-recovered runs
        preload = len(table_fps)
        while self._grow_at * (self._capacity // D) \
                <= headroom + preload \
                and spill_pol.can_grow(self._capacity):
            self._capacity *= 4
        if self._grow_at * (self._capacity // D) <= headroom + preload:
            # the preloaded set alone exceeds the HBM budget: evict at
            # seed (the single-chip engine's seed-spill, per shard by
            # construction — prefix ranges are owner-consistent)
            plan = (shadow.spill_plan(
                int(self._grow_at * (self._capacity // D))
                - headroom - 1)
                if spill_on and shadow is not None else None)
            if plan is None:
                self._capacity_terminal(RuntimeError(
                    f"sharded table budget (max_capacity="
                    f"{spill_pol.max_capacity}) cannot hold the seeded "
                    f"reached set ({preload} keys) with spill "
                    "unavailable"), shadow, discoveries)
            table_fps = shadow.hot_keys()
            preload = len(table_fps)
            self._metrics.inc("spills")
            if plan[2]:
                self._metrics.inc("evicted_keys", plan[2])
            self._metrics.set("host_tier_keys", shadow.host_tier_keys)
            if self._trace:
                self._trace.emit("evict", prefixes=len(plan[0]),
                                 keys=plan[2])
                self._trace.emit("spill", capacity=self._capacity,
                                 hot=preload, reason="seed",
                                 host_tier_keys=shadow.host_tier_keys)
        # per-shard init fps in queue order (post-hoc witness mapping);
        # the queue slices are sized from the per-shard split, not the
        # total frontier (a resumed frontier routes ~1/D to each shard)
        init_by_shard: List[List[int]] = [[] for _ in range(D)]
        for fp in frontier_fps:
            init_by_shard[owner_of(fp, D)].append(fp)
        self._init_by_shard = init_by_shard
        n_init_arr = np.asarray([len(b) for b in init_by_shard], np.int32)
        qcap = self._sharded_qcap(
            max((len(b) for b in init_by_shard), default=0), headroom, D)

        insert_fn = build_sharded_insert(mesh, axis)
        # the queue caches STATE fps; frontier_fps (the routing/dedup
        # keys) are node keys under sound — see seed_sharded_carry
        cache_fps = (self._seed_cache_fps
                     if self._resume_path is None else resume_cache_fps)
        # the table seeds with EVERYTHING known (on resume: the whole
        # mirrored reached set, not just the pending frontier). Small
        # seeds (the fresh-run case) are placed by per-shard host plans
        # scattered INSIDE the seed program — the bulk-insert dispatch
        # ended with a blocking overflow device_get, one more sync
        # before the first chunk launch (the single-chip
        # engine's table_plan trick, checker/tpu.py).
        table_plan = None
        if len(table_fps) <= (1 << 15):
            from ..ops.hashtable import plan_insert_host
            keys_by_shard: List[List[int]] = [[] for _ in range(D)]
            for fp in table_fps:
                keys_by_shard[owner_of(fp, D)].append(fp)
            table_plan = ([plan_insert_host(b, self._capacity // D)
                           for b in keys_by_shard], keys_by_shard)
        with self._spans.span("seed"):
            carry = seed_sharded_carry(model, mesh, axis, qcap,
                                       self._capacity, init_rows,
                                       frontier_fps, seed_ebits,
                                       prop_count,
                                       symmetry=self._symmetry,
                                       sound=self._sound,
                                       cache_fps=cache_fps,
                                       table_plan=table_plan, ecap=ecap)
            if table_plan is None:
                key_hi, key_lo = self._sharded_bulk_insert(
                    insert_fn, carry.key_hi, carry.key_lo, table_fps, D)
                carry = carry._replace(key_hi=key_hi, key_lo=key_lo)

        self._metrics.set("action_lanes", model.max_actions)
        self._metrics.set("row_words", model.packed_width)
        # True until a freshly built chunk program's first call, which
        # traces and compiles it (or loads it from the compile cache)
        first_call = [False]

        def rebuild_chunk(reason: str = "initial"):
            self._metrics.inc("compiles")
            if self._trace:
                self._trace.emit("compile", reason=reason)
            with self._spans.span("program"):
                fn = build_sharded_chunk_fn(
                    model, mesh, axis, qcap, self._capacity, fmax, kmax,
                    symmetry=self._symmetry, sound=self._sound,
                    kraw=kraw, exchange=exchange, kb=kb, ecap=ecap)
            first_call[0] = True
            return fn

        chunk_fn = rebuild_chunk()
        pipeline = bool(opts.get("pipeline", True))

        import time
        from collections import deque

        import jax.numpy as jnp

        host_prop_idx = {i for i, _p in self._host_props}

        self._fault_shards = D
        self._metrics.set("mesh_shards", D)

        # --- fleet visibility (cluster/mesh.py): host labels (real
        # process_index, or the simulated tpu_options(host_map=...)),
        # process count, and — once the mesh spans processes — one
        # timed DCN round trip, the latency floor every fingerprint
        # exchange pays between hosts
        from ..cluster.mesh import dcn_probe, mesh_hosts
        host_map = opts.get("host_map")
        n_hosts = len(set(mesh_hosts(mesh, host_map)))
        n_procs = int(jax.process_count())
        self._metrics.set("hosts", n_hosts)
        self._metrics.set("procs", n_procs)
        probe_s = None
        if n_procs > 1:
            probe_s = dcn_probe(mesh, axis)
            self._metrics.add_time("dcn_exchange_s", probe_s)
            # the collective's interval on the span timeline: the DCN
            # floor every cross-host fingerprint exchange pays
            t_probe = time.perf_counter()
            self._spans.record("exchange", t_probe - probe_s, t_probe,
                               shard=D)
        if self._trace:
            self._trace.emit(
                "mesh_init", shards=D, hosts=n_hosts, procs=n_procs,
                dcn_exchange_s=(round(probe_s, 6)
                                if probe_s is not None else None))

        def seed_shadow_epoch(rows_list, frontier_keys, ebs_arr,
                              cache_list) -> None:
            # per-shard rows in the DEVICE routing order (the stable
            # order of appearance seed_sharded_carry uses)
            per = [([], [], []) for _ in range(D)]
            for i, key in enumerate(frontier_keys):
                pr, pe, pf = per[owner_of(key, D)]
                pr.append(rows_list[i])
                pe.append(int(ebs_arr[i]))
                pf.append(int(cache_list[i]))
            shadow.seed_epoch([
                pack_qrows(pr, np.asarray(pe, np.uint32), pf,
                           model.packed_width)
                for pr, pe, pf in per])

        if shadow is not None:
            ebs_b = np.broadcast_to(np.asarray(seed_ebits, np.uint32),
                                    (len(init_rows),))
            seed_shadow_epoch(init_rows, frontier_fps, ebs_b, cache_fps)

        # --- chunk loop -------------------------------------------------
        # Double-buffered dispatch, exactly like the single-chip engine
        # (checker/tpu.py chunk loop): chunk N+1 launches on the donated
        # carry future before chunk N's stats materialize, hiding the
        # host work (stats decode, the post-hoc host-property pass)
        # under the mesh. Every host-intervention condition also gates
        # the SPMD loop's replicated cond (sharded.go_from), so a
        # speculatively launched chunk past one of them runs zero
        # iterations and its stats replay idempotently; host-only exits
        # (host-property discoveries, the generation target) land one
        # chunk late — the documented chunk-granularity overshoot.
        inflight: deque = deque()
        cur = {"q_head": np.zeros(D, np.int64),
               "q_tail": np.zeros(D, np.int64),
               "log_n": np.zeros(D, np.int64),
               "e_n": np.zeros(D, np.int64)}
        kovf_pend = [0, 0, 0]  # observed vmax/dmax/bmax of kovf chunks

        def dispatch() -> None:
            nonlocal carry
            closc = self._capacity // D
            # epoch-local growth limit: the preloaded table keys are
            # subtracted, as in the single-chip dispatch
            grow_limit = np.int32(min(self._grow_at * closc,
                                      closc - headroom) - preload)
            remaining = np.int32(
                min(max(target - self._state_count, 0), 2**31 - 1)
                if target is not None else 2**31 - 1)
            carry = carry._replace(gen=jnp.int32(0),
                                   steps=jnp.int32(k_steps),
                                   vmax=jnp.int32(0),
                                   dmax=jnp.int32(0),
                                   bmax=jnp.int32(0),
                                   pdh=jnp.int32(0),
                                   prb=jnp.int32(0))
            ordinal = int(self._metrics.get("chunks", 0)) + 1
            with self._spans.span("dispatch", chunk=ordinal), \
                    (self._spans.span("program", chunk=ordinal)
                     if first_call[0] else nullcontext()):
                carry, stats_d = chunk_fn(carry, remaining, grow_limit)
            first_call[0] = False
            t_disp = time.perf_counter()
            self._metrics.inc("chunks")
            if self._metrics.get("first_dispatch_s") is None:
                self._metrics.set("first_dispatch_s",
                                  t_disp - self._spawn_t0)
            inflight.append((ordinal, stats_d, int(grow_limit), t_disp))

        def process(ordinal: int, stats_d, grow_limit: int,
                    t_disp: float) -> set:
            nonlocal fault_attempt, spill_attempt
            # ONE transfer for everything the host reads per chunk —
            # routed through the fault hook + watchdog deadline (the
            # sync / pull / device spans, checker/tpu.py)
            stats = self._materialize_stats(stats_d, ordinal,
                                            t_disp=t_disp)
            # a successful sync proves the backend is alive; the retry
            # budget (and the per-device blame streak) bounds
            # CONSECUTIVE faults, the spill budget CONSECUTIVE spills
            fault_attempt = 0
            spill_attempt = 0
            attributor.clear()
            with self._spans.span("decode", chunk=ordinal):
                return decode(ordinal, stats, grow_limit)

        def decode(ordinal: int, stats, grow_limit: int) -> set:
            nonlocal corruption_attempt
            acts: set = set()
            q_head = stats[:D].astype(np.int64)
            q_tail = stats[D:2 * D].astype(np.int64)
            log_n = stats[2 * D:3 * D].astype(np.int64)
            gen = int(stats[3 * D])
            ovf = bool(stats[3 * D + 1])
            xovf = bool(stats[3 * D + 2])
            kovf = bool(stats[3 * D + 3])
            vmax = int(stats[3 * D + 4])
            dmax = int(stats[3 * D + 5])
            bmax = int(stats[3 * D + 6])
            pdh = int(stats[3 * D + 7])
            prb = int(stats[3 * D + 8])
            mat = int(stats[3 * D + 9])
            base = 3 * D + 10
            disc_hit = stats[base:base + prop_count].astype(bool)
            disc_hi = stats[base + prop_count:base + 2 * prop_count]
            disc_lo = stats[base + 2 * prop_count:base + 3 * prop_count]
            e_n = stats[base + 3 * prop_count:
                        base + 3 * prop_count + D].astype(np.int64)
            if shadow is not None:
                # fold each shard's appends into the host shadow: the
                # per-shard queue/log slices are append-only and keep
                # their shard-relative positions across growths, so the
                # suffix gathers reconstruct the device state exactly
                with self._spans.span("shadow", chunk=ordinal):
                    qloc = qcap // D
                    closc = self._capacity // D
                    eloc = (ecap // D) if ecap else 0
                    q_idx, l_idx, e_idx = [], [], []
                    q_cnt, e_cnt = [0] * D, [0] * D
                    for s in range(D):
                        prev = shadow.log_n[s]
                        nn = int(log_n[s]) - prev
                        if nn > 0:
                            n0 = int(n_init_arr[s])
                            q_idx.append(np.arange(
                                s * qloc + n0 + prev,
                                s * qloc + n0 + prev + nn, dtype=np.int32))
                            l_idx.append(np.arange(
                                s * closc + prev, s * closc + prev + nn,
                                dtype=np.int32))
                            q_cnt[s] = nn
                        if eloc:
                            pe = shadow.e_n[s]
                            ne = int(e_n[s]) - pe
                            if ne > 0:
                                e_idx.append(np.arange(
                                    s * eloc + pe, s * eloc + pe + ne,
                                    dtype=np.int32))
                                e_cnt[s] = ne
                    empty = np.zeros((0,), np.int32)
                    q_new = gather_rows(
                        carry.q, np.concatenate(q_idx) if q_idx else empty)
                    l_new = gather_rows(
                        carry.log,
                        np.concatenate(l_idx) if l_idx else empty)
                    e_new = (gather_rows(
                        carry.elog,
                        np.concatenate(e_idx) if e_idx else empty)
                        if eloc else None)
                    # --- silent-corruption defense (AuditPolicy) ------
                    # injection + audit run on the gathered host copies
                    # BEFORE any shard folds into the shadow, so a
                    # caught lie never enters the mirror; the audit of
                    # shard s's slice re-executes on the NEXT device in
                    # the mesh (cross-device redundant execution — a
                    # lying chip cannot vouch for its own rows), with
                    # the host oracle answering on a one-shard mesh
                    lie_at = (corrupt_hook(ordinal, D)
                              if corrupt_hook is not None else None)
                    if lie_at is not None and lie_at is not False \
                            and q_cnt[int(lie_at)]:
                        s = int(lie_at)
                        o0 = sum(q_cnt[:s])
                        q_new = q_new.copy()
                        l_new = l_new.copy()
                        width = model.packed_width
                        q_new[o0:o0 + q_cnt[s], width + 1] ^= np.uint32(1)
                        l_new[o0:o0 + q_cnt[s], 0] ^= np.uint32(1)
                    audited = audit_pol.should_audit(ordinal)
                    if audited:
                        self._metrics.inc("audits")
                        mesh_devs = list(mesh.devices.flat)
                        qo = 0
                        for s in range(D):
                            nn = q_cnt[s]
                            bad = audit_chunk_rows(
                                q_new[qo:qo + nn], l_new[qo:qo + nn],
                                model.packed_width, sound=self._sound,
                                device=(mesh_devs[(s + 1) % D]
                                        if D > 1 else None))
                            if self._trace:
                                self._trace.emit(
                                    "audit", chunk=ordinal, rows=nn,
                                    mismatches=bad, device=s)
                            if bad:
                                self._metrics.inc("audit_mismatches")
                                raise CorruptionError(
                                    f"chunk {ordinal} audit: {bad} of "
                                    f"{nn} frontier fingerprints from "
                                    f"shard {s} disagree with their "
                                    "re-execution on "
                                    + ("the host oracle" if D == 1 else
                                       f"device {(s + 1) % D}")
                                    + " — the chip is returning wrong "
                                    "results", device_index=s,
                                    mismatches=bad)
                            qo += nn
                    qo = eo = 0
                    hits = 0
                    for s in range(D):
                        nn, ne = q_cnt[s], e_cnt[s]
                        hits += shadow.note_chunk(
                            s, q_new[qo:qo + nn], l_new[qo:qo + nn],
                            (e_new[eo:eo + ne] if eloc else None),
                            int(q_head[s]))
                        qo += nn
                        eo += ne
                    if audited:
                        # a PASSED audit pins the rollback boundary and
                        # (unlike a successful sync, which a lying chip
                        # passes happily) resets the consecutive-
                        # corruption budget
                        shadow.audit_mark()
                        corruption_attempt = 0
                    self._shadow_chain_head = shadow.chain_head
                    if hits:
                        # host-tier re-probe hits: rediscoveries of
                        # evicted ranges, excluded from unique counts
                        self._metrics.inc("host_probe_hits", hits)
                        self._metrics.set("host_tier_keys",
                                          shadow.host_tier_keys)
                if (self._autosave_path is not None
                        and self._autosave_every > 0
                        and ordinal % self._autosave_every == 0):
                    self._write_autosave(shadow, discoveries)
            shard_new = log_n - cur["log_n"]  # per-shard fresh inserts
            metrics = self._metrics
            # rows this chunk expanded, over the shards: each head only
            # advances (a re-seed restarts them at 0, and cur with them)
            metrics.inc("expanded", int((q_head - cur["q_head"]).sum()))
            metrics.inc("materialized", mat)
            cur.update(q_head=q_head, q_tail=q_tail, log_n=log_n,
                       e_n=e_n)
            metrics.observe_max("vmax", vmax)
            metrics.observe_max("dmax", dmax)
            # dedup telemetry: chunk-local (reset at dispatch, so a
            # zero-iteration speculative chunk contributes 0)
            if pdh:
                metrics.inc("predup_hits", pdh)
            if prb:
                metrics.inc("probe_rounds", prb)
            if size_key is not None:
                _SIZE_MEMO.merge_max(size_key, (vmax, dmax))
            self._state_count += gen
            # with the shadow on, len(generated) is authoritative (and
            # past a spill the per-shard logs include host-filtered
            # rediscoveries, so the sum would over-count)
            self._unique_state_count = (len(generated)
                                        if shadow is not None
                                        else base_unique
                                        + int(log_n.sum()))
            trace = self._trace
            if trace:
                new = int(shard_new.sum())
                trace.emit(
                    "chunk", chunk=ordinal,
                    gen=gen, unique=self._unique_state_count,
                    q_size=int((q_tail - q_head).sum()), new=new,
                    dedup_hit=(round(1.0 - new / gen, 4)
                               if gen else 0.0),
                    load=round(int(log_n.max()) / (self._capacity // D),
                               4),
                    vmax=vmax, dmax=dmax, bmax=bmax,
                    # per-shard balance/exchange volumes: states each
                    # owner shard inserted this chunk, plus its live
                    # queue depth
                    shard_new=[int(x) for x in shard_new],
                    shard_q=[int(x) for x in (q_tail - q_head)])
            disc_fps = _combine64(disc_hi, disc_lo)
            for i, prop in enumerate(properties):
                if i in host_prop_idx:
                    continue  # device bits are placeholders
                if disc_hit[i] and prop.name not in discoveries:
                    discoveries[prop.name] = int(disc_fps[i])
                    self._note_discovery(prop.name, int(disc_fps[i]))
            if xovf:
                from ..checker.tpu import _XOVF_MESSAGE
                raise RuntimeError(_XOVF_MESSAGE)
            if ovf:
                raise RuntimeError(
                    "device hash table probe overflow below the growth "
                    f"limit (capacity {self._capacity}); raise via "
                    "checker_builder.tpu_options(capacity=...)")
            if self._host_props and any(
                    p.name not in discoveries
                    for _i, p in self._host_props):
                with self._spans.span("props", chunk=ordinal):
                    # the reduction is pinned to THIS chunk's per-shard
                    # queue tails: under pipelining the live carry
                    # already holds the next chunk's appends, and
                    # evaluating them early could report a different
                    # (later) witness than the synchronous path
                    self._posthoc_sharded(carry, qcap, n_init_arr,
                                          discoveries,
                                          q_tail_h=q_tail)
            if kovf:
                kovf_pend[0] = max(kovf_pend[0], vmax)
                kovf_pend[1] = max(kovf_pend[1], dmax)
                kovf_pend[2] = max(kovf_pend[2], bmax)
                acts.add("kovf")
                return acts
            if (int((q_tail - q_head).sum()) == 0
                    or len(discoveries) == prop_count
                    or (target is not None
                        and self._state_count >= target)
                    or self._cancel_event.is_set()
                    or self._pause_event.is_set()):
                acts.add("done")
                return acts
            if self._promote_event.is_set() and shadow is not None:
                # elastic scale-up request (request_promote): surface
                # it as an act so the intervention path below drains
                # the double-buffered pipeline before the mesh widens
                acts.add("promote")
            need_grow = (int(log_n.max()) >= grow_limit
                         or int(q_tail.max()) > qcap // D - headroom)
            if need_grow:
                acts.add("grow")
            elif ecap and int(e_n.max()) >= ecap // D - headroom:
                acts.add("egrow")
            return acts

        def handle_kovf() -> None:
            # a shard's batch outran one of the candidate buffers;
            # nothing was committed — resize the overflowed stage(s)
            # (vmax sizes kraw, dmax sizes kmax, bmax sizes the
            # bucketed exchange's kb) and resume
            nonlocal carry, chunk_fn, kraw, kmax, kb, headroom
            vmax, dmax, bmax = kovf_pend
            before = (kraw, kmax, kb)
            grew = False
            if vmax > kraw:
                kraw = min(max(kraw * 2,
                               -(-(vmax + vmax // 4) // 256) * 256),
                           fa)
                grew = True
            if exchange == "bucket":
                kb_now = effective_kb(kmax, D, kb)
                if bmax > kb_now:
                    kb = min(kmax,
                             max(kb_now * 2,
                                 -(-(bmax + bmax // 4) // 256) * 256))
                    grew = True
            if dmax > kmax or not grew:
                kmax = min(max(kmax * 2,
                               -(-(dmax + dmax // 4) // 256) * 256),
                           kraw)
            kmax = min(kmax, kraw)
            headroom = max(D * kmax, fmax)
            if (kraw, kmax, kb) == before:
                # wedged pre-mutation abort: rebuilding the identical
                # program would abort forever — reclassify as a
                # capacity fault; the retry envelope recovers with a
                # k-buffer grown to its bound (the kovf abort never
                # surfaces to the user)
                from ..checker.resilience import CandidateOverflowError
                raise CandidateOverflowError(
                    "candidate-buffer capacity overflow (kovf) wedged "
                    f"at kraw={kraw} kmax={kmax} kb={kb} (observed "
                    f"vmax={vmax} dmax={dmax} bmax={bmax})",
                    vmax=vmax, dmax=dmax, bmax=bmax)
            self._metrics.inc("kovfs")
            if self._trace:
                self._trace.emit("kovf", kraw=kraw, kmax=kmax, kb=kb,
                                 vmax=kovf_pend[0], dmax=kovf_pend[1],
                                 bmax=kovf_pend[2])
            kovf_pend[:] = [0, 0, 0]
            chunk_fn = rebuild_chunk("kovf")
            carry = carry._replace(kovf=jnp.bool_(False))

        def handle_egrow() -> None:
            # cross-edge log full: grow JUST the shard-local elog
            # (cross edges scale with transitions, not states — a full
            # capacity/table/queue regrow would inflate every buffer
            # toward O(edges))
            nonlocal carry, chunk_fn, ecap
            with self._spans.span("grow"):
                from jax.sharding import (NamedSharding,
                                          PartitionSpec as P)
                old_eloc = ecap // D
                ecap *= 4
                eloc = ecap // D
                elog_h, en_h = self._pull_global(
                    (carry.elog, carry.e_n))
                new_elog = np.zeros((ecap, 4), np.uint32)
                for s in range(D):
                    en = int(en_h[s])
                    new_elog[s * eloc:s * eloc + en] = \
                        elog_h[s * old_eloc:s * old_eloc + en]
                sh = NamedSharding(mesh, P(axis))
                carry = carry._replace(
                    elog=jax.device_put(new_elog, sh))
            if self._trace:
                self._trace.emit("egrow", ecap=ecap)
            chunk_fn = rebuild_chunk("egrow")

        def handle_grow() -> None:
            nonlocal carry, chunk_fn, qcap, ecap
            self._metrics.inc("grows")
            with self._spans.span("grow"):
                carry, qcap = self._grow_sharded(
                    carry, qcap, n_init, headroom, table_fps, insert_fn)
            if ecap:
                ecap = max(self._capacity, ecap)
            if self._trace:
                self._trace.emit("grow", capacity=self._capacity,
                                 qcap=qcap)
            chunk_fn = rebuild_chunk("grow")

        def reseed() -> None:
            # post-fault recovery: rebuild the sharded device state
            # from the shadow — the pending frontier re-routes by owner
            # on this mesh exactly like a checkpoint resume, the table
            # re-seeds from the complete host mirror, and the chunk
            # program recompiles. Set-semantics dedup makes the rebuilt
            # run explore exactly the remaining graph.
            nonlocal carry, chunk_fn, qcap, ecap, n_init, n_init_arr, \
                base_unique, table_fps, preload
            rows, ebs, fps = shadow.pending()
            init_rows2 = [rows[i] for i in range(rows.shape[0])]
            cache2 = [int(f) for f in fps]
            if self._sound:
                from ..fingerprint import fp64_node
                frontier2 = [fp64_node(int(f), int(e))
                             for f, e in zip(fps, ebs)]
            else:
                frontier2 = cache2
            n_init = len(init_rows2)
            # the device tables re-seed with the HOT set only (== the
            # whole mirror until ranges have been evicted): a recovery
            # must not re-promote what a spill moved to the host tier
            table_fps = shadow.hot_keys()
            base_unique = len(generated)
            preload = len(table_fps)
            while self._grow_at * (self._capacity // D) \
                    <= headroom + preload \
                    and spill_pol.can_grow(self._capacity):
                self._capacity *= 4
            if self._grow_at * (self._capacity // D) \
                    <= headroom + preload:
                plan = (shadow.spill_plan(
                    int(self._grow_at * (self._capacity // D))
                    - headroom - 1) if spill_on else None)
                if plan is None:
                    self._capacity_terminal(RuntimeError(
                        "sharded table budget (max_capacity="
                        f"{spill_pol.max_capacity}) cannot hold the "
                        f"re-seeded hot set ({preload} keys)"),
                        shadow, discoveries)
                table_fps = shadow.hot_keys()
                preload = len(table_fps)
                self._metrics.inc("spills")
                if plan[2]:
                    self._metrics.inc("evicted_keys", plan[2])
                self._metrics.set("host_tier_keys",
                                  shadow.host_tier_keys)
                if self._trace:
                    self._trace.emit("evict", prefixes=len(plan[0]),
                                     keys=plan[2])
                    self._trace.emit(
                        "spill", capacity=self._capacity,
                        hot=preload, reason="reseed",
                        host_tier_keys=shadow.host_tier_keys)
            init_by_shard2: List[List[int]] = [[] for _ in range(D)]
            for fp in frontier2:
                init_by_shard2[owner_of(fp, D)].append(fp)
            self._init_by_shard = init_by_shard2
            n_init_arr = np.asarray([len(b) for b in init_by_shard2],
                                    np.int32)
            qcap = self._sharded_qcap(
                max((len(b) for b in init_by_shard2), default=0),
                headroom, D)
            if self._sound:
                ecap = max(ecap, self._capacity)
            with self._spans.span("seed"):
                carry2 = seed_sharded_carry(
                    model, mesh, axis, qcap, self._capacity, init_rows2,
                    frontier2, np.asarray(ebs, np.uint32), prop_count,
                    symmetry=self._symmetry, sound=self._sound,
                    cache_fps=cache2, ecap=ecap)
                key_hi, key_lo = self._sharded_bulk_insert(
                    insert_fn, carry2.key_hi, carry2.key_lo, table_fps,
                    D)
                carry = carry2._replace(key_hi=key_hi, key_lo=key_lo)
            seed_shadow_epoch(init_rows2, frontier2, ebs, cache2)
            cur.update(q_head=np.zeros(D, np.int64),
                       q_tail=n_init_arr.astype(np.int64),
                       log_n=np.zeros(D, np.int64),
                       e_n=np.zeros(D, np.int64))
            kovf_pend[:] = [0, 0, 0]
            chunk_fn = rebuild_chunk(recover_reason)

        spill_warned = [False]

        def warn_spill_eventually() -> None:
            # see the single-chip twin (checker/tpu.py): unsound
            # EVENTUALLY verdicts are path-dependent across a spill
            if spill_warned[0] or self._sound:
                return
            from ..core import Expectation
            if any(p.expectation == Expectation.EVENTUALLY
                   for p in properties):
                import warnings
                warnings.warn(
                    "memory tiering with (unsound) eventually "
                    "properties: rediscovered duplicates re-enter the "
                    "frontier with rediscovery-path pending bits, so "
                    "eventually verdicts may differ from an uncapped "
                    "run", RuntimeWarning, stacklevel=2)
            spill_warned[0] = True

        def handle_spill(reason: str = "budget") -> None:
            # growth would exceed the HBM budget: evict the coldest
            # prefix ranges (owner-consistent by construction — top-bit
            # prefixes nest inside top-bit shard ownership) and re-seed
            # each shard's table with its share of the hot set; the
            # pending frontier re-routes exactly like a recovery
            nonlocal recover_reason
            occupancy = preload + int(cur["log_n"].sum())
            closc = self._capacity // D
            if int(min(self._grow_at * closc, closc - headroom)) <= 0:
                # even empty shard tables cannot fit one iteration's
                # headroom under this budget: spilling would spin
                self._capacity_terminal(RuntimeError(
                    f"sharded table budget (per-shard {closc}) cannot "
                    f"fit one iteration's headroom ({headroom}) — "
                    "raise tpu_options(max_capacity=...) or shrink "
                    "fmax/kmax"), shadow, discoveries)
            hot_budget = max(0, min(
                int((1.0 - spill_pol.frac) * occupancy),
                int(self._grow_at * closc) - headroom - 1))
            plan = shadow.spill_plan(hot_budget)
            if plan is None:
                self._capacity_terminal(RuntimeError(
                    "host tier exhausted: range eviction cannot bring "
                    f"the sharded table (capacity {self._capacity}) "
                    "under its growth budget"), shadow, discoveries)
            warn_spill_eventually()
            self._metrics.inc("spills")
            if plan[2]:
                self._metrics.inc("evicted_keys", plan[2])
            self._metrics.set("host_tier_keys", shadow.host_tier_keys)
            if self._trace:
                self._trace.emit("evict", prefixes=len(plan[0]),
                                 keys=plan[2])
                self._trace.emit("spill", capacity=self._capacity,
                                 hot=plan[1], reason=reason,
                                 host_tier_keys=shadow.host_tier_keys)
            recover_reason = "spill"
            with self._spans.span("spill"):
                reseed()

        def degrade_step(blamed, exc) -> bool:
            # one ladder rung (checker/resilience.py DegradePolicy):
            # halve the mesh onto the surviving power-of-two device
            # subset — dropping the blamed chip when the fault names
            # one — and resume from the shadow; the reseed that follows
            # re-routes the pending frontier by owner_of(fp, D/2) and
            # recomputes the preload-aware growth limits at the new D,
            # exactly like a cross-mesh checkpoint resume. Returns True
            # when the next rung is the single-chip device loop
            # (checker/tpu.py shadow handoff).
            nonlocal mesh, D, insert_fn, headroom, size_key
            from ..cluster.mesh import device_host
            new_d = D // 2
            devs = list(mesh.devices.flat)
            host_map = opts.get("host_map")
            labels = [device_host(dv, host_map) for dv in devs]
            hosts_before = set(labels)
            # survivor selection is shared with promote_step
            # (checker/resilience.py) so the ladder's two directions
            # cannot drift: a real PJRT fault names the GLOBAL device
            # id, an injected one may name the mesh position (id match
            # first, position fallback); on a multi-host mesh the HOST
            # RUNG takes the blamed chip's whole host down the ladder
            # (DCN partitions and host deaths fault every chip behind
            # that NIC) — the survivors stay host-major, so the halved
            # mesh stays host-aligned and the owner_of(fp, D/2)
            # re-route is exactly the chip rung's math
            pos = match_device(devs, blamed)
            keep = select_survivors(devs, new_d, blamed_pos=pos,
                                    labels=labels)
            hosts_after = {device_host(dv, host_map) for dv in keep}
            self._metrics.inc("degrades")
            self._metrics.set("mesh_shards", new_d)
            self._metrics.set("hosts", len(hosts_after))
            if self._trace:
                self._trace.emit(
                    "degrade", from_shards=D, to_shards=new_d,
                    device=blamed,
                    error=f"{type(exc).__name__}: {exc}")
                for h in sorted(hosts_before - hosts_after, key=str):
                    self._trace.emit("host_drop", host=h,
                                     from_shards=D, to_shards=new_d,
                                     device=blamed)
            # each rung is a postmortem-worthy incident even though the
            # run survives it: land the ring (the final error dump, if
            # the ladder too fails, overwrites this with a superset)
            self._flight_dump("degrade")
            attributor.clear()
            if new_d == 1:
                # final rung: the plain single-chip loop adopts the
                # shadow (pending frontier + run-spanning records)
                rows, ebs, fps = shadow.pending()
                self._handoff = (
                    [rows[i] for i in range(rows.shape[0])],
                    np.asarray(ebs, np.uint32),
                    [int(f) for f in fps],
                    dict(discoveries))
                self._handoff_shadow = shadow
                self._handoff_device = keep[0] if keep else None
                return True
            from jax.sharding import Mesh
            mesh = self._mesh = Mesh(np.asarray(keep), (axis,))
            D = new_d
            self._fault_shards = D
            insert_fn = build_sharded_insert(mesh, axis)
            headroom = max(D * kmax, fmax)
            mk = model_cache_key(model)
            size_key = ((mk, fmax, self._sound, self._symmetry, D)
                        if mk is not None else None)
            shadow.reshard(D)
            return False

        def promote_step() -> bool:
            # the scale-UP mirror of degrade_step (one rung back up the
            # elastic ladder): at a drained chunk boundary, extend the
            # mesh with D of the granted devices, re-route the shadow's
            # mirror + pending frontier by owner_of(fp, 2D) with the
            # preload-aware growth limits recomputed at the new width,
            # recompile, and resume D -> 2D. The reseed that follows is
            # exactly a cross-mesh checkpoint resume, so it composes
            # with spill tiering for free: evicted prefix ranges
            # (SPILL_PREFIX_BITS top bits) re-nest inside the wider
            # shard ownership and stay on the host tier. A grant that
            # cannot double the mesh is declined quietly — the run
            # resumes at the old width rather than dying mid-flight.
            nonlocal mesh, D, insert_fn, headroom, size_key, ecap, \
                recover_reason
            grant_refs = self._promote_request
            self._promote_request = None
            self._promote_event.clear()
            if not grant_refs or shadow is None:
                return False
            new_d = D * 2
            if new_d > MAX_MESH_SHARDS:
                return False
            devs = list(mesh.devices.flat)
            grant = resolve_grant(jax.devices(), grant_refs,
                                  exclude=devs)
            if len(grant) < D:
                return False  # doubling needs D fresh distinct chips
            grant = grant[:D]
            # budget viability at the new width: doubling the mesh
            # doubles the kmax share of the per-iteration headroom,
            # and under a tight HBM budget (max_capacity) the wider
            # mesh may no longer fit that headroom below each shard's
            # growth limit — decline rather than trade a viable narrow
            # run for a capacity-terminal wide one (the same bound
            # handle_spill treats as terminal)
            cap = self._capacity
            new_head = max(new_d * kmax, fmax)
            while self._grow_at * (cap // new_d) <= new_head + 1 \
                    and spill_pol.can_grow(cap):
                cap *= 4
            if self._grow_at * (cap // new_d) <= new_head + 1:
                return False
            from ..cluster.mesh import device_host, host_major
            hosts_before = {device_host(dv, host_map) for dv in devs}
            # host-major so a join lands host-aligned: a later HOST
            # RUNG can drop the joined host as a contiguous block
            keep = host_major(devs + grant, host_map)
            hosts_after = {device_host(dv, host_map) for dv in keep}
            while self._capacity % new_d:
                self._capacity *= 2
            while ecap and ecap % new_d:
                ecap *= 2
            self._metrics.inc("promotes")
            self._metrics.set("mesh_shards", new_d)
            self._metrics.set("hosts", len(hosts_after))
            if self._trace:
                self._trace.emit(
                    "promote", from_shards=D, to_shards=new_d,
                    devices=[getattr(dv, "id", None) for dv in grant])
                for h in sorted(hosts_after - hosts_before, key=str):
                    self._trace.emit("host_promote", host=h,
                                     from_shards=D, to_shards=new_d)
            # the blame streak was pinned at the old width; a fresh
            # mesh must not inherit it (mirrors the taken-rung clear)
            attributor.clear()
            from jax.sharding import Mesh
            mesh = self._mesh = Mesh(np.asarray(keep), (axis,))
            D = new_d
            self._fault_shards = D
            insert_fn = build_sharded_insert(mesh, axis)
            headroom = max(D * kmax, fmax)
            mk = model_cache_key(model)
            size_key = ((mk, fmax, self._sound, self._symmetry, D)
                        if mk is not None else None)
            shadow.reshard(D)
            recover_reason = "promote"
            with self._spans.span("promote"):
                reseed()
            return True

        fault_attempt = 0
        spill_attempt = 0
        corruption_attempt = 0
        recover_delay = None
        recover_reason = "retry"
        handoff_rung = False
        while True:
            try:
                if recover_delay is not None:
                    # back off before touching the mesh again; the
                    # reseed runs inside the retry envelope, so a
                    # still-dead backend burns another attempt
                    if recover_delay > 0:
                        time.sleep(recover_delay)
                    recover_delay = None
                    reseed()
                dispatch()
                while True:
                    if pipeline and len(inflight) == 1:
                        dispatch()
                    acts = process(*inflight.popleft())
                    if not acts:
                        if not inflight:
                            dispatch()
                        yield  # step boundary: one chunk consumed
                        continue
                    # drain the speculative chunk before any host
                    # intervention: under a device-visible stop
                    # condition it ran zero iterations; past a host-only
                    # exit it is one extra chunk of real (merged)
                    # exploration
                    while inflight:
                        acts |= process(*inflight.popleft())
                    if "kovf" in acts:
                        handle_kovf()
                    elif "done" in acts:
                        break
                    elif "promote" in acts:
                        # widen before considering growth: the promote
                        # reseed re-runs the preload-aware grow loop at
                        # the new width, subsuming a pending "grow"; a
                        # declined grant resumes at the old width and
                        # the next chunk re-raises any growth pressure
                        promote_step()
                    elif "grow" in acts:
                        # budget-aware growth: grow while the HBM
                        # budget allows, spill to the host tier once
                        # it does not
                        if spill_pol.can_grow(self._capacity):
                            handle_grow()
                        elif spill_on and shadow is not None:
                            handle_spill("budget")
                        else:
                            self._capacity_terminal(RuntimeError(
                                "sharded table growth past tpu_options("
                                f"max_capacity={spill_pol.max_capacity})"
                                " needed and spill is disabled"),
                                shadow, discoveries)
                    elif "egrow" in acts:
                        handle_egrow()
                    dispatch()
                    yield  # step boundary: intervention handled
                break
            except BaseException as exc:
                if shadow is None:
                    raise
                kind = classify_error(exc)
                if kind is FaultKind.CAPACITY:
                    # capacity fault in the retry envelope: spill (or
                    # grow the k-buffer for a wedged kovf) and re-seed;
                    # ineligible faults and an exhausted spill budget
                    # take the capacity-terminal ending
                    if not (spill_on and spill_eligible(exc)):
                        self._capacity_terminal(exc, shadow, discoveries)
                    inflight.clear()
                    spill_attempt += 1
                    if spill_attempt > spill_pol.max_spills:
                        self._capacity_terminal(exc, shadow, discoveries)
                    cand = find_candidate_overflow(exc)
                    if cand is not None:
                        # the kovf pre-mutation abort re-routes here
                        # with a GROWN k-buffer instead of raising to
                        # the user
                        kraw = fa
                        kmax = min(max(kmax * 2, cand.dmax
                                       + cand.dmax // 4), fa)
                        if exchange == "bucket" and cand.bmax:
                            kb = min(kmax, max(
                                effective_kb(kmax, D, kb),
                                cand.bmax + cand.bmax // 4))
                        headroom = max(D * kmax, fmax)
                        self._metrics.inc("kovfs")
                        if self._trace:
                            self._trace.emit("kovf", kraw=kraw,
                                             kmax=kmax, kb=kb,
                                             recovered=True)
                        recover_reason = "kovf"
                    else:
                        # the backend named the budget: clamp growth at
                        # the current capacity and spill
                        if spill_pol.max_capacity is None \
                                or spill_pol.max_capacity > self._capacity:
                            spill_pol.max_capacity = self._capacity
                        closc = self._capacity // D
                        plan = shadow.spill_plan(max(0, min(
                            int((1.0 - spill_pol.frac)
                                * self._grow_at * closc),
                            int(self._grow_at * closc)
                            - headroom - 1)))
                        if plan is None:
                            self._capacity_terminal(exc, shadow,
                                                    discoveries)
                        warn_spill_eventually()
                        self._metrics.inc("spills")
                        if plan[2]:
                            self._metrics.inc("evicted_keys", plan[2])
                        self._metrics.set("host_tier_keys",
                                          shadow.host_tier_keys)
                        if self._trace:
                            self._trace.emit("evict",
                                             prefixes=len(plan[0]),
                                             keys=plan[2])
                            self._trace.emit(
                                "spill", capacity=self._capacity,
                                hot=plan[1], reason="fault",
                                host_tier_keys=shadow.host_tier_keys,
                                error=f"{type(exc).__name__}: {exc}")
                        recover_reason = "spill"
                    recover_delay = 0.0
                    continue
                if kind is FaultKind.CORRUPTION:
                    # a sampled audit caught a chip returning wrong
                    # fingerprints: undo every fold since the last
                    # audited boundary (the corrupt appends never reach
                    # the final digest), quarantine the liar for the
                    # fleet (service/scheduler.py withholds it from all
                    # future grants), and take the ladder DOWN a rung
                    # immediately — retrying on silicon that computes
                    # wrong answers is worse than useless
                    inflight.clear()
                    blamed = blamed_device(exc)
                    devs = list(mesh.devices.flat)
                    pos = match_device(devs, blamed)
                    qid = (getattr(devs[pos], "id", pos)
                           if pos is not None
                           else (blamed if blamed is not None else 0))
                    self._quarantined.add(qid)
                    self._metrics.set(
                        "fault_device",
                        blamed if blamed is not None else 0)
                    self._metrics.set("quarantined",
                                      len(self._quarantined))
                    shadow.rollback_to_mark()
                    self._unique_state_count = len(generated)
                    if self._trace:
                        self._trace.emit(
                            "corruption", device=blamed,
                            error=f"{type(exc).__name__}: {exc}")
                        self._trace.emit(
                            "quarantine", device=qid,
                            quarantined=len(self._quarantined))
                    attributor.note(blamed)
                    if ladder.enabled and D > ladder.min_mesh:
                        if degrade_step(blamed, exc):
                            handoff_rung = True
                            break
                        fault_attempt = 0
                        recover_delay = 0.0
                        recover_reason = "degrade"
                        continue
                    # no rung below this mesh: bounded replay from the
                    # audited boundary on the same silicon (the counter
                    # only resets on a PASSED audit, so a persistent
                    # liar cannot loop forever)
                    if corruption_attempt >= max(1, policy.retries):
                        self._flight_dump("corruption")
                        raise RuntimeError(
                            "chunk audit failed "
                            f"{corruption_attempt + 1} consecutive "
                            "times with no healthy mesh subset to "
                            "degrade onto — the chip is persistently "
                            "returning wrong results; replace the "
                            "device or widen the mesh so the "
                            "degradation ladder can route around it"
                        ) from exc
                    corruption_attempt += 1
                    recover_delay = 0.0
                    recover_reason = "retry"
                    continue
                if kind is not FaultKind.TRANSIENT:
                    raise
                inflight.clear()
                blamed = blamed_device(exc)
                if blamed is not None:
                    ids = [getattr(d, "id", None)
                           for d in mesh.devices.flat]
                    if blamed not in ids and not 0 <= blamed < D:
                        blamed = None  # names no chip on this mesh
                if blamed is not None:
                    self._metrics.set("fault_device", blamed)
                # the ladder drops a rung when the retry budget is
                # spent on this mesh, or sooner when the blame streak
                # pins the faults on one chip (beating the rest of the
                # budget on a dead device is pure waste)
                exhausted = fault_attempt >= policy.retries
                offender = attributor.note(blamed)
                if (ladder.enabled and D > ladder.min_mesh
                        and (exhausted or offender)):
                    if degrade_step(blamed, exc):
                        handoff_rung = True
                        break
                    fault_attempt = 0
                    recover_delay = 0.0
                    recover_reason = "degrade"
                    continue
                if exhausted:
                    self._resilience_degrade(exc, shadow, discoveries)
                fault_attempt += 1
                recover_delay = policy.delay(fault_attempt)
                recover_reason = "retry"
                self._metrics.inc("retries")
                if self._trace:
                    self._trace.emit(
                        "retry", attempt=fault_attempt,
                        delay=round(recover_delay, 3),
                        error=f"{type(exc).__name__}: {exc}",
                        device=blamed, shards=D)
        if handoff_rung:
            # the ladder's last rung: run the plain single-chip device
            # loop (checker/tpu.py) on the surviving chip, seeded from
            # the shadow handoff. Its own retry envelope (and the
            # shadow-spanning lasso sweep / resumable-frontier /
            # mirror post-passes) take over from here — driven through
            # the same generator so a stepped/paused run stays
            # responsive across the handoff.
            self._fault_shards = 1
            dev = self._handoff_device
            ctx = (jax.default_device(dev) if dev is not None
                   else nullcontext())
            with ctx:
                yield from self._drive_device()
            if self._visitor is not None and not self._paused:
                with self._spans.span("visit"):
                    self._visit_reached()
            return

        # the engine's tail after its last chunk, up to done
        with self._spans.span("finish"):
            q_head, q_tail = cur["q_head"], cur["q_tail"]
            log_n, e_n = cur["log_n"], cur["e_n"]
            if int(log_n.max()):
                # end-of-run shard balance: min/max per-shard inserted
                # states (1.0 = perfectly balanced fingerprint routing)
                self._metrics.set(
                    "shard_balance",
                    round(float(int(log_n.min()) / int(log_n.max())), 4))

            if (self._pause_event.is_set()
                    and not self._cancel_event.is_set()
                    and int((q_tail - q_head).sum()) > 0
                    and len(discoveries) < prop_count
                    and not (target is not None
                             and self._state_count >= target)):
                # pause exit (the run did NOT finish): the pipeline drained
                # above; checkpoint the complete mirror + pending frontier
                # in the shard-agnostic single-chip format, so the job
                # resumes on ANY mesh width (preemption onto a smaller
                # subset rides the same machinery as a cross-mesh resume)
                if shadow is not None:
                    p_rows, p_ebs, p_fps = shadow.pending()
                else:
                    self._finalize_sharded(carry)
                    self._ensure_mirror()
                    qloc = qcap // D
                    width = model.packed_width
                    q_h, qh, qt = self._pull_global(
                        (carry.q, carry.q_head, carry.q_tail))
                    pend = np.concatenate(
                        [q_h[s * qloc + int(qh[s]):s * qloc + int(qt[s])]
                         for s in range(D)])
                    p_rows = pend[:, :width]
                    p_ebs = pend[:, width]
                    p_fps = _combine64(pend[:, width + 1],
                                       pend[:, width + 2])
                self._write_pause_checkpoint(p_rows, p_ebs, p_fps,
                                             discoveries)
                self._discovery_fps.update(discoveries)
                return

            if (self._sound and int((q_tail - q_head).sum()) == 0
                    and self._resume_path is not None):
                import warnings
                warnings.warn(
                    "resume_from() + sound_eventually(): the post-exhaustion "
                    "lasso sweep is SKIPPED on resumed runs (the "
                    "pre-checkpoint subgraph's edges are not in this run's "
                    "device logs), so liveness cycles entered through "
                    "pre-checkpoint states go unreported. Re-run without "
                    "resume_from() for a cycle-complete liveness verdict.",
                    RuntimeWarning, stacklevel=2)
            if (self._sound and int((q_tail - q_head).sum()) == 0
                    and self._resume_path is None and not self._symmetry):
                # (not under symmetry — cross-branch witnesses cannot replay
                # through concrete orbit members; see the single-chip sweep)
                # full exhaustion under sound mode: merged lasso sweep over
                # every shard's node graph (insert edges from the per-shard
                # logs, cross edges from the per-shard edge logs) — the
                # sharded twin of TpuChecker._device_lasso_sweep
                with self._spans.span("lasso"):
                    if shadow is not None:
                        # after a mid-run recovery the device logs cover
                        # only the last epoch; the shadow spans the run
                        self._shadow_lasso_sweep(shadow, int(full_ebits),
                                                 discoveries)
                    else:
                        self._sharded_lasso_sweep(carry, qcap, q_tail,
                                                  log_n, e_n, discoveries,
                                                  int(full_ebits))

            if self._tpu_options.get("resumable"):
                # pull the pending per-shard frontiers eagerly so save()
                # needs no pinned device buffers; the checkpoint format is
                # the single-chip one (shard-agnostic)
                qloc = qcap // D
                width = model.packed_width
                q_h, qh, qt = self._pull_global(
                    (carry.q, carry.q_head, carry.q_tail))
                pend_l = [q_h[s * qloc + int(qh[s]):s * qloc + int(qt[s])]
                          for s in range(D)]
                pend = np.concatenate(pend_l)
                self._resume_frontier = (
                    pend[:, :width].copy(), pend[:, width].copy(),
                    _combine64(pend[:, width + 1], pend[:, width + 2]))
            if shadow is not None:
                # the shadow-maintained host mirror is already complete
                self._mirror_carry = None
            else:
                self._finalize_sharded(carry)
            self._discovery_fps.update(discoveries)
            if self._visitor is not None:
                # same post-hoc visitation as the single-chip engine; the
                # global interleaving of per-shard insertion orders is
                # unspecified, like the reference's multithreaded visitors
                with self._spans.span("visit"):
                    self._visit_reached()

    def _sharded_qcap(self, n_init: int, headroom: int, d: int) -> int:
        """Append-only per-shard queues: a shard's tail never exceeds its
        seed count plus its log growth limit plus one iteration."""
        closc = self._capacity // d
        grow_limit = int(min(self._grow_at * closc, closc - headroom))
        return (n_init + grow_limit + 2 * headroom) * d

    # ------------------------------------------------------------------
    def _sharded_bulk_insert(self, insert_fn, key_hi, key_lo,
                             fps: List[int], d: int):
        """Route fingerprints to their owner shards' blocks and insert."""
        per_shard: List[List[int]] = [[] for _ in range(d)]
        for fp in fps:
            per_shard[owner_of(fp, d)].append(fp)
        n = max(1, max(len(b) for b in per_shard))
        n = 1 << (n - 1).bit_length()
        fhi = np.zeros((d * n,), dtype=np.uint32)
        flo = np.zeros((d * n,), dtype=np.uint32)
        valid = np.zeros((d * n,), dtype=bool)
        for s, block in enumerate(per_shard):
            arr = np.asarray(block, dtype=np.uint64)
            fhi[s * n:s * n + len(block)] = (arr >> np.uint64(32)).astype(
                np.uint32)
            flo[s * n:s * n + len(block)] = arr.astype(np.uint32)
            valid[s * n:s * n + len(block)] = True
        key_hi, key_lo, ovf = insert_fn(key_hi, key_lo, fhi, flo, valid)
        import jax
        if bool(jax.device_get(ovf)):
            raise RuntimeError(
                "device hash table overflow during sharded bulk insert")
        return key_hi, key_lo

    # ------------------------------------------------------------------
    def _grow_sharded(self, carry: ShardedCarry, qcap: int, n_init: int,
                      headroom: int, init_fps: List[int], insert_fn):
        """Quadruple the sharded table/log (and resize the queues): pull
        the carry, rebuild host-side preserving each shard's [0, tail)
        prefix at its positions (the prefix doubles as the shard's
        reached-set rows), re-insert every logged fingerprint into the
        fresh table slices device-side."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..ops.hashtable import _BUCKET

        mesh, axis = self._mesh, self._axis
        D = mesh.shape[axis]
        # pull only what the rebuild reads — NOT the old table halves,
        # which are discarded and re-derived from the logs
        (q_h, qh, qt, log_h, ln_h, elog_h, en_h, disc_hit, disc_hi,
         disc_lo, gen, xovf, steps) = self._pull_global(
            (carry.q, carry.q_head, carry.q_tail, carry.log,
             carry.log_n, carry.elog, carry.e_n, carry.disc_hit,
             carry.disc_hi, carry.disc_lo,
             carry.gen, carry.xovf, carry.steps))
        old_qloc = qcap // D
        old_closc = self._capacity // D
        old_eloc = elog_h.shape[0] // D
        sound_on = old_eloc > 0 and elog_h.shape[0] > D
        self._capacity *= 4
        new_qcap = self._sharded_qcap(n_init, headroom, D)
        qloc = new_qcap // D
        closc = self._capacity // D
        width = self._model.packed_width
        log_w = log_h.shape[1]

        q = np.zeros((new_qcap, width + 3), dtype=np.uint32)
        log = np.zeros((self._capacity, log_w), dtype=np.uint32)
        # the elog may have outgrown the main capacity via its own
        # standalone growth path — never shrink it here
        elog = np.zeros((max(self._capacity, D * old_eloc)
                         if sound_on else D, 4), dtype=np.uint32)
        eloc = elog.shape[0] // D
        for s in range(D):
            tail = int(qt[s])
            q[s * qloc:s * qloc + tail] = \
                q_h[s * old_qloc:s * old_qloc + tail]
            ln = int(ln_h[s])
            log[s * closc:s * closc + ln] = \
                log_h[s * old_closc:s * old_closc + ln]
            if sound_on:
                en = int(en_h[s])
                elog[s * eloc:s * eloc + en] = \
                    elog_h[s * old_eloc:s * old_eloc + en]

        sh = NamedSharding(mesh, P(axis))
        rep = NamedSharding(mesh, P())
        key_hi = jax.device_put(
            np.zeros((self._capacity // _BUCKET, _BUCKET), np.uint32), sh)
        key_lo = jax.device_put(
            np.zeros((self._capacity // _BUCKET, _BUCKET), np.uint32), sh)
        # rebuild the table device-side: each shard's log slice holds
        # exactly the fps it owns; only the init fps need host routing
        from .sharded import build_sharded_rebuild
        d_log = jax.device_put(log, sh)
        d_log_n = jax.device_put(ln_h, sh)
        key_hi, key_lo, r_ovf = build_sharded_rebuild(mesh, axis)(
            key_hi, key_lo, d_log, d_log_n)
        if bool(jax.device_get(r_ovf)):
            raise RuntimeError("overflow while re-inserting during growth")
        key_hi, key_lo = self._sharded_bulk_insert(
            insert_fn, key_hi, key_lo, init_fps, D)
        new_carry = ShardedCarry(
            q=jax.device_put(q, sh),
            q_head=jax.device_put(qh, sh),
            q_tail=jax.device_put(qt, sh),
            key_hi=key_hi, key_lo=key_lo,
            log=d_log,
            log_n=jax.device_put(ln_h, sh),
            elog=jax.device_put(elog, sh),
            e_n=jax.device_put(en_h, sh),
            disc_hit=jax.device_put(disc_hit, rep),
            disc_hi=jax.device_put(disc_hi, rep),
            disc_lo=jax.device_put(disc_lo, rep),
            gen=jax.device_put(gen, rep),
            ovf=jax.device_put(np.bool_(False), rep),
            xovf=jax.device_put(xovf, rep),
            kovf=jax.device_put(np.bool_(False), rep),
            vmax=jax.device_put(np.int32(0), rep),
            dmax=jax.device_put(np.int32(0), rep),
            bmax=jax.device_put(np.int32(0), rep),
            steps=jax.device_put(steps, rep),
            go=jax.device_put(np.bool_(False), rep),
            pavail=jax.device_put(np.int32(0), rep),
            pdh=jax.device_put(np.int32(0), rep),
            prb=jax.device_put(np.int32(0), rep))
        return new_carry, new_qcap

    # ------------------------------------------------------------------
    def _posthoc_sharded(self, carry: ShardedCarry, qcap: int,
                         n_init_arr, discoveries: Dict[str, int],
                         q_tail_h=None) -> None:
        """Host-property evaluation over each shard's reached set: local
        device dedup by host-property key, host merge across shards by
        key bytes (memoized), witness fps from the per-shard queue/log
        lockstep. ``q_tail_h`` (per-shard tails from a chunk's stats)
        pins the scanned queue prefixes to that chunk's appends — under
        the pipelined loop the live carry may already hold the NEXT
        chunk's rows, which must not be evaluated early."""
        import jax

        from .sharded import build_sharded_posthoc

        mesh, axis = self._mesh, self._axis
        D = mesh.shape[axis]
        model = self._model
        hmax = int(self._tpu_options.get("hmax", 1 << 13))
        shard_sharding = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(axis))
        n_init_d = jax.device_put(n_init_arr, shard_sharding)
        q_tail_d = (carry.q_tail if q_tail_h is None else jax.device_put(
            np.asarray(q_tail_h, np.int32), shard_sharding))
        while True:
            fn = build_sharded_posthoc(model, mesh, axis, qcap,
                                       self._capacity, hmax)
            (rows_d, src_d, whi_d, wlo_d, hcount_d, tovf, over) = fn(
                carry.q, q_tail_d, carry.log, n_init_d)
            hcount, tovf, over = self._pull_global(
                (hcount_d, tovf, over))
            if bool(tovf):
                raise RuntimeError(
                    "device hash table probe overflow during post-hoc "
                    "reduction; raise tpu_options(capacity=...)")
            if not bool(over):
                break
            hmax *= 2
        rows_h, src_h, whi_h, wlo_h = self._pull_global(
            (rows_d, src_d, whi_d, wlo_d))
        for s in range(D):
            hc = int(hcount[s])
            if not hc:
                continue
            if all(p.name in discoveries for _i, p in self._host_props):
                return
            wfp = _combine64(whi_h[s][:hc], wlo_h[s][:hc])
            inits = self._init_by_shard[s]
            fps = [inits[int(src_h[s][j])]
                   if int(src_h[s][j]) < len(inits) else int(wfp[j])
                   for j in range(hc)]
            self._eval_host_props_block(rows_h[s * hmax:s * hmax + hc],
                                        fps, discoveries)

    # ------------------------------------------------------------------
    def _sharded_lasso_sweep(self, carry: ShardedCarry, qcap: int,
                             q_tail, log_n, e_n,
                             discoveries: Dict[str, object],
                             full_mask: int) -> None:
        """Merge every shard's node graph and run the shared SCC sweep
        (checker/lasso.py). Per-shard queue row ``n_init_s + i`` aligns
        with per-shard log row ``i``; node masks come from the queue's
        at-enqueue ebits column."""
        import jax

        from ..checker.lasso import (add_log_block, add_seed_nodes,
                                     lasso_sweep)

        mesh, axis = self._mesh, self._axis
        D = mesh.shape[axis]
        model = self._model
        width = model.packed_width
        qloc = qcap // D
        closc = self._capacity // D
        q_h, log_h, elog_h = self._pull_global(
            (carry.q, carry.log, carry.elog))
        eloc = elog_h.shape[0] // D
        node_fp: Dict[int, int] = {}
        node_parent: Dict[int, tuple] = {}
        node_mask: Dict[int, int] = {}
        node_edges: Dict[int, list] = {}
        for s in range(D):
            add_seed_nodes(node_fp, node_parent, node_mask,
                           self._init_by_shard[s], self._orig_of,
                           full_mask)
        for s in range(D):
            n0 = len(self._init_by_shard[s])
            ln = int(log_n[s])
            en = int(e_n[s])
            add_log_block(
                node_fp, node_parent, node_mask, node_edges,
                log_h[s * closc:s * closc + ln],
                q_h[s * qloc + n0:s * qloc + n0 + ln, width],
                elog_h[s * eloc:s * eloc + en])
        lasso_sweep(self._properties, discoveries, node_edges,
                    node_mask, node_parent, node_fp)
        if self._trace:
            self._trace.emit(
                "lasso", nodes=len(node_mask),
                edges=sum(len(v) for v in node_edges.values()))

    # ------------------------------------------------------------------
    def _finalize_sharded(self, carry: ShardedCarry) -> None:
        """Stash the device-resident per-shard logs; they are pulled
        lazily on first use (`_pull_log`) — the log pull is ~tens of MB
        over a ~35 MB/s link, pointless for count-only runs (the unique
        count comes from the stats vector)."""
        self._mirror_carry = ("sharded", carry.log, carry.log_n)

    def _pull_log_blocks(self, mirror) -> List[np.ndarray]:
        if mirror[0] != "sharded":
            return super()._pull_log_blocks(mirror)
        _tag, log_d, log_n_d = mirror
        D = self._mesh.shape[self._axis]
        closc = self._capacity // D
        log_n, log = self._pull_global((log_n_d, log_d))
        if self._trace:
            # per-shard pull volumes: the mirror transfer is the big
            # host-link cost of a sharded run
            self._trace.emit(
                "mirror_pull", n=int(np.asarray(log_n).sum()),
                shards=[int(x) for x in np.asarray(log_n)])
        return [log[s * closc:s * closc + int(log_n[s])]
                for s in range(D) if int(log_n[s])]
