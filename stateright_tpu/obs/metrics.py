"""The metrics registry shared by every checking engine.

One ``Metrics`` object per checker replaces the per-engine ad-hoc
``self._prof`` dicts that had drifted apart (inconsistent keys, missing
phases on some engines, ``{}`` from a host-won race). The registry is a
flat ``key -> number`` map — cheap enough for per-chunk hot paths — with
three access idioms (counters, phase timers, observed maxima) and ONE
canonical key glossary, :data:`GLOSSARY`, that every ``profile()``
docstring references instead of restating.

Key-name conventions:

* phase timers are wall-seconds and use bare phase names (``dispatch``,
  ``sync_stall``, ``grow``);
* counters are integral and plural where natural (``chunks``,
  ``grows``);
* observed maxima keep their engine names (``vmax``/``dmax``/``rmax``).

Engines that historically used divergent keys now agree: the sharded
engine's growth pass reports BOTH the ``grow`` timer and the ``grows``
counter, exactly like the single-chip engine (which gained ``grows``);
``hgrow`` remains a distinct key because it times a different structure
(the host-property history table), not a naming drift.
"""

from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Dict, Optional

#: The canonical metrics glossary. ``Checker.profile()`` returns a
#: snapshot whose keys are drawn from this table (engines only report
#: the phases they run). Timers are wall-clock seconds; counters and
#: maxima are integers.
GLOSSARY: Dict[str, str] = {
    # --- device chunk-loop phase timers (single-chip + sharded) -------
    "seed": "building + inserting the initial frontier/table buffers",
    "dispatch": "host time launching chunk programs (async; small "
                "unless tracing/compiling)",
    "sync_stall": "time blocked materializing a chunk's stats vector — "
                  "the device round trip the pipeline hides host work "
                  "under; if it dominates, the device is the "
                  "bottleneck (try a larger fmax/chunk_steps)",
    "host_overlap": "host-side consumption of a chunk's outputs (stats "
                    "decode, batched host-property evaluation, "
                    "discovery bookkeeping) that overlaps the NEXT "
                    "in-flight chunk under tpu_options(pipeline=True)",
    "grow": "hash-table/queue/log growth passes (rebuild + re-insert)",
    "hgrow": "host-property history-table growth (re-seed + rescan)",
    "posthoc": "host-property evaluation over pulled representatives",
    "lasso": "post-exhaustion SCC sweep (sound_eventually)",
    "mirror_pull": "pulling the device (child, parent) log into the "
                   "host mirror",
    "visit": "post-hoc CheckerVisitor replay over the reached set",
    "shadow": "maintaining the host-side authoritative state "
              "(checker/resilience.py) — per-chunk queue/log suffix "
              "gathers while retry/autosave/tiering is enabled",
    "spill": "visited-set spill passes (drain + cold-range eviction + "
             "epoch re-seed) taken when table growth would exceed the "
             "HBM budget (tpu_options(max_capacity=...)); includes "
             "the embedded re-seed time",
    # --- counters ----------------------------------------------------
    "chunks": "completed chunk dispatches (each up to chunk_steps "
              "frontier levels)",
    "grows": "table growth passes taken",
    "hgrows": "history-table growth passes taken",
    "kovfs": "candidate-buffer overflow retries (kraw/kmax resizes)",
    "compiles": "chunk-program (re)builds — each implies an XLA "
                "retrace unless the shapes hit the compile cache",
    "levels": "BFS levels completed (host/per-level engines)",
    "jobs": "DFS stack jobs completed (multi-process DFS)",
    "retries": "transient-fault recoveries taken (re-seed + resume; "
               "bounded per consecutive burst by "
               "tpu_options(retries=N))",
    "failovers": "raced runs adopted by the un-budgeted host BFS "
                 "fallback after a transient device failure (the rung "
                 "BELOW the degradation ladder)",
    "degrades": "mesh degradation rungs taken: exhausted retries (or "
                "per-device fault attribution) re-shard the run onto "
                "the surviving power-of-two device subset, D -> D/2 "
                "-> ... -> single chip "
                "(tpu_options(degrade=, min_mesh=))",
    "promotes": "elastic scale-up rungs taken: a granted device subset "
                "doubled the mesh D -> 2D at a drained chunk boundary "
                "(Checker.request_promote / the scheduler's flex "
                "controller) — the exact mirror of a degradation rung, "
                "so a run that degraded around a transient fault can "
                "climb back up the ladder",
    "promote": "elastic scale-up passes: widening the mesh and "
               "re-seeding the sharded carry at the new width "
               "(promote_step, parallel/engine.py)",
    "autosaves": "resilience checkpoints written (periodic "
                 "tpu_options(autosave=...) snapshots plus the "
                 "exhausted-retries and capacity-terminal writes)",
    "spills": "visited-set spills taken (HBM -> host tiering, README "
              "§ Memory tiering): growth past the "
              "tpu_options(max_capacity=...) budget — or a "
              "spill-eligible capacity fault in the retry envelope — "
              "evicted cold fingerprint-prefix ranges to the host "
              "tier and resumed instead of dying",
    "evicted_keys": "fingerprints evicted from the device table into "
                    "the host tier across the run's spills (the "
                    "shadow mirror holds them; rediscoveries are "
                    "filtered by the host re-probe)",
    "host_probe_hits": "device-'fresh' keys the host tier recognized "
                       "as rediscoveries of evicted ranges and "
                       "filtered out of the mirror and unique counts "
                       "(their re-expansion is the price of tiering)",
    "audits": "sampled chunk audits taken (tpu_options(audit=...), "
              "README § Silent corruption defense): the chunk's "
              "frontier slice re-executed on a different device (host "
              "oracle on single-chip) and compared word-for-word "
              "against the fingerprints the chip claimed",
    "audit_mismatches": "chunk audits that caught a chip returning "
                        "WRONG results (silent data corruption): each "
                        "one rolled the shadow back to the last "
                        "audited boundary, quarantined the liar, and "
                        "replayed — the final digest stays identical "
                        "to an uncorrupted oracle run",
    "fused_chunks": "chunks dispatched through the fused Pallas "
                    "expand→fingerprint→dedup kernel (ops/fused.py; "
                    "tpu_options(fused=...))",
    "predup_hits": "duplicate candidate lanes killed by the in-batch "
                   "pre-dedup before the visited-table probe — the "
                   "fusion win's direct measure (compare against "
                   "state_count - unique_state_count, the TOTAL "
                   "duplicate work)",
    "probe_rounds": "visited-table bucket probe rounds taken across "
                    "the run (claim-retry pressure: rising rounds per "
                    "chunk mean duplicate lanes or load factor are "
                    "stressing the open-addressed table)",
    "cc_dedup_hits": "duplicate lanes killed by the cross-chunk "
                     "in-kernel recent-key ring BEFORE the table probe "
                     "(or the sharded exchange) — the tier that "
                     "attacks the re-expansion share of gen/uniq the "
                     "in-batch pre-dedup cannot see "
                     "(tpu_options(cc_dedup=...), fused path only)",
    "probe_kernel_s": "verify/compile wall time of the owner-side "
                      "post-exchange probe kernel (the sharded fused "
                      "pipeline's second Pallas kernel; per-dispatch "
                      "timings come from tools/kernel_bench.py)",
    # --- soak harness (actor/chaos.py + tools/soak.py) ----------------
    "ops": "client operations completed (returned) during a soak run "
           "against the spawned UDP cluster",
    "op_timeouts": "client operations that timed out awaiting a reply "
                   "and were abandoned (the op stays in-flight in the "
                   "recorded history; the client retires that logical "
                   "thread id)",
    "crashes": "live actor crash injections taken "
               "(SpawnHandle.crash: thread torn down, durable() "
               "projection captured)",
    "restarts": "live actor restarts taken (SpawnHandle.restart: "
                "reboot through on_restart with the captured durable "
                "projection)",
    "dropped": "datagrams dropped by the chaos layer (seeded loss plus "
               "partition suppression)",
    "duplicated": "datagrams duplicated by the chaos layer (the copy "
                  "rides the delay scheduler)",
    "delayed": "datagrams deferred by the chaos layer's delay "
               "scheduler",
    "reordered": "deferred datagrams delivered after a later-sent "
                 "datagram on the same link had already landed",
    "partitions": "partition episodes installed "
                  "(ChaosNetwork.set_partition)",
    "history_ok": "1 when the recorded runtime history passed the "
                  "consistency cross-check (LinearizabilityTester / "
                  "SequentialConsistencyTester), 0 when it was "
                  "rejected (a dumped seed artifact reproduces it)",
    "violations": "consistency violations flagged by the cross-check "
                  "— online (the incremental Wing&Gong checker aborts "
                  "the soak at the offending op) or post-hoc; every "
                  "one auto-files a seed artifact under its "
                  "(protocol, tester, sha256(ops)) dedup key",
    # --- observed maxima (buffer autotuning inputs) -------------------
    "vmax": "max raw-valid candidate lanes in one iteration (sizes "
            "kraw; compare against fmax*max_actions)",
    "dmax": "max post-dedup survivors in one iteration (sizes kmax)",
    "rmax": "max valid children of a single row (sizes "
            "tpu_options(hint=...))",
    "visit_peak_resident": "max decoded states resident during the "
                           "visitor replay (bounded by path depth)",
    # --- gauges --------------------------------------------------------
    "shard_balance": "end-of-run min/max ratio of per-shard inserted "
                     "states (1.0 = perfectly balanced routing)",
    "mesh_shards": "current mesh width of a sharded run (drops rung "
                   "by rung under the degradation ladder; the final "
                   "value is the width the run FINISHED on)",
    "fault_device": "device index the most recent transient fault was "
                    "attributed to (blamed_device: an explicit "
                    "device_index attribute or the chip named in the "
                    "error message)",
    "engine": "race winner tag on a raced spawn_tpu profile: 'host' "
              "or 'device'",
    "fused": "1 when the run's chunk program took the fused Pallas "
             "path, 0 when staged (bench tags its contract lines from "
             "this so the perf trajectory can't silently mix paths)",
    "fused_unsupported": "1 when a fused='auto' run stayed staged "
                         "because the configuration is outside the "
                         "kernel's support matrix (the one-time "
                         "fused_unsupported trace event carries the "
                         "reason; report()'s metrics line renders it)",
    "cc_dedup_capacity": "slot count of the cross-chunk recent-key "
                         "ring when enabled (gauge; "
                         "tpu_options(cc_dedup=True|N|False), 0/absent "
                         "when off or staged)",
    "host_tier_keys": "keys resident ONLY in the host tier after the "
                      "most recent spill (decremented as evicted keys "
                      "are rediscovered and re-promoted); 0 until the "
                      "run hits its HBM budget",
    "quarantined": "devices the chunk auditor caught returning wrong "
                   "results this run (gauge — the cumulative "
                   "quarantine-set size; the service scheduler "
                   "persists the set and withholds these devices from "
                   "all future grants until an audit probe re-admits "
                   "them)",
    # --- host search timers -------------------------------------------
    "search": "host-engine search loop wall time",
    # --- device-time attribution (chunk loops) ------------------------
    "device_s": "estimated device-execution seconds: the dispatch-to-"
                "stats-ready interval summed over chunks. Splits the "
                "old host-side sync_stall conflation of compute and "
                "transfer; under the pipelined loop host work overlaps "
                "this interval, so it is an upper bound on pure device "
                "compute (per-chunk values ride the chunk trace event)",
    "xfer_s": "estimated device->host transfer seconds: stats-ready-to-"
              "materialized, summed over chunks (the transfer "
              "component of each sync)",
    # --- flight recorder (obs/recorder.py) -----------------------------
    "recorder_dumps": "flight-recorder artifacts written (the bounded "
                      "always-on event ring dumped as JSONL on error, "
                      "watchdog expiry, exhausted retries, and "
                      "degradation rungs; see the recorder_dump trace "
                      "event for the path)",
    # --- pausable runs + the job service (stateright_tpu/service) ------
    "pause": "engine-level pause: draining the pipeline and writing "
             "the resume_from-loadable pause checkpoint "
             "(Checker.request_pause; the step-driver boundary)",
    "pauses": "pause checkpoints written (a paused run exits its "
              "engine loop cleanly; resumption is a fresh checker via "
              "resume_from — possibly on a different mesh width, which "
              "is how the scheduler preempts onto smaller subsets)",
    "jobs_submitted": "checking jobs accepted by the scheduler "
                      "(service/scheduler.py)",
    "jobs_done": "jobs that ran to completion and landed a result "
                 "artifact",
    "jobs_failed": "jobs whose engine raised (the classified error "
                   "rides the job's status artifact)",
    "preemptions": "running jobs paused by the scheduler to free "
                   "device subsets for higher-priority work (the "
                   "victim re-queues and resumes from its pause "
                   "checkpoint, typically on a smaller subset)",
    "demotes": "flex-controller demotions: over-width running jobs "
               "preempted under queue pressure to resume on a smaller "
               "subset (a subset of preemptions — only the ones the "
               "SLO-driven flex controller initiated)",
    "flex_width": "extra device-width currently leased to running "
                  "jobs by in-place flex promotes (gauge — rises when "
                  "the controller grants a doubling lease, falls back "
                  "as promoted jobs finish or the engine declines)",
    "queue_depth": "jobs currently waiting for a device subset "
                   "(gauge; sampled after every scheduling pass)",
    # --- continuous verification fleet (soak/fuzz as service load) -----
    "soak_jobs": "soak/fuzz service jobs run to completion (kind: "
                 "soak|fuzz specs over SOAK_REGISTRY — the standing "
                 "chaos/fuzz lane beside checking jobs)",
    "fuzz_ops": "client operations completed across the scheduler's "
                "soak/fuzz jobs (all segments; the burn-in lane's "
                "work measure, the ops/s numerator per job rides the "
                "job's result.json)",
    "burnin_frac": "fraction of the device pool currently leased to "
                   "burn-in (low-priority background soak/fuzz) jobs "
                   "(gauge; sampled with pool_busy_frac — burn-in "
                   "load is visible, not invisible)",
    # --- utilization + SLO accounting (PR 14) --------------------------
    "queue_wait_s": "cumulative submit->grant wall seconds across jobs "
                    "(the queueing SLO numerator; divide by "
                    "jobs_submitted-queue_depth for the mean wait; "
                    "per-job values ride job_grant events and "
                    "result.json lifecycle)",
    "first_chunk_s": "cumulative start->first-materialized-chunk wall "
                     "seconds across jobs — the compile/seed latency a "
                     "tenant pays before any progress (per-job values "
                     "ride job_first_chunk events)",
    "pool_busy_frac": "fraction of the device pool currently leased "
                      "(gauge; 1 - free_width/width, sampled by the "
                      "scheduler's utilization sampler — the per-host "
                      "split rides pool_util events and "
                      "Scheduler.utilization())",
    "jobs_per_min": "completions in the trailing 60s window (gauge; "
                    "the service-throughput SLO the batch lane engine "
                    "exists to move)",
    "sse_dropped": "events dropped across SSE clients too slow to "
                   "drain their bounded queues (Explorer /.events and "
                   "the service's per-job /events; the engine writer "
                   "never blocks — a rising count means a console is "
                   "starved, not the run)",
    # --- batch lane engine (service/batch.py + checker/batch_loop.py) --
    "batched_jobs": "jobs completed as lanes of a vmapped batch chunk "
                    "program (vs solo engine runs) — the "
                    "compile-amortized small-job path",
    "lanes": "lane width of the batch programs (gauge; the vmapped "
             "leading axis — up to this many jobs advance per kernel "
             "launch)",
    "bucket_hits": "submissions whose NORMALIZED compile bucket "
                   "(model config × padded capacity/fmax) matched a "
                   "bucket already seen this process — the spec "
                   "normalizer turning per-user shape drift into "
                   "compile-cache hits",
    "compile_reuse": "batched lane-jobs that ran WITHOUT paying a "
                     "chunk-program build (every lane after the first "
                     "of a fresh build, and every lane of a "
                     "cache-hit batch)",
    # --- fleet layer (stateright_tpu/cluster + multi-host meshes) ------
    "hosts": "distinct hosts behind the run's mesh or the scheduler's "
             "device pool (gauge; real process_index or the simulated "
             "host_map/hosts= labels; drops when the degradation "
             "ladder's host rung fires)",
    "procs": "jax processes participating in the run (gauge; 1 for "
             "single-controller runs, the jax.distributed world size "
             "on a fleet mesh)",
    "dcn_exchange_s": "timed cross-host collective round trip at mesh "
                      "init (one warm replicated psum over the global "
                      "mesh — the DCN latency floor every fingerprint "
                      "all-to-all pays between hosts; 0 on "
                      "single-process meshes, which skip the probe)",
    # --- span attribution (obs/spans.py; attached by profile(), NOT
    # stored in the registry — merge() would sum fractions) -------------
    "attribution": "overlap-aware wall-time split (dict: bucket -> "
                   "seconds, largest first) from the run's span "
                   "timeline — device-only buckets (device/xfer/"
                   "exchange) are device-bound, 'overlap' is host work "
                   "hidden under an in-flight chunk (free), 'host:*' "
                   "is host work blocking an idle device (the pipeline "
                   "bubble), 'idle' is dead air; rows sum to wall "
                   "(tools/stall_report.py renders the ranked table)",
    "idle_s": "wall seconds with NO span active (neither device nor "
              "host side) inside the run's span extent — dead air "
              "between chunks (from the attribution sweep; not a "
              "registry counter)",
    "bubble_frac": "fraction of span-extent wall time where the host "
                   "blocked the critical path (host:* buckets) or "
                   "nothing ran (idle) — the addressable pipeline "
                   "bubble; 0 means every host second hid under device "
                   "compute (not a registry counter)",
}

#: keys that are point-in-time GAUGES, not accumulating counters:
#: :meth:`Metrics.merge` takes the incoming value (last-writer-wins)
#: instead of summing — summing gauges produced impossible merged
#: values (``fused=2``, a ``mesh_shards`` no mesh ever had).
GAUGES = frozenset({
    "mesh_shards", "fused", "engine", "fault_device", "history_ok",
    "shard_balance", "host_tier_keys", "queue_depth", "lanes",
    "hosts", "procs", "fused_unsupported", "cc_dedup_capacity",
    "pool_busy_frac", "jobs_per_min", "burnin_frac", "flex_width",
    "quarantined",
})

#: keys merged by maximum (observed buffer-sizing maxima).
MAXIMA = frozenset({"vmax", "dmax", "rmax", "visit_peak_resident"})


class Metrics:
    """Counters, phase timers and observed maxima for one checker run.

    The backing store is a plain dict so ``snapshot()`` is O(keys) and
    hot-path updates are one dict op; the counter/timer/maximum
    distinction lives in :data:`GLOSSARY`, not in per-key objects.
    """

    __slots__ = ("_data",)

    def __init__(self, data: Optional[Dict[str, float]] = None):
        self._data: Dict[str, float] = dict(data) if data else {}

    # --- update idioms ------------------------------------------------
    def inc(self, key: str, n: int = 1) -> None:
        self._data[key] = self._data.get(key, 0) + n

    def add_time(self, key: str, seconds: float) -> None:
        self._data[key] = self._data.get(key, 0.0) + seconds

    @contextmanager
    def timed(self, key: str):
        """Accumulate wall time under ``key`` (the phase-timer idiom)."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.add_time(key, time.perf_counter() - t0)

    def observe_max(self, key: str, value: float) -> None:
        cur = self._data.get(key)
        if cur is None or value > cur:
            self._data[key] = value

    def set(self, key: str, value: float) -> None:
        self._data[key] = value

    # --- read side ----------------------------------------------------
    def get(self, key: str, default=None):
        return self._data.get(key, default)

    def snapshot(self) -> Dict[str, float]:
        """A copy of every recorded metric (the ``profile()`` payload)."""
        return dict(self._data)

    def merge(self, other: "Metrics") -> None:
        """Fold ``other`` in: timers/counters add, maxima take max, and
        gauges (:data:`GAUGES`) take the incoming value — last-writer-
        wins, so a merged profile can never report ``fused=2`` or a
        summed ``mesh_shards`` no mesh ever had.

        Used by consumers that aggregate engines (e.g. the host-vs-
        device race reporting the winner on top of its own bookkeeping).
        """
        for key, value in other._data.items():
            if key in MAXIMA:
                self.observe_max(key, value)
            elif key in GAUGES:
                self.set(key, value)
            else:
                self.add_time(key, value)

    def __bool__(self) -> bool:
        return bool(self._data)

    def __repr__(self) -> str:  # debugging aid
        return f"Metrics({self._data!r})"


class MetricsRing:
    """Bounded time series of periodic metric snapshots.

    Lived in ``checker/explorer.py`` through PR 13 as the
    ``/.metrics?history`` backing store; it is an obs concern (moved
    here in PR 14) because the service's utilization accounting needs
    the same shape — a daemon sampler appends one snapshot per
    ``interval`` seconds while the producer is live, the ring keeps
    the most recent ``limit`` samples, and a consumer attaching
    mid-run can plot the trend it missed without having polled from
    the start. Every sample is stamped with its ``wall`` time."""

    def __init__(self, limit: int = 512, interval: float = 1.0):
        import threading as _threading
        from collections import deque as _deque
        self.interval = interval
        self._buf = _deque(maxlen=max(4, int(limit)))
        self._lock = _threading.Lock()

    def add(self, sample: Dict) -> None:
        sample = dict(sample)
        sample["wall"] = time.time()
        with self._lock:
            self._buf.append(sample)

    def snapshot(self) -> list:
        with self._lock:
            return list(self._buf)

    def sample_until(self, sample_fn, done_fn) -> None:
        """Generic sampler loop body (run on a daemon thread): one
        snapshot immediately, then one per interval until ``done_fn()``
        is true — plus a final post-done sample so the series ends at
        the terminal values. Sampling exceptions are swallowed (a
        mid-teardown race must not kill the sampler's owner)."""
        while True:
            done = bool(done_fn())
            try:
                self.add(sample_fn())
            except Exception:
                pass
            if done:
                return
            time.sleep(self.interval)

    def run_sampler(self, checker) -> None:
        """The Explorer's historical entry point: snapshot a checker's
        ``/.metrics`` view until the run completes (kept here so the
        ``checker.explorer`` re-export stays drop-in compatible)."""
        from ..checker.explorer import metrics_view
        self.sample_until(lambda: metrics_view(checker),
                          checker.is_done)
