"""Batch lane engine: compile-amortized checking of many small jobs.

Two pieces close ROADMAP's "millions of tiny jobs" gap:

**The spec normalizer** (:func:`plan_batch`) canonicalizes a JobSpec's
shape-bearing knobs into power-of-two buckets — ``capacity`` and
``fmax`` padded UP to the bucket grid — so the jit compile key
(model config × buffer shapes, exactly what
``device_loop.build_chunk_fn`` memoizes on) collides across users
instead of fragmenting per submission. Padding never changes a
model's reachable fingerprint set (dedup is set-semantics; buffer
shapes only change batching granularity — pinned by the normalizer
property test), so a padded run is bit-identical to the requested one.

**The batch engine** (:class:`BatchRun`) packs up to L same-bucket
jobs as lanes of ONE vmapped chunk program
(``checker/batch_loop.BatchLoop``): per-lane frontier/queue/visited
slices, per-lane done flags, dead lanes masked out, finished lanes
retired and backfilled from the bucket queue mid-flight. Each job
still lands the standard per-job artifacts (trace.jsonl with
run_start/chunk/done events, result.json with the sha256
fingerprint-set digest) — bit-identical to a solo run of the same
job.

Jobs opt in with ``JobSpec(batch='auto')``; ineligible specs (wide
meshes, host-property models, capped runs, exotic options) and lanes
the bucket cannot hold (table growth, candidate overflow) fall back
to the solo engine transparently. Pausing a batched job writes a
normal ``resume_from``-loadable checkpoint for its lane; the resumed
job runs solo (a checkpointed lane is no longer bucket-shaped), with
the solo engine's existing parity guarantee.

NOTE the compile-cache interplay inherited from the solo engines
(CHANGES.md PR 9): ``seed_carry`` keeps its 5-arg traced signature for
the non-adopting path, and the batch seed goes through the same
program — bucketing rides the persistent compile cache, never
invalidates it.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional, Tuple

import numpy as np

from ..obs import Metrics, identity_fields, make_trace, new_run_id
from . import jobs as jobstates
from .jobs import Job

#: lane-retirement reason for a completed run (mirrors
#: ``checker.batch_loop.DONE``; the heavy jax-importing module is
#: loaded lazily so ``import stateright_tpu.service`` stays light)
LANE_DONE = "done"

#: default lanes per batch program (the vmapped leading axis; also the
#: bucket-queue flush threshold)
DEFAULT_LANES = 8
#: seconds a lone small job waits for bucket-mates before the batch
#: launches anyway
DEFAULT_MAX_WAIT = 0.25

#: normalized capacity grid: small jobs live here; a spec asking for
#: more is not "small" and runs solo
MIN_CAPACITY = 1 << 12
MAX_CAPACITY = 1 << 16
#: normalized fmax grid
MIN_FMAX = 32
MAX_FMAX = 512
DEFAULT_FMAX = 128

#: tpu_options a batched lane can honor (shape knobs are normalized
#: into the bucket; the rest are solo-engine machinery a lane either
#: inherits implicitly or cannot run) — anything else disqualifies
_BATCHABLE_OPTIONS = frozenset({
    "capacity", "fmax", "qcap", "kraw", "kmax", "chunk_steps",
    "retries", "backoff", "pipeline", "grow_at", "autosave_interval",
    "max_segment", "flight",
})


def _next_pow2(n: int) -> int:
    return 1 << max((int(n) - 1).bit_length(), 0)


def normalize_shapes(options: dict) -> Tuple[int, int]:
    """Pad a spec's shape knobs UP to the bucket grid: the returned
    ``(capacity, fmax)`` are the power-of-two bucket coordinates every
    same-bucket job compiles (and caches) against."""
    capacity = _next_pow2(max(int(options.get("capacity",
                                              MIN_CAPACITY * 2)),
                              MIN_CAPACITY))
    fmax = _next_pow2(min(max(int(options.get("fmax", DEFAULT_FMAX)),
                              MIN_FMAX), MAX_FMAX))
    return capacity, fmax


def bucket_label(model_name: str, args, capacity: int,
                 fmax: int) -> str:
    """Human-readable bucket id for events and status artifacts."""
    a = ",".join(str(x) for x in (args or ()))
    return f"{model_name}({a})/cap{capacity}/f{fmax}"


def plan_batch(spec) -> Tuple[Optional[str], Optional[Any],
                              Optional[tuple], Optional[str]]:
    """Eligibility + normalization for one spec: returns
    ``(reason, model, bucket_key, label)`` — ``reason`` is None when
    the spec can batch, else why it must run solo. The built model
    rides back so the scheduler never builds twice."""
    if not spec.batch:
        return "batch=False", None, None, None
    if spec.width != 1:
        return "width > 1 (batches are single-chip allocations)", \
            None, None, None
    if spec.target is not None:
        return "target_state_count caps depend on chunk granularity " \
               "(digest parity vs solo would not hold)", None, None, \
            None
    unknown = sorted(set(spec.options) - _BATCHABLE_OPTIONS)
    if unknown:
        return f"options outside the batch matrix: {unknown}", None, \
            None, None
    if int(spec.options.get("capacity", MIN_CAPACITY)) > MAX_CAPACITY:
        return f"capacity > {MAX_CAPACITY} is not a small job", None, \
            None, None
    try:
        model = spec.build()
    except Exception as exc:
        # let the solo path surface the build error with full context
        return f"model build failed ({type(exc).__name__})", None, \
            None, None
    from ..checker.batch_loop import batch_supports
    reason = batch_supports(model)
    if reason is not None:
        return reason, None, None, None
    from ..checker.device_loop import model_cache_key
    capacity, fmax = normalize_shapes(spec.options)
    key = (model_cache_key(model), capacity, fmax)
    return None, model, key, bucket_label(spec.model_name, spec.args,
                                          capacity, fmax)


def lane_checkpoint(path, model, mirror: Dict[int, Optional[int]],
                    rows, ebits, fps, discoveries: Dict[str, int],
                    state_count: int) -> None:
    """Write one lane's state as a standard ``resume_from``-loadable
    checkpoint (the solo engines' format — ``TpuChecker
    ._checkpoint_save``): complete mirror + pending frontier. The
    resumed job runs on the SOLO engine; parity with an uninterrupted
    run is the existing cross-engine resume guarantee."""
    import json

    from ..checker.resilience import atomic_savez
    from ..checker.tpu import model_tag

    child = np.fromiter(mirror.keys(), np.uint64, len(mirror))
    parent = np.fromiter(
        (p if p is not None else 0 for p in mirror.values()),
        np.uint64, len(mirror))
    meta = json.dumps({
        "model": model_tag(model),
        "discoveries": {n: int(fp) for n, fp in discoveries.items()},
        "symmetry": False,
        "sound": False,
    })
    atomic_savez(path, child=child, parent=parent,
                 rows=np.asarray(rows, np.uint32),
                 ebits=np.asarray(ebits, np.uint32),
                 ffps=np.asarray(fps, np.uint64),
                 state_count=np.int64(state_count),
                 meta=np.asarray(meta))


class LaneView:
    """Checker-shaped facade over one lane's job: what
    ``scheduler.write_result`` needs to land the standard result.json
    (model / counts / discoveries / fingerprint set / profile), plus
    the ``_trace`` handle the HTTP API's per-job SSE stream
    subscribes to. Live while the lane runs; frozen at retirement."""

    def __init__(self, model, trace, metrics: Metrics, lane: int):
        self._model = model
        self._trace = trace        # serve_events reads this
        self._recorder = None      # (and this: no flight ring per lane)
        self._metrics = metrics
        self.lane = lane
        self._mirror: Dict[int, Optional[int]] = {}
        self._disc: Dict[str, int] = {}
        self._state_count = 0
        self._done = False

    def adopt(self, mirror, disc, state_count: int) -> None:
        self._mirror = mirror
        self._disc = disc
        self._state_count = int(state_count)

    def finish(self) -> None:
        self._done = True

    # --- the Checker surface write_result/metrics_view consume --------
    def model(self):
        return self._model

    def is_done(self) -> bool:
        return self._done

    def state_count(self) -> int:
        return self._state_count

    def unique_state_count(self) -> int:
        return len(self._mirror)

    def generated_fingerprints(self):
        return set(self._mirror)

    def discoveries(self):
        from collections import deque as _deque

        from ..checker.path import Path

        out = {}
        for name, fp in self._disc.items():
            fps: _deque = _deque()
            nxt = fp
            while nxt in self._mirror:
                fps.appendleft(nxt)
                parent = self._mirror[nxt]
                if parent is None:
                    break
                nxt = parent
            out[name] = Path.from_fingerprints(self._model, fps)
        return out

    def profile(self) -> Dict[str, float]:
        return self._metrics.snapshot()

    def subscribe(self, fn) -> None:
        self._trace.subscribe(fn)


class BatchRun:
    """One running batch: drives a :class:`BatchLoop` over the bucket's
    job feed, mapping lanes to jobs and landing per-job artifacts.

    Runs on a scheduler worker thread inside the batch's device lease;
    talks back to the scheduler only through the small adapter surface
    it is constructed with (pop a job, emit a service event, metrics).
    """

    def __init__(self, batch_id: str, key: tuple, label: str, model,
                 lanes: int, capacity: int, fmax: int, scheduler,
                 runtime, chunk_steps: int = 32):
        self.id = batch_id
        self._chunk_steps = int(chunk_steps)
        self.key = key
        self.label = label
        self._model = model
        self._lanes = int(lanes)
        self._capacity = int(capacity)
        self._fmax = int(fmax)
        self._sched = scheduler
        self._runtime = runtime
        self._metrics = Metrics()
        self._metrics.set("lanes", self._lanes)
        self._loop = None
        self._jobs: Dict[int, Job] = {}
        self._views: Dict[str, LaneView] = {}
        self._traces: Dict[int, Any] = {}
        self._prev_unique: Dict[int, int] = {}
        self._chunks: Dict[int, int] = {}
        self._built_fresh = False
        self._seeded = 0

    # --- the scheduler's live-introspection hooks ----------------------
    def view_for(self, job_id: str) -> Optional[LaneView]:
        return self._views.get(job_id)

    def profile(self) -> Dict[str, float]:
        return self._metrics.snapshot()

    # --- lifecycle ------------------------------------------------------
    def run(self) -> None:
        from ..checker.batch_loop import BatchLoop
        sched = self._sched
        trace = sched._trace
        # the batch's phase intervals land on the SCHEDULER stream
        # (service.jsonl) — batch-wide, not per-lane, so the stall
        # report attributes the shared kernel launches once
        loop = BatchLoop(self._model, self._lanes, self._capacity,
                         self._fmax, chunk_steps=self._chunk_steps,
                         metrics=self._metrics, trace=trace)
        before = self._metrics.get("compiles", 0)
        loop.start()
        self._built_fresh = self._metrics.get("compiles", 0) > before
        self._loop = loop
        seeded = 0
        for lane in range(self._lanes):
            if not self._backfill(lane):
                break
            seeded += 1
        if not seeded:
            return
        if trace:
            trace.emit("batch_form", batch=self.id, bucket=self.label,
                       jobs=seeded, lanes=self._lanes)
        while True:
            lanes = loop.active_lanes()
            if not lanes:
                # every lane idle: one backfill round; a dry bucket
                # queue means the batch is complete
                filled = [self._backfill(lane)
                          for lane in range(self._lanes)]
                if not any(filled):
                    return
                continue
            exits = loop.step()
            self._emit_chunk_events()
            for lane, reason in exits:
                self._retire(lane, reason)
            for lane, _reason in exits:
                self._backfill(lane)
            if self._handle_controls():
                return  # shutdown: every live lane checkpointed

    def close(self) -> None:
        """Merge the batch's metrics into the service registry and
        close any still-open per-job traces (defensive: retire paths
        close them individually)."""
        self._sched._metrics.merge(self._metrics)
        for tr in self._traces.values():
            try:
                tr.close()
            except Exception:
                pass

    # --- controls (pause / cancel / shutdown) ---------------------------
    def _handle_controls(self) -> bool:
        """Apply the scheduler's queued controls. Returns True on
        shutdown (all live lanes checkpointed and re-queued)."""
        loop = self._loop
        for ctl, job_id in self._runtime.take_controls():
            if ctl == "shutdown":
                for lane in list(self._jobs):
                    if loop is not None and lane in set(
                            loop.active_lanes()):
                        self._pause_lane(lane, reason="shutdown")
                    else:
                        # retired-but-unprocessed lanes re-queue plain
                        job = self._jobs.pop(lane, None)
                        if job is not None:
                            job.set_state(jobstates.QUEUED, resume=job
                                          .has_checkpoint())
                return True
            lane = next((ln for ln, j in self._jobs.items()
                         if j.id == job_id), None)
            if lane is None:
                continue  # already retired
            if ctl == "pause":
                self._pause_lane(lane, reason="user")
                self._backfill(lane)
            elif ctl == "cancel":
                self._cancel_lane(lane)
                self._backfill(lane)
        return False

    # --- lane transitions ----------------------------------------------
    def _backfill(self, lane: int) -> bool:
        if lane in self._jobs:
            return False  # still occupied
        job = self._sched._pop_bucket_job(self.key)
        if job is None:
            return False
        loop = self._loop
        tr = make_trace(job.paths["trace"], engine="batch")
        view = LaneView(self._model, tr, self._metrics, lane)
        loop.activate(lane)
        view.adopt(loop.lane_mirror(lane),
                   self._lanes_disc_live(lane), 0)
        self._jobs[lane] = job
        self._views[job.id] = view
        self._traces[lane] = tr
        self._prev_unique[lane] = loop.lane_unique(lane)
        self._chunks[lane] = 0
        # compile amortization, measured: only the FIRST job of a
        # freshly built program pays the trace/compile; every other
        # lane-job (and every job of a cache-hit batch) reuses it
        if not self._built_fresh or self._seeded > 0:
            self._metrics.inc("compile_reuse")
        self._seeded += 1
        job.set_state(jobstates.RUNNING, granted_width=1,
                      batch=self.id, lane=lane, resume=False)
        sched_trace = self._sched._trace
        if sched_trace:
            sched_trace.emit("job_start", job=job.id, width=1,
                             batch=self.id, lane=lane)
        if tr:
            # the correlation header (obs/trace.py): a lane-job's
            # stream is self-describing on the fleet timeline exactly
            # like a solo engine run's
            tr.emit("run_start", model=type(self._model).__name__,
                    wall=time.time(),
                    properties=len(self._model.properties()),
                    **identity_fields(tr, new_run_id("lane")),
                    job=job.id, batch=self.id, lane=lane)
        return True

    def _lanes_disc_live(self, lane: int) -> Dict[str, int]:
        # the loop's per-lane disc dict, shared by reference so the
        # live view reflects discoveries as they land
        return self._loop._lanes[lane].disc

    def _emit_chunk_events(self) -> None:
        loop = self._loop
        for lane, job in self._jobs.items():
            tr = self._traces.get(lane)
            if not tr:
                continue
            st = loop.lane_chunk_stats(lane)
            unique = loop.lane_unique(lane)
            new = unique - self._prev_unique.get(lane, unique)
            self._prev_unique[lane] = unique
            self._chunks[lane] += 1
            gen = st["gen"]
            tr.emit("chunk", chunk=self._chunks[lane], gen=gen,
                    unique=unique, q_size=st["q_size"], new=new,
                    dedup_hit=(round(1.0 - new / gen, 4)
                               if gen else 0.0),
                    load=round(st["log_n"] / self._capacity, 4),
                    lane=lane)

    def _finish_view(self, lane: int, job: Job) -> LaneView:
        loop = self._loop
        view = self._views[job.id]
        view.adopt(loop.lane_mirror(lane),
                   loop.lane_discoveries(lane),
                   loop.lane_state_count(lane))
        return view

    def _lane_retire_event(self, job: Job, lane: int, reason: str,
                           **extra) -> None:
        trace = self._sched._trace
        if trace:
            trace.emit("lane_retire", batch=self.id, job=job.id,
                       lane=lane, reason=reason, **extra)

    def _retire(self, lane: int, reason: str) -> None:
        job = self._jobs.pop(lane, None)
        if job is None:
            return
        view = self._finish_view(lane, job)
        tr = self._traces.pop(lane, None)
        sched = self._sched
        if reason == LANE_DONE:
            from .scheduler import write_result
            result = write_result(job, view)
            view.finish()
            self._metrics.inc("batched_jobs")
            sched._metrics.inc("jobs_done")
            sched._note_done()  # the jobs/min window counts lanes too
            job.set_state(jobstates.DONE,
                          unique=result["unique_state_count"])
            self._lane_retire_event(job, lane, "done",
                                    unique=result["unique_state_count"])
            if sched._trace:
                sched._trace.emit(
                    "job_done", job=job.id, state="done",
                    unique=result["unique_state_count"],
                    batch=self.id, lane=lane)
            if tr:
                tr.emit("done", gen=view.state_count(),
                        unique=view.unique_state_count(),
                        discoveries=sorted(view._disc))
                tr.close()
            return
        # abnormal retirement: the lane outgrew the bucket (or wedged)
        # — re-queue the job with batching disabled so the solo
        # engine's full growth/retry machinery takes it
        view.finish()
        job.spec.batch = False
        job.set_state(jobstates.QUEUED, batch_fallback=reason,
                      resume=job.has_checkpoint())
        self._lane_retire_event(job, lane, reason)
        if tr:
            tr.emit("done", gen=view.state_count(),
                    unique=view.unique_state_count(),
                    fallback=reason)
            tr.close()
        sched._schedule()

    def _pause_lane(self, lane: int, reason: str) -> None:
        job = self._jobs.pop(lane, None)
        if job is None:
            return
        loop = self._loop
        view = self._finish_view(lane, job)
        rows, ebits, fps = loop.lane_pending(lane)
        lane_checkpoint(job.paths["autosave"], self._model,
                        loop.lane_mirror(lane), rows, ebits, fps,
                        loop.lane_discoveries(lane),
                        loop.lane_state_count(lane))
        loop.deactivate(lane)
        view.finish()
        self._metrics.inc("pauses")
        if reason == "shutdown":
            job.set_state(jobstates.QUEUED, resume=True)
        else:
            job.set_state(jobstates.PAUSED, resume=True)
        self._lane_retire_event(job, lane, "pause")
        sched_trace = self._sched._trace
        if sched_trace:
            sched_trace.emit("job_pause", job=job.id, reason=reason,
                             batch=self.id, lane=lane)
        tr = self._traces.pop(lane, None)
        if tr:
            tr.emit("pause", path=str(job.paths["autosave"]),
                    unique=view.unique_state_count())
            tr.close()

    def _cancel_lane(self, lane: int) -> None:
        job = self._jobs.pop(lane, None)
        if job is None:
            return
        view = self._finish_view(lane, job)
        self._loop.deactivate(lane)
        view.finish()
        job.set_state(jobstates.CANCELLED)
        self._lane_retire_event(job, lane, "cancel")
        if self._sched._trace:
            self._sched._trace.emit("job_done", job=job.id,
                                    state="cancelled", batch=self.id,
                                    lane=lane)
        tr = self._traces.pop(lane, None)
        if tr:
            tr.emit("done", gen=view.state_count(),
                    unique=view.unique_state_count(), cancelled=True)
            tr.close()
