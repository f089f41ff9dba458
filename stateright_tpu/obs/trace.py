"""Structured run-trace: timestamped JSONL events from every engine.

The checker's legacy progress surface was one coarse ``Checking.
states=N`` line (`src/checker.rs:217-242`) — nothing recorded *when*
anything happened, so pipeline stalls, hash-table growth storms and
shard imbalance were invisible after a run. ``RunTrace`` is the
replacement: engines emit small dict events (chunk completed, growth,
candidate-buffer resize, compile, discovery, mirror pull, ...) to a
sink configured via ``tpu_options(trace=...)``:

* a **path** (``str``/``os.PathLike``): JSONL appended line-per-event
  (line-buffered, one ``write()`` per event, so a host-vs-device race
  writing from two engines interleaves whole lines, each tagged with
  its ``engine``);
* a **file-like** object (has ``write``): same JSONL lines;
* a **callable**: called with each event dict (in-process consumers —
  the perf tools attach collectors this way);
* a **list**: events appended as dicts.

Tracing is **zero-cost when off**: with no sink and no subscribers the
checker holds the module singleton :data:`NULL_TRACE`, whose truth
value is ``False`` — engines guard event construction with
``if trace:`` so no dict is ever built. Event timestamps (``t``) are
seconds since the trace was created (monotonic); the ``run_start``
event carries the wall-clock epoch for cross-run alignment.
Fingerprints are emitted as **strings**: they are uint64 and JSON
numbers lose integer precision past 2^53.

Every event dict has ``t``, ``ev`` and ``engine``; per-event required
fields are pinned by :data:`EVENT_SCHEMA` (validated by the obs tests
and ``tools/trace_report.py --validate``).
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional

#: event name -> field names required beyond the base (t, ev, engine).
#: Optional fields (per-shard vectors, rates, reasons) may ride along;
#: consumers must ignore fields they do not know.
EVENT_SCHEMA: Dict[str, frozenset] = {
    # one per run. Since the fleet observability plane (PR 14) engine
    # run_start events additionally carry the CORRELATION HEADER —
    # `run_id` (unique per run), `t0_unix` (the trace's wall-clock
    # anchor: wall(event) = t0_unix + event.t), `host`/`rank`
    # (cluster/mesh.py process_identity), and `job`/`lane` when the
    # service or batch engine drives the run — optional in the schema
    # so pre-header artifacts still validate, but obs/aggregate.py
    # needs them to place a stream on the fleet timeline
    "run_start": frozenset({"model", "wall"}),
    # the header twin for streams with no run_start of their own
    # (service.jsonl, fleet.jsonl): emitted once when the stream opens
    # (emit_trace_header), so obs/aggregate.py can anchor and identify
    # every stream the same way
    "trace_header": frozenset({"run_id", "t0_unix", "host", "rank"}),
    "done": frozenset({"gen", "unique"}),
    "error": frozenset({"error"}),
    # chunk-loop progress (device engines); sharded runs add
    # shard_log/shard_q per-shard vectors and exchange stats
    "chunk": frozenset({"chunk", "gen", "unique", "q_size", "new",
                        "dedup_hit", "load"}),
    # per-level progress (host + per-level device engines)
    "level": frozenset({"level", "frontier", "gen", "unique"}),
    # periodic host-engine progress (every ``_PROGRESS_EVERY`` pops)
    "progress": frozenset({"gen", "unique"}),
    # growth / resize interventions
    "grow": frozenset({"capacity"}),
    "hgrow": frozenset({"hcap", "hovf"}),
    "egrow": frozenset({"ecap"}),
    "kovf": frozenset({"kraw", "kmax"}),
    "compile": frozenset({"reason"}),
    # search record + post-passes
    "mirror_pull": frozenset({"n"}),
    "lasso": frozenset({"nodes", "edges"}),
    "visit": frozenset({"visited", "peak_resident"}),
    # fault injection declared by the model (PR 1 crash–restart)
    "fault_injection": frozenset({"max_crashes"}),
    # a property discovery was recorded
    "discovery": frozenset({"property", "fp"}),
    # resilience layer (checker/resilience.py): a transient-fault
    # recovery (re-seed + resume), a hung chunk sync converted to a
    # classified fault by the watchdog, a checkpoint autosave, and a
    # raced run falling over to the un-budgeted host BFS. retry and
    # failover carry an optional `device` (the blamed chip index, None
    # when unattributable) and retry/watchdog a `shards` mesh width,
    # so postmortems name the chip, not just the attempt count
    "retry": frozenset({"attempt", "delay", "error"}),
    "watchdog": frozenset({"deadline"}),
    "autosave": frozenset({"path", "unique"}),
    "failover": frozenset({"to", "error"}),
    # a degradation-ladder rung: the mesh halved onto the surviving
    # device subset (optional fields: the blamed device, the error)
    "degrade": frozenset({"from_shards", "to_shards"}),
    # the elastic ladder's scale-UP rung (parallel/engine.py
    # promote_step): the mesh doubled onto a granted device subset at
    # a drained chunk boundary — the exact mirror of `degrade`
    # (optional field: the granted device ids); `host_promote` records
    # each NEW host the widened mesh spans (the reverse of the host
    # rung's `host_drop`; optional from/to shard widths)
    "promote": frozenset({"from_shards", "to_shards"}),
    "host_promote": frozenset({"host"}),
    # memory tiering (checker/resilience.py SpillPolicy): `evict`
    # records the range selection (how many fingerprint-prefix ranges
    # were newly evicted and how many keys they held), `spill` the
    # recovery it enabled — the capacity the run stays within, the
    # device-resident hot-set size it re-seeded with, and the host-tier
    # population; optional fields: `reason` (budget / fault / seed /
    # reseed) and `error` (the capacity fault that forced it)
    "evict": frozenset({"prefixes", "keys"}),
    "spill": frozenset({"capacity", "hot", "host_tier_keys"}),
    # silent-corruption defense (checker/resilience.py AuditPolicy +
    # README § Silent corruption defense): `audit` — one sampled
    # redundant re-execution of a chunk's frontier slice (`mismatches`
    # is 0 on a clean pass; optional `device` names the audited shard);
    # `corruption` — the auditor caught wrong results, or an artifact
    # failed its integrity chain (`device` rides along: the blamed chip
    # index, or None for artifact-level corruption such as an autosave
    # generation rollback); `quarantine` — a corruption-blamed device
    # was withheld from the run (and, via service/scheduler.py, from
    # all future grants; `quarantined` is the cumulative count)
    "audit": frozenset({"chunk", "rows", "mismatches"}),
    "corruption": frozenset({"error"}),
    "quarantine": frozenset({"device", "quarantined"}),
    # a fused='auto' configuration is OUTSIDE the kernel's support
    # matrix (sound mode / host properties / hint) and stayed staged —
    # emitted once per run with the supports() reason, so "why didn't
    # this run fuse?" is answerable from the trace, not a shrug
    "fused_unsupported": frozenset({"reason"}),
    # chaos soak harness (actor/chaos.py + tools/soak.py): live
    # crash/restart of one spawned actor (the runtime twin of the
    # modeled Crash/Restart), a partition flip (groups=[] on heal), a
    # periodic op-counter summary (op_invoke/op_return cumulative
    # counts — per-op events would flood the stream), and the soak
    # verdict with the history cross-check result
    "crash": frozenset({"actor"}),
    "restart": frozenset({"actor"}),
    "partition": frozenset({"groups"}),
    "ops": frozenset({"op_invoke", "op_return", "op_timeouts"}),
    # one per soak run/segment: the resolved configuration summary
    # (protocol, op budget, seed; optional clients/online) — the soak
    # twin of run_start's model field, emitted by the driver so
    # service-scheduled soak/fuzz segments self-describe
    "soak_start": frozenset({"protocol", "ops", "seed"}),
    # the consistency cross-check REJECTED the recorded history:
    # `tester` names which semantics failed; optional `op_index` pins
    # the offending operation when the ONLINE checker flagged it
    # mid-run (None for post-hoc rejections), optional `artifact` the
    # auto-filed seed-corpus path
    "violation": frozenset({"tester"}),
    "soak_done": frozenset({"ops", "history_ok"}),
    # the flight recorder (obs/recorder.py) wrote its ring as a JSONL
    # artifact (on error / watchdog expiry / exhausted retries / a
    # degradation rung); optional fields: `reason`, `dropped` (events
    # evicted by the ring bound before the dump)
    "recorder_dump": frozenset({"path", "events"}),
    # pausable runs: the engine drained its pipeline and wrote a
    # resume_from-loadable pause checkpoint (Checker.request_pause —
    # the step-driver/job-service boundary)
    "pause": frozenset({"path", "unique"}),
    # the checking-as-a-service job lifecycle (stateright_tpu/service,
    # engine="service"): submission, placement on a device subset
    # (`width`), a pause (reason: "user" | "preempt" | "shutdown"),
    # resumption (optionally on a different width), and the terminal
    # transition (`state`: done / failed / cancelled; optional fields
    # ride along — unique counts, error strings, the blamed job)
    "job_submit": frozenset({"job", "model", "priority"}),
    # SLO lifecycle stamps (PR 14): `job_grant` — the pool granted the
    # job its device subset (the queue-wait clock stops here);
    # `job_first_chunk` — the job's engine materialized its first
    # chunk (compile/seed latency ends; carries `first_chunk_s`)
    "job_grant": frozenset({"job", "width"}),
    "job_start": frozenset({"job", "width"}),
    "job_first_chunk": frozenset({"job"}),
    "job_pause": frozenset({"job", "reason"}),
    "job_resume": frozenset({"job", "width"}),
    "job_done": frozenset({"job", "state"}),
    # the scheduler's flex controller (README § Elastic fleet):
    # `job_promote` — freed pool width granted to a running
    # width-hungry job (in place via Checker.request_promote, or
    # through the pause/resume-wider checkpoint path; `width` is the
    # new width); `job_demote` — an over-width job preempted under
    # queue pressure to resume on a smaller subset (`width` is the
    # width it gave up)
    "job_promote": frozenset({"job", "width"}),
    "job_demote": frozenset({"job", "width"}),
    # burn-in mode (README § Continuous verification): a low-priority
    # background soak/fuzz job was preempted at an op-count boundary to
    # free its device subset for a real checking job — it re-queues and
    # resumes its remaining op budget later (optional fields: ops_done,
    # the preempting context)
    "burnin_preempt": frozenset({"job"}),
    # device-pool utilization sample (engine="service"): the busy
    # fraction of the whole pool plus the per-host split, emitted on
    # change by the scheduler's utilization sampler — the series
    # tools/fleetboard.py and the fleet timeline read
    "pool_util": frozenset({"busy_frac", "per_host"}),
    # the batch lane engine (service/batch.py + checker/batch_loop.py):
    # `bucket_flush` — a bucket queue launched as a batch (reason:
    # "full" | "max_wait"); `batch_form` — the batch's initial lane
    # fill (jobs seeded, lane width); `lane_retire` — one lane's job
    # left the batch (reason: "done" | "pause" | "cancel" | an
    # abnormal cause like "grow"/"kovf" that falls the job back to the
    # solo engine); optional fields (unique counts, the batch id on
    # job_* events) ride along
    "bucket_flush": frozenset({"bucket", "jobs", "reason"}),
    "batch_form": frozenset({"batch", "bucket", "jobs", "lanes"}),
    "lane_retire": frozenset({"batch", "job", "lane", "reason"}),
    # the fleet layer (stateright_tpu/cluster + the sharded engine on a
    # multi-host mesh): `mesh_init` — the global mesh is up (shard
    # count, distinct hosts, jax processes; optional `dcn_exchange_s`,
    # the timed cross-host psum round trip); `host_join` — one rank's
    # ready marker landed at the launcher (engine="fleet"; optional
    # device counts); `host_drop` — the degradation ladder's host rung
    # dropped an entire host's devices (optional from/to shard widths
    # and the blamed device)
    "mesh_init": frozenset({"shards", "hosts", "procs"}),
    "host_join": frozenset({"host"}),
    "host_drop": frozenset({"host"}),
    # one phase INTERVAL on the trace clock (obs/spans.py): `name` is
    # the phase (dispatch / device / xfer / host / host_probe / mirror
    # / exchange / props / idle), `t0`/`t1` its trace-relative bounds
    # — unlike the flat phase timers these compose under the pipeline:
    # the overlap-aware sweep (spans.analyze, tools/stall_report.py)
    # attributes wall time only where a phase is the unique blocker.
    # Optional identity fields (`chunk`, `shard`, `lane`, `job`) ride
    # along when the emitting loop has them
    "span": frozenset({"name", "t0", "t1"}),
}

_BASE_FIELDS = frozenset({"t", "ev", "engine"})


def validate_event(event: Dict[str, Any]) -> None:
    """Raise ``ValueError`` unless ``event`` matches the schema."""
    missing = _BASE_FIELDS - event.keys()
    if missing:
        raise ValueError(f"trace event missing base fields {sorted(missing)}:"
                         f" {event!r}")
    ev = event["ev"]
    required = EVENT_SCHEMA.get(ev)
    if required is None:
        raise ValueError(f"unknown trace event {ev!r}: {event!r}")
    missing = required - event.keys()
    if missing:
        raise ValueError(
            f"trace event {ev!r} missing fields {sorted(missing)}: "
            f"{event!r}")


class NullTrace:
    """The off switch: falsy, and every emit is a no-op."""

    __slots__ = ()
    enabled = False

    def __bool__(self) -> bool:
        return False

    def emit(self, ev: str, **fields) -> None:
        pass

    def subscribe(self, fn: Callable[[Dict[str, Any]], None]) -> None:
        raise RuntimeError(
            "cannot subscribe to a disabled trace; enable it with "
            "tpu_options(trace=...) (any sink, e.g. trace=[]) first")

    def close(self) -> None:
        pass


#: process-wide disabled trace, shared by every untraced checker
NULL_TRACE = NullTrace()


class RunTrace:
    """A live JSONL event stream plus in-process subscribers.

    Thread-safety contract: sink writes and the flight-recorder append
    run under ``_lock`` (so two engines sharing one file sink
    interleave whole lines); subscriber callbacks run **outside** it on
    a snapshot of the subscriber list, so one slow subscriber (an SSE
    client, a rendering console) can never block an engine writer, and
    ``subscribe`` on a live raced run can never corrupt the iteration
    (the list is replaced, not mutated, under the lock)."""

    def __init__(self, sink: Any = None, engine: str = "?",
                 recorder=None):
        self._engine = engine
        self._t0 = time.monotonic()
        #: wall-clock anchor: every event's absolute time is
        #: ``t0_unix + event["t"]`` — what the correlation header
        #: publishes so obs/aggregate.py can join streams from
        #: different processes/hosts onto one fleet timeline
        self.t0_unix = time.time()
        self._lock = threading.Lock()
        self._subs: List[Callable[[Dict[str, Any]], None]] = []
        self._recorder = recorder
        self._write: Optional[Callable[[str], None]] = None
        self._append: Optional[Callable[[Dict[str, Any]], None]] = None
        self._fh = None
        if sink is None:
            pass
        elif isinstance(sink, (str, os.PathLike)):
            # line-buffered: one write() per event line
            self._fh = open(os.fspath(sink), "a", buffering=1)
            self._write = self._fh.write
        elif callable(sink):
            self._append = sink
        elif hasattr(sink, "append") and not hasattr(sink, "write"):
            self._append = sink.append
        elif hasattr(sink, "write"):
            self._write = sink.write
        else:
            raise TypeError(
                "tpu_options(trace=...) accepts a path, a file-like "
                "object, a callable, or a list; got "
                f"{type(sink).__name__}")

    def __bool__(self) -> bool:
        return (self._write is not None or self._append is not None
                or self._recorder is not None or bool(self._subs))

    @property
    def enabled(self) -> bool:
        return bool(self)

    def subscribe(self, fn: Callable[[Dict[str, Any]], None]) -> None:
        """Register a progress callback invoked with every event dict
        (after the sink write). Callbacks run on the emitting engine's
        thread, outside the sink lock — they may be slow without
        blocking the engine, but must be exception-free."""
        with self._lock:
            # copy-on-write: emit() iterates a snapshot reference, so
            # the list object it captured is never mutated under it
            self._subs = self._subs + [fn]

    def unsubscribe(self, fn: Callable[[Dict[str, Any]], None]) -> None:
        """Remove a subscriber (no-op if absent) — disconnecting SSE
        clients and finished consoles detach this way."""
        with self._lock:
            self._subs = [s for s in self._subs if s is not fn]

    def emit(self, ev: str, **fields) -> None:
        if not self:
            return
        event: Dict[str, Any] = {
            "t": round(time.monotonic() - self._t0, 6),
            "ev": ev, "engine": self._engine}
        event.update(fields)
        with self._lock:
            if self._write is not None:
                self._write(json.dumps(event, separators=(",", ":"))
                            + "\n")
            if self._append is not None:
                self._append(event)
            if self._recorder is not None:
                self._recorder.record(event)
            subs = self._subs
        for fn in subs:
            fn(event)

    def close(self) -> None:
        if self._fh is not None:
            self._fh.close()
            self._fh = None
            self._write = None


def make_trace(sink: Any, engine: str,
               recorder=None) -> "RunTrace | NullTrace":
    """Build the engine's trace from a ``tpu_options(trace=...)`` value
    (``None`` with no recorder -> the shared :data:`NULL_TRACE`). An
    existing ``RunTrace`` passes through re-tagged with this engine's
    name. A ``recorder`` (the always-on flight recorder,
    `obs/recorder.py`) makes the trace truthy even sink-less, so the
    engines' one-branch ``if trace:`` guard covers it."""
    if sink is None and recorder is None:
        return NULL_TRACE
    if isinstance(sink, NullTrace):
        return sink
    if isinstance(sink, RunTrace):
        sink._engine = engine
        if recorder is not None and sink._recorder is None:
            sink._recorder = recorder
        return sink
    return RunTrace(sink, engine=engine, recorder=recorder)


def new_run_id(prefix: str = "run") -> str:
    """A fresh correlation id for one trace stream/run. Short (12 hex
    chars of entropy) but collision-safe at fleet scale; the prefix
    tags the stream kind (``run``/``svc``/``fleet``/``soak``) so a
    merged timeline reads without a legend."""
    import uuid
    return f"{prefix}-{uuid.uuid4().hex[:12]}"


def identity_fields(trace, run_id: str) -> Dict[str, Any]:
    """The correlation-header fields stamped onto ``run_start`` (engine
    streams) or a ``trace_header`` event (service/fleet streams):
    ``run_id``, the stream's wall-clock anchor ``t0_unix``, and this
    process's ``host``/``rank`` (``cluster/mesh.py``)."""
    from ..cluster.mesh import process_identity
    rank, host = process_identity()
    return {"run_id": run_id,
            "t0_unix": getattr(trace, "t0_unix", None),
            "host": host, "rank": rank}


def emit_trace_header(trace, run_id: Optional[str] = None,
                      prefix: str = "run", **extra) -> Optional[str]:
    """Stamp the correlation header on a stream with no ``run_start``
    of its own (the scheduler's ``service.jsonl``, the launcher's
    ``fleet.jsonl``). Returns the run id used (None when the trace is
    disabled). Engine streams do NOT call this — their header rides
    ``run_start`` (``HostChecker._step_wrapper``)."""
    if not trace:
        return None
    run_id = run_id or new_run_id(prefix)
    trace.emit("trace_header", **identity_fields(trace, run_id),
               **extra)
    return run_id


def fault_info(model) -> Optional[Dict[str, Any]]:
    """Crash–restart injection parameters declared by the model (the
    host ``ActorModel.crash_restart`` surface or a packed model built
    from one), or ``None`` when the model injects no faults."""
    for attr in ("max_crashes_", "max_crashes"):
        n = getattr(model, attr, 0)
        if n:
            crashable = getattr(model, "crashable_", None)
            info: Dict[str, Any] = {"max_crashes": int(n)}
            if crashable is not None:
                info["actors"] = list(crashable)
            return info
    inner = getattr(model, "model", None)
    if inner is not None and inner is not model:
        return fault_info(inner)
    return None
