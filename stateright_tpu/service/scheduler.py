"""The job scheduler: pack concurrent checking jobs onto DISJOINT
power-of-two device subsets.

The degradation ladder (``checker/resilience.py DegradePolicy``)
already carves power-of-two device subsets out of a mesh — as a fault
response. This module generalizes that carving to CAPACITY allocation:
:class:`DevicePool` is a TWO-LEVEL buddy allocator — device slices
within hosts, whole hosts within the fleet (an 8-device host can host
one D=4 job + two D=2 jobs + singles, blocks merging back as jobs
finish; a 2-host × 4-device fleet additionally grants a width-8 job
both hosts whole, never a subset straddling a partially-carved host) —
and :class:`Scheduler` drives one worker thread per RUNNING job
through the engines' step generators
(:class:`~stateright_tpu.service.driver.StepDriver`), so every job is
pausable between chunks. Host labels come from ``Scheduler(hosts=...)``
(simulated fleets, tests) or each device's ``process_index`` (real
multi-host pools).

Scheduling policy:

* queued jobs place in (priority desc, submission order) — a job asks
  for ``width`` devices and is granted the largest free power-of-two
  block ≤ its request (down to 1);
* a running job's mesh width changes only at a chunk boundary: by
  default via pause/resume (the checkpoint format is shard-agnostic);
  with ``flex=True`` the elastic controller may also DOUBLE a hungry
  running job in place (``Checker.request_promote`` — the degradation
  ladder run upward) when buddies merge free and the queue is empty,
  and demote over-width jobs first under queue pressure;
* **preemption**: when nothing is free and a queued job outranks a
  running one, the lowest-priority victim is paused (checkpoint
  written, subset released) and re-queued to resume on whatever subset
  remains — typically a smaller one;
* restart recovery: jobs found RUNNING at boot (a killed service)
  re-enqueue and resume from their last autosave; QUEUED jobs simply
  re-enqueue; PAUSED jobs wait for an explicit resume.

Observability: the scheduler emits ``job_submit`` / ``job_start`` /
``job_pause`` / ``job_resume`` / ``job_done`` events (engine
``service``) to ``<root>/service.jsonl`` and keeps the
``jobs_submitted`` / ``jobs_done`` / ``jobs_failed`` / ``preemptions``
/ ``queue_depth`` metrics (``stateright_tpu.obs.GLOSSARY``).
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Dict, List, NamedTuple, Optional, Tuple

from ..obs import Metrics, MetricsRing, emit_trace_header, make_trace
from . import jobs as jobstates
from .driver import DONE, FAILED, RUNNING, StepDriver
from .jobs import (KIND_CHECK, Job, JobSpec, JobStore, TERMINAL_STATES,
                   _atomic_write_json)

#: priority the scheduler's own burn-in jobs run at: below anything a
#: tenant can reasonably submit, so ANY real job outranks (and
#: preempts) the background soak/fuzz load
BURNIN_PRIORITY = -(1 << 20)


class DeviceLease(NamedTuple):
    """A granted device subset: ``offset`` into the pool's device
    list, power-of-two ``width``, the device objects themselves, and
    the host labels the subset spans (one label for slice-level
    leases, several for whole-host fleet leases)."""
    offset: int
    width: int
    devices: Tuple
    hosts: Tuple = ()


def _pow2_floor(n: int) -> int:
    return 1 << (int(n).bit_length() - 1) if n else 0


class DevicePool:
    """TWO-LEVEL buddy allocator: device slices within hosts, whole
    hosts within the fleet.

    Level 1 — within a host: power-of-two, naturally aligned slices
    (``offset % width == 0``), splitting and buddy-merging exactly like
    the degradation ladder's subset carving. Level 2 — across the
    fleet: whole hosts as the allocation unit, buddy-merged in host
    units, so a job wider than one host is granted an aligned run of
    FULLY-FREE hosts (a mesh must never straddle a partially-carved
    host — its all-to-all would share chips with another tenant's
    lanes).

    Construction trims to the fleet shape both levels need: devices
    are grouped host-major (``hosts=`` labels, else each device's
    ``process_index`` — one anonymous host for plain lists, which
    keeps the original single-level behavior bit-for-bit), every host
    contributes the same power-of-two device count, and the host count
    is a power of two.

    Placement policy: slice-level requests prefer the host whose
    smallest adequate free block is TIGHTEST (best fit), breaking a
    fully-free host out of the fleet level only when no partially-used
    host fits — small jobs pack into already-carved hosts, preserving
    whole hosts for fleet-wide work. Not thread-safe on its own; the
    scheduler serializes access."""

    def __init__(self, devices, hosts=None):
        devices = list(devices)
        if not devices:
            raise ValueError("DevicePool needs at least one device")
        if hosts is None:
            hosts = [getattr(d, "process_index", 0) for d in devices]
        else:
            hosts = list(hosts)
            if len(hosts) != len(devices):
                raise ValueError(
                    f"hosts ({len(hosts)}) must label every device "
                    f"({len(devices)})")
        order: List = []
        groups: Dict = {}
        for d, h in zip(devices, hosts):
            if h not in groups:
                groups[h] = []
                order.append(h)
            groups[h].append(d)
        hw = min(_pow2_floor(len(g)) for g in groups.values())
        nh = _pow2_floor(len(order))
        #: devices each host contributes (the slice-level ceiling)
        self.host_width = hw
        #: host labels, in pool order (host ``i`` owns the global
        #: offset range ``[i*host_width, (i+1)*host_width)``)
        self.host_labels: List = order[:nh]
        self._devices = [d for h in self.host_labels
                         for d in groups[h][:hw]]
        self.width = nh * hw
        # level 1: per-host free blocks, GLOBAL offsets (empty dict =
        # the host is wholly at level 2)
        self._local_free: List[Dict[int, set]] = [
            {} for _ in range(nh)]
        # level 2: free blocks of whole hosts, in host units
        self._free_hosts: Dict[int, set] = {nh: {0}}
        # hosts withdrawn mid-run (rolling leave): their indices stay
        # valid — offsets are positional — but their free blocks are
        # gone and release() DISCARDS their returning blocks instead
        # of merging them back, so the host drains as leases end
        self._retired: set = set()

    @property
    def host_count(self) -> int:
        return len(self.host_labels)

    @property
    def active_host_count(self) -> int:
        return len(self.host_labels) - len(self._retired)

    def _host_of_offset(self, offset: int) -> int:
        return offset // self.host_width

    def _carve_host(self) -> Optional[int]:
        """Break the lowest fully-free host out of level 2 for
        slice-level use (splitting its host-block buddy-style)."""
        sizes = sorted(s for s, offs in self._free_hosts.items()
                       if offs)
        if not sizes:
            return None
        size = sizes[0]
        h = min(self._free_hosts[size])
        self._free_hosts[size].discard(h)
        while size > 1:  # keep the upper host-buddy at level 2
            size //= 2
            self._free_hosts.setdefault(size, set()).add(h + size)
        self._local_free[h] = {self.host_width: {h * self.host_width}}
        return h

    def acquire(self, width: int) -> Optional[DeviceLease]:
        width = int(width)
        if width < 1 or (width & (width - 1)) or width > self.width:
            return None
        hw = self.host_width
        if width > hw:
            # fleet level: an aligned run of width/hw fully-free hosts
            k = width // hw
            sizes = sorted(s for s, offs in self._free_hosts.items()
                           if offs and s >= k)
            if not sizes:
                return None
            size = sizes[0]
            h = min(self._free_hosts[size])
            self._free_hosts[size].discard(h)
            while size > k:
                size //= 2
                self._free_hosts.setdefault(size, set()).add(h + size)
            offset = h * hw
            return DeviceLease(
                offset, width,
                tuple(self._devices[offset:offset + width]),
                tuple(self.host_labels[h:h + k]))
        # slice level: best-fit across partially-used hosts first
        best = None  # (block_size, host)
        for hi, free in enumerate(self._local_free):
            sizes = [s for s, offs in free.items()
                     if offs and s >= width]
            if sizes:
                cand = (min(sizes), hi)
                if best is None or cand < best:
                    best = cand
        if best is None:
            hi = self._carve_host()
            if hi is None:
                return None
            best = (hw, hi)
        size, hi = best
        free = self._local_free[hi]
        offset = min(free[size])
        free[size].discard(offset)
        while size > width:  # split, keeping the upper buddy free
            size //= 2
            free.setdefault(size, set()).add(offset + size)
        return DeviceLease(offset, width,
                           tuple(self._devices[offset:offset + width]),
                           (self.host_labels[hi],))

    def release(self, lease: DeviceLease) -> None:
        offset, width = lease.offset, lease.width
        hw = self.host_width
        if width > hw:
            h, k = offset // hw, width // hw
            if self._retired & set(range(h, h + k)):
                # the fleet lease touched a retired host: hand back
                # only the still-active hosts, one by one
                for i in range(h, h + k):
                    if i not in self._retired:
                        self._merge_hosts(i, 1)
                return
            self._merge_hosts(h, k)
            return
        hi = self._host_of_offset(offset)
        if hi in self._retired:
            return  # the host left the fleet; its block leaves the pool
        free = self._local_free[hi]
        while width < hw:  # merge with the free buddy (host-local)
            rel = offset - hi * hw
            buddy = hi * hw + (rel ^ width)
            if buddy not in free.get(width, ()):
                break
            free[width].discard(buddy)
            offset = min(offset, buddy)
            width *= 2
        if width == hw:
            # the host is whole again: hand it back to the fleet level
            self._local_free[hi] = {}
            self._merge_hosts(hi, 1)
        else:
            free.setdefault(width, set()).add(offset)

    def _merge_hosts(self, h: int, k: int) -> None:
        nh = len(self.host_labels)
        while k < nh:  # buddy merge in host units
            buddy = h ^ k
            if buddy not in self._free_hosts.get(k, ()):
                break
            self._free_hosts[k].discard(buddy)
            h = min(h, buddy)
            k *= 2
        self._free_hosts.setdefault(k, set()).add(h)

    # --- silent-corruption quarantine ----------------------------------
    def carve_out(self, pos: int) -> bool:
        """Withhold the single device at global offset ``pos`` from
        every future grant (README § Silent corruption defense): its
        width-1 block is split out of the free structures and simply
        never re-freed — the buddy allocator's own alignment rules
        then keep every later lease away from it. Returns False when
        the block is currently LEASED (the caller retries after the
        holding lease releases) or ``pos`` is out of range; a retired
        host's devices are already out of the pool (True)."""
        hw = self.host_width
        if not 0 <= pos < len(self._devices):
            return False
        hi = pos // hw
        if hi in self._retired:
            return True
        # the host may still be at the fleet level: break its block
        # down to this single host first (buddy-style, keeping every
        # other host of the block free)
        for s, offs in list(self._free_hosts.items()):
            for h in list(offs):
                if h <= hi < h + s:
                    offs.discard(h)
                    for k in range(h, h + s):
                        if k != hi:
                            self._merge_hosts(k, 1)
                    self._local_free[hi] = {hw: {hi * hw}}
                    break
        free = self._local_free[hi]
        for size in sorted(free):
            for off in sorted(free[size]):
                if off <= pos < off + size:
                    free[size].discard(off)
                    while size > 1:  # split, freeing the clean halves
                        size //= 2
                        if pos >= off + size:
                            free.setdefault(size, set()).add(off)
                            off += size
                        else:
                            free.setdefault(size, set()).add(off + size)
                    return True  # pos's width-1 block left the pool
        return False

    def readmit(self, pos: int) -> None:
        """Return a quarantined device's width-1 block to the free
        structures (probation passed — :meth:`Scheduler.audit_probe`);
        it buddy-merges back like any releasing lease."""
        hw = self.host_width
        hi = pos // hw
        if not 0 <= pos < len(self._devices) or hi in self._retired:
            return
        self.release(DeviceLease(pos, 1, (self._devices[pos],),
                                 (self.host_labels[hi],)))

    def free_width(self) -> int:
        local = sum(s * len(offs)
                    for free in self._local_free
                    for s, offs in free.items())
        fleet = sum(s * len(offs) * self.host_width
                    for s, offs in self._free_hosts.items())
        return local + fleet

    def largest_free(self) -> int:
        local = [s for free in self._local_free
                 for s, offs in free.items() if offs]
        fleet = [s * self.host_width
                 for s, offs in self._free_hosts.items() if offs]
        avail = local + fleet
        return max(avail) if avail else 0

    def per_host_free(self) -> Dict:
        """Free device count per host label (the fleet-utilization
        view bench's multihost smoke and operators read). Retired
        hosts are omitted — they are no longer capacity."""
        out = {h: 0 for hi, h in enumerate(self.host_labels)
               if hi not in self._retired}
        for hi, free in enumerate(self._local_free):
            if hi in self._retired:
                continue
            out[self.host_labels[hi]] += sum(
                s * len(offs) for s, offs in free.items())
        for s, offs in self._free_hosts.items():
            for h in offs:
                for hi in range(h, h + s):
                    out[self.host_labels[hi]] += self.host_width
        return out

    # --- elastic fleet: rolling host join / leave ----------------------
    def add_host(self, label, devices) -> int:
        """Register a freshly-ready host's devices as new free pool
        width MID-RUN (the rolling-join half of the elastic fleet).

        The host lands as one fully-free level-2 block and buddy-merges
        with its aligned neighbors, so joining the 4th host of a
        2-wide fleet restores a fleet-level width-4·hw block. A host
        count that is momentarily not a power of two degrades
        gracefully — the odd host serves slice-level and single-host
        work until its buddy arrives. Brings exactly ``host_width``
        devices into play (extras are ignored, keeping every host's
        contribution uniform); returns the new host index."""
        devices = list(devices)
        if label in self.host_labels:
            raise ValueError(f"host {label!r} is already in the pool")
        if len(devices) < self.host_width:
            raise ValueError(
                f"a joining host must bring at least host_width="
                f"{self.host_width} devices (got {len(devices)})")
        h = len(self.host_labels)
        self.host_labels.append(label)
        self._devices.extend(devices[:self.host_width])
        self._local_free.append({})
        self.width += self.host_width
        self._merge_hosts(h, 1)
        return h

    def retire_host(self, label) -> List:
        """Withdraw a host's FREE width so nothing new lands there
        (the rolling-leave half). Busy slices drain as their leases
        release — ``release`` discards a retired host's blocks instead
        of merging them back. Level-2 blocks spanning the host are
        broken up and their still-active hosts re-freed. Returns the
        withdrawn device objects."""
        hi = self.host_labels.index(label)
        if hi in self._retired:
            raise ValueError(f"host {label!r} is already retired")
        self._retired.add(hi)
        for s, offs in list(self._free_hosts.items()):
            for h in list(offs):
                if h <= hi < h + s:
                    offs.discard(h)
                    for k in range(h, h + s):
                        if k != hi and k not in self._retired:
                            self._merge_hosts(k, 1)
        self._local_free[hi] = {}
        self.width -= self.host_width
        hw = self.host_width
        return self._devices[hi * hw:(hi + 1) * hw]


class _JobRuntime:
    """Scheduler-side handle on one RUNNING job: the live checker and
    driver (for the HTTP API's SSE/metrics), the worker thread, and a
    one-slot control channel (pause / preempt / shutdown / cancel)."""

    __slots__ = ("lease", "thread", "checker", "driver", "_control",
                 "_ctl_lock", "granted_at", "first_chunk_seen",
                 "burnin", "promote_lease", "flexed_at")

    def __init__(self, lease: DeviceLease):
        self.lease = lease
        self.thread: Optional[threading.Thread] = None
        self.checker = None
        self.driver: Optional[StepDriver] = None
        self._control: Optional[str] = None
        self._ctl_lock = threading.Lock()
        # SLO lifecycle stamps (PR 14): when the pool granted the
        # subset, and whether the first-chunk latency has been recorded
        self.granted_at = time.time()
        self.first_chunk_seen = False
        #: burn-in lane marker (set at launch) — the utilization
        #: sampler splits pool occupancy into burnin_frac with it
        self.burnin = False
        #: the SECOND lease a flex promote granted (the in-place
        #: widen): held until the job exits, or released immediately
        #: when the engine declines the grant at the chunk boundary
        self.promote_lease: Optional[DeviceLease] = None
        #: last flex action stamp (per-job hysteresis window)
        self.flexed_at = 0.0

    def set_control(self, ctl: str) -> None:
        with self._ctl_lock:
            # cancel beats pause; a pending flex promote yields to
            # ANY other request (widening is opportunistic — a pause/
            # preempt/cancel racing it must not be dropped); otherwise
            # first request wins
            if self._control is None or ctl == "cancel" \
                    or (self._control == "promote"
                        and ctl != "promote"):
                self._control = ctl

    def take_control(self) -> Optional[str]:
        with self._ctl_lock:
            ctl, self._control = self._control, None
            return ctl


class _BatchRuntime:
    """Scheduler-side handle on one RUNNING batch: the device lease,
    the worker thread, the live :class:`~stateright_tpu.service.batch.
    BatchRun`, and a multi-slot control channel (per-job pause/cancel
    plus shutdown)."""

    __slots__ = ("lease", "thread", "run", "_controls", "_ctl_lock")

    def __init__(self, lease: DeviceLease):
        self.lease = lease
        self.thread: Optional[threading.Thread] = None
        self.run = None
        self._controls: List[tuple] = []
        self._ctl_lock = threading.Lock()

    def set_control(self, ctl: str, job_id: Optional[str] = None) \
            -> None:
        with self._ctl_lock:
            self._controls.append((ctl, job_id))

    def take_controls(self) -> List[tuple]:
        with self._ctl_lock:
            ctls, self._controls = self._controls, []
            return ctls


class Scheduler:
    """Multi-tenant job scheduler over the device mesh."""

    def __init__(self, store, devices=None, step_budget: int = 4,
                 trace=None, recover: bool = True,
                 batch_lanes: Optional[int] = None,
                 batch_wait: Optional[float] = None, hosts=None,
                 burnin: Optional[dict] = None,
                 corpus_dir: Optional[str] = None,
                 flex: bool = False, flex_interval: float = 5.0):
        from .batch import DEFAULT_LANES, DEFAULT_MAX_WAIT
        self._store = store if isinstance(store, JobStore) \
            else JobStore(store)
        self._lock = threading.RLock()
        self._running: Dict[str, _JobRuntime] = {}
        #: job id -> its worker thread, until the worker's exit
        #: bookkeeping (lease release, quarantine harvest and its
        #: persisted record) is done: a job turns terminal BEFORE that,
        #: so :meth:`wait` joins the thread it finds here
        self._workers: Dict[str, threading.Thread] = {}
        self._closed = False
        self._step_budget = max(1, int(step_budget))
        self._metrics = Metrics()
        self._trace = make_trace(
            self._store.service_trace_path if trace is None else trace,
            engine="service")
        # correlation header: service.jsonl has no run_start of its
        # own, so the scheduler stamps a trace_header at boot (a
        # restarted scheduler appends a new header — obs/aggregate.py
        # segments the stream on it)
        self._run_id = emit_trace_header(self._trace, prefix="svc")
        # --- utilization + SLO accounting (PR 14) ----------------------
        #: completion wall times inside the trailing 60s window (the
        #: jobs_per_min gauge)
        self._done_times: deque = deque()
        #: bounded busy-fraction time series (per-host split included
        #: in every sample; obs/metrics.py MetricsRing)
        self._util_ring = MetricsRing(limit=512, interval=1.0)
        self._util_prev: Optional[tuple] = None
        self._util_thread: Optional[threading.Thread] = None
        self._devices = None if devices is None else list(devices)
        #: per-device host labels (simulated fleets / real
        #: process_index grouping) — the two-level pool's second level
        self._hosts = None if hosts is None else list(hosts)
        self._pool: Optional[DevicePool] = None
        # --- batch lane engine (service/batch.py): same-bucket small
        # jobs coalesce in per-bucket queues and run as lanes of ONE
        # vmapped chunk program on a width-1 allocation
        self._batch_lanes = int(batch_lanes if batch_lanes is not None
                                else DEFAULT_LANES)
        self._batch_wait = float(batch_wait if batch_wait is not None
                                 else DEFAULT_MAX_WAIT)
        #: bucket key -> {"jobs": deque[Job], "label", "model",
        #: "capacity", "fmax", "since"}
        self._buckets: Dict[tuple, dict] = {}
        self._batch_running: Dict[tuple, _BatchRuntime] = {}
        self._job_batch: Dict[str, tuple] = {}
        self._batch_reason: Dict[str, str] = {}
        self._bucket_keys_seen: set = set()
        self._batch_seq = 0
        self._flush_timer: Optional[threading.Timer] = None
        # --- continuous verification fleet (PR 15) ---------------------
        #: burn-in mode: keep the pool saturated with low-priority
        #: seeded soak/fuzz jobs that preempt cleanly at op boundaries
        #: when real work arrives. Spec keys: "config" (SOAK_REGISTRY
        #: name), "kind" ("fuzz" default | "soak"), "overrides"
        #: (SoakConfig fields), "seed0" (first seed), "max_jobs"
        #: (total burn-in jobs to synthesize; None = keep refilling)
        self._burnin = dict(burnin) if burnin else None
        self._burnin_seq = 0
        #: where rejected soak/fuzz histories are auto-filed under
        #: their (protocol, tester, sha256(ops)) dedup key — point it
        #: at tests/soak_seeds to feed the regression corpus; None
        #: keeps artifacts inside each job's directory
        self._corpus_dir = corpus_dir
        # --- elastic flex controller (promote-on-freed-width) ----------
        #: opt-in: the default keeps the historical "a running job's
        #: width never changes mid-flight" contract for existing
        #: deployments. With ``flex=True`` every placement pass that
        #: leaves the queue empty may widen ONE hungry running job
        #: (granted < requested) onto freed width — in place for
        #: width>=2 sharded jobs, via checkpoint migration for singles
        self._flex = bool(flex)
        #: hysteresis window between flex actions (fleet-wide AND
        #: per-job), bounding promote/demote churn under bursty load
        self._flex_interval = float(flex_interval)
        self._flex_last = 0.0
        #: extra device-width currently out on promote leases (the
        #: flex_width gauge; symmetric grant/release accounting)
        self._flex_extra = 0
        # --- silent-corruption quarantine (persisted fleet state) ------
        #: device key (stable ``.id``, else pool offset) -> blame
        #: record. Loaded from ``<root>/quarantine.json`` at boot and
        #: re-written on every change, so a chip the chunk auditor
        #: caught lying stays withheld across service restarts until
        #: :meth:`audit_probe` re-admits it
        self._quarantine_path = os.path.join(self._store.root,
                                             "quarantine.json")
        self._quarantined: Dict[str, dict] = {}
        try:
            import json
            with open(self._quarantine_path) as f:
                self._quarantined = {str(k): dict(v)
                                     for k, v in json.load(f).items()}
        except (OSError, ValueError):
            self._quarantined = {}
        if recover:
            self._recover()
            # boot placement pass: recovered RUNNING jobs (and any
            # still-QUEUED ones) must not wait for the next submit —
            # and a burn-in scheduler saturates the pool at boot
            if self._burnin is not None \
                    or any(j.state == jobstates.QUEUED
                           for j in self._store.jobs()):
                self._schedule()

    # --- introspection -------------------------------------------------
    @property
    def store(self) -> JobStore:
        return self._store

    def profile(self) -> dict:
        return self._metrics.snapshot()

    def jobs(self) -> List[Job]:
        return self._store.jobs()

    def job(self, job_id: str) -> Optional[Job]:
        return self._store.get(job_id)

    def checker_for(self, job_id: str):
        """The live checker of a RUNNING job (None otherwise) — the
        HTTP API's hook for per-job SSE/metrics. A batched job returns
        its :class:`~stateright_tpu.service.batch.LaneView`, which
        speaks the same surface (``_trace`` for SSE, ``profile`` /
        counts for metrics)."""
        with self._lock:
            rt = self._running.get(job_id)
            if rt is not None:
                return rt.checker
            key = self._job_batch.get(job_id)
            if key is not None:
                brt = self._batch_running.get(key)
                if brt is not None and brt.run is not None:
                    return brt.run.view_for(job_id)
        return None

    def pool_width(self) -> int:
        self._ensure_pool()
        return self._pool.width

    # --- lifecycle -----------------------------------------------------
    def submit(self, spec: JobSpec) -> Job:
        if self._closed:
            raise RuntimeError("scheduler is shut down")
        job = self._store.create(spec)
        self._metrics.inc("jobs_submitted")
        self._trace.emit("job_submit", job=job.id,
                         model=spec.model_name, priority=spec.priority)
        self._schedule()
        return job

    def pause(self, job_id: str) -> bool:
        """Pause a job: a RUNNING one checkpoints at the next chunk
        boundary; a QUEUED one is simply held. Returns False for
        unknown/terminal jobs."""
        job = self._store.get(job_id)
        if job is None:
            return False
        with self._lock:
            rt = self._running.get(job_id)
            if rt is not None:
                rt.set_control("pause")
                return True
            brt = self._batch_rt_for(job_id)
            if brt is not None:
                brt.set_control("pause", job_id)
                return True
            if job.state == jobstates.QUEUED:
                self._drop_from_bucket(job_id)
                job.set_state(jobstates.PAUSED,
                              resume=job.has_checkpoint())
                self._trace.emit("job_pause", job=job.id, reason="user")
                return True
        return False

    def resume(self, job_id: str) -> bool:
        """Re-enqueue a PAUSED job (it resumes from its pause
        checkpoint on whatever subset the pool can grant)."""
        job = self._store.get(job_id)
        if job is None or job.state != jobstates.PAUSED:
            return False
        job.set_state(jobstates.QUEUED, resume=job.has_checkpoint())
        self._schedule()
        return True

    def cancel(self, job_id: str) -> bool:
        job = self._store.get(job_id)
        if job is None or job.state in TERMINAL_STATES:
            return False
        with self._lock:
            rt = self._running.get(job_id)
            if rt is not None:
                rt.set_control("cancel")
                return True
            brt = self._batch_rt_for(job_id)
            if brt is not None:
                brt.set_control("cancel", job_id)
                return True
            self._drop_from_bucket(job_id)
        job.set_state(jobstates.CANCELLED)
        self._trace.emit("job_done", job=job.id, state="cancelled")
        self._schedule()
        return True

    def wait(self, job_id: str, timeout: float = 60.0,
             states=TERMINAL_STATES) -> str:
        """Poll until the job reaches one of ``states`` (default: a
        terminal state; a terminal state also waits out the worker's
        exit bookkeeping); returns the state reached (or the current
        one on timeout)."""
        deadline = time.monotonic() + timeout
        job = self._store.get(job_id)
        while job is not None and job.state not in states \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        if job is None:
            return "unknown"
        worker = self._workers.get(job_id)
        if job.state in TERMINAL_STATES and worker is not None \
                and worker is not threading.current_thread():
            # the state flips inside the worker, before its exit
            # bookkeeping: a caller reading quarantined() or the pool
            # right after wait() must see that bookkeeping done
            worker.join(max(0.0, deadline - time.monotonic()))
        return job.state

    def shutdown(self, wait: bool = True, timeout: float = 60.0) -> None:
        """Stop placing work and pause every RUNNING job (each lands
        its checkpoint and re-enqueues, so the next boot resumes it).
        Batched lanes checkpoint per lane; bucket-queued jobs simply
        stay QUEUED for the next boot."""
        with self._lock:
            self._closed = True
            rts = list(self._running.values())
            brts = list(self._batch_running.values())
            if self._flush_timer is not None:
                self._flush_timer.cancel()
                self._flush_timer = None
        for rt in rts:
            rt.set_control("shutdown")
        for brt in brts:
            brt.set_control("shutdown")
        if wait:
            deadline = time.monotonic() + timeout
            for rt in rts + brts:
                t = rt.thread
                if t is not None:
                    t.join(max(0.0, deadline - time.monotonic()))
        self._trace.close()

    # --- elastic fleet: rolling host join / leave ----------------------
    def join_host(self, label, devices) -> int:
        """Rolling host join: register a freshly-ready host's devices
        as new free pool width MID-RUN and immediately re-run
        placement — queued jobs place wider, and with ``flex=True``
        hungry running jobs promote onto the widened fleet. Emits
        ``host_join`` (the same event the fleet launcher stamps when a
        rank's ready marker lands). Returns the new host index."""
        with self._lock:
            if self._closed:
                raise RuntimeError("scheduler is shut down")
            self._ensure_pool()
            hi = self._pool.add_host(label, devices)
            self._metrics.set("hosts", self._pool.active_host_count)
            self._trace.emit("host_join", host=str(label),
                             devices=self._pool.host_width)
        self._schedule()
        return hi

    def leave_host(self, label) -> List:
        """Rolling host leave: withdraw the host's free width so
        nothing new lands there, then preempt every job whose lease
        touches it — each checkpoints at its next chunk boundary and
        re-places on the remaining fleet through the shard-agnostic
        resume path (the demote mirror of :meth:`join_host`). Returns
        the withdrawn device objects."""
        with self._lock:
            if self._closed:
                raise RuntimeError("scheduler is shut down")
            self._ensure_pool()
            if label not in self._pool.host_labels:
                raise ValueError(f"unknown host {label!r}")
            gone = self._pool.retire_host(label)
            self._metrics.set("hosts", self._pool.active_host_count)
            self._trace.emit("host_drop", host=str(label))
            for rt in self._running.values():
                if label in rt.lease.hosts or (
                        rt.promote_lease is not None
                        and label in rt.promote_lease.hosts):
                    rt.set_control("preempt")
            for brt in self._batch_running.values():
                if label in brt.lease.hosts:
                    # batched lanes checkpoint and their jobs requeue
                    brt.set_control("shutdown")
        self._schedule()
        return gone

    # --- recovery ------------------------------------------------------
    def _recover(self) -> None:
        """Boot pass over the durable store: QUEUED jobs re-enqueue;
        jobs found RUNNING (a killed service) re-enqueue with their
        last autosave as the resume point (or from scratch when none
        landed); PAUSED jobs stay paused until an explicit resume.
        Non-durable (callable-factory) jobs cannot be rebuilt and
        fail."""
        for job in self._store.jobs():
            if job.state != jobstates.RUNNING:
                continue
            if not job.spec.durable:
                job.set_state(jobstates.FAILED, error=(
                    "service restarted and the job's model factory "
                    "was a callable (non-durable spec); submit named "
                    "models for restart-safe jobs"))
                self._metrics.inc("jobs_failed")
                self._trace.emit("job_done", job=job.id,
                                 state="failed")
                continue
            job.set_state(jobstates.QUEUED, recovered=True,
                          resume=job.has_checkpoint())

    # --- placement core ------------------------------------------------
    def _ensure_pool(self) -> None:
        if self._pool is None:
            if self._devices is None:
                import jax
                self._devices = list(jax.devices())
            self._pool = DevicePool(self._devices, hosts=self._hosts)
            self._metrics.set("hosts", self._pool.host_count)
            # persisted quarantine survives restarts: carve every
            # still-blamed device back out before any grant lands
            for key in self._quarantined:
                pos = self._pool_pos(key)
                if pos is not None:
                    self._pool.carve_out(pos)
            self._metrics.set("quarantined", len(self._quarantined))
            # the utilization sampler: one busy-fraction sample per
            # second while the service lives (plus a synchronous
            # sample after every placement pass, so tests and bursty
            # schedulers see every occupancy step without sleeping)
            self._util_thread = threading.Thread(
                target=self._util_ring.sample_until,
                args=(self._util_sample, lambda: self._closed),
                name="stateright-util-sampler", daemon=True)
            self._util_thread.start()

    # --- utilization accounting (PR 14) --------------------------------
    def _util_sample(self) -> dict:
        """One busy-fraction sample of the device pool (called under
        no lock by the sampler thread; takes the scheduler lock for a
        consistent pool view). Sets the ``pool_busy_frac`` gauge and
        emits a ``pool_util`` event when occupancy changed."""
        with self._lock:
            if self._pool is None:
                return {"busy_frac": 0.0, "per_host": {},
                        "queue_depth": 0, "burnin_frac": 0.0}
            per_free = self._pool.per_host_free()
            hw = self._pool.host_width
            width = self._pool.width
            free = self._pool.free_width()
            depth = int(self._metrics.get("queue_depth", 0) or 0)
            burn_w = sum(rt.lease.width
                         for rt in self._running.values() if rt.burnin)
        per_host = {str(h): round(1.0 - f / hw, 4)
                    for h, f in per_free.items()}
        busy = round(1.0 - free / width, 4) if width else 0.0
        burn = round(burn_w / width, 4) if width else 0.0
        self._metrics.set("pool_busy_frac", busy)
        self._metrics.set("burnin_frac", burn)
        fingerprint = (busy, burn, tuple(sorted(per_host.items())))
        if fingerprint != self._util_prev:
            self._util_prev = fingerprint
            self._trace.emit("pool_util", busy_frac=busy,
                             per_host=per_host, queue_depth=depth,
                             burnin_frac=burn)
        return {"busy_frac": busy, "per_host": per_host,
                "queue_depth": depth, "burnin_frac": burn}

    def utilization(self) -> dict:
        """The live utilization view (`GET /utilization`): current
        pool occupancy plus the sampler's bounded time series."""
        current = self._util_sample()
        self._util_ring.add(current)
        return {"width": self._pool.width if self._pool else 0,
                "hosts": (self._pool.host_count if self._pool
                          else 0),
                **current,
                "quarantined": sorted(self._quarantined),
                "samples": self._util_ring.snapshot()}

    def prom_rows(self) -> list:
        """``(labels, registry)`` rows for the Prometheus exposition
        (``obs/prom.py``): the scheduler's own registry unlabeled,
        plus every LIVE per-job registry under ``job``/``host``
        labels (batches export one row under their batch id — the
        lanes share one registry)."""
        rows = [({}, self._metrics.snapshot())]
        with self._lock:
            running = [(jid, rt.checker,
                        ",".join(str(h) for h in rt.lease.hosts))
                       for jid, rt in self._running.items()]
            batches = [(brt.run, ",".join(str(h) for h in
                                          brt.lease.hosts))
                       for brt in self._batch_running.values()
                       if brt.run is not None]
        for jid, checker, hosts in running:
            if checker is None:
                continue
            try:
                rows.append(({"job": jid, "host": hosts},
                             checker.profile()))
            except Exception:
                continue  # a mid-teardown profile race drops one row
        for run, hosts in batches:
            try:
                rows.append(({"job": run.id, "host": hosts},
                             run._metrics.snapshot()))
            except Exception:
                continue
        return rows

    # --- batch lane engine plumbing (service/batch.py) -----------------
    def _batch_rt_for(self, job_id: str) -> Optional[_BatchRuntime]:
        """The RUNNING batch currently holding ``job_id`` as a lane
        (None when the job is not a live batched lane). Caller holds
        the lock."""
        key = self._job_batch.get(job_id)
        if key is None:
            return None
        brt = self._batch_running.get(key)
        if brt is None or brt.run is None:
            return None
        if brt.run.view_for(job_id) is None:
            return None
        return brt

    def _drop_from_bucket(self, job_id: str) -> None:
        """Remove a still-queued job from its bucket queue (pause and
        cancel of not-yet-seeded batched jobs). Caller holds the
        lock."""
        key = self._job_batch.pop(job_id, None)
        bucket = self._buckets.get(key) if key is not None else None
        if bucket is not None:
            bucket["jobs"] = deque(
                j for j in bucket["jobs"] if j.id != job_id)

    def _pop_bucket_job(self, key: tuple) -> Optional[Job]:
        """The running batch's backfill feed: the next queued job of
        the bucket, or None when the queue is dry."""
        with self._lock:
            bucket = self._buckets.get(key)
            if bucket and bucket["jobs"]:
                return bucket["jobs"].popleft()
            return None

    def _route_to_bucket(self, job: Job) -> bool:
        """Decide (once per job) whether ``job`` coalesces into a
        bucket queue instead of taking a solo placement. Caller holds
        the lock."""
        from .batch import plan_batch
        solo_bound = (not job.spec.batch
                      or job.status.get("batch_fallback")
                      or (job.status.get("resume")
                          and job.has_checkpoint()))
        if job.id in self._job_batch:
            if solo_bound:
                # the job LEFT the batch lifecycle (abnormal-lane
                # fallback, or a paused lane resuming from its
                # checkpoint): un-map it so solo placement takes it
                self._job_batch.pop(job.id, None)
                return False
            return True  # already bucketed (waiting or running)
        if job.id in self._batch_reason:
            return False
        if solo_bound:
            # fallback and resumed jobs take the solo engine (growth /
            # checkpoint machinery lives there)
            if job.spec.batch:
                self._batch_reason[job.id] = "fallback-or-resume"
            return False
        reason, model, key, label = plan_batch(job.spec)
        if reason is not None:
            self._batch_reason[job.id] = reason
            return False
        bucket = self._buckets.get(key)
        if bucket is None:
            from .batch import normalize_shapes
            capacity, fmax = normalize_shapes(job.spec.options)
            # chunk_steps is DATA (not part of the compile key), so
            # the bucket simply adopts the first job's value
            bucket = {"jobs": deque(), "label": label, "model": model,
                      "capacity": capacity, "fmax": fmax,
                      "chunk_steps": int(job.spec.options.get(
                          "chunk_steps", 32)),
                      "since": time.monotonic()}
            self._buckets[key] = bucket
        elif not bucket["jobs"]:
            bucket["since"] = time.monotonic()
        if key in self._bucket_keys_seen:
            # a later user landed in an already-seen compile bucket —
            # the normalizer doing its job across submissions
            self._metrics.inc("bucket_hits")
        else:
            self._bucket_keys_seen.add(key)
        bucket["jobs"].append(job)
        self._job_batch[job.id] = key
        return True

    def _flush_buckets(self) -> None:
        """Start a batch for every bucket that is FULL (>= lanes jobs)
        or has waited past the max-wait window; arm the flush timer
        for the rest. Caller holds the lock."""
        now = time.monotonic()
        next_due = None
        for key, bucket in self._buckets.items():
            if not bucket["jobs"] or key in self._batch_running:
                continue
            waited = now - bucket["since"]
            full = len(bucket["jobs"]) >= self._batch_lanes
            if full or waited >= self._batch_wait:
                self._start_batch(key, bucket,
                                  reason="full" if full else "max_wait")
            else:
                due = self._batch_wait - waited
                next_due = due if next_due is None \
                    else min(next_due, due)
        if next_due is not None and self._flush_timer is None:
            timer = threading.Timer(next_due + 0.01, self._flush_tick)
            timer.daemon = True
            self._flush_timer = timer
            timer.start()

    def _flush_tick(self) -> None:
        with self._lock:
            self._flush_timer = None
        self._schedule()

    def _start_batch(self, key: tuple, bucket: dict,
                     reason: str) -> None:
        """Place one batch as a width-1 pool allocation and launch its
        worker. Caller holds the lock; no-op (retried on the next
        pass) when the pool is saturated."""
        from .batch import BatchRun
        lease = self._pool.acquire(1)
        if lease is None:
            return
        self._batch_seq += 1
        batch_id = f"b{self._batch_seq:03d}"
        brt = _BatchRuntime(lease)
        run = BatchRun(batch_id, key, bucket["label"], bucket["model"],
                       self._batch_lanes, bucket["capacity"],
                       bucket["fmax"], self, brt,
                       chunk_steps=bucket["chunk_steps"])
        brt.run = run
        self._batch_running[key] = brt
        self._trace.emit("bucket_flush", bucket=bucket["label"],
                         jobs=len(bucket["jobs"]), reason=reason,
                         batch=batch_id)
        thread = threading.Thread(
            target=self._run_batch, args=(key, brt),
            name=f"stateright-batch-{batch_id}", daemon=True)
        brt.thread = thread
        thread.start()

    def _run_batch(self, key: tuple, brt: _BatchRuntime) -> None:
        run = brt.run
        try:
            import contextlib

            import jax
            lease = brt.lease
            ctx = (jax.default_device(lease.devices[0])
                   if lease.width == 1 else contextlib.nullcontext())
            with ctx:
                run.run()
        except BaseException as exc:
            # the batch engine died: fail its live lanes loudly (their
            # artifacts hold whatever landed) — queued bucket jobs are
            # untouched and re-batch on the next pass
            for lane, job in list(run._jobs.items()):
                self._metrics.inc("jobs_failed")
                job.set_state(jobstates.FAILED,
                              error=f"{type(exc).__name__}: {exc}")
                self._trace.emit("job_done", job=job.id,
                                 state="failed", batch=run.id,
                                 error=f"{type(exc).__name__}: {exc}")
        finally:
            run.close()
            with self._lock:
                self._batch_running.pop(key, None)
                self._pool.release(brt.lease)
            self._schedule()

    def _schedule(self) -> None:
        """One placement pass (called on submit / resume / job exit):
        route batch-eligible small jobs into bucket queues (flushed as
        lane batches when full or past max-wait), then grant the
        remaining queued jobs the largest free power-of-two subset ≤
        their request, highest priority first; when nothing is free,
        preempt the lowest-priority running job that the queue head
        outranks."""
        with self._lock:
            if self._closed:
                return
            self._ensure_pool()
            queued = [j for j in self._store.jobs()
                      if j.state == jobstates.QUEUED
                      and j.id not in self._running]
            queued.sort(key=lambda j: (-j.priority, j.seq))
            queued = [j for j in queued
                      if not self._route_to_bucket(j)]
            self._flush_buckets()
            for job in queued:
                want = min(job.spec.width, self._pool.width)
                lease = None
                width = want
                while width >= 1 and lease is None:
                    lease = self._pool.acquire(width)
                    width //= 2
                if lease is None:
                    self._maybe_preempt(job)
                    continue
                self._launch(job, lease)
            # flex BEFORE burn-in: a finishing job's buddy-merged width
            # goes to a promotion-eligible RUNNING job first (this pass
            # runs on every release, fixing the historical gap where
            # freed width was only ever offered to QUEUED jobs), and
            # only what flex declines is soaked by burn-in below
            self._flex_pass()
            # burn-in AFTER real placement: leftover free width is
            # soaked with low-priority fuzz work (re-queued burn-in
            # jobs re-place through the queued loop above first, so
            # preempted segments resume before new seeds spawn)
            self._fill_burnin()
            depth = sum(1 for j in self._store.jobs()
                        if j.state == jobstates.QUEUED
                        and j.id not in self._running)
            self._metrics.set("queue_depth", depth)
        # synchronous utilization step: every placement pass lands a
        # sample, so occupancy edges are never lost between the 1 Hz
        # sampler ticks. OUTSIDE the lock: the pool_util emit writes a
        # line to service.jsonl, and a finishing job's lease release
        # queues behind this critical section — holding the lock
        # across file I/O visibly delayed buddy merge-back
        self._util_ring.add(self._util_sample())

    def _fill_burnin(self) -> None:
        """Saturate remaining free pool width with burn-in soak/fuzz
        jobs (caller holds the lock). Each synthesized job is a real
        durable store entry at :data:`BURNIN_PRIORITY`, so it survives
        restarts, shows in every listing, and is preempted by ANY real
        submission; ``max_jobs`` caps total synthesis (None = a
        standing burn-in fleet that refills as jobs finish)."""
        b = self._burnin
        if not b or self._closed:
            return
        limit = b.get("max_jobs")
        while True:
            if limit is not None and self._burnin_seq >= int(limit):
                return
            lease = self._pool.acquire(1)
            if lease is None:
                return
            seed = int(b.get("seed0", 0)) + self._burnin_seq
            self._burnin_seq += 1
            spec = JobSpec(
                b.get("config", "write_once"),
                kwargs=dict(b.get("overrides") or {}, seed=seed),
                kind=b.get("kind", jobstates.KIND_FUZZ),
                priority=BURNIN_PRIORITY, burnin=True)
            job = self._store.create(spec)
            self._metrics.inc("jobs_submitted")
            self._trace.emit("job_submit", job=job.id,
                             model=spec.model_name,
                             priority=spec.priority, burnin=True,
                             kind=spec.kind)
            self._launch(job, lease)

    # --- elastic flex controller (promote-on-freed-width) --------------
    def _flex_pass(self) -> None:
        """Scale-UP policy pass (caller holds the lock; no-op unless
        ``flex=True``): when placement left the queue EMPTY and buddy
        merge-back freed width, widen the hungriest RUNNING job
        instead of letting the width idle. Width>=2 sharded jobs
        double IN PLACE — the pool grants a second lease of equal
        width and the worker hands it to the live engine
        (``Checker.request_promote``, the degradation ladder run
        upward); width-1 singles have no mesh to widen and migrate
        through the shard-agnostic checkpoint instead (pause +
        requeue; the queued loop re-grants wider). One action per pass
        under a ``flex_interval`` hysteresis window (fleet-wide and
        per-job), so promote/demote cannot thrash against bursty
        arrivals."""
        if not self._flex or self._closed or self._pool is None:
            return
        if any(j.state == jobstates.QUEUED and j.id not in self._running
               for j in self._store.jobs()):
            return  # queued work outranks widening anyone
        now = time.monotonic()
        if now - self._flex_last < self._flex_interval:
            return
        cands = []
        for jid, rt in self._running.items():
            job = self._store.get(jid)
            # a still-compiling job (rt.checker None) stays eligible:
            # the control slot holds the promote until its worker
            # loop starts, so a host joining mid-compile is not lost
            if job is None or rt.burnin \
                    or job.spec.kind != KIND_CHECK \
                    or rt.promote_lease is not None:
                continue
            hunger = min(job.spec.width, self._pool.width) \
                - rt.lease.width
            if hunger <= 0 or now - rt.flexed_at < self._flex_interval:
                continue
            cands.append((hunger, -job.priority, job.seq, job, rt))
        # widest-hungry first; priority then age break ties
        cands.sort(key=lambda c: (-c[0], c[1], c[2]))
        for _hunger, _pri, _seq, job, rt in cands:
            if rt.lease.width >= 2:
                extra = self._pool.acquire(rt.lease.width)
                if extra is None:
                    continue  # freed width doesn't fit a double; next
                rt.promote_lease = extra
                self._flex_extra += extra.width
                self._metrics.set("flex_width", self._flex_extra)
                rt.flexed_at = self._flex_last = now
                rt.set_control("promote")
            else:
                if self._pool.largest_free() < 2:
                    continue  # a wash: it would resume at width 1
                rt.flexed_at = self._flex_last = now
                rt.set_control("promote_migrate")
            return  # one flex action per pass (hysteresis)

    def _maybe_preempt(self, job: Job) -> None:
        """Nothing is free and ``job`` waits: pause the lowest-priority
        RUNNING job it strictly outranks (the victim checkpoints,
        releases its subset, and re-queues to resume on a smaller
        one). With flex enabled, over-width victims are DEMOTED first
        — same pause-and-requeue mechanics, but it frees more width
        per victim and shows up as the scale-down half of the elastic
        ladder (``job_demote`` / ``demotes``)."""
        victims = [(self._store.get(jid), rt)
                   for jid, rt in self._running.items()]
        victims = [(vj, rt) for vj, rt in victims
                   if vj is not None and vj.priority < job.priority]
        if not victims:
            return
        victims.sort(key=lambda pair: (pair[0].priority, -pair[0].seq))
        if self._flex:
            for vj, rt in victims:
                if rt.lease.width > 1 or rt.promote_lease is not None:
                    rt.set_control("demote")
                    return
        victims[0][1].set_control("preempt")

    def _launch(self, job: Job, lease: DeviceLease) -> None:
        # registered under the lock BEFORE the thread starts, so a
        # concurrent _schedule pass can never double-place the job
        rt = _JobRuntime(lease)
        rt.burnin = bool(job.spec.burnin)
        self._running[job.id] = rt
        # SLO stamp: the queue-wait clock stops the moment the pool
        # GRANTS the subset (compile/seed latency is first_chunk_s's
        # problem, not queueing's)
        job.status["granted_at"] = rt.granted_at
        # a width-1 job the flex controller migrated through its
        # checkpoint lands here for the wider grant: the promote is
        # only real if the pool actually granted MORE than it had
        prev_w = job.status.get("granted_width")
        if job.status.pop("flex_migrate", None) \
                and prev_w and lease.width > int(prev_w):
            self._metrics.inc("promotes")
            self._trace.emit("job_promote", job=job.id,
                             width=lease.width,
                             hosts=[str(h) for h in lease.hosts],
                             migrated=True)
        queued_at = job.status.get("queued_at")
        if queued_at is not None:
            self._metrics.add_time(
                "queue_wait_s", max(0.0, rt.granted_at - queued_at))
        self._trace.emit(
            "job_grant", job=job.id, width=lease.width,
            hosts=[str(h) for h in lease.hosts],
            queue_wait_s=(round(rt.granted_at - queued_at, 6)
                          if queued_at is not None else None))
        if queued_at is not None and self._trace:
            # the job-dispatch gap as a span: submit->grant on the
            # service stream's clock (wall = t0_unix + t), so the
            # stall report sees queueing dead-air beside engine spans
            t0_unix = getattr(self._trace, "t0_unix", None)
            if t0_unix is not None:
                self._trace.emit(
                    "span", name="idle", job=job.id,
                    t0=round(max(0.0, queued_at - t0_unix), 6),
                    t1=round(max(0.0, rt.granted_at - t0_unix), 6))
        thread = threading.Thread(
            target=self._run_job, args=(job, lease, rt),
            name=f"stateright-job-{job.id}", daemon=True)
        rt.thread = thread
        self._workers[job.id] = thread
        thread.start()

    # --- the per-job worker --------------------------------------------
    def _run_job(self, job: Job, lease: DeviceLease,
                 rt: _JobRuntime) -> None:
        try:
            self._drive_job(job, lease, rt)
        except BaseException as exc:
            # metrics BEFORE the state flip: wait(job) unblocks on the
            # state, and the profile must already account for the job
            self._metrics.inc("jobs_failed")
            job.set_state(jobstates.FAILED,
                          error=f"{type(exc).__name__}: {exc}")
            self._trace.emit("job_done", job=job.id, state="failed",
                             error=f"{type(exc).__name__}: {exc}")
        finally:
            with self._lock:
                self._running.pop(job.id, None)
                self._pool.release(lease)
                extra = rt.promote_lease
                rt.promote_lease = None
                if extra is not None:
                    self._pool.release(extra)
                    self._flex_extra -= extra.width
                    self._metrics.set("flex_width", self._flex_extra)
                # quarantine AFTER release: the blamed width-1 blocks
                # just buddy-merged back, so carve_out can split them
                # out of the free structures for good
                fresh = self._harvest_quarantine(job, rt)
            for key in fresh:
                self._trace.emit("quarantine", device=key,
                                 quarantined=len(self._quarantined),
                                 job=job.id)
            if fresh:
                self._persist_quarantine()
            self._schedule()
            self._workers.pop(job.id, None)

    # --- silent-corruption quarantine ----------------------------------
    def _device_key(self, device, pos: int) -> str:
        did = getattr(device, "id", None)
        return str(did if did is not None else pos)

    def _pool_pos(self, key) -> Optional[int]:
        """Global pool offset of the device with stable key ``key``
        (None when it is no longer in the pool). Caller holds the
        lock; the pool exists."""
        for i, d in enumerate(self._pool._devices):
            if self._device_key(d, i) == str(key):
                return i
        return None

    def _harvest_quarantine(self, job: Job,
                            rt: _JobRuntime) -> List[str]:
        """Map a finished job's auditor blame set (mesh-relative
        device references on ``checker._quarantined``) through its
        lease onto pool devices, carve each out of the free
        structures, and record the blame. Caller holds the lock;
        returns the NEWLY quarantined device keys."""
        blamed = getattr(rt.checker, "_quarantined", None)
        if not blamed:
            return []
        from ..checker.resilience import match_device
        devs = list(rt.lease.devices)
        fresh: List[str] = []
        for ref in sorted(blamed, key=str):
            idx = match_device(devs, ref)
            if idx is None:
                continue
            device = devs[idx]
            pos = None
            for i, d in enumerate(self._pool._devices):
                if d is device:
                    pos = i
                    break
            if pos is None:
                continue
            key = self._device_key(device, pos)
            self._pool.carve_out(pos)
            if key not in self._quarantined:
                self._quarantined[key] = {
                    "device": key, "pos": pos, "job": job.id,
                    "host": (str(rt.lease.hosts[0])
                             if rt.lease.hosts else None),
                    "at": time.time()}
                fresh.append(key)
        if fresh:
            self._metrics.set("quarantined", len(self._quarantined))
        return fresh

    def _persist_quarantine(self) -> None:
        with self._lock:
            snapshot = dict(self._quarantined)
        _atomic_write_json(self._quarantine_path, snapshot)

    def quarantined(self) -> List[str]:
        """The device keys currently withheld from every grant."""
        with self._lock:
            return sorted(self._quarantined)

    def audit_probe(self, device_key, oracle=None) -> bool:
        """Probation re-admission for a quarantined device: run the
        dedicated audit-probe workload — a deterministic packed-row
        matrix fingerprinted ON the device and compared word-for-word
        against the host oracle (``checker/resilience.oracle_fps``,
        the same comparison the chunk auditor makes). Pass: the
        device's width-1 block buddy-merges back into the pool and the
        persisted blame record is dropped. Fail: it stays quarantined.
        ``oracle`` overrides the device-side computation (fault
        injection for tests). Returns whether the probe passed."""
        key = str(device_key)
        with self._lock:
            if key not in self._quarantined:
                raise ValueError(
                    f"device {key!r} is not quarantined "
                    f"(quarantined: {sorted(self._quarantined)})")
            self._ensure_pool()
            pos = self._pool_pos(key)
        import numpy as np

        from ..checker.resilience import oracle_fps
        rows = _audit_probe_rows()
        want = oracle_fps(rows)
        device = (self._pool._devices[pos] if pos is not None
                  else None)
        got = (oracle if oracle is not None else oracle_fps)(
            rows, device)
        ok = bool(np.array_equal(np.asarray(want, np.uint64),
                                 np.asarray(got, np.uint64)))
        with self._lock:
            if ok:
                self._quarantined.pop(key, None)
                if pos is not None:
                    self._pool.readmit(pos)
                self._metrics.set("quarantined",
                                  len(self._quarantined))
            n = len(self._quarantined)
        self._persist_quarantine()
        self._trace.emit("quarantine", device=key, quarantined=n,
                         probe="pass" if ok else "fail")
        if ok:
            self._schedule()
        return ok

    def _drive_job(self, job: Job, lease: DeviceLease,
                   rt: _JobRuntime) -> None:
        if job.spec.kind != KIND_CHECK:
            self._drive_soak(job, lease, rt)
            return
        import contextlib

        import jax
        import numpy as np

        # a width-1 job pins every dispatch to its granted device
        # (thread-local JAX config), so singles on different chips
        # truly run disjoint; wider jobs carry their own mesh
        ctx = (jax.default_device(lease.devices[0])
               if lease.width == 1 else contextlib.nullcontext())
        with ctx:
            model = job.spec.build()
            builder = (model.checker()
                       .tpu_options(**job.spec.options)
                       .tpu_options(race=False, artifact_dir=job.dir,
                                    job_id=job.id))
            if lease.width > 1:
                from jax.sharding import Mesh
                builder.tpu_options(mesh=Mesh(
                    np.array(list(lease.devices)), ("shards",)))
            if job.spec.target:
                builder.target_state_count(job.spec.target)
            resumed = bool(job.status.get("resume")) \
                and job.has_checkpoint()
            if resumed:
                builder.resume_from(job.paths["autosave"])
            # a job that previously ran as a batch lane (fallback or
            # checkpoint resume) must not advertise a stale lane
            job.status.pop("batch", None)
            job.status.pop("lane", None)
            checker = builder.spawn_tpu()
            rt.checker = checker
            driver = StepDriver(checker).start()
            rt.driver = driver
            job.set_state(jobstates.RUNNING, granted_width=lease.width,
                          resume=resumed,
                          hosts=[str(h) for h in lease.hosts])
            self._trace.emit("job_resume" if resumed else "job_start",
                             job=job.id, width=lease.width,
                             hosts=[str(h) for h in lease.hosts])
            delay = job.spec.step_delay
            while True:
                ctl = rt.take_control()
                if ctl == "promote":
                    status = self._apply_promote(job, lease, rt,
                                                 checker, driver)
                    if status != RUNNING:
                        self._finish_job(job, checker, driver)
                        return
                    continue
                if ctl in ("pause", "preempt", "demote",
                           "promote_migrate", "shutdown"):
                    checker.request_pause()
                    driver.drain()
                    if checker.paused():
                        if ctl in ("preempt", "demote"):
                            self._metrics.inc("preemptions")
                            if ctl == "demote":
                                w = lease.width + (
                                    rt.promote_lease.width
                                    if rt.promote_lease is not None
                                    else 0)
                                self._metrics.inc("demotes")
                                self._trace.emit("job_demote",
                                                 job=job.id, width=w)
                            job.set_state(jobstates.QUEUED,
                                          resume=True, preempted=True)
                        elif ctl == "promote_migrate":
                            # flex scale-up for a width-1 single: ride
                            # the shard-agnostic checkpoint — requeue,
                            # let the placement loop re-grant wider,
                            # and _launch emits the job_promote
                            job.set_state(jobstates.QUEUED,
                                          resume=True,
                                          flex_migrate=True)
                        elif ctl == "shutdown":
                            # graceful stop: re-enqueue so the next
                            # boot resumes it without an operator
                            job.set_state(jobstates.QUEUED, resume=True)
                        else:
                            job.set_state(jobstates.PAUSED, resume=True)
                        self._trace.emit(
                            "job_pause", job=job.id,
                            reason={"preempt": "preempt",
                                    "demote": "preempt",
                                    "promote_migrate": "promote",
                                    "shutdown": "shutdown"}.get(
                                        ctl, "user"))
                        return
                    # the run finished before the pause landed
                    self._finish_job(job, checker, driver)
                    return
                if ctl == "cancel":
                    driver.cancel()
                    job.set_state(jobstates.CANCELLED)
                    self._trace.emit("job_done", job=job.id,
                                     state="cancelled")
                    return
                status = driver.step(self._step_budget)
                if not rt.first_chunk_seen \
                        and checker.state_count() > 0:
                    # the engine materialized its first chunk: the
                    # compile/seed latency a tenant pays before any
                    # progress ends here
                    rt.first_chunk_seen = True
                    now = time.time()
                    job.status["first_chunk_at"] = now
                    elapsed = max(0.0, now - rt.granted_at)
                    self._metrics.add_time("first_chunk_s", elapsed)
                    self._trace.emit("job_first_chunk", job=job.id,
                                     first_chunk_s=round(elapsed, 6))
                if delay:
                    time.sleep(delay)
                if status != RUNNING:
                    self._finish_job(job, checker, driver)
                    return

    def _apply_promote(self, job: Job, lease: DeviceLease,
                       rt: _JobRuntime, checker, driver) -> str:
        """Hand the flex grant to the LIVE engine (worker thread): ask
        for the in-place double (``Checker.request_promote``) and step
        the driver until the next chunk boundary takes the decision.
        The engine may decline — no host shadow, or the doubled mesh
        would be budget-unviable — and a declined grant's lease merges
        straight back, so the width was only reserved, never wasted.
        Applied grants stay leased until the job exits (released with
        the base lease in ``_run_job``)."""
        extra = rt.promote_lease
        status = RUNNING
        applied = False
        if extra is not None and lease.width >= 2:
            before = int(checker.profile().get("promotes", 0) or 0)
            checker.request_promote(list(extra.devices))
            spins = 0
            while status == RUNNING and checker.promote_pending() \
                    and spins < 256:
                status = driver.step(1)
                spins += 1
            if checker.promote_pending():
                # no decision landed (the run ended first, or the
                # engine has no chunk boundary to decide at): keep
                # the lease reserved — it releases with the job, and
                # releasing it NOW could hand devices the engine may
                # still widen onto to another tenant
                return status
            applied = int(checker.profile().get(
                "promotes", 0) or 0) > before
        if applied:
            width = lease.width + extra.width
            hosts: list = []
            for h in (*lease.hosts, *extra.hosts):
                if str(h) not in hosts:
                    hosts.append(str(h))
            self._metrics.inc("promotes")
            job.set_state(jobstates.RUNNING, granted_width=width,
                          hosts=hosts)
            self._trace.emit("job_promote", job=job.id, width=width,
                             hosts=[str(h) for h in extra.hosts])
        elif extra is not None:
            with self._lock:
                rt.promote_lease = None
                self._pool.release(extra)
                self._flex_extra -= extra.width
                self._metrics.set("flex_width", self._flex_extra)
        return status

    # --- the soak/fuzz worker (continuous verification fleet) ----------
    def _drive_soak(self, job: Job, lease: DeviceLease,
                    rt: _JobRuntime) -> None:
        """Run one soak/fuzz job segment on this worker thread. The
        driver's ``on_tick`` hook polls the runtime's control channel
        ~10x/s, so pause/preempt/shutdown stop the soak cleanly at a
        SETTLED op-count boundary (every claimed op returned or
        abandoned) and the job re-queues with its remaining op budget
        — each resumption is a fresh seeded segment (seed offset by
        the segment index: new ports, fresh chaos stream), each
        segment independently cross-checked ONLINE. A violation
        finishes the job immediately (that is the find), auto-filing
        the rejected history under its corpus dedup key."""
        from ..soak import build_soak_config, run_soak

        spec = job.spec
        overrides = dict(spec.kwargs)
        base_seed = int(overrides.pop("seed", 0))
        done_ops = int(job.status.get("ops_done", 0))
        completed = int(job.status.get("ops_completed", 0))
        segment = int(job.status.get("segments", 0))
        resumed = segment > 0
        ctl_box: List[str] = []

        def tick() -> bool:
            ctl = rt.take_control()
            if ctl is not None:
                ctl_box.append(ctl)
                return True
            return False

        cfg = build_soak_config(spec.model_name, overrides,
                                kind=spec.kind, seed=base_seed)
        total = int(cfg.ops)
        # fuzz knobs derive from the BASE seed (stable across
        # segments); the runtime streams re-seed per segment
        cfg.seed = base_seed + segment * 10007
        cfg.ops = max(total - done_ops, 0)
        cfg.on_tick = tick
        cfg.trace = job.paths["trace"]
        cfg.artifact_dir = self._corpus_dir or job.dir
        cfg.history_path = os.path.join(
            job.dir, "history.jsonl" if segment == 0
            else f"history.{segment + 1}.jsonl")
        job.set_state(jobstates.RUNNING, granted_width=lease.width,
                      resume=resumed,
                      hosts=[str(h) for h in lease.hosts])
        self._trace.emit("job_resume" if resumed else "job_start",
                         job=job.id, width=lease.width,
                         hosts=[str(h) for h in lease.hosts],
                         kind=spec.kind)
        if cfg.ops > 0:
            res = run_soak(cfg)
        else:  # resumed with nothing left: trivially complete
            res = {"protocol": cfg.protocol, "ops": 0, "completed": 0,
                   "op_timeouts": 0, "history_ok": True, "testers": {},
                   "artifact": None, "artifacts": {},
                   "violation_op": None, "stopped": False,
                   "elapsed": 0.0, "ops_per_s": None,
                   "crashes": 0, "restarts": 0, "dropped": 0,
                   "duplicated": 0, "delayed": 0, "reordered": 0,
                   "partitions": 0}
        segment += 1
        done_ops += int(res.get("ops") or 0)
        completed += int(res.get("completed") or 0)
        violated = not res.get("history_ok", True)
        faults = dict(job.status.get("soak_faults") or {})
        for key in ("crashes", "restarts", "dropped", "duplicated",
                    "delayed", "reordered", "partitions",
                    "op_timeouts"):
            faults[key] = int(faults.get(key, 0)) + int(res.get(key, 0))
        self._metrics.inc("fuzz_ops", int(res.get("completed") or 0))
        if violated:
            self._metrics.inc("violations")
        progress = dict(ops_done=done_ops, ops_completed=completed,
                        segments=segment, soak_faults=faults)
        ctl = ctl_box[0] if ctl_box else None
        if ctl == "cancel":
            job.set_state(jobstates.CANCELLED, **progress)
            self._trace.emit("job_done", job=job.id,
                             state="cancelled")
            return
        if violated or done_ops >= total or ctl is None:
            # ran to completion — or stopped AT the violating op: the
            # find IS the result, the artifact is already corpus-filed
            result = self._soak_result(job, res, base_seed, total,
                                       done_ops, completed, segment,
                                       faults, violated)
            self._metrics.inc("jobs_done")
            self._metrics.inc("soak_jobs")
            self._note_done()
            job.set_state(jobstates.DONE,
                          history_ok=not violated, **progress)
            self._trace.emit("job_done", job=job.id, state="done",
                             kind=spec.kind,
                             history_ok=not violated,
                             ops=completed,
                             violation_op=result["violation_op"])
            return
        # op-boundary stop with budget left: hand the subset back
        if ctl == "preempt":
            self._metrics.inc("preemptions")
            if spec.burnin:
                self._trace.emit("burnin_preempt", job=job.id,
                                 ops_done=done_ops)
            job.set_state(jobstates.QUEUED, resume=True,
                          preempted=True, **progress)
        elif ctl == "shutdown":
            job.set_state(jobstates.QUEUED, resume=True, **progress)
        else:
            job.set_state(jobstates.PAUSED, resume=True, **progress)
        self._trace.emit("job_pause", job=job.id,
                         reason=("preempt" if ctl == "preempt"
                                 else ctl if ctl else "user"))

    def _soak_result(self, job: Job, res: dict, seed: int, total: int,
                     done_ops: int, completed: int, segment: int,
                     faults: dict, violated: bool) -> dict:
        """The durable result summary for a soak/fuzz job: the verdict,
        cumulative op/fault counts across segments, the violation pin
        (op index + corpus artifact) and the SLO lifecycle stamps."""
        result = {
            "job": job.id,
            "kind": job.spec.kind,
            "config": job.spec.model_name,
            "protocol": res.get("protocol"),
            "seed": seed,
            "burnin": job.spec.burnin,
            "ops": done_ops,
            "ops_budget": total,
            "completed": completed,
            "segments": segment,
            "history_ok": not violated,
            "testers": res.get("testers"),
            "violation_op": res.get("violation_op"),
            "artifact": res.get("artifact"),
            "artifacts": res.get("artifacts"),
            "ops_per_s": res.get("ops_per_s"),
            "faults": faults,
            "lifecycle": job_lifecycle(job),
        }
        _atomic_write_json(job.paths["result"], result)
        return result

    def _finish_job(self, job: Job, checker, driver: StepDriver) -> None:
        # metrics BEFORE the state flip (wait(job) unblocks on it)
        if driver.status == FAILED:
            err = checker.error()
            self._metrics.inc("jobs_failed")
            job.set_state(jobstates.FAILED,
                          error=f"{type(err).__name__}: {err}")
            self._trace.emit("job_done", job=job.id, state="failed",
                             error=f"{type(err).__name__}: {err}")
            return
        assert driver.status == DONE, driver.status
        result = write_result(job, checker)
        self._metrics.inc("jobs_done")
        self._note_done()
        job.set_state(jobstates.DONE,
                      unique=result["unique_state_count"])
        self._trace.emit("job_done", job=job.id, state="done",
                         unique=result["unique_state_count"])

    def _note_done(self) -> None:
        """Roll the jobs/min window forward by one completion."""
        now = time.time()
        self._done_times.append(now)
        while self._done_times and now - self._done_times[0] > 60.0:
            self._done_times.popleft()
        self._metrics.set("jobs_per_min", len(self._done_times))


def _audit_probe_rows(n: int = 4096, width: int = 8):
    """The deterministic packed-row workload ``Scheduler.audit_probe``
    fingerprints on a quarantined device: a Knuth-hash ramp wide
    enough to exercise every fingerprint lane, identical on every
    call so probe verdicts are reproducible."""
    import numpy as np
    ramp = (np.arange(n * width, dtype=np.uint64)
            * np.uint64(2654435761)) % np.uint64(1 << 32)
    return ramp.astype(np.uint32).reshape(n, width)


def job_lifecycle(job: Job, done_wall: Optional[float] = None) -> dict:
    """The submit→grant→start→first-chunk→done stamps (absolute wall
    seconds) plus the derived SLO intervals, from the job's status
    dict — what ``result.json`` records so a postmortem reads queueing
    vs compile vs run time without re-deriving from events."""
    status = job.status
    out = {}
    for key, stamp in (("submit", "queued_at"),
                       ("grant", "granted_at"),
                       ("start", "running_at"),
                       ("first_chunk", "first_chunk_at")):
        if status.get(stamp) is not None:
            out[key] = status[stamp]
    done = done_wall if done_wall is not None else time.time()
    out["done"] = done
    if "submit" in out and "grant" in out:
        out["queue_wait_s"] = round(out["grant"] - out["submit"], 6)
    if "grant" in out and "first_chunk" in out:
        out["first_chunk_s"] = round(
            out["first_chunk"] - out["grant"], 6)
    if "start" in out:
        out["run_s"] = round(done - out["start"], 6)
    return out


def write_result(job: Job, checker) -> dict:
    """The durable result summary: property verdicts, counts, the
    discoveries (encoded fingerprint paths), the metrics profile, the
    lifecycle/SLO stamps, and a sha256 digest of the sorted reached
    fingerprint set — the restart/parity tests' bit-identity hook."""
    import hashlib
    import json as _json

    from .jobs import _atomic_write_json

    model = checker.model()
    fps = sorted(int(f) for f in checker.generated_fingerprints())
    digest = hashlib.sha256(
        "\n".join(map(str, fps)).encode()).hexdigest()
    discs = checker.discoveries()
    properties = []
    for prop in model.properties():
        found = discs.get(prop.name)
        properties.append({
            "expectation": prop.expectation.value,
            "name": prop.name,
            "discovery": (found.encode(model)
                          if found is not None else None)})
    profile = {k: (round(v, 6) if isinstance(v, float) else v)
               for k, v in checker.profile().items()}
    result = {
        "job": job.id,
        "model": job.spec.model_name,
        "run_id": (checker.run_id()
                   if hasattr(checker, "run_id") else None),
        "state_count": checker.state_count(),
        "unique_state_count": checker.unique_state_count(),
        "properties": properties,
        "profile": profile,
        "lifecycle": job_lifecycle(job),
        "fingerprint_count": len(fps),
        "fingerprints_sha256": digest,
    }
    # artifact integrity chain (silent-corruption defense): bind the
    # result digest to the run's audited chunk-digest head so a reader
    # can tell a tampered/corrupted result.json from a genuine one
    from ..checker.resilience import chain_integrity
    chain_head = getattr(checker, "_shadow_chain_head", None) or ""
    result["chain_head"] = chain_head
    result["integrity"] = chain_integrity(digest, chain_head)
    _json.dumps(result)  # fail here, not mid-atomic-write
    _atomic_write_json(job.paths["result"], result)
    return result
