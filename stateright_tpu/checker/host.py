"""Shared scaffolding for the host (CPU reference) engines.

The reference spawns worker OS threads sharing a job market
(`/root/reference/src/checker/bfs.rs:70-152`). A pure-Python translation of
that would serialize on the GIL, so the host engines here run the search on a
single worker thread started lazily — checking begins at the first
observation (``join``/``report``/``is_done``/``serve``), which keeps the
golden report output deterministic. These engines are the correctness oracle
the TPU engine is differentially tested against.
"""

from __future__ import annotations

import os
import threading
import time
from contextlib import nullcontext
from typing import Dict, Optional

from ..core import Expectation, Model
from ..fingerprint import fp64_node
from ..obs import (FlightRecorder, Metrics, SpanRecorder,
                   apply_artifact_dir, attach_attribution,
                   default_flight_path, fault_info, gc_totals,
                   identity_fields, make_trace, new_run_id)
from .builder import Checker, CheckerBuilder


class HostChecker(Checker):
    """Base for BfsChecker/DfsChecker: lazy single-worker execution."""

    def __init__(self, builder: CheckerBuilder):
        self._model = builder.model
        self._symmetry = builder.symmetry_fn_
        self._target_state_count = builder.target_state_count_
        self._visitor = builder.visitor_
        self._properties = self._model.properties()
        self._state_count = 0
        self._unique_state_count = 0
        self._discovery_fps: Dict[str, object] = {}
        self._done = False
        self._error: Optional[BaseException] = None
        self._thread: Optional[threading.Thread] = None
        self._start_lock = threading.Lock()
        self._cancel_event = threading.Event()
        # pausable runs (the step-driver/job-service boundary): the
        # device engines honor the pause event at their chunk-loop exit
        # checks, drain the pipeline, and write a resume_from-loadable
        # checkpoint to _pause_path before returning; engines without
        # a checkpointable loop treat request_pause() as cancel()
        self._pause_event = threading.Event()
        self._pause_path = None
        self._paused = False
        # elastic runs (the scale-UP mirror of the degradation ladder):
        # request_promote(devices) stashes the grant and sets the
        # event; the sharded chunk loop drains its pipeline at the
        # next chunk boundary and widens D -> 2D onto the granted
        # devices (promote_step, parallel/engine.py). Engines without
        # a widen-capable loop leave the event unread — a no-op.
        self._promote_event = threading.Event()
        self._promote_request = None
        # True once a StepDriver has claimed this run: the background
        # thread must never start on top of an externally driven run
        self._driven = False
        # job-scoped artifacts: tpu_options(artifact_dir=dir) expands
        # to autosave/flight_path/trace paths under one directory
        # (explicit knobs win; obs/artifacts.py). Mutates the builder's
        # dict so a race's twin checkers resolve identical paths.
        apply_artifact_dir(builder.tpu_options_)
        # unified observability (obs/): every engine records into ONE
        # Metrics registry behind profile(), and emits structured
        # run-trace events when tpu_options(trace=...) names a sink.
        # The flight recorder (obs/recorder.py) is always on by default:
        # with no trace configured the engine still holds a sink-less
        # RunTrace feeding the bounded event ring, dumped as a JSONL
        # artifact on any crash (tpu_options(flight=False) opts out,
        # flight=N resizes the ring)
        self._metrics = Metrics()
        obs_opts = builder.tpu_options_
        flight = obs_opts.get("flight", True)
        if flight is False:
            self._recorder = None
        else:
            self._recorder = FlightRecorder() if flight is True \
                else FlightRecorder(limit=int(flight))
        self._flight_path: Optional[str] = None
        self._flight_target_cached: Optional[str] = None
        self._autosave_path = obs_opts.get("autosave")
        self._flight_path_opt = obs_opts.get("flight_path")
        self._profile_dir = obs_opts.get("profile_dir")
        self._trace = make_trace(obs_opts.get("trace"),
                                 engine=type(self).__name__,
                                 recorder=self._recorder)
        # correlation identity (obs/trace.py): every run is born with a
        # run_id; the job service injects its job id through
        # tpu_options(job_id=...) so the engine's own trace stream is
        # join-able with the scheduler's service.jsonl without guessing
        # from file paths. Stamped onto run_start by _step_wrapper.
        self._run_id = obs_opts.get("run_id") or new_run_id()
        self._job_id = obs_opts.get("job_id")
        # span profiler (obs/spans.py): the engines record each host
        # phase as an INTERVAL here, its seconds under span_s.<phase>;
        # always on (bounded ring) so profile()'s attribution works
        # traceless, mirrored as `span` trace events when a sink is
        # configured and annotated in any jax.profiler trace
        self._spans = SpanRecorder(self._trace, metrics=self._metrics,
                                   hold=True)
        #: a span around the whole background run (the race names its
        #: host racer's run "race.host"); None records none
        self._run_span: Optional[str] = None
        #: perf_counter stamp of the run's end (set with _done)
        self._t_done: Optional[float] = None
        self._gc0 = gc_totals()

    def profile(self) -> Dict[str, float]:
        """Snapshot of the run's metrics registry: phase timers
        (wall-seconds), counters, and observed maxima. Key meanings are
        pinned in ONE place — ``stateright_tpu.obs.GLOSSARY`` (also
        rendered in README.md § Observability) — rather than restated
        per engine; engines report only the phases they run. Engines
        that ran a chunk loop additionally report ``attribution`` /
        ``idle_s`` / ``bubble_frac`` — the overlap-aware wall-time
        split of the loop (attached post-snapshot: fractions must never
        ride a summing ``Metrics.merge``); every engine reports the
        process's ``gc_s`` / ``gc_collections`` since its construction,
        and ``spans_dropped`` once its span ring wrapped."""
        snap = attach_attribution(self._metrics.snapshot(), self._spans)
        gc_s, gc_n = gc_totals()
        snap["gc_s"] = gc_s - self._gc0[0]
        snap["gc_collections"] = gc_n - self._gc0[1]
        if self._spans.dropped:
            snap["spans_dropped"] = self._spans.dropped
        return snap

    def run_id(self) -> str:
        """This run's correlation id (stamped on its ``run_start``
        trace event and every artifact derived from it)."""
        return self._run_id

    def subscribe(self, fn) -> None:
        """Register a live progress callback on the run trace; ``fn``
        is invoked with every emitted event dict. Enabled by default
        (the flight recorder keeps the trace live); only with
        ``tpu_options(flight=False)`` and no trace sink does this
        raise."""
        self._trace.subscribe(fn)

    # --- flight recorder (obs/recorder.py) -----------------------------
    def flight_path(self) -> Optional[str]:
        """Path of the most recent flight-recorder artifact this run
        dumped, or ``None`` when nothing went wrong (or flight=False)."""
        return self._flight_path

    def _flight_target(self) -> str:
        """Stable per-run artifact destination: explicit
        ``tpu_options(flight_path=...)``, else next to the autosave
        checkpoint, else a per-checker file in the temp dir — repeated
        dumps of one run (watchdog, then retries, then the final error)
        overwrite in place, keeping the most complete artifact."""
        if self._flight_target_cached is None:
            if self._flight_path_opt is not None:
                self._flight_target_cached = os.fspath(
                    self._flight_path_opt)
            elif self._autosave_path is not None:
                self._flight_target_cached = (
                    os.fspath(self._autosave_path) + ".flight.jsonl")
            else:
                self._flight_target_cached = default_flight_path(
                    type(self._model).__name__)
        return self._flight_target_cached

    def _flight_dump(self, reason: str) -> Optional[str]:
        """Dump the event ring as a JSONL postmortem artifact. The
        ``recorder_dump`` event is emitted FIRST (and thus recorded),
        so the artifact names itself; dump failures (read-only temp
        dir, full disk) never mask the original fault."""
        rec = self._recorder
        if rec is None:
            return None
        path = self._flight_target()
        try:
            if self._trace:
                self._trace.emit("recorder_dump", path=path,
                                 reason=reason, events=rec.recorded,
                                 dropped=rec.dropped)
            rec.dump(path)
        except OSError:
            return None
        self._flight_path = path
        self._metrics.inc("recorder_dumps")
        return path

    def _note_discovery(self, name: str, fp) -> None:
        """Emit the trace event for a just-recorded discovery
        (fingerprints are stringified: uint64 exceeds JSON-safe ints)."""
        trace = self._trace
        if trace:
            trace.emit("discovery", property=name,
                       fp=([str(int(f)) for f in fp]
                           if isinstance(fp, (list, tuple))
                           else str(int(fp))))

    def cancel(self) -> None:
        """Cooperatively stop the run (checked at engine loop points);
        used by the spawn_tpu host-vs-device race to stop the loser."""
        self._cancel_event.set()

    def cancelled(self) -> bool:
        return self._cancel_event.is_set()

    def request_pause(self, path=None) -> None:
        """Cooperatively pause the run at the next engine step: the
        device engines drain their pipeline and write a
        ``resume_from``-loadable checkpoint (to ``path``, defaulting to
        the ``tpu_options(autosave=...)`` destination) before exiting
        the loop; ``paused()`` then reports True. Resumption is a fresh
        checker built with ``resume_from(path)`` — possibly on a
        different mesh width, which is how the job scheduler preempts
        runs onto smaller device subsets. Host engines (and the
        per-level device mode) have no checkpointable loop: they stop
        like ``cancel()`` and ``paused()`` stays False."""
        self._pause_event.set()
        # default: engines without a pause-aware loop stop at their
        # cancel checks; TpuChecker overrides the pause semantics
        self._cancel_event.set()

    def paused(self) -> bool:
        """True when the run exited via a pause checkpoint (the file
        named by ``pause_path()`` resumes it)."""
        return self._paused

    def pause_path(self):
        """Destination of the pause checkpoint (falls back to the
        autosave path), or ``None`` when neither is configured."""
        return self._pause_path if self._pause_path is not None \
            else self._autosave_path

    def generated_fingerprints(self):
        """All visited STATE fingerprints (the dedup record, translated
        out of node-key space under ``sound_eventually``)."""
        node_fp = getattr(self, "_node_fp", None)
        if node_fp is None:
            return set(self._generated)
        return {node_fp.get(k, k) for k in self._generated}

    def _reconstruct_path(self, fp: int):
        """Walk parent pointers to an init state, then replay forward
        (`bfs.rs:314-342`). Engines whose ``_generated`` maps dedup key
        -> parent dedup key share this; under ``sound_eventually`` the
        keys are (state, ebits) nodes and ``_node_fp`` translates each to
        its state fingerprint for replay."""
        from collections import deque

        from .path import Path

        node_fp = getattr(self, "_node_fp", None) or {}
        fingerprints: deque = deque()
        next_fp = fp
        while next_fp in self._generated:
            parent = self._generated[next_fp]
            fingerprints.appendleft(node_fp.get(next_fp, next_fp))
            if parent is None:
                break
            next_fp = parent
        return Path.from_fingerprints(self._model, fingerprints)

    def discoveries(self):
        from .path import Path

        # a list-valued discovery is an explicit fingerprint path (lasso
        # witnesses: stem + one cycle lap — NOT a parent-chain walk);
        # scalars reconstruct by walking the mirror as usual
        return {
            name: (Path.from_fingerprints(self._model, fp)
                   if isinstance(fp, (list, tuple))
                   else self._reconstruct_path(fp))
            for name, fp in list(self._discovery_fps.items())
        }

    # --- execution -------------------------------------------------------
    def _run(self) -> None:  # pragma: no cover - overridden
        raise NotImplementedError

    def _run_steps(self):
        """Generator form of the engine loop — the step-driver surface
        (``stateright_tpu.service.StepDriver``). Each ``yield`` is one
        engine quantum (a processed chunk on the device engines); the
        default implementation runs the whole blocking search as one
        step, which is all a host engine can offer. The device engines
        override this with chunk-granular yields, so a driven run can
        be paused/stepped without a dedicated thread."""
        self._run()
        return
        yield  # pragma: no cover — makes this function a generator

    def _claim_driver(self) -> None:
        """Claim this run for an external step driver: the background
        thread must never start on top of it (and vice versa)."""
        with self._start_lock:
            if self._thread is not None:
                raise RuntimeError(
                    "checker is already running on its background "
                    "thread; a StepDriver must claim the run before "
                    "join()/report()/serve() start it")
            if self._driven:
                raise RuntimeError(
                    "checker is already claimed by a step driver")
            self._driven = True

    def _start_background(self) -> None:
        with self._start_lock:
            if self._thread is None and not self._driven:
                self._thread = threading.Thread(target=self._run_wrapper,
                                                daemon=True)
                self._thread.start()

    def _start_profiler(self) -> bool:
        """Optional ``jax.profiler`` capture behind
        ``tpu_options(profile_dir=...)``: the full XLA-level trace
        (device timelines, HLO costs) lands in the directory for
        TensorBoard/Perfetto, with every host phase span annotated as
        ``sr.<phase>`` on the same clock. Failures never kill the run."""
        if self._profile_dir is None:
            return False
        try:
            import jax
            jax.profiler.start_trace(os.fspath(self._profile_dir))
            return True
        except Exception as exc:
            import warnings
            warnings.warn(
                f"tpu_options(profile_dir=...) capture failed to start "
                f"({type(exc).__name__}: {exc}); run continues "
                "unprofiled", RuntimeWarning, stacklevel=2)
            return False

    def _run_wrapper(self) -> None:
        with (self._spans.span(self._run_span) if self._run_span
              else nullcontext()):
            for _ in self._step_wrapper():
                pass

    def _step_wrapper(self):
        """Generator twin of the old blocking run wrapper: the SAME
        lifecycle (run_start/fault_injection events, profiler capture,
        error capture + flight dump, the terminal done event) around
        ``_run_steps()``'s quanta. The background thread drives it to
        exhaustion; a ``StepDriver`` advances it step by step from the
        caller's thread. Errors land in ``error()`` (raised at
        ``join()``), never out of the generator — matching the
        background-thread contract."""
        trace = self._trace
        if trace:
            # the correlation header rides run_start: run_id, the
            # stream's wall anchor, this process's host/rank, and the
            # owning job when the service drives the run — any single
            # artifact is then self-describing on the fleet timeline
            header = identity_fields(trace, self._run_id)
            if self._job_id is not None:
                header["job"] = self._job_id
            trace.emit("run_start", model=type(self._model).__name__,
                       wall=time.time(),
                       properties=len(self._properties), **header)
            faults = fault_info(self._model)
            if faults is not None:
                trace.emit("fault_injection", **faults)
        # the header leads: now the set-up spans (spawn, init) follow
        self._spans.release()
        profiling = self._start_profiler()
        try:
            with self._metrics.timed("search"):
                yield from self._run_steps()
        except GeneratorExit:  # an abandoned driver closing us
            raise
        except BaseException as exc:  # re-raised at join()
            self._error = exc
            if trace:
                trace.emit("error",
                           error=f"{type(exc).__name__}: {exc}")
            # the crash postmortem: dump the always-on event ring as a
            # JSONL artifact, trace or no trace configured
            self._flight_dump("error")
        finally:
            if profiling:
                try:
                    import jax
                    jax.profiler.stop_trace()
                except Exception:
                    pass  # a failed stop must not mask the run result
            self._t_done = time.perf_counter()
            self._done = True
            if trace:
                trace.emit("done", gen=self._state_count,
                           unique=self._unique_state_count,
                           cancelled=self._cancel_event.is_set(),
                           paused=self._paused,
                           discoveries=sorted(self._discovery_fps))

    def _init_ebits(self) -> frozenset:
        """Bit per not-yet-satisfied ``eventually`` property
        (`src/checker.rs:341-348`)."""
        return frozenset(
            i for i, p in enumerate(self._properties)
            if p.expectation == Expectation.EVENTUALLY)

    # --- sound_eventually() support (shared by BFS/DFS) -------------------
    def _init_sound(self, builder, ebits) -> None:
        """Node-keyed dedup setup: keys combine the state fingerprint
        with the pending eventually-bits (``fp64_node``); ``_node_fp``
        translates keys back to state fingerprints for replay."""
        self._sound = bool(builder.sound_eventually_) and bool(ebits)
        if self._sound:
            if max(ebits) > 31:
                # fp64_node hashes a 32-bit mask; truncating silently
                # would quietly reintroduce the miss this mode removes
                raise NotImplementedError(
                    "sound_eventually() supports eventually-property "
                    "indices 0..31")
            self._node_fp: Dict[int, int] = {}

    def _ebits_mask(self, ebits) -> int:
        """Bitmask form of an ebits set (0 when sound mode is off) —
        computed once per pop, not per child."""
        if not self._sound:
            return 0
        return sum(1 << i for i in ebits)

    def _node_key(self, fp: int, ebits_mask: int) -> int:
        if not self._sound:
            return fp
        key = fp64_node(fp, ebits_mask)
        self._node_fp[key] = fp
        return key

    # --- Checker interface ----------------------------------------------
    def model(self) -> Model:
        return self._model

    def state_count(self) -> int:
        return self._state_count

    def unique_state_count(self) -> int:
        return self._unique_state_count

    def join(self) -> "HostChecker":
        self._start_background()
        if self._thread is not None:
            self._thread.join()
        else:
            # externally driven (StepDriver): wait for the driver to
            # finish the run instead of owning a thread
            while not self._done:
                time.sleep(0.005)
        if self._error is not None:
            raise self._error
        return self

    def error(self) -> Optional[BaseException]:
        """The engine's failure, if it crashed; raised by ``join()``."""
        return self._error

    def is_done(self) -> bool:
        # a crashed engine counts as done for polling purposes; the failure
        # itself surfaces on join() (and report(), which joins at the end)
        return self._done or (
            len(self._discovery_fps) == len(self._properties))
