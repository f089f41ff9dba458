"""Small-model latency: race a budgeted host BFS against the device engine.

The device engine pays fixed costs a tiny model never amortizes — the
XLA dispatch floor, a sync for every device-to-host transfer, and
buffer seeding (NOTES.md) — so `increment_lock 3` (61 states) took
seconds on `spawn_tpu()` while the host enumerates it in milliseconds.
The reference's `check` subcommand semantics are simply "the spawned
checking run finishes" (`/root/reference/src/checker.rs:116-145`), so
`spawn_tpu()` now spawns BOTH engines and adopts whichever finishes
first:

  * the host racer is BUDGETED (default 1.5 s): small models finish
    well inside it; for big models it cancels itself so the only lasting
    cost is ~one host core for the first moments of a long device run;
  * the loser is cancelled cooperatively (`HostChecker.cancel()`), and a
    cancelled or errored racer is never adopted as a RESULT. A fatal
    device error (e.g. packed-capacity overflow) waits for the budgeted
    host racer: a complete host result wins (the check IS answered);
    the device error surfaces only when the host cannot finish in
    budget — deterministic up to the budget. Runs that must exercise
    the device guards pin ``tpu_options(race=False)`` (or a ``mode``);
  * both engines satisfy the same `Checker` contract, and for full
    enumerations their unique counts/fingerprint sets agree exactly (the
    host BFS is the differential oracle for the device engine), so the
    adopted winner is observationally equivalent. Early-exit
    generated-counts are engine-specific, as with the reference's
    multithreaded runs.

Racing is skipped (pure device engine) whenever the run needs a
device-only or engine-specific feature: a visitor, symmetry reduction,
`sound_eventually`, checkpoint resume/resumable, an explicit
`tpu_options(mode=...)`, or `tpu_options(race=False)` (the Explorer
disables it to introspect the device checker). A mesh run races only
on explicit `race=True` — its device lane is the sharded engine, and
the resilience order is ladder-first: a transient device death
degrades the mesh (D -> D/2 -> single chip, `checker/resilience.py`)
INSIDE the device engine; the un-budgeted host-BFS failover below only
fires once the ladder itself is exhausted.
"""

from __future__ import annotations

import atexit
import threading
import time
from typing import Optional

from .builder import Checker, CheckerBuilder, device_engine

#: worker THREADS of cancelled losers (threads only — retaining the
#: checker objects would pin their visited sets/frontiers/device logs
#: for process lifetime); a loser may still be draining a device chunk,
#: and XLA teardown racing a live dispatch aborts the process
#: (observed: "FATAL: exception not rethrown" on exit). Pruned of
#: finished threads on every append, so it holds live threads only.
_LOSER_THREADS: list = []
_LOSER_LOCK = threading.Lock()


@atexit.register
def _drain_losers() -> None:
    for thread in _LOSER_THREADS:
        if thread is not None and thread.is_alive():
            thread.join(timeout=60.0)
            if thread.is_alive():
                # a silent give-up here left a wedged XLA teardown
                # (e.g. a transfer blocked on a dead device) completely
                # invisible; name the thread so the hang is diagnosable
                import sys
                print(
                    f"stateright_tpu: raced loser thread "
                    f"{thread.name!r} is still alive after a 60s join "
                    "at interpreter exit — XLA teardown appears wedged "
                    "(often a device transfer blocked on a dead "
                    "device); the process may abort instead of exiting "
                    "cleanly", file=sys.stderr)


def _retire(checker) -> None:
    checker.cancel()
    thread = getattr(checker, "_thread", None)
    with _LOSER_LOCK:
        _LOSER_THREADS[:] = [t for t in _LOSER_THREADS if t.is_alive()]
        if thread is not None:
            _LOSER_THREADS.append(thread)


def losers_alive() -> int:
    """Retired racer threads still running (a loser finishes its
    current chunk or level before it sees the cancel)."""
    with _LOSER_LOCK:
        return sum(1 for t in _LOSER_THREADS if t.is_alive())


def race_eligible(builder: CheckerBuilder) -> bool:
    opts = builder.tpu_options_
    # a mesh run only races on explicit race=True: small models never
    # pick a mesh, so the default keeps sharded runs un-raced — but an
    # opted-in raced mesh gets the full degradation ladder (the engine
    # re-shards onto surviving chips) BEFORE the host-BFS failover rung
    return (opts.get("race", True)
            and ("mesh" not in opts or opts.get("race") is True)
            and "mode" not in opts
            and not opts.get("resumable")
            and builder.visitor_ is None
            and builder.symmetry_fn_ is None
            and not builder.sound_eventually_
            and builder.resume_path_ is None)


class RacingChecker(Checker):
    """Adopts the first engine (host BFS vs device) to finish."""

    #: host racer budget: small models finish in milliseconds; anything
    #: that outlives this is device territory. Overridable per run via
    #: ``tpu_options(race_budget=seconds)`` — a model the host would
    #: finish at ~2 s should not get its racer cancelled at the line.
    HOST_BUDGET_S = 1.5

    def __init__(self, builder: CheckerBuilder,
                 spawn_t0: Optional[float] = None):
        from .bfs import BfsChecker

        self._model = builder.model
        self._builder = builder  # kept for the engine-failover fallback
        budget = builder.tpu_options_.get("race_budget")
        if budget is not None:
            self.HOST_BUDGET_S = float(budget)
        # with explicit race=True on a mesh run (race_eligible) the
        # device lane is the sharded engine, whose degradation ladder
        # runs BEFORE the failover rung below ever applies
        self._tpu = device_engine(builder, spawn_t0)
        try:
            self._host = BfsChecker(builder)
            self._host._run_span = "race.host"
        except Exception:
            # a model that can't run on the host engine races nothing
            self._host = None
        self._winner = None
        self._failover = False
        self._decided = threading.Event()
        self._decider: threading.Thread | None = None
        self._decider_lock = threading.Lock()

    # ------------------------------------------------------------------
    def _start_background(self) -> None:
        """Start both engines plus the decider thread (non-blocking, so
        ``report()``'s periodic progress lines keep working)."""
        with self._decider_lock:
            if self._decider is None:
                self._tpu._start_background()
                if self._host is not None:
                    self._host._start_background()
                self._decider = threading.Thread(target=self._decide_loop,
                                                 daemon=True)
                self._decider.start()

    def _decide_loop(self) -> None:
        host, tpu = self._host, self._tpu
        tpu_failed = False
        fallback = False  # host is the un-budgeted failover engine
        t0 = time.monotonic()
        while True:
            if host is not None and host._done:
                if host._error is None and not host.cancelled():
                    self._winner = host
                    _retire(tpu)
                    break
                host = None  # disqualified; the device run decides
            if tpu._done and not tpu_failed:
                if tpu._error is None:
                    self._winner = tpu
                    if host is not None:
                        _retire(host)
                    break
                # device run failed (e.g. packed capacity overflow): the
                # budgeted host racer may still deliver a complete,
                # correct result — wait for it; the error surfaces only
                # if the host cannot (deterministic up to the budget)
                tpu_failed = True
            if host is None and tpu._done:
                # engine failover: a TRANSIENT device failure (a lost
                # backend, exhausted retries) on a raced run falls back
                # to an UN-budgeted host BFS continuing the check
                # rather than surfacing the backend's error — the
                # check still gets answered, just at host speed.
                # Capacity/programming errors surface as before: the
                # host would either hit the same model bug or silently
                # mask it.
                host = None if fallback else self._spawn_fallback(tpu)
                if host is None:
                    self._winner = tpu  # surfaces the device error
                    break
                fallback = True
                continue
            if (host is not None and not fallback
                    and time.monotonic() - t0 > self.HOST_BUDGET_S):
                _retire(host)
                host = None
            time.sleep(0.002)
        winner = self._winner
        if winner._t_done is not None:
            # done -> decision: the decider's poll, seen only once it has
            # happened (so no profiler annotation); recorded before the
            # decision is published, so every profile read after it
            # carries the span
            winner._spans.record("race.decide", winner._t_done,
                                 time.perf_counter())
        self._decided.set()
        # drop the loser references AFTER publishing the decision, so
        # concurrent progress readers never see a half-decided state;
        # retaining the losers would pin their visited sets / frontiers /
        # device log buffers for the result object's lifetime
        if self._winner is not self._tpu:
            self._tpu = None
        if self._winner is not self._host:
            self._host = None

    def _spawn_fallback(self, tpu):
        """Start the un-budgeted host BFS after a transient device
        failure (``tpu_options(failover=False)`` opts out); returns the
        running checker, or ``None`` when failover does not apply.
        This is the LAST resilience rung: the device engine's own
        degradation ladder (retry -> re-shard onto surviving chips ->
        single chip) has already run inside ``tpu`` by the time its
        error surfaces here."""
        from .resilience import FaultKind, blamed_device, classify_error

        err = tpu._error
        if (err is None
                or not self._builder.tpu_options_.get("failover", True)
                or classify_error(err) is not FaultKind.TRANSIENT):
            return None
        from .bfs import BfsChecker

        try:
            host = BfsChecker(self._builder)
        except Exception:
            return None
        self._failover = True
        if tpu._trace:
            tpu._trace.emit("failover", to="host-bfs",
                            error=f"{type(err).__name__}: {err}",
                            device=blamed_device(err))
        host._start_background()
        return host

    def _decide(self):
        if self._winner is None:
            self._start_background()
            self._decided.wait()
        return self._winner

    # --- Checker interface (decides, then delegates) -------------------
    def join(self) -> "Checker":
        self._decide().join()
        return self

    def is_done(self) -> bool:
        return self._decided.is_set() and self._winner.is_done()

    def model(self):
        return self._model

    def state_count(self) -> int:
        # live progress before a winner exists: the device run's counts
        # (the host racer either wins within its budget or is cancelled)
        if self._decided.is_set():
            return self._winner.state_count()
        tpu = self._tpu
        return tpu.state_count() if tpu is not None else 0

    def unique_state_count(self) -> int:
        if self._decided.is_set():
            return self._winner.unique_state_count()
        tpu = self._tpu
        return tpu.unique_state_count() if tpu is not None else 0

    def profile(self):
        """The WINNING engine's metrics snapshot (keys documented in
        ``stateright_tpu.obs.GLOSSARY``), tagged with which engine won:
        ``engine`` is ``"host"`` for the budgeted host racer,
        ``"device"`` for the device engine. A host win used to report
        ``{}``; now both outcomes carry the winner's real phase
        timers/counters. An engine failover (transient device failure
        adopted by the un-budgeted host fallback) adds
        ``failovers=1``. ``race_losers_alive`` counts the retired
        racer threads (of this race and earlier ones) still running."""
        from .bfs import BfsChecker

        winner = self._decide()
        prof = winner.profile()
        prof["engine"] = ("host" if isinstance(winner, BfsChecker)
                          else "device")
        if self._failover:
            prof["failovers"] = prof.get("failovers", 0) + 1
        prof["race_losers_alive"] = losers_alive()
        return prof

    def discoveries(self):
        return self._decide().discoveries()

    def generated_fingerprints(self):
        return self._decide().generated_fingerprints()

    def error(self):
        return self._decide().error()

    def save(self, path) -> None:
        # tpu_options(resumable=True) disables racing, so a raced run
        # never has a checkpointable frontier regardless of which engine
        # won — surface the same guidance the device engine gives
        raise RuntimeError(
            "save() needs the pending frontier: run with "
            "tpu_options(resumable=True) on the device engine")

    def __getattr__(self, name):
        # engine-specific surface: the winner's (losers are freed on
        # decision), else the not-yet-decided device checker's
        winner = self.__dict__.get("_winner")
        if winner is not None:
            return getattr(winner, name)
        tpu = self.__dict__.get("_tpu")
        if tpu is not None:
            return getattr(tpu, name)
        raise AttributeError(name)
