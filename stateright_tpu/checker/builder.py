"""Checker builder and results interface.

Reference: ``CheckerBuilder`` (`/root/reference/src/checker.rs:35-179`) and the
``Checker`` trait (`src/checker.rs:185-338`). ``spawn_tpu`` is the new
TPU-native strategy added alongside the reference's ``spawn_bfs``/``spawn_dfs``.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional, Sequence

from ..core import Expectation, Model
from .path import Path
from .visitor import as_visitor


class CheckerBuilder:
    """Builder for checking runs (`src/checker.rs:35-179`)."""

    def __init__(self, model: Model):
        self.model = model
        self.symmetry_fn_: Optional[Callable[[Any], Any]] = None
        self.target_state_count_: Optional[int] = None
        self.thread_count_: int = 1
        self.visitor_ = None
        self.tpu_options_: dict = {}
        self.resume_path_ = None
        self.sound_eventually_: bool = False

    def symmetry(self) -> "CheckerBuilder":
        """Enable symmetry reduction via ``state.representative()``
        (`src/checker.rs:150-154`)."""
        return self.symmetry_fn(lambda state: state.representative())

    def symmetry_fn(self, representative: Callable[[Any], Any]) -> "CheckerBuilder":
        self.symmetry_fn_ = representative
        return self

    def target_state_count(self, count: int) -> "CheckerBuilder":
        """The checker may exceed this count but never stops short of it
        while more states exist (`src/checker.rs:163-167`)."""
        self.target_state_count_ = count if count > 0 else None
        return self

    def threads(self, thread_count: int) -> "CheckerBuilder":
        """Host-engine worker count (`src/checker.rs:171-173`). With
        ``thread_count > 1``, ``spawn_bfs`` runs the level-synchronous
        multi-process engine and ``spawn_dfs`` the job-market
        multi-process DFS (the GIL rules out shared-memory threads;
        workers are separate processes sharing the visited table)."""
        self.thread_count_ = thread_count
        return self

    def visitor(self, visitor) -> "CheckerBuilder":
        self.visitor_ = as_visitor(visitor)
        return self

    def sound_eventually(self) -> "CheckerBuilder":
        """Include the pending ``eventually`` bits in the dedup identity.

        The reference accepts missed ``eventually`` counterexamples when a
        state is revisited with different pending bits (the documented
        FIXME at `/root/reference/src/checker/bfs.rs:239-244`; pinned by
        its ``fixme_can_miss_counterexample_when_revisiting_a_state``
        test). This opt-in goes beyond the reference: dedup works on
        (state, pending-bits) NODES, so DAG rejoins can no longer mask a
        counterexample, at the cost of exploring a state once per distinct
        pending-bits value (``unique_state_count`` counts nodes). The DFS
        engine is additionally lasso-COMPLETE (without symmetry
        reduction): expansion rejoining the CURRENT path with bits still
        pending reports immediately, and a post-exhaustion SCC sweep
        over the explored (state, pending-bits) node graph reports
        cycles entered via cross edges into already-explored branches —
        around any node-graph cycle the pending mask is invariant, so a
        cyclic SCC with bit ``i`` still set is an infinite run on which
        property ``i`` never holds. Under symmetry reduction only the
        on-path check runs (a cross-branch lap cannot be replayed
        through concrete orbit members). Supported by ``spawn_bfs``
        (single worker), ``spawn_dfs``, and the single-chip
        ``spawn_tpu`` device mode. A model with no ``eventually``
        properties is unaffected."""
        self.sound_eventually_ = True
        return self

    #: every ``tpu_options`` key an engine reads (``checker/``,
    #: ``parallel/``, ``service/``); any other key raises, so a
    #: misspelt or removed option fails loudly instead of running the
    #: default path
    TPU_OPTION_KEYS = frozenset({
        "artifact_dir", "audit", "autosave", "autosave_interval",
        "backoff", "blame_after", "capacity", "chunk_deadline",
        "chunk_steps", "corrupt_hook", "degrade", "exchange", "failover",
        "fault_hook", "flight", "flight_path", "fmax", "grow_at", "hcap",
        "hint", "hmax", "host_map", "host_table_capacity", "job_id",
        "kb", "kmax", "kraw", "max_capacity", "max_segment",
        "max_spills", "mesh", "mesh_axis", "min_mesh", "mode",
        "pipeline", "profile_dir", "race", "race_budget", "resumable",
        "retries", "retry_seed", "run_id", "spill", "spill_frac",
        "trace",
    })

    def tpu_options(self, **options) -> "CheckerBuilder":
        """Tuning knobs for ``spawn_tpu`` (table capacity, batch caps,
        mesh selection, ...). A key outside :attr:`TPU_OPTION_KEYS`
        raises ``ValueError`` naming it. Notable:

        * ``pipeline`` (default ``True``) double-buffers the chunk
          loop — chunk N+1 is dispatched while the host consumes chunk
          N's stats; ``pipeline=False`` forces the synchronous loop
          (debugging, latency A/B — observable results are identical
          either way, measurable via ``profile()['attribution']``);
        * ``trace=<path | file | callable | list>`` enables the
          structured run-trace: every engine (host engines included)
          emits timestamped JSONL events (chunk completed, growth and
          resize interventions, compiles, discoveries, ...) to the
          sink. Format and the metrics key glossary: README.md
          § Observability and ``stateright_tpu.obs``;
        * ``flight`` (default ``True``) keeps the **flight recorder**
          on: a bounded ring of the most recent trace events (no sink
          needed) dumped as a JSONL postmortem artifact on any engine
          error, watchdog expiry, exhausted retries, and each
          degradation rung — ``checker.flight_path()`` names the
          artifact, ``tools/trace_report.py`` reads it. ``flight=N``
          resizes the ring, ``flight=False`` disables it (restoring
          the zero-cost NULL trace), ``flight_path=...`` pins the
          artifact destination (default: next to ``autosave``, else
          the temp dir);
        * ``profile_dir=path`` captures a ``jax.profiler`` trace of
          the whole run into the directory (TensorBoard/Perfetto),
          device ops beside the host phase spans (``sr.<phase>``) —
          the deep-dive tier under ``profile()``'s span timers;
        * resilience (README § Resilience, ``checker/resilience.py``):
          ``retries=N`` retries a transient backend fault (UNAVAILABLE,
          DEADLINE_EXCEEDED, connection resets) up to N consecutive times,
          re-seeding the device from the host-side shadow;
          ``backoff=s`` is the first retry delay (exponential,
          jittered); ``chunk_deadline=s`` converts a hung chunk sync
          into a classified transient fault (watchdog);
          ``autosave=path`` + ``autosave_interval=chunks`` checkpoint
          progress periodically and on exhausted retries (resume via
          ``resume_from``); ``retry_seed=n`` pins the backoff jitter
          to a private RNG stream (deterministic fault tests);
          ``degrade=True`` (default) + ``min_mesh=1`` gate the mesh
          degradation ladder — a sharded run that exhausts its retries
          (or whose faults pin on one chip) re-routes the pending
          frontier onto the surviving power-of-two device subset,
          D -> D/2 -> ... -> single chip, before any host fallback;
          ``failover=False`` opts a raced run out of the final
          device->host rung."""
        unknown = sorted(set(options) - self.TPU_OPTION_KEYS)
        if unknown:
            raise ValueError(
                f"unknown tpu_options key(s) {', '.join(unknown)}; "
                f"accepted: {', '.join(sorted(self.TPU_OPTION_KEYS))}")
        self.tpu_options_.update(options)
        return self

    def resume_from(self, path) -> "CheckerBuilder":
        """Resume a ``spawn_tpu`` run from a checkpoint written by
        ``Checker.save`` (the TLC-style fingerprint record + pending
        frontier; SURVEY.md §5 checkpoint note)."""
        self.resume_path_ = path
        return self

    def spawn_bfs(self) -> "Checker":
        """Breadth-first host engine (`src/checker.rs:116-130`); with
        ``threads(n > 1)``, multi-process over frontier blocks."""
        if self.thread_count_ > 1 and self.visitor_ is None:
            from .parallel_bfs import ParallelBfsChecker
            return ParallelBfsChecker(self)
        from .bfs import BfsChecker
        return BfsChecker(self)

    def spawn_dfs(self) -> "Checker":
        """Depth-first host engine (`src/checker.rs:132-145`). The only host
        engine supporting symmetry reduction, as in the reference; with
        ``threads(n > 1)``, the job-market multi-process DFS
        (`dfs.rs:76-159`)."""
        if (self.thread_count_ > 1 and self.visitor_ is None
                and not self.sound_eventually_):
            from .parallel_dfs import ParallelDfsChecker
            return ParallelDfsChecker(self)
        from .dfs import DfsChecker
        return DfsChecker(self)

    def spawn_tpu(self) -> "Checker":
        """TPU-native engine: vmapped frontier expansion with device-resident
        fingerprint dedup. Requires the model to implement the
        :class:`~stateright_tpu.models.packed.PackedModel` protocol.
        With ``tpu_options(mesh=jax.sharding.Mesh(...))`` the search runs
        SPMD over the mesh: frontier, visited table and logs sharded by
        fingerprint prefix, children routed to owner shards over ICI.
        The whole call is the ``spawn`` span of the device engine."""
        from ..obs import annotate
        from .race import RacingChecker, race_eligible
        t0 = time.perf_counter()
        with annotate("spawn"):
            if race_eligible(self):
                # small-model latency: the device engine's fixed
                # dispatch + sync costs dwarf tiny models, so a budgeted
                # host BFS races the device run and the first finisher
                # wins (see checker/race.py); tpu_options(race=False)
                # opts out. Mesh runs race only on explicit race=True
                # (the device lane is then the sharded engine).
                checker = RacingChecker(self, spawn_t0=t0)
                engine = checker._tpu
            else:
                checker = engine = device_engine(self, spawn_t0=t0)
        # the recorder is born with the engine: the span is recorded
        # once it exists
        engine._spans.record("spawn", t0, time.perf_counter())
        return checker

    def serve(self, address, engine: str = "bfs") -> "Checker":
        """Start the Explorer web service (`src/checker.rs:99-114`).
        ``engine="tpu"`` runs the device engine behind the browser (the
        reference always spawns BFS, `explorer.rs:85-88`)."""
        from .explorer import serve as explorer_serve
        return explorer_serve(self, address, engine=engine)


class Checker:
    """Results interface shared by all engines (`src/checker.rs:185-338`)."""

    # --- engine-provided -------------------------------------------------
    def model(self) -> Model:
        raise NotImplementedError

    def state_count(self) -> int:
        """Total states generated including repeats (>= unique)."""
        raise NotImplementedError

    def unique_state_count(self) -> int:
        raise NotImplementedError

    def discoveries(self) -> Dict[str, Path]:
        raise NotImplementedError

    def join(self) -> "Checker":
        raise NotImplementedError

    def is_done(self) -> bool:
        raise NotImplementedError

    # --- shared helpers --------------------------------------------------
    def error(self) -> Optional[BaseException]:
        """The engine's failure, if any (overridden by engines)."""
        return None

    def profile(self) -> Dict[str, float]:
        """Snapshot of the run's metrics registry (phase timers,
        counters, observed maxima). Key meanings are documented once,
        in ``stateright_tpu.obs.GLOSSARY`` (rendered in README.md
        § Observability). Engines without instrumentation report {}."""
        return {}

    def _metrics_summary(self, elapsed: float) -> str:
        """One compact ``# key=value ...`` line from the metrics
        registry (empty when there is nothing beyond the raw timer)."""
        prof = self.profile()
        parts: List[str] = []
        if "engine" in prof:
            parts.append(f"engine={prof['engine']}")
        for key in ("chunks", "levels", "jobs", "grows", "hgrows",
                    "kovfs", "compiles", "retries", "failovers",
                    "degrades", "autosaves"):
            if prof.get(key):
                parts.append(f"{key}={int(prof[key])}")
        if prof.get("degrades"):
            # a degraded run finished on fewer chips; name the final
            # width and the blamed device so the line says WHY
            if "mesh_shards" in prof:
                parts.append(f"mesh={int(prof['mesh_shards'])}")
            if "fault_device" in prof:
                parts.append(f"fault_device={int(prof['fault_device'])}")
        attribution = prof.get("attribution") or {}
        wall = sum(attribution.values())
        if wall > 0:
            # the chunk loop's span attribution (obs/spans.py): stall =
            # device-bound share, overlap = host work hidden under it
            from ..obs import DEVICE_SPANS
            stall = sum(v for k, v in attribution.items()
                        if k in DEVICE_SPANS)
            parts.append(f"stall={stall / wall:.0%}")
            parts.append(
                f"overlap={attribution.get('overlap', 0.0) / wall:.0%}")
        if "shard_balance" in prof:
            parts.append(f"shard_balance={prof['shard_balance']}")
        return "# " + " ".join(parts) if parts else ""

    def discovery(self, name: str) -> Optional[Path]:
        return self.discoveries().get(name)

    def report(self, w) -> "Checker":
        """Periodic status lines + discovery summary (`src/checker.rs:217-242`).

        Emits ``Checking. states=N, unique=N`` once per second while running,
        then ``Done. states=N, unique=N, sec=S[, rate=R/s]``, a compact
        ``# chunks=... stall=...`` metrics line when the engine recorded
        any (key glossary: ``stateright_tpu.obs.GLOSSARY``), and one
        block per discovery.
        """
        start = time.monotonic()
        if not self.is_done():
            w.write(f"Checking. states={self.state_count()}, "
                    f"unique={self.unique_state_count()}\n")
            self._start_background()
            last_print = time.monotonic()
            while not self.is_done():
                time.sleep(0.01)
                now = time.monotonic()
                if now - last_print >= 1.0:
                    w.write(f"Checking. states={self.state_count()}, "
                            f"unique={self.unique_state_count()}\n")
                    last_print = now
        err = self.error()
        if err is not None:
            raise err
        elapsed = time.monotonic() - start
        rate = (f", rate={self.state_count() / elapsed:.0f}/s"
                if elapsed > 0.1 else "")
        w.write(f"Done. states={self.state_count()}, "
                f"unique={self.unique_state_count()}, "
                f"sec={int(elapsed)}{rate}\n")
        summary = self._metrics_summary(elapsed)
        if summary:
            w.write(summary + "\n")
        for name, path in self.discoveries().items():
            w.write(f'Discovered "{name}" '
                    f"{self.discovery_classification(name)} {path}")
        return self

    def _start_background(self) -> None:
        """Hook for engines that can make progress concurrently."""
        pass

    def discovery_classification(self, name: str) -> str:
        prop = self.model().property(name)
        if prop.expectation in (Expectation.ALWAYS, Expectation.EVENTUALLY):
            return "counterexample"
        return "example"

    def assert_properties(self) -> None:
        """Examples exist for every ``sometimes``; no counterexamples for any
        ``always``/``eventually`` (`src/checker.rs:256-267`)."""
        for p in self.model().properties():
            if p.expectation == Expectation.SOMETIMES:
                self.assert_any_discovery(p.name)
            else:
                self.assert_no_discovery(p.name)

    def _raise_engine_error(self) -> None:
        """A crashed engine must not read as "checked clean"."""
        err = self.error()
        if err is not None:
            raise err

    def assert_any_discovery(self, name: str) -> Path:
        found = self.discovery(name)
        if found is not None:
            return found
        self._raise_engine_error()
        assert self.is_done(), (
            f'Discovery for "{name}" not found, but model checking is '
            "incomplete.")
        raise AssertionError(f'Discovery for "{name}" not found.')

    def assert_no_discovery(self, name: str) -> None:
        found = self.discovery(name)
        if found is not None:
            raise AssertionError(
                f'Unexpected "{name}" {self.discovery_classification(name)} '
                f"{found}Last state: {found.last_state()!r}\n")
        self._raise_engine_error()
        assert self.is_done(), (
            f'Discovery for "{name}" not found, but model checking is '
            "incomplete.")

    def assert_discovery(self, name: str, actions: Sequence[Any]) -> None:
        """Panics unless ``actions`` also witness the property
        (`src/checker.rs:291-338`)."""
        additional_info: List[str] = []
        found = self.assert_any_discovery(name)
        model = self.model()
        for init_state in model.init_states():
            path = Path.from_actions(model, init_state, actions)
            if path is None:
                continue
            prop = model.property(name)
            if prop.expectation == Expectation.ALWAYS:
                if not prop.condition(model, path.last_state()):
                    return
            elif prop.expectation == Expectation.EVENTUALLY:
                states = path.into_states()
                is_satisfied = any(prop.condition(model, s) for s in states)
                acts: List[Any] = []
                model.actions(states[-1], acts)
                is_terminal = not acts
                if not is_satisfied and is_terminal:
                    return
                if is_satisfied:
                    additional_info.append(
                        "incorrect counterexample satisfies eventually property")
                if not is_terminal:
                    additional_info.append(
                        "incorrect counterexample is nonterminal")
            else:
                if prop.condition(model, path.last_state()):
                    return
        info = f" ({'; '.join(additional_info)})" if additional_info else ""
        raise AssertionError(
            f'Invalid discovery for "{name}"{info}, but a valid one was '
            f"found. found={found.into_actions()!r}")


def device_engine(builder: CheckerBuilder,
                  spawn_t0: Optional[float] = None) -> "Checker":
    """The device engine ``builder`` asks for — sharded over
    ``tpu_options(mesh=...)``, else single-chip — its construction
    recorded as the engine's ``init`` span. ``spawn_t0`` is
    ``spawn_tpu()``'s entry stamp, where ``first_dispatch_s`` counts
    from."""
    from ..obs import annotate
    t0 = time.perf_counter()
    with annotate("init"):
        if "mesh" in builder.tpu_options_:
            from ..parallel.engine import ShardedTpuChecker
            engine = ShardedTpuChecker(builder, spawn_t0)
        else:
            from .tpu import TpuChecker
            engine = TpuChecker(builder, spawn_t0)
    engine._spans.record("init", t0, time.perf_counter())
    return engine
