"""Resilience layer (checker/resilience.py + README § Resilience).

A transient backend fault injected mid-run must change NOTHING the
checker reports: discoveries, unique counts, and reached fingerprint
sets are pinned against an uninterrupted run across the single-chip and
sharded engines, pipelined and synchronous. Exhausted retries degrade
instead of dying — an ``autosave=`` checkpoint loads and completes via
``resume_from``; a raced run fails over to an un-budgeted host BFS; a
hung chunk sync is converted to a classified fault by the watchdog —
and ``bench.py`` always lands a valid JSON contract line, even with
every device workload forced to fail.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from stateright_tpu.checker.resilience import (  # noqa: E402
    CAPACITY_MARKERS, ChunkDeadlineError, FaultKind, RetryPolicy,
    classify_error, match_device, resolve_grant, select_survivors)
from stateright_tpu.examples.paxos_packed import PackedPaxos  # noqa: E402
from stateright_tpu.models.twopc import TwoPhaseSys  # noqa: E402

pytestmark = pytest.mark.faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _unavailable(msg="UNAVAILABLE: fake backend drop (injected)"):
    return RuntimeError(msg)


def _hook_at(k):
    """Raise a fake transient backend fault when chunk ``k`` syncs."""

    def hook(chunk):
        if chunk == k:
            raise _unavailable()

    return hook


def _run(mk, **opts):
    return (mk().checker().tpu_options(race=False, **opts)
            .spawn_tpu().join())


def _mesh(n):
    devices = jax.devices()
    if len(devices) < n:
        pytest.skip(f"need {n} devices, have {len(devices)}")
    from jax.sharding import Mesh
    return Mesh(np.array(devices[:n]), ("shards",))


def _dead_above(shards_alive, device=None):
    """A chip that is gone FOR GOOD: every chunk sync faults while the
    mesh is wider than ``shards_alive`` (two-parameter hooks receive
    the current mesh width, so the fault disappears once the ladder
    has dropped the dead chip). ``device`` names the blamed chip in
    the error message, like a real PJRT status string."""
    msg = ("UNAVAILABLE: fake permanent chip death (injected)"
           if device is None else
           f"UNAVAILABLE: device {device} fell off the mesh (injected)")

    def hook(chunk, shards):
        if shards > shards_alive:
            raise RuntimeError(msg)

    return hook


def _dead_above_after(shards_alive, k):
    """Like :func:`_dead_above`, but the chip only dies at chunk ``k``
    (chunk ordinals are cumulative across recoveries) — lets the run
    make real progress, e.g. through growth passes, first."""

    def hook(chunk, shards):
        if chunk >= k and shards > shards_alive:
            raise RuntimeError(
                "UNAVAILABLE: fake permanent chip death (injected)")

    return hook


def _assert_parity(faulty, clean):
    assert faulty.unique_state_count() == clean.unique_state_count()
    assert (faulty.generated_fingerprints()
            == clean.generated_fingerprints())
    assert set(faulty.discoveries()) == set(clean.discoveries())


class TestClassification:
    def test_transient_markers(self):
        for msg in ("UNAVAILABLE: TPU backend setup/compile error",
                    "DEADLINE_EXCEEDED: slice op",
                    "connection reset by peer",
                    "socket closed by the remote end"):
            assert classify_error(RuntimeError(msg)) \
                is FaultKind.TRANSIENT, msg
        assert classify_error(ChunkDeadlineError("hung")) \
            is FaultKind.TRANSIENT
        assert classify_error(ConnectionResetError()) \
            is FaultKind.TRANSIENT

    def test_capacity_markers(self):
        for msg in ("RESOURCE_EXHAUSTED: out of memory while trying",
                    "device hash table overflow while seeding",
                    "packed-state capacity overflow: ..."):
            assert classify_error(RuntimeError(msg)) \
                is FaultKind.CAPACITY, msg
        # the engines' real overflow messages stay capacity-classified
        for marker in CAPACITY_MARKERS:
            assert classify_error(RuntimeError(marker)) \
                is FaultKind.CAPACITY

    def test_programming_default_and_cause_chain(self):
        assert classify_error(ValueError("a model bug")) \
            is FaultKind.PROGRAMMING
        # a wrapper raised `from` a transient error keeps the class
        # (the degrade path's RuntimeError must stay failover-eligible)
        try:
            try:
                raise _unavailable()
            except RuntimeError as inner:
                raise RuntimeError("run failed after retries") from inner
        except RuntimeError as wrapped:
            assert classify_error(wrapped) is FaultKind.TRANSIENT

    def test_retry_policy_bounds(self):
        with pytest.raises(ValueError):
            RetryPolicy(retries=-1)
        p = RetryPolicy(retries=3, backoff=1.0)
        assert p.enabled
        for attempt in (1, 2, 3, 8):
            d = p.delay(attempt)
            assert 0.0 < d <= p.cap * (1 + p.jitter)
        assert RetryPolicy(retries=0).enabled is False
        assert RetryPolicy(retries=2, backoff=0.0).delay(1) == 0.0

    def test_retry_policy_seeded_jitter_deterministic(self):
        # tpu_options(retry_seed=...) pins the jitter to a private RNG
        # stream: same seed -> same delay sequence, independent of the
        # global RNG state, PYTHONHASHSEED, and reruns
        def seq(p):
            return [p.delay(i) for i in (1, 2, 3, 4)]

        assert seq(RetryPolicy(retries=3, backoff=1.0, seed=42)) \
            == seq(RetryPolicy(retries=3, backoff=1.0, seed=42))
        assert seq(RetryPolicy(retries=3, backoff=1.0, seed=42)) \
            != seq(RetryPolicy(retries=3, backoff=1.0, seed=7))
        opts = {"retries": 2, "backoff": 1.0, "retry_seed": 5}
        assert seq(RetryPolicy.from_options(opts)) \
            == seq(RetryPolicy.from_options(dict(opts)))
        import random
        random.seed(0)
        a = seq(RetryPolicy(retries=3, backoff=1.0, seed=9))
        random.seed(12345)
        assert a == seq(RetryPolicy(retries=3, backoff=1.0, seed=9))

    def test_blamed_device_attribution(self):
        from stateright_tpu.checker.resilience import blamed_device
        assert blamed_device(RuntimeError(
            "UNAVAILABLE: device 3 heartbeat lost")) == 3
        assert blamed_device(RuntimeError(
            "UNAVAILABLE: TPU_2 connection reset")) == 2
        assert blamed_device(RuntimeError(
            "ABORTED: chip 1 power fault")) == 1
        assert blamed_device(RuntimeError(
            "UNAVAILABLE: backend gone")) is None
        err = RuntimeError("UNAVAILABLE: gone")
        err.device_index = 5
        assert blamed_device(err) == 5
        # attribution walks the cause chain like classify_error
        try:
            try:
                raise RuntimeError("UNAVAILABLE: device 4 dead")
            except RuntimeError as inner:
                raise RuntimeError("retries exhausted") from inner
        except RuntimeError as wrapped:
            assert blamed_device(wrapped) == 4

    def test_fault_attributor_streak(self):
        from stateright_tpu.checker.resilience import FaultAttributor
        a = FaultAttributor(blame_after=2)
        assert not a.note(3)
        assert a.note(3)          # same chip twice in a row
        a.clear()
        assert not a.note(3)
        assert not a.note(2)      # a different chip resets the streak
        assert a.note(2)
        assert not a.note(None)   # unattributable faults break streaks
        assert a.totals == {3: 3, 2: 2}  # lifetime totals survive clear()

    def test_degrade_policy_bounds(self):
        from stateright_tpu.checker.resilience import DegradePolicy
        assert DegradePolicy.from_options({}).enabled
        assert DegradePolicy.from_options({}).min_mesh == 1
        assert not DegradePolicy.from_options({"degrade": False}).enabled
        with pytest.raises(ValueError, match="min_mesh"):
            DegradePolicy(min_mesh=3)
        with pytest.raises(ValueError, match="min_mesh"):
            (TwoPhaseSys(3).checker()
             .tpu_options(race=False, min_mesh=3).spawn_tpu())


@pytest.fixture(scope="module")
def clean_paxos1():
    """One uninterrupted single-chip paxos run (host-evaluated
    linearizability), shared by the retry and degrade parity tests."""
    return _run(lambda: PackedPaxos(1), capacity=1 << 12, fmax=64,
                chunk_steps=2)


class TestRetryParity:
    """Acceptance: an injected transient UNAVAILABLE on chunk k leaves
    discoveries and unique/generated fingerprint sets identical to the
    uninterrupted run, with profile()['retries'] == 1."""

    def test_single_chip_pipelined(self):
        clean = _run(lambda: TwoPhaseSys(3), capacity=1 << 12, fmax=64,
                     chunk_steps=2)
        faulty = _run(lambda: TwoPhaseSys(3), capacity=1 << 12, fmax=64,
                      chunk_steps=2, retries=2, backoff=0.0,
                      fault_hook=_hook_at(2))
        _assert_parity(faulty, clean)
        assert faulty.profile()["retries"] == 1

    def test_single_chip_sync(self):
        clean = _run(lambda: TwoPhaseSys(3), capacity=1 << 12, fmax=64,
                     chunk_steps=2, pipeline=False)
        faulty = _run(lambda: TwoPhaseSys(3), capacity=1 << 12, fmax=64,
                      chunk_steps=2, pipeline=False, retries=2,
                      backoff=0.0, fault_hook=_hook_at(2))
        _assert_parity(faulty, clean)
        assert faulty.profile()["retries"] == 1

    def test_sharded(self):
        mesh = _mesh(2)
        clean = _run(lambda: TwoPhaseSys(3), capacity=1 << 12, fmax=64,
                     chunk_steps=2, mesh=mesh)
        faulty = _run(lambda: TwoPhaseSys(3), capacity=1 << 12, fmax=64,
                      chunk_steps=2, mesh=mesh, retries=2, backoff=0.0,
                      fault_hook=_hook_at(2))
        _assert_parity(faulty, clean)
        assert faulty.profile()["retries"] == 1

    def test_host_props_and_witness_paths(self, clean_paxos1):
        # paxos: 'linearizable' is host-evaluated — the recovery must
        # re-arm the in-carry history dedup and keep memoized results
        faulty = _run(lambda: PackedPaxos(1), capacity=1 << 12, fmax=64,
                      chunk_steps=2, retries=2, backoff=0.0,
                      fault_hook=_hook_at(2))
        _assert_parity(faulty, clean_paxos1)
        faulty.assert_properties()

    def test_mid_growth_recovery(self):
        # a fault landing after table growths: the re-seeded table must
        # re-insert the whole (grown) mirror
        clean = _run(lambda: TwoPhaseSys(4), capacity=1 << 8, fmax=16,
                     chunk_steps=2)
        faulty = _run(lambda: TwoPhaseSys(4), capacity=1 << 8, fmax=16,
                      chunk_steps=2, retries=2, backoff=0.0,
                      fault_hook=_hook_at(3))
        _assert_parity(faulty, clean)
        assert faulty.profile()["retries"] == 1
        assert clean.profile().get("grows", 0) > 0

    def test_retry_trace_events(self):
        trace = []
        _run(lambda: TwoPhaseSys(3), capacity=1 << 12, fmax=64,
             chunk_steps=2, retries=2, backoff=0.0,
             fault_hook=_hook_at(2), trace=trace)
        retries = [e for e in trace if e["ev"] == "retry"]
        assert len(retries) == 1
        assert retries[0]["attempt"] == 1
        assert "UNAVAILABLE" in retries[0]["error"]
        from stateright_tpu.obs import validate_event
        for ev in trace:
            validate_event(ev)

    def test_sound_eventually_retry(self):
        # the lasso sweep must rebuild from the shadow's cross-run edge
        # records, not the (epoch-only) device logs
        from stateright_tpu.core import Property
        from stateright_tpu.models.fixtures import PackedDGraph

        def cyc():
            return (PackedDGraph.with_property(
                Property.eventually("odd", lambda _, s: s % 2 == 1))
                .with_path([0, 2, 4, 2]))

        clean = (cyc().checker().sound_eventually()
                 .tpu_options(race=False, capacity=1 << 10,
                              chunk_steps=1).spawn_tpu().join())
        assert "odd" in clean.discoveries()
        faulty = (cyc().checker().sound_eventually()
                  .tpu_options(race=False, capacity=1 << 10,
                               chunk_steps=1, retries=2, backoff=0.0,
                               fault_hook=_hook_at(2))
                  .spawn_tpu().join())
        assert "odd" in faulty.discoveries()
        assert (faulty.generated_fingerprints()
                == clean.generated_fingerprints())

    def test_non_transient_faults_not_retried(self):
        def hook(chunk):
            if chunk == 2:
                raise ValueError("a genuine model bug")

        with pytest.raises(ValueError, match="model bug"):
            _run(lambda: TwoPhaseSys(3), capacity=1 << 12, fmax=64,
                 chunk_steps=2, retries=2, backoff=0.0, fault_hook=hook)


@pytest.fixture(scope="module")
def clean_2pc3_d2():
    """One uninterrupted D=2 oracle run shared by the degrade parity
    tests (set-semantics parity is pipeline-agnostic)."""
    return _run(lambda: TwoPhaseSys(3), capacity=1 << 12, fmax=64,
                chunk_steps=2, mesh=_mesh(2))


@pytest.fixture(scope="module")
def clean_2pc3_single():
    """One uninterrupted single-chip oracle run (the ladder's bottom
    rung parity target)."""
    return _run(lambda: TwoPhaseSys(3), capacity=1 << 12, fmax=64,
                chunk_steps=2)


class TestDegrade:
    """Acceptance: a permanently failing chip shrinks the mesh instead
    of ending the run — D=4 degrades to D=2 (virtual CPU mesh) with
    discoveries and unique/generated fingerprint sets bit-identical to
    an uninterrupted D=2 run, pipelined and synchronous; the ladder
    descends to the single-chip rung; raced mesh runs prefer a
    degraded device finish over the host-BFS failover."""

    def test_permanent_fault_degrades_to_half_mesh_pipelined(
            self, clean_2pc3_d2):
        faulty = _run(lambda: TwoPhaseSys(3), capacity=1 << 12, fmax=64,
                      chunk_steps=2, mesh=_mesh(4), retries=1,
                      backoff=0.0, fault_hook=_dead_above(2))
        _assert_parity(faulty, clean_2pc3_d2)
        prof = faulty.profile()
        assert prof["degrades"] == 1
        assert prof["mesh_shards"] == 2
        assert prof["retries"] == 1  # the budget was spent, then the rung

    def test_permanent_fault_degrades_to_half_mesh_sync(
            self, clean_2pc3_d2):
        faulty = _run(lambda: TwoPhaseSys(3), capacity=1 << 12, fmax=64,
                      chunk_steps=2, mesh=_mesh(4), pipeline=False,
                      retries=1, backoff=0.0, fault_hook=_dead_above(2))
        _assert_parity(faulty, clean_2pc3_d2)
        prof = faulty.profile()
        assert prof["degrades"] == 1
        assert prof["mesh_shards"] == 2

    def test_blamed_chip_is_dropped_without_burning_budget(
            self, clean_2pc3_d2):
        # consecutive faults naming ONE chip drop a rung after
        # blame_after=2, not after the full retries=5 budget — and the
        # blamed device leaves the surviving mesh
        trace = []
        faulty = _run(lambda: TwoPhaseSys(3), capacity=1 << 12, fmax=64,
                      chunk_steps=2, mesh=_mesh(4), retries=5,
                      backoff=0.0, fault_hook=_dead_above(2, device=3),
                      trace=trace)
        _assert_parity(faulty, clean_2pc3_d2)
        prof = faulty.profile()
        assert prof["degrades"] == 1
        assert prof["retries"] == 1  # one retry, then the blame streak
        assert prof["fault_device"] == 3
        assert jax.devices()[3] not in list(faulty._mesh.devices.flat)
        degrades = [e for e in trace if e["ev"] == "degrade"]
        assert len(degrades) == 1
        assert degrades[0]["from_shards"] == 4
        assert degrades[0]["to_shards"] == 2
        assert degrades[0]["device"] == 3
        retries = [e for e in trace if e["ev"] == "retry"]
        assert retries and retries[0]["device"] == 3
        assert retries[0]["shards"] == 4
        from stateright_tpu.obs import validate_event
        for ev in trace:
            validate_event(ev)

    def test_ladder_descends_to_single_chip(self, clean_2pc3_single):
        # D=4 -> D=2 -> the single-chip rung (TpuChecker._run_device
        # adopting the shadow handoff); parity against an uninterrupted
        # single-chip run
        faulty = _run(lambda: TwoPhaseSys(3), capacity=1 << 12, fmax=64,
                      chunk_steps=2, mesh=_mesh(4), retries=1,
                      backoff=0.0, fault_hook=_dead_above(1))
        _assert_parity(faulty, clean_2pc3_single)
        prof = faulty.profile()
        assert prof["degrades"] == 2
        assert prof["mesh_shards"] == 1

    @pytest.mark.slow
    def test_late_fault_reinserts_accumulated_mirror(self):
        # a fault landing chunks into the run: the degraded mesh must
        # re-route the mid-flight frontier AND re-insert the whole
        # accumulated mirror at the new D (preload-aware limits)
        clean = _run(lambda: TwoPhaseSys(4), capacity=1 << 8, fmax=16,
                     chunk_steps=2, mesh=_mesh(2))
        faulty = _run(lambda: TwoPhaseSys(4), capacity=1 << 8, fmax=16,
                      chunk_steps=2, mesh=_mesh(4), retries=1,
                      backoff=0.0, fault_hook=_dead_above_after(2, 3))
        _assert_parity(faulty, clean)
        assert faulty.profile()["degrades"] == 1

    @pytest.mark.slow
    def test_sound_degrade_to_single_chip_keeps_lasso(self):
        # sound mode across a rung: the post-exhaustion SCC sweep must
        # rebuild from the shadow's cross-RUNG insert/edge records
        # (resharded down the ladder), not any single epoch's logs
        from stateright_tpu.core import Property
        from stateright_tpu.models.fixtures import PackedDGraph

        def cyc():
            return (PackedDGraph.with_property(
                Property.eventually("odd", lambda _, s: s % 2 == 1))
                .with_path([0, 2, 4, 2]))

        clean = (cyc().checker().sound_eventually()
                 .tpu_options(race=False, capacity=1 << 10,
                              chunk_steps=1).spawn_tpu().join())
        assert "odd" in clean.discoveries()
        faulty = (cyc().checker().sound_eventually()
                  .tpu_options(race=False, capacity=1 << 10, fmax=16,
                               chunk_steps=1, mesh=_mesh(2), retries=1,
                               backoff=0.0, fault_hook=_dead_above(1))
                  .spawn_tpu().join())
        assert "odd" in faulty.discoveries()
        assert (faulty.generated_fingerprints()
                == clean.generated_fingerprints())
        assert faulty.profile()["degrades"] == 1
        assert faulty.profile()["mesh_shards"] == 1

    @pytest.mark.slow
    def test_host_props_degrade_to_single_chip(self, clean_paxos1):
        # paxos: 'linearizable' is host-evaluated — the sharded rung
        # uses the post-hoc per-shard reduction, the single-chip rung
        # the in-carry history dedup; the handoff must keep memoized
        # results and carry prior discoveries across the switch
        faulty = _run(lambda: PackedPaxos(1), capacity=1 << 12, fmax=64,
                      chunk_steps=2, mesh=_mesh(2), retries=1,
                      backoff=0.0, fault_hook=_dead_above(1))
        _assert_parity(faulty, clean_paxos1)
        assert faulty.profile()["degrades"] == 1
        faulty.assert_properties()

    def test_min_mesh_floors_the_ladder(self, tmp_path,
                                        clean_2pc3_single):
        # min_mesh=2: the ladder stops at D=2; a fault persisting there
        # takes the old ending (autosave checkpoint + actionable raise)
        path = tmp_path / "floor.npz"
        ck = (TwoPhaseSys(3).checker()
              .tpu_options(race=False, capacity=1 << 12, fmax=64,
                           chunk_steps=2, mesh=_mesh(4), retries=1,
                           backoff=0.0, min_mesh=2,
                           autosave=os.fspath(path),
                           fault_hook=_dead_above(1))
              .spawn_tpu())
        with pytest.raises(RuntimeError, match="resume_from"):
            ck.join()
        assert ck.profile()["degrades"] == 1  # 4 -> 2, then the floor
        assert path.exists()
        # the autosave written at the DEGRADED width resumes anywhere
        resumed = (TwoPhaseSys(3).checker()
                   .tpu_options(capacity=1 << 12)
                   .resume_from(path).spawn_tpu().join())
        assert (resumed.generated_fingerprints()
                == clean_2pc3_single.generated_fingerprints())

    def test_degrade_opt_out_keeps_old_ending(self):
        ck = (TwoPhaseSys(3).checker()
              .tpu_options(race=False, capacity=1 << 12, fmax=64,
                           chunk_steps=2, mesh=_mesh(4), retries=1,
                           backoff=0.0, degrade=False,
                           fault_hook=_dead_above(2))
              .spawn_tpu())
        with pytest.raises(RuntimeError, match="autosave"):
            ck.join()
        assert "degrades" not in ck.profile()

    def test_mesh_races_only_on_explicit_opt_in(self):
        from stateright_tpu.checker.race import race_eligible
        assert not race_eligible(
            TwoPhaseSys(3).checker().tpu_options(mesh=_mesh(2)))
        assert race_eligible(
            TwoPhaseSys(3).checker().tpu_options(mesh=_mesh(2),
                                                 race=True))
        assert not race_eligible(
            TwoPhaseSys(3).checker().tpu_options(mesh=_mesh(2),
                                                 race=False))

    def test_raced_mesh_prefers_ladder_over_failover(self):
        # acceptance: a raced run under a permanent D=4 fault finishes
        # on the DEGRADED device engine, not the host fallback
        ck = (TwoPhaseSys(4).checker()
              .tpu_options(capacity=1 << 12, fmax=64, chunk_steps=2,
                           mesh=_mesh(4), race=True, race_budget=0.0,
                           retries=1, backoff=0.0,
                           fault_hook=_dead_above(2))
              .spawn_tpu().join())
        host = TwoPhaseSys(4).checker().spawn_bfs().join()
        assert ck.unique_state_count() == host.unique_state_count()
        assert (ck.generated_fingerprints()
                == host.generated_fingerprints())
        prof = ck.profile()
        assert prof["engine"] == "device"
        assert prof["degrades"] >= 1
        assert prof.get("failovers", 0) == 0
        ck.assert_properties()

    def test_raced_mesh_ladder_exhaustion_still_fails_over(self):
        # every rung dead (the hook faults at every width, single chip
        # included): the ladder exhausts and the race's un-budgeted
        # host BFS remains the last rung
        ck = (TwoPhaseSys(4).checker()
              .tpu_options(capacity=1 << 12, fmax=64, chunk_steps=2,
                           mesh=_mesh(4), race=True, race_budget=0.0,
                           retries=1, backoff=0.0,
                           fault_hook=_dead_above(0))
              .spawn_tpu().join())
        host = TwoPhaseSys(4).checker().spawn_bfs().join()
        assert ck.unique_state_count() == host.unique_state_count()
        prof = ck.profile()
        assert prof["engine"] == "host"
        assert prof["failovers"] == 1


class TestAutosave:
    def test_exhausted_retries_write_loadable_checkpoint(self, tmp_path):
        path = tmp_path / "auto.npz"

        def hook(chunk):
            if chunk >= 2:
                raise _unavailable()

        ck = (TwoPhaseSys(3).checker()
              .tpu_options(race=False, capacity=1 << 12, fmax=64,
                           chunk_steps=2, retries=1, backoff=0.0,
                           autosave=os.fspath(path), fault_hook=hook)
              .spawn_tpu())
        with pytest.raises(RuntimeError, match="resume_from"):
            ck.join()
        assert path.exists()
        assert ck.profile()["retries"] == 1
        assert ck.profile()["autosaves"] >= 1

        clean = _run(lambda: TwoPhaseSys(3), capacity=1 << 12)
        resumed = (TwoPhaseSys(3).checker()
                   .tpu_options(capacity=1 << 12)
                   .resume_from(path).spawn_tpu().join())
        assert resumed.unique_state_count() == 288
        assert (resumed.generated_fingerprints()
                == clean.generated_fingerprints())

    def test_periodic_autosave(self, tmp_path):
        path = tmp_path / "periodic.npz"
        trace = []
        ck = _run(lambda: TwoPhaseSys(3), capacity=1 << 12, fmax=64,
                  chunk_steps=2, autosave=os.fspath(path),
                  autosave_interval=1, trace=trace)
        assert ck.profile()["autosaves"] >= 1
        assert path.exists()
        saves = [e for e in trace if e["ev"] == "autosave"]
        assert saves and all("path" in e and "unique" in e
                             for e in saves)
        # the final autosave resumes to the full reached set
        resumed = (TwoPhaseSys(3).checker()
                   .tpu_options(capacity=1 << 12)
                   .resume_from(path).spawn_tpu().join())
        assert (resumed.generated_fingerprints()
                == ck.generated_fingerprints())

    @pytest.mark.slow
    def test_sharded_autosave_round_trips_across_mesh_sizes(
            self, tmp_path, clean_2pc3_single):
        # the shard-agnostic checkpoint claim (parallel/engine.py)
        # pinned ACROSS D changes — the degrade path depends on it: an
        # autosave written on a D=4 mesh must resume on D=2 and on a
        # single chip, converging to the same reached set
        path = tmp_path / "auto4.npz"

        def hook(chunk):  # legacy one-parameter hook shape
            if chunk >= 2:
                raise _unavailable()

        ck = (TwoPhaseSys(3).checker()
              .tpu_options(race=False, capacity=1 << 12, fmax=64,
                           chunk_steps=2, mesh=_mesh(4), retries=1,
                           backoff=0.0, degrade=False,
                           autosave=os.fspath(path), fault_hook=hook)
              .spawn_tpu())
        with pytest.raises(RuntimeError, match="resume_from"):
            ck.join()
        assert path.exists()
        assert ck.profile()["retries"] == 1
        for opts in ({"mesh": _mesh(2)}, {}):
            resumed = (TwoPhaseSys(3).checker()
                       .tpu_options(capacity=1 << 12, **opts)
                       .resume_from(path).spawn_tpu().join())
            assert resumed.unique_state_count() == 288, opts
            assert (resumed.generated_fingerprints()
                    == clean_2pc3_single.generated_fingerprints()), opts

    def test_degrade_without_autosave_names_the_knob(self):
        def hook(chunk):
            raise _unavailable()

        ck = (TwoPhaseSys(3).checker()
              .tpu_options(race=False, capacity=1 << 12, retries=1,
                           backoff=0.0, fault_hook=hook)
              .spawn_tpu())
        with pytest.raises(RuntimeError, match="autosave"):
            ck.join()

    def test_corrupt_newest_generation_resumes_from_previous(
            self, tmp_path, clean_2pc3_single):
        # ACCEPTANCE (silent-corruption defense, artifact leg): the
        # newest autosave is TRUNCATED on disk — the integrity chain
        # rejects it and ``resume_from`` rolls back to the previous
        # generation (``<path>.g1`` kept by rotation), completing to
        # full parity instead of resuming from garbage
        path = tmp_path / "auto.npz"
        ck = _run(lambda: TwoPhaseSys(3), capacity=1 << 12, fmax=64,
                  chunk_steps=2, autosave=os.fspath(path),
                  autosave_interval=1)
        assert ck.profile()["autosaves"] >= 2
        prev = str(path) + ".g1"
        assert os.path.exists(prev)  # rotation kept the generation
        with open(path, "r+b") as f:  # truncate mid-payload
            f.truncate(max(os.path.getsize(path) // 2, 16))
        trace = []
        resumed = (TwoPhaseSys(3).checker()
                   .tpu_options(capacity=1 << 12, trace=trace)
                   .resume_from(path).spawn_tpu().join())
        # reached-set parity (the resume-idiom pin: discoveries that
        # fired AFTER the older generation's sync are not replayed)
        assert resumed.unique_state_count() == \
            clean_2pc3_single.unique_state_count()
        assert (resumed.generated_fingerprints()
                == clean_2pc3_single.generated_fingerprints())
        rolls = [e for e in trace if e["ev"] == "corruption"]
        assert rolls and ".g1" in rolls[0]["error"]
        # with BOTH generations gone, the failure is actionable
        with open(prev, "r+b") as f:
            f.truncate(16)
        with pytest.raises(RuntimeError, match="integrity|checkpoint"):
            (TwoPhaseSys(3).checker().tpu_options(capacity=1 << 12)
             .resume_from(path).spawn_tpu().join())


class TestAudit:
    """Acceptance (silent-corruption defense, compute leg): a chip
    that RETURNS WRONG RESULTS — one fingerprint bit flipped by
    ``corrupt_hook`` in a chunk the auditor samples — is caught by
    re-executing the frontier slice (host oracle single-chip, a
    different device sharded), blamed, quarantined, and the run
    replayed from the last audited boundary finishes with counts,
    fingerprint sets, and discoveries bit-identical to an
    uncorrupted run; ``audit=False`` (the default) stays free."""

    def test_lying_chip_caught_single_pipelined(self, clean_2pc3_single):
        trace = []
        faulty = _run(lambda: TwoPhaseSys(3), capacity=1 << 12,
                      fmax=64, chunk_steps=2, audit=1, retries=2,
                      backoff=0.0, trace=trace,
                      corrupt_hook=lambda o, d: 0 if o == 2 else None)
        _assert_parity(faulty, clean_2pc3_single)
        prof = faulty.profile()
        assert prof["audits"] >= 1
        assert prof["audit_mismatches"] >= 1
        assert prof["quarantined"] == 1
        from stateright_tpu.obs.trace import validate_event
        by_kind = {}
        for e in trace:
            validate_event(e)
            by_kind.setdefault(e["ev"], []).append(e)
        assert any(e["mismatches"] for e in by_kind["audit"])
        assert "chip is returning wrong results" \
            in by_kind["corruption"][0]["error"]
        assert by_kind["quarantine"][0]["quarantined"] == 1

    def test_lying_chip_caught_single_sync(self, clean_2pc3_single):
        faulty = _run(lambda: TwoPhaseSys(3), capacity=1 << 12,
                      fmax=64, chunk_steps=2, audit=1, retries=2,
                      backoff=0.0, pipeline=False,
                      corrupt_hook=lambda o, d: 0 if o == 2 else None)
        _assert_parity(faulty, clean_2pc3_single)
        assert faulty.profile()["audit_mismatches"] >= 1

    def test_lying_shard_quarantined_and_degraded(self, clean_2pc3_d2):
        # a PERSISTENT liar at mesh position 1 while D=4 (the hook is
        # width-pinned: one physical chip): the cross-device audit
        # catches it, the ladder excludes exactly that chip, and the
        # survivors converge to D=2 oracle parity
        trace = []
        faulty = _run(lambda: TwoPhaseSys(3), capacity=1 << 12,
                      fmax=64, chunk_steps=2, mesh=_mesh(4), audit=1,
                      retries=2, backoff=0.0, trace=trace,
                      corrupt_hook=lambda o, d: 1 if d == 4 else None)
        _assert_parity(faulty, clean_2pc3_d2)
        prof = faulty.profile()
        assert prof["audit_mismatches"] >= 1
        assert prof["quarantined"] >= 1
        assert prof["degrades"] >= 1
        assert faulty._quarantined  # never granted again this run
        bad = [e for e in trace if e["ev"] == "audit"
               and e.get("mismatches")]
        assert bad and all(e["device"] == 1 for e in bad)

    def test_clean_audited_run_reports_zero_mismatches(
            self, clean_2pc3_single):
        audited = _run(lambda: TwoPhaseSys(3), capacity=1 << 12,
                       fmax=64, chunk_steps=2, audit=1)
        _assert_parity(audited, clean_2pc3_single)
        prof = audited.profile()
        assert prof["audits"] >= 1
        assert not prof.get("audit_mismatches")
        assert not prof.get("quarantined")

    def test_audit_off_default_adds_nothing(self, clean_2pc3_single):
        # satellite pin: audit=False (the default) must not change the
        # engine's behavior — no audit work, no new trace events, and
        # the reached set bit-identical to a plain run
        trace = []
        plain = _run(lambda: TwoPhaseSys(3), capacity=1 << 12,
                     fmax=64, chunk_steps=2, trace=trace)
        _assert_parity(plain, clean_2pc3_single)
        assert not plain.profile().get("audits")
        assert not [e for e in trace if e["ev"] in
                    ("audit", "corruption", "quarantine")]

    def test_audit_policy_mapping(self):
        from stateright_tpu.checker.resilience import AuditPolicy

        def pol(raw):
            return AuditPolicy.from_options({"audit": raw})

        assert pol(False).every == 0 and not pol(False).enabled
        assert pol(None).every == 0
        assert pol(True).every == 1
        assert pol(4).every == 4
        assert pol(0.25).every == 4  # a fraction: every 4th chunk
        assert not pol(False).should_audit(0)
        assert [o for o in range(6)
                if pol(2).should_audit(o)] == [0, 2, 4]
        with pytest.raises(ValueError):
            pol(-1)
        with pytest.raises(ValueError):
            pol(1.5)

    def test_symmetry_with_audit_is_explicit(self):
        def mk():
            return TwoPhaseSys(3, complete_symmetry=True)

        with pytest.raises(NotImplementedError, match="audit"):
            (mk().checker().symmetry_fn(mk().representative)
             .tpu_options(race=False, capacity=1 << 12, audit=1)
             .spawn_tpu().join())


class TestBenchAuditSmoke:
    @pytest.mark.slow
    def test_contract_line_lands_rc0(self):
        # ACCEPTANCE: --audit-smoke runs the lying-chip storyline and
        # ALWAYS lands a JSON contract line, rc=0; a full (non-partial)
        # round pins the catch + quarantine + oracle-parity claims
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py"),
             "--audit-smoke"],
            capture_output=True, text=True, timeout=420, env=env,
            cwd=REPO)
        assert proc.returncode == 0, proc.stderr[-2000:]
        contract = json.loads(proc.stdout.strip().splitlines()[-1])
        assert contract["audit"] is True
        assert contract["unit"] == "uniq/s"
        if "partial" not in contract:
            assert contract["audited"] is True
            assert contract["audits"] >= 1
            assert contract["audit_mismatches"] >= 1
            assert contract["quarantined"] >= 1


class TestWatchdog:
    def test_stalled_sync_becomes_classified_fault(self):
        # the hook stalls one chunk's sync well past the deadline: the
        # watchdog must convert the hang into a transient fault the
        # retry loop recovers from
        def hook(chunk):
            if chunk == 2:
                time.sleep(5.0)

        trace = []
        clean = _run(lambda: TwoPhaseSys(3), capacity=1 << 12, fmax=64,
                     chunk_steps=2)
        ck = _run(lambda: TwoPhaseSys(3), capacity=1 << 12, fmax=64,
                  chunk_steps=2, retries=2, backoff=0.0,
                  chunk_deadline=0.3, fault_hook=hook, trace=trace)
        _assert_parity(ck, clean)
        assert ck.profile()["retries"] >= 1
        evs = {e["ev"] for e in trace}
        assert "watchdog" in evs and "retry" in evs

    def test_invalid_deadline_rejected(self):
        with pytest.raises(ValueError, match="chunk_deadline"):
            (TwoPhaseSys(3).checker()
             .tpu_options(race=False, chunk_deadline=0).spawn_tpu())


class TestFailover:
    def test_raced_transient_failure_falls_over_to_host(self):
        # race budget 0 retires the budgeted racer immediately; the
        # device dies with a transient fault; the un-budgeted host BFS
        # fallback must still answer the check
        def hook(chunk):
            raise _unavailable("UNAVAILABLE: permanent backend death")

        ck = (TwoPhaseSys(4).checker()
              .tpu_options(capacity=1 << 12, race_budget=0.0,
                           fault_hook=hook)
              .spawn_tpu().join())
        host = TwoPhaseSys(4).checker().spawn_bfs().join()
        assert ck.unique_state_count() == host.unique_state_count()
        assert (ck.generated_fingerprints()
                == host.generated_fingerprints())
        prof = ck.profile()
        assert prof["engine"] == "host"
        assert prof["failovers"] == 1
        ck.assert_properties()

    def test_programming_error_still_surfaces(self):
        # TwoPhaseSys(4): big enough that the budgeted racer cannot
        # finish before the decider's first tick retires it — the
        # device's programming error must surface, not fail over
        def hook(chunk):
            raise ValueError("a genuine model bug")

        ck = (TwoPhaseSys(4).checker()
              .tpu_options(capacity=1 << 12, race_budget=0.0,
                           fault_hook=hook)
              .spawn_tpu())
        with pytest.raises(ValueError, match="model bug"):
            ck.join()

    def test_failover_opt_out(self):
        def hook(chunk):
            raise _unavailable()

        ck = (TwoPhaseSys(4).checker()
              .tpu_options(capacity=1 << 12, race_budget=0.0,
                           failover=False, fault_hook=hook)
              .spawn_tpu())
        with pytest.raises(RuntimeError, match="UNAVAILABLE"):
            ck.join()


def _run_bench(*flags):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--smoke",
         *flags],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)


def test_bench_degraded_tagging():
    # a degraded primary sample must tag the stdout contract line
    # ("degraded": true + final mesh size) so the perf trajectory can't
    # silently mix rates measured on fewer chips
    import bench

    class FakeCk:
        def profile(self):
            return {"degrades": 2, "mesh_shards": 2}

    class CleanCk:
        def profile(self):
            return {"chunks": 5}

    saved = dict(bench.DEGRADED)
    try:
        bench.DEGRADED.update(any=False, final_shards=None)
        assert bench._note_degraded(CleanCk()) == {}
        assert bench.DEGRADED["any"] is False
        assert bench._note_degraded(FakeCk()) == {}
        assert bench.DEGRADED == {"any": True, "final_shards": 2}
    finally:
        bench.DEGRADED.update(saved)


@pytest.mark.slow
class TestBenchContract:
    """bench.py must ALWAYS land a valid JSON contract line on stdout
    and exit 0 — the round-5 failure mode (rc=1, parsed=null) is
    pinned out in both the healthy and the all-device-workloads-dead
    shapes."""

    def test_smoke_contract_schema(self):
        proc = _run_bench()
        assert proc.returncode == 0, proc.stderr[-2000:]
        line = proc.stdout.strip().splitlines()[-1]
        payload = json.loads(line)
        for key in ("metric", "value", "unit", "vs_baseline", "backend",
                    "pipeline"):
            assert key in payload, key
        assert set(payload["pipeline"]) == {"on", "off"}
        assert payload["value"] is not None
        assert "partial" not in payload
        assert "init_fallback" not in payload  # backend came up clean
        # every workload row carries the fusion-proxy ratio and the
        # dedup-path tag, so the trajectory can't silently mix paths
        rows = [json.loads(ln) for ln in proc.stderr.splitlines()
                if ln.startswith("{")]
        samples = [r for r in rows if "samples" in r]
        assert samples
        for row in samples:
            assert "fused" in row, row["workload"]
            assert row["gen_per_uniq"] is None \
                or row["gen_per_uniq"] >= 1.0

    def test_backend_init_failure_exits_classified(self):
        # an unusable configured backend ends the bench non-zero with
        # the classified cause — it never moves to the CPU, so no CPU
        # number can land under a device metric's name
        env = dict(os.environ, JAX_PLATFORMS="definitely_not_a_backend")
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py"), "--smoke"],
            capture_output=True, text=True, timeout=600, env=env,
            cwd=REPO)
        assert proc.returncode == 2, proc.stderr[-2000:]
        assert not proc.stdout.strip()  # no contract line at all
        rows = [json.loads(ln) for ln in proc.stderr.splitlines()
                if ln.startswith("{")]
        fail = [r for r in rows if r.get("workload") == "backend"]
        assert len(fail) == 1
        assert fail[0]["cause"] in ("transient", "capacity",
                                    "programming")
        assert "definitely_not_a_backend" in fail[0]["error"]
        assert "backend init failed" in fail[0]["error"]

    def test_forced_failure_still_lands_artifact(self):
        proc = _run_bench("--inject-fault")
        assert proc.returncode == 0, proc.stderr[-2000:]
        line = proc.stdout.strip().splitlines()[-1]
        payload = json.loads(line)
        assert payload["partial"] is True
        assert isinstance(payload["failed"], list) and payload["failed"]
        assert "device-pipelined" in payload["failed"]


# --- elastic ladder, upward rung ---------------------------------------

class _Dev:
    """A stand-in ``jax.Device``: a global ``.id`` at a mesh position
    (the survivor-selection helpers never touch real hardware)."""

    def __init__(self, id):
        self.id = id

    def __repr__(self):
        return f"_Dev({self.id})"


class TestSurvivorHelpers:
    """The shared ladder arithmetic (checker/resilience.py): both
    ``degrade_step`` and ``promote_step`` resolve device references and
    pick survivor subsets through these, so the two directions cannot
    drift."""

    def test_match_device_by_object_then_id_then_position(self):
        devs = [_Dev(100), _Dev(101), _Dev(102)]
        assert match_device(devs, devs[1]) == 1      # object identity
        assert match_device(devs, 102) == 2          # global id
        assert match_device(devs, _Dev(100)) == 0    # foreign obj, .id
        assert match_device(devs, 1) == 1            # position fallback
        assert match_device(devs, None) is None
        assert match_device(devs, 999) is None
        assert match_device(devs, object()) is None  # no .id at all

    def test_select_survivors_single_host_drops_only_the_blamed_chip(
            self):
        devs = [_Dev(i) for i in range(4)]
        assert select_survivors(devs, 2, blamed_pos=3) == devs[:2]
        assert select_survivors(devs, 2, blamed_pos=0) == devs[1:3]
        assert select_survivors(devs, 2) == devs[:2]  # no blame: prefix

    def test_select_survivors_multi_host_drops_the_whole_host(self):
        # a blamed chip takes its HOST out (DCN partitions fault every
        # chip behind that NIC), keeping the halved mesh host-aligned
        devs = [_Dev(i) for i in range(4)]
        labels = ["a", "a", "b", "b"]
        assert select_survivors(devs, 2, blamed_pos=2,
                                labels=labels) == devs[:2]
        assert select_survivors(devs, 2, blamed_pos=1,
                                labels=labels) == devs[2:]

    def test_resolve_grant_dedups_and_excludes_the_current_mesh(self):
        universe = [_Dev(i + 100) for i in range(4)]
        got = resolve_grant(
            universe,
            [universe[2], 103, 0, 103, object()],  # obj, id, pos, dup
            exclude=(universe[0],))                # mesh already holds
        assert got == [universe[2], universe[3]]
        assert resolve_grant(universe, [999, None]) == []


@pytest.fixture(scope="module")
def clean_2pc3_d4():
    """One uninterrupted D=4 oracle run (the promote parity target)."""
    return _run(lambda: TwoPhaseSys(3), capacity=1 << 12, fmax=64,
                chunk_steps=2, mesh=_mesh(4))


def _promote_mid_run(ck, grant, timeout=180.0):
    """Drive ``ck`` one quantum, hand it ``grant``, and run to the end:
    the widening lands at the next chunk boundary, genuinely mid-run."""
    from stateright_tpu.service import RUNNING, StepDriver
    drv = StepDriver(ck).start()
    drv.step(1)
    ck.request_promote(list(grant))
    deadline = time.monotonic() + timeout
    while (drv.status == RUNNING and ck.promote_pending()
           and time.monotonic() < deadline):
        drv.step(1)
    drv.drain()
    return ck


class TestPromote:
    """Acceptance (elastic fleet): ``request_promote`` doubles a
    sharded run D=2 -> D=4 at a chunk boundary with discoveries and
    fingerprint sets bit-identical to an uninterrupted D=4 run,
    pipelined and synchronous; the widening composes with host-tier
    spill; and a run that degraded around a blame streak climbs BACK
    to its original width once the blamed chip is released healthy."""

    def test_promote_doubles_mesh_pipelined(self, clean_2pc3_d4):
        devices = jax.devices()
        if len(devices) < 4:
            pytest.skip("need 4 devices")
        trace = []
        ck = (TwoPhaseSys(3).checker()
              .tpu_options(race=False, capacity=1 << 12, fmax=64,
                           chunk_steps=2, retries=1, backoff=0.0,
                           mesh=_mesh(2), trace=trace)
              .spawn_tpu())
        _promote_mid_run(ck, devices[2:4])
        _assert_parity(ck, clean_2pc3_d4)
        prof = ck.profile()
        assert prof["promotes"] == 1
        assert prof["mesh_shards"] == 4
        promotes = [e for e in trace if e["ev"] == "promote"]
        assert len(promotes) == 1
        assert promotes[0]["from_shards"] == 2
        assert promotes[0]["to_shards"] == 4
        from stateright_tpu.obs import validate_event
        for ev in trace:
            validate_event(ev)

    def test_promote_doubles_mesh_sync(self, clean_2pc3_d4):
        devices = jax.devices()
        if len(devices) < 4:
            pytest.skip("need 4 devices")
        ck = (TwoPhaseSys(3).checker()
              .tpu_options(race=False, capacity=1 << 12, fmax=64,
                           chunk_steps=2, pipeline=False, retries=1,
                           backoff=0.0, mesh=_mesh(2))
              .spawn_tpu())
        _promote_mid_run(ck, devices[2:4])
        _assert_parity(ck, clean_2pc3_d4)
        prof = ck.profile()
        assert prof["promotes"] == 1
        assert prof["mesh_shards"] == 4

    @pytest.mark.slow
    def test_promote_composes_with_spill(self):
        # a budget-capped D=2 run spills to the host tier, THEN the
        # grant doubles the mesh and the run finishes wide — parity
        # (set semantics: shapes differ) vs an uncapped clean D=4 run
        devices = jax.devices()
        if len(devices) < 4:
            pytest.skip("need 4 devices")
        from stateright_tpu.service import RUNNING, StepDriver
        spilled = (TwoPhaseSys(4).checker()
                   .tpu_options(race=False, capacity=1 << 11,
                                max_capacity=1 << 11, fmax=8, kmax=64,
                                chunk_steps=2, retries=1, backoff=0.0,
                                mesh=_mesh(2))
                   .spawn_tpu())
        drv = StepDriver(spilled).start()
        deadline = time.monotonic() + 180.0
        while (drv.status == RUNNING
               and not spilled.profile().get("spills")
               and time.monotonic() < deadline):
            drv.step(1)
        spilled.request_promote(devices[2:4])
        drv.drain()
        clean = _run(lambda: TwoPhaseSys(4), capacity=1 << 12, fmax=16,
                     chunk_steps=2, mesh=_mesh(4))
        assert spilled.unique_state_count() == clean.unique_state_count()
        assert (set(spilled.generated_fingerprints())
                == set(clean.generated_fingerprints()))
        prof = spilled.profile()
        assert prof["promotes"] == 1
        assert prof["mesh_shards"] == 4
        assert prof["spills"] >= 1

    def test_degrade_then_promote_roundtrip(self, clean_2pc3_d4):
        # REGRESSION (elastic fleet): D=4 drops to D=2 on a transient
        # blame streak, then climbs back 2 -> 4 when the blamed chip is
        # released healthy — bit-identical to an uninterrupted D=4 run
        devices = jax.devices()
        if len(devices) < 4:
            pytest.skip("need 4 devices")

        faults = {"n": 0}

        def flaky(chunk, shards):
            # exactly two faults naming one chip: a blame streak at
            # D=4, inert afterwards so the climb back up stays clean
            if shards == 4 and faults["n"] < 2:
                faults["n"] += 1
                raise RuntimeError(
                    "UNAVAILABLE: device 3 fell off the mesh "
                    "(injected)")

        from stateright_tpu.service import RUNNING, StepDriver
        trace = []
        ck = (TwoPhaseSys(3).checker()
              .tpu_options(race=False, capacity=1 << 12, fmax=64,
                           chunk_steps=2, retries=5, backoff=0.0,
                           blame_after=2, mesh=_mesh(4),
                           fault_hook=flaky, trace=trace)
              .spawn_tpu())
        drv = StepDriver(ck).start()
        deadline = time.monotonic() + 180.0
        while (drv.status == RUNNING
               and not ck.profile().get("degrades")
               and time.monotonic() < deadline):
            drv.step(1)
        assert ck.profile()["degrades"] == 1  # narrowed, still running
        # the blamed chip comes back: grant the dropped half back
        held = list(ck._mesh.devices.flat)
        gone = [d for d in devices[:4] if d not in held]
        assert len(gone) == 2
        ck.request_promote(gone)
        drv.drain()
        _assert_parity(ck, clean_2pc3_d4)
        prof = ck.profile()
        assert prof["degrades"] == 1
        assert prof["promotes"] == 1
        assert prof["mesh_shards"] == 4
        kinds = [e["ev"] for e in trace]
        assert kinds.index("degrade") < kinds.index("promote")
        from stateright_tpu.obs import validate_event
        for ev in trace:
            validate_event(ev)
